"""Foundations: error model, env-var config registry, dtype maps.

TPU-native rebuild of the roles played in the reference by dmlc-core
(logging/CHECK macros, `dmlc::GetEnv` env-var config — SURVEY.md §5.6) and
`python/mxnet/base.py` (error propagation, name managers).  There is no C ABI
here: the "core" is JAX/XLA, so errors are plain Python exceptions and the
config registry is a typed view over ``os.environ``.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

import numpy as _np

__all__ = [
    "MXNetError",
    "is_channels_last",
    "register_env",
    "get_env",
    "list_env",
    "hot_path",
    "string_types",
    "numeric_types",
    "integer_types",
    "dtype_np",
    "dtype_name",
    "default_dtype",
]

string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)


class MXNetError(RuntimeError):
    """Default error type for this framework.

    Mirrors the reference's ``mxnet.base.MXNetError`` which surfaces C-side
    ``dmlc::Error``; here errors originate in Python/JAX directly.
    """


def hot_path(kind: str) -> Callable:
    """Marker decorator: this function is a hot-path ROOT for mxlint's
    interprocedural rules.  Zero runtime cost (returns the function
    unchanged, tagged); the lint reads the decoration statically.

    ``kind``:
      - ``"dispatch"`` — the per-op dispatch/flush path (engine push,
        bulk-segment defer/flush).  Code reachable from here must stay
        PURE: no allocation, env reads, lock creation, or logging
        (rule ``hot-path-purity``), and must not hide host syncs
        (rule ``hidden-host-sync``).
      - ``"step"`` — the per-step training/serving path.  Allocation is
        fine here (checkpointing etc.), but hidden host syncs
        (``.asnumpy()``/``.item()``/value casts on device arrays) still
        serialize the async engine and are flagged.
    """
    if kind not in ("dispatch", "step"):
        raise ValueError(f"hot_path kind must be 'dispatch' or 'step', "
                         f"got {kind!r}")

    def mark(fn):
        fn.__mxlint_hot_path__ = kind
        return fn
    return mark


_CHANNELS_LAST = {"NWC": 1, "NHWC": 2, "NDHWC": 3}


def is_channels_last(layout, ndim=None):
    """True for the channels-last conv/pool layouts (NWC/NHWC/NDHWC).
    With ``ndim`` given, a rank-mismatched layout string raises instead
    of being silently remapped."""
    if layout not in _CHANNELS_LAST:
        return False
    if ndim is not None and _CHANNELS_LAST[layout] != ndim:
        raise MXNetError(
            f"layout {layout!r} is for {_CHANNELS_LAST[layout]}d "
            f"convolution/pooling, got {ndim}d")
    return True


def force_cpu_mesh(n_devices: int, verify: bool = True) -> None:
    """Force jax onto a virtual ``n_devices``-device CPU mesh.

    Must run before the first jax backend query.  Two steps:

    1. ``XLA_FLAGS --xla_force_host_platform_device_count=n`` — rewritten
       in place if a different count is already present and the backend is
       not yet initialized.
    2. ``jax.config.update("jax_platforms", "cpu")`` — holds whatever
       ``JAX_PLATFORMS`` says, so a test process on a TPU host stays off
       the chip.

    Used by ``tests/conftest.py`` and ``__graft_entry__.dryrun_multichip``.
    """
    import re

    flag = f"--xla_force_host_platform_device_count={n_devices}"
    flags = os.environ.get("XLA_FLAGS", "")
    flags, n_sub = re.subn(
        r"--xla_force_host_platform_device_count[= ]\S+", flag, flags)
    if not n_sub:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    if not verify:
        # caller must do something that must precede the first backend
        # query (e.g. jax.distributed.initialize) — skip the device check
        return
    devs = jax.devices()
    if devs[0].platform != "cpu":
        raise MXNetError(
            f"force_cpu_mesh: platform is {devs[0].platform!r}, not cpu — "
            "a jax backend was already initialized before this call")
    if len(devs) < n_devices:
        raise MXNetError(
            f"force_cpu_mesh: requested {n_devices} devices but only "
            f"{len(devs)} are visible — XLA_FLAGS was read before it could "
            "be rewritten (jax backend initialized too early)")


# ---------------------------------------------------------------------------
# Environment-variable config registry (reference: ~100 MXNET_* vars read via
# dmlc::GetEnv, documented in docs/faq/env_var.md — SURVEY.md §5.6).
# ---------------------------------------------------------------------------

class _EnvEntry:
    __slots__ = ("name", "default", "typ", "help")

    def __init__(self, name: str, default: Any, typ: Callable, help: str):
        self.name = name
        self.default = default
        self.typ = typ
        self.help = help


_env_registry: Dict[str, _EnvEntry] = {}
_env_lock = threading.Lock()


def _parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("1", "true", "yes", "on")


def register_env(name: str, default: Any, typ: Callable = str, help: str = "") -> None:
    """Register an ``MXNET_*`` style environment variable with a typed default."""
    if typ is bool:
        typ = _parse_bool
    with _env_lock:
        _env_registry[name] = _EnvEntry(name, default, typ, help)


def get_env(name: str, default: Any = None) -> Any:
    """Read a registered env var, applying its type; unregistered names fall
    back to raw ``os.environ`` access with ``default``."""
    entry = _env_registry.get(name)
    raw = os.environ.get(name)
    if entry is None:
        return raw if raw is not None else default
    if raw is None:
        return entry.default
    try:
        return entry.typ(raw)
    except (TypeError, ValueError):
        return entry.default


def list_env() -> Dict[str, Any]:
    """All registered env vars with their current effective values."""
    return {k: get_env(k) for k in sorted(_env_registry)}


# Core knobs (subset of the reference's env_var.md; registered at import so
# `list_env()` documents them).
register_env("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice", str,
             "Engine type: NaiveEngine (sync, debug) or ThreadedEnginePerDevice (async).")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", True, bool,
             "Fuse op sequences into bulked dispatch segments (maps to jit).")
register_env("MXNET_ENGINE_BULK_SIZE", 15, int,
             "Max ops per bulked dispatch segment before a forced flush.")
register_env("MXNET_ENGINE_BULK_FUSE", "exact", str,
             "Bulk segment codegen: 'exact' (one dispatch, per-op kernels, "
             "bitwise-identical to unbulked) or 'aggressive' (full XLA "
             "fusion incl. taped segments; FMA contraction may shift "
             "results by ~1 ulp).")
register_env("MXNET_TEST_SEED", None, int, "Seed override for the test harness.")
register_env("MXNET_SAFE_ACCUMULATION", True, bool,
             "Accumulate fp16/bf16 reductions in fp32.")
register_env("MXNET_DEFAULT_DTYPE", "float32", str,
             "Default dtype for new arrays (float32; set bfloat16 for TPU-native).")
register_env("MXNET_MATMUL_PRECISION", "", str,
             "jax matmul precision override; 'highest' forces full fp32 "
             "accumulation (reference-exact numerics; a float32 matmul "
             "then takes several bfloat16 passes).")
register_env("MXNET_OPTIMIZER_AGGREGATION_SIZE", 4, int,
             "Max weights updated per fused multi-tensor optimizer call.")
register_env("MXNET_TEST_DEFAULT_CTX", "", str,
             "Context the test harness runs in, e.g. 'tpu(0)' "
             "(the import-and-rerun TPU suite sets it).")
register_env("MXNET_PALLAS_INTERPRET", False, bool,
             "Run Pallas kernels in interpret mode (CPU-testable kernels).")
register_env("MXNET_ATTENTION_KERNEL", "auto", str,
             "Attention path: 'auto' (flash when eligible), 'flash' "
             "(force the Pallas kernel), or 'xla' (full-softmax XLA path).")
register_env("MXTPU_DIST_TIMEOUT", 300.0, float,
             "Per-attempt timeout (seconds) for joining the process group "
             "and for the coordination-service KV/barrier collectives.")
register_env("MXTPU_FAULT_PLAN", "", str,
             "Deterministic fault-injection schedule, e.g. "
             "'step_error@3;nan@5;ckpt_fail@2;loader_stall@4:1.5'.")
register_env("MXTPU_METRICS_PORT", "", str,
             "Serve the Prometheus /metrics endpoint on this port "
             "(unset = no HTTP server).")
register_env("MXTPU_METRICS_JSONL", "", str,
             "Append periodic registry snapshots to this JSONL path "
             "(unset = no writer).")
register_env("MXTPU_METRICS_INTERVAL", 60.0, float,
             "Seconds between JSONL metric snapshots.")
register_env("MXTPU_METRICS_AGGREGATE", False, bool,
             "Serve the fleet (all-hosts) view from /metrics, every "
             "series host-labeled; refreshed at checkpoint boundaries.")
register_env("MXTPU_FLIGHT_STEPS", 256, int,
             "Crash flight-recorder ring capacity in steps (0 disables).")
register_env("MXTPU_FLIGHT_PATH", "", str,
             "Crash flight-recorder dump file "
             "(default <tmpdir>/mxtpu_flight_<pid>.json).")
register_env("MXTPU_SERVING_MAX_BATCH", 8, int,
             "Serving: max requests fused into one batched CachedOp "
             "call; batch buckets are powers of two up to this.")
register_env("MXTPU_SERVING_QUEUE_DEPTH", 256, int,
             "Serving: admission-queue bound; submits beyond it are "
             "rejected with ServerOverloaded (the HTTP-429 analog).")
register_env("MXTPU_SERVING_DEADLINE_MS", 100.0, float,
             "Serving: default per-request deadline; requests still "
             "queued when it expires are rejected at batch assembly "
             "(429-style). 0 disables.")
register_env("MXTPU_SERVING_WORKERS", 2, int,
             "Serving: dispatch worker threads; >1 lets batch "
             "formation overlap device execution.")
register_env("MXTPU_SERVING_BATCH_WINDOW_US", 2000.0, float,
             "Serving: how long the batcher waits for the current "
             "shape bucket to fill before dispatching a partial batch. "
             "Read live per batch, so the BatchWindowController (and "
             "operators) can adapt it on a running server.")
register_env("MXTPU_SERVING_KV_BLOCK", 16, int,
             "Serving: KV-cache block size in token positions; the "
             "paging granularity of the generation scheduler's block "
             "manager (serving.kv_cache).")
register_env("MXTPU_SERVING_KV_BLOCKS", 128, int,
             "Serving: total KV-cache blocks pre-allocated per "
             "generation server (block 0 is reserved scratch, so "
             "usable capacity is one less).  Admission to the running "
             "batch gates on a worst-case block reservation against "
             "this pool.")
register_env("MXTPU_SERVING_DECODE_SLOTS", 4, int,
             "Serving: running-batch slot count of the iteration-level "
             "decode scheduler — how many requests decode together in "
             "one compiled decode step.  Recompile-costly; the "
             "DecodeSlotController hill-climbs it between generations.")
register_env("MXTPU_SERVING_PREFILL_MODE", "interleave", str,
             "Serving: 'interleave' admits at most one prompt prefill "
             "per decode iteration (smooth decode cadence); 'step' "
             "prefills every admissible queued request before the next "
             "decode step (fastest drain of a burst).  Read live per "
             "iteration.")
register_env("MXTPU_SERVING_MAX_NEW_TOKENS", 64, int,
             "Serving: default cap on generated tokens per request "
             "when submit_generate() is not given max_new_tokens; also "
             "bounds the worst-case KV block reservation.")
register_env("MXTPU_FRONTEND_PORT", "", str,
             "Serving: TCP port for the multi-model HTTP frontend "
             "(mxnet_tpu.serving.HttpFrontend — JSON predict, SSE "
             "token streaming, W3C traceparent).  Empty (default) "
             "binds an ephemeral port; the frontend only listens when "
             "constructed explicitly.")
register_env("MXTPU_FRONTEND_PRIORITY", 0, int,
             "Serving: default priority for models loaded into the "
             "ModelRegistry without an explicit one (higher = more "
             "important; models below the registry shed level are "
             "429'd at the door).")
register_env("MXTPU_FRONTEND_SLO_MS", 0.0, float,
             "Serving: default per-model p99 latency SLO in ms for "
             "models loaded without an explicit slo_ms — the budget "
             "the SloController defends (0 = no SLO, never watched).")
register_env("MXTPU_TUNE_SLO", True, bool,
             "Self-tuning: enable the SloController (watches each "
             "registered model's socket-to-socket request p99 against "
             "its SLO; sheds lowest-priority-first via the registry "
             "gate and scales the violator's dispatch workers).  "
             "Per-registry instance surface: attach it explicitly.")
register_env("MXTPU_TUNE_DECODE_SLOTS", False, bool,
             "Self-tuning: enable the DecodeSlotController (hill-climbs "
             "MXTPU_SERVING_DECODE_SLOTS on interval tokens/s with the "
             "bracketing stop; recompiles are the cost, so it parks at "
             "the bracketed best).  Off by default: attach it to a "
             "generation server explicitly.")
register_env("MXTPU_TUNE_INTERVAL", 2.0, float,
             "Self-tuning: seconds between controller timer-thread "
             "ticks (mxnet_tpu.tuning).")
register_env("MXTPU_TUNE_DRY_RUN", False, bool,
             "Self-tuning: compute and record every controller "
             "decision (tuning.* metrics + flight ring) but apply "
             "nothing — the observe-before-trust mode.")
register_env("MXTPU_TUNE_BULK", True, bool,
             "Self-tuning: enable the BulkSizeController "
             "(hill-climbs MXNET_ENGINE_BULK_SIZE from the live "
             "engine.flush_us histogram) when the runtime starts.")
register_env("MXTPU_TUNE_PREFETCH", True, bool,
             "Self-tuning: enable the PrefetchController (adapts the "
             "DataLoader prefetch depth from the loader.prefetch_depth "
             "gauge) when the runtime starts.")
register_env("MXTPU_TUNE_BATCH_WINDOW", True, bool,
             "Self-tuning: enable the BatchWindowController (adapts "
             "MXTPU_SERVING_BATCH_WINDOW_US from serving.queue_depth "
             "and serving.request_us p99) when the runtime starts.")
register_env("MXTPU_TUNE_FLEET_GATHER", True, bool,
             "Self-tuning: enable the FleetGatherController (streams "
             "the multi-host metric gather over the barrier-free "
             "KV-store transport on the timer thread) when the runtime "
             "starts in an initialized process group.")
register_env("MXTPU_COMPILE_CACHE_DIR", "", str,
             "Persistent compilation cache directory: exact-mode bulk "
             "segments and HybridBlock cached-graph executables are "
             "serialized here and reloaded by later processes, so a "
             "restart (auto-resume, server cold start) skips the XLA "
             "compile.  JAX_COMPILATION_CACHE_DIR, where set, takes its "
             "place (entries under <that>/mxnet_tpu).  Neither set "
             "disables.")
register_env("MXTPU_COMPILE_CACHE_JAX", True, bool,
             "With MXTPU_COMPILE_CACHE_DIR set (and "
             "JAX_COMPILATION_CACHE_DIR not), also point jax's own "
             "persistent compilation cache at <dir>/jax so plain "
             "jax.jit paths (per-op fns, training vjp graphs) reuse "
             "compiles across processes too.")
register_env("MXTPU_ELASTIC", False, bool,
             "Elastic-fleet mode for init_process_group: raises the "
             "coordination service's own task-heartbeat tolerance to "
             "effectively-forever so a dead host does NOT make the "
             "service propagate a fatal error that terminates every "
             "survivor (~100s after the death, with jax defaults).  "
             "Liveness then belongs solely to the membership lease "
             "layer (parallel.membership), which detects the loss "
             "within MXTPU_ELASTIC_LEASE_TTL and re-forms.  Leave off "
             "for non-elastic jobs, where whole-fleet fail-fast on a "
             "dead host is the desired behavior.")
register_env("MXTPU_ELASTIC_LEASE_TTL", 10.0, float,
             "Elastic-fleet membership lease TTL in seconds: a host "
             "whose heartbeat lease has not advanced for this long (on "
             "the OBSERVER's clock — no cross-host clock trust) is "
             "declared dead and the survivors re-form.  Lower = faster "
             "host-loss detection, higher = more tolerance for GC/IO "
             "pauses.")
register_env("MXTPU_ELASTIC_HEARTBEAT", 2.0, float,
             "Elastic-fleet heartbeat publish interval in seconds "
             "(should be several times smaller than "
             "MXTPU_ELASTIC_LEASE_TTL so one dropped publish never "
             "reads as a death).")
register_env("MXTPU_ELASTIC_COORD_LINGER", 8.0, float,
             "Seconds a dirty-detaching process that HOSTS the "
             "coordination service lingers before its final os._exit: "
             "the service's death severs every peer's fabric mid-RPC "
             "(jax's error polling then aborts them), so the "
             "coordinator gives peers still wrapping up — or a fenced "
             "host still discovering its exclusion — time to exit "
             "with their own clean codes first.")
register_env("MXTPU_ELASTIC_REFORM_TIMEOUT", 60.0, float,
             "Wall-clock budget in seconds for one fleet re-form round "
             "(view exchange, plan, acks, commit).  A survivor that "
             "cannot complete the round within it raises FleetLost "
             "instead of waiting forever on a fleet that cannot agree.")
register_env("MXTPU_PREEMPT_COORD", True, bool,
             "Coordinated preemption checkpoints: in a multi-process "
             "group, a SIGTERM'd ResilientTrainer publishes a flush "
             "vote over the coordination-service KV tier (no device "
             "collective) and every host commits the SAME state-<t> "
             "checkpoint — the agreed step is the max of all hosts' "
             "votes.  Off = each host flushes unilaterally at its own "
             "step (the pre-coordination behavior).")
register_env("MXTPU_PREEMPT_POLL", 0.05, float,
             "Poll interval in seconds for the preemption-coordination "
             "vote wait (bounded overall by MXTPU_DIST_TIMEOUT, after "
             "which the host falls back to a unilateral flush).")
register_env("MXTPU_COMM_BUCKET_MB", 0.0, float,
             "Bucketed gradient reduce-scatter for ShardedTrainer: "
             "split the step's gradients into buckets of at most this "
             "many MB (in reverse parameter order — the order backward "
             "materializes them) and pin each bucket's dp-reduction "
             "with an optimization_barrier-ordered sharding "
             "constraint, so XLA's latency-hiding scheduler can "
             "overlap the per-bucket collectives with the remaining "
             "backward compute.  0 (the default) = one fused "
             "reduction after the full backward — bitwise-identical "
             "to the pre-bucketing step.  The comm_bucket_mb= "
             "constructor argument overrides.")
register_env("MXTPU_DEVICE_PREFETCH", 0, int,
             "DataLoader device-input double buffering: keep up to N "
             "batches resident on device beyond the one being "
             "consumed, transferred through an async jax.device_put "
             "stage (sharding-aware when a ShardedTrainer's "
             "place_batch is attached), so step t's jit consumes an "
             "already-resident batch while t+1 transfers.  0 (the "
             "default) = off: every step pays the host->device "
             "ingestion transfer on the critical path.  The "
             "device_prefetch= constructor argument overrides; "
             "applied at each __iter__.")
register_env("MXTPU_ASYNC_CKPT", False, bool,
             "Async distributed checkpoints: the host-local npz "
             "checkpoint write (the multi-process fleet path) "
             "snapshots state at the step boundary and commits on a "
             "background thread, and the coordinated-preemption KV "
             "vote wait moves off the step path (hosts keep stepping "
             "toward the highest vote seen while the round resolves). "
             "Committed-dir semantics are unchanged: a crash mid-"
             "write leaves a torn tmp dir that resume filters out.  "
             "Off (the default) = the blocking PR-10 flush.")
register_env("MXTPU_SPARSE_GRAD", True, bool,
             "Row-sparse embedding gradients inside the sharded step: "
             "an Embedding(sparse_grad=True) produces its gradient as "
             "(values, unique_ids) via an in-graph segment-sum over "
             "the batch's deduplicated ids, and SGD/Adam lazy updates "
             "gather/update/scatter only the live rows — per-step "
             "update cost scales with batch-unique ids, not vocab.  "
             "Off = such embeddings fall back to dense gradients "
             "(bitwise the pre-sparse step).")
register_env("MXTPU_SPARSE_ID_BUCKET", 0, int,
             "Fixed id-bucket capacity for the sparse embedding "
             "gradient path (rounded up to a power of 2).  0 (the "
             "default) sizes the bucket per compiled batch shape: the "
             "next power of 2 >= the batch's id count.  Setting it "
             "larger pins ONE bucket size across varying batch "
             "shapes (one compiled step); a value smaller than a "
             "batch's id count is clamped up to that batch's own "
             "bucket — capacity below the id count could drop rows.")
register_env("MXTPU_SPARSE_EXCHANGE", True, bool,
             "Coalesced cross-worker exchange for row-sparse "
             "gradients in the gluon Trainer: workers allgather "
             "(ids, rows) pairs over dist.allgather_rows and "
             "dedup+sum on the host (the modern ps-lite push/pull) "
             "instead of allreducing the dense matrix.  Off = sparse "
             "grads densify before the wire.")
register_env("MXTPU_TUNE_COMM_BUCKET", True, bool,
             "Self-tuning: enable the CommBucketController (hill-"
             "climbs a ShardedTrainer's MXTPU_COMM_BUCKET_MB on the "
             "resilience.step_us interval mean) when one is "
             "constructed with a trainer.  Not in the stock runtime "
             "set — it needs a live trainer reference.")
register_env("MXTPU_TRACE", False, bool,
             "Causal tracing: record request/step span trees with "
             "W3C-style trace/span ids (observability.tracing), "
             "propagate contexts through serving batches, training "
             "steps, and the coordination-service KV tier, and attach "
             "trace-id exemplars to every histogram bucket.  Off (the "
             "default) = the instrumented paths pay one memoized env "
             "probe and nothing else.")
register_env("MXTPU_TRACE_SAMPLE", 1, int,
             "Causal tracing head sampling: start a new ROOT trace for "
             "1 in N sampling decisions (1 = trace every root; "
             "children of a sampled trace are always recorded, so "
             "traces stay whole).  Fleet-lockstep roots (training "
             "steps) sample deterministically on the step index, so "
             "every host keeps or drops the same step.")
register_env("MXTPU_TRACE_RING", 2048, int,
             "Causal tracing: bounded ring capacity of completed spans "
             "kept in memory for exemplar resolution, chrome-trace "
             "export, and crash dumps (resolved when tracing first "
             "switches on).")
register_env("MXTPU_TRACE_JSONL", "", str,
             "Causal tracing: append completed spans to this JSONL "
             "path (size-rotated, buffered ~64 spans per write; one "
             "file per host — concatenate hosts' files and feed "
             "tracing.chrome_trace_from_spans for a cross-host "
             "timeline).  Unset disables the stream; the in-memory "
             "ring always records.")
register_env("MXTPU_TUNE_DEVICE_PREFETCH", True, bool,
             "Self-tuning: enable the DevicePrefetchController "
             "(adapts the DataLoader device-prefetch depth from the "
             "loader.device_buffer_depth gauge — each slot is a "
             "resident device batch, i.e. HBM) when the runtime "
             "starts.")
register_env("MXTPU_PROF_SAMPLE_HZ", 0.0, float,
             "Continuous stack-sampling profiler: walk every thread's "
             "frames (sys._current_frames) this many times per second, "
             "folding them into collapsed-stack (flamegraph) counts in "
             "rotating profile windows.  0 (the default) = off; the "
             "off path on instrumented start sites is one memoized "
             "env probe.")
register_env("MXTPU_PROF_WINDOW_SECS", 60.0, float,
             "Stack sampler: seconds of samples per profile window "
             "before it rotates into the bounded window ring "
             "(/debug/profile and watchdog postmortems serve the "
             "current + recent windows).")
register_env("MXTPU_PROF_WINDOWS", 8, int,
             "Stack sampler: how many rotated profile windows to keep "
             "(a bounded ring — memory is bounded by windows x "
             "distinct folded stacks per window).")
register_env("MXTPU_DEBUG_ENDPOINTS", False, bool,
             "Serve the live-introspection /debug/* surface "
             "(/debug/stacks, /debug/profile, /debug/flight, "
             "/debug/trace/<id>, /debug/vars) from the serving "
             "HttpFrontend and the MXTPU_METRICS_PORT exporter.  Off "
             "(the default) = those paths 404; the endpoints are "
             "auth-free, so only enable them on trusted networks.")
register_env("MXTPU_WATCHDOG_FACTOR", 0.0, float,
             "Progress watchdog: flag a heartbeat touchpoint (trainer "
             "step, decode loop, dispatch workers) as stalled when it "
             "goes silent for FACTOR x its own recent p99 interval "
             "(from the metrics spine), then dump one postmortem "
             "bundle (stacks + flight rings + span ring + profile "
             "window).  0 (the default) = off; typical values 4-10.")
register_env("MXTPU_WATCHDOG_ACTION", "dump", str,
             "Progress watchdog action on a detected stall: 'dump' "
             "(write the postmortem bundle and keep running) or "
             "'term' (dump, then SIGTERM the process so the existing "
             "drain/checkpoint handlers take over).")
register_env("MXTPU_STACKS_SIGNAL", "SIGQUIT", str,
             "Signal that dumps all-thread stacks + flight rings to "
             "the flight path WITHOUT killing the process (the manual "
             "'what is it doing right now' probe; chains any previous "
             "handler).  Named signal (SIGQUIT, SIGUSR2, ...); empty "
             "disables installation.")


# ---------------------------------------------------------------------------
# Dtypes
# ---------------------------------------------------------------------------

_DTYPE_ALIASES: Dict[str, str] = {
    "float32": "float32", "float64": "float64", "float16": "float16",
    "bfloat16": "bfloat16", "uint8": "uint8", "int8": "int8",
    "int32": "int32", "int64": "int64", "int16": "int16", "uint16": "uint16",
    "uint32": "uint32", "uint64": "uint64", "bool": "bool",
}


def dtype_np(dtype: Any) -> "_np.dtype":
    """Canonicalize a dtype spec (str / np.dtype / jnp dtype) to np.dtype.

    bfloat16 round-trips via ml_dtypes (numpy has no native bfloat16).
    """
    if dtype is None:
        return _np.dtype(default_dtype())
    if isinstance(dtype, str):
        name = _DTYPE_ALIASES.get(dtype)
        if name is None:
            raise MXNetError(f"unknown dtype {dtype!r}")
        if name == "bfloat16":
            import ml_dtypes
            return _np.dtype(ml_dtypes.bfloat16)
        return _np.dtype(name)
    return _np.dtype(dtype)


def jax_compute_dtype(dtype: Any) -> "_np.dtype":
    """The dtype jax will actually store: under the int32 default
    (``runtime.enable_large_tensor()`` off), 64-bit requests map to their
    32-bit duals — the DOCUMENTED large-tensor truncation contract
    (runtime.py), applied explicitly here so jax never emits its
    truncation UserWarning on the library's own paths."""
    d = dtype_np(dtype)
    import jax
    if not jax.config.jax_enable_x64 and d.itemsize == 8 \
            and d.kind in "iuf":
        return _np.dtype({"i": _np.int32, "u": _np.uint32,
                          "f": _np.float32}[d.kind])
    return d


def dtype_name(dtype: Any) -> str:
    """Canonical string name for a dtype."""
    d = _np.dtype(dtype) if not isinstance(dtype, str) else dtype_np(dtype)
    return str(d.name) if d.name != "bfloat16" else "bfloat16"


def default_dtype() -> str:
    return get_env("MXNET_DEFAULT_DTYPE")


def resolve_reshape_spec(in_dims, spec, reverse=False):
    """Resolve MXNet reshape specials (src/operator/tensor/matrix_op-inl.h):
    0 = copy input dim, -1 = infer, -2 = copy all remaining dims,
    -3 = merge next two dims, -4 d1 d2 = split one dim into (d1, d2).
    ``reverse=True`` applies the rules right-to-left.  The single source of
    truth for both the reshape op and the NDArray.reshape view path."""
    in_dims = list(in_dims)
    spec = [int(s) for s in spec]
    # group multi-token units so reverse mode can't split a -4 triple
    units = []
    j = 0
    while j < len(spec):
        if spec[j] == -4:
            units.append(spec[j:j + 3])
            j += 3
        else:
            units.append([spec[j]])
            j += 1
    if reverse:
        # mirror both sides; a -4's operands swap roles in the mirror
        units = [([-4, u[2], u[1]] if u[0] == -4 else u)
                 for u in units[::-1]]
        in_dims = in_dims[::-1]
    out = []
    i = 0
    for u in units:
        s = u[0]
        if s == 0:
            out.append(in_dims[i])
            i += 1
        elif s == -2:
            out.extend(in_dims[i:])
            i = len(in_dims)
        elif s == -3:
            out.append(in_dims[i] * in_dims[i + 1])
            i += 2
        elif s == -4:
            d1, d2 = u[1], u[2]
            cur = in_dims[i]
            if d1 == -1:
                d1 = cur // d2
            if d2 == -1:
                d2 = cur // d1
            out.extend([d1, d2])
            i += 1
        elif s == -1:
            out.append(-1)
            i += 1
        else:
            out.append(s)
            i += 1
    if reverse:
        out = out[::-1]
    if -1 in out:
        known = 1
        for s in out:
            if s != -1:
                known *= s
        total = 1
        for s in in_dims:
            total *= s
        out[out.index(-1)] = total // max(known, 1)
    return tuple(out)


def rnn_packed_param_count(mode: str, input_size: int, hidden: int,
                           num_layers: int, bidirectional: bool) -> int:
    """Length of the packed cuDNN-layout RNN parameter vector (shared by
    symbol shape inference and mx.rnn.FusedRNNCell so the two can never
    disagree): per layer, per direction: Wx, Wh, bx, bh."""
    ngates = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}[mode]
    ndir = 2 if bidirectional else 1
    total = 0
    layer_in = input_size
    for _ in range(num_layers):
        total += ndir * (ngates * hidden * layer_in
                         + ngates * hidden * hidden + 2 * ngates * hidden)
        layer_in = hidden * ndir
    return total
