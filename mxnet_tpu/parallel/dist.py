"""Multi-process distributed runtime (the DCN story).

Reference parity: ps-lite's process bootstrap — workers/servers wired up
from ``DMLC_*`` environment variables set by the launcher (SURVEY.md §2.3
ps-lite row, §5.8).  TPU-native replacement: no parameter server; all
processes join one JAX coordination service (`jax.distributed.initialize`)
and gradient reduction rides XLA collectives / host allgather over DCN.

The same launcher env-var names are honored so reference launch scripts
carry over:

- ``DMLC_PS_ROOT_URI`` / ``DMLC_PS_ROOT_PORT`` — coordinator address
  (reference: the ps-lite scheduler address).
- ``DMLC_NUM_WORKER`` — total number of worker processes.
- ``DMLC_WORKER_ID`` — this process's rank (assigned by the launcher).

``dist_async`` has no analog here by design: synchronous SPMD replaces
stale parameter-server updates (SURVEY.md §5.8).

**The collectivity contract is machine-checked.**  Every public entry
point here (``allgather_*``, ``allreduce_host``, ``broadcast_host``,
``barrier``) must be reached by EVERY process or by none — the KV-path
generation counters below depend on per-process call counts staying in
lockstep, and a rank that skips a collective wedges the fleet until
the DCN timeout.  mxlint's ``collective-safety`` rule enforces this
repo-wide and *interprocedurally*: a call to one of these functions —
or to any helper that transitively reaches one, resolved through the
project call graph — from under a branch conditioned on
``rank``/``process_index``/``host_id``/... is a lint failure carrying
the call chain as evidence.  Branch on fleet-uniform state only
(``is_initialized()``, ``num_workers()``); the deterministic
backend-capability fallbacks inside this module (every rank takes the
same branch) are the sanctioned pattern.
"""
from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

from ..base import MXNetError, get_env, hot_path
from . import _coordination

__all__ = ["init_process_group", "is_initialized", "rank", "num_workers",
           "phys_rank", "active_members", "fence_generation",
           "set_active_members", "reset_active_members",
           "allreduce_host", "allgather_host", "allgather_bytes",
           "allgather_rows", "dedup_sum_rows",
           "reduce_scatter_host", "broadcast_host", "barrier",
           "kv_publish", "kv_collect", "kv_purge_rank"]


def is_initialized() -> bool:
    """True if this process has joined a multi-process JAX runtime."""
    import jax
    return jax.distributed.is_initialized()   # touches no backend


def init_process_group(coordinator: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None,
                       timeout: Optional[float] = None,
                       retries: int = 2,
                       backoff: float = 1.0,
                       elastic: Optional[bool] = None) -> None:
    """Join the multi-process runtime (idempotent).

    Arguments default to the reference's launcher env vars
    (``DMLC_PS_ROOT_URI:DMLC_PS_ROOT_PORT``, ``DMLC_NUM_WORKER``,
    ``DMLC_WORKER_ID``).  Raises if neither arguments nor env are present.

    Failure handling (this used to hang forever on an unreachable
    coordinator): each join attempt waits at most ``timeout`` seconds
    (default: ``MXTPU_DIST_TIMEOUT`` env or 300), and is retried up to
    ``retries`` times with exponential backoff starting at ``backoff``
    seconds — under a real launcher the coordinator routinely comes up
    AFTER the workers.  The final failure is wrapped in an
    :class:`MXNetError` naming the coordinator and rank.

    ``elastic`` (default: the ``MXTPU_ELASTIC`` env knob) prepares the
    group for host loss: the coordination service's OWN task-heartbeat
    reaper is effectively disabled, because its reaction to a silent
    task is to propagate a fatal error that TERMINATES every surviving
    process (~100s after the death, with jax defaults) — the opposite
    of surviving it.  Liveness judgment then belongs solely to the
    membership lease layer (:mod:`mxnet_tpu.parallel.membership`),
    which detects the loss within one lease TTL and re-forms the fleet
    instead of dying with it.
    """
    if is_initialized():
        return
    if coordinator is None:
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9099")
        coordinator = f"{uri}:{port}" if uri else None
    if num_processes is None:
        nw = os.environ.get("DMLC_NUM_WORKER")
        num_processes = int(nw) if nw else None
    if process_id is None:
        wid = os.environ.get("DMLC_WORKER_ID")
        process_id = int(wid) if wid else None
    if num_processes == 1:
        return  # single worker: nothing to join
    if coordinator is None or num_processes is None or process_id is None:
        missing = []
        if coordinator is None:
            missing.append("DMLC_PS_ROOT_URI (+ optional DMLC_PS_ROOT_PORT)")
        if num_processes is None:
            missing.append("DMLC_NUM_WORKER")
        if process_id is None:
            missing.append("DMLC_WORKER_ID")
        raise MXNetError(
            "multi-process kvstore requires the process group to be "
            "initialized, but these launcher env vars are unset: "
            + ", ".join(missing) +
            " — set them (reference launcher env vars) or call "
            "mxnet_tpu.parallel.dist.init_process_group(coordinator, "
            "num_processes, process_id) before kv.create('dist_sync')")
    if timeout is None:
        timeout = float(get_env("MXTPU_DIST_TIMEOUT"))
    if elastic is None:
        elastic = bool(get_env("MXTPU_ELASTIC"))
    # the service would otherwise declare a silent task dead after its
    # heartbeat timeout and jax's error-polling thread would terminate
    # every survivor — the membership lease layer is the liveness
    # authority in an elastic fleet, so its timeout is out of reach
    heartbeat = 10_000_000 if elastic else 100   # 100 s: jax's default
    import jax
    from ..faults import retry_call

    def _join():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
                initialization_timeout=max(1, int(timeout)),
                heartbeat_timeout_seconds=heartbeat)
        except Exception:
            # a failed connect leaves jax's global client/service assigned
            # (State.initialize sets them BEFORE connect()), and a retry
            # would then die on 'initialize should only be called once' —
            # reset so the next attempt is a real join
            try:
                jax.distributed.shutdown()
            except Exception:   # noqa: BLE001 — best-effort state reset
                pass
            raise

    from ..observability.registry import registry as _metrics_registry

    def _count_retry(attempt, exc, delay):
        _metrics_registry().counter("dist.init_retries").inc()

    try:
        retry_call(_join, retries=retries, base_delay=backoff,
                   max_delay=30.0,
                   retry_on=(RuntimeError, ConnectionError, TimeoutError,
                             OSError), on_retry=_count_retry)
    except Exception as exc:
        raise MXNetError(
            f"could not join the process group at {coordinator!r} as rank "
            f"{process_id}/{num_processes} after {retries + 1} attempt(s) "
            f"({timeout:.0f}s connect timeout each): {exc}") from exc


# -- the active process group (elastic-fleet narrowing) ---------------------
#
# The coordination service is joined ONCE at the launcher's world size and
# its process ids never change.  After a host loss the survivors re-form
# the logical process group at the new world size (parallel/membership.py):
# the surviving ORIGINAL process ids become the active member set, logical
# ranks are re-assigned contiguously by sorting them, and every KV-path
# collective below iterates the active set only — so the group keeps
# working over the same coordinator without the dead host.  Physical ids
# (``phys_rank``) stay stable across re-forms and key every per-host KV
# namespace; logical coordinates (``rank``/``num_workers``) are what data
# sharding and collective result indexing see.

_group_lock = threading.Lock()
_members: Optional[Tuple[int, ...]] = None   # original ids, sorted; None =
_fence = 0                                   # full launcher world


def phys_rank() -> int:
    """This process's ORIGINAL id in the coordination service — stable
    across fleet re-forms (logical :func:`rank` is not)."""
    import jax
    return jax.process_index()


def rank() -> int:
    """Logical rank: contiguous in the ACTIVE member set.  Equal to
    :func:`phys_rank` until a fleet re-form narrows the group."""
    with _group_lock:
        members = _members
    if members is None:
        import jax
        return jax.process_index()
    return members.index(phys_rank())


def num_workers() -> int:
    """Logical world size: the ACTIVE member count after re-forms."""
    with _group_lock:
        members = _members
    if members is None:
        import jax
        return jax.process_count()
    return len(members)


def active_members() -> Tuple[int, ...]:
    """The ORIGINAL process ids of the active group, sorted (logical
    rank r is ``active_members()[r]``)."""
    with _group_lock:
        members = _members
    if members is not None:
        return members
    import jax
    return tuple(range(jax.process_count()))


def fence_generation() -> int:
    """The membership fencing generation: bumped by every fleet re-form;
    KV state stamped with an older generation belongs to a fenced-out
    incarnation and must be ignored."""
    with _group_lock:
        return _fence


def set_active_members(members, fence: int) -> None:
    """Install a re-formed process group (every survivor calls this with
    the SAME committed member set — parallel/membership.py's consensus
    round is the only sanctioned caller).  ``members`` are original
    process ids; this process must be one of them."""
    global _members, _fence
    members = tuple(sorted(int(m) for m in members))
    if not members:
        raise MXNetError("set_active_members: empty member set")
    me = phys_rank()
    if me not in members:
        raise MXNetError(
            f"set_active_members: this process (id {me}) is not in the "
            f"re-formed member set {members} — it has been fenced out "
            f"and must exit, not install the group")
    with _group_lock:
        _members = members
        _fence = int(fence)


def reset_active_members() -> None:
    """Drop the narrowed group (back to the full launcher world)."""
    global _members, _fence
    with _group_lock:
        _members = None
        _fence = 0


def _deadline_wait(what: str, timeout: float, fn, *args, **kwargs):
    """Run one blocking coordination-service call and convert its
    DEADLINE_EXCEEDED into the typed :class:`~mxnet_tpu.faults.
    DeadlineExceeded` every KV wait path promises.  A dead host then
    produces a catchable fault the membership watcher takes over from,
    instead of an opaque runtime error (or, before timeouts were
    threaded through, an unbounded hang)."""
    from ..faults import DeadlineExceeded
    try:
        return fn(*args, **kwargs)
    except TimeoutError as exc:
        raise DeadlineExceeded(
            f"{what} timed out after {timeout:.1f}s "
            f"(MXTPU_DIST_TIMEOUT) — a peer never arrived; if a host "
            f"died, the membership layer (parallel.membership) re-forms "
            f"the fleet from this signal") from exc
    except Exception as exc:   # noqa: BLE001 — narrow re-raise below:
        # jaxlib surfaces coordination-service timeouts as
        # XlaRuntimeError('DEADLINE_EXCEEDED: ...'), not TimeoutError
        if "DEADLINE_EXCEEDED" not in str(exc):
            raise
        raise DeadlineExceeded(
            f"{what} timed out after {timeout:.1f}s "
            f"(MXTPU_DIST_TIMEOUT) — a peer never arrived; if a host "
            f"died, the membership layer (parallel.membership) re-forms "
            f"the fleet from this signal") from exc


def _gather_arrays_kv(arr, timeout: Optional[float] = None):
    """KV-store transport for the host collectives: each rank ships its
    numpy array (npy-serialized) through :func:`_allgather_bytes_kv` and
    stacks the fleet's contributions.  Same contract as
    ``process_allgather`` with equal shapes; exists because device
    collectives don't span processes on every backend (multi-process
    CPU), while the coordination service always does."""
    import io
    import numpy as np
    if timeout is None:
        timeout = float(get_env("MXTPU_DIST_TIMEOUT"))
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(arr), allow_pickle=False)
    blobs = _allgather_bytes_kv(buf.getvalue(), timeout)
    return np.stack([np.load(io.BytesIO(b), allow_pickle=False)
                     for b in blobs])


def allreduce_host(x):
    """Sum a host-local numpy array across all processes.

    DCN-path reduction for the kvstore object plane (the compiled trainer
    path uses in-graph psum over the device mesh instead).
    """
    import numpy as np
    return np.sum(allgather_host(x), axis=0)


def reduce_scatter_host(x):
    """Reduce-scatter a host-local numpy array: sum it across all
    processes and return THIS rank's 1/num_workers slice along dim 0.

    The DCN object plane's analog of the in-graph ZeRO gradient
    reduce-scatter (``ShardedTrainer(zero_stage>=1)`` — there XLA emits
    the collective inside the jitted step; here the object plane gets
    the same reduce-then-own-slice contract for host-side state).  Dim
    0 must divide by the active world size.  Like every entry point in
    this module it is a COLLECTIVE: all ranks must call it or none —
    the collective-safety lint rule enforces that, rank-gated calls are
    a lint failure."""
    import numpy as np
    if not is_initialized():
        # local-only fallback (1-rank world): the sum is the input and
        # the slice is everything — same tiering as allgather_bytes
        return np.asarray(x)
    total = allreduce_host(x)
    n = num_workers()
    if total.shape[0] % n:
        raise MXNetError(
            f"reduce_scatter_host: dim 0 of {total.shape} does not "
            f"divide by the world size {n}")
    chunk = total.shape[0] // n
    r = rank()
    return total[r * chunk:(r + 1) * chunk]


def allgather_host(x):
    """Gather each process's host-local numpy array; returns an array with
    a leading num_workers axis (this process's slot included).

    Transport is tiered like :func:`allgather_bytes`: the XLA device
    collective where the backend spans processes (TPU pods), else the
    coordination-service KV store — so the object plane works on the
    multi-process CPU backend too (where XLA reports 'Multiprocess
    computations aren't implemented')."""
    import numpy as np
    from jax.experimental import multihost_utils
    arr = np.asarray(x)
    if _narrowed():
        # a re-formed group no longer matches the device world the
        # backend was built with (the dead host is still in it) — the
        # KV path over the surviving member set is the only transport
        return _gather_arrays_kv(arr)
    try:
        return np.asarray(multihost_utils.process_allgather(arr))
    except Exception:   # noqa: BLE001 — backend capability, determinis-
        # tic per backend: every rank takes the same branch
        if not is_initialized():
            raise
        return _gather_arrays_kv(arr)


def _allgather_bytes_device(data: bytes):
    """Byte gather over the raw ``process_allgather`` device collective
    (deliberately NOT :func:`allgather_host`, whose KV fallback would
    turn one logical gather into two — an unsupported backend should
    fail fast here so :func:`allgather_bytes` takes its single-gather
    KV path instead).  Variable lengths need two collectives (equal
    shapes are required): gather the lengths, then gather payloads
    padded to the fleet maximum and trim each back to its sender's
    true length."""
    import numpy as np
    from jax.experimental import multihost_utils
    sizes = np.asarray(multihost_utils.process_allgather(
        np.asarray([len(data)], dtype=np.int64)))[:, 0]
    cap = int(sizes.max())  # mxlint: disable=hidden-host-sync — the length gather is itself a host collective; its result sizes the payload buffer
    if cap == 0:
        return [b""] * len(sizes)
    buf = np.zeros((cap,), dtype=np.uint8)
    buf[:len(data)] = np.frombuffer(data, dtype=np.uint8)
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    return [gathered[i, :int(sizes[i])].tobytes()
            for i in range(len(sizes))]


# generation counters for the KV-store fallbacks below.  Every KV-path
# entry point is a COLLECTIVE (each process calls it the same number of
# times in the same order), so per-process counters stay in lockstep
# across the fleet and key/barrier names never collide across calls.
_gen_lock = threading.Lock()
_agb_gen = 0


def _narrowed() -> bool:
    """True once a fleet re-form has narrowed the active group below the
    launcher world — device collectives (which still span the ORIGINAL
    world, dead host included) are then off the table and every
    collective takes its coordination-service KV path."""
    with _group_lock:
        return _members is not None


def _barrier_ids(members: Tuple[int, ...]):
    """``process_ids`` for a coordination-service barrier: None (= the
    full launcher world, every jaxlib supports it) until a re-form has
    narrowed the group, then the explicit surviving id list."""
    with _group_lock:
        narrowed = _members is not None
    return list(members) if narrowed else None


def _allgather_bytes_kv(data: bytes, timeout: float):
    """Byte gather over the coordination-service KV store (the same
    coordinator TCP fabric ``jax.distributed.initialize`` joined): each
    rank publishes its payload under a generation-unique key and blocks
    reading every peer's.  No device round-trip and no padding — and it
    works on backends whose device collectives don't span processes
    (the multi-process CPU backend used in tests).

    Every blocking read is bounded by ``timeout`` and a peer that never
    arrives raises :class:`~mxnet_tpu.faults.DeadlineExceeded` naming
    it — the signal the membership watcher turns into a fleet re-form.
    Peers are the ACTIVE member set: after a re-form the gather spans
    the survivors only, indexed by logical rank."""
    import base64

    # the KV gather is a blocking fleet-wide wait: measured as a span so
    # it lands in the histogram AND — inside a traced region (a step or
    # re-form trace) — as a child span attributing collective time
    from ..observability.trace import span as _span
    global _agb_gen
    client = _coordination.client()
    me = phys_rank()
    members = active_members()
    with _gen_lock:
        gen = _agb_gen
        _agb_gen += 1
    # fence-scoped namespace: a fenced-out incarnation's in-flight gather
    # writes under the OLD fence and can never collide with the re-formed
    # group's generation counters
    key = f"mxtpu/agb/{fence_generation()}/{gen}"
    timeout_ms = max(1000, int(timeout * 1000))
    client.key_value_set(f"{key}/{me}",
                         base64.b64encode(data).decode("ascii"))
    with _span("dist.allgather_kv_us", args={"gen": gen}):
        out = [base64.b64decode(_deadline_wait(
            f"allgather_bytes gen {gen}: waiting for rank {i}", timeout,
            client.blocking_key_value_get, f"{key}/{i}", timeout_ms))
            for i in members]
    try:
        # only safe to delete our key once EVERY rank has read it
        client.wait_at_barrier(
            f"mxtpu_agb_{fence_generation()}_{gen}", timeout_ms,
            _barrier_ids(members))
        client.key_value_delete(f"{key}/{me}")
    except Exception:   # noqa: BLE001 — cleanup is best-effort; a few
        pass            # stale keys beat a wedged gather
    return out


def allgather_bytes(data: bytes, timeout: Optional[float] = None):
    """Gather one variable-length byte payload from every process;
    returns a list of ``num_workers`` byte strings indexed by rank.

    The DCN object plane for non-array payloads (the multi-host metrics
    gather ships JSON snapshots through here).  Transport is tiered:
    the ``allgather_host`` device collective when the backend spans
    processes (TPU pods — the efficient DCN path), else the
    coordination-service KV store (always available once the process
    group is up).  Local-only fallback: a single-element list when the
    process group is not initialized."""
    data = bytes(data)
    if not is_initialized():
        return [data]
    if timeout is None:
        timeout = float(get_env("MXTPU_DIST_TIMEOUT"))
    if _narrowed():
        return _allgather_bytes_kv(data, timeout)
    try:
        return _allgather_bytes_device(data)
    except Exception:   # noqa: BLE001 — backend-dependent capability
        # (e.g. CPU: "Multiprocess computations aren't implemented");
        # deterministic per backend, so every rank takes the same branch
        return _allgather_bytes_kv(data, timeout)


# -- row-sparse gradient exchange --------------------------------------------


@hot_path("step")
def allgather_rows(ids, rows, timeout: Optional[float] = None):
    """Gather one ``(ids, rows)`` row-sparse gradient slab from every
    process; returns a list of ``num_workers`` ``(ids, rows)`` numpy
    pairs indexed by rank.  The modern ps-lite push/pull: each worker
    ships only the rows its batch touched (ids ``(n,)`` int, rows
    ``(n, width)`` float) instead of allreducing the dense table, and
    the caller reduces with :func:`dedup_sum_rows`.

    Rides :func:`allgather_bytes` (device collective on pods, KV store
    fallback), so slabs may be DIFFERENT lengths per rank — no padding
    protocol needed.  Bumps the ``sparse.exchange_bytes`` counter with
    the actual wire payload.  Single-process: a one-element list."""
    import io
    import numpy as np
    from ..observability.registry import registry as _registry
    ids = np.ascontiguousarray(np.asarray(ids))  # mxlint: disable=hidden-host-sync — the exchange IS the host boundary: ids leave the device to ride the DCN
    rows = np.ascontiguousarray(np.asarray(rows))  # mxlint: disable=hidden-host-sync — same boundary: rows serialize into the wire payload
    if ids.shape[0] != rows.shape[0]:
        raise MXNetError(
            f"allgather_rows: {ids.shape[0]} ids vs {rows.shape[0]} rows")
    buf = io.BytesIO()
    np.savez(buf, ids=ids, rows=rows)
    payload = buf.getvalue()
    _registry().counter(
        "sparse.exchange_bytes",
        "bytes of (ids, rows) row-sparse gradient payload "
        "exchanged instead of dense table reductions").inc(len(payload))
    out = []
    for blob in allgather_bytes(payload, timeout=timeout):
        z = np.load(io.BytesIO(blob))
        out.append((z["ids"], z["rows"]))
    return out


def dedup_sum_rows(pairs):
    """Reduce :func:`allgather_rows` output: union the id sets and sum
    rows that collide — the server-side aggregation of the push/pull.
    Returns one ``(ids, rows)`` pair with ids sorted unique."""
    import numpy as np
    pairs = [p for p in pairs if p[0].size]
    if not pairs:
        return np.zeros((0,), np.int64), np.zeros((0, 0), np.float32)
    all_ids = np.concatenate([p[0] for p in pairs])
    all_rows = np.concatenate([p[1] for p in pairs], axis=0)
    uids, inv = np.unique(all_ids, return_inverse=True)
    out = np.zeros((uids.size, all_rows.shape[1]), all_rows.dtype)
    np.add.at(out, inv, all_rows)
    return uids, out


# -- barrier-free KV publish/collect ----------------------------------------
#
# NOT collectives: no barrier, no blocking peer read, no lockstep
# call-count requirement — which is exactly why the timer-thread fleet
# metric gather (tuning.FleetGatherController) can run free on every
# host at its own cadence.  Each rank overwrite-publishes its newest
# payload under a generation-stamped key; a collect reads whatever
# generation every peer has published most recently (possibly one tick
# stale — staleness is the price of barrier freedom, and the consumer's
# contract already labels remote hosts "as-of last gather").

_kv_pub_lock = threading.Lock()
_kv_pub_gens = {}      # prefix -> next generation for THIS process


def kv_publish(prefix: str, payload: bytes) -> None:
    """Publish this rank's ``payload`` under ``prefix`` (overwrite
    semantics: a fresh generation-stamped key is written, older own
    generations deleted best-effort).  Requires an initialized process
    group.

    Restart-safe: the first publish of a fresh process resumes ABOVE
    any generations a dead predecessor of the same rank left in the
    store (and purges them), so ``kv_collect`` prefers the live
    incarnation's state immediately instead of serving the dead
    process's frozen payload until the new counter catches up."""
    import base64
    if not is_initialized():
        raise MXNetError("kv_publish requires an initialized process "
                         "group (init_process_group)")
    client = _coordination.client()
    r = phys_rank()   # stable across re-forms: a host's namespace is its
    own = f"{prefix}/{r}"   # ORIGINAL id, so survivors' keys never move
    with _kv_pub_lock:
        gen = _kv_pub_gens.get(prefix)
        if gen is None:
            gen = 0
            try:
                for k, _v in client.key_value_dir_get(own):
                    try:
                        gen = max(gen, int(k.rsplit("/", 1)[1]) + 1)
                    except (ValueError, IndexError):
                        continue
            except Exception:   # noqa: BLE001 — empty/missing dir (the
                pass            # common case) or transport hiccup: gen 0
        _kv_pub_gens[prefix] = gen + 1
    key = f"{own}/{gen:012d}"
    client.key_value_set(key, base64.b64encode(payload).decode("ascii"))
    try:
        # purge every strictly-OLDER own generation — the previous
        # tick's and any dead predecessor's.  Gen-compared, not
        # key-compared: a concurrent publisher (two controllers on one
        # process) may have already written a NEWER generation, which
        # must survive this purge.  Best-effort; collect picks the
        # highest either way.
        for k, _v in client.key_value_dir_get(own):
            try:
                if int(k.rsplit("/", 1)[1]) < gen:
                    client.key_value_delete(k)
            except (ValueError, IndexError):
                continue
    except Exception:   # noqa: BLE001 — cleanup is best-effort; a few
        pass            # stale keys beat a failed publish


def kv_collect(prefix: str):
    """Every rank's most recently published payload under ``prefix`` as
    ``{rank: bytes}`` (only ranks that have published appear).  Never
    blocks on a peer: a rank that has not published yet is simply
    absent from this collect and present in a later one."""
    import base64
    if not is_initialized():
        raise MXNetError("kv_collect requires an initialized process "
                         "group (init_process_group)")
    client = _coordination.client()
    newest = {}            # rank -> (gen, value)
    for key, value in client.key_value_dir_get(prefix):
        parts = key.rsplit("/", 2)
        if len(parts) != 3:
            continue
        try:
            r, gen = int(parts[1]), int(parts[2])
        except ValueError:
            continue
        if r not in newest or gen > newest[r][0]:
            newest[r] = (gen, value)
    return {r: base64.b64decode(v) for r, (_g, v) in newest.items()}


def kv_purge_rank(prefix: str, dead_rank: int) -> int:
    """Best-effort deletion of every key under ``prefix`` belonging to
    ``dead_rank`` (by its ORIGINAL process id); returns the count
    removed.  Covers both per-rank key shapes used in this module:
    ``{prefix}/{rank}/{gen}`` (the :func:`kv_publish` namespace — lease
    and fleet-gather state) and ``{prefix}/.../{rank}`` (the allgather
    generation keys).  The membership reaper calls this after a re-form
    commits so a dead host's frozen generations can never be served to
    a later collect — the restart-safety purge in :func:`kv_publish`
    only covers the SAME rank coming back, not a rank that never
    returns."""
    if not is_initialized():
        return 0
    client = _coordination.client()
    tag = str(int(dead_rank))
    removed = 0
    try:
        entries = client.key_value_dir_get(prefix)
    except Exception:   # noqa: BLE001 — purge is best-effort; a few
        return 0        # stale keys beat a crashed reaper
    for key, _value in entries:
        parts = key.split("/")
        owned = parts[-1] == tag or \
            (len(parts) >= 2 and parts[-2] == tag and parts[-1].isdigit())
        if not owned:
            continue
        try:
            client.key_value_delete(key)
            removed += 1
        except Exception:   # noqa: BLE001 — same best-effort contract
            continue
    return removed


def broadcast_host(x):
    """Broadcast rank 0's host-local numpy array to all processes."""
    import numpy as np
    from jax.experimental import multihost_utils
    arr = np.asarray(x)
    if _narrowed():
        # logical rank 0 = the lowest surviving member: its slot leads
        # the KV gather, same contract as the device broadcast
        return _gather_arrays_kv(arr)[0]
    try:
        return np.asarray(multihost_utils.broadcast_one_to_all(arr))
    except Exception:   # noqa: BLE001 — same tiering as allgather_host
        if not is_initialized():
            raise
        return _gather_arrays_kv(arr)[0]


_barrier_gen = 0


def _barrier_kv(name: str, timeout: Optional[float] = None) -> None:
    """Coordination-service barrier over the ACTIVE member set, bounded
    by ``timeout`` (default ``MXTPU_DIST_TIMEOUT``) — an absent peer
    raises :class:`~mxnet_tpu.faults.DeadlineExceeded` instead of
    wedging the fleet.  Barrier ids must be unique per use; the
    generation counter stays in lockstep because barrier() is a
    collective, and it is fence-scoped so a fenced-out incarnation's
    barriers can never alias the re-formed group's."""
    global _barrier_gen   # noqa: PLW0603 — lockstep generation counter
    with _gen_lock:
        gen = _barrier_gen
        _barrier_gen += 1
    if timeout is None:
        timeout = float(get_env("MXTPU_DIST_TIMEOUT"))
    timeout_ms = max(1000, int(timeout * 1000))
    members = active_members()
    # span: the barrier wait is collective time on the step/re-form
    # critical path — histogram always, trace child inside a traced
    # region
    from ..observability.trace import span as _span
    with _span("dist.barrier_kv_us", args={"name": name, "gen": gen}):
        _deadline_wait(
            f"barrier '{name}' gen {gen} over ranks {list(members)}",
            timeout, _coordination.client().wait_at_barrier,
            f"mxtpu_barrier_{fence_generation()}_{name}_{gen}",
            timeout_ms, _barrier_ids(members))


def barrier(name: str = "mxnet_tpu_barrier",
            timeout: Optional[float] = None) -> None:
    """Fleet barrier, tiered like the gathers.  ``timeout`` bounds the
    coordination-service tier (typed ``DeadlineExceeded`` on an absent
    peer); the device-collective tier, when the backend supports it, is
    bounded only by the backend's own collective timeout — Python
    cannot interrupt an XLA collective.  The elastic arc therefore
    never relies on this function for loss detection: the membership
    layer's ``step_barrier`` goes straight to the bounded
    coordination-service barrier."""
    if _narrowed():
        _barrier_kv(name, timeout)
        return
    from jax.experimental import multihost_utils
    try:
        multihost_utils.sync_global_devices(name)
    except Exception:   # noqa: BLE001 — same tiering: the coordination
        # service's own barrier when device collectives can't span
        # processes
        if not is_initialized():
            raise
        _barrier_kv(name, timeout)
