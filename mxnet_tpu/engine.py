"""Dispatch engine: ordering + synchronization over JAX's async runtime.

Reference role: src/engine/ — the threaded dependency engine that serializes
conflicting reads/writes of NDArray variables and runs everything async
(SURVEY.md §2.1, "the heart of MXNet's async-everything model").

TPU-native design: XLA/PJRT *already* provides async dispatch with data-flow
ordering — every jax op returns immediately with a future-like Array, and
consumers are ordered by value dependence.  What the reference's engine adds
beyond that is (a) ordering of *mutations* (NDArray is mutable), and
(b) explicit sync points.  Mutation ordering here is achieved structurally:
an in-place op produces a *new* immutable buffer and bumps the NDArray's
version, so conflicting writes are serialized by the GIL-ordered version
update rather than by a scheduler (see ndarray.py).  This module therefore
carries the *interface*: engine-type selection (NaiveEngine = force-sync for
debugging, exactly the reference's MXNET_ENGINE_TYPE escape hatch), sync
points (wait_for_var / wait_all), and the bulk/dispatch-statistics surface.

Bulked dispatch (reference: MXNET_EXEC_BULK_EXEC_TRAIN, the "bulking" of
consecutive engine pushes into one dispatch): the imperative invoke path in
ndarray/register.py defers fusable ops into a lazy segment instead of
executing them one XLA dispatch at a time, and flushes the whole segment as
ONE jitted fused executable at a sync point.  This module owns the knobs
(bulk on/off, MXNET_ENGINE_BULK_SIZE cap, NaiveEngine forces flush-per-op),
the counters (``Engine.stats()``), and the flush hook the sync points call
— the segment builder itself lives next to the invoke path it serves.
"""
from __future__ import annotations

import os
import threading
from typing import Any

from .base import get_env, hot_path
from .observability.registry import registry as _metrics_registry

__all__ = ["Engine", "engine", "is_naive", "wait_all", "PendingValue"]


class PendingValue:
    """Placeholder living in ``NDArray._data`` while the producing op sits
    in an unflushed bulk segment (the 'pending write var' of the reference
    engine).  ``segment`` is the owning segment (duck-typed: needs only
    ``.flush()`` and ``.error``), ``index`` its slot in the segment's flat
    output tuple.  NDArray._read() treats this type as the barrier: any
    read materializes the whole segment first."""

    __slots__ = ("segment", "index")

    def __init__(self, segment, index: int):
        self.segment = segment
        self.index = index


# Installed by ndarray.register at import time; called by the sync points
# below so `engine` never has to import the frontend layer (which imports
# this module).  The hook flushes the CALLING thread's pending segment.
_flush_hook = None


def _install_flush_hook(fn) -> None:
    global _flush_hook
    _flush_hook = fn


@hot_path("dispatch")
def flush_pending() -> None:
    """Flush the calling thread's pending bulk segment, if any."""
    if _flush_hook is not None:
        _flush_hook()


# os.environ's decoded-bytes dict, when the platform exposes it: the bulk
# knobs are re-read on EVERY op dispatch (live toggling is part of the
# env-var contract), and os.environ.get's key encode costs ~1µs — real
# money on a ~6µs defer path.  Falls back to os.environ.get elsewhere.
# posix-only: on Windows os.environ._data is str-keyed (and upper-cased),
# so bytes lookups would silently always miss.
_ENV_DATA = getattr(os.environ, "_data", None) if os.name == "posix" \
    else None
if not isinstance(_ENV_DATA, dict):
    _ENV_DATA = None


def _raw_env(key_bytes: bytes, key_str: str):
    if _ENV_DATA is not None:
        return _ENV_DATA.get(key_bytes)
    return os.environ.get(key_str)


class Engine:
    """Process-wide engine singleton (interface-compatible with the reference's
    ``Engine::Get()``)."""

    _inst = None
    _lock = threading.Lock()

    def __init__(self):
        # singleton __init__: runs once per process, after which
        # engine() is a plain attribute read
        # mxlint: disable=hot-path-purity — one-time singleton init
        self._type = get_env("MXNET_ENGINE_TYPE")
        # profiler hooks: fn(op_name, outputs, dispatch_us)
        self._listeners = []
        # bulk_enabled memo: (raw env string, parsed bool) — the invoke
        # hot path asks once per op, so a full get_env parse each time
        # showed up in profiles; os.environ.get + string compare doesn't
        self._bulk_raw = object()
        self._bulk_parsed = True
        self._fuse_raw = object()
        self._fuse_parsed = "exact"
        # dispatch/bulking counters live in the process-global metrics
        # registry (mxnet_tpu.observability) under `engine.*`; stats()
        # below is a thin back-compat view.  Hot paths bump `.n` directly
        # — the same plain int add the former attributes were.
        reg = _metrics_registry()
        self._c_dispatched = reg.counter(
            "engine.ops_dispatched",
            help="per-op XLA dispatches (unbulked path)")
        self._c_bulked = reg.counter(
            "engine.ops_bulked",
            help="ops deferred into fused bulk segments")
        self._c_segments = reg.counter(
            "engine.segments_flushed",
            help="bulk segments executed as one fused dispatch")
        self._c_bulked_flushed = reg.counter(
            "engine.bulked_ops_flushed",
            help="ops carried by flushed segments")
        self._c_cache_hits = reg.counter(
            "engine.segment_cache_hits",
            help="fused-executable cache hits")
        self._c_cache_misses = reg.counter(
            "engine.segment_cache_misses",
            help="fused-executable cache misses (compiles)")
        self._h_flush = reg.histogram(
            "engine.flush_us",
            help="per-segment flush latency in microseconds")

    @classmethod
    def get(cls) -> "Engine":
        with cls._lock:
            if cls._inst is None:
                cls._inst = Engine()
            return cls._inst

    # -- mode --------------------------------------------------------------
    @property
    def engine_type(self) -> str:
        return self._type

    def set_engine_type(self, name: str) -> None:
        # NaiveEngine must observe every op synchronously from the moment
        # it is selected — anything still parked in a segment flushes now
        flush_pending()
        self._type = name

    @property
    def is_naive(self) -> bool:
        return self._type == "NaiveEngine"

    # -- bulking config ----------------------------------------------------
    @property
    def bulk_enabled(self) -> bool:
        """Whether the invoke path may defer ops into fused segments.
        NaiveEngine forces flush-per-op (the reference's behavior: the
        debug engine never bulks); the env var is read live so tests and
        users can toggle at runtime, as with the reference's knobs.
        The raw value is memoized against the environ entry itself —
        this property runs once per op dispatch."""
        if self._type == "NaiveEngine":
            return False
        raw = _raw_env(b"MXNET_EXEC_BULK_EXEC_TRAIN",
                       "MXNET_EXEC_BULK_EXEC_TRAIN")
        if raw != self._bulk_raw:
            self._bulk_parsed = bool(get_env("MXNET_EXEC_BULK_EXEC_TRAIN"))
            self._bulk_raw = raw
        return self._bulk_parsed

    @property
    def bulk_size(self) -> int:
        """Max ops per segment (reference: MXNET_ENGINE_BULK_SIZE)."""
        n = get_env("MXNET_ENGINE_BULK_SIZE")
        return max(1, int(n))

    def set_bulk_size(self, n: int) -> None:
        """Set the live ``MXNET_ENGINE_BULK_SIZE`` cap — the
        BulkSizeController's apply path.  Environment-backed on purpose:
        the ``bulk_size`` property reads the knob at segment creation,
        so the new cap takes effect on the very next segment, and child
        processes (spawned workers) inherit the tuned value."""
        os.environ["MXNET_ENGINE_BULK_SIZE"] = str(max(1, int(n)))

    @property
    def bulk_fuse_mode(self) -> str:
        """Segment codegen mode: 'exact' (default — one dispatch per
        segment but per-op kernels, BITWISE identical to the unbulked
        path) or 'aggressive' (full XLA fusion: fastest, enables taped
        segments, allows FMA contraction ⇒ ~1-ulp drift)."""
        raw = _raw_env(b"MXNET_ENGINE_BULK_FUSE", "MXNET_ENGINE_BULK_FUSE")
        if raw != self._fuse_raw:
            v = (raw or b"exact").strip().lower()
            self._fuse_parsed = "aggressive" \
                if v in (b"aggressive", "aggressive") else "exact"
            self._fuse_raw = raw
        return self._fuse_parsed

    # -- dispatch hooks ----------------------------------------------------
    @hot_path("dispatch")
    def on_push(self, op_name: str, outputs: Any,
                dispatch_us: float = 0.0) -> None:
        """Called by the invoke path after dispatching an op; dispatch_us
        is the measured host-side dispatch latency (async — device time is
        the XLA trace's job, as it was the CUDA profiler's in the
        reference).

        In NaiveEngine mode, block until the results are ready — the direct
        analog of the reference's synchronous debug engine.
        """
        self._c_dispatched.n += 1
        for fn in self._listeners:
            fn(op_name, outputs, dispatch_us)
        if self.is_naive:
            import jax
            jax.block_until_ready(outputs)

    @hot_path("dispatch")
    def on_bulk_flush(self, n_ops: int, cache_hit,
                      flush_us: float = 0.0) -> None:
        """A segment of ``n_ops`` deferred ops executed as one fused
        dispatch.  cache_hit: True/False = the fused-executable cache was
        consulted; None = it never was (fully-dead segment, nothing ran)
        — counted in neither hits nor misses.  ``flush_us`` (measured by
        the segment builder) lands in the ``engine.flush_us`` histogram —
        the signal the MXNET_ENGINE_BULK_SIZE auto-tune follow-up needs."""
        self._c_segments.n += 1
        self._c_bulked_flushed.n += n_ops
        if cache_hit is not None:
            if cache_hit:
                self._c_cache_hits.n += 1
            else:
                self._c_cache_misses.n += 1
        self._h_flush.observe(flush_us)
        for fn in self._listeners:
            fn(f"_BulkFlush[{n_ops}]", (), flush_us)

    def add_listener(self, fn) -> None:
        """Install a dispatch listener (profiler/monitor).  Listeners
        need REAL per-op outputs, so bulking suspends while any listener
        is installed — the invoke path checks ``_listeners`` directly;
        anything already deferred flushes on its usual sync points (the
        listener then sees the ``_BulkFlush[n]`` event)."""
        self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        if fn in self._listeners:
            self._listeners.remove(fn)

    @property
    def num_ops_dispatched(self) -> int:
        return self._c_dispatched.n

    # -- statistics (the "bulk/dispatch-statistics hook") ------------------
    def stats(self) -> dict:
        """Dispatch/bulking counters — a back-compat VIEW over the
        ``engine.*`` metrics in the observability registry (one
        ``registry().snapshot()`` returns these plus every other
        subsystem's).  ``ops_dispatched`` counts per-op XLA dispatches
        (unbulked path), ``ops_bulked`` ops deferred into segments; their
        sum is every op that entered the invoke path.  Mean segment
        length is over FLUSHED segments; flush latency percentiles come
        from the ``engine.flush_us`` histogram."""
        flushed = self._c_segments.n
        flush_h = self._h_flush.read()
        return {
            "ops_dispatched": self._c_dispatched.n,
            "ops_bulked": self._c_bulked.n,
            "segments_flushed": flushed,
            "mean_segment_length": (
                round(self._c_bulked_flushed.n / flushed, 3) if flushed
                else 0.0),
            "segment_cache_hits": self._c_cache_hits.n,
            "segment_cache_misses": self._c_cache_misses.n,
            "flush_us_p50": flush_h["p50"],
            "flush_us_p99": flush_h["p99"],
        }

    def reset_stats(self) -> None:
        for m in (self._c_dispatched, self._c_bulked, self._c_segments,
                  self._c_bulked_flushed, self._c_cache_hits,
                  self._c_cache_misses, self._h_flush):
            m.reset()

    # -- sync points -------------------------------------------------------
    def wait_for_var(self, data) -> None:
        """Block until a value is computed (reference: Engine::WaitForVar).
        A pending bulk segment flushes first — WaitForVar is a sync point."""
        flush_pending()
        import jax
        if hasattr(data, "_read"):       # NDArray accepted for convenience
            data = data._read()
        jax.block_until_ready(data)

    def wait_all(self) -> None:
        """Block until all outstanding computation completes
        (reference: Engine::WaitForAll / MXNDArrayWaitAll).

        Runtime errors raised by async computation surface HERE, exactly
        as in the reference engine.  Only errors that mean "this buffer no
        longer exists" (deleted/donated while we iterate the live list —
        an expected race) are suppressed.
        """
        flush_pending()
        import jax
        for arr in jax.live_arrays():
            try:
                arr.block_until_ready()
            except (RuntimeError, ValueError) as e:
                msg = str(e).lower()
                if "deleted" in msg or "donated" in msg:
                    continue  # buffer went away mid-iteration: not an error
                raise


def engine() -> Engine:
    # lock-free fast path: the singleton never changes once created, and
    # the invoke hot path calls this per op
    inst = Engine._inst
    return inst if inst is not None else Engine.get()


def is_naive() -> bool:
    return Engine.get().is_naive


def wait_all() -> None:
    Engine.get().wait_all()
