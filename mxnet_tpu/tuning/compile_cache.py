"""Persistent compilation cache: compiled executables that survive the
process.

PERF.md documents multi-minute XLA compiles inside 2-minute chip
windows: every restart — a preemption auto-resume, a ModelServer cold
start — re-pays the full compile for graphs the previous process
already built.  The in-memory caches this repo already keys carefully
(``_segment_cache`` in ndarray/register.py, the per-signature
``HybridBlock._cached_graph``) die with the process; this module gives
those same keys a disk tier.

Design:

- **Keyed on the existing signature keys + a backend fingerprint.**
  A cache entry's name is ``sha256(kind + canonical-key + fingerprint)``
  where the fingerprint covers jax/jaxlib versions, the backend
  platform, and the device kind — a cache written by one toolchain or
  chip generation can never be replayed onto another (the stale entry
  simply never matches and ages out).
- **Written atomically** (tmp + ``os.replace``), so a crash mid-write
  leaves no torn entry and concurrent processes can share one
  directory — last writer wins, both wrote the same bytes.
- **Loaded lazily on first miss.**  Nothing is read at import or
  construction; a lookup happens only where the in-memory cache already
  missed, i.e. on the cold compile path — the steady-state hot path
  never touches this module (the mxlint ``hot-path-purity`` reachability
  proof holds because the wiring seams are installed hooks, not direct
  calls).

One payload format: every wired site (exact-mode bulk segments, cached
graphs) holds a ``jax.stages.Compiled``, and the AOT
``jax.experimental.serialize_executable`` pickle (payload + in/out
trees) round-trips it.  Entries are trusted local state (same trust
level as jax's own persistent cache, which uses the same mechanism).

Metrics (process-global registry): ``tuning.compile_cache_hits`` /
``_misses`` / ``_stores`` / ``_errors``, and ``tuning.compiles`` — the
count of actual backend compiles performed at cache-wired sites.  A
warm-started process replaying only previously-seen signatures holds
``tuning.compiles`` at ~0; the subprocess test asserts exactly that.

Where the cache lives is decided from outside, by :func:`cache_dir`:
``JAX_COMPILATION_CACHE_DIR`` if it is set — jax reads that variable
itself for its own persistent cache, this module's entries go in the
``mxnet_tpu`` directory under it, and nothing here touches
``jax_compilation_cache_dir`` — else ``MXTPU_COMPILE_CACHE_DIR``, which
(with ``MXTPU_COMPILE_CACHE_JAX``, default on) also hosts jax's own
cache in ``<dir>/jax``, so plain ``jax.jit`` paths — per-op fns,
training vjp graphs — reuse compiles across processes too.  Neither
set: no persistent cache.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
from typing import Optional

from ..base import get_env
from ..observability.registry import registry as _metrics_registry

__all__ = ["CompileCache", "active", "cache_dir", "configure",
           "CACHE_DIR_ENV", "CACHE_JAX_ENV", "JAX_CACHE_DIR_ENV"]

CACHE_DIR_ENV = "MXTPU_COMPILE_CACHE_DIR"
CACHE_JAX_ENV = "MXTPU_COMPILE_CACHE_JAX"
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def _jax_cache_dir() -> str:
    return os.environ.get(JAX_CACHE_DIR_ENV, "").strip()


def cache_dir() -> Optional[str]:
    """The directory this module's entries live in, or None (module
    docstring: the environment decides, jax's own variable first)."""
    outer = _jax_cache_dir()
    if outer:
        return os.path.join(outer, "mxnet_tpu")
    return (get_env(CACHE_DIR_ENV) or "").strip() or None


def _fingerprint() -> str:
    """Toolchain + backend identity baked into every key: an entry
    compiled by a different jax/jaxlib or for a different chip must
    never deserialize into this process."""
    import jax
    import jaxlib
    try:
        dev = jax.devices()[0]
        backend = f"{dev.platform}/{dev.device_kind}"
    except Exception:   # noqa: BLE001 — no backend yet: fingerprint
        backend = "unknown"        # conservatively mismatches later runs
    return f"jax={jax.__version__};jaxlib={jaxlib.__version__};" \
           f"backend={backend}"


class CompileCache:
    """One directory of serialized executables (see module docstring).

    All I/O failures degrade to a miss (and count in
    ``tuning.compile_cache_errors``): a broken cache dir must never take
    down the compile it was supposed to skip.
    """

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self._fp: Optional[str] = None
        self._lock = threading.Lock()
        reg = _metrics_registry()
        self._c_hits = reg.counter(
            "tuning.compile_cache_hits",
            help="persistent compile-cache entries deserialized instead "
                 "of compiled")
        self._c_misses = reg.counter(
            "tuning.compile_cache_misses",
            help="persistent compile-cache lookups that found no entry")
        self._c_stores = reg.counter(
            "tuning.compile_cache_stores",
            help="executables serialized into the persistent cache")
        self._c_errors = reg.counter(
            "tuning.compile_cache_errors",
            help="cache I/O or (de)serialization failures, each "
                 "degraded to a miss")
        self._c_compiles = reg.counter(
            "tuning.compiles",
            help="actual backend compiles at persistent-cache-wired "
                 "sites — ~0 on a warm start replaying known "
                 "signatures")

    # -- keys / paths --------------------------------------------------------
    def _fingerprint(self) -> str:
        fp = self._fp
        if fp is None:
            fp = self._fp = _fingerprint()
        return fp

    def entry_key(self, kind: str, canonical: str) -> str:
        h = hashlib.sha256()
        h.update(kind.encode("utf-8"))
        h.update(b"\0")
        h.update(self._fingerprint().encode("utf-8"))
        h.update(b"\0")
        h.update(canonical.encode("utf-8"))
        return f"{kind}-{h.hexdigest()}"

    def _entry_path(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.bin")

    def __len__(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.path)
                       if n.endswith(".bin"))
        except OSError:
            return 0

    # -- raw byte tier -------------------------------------------------------
    def load_bytes(self, key: str) -> Optional[bytes]:
        try:
            with open(self._entry_path(key), "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None
        except OSError:
            self._c_errors.inc()
            return None

    def store_bytes(self, key: str, data: bytes) -> bool:
        """Atomic write: tmp + rename, pid-suffixed so concurrent
        processes never clobber each other's tmp files."""
        path = self._entry_path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(self.path, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
            self._c_stores.inc()
            return True
        except OSError:
            self._c_errors.inc()
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False

    # -- AOT-compiled jax.jit executables ------------------------------------
    def load_jit(self, key: str, device=None):
        """Deserialize an AOT ``Compiled`` callable, or None on miss.
        ``device``: the one device the executable was compiled for (it
        names its devices by id); None loads over the default backend's
        devices."""
        data = self.load_bytes(key)
        if data is None:
            self._c_misses.inc()
            return None
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = pickle.loads(data)
            if device is None:
                compiled = _se.deserialize_and_load(payload, in_tree,
                                                    out_tree)
            else:
                compiled = _se.deserialize_and_load(
                    payload, in_tree, out_tree, backend=device.client,
                    execution_devices=[device])
        except Exception:   # noqa: BLE001 — toolchain drift or torn
            self._c_errors.inc()       # entry reads as a plain miss
            return None
        self._c_hits.inc()
        return compiled

    def store_jit(self, key: str, compiled) -> None:
        self._c_compiles.inc()
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            data = pickle.dumps((payload, in_tree, out_tree))
        except Exception:   # noqa: BLE001 — backend without executable
            self._c_errors.inc()       # serialization: run-only, no disk
            return
        self.store_bytes(key, data)


# -- process-global instance + wiring ---------------------------------------

_active_lock = threading.Lock()
_active: Optional[CompileCache] = None


def active() -> Optional[CompileCache]:
    """THE process-global cache, or None when :func:`cache_dir` names no
    directory.  Resolved live so a test (or a late-exported env) can
    enable it after import; the instance is rebuilt if the dir changes."""
    global _active
    path = cache_dir()
    if path is None:
        return None
    path = os.path.abspath(path)
    inst = _active
    if inst is not None and inst.path == path:
        return inst
    with _active_lock:
        if _active is None or _active.path != path:
            _active = CompileCache(path)
            _wire(_active)
    return _active


def configure(path: str) -> CompileCache:
    """Explicit enable for a program that wants a cache whatever the
    environment says (``chip_smoke.py``, the benchmark's harness):
    where ``JAX_COMPILATION_CACHE_DIR`` is set that directory is used
    and ``path`` is ignored; where it is not, ``path`` is exported as
    ``MXTPU_COMPILE_CACHE_DIR`` (child processes inherit it)."""
    if not _jax_cache_dir():
        os.environ[CACHE_DIR_ENV] = os.path.abspath(path)
    return active()


def _wire(cache: CompileCache) -> None:
    """Install the lazy-load seams.  Hook indirection keeps the cache
    OFF the dispatch hot path in mxlint's reachability proof and keeps
    the frontend layers free of a tuning import."""
    from ..ndarray import register as _register
    _register._install_persist_hooks(_segment_lookup, _segment_store)
    _configure_jax_cache(cache)


def _configure_jax_cache(cache: CompileCache) -> None:
    """Let jax's own persistent compilation cache keep the plain
    ``jax.jit`` paths (per-op fns, training vjp graphs) across restarts:
    in the directory the environment gave it, else in ``<dir>/jax``."""
    import jax
    if not _jax_cache_dir():
        if not get_env(CACHE_JAX_ENV):
            return
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(cache.path, "jax"))
    # default thresholds skip sub-second compiles and tiny executables —
    # this repo's segment graphs are exactly those
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# -- the segment seam (installed into ndarray.register) ---------------------

def _segment_lookup(canonical: str, device):
    """Hook: exact-mode segment cache miss → try the disk tier."""
    cache = active()
    if cache is None:
        return None
    return cache.load_jit(cache.entry_key("seg", canonical), device)


def _segment_store(canonical: str, exe) -> None:
    """Hook: a segment executable was compiled → persist it."""
    cache = active()
    if cache is None:
        return
    cache.store_jit(cache.entry_key("seg", canonical), exe)


# -- the cached-graph seam (called from gluon.block) ------------------------

def aot_compile(lowered, kind: str = "graph", device=None):
    """Compile a ``jax.jit(...).lower(...)`` artifact through the
    persistent cache: the lowered StableHLO text (plus the backend
    fingerprint) is the key, so identical traces in a fresh process
    deserialize instead of compiling.  ``device`` is the one device the
    graph is lowered for (see :meth:`CompileCache.load_jit`).  Returns
    the AOT ``Compiled`` callable, or None when the cache is disabled
    (callers then keep their plain jit path)."""
    cache = active()
    if cache is None:
        return None
    try:
        canonical = lowered.as_text()
    except Exception:   # noqa: BLE001 — no text form: nothing to key on
        cache._c_errors.inc()
        return None
    key = cache.entry_key(kind, canonical)
    compiled = cache.load_jit(key, device)
    if compiled is not None:
        return compiled
    compiled = lowered.compile()
    cache.store_jit(key, compiled)
    return compiled


# -- what compiling costs, from jax's own events ----------------------------

#: jax's duration event -> the registry counters ``compile.<phase>_s``
#: (seconds) and ``compile.<phase>_n`` (times).  ``backend`` is jax's
#: ``compile_or_get_cached``: the backend's compile, or the read of the
#: persistent cache that stood in for it; ``cache_read`` is that read
#: alone where it was a hit, so it lies inside ``backend``.
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_watch_lock = threading.Lock()
_watch_counters: Optional[dict] = None
_watch_tls = threading.local()


def watch_compiles() -> dict:
    """Install, once a process, the two ``jax.monitoring`` listeners that
    fold jax's compile events into the registry, and return the counters:
    ``{phase: (seconds counter, times counter)}``.  They fire only where
    something is traced, lowered or compiled: nothing on a hot path.

    jax times every ``jit`` it traces, also those traced inside another's
    trace or while another is lowered, and the outer duration holds the
    inner ones.  Each timed region announces its start through
    ``record_scalar`` under the same event name, so a per-thread depth
    keeps the outermost region alone: the three phases' seconds add up
    to wall time."""
    global _watch_counters
    with _watch_lock:
        if _watch_counters is not None:
            return _watch_counters
        import jax
        from ..observability.registry import registry
        reg = registry()
        counters = {
            phase: (reg.counter(f"compile.{phase}_s",
                                f"seconds jax spent in {phase}"),
                    reg.counter(f"compile.{phase}_n",
                                f"times jax ran {phase}"))
            for phase in (*COMPILE_PHASES.values(), "cache_read")}

        def count(phase, seconds):
            secs, times = counters[phase]
            secs.inc(seconds)
            times.inc()

        def on_start(event, _value, **_):
            if event in COMPILE_PHASES:
                _watch_tls.depth = getattr(_watch_tls, "depth", 0) + 1

        def on_duration(event, seconds, **_):
            if event == CACHE_READ_EVENT:
                count("cache_read", seconds)
            elif event in COMPILE_PHASES:
                depth = _watch_tls.depth = \
                    max(getattr(_watch_tls, "depth", 1) - 1, 0)
                if depth == 0:
                    count(COMPILE_PHASES[event], seconds)

        jax.monitoring.register_scalar_listener(on_start)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _watch_counters = counters
    return counters
