"""The chip: whether it is there, its peaks, its memory, the compile cache."""
import os
import sys

from . import loader


def require_chips(n):
    """The devices JAX sees, or exit: no accelerator (or too few chips)
    means no result line and a non-zero exit code, never a CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: JAX found no TPU (devices: {devices})")
    if len(devices) < n:
        sys.exit(f"benchmark: the cell asks for {n} chip(s), JAX sees "
                 f"{len(devices)}")
    return devices


def peaks(device_kind):
    table = loader.load_json(loader.BENCH, "harness", "peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not in "
                         f"harness/peaks.json; add it with its source")
    return table[device_kind]


def memory_peak(devices):
    """Peak bytes occupied on the fullest chip: the peak of the live
    buffers plus the peak of what the runtime reserved for the loaded
    programs' temporaries.  On the TPU runtime ``peak_bytes_in_use`` counts
    the buffers alone and a compiled step's scratch sits under
    ``peak_bytes_reserved`` (free = limit - in_use - reserved, PERF.md)."""
    return max(sum(memory_peaks(d).values()) for d in devices)


def memory_peaks(dev):
    """The two peaks that ``memory_peak`` adds, apart (they need not have
    come at the same moment, so their sum is an upper estimate)."""
    st = dev.memory_stats()
    return {"peak_bytes_in_use": int(st["peak_bytes_in_use"]),
            "peak_bytes_reserved": int(st.get("peak_bytes_reserved", 0))}


class CacheEvents:
    """Counts what jax looked up in its persistent compilation cache."""

    def __init__(self):
        self.hits = self.misses = 0

    def listen(self):
        import jax
        jax.monitoring.register_event_listener(self._on)
        return self

    def _on(self, event, **_):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def __str__(self):
        return f"cache {self.hits} hit {self.misses} miss"


def configure_compile_cache():
    """jax's persistent cache (and the program's own tier) at
    ``JAX_COMPILATION_CACHE_DIR`` if set, else at the fixed path
    ``.compile_cache/`` in the checkout: the path is part of the key."""
    from mxnet_tpu.tuning import compile_cache
    cache = compile_cache.configure(
        os.path.join(loader.ROOT, ".compile_cache"))
    return cache.path
