"""Device/context model: ``mx.cpu()``, ``mx.gpu(i)``, ``mx.tpu(i)``.

Reference role: ``Context{dev_type, dev_id}`` in include/mxnet/base.h —
every NDArray and op execution is bound to a Context (SURVEY.md §2.1).
TPU-native design: a Context is a symbolic device name resolved lazily to a
``jax.Device``.  ``mx.tpu(i)`` is first-class; ``mx.gpu(i)`` resolves to the
i-th accelerator so reference scripts run unmodified on a TPU host.  Both
raise ``MXNetError`` when this process has no such device — as the reference
does on a host without the GPU — and never stand in a host core for it.
``mx.cpu()`` resolves to a CPU device when the CPU platform is available,
else the default platform (XLA owns placement, unlike the reference's
explicit per-device streams).  The default context is ``cpu(0)``, the
reference's: work reaches the chip because the caller names ``mx.tpu(0)``.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "num_gpus", "num_tpus",
           "current_context"]


def _jax():
    import jax
    return jax


class Context:
    """A symbolic device. Comparable/hashable; resolves to a jax.Device lazily."""

    # Mirrors the reference's devtype enum, extended with tpu.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devstr2type:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    @classmethod
    def from_str(cls, s: str) -> "Context":
        """Parse 'cpu(0)' / 'tpu(1)' / 'cpu' (the reference's repr form)."""
        s = str(s).strip()
        kind, _, idx = s.partition("(")
        idx = idx.rstrip(")").strip()
        return cls(kind.strip(), int(idx) if idx else 0)

    # -- resolution --------------------------------------------------------
    @property
    def device(self):
        """Resolve to a concrete jax.Device."""
        return _resolve_device(self.device_type, self.device_id)

    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    # -- protocol ----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *a):
        Context._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _default_device_context()


_ACCEL_PLATFORMS = ("tpu", "gpu", "cuda", "rocm")


def _platform_devices(kinds) -> List:
    # process-LOCAL devices: under multi-process JAX (dist_sync), a Context
    # must never resolve to another process's device — an array placed
    # there would be non-addressable here
    jax = _jax()
    for kind in kinds:
        try:
            devs = jax.local_devices(backend=kind)
            if devs:
                return devs
        except RuntimeError:
            continue
    return []


def _resolve_device(device_type: str, device_id: int):
    if device_type in ("cpu", "cpu_pinned", "cpu_shared"):
        devs = _platform_devices(("cpu",))
        if not devs:
            devs = _jax().local_devices()  # accelerator build: CPU ctx
            # falls through to the default platform; XLA handles host staging.
        if not devs:
            raise MXNetError(
                f"no devices for context {device_type}({device_id})")
        return devs[device_id % len(devs)]
    # gpu == "the accelerator" so reference scripts run unchanged
    devs = _platform_devices(
        ("tpu",) if device_type == "tpu" else _ACCEL_PLATFORMS)
    if not 0 <= device_id < len(devs):
        raise MXNetError(
            f"context {device_type}({device_id}): this process has "
            f"{len(devs)} such device(s)")
    return devs[device_id]


def _default_device_context() -> Context:
    return Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_platform_devices(_ACCEL_PLATFORMS))


def num_tpus() -> int:
    return len(_platform_devices(("tpu",)))


def current_context() -> Context:
    return Context.default_ctx()
