"""Runtime kernel compilation (reference: src/common/rtc.cc +
python/mxnet/rtc.py — SURVEY.md §2.1 "Engine-level RTC").

The reference let users hand NVRTC a CUDA source string
(``mx.rtc.CudaModule``).  The TPU analog is **Pallas**: users hand us a
Python kernel function written against ``jax.experimental.pallas`` and get
back a launchable module with the same get_kernel/launch workflow.  There
is deliberately no source-string compiler here — on TPU the kernel language
IS Python/Pallas, and Mosaic does the runtime compilation NVRTC did.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import numpy as _np

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]


class PallasKernel:
    """A launchable kernel (reference analog: rtc.CudaKernel)."""

    def __init__(self, kernel_fn: Callable, name: str,
                 out_shape: Optional[Tuple] = None,
                 out_dtype=None, grid=None, **pallas_kwargs):
        self._fn = kernel_fn
        self.name = name
        self._out_shape = out_shape
        self._out_dtype = out_dtype
        self._grid = grid
        self._kwargs = pallas_kwargs
        self._compiled = {}

    def _build(self, shapes, dtypes, out_shape, grid, interpret):
        import jax
        from jax.experimental import pallas as pl
        out_shape = out_shape or self._out_shape or shapes[0]
        out_dtype = self._out_dtype or dtypes[0]
        kwargs = dict(self._kwargs)
        g = grid if grid is not None else self._grid
        if g is not None:
            kwargs["grid"] = g
        # Mosaic compiles for TPU; data that lives elsewhere (the CPU test
        # mesh, a host context on a TPU machine) runs the pallas
        # interpreter so kernels stay testable everywhere
        if interpret:
            kwargs.setdefault("interpret", True)
        call = pl.pallas_call(
            self._fn,
            out_shape=jax.ShapeDtypeStruct(tuple(out_shape), out_dtype),
            **kwargs)
        return jax.jit(call)

    def launch(self, args: Sequence[NDArray], grid=None,
               out_shape=None) -> NDArray:
        """Run the kernel; returns a new NDArray (TPU buffers are
        immutable — unlike the reference's in-place CUDA launches, the
        output is the return value)."""
        from .kernels.multi_sgd import _interpret
        vals = [a._read() for a in args]
        interpret = _interpret(vals[0])
        key = (tuple(v.shape for v in vals),
               tuple(str(v.dtype) for v in vals),
               tuple(out_shape) if out_shape else None,
               grid if not isinstance(grid, list) else tuple(grid),
               interpret)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._build([v.shape for v in vals],
                             [v.dtype for v in vals], out_shape, grid,
                             interpret)
            self._compiled[key] = fn
        out = fn(*vals)
        return NDArray(out, ctx=args[0].context)

    __call__ = launch


class PallasModule:
    """Container of named kernels (reference analog: rtc.CudaModule)."""

    def __init__(self, kernels=None):
        self._kernels = dict(kernels or {})

    def add_kernel(self, name: str, kernel_fn: Callable,
                   **kwargs) -> PallasKernel:
        k = PallasKernel(kernel_fn, name, **kwargs)
        self._kernels[name] = k
        return k

    def get_kernel(self, name: str, signature: str = "") -> PallasKernel:
        if name not in self._kernels:
            raise MXNetError(f"no kernel {name!r}; have "
                             f"{sorted(self._kernels)}")
        return self._kernels[name]


class CudaModule:
    """The reference's CUDA RTC entry point.  Raises with guidance — CUDA
    source strings cannot target a TPU; write a Pallas kernel instead."""

    def __init__(self, *a, **kw):
        raise MXNetError(
            "CudaModule is not supported on TPU builds; use "
            "mx.rtc.PallasModule with a jax.experimental.pallas kernel "
            "function (the TPU runtime-compilation path)")
