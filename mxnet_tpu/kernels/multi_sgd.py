"""Fused multi-tensor SGD apply as a Pallas TPU kernel.

Reference parity: src/operator/optimizer_op.cc multi_sgd_update /
multi_sgd_mom_update (multi-tensor apply, SURVEY.md §2.2 optimizer_op row)
— one kernel launch updates EVERY parameter, instead of one launch per
parameter.  The reference needs this because a ResNet has ~160 small
params whose per-kernel launch overhead dominates; on TPU the same tail
of small HBM round-trips motivates the same fusion.

TPU-native design: all tensors are flattened, each padded to a whole
number of (8, 128) fp32 tiles, and concatenated into ONE flat buffer.
The grid walks chunks of shape (8, 128); each chunk's learning rate and
weight decay are scalar-prefetched from SMEM (per-chunk arrays built on
the host once per signature), so the VPU inner loop is a single FMA pass:

    out = w - lr_chunk * (clip(g * rescale) + wd_chunk * w)

Padding guarantees a chunk never spans two tensors.  The momentum variant
carries a second state buffer through the same grid.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

# one grid step processes this many elements: a full fp32 VREG tile
_LANES = 128
_SUBLANES = 8
_CHUNK = _LANES * _SUBLANES


def _plan(shapes: Tuple[Tuple[int, ...], ...]):
    """Chunk layout for a tensor list: (chunks_per_tensor, total_chunks)."""
    chunks = tuple(max(1, -(-_size(s) // _CHUNK)) for s in shapes)
    return chunks, sum(chunks)


def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


@functools.lru_cache(maxsize=None)
def _jnp_dual(clip: float, dtype_name: str, momentum: float | None):
    """The kernel's jnp twin over the same packed (rows, 128) buffers.

    Off-TPU production path: interpret-mode Pallas executes the kernel
    grid step-by-step in Python, which is a TESTING vehicle, not a CPU
    backend.  XLA:CPU compiles this dual to the same math.
    Kernel-semantics tests opt back into real interpret mode with
    MXNET_PALLAS_INTERPRET=1."""
    import jax
    import jax.numpy as jnp

    def _rowwise(lr_c, wd_c, like):
        lr = jnp.repeat(lr_c, _SUBLANES)[:, None].astype(like.dtype)
        wd = jnp.repeat(wd_c, _SUBLANES)[:, None].astype(like.dtype)
        return lr, wd

    if momentum is None:
        @jax.jit
        def sgd(lr_c, wd_c, w, g):
            lr, wd = _rowwise(lr_c, wd_c, w)
            if clip > 0:
                g = jnp.clip(g, -clip, clip)
            return w - lr * (g + wd * w)
        return sgd

    @jax.jit
    def sgd_mom(lr_c, wd_c, w, g, m):
        lr, wd = _rowwise(lr_c, wd_c, w)
        if clip > 0:
            g = jnp.clip(g, -clip, clip)
        mom_new = momentum * m - lr * (g + wd * w)
        return w + mom_new, mom_new
    return sgd_mom


def _build_call(n_chunks: int, clip: float, dtype_name: str,
                momentum: float | None, interpret: bool):
    # env resolved OUTSIDE the cache so a test's monkeypatched
    # MXNET_PALLAS_INTERPRET takes effect regardless of call order
    from ..base import get_env
    if interpret and not get_env("MXNET_PALLAS_INTERPRET"):
        return _jnp_dual(clip, dtype_name, momentum)
    return _build_pallas(n_chunks, clip, dtype_name, momentum, interpret)


@functools.lru_cache(maxsize=None)
def _build_pallas(n_chunks: int, clip: float, dtype_name: str,
                  momentum: float | None, interpret: bool):
    # rescale_grad is deliberately NOT part of this key: it changes with
    # batch size, and each new key would mean a fresh Mosaic compile.
    # The caller pre-scales the gradient instead (XLA fuses that multiply
    # into the pack reshape); clip then applies to the rescaled gradient,
    # matching the reference order clip(rescale * g).
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_name)

    def sgd_kernel(lr_ref, wd_ref, w_ref, g_ref, out_ref):
        i = pl.program_id(0)
        lr = lr_ref[i]
        wd = wd_ref[i]
        g = g_ref[:]
        if clip > 0:
            g = jnp.clip(g, -clip, clip)
        out_ref[:] = w_ref[:] - lr * (g + wd * w_ref[:])

    def sgd_mom_kernel(lr_ref, wd_ref, w_ref, g_ref, m_ref,
                       out_ref, mom_out_ref):
        i = pl.program_id(0)
        lr = lr_ref[i]
        wd = wd_ref[i]
        g = g_ref[:]
        if clip > 0:
            g = jnp.clip(g, -clip, clip)
        mom_new = momentum * m_ref[:] - lr * (g + wd * w_ref[:])
        mom_out_ref[:] = mom_new
        out_ref[:] = w_ref[:] + mom_new

    block = pl.BlockSpec((_SUBLANES, _LANES), lambda i, *_: (i, 0))
    shape = jax.ShapeDtypeStruct((n_chunks * _SUBLANES, _LANES), dtype)
    n_in = 2 if momentum is None else 3
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # lr and wd ride SMEM
        grid=(n_chunks,),
        in_specs=[block] * n_in,
        out_specs=block if momentum is None else [block, block],
    )
    if momentum is None:
        call = pl.pallas_call(
            sgd_kernel, grid_spec=grid_spec, out_shape=shape,
            interpret=interpret)
    else:
        call = pl.pallas_call(
            sgd_mom_kernel, grid_spec=grid_spec, out_shape=(shape, shape),
            interpret=interpret)
    return call


def _pack(arrays, chunks):
    """Flatten+pad each array to whole chunks; concat to (rows, 128)."""
    import jax.numpy as jnp
    flat = []
    for a, c in zip(arrays, chunks):
        v = jnp.ravel(a)
        pad = c * _CHUNK - v.size
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
        flat.append(v)
    return jnp.concatenate(flat).reshape(-1, _LANES)


def _unpack(buf, shapes, chunks):
    import jax.numpy as jnp
    out = []
    offset = 0
    flat = jnp.ravel(buf)
    for s, c in zip(shapes, chunks):
        n = _size(s)
        out.append(flat[offset:offset + n].reshape(s))
        offset += c * _CHUNK
    return out


def _per_chunk(values, chunks, dtype):
    # values may be a traced array (LR schedules must not retrigger
    # compilation); chunks is always a static tuple, so repeat is traceable
    import jax.numpy as jnp
    return jnp.repeat(jnp.asarray(values, dtype), jnp.asarray(chunks),
                      total_repeat_length=sum(chunks))


def _interpret(example=None) -> bool:
    """Interpret mode off-TPU.  Decided by where the DATA lives, not the
    default backend: a live TPU backend with CPU-resident arrays would
    otherwise hand Mosaic a CPU lowering (which pallas rejects).

    Only meaningful on EAGER calls — under jit ``example`` is a tracer
    with no device and this falls back to the default backend; traced
    callers (the registered multi_sgd ops) must pass the decision in as
    the explicit static ``interpret`` kwarg instead."""
    import jax
    if isinstance(example, jax.Array) and \
            not isinstance(example, jax.core.Tracer):
        return next(iter(example.devices())).platform != "tpu"
    return jax.default_backend() != "tpu"


def fused_multi_sgd(weights: Sequence, grads: Sequence,
                    lrs, wds, rescale_grad: float = 1.0,
                    clip_gradient: float = -1.0, interpret=None):
    """One Pallas launch updating every (weight, grad) pair.

    ``lrs``/``wds`` are per-tensor sequences OR traced arrays (LR
    schedules therefore never retrigger compilation).  Returns the list
    of updated weights (same shapes/dtypes).
    """
    import jax.numpy as jnp
    shapes = tuple(tuple(w.shape) for w in weights)
    chunks, n_chunks = _plan(shapes)
    dtype = jnp.result_type(weights[0])
    if interpret is None:
        interpret = _interpret(weights[0])
    call = _build_call(n_chunks, float(clip_gradient),
                       dtype.name, None, bool(interpret))
    lr_c = _per_chunk(lrs, chunks, dtype)
    wd_c = _per_chunk(wds, chunks, dtype)
    w_buf = _pack(weights, chunks)
    g_buf = _pack([g * rescale_grad for g in grads], chunks)
    out = call(lr_c, wd_c, w_buf, g_buf)
    return _unpack(out, shapes, chunks)


def fused_multi_sgd_mom(weights: Sequence, grads: Sequence, moms: Sequence,
                        lrs, wds, momentum: float = 0.9,
                        rescale_grad: float = 1.0,
                        clip_gradient: float = -1.0, interpret=None):
    """Momentum variant; returns (updated_weights, updated_moms)."""
    import jax.numpy as jnp
    shapes = tuple(tuple(w.shape) for w in weights)
    chunks, n_chunks = _plan(shapes)
    dtype = jnp.result_type(weights[0])
    if interpret is None:
        interpret = _interpret(weights[0])
    call = _build_call(n_chunks, float(clip_gradient),
                       dtype.name, float(momentum), bool(interpret))
    lr_c = _per_chunk(lrs, chunks, dtype)
    wd_c = _per_chunk(wds, chunks, dtype)
    w_buf = _pack(weights, chunks)
    g_buf = _pack([g * rescale_grad for g in grads], chunks)
    m_buf = _pack(moms, chunks)
    w_out, m_out = call(lr_c, wd_c, w_buf, g_buf, m_buf)
    return _unpack(w_out, shapes, chunks), _unpack(m_out, shapes, chunks)
