"""The delta rule's pass over the chunks, ``S -> S'``, as Pallas TPU
kernels, forward and backward (``gated_delta_rule``, scope ``state``).

For a head whose state ``S`` (dk, dv) enters chunk n, with ``W``, ``U``
and ``Kd = K * exp(gamma_C - gamma)`` of the chunk (C, dk | dv) and its
decay ``d`` (a number, or a vector over the state's rows under the channel
gate)::

    states[n] = S
    U'[n]     = U - W S
    S         <- d * S + Kd^T U'[n]

from ``S = 0``; ``final`` is the state after the last chunk.
``gdn_state_pass_fwd`` has a grid of (groups of ``hb`` heads, blocks of
``cb`` chunks), the chunk axis sequential: each head's state lives in a
float32 VMEM scratch from the first block to the last, and the operands are
read where the rule made them, (heads, chunks, C, width), with no copy in
or out.  ``gdn_state_pass_bwd`` walks the same grid with the chunk blocks
taken in reverse, the state's running cotangent ``dS`` in the scratch from
``final``'s cotangent on::

    dU'    = dU'[n] + Kd dS            (U's cotangent)
    dW     = -dU' states[n]^T
    dKd    = U'[n] dS^T
    dd     = sum(dS * states[n])       over dv (channel gate), or all
    dS     <- dstates[n] + d * dS - W^T dU'

so ``dS`` never leaves VMEM.  The residuals are ``W``, ``Kd``, the decay,
``states`` and ``U'``.  Both products of a chunk run at HIGHEST in the
order above, which is the recurrence's; a block's chunks and heads are
unrolled, so that the chains of a group's heads, independent of each
other, share the MXU.  A chunk past the end of a last, partial block
changes no state.  A decay of one a head is broadcast over the state's
rows below the call, so one kernel serves both gates.  Off the chip the
kernels run in interpret mode.

Gauge ``gdn.scan_steps``: every pass traced, forward or backward, adds
the chunks it walks in sequence (``ShardedTrainer`` sets it to 0 where it
begins to trace a step).  Counter ``gdn.state_kernel_calls``: kernel calls
traced, forward and backward.
"""
from __future__ import annotations

import functools

# heads a grid step: independent chains whose products the MXU interleaves
_HEADS = 4
# chunks a grid step: the sublanes of the decay's block
_BLOCK_CHUNKS = 8
_VMEM_LIMIT = 64 * 2 ** 20


def _interpret() -> bool:
    from .multi_sgd import _interpret as _i
    return _i()


def _count(chunks: int) -> None:
    from ..observability.registry import registry
    reg = registry()
    gauge = reg.gauge(
        "gdn.scan_steps", "dependent iterations of the delta rule's state "
        "passes traced since a trainer last began to trace its step")
    gauge.set(gauge.value + chunks)
    reg.counter("gdn.state_kernel_calls", "state-pass kernel calls traced, "
                "forward and backward").inc()


def _blocks(rows: int, chunks: int):
    """(heads, chunks) a grid step: the most heads up to ``_HEADS`` that
    divide ``rows``; ``_BLOCK_CHUNKS`` chunks, or all where fewer."""
    hb = next(d for d in range(min(rows, _HEADS), 0, -1) if rows % d == 0)
    return hb, min(chunks, _BLOCK_CHUNKS)


def _mm(a, b, contract):
    import jax.numpy as jnp
    from jax import lax
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _eye(dk):
    import jax.numpy as jnp
    from jax import lax
    return lax.broadcasted_iota(jnp.int32, (dk, dk), 0) == \
        lax.broadcasted_iota(jnp.int32, (dk, dk), 1)


def _column(row, eye):
    """A (1, dk) row as a (dk, 1) column: a masked sum, exact."""
    import jax.numpy as jnp
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(column, eye):
    """A (dk, 1) column as a (1, dk) row."""
    import jax.numpy as jnp
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _specs(hb, cb, c, dk, dv, block):
    """BlockSpecs of the (rows, chunks, ...) operands, and of the (rows,
    dk, dv) state that stays with a group: ``block(j)`` is the chunk block
    of the grid step j along the chunks."""
    from jax.experimental import pallas as pl

    def chunked(*tail):
        return pl.BlockSpec((hb, cb) + tail,
                            lambda i, j: (i, block(j)) + (0,) * len(tail))
    return {"ck": chunked(c, dk), "cv": chunked(c, dv), "d": chunked(dk),
            "s": chunked(dk, dv),
            "final": pl.BlockSpec((hb, dk, dv), lambda i, j: (i, 0, 0))}


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, name,
          interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(scratch, "float32")],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


@functools.lru_cache(maxsize=None)
def _forward_call(m, n, c, dk, dv, hb, cb, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    ragged = n % cb

    def kernel(w_ref, u_ref, kd_ref, d_ref, st_ref, un_ref, fin_ref, s_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            s_ref[...] = jnp.zeros(s_ref.shape, f32)

        eye = _eye(dk)
        s = [s_ref[h] for h in range(hb)]
        for t in range(cb):
            for h in range(hb):
                st_ref[h, t] = s[h]
                un = u_ref[h, t] - _mm(w_ref[h, t], s[h], ((1,), (0,)))
                un_ref[h, t] = un
                nxt = _column(d_ref[h, t:t + 1, :], eye) * s[h] + _mm(
                    kd_ref[h, t], un, ((0,), (0,)))
                s[h] = jnp.where(j * cb + t < n, nxt, s[h]) if ragged \
                    else nxt
        for h in range(hb):
            s_ref[h] = s[h]

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            fin_ref[...] = s_ref[...]

    sp = _specs(hb, cb, c, dk, dv, lambda j: j)
    return _call(
        kernel, (m // hb, pl.cdiv(n, cb)),
        [sp["ck"], sp["cv"], sp["ck"], sp["d"]],
        [sp["s"], sp["cv"], sp["final"]],
        [jax.ShapeDtypeStruct((m, n, dk, dv), f32),
         jax.ShapeDtypeStruct((m, n, c, dv), f32),
         jax.ShapeDtypeStruct((m, dk, dv), f32)],
        (hb, dk, dv), "gdn_state_pass_fwd", interpret)


@functools.lru_cache(maxsize=None)
def _backward_call(m, n, c, dk, dv, hb, cb, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    ragged = n % cb
    nb = pl.cdiv(n, cb)

    def kernel(w_ref, kd_ref, d_ref, st_ref, un_ref, dst_ref, dun_ref,
               dfin_ref, dw_ref, du_ref, dkd_ref, dd_ref, ds_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            ds_ref[...] = dfin_ref[...]

        eye = _eye(dk)
        ds = [ds_ref[h] for h in range(hb)]
        for t in reversed(range(cb)):
            for h in range(hb):
                states = st_ref[h, t]
                dut = dun_ref[h, t] + _mm(kd_ref[h, t], ds[h], ((1,), (0,)))
                du_ref[h, t] = dut
                dw_ref[h, t] = -_mm(dut, states, ((1,), (1,)))
                dkd_ref[h, t] = _mm(un_ref[h, t], ds[h], ((1,), (1,)))
                dd_ref[h, t:t + 1, :] = _row(jnp.sum(
                    ds[h] * states, axis=1, keepdims=True), eye)
                before = dst_ref[h, t] + _column(
                    d_ref[h, t:t + 1, :], eye) * ds[h] - _mm(
                        w_ref[h, t], dut, ((0,), (0,)))
                ds[h] = jnp.where((nb - 1 - j) * cb + t < n, before, ds[h]) \
                    if ragged else before
        for h in range(hb):
            ds_ref[h] = ds[h]

    sp = _specs(hb, cb, c, dk, dv, lambda j: nb - 1 - j)
    return _call(
        kernel, (m // hb, nb),
        [sp["ck"], sp["ck"], sp["d"], sp["s"], sp["cv"], sp["s"], sp["cv"],
         sp["final"]],
        [sp["ck"], sp["cv"], sp["ck"], sp["d"]],
        [jax.ShapeDtypeStruct((m, n, c, dk), f32),
         jax.ShapeDtypeStruct((m, n, c, dv), f32),
         jax.ShapeDtypeStruct((m, n, c, dk), f32),
         jax.ShapeDtypeStruct((m, n, dk), f32)],
        (hb, dk, dv), "gdn_state_pass_bwd", interpret)


def _rows(decay, dk):
    """The decay as (rows, chunks, dk): one a key channel, a head's one
    broadcast over them."""
    import jax.numpy as jnp
    b, h, n = decay.shape[:3]
    if decay.ndim == 3:
        decay = jnp.broadcast_to(decay[..., None], (b, h, n, dk))
    return decay.reshape((b * h, n, dk))


def _flat(x):
    """(B, H, ...) as (B H, ...): a change of shape alone."""
    return x.reshape((-1,) + x.shape[2:])


def _forward(w, u, kd, decay):
    b, h, n, c, dk = w.shape
    dv = u.shape[-1]
    m = b * h
    _count(n)
    call = _forward_call(m, n, c, dk, dv, *_blocks(m, n), _interpret())
    states, un, final = call(_flat(w), _flat(u), _flat(kd),
                             _rows(decay, dk))
    return (states.reshape((b, h) + states.shape[1:]),
            un.reshape(u.shape), final.reshape((b, h, dk, dv)))


def _backward(res, cts):
    import jax.numpy as jnp
    w, kd, decay, states, un = res
    d_states, d_un, d_final = cts
    b, h, n, c, dk = w.shape
    dv = un.shape[-1]
    m = b * h
    _count(n)
    call = _backward_call(m, n, c, dk, dv, *_blocks(m, n), _interpret())
    d_w, d_u, d_kd, d_decay = call(
        _flat(w), _flat(kd), _rows(decay, dk), _flat(states), _flat(un),
        _flat(d_states), _flat(d_un), _flat(d_final))
    d_decay = d_decay.reshape((b, h, n, dk))
    if decay.ndim == 3:
        d_decay = jnp.sum(d_decay, axis=-1)
    return (d_w.reshape(w.shape), d_u.reshape(un.shape),
            d_kd.reshape(kd.shape), d_decay)


@functools.lru_cache(maxsize=1)
def _state_pass_fn():
    import jax

    @jax.custom_vjp
    def state_pass(w, u, kd, decay):
        return _forward(w, u, kd, decay)

    def fwd(w, u, kd, decay):
        states, un, final = _forward(w, u, kd, decay)
        return (states, un, final), (w, kd, decay, states, un)
    state_pass.defvjp(fwd, _backward)
    return state_pass


def state_pass(w, u, kd, decay):
    """``w`` (B, H, N, C, dk), ``u`` (B, H, N, C, dv), ``kd`` = ``K *
    exp(gamma_C - gamma)`` (B, H, N, C, dk), ``decay`` = ``exp(gamma_C)``
    (B, H, N), or (B, H, N, dk) under the channel gate, all float32.
    Returns the state each chunk starts from (B, H, N, dk, dv), ``U'`` (B,
    H, N, C, dv) and the state after the last chunk (B, H, dk, dv).
    Differentiable in all four."""
    return _state_pass_fn()(w, u, kd, decay)
