"""The channel gate's decayed products of a chunk as Pallas TPU kernels,
forward and backward (``gated_delta_rule._channel_rule``, scope
``subchunk``).

For ``x`` in {k, q}, chunks of C tokens and ``gamma`` the running sum of
the channel gate inside a chunk (falling along it)::

    P_x[i, j] = sum_d x_i[d] k_j[d] exp(gamma_i[d] - gamma_j[d])    i >= j

and nought above the diagonal.  A grid step holds ``nb`` chunks in VMEM
and splits each into sub-chunks of ``min(C, 16)`` tokens.  A sub-chunk's
rows against the keys before it are one matmul about its first token
``r``, ``(x_i exp(gamma_i - gamma_r)) (k_j exp(gamma_r - gamma_j))^T``,
both factors at most 1; against its own keys the decay is taken pair by
pair, channel by channel, on the vector units, with the exponent set to
-inf above the diagonal.  No exponent is positive and no bound on the gate
is relied on (a decay that underflows gives 0, as the recurrence's does).
The forward writes ``[P_k | P_q]`` as one (C, 2C) block, 128 lanes at
C = 64 (``gdn_decayed_products_fwd``).

The backward (``gdn_decayed_products_bwd``, under a ``jax.custom_vjp``
whose residuals are q, k and gamma alone) takes the cotangent D of that
block and makes, with ``E_ij = exp(gamma_i - gamma_j)`` and for each of
the two products, about the same sub-chunks and reference tokens::

    dx_i  = sum_{j <= i} D_ij k_j E_ij         (dq, or dk for P_k)
    dk_j += sum_{i >= j} D_ij x_i E_ij
    dgamma = x * dx - k * (the second sum)

with the diagonal's pairs left out of dgamma: they add ``x_i k_i D_ii`` to
both terms, which cancels to nought but leaves its rounding, undecayed,
where a strong gate makes dgamma small.  P reads gamma only through its
differences inside a chunk, so the exact dgamma sums to nought over a chunk,
channel by channel; the kernel takes its chunk mean off, the rounding of
the two sums, which a_log's gradient would read weighted by gamma itself.

Lanes are padded to whole lane groups (zeros add nothing, a zero gamma
decays by 1), whatever runs the kernel.  Every matmul is float32 at
HIGHEST.  Off the chip the kernels run in interpret mode.

Counter ``gdn.products_kernel_calls``: kernel calls traced, forward and
backward.
"""
from __future__ import annotations

import functools

# tokens a sub-chunk: its own pairs are taken one by one on the vector
# units, the pairs with the keys before it by the MXU
_SUB = 16
# chunks a grid step: 32 KB of each operand a chunk at C = 64, dk = 128
_BLOCK_CHUNKS = 8
_LANES = 128


def _interpret() -> bool:
    from .multi_sgd import _interpret as _i
    return _i()


def _block_of(chunks: int) -> int:
    return next(d for d in range(min(chunks, _BLOCK_CHUNKS), 0, -1)
                if chunks % d == 0)


def _count() -> None:
    from ..observability.registry import registry
    registry().counter("gdn.products_kernel_calls", "decayed-product kernel "
                       "calls traced, forward and backward").inc()


def _mm(a, b, contract):
    import jax.numpy as jnp
    from jax import lax
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _about_first(g, r):
    """The sub-chunk at ``r``'s decay from its first token to each of its
    rows, and from each key before it to that token (nought from the keys
    at and after it): ``(s, dkp)`` and ``(C, dkp)``, both at most 1."""
    import jax.numpy as jnp
    from jax import lax
    s = min(g.shape[0], _SUB)
    gr = g[r:r + 1]
    key = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    return (jnp.exp(g[r:r + s] - gr),
            jnp.exp(jnp.where(key < r, gr - g, -jnp.inf)))


def _pair_decay(ga, t, diagonal=True):
    """``exp(gamma_i - gamma_t)`` over a sub-chunk's rows i, channel by
    channel; nought for i < t, and for i = t unless ``diagonal``."""
    import jax.numpy as jnp
    from jax import lax
    row = lax.broadcasted_iota(jnp.int32, ga.shape, 0)
    return jnp.exp(jnp.where(row >= t + (not diagonal), ga - ga[t:t + 1],
                             -jnp.inf))


@functools.lru_cache(maxsize=None)
def _forward_call(m: int, c: int, dkp: int, nb: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = min(c, _SUB)

    def kernel(q_ref, k_ref, g_ref, o_ref):
        lane = lax.broadcasted_iota(jnp.int32, (s, 2 * c), 1)

        def chunk(b, carry):
            q, k, g = q_ref[b], k_ref[b], g_ref[b]
            for a in range(c // s):
                r = a * s
                ga, ka, qa = g[r:r + s], k[r:r + s], q[r:r + s]
                out = jnp.zeros((s, 2 * c), jnp.float32)
                if a:
                    down, up = _about_first(g, r)
                    kk = k * up
                    both = _mm(jnp.concatenate([ka * down, qa * down]),
                               jnp.concatenate([kk, kk]), ((1,), (1,)))
                    out = jnp.where(lane < c, both[:s], both[s:])
                for t in range(s):
                    m_t = ka[t:t + 1] * _pair_decay(ga, t)
                    out = jnp.where(lane == r + t, jnp.sum(
                        ka * m_t, axis=1, keepdims=True), out)
                    out = jnp.where(lane == c + r + t, jnp.sum(
                        qa * m_t, axis=1, keepdims=True), out)
                o_ref[b, r:r + s, :] = out
            return carry
        lax.fori_loop(0, nb, chunk, 0)

    spec = pl.BlockSpec((nb, c, dkp), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(m // nb,),
        in_specs=[spec] * 3,
        out_specs=pl.BlockSpec((nb, c, 2 * c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c, 2 * c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="gdn_decayed_products_fwd",
    )


@functools.lru_cache(maxsize=None)
def _backward_call(m: int, c: int, dkp: int, nb: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = min(c, _SUB)

    def kernel(q_ref, k_ref, g_ref, d_ref, dq_ref, dk_ref, dg_ref):
        lane = lax.broadcasted_iota(jnp.int32, (s, 2 * c), 1)
        at = lax.broadcasted_iota(jnp.int32, (s, 2 * c), 0)
        row = lax.broadcasted_iota(jnp.int32, (s, dkp), 0)

        def chunk(b, carry):
            q, k, g, d = q_ref[b], k_ref[b], g_ref[b], d_ref[b]
            # the second sum over the rows of the sub-chunks after a key's
            later = jnp.zeros((c, dkp), jnp.float32)
            for a in reversed(range(c // s)):
                r = a * s
                ga, ka, qa, da = g[r:r + s], k[r:r + s], q[r:r + s], \
                    d[r:r + s]
                ak = aq = jnp.zeros((s, dkp), jnp.float32)
                if a:
                    down, up = _about_first(g, r)
                    kk = k * up
                    # rows [0, s) against P_k's cotangent, [s, 2s) P_q's
                    dd = jnp.concatenate([jnp.where(lane < c, da, 0.0),
                                          jnp.where(lane < c, 0.0, da)])
                    both = _mm(dd, jnp.concatenate([kk, kk]), ((1,), (0,)))
                    ak, aq = down * both[:s], down * both[s:]
                    back = _mm(dd, jnp.concatenate([ka * down, qa * down]),
                               ((0,), (0,)))
                    later = later + up * (back[:c] + back[c:])
                # the pairs below the diagonal (the sub-chunk's last key
                # has none); the diagonal's, which gamma does not reach,
                # is added to dq and dk alone
                own = jnp.zeros((s, dkp), jnp.float32)
                for t in range(s - 1):
                    e_t = _pair_decay(ga, t, diagonal=False)
                    dk_t = da[:, r + t:r + t + 1]
                    dq_t = da[:, c + r + t:c + r + t + 1]
                    m_t = ka[t:t + 1] * e_t
                    ak = ak + dk_t * m_t
                    aq = aq + dq_t * m_t
                    own = jnp.where(row == t, jnp.sum(
                        (dk_t * ka + dq_t * qa) * e_t, axis=0,
                        keepdims=True), own)
                keys = own + later[r:r + s]
                dkd, dqd = (jnp.sum(jnp.where(lane == at + o, da, 0.0),
                                    axis=1, keepdims=True)
                            for o in (r, c + r))
                dq_ref[b, r:r + s, :] = aq + dqd * ka
                dk_ref[b, r:r + s, :] = ak + keys + 2.0 * dkd * ka + dqd * qa
                dg_ref[b, r:r + s, :] = ka * ak + qa * aq - ka * keys
            dg = dg_ref[b]
            dg_ref[b] = dg - jnp.mean(dg, axis=0, keepdims=True)
            return carry
        lax.fori_loop(0, nb, chunk, 0)

    spec = pl.BlockSpec((nb, c, dkp), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(m // nb,),
        in_specs=[spec] * 3 + [pl.BlockSpec((nb, c, 2 * c),
                                            lambda i: (i, 0, 0))],
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((m, c, dkp), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="gdn_decayed_products_bwd",
    )


def _operands(q, k, gamma):
    """(q, k, gamma) as (chunks, C, dkp), lanes padded, and the grid step's
    chunks; counts the call."""
    import jax.numpy as jnp
    c, dk = q.shape[-2:]
    xs = [x.reshape((-1, c, dk)) for x in (q, k, gamma)]
    if dk % _LANES:
        xs = [jnp.pad(x, ((0, 0), (0, 0), (0, -dk % _LANES))) for x in xs]
    _count()
    return xs, _block_of(xs[0].shape[0])


def _forward(q, k, gamma):
    c = q.shape[-2]
    xs, nb = _operands(q, k, gamma)
    out = _forward_call(*xs[0].shape, nb, _interpret())(*xs)
    out = out.reshape(q.shape[:-1] + (2 * c,))
    return out[..., :c], out[..., c:]


def _backward(res, cts):
    import jax.numpy as jnp
    q, k, gamma = res
    xs, nb = _operands(q, k, gamma)
    m, c, dkp = xs[0].shape
    d = jnp.concatenate(cts, axis=-1).reshape((m, c, 2 * c))
    grads = _backward_call(m, c, dkp, nb, _interpret())(*xs, d)
    return tuple(x[..., :q.shape[-1]].reshape(q.shape) for x in grads)


@functools.lru_cache(maxsize=1)
def _products_fn():
    import jax

    @jax.custom_vjp
    def products(q, k, gamma):
        return _forward(q, k, gamma)

    def fwd(q, k, gamma):
        return _forward(q, k, gamma), (q, k, gamma)
    products.defvjp(fwd, _backward)
    return products


def decayed_products(q, k, gamma):
    """``(P_k, P_q)`` (..., C, C) of ``q``, ``k``, ``gamma`` (..., C, dk),
    float32, ``gamma`` falling along C: ``P_x[i, j] = sum_d x_i[d] k_j[d]
    exp(gamma_i[d] - gamma_j[d])`` for ``i >= j``, nought above.  C is 16,
    32 or 64 (``gated_delta_rule.chunk_of``).  Differentiable in all
    three."""
    return _products_fn()(q, k, gamma)
