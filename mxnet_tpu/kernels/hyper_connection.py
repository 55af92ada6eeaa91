"""Manifold-constrained hyper-connections (mHC: DeepSeek-AI 2025, on
Hyper-Connections: Zhu et al., ICLR 2025) as Pallas TPU kernels: a residual
stream of ``n`` streams a token, mixed around each sublayer by coefficients
that the state itself gives.  With ``X`` the (n, C) state of a token::

    x = RMSNorm(vec X)                       over the n C lanes, a gain
    h_pre  = sigmoid(a_pre  x phi_pre  + b_pre)           (n)
    h_post = 2 sigmoid(a_post x phi_post + b_post)        (n)
    H_res  = SK(clamp(a_res mat(x phi_res) + b_res, lo, hi))   (n, n)
    y = F(h_pre^T X)                          the sublayer reads one stream
    X <- H_res X + h_post y^T

``SK`` is Sinkhorn-Knopp: ``M = exp(.)``, then ``iters`` times every row
divided by its sum plus ``eps`` and every column by its, which leaves ``M``
doubly stochastic.  ``phi`` is one (R, n C) matrix, ``R = n n + 2 n``, its
rows in the order pre, post, res (row ``2 n + i n + j`` is ``H_res[i,
j]``); ``bias`` likewise; ``alpha`` holds ``(a_pre, a_post, a_res)``.  A
token's coefficients travel as one row of R, ``coef = [h_pre | h_post |
H_res]`` in that order (:func:`unpack`).

X arrives as (..., n C), the streams side by side in the lanes (stream j
the lanes [j C, (j + 1) C)), which the four kernels view as (tokens, n C)
with tokens on the grid (C padded to whole lane groups where it is not one,
whatever runs the kernel):

- ``hc_pre_fwd`` reads a tile of X once for the squared norm, the
  projection ``phi_g X^T`` (``phi_g = phi * gamma``: one MXU contraction
  that leaves the R coefficients on the sublanes and the tokens on the
  lanes, so that the Sinkhorn steps run on lane-dense rows), the
  coefficients, and the pre-mix ``sum_j h_pre_j X_j``, one stream at a
  time.  It writes x_in and a token's packed row for the backward:
  ``[coef | the projection | rsqrt | 0...]``.
- ``hc_mix_fwd``: ``H_res X + h_post y``, each output stream an
  elementwise sum over the input streams (no n-wide MXU contraction rounds
  the stream).
- ``hc_mix_bwd`` reads the update's cotangent, X and y once: dX, dy, and
  the coefficients' cotangents, reductions over C made in VMEM.
- ``hc_pre_bwd`` reads X and x_in's cotangent once, runs the Sinkhorn's
  steps again from the packed row and then back in reverse, and adds the
  norm's, the projection's and the pre-mix's terms into the update's dX in
  place (``input_output_aliases``).  ``phi_g``'s cotangent, a sum over
  tokens, is a block resident over the sequential token axis.

``pre`` hands X back beside its results and the model gives ``mix`` that
X: the update's cotangent of X then reaches ``pre``'s backward, which adds
its own terms to it instead of a pass of its own over the streams.  The
residuals are X and the packed rows; nothing of the size of the streams is
kept.  Everything is float32 whatever X is kept in, every contraction at
the highest precision.  The tile follows from a VMEM budget and the shape.
Off the chip the kernels run in interpret mode.

Counter ``hc.kernel_calls``: kernel calls traced, forward and backward.
"""
from __future__ import annotations

import functools

_LANES = 128
# bytes of a grid step's blocks that scale with its tokens, double-buffered;
# the scoped VMEM limit leaves room beside them for the kernels' temporaries
_BLOCK_BYTES = 40 * 2 ** 20
_VMEM_LIMIT = 100 * 2 ** 20
# a tile's tokens are a multiple of a packed 16-bit array's sublanes
_ROWS = 16


def _interpret() -> bool:
    from .multi_sgd import _interpret as _i
    return _i()


def _count() -> None:
    from ..observability.registry import registry
    registry().counter("hc.kernel_calls", "hyper-connection kernel calls "
                       "traced, forward and backward").inc()


def _widths(n: int):
    """(R, K): a token's coefficients, and its packed row's lanes."""
    r = n * n + 2 * n
    return r, -(-(2 * r + 1) // 8) * 8


def _tile(tokens: int, blocks) -> int:
    """Tokens a grid step: as many as ``_BLOCK_BYTES`` holds of the blocks
    ``[(lanes, itemsize)]`` a token, double-buffered; all where they fit."""
    per = 2 * sum(-(-w // _LANES) * _LANES * s for w, s in blocks)
    fit = max(_ROWS, _BLOCK_BYTES // per // _ROWS * _ROWS)
    return tokens if tokens <= fit else fit


def _mm(a, b, contract):
    import jax.numpy as jnp
    from jax import lax
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _call(kernel, grid, in_specs, out_specs, out_shape, name, interpret,
          sequential=False, **kw):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential else "parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name, **kw)


def _sinkhorn(rows, iters, eps):
    """Sinkhorn-Knopp on ``exp(rows)``, row i of the (n, n) matrices one
    (n, tokens) array with the columns on the sublanes: ``(exp(rows), the
    iterates before each step, the result)``."""
    import jax.numpy as jnp
    m0 = m = [jnp.exp(r) for r in rows]
    states = []
    for _ in range(iters):
        states.append(m)
        m = [r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in m]
        col = sum(m) + eps
        m = [r / col for r in m]
    return m0, states, m


def _sinkhorn_bwd(m0, states, g, eps):
    """The logits' cotangent from the result's, ``g`` (rows as in
    :func:`_sinkhorn`): each step made again from its iterate, in
    reverse."""
    import jax.numpy as jnp
    for prev in reversed(states):
        rs = [jnp.sum(r, axis=0, keepdims=True) + eps for r in prev]
        rn = [r / s for r, s in zip(prev, rs)]
        cs = sum(rn) + eps
        # columns: M_i = R_i / c, c = sum_i R_i + eps
        gm = sum(gi * ri for gi, ri in zip(g, rn)) / cs
        dr = [(gi - gm) / cs for gi in g]
        # rows: R_i = M_i / r_i, r_i = sum_j M_ij + eps
        g = [(d - jnp.sum(d * r, axis=0, keepdims=True)) / s
             for d, r, s in zip(dr, rn, rs)]
    return [gi * mi for gi, mi in zip(g, m0)]


@functools.lru_cache(maxsize=None)
def _pre_fwd_call(t, n, cp, width, tm, dtype, iters, eps, lo, hi,
                  interpret):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    r_, k_ = _widths(n)
    f32 = jnp.float32

    def kernel(x_ref, phig_ref, aff_ref, xin_ref, row_ref):
        x = x_ref[...].astype(f32)
        rs = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) / width + eps).T
        proj = _mm(phig_ref[...], x, ((1,), (1,))) * rs          # (R, tm)
        logit = proj * aff_ref[:, 0:1] + aff_ref[:, 1:2]
        res = jnp.clip(logit[2 * n:], lo, hi)
        _, _, h_res = _sinkhorn([res[i * n:(i + 1) * n] for i in range(n)],
                                iters, eps)
        row = jnp.concatenate(
            [jax.nn.sigmoid(logit[:n]), 2.0 * jax.nn.sigmoid(logit[n:2 * n]),
             *h_res, proj, rs,
             jnp.zeros((k_ - 2 * r_ - 1, rs.shape[1]), f32)]).T   # (tm, K)
        row_ref[...] = row
        x_in = sum(row[:, j:j + 1] * x_ref[:, j * cp:(j + 1) * cp].astype(f32)
                   for j in range(n))
        xin_ref[...] = x_in.astype(xin_ref.dtype)

    d = n * cp
    return _call(
        kernel, (pl.cdiv(t, tm),),
        [pl.BlockSpec((tm, d), lambda i: (i, 0)),
         pl.BlockSpec((r_, d), lambda i: (0, 0)),
         pl.BlockSpec((r_, 2), lambda i: (0, 0))],
        [pl.BlockSpec((tm, cp), lambda i: (i, 0)),
         pl.BlockSpec((tm, k_), lambda i: (i, 0))],
        [jax.ShapeDtypeStruct((t, cp), dtype),
         jax.ShapeDtypeStruct((t, k_), f32)],
        "hc_pre_fwd", interpret)


@functools.lru_cache(maxsize=None)
def _mix_fwd_call(t, n, cp, tm, dtype, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    r_ = _widths(n)[0]
    f32 = jnp.float32

    def kernel(x_ref, y_ref, c_ref, o_ref):
        y, coef = y_ref[...].astype(f32), c_ref[...]
        for i in range(n):
            out = coef[:, n + i:n + i + 1] * y
            for j in range(n):
                k = 2 * n + i * n + j
                out = out + coef[:, k:k + 1] * x_ref[
                    :, j * cp:(j + 1) * cp].astype(f32)
            o_ref[:, i * cp:(i + 1) * cp] = out.astype(o_ref.dtype)

    d = n * cp
    return _call(
        kernel, (pl.cdiv(t, tm),),
        [pl.BlockSpec((tm, d), lambda i: (i, 0)),
         pl.BlockSpec((tm, cp), lambda i: (i, 0)),
         pl.BlockSpec((tm, r_), lambda i: (i, 0))],
        pl.BlockSpec((tm, d), lambda i: (i, 0)),
        jax.ShapeDtypeStruct((t, d), dtype), "hc_mix_fwd", interpret)


@functools.lru_cache(maxsize=None)
def _mix_bwd_call(t, n, cp, tm, dtype, ydtype, interpret):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    r_ = _widths(n)[0]
    f32 = jnp.float32

    def kernel(g_ref, x_ref, y_ref, c_ref, dx_ref, dy_ref, dc_ref):
        y, coef = y_ref[...].astype(f32), c_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, coef.shape, 1)

        def stream(ref, j):
            return ref[:, j * cp:(j + 1) * cp].astype(f32)
        dc = jnp.zeros(coef.shape, f32)
        dy = 0.0
        for i in range(n):
            g = stream(g_ref, i)
            dy = dy + coef[:, n + i:n + i + 1] * g
            dc = jnp.where(lane == n + i, jnp.sum(g * y, axis=1,
                                                  keepdims=True), dc)
            for j in range(n):
                dc = jnp.where(lane == 2 * n + i * n + j, jnp.sum(
                    g * stream(x_ref, j), axis=1, keepdims=True), dc)
        dy_ref[...] = dy.astype(dy_ref.dtype)
        dc_ref[...] = dc
        for j in range(n):
            dx = sum(coef[:, 2 * n + i * n + j:2 * n + i * n + j + 1]
                     * stream(g_ref, i) for i in range(n))
            dx_ref[:, j * cp:(j + 1) * cp] = dx.astype(dx_ref.dtype)

    d = n * cp
    tokens = pl.BlockSpec((tm, d), lambda i: (i, 0))
    one = pl.BlockSpec((tm, cp), lambda i: (i, 0))
    coef = pl.BlockSpec((tm, r_), lambda i: (i, 0))
    return _call(
        kernel, (pl.cdiv(t, tm),), [tokens, tokens, one, coef],
        [tokens, one, coef],
        [jax.ShapeDtypeStruct((t, d), dtype),
         jax.ShapeDtypeStruct((t, cp), ydtype),
         jax.ShapeDtypeStruct((t, r_), f32)], "hc_mix_bwd", interpret)


@functools.lru_cache(maxsize=None)
def _pre_bwd_call(t, n, cp, width, tm, dtype, iters, eps, lo, hi,
                  interpret):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    r_, k_ = _widths(n)
    f32 = jnp.float32
    ragged = t % tm

    def kernel(x_ref, gin_ref, row_ref, dc_ref, acc_ref, phig_ref, aff_ref,
               dx_ref, dphig_ref, dlog_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            dphig_ref[...] = jnp.zeros(dphig_ref.shape, f32)

        def stream(ref, j):
            return ref[:, j * cp:(j + 1) * cp].astype(f32)
        gin, row = gin_ref[...].astype(f32), row_ref[...]
        # h_pre's cotangent, its own and the pre-mix's
        lane = lax.broadcasted_iota(jnp.int32, (row.shape[0], r_), 1)
        dc = dc_ref[...]
        for j in range(n):
            dc = jnp.where(lane == j, dc + jnp.sum(
                gin * stream(x_ref, j), axis=1, keepdims=True), dc)
        dc, rowl = dc.T, row.T                                  # (., tm)
        h_pre, h_post = rowl[:n], rowl[n:2 * n]
        proj, rs = rowl[r_:2 * r_], rowl[2 * r_:2 * r_ + 1]
        scale = aff_ref[:, 0:1]
        res = (proj * scale + aff_ref[:, 1:2])[2 * n:]
        m0, states, _ = _sinkhorn(
            [jnp.clip(res[i * n:(i + 1) * n], lo, hi) for i in range(n)],
            iters, eps)
        d_res = jnp.concatenate(_sinkhorn_bwd(
            m0, states, [dc[2 * n + i * n:2 * n + (i + 1) * n]
                         for i in range(n)], eps))
        dlog = jnp.concatenate([
            dc[:n] * h_pre * (1.0 - h_pre),
            dc[n:2 * n] * h_post * (1.0 - 0.5 * h_post),
            jnp.where((res >= lo) & (res <= hi), d_res, 0.0)])   # (R, tm)
        dproj = dlog * scale
        dz = dproj * rs
        # the norm's: d rs / d x = -rs^3 x / width
        knorm = -(rs * rs / width) * jnp.sum(dproj * proj, axis=0,
                                             keepdims=True)
        x = x_ref[...].astype(f32)
        if ragged:     # a last tile's rows past the tokens hold anything
            first = step * tm
            dz_in = jnp.where(first + lax.broadcasted_iota(
                jnp.int32, dz.shape, 1) < t, dz, 0.0)
            x = jnp.where(first + lax.broadcasted_iota(
                jnp.int32, x.shape, 0) < t, x, 0.0)
        else:
            dz_in = dz
        dphig_ref[...] += _mm(dz_in, x, ((1,), (0,)))
        pack = jnp.concatenate(
            [dz, knorm, dlog,
             jnp.zeros((k_ - 2 * r_ - 1, dz.shape[1]), f32)]).T  # (tm, K)
        dz, knorm = pack[:, :r_], pack[:, r_:r_ + 1]
        dlog_ref[...] = pack[:, r_ + 1:2 * r_ + 1]
        for j in range(n):
            cols = slice(j * cp, (j + 1) * cp)
            dx_ref[:, cols] = (
                stream(acc_ref, j) + row[:, j:j + 1] * gin
                + _mm(dz, phig_ref[:, cols], ((1,), (0,)))
                + knorm * stream(x_ref, j)).astype(dx_ref.dtype)

    d = n * cp
    tokens = pl.BlockSpec((tm, d), lambda i: (i, 0))
    weights = pl.BlockSpec((r_, d), lambda i: (0, 0))
    return _call(
        kernel, (pl.cdiv(t, tm),),
        [tokens, pl.BlockSpec((tm, cp), lambda i: (i, 0)),
         pl.BlockSpec((tm, k_), lambda i: (i, 0)),
         pl.BlockSpec((tm, r_), lambda i: (i, 0)), tokens, weights,
         pl.BlockSpec((r_, 2), lambda i: (0, 0))],
        [tokens, weights, pl.BlockSpec((tm, r_), lambda i: (i, 0))],
        [jax.ShapeDtypeStruct((t, d), dtype),
         jax.ShapeDtypeStruct((r_, d), f32),
         jax.ShapeDtypeStruct((t, r_), f32)],
        "hc_pre_bwd", interpret, sequential=True,
        input_output_aliases={4: 0})


# -- the operands as the kernels take them ------------------------------------

def _lanes(c: int) -> int:
    return -(-c // _LANES) * _LANES


def _n_of(r: int) -> int:
    """The streams whose coefficients are ``r = n n + 2 n``."""
    n = round((r + 1) ** 0.5) - 1
    if n < 1 or n * n + 2 * n != r:
        raise ValueError(f"{r} coefficients are no n^2 + 2n")
    return n


def _streams(x, n, cp):
    """(..., n C) -> (tokens, n cp), each stream padded to cp lanes."""
    import jax.numpy as jnp
    c = x.shape[-1] // n
    if cp == c:
        return x.reshape((-1, n * c))
    x = jnp.pad(x.reshape((-1, n, c)), ((0, 0), (0, 0), (0, cp - c)))
    return x.reshape((x.shape[0], n * cp))


def _unstreams(x, shape, n):
    c = shape[-1] // n
    return x.reshape((-1, n, x.shape[-1] // n))[..., :c].reshape(shape)


def _one(y, cp):
    """(..., C) -> (tokens, cp)."""
    import jax.numpy as jnp
    y = y.reshape((-1, y.shape[-1]))
    return y if y.shape[1] == cp else jnp.pad(y, ((0, 0),
                                                  (0, cp - y.shape[1])))


def _weights(phi, bias, alpha, gamma, n, cp):
    """``phi * gamma`` (R, n cp) and the logits' scale and shift (R, 2)."""
    import jax.numpy as jnp
    f32 = jnp.float32
    phig = _streams(phi.astype(f32) * gamma.astype(f32), n, cp)
    scale = jnp.concatenate([jnp.broadcast_to(a, (k,)) for a, k in
                             zip(alpha.astype(f32), (n, n, n * n))])
    return phig, jnp.stack([scale, bias.astype(f32)], axis=1)


def _pre_forward(x, phi, bias, alpha, gamma, iters, eps, clamp):
    import jax.numpy as jnp
    n = _n_of(phi.shape[0])
    c = x.shape[-1] // n
    cp, (r_, k_) = _lanes(c), _widths(n)
    xs = _streams(x, n, cp)
    t, size = xs.shape[0], x.dtype.itemsize
    tm = _tile(t, [(n * cp, size), (cp, size), (k_, 4)])
    phig, aff = _weights(phi, bias, alpha, gamma, n, cp)
    _count()
    x_in, row = _pre_fwd_call(t, n, cp, n * c, tm, jnp.dtype(x.dtype),
                              iters, eps, *clamp, _interpret())(xs, phig, aff)
    lead = x.shape[:-1]
    return (x_in[:, :c].reshape(lead + (c,)),
            row[:, :r_].reshape(lead + (r_,)), x), row


def _pre_backward(iters, eps, clamp, res, cts):
    import jax.numpy as jnp
    f32 = jnp.float32
    x, row, phi, bias, alpha, gamma = res
    d_in, d_coef, d_x = cts
    n = _n_of(phi.shape[0])
    c = x.shape[-1] // n
    cp, (r_, k_) = _lanes(c), _widths(n)
    xs = _streams(x, n, cp)
    t, size = xs.shape[0], x.dtype.itemsize
    tm = _tile(t, [(n * cp, size)] * 3 + [(cp, 4), (k_, 4), (r_, 4),
                                          (r_, 4)])
    phig, aff = _weights(phi, bias, alpha, gamma, n, cp)
    _count()
    dx, dphig, dlog = _pre_bwd_call(
        t, n, cp, n * c, tm, jnp.dtype(x.dtype), iters, eps, *clamp,
        _interpret())(xs, _one(d_in.astype(f32), cp), row,
                      d_coef.reshape((t, r_)).astype(f32),
                      _streams(d_x.astype(x.dtype), n, cp), phig, aff)
    dphig = _unstreams(dphig, phi.shape, n)
    proj = row[:, r_:2 * r_]
    groups = (slice(0, n), slice(n, 2 * n), slice(2 * n, r_))
    return (_unstreams(dx, x.shape, n),
            (dphig * gamma.astype(f32)).astype(phi.dtype),
            jnp.sum(dlog, axis=0).astype(bias.dtype),
            jnp.stack([jnp.sum(dlog[:, g] * proj[:, g]) for g in groups])
            .astype(alpha.dtype),
            jnp.sum(dphig * phi.astype(f32), axis=0).astype(gamma.dtype))


@functools.lru_cache(maxsize=None)
def _pre_fn(iters, eps, clamp):
    import jax

    @jax.custom_vjp
    def fn(x, phi, bias, alpha, gamma):
        return _pre_forward(x, phi, bias, alpha, gamma, iters, eps, clamp)[0]

    def fwd(x, phi, bias, alpha, gamma):
        out, row = _pre_forward(x, phi, bias, alpha, gamma, iters, eps,
                                clamp)
        return out, (x, row, phi, bias, alpha, gamma)
    fn.defvjp(fwd, functools.partial(_pre_backward, iters, eps, clamp))
    return fn


def _mix_forward(x, y, coef):
    n = _n_of(coef.shape[-1])
    c = x.shape[-1] // n
    cp = _lanes(c)
    xs = _streams(x, n, cp)
    t, size = xs.shape[0], x.dtype.itemsize
    tm = _tile(t, [(n * cp, size)] * 2 + [(cp, y.dtype.itemsize),
                                          (coef.shape[-1], 4)])
    _count()
    out = _mix_fwd_call(t, n, cp, tm, x.dtype, _interpret())(
        xs, _one(y, cp), coef.reshape((t, -1)))
    return _unstreams(out, x.shape, n)


def _mix_backward(res, g):
    x, y, coef = res
    n = _n_of(coef.shape[-1])
    c = x.shape[-1] // n
    cp = _lanes(c)
    xs = _streams(x, n, cp)
    t, size, ysize = xs.shape[0], x.dtype.itemsize, y.dtype.itemsize
    tm = _tile(t, [(n * cp, size)] * 3 + [(cp, ysize)] * 2
               + [(coef.shape[-1], 4)] * 2)
    _count()
    dx, dy, dc = _mix_bwd_call(t, n, cp, tm, x.dtype, y.dtype,
                               _interpret())(
        _streams(g.astype(x.dtype), n, cp), xs, _one(y, cp),
        coef.reshape((t, -1)))
    return (_unstreams(dx, x.shape, n), dy[:, :c].reshape(y.shape),
            dc.reshape(coef.shape))


@functools.lru_cache(maxsize=1)
def _mix_fn():
    import jax

    @jax.custom_vjp
    def fn(x, y, coef):
        return _mix_forward(x, y, coef)

    def fwd(x, y, coef):
        return _mix_forward(x, y, coef), (x, y, coef)
    fn.defvjp(fwd, _mix_backward)
    return fn


# -- the model's two calls ----------------------------------------------------

def pre(x, phi, bias, alpha, gamma, *, iters, eps, clamp):
    """x (..., n C), the n streams of a token side by side (n from phi's
    R rows); returns ``(x_in (..., C) in x's dtype, coef (..., R) float32,
    x)``: the sublayer's input, the coefficients ``[h_pre | h_post |
    H_res]`` (:func:`unpack`), and X again, which is what :func:`mix`
    takes.  Under the scopes ``hc/pre``."""
    import jax
    with jax.named_scope("hc"), jax.named_scope("pre"):
        return _pre_fn(int(iters), float(eps),
                       tuple(float(v) for v in clamp))(x, phi, bias, alpha,
                                                       gamma)


def mix(x, y, coef):
    """``H_res X + h_post y^T`` of x (..., n C), y (..., C) and
    :func:`pre`'s ``coef``, kept in x's dtype.  Under ``hc/mix``."""
    import jax
    with jax.named_scope("hc"), jax.named_scope("mix"):
        return _mix_fn()(x, y, coef)


def unpack(coef):
    """``(h_pre (..., n), h_post (..., n), H_res (..., n, n))`` of
    ``coef`` (..., n n + 2 n)."""
    n = _n_of(coef.shape[-1])
    return (coef[..., :n], coef[..., n:2 * n],
            coef[..., 2 * n:].reshape(coef.shape[:-1] + (n, n)))
