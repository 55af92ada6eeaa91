"""Manifold-constrained hyper-connections (mHC: DeepSeek-AI 2025, on
Hyper-Connections: Zhu et al., ICLR 2025): a residual stream of ``n``
streams a token, mixed around each sublayer by coefficients that the
state itself gives.  With ``X`` the (n, C) state of a token::

    x = RMSNorm(vec X)                       over the n C lanes, a gain
    h_pre  = sigmoid(a_pre  x phi_pre  + b_pre)           (n)
    h_post = 2 sigmoid(a_post x phi_post + b_post)        (n)
    H_res  = SK(clamp(a_res mat(x phi_res) + b_res, lo, hi))   (n, n)
    y = F(h_pre^T X)                          the sublayer reads one stream
    X <- H_res X + h_post y^T

``SK`` is Sinkhorn-Knopp: ``M = exp(.)``, then ``iters`` times every row
divided by its sum plus ``eps`` and every column by its, which leaves ``M``
doubly stochastic.  ``phi`` is one (n n + 2 n, n C) matrix, its rows in the
order pre, post, res (row ``i n + j`` of res is ``H_res[i, j]``); ``bias``
likewise; ``alpha`` holds ``(a_pre, a_post, a_res)``.

Everything here is float32 whatever ``X`` is kept in, the projection at the
highest precision; the mixes over the streams are sums of elementwise
products, one stream at a time (a 4-wide contraction on the MXU would round
the residual stream, and one broadcast product would hold n copies of it).
``pre`` runs the coefficient path under the scopes ``hc/coeff``,
``hc/sinkhorn`` and ``hc/pre``, ``mix`` the update under ``hc/mix``: the
model calls the sublayer between the two.
"""


def sinkhorn(logits, iters, eps):
    """``iters`` Sinkhorn-Knopp steps on ``exp(logits)`` over the last two
    axes: rows divided by their sums plus ``eps``, then columns by theirs.
    Unrolled, so that its gradient goes through every step."""
    import jax.numpy as jnp
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def pre(x, phi, bias, alpha, gamma, *, iters, eps, clamp):
    """x (..., n, C); returns ``(x_in (..., C), h_post (..., n), h_res
    (..., n, n))``, all float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    f32 = jnp.float32
    n, c = x.shape[-2:]
    xf = x.astype(f32)
    with jax.named_scope("hc"):
        with jax.named_scope("coeff"):
            flat = xf.reshape(x.shape[:-2] + (n * c,))
            normed = flat * lax.rsqrt(
                jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps) \
                * gamma.astype(f32)
            proj = jnp.einsum("...i,oi->...o", normed, phi.astype(f32),
                              precision=lax.Precision.HIGHEST)
            a, b = alpha.astype(f32), bias.astype(f32)
            h_pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
            h_post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n]
                                          + b[n:2 * n])
            res = jnp.clip(a[2] * proj[..., 2 * n:] + b[2 * n:], clamp[0],
                           clamp[1])
        with jax.named_scope("sinkhorn"):
            h_res = sinkhorn(res.reshape(res.shape[:-1] + (n, n)), iters, eps)
        with jax.named_scope("pre"):
            x_in = sum(h_pre[..., j, None] * xf[..., j, :] for j in range(n))
    return x_in.astype(x.dtype), h_post, h_res


def mix(x, y, h_post, h_res):
    """``H_res X + h_post y^T``: x (..., n, C), y (..., C), kept in x's
    dtype."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    with jax.named_scope("hc"), jax.named_scope("mix"):
        xf = x.astype(f32)
        out = h_post[..., None] * y.astype(f32)[..., None, :]
        for j in range(x.shape[-2]):
            out = out + h_res[..., j, None] * xf[..., None, j, :]
    return out.astype(x.dtype)
