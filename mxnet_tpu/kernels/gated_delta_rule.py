"""The gated delta rule (Gated DeltaNet: Yang, Kautz, Hatamizadeh 2024), the
linear-attention layer whose cache is one fixed-size state a head, in the
chunked form that a TPU runs as matmuls; and the short depthwise causal
convolution that its layers put in front of it.

Per head, with a state ``S`` of (dk, dv) that starts at nought::

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay and ``beta_t`` the write strength (up to
2, which lets an eigenvalue of ``I - beta k k^T`` reach -1).  The caller
normalises and scales ``q`` and ``k``.

The chunked form, for a chunk of C tokens that the state ``S`` enters
(``gamma`` the running sum of ``g`` inside the chunk, ``Gam_ij = exp(gamma_i
- gamma_j)`` for i >= j)::

    A  = strictly_lower(diag(beta) (K K^T * Gam))      scope kkt
    T  = (I + A)^-1 diag(beta)                         scope inverse
    W  = T (K * exp(gamma)),  U = T V                  scope wu
    U' = U - W S                                       scope state
    S' = exp(gamma_C) S + (K * exp(gamma_C - gamma))^T U'
    O  = (Q * exp(gamma)) S + lower(Q K^T * Gam) U'    scope output

With the channel gate ``gamma`` is the running sum of ``g`` channel by
channel, and ``K K^T * Gam`` and ``Q K^T * Gam`` become sums inside the
channels, ``P_ij = sum_d x_i[d] k_j[d] exp(gamma_i[d] - gamma_j[d])`` (scope
subchunk); ``W``, ``S'`` and ``O``'s first term take ``exp(gamma)`` channel by
channel, and the state's decay ``exp(gamma_C)`` is a vector of dk.  ``P`` is
made by a pair of Pallas kernels, forward and backward, that hold a chunk in
VMEM (``decayed_products``): a product about a token between the keys and
the rows after them, the rest pair by pair, no positive exponent and no
bound on ``g`` relied on; nothing of tokens x C x dk is made, and nothing but
``q``, ``k`` and ``gamma`` is kept for the backward.

Everything but the pass ``S -> S'`` is batched over heads and chunks; that
pass is a pair of Pallas kernels, forward and backward, that hold each
head's state, and then its cotangent, in VMEM across the chunks and read
``W``, ``U`` and ``K * exp(gamma_C - gamma)`` where this module makes them
(``state_pass``; the rest is left to jax's own differentiation).  Sums of
``g``, exponentials, the inverse and the carried state are float32 whatever
the operands came in.

``(I + A)^-1``: ``A`` is nilpotent, so on a diagonal block of ``_BASE`` rows
the finite series ``(I - A)(I + A^2)(I + A^4)...`` is exact; blocks are then
merged in pairs, ``[[P, 0], [M, R]]^-1 = [[P^-1, 0], [-R^-1 M P^-1,
R^-1]]``, which keeps the intermediate values the size of the inverse's own
entries where the series over a whole chunk would not.

Gauges (set while a program that holds the op is traced, as the flash
kernel sets its tiling): ``gdn.heads``, ``gdn.key_dim``, ``gdn.value_dim``,
``gdn.chunk``, ``gdn.gate_lanes`` (1, or dk for the channel gate),
``gdn.state_bytes`` (the chunk states one call's backward holds) and
``gdn.scan_steps``, to which every state pass traced adds the chunks it
walks in sequence (``state_pass``): ``ShardedTrainer`` sets it to 0 where it
begins to trace a step (beside ``trainer.remat_kept_bytes``), so that after
the trace it reads the dependent iterations of one step, forward,
recomputed and backward, all layers.  Counter ``gdn.builds``.
"""
from __future__ import annotations

import functools

# tokens a chunk: the work inside a chunk grows with its square, the
# sequential pass shortens with it; at (2048, 30 heads of 96 / 192) forward
# and backward read 13.6 / 14.0 / 14.8 ms at 32 / 64 / 128 on a v5e (PERF.md
# section 6, PR 34).  Short sequences take the power of two that holds them
_MAX_CHUNK = 64
_BASE = 16
# heads that the channel gate's batched work takes at a time: a group's
# temporaries are a sixteenth of 32 heads' (ISSUE 40's bound on what one
# call holds)
_GROUP_HEADS = 2
# every matmul of the rule on float32 operands, six bfloat16 passes: at the
# chip's default (one pass) the same call reads 10.6 ms for 14.0 and its
# gradients 4e-3 from the recurrence's as vectors where these read 1e-6;
# with only the batched matmuls at the default 13.3 ms and the same 4e-3
_PRECISION = "highest"


def chunk_of(seq: int) -> int:
    """Tokens a chunk for a sequence of ``seq``: from the shape alone."""
    c = _BASE
    while c < min(seq, _MAX_CHUNK):
        c *= 2
    return c


@functools.lru_cache(maxsize=1)
def _unit_lower_inverse_fn():
    """``a -> (I + a)^-1`` for strictly lower-triangular ``a`` (..., c, c)
    with c a power-of-two multiple of ``_BASE`` (or less than it); its
    backward is ``-inv^T g inv^T``, so nothing of the way there is kept."""
    import jax
    import jax.numpy as jnp

    def mm(x, y):
        return jnp.matmul(x, y, precision=_PRECISION)

    def forward(a):
        c = a.shape[-1]
        base = min(c, _BASE)
        blocks = jnp.stack([a[..., i * base:(i + 1) * base,
                              i * base:(i + 1) * base]
                            for i in range(c // base)], axis=-3)
        inv = jnp.eye(base, dtype=a.dtype) - blocks
        power, reach = blocks, 2
        while reach < base:             # a^base = 0 on a block of base rows
            power = mm(power, power)
            inv = inv + mm(inv, power)
            reach *= 2
        size = base
        while size < c:
            merged = []
            for j in range(0, c // size, 2):
                lo = j * size
                p, r = inv[..., j, :, :], inv[..., j + 1, :, :]
                off = -mm(mm(r, a[..., lo + size:lo + 2 * size,
                                  lo:lo + size]), p)
                merged.append(jnp.concatenate(
                    [jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
                     jnp.concatenate([off, r], axis=-1)], axis=-2))
            inv = jnp.stack(merged, axis=-3)
            size *= 2
        return inv[..., 0, :, :]

    @jax.custom_vjp
    def inverse(a):
        return forward(a)

    def fwd(a):
        inv = forward(a)
        return inv, inv

    def bwd(inv, g):
        inv_t = jnp.swapaxes(inv, -1, -2)
        return (-mm(mm(inv_t, g), inv_t),)
    inverse.defvjp(fwd, bwd)
    return inverse


def gated_delta_rule(q, k, v, g, beta):
    """``q``, ``k`` (B, L, H, dk), ``v`` (B, L, H, dv), ``beta`` (B, L, H),
    ``g`` (B, L, H), one decay a head, or (B, L, H, dk), one a key channel
    (the channel gate).  Returns the outputs (B, L, H, dv) in ``v``'s type
    and the state after the last token (B, H, dk, dv), float32.
    Differentiable in all five operands."""
    import jax
    import jax.numpy as jnp
    from ..observability.registry import registry
    from .state_pass import state_pass

    b, seq, h, dk = q.shape
    dv = v.shape[-1]
    c = chunk_of(seq)
    n = -(-seq // c)
    reg = registry()
    reg.counter("gdn.builds", "delta-rule calls traced").inc()
    channel = g.ndim == 4
    for name, value in (("heads", h), ("key_dim", dk), ("value_dim", dv),
                        ("chunk", c), ("gate_lanes", dk if channel else 1),
                        ("state_bytes", n * b * h * dk * dv * 4)):
        reg.gauge(f"gdn.{name}", "of the last delta-rule call traced"
                  ).set(value)

    def chunks(x):
        """(B, L, H, ...) -> (B, H, N, C, ...), float32; the tokens that
        fill the last chunk write nothing (beta 0) and decay nothing."""
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, n * c - seq)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 3, 1)
    out_dtype = v.dtype
    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))

    def mm(eq, x, y):
        return jnp.einsum(eq, x, y, precision=_PRECISION)
    lower = jnp.tril(jnp.ones((c, c), bool))
    if channel:
        return _channel_rule(q, k, v, g, beta, lower, mm, seq, out_dtype)
    with jax.named_scope("decay"):
        gamma = jnp.cumsum(g, axis=-1)                      # (B, H, N, C)
        gam = jnp.exp(jnp.where(
            lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        grown = jnp.exp(gamma)[..., None]
        total = gamma[..., -1]
        rest = jnp.exp(total[..., None] - gamma)[..., None]
    with jax.named_scope("kkt"):
        a = jnp.where(jnp.tril(lower, -1),
                      beta[..., None] * mm("bhncd,bhnsd->bhncs", k, k) * gam,
                      0.0)
    with jax.named_scope("inverse"):
        t = _unit_lower_inverse_fn()(a) * beta[..., None, :]
    with jax.named_scope("wu"):
        w = mm("bhncs,bhnsd->bhncd", t, k * grown)
        u = mm("bhncs,bhnsd->bhncd", t, v)
    with jax.named_scope("state"):
        states, un, final = state_pass(w, u, k * rest, jnp.exp(total))
    with jax.named_scope("output"):
        o = mm("bhncd,bhndv->bhncv", q * grown, states) + mm(
            "bhncs,bhnsv->bhncv", mm("bhncd,bhnsd->bhncs", q, k) * gam, un)
        o = jnp.moveaxis(o, 1, 3).reshape(b, n * c, h, dv)[:, :seq]
    return o.astype(out_dtype), final


def _by_rows(xs, rows):
    """(B, H, ...) operands as (groups, rows / groups, ...): the B x H rows
    (each a head of a sequence, independent of the others until the state
    pass) in groups of at most ``_GROUP_HEADS``, along a leading axis (a
    change of shape alone), and the way back."""
    per = next(d for d in range(min(rows, _GROUP_HEADS), 0, -1)
               if rows % d == 0)

    def merge(x):
        return x.reshape(xs[0].shape[:2] + x.shape[2:])
    return tuple(x.reshape((rows // per, per) + x.shape[2:])
                 for x in xs), merge


def _channel_rule(q, k, v, g, beta, lower, mm, seq, out_dtype):
    """``gated_delta_rule``'s chunked form under the channel gate, from the
    chunked float32 operands ((B, H, N, C, ...); ``g`` (B, H, N, C, dk)).
    What is batched over chunks is done a group of rows (heads) at a time:
    two ``lax.map``s over the groups with checkpointed bodies, one before
    the state pass (``W``, ``U``, ``K * exp(gamma_C - gamma)``, the decay
    and the decayed ``Q K^T``) and one after it (the output), so that
    nothing but their inputs and outputs is kept for the backward and one
    group's temporaries are all that any moment holds besides."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from .decayed_products import decayed_products
    from .state_pass import state_pass

    b, h, n, c, dv = v.shape

    def gamma_of(g):
        with jax.named_scope("decay"):
            return jnp.cumsum(g, axis=-2)                # (..., N, C, dk)

    def before(xs):
        q, k, v, g, beta = xs
        gamma = gamma_of(g)
        with jax.named_scope("decay"):
            total = gamma[..., -1, :]
            grown = jnp.exp(gamma)
            rest = jnp.exp(total[..., None, :] - gamma)
        with jax.named_scope("subchunk"):
            pk, pq = decayed_products(q, k, gamma)
        with jax.named_scope("kkt"):
            a = jnp.where(jnp.tril(lower, -1), beta[..., None] * pk, 0.0)
        with jax.named_scope("inverse"):
            t = _unit_lower_inverse_fn()(a) * beta[..., None, :]
        with jax.named_scope("wu"):
            w = mm("...cs,...sd->...cd", t, k * grown)
            u = mm("...cs,...sd->...cd", t, v)
            kd = k * rest
        return w, u, kd, jnp.exp(total), pq

    def after(xs):
        q, g, states, un, pq = xs
        grown = jnp.exp(gamma_of(g))
        with jax.named_scope("output"):
            return mm("...cd,...dv->...cv", q * grown, states) + mm(
                "...cs,...sv->...cv", pq, un)

    groups, merge = _by_rows((q, k, v, g, beta), b * h)
    w, u, kd, decay, pq = (merge(x) for x in lax.map(
        jax.checkpoint(before), groups))
    with jax.named_scope("state"):
        states, un, final = state_pass(w, u, kd, decay)
    groups, merge = _by_rows((q, g, states, un, pq), b * h)
    o = merge(lax.map(jax.checkpoint(after), groups))
    with jax.named_scope("output"):
        o = jnp.moveaxis(o, 1, 3).reshape(b, n * c, h, dv)[:, :seq]
    return o.astype(out_dtype), final


def causal_conv1d(x, weight):
    """Depthwise causal convolution over time, no bias: ``x`` (B, L, C),
    ``weight`` (C, K); ``y[t, c] = sum_j weight[c, j] x[t - (K - 1) + j,
    c]`` with zeros before the first token."""
    import jax.numpy as jnp
    taps = weight.shape[1]
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + seq] * weight[:, j].astype(x.dtype)
               for j in range(taps))
