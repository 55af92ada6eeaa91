"""Plain reference for the ``mhc_mla_moe_lm`` family (Xing4.0): the
``mla_moe_lm`` decoder (latent attention, one leading dense SwiGLU block,
sparse-expert blocks with a shared expert, one MTP module) whose residual
path is four streams mixed by manifold-constrained hyper-connections (mHC),
with YaRN positions on the rotary lanes; trained with Adam, as
``benchmarks/configs/<config>.json`` states it.

Straightforward ``jax.numpy``: no kernel, no sorting (the expert layer is a
loop over the held experts with masks, each expert applied to every token),
nothing imported from the program under test.  RMSNorm, SwiGLU, the router
and the expert layer, the target masks, the cross-entropy and Adam are
``reference/mla_moe_lm.py``'s, imported and not edited.  The equations,
``X`` the (n, C) streams of a token (``n`` = ``hc_mult``):

- streams: ``X = [e, e, e, e]`` for the embedding ``e``; at the top ``h =
  sum_i X_i``, then the final RMSNorm and the head.
- each sublayer ``F`` (RMSNorm then MLA; RMSNorm then the dense SwiGLU or
  the expert layer): ``x = RMSNorm_g(vec X)`` over the n C lanes, eps
  ``hc_eps``; ``[p | q | r] = x Phi^T`` (4 + 4 + 16); ``h_pre =
  sigmoid(a_pre p + b_pre)``; ``h_post = 2 sigmoid(a_post q + b_post)``;
  ``H = SK(clamp(a_res mat(r) + b_res, min, max))`` with ``SK``: ``M =
  exp(.)``, then ``hc_sinkhorn_iters`` times rows divided by their sums
  plus ``hc_eps`` and columns by theirs; ``y = F(sum_i h_pre_i X_i)``;
  ``X <- H X + h_post y^T``.  In float32 whatever the activations are kept
  in (the configuration's precision), the contractions at the highest
  precision.
- MLA: ``cq = RMSNorm(x Wdq)``; ``[q_nope_h | q_rope_h] = cq Wuq``;
  ``[ckv | k_rope] = x Wdkv``; ``[k_nope_h | v_h] = RMSNorm(ckv) Wukv``;
  rotate-half RoPE with YaRN's frequencies (``yarn``, written out from the
  formula below) on ``q_rope_h`` and on ``k_rope``, one vector shared by
  the heads; causal softmax of ``q_h k_h^T * mscale^2 / sqrt(nope +
  rope)``; heads concatenated, then ``Wo``.  YaRN (DeepSeek-V3's form):
  frequency i of d rotary lanes is ``theta^(-2i/d)``, blended towards the
  same over ``factor`` by ``ramp_i = clip((i - low) / (high - low), 0,
  1)``, ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``,
  ``c(r) = d ln(orig / (2 pi r)) / (2 ln theta)``; cos and sin times
  ``m(mscale) / m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``; the
  softmax scale times ``m(mscale_all_dim)^2``.
- expert layer: ``reference/mla_moe_lm.expert_layer`` (sigmoid router over
  all experts in float32, top-k by score plus the fixed bias, renormalised,
  times ``routed_scaling_factor``; the shared expert once; the held
  experts' part alone).
- MTP: ``h' = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] Weh`` with ``h_i``
  the main stack's summed streams before the final norm; ``h'`` spread into
  four streams of its own; one sparse-expert block with its two
  hyper-connections; the streams summed; a final RMSNorm of its own; the
  shared head; cross-entropy against ``t_{i+2}``.  ``loss = CE(t_{i+1}) +
  mtp_loss_weight * CE_mtp(t_{i+2})``.

Departures from the published model, all stated in the configuration's
``assumed``: how the embedding enters the streams and how they leave, the
MTP module's streams, the coefficient norm's gain, the order of the
Sinkhorn steps, rotate-half pairing of the rotary lanes, the router's bias
a fixed buffer dealt by the seed, no balance loss, one sequence one
document.  The memory-saving devices here change no number: attention goes
by blocks of ``Q_BLOCK`` queries, every block of the model is under
``jax.checkpoint``, Adam's moments wait on the host while the gradient is
made and are updated a leaf at a time, the seed's weights are made again
leaf by leaf for the change.

``train_readings`` follows the first steps of training from the seed's
weights and returns what ``correct`` compares.  Its ``fault`` plants one
fault in the reference put in the program's place (the tests and PERF.md's
upper readings).  With ``dtype`` bfloat16 (the control) weights and
activations are bfloat16; the router and the hyper-connections' coefficient
path stay in float32, as the configuration keeps them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import mla_moe_lm as M
from . import transformer as T

ADAM_B1, ADAM_B2, ADAM_EPS = M.ADAM_B1, M.ADAM_B2, M.ADAM_EPS
Q_BLOCK = 256           # attention by query blocks above this many rows
INIT_STD = T.INIT_STD
HIGHEST = jax.lax.Precision.HIGHEST
SIDES = ("attn_hc_", "ffn_hc_")
FAULTS = ("half_batch", "state_unchanged", "sinkhorn_0", "sinkhorn_1",
          "post_without_2", "no_clamp", "no_hc_eps", "one_stream",
          "yarn_off", "no_mscale", "no_routed_scale", "no_shared_expert",
          "mtp_shift")


# -- weights -------------------------------------------------------------------

def _hc_spec(p, cfg):
    n, width = cfg["hc_mult"], cfg["hc_mult"] * cfg["hidden_size"]
    out = {}
    for side in SIDES:
        q = p + side
        out.update({q + "norm_g": (width,), q + "phi": (n * n + 2 * n, width),
                    q + "b": (n * n + 2 * n,), q + "alpha": (3,)})
    return out


def spec(cfg):
    s = M.spec(cfg)
    for i in range(cfg["num_hidden_layers"]):
        s.update(_hc_spec(f"l{i}.", cfg))
    if cfg["num_nextn_predict_layers"]:
        s.update(_hc_spec("mtp.", cfg))
    return s


def buffers(cfg):
    """The leaves no gradient reaches: the routers' selection bias."""
    return {k for k in spec(cfg) if k.endswith("router_b")}


def _given_leaves(cfg, seed):
    """On the host, from the seed: each expert layer's selection bias
    (``reference/mla_moe_lm.router_bias``); each hyper-connection's biases
    (pre and post N(0, 0.5), res N(0, 1)) and its three alphas U(0.01,
    0.1), so that H_res is far from uniform and h_post differs between the
    streams."""
    rng = np.random.default_rng(seed)
    n = cfg["hc_mult"]
    out = {}
    for k, shape in sorted(spec(cfg).items()):
        if k.endswith("router_b"):
            out[k] = M.router_bias(cfg, rng).astype(np.float32)
        elif k.endswith("_hc_b"):
            out[k] = np.concatenate([rng.normal(0.0, 0.5, 2 * n),
                                     rng.normal(0.0, 1.0, n * n)]) \
                .astype(np.float32)
        elif k.endswith("_hc_alpha"):
            out[k] = rng.uniform(0.01, 0.1, shape).astype(np.float32)
    return out


def _keep(w):
    return w


def _change(w0, w):
    return _norms({k: w[k] - w0[k] for k in w})


@functools.partial(jax.jit, static_argnums=(0, 1))
def _made(shapes, use, key, given, *args):
    """``use({leaf: the seed's value}, *args)``: N(0, 0.02) everywhere and
    norm gains 1 + N(0, 0.02), every leaf of ``shapes`` ((name, shape)
    pairs by name) from a key of its own (a leaf can be made again without
    the others), the leaves of ``given`` as given.  The seed enters as
    arguments alone, so one program serves every seed.  Whatever ``use``
    does not return is never held."""
    out = {}
    for i, (name, shape) in enumerate(shapes):
        if name in given:
            out[name] = given[name]
            continue
        v = INIT_STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
        out[name] = 1.0 + v if name.endswith("_g") else v
    return use(out, *args)


def _seeded(cfg, seed, use, *args):
    shapes = tuple(sorted((k, tuple(s)) for k, s in spec(cfg).items()))
    return _made(shapes, use, T.seed_key(seed), _given_leaves(cfg, seed),
                 *args)


def init_weights(cfg, seed):
    """Every leaf as the seed gives it."""
    return _seeded(cfg, seed, _keep)


def change_norms(cfg, seed, w):
    """``{leaf: |w[leaf] - the seed's leaf|}`` over the leaves of ``w``; the
    seed's leaves are made again inside the call and subtracted as they are
    made, so no second copy of the weights is held."""
    norms = _seeded(cfg, seed, _change, w)
    return {k: float(a) for k, a in norms.items()}


# -- positions -----------------------------------------------------------------

def yarn(cfg, dim, fault=None):
    """(inverse frequencies (dim // 2,), float64; the factor on cos and sin;
    the factor on the softmax scale), from the formula in the module's
    docstring.  ``fault`` ``yarn_off``: plain RoPE at ``rope_theta``, both
    factors 1; ``no_mscale``: the softmax factor 1."""
    theta = float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if fault == "yarn_off":
        return plain, 1.0, 1.0
    sc = cfg["rope_scaling"]
    factor, orig = float(sc["factor"]), sc["original_max_position_embeddings"]

    def c(rotations):
        return dim * math.log(orig / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0
    low = max(math.floor(c(sc["beta_fast"])), 0)
    high = min(math.ceil(c(sc["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    inv = plain / factor * ramp + plain * (1.0 - ramp)
    soft = 1.0 if fault == "no_mscale" else m(sc["mscale_all_dim"]) ** 2
    return inv, m(sc["mscale"]) / m(sc["mscale_all_dim"]), soft


def rope(x, cfg, fault=None):
    """Rotate-half rotary embedding over the whole last axis of ``x``
    (..., S, R) with ``yarn``'s tables; position i is row i.  The angles in
    float32, as any float32 implementation makes them."""
    s, r = x.shape[-2], x.shape[-1]
    inv, scale, _ = yarn(cfg, r, fault)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * (jnp.cos(ang) * scale)
            + rot.astype(jnp.float32) * (jnp.sin(ang) * scale)) \
        .astype(x.dtype)


# -- layers ------------------------------------------------------------------

_lin = M._lin
rms_norm = M.rms_norm


def causal_attention(q, k, v, scale):
    """q, k (B, H, S, D), v (B, H, S, Dv): full softmax over each query's
    own prefix, by blocks of ``Q_BLOCK`` queries so that the scores fit."""
    s = q.shape[2]
    kpos = jnp.arange(s)

    def rows(q_blk, q0):
        sc = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k).astype(jnp.float32) \
            * scale
        qpos = q0 + jnp.arange(q_blk.shape[2])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -1e30)
        att = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", att, v)

    if s <= Q_BLOCK:
        return rows(q, 0)
    n = s // Q_BLOCK
    qb = jnp.moveaxis(q.reshape(q.shape[:2] + (n, Q_BLOCK, q.shape[3])), 2, 0)
    out = jax.lax.map(lambda a: jax.checkpoint(rows)(a[0], a[1]),
                      (qb, jnp.arange(n) * Q_BLOCK))
    return jnp.moveaxis(out, 0, 2).reshape(q.shape[:3] + (v.shape[3],))


def mla(w, p, x, cfg, fault=None):
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    nope, rd, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    kvr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    cq = rms_norm(_lin(x, w[p + "q_down_w"]), w[p + "q_norm_g"], eps)
    q = _lin(cq, w[p + "q_up_w"]).reshape(b, s, heads, nope + rd)
    q = q.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg, fault)], -1)
    ckv = _lin(x, w[p + "kv_down_w"])
    k_rope = rope(ckv[..., kvr:], cfg, fault)
    kv = _lin(rms_norm(ckv[..., :kvr], w[p + "kv_norm_g"], eps),
              w[p + "kv_up_w"]).reshape(b, s, heads, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None], (b, heads, s, rd))], -1)
    soft = yarn(cfg, rd, fault)[2]
    o = causal_attention(q, k, kv[..., nope:], soft / math.sqrt(nope + rd))
    return _lin(o.transpose(0, 2, 1, 3).reshape(b, s, heads * vd),
                w[p + "proj_w"])


def sinkhorn(logits, iters, eps):
    """Sinkhorn-Knopp on ``exp(logits)`` over the last two axes: ``iters``
    times every row divided by its sum plus ``eps``, then every column."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_coefficients(w, q, xs, cfg, fault=None):
    """One hyper-connection's ``(h_pre (..., n), h_post (..., n), H (..., n,
    n))`` from the streams ``xs`` (..., n, C), float32."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    f32 = jnp.float32
    flat = xs.astype(f32).reshape(xs.shape[:-2] + (-1,))
    x = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                             + eps) * w[q + "norm_g"]
    proj = jnp.einsum("...i,oi->...o", x, w[q + "phi"], precision=HIGHEST)
    a, b = w[q + "alpha"], w[q + "b"]
    h_pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    h_post = (1.0 if fault == "post_without_2" else 2.0) \
        * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    logits = a[2] * proj[..., 2 * n:] + b[2 * n:]
    if fault != "no_clamp":
        logits = jnp.clip(logits, cfg["mhc_h_res_clamp_min"],
                          cfg["mhc_h_res_clamp_max"])
    iters = {"sinkhorn_0": 0, "sinkhorn_1": 1}.get(fault,
                                                     cfg["hc_sinkhorn_iters"])
    h = sinkhorn(logits.reshape(logits.shape[:-1] + (n, n)), iters,
                 0.0 if fault == "no_hc_eps" else eps)
    return h_pre, h_post, h


def sublayer(w, q, xs, cfg, fn, fault=None):
    """``fn`` (the sublayer: a norm and a mixer or a feed-forward) inside
    the hyper-connection of prefix ``q``; under the fault ``one_stream``
    the plain residual path ``x + fn(x)`` on one stream."""
    if fault == "one_stream":
        return xs + fn(xs)
    f32 = jnp.float32
    h_pre, h_post, h = hc_coefficients(w, q, xs, cfg, fault)
    xf = xs.astype(f32)
    x_in = jnp.einsum("...n,...nc->...c", h_pre, xf, precision=HIGHEST)
    y = fn(x_in.astype(xs.dtype)).astype(f32)
    out = jnp.einsum("...mn,...nc->...mc", h, xf, precision=HIGHEST) \
        + h_post[..., None] * y[..., None, :]
    return out.astype(xs.dtype)


def block(w, p, xs, cfg, dense, fault=None):
    eps = cfg["rms_norm_eps"]

    def attention(x):
        return mla(w, p, rms_norm(x, w[p + "attn_norm_g"], eps), cfg, fault)

    def ffn(x):
        h = rms_norm(x, w[p + "ffn_norm_g"], eps)
        if dense:
            return M.swiglu(h, w[p + "gate_w"], w[p + "up_w"], w[p + "down_w"])
        # the lower-precision control keeps the router in float32, as the
        # program does
        return M.expert_layer(w, p, h, cfg, fault if h.dtype == jnp.float32
                              else M.ROUTER_FLOAT32)
    xs = sublayer(w, p + SIDES[0], xs, cfg, attention, fault)
    return sublayer(w, p + SIDES[1], xs, cfg, ffn, fault)


def forward(w, cfg, tokens, dtype=jnp.float32, remat=False, fault=None):
    """(main logits, MTP logits or None), float32, (B, S, vocab) each."""
    eps = cfg["rms_norm_eps"]
    blk = jax.checkpoint(block, static_argnums=(1, 3, 4, 5)) if remat \
        else block

    def streams(x):
        if fault == "one_stream":
            return x
        return jnp.broadcast_to(x[:, :, None], x.shape[:2]
                                + (cfg["hc_mult"],) + x.shape[2:])

    def merged(xs):
        if fault == "one_stream":
            return xs
        return jnp.sum(xs.astype(jnp.float32), axis=2).astype(xs.dtype)

    xs = streams(w["embed"][tokens].astype(dtype))
    for i in range(cfg["num_hidden_layers"]):
        xs = blk(w, f"l{i}.", xs, cfg, M.is_dense(cfg, i), fault)
    x = merged(xs)

    def head(x, g):
        return _lin(rms_norm(x, g, eps), w["head"]).astype(jnp.float32)

    main = head(x, w["final_norm_g"])
    if not cfg["num_nextn_predict_layers"]:
        return main, None
    nxt = w["embed"][jnp.roll(tokens, -1, axis=1)].astype(dtype)
    h = jnp.concatenate([rms_norm(nxt, w["mtp.enorm_g"], eps),
                         rms_norm(x, w["mtp.hnorm_g"], eps)], axis=-1)
    hs = blk(w, "mtp.", streams(_lin(h, w["mtp.eh_proj_w"])), cfg, False,
             fault)
    return main, head(merged(hs), w["mtp.final_norm_g"])


def loss_fn(w, cfg, tokens, dtype=jnp.float32, remat=False, fault=None):
    """``CE(t_{i+1}) + mtp_loss_weight * CE_mtp(t_{i+2})``; under the fault
    ``mtp_shift`` the MTP head is scored against ``t_{i+1}``, the main
    head's target; under ``half_batch`` only the first half of the
    positions count."""
    main, mtp = forward(w, cfg, tokens, dtype, remat, fault)
    m1, m2 = M.target_masks(tokens.shape[1], fault)
    loss = M._ce(main, jnp.roll(tokens, -1, axis=1), m1[None])
    if mtp is not None:
        shift = -1 if fault == "mtp_shift" else -2
        loss = loss + cfg["mtp_loss_weight"] * M._ce(
            mtp, jnp.roll(tokens, shift, axis=1), m2[None])
    return loss


# -- Adam, as the configuration states it --------------------------------------

adam_step = M.adam_step
_norms = M._norms


def train_readings(cfg, seed, batches, lr, dtype=jnp.float32, steps=3,
                   remat=True, fault=None, grad_leaves=()):
    """Follow ``steps`` Adam steps from the seed's weights over
    ``batches[i] = ((tokens,), tokens)``; returns ``{"loss": [...],
    "grad_norm": {leaf: float}, "change_norm": {leaf: float},
    "grad_vector": {leaf: float32 host array for leaf in grad_leaves}}``
    over the leaves that have a gradient.

    ``fault`` is one of ``FAULTS``: ``half_batch`` counts only the first
    half of the sequence's targets; ``state_unchanged`` puts the weights
    back after every step; ``sinkhorn_0`` / ``sinkhorn_1`` run that many
    Sinkhorn steps; ``post_without_2`` leaves h_post's factor 2 out;
    ``no_clamp`` / ``no_hc_eps`` leave the clamp / the Sinkhorn's eps out;
    ``one_stream`` is the plain residual path; ``yarn_off`` plain RoPE and
    no softmax factor, ``no_mscale`` the factor alone left out; the others
    break one term of the expert layer or of the MTP loss."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    fixed = buffers(cfg)

    def grads(w, tokens):
        train = {k: a for k, a in w.items() if k not in fixed}
        rest = {k: a for k, a in w.items() if k in fixed}

        with T.arithmetic(dtype) as act:
            loss, g = jax.value_and_grad(lambda tr: loss_fn(
                {**tr, **rest}, cfg, tokens, act, remat, fault))(train)
        return loss, g, _norms(g), {k: g[k].astype(jnp.float32)
                                    for k in grad_leaves}

    def update(w, g, m, v, t):
        (w,), (m,), (v,) = (tuple(d.values()) for d in adam_step(
            {"leaf": w}, {"leaf": g}, {"leaf": m}, {"leaf": v}, t, lr))
        return w, m, v

    grad_step = jax.jit(grads)
    update_leaf = jax.jit(update, donate_argnums=(0, 1))
    w = init_weights(cfg, seed)
    # Adam's moments wait on the host while the gradient is made, and come
    # to the device a leaf at a time for the update: the weights, the
    # gradient and both moments of 913 M parameters would not fit beside
    # the activations
    m = {k: np.zeros(a.shape, np.float32) for k, a in w.items()
         if k not in fixed}
    v = {k: np.zeros(a.shape, np.float32) for k, a in m.items()}
    losses, gnorm, gvec = [], None, None
    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1][1], jnp.int32)
        loss, g, gn, gv = grad_step(w, tokens)
        for k in sorted(g):
            w[k], new_m, new_v = update_leaf(w[k], g.pop(k), m[k], v[k],
                                             jnp.float32(t))
            m[k], v[k] = np.asarray(new_m), np.asarray(new_v)
        if fault == "state_unchanged":
            del w               # first, or the two copies would not fit
            w = init_weights(cfg, seed)
        losses.append(float(loss))
        if t == 1:
            gnorm = {k: float(a) for k, a in gn.items()}
            gvec = {k: np.asarray(a) for k, a in gv.items()}
    del m, v
    return {"loss": losses, "grad_norm": gnorm, "grad_vector": gvec,
            "change_norm": change_norms(
                cfg, seed, {k: a for k, a in w.items() if k not in fixed})}
