"""Plain reference for the ``mla_moe_lm`` family: a pre-norm causal decoder
with latent attention (MLA), one leading dense SwiGLU block, sparse-expert
blocks with a shared expert, and one multi-token-prediction (MTP) module,
trained with Adam, as ``benchmarks/configs/<config>.json`` states it.

Straightforward ``jax.numpy``: no kernel, no sorting (the expert layer is a
loop over the held experts with masks, each expert applied to every token),
nothing imported from the program under test.  The equations:

- block: ``x = x + MLA(RMSNorm(x))``; ``x = x + FFN(RMSNorm(x))``; the FFN
  is SwiGLU in the first ``first_k_dense_replace`` blocks, the expert layer
  after; a final RMSNorm, then the head.
- MLA: ``cq = RMSNorm(x Wdq)``; ``[q_nope_h | q_rope_h] = cq Wuq``;
  ``[ckv | k_rope] = x Wdkv``; ``[k_nope_h | v_h] = RMSNorm(ckv) Wukv``;
  RoPE (rotate-half, positions from 0) on ``q_rope_h`` and on ``k_rope``,
  one vector shared by all heads; ``q_h = [q_nope_h | q_rope_h]``,
  ``k_h = [k_nope_h | k_rope]``; causal softmax of
  ``q_h k_h^T / sqrt(nope + rope)``; heads concatenated, then ``Wo``.
- expert layer: ``s = sigmoid(x Wr)`` in float32 at the highest precision
  (the bfloat16 control, one step below everything the configuration
  states, rounds the router too); ``sel = top_k(s + b)``; ``g_e = s_e /
  (sum_{sel} s + 1e-20) * routed_scaling_factor``; ``y = Shared(x) +
  sum_{e in sel, e held} g_e E_e(x)``.  The experts that the deployment
  keeps on other chips add nothing here (the configuration's share).
- MTP: ``h' = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] Weh`` with ``h_i``
  the main stack's output before its final norm; one sparse-expert block;
  a final RMSNorm of its own; the shared head; cross-entropy against
  ``t_{i+2}``.  ``loss = CE(t_{i+1}) + mtp_loss_weight * CE_mtp(t_{i+2})``,
  each a mean over the positions that have a target.

Departures from the published model, all stated in the configuration's
``assumed``: the router's bias is a fixed buffer dealt by the seed; no
auxiliary balance loss; one sequence is one document (no mask at document
boundaries); the memory-saving devices here (attention by query blocks,
``jax.checkpoint`` per block and per head) change no number.

``train_readings`` follows the first steps of training from the seed's
weights and returns what ``correct`` compares.  Its ``fault`` plants one
fault in the reference put in the program's place (the tests and PERF.md's
upper readings).
"""
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as T

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK = 1024          # attention by query blocks above this many rows
FAULTS = ("half_batch", "state_unchanged", "top_k_minus_1", "no_routed_scale",
          "no_router_bias", "no_key_rope", "no_shared_expert", "mtp_shift")
# no fault, a second lower-precision control: bfloat16 weights and
# activations with the router kept in float32, as the program's own
# lower-precision path keeps it (``routed_experts`` casts to float32)
ROUTER_FLOAT32 = "router_float32"


# -- weights -----------------------------------------------------------------

def _attn_spec(p, cfg):
    u, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return {p + "attn_norm_g": (u,), p + "q_down_w": (qr, u),
            p + "q_norm_g": (qr,), p + "q_up_w": (heads * (nope + rope), qr),
            p + "kv_down_w": (kvr + rope, u), p + "kv_norm_g": (kvr,),
            p + "kv_up_w": (heads * (nope + vd), kvr),
            p + "proj_w": (u, heads * vd), p + "ffn_norm_g": (u,)}


def _dense_spec(p, cfg):
    u, i = cfg["hidden_size"], cfg["intermediate_size"]
    return {p + "gate_w": (i, u), p + "up_w": (i, u), p + "down_w": (u, i)}


def _moe_spec(p, cfg):
    u, h = cfg["hidden_size"], cfg["moe_intermediate_size"]
    hs = h * cfg["n_shared_experts"]
    e, held = cfg["n_routed_experts"], cfg["n_routed_experts_held"]
    return {p + "router_w": (e, u), p + "router_b": (e,),
            p + "shared_gate_w": (hs, u), p + "shared_up_w": (hs, u),
            p + "shared_down_w": (u, hs),
            p + "experts_gate_w": (held, u, h), p + "experts_up_w": (held, u, h),
            p + "experts_down_w": (held, h, u)}


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def spec(cfg):
    u, vocab = cfg["hidden_size"], cfg["vocab_size"]
    s = {"embed": (vocab, u), "head": (vocab, u), "final_norm_g": (u,)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        s.update(_attn_spec(p, cfg))
        s.update(_dense_spec(p, cfg) if is_dense(cfg, i)
                 else _moe_spec(p, cfg))
    if cfg["num_nextn_predict_layers"]:
        s.update({"mtp.enorm_g": (u,), "mtp.hnorm_g": (u,),
                  "mtp.eh_proj_w": (u, 2 * u), "mtp.final_norm_g": (u,)})
        s.update(_attn_spec("mtp.", cfg))
        s.update(_moe_spec("mtp.", cfg))
    return s


def buffers(cfg):
    """The leaves no gradient reaches: the router's selection bias."""
    return {k for k in spec(cfg) if k.endswith("router_b")}


def router_bias(cfg, rng):
    """One expert layer's selection bias: the ``n_routed_experts``
    quantiles of N(0, ``router_bias_std``), dealt by ``rng`` so that every
    chip's share of ``n_routed_experts_held`` consecutive experts holds one
    value of each stratum (the lowest eighth, the next, ...).  Which expert
    of a share is favoured is the seed's; how many token-assignments a
    share draws in all is nearly every seed's alike, so no seed changes the
    work (as the BERT cell deals one set of lengths in another order)."""
    e, held = cfg["n_routed_experts"], cfg["n_routed_experts_held"]
    dist = statistics.NormalDist(0.0, cfg["router_bias_std"])
    q = np.array([dist.inv_cdf((i + 0.5) / e) for i in range(e)], np.float32)
    if e % held:
        return rng.permutation(q)
    shares = e // held
    # stratum o is q[o * shares:(o + 1) * shares], one value to each share
    dealt = np.stack([rng.permutation(q[o * shares:(o + 1) * shares])
                      for o in range(held)], axis=1)        # (shares, held)
    return np.concatenate([rng.permutation(row) for row in dealt])


def init_weights(cfg, seed):
    """N(0, 0.02) everywhere and norm gains 1 + N(0, 0.02) from one draw of
    the seed; the routers' biases from ``router_bias``."""
    w = T.init_weights(spec(cfg), seed)
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(router_bias(cfg, rng)) if k.endswith("router_b")
            else a for k, a in sorted(w.items())}


# -- layers ------------------------------------------------------------------

def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def rope(x, theta):
    """Rotate-half rotary embedding over the whole last axis of ``x``
    (..., S, R); position i is row i."""
    s, r = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + rot.astype(jnp.float32) * jnp.sin(ang)).astype(x.dtype)


def causal_attention(q, k, v, scale):
    """q, k, v (B, H, S, D): full softmax over each query's own prefix, by
    blocks of ``Q_BLOCK`` queries so that the scores fit."""
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


def _lin(x, w):
    return jnp.einsum("...i,oi->...o", x, w.astype(x.dtype))


def mla(w, p, x, cfg, fault=None):
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    nope, rd, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    kvr, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        cfg["rope_theta"]
    cq = rms_norm(_lin(x, w[p + "q_down_w"]), w[p + "q_norm_g"], eps)
    q = _lin(cq, w[p + "q_up_w"]).reshape(b, s, heads, nope + rd)
    q = q.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    ckv = _lin(x, w[p + "kv_down_w"])
    k_rope = ckv[..., kvr:]
    if fault != "no_key_rope":
        k_rope = rope(k_rope, theta)
    kv = _lin(rms_norm(ckv[..., :kvr], w[p + "kv_norm_g"], eps),
              w[p + "kv_up_w"]).reshape(b, s, heads, nope + vd)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None], (b, heads, s, rd))], -1)
    o = causal_attention(q, k, kv[..., nope:], 1.0 / math.sqrt(nope + rd))
    return _lin(o.transpose(0, 2, 1, 3).reshape(b, s, heads * vd),
                w[p + "proj_w"])


def swiglu(x, wg, wu, wd):
    return _lin(jax.nn.silu(_lin(x, wg)) * _lin(x, wu), wd)


def route(w, p, x, cfg, fault=None):
    """(selected experts (..., k) int32, their gates (..., k) float32)."""
    k = cfg["num_experts_per_tok"] - (fault == "top_k_minus_1")
    if x.dtype == jnp.float32 or fault == ROUTER_FLOAT32:
        s = jax.nn.sigmoid(jnp.einsum(
            "...i,ei->...e", x.astype(jnp.float32), w[p + "router_w"],
            precision=jax.lax.Precision.HIGHEST))
    else:       # the lower-precision control rounds the router as well
        s = jax.nn.sigmoid(_lin(x, w[p + "router_w"]))
    choose = s if fault == "no_router_bias" else \
        s + w[p + "router_b"].astype(s.dtype)
    _, sel = jax.lax.top_k(choose, k)
    g = jnp.take_along_axis(s, sel, axis=-1).astype(jnp.float32)
    if cfg["norm_topk_prob"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        g = g * cfg["routed_scaling_factor"]
    return sel, g


def expert_layer(w, p, x, cfg, fault=None, held=None):
    """``held`` = (first, count) of the routed experts computed here (the
    configuration's share by default); the weights' leading axis is the
    held experts in order."""
    first, count = held if held is not None else \
        (cfg.get("experts_held_first", 0), cfg["n_routed_experts_held"])
    sel, g = route(w, p, x, cfg, fault)
    y = jnp.zeros_like(x) if fault == "no_shared_expert" else swiglu(
        x, w[p + "shared_gate_w"], w[p + "shared_up_w"],
        w[p + "shared_down_w"])
    for j in range(count):
        gate = jnp.sum(jnp.where(sel == first + j, g, 0.0), axis=-1)
        wg, wu, wd = (w[p + f"experts_{n}_w"][j].astype(x.dtype)
                      for n in ("gate", "up", "down"))
        h = jax.nn.silu(jnp.einsum("...i,ih->...h", x, wg)) \
            * jnp.einsum("...i,ih->...h", x, wu)
        y = y + gate[..., None].astype(x.dtype) \
            * jnp.einsum("...h,ho->...o", h, wd)
    return y


def block(w, p, x, cfg, dense, fault=None):
    eps = cfg["rms_norm_eps"]
    x = x + mla(w, p, rms_norm(x, w[p + "attn_norm_g"], eps), cfg, fault)
    h = rms_norm(x, w[p + "ffn_norm_g"], eps)
    if dense:
        return x + swiglu(h, w[p + "gate_w"], w[p + "up_w"], w[p + "down_w"])
    return x + expert_layer(w, p, h, cfg, fault)


def forward(w, cfg, tokens, dtype=jnp.float32, remat=False, fault=None):
    """(main logits, MTP logits or None), float32, (B, S, vocab) each."""
    eps = cfg["rms_norm_eps"]
    blk = jax.checkpoint(block, static_argnums=(1, 3, 4, 5)) if remat \
        else block
    x = w["embed"][tokens].astype(dtype)
    for i in range(cfg["num_hidden_layers"]):
        x = blk(w, f"l{i}.", x, cfg, is_dense(cfg, i), fault)

    def head(x, g):
        return _lin(rms_norm(x, g, eps), w["head"]).astype(jnp.float32)

    main = head(x, w["final_norm_g"])
    if not cfg["num_nextn_predict_layers"]:
        return main, None
    nxt = w["embed"][jnp.roll(tokens, -1, axis=1)].astype(dtype)
    h = jnp.concatenate([rms_norm(nxt, w["mtp.enorm_g"], eps),
                         rms_norm(x, w["mtp.hnorm_g"], eps)], axis=-1)
    h = blk(w, "mtp.", _lin(h, w["mtp.eh_proj_w"]), cfg, False, fault)
    return main, head(h, w["mtp.final_norm_g"])


def _ce(logits, targets, valid):
    """Mean cross-entropy over the positions where ``valid`` is 1."""
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                              targets[..., None], axis=-1)[..., 0]
    return jnp.sum(ce * valid) / jnp.sum(jnp.broadcast_to(valid, ce.shape))


def target_masks(seq, fault=None):
    """Which positions have a next token and a token after it; under the
    fault ``half_batch`` only the first half of them count."""
    pos = jnp.arange(seq)
    keep = pos < seq // 2 if fault == "half_batch" else pos < seq
    return ((pos < seq - 1) & keep).astype(jnp.float32), \
        ((pos < seq - 2) & keep).astype(jnp.float32)


def loss_fn(w, cfg, tokens, dtype=jnp.float32, remat=False, fault=None):
    main, mtp = forward(w, cfg, tokens, dtype, remat, fault)
    m1, m2 = target_masks(tokens.shape[1], fault)
    loss = _ce(main, jnp.roll(tokens, -1, axis=1), m1[None])
    if mtp is not None:
        shift = -3 if fault == "mtp_shift" else -2
        loss = loss + cfg["mtp_loss_weight"] * _ce(
            mtp, jnp.roll(tokens, shift, axis=1), m2[None])
    return loss


# -- Adam, as the configuration states it --------------------------------------

def adam_step(w, g, m, v, t, lr):
    """One Adam update of every leaf that has a gradient (no weight decay,
    bias-corrected lr), float32 whatever the gradients were computed in."""
    coef = jnp.sqrt(1.0 - ADAM_B2 ** t) / (1.0 - ADAM_B1 ** t)
    new_w, new_m, new_v = dict(w), {}, {}
    for k in g:
        gk = g[k].astype(jnp.float32)
        new_m[k] = ADAM_B1 * m[k] + (1 - ADAM_B1) * gk
        new_v[k] = ADAM_B2 * v[k] + (1 - ADAM_B2) * jnp.square(gk)
        new_w[k] = w[k] - (lr * coef) * new_m[k] \
            / (jnp.sqrt(new_v[k]) + ADAM_EPS)
    return new_w, new_m, new_v


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_readings(cfg, seed, batches, lr, dtype=jnp.float32, steps=3,
                   remat=True, fault=None, grad_leaves=()):
    """Follow ``steps`` Adam steps from the seed's weights over
    ``batches[i] = ((tokens,), tokens)``; returns ``{"loss": [...],
    "grad_norm": {leaf: float}, "change_norm": {leaf: float},
    "grad_vector": {leaf: float32 host array for leaf in grad_leaves}}``
    over the leaves that have a gradient.

    ``fault`` is one of ``FAULTS``: ``half_batch`` counts only the first
    half of each sequence's targets (the batch is one sequence);
    ``state_unchanged`` puts the weights back after every step; the others
    break one term of the model (see each use).  ``ROUTER_FLOAT32`` in its
    place breaks nothing: with ``dtype`` bfloat16 it keeps the router in
    float32."""
    if fault is not None and fault not in FAULTS + (ROUTER_FLOAT32,):
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    fixed = buffers(cfg)

    def one_step(w, m, v, tokens, t):
        train = {k: a for k, a in w.items() if k not in fixed}
        rest = {k: a for k, a in w.items() if k in fixed}

        with T.arithmetic(dtype) as act:
            loss, g = jax.value_and_grad(lambda tr: loss_fn(
                {**tr, **rest}, cfg, tokens, act, remat, fault))(train)
        return loss, _norms(g), {k: g[k].astype(jnp.float32)
                                 for k in grad_leaves}, \
            adam_step(w, g, m, v, t, lr)

    step = jax.jit(one_step, donate_argnums=(0, 1, 2))
    w = init_weights(cfg, seed)
    m = {k: jnp.zeros_like(a) for k, a in w.items() if k not in fixed}
    v = {k: jnp.zeros_like(a) for k, a in w.items() if k not in fixed}
    losses, gnorm, gvec = [], None, None
    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1][1], jnp.int32)
        loss, gn, gv, (w, m, v) = step(w, m, v, tokens, jnp.float32(t))
        if fault == "state_unchanged":
            w = init_weights(cfg, seed)
        losses.append(float(loss))
        if t == 1:
            gnorm = {k: float(a) for k, a in gn.items()}
            gvec = {k: np.asarray(a) for k, a in gv.items()}
    del m, v
    # the seed's weights are made again: a copy kept beside the state
    # would not fit the chip at the cell's size
    w0 = init_weights(cfg, seed)
    change = jax.jit(lambda a, b: _norms(
        {k: a[k] - b[k] for k in a if k not in fixed}))(w, w0)
    return {"loss": losses, "grad_norm": gnorm, "grad_vector": gvec,
            "change_norm": {k: float(a) for k, a in change.items()}}
