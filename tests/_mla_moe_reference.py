"""The plain reference of the MLA / sparse-expert / MTP decoder for the
tier-1 tests: a COPY of the forward pass and the loss of
``benchmarks/reference/mla_moe_lm.py`` (the benchmark's own tests are not
tier-1; the equations and each departure are in that file's docstring).
Straightforward ``jax.numpy``, nothing imported from the program under test.
"""
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
Q_BLOCK = 1024          # attention by query blocks above this many rows


def tiny_config(**over):
    """Hidden 64, 2 heads, ranks 24/16, a head of 12 + 4 / 16, 8 experts
    with 2 a token, 1 dense + 2 sparse-expert blocks + MTP."""
    cfg = dict(vocab_size=61, hidden_size=64, num_hidden_layers=3,
               num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
               intermediate_size=96, moe_intermediate_size=32,
               n_routed_experts=8, n_routed_experts_held=8,
               experts_held_first=0, num_experts_per_tok=2,
               n_shared_experts=1, routed_scaling_factor=1.8,
               norm_topk_prob=True, first_k_dense_replace=1,
               num_nextn_predict_layers=1, rms_norm_eps=1e-5,
               rope_theta=1e6, mtp_loss_weight=0.3, router_bias_std=0.05)
    cfg.update(over)
    return cfg


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


def init_weights(cfg, seed):
    """N(0, 0.02) everywhere, norm gains 1 + N(0, 0.02), the router's bias
    N(0, ``router_bias_std``), from the seed."""
    out, key = {}, jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(sorted(spec(cfg).items())):
        v = INIT_STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
        if name.endswith("router_b"):
            v = v * (cfg["router_bias_std"] / INIT_STD)
        out[name] = 1.0 + v if name.endswith("_g") else v
    return out


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
    if x.dtype == jnp.float32:
        s = jax.nn.sigmoid(jnp.einsum(
            "...i,ei->...e", x, w[p + "router_w"],
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
