"""The plain reference of the window / full attention, grouped-query,
sparse-expert decoder for the tier-1 tests: a COPY of the forward pass and
the loss of ``benchmarks/reference/swa_moe_lm.py`` (the benchmark's own
tests are not tier-1; the equations and each assumption are in that file's
docstring).  Straightforward ``jax.numpy``, nothing imported from the
program under test.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
Q_BLOCK = 256           # attention by query blocks above this many rows
FAULTS = ("window_ignored", "window_short", "window_long", "rope_in_full",
          "no_rope_in_window", "kv_head_mod", "router_after_norm", "silu",
          "softmax_all")


def tiny_config(**over):
    """Hidden 64, 4 query / 2 key heads of 16, a window of 7, 8 experts of
    32 with 2 a token, two periods of (full, window, window, window)."""
    cfg = dict(vocab_size=61, hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=8, sliding_window_layout=[0, 1, 1, 1] * 2,
               rope_layout=[0, 1, 1, 1] * 2, sliding_window_size=7,
               moe_ffn_hidden_size=32, moe_num_primary_experts=8,
               moe_num_active_primary_experts=2, n_routed_experts_held=8,
               experts_held_first=0, rms_norm_eps=1e-6, rope_theta=1.5e6)
    cfg.update(over)
    return cfg


# -- weights -----------------------------------------------------------------

def spec(cfg):
    u, vocab, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, e = cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"]
    held = cfg["n_routed_experts_held"]
    s = {"embed": (vocab, u), "head": (vocab, u), "final_norm_g": (u,)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        s.update({p + "attn_norm_g": (u,), p + "q_w": (heads * d, u),
                  p + "k_w": (kv * d, u), p + "v_w": (kv * d, u),
                  p + "o_w": (u, heads * d), p + "ffn_norm_g": (u,),
                  p + "router_w": (e, u),
                  p + "experts_gate_w": (held, u, h),
                  p + "experts_up_w": (held, u, h),
                  p + "experts_down_w": (held, h, u)})
    return s


def init_weights(cfg, seed):
    """N(0, 0.02) everywhere and norm gains 1 + N(0, 0.02), from the seed.
    (The routers' logits are then near nought and the six gates near even;
    the tests that need a router with opinions scale ``router_w`` up.)"""
    out, key = {}, jax.random.PRNGKey(seed)
    for i, (name, shape) in enumerate(sorted(spec(cfg).items())):
        v = INIT_STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
        out[name] = 1.0 + v if name.endswith("_g") else v
    return out


# -- layers ------------------------------------------------------------------

def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def rope(x, theta):
    """Rotate-half rotary embedding over the whole last axis of ``x``
    (..., S, d); position i is row i."""
    s, r = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + rot.astype(jnp.float32) * jnp.sin(ang)).astype(x.dtype)


def attention(q, k, v, scale, window):
    """q, k, v (B, H, S, d): the softmax over the keys ``j <= i`` and, with
    a ``window``, ``i - j < window``; by blocks of ``Q_BLOCK`` queries so
    that the scores fit."""
    s = q.shape[2]
    kpos = jnp.arange(s)

    def rows(q_blk, q0):
        sc = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k).astype(jnp.float32) \
            * scale
        qpos = q0 + jnp.arange(q_blk.shape[2])
        keep = kpos[None, :] <= qpos[:, None]
        if window is not None:
            keep = keep & (qpos[:, None] - kpos[None, :] < window)
        att = jax.nn.softmax(jnp.where(keep, sc, -1e30), axis=-1) \
            .astype(v.dtype)
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


def attn_mixer(w, p, x, cfg, windowed, rotary, fault=None):
    b, s, _ = x.shape
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]

    def split(t, n):
        return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)
    q, k, v = split(_lin(x, w[p + "q_w"]), heads), \
        split(_lin(x, w[p + "k_w"]), kv), split(_lin(x, w[p + "v_w"]), kv)
    if fault == "rope_in_full":
        rotary = True
    if fault == "no_rope_in_window" and windowed:
        rotary = False
    if rotary:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    # the key head of each query head, copied out once for each
    of_head = np.arange(heads) % kv if fault == "kv_head_mod" \
        else np.arange(heads) // (heads // kv)
    window = cfg["sliding_window_size"] if windowed else None
    if window is not None:
        window = {"window_ignored": None, "window_short": window - 1,
                  "window_long": window + 1}.get(fault, window)
    o = attention(q, k[:, of_head], v[:, of_head], 1.0 / math.sqrt(d), window)
    return _lin(o.transpose(0, 2, 1, 3).reshape(b, s, heads * d),
                w[p + "o_w"])


def route(w, p, x, cfg, fault=None):
    """(selected experts (..., k) int32, their gates (..., k) float32) from
    ``x``, what the router reads."""
    if x.dtype == jnp.float32:
        r = jnp.einsum("...i,ei->...e", x, w[p + "router_w"],
                       precision=jax.lax.Precision.HIGHEST)
    else:       # the lower-precision control rounds the router as well
        r = _lin(x, w[p + "router_w"]).astype(jnp.float32)
    top, sel = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
    if fault == "softmax_all":
        return sel, jnp.take_along_axis(jax.nn.softmax(r, axis=-1), sel,
                                        axis=-1)
    return sel, jax.nn.softmax(top, axis=-1)


def expert_layer(w, p, x, sel, g, cfg, fault=None, held=None):
    """``held`` = (first, count) of the routed experts computed here (the
    configuration's share by default); the weights' leading axis is the
    held experts in order."""
    first, count = held if held is not None else \
        (cfg.get("experts_held_first", 0), cfg["n_routed_experts_held"])
    act = jax.nn.silu if fault == "silu" else jax.nn.relu
    y = jnp.zeros_like(x)
    for j in range(count):
        gate = jnp.sum(jnp.where(sel == first + j, g, 0.0), axis=-1)
        wg, wu, wd = (w[p + f"experts_{n}_w"][j].astype(x.dtype)
                      for n in ("gate", "up", "down"))
        h = act(jnp.einsum("...i,ih->...h", x, wg)) \
            * jnp.einsum("...i,ih->...h", x, wu)
        y = y + gate[..., None].astype(x.dtype) \
            * jnp.einsum("...h,ho->...o", h, wd)
    return y


def block(w, p, x, cfg, windowed, rotary, fault=None):
    eps = cfg["rms_norm_eps"]
    h1 = x + attn_mixer(w, p, rms_norm(x, w[p + "attn_norm_g"], eps), cfg,
                        windowed, rotary, fault)
    m = rms_norm(h1, w[p + "ffn_norm_g"], eps)
    sel, g = route(w, p, m if fault == "router_after_norm" else x, cfg, fault)
    return h1 + expert_layer(w, p, m, sel, g, cfg, fault)


def forward(w, cfg, tokens, dtype=jnp.float32, remat=False, fault=None):
    """The logits, float32, (B, S, vocab)."""
    blk = jax.checkpoint(block, static_argnums=(1, 3, 4, 5, 6)) if remat \
        else block
    x = w["embed"][tokens].astype(dtype)
    for i, (windowed, rotary) in enumerate(zip(cfg["sliding_window_layout"],
                                               cfg["rope_layout"])):
        x = blk(w, f"l{i}.", x, cfg, bool(windowed), bool(rotary), fault)
    x = rms_norm(x, w["final_norm_g"], cfg["rms_norm_eps"])
    return _lin(x, w["head"]).astype(jnp.float32)


def loss_fn(w, cfg, tokens, dtype=jnp.float32, remat=False, fault=None):
    """Mean cross-entropy of position i against token i + 1 over the
    positions that have one; under the fault ``half_batch`` only the first
    half of them count (the batch is one sequence)."""
    logits = forward(w, cfg, tokens, dtype, remat, fault)
    seq = tokens.shape[1]
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                              jnp.roll(tokens, -1, axis=1)[..., None],
                              axis=-1)[..., 0]
    pos = jnp.arange(seq)
    valid = (pos < (seq // 2 if fault == "half_batch" else seq - 1))
    valid = jnp.broadcast_to(valid.astype(jnp.float32)[None], ce.shape)
    return jnp.sum(ce * valid) / jnp.sum(valid)
