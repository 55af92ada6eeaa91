"""The plain reference of the hybrid delta-rule / attention decoder for the
tier-1 tests: a COPY of ``benchmarks/reference/gdn_hybrid_lm.py`` (the
benchmark's own tests are not tier-1; the equations and each departure are
in that file's docstring), with a tiny configuration and the seed's weights
from one draw.  The delta rule is its per-token recurrence.  Straightforward
``jax.numpy``, nothing imported from the program under test.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK = 1024          # attention by query blocks above this many rows
TOKEN_BLOCK = 64        # the recurrence's checkpointed blocks of tokens
L2_EPS = 1e-6
FAULT_ROPE_THETA = 500000.0
INIT_STD = 0.02
SEED_KEY = jax.random.PRNGKey
FAULTS = ("half_batch", "state_unchanged", "no_decay", "beta_not_doubled",
          "no_conv", "no_k_l2norm", "no_output_gate", "no_qk_norm", "rope",
          "pre_norm")
LINEAR, FULL = "linear_attention", "full_attention"


def tiny_config(**over):
    """Hidden 64; two periods of (delta rule, delta rule, attention); 2
    heads of 32 for attention, 2 of 16 / 24 for the delta rule; 4 taps."""
    cfg = dict(vocab_size=61, hidden_size=64, intermediate_size=96,
               num_attention_heads=2, rms_norm_eps=1e-6,
               layer_types=[LINEAR, LINEAR, FULL] * 2,
               linear_num_key_heads=2, linear_num_value_heads=2,
               linear_key_head_dim=16, linear_value_head_dim=24,
               linear_conv_kernel_dim=4, linear_allow_neg_eigval=True)
    cfg.update(over)
    return cfg


# -- weights -----------------------------------------------------------------

def _gdn_spec(p, cfg):
    u, h = cfg["hidden_size"], cfg["linear_num_value_heads"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = h * cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    return {p + "gdn_q_w": (kd, u), p + "gdn_k_w": (kd, u),
            p + "gdn_v_w": (vd, u), p + "gdn_gate_w": (vd, u),
            p + "gdn_o_w": (u, vd), p + "gdn_a_w": (h, u),
            p + "gdn_b_w": (h, u), p + "gdn_q_conv": (kd, taps),
            p + "gdn_k_conv": (kd, taps), p + "gdn_v_conv": (vd, taps),
            p + "gdn_a_log": (h,), p + "gdn_dt_bias": (h,),
            p + "gdn_norm_g": (cfg["linear_value_head_dim"],)}


def _attn_spec(p, cfg):
    u = cfg["hidden_size"]
    return {p + "attn_q_w": (u, u), p + "attn_k_w": (u, u),
            p + "attn_v_w": (u, u), p + "attn_o_w": (u, u),
            p + "attn_q_norm_g": (u,), p + "attn_k_norm_g": (u,)}


def spec(cfg):
    u, i, vocab = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"]
    s = {"embed": (vocab, u), "head": (vocab, u), "final_norm_g": (u,)}
    for n, kind in enumerate(cfg["layer_types"]):
        p = f"l{n}."
        if kind not in (LINEAR, FULL):
            raise ValueError(f"layer_types[{n}] = {kind!r} is neither "
                             f"{LINEAR!r} nor {FULL!r}")
        s.update(_gdn_spec(p, cfg) if kind == LINEAR else _attn_spec(p, cfg))
        s.update({p + "mixer_norm_g": (u,), p + "ffn_norm_g": (u,),
                  p + "ffn_gate_w": (i, u), p + "ffn_up_w": (i, u),
                  p + "ffn_down_w": (u, i)})
    return s


def _decay_leaves(cfg, seed):
    """Each delta-rule layer's ``A_log = log U(1, 16)`` and ``dt_bias =
    softplus^-1(exp(U(log 0.001, log 0.1)))`` (the initialisation of the
    ``fla`` layer), from the seed, on the host."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, shape in sorted(spec(cfg).items()):
        if k.endswith("gdn_a_log"):
            out[k] = np.log(rng.uniform(1.0, 16.0, shape)).astype(np.float32)
        elif k.endswith("gdn_dt_bias"):
            dt = np.exp(rng.uniform(math.log(0.001), math.log(0.1), shape))
            out[k] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    return out


def init_weights(cfg, seed):
    """N(0, 0.02) everywhere and norm gains 1 + N(0, 0.02) from one draw of
    the seed (the benchmark's reference draws leaf by leaf, so that a leaf
    can be made again alone at the cell's size); the decay leaves from
    ``_decay_leaves``."""
    shapes = spec(cfg)
    names = sorted(shapes)
    sizes = [math.prod(shapes[n]) for n in names]
    flat = INIT_STD * jax.random.normal(SEED_KEY(seed), (sum(sizes),),
                                        jnp.float32)
    out, lo = dict(_decay_leaves(cfg, seed)), 0
    for name, size in zip(names, sizes):
        v = flat[lo:lo + size].reshape(shapes[name])
        lo += size
        if name not in out:
            out[name] = 1.0 + v if name.endswith("_g") else v
    return {k: jnp.asarray(out[k]) for k in names}


def change_norms(cfg, seed, w):
    """``{leaf: |w[leaf] - the seed's leaf|}``."""
    w0 = init_weights(cfg, seed)
    return {k: float(a) for k, a in
            _norms({k: w[k] - w0[k] for k in w}).items()}


# -- layers ------------------------------------------------------------------

def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def _lin(x, w):
    return jnp.einsum("...i,oi->...o", x, w.astype(x.dtype))


def swiglu(x, wg, wu, wd):
    return _lin(jax.nn.silu(_lin(x, wg)) * _lin(x, wu), wd)


def causal_conv(u, w):
    """u (B, L, C), w (C, K): ``y[t, c] = sum_j w[c, j] u[t - (K - 1) + j,
    c]``, zeros before the first token."""
    taps, seq = w.shape[1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    y = jnp.zeros_like(u)
    for j in range(taps):
        y = y + padded[:, j:j + seq] * w[:, j].astype(u.dtype)
    return y


def l2norm(u):
    return u * jax.lax.rsqrt(jnp.sum(jnp.square(u), -1, keepdims=True)
                             + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token, float32.  q, k (B, L, H, dk), v (B,
    L, H, dv), g and beta (B, L, H); returns (o (B, L, H, dv), the last
    state (B, H, dk, dv))."""
    b, seq, h, dk = q.shape
    dv = v.shape[-1]

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        b_t = b_t[..., None, None]
        kk = k_t[..., :, None]
        s = jnp.exp(g_t)[..., None, None] * (
            s - b_t * kk * jnp.einsum("bhk,bhkv->bhv", k_t, s)[..., None, :])
        s = s + b_t * kk * v_t[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    block = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else seq
    # (blocks, tokens of a block, B, H, ...)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(
        (seq // block, block) + x.shape[:1] + x.shape[2:])
        for x in (q, k, v, g, beta))
    last, o = jax.lax.scan(
        jax.checkpoint(lambda s, x: jax.lax.scan(token, s, x)),
        jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((seq, b, h, dv)), 0, 1), last


def gdn_mixer(w, p, x, cfg, fault=None):
    b, seq, _ = x.shape
    h, dk, dv = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], \
        cfg["linear_value_head_dim"]
    f32 = jnp.float32

    def short(name):
        u = _lin(x, w[p + f"gdn_{name}_w"])
        if fault != "no_conv":
            u = causal_conv(u, w[p + f"gdn_{name}_conv"])
        return jax.nn.silu(u)
    q = l2norm(short("q").reshape(b, seq, h, dk).astype(f32)) / math.sqrt(dk)
    k = short("k").reshape(b, seq, h, dk).astype(f32)
    if fault != "no_k_l2norm":
        k = l2norm(k)
    v = short("v").reshape(b, seq, h, dv)
    beta = jax.nn.sigmoid(_lin(x, w[p + "gdn_b_w"]).astype(f32))
    if cfg["linear_allow_neg_eigval"] and fault != "beta_not_doubled":
        beta = 2.0 * beta
    g = -jnp.exp(w[p + "gdn_a_log"]) * jax.nn.softplus(
        _lin(x, w[p + "gdn_a_w"]).astype(f32) + w[p + "gdn_dt_bias"])
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    o, _ = delta_rule(q, k, v.astype(f32), g, beta)
    o = rms_norm(o.astype(x.dtype), w[p + "gdn_norm_g"], cfg["rms_norm_eps"])
    if fault != "no_output_gate":
        o = o * jax.nn.silu(_lin(x, w[p + "gdn_gate_w"])
                            .reshape(b, seq, h, dv))
    return _lin(o.reshape(b, seq, h * dv), w[p + "gdn_o_w"])


def rope(x, theta):
    """Rotate-half rotary embedding over the last axis of ``x`` (..., S,
    R); position i is row i.  Only the fault ``rope`` uses it."""
    s, r = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], axis=-1)
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


def attn_mixer(w, p, x, cfg, fault=None):
    b, seq, u = x.shape
    heads = cfg["num_attention_heads"]
    d = u // heads
    q, k = _lin(x, w[p + "attn_q_w"]), _lin(x, w[p + "attn_k_w"])
    if fault != "no_qk_norm":
        q = rms_norm(q, w[p + "attn_q_norm_g"], cfg["rms_norm_eps"])
        k = rms_norm(k, w[p + "attn_k_norm_g"], cfg["rms_norm_eps"])

    def split(t):
        return t.reshape(b, seq, heads, d).transpose(0, 2, 1, 3)
    q, k, v = split(q), split(k), split(_lin(x, w[p + "attn_v_w"]))
    if fault == "rope":
        q, k = rope(q, FAULT_ROPE_THETA), rope(k, FAULT_ROPE_THETA)
    o = causal_attention(q, k, v, 1.0 / math.sqrt(d))
    return _lin(o.transpose(0, 2, 1, 3).reshape(b, seq, u), w[p + "attn_o_w"])


def block(w, p, x, cfg, kind, fault=None):
    eps = cfg["rms_norm_eps"]
    mixer = gdn_mixer if kind == LINEAR else attn_mixer

    def ffn(t):
        return swiglu(t, w[p + "ffn_gate_w"], w[p + "ffn_up_w"],
                      w[p + "ffn_down_w"])
    if fault == "pre_norm":
        x = x + mixer(w, p, rms_norm(x, w[p + "mixer_norm_g"], eps), cfg)
        return x + ffn(rms_norm(x, w[p + "ffn_norm_g"], eps))
    x = x + rms_norm(mixer(w, p, x, cfg, fault), w[p + "mixer_norm_g"], eps)
    return x + rms_norm(ffn(x), w[p + "ffn_norm_g"], eps)


def forward(w, cfg, tokens, dtype=jnp.float32, remat=False, fault=None):
    """The logits, float32, (B, S, vocab)."""
    blk = jax.checkpoint(block, static_argnums=(1, 3, 4, 5)) if remat \
        else block
    x = w["embed"][tokens].astype(dtype)
    for n, kind in enumerate(cfg["layer_types"]):
        x = blk(w, f"l{n}.", x, cfg, kind, fault)
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


# -- Adam, as the configuration states it -------------------------------------

def adam_step(w, g, m, v, t, lr):
    """One Adam update of every leaf (no weight decay, bias-corrected lr),
    float32 whatever the gradients were computed in."""
    coef = jnp.sqrt(1.0 - ADAM_B2 ** t) / (1.0 - ADAM_B1 ** t)
    new_w, new_m, new_v = {}, {}, {}
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
    "grad_vector": {leaf: float32 host array for leaf in grad_leaves}}``.

    ``fault`` is one of ``FAULTS``: ``half_batch`` counts only the first
    half of the sequence's targets; ``state_unchanged`` puts the weights
    back after every step; the others break one term of the model (see
    each use)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")

    def one_step(w, m, v, tokens, t):
        prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
        with jax.default_matmul_precision(prec):
            loss, g = jax.value_and_grad(lambda tr: loss_fn(
                tr, cfg, tokens, jnp.dtype(dtype), remat, fault))(w)
        return loss, _norms(g), {k: g[k].astype(jnp.float32)
                                 for k in grad_leaves}, \
            adam_step(w, g, m, v, t, lr)

    step = jax.jit(one_step, donate_argnums=(0, 1, 2))
    w = init_weights(cfg, seed)
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    losses, gnorm, gvec = [], None, None
    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1][1], jnp.int32)
        loss, gn, gv, (w, m, v) = step(w, m, v, tokens, jnp.float32(t))
        if fault == "state_unchanged":
            del w               # first, or the two copies would not fit
            w = init_weights(cfg, seed)
        losses.append(float(loss))
        if t == 1:
            gnorm = {k: float(a) for k, a in gn.items()}
            gvec = {k: np.asarray(a) for k, a in gv.items()}
    del m, v
    return {"loss": losses, "grad_norm": gnorm, "grad_vector": gvec,
            "change_norm": change_norms(cfg, seed, w)}
