"""Plain reference for the ``gdn_hybrid_lm`` family: a post-norm causal
decoder whose blocks take, by ``layer_types``, a gated delta-rule mixer
(linear attention: Gated DeltaNet, Yang, Kautz, Hatamizadeh 2024) or a
full-attention mixer with q/k norms and no rotary embedding, trained with
Adam, as ``benchmarks/configs/<config>.json`` states it.

Straightforward ``jax.numpy``: no kernel, no chunked form (the delta rule is
its per-token recurrence), nothing imported from the program under test.
The equations, ``x`` (L, hidden):

- block: ``h = x + RMSNorm(Mixer(x))``; ``out = h + RMSNorm(SwiGLU(h))``;
  after the blocks a final RMSNorm, then the untied head; the loss is the
  mean next-token cross-entropy over the L - 1 positions that have one.
- delta-rule mixer (H heads of dk / dv lanes): ``q~ = x Wq``, ``k~ = x Wk``,
  ``v~ = x Wv``, each through its own depthwise causal convolution over time
  (``y[t, c] = sum_j w[c, j] u[t - (K - 1) + j, c]``, zeros before t = 0, no
  bias), then SiLU; per head ``q_t = l2norm(q~_t) / sqrt(dk)``, ``k_t =
  l2norm(k~_t)`` with ``l2norm(u) = u / sqrt(sum u^2 + 1e-6)``; ``beta_t = 2
  sigmoid(x_t Wb)`` (the 2 is ``linear_allow_neg_eigval``); ``g_t =
  -exp(A_log) softplus(x_t Wa + dt_bias)``; the state ``S`` (dk, dv) starts
  at nought, ``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t``; ``y_t = Wo concat_h(RMSNorm_dv(o_{t,h}) *
  SiLU((x_t Wg)_h))``, the norm's gain shared by the heads.
- attention mixer: ``q = RMSNorm(x Wq)``, ``k = RMSNorm(x Wk)`` over all
  heads' lanes, ``v = x Wv``; causal softmax at ``1 / sqrt(head)``; no
  rotary embedding; ``y = Wo concat(heads)``.

The memory-saving devices here change no number: the recurrence is a scan
over tokens inside a checkpointed scan over blocks of ``TOKEN_BLOCK`` tokens
(the backward holds a state a block, not a state a token), attention goes by
query blocks, every block of the model is under ``jax.checkpoint``.

``train_readings`` follows the first steps of training from the seed's
weights and returns what ``correct`` compares.  Its ``fault`` plants one
fault in the reference put in the program's place (the tests and PERF.md's
upper readings).  With ``dtype`` bfloat16 (the control) weights and
activations are bfloat16 and the delta rule keeps in float32 what the
configuration's ``precision`` says it keeps: ``l2norm``, ``beta``, ``g``
and the recurrence.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as T

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK = 1024          # attention by query blocks above this many rows
TOKEN_BLOCK = 64        # the recurrence's checkpointed blocks of tokens
L2_EPS = 1e-6
FAULT_ROPE_THETA = 500000.0
INIT_STD = T.INIT_STD
SEED_KEY = T.seed_key       # a PRNG key from any whole number up to 2**63
FAULTS = ("half_batch", "state_unchanged", "no_decay", "beta_not_doubled",
          "no_conv", "no_k_l2norm", "no_output_gate", "no_qk_norm", "rope",
          "pre_norm")
LINEAR, FULL = "linear_attention", "full_attention"


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


def _seeded(cfg, seed, use, *args):
    """``use({leaf: the seed's value}, *args)`` in one jitted call: N(0,
    0.02) everywhere and norm gains 1 + N(0, 0.02), every leaf from a key of
    its own (so that a leaf can be made again without the others), the decay
    leaves from ``_decay_leaves``.  Whatever ``use`` does not return is
    never held."""
    shapes = spec(cfg)
    names = sorted(shapes)
    given = _decay_leaves(cfg, seed)

    def make(key, *args):
        out = {}
        for i, name in enumerate(names):
            if name in given:
                out[name] = jnp.asarray(given[name])
                continue
            v = INIT_STD * jax.random.normal(jax.random.fold_in(key, i),
                                             shapes[name], jnp.float32)
            out[name] = 1.0 + v if name.endswith("_g") else v
        return use(out, *args)
    return jax.jit(make)(SEED_KEY(seed), *args)


def init_weights(cfg, seed):
    """Every leaf as the seed gives it."""
    return _seeded(cfg, seed, lambda w: w)


def change_norms(cfg, seed, w):
    """``{leaf: |w[leaf] - the seed's leaf|}``; the seed's leaves are made
    again inside the call and subtracted as they are made, so that no
    second copy of the weights is held (beside the state it would not fit
    the chip at the cell's size)."""
    norms = _seeded(cfg, seed, lambda w0, w: _norms(
        {k: w[k] - w0[k] for k in w}), w)
    return {k: float(a) for k, a in norms.items()}


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
        with T.arithmetic(dtype) as act:
            loss, g = jax.value_and_grad(lambda tr: loss_fn(
                tr, cfg, tokens, act, remat, fault))(w)
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
