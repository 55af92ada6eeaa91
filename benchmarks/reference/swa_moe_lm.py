"""Plain reference for the ``swa_moe_lm`` family: a pre-norm causal decoder
whose blocks attend, by ``sliding_window_layout`` and ``rope_layout``, within
a window with rotary positions or over every earlier key with no position
at all, over grouped-query heads, and whose feed-forward is a sparse-expert
layer with a softmax top-k router that reads the BLOCK'S INPUT and
ReLU-gated experts, trained with Adam, as
``benchmarks/configs/<config>.json`` states it.

Straightforward ``jax.numpy``: no kernel, no sorting (the expert layer is a
loop over the held experts with masks, each expert applied to every token),
no skipped key (a window block computes every score and masks), a key head
copied out once for each query head that reads it, nothing imported from
the program under test.  The equations, ``x`` (T, hidden) a block's input,
``l`` the block's index, H query heads and K key heads of d lanes:

    r   = x Wr^T                 E logits, float32 at the highest precision
                                 (the block's input, BEFORE the norm)
    sel = top_k(r);  g = softmax(r[sel])
                                 (= the softmax over all E, renormalised
                                 over the selected: norm_topk_prob)
    a   = RMSNorm(x; eps)
    q = a Wq^T (H x d)   k = a Wk^T (K x d)   v = a Wv^T (K x d)   no bias
    rope_layout[l] = 1:  q, k <- RoPE(theta, rotate-half over all d lanes,
                         positions from 0);  0: no position
    head h reads key head h // (H / K);  s_ij = q_i . k_j / sqrt(d)
    key j weighs on query i  iff  j <= i  and
                         (sliding_window_layout[l] = 0  or  i - j < window)
    h1  = x + concat_h(softmax(s) v) Wo^T
    m   = RMSNorm(h1; eps)
    f   = sum over e in sel, e held here:
                         g_e . Wdown_e^T (relu(Wgate_e^T m) * (Wup_e^T m))
    out = h1 + f
    logits = RMSNorm(out of the last block) Whead^T
    loss   = mean next-token cross-entropy over the T - 1 positions that
             have a next token, ids and logits over the vocabulary slice

The experts that the deployment keeps on other chips add nothing here (the
configuration's share); nothing stands in for them.

Assumed, each also under ``assumed`` in the configuration's file: the router
reads the block's input before ``input_layernorm`` (the catalog describes
"router placed before attention"; the config has no key for it); ReLU
gating ("sparse ReGLU"; the config names no activation); ``i - j < window``,
the query's own key among the ``window``; rotate-half RoPE; no bias
anywhere; no balance loss; no secondary experts (the config has only
``moe_num_primary_experts``); N(0, 0.02) weights and ``1 + N(0, 0.02)`` gains
from the seed, the embedding N(0, 1) and the two projections into the
residual stream N(0, 0.02 / sqrt(2 x 52)) (``init_scales`` says why); Adam
0.9 / 0.95 / 1e-8 at a constant rate; documents packed end to end with no
mask at their boundaries.  The memory-saving devices
here (attention by query blocks, ``jax.checkpoint`` per block) change no
number.

``train_readings`` follows the first steps of training from the seed's
weights and returns what ``correct`` compares.  Its ``fault`` plants one
fault in the reference put in the program's place (the tests and PERF.md's
upper readings).  With ``dtype`` bfloat16 (the control, one step below
everything the configuration states) weights and activations are bfloat16,
the router's too.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as T

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
Q_BLOCK = 256           # attention by query blocks above this many rows
FAULTS = ("half_batch", "state_unchanged", "window_ignored", "window_short",
          "window_long", "rope_in_full", "no_rope_in_window", "kv_head_mod",
          "router_after_norm", "silu", "softmax_all")


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


def init_scales(cfg):
    """{leaf suffix: what its N(0, 0.02) draw is multiplied by}: the
    embedding N(0, 1) (the framework default of the source's embedding
    layer), and the two projections that write into the residual stream,
    ``o_w`` and ``experts_down_w``, N(0, 0.02 / sqrt(2 x published depth)),
    the GPT-2 / Megatron recipe.  With every leaf at 0.02 the router's input
    is, from the second block on, one vector common to all tokens (uniform
    attention averages the tokens' own parts away, RMSNorm gives the common
    part its norm back, block after block), every token picks the same six
    experts, and whether one of them is among the eight held here is the
    seed's lottery: the step's work, and the rate, then differ by seed
    (PERF.md section 6, PR 36).  A trained router is balanced; under these
    scales the random one is, within 1.1-1.2 of the mean."""
    return {"embed": 1.0 / T.INIT_STD,
            "o_w": (2 * cfg["published"]["num_hidden_layers"]) ** -0.5,
            "experts_down_w": (2 * cfg["published"]["num_hidden_layers"])
            ** -0.5}


def init_weights(cfg, seed):
    """One draw of the seed: N(0, 0.02) and norm gains 1 + N(0, 0.02), the
    leaves ``init_scales`` names multiplied by their scale."""
    scales = init_scales(cfg)

    def scaled(w):
        return {k: v * next((s for suffix, s in scales.items()
                             if k.endswith(suffix)), 1.0)
                for k, v in sorted(w.items())}
    return jax.jit(scaled, donate_argnums=0)(
        T.init_weights(spec(cfg), seed))


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


# -- Adam, as the configuration states it --------------------------------------

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


def change_norms(cfg, seed, w):
    """Each leaf's change since the seed's weights, which are made again
    from the seed (a copy kept beside the state would not fit the chip at
    the cell's size)."""
    w0 = init_weights(cfg, seed)
    norms = jax.jit(lambda a, b: _norms({k: a[k] - b[k] for k in a}))(
        dict(w), w0)
    return {k: float(a) for k, a in norms.items()}


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
