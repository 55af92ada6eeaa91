"""Plain reference for the ``bert`` family: BERT pre-training (MLM + NSP)
with Adam, as ``benchmarks/configs/<config>.json`` states it.

``train_readings`` follows the first steps of training from the seed's
weights and returns the readings that ``correct`` compares: each step's
loss, every leaf's first gradient norm, the first gradient itself of the
leaves asked for, and every leaf's change after the steps.
"""
import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as T


def spec(cfg):
    u, h = cfg["hidden_size"], cfg["intermediate_size"]
    s = {"word_embed": (cfg["vocab_size"], u),
         "type_embed": (cfg["type_vocab_size"], u),
         "pos_embed": (cfg["max_position_embeddings"], u),
         "embed_ln_g": (u,), "embed_ln_b": (u,),
         "pooler_w": (u, u), "pooler_b": (u,),
         "mlm_dense_w": (u, u), "mlm_dense_b": (u,),
         "mlm_ln_g": (u,), "mlm_ln_b": (u,),
         "mlm_out_b": (cfg["vocab_size"],),
         "nsp_w": (2, u), "nsp_b": (2,)}
    for i in range(cfg["num_hidden_layers"]):
        s.update(T.layer_spec(i, u, h))
    return s


def init_weights(cfg, seed):
    return T.init_weights(spec(cfg), seed)


def loss_fn(w, cfg, x, y, dtype, remat):
    """Mean MLM cross-entropy over the masked positions plus mean NSP
    cross-entropy; the embedding LayerNorm comes before the position
    embedding (the order the configuration file states)."""
    tokens, types, valid_len = x
    labels, weights, nsp_y = y
    b, s = tokens.shape
    e = w["word_embed"][tokens] + w["type_embed"][types]
    e = T._layer_norm(e.astype(dtype), w["embed_ln_g"], w["embed_ln_b"])
    e = e + w["pos_embed"][:s][None].astype(dtype)
    keep = (jnp.arange(s)[None, :] < valid_len[:, None])[:, None, None, :]
    seq = T.run_layers(w, e, keep, cfg["num_hidden_layers"],
                       cfg["num_attention_heads"], remat)
    h = T._gelu(T._dense(seq, w["mlm_dense_w"], w["mlm_dense_b"]))
    h = T._layer_norm(h, w["mlm_ln_g"], w["mlm_ln_b"])
    mlm = T._dense(h, w["word_embed"], w["mlm_out_b"]).astype(jnp.float32)
    pooled = jnp.tanh(T._dense(seq[:, 0], w["pooler_w"], w["pooler_b"]))
    nsp = T._dense(pooled, w["nsp_w"], w["nsp_b"]).astype(jnp.float32)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(mlm, axis=-1),
                              labels[..., None], axis=-1)[..., 0]
    mlm_l = jnp.sum(ce * weights) / jnp.sum(weights)
    nsp_l = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(nsp, axis=-1), nsp_y[:, None], axis=-1))
    return mlm_l + nsp_l


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_readings(cfg, seed, batches, lr, dtype=jnp.float32, steps=3,
                   remat=True, fault=None, grad_leaves=()):
    """Follow ``steps`` Adam steps from the seed's weights over
    ``batches[i] = (x, y)`` (host arrays); returns ``{"loss": [...],
    "grad_norm": {leaf: float}, "change_norm": {leaf: float},
    "grad_vector": {leaf: float32 host array for leaf in grad_leaves}}``.

    ``fault`` plants one of the faults a training cell can have in the
    reference put in the program's place (the tests and PERF.md's upper
    readings): ``"half_batch"`` leaves the second half of every batch out
    and takes the mean over the rest; ``"state_unchanged"`` puts the
    weights back after every step as they were before it."""
    w0 = init_weights(cfg, seed)

    def one_step(w, m, v, x, y, t):
        with T.arithmetic(dtype) as act:
            wc = w if act == jnp.float32 else \
                {k: a.astype(act) for k, a in w.items()}
            loss, g = jax.value_and_grad(loss_fn)(wc, cfg, x, y, act,
                                                  remat)
        new = T.adam_step(w, g, m, v, t, lr)
        return loss, _norms(g), {k: g[k].astype(jnp.float32)
                                 for k in grad_leaves}, new

    step = jax.jit(one_step, donate_argnums=(1, 2))
    w = w0
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    losses, gnorm, gvec = [], None, None
    for t in range(1, steps + 1):
        x, y = batches[t - 1]
        if fault == "half_batch":
            half = x[0].shape[0] // 2
            x = tuple(a[:half] for a in x)
            y = tuple(a[:half] for a in y)
        x = (jnp.asarray(x[0], jnp.int32), jnp.asarray(x[1], jnp.int32),
             jnp.asarray(x[2], jnp.float32))
        y = (jnp.asarray(y[0], jnp.int32), jnp.asarray(y[1], jnp.float32),
             jnp.asarray(y[2], jnp.int32))
        loss, gn, gv, (w_new, m, v) = step(w, m, v, x, y, jnp.float32(t))
        if t > 1:
            del w            # the seed's weights are kept for the change
        w = w0 if fault == "state_unchanged" else w_new
        losses.append(float(loss))
        if t == 1:
            gnorm = {k: float(a) for k, a in gn.items()}
            gvec = {k: np.asarray(a) for k, a in gv.items()}
    change = jax.jit(lambda a, b: _norms(
        {k: a[k] - b[k] for k in a}))(w, w0)
    return {"loss": losses, "grad_norm": gnorm, "grad_vector": gvec,
            "change_norm": {k: float(a) for k, a in change.items()}}
