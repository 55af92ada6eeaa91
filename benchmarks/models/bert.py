"""The ``bert`` family under test: the program's ``BERTModel`` trained by
``parallel.ShardedTrainer`` with the MLM + NSP loss and Adam, as
``chip_smoke.train_bert_base`` proved it on the chip (copied, not imported).

The benchmark takes from the program only the system under test.  Two reads
are private, for ``correct`` alone (``ShardedTrainer`` has no public view of
its optimizer state or of its weights on the device): ``_state`` and
``_pvals``; PERF.md lists them under Open questions.
"""
import statistics

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon.model_zoo.transformer import BERTModel

from ..reference import bert as ref
from ..reference import transformer as T
from . import _assign

MASK_ID = 103


def lengths(traffic):
    """The cell's fixed set of valid lengths, one per row of every host
    batch: the quantiles of a lognormal clipped to [min, max].  Every seed
    gets this same set in another order, so no seed changes the work."""
    n = traffic["batch"] * traffic["host_batches"]
    lo, hi = traffic["valid_len_min"], traffic["valid_len_max"]
    dist = statistics.NormalDist(np.log(traffic["valid_len_median"]),
                                 traffic["valid_len_sigma"])
    q = [dist.inv_cdf((i + 0.5) / n) for i in range(n)]
    return np.clip(np.round(np.exp(q)), lo, hi).astype(np.int64)


def make_batches(cfg, traffic, seed):
    """``host_batches`` distinct (x, y) host batches from the seed: random
    token ids, per-row valid lengths, 15% of the valid positions masked
    (position 0 always, so no row is without a label), random NSP labels.
    Every row of every batch differs."""
    rng = np.random.default_rng(seed)
    b, s = traffic["batch"], traffic["seq"]
    lens = rng.permutation(lengths(traffic)).reshape(-1, b)
    out = []
    for valid_lens in lens:
        tokens = rng.integers(0, cfg["vocab_size"], (b, s))
        valid = np.arange(s)[None, :] < valid_lens[:, None]
        mask_pos = (rng.random((b, s)) < traffic["mask_share"]) & valid
        mask_pos[:, 0] = True
        x = (np.where(mask_pos, MASK_ID, tokens),
             np.zeros((b, s), np.int64), valid_lens.astype(np.float32))
        y = (tokens, mask_pos.astype(np.float32), rng.integers(0, 2, (b,)))
        out.append((x, y))
    return out


def mlm_nsp_loss(out, ys):
    mlm, nsp = out
    labels, weights, nsp_y = ys
    ce = -mx.nd.pick(mx.nd.log_softmax(mlm, axis=-1), labels, axis=-1)
    mlm_l = mx.nd.sum(ce * weights) / mx.nd.sum(weights)
    nsp_l = -mx.nd.mean(mx.nd.pick(mx.nd.log_softmax(nsp, axis=-1),
                                   nsp_y, axis=-1))
    return mlm_l + nsp_l


def _leaf_norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(a))) for a in leaves]


class Trainer:
    """The one object that set-up builds, drives through its first steps
    and hands to the window."""

    def __init__(self, cfg, traffic, seed, log=lambda _: None):
        self.cfg = cfg
        mx.random.seed(seed % (2 ** 31))
        net = BERTModel(
            vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_hidden_layers"], units=cfg["hidden_size"],
            hidden_size=cfg["intermediate_size"],
            num_heads=cfg["num_attention_heads"],
            max_length=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            dropout=cfg["hidden_dropout_prob"])
        net.initialize(mx.init.Zero())     # overwritten from the seed below
        log("net initialized")
        self.seed = seed
        self.names = {}
        self._assign(net, ref.init_weights(cfg, seed))
        log("weights made from the seed and assigned")
        self.tr = par.ShardedTrainer(
            net, mlm_nsp_loss, "adam",
            {"learning_rate": cfg["learning_rate"], "beta1": T.ADAM_B1,
             "beta2": T.ADAM_B2, "epsilon": T.ADAM_EPS})

    def _assign(self, net, w):
        names = self.names
        put = _assign.put
        put(net.word_embed.weight, w["word_embed"], names, "word_embed")
        put(net.token_type_embed.weight, w["type_embed"], names, "type_embed")
        put(net.encoder.pos_embed.weight, w["pos_embed"], names, "pos_embed")
        for leaf, blk, attr in (
                ("embed_ln_g", net.embed_ln, "gamma"),
                ("embed_ln_b", net.embed_ln, "beta"),
                ("pooler_w", net.pooler, "weight"),
                ("pooler_b", net.pooler, "bias"),
                ("mlm_dense_w", net.mlm_dense, "weight"),
                ("mlm_dense_b", net.mlm_dense, "bias"),
                ("mlm_ln_g", net.mlm_ln, "gamma"),
                ("mlm_ln_b", net.mlm_ln, "beta"),
                ("mlm_out_b", net.mlm_decoder, "bias"),
                ("nsp_w", net.nsp, "weight"), ("nsp_b", net.nsp, "bias")):
            put(getattr(blk, attr), w[leaf], names, leaf)
        for i, cell in enumerate(net.encoder.cells):
            _assign.put_layer(cell, cell.attn, w, i, names)

    def step(self, batch):
        """One training step, not waited for.  The loss is already a mean,
        so the optimizer's 1/batch rescale is 1 (``batch_size=1``)."""
        x, y = batch
        return self.tr.step(x, y, batch_size=1)

    @staticmethod
    def loss_value(loss):
        return float(loss.asnumpy())          # waits for the device

    # -- what ``correct`` reads (private state, see the module docstring) --
    def _leaf_names(self):
        return [self.names[p.name] for p in self.tr._train_params]

    def first_gradient_norms(self):
        """Each leaf's first gradient as the optimizer got it, from Adam's
        first moment after one step: m1 = (1 - beta1) g1."""
        ms = [s[0] for s in self.tr._state]
        norms = jax.jit(_leaf_norms)(ms)
        return {k: float(n) / (1.0 - T.ADAM_B1)
                for k, n in zip(self._leaf_names(), norms)}

    def first_gradient_vectors(self, leaves):
        """The first gradient itself of the few ``leaves`` the cell's file
        names, on the host (same source as the norms)."""
        by_name = dict(zip(self._leaf_names(), self.tr._state))
        return {k: np.asarray(by_name[k][0], np.float32) / (1.0 - T.ADAM_B1)
                for k in leaves}

    def change_norms(self):
        """Each leaf's change since the seed's weights (made again from
        the seed: the program was given the first copy, and donates it)."""
        names = self._leaf_names()
        w0 = ref.init_weights(self.cfg, self.seed)
        w0 = [w0[k] for k in names]
        norms = jax.jit(lambda a, b: _leaf_norms(
            [x - y for x, y in zip(a, b)]))(list(self.tr._pvals), w0)
        return {k: float(n) for k, n in zip(names, norms)}

    def free(self):
        self.tr = None


def build_trainer(cfg, traffic, seed, log=lambda _: None):
    return Trainer(cfg, traffic, seed, log)
