"""The ``gdn_hybrid_lm`` family under test: the program's ``OlmoHybridLM``
(gated delta-rule blocks and full-attention blocks as ``layer_types`` says,
post-norm, SwiGLU, untied head) trained by ``parallel.ShardedTrainer`` with
Adam, every block rematerialised, on the next-token loss.

The benchmark takes from the program only the system under test; the reads
that ``correct`` needs are those of ``models/bert.py`` (its ``Trainer`` is
reused for them); the change since the seed's weights is the reference's
``change_norms`` (the seed's leaves made again one by one as they are
subtracted: a second copy of the weights beside 11 GB of state and the
loaded step does not fit), and the batches are the ``mla_moe_lm`` family's
(full rows, ids uniform over the vocabulary slice).

What a family with two kinds of block brings (the pattern of this file):
``reference/<family>.py`` names a block's leaves by its kind (``l<i>.gdn_*``
or ``l<i>.attn_*``, beside the leaves every block has), and ``assign`` below
walks ``layer_types`` and takes the table of the kind; ``flops/<family>.py``
counts each kind's mixer apart and sums them by ``layer_types``, the
sequential mechanism by its recurrence (what the algorithm needs, not what
the chunked form spends); the kernel that only one kind runs is counted by
the blocks of that kind (``flash_fwd_per_step``: one call an attention
block, because a rematerialised block keeps the kernel's output); the
mechanism's own per-layer metric reads a gauge the program sets while the
step is traced (``metrics/gdn_scan_steps.train.py``), and returns nothing
where the program has no such gauge.
"""
import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon.model_zoo.transformer import OlmoHybridLM

from ..reference import gdn_hybrid_lm as ref
from . import _assign
from . import bert as _bert
from . import mla_moe_lm as _mla

BLOCK_LEAVES = (("mixer_norm_g", "mixer_norm", "gamma"),
                ("ffn_norm_g", "ffn_norm", "gamma"),
                ("ffn_gate_w", "ffn.gate", "weight"),
                ("ffn_up_w", "ffn.up", "weight"),
                ("ffn_down_w", "ffn.down", "weight"))
MIXER_LEAVES = {
    ref.LINEAR: (("gdn_q_w", "mixer.q", "weight"),
                 ("gdn_k_w", "mixer.k", "weight"),
                 ("gdn_v_w", "mixer.v", "weight"),
                 ("gdn_gate_w", "mixer.gate", "weight"),
                 ("gdn_o_w", "mixer.proj", "weight"),
                 ("gdn_a_w", "mixer.a", "weight"),
                 ("gdn_b_w", "mixer.b", "weight"),
                 ("gdn_q_conv", "mixer", "q_conv"),
                 ("gdn_k_conv", "mixer", "k_conv"),
                 ("gdn_v_conv", "mixer", "v_conv"),
                 ("gdn_a_log", "mixer", "a_log"),
                 ("gdn_dt_bias", "mixer", "dt_bias"),
                 ("gdn_norm_g", "mixer.o_norm", "gamma")),
    ref.FULL: (("attn_q_w", "mixer.q", "weight"),
               ("attn_k_w", "mixer.k", "weight"),
               ("attn_v_w", "mixer.v", "weight"),
               ("attn_o_w", "mixer.proj", "weight"),
               ("attn_q_norm_g", "mixer.q_norm", "gamma"),
               ("attn_k_norm_g", "mixer.k_norm", "gamma"))}

make_batches = _mla.make_batches


def lm_loss(logits, tokens):
    """Mean cross-entropy of position i against token i + 1, over the
    positions that have one."""
    seq = tokens.shape[1]
    target = mx.nd.concat(
        mx.nd.slice_axis(tokens, axis=1, begin=1, end=None),
        mx.nd.slice_axis(tokens, axis=1, begin=0, end=1), dim=1)
    ce = -mx.nd.pick(mx.nd.log_softmax(logits, axis=-1), target, axis=-1)
    has = mx.nd.arange(seq).reshape((1, seq)) < (seq - 1)
    return mx.nd.sum(ce * has) / (tokens.shape[0] * (seq - 1))


def build_net(cfg):
    return OlmoHybridLM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_heads=cfg["num_attention_heads"],
        hidden_size=cfg["intermediate_size"],
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        epsilon=cfg["rms_norm_eps"], prefix="lm_")


def assign(net, cfg, w, names):
    """The reference's leaves into the program's parameters."""
    put = _assign.put
    put(net.embed.weight, w["embed"], names, "embed")
    put(net.head.weight, w["head"], names, "head")
    put(net.final_norm.gamma, w["final_norm_g"], names, "final_norm_g")
    for i, (kind, cell) in enumerate(zip(cfg["layer_types"], net.cells)):
        for leaf, path, attr in BLOCK_LEAVES + MIXER_LEAVES[kind]:
            put(getattr(_assign._walk(cell, path), attr), w[f"l{i}.{leaf}"],
                names, f"l{i}.{leaf}")


class Trainer(_bert.Trainer):
    """The one object that set-up builds, drives through its first steps
    and hands to the window (``step``, ``loss_value``, the gradient reads
    and ``free`` are the ``bert`` family's)."""

    def __init__(self, cfg, traffic, seed, log=lambda _: None):
        if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
            raise ValueError("the program's delta-rule mixer has as many "
                             "key heads as value heads")
        self.cfg, self.seed, self.names = cfg, seed, {}
        mx.random.seed(seed % (2 ** 31))
        net = build_net(cfg)
        net.initialize(mx.init.Zero())     # overwritten from the seed below
        log("net initialized")
        assign(net, cfg, ref.init_weights(cfg, seed), self.names)
        log("weights made from the seed and assigned")
        self.tr = par.ShardedTrainer(
            net, lm_loss, "adam",
            {"learning_rate": cfg["learning_rate"], "beta1": ref.ADAM_B1,
             "beta2": ref.ADAM_B2, "epsilon": ref.ADAM_EPS},
            remat=net.remat_blocks)

    def change_norms(self):
        """Each leaf's change since the seed's weights, which the reference
        makes again leaf by leaf as it subtracts them: beside 11 GB of state
        and the loaded step a second copy of the weights does not fit."""
        return ref.change_norms(self.cfg, self.seed, dict(zip(
            self._leaf_names(), self.tr._pvals)))


def build_trainer(cfg, traffic, seed, log=lambda _: None):
    return Trainer(cfg, traffic, seed, log)

