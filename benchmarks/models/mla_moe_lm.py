"""The ``mla_moe_lm`` family under test: the program's ``MLAMoELM`` (latent
attention, one chip's share of the routed experts, a shared expert, one
MTP module) trained by ``parallel.ShardedTrainer`` with Adam, every block
rematerialised, on the next-token loss plus the weighted MTP loss.

The benchmark takes from the program only the system under test; the reads
that ``correct`` needs are those of ``models/bert.py`` (its ``Trainer`` is
reused for them).  Each read of the loss also publishes what that step
wrote into the expert layers' aux buffers (``moe.publish_routing``).

What a family whose step does not fit without rematerialisation brings
(the pattern of this file): the model names its blocks
(``net.remat_blocks``) and ``build_trainer`` hands them to
``ShardedTrainer(remat=...)``; ``flops/<family>.py`` still counts the
model's work once (``train_step``), and counts a kernel that the backward
runs again as the calls made (``flash_fwd_per_step``);
``reference/<family>.py`` makes the seed's weights again for the change (a
copy kept beside the state would not fit) and takes the family's own
``fault``s, which ``tests/control_<family>.py`` plants beside the three of
``control_train.py``.
"""
import jax
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.parallel import moe
from mxnet_tpu.gluon.model_zoo.transformer import MLAMoELM

from ..reference import mla_moe_lm as ref
from . import _assign
from . import bert as _bert

ATTN_LEAVES = (("attn_norm_g", "attn_norm", "gamma"),
               ("q_down_w", "mla.q_down", "weight"),
               ("q_norm_g", "mla.q_norm", "gamma"),
               ("q_up_w", "mla.q_up", "weight"),
               ("kv_down_w", "mla.kv_down", "weight"),
               ("kv_norm_g", "mla.kv_norm", "gamma"),
               ("kv_up_w", "mla.kv_up", "weight"),
               ("proj_w", "mla.proj", "weight"),
               ("ffn_norm_g", "ffn_norm", "gamma"))
DENSE_LEAVES = (("gate_w", "ffn.gate", "weight"), ("up_w", "ffn.up", "weight"),
                ("down_w", "ffn.down", "weight"))
MOE_LEAVES = (("router_w", "ffn", "router_weight"),
              ("router_b", "ffn", "router_bias"),
              ("shared_gate_w", "ffn.shared.gate", "weight"),
              ("shared_up_w", "ffn.shared.up", "weight"),
              ("shared_down_w", "ffn.shared.down", "weight"),
              ("experts_gate_w", "ffn", "experts_gate"),
              ("experts_up_w", "ffn", "experts_up"),
              ("experts_down_w", "ffn", "experts_down"))


def make_batches(cfg, traffic, seed):
    """``host_batches`` distinct host batches from the seed: every row full
    (documents packed end to end), token ids uniform over the vocabulary
    slice.  The targets are the tokens themselves, shifted by the loss."""
    rng = np.random.default_rng(seed)
    shape = (traffic["batch"], traffic["seq"])
    out = []
    for _ in range(traffic["host_batches"]):
        tokens = rng.integers(0, cfg["vocab_size"], shape).astype(np.int32)
        out.append(((tokens,), tokens))
    return out


def lm_mtp_loss(mtp_weight):
    def shifted_ce(logits, tokens, shift):
        """Mean cross-entropy of position i against token i + shift, over
        the positions that have one."""
        seq = tokens.shape[1]
        target = mx.nd.concat(
            mx.nd.slice_axis(tokens, axis=1, begin=shift, end=None),
            mx.nd.slice_axis(tokens, axis=1, begin=0, end=shift), dim=1)
        ce = -mx.nd.pick(mx.nd.log_softmax(logits, axis=-1), target, axis=-1)
        has = mx.nd.arange(seq).reshape((1, seq)) < (seq - shift)
        return mx.nd.sum(ce * has) / (tokens.shape[0] * (seq - shift))

    def loss(out, tokens):
        main, mtp = out
        return shifted_ce(main, tokens, 1) \
            + mtp_weight * shifted_ce(mtp, tokens, 2)
    return loss


def build_net(cfg):
    return MLAMoELM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], hidden_size=cfg["intermediate_size"],
        moe_hidden_size=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        experts_held=(cfg.get("experts_held_first", 0),
                      cfg["n_routed_experts_held"]),
        num_shared_experts=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        first_dense=cfg["first_k_dense_replace"],
        num_mtp=cfg["num_nextn_predict_layers"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"],
        prefix="lm_")


def assign(net, w, names):
    """The reference's leaves into the program's parameters."""
    put = _assign.put
    put(net.embed.weight, w["embed"], names, "embed")
    put(net.head.weight, w["head"], names, "head")
    put(net.final_norm.gamma, w["final_norm_g"], names, "final_norm_g")
    cells = [(f"l{i}.", c) for i, c in
             enumerate(net.cells)]
    if net.mtp is not None:
        for leaf, attr in (("enorm_g", net.mtp.enorm.gamma),
                           ("hnorm_g", net.mtp.hnorm.gamma),
                           ("eh_proj_w", net.mtp.eh_proj.weight),
                           ("final_norm_g", net.mtp.final_norm.gamma)):
            put(attr, w["mtp." + leaf], names, "mtp." + leaf)
        cells.append(("mtp.", net.mtp.cell))
    for p, cell in cells:
        leaves = ATTN_LEAVES + (DENSE_LEAVES if p + "gate_w" in w
                                else MOE_LEAVES)
        for leaf, path, attr in leaves:
            put(getattr(_assign._walk(cell, path), attr), w[p + leaf], names,
                p + leaf)


class Trainer(_bert.Trainer):
    """The one object that set-up builds, drives through its first steps
    and hands to the window (``step``, ``loss_value``, the gradient reads
    and ``free`` are the ``bert`` family's)."""

    def __init__(self, cfg, traffic, seed, log=lambda _: None):
        self.cfg, self.seed, self.names = cfg, seed, {}
        mx.random.seed(seed % (2 ** 31))
        net = build_net(cfg)
        net.initialize(mx.init.Zero())     # overwritten from the seed below
        log("net initialized")
        assign(net, ref.init_weights(cfg, seed), self.names)
        log("weights made from the seed and assigned")
        self.tr = par.ShardedTrainer(
            net, lm_mtp_loss(cfg["mtp_loss_weight"]), "adam",
            {"learning_rate": cfg["learning_rate"], "beta1": ref.ADAM_B1,
             "beta2": ref.ADAM_B2, "epsilon": ref.ADAM_EPS},
            remat=net.remat_blocks)

    def loss_value(self, loss):
        value = float(loss.asnumpy())          # waits for the device
        moe.publish_routing(self.tr)
        return value

    def change_norms(self):
        """Each leaf's change since the seed's weights (made again from
        the seed: the program was given the first copy, and donates it)."""
        names = self._leaf_names()
        w0 = ref.init_weights(self.cfg, self.seed)
        w0 = [w0[k] for k in names]
        norms = jax.jit(lambda a, b: _bert._leaf_norms(
            [x - y for x, y in zip(a, b)]))(list(self.tr._pvals), w0)
        return {k: float(n) for k, n in zip(names, norms)}


def build_trainer(cfg, traffic, seed, log=lambda _: None):
    return Trainer(cfg, traffic, seed, log)
