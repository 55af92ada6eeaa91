"""The ``swa_moe_lm`` family under test: the program's ``WindowMoELM``
(window + rotary and full position-free attention blocks as the two layouts
say, grouped-query heads, a softmax top-k router that reads the block's
input, one chip's share of the ReLU-gated experts, untied head) trained by
``parallel.ShardedTrainer`` with Adam, every block rematerialised, on the
next-token loss.

The benchmark takes from the program only the system under test; the reads
that ``correct`` needs are those of ``models/bert.py`` (its ``Trainer`` is
reused for them), the loss is the ``gdn_hybrid_lm`` family's, the batches
are the ``mla_moe_lm`` family's (full rows, ids uniform over the vocabulary
slice), and each read of the loss also publishes what that step wrote into
the expert layers' aux buffers (``moe.publish_routing``), as that family's
does.

What a family whose kernel comes in two builds brings (the pattern of this
file's siblings): ``flops/<family>.py`` counts the attention core by the
pairs each kind of block weighs (the window's pairs exactly), the kernel's
calls over all blocks (``flash_fwd_per_step``) and those of the window
blocks alone (``flash_window_fwd_per_step``, ``flash_window_bwd_per_step``)
under the names the window build carries; the metrics of the window build
read those names in the trace and the program's gauges, and return nothing
where the program has neither.
"""
import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.parallel import moe
from mxnet_tpu.gluon.model_zoo.transformer import WindowMoELM

from ..reference import swa_moe_lm as ref
from . import _assign
from . import bert as _bert
from . import gdn_hybrid_lm as _gdn
from . import mla_moe_lm as _mla

BLOCK_LEAVES = (("attn_norm_g", "attn_norm", "gamma"),
                ("q_w", "attn.q", "weight"), ("k_w", "attn.k", "weight"),
                ("v_w", "attn.v", "weight"), ("o_w", "attn.proj", "weight"),
                ("ffn_norm_g", "ffn_norm", "gamma"),
                ("router_w", "moe", "router_weight"),
                ("experts_gate_w", "moe", "experts_gate"),
                ("experts_up_w", "moe", "experts_up"),
                ("experts_down_w", "moe", "experts_down"))

make_batches = _mla.make_batches
lm_loss = _gdn.lm_loss


def build_net(cfg):
    return WindowMoELM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_hidden_size=cfg["moe_ffn_hidden_size"],
        num_experts=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        experts_held=(cfg.get("experts_held_first", 0),
                      cfg["n_routed_experts_held"]),
        sliding_window_layout=cfg["sliding_window_layout"],
        rope_layout=cfg["rope_layout"], window=cfg["sliding_window_size"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"],
        prefix="lm_")


def assign(net, w, names):
    """The reference's leaves into the program's parameters."""
    put = _assign.put
    put(net.embed.weight, w["embed"], names, "embed")
    put(net.head.weight, w["head"], names, "head")
    put(net.final_norm.gamma, w["final_norm_g"], names, "final_norm_g")
    for i, cell in enumerate(net.cells):
        for leaf, path, attr in BLOCK_LEAVES:
            put(getattr(_assign._walk(cell, path), attr), w[f"l{i}.{leaf}"],
                names, f"l{i}.{leaf}")


class Trainer(_bert.Trainer):
    """The one object that set-up builds, drives through its first steps
    and hands to the window (``step``, the gradient reads and ``free`` are
    the ``bert`` family's)."""

    def __init__(self, cfg, traffic, seed, log=lambda _: None):
        if len(cfg["sliding_window_layout"]) != cfg["num_hidden_layers"] or \
                not (cfg["moe_primary_router_apply_softmax"]
                     and cfg["norm_topk_prob"]):
            raise ValueError("the family is one block an entry of the two "
                             "layouts, with the softmax router renormalised "
                             "over the selected experts")
        self.cfg, self.seed, self.names = cfg, seed, {}
        mx.random.seed(seed % (2 ** 31))
        net = build_net(cfg)
        net.initialize(mx.init.Zero())     # overwritten from the seed below
        log("net initialized")
        assign(net, ref.init_weights(cfg, seed), self.names)
        log("weights made from the seed and assigned")
        self.tr = par.ShardedTrainer(
            net, lm_loss, "adam",
            {"learning_rate": cfg["learning_rate"], "beta1": ref.ADAM_B1,
             "beta2": ref.ADAM_B2, "epsilon": ref.ADAM_EPS},
            remat=net.remat_blocks)

    def loss_value(self, loss):
        value = float(loss.asnumpy())          # waits for the device
        moe.publish_routing(self.tr)
        return value

    def change_norms(self):
        """Each leaf's change since the seed's weights, which the reference
        makes again from the seed (the program was given the first copy,
        and donates it)."""
        return ref.change_norms(self.cfg, self.seed, dict(zip(
            self._leaf_names(), self.tr._pvals)))


def build_trainer(cfg, traffic, seed, log=lambda _: None):
    return Trainer(cfg, traffic, seed, log)
