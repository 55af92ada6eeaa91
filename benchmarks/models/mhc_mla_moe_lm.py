"""The ``mhc_mla_moe_lm`` family under test (Xing4.0): the program's
``MLAMoELM`` with ``hc_mult`` residual streams mixed by manifold-
constrained hyper-connections around every sublayer and YaRN positions in
its latent attention (one leading dense block, then sparse-expert blocks
holding one chip's share of the routed experts beside a shared expert, an
untied head, one MTP module) trained by ``parallel.ShardedTrainer`` with
Adam, every block rematerialised, on the next-token loss plus the weighted
MTP loss (``lm_mtp_loss``).

The reads that ``correct`` needs are those of ``models/bert.py`` (its
``Trainer`` is reused for them); each read of the loss also publishes what
that step wrote into the expert layers' aux buffers; the change since the
seed's weights is the reference's ``change_norms`` (the seed's leaves made
again one by one as they are subtracted: a second copy of 3.65 GB of
weights would not fit beside the state); the batches are the
``mla_moe_lm`` family's (full rows, ids uniform over the vocabulary
slice).
"""
import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.parallel import moe
from mxnet_tpu.gluon.model_zoo.transformer import MLAMoELM

from ..reference import mhc_mla_moe_lm as ref
from . import _assign
from . import bert as _bert
from . import mla_moe_lm as _mla

HC_LEAVES = tuple(
    (f"{side}{leaf}", side.rstrip("_"), attr) for side in ref.SIDES
    for leaf, attr in (("norm_g", "gamma"), ("phi", "phi"), ("b", "bias"),
                       ("alpha", "alpha")))

make_batches = _mla.make_batches


def lm_mtp_loss(mtp_weight):
    """The ``mla_moe_lm`` family's loss, ``CE(t_{i+1}) + mtp_weight *
    CE_mtp(t_{i+2})``, each cross-entropy as ``logsumexp(logits) -
    logits[target]`` (the ``kda_mla_moe_lm`` family's form): no
    ``log_softmax`` of (4096, 16,384) floats is held beside the logits of
    either head, in a step that has 6 GB beside its state."""
    def shifted_ce(logits, tokens, shift):
        seq = tokens.shape[1]
        target = mx.nd.concat(
            mx.nd.slice_axis(tokens, axis=1, begin=shift, end=None),
            mx.nd.slice_axis(tokens, axis=1, begin=0, end=shift), dim=1)
        ce = mx.nd.logsumexp(logits, axis=-1) \
            - mx.nd.pick(logits, target, axis=-1)
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
        rope_scaling=cfg["rope_scaling"], hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
        prefix="lm_")


def assign(net, w, names):
    """The reference's leaves into the program's parameters."""
    put = _assign.put
    put(net.embed.weight, w["embed"], names, "embed")
    put(net.head.weight, w["head"], names, "head")
    put(net.final_norm.gamma, w["final_norm_g"], names, "final_norm_g")
    cells = [(f"l{i}.", c) for i, c in enumerate(net.cells)]
    if net.mtp is not None:
        for leaf, attr in (("enorm_g", net.mtp.enorm.gamma),
                           ("hnorm_g", net.mtp.hnorm.gamma),
                           ("eh_proj_w", net.mtp.eh_proj.weight),
                           ("final_norm_g", net.mtp.final_norm.gamma)):
            put(attr, w["mtp." + leaf], names, "mtp." + leaf)
        cells.append(("mtp.", net.mtp.cell))
    for p, cell in cells:
        leaves = _mla.ATTN_LEAVES + HC_LEAVES + (
            _mla.DENSE_LEAVES if p + "gate_w" in w else _mla.MOE_LEAVES)
        for leaf, path, attr in leaves:
            put(getattr(_assign._walk(cell, path), attr), w[p + leaf], names,
                p + leaf)


class Trainer(_bert.Trainer):
    """The one object that set-up builds, drives through its first steps
    and hands to the window (``step``, the gradient reads and ``free`` are
    the ``bert`` family's)."""

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
        """Each leaf's change since the seed's weights, which the reference
        makes again leaf by leaf as it subtracts them."""
        return ref.change_norms(self.cfg, self.seed, dict(zip(
            self._leaf_names(), self.tr._pvals)))


def build_trainer(cfg, traffic, seed, log=lambda _: None):
    return Trainer(cfg, traffic, seed, log)
