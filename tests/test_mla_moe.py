"""The MLA / sparse-expert / MTP decoder (``MLAMoELM``, ``parallel.moe.
SparseMoE``) against its plain reference (``tests/_mla_moe_reference.py``)
at a small size on the CPU, and the rematerialised step of
``ShardedTrainer``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon.model_zoo.transformer import (BERTModel, MLAMoELM,
                                                   MLAttention)
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.parallel.moe import SparseMoE, publish_routing
from tests import _mla_moe_reference as R
from tests._jaxpr import pallas_call_names

ATTN = (("attn_norm_g", "attn_norm.gamma"), ("q_down_w", "mla.q_down.weight"),
        ("q_norm_g", "mla.q_norm.gamma"), ("q_up_w", "mla.q_up.weight"),
        ("kv_down_w", "mla.kv_down.weight"),
        ("kv_norm_g", "mla.kv_norm.gamma"), ("kv_up_w", "mla.kv_up.weight"),
        ("proj_w", "mla.proj.weight"), ("ffn_norm_g", "ffn_norm.gamma"))
DENSE = (("gate_w", "ffn.gate.weight"), ("up_w", "ffn.up.weight"),
         ("down_w", "ffn.down.weight"))
MOE = (("router_w", "router_weight"), ("router_b", "router_bias"),
       ("shared_gate_w", "shared.gate.weight"),
       ("shared_up_w", "shared.up.weight"),
       ("shared_down_w", "shared.down.weight"),
       ("experts_gate_w", "experts_gate"), ("experts_up_w", "experts_up"),
       ("experts_down_w", "experts_down"))


def _walk(obj, path):
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


def build(cfg, w):
    """The program's model with the reference's weights; returns it and
    ``{reference leaf: Parameter}``."""
    net = MLAMoELM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], hidden_size=cfg["intermediate_size"],
        moe_hidden_size=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=(cfg["experts_held_first"],
                      cfg["n_routed_experts_held"]),
        routed_scale=cfg["routed_scaling_factor"],
        num_mtp=cfg["num_nextn_predict_layers"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"])
    net.initialize(mx.init.Zero())
    leaves = {"embed": net.embed.weight, "head": net.head.weight,
              "final_norm_g": net.final_norm.gamma}
    cells = [(f"l{i}.", c) for i, c in
             enumerate(net.cells)]
    if net.mtp is not None:
        leaves.update({"mtp.enorm_g": net.mtp.enorm.gamma,
                       "mtp.hnorm_g": net.mtp.hnorm.gamma,
                       "mtp.eh_proj_w": net.mtp.eh_proj.weight,
                       "mtp.final_norm_g": net.mtp.final_norm.gamma})
        cells.append(("mtp.", net.mtp.cell))
    for p, cell in cells:
        dense = p + "gate_w" in w
        for leaf, path in ATTN + (DENSE if dense else ()):
            leaves[p + leaf] = _walk(cell, path)
        if not dense:
            for leaf, path in MOE:
                leaves[p + leaf] = _walk(cell.ffn, path)
    for leaf, param in leaves.items():
        param.set_data(nd.array(np.asarray(w[leaf])))
    return net, leaves


def lm_loss(weight=0.3, mtp_shift=2):
    def shifted(logits, tokens, shift):
        seq = tokens.shape[1]
        target = nd.concat(nd.slice_axis(tokens, axis=1, begin=shift, end=None),
                           nd.slice_axis(tokens, axis=1, begin=0, end=shift),
                           dim=1)
        ce = -nd.pick(nd.log_softmax(logits, axis=-1), target, axis=-1)
        has = nd.arange(seq).reshape((1, seq)) < (seq - shift)
        return nd.sum(ce * has) / (tokens.shape[0] * (seq - shift))

    def loss(out, tokens):
        return shifted(out[0], tokens, 1) \
            + weight * shifted(out[1], tokens, mtp_shift)
    return loss


def tokens_for(cfg, batch=2, seq=24, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq)).astype(np.int32)


def program_loss_and_grads(cfg, w, tokens, loss=None):
    net, leaves = build(cfg, w)
    t = nd.array(tokens, dtype="int32")
    with autograd.record():
        out = net(t)
        value = (loss or lm_loss(cfg["mtp_loss_weight"]))(out, t)
    value.backward()
    grads = {k: p.grad().asnumpy() for k, p in leaves.items()
             if p.grad_req != "null"}
    return [o.asnumpy() for o in out], float(value.asnumpy()), grads


# -- the model against the reference ---------------------------------------------

@pytest.mark.parametrize("held", [(0, 8), (2, 2)],
                         ids=["all_experts", "2_of_8"])
def test_program_matches_reference(held):
    """Logits to 1e-5, the loss, and every leaf's gradient, with every
    expert held here and with one share (experts 2-3) of four."""
    cfg = R.tiny_config(experts_held_first=held[0],
                        n_routed_experts_held=held[1])
    w = R.init_weights(cfg, 3)
    tokens = tokens_for(cfg)
    (main, mtp), loss, grads = program_loss_and_grads(cfg, w, tokens)
    with jax.default_matmul_precision("highest"):
        want_main, want_mtp = R.forward(w, cfg, tokens)
        train = {k: v for k, v in w.items() if k not in R.buffers(cfg)}
        rest = {k: v for k, v in w.items() if k in R.buffers(cfg)}
        want_loss, want = jax.value_and_grad(
            lambda tr: R.loss_fn({**tr, **rest}, cfg, tokens))(train)
    np.testing.assert_allclose(main, want_main, atol=1e-5, rtol=0)
    np.testing.assert_allclose(mtp, want_mtp, atol=1e-5, rtol=0)
    assert abs(loss - float(want_loss)) < 1e-5
    assert set(grads) == set(want)
    for leaf, g in want.items():
        np.testing.assert_allclose(grads[leaf], np.asarray(g), atol=2e-6,
                                   rtol=1e-4, err_msg=leaf)
    assert float(np.abs(grads["l1.experts_gate_w"]).max()) > 0


@pytest.mark.parametrize("fault", ["top_k_minus_1", "no_routed_scale",
                                   "no_router_bias", "no_key_rope",
                                   "no_shared_expert", "mtp_shift"])
def test_planted_fault_is_seen(fault):
    """Each term of the model matters at this size: the reference with the
    term broken is not the program (the benchmark's ``correct`` plants the
    same faults at the timed size)."""
    cfg = R.tiny_config(router_bias_std=0.3)
    w = R.init_weights(cfg, 5)
    tokens = tokens_for(cfg)
    _, loss, grads = program_loss_and_grads(cfg, w, tokens)
    train = {k: v for k, v in w.items() if k not in R.buffers(cfg)}
    rest = {k: v for k, v in w.items() if k in R.buffers(cfg)}
    with jax.default_matmul_precision("highest"):
        bad_loss, bad = jax.value_and_grad(lambda tr: R.loss_fn(
            {**tr, **rest}, cfg, tokens, fault=fault))(train)
    worst = max(float(np.linalg.norm(grads[k] - np.asarray(g))
                      / (np.linalg.norm(np.asarray(g)) + 1e-12))
                for k, g in bad.items())
    assert worst > 1e-2 or abs(loss - float(bad_loss)) > 1e-3


def test_mtp_predicts_the_token_after_next():
    """The MTP head at position i scores token i + 2 from the embedding of
    token i + 1: changing token i + 1 moves ``mtp[:, i]`` and leaves
    ``mtp[:, :i]`` (causal) alone; the main head at i does not see it."""
    cfg = R.tiny_config()
    net, _ = build(cfg, R.init_weights(cfg, 2))
    tokens = tokens_for(cfg, batch=1, seq=12)
    other = tokens.copy()
    other[0, 7] = (other[0, 7] + 1) % cfg["vocab_size"]
    (m1, p1), (m2, p2) = (
        [o.asnumpy() for o in net(nd.array(t, dtype="int32"))]
        for t in (tokens, other))
    assert np.array_equal(m1[:, :7], m2[:, :7])
    assert np.array_equal(p1[:, :6], p2[:, :6])
    assert np.abs(p1[:, 6] - p2[:, 6]).max() > 1e-6
    assert np.abs(m1[:, 7] - m2[:, 7]).max() > 1e-6


# -- the expert layer ------------------------------------------------------------

def _layer(cfg, w, p, held):
    layer = SparseMoE(cfg["hidden_size"], cfg["moe_intermediate_size"],
                      cfg["n_routed_experts"], cfg["num_experts_per_tok"],
                      experts_held=held,
                      shared_hidden=cfg["moe_intermediate_size"],
                      routed_scale=cfg["routed_scaling_factor"])
    layer.initialize(mx.init.Zero())
    first, count = held
    for leaf, path in MOE:
        v = np.asarray(w[p + leaf])
        if leaf.startswith("experts_"):
            v = v[first:first + count]
        _walk(layer, path).set_data(nd.array(v))
    return layer


def test_the_shares_add_up():
    """The four 2-expert shares' routed parts, with the shared expert that
    every chip computes alike counted once, are the uncut layer."""
    cfg = R.tiny_config()
    w = R.init_weights(cfg, 7)
    x = np.random.default_rng(1).standard_normal((2, 16, 64)) \
        .astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R.expert_layer(w, "l1.", jnp.asarray(x), cfg))
        shared = np.asarray(R.swiglu(
            jnp.asarray(x), w["l1.shared_gate_w"], w["l1.shared_up_w"],
            w["l1.shared_down_w"]))
    parts = [_layer(cfg, w, "l1.", (first, 2))(nd.array(x)).asnumpy() - shared
             for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5, rtol=0)
    assert all(np.abs(part).max() > 1e-4 for part in parts)


def test_no_token_is_dropped():
    """Every token picks held expert 3 (and one other, held elsewhere): all
    16 rows are computed."""
    cfg = R.tiny_config()
    w = dict(R.init_weights(cfg, 9))
    bias = np.full((8,), -5.0, np.float32)
    bias[3], bias[6] = 5.0, 4.0
    w["l1.router_b"] = jnp.asarray(bias)
    x = np.random.default_rng(2).standard_normal((1, 16, 64)) \
        .astype(np.float32)
    layer = _layer(cfg, w, "l1.", (2, 2))
    with autograd.train_mode():
        got = layer(nd.array(x)).asnumpy()
    assert layer.expert_load.data().asnumpy().tolist() == [0.0, 16.0]
    share = {k: v[2:4] if "experts_" in k else v for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.expert_layer(share, "l1.", jnp.asarray(x), cfg,
                                         held=(2, 2)))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_rows_beyond_the_groups_may_hold_anything(monkeypatch):
    """The chip's grouped matmul writes neither the rows beyond the last
    group nor, in its backward, their gradient (the CPU's zeroes them):
    with both poisoned the layer's output and its input's gradient are
    what they were."""
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, sizes, **kw):
        beyond = (jnp.arange(lhs.shape[0]) >= jnp.sum(sizes))[:, None]

        @jax.custom_vjp
        def dot(lhs, rhs):
            return jnp.where(beyond, jnp.nan, real(lhs, rhs, sizes, **kw))

        def fwd(lhs, rhs):
            return dot(lhs, rhs), (lhs, rhs)

        def bwd(res, g):
            _, vjp = jax.vjp(lambda a, b: real(a, b, sizes, **kw), *res)
            da, db = vjp(jnp.where(beyond, 0.0, g))
            return jnp.where(beyond, jnp.nan, da), db
        dot.defvjp(fwd, bwd)
        return dot(lhs, rhs)

    cfg = R.tiny_config()
    layer = _layer(cfg, R.init_weights(cfg, 4), "l1.", (2, 2))
    got = []
    for fn in (real, poisoned):
        monkeypatch.setattr(jax.lax, "ragged_dot", fn)
        x = nd.array(np.random.default_rng(5).standard_normal((1, 16, 64))
                     .astype(np.float32))
        x.attach_grad()
        with autograd.record():
            y = layer(x)
            loss = nd.sum(y * y)
        loss.backward()
        got.append((y.asnumpy(), x.grad.asnumpy()))
    assert np.isfinite(got[1][0]).all() and np.isfinite(got[1][1]).all()
    np.testing.assert_allclose(got[1][0], got[0][0], atol=1e-6)
    np.testing.assert_allclose(got[1][1], got[0][1], atol=1e-6)


# -- latent attention ------------------------------------------------------------

def _attention(cfg):
    att = MLAttention(cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["q_lora_rank"], cfg["kv_lora_rank"],
                      cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"], rope_theta=cfg["rope_theta"])
    att.initialize(mx.init.Normal(0.2))
    return att


def test_rotary_key_is_shared_by_all_heads():
    """One rotary key a token: with the heads' own key parts zeroed (the
    ``nope`` rows of ``kv_up``), every head scores with the same keys, so
    two heads given the same queries and values give the same output."""
    cfg = R.tiny_config(qk_nope_head_dim=12, v_head_dim=16)
    att = _attention(cfg)
    nope, rd, vd, heads = 12, 4, 16, 2
    kv_up = att.kv_up.weight.data().asnumpy().reshape(heads, nope + vd, -1) \
        .copy()
    kv_up[:, :nope] = 0.0
    kv_up[1, nope:] = kv_up[0, nope:]
    att.kv_up.weight.set_data(nd.array(kv_up.reshape(heads * (nope + vd), -1)))
    q_up = att.q_up.weight.data().asnumpy().reshape(heads, nope + rd, -1) \
        .copy()
    q_up[1] = q_up[0]
    att.q_up.weight.set_data(nd.array(q_up.reshape(heads * (nope + rd), -1)))
    proj = np.zeros((64, heads * vd), np.float32)
    proj[:vd, :vd] = np.eye(vd)
    proj[vd:2 * vd, vd:] = np.eye(vd)
    att.proj.weight.set_data(nd.array(proj))
    x = nd.array(np.random.default_rng(3).standard_normal((1, 10, 64))
                 .astype(np.float32))
    out = att(x).asnumpy()
    assert np.abs(out[..., :vd]).max() > 1e-3
    np.testing.assert_allclose(out[..., :vd], out[..., vd:2 * vd], atol=1e-6)


def test_rope_turns_the_rotary_lanes_only():
    """``rotary_dim`` trailing lanes are rotated by position (rotate-half,
    as the reference), the lanes before pass through, position 0 is the
    identity."""
    x = np.random.default_rng(4).standard_normal((2, 9, 3, 16)) \
        .astype(np.float32)
    got = nd.rope(nd.array(x), base=1e6, rotary_dim=4, seq_axis=1).asnumpy()
    assert np.array_equal(got[..., :12], x[..., :12])
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)
    want = np.asarray(R.rope(jnp.asarray(x[..., 12:]).transpose(0, 2, 1, 3),
                             1e6)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got[..., 12:], want, atol=1e-6)
    assert np.abs(got[:, 1:, :, 12:] - x[:, 1:, :, 12:]).max() > 1e-2


# -- the rematerialised step -------------------------------------------------------

def _one_device():
    return par.make_mesh({"dp": 1}, devices=jax.devices()[:1])


def _trainer(cfg, remat, seed=11):
    net, leaves = build(cfg, R.init_weights(cfg, seed))
    tr = par.ShardedTrainer(
        net, lm_loss(cfg["mtp_loss_weight"]), "adam",
        {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95},
        mesh=_one_device(), remat=net.remat_blocks if remat else ())
    return tr, {p.name: k for k, p in leaves.items()}


def test_rematerialised_step_is_the_plain_step():
    """Loss and every leaf's first gradient (Adam's first moment after one
    step) to 1e-6; the expert layers' aux buffers come out of the
    checkpointed blocks as they come out of the plain ones."""
    cfg = R.tiny_config(n_routed_experts_held=2, experts_held_first=2)
    tokens = tokens_for(cfg)
    got = {}
    for remat in (False, True):
        tr, _ = _trainer(cfg, remat)
        loss = float(tr.step((tokens,), tokens, batch_size=1).asnumpy())
        got[remat] = (loss, [np.asarray(s[0]) for s in tr._state],
                      list(tr.aux_values().items()))
        assert registry().get("trainer.remat_blocks").value == \
            (4 if remat else 0)
    assert abs(got[True][0] - got[False][0]) < 1e-6
    for a, b in zip(got[True][1], got[False][1]):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5)
    for (name, v), (_, same) in zip(got[False][2], got[True][2]):
        assert np.array_equal(v, same), name
    assert any(v.sum() > 0 for k, v in got[True][2]
               if k.endswith("expert_load"))


@pytest.mark.parametrize("kernel,a_layer", [
    ("flash_attention_fwd", 1), ("flash_attention_bwd", 2)],
    ids=["forward", "backward"])
def test_rematerialised_step_runs_the_flash_kernel_once_a_block(
        monkeypatch, kernel, a_layer):
    """A rematerialised block keeps the flash kernel's output across its
    checkpoint: the step's jaxpr holds one forward ``pallas_call``, counted
    by its name, for each of the four attention layers (three blocks and
    the MTP module's), as the plain step does, where a checkpoint that
    keeps nothing holds two; the backward's two kernels run once a layer
    whatever is kept; and ``trainer.remat_kept_bytes`` reads the four
    outputs' bytes, 0 where no block is rematerialised."""
    from mxnet_tpu.gluon import block as block_mod
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    cfg = R.tiny_config(n_routed_experts_held=2, experts_held_first=2)
    tokens = tokens_for(cfg)
    # one output: (batch 2 x 2 heads, 24 rows, a head of 16) float32
    out_bytes = 2 * cfg["num_attention_heads"] * 24 * cfg["v_head_dim"] * 4

    def calls(remat):
        tr, _ = _trainer(cfg, remat)
        names = pallas_call_names(
            tr.trace_step((tokens,), tokens).jaxpr)
        assert all(n.startswith("flash_attention_") for n in names)
        return sum(n.startswith(kernel) for n in names)

    kept = registry().gauge("trainer.remat_kept_bytes")
    assert calls(remat=False) == 4 * a_layer and kept.value == 0
    assert calls(remat=True) == 4 * a_layer and kept.value == 4 * out_bytes
    monkeypatch.setattr(block_mod, "_keep_named", lambda: None)
    again = 8 if kernel == "flash_attention_fwd" else 4 * a_layer
    assert calls(remat=True) == again and kept.value == 0


def _tiny_bert_step_text(**kw):
    mx.random.seed(0)
    net = BERTModel(vocab_size=50, num_layers=2, units=32, hidden_size=64,
                    num_heads=2, max_length=16, dropout=0.0,
                    prefix="remat_bert_")
    net.initialize()

    def loss(out, ys):
        return nd.mean(-nd.pick(nd.log_softmax(out[0], axis=-1), ys, axis=-1))
    if kw.get("remat"):
        kw["remat"] = list(net.encoder.cells)
    tr = par.ShardedTrainer(net, loss, "adam", {"learning_rate": 1e-3},
                            mesh=_one_device(), **kw)
    x = (np.zeros((2, 16), np.int32), np.zeros((2, 16), np.int32),
         np.full((2,), 16, np.float32))
    return tr.lower_step(x, np.zeros((2, 16), np.int32)).as_text()


def test_remat_off_leaves_berts_step_as_it_was():
    """With no block to rematerialise the lowered step is the same text
    whether the argument is given or not, and holds none of the barriers
    that a checkpoint lowers to; with the encoder's cells it holds them."""
    plain = _tiny_bert_step_text()
    assert plain == _tiny_bert_step_text(remat=())
    assert "optimization_barrier" not in plain
    assert "optimization_barrier" in _tiny_bert_step_text(remat=True)


# -- gauges and scopes -------------------------------------------------------------

def test_gauges_and_scopes_are_there():
    """The new layers' names in the compiled step (what ``mx.profiler.
    dumps`` folds device time by) and their gauges in the registry."""
    cfg = R.tiny_config(n_routed_experts_held=2)
    tr, _ = _trainer(cfg, remat=True)
    tokens = tokens_for(cfg)
    text = tr.lower_step((tokens,), tokens).compile().as_text()
    for scope in ("mla/q_down", "mla/q_up", "mla/kv_down", "mla/kv_up",
                  "mla/rope", "mla/proj", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine", "moe/shared", "mtp/",
                  "remat/"):
        assert re.search(scope, text), scope
    gauges = registry().snapshot()
    assert gauges["moe.experts_routed"] == 8
    assert gauges["moe.experts_held"] == 2
    assert gauges["moe.top_k"] == 2
    assert gauges["trainer.remat_blocks"] == 4
    tr.step((tokens,), tokens, batch_size=1)
    routing = publish_routing(tr)
    assert routing["expert_load_max"] >= routing["expert_load_mean"] > 0
    assert registry().get("moe.expert_load_max").value == \
        routing["expert_load_max"]
    # the row movers' gauges: the buffer is the worst case (top_k x tokens),
    # a pass stops with the tile that holds the last live row
    gauges = registry().snapshot()
    assert gauges["moe.buffer_rows"] == 2 * tokens.size
    assert 1 <= gauges["moe.row_tile"] <= gauges["moe.buffer_rows"]
    assert 0 < gauges["moe.live_rows"] == routing["live_rows"] <= \
        gauges["moe.rows_moved"] == routing["rows_moved"] <= \
        gauges["moe.live_rows"] + gauges["moe.row_tile"] - 1
    assert gauges["moe.rows_moved"] % gauges["moe.row_tile"] == 0


@pytest.mark.parametrize("op_name,scope,way", [
    ("jit(step_fn)/jvp(lm0)/layer1/remat/mla/rope/jit(fn)/mul",
     "lm*/layer*/remat/mla/rope", "fwd"),
    ("jit(step_fn)/transpose(jvp(lm0))/layer1/remat/jvp(lm0)/layer1/remat/"
     "checkpoint/mla/flash_attention_bwd/while/body/closed_call/add",
     "lm*/layer*/remat/mla/flash_attention_bwd", "bwd"),
    ("jit(step_fn)/transpose(jvp(lm0))/mtp/remat/jvp(lm0)/mtp/remat/"
     "checkpoint/rematted_computation/cell/moe/router/dot_general",
     "lm*/mtp/remat/recompute/cell/moe/router", "bwd"),
])
def test_profiler_folds_a_rematerialised_block(op_name, scope, way):
    """``mx.profiler.dumps`` reads a rematerialised block's backward under
    the block's own scopes, and the forward run again under ``recompute``."""
    from mxnet_tpu.profiler import scope_of
    assert scope_of(op_name, 8) == (scope, way)
