"""The window / full attention, grouped-query, sparse-expert decoder
(``WindowMoELM``, ``GroupedQueryAttention``, ``parallel.moe.SparseMoE`` with
its softmax router on the block's input and ReLU-gated experts) against its
plain reference (``tests/_swa_moe_reference.py``) at a small size on the
CPU, and its rematerialised step through ``ShardedTrainer``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon.model_zoo.transformer import WindowMoELM
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.parallel.moe import SparseMoE, publish_routing
from tests import _swa_moe_reference as R
from tests._jaxpr import pallas_call_names

BLOCK = (("attn_norm_g", "attn_norm.gamma"), ("q_w", "attn.q.weight"),
         ("k_w", "attn.k.weight"), ("v_w", "attn.v.weight"),
         ("o_w", "attn.proj.weight"), ("ffn_norm_g", "ffn_norm.gamma"),
         ("router_w", "moe.router_weight"),
         ("experts_gate_w", "moe.experts_gate"),
         ("experts_up_w", "moe.experts_up"),
         ("experts_down_w", "moe.experts_down"))


def _walk(obj, path):
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


def build(cfg, w):
    """The program's model with the reference's weights; returns it and
    ``{reference leaf: Parameter}``."""
    net = WindowMoELM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_hidden_size=cfg["moe_ffn_hidden_size"],
        num_experts=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        experts_held=(cfg["experts_held_first"],
                      cfg["n_routed_experts_held"]),
        sliding_window_layout=cfg["sliding_window_layout"],
        rope_layout=cfg["rope_layout"], window=cfg["sliding_window_size"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"])
    net.initialize(mx.init.Zero())
    leaves = {"embed": net.embed.weight, "head": net.head.weight,
              "final_norm_g": net.final_norm.gamma}
    for i, cell in enumerate(net.cells):
        for leaf, path in BLOCK:
            leaves[f"l{i}.{leaf}"] = _walk(cell, path)
    for leaf, param in leaves.items():
        param.set_data(nd.array(np.asarray(w[leaf])))
    return net, leaves


def lm_loss(logits, tokens):
    seq = tokens.shape[1]
    target = nd.concat(nd.slice_axis(tokens, axis=1, begin=1, end=None),
                       nd.slice_axis(tokens, axis=1, begin=0, end=1), dim=1)
    ce = -nd.pick(nd.log_softmax(logits, axis=-1), target, axis=-1)
    has = nd.arange(seq).reshape((1, seq)) < (seq - 1)
    return nd.sum(ce * has) / (tokens.shape[0] * (seq - 1))


def tokens_for(cfg, batch=2, seq=24, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq)).astype(np.int32)


def weights_for(cfg, seed, router_scale=20.0):
    """The seed's weights with the routers' scaled up, so that the logits
    spread and the gates are far from even."""
    return {k: v * router_scale if k.endswith("router_w") else v
            for k, v in R.init_weights(cfg, seed).items()}


def program_loss_and_grads(cfg, w, tokens):
    net, leaves = build(cfg, w)
    t = nd.array(tokens, dtype="int32")
    with autograd.record():
        out = net(t)
        value = lm_loss(out, t)
    value.backward()
    grads = {k: p.grad().asnumpy() for k, p in leaves.items()}
    return out.asnumpy(), float(value.asnumpy()), grads


def share_of(cfg, w, first, count):
    """``cfg`` and ``w`` cut to the experts ``first .. first + count - 1``."""
    cut = {k: v[first:first + count] if ".experts_" in k else v
           for k, v in w.items()}
    return dict(cfg, experts_held_first=first,
                n_routed_experts_held=count), cut


# -- the model against the reference ---------------------------------------------

@pytest.mark.parametrize("held", [(0, 8), (2, 2)],
                         ids=["all_experts", "2_of_8"])
def test_program_matches_reference(held):
    """Logits to 1e-5, the loss, and every leaf's gradient, with every
    expert held here and with one share (experts 2-3) of four; the row is
    longer than the window, so the window blocks mask keys that the full
    blocks weigh."""
    cfg, w = share_of(R.tiny_config(), weights_for(R.tiny_config(), 3),
                      *held)
    tokens = tokens_for(cfg)
    assert tokens.shape[1] > 2 * cfg["sliding_window_size"]
    logits, loss, grads = program_loss_and_grads(cfg, w, tokens)
    with jax.default_matmul_precision("highest"):
        want_logits = R.forward(w, cfg, tokens)
        want_loss, want = jax.value_and_grad(
            lambda tr: R.loss_fn(tr, cfg, tokens))(w)
    np.testing.assert_allclose(logits, want_logits, atol=1e-5, rtol=0)
    assert abs(loss - float(want_loss)) < 1e-5
    assert set(grads) == set(want)
    for leaf, g in want.items():
        np.testing.assert_allclose(grads[leaf], np.asarray(g), atol=2e-6,
                                   rtol=1e-4, err_msg=leaf)
    for leaf in ("l1.experts_gate_w", "l0.router_w", "l1.k_w"):
        assert float(np.abs(grads[leaf]).max()) > 0, leaf


@pytest.mark.parametrize("fault", R.FAULTS)
def test_planted_fault_is_seen(fault):
    """Each term of the model matters at this size: the reference with the
    term broken is not the program (the benchmark's ``correct`` plants the
    same faults at the timed size)."""
    cfg = R.tiny_config()
    w = weights_for(cfg, 5)
    tokens = tokens_for(cfg)
    _, loss, grads = program_loss_and_grads(cfg, w, tokens)
    with jax.default_matmul_precision("highest"):
        bad_loss, bad = jax.value_and_grad(lambda tr: R.loss_fn(
            tr, cfg, tokens, fault=fault))(w)
    worst = max(float(np.linalg.norm(grads[k] - np.asarray(g))
                      / (np.linalg.norm(np.asarray(g)) + 1e-12))
                for k, g in bad.items())
    assert worst > 1e-2 or abs(loss - float(bad_loss)) > 1e-3


def test_a_key_behind_the_window_is_not_seen():
    """Changing token 3 moves a window block's output at position 3 + 6
    (the last query whose window of 7 holds it) and, through the window
    blocks alone, nothing later than the blocks' reach; the full block sees
    it from everywhere after.  One block of each kind, so the reach is
    exact."""
    for layout, reach in (([1], 3 + 7), ([0], 24)):
        cfg = R.tiny_config(num_hidden_layers=1, sliding_window_layout=layout,
                            rope_layout=layout)
        net, _ = build(cfg, weights_for(cfg, 2))
        tokens = tokens_for(cfg, batch=1)
        other = tokens.copy()
        other[0, 3] = (other[0, 3] + 1) % cfg["vocab_size"]
        a, b = (net(nd.array(t, dtype="int32")).asnumpy()
                for t in (tokens, other))
        moved = np.abs(a - b).max(axis=-1)[0] > 1e-7
        assert not moved[:3].any() and moved[3:reach].all()
        assert not moved[reach:].any()


# -- the expert layer ------------------------------------------------------------

def _layer(cfg, w, p, held):
    layer = SparseMoE(cfg["hidden_size"], cfg["moe_ffn_hidden_size"],
                      cfg["moe_num_primary_experts"],
                      cfg["moe_num_active_primary_experts"],
                      experts_held=held, score="softmax", activation="relu")
    layer.initialize(mx.init.Zero())
    first, count = held
    layer.router_weight.set_data(nd.array(np.asarray(w[p + "router_w"])))
    for name in ("gate", "up", "down"):
        getattr(layer, f"experts_{name}").set_data(nd.array(np.asarray(
            w[p + f"experts_{name}_w"])[first:first + count]))
    return layer


def test_the_shares_add_up():
    """The eight one-expert shares' routed parts are the uncut layer (there
    is no shared expert to count once); the router reads one array and the
    experts another."""
    cfg = R.tiny_config()
    w = weights_for(cfg, 7)
    rng = np.random.default_rng(1)
    x, rx = (rng.standard_normal((2, 16, 64)).astype(np.float32)
             for _ in range(2))
    with jax.default_matmul_precision("highest"):
        sel, g = R.route(w, "l1.", jnp.asarray(rx), cfg)
        whole = np.asarray(R.expert_layer(w, "l1.", jnp.asarray(x), sel, g,
                                          cfg))
    parts = [_layer(cfg, w, "l1.", (first, 1))(nd.array(x), nd.array(rx))
             .asnumpy() for first in range(8)]
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5, rtol=0)
    assert all(np.abs(part).max() > 1e-4 for part in parts)
    # routed on its own input the layer is another function
    own = _layer(cfg, w, "l1.", (0, 8))(nd.array(x)).asnumpy()
    assert np.abs(own - whole).max() > 1e-3


def test_no_token_is_dropped():
    """Every token's router input picks held expert 3 first (and one held
    elsewhere): all 16 rows are computed by the share (2, 2)."""
    cfg = R.tiny_config()
    w = dict(weights_for(cfg, 9))
    router = np.zeros((8, 64), np.float32)
    router[3, 0], router[6, 0] = 5.0, 4.0
    w["l1.router_w"] = jnp.asarray(router)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((1, 16, 64)).astype(np.float32)
    rx = np.abs(rng.standard_normal((1, 16, 64))).astype(np.float32) + 0.1
    layer = _layer(cfg, w, "l1.", (2, 2))
    with autograd.train_mode():
        got = layer(nd.array(x), nd.array(rx)).asnumpy()
    assert layer.expert_load.data().asnumpy().tolist() == [0.0, 16.0]
    share_cfg, share = share_of(cfg, w, 2, 2)
    with jax.default_matmul_precision("highest"):
        sel, g = R.route(share, "l1.", jnp.asarray(rx), share_cfg)
        want = np.asarray(R.expert_layer(share, "l1.", jnp.asarray(x), sel,
                                         g, share_cfg))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


# -- the rematerialised step -----------------------------------------------------

def _one_device():
    return par.make_mesh({"dp": 1}, devices=jax.devices()[:1])


def _trainer(cfg, remat, seed=11):
    net, leaves = build(cfg, weights_for(cfg, seed))
    tr = par.ShardedTrainer(
        net, lm_loss, "adam",
        {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95},
        mesh=_one_device(), remat=net.remat_blocks if remat else ())
    return tr, {p.name: k for k, p in leaves.items()}


def test_rematerialised_step_is_the_plain_step():
    """Loss and every leaf's first gradient (Adam's first moment after one
    step) to 1e-6; the expert layers' aux buffers come out of the
    checkpointed blocks as they come out of the plain ones."""
    cfg, _ = share_of(R.tiny_config(), {}, 2, 2)
    tokens = tokens_for(cfg)
    got = {}
    for remat in (False, True):
        tr, _ = _trainer(cfg, remat)
        loss = float(tr.step((tokens,), tokens, batch_size=1).asnumpy())
        got[remat] = (loss, [np.asarray(s[0]) for s in tr._state],
                      list(tr.aux_values().items()))
        assert registry().get("trainer.remat_blocks").value == \
            (8 if remat else 0)
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
    checkpoint: the step's jaxpr holds one forward ``pallas_call`` for each
    of the eight blocks, the six window blocks' under the window build's
    names and the two full blocks' under today's, as the plain step does;
    the backward's two kernels run once a block."""
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    # heads of a whole lane group: the width whose groups the kernel reads
    cfg, _ = share_of(R.tiny_config(head_dim=128), {}, 2, 2)
    tokens = tokens_for(cfg)

    def calls(remat):
        tr, _ = _trainer(cfg, remat)
        names = [n for n in pallas_call_names(
            tr.trace_step((tokens,), tokens).jaxpr) if n.startswith(kernel)]
        return len(names), sum(n.endswith("_window") for n in names)

    assert calls(remat=False) == (8 * a_layer, 6 * a_layer)
    assert calls(remat=True) == (8 * a_layer, 6 * a_layer)


# -- gauges and scopes -------------------------------------------------------------

def test_gauges_and_scopes_are_there(monkeypatch):
    """The new layers' names in the compiled step (what ``mx.profiler.
    dumps`` folds device time by) and their gauges in the registry."""
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    import importlib
    fa = importlib.import_module("mxnet_tpu.kernels.flash_attention")
    fa._build_call.cache_clear()        # the gauges are set by a build
    fa._build_backward.cache_clear()
    cfg, _ = share_of(R.tiny_config(head_dim=128), {}, 0, 2)
    tr, _ = _trainer(cfg, remat=True)
    tokens = tokens_for(cfg)
    text = tr.lower_step((tokens,), tokens).compile().as_text()
    for scope in ("attn/q", "attn/k", "attn/v", "attn/rope", "attn/proj",
                  "attn/flash_attention_bwd", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine", "remat/"):
        assert re.search(scope, text), scope
    gauges = registry().snapshot()
    assert gauges["lm.window_layers"] == 6 and gauges["lm.full_layers"] == 2
    assert gauges["moe.experts_routed"] == 8
    assert gauges["moe.experts_held"] == 2 and gauges["moe.top_k"] == 2
    assert gauges["trainer.remat_blocks"] == 8
    # the forward kernel last built was a window block's; the backward is
    # traced from the last block to the first, so its last build was a
    # full block's, and the key tiles are still the window build's (24
    # rows are one tile, so the window of 7 spares none of it).  Two query
    # heads of 128 lanes read each key head in place
    assert gauges["kernels.flash_attention.window"] == 7
    assert gauges["kernels.flash_attention_bwd.window"] == 0
    for side in ("flash_attention", "flash_attention_bwd"):
        assert gauges[f"kernels.{side}.key_tiles"] == \
            gauges[f"kernels.{side}.key_tiles_causal"] == 1
        assert gauges[f"kernels.{side}.kv_group"] == 2
    tr.step((tokens,), tokens, batch_size=1)
    routing = publish_routing(tr)
    assert routing["expert_load_max"] >= routing["expert_load_mean"] > 0
    gauges = registry().snapshot()
    assert gauges["moe.buffer_rows"] == 2 * tokens.size
    assert 0 < routing["live_rows"] <= routing["rows_moved"] <= \
        routing["live_rows"] + gauges["moe.row_tile"] - 1
