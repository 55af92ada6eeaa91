"""The hybrid delta-rule / attention decoder (``OlmoHybridLM``, ``F.
gated_delta_rule``, ``F.causal_conv1d``) against its plain reference
(``tests/_gdn_hybrid_reference.py``: the delta rule as its per-token
recurrence) at a small size on the CPU, and the rematerialised step of
``ShardedTrainer``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu import parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import OlmoHybridLM
from mxnet_tpu.kernels.gated_delta_rule import chunk_of, gated_delta_rule
from mxnet_tpu.observability.registry import registry
from tests import _gdn_hybrid_reference as R
from tests._jaxpr import pallas_call_names

BLOCK = (("mixer_norm_g", "mixer_norm.gamma"),
         ("ffn_norm_g", "ffn_norm.gamma"), ("ffn_gate_w", "ffn.gate.weight"), ("ffn_up_w", "ffn.up.weight"),
         ("ffn_down_w", "ffn.down.weight"))
MIXER = {
    R.LINEAR: (("gdn_q_w", "mixer.q.weight"), ("gdn_k_w", "mixer.k.weight"),
               ("gdn_v_w", "mixer.v.weight"),
               ("gdn_gate_w", "mixer.gate.weight"),
               ("gdn_o_w", "mixer.proj.weight"), ("gdn_a_w", "mixer.a.weight"),
               ("gdn_b_w", "mixer.b.weight"), ("gdn_q_conv", "mixer.q_conv"),
               ("gdn_k_conv", "mixer.k_conv"), ("gdn_v_conv", "mixer.v_conv"),
               ("gdn_a_log", "mixer.a_log"), ("gdn_dt_bias", "mixer.dt_bias"),
               ("gdn_norm_g", "mixer.o_norm.gamma")),
    R.FULL: (("attn_q_w", "mixer.q.weight"), ("attn_k_w", "mixer.k.weight"),
             ("attn_v_w", "mixer.v.weight"), ("attn_o_w", "mixer.proj.weight"),
             ("attn_q_norm_g", "mixer.q_norm.gamma"),
             ("attn_k_norm_g", "mixer.k_norm.gamma"))}
SEQ = 80            # two chunks of 64, the second one padded


def _walk(obj, path):
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


def build(cfg, w=None):
    """The program's model, with the reference's weights where given;
    returns it and ``{reference leaf: Parameter}``."""
    net = OlmoHybridLM(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_heads=cfg["num_attention_heads"],
        hidden_size=cfg["intermediate_size"],
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        epsilon=cfg["rms_norm_eps"])
    net.initialize()
    leaves = {"embed": net.embed.weight, "head": net.head.weight,
              "final_norm_g": net.final_norm.gamma}
    for i, (kind, cell) in enumerate(zip(cfg["layer_types"], net.cells)):
        for leaf, path in BLOCK + MIXER[kind]:
            leaves[f"l{i}.{leaf}"] = _walk(cell, path)
    if w is not None:
        assert set(leaves) == set(w)
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


def tokens_for(cfg, batch=2, seq=SEQ, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq)).astype(np.int32)


def program_loss_and_grads(cfg, w, tokens):
    net, leaves = build(cfg, w)
    net.hybridize()
    t = nd.array(tokens, dtype="int32")
    with autograd.record():
        out = net(t)
        value = lm_loss(out, t)
    value.backward()
    return out.asnumpy(), float(value.asnumpy()), \
        {k: p.grad().asnumpy() for k, p in leaves.items()}


def one_period(**over):
    """One delta-rule block and one attention block: what the tests that
    need no second period build."""
    return R.tiny_config(layer_types=[R.LINEAR, R.FULL], **over)


# -- the two ops --------------------------------------------------------------

def _rule_operands(seq, batch=2, heads=3, dk=16, dv=24, seed=0):
    """Unit keys, ``beta`` up to 2 and a decay a token from 0.2 to 1."""
    r = np.random.default_rng(seed)
    q = r.normal(size=(batch, seq, heads, dk))
    k = r.normal(size=(batch, seq, heads, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(batch, seq, heads, dv))
    g = np.log(r.uniform(0.2, 1.0, size=(batch, seq, heads)))
    beta = r.uniform(0.0, 2.0, size=(batch, seq, heads))
    return [x.astype(np.float32) for x in (q, k, v, g, beta)]


@pytest.mark.parametrize("seq", [128, 100, 37, 16, 7],
                         ids=["2_chunks", "2_chunks_padded", "1_chunk_padded",
                              "1_short_chunk", "shorter_than_a_block"])
def test_gated_delta_rule_is_the_recurrence(seq):
    """Outputs, the state after the last token and the gradients of all five
    operands (through the outputs and through the last state) against the
    per-token recurrence, at lengths that are and are not multiples of the
    chunk.  The kernel's own function: the op hands on its outputs alone."""
    ops = _rule_operands(seq)
    r = np.random.default_rng(1)
    wo = r.normal(size=ops[2].shape).astype(np.float32)
    ws = r.normal(size=(2, 3, 16, 24)).astype(np.float32)

    def loss_of(rule):
        def loss(*a):
            o, s = rule(*a)
            return jnp.sum(o * wo) + jnp.sum(s * ws)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    out, last = jax.jit(gated_delta_rule)(*ops)
    got = loss_of(gated_delta_rule)(*ops)
    with jax.default_matmul_precision("highest"):
        want_out, want_last = jax.jit(R.delta_rule)(*ops)
        want = loss_of(R.delta_rule)(*ops)
    np.testing.assert_allclose(out, want_out, atol=1e-5, rtol=0)
    np.testing.assert_allclose(last, want_last, atol=1e-5, rtol=0)
    assert last.dtype == jnp.float32
    assert chunk_of(seq) == (64 if seq > 32 else 16)
    for name, a, g in zip("q k v g beta".split(), got, want):
        g = np.asarray(g)
        np.testing.assert_allclose(a, g, rtol=0,
                                   atol=1e-5 * np.abs(g).max(), err_msg=name)


def test_gated_delta_rule_op_hands_on_the_kernels_outputs_and_gradients():
    """``F.gated_delta_rule`` is the kernel's outputs (no state: nothing
    the program runs reads it yet), and the tape's gradients of all five
    operands are the kernel's."""
    ops = _rule_operands(40)
    wo = np.random.default_rng(3).normal(size=ops[2].shape).astype(np.float32)
    arrays = [nd.array(x) for x in ops]
    for a in arrays:
        a.attach_grad()
    with autograd.record():
        out = nd.gated_delta_rule(*arrays)
        value = nd.sum(out * nd.array(wo))
    value.backward()
    assert out.shape == (2, 40, 3, 24)
    np.testing.assert_array_equal(out.asnumpy(), gated_delta_rule(*ops)[0])
    want = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a)[0] * wo),
                    argnums=(0, 1, 2, 3, 4))(*ops)
    for name, a, g in zip("q k v g beta".split(), arrays, want):
        g = np.asarray(g)
        np.testing.assert_allclose(a.grad.asnumpy(), g, rtol=0,
                                   atol=1e-6 * np.abs(g).max(), err_msg=name)


def test_causal_conv1d_is_the_sum_and_is_causal():
    """``y[t, c] = sum_j w[c, j] x[t - 3 + j, c]`` with zeros before the
    first token; a change at token t moves nothing before t."""
    r = np.random.default_rng(2)
    x = r.normal(size=(2, 11, 5)).astype(np.float32)
    w = r.normal(size=(5, 4)).astype(np.float32)
    got = nd.causal_conv1d(nd.array(x), nd.array(w)).asnumpy()
    want = np.zeros_like(x)
    for t in range(11):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    moved = x.copy()
    moved[:, 6] += 1.0
    again = nd.causal_conv1d(nd.array(moved), nd.array(w)).asnumpy()
    np.testing.assert_array_equal(again[:, :6], got[:, :6])
    assert np.abs(again[:, 6:10] - got[:, 6:10]).min() > 0
    np.testing.assert_array_equal(again[:, 10], got[:, 10])


# -- the model against the reference ------------------------------------------

def _reference_logits(cfg, w, tokens, fault=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda tr: R.forward(tr, cfg, tokens, fault=fault))(w))


def test_program_matches_reference():
    """Logits to 1e-5, the loss, and every leaf's gradient, at two periods
    of (delta rule, delta rule, attention)."""
    cfg = R.tiny_config()
    w = R.init_weights(cfg, 3)
    tokens = tokens_for(cfg)
    logits, loss, grads = program_loss_and_grads(cfg, w, tokens)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda tr: R.loss_fn(tr, cfg, tokens)))(w)
    np.testing.assert_allclose(logits, _reference_logits(cfg, w, tokens),
                               atol=1e-5, rtol=0)
    assert abs(loss - float(want_loss)) < 1e-5
    assert set(grads) == set(want)
    for leaf, g in want.items():
        g = np.asarray(g)
        np.testing.assert_allclose(grads[leaf], g, rtol=0, err_msg=leaf,
                                   atol=2e-6 + 1e-4 * np.abs(g).max())


@pytest.fixture(scope="module")
def eager_logits():
    """(cfg, weights, tokens, the program's logits run eagerly, block by
    block and op by op, the reference's)."""
    cfg = one_period()
    w = R.init_weights(cfg, 5)
    tokens = tokens_for(cfg, seed=1)
    net, _ = build(cfg, w)
    logits = net(nd.array(tokens, dtype="int32")).asnumpy()
    return cfg, w, tokens, logits, _reference_logits(cfg, w, tokens)


@pytest.mark.parametrize("fault", [f for f in R.FAULTS if f not in
                                   ("half_batch", "state_unchanged")])
def test_planted_fault_is_seen(eager_logits, fault):
    """Each term of the model that a fault of the reference leaves out or
    changes moves the logits by far more than the program (run eagerly
    here) differs from the reference: the comparison would see the program
    leave it out."""
    cfg, w, tokens, logits, want = eager_logits
    assert np.abs(logits - want).max() < 1e-5
    assert np.abs(_reference_logits(cfg, w, tokens, fault) - want).max() \
        > 1e-3


def test_unknown_layer_type_raises():
    cfg = R.tiny_config(layer_types=[R.LINEAR, "sliding_attention"])
    with pytest.raises(MXNetError, match="sliding_attention"):
        build(cfg)


def test_default_initialisation_gives_every_head_its_decay():
    """Without given weights the delta-rule layers draw ``A_log`` from log
    U(1, 16) and ``dt_bias`` so that softplus of it lies in [0.001, 0.1]."""
    net, leaves = build(one_period())
    a_log = leaves["l0.gdn_a_log"].data().asnumpy()
    dt = np.log1p(np.exp(leaves["l0.gdn_dt_bias"].data().asnumpy()))
    assert (0 <= a_log).all() and (a_log <= np.log(16.0)).all()
    assert (0.000999 <= dt).all() and (dt <= 0.1001).all()
    net.hybridize()
    assert np.isfinite(net(nd.array(tokens_for(one_period()),
                                    dtype="int32")).asnumpy()).all()


# -- the rematerialised step --------------------------------------------------

def _one_device():
    return par.make_mesh({"dp": 1}, devices=jax.devices()[:1])


def _trainer(cfg, remat, seed=11, lr=1e-3):
    net, leaves = build(cfg, R.init_weights(cfg, seed))
    tr = par.ShardedTrainer(
        net, lm_loss, "adam",
        {"learning_rate": lr, "beta1": R.ADAM_B1, "beta2": R.ADAM_B2,
         "epsilon": R.ADAM_EPS},
        mesh=_one_device(), remat=net.remat_blocks if remat else ())
    return tr, {p.name: k for k, p in leaves.items()}


def test_three_adam_steps_through_the_rematerialised_trainer():
    """Three steps of ``ShardedTrainer(remat=net.remat_blocks)`` against the
    reference's ``train_readings``: each loss, every leaf's first gradient
    norm and every leaf's change since the seed's weights."""
    cfg = R.tiny_config(layer_types=[R.LINEAR, R.LINEAR, R.FULL])
    batches = [((t,), t) for t in (tokens_for(cfg, batch=1, seed=s)
                                   for s in range(3))]
    tr, names = _trainer(cfg, remat=True)
    w0 = R.init_weights(cfg, 11)
    losses, first = [], None
    for (x,), y in batches:
        losses.append(float(tr.step((x,), y, batch_size=1).asnumpy()))
        if first is None:
            first = {names[p.name]:
                     float(np.linalg.norm(s[0])) / (1 - R.ADAM_B1)
                     for p, s in zip(tr._train_params, tr._state)}
    change = {names[p.name]:
              float(np.linalg.norm(np.asarray(v) - w0[names[p.name]]))
              for p, v in zip(tr._train_params, tr._pvals)}
    want = R.train_readings(cfg, 11, batches, 1e-3)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-6)
    assert set(first) == set(want["grad_norm"]) == set(change)
    for leaf, g in want["grad_norm"].items():
        assert abs(first[leaf] - g) <= 1e-4 * g + 1e-9, leaf
        assert abs(change[leaf] - want["change_norm"][leaf]) \
            <= 2e-3 * want["change_norm"][leaf], leaf
    assert registry().get("trainer.remat_blocks").value == 3


def _scan_lengths(jaxpr):
    """The lengths of the ``scan`` equations in ``jaxpr``, sub-jaxprs
    included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))):
            sub = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
            if isinstance(sub, Jaxpr):
                out += _scan_lengths(sub)
    return out


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_scan_steps_gauge_counts_the_steps_scans(remat):
    """``gdn.scan_steps`` after a step has been traced is the sum of the
    lengths of the step's scans: each of the two delta-rule layers passes
    over its two chunks forward and backward, and once more where its block
    is rematerialised.  The trainer starts the count where it begins to
    trace, so what was traced before (a call of the op on its own) is not
    in it."""
    cfg = R.tiny_config(layer_types=[R.LINEAR, R.LINEAR, R.FULL])
    tokens = tokens_for(cfg, batch=1)
    tr, _ = _trainer(cfg, remat)
    nd.gated_delta_rule(*[nd.array(x) for x in _rule_operands(40)])
    assert registry().get("gdn.scan_steps").value > 0
    lengths = _scan_lengths(tr.trace_step((tokens,), tokens).jaxpr)
    assert lengths == [2] * (2 * (3 if remat else 2))
    assert registry().get("gdn.scan_steps").value == sum(lengths)


def test_gauges_and_scopes_are_there():
    """The new layers' names in the compiled step (what ``mx.profiler.
    dumps`` folds device time by) and their gauges in the registry."""
    cfg = one_period()
    tr, _ = _trainer(cfg, remat=True)
    tokens = tokens_for(cfg, batch=1)
    text = tr.lower_step((tokens,), tokens).compile().as_text()
    for scope in ("gdn/q/", "gdn/k/", "gdn/v/", "gdn/conv", "gdn/gate/",
                  "gdn/decay", "gdn/kkt", "gdn/inverse", "gdn/wu",
                  "gdn/state", "gdn/output", "gdn/norm", "gdn/proj/",
                  "attn/q/", "attn/k/", "attn/v/", "attn/qk_norm",
                  "attn/proj/", r"layer\d/remat/"):
        assert re.search(scope, text), scope
    gauges = registry().snapshot()
    assert gauges["gdn.heads"] == 2
    assert gauges["gdn.key_dim"] == 16 and gauges["gdn.value_dim"] == 24
    assert gauges["gdn.chunk"] == 64
    # two chunk states of (1, 2, 16, 24) float32
    assert gauges["gdn.state_bytes"] == 2 * 2 * 16 * 24 * 4
    assert gauges["gdn.builds"] >= 1


@pytest.mark.parametrize("op_name,scope,way", [
    ("jit(step_fn)/jvp(lm0)/layer1/remat/gdn/state/while/body/dot_general",
     "lm*/layer*/remat/gdn/state", "fwd"),
    ("jit(step_fn)/transpose(jvp(lm0))/layer2/remat/jvp(lm0)/layer2/remat/"
     "checkpoint/rematted_computation/gdn/inverse/dot_general",
     "lm*/layer*/remat/recompute/gdn/inverse", "bwd"),
    ("jit(step_fn)/transpose(jvp(lm0))/layer3/remat/jvp(lm0)/layer3/remat/"
     "checkpoint/attn/qk_norm/k_norm/jit(fn)/mul",
     "lm*/layer*/remat/attn/qk_norm/k_norm", "bwd"),
])
def test_profiler_folds_the_new_scopes(op_name, scope, way):
    from mxnet_tpu.profiler import scope_of
    assert scope_of(op_name, 8) == (scope, way)


def test_attention_block_rides_the_flash_kernels(monkeypatch):
    """With the kernel selected the attention block runs the flash forward
    once, its output kept across the checkpoint, and the backward's two
    kernels once each; the loss and the first gradients are the XLA
    path's."""
    cfg = one_period()
    tokens = tokens_for(cfg, batch=1)

    def first_step():
        tr, names = _trainer(cfg, remat=True)
        kernels = pallas_call_names(tr.trace_step((tokens,), tokens).jaxpr)
        loss = float(tr.step((tokens,), tokens, batch_size=1).asnumpy())
        return kernels, loss, {names[p.name]: np.asarray(s[0]) for p, s in
                               zip(tr._train_params, tr._state)}
    none, want_loss, want = first_step()
    assert none == []
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    kernels, loss, got = first_step()
    assert sorted(kernels) == ["flash_attention_bwd_dkv",
                               "flash_attention_bwd_dq",
                               "flash_attention_fwd"]
    # the one output: (1 x 2 heads, 80 rows, a head of 32) float32
    assert registry().get("trainer.remat_kept_bytes").value == \
        2 * SEQ * 32 * 4
    assert abs(loss - want_loss) < 1e-5
    for leaf, m in want.items():
        np.testing.assert_allclose(got[leaf], m, rtol=0, err_msg=leaf,
                                   atol=1e-6 + 2e-2 * np.abs(m).max())
