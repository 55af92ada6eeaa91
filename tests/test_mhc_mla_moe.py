"""Xing4.0 on the CPU at a tiny size: manifold-constrained hyper-connections
(``HyperConnection``, ``kernels/hyper_connection.py``) against the
equations written out in ``tests/_mhc_reference.py``; the Sinkhorn-Knopp
projection; YaRN in the ``rope`` op and ``MLAttention``; ``MLAMoELM`` with
four streams against the benchmark's plain reference
(``benchmarks/reference/mhc_mla_moe_lm.py``), eagerly and through
``ShardedTrainer`` with Adam and every block rematerialised; one chip's
share of 64 experts; the gauges; and, with ``hc_mult`` absent, the GLM and
Kimi steps as the parent traced them."""
import functools
import hashlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.model_zoo.transformer import (HyperConnection, MLAMoELM,
                                                   MLAttention)
from mxnet_tpu.kernels import hyper_connection as hc_mod
from mxnet_tpu.ndarray.ops_nn import yarn_frequencies, yarn_softmax_factor
from mxnet_tpu.observability.registry import registry

from benchmarks.harness import loader
from benchmarks.models import mhc_mla_moe_lm as XM
from benchmarks.reference import mhc_mla_moe_lm as R
from benchmarks.reference import mla_moe_lm as RM
from tests import _mhc_reference as H

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4_29b_ep8.pretrain_s4k"
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}


@pytest.fixture
def one_device(monkeypatch):
    """The benchmark's trainers on one device, as on the chip (the suite's
    mesh has eight, and a row of one token sequence does not divide)."""
    from mxnet_tpu import parallel as par
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])

    class OneDevice(par.ShardedTrainer):
        def __init__(self, *args, **kw):
            kw.setdefault("mesh", mesh)
            super().__init__(*args, **kw)
    monkeypatch.setattr(par, "ShardedTrainer", OneDevice)


def tiny_config(**over):
    """The cell's configuration at the rehearsal's tiny sizes: hidden 64,
    four streams, one dense and four sparse blocks and the MTP module, 2
    heads of 12 + 8 / 16 lanes, 4 of 16 experts held, top 4, 20 Sinkhorn
    steps, YaRN over the 8 rotary lanes."""
    _, _, cfg = loader.cell_and_config(
        loader.benchmark(), CELL, os.path.join(DATA,
                                               "tiny_mhc_mla_moe_lm.json"))
    cfg.update(over)
    return cfg


# -- YaRN ------------------------------------------------------------------------

def test_yarn_frequencies_are_the_formula():
    """At 64 rotary lanes and the configuration's scaling: the frequencies
    of the formula, the ramp from dimension 10 to 23 (the first ten the
    plain frequencies, those from 23 on a 64th of them), cos and sin
    unscaled (mscale = mscale_all_dim), the softmax scale times (0.1 ln 64
    + 1)^2."""
    inv, mscale = yarn_frequencies(64, 10000.0, YARN)
    want, (low, high) = H.yarn_inverse_frequencies(64, 10000.0, 64, 4096, 32,
                                                   1)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv, want, rtol=1e-12, atol=0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-12)
    assert np.all((inv[11:23] < plain[11:23]) & (inv[11:23] > plain[11:23]
                                                   / 64))
    assert mscale == 1.0
    assert yarn_softmax_factor(YARN) == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2, rel=1e-12)
    assert yarn_softmax_factor(YARN) == pytest.approx(2.00474, abs=1e-5)
    assert yarn_softmax_factor(dict(YARN, mscale_all_dim=0)) == 1.0
    _, scaled = yarn_frequencies(64, 10000.0, dict(YARN, mscale=2))
    assert scaled == pytest.approx(H.yarn_mscale(64, 2) / H.yarn_mscale(64, 1))


@pytest.mark.parametrize("mscale", [1, 2])
def test_rope_with_yarn_turns_the_rotary_lanes_by_the_tables(mscale):
    """The rotary lanes turned by YaRN's frequencies, cos and sin times
    m(mscale) / m(mscale_all_dim) (1 in the configuration, 1.416 / 1.208
    at mscale 2); the lanes before pass through."""
    r = np.random.default_rng(0)
    x = r.normal(size=(2, 300, 3, 72)).astype(np.float32)
    scaling = dict(YARN, mscale=mscale)
    got = nd.rope(nd.array(x), base=10000.0, rotary_dim=64, seq_axis=1,
                  scaling=tuple(sorted(scaling.items()))).asnumpy()
    inv, _ = H.yarn_inverse_frequencies(64, 10000.0, 64, 4096, 32, 1)
    ang = np.arange(300)[:, None] * inv[None, :]
    m = H.yarn_mscale(64, mscale) / H.yarn_mscale(64, 1)
    cos, sin = (m * np.tile(f(ang), (1, 2))[None, :, None]
                for f in (np.cos, np.sin))
    rot = x[..., 8:].astype(np.float64)
    half = np.concatenate([-rot[..., 32:], rot[..., :32]], -1)
    np.testing.assert_array_equal(got[..., :8], x[..., :8])
    np.testing.assert_allclose(got[..., 8:], rot * cos + half * sin,
                               atol=2e-4, rtol=0)
    plain = nd.rope(nd.array(x), base=10000.0, rotary_dim=64,
                    seq_axis=1).asnumpy()
    assert np.abs(plain - got).max() > 0.1


def _parent_rope(x, base, r):
    """The ``rope`` op as the parent tree wrote it (rotate-half over the
    last ``r`` lanes, position i row i of axis 1)."""
    d = x.shape[-1]
    inv = float(base) ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    shape = [1] * x.ndim
    shape[1], shape[-1] = x.shape[1], r
    cos = jnp.tile(jnp.cos(ang), (1, 2)).reshape(shape)
    sin = jnp.tile(jnp.sin(ang), (1, 2)).reshape(shape)
    keep, rot = x[..., :d - r], x[..., d - r:].astype(jnp.float32)
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], axis=-1)
    out = (rot * cos + half * sin).astype(x.dtype)
    return out if r == d else jnp.concatenate([keep, out], axis=-1)


@pytest.mark.parametrize("shape,r", [((1, 257, 4, 192), 64),
                                     ((2, 64, 32), 32)])
def test_rope_without_scaling_is_the_parents_bit_for_bit(shape, r):
    """``scaling`` absent or None, the op is the parent's function: the same
    jaxpr as the parent's code, and the same bits either way."""
    from mxnet_tpu.ndarray.register import get_op
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    want = str(jax.make_jaxpr(lambda t: _parent_rope(t, 1e6, r))(x))
    maker = get_op("rope").maker
    for kw in ({}, {"scaling": None}):
        fn = maker(base=1e6, rotary_dim=r, seq_axis=1, **kw)
        assert str(jax.make_jaxpr(fn)(x)) == want
    a = np.random.default_rng(1).normal(size=shape).astype(np.float32)
    np.testing.assert_array_equal(
        nd.rope(nd.array(a), base=1e6, rotary_dim=r, seq_axis=1).asnumpy(),
        nd.rope(nd.array(a), base=1e6, rotary_dim=r, seq_axis=1,
                scaling=None).asnumpy())


def test_mla_with_yarn_is_the_reference():
    """``MLAttention`` with the configuration's YaRN scaling against the
    reference's MLA; the up-projections scaled so that the scores are far
    from uniform and the softmax factor shows."""
    cfg = tiny_config()
    w = dict(R.init_weights(cfg, 4))
    for leaf in ("l2.q_up_w", "l2.kv_up_w"):
        w[leaf] = 20 * w[leaf]
    att = MLAttention(cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["q_lora_rank"], cfg["kv_lora_rank"],
                      cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
                      epsilon=cfg["rms_norm_eps"],
                      rope_scaling=cfg["rope_scaling"], prefix="a_")
    att.initialize(mx.init.Zero())
    p = "l2."
    for leaf, path in (("q_down_w", "q_down.weight"),
                       ("q_norm_g", "q_norm.gamma"),
                       ("q_up_w", "q_up.weight"),
                       ("kv_down_w", "kv_down.weight"),
                       ("kv_norm_g", "kv_norm.gamma"),
                       ("kv_up_w", "kv_up.weight"), ("proj_w", "proj.weight")):
        block, attr = path.split(".")
        getattr(getattr(att, block), attr).set_data(
            nd.array(np.asarray(w[p + leaf])))
    x = np.random.default_rng(0).normal(
        size=(2, 40, cfg["hidden_size"])).astype(np.float32)
    got = att(nd.array(x)).asnumpy()
    with jax.default_matmul_precision("highest"):
        want = R.mla(w, p, jnp.asarray(x), cfg)
        faults = [R.mla(w, p, jnp.asarray(x), cfg, fault=f)
                  for f in ("yarn_off", "no_mscale")]
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
    for f in faults:
        assert float(np.abs(got - np.asarray(f)).max()) > 0.02 * scale


# -- Sinkhorn-Knopp and the hyper-connection ----------------------------------------

def _as_state(logits, c=128):
    """A state X (tokens, n c) and the leaves under which the kernel's H_res
    logits are ``logits`` (tokens, n, n) times rsqrt(1 + eps): the first n^2
    lanes carry them, the next brings the mean square to 1, phi's res rows
    pick the lanes, alpha (0, 0, 1), no bias, a gain of 1."""
    t, n = logits.shape[:2]
    x = np.zeros((t, n * c))
    x[:, :n * n] = logits.reshape(t, -1)
    x[:, n * n] = np.sqrt(n * c - np.square(x).sum(-1))
    phi = np.zeros((n * n + 2 * n, n * c))
    phi[2 * n + np.arange(n * n), np.arange(n * n)] = 1.0
    leaves = [phi, np.zeros(n * n + 2 * n), np.array([0.0, 0.0, 1.0]),
              np.ones(n * c)]
    return [jnp.asarray(a, jnp.float32) for a in [x] + leaves]


@functools.partial(jax.jit, static_argnames=("iters", "eps", "clamp"))
def _h_res(x, phi, bias, alpha, gamma, iters=20, eps=1e-6,
           clamp=(-30.0, 30.0)):
    """The kernel's H_res of the state x (..., n c)."""
    coef = hc_mod.pre(x, phi, bias, alpha, gamma, iters=iters, eps=eps,
                      clamp=clamp)[1]
    return hc_mod.unpack(coef)[2]


def _want_h_res(x, phi, bias, alpha, gamma, iters=20, eps=1e-6,
                clamp=(-30.0, 30.0)):
    """The float64 equations' H_res of the same state and leaves."""
    n = round(math.sqrt(phi.shape[0] + 1)) - 1
    x = np.asarray(x, np.float64)
    return H.coefficients(x.reshape(x.shape[:-1] + (n, -1)),
                          *(np.asarray(a, np.float64)
                            for a in (phi, bias, alpha, gamma)),
                          iters, eps, clamp)[2]


def test_sinkhorn_is_doubly_stochastic_after_twenty_steps():
    """The kernel's H_res after 20 steps: the columns (normalised last) sum
    to 1 within hc_eps and float32's rounding; the rows do too where the
    logits spread by 0.5, and where they spread as the cell's biases do (1)
    the rows are within 1e-3 at 20 steps and within hc_eps at 60: twenty
    steps are the configuration's, not a convergence criterion.  Against
    the float64 equations throughout."""
    r = np.random.default_rng(2)
    tol = 1e-6 + 2e-7

    def sums(state, iters):
        m = np.asarray(_h_res(*state, iters=iters), np.float64)
        np.testing.assert_allclose(m, _want_h_res(*state, iters=iters),
                                   atol=2e-6, rtol=0)
        assert (m > 0).all()
        return np.abs(m.sum(-1) - 1.0).max(), np.abs(m.sum(-2) - 1.0).max()
    for spread in (0.5, 1.0, 3.0):
        state = _as_state(spread * r.normal(size=(1024, 4, 4)))
        rows, cols = sums(state, 20)
        assert cols <= tol, spread
        assert rows <= {0.5: tol, 1.0: 1e-3, 3.0: 0.1}[spread], spread
        assert rows < sums(state, 5)[0]
        if spread == 1.0:
            assert max(sums(state, 60)) <= tol


def _hc_leaves(r, n, c, res_bias=1.0):
    phi = 0.02 * r.normal(size=(n * n + 2 * n, n * c))
    bias = np.concatenate([r.normal(0, 0.5, 2 * n),
                           r.normal(0, res_bias, n * n)])
    alpha = r.uniform(0.01, 0.1, 3)
    gamma = 1.0 + 0.02 * r.normal(size=n * c)
    return [a.astype(np.float32) for a in (phi, bias, alpha, gamma)]


def test_the_clamp_holds_at_thirty():
    """The kernel's H_res logits beyond +-30 read as +-30: biases of +-100
    give the H_res that biases of +-30 give."""
    r = np.random.default_rng(3)
    n, c = 4, 16
    x = jnp.asarray(r.normal(size=(5, n * c)), jnp.float32)
    phi, bias, alpha, gamma = _hc_leaves(r, n, c)
    sign = np.where(r.random(n * n) < 0.5, -1.0, 1.0).astype(np.float32)
    alpha = alpha * np.array([1, 1, 0], np.float32)     # res logits = bias
    out = {}
    for big in (30.0, 100.0):
        b = bias.copy()
        b[2 * n:] = big * sign
        out[big] = np.asarray(_h_res(x, phi, b, alpha, gamma))
    np.testing.assert_array_equal(out[30.0], out[100.0])
    b = bias.copy()
    b[2 * n:] = 29.0 * sign
    assert np.abs(np.asarray(_h_res(x, phi, b, alpha, gamma))
                  - out[30.0]).max() > 1e-6


def test_sinkhorn_gradient_is_finite_differences():
    """The kernel's gradient goes through all twenty steps, and through the
    norm and the projection that make the logits: the state's gradient
    against central differences of the float64 equations."""
    r = np.random.default_rng(4)
    state = _as_state(r.normal(size=(3, 4, 4)))
    weight = r.normal(size=(3, 4, 4))
    got = np.asarray(jax.grad(lambda x: jnp.sum(
        jnp.asarray(weight, jnp.float32) * _h_res(x, *state[1:])))(
        state[0]))[:, :16]
    x = np.asarray(state[0], np.float64)
    h = 1e-6
    want = np.zeros((3, 16))
    for i in np.ndindex(want.shape):
        up, down = x.copy(), x.copy()
        up[i] += h
        down[i] -= h
        want[i] = (np.sum(weight * _want_h_res(up, *state[1:]))
                   - np.sum(weight * _want_h_res(down, *state[1:]))) / (2 * h)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                               rtol=0)


def test_hyper_connection_is_the_equations():
    """The program's ``HyperConnection`` around a sublayer (here a fixed
    nonlinear map), and the benchmark reference's, against the float64
    equations; the gradients through the block against the reference's."""
    r = np.random.default_rng(5)
    n, c = 4, 24
    x = r.normal(size=(2, 7, n, c)).astype(np.float32)
    phi, bias, alpha, gamma = _hc_leaves(r, n, c)
    wf = (0.3 * r.normal(size=(c, c))).astype(np.float32)
    hc = HyperConnection(c, n, prefix="hc_")
    hc.initialize(mx.init.Zero())
    for p, v in ((hc.phi, phi), (hc.bias, bias), (hc.alpha, alpha),
                 (hc.gamma, gamma)):
        p.set_data(nd.array(v))

    def program(x):
        x_in, coef, x = hc(x)
        return nd.hyper_connection_mix(
            x, nd.tanh(nd.dot(x_in, nd.array(wf))), coef)
    xs = nd.array(x.reshape(2, 7, n * c))
    xs.attach_grad()
    with autograd.record():
        got = program(xs)
        total = (got * got).sum()
    total.backward()
    want = H.sublayer(x.astype(np.float64), lambda t: np.tanh(t @ wf), phi,
                      bias, alpha, gamma)
    np.testing.assert_allclose(got.asnumpy().reshape(x.shape), want,
                               atol=2e-5, rtol=0)
    cfg = {"hc_mult": n, "hc_eps": 1e-6, "hc_sinkhorn_iters": 20,
           "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
    w = {"q_phi": phi, "q_b": bias, "q_alpha": alpha, "q_norm_g": gamma}
    w = {k: jnp.asarray(v) for k, v in w.items()}

    def reference(x, w):
        return R.sublayer(w, "q_", x, cfg, lambda t: jnp.tanh(t @ wf))
    with jax.default_matmul_precision("highest"):
        ref_out = reference(jnp.asarray(x), w)
        dx, dw = jax.grad(lambda x, w: jnp.sum(jnp.square(reference(x, w))),
                          argnums=(0, 1))(jnp.asarray(x), w)
    np.testing.assert_allclose(np.asarray(ref_out), want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(xs.grad.asnumpy().reshape(x.shape),
                               np.asarray(dx), atol=1e-4, rtol=1e-4)
    for leaf, param in (("q_phi", hc.phi), ("q_b", hc.bias),
                        ("q_alpha", hc.alpha), ("q_norm_g", hc.gamma)):
        np.testing.assert_allclose(param.grad().asnumpy(),
                                   np.asarray(dw[leaf]), atol=1e-4,
                                   rtol=1e-3, err_msg=leaf)


# -- the model ----------------------------------------------------------------------

def build(cfg, seed=3):
    net = XM.build_net(cfg)
    net.initialize(mx.init.Zero())
    names = {}
    w = R.init_weights(cfg, seed)
    XM.assign(net, w, names)
    params = net.collect_params()
    return net, w, {leaf: params[n] for n, leaf in names.items()}


def test_the_model_is_the_reference():
    """Logits, MTP logits, the loss and every leaf's gradient of the dense
    block, two sparse blocks and the MTP module, each sublayer inside a
    hyper-connection, YaRN in the MLA blocks, one share of the experts
    held."""
    cfg = tiny_config(num_hidden_layers=3)
    tokens = np.random.default_rng(2).integers(
        0, cfg["vocab_size"], (1, 48)).astype(np.int32)
    net, w, leaves = build(cfg)
    t = nd.array(tokens, dtype="int32")
    loss_of = XM.lm_mtp_loss(cfg["mtp_loss_weight"])
    with autograd.record():
        main, mtp = net(t)
        loss = loss_of((main, mtp), t)
    loss.backward()
    grads = {k: p.grad().asnumpy() for k, p in leaves.items()
             if p.grad_req != "null"}
    fixed = R.buffers(cfg)
    train = {k: v for k, v in w.items() if k not in fixed}
    rest = {k: v for k, v in w.items() if k in fixed}
    with jax.default_matmul_precision("highest"):
        want_main, want_mtp = R.forward(w, cfg, tokens)
        want_loss, want = jax.value_and_grad(
            lambda tr: R.loss_fn({**tr, **rest}, cfg, tokens))(train)
    np.testing.assert_allclose(main.asnumpy(), want_main, atol=2e-5, rtol=0)
    np.testing.assert_allclose(mtp.asnumpy(), want_mtp, atol=2e-5, rtol=0)
    assert abs(float(loss.asnumpy()) - float(want_loss)) < 1e-5
    assert set(grads) == set(want)
    for leaf, g in want.items():
        np.testing.assert_allclose(grads[leaf], np.asarray(g), atol=2e-6,
                                   rtol=1e-4, err_msg=leaf)
    assert float(np.abs(grads["mtp.ffn_hc_phi"]).max()) > 0


def test_the_trainers_first_steps_are_the_references(one_device,
                                                      monkeypatch):
    """Through the normal path: ``ShardedTrainer`` with Adam and every block
    rematerialised: the loss of two steps, the first gradient's norms and
    each leaf's change after the Adam steps (the dense block, a sparse
    block and the MTP module), against the reference's equations followed
    in float64.  (Its float32 arithmetic is itself 1.28 times the limit
    away from them on ``l0.attn_hc_b``, whose h_pre entries have no
    gradient but the attention norm's eps: the four streams are equal in
    the first block, and the norm takes x_in's scale away.)"""
    cfg = tiny_config(num_hidden_layers=2)
    traffic = {"batch": 1, "seq": 48, "host_batches": 2}
    batches = XM.make_batches(cfg, traffic, 11)
    tr = XM.build_trainer(cfg, traffic, 11)
    losses = [tr.loss_value(tr.step(batches[0]))]
    norms = tr.first_gradient_norms()
    losses.append(tr.loss_value(tr.step(batches[1])))
    change = tr.change_norms()
    tr.free()
    seeded = R.init_weights
    monkeypatch.setattr(R, "init_weights", lambda cfg, seed: {
        k: v.astype(jnp.float64) if v.dtype == jnp.float32 else v
        for k, v in seeded(cfg, seed).items()})
    with jax.enable_x64(True):
        want = R.train_readings(cfg, 11, batches, cfg["learning_rate"],
                                dtype=jnp.float64, steps=2)
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    for got, ref in ((norms, want["grad_norm"]),
                     (change, want["change_norm"])):
        assert set(got) == set(ref)
        for leaf, n in ref.items():
            assert abs(got[leaf] - n) <= 1e-3 * n + 1e-8, leaf


def test_the_step_names_its_hyper_connections(one_device):
    """The compiled step carries the scopes that
    ``hyper_connection_device_ms.train`` reads, around both sublayers, and
    the four kernels inside them; ``hc.kernel_calls`` counts the kernels
    traced: each sublayer's two forward kernels for the step and again for
    its block's checkpoint, its two backward kernels once."""
    cfg = tiny_config(num_hidden_layers=2)
    traffic = {"batch": 1, "seq": 32, "host_batches": 1}
    (x, y), = XM.make_batches(cfg, traffic, 1)
    tr = XM.build_trainer(cfg, traffic, 1)
    calls = registry().counter("hc.kernel_calls")
    before = calls.value
    text = tr.tr.lower_step((nd.array(x[0], dtype="int32"),),
                            nd.array(y, dtype="int32")).as_text(
        debug_info=True)
    tr.free()
    assert calls.value - before == 6 * 2 * (2 + 1)   # two blocks and MTP
    for side in ("attn_hc", "ffn_hc"):
        for kernel in ("hc_pre_fwd", "hc_pre_bwd"):
            assert re.search(f"{side}/hc/pre/{kernel}", text), (side, kernel)
    # the mix takes no parameter: the cell calls its op between the blocks
    for kernel in ("hc_mix_fwd", "hc_mix_bwd"):
        assert re.search(rf"layer1/(remat/)?(checkpoint/)?hc/mix/{kernel}",
                         text), kernel


def test_gauges_of_the_streams():
    cfg = tiny_config()
    net, _, _ = build(cfg)
    gauges = registry().snapshot()
    assert gauges["hc.streams"] == 4
    assert gauges["hc.sinkhorn_iters"] == 20
    assert gauges["hc.sublayers"] == 2 * (5 + 1)
    net(nd.array(np.zeros((2, 24)), dtype="int32"))
    assert registry().get("hc.saved_stream_bytes").value == \
        5 * 2 * 24 * 4 * cfg["hidden_size"] * 4


def _kw(**over):
    kw = dict(vocab_size=50, units=16, num_layers=2, num_heads=2,
              q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, hidden_size=32,
              moe_hidden_size=8, num_experts=4, top_k=2, num_mtp=1)
    kw.update(over)
    return kw


def test_hc_absent_builds_no_new_parameter():
    plain = MLAMoELM(prefix="lm_", **_kw()).collect_params()
    streams = MLAMoELM(prefix="lm_", hc_mult=4, **_kw()).collect_params()
    assert not [k for k in plain if "hc" in k]
    extra = sorted(set(streams) - set(plain))
    assert len(extra) == 3 * 2 * 4       # 2 blocks + MTP, 2 sublayers each
    assert streams["lm_layer1_ffn_hc_phi"].shape == (24, 64)
    assert list(plain) == [k for k in streams if k in plain]


# -- one chip's share of the experts ---------------------------------------------

def test_the_shares_add_up():
    """The cell's router at a tiny width: the routed part that each of the
    eight shares of 8 of the 64 experts computes (top 4, renormalised,
    times 2), plus the shared expert once, is the uncut reference layer."""
    cfg = tiny_config(n_routed_experts=64, n_routed_experts_held=64,
                      hidden_size=16, moe_intermediate_size=8)
    w = R.init_weights(cfg, 6)
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(40, cfg["hidden_size"])).astype(np.float32))
    p = "l1."
    assert cfg["num_experts_per_tok"] == 4 and cfg[
        "routed_scaling_factor"] == 2
    with jax.default_matmul_precision("highest"):
        whole = RM.expert_layer(w, p, x, cfg)
        shared = RM.swiglu(x, w[p + "shared_gate_w"], w[p + "shared_up_w"],
                           w[p + "shared_down_w"])
        parts = 0
        for first in range(0, 64, 8):
            share = dict(w, **{p + f"experts_{n}_w":
                               w[p + f"experts_{n}_w"][first:first + 8]
                               for n in ("gate", "up", "down")})
            parts = parts + RM.expert_layer(share, p, x, cfg,
                                            held=(first, 8)) - shared
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5, rtol=0)


# -- the older cells' programs are the parent's -------------------------------------

# the jaxprs of the parent tree at the rehearsal's sizes, read with this code;
# Kimi's with the state pass's kernels in place of the delta rule's scan
PARENT_STEPS = {"glm47_flash_ep8.pretrain_s8k": "3fe348c3135d971d",
                "kimi_linear_48b_ep32.pretrain_s16k": "3e81d886df6b35c4"}
TINY_FILES = {"glm47_flash_ep8.pretrain_s8k": "tiny_mla_moe_lm.json",
              "kimi_linear_48b_ep32.pretrain_s16k": "tiny_kda_mla_moe_lm.json"}


def _fingerprint(text):
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _nd(a):
    return tuple(_nd(b) for b in a) if isinstance(a, tuple) \
        else nd.array(a, dtype=a.dtype.name)


@pytest.mark.parametrize("cell", sorted(PARENT_STEPS))
def test_no_hyper_connection_traces_the_parents_step(cell, one_device):
    """With ``hc_mult`` absent and no YaRN scaling, the GLM and Kimi cells'
    whole training steps (the rematerialised blocks, the flash kernels in
    interpret mode, the expert layer, Adam) trace to the parent's jaxpr."""
    _, c, cfg = loader.cell_and_config(loader.benchmark(), cell,
                                       os.path.join(DATA, TINY_FILES[cell]))
    model = loader.load_module("models", cfg["family"])
    x, y = model.make_batches(cfg, c["traffic_params"], 5)[0]
    tr = model.build_trainer(cfg, c["traffic_params"], 5)
    jaxpr = tr.tr.trace_step(_nd(tuple(x)), _nd(y)).jaxpr
    tr.free()
    assert _fingerprint(str(jaxpr)) == PARENT_STEPS[cell]
