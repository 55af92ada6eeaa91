"""Kimi Linear on the CPU at a tiny size against its plain reference
(``benchmarks/reference/kda_mla_moe_lm.py``: the delta rule as its
per-token recurrence): the channel-gated delta rule (``gated_delta_rule``
with a (B, L, H, dk) gate), its decayed products' kernels against the
halvings that made them before (``halving_products``), the state pass's
kernels against the scan that made it before (``scan_pass``), the flash
kernels with a value width of its own, ``MLAttention`` without rotary positions or a query bottleneck, the
five-layer ``MLAMoELM`` with ``layer_types``, one chip's share of the
experts, the expert layer in pieces, and the programs of the four older
cells, which must trace as the parent's did."""
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import MLAMoELM, MLAttention
from mxnet_tpu.kernels import decayed_products as dp_mod
from mxnet_tpu.kernels import gated_delta_rule as gdr_mod
from mxnet_tpu.kernels import state_pass as sp_mod
from mxnet_tpu.kernels.flash_attention import flash_attention
from mxnet_tpu.kernels.gated_delta_rule import gated_delta_rule
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.parallel import moe
from mxnet_tpu.profiler import scope_way

from benchmarks.harness import loader
from benchmarks.models import kda_mla_moe_lm as KM
from benchmarks.reference import kda_mla_moe_lm as R
from benchmarks.reference import mla_moe_lm as RM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kimi_linear_48b_ep32.pretrain_s16k"
TINY = os.path.join(ROOT, "benchmarks", "tests", "data",
                    "tiny_kda_mla_moe_lm.json")


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
    the five published layers (KDA dense, KDA, KDA, MLA, KDA), 2 KDA heads
    of 16, 2 MLA heads of 16 + 8 / 16, 4 of 16 experts held, top 4."""
    _, _, cfg = loader.cell_and_config(loader.benchmark(), CELL, TINY)
    cfg.update(over)
    return cfg


def rule_operands(seq, seed=0, h=2, dk=8, dv=12, strength=1.0):
    r = np.random.default_rng(seed)
    q, k = (r.normal(size=(1, seq, h, dk)).astype(np.float32)
            for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(1, seq, h, dv)).astype(np.float32)
    g = -strength * r.uniform(0.0, 1.0, (1, seq, h, dk)).astype(np.float32)
    beta = r.uniform(0.0, 1.0, (1, seq, h)).astype(np.float32)
    return [jnp.asarray(x) for x in (q, k, v, g, beta)]


def recurrence(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return R.kda_rule(q, k, v, g, beta)


# -- the channel-gated delta rule ------------------------------------------------

@pytest.mark.parametrize("seq", [64, 80, 20], ids=["one_chunk", "ragged",
                                                   "short"])
def test_channel_rule_is_the_recurrence(seq):
    """Outputs and all five gradients of the chunked form against the
    token-by-token recurrence (decay, then the update against the decayed
    state), at lengths that are and are not a multiple of the chunk."""
    args = rule_operands(seq)
    got = gated_delta_rule(*args)[0]
    np.testing.assert_allclose(got, recurrence(*args), atol=2e-6, rtol=0)
    ct = jnp.asarray(np.random.default_rng(1).normal(size=got.shape)
                     .astype(np.float32))
    grads = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a)[0] * ct),
                     argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * ct),
                    argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), grads, want):
        np.testing.assert_allclose(a, b, atol=3e-6 * float(
            jnp.max(jnp.abs(b))) + 1e-7, rtol=0, err_msg=name)


def test_equal_lanes_are_the_scalar_gate():
    """A channel gate whose lanes agree is the rule with one decay a head,
    outputs and final state."""
    q, k, v, g, beta = rule_operands(80)
    scalar = g[..., 0]
    o1, s1 = gated_delta_rule(q, k, v, jnp.broadcast_to(
        scalar[..., None], q.shape), beta)
    o2, s2 = gated_delta_rule(q, k, v, scalar, beta)
    np.testing.assert_allclose(o1, o2, atol=2e-6, rtol=0)
    np.testing.assert_allclose(s1, s2, atol=2e-6, rtol=0)


def test_a_strong_gate_stays_finite_and_exact():
    """Decays of up to e^-60 a token: a chunk's running sum reaches -3800,
    where ``exp(gamma_i) exp(-gamma_j)`` would overflow float32 (shown);
    the chunked form forms no positive exponent and stays the recurrence,
    outputs and gradients."""
    args = rule_operands(80, strength=60.0)
    gamma = np.cumsum(np.asarray(args[3])[0, :64], axis=0)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-gamma)).all()
    got = gated_delta_rule(*args)[0]
    np.testing.assert_allclose(got, recurrence(*args), atol=1e-5, rtol=0)
    grads = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a)[0]),
                     argnums=range(5))(*args)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)


def _largest(jaxpr):
    """The most elements of any value an equation of ``jaxpr`` (sub-jaxprs
    included) makes."""
    from tests._jaxpr import _equations
    return max((int(np.prod(v.aval.shape)) for e in _equations(jaxpr)
                for v in e.outvars if hasattr(v.aval, "shape")), default=0)


def test_no_value_of_tokens_by_chunk_by_key_lanes(monkeypatch):
    """The rule of one layer, forward and backward, at 512 tokens, 4 heads
    of 32 / 32: no value of tokens x C x dk elements (a (C, C, dk) decay a
    chunk) is ever made; the largest are what the rule with one decay a
    head holds too, a (tokens x heads x dk) operand or the (C, C) pairs of
    every chunk; and none is larger than the largest the halvings made
    (the kernels' lanes are padded to 128, a group's (2 x 8, 64, 128)
    operands no more than the halvings' largest)."""
    seq, h, dk = 512, 4, 32
    args = rule_operands(seq, h=h, dk=dk, dv=32)
    c = gdr_mod.chunk_of(seq)
    f = jax.value_and_grad(lambda *a: jnp.sum(gated_delta_rule(*a)[0]),
                           argnums=range(5))
    largest = _largest(jax.make_jaxpr(f)(*args).jaxpr)
    assert largest < seq * c * dk
    assert largest <= seq * h * max(dk, c)
    monkeypatch.setattr(dp_mod, "decayed_products", halving_products)
    assert largest <= _largest(jax.make_jaxpr(f)(*args).jaxpr)


def test_gauges_and_the_channel_gates_scope():
    q, k, v, g, beta = rule_operands(80)
    text = jax.jit(lambda *a: gated_delta_rule(*a)[0]).lower(
        q, k, v, g, beta).as_text(debug_info=True)
    assert registry().get("gdn.gate_lanes").value == 8
    assert "subchunk" in text
    text = jax.jit(lambda *a: gated_delta_rule(*a)[0]).lower(
        q, k, v, g[..., 0], beta).as_text(debug_info=True)
    assert registry().get("gdn.gate_lanes").value == 1
    assert "subchunk" not in text


# -- the decayed products' kernels ------------------------------------------------

def halving_products(q, k, gamma):
    """The oracle: the decayed products as the rule made them before its
    kernels, in ``jnp``.  Blocks of ``s`` rows on the diagonal are merged in
    pairs, ``[[P_first, 0], [X, P_second]]``, where the cross block ``X``
    of the pair's two halves is one product about the first half's last
    token ``r``: ``(x_i exp(gamma_i - gamma_r)) (k_j exp(gamma_r -
    gamma_j))^T``, no exponent positive."""
    c, dk = gamma.shape[-2:]
    lead = gamma.shape[:-2]

    def cross(s):
        def halves(x):
            x = x.reshape(lead + (c // (2 * s), 2, s, dk))
            return x[..., 0, :, :], x[..., 1, :, :]
        g0, g1 = halves(gamma)
        r = g0[..., s - 1:, :]
        right = halves(k)[0] * jnp.exp(r - g0)
        down = jnp.exp(g1 - r)
        return tuple(jnp.einsum("...id,...jd->...ij", halves(x)[1] * down,
                                right, precision="highest")
                     for x in (k, q))

    out, s = [jnp.sum(x * k, axis=-1)[..., None, None] for x in (k, q)], 1
    while s < c:
        merged = []
        for p, x in zip(out, cross(s)):
            p = p.reshape(lead + (c // (2 * s), 2, s, s))
            merged.append(jnp.concatenate(
                [jnp.concatenate([p[..., 0, :, :],
                                  jnp.zeros_like(p[..., 0, :, :])], -1),
                 jnp.concatenate([x, p[..., 1, :, :]], -1)], -2))
        out, s = merged, 2 * s
    return tuple(p.reshape(lead + (c, c)) for p in out)


def product_operands(c, dk, strength=1.0, lead=(2, 3), seed=0):
    """q, k (unit rows) and gamma, the running sum of a gate of up to
    e^-strength a token, (lead..., C, dk); and a cotangent for each
    product, lower triangle and all."""
    r = np.random.default_rng(seed)
    q, k = (r.normal(size=lead + (c, dk)).astype(np.float32)
            for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -strength * r.uniform(0.0, 1.0, lead + (c, dk)).astype(np.float32)
    cts = tuple(jnp.asarray(r.normal(size=lead + (c, c)).astype(np.float32))
                for _ in range(2))
    return [jnp.asarray(x) for x in (q, k, np.cumsum(g, axis=-2))], cts


def _near(got, want, rel):
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=0, atol=rel * float(
            jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("c,dk,strength", [
    (c, dk, 1.0) for c in (16, 32, 64) for dk in (8, 32, 128)] + [
    (64, 128, 60.0)])
def test_products_kernels_are_the_halvings(c, dk, strength):
    """The kernels (interpret mode; lanes padded to 128 below it) against
    the halvings, with decays of up to e^-strength a token (at 60 the
    pairs whose decay underflows are nought in both): P_k and P_q within
    1e-6 of their size, and dq, dk and dgamma against ``jax.vjp`` of the
    halvings, within 2e-6 of theirs, from cotangents that are not nought
    above the diagonal (``Q K^T``'s is not); all finite."""
    args, cts = product_operands(c, dk, strength)
    got, vjp = jax.vjp(dp_mod.decayed_products, *args)
    want, vjp_want = jax.vjp(halving_products, *args)
    grads = vjp(cts)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got + grads)
    _near(got, want, 1e-6)
    for a in got:
        assert not np.any(np.triu(np.asarray(a), 1))
    _near(grads, vjp_want(cts), 2e-6)


def test_products_kernels_leave_the_diagonal_out_of_dgamma():
    """A cotangent on the diagonals alone: ``x_i . k_i`` does not depend on
    gamma, so dgamma is nought to the last bit (the pair's two terms would
    cancel only to rounding, which no decay shrinks), and dq, dk are the
    diagonal's own ``D_ii k_i`` and ``2 D_ii k_i + D_ii q_i``."""
    args, cts = product_operands(64, 128, strength=60.0)
    eye = jnp.eye(64, dtype=bool)
    cts = tuple(jnp.where(eye, x, 0.0) for x in cts)
    dq, dk, dgamma = jax.vjp(dp_mod.decayed_products, *args)[1](cts)
    assert not np.any(np.asarray(dgamma))
    q, k, _ = args
    dkk, dqq = (jnp.diagonal(x, axis1=-2, axis2=-1)[..., None] for x in cts)
    _near((dq, dk), (dqq * k, 2.0 * dkk * k + dqq * q), 1e-6)


@pytest.mark.parametrize("strength", [1.0, 60.0])
def test_products_kernels_dgamma_sums_to_nought_over_a_chunk(strength):
    """P reads gamma through differences inside a chunk alone, so dgamma
    sums to nought over each chunk, channel by channel: the kernel's within
    1.5e-7 of its size, its chunk mean taken off (the two sums' rounding
    leaves up to 3.3e-7 under the strong gate, the halvings 7e-8)."""
    args, cts = product_operands(64, 128, strength)
    dgamma = np.asarray(jax.vjp(dp_mod.decayed_products, *args)[1](cts)[2],
                        np.float64)
    size = np.sum(np.abs(dgamma), axis=-2)
    assert np.all(np.abs(np.sum(dgamma, axis=-2)) <= 1.5e-7 * size)


def test_products_kernels_sit_under_subchunk_and_count(monkeypatch):
    """The channel rule's gradient lowered for the chip (``jax.export``,
    no chip needed): its Mosaic calls are the forward kernel, made again
    by the map body's checkpoint, and the backward kernel, each located
    under the scope ``subchunk`` (which ``gdn_channel_gate_device_ms.train``
    reads); ``gdn.products_kernel_calls`` counts each call traced."""
    from jax import export
    monkeypatch.setattr(dp_mod, "_interpret", lambda: False)
    S = jax.ShapeDtypeStruct
    args = [S((1, 80, 2, 8), jnp.float32)] * 4 + [S((1, 80, 2), jnp.float32)]
    calls = registry().counter("gdn.products_kernel_calls")
    before = calls.value
    text = export.export(jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a)[0]), argnums=range(5))),
        platforms=("tpu",))(*args).mlir_module()
    assert calls.value - before == 3
    assert text.count("@tpu_custom_call") == 3
    located = re.findall(r'loc\("([^"]*/gdn_decayed_products_(?:fwd|bwd))/',
                         text)
    assert sorted(x.rsplit("_", 1)[1] for x in located) == \
        ["bwd", "fwd", "fwd"]
    assert all(x.split("/")[-2] == "subchunk" for x in located)


@pytest.mark.parametrize("chunks,block", [(4, 4), (512, 8), (12, 6), (7, 7),
                                          (11, 1)])
def test_products_grid_step_divides_the_chunks(chunks, block):
    """A grid step holds the most chunks, up to 8, that divide the call's:
    two heads of the Kimi cell's 256 chunks take 8 a step."""
    assert dp_mod._block_of(chunks) == block



# -- the state pass's kernels ------------------------------------------------------

def scan_pass(w, u, kd, decay):
    """The oracle: the pass over the chunks as the rule made it before its
    kernels, a ``lax.scan`` with the chunks moved to the front, its
    gradients jax's own."""
    from jax import lax

    def scaled(d_n, s):
        return d_n[..., None] * s if d_n.ndim == 3 else \
            d_n[..., None, None] * s

    def body(s, x):
        w_n, u_n, kd_n, d_n = x
        un = u_n - jnp.matmul(w_n, s, precision="highest")
        nxt = scaled(d_n, s) + jnp.einsum(
            "bhck,bhcv->bhkv", kd_n, un, precision="highest")
        return nxt, (s, un)
    b, h, _, _, dk = w.shape
    final, (states, un) = lax.scan(
        body, jnp.zeros((b, h, dk, u.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (w, u, kd, decay)))
    return jnp.moveaxis(states, 0, 2), jnp.moveaxis(un, 0, 2), final


def pass_operands(n, dk, dv, channel, strength=1.0, lead=(1, 4), c=16,
                  seed=0):
    """``w``, ``u``, ``kd`` and a chunk's decay ``exp(gamma_C)`` from a gate
    of up to e^-strength a token (at 60 it underflows to nought), and a
    cotangent for each of the pass's three results."""
    r = np.random.default_rng(seed)
    w, kd = (0.1 * r.normal(size=lead + (n, c, dk)) for _ in range(2))
    u = r.normal(size=lead + (n, c, dv))
    gate = lead + (n, dk) if channel else lead + (n,)
    decay = np.exp(-strength * np.sum(r.uniform(0.0, 0.1, gate + (c,)),
                                      axis=-1))
    cts = [r.normal(size=lead + tail) for tail in
           ((n, dk, dv), (n, c, dv), (dk, dv))]
    return ([jnp.asarray(x, jnp.float32) for x in (w, u, kd, decay)],
            tuple(jnp.asarray(x, jnp.float32) for x in cts))


@pytest.mark.parametrize("n,dk,dv,channel,strength,lead", [
    (11, 128, 128, True, 1.0, (1, 4)),
    (16, 128, 128, False, 1.0, (1, 2)),
    (5, 96, 192, False, 1.0, (2, 3)),
    (19, 96, 192, True, 1.0, (1, 2)),
    (11, 128, 128, True, 60.0, (1, 4))],
    ids=["kimi_widths_ragged", "scalar_two_blocks", "olmo_widths_short",
         "olmo_widths_ragged_channel", "strong_gate"])
def test_state_pass_kernels_are_the_scan(n, dk, dv, channel, strength, lead):
    """The kernels (interpret mode) against the scan: the states, ``U'``
    and the final state within 1e-6 of their size, and the cotangents of
    ``w``, ``u``, ``kd`` and the decay within 1e-5 of theirs (the scalar
    gate's is summed over (dk, dv) in another order), at the Kimi and Olmo
    cells' widths, with both gates, chunk counts that a grid step's eight
    do not divide and fewer than eight, and a decay that underflows."""
    args, cts = pass_operands(n, dk, dv, channel, strength, lead)
    got, vjp = jax.vjp(sp_mod.state_pass, *args)
    want, vjp_want = jax.vjp(scan_pass, *args)
    grads = vjp(cts)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got + grads)
    _near(got, want, 1e-6)
    _near(grads, vjp_want(cts), 1e-5)


@pytest.mark.parametrize("channel", [False, True], ids=["scalar", "channel"])
def test_state_pass_kernels_sit_under_state_and_count(monkeypatch, channel):
    """The rule's gradient lowered for the chip (``jax.export``, no chip
    needed; the decayed products left in interpret mode): one call of
    ``gdn_state_pass_fwd`` and one of ``gdn_state_pass_bwd``, each located
    under the scope ``state`` (which ``gdn_rule_device_ms.train`` reads)
    on its own side of the step, and no ``while`` under the scalar gate
    (the channel gate's groups are ``lax.map``s); ``gdn.state_kernel_calls`` counts each call traced and
    ``gdn.scan_steps`` the chunks each walks in sequence."""
    from jax import export
    monkeypatch.setattr(sp_mod, "_interpret", lambda: False)
    S = jax.ShapeDtypeStruct
    gate = (1, 80, 2, 8) if channel else (1, 80, 2)
    args = [S((1, 80, 2, 8), jnp.float32)] * 3 + [S(gate, jnp.float32),
                                                  S((1, 80, 2), jnp.float32)]
    calls = registry().counter("gdn.state_kernel_calls")
    steps = registry().gauge("gdn.scan_steps")
    before = calls.value, steps.value
    text = export.export(jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a)[0]), argnums=range(5))),
        platforms=("tpu",))(*args).mlir_module()
    assert (calls.value - before[0], steps.value - before[1]) == (2, 4)
    assert text.count("@tpu_custom_call") == 2
    assert channel or "stablehlo.while" not in text
    located = re.findall(r'loc\("([^"]*/gdn_state_pass_(?:fwd|bwd))/', text)
    assert sorted((x.rsplit("_", 1)[1],) + scope_way(x) for x in located) \
        == [("bwd", "state", "bwd"), ("fwd", "state", "fwd")]


@pytest.mark.parametrize("rows,chunks,blocks", [
    (32, 256, (4, 8)), (30, 32, (3, 8)), (6, 5, (3, 5)), (7, 11, (1, 8))])
def test_state_pass_grid_step(rows, chunks, blocks):
    """A grid step takes the most heads, up to four, that divide the rows
    (the Kimi cell's 32 take 4, Olmo's 30 take 3), and eight chunks, or all
    where fewer: a last block may be partial."""
    assert sp_mod._blocks(rows, chunks) == blocks

# -- flash attention with a value width of its own --------------------------------

def _softmax_attention(q, k, v, scale):
    s = q.shape[-2]
    with jax.default_matmul_precision("highest"):
        sc = jnp.einsum("...qd,...kd->...qk", q, k) * scale
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
        return jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(sc, -1), v)


@pytest.mark.parametrize("shape", [(4, 200, 192, 128), (2, 2, 130, 96, 64),
                                   (2, 300, 64, 32)],
                         ids=["mla_192_128", "4d_96_64", "64_32"])
def test_flash_kernels_take_v_at_its_own_width(shape):
    """Forward, dq, dk and dv of the three kernels (interpret mode) at a
    value width other than the query/key width, heads-first, against the
    XLA softmax; the backward's operands reach the MXU as bfloat16, so its
    gradients are compared to 2e-2 of their size."""
    *lead, seq, d, dv = shape
    r = np.random.default_rng(7)
    q, k = (jnp.asarray(r.normal(size=(*lead, seq, d)).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(r.normal(size=(*lead, seq, dv)).astype(np.float32))
    g = jnp.asarray(r.normal(size=(*lead, seq, dv)).astype(np.float32))
    scale = d ** -0.5
    got, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=True,
                                                  interpret=True), q, k, v)
    want, vjp_want = jax.vjp(lambda *a: _softmax_attention(*a, scale),
                             q, k, v)
    assert got.shape == want.shape == (*lead, seq, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    for name, a, b in zip("qkv", vjp(g), vjp_want(g)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-2 * float(
            jnp.max(jnp.abs(b))), rtol=0, err_msg=name)


# -- the model --------------------------------------------------------------------

def build(cfg, seed=3):
    net = KM.build_net(cfg)
    net.initialize(mx.init.Zero())
    names = {}
    w = R.init_weights(cfg, seed)
    KM.assign(net, cfg, w, names)
    params = net.collect_params()
    return net, w, {leaf: params[n] for n, leaf in names.items()}


def test_mla_without_rope_or_query_bottleneck_is_the_reference():
    cfg = tiny_config()
    w = R.init_weights(cfg, 4)
    att = MLAttention(cfg["hidden_size"], cfg["num_attention_heads"], None,
                      cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"], rope=False,
                      epsilon=cfg["rms_norm_eps"], prefix="a_")
    att.initialize(mx.init.Zero())
    p = "l3."
    for leaf, path in (("q_w", "q"), ("kv_down_w", "kv_down"),
                       ("kv_up_w", "kv_up"), ("proj_w", "proj")):
        getattr(att, path).weight.set_data(nd.array(np.asarray(w[p + leaf])))
    att.kv_norm.gamma.set_data(nd.array(np.asarray(w[p + "kv_norm_g"])))
    x = np.random.default_rng(0).normal(
        size=(2, 24, cfg["hidden_size"])).astype(np.float32)
    got = att(nd.array(x)).asnumpy()
    with jax.default_matmul_precision("highest"):
        want = R.mla_mixer(w, p, jnp.asarray(x), cfg)
        rotated = R.mla_mixer(w, p, jnp.asarray(x), cfg, fault="mla_rope")
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert float(np.abs(got - np.asarray(rotated)).max()) > 1e-4
    assert not hasattr(att, "q_down")


def _program(cfg, tokens, seed=3):
    net, w, leaves = build(cfg, seed)
    t = nd.array(tokens, dtype="int32")
    with autograd.record():
        logits = net(t)
        loss = KM.lm_loss(logits, t)
    loss.backward()
    grads = {k: p.grad().asnumpy() for k, p in leaves.items()
             if p.grad_req != "null"}
    return w, logits.asnumpy(), float(loss.asnumpy()), grads


def test_the_five_layers_are_the_reference():
    """Logits, the loss and every leaf's gradient of the five published
    layers (KDA with the dense SwiGLU, KDA, KDA, MLA, KDA, each of the last
    four with the expert layer), one share of the experts held."""
    cfg = tiny_config()
    tokens = np.random.default_rng(2).integers(
        0, cfg["vocab_size"], (1, 80)).astype(np.int32)
    w, logits, loss, grads = _program(cfg, tokens)
    fixed = R.buffers(cfg)
    train = {k: v for k, v in w.items() if k not in fixed}
    rest = {k: v for k, v in w.items() if k in fixed}
    with jax.default_matmul_precision("highest"):
        want_logits = R.forward(w, cfg, tokens)
        want_loss, want = jax.value_and_grad(
            lambda tr: R.loss_fn({**tr, **rest}, cfg, tokens))(train)
    np.testing.assert_allclose(logits, want_logits, atol=1e-5, rtol=0)
    assert abs(loss - float(want_loss)) < 1e-5
    assert set(grads) == set(want)
    for leaf, g in want.items():
        np.testing.assert_allclose(grads[leaf], np.asarray(g), atol=2e-6,
                                   rtol=1e-4, err_msg=leaf)
    assert float(np.abs(grads["l0.gdn_f_b_w"]).max()) > 0


def test_the_trainers_first_steps_are_the_references(one_device):
    """Through the normal path: ``ShardedTrainer`` with Adam and every block
    rematerialised (the KDA mixer's nested checkpoints inside): the loss of
    two steps, the first gradient's norms and each leaf's change."""
    cfg = tiny_config()
    traffic = {"batch": 1, "seq": 80, "host_batches": 2}
    batches = KM.make_batches(cfg, traffic, 11)
    tr = KM.build_trainer(cfg, traffic, 11)
    losses = [tr.loss_value(tr.step(batches[0]))]
    norms = tr.first_gradient_norms()
    losses.append(tr.loss_value(tr.step(batches[1])))
    change = tr.change_norms()
    tr.free()
    want = R.train_readings(cfg, 11, batches, cfg["learning_rate"], steps=2)
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    for got, ref in ((norms, want["grad_norm"]),
                     (change, want["change_norm"])):
        assert set(got) == set(ref)
        for leaf, n in ref.items():
            assert abs(got[leaf] - n) <= 1e-3 * n + 1e-8, leaf


def test_layer_types_are_checked():
    kw = dict(vocab_size=50, units=16, num_layers=2, num_heads=2,
              q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, hidden_size=32,
              moe_hidden_size=8, num_experts=4, top_k=2, num_mtp=0,
              linear_num_heads=2, linear_head_dim=8)
    with pytest.raises(MXNetError):
        MLAMoELM(layer_types=["full_attention"], **kw)
    with pytest.raises(MXNetError):
        MLAMoELM(layer_types=["full_attention", "window"], **kw)
    net = MLAMoELM(layer_types=["linear_attention", "full_attention"], **kw)
    assert hasattr(net.cells[0], "gdn") and hasattr(net.cells[1], "mla")


# -- one chip's share of the experts ---------------------------------------------

def test_the_shares_add_up():
    """At a tiny size, the routed part that each of the four shares of 4
    experts computes, plus the shared expert once, is the uncut reference
    layer of all 16."""
    cfg = tiny_config()
    w = R.init_weights(dict(cfg, n_routed_experts_held=16), 6)
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(40, cfg["hidden_size"])).astype(np.float32))
    mc = R.moe_cfg(dict(cfg, n_routed_experts_held=16))
    p = "l1."
    with jax.default_matmul_precision("highest"):
        whole = RM.expert_layer(w, p, x, mc)
        shared = RM.swiglu(x, w[p + "shared_gate_w"], w[p + "shared_up_w"],
                           w[p + "shared_down_w"])
        parts = 0
        for first in range(0, 16, 4):
            share = dict(w, **{p + f"experts_{n}_w":
                               w[p + f"experts_{n}_w"][first:first + 4]
                               for n in ("gate", "up", "down")})
            parts = parts + RM.expert_layer(share, p, x, mc,
                                            held=(first, 4)) - shared
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5, rtol=0)


def test_the_expert_layer_in_pieces_is_the_whole(monkeypatch):
    """Past its buffer's byte budget the layer takes the tokens in equal
    pieces, one after another: outputs, loads and gradients are those of
    the whole."""
    r = np.random.default_rng(0)
    t, d, h, e, held = 64, 16, 8, 8, 4
    x = jnp.asarray(r.normal(size=(t, d)).astype(np.float32))
    ws = [jnp.asarray(0.3 * r.normal(size=s).astype(np.float32)) for s in
          ((e, d), (held, d, h), (held, d, h), (held, h, d))]
    bias = jnp.zeros((e,), jnp.float32)

    def run(x, rw, wg, wu, wd):
        y, load = moe.routed_experts(x, rw, bias, wg, wu, wd, top_k=4,
                                     scale=2.0)
        return jnp.sum(y * y), (y, load)
    whole = jax.value_and_grad(run, argnums=range(5), has_aux=True)(x, *ws)
    assert moe._pieces(t, 4, d, 4) == 1
    monkeypatch.setattr(moe, "_BUFFER_BYTES", 4 * 16 * d * 4)
    assert moe._pieces(t, 4, d, 4) == 4
    pieces = jax.value_and_grad(run, argnums=range(5), has_aux=True)(x, *ws)
    assert registry().get("moe.buffer_rows").value == 4 * t
    (v1, (y1, l1)), g1 = whole
    (v2, (y2, l2)), g2 = pieces
    np.testing.assert_allclose(y2, y1, atol=1e-5, rtol=0)
    np.testing.assert_array_equal(l2, l1)
    for a, b in zip(g2, g1):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# -- the older cells' programs are the parent's -------------------------------------

def _fingerprint(text):
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# the jaxprs of PR 39's tree (the parent of PR 40) at these sizes, read with
# the same code there and here; a PR that changes one of these programs on
# purpose writes its new fingerprint (the Olmo step and the scalar rule's:
# the state pass's kernels)
PARENT_STEPS = {"bert_base.pretrain_s512": "6ea0398824758236",
                "glm47_flash_ep8.pretrain_s8k": "3fe348c3135d971d",
                "olmo_hybrid_7b_l4.pretrain_s2k": "69961ebcc5e5bdab",
                "smallthinker_21b_ep8.pretrain_s16k": "bb679c5b52ba8199"}
TINY_FILES = {"bert_base.pretrain_s512": "tiny_bert.json",
              "glm47_flash_ep8.pretrain_s8k": "tiny_mla_moe_lm.json",
              "olmo_hybrid_7b_l4.pretrain_s2k": "tiny_gdn_hybrid_lm.json",
              "smallthinker_21b_ep8.pretrain_s16k": "tiny_swa_moe_lm.json"}


def _nd(a):
    return tuple(_nd(b) for b in a) if isinstance(a, tuple) \
        else nd.array(a, dtype=a.dtype.name)


@pytest.mark.parametrize("cell", sorted(PARENT_STEPS))
def test_older_cells_trace_the_parents_step(cell, one_device):
    """The whole training step of each older cell's family (its model, the
    rematerialised blocks, the flash kernels in interpret mode, the expert
    layer, the scalar delta rule, Adam) at the rehearsal's tiny sizes
    traces to the jaxpr of its fingerprint, the Olmo cell's with the state
    pass's kernels in place of its scan."""
    _, c, cfg = loader.cell_and_config(
        loader.benchmark(), cell,
        os.path.join(ROOT, "benchmarks", "tests", "data", TINY_FILES[cell]))
    model = loader.load_module("models", cfg["family"])
    x, y = model.make_batches(cfg, c["traffic_params"], 5)[0]
    tr = model.build_trainer(cfg, c["traffic_params"], 5)
    jaxpr = tr.tr.trace_step(_nd(tuple(x)), _nd(y)).jaxpr
    tr.free()
    assert _fingerprint(str(jaxpr)) == PARENT_STEPS[cell]


PARENT_KERNELS = {"rule_fwd": "6fa49aa2920db991",
                  "rule_grad": "aed20f7f4d82a307",
                  "flash_heads_first_grad": "03e117ebbf37e5a1",
                  "flash_fused_grad": "c72b5bc4133ce1d0",
                  "flash_tokens_major_grad": "25a777cab5ae9fd9",
                  "flash_window_group_grad": "2263aea2cb765975"}


def _kernel_jaxpr(name):
    S = jax.ShapeDtypeStruct

    def grad_of(f, *shapes):
        args = [S(s, jnp.float32) for s in shapes]

        def loss(*a):
            out = f(*a)
            out = out[0] if isinstance(out, tuple) else out
            return jnp.sum(out * out)
        return jax.make_jaxpr(jax.value_and_grad(
            loss, argnums=tuple(range(len(args)))))(*args)

    def fa(**kw):
        return lambda q, k, v: flash_attention(q, k, v, interpret=True, **kw)
    rule = ((1, 80, 2, 16),) * 2 + ((1, 80, 2, 24), (1, 80, 2), (1, 80, 2))
    if name == "rule_fwd":
        return jax.make_jaxpr(gated_delta_rule)(
            *[S(s, jnp.float32) for s in rule])
    if name == "rule_grad":
        return grad_of(gated_delta_rule, *rule)
    if name == "flash_heads_first_grad":
        return grad_of(fa(causal=True, scale=0.07), *((4, 384, 128),) * 3)
    if name == "flash_fused_grad":
        f = fa(causal=False, head_dim=64, num_heads=2, first_head=(0, 2, 4))
        return grad_of(lambda a: f(a, a, a), (2, 256, 384))
    if name == "flash_tokens_major_grad":
        return grad_of(fa(causal=True, num_heads=2), *((1, 384, 256),) * 3)
    return grad_of(fa(causal=True, num_heads=4, num_kv_heads=1, window=128),
                   (1, 512, 512), (1, 512, 128), (1, 512, 128))


@pytest.mark.parametrize("name", sorted(PARENT_KERNELS))
def test_older_kernel_calls_trace_the_parents(name):
    """The scalar delta rule (Olmo's call) and the flash calls as the BERT
    (fused projection), GLM (heads-first), Olmo (tokens-major) and
    SmallThinker (grouped heads under a window) cells make them: forward
    and gradient jaxprs as their fingerprints record them (the rule's with
    the state pass's kernels in place of its scan); the value width and the
    channel gate add nothing where they are not used."""
    assert _fingerprint(str(_kernel_jaxpr(name))) == PARENT_KERNELS[name]
