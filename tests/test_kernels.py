"""In-tree Pallas kernel tests (multi-tensor optimizer apply).

Reference parity: src/operator/optimizer_op.cc multi_sgd_update family
(SURVEY.md §2.2 optimizer_op row; §7 M9 native hardening).  On the CPU
test mesh the kernels run under the Pallas interpreter — the same code
Mosaic compiles on TPU.  The fixture below opts THIS module into real
interpret mode (production off-TPU dispatch uses the kernels' jnp duals;
these tests exist to execute the kernel bodies themselves).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from tests._jaxpr import pallas_call_names, primitives_outside_kernels


@pytest.fixture(autouse=True)
def _real_interpret_mode(monkeypatch):
    # the op-dispatch compile caches key on (op, kwargs), not on this env
    # var — drop them on BOTH sides of the test: before, so a jnp-dual
    # entry traced by an earlier module cannot satisfy a kernel test
    # without executing the kernel body; after, so interpret-mode entries
    # can't leak into (and slow down) later modules
    from mxnet_tpu.ndarray.register import clear_op_caches
    clear_op_caches()
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    yield
    clear_op_caches()


SHAPES = [(3, 5), (1000,), (17, 9, 2), (1,), (128, 128)]


def _rand_set(seed=0):
    rng = np.random.default_rng(seed)
    ws = [rng.standard_normal(s, dtype=np.float32) for s in SHAPES]
    gs = [rng.standard_normal(s, dtype=np.float32) for s in SHAPES]
    ms = [rng.standard_normal(s, dtype=np.float32) for s in SHAPES]
    lrs = [0.1, 0.2, 0.3, 0.4, 0.05]
    wds = [0.0, 0.01, 0.1, 0.0, 0.001]
    return ws, gs, ms, lrs, wds


def test_fused_multi_sgd_matches_formula():
    from mxnet_tpu.kernels import fused_multi_sgd
    ws, gs, _, lrs, wds = _rand_set()
    outs = fused_multi_sgd(ws, gs, lrs, wds, rescale_grad=0.5,
                           clip_gradient=1.0)
    for w, g, lr, wd, o in zip(ws, gs, lrs, wds, outs):
        expect = w - lr * (np.clip(g * 0.5, -1, 1) + wd * w)
        assert o.shape == w.shape
        assert np.allclose(np.asarray(o), expect, atol=1e-6)


def test_fused_multi_sgd_mom_matches_formula():
    from mxnet_tpu.kernels import fused_multi_sgd_mom
    ws, gs, ms, lrs, wds = _rand_set(1)
    wo, mo = fused_multi_sgd_mom(ws, gs, ms, lrs, wds, momentum=0.9)
    for w, g, m, lr, wd, ow, om in zip(ws, gs, ms, lrs, wds, wo, mo):
        mn = 0.9 * m - lr * (g + wd * w)
        assert np.allclose(np.asarray(om), mn, atol=1e-6)
        assert np.allclose(np.asarray(ow), w + mn, atol=1e-6)


def test_multi_sgd_op_registry_dispatch():
    """multi_sgd_update through the op registry with out= write-back."""
    ws = [nd.array(np.full((4, 3), 2.0, np.float32)),
          nd.array(np.full((7,), 3.0, np.float32))]
    gs = [nd.array(np.ones((4, 3), np.float32)),
          nd.array(np.ones((7,), np.float32))]
    lrs = nd.array(np.array([0.5, 0.1], np.float32))
    wds = nd.array(np.zeros(2, np.float32))
    outs = nd.multi_sgd_update(ws[0], gs[0], ws[1], gs[1], lrs, wds,
                               num_weights=2)
    assert np.allclose(outs[0].asnumpy(), 1.5)
    assert np.allclose(outs[1].asnumpy(), 2.9)


def test_trainer_aggregated_matches_per_tensor():
    """The fused Pallas path must be bit-for-bit interchangeable with the
    per-tensor update loop."""
    np.random.seed(0)
    X = nd.array(np.random.randn(16, 6).astype(np.float32))
    Y = nd.array(np.random.randint(0, 4, 16), dtype="int32")
    mx.random.seed(3)

    def mknet():
        net = gluon.nn.Sequential()
        net.add(gluon.nn.Dense(9, activation="relu"), gluon.nn.Dense(4))
        net.initialize()
        net(X)
        return net

    net_a, net_b = mknet(), mknet()
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        pb.set_data(pa.data())
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9,
                          "wd": 1e-3})
    assert tr_a._optimizer.aggregate_num > 1  # fused path active
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9,
                          "wd": 1e-3})
    tr_b._optimizer.aggregate_num = 0          # per-tensor path
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(5):
        for net, tr in ((net_a, tr_a), (net_b, tr_b)):
            with autograd.record():
                L = lossfn(net(X), Y).mean()
            L.backward()
            tr.step(1)
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        assert np.allclose(pa.data().asnumpy(), pb.data().asnumpy(),
                           atol=1e-6), pa.name


def test_trainer_aggregated_multi_precision():
    """multi_mp path: bf16 weights, fp32 masters, fused apply."""
    np.random.seed(0)
    X = nd.array(np.random.randn(8, 5).astype(np.float32)).astype("bfloat16")
    Y = nd.array(np.random.randint(0, 3, 8), dtype="int32")
    mx.random.seed(5)
    net = gluon.nn.Dense(3, dtype="bfloat16")
    net.initialize()
    net(X)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9,
                        "multi_precision": True})
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    losses = []
    for _ in range(20):
        with autograd.record():
            L = lossfn(net(X), Y).mean()
        L.backward()
        tr.step(1)
        losses.append(float(L.asnumpy()))
    assert losses[-1] < losses[0]
    # fp32 master exists and tracks the bf16 weight
    state = tr._states[(0, list(net.weight._data)[0])]
    assert isinstance(state, tuple) and state[1].dtype == np.float32


def test_lr_schedule_does_not_retrace():
    """lrs ride as array inputs: changing lr must hit the same compiled
    fn (VERDICT hard-part #6: imperative dispatch fast path)."""
    from mxnet_tpu.ndarray.register import get_op
    op = get_op("multi_sgd_update")
    before = op.cache_info()["fn"]["misses"]
    w = nd.array(np.ones((8,), np.float32))
    g = nd.array(np.ones((8,), np.float32))
    for lr in (0.1, 0.2, 0.3):
        lrs = nd.array(np.array([lr], np.float32))
        wds = nd.array(np.zeros(1, np.float32))
        nd.multi_sgd_update(w, g, lrs, wds, num_weights=1)
    after = op.cache_info()["fn"]["misses"]
    assert after - before <= 1


def _ref_attn(q, k, v, causal=False, scale=None):
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    s = (q * scale) @ np.swapaxes(k, -1, -2)
    if causal:
        lq, lk = s.shape[-2:]
        mask = np.tril(np.ones((lq, lk), bool), lk - lq)
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return p @ v


def test_flash_attention_matches_reference():
    """Tiled online-softmax kernel == full softmax(QKᵀ)V, including
    cross-attention lengths and causal masking (kernels/flash_attention)."""
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    rs = np.random.RandomState(0)
    q = rs.randn(2, 256, 128).astype(np.float32)
    k = rs.randn(2, 384, 128).astype(np.float32)
    v = rs.randn(2, 384, 128).astype(np.float32)
    out = np.asarray(flash_attention(jnp.array(q), jnp.array(k),
                                     jnp.array(v)))
    np.testing.assert_allclose(out, _ref_attn(q, k, v), atol=2e-5)

    q2 = rs.randn(1, 256, 128).astype(np.float32)
    out = np.asarray(flash_attention(jnp.array(q2), jnp.array(q2),
                                     jnp.array(q2), causal=True))
    np.testing.assert_allclose(out, _ref_attn(q2, q2, q2, causal=True),
                               atol=2e-5)


def test_flash_attention_ragged_and_4d():
    """Non-tile-multiple L/D get padded internally with exact K masking;
    (B, H, L, D) inputs round-trip."""
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    rs = np.random.RandomState(1)
    q = rs.randn(3, 100, 64).astype(np.float32)
    k = rs.randn(3, 75, 64).astype(np.float32)
    v = rs.randn(3, 75, 64).astype(np.float32)
    out = np.asarray(flash_attention(jnp.array(q), jnp.array(k),
                                     jnp.array(v)))
    np.testing.assert_allclose(out, _ref_attn(q, k, v), atol=2e-5)

    q4 = rs.randn(2, 4, 128, 32).astype(np.float32)
    out = np.asarray(flash_attention(jnp.array(q4), jnp.array(q4),
                                     jnp.array(q4), causal=True))
    assert out.shape == (2, 4, 128, 32)
    np.testing.assert_allclose(out, _ref_attn(q4, q4, q4, causal=True),
                               atol=2e-5)


def test_flash_attention_op_and_transformer_path(monkeypatch):
    """The registered _contrib_flash_attention op and the env-gated
    MultiHeadAttention inference path must match the XLA softmax path."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import MultiHeadAttention

    rs = np.random.RandomState(2)
    q = mx.nd.array(rs.randn(2, 40, 16).astype(np.float32))
    k = mx.nd.array(rs.randn(2, 30, 16).astype(np.float32))
    v = mx.nd.array(rs.randn(2, 30, 16).astype(np.float32))
    out = mx.nd.flash_attention(q, k, v).asnumpy()
    np.testing.assert_allclose(
        out, _ref_attn(q.asnumpy(), k.asnumpy(), v.asnumpy(),
                       scale=1.0 / np.sqrt(16)), atol=2e-5)

    att = MultiHeadAttention(units=32, num_heads=4)
    att.initialize()
    x = mx.nd.array(rs.randn(2, 20, 32).astype(np.float32))
    base = att(x).asnumpy()
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    flash = att(x).asnumpy()
    np.testing.assert_allclose(flash, base, atol=3e-5)


def test_flash_attention_causal_decode_alignment():
    """Causal masking must be bottom-right aligned: a 1-token query
    against an N-token KV cache (decode step) attends ALL N keys, and
    Lq<Lk generally offsets by Lk-Lq (review regression)."""
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    rs = np.random.RandomState(3)
    # decode: Lq=1 vs cache of 16
    q = rs.randn(1, 1, 32).astype(np.float32)
    k = rs.randn(1, 16, 32).astype(np.float32)
    v = rs.randn(1, 16, 32).astype(np.float32)
    out = np.asarray(flash_attention(jnp.array(q), jnp.array(k),
                                     jnp.array(v), causal=True))
    np.testing.assert_allclose(out, _ref_attn(q, k, v, causal=True),
                               atol=2e-5)
    # general Lq < Lk
    q = rs.randn(2, 4, 32).astype(np.float32)
    k = rs.randn(2, 16, 32).astype(np.float32)
    v = rs.randn(2, 16, 32).astype(np.float32)
    out = np.asarray(flash_attention(jnp.array(q), jnp.array(k),
                                     jnp.array(v), causal=True))
    np.testing.assert_allclose(out, _ref_attn(q, k, v, causal=True),
                               atol=2e-5)


def test_flash_attention_causal_lq_gt_lk_dead_rows():
    """valid_lq > valid_lk under causal: early queries have NO unmasked
    keys; the reference degenerates to uniform attention over the valid
    keys — padded slots must not absorb weight (review regression)."""
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    rs = np.random.RandomState(4)
    q = rs.randn(1, 8, 32).astype(np.float32)
    k = rs.randn(1, 4, 32).astype(np.float32)
    v = rs.randn(1, 4, 32).astype(np.float32)
    out = np.asarray(flash_attention(jnp.array(q), jnp.array(k),
                                     jnp.array(v), causal=True))
    np.testing.assert_allclose(out, _ref_attn(q, k, v, causal=True),
                               atol=2e-5)
    # rows 0..3 (bound < 0) must equal mean of the 4 valid V rows
    np.testing.assert_allclose(out[0, 0], v[0].mean(0), atol=2e-5)


def _assert_grads_close(got, want):
    """The backward's kernels multiply as the chip's default precision
    does (operands rounded to bfloat16 once, on every platform): each
    gradient is held to 2 % of the reference's largest entry and to 1 % as
    a vector (what one bfloat16 rounding leaves is 0.3-0.6 %)."""
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 2e-2 * np.abs(b).max()
        assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(b)


def test_flash_attention_gradients_match_full_softmax():
    """The custom VJP (the backward's two Pallas kernels) must match
    full-softmax autodiff on dq/dk/dv, causal and not."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    rs = np.random.RandomState(5)
    q = jnp.array(rs.randn(2, 100, 64).astype(np.float32))
    k = jnp.array(rs.randn(2, 75, 64).astype(np.float32))
    v = jnp.array(rs.randn(2, 75, 64).astype(np.float32))

    for causal in (False, True):
        def full(qq, kk, vv):
            scale = 1.0 / np.sqrt(qq.shape[-1])
            s = (qq * scale) @ jnp.swapaxes(kk, -1, -2)
            if causal:
                lq, lk = s.shape[-2:]
                mask = jnp.tril(jnp.ones((lq, lk), bool), lk - lq)
                s = jnp.where(mask, s, -1e30)
            return jnp.sum((jax.nn.softmax(s, axis=-1) @ vv) ** 2)

        def flashed(qq, kk, vv):
            return jnp.sum(flash_attention(qq, kk, vv,
                                           causal=causal) ** 2)

        g_ref = jax.grad(full, argnums=(0, 1, 2))(q, k, v)
        g_fla = jax.grad(flashed, argnums=(0, 1, 2))(q, k, v)
        _assert_grads_close(g_fla, g_ref)


def test_flash_attention_trains_transformer():
    """MXNET_ATTENTION_KERNEL=flash on a dropout-free attention block:
    training itself rides the flash kernel and converges like the XLA
    path."""
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo.transformer import MultiHeadAttention

    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(4, 12, 16).astype(np.float32))
    tgt = nd.array(rs.randn(4, 12, 16).astype(np.float32))

    def train(flag):
        mx.random.seed(3)
        np.random.seed(3)
        att = MultiHeadAttention(units=16, num_heads=2)
        att.initialize(mx.init.Xavier())
        tr = gluon.Trainer(att.collect_params(), "adam",
                           {"learning_rate": 1e-2})
        # baseline must explicitly force XLA so a pre-exported
        # env var can't make both runs take the flash path
        env = {"MXNET_ATTENTION_KERNEL": "flash" if flag else "xla"}
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            losses = []
            for _ in range(12):
                with autograd.record():
                    L = nd.mean(nd.square(att(x) - tgt))
                L.backward()
                tr.step(4)
                losses.append(float(L.asnumpy()))
        finally:
            for k, vv in old.items():
                if vv is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = vv
        return losses

    base = train(False)
    flash = train(True)
    assert flash[-1] < flash[0] * 0.8
    np.testing.assert_allclose(flash, base, rtol=2e-2, atol=1e-4)


def test_flash_attention_gradient_through_nd_tape():
    """The registered op's vjp_maker resolves Mosaic-vs-interpret from
    CONCRETE arrays before jax.vjp traces (review regression): gradients
    flow through the mx.nd tape."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd

    rs = np.random.RandomState(6)
    q = nd.array(rs.randn(1, 40, 32).astype(np.float32))
    q.attach_grad()
    with autograd.record():
        out = nd.flash_attention(q, q, q, causal=True)
        L = nd.sum(nd.square(out))
    L.backward()
    g = q.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0
    # parity vs full softmax tape
    q2 = nd.array(q.asnumpy())
    q2.attach_grad()
    with autograd.record():
        scores = nd.batch_dot(q2, q2, transpose_b=True) * \
            (1.0 / np.sqrt(32))
        lq = 40
        mask = np.tril(np.ones((lq, lq), np.float32))
        att = nd.softmax(nd.array(mask[None]) * 0 +
                         scores + nd.array((mask[None] - 1) * 1e9),
                         axis=-1)
        L2 = nd.sum(nd.square(nd.batch_dot(att, q2)))
    L2.backward()
    _assert_grads_close([g], [q2.grad.asnumpy()])


def test_flash_attention_valid_len_matches_masked_softmax():
    """Per-row valid_len == the XLA additive -1e9 key-padding mask, fwd
    and bwd (VERDICT r4 ask: flash must serve padding-masked workloads)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    rs = np.random.RandomState(3)
    q = rs.randn(3, 100, 64).astype(np.float32)
    k = rs.randn(3, 100, 64).astype(np.float32)
    v = rs.randn(3, 100, 64).astype(np.float32)
    vlen = np.array([100, 37, 64], np.float32)

    def ref(qq, kk, vv):
        s = np.einsum("bqd,bkd->bqk", qq, kk) / np.sqrt(64)
        mask = np.arange(100)[None, None, :] < vlen[:, None, None]
        s = np.where(mask, s, -1e9)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum("bqk,bkd->bqd", p, vv)

    out = np.asarray(flash_attention(jnp.array(q), jnp.array(k),
                                     jnp.array(v),
                                     valid_len=jnp.array(vlen)))
    np.testing.assert_allclose(out, ref(q, k, v), atol=2e-5)

    # gradients agree with the masked-softmax formulation
    def loss_flash(qq, kk, vv):
        return jnp.sum(flash_attention(qq, kk, vv,
                                       valid_len=jnp.array(vlen)) ** 2)

    def loss_ref(qq, kk, vv):
        s = jnp.einsum("bqd,bkd->bqk", qq, kk) / jnp.sqrt(64.0)
        mask = jnp.arange(100)[None, None, :] < vlen[:, None, None]
        s = jnp.where(mask, s, -1e9)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bqk,bkd->bqd", p, vv) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(
        jnp.array(q), jnp.array(k), jnp.array(v))
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.array(q), jnp.array(k), jnp.array(v))
    _assert_grads_close(gf, gr)


# heads as blocks of a tokens-major array's lanes: the BERT cell's form (an
# even count of 64-lane heads, two to a 128-lane block, with lengths, one of
# them nought) from separate projections and read in place from one fused
# array, bfloat16, causal pairs, an odd count of 64-lane heads (no pair:
# the entry makes the heads-first form itself, through transposes), heads
# of one and of two lane groups, ragged Lk > Lq, and Lk < Lq whose first
# rows see no key.  ``lanes``: (heads a block holds, operands tokens-major)
# as the gauges read after the call.
@pytest.mark.parametrize(
    "heads,d,lq,lk,causal,lens,dtype,fused,lanes", [
        (12, 64, 128, 128, False, [128, 100], "float32", False, (2, 1)),
        (4, 64, 200, 200, True, None, "float32", False, (2, 1)),
        (3, 64, 128, 128, False, [77, 128], "float32", False, (1, 0)),
        (2, 128, 130, 300, True, None, "float32", False, (1, 1)),
        (2, 256, 256, 256, True, None, "float32", False, (1, 1)),
        (2, 64, 300, 200, True, None, "float32", False, (2, 1)),
        (4, 64, 128, 128, False, [0, 128], "float32", False, (2, 1)),
        (4, 64, 128, 128, False, [128, 77], "bfloat16", False, (2, 1)),
        (4, 64, 128, 128, False, [128, 77], "float32", True, (2, 1)),
        (2, 128, 128, 128, True, None, "float32", True, (1, 1)),
    ], ids=["pairs_of_64_lengths", "pairs_of_64_causal", "odd_heads_of_64",
            "heads_of_128_causal_lk_gt_lq", "heads_of_256_causal",
            "pairs_of_64_causal_lk_lt_lq_dead_rows", "a_row_of_length_0",
            "pairs_of_64_bfloat16", "fused_qkv_read_in_place",
            "fused_qkv_heads_of_128_causal"])
def test_flash_attention_tokens_major_matches_heads_first(
        heads, d, lq, lk, causal, lens, dtype, fused, lanes):
    """Forward and gradients of the tokens-major call, (B, L, H*d) operands
    with the head count given, against the heads-first call on the
    transposed operands: the same kernels with another index map, so the
    same numbers (to the tolerance the gradients are held to against the
    full softmax).  A fused (B, L, 3*H*d) projection passed three times
    with ``first_head=(0, H, 2*H)`` is read where it lies and its gradient
    comes back as the one array."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.observability.registry import registry

    b = 2
    rs = np.random.RandomState(13)
    q, k, v, g = (jnp.asarray(rs.randn(b, n, heads * d).astype(np.float32))
                  .astype(dtype) for n in (lq, lk, lk, lq))
    vl = None if lens is None else jnp.asarray(lens, jnp.float32)

    def heads_first(t):
        return t.reshape(b, -1, heads, d).transpose(0, 2, 1, 3)

    def reference(q, k, v):
        out = flash_attention(heads_first(q), heads_first(k), heads_first(v),
                              causal=causal, valid_len=vl)
        return out.transpose(0, 2, 1, 3).reshape(b, lq, heads * d)

    if fused:
        operands = (jnp.concatenate([q, k, v], axis=-1),)

        def lanes_call(x):
            return flash_attention(
                x, x, x, causal=causal, valid_len=vl, num_heads=heads,
                head_dim=d, first_head=(0, heads, 2 * heads))
        want_out, want_vjp = jax.vjp(
            lambda x: reference(*jnp.split(x, 3, axis=-1)), *operands)
    else:
        operands = (q, k, v)

        def lanes_call(q, k, v):
            return flash_attention(q, k, v, causal=causal, valid_len=vl,
                                   num_heads=heads)
        want_out, want_vjp = jax.vjp(reference, q, k, v)
    want = want_vjp(g)

    got_out, got_vjp = jax.vjp(lanes_call, *operands)
    got = got_vjp(g)
    reg = registry()
    assert (reg.get("kernels.flash_attention.lane_heads").read(),
            reg.get("kernels.flash_attention.tokens_major").read(),
            reg.get("kernels.flash_attention_bwd.lane_heads").read()) == (
                lanes[0], lanes[1], lanes[0])
    assert got_out.shape == (b, lq, heads * d) and got_out.dtype == q.dtype
    assert [x.shape for x in got] == [x.shape for x in operands]
    out_tol = 3e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got_out, np.float32),
                               np.asarray(want_out, np.float32),
                               atol=out_tol, rtol=0)
    _assert_grads_close(got, want)
    if lens is not None and 0 in lens:
        row = lens.index(0)
        assert not np.asarray(got_out)[row].any()   # no valid key: 0 / 1
        assert not np.asarray(got[1])[row].any()    # ... and dk, dv 0
        assert not np.asarray(got[2])[row].any()
    if causal and lk < lq:
        assert not np.asarray(got[0])[:, :lq - lk].any()   # dead rows: dq 0
    # the three kernels once each, under their names, and for lane blocks
    # no transposed copy of an operand beside them
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(lanes_call, *a)[1](g))(
        *operands)
    assert sorted(pallas_call_names(jaxpr.jaxpr)) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
        "flash_attention_fwd"]
    assert ("transpose" in primitives_outside_kernels(jaxpr.jaxpr)) == (
        not lanes[1])


def test_flash_attention_heads_first_call_reads_one_head_a_block():
    """The heads-first entry is the case of one head that is the whole
    array: the gauges read 1 / 0 / 1 after it, and heads that do not fit
    their array are refused."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.observability.registry import registry

    fa = _flash_module()
    fa._build_call.cache_clear()
    fa._build_backward.cache_clear()
    x = jnp.ones((4, 128, 64), jnp.float32)
    jax.grad(lambda a: jnp.sum(flash_attention(a, a, a)))(x)
    reg = registry()
    assert (reg.get("kernels.flash_attention.lane_heads").read(),
            reg.get("kernels.flash_attention.tokens_major").read(),
            reg.get("kernels.flash_attention_bwd.lane_heads").read()) == (
                1, 0, 1)
    with pytest.raises(ValueError, match="do not fit"):
        flash_attention(x, x, x, num_heads=2, head_dim=64)


def _flash_module():
    # the package binds the function under the module's name
    import importlib
    return importlib.import_module("mxnet_tpu.kernels.flash_attention")


def _flash_gauges():
    from mxnet_tpu.observability.registry import registry
    return {n: registry().get(f"kernels.flash_attention.{n}").read()
            for n in ("block_q", "block_k", "kv_resident", "grid_steps",
                      "builds")}


# the tile choice at the shapes the program meets: the benchmark's cell,
# bf16, ragged cross-attention, one query against a cache, and keys too
# long to stay resident (the K-major grid axis)
@pytest.mark.parametrize("lq,lk,d,dtype,causal,resident,block_q", [
    (512, 512, 64, "float32", False, 1, None),
    (256, 256, 64, "bfloat16", False, 1, 256),
    (100, 77, 64, "float32", False, 1, 104),
    (1, 300, 128, "float32", True, 1, 8),
    (256, 4096, 128, "float32", False, 0, 256),
    (300, 200, 64, "float32", True, 1, 304),     # dead rows: Lq > Lk
])
def test_flash_attention_tile_choice_and_skipped_tiles(
        lq, lk, d, dtype, causal, resident, block_q):
    """Every tiling against the full softmax at highest precision, with
    rows whose valid length sits on each side of a key tile's edge (and
    of a K-major block's): tiles beyond the length are never visited, so
    a wrong trip count shows as a wrong row."""
    import jax
    import jax.numpy as jnp
    fa = _flash_module()

    _, lqp, bk, kvb, lkp, _ = fa._tiling(lq, lk, d, jnp.dtype(dtype).itemsize)
    lens = {0, 1, bk - 1, bk, bk + 1, lk}
    if kvb < lkp:
        lens |= {kvb - 1, kvb, kvb + 1}
    lens = np.array(sorted(min(n, lk) for n in lens), np.float32)
    bh = len(lens)
    rs = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rs.randn(bh, n, d).astype(np.float32)).astype(dtype)
               for n in (lq, lk, lk))
    from mxnet_tpu.observability.registry import registry
    builds = registry().counter("kernels.flash_attention.builds").read()
    fa._build_call.cache_clear()
    out = np.asarray(fa.flash_attention(q, k, v, causal=causal,
                                        valid_len=jnp.asarray(lens)),
                     np.float32)

    g = _flash_gauges()
    assert g["builds"] == builds + 1
    assert g["kv_resident"] == resident and g["block_k"] == bk
    assert g["block_q"] == (block_q or fa._MAX_BLOCK_Q)
    assert g["grid_steps"] == bh * (lqp // g["block_q"]) * (lkp // kvb)
    if resident:
        assert kvb == lkp
    else:
        assert kvb == fa._KV_MAJOR and lkp // kvb > 1

    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqd,bkd->bqk", qf, kf) / np.sqrt(d)
        keep = jnp.arange(lk)[None, None, :] < lens[:, None, None]
        live = keep
        if causal:
            live = keep & (jnp.arange(lk)[None, None, :] <=
                           jnp.arange(lq)[None, :, None] + (lk - lq))
        # a row with no live key weights its valid keys evenly (none: 0)
        dead = ~live.any(-1, keepdims=True)
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        p = jnp.where(dead, keep / jnp.maximum(keep.sum(-1, keepdims=True),
                                               1), p)
        ref = np.asarray(jnp.einsum("bqk,bkd->bqd", p, vf))
    if dtype == "float32":
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=0)
    else:
        assert (np.abs(out - ref) <= 1e-2 + 1e-2 * np.abs(ref)).all()
    assert not out[0].any()          # no valid key: the row divides by 1


def _attention_and_grads(q, k, v, g, lens, causal, scale, rounded=False,
                         window=None):
    """(out, dq, dk, dv) of the masked softmax attention with the kernel's
    dead-row rule, written out by hand in float32.  ``rounded``: operands
    reach each product rounded to bfloat16 once, as the backward's kernels
    (and XLA's default precision on the chip) give them to the MXU.
    ``window``: a query weighs its own key and the ``window - 1`` before
    it; a row that then sees none weighs nothing."""
    import jax
    import jax.numpy as jnp
    r = (lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)) if rounded \
        else (lambda x: x)
    # the scale rides q into its products, as a jnp backward's einsums
    # give it
    qs = r(q.astype(jnp.float32) * scale)
    k, v, g = (r(x.astype(jnp.float32)) for x in (k, v, g))
    lq, lk = q.shape[1], k.shape[1]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqd,bkd->bqk", qs, k)
        keep = jnp.arange(lk)[None, None, :] < lens[:, None, None]
        live = keep
        if causal:
            live = keep & (jnp.arange(lk)[None, None, :] <=
                           jnp.arange(lq)[None, :, None] + (lk - lq))
        if window is not None:
            live = live & (jnp.arange(lk)[None, None, :] >
                           jnp.arange(lq)[None, :, None] + (lk - lq) - window)
        # a row with no live key weighs its valid keys evenly (none: 0)
        # and passes no gradient to q and k
        dead = ~live.any(-1, keepdims=True)
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        p = jnp.where(dead, 0.0 if window is not None else
                      keep / jnp.maximum(keep.sum(-1, keepdims=True), 1), p)
        dp = jnp.einsum("bqd,bkd->bqk", g, v)
        ds = jnp.where(dead, 0.0,
                       p * (dp - jnp.sum(p * dp, -1, keepdims=True)))
        return (jnp.einsum("bqk,bkd->bqd", p, v),
                jnp.einsum("bqk,bkd->bqd", r(ds), k) * scale,
                jnp.einsum("bqk,bqd->bkd", r(ds), qs),
                jnp.einsum("bqk,bqd->bkd", r(p), g))


@pytest.mark.parametrize("lq,lk,d,dtype,causal,ragged,unnamed", [
    (600, 600, 16, "float32", True, False, False),
    (600, 600, 16, "float32", True, True, False),
    (520, 520, 16, "float32", False, False, False),
    (130, 700, 16, "float32", False, True, False),
    (200, 640, 16, "float32", True, True, False),
    (640, 200, 16, "float32", True, False, False),
    (640, 200, 16, "float32", True, True, False),
    (600, 600, 16, "float32", True, True, True),
    (512, 512, 64, "float32", False, True, False),
    (512, 512, 64, "bfloat16", False, True, False),
    (2304, 2304, 128, "float32", True, False, False)],
    ids=["causal", "causal_ragged", "full", "cross_ragged", "causal_lk_gt_lq",
         "causal_lk_lt_lq_dead_rows", "causal_lk_lt_lq_ragged",
         "causal_ragged_output_unnamed", "resident_head64_ragged",
         "resident_head64_bfloat16", "causal_k_major"])
def test_flash_backward_kernels_match_full_softmax(
        monkeypatch, lq, lk, d, dtype, causal, ragged, unnamed):
    """The backward's two Pallas kernels give the dq, dk, dv of the plain
    softmax attention: several query tiles and key blocks, tiles above the
    diagonal left out, rows' valid lengths on both sides of a tile's edge
    and nought, rows that see no key (they weigh their valid keys evenly
    and pass no gradient to q and k), Lk on either side of Lq, K and V
    resident at a head of 64 (the BERT cell's form), bfloat16 operands,
    and K-major and Q-major blocks with the diagonal through them (the
    GLM cell's form).  Against the gradients at the kernels' own roundings
    (operands to bfloat16 once, everything else float32) they agree to a
    few flipped roundings; against the exact ones to what one bfloat16
    rounding costs.  ``unnamed``: outside a checkpoint the name on the
    kernel's output (``KEPT_OUTPUT``) is an identity, the output and the
    gradients are bit for bit those of a kernel whose output carries no
    name; and the backward reads neither the output nor q, k, v through
    anything but its own kernels."""
    import jax
    import jax.numpy as jnp
    fa = _flash_module()

    scale = 0.3 if d == 16 else d ** -0.5
    lens = [lk, 0, 1, 511, 513, lk - 1] if ragged else [lk, lk]
    lens = np.array([min(n, lk) for n in lens], np.float32)
    rs = np.random.RandomState(11)
    q, k, v, g = (jnp.asarray(rs.randn(len(lens), n, d).astype(np.float32))
                  .astype(dtype) for n in (lq, lk, lk, lq))

    def flashed(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                  valid_len=jnp.asarray(lens))

    fa._build_backward.cache_clear()
    got_out, vjp = jax.vjp(flashed, q, k, v)
    got = vjp(g)
    names = pallas_call_names(
        jax.make_jaxpr(lambda *a: jax.vjp(flashed, *a)[1](g))(q, k, v).jaxpr)
    assert sorted(n for n in names if "bwd" in n) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq"]
    # nothing of the backward may read as the forward in a trace
    assert sum("flash_attention_fwd" in n for n in names) <= 1

    exact = _attention_and_grads(q, k, v, g, lens, causal, scale)
    same = _attention_and_grads(q, k, v, g, lens, causal, scale,
                                rounded=True)
    out_tol = 3e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got_out, np.float32),
                               np.asarray(exact[0]), atol=out_tol, rtol=0)
    for a, b, c, name in zip(got, same[1:], exact[1:], ("dq", "dk", "dv")):
        a, top = np.asarray(a, np.float32), np.abs(np.asarray(c)).max()
        # bfloat16 gradients carry their own last rounding.  dq's roundings
        # (it is made from sums of the unnormalised p and p dp, rescaled
        # as the running maximum moves) are not the hand-written ones: as
        # a vector it is as close to the exact one as they are
        tight = 1e-3 if dtype == "float32" else 6e-3
        if name == "dq":
            c = np.asarray(c)
            assert np.linalg.norm(a - c) <= (
                1.5 if dtype == "float32" else 2.5) * np.linalg.norm(
                    np.asarray(b) - c)
        else:
            assert np.abs(a - np.asarray(b)).max() <= tight * top, name
        assert np.abs(a - np.asarray(c)).max() <= 2.5e-2 * top, name
    if causal and lk < lq:
        assert not np.asarray(got[0])[0, :lq - lk].any()   # dead rows: dq 0
    if ragged:
        assert not np.asarray(got[1])[1].any()     # no valid key: dk, dv 0
        assert not np.asarray(got[2])[1].any()
    if unnamed:
        names = []
        with monkeypatch.context() as m:
            m.setattr(jax.ad_checkpoint, "checkpoint_name",
                      lambda x, name: names.append(name) or x)
            fa._flash_core_fn.cache_clear()
            try:
                bare_out, vjp = jax.vjp(flashed, q, k, v)
                bare = vjp(g)
            finally:
                fa._flash_core_fn.cache_clear()
        assert names == [fa.KEPT_OUTPUT]
        for a, b in zip((got_out, *got), (bare_out, *bare)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        text = str(jax.make_jaxpr(lambda *a: jax.vjp(flashed, *a)[1](g))(
            q, k, v))
        assert "reduce_precision[" not in text and "scan[" not in text


# the sweep's lower bound: windows that are no multiple of a key tile (256)
# or of a query tile, that end inside the first tile, that cross K-major
# blocks of 2048 keys, with rows' valid lengths on both sides of a tile's
# edge and nought (a padded query a window beyond its row's length sees no
# key: it gives 0 and passes no gradient), with Lk > Lq, and one that
# reaches every key, which is no window: today's kernels under today's names
@pytest.mark.parametrize("lq,lk,d,window,ragged", [
    (600, 600, 16, 100, False),
    (600, 600, 16, 300, True),
    (700, 700, 16, 513, True),
    (2304, 2304, 128, 700, False),
    (200, 640, 16, 256, True),
    (600, 600, 16, 600, False),
    (600, 600, 16, 5000, True)],
    ids=["inside_a_tile", "ragged", "two_tiles_and_a_key_ragged",
         "across_k_major_blocks", "lk_gt_lq_ragged", "every_key",
         "beyond_every_key_ragged"])
def test_flash_window_kernels_match_masked_softmax(lq, lk, d, window,
                                                   ragged):
    """Forward, dq, dk and dv of the kernels built with a window against
    the masked softmax written out by hand (at the kernels' own roundings
    to a few flipped ones, against the exact ones to what one bfloat16
    rounding costs), the three kernels under the window build's names; and
    the tiles the gauges say the forward visits are those its loop bounds
    give."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability.registry import registry
    fa = _flash_module()

    scale = 0.3 if d == 16 else d ** -0.5
    lens = [lk, 0, 1, 255, 257, lk - 1] if ragged else [lk, lk]
    lens = np.array([min(n, lk) for n in lens], np.float32)
    rs = np.random.RandomState(17)
    q, k, v, g = (jnp.asarray(rs.randn(len(lens), n, d).astype(np.float32))
                  for n in (lq, lk, lk, lq))

    def flashed(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, scale=scale,
                                  valid_len=jnp.asarray(lens), window=window)

    fa._build_call.cache_clear()
    fa._build_backward.cache_clear()
    reg = registry()
    for n in ("key_tiles", "key_tiles_causal"):     # a window build's alone
        reg.gauge(f"kernels.flash_attention.{n}").set(-1)
    got_out, vjp = jax.vjp(flashed, q, k, v)
    got = vjp(g)
    names = pallas_call_names(
        jax.make_jaxpr(lambda *a: jax.vjp(flashed, *a)[1](g))(q, k, v).jaxpr)
    tail = "_window" if window < lk else ""
    assert sorted(names) == [f"flash_attention_bwd_dkv{tail}",
                             f"flash_attention_bwd_dq{tail}",
                             f"flash_attention_fwd{tail}"]
    read = {n: reg.get(f"kernels.flash_attention.{n}").read()
            for n in ("window", "kv_group", "key_tiles", "key_tiles_causal",
                      "block_q", "block_k")}
    assert read["window"] == (window if window < lk else 0)
    assert read["kv_group"] == 1
    bq, bk = int(read["block_q"]), int(read["block_k"])
    off = lk - lq
    causal_tiles = sum(-(-min(lk, q0 + bq + off) // bk)
                       for q0 in range(0, lq, bq))
    behind = sum(max(q0 + off - window + 1, 0) // bk
                 for q0 in range(0, lq, bq))
    if window < lk:
        assert read["key_tiles_causal"] == causal_tiles
        assert read["key_tiles"] == causal_tiles - behind
    else:       # no window, no word on the tiles a window would leave
        assert read["key_tiles_causal"] == read["key_tiles"] == -1
    if (lq, window) == (2304, 700):
        assert behind > 0 and bk == 256

    exact = _attention_and_grads(q, k, v, g, lens, True, scale,
                                 window=window)
    same = _attention_and_grads(q, k, v, g, lens, True, scale, rounded=True,
                                window=window)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(exact[0]),
                               atol=3e-5, rtol=0)
    for a, b, c, name in zip(got, same[1:], exact[1:], ("dq", "dk", "dv")):
        a, c = np.asarray(a), np.asarray(c)
        top = np.abs(c).max()
        if name == "dq":
            assert np.linalg.norm(a - c) <= 1.5 * np.linalg.norm(
                np.asarray(b) - c)
        else:
            assert np.abs(a - np.asarray(b)).max() <= 1e-3 * top, name
        assert np.abs(a - c).max() <= 2.5e-2 * top, name
    if ragged:
        assert not np.asarray(got_out)[1].any()    # no valid key at all
        assert not np.asarray(got[1])[1].any()
        assert not np.asarray(got[2])[1].any()
        if window < lk - 1:
            # a query a window beyond a row of length 1 sees no key
            assert not np.asarray(got_out)[2, lq - 1].any()
            assert not np.asarray(got[0])[2, lq - 1].any()


# every kind of tile in one sweep: tiles wholly under the diagonal and inside
# the window (which run the body without a mask), tiles the diagonal or the
# window's edge crosses, and tiles a row's length ends in, which the diagonal
# alone would call interior
@pytest.mark.parametrize("lq,lk,lanes,blocks,hb,group,window,lens", [
    (2560, 2560, 256, 1, 1, 1, 0, [2560, 1100, 0]),
    (700, 1700, 128, 1, 1, 1, 0, [1700, 1300]),
    (2560, 2560, 128, 1, 1, 1, 1100, [2560, 900]),
    (1280, 1280, 128, 7, 1, 7, 0, [1280, 1000]),
    (1536, 1536, 128, 1, 2, 1, 900, [1536, 500])],
    ids=["causal_k_major_length_inside_and_nought", "lk_gt_lq",
         "window_edges_and_padded_rows", "group_of_7",
         "two_heads_of_64_window"])
def test_flash_split_build_is_the_masked_build_bit_for_bit(
        lq, lk, lanes, blocks, hb, group, window, lens):
    """The build that masks only the tiles ``_plain`` does not vouch for
    (at these sizes by the builders' own ``split=True``: the rule of the
    shape wants sweeps of sixteen tiles) gives the output, dq, the rows'
    statistics, dk and dv of the build that masks every tile
    (``split=False``) exactly: the same
    products, roundings and order of accumulation.  The scale is a power
    of two: the CPU backend contracts ``s * scale - m`` into one fused
    multiply-add where no select stands between the two, which rounds
    once where the masked body rounds twice, unless the product is exact
    (Mosaic does no such thing: on the chip the two builds read equal at
    the cells' own scales, PERF.md section 6, PR 39)."""
    import jax.numpy as jnp
    from mxnet_tpu.observability.registry import registry
    fa = _flash_module()

    b, scale = len(lens), 0.125
    rs = np.random.RandomState(39)
    q, g = (jnp.asarray(rs.randn(b, lq, blocks * lanes).astype(np.float32))
            for _ in range(2))
    k, v = (jnp.asarray(rs.randn(b, lk, blocks // group * lanes)
                        .astype(np.float32)) for _ in range(2))
    vl = jnp.asarray(lens, jnp.int32)
    _, lqp, _, kvb, lkp, _ = fa._tiling(lq, lk, lanes, 4)
    _, qb, lqp_b, _, _, _, lkp_b, _ = fa._bwd_tiling(lq, lk, lanes, 4)
    if lanes == 256:                    # K-major and Q-major blocks
        assert lkp // kvb > 1 and lqp_b // qb > 1
    reg = registry()

    def plain_tiles(kind):
        return tuple(reg.get(f"kernels.flash_attention{kind}.{n}").read()
                     for n in ("tiles_plain", "tiles_visited"))

    got = {}
    for split in (False, True):
        args = (b, lq, lk, lanes, True, scale, "float32", True, blocks, hb,
                (0, 0, 0), window, group)
        out = fa._build_call(*args, split=split)(
            vl, *(fa._pad_to(x, n) for x, n in ((q, lqp), (k, lkp),
                                                (v, lkp))))
        fwd_tiles = plain_tiles("")
        dq_call, dkv_call = fa._build_backward(*args, split=split)
        qp, gp, kp, vp = (fa._pad_to(x, n) for x, n in (
            (q, lqp_b), (g, lqp_b), (k, lkp_b), (v, lkp_b)))
        dq, stats = dq_call(vl, qp, gp, kp, vp)
        dk, dv = dkv_call(vl, kp, vp, qp, gp, stats)
        for tiles in (fwd_tiles, plain_tiles("_bwd")):
            # both bodies are in the split build's sweeps, one in the other's
            assert (0 < tiles[0] < tiles[1]) if split \
                else tiles[0] == 0 < tiles[1]
        got[split] = [np.asarray(x) for x in (out, dq, stats, dk, dv)]
    for name, a, c in zip(("out", "dq", "stats", "dk", "dv"), got[True],
                          got[False]):
        assert np.isfinite(a[..., :lq if name in ("out", "dq") else lk, :]
                           if name != "stats" else a[a > -1e29]).all(), name
        assert (a == c).all(), name
    out, dq, _, dk, _ = got[True]
    assert np.abs(out).max() > 0.1 and np.abs(dq).max() > 0.1
    if 0 in lens:                       # no valid key: nothing weighs
        row = lens.index(0)
        assert not out[row].any() and not dq[row].any() \
            and not dk[row].any()
    if window:
        # a padded query a window beyond its row's length sees no key, in a
        # tile that is masked: the dead-row rule gives 0 and no gradient
        row, n = 1, lens[1]
        assert n + window < lq
        assert not out[row, n + window:lq].any()
        assert not dq[row, n + window:lq].any()
        assert np.abs(out[row, :n]).min() > 0


# the gauges' arithmetic at (512, 256) tiles, and the rule of the shape: a
# window block and a full block of the window cell (one row of 16,384), the
# MLA cell, 4096 keys; the hybrid cell (eight tiles a sweep at most), BERT's
# cell (no causal mask, two key tiles a row) and one query against a long
# cache keep the one masked body
@pytest.mark.parametrize("lq,lk,d,causal,window,visited,plain", [
    (16384, 16384, 128, True, 4096, 504, 392),
    (16384, 16384, 128, True, 0, 1056, 992),
    (8192, 8192, 256, True, 0, 272, 240),
    (4096, 4096, 128, True, 0, 72, 56),
    (2048, 2048, 128, True, 0, 20, 0),
    (512, 512, 64, False, 0, 2, 0),
    (1, 8192, 128, True, 0, 32, 0),
    (600, 600, 128, True, 0, 5, 0)],
    ids=["window_cell_window_block", "window_cell_full_block", "mla_cell",
         "sixteen_tiles_a_sweep", "hybrid_cell", "bert_cell", "decode",
         "three_tiles_a_sweep"])
def test_flash_plain_tile_gauges_and_the_rule_of_the_shape(
        lq, lk, d, causal, window, visited, plain):
    """``tiles_visited`` / ``tiles_plain`` of a build: of one head's sweeps,
    all rows full, the key tiles visited and those that run the body
    without a mask; 0 where the shape keeps the one masked body (then the
    build is the kernel it was before there were two).  Forward and
    backward publish the same pair; built, not run."""
    from mxnet_tpu.observability.registry import registry
    fa = _flash_module()
    reg = registry()
    fa._build_call.cache_clear()
    fa._build_backward.cache_clear()
    args = (192 if d == 64 else 1, lq, lk, d, causal, d ** -0.5, "float32",
            True)
    fa._build_call(*args, window=window)
    fa._build_backward(*args, window=window)
    for kind in ("", "_bwd"):
        read = tuple(reg.get(f"kernels.flash_attention{kind}.{n}").read()
                     for n in ("tiles_visited", "tiles_plain"))
        assert read == (visited, plain), kind
    # the tests' reference build has no second body whatever the shape,
    # and the one they force has two (at 2048 keys 12 of the 20 tiles)
    fa._build_call(*args, window=window, split=False)
    assert reg.get("kernels.flash_attention.tiles_plain").read() == 0
    if lq == 2048:
        fa._build_call(*args, split=True)
        assert reg.get("kernels.flash_attention.tiles_plain").read() == 12


# key heads shared by a group of query heads, read in place: groups of 1,
# 2 and 7 query heads of a whole lane group (128 lanes) to a key head, with
# a window and without, with rows' lengths
@pytest.mark.parametrize("heads,kv_heads,d,seq,window,lens", [
    (2, 2, 128, 300, 100, None),
    (4, 2, 128, 300, None, [300, 77]),
    (4, 2, 128, 520, 257, [520, 130]),
    (7, 1, 128, 300, 129, None),
    (7, 1, 128, 300, None, [300, 77])],
    ids=["group_of_1_window", "group_of_2_lengths", "group_of_2_window",
         "group_of_7_window", "group_of_7_lengths"])
def test_flash_grouped_heads_match_masked_softmax(heads, kv_heads, d, seq,
                                                  window, lens):
    """Query head h reads key head h // group: forward and gradients of the
    tokens-major call with ``num_kv_heads`` against the masked softmax over
    (B, key head, group, S, d) written out by hand; dk and dv come back a
    key head wide, the sum over the group's query heads, and no copy of k
    or v a query head wide is made."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.observability.registry import registry

    b, group = 2, heads // kv_heads
    rs = np.random.RandomState(23)
    q, g = (jnp.asarray(rs.randn(b, seq, heads * d).astype(np.float32))
            for _ in range(2))
    k, v = (jnp.asarray(rs.randn(b, seq, kv_heads * d).astype(np.float32))
            for _ in range(2))
    vl = jnp.asarray(lens if lens else [seq] * b, jnp.float32)

    def flashed(q, k, v):
        return flash_attention(q, k, v, causal=True, num_heads=heads,
                               num_kv_heads=kv_heads, head_dim=d,
                               window=window,
                               valid_len=vl if lens else None)

    def by_hand(q, k, v):
        q5 = q.reshape(b, seq, kv_heads, group, d)
        k4, v4 = (t.reshape(b, seq, kv_heads, d) for t in (k, v))
        s = jnp.einsum("bqngd,bknd->bngqk", q5, k4) * d ** -0.5
        at, key = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
        keep = key <= at
        if window is not None:
            keep = keep & (at - key < window)
        keep = keep[None, None, None] & (
            key[None, None, None] < vl[:, None, None, None, None])
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        p = jnp.where(keep.any(-1, keepdims=True), p, 0.0)
        return jnp.einsum("bngqk,bknd->bqngd", p, v4).reshape(
            b, seq, heads * d)

    got_out, vjp = jax.vjp(flashed, q, k, v)
    got = vjp(g)
    with jax.default_matmul_precision("highest"):
        want_out, want_vjp = jax.vjp(by_hand, q, k, v)
        want = want_vjp(g)
    assert registry().get("kernels.flash_attention.kv_group").read() == \
        group
    assert registry().get("kernels.flash_attention_bwd.kv_group").read() \
        == group
    assert [x.shape for x in got] == [q.shape, k.shape, v.shape]
    live = np.asarray(jnp.arange(seq)[None, :] < vl[:, None])
    np.testing.assert_allclose(np.asarray(got_out)[live],
                               np.asarray(want_out)[live], atol=3e-5, rtol=0)
    # a padded query with no window weighs its row's valid keys (the
    # kernel's rule); the comparison is of what a loss would read
    mask = jnp.asarray(live[..., None], jnp.float32)
    got = jax.vjp(flashed, q, k, v)[1](g * mask)
    want = want_vjp(g * mask)
    for a, c, name in zip(got, want, ("dq", "dk", "dv")):
        a, c = np.asarray(a), np.asarray(c)
        assert np.abs(a - c).max() <= 2.5e-2 * np.abs(c).max(), name
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(flashed, *a)[1](g))(q, k, v)
    assert "transpose" not in primitives_outside_kernels(jaxpr.jaxpr)


def test_flash_window_and_groups_are_refused_where_they_mean_nothing():
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    x = jnp.ones((2, 128, 256), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(x, x, x, window=16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(x, x[:, :64], x[:, :64], causal=True, window=16)
    with pytest.raises(ValueError, match="tokens-major"):
        flash_attention(x, x, x, causal=True, num_kv_heads=1)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(x, x[:, :, :128], x[:, :, :128], causal=True,
                        num_heads=4, num_kv_heads=3, head_dim=64)
    # a head of 64 lanes is no lane group: its key head is not read in place
    with pytest.raises(ValueError, match="whole lane groups"):
        flash_attention(x, x[:, :, :128], x[:, :, :128], causal=True,
                        num_heads=4, num_kv_heads=2, head_dim=64)


# the backward's tiles at the shapes the program meets: the BERT cell's,
# bf16, ragged cross-attention, the GLM cell's (K-major and Q-major blocks
# of 1024 rows of 256 float32 lanes) and keys that fit one lane group
@pytest.mark.parametrize(
    "lq,lk,d,dtype,block_q,q_block,block_k,key_block,kv_block", [
        (512, 512, 64, "float32", 512, 512, 256, 512, 512),
        (512, 512, 64, "bfloat16", 512, 512, 256, 512, 512),
        (100, 77, 64, "float32", 128, 128, 128, 128, 128),
        (8192, 8192, 256, "float32", 512, 1024, 256, 512, 1024),
        (300, 4096, 128, "float32", 384, 384, 256, 512, 2048)])
def test_flash_backward_tiles_come_from_the_shape(
        monkeypatch, lq, lk, d, dtype, block_q, q_block, block_k, key_block,
        kv_block):
    """The backward's tiles are a function of (Lq, Lk, d, dtype) and the
    module's one VMEM budget: no argument, no environment variable (every
    ``MXNET_*`` / ``MXTPU_*`` one is taken away and the tiles stay); and
    the ``kernels.flash_attention_bwd.*`` gauges read what a build chose."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability.registry import registry
    fa = _flash_module()
    for name in list(os.environ):
        if name.startswith(("MXNET_", "MXTPU_")):
            monkeypatch.delenv(name)

    import inspect
    assert list(inspect.signature(fa._bwd_tiling).parameters) == [
        "lq", "lk", "d", "itemsize"]
    bq, qb, lqp, bk, kb, kvb, lkp, dp = fa._bwd_tiling(
        lq, lk, d, jnp.dtype(dtype).itemsize)
    assert (bq, qb, bk, kb, kvb) == (block_q, q_block, block_k, key_block,
                                     kv_block)
    assert dp == d and lqp % qb == 0 and qb % bq == 0 and bq % 128 == 0
    assert lkp % kvb == 0 and kvb % kb == 0 and kb % bk == 0
    assert lqp - lq < qb and lkp - lk < kvb

    # the gauges, at a batch small enough to interpret (the tiles do not
    # depend on it); the 8k shape is built and not run
    bh = 2
    reg = registry()
    builds = reg.counter("kernels.flash_attention_bwd.builds").read()
    fa._build_backward.cache_clear()
    if lq * lk > 2 ** 21:
        fa._build_backward(bh, lq, lk, d, True, 0.1, dtype, True)
    else:
        rs = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rs.randn(bh, n, d).astype(np.float32))
                   .astype(dtype) for n in (lq, lk, lk))
        grads = jax.grad(lambda *a: jnp.sum(fa.flash_attention(*a).astype(
            jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
        assert all(np.isfinite(np.asarray(x, np.float32)).all()
                   for x in grads)
    read = {n: reg.get(f"kernels.flash_attention_bwd.{n}").read()
            for n in ("builds", "block_q", "block_k", "grid_steps")}
    assert read["builds"] == builds + 1
    assert (read["block_q"], read["block_k"]) == (bq, bk)
    assert read["grid_steps"] == bh * (
        lqp // bq * (lkp // kvb) + lkp // kb * (lqp // qb))


def test_flash_backward_rows_of_ds_sum_to_nought():
    """Requirement of the backward: it makes its own row maximum,
    denominator and ``delta`` from the scores it computes, so a row of
    ``ds`` sums to nought whatever rounding the scores took, and the
    gradient of a bias added to every key (true value: nought) is what one
    rounding of ``ds`` leaves.  A ``delta`` taken from the full-precision
    output beside probabilities made again from rounded operands does not:
    that bias then gets a gradient that Adam moves (PERF.md section 6,
    PR 30).  At the BERT cell's form, by the rms over heads and lanes of
    ``sum_j dk_j``: the kernels read as the self-consistent gradients at
    their own roundings do, several times under the other."""
    import jax
    import jax.numpy as jnp
    fa = _flash_module()

    bh, seq, d = 12, 512, 64
    rs = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rs.randn(bh, seq, d).astype(np.float32))
                  for _ in range(4))
    lens = np.array([64, 100, 128, 200, 256, 257, 300, 384, 400, 450, 511,
                     512], np.float32)
    scale = d ** -0.5

    def bias_grad(dk):
        return np.asarray(jnp.sum(dk.astype(jnp.float32), axis=1))

    def rms(x):
        return float(np.sqrt(np.mean(x ** 2)))

    got = bias_grad(jax.vjp(
        lambda a, b, c: fa.flash_attention(a, b, c, scale=scale,
                                           valid_len=jnp.asarray(lens)),
        q, k, v)[1](g)[1])
    exact = bias_grad(_attention_and_grads(q, k, v, g, lens, False,
                                           scale)[2])
    same = bias_grad(_attention_and_grads(q, k, v, g, lens, False, scale,
                                          rounded=True)[2])
    # p and dp from rounded operands, delta from the full-precision output
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out = _attention_and_grads(q, k, v, g, lens, False, scale)[0]
        keep = jnp.arange(seq)[None, None, :] < lens[:, None, None]
        s = jnp.einsum("bqd,bkd->bqk", r(q), r(k)) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        ds = p * (jnp.einsum("bqd,bkd->bqk", r(g), r(v))
                  - jnp.sum(g * out, -1, keepdims=True))
        other = bias_grad(jnp.einsum("bqk,bqd->bkd", r(ds), r(q)) * scale)
    assert rms(exact) < 1e-5                   # the true value is nought
    # readings (CPU, interpreted): kernels 3.9e-3, their own arithmetic in
    # jnp 3.9e-3 and 4e-5 apart from them, the full-precision delta 9.2e-3
    assert rms(got - same) < 4e-4
    assert rms(got) < 1.2 * rms(same)
    assert rms(other) > 1.8 * rms(got)
    assert rms(other - same) > 10 * rms(got - same)


def test_flash_attention_padding_mask_transformer_path(monkeypatch):
    """Encoder self-attention with (B,) valid LENGTHS (the GluonNLP
    valid_length idiom): the flash path must match the XLA mask path."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import BERTEncoder

    rs = np.random.RandomState(4)
    enc = BERTEncoder(num_layers=2, units=32, hidden_size=64, num_heads=4,
                      max_length=64, dropout=0.0)
    enc.initialize()
    x = mx.nd.array(rs.randn(2, 24, 32).astype(np.float32))
    lens = mx.nd.array(np.array([24, 10], np.float32))
    base = enc(x, lens).asnumpy()
    # the length form and the equivalent (B,S) prefix mask agree on XLA
    mask = np.zeros((2, 24), np.float32)
    mask[0, :24] = 1
    mask[1, :10] = 1
    base_mask = enc(x, mx.nd.array(mask)).asnumpy()
    np.testing.assert_allclose(base, base_mask, atol=1e-5)
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    flash = enc(x, lens).asnumpy()
    # padded positions' outputs are don't-cares downstream; compare valid
    np.testing.assert_allclose(flash[0], base[0], atol=5e-5)
    np.testing.assert_allclose(flash[1, :10], base[1, :10], atol=5e-5)


def test_flash_env_non_prefix_mask_falls_back_exact(monkeypatch):
    """A 2-D (B,S) mask with HOLES (non-prefix) must NOT be collapsed to a
    length by the flash path — round-4 review regression: the env flag
    being on must not change the numerics of arbitrary-masked attention."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import BERTEncoder

    rs = np.random.RandomState(5)
    enc = BERTEncoder(num_layers=1, units=32, hidden_size=64, num_heads=4,
                      max_length=64, dropout=0.0)
    enc.initialize()
    x = mx.nd.array(rs.randn(1, 8, 32).astype(np.float32))
    holes = mx.nd.array(np.array([[1, 0, 1, 1, 1, 0, 1, 1]], np.float32))
    base = enc(x, holes).asnumpy()
    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    flashed = enc(x, holes).asnumpy()
    np.testing.assert_allclose(flashed, base, atol=1e-6)


def test_attention_kernel_policy(monkeypatch):
    """MXNET_ATTENTION_KERNEL policy: 'flash'/'xla' force the path;
    'auto' (the default) picks flash only on the TPU backend, so on this
    CPU-backed suite auto must resolve to the XLA softmax path."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import MultiHeadAttention

    att = MultiHeadAttention(units=16, num_heads=2)
    att.initialize()
    F = mx.nd

    monkeypatch.delenv("MXNET_ATTENTION_KERNEL", raising=False)
    on_tpu = jax.default_backend() == "tpu"
    assert att._flash_eligible(F, None, None) == on_tpu

    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "flash")
    assert att._flash_eligible(F, None, None)
    # an arbitrary 2-D mask without lengths can never ride the kernel
    assert not att._flash_eligible(F, object(), None)

    monkeypatch.setenv("MXNET_ATTENTION_KERNEL", "xla")
    assert not att._flash_eligible(F, None, None)
