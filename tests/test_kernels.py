"""In-tree Pallas kernel tests (multi-tensor optimizer apply).

Reference parity: src/operator/optimizer_op.cc multi_sgd_update family
(SURVEY.md §2.2 optimizer_op row; §7 M9 native hardening).  On the CPU
test mesh the kernels run under the Pallas interpreter — the same code
Mosaic compiles on TPU.  The fixture below opts THIS module into real
interpret mode (production off-TPU dispatch uses the kernels' jnp duals;
these tests exist to execute the kernel bodies themselves).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd


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


def test_flash_attention_gradients_match_full_softmax():
    """The custom VJP (chunked-formulation backward) must match
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
        for a, b in zip(g_ref, g_fla):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)


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
    np.testing.assert_allclose(g, q2.grad.asnumpy(), rtol=1e-3,
                               atol=1e-4)


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
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


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


@pytest.mark.parametrize("lq,lk,causal,ragged,unnamed", [
    (600, 600, True, False, False), (600, 600, True, True, False),
    (520, 520, False, False, False), (130, 700, False, True, False),
    (200, 640, True, True, False), (640, 200, True, False, False),
    (640, 200, True, True, False), (600, 600, True, True, True)],
    ids=["causal", "causal_ragged", "full", "cross_ragged", "causal_lk_gt_lq",
         "causal_lk_lt_lq_dead_rows", "causal_lk_lt_lq_ragged",
         "causal_ragged_output_unnamed"])
def test_flash_blocked_backward_matches_full_softmax(
        monkeypatch, lq, lk, causal, ragged, unnamed):
    """The blocked backward (taken where the scanned one would stack too
    much; here forced) gives the dq, dk, dv of the plain softmax
    attention: several query and key blocks, pairs above the diagonal
    left out, rows' valid lengths on both sides of a block's edge and
    nought, rows that see no key (they weigh their valid keys evenly and
    pass no gradient to q and k), Lk on either side of Lq.  ``unnamed``:
    outside a checkpoint the name on the kernel's output (``KEPT_OUTPUT``)
    is an identity, the output and the gradients are bit for bit those of
    a kernel whose output carries no name; and q, k and v reach this
    backward through a rounding to their own precision."""
    import jax
    import jax.numpy as jnp
    fa = _flash_module()
    monkeypatch.setattr(fa, "_BWD_CARRY_BUDGET", 0)
    calls, blocked = [], fa._blocked_backward
    monkeypatch.setattr(fa, "_blocked_backward",
                        lambda *a: calls.append(1) or blocked(*a))

    d, scale = 16, 0.3
    lens = [lk, 0, 1, 511, 513, lk - 1] if ragged else [lk, lk]
    lens = np.array([min(n, lk) for n in lens], np.float32)
    rs = np.random.RandomState(11)
    q, k, v, g = (jnp.asarray(rs.randn(len(lens), n, d).astype(np.float32))
                  for n in (lq, lk, lk, lq))

    def full(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        keep = jnp.arange(lk)[None, None, :] < lens[:, None, None]
        live = keep
        if causal:
            live = keep & (jnp.arange(lk)[None, None, :] <=
                           jnp.arange(lq)[None, :, None] + (lk - lq))
        dead = ~live.any(-1, keepdims=True)
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        p = jnp.where(dead, keep / jnp.maximum(keep.sum(-1, keepdims=True),
                                               1), p)
        return jnp.einsum("bqk,bkd->bqd", p, v)

    def flashed(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                  valid_len=jnp.asarray(lens))

    with jax.default_matmul_precision("highest"):
        want_out, vjp = jax.vjp(full, q, k, v)
        want = vjp(g)
        got_out, vjp = jax.vjp(flashed, q, k, v)
        got = vjp(g)
    assert calls
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                               atol=3e-5, rtol=0)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=0, err_msg=name)
    if causal and lk < lq:
        assert not np.asarray(got[0])[0, :lq - lk].any()   # dead rows: dq 0
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
        # q, k and v reach the blocked backward through a rounding to
        # their own precision (an identity that keeps XLA from narrowing
        # what makes them again in a rematerialised block)
        text = str(jax.make_jaxpr(lambda *a: jax.vjp(flashed, *a)[1](g))(
            q, k, v))
        assert text.count("reduce_precision[") == 3


def test_flash_backward_keeps_its_chunk(monkeypatch):
    """The scanned backward has a chunk of its own (128): its HLO does not
    move with the forward's tiles, and it still sweeps ceil(Lk / 128)
    chunks."""
    import jax
    import jax.numpy as jnp
    fa = _flash_module()

    assert fa._BWD_CHUNK == 128
    arg = jax.ShapeDtypeStruct((4, 512, 64), jnp.float32)
    vl = jax.ShapeDtypeStruct((4,), jnp.float32)

    def bwd(q, k, v, vl, g):
        _, vjp = jax.vjp(lambda a, b, c: fa._chunked_reference(
            a, b, c, vl, False, 0.125), q, k, v)
        return vjp(g)

    def text():
        return jax.jit(bwd).lower(arg, arg, arg, vl, arg).as_text()

    was = text()
    assert "4x4x128x64" in was       # K in four chunks of 128 keys
    monkeypatch.setattr(fa, "_MAX_BLOCK_K", 128)
    monkeypatch.setattr(fa, "_MAX_BLOCK_Q", 128)
    assert text() == was


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
