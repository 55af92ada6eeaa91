"""TPU-only Pallas kernel tests: run the in-tree Mosaic kernels on a real
chip and check them against pure references (SURVEY.md §7 M9 — the ◆
RTC/kernels mandate).

Skipped without a chip (tests/conftest.py forces the CPU mesh).  Run on a
machine with a TPU, in ONE process (a chip belongs to one process at a
time):  MXNET_TEST_ON_TPU=1 python -m pytest tests/test_kernels_tpu.py
That Mosaic ACCEPTS the kernels is shown without a chip by
tests/test_chip_compile.py; that they run right on one, here and by
chip_smoke.py.
"""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def tpu():
    """Skip from inside a fixture, never while the module is imported:
    every xdist worker must collect the same tests."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU (Mosaic)")
    import mxnet_tpu as mx
    return mx.tpu(0)


def _mk(shapes, seed=0):
    rng = np.random.default_rng(seed)
    ws = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    gs = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    return ws, gs


SHAPES = [(64, 128), (3,), (7, 7, 3, 8), (1000,)]


def test_multi_sgd_mosaic_compiles_and_matches_reference(tpu):
    import jax.numpy as jnp
    from mxnet_tpu.kernels.multi_sgd import fused_multi_sgd

    ws, gs = _mk(SHAPES)
    lrs = [0.1, 0.05, 0.2, 0.01]
    wds = [1e-4, 0.0, 1e-3, 0.0]
    out = fused_multi_sgd([jnp.asarray(w) for w in ws],
                          [jnp.asarray(g) for g in gs], lrs, wds,
                          rescale_grad=0.5)
    for w, g, lr, wd, o in zip(ws, gs, lrs, wds, out):
        ref = w - lr * (0.5 * g + wd * w)
        np.testing.assert_allclose(np.asarray(o), ref, rtol=1e-6,
                                   atol=1e-6)


def test_multi_sgd_mom_mosaic_matches_xla_update(tpu):
    import jax.numpy as jnp
    from mxnet_tpu.kernels.multi_sgd import fused_multi_sgd_mom

    ws, gs = _mk(SHAPES, seed=1)
    ms = [np.zeros_like(w) for w in ws]
    lrs = [0.1] * len(ws)
    wds = [1e-4] * len(ws)
    wj = [jnp.asarray(w) for w in ws]
    mj = [jnp.asarray(m) for m in ms]
    for _ in range(3):
        wj, mj = fused_multi_sgd_mom(wj, [jnp.asarray(g) for g in gs],
                                     mj, lrs, wds, momentum=0.9,
                                     rescale_grad=1.0)
    # pure-numpy reference of the same recurrence
    wn = [w.copy() for w in ws]
    mn = [np.zeros_like(w) for w in ws]
    for _ in range(3):
        for k in range(len(wn)):
            mn[k] = 0.9 * mn[k] - lrs[k] * (gs[k] + wds[k] * wn[k])
            wn[k] = wn[k] + mn[k]
    for o, r in zip(wj, wn):
        np.testing.assert_allclose(np.asarray(o), r, rtol=1e-5,
                                   atol=1e-5)


def test_trainer_update_multi_runs_kernel_on_tpu(tpu):
    """The imperative Trainer's fused group apply goes through the
    Pallas kernel (optimizer.py update_multi) — drive it on-device.
    Params and data are placed on mx.tpu(0): the kernel selects Mosaic
    from the DATA's device, so host-resident params would take the jnp
    twin and prove nothing."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    ctx = tpu
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(32, activation="relu"))
        net.add(gluon.nn.Dense(8))
    net.initialize(ctx=ctx)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.nd.array(np.random.randn(16, 20).astype(np.float32), ctx=ctx)
    y = mx.nd.array(np.random.randint(0, 8, 16), ctx=ctx)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    l0 = None
    # 3 iterations: every extra iteration is pure repeat (compiles are
    # cached after step 1)
    for _ in range(3):
        with autograd.record():
            L = mx.nd.mean(loss_fn(net(x), y))
        L.backward()
        tr.step(16)
        if l0 is None:
            l0 = float(L.asnumpy())
    assert float(L.asnumpy()) < l0


def test_flash_attention_mosaic_compiles_and_matches(tpu):
    """Mosaic-compile the flash-attention kernel on the chip; outputs
    must match the full-softmax XLA reference computed on-device."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    rs = np.random.default_rng(0)
    q = jnp.asarray(rs.standard_normal((2, 256, 128), np.float32))
    k = jnp.asarray(rs.standard_normal((2, 256, 128), np.float32))
    v = jnp.asarray(rs.standard_normal((2, 256, 128), np.float32))
    out = flash_attention(q, k, v, causal=True)   # Mosaic path on TPU
    scale = 1.0 / np.sqrt(128)
    # the chip's default matmul precision rounds float32 to bf16
    with jax.default_matmul_precision("highest"):
        s = (q * scale) @ jnp.swapaxes(k, -1, -2)
        mask = jnp.tril(jnp.ones((256, 256), bool))
        s = jnp.where(mask, s, -1e30)
        ref = jax.nn.softmax(s, axis=-1) @ v
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


@pytest.mark.parametrize("shape", [(96, 256, 64), (192, 512, 64)])
def test_flash_attention_valid_len_matches_on_chip(tpu, shape):
    """BERT-base's heads at chip_smoke's shape and at the benchmark cell's
    (batch 16 x seq 512, its lengths): float32 to 3e-5 of the full softmax
    at highest precision."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    from chip_smoke import flash_lengths    # the cell's own lengths at 512

    bh, seq, d = shape
    rs = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rs.standard_normal(shape, np.float32))
               for _ in range(3))
    vl = jnp.asarray(flash_lengths(rs, bh, seq))
    out = flash_attention(q, k, v, valid_len=vl)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
        keep = jnp.arange(seq)[None, None, :] < vl[:, None, None]
        ref = jnp.einsum("bqk,bkd->bqd",
                         jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape,causal", [((192, 512, 64), False),
                                          ((8, 2048, 256), True)],
                         ids=["bert_cell_lengths", "causal_k_major"])
def test_flash_attention_backward_kernels_match_on_chip(tpu, shape, causal,
                                                        dtype):
    """The backward's two Mosaic kernels against the full softmax's
    gradients at highest precision: the BERT cell's heads with its lengths
    (K and V resident, tiles beyond a length skipped) and causal rows long
    enough for K-major and Q-major blocks with the diagonal through them,
    float32 and bfloat16 operands.  The kernels multiply at the chip's
    default precision (operands rounded to bfloat16 once), so each gradient
    is held to 2 % of the reference's largest entry and 1 % as a vector;
    the gradient of a bias added to every key stays what one rounding of
    ``ds`` leaves (under 1 % of dk's own norm)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    from chip_smoke import flash_lengths

    bh, seq, d = shape
    rs = np.random.default_rng(2)
    q, k, v, g = (jnp.asarray(rs.standard_normal(shape, np.float32))
                  .astype(dtype) for _ in range(4))
    vl = (jnp.full((bh,), float(seq), jnp.float32) if causal
          else jnp.asarray(flash_lengths(rs, bh, seq)))

    def full(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
        keep = jnp.arange(seq)[None, None, :] < vl[:, None, None]
        if causal:
            keep = keep & (jnp.arange(seq)[None, None, :]
                           <= jnp.arange(seq)[None, :, None])
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v, g: jax.vjp(full, q, k, v)[1](
            g.astype(jnp.float32)))(q, k, v, g)
    got = jax.jit(lambda q, k, v, g: jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, causal=causal,
                                        valid_len=vl), q, k, v)[1](g))(
        q, k, v, g)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        assert np.abs(a - b).max() <= 2e-2 * np.abs(b).max(), name
        assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(b), name
    dk = np.asarray(got[1], np.float32)
    bias = dk.sum(axis=1)
    assert np.sqrt((bias ** 2).mean()) <= 1e-2 * np.sqrt(
        (dk ** 2).sum(axis=1).mean())



# the three cells' attention as their models hand it over: (B, L, H * d)
# operands, a head a block of the lanes (two of BERT's 64-lane heads to a
# block of 128), against the heads-first call on the transposed operands
@pytest.mark.parametrize("b,seq,heads,d,dtype,causal", [
    (16, 512, 12, 64, "float32", False),
    (16, 512, 12, 64, "bfloat16", False),
    (1, 8192, 20, 256, "float32", True),
    (1, 2048, 30, 128, "float32", True),
], ids=["bert_cell", "bert_cell_bfloat16", "mla_cell_8k", "hybrid_cell_2k"])
def test_flash_attention_tokens_major_matches_heads_first_on_chip(
        tpu, b, seq, heads, d, dtype, causal):
    """Forward and gradients of the tokens-major call equal the heads-first
    call's on the chip (the same kernels with another index map: float32
    forward to 3e-5, gradients to the tolerance they are held to against
    the full softmax), for separate q, k, v and for one fused
    (B, L, 3 * H * d) array read in place; and the gauges say which form
    engaged."""
    from chip_smoke import tokens_major_against_heads_first

    read = tokens_major_against_heads_first(
        np.random.default_rng(4), b, seq, heads, d, causal, dtype)
    lane_heads = max(128 // d, 1)
    gauges = read["gauges"]
    assert (gauges["lane_heads"], gauges["tokens_major"],
            gauges["bwd_lane_heads"]) == (lane_heads, 1, lane_heads)
    assert "tpu_custom_call" in read["text"]
    assert read["out_err"] <= (3e-5 if dtype == "float32" else 2e-2)
    assert read["grad_max_rel"] <= 2e-2
    assert read["grad_vector_rel"] <= 1e-2
