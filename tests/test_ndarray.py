"""NDArray basics: creation, arithmetic, views, mutation, indexing.

Reference analog: tests/python/unittest/test_ndarray.py (SURVEY.md §4.2).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_create_and_asnumpy():
    x = nd.array([[1, 2], [3, 4]])
    assert x.shape == (2, 2)
    assert x.dtype == np.float32
    np.testing.assert_allclose(x.asnumpy(), [[1, 2], [3, 4]])


def test_zeros_ones_full_arange():
    assert nd.zeros((2, 3)).asnumpy().sum() == 0
    assert nd.ones((2, 3)).asnumpy().sum() == 6
    np.testing.assert_allclose(nd.full((2,), 7).asnumpy(), [7, 7])
    np.testing.assert_allclose(nd.arange(0, 5).asnumpy(), np.arange(0, 5.0))


def test_arithmetic():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([4.0, 5.0, 6.0])
    np.testing.assert_allclose((a + b).asnumpy(), [5, 7, 9])
    np.testing.assert_allclose((a - b).asnumpy(), [-3, -3, -3])
    np.testing.assert_allclose((a * b).asnumpy(), [4, 10, 18])
    np.testing.assert_allclose((b / a).asnumpy(), [4, 2.5, 2])
    np.testing.assert_allclose((a ** 2).asnumpy(), [1, 4, 9])
    np.testing.assert_allclose((2 + a).asnumpy(), [3, 4, 5])
    np.testing.assert_allclose((2 - a).asnumpy(), [1, 0, -1])
    np.testing.assert_allclose((1 / a).asnumpy(), [1, 0.5, 1 / 3], rtol=1e-6)
    np.testing.assert_allclose((-a).asnumpy(), [-1, -2, -3])


def test_scalar_dtype_rule():
    # MXNet rule: scalar is cast to array dtype
    a = nd.array([1, 2, 3], dtype="int32")
    r = a + 1.5
    assert r.dtype == np.int32
    np.testing.assert_array_equal(r.asnumpy(), [2, 3, 4])


def test_comparison_returns_input_dtype():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([2.0, 2.0, 2.0])
    r = a > b
    assert r.dtype == np.float32
    np.testing.assert_allclose(r.asnumpy(), [0, 0, 1])


def test_inplace_ops():
    a = nd.array([1.0, 2.0, 3.0])
    a += 1
    np.testing.assert_allclose(a.asnumpy(), [2, 3, 4])
    a *= 2
    np.testing.assert_allclose(a.asnumpy(), [4, 6, 8])


def test_reshape_view_shares_memory():
    a = nd.zeros((2, 3))
    v = a.reshape((3, 2))
    a[0, 0] = 5.0
    assert v.asnumpy()[0, 0] == 5.0
    v[2, 1] = 7.0
    assert a.asnumpy()[1, 2] == 7.0


def test_slice_view_write_through():
    a = nd.zeros((4, 4))
    s = a[1:3]
    s[:] = 1.0
    assert a.asnumpy()[1:3].sum() == 8.0
    assert a.asnumpy()[0].sum() == 0.0


def test_basic_indexing():
    a = nd.array(np.arange(12).reshape(3, 4))
    np.testing.assert_allclose(a[1].asnumpy(), np.arange(4, 8))
    np.testing.assert_allclose(a[1:3, 2].asnumpy(), [6, 10])
    np.testing.assert_allclose(a[:, ::2].asnumpy(),
                               np.arange(12).reshape(3, 4)[:, ::2])


def test_advanced_indexing():
    a = nd.array(np.arange(10.0))
    idx = nd.array([1, 3, 5], dtype="int32")
    np.testing.assert_allclose(a[idx].asnumpy(), [1, 3, 5])


def test_setitem():
    a = nd.zeros((3, 3))
    a[1, 1] = 9.0
    assert a.asnumpy()[1, 1] == 9.0
    a[0] = np.array([1.0, 2.0, 3.0])
    np.testing.assert_allclose(a.asnumpy()[0], [1, 2, 3])


def test_astype_copy_copyto():
    a = nd.array([1.1, 2.9])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.copy()
    c[0] = 100.0
    assert a.asnumpy()[0] != 100.0
    d = nd.zeros((2,))
    a.copyto(d)
    np.testing.assert_allclose(d.asnumpy(), a.asnumpy())


def test_reductions():
    a = nd.array(np.arange(6.0).reshape(2, 3))
    assert float(nd.sum(a).asnumpy()) == 15.0
    np.testing.assert_allclose(nd.sum(a, axis=0).asnumpy(), [3, 5, 7])
    np.testing.assert_allclose(nd.mean(a, axis=1).asnumpy(), [1, 4])
    np.testing.assert_allclose(nd.max(a, axis=1).asnumpy(), [2, 5])
    # exclude semantics
    np.testing.assert_allclose(
        nd.sum(a, axis=0, exclude=True).asnumpy(), [3, 12])


def test_dot():
    a = nd.array(np.random.rand(3, 4).astype(np.float32))
    b = nd.array(np.random.rand(4, 5).astype(np.float32))
    np.testing.assert_allclose(nd.dot(a, b).asnumpy(),
                               a.asnumpy() @ b.asnumpy(), rtol=1e-5)


def test_concat_split_stack():
    a, b = nd.ones((2, 3)), nd.zeros((2, 3))
    c = nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    parts = nd.split(c, num_outputs=2, axis=0)
    assert parts[0].shape == (2, 3)
    np.testing.assert_allclose(parts[1].asnumpy(), 0)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)


def test_transpose_tile_repeat():
    a = nd.array(np.arange(6.0).reshape(2, 3))
    assert nd.transpose(a).shape == (3, 2)
    assert a.T.shape == (3, 2)
    assert nd.tile(a, reps=(2, 2)).shape == (4, 6)
    assert nd.repeat(a, repeats=2, axis=0).shape == (4, 3)


def test_take_embedding_onehot():
    w = nd.array(np.arange(12.0).reshape(4, 3))
    idx = nd.array([0, 3], dtype="int32")
    np.testing.assert_allclose(nd.take(w, idx).asnumpy(),
                               w.asnumpy()[[0, 3]])
    e = nd.Embedding(idx, w, input_dim=4, output_dim=3)
    np.testing.assert_allclose(e.asnumpy(), w.asnumpy()[[0, 3]])
    oh = nd.one_hot(idx, depth=4)
    np.testing.assert_allclose(oh.asnumpy(), np.eye(4)[[0, 3]])


def test_slice_ops():
    a = nd.array(np.arange(24.0).reshape(2, 3, 4))
    s = nd.slice(a, begin=(0, 1), end=(2, 3))
    np.testing.assert_allclose(s.asnumpy(), a.asnumpy()[0:2, 1:3])
    s2 = nd.slice_axis(a, axis=2, begin=1, end=3)
    np.testing.assert_allclose(s2.asnumpy(), a.asnumpy()[:, :, 1:3])


def test_where_clip():
    a = nd.array([-1.0, 0.5, 2.0])
    np.testing.assert_allclose(nd.clip(a, a_min=0.0, a_max=1.0).asnumpy(),
                               [0, 0.5, 1])
    c = nd.array([1.0, 0.0, 1.0])
    np.testing.assert_allclose(
        nd.where(c, a, nd.zeros((3,))).asnumpy(), [-1, 0, 2])


def test_topk_sort():
    a = nd.array([[3.0, 1.0, 2.0]])
    idx = nd.topk(a, k=2)
    np.testing.assert_allclose(idx.asnumpy(), [[0, 2]])
    both = nd.topk(a, k=2, ret_typ="both")
    np.testing.assert_allclose(both[0].asnumpy(), [[3, 2]])
    np.testing.assert_allclose(nd.sort(a).asnumpy(), [[1, 2, 3]])
    np.testing.assert_allclose(nd.argsort(a).asnumpy(), [[1, 2, 0]])


def test_random_ops():
    mx.random.seed(42)
    u = nd.random.uniform(0, 1, shape=(100,))
    assert 0 <= float(u.min().asnumpy()) and float(u.max().asnumpy()) <= 1
    n = nd.random.normal(0, 1, shape=(1000,))
    assert abs(float(n.mean().asnumpy())) < 0.2
    r = nd.random.randint(0, 10, shape=(50,))
    assert r.dtype == np.int32
    assert (r.asnumpy() >= 0).all() and (r.asnumpy() < 10).all()


def test_save_load(tmp_path):
    a = nd.array([1.0, 2.0])
    b = nd.array([[3.0]])
    f = str(tmp_path / "arrs")
    nd.save(f, {"a": a, "b": b})
    loaded = nd.load(f)
    np.testing.assert_allclose(loaded["a"].asnumpy(), a.asnumpy())
    nd.save(f, [a, b])
    lst = nd.load(f)
    np.testing.assert_allclose(lst[1].asnumpy(), b.asnumpy())


def test_context_placement():
    x = nd.ones((2,), ctx=mx.cpu(0))
    assert x.context == mx.cpu(0)
    y = x.as_in_context(mx.cpu(1))
    assert y.context == mx.cpu(1)
    np.testing.assert_allclose(y.asnumpy(), x.asnumpy())


def test_default_context_is_the_host():
    """The reference's default: work reaches an accelerator only because
    the caller names it."""
    assert mx.current_context() == mx.cpu(0)
    assert nd.ones((2,)).context == mx.cpu(0)
    with mx.cpu(1):
        assert mx.current_context() == mx.cpu(1)
    assert mx.current_context() == mx.cpu(0)


@pytest.mark.parametrize("make", [mx.tpu, mx.gpu])
def test_accelerator_context_raises_without_the_device(make):
    """No fall-through to a host core: on a CPU-only process mx.tpu(0) /
    mx.gpu(0) fail where they are resolved, as the reference's gpu(0) does
    on a host without one."""
    assert mx.context.num_tpus() == 0 and mx.context.num_gpus() == 0
    with pytest.raises(mx.MXNetError, match="0 such device"):
        make(0).device
    with pytest.raises(mx.MXNetError):
        nd.ones((2,), ctx=make(0))


@pytest.mark.parametrize("kind", ["tpu", "gpu"])
def test_accelerator_context_index_is_exact(monkeypatch, kind):
    """mx.tpu(n) is device n or an error, never n modulo the count.
    Virtual CPU devices cannot stand in for TPUs, so the resolver runs
    against a stubbed device list."""
    from mxnet_tpu import context
    chips = ["chip0", "chip1"]
    monkeypatch.setattr(
        context, "_platform_devices",
        lambda kinds: chips if "tpu" in kinds else [])
    assert context._resolve_device(kind, 0) == "chip0"
    assert context._resolve_device(kind, 1) == "chip1"
    for bad in (2, 3, -1):
        with pytest.raises(mx.MXNetError, match="2 such device"):
            context._resolve_device(kind, bad)


def test_waitall_and_naive_engine():
    x = nd.ones((8, 8))
    y = nd.dot(x, x)
    y.wait_to_read()
    mx.waitall()
    assert mx.engine.engine().num_ops_dispatched > 0


def test_norm_argmax():
    a = nd.array([[1.0, -2.0], [3.0, 4.0]])
    np.testing.assert_allclose(float(nd.norm(a).asnumpy()),
                               np.sqrt(1 + 4 + 9 + 16), rtol=1e-6)
    am = nd.argmax(a, axis=1)
    assert am.dtype == np.float32
    np.testing.assert_allclose(am.asnumpy(), [0, 1])


def test_broadcast_ops():
    a = nd.ones((2, 1, 3))
    b = nd.broadcast_to(a, shape=(2, 4, 3))
    assert b.shape == (2, 4, 3)
    np.testing.assert_allclose(
        nd.broadcast_add(nd.ones((2, 1)), nd.ones((1, 3))).asnumpy(),
        np.full((2, 3), 2.0))


def test_logical_moments_reshape_like_linspace():
    """Round-3 API fill-ins (reference: elemwise logical ops, moments,
    reshape_like, linspace ctor)."""
    a = nd.array(np.array([[1., 0.], [2., 3.]], np.float32))
    b = nd.array(np.array([[0., 0.], [1., 5.]], np.float32))
    assert np.array_equal(nd.logical_and(a, b).asnumpy(),
                          [[0, 0], [1, 1]])
    assert np.array_equal(nd.logical_or(a, b).asnumpy(),
                          [[1, 0], [1, 1]])
    assert np.array_equal(nd.logical_xor(a, b).asnumpy(),
                          [[1, 0], [0, 0]])
    x = nd.array(np.random.randn(3, 4).astype(np.float32))
    m, v = nd.moments(x, axes=(0, 1))
    assert abs(float(m.asnumpy()) - x.asnumpy().mean()) < 1e-6
    assert abs(float(v.asnumpy()) - x.asnumpy().var()) < 1e-6
    r = nd.reshape_like(nd.array(np.arange(6, dtype=np.float32)),
                        nd.array(np.zeros((2, 3), np.float32)))
    assert r.shape == (2, 3)
    assert np.allclose(nd.linspace(0, 1, 5).asnumpy(),
                       np.linspace(0, 1, 5))


def test_boolean_mask_indexing():
    """reference advanced indexing: x[bool_array] selects rows (eager by
    nature — data-dependent shape)."""
    x = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    m = np.array([True, False, True])
    np.testing.assert_allclose(x[m].asnumpy(), x.asnumpy()[m])
    # a float 1/0 array is INTEGER indices, not a mask (reference
    # semantics: only bool dtype masks)
    np.testing.assert_allclose(
        x[np.array([1.0, 0.0])].asnumpy(), x.asnumpy()[[1, 0]])
    y = nd.array(np.zeros((3, 4), np.float32))
    y[m] = 5.0
    want = np.zeros((3, 4), np.float32); want[m] = 5.0
    np.testing.assert_allclose(y.asnumpy(), want)


def test_boolean_mask_indexing_validation_and_lists():
    import pytest as _pt
    x = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    with _pt.raises(IndexError):
        x[np.array([True, False])]             # wrong length
    with _pt.raises(IndexError):
        x[np.array([True] * 5)]
    y = nd.array(np.zeros((3, 4), np.float32))
    with _pt.raises(IndexError):
        y[np.array([True, False])] = 1.0
    # plain bool list is a mask (numpy/reference semantics)
    np.testing.assert_allclose(x[[True, False, True]].asnumpy(),
                               x.asnumpy()[[True, False, True]])


def test_positional_op_parameters():
    """Reference generated-wrapper convention: trailing non-tensor
    positionals are op parameters in declaration order."""
    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    idx = nd.array([0, 2], dtype="int32")
    assert nd.one_hot(idx, 4).shape == (2, 4)
    assert nd.reshape(x, (3, 2)).shape == (3, 2)
    assert nd.expand_dims(x, 0).shape == (1, 2, 3)
    assert nd.transpose(x, (1, 0)).shape == (3, 2)
    np.testing.assert_allclose(nd.sum(x, 1).asnumpy(), x.asnumpy().sum(1))
    import pytest as _pt
    with _pt.raises(TypeError):
        nd.sum(x, 1, axis=0)          # double assignment
    # tensors (incl. plain lists) still route as inputs
    np.testing.assert_allclose(
        nd.broadcast_add(x, [[1.0, 1.0, 1.0]] * 2).asnumpy(),
        x.asnumpy() + 1.0)


def test_positional_op_parameters_symbol_side():
    from mxnet_tpu import sym
    import pytest as _pt
    d = sym.var("d")
    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    r = sym.sum(d, 1).eval_dict({"d": x})
    np.testing.assert_allclose(r.asnumpy(), x.asnumpy().sum(1))
    r = sym.reshape(sym.transpose(d, (1, 0)), (-1,)).eval_dict({"d": x})
    np.testing.assert_allclose(r.asnumpy(),
                               x.asnumpy().T.reshape(-1))
    with _pt.raises(TypeError):
        sym.sum(d, 1, axis=0)


def test_positional_param_order_matches_reference_decl():
    """Makers whose kwarg order diverged from the reference declaration
    order were re-aligned (review finding): norm(ord, axis, out_dtype,
    keepdims), clip(a_min, a_max), creation ops (shape, ctx, dtype)."""
    x = nd.array(np.array([[3.0, 4.0], [6.0, 8.0]], np.float32))
    # norm(x, ord, axis) positionally
    np.testing.assert_allclose(nd.norm(x, 2, 1).asnumpy(), [5.0, 10.0],
                               rtol=1e-6)
    np.testing.assert_allclose(nd.clip(x, 4.0, 7.0).asnumpy(),
                               np.clip(x.asnumpy(), 4, 7))
    from mxnet_tpu.ndarray.register import invoke_by_name
    z = invoke_by_name("_zeros", [], {"shape": (2,), "ctx": "cpu(0)",
                                      "dtype": "int32"})
    assert z.dtype == np.int32


def test_fluent_methods():
    """reference: the generated NDArray method surface — x.op(args) ==
    nd.op(x, args)."""
    x = nd.array(np.array([[3.0, 1.0, 2.0], [6.0, 5.0, 4.0]], np.float32))
    np.testing.assert_allclose(x.prod(1).asnumpy(), [6.0, 120.0])
    np.testing.assert_allclose(x.abs().asnumpy(), np.abs(x.asnumpy()))
    assert x.swapaxes(0, 1).shape == (3, 2)
    np.testing.assert_allclose(x.sort(1).asnumpy(),
                               np.sort(x.asnumpy(), 1))
    np.testing.assert_allclose(x.argsort(1).asnumpy(),
                               np.argsort(x.asnumpy(), 1))
    np.testing.assert_allclose(x.tanh().asnumpy(),
                               np.tanh(x.asnumpy()), rtol=1e-6)
    np.testing.assert_allclose(x.norm(2, 1).asnumpy(),
                               np.linalg.norm(x.asnumpy(), 2, 1),
                               rtol=1e-6)
    np.testing.assert_allclose(x.clip(2.0, 5.0).asnumpy(),
                               np.clip(x.asnumpy(), 2, 5))
    idx = nd.array([1, 0], dtype="int32")
    np.testing.assert_allclose(x.take(idx).asnumpy(),
                               x.asnumpy()[[1, 0]])
    np.testing.assert_allclose(x.pick(idx, axis=1).asnumpy(),
                               x.asnumpy()[np.arange(2), [1, 0]])
    np.testing.assert_allclose(x.zeros_like().asnumpy(), 0.0)
    np.testing.assert_allclose(x.ones_like().asnumpy(), 1.0)
    parts = x.split(num_outputs=3, axis=1)
    assert len(parts) == 3 and parts[0].shape == (2, 1)


def test_fluent_methods_symbol_lockstep():
    """The same fluent surface attaches to Symbol (hybridize safety)."""
    from mxnet_tpu import sym, gluon
    x = nd.array(np.array([[3.0, 1.0], [2.0, 4.0]], np.float32))
    d = sym.var("d")
    r = d.abs().sum(1).eval_dict({"d": x})
    np.testing.assert_allclose(r.asnumpy(), np.abs(x.asnumpy()).sum(1))

    class Net(gluon.HybridBlock):
        def hybrid_forward(self, F, v):
            return v.tanh().norm(2, 1)
    n = Net(); n.initialize(); n.hybridize()
    np.testing.assert_allclose(
        n(x).asnumpy(),
        np.linalg.norm(np.tanh(x.asnumpy()), 2, 1), rtol=1e-5)
    # out= flows through the frontends on the nd side
    y = nd.zeros((2, 2))
    x.zeros_like(out=y)
    assert float(y.asnumpy().sum()) == 0.0
