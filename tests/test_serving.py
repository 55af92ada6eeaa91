"""Serving subsystem: bucketed batch assembly, backpressure + deadline
rejection, concurrent-client correctness (bitwise vs direct block(x)),
graceful drain (stop() and SIGTERM), metrics, and flight-recorder
request records.

Model sizes are deliberately tiny (seconds of compile, not minutes);
every server is stopped in a finally block so a failing assertion never
leaks threads into the rest of the suite.
"""
import os
import signal
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.observability.flight import FlightRecorder
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.serving import (Bucketer, DeadlineExceeded, ModelServer,
                               NoBucketError, ServerClosed,
                               ServerOverloaded)


def _mlp(in_units=16, out=6):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(12, activation="relu", in_units=in_units),
                gluon.nn.Dense(out, in_units=12))
    net.initialize()
    net.hybridize()
    return net


class _Elemwise(gluon.HybridBlock):
    """Row-independent elementwise model: batched rows are bitwise
    identical to batch-1 rows regardless of batch composition, so the
    CONCURRENT bitwise test has no cross-row reduction caveats."""

    def hybrid_forward(self, F, x):
        return F.tanh(x * 2.0) + 0.5


# -- buckets ----------------------------------------------------------------

def test_batch_buckets_default_powers_of_two():
    b = Bucketer(max_batch=8)
    assert b.batch_buckets == (1, 2, 4, 8)
    assert b.batch_bucket(1) == 1
    assert b.batch_bucket(3) == 4
    assert b.batch_bucket(8) == 8
    b12 = Bucketer(max_batch=12)
    assert b12.batch_buckets == (1, 2, 4, 8, 12)


def test_length_bucket_selection_and_key():
    b = Bucketer(max_batch=4, length_buckets=(32, 64), pad_axis=0)
    key = b.sample_key([np.zeros((20,), np.int32),
                        np.zeros((20,), np.int32)])
    assert key == (((32,), "int32"), ((32,), "int32"))
    # a fixed-shape side input (no length axis match) passes through
    key2 = b.sample_key([np.zeros((40,), np.int32),
                         np.zeros((3,), np.float32)])
    assert key2 == (((64,), "int32"), ((3,), "float32"))
    with pytest.raises(NoBucketError):
        b.sample_key([np.zeros((65,), np.int32)])


def test_assembly_pads_and_counts_efficiency():
    b = Bucketer(max_batch=4, length_buckets=(32,), pad_axis=0)

    class R:
        def __init__(self, n):
            self.inputs = (np.arange(n, dtype=np.float32),)
            self.key = b.sample_key(self.inputs)

    reqs = [R(10), R(20), R(5)]
    arrays, bsz, real, slots_padded, tokens_padded = b.assemble(reqs)
    assert bsz == 4 and arrays[0].shape == (4, 32)
    assert real == 35
    assert slots_padded == 1                     # batch-bucket waste
    assert tokens_padded == 3 * 32 - 35          # length-bucket waste
    np.testing.assert_array_equal(arrays[0][1, :20], np.arange(20))
    assert arrays[0][1, 20:].sum() == 0          # zero padding
    assert arrays[0][3].sum() == 0               # empty batch slot


# -- the direct cached-graph entry ------------------------------------------

def test_cached_graph_matches_hybridized_call_bitwise():
    net = _mlp()
    x = mx.nd.array(np.random.default_rng(0).standard_normal(
        (4, 16)).astype(np.float32))
    g = net.cached_graph(x)
    ref = net(x)                 # same signature -> same cache entry
    np.testing.assert_array_equal(g(x).asnumpy(), ref.asnumpy())


def test_cached_graph_skips_autograd_bookkeeping():
    from mxnet_tpu import autograd
    net = _mlp()
    x = mx.nd.array(np.ones((2, 16), np.float32))
    g = net.cached_graph(x)
    with autograd.record():
        out = g(x)
    assert out._ag is None       # no tape node: inference-only entry
    raw = g.raw(np.ones((2, 16), np.float32))
    assert len(raw) == 1 and raw[0].shape == (2, 6)


# -- served output equals direct block(x) -----------------------------------

def test_served_bitwise_equals_direct_on_controlled_batch():
    """Submit exactly one bucket's worth BEFORE start: the server forms
    one deterministic batch, whose compiled call must be bitwise equal
    to running the hybridized block on the same stacked batch."""
    net = _mlp()
    rng = np.random.default_rng(1)
    xs = [rng.standard_normal((16,)).astype(np.float32) for _ in range(4)]
    srv = ModelServer(net, max_batch=4, batch_buckets=(4,),
                      deadline_ms=0, workers=1)
    try:
        futs = [srv.submit(x) for x in xs]
        srv.start()
        outs = [f.result(timeout=60) for f in futs]
    finally:
        srv.stop()
    ref = net(mx.nd.array(np.stack(xs))).asnumpy()
    for out, r in zip(outs, ref):
        np.testing.assert_array_equal(out, r)


def test_served_model_runs_where_its_parameters_live():
    """Requests arrive as host arrays on threads whose default context is
    cpu(0).  The served graph must still be built and run on the context
    the parameters were initialized on — on a TPU host that is the chip,
    here a second CPU device stands in for it."""
    ctx = mx.cpu(1)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(6, in_units=16))
    net.initialize(ctx=ctx)
    net.hybridize()
    x = np.random.default_rng(3).standard_normal((16,)).astype(np.float32)
    g = net.cached_graph(np.stack([x, x]))       # host example values
    assert {d for o in g.raw(np.stack([x, x])) for d in o.devices()} \
        == {ctx.device}
    srv = ModelServer(net, max_batch=2, deadline_ms=0, workers=1)
    try:
        srv.start()
        out = srv.infer(x, timeout=60)
    finally:
        srv.stop()
    np.testing.assert_allclose(
        out, net(mx.nd.array(x[None], ctx=ctx)).asnumpy()[0], rtol=1e-6)


def test_concurrent_clients_bitwise_elementwise():
    """4 client threads x 8 requests against an elementwise model:
    whatever batches the continuous batcher forms, every served row is
    bitwise equal to the direct batch-1 forward."""
    net = _Elemwise()
    net.hybridize()
    rng = np.random.default_rng(2)
    xs = [rng.standard_normal((8,)).astype(np.float32)
          for _ in range(32)]
    refs = [net(mx.nd.array(x[None])).asnumpy()[0] for x in xs]
    srv = ModelServer(net, max_batch=8, deadline_ms=0, workers=2,
                      batch_window_us=500)
    results = {}
    errors = []

    def client(tid):
        try:
            for i in range(tid, 32, 4):
                results[i] = srv.infer(xs[i], timeout=60)
        except Exception as e:  # noqa: BLE001 — surfaced by the assert
            errors.append(e)

    try:
        srv.warmup(xs[0])
        srv.start()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        srv.stop()
    assert not errors, errors
    assert len(results) == 32
    for i in range(32):
        np.testing.assert_array_equal(results[i], refs[i])


def test_concurrent_clients_mlp_close_and_batched():
    """MLP (has matmuls, so batched rows may differ from batch-1 in the
    last ulp): concurrent clients must still match the direct forward
    numerically, and the server must actually have batched."""
    net = _mlp()
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal((16,)).astype(np.float32)
          for _ in range(24)]
    refs = [net(mx.nd.array(x[None])).asnumpy()[0] for x in xs]
    srv = ModelServer(net, max_batch=8, deadline_ms=0, workers=2,
                      batch_window_us=3000)
    results = {}
    b0 = registry().counter("serving.batches").n   # global counter: delta

    def client(tid):
        for i in range(tid, 24, 3):
            results[i] = srv.infer(xs[i], timeout=60)

    try:
        srv.warmup(xs[0])
        srv.start()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        batches = registry().counter("serving.batches").n - b0
    finally:
        srv.stop()
    assert len(results) == 24
    for i in range(24):
        np.testing.assert_allclose(results[i], refs[i], rtol=1e-5,
                                   atol=1e-5)
    assert batches < 24          # dynamic batching actually happened


# -- backpressure + deadlines -----------------------------------------------

def test_backpressure_rejects_past_queue_depth():
    net = _mlp()
    srv = ModelServer(net, max_batch=2, queue_depth=4, deadline_ms=0)
    c0 = registry().counter("serving.rejected_429").n
    try:
        for _ in range(4):       # not started: nothing drains the queue
            srv.submit(np.zeros((16,), np.float32))
        with pytest.raises(ServerOverloaded):
            srv.submit(np.zeros((16,), np.float32))
        assert registry().counter("serving.rejected_429").n == c0 + 1
    finally:
        srv.stop()               # sheds the queued four


def test_deadline_rejection_is_429_style():
    import time
    net = _mlp()
    srv = ModelServer(net, max_batch=2, queue_depth=8, deadline_ms=0)
    try:
        req = srv.submit(np.zeros((16,), np.float32), deadline_ms=10)
        time.sleep(0.05)         # expires while queued (not started)
        srv.start()
        with pytest.raises(DeadlineExceeded):
            req.result(timeout=30)
        # a deadline-free request on the same server still serves
        out = srv.infer(np.zeros((16,), np.float32), timeout=60)
        assert out.shape == (6,)
    finally:
        srv.stop()


def test_no_bucket_rejection():
    net = _mlp()
    srv = ModelServer(net, max_batch=2, length_buckets=(8, 16),
                      deadline_ms=0)
    try:
        with pytest.raises(NoBucketError):
            srv.submit(np.zeros((17,), np.float32))
    finally:
        srv.stop()


# -- graceful shutdown ------------------------------------------------------

def test_stop_drains_queued_requests():
    net = _mlp()
    rng = np.random.default_rng(4)
    xs = [rng.standard_normal((16,)).astype(np.float32) for _ in range(6)]
    srv = ModelServer(net, max_batch=4, deadline_ms=0, workers=1)
    futs = [srv.submit(x) for x in xs]
    srv.start()
    srv.stop(drain=True)
    for f in futs:
        assert f.result(timeout=1).shape == (6,)   # all completed
    with pytest.raises(ServerClosed):
        srv.submit(xs[0])


def test_sigterm_drains_and_closes():
    prev = signal.signal(signal.SIGTERM, lambda *a: None)
    net = _mlp()
    rng = np.random.default_rng(5)
    xs = [rng.standard_normal((16,)).astype(np.float32) for _ in range(4)]
    srv = ModelServer(net, max_batch=2, deadline_ms=0, workers=1)
    try:
        srv.install_sigterm()
        futs = [srv.submit(x) for x in xs]
        srv.start()
        os.kill(os.getpid(), signal.SIGTERM)
        for f in futs:
            assert f.result(timeout=60) is not None
        # the drain runs on its own thread (the handler must not block
        # in signal context) — wait for admission to close
        import time
        for _ in range(200):
            if srv._admission.closed:
                break
            time.sleep(0.02)
        with pytest.raises(ServerClosed):
            srv.submit(xs[0])
    finally:
        srv.uninstall_sigterm()
        signal.signal(signal.SIGTERM, prev)
        srv.stop()


# -- observability ----------------------------------------------------------

def test_metrics_emitted():
    reg = registry()
    h0 = reg.histogram("serving.request_us").count
    d0 = reg.counter("serving.requests_done").n
    b0 = reg.counter("serving.batches").n
    r0 = reg.counter("serving.tokens_real").n
    p0 = reg.counter("serving.tokens_padded").n
    s0 = reg.counter("serving.slots_padded").n
    net = _mlp()
    # length buckets so sequence padding is exercised: 10-elem requests
    # ride the 16 bucket (6 padded positions each, 0 padded slots)
    srv = ModelServer(net, max_batch=4, deadline_ms=0,
                      length_buckets=(16,), pad_axis=0)
    try:
        srv.warmup(np.zeros((16,), np.float32))
        srv.start()
        for _ in range(5):
            srv.infer(np.zeros((10,), np.float32), timeout=60)
    finally:
        srv.stop()
    assert reg.histogram("serving.request_us").count == h0 + 5
    assert reg.counter("serving.requests_done").n == d0 + 5
    assert reg.counter("serving.batches").n > b0
    real = reg.counter("serving.tokens_real").n - r0
    tokens_padded = reg.counter("serving.tokens_padded").n - p0
    slots_padded = reg.counter("serving.slots_padded").n - s0
    assert real == 5 * 10
    assert tokens_padded == 5 * 6       # length-bucket waste only
    assert slots_padded >= 0            # batch-bucket waste counted apart
    assert "serving.queue_depth" in reg.snapshot()


def test_flight_recorder_request_records(tmp_path):
    fr = FlightRecorder(capacity=64)
    net = _mlp()
    srv = ModelServer(net, max_batch=4, batch_buckets=(4,),
                      deadline_ms=0, workers=1, flight=fr)
    xs = [np.zeros((16,), np.float32) for _ in range(4)]
    try:
        futs = [srv.submit(x) for x in xs]
        srv.start()
        for f in futs:
            f.result(timeout=60)
    finally:
        srv.stop()
    recs = fr.requests()
    assert len(recs) == 4
    for r in recs:
        assert r["ok"] and r["batch_size"] == 4
        assert r["bucket"] == "16:float32"
        assert r["enqueue"] <= r["assemble"] <= r["dispatch"] \
            <= r["done"]
    # the crash dump carries the request ring alongside step records
    import json
    path = fr.dump("test", str(tmp_path / "flight.json"))
    payload = json.loads(open(path).read())
    assert payload["n_requests"] == 4
    assert {"steps", "requests"} <= set(payload)


class _SeqModel(gluon.HybridBlock):
    """Per-position + pooled outputs, to exercise output unpadding."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.emb = gluon.nn.Embedding(50, 8)
            self.head = gluon.nn.Dense(4, flatten=False, in_units=8)

    def hybrid_forward(self, F, toks):
        x = self.emb(toks)                      # (B, S, 8)
        return self.head(x), F.max(x, axis=1)   # per-position, pooled


def test_length_buckets_pad_serve_and_unpad_outputs():
    net = _SeqModel()
    net.initialize()
    net.hybridize()
    rng = np.random.default_rng(7)
    srv = ModelServer(net, max_batch=4, length_buckets=(16, 32),
                      deadline_ms=0, workers=2)
    lens = [5, 11, 16, 20, 31]
    toks = [rng.integers(0, 50, (n,)).astype(np.int32) for n in lens]
    try:
        srv.start()
        outs = [srv.infer(t, timeout=60) for t in toks]
    finally:
        srv.stop()
    for t, (per_pos, pooled) in zip(toks, outs):
        # per-position output sliced back to the REQUEST's length...
        assert per_pos.shape == (len(t), 4)
        # ...and the real positions match a direct padded batch-1 call
        # (padding VALUES are the model's contract; shapes are ours)
        bucket = 16 if len(t) <= 16 else 32
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(t)] = t
        ref_pos, ref_pool = net(mx.nd.array(padded))
        np.testing.assert_allclose(per_pos,
                                   ref_pos.asnumpy()[0, :len(t)],
                                   rtol=1e-5, atol=1e-6)
        # pooled output (no length axis) passes through unsliced
        assert pooled.shape == (8,)
        np.testing.assert_allclose(pooled, ref_pool.asnumpy()[0],
                                   rtol=1e-5, atol=1e-6)


def test_warmup_canonicalizes_dtypes_like_submit():
    net = _mlp()
    srv = ModelServer(net, max_batch=2, batch_buckets=(2,),
                      deadline_ms=0)
    try:
        # float64 sample (numpy's default) must warm the SAME executable
        # float32 requests hit
        n = srv.warmup(np.zeros((16,), np.float64))
        assert n == 1
        srv.start()
        srv.infer(np.zeros((16,), np.float64), timeout=60)
        srv.infer(np.zeros((16,), np.float32), timeout=60)
        assert len(srv._graphs) == 1        # no second compile
    finally:
        srv.stop()


# -- the export seam --------------------------------------------------------

def test_serve_exported_symbol_params(tmp_path):
    net = _mlp(in_units=6, out=3)
    sym_f, par_f = net.export(str(tmp_path / "m"))
    srv = ModelServer.from_exported(sym_f, "data", par_f, max_batch=4,
                                    deadline_ms=0)
    rng = np.random.default_rng(6)
    xs = [rng.standard_normal((6,)).astype(np.float32) for _ in range(5)]
    try:
        srv.start()
        outs = [srv.infer(x, timeout=60) for x in xs]
    finally:
        srv.stop()
    refs = [net(mx.nd.array(x[None])).asnumpy()[0] for x in xs]
    for o, r in zip(outs, refs):
        np.testing.assert_allclose(o, r, rtol=1e-5, atol=1e-6)
