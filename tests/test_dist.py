"""Multi-process dist_sync kvstore tests.

Reference parity: tests/nightly/dist_sync_kvstore.py launched by the dmlc
local tracker, which forks N worker processes on one machine and asserts
push/pull invariants (SURVEY.md §4.5).  TPU analog: N localhost processes
joined via jax.distributed.initialize (driven by the same DMLC_* env vars),
asserting pulled value == num_workers × pushed gradient through KVStore.
"""
import os
import socket
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
    from mxnet_tpu.base import force_cpu_mesh
    force_cpu_mesh(1, verify=False)  # distributed init must precede the
    import numpy as np               # first backend query
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore as kv

    store = kv.create("dist_sync")   # joins process group from DMLC_* env
    rank, nw = store.rank, store.num_workers
    assert nw == int(os.environ["DMLC_NUM_WORKER"]), nw

    # --- invariant 1: init broadcasts rank 0's value -----------------------
    store.init(3, mx.nd.ones((4, 5)) * (1.0 if rank == 0 else 99.0))
    out = mx.nd.zeros((4, 5))
    store.pull(3, out=out)
    assert np.allclose(out.asnumpy(), 1.0), (rank, out.asnumpy())

    # --- invariant 2: pulled value == num_workers x pushed gradient -------
    store.push(3, mx.nd.ones((4, 5)) * 2.0)
    store.pull(3, out=out)
    assert np.allclose(out.asnumpy(), 2.0 * nw), (rank, out.asnumpy())

    # --- invariant 3: per-worker distinct grads sum ------------------------
    store.push(3, mx.nd.ones((4, 5)) * (rank + 1))
    store.pull(3, out=out)
    expect = sum(r + 1 for r in range(nw))
    assert np.allclose(out.asnumpy(), expect), (rank, out.asnumpy())

    # --- invariant 4: 2-bit compression with error feedback ----------------
    store2 = kv.KVStore("dist_sync")
    store2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    store2.init(7, mx.nd.zeros((8,)))
    g = np.full((8,), 0.3, np.float32)
    store2.push(7, mx.nd.array(g))   # acc=0.3 < thr -> q=0, resid=0.3
    out2 = mx.nd.zeros((8,))
    store2.pull(7, out=out2)
    assert np.allclose(out2.asnumpy(), 0.0), (rank, out2.asnumpy())
    store2.push(7, mx.nd.array(g))   # acc=0.6 >= thr -> q=+0.5, resid=0.1
    store2.pull(7, out=out2)
    assert np.allclose(out2.asnumpy(), 0.5 * nw), (rank, out2.asnumpy())

    # --- invariant 5: gluon Trainer trains through the dist kvstore --------
    from mxnet_tpu import nd, autograd, gluon
    np.random.seed(42)
    X = nd.array(np.random.randn(16, 5).astype(np.float32))
    Y = nd.array(np.random.randint(0, 3, 16), dtype="int32")
    mx.random.seed(7)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.05 / nw}, kvstore=store)
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    first = None
    for _ in range(30):
        with autograd.record():
            L = lossfn(net(X), Y).mean()
        L.backward()
        tr.step(1)
        first = first if first is not None else float(L.asnumpy())
    last = float(L.asnumpy())
    assert last < first * 0.7, (first, last)
    wsum = float(sum(p.data().asnumpy().sum()
                     for p in net.collect_params().values()))
    from mxnet_tpu.parallel import dist as _dist
    allw = _dist.allgather_host(np.array([wsum]))
    assert np.allclose(allw, allw[0]), allw   # replicas stay in sync

    # --- invariant 6: update_on_kvstore=False still reduces across workers
    mx.random.seed(7)
    net2 = gluon.nn.Dense(3)
    net2.initialize()
    store3 = kv.KVStore("dist_sync")
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.1}, kvstore=store3,
                        update_on_kvstore=False)
    with autograd.record():
        L2 = lossfn(net2(X), Y).mean()
    L2.backward()
    g_local = net2.weight.grad().asnumpy().copy()
    tr2.allreduce_grads()
    g_summed = net2.weight.grad().asnumpy()
    assert np.allclose(g_summed, g_local * nw, atol=1e-5), \
        (rank, g_local.sum(), g_summed.sum())
    tr2.update(1)

    # --- invariant 7: row_sparse push crosses DCN sparse and reduces over
    # the UNION of row sets (kvstore_dist sparse path) ----------------------
    from mxnet_tpu.sparse import RowSparseNDArray
    store4 = kv.KVStore("dist_sync")
    VOCAB, DIM = 50, 4
    # worker r touches rows {r, r+1, 40}: pairwise overlap + one shared row
    my_rows = np.array([rank, rank + 1, 40], np.int64)
    g_sp = RowSparseNDArray(
        np.full((3, DIM), float(rank + 1), np.float32), my_rows,
        (VOCAB, DIM))
    store4.push(11, g_sp)             # aggregation mode: no stored weight
    agg = store4._store[11]
    assert isinstance(agg, RowSparseNDArray), type(agg)   # never densified
    union = sorted(set(int(r) for w in range(nw)
                       for r in (w, w + 1, 40)))
    assert list(agg.indices) == union, (rank, agg.indices)
    dense = agg.todense().asnumpy()
    expect_d = np.zeros((VOCAB, DIM), np.float32)
    for w in range(nw):
        for r in (w, w + 1, 40):
            expect_d[r] += w + 1
    assert np.allclose(dense, expect_d), (rank, dense[:5])
    # a worker whose batch touched NO rows pushes an EMPTY row_sparse —
    # it must still join the collective (peers would hang otherwise)
    if rank == 0:
        g_empty = RowSparseNDArray(np.zeros((0, DIM), np.float32),
                                   np.zeros((0,), np.int64), (VOCAB, DIM))
    else:
        g_empty = RowSparseNDArray(
            np.full((1, DIM), 5.0, np.float32),
            np.array([2], np.int64), (VOCAB, DIM))
    store4.push(13, g_empty)
    agg13 = store4._store[13]
    assert isinstance(agg13, RowSparseNDArray)
    assert list(agg13.indices) == ([2] if nw > 1 else []), agg13.indices
    if nw > 1:
        assert np.allclose(agg13.data, 5.0 * (nw - 1)), agg13.data

    # sparse pull of selected rows from a DENSE stored weight
    store4.init(12, mx.nd.array(np.arange(VOCAB * DIM, dtype=np.float32)
                                .reshape(VOCAB, DIM)))
    out_sp = RowSparseNDArray(np.zeros((2, DIM), np.float32),
                              np.array([0, 0], np.int64), (VOCAB, DIM))
    store4.row_sparse_pull(12, out=out_sp,
                           row_ids=mx.nd.array(np.array([3, 7]),
                                               dtype="int64"))
    assert np.allclose(out_sp.data[0], np.arange(12, 16)), out_sp.data
    assert np.allclose(out_sp.data[1], np.arange(28, 32)), out_sp.data

    # --- invariant 8: reduce-scatter = fleet sum, then THIS rank's slice
    # (the ZeRO object-plane entry point; in-graph the trainer's
    # zero_stage>=1 path does the same through XLA) ------------------------
    contrib = np.full((2 * nw, 3), float(rank + 1), np.float32)
    rs = _dist.reduce_scatter_host(contrib)
    expect_sum = sum(w + 1 for w in range(nw))
    assert rs.shape == (2, 3), rs.shape
    assert np.allclose(rs, expect_sum), (rank, rs)

    store.barrier()
    print(f"WORKER_{rank}_OK")
""")


SPARSE_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
    from mxnet_tpu.base import force_cpu_mesh
    force_cpu_mesh(1, verify=False)
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore as kv

    store = kv.create("dist_sync")    # joins the process group
    rank, nw = store.rank, store.num_workers
    from mxnet_tpu.parallel import dist

    # --- invariant 1: allgather_rows round-trips variable-length slabs ----
    n = rank + 1                      # DIFFERENT length per rank
    ids = np.arange(n, dtype=np.int64) + 10 * rank
    rows = np.full((n, 3), float(rank + 1), np.float32)
    pairs = dist.allgather_rows(ids, rows)
    assert len(pairs) == nw, len(pairs)
    for r, (pi, pr) in enumerate(pairs):
        assert pi.tolist() == [10 * r + k for k in range(r + 1)], (r, pi)
        assert np.allclose(pr, r + 1) and pr.shape == (r + 1, 3), (r, pr)

    # --- invariant 2: dedup_sum_rows == the dense scatter-sum -------------
    ids2 = np.array([0, 3, 7], np.int64)      # same ids on every rank:
    rows2 = np.full((3, 2), float(rank + 1), np.float32)  # full collision
    uids, summed = dist.dedup_sum_rows(dist.allgather_rows(ids2, rows2))
    assert uids.tolist() == [0, 3, 7], uids
    expect = sum(r + 1 for r in range(nw))
    assert np.allclose(summed, expect), summed

    # --- invariant 3: coalesced sparse exchange trains identically to the
    # dense kvstore path (sgd, wd=0: lazy == dense on touched rows) --------
    from mxnet_tpu import nd, autograd, gluon
    VOCAB, DIM = 40, 6
    np.random.seed(100 + rank)        # per-rank batches: the exchange
    Xe = nd.array(np.random.randint(  # must reconcile DIFFERENT row sets
        0, VOCAB, (8, 2)).astype(np.float32))
    Ye = nd.array(np.random.randint(0, 3, 8), dtype="int32")
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    finals = []
    for knob in ("1", "0"):
        os.environ["MXTPU_SPARSE_EXCHANGE"] = knob
        mx.random.seed(5)
        net = gluon.nn.HybridSequential(prefix=f"sx{knob}_")
        with net.name_scope():
            net.add(gluon.nn.Embedding(VOCAB, DIM, sparse_grad=True))
            net.add(gluon.nn.Flatten())
            net.add(gluon.nn.Dense(3))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1},
                           kvstore=kv.KVStore("dist_sync"),
                           update_on_kvstore=False)
        for _ in range(4):
            with autograd.record():
                L = lossfn(net(Xe), Ye).mean()
            L.backward()
            tr.step(1)
        finals.append([p.data().asnumpy()
                       for p in net.collect_params().values()])
    for a, b in zip(*finals):
        assert np.allclose(a, b, rtol=1e-5, atol=1e-6), \
            (rank, np.abs(a - b).max())
    # replicas in sync after the sparse exchange
    wsum = float(sum(a.sum() for a in finals[0]))
    allw = dist.allgather_host(np.array([wsum]))
    assert np.allclose(allw, allw[0]), allw

    store.barrier()
    print(f"SPARSE_WORKER_{rank}_OK")
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.parametrize("n_workers", [2, 3])
def test_dist_sync_kvstore_multiprocess(tmp_path, n_workers):
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = []
    for r in range(n_workers):
        env = dict(os.environ)
        env.update({
            "MXNET_TEST_ROOT": ROOT,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(n_workers),
            "DMLC_WORKER_ID": str(r),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((r, p.returncode, out))
    for r, rc, out in outs:
        assert rc == 0, f"worker {r} failed:\n{out}"
        assert f"WORKER_{r}_OK" in out, f"worker {r} output:\n{out}"


def test_dist_sparse_exchange_multiprocess(tmp_path):
    """2-proc coalesced row-sparse gradient exchange: allgather_rows
    round-trip, dedup_sum_rows == dense scatter-sum, and gluon training
    through the sparse exchange matches the dense kvstore path."""
    n_workers = 2
    port = _free_port()
    script = tmp_path / "sparse_worker.py"
    script.write_text(SPARSE_WORKER)
    procs = []
    for r in range(n_workers):
        env = dict(os.environ)
        env.update({
            "MXNET_TEST_ROOT": ROOT,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(n_workers),
            "DMLC_WORKER_ID": str(r),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((r, p.returncode, out))
    for r, rc, out in outs:
        assert rc == 0, f"worker {r} failed:\n{out}"
        assert f"SPARSE_WORKER_{r}_OK" in out, f"worker {r} output:\n{out}"


def test_dist_sync_requires_process_group():
    """create('dist_sync') without env/init must raise, never silently
    run process-local (VERDICT.md weak #3)."""
    import mxnet_tpu.kvstore as kv
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import dist
    if dist.is_initialized():
        pytest.skip("process group already initialized in this interpreter")
    saved = {k: os.environ.pop(k) for k in list(os.environ)
             if k.startswith("DMLC_")}
    try:
        with pytest.raises(MXNetError, match="process group"):
            kv.create("dist_sync")
    finally:
        os.environ.update(saved)


def test_row_sparse_pull_local():
    """row_sparse_pull returns only the requested rows (VERDICT weak #4:
    kvstore must agree with the sparse subsystem, not contradict it)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore as kv
    from mxnet_tpu.sparse import RowSparseNDArray
    store = kv.create("local")
    w = np.arange(20, dtype=np.float32).reshape(5, 4)
    store.init("emb", mx.nd.array(w))
    out = RowSparseNDArray(np.zeros((0, 4), np.float32), [], (5, 4))
    store.row_sparse_pull("emb", out=out, row_ids=mx.nd.array([3, 1, 3]))
    assert out.indices.tolist() == [1, 3]
    assert np.allclose(out.data, w[[1, 3]])
    dense = out.todense().asnumpy()
    assert np.allclose(dense[[1, 3]], w[[1, 3]]) and np.all(dense[[0, 2, 4]] == 0)


def test_gradient_compression_requires_dist():
    import mxnet_tpu.kvstore as kv
    from mxnet_tpu.base import MXNetError
    store = kv.create("local")
    with pytest.raises(MXNetError, match="dist"):
        store.set_gradient_compression({"type": "2bit"})
    with pytest.raises(MXNetError, match="compression type"):
        kv.KVStore("dist_sync").set_gradient_compression({"type": "1bit"})


def test_pack2bit_roundtrip():
    import numpy as np
    from mxnet_tpu.kvstore import _pack2bit, _unpack2bit
    codes = np.array([0, 1, 2, 0, 1, 1, 2], np.uint8)
    packed = _pack2bit(codes)
    assert packed.size == 2  # 7 codes -> 2 bytes
    signed = _unpack2bit(packed, 7)
    assert signed.tolist() == [0, 1, -1, 0, 1, 1, -1]
