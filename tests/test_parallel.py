"""Sharded (multi-chip) training path: mesh building, dp/tp shardings, and
numerical parity with the single-device imperative Trainer.

Reference strategy analog: tests/nightly/dist_sync_kvstore.py asserts the
reduced value equals num_workers x the pushed gradient; here the invariant
is stronger — the whole dp-sharded step must equal the unsharded step
(SURVEY.md §4.5)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon import nn, loss as gloss, Trainer


def _mlp(prefix):
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", in_units=16))
        net.add(nn.Dense(10, in_units=32))
    return net


def _init_same(net_a, net_b):
    net_a.initialize(mx.init.Xavier(rnd_type="gaussian"))
    net_b.initialize()
    pa = list(net_a.collect_params().values())
    pb = list(net_b.collect_params().values())
    for a, b in zip(pa, pb):
        b.set_data(a.data())


def test_make_mesh_axes():
    mesh = par.make_mesh({"dp": 4, "tp": 2})
    assert mesh.axis_names == ("dp", "tp")
    assert mesh.devices.shape == (4, 2)
    mesh = par.make_mesh()
    assert mesh.axis_names == ("dp",)
    assert mesh.devices.size == 8


def test_sharding_rules():
    from jax.sharding import PartitionSpec as P
    rules = par.ShardingRules([
        (r".*_qkv_weight$", ("tp", None)),
        (r".*_proj_weight$", (None, "tp")),
    ])
    assert rules.spec_for("enc0_qkv_weight") == P("tp", None)
    assert rules.spec_for("enc0_proj_weight") == P(None, "tp")
    assert rules.spec_for("enc0_bias") == P()


@pytest.mark.parametrize("opt,opt_args", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01}),
])
def test_sharded_matches_imperative(opt, opt_args):
    np.random.seed(7)
    net_ref = _mlp("ref_")
    net_par = _mlp("par_")
    _init_same(net_ref, net_par)

    trainer_ref = Trainer(net_ref.collect_params(), opt, dict(opt_args))
    loss_fn = gloss.SoftmaxCrossEntropyLoss()
    sharded = par.ShardedTrainer(net_par, loss_fn, opt, dict(opt_args))

    x = np.random.randn(16, 16).astype(np.float32)
    y = np.random.randint(0, 10, (16,))

    for _ in range(3):
        data, label = mx.nd.array(x), mx.nd.array(y)
        with mx.autograd.record():
            out = net_ref(data)
            l = loss_fn(out, label)
        l.backward()
        trainer_ref.step(16)
        sharded.step(x, y)

    sharded.sync_params()
    for p_ref, p_par in zip(net_ref.collect_params().values(),
                            net_par.collect_params().values()):
        np.testing.assert_allclose(
            p_ref.data().asnumpy(), p_par.data().asnumpy(),
            rtol=2e-5, atol=2e-5,
            err_msg=f"{p_ref.name} diverged from imperative trainer")


def test_sharded_loss_decreases_tp():
    """dp x tp mesh: Dense weights sharded over tp; loss must go down."""
    np.random.seed(3)
    mesh = par.make_mesh({"dp": 4, "tp": 2})
    rules = par.ShardingRules([
        (r".*dense0_weight$", ("tp", None)),
        (r".*dense1_weight$", (None, "tp")),
    ])
    net = _mlp("tp_")
    net.initialize()
    loss_fn = gloss.SoftmaxCrossEntropyLoss()
    tr = par.ShardedTrainer(net, loss_fn, "sgd",
                            {"learning_rate": 0.5}, mesh=mesh, rules=rules)
    x = np.random.randn(32, 16).astype(np.float32)
    y = np.random.randint(0, 10, (32,))
    losses = [float(tr.step(x, y).asnumpy()) for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_sharded_batchnorm_aux_updates():
    """BatchNorm running stats (aux, FMutateInputs analog) must update
    through the sharded step."""
    net = nn.HybridSequential(prefix="bn_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
        net.add(nn.BatchNorm())
        net.add(nn.Dense(3, in_units=8))
    net.initialize()
    loss_fn = gloss.SoftmaxCrossEntropyLoss()
    tr = par.ShardedTrainer(net, loss_fn, "sgd", {"learning_rate": 0.1})
    x = (np.random.randn(16, 4) * 3 + 1).astype(np.float32)
    y = np.random.randint(0, 3, (16,))
    for _ in range(5):
        tr.step(x, y)
    tr.sync_params()
    params = net.collect_params()
    rm = [p for n, p in params.items() if n.endswith("running_mean")][0]
    assert abs(rm.data().asnumpy()).sum() > 1e-3, \
        "running_mean never updated through the sharded step"


def test_functional_nag_default_momentum():
    """Regression: NAG with default momentum=0 must not crash in the
    functional lowering."""
    net = _mlp("nag_")
    net.initialize()
    tr = par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "nag",
                            {"learning_rate": 0.1})
    x = np.random.randn(8, 16).astype(np.float32)
    y = np.random.randint(0, 10, (8,))
    l0 = float(tr.step(x, y).asnumpy())
    l1 = float(tr.step(x, y).asnumpy())
    assert np.isfinite(l0) and np.isfinite(l1)


def test_trainer_stale_grad_raises():
    """Reference parity: step() without backward raises unless
    ignore_stale_grad."""
    net = _mlp("stale_")
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    with pytest.raises(mx.MXNetError, match="stale"):
        tr.step(8)
    tr.step(8, ignore_stale_grad=True)  # skips, no crash


def test_ring_attention_matches_dense():
    """Ring attention over the sp axis must equal dense softmax attention
    exactly (it is exact, not approximate) — causal and non-causal."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.ring import ring_attention

    mesh = par.make_mesh({"dp": 2, "sp": 4})
    rng = np.random.RandomState(0)
    BH, S, D = 4, 32, 8
    q = jnp.asarray(rng.randn(BH, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(BH, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(BH, S, D).astype(np.float32))

    def dense(q, k, v, causal):
        s = np.einsum("bqd,bkd->bqk", q, k) / np.sqrt(D)
        if causal:
            mask = np.tril(np.ones((S, S), bool))
            s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bqk,bkd->bqd", p, v)

    for causal in (False, True):
        out = np.asarray(ring_attention(q, k, v, mesh, causal=causal))
        ref = dense(np.asarray(q), np.asarray(k), np.asarray(v), causal)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5,
                                   err_msg=f"causal={causal}")


def test_trainer_list_labels_and_shard_batch():
    """Labels given as a python list are ONE label array (regression:
    _to_vals unpacking rejected lists); shard_batch is the public way to
    pre-place batches on the mesh."""
    np.random.seed(5)
    net = _mlp("lbl_")
    net.initialize()
    tr = par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                            {"learning_rate": 0.1})
    x = np.random.randn(8, 16).astype(np.float32)
    y = [int(i % 10) for i in range(8)]
    l1 = float(tr.step(x, y).asnumpy())
    assert np.isfinite(l1)
    xs, ys = tr.shard_batch(x, y)
    l2 = float(tr.step(xs, ys).asnumpy())
    assert np.isfinite(l2)


def test_batchnorm_is_sync_under_sharded_step():
    """SyncBatchNorm semantics come free from GSPMD: with the batch
    sharded over 8 devices, the BN statistics the sharded step computes
    equal the GLOBAL batch statistics, not per-shard ones (reference:
    contrib SyncBatchNorm's raison d'etre)."""
    from mxnet_tpu.gluon.contrib.nn import SyncBatchNorm

    np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(SyncBatchNorm(momentum=0.0))   # new stats == batch stats
    net.initialize()
    bn = net[0]
    tr = par.ShardedTrainer(
        net, lambda out, y: mx.nd.mean(out * 0), "sgd",
        {"learning_rate": 0.0})
    # per-shard distributions differ wildly: shard i ~ N(i, 1)
    x = np.concatenate([np.random.randn(2, 3, 4, 4) + i
                        for i in range(8)]).astype(np.float32)
    tr.step(x, np.zeros((16,), np.float32))
    tr.sync_params()
    got_mean = bn.running_mean.data().asnumpy()
    want = x.mean(axis=(0, 2, 3))         # GLOBAL batch mean
    np.testing.assert_allclose(got_mean, want, rtol=1e-4, atol=1e-4)
    # variance is the real discriminator: the GLOBAL var (~6+, the
    # shard means spread 0..7) vs the average of per-shard vars (~1);
    # a per-shard-stats regression would pass the mean check alone
    got_var = bn.running_var.data().asnumpy()
    want_var = x.var(axis=(0, 2, 3))
    assert want_var.mean() > 4.0          # sanity: spread dominates
    np.testing.assert_allclose(got_var, want_var, rtol=1e-3, atol=1e-3)


def test_sharded_trainer_checkpoint_resume(tmp_path):
    """Orbax-backed sharded checkpoint (§5.4 async-writes story): resume
    must replay identically to the uninterrupted run — params, momenta,
    and the update counter all restored into their shardings."""
    from mxnet_tpu.gluon import loss as gloss

    np.random.seed(0)

    def build_tr():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"))
            net.add(nn.Dropout(0.5))      # stochastic: proves RNG resume
            net.add(nn.Dense(4))
        net.initialize()
        return par.ShardedTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9})

    x = np.random.randn(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, 16)
    tr = build_tr()
    for _ in range(5):
        tr.step(x, y)
    tr.save_checkpoint(str(tmp_path / "ckpt"))
    for _ in range(3):
        loss_a = tr.step(x, y)

    tr2 = build_tr()
    tr2.step(x, y)                      # build shardings
    tr2.load_checkpoint(str(tmp_path / "ckpt"))
    assert tr2._t == 5                  # update counter restored
    for _ in range(3):
        loss_b = tr2.step(x, y)
    # bit-identical resume INCLUDING dropout masks (RNG stream restored)
    assert abs(float(loss_b.asnumpy()) -
               float(loss_a.asnumpy())) < 1e-6
    # a later save lands in a NEW step dir; the old one survives
    tr2.save_checkpoint(str(tmp_path / "ckpt"))
    tr2.wait_checkpoint()
    import os
    dirs = sorted(os.listdir(tmp_path / "ckpt"))
    assert dirs == ["state-00000005", "state-00000008"]


def test_sharded_trainer_tuple_labels():
    """Multi-stream labels (BERT pretraining shape: mlm labels + weights +
    nsp labels) shard element-wise and reach the loss as a tuple."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu import parallel as par

    net = gluon.nn.Dense(4, flatten=False)
    net.initialize()

    def loss_fn(out, ys):
        lab, w = ys
        return nd.sum(nd.square(out - lab) * w) / nd.maximum(
            nd.sum(w), nd.array(np.array(1.0, np.float32)))

    tr = par.ShardedTrainer(net, loss_fn, "sgd", {"learning_rate": 0.2})
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 4, 6)).astype(np.float32)
    w_true = rng.standard_normal((6, 4)).astype(np.float32)
    lab = x @ w_true                    # learnable target
    w = (rng.random((8, 4, 4)) < 0.5).astype(np.float32)
    # the loss is already weight-normalized; batch_size=1 keeps the
    # trainer's 1/batch rescale from shrinking the effective lr
    l0 = float(tr.step(x, (lab, w), batch_size=1).asnumpy())
    for _ in range(60):
        loss = tr.step(x, (lab, w), batch_size=1)
    l1 = float(loss.asnumpy())
    assert l1 < 0.2 * l0, (l0, l1)


# -- ZeRO scale-out (zero_stage / accum_steps / re-shard) --------------------

def _zero_run(zero, accum, opt="adam", opt_args=None, mesh=None, steps=5,
              guard=False, bucket=0.0):
    """One short training run; returns (trainer, params-by-suffix,
    final loss).  Every call re-seeds identically, so two runs differ
    only by the knobs under test."""
    np.random.seed(7)
    mx.random.seed(3)
    btag = str(bucket).replace(".", "p").replace("-", "m").replace("+", "")
    net = _mlp(f"zr{zero}a{accum}{'g' if guard else ''}b{btag}_")
    net.initialize(mx.init.Xavier(rnd_type="gaussian"))
    tr = par.ShardedTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), opt,
        dict(opt_args or {"learning_rate": 0.01}), mesh=mesh,
        zero_stage=zero, accum_steps=accum, comm_bucket_mb=bucket)
    if guard:
        tr.enable_nonfinite_guard(dynamic_loss_scale=True)
    rng = np.random.RandomState(11)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 10, (16,))
    for _ in range(steps):
        loss = tr.step(x, y)
    tr.sync_params()
    params = {n.split("_", 1)[1]: p.data().asnumpy()
              for n, p in net.collect_params().items()}
    return tr, params, float(loss.asnumpy())


def test_zero_stage0_bitwise_deterministic():
    """zero_stage=0 is the pre-ZeRO replicated step: two identical runs
    through the (refactored) build path must be BITWISE equal.  This
    in-tree test pins run-to-run determinism of the stage-0/accum-1
    graph; the cross-version half of the acceptance contract — the same
    run bitwise-equal to the PRE-refactor step — was verified against a
    pre-PR worktree at review time (identical params SHA + loss bits)
    and cannot be re-asserted from inside one tree."""
    _, p_a, l_a = _zero_run(0, 1)
    _, p_b, l_b = _zero_run(0, 1)
    assert l_a == l_b
    for n in p_a:
        np.testing.assert_array_equal(p_a[n], p_b[n], err_msg=n)


@pytest.mark.parametrize("zero,accum", [(1, 1), (2, 1), (1, 4), (2, 4)])
def test_zero_and_accum_match_replicated(zero, accum):
    """ZeRO-sharded state (+ microbatched accumulation) is a LAYOUT
    change, not a numerics change: final params must match the
    replicated stage-0 trainer on the same data (allclose — the
    reduce-scatter reassociates the dp sum)."""
    _, p_ref, _ = _zero_run(0, 1)
    _, p_z, _ = _zero_run(zero, accum)
    for n in p_ref:
        np.testing.assert_allclose(p_ref[n], p_z[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)


def test_zero_guarded_dynamic_scale_matches():
    """The in-graph all-finite guard + dynamic loss scale compose with
    ZeRO + accumulation (the ResilientTrainer configuration)."""
    tr_ref, p_ref, _ = _zero_run(0, 1, guard=True)
    tr_z, p_z, _ = _zero_run(2, 2, guard=True)
    for n in p_ref:
        np.testing.assert_allclose(p_ref[n], p_z[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)
    assert tr_z.loss_scale == tr_ref.loss_scale


def test_zero_opt_state_bytes_sharded():
    """The ZeRO acceptance metric: Adam state (m, v per param) at
    zero_stage=1 must cost >= 40% less per chip than the replicated
    layout (here dp=8: the partitionable tensors drop to 1/8)."""
    tr0, _, _ = _zero_run(0, 1)
    tr1, _, _ = _zero_run(1, 1)
    b0, b1 = tr0.peak_opt_state_bytes(), tr1.peak_opt_state_bytes()
    assert b1 <= 0.6 * b0, (b0, b1)
    # stage 0 really is replicated: every chip carries the full state
    per_dev = tr0.opt_state_bytes_per_device()
    assert len(set(per_dev.values())) == 1


@pytest.mark.parametrize("guard", [False, True])
def test_lower_step_reads_the_compiled_program(guard):
    """lower_step() hands back the very step the trainer runs, lowered
    against its live state: compiling it shows the collectives ZeRO put
    there, and neither runs nor donates anything (the next step still
    works, bitwise as if it had not been looked at)."""
    tr, _, _ = _zero_run(1, 1, guard=guard, steps=1)
    rng = np.random.RandomState(11)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 10, (16,))
    text = tr.lower_step(x, y).compile().as_text()
    assert "all-gather" in text             # updated params gathered back
    assert not tr.donation_consumed
    looked = float(tr.step(x, y).asnumpy())
    tr_ref, _, _ = _zero_run(1, 1, guard=guard, steps=1)
    assert looked == float(tr_ref.step(x, y).asnumpy())


def test_param_bytes_per_device_counts_every_holder():
    tr, _, _ = _zero_run(0, 1, steps=1)
    per_dev = tr.param_bytes_per_device()
    n_param_bytes = sum(int(np.prod(p.shape)) * 4 for p in
                        tr._block.collect_params().values())
    assert len(per_dev) == 8 and set(per_dev.values()) == {n_param_bytes}
    net = _mlp("pbytes_")
    net.initialize()
    fresh = par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd")
    with pytest.raises(mx.MXNetError, match="param_bytes_per_device"):
        fresh.param_bytes_per_device()
    with pytest.raises(mx.MXNetError, match="opt_state_bytes_per_device"):
        fresh.opt_state_bytes_per_device()


def test_accum_requires_divisible_batch():
    np.random.seed(0)
    net = _mlp("accval_")
    net.initialize()
    tr = par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                            {"learning_rate": 0.1}, accum_steps=3)
    x = np.random.randn(16, 16).astype(np.float32)
    y = np.random.randint(0, 10, (16,))
    with pytest.raises(mx.MXNetError, match="accum_steps"):
        tr.step(x, y)
    with pytest.raises(mx.MXNetError, match="zero_stage"):
        par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                           {"learning_rate": 0.1}, zero_stage=3)


def test_zero_checkpoint_reshard_roundtrip(tmp_path):
    """Save at dp=4 / restore at dp=2 (zero_stage=1): the restore
    template carries the CURRENT trainer's shardings, so the sharded
    opt state re-shards on load — the elastic re-form hook's
    persistence story.  Continued training must match the uninterrupted
    dp=4 run."""
    import jax
    mesh4 = par.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    mesh2 = par.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tr4, _, _ = _zero_run(1, 1, mesh=mesh4, steps=3)
    tr4.save_checkpoint(str(tmp_path / "ck"))
    tr4.wait_checkpoint()

    np.random.seed(7)
    mx.random.seed(3)
    net2 = _mlp("zres_")
    net2.initialize(mx.init.Xavier(rnd_type="gaussian"))
    tr2 = par.ShardedTrainer(net2, gloss.SoftmaxCrossEntropyLoss(),
                             "adam", {"learning_rate": 0.01}, mesh=mesh2,
                             zero_stage=1)
    rng = np.random.RandomState(11)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 10, (16,))
    tr2.step(x, y)                       # build dp=2 shardings
    tr2.load_checkpoint(str(tmp_path / "ck"))
    assert tr2.num_update == 3
    for _ in range(2):
        l4 = tr4.step(x, y)
        l2 = tr2.step(x, y)
    assert abs(float(l4.asnumpy()) - float(l2.asnumpy())) < 1e-5
    tr4.sync_params()
    tr2.sync_params()
    p4 = [p.data().asnumpy()
          for p in tr4._block.collect_params().values()]
    p2 = [p.data().asnumpy()
          for p in tr2._block.collect_params().values()]
    for a, b in zip(p4, p2):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_reshard_in_place_preserves_state():
    """trainer.reshard(new_mesh) — the in-graph re-shard hook a fleet
    re-form calls — re-places live params/opt state/RNG onto the new
    mesh and keeps training: values preserved, step counter intact, and
    the continued run matches a never-resharded trainer."""
    import jax
    mesh2 = par.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tr_a, _, _ = _zero_run(1, 1, steps=3)          # full 8-dev mesh
    tr_b, _, _ = _zero_run(1, 1, steps=3)
    tr_b.reshard(mesh2)
    assert tr_b.num_update == 3 and tr_b.dp_size == 2
    rng = np.random.RandomState(11)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 10, (16,))
    for _ in range(2):
        la = tr_a.step(x, y)
        lb = tr_b.step(x, y)
    assert abs(float(la.asnumpy()) - float(lb.asnumpy())) < 1e-5
    tr_a.sync_params()
    tr_b.sync_params()
    pa = [p.data().asnumpy()
          for p in tr_a._block.collect_params().values()]
    pb = [p.data().asnumpy()
          for p in tr_b._block.collect_params().values()]
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# -- bucketed gradient reduce-scatter (comm_bucket_mb) -----------------------

def test_comm_bucket_infinite_bitwise_matches_fused():
    """comm_bucket_mb large enough to hold EVERY gradient is one
    bucket, and one bucket short-circuits to the fused constraint
    sweep — byte-for-byte the PR-10 trace (the bucket=∞ half of the
    acceptance contract), asserted bitwise against bucketing off."""
    tr_off, p_off, l_off = _zero_run(1, 1)
    tr_inf, p_inf, l_inf = _zero_run(1, 1, bucket=1e9)
    assert tr_off.grad_buckets is None and tr_inf.grad_buckets is None
    assert l_off == l_inf
    for n in p_off:
        np.testing.assert_array_equal(p_off[n], p_inf[n], err_msg=n)


@pytest.mark.parametrize("zero,accum,bucket", [
    (0, 1, 1e-5), (1, 1, 1e-5), (2, 1, 1e-5), (1, 2, 1e-5),
    (1, 1, 2e-3),    # mid cap: some buckets hold > 1 gradient
])
def test_comm_bucket_allclose_across_sizes(zero, accum, bucket):
    """Bucketing is a SCHEDULE change, not a numerics change: any cap,
    at any zero stage (and under accumulation), must match the fused
    replicated step to float tolerance (the optimization_barrier chain
    is an identity; only collective placement moves)."""
    _, p_ref, _ = _zero_run(0, 1)
    tr_b, p_b, _ = _zero_run(zero, accum, bucket=bucket)
    assert tr_b.grad_buckets is not None   # the cap really bucketed
    for n in p_ref:
        np.testing.assert_allclose(p_ref[n], p_b[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)


def test_comm_bucket_partition_and_validation():
    """The partition is reverse parameter order (backward materializes
    last layers' gradients first) and a negative cap is rejected."""
    tr, _, _ = _zero_run(1, 1, bucket=1e-5)   # tiny: one grad per bucket
    bks = tr.grad_buckets
    n_params = len(tr._train_params)
    assert [b for bs in bks for b in bs] == list(range(n_params - 1,
                                                       -1, -1))
    net = _mlp("bval_")
    net.initialize()
    with pytest.raises(mx.MXNetError, match="comm_bucket_mb"):
        par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                           {"learning_rate": 0.1}, comm_bucket_mb=-1)
    # the live setter shares the constructor's contract: a negative
    # cap is rejected, not silently coerced to bucketing-off
    with pytest.raises(mx.MXNetError, match="comm_bucket_mb"):
        tr.set_comm_bucket_mb(-2)


def test_set_comm_bucket_live_rebuild_matches():
    """set_comm_bucket_mb on a built trainer (the CommBucketController
    apply target) rebuilds the jitted step without touching training
    state: continued training matches a never-rebucketed run."""
    tr_a, _, _ = _zero_run(1, 1, steps=3)
    tr_b, _, _ = _zero_run(1, 1, steps=3)
    tr_b.set_comm_bucket_mb(1e-5)
    assert tr_b.grad_buckets is not None and tr_b.num_update == 3
    rng = np.random.RandomState(11)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 10, (16,))
    for _ in range(2):
        la = tr_a.step(x, y)
        lb = tr_b.step(x, y)
    assert abs(float(la.asnumpy()) - float(lb.asnumpy())) < 1e-5
    # a cap move that lands on the SAME partition is free (no rebuild)
    jit_before = tr_b._jit_step
    tr_b.set_comm_bucket_mb(1.1e-5)       # still one grad per bucket
    assert tr_b._jit_step is jit_before


# -- accumulation-aware BatchNorm (the PR-10 carried follow-up) ---------------

def test_accum_batchnorm_stats_sequential_per_microbatch():
    """Pins the accumulation-aware BatchNorm semantics the README's
    SPMD section documents: with accum_steps=N the BN aux stats update
    SEQUENTIALLY, once per microbatch, through the scan carry —
    equivalent to stepping the N microbatches one after another — and
    are NOT a single update computed from the aggregate global batch
    (nor just the last microbatch's stats: every microbatch
    contributes through the momentum recursion)."""
    def build(prefix):
        np.random.seed(4)
        mx.random.seed(4)                # identical weights in every net
        net = nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(nn.Dense(8, in_units=4), nn.BatchNorm(),
                    nn.Dense(3, in_units=8))
        net.initialize()
        return net

    def running_mean(net):
        return [p for n, p in net.collect_params().items()
                if n.endswith("running_mean")][0].data().asnumpy()

    rng = np.random.RandomState(2)
    # microbatches with deliberately DIFFERENT distributions (block i
    # ~ N(i, 1)) so the three candidate semantics give far-apart stats
    x = np.concatenate([
        rng.randn(8, 4).astype(np.float32) + i for i in range(4)])
    y = rng.randint(0, 3, (32,))
    # lr=0 freezes params; only the aux stats move
    net_a = build("bnacc_")
    tr_a = par.ShardedTrainer(net_a, gloss.SoftmaxCrossEntropyLoss(),
                              "sgd", {"learning_rate": 0.0},
                              accum_steps=4)
    tr_a.step(x, y)
    tr_a.sync_params()
    rm_accum = running_mean(net_a)

    net_s = build("bnseq_")
    tr_s = par.ShardedTrainer(net_s, gloss.SoftmaxCrossEntropyLoss(),
                              "sgd", {"learning_rate": 0.0})
    for i in range(4):
        tr_s.step(x[8 * i:8 * (i + 1)], y[8 * i:8 * (i + 1)])
    tr_s.sync_params()
    rm_seq = running_mean(net_s)
    np.testing.assert_allclose(rm_accum, rm_seq, rtol=1e-4, atol=1e-5)

    net_g = build("bnagg_")
    tr_g = par.ShardedTrainer(net_g, gloss.SoftmaxCrossEntropyLoss(),
                              "sgd", {"learning_rate": 0.0})
    tr_g.step(x, y)                      # ONE aggregate-batch update
    tr_g.sync_params()
    rm_agg = running_mean(net_g)
    # the discriminator: sequential-momentum stats weight 4 updates
    # (sum of 0.1 * 0.9^k) — far from one aggregate update's 0.1
    assert not np.allclose(rm_accum, rm_agg, rtol=0.05, atol=1e-3)


def test_reduce_scatter_host_local_fallback():
    """Without a process group, reduce_scatter_host degrades to the
    1-rank case: sum == identity, slice == everything."""
    from mxnet_tpu.parallel import dist
    if dist.is_initialized():
        pytest.skip("process group active in this interpreter")
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    out = dist.reduce_scatter_host(x)
    np.testing.assert_array_equal(out, x)


def test_sharded_embedding_large_vocab():
    """The reference's sparse flagship shape, TPU-first: a large-vocab
    Embedding trained under ShardedTrainer with the table ROW-SHARDED over
    the mesh (vocab dim split over 'tp'), dp over the batch.  XLA turns
    the gather/scatter-add into collectives; no step densifies a
    (vocab, dim) gradient on any single device.  Trained weights must
    match single-device training and only touched rows may change."""
    np.random.seed(11)
    VOCAB, DIM, CLASSES = 512, 16, 4

    def build(prefix):
        net = mx.gluon.nn.Sequential(prefix=prefix)
        with net.name_scope():
            net.add(mx.gluon.nn.Embedding(VOCAB, DIM),
                    mx.gluon.nn.HybridLambda(
                        lambda F, t: F.mean(t, axis=1)),
                    mx.gluon.nn.Dense(CLASSES))
        return net

    mesh = par.make_mesh({"dp": 4, "tp": 2})
    rules = par.ShardingRules([
        # row-shard the embedding table over tp: each device holds
        # VOCAB/2 rows; XLA inserts the gather collective
        (r".*embedding0_weight$", ("tp", None)),
    ])
    net_ref = build("embref_")
    net_par = build("embpar_")
    net_ref.initialize(mx.init.Xavier())
    x0 = mx.nd.array(np.zeros((8, 6), np.int64), dtype="int64")
    net_ref(x0)                               # materialize shapes
    net_par.initialize(mx.init.Xavier())
    net_par(x0)
    for p_ref, p_par in zip(net_ref.collect_params().values(),
                            net_par.collect_params().values()):
        p_par.set_data(p_ref.data().copy())

    loss_fn = gloss.SoftmaxCrossEntropyLoss()
    tr_ref = Trainer(net_ref.collect_params(), "sgd",
                     {"learning_rate": 0.5})
    tr_par = par.ShardedTrainer(net_par, loss_fn, "sgd",
                                {"learning_rate": 0.5},
                                mesh=mesh, rules=rules)

    # batch touches a SMALL subset of the vocab (the sparse regime)
    tokens = np.random.randint(0, 40, (8, 6)).astype(np.int64)
    labels = np.random.randint(0, CLASSES, (8,))
    w_before = net_par.collect_params()[
        "embpar_embedding0_weight"].data().asnumpy().copy()
    for _ in range(3):
        with mx.autograd.record():
            l = loss_fn(net_ref(mx.nd.array(tokens, dtype="int64")),
                        mx.nd.array(labels))
        l.backward()
        tr_ref.step(8)
        tr_par.step(tokens, labels)
    tr_par.sync_params()
    for p_ref, p_par in zip(net_ref.collect_params().values(),
                            net_par.collect_params().values()):
        np.testing.assert_allclose(
            p_ref.data().asnumpy(), p_par.data().asnumpy(),
            rtol=3e-5, atol=3e-5, err_msg=p_ref.name)
    w_after = net_par.collect_params()[
        "embpar_embedding0_weight"].data().asnumpy()
    untouched = np.setdiff1d(np.arange(VOCAB), np.unique(tokens))
    np.testing.assert_array_equal(w_after[untouched],
                                  w_before[untouched])
    assert not np.allclose(w_after[np.unique(tokens)],
                           w_before[np.unique(tokens)])
