"""Fault-injection suite for the resilience layer (all CPU, tier-1).

Covers the acceptance matrix of the resilient-supervisor issue: (a) a
NaN-poisoned step is skipped with params bit-identical, (b) a transient
step failure is retried and recovers, (c) a simulated crash between
checkpoints resumes from the newest COMMITTED checkpoint and reproduces
the uninterrupted run bit-for-bit, (d) SIGTERM triggers a flushed
checkpoint before exit — plus retention, dataloader and dist failure
paths, and the thin 'bare-except' mxlint gate (the walker itself lives
in mxnet_tpu/tools/mxlint)."""
import os
import signal
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults
from mxnet_tpu import parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.faults import (Deadline, DeadlineExceeded, FaultPlan,
                              TransientFault, call_with_deadline,
                              retry_call)
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.gluon.data import DataLoader
from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer, \
    TrainingPreempted


# -- helpers ----------------------------------------------------------------

def _build_trainer(seed=42, **kw):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dropout(0.5))        # stochastic: proves RNG resume
        net.add(nn.Dense(4, in_units=16))
    net.initialize()
    return ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9}, **kw)


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 8).astype(np.float32),
             rng.randint(0, 4, (8,))) for _ in range(n)]


def _params(tr):
    import jax
    return [np.asarray(v) for v in jax.device_get(tr._pvals)]


def _opt_state(tr):
    import jax
    return [np.asarray(v) for v in jax.device_get(jax.tree.leaves(tr._state))]


# -- faults.py utilities ----------------------------------------------------

def test_fault_plan_grammar():
    plan = FaultPlan("step_error@3;nan@5 ; ckpt_fail@1x2, loader_stall@4:1.5")
    assert not plan.empty
    assert plan.scheduled("nan", 4) is None
    spec = plan.scheduled("nan", 5)
    assert spec.kind == "nan" and spec.arg is None
    assert plan.scheduled("nan", 5) is None         # consumed exactly once
    # x2 expands to two consecutive indices
    assert plan.scheduled("ckpt_fail", 1) is not None
    assert plan.scheduled("ckpt_fail", 2) is not None
    assert plan.scheduled("ckpt_fail", 3) is None
    assert plan.scheduled("loader_stall", 4).arg == 1.5
    with pytest.raises(TransientFault, match="step_error@3"):
        plan.fire("step_error", 3)
    assert plan.empty
    with pytest.raises(MXNetError, match="bad MXTPU_FAULT_PLAN"):
        FaultPlan("what even is this")
    assert FaultPlan("").empty


def test_fault_plan_env_and_global(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULT_PLAN", "nan@7")
    faults.set_fault_plan(None)
    try:
        # cleared explicitly -> env is NOT re-read (consumed must stay
        # consumed); install from env via from_env
        assert faults.active_plan() is None
        faults.set_fault_plan(FaultPlan.from_env())
        assert faults.active_plan().scheduled("nan", 7) is not None
        faults.set_fault_plan("step_error@1")       # grammar string accepted
        assert faults.active_plan().pending()[0].kind == "step_error"
    finally:
        faults.set_fault_plan(None)


def test_retry_call_backoff_and_exhaustion():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("boom")
        return "ok"

    out = retry_call(flaky, retries=5, base_delay=0.1, max_delay=0.15,
                     jitter=0.0, sleep=sleeps.append)
    assert out == "ok" and calls["n"] == 3
    assert sleeps == [0.1, 0.15]                    # exponential, capped

    calls["n"] = -10                                # always failing now
    with pytest.raises(TransientFault):
        retry_call(flaky, retries=2, base_delay=0.0, jitter=0.0,
                   sleep=lambda _d: None)
    with pytest.raises(MXNetError, match="retries"):
        retry_call(flaky, retries=-1)
    # non-matching exceptions propagate immediately
    def wrong():
        raise ValueError("not transient")
    with pytest.raises(ValueError):
        retry_call(wrong, retries=5, sleep=lambda _d: None)


def test_deadline():
    d = Deadline(30.0)
    assert not d.expired and d.remaining() > 29.0
    d.check()
    d0 = Deadline(0.0)
    assert d0.expired
    with pytest.raises(DeadlineExceeded, match="connect"):
        d0.check("connect")
    import time
    assert call_with_deadline(lambda: 7, 5.0) == 7
    with pytest.raises(DeadlineExceeded):
        call_with_deadline(time.sleep, 0.2, 5.0)
    with pytest.raises(ZeroDivisionError):          # errors pass through
        call_with_deadline(lambda: 1 / 0, 5.0)


# -- (a) NaN/grad-skip guard ------------------------------------------------

def test_nan_step_skipped_params_unchanged():
    rt = ResilientTrainer(_build_trainer(), fault_plan="nan@2",
                          auto_resume=False)
    bs = _batches(3)
    rt.step(*bs[0])
    p1, s1 = _params(rt.trainer), _opt_state(rt.trainer)
    loss2 = rt.step(*bs[1])                  # poisoned -> skipped
    assert np.isnan(float(loss2.asnumpy()))
    p2, s2 = _params(rt.trainer), _opt_state(rt.trainer)
    for a, b in zip(p1, p2):
        assert np.array_equal(a, b)          # bit-identical, not allclose
    for a, b in zip(s1, s2):
        assert np.array_equal(a, b)
    rt.step(*bs[2])                          # training continues
    p3 = _params(rt.trainer)
    assert any(not np.array_equal(a, b) for a, b in zip(p2, p3))
    c = rt.counters
    assert c["steps_skipped"] == 1 and c["steps_retried"] == 0
    # skipped steps still advance the update counter (GradScaler-style)
    assert rt.trainer.num_update == 3


def test_dynamic_loss_scale_decay_and_growth():
    rt = ResilientTrainer(_build_trainer(), fault_plan="nan@2",
                          auto_resume=False, dynamic_loss_scale=True,
                          init_loss_scale=8.0, scale_growth_interval=2,
                          scale_backoff=0.5)
    bs = _batches(5)
    rt.step(*bs[0])
    assert rt.loss_scale == 8.0
    rt.step(*bs[1])                          # skipped -> decay
    assert rt.loss_scale == 4.0
    rt.step(*bs[2])
    rt.step(*bs[3])                          # 2 clean steps -> grow
    assert rt.loss_scale == 8.0
    assert rt.counters["steps_skipped"] == 1


# -- (b) transient step failures retried ------------------------------------

def test_transient_step_failure_retried_and_recovers():
    rt = ResilientTrainer(_build_trainer(), fault_plan="step_error@2",
                          auto_resume=False, max_retries=2,
                          retry_base_delay=0.001)
    bs = _batches(3)
    for x, y in bs:
        loss = rt.step(x, y)
    assert np.isfinite(float(loss.asnumpy()))
    c = rt.counters
    assert c["steps_retried"] == 1 and c["steps_failed"] == 0
    assert rt.trainer.num_update == 3


def test_transient_step_failure_exhausts_retries():
    rt = ResilientTrainer(
        _build_trainer(),
        fault_plan="step_error@2;step_error@2;step_error@2",
        auto_resume=False, max_retries=1, retry_base_delay=0.001)
    bs = _batches(2)
    rt.step(*bs[0])
    with pytest.raises(TransientFault):
        rt.step(*bs[1])                      # 1 try + 1 retry < 3 faults
    c = rt.counters
    assert c["steps_retried"] == 1 and c["steps_failed"] == 1


# -- mid-step failure rollback (ROADMAP 'Known gap' from PR 1) --------------

def _stand_in(st, call):
    """``call`` in place of every executable the trainer owns (what
    ``step()`` calls: ``ShardedTrainer._jit_call``); the real ones back."""
    real = dict(st._compiled)
    st._compiled.update(dict.fromkeys(real, call))
    return real


def test_midstep_failure_rolls_back_t_and_rng():
    """A failure raised from INSIDE ShardedTrainer.step leaves `_t` and
    the RNG stream advanced; the supervisor must roll both back per
    attempt so the retried trajectory is bit-identical to an
    uninterrupted run (the model has Dropout, so a desynced stream WOULD
    change the losses)."""
    bs = _batches(4)
    rt0 = ResilientTrainer(_build_trainer(), auto_resume=False)
    want = [float(rt0.step(x, y).asnumpy()) for x, y in bs]

    rt = ResilientTrainer(_build_trainer(), auto_resume=False,
                          retry_on=(ValueError,), retry_base_delay=0.001)
    rt.step(*bs[0])                      # builds the step
    st = rt.trainer
    (orig,), state = st._compiled.values(), {"fail": True}

    def flaky_jit(*a, **kw):
        # dies AFTER step() advanced _t and consumed the RNG key — the
        # exact non-idempotence the rollback exists for
        if state["fail"]:
            state["fail"] = False
            raise ValueError("injected mid-step failure")
        return orig(*a, **kw)

    _stand_in(st, flaky_jit)
    got = [float(rt.step(x, y).asnumpy()) for x, y in bs[1:]]
    assert want == [want[0]] + got       # bit-identical trajectory
    c = rt.counters
    assert c["rollbacks"] == 1 and c["steps_retried"] == 1
    assert rt.trainer.num_update == 4


def test_midstep_failure_without_retry_still_rolls_back():
    """Even when retries are exhausted, the rollback leaves the trainer
    consistent: `_t` matches the number of APPLIED updates."""
    rt = ResilientTrainer(_build_trainer(), auto_resume=False,
                          retry_on=(ValueError,), max_retries=0)
    bs = _batches(2)
    rt.step(*bs[0])
    st = rt.trainer

    def dead_jit(*a, **kw):
        raise ValueError("boom")

    _stand_in(st, dead_jit)
    with pytest.raises(ValueError):
        rt.step(*bs[1])
    assert st.num_update == 1            # rolled back, not desynced
    assert rt.counters["rollbacks"] == 1
    assert rt.counters["steps_failed"] == 1


def test_midstep_nonretryable_failure_also_rolls_back():
    """A failure type NOT in retry_on still must not desync `_t`/RNG: the
    supervisor rolls back before re-raising, so a caller that catches and
    continues sees a consistent trainer."""
    rt = ResilientTrainer(_build_trainer(), auto_resume=False)  # default
    bs = _batches(2)                         # retry_on=(TransientFault,)
    rt.step(*bs[0])
    st = rt.trainer
    rng_before = mx.random.get_state()

    def dead_jit(*a, **kw):
        raise ValueError("not transient")

    orig = _stand_in(st, dead_jit)
    with pytest.raises(ValueError):
        rt.step(*bs[1])
    assert st.num_update == 1                # rolled back
    assert mx.random.get_state() is rng_before
    assert rt.counters["rollbacks"] == 1
    # the trainer is still usable after restoring the real step
    st._compiled.update(orig)
    rt.step(*bs[1])
    assert st.num_update == 2


def test_refuse_retry_after_donation_consumed():
    """A step that dies AFTER its donated buffers were consumed cannot be
    retried (the live training state is gone): the supervisor raises a
    clear error pointing at checkpoint restore instead of crashing later
    on deleted arrays."""
    rt = ResilientTrainer(_build_trainer(), auto_resume=False,
                          retry_on=(ValueError,), retry_base_delay=0.001)
    bs = _batches(2)
    rt.step(*bs[0])
    st = rt.trainer

    def donated_then_dead(*a, **kw):
        for v in st._pvals:
            v.delete()                   # what real donation leaves
        raise ValueError("dies after donation")

    _stand_in(st, donated_then_dead)
    with pytest.raises(MXNetError, match="donated"):
        rt.step(*bs[1])
    assert st.donation_consumed
    assert rt.counters["rollbacks"] == 0  # refused, never rolled back


# -- committed-checkpoint filtering (satellite 1) ---------------------------

def test_latest_checkpoint_skips_uncommitted(tmp_path):
    tr = _build_trainer()
    x, y = _batches(1)[0]
    tr.step(x, y)
    tr.step(x, y)
    ckdir = tmp_path / "ckpt"
    tr.save_checkpoint(str(ckdir))
    tr.wait_checkpoint()
    committed = str(ckdir / "state-00000002")
    assert ShardedTrainer.latest_checkpoint(str(ckdir)) == committed
    # a crash mid-async-write leaves (i) a torn final dir with no commit
    # marker, (ii) an orbax tmp staging dir — BOTH newer-sorting than the
    # real checkpoint, and both must lose to it
    torn = ckdir / "state-00000099"
    torn.mkdir()
    (torn / "junk").write_text("partial write")
    tmp = ckdir / "state-00000002.orbax-checkpoint-tmp-1234"
    tmp.mkdir()
    assert ShardedTrainer.committed_checkpoints(str(ckdir)) == [committed]
    assert ShardedTrainer.latest_checkpoint(str(ckdir)) == committed
    assert ShardedTrainer.latest_checkpoint(str(tmp_path / "nope")) is None


# -- retention / GC ---------------------------------------------------------

def test_checkpoint_retention_keep_last_k(tmp_path):
    rt = ResilientTrainer(_build_trainer(), auto_resume=False,
                          checkpoint_dir=str(tmp_path / "ck"),
                          checkpoint_every=1, keep_last=2)
    for x, y in _batches(6):
        rt.step(x, y)
    rt.flush()
    committed = ShardedTrainer.committed_checkpoints(str(tmp_path / "ck"))
    assert [os.path.basename(p) for p in committed] == \
        ["state-00000005", "state-00000006"]
    c = rt.counters
    assert c["checkpoints_written"] == 6
    assert c["checkpoints_pruned"] == 4


def test_failed_checkpoint_write_never_counts_as_committed(tmp_path):
    ckdir = str(tmp_path / "ck")
    rt = ResilientTrainer(_build_trainer(), auto_resume=False,
                          fault_plan="ckpt_fail@2",
                          checkpoint_dir=ckdir, checkpoint_every=1,
                          keep_last=10)
    for x, y in _batches(3):
        rt.step(x, y)                        # save #2 (t=2) is torn
    rt.flush()
    names = [os.path.basename(p)
             for p in ShardedTrainer.committed_checkpoints(ckdir)]
    assert names == ["state-00000001", "state-00000003"]
    c = rt.counters
    assert c["checkpoints_failed"] == 1 and c["checkpoints_written"] == 2
    # the torn partial was swept once a newer committed ckpt existed
    assert not os.path.exists(os.path.join(ckdir, "state-00000002"))
    assert ShardedTrainer.latest_checkpoint(ckdir).endswith(
        "state-00000003")
    # keep_last=1 with a single committed checkpoint never deletes it
    rt2 = ResilientTrainer(_build_trainer(), auto_resume=False,
                           checkpoint_dir=str(tmp_path / "ck1"),
                           checkpoint_every=1, keep_last=1)
    x, y = _batches(1)[0]
    rt2.step(x, y)
    rt2.flush()
    assert len(ShardedTrainer.committed_checkpoints(
        str(tmp_path / "ck1"))) == 1


# -- (c) crash-safe resume, bit-for-bit (satellite 4) -----------------------

def test_crash_resume_bit_identical(tmp_path):
    """Train 5 steps with periodic checkpoints, 'crash', resume in a fresh
    process-state trainer, finish to 6 — params, optimizer state, update
    counter and RNG stream must match the uninterrupted 6-step run
    bit-for-bit (including dropout masks)."""
    import jax
    ckdir = str(tmp_path / "ck")
    bs = _batches(6, seed=5)

    # interrupted run: checkpoints commit at t=2 and t=4, crash at t=5
    rt_a = ResilientTrainer(_build_trainer(seed=42), checkpoint_dir=ckdir,
                            checkpoint_every=2, auto_resume=False)
    for x, y in bs[:5]:
        rt_a.step(x, y)
    rt_a.trainer.wait_checkpoint()           # crash: nothing after t=4 lands

    # uninterrupted reference run (same seed, same batches, no ckpt dir)
    rt_c = ResilientTrainer(_build_trainer(seed=42), auto_resume=False)
    for x, y in bs:
        rt_c.step(x, y)
    p_c, s_c = _params(rt_c.trainer), _opt_state(rt_c.trainer)
    rng_c = np.asarray(jax.device_get(mx.random.get_state()))

    # debris a real crash leaves: a torn step dir and orbax tmp staging,
    # both newer than the last committed checkpoint
    os.mkdir(os.path.join(ckdir, "state-00000005"))
    with open(os.path.join(ckdir, "state-00000005", "junk"), "w") as f:
        f.write("torn")
    os.mkdir(os.path.join(ckdir, "state-00000004.orbax-checkpoint-tmp-9"))

    # resume: DIFFERENT seed proves params/opt/t/rng all come from the
    # checkpoint, not from this process's init
    rt_b = ResilientTrainer(_build_trainer(seed=123), checkpoint_dir=ckdir,
                            checkpoint_every=2, auto_resume=True)
    x, y = bs[4]
    rt_b.step(x, y)                          # auto-resume from t=4, then t=5
    assert rt_b.resumed_t == 4 and rt_b.counters["resumes"] == 1
    assert rt_b.trainer.num_update == 5
    rt_b.step(*bs[5])
    assert rt_b.trainer.num_update == 6
    p_b, s_b = _params(rt_b.trainer), _opt_state(rt_b.trainer)
    rng_b = np.asarray(jax.device_get(mx.random.get_state()))

    for a, b in zip(p_c, p_b):
        assert np.array_equal(a, b)
    for a, b in zip(s_c, s_b):
        assert np.array_equal(a, b)
    assert np.array_equal(rng_c, rng_b)      # RNG stream restored
    rt_b.flush()


# -- (d) SIGTERM -> checkpoint-and-raise ------------------------------------

def test_sigterm_flushes_checkpoint_before_exit(tmp_path):
    ckdir = str(tmp_path / "ck")
    rt = ResilientTrainer(_build_trainer(), checkpoint_dir=ckdir,
                          auto_resume=False)
    rt.install_signal_handlers()
    try:
        x, y = _batches(1)[0]
        rt.step(x, y)
        os.kill(os.getpid(), signal.SIGTERM)
        with pytest.raises(TrainingPreempted, match="signal"):
            rt.step(x, y)
    finally:
        rt.uninstall_signal_handlers()
    # the preemption checkpoint is already COMMITTED (flushed, not async)
    latest = ShardedTrainer.latest_checkpoint(ckdir)
    assert latest is not None and latest.endswith("state-00000001")
    assert rt.preempted


def test_sigterm_with_failing_checkpoint_still_raises_preempted(tmp_path):
    """A failed preemption save must still surface as TrainingPreempted —
    never as a retryable TransientFault (a retrying caller would resume
    stepping with the SIGTERM swallowed)."""
    rt = ResilientTrainer(_build_trainer(), checkpoint_dir=str(tmp_path),
                          fault_plan="ckpt_fail@1", auto_resume=False)
    rt.install_signal_handlers()
    try:
        x, y = _batches(1)[0]
        rt.step(x, y)
        os.kill(os.getpid(), signal.SIGTERM)
        with pytest.raises(TrainingPreempted, match="FAILED"):
            rt.step(x, y)
    finally:
        rt.uninstall_signal_handlers()
    assert rt.counters["checkpoints_failed"] == 1


def test_checkpoint_guard_cross_compatibility(tmp_path):
    """Guard-on trainers restore guard-less checkpoints and vice versa
    (the template follows what the checkpoint CONTAINS, not this
    trainer's configuration)."""
    x, y = _batches(1)[0]
    # guard-less save -> guard-on restore
    plain = _build_trainer(seed=9)
    plain.step(x, y)
    plain.save_checkpoint(str(tmp_path / "a"))
    plain.wait_checkpoint()
    guarded = ResilientTrainer(_build_trainer(seed=10), auto_resume=False)
    guarded.step(x, y)
    guarded.trainer.load_checkpoint(str(tmp_path / "a"))
    assert guarded.trainer.num_update == 1
    # guard-on save -> guard-less restore
    guarded.trainer.save_checkpoint(str(tmp_path / "b"))
    guarded.trainer.wait_checkpoint()
    plain2 = _build_trainer(seed=11)
    plain2.step(x, y)
    plain2.load_checkpoint(str(tmp_path / "b"))
    assert plain2.num_update == 1


def test_exit_flush_hook_is_shared_and_weak(tmp_path):
    import gc
    import weakref
    from mxnet_tpu.parallel import resilience as res
    rt1 = ResilientTrainer(_build_trainer(), auto_resume=False,
                           checkpoint_dir=str(tmp_path / "a"))
    rt2 = ResilientTrainer(_build_trainer(), auto_resume=False,
                           checkpoint_dir=str(tmp_path / "b"))
    assert rt1.trainer in res._exit_flush_trainers
    assert rt2.trainer in res._exit_flush_trainers
    # WeakSet: dropping the supervisor must not pin the trainer (and its
    # device arrays) for the life of the process
    ref = weakref.ref(rt1.trainer)
    del rt1
    gc.collect()
    assert ref() is None
    assert rt2.trainer in res._exit_flush_trainers


# -- DataLoader failure paths (satellite 3) ---------------------------------

class _FlakyFirstBatch:
    """Sample 0 fails on its first access only (a transient I/O blip)."""

    def __init__(self, n=8):
        self._n = n
        self._failed = False

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        if i == 0 and not self._failed:
            self._failed = True
            raise OSError("flaky read")
        return np.full((2,), i, np.float32)


def test_dataloader_timeout_names_worker_and_batch():
    data = [np.full((2,), i, np.float32) for i in range(8)]
    faults.set_fault_plan("loader_stall@1:3.0")
    try:
        dl = DataLoader(data, batch_size=2, num_workers=1, timeout=0.5)
        with pytest.raises(MXNetError,
                           match=r"waiting for batch 0.*stalled workers"):
            list(dl)
    finally:
        faults.set_fault_plan(None)


def test_dataloader_worker_retry_recovers():
    dl = DataLoader(_FlakyFirstBatch(), batch_size=2, num_workers=2,
                    worker_retries=1)
    got = list(dl)
    assert len(got) == 4
    # order and contents preserved through the retry
    assert np.allclose(got[0].asnumpy()[1], 1.0)
    assert np.allclose(got[3].asnumpy()[1], 7.0)

    dl0 = DataLoader(_FlakyFirstBatch(), batch_size=2, num_workers=2)
    with pytest.raises(MXNetError,
                       match=r"worker .* failed on batch 0"):
        list(dl0)


def test_dataloader_broken_dataset_not_retried():
    """Non-transient failures (a broken dataset) surface after ONE
    attempt even with retries configured — only flaky-I/O-shaped errors
    burn the retry budget."""

    class Broken:
        def __len__(self):
            return 4

        def __getitem__(self, i):
            raise ValueError("dataset is just broken")

    dl = DataLoader(Broken(), batch_size=2, num_workers=1,
                    worker_retries=3)
    with pytest.raises(MXNetError, match=r"after 1 attempt"):
        list(dl)


def test_dataloader_injected_worker_error_retried():
    data = [np.full((2,), i, np.float32) for i in range(8)]
    faults.set_fault_plan("loader_error@3")
    try:
        dl = DataLoader(data, batch_size=2, num_workers=2, worker_retries=1)
        assert len(list(dl)) == 4
        assert faults.active_plan().empty   # the fault actually fired
    finally:
        faults.set_fault_plan(None)


# -- dist bootstrap failure paths (satellite 2) -----------------------------

def test_init_process_group_names_missing_env(monkeypatch):
    from mxnet_tpu.parallel import dist
    if dist.is_initialized():
        pytest.skip("process group already initialized")
    for k in list(os.environ):
        if k.startswith("DMLC_"):
            monkeypatch.delenv(k)
    with pytest.raises(MXNetError, match="DMLC_PS_ROOT_URI"):
        dist.init_process_group(num_processes=2, process_id=0)
    with pytest.raises(MXNetError, match="DMLC_NUM_WORKER"):
        dist.init_process_group(coordinator="127.0.0.1:9", process_id=0)
    # the kvstore entry point contract: message still names the process
    # group (tests/test_dist.py matches on it)
    with pytest.raises(MXNetError, match="process group"):
        dist.init_process_group(process_id=0)


def test_init_process_group_retries_then_clear_error(monkeypatch):
    import jax
    from mxnet_tpu.parallel import dist
    if dist.is_initialized():
        pytest.skip("process group already initialized")
    calls = {"n": 0, "shutdowns": 0}

    def fake_initialize(**kw):
        calls["n"] += 1
        assert kw["initialization_timeout"] == 1
        raise RuntimeError("coordinator unreachable")

    def fake_shutdown():
        calls["shutdowns"] += 1

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(jax.distributed, "shutdown", fake_shutdown)
    with pytest.raises(MXNetError,
                       match=r"could not join .* rank 0/2 after 3"):
        dist.init_process_group("127.0.0.1:1", 2, 0, timeout=1,
                                retries=2, backoff=0.001)
    assert calls["n"] == 3                   # 1 try + 2 backoff retries
    # jax leaves its global client assigned on a failed connect; without a
    # shutdown between attempts every retry dies on 'only be called once'
    assert calls["shutdowns"] == 3

    # coordinator coming up AFTER the worker: fail once, then join
    calls["n"] = 0

    def flaky_initialize(**kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("not up yet")

    monkeypatch.setattr(jax.distributed, "initialize", flaky_initialize)
    dist.init_process_group("127.0.0.1:1", 2, 0, timeout=1,
                            retries=2, backoff=0.001)
    assert calls["n"] == 2


# -- coordinated preemption checkpoints (multi-process) ---------------------

_PREEMPT_WORKER = r'''
import os, signal, sys, time
sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
from mxnet_tpu.base import force_cpu_mesh
force_cpu_mesh(1, verify=False)   # distributed init must precede the
import numpy as np                # first backend query
import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.parallel import dist
from mxnet_tpu.parallel.resilience import ResilientTrainer, \
    TrainingPreempted
from mxnet_tpu.gluon import nn, loss as gloss

dist.init_process_group()
rank = dist.rank()
np.random.seed(0)
mx.random.seed(0)
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
net.initialize()
import jax
tr = par.ShardedTrainer(
    net, gloss.SoftmaxCrossEntropyLoss(), "sgd", {"learning_rate": 0.1},
    mesh=par.make_mesh({"dp": 1}, devices=jax.local_devices()[:1]))
ckpt = os.path.join(os.environ["CKPT_ROOT"], f"rank{rank}")
rt = ResilientTrainer(tr, checkpoint_dir=ckpt, auto_resume=False)
rt.install_signal_handlers()
x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
y = np.random.RandomState(1).randint(0, 4, (8,))
# deliberately UNEQUAL step cadence: at SIGTERM time the two hosts sit
# at different update counters — exactly the skew the coordination
# protocol must resolve into one agreed flush step
delay = 0.02 if rank == 0 else 0.06
try:
    for i in range(600):
        rt.step(x, y)
        if i == 2:   # both hosts demonstrably stepping before the signal
            open(os.path.join(os.environ["CKPT_ROOT"],
                              f"ready-{rank}"), "w").close()
        time.sleep(delay)
    print(f"NOT_PREEMPTED_{rank}", flush=True)
    sys.exit(2)
except TrainingPreempted:
    newest = par.ShardedTrainer.latest_checkpoint(ckpt)
    name = os.path.basename(newest) if newest else "NONE"
    print(f"PREEMPTED_{rank} t={tr.num_update} ckpt={name}", flush=True)
'''


@pytest.mark.parametrize("async_ckpt", ["0", "1"])
def test_coordinated_preemption_two_procs(tmp_path, async_ckpt):
    """SIGTERM one of two workers: BOTH must exit preempted and commit
    the SAME `state-<t>` checkpoint — the flush step agreed over the
    coordination-service KV tier (max of the hosts' votes), not each
    host's own next boundary (PR-1 carried follow-up).

    Parametrized over MXTPU_ASYNC_CKPT: '1' routes the vote wait
    through the background _AsyncVoteRound (hosts keep stepping toward
    the highest vote seen instead of parking) — the agreed-state
    invariant must hold identically on both paths."""
    import re
    import socket
    import subprocess
    import sys as _sys
    import time as _time

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    script = tmp_path / "worker.py"
    script.write_text(_PREEMPT_WORKER)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.update({
            "MXNET_TEST_ROOT": root,
            "CKPT_ROOT": str(tmp_path),
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": "2",
            "DMLC_WORKER_ID": str(r),
            "MXTPU_ASYNC_CKPT": async_ckpt,
        })
        procs.append(subprocess.Popen(
            [_sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    # SIGTERM rank 0 only — but not before both hosts are demonstrably
    # stepping (a pre-handler signal would just kill the process)
    deadline = _time.monotonic() + 120
    while _time.monotonic() < deadline:
        if all(os.path.exists(tmp_path / f"ready-{r}") for r in range(2)):
            break
        if any(p.poll() is not None for p in procs):
            break
        _time.sleep(0.05)
    _time.sleep(0.3)
    procs[0].send_signal(signal.SIGTERM)
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((r, p.returncode, out))
    records = {}
    for r, rc, out in outs:
        assert rc == 0, f"worker {r} rc={rc}:\n{out}"
        m = re.search(rf"PREEMPTED_{r} t=(\d+) ckpt=(state-\d+)", out)
        assert m, f"worker {r} never reported a preemption flush:\n{out}"
        records[r] = (int(m.group(1)), m.group(2))
    # the satellite's whole point: ONE agreed step, fleet-wide
    assert records[0] == records[1], records
    t, name = records[0]
    assert name == f"state-{t:08d}"
    # the agreed step is COMMITTED in both hosts' checkpoint dirs
    for r in range(2):
        assert os.path.exists(tmp_path / f"rank{r}" / name /
                              "_CHECKPOINT_METADATA")


# -- async distributed checkpoint (MXTPU_ASYNC_CKPT) ------------------------

def _host_local_trainer(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    tr = ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9})
    tr.host_local_ckpt = True        # force the npz writer in 1 process
    return tr


def test_async_ckpt_commit_off_step_path(tmp_path, monkeypatch):
    """With MXTPU_ASYNC_CKPT the npz write + commit rename run on a
    background thread: save_checkpoint returns with the write in
    flight (inflight gauge 1, commit histogram grows only after the
    wait), and the committed checkpoint restores a bit-identical
    continuation — same contract as the synchronous path."""
    from mxnet_tpu.observability.registry import registry
    monkeypatch.setenv("MXTPU_ASYNC_CKPT", "1")
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (8,))
    tr = _host_local_trainer()
    for _ in range(3):
        tr.step(x, y)
    h = registry().histogram("ckpt.async_commit_us")
    n0 = h.count
    # gate the background writer so the in-flight window is observable
    # deterministically (on a tiny model the commit can otherwise win
    # the race and land before the gauge assertion runs)
    gate = threading.Event()
    real_write = ShardedTrainer._write_host_local

    def gated_write(*a, **kw):
        assert gate.wait(30)
        return real_write(*a, **kw)

    monkeypatch.setattr(ShardedTrainer, "_write_host_local",
                        staticmethod(gated_write))
    tr.save_checkpoint(str(tmp_path))
    assert registry().gauge("resilience.ckpt_inflight").value == 1
    assert h.count == n0          # commit strictly after the wait
    gate.set()
    tr.wait_checkpoint()
    assert registry().gauge("resilience.ckpt_inflight").value == 0
    assert h.count == n0 + 1
    assert os.path.basename(
        ShardedTrainer.latest_checkpoint(str(tmp_path))) \
        == "state-00000003"
    loss_a = tr.step(x, y)

    tr2 = _host_local_trainer(seed=9)    # different weights: restore wins
    tr2.step(x, y)
    tr2.load_checkpoint(str(tmp_path))
    assert tr2.num_update == 3
    loss_b = tr2.step(x, y)
    assert float(loss_a.asnumpy()) == float(loss_b.asnumpy())


def test_async_ckpt_writer_error_surfaces_at_wait(tmp_path, monkeypatch):
    """A failed background write must raise at the next explicit flush
    (wait_checkpoint), not vanish with the thread — and never into the
    training step itself."""
    monkeypatch.setenv("MXTPU_ASYNC_CKPT", "1")
    tr = _host_local_trainer()
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (8,))
    tr.step(x, y)

    def boom(flat, tmp, final):
        raise OSError("disk gone")

    monkeypatch.setattr(ShardedTrainer, "_write_host_local",
                        staticmethod(boom))
    tr.save_checkpoint(str(tmp_path))
    tr.step(x, y)                        # the step path stays clean
    tr.save_checkpoint(str(tmp_path))    # a periodic save after the
    # failure drains the dead writer WITHOUT raising (the previous
    # committed dir is intact — the step path must keep going)
    with pytest.raises(MXNetError, match="async host-local checkpoint"):
        tr.wait_checkpoint()             # ...the explicit flush raises
    tr.wait_checkpoint()                 # error consumed, not sticky


_ASYNC_TORN_WORKER = r'''
import os, sys, time
sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon import nn, loss as gloss

np.random.seed(0); mx.random.seed(0)
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
net.initialize()
tr = par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9})
tr.host_local_ckpt = True
x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
y = np.random.RandomState(1).randint(0, 4, (8,))
ckpt = os.environ["CKPT_ROOT"]
tr.step(x, y)
tr.save_checkpoint(ckpt)               # ckpt #1, async
tr.wait_checkpoint()                   # ...committed
tr.step(x, y)
# die DURING ckpt #2's background write: the npz lands in the tmp dir,
# the commit marker and the atomic rename never happen
real_savez = np.savez
def dying_savez(path, **kw):
    real_savez(path, **kw)
    os._exit(17)
np.savez = dying_savez
tr.save_checkpoint(ckpt)
time.sleep(60)                         # never reached: the writer kills us
'''


def test_async_ckpt_crash_mid_write_leaves_committed(tmp_path):
    """The torn-dir filter test of the async-checkpoint acceptance: a
    crash mid-background-write leaves ONLY an uncommitted tmp partial
    behind; resume sees exactly the previous committed state-<t>."""
    import subprocess
    import sys as _sys
    script = tmp_path / "worker.py"
    script.write_text(_ASYNC_TORN_WORKER)
    ckpt_root = tmp_path / "ckpt"
    env = dict(os.environ,
               MXNET_TEST_ROOT=os.path.dirname(
                   os.path.dirname(os.path.abspath(__file__))),
               CKPT_ROOT=str(ckpt_root),
               MXTPU_ASYNC_CKPT="1",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([_sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 17, (r.returncode, r.stdout, r.stderr)
    entries = sorted(os.listdir(ckpt_root))
    assert "state-00000001" in entries
    torn = [d for d in entries if ".mxtpu-tmp-" in d]
    assert torn and torn[0].startswith("state-00000002"), entries
    # the partial carries DATA but no commit marker — and the filters
    # never serve it
    assert os.path.exists(ckpt_root / torn[0] / "host_local.npz")
    assert not os.path.exists(ckpt_root / torn[0] /
                              "_CHECKPOINT_METADATA")
    committed = ShardedTrainer.committed_checkpoints(str(ckpt_root))
    assert [os.path.basename(p) for p in committed] == \
        ["state-00000001"]
    tr = _host_local_trainer()
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (8,))
    tr.step(x, y)
    tr.load_checkpoint(str(ckpt_root))
    assert tr.num_update == 1


# -- lint gate: no bare except under mxnet_tpu/ (satellite 6) ---------------
# The AST walker that used to live here moved into the mxlint subsystem
# (mxnet_tpu/tools/mxlint — the 'bare-except' rule); this thin assertion
# rides the suite's single cached lint pass.

def test_no_bare_except_in_package():
    from mxnet_tpu.tools import mxlint
    assert mxlint.rule_findings("bare-except") == []
