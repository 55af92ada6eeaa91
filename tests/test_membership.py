"""Elastic-fleet acceptance suite: membership leases, host-loss
detection, and the automatic re-form/resume arc (all CPU, tier-1).

The headline test SIGKILLs one of three workers mid-run and asserts the
survivors detect the loss within a lease TTL, re-form at world size 2,
resume from the last committed checkpoint, and reach a final state
bit-identical to a clean 2-process run resumed from that same
checkpoint — no operator action, no hung collective.  Around it:
lease-expiry math, the reaper's purge of dead-host KV generations,
deterministic ``host_loss``/``heartbeat_stall`` fault firing, the
false-death fencing (split-brain) case, bounded KV waits, and the
shard-aware loader position cursor.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_workers(script_path, n_workers, env_common, env_per_rank=None,
                 timeout=240):
    """Launch n coordinated workers; returns [(rank, rc, output)]."""
    port = _free_port()
    procs = []
    for r in range(n_workers):
        env = dict(os.environ)
        env.update({
            "MXNET_TEST_ROOT": REPO,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(n_workers),
            "DMLC_WORKER_ID": str(r),
        })
        env.update(env_common)
        if env_per_rank and r in env_per_rank:
            env.update(env_per_rank[r])
        procs.append(subprocess.Popen(
            [sys.executable, str(script_path)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((r, p.returncode, out))
    return outs


# -- the shared elastic training worker --------------------------------------
#
# Deterministic by construction: synthetic dataset whose every value is
# a pure function of the sample index, fixed seeds, sequential sampler,
# exact-mode dispatch.  Each process trains its own replica on its
# "dist"-sharded batch stripe with a per-step bounded fleet sync; hosts
# checkpoint every 2 updates (plus the loader-cursor sidecar).

_ELASTIC_WORKER = textwrap.dedent("""
    import hashlib, json, os, shutil, sys, time
    sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
    from mxnet_tpu.base import force_cpu_mesh
    force_cpu_mesh(1, verify=False)   # distributed init precedes the
    import numpy as np                # first backend query
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.parallel import (dist, FleetReformed, HostFenced,
                                    ResilientTrainer, ShardedTrainer)
    from mxnet_tpu.observability.flight import recorder
    from mxnet_tpu.observability.registry import registry

    dist.init_process_group()
    phys = dist.phys_rank()
    TARGET = int(os.environ["ELASTIC_TARGET_T"])
    STOP_AFTER_REFORM = os.environ.get("ELASTIC_STOP_AFTER_REFORM") == "1"
    STEP_SLEEP = float(os.environ.get("ELASTIC_STEP_SLEEP", "0"))
    root = os.environ["ELASTIC_CKPT_ROOT"]
    suffix = os.environ.get("ELASTIC_CKPT_SUFFIX", "")
    ckpt_dir = os.path.join(root, "rank%d%s" % (phys, suffix))
    frozen_dir = os.path.join(root, "rank%d_frozen" % phys)

    N, F, C = 256, 8, 4
    def sample(i):
        x = ((np.arange(F) * 7 + i * 13) % 97).astype(np.float32) / 97.0
        return x, np.int32(i % C)
    ds = [sample(i) for i in range(N)]
    loader = DataLoader(ds, batch_size=8, num_shards="dist")

    mx.random.seed(11)
    np.random.seed(11)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=F))
        net.add(nn.Dense(C, in_units=16))
    net.initialize()
    # each host trains its own replica on its batch stripe: the mesh is
    # LOCAL devices (cross-host sync rides the dist KV plane; the CPU
    # backend cannot run device collectives across processes anyway)
    import jax
    from mxnet_tpu.parallel.mesh import make_mesh
    local_mesh = make_mesh({"dp": 1}, devices=jax.local_devices()[:1])
    trainer = ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9},
                             mesh=local_mesh)
    rt = ResilientTrainer(trainer, checkpoint_dir=ckpt_dir,
                          checkpoint_every=2, keep_last=20,
                          elastic=True, loader=loader,
                          skip_nonfinite=False)
    probe = (np.zeros((8, F), np.float32), np.zeros((8,), np.int32))
    rt.maybe_resume(*probe)
    if rt.resumed_t is not None:
        print("RESUMED_%d_t%d" % (phys, rt.resumed_t), flush=True)

    target = TARGET
    done = False
    while not done:
        try:
            for x, y in loader:
                rt.step(x, y)
                if STEP_SLEEP:
                    time.sleep(STEP_SLEEP)
                if trainer.num_update >= target:
                    done = True
                    break
        except FleetReformed as e:
            r = e.result
            print("REFORMED_%d world=%d rank=%d resumed_t=%s" %
                  (phys, r.new_world, r.new_rank, r.resumed_t),
                  flush=True)
            if not os.path.isdir(frozen_dir):
                # snapshot the checkpoints AS OF the re-form so the
                # clean-run comparison starts from the same bytes
                shutil.copytree(ckpt_dir, frozen_dir)
            if STOP_AFTER_REFORM:
                target = trainer.num_update + 3
            continue
        except HostFenced:
            print("FENCED_%d" % phys, flush=True)
            sys.exit(3)

    rt.flush()
    import jax
    blob = b"".join(np.ascontiguousarray(np.asarray(v)).tobytes()
                    for v in jax.device_get(trainer._pvals))
    digest = hashlib.sha256(blob).hexdigest()

    if os.environ.get("ELASTIC_EXPECT_REFORM") == "1":
        assert dist.num_workers() == 2, dist.num_workers()
        assert registry().counter("dist.membership.reforms").n >= 1
        assert registry().counter("dist.membership.expired").n >= 1
        assert registry().gauge("dist.membership.world").value == 2
        assert registry().gauge("dist.membership.fence").value >= 1
        events = [m.get("event") for m in recorder().memberships()]
        for ev in ("suspect", "quiesce", "reform", "resume"):
            assert ev in events, events
        # the dead host's lease generations were purged by the leader
        from mxnet_tpu.parallel import membership as ms
        dead = int(os.environ["ELASTIC_DEAD_RANK"])
        assert dead not in dist.kv_collect(ms.LEASE_PREFIX)
        path = recorder().dump(
            "elastic-test-done",
            os.path.join(root, "flight_rank%d.json" % phys))
        assert path is not None
        # post-re-form the narrowed collectives still work end to end
        fleet = dist.allgather_host(np.array([float(phys)]))
        assert fleet.shape[0] == 2, fleet

    dist.barrier("elastic_done", timeout=60)
    print("FINAL_%d t=%d sha=%s" % (phys, trainer.num_update, digest),
          flush=True)
    print("WORKER_%d_OK" % phys, flush=True)
""")

_ELASTIC_ENV = {
    "MXTPU_ELASTIC": "1",
    "MXTPU_ELASTIC_LEASE_TTL": "1.5",
    "MXTPU_ELASTIC_HEARTBEAT": "0.3",
    "MXTPU_ELASTIC_REFORM_TIMEOUT": "45",
    "MXTPU_DIST_TIMEOUT": "20",
}


def _final_sha(out, rank):
    lines = [ln for ln in out.splitlines()
             if ln.startswith(f"FINAL_{rank} ")]
    assert lines, out
    return lines[-1].split("sha=")[1].strip()


def test_host_kill_reform_resume_bitwise(tmp_path):
    """THE acceptance test: 3 workers, rank 2 SIGKILLs itself at step 5
    (the host_loss fault — indistinguishable from machine loss).  The
    survivors must re-form at world size 2, resume from the step-4
    committed checkpoint, finish training, and match a clean 2-process
    run resumed from the same checkpoint bit for bit."""
    script = tmp_path / "elastic_worker.py"
    script.write_text(_ELASTIC_WORKER)
    root = str(tmp_path / "fleet")
    env = dict(_ELASTIC_ENV, ELASTIC_TARGET_T="10", ELASTIC_CKPT_ROOT=root,
               ELASTIC_EXPECT_REFORM="1", ELASTIC_DEAD_RANK="2")
    outs = _run_workers(script, 3, env, env_per_rank={
        2: {"MXTPU_FAULT_PLAN": "host_loss@5",
            "ELASTIC_EXPECT_REFORM": "0"}})
    by_rank = {r: (rc, out) for r, rc, out in outs}
    # the victim died by SIGKILL, mid-run, with no output after step 5
    rc2, out2 = by_rank[2]
    assert rc2 == -signal.SIGKILL, (rc2, out2)
    assert "WORKER_2_OK" not in out2
    # both survivors re-formed at world 2 and resumed from step 4
    for r in (0, 1):
        rc, out = by_rank[r]
        assert rc == 0, f"survivor {r} failed:\n{out}"
        assert f"REFORMED_{r} world=2" in out, out
        assert "resumed_t=4" in out, out
        assert f"WORKER_{r}_OK" in out, out
        assert "t=10" in out, out

    # the re-form timeline (detect -> quiesce -> reform -> resume, with
    # timestamps) landed in the flight-recorder dump
    with open(os.path.join(root, "flight_rank0.json")) as f:
        dump = json.load(f)
    assert dump["n_membership"] >= 3
    events = {m["event"]: m for m in dump["membership"]}
    for ev in ("suspect", "quiesce", "reform", "resume"):
        assert ev in events, list(events)
        assert events[ev].get("ts"), events[ev]
    timeline = dict(events["reform"]["timeline"])
    assert "detect" in timeline and "reformed" in timeline
    assert timeline["reformed"] >= timeline["detect"]
    assert events["reform"]["members"] == [0, 1]
    assert events["reform"]["dead"] == [2]

    # the clean comparison run: 2 fresh workers, world size 2 from the
    # START, resuming the frozen (as-of-re-form) checkpoints
    script_b = tmp_path / "elastic_worker_b.py"
    script_b.write_text(_ELASTIC_WORKER)
    env_b = dict(_ELASTIC_ENV, ELASTIC_TARGET_T="10",
                 ELASTIC_CKPT_ROOT=root, ELASTIC_CKPT_SUFFIX="_frozen")
    outs_b = _run_workers(script_b, 2, env_b)
    for r, rc, out in outs_b:
        assert rc == 0, f"clean-run worker {r} failed:\n{out}"
        assert f"RESUMED_{r}_t4" in out, out
        assert f"WORKER_{r}_OK" in out, out
        # bit-identical final state vs the surviving fleet
        assert _final_sha(out, r) == _final_sha(by_rank[r][1], r), \
            f"rank {r} diverged from the clean 2-process run"


@pytest.mark.parametrize(
    "stall_rank",
    [1, pytest.param(0, marks=pytest.mark.slow)])
def test_heartbeat_stall_fences_false_death(tmp_path, stall_rank):
    """The split-brain case: one rank's lease publisher freezes at step
    3 while the process keeps stepping.  The peers must reap it and
    re-form WITHOUT it (fencing generation bump); the stalled host must
    discover the fence and exit — never rejoin.  ``stall_rank=0`` is
    the nastier variant: the stalled host is the LOWEST rank, so when
    it joins the peer-opened re-form round it is min() of its own view
    — it must refuse to elect itself leader and author a plan that
    re-admits itself (every peer's view excludes it)."""
    script = tmp_path / "elastic_worker.py"
    script.write_text(_ELASTIC_WORKER)
    root = str(tmp_path / "fleet")
    survivors = sorted({0, 1, 2} - {stall_rank})
    env = dict(_ELASTIC_ENV, ELASTIC_TARGET_T="4000",
               ELASTIC_CKPT_ROOT=root, ELASTIC_EXPECT_REFORM="1",
               ELASTIC_DEAD_RANK=str(stall_rank),
               ELASTIC_STOP_AFTER_REFORM="1",
               ELASTIC_STEP_SLEEP="0.05")
    outs = _run_workers(script, 3, env, env_per_rank={
        stall_rank: {"MXTPU_FAULT_PLAN": "heartbeat_stall@3",
                     "ELASTIC_EXPECT_REFORM": "0"}})
    by_rank = {r: (rc, out) for r, rc, out in outs}
    rc_s, out_s = by_rank[stall_rank]
    assert rc_s == 3, (rc_s, out_s)          # fenced, exited, no rejoin
    assert f"FENCED_{stall_rank}" in out_s, out_s
    assert f"WORKER_{stall_rank}_OK" not in out_s
    for r in survivors:
        rc, out = by_rank[r]
        assert rc == 0, f"survivor {r} failed:\n{out}"
        assert f"REFORMED_{r} world=2" in out, out
        assert f"WORKER_{r}_OK" in out, out


# -- deterministic host-fault firing ----------------------------------------

def test_host_fault_plan_grammar():
    from mxnet_tpu.faults import FaultPlan
    plan = FaultPlan("host_loss@5;heartbeat_stall@3:2.5")
    assert plan.scheduled("host_loss", 4) is None
    spec = plan.scheduled("host_loss", 5)
    assert spec.kind == "host_loss" and spec.arg is None
    assert plan.scheduled("host_loss", 5) is None    # consumed once
    stall = plan.scheduled("heartbeat_stall", 3)
    assert stall.arg == 2.5
    assert plan.empty


def test_host_loss_fires_deterministically(tmp_path):
    """host_loss@3 hard-kills the process at supervisor step 3 exactly:
    steps 1-2 complete, step 3 never returns, exit is SIGKILL (no
    flush, no atexit — a machine loss, not a shutdown)."""
    script = tmp_path / "host_loss_worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
        from mxnet_tpu.base import force_cpu_mesh
        force_cpu_mesh(1, verify=False)
        import numpy as np
        import mxnet_tpu as mx
        from mxnet_tpu.gluon import nn, loss as gloss
        from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer
        mx.random.seed(0); np.random.seed(0)
        net = nn.Dense(4, in_units=8); net.initialize()
        rt = ResilientTrainer(
            ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                           {"learning_rate": 0.1}),
            fault_plan="host_loss@3", skip_nonfinite=False)
        x = np.zeros((4, 8), np.float32)
        y = np.zeros((4,), np.int32)
        for i in range(1, 6):
            rt.step(x, y)
            print("STEP_%d_DONE" % i, flush=True)
    """))
    env = dict(os.environ, MXNET_TEST_ROOT=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)
    assert "STEP_2_DONE" in r.stdout
    assert "STEP_3_DONE" not in r.stdout


def test_heartbeat_stall_requires_membership():
    """heartbeat_stall with no membership layer attached is a clear
    error, not a silent no-op (the fault would otherwise 'pass' without
    testing anything)."""
    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    rt = ResilientTrainer(
        ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                       {"learning_rate": 0.1}),
        fault_plan="heartbeat_stall@1", skip_nonfinite=False)
    with pytest.raises(MXNetError, match="membership"):
        rt.step(np.zeros((4, 8), np.float32), np.zeros((4,), np.int32))


# -- lease-expiry math (pure, no process group) ------------------------------

def test_lease_tracker_expiry_math():
    from mxnet_tpu.parallel.membership import LeaseTracker
    lt = LeaseTracker(2.0)
    lt.track(1, now=10.0)
    lt.track(2, now=10.0)
    # never-heartbeated ranks age from track time
    assert lt.expired(now=11.9) == []
    assert lt.expired(now=12.1) == [1, 2]
    # a fresh sequence resets the clock
    assert lt.observe(1, seq=1, now=12.1)
    assert lt.expired(now=13.0) == [2]
    # the SAME sequence re-observed does NOT refresh the lease (that is
    # the whole point: a frozen publisher keeps re-serving its last key)
    assert not lt.observe(1, seq=1, now=14.0)
    assert lt.expired(now=14.2) == [1, 2]
    # regressing sequences (a restarted predecessor's stale key) ignored
    assert not lt.observe(1, seq=0, now=14.0)
    # advancing revives
    assert lt.observe(2, seq=9, now=14.0)
    assert lt.expired(now=15.0) == [1]
    assert lt.age(2, now=15.0) == 1.0
    lt.forget(1)
    assert lt.expired(now=100.0) == [2]
    with pytest.raises(Exception):
        LeaseTracker(0.0)


# -- reaper purge + bounded KV waits (1-process coordination service) --------

def test_purge_and_bounded_waits(tmp_path):
    """In a real (1-process) coordination service: kv_purge_rank removes
    exactly the dead rank's generations across both key shapes, and a
    KV-path collective waiting on an absent member raises the typed
    DeadlineExceeded instead of hanging."""
    script = tmp_path / "purge_worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
        from mxnet_tpu.base import force_cpu_mesh
        force_cpu_mesh(1, verify=False)
        import jax
        jax.distributed.initialize("127.0.0.1:%s" % os.environ["KV_PORT"],
                                   num_processes=1, process_id=0)
        from jax._src import distributed
        from mxnet_tpu.faults import DeadlineExceeded
        from mxnet_tpu.parallel import dist

        client = distributed.global_state.client
        # dead rank 7's state in both per-rank key shapes + a survivor's
        client.key_value_set("mxtpu/member/lease/7/000000000003", "x")
        client.key_value_set("mxtpu/member/lease/1/000000000002", "x")
        client.key_value_set("mxtpu/fleet/7/000000000001", "x")
        client.key_value_set("mxtpu/agb/0/5/7", "x")
        client.key_value_set("mxtpu/agb/0/5/1", "x")
        n = 0
        for prefix in ("mxtpu/member/lease", "mxtpu/fleet",
                       "mxtpu/agb/0"):
            n += dist.kv_purge_rank(prefix, 7)
        assert n == 3, n
        left = [k for k, _v in client.key_value_dir_get("mxtpu")]
        assert sorted(left) == ["mxtpu/agb/0/5/1",
                                "mxtpu/member/lease/1/000000000002"], left
        print("PURGE_OK", flush=True)

        # bounded wait: narrow the group to {0, 1}; rank 1 does not
        # exist, so the KV gather must raise the TYPED deadline fault
        # (never hang) naming the absent rank
        dist.set_active_members((0, 1), 1)
        t0 = time.monotonic()
        try:
            dist.allgather_bytes(b"payload", timeout=1.0)
        except DeadlineExceeded as e:
            took = time.monotonic() - t0
            assert took < 15, took
            assert "rank 1" in str(e), e
            print("DEADLINE_OK", flush=True)
        else:
            raise AssertionError("allgather over a dead rank returned")
    """))
    env = dict(os.environ, MXNET_TEST_ROOT=REPO, JAX_PLATFORMS="cpu",
               KV_PORT=str(_free_port()))
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PURGE_OK" in r.stdout
    assert "DEADLINE_OK" in r.stdout


def test_barrier_deadline_two_proc(tmp_path):
    """dist.barrier() with an absent peer raises DeadlineExceeded after
    the bounded timeout (the PR-9 bugfix: this used to wait forever on
    the coordination service)."""
    script = tmp_path / "barrier_worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
        from mxnet_tpu.base import force_cpu_mesh
        force_cpu_mesh(1, verify=False)
        from mxnet_tpu.faults import DeadlineExceeded
        from mxnet_tpu.parallel import dist
        dist.init_process_group()
        if dist.rank() == 0:
            try:
                dist.barrier("lonely", timeout=1.5)
            except DeadlineExceeded:
                print("BARRIER_DEADLINE_OK", flush=True)
            else:
                raise AssertionError("barrier returned without peer")
        else:
            time.sleep(5)   # never calls the barrier
        print("WORKER_%d_OK" % dist.rank(), flush=True)
    """))
    outs = _run_workers(script, 2, {"MXTPU_DIST_TIMEOUT": "20"})
    for r, rc, out in outs:
        assert rc == 0, f"worker {r}:\n{out}"
    assert "BARRIER_DEADLINE_OK" in outs[0][2]


# -- membership watcher internals (1-process group) --------------------------

def test_reaper_and_fence_discovery(tmp_path):
    """In a 1-process group: the reaper suspects a silent tracked peer
    after one TTL; a committed epoch record excluding this host flips it
    to fenced; stall_heartbeats freezes the publisher (the
    heartbeat_stall fault's mechanism)."""
    script = tmp_path / "reaper_worker.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys, time
        sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
        from mxnet_tpu.base import force_cpu_mesh
        force_cpu_mesh(1, verify=False)
        import jax
        jax.distributed.initialize("127.0.0.1:%s" % os.environ["KV_PORT"],
                                   num_processes=1, process_id=0)
        from jax._src import distributed
        from mxnet_tpu.parallel import dist
        from mxnet_tpu.parallel.membership import (HostFenced,
                                                   MembershipManager)

        m = MembershipManager(lease_ttl=0.6, heartbeat_interval=0.2)
        m.start()
        # a phantom peer the launcher promised but that never arrived:
        # track it; the reaper must suspect it after one TTL
        m._members = (0, 9)
        m._tracker.track(9, time.monotonic())
        deadline = time.monotonic() + 5
        while not m.reform_needed and time.monotonic() < deadline:
            time.sleep(0.1)
        assert m.reform_needed, "reaper never suspected the dead peer"
        assert m.suspects == (9,), m.suspects
        print("REAPER_OK", flush=True)

        # heartbeat publishing: counter advanced, then stall freezes it
        from mxnet_tpu.observability.registry import registry
        hb = registry().counter("dist.membership.heartbeats")
        before = hb.n
        time.sleep(0.7)
        assert hb.n > before, (hb.n, before)
        m.stall_heartbeats(None)     # forever
        time.sleep(0.5)
        frozen = hb.n
        time.sleep(0.7)
        assert hb.n == frozen, (hb.n, frozen)
        print("STALL_OK", flush=True)

        # fence discovery: a committed epoch record that excludes us
        client = distributed.global_state.client
        client.key_value_set("mxtpu/member/epoch/record", json.dumps(
            {"fence": 1, "members": [9]}), allow_overwrite=True)
        deadline = time.monotonic() + 5
        while not m.fenced and time.monotonic() < deadline:
            time.sleep(0.1)
        assert m.fenced, "fence record never discovered"
        try:
            m.raise_if_fenced()
        except HostFenced as e:
            assert "fenced out" in str(e)
            print("FENCE_OK", flush=True)
        m.stop()
    """))
    env = dict(os.environ, MXNET_TEST_ROOT=REPO, JAX_PLATFORMS="cpu",
               KV_PORT=str(_free_port()))
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("REAPER_OK", "STALL_OK", "FENCE_OK"):
        assert marker in r.stdout, r.stdout


# -- shard-aware loader position cursor (PR-1 carried follow-up) -------------

class _CountingDataset:
    """Counts __getitem__ calls: fast-forward must never build skipped
    batches."""

    def __init__(self, n):
        self.n = n
        self.reads = 0

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        self.reads += 1
        return np.float32([i])


def test_loader_shard_striping():
    from mxnet_tpu.gluon.data import DataLoader
    ds = [np.float32([i]) for i in range(24)]
    seen = []
    for s in range(3):
        dl = DataLoader(ds, batch_size=2, num_shards=3, shard_index=s)
        assert len(dl) == 4
        seen.append([int(b.asnumpy()[0, 0]) for b in dl])
    # round-robin batch striping: disjoint, union = every batch, batch
    # size unchanged
    assert seen[0] == [0, 6, 12, 18]
    assert seen[1] == [2, 8, 14, 20]
    assert seen[2] == [4, 10, 16, 22]


def test_loader_cursor_rewind_and_reshard():
    from mxnet_tpu.gluon.data import DataLoader
    ds = _CountingDataset(48)
    dl = DataLoader(ds, batch_size=2, num_shards=3, shard_index=0)
    it = iter(dl)
    for _ in range(4):
        next(it)
    state = dl.state_dict()
    assert state == {"epoch": 1, "batch": 4, "num_shards": 3,
                     "global": 12}
    # restore onto a DIFFERENT shard assignment (3 -> 2 shards): the
    # saved global position (4 * 3 = 12) maps to per-shard batch 6
    ds2 = _CountingDataset(48)
    dl2 = DataLoader(ds2, batch_size=2, num_shards=2, shard_index=1)
    dl2.load_state_dict(state)
    vals = [int(b.asnumpy()[0, 0]) for b in dl2]
    # shard 1 of 2 owns odd global batches 1,3,5,...; skipping 6 of them
    # resumes at global batch 13 (samples 26,27)
    assert vals[0] == 26, vals
    assert dl2.state_dict()["epoch"] == 1
    assert dl2.state_dict()["num_shards"] == 2
    # fast-forward dropped index lists unbuilt: only consumed batches
    # touched the dataset
    assert ds2.reads == len(vals) * 2, (ds2.reads, len(vals))
    # non-divisible re-map: G = 9 consumed globals onto 2 shards —
    # shard 0 owns 5 of [0, 9) (0,2,4,6,8), shard 1 owns 4 (1,3,5,7);
    # without the remainder correction shard 0 would replay global 8
    state9 = {"epoch": 1, "global": 9}
    ds3 = _CountingDataset(48)
    dl3 = DataLoader(ds3, batch_size=2, num_shards=2, shard_index=0)
    dl3.load_state_dict(state9)
    it3 = iter(dl3)
    assert int(next(it3).asnumpy()[0, 0]) == 20   # global batch 10
    # the cursor keeps the EXACT global position across the restore
    # (9 + 1 consumed * 2 shards = 11, not start_batch*2 = 10), so a
    # SECOND re-shard re-maps from the true fleet position
    assert dl3.state_dict()["global"] == 11
    ds4 = _CountingDataset(48)
    dl4 = DataLoader(ds4, batch_size=2, num_shards=2, shard_index=1)
    dl4.load_state_dict(state9)
    it4 = iter(dl4)
    assert int(next(it4).asnumpy()[0, 0]) == 18   # global batch 9
    assert dl4.state_dict()["global"] == 11
    # legacy cursor without "global" still restores (batch*num_shards)
    dl5 = DataLoader(_CountingDataset(48), batch_size=2, num_shards=2,
                     shard_index=0)
    dl5.load_state_dict({"epoch": 1, "batch": 3, "num_shards": 3})
    assert int(next(iter(dl5)).asnumpy()[0, 0]) == 20


def test_loader_cursor_threaded_path():
    from mxnet_tpu.gluon.data import DataLoader
    ds = [np.float32([i]) for i in range(32)]
    dl = DataLoader(ds, batch_size=2, num_workers=2, num_shards=2,
                    shard_index=0)
    it = iter(dl)
    first = int(next(it).asnumpy()[0, 0])
    assert first == 0
    consumed = 1
    for _ in it:
        consumed += 1
    assert dl.state_dict() == {"epoch": 1, "batch": consumed,
                               "num_shards": 2,
                               "global": consumed * 2}
    # a second epoch bumps the epoch counter and resets the batch cursor
    next(iter(dl))
    assert dl.state_dict()["epoch"] == 2
    assert dl.state_dict()["batch"] == 1


def test_loader_abandoned_epoch_releases_producer():
    """Dropping a threaded epoch iterator mid-epoch (a `break` at a
    target step, FleetReformed — routine under elastic supervision)
    must release the producer thread and its worker pool instead of
    leaving them blocked on the full prefetch queue forever."""
    import threading
    import time
    from mxnet_tpu.gluon.data import DataLoader
    ds = [np.float32([i]) for i in range(400)]
    dl = DataLoader(ds, batch_size=2, num_workers=2, prefetch=2)
    before = threading.active_count()
    it = iter(dl)
    next(it)
    it.close()   # GeneratorExit -> the abandonment path
    deadline = time.time() + 15
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, (
        f"{threading.active_count() - before} loader thread(s) leaked "
        f"after abandoning the epoch")


def test_loader_shard_validation():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon.data import DataLoader
    ds = [np.float32([i]) for i in range(8)]
    with pytest.raises(MXNetError, match="shard_index"):
        DataLoader(ds, batch_size=2, num_shards=2, shard_index=5)
    with pytest.raises(MXNetError, match="num_shards"):
        DataLoader(ds, batch_size=2, shard_index=1)
    with pytest.raises(MXNetError, match="dist"):
        DataLoader(ds, batch_size=2, num_shards="dist", shard_index=0)
    # unsharded loaders keep the cursor too (plain resume rewind)
    dl = DataLoader(ds, batch_size=2)
    assert [int(b.asnumpy()[0, 0]) for b in dl] == [0, 2, 4, 6]
    assert dl.state_dict() == {"epoch": 1, "batch": 4, "num_shards": 1,
                               "global": 4}
