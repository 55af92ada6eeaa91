"""Elastic fleet demo: survive a host loss mid-run, no operator action.

Launches a small localhost fleet (default 3 worker processes joined
through one JAX coordination service), trains a toy MLP data-parallel
(each worker on its own ``num_shards="dist"`` batch stripe), and
SIGKILLs one worker mid-run via the deterministic fault plan
(``host_loss@<step>``).  The survivors then:

1. detect the dead host within one lease TTL (heartbeat leases over the
   coordination-service KV store — ``parallel/membership.py``),
2. quiesce at the next step boundary and run the KV consensus re-form
   (view exchange → plan → acks → committed fence bump),
3. re-install the process group at the reduced world size with
   contiguous ranks, purge the dead host's KV generations,
4. restore the last committed checkpoint, re-wind the loader onto the
   new shard assignment, and keep training to the target step.

Run::

    python examples/elastic_fleet.py            # 3 workers, kill rank 2
    python examples/elastic_fleet.py --workers 3 --kill-rank 2 \
        --kill-step 5 --target 10

Each surviving worker prints its re-form line and final state; the
launcher prints the merged timeline and ``ELASTIC_EXAMPLE_OK``.
"""
import argparse
import os
import socket
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
    from mxnet_tpu.base import force_cpu_mesh
    force_cpu_mesh(1, verify=False)   # distributed init precedes the
    import numpy as np                # first backend query
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.parallel import (dist, FleetReformed, HostFenced,
                                    ResilientTrainer, ShardedTrainer)
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.observability.flight import recorder

    dist.init_process_group()          # MXTPU_ELASTIC=1 set by launcher
    phys = dist.phys_rank()
    TARGET = int(os.environ["ELASTIC_TARGET_T"])
    ckpt_dir = os.path.join(os.environ["ELASTIC_CKPT_ROOT"],
                            "rank%d" % phys)

    N, F, C = 256, 8, 4
    def sample(i):
        x = ((np.arange(F) * 7 + i * 13) % 97).astype(np.float32) / 97.0
        return x, np.int32(i % C)
    loader = DataLoader([sample(i) for i in range(N)], batch_size=8,
                        num_shards="dist")

    mx.random.seed(11)
    np.random.seed(11)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=F))
        net.add(nn.Dense(C, in_units=16))
    net.initialize()
    trainer = ShardedTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh({"dp": 1}, devices=jax.local_devices()[:1]))
    rt = ResilientTrainer(trainer, checkpoint_dir=ckpt_dir,
                          checkpoint_every=2, elastic=True,
                          loader=loader, skip_nonfinite=False)

    done = False
    while not done:
        try:
            for x, y in loader:
                loss = rt.step(x, y)
                if trainer.num_update >= TARGET:
                    done = True
                    break
        except FleetReformed as e:
            r = e.result
            print("rank %d: fleet re-formed at generation %d — lost %s, "
                  "world %d -> %d, resumed from step %s" %
                  (phys, r.fence, list(r.dead), len(r.old_members),
                   r.new_world, r.resumed_t), flush=True)
            continue
        except HostFenced:
            print("rank %d: fenced out (false death) — exiting" % phys,
                  flush=True)
            sys.exit(3)

    rt.flush()
    events = [m.get("event") for m in recorder().memberships()]
    loss_val = float(np.asarray(jax.device_get(loss._read())))
    print("rank %d: done at step %d (loss %.4f; membership timeline: %s)"
          % (phys, trainer.num_update, loss_val, " -> ".join(events)),
          flush=True)
    dist.barrier("elastic_example_done", timeout=60)
    print("WORKER_%d_DONE" % phys, flush=True)
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--kill-rank", type=int, default=2,
                    help="rank that dies (host_loss fault; SIGKILL)")
    ap.add_argument("--kill-step", type=int, default=5)
    ap.add_argument("--target", type=int, default=10,
                    help="train until this update counter")
    ap.add_argument("--workdir", default=None,
                    help="checkpoint root (default: a temp dir)")
    args = ap.parse_args()
    if not 0 <= args.kill_rank < args.workers:
        sys.exit("--kill-rank must name one of the workers")
    if args.workers < 3:
        sys.exit("need >= 3 workers: 2 survivors must outvote the loss")

    import tempfile
    workdir = args.workdir or tempfile.mkdtemp(prefix="mxtpu_elastic_")
    port = _free_port()
    script = os.path.join(workdir, "elastic_worker.py")
    with open(script, "w") as f:
        f.write(WORKER)

    procs = []
    for r in range(args.workers):
        env = dict(os.environ)
        env.update({
            "MXNET_TEST_ROOT": ROOT,
            "JAX_PLATFORMS": "cpu",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(args.workers),
            "DMLC_WORKER_ID": str(r),
            # elastic mode + test-scale lease timings
            "MXTPU_ELASTIC": "1",
            "MXTPU_ELASTIC_LEASE_TTL": "1.5",
            "MXTPU_ELASTIC_HEARTBEAT": "0.3",
            "MXTPU_ELASTIC_REFORM_TIMEOUT": "45",
            "MXTPU_DIST_TIMEOUT": "20",
            "ELASTIC_TARGET_T": str(args.target),
            "ELASTIC_CKPT_ROOT": workdir,
        })
        if r == args.kill_rank:
            env["MXTPU_FAULT_PLAN"] = f"host_loss@{args.kill_step}"
        procs.append(subprocess.Popen(
            [sys.executable, script], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))

    failed = False
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=300)
        for line in out.splitlines():
            if line.startswith(("rank ", "WORKER_")):
                print(f"[worker {r}] {line}")
        if r == args.kill_rank:
            if p.returncode == 0:
                print(f"[launcher] worker {r} was supposed to die "
                      f"(host_loss@{args.kill_step}) but exited 0")
                failed = True
            else:
                print(f"[launcher] worker {r} killed as planned "
                      f"(rc {p.returncode})")
        elif p.returncode != 0:
            print(f"[launcher] survivor {r} FAILED (rc {p.returncode}):\n"
                  + out[-2000:])
            failed = True
    if failed:
        sys.exit(1)
    survivors = args.workers - 1
    print(f"survived host loss: {survivors} of {args.workers} workers "
          f"re-formed and reached step {args.target}")
    print("ELASTIC_EXAMPLE_OK")


if __name__ == "__main__":
    main()
