"""Continuous-batching model serving (the inference path).

Serves a hybridized MLP through `mxnet_tpu.serving.ModelServer`: an
admission queue with backpressure, shape-bucketed batch assembly, and
one compiled CachedOp call per bucket, with concurrent client threads
offering load.  Prints p50/p99 latency, achieved QPS, and the
batch-formation efficiency the observability registry measured.

    python examples/serve_continuous_batching.py --clients 4 --requests 200

The exported-model path (the C-ABI seam documented in
examples/serve_c_api.md) serves the same way:

    net.export("model")   # model-symbol.json + model-0000.params
    srv = ModelServer.from_exported("model-symbol.json", "data",
                                    "model-0000.params")

Knobs (also settable per-constructor): MXTPU_SERVING_MAX_BATCH,
MXTPU_SERVING_QUEUE_DEPTH, MXTPU_SERVING_DEADLINE_MS,
MXTPU_SERVING_WORKERS, MXTPU_SERVING_BATCH_WINDOW_US.
"""
import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.serving import ModelServer, ServingError


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=200,
                    help="requests per client")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline (0 = none)")
    ap.add_argument("--ctx", default="tpu(0)",
                    help="context to place the model and data on — the "
                         "default context is the host, so a run meant "
                         "for the chip names it (cpu(0) for a host run)")
    args = ap.parse_args()
    ctx = mx.Context.from_str(args.ctx)
    print(f"running on {ctx}: {ctx.device.platform} "
          f"{ctx.device.device_kind}")

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(128, activation="relu"),
                gluon.nn.Dense(64, activation="relu"),
                gluon.nn.Dense(10))
    net.initialize(ctx=ctx)   # the served graphs follow the parameters
    net.hybridize()

    rng = np.random.default_rng(0)
    lat_ms, rejected = [], [0]
    lock = threading.Lock()

    def client(cid):
        crng = np.random.default_rng(cid)
        for _ in range(args.requests):
            x = crng.standard_normal((784,)).astype(np.float32)
            try:
                t0 = time.monotonic()
                y = srv.infer(x, timeout=60)
                dt = (time.monotonic() - t0) * 1e3
                assert y.shape == (10,)
                with lock:
                    lat_ms.append(dt)
            except ServingError:
                with lock:
                    rejected[0] += 1

    with ModelServer(net, max_batch=args.max_batch,
                     deadline_ms=args.deadline_ms) as srv:
        srv.warmup(rng.standard_normal((784,)).astype(np.float32))
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(args.clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0

    lat_ms.sort()
    n = len(lat_ms)
    snap = registry().snapshot()
    real = snap["serving.tokens_real"]
    padded = snap["serving.tokens_padded"]   # sequence-pad positions
    slots = snap.get("serving.slots_padded", 0)
    print(f"served {n} requests from {args.clients} clients in "
          f"{wall:.2f}s ({n / wall:.0f} req/s), {rejected[0]} rejected")
    if n:
        print(f"latency p50 {lat_ms[n // 2]:.2f} ms, "
              f"p99 {lat_ms[int(n * 0.99)]:.2f} ms")
    print(f"batch efficiency {real / max(real + padded, 1):.2%} "
          f"(real / real+padded positions; {slots} padded slots)")


if __name__ == "__main__":
    main()
