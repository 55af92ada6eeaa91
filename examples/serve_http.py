"""The multi-model production frontend, end to end over a real socket.

Serves TWO models from one process through
`mxnet_tpu.serving.HttpFrontend` — a predict model (JSON in/out over
`POST /v1/models/<name>/predict`) and a small causal LM streaming
tokens over Server-Sent Events (`POST /v1/models/<name>/generate`) —
then exercises the whole wire surface with stdlib HTTP clients:

1. readiness + the registry listing (`/readyz`, `/v1/models`);
2. concurrent JSON predict clients (responses bitwise-match what
   `submit()` returns in-process);
3. SSE generation with socket-measured TTFT;
4. a rolling blue/green weight swap while predict traffic is live
   (zero dropped requests — every response is old weights or new,
   never torn);
5. priority shedding: the registry gate 429s the low-priority model
   while the high-priority one keeps flowing;
6. graceful shutdown draining every model.

    python examples/serve_http.py --clients 4 --requests 12

Knobs: MXTPU_FRONTEND_PORT (deployment port; this example binds
ephemeral), MXTPU_FRONTEND_PRIORITY, MXTPU_FRONTEND_SLO_MS.
"""
import argparse
import http.client
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo.transformer import causal_lm_small
from mxnet_tpu.serving import (GenerationServer, HttpFrontend,
                               ModelRegistry, ModelServer)


class Scale2(gluon.HybridBlock):
    def hybrid_forward(self, F, x):
        return F.tanh(x * 2.0) + 0.5


class Scale3(gluon.HybridBlock):
    """The 'green' weights for the blue/green swap demo."""

    def hybrid_forward(self, F, x):
        return F.tanh(x * 3.0) - 0.25


def _block(cls, ctx):
    net = cls()
    net.initialize(ctx=ctx)
    net.hybridize()
    return net


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12,
                    help="predict requests per client")
    ap.add_argument("--generations", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--ctx", default="tpu(0)",
                    help="context to place the model and data on — the "
                         "default context is the host, so a run meant "
                         "for the chip names it (cpu(0) for a host run)")
    args = ap.parse_args()
    ctx = mx.Context.from_str(args.ctx)
    print(f"running on {ctx}: {ctx.device.platform} "
          f"{ctx.device.device_kind}")

    np.random.seed(0)
    mx.random.seed(0)
    lm = causal_lm_small()
    lm.initialize(ctx=ctx)
    lm.hybridize()

    registry = ModelRegistry()
    predict_srv = ModelServer(_block(Scale2, ctx), max_batch=8,
                              batch_window_us=300.0)
    registry.load("scale", predict_srv, priority=1, slo_ms=50.0)
    gen_srv = GenerationServer(lm, slots=2, kv_block=16, kv_blocks=64,
                               max_new_tokens=args.max_new,
                               prompt_buckets=(16,), queue_depth=64,
                               deadline_ms=0)
    registry.load("lm", gen_srv, priority=2, slo_ms=200.0, warm=True)

    frontend = HttpFrontend(registry, port=0).start()
    port = frontend.port
    print(f"frontend listening on 127.0.0.1:{port} "
          f"({len(registry.names())} models)")

    status, body = _get(port, "/readyz")
    names = [m["name"] for m in _get(port, "/v1/models")[1]["models"]]
    print(f"readyz {status}, models: {','.join(names)}")

    # -- concurrent JSON predict --------------------------------------
    rng = np.random.default_rng(7)
    xs = [rng.uniform(-1, 1, (16,)).astype(np.float32)
          for _ in range(args.clients * args.requests)]
    direct = [predict_srv.infer(x) for x in xs]
    mismatches, errors = [0], [0]
    lock = threading.Lock()

    def client(cid):
        for i in range(cid, len(xs), args.clients):
            st, _, out = _post(port, "/v1/models/scale/predict",
                               {"inputs": [xs[i].tolist()],
                                "dtype": "float32"})
            with lock:
                if st != 200:
                    errors[0] += 1
                elif not np.array_equal(
                        np.asarray(out["outputs"][0], np.float32),
                        direct[i]):
                    mismatches[0] += 1

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    print(f"predict: {len(xs)} requests from {args.clients} HTTP "
          f"clients in {wall:.2f}s, {errors[0]} errors, "
          f"{mismatches[0]} mismatches vs direct submit() "
          f"(bitwise: {'OK' if not mismatches[0] else 'FAIL'})")

    # -- SSE token streaming ------------------------------------------
    ttfts = []
    for g in range(args.generations):
        prompt = rng.integers(1, 250, (5,)).astype(np.int32)
        toks, ttft = _sse(port, "lm", prompt, args.max_new)
        ttfts.append(ttft * 1e3)
        if g == 0:
            print(f"generate: streamed {len(toks)} tokens over SSE "
                  f"{toks}")
    print(f"SSE socket TTFT: " +
          ", ".join(f"{t:.1f}ms" for t in sorted(ttfts)))

    # -- blue/green swap under live traffic ---------------------------
    x = xs[0]
    old = predict_srv.infer(x)
    stop = threading.Event()
    outs, swap_errors = [], [0]

    def swap_client():
        while not stop.is_set():
            st, _, out = _post(port, "/v1/models/scale/predict",
                               {"inputs": [x.tolist()],
                                "dtype": "float32"})
            with lock:
                if st != 200:
                    swap_errors[0] += 1
                else:
                    outs.append(np.asarray(out["outputs"][0],
                                           np.float32))

    threads = [threading.Thread(target=swap_client) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    staged = registry.swap("scale", _block(Scale3, ctx))
    time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join()
    new = predict_srv.infer(x)
    n_old = sum(np.array_equal(o, old) for o in outs)
    n_new = sum(np.array_equal(o, new) for o in outs)
    print(f"blue/green swap: staged {staged} executable(s) under live "
          f"traffic; responses old={n_old} new={n_new} "
          f"torn={len(outs) - n_old - n_new} errors={swap_errors[0]} "
          f"(zero dropped: "
          f"{'OK' if not swap_errors[0] else 'FAIL'})")

    # -- priority shedding --------------------------------------------
    registry.set_shed_level(2)        # sheds priority < 2 ("scale")
    st_low = _post(port, "/v1/models/scale/predict",
                   {"inputs": [x.tolist()], "dtype": "float32"})[0]
    st_high = _post(port, "/v1/models/lm/generate",
                    {"prompt": [3, 5], "max_new_tokens": 2},
                    stream=False)[0]
    registry.set_shed_level(0)
    print(f"shedding at level 2: low-priority predict -> {st_low}, "
          f"high-priority generate -> {st_high}")

    frontend.stop(drain=True)
    print(f"frontend drained; KV blocks used: "
          f"{gen_srv.stats()['kv_blocks_used']} (must be 0)")


def _get(port, path):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        c.request("GET", path)
        r = c.getresponse()
        return r.status, json.loads(r.read())
    finally:
        c.close()


def _post(port, path, obj, stream=True):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        c.request("POST", path, body=json.dumps(obj))
        r = c.getresponse()
        body = r.read()
        try:
            parsed = json.loads(body)
        except ValueError:
            parsed = {}               # SSE body
        return r.status, dict(r.getheaders()), parsed
    finally:
        c.close()


def _sse(port, name, prompt, max_new):
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new_tokens": max_new})
    s = socket.create_connection(("127.0.0.1", port), timeout=60)
    try:
        t0 = time.monotonic()
        s.sendall((f"POST /v1/models/{name}/generate HTTP/1.1\r\n"
                   f"Host: x\r\nContent-Length: {len(body)}\r\n\r\n"
                   f"{body}").encode())
        buf, ttft = b"", None
        while True:
            chunk = s.recv(65536)
            if ttft is None and b"data:" in buf + chunk:
                ttft = time.monotonic() - t0
            if not chunk:
                break
            buf += chunk
    finally:
        s.close()
    toks = [json.loads(line.partition(b":")[2])["token"]
            for line in buf.split(b"\n")
            if line.startswith(b"data:") and b'"token"' in line]
    return toks, ttft


if __name__ == "__main__":
    main()
