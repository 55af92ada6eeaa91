"""Token-level continuous batching for generation (the decode path).

Serves a small causal transformer LM through
`mxnet_tpu.serving.GenerationServer`: one compiled prefill graph per
prompt bucket, ONE single-token decode-step graph whose carried state
is a block-managed paged KV cache, and an iteration-level scheduler —
finished generations exit the running batch at every decode step and
queued prompts take the freed slot immediately, instead of the whole
batch waiting for its slowest member.

    python examples/serve_generation.py --clients 4 --requests 24

Prints tokens/s, TTFT (time-to-first-token) p50/p99, decode-step
latency, and the KV-block occupancy the observability registry
measured (which must drain back to zero — blocks are freed on finish,
deadline expiry, and 429 alike).

Knobs (also settable per-constructor): MXTPU_SERVING_KV_BLOCK,
MXTPU_SERVING_KV_BLOCKS, MXTPU_SERVING_DECODE_SLOTS,
MXTPU_SERVING_PREFILL_MODE, MXTPU_SERVING_MAX_NEW_TOKENS.
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
from mxnet_tpu.gluon.model_zoo.transformer import causal_lm_small
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.serving import GenerationServer, ServingError


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=24,
                    help="generations per client")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-batch width (running generations)")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prefill-mode", choices=("interleave", "step"),
                    default="interleave")
    ap.add_argument("--ctx", default="tpu(0)",
                    help="context to place the model and data on — the "
                         "default context is the host, so a run meant "
                         "for the chip names it (cpu(0) for a host run)")
    args = ap.parse_args()
    ctx = mx.Context.from_str(args.ctx)
    print(f"running on {ctx}: {ctx.device.platform} "
          f"{ctx.device.device_kind}")
    os.environ["MXTPU_SERVING_PREFILL_MODE"] = args.prefill_mode

    np.random.seed(0)
    lm = causal_lm_small()
    lm.initialize(ctx=ctx)   # the server's graphs and KV pool follow it
    lm.hybridize()
    ttft_ms, tokens, rejected = [], [0], [0]
    lock = threading.Lock()

    def client(cid):
        rng = np.random.default_rng(cid)
        for _ in range(args.requests):
            plen = int(rng.integers(3, 14))
            prompt = rng.integers(1, 250, (plen,)).astype(np.int32)
            try:
                req = srv.submit_generate(prompt)
                out = req.result(timeout=60)
                with lock:
                    tokens[0] += len(out)
                    ttft_ms.append((req.t_first - req.t_enqueue) * 1e3)
            except ServingError:
                with lock:
                    rejected[0] += 1

    with GenerationServer(lm, slots=args.slots, kv_block=16,
                          kv_blocks=128, max_new_tokens=args.max_new,
                          prompt_buckets=(16,), queue_depth=256,
                          deadline_ms=0) as srv:
        srv.warmup()                # all graphs compiled up front
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(args.clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        st = srv.stats()

    ttft_ms.sort()
    n = len(ttft_ms)
    snap = registry().snapshot()
    step = snap.get("serving.decode_step_us", {})
    print(f"completed {st['done']} generations "
          f"({tokens[0]} tokens) from {args.clients} clients in "
          f"{wall:.2f}s = {tokens[0] / wall:.1f} tokens/s, "
          f"{rejected[0]} rejected")
    if n:
        print(f"TTFT p50 {ttft_ms[n // 2]:.2f} ms, "
              f"p99 {ttft_ms[min(n - 1, int(n * 0.99))]:.2f} ms")
    if step.get("count"):
        print(f"decode steps {st['decode_steps']} "
              f"(mean {step['mean']:.0f} us/step, p99 "
              f"{step['p99']:.0f} us, batch width {st['slots']})")
    print(f"KV blocks used after drain: {st['kv_blocks_used']} "
          f"of {st['kv_blocks_total']} (must be 0)")


if __name__ == "__main__":
    main()
