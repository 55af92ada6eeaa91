"""Generation serving: the block-managed KV cache allocator and the
token-level (iteration-level) decode scheduler.

Covers the PR-14 acceptance surface: greedy tokens bitwise-identical
batched vs alone while requests join and leave mid-stream, KV-block
occupancy back to zero after EVERY drain path (finish, deadline, 429,
abort), admission gating on block availability, the live decode-slot
retarget seam, and the warm-process compile-cache contract
(compiles==0 on a second process).

One module-scoped CausalLM is shared across scheduler tests (its
compile dominates the test cost); every server is stopped in a finally
block so a failing assertion never leaks the scheduler thread.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import causal_lm_small
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.serving import (BlockKVCache, BlockTable, DeadlineExceeded,
                               GenerationServer, NoBucketError,
                               SCRATCH_BLOCK, ServerOverloaded)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- BlockKVCache unit tests -------------------------------------------------

def test_kv_blocks_needed_is_ceil_and_capacity_excludes_scratch():
    kv = BlockKVCache(n_blocks=8, block_size=4)
    assert kv.capacity == 7            # block 0 is scratch
    assert kv.blocks_needed(1, 0) == 1
    assert kv.blocks_needed(4, 0) == 1
    assert kv.blocks_needed(5, 0) == 2
    assert kv.blocks_needed(3, 6) == 3     # 9 tokens / 4 per block
    assert kv.fits(4, 24) and not kv.fits(4, 25)


def test_kv_validates_constructor_args():
    with pytest.raises(ValueError):
        BlockKVCache(n_blocks=1, block_size=4)   # no room beside scratch
    with pytest.raises(ValueError):
        BlockKVCache(n_blocks=8, block_size=0)


def test_kv_lazy_growth_and_scratch_padded_tail():
    kv = BlockKVCache(n_blocks=8, block_size=4)
    table = kv.reserve(1, prompt_len=5, max_new_tokens=6)   # 3 blocks
    assert table is not None and table.reserved == 3
    assert kv.used() == 0                  # reservation allocates nothing
    kv.ensure(1, 5)
    assert kv.used() == 2                  # ceil(5/4) physical blocks
    assert SCRATCH_BLOCK not in table.blocks
    row = table.padded(4)
    assert len(row) == 4
    assert row[:2] == table.blocks and row[2:] == [SCRATCH_BLOCK] * 2
    kv.ensure(1, 9)
    assert kv.used() == 3
    kv.release(1)
    assert kv.used() == 0 and kv.reserved() == 0


def test_kv_release_returns_unused_reservation():
    kv = BlockKVCache(n_blocks=4, block_size=4)   # capacity 3
    assert kv.reserve(1, 4, 8) is not None        # reserves all 3
    assert kv.reserve(2, 1, 1) is None            # pool promised away
    kv.ensure(1, 4)                               # only 1 block touched
    kv.release(1)
    t2 = kv.reserve(2, 4, 8)                      # whole pool back
    assert t2 is not None and t2.reserved == 3


def test_kv_ensure_past_reservation_raises():
    kv = BlockKVCache(n_blocks=8, block_size=4)
    kv.reserve(1, 4, 0)
    with pytest.raises(RuntimeError):
        kv.ensure(1, 5)


def test_kv_occupancy_gauge_tracks_pool():
    kv = BlockKVCache(n_blocks=8, block_size=2)
    kv.reserve(7, 4, 0)
    kv.ensure(7, 4)
    assert registry().snapshot()["serving.kv_blocks_used"] == 2
    kv.release(7)
    assert registry().snapshot()["serving.kv_blocks_used"] == 0


def test_kv_double_release_is_idempotent():
    kv = BlockKVCache(n_blocks=8, block_size=4)
    kv.reserve(1, 4, 0)
    kv.ensure(1, 4)
    kv.release(1)
    kv.release(1)
    assert kv.used() == 0 and kv.reserved() == 0


# -- GenerationServer scheduler tests ---------------------------------------

@pytest.fixture(scope="module")
def lm():
    np.random.seed(0)
    mx.random.seed(0)
    net = causal_lm_small()
    net.initialize()
    net.hybridize()
    return net


def _server(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("kv_block", 16)
    kw.setdefault("kv_blocks", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("prompt_buckets", (16,))
    kw.setdefault("queue_depth", 64)
    kw.setdefault("deadline_ms", 0)
    return GenerationServer(lm, **kw)


def _prompts(n, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, (int(rng.integers(2, 14)),))
            .astype(np.int32) for _ in range(n)]


def test_generate_batched_bitwise_equals_alone(lm):
    """THE correctness acceptance: greedy tokens for each request are
    bitwise-identical whether it decoded alone (slots=1, serial) or
    batched with strangers joining and leaving mid-stream (varying
    max_new_tokens forces slot turnover)."""
    prompts = _prompts(6)
    caps = [3, 8, 5, 8, 2, 6]      # staggered finishes: joins + leaves
    srv = _server(lm, slots=3)
    try:
        srv.start()
        srv.warmup()
        reqs = [srv.submit_generate(p, max_new_tokens=c)
                for p, c in zip(prompts, caps)]
        batched = [r.result(timeout=60) for r in reqs]
    finally:
        srv.stop()
    assert srv.stats()["kv_blocks_used"] == 0
    alone = []
    srv1 = _server(lm, slots=1)
    try:
        srv1.start()
        for p, c in zip(prompts, caps):
            alone.append(srv1.generate(p, timeout=60, max_new_tokens=c))
    finally:
        srv1.stop()
    assert batched == alone
    assert [len(t) for t in batched] == caps


def test_generation_runs_where_the_parameters_live():
    """The scheduler thread's default context is cpu(0) and the KV pool
    starts as a host array; the prefill/decode graphs and the pool must
    still end up on the context the model was initialized on (on a TPU
    host: the chip; here a second CPU device stands in for it)."""
    ctx = mx.cpu(1)
    np.random.seed(0)
    mx.random.seed(0)
    lm = causal_lm_small()
    lm.initialize(ctx=ctx)
    lm.hybridize()
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    srv = GenerationServer(lm, slots=2, kv_block=16, kv_blocks=16,
                           max_new_tokens=4, prompt_buckets=(16,),
                           deadline_ms=0)
    with srv:
        toks = srv.generate(prompt, timeout=120)
        assert set(srv._pool.devices()) == {ctx.device}
    assert len(toks) == 4
    seq = np.concatenate([prompt, toks[:-1]])[None]
    logits = lm(mx.nd.array(seq, ctx=ctx, dtype="int32"))
    assert set(logits._read().devices()) == {ctx.device}
    assert logits.asnumpy()[0, len(prompt) - 1:].argmax(-1).tolist() == toks


def test_iteration_level_turnover_batches_decodes(lm):
    """Finished generations leave and queued prompts join every step:
    with 2 slots and 6 requests the decode-step count must sit well
    below the serial sum (batching happened) and at/above the longest
    single request (it cannot be shorter than one member)."""
    reg = registry()
    steps0 = reg.snapshot().get("serving.decode_steps", 0)
    srv = _server(lm, slots=2, max_new_tokens=8)
    try:
        srv.start()
        srv.warmup()
        reqs = [srv.submit_generate(p) for p in _prompts(6, seed=5)]
        outs = [r.result(timeout=60) for r in reqs]
    finally:
        srv.stop()
    assert all(len(o) == 8 for o in outs)
    steps = reg.snapshot()["serving.decode_steps"] - steps0
    # 6 requests x 7 decode steps each (first token comes from prefill)
    # = 42 serial; 2-wide batching must land well under that
    assert steps < 35, steps
    st = srv.stats()
    assert st["kv_blocks_used"] == 0
    assert st["tokens_generated"] >= 48


def test_drain_paths_release_kv_blocks(lm):
    """Occupancy returns to zero through EVERY exit: normal finish,
    deadline expiry of queued work, and 429 shed at admission."""
    srv = _server(lm, queue_depth=2)
    try:
        # 429 path: pre-start, the queue holds 2 — the third sheds
        srv.submit_generate(np.asarray([1, 2, 3], np.int32))
        srv.submit_generate(np.asarray([4, 5], np.int32),
                            deadline_ms=5)
        with pytest.raises(ServerOverloaded):
            srv.submit_generate(np.asarray([6], np.int32))
        time.sleep(0.05)        # the deadline_ms=5 request expires queued
        srv.start()
        srv.warmup()
        time.sleep(0.3)
    finally:
        srv.stop()
    st = srv.stats()
    assert st["kv_blocks_used"] == 0
    assert st["rejected_429"] >= 1
    assert registry().snapshot()["serving.kv_blocks_used"] == 0


def test_deadline_expired_queued_generation_raises(lm):
    srv = _server(lm)
    try:
        req = srv.submit_generate(np.asarray([1, 2, 3], np.int32),
                                  deadline_ms=5)
        time.sleep(0.05)
        srv.start()
        with pytest.raises(DeadlineExceeded):
            req.result(timeout=30)
    finally:
        srv.stop()
    assert srv.stats()["kv_blocks_used"] == 0


def test_admission_gates_on_block_availability(lm):
    """A request whose worst case cannot fit the pool EVER is rejected
    at submit; one that cannot fit NOW queues until blocks free up."""
    srv = _server(lm, kv_blocks=3, max_new_tokens=32)  # capacity 2
    try:
        with pytest.raises(NoBucketError):
            # ceil((14+32)/16) = 3 blocks; the pool never holds 3
            srv.submit_generate(np.arange(1, 15, dtype=np.int32),
                                max_new_tokens=32)
        srv.start()
        srv.warmup()
        # each of these needs 2 blocks = the whole pool: they must run
        # one after the other, both completing via the FIFO hold
        r1 = srv.submit_generate(np.arange(1, 15, dtype=np.int32),
                                 max_new_tokens=16)
        r2 = srv.submit_generate(np.arange(1, 15, dtype=np.int32),
                                 max_new_tokens=16)
        assert len(r1.result(timeout=60)) == 16
        assert len(r2.result(timeout=60)) == 16
    finally:
        srv.stop()
    assert srv.stats()["kv_blocks_used"] == 0


def test_submit_validation(lm):
    srv = _server(lm)
    try:
        with pytest.raises(NoBucketError):
            srv.submit_generate(np.arange(30, dtype=np.int32))  # > bucket
        with pytest.raises(MXNetError):
            srv.submit_generate(np.asarray([1], np.int32),
                                max_new_tokens=10 ** 6)  # > knob cap
    finally:
        srv.stop(drain=False)


def test_set_decode_slots_retargets_between_iterations(lm):
    srv = _server(lm, slots=2)
    try:
        srv.start()
        srv.warmup()
        srv.set_decode_slots(4)
        outs = [srv.submit_generate(p) for p in _prompts(4, seed=9)]
        for r in outs:
            assert len(r.result(timeout=60)) == 8
        assert srv.decode_slots == 4
        assert srv.stats()["slots"] == 4
    finally:
        srv.stop()
    assert srv.stats()["kv_blocks_used"] == 0


def test_stop_without_drain_sheds_and_releases(lm):
    srv = _server(lm)
    try:
        srv.start()
        srv.warmup()
        reqs = [srv.submit_generate(p, max_new_tokens=8)
                for p in _prompts(8, seed=11)]
    finally:
        srv.stop(drain=False)
    done = sum(1 for r in reqs if not r._error)
    del done                                 # either outcome is legal
    assert srv.stats()["kv_blocks_used"] == 0


def test_generation_metrics_emitted(lm):
    reg = registry()
    base = reg.snapshot()
    t0 = base.get("serving.ttft_us", {}).get("count", 0)
    d0 = base.get("serving.decode_step_us", {}).get("count", 0)
    g0 = base.get("serving.tokens_generated", 0)
    srv = _server(lm)
    try:
        srv.start()
        srv.warmup()
        srv.generate(np.asarray([5, 6, 7], np.int32), timeout=60)
    finally:
        srv.stop()
    snap = reg.snapshot()
    assert snap["serving.ttft_us"]["count"] == t0 + 1
    assert snap["serving.decode_step_us"]["count"] - d0 >= 7
    assert snap["serving.tokens_generated"] - g0 == 8
    assert snap["serving.kv_blocks_used"] == 0


_WARM_GEN_SCRIPT = """
import json, os, sys
sys.path.insert(0, os.environ["MXTPU_GEN_ROOT"])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.transformer import causal_lm_small
from mxnet_tpu.serving import GenerationServer
np.random.seed(0); mx.random.seed(0)
lm = causal_lm_small(); lm.initialize(); lm.hybridize()
srv = GenerationServer(lm, slots=2, kv_block=16, kv_blocks=16,
                       max_new_tokens=4, prompt_buckets=(16,),
                       deadline_ms=0)
with srv:
    srv.warmup()
    toks = srv.generate(np.asarray([3, 1, 4], np.int32), timeout=120)
from mxnet_tpu.observability.registry import registry
snap = registry().snapshot()
print("RESULT " + json.dumps({
    "tokens": toks,
    "compiles": snap.get("tuning.compiles", 0),
    "cache_hits": snap.get("tuning.compile_cache_hits", 0)}))
"""


@pytest.mark.slow
def test_warm_process_decode_graphs_hit_compile_cache(tmp_path):
    """PR-14 acceptance: a second process with the same
    MXTPU_COMPILE_CACHE_DIR populates BOTH graph families (prefill
    buckets + the decode step) from disk — compiles==0 — and generates
    the identical greedy tokens."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_GEN_ROOT=ROOT,
               MXTPU_COMPILE_CACHE_DIR=str(tmp_path / "cc"))
    out = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _WARM_GEN_SCRIPT],
                           capture_output=True, text=True, timeout=600,
                           env=env, cwd=ROOT)
        assert r.returncode == 0, r.stderr[-3000:]
        line = [l for l in r.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        out.append(json.loads(line[len("RESULT "):]))
    cold, warm = out
    assert cold["compiles"] > 0
    assert warm["compiles"] == 0, warm
    assert warm["cache_hits"] >= cold["compiles"]
    assert warm["tokens"] == cold["tokens"]
