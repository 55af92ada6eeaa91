"""BASELINE benchmark suite — one bare `python bench.py` run measures the
whole perf story and prints ONE JSON line.

Headline metric: ResNet-50 bf16 training throughput (images/sec/chip) —
the MXU-native mode, the number comparable to the reference's fp16-era
results (SURVEY.md §6).  The `rows` key carries the other BASELINE
configs: ResNet-50 fp32, MNIST-MLP imperative (dispatch-overhead config
#1), BERT-base step time (config #3), and the native input-pipeline
decode rate (SURVEY.md hard-part #4), plus achieved MFU per resnet row.

vs_baseline divides by 850 img/s/chip — the middle of SURVEY.md §6's
LOW-CONFIDENCE V100 fp16 planning envelope (700–1000; no published
number survived in the reference mount).  The honest headline remains
the raw img/s and MFU.

Where it runs: a row measured in this process runs on the chip, inside
``with mx.tpu(0):``, or the run fails — there is no CPU fallback.  The
full suite's parent stays off jax and runs each row in a child, one after
another (a chip belongs to one process).  The grid rows (multichip,
overlap, generate, recommender) are CPU-host rows by construction: their
cells force ``JAX_PLATFORMS=cpu`` and say so in what they print.  ROADMAP
Queue 1 #1 replaces this file with a table of cells and one runner.
"""
import argparse
import json
import os
import time

import numpy as np

BASELINE_IMG_S_FP32 = 375.0         # fp32 planning envelope (SURVEY §6)
BASELINE_IMG_S_FP16 = 850.0         # mid fp16 envelope 700-1000 (SURVEY §6)
R50_TRAIN_GFLOP_PER_IMG = 12.3      # 4.1 fwd x3 (fwd+bwd) @224
# Published bf16 peak of one chip in TFLOP/s, keyed by jax's device_kind
# (Google Cloud documentation, "TPU v5e").  A device that is not in the
# table is an error, not a default.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def _chip():
    """The device the in-process rows measure on.  A measurement path that
    finds no chip, or a chip whose peak it does not know, fails: it never
    falls back to the CPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU; JAX found "
                         f"{jax.devices()}")
    if dev.device_kind not in PEAK_BF16_TFLOPS:
        raise SystemExit(f"bench.py knows no peak for device kind "
                         f"{dev.device_kind!r}: add it to PEAK_BF16_TFLOPS "
                         f"with its source")
    return dev


def _sync(x):
    import jax
    jax.block_until_ready(x)


def bench_resnet50(dtype, batch, iters, warmup, size=224,
                   layout="NCHW"):
    """Whole-step jitted train throughput (the round-1/2 bench)."""
    import jax
    from mxnet_tpu.contrib import amp
    if dtype == "bfloat16":
        amp.init("bfloat16")
    try:
        from mxnet_tpu import parallel as par
        from mxnet_tpu.gluon import loss as gloss
        from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

        n_dev = len(jax.devices())
        batch = max(batch, n_dev) // n_dev * n_dev
        net = resnet50_v1(layout=layout)
        net.initialize()
        tr = par.ShardedTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
        rng = np.random.default_rng(0)
        shape = (batch, 3, size, size) if layout == "NCHW" else \
            (batch, size, size, 3)
        x = rng.standard_normal(shape, dtype=np.float32)
        y = rng.integers(0, 1000, (batch,))
        loss = tr.step(x, y)          # build + compile
        # keep the batch resident in HBM: real input pipelines prefetch to
        # device; re-uploading 77MB/step would bench the host link, not
        # the chip
        x, y = tr.shard_batch(x, np.asarray(y))
        for _ in range(warmup):
            loss = tr.step(x, y)
        float(loss.asnumpy())
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = tr.step(x, y)
        lval = float(loss.asnumpy())
        dt = time.perf_counter() - t0
        assert np.isfinite(lval), "non-finite loss in benchmark"
        img_s = batch * iters / dt / n_dev
        mfu = img_s * R50_TRAIN_GFLOP_PER_IMG / (
            PEAK_BF16_TFLOPS[_chip().device_kind] * 1e3)
        return {"images_per_sec_per_chip": round(img_s, 2),
                "batch": batch, "mfu_vs_bf16_peak": round(mfu, 4)}
    finally:
        amp.disable()



def _host_cores() -> int:
    """Cores THIS process may use (cgroup/affinity-aware): the number
    that explains cross-session host-shape variation, unlike
    os.cpu_count() which reports the physical machine."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):
        return os.cpu_count() or 1

def bench_mnist_mlp(iters=200, warmup=30, batch=64):
    """Config #1: IMPERATIVE Gluon MLP — measures the op-dispatch hot
    loop (SURVEY.md §3.1, hard-part #6), deliberately not hybridized."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(128, activation="relu"))
        net.add(gluon.nn.Dense(64, activation="relu"))
        net.add(gluon.nn.Dense(10))
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.default_rng(0)
    x = mx.nd.array(rng.standard_normal((batch, 784), dtype=np.float32))
    y = mx.nd.array(rng.integers(0, 10, (batch,)))

    def step():
        with autograd.record():
            L = loss_fn(net(x), y)
        L.backward()
        tr.step(batch)
        return L

    for _ in range(warmup):
        L = step()
    _sync(L._read())
    # best-of-3 measurement passes: on a shared host a transient
    # background load can slow one pass by 40%+.  BEST is the honest
    # dispatch-cost figure; the spread is reported so a loaded run is
    # visible instead of silently skewing the headline.
    passes = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            L = step()
        _sync(L._read())
        passes.append(time.perf_counter() - t0)
    dt = min(passes)
    # ~23 op dispatches per step: fwd (3 FC + 2 act + loss), their vjps,
    # and 6 optimizer update invokes
    return {"images_per_sec": round(batch * iters / dt, 1),
            "step_us": round(dt / iters * 1e6, 1),
            "us_per_op_dispatch": round(dt / iters * 1e6 / 23, 1),
            "batch": batch,
            "pass_spread_pct": round(
                (max(passes) / min(passes) - 1) * 100, 1),
            "host_cores": _host_cores()}


def bench_eager_dispatch(iters=150, chain=24, warmup=20, size=4096):
    """Config: eager small-op dispatch — a chain of small elementwise ops
    with NO reads inside, the dispatch-overhead workload bulking
    (MXNET_EXEC_BULK_EXEC_TRAIN lazy fusion segments) exists for.
    NaiveEngine (per-op synchronous dispatch, the reference's debug
    engine) pays a jit dispatch + threadpool sync PER OP; bulked mode
    pays one dispatch per MXNET_ENGINE_BULK_SIZE segment.  Both fuse
    modes are measured: 'exact' (the default — per-op kernels inside one
    dispatch, bitwise identical to unbulked) and 'aggressive' (full XLA
    fusion).  16KB vectors: big enough that the per-op dispatch/sync
    cost is the real-world one, small enough to stay a "small op"."""
    import mxnet_tpu as mx
    from mxnet_tpu.engine import engine

    eng = engine()
    rng = np.random.default_rng(0)
    x0 = mx.nd.array(rng.standard_normal((size,), dtype=np.float32))
    a = mx.nd.array(rng.standard_normal((size,), dtype=np.float32))
    b = mx.nd.array(rng.standard_normal((size,), dtype=np.float32))
    ops_per_iter = 3 * chain

    def run(n):
        y = x0
        for _ in range(n):
            for _ in range(chain):
                y = y * a + b
                y = mx.nd.tanh(y)
        y.wait_to_read()
        return y

    prev_type = eng.engine_type
    prev = {k: os.environ.get(k) for k in
            ("MXNET_EXEC_BULK_EXEC_TRAIN", "MXNET_ENGINE_BULK_FUSE")}
    results = {}
    per_op_us = None
    try:
        for mode, etype, bulk, fuse in (
                ("bulk", "ThreadedEnginePerDevice", "1", "exact"),
                ("bulk_aggressive", "ThreadedEnginePerDevice", "1",
                 "aggressive"),
                ("naive", "NaiveEngine", "0", "exact")):
            eng.set_engine_type(etype)
            os.environ["MXNET_EXEC_BULK_EXEC_TRAIN"] = bulk
            os.environ["MXNET_ENGINE_BULK_FUSE"] = fuse
            run(warmup)
            eng.reset_stats()
            # best-of-3: same shared-host rationale as the mnist row
            passes = []
            for _ in range(3):
                t0 = time.perf_counter()
                run(iters)
                passes.append(time.perf_counter() - t0)
            results[mode] = ops_per_iter * iters / min(passes)
            if mode == "bulk":
                stats = eng.stats()
                per_op_us = min(passes) / (ops_per_iter * iters) * 1e6
    finally:
        eng.set_engine_type(prev_type)
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    overhead = _metrics_overhead_pct(per_op_us,
                                     stats["mean_segment_length"] or 15)
    snapshot_us, flight_record_us = _observability_costs()
    trace_span_off_us, trace_span_us = _tracing_costs()
    sampler_off_us, sampler_on_us = _sampler_costs()
    return {"ops_per_sec_bulk": round(results["bulk"], 1),
            "ops_per_sec_bulk_aggressive": round(
                results["bulk_aggressive"], 1),
            "ops_per_sec_naive": round(results["naive"], 1),
            "bulk_speedup": round(results["bulk"] / results["naive"], 2),
            "aggressive_speedup": round(
                results["bulk_aggressive"] / results["naive"], 2),
            "chain_len": chain, "vector_size": size,
            "mean_segment_length": stats["mean_segment_length"],
            "segment_cache_hit_rate": round(
                stats["segment_cache_hits"] /
                max(1, stats["segment_cache_hits"]
                    + stats["segment_cache_misses"]), 3),
            # per-flush latency distribution (engine.flush_us histogram)
            # — the MXNET_ENGINE_BULK_SIZE auto-tune groundwork: p50 is
            # the steady-state (cache-hit) flush, p99 catches compiles
            "flush_us_p50": stats["flush_us_p50"],
            "flush_us_p99": stats["flush_us_p99"],
            # observability tax on the bulk row (measured, see helper) —
            # the <3% overhead guard reported honestly
            "metrics_overhead_pct": overhead,
            # consumer-side costs (scrape/supervisor cadence, not per
            # op): one full registry snapshot, one flight-recorder
            # per-step record
            "snapshot_us": snapshot_us,
            "flight_record_us": flight_record_us,
            # causal tracing: the instrumented-call-site probe with
            # tracing OFF (a memoized env dict hit — the always-paid
            # cost) and one fully-sampled begin+finish span (the
            # 1-in-N cost)
            "trace_span_off_us": trace_span_off_us,
            "trace_span_us": trace_span_us,
            # stack sampler: the init-site probe with sampling OFF (a
            # memoized env dict hit — the always-paid cost) and one
            # full all-thread sampling pass (what each tick at
            # MXTPU_PROF_SAMPLE_HZ costs the sampler daemon, NOT the
            # sampled threads — their tax is GIL interference only,
            # pinned <3% by the slow-marked overhead guard test)
            "sampler_off_us": sampler_off_us,
            "sampler_on_us": sampler_on_us,
            "host_cores": _host_cores()}


def _metrics_overhead_pct(per_op_us, mean_segment_len,
                          reps=200_000) -> float:
    """Measured cost of the registry instrumentation on the bulked
    dispatch path, as a percentage of the measured per-op dispatch time.

    Per deferred op the path pays ONE counter bump (`eng._c_bulked.n`);
    per flushed segment it pays three counter bumps, one histogram
    observe, and one perf_counter() pair.  Time those primitives
    directly and amortize the per-segment part over the mean segment
    length — an in-run measurement rather than a cross-run diff, so a
    shared CI host's load spikes can't masquerade as regression."""
    # unregistered instances: probe metrics must not pollute the global
    # registry (they would ride every later scrape/JSONL line)
    from mxnet_tpu.observability.registry import Counter, Histogram
    c = Counter("bench.overhead_probe")
    h = Histogram("bench.overhead_probe_us")
    t0 = time.perf_counter()
    for _ in range(reps):
        c.n += 1
    bump_us = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for _ in range(reps // 10):
        h.observe(7.3)
    observe_us = (time.perf_counter() - t0) / (reps // 10) * 1e6
    t0 = time.perf_counter()
    for _ in range(reps // 10):
        time.perf_counter()
    clock_us = (time.perf_counter() - t0) / (reps // 10) * 1e6
    per_op = bump_us + (3 * bump_us + observe_us + 2 * clock_us) \
        / max(1.0, mean_segment_len)
    if not per_op_us:
        return 0.0
    return round(per_op / per_op_us * 100.0, 3)


def _observability_costs(reps=2_000):
    """Measured per-call cost of the two consumer-side observability
    surfaces: a full ``registry().snapshot()`` (what a scrape or JSONL
    tick pays) and one flight-recorder ``record()`` (what the resilience
    supervisor pays per step).  Neither is on the dispatch hot path —
    reported so the step-cadence tax is a number, not a guess."""
    from mxnet_tpu.observability.flight import FlightRecorder
    from mxnet_tpu.observability.registry import registry
    reg = registry()
    t0 = time.perf_counter()
    for _ in range(reps // 20):
        reg.snapshot()
    snapshot_us = (time.perf_counter() - t0) / (reps // 20) * 1e6
    fr = FlightRecorder(capacity=256)     # unregistered probe instance
    rec = {"step": 1, "t": 1, "step_us": 1234.5, "loss": 0.7,
           "loss_scale": 1.0, "flush_us_p99": 99.0, "flush_count": 10,
           "steps_skipped": 0, "rollbacks": 0, "loader_depth": 2.0,
           "failed": False}
    t0 = time.perf_counter()
    for _ in range(reps):
        fr.record(**rec)
    flight_record_us = (time.perf_counter() - t0) / reps * 1e6
    return round(snapshot_us, 2), round(flight_record_us, 3)


def _tracing_costs(reps=20_000):
    """Measured cost of the causal-tracing seam: the OFF path (what
    every instrumented call site pays when ``MXTPU_TRACE`` is unset —
    one memoized env probe returning None) and one fully sampled
    begin+finish span (ids, clocks, ring append).  Probe instance, not
    the process tracer — bench spans must not pollute the live ring."""
    from mxnet_tpu.observability.registry import registry as _reg
    from mxnet_tpu.observability.tracing import Tracer
    # jsonl="" pins the stream OFF: the probe instance must not resolve
    # an operator's MXTPU_TRACE_JSONL and flush 2k bench spans into the
    # production trace file
    t = Tracer(ring=1024, jsonl="")
    # the tracer's tracing.* counters are get-or-create on the shared
    # registry: snapshot and restore them so ~22k probe begin/finishes
    # don't inflate the live series (bench.py is a standalone tool — no
    # concurrent traced workload runs in this process, which also makes
    # the MXTPU_TRACE flip below safe)
    probe_counters = [_reg().counter(n) for n in
                      ("tracing.spans_recorded", "tracing.roots_sampled",
                       "tracing.roots_unsampled")]
    saved_ns = [c.n for c in probe_counters]
    # pin BOTH knobs: an ambient MXTPU_TRACE_SAMPLE > 1 would make the
    # ON loop's root begins return None
    prev = {k: os.environ.pop(k, None)
            for k in ("MXTPU_TRACE", "MXTPU_TRACE_SAMPLE")}
    try:
        t0 = time.perf_counter()
        for _ in range(reps):
            t.begin("bench.trace_probe")
        off_us = (time.perf_counter() - t0) / reps * 1e6
        os.environ["MXTPU_TRACE"] = "1"
        t0 = time.perf_counter()
        for _ in range(reps // 10):
            sp = t.begin("bench.trace_probe", activate=False)
            sp.finish()
        on_us = (time.perf_counter() - t0) / (reps // 10) * 1e6
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for c, n in zip(probe_counters, saved_ns):
            c.n = n
    return round(off_us, 3), round(on_us, 2)


def _sampler_costs(reps=20_000):
    """Measured cost of the stack-sampler seam: the OFF path (what the
    trainer/server init sites pay when ``MXTPU_PROF_SAMPLE_HZ`` is
    unset — one memoized env probe) and ONE all-thread sampling pass
    (the per-tick cost the sampler daemon pays at N Hz; sampled threads
    pay only GIL interference, guarded <3% in the test suite)."""
    from mxnet_tpu.observability import sampler as _smp
    prev = os.environ.pop("MXTPU_PROF_SAMPLE_HZ", None)
    try:
        _smp.maybe_start_from_env()     # settle the memo on "unset"
        t0 = time.perf_counter()
        for _ in range(reps):
            _smp.maybe_start_from_env()
        off_us = (time.perf_counter() - t0) / reps * 1e6
    finally:
        if prev is not None:
            os.environ["MXTPU_PROF_SAMPLE_HZ"] = prev
    # probe window, not the process sampler: bench samples must not
    # pollute a live profile ring
    win = _smp.ProfileWindow(hz=100.0)
    n = max(1, reps // 40)
    t0 = time.perf_counter()
    for _ in range(n):
        # skip_ident=0 matches no thread: sample EVERY thread, the
        # daemon's worst case
        _smp._collect_into(win, skip_ident=0)
    on_us = (time.perf_counter() - t0) / n * 1e6
    return round(off_us, 3), round(on_us, 2)


def bench_bert_base(iters=10, warmup=3, batch=8, seq=256,
                    dtype="float32", attention="xla"):
    """Config #3: BERT-base pretraining whole-step time on the dp mesh
    (dp×tp×sp on multi-chip — tested in tests/test_parallel.py; one real
    chip here).  The objective is the REAL pretraining loss: masked-LM
    cross-entropy over the 15%-masked positions plus the NSP head's CE —
    with per-sequence padding (valid lengths in [seq/2, seq]), so the
    attention mask path is exercised.  attention='flash' routes the
    encoder's self-attention through the Pallas flash kernel (per-row
    valid-length masking); 'xla' is the additive-mask softmax path.
    dtype='bfloat16' enables the AMP hook (the MXU-native mode)."""
    from mxnet_tpu.contrib import amp

    if dtype == "bfloat16":
        amp.init("bfloat16")
    # pin the kernel per row (auto-select would otherwise give both rows
    # the same kernel on TPU and make the comparison vacuous); the legacy
    # force-on/off var outranks the policy var, so clear it too
    prev = {k: os.environ.get(k)
            for k in ("MXNET_ATTENTION_KERNEL", "MXNET_USE_FLASH_ATTENTION")}
    os.environ["MXNET_ATTENTION_KERNEL"] = \
        "flash" if attention == "flash" else "xla"
    os.environ.pop("MXNET_USE_FLASH_ATTENTION", None)
    try:
        return _bench_bert_inner(iters, warmup, batch, seq, attention)
    finally:
        amp.disable()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _bench_bert_inner(iters, warmup, batch, seq, attention):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo.transformer import bert_base

    # dropout=0 keeps the two attention paths numerically comparable (the
    # flash kernel has no attention-probs tensor to drop) — standard
    # benchmarking config
    net = bert_base(dropout=0.0)
    net.initialize()

    MASK_ID, VOCAB = 103, 30522

    def mlm_nsp_loss(out, ys):
        mlm, nsp = out
        labels, weights, nsp_y = ys
        logp = mx.nd.log_softmax(mlm, axis=-1)
        ce = -mx.nd.pick(logp, labels, axis=-1)           # (B, S)
        mlm_l = mx.nd.sum(ce * weights) / mx.nd.sum(weights)
        nsp_logp = mx.nd.log_softmax(nsp, axis=-1)
        nsp_l = -mx.nd.mean(mx.nd.pick(nsp_logp, nsp_y, axis=-1))
        return mlm_l + nsp_l

    tr = par.ShardedTrainer(net, mlm_nsp_loss, "adam",
                            {"learning_rate": 1e-4})
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, (batch, seq))
    valid_lens = rng.integers(seq // 2, seq + 1, (batch,))
    valid = (np.arange(seq)[None, :] < valid_lens[:, None]) \
        .astype(np.float32)
    mask_pos = (rng.random((batch, seq)) < 0.15) & (valid > 0)
    mask_pos[:, 0] = True                    # >=1 masked position per row
    inputs = np.where(mask_pos, MASK_ID, tokens)
    weights = mask_pos.astype(np.float32)
    segs = np.zeros((batch, seq), np.int64)
    nsp_y = rng.integers(0, 2, (batch,))
    # padding as (B,) valid LENGTHS (the GluonNLP valid_length idiom) —
    # authoritative, so the flash path can mask per row under jit
    x = (inputs, segs, valid_lens.astype(np.float32))
    y = (tokens, weights, nsp_y)
    loss = tr.step(x, y)                     # build + compile
    for _ in range(warmup):
        loss = tr.step(x, y)
    float(loss.asnumpy())
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = tr.step(x, y)
    lval = float(loss.asnumpy())
    dt = time.perf_counter() - t0
    assert np.isfinite(lval), "non-finite BERT loss in benchmark"
    return {"step_ms": round(dt / iters * 1e3, 2), "batch": batch,
            "seq_len": seq, "attention": attention,
            "kernel": os.environ.get("MXNET_ATTENTION_KERNEL", "auto"),
            "masked_positions": int(weights.sum()),
            "loss": round(lval, 3),
            "sequences_per_sec": round(batch * iters / dt, 1)}


def bench_nmt(iters=8, warmup=2, batch=16, buckets=(32, 48, 64)):
    """Config #4 (Sockeye-style NMT): transformer-base seq2seq with
    BUCKETED sequence lengths — one jit cache entry per bucket shape
    (the reference's BucketingModule economics, SURVEY §5.7)."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo.transformer import transformer_nmt_base

    net = transformer_nmt_base(vocab_size=32000, max_length=128)
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-4})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.default_rng(0)

    def batch_for(seq):
        src = mx.nd.array(rng.integers(1, 32000, (batch, seq)))
        tgt = mx.nd.array(rng.integers(1, 32000, (batch, seq)))
        lab = mx.nd.array(rng.integers(1, 32000, (batch, seq)))
        return src, tgt, lab

    def step(src, tgt, lab):
        with autograd.record():
            out = net(src, tgt)
            L = mx.nd.mean(loss_fn(out, lab))
        L.backward()
        tr.step(batch)
        return L

    data = {s: batch_for(s) for s in buckets}
    for s in buckets:                      # compile one exec per bucket
        L = step(*data[s])
    for _ in range(warmup):
        for s in buckets:
            L = step(*data[s])
    float(L.asnumpy())
    t0 = time.perf_counter()
    tokens = 0
    for _ in range(iters):
        for s in buckets:
            L = step(*data[s])
            tokens += batch * s
    float(L.asnumpy())
    dt = time.perf_counter() - t0
    return {"tokens_per_sec": round(tokens / dt, 1), "batch": batch,
            "buckets": list(buckets)}


def bench_ssd(iters=10, warmup=2, batch=8, size=512):
    """Config #5 (SSD detection): train-step throughput of the
    resnet50-backed SSD with the multibox loss (pad-and-mask static
    shapes throughout — SURVEY §2.2 contrib row)."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo.ssd import (SSDMultiBoxLoss,
                                               ssd_512_resnet50_v1)

    net = ssd_512_resnet50_v1(classes=20)
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 1e-3, "momentum": 0.9})
    loss_fn = SSDMultiBoxLoss()
    rng = np.random.default_rng(0)
    x = mx.nd.array(rng.standard_normal((batch, 3, size, size),
                                        dtype=np.float32))
    labels = np.full((batch, 4, 5), -1, np.float32)
    for i in range(batch):
        labels[i, 0] = [i % 20, 0.1, 0.1, 0.6, 0.6]
        labels[i, 1] = [(i + 3) % 20, 0.5, 0.5, 0.9, 0.9]
    y = mx.nd.array(labels)

    def step():
        with autograd.record():
            anchors, cls_preds, box_preds = net(x)
            L = loss_fn(anchors, cls_preds, box_preds, y)
        L.backward()
        tr.step(batch)
        return L

    L = step()
    for _ in range(warmup):
        L = step()
    float(L.asnumpy())
    t0 = time.perf_counter()
    for _ in range(iters):
        L = step()
    float(L.asnumpy())
    dt = time.perf_counter() - t0
    return {"images_per_sec": round(batch * iters / dt, 2),
            "batch": batch, "size": size}


def bench_pipeline(n_images=1024, batch=128, threads=None,
                   scaling=True):
    """SURVEY hard-part #4: RecordIO+JPEG decode/augment throughput
    through the native C++ core (mxnet_tpu/native/io_core.cc).  Scales
    with host cores (this CI host has 1); per-core rate is the portable
    number.  The row pins its thread config AND carries a 1/2/4/8-thread
    scaling table (VERDICT r3 Weak #5: 533 vs 860 img/s were measured at
    different thread counts — the table makes the config explicit)."""
    from mxnet_tpu.io import ImageRecordIter
    from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack_img

    ncores = _host_cores()
    threads = threads or min(8, ncores)
    path = "/tmp/mxtpu_bench_pipeline.rec"
    if not os.path.exists(path):
        # write-then-rename so an interrupted run never leaves a
        # truncated file at the cached path
        tmp = path + ".tmp"
        rng = np.random.default_rng(0)
        rec = MXRecordIO(tmp, "w")
        # photo-like content (round-5 change): uniform NOISE is the
        # worst case for libjpeg's entropy decode (~2-3x slower per
        # pixel than real photographs) and made earlier rows measure
        # the huffman pathology, not the pipeline.  Smooth structure +
        # mild texture matches real training data's decode profile.
        yy, xx = np.mgrid[0:256, 0:277]
        for i in range(n_images):
            base = (128 + 60 * np.sin(xx / 23.0 + i * 0.7)
                    + 50 * np.cos(yy / 31.0 + i * 0.3)
                    + 12 * rng.standard_normal((256, 277)))
            img = np.clip(np.stack(
                [base, base * 0.9 + 10, base * 1.1 - 10], -1), 0,
                255).astype(np.uint8)
            rec.write(pack_img(IRHeader(0, float(i % 1000), i, 0), img,
                               quality=85))
        rec.close()
        os.rename(tmp, path)
    try:
        it = ImageRecordIter(path, (3, 224, 224), batch, use_native=True,
                             shuffle=True, rand_crop=True,
                             rand_mirror=True, preprocess_threads=threads)
        native = True
    except Exception:
        it = ImageRecordIter(path, (3, 224, 224), batch, use_native=False,
                             preprocess_threads=threads)
        native = False
    def epoch_rate(iterator, repeats=2):
        # best-of-N epochs: host noise must not read as a pipeline
        # regression (the r4 driver row dropped 29% purely from load)
        best = 0.0
        for _ in range(repeats):
            m = 0
            iterator.reset()
            t0 = time.perf_counter()
            for b in iterator:
                m += b.data[0].shape[0]
            best = max(best, m / (time.perf_counter() - t0))
        return best

    rate = epoch_rate(it)
    # the PORTABLE number: one decode thread, whole pipeline, SAME
    # workload config as the main row.  The r3/r4 "per-core" figures
    # divided different thread counts by different core counts across
    # hosts and were not comparable; a single-thread rate is
    # host-shape-independent up to CPU model.
    it1 = ImageRecordIter(path, (3, 224, 224), batch, use_native=native,
                          shuffle=native, rand_crop=native,
                          rand_mirror=native, preprocess_threads=1)
    single = epoch_rate(it1)
    row = {"images_per_sec": round(rate, 1),
           "single_thread_images_per_sec": round(single, 1),
           "images_per_sec_per_core": round(single, 1),
           "native_core": native, "host_cores": ncores,
           "decode_threads": threads}
    if scaling and native:
        table = {"1": round(single, 1)}
        for th in (2, 4, 8):
            if th > 2 * ncores:
                break            # deeper oversubscription measures noise
            if th == threads:
                table[str(th)] = round(rate, 1)   # already timed
                continue
            it2 = ImageRecordIter(path, (3, 224, 224), batch,
                                  use_native=True, shuffle=True,
                                  rand_crop=True, rand_mirror=True,
                                  preprocess_threads=th)
            table[str(th)] = round(epoch_rate(it2), 1)
        row["thread_scaling_images_per_sec"] = table
        row["thread_scaling_note"] = (
            f"{ncores}-core host: entries beyond {2 * ncores} threads "
            "omitted; entries beyond the core count oversubscribe and "
            "are expected flat")
    return row


def _offered_load(server, gen_sample, offered_qps, duration_s):
    """Fire requests at a fixed offered rate (open-loop client with
    catch-up arithmetic — the honest overload model: arrivals do NOT
    slow down because the server is behind), then wait for completions
    and report the latency distribution and achieved goodput."""
    from mxnet_tpu.serving import ServingError

    t_start = time.monotonic()
    t_end = t_start + duration_s
    futs, rejected, offered = [], 0, 0
    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        due = int((now - t_start) * offered_qps) - offered
        for _ in range(due):
            offered += 1
            try:
                futs.append(server.submit(*gen_sample()))
            except ServingError:
                rejected += 1
        time.sleep(0.002)
    lats = []
    for f in futs:
        try:
            f.result(timeout=60)
            lats.append((f.t_done - f.t_enqueue) * 1e3)
        except Exception:  # noqa: BLE001 — deadline/shed rejections
            rejected += 1
    lats.sort()
    completed = len(lats)

    def pct(q):
        if not lats:
            return 0.0
        return round(lats[min(completed - 1,
                              int(q / 100.0 * completed))], 2)

    return {"offered": offered, "completed": completed,
            "rejected": rejected,
            "achieved_qps": round(completed / duration_s, 1),
            "p50_ms": pct(50), "p99_ms": pct(99)}


def _max_sustainable(server, gen_sample, trial_s=1.2,
                     p50_budget_ms=250.0):
    """Geometric ramp search for the highest offered rate the server
    sustains (>=95% goodput — i.e. the bounded admission queue did not
    overflow into 429s — and MEDIAN latency within budget; the median,
    not p99, keeps one scheduler stall on a noisy shared host from
    reading as a capacity cliff).  Each trial drains fully before the
    next, so backlog never bleeds across rates."""
    rate, best_rate, best_row, retried = 25.0, 0.0, None, False
    while rate < 50000:
        row = _offered_load(server, gen_sample, rate, trial_s)
        if row["completed"] < 0.95 * row["offered"] or \
                row["p50_ms"] > p50_budget_ms:
            # one retry per rate: a single scheduler stall on a shared
            # host must not read as the capacity cliff
            if retried:
                break
            retried = True
            continue
        retried = False
        best_rate, best_row = rate, row
        rate *= 1.7
    return best_rate, best_row


def _serving_pair(make_server, gen_sample, warm_samples, duration_s):
    """The acceptance comparison, twice over:

    1. **max sustainable QPS** — geometric ramp per mode: the highest
       offered rate each sustains at >=95% goodput with bounded p99;
    2. **fixed offered load** — BOTH modes at 1.5x the serial ceiling
       (overload for serial, headroom for batching): p50/p99, goodput,
       and 429s, plus the batch-formation efficiency.
    """
    serial = make_server(1, 1)
    serial.warmup(*warm_samples)
    serial.start()
    serial.infer(*gen_sample(), timeout=60)      # settle the path
    serial_max, _ = _max_sustainable(serial, gen_sample)
    offered_qps = max(40.0, 1.5 * serial_max)
    serial_row = _offered_load(serial, gen_sample, offered_qps,
                               duration_s)
    serial.stop()

    batched = make_server(None, None)        # knob/default batch+workers
    batched.warmup(*warm_samples)
    batched.start()
    batched.infer(*gen_sample(), timeout=60)
    batched_max, _ = _max_sustainable(batched, gen_sample)
    t0r, t0p = batched._c_real.n, batched._c_padded.n
    batched_row = _offered_load(batched, gen_sample, offered_qps,
                                duration_s)
    real = batched._c_real.n - t0r
    padded = batched._c_padded.n - t0p      # sequence-pad positions only
    batched_row["batch_efficiency"] = round(real / (real + padded), 3) \
        if real + padded else 0.0
    batched.stop()

    qps_win = round(batched_max / max(serial_max, 0.1), 2)
    p99_win = round(serial_row["p99_ms"] /
                    max(batched_row["p99_ms"], 1e-3), 2)
    return {"offered_qps": round(offered_qps, 1),
            "max_sustainable_qps_serial": round(serial_max, 1),
            "max_sustainable_qps_batched": round(batched_max, 1),
            "batched": batched_row, "serial": serial_row,
            "qps_win": qps_win, "p99_win": p99_win,
            "dynamic_batching_wins": bool(qps_win > 1.0 or p99_win > 1.0)}


def bench_serving(duration_s=3.0):
    """Serving row: continuous-batching ModelServer vs batch-size-1
    serial dispatch at the SAME offered load, on the MNIST-MLP (fixed
    shape, batch buckets only) and a BERT encoder (padding-length
    buckets — bert_base).
    Reports p50/p99 latency, achieved QPS, rejects, and the
    batch-formation efficiency (real/padded elements)."""
    import mxnet_tpu as mx  # noqa: F401 — backend/session init
    from mxnet_tpu import gluon
    from mxnet_tpu.serving import ModelServer

    rng = np.random.default_rng(0)
    rows = {}

    # --- MNIST-MLP: the dispatch-overhead workload -----------------------
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(128, activation="relu"),
                gluon.nn.Dense(64, activation="relu"),
                gluon.nn.Dense(10))
    net.initialize()
    net.hybridize()

    def mlp_sample():
        return (rng.standard_normal((784,)).astype(np.float32),)

    def mlp_server(max_batch, workers):
        return ModelServer(
            net, max_batch=max_batch or 16, workers=workers or 2,
            queue_depth=64, deadline_ms=0, batch_window_us=2000)

    rows["mnist_mlp"] = _serving_pair(mlp_server, mlp_sample,
                                      [mlp_sample()], duration_s)

    # --- BERT: the padding-length-bucketed workload ----------------------
    from mxnet_tpu.gluon.model_zoo.transformer import bert_base
    bert = bert_base(dropout=0.0)
    bert.initialize()
    bert.hybridize()
    lengths = (32, 64, 128)
    vocab = 30522

    def bert_sample():
        n = int(rng.integers(16, 129))
        toks = rng.integers(0, vocab, (n,)).astype(np.int32)
        segs = np.zeros((n,), np.int32)
        return toks, segs

    def bert_server(max_batch, workers):
        return ModelServer(
            bert, max_batch=max_batch or 8, workers=workers or 2,
            batch_buckets=None if max_batch == 1 else (1, 8),
            length_buckets=lengths, queue_depth=64, deadline_ms=0,
            batch_window_us=3000)

    warm = [(np.zeros((n,), np.int32), np.zeros((n,), np.int32))
            for n in lengths]
    rows["bert_base"] = _serving_pair(
        bert_server, bert_sample, warm, duration_s)

    rows["requests_per_sec"] = \
        rows["mnist_mlp"]["batched"]["achieved_qps"]
    return rows


# ---------------------------------------------------------------------------
# frontend row: two models with conflicting diurnal load on one HTTP host —
# the SloController defends the priority model's p99 by shedding the other
# ---------------------------------------------------------------------------


def bench_frontend(duration_s=2.0):
    """Frontend row: TWO models behind one :class:`HttpFrontend` over
    real sockets — a high-priority MLP carrying a p99 SLO, and a
    low-priority heavy model whose diurnal load ramps calm → surge →
    calm.  The same three-phase offered-load script runs twice: with no
    controller (the surge tramples the priority tail) and with the
    SloController ticking (the low-priority class 429s at the door and
    the priority p99 comes back under its SLO — ``surge_settled`` is
    the second half of the surge, after the control loop's reaction
    time).  Also streams SSE generations for the socket-measured TTFT
    tail (the <10ms wire-overhead budget)."""
    import http.client
    import socket as socketlib
    import threading

    import mxnet_tpu as mx  # noqa: F401 — backend/session init
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.transformer import causal_lm_small
    from mxnet_tpu.serving import (GenerationServer, HttpFrontend,
                                   ModelRegistry, ModelServer)
    from mxnet_tpu.tuning import SloController

    rng = np.random.default_rng(0)
    SLO_MS = 30.0
    PRIO_RPS = 30.0
    SURGE_HAMMERS = 6

    def _mlp(in_units, units):
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(units, activation="relu",
                                   in_units=in_units),
                    gluon.nn.Dense(units, activation="relu",
                                   in_units=units),
                    gluon.nn.Dense(10, in_units=units))
        net.initialize()
        net.hybridize()
        return net

    x_prio = rng.standard_normal((784,)).astype(np.float32)
    x_heavy = rng.standard_normal((1024,)).astype(np.float32)
    prio_body = json.dumps({"inputs": [x_prio.tolist()],
                            "dtype": "float32"})
    heavy_body = json.dumps({"inputs": [x_heavy.tolist()],
                             "dtype": "float32"})

    def run_pass(with_controller):
        reg = ModelRegistry()
        reg.load("prio", ModelServer(
            _mlp(784, 128), max_batch=8, workers=2, queue_depth=256,
            deadline_ms=0, batch_window_us=1000),
            priority=3, slo_ms=SLO_MS, warm=[(x_prio,)])
        reg.load("batch", ModelServer(
            _mlp(1024, 1024), max_batch=8, workers=2, queue_depth=256,
            deadline_ms=0, batch_window_us=1000),
            priority=1, slo_ms=0.0, warm=[(x_heavy,)])
        fe = HttpFrontend(reg, port=0).start()
        port = fe.port

        ctl = SloController(reg, enabled=True, dry_run=False,
                            min_requests=4, recover_intervals=2,
                            hysteresis=1) if with_controller else None
        stop_ctl = threading.Event()
        shed_seen = [0]

        def ctl_loop():
            while not stop_ctl.wait(0.2):
                try:
                    ctl.tick()
                except Exception:  # noqa: BLE001 — keep ticking
                    pass
                shed_seen[0] = max(shed_seen[0], reg.shed_level)

        # diurnal low-priority load: one always-on client plus a surge
        # pool that only hammers during the middle window
        done = threading.Event()
        surge_on = threading.Event()
        batch_200, batch_429 = [0], [0]
        cnt_lock = threading.Lock()

        def hammer(always):
            c = http.client.HTTPConnection("127.0.0.1", port,
                                           timeout=60)
            while not done.is_set():
                if not always and not surge_on.is_set():
                    time.sleep(0.02)
                    continue
                try:
                    c.request("POST", "/v1/models/batch/predict",
                              body=heavy_body)
                    st = c.getresponse()
                    st.read()
                    with cnt_lock:
                        if st.status == 200:
                            batch_200[0] += 1
                        elif st.status == 429:
                            batch_429[0] += 1
                    if st.status == 429:
                        time.sleep(0.05)   # the 429 contract: back off
                except OSError:
                    try:
                        c.close()
                    except OSError:
                        pass
                    c = http.client.HTTPConnection("127.0.0.1", port,
                                                   timeout=60)
            c.close()

        # priority client: fixed-rate open-loop arrivals (no coordinated
        # omission — a slow response never delays the next arrival)
        lat = []
        lat_lock = threading.Lock()

        def one_prio(t_sched):
            c = http.client.HTTPConnection("127.0.0.1", port,
                                           timeout=60)
            t0 = time.perf_counter()
            try:
                c.request("POST", "/v1/models/prio/predict",
                          body=prio_body)
                r = c.getresponse()
                r.read()
                st = r.status
            except OSError:
                st = -1
            finally:
                c.close()
            with lat_lock:
                lat.append((t_sched,
                            (time.perf_counter() - t0) * 1e3, st))

        threads = [threading.Thread(target=hammer, args=(True,),
                                    daemon=True)]
        threads += [threading.Thread(target=hammer, args=(False,),
                                     daemon=True)
                    for _ in range(SURGE_HAMMERS)]
        ctl_thread = threading.Thread(target=ctl_loop, daemon=True)
        if ctl is not None:
            ctl.tick()              # prime the interval baselines
            ctl_thread.start()
        for t in threads:
            t.start()

        d = duration_s
        total = 4 * d               # calm | surge(2d) | recover
        prio_threads = []
        t_start = time.perf_counter()
        k = 0
        while True:
            now = time.perf_counter() - t_start
            if now >= total:
                break
            if d <= now < 3 * d:
                surge_on.set()
            else:
                surge_on.clear()
            t_k = k / PRIO_RPS
            if now >= t_k:
                th = threading.Thread(target=one_prio, args=(t_k,),
                                      daemon=True)
                th.start()
                prio_threads.append(th)
                k += 1
            else:
                time.sleep(min(t_k - now, 0.005))
        done.set()
        surge_on.clear()
        for th in prio_threads:
            th.join(timeout=60)
        stop_ctl.set()
        if ctl is not None:
            ctl_thread.join(timeout=5)
        workers_final = int(reg.get("prio").server.workers)
        fe.stop(drain=True)

        def phase(lo, hi):
            vals = sorted(v for t, v, s in lat
                          if lo <= t < hi and s == 200)
            return {"n": len(vals),
                    "p50_ms": round(_gen_percentile(vals, 0.50), 2),
                    "p99_ms": round(_gen_percentile(vals, 0.99), 2)}

        return {"phases": {"calm": phase(0, d),
                           "surge_early": phase(d, 2 * d),
                           "surge_settled": phase(2 * d, 3 * d),
                           "recover": phase(3 * d, 4 * d)},
                "priority_errors": sum(1 for _, _, s in lat
                                       if s not in (200,)),
                "batch_200": batch_200[0],
                "batch_429": batch_429[0],
                "max_shed_level": shed_seen[0],
                "prio_workers_final": workers_final}

    off = run_pass(with_controller=False)
    on = run_pass(with_controller=True)

    # --- SSE TTFT through the socket -------------------------------------
    lm = causal_lm_small()
    lm.initialize()
    lm.hybridize()
    reg = ModelRegistry()
    reg.load("lm", GenerationServer(
        lm, slots=4, kv_block=16, kv_blocks=64, max_new_tokens=8,
        prompt_buckets=(16,), queue_depth=64, deadline_ms=0),
        priority=1, warm=True)
    fe = HttpFrontend(reg, port=0).start()
    ttfts = []
    try:
        for i in range(30):
            n = int(rng.integers(4, 13))
            body = json.dumps({
                "prompt": [int(t) for t in rng.integers(1, 250, (n,))],
                "max_new_tokens": 8})
            s = socketlib.create_connection(("127.0.0.1", fe.port),
                                            timeout=60)
            try:
                t0 = time.perf_counter()
                s.sendall(("POST /v1/models/lm/generate HTTP/1.1\r\n"
                           f"Host: x\r\nContent-Length: {len(body)}"
                           "\r\n\r\n" + body).encode())
                buf = b""
                while b"data:" not in buf:
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
                ttft_ms = (time.perf_counter() - t0) * 1e3
                while s.recv(65536):   # drain: server closes SSE conns
                    pass
            finally:
                s.close()
            if i >= 5:                 # settle scheduler/alloc jitter
                ttfts.append(ttft_ms)
    finally:
        fe.stop(drain=True)
    ttfts.sort()

    off_p99 = off["phases"]["surge_settled"]["p99_ms"]
    on_p99 = on["phases"]["surge_settled"]["p99_ms"]
    return {
        "slo_ms": SLO_MS,
        "priority_offered_rps": PRIO_RPS,
        "without_slo_controller": off,
        "with_slo_controller": on,
        "surge_p99_no_controller_ms": off_p99,
        "surge_p99_with_controller_ms": on_p99,
        "slo_violated_without_controller": bool(off_p99 > SLO_MS),
        "slo_held_with_controller": bool(0 < on_p99 <= SLO_MS),
        "batch_shed_429": on["batch_429"],
        "surge_p99_improvement_x": round(
            off_p99 / max(on_p99, 1e-3), 2),
        "sse_ttft_p50_ms": round(_gen_percentile(ttfts, 0.50), 2),
        "sse_ttft_p99_ms": round(_gen_percentile(ttfts, 0.99), 2),
        "sse_generations": len(ttfts),
    }


# ---------------------------------------------------------------------------
# generation row: token-level continuous batching vs the whole-sequence
# batcher
# ---------------------------------------------------------------------------

_GEN_PROMPT_RANGE = (4, 15)     # sampled prompt lengths (bucket 16)
_GEN_MAX_NEW = 48               # tokens per generation — long enough
                                # that the whole-sequence baseline's
                                # grow-and-recompute cost is the real
                                # per-token cost, not dispatch overhead


def _gen_percentile(sorted_vals, frac):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(len(sorted_vals) * frac))
    return sorted_vals[i]


def _gen_measure(launch, rate_rps, duration_s, grace_s=6.0):
    """Offer generations at ``rate_rps`` for ``duration_s`` (fixed
    arrival schedule, no coordinated omission: the k-th arrival fires at
    t0 + k/rate regardless of how slow earlier ones are), then wait out
    the grace window and report tokens/s + TTFT percentiles over the
    completions.  ``launch(prompt)`` starts ONE generation and returns a
    ``wait(deadline) -> (ttft_s, n_tokens) | None`` closure."""
    rng = np.random.default_rng(7)
    waiters = []
    t0 = time.monotonic()
    k = 0
    while True:
        due = t0 + k / rate_rps
        now = time.monotonic()
        if due - t0 >= duration_s:
            break
        if due > now:
            time.sleep(due - now)
        n = int(rng.integers(*_GEN_PROMPT_RANGE))
        prompt = rng.integers(1, 250, (n,)).astype(np.int32)
        waiters.append(launch(prompt))
        k += 1
    deadline = t0 + duration_s + grace_s
    done = []
    for w in waiters:
        r = w(deadline)
        if r is not None:
            done.append(r)
    wall = time.monotonic() - t0
    tokens = sum(n for _, n in done)
    ttfts = sorted(t * 1e3 for t, _ in done)
    launched = len(waiters)
    return {
        "offered_rps": round(rate_rps, 2),
        "launched": launched,
        "completed": len(done),
        "goodput": round(len(done) / launched, 3) if launched else 0.0,
        "tokens_s": round(tokens / wall, 1) if wall > 0 else 0.0,
        "ttft_p50_ms": round(_gen_percentile(ttfts, 0.50), 2),
        "ttft_p99_ms": round(_gen_percentile(ttfts, 0.99), 2),
        "wall_s": round(wall, 2),
    }


def _gen_ramp(launch, duration_s=2.5, start_rps=4.0, max_rps=512.0,
              growth=1.4):
    """The PR-7 serving-row ramp discipline: geometric offered-rate
    ramp, highest rate sustained at >=95% goodput wins; one retry per
    rate so a single scheduler stall on a shared host does not read as
    the capacity cliff.  The 1.4x growth keeps the parked rate within
    ~30% of the true knee — the comparison cells offer a multiple of
    it, so ramp undershoot directly understates the measured win.
    Sustained means BOTH >=95% goodput AND the backlog cleared in near
    real time (wall <= duration + a generation-latency slack): a cell
    that only completes by eating the grace window is already past the
    knee even though every request eventually finished."""
    best_rate, best_row = 0.0, None
    rate, retried = start_rps, False
    while rate <= max_rps:
        row = _gen_measure(launch, rate, duration_s)
        if row["goodput"] < 0.95 or row["wall_s"] > duration_s + 1.5:
            if retried:
                break
            retried = True
            continue
        retried = False
        best_rate, best_row = rate, row
        rate *= growth
    return best_rate, best_row


def _gen_lm():
    """The generation rows' shared model: the 2-layer CausalLM,
    seeded identically in every cell so greedy decode is comparable
    across schedulers."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import causal_lm_small
    np.random.seed(0)
    mx.random.seed(0)
    lm = causal_lm_small()
    lm.initialize()
    lm.hybridize()
    return lm


def _generate_one_main(spec):
    """Entry for ONE generation cell subprocess (``--generate-one
    whole_seq:ramp`` / ``whole_seq:RATE`` / ``continuous:MODE:RATE``).
    Pinned to the same two cores in every cell, so the scheduler is the
    only variable across cells."""
    try:
        os.sched_setaffinity(0, set(range(2)))
    except (AttributeError, OSError):
        pass   # non-linux / restricted: unpinned, still measured
    import threading

    parts = spec.split(":")
    kind = parts[0]
    lm = _gen_lm()

    if kind == "whole_seq":
        # the era-native baseline: every decode step re-submits the
        # GROWING sequence through the request-level batcher and runs a
        # FULL causal forward over it — the longest request in a batch
        # holds every slot member hostage, and each token recomputes
        # the whole prefix
        from mxnet_tpu.serving import ModelServer
        srv = ModelServer(lm, max_batch=4, workers=2,
                          length_buckets=(16, 32, 64), pad_axis=0,
                          queue_depth=256, deadline_ms=0,
                          batch_window_us=2000)
        srv.warmup((np.zeros((16,), np.int32),),
                   (np.zeros((32,), np.int32),),
                   (np.zeros((64,), np.int32),))
        srv.start()

        def launch(prompt):
            out = {}

            def run():
                t0 = time.monotonic()
                seq = [int(v) for v in prompt]
                ttft = None
                for _ in range(_GEN_MAX_NEW):
                    logits = srv.infer(np.asarray(seq, np.int32),
                                       timeout=60)
                    nxt = int(np.asarray(
                        logits.asnumpy() if hasattr(logits, "asnumpy")
                        else logits)[len(seq) - 1].argmax())
                    if ttft is None:
                        ttft = time.monotonic() - t0
                    seq.append(nxt)
                out["r"] = (ttft, _GEN_MAX_NEW)

            th = threading.Thread(target=run, daemon=True)
            th.start()

            def wait(deadline):
                th.join(max(0.0, deadline - time.monotonic()))
                return out.get("r")
            return wait

        if parts[1] == "ramp":
            max_rate, row = _gen_ramp(launch)
            srv.stop()
            print(json.dumps({"max_rate": round(max_rate, 2),
                              "at_max": row}))
        else:
            row = _gen_measure(launch, float(parts[1]), duration_s=4.0)
            srv.stop()
            print(json.dumps(row))
        return

    # kind == "continuous": the token-level scheduler under test
    mode, rate = parts[1], float(parts[2])
    os.environ["MXTPU_SERVING_PREFILL_MODE"] = mode
    from mxnet_tpu.serving import GenerationServer, ServingError
    srv = GenerationServer(lm, slots=4, kv_block=16, kv_blocks=128,
                           max_new_tokens=_GEN_MAX_NEW,
                           prompt_buckets=(16,), queue_depth=256,
                           deadline_ms=0)
    srv.start()
    srv.warmup()

    def launch(prompt):
        try:
            req = srv.submit_generate(prompt,
                                      max_new_tokens=_GEN_MAX_NEW)
        except ServingError:
            return lambda deadline: None       # shed = failed offer
        def wait(deadline):
            if not req._event.wait(max(0.0, deadline -
                                       time.monotonic())):
                return None
            if req._error is not None:
                return None
            return (req.t_first - req.t_enqueue, len(req.tokens))
        return wait

    row = _gen_measure(launch, rate, duration_s=4.0)
    row["kv_blocks_leaked"] = srv.stats()["kv_blocks_used"]
    srv.stop()
    print(json.dumps(row))


def bench_generate(per_cell_timeout=600):
    """Generation row (the token-level continuous-batching acceptance):
    tokens/s and TTFT p50/p99 for the iteration-level decode scheduler
    vs the whole-sequence batcher at the SAME offered load.

    Cells run in their own CPU-forced subprocesses pinned to the same
    two cores (the multichip/overlap grid discipline): first the
    whole-sequence ramp finds the baseline's max sustainable generation
    rate, then all three schedulers — whole-sequence, continuous with
    interleaved prefill, continuous with batch-first (``step``) prefill
    — are measured at 2x that ceiling (overload for the baseline,
    headroom for the token-level scheduler)."""
    ramp = _grid_cell("--generate-one", "whole_seq:ramp",
                      per_cell_timeout)
    serial_max = float(ramp.get("max_rate") or 1.0)
    offered = max(2.0, round(2.0 * serial_max, 2))
    row = {"max_sustainable_rps_whole_seq": serial_max,
           "offered_rps": offered,
           "whole_sequence": _grid_cell(
               "--generate-one", f"whole_seq:{offered}",
               per_cell_timeout)}
    for mode in ("interleave", "step"):
        row[f"continuous_{mode}"] = _grid_cell(
            "--generate-one", f"continuous:{mode}:{offered}",
            per_cell_timeout)
    ws = row["whole_sequence"]
    best_mode, best = max(
        ((m, row[f"continuous_{m}"]) for m in ("interleave", "step")),
        key=lambda kv: kv[1].get("tokens_s", 0.0))
    row["best_continuous_mode"] = best_mode
    if ws.get("tokens_s") and best.get("tokens_s"):
        row["tokens_s_win"] = round(best["tokens_s"] / ws["tokens_s"],
                                    2)
        row["ttft_p99_win"] = round(
            ws["ttft_p99_ms"] / max(best["ttft_p99_ms"], 1e-3), 2)
        row["continuous_wins"] = bool(row["tokens_s_win"] > 1.0
                                      and row["ttft_p99_win"] > 1.0)
    return row


_WARM_START_SCRIPT = """
import json, os, sys, time
sys.path.insert(0, os.environ["MXTPU_BENCH_ROOT"])
t0 = time.perf_counter()
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
x = nd.ones((4096,))                      # exact-mode segment chain
y = x
for _ in range(48):
    y = y * 1.0001 + 0.0001
    y = nd.tanh(y)
seg = y.asnumpy()
net = gluon.nn.HybridSequential()         # cached-graph (serving) path
with net.name_scope():
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(10))
net.initialize()
net.hybridize()
g = net.cached_graph(np.ones((16, 784), np.float32))
out = g(nd.array(np.ones((16, 784), np.float32)))
build_s = time.perf_counter() - t0
import hashlib
def sha(a):
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()
from mxnet_tpu.observability.registry import registry
snap = registry().snapshot()
print("RESULT " + json.dumps({
    "time_to_first_inference_s": round(build_s, 3),
    "compiles": snap.get("tuning.compiles", 0),
    "cache_hits": snap.get("tuning.compile_cache_hits", 0),
    "out_sha": sha(out.asnumpy()) + ":" + sha(seg),
}))
"""


def _pin_cpu_mesh(dp):
    """Shared preamble of every multichip/overlap grid cell: pin THIS
    process to dp cores BEFORE the first jax import (XLA's
    execution-pool threads inherit the main thread's affinity at
    client creation — set it later and every virtual chip still sees
    the whole host), then force a dp-device virtual CPU mesh.  One
    pinned core per virtual chip keeps per-chip resources constant
    across dp — the weak-scaling contract a real pod slice has."""
    try:
        os.sched_setaffinity(0, set(range(dp)))
    except (AttributeError, OSError):
        pass   # non-linux / restricted: unpinned, still measured
    from mxnet_tpu.base import force_cpu_mesh
    force_cpu_mesh(dp)


def _weak_scaling_mlp(dp, zero=0, comm_bucket_mb=0.0):
    """The multichip/overlap rows' shared model: MLP 784-1024-1024-10,
    adam, fp32, seeded identically, on a dp-device mesh."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import nn, loss as gloss
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(1024, activation="relu", in_units=784),
                nn.Dense(1024, activation="relu", in_units=1024),
                nn.Dense(10, in_units=1024))
    net.initialize()
    return par.ShardedTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=par.make_mesh({"dp": dp}),
        zero_stage=zero, comm_bucket_mb=comm_bucket_mb)


def _grid_cell(flag, spec, timeout):
    """Run ONE grid-config subprocess (``bench.py <flag> <spec>``)
    with the CPU-forced env and parse its one-JSON-line stdout; a
    failure becomes an ``{"error": ...}`` cell so one dead config
    never zeroes its row — the shared cell discipline of the
    multichip and overlap rows."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag, spec],
            capture_output=True, text=True, timeout=timeout, env=env)
        return json.loads(r.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _multichip_one_main(spec):
    """Entry for ONE multichip config subprocess (``--multichip-one
    dp,zero``): time the ZeRO-sharded step on the pinned-core virtual
    CPU mesh (see :func:`_pin_cpu_mesh`)."""
    dp, zero = (int(v) for v in spec.split(","))
    _pin_cpu_mesh(dp)
    import jax
    tr = _weak_scaling_mlp(dp, zero)
    per_chip, iters, warmup = 256, 10, 3
    B = per_chip * dp
    x = np.random.randn(B, 784).astype(np.float32)
    y = np.random.randint(0, 10, (B,))
    xs, ys = tr.shard_batch(x, y)
    for _ in range(warmup):
        tr.step(xs, ys)
    jax.block_until_ready(tr._pvals)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = tr.step(xs, ys)
    jax.block_until_ready(loss._read())
    dt = time.perf_counter() - t0
    print(json.dumps({
        "dp": dp, "zero_stage": zero,
        "img_s": round(B * iters / dt, 1),
        "opt_state_bytes_per_chip": tr.peak_opt_state_bytes(),
        "global_batch": B,
    }))


def bench_multichip(per_config_timeout=600):
    """Multichip row (ROADMAP #3 acceptance): weak-scaling aggregate
    img/s and peak optimizer-state bytes/chip for the ZeRO-sharded
    training step, dp=1/2/4/8 x zero_stage=0/1/2, on the virtual
    CPU-host mesh.  Every config runs in its own subprocess because the
    core pinning must precede XLA client creation (see
    ``_multichip_one_main``); zero_stage changes the STATE LAYOUT only,
    so its img/s columns double as a collective-overhead check while
    the bytes columns are the ZeRO story.  The on-chip (real pod
    slice) rerun is queued in the PERF.md runbook."""
    import sys
    grid = {}
    for dp in (1, 2, 4, 8):
        for zero in (0, 1, 2):
            grid.setdefault(f"dp{dp}", {})[f"zero{zero}"] = _grid_cell(
                "--multichip-one", f"{dp},{zero}", per_config_timeout)
    row = {"model": "mlp 784-1024-1024-10, adam, fp32",
           "per_chip_batch": 256,
           "chip": "1 pinned CPU core per virtual chip (weak scaling: "
                   "global batch = 256 x dp)",
           "grid": grid}
    try:
        base = grid["dp1"]["zero0"]["img_s"]
        for dp in (2, 4, 8):
            v = grid[f"dp{dp}"]["zero0"]["img_s"]
            row[f"speedup_dp{dp}"] = round(v / base, 2)
            row[f"scaling_efficiency_dp{dp}"] = round(v / (dp * base), 3)
        b0 = grid["dp4"]["zero0"]["opt_state_bytes_per_chip"]
        row["opt_state_reduction_zero1_dp4"] = round(
            1 - grid["dp4"]["zero1"]["opt_state_bytes_per_chip"] / b0, 3)
        row["opt_state_reduction_zero2_dp4"] = round(
            1 - grid["dp4"]["zero2"]["opt_state_bytes_per_chip"] / b0, 3)
        # the satellite's 'scaling efficiency printed' — stderr, the
        # stdout line stays the one-JSON protocol
        print(f"multichip: dp2 {row['speedup_dp2']}x / dp4 "
              f"{row['speedup_dp4']}x / dp8 {row['speedup_dp8']}x "
              f"aggregate img/s vs dp1 (efficiency "
              f"{row['scaling_efficiency_dp2']}, "
              f"{row['scaling_efficiency_dp4']}, "
              f"{row['scaling_efficiency_dp8']}); zero1 opt-state "
              f"-{100 * row['opt_state_reduction_zero1_dp4']:.0f}%/chip "
              f"at dp4", file=sys.stderr)
    except (KeyError, TypeError, ZeroDivisionError):
        row["error_summary"] = "one or more grid cells failed " \
                               "(see grid entries)"
    return row


def _overlap_one_main(spec):
    """Entry for ONE overlap config subprocess (``--overlap-one
    MODE:ARGS``) — same discipline as the multichip row: pin THIS
    process to dp cores BEFORE the first jax import, one pinned core
    per virtual chip, then measure one overlap configuration.

    - ``bucket:dp,zero,mb`` — step time of the ZeRO-sharded step with
      the gradient reduction fused (mb=0) vs bucketed (comm_bucket_mb);
    - ``prefetch:dp,depth`` — per-step wall time of a DataLoader-fed
      training loop with the device double-buffer off (0) vs N-deep
      (every step pays / hides the host→device ingestion transfer);
    - ``ckpt:dp,async`` — a training loop with periodic host-local npz
      checkpoints: the per-save boundary stall and the loop wall time,
      blocking (async=0) vs background commit (async=1).
    """
    mode, args = spec.split(":", 1)
    vals = args.split(",")
    dp = int(vals[0])
    _pin_cpu_mesh(dp)
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import nn, loss as gloss

    per_chip = 256
    B = per_chip * dp
    rng = np.random.RandomState(0)
    x = rng.randn(B, 784).astype(np.float32)
    y = rng.randint(0, 10, (B,))

    if mode == "bucket":
        zero, mb = int(vals[1]), float(vals[2])
        tr = _weak_scaling_mlp(dp, zero, comm_bucket_mb=mb)
        xs, ys = tr.shard_batch(x, y)    # device-resident: this cell
        iters, warmup = 12, 3            # measures the STEP, not ingest
        for _ in range(warmup):
            tr.step(xs, ys)
        jax.block_until_ready(tr._pvals)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = tr.step(xs, ys)
        jax.block_until_ready(loss._read())
        dt = time.perf_counter() - t0
        print(json.dumps({
            "dp": dp, "zero_stage": zero, "comm_bucket_mb": mb,
            "n_buckets": len(tr.grad_buckets or []) or 1,
            "step_us": round(dt / iters * 1e6, 1),
            "img_s": round(B * iters / dt, 1)}))
    elif mode == "prefetch":
        depth = int(vals[1])
        from mxnet_tpu.gluon.data import DataLoader
        # a SMALL model on purpose: the cell measures the ingestion
        # transfer on the step's critical path, so the step must not
        # dwarf it (the bucket cells own the big-model story).  The
        # dataset is pre-batched (one sample IS one batch, pass-through
        # batchify), so host-side batch assembly — a separate, already-
        # overlapped pipeline stage — cannot drown the transfer either.
        np.random.seed(0)
        mx.random.seed(0)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(256, activation="relu", in_units=784),
                    nn.Dense(10, in_units=256))
        net.initialize()
        tr = par.ShardedTrainer(
            net, gloss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 1e-3}, mesh=par.make_mesh({"dp": dp}))
        n_batches = 16
        ds = [(rng.randn(B, 784).astype(np.float32),
               rng.randint(0, 10, (B,)).astype(np.float32))
              for _ in range(n_batches)]
        loader = DataLoader(ds, batch_size=1, num_workers=1,
                            batchify_fn=lambda s: s[0],
                            device_prefetch=depth,
                            device_put_fn=tr.place_batch)
        for xb, yb in loader:            # epoch 0: build + compile
            tr.step(xb, yb)
        losses = None
        t0 = time.perf_counter()
        for _ in range(3):
            for xb, yb in loader:
                losses = tr.step(xb, yb)
        jax.block_until_ready(losses._read())
        dt = time.perf_counter() - t0
        steps = 3 * n_batches
        print(json.dumps({
            "dp": dp, "device_prefetch": depth,
            "batch_bytes": int(B * 784 * 4),
            "step_us": round(dt / steps * 1e6, 1),
            "img_s": round(B * steps / dt, 1)}))
    elif mode == "ckpt":
        import tempfile
        os.environ["MXTPU_ASYNC_CKPT"] = vals[1]
        tr = _weak_scaling_mlp(dp)
        tr.host_local_ckpt = True        # the npz fleet path, 1 process
        xs, ys = tr.shard_batch(x, y)
        for _ in range(3):
            tr.step(xs, ys)
        jax.block_until_ready(tr._pvals)
        stalls = []
        with tempfile.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            for i in range(12):
                loss = tr.step(xs, ys)
                if (i + 1) % 3 == 0:
                    # the BOUNDARY STALL: what the save call costs the
                    # step loop.  The blocking path pays the full
                    # serialize+commit here; the async path only the
                    # device_get snapshot + thread handoff.
                    s0 = time.perf_counter()
                    tr.save_checkpoint(d)
                    stalls.append(time.perf_counter() - s0)
            jax.block_until_ready(loss._read())
            # the final commit drains INSIDE the timed region: the
            # async cell's last write has no steps left to hide
            # behind, and excluding its tail would overstate the loop
            # win by ~one commit per measurement window
            tr.wait_checkpoint()
            wall = time.perf_counter() - t0
        stalls.sort()
        print(json.dumps({
            "dp": dp, "async": vals[1] == "1", "saves": len(stalls),
            "save_stall_us": round(
                stalls[len(stalls) // 2] * 1e6, 1),
            "loop_wall_us_per_step": round(wall / 12 * 1e6, 1)}))
    else:
        raise SystemExit(f"unknown overlap mode {mode!r}")


def bench_overlap(per_config_timeout=600):
    """Overlap row (ROADMAP #4 / 'hide the fleet' acceptance): the
    three serialized phases measured against their overlapped
    versions on the pinned-core CPU mesh — (a) fused vs bucketed
    gradient reduce-scatter at dp=4/8 (zero_stage=1), (b) device-input
    double buffering off vs 2-deep at dp=4, (c) blocking vs async
    host-local checkpoint commit at dp=4.  Every cell runs in its own
    core-pinned subprocess (the multichip discipline: affinity must
    precede XLA client creation).  The on-chip half — confirming the
    latency-hiding scheduler actually interleaves the per-bucket
    collectives — is queued in the PERF.md runbook."""
    import sys

    def cell(spec):
        return _grid_cell("--overlap-one", spec, per_config_timeout)

    rows = {}
    for dp in (4, 8):
        g = {"off": cell(f"bucket:{dp},1,0"),
             "bucket_1mb": cell(f"bucket:{dp},1,1"),
             "bucket_4mb": cell(f"bucket:{dp},1,4")}
        try:
            best = min(g["bucket_1mb"]["step_us"],
                       g["bucket_4mb"]["step_us"])
            g["step_improvement_x"] = round(g["off"]["step_us"] / best, 3)
        except (KeyError, TypeError, ZeroDivisionError):
            pass
        rows[f"grad_bucket_dp{dp}"] = g
    p = {"off": cell("prefetch:4,0"), "depth2": cell("prefetch:4,2")}
    try:
        p["step_improvement_x"] = round(
            p["off"]["step_us"] / p["depth2"]["step_us"], 3)
    except (KeyError, TypeError, ZeroDivisionError):
        pass
    rows["device_prefetch_dp4"] = p
    c = {"blocking": cell("ckpt:4,0"), "async": cell("ckpt:4,1")}
    try:
        c["stall_reduction_x"] = round(
            c["blocking"]["save_stall_us"] / c["async"]["save_stall_us"],
            2)
        c["step_improvement_x"] = round(
            c["blocking"]["loop_wall_us_per_step"] /
            c["async"]["loop_wall_us_per_step"], 3)
    except (KeyError, TypeError, ZeroDivisionError):
        pass
    rows["async_ckpt_dp4"] = c
    # failed cells are flagged explicitly: a .get(..., 0.0) default
    # would make an all-cells-dead row indistinguishable from a real
    # measured "no improvement"
    failed = sorted(
        k for k, v in rows.items()
        if any(isinstance(cc, dict) and "error" in cc
               for cc in v.values()))
    if failed:
        rows["error_summary"] = \
            f"cells failed in: {', '.join(failed)} (see cell entries)"
    improvements = [v["step_improvement_x"] for v in rows.values()
                    if isinstance(v, dict) and "step_improvement_x" in v]
    if improvements:
        rows["best_step_improvement_x"] = max(improvements)
        rows["async_ckpt_stall_reduction_x"] = \
            c.get("stall_reduction_x", 0.0)
        print(f"overlap: best step improvement "
              f"{rows['best_step_improvement_x']}x; async-ckpt boundary "
              f"stall -{rows['async_ckpt_stall_reduction_x']}x",
              file=sys.stderr)
    return rows


def _recommender_one_main(spec):
    """Entry for ONE recommender config subprocess
    (``--recommender-one dp,sparse``): a wide-embedding two-tower MLP
    (user/item towers over a shared 100k vocab) trained under
    Zipfian(1.05) id traffic on the pinned-core CPU mesh, timing the
    step and reading the sparse.* exchange counters back out of the
    metrics registry."""
    dp, sparse = (int(v) for v in spec.split(","))
    _pin_cpu_mesh(dp)
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.observability.registry import registry

    VOCAB, DIM, B = 100_000, 64, 2048
    np.random.seed(0)
    mx.random.seed(0)

    class TwoTower(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.user = nn.Embedding(VOCAB, DIM,
                                         sparse_grad=bool(sparse))
                self.item = nn.Embedding(VOCAB, DIM,
                                         sparse_grad=bool(sparse))
                self.user_mlp = nn.Dense(64, activation="relu")
                self.item_mlp = nn.Dense(64, activation="relu")
                self.top = nn.Dense(2)

        def hybrid_forward(self, F, x):
            u = self.user_mlp(F.flatten(
                self.user(F.slice_axis(x, axis=1, begin=0, end=1))))
            i = self.item_mlp(F.flatten(
                self.item(F.slice_axis(x, axis=1, begin=1, end=2))))
            return self.top(F.concat(u, i, dim=1))

    net = TwoTower(prefix="rec_")
    net.initialize(mx.init.Xavier(rnd_type="uniform"))
    tr = par.ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                            "adam", {"learning_rate": 1e-3},
                            mesh=par.make_mesh({"dp": dp}))
    # Zipfian(1.05) id traffic, the canonical recommender popularity
    # skew; clip folds the open tail onto the coldest id
    ids = np.minimum(np.random.zipf(1.05, (B, 2)) - 1,
                     VOCAB - 1).astype(np.float32)
    y = np.random.randint(0, 2, (B,))
    uniq = max(len(np.unique(ids[:, 0])), len(np.unique(ids[:, 1])))
    iters, warmup = 10, 3
    for _ in range(warmup):
        tr.step(ids, y)
    jax.block_until_ready(tr._pvals)
    s0 = registry().snapshot()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = tr.step(ids, y)
    jax.block_until_ready(loss._read())
    dt = time.perf_counter() - t0
    s1 = registry().snapshot()

    def delta(name):
        return (s1.get(name, 0) - s0.get(name, 0)) / iters

    print(json.dumps({
        "dp": dp, "sparse": bool(sparse),
        "step_us": round(dt / iters * 1e6, 1),
        "examples_s": round(B * iters / dt, 1),
        "unique_id_frac": round(uniq / VOCAB, 4),
        "exchange_bytes_per_step": round(delta("sparse.exchange_bytes")),
        "dense_equiv_bytes_per_step": round(
            delta("sparse.exchange_bytes_dense_equiv")),
        "grad_rows_per_step": round(delta("sparse.grad_rows")),
    }))


def bench_recommender(per_config_timeout=600):
    """Recommender row (the sparse-embedding fast-path acceptance):
    two-tower MLP over two 100k x 64 tables, Zipfian(1.05) ids
    (batch-unique ids ~2% of vocab by construction), sparse_grad on
    vs off at dp=1 and dp=4 on the pinned-core CPU mesh.  The dp=1
    comparison is the in-graph win (segment-sum backward + lazy row
    update vs dense scatter + full-table update); the dp=4 comparison
    adds the wire story — logical exchange bytes of the (ids, rows)
    layout vs the dense table-sized reduction it replaced.  The
    on-chip rerun is queued in the PERF.md runbook."""
    import sys
    grid = {}
    for dp in (1, 4):
        grid[f"dp{dp}"] = {
            "dense": _grid_cell("--recommender-one", f"{dp},0",
                                per_config_timeout),
            "sparse": _grid_cell("--recommender-one", f"{dp},1",
                                 per_config_timeout)}
    row = {"model": "two-tower MLP, 2 x (100k x 64) embedding tables, "
                    "adam, fp32, Zipfian(1.05) ids, batch 2048",
           "chip": "1 pinned CPU core per virtual chip",
           "grid": grid}
    try:
        for dp in (1, 4):
            d, s = grid[f"dp{dp}"]["dense"], grid[f"dp{dp}"]["sparse"]
            row[f"sparse_step_speedup_dp{dp}"] = round(
                d["step_us"] / s["step_us"], 2)
        sp4 = grid["dp4"]["sparse"]
        row["exchange_bytes_reduction_dp4"] = round(
            sp4["dense_equiv_bytes_per_step"] /
            sp4["exchange_bytes_per_step"], 1)
        row["unique_id_frac"] = sp4["unique_id_frac"]
        print(f"recommender: sparse step "
              f"{row['sparse_step_speedup_dp1']}x at dp1 / "
              f"{row['sparse_step_speedup_dp4']}x at dp4; exchange "
              f"bytes -{row['exchange_bytes_reduction_dp4']}x at dp4 "
              f"({100 * row['unique_id_frac']:.1f}% of vocab live "
              f"per batch)", file=sys.stderr)
    except (KeyError, TypeError, ZeroDivisionError):
        row["error_summary"] = "one or more grid cells failed " \
                               "(see grid entries)"
    return row


def bench_autotune(duration_s=2.0):
    """Autotune row — the three self-tuning acceptance comparisons:

    1. **bulk size**: manual MXNET_ENGINE_BULK_SIZE sweep (flush
       p50/p99 + throughput per size) vs the BulkSizeController's
       converged size starting from the default 15 — acceptance is the
       converged size's flush p99 landing within the measured-best
       manual size's;
    2. **serving batch window**: static default window vs the
       BatchWindowController adapting the live knob, both at the PR-7
       ramp load (1.5x the serial ceiling, the bench_serving idiom);
    3. **compile cache**: time-to-first-inference and compile counters
       for a cold process vs a second process warm-starting from
       MXTPU_COMPILE_CACHE_DIR (bitwise-equal outputs asserted).
    """
    import subprocess
    import sys
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import tuning
    from mxnet_tpu.engine import engine

    rows = {}
    eng = engine()
    rng = np.random.default_rng(0)
    size, chain = 4096, 24
    x0 = mx.nd.array(rng.standard_normal((size,), dtype=np.float32))
    a = mx.nd.array(rng.standard_normal((size,), dtype=np.float32))
    b = mx.nd.array(rng.standard_normal((size,), dtype=np.float32))
    ops_per_iter = 3 * chain

    def run(n):
        y = x0
        for _ in range(n):
            for _ in range(chain):
                y = y * a + b
                y = mx.nd.tanh(y)
        y.wait_to_read()

    prev_env = {k: os.environ.get(k) for k in
                ("MXNET_ENGINE_BULK_SIZE",
                 "MXTPU_SERVING_BATCH_WINDOW_US",
                 "MXTPU_TUNE_INTERVAL")}
    try:
        # --- 1. bulk size: manual sweep vs controller convergence ----
        def measure(bulk, iters=60):
            eng.set_bulk_size(bulk)
            run(12)                        # compile/warm at this cap
            eng.reset_stats()
            t0 = time.perf_counter()
            run(iters)
            dt = time.perf_counter() - t0
            st = eng.stats()
            return {"bulk_size": bulk,
                    "ops_per_sec": round(ops_per_iter * iters / dt, 1),
                    "flush_us_p50": st["flush_us_p50"],
                    "flush_us_p99": st["flush_us_p99"]}

        sweep = [measure(s) for s in (4, 8, 15, 30, 60)]
        best = max(sweep, key=lambda r: r["ops_per_sec"])
        default = next(r for r in sweep if r["bulk_size"] == 15)

        eng.set_bulk_size(15)
        ctl = tuning.BulkSizeController(min_segments=8, enabled=True,
                                        dry_run=False)
        run(12)
        ctl.tick()                         # baseline interval
        trail, settled = [], 0
        for _ in range(24):                # convergence loop
            run(20)
            d = ctl.tick()
            now = int(os.environ["MXNET_ENGINE_BULK_SIZE"])
            trail.append(now)
            settled = settled + 1 if (d is None or not d["applied"]) \
                else 0
            if settled >= 3:               # 3 quiet ticks = converged
                break
        converged = measure(int(os.environ["MXNET_ENGINE_BULK_SIZE"]))
        rows["bulk_size"] = {
            "sweep": sweep,
            "best_manual": best,
            "default_15": default,
            "controller_trail": trail,
            "converged": converged,
            "ops_ratio_vs_best": round(
                converged["ops_per_sec"] / best["ops_per_sec"], 3),
            # the acceptance criterion, self-reported: converged flush
            # p99 within the measured-best manual size's — tolerance is
            # one log-histogram bucket (growth 10^0.1 ~ 1.26x, the
            # registry's stated +-12% resolution) plus a noise margin
            "converged_within_best_p99": bool(
                converged["flush_us_p99"]
                <= 1.35 * best["flush_us_p99"]),
        }

        # --- 2. serving window: static vs adaptive at ramp load ------
        from mxnet_tpu import gluon
        from mxnet_tpu.serving import ModelServer
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(128, activation="relu"),
                    gluon.nn.Dense(64, activation="relu"),
                    gluon.nn.Dense(10))
        net.initialize()
        net.hybridize()

        def sample():
            return (rng.standard_normal((784,)).astype(np.float32),)

        def make(window_us):
            return ModelServer(net, max_batch=16, workers=2,
                               queue_depth=64, deadline_ms=0,
                               batch_window_us=window_us)

        serial = ModelServer(net, max_batch=1, workers=1,
                             queue_depth=64, deadline_ms=0,
                             batch_window_us=2000)
        serial.warmup(sample())
        serial.start()
        serial.infer(*sample(), timeout=60)
        serial_max, _ = _max_sustainable(serial, sample)
        serial.stop()
        offered = max(40.0, 1.5 * serial_max)   # the PR-7 ramp load

        static = make(2000)                # frozen default window
        static.warmup(sample())
        static.start()
        static.infer(*sample(), timeout=60)
        static_row = _offered_load(static, sample, offered, duration_s)
        static.stop()

        os.environ["MXTPU_SERVING_BATCH_WINDOW_US"] = "2000.0"
        adaptive = make(None)              # live knob-governed window
        adaptive.warmup(sample())
        adaptive.start()
        adaptive.infer(*sample(), timeout=60)
        os.environ["MXTPU_TUNE_INTERVAL"] = "0.25"
        rt = tuning.TuningRuntime()        # private runtime: only the
        rt.add(tuning.BatchWindowController(   # window loop runs here
            min_requests=10, enabled=True, dry_run=False))
        rt.start()
        try:
            adaptive_row = _offered_load(adaptive, sample, offered,
                                         duration_s)
        finally:
            rt.stop()
            adaptive.stop()
        rows["serving_window"] = {
            "offered_qps": round(offered, 1),
            "max_sustainable_qps_serial": round(serial_max, 1),
            "static_2000us": static_row,
            "adaptive": adaptive_row,
            "final_window_us": float(
                os.environ["MXTPU_SERVING_BATCH_WINDOW_US"]),
            "p99_win": round(static_row["p99_ms"] /
                             max(adaptive_row["p99_ms"], 1e-3), 2),
        }
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # --- 3. compile cache: cold vs warm process ----------------------
    with tempfile.TemporaryDirectory() as tmp:
        script = os.path.join(tmp, "warm_start.py")
        with open(script, "w") as f:
            f.write(_WARM_START_SCRIPT)
        # a test of the cache itself: a directory of its own, empty at
        # the cold run, named through the resolver's own variables
        env = dict(os.environ,
                   MXTPU_COMPILE_CACHE_DIR=os.path.join(tmp, "cache"),
                   MXTPU_BENCH_ROOT=os.path.dirname(
                       os.path.abspath(__file__)))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)

        def one():
            r = subprocess.run([sys.executable, script], env=env,
                               capture_output=True, text=True,
                               timeout=600)
            lines = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("RESULT ")]
            if not lines:
                return {"error": (r.stderr or r.stdout)[-300:],
                        "time_to_first_inference_s": 0.0,
                        "compiles": -1, "cache_hits": -1,
                        "out_sha": "failed"}
            return json.loads(lines[-1][len("RESULT "):])

        cold = one()
        warm = one()
        rows["compile_cache"] = {
            "cold": cold,
            "warm": warm,
            "warm_start_speedup": round(
                cold["time_to_first_inference_s"] /
                max(warm["time_to_first_inference_s"], 1e-3), 2),
            "warm_recompiles": warm["compiles"],   # the ~0 acceptance
            # sha256 over BOTH full output arrays (segment chain +
            # cached-graph batch), so "bitwise" means every element
            "bitwise_equal": bool(
                cold["out_sha"] == warm["out_sha"] != "failed"),
        }
    rows["converged_bulk_size"] = \
        rows["bulk_size"]["converged"]["bulk_size"]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--only", choices=["resnet_bf16", "resnet_fp32",
                                       "mnist_mlp", "eager_dispatch",
                                       "bert", "bert_bf16",
                                       "nmt", "ssd", "pipeline",
                                       "serving", "frontend",
                                       "generate", "autotune",
                                       "multichip", "overlap",
                                       "recommender"],
                    help="run a single row (default: the full suite)")
    ap.add_argument("--multichip-one", metavar="DP,ZERO",
                    help="internal: measure ONE multichip grid config "
                         "(core-pinned subprocess of --only multichip)")
    ap.add_argument("--overlap-one", metavar="MODE:ARGS",
                    help="internal: measure ONE overlap config "
                         "(core-pinned subprocess of --only overlap)")
    ap.add_argument("--generate-one", metavar="SCHED:ARGS",
                    help="internal: measure ONE generation cell "
                         "(core-pinned subprocess of --only generate)")
    ap.add_argument("--recommender-one", metavar="DP,SPARSE",
                    help="internal: measure ONE recommender grid config "
                         "(core-pinned subprocess of --only recommender)")
    ap.add_argument("--dtype", choices=["float32", "bfloat16"],
                    default=None,
                    help="kept for compat: forces the single resnet row")
    ap.add_argument("--layout", choices=["NCHW", "NHWC"], default="NCHW",
                    help="resnet rows' data layout (NHWC = channels-last "
                    "experiment)")
    ap.add_argument("--profile", metavar="DIR",
                    help="capture a jax.profiler trace of the bf16 "
                    "resnet row into DIR")
    args = ap.parse_args()

    import sys
    # one fixed, git-ignored cache directory in the checkout unless the
    # environment names jax's own (a path that moved would never hit);
    # exported, so every row's child shares it.  Touches no backend.
    from mxnet_tpu.tuning import compile_cache
    compile_cache.configure(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".compile_cache"))
    if args.multichip_one:
        # config child of --only multichip: CPU-forced, and affinity
        # must be set before any jax touch
        _multichip_one_main(args.multichip_one)
        return
    if args.overlap_one:
        _overlap_one_main(args.overlap_one)
        return
    if args.generate_one:
        _generate_one_main(args.generate_one)
        return
    if args.recommender_one:
        _recommender_one_main(args.recommender_one)
        return
    if args.only == "recommender":
        # CPU-host row like multichip: every cell is its own CPU-forced
        # core-pinned subprocess; this parent stays off jax
        row = bench_recommender()
        print(json.dumps({
            "metric": "recommender_sparse_step_speedup_dp1",
            "unit": "x vs dense grad",
            "value": row.get("sparse_step_speedup_dp1", 0.0),
            "vs_baseline": 0.0,
            "rows": {"recommender": row}}))
        return
    if args.only == "generate":
        # CPU-host row like multichip/overlap: every cell is its own
        # CPU-forced core-pinned subprocess; this parent stays off jax
        row = bench_generate()
        print(json.dumps({
            "metric": "generate_tokens_s_win",
            "unit": "x vs whole-sequence batcher",
            "value": row.get("tokens_s_win", 0.0),
            "vs_baseline": 0.0,
            "rows": {"generate": row}}))
        return
    if args.only == "overlap":
        # CPU-host row like multichip: every cell is its own CPU-forced
        # core-pinned subprocess; this parent stays off jax
        row = bench_overlap()
        print(json.dumps({
            "metric": "overlap_best_step_improvement",
            "unit": "x vs overlap-off",
            "value": row.get("best_step_improvement_x", 0.0),
            "vs_baseline": 0.0,
            "rows": {"overlap": row}}))
        return
    if args.only == "multichip":
        # CPU-host row by definition: every measurement runs in its own
        # CPU-forced subprocess; this parent stays off jax, so it holds no
        # chip beside its children (which need none)
        row = bench_multichip()
        print(json.dumps({
            "metric": "multichip_speedup_dp2", "unit": "x vs dp=1",
            "value": row.get("speedup_dp2", 0.0), "vs_baseline": 0.0,
            "rows": {"multichip": row}}))
        return
    import contextlib

    def profiled():
        if args.profile:
            import jax
            return jax.profiler.trace(args.profile)
        return contextlib.nullcontext()

    rows = {}
    device = None
    in_process = contextlib.ExitStack()
    if args.only or args.dtype:
        # a row in THIS process runs on the chip or fails.  The default
        # context is the host (the reference's), so the rows that name
        # none — Gluon training, serving — are placed on tpu(0) here.
        import mxnet_tpu as mx
        dev = _chip()
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(dev.client.devices())}
        in_process.enter_context(mx.tpu(0))
    if args.only == "mnist_mlp":
        rows["mnist_mlp_imperative"] = bench_mnist_mlp()
    elif args.only == "eager_dispatch":
        rows["eager_dispatch"] = bench_eager_dispatch()
    elif args.only == "bert":
        rows["bert_base"] = bench_bert_base()
        rows["bert_base_flash"] = bench_bert_base(attention="flash")
    elif args.only == "bert_bf16":
        rows["bert_base_bf16"] = bench_bert_base(dtype="bfloat16")
        rows["bert_base_bf16_flash"] = bench_bert_base(
            dtype="bfloat16", attention="flash")
    elif args.only == "nmt":
        rows["nmt_transformer"] = bench_nmt()
    elif args.only == "ssd":
        rows["ssd_detection"] = bench_ssd()
    elif args.only == "pipeline":
        rows["input_pipeline"] = bench_pipeline()
    elif args.only == "serving":
        rows["serving"] = bench_serving()
    elif args.only == "frontend":
        rows["frontend"] = bench_frontend()
    elif args.only == "autotune":
        rows["autotune"] = bench_autotune()
    elif args.only in ("resnet_bf16", "resnet_fp32") or args.dtype:
        dt = args.dtype or ("bfloat16" if args.only == "resnet_bf16"
                            else "float32")
        key = f"resnet50_{'bf16' if dt == 'bfloat16' else 'fp32'}"
        # a profiled run traces a SHORT window: 3 steps are plenty for an
        # XPlane/MFU analysis, traces are large and tracing slows the host
        iters = min(args.iters, 3) if args.profile else args.iters
        warmup = min(args.warmup, 1) if args.profile else args.warmup
        with profiled():
            rows[key] = bench_resnet50(dt, args.batch, iters,
                                       warmup, args.size,
                                       args.layout)
    else:
        # FULL suite: every row runs in its OWN subprocess (`--only ROW`)
        # with a hard timeout: one failing or hanging row must not zero
        # the suite.  Rows share no in-process compile cache anyway
        # (different graphs); the persistent cache amortizes across them.
        import subprocess

        # One process per chip: this parent must NOT touch jax — a
        # parent that has initialized the backend holds the chip, and the
        # row children that need it then fail or hang.  The rows run one
        # after another, never beside each other.
        row_budget = 1800

        def sub_row(only, canonical_keys, timeout, extra=()):
            """Run one row via `--only` in its own process; record errors
            under the row's CANONICAL key with the child's stderr tail
            (the only place a crash explains itself)."""
            nonlocal device
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--only", only,
                   "--batch", str(args.batch), "--iters", str(args.iters),
                   "--warmup", str(args.warmup), "--size", str(args.size),
                   *extra]
            if args.layout != "NCHW":
                cmd += ["--layout", args.layout]

            def err(msg):
                for k in canonical_keys:
                    rows[k] = {"error": msg[:400]}
            try:
                r = subprocess.run(cmd, capture_output=True,
                                   text=True, timeout=timeout)
            except subprocess.TimeoutExpired:
                err(f"row timed out after {timeout}s (subprocess killed)")
                return
            try:
                data = json.loads(r.stdout.strip().splitlines()[-1])
                got = data.get("rows", {})
            except Exception:  # noqa: BLE001
                err(f"row subprocess rc={r.returncode}, unparseable "
                    f"output; stderr: {r.stderr[-300:]}")
                return
            missing = [k for k in canonical_keys if k not in got]
            if missing:
                err(f"row subprocess rc={r.returncode} returned no "
                    f"{missing}; {r.stderr[-300:]}")
                return
            for k in canonical_keys:
                rows[k] = got[k]
            device = device or data.get("device")

        # the profiled headline row is traced in ITS child, where the
        # work runs: only the process that holds the chip can trace it
        sub_row("resnet_bf16", ["resnet50_bf16"], row_budget,
                ["--profile", args.profile] if args.profile else ())
        sub_row("resnet_fp32", ["resnet50_fp32"], row_budget)
        sub_row("mnist_mlp", ["mnist_mlp_imperative"], 900)
        sub_row("eager_dispatch", ["eager_dispatch"], 900)
        sub_row("bert", ["bert_base", "bert_base_flash"], row_budget)
        sub_row("bert_bf16", ["bert_base_bf16", "bert_base_bf16_flash"],
                row_budget)
        sub_row("nmt", ["nmt_transformer"], row_budget)
        sub_row("ssd", ["ssd_detection"], row_budget)
        sub_row("pipeline", ["input_pipeline"], 900)
        sub_row("serving", ["serving"], 900)
        sub_row("frontend", ["frontend"], 900)
        sub_row("generate", ["generate"], 1800)
        sub_row("autotune", ["autotune"], 900)
        sub_row("multichip", ["multichip"], 1800)
        sub_row("overlap", ["overlap"], 1800)
    in_process.close()

    # per-row headline field + unit, so --only rows are labeled honestly
    HEADLINE = {
        "resnet50_bf16": ("images_per_sec_per_chip", "images/sec/chip"),
        "resnet50_fp32": ("images_per_sec_per_chip", "images/sec/chip"),
        "mnist_mlp_imperative": ("images_per_sec", "images/sec"),
        "eager_dispatch": ("ops_per_sec_bulk", "ops/sec"),
        "bert_base": ("step_ms", "ms/step"),
        "bert_base_flash": ("step_ms", "ms/step"),
        "bert_base_bf16": ("step_ms", "ms/step"),
        "bert_base_bf16_flash": ("step_ms", "ms/step"),
        "nmt_transformer": ("tokens_per_sec", "tokens/sec"),
        "ssd_detection": ("images_per_sec", "images/sec"),
        "input_pipeline": ("images_per_sec", "images/sec"),
        "serving": ("requests_per_sec", "req/s"),
        "frontend": ("surge_p99_improvement_x",
                     "x priority p99 under surge vs no controller"),
        "autotune": ("converged_bulk_size", "ops/segment"),
        "multichip": ("speedup_dp2", "x aggregate img/s vs dp=1"),
        "overlap": ("best_step_improvement_x", "x vs overlap-off"),
    }
    ok = {k: v for k, v in rows.items() if "error" not in v}
    if "resnet50_bf16" in ok:
        value = rows["resnet50_bf16"]["images_per_sec_per_chip"]
        metric = "resnet50_bf16_train_images_per_sec_per_chip"
        unit = "images/sec/chip"
        vs = value / BASELINE_IMG_S_FP16
    elif "resnet50_fp32" in ok:
        value = rows["resnet50_fp32"]["images_per_sec_per_chip"]
        metric = "resnet50_fp32_train_images_per_sec_per_chip"
        unit = "images/sec/chip"
        vs = value / BASELINE_IMG_S_FP32
    elif ok:
        key, r = next(iter(ok.items()))
        field, unit = HEADLINE[key]
        metric, value = f"{key}_{field}", r[field]
        vs = 0.0
    else:
        metric, value, unit, vs = "bench_failed", 0.0, "n/a", 0.0
        import sys
        print(json.dumps({"metric": metric, "value": value, "unit": unit,
                          "vs_baseline": vs, "device": device,
                          "rows": rows}))
        sys.exit(1)        # total failure must be visible to the driver
    print(json.dumps({
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": round(vs, 3),
        "device": device,       # of the in-process rows; the CPU-host grid
        "rows": rows,           # rows (multichip, overlap, ...) say so
    }))


if __name__ == "__main__":
    main()
