"""Causal-tracing suite: context propagation, sampling, exemplars,
critical-path attribution, and the cross-host stitch.

The acceptance experiment (the issue's end-to-end demo) runs as a REAL
2-process coordination-service group: under an injected
``loader_stall@N`` fault on rank 0, the p99 ``resilience.step_wall_us``
exemplar must resolve to a single trace that (a) spans BOTH hosts'
span rings — stitched through the deterministic lockstep trace id and
the KV tier — and (b) whose critical-path attribution names the loader
stage."""
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.gluon import nn, loss as gloss  # noqa: E402
from mxnet_tpu.gluon.data import DataLoader  # noqa: E402
from mxnet_tpu.observability import tracing  # noqa: E402
from mxnet_tpu.observability.flight import FlightRecorder  # noqa: E402
from mxnet_tpu.observability.registry import registry  # noqa: E402
from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer  # noqa: E402
from mxnet_tpu.parallel.resilience import (  # noqa: E402
    BREAKDOWN_STAGES, _run_vote_round)


@pytest.fixture()
def traced(monkeypatch):
    """Tracing on, sample-everything, clean ring."""
    monkeypatch.setenv("MXTPU_TRACE", "1")
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    tr = tracing.tracer()
    tr.clear()
    yield tr
    tr.clear()


def _mini_trainer(seed=7):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(4, in_units=16))
    net.initialize()
    return ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                          {"learning_rate": 0.1})


# -- context model -----------------------------------------------------------

def test_off_is_noop_and_records_nothing(monkeypatch):
    monkeypatch.delenv("MXTPU_TRACE", raising=False)
    tr = tracing.tracer()
    n0 = len(tr.spans())
    assert tr.begin("t.off") is None
    assert tracing.traceparent() is None
    assert not tr.sampled_index(0)
    assert len(tr.spans()) == n0


def test_nesting_and_parenting(traced):
    tr = traced
    with tr.begin("outer") as outer:
        assert tracing.current() is outer
        with tr.begin("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        assert tracing.current() is outer
    assert tracing.current() is None
    names = [s["name"] for s in tr.find(outer.trace_id)]
    assert names == ["inner", "outer"]


def test_traceparent_round_trip(traced):
    with traced.begin("root") as root:
        tp = tracing.traceparent()
    assert tp == f"00-{root.trace_id}-{root.span_id}-01"
    ctx = tracing.parse_traceparent(tp)
    assert (ctx.trace_id, ctx.span_id) == (root.trace_id, root.span_id)
    # malformed inputs parse to None, never raise
    for bad in (None, "", "junk", "00-xy-zz-01", tp.replace("-", "_")):
        assert tracing.parse_traceparent(bad) is None
    with tracing.activate(ctx):
        with traced.begin("remote") as sp:
            assert sp.trace_id == root.trace_id
            assert sp.parent_id == root.span_id
    # activate(None) is a transparent no-op
    with tracing.activate(None):
        assert tracing.current() is None


def test_head_sampling_1_in_n(traced, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_SAMPLE", "4")
    tr = traced
    kept = [tr.begin(f"r{i}", activate=False) for i in range(8)]
    assert sum(1 for s in kept if s is not None) == 2
    # children of a sampled root are never dropped (traces stay whole)
    root = next(s for s in kept if s is not None)
    for i in range(5):
        ch = tr.begin(f"c{i}", parent=root, activate=False)
        assert ch is not None
        ch.finish()
    # deterministic index sampling: fleet-uniform verdicts
    assert [tr.sampled_index(i) for i in range(1, 9)] == \
        [False, False, False, True, False, False, False, True]


def test_ring_is_bounded():
    tr = tracing.Tracer(ring=8)
    os.environ["MXTPU_TRACE"] = "1"
    try:
        for i in range(32):
            tr.begin(f"s{i}", activate=False).finish()
    finally:
        os.environ.pop("MXTPU_TRACE", None)
    spans = tr.spans()
    assert len(spans) == 8
    assert spans[-1]["name"] == "s31"


def test_deterministic_trace_ids():
    a = tracing.deterministic_trace_id("resilience.step", "fence0", 7)
    b = tracing.deterministic_trace_id("resilience.step", "fence0", 7)
    c = tracing.deterministic_trace_id("resilience.step", "fence0", 8)
    assert a == b != c and len(a) == 32
    int(a, 16)


def test_jsonl_stream_rotates_and_flushes(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE", "1")
    path = str(tmp_path / "spans.jsonl")
    tr = tracing.Tracer(ring=64, jsonl=path)
    for i in range(70):                   # crosses the 64-line buffer
        tr.begin(f"s{i}", activate=False).finish()
    tr.flush_jsonl()
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 70
    assert {"name", "trace_id", "span_id", "dur_us", "host"} <= \
        set(lines[0])


# -- exemplars ---------------------------------------------------------------

def test_exemplar_round_trip(traced):
    """The satellite's exemplar contract: the p99 bucket of a histogram
    resolves to a trace actually present in the ring."""
    h = registry().histogram("t.exemplar_us")
    h.reset()
    tids = {}
    for v in (10.0, 20.0, 30.0, 90_000.0):     # one clear tail outlier
        with traced.begin("t.work", args={"v": v}) as sp:
            h.observe(v)
            tids[v] = sp.trace_id
    ex = h.exemplars()
    assert ex
    top_bucket = max(ex)
    tid, val, ts = ex[top_bucket][-1]
    assert val == 90_000.0 and tid == tids[90_000.0]
    spans = traced.find(tid)
    assert spans and spans[0]["args"]["v"] == 90_000.0
    # exemplar suffixes are OPT-IN (OpenMetrics syntax is illegal in
    # the classic 0.0.4 exposition — a scraper receiving it rejects
    # the whole scrape), so the default text stays clean
    from mxnet_tpu.observability.export import prometheus_text
    assert "trace_id=" not in prometheus_text()
    txt = prometheus_text(exemplars=True)
    assert f'# {{trace_id="{tid}"}} 90000' in txt


def test_exemplar_explicit_trace_id_and_reset(traced):
    h = registry().histogram("t.explicit_us")
    h.reset()
    h.observe(5.0, trace_id="f" * 32)
    assert h.exemplars()[max(h.exemplars())][-1][0] == "f" * 32
    h.reset()
    assert h.exemplars() == {}


def test_exemplars_off_without_tracing(monkeypatch):
    monkeypatch.delenv("MXTPU_TRACE", raising=False)
    h = registry().histogram("t.notrace_us")
    h.reset()
    h.observe(5.0)
    assert h.exemplars() == {}


# -- chrome-trace export -----------------------------------------------------

def test_chrome_flow_events_link_parent_child(traced, tmp_path):
    with traced.begin("parent") as p:
        with traced.begin("child"):
            pass
    evs = traced.chrome_events()
    x = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in x} >= {"parent", "child"}
    starts = [e for e in evs if e["ph"] == "s"]
    ends = [e for e in evs if e["ph"] == "f"]
    assert starts and ends
    assert {e["id"] for e in starts} == {e["id"] for e in ends}
    out = tracing.chrome_trace_from_spans(traced.spans(),
                                          str(tmp_path / "trace.json"))
    payload = json.load(open(out))
    assert any(e.get("ph") == "M" for e in payload["traceEvents"])
    assert p.trace_id in json.dumps(payload)


def test_profiler_merges_trace_flows(traced, tmp_path):
    from mxnet_tpu import profiler
    p = profiler.Profiler.get()
    p.filename = str(tmp_path / "prof.json")
    p.reset()
    profiler.set_state("run")
    try:
        with traced.begin("step.outer"):
            with traced.begin("step.inner"):
                pass
    finally:
        profiler.set_state("stop")
    profiler.dump()
    payload = json.load(open(p.filename))
    evs = payload["traceEvents"]
    trace_x = [e for e in evs if e.get("cat") == "trace"
               and e.get("ph") == "X"]
    assert {e["name"] for e in trace_x} >= {"step.outer", "step.inner"}
    assert any(e.get("ph") == "s" and e.get("cat") == "trace"
               for e in evs)
    # trace lanes are named and offset past the profiler's own
    assert any(e.get("ph") == "M"
               and str(e.get("args", {}).get("name", "")
                       ).startswith("trace:") for e in evs)


# -- serving -----------------------------------------------------------------

def test_serving_request_trace_tree_and_links(traced):
    from mxnet_tpu.serving import ModelServer
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
    net.initialize()
    net.hybridize()
    srv = ModelServer(net, max_batch=4, workers=1,
                      batch_window_us=20_000, deadline_ms=0)
    with srv:
        srv.warmup(np.zeros((4,), np.float32))
        reqs = [srv.submit(np.random.randn(4).astype(np.float32))
                for _ in range(4)]
        for r in reqs:
            r.result(timeout=60)
    spans = traced.spans()
    req_spans = [s for s in spans if s["name"] == "serving.request"]
    assert len(req_spans) == 4
    # the batch's assemble span parents on ONE member request and
    # links the rest; dispatch + readback chain under it
    asm = [s for s in spans if s["name"] == "serving.assemble"]
    assert asm
    linked = [tuple(l) for s in asm for l in (s.get("links") or ())]
    parent_ids = {s["parent_id"] for s in asm}
    member_ids = {s["span_id"] for s in req_spans}
    assert parent_ids <= member_ids
    assert all(ls in member_ids for _lt, ls in linked)
    tree = traced.find(asm[0]["trace_id"])
    names = {s["name"] for s in tree}
    assert {"serving.request", "serving.assemble", "serving.dispatch",
            "serving.readback"} <= names
    # flight request records cross-reference the span ring
    from mxnet_tpu.observability.flight import recorder
    recent = recorder().requests()[-4:]
    assert all(r["trace_id"] in {s["trace_id"] for s in req_spans}
               for r in recent)
    # request_us exemplars point at request traces
    ex = registry().get("serving.request_us").exemplars()
    assert ex
    tids = {t for lst in ex.values() for t, _v, _ts in lst}
    assert tids & {s["trace_id"] for s in req_spans}


def test_serving_untraced_requests_have_no_spans(monkeypatch):
    monkeypatch.delenv("MXTPU_TRACE", raising=False)
    from mxnet_tpu.serving import ModelServer
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
    net.initialize()
    net.hybridize()
    tr = tracing.tracer()
    n0 = len(tr.spans())
    srv = ModelServer(net, max_batch=2, workers=1, deadline_ms=0)
    with srv:
        req = srv.submit(np.zeros((4,), np.float32))
        req.result(timeout=60)
    assert req.trace is None
    assert len(tr.spans()) == n0


# -- training step: breakdown + flight dump ----------------------------------

def test_step_breakdown_names_loader_under_stall(traced, tmp_path):
    """The satellite's flight-dump test: under an injected
    ``loader_stall``, the per-step flight record carries the breakdown
    field naming the loader stage, the step trace holds the retroactive
    ``loader.wait`` child, and the crash dump cross-references the span
    ring."""
    from mxnet_tpu import faults
    # set_fault_plan, not the env knob: active_plan() memoizes the env
    # parse once per process, so in a full-suite run a monkeypatched
    # env var would be ignored
    # 1.2s: far above what the prefetched batches absorb, so the
    # stalled step's consumer-visible wait names the loader
    faults.set_fault_plan("loader_stall@4:1.2")
    # the wall histogram is the process's: an earlier file on this worker
    # may have left a slower step's exemplar in its top bucket
    registry().reset("resilience.")
    tr = _mini_trainer()
    rng = np.random.RandomState(0)
    data = [(rng.randn(8).astype(np.float32), rng.randint(0, 4))
            for _ in range(48)]
    loader = DataLoader(data, batch_size=8, num_workers=1)
    flight = FlightRecorder(capacity=64,
                            path=str(tmp_path / "flight.json"))
    rt = ResilientTrainer(tr, auto_resume=False, loader=loader)
    rt._flight = flight
    try:
        # prime the jit compile OUTSIDE the measured epoch: the first
        # step's compile wall would otherwise out-bucket the stall
        rt.step(rng.randn(8, 8).astype(np.float32),
                rng.randint(0, 4, (8,)))
        for x, y in loader:
            rt.step(x, y)
    finally:
        faults.set_fault_plan(None)
    recs = flight.records()[1:]           # drop the priming step
    assert len(recs) == 6
    assert all(set(BREAKDOWN_STAGES) == set(r["breakdown"]) and
               r["trace_id"] for r in recs)
    # the stalled step is the one that waited longest for its batch
    # (on a loaded host another step may name the loader too)
    sr = max(recs, key=lambda r: r["breakdown"]["loader"])
    assert sr["bottleneck"] == "loader", [r["bottleneck"] for r in recs]
    # prefetched batches absorb part of the stall; the consumer-visible
    # wait still dominates the step
    assert sr["breakdown"]["loader"] > 100_000
    # the breakdown gauges carry the last step's decomposition
    assert registry().get("step.breakdown.compute_us").value > 0
    b = registry().get("step.breakdown.bottleneck").value
    assert BREAKDOWN_STAGES[int(b)] in BREAKDOWN_STAGES
    # the stalled step's trace holds the retroactive loader child
    names = {s["name"] for s in traced.find(sr["trace_id"])}
    assert {"resilience.step", "resilience.step_us",
            "loader.wait"} <= names
    # the wall histogram's exemplars resolve to the stalled trace: the
    # bucket that took the stalled step's wall (loader wait included)
    # names its trace.  On an idle host that is the top bucket; beside
    # five busy workers another step may out-wait the stall, so the
    # bucket is found by what it must hold, not by its rank
    ex = registry().get("resilience.step_wall_us").exemplars()
    hits = [(bound, wall) for bound, lst in ex.items()
            for tid, wall, _ in lst if tid == sr["trace_id"]]
    assert len(hits) == 1, (sr["trace_id"], ex)
    bound, wall = hits[0]
    assert sr["breakdown"]["loader"] <= wall <= bound
    # crash dump: step records + span ring side by side
    path = flight.dump("test")
    payload = json.load(open(path))
    assert payload["n_trace_spans"] > 0
    dumped_tids = {s["trace_id"] for s in payload["trace_spans"]}
    assert sr["trace_id"] in dumped_tids
    assert any(r.get("trace_id") == sr["trace_id"]
               for r in payload["steps"])


def test_step_tracing_off_keeps_breakdown_fields_none(monkeypatch,
                                                      tmp_path):
    monkeypatch.delenv("MXTPU_TRACE", raising=False)
    tr = _mini_trainer()
    flight = FlightRecorder(capacity=16,
                            path=str(tmp_path / "flight.json"))
    rt = ResilientTrainer(tr, auto_resume=False)
    rt._flight = flight
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (8,))
    rt.step(x, y)
    rec = flight.records()[-1]
    # no trace, but the attribution fields still exist (no loader
    # attached -> wall ~= compute)
    assert rec["trace_id"] is None
    assert rec["bottleneck"] in BREAKDOWN_STAGES
    assert set(rec["breakdown"]) == set(BREAKDOWN_STAGES)


# -- KV-tier carry -----------------------------------------------------------

def test_vote_round_degrades_and_finishes_span(traced):
    """The vote payload stays the bare ascii int (the traceparent
    rides a side key, so tracing can never perturb the protocol); with
    no process group the publish fails and the round degrades to the
    unilateral own-vote — while still closing its trace span."""
    with traced.begin("step.fake") as root:
        agreed = _run_vote_round("mxtpu/test_preempt", 7, [0],
                                 timeout=0.2, poll=0.01)
    assert agreed == 7
    votes = [s for s in traced.spans()
             if s["name"] == "resilience.vote_round"]
    assert votes and votes[-1]["trace_id"] == root.trace_id
    assert votes[-1]["args"]["agreed"] == 7


# -- overhead guard (slow) ---------------------------------------------------

@pytest.mark.slow
def test_tracing_overhead_under_guard(monkeypatch):
    """Extend the <3% observability-overhead guard to tracing: with
    sampling off the instrumented-call-site probe must be noise next to
    one dispatched segment, and a fully sampled span must stay tens of
    microseconds."""
    from tests._overhead import _tracing_costs
    off_us, on_us = _tracing_costs()
    # a per-dispatch-batch probe against the measured per-op cost:
    # one probe per ~15-op segment must stay under the 3% budget
    import time as _time
    eng = mx.engine.engine()
    x = mx.nd.ones((4096,))
    y = x
    eng.reset_stats()
    t0 = _time.perf_counter()
    n = 600
    for _ in range(n):
        y = mx.nd.tanh(y * x)
    y.wait_to_read()
    per_op_us = (_time.perf_counter() - t0) / n * 1e6
    seg = eng.stats()["mean_segment_length"] or 15
    budget_us = 0.03 * per_op_us * seg
    assert off_us < max(1.0, budget_us), \
        f"tracing-off probe costs {off_us}us (budget {budget_us:.2f})"
    assert on_us < 100.0, f"sampled span costs {on_us}us"


# -- the 2-process stitch + acceptance experiment ----------------------------

_WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
    from mxnet_tpu.base import force_cpu_mesh
    force_cpu_mesh(1, verify=False)   # distributed init precedes the
    import numpy as np                # first backend query
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import dist

    os.environ["MXTPU_TRACE"] = "1"
    dist.init_process_group()
    rank, nw = dist.rank(), dist.num_workers()
    from mxnet_tpu.observability import tracing
    from mxnet_tpu.observability.registry import registry
    tr = tracing.tracer()

    # -- phase A: explicit traceparent through the KV tier ---------------
    if rank == 0:
        with tr.begin("work.rank0") as root:
            dist.kv_publish("mxtpu/test_tp",
                            tracing.traceparent().encode("ascii"))
            dist.barrier("tp_posted")
    else:
        dist.barrier("tp_posted")
        tp = dist.kv_collect("mxtpu/test_tp")[0].decode("ascii")
        ctx = tracing.parse_traceparent(tp)
        assert ctx is not None, tp
        with tracing.activate(ctx):
            with tr.begin("work.rank1"):
                pass
    dist.barrier("phase_a_done")
    dist.kv_publish("mxtpu/test_rings_a",
                    json.dumps(tr.spans()).encode("utf-8"))
    dist.barrier("rings_a")
    merged = []
    for r, blob in dist.kv_collect("mxtpu/test_rings_a").items():
        merged += json.loads(blob.decode("utf-8"))
    work = [s for s in merged if s["name"].startswith("work.")]
    assert len(work) == 2, work
    assert len({s["trace_id"] for s in work}) == 1, work
    assert {s["host"] for s in work} == {0, 1}, work
    print("STITCH_%d_OK" % rank, flush=True)

    # -- phase B: the loader_stall acceptance experiment ------------------
    # deterministic lockstep step traces: every host's step-i spans
    # share one trace id with ZERO cross-host traffic
    tr.clear()
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer
    from mxnet_tpu.observability.flight import FlightRecorder
    import jax
    from mxnet_tpu import parallel as par
    mx.random.seed(0); np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    strainer = par.ShardedTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1},
        mesh=par.make_mesh({"dp": 1}, devices=jax.local_devices()[:1]))
    rng = np.random.RandomState(0)
    data = [(rng.randn(8).astype(np.float32), rng.randint(0, 4))
            for _ in range(48)]
    loader = DataLoader(data, batch_size=8, num_workers=1)
    flight = FlightRecorder(capacity=64)
    rt = ResilientTrainer(strainer, auto_resume=False, loader=loader)
    rt._flight = flight
    # prime the jit compile outside the measured epoch so the stall,
    # not the compile, owns the p99 wall bucket
    rt.step(rng.randn(8, 8).astype(np.float32),
            rng.randint(0, 4, (8,)))
    for x, y in loader:
        rt.step(x, y)
    dist.barrier("steps_done")
    dist.kv_publish("mxtpu/test_rings_b",
                    json.dumps(tr.spans()).encode("utf-8"))
    dist.barrier("rings_b")
    merged = []
    for r, blob in dist.kv_collect("mxtpu/test_rings_b").items():
        merged += json.loads(blob.decode("utf-8"))
    if rank == 0:
        # p99 exemplar of the wall histogram -> the stalled trace
        ex = registry().get("resilience.step_wall_us").exemplars()
        tid = ex[max(ex)][-1][0]
        # the exemplar's trace is the stalled step's, and that step's
        # critical-path attribution names the loader.  (Other steps may
        # name the loader too: on a quick host the epoch's first batch
        # waits longer for the worker than the tiny step computes, so
        # "the first record whose bottleneck is the loader" is not the
        # stalled one.)
        stalled = [r for r in flight.records() if r["trace_id"] == tid]
        assert len(stalled) == 1, (flight.records(), tid)
        assert stalled[0]["bottleneck"] == "loader", stalled
        assert stalled[0]["wall_us"] == max(
            r["wall_us"] for r in flight.records()), flight.records()
        assert stalled[0]["wall_us"] > 0.5e6, stalled
        # ONE stitched trace spanning BOTH hosts' spans
        trace = [s for s in merged if s["trace_id"] == tid]
        assert {s["host"] for s in trace} == {0, 1}, trace
        names0 = {s["name"] for s in trace if s["host"] == 0}
        assert {"resilience.step", "loader.wait"} <= names0, names0
        assert any(s["name"] == "resilience.step" and s["host"] == 1
                   for s in trace), trace
        print("ACCEPT_0_OK", flush=True)
    else:
        print("ACCEPT_1_OK", flush=True)
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_cross_host_stitch_and_loader_attribution_2proc(tmp_path):
    """Acceptance: (a) a traceparent shipped over the KV tier stitches
    spans from two hosts into one trace; (b) under ``loader_stall`` on
    rank 0, the p99 ``resilience.step_wall_us`` exemplar resolves to a
    single stitched trace whose critical-path attribution names the
    loader stage."""
    n_workers = 2
    port = _free_port()
    script = tmp_path / "trace_worker.py"
    script.write_text(_WORKER)
    procs = []
    for r in range(n_workers):
        env = dict(os.environ)
        env.pop("MXTPU_TRACE_SAMPLE", None)
        env.update({
            "MXNET_TEST_ROOT": REPO,
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(n_workers),
            "DMLC_WORKER_ID": str(r),
        })
        # the stall targets rank 0 only: fault plans are per-process
        if r == 0:
            env["MXTPU_FAULT_PLAN"] = "loader_stall@4:1.0"
        else:
            env.pop("MXTPU_FAULT_PLAN", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((r, p.returncode, out))
    for r, rc, out in outs:
        assert rc == 0, f"worker {r} failed:\n{out}"
        assert f"STITCH_{r}_OK" in out, f"worker {r} output:\n{out}"
        assert f"ACCEPT_{r}_OK" in out, f"worker {r} output:\n{out}"
