"""Unified observability subsystem (mxnet_tpu/observability/): registry
thread-safety, histogram bucket math, span nesting, Prometheus endpoint
round-trip, JSONL writer rotation, back-compat of the legacy
``engine().stats()`` / ``ResilientTrainer.counters`` views; the fleet
layer — multi-host snapshot merging (single-process fallback AND a real
multi-process group), host-labeled aggregate text format, the unified
chrome-trace timeline (op + span events), and the crash flight recorder
— plus the thin 'counter-dict' and 'timing-pair' mxlint gates (the
walkers themselves live in mxnet_tpu/tools/mxlint)."""
import json
import os
import re
import socket
import subprocess
import sys
import textwrap
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.engine import engine
from mxnet_tpu.observability import export, trace
from mxnet_tpu.observability.registry import (Counter, Gauge, Histogram,
                                              MetricsRegistry, registry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry primitives ----------------------------------------------------

def test_counter_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("t.concurrent")
    n_threads, per_thread = 8, 10_000

    def work():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.n == n_threads * per_thread


def test_histogram_thread_safety():
    reg = MetricsRegistry()
    h = reg.histogram("t.hist")
    n_threads, per_thread = 8, 5_000

    def work(k):
        for i in range(per_thread):
            h.observe(float(1 + (i + k) % 100))

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == n_threads * per_thread
    assert sum(h.counts) == h.count


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("t.c")
    c.inc()
    c.inc(5)
    assert c.value == 6
    assert reg.counter("t.c") is c            # get-or-create idempotent
    g = reg.gauge("t.g")
    g.set(2.5)
    assert g.value == 2.5
    snap = reg.snapshot()
    assert snap["t.c"] == 6 and snap["t.g"] == 2.5
    c.reset()
    assert c.value == 0


def test_metric_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("t.x")
    with pytest.raises(MXNetError, match="already registered"):
        reg.gauge("t.x")
    with pytest.raises(MXNetError, match="already registered"):
        reg.histogram("t.x")


def test_metric_name_validation():
    reg = MetricsRegistry()
    for bad in ("nodots", "Upper.case", "a..b", "a.b-c", "9.lead", ""):
        with pytest.raises(MXNetError, match="bad metric name"):
            reg.counter(bad)
    reg.counter("fine.name_2.ok")             # multi-level is fine


def test_histogram_bucket_math():
    h = Histogram("t.h", base=1.0, growth=2.0, buckets=8)
    # bounds: 1, 2, 4, ..., 128; counts[i] covers (bounds[i-1], bounds[i]]
    assert h.bounds == (1, 2, 4, 8, 16, 32, 64, 128)
    h.observe(1.0)          # == bounds[0] -> bucket 0
    h.observe(1.5)          # bucket 1
    h.observe(3.0)          # bucket 2
    h.observe(100.0)        # bucket 7
    h.observe(1e9)          # overflow bucket
    assert h.counts[0] == 1 and h.counts[1] == 1 and h.counts[2] == 1
    assert h.counts[7] == 1 and h.counts[8] == 1
    assert h.count == 5
    assert h.vmin == 1.0 and h.vmax == 1e9
    assert abs(h.total - (1.0 + 1.5 + 3.0 + 100.0 + 1e9)) < 1e-3
    # cumulative buckets end with (+inf, total) and are monotone
    cum = h.cumulative_buckets()
    assert cum[-1] == (float("inf"), 5)
    assert [c for _, c in cum] == sorted(c for _, c in cum)


def test_histogram_percentiles():
    h = Histogram("t.p", base=1.0, growth=10 ** 0.1, buckets=120)
    for v in range(1, 1001):
        h.observe(float(v))
    # log-bucket resolution is one growth step (~26%); assert within 2x
    p50, p99 = h.percentile(50), h.percentile(99)
    assert 250 <= p50 <= 1000 and p50 <= p99
    assert 500 <= p99 <= 1000
    assert h.percentile(100) == 1000.0
    read = h.read()
    assert read["count"] == 1000 and read["p50"] == round(p50, 3)
    h.reset()
    assert h.count == 0 and h.percentile(50) == 0.0


def test_registry_reset_prefix():
    reg = MetricsRegistry()
    reg.counter("a.x").inc()
    reg.counter("b.y").inc()
    reg.reset("a.")
    assert reg.counter("a.x").n == 0 and reg.counter("b.y").n == 1


# -- spans ------------------------------------------------------------------

def test_span_records_and_nests():
    seen = []
    fn = lambda name, t_end, us, args: seen.append((name, t_end, us))  # noqa: E731
    trace.add_span_listener(fn)
    try:
        with trace.span("t.outer_us"):
            with trace.span("t.inner_us"):
                pass
    finally:
        trace.remove_span_listener(fn)
    # the inner span ends first and lies inside the outer one
    (n_in, end_in, us_in), (n_out, end_out, us_out) = seen
    assert (n_in, n_out) == ("t.inner_us", "t.outer_us")
    assert end_out - us_out / 1e6 <= end_in - us_in / 1e6 <= end_in <= end_out
    outer = registry().get("t.outer_us").read()
    inner = registry().get("t.inner_us").read()
    assert outer["count"] >= 1 and inner["count"] >= 1
    # the inner span is contained in the outer: its mean cannot exceed it
    assert inner["max"] <= outer["max"] + 1.0


def test_span_pops_on_exception():
    with pytest.raises(ValueError):
        with trace.span("t.raises_us"):
            raise ValueError("boom")
    assert registry().get("t.raises_us").read()["count"] >= 1


def test_span_duration_and_no_histogram_mode():
    with trace.span("t.nohist", histogram=False) as sp:
        pass
    assert sp.duration_us >= 0.0
    assert registry().get("t.nohist") is None


def test_span_emits_to_profiler_listener():
    events = []
    fn = lambda name, t_end, us, args: events.append((name, us))  # noqa: E731
    trace.add_span_listener(fn)
    try:
        with trace.span("t.listened_us"):
            pass
    finally:
        trace.remove_span_listener(fn)
    assert any(n == "t.listened_us" for n, _ in events)


# -- back-compat views ------------------------------------------------------

def test_engine_stats_is_registry_view():
    eng = engine()
    x = mx.nd.ones((16,))
    y = x
    for _ in range(6):
        y = mx.nd.tanh(y * x)
    y.wait_to_read()
    s = eng.stats()
    snap = registry().snapshot()
    assert snap["engine.ops_dispatched"] == s["ops_dispatched"]
    assert snap["engine.ops_bulked"] == s["ops_bulked"]
    assert snap["engine.segments_flushed"] == s["segments_flushed"]
    assert snap["engine.segment_cache_hits"] == s["segment_cache_hits"]
    # the op ran through SOME path
    assert s["ops_dispatched"] + s["ops_bulked"] > 0
    # flush latency histogram feeds the stats percentiles
    if s["segments_flushed"]:
        assert snap["engine.flush_us"]["count"] >= s["segments_flushed"]
        assert s["flush_us_p50"] == snap["engine.flush_us"]["p50"]


def test_engine_reset_stats_resets_registry():
    eng = engine()
    mx.nd.ones((4,)).wait_to_read()
    eng.reset_stats()
    s = eng.stats()
    assert s["ops_dispatched"] == 0 and s["ops_bulked"] == 0
    assert registry().snapshot()["engine.flush_us"]["count"] == 0


def test_loader_counters():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    base = registry().counter("loader.batches").n
    data = np.arange(32, dtype=np.float32).reshape(16, 2)
    label = np.arange(16, dtype=np.float32)
    loader = DataLoader(ArrayDataset(mx.nd.array(data),
                                     mx.nd.array(label)),
                        batch_size=4, num_workers=2)
    n = sum(1 for _ in loader)
    assert n == 4
    assert registry().counter("loader.batches").n - base == 4
    assert registry().get("loader.batch_build_us").read()["count"] >= 4


def test_resilience_counters_backcompat_view(tmp_path):
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer

    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(8, activation="relu", in_units=4))
            net.add(nn.Dense(2, in_units=8))
        net.initialize()
        return ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                              {"learning_rate": 0.1})

    rng = np.random.RandomState(0)
    batches = [(rng.randn(8, 4).astype(np.float32),
                rng.randint(0, 2, (8,))) for _ in range(3)]
    global_before = registry().counter("resilience.steps_skipped").n
    rt = ResilientTrainer(build(), auto_resume=False,
                          fault_plan="nan@2")
    for x, y in batches:
        rt.step(x, y)
    c = rt.counters
    assert c["steps_skipped"] == 1
    # per-instance view is a DELTA over the process-global registry
    assert registry().counter("resilience.steps_skipped").n \
        == global_before + 1
    # a second trainer starts its view at zero even though the global
    # counter is nonzero — the back-compat contract
    rt2 = ResilientTrainer(build(), auto_resume=False)
    assert rt2.counters["steps_skipped"] == 0
    # step wall-time recorded via the span
    assert registry().get("resilience.step_us").read()["count"] >= 3


def test_snapshot_is_one_call():
    """Acceptance: one registry().snapshot() carries engine, resilience,
    loader AND latency histograms (whatever has been exercised so far in
    this process — the suite above touched all of them)."""
    mx.nd.ones((4,)).wait_to_read()
    snap = registry().snapshot()
    assert any(k.startswith("engine.") for k in snap)
    assert isinstance(snap["engine.flush_us"], dict)
    assert "p99" in snap["engine.flush_us"]


# -- exporters --------------------------------------------------------------

# one or more label pairs: bare histograms carry {le=...}, the
# frontend's per-model families carry {model=...} (and both on their
# bucket series)
_PROM_LINE = re.compile(
    r'^[a-zA-Z_][a-zA-Z0-9_]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? [^ ]+$')


def test_prometheus_text_wellformed():
    registry().counter("t.prom_counter").inc(3)
    registry().gauge("t.prom_gauge").set(1.5)
    registry().histogram("t.prom_hist").observe(10.0)
    text = export.prometheus_text()
    typed = set()
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert kind in ("counter", "gauge", "histogram")
            typed.add(name)
            continue
        assert _PROM_LINE.match(line), f"malformed sample line: {line!r}"
        base = line.split("{")[0].split(" ")[0]
        base = re.sub(r"_(bucket|sum|count)$", "", base)
        assert base in typed or line.split(" ")[0] in typed, \
            f"sample {line!r} has no preceding # TYPE"
    assert "mxtpu_t_prom_counter 3" in text
    assert "mxtpu_t_prom_gauge 1.5" in text
    assert 'mxtpu_t_prom_hist_bucket{le="+Inf"} 1' in text
    assert "mxtpu_t_prom_hist_count 1" in text


def test_prometheus_endpoint_roundtrip():
    registry().counter("t.endpoint_hits").inc(7)
    srv = export.MetricsServer(port=0, addr="127.0.0.1")
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "mxtpu_t_endpoint_hits 7" in body
        assert "# TYPE mxtpu_t_endpoint_hits counter" in body
        # engine metrics ride the same scrape
        assert "mxtpu_engine_ops_bulked" in body
        # the JSON twin parses and matches
        jurl = f"http://127.0.0.1:{srv.port}/metrics.json"
        snap = json.loads(
            urllib.request.urlopen(jurl, timeout=10).read().decode())
        assert snap["t.endpoint_hits"] == 7
        # unknown paths 404 instead of crashing the server thread
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)
    finally:
        srv.stop()


def test_jsonl_writer_rotation(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    registry().counter("t.jsonl_probe").inc()
    w = export.JsonlWriter(path, interval=3600, max_bytes=400)
    for _ in range(6):
        w.write_now()
    assert os.path.exists(path)
    assert os.path.exists(path + ".1"), "size-based rotation never fired"
    assert os.path.getsize(path) <= 400 + 8192   # one line of slack
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            assert "ts" in rec and "metrics" in rec
            assert rec["metrics"]["t.jsonl_probe"] == 1


def test_jsonl_writer_periodic_thread(tmp_path):
    import time as _time
    path = str(tmp_path / "periodic.jsonl")
    w = export.JsonlWriter(path, interval=0.05)
    w.start()
    _time.sleep(0.3)
    w.stop()
    with open(path) as f:
        lines = f.readlines()
    assert len(lines) >= 2                      # ticked + final write
    json.loads(lines[-1])


# -- lint gate: no new ad-hoc counter dicts ---------------------------------
# The AST walker that used to live here moved into the mxlint subsystem
# (mxnet_tpu/tools/mxlint — the 'counter-dict' rule); this thin
# assertion rides the suite's single cached lint pass.

def test_no_adhoc_counter_dicts_in_package():
    from mxnet_tpu.tools import mxlint
    assert mxlint.rule_findings("counter-dict") == []


# -- help lines -------------------------------------------------------------

def test_help_lines_in_prometheus_text():
    reg = registry()
    reg.counter("t.helped_total", help="a helped counter").inc(2)
    reg.gauge("t.helped_gauge", help="a helped gauge").set(1.0)
    reg.histogram("t.helped_us", help="a helped histogram").observe(5.0)
    text = export.prometheus_text()
    assert "# HELP mxtpu_t_helped_total a helped counter" in text
    assert "# HELP mxtpu_t_helped_gauge a helped gauge" in text
    assert "# HELP mxtpu_t_helped_us a helped histogram" in text
    # HELP precedes TYPE for the same family (exposition-format order)
    lines = text.splitlines()
    i_help = lines.index("# HELP mxtpu_t_helped_total a helped counter")
    assert lines[i_help + 1] == "# TYPE mxtpu_t_helped_total counter"
    # a later registration back-fills a missing description
    reg.counter("t.late_help")
    reg.counter("t.late_help", help="arrived later")
    assert "# HELP mxtpu_t_late_help arrived later" in \
        export.prometheus_text()
    # engine metrics ship descriptions out of the box
    from mxnet_tpu.engine import engine
    engine()
    assert "# HELP mxtpu_engine_ops_dispatched " in \
        export.prometheus_text()


# -- multi-host aggregation -------------------------------------------------

def test_snapshot_all_hosts_single_process_fallback():
    """Without a process group, snapshot(all_hosts=True) serves the
    local registry as host 0 — same shape as the fleet view, no guard
    needed in calling code."""
    reg = registry()
    reg.counter("t.sh_events").inc(4)
    reg.gauge("t.sh_depth").set(3.0)
    h = reg.histogram("t.sh_us")
    for v in (10.0, 20.0, 30.0):
        h.observe(v)
    snap = reg.snapshot(all_hosts=True)
    c = snap["t.sh_events"]
    assert c["kind"] == "counter" and c["total"] == 4
    assert c["host"] == {"0": 4}
    assert snap["t.sh_depth"]["host"] == {"0": 3.0}
    hh = snap["t.sh_us"]
    assert hh["count"] == 3 and hh["host"]["0"]["count"] == 3
    # merged-bucket aggregates match the local read exactly (one host)
    assert hh["p50"] == h.read()["p50"]


def test_merge_host_states_math():
    """Merging is pure bucket/count arithmetic — simulate three hosts
    without any process group."""
    from mxnet_tpu.observability.registry import (MetricsRegistry,
                                                  merge_host_states)
    states = []
    for host in range(3):
        reg = MetricsRegistry()
        reg.counter("t.m_events").inc(host + 1)
        reg.gauge("t.m_depth").set(float(host))
        h = reg.histogram("t.m_us", base=1.0, growth=2.0, buckets=8)
        for _ in range(host + 1):
            h.observe(2.0 ** host)
        if host == 2:          # a host-local-only metric stays labeled
            reg.counter("t.m_only_host2").inc(7)
        states.append((host, reg.export_state()))
    merged = merge_host_states(states)
    assert merged["t.m_events"]["total"] == 6
    assert merged["t.m_events"]["host"] == {"0": 1, "1": 2, "2": 3}
    assert merged["t.m_depth"]["host"] == {"0": 0.0, "1": 1.0, "2": 2.0}
    hh = merged["t.m_us"]
    assert hh["count"] == 6
    assert hh["min"] == 1.0 and hh["max"] == 4.0
    assert hh["host"]["2"]["count"] == 3
    only = merged["t.m_only_host2"]
    assert only["total"] == 7 and only["host"] == {"2": 7}


def test_prometheus_aggregate_text_host_labels(monkeypatch):
    """The AGGREGATE endpoint serves every series with a host label;
    single-process it serves the local host's series as host 0."""
    registry().counter("t.agg_probe").inc(9)
    registry().histogram("t.agg_probe_us").observe(3.0)
    text = export.prometheus_text_aggregate()
    assert 'mxtpu_t_agg_probe{host="0"} 9' in text
    assert 'mxtpu_t_agg_probe_us_bucket{host="0",le=' in text
    assert 'mxtpu_t_agg_probe_us_count{host="0"}' in text
    # the endpoint switches on the env var, read live per scrape
    monkeypatch.setenv("MXTPU_METRICS_AGGREGATE", "1")
    srv = export.MetricsServer(port=0, addr="127.0.0.1")
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics",
            timeout=10).read().decode()
        assert 'mxtpu_t_agg_probe{host="0"} 9' in body
    finally:
        srv.stop()


_MH_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
    from mxnet_tpu.base import force_cpu_mesh
    force_cpu_mesh(1, verify=False)  # distributed init must precede the
    import numpy as np               # first backend query
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import dist

    dist.init_process_group()        # joins from DMLC_* env
    rank, nw = dist.rank(), dist.num_workers()

    from mxnet_tpu.observability import export, registry
    reg = registry()
    reg.counter("t.mh_events", help="multi-host probe").inc(rank + 1)
    reg.gauge("t.mh_depth").set(float(rank) * 2.0)
    h = reg.histogram("t.mh_us")
    for _ in range(rank + 2):
        h.observe(10.0 * (rank + 1))

    # raw byte-plane round-trip under unequal payload sizes
    blobs = dist.allgather_bytes(b"host" * (rank + 1))
    assert blobs == [b"host" * (r + 1) for r in range(nw)], blobs

    from mxnet_tpu.engine import engine
    engine()                     # materialize engine.* metric families

    snap = reg.snapshot(all_hosts=True)   # the collective gather
    c = snap["t.mh_events"]
    assert c["total"] == sum(r + 1 for r in range(nw)), c
    assert c["host"] == {str(r): r + 1 for r in range(nw)}, c
    g = snap["t.mh_depth"]
    assert g["host"] == {str(r): float(r) * 2.0 for r in range(nw)}, g
    hh = snap["t.mh_us"]
    assert hh["count"] == sum(r + 2 for r in range(nw)), hh
    assert hh["max"] == 10.0 * nw and hh["min"] == 10.0, hh
    assert set(hh["host"]) == {str(r) for r in range(nw)}, hh
    # every host's engine counters ride the same gather
    assert snap["engine.ops_dispatched"]["total"] >= 0

    # the gathered states feed the host-labeled text format on EVERY
    # host (MXTPU_METRICS_AGGREGATE mode serves this from host 0)
    txt = export.prometheus_text_aggregate()
    for r in range(nw):
        line = 'mxtpu_t_mh_events{host="%d"} %d' % (r, r + 1)
        assert line in txt, txt[:800]
    assert 'mxtpu_t_mh_us_bucket{host="1",le=' in txt
    print(f"WORKER_{rank}_OK")
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_snapshot_all_hosts_multiprocess(tmp_path):
    """Acceptance: host-labeled merged metrics under a REAL (simulated
    localhost) multi-process group over the allgather_host DCN path."""
    n_workers = 2
    port = _free_port()
    script = tmp_path / "mh_worker.py"
    script.write_text(_MH_WORKER)
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
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((r, p.returncode, out))
    for r, rc, out in outs:
        assert rc == 0, f"worker {r} failed:\n{out}"
        assert f"WORKER_{r}_OK" in out, f"worker {r} output:\n{out}"


# -- unified trace timeline -------------------------------------------------

def test_chrome_trace_contains_op_and_span_events(tmp_path):
    """Acceptance: trace.span events land in the profiler's chrome-trace
    JSON as PROPER duration events (pid=host, tid=thread lane) on the
    same timeline as per-op dispatch events."""
    from mxnet_tpu import profiler
    fn = str(tmp_path / "trace.json")
    p = profiler.Profiler.get()
    p.reset()
    profiler.set_config(filename=fn)
    profiler.set_state("run")
    try:
        with trace.span("t.timeline_step_us"):
            y = mx.nd.ones((16,))
            for _ in range(3):
                y = mx.nd.tanh(y * 2.0)
            y.wait_to_read()
    finally:
        profiler.set_state("stop")
    profiler.dump()
    events = json.load(open(fn))["traceEvents"]
    ops = [e for e in events if e.get("cat") == "operator"]
    spans = [e for e in events if e.get("cat") == "span"]
    meta = [e for e in events if e.get("ph") == "M"]
    assert ops, "no operator events on the timeline"
    assert any(e["name"] == "t.timeline_step_us" for e in spans)
    # spans are duration events with real geometry, not instants
    sp = next(e for e in spans if e["name"] == "t.timeline_step_us")
    assert sp["ph"] == "X" and sp["dur"] > 0 and sp["ts"] >= 0
    # one process lane per host, named thread lanes
    assert sp["pid"] == 0 and isinstance(sp["tid"], int)
    assert any(m["name"] == "process_name" and
               m["args"]["name"] == "host 0" for m in meta)
    assert any(m["name"] == "thread_name" for m in meta)
    # ops within the span sit inside its time range (same clock/epoch)
    inside = [e for e in ops if e["ts"] >= sp["ts"] - 1 and
              e["ts"] + e["dur"] <= sp["ts"] + sp["dur"] + 1]
    assert inside, "op events do not overlap their enclosing span"
    # the listener echo is NOT double-counted as an operator event
    assert not any(e["name"].startswith("span:") for e in ops)


def test_span_args_surface_as_chrome_trace_event_args(tmp_path):
    """PR-4 follow-up: ``span(name, args={...})`` metadata (step number,
    batch id) lands as the chrome-trace event's ``args`` — and never as
    histogram labels (the registry metric stays unlabeled)."""
    from mxnet_tpu import profiler
    fn = str(tmp_path / "trace_args.json")
    p = profiler.Profiler.get()
    p.reset()
    profiler.set_config(filename=fn)
    profiler.set_state("run")
    try:
        with trace.span("t.argstep_us", args={"step": 41, "batch": 7}):
            pass
        with trace.span("t.argstep_us"):     # args are per-instance
            pass
    finally:
        profiler.set_state("stop")
    profiler.dump()
    events = json.load(open(fn))["traceEvents"]
    spans = [e for e in events if e.get("cat") == "span"
             and e["name"] == "t.argstep_us"]
    assert len(spans) == 2
    with_args = [e for e in spans if "args" in e]
    assert len(with_args) == 1
    assert with_args[0]["args"] == {"step": 41, "batch": 7}
    # the histogram is shared and label-free regardless of args
    assert registry().get("t.argstep_us").read()["count"] >= 2


# -- crash flight recorder --------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    from mxnet_tpu.observability.flight import FlightRecorder
    path = str(tmp_path / "flight.json")
    fr = FlightRecorder(capacity=4, path=path)
    for i in range(10):
        fr.record(step=i, loss=float(i))
    assert [r["step"] for r in fr.records()] == [6, 7, 8, 9]
    registry().counter("t.flight_probe").inc(3)
    out = fr.dump("unit test")
    assert out == path
    d = json.load(open(path))
    assert d["reason"] == "unit test"
    assert d["n_steps"] == 4
    assert [r["step"] for r in d["steps"]] == [6, 7, 8, 9]
    assert d["steps"][-1]["loss"] == 9.0
    assert d["snapshot"]["t.flight_probe"] == 3
    assert d["host"] == 0 and d["capacity"] == 4
    # capacity 0 disables both recording and dumping
    off = FlightRecorder(capacity=0, path=str(tmp_path / "off.json"))
    off.record(step=1)
    assert off.dump("nope") is None
    assert not os.path.exists(str(tmp_path / "off.json"))


def test_flight_recorder_dump_on_injected_crash(tmp_path, monkeypatch):
    """Acceptance: an injected mid-step crash (MXTPU_FAULT_PLAN
    step_error site) leaves a flight-recorder JSON with the last steps
    and a full snapshot."""
    from mxnet_tpu.faults import TransientFault
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.observability.flight import recorder
    from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer
    path = str(tmp_path / "crash_flight.json")
    monkeypatch.setenv("MXTPU_FLIGHT_PATH", path)
    recorder().clear()      # the ring is process-global; earlier tests
    # in this file may have run supervised steps

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu", in_units=4))
        net.add(nn.Dense(2, in_units=8))
    net.initialize()
    tr = ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.1})
    # two entries at the same step index = both attempts of step 2 fail
    rt = ResilientTrainer(tr, auto_resume=False, max_retries=1,
                          fault_plan="step_error@2,step_error@2")
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype(np.float32)
    y = rng.randint(0, 2, (8,))
    rt.step(x, y)
    with pytest.raises(TransientFault):
        rt.step(x, y)
    d = json.load(open(path))
    assert "step 2 failed" in d["reason"]
    assert d["n_steps"] == 2
    ok, crashed = d["steps"]
    assert ok["step"] == 1 and ok["failed"] is False
    assert isinstance(ok["loss"], float)          # device value, synced
    assert ok["step_us"] > 0                      # at dump time only
    assert crashed["step"] == 2 and crashed["failed"] is True
    assert crashed["loss"] is None
    for k in ("loss_scale", "flush_us_p99", "flush_count",
              "steps_skipped", "rollbacks", "loader_depth", "t",
              "ckpt_inflight"):
        assert k in ok, k
    assert d["snapshot"]["resilience.steps_retried"] >= 1


def test_flight_recorder_excepthook_dump(tmp_path):
    """An UNHANDLED exception dumps through the chained sys.excepthook
    — exercised in a subprocess (pytest swallows in-process ones)."""
    path = str(tmp_path / "hook_flight.json")
    script = tmp_path / "crash.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {REPO!r})
        from mxnet_tpu.observability import flight
        r = flight.recorder()
        r.install()
        r.record(step=1, loss=0.5)
        r.record(step=2, loss=0.25)
        raise RuntimeError("boom")
    """))
    env = dict(os.environ, MXTPU_FLIGHT_PATH=path, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode != 0
    assert "RuntimeError: boom" in r.stderr     # original traceback kept
    d = json.load(open(path))
    assert d["reason"].startswith("unhandled RuntimeError: boom")
    assert [s["step"] for s in d["steps"]] == [1, 2]
    assert "snapshot" in d


def test_resilience_gauges(tmp_path):
    """ROADMAP gauges: resilience.ckpt_inflight tracks the async write
    window; resilience.loss_scale refreshes at sync points."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4, in_units=4))
    net.initialize()
    tr = ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.1})
    rt = ResilientTrainer(tr, checkpoint_dir=str(tmp_path),
                          auto_resume=False, dynamic_loss_scale=True,
                          init_loss_scale=1024.0)
    assert registry().gauge("resilience.loss_scale").value == 1024.0
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype(np.float32)
    y = rng.randint(0, 2, (8,))
    rt.step(x, y)
    rt.checkpoint()             # async enqueue: write now in flight
    g = registry().gauge("resilience.ckpt_inflight")
    assert g.value == 1.0
    rt.flush()                  # committed: window closed
    assert g.value == 0.0
    _ = rt.counters             # drains skip flags -> refreshes scale
    assert registry().gauge("resilience.loss_scale").value == \
        rt.loss_scale


def test_loader_prefetch_depth_gauge():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    data = np.arange(64, dtype=np.float32).reshape(32, 2)
    label = np.arange(32, dtype=np.float32)
    loader = DataLoader(ArrayDataset(mx.nd.array(data),
                                     mx.nd.array(label)),
                        batch_size=4, num_workers=2, prefetch=4)
    for _ in loader:
        pass
    g = registry().get("loader.prefetch_depth")
    assert g is not None and g.kind == "gauge"
    assert 0.0 <= g.value <= 4.0        # sampled inside queue bounds
    assert g.help                       # ships a description


# -- lint gate: no new ad-hoc timing pairs ----------------------------------
# The AST walker (and its grandfather list) that used to live here moved
# into the mxlint subsystem (mxnet_tpu/tools/mxlint — the 'timing-pair'
# rule; legacy debt is frozen in mxlint's baseline.json, the deliberate
# hot-path pair in ndarray/register.py carries an inline pragma); this
# thin assertion rides the suite's single cached lint pass.

def test_no_adhoc_timing_pairs_in_package():
    from mxnet_tpu.tools import mxlint
    assert mxlint.rule_findings("timing-pair") == []


# -- overhead guard (non-tier-1: -m slow only) ------------------------------

@pytest.mark.slow
def test_instrumentation_overhead_under_guard():
    """The acceptance bound: the registry instrumentation on the
    bulked-dispatch path (one counter bump per op + three bumps, one
    histogram observe and one perf_counter pair per segment) must cost
    well under 3% of the measured per-op dispatch time."""
    from tests._overhead import _metrics_overhead_pct
    eng = engine()
    x = mx.nd.ones((4096,))
    y = x
    eng.reset_stats()
    import time as _time
    t0 = _time.perf_counter()
    n = 600
    for _ in range(n):
        y = mx.nd.tanh(y * x)
    y.wait_to_read()
    per_op_us = (_time.perf_counter() - t0) / n * 1e6
    seg = eng.stats()["mean_segment_length"] or 15
    pct = _metrics_overhead_pct(per_op_us, seg, reps=50_000)
    assert pct < 3.0, \
        f"observability instrumentation costs {pct}% of dispatch (>3%)"
