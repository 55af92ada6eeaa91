"""Live-introspection suite: the stack-sampling profiler, the
``/debug/*`` surface, and the progress watchdog.

Covers the PR-19 acceptance matrix: sampler fold/ring/rotation units
and trace-tagged sample resolution, watchdog unit arcs driven by
synthetic ``tick(dt)`` (silence detection, p99-interval math from the
metrics spine, dump-once dedup, the ``term`` action through an
injected kill_fn — zero real sleeps), the flight recorder's
snapshot-then-encode dump discipline under a concurrent writer, every
``/debug/*`` endpoint round-tripped through a live HttpFrontend under
concurrent predict traffic (and the stdlib metrics exporter fallback),
the ``MXTPU_STACKS_SIGNAL`` manual dump with handler chaining, and —
slow-marked — the <3% sampler overhead guard plus the closed-loop
2-process stall acceptance test (injected ``loader_stall`` → exactly
one postmortem bundle naming the stalled loader frame, span ring
stitched to the stalled step's trace).

Watchdog unit tests build PRIVATE ``Watchdog`` instances (no monitor
thread) and per-test histogram names: the metrics registry is
process-global and must not leak state between tests.
"""
import http.client
import itertools
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.observability import tracing
from mxnet_tpu.observability import watchdog as watchdog_mod
from mxnet_tpu.observability.export import MetricsServer, debug_route
from mxnet_tpu.observability.flight import FlightRecorder
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.observability.sampler import (MAX_DEPTH, ProfileWindow,
                                             StackSampler, _fold,
                                             collapsed_from_windows,
                                             chrome_events_from_window,
                                             maybe_start_from_env,
                                             profile, thread_stacks)
from mxnet_tpu.observability.watchdog import (Watchdog, build_postmortem,
                                              install_stack_signal)
from mxnet_tpu.serving import HttpFrontend, ModelRegistry, ModelServer


_uniq = itertools.count()


def _hist_name():
    """Fresh spine-histogram name per test: the registry is global."""
    return f"introspect.tp{next(_uniq)}_us"


class _Elemwise(gluon.HybridBlock):
    def hybrid_forward(self, F, x):
        return F.tanh(x * 2.0) + 0.5


def _net():
    net = _Elemwise()
    net.initialize()
    net.hybridize()
    return net


def _raw_get(port, path, timeout=30.0):
    """(status, content_type, bytes) — /debug serves text AND json."""
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("GET", path)
        r = c.getresponse()
        return r.status, r.getheader("Content-Type") or "", r.read()
    finally:
        c.close()


def _get_json(port, path, timeout=30.0):
    status, _, body = _raw_get(port, path, timeout=timeout)
    return status, json.loads(body)


def _post(port, path, obj, timeout=60.0):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("POST", path, body=json.dumps(obj))
        r = c.getresponse()
        return r.status, json.loads(r.read())
    finally:
        c.close()


class _Spinner:
    """A named worker thread burning CPU in a recognizable frame."""

    def __init__(self, name="introspect-spin"):
        self._stop = threading.Event()
        self.thread = threading.Thread(target=self._spin_work,
                                       name=name, daemon=True)

    def _spin_work(self):
        while not self._stop.is_set():
            sum(i * i for i in range(500))

    def __enter__(self):
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self.thread.join(5.0)


# -- sampler units -----------------------------------------------------------

def test_fold_is_function_identity_and_depth_bounded():
    def leaf():
        return sys._getframe()

    def mid():
        return leaf()

    frame = mid()
    folded = _fold(frame, "worker-0")
    parts = folded.split(";")
    assert parts[0] == "worker-0"
    # outermost-first, leaf last; keys are file:func, no line numbers
    assert parts[-1] == "test_introspection.py:leaf"
    assert parts[-2] == "test_introspection.py:mid"
    assert not any(p.split(":")[-1].isdigit() for p in parts)

    def deep(n):
        if n == 0:
            return sys._getframe()
        return deep(n - 1)

    folded = _fold(deep(MAX_DEPTH + 40), "w")
    # prefix + at most MAX_DEPTH frames, innermost frames kept
    assert len(folded.split(";")) == MAX_DEPTH + 1
    assert folded.endswith("test_introspection.py:deep")


def test_profile_window_counts_collapsed_and_trace_split():
    win = ProfileWindow(hz=100.0)
    for _ in range(3):
        win.add("main;a;b", trace_id="t1")
    win.add("main;a;b", trace_id="t2")
    win.add("main;a;c")
    win.samples = 5
    win.close()
    # collapsed aggregates trace ids away, most-sampled first
    lines = win.collapsed().splitlines()
    assert lines[0] == "main;a;b 4"
    assert lines[1] == "main;a;c 1"
    assert win.by_trace() == {"t1": 3, "t2": 1, "": 1}
    d = win.to_dict()
    assert d["samples"] == 5 and d["hz"] == 100.0
    assert d["t1"] is not None and d["t1"] >= d["t0"]
    assert d["stacks"][0] == {"stack": "main;a;b", "trace_id": "t1",
                              "count": 3}
    # merged view across windows sums per-stack counts
    win2 = ProfileWindow(hz=100.0)
    win2.add("main;a;b")
    merged = collapsed_from_windows([win, win2])
    assert merged.splitlines()[0] == "main;a;b 5"
    # chrome export: one X event per folded stack + thread_name metadata
    events = chrome_events_from_window(win)
    xs = [e for e in events if e["ph"] == "X"]
    assert xs and xs[0]["name"] == "b" and xs[0]["args"]["count"] == 3
    assert any(e["ph"] == "M" and e["args"]["name"] == "main"
               for e in events)


def test_thread_stacks_names_sleeping_frame():
    woke = threading.Event()

    def _nap():
        woke.wait(10.0)

    t = threading.Thread(target=_nap, name="introspect-nap", daemon=True)
    t.start()
    try:
        time.sleep(0.05)
        recs = thread_stacks()
        me = threading.current_thread().name
        by_name = {r["name"]: r for r in recs}
        assert by_name[me]["current"] is True
        nap = by_name["introspect-nap"]
        assert nap["daemon"] is True and nap["current"] is False
        funcs = [f["func"] for f in nap["frames"]]
        assert "_nap" in funcs          # the stalled frame, by name
        assert all({"file", "func", "line"} <= set(f)
                   for f in nap["frames"])
    finally:
        woke.set()
        t.join(5.0)


def test_sampler_daemon_rotates_and_bounds_ring():
    s = StackSampler(hz=400.0, window_secs=0.05, windows=3)
    with _Spinner():
        assert s.start() is True
        assert s.start() is False       # idempotent
        try:
            deadline = time.monotonic() + 5.0
            while (len(s.windows(include_current=False)) < 4
                   and time.monotonic() < deadline):
                time.sleep(0.02)
        finally:
            s.stop()
    wins = s.windows()
    assert 1 <= len(wins) <= 3          # deque bound, not unbounded
    assert all(w.t1 is not None for w in wins)
    assert sum(w.samples for w in wins) > 0
    # the spinner's frame made it into the fold
    assert "_spin_work" in s.collapsed()
    # rate 0 never starts
    assert StackSampler(hz=0.0, window_secs=1.0, windows=2).start() is False


def test_profile_skips_caller_samples_workers():
    with _Spinner():
        win = profile(seconds=0.25, hz=200.0)
    assert win.samples > 0 and win.t1 is not None
    text = win.collapsed()
    assert "introspect-spin" in text and "_spin_work" in text
    # the calling thread is never in its own profile
    assert threading.current_thread().name not in text


def test_trace_tagged_samples_resolve_to_span_ring(monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE", "1")
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    tr = tracing.tracer()
    tr.clear()
    # tracking must be on BEFORE the span activates (production order:
    # the daemon sampler starts at init, spans begin per step/request)
    tracing.enable_thread_span_tracking()
    stop = threading.Event()
    seen = {}

    def work():
        with tr.begin("introspect.traced_work") as sp:
            seen["trace_id"] = sp.trace_id
            while not stop.is_set():
                sum(i * i for i in range(500))

    t = threading.Thread(target=work, name="introspect-traced",
                         daemon=True)
    t.start()
    try:
        time.sleep(0.05)
        win = profile(seconds=0.25, hz=200.0)
    finally:
        stop.set()
        t.join(5.0)
        tracing.disable_thread_span_tracking()
    tid = seen["trace_id"]
    by_trace = win.by_trace()
    assert by_trace.get(tid, 0) > 0     # samples carry the span's trace
    # exemplar-style resolution: sample tag -> the actual ring span
    spans = tr.find(tid)
    assert any(s["name"] == "introspect.traced_work" for s in spans)


def test_maybe_start_from_env_probe_and_live_toggle(monkeypatch):
    import mxnet_tpu.observability.sampler as sampler_mod
    monkeypatch.delenv("MXTPU_PROF_SAMPLE_HZ", raising=False)
    try:
        assert maybe_start_from_env() is False
        assert sampler_mod.sampler().running is False
        monkeypatch.setenv("MXTPU_PROF_SAMPLE_HZ", "200")
        assert maybe_start_from_env() is True
        assert sampler_mod.sampler().running is True
        assert sampler_mod.sampler().hz == 200.0
        # unchanged raw entry: pure memo hit, still on
        assert maybe_start_from_env() is True
        monkeypatch.delenv("MXTPU_PROF_SAMPLE_HZ", raising=False)
        assert maybe_start_from_env() is False
        assert sampler_mod.sampler().running is False
    finally:
        monkeypatch.delenv("MXTPU_PROF_SAMPLE_HZ", raising=False)
        maybe_start_from_env()
        sampler_mod.sampler().stop()


# -- watchdog unit arcs (synthetic tick, no sleeps) --------------------------

def test_watchdog_silence_detection_at_floor(tmp_path):
    hist = _hist_name()
    h = registry().histogram(hist)
    for _ in range(20):
        h.observe(100_000.0)            # p99 = 0.1s
    wd = Watchdog(factor=4.0, action="dump",
                  path=str(tmp_path / "pm.json"))
    tp = wd.touchpoint("introspect.step", hist=hist)
    tp.beat()
    assert wd.tick(0.5) == []           # progress tick: arms the clock
    assert wd.tick(0.5) == []           # silent 0.5s < floor
    stalls0 = registry().counter("watchdog.stalls").value
    (stall,) = wd.tick(0.5)             # silent 1.0s: floor crossed
    # 4 x 0.1s = 0.4s is below the 1.0s floor -> floor wins
    assert stall["touchpoint"] == "introspect.step"
    assert stall["threshold_s"] == pytest.approx(1.0)
    assert stall["p99_us"] == pytest.approx(100_000.0)
    assert stall["silent_s"] == pytest.approx(1.0)
    assert stall["beats"] == 1 and stall["factor"] == 4.0
    assert registry().counter("watchdog.stalls").value == stalls0 + 1
    assert os.path.exists(wd.last_postmortem)


def test_watchdog_p99_interval_math_uses_spine_delta(tmp_path):
    hist = _hist_name()
    h = registry().histogram(hist)
    for _ in range(20):
        h.observe(1_000_000.0)          # slow history: p99 = 1.0s
    wd = Watchdog(factor=2.0, floor_s=0.05,
                  path=str(tmp_path / "pm.json"))
    tp = wd.touchpoint("introspect.step", hist=hist)
    tp.beat()
    assert wd.tick(0.1) == []           # snapshot taken here (count=20)
    for _ in range(10):
        h.observe(100_000.0)            # recent beats are 10x faster
    tp.beat()
    assert wd.tick(0.1) == []           # progress; snapshot kept
    # recent p99 (the 0.1s delta), NOT the 1.0s lifetime p99, sets the
    # threshold: 2 x 0.1s = 0.2s.  A lifetime-p99 watchdog would need
    # 2.0s of silence here.
    assert wd.tick(0.15) == []          # 0.15s < 0.2s
    (stall,) = wd.tick(0.1)             # 0.25s >= 0.2s
    assert stall["p99_us"] == pytest.approx(100_000.0)
    assert stall["threshold_s"] == pytest.approx(0.2)


def test_watchdog_dump_once_dedup_and_rearm(tmp_path):
    hist = _hist_name()
    h = registry().histogram(hist)
    for _ in range(10):
        h.observe(50_000.0)
    pm = str(tmp_path / "pm.json")
    wd = Watchdog(factor=1.0, floor_s=0.2, path=pm)
    tp = wd.touchpoint("introspect.step", hist=hist)
    dumps0 = registry().counter("watchdog.postmortems").value
    tp.beat()
    wd.tick(0.1)
    assert len(wd.tick(0.2)) == 1       # fires
    n_dumps = registry().counter("watchdog.postmortems").value
    assert n_dumps == dumps0 + 1
    # still silent: no re-fire, no second bundle
    for _ in range(5):
        assert wd.tick(0.2) == []
    assert registry().counter("watchdog.postmortems").value == n_dumps
    bundle = json.load(open(pm))
    assert bundle["stalled"][0]["touchpoint"] == "introspect.step"
    assert bundle["stacks"] and "reason" in bundle
    # progress re-arms; a second quiet period dumps again
    tp.beat()
    assert wd.tick(0.1) == []
    assert len(wd.tick(0.3)) == 1
    assert registry().counter("watchdog.postmortems").value == n_dumps + 1


def test_watchdog_term_action_via_injected_kill_fn(tmp_path):
    hist = _hist_name()
    h = registry().histogram(hist)
    for _ in range(10):
        h.observe(50_000.0)
    killed = []
    wd = Watchdog(factor=1.0, floor_s=0.2, action="term",
                  path=str(tmp_path / "pm.json"),
                  kill_fn=lambda: killed.append(1))
    tp = wd.touchpoint("introspect.step", hist=hist)
    tp.beat()
    wd.tick(0.1)
    assert len(wd.tick(0.25)) == 1
    assert killed == [1]                # injected, no real SIGTERM
    # the postmortem still landed BEFORE the kill
    assert os.path.exists(wd.last_postmortem)
    wd.tick(0.25)
    assert killed == [1]                # fired flag: kill once per stall


def test_watchdog_no_data_never_fires(tmp_path):
    wd = Watchdog(factor=2.0, floor_s=0.1, path=str(tmp_path / "pm.json"))
    # never-beaten touchpoint: the loop hasn't started
    wd.touchpoint("introspect.idle", hist=_hist_name())
    for _ in range(10):
        assert wd.tick(1.0) == []
    # beats but an empty histogram: nothing to compare silence against
    tp = wd.touchpoint("introspect.nohist", hist=_hist_name())
    tp.beat()
    for _ in range(10):
        assert wd.tick(1.0) == []
    # factor 0 = disarmed entirely
    wd0 = Watchdog(factor=0.0, path=str(tmp_path / "pm0.json"))
    tp0 = wd0.touchpoint("introspect.off", hist=_hist_name())
    tp0.beat()
    assert wd0.tick(100.0) == []
    assert wd.last_postmortem is None and wd0.last_postmortem is None


def test_build_postmortem_bundle_shape():
    with _Spinner():
        bundle = build_postmortem("unit test", stalled=[{"touchpoint": "x"}])
    assert bundle["reason"] == "unit test"
    assert bundle["pid"] == os.getpid()
    assert bundle["stalled"] == [{"touchpoint": "x"}]
    names = [r["name"] for r in bundle["stacks"]]
    assert "introspect-spin" in names
    assert {"n_steps", "steps", "n_requests", "requests"} \
        <= set(bundle["flight"])
    assert isinstance(bundle["trace_spans"], list)
    assert isinstance(bundle["snapshot"], dict)


# -- flight recorder: dump must not block writers ----------------------------

class _SlowDeviceVal:
    """A device-value stand-in whose materialization blocks until
    released — the regression shape: dump() used to materialize under
    the ring lock, wedging every concurrent record()."""

    def __init__(self, started, release):
        self._started = started
        self._release = release

    def asnumpy(self):
        self._started.set()
        self._release.wait(10.0)
        return np.float32(1.25)


def test_flight_dump_encodes_outside_lock_writers_unblocked(tmp_path):
    rec = FlightRecorder(capacity=8)
    path = str(tmp_path / "flight.json")
    started, release = threading.Event(), threading.Event()
    rec.record(step=1, loss=_SlowDeviceVal(started, release))
    dump_out = {}

    def _dump():
        dump_out["path"] = rec.dump("regression", path=path)

    dumper = threading.Thread(target=_dump, daemon=True)
    dumper.start()
    assert started.wait(5.0)            # dump is inside materialization

    writer = threading.Thread(
        target=lambda: rec.record(step=2, loss=0.5), daemon=True)
    writer.start()
    writer.join(2.0)
    # the writer finished WHILE the dump was still materializing: the
    # ring lock covers only the snapshot copies
    assert not writer.is_alive()
    assert dumper.is_alive()
    release.set()
    dumper.join(5.0)
    assert dump_out["path"] == path
    payload = json.load(open(path))
    # snapshot semantics: the dump saw the ring as of its snapshot
    assert payload["n_steps"] == 1
    assert payload["steps"][0]["loss"] == pytest.approx(1.25)
    # the concurrent write landed in the ring for the NEXT dump
    assert len(rec.records()) == 2


def test_flight_live_view_shape(tmp_path):
    rec = FlightRecorder(capacity=4)
    rec.record(step=1, loss=0.5)
    rec.record_request(model="m", e2e_us=12.0)
    live = rec.live()
    assert live["n_steps"] == 1 and live["steps"][0]["step"] == 1
    assert live["n_requests"] == 1 and live["requests"][0]["model"] == "m"
    assert {"n_tuning", "tuning", "n_membership", "membership"} \
        <= set(live)
    json.dumps(live)                    # strictly JSON-clean


# -- /debug surface ----------------------------------------------------------

def test_debug_gate_off_is_404_naming_the_knob(monkeypatch):
    monkeypatch.delenv("MXTPU_DEBUG_ENDPOINTS", raising=False)
    assert debug_route("/metrics") is None      # non-debug: fall through
    status, ctype, body = debug_route("/debug/stacks")
    assert status == 404 and b"MXTPU_DEBUG_ENDPOINTS" in body
    fe = HttpFrontend(ModelRegistry(), port=0).start()
    try:
        assert _raw_get(fe.port, "/debug/stacks")[0] == 404
        assert _raw_get(fe.port, "/healthz")[0] == 200
    finally:
        fe.stop(drain=True)


def test_debug_endpoints_live_frontend_under_traffic(monkeypatch):
    monkeypatch.setenv("MXTPU_DEBUG_ENDPOINTS", "1")
    monkeypatch.setenv("MXTPU_TRACE", "1")
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    reg = ModelRegistry()
    reg.load("m", ModelServer(_net(), max_batch=4,
                              batch_window_us=100.0), priority=1)
    fe = HttpFrontend(reg, port=0).start()
    stop = threading.Event()
    errors = []

    def hammer():
        while not stop.is_set():
            try:
                status, _ = _post(fe.port, "/v1/models/m/predict",
                                  {"inputs": [[0.1, -0.2]]})
                if status != 200:
                    errors.append(status)
                    return
            except Exception as exc:   # noqa: BLE001 — surfaced below
                errors.append(exc)
                return

    clients = [threading.Thread(target=hammer, daemon=True)
               for _ in range(3)]
    for c in clients:
        c.start()
    try:
        # index
        status, ctype, body = _raw_get(fe.port, "/debug")
        assert status == 200 and b"/debug/profile" in body
        # stacks: every live thread, trace-tag ready
        status, stacks = _get_json(fe.port, "/debug/stacks")
        assert status == 200 and stacks["pid"] == os.getpid()
        assert len(stacks["threads"]) >= 2
        assert all(t["frames"] for t in stacks["threads"])
        # on-demand profile, all three formats (handler thread samples,
        # so the hammering clients are visible)
        status, ctype, body = _raw_get(
            fe.port, "/debug/profile?seconds=0.2&hz=200")
        assert status == 200 and ctype.startswith("text/plain")
        assert b";" in body             # folded frames present
        status, prof = _get_json(
            fe.port, "/debug/profile?seconds=0.1&hz=100&format=json")
        assert status == 200 and prof["samples"] >= 1 and prof["stacks"]
        status, chrome = _get_json(
            fe.port, "/debug/profile?seconds=0.1&format=chrome")
        assert status == 200 and chrome["traceEvents"]
        # flight rings, live (no dump file involved)
        status, flt = _get_json(fe.port, "/debug/flight")
        assert status == 200
        assert {"steps", "requests", "tuning", "membership"} <= set(flt)
        # trace lookup round-trip through the span ring
        tr = tracing.tracer()
        with tr.begin("introspect.debug_http") as sp:
            tid = sp.trace_id
        status, found = _get_json(fe.port, f"/debug/trace/{tid}")
        assert status == 200 and found["n_spans"] >= 1
        assert any(s["name"] == "introspect.debug_http"
                   for s in found["spans"])
        assert _get_json(fe.port, "/debug/trace/00deadbeef")[0] == 404
        # vars: the live knob table, including the gate itself
        status, knobs = _get_json(fe.port, "/debug/vars")
        assert status == 200 and knobs["MXTPU_DEBUG_ENDPOINTS"] is True
        assert "MXTPU_PROF_SAMPLE_HZ" in knobs
        # unknown debug path
        assert _raw_get(fe.port, "/debug/nope")[0] == 404
    finally:
        stop.set()
        for c in clients:
            c.join(10.0)
        fe.stop(drain=True)
    assert not errors


def test_debug_surface_on_metrics_exporter(monkeypatch):
    monkeypatch.setenv("MXTPU_DEBUG_ENDPOINTS", "1")
    srv = MetricsServer(port=0, addr="127.0.0.1")
    srv.start()
    try:
        status, stacks = _get_json(srv.port, "/debug/stacks")
        assert status == 200 and stacks["threads"]
        assert _raw_get(srv.port, "/metrics")[0] == 200
        monkeypatch.delenv("MXTPU_DEBUG_ENDPOINTS", raising=False)
        assert _raw_get(srv.port, "/debug/stacks")[0] == 404
    finally:
        srv.stop()


# -- MXTPU_STACKS_SIGNAL manual dump -----------------------------------------

def test_stack_signal_dumps_and_chains_previous_handler(
        monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_STACKS_SIGNAL", "SIGUSR1")
    monkeypatch.setenv("MXTPU_FLIGHT_PATH", str(tmp_path / "flight.json"))
    monkeypatch.setattr(watchdog_mod, "_signal_installed", False)
    chained = threading.Event()
    prev = signal.signal(signal.SIGUSR1, lambda s, f: chained.set())
    try:
        assert install_stack_signal() is True
        assert install_stack_signal() is True   # idempotent
        os.kill(os.getpid(), signal.SIGUSR1)
        out = tmp_path / "flight.stacks.json"
        deadline = time.monotonic() + 10.0
        while not out.exists() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert out.exists(), "signal handler wrote no stacks bundle"
        bundle = json.load(open(out))
        assert bundle["reason"] == "stack signal"
        funcs = {f["func"] for r in bundle["stacks"]
                 for f in r["frames"]}
        assert funcs                     # real frames captured
        # drain-chain discipline: the pre-existing handler still ran
        assert chained.wait(5.0)
    finally:
        signal.signal(signal.SIGUSR1, prev)
        monkeypatch.setattr(watchdog_mod, "_signal_installed", False)


def test_stack_signal_disabled_and_unknown_names(monkeypatch):
    monkeypatch.setattr(watchdog_mod, "_signal_installed", False)
    monkeypatch.setenv("MXTPU_STACKS_SIGNAL", "")
    assert install_stack_signal() is False
    monkeypatch.setenv("MXTPU_STACKS_SIGNAL", "SIGNOPE")
    assert install_stack_signal() is False


# -- sampler overhead guard (slow) -------------------------------------------

@pytest.mark.slow
def test_sampler_on_overhead_under_3pct():
    """The tentpole's cost pin: a dispatched-segment loop with the
    daemon sampler running at 100 Hz stays within 3% of the
    sampler-off time (min-of-N beats wall noise)."""
    def loop(n=400):
        x = mx.nd.ones((64, 64))
        for _ in range(n):
            x = x * 1.0001 + 0.0001
        mx.waitall()

    def best(reps=7):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            loop()
            times.append(time.perf_counter() - t0)
        return min(times)

    loop(50)                            # warm the jit/segment caches
    off = best()
    s = StackSampler(hz=100.0, window_secs=60.0, windows=2)
    assert s.start() is True
    try:
        on = best()
    finally:
        s.stop()
    assert s.collapsed()                # it really was sampling
    assert on <= off * 1.03, \
        f"sampler-on overhead {on / off - 1:.2%} exceeds 3% " \
        f"(off={off * 1e3:.1f}ms on={on * 1e3:.1f}ms)"


# -- closed-loop acceptance: injected loader stall -> one postmortem ---------

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_STALL_SCRIPT = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["MXNET_TEST_ROOT"])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.gluon.data import DataLoader
from mxnet_tpu.parallel import ResilientTrainer, ShardedTrainer
from mxnet_tpu.observability.registry import registry
from mxnet_tpu.observability.watchdog import watchdog

mx.random.seed(0)
np.random.seed(0)
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(8, activation="relu", in_units=4))
    net.add(nn.Dense(2, in_units=8))
net.initialize()
tr = ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                    {"learning_rate": 0.1})
rt = ResilientTrainer(tr, auto_resume=False)
wd = watchdog()
assert wd.running, "watchdog did not auto-start from the env knobs"

data = [np.random.randn(4).astype(np.float32) for _ in range(64)]
dl = DataLoader(data, batch_size=8, num_workers=1, timeout=120)
for x in dl:                      # MXTPU_FAULT_PLAN stalls batch 5
    y = np.zeros((x.shape[0],), dtype=np.int64)
    rt.step(x, y)

deadline = time.time() + 10
while wd.last_postmortem is None and time.time() < deadline:
    time.sleep(0.1)
pm = wd.last_postmortem
assert pm and os.path.exists(pm), "no postmortem written"
n_dumps = registry().counter("watchdog.postmortems").value
assert n_dumps == 1, "expected exactly one bundle, got %d" % n_dumps
assert registry().counter("watchdog.stalls").value >= 1

bundle = json.load(open(pm))
assert bundle["stalled"][0]["touchpoint"] == "resilience.step"

# the point of the whole feature: the bundle NAMES the stalled frame
stack_funcs = {f["func"] for r in bundle["stacks"] for f in r["frames"]}
assert "_worker_batch" in stack_funcs, "stalled loader frame not in stacks"
prof = bundle.get("profile") or {}
assert "_worker_batch" in json.dumps(prof), \
    "stalled loader frame not in the sampled profile window"

# span-ring stitch: the last completed step's flight trace_id resolves
steps = bundle["flight"]["steps"]
assert steps, "flight step ring empty in bundle"
tid = steps[-1]["trace_id"]
assert tid, "flight step record carries no trace_id"
ring = {s["trace_id"] for s in bundle["trace_spans"]}
assert tid in ring, "span ring does not stitch to the stalled step"
print("PM=" + pm)
print("STALL_ACCEPT_OK")
"""


@pytest.mark.slow
def test_loader_stall_postmortem_closed_loop(tmp_path):
    """2-process acceptance: a child trainer with an injected
    ``loader_stall`` must produce exactly ONE postmortem whose sampled
    stacks name ``_worker_batch`` and whose span ring stitches to the
    stalled step's trace — asserted inside the child, verified here."""
    script = tmp_path / "stall_child.py"
    script.write_text(_STALL_SCRIPT)
    env = dict(os.environ)
    env.update({
        "MXNET_TEST_ROOT": _REPO_ROOT,
        "JAX_PLATFORMS": "cpu",
        "MXTPU_WATCHDOG_FACTOR": "0.5",
        "MXTPU_WATCHDOG_ACTION": "dump",
        "MXTPU_PROF_SAMPLE_HZ": "67",
        "MXTPU_PROF_WINDOW_SECS": "60",
        "MXTPU_TRACE": "1",
        "MXTPU_FLIGHT_PATH": str(tmp_path / "flight.json"),
        "MXTPU_FAULT_PLAN": "loader_stall@5:8.0",
    })
    env.pop("MXTPU_TRACE_SAMPLE", None)
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        out, _ = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"stall child hung:\n{out}")
    assert proc.returncode == 0, out
    assert "STALL_ACCEPT_OK" in out
    # exactly one bundle on disk too (dump-once, atomic writer)
    bundles = list(tmp_path.glob("flight.postmortem*"))
    assert len(bundles) == 1, out
