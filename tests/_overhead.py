"""The probes behind the two ``slow`` overhead guards
(``test_observability.py::test_instrumentation_overhead_under_guard``,
``test_tracing.py::test_tracing_overhead_under_guard``): each times the
registry's or the tracer's own primitives in the running process, so a
loaded host cannot pass for a regression."""
import os
import time


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
    c = Counter("overhead.probe")
    h = Histogram("overhead.probe_us")
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


def _tracing_costs(reps=20_000):
    """Measured cost of the causal-tracing seam: the OFF path (what
    every instrumented call site pays when ``MXTPU_TRACE`` is unset —
    one memoized env probe returning None) and one fully sampled
    begin+finish span (ids, clocks, ring append).  Probe instance, not
    the process tracer — probe spans must not pollute the live ring."""
    from mxnet_tpu.observability.registry import registry as _reg
    from mxnet_tpu.observability.tracing import Tracer
    # jsonl="" pins the stream OFF: the probe instance must not resolve
    # an operator's MXTPU_TRACE_JSONL and flush 2k probe spans into the
    # production trace file
    t = Tracer(ring=1024, jsonl="")
    # the tracer's tracing.* counters are get-or-create on the shared
    # registry: snapshot and restore them so ~22k probe begin/finishes
    # don't inflate the live series (no traced workload runs beside the
    # guard in its process, which also makes the MXTPU_TRACE flip below
    # safe)
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
            t.begin("overhead.trace_probe")
        off_us = (time.perf_counter() - t0) / reps * 1e6
        os.environ["MXTPU_TRACE"] = "1"
        t0 = time.perf_counter()
        for _ in range(reps // 10):
            sp = t.begin("overhead.trace_probe", activate=False)
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
