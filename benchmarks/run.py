"""One run of one benchmark cell:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process: load, build the program from the seed, warm up every shape
the cell uses (all of that is ``setup_s``), measure for ``--seconds``, read
the device's memory peak, free the program, check what the timed path
produced against the plain reference, print one JSON line, exit.  No TPU,
or fewer chips than the cell asks for: a non-zero exit and no result line.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` prints its
per-layer metrics, with a few seconds of the same traffic traced by the
profiler after the window has closed (so the window itself is never traced).

``--rehearse FILE`` (for ``benchmarks/tests`` only; the driver's four flags
cannot reach it) overrides sizes from FILE, runs wherever JAX runs, marks
the device as it is, and prints no metric.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TRACE_SECONDS = 3.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def log(what):
    """A progress line on stderr with the seconds since the process began
    (the lines compared come after all of these)."""
    print(f"[bench {time.perf_counter() - T_PROCESS_START:8.2f}s] {what}",
          file=sys.stderr, flush=True)


def say_compared(numbers, notes, correct):
    """Each number compared beside its limit, as the last lines of stderr."""
    for name, (value, limit) in numbers.items():
        print(f"compared {name} = {value:.6g} (limit {limit:.6g})",
              file=sys.stderr)
    print(f"compared notes {json.dumps(notes)} -> correct={correct}",
          file=sys.stderr, flush=True)


def run(args, faults=None):
    """The whole of a run; returns the result line's object.  ``faults`` is
    for ``benchmarks/tests``: a callable given the kind once its timed
    object is built and before that object's first step, to break the
    timed path underneath."""
    from benchmarks.harness import compare, device, loader, spans, \
        trace_reduce

    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        sys.exit("benchmark: the program (mxnet_tpu/) is not in this "
                 "checkout; nothing to measure")
    bench = loader.benchmark()
    entry, cell, cfg = loader.cell_and_config(bench, args.workload,
                                              args.rehearse)
    rehearsal = args.rehearse is not None
    if rehearsal:
        import jax
        devices = jax.devices()[:entry["chips"]]
    else:
        devices = device.require_chips(entry["chips"])[:entry["chips"]]
    cache_dir = device.configure_compile_cache()
    kind_mod = loader.load_module("traffic", cell["kind"])
    ctx = types.SimpleNamespace(
        bench=bench, entry=entry, cell=cell, cfg=cfg, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices,
        rehearsal=rehearsal, spans=spans.Spans(), on_built=faults,
        model=loader.load_module("models", cfg["family"]),
        reference=loader.load_module("reference", cfg["family"]),
        flops=loader.load_module("flops", cfg["family"]),
        peaks=None if rehearsal else device.peaks(devices[0].device_kind))

    cache_events = device.CacheEvents().listen()
    ctx.log = lambda what: log(f"{what} [{cache_events}]")
    log(f"imports done; devices {devices}; compile cache {cache_dir}")
    kind = ctx.kind = kind_mod.Kind(ctx)
    kind.setup()
    setup_s = time.perf_counter() - T_PROCESS_START
    ctx.log(f"set-up done; window of {args.seconds} s opens")
    in_setup = (cache_events.hits, cache_events.misses)
    result = ctx.result = kind.window(args.seconds)
    ctx.log("window closed")
    in_window = cache_events.misses - in_setup[1]

    reduced = None
    if ctx.trace:
        import jax
        trace_dir = os.path.join(loader.ROOT, ".bench_trace",
                                 f"{args.workload}.{os.getpid()}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            traced_s = kind.traced(TRACE_SECONDS)
        finally:
            jax.profiler.stop_trace()
    kind.finish()
    on_cpu = rehearsal and devices[0].platform == "cpu"
    memory_peak = None if on_cpu else device.memory_peak(devices)
    memory_peaks = {} if on_cpu else device.memory_peaks(devices[0])
    kind.free()
    if ctx.trace:
        planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        reduced = trace_reduce.reduce(planes, traced_s)
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx.log("program freed, trace reduced; the reference runs")
    numbers, notes = kind.verify()
    ctx.log("reference done")
    correct = compare.verdict(numbers)

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": dev}
    ctx.reduced = reduced
    if not rehearsal:
        values = dict(result["end_to_end"], setup_s=setup_s)
        if ctx.trace:
            if reduced is None:
                sys.exit("benchmark: the traced run saw no operation on "
                         "the device")
            dev["busy_s"], dev["window_s"] = reduced["busy_s"], \
                reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
            for m in loader.metrics_for(bench, "per_layer", args.workload,
                                        values):
                v = loader.load_module("metrics", m["name"]).read(ctx)
                if v is not None:
                    line["metrics"][m["name"]] = {"value": v,
                                                  "unit": m["unit"]}
        else:
            for m in loader.metrics_for(bench, "end_to_end", args.workload,
                                        values):
                if m["name"] in values:
                    line["metrics"][m["name"]] = {
                        "value": values[m["name"]], "unit": m["unit"]}
    line["notes"] = dict(notes, compile_cache=cache_dir, setup_s=setup_s,
                         cache_hits_in_setup=in_setup[0],
                         cache_misses_in_setup=in_setup[1],
                         compiles_in_window=in_window, **memory_peaks,
                         **result.get("notes", {}))
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    say_compared(numbers, notes, correct)
    return line


def main(argv=None):
    args = parse(argv)
    line = run(args)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
