"""Device seconds by the program's own scopes: the traced run's self time by
HLO instruction (``ctx.reduced["ops"]``, ``trace_reduce.reduce``) joined with
the table the program publishes for the step it compiled,
``mx.profiler.step_scopes()``: {instruction name: (scope, "fwd" |
"recompute" | "bwd", inferred)}.  Made once a run and kept on ``ctx``.

``read(ctx)`` returns None, with one line on stderr, where there is nothing
to join: no traced run, a program without ``step_scopes`` (an older tree
under these benchmark files), no step compiled, or a table that knows less
than 95 % of the busy seconds by instruction name (the table of another
module than the one that ran).  It never raises.

The traced seconds also hold the small programs beside the step (the RNG
key's split, the loss's read).  Their instructions are not in the table and
count as unscoped.  Where one of them shares a name with an instruction of
the step (``fusion``), ``reduced["ops"]`` has already added the two:
microseconds against a step of 80-825 ms, and nothing is done about it.
"""
import sys
import time

MIN_COVERED = 0.95
_UNREAD = object()


def read(ctx):
    """{"seconds": {(scope, way): s}, "inferred_s", "unscoped_s" (no scope
    after inference, or an instruction the table does not know),
    "unstated_s" (the same before inference: what a trace alone leaves
    without a scope), "covered", "busy_s", "steps"} or None."""
    got = getattr(ctx, "scope_times", _UNREAD)
    if got is _UNREAD:
        try:
            got = _join(ctx)
        except Exception as e:      # noqa: BLE001 - a reader never fails a run
            got = f"{type(e).__name__}: {e}"
        if isinstance(got, str):
            print(f"scope_times: no device time by scope ({got})",
                  file=sys.stderr, flush=True)
            got = None
        ctx.scope_times = got
    return got


def _join(ctx):
    reduced = getattr(ctx, "reduced", None)
    if reduced is None:
        return "no traced run"
    import mxnet_tpu as mx
    step_scopes = getattr(mx.profiler, "step_scopes", None)
    if step_scopes is None:
        return "the program has no mx.profiler.step_scopes"
    t0 = time.perf_counter()
    table = step_scopes()
    took = time.perf_counter() - t0
    if table is None:
        return "no trainer compiled a step"
    ops = reduced["ops"]
    total = sum(ops.values())
    seconds, known = {}, 0.0
    inferred = unscoped = unstated = 0.0
    for name, s in ops.items():
        scope, way, guessed = table.get(name, ("", "fwd", False))
        known += s if name in table else 0.0
        if guessed or not scope:
            unstated += s
        if not scope:
            unscoped += s
            continue
        inferred += s if guessed else 0.0
        seconds[scope, way] = seconds.get((scope, way), 0.0) + s
    covered = known / total if total else 0.0
    notes = ctx.result.setdefault("notes", {})
    notes.update(scope_table_covered=covered, step_scopes_s=took,
                 scope_table_instructions=len(table))
    if covered < MIN_COVERED:
        return (f"the table knows {100 * covered:.1f} % of the busy "
                f"seconds by instruction name: another module's")
    busy = reduced["busy_s"]
    notes.update(unscoped_before_inference_pct=100.0 * unstated / busy,
                 inferred_device_pct=100.0 * inferred / busy)
    return {"seconds": seconds, "inferred_s": inferred,
            "unscoped_s": unscoped, "unstated_s": unstated,
            "covered": covered, "busy_s": busy,
            "steps": len(ctx.kind.traced_batches)}


def _holds(scope, path):
    """``path`` (a tuple of names) lies in ``scope`` as consecutive
    components, at any depth."""
    parts = scope.split("/")
    n = len(path)
    return any(tuple(parts[i:i + n]) == path
               for i in range(len(parts) - n + 1))


def ms_a_step(ctx, *paths):
    """Milliseconds a traced step of the scopes that hold one of ``paths``
    (each a name or a ``/``-joined run of names), all ways together; None
    where ``read`` gives nothing or no such scope ran."""
    got = read(ctx)
    if got is None or not got["steps"]:
        return None
    paths = [tuple(p.split("/")) for p in paths]
    hit = [s for (scope, _), s in got["seconds"].items()
           if any(_holds(scope, p) for p in paths)]
    return 1e3 * sum(hit) / got["steps"] if hit else None


def way_pct(ctx, way):
    """Busy seconds that went one way, scoped time alone, in percent of
    the busy seconds."""
    got = read(ctx)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * sum(s for (_, w), s in got["seconds"].items()
                       if w == way) / got["busy_s"]
