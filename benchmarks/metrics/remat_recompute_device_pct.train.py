"""What rematerialisation costs on the device: the self time of the
instructions that run a block's forward again in the backward (the way
``recompute`` of the program's table), over the busy seconds of the traced
run, in percent.  Nothing without a traced run, on a program that publishes
no table, or where nothing is rematerialised."""
from benchmarks.harness import scope_times


def read(ctx):
    return scope_times.way_pct(ctx, "recompute") or None
