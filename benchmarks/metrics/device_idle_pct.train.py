"""The device's idle share in the traced seconds of a training cell: one
minus the union of the device operations' intervals over the traced
window."""
from benchmarks.harness import trace_reduce


def read(ctx):
    if ctx.reduced is None or ctx.cell["kind"] != "train_steps":
        return None
    return trace_reduce.idle_pct(ctx.reduced)
