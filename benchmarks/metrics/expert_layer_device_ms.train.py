"""The expert layer's device time a traced step, in milliseconds: the self
time of the instructions under a scope ``moe`` (router, dispatch, experts,
combine, shared), stated or inferred: the grouped matmuls' custom calls and
the row movers' zero-fills state no scope and are given their neighbours'
(``mx.profiler.step_scopes``), so they land here.  Nothing without a traced
run, on a program that publishes no table, or in a model without the
layer."""
from benchmarks.harness import scope_times


def read(ctx):
    return scope_times.ms_a_step(ctx, "moe")
