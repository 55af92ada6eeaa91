"""The dense feed-forward blocks' device time a traced step, in
milliseconds: the self time of the instructions under a scope ``ffn``
(forward, the forward run again, backward; Adam's update of a weight is
fused into the matmul that makes its gradient, so it is in here).  Nothing
without a traced run, on a program that publishes no table, or in a model
without such a block."""
from benchmarks.harness import scope_times


def read(ctx):
    return scope_times.ms_a_step(ctx, "ffn")
