"""The hyper-connections' device time a traced step, in milliseconds: the
self time of the instructions under a scope ``hc`` (``hc/coeff``: the norm
over a token's streams and the projection to its 24 coefficients;
``hc/sinkhorn``: the 20 Sinkhorn-Knopp steps of H_res; ``hc/pre``: the
sublayer's input; ``hc/mix``: the streams' update), forward, recomputed and
backward, around every sublayer.  Nothing without a traced run, on a
program that publishes no table, or in a model with the plain residual
path (no such scope runs)."""
from benchmarks.harness import scope_times


def read(ctx):
    return scope_times.ms_a_step(ctx, "hc")
