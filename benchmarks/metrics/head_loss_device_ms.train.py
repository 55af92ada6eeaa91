"""The vocabulary head's and the loss's device time a traced step, in
milliseconds: the self time of the instructions under the scopes
``mlm_out`` (BERT's decoder), ``head``, ``final_norm`` and ``loss``.
Nothing without a traced run or on a program that publishes no table."""
from benchmarks.harness import scope_times


def read(ctx):
    return scope_times.ms_a_step(ctx, "mlm_out", "head", "loss",
                                 "final_norm")
