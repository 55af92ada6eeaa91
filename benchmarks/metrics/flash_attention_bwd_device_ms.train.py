"""The flash backward's device time a traced step, in milliseconds: the self
time of the instructions under a scope ``flash_attention_bwd`` (the kernels
``flash_attention_bwd_dq`` / ``_dkv``, their window builds, the
concatenation of their results), by the program's table of its compiled
step (``benchmarks/harness/scope_times.py``).  Nothing without a traced run
or on a program that publishes no table."""
from benchmarks.harness import scope_times


def read(ctx):
    return scope_times.ms_a_step(ctx, "flash_attention_bwd")
