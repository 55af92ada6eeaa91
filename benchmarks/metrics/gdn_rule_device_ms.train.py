"""The delta rule's own device time a traced step, in milliseconds: the self
time of the instructions under ``gdn/kkt``, ``gdn/inverse``, ``gdn/wu``,
``gdn/state`` (the scan over the chunks), ``gdn/output`` and ``gdn/decay``;
the mixer's projections, convolutions and norms are not in it.  Nothing
without a traced run, on a program that publishes no table, or in a model
without the rule."""
from benchmarks.harness import scope_times

PARTS = ("kkt", "inverse", "wu", "state", "output", "decay")


def read(ctx):
    return scope_times.ms_a_step(ctx, *(f"gdn/{p}" for p in PARTS))
