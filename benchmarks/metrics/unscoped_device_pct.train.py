"""The device time no scope of the program owns: the self time of the
instructions that have no scope after the table's inference, and of those
the table does not know (the small programs beside the step), over the busy
seconds of the traced run, in percent.  Nothing without a traced run or on
a program that publishes no table."""
from benchmarks.harness import scope_times


def read(ctx):
    got = scope_times.read(ctx)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * got["unscoped_s"] / got["busy_s"]
