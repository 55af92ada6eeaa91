"""What building the step cost this process: the seconds of the trainer's
calls during which jax traced, lowered or compiled anything (the program's
counter ``trainer.compile_call_s``; the first call, and any later one that
compiled again).  Nothing where the program does not count it."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    calls = registry().get("trainer.compile_calls")
    seconds = registry().get("trainer.compile_call_s")
    if calls is None or seconds is None or not calls.n:
        return None
    return float(seconds.n)
