"""The part of ``train_step_build_s`` that was jaxpr tracing and lowering,
which no compile cache saves (the program's counter
``trainer.trace_lower_s``).  Nothing where the program does not count it."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    calls = registry().get("trainer.compile_calls")
    seconds = registry().get("trainer.trace_lower_s")
    if calls is None or seconds is None or not calls.n:
        return None
    return float(seconds.n)
