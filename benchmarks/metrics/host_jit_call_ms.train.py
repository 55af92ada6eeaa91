"""Host side of a training step, the call: the program's own span
``trainer.jit_call_us`` (the jitted step until it returns, not waited for;
a call that traced or compiled is not in it), the histogram's exact total
over its count, in milliseconds.  Nothing where the program has no such
span."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    span = registry().get("trainer.jit_call_us")
    if span is None or not span.count:
        return None
    return span.total / span.count / 1e3
