"""Host side of a training step, the transfers: the program's own span
``trainer.h2d_us`` (``ShardedTrainer.step``'s ``device_put``s and its three
scalars), the histogram's exact total over its count, in milliseconds.
Nothing where the program has no such span."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    span = registry().get("trainer.h2d_us")
    if span is None or not span.count:
        return None
    return span.total / span.count / 1e3
