"""The dependent iterations of the delta rule's state pass that one compiled
training step runs: every delta-rule layer's pass over its chunks, forward,
recomputed (the blocks are rematerialised) and backward, added up while the
step was traced (the program's gauge ``gdn.scan_steps``, as the flash kernel
publishes its grid).  Each iteration waits for the one before it, so this is
the length of the step's sequential part: a larger chunk or a state kept
across the checkpoint shortens it.  Nothing where the program has no such
gauge."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    steps = registry().get("gdn.scan_steps")
    if steps is None or not steps.value:
        return None
    return steps.value
