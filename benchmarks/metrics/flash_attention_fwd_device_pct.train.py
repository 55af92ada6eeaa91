"""The flash forward kernel's share of the device's busy time in the traced
seconds of a training cell: the device time of the events named
``flash_attention_fwd`` over the busy seconds.  Nothing where the kernel
did not run."""
from benchmarks.harness import trace_reduce


def read(ctx):
    if ctx.reduced is None or not hasattr(ctx.flops, "FLASH_KERNEL"):
        return None
    seconds, _ = trace_reduce.kernel_seconds(ctx.reduced,
                                             ctx.flops.FLASH_KERNEL)
    if not seconds or not ctx.reduced["busy_s"]:
        return None
    return 100.0 * seconds / ctx.reduced["busy_s"]
