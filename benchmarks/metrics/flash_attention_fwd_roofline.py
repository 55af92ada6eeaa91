"""The flash forward kernel's share of its roofline: the least time the chip
could take for the calls traced (the larger of operations over the bf16 peak
and bytes over the HBM bandwidth, both from shapes and the rows' valid
lengths), over the device time of the events named ``flash_attention_fwd``
in the trace.  Nothing where the kernel did not run."""
from benchmarks.harness import trace_reduce


def read(ctx):
    if ctx.reduced is None or not hasattr(ctx.flops, "flash_fwd_per_step"):
        return None
    seconds, _ = trace_reduce.kernel_seconds(ctx.reduced,
                                             ctx.flops.FLASH_KERNEL)
    if not seconds:
        return None
    ops = byts = 0
    for batch in ctx.kind.traced_batches:
        o, b = ctx.flops.flash_fwd_per_step(
            ctx.cfg, ctx.cell["traffic_params"], batch)
        ops, byts = ops + o, byts + b
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    ctx.result.setdefault("notes", {})["flash_fwd_bound_by"] = \
        "compute" if ops / ctx.peaks["bf16_flops_per_s"] >= \
        byts / ctx.peaks["hbm_bytes_per_s"] else "memory"
    return 100.0 * least / seconds
