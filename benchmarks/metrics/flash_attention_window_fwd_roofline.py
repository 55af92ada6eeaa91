"""The window build of the flash forward kernel against its roofline: the
least time the chip could take for the window blocks' calls traced (the
larger of operations over the bf16 peak and bytes over the HBM bandwidth;
the operations are those of the pairs inside the window, k and v are read
once a key head), over the device time of the events named
``flash_attention_fwd_window`` in the trace.  Nothing where no such kernel
ran (a program without the window build, a configuration without window
blocks)."""
from benchmarks.harness import trace_reduce


def share(ctx, names, per_step):
    """100 x least time / device time of the events whose name holds one of
    ``names``, the work counted by ``ctx.flops.<per_step>``; None where
    either is missing."""
    count = getattr(ctx.flops, per_step, None)
    if ctx.reduced is None or count is None:
        return None
    seconds = sum(trace_reduce.kernel_seconds(ctx.reduced, n)[0] or 0.0
                  for n in names)
    if not seconds:
        return None
    ops = byts = 0
    for batch in ctx.kind.traced_batches:
        o, b = count(ctx.cfg, ctx.cell["traffic_params"], batch)
        ops, byts = ops + o, byts + b
    return 100.0 * max(ops / ctx.peaks["bf16_flops_per_s"],
                       byts / ctx.peaks["hbm_bytes_per_s"]) / seconds


def read(ctx):
    name = getattr(ctx.flops, "FLASH_WINDOW_FWD", None)
    return None if name is None else share(
        ctx, (name,), "flash_window_fwd_per_step")
