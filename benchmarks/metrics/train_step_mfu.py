"""The whole training step's share of the chip's bf16 peak: the operations
the forward and backward need (from shapes and the rows' valid lengths,
``benchmarks/flops/<family>``), summed over the steps the window finished,
over the window's time and the peak of the chips used."""


def read(ctx):
    traffic = ctx.cell["traffic_params"]
    flops = sum(ctx.flops.train_step(ctx.cfg, traffic, batch)
                for batch in ctx.kind.window_batches)
    peak = ctx.peaks["bf16_flops_per_s"] * len(ctx.devices)
    return 100.0 * flops / (ctx.kind.window_s * peak)
