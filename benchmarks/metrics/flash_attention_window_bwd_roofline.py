"""The window build of the flash backward's two kernels against their
roofline: the least time the chip could take for the window blocks' ``dq``
and ``dkv`` calls traced (five products a pair inside the window; q and the
cotangent read and dq written a query head wide, k and v read and dk, dv
written once a key head), over the device time of the events named
``flash_attention_bwd_dq_window`` and ``flash_attention_bwd_dkv_window``.
Nothing where no such kernel ran."""
from benchmarks.harness import loader


def read(ctx):
    names = getattr(ctx.flops, "FLASH_WINDOW_BWD", None)
    if names is None:
        return None
    fwd = loader.load_module("metrics", "flash_attention_window_fwd_roofline")
    return fwd.share(ctx, names, "flash_window_bwd_per_step")
