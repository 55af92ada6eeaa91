"""How much of the causal triangle the window leaves the flash forward
kernel: the key tiles its query tiles' sweeps visit over those the diagonal
alone would leave them (the program's gauges
``kernels.flash_attention.key_tiles`` / ``.key_tiles_causal``, from shapes,
as the kernel publishes its grid; only a build with a window sets them, so
they are the window blocks' wherever the model's full blocks stand).  100
where the window stopped bounding the sweep.  Nothing where the program has
no such gauges."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    visited, causal = (registry().get(f"kernels.flash_attention.{k}")
                       for k in ("key_tiles", "key_tiles_causal"))
    if visited is None or causal is None or not causal.value:
        return None
    return 100.0 * visited.value / causal.value
