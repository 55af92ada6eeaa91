"""How many of the key tiles the flash forward kernel visits run its loop
body without a mask: of one head's sweeps, all rows full, the tiles wholly
below the length, under every row's diagonal and inside every row's window
over all the tiles visited (the program's gauges
``kernels.flash_attention.tiles_plain`` / ``.tiles_visited``, from shapes,
set by every build, so they are the forward kernel's last built: in a model
of window and full blocks, the last block's kind).  0 where the shape keeps
the one masked body.  Nothing where the program has no such gauges."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    plain, visited = (registry().get(f"kernels.flash_attention.{k}")
                      for k in ("tiles_plain", "tiles_visited"))
    if plain is None or visited is None or not visited.value:
        return None
    return 100.0 * plain.value / visited.value
