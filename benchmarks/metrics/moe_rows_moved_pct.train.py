"""How much of the dispatch buffer one pass of the expert layer's row movers
touched in the last step read: whole row tiles up to the live count
(``moe.rows_moved``, in the layer with the most live rows) over the buffer's
``top_k`` x tokens rows (``moe.buffer_rows``), in percent.  The live share
is the floor (an eighth where 8 of 64 experts are held and the routing is
even); 100 means every pass sweeps the worst-case buffer.  Nothing where the
program has no such gauge."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    moved, rows = (registry().get(f"moe.{k}")
                   for k in ("rows_moved", "buffer_rows"))
    if moved is None or rows is None or not rows.value:
        return None
    return 100.0 * moved.value / rows.value
