"""How uneven the routing was in the last step read: the most loaded held
expert's token-assignments over the mean of the held experts', in the
expert layer where that ratio is worst (the program's gauges
``moe.expert_load_max`` / ``moe.expert_load_mean``, read from the aux
buffers the step overwrites).  1 is even; the grouped matmul's time follows
the sum, the deployment's slowest chip the maximum.  Nothing where the
program has no such gauge."""


def read(ctx):
    from mxnet_tpu.observability.registry import registry
    top, mean = (registry().get(f"moe.expert_load_{k}")
                 for k in ("max", "mean"))
    if top is None or mean is None or not mean.value:
        return None
    return top.value / mean.value
