"""Copies the reference's weights (made from the seed by
``benchmarks/reference``) into a program's Parameters, leaf by leaf."""
from mxnet_tpu.ndarray import NDArray

LAYER_LEAVES = (("qkv_w", "qkv", "weight"), ("qkv_b", "qkv", "bias"),
                ("proj_w", "proj", "weight"), ("proj_b", "proj", "bias"),
                ("ln1_g", "ln1", "gamma"), ("ln1_b", "ln1", "beta"),
                ("ffn1_w", "ffn.ffn1", "weight"), ("ffn1_b", "ffn.ffn1", "bias"),
                ("ffn2_w", "ffn.ffn2", "weight"), ("ffn2_b", "ffn.ffn2", "bias"),
                ("ln2_g", "ln2", "gamma"), ("ln2_b", "ln2", "beta"))


def _walk(block, path):
    for part in path.split("."):
        block = getattr(block, part)
    return block


def put(param, value, names, leaf):
    """``value`` (a jax array on the device) into ``param``; remembers
    which reference leaf the program's parameter name stands for."""
    ctx = param.list_ctx()[0]
    param.set_data(NDArray(value, ctx=ctx))
    names[param.name] = leaf


def put_layer(cell, attn, w, i, names):
    """One post-LN layer; ``attn`` holds ``qkv`` and ``proj`` (``cell.attn``
    for the encoder cell)."""
    for leaf, path, attr in LAYER_LEAVES:
        owner = attn if path in ("qkv", "proj") else cell
        put(getattr(_walk(owner, path), attr), w[f"l{i}.{leaf}"], names,
            f"l{i}.{leaf}")
