"""The kernels of the main paths, compiled for a TPU v5e that is
described and not attached (on-chip-measurement guide §2, rehearsal 3).

Interpret mode cannot see what Mosaic refuses — block shapes, loop
carries, memory spaces — and the chip is not here.  The TPU compiler is:
``jax.experimental.topologies`` describes a ``v5e:2x2`` host, and
``jit(...).lower(shapes on its device).compile()`` raises what the chip's
compiler would raise.  Nothing runs; a compile that passes is not a chip
run (``chip_smoke.py`` is).

All of these stay in THIS file: the worker that describes the topology
loads libtpu and keeps it until it exits, so a second file on another
xdist worker could not.  The topology is described inside a fixture, never
at import.
"""
import os
import re

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to jax's persistent cache
    # but cannot be read back without the chip: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()
    if not had_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


# BERT-base at batch 8, seq 256: 8 x 12 heads of 64; the causal case
# tests/test_kernels_tpu.py runs on the chip; the benchmark's cell (batch
# 16, seq 512: K and V resident, the head at its own 64 lanes); keys too
# long to stay resident (the K-major grid axis); one query against a cache.
# ``pads``: only a ragged Lq or Lk is padded, never a head of 64.
# ``heads``: the operands are tokens-major, (B, L, heads * d), as the three
# cells' models hand them over: a head is a block of the lanes (two heads of
# 64 to a block of 128), and no transposed copy is made beside the kernel.
@pytest.mark.parametrize("shape,lk,dtype,valid_len,causal,pads,heads", [
    ((96, 256, 64), None, "float32", False, False, False, None),
    ((96, 256, 64), None, "float32", True, False, False, None),
    ((96, 256, 64), None, "bfloat16", False, False, False, None),
    ((96, 256, 64), None, "bfloat16", True, False, False, None),
    ((2, 256, 128), None, "float32", False, True, False, None),
    ((192, 512, 64), None, "float32", True, False, False, None),
    ((192, 512, 64), None, "bfloat16", True, False, False, None),
    ((8, 4096, 128), None, "float32", True, False, False, None),
    ((8, 1, 128), 300, "float32", True, True, True, None),
    # the MLA cell: 20 heads of 256 over 8192 keys, causal, K-major
    ((20, 8192, 256), None, "float32", False, True, False, None),
    # the hybrid cell's attention block: 30 heads of 128 over 2048, causal
    ((30, 2048, 128), None, "float32", False, True, False, None),
    # the three cells' shapes as their models hand them over
    ((16, 512, 768), None, "float32", True, False, False, 12),
    ((16, 512, 768), None, "bfloat16", True, False, False, 12),
    ((1, 8192, 5120), None, "float32", False, True, False, 20),
    ((1, 2048, 3840), None, "float32", False, True, False, 30),
])
def test_flash_attention_compiles_for_v5e(one_chip, shape, lk, dtype,
                                          valid_len, causal, pads, heads):
    import jax
    from mxnet_tpu.kernels import flash_attention

    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((shape[0], lk or shape[1], shape[2]), dtype,
                              sharding=one_chip)
    args = [q, kv, kv]
    if valid_len:
        args.append(jax.ShapeDtypeStruct((shape[0],), "float32",
                                         sharding=one_chip))

    def fwd(q, k, v, vl=None):
        return flash_attention(q, k, v, causal=causal, valid_len=vl,
                               interpret=False, num_heads=heads)

    text = _compiled_text(fwd, *args)
    assert "tpu_custom_call" in text
    assert (" pad(" in text) == pads
    if heads:
        assert " transpose(" not in text and " copy(" not in text


def _build_anew():
    """The gauges are those of the kernels last BUILT: drop the builders'
    caches, so that the compile below builds its own."""
    import importlib
    fa = importlib.import_module("mxnet_tpu.kernels.flash_attention")
    fa._build_call.cache_clear()
    fa._build_backward.cache_clear()


def _kernel_calls(text, name):
    """Mosaic custom calls of the compiled ``text`` whose kernel is named
    ``name``: XLA names the instruction after the ``pallas_call``, with the
    transformations it went through before it (``jvp_<name>_``), which is
    also how a device trace names the operation."""
    return sum(name in line.split(" = ")[0] for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line)


# the backward's two kernels at the shapes the cells meet: the BERT cell
# (K and V resident, lengths, a head at its own 64 lanes) in float32 and
# bfloat16; the MLA cell (20 heads of 256 over 8192 keys, causal: K-major
# and Q-major blocks, the diagonal through them); a ragged causal Lk > Lq
# and a causal Lk < Lq, whose first rows see no key
@pytest.mark.parametrize("shape,lk,dtype,valid_len,causal,heads", [
    ((192, 512, 64), None, "float32", True, False, None),
    ((192, 512, 64), None, "bfloat16", True, False, None),
    ((20, 8192, 256), None, "float32", False, True, None),
    ((20, 8192, 256), None, "bfloat16", False, True, None),
    ((4, 200, 64), 640, "float32", True, True, None),
    ((4, 640, 128), 200, "float32", True, True, None),
    ((30, 2048, 128), None, "float32", False, True, None),
    ((16, 512, 768), None, "float32", True, False, 12),
    ((16, 512, 768), None, "bfloat16", True, False, 12),
    ((1, 8192, 5120), None, "float32", False, True, 20),
    ((1, 2048, 3840), None, "float32", False, True, 30),
], ids=["bert_cell", "bert_cell_bfloat16", "mla_cell_8k",
        "mla_cell_8k_bfloat16", "causal_lk_gt_lq",
        "causal_lk_lt_lq_dead_rows", "hybrid_cell_2k",
        "bert_cell_tokens_major", "bert_cell_tokens_major_bfloat16",
        "mla_cell_8k_tokens_major", "hybrid_cell_2k_tokens_major"])
def test_flash_attention_backward_compiles_for_v5e(
        one_chip, shape, lk, dtype, valid_len, causal, heads):
    """Forward and the backward's kernels compile for the chip and fit it:
    one Mosaic call each for the forward, ``dq`` and ``dkv``, and at the
    MLA cell's shape temporaries far under 2 GiB (a scanned backward would
    stack 10.8 GB of carries there; a blocked one made eight block-major
    float32 copies, 1.3 GB).  ``heads``: tokens-major operands, as the
    cells' models hand them over (heads as blocks of the lanes, two of 64
    to a block): no transposed copy beside the kernels.  What compiles at
    8192 keys is the build of two loop bodies (240 of a head's 272 key
    tiles take the one without a mask); the BERT cell's is the one masked
    body."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.observability.registry import registry

    _build_anew()
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((shape[0], lk or shape[1], shape[2]), dtype,
                              sharding=one_chip)
    args = [q, kv, kv]
    if valid_len:
        args.append(jax.ShapeDtypeStruct((shape[0],), "float32",
                                         sharding=one_chip))

    def loss(q, k, v, vl=None):
        out = flash_attention(q, k, v, causal=causal, valid_len=vl,
                              interpret=False, num_heads=heads)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(*args).compile()
    text = compiled.as_text()
    for name in ("flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        assert _kernel_calls(text, name) == 1, name
    assert _kernel_calls(text, "flash_attention_fwd") <= 1
    plain = [registry().get(f"kernels.flash_attention{kind}.tiles_plain")
             .read() for kind in ("", "_bwd")]
    assert plain == [{8192: 240}.get(shape[1], 0)
                     if causal and not lk else 0] * 2
    if shape[1] == 8192:
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29
    if heads:
        assert " transpose(" not in text and " copy(" not in text


# the window / full attention cell's kernels as its model hands them over:
# one row of 16,384 tokens, 28 query heads of 128 lanes that read 4 key
# heads, causal, a window of 4096 in three blocks of four and none in one
@pytest.mark.parametrize("window,tail,dtype,plain", [
    (4096, "_window", "float32", 392), (None, "", "float32", 992),
    (4096, "_window", "bfloat16", 392)],
    ids=["window_block", "full_block", "window_block_bfloat16"])
def test_grouped_window_kernels_compile_for_v5e(one_chip, window, tail,
                                                dtype, plain):
    """Forward, ``dq`` and ``dkv`` at (28 / 4 heads, 128, 16,384, window
    4096) compile for the chip, one Mosaic call each, the window build's
    under its own names; k and v are read, and dk and dv written, a key head
    wide: the compiled gradient holds no array of a key operand's rows that
    is a query head wide but q's own, the result's and their gradients', no
    transpose and no copy.  All three are the build of two loop bodies:
    ``plain`` of a head's key tiles take the one without a mask."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    from mxnet_tpu.observability.registry import registry

    _build_anew()
    seq, heads, kv_heads, d = 16384, 28, 4, 128
    q = jax.ShapeDtypeStruct((1, seq, heads * d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, kv_heads * d), dtype,
                              sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False,
                              num_heads=heads, num_kv_heads=kv_heads,
                              head_dim=d, window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(q, kv, kv).compile()
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert _kernel_calls(text, name + tail) == 1, name
        assert _kernel_calls(text, name) == 1, name
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert " transpose(" not in text and " copy(" not in text
    for kind in ("", "_bwd"):
        assert registry().get(
            f"kernels.flash_attention{kind}.tiles_plain").read() == plain
    # dk and dv leave the kernel 4 heads wide
    dkv = [line for line in text.splitlines()
           if "flash_attention_bwd_dkv" in line.split(" = ")[0]
           and "tpu_custom_call" in line]
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    assert f"{short}[1,{seq},{kv_heads * d}]" in \
        dkv[0].split(" custom-call(")[0]
    # q, the result, the cotangent and dq: the only arrays 28 heads wide
    assert compiled.memory_analysis().temp_size_in_bytes < \
        3 * seq * heads * d * 4


def test_fused_projection_is_read_in_place_for_v5e(one_chip):
    """Projection, attention, projection at the BERT cell's shape from one
    fused (768, 2304) weight, forward and gradient: the flash kernels read
    q, k and v where the projection left them, as lane blocks of the
    (16, 512, 2304) array (two heads of 64 to a block), and write the
    result and dq, dk, dv tokens-major.  The compiled gradient holds one
    Mosaic call of each of the three names and, of an operand the size of
    an activation ((16, 512, 768) float32) or larger, no transpose, no copy
    and no concatenate as an instruction of its own.  What is left beside
    the kernels is the concatenation of dq, dk, dv into the fused
    projection's gradient (``flash_attention_bwd/concatenate``), which XLA
    folds into the rounding to bfloat16 that the projection's backward
    matmuls ask for anyway: three ``dynamic-update-slice`` fusions that
    write one bfloat16 (16, 512, 2304) array in place."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention

    b, seq, heads, d = 16, 512, 12, 64
    units = heads * d

    def operand(*shape):
        return jax.ShapeDtypeStruct(shape, "float32", sharding=one_chip)

    def loss(x, w_qkv, w_out, vl):
        qkv = x @ w_qkv
        out = flash_attention(qkv, qkv, qkv, valid_len=vl, interpret=False,
                              num_heads=heads, head_dim=d,
                              first_head=(0, heads, 2 * heads))
        return jnp.sum((out @ w_out) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          operand(b, seq, units), operand(units, 3 * units),
                          operand(units, units), operand(b))
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert _kernel_calls(text, name) == 1, name
    activation = b * seq * units
    for line in text.splitlines():
        found = re.search(
            r" = \w+\[([\d,]+)\]\S* (transpose|copy|concatenate)\(", line)
        assert not found or np.prod(
            [int(n) for n in found.group(1).split(",")]) < activation, line
    joined = [line for line in text.splitlines()
              if re.search(r"flash_attention_bwd\)*/concatenate", line)]
    assert joined and all("dynamic-update-slice" in line and
                          f"bf16[{b},{seq},{3 * units}]" in line
                          for line in joined)


def test_gated_delta_rule_compiles_for_v5e_and_fits(one_chip):
    """The chunked delta rule at the hybrid cell's shape (one row of 2048
    tokens, 30 heads of 96 / 192), forward and backward, compiles for the
    chip: two ``while`` loops (the state pass and its backward) and
    temporaries under 1 GiB (0.90 GB when written: some twenty arrays of
    (30, 32, 64, 64..192) float32 that the batched parts keep for their
    backward, among them 71 MB of chunk states)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels.gated_delta_rule import gated_delta_rule

    def operand(*shape):
        return jax.ShapeDtypeStruct(shape, "float32", sharding=one_chip)
    args = [operand(1, 2048, 30, 96), operand(1, 2048, 30, 96),
            operand(1, 2048, 30, 192), operand(1, 2048, 30),
            operand(1, 2048, 30)]
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a)[0]),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    assert compiled.as_text().count(" while(") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("keeps,calls", [(True, 1), (False, 2)],
                         ids=["output_kept", "nothing_kept"])
def test_checkpointed_attention_block_runs_the_kernel_once_for_v5e(
        one_chip, keeps, calls):
    """A projection-attention-projection block at the MLA cell's shape
    under ``jax.checkpoint`` with the policy of a rematerialised block
    (``gluon/block.py:_keep_named``): the compiled gradient holds one
    forward flash kernel, counted by its name, whose output the
    projection's backward reads; under a checkpoint that keeps nothing it
    holds two.  The backward's two kernels are there once either way."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.block import _keep_named
    from mxnet_tpu.kernels import flash_attention

    heads, seq, d, units = 20, 8192, 256, 128
    x = jax.ShapeDtypeStruct((seq, units), "float32", sharding=one_chip)
    w_in = jax.ShapeDtypeStruct((units, 3 * heads * d), "float32",
                                sharding=one_chip)
    w_out = jax.ShapeDtypeStruct((heads * d, units), "float32",
                                 sharding=one_chip)

    def block(x, w_in, w_out):
        qkv = (x @ w_in)[None]
        out = flash_attention(qkv, qkv, qkv, causal=True, interpret=False,
                              num_heads=heads, head_dim=d,
                              first_head=(0, heads, 2 * heads))
        return out[0] @ w_out

    def loss(x, w_in, w_out):
        policy = _keep_named() if keeps else None
        # squared: the cotangent reads the forward's result, so the
        # forward is not dead beside the backward's own
        return jnp.sum(
            jax.checkpoint(block, policy=policy)(x, w_in, w_out) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, w_in, w_out)
    assert _kernel_calls(text, "flash_attention_fwd") == calls
    assert _kernel_calls(text, "flash_attention_bwd_dq") == 1
    assert _kernel_calls(text, "flash_attention_bwd_dkv") == 1
    assert text.count('custom_call_target="tpu_custom_call"') == calls + 2


def _resnet50_shapes():
    """Every trainable tensor of ResNet-50 v1 (161 of them, 25.6M
    elements): the tail of small tensors is what the multi-tensor apply
    exists for."""
    shapes = [(64, 3, 7, 7), (64,), (64,)]
    c_in = 64
    for blocks, mid in ((3, 64), (4, 128), (6, 256), (3, 512)):
        out = 4 * mid
        for b in range(blocks):
            shapes += [(mid, c_in, 1, 1), (mid,), (mid,),
                       (mid, mid, 3, 3), (mid,), (mid,),
                       (out, mid, 1, 1), (out,), (out,)]
            if b == 0:
                shapes += [(out, c_in, 1, 1), (out,), (out,)]
            c_in = out
    return shapes + [(1000, 2048), (1000,)]


@pytest.mark.parametrize("dtype,momentum", [
    ("float32", None), ("float32", 0.9), ("bfloat16", 0.9)])
def test_multi_sgd_compiles_for_v5e(one_chip, dtype, momentum):
    import jax
    from mxnet_tpu.kernels import fused_multi_sgd, fused_multi_sgd_mom

    shapes = _resnet50_shapes()
    assert len(shapes) == 161
    assert sum(int(np.prod(s)) for s in shapes) == 25_557_032
    ws = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s in shapes]
    per_tensor = jax.ShapeDtypeStruct((len(shapes),), "float32",
                                      sharding=one_chip)
    if momentum is None:
        def update(w, g, lrs, wds):
            return fused_multi_sgd(w, g, lrs, wds, rescale_grad=1 / 128,
                                   interpret=False)
        text = _compiled_text(update, ws, ws, per_tensor, per_tensor)
    else:
        def update(w, g, m, lrs, wds):
            return fused_multi_sgd_mom(w, g, m, lrs, wds, momentum=momentum,
                                       rescale_grad=1 / 128, interpret=False)
        text = _compiled_text(update, ws, ws, ws, per_tensor, per_tensor)
    assert "tpu_custom_call" in text


def test_rtc_kernel_compiles_for_v5e(one_chip):
    import jax
    from mxnet_tpu import rtc

    def axpy(x_ref, y_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...] + y_ref[...]

    kernel = rtc.PallasModule().add_kernel("axpy", axpy)
    shape, dtype = (256, 512), np.dtype("float32")
    call = kernel._build([shape, shape], [dtype, dtype], None, None,
                         interpret=False)
    arg = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    assert "tpu_custom_call" in call.lower(arg, arg).compile().as_text()
