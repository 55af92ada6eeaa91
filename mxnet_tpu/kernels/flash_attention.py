"""Flash attention as a Pallas TPU kernel.

Reference context: the reference's attention rides cuDNN/hand-CUDA
softmax(QKᵀ)V with the full (Lq, Lk) score matrix in HBM; the TPU-native
answer is the tiled online-softmax formulation (Flash Attention), which
never materializes the score matrix: each grid step owns one
(BLOCK_Q, D) query tile in VMEM and streams K/V tiles through the MXU,
carrying the running max/denominator.  HBM traffic drops from
O(Lq·Lk) to O(Lq·D + Lk·D) — exactly the memory-bound regime SURVEY §6
flags for long sequences (ring attention in parallel/ring.py handles the
multi-chip axis; this kernel is the single-chip inner loop).

Grid: (batch·heads, Lq/BLOCK_Q, Lk/BLOCK_K); the K/V sweep is the
innermost, sequential grid axis, so one (BLOCK_K, D) tile of K and of V
is in VMEM at a time and the running max/denominator/accumulator live in
VMEM scratch between its steps.  The per-row valid length is
scalar-prefetched into SMEM.

Numerics: f32 accumulation regardless of input dtype, f32 operands
multiplied at full precision, bf16 operands as they are (the
probabilities are rounded to bf16 for the PV product); causal masking and
right-padding masks derive from 2-D broadcasted_iota (TPU requires ≥2-D
iota).  Interpret mode runs the same kernel on CPU (tests/conftest mesh);
Mosaic compiles it for the chip (tests/test_chip_compile.py compiles
it for a described v5e; chip_smoke.py runs it).
"""
from __future__ import annotations

import functools

BLOCK_Q = 128
BLOCK_K = 128
_NEG_INF = -1e30


def _interpret(example=None) -> bool:
    from .multi_sgd import _interpret as _i
    return _i(example)


@functools.lru_cache(maxsize=None)
def _build_call(bh: int, lq: int, lk: int, d: int, valid_lq: int,
                valid_lk: int, causal: bool, scale: float,
                dtype_name: str, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq = lq // BLOCK_Q
    nk = lk // BLOCK_K
    dtype = jnp.dtype(dtype_name)
    precision = lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _():
            m_ref[...] = jnp.full((BLOCK_Q, 1), _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros((BLOCK_Q, 1), jnp.float32)
            acc_ref[...] = jnp.zeros((BLOCK_Q, d), jnp.float32)

        # per-sequence valid key length (padding mask support): the tile
        # padding bound `valid_lk` is static; vl tightens it per row
        vl = jnp.minimum(vl_ref[b], valid_lk)
        # operands stay in the input dtype, the scale is applied to the
        # f32 scores: bf16 products are exact in the MXU's f32
        # accumulator, and f32 operands ask for full precision (the MXU's
        # default would round them to bf16: 5e-3 off at seq 256)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32) * scale    # (BQ, BK)
        # mask K padding (and the causal upper triangle)
        k_idx = ki * BLOCK_K + lax.broadcasted_iota(
            jnp.int32, (BLOCK_Q, BLOCK_K), 1)
        kmask = k_idx < vl
        mask = kmask
        if causal:
            # bottom-right alignment (the flash/decode convention and
            # this repo's reference): query i sits at absolute key
            # position (valid_lk - valid_lq + i), so Lq=1 against a
            # length-N cache attends ALL N keys
            q_idx = qi * BLOCK_Q + lax.broadcasted_iota(
                jnp.int32, (BLOCK_Q, BLOCK_K), 0)
            mask = mask & (k_idx <= q_idx + (valid_lk - valid_lq))
        s = jnp.where(mask, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # rows whose every key is masked (causal bound < 0): the
        # reference softmaxes a uniform -NEG_INF row, i.e. uniform
        # attention over the valid keys — exp(0)=1 here would
        # instead spread over PADDED slots, so substitute the valid
        # mask as the weights (masks are prefixes, so a row dead in
        # this block is dead in every block)
        dead = m_new <= (_NEG_INF * 0.5)
        p = jnp.where(dead, kmask.astype(jnp.float32), p)
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(dtype), v_ref[0], (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)

        @pl.when(ki == nk - 1)
        def _():
            # rows with no valid keys (padded queries) divide by 1 instead
            l = l_ref[...]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(dtype)

    # Mosaic takes neither a rank-1 block of one element nor rank-1 loop
    # carries: the per-row length rides scalar memory, and the running
    # max/denominator are (BLOCK_Q, 1) columns in VMEM scratch.  K/V are a
    # grid axis (innermost, sequential), so one (BLOCK_K, D) tile of each
    # is resident at a time whatever Lk is.
    q_spec = pl.BlockSpec((1, BLOCK_Q, d), lambda b, i, j, vl: (b, i, 0))
    kv_spec = pl.BlockSpec((1, BLOCK_K, d), lambda b, i, j, vl: (b, j, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((BLOCK_Q, 1), jnp.float32),
                            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),
                            pltpu.VMEM((BLOCK_Q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )


def _chunked_reference(q, k, v, vl, causal: bool, scale: float):
    """Pure-jnp online-softmax attention, chunked over KV blocks with
    lax.scan — numerically identical to the kernel (same masks, same
    dead-row semantics) and DIFFERENTIABLE.  The custom VJP below runs
    the Pallas kernel forward and differentiates THIS formulation
    backward, so training never materializes the (Lq, Lk) score matrix
    either (per-step residuals are O(Lq·D·Lk/BLOCK_K))."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    bh, lq, d = q.shape
    lk = k.shape[1]
    pad = (-lk) % BLOCK_K
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    nk = k.shape[1] // BLOCK_K
    qf = q.astype(jnp.float32) * scale
    kb = k.astype(jnp.float32).reshape(bh, nk, BLOCK_K, d)
    vb = v.astype(jnp.float32).reshape(bh, nk, BLOCK_K, d)
    q_idx = jnp.arange(lq)
    vl_eff = jnp.minimum(vl.astype(jnp.float32), jnp.float32(lk))  # (bh,)

    # remat: without checkpointing, vjp-of-scan stacks each step's p
    # (bh, Lq, BLOCK_K) — a full probability matrix across steps; with it,
    # backward recomputes per-block and stores only the carries
    # (O(Lq·(D+2)·nk))
    @jax.checkpoint
    def step(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, ki = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, k_blk)
        k_ids = ki * BLOCK_K + jnp.arange(BLOCK_K)
        kmask = (k_ids[None, :].astype(jnp.float32)
                 < vl_eff[:, None])[:, None, :]        # (bh, 1, BK)
        mask = kmask
        if causal:
            mask = mask & (k_ids[None, None, :] <=
                           q_idx[None, :, None] + (lk - lq))
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        dead = m_new <= (_NEG_INF * 0.5)
        p = jnp.where(dead[..., None],
                      jnp.broadcast_to(kmask.astype(jnp.float32),
                                       p.shape), p)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqk,bkd->bqd", p, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((bh, lq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, lq), jnp.float32)
    a0 = jnp.zeros((bh, lq, d), jnp.float32)
    blks = (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
            jnp.arange(nk))
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), blks)
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


@functools.lru_cache(maxsize=1)
def _flash_core_fn():
    """Module-singleton custom-VJP core (built lazily so importing this
    module never imports jax)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
    def core(q, k, v, vl, causal, scale, interpret):
        return _run_kernel(q, k, v, vl, causal, scale, interpret)

    def core_fwd(q, k, v, vl, causal, scale, interpret):
        return _run_kernel(q, k, v, vl, causal, scale, interpret), \
            (q, k, v, vl)

    def core_bwd(causal, scale, interpret, res, g):
        q, k, v, vl = res
        import jax.numpy as jnp
        with jax.named_scope("flash_attention_bwd"):
            _, vjp = jax.vjp(
                lambda a, b, c: _chunked_reference(a, b, c, vl, causal,
                                                   scale),
                q, k, v)
            dq, dk, dv = vjp(g)
            # vl is a mask, not a weight
            return dq, dk, dv, jnp.zeros_like(vl)
    core.defvjp(core_fwd, core_bwd)
    return core


def _flash_core(q, k, v, vl, causal: bool, scale: float, interpret: bool):
    return _flash_core_fn()(q, k, v, vl, causal, scale, interpret)


def _run_kernel(q, k, v, vl, causal: bool, scale: float, interpret: bool):
    import jax
    import jax.numpy as jnp

    bh, lq, d = q.shape
    lk = k.shape[1]

    def pad_to(x, axis, mult):
        n = x.shape[axis]
        pad = (-n) % mult
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    # the kernel's own pad and unpad (a head of 64 goes to 128 lanes) carry
    # a name of their own: their device time is the kernel's to answer for
    with jax.named_scope("flash_attention_pad"):
        qp = pad_to(pad_to(q, 1, BLOCK_Q), 2, 128)
        kp = pad_to(pad_to(k, 1, BLOCK_K), 2, 128)
        vp = pad_to(pad_to(v, 1, BLOCK_K), 2, 128)
    call = _build_call(bh, qp.shape[1], kp.shape[1], qp.shape[2], lq, lk,
                       bool(causal), float(scale),
                       jnp.result_type(q).name, bool(interpret))
    out = call(vl.astype(jnp.int32), qp, kp, vp)
    with jax.named_scope("flash_attention_pad"):
        return out[:, :lq, :d]


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    interpret=None, valid_len=None):
    """Tiled attention: softmax(scale·QKᵀ + mask)V without materializing
    the score matrix.

    Accepts (B, H, L, D) or (BH, L, D); Lq/Lk/D are padded internally to
    tile multiples (K padding is masked exactly, never approximated).
    ``valid_len`` enables per-sequence key-padding masks — shape (B,) or
    (B*H,); keys at positions >= valid_len[i] are masked exactly like the
    additive -1e9 padding mask of the XLA path.
    DIFFERENTIABLE: the forward runs the Pallas kernel, the backward
    differentiates an equivalent chunked jnp formulation — gradients also
    never touch an (Lq, Lk) score matrix.
    """
    import jax.numpy as jnp

    squeeze4 = q.ndim == 4
    if squeeze4:
        b, h, lq, dd = q.shape
        q = q.reshape(b * h, lq, dd)
        k = k.reshape(b * h, k.shape[2], dd)
        v = v.reshape(b * h, v.shape[2], dd)
    bh, lq, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _interpret(q)
    if valid_len is None:
        vl = jnp.full((bh,), lk, jnp.float32)
    else:
        vl = jnp.asarray(valid_len).reshape(-1).astype(jnp.float32)
        if vl.shape[0] != bh:
            if bh % vl.shape[0]:
                raise ValueError(
                    f"valid_len length {vl.shape[0]} does not divide "
                    f"batch*heads {bh}")
            vl = jnp.repeat(vl, bh // vl.shape[0])

    out = _flash_core(q, k, v, vl, bool(causal), float(scale),
                      bool(interpret))
    if squeeze4:
        out = out.reshape(b, h, lq, d)
    return out
