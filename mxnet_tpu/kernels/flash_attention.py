"""Flash attention as a Pallas TPU kernel.

Reference context: the reference's attention rides cuDNN/hand-CUDA
softmax(QKᵀ)V with the full (Lq, Lk) score matrix in HBM; the TPU-native
answer is the tiled online-softmax formulation (Flash Attention), which
never materializes the score matrix: each grid step owns one
(block_q, D) query tile in VMEM and sweeps tiles of K/V through the MXU,
carrying the running max/denominator.  HBM traffic drops from
O(Lq·Lk) to O(Lq·D + Lk·D) — exactly the memory-bound regime SURVEY §6
flags for long sequences (ring attention in parallel/ring.py handles the
multi-chip axis; this kernel is the single-chip inner loop).

Tiling (``_tiling``, chosen from the shape and the dtype under one VMEM
budget, never from a constant a user sets): the grid is
(batch·heads, Lq/block_q, Lk/kv_block).  A head's K and V stay resident
in VMEM as one block whenever they fit the budget (kv_block = Lk, the
last grid axis has one step); longer keys ride a K-major, sequential grid
axis of blocks of at most ``_KV_MAJOR`` keys, as many as the budget holds
(1024 of a 256-lane float32 head), with the running max/denominator/
accumulator in VMEM scratch between its steps.  Inside a block the sweep
over (block_k, D) key tiles is a ``lax.fori_loop`` whose carries are the
(block_q, 1) max and denominator and the (block_q, D) accumulator.  The
per-row valid length is scalar-prefetched into SMEM and bounds the
sweep: the loop's trip count is the number of key tiles that hold a valid
key, and a K-major block wholly beyond the length is neither computed
nor fetched (its index map is clamped to the last block that holds one).
Under a causal mask with Lk >= Lq the keys beyond a query tile's last row
bound the sweep in the same way: tiles and blocks wholly above the diagonal
are neither visited nor fetched.  The backward is jnp: the vjp of a
chunked scan where its stacked carries fit ``_BWD_CARRY_BUDGET``, else
``_blocked_backward``, which stacks nothing.
The head keeps its own width (a head of 64 is a block 64 lanes wide);
only ragged Lq/Lk are padded, to the tile.

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

_NEG_INF = -1e30
# the chunk of the scanned backward: a constant of its own, so the
# forward's tiles never change the backward's HLO
_BWD_CHUNK = 128
# what the scanned backward may stack as carries (one running max,
# denominator and accumulator per chunk: Lk / 128 x BH x Lq x (D + 2)
# float32).  A shape over it takes the blocked backward below, which
# stacks nothing: 64 chunks of (20, 8192, 256) would be 10.8 GB.
_BWD_CARRY_BUDGET = 1 << 30
# the blocked backward's query and key block
_BWD_BLOCK = 512
# the forward's tiles (read on a v5e at (192, 512, 64) float32 with the
# benchmark's lengths, PERF.md section 6, PR 29): a query tile of 512 rows
# and a key tile of 256 were the fastest pair; keys that fit one lane
# group keep a tile of 128
_MAX_BLOCK_Q = 512
_MAX_BLOCK_K = 256
# what K and V may hold in VMEM as whole-head blocks, double-buffered by
# the pipeline: a quarter of the 16 MB scoped limit of a v5e core, the
# rest is Q, the output and the sweep's own temporaries
_KV_VMEM_BUDGET = 4 * 1024 * 1024
_KV_MAJOR = 2048
# the ``checkpoint_name`` of the kernel's output: dearer to make again
# than to hold, so a rematerialised block keeps it across its checkpoint
# (``gluon/block.py:_remat_forward``) and the backward does not run the
# kernel a second time.  Outside a checkpoint the name lowers to nothing.
KEPT_OUTPUT = "flash_attention_out"


def _interpret(example=None) -> bool:
    from .multi_sgd import _interpret as _i
    return _i(example)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _tiling(lq: int, lk: int, d: int, itemsize: int):
    """(block_q, padded Lq, block_k, kv_block, padded Lk, padded D) for a
    call's shape.  One query tile when Lq fits ``_MAX_BLOCK_Q`` rows
    (Lq = 1 against a cache is one tile of a sublane group), else tiles of
    ``_MAX_BLOCK_Q``; K and V resident whole (kv_block = padded Lk) when
    they fit ``_KV_VMEM_BUDGET``, else K-major blocks of ``_KV_MAJOR``
    keys."""
    sublanes = 32 // itemsize           # rows of one (sublanes, 128) tile
    # a width the MXU contracts over as it is, or whole lanes
    dp = d if d % 64 == 0 else _round_up(d, 128)
    block_q = min(_round_up(lq, sublanes), _MAX_BLOCK_Q)
    lqp = _round_up(lq, block_q)
    block_k = min(_round_up(lk, 128), _MAX_BLOCK_K)
    lkp = _round_up(lk, block_k)
    # K and V, two buffers each; VMEM rows are whole lanes, so a 64-wide
    # block takes the room of 128
    per_key = 2 * 2 * _round_up(dp, 128) * itemsize
    if lkp * per_key <= _KV_VMEM_BUDGET:
        kv_block = lkp
    else:
        # as many keys as the budget holds, in whole key tiles (a head of
        # 256 float32 lanes holds 1024 keys where one of 128 holds 2048)
        kv_block = min(_KV_MAJOR, max(
            block_k, _KV_VMEM_BUDGET // per_key // block_k * block_k))
        lkp = _round_up(lk, kv_block)
    return block_q, lqp, block_k, kv_block, lkp, dp


@functools.lru_cache(maxsize=None)
def _build_call(bh: int, lq: int, lk: int, d: int, causal: bool,
                scale: float, dtype_name: str, interpret: bool):
    """The kernel for one call's (unpadded) shape; it takes the operands
    padded as ``_tiling`` says.  Each build says which tiling engaged in
    the ``kernels.flash_attention.*`` gauges."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..observability.registry import registry

    dtype = jnp.dtype(dtype_name)
    block_q, lqp, block_k, kv_block, lkp, dp = _tiling(
        lq, lk, d, dtype.itemsize)
    nq, nkv = lqp // block_q, lkp // kv_block
    tiles = kv_block // block_k
    precision = lax.Precision.HIGHEST if dtype == jnp.float32 else None

    reg = registry()
    reg.counter("kernels.flash_attention.builds",
                "flash forward kernels built (one per shape)").inc()
    for name, value in (("block_q", block_q), ("block_k", block_k),
                        ("kv_resident", int(nkv == 1)),
                        ("grid_steps", bh * nq * nkv)):
        reg.gauge(f"kernels.flash_attention.{name}",
                  "tiling of the last flash forward kernel built").set(value)

    # under a causal mask whose every row sees a key (Lk >= Lq) the keys
    # beyond a query tile's last row weigh nothing for the whole tile, so
    # they bound the sweep as the valid length does; with Lk < Lq some rows
    # see no key and take the dead-row rule below, which reads every tile
    diagonal = causal and lk >= lq

    def key_limit(vl, qi):
        """Keys that query tile ``qi`` of a row of length ``vl`` can weigh."""
        if not diagonal:
            return vl
        return jnp.minimum(vl, (qi + 1) * block_q + (lk - lq))

    def sweep(vl, q, k_ref, v_ref, qi, kj, carry):
        """Online softmax of one query tile over the key tiles of K-major
        block ``kj`` that hold a key below ``vl`` (the tile's key limit): a
        key at or beyond it has weight 0 whether the row is live or dead,
        so the tiles beyond it are never visited."""
        k0 = kj * kv_block

        def tile(t, carry):
            m, l, acc = carry
            start = pl.multiple_of(t * block_k, block_k)
            k = k_ref[0, pl.ds(start, block_k), :]
            v = v_ref[0, pl.ds(start, block_k), :]
            # operands stay in the input dtype, the scale is applied to
            # the f32 scores: bf16 products are exact in the MXU's f32
            # accumulator, and f32 operands ask for full precision (the
            # MXU's default would round them to bf16: 5e-3 off at seq 256)
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale   # (BQ, BK)
            # mask K padding (and the causal upper triangle)
            k_idx = k0 + start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            kmask = k_idx < vl
            mask = kmask
            if causal:
                # bottom-right alignment (the flash/decode convention and
                # this repo's reference): query i sits at absolute key
                # position (lk - lq + i), so Lq=1 against a length-N
                # cache attends ALL N keys
                q_idx = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = mask & (k_idx <= q_idx + (lk - lq))
            s = jnp.where(mask, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            if causal:
                # rows whose every key so far is masked (causal bound
                # < 0): the reference softmaxes a uniform -NEG_INF row,
                # i.e. uniform attention over the valid keys — exp(0)=1
                # here would instead spread over PADDED slots, so
                # substitute the valid mask as the weights (masks are
                # prefixes, so a row dead in this tile is dead in every
                # tile).  Without ``causal`` every visited tile holds a
                # valid key and no row is dead.
                dead = m_new <= (_NEG_INF * 0.5)
                p = jnp.where(dead, kmask.astype(jnp.float32), p)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * corr + lax.dot_general(
                p.astype(dtype), v, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        n = jnp.clip(pl.cdiv(vl - k0, block_k), 0, tiles)
        return lax.fori_loop(0, n, tile, carry)

    def start():
        return (jnp.full((block_q, 1), _NEG_INF, jnp.float32),
                jnp.zeros((block_q, 1), jnp.float32),
                jnp.zeros((block_q, dp), jnp.float32))

    def finish(o_ref, l, acc):
        # rows with no valid keys (padded queries) divide by 1 instead
        o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(dtype)

    def kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, *carries):
        b, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        # per-sequence valid key length (padding mask support): the tile
        # padding bound ``lk`` is static; vl tightens it per row
        vl = key_limit(jnp.minimum(vl_ref[b], lk), qi)
        if nkv == 1:
            _, l, acc = sweep(vl, q_ref[0], k_ref, v_ref, qi, kj, start())
            finish(o_ref, l, acc)
            return
        m_ref, l_ref, acc_ref = carries

        @pl.when(kj == 0)
        def _():
            m_ref[...], l_ref[...], acc_ref[...] = start()

        @pl.when(kj * kv_block < vl)
        def _():
            m_ref[...], l_ref[...], acc_ref[...] = sweep(
                vl, q_ref[0], k_ref, v_ref, qi, kj,
                (m_ref[...], l_ref[...], acc_ref[...]))

        @pl.when(kj == nkv - 1)
        def _():
            finish(o_ref, l_ref[...], acc_ref[...])

    def kv_index(b, i, j, vl_ref):
        # a K-major block wholly beyond the row's length maps to the last
        # block that holds a valid key: the pipeline sees the same block
        # index again and issues no DMA for it
        last = jnp.maximum(
            pl.cdiv(key_limit(jnp.minimum(vl_ref[b], lk), i), kv_block) - 1,
            0)
        return (b, jnp.minimum(j, last), 0)

    # Mosaic takes neither a rank-1 block of one element nor rank-1 loop
    # carries: the per-row length rides scalar memory, and the running
    # max/denominator are (block_q, 1) columns.
    q_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j, vl: (b, i, 0))
    kv_spec = pl.BlockSpec((1, kv_block, dp), kv_index)
    scratch = [] if nkv == 1 else [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, dp), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nkv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((bh, lqp, dp), dtype),
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
    either (per-step residuals are O(Lq·D·Lk/_BWD_CHUNK))."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    bh, lq, d = q.shape
    lk = k.shape[1]
    pad = (-lk) % _BWD_CHUNK
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    nk = k.shape[1] // _BWD_CHUNK
    qf = q.astype(jnp.float32) * scale
    kb = k.astype(jnp.float32).reshape(bh, nk, _BWD_CHUNK, d)
    vb = v.astype(jnp.float32).reshape(bh, nk, _BWD_CHUNK, d)
    q_idx = jnp.arange(lq)
    vl_eff = jnp.minimum(vl.astype(jnp.float32), jnp.float32(lk))  # (bh,)

    # remat: without checkpointing, vjp-of-scan stacks each step's p
    # (bh, Lq, _BWD_CHUNK) — a full probability matrix across steps; with
    # it, backward recomputes per-block and stores only the carries
    # (O(Lq·(D+2)·nk))
    @jax.checkpoint
    def step(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, ki = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, k_blk)
        k_ids = ki * _BWD_CHUNK + jnp.arange(_BWD_CHUNK)
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


def _blocked_backward(q, k, v, vl, causal: bool, scale: float, out, g):
    """(dq, dk, dv) of the attention whose output is ``out``, for the
    cotangent ``g``, with nothing stacked: one scan over the (query block,
    key block) pairs that hold a weight finds each row's max and
    denominator, a second forms ``p = exp(s - m) / l`` again pair by pair
    and adds ``dv += p^T g``, ``ds = p (g v^T - rowsum(g out))``,
    ``dq += ds k``, ``dk += ds^T q`` into the gradients it carries.  Every
    array is kept block-major, (blocks, BH, block, D), so that a pair
    reads and writes whole leading slabs.  Under a causal mask with
    Lk >= Lq the pairs above the diagonal are not in the list.  Same masks
    and dead-row rule as the kernel."""
    import jax.numpy as jnp
    from jax import lax

    bh, lq, d = q.shape
    lk = k.shape[1]
    bq = min(_BWD_BLOCK, _round_up(lq, 8))
    bk = min(_BWD_BLOCK, _round_up(lk, 128))
    nq, nk = -(-lq // bq), -(-lk // bk)
    off = lk - lq

    def blocks(x, n, b):
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, n * b - x.shape[1]), (0, 0)))
        return jnp.moveaxis(x.reshape(bh, n, b, d), 1, 0)

    def rows(x, n):
        return jnp.moveaxis(x, 0, 1).reshape(bh, -1, d)[:, :n]
    qf, gf, of = blocks(q, nq, bq) * scale, blocks(g, nq, bq), \
        blocks(out, nq, bq)
    kf, vf = blocks(k, nk, bk), blocks(v, nk, bk)
    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if not (causal and off >= 0) or j * bk <= (i + 1) * bq - 1 + off]
    pairs = (jnp.asarray([p[0] for p in pairs], jnp.int32),
             jnp.asarray([p[1] for p in pairs], jnp.int32))
    vl_eff = jnp.minimum(vl.astype(jnp.float32), jnp.float32(lk))

    def at(x, i):
        return lax.dynamic_index_in_dim(x, i, 0, keepdims=False)

    def put(x, i, blk):
        return lax.dynamic_update_index_in_dim(x, blk, i, 0)

    def scores(i, j):
        s = jnp.einsum("bqd,bkd->bqk", at(qf, i), at(kf, j))
        k_ids = j * bk + jnp.arange(bk)
        kmask = (k_ids[None, :].astype(jnp.float32)
                 < vl_eff[:, None])[:, None, :]
        mask = kmask
        if causal:
            q_ids = i * bq + jnp.arange(bq)
            mask = mask & (k_ids[None, None, :] <=
                           q_ids[None, :, None] + off)
        return jnp.where(mask, s, _NEG_INF), kmask.astype(jnp.float32)

    def stats(carry, ij):
        m, l = carry
        i, j = ij
        s, kmask = scores(i, j)
        mb = at(m, i)
        m_new = jnp.maximum(mb, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where((m_new <= _NEG_INF * 0.5)[..., None], kmask, p)
        l_new = at(l, i) * jnp.exp(mb - m_new) + jnp.sum(p, axis=-1)
        return (put(m, i, m_new), put(l, i, l_new)), None

    (m, l), _ = lax.scan(
        stats, (jnp.full((nq, bh, bq), _NEG_INF, jnp.float32),
                jnp.zeros((nq, bh, bq), jnp.float32)), pairs)
    l = jnp.where(l == 0.0, 1.0, l)
    delta = jnp.sum(gf * of, axis=-1)

    def grads(carry, ij):
        dq, dk, dv = carry
        i, j = ij
        s, kmask = scores(i, j)
        mb, gb = at(m, i)[..., None], at(gf, i)
        dead = mb <= _NEG_INF * 0.5
        p = jnp.where(dead, kmask, jnp.exp(s - mb)) / at(l, i)[..., None]
        dp = jnp.einsum("bqd,bkd->bqk", gb, at(vf, j))
        ds = jnp.where(dead, 0.0, p * (dp - at(delta, i)[..., None]))
        dq = put(dq, i, at(dq, i)
                 + jnp.einsum("bqk,bkd->bqd", ds, at(kf, j)) * scale)
        dk = put(dk, j, at(dk, j)
                 + jnp.einsum("bqk,bqd->bkd", ds, at(qf, i)))
        dv = put(dv, j, at(dv, j) + jnp.einsum("bqk,bqd->bkd", p, gb))
        return (dq, dk, dv), None

    (dq, dk, dv), _ = lax.scan(
        grads, (jnp.zeros_like(qf), jnp.zeros_like(kf), jnp.zeros_like(vf)),
        pairs)
    return rows(dq, lq).astype(q.dtype), rows(dk, lk).astype(k.dtype), \
        rows(dv, lk).astype(v.dtype)


@functools.lru_cache(maxsize=1)
def _flash_core_fn():
    """Module-singleton custom-VJP core (built lazily so importing this
    module never imports jax)."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
    def core(q, k, v, vl, causal, scale, interpret):
        return _run_kernel(q, k, v, vl, causal, scale, interpret)

    def stacks_too_much(q, k):
        bh, lq, d = q.shape
        return -(-k.shape[1] // _BWD_CHUNK) * bh * lq * (d + 2) * 4 \
            > _BWD_CARRY_BUDGET

    def core_fwd(q, k, v, vl, causal, scale, interpret):
        # named here, before it is both the primal and a residual: a name
        # put on by the caller would sit on another variable than the one
        # the backward reads
        out = checkpoint_name(
            _run_kernel(q, k, v, vl, causal, scale, interpret), KEPT_OUTPUT)
        # the blocked backward reads the output; the scanned one makes
        # its own and keeps what it always kept
        return out, (q, k, v, vl, out if stacks_too_much(q, k) else None)

    def core_bwd(causal, scale, interpret, res, g):
        q, k, v, vl, out = res
        import jax.numpy as jnp
        with jax.named_scope("flash_attention_bwd"):
            if out is not None:
                # where a rematerialised block has kept ``out``, nothing
                # in its backward reads q, k, v at their own precision any
                # more (the kernel's call did), and XLA narrows the
                # projections that make them again to the bfloat16 of the
                # matmuls below; a narrowed 84 MB operand of the second
                # scan then fits VMEM, is left there across the loop and is
                # copied out and sliced back in every pair (130 ms of the
                # 8k cell's step, PERF.md section 6, PR 31).  A rounding to
                # the precision they have is an identity that no conversion
                # moves across: they arrive as the kernel took them.
                bits = jnp.finfo(q.dtype)
                q, k, v = (jax.lax.reduce_precision(x, bits.nexp, bits.nmant)
                           for x in (q, k, v))
                dq, dk, dv = _blocked_backward(q, k, v, vl, causal, scale,
                                               out, g)
                return dq, dk, dv, jnp.zeros_like(vl)
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
    _, lqp, _, _, lkp, dp = _tiling(lq, lk, d, jnp.result_type(q).itemsize)

    def pad_to(x, rows):
        # only what the chosen tiles still need: ragged Lq/Lk, and a head
        # whose width the kernel does not take as it is
        if x.shape[1:] == (rows, dp):
            return x
        return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, dp - d)))

    # the kernel's own pad and unpad carry a name of their own: their
    # device time is the kernel's to answer for
    with jax.named_scope("flash_attention_pad"):
        qp, kp, vp = pad_to(q, lqp), pad_to(k, lkp), pad_to(v, lkp)
    call = _build_call(bh, lq, lk, d, bool(causal), float(scale),
                       jnp.result_type(q).name, bool(interpret))
    out = call(vl.astype(jnp.int32), qp, kp, vp)
    if out.shape == q.shape:
        return out
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
