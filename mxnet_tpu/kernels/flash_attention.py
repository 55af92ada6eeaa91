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
are neither visited nor fetched.
The head keeps its own width (a head of 64 is a block 64 lanes wide);
only ragged Lq/Lk are padded, to the tile.

The backward is two Pallas kernels (``_build_backward``; tiles from
``_bwd_tiling``, by the same rules and the same budget): ``dq``, which
also makes the rows' statistics from the scores it computes itself, and
``dkv``.  A block of scores, probabilities and ds lives and dies in VMEM;
the tiles the forward skips are skipped; nothing of the forward but q, k
and v is a residual.

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
    kv_block, lkp = _major(lk, block_k, dp, itemsize)
    return block_q, lqp, block_k, kv_block, lkp, dp


def _major(rows: int, tile: int, dp: int, itemsize: int):
    """(block, padded rows) of an operand pair that a kernel sweeps (K and
    V; in the backward's ``dkv`` Q and the cotangent): resident whole when
    the pair fits ``_KV_VMEM_BUDGET``, else in blocks of as many rows as
    the budget holds, in whole tiles (a head of 256 float32 lanes holds
    1024 where one of 128 holds 2048)."""
    # two arrays, two buffers each; VMEM rows are whole lanes, so a 64-wide
    # block takes the room of 128
    per_row = 2 * 2 * _round_up(dp, 128) * itemsize
    padded = _round_up(rows, tile)
    if padded * per_row <= _KV_VMEM_BUDGET:
        return padded, padded
    block = min(_KV_MAJOR,
                max(tile, _KV_VMEM_BUDGET // per_row // tile * tile))
    return block, _round_up(rows, block)


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


def _bwd_tiling(lq: int, lk: int, d: int, itemsize: int):
    """(block_q, q_block, padded Lq, block_k, key_block, kv_block, padded
    Lk, padded D) of the backward's two kernels, from the shape alone.  A
    score block is (block_k, block_q) in both.  ``dq`` holds one query tile
    of ``block_q`` rows and sweeps K and V, resident whole (kv_block =
    padded Lk) when they fit ``_KV_VMEM_BUDGET``, else in K-major blocks of
    ``kv_block`` keys; ``dkv`` holds ``key_block`` keys (as many key tiles
    as a query tile has rows) and sweeps Q and the cotangent the same way,
    in blocks of ``q_block`` rows.  Queries ride the lanes of a score
    block, so a query tile is whole lanes.  The forward's pair of tiles
    (512, 256) read fastest here too (PERF.md section 6, PR 33)."""
    dp = d if d % 64 == 0 else _round_up(d, 128)
    block_q = min(_round_up(lq, 128), _MAX_BLOCK_Q)
    block_k = min(_round_up(lk, 128), _MAX_BLOCK_K)
    key_block = min(_round_up(lk, block_k), max(_MAX_BLOCK_Q, block_k))
    q_block, lqp = _major(lq, block_q, dp, itemsize)
    kv_block, lkp = _major(lk, key_block, dp, itemsize)
    return block_q, q_block, lqp, block_k, key_block, kv_block, lkp, dp


@functools.lru_cache(maxsize=None)
def _build_backward(bh: int, lq: int, lk: int, d: int, causal: bool,
                    scale: float, dtype_name: str, interpret: bool):
    """The backward's two kernels for one call's (unpadded) shape; they
    take the operands padded as ``_bwd_tiling`` says.  Both compute their
    (block_k, block_q) score blocks with the keys down the sublanes and
    the queries along the lanes: a row's statistics are then lane-dense
    (1, block_q) rows that broadcast and reduce down the sublanes (the
    other way round they are columns, and every use of one is a pass
    through the cross-lane unit: 1.58 against 0.99 ms a call at the BERT
    cell's shape, PERF.md section 6, PR 33), and ``p^T g``, ``ds^T q`` are
    plain products.  A block of scores, probabilities and ds lives and
    dies in VMEM.

    ``dq`` (grid: batch·heads, query tiles, K-major blocks) sweeps the keys
    of a query tile once and makes, online and from the scores it computes
    itself, the row's maximum ``m``, denominator ``l``,
    ``delta = sum_j p_ij dp_ij / l`` and, because dq is linear in delta,
    dq itself: with ``a = sum_j p dp k`` and ``b = sum_j p k`` of the
    unnormalised p (rescaled like ``l`` as the maximum moves),
    ``dq = scale (a - delta b) / l``.  A row of
    ``ds = p (dp - delta)`` so sums to nought by construction, whatever
    rounding the scores took (a ``delta`` taken from the forward's
    full-precision output does not: the key bias drifts, PERF.md section
    6, PR 30), and the backward needs no statistic and no output of the
    forward.  It writes ``m + log l``, delta and ``1 / l``, a row each of
    an (8, block_q) block a query tile, for ``dkv`` (grid: batch·heads, key
    blocks, Q-major blocks), which makes the same scores again bit for
    bit, ``p = exp(s - m - log l)``, ``ds``, and adds ``p^T g`` to dv and
    ``ds^T q`` to dk.

    Key tiles at or beyond a row's valid length, and under a causal mask
    with Lk >= Lq the tiles wholly above the diagonal, are neither visited
    nor fetched, as in the forward; a key tile wholly beyond the length
    gets zeros.  Operands reach the MXU as XLA's default precision gives
    them to it (float32 rounded to bfloat16 once, bfloat16 as it is);
    products, statistics and accumulators are float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..observability.registry import registry

    dtype = jnp.dtype(dtype_name)
    block_q, q_block, lqp, block_k, key_block, kv_block, lkp, dp = \
        _bwd_tiling(lq, lk, d, dtype.itemsize)
    nq, nkv = lqp // block_q, lkp // kv_block
    nkb, nqb = lkp // key_block, lqp // q_block
    mxu = jnp.bfloat16 if dtype == jnp.float32 else dtype
    off = lk - lq
    diagonal = causal and off >= 0          # as the forward's

    reg = registry()
    reg.counter("kernels.flash_attention_bwd.builds",
                "flash backward kernel pairs built (one per shape)").inc()
    for name, value in (("block_q", block_q), ("block_k", block_k),
                        ("grid_steps", bh * (nq * nkv + nkb * nqb))):
        reg.gauge(f"kernels.flash_attention_bwd.{name}",
                  "tiling of the last flash backward kernels built"
                  ).set(value)

    def nt(a, b):
        return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def nn(a, b):
        return lax.dot_general(a.astype(mxu), b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def tn(a, b):
        return lax.dot_general(a, b.astype(mxu), (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def scaled(x):
        # the scale rides q into the MXU, as the einsums of a jnp backward
        # give it: both kernels then make the same scores bit for bit
        return (x.astype(jnp.float32) * scale).astype(mxu)

    # a row that sees no key among the tiles visited: only a causal mask
    # with Lk < Lq makes one (with Lk >= Lq every row sees key 0, and a
    # row of length 0 visits nothing).  It weighs its valid keys evenly
    # and passes nothing to q and k, as in the forward.
    dead_rows = causal and not diagonal

    def positions():
        """Of a score block (keys down the sublanes, queries along the
        lanes, in both kernels: a row's statistics are then lane-dense
        rows that broadcast and reduce down the sublanes): each entry's
        key, and key less query, both counted from the block's corner."""
        key = lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
        return key, (key - lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1) if causal else None)

    def mask(s, key, rel, length, q0, k0):
        """Scores masked as the forward masks them, and the valid keys."""
        kmask = key < length - k0
        keep = kmask & (rel <= q0 + off - k0) if causal else kmask
        return jnp.where(keep, s, _NEG_INF), kmask

    def weights(s, kmask, lse, linv, dp_, delta):
        """(p, ds) of a block from its rows' statistics."""
        p = jnp.exp(s - lse)
        if dead_rows:
            dead = lse <= _NEG_INF * 0.5
            p = jnp.where(dead, kmask.astype(jnp.float32) * linv, p)
        ds = p * (dp_ - delta)
        if dead_rows:
            ds = jnp.where(dead, 0.0, ds)
        return p, ds

    def key_limit(length, qi):
        if not diagonal:
            return length
        return jnp.minimum(length, (qi + 1) * block_q + off)

    # -- dq, and the statistics -------------------------------------------
    def dq_kernel(vl_ref, q_ref, g_ref, k_ref, v_ref, dq_ref, st_ref,
                  m_ref, l_ref, n_ref, c_ref, a_ref, b_ref):
        b, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        k0 = kj * kv_block
        length = jnp.minimum(vl_ref[b], lk)
        # of this K-major block: the tiles that hold a key the query tile
        # can weigh
        tiles = jnp.clip(pl.cdiv(key_limit(length, qi) - k0, block_k), 0,
                         kv_block // block_k)
        q, g = scaled(q_ref[0]), g_ref[0].astype(mxu)
        key, rel = positions()

        @pl.when(kj == 0)
        def _():
            m_ref[...] = jnp.full((1, block_q), _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros((1, block_q), jnp.float32)
            n_ref[...] = jnp.zeros((1, block_q), jnp.float32)
            a_ref[...] = jnp.zeros((dp, block_q), jnp.float32)
            b_ref[...] = jnp.zeros((dp, block_q), jnp.float32)
            # dp is counted from the row's dp at key 0, the one key every
            # live row sees: dq below is a difference of two sums, and
            # where a row's weight sits on few keys (its only one; a
            # first key that draws most of it) both are then small
            c_ref[...] = nt(v_ref[0, :8, :].astype(mxu), g)[:1]

        c = c_ref[...]

        def tile(t, carry):
            m, l, n, a, b_ = carry
            start = pl.multiple_of(t * block_k, block_k)
            k = k_ref[0, pl.ds(start, block_k), :].astype(mxu)
            v = v_ref[0, pl.ds(start, block_k), :].astype(mxu)
            s, kmask = mask(nt(k, q), key, rel, length, qi * block_q,
                            k0 + start)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            if dead_rows:
                p = jnp.where(m_new <= _NEG_INF * 0.5,
                              kmask.astype(jnp.float32), p)
            corr = jnp.exp(m - m_new)
            pdp = p * (nt(v, g) - c)
            return (m_new, l * corr + jnp.sum(p, axis=0, keepdims=True),
                    n * corr + jnp.sum(pdp, axis=0, keepdims=True),
                    a * corr + tn(k, pdp), b_ * corr + tn(k, p))

        refs = (m_ref, l_ref, n_ref, a_ref, b_ref)
        for r, x in zip(refs, lax.fori_loop(
                0, tiles, tile, tuple(r[...] for r in refs))):
            r[...] = x

        @pl.when(kj == nkv - 1)
        def _():
            # dq is linear in delta: with a = sum_j p dp k and b = sum_j p k
            # of the unnormalised p, dq = scale (a - delta b) / l, whatever
            # dp is counted from.  The statistics ``dkv`` needs, a row
            # each: m + log l (so p = exp(s - it)), delta, and for a dead
            # row 1 / l, its valid keys' even weight; a row with no valid
            # key divides by 1
            m, l = m_ref[...], l_ref[...]
            l = jnp.where(l == 0.0, 1.0, l)
            delta = n_ref[...] / l
            dq = (a_ref[...] - delta * b_ref[...]) * (scale / l)
            if dead_rows:
                dq = jnp.where(m <= _NEG_INF * 0.5, 0.0, dq)
            dq_ref[0] = dq.T.astype(dtype)
            lse = jnp.where(m <= _NEG_INF * 0.5, _NEG_INF, m + jnp.log(l))
            row = lax.broadcasted_iota(jnp.int32, (8, block_q), 0)
            st_ref[0, 0] = jnp.where(
                row == 0, lse, jnp.where(row == 1, delta + c, 1.0 / l))

    def kv_index(b, i, j, vl_ref):
        # the sweep stops at the last K-major block that holds a key the
        # tile can weigh; a block beyond it maps to that one: no DMA
        last = jnp.maximum(
            pl.cdiv(key_limit(jnp.minimum(vl_ref[b], lk), i), kv_block) - 1,
            0)
        return (b, jnp.minimum(j, last), 0)

    q_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j, vl: (b, i, 0))
    kv_spec = pl.BlockSpec((1, kv_block, dp), kv_index)
    stats = jax.ShapeDtypeStruct((bh, nq, 8, block_q), jnp.float32)
    dq_call = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nkv),
            in_specs=[q_spec, q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, pl.BlockSpec(
                (1, 1, 8, block_q), lambda b, i, j, vl: (b, i, 0, 0))],
            scratch_shapes=[pltpu.VMEM((1, block_q), jnp.float32)] * 4
            + [pltpu.VMEM((dp, block_q), jnp.float32)] * 2),
        out_shape=[jax.ShapeDtypeStruct((bh, lqp, dp), dtype), stats],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )

    # -- dk and dv --------------------------------------------------------
    q_tiles = q_block // block_q

    def dkv_kernel(vl_ref, k_ref, v_ref, q_ref, g_ref, st_ref, dk_ref,
                   dv_ref, dk_acc, dv_acc):
        b, kb, qb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        length = jnp.minimum(vl_ref[b], lk)
        k0, q0 = kb * key_block, qb * q_block
        key, rel = positions()

        @pl.when(qb == 0)
        def _():
            dk_acc[...] = jnp.zeros((key_block, dp), jnp.float32)
            dv_acc[...] = jnp.zeros((key_block, dp), jnp.float32)

        def key_tile(t, _):
            start = pl.multiple_of(t * block_k, block_k)
            rows = pl.ds(start, block_k)
            k = k_ref[0, rows, :].astype(mxu)
            v = v_ref[0, rows, :].astype(mxu)
            kt = k0 + start

            def query_tile(u, carry):
                dk, dv = carry
                at = pl.ds(pl.multiple_of(u * block_q, block_q), block_q)
                q, g = scaled(q_ref[0, at, :]), g_ref[0, at, :].astype(mxu)
                st = st_ref[0, u]
                s, kmask = mask(nt(k, q), key, rel, length,
                                q0 + u * block_q, kt)
                p, ds = weights(s, kmask, st[0:1], st[2:3], nt(v, g),
                                st[1:2])
                return dk + nn(ds, q), dv + nn(p, g)

            # under the diagonal, the first query tile of this Q-major
            # block whose last row sees the tile's first key
            first = (jnp.clip(kt - off - q0, 0, q_block) // block_q
                     if diagonal else 0)
            dk, dv = lax.fori_loop(
                first, q_tiles, query_tile,
                (jnp.zeros((block_k, dp), jnp.float32),) * 2)
            dk_acc[rows, :] += dk
            dv_acc[rows, :] += dv
            return 0

        # key tiles that hold a valid key; the others keep their zeros
        lax.fori_loop(
            0, jnp.clip(pl.cdiv(length - k0, block_k), 0,
                        key_block // block_k),
            key_tile, 0)

        @pl.when(qb == nqb - 1)
        def _():
            dk_ref[0] = dk_acc[...].astype(dtype)
            dv_ref[0] = dv_acc[...].astype(dtype)

    def q_index(b, kb, qb, vl_ref):
        # Q-major blocks wholly above the diagonal map to the first that
        # is not, and every block of a key block beyond the length to the
        # last: the pipeline sees the block it holds and issues no DMA
        first = (jnp.clip(kb * key_block - off, 0, lqp - 1) // q_block
                 if diagonal else 0)
        beyond = kb * key_block >= jnp.minimum(vl_ref[b], lk)
        return jnp.where(beyond, nqb - 1, jnp.maximum(qb, first))

    kb_spec = pl.BlockSpec((1, key_block, dp),
                           lambda b, kb, qb, vl: (b, kb, 0))
    qb_spec = pl.BlockSpec(
        (1, q_block, dp),
        lambda b, kb, qb, vl: (b, q_index(b, kb, qb, vl), 0))
    dkv_call = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nkb, nqb),
            in_specs=[kb_spec, kb_spec, qb_spec, qb_spec, pl.BlockSpec(
                (1, q_tiles, 8, block_q),
                lambda b, kb, qb, vl: (b, q_index(b, kb, qb, vl), 0, 0))],
            out_specs=[kb_spec, kb_spec],
            scratch_shapes=[pltpu.VMEM((key_block, dp), jnp.float32)] * 2),
        out_shape=[jax.ShapeDtypeStruct((bh, lkp, dp), dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )
    return dq_call, dkv_call


def _run_backward(q, k, v, vl, g, causal: bool, scale: float,
                  interpret: bool):
    """(dq, dk, dv) of the attention the forward kernel computes, for the
    cotangent ``g``, by the two kernels of ``_build_backward``."""
    import jax
    import jax.numpy as jnp

    bh, lq, d = q.shape
    lk = k.shape[1]
    _, _, lqp, _, _, _, lkp, dp = _bwd_tiling(
        lq, lk, d, jnp.result_type(q).itemsize)

    with jax.named_scope("flash_attention_pad"):
        qp, gp, kp, vp = (_pad_to(x, rows, dp) for x, rows in (
            (q, lqp), (g.astype(q.dtype), lqp), (k, lkp), (v, lkp)))
    dq_call, dkv_call = _build_backward(
        bh, lq, lk, d, bool(causal), float(scale), jnp.result_type(q).name,
        bool(interpret))
    lens = vl.astype(jnp.int32)
    dq, stats = dq_call(lens, qp, gp, kp, vp)
    dk, dv = dkv_call(lens, kp, vp, qp, gp, stats)
    if dq.shape == q.shape and dk.shape == k.shape:
        return dq, dk, dv
    with jax.named_scope("flash_attention_pad"):
        return dq[:, :lq, :d], dk[:, :lk, :d], dv[:, :lk, :d]


@functools.lru_cache(maxsize=1)
def _flash_core_fn():
    """Module-singleton custom-VJP core (built lazily so importing this
    module never imports jax)."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
    def core(q, k, v, vl, causal, scale, interpret):
        return _run_kernel(q, k, v, vl, causal, scale, interpret)

    def core_fwd(q, k, v, vl, causal, scale, interpret):
        # named here, before it is the primal: a name put on by the caller
        # would sit on another variable than the one a checkpoint's
        # policy is asked about.  The backward reads neither the output
        # nor any statistic of the forward: its kernels make their own.
        out = checkpoint_name(
            _run_kernel(q, k, v, vl, causal, scale, interpret), KEPT_OUTPUT)
        return out, (q, k, v, vl)

    def core_bwd(causal, scale, interpret, res, g):
        q, k, v, vl = res
        import jax.numpy as jnp
        with jax.named_scope("flash_attention_bwd"):
            dq, dk, dv = _run_backward(q, k, v, vl, g, causal, scale,
                                       interpret)
        # vl is a mask, not a weight
        return dq, dk, dv, jnp.zeros_like(vl)
    core.defvjp(core_fwd, core_bwd)
    return core


def _flash_core(q, k, v, vl, causal: bool, scale: float, interpret: bool):
    return _flash_core_fn()(q, k, v, vl, causal, scale, interpret)


def _pad_to(x, rows: int, dp: int):
    """``x`` padded to (rows, dp): only what the chosen tiles still need, a
    ragged Lq/Lk and a head whose width a kernel does not take as it is."""
    import jax.numpy as jnp
    if x.shape[1:] == (rows, dp):
        return x
    return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]),
                       (0, dp - x.shape[2])))


def _run_kernel(q, k, v, vl, causal: bool, scale: float, interpret: bool):
    import jax
    import jax.numpy as jnp

    bh, lq, d = q.shape
    lk = k.shape[1]
    _, lqp, _, _, lkp, dp = _tiling(lq, lk, d, jnp.result_type(q).itemsize)

    # the kernel's own pad and unpad carry a name of their own: their
    # device time is the kernel's to answer for
    with jax.named_scope("flash_attention_pad"):
        qp, kp, vp = (_pad_to(x, rows, dp)
                      for x, rows in ((q, lqp), (k, lkp), (v, lkp)))
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
    DIFFERENTIABLE: the forward runs the Pallas kernel, the backward two
    more (``dq`` and ``dkv``), at the precision XLA's default gives a
    matmul on the chip (float32 operands rounded to bfloat16 once,
    float32 accumulation) — gradients also never touch an (Lq, Lk) score
    matrix, and key tiles beyond a row's ``valid_len`` cost nothing in
    either direction.
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
