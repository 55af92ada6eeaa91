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

Layouts (``flash_attention``; which form engages follows from the
operands' shape and the head count alone, never from a switch a user
sets).  *Tokens-major*, with the head count given: q is (B, Lq, ..) and
k, v are (B, Lk, ..) exactly as the projections leave them, head beside
head in the lanes, and the result, dq, dk and dv are (B, L, H·d).  **The
head is a lane block of the array, chosen by the index map, not an axis
made by a transpose**: a head of whole lane groups (d % 128 == 0) is the
block ``(1, rows, d)`` at ``(b, i, h)``; two heads of 64 share the
128-lane block ``(1, rows, 128)`` (Mosaic takes no 64-lane block out of a
wider array) and one grid step does both, each with the other's lanes of
the operand it holds still set to nought, so a contraction over all 128
lanes is one head's (the other's add exact zeros, and 64 deep fills half
of the 128-deep MXU either way) and of every product that comes out 128
wide each head keeps its own half.  HBM rows are then whole 128-lane rows.
Each operand also says at which head of its array it starts, so a fused
projection (one (B, L, 3·H·d) array) is read where it lies; its gradient
comes back as one concatenation of dq, dk, dv.  *Heads-first*, the case
H = 1: (B·H, L, d) (or (B, H, L, D)) is a tokens-major array of batch B·H
whose one head is the whole last axis, at its own width (a head of 64 is a
block 64 lanes wide); ``valid_len`` is then a length a row of B·H.  A head
count or width that lane blocks do not serve (an odd count of 64-lane
heads, d = 32, 96, ...) is split off by a transpose inside the entry and
takes the heads-first form.  Gauges ``kernels.flash_attention.lane_heads``
(heads a block holds), ``.tokens_major`` (1: H > 1 heads read as lane
blocks) and ``kernels.flash_attention_bwd.lane_heads`` say which engaged.
Which form a caller should hand over is its own to know: tokens-major
spares the copies only where the operands already lie (B, L, H·d) in
memory (BERT's fused projection, a plain q/k/v projection), and a lane
block is a strided DMA, rows of d lanes at a stride of the array's width:
the MLA block, which makes q, k and v a head at a time and whose K-major
blocks are fetched many times over, reads faster heads-first (PERF.md
section 6, PR 35).

Tiling (``_tiling``, chosen from the shape and the dtype under one VMEM
budget, never from a constant a user sets): the grid is
(batch, lane blocks, Lq/block_q, Lk/kv_block).  A lane block's K and V
stay resident in VMEM whole whenever they fit the budget (kv_block = Lk,
the last grid axis has one step); longer keys ride a K-major, sequential grid
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
Only a ragged Lq/Lk is padded, to the tile, and a lone head to a width
the MXU contracts over.

The sweep's two bounds.  ``window`` (keys; it goes with the causal mask and
follows from the model's layer, never from a switch a user sets): key j
weighs on the query at key position i where ``j <= i`` and ``i - j <
window``.  The sweep of query tile ``qi`` then also has a bound from BELOW:
it starts at the key tile that holds the first key its first row reaches
(``key_start``) and ends at ``key_limit``; a K-major block wholly behind the
window is neither computed nor fetched (its index map is clamped from below
as ``last_block`` clamps it from above); the masks inside the boundary
tiles are exact.  ``dq`` skips what the forward skips; in ``dkv`` a key
tile's query sweep ends at the last query tile that holds a query less than
``window`` beyond the tile's last key, and Q-major blocks beyond it are not
fetched.  Kernels built with a window carry the names
``flash_attention_fwd_window``, ``flash_attention_bwd_dq_window`` and
``flash_attention_bwd_dkv_window`` (a device trace then tells a window
layer's calls from a full layer's, and whoever reads ``flash_attention_fwd``
/ ``_bwd`` counts both); one built without keeps today's names and code.

Masks only where a mask can change a tile.  A (query tile, key tile) pair
whose every key is below the row's length, at or under every row's diagonal
and inside every row's window (``_plain``: three scalar comparisons of what
a kernel already holds) needs no iota, no compare, no ``where`` and no
dead-row rule, and each of those is the identity there, so all three kernels
run such a tile through a body without them and every other tile through the
masked body, bit for bit the results of masking every tile.  The two bodies
are one tile function (``masked``) under a ``lax.cond`` inside the sweep's
one loop (``_sweep_tiles``), and a sweep with two bodies keeps its state (the
running max, denominator and accumulators) in VMEM scratch that the bodies
update in place: as values of a loop or a ``cond`` such accumulators, which
fill the registers several times over, are copied wherever control flow
joins, and that cost more than the masks (PERF.md section 6, PR 39).  The
build engages this from the shape alone (``_sweeps``): a causal build of at
least a lane group of queries whose longest sweep holds ``_SPLIT_TILES`` key
tiles or more; every other build (no causal mask, one query against a cache,
a row of two or three key tiles) is the one masked body with its state in
the loop's carry, as it always was.  Gauges ``kernels.flash_attention.tiles_visited``
and ``.tiles_plain`` (of one head's sweeps, all rows full: the key tiles
visited and those that take the body without a mask; 0 where the build has
one body), and the same two under ``kernels.flash_attention_bwd.``.

The grouped index map.  With ``num_kv_heads`` < ``num_heads`` (tokens-major,
heads of whole lane groups: another width is refused) the q, output and dq
blocks are at head ``h`` and the k and v blocks at head ``first + h //
group``: the forward and ``dq`` read a group's key head in place, once a
query head, and ``dkv`` adds the group's query heads up inside the kernel
(a sequential grid axis over the group before the Q-major one, accumulating
in the one scratch), so dk and dv leave it a key head wide and no copy a
query head wide of k, v, dk or dv exists.  Gauges ``kernels.flash_attention.window`` (keys; 0
without) and ``.kv_group`` (query heads a key head serves) for the kernels
last built, ``.key_tiles`` and ``.key_tiles_causal`` (the key tiles one
head's query tiles visit, all rows full, and what the diagonal alone would
leave them) for the last built WITH a window, and the same four under
``kernels.flash_attention_bwd.``.

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
import typing

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


def _lane_blocks(heads: int, d: int, first, widths):
    """(heads a lane block holds, lanes of a block) for ``heads`` heads of
    ``d`` lanes that start at head ``first[i]`` of an array ``widths[i]``
    lanes wide (q, k, v in turn), or None where no lane block serves and
    the caller makes the heads-first form itself.  A head of whole lane
    groups is its own block; two heads of 64 share one 128-lane block
    (Mosaic takes no 64-lane block out of a wider array), so they come in
    pairs that start on an even head; one head that is the whole array
    keeps its own width, padded to what the MXU contracts over."""
    if d % 128 == 0:
        return 1, d
    if d == 64 and heads % 2 == 0 and not any(f % 2 for f in first) \
            and not any(w % 128 for w in widths):
        return 2, 128
    if heads == 1 and not any(first) and all(w == d for w in widths):
        return 1, d
    return None


def _own(x, h: int, hb: int):
    """``x`` with the lanes of every head of its block but ``h`` set to
    nought (a block holds ``hb`` heads side by side, the first in the low
    lanes): a contraction over all its lanes is then head ``h``'s alone,
    the others adding exact zeros."""
    import jax.numpy as jnp
    from jax import lax
    if hb == 1:
        return x
    head = lax.broadcasted_iota(jnp.int32, x.shape, 1) // (x.shape[1] // hb)
    return jnp.where(head == h, x, jnp.zeros_like(x))


def _each(parts, width: int, axis: int = 1):
    """One block from a result a head that came out all ``width`` lanes
    (``axis`` 0: sublanes) wide: of ``parts[h]`` head ``h``'s own share.
    Parts may be rows or columns that broadcast along ``axis``."""
    import jax.numpy as jnp
    from jax import lax
    whole = parts[0]
    for h in range(1, len(parts)):
        shape = list(jnp.broadcast_shapes(whole.shape, parts[h].shape))
        shape[axis] = width
        head = lax.broadcasted_iota(jnp.int32, shape, axis) // (
            width // len(parts))
        whole = jnp.where(head == h, parts[h], whole)
    return whole


def _plain(q0, k0, length, block_q: int, block_k: int, off: int,
           window: int):
    """Whether no mask can change the score tile of the ``block_q`` queries
    from ``q0`` and the ``block_k`` keys from ``k0`` under the causal mask:
    no key of it is beyond the row's ``length``, above any row's diagonal
    (the first row's, at key ``q0 + off``, is the lowest) or behind any
    row's ``window`` (the last row's reaches back least far).  Every weight
    of such a tile is live, so no row of it is dead either.  Python ints
    (the gauges) and traced scalars (the kernels) alike."""
    plain = (k0 + block_k <= length) & (k0 + block_k - 1 <= q0 + off)
    if window:
        plain = plain & (k0 > q0 + block_q - 1 + off - window)
    return plain


# the shortest longest sweep, in key tiles, that is given a second loop
# body: the shortest read to gain (4096 keys, K-major: forward, ``dq`` and
# ``dkv`` 13, 11 and 3 % faster); at 8 and at 4 (2048 and 1024 keys, K and
# V resident) the forward lost the 5-7 % that ``dq`` gained (PERF.md
# section 6, PR 39), and BERT's two tiles a row read slower in PR 29
_SPLIT_TILES = 16


def _sweeps(lq: int, lk: int, block_q: int, block_k: int, causal: bool,
            window: int, split=None):
    """(whether the build masks only the tiles a mask can change, its
    gauges) from the shape alone: of one head's sweeps, all rows full,
    ``tiles_visited`` key tiles and ``tiles_plain`` that run the body
    without a mask (0 where one masked body is kept) and, for a build with
    a window, ``key_tiles`` and ``key_tiles_causal`` (what the window and
    what the diagonal alone leave the sweeps; a build without a window
    publishes neither, so the two are the last window build's).  The rule:
    a causal build of a lane group of queries or more whose longest sweep
    holds ``_SPLIT_TILES`` key tiles or more; ``split`` given is the
    tests' own: False keeps the one masked body (their reference), True has
    two at a causal shape small enough to interpret."""
    off = lk - lq
    diagonal = causal and off >= 0
    visited = causal_only = plain = longest = 0
    for q0 in range(0, _round_up(lq, block_q), block_q):
        end = -(-min(lk, q0 + block_q + off if diagonal else lk) // block_k)
        start = max(q0 + off - window + 1, 0) // block_k if window else 0
        causal_only += end
        visited += end - start
        longest = max(longest, end - start)
        plain += sum(bool(_plain(q0, t * block_k, lk, block_q, block_k, off,
                                 window)) for t in range(start, end))
    if split is None:
        split = causal and lq >= 128 and longest >= _SPLIT_TILES
    gauges = (("tiles_visited", visited),
              ("tiles_plain", plain if split else 0))
    if window:
        gauges += (("key_tiles", visited), ("key_tiles_causal", causal_only))
    return split, gauges


def _sweep_tiles(lo, hi, tile, plain, held, keep):
    """The loop of a sweep with two bodies: tile ``t`` of [lo, hi) runs
    ``tile(t, state, masked)``, without its masks where ``plain(t)`` (a
    scalar of ``_plain``).  The state goes from ``held()`` to ``keep()``
    round every tile, that is, it stays in the VMEM scratch those read and
    write, and the loop carries nothing: accumulators that fill the
    registers several times over are copied wherever control flow joins
    when they are a loop's or a ``cond``'s values (two bodies then read
    slower than one; in place, faster: PERF.md section 6, PR 39)."""
    from jax import lax

    def body(masked):
        def step(t):
            keep(*tile(t, held(), masked))
        return step

    def either(t, _):
        lax.cond(plain(t), body(False), body(True), t)
        return 0
    lax.fori_loop(lo, hi, either, 0)


@functools.lru_cache(maxsize=None)
def _build_call(bh: int, lq: int, lk: int, d: int, causal: bool,
                scale: float, dtype_name: str, interpret: bool,
                blocks: int = 1, lane_heads: int = 1, first=(0, 0, 0),
                window: int = 0, group: int = 1, split=None):
    """The kernel for one call's (unpadded) shape; it takes the operands
    padded as ``_tiling`` says.  ``d`` is the width of a lane block: of
    every operand's last axis the grid's second axis owns ``blocks`` of
    them in turn, from block ``first[i]`` of operand i on, and a block
    holds ``lane_heads`` heads side by side (heads-first operands are one
    block, the whole last axis).  ``window`` keys (0: none) bound the sweep
    from below; ``group`` query blocks in a row read one block of k and of
    v.  Each build says which tiling engaged in the
    ``kernels.flash_attention.*`` gauges."""
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
    hb = lane_heads

    # under a causal mask whose every row sees a key (Lk >= Lq) the keys
    # beyond a query tile's last row weigh nothing for the whole tile, so
    # they bound the sweep as the valid length does; with Lk < Lq some rows
    # see no key and take the dead-row rule below, which reads every tile
    off = lk - lq
    diagonal = causal and off >= 0
    split, sweeps = _sweeps(lq, lk, block_q, block_k, causal, window, split)

    reg = registry()
    reg.counter("kernels.flash_attention.builds",
                "flash forward kernels built (one per shape)").inc()
    for name, value in (("block_q", block_q), ("block_k", block_k),
                        ("kv_resident", int(nkv == 1)),
                        ("grid_steps", bh * blocks * nq * nkv),
                        ("lane_heads", hb),
                        ("tokens_major", int(blocks * hb > 1)),
                        ("window", window), ("kv_group", group), *sweeps):
        reg.gauge(f"kernels.flash_attention.{name}",
                  "tiling of the last flash forward kernel built").set(value)

    def key_limit(vl, qi):
        """Keys that query tile ``qi`` of a row of length ``vl`` can weigh."""
        if not diagonal:
            return vl
        return jnp.minimum(vl, (qi + 1) * block_q + off)

    def key_start(qi):
        """The first key that query tile ``qi`` can weigh under the window:
        its first row's, which reaches furthest back."""
        return jnp.maximum(qi * block_q + off - window + 1, 0)

    def sweep(vl, q, k_ref, v_ref, qi, kj, held, keep):
        """Online softmax of one query tile over the key tiles of K-major
        block ``kj`` that hold a key below ``vl`` (the tile's key limit): a
        key at or beyond it has weight 0 whether the row is live or dead,
        so the tiles beyond it are never visited.  The running max,
        denominator and accumulator come from ``held()`` and go to
        ``keep()``.  Where the block holds
        two heads, each is swept with the other's lanes of q set to
        nought (its scores are then a contraction over all the lanes, the
        other head's adding exact zeros), and of ``p v``, which comes out
        all lanes wide, each head keeps its own."""
        # one body: the state is the loop's carry, read before the bounds
        # are made (the kernel as it was before some builds had two bodies)
        carry = None if split else held()
        k0 = kj * kv_block
        qs = [_own(q, h, hb) for h in range(hb)]

        def tile(t, carry, masked=True):
            ms, ls, acc = carry
            start = pl.multiple_of(t * block_k, block_k)
            k = k_ref[0, pl.ds(start, block_k), :]
            v = v_ref[0, pl.ds(start, block_k), :]
            # mask K padding (and the causal upper triangle); a tile that
            # ``_plain`` vouches for has no weight to mask and no dead row
            if masked:
                k_idx = k0 + start + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                kmask = k_idx < vl
                mask = kmask
            if masked and causal:
                # bottom-right alignment (the flash/decode convention and
                # this repo's reference): query i sits at absolute key
                # position (lk - lq + i), so Lq=1 against a length-N
                # cache attends ALL N keys
                q_idx = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = mask & (k_idx <= q_idx + off)
                if window:
                    mask = mask & (k_idx > q_idx + off - window)
            ms_new, ls_new, corrs, pvs = [], [], [], []
            for q, m, l in zip(qs, ms, ls):
                # operands stay in the input dtype, the scale is applied to
                # the f32 scores: bf16 products are exact in the MXU's f32
                # accumulator, and f32 operands ask for full precision (the
                # MXU's default would round them to bf16: 5e-3 off at seq
                # 256)
                s = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32) * scale  # (BQ, BK)
                if masked:
                    s = jnp.where(mask, s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                if masked and causal:
                    # rows whose every key so far is masked (causal bound
                    # < 0): the reference softmaxes a uniform -NEG_INF row,
                    # i.e. uniform attention over the valid keys — exp(0)=1
                    # here would instead spread over PADDED slots, so
                    # substitute the valid mask as the weights (masks are
                    # prefixes, so a row dead in this tile is dead in every
                    # tile).  Without ``causal`` every visited tile holds a
                    # valid key and no row is dead.
                    # Under a window a row may see its first key only in
                    # a later tile (what it holds till then is wiped by
                    # ``corr`` = 0), and a row that never sees one (a
                    # padded query a window beyond the length) weighs
                    # nothing and gives 0.
                    dead = m_new <= (_NEG_INF * 0.5)
                    p = jnp.where(dead, 0.0 if window
                                  else kmask.astype(jnp.float32), p)
                corrs.append(jnp.exp(m - m_new))
                ms_new.append(m_new)
                ls_new.append(l * corrs[-1]
                              + jnp.sum(p, axis=1, keepdims=True))
                pvs.append(lax.dot_general(
                    p.astype(dtype), v, (((1,), (0,)), ((), ())),
                    precision=precision, preferred_element_type=jnp.float32))
            return (tuple(ms_new), tuple(ls_new),
                    acc * _each(corrs, dp) + _each(pvs, dp))

        n = jnp.clip(pl.cdiv(vl - k0, block_k), 0, tiles)
        lo = jnp.clip((key_start(qi) - k0) // block_k, 0, tiles) \
            if window else 0
        if not split:
            keep(*lax.fori_loop(lo, n, tile, carry))
            return
        _sweep_tiles(lo, n, tile, lambda t: _plain(
            qi * block_q, k0 + t * block_k, vl, block_q, block_k, off,
            window), held, keep)

    def start():
        return ((jnp.full((block_q, 1), _NEG_INF, jnp.float32),) * hb,
                (jnp.zeros((block_q, 1), jnp.float32),) * hb,
                jnp.zeros((block_q, dp), jnp.float32))

    def finish(o_ref, ls, acc):
        l = _each(ls, dp)
        # rows with no valid keys (padded queries) divide by 1 instead
        o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(dtype)

    def kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, *scratch):
        b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        # per-sequence valid key length (padding mask support): the tile
        # padding bound ``lk`` is static; vl tightens it per row
        vl = key_limit(jnp.minimum(vl_ref[b], lk), qi)
        if not scratch:
            # K and V resident and one body: the state is the loop's carry
            sweep(vl, q_ref[0], k_ref, v_ref, qi, kj, start,
                  lambda ms, ls, acc: finish(o_ref, ls, acc))
            return
        m_refs, l_refs, acc_ref = scratch[:hb], scratch[hb:-1], scratch[-1]

        def keep(ms, ls, acc):
            for r, x in zip(m_refs + l_refs, ms + ls):
                r[...] = x
            acc_ref[...] = acc

        def held():
            return (tuple(r[...] for r in m_refs),
                    tuple(r[...] for r in l_refs), acc_ref[...])

        @pl.when(kj == 0)
        def _():
            keep(*start())

        @pl.when((kj * kv_block < vl)
                 & ((kj + 1) * kv_block > key_start(qi)) if window
                 else kj * kv_block < vl)
        def _():
            sweep(vl, q_ref[0], k_ref, v_ref, qi, kj, held, keep)

        @pl.when(kj == nkv - 1)
        def _():
            finish(o_ref, tuple(r[...] for r in l_refs), acc_ref[...])

    def last_block(b, i, vl_ref):
        # a K-major block wholly beyond the row's length maps to the last
        # block that holds a valid key: the pipeline sees the same block
        # index again and issues no DMA for it
        return jnp.maximum(
            pl.cdiv(key_limit(jnp.minimum(vl_ref[b], lk), i), kv_block) - 1,
            0)

    def kv_block_of(b, i, j, vl_ref):
        # ... and one wholly behind the window to the first that is not
        j = jnp.maximum(j, key_start(i) // kv_block) if window else j
        return jnp.minimum(j, last_block(b, i, vl_ref))

    # Mosaic takes neither a rank-1 block of one element nor rank-1 loop
    # carries: the per-row length rides scalar memory, and the running
    # max/denominator are (block_q, 1) columns.  A head (or a pair) is the
    # lane block ``h`` of its operand, counted from the operand's first.
    fq, fk, fv = first

    def q_spec(at):
        return pl.BlockSpec((1, block_q, dp),
                            lambda b, h, i, j, vl: (b, i, at + h))

    def kv_spec(at):
        # ``group`` query blocks in a row read the one key block in place
        return pl.BlockSpec(
            (1, kv_block, dp),
            lambda b, h, i, j, vl: (b, kv_block_of(b, i, j, vl),
                                    at + (h // group if group > 1 else h)))
    # the sweep's state between K-major blocks, and between the tiles of a
    # sweep that has two bodies
    scratch = [] if nkv == 1 and not split else (
        [pltpu.VMEM((block_q, 1), jnp.float32)] * (2 * hb)
        + [pltpu.VMEM((block_q, dp), jnp.float32)])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, blocks, nq, nkv),
            in_specs=[q_spec(fq), kv_spec(fk), kv_spec(fv)],
            out_specs=q_spec(0),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((bh, lqp, blocks * dp), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd" + ("_window" if window else ""),
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
                    scale: float, dtype_name: str, interpret: bool,
                    blocks: int = 1, lane_heads: int = 1, first=(0, 0, 0),
                    window: int = 0, group: int = 1, split=None):
    """The backward's two kernels for one call's (unpadded) shape; they
    take the operands padded as ``_bwd_tiling`` says, and lane blocks as
    the forward does (``_build_call``: ``d`` lanes a block, ``blocks`` of
    them from block ``first[i]`` of q, k and v on, ``lane_heads`` heads to
    a block; the cotangent, dq, dk and dv hold the call's heads alone,
    from block 0).  Both compute their
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

    Where a block holds two heads, a kernel sets the other head's lanes
    to nought in the operand it holds still (``dq``: q and the cotangent;
    ``dkv``: k and v), so that a product over all the lanes is one head's;
    the products that come out all lanes wide (``a``, ``b`` down the
    sublanes; dk, dv along the lanes) keep each head's own part, and a
    query tile's (8, block_q) block of statistics holds three rows a head.

    Key tiles at or beyond a row's valid length, and under a causal mask
    with Lk >= Lq the tiles wholly above the diagonal, are neither visited
    nor fetched, as in the forward; a key tile wholly beyond the length
    gets zeros.  Operands reach the MXU as XLA's default precision gives
    them to it (float32 rounded to bfloat16 once, bfloat16 as it is);
    products, statistics and accumulators are float32.

    Under a ``window`` (keys; 0: none) ``dq`` starts its sweep where the
    forward does, and ``dkv`` ends a key tile's query sweep at the last
    query tile that holds a query less than ``window`` beyond the tile's
    last key; Q-major blocks beyond it are not fetched.  With ``group`` > 1
    query blocks to a block of k and v, ``dq`` reads the key block in place
    and ``dkv``'s grid is (batch, key blocks of the lanes, key blocks, the
    group's query blocks, Q-major blocks): the last two are sequential and
    add up in the one scratch, so dk and dv come out a key head wide and no
    copy a query head wide of k, v, dk or dv is made."""
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
    hb = lane_heads
    split, sweeps = _sweeps(lq, lk, block_q, block_k, causal, window, split)

    reg = registry()
    reg.counter("kernels.flash_attention_bwd.builds",
                "flash backward kernel pairs built (one per shape)").inc()
    for name, value in (("block_q", block_q), ("block_k", block_k),
                        ("grid_steps",
                         bh * blocks * (nq * nkv + nkb * nqb)),
                        ("lane_heads", hb),
                        ("window", window), ("kv_group", group), *sweeps):
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
    # Under a window a padded query a window beyond the length sees none
    # either: it weighs nothing, as in the forward.
    dead_rows = (causal and not diagonal) or bool(window)

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
        if window:
            keep = keep & (rel > q0 + off - k0 - window)
        return jnp.where(keep, s, _NEG_INF), kmask

    def weights(s, kmask, lse, linv, dp_, delta):
        """(p, ds) of a block from its rows' statistics; ``kmask`` None: a
        tile that ``_plain`` vouches for, which holds no dead row."""
        p = jnp.exp(s - lse)
        if dead_rows and kmask is not None:
            dead = lse <= _NEG_INF * 0.5
            p = jnp.where(dead, 0.0 if window
                          else kmask.astype(jnp.float32) * linv, p)
        ds = p * (dp_ - delta)
        if dead_rows and kmask is not None:
            ds = jnp.where(dead, 0.0, ds)
        return p, ds

    def key_limit(length, qi):
        if not diagonal:
            return length
        return jnp.minimum(length, (qi + 1) * block_q + off)

    def key_start(qi):
        # the first key that query tile ``qi`` can weigh under the window
        return jnp.maximum(qi * block_q + off - window + 1, 0)

    # -- dq, and the statistics -------------------------------------------
    def dq_kernel(vl_ref, q_ref, g_ref, k_ref, v_ref, dq_ref, st_ref,
                  m_ref, l_ref, n_ref, c_ref, a_ref, b_ref):
        b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        k0 = kj * kv_block
        length = jnp.minimum(vl_ref[b], lk)
        # of this K-major block: the tiles that hold a key the query tile
        # can weigh
        tiles = jnp.clip(pl.cdiv(key_limit(length, qi) - k0, block_k), 0,
                         kv_block // block_k)
        lo = jnp.clip((key_start(qi) - k0) // block_k, 0,
                      kv_block // block_k) if window else 0
        qs = [scaled(_own(q_ref[0], h, hb)) for h in range(hb)]
        gs = [_own(g_ref[0], h, hb).astype(mxu) for h in range(hb)]
        key, rel = positions()

        @pl.when(kj == 0)
        def _():
            m_ref[...] = jnp.full((hb, block_q), _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros((hb, block_q), jnp.float32)
            n_ref[...] = jnp.zeros((hb, block_q), jnp.float32)
            a_ref[...] = jnp.zeros((dp, block_q), jnp.float32)
            b_ref[...] = jnp.zeros((dp, block_q), jnp.float32)
            # dp is counted from the row's dp at key 0, the one key every
            # live row sees: dq below is a difference of two sums, and
            # where a row's weight sits on few keys (its only one; a
            # first key that draws most of it) both are then small
            for h, g in enumerate(gs):
                c_ref[h:h + 1, :] = nt(v_ref[0, :8, :].astype(mxu), g)[:1]

        def rows(ref):
            return tuple(ref[h:h + 1, :] for h in range(hb))

        cs = rows(c_ref)

        def tile(t, carry, masked=True):
            ms, ls, ns, a, b_ = carry
            start = pl.multiple_of(t * block_k, block_k)
            k = k_ref[0, pl.ds(start, block_k), :].astype(mxu)
            v = v_ref[0, pl.ds(start, block_k), :].astype(mxu)
            ms_new, ls_new, ns_new, corrs, kas, kbs = [], [], [], [], [], []
            for q, g, c, m, l, n in zip(qs, gs, cs, ms, ls, ns):
                s = nt(k, q)
                if masked:
                    s, kmask = mask(s, key, rel, length, qi * block_q,
                                    k0 + start)
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - m_new)
                if masked and dead_rows:
                    p = jnp.where(m_new <= _NEG_INF * 0.5, 0.0 if window
                                  else kmask.astype(jnp.float32), p)
                corr = jnp.exp(m - m_new)
                pdp = p * (nt(v, g) - c)
                ms_new.append(m_new)
                ls_new.append(l * corr + jnp.sum(p, axis=0, keepdims=True))
                ns_new.append(n * corr + jnp.sum(pdp, axis=0, keepdims=True))
                corrs.append(corr)
                kas.append(tn(k, pdp))
                kbs.append(tn(k, p))
            corr = _each(corrs, dp, 0)
            return (tuple(ms_new), tuple(ls_new), tuple(ns_new),
                    a * corr + _each(kas, dp, 0),
                    b_ * corr + _each(kbs, dp, 0))

        def held():
            return (rows(m_ref), rows(l_ref), rows(n_ref), a_ref[...],
                    b_ref[...])

        def keep(ms, ls, ns, a, b_):
            for ref, xs in ((m_ref, ms), (l_ref, ls), (n_ref, ns)):
                for h, x in enumerate(xs):
                    ref[h:h + 1, :] = x
            a_ref[...], b_ref[...] = a, b_

        if split:
            _sweep_tiles(lo, tiles, tile, lambda t: _plain(
                qi * block_q, k0 + t * block_k, length, block_q, block_k,
                off, window), held, keep)
        else:
            keep(*lax.fori_loop(lo, tiles, tile, held()))

        @pl.when(kj == nkv - 1)
        def _():
            # dq is linear in delta: with a = sum_j p dp k and b = sum_j p k
            # of the unnormalised p, dq = scale (a - delta b) / l, whatever
            # dp is counted from.  The statistics ``dkv`` needs, a row
            # each: m + log l (so p = exp(s - it)), delta, and for a dead
            # row 1 / l, its valid keys' even weight; a row with no valid
            # key divides by 1
            # (three rows a head of the block, the last repeated below)
            ms = rows(m_ref)
            ls = [jnp.where(l == 0.0, 1.0, l) for l in rows(l_ref)]
            deltas = [n / l for n, l in zip(rows(n_ref), ls)]
            dq = (a_ref[...] - _each(deltas, dp, 0) * b_ref[...]) * (
                scale / _each(ls, dp, 0))
            if dead_rows:
                dq = jnp.where(_each(ms, dp, 0) <= _NEG_INF * 0.5, 0.0, dq)
            dq_ref[0] = dq.T.astype(dtype)
            stats = []
            for m, l, delta, c in zip(ms, ls, deltas, cs):
                stats += [jnp.where(m <= _NEG_INF * 0.5, _NEG_INF,
                                    m + jnp.log(l)), delta + c, 1.0 / l]
            row = lax.broadcasted_iota(jnp.int32, (8, block_q), 0)
            st = stats[-1]
            for r in reversed(range(len(stats) - 1)):
                st = jnp.where(row == r, stats[r], st)
            st_ref[0, 0, 0] = st

    def last_block(b, i, vl_ref):
        # the sweep stops at the last K-major block that holds a key the
        # tile can weigh; a block beyond it maps to that one: no DMA
        return jnp.maximum(
            pl.cdiv(key_limit(jnp.minimum(vl_ref[b], lk), i), kv_block) - 1,
            0)

    def kv_block_of(b, i, j, vl_ref):
        # ... and it starts at the first that the window reaches
        j = jnp.maximum(j, key_start(i) // kv_block) if window else j
        return jnp.minimum(j, last_block(b, i, vl_ref))

    fq, fk, fv = first

    def q_spec(at):
        return pl.BlockSpec((1, block_q, dp),
                            lambda b, h, i, j, vl: (b, i, at + h))

    def kv_spec(at):
        return pl.BlockSpec(
            (1, kv_block, dp),
            lambda b, h, i, j, vl: (b, kv_block_of(b, i, j, vl),
                                    at + (h // group if group > 1 else h)))
    stats = jax.ShapeDtypeStruct((bh, blocks, nq, 8, block_q), jnp.float32)
    dq_call = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, blocks, nq, nkv),
            in_specs=[q_spec(fq), q_spec(0), kv_spec(fk), kv_spec(fv)],
            out_specs=[q_spec(0), pl.BlockSpec(
                (1, 1, 1, 8, block_q),
                lambda b, h, i, j, vl: (b, h, i, 0, 0))],
            scratch_shapes=[pltpu.VMEM((hb, block_q), jnp.float32)] * 4
            + [pltpu.VMEM((dp, block_q), jnp.float32)] * 2),
        out_shape=[jax.ShapeDtypeStruct((bh, lqp, blocks * dp), dtype),
                   stats],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq" + ("_window" if window else ""),
    )

    # -- dk and dv --------------------------------------------------------
    q_tiles = q_block // block_q
    # with ``group`` query blocks to a key block the grid's second axis
    # counts key blocks of the lanes and a sequential axis before the
    # Q-major one counts the group's query blocks
    key_heads = blocks // group

    def dkv_kernel(vl_ref, k_ref, v_ref, q_ref, g_ref, st_ref, dk_ref,
                   dv_ref, dk_acc, dv_acc, dk_tile=None, dv_tile=None):
        # ``dk_tile``, ``dv_tile``: a key tile's sums over its query tiles
        # where that sweep has two bodies (``_sweep_tiles``)
        b, kb = pl.program_id(0), pl.program_id(2)
        if group > 1:
            gi, qb = pl.program_id(3), pl.program_id(4)
        else:
            gi, qb = None, pl.program_id(3)

        def at_step(group_step, q_step):
            # the sequential axes stand at this step of the group's query
            # blocks (where there are any) and of the Q-major blocks
            if gi is None:
                return qb == q_step
            return (gi == group_step) & (qb == q_step)
        length = jnp.minimum(vl_ref[b], lk)
        k0, q0 = kb * key_block, qb * q_block
        key, rel = positions()

        @pl.when(at_step(0, 0))
        def _():
            dk_acc[...] = jnp.zeros((key_block, dp), jnp.float32)
            dv_acc[...] = jnp.zeros((key_block, dp), jnp.float32)

        def key_tile(t, _):
            start = pl.multiple_of(t * block_k, block_k)
            rows = pl.ds(start, block_k)
            ks = [_own(k_ref[0, rows, :], h, hb).astype(mxu)
                  for h in range(hb)]
            vs = [_own(v_ref[0, rows, :], h, hb).astype(mxu)
                  for h in range(hb)]
            kt = k0 + start

            def query_tile(u, carry, masked=True):
                dk, dv = carry
                at = pl.ds(pl.multiple_of(u * block_q, block_q), block_q)
                q, g = scaled(q_ref[0, at, :]), g_ref[0, at, :].astype(mxu)
                st = st_ref[0, 0, u]
                dks, dvs = [], []
                for h, (k, v) in enumerate(zip(ks, vs)):
                    s, kmask = nt(k, q), None
                    if masked:
                        s, kmask = mask(s, key, rel, length,
                                        q0 + u * block_q, kt)
                    p, ds = weights(s, kmask, st[3 * h:3 * h + 1],
                                    st[3 * h + 2:3 * h + 3], nt(v, g),
                                    st[3 * h + 1:3 * h + 2])
                    dks.append(nn(ds, q))
                    dvs.append(nn(p, g))
                return dk + _each(dks, dp), dv + _each(dvs, dp)

            # under the diagonal, the first query tile of this Q-major
            # block whose last row sees the tile's first key; under the
            # window, the sweep ends with the last query tile whose first
            # row still reaches the tile's last key
            first = (jnp.clip(kt - off - q0, 0, q_block) // block_q
                     if diagonal else 0)
            last = jnp.clip(pl.cdiv(kt + block_k - 1 + window - off - q0,
                                    block_q), 0, q_tiles) \
                if window else q_tiles
            nought = (jnp.zeros((block_k, dp), jnp.float32),) * 2
            if split:
                def keep(dk, dv):
                    dk_tile[...], dv_tile[...] = dk, dv

                def held():
                    return dk_tile[...], dv_tile[...]

                keep(*nought)
                _sweep_tiles(first, last, query_tile, lambda u: _plain(
                    q0 + u * block_q, kt, length, block_q, block_k, off,
                    window), held, keep)
                dk, dv = held()
            else:
                dk, dv = lax.fori_loop(first, last, query_tile, nought)
            dk_acc[rows, :] += dk
            dv_acc[rows, :] += dv
            return 0

        # key tiles that hold a valid key; the others keep their zeros
        lax.fori_loop(
            0, jnp.clip(pl.cdiv(length - k0, block_k), 0,
                        key_block // block_k),
            key_tile, 0)

        @pl.when(at_step(group - 1, nqb - 1))
        def _():
            dk_ref[0] = dk_acc[...].astype(dtype)
            dv_ref[0] = dv_acc[...].astype(dtype)

    def q_index(b, kb, qb, vl_ref):
        # Q-major blocks wholly above the diagonal map to the first that
        # is not, those wholly beyond the window to the last that is not,
        # and every block of a key block beyond the length to the last:
        # the pipeline sees the block it holds and issues no DMA
        first = (jnp.clip(kb * key_block - off, 0, lqp - 1) // q_block
                 if diagonal else 0)
        beyond = kb * key_block >= jnp.minimum(vl_ref[b], lk)
        at = jnp.maximum(qb, first)
        if window:
            at = jnp.minimum(at, jnp.clip(
                (kb + 1) * key_block - 1 + window - 1 - off, 0, lqp - 1)
                // q_block)
        return jnp.where(beyond, nqb - 1, at)

    def on_grid(index):
        """``index(b, key head, query head, kb, qb, vl)`` as the grid's
        index map."""
        if group > 1:
            return lambda b, h, kb, gi, qb, vl: index(
                b, h, h * group + gi, kb, qb, vl)
        return lambda b, h, kb, qb, vl: index(b, h, h, kb, qb, vl)

    def kb_spec(at):
        return pl.BlockSpec((1, key_block, dp), on_grid(
            lambda b, hk, hq, kb, qb, vl: (b, kb, at + hk)))

    def qb_spec(at):
        return pl.BlockSpec((1, q_block, dp), on_grid(
            lambda b, hk, hq, kb, qb, vl: (b, q_index(b, kb, qb, vl),
                                           at + hq)))
    dkv_call = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, key_heads, nkb) + ((group,) if group > 1 else ())
            + (nqb,),
            in_specs=[kb_spec(fk), kb_spec(fv), qb_spec(fq), qb_spec(0),
                      pl.BlockSpec(
                          (1, 1, q_tiles, 8, block_q), on_grid(
                              lambda b, hk, hq, kb, qb, vl: (
                                  b, hq, q_index(b, kb, qb, vl), 0, 0)))],
            out_specs=[kb_spec(0), kb_spec(0)],
            scratch_shapes=[pltpu.VMEM((key_block, dp), jnp.float32)] * 2
            + [pltpu.VMEM((block_k, dp), jnp.float32)]
            * (2 if split else 0)),
        out_shape=[jax.ShapeDtypeStruct((bh, lkp, key_heads * dp),
                                        dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
            + ("arbitrary",) * (2 if group > 1 else 1)),
        interpret=interpret,
        name="flash_attention_bwd_dkv" + ("_window" if window else ""),
    )
    return dq_call, dkv_call


class _Call(typing.NamedTuple):
    """What a call's kernels are built from beside the operands' shapes
    (static: the custom VJP's one non-differentiable argument)."""
    causal: bool
    scale: float
    interpret: bool
    heads: int          # heads of the call
    d: int              # lanes of a head
    src: tuple          # which of the call's arrays q, k and v are read from
    first: tuple        # ... and the head of it that each starts at
    window: int = 0     # keys a query reaches back over, itself among them
    group: int = 1      # query heads in a row that read one head of k and v


def _blocks(call: _Call, arrays):
    """(lane blocks of the call, heads to a block, lanes of a block, each
    operand's first block) by ``_lane_blocks``, which serves (the entry
    has seen to it)."""
    hb, lanes = _lane_blocks(call.heads, call.d, call.first,
                             [arrays[i].shape[2] for i in call.src])
    return (call.heads // hb, hb, lanes,
            tuple(f * call.d // lanes for f in call.first))


def _run_backward(arrays, vl, g, call: _Call):
    """(dq, dk, dv) of the attention the forward kernel computes, for the
    cotangent ``g``, by the two kernels of ``_build_backward``: each the
    call's heads side by side, as ``g`` is."""
    import jax
    import jax.numpy as jnp

    q, k, v = (arrays[i] for i in call.src)
    bh, lq, lk = q.shape[0], q.shape[1], k.shape[1]
    blocks, hb, lanes, first = _blocks(call, arrays)
    _, _, lqp, _, _, _, lkp, dp = _bwd_tiling(
        lq, lk, lanes, jnp.result_type(q).itemsize)
    key_width = call.heads // call.group * call.d

    with jax.named_scope("flash_attention_pad"):
        qp, gp, kp, vp = (_pad_to(x, rows, dp - lanes) for x, rows in (
            (q, lqp), (g.astype(q.dtype), lqp), (k, lkp), (v, lkp)))
    dq_call, dkv_call = _build_backward(
        bh, lq, lk, lanes, call.causal, call.scale, jnp.result_type(q).name,
        call.interpret, blocks, hb, first, call.window, call.group)
    lens = vl.astype(jnp.int32)
    dq, stats = dq_call(lens, qp, gp, kp, vp)
    dk, dv = dkv_call(lens, kp, vp, qp, gp, stats)
    if dq.shape == g.shape and dk.shape[1:] == (lk, key_width):
        return dq, dk, dv
    with jax.named_scope("flash_attention_pad"):
        return (dq[:, :lq, :g.shape[2]], dk[:, :lk, :key_width],
                dv[:, :lk, :key_width])


def _cotangents(arrays, call: _Call, grads):
    """The cotangent of each of the call's arrays from (dq, dk, dv): an
    array that holds one operand and no more takes its gradient as it is;
    a fused projection takes its operands' gradients side by side, in one
    concatenation (nought for lanes that no operand read); operands that
    read the same lanes add up."""
    import jax.numpy as jnp

    out = []
    for j, x in enumerate(arrays):
        parts = sorted(((call.first[i] * call.d, grads[i])
                        for i in range(3) if call.src[i] == j),
                       key=lambda part: part[0])
        width, pieces, at = x.shape[2], [], 0
        if any(a + ga.shape[2] > b for (a, ga), (b, _) in
               zip(parts, parts[1:])):
            out.append(sum(jnp.pad(ga, ((0, 0), (0, 0),
                                        (a, width - a - ga.shape[2])))
                           for a, ga in parts))
            continue
        for a, ga in parts + [(width, None)]:
            if a > at:
                pieces.append(jnp.zeros(x.shape[:2] + (a - at,), x.dtype))
            if ga is not None:
                pieces.append(ga)
                at = a + ga.shape[2]
        out.append(pieces[0] if len(pieces) == 1
                   else jnp.concatenate(pieces, axis=2))
    return tuple(out)


@functools.lru_cache(maxsize=1)
def _flash_core_fn():
    """Module-singleton custom-VJP core (built lazily so importing this
    module never imports jax)."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(arrays, vl, call):
        return _run_kernel(arrays, vl, call)

    def core_fwd(arrays, vl, call):
        # named here, before it is the primal: a name put on by the caller
        # would sit on another variable than the one a checkpoint's
        # policy is asked about.  The backward reads neither the output
        # nor any statistic of the forward: its kernels make their own.
        out = checkpoint_name(_run_kernel(arrays, vl, call), KEPT_OUTPUT)
        return out, (arrays, vl)

    def core_bwd(call, res, g):
        arrays, vl = res
        import jax.numpy as jnp
        with jax.named_scope("flash_attention_bwd"):
            grads = _run_backward(arrays, vl, g, call)
            # vl is a mask, not a weight
            return _cotangents(arrays, call, grads), jnp.zeros_like(vl)
    core.defvjp(core_fwd, core_bwd)
    return core


def _flash_core(arrays, vl, call: _Call):
    return _flash_core_fn()(arrays, vl, call)


def _pad_to(x, rows: int, lanes: int = 0):
    """``x`` padded to ``rows`` rows and by ``lanes`` lanes: only what the
    chosen tiles still need, a ragged Lq/Lk and a lone head whose width a
    kernel does not take as it is."""
    import jax.numpy as jnp
    if x.shape[1] == rows and not lanes:
        return x
    return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, lanes)))


def _run_kernel(arrays, vl, call: _Call):
    import jax
    import jax.numpy as jnp

    q, k, v = (arrays[i] for i in call.src)
    bh, lq, lk = q.shape[0], q.shape[1], k.shape[1]
    blocks, hb, lanes, first = _blocks(call, arrays)
    _, lqp, _, _, lkp, dp = _tiling(lq, lk, lanes,
                                    jnp.result_type(q).itemsize)

    # the kernel's own pad and unpad carry a name of their own: their
    # device time is the kernel's to answer for
    with jax.named_scope("flash_attention_pad"):
        qp, kp, vp = (_pad_to(x, rows, dp - lanes)
                      for x, rows in ((q, lqp), (k, lkp), (v, lkp)))
    kernel = _build_call(bh, lq, lk, lanes, call.causal, call.scale,
                         jnp.result_type(q).name, call.interpret, blocks, hb,
                         first, call.window, call.group)
    out = kernel(vl.astype(jnp.int32), qp, kp, vp)
    width = call.heads * call.d
    if out.shape == (bh, lq, width):
        return out
    with jax.named_scope("flash_attention_pad"):
        return out[:, :lq, :width]


def _heads_first(x, heads: int, d: int, first: int):
    """(B, L, ..) tokens-major lanes [first head, first + heads) as
    (B * heads, L, d)."""
    b, rows = x.shape[:2]
    x = x[:, :, first * d:(first + heads) * d].reshape(b, rows, heads, d)
    return x.transpose(0, 2, 1, 3).reshape(b * heads, rows, d)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    interpret=None, valid_len=None, num_heads=None,
                    head_dim=None, first_head=(0, 0, 0), window=None,
                    num_kv_heads=None):
    """Tiled attention: softmax(scale·QKᵀ + mask)V without materializing
    the score matrix.

    Heads-first (``num_heads`` None): (B, H, L, D) or (BH, L, D) operands;
    Lq/Lk/D are padded internally to tile multiples (K padding is masked
    exactly, never approximated).  ``valid_len`` enables per-sequence
    key-padding masks — shape (B,) or (B*H,); keys at positions >=
    valid_len[i] are masked exactly like the additive -1e9 padding mask of
    the XLA path.

    Tokens-major (``num_heads`` = H, because the shape cannot say it): q
    is (B, Lq, ..) and k, v are (B, Lk, ..) as the projections leave them,
    a head's ``head_dim`` lanes (default: q's width / H) beside the next
    head's; the result is (B, Lq, H·head_dim) and ``valid_len`` is (B,).
    ``first_head`` says at which head of its array each of q, k and v
    starts, so a fused projection is read in place: pass the one
    (B, L, 3·H·d) array three times with ``first_head=(0, H, 2 * H)`` and
    its gradient comes back whole.  The kernels then take each head as a
    lane block of the array (two heads of 64 to a block of 128), and no
    transposed copy of an operand, of the result or of a gradient is
    made.  Heads of another width, an odd count of 64-lane heads and
    lengths a head are served too: by the heads-first form, through the
    transposes this form spares.

    ``window`` (with ``causal`` and Lk >= Lq; what the model's layer has,
    not a switch): key j weighs on the query at key position i where
    ``j <= i`` and ``i - j < window``, the query's own key among the
    ``window``.  Tiles and blocks wholly behind the window are neither
    visited nor fetched, in the forward and in both backward kernels, which
    then carry the names ``flash_attention_{fwd,bwd_dq,bwd_dkv}_window``.  A
    window that reaches every key (``>= Lk``) is no window.  A padded query
    whose window holds no key below ``valid_len`` gives 0 and passes no
    gradient.

    ``num_kv_heads`` (tokens-major; grouped-query attention): k and v hold
    that many heads and query head h reads key head
    ``h // (num_heads / num_kv_heads)``; dk and dv come back that many
    heads wide.  The kernels read a group's key head in place by the index
    map and add the group's dk, dv up in VMEM, so the heads are whole lane
    groups (``head_dim`` a multiple of 128); another width is refused.

    DIFFERENTIABLE: the forward runs the Pallas kernel, the backward two
    more (``dq`` and ``dkv``), at the precision XLA's default gives a
    matmul on the chip (float32 operands rounded to bfloat16 once,
    float32 accumulation) — gradients also never touch an (Lq, Lk) score
    matrix, and key tiles beyond a row's ``valid_len`` cost nothing in
    either direction.
    """
    import jax.numpy as jnp

    if interpret is None:
        interpret = _interpret(q)
    if window is not None:
        lq, lk = q.shape[-2 if num_heads is None else 1], \
            k.shape[-2 if num_heads is None else 1]
        if not causal or lk < lq or int(window) < 1:
            raise ValueError("a window of at least one key goes with a "
                             "causal mask and Lk >= Lq")
    window = 0 if window is None or int(window) >= lk else int(window)
    if num_heads is None:
        if num_kv_heads is not None:
            raise ValueError("key heads shared by a group of query heads "
                             "are read from tokens-major operands "
                             "(num_heads given)")
        squeeze4 = q.ndim == 4
        if squeeze4:
            b, h, lq, dd = q.shape
            q = q.reshape(b * h, lq, dd)
            k = k.reshape(b * h, k.shape[2], dd)
            v = v.reshape(b * h, v.shape[2], dd)
        out = _attend((q, k, v), 1, q.shape[2], (0, 0, 0), causal, scale,
                      interpret, valid_len, window)
        return out.reshape(b, h, lq, dd) if squeeze4 else out

    heads, first = int(num_heads), tuple(int(f) for f in first_head)
    key_heads = heads if num_kv_heads is None else int(num_kv_heads)
    if heads % key_heads:
        raise ValueError(f"{heads} query heads do not divide into groups "
                         f"over {key_heads} key heads")
    group = heads // key_heads
    d = int(head_dim) if head_dim else q.shape[2] // heads
    for x, f, n in zip((q, k, v), first, (heads, key_heads, key_heads)):
        if (f + n) * d > x.shape[2]:
            raise ValueError(
                f"{n} heads of {d} lanes from head {f} on do not fit "
                f"an operand of {x.shape[2]} lanes")
    b = q.shape[0]
    rows = None if valid_len is None else jnp.asarray(valid_len).size
    lanes = rows in (None, b) and _lane_blocks(
        heads, d, first, [x.shape[2] for x in (q, k, v)])
    if group > 1 and not (lanes and d % 128 == 0):
        raise ValueError("a group's key head is read in place: heads of "
                         "whole lane groups (head_dim a multiple of 128) "
                         "and lengths a batch row")
    if lanes:
        return _attend((q, k, v), heads, d, first, causal, scale, interpret,
                       valid_len, window, group)
    out = _attend(tuple(_heads_first(x, heads, d, f)
                        for x, f in zip((q, k, v), first)),
                  1, d, (0, 0, 0), causal, scale, interpret, valid_len,
                  window)
    return out.reshape(b, heads, -1, d).transpose(0, 2, 1, 3).reshape(
        b, -1, heads * d)


def _attend(operands, heads: int, d: int, first, causal, scale, interpret,
            valid_len, window: int = 0, group: int = 1):
    """The kernels over (q, k, v) that ``_lane_blocks`` serves: ``heads``
    heads of ``d`` lanes from head ``first[i]`` of operand i on (heads-first
    operands: one head, the whole width); of k and v ``heads / group``."""
    import jax.numpy as jnp

    rows, lk = operands[0].shape[0], operands[1].shape[1]
    if valid_len is None:
        vl = jnp.full((rows,), lk, jnp.float32)
    else:
        vl = jnp.asarray(valid_len).reshape(-1).astype(jnp.float32)
        if vl.shape[0] != rows:
            if rows % vl.shape[0]:
                raise ValueError(
                    f"valid_len length {vl.shape[0]} does not divide "
                    f"batch*heads {rows}")
            vl = jnp.repeat(vl, rows // vl.shape[0])
    # one array read in several places (a fused projection) is one
    # operand of the differentiated call: its gradient is made whole
    arrays, src = [], []
    for x in operands:
        at = next((i for i, y in enumerate(arrays) if y is x), len(arrays))
        if at == len(arrays):
            arrays.append(x)
        src.append(at)
    return _flash_core(tuple(arrays), vl, _Call(
        bool(causal), float(d ** -0.5 if scale is None else scale),
        bool(interpret),
        heads, d, tuple(src), tuple(first), window, group))
