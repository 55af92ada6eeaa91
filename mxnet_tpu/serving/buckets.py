"""Shape-bucketed batch assembly for the serving subsystem.

A compiled CachedOp executable is pinned to ONE input signature
(shapes + dtypes), so a server that dispatched every request shape
as-is would recompile constantly — the recompile storm
``HybridBlock.CACHED_GRAPH_LIMIT`` warns about.  The classic fix (the
reference's BucketingModule economics) is *bucketing*: pad variable
dimensions up to a small fixed menu of sizes so the whole workload
funnels through a handful of executables.

Two bucket axes compose here:

- **batch buckets** — powers of two up to ``max_batch`` (a partial
  batch of 3 dispatches as a padded batch of 4), so batch assembly
  never introduces new signatures;
- **length buckets** — optional per-sample padding of ``pad_axis`` to
  the smallest configured length that fits (BERT's valid-length
  padding idiom): a 20-token request joins the 32-token bucket.

Padding is real work the chip does for nothing, so the assembler
reports it — with the two pad axes kept SEPARATE, because they waste
differently:

- ``serving.tokens_padded`` — padded *sequence positions* inside
  occupied batch slots (a 20-token request in a 32-token bucket wastes
  12 positions): the length-bucket cost;
- ``serving.slots_padded`` — *empty batch slots* (3 requests dispatched
  as a padded batch of 4 waste one whole slot): the batch-bucket cost.

``serving.tokens_real`` stays the numerator.  Conflating the two (as
one "padded elements" denominator) polluted the sequence-padding
efficiency number with batch-pad, which matters once the generation
scheduler reports per-token decode efficiency.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError, hot_path

__all__ = ["Bucketer", "NoBucketError"]


class NoBucketError(MXNetError):
    """The request's shape fits no configured bucket (e.g. a sequence
    longer than the largest length bucket) — a client error, rejected
    at submission."""


def _pow2_buckets(max_batch: int) -> Tuple[int, ...]:
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


class Bucketer:
    """Maps request samples to (shape-bucket, batch-bucket) signatures
    and assembles padded batches.

    A *sample* is a tuple of per-request input arrays WITHOUT the batch
    dimension (the server stacks them).  With ``length_buckets`` set,
    every input whose ``pad_axis`` dimension equals the first input's
    length is padded (zeros) up to the smallest bucket that fits;
    inputs without that dimension pass through fixed-shape.
    """

    def __init__(self, max_batch: int = 8,
                 length_buckets: Optional[Sequence[int]] = None,
                 pad_axis: int = 0,
                 batch_buckets: Optional[Sequence[int]] = None):
        if max_batch < 1:
            raise MXNetError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = int(max_batch)
        self.pad_axis = int(pad_axis)
        self.length_buckets = tuple(sorted(set(int(b) for b in
                                               length_buckets))) \
            if length_buckets else ()
        if batch_buckets:
            bb = tuple(sorted(set(int(b) for b in batch_buckets)))
            if bb[-1] != self.max_batch:
                raise MXNetError(
                    f"largest batch bucket {bb[-1]} must equal "
                    f"max_batch {self.max_batch}")
            self.batch_buckets = bb
        else:
            self.batch_buckets = _pow2_buckets(self.max_batch)

    # -- bucket selection ---------------------------------------------------
    def batch_bucket(self, n: int) -> int:
        """Smallest batch bucket that holds ``n`` requests."""
        for b in self.batch_buckets:
            if b >= n:
                return b
        return self.batch_buckets[-1]

    def length_bucket(self, length: int) -> int:
        """Smallest length bucket >= ``length`` (raises NoBucketError
        past the largest)."""
        for b in self.length_buckets:
            if b >= length:
                return b
        raise NoBucketError(
            f"sample length {length} exceeds the largest length bucket "
            f"{self.length_buckets[-1]}")

    def sample_key(self, inputs: Sequence[_np.ndarray]) -> Tuple:
        """The shape-bucket key for one sample: a tuple of (padded
        per-sample shape, dtype name) per input.  Requests sharing a key
        batch together and share one executable per batch bucket."""
        if not inputs:
            raise MXNetError("empty request")
        if not self.length_buckets:
            return tuple((tuple(a.shape), str(a.dtype)) for a in inputs)
        ax = self.pad_axis
        lead = inputs[0]
        if lead.ndim <= ax:
            raise NoBucketError(
                f"pad_axis {ax} out of range for sample shape "
                f"{tuple(lead.shape)}")
        length = lead.shape[ax]
        bucket = self.length_bucket(length)
        key = []
        for a in inputs:
            shape = list(a.shape)
            if a.ndim > ax and a.shape[ax] == length:
                shape[ax] = bucket
            key.append((tuple(shape), str(a.dtype)))
        return tuple(key)

    # -- assembly -----------------------------------------------------------
    @hot_path("dispatch")
    def assemble(self, requests
                 ) -> Tuple[List[_np.ndarray], int, int, int, int]:
        """Pad-and-stack one bucket's requests into batch arrays.

        Returns ``(arrays, batch_bucket, real_elements, slots_padded,
        tokens_padded)``: element counts are over the first input.
        ``slots_padded`` is the count of EMPTY batch slots (batch-bucket
        rounding); ``tokens_padded`` is the padded sequence positions
        within OCCUPIED slots (length-bucket rounding) — two different
        wastes, counted apart.  Runs once per BATCH on the batcher
        thread; the pad buffers are per-batch allocations amortized over
        every request in them.
        """
        n = len(requests)
        bsz = self.batch_bucket(n)
        key = requests[0].key
        arrays: List[_np.ndarray] = []
        for j, (pshape, dt) in enumerate(key):
            # per-BATCH pad buffer (not per-op, not per-request): the one
            # allocation continuous batching exists to amortize
            buf = _np.zeros((bsz,) + tuple(pshape), dtype=dt)  # mxlint: disable=hot-path-purity — per-batch pad buffer, amortized over the batch
            for i, req in enumerate(requests):
                a = req.inputs[j]
                buf[(i,) + tuple(slice(0, s) for s in a.shape)] = a
            arrays.append(buf)
        real = sum(int(req.inputs[0].size) for req in requests)
        slot_elems = 1
        for s in key[0][0]:
            slot_elems *= int(s)
        slots_padded = bsz - n
        tokens_padded = n * slot_elems - real
        return arrays, bsz, real, slots_padded, tokens_padded
