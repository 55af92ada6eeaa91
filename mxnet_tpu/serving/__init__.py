"""Serving subsystem: continuous-batching inference over CachedOp graphs.

Everything before this package optimized *training*; the north star says
"serve heavy traffic from millions of users".  This package is the
inference path: a :class:`ModelServer` that loads a hybridized
``HybridBlock`` (served through the direct cached-graph entry,
``HybridBlock.cached_graph`` — no autograd bookkeeping) or an exported
symbol/params pair (``ModelServer.from_exported``, the
``examples/serve_c_api.md`` seam), and runs **continuous/dynamic
batching**:

- :mod:`.batcher` — bounded admission queue (submits past the depth are
  rejected with :class:`ServerOverloaded`, the 429 analog; requests that
  out-wait their deadline are rejected with :class:`DeadlineExceeded`),
  plus the batcher thread and the dispatch handoff queue, so batch
  formation overlaps device execution;
- :mod:`.buckets` — shape-bucketed batch assembly: padding-length
  buckets (BERT's valid-length padding) x power-of-two batch
  buckets, one compiled executable per signature, with
  real/padded-element accounting for the batch-efficiency metric;
- :mod:`.server` — the :class:`ModelServer` lifecycle (start / graceful
  drain on ``stop()`` and SIGTERM), per-request metrics
  (``serving.request_us``, ``serving.queue_depth``, ``serving.tokens_*``)
  and flight-recorder request records;
- :mod:`.kv_cache` — the block-managed (paged) KV cache backing
  generation: fixed-size token blocks handed out from a free list, a
  worst-case reservation at admission, released the moment a request
  leaves (finish, deadline, or shed) — the ``serving.kv_blocks_used``
  gauge is the occupancy signal;
- :class:`GenerationServer` (in :mod:`.server`) — **token-level
  continuous batching** for autoregressive decode: an iteration-level
  scheduler where the schedulable unit is one decode step, finished
  requests exit the running batch every iteration, and queued prefills
  join open slots immediately (``MXTPU_SERVING_PREFILL_MODE`` picks
  interleaved vs batch-first prefill);
- :mod:`.registry` + :mod:`.frontend` — the production front door: a
  :class:`ModelRegistry` holding N named servers with priorities and
  per-model SLOs behind one admission gate, and the stdlib
  :class:`HttpFrontend` speaking JSON predict / SSE token streaming /
  W3C ``traceparent`` over it (``POST /v1/models/<name>/predict``,
  ``.../generate``, ``GET /v1/models``, ``/healthz``, ``/readyz``).

Quick start::

    from mxnet_tpu.serving import ModelServer
    net.hybridize()
    with ModelServer(net, max_batch=16) as srv:
        y = srv.infer(x)            # x: ONE sample, no batch dim

Generation::

    from mxnet_tpu.serving import GenerationServer
    lm = causal_lm_small(); ...
    with GenerationServer(lm, slots=4) as srv:
        ids = srv.generate(prompt_ids)      # greedy token ids

Knobs: ``MXTPU_SERVING_MAX_BATCH``, ``MXTPU_SERVING_QUEUE_DEPTH``,
``MXTPU_SERVING_DEADLINE_MS``, ``MXTPU_SERVING_WORKERS``,
``MXTPU_SERVING_BATCH_WINDOW_US``, ``MXTPU_SERVING_KV_BLOCK``,
``MXTPU_SERVING_KV_BLOCKS``, ``MXTPU_SERVING_DECODE_SLOTS``,
``MXTPU_SERVING_PREFILL_MODE``, ``MXTPU_SERVING_MAX_NEW_TOKENS``,
``MXTPU_FRONTEND_PORT``, ``MXTPU_FRONTEND_PRIORITY``,
``MXTPU_FRONTEND_SLO_MS`` (see the README knob table).
"""
from __future__ import annotations

from .batcher import (AdmissionQueue, Batcher, DeadlineExceeded,
                      GenRequest, Request, RequestCancelled, ServerClosed,
                      ServerOverloaded, ServingError)
from .buckets import Bucketer, NoBucketError
from .frontend import HttpFrontend
from .kv_cache import BlockKVCache, BlockTable, SCRATCH_BLOCK
from .registry import ModelEntry, ModelRegistry, UnknownModel
from .server import GenerationServer, ModelServer

__all__ = ["ModelServer", "GenerationServer", "Bucketer", "Request",
           "GenRequest", "AdmissionQueue", "Batcher", "BlockKVCache",
           "BlockTable", "SCRATCH_BLOCK", "ServingError", "ServerClosed",
           "ServerOverloaded", "DeadlineExceeded", "RequestCancelled",
           "NoBucketError", "HttpFrontend", "ModelRegistry", "ModelEntry",
           "UnknownModel"]
