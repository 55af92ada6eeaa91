"""ModelServer: continuous-batching inference over CachedOp graphs.

The executable a model server wants already exists in this stack:
``hybridize()``'s compiled-graph artifact (PAPER.md L6a — the CachedOp
analog).  This module wraps it in the serving loop the north star's
"millions of users" traffic shape needs:

    submit() -> AdmissionQueue (bounded, 429 past depth)
             -> batcher thread: shape-bucketed batch assembly
                (padding-length buckets, BERT's valid-length idiom)
             -> dispatch workers: ONE CachedGraph.raw call per bucket,
                batch formation overlapping device execution
             -> per-request results, metrics, flight-recorder records

Observability is wired from day one: ``serving.request_us`` (per-request
end-to-end latency histogram), ``serving.queue_depth`` (gauge),
``serving.dispatch_us`` (per-batch device-call histogram), and the
batch-formation-efficiency counters ``serving.tokens_real`` /
``serving.tokens_padded`` — all through the process-global registry, so
the Prometheus endpoint and JSONL writer see the serving path with zero
extra plumbing.  Every completed request also lands in the flight
recorder's per-request ring, dumped on crash alongside step records.

Knobs (all through ``base.register_env``): ``MXTPU_SERVING_MAX_BATCH``,
``MXTPU_SERVING_QUEUE_DEPTH``, ``MXTPU_SERVING_DEADLINE_MS``,
``MXTPU_SERVING_WORKERS``, ``MXTPU_SERVING_BATCH_WINDOW_US``.
"""
from __future__ import annotations

import itertools
import queue as _queue
import signal
import threading
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError, get_env, hot_path, jax_compute_dtype
from ..ndarray import NDArray
from ..observability import tracing as _tracing
from ..observability.flight import recorder as _flight_recorder
from ..observability.registry import registry
from ..observability.trace import span as _span
from ..observability.sampler import maybe_start_from_env as \
    _maybe_start_sampler
from ..observability.watchdog import touchpoint as _touchpoint
from .batcher import (AdmissionQueue, Batcher, DeadlineExceeded,
                      GenRequest, Request, RequestCancelled, ServerClosed,
                      ServerOverloaded)
from .buckets import Bucketer, NoBucketError
from .kv_cache import BlockKVCache

__all__ = ["ModelServer", "GenerationServer"]

MAX_BATCH_ENV = "MXTPU_SERVING_MAX_BATCH"
QUEUE_DEPTH_ENV = "MXTPU_SERVING_QUEUE_DEPTH"
DEADLINE_MS_ENV = "MXTPU_SERVING_DEADLINE_MS"
WORKERS_ENV = "MXTPU_SERVING_WORKERS"
BATCH_WINDOW_US_ENV = "MXTPU_SERVING_BATCH_WINDOW_US"
KV_BLOCK_ENV = "MXTPU_SERVING_KV_BLOCK"
KV_BLOCKS_ENV = "MXTPU_SERVING_KV_BLOCKS"
DECODE_SLOTS_ENV = "MXTPU_SERVING_DECODE_SLOTS"
PREFILL_MODE_ENV = "MXTPU_SERVING_PREFILL_MODE"
MAX_NEW_ENV = "MXTPU_SERVING_MAX_NEW_TOKENS"


def _live_window_s() -> float:
    """The knob-governed batch window, re-read before every batch pop
    (the BatchWindowController's live adaptation seam)."""
    return float(get_env(BATCH_WINDOW_US_ENV)) / 1e6


def _key_str(key: Tuple) -> str:
    """Compact human-readable bucket tag for records/debugging:
    ``32:int32|32:int32`` — dtype included, so two buckets differing
    only in dtype stay distinguishable in postmortems."""
    parts = []
    for shape, dt in key:
        parts.append(("x".join(str(s) for s in shape) or "scalar")
                     + ":" + str(dt))
    return "|".join(parts)


def _freeze_generic(block, examples):
    """Compile a non-Hybrid block (e.g. a SymbolBlock from the export
    seam) into one jitted inference callable with the CachedGraph.raw
    contract: raw values in, tuple of raw jax arrays out.  Parameters
    are baked as constants — fine for serving, where weights are
    immutable."""
    import jax

    from .. import autograd as _autograd

    ctx = examples[0].context

    def fn(*vals):
        ins = [NDArray(v, ctx=ctx) for v in vals]
        with _autograd.pause():
            out = block(*ins)
        outs = out if isinstance(out, (list, tuple)) else (out,)
        return tuple(o._read() for o in outs)

    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*[e._read() for e in examples]))
    return jitted


class ModelServer:
    """Continuous-batching inference server over one model.

    ``block`` is a :class:`~mxnet_tpu.gluon.HybridBlock` (served through
    the direct cached-graph entry — no autograd bookkeeping) or any
    Block (e.g. a ``SymbolBlock`` imported from the ``export()`` seam —
    see :meth:`from_exported`), serving host-side numpy results.

    Requests are single samples WITHOUT the batch dimension; the server
    assembles them into padded, bucketed batches and runs one compiled
    call per bucket.  ``submit`` is non-blocking and returns a
    :class:`~mxnet_tpu.serving.batcher.Request` future; ``infer`` is the
    blocking convenience wrapper.

    Lifecycle: ``start()`` spawns the batcher + N dispatch workers;
    ``stop(drain=True)`` (or context-manager exit, or SIGTERM via
    :meth:`install_sigterm`) closes admission, drains every queued
    request, and joins the threads.
    """

    def __init__(self, block, *, max_batch: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 workers: Optional[int] = None,
                 length_buckets: Optional[Sequence[int]] = None,
                 pad_axis: int = 0,
                 batch_buckets: Optional[Sequence[int]] = None,
                 batch_window_us: Optional[float] = None,
                 unpad_outputs: bool = True,
                 flight=None):
        self._block = block
        self.unpad_outputs = unpad_outputs
        self.max_batch = int(get_env(MAX_BATCH_ENV) if max_batch is None
                             else max_batch)
        self.queue_depth = int(get_env(QUEUE_DEPTH_ENV)
                               if queue_depth is None else queue_depth)
        self.deadline_ms = float(get_env(DEADLINE_MS_ENV)
                                 if deadline_ms is None else deadline_ms)
        self.workers = max(1, int(get_env(WORKERS_ENV)
                                  if workers is None else workers))
        # explicit argument = frozen window; knob-governed (the default)
        # = read LIVE per batch, so the BatchWindowController (or an
        # operator export) retunes a running server
        if batch_window_us is None:
            window_s = _live_window_s
        else:
            window_s = float(batch_window_us) / 1e6
        self._bucketer = Bucketer(self.max_batch,
                                  length_buckets=length_buckets,
                                  pad_axis=pad_axis,
                                  batch_buckets=batch_buckets)
        reg = registry()
        self._g_depth = reg.gauge(
            "serving.queue_depth",
            help="admission-queue depth (requests waiting for assembly)")
        self._h_request = reg.histogram(
            "serving.request_us",
            help="per-request end-to-end latency (enqueue to done)")
        self._h_dispatch = reg.histogram(
            "serving.dispatch_us",
            help="per-batch compiled-call wall time")
        self._c_requests = reg.counter(
            "serving.requests", help="requests admitted")
        self._c_done = reg.counter(
            "serving.requests_done", help="requests completed ok")
        self._c_rej_429 = reg.counter(
            "serving.rejected_429",
            help="requests rejected at admission (queue full)")
        self._c_rej_deadline = reg.counter(
            "serving.rejected_deadline",
            help="requests rejected at assembly (deadline expired)")
        self._c_batches = reg.counter(
            "serving.batches", help="batched compiled calls dispatched")
        self._c_real = reg.counter(
            "serving.tokens_real",
            help="real (unpadded) elements served — batch-efficiency "
                 "numerator")
        self._c_padded = reg.counter(
            "serving.tokens_padded",
            help="padded sequence positions dispatched within occupied "
                 "batch slots (length-bucket waste)")
        self._c_slots_padded = reg.counter(
            "serving.slots_padded",
            help="empty batch slots dispatched (batch-bucket waste), "
                 "counted in slots — kept apart from tokens_padded so "
                 "sequence-padding efficiency is not polluted by "
                 "batch-pad")
        self._flight = _flight_recorder() if flight is None else flight
        self._admission = AdmissionQueue(self.queue_depth,
                                         gauge=self._g_depth)
        self._out: _queue.Queue = _queue.Queue(
            maxsize=max(2, 2 * self.workers))
        self._batcher = Batcher(self._admission, self._bucketer,
                                self._out, self.max_batch,
                                window_s, self._expire,
                                on_error=self._fail)
        self._graphs: Dict[Tuple, object] = {}
        self._compile_lock = threading.Lock()
        self._lifecycle_lock = threading.Lock()
        self._threads = []
        self._started = False
        self._stopped = False
        self._drain_down = False
        self._rid = itertools.count()
        self._prev_sigterm = None
        # progress heartbeat for the watchdog: one bump per worker-loop
        # iteration (idle pops included — a healthy-idle server keeps
        # beating; only a wedged dispatch goes silent), thresholded on
        # the dispatch histogram's recent p99
        self._tp_dispatch = _touchpoint("serving.dispatch",
                                        hist="serving.dispatch_us")
        _maybe_start_sampler()

    # -- construction helpers ----------------------------------------------
    @classmethod
    def from_exported(cls, symbol_file: str, input_names,
                      param_file: Optional[str] = None, ctx=None, **kw
                      ) -> "ModelServer":
        """Serve an exported symbol/params pair (the
        ``examples/serve_c_api.md`` export seam): loads via
        ``SymbolBlock.imports`` and serves through one jitted graph."""
        from ..gluon.block import SymbolBlock
        blk = SymbolBlock.imports(symbol_file, input_names, param_file,
                                  ctx=ctx)
        return cls(blk, **kw)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ModelServer":
        with self._lifecycle_lock:
            if self._started:
                return self
            if self._stopped:
                raise ServerClosed("server already stopped")
            self._batcher.start()
            for i in range(self.workers):
                t = threading.Thread(target=self._worker_loop,
                                     name=f"mxtpu-serving-worker-{i}",
                                     daemon=True)
                t.start()
                self._threads.append(t)
            self._started = True
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None
             ) -> None:
        """Shut down: close admission (further submits raise
        ServerClosed), then either drain every queued request through
        the normal path (``drain=True``) or fail them immediately."""
        with self._lifecycle_lock:
            if self._stopped:
                return
            self._stopped = True
            self._admission.close()
            if not drain:
                for r in self._admission.shed():
                    self._finish(r, error=ServerClosed(
                        "server stopped without draining"))
            if self._started:
                self._batcher.join(timeout)
                if self._batcher.is_alive():
                    # timed-out join: the batcher may still be putting
                    # batches — sentinels would race AHEAD of them and
                    # strand their requests.  Flag the workers down
                    # instead; they drain whatever still arrives and
                    # exit on an idle tick.
                    self._drain_down = True
                else:
                    for _ in self._threads:
                        try:
                            self._out.put(None, timeout=1.0)
                        except _queue.Full:   # a wedged worker: flag
                            self._drain_down = True
                            break
                for t in self._threads:
                    t.join(timeout)
            else:
                # never started: nothing will drain the queue — shed
                for r in self._admission.shed():
                    self._finish(r, error=ServerClosed(
                        "server stopped before start"))
            self._g_depth.set(0)

    def __enter__(self) -> "ModelServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop(drain=exc_type is None)

    def install_sigterm(self) -> None:
        """Chain a SIGTERM handler that drains and stops the server
        (the k8s/preemption graceful-shutdown contract), then calls the
        previous handler.  The drain runs on its OWN (non-daemon)
        thread: the signal may have interrupted a frame on this very
        thread holding the locks stop() needs, so blocking inside the
        handler would deadlock — the handler returns immediately, the
        interrupted frame resumes and releases its locks, and the drain
        thread keeps the process alive until shutdown completes."""
        prev = signal.getsignal(signal.SIGTERM)
        self._prev_sigterm = prev

        def drain_then_chain(signum, frame):
            self.stop(drain=True)
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)

        def handler(signum, frame):
            threading.Thread(target=drain_then_chain,
                             args=(signum, frame),
                             name="mxtpu-serving-sigterm-drain",
                             daemon=False).start()

        signal.signal(signal.SIGTERM, handler)

    def uninstall_sigterm(self) -> None:
        if self._prev_sigterm is not None:
            signal.signal(signal.SIGTERM, self._prev_sigterm)
            self._prev_sigterm = None

    # -- client surface ------------------------------------------------------
    def submit(self, *inputs, deadline_ms: Optional[float] = None
               ) -> Request:
        """Enqueue one sample (inputs WITHOUT the batch dim); returns a
        Request future.  Raises :class:`ServerOverloaded` when the
        admission queue is full, :class:`ServerClosed` after stop, and
        :class:`NoBucketError` when no shape bucket fits."""
        arrs = []
        for x in inputs:
            a = x.asnumpy() if isinstance(x, NDArray) else _np.asarray(x)  # mxlint: disable=hidden-host-sync — request ingestion: client samples become host buffers at the serving boundary
            cd = jax_compute_dtype(a.dtype)
            if a.dtype != cd:
                a = a.astype(cd)
            arrs.append(a)
        key = self._bucketer.sample_key(arrs)
        ms = self.deadline_ms if deadline_ms is None else float(deadline_ms)
        deadline = (time.monotonic() + ms / 1e3) if ms > 0 else None
        req = Request(next(self._rid), tuple(arrs), key, deadline)
        # causal tracing root: ONE trace per request, head-sampled here
        # at admission (explicit lifecycle — finished in _finish on a
        # worker thread; the Request object carries the context across
        # the queue hops)
        req.trace = _tracing.tracer().begin(
            "serving.request", activate=False,
            args={"rid": req.rid, "bucket": _key_str(key)})
        try:
            self._admission.submit(req)
        except BaseException as exc:
            # admission refused ownership: the request never enters the
            # pipeline, so nobody downstream will ever finish this span.
            # Span hygiene FIRST, metrics after — the close must not
            # depend on anything else in the handler succeeding.
            if req.trace is not None:
                req.trace.annotate(error=type(exc).__name__)
                req.trace.finish()
            if isinstance(exc, ServerOverloaded):
                self._c_rej_429.inc()
            raise
        self._c_requests.inc()
        return req

    def infer(self, *inputs, timeout: Optional[float] = None,
              deadline_ms: Optional[float] = None):
        """Blocking convenience: submit + wait; returns host numpy
        output(s)."""
        return self.submit(*inputs, deadline_ms=deadline_ms
                           ).result(timeout)

    def warmup(self, *samples) -> int:
        """Precompile every (shape bucket, batch bucket) signature the
        given example samples imply, so no live request pays a compile.
        Each sample is one request's input tuple (or a single array).
        Returns the number of executables now resident."""
        for sample in samples:
            sample = sample if isinstance(sample, (tuple, list)) \
                else (sample,)
            # canonicalize dtypes exactly as submit() does, or the
            # warmed signatures can never match live requests
            arrs = []
            for a in sample:
                a = _np.asarray(a)
                cd = jax_compute_dtype(a.dtype)
                arrs.append(a.astype(cd) if a.dtype != cd else a)
            key = self._bucketer.sample_key(arrs)
            for bsz in self._bucketer.batch_buckets:
                self._graph_for(key, bsz)
        return len(self._graphs)

    def stats(self) -> dict:
        """Serving-side registry view plus the derived
        sequence-padding-efficiency ratio (real positions over positions
        dispatched in occupied slots — empty batch slots are reported
        separately as ``slots_padded``, not folded into the ratio)."""
        real, padded = self._c_real.n, self._c_padded.n
        return {
            "requests": self._c_requests.n,
            "done": self._c_done.n,
            "rejected_429": self._c_rej_429.n,
            "rejected_deadline": self._c_rej_deadline.n,
            "batches": self._c_batches.n,
            "queue_depth": self._g_depth.value,
            "tokens_real": real,
            "tokens_padded": padded,
            "slots_padded": self._c_slots_padded.n,
            "batch_efficiency": round(real / (real + padded), 4)
            if real + padded else 0.0,
            "executables": len(self._graphs),
        }

    # -- compiled-graph resolution (cold path) -------------------------------
    def _build_graph(self, block, key: Tuple, batch: int):
        """Compile ``block``'s executable for one (shape bucket, batch
        bucket) signature — lock-free, so :meth:`swap_block` can stage a
        full replacement graph set while live traffic keeps hitting the
        current one."""
        examples = block.example_inputs(
            [_np.zeros((batch,) + tuple(shape), dtype=dt)
             for shape, dt in key])
        from ..gluon.block import HybridBlock
        if isinstance(block, HybridBlock):
            g = block.cached_graph(*examples).raw
        else:
            g = _freeze_generic(block, examples)
        # one throwaway dispatch with HOST (numpy) arguments — the
        # argument types live batches arrive with.  The build above
        # warmed the executable against device-committed example
        # arrays; jax keys the lowering on argument sharding, so
        # without this the FIRST live batch would pay a second
        # lowering+compile (measured: ~600ms on the transformer)
        import jax as _jax
        _jax.block_until_ready(g(
            *[_np.zeros((batch,) + tuple(shape), dtype=dt)
              for shape, dt in key]))
        return g

    def _graph_for(self, key: Tuple, batch: int):
        """The executable for one (shape bucket, batch bucket): built on
        first use (``warmup()`` prebuilds), then a dict hit forever."""
        gk = (key, batch)
        g = self._graphs.get(gk)
        if g is not None:
            return g
        with self._compile_lock:
            g = self._graphs.get(gk)
            if g is not None:
                return g
            g = self._build_graph(self._block, key, batch)
            self._graphs[gk] = g
            return g

    # -- blue/green weight swap ----------------------------------------------
    def swap_block(self, new_block) -> int:
        """Rolling blue/green swap: compile ``new_block`` (the green
        side — typically the same architecture with new parameters) for
        EVERY signature the current graph set serves, all outside the
        lock while live traffic keeps dispatching on the old
        executables, then flip the block and the whole graph dict
        atomically.  In-flight batches hold a reference to the old
        executable and complete on it — zero requests drop.  With
        ``MXTPU_COMPILE_CACHE_DIR`` set the green compiles deserialize
        from the persistent cache (same architecture = same lowering).
        Returns the number of executables in the new set."""
        staged: Dict[Tuple, object] = {}
        for gk in list(self._graphs.keys()):
            staged[gk] = self._build_graph(new_block, gk[0], gk[1])
        with self._compile_lock:
            # a signature first compiled while we staged: build it for
            # the green side too (rare — the race window is one compile)
            for gk in list(self._graphs.keys()):
                if gk not in staged:
                    staged[gk] = self._build_graph(new_block, gk[0],
                                                   gk[1])
            self._block = new_block
            self._graphs = staged
        return len(staged)

    # -- dispatch-worker scaling (SloController seam) ------------------------
    def set_workers(self, n: int) -> int:
        """Retarget the dispatch-worker count on a RUNNING server (the
        :class:`~mxnet_tpu.tuning.controllers.SloController`'s scaling
        surface).  Growth spawns workers immediately; shrink retires
        one worker per sentinel, after any batches already queued ahead
        of it — requests are never dropped by a shrink.  Returns the
        new target."""
        n = max(1, int(n))
        with self._lifecycle_lock:
            if self._stopped:
                return self.workers
            delta = n - self.workers
            if delta == 0:
                return n
            if not self._started:
                self.workers = n
                return n
            if delta > 0:
                for _ in range(delta):
                    t = threading.Thread(
                        target=self._worker_loop,
                        name=f"mxtpu-serving-worker-{len(self._threads)}",
                        daemon=True)
                    t.start()
                    self._threads.append(t)
            else:
                for _ in range(-delta):
                    try:
                        self._out.put(None, timeout=1.0)
                    except _queue.Full:
                        # a wedged dispatch queue: scaling DOWN under
                        # that much pressure is wrong anyway — keep the
                        # workers we failed to retire
                        n += 1
            self.workers = n
            return n

    # -- dispatch (hot path) -------------------------------------------------
    def _worker_loop(self) -> None:
        tp = self._tp_dispatch
        while True:
            tp.beat()
            try:
                batch = self._out.get(timeout=0.25)
            except _queue.Empty:
                if self._drain_down:
                    break
                continue
            if batch is None:
                break
            try:
                graph = self._graph_for(batch.key, batch.batch)
                self._dispatch_batch(graph, batch)
            except Exception as e:  # a failed batch fails ITS requests,
                for req in batch.requests:      # never the server
                    if not req.done():
                        self._finish(req, error=e)

    @hot_path("dispatch")
    def _dispatch_batch(self, graph, batch) -> None:
        """Serving dispatch entry point: ONE compiled call for the whole
        bucket, one batched device→host transfer, then per-request
        fan-out."""
        # dispatch span: child of the batch's assembly span (tracing
        # off = batch.trace is None = no tracer touch on this hot root)
        sp = None if batch.trace is None else _tracing.tracer().begin(
            "serving.dispatch", parent=batch.trace, activate=False,
            args={"batch": batch.batch, "bucket": _key_str(batch.key)})
        rb = None
        try:
            t0 = time.monotonic()
            for req in batch.requests:
                req.t_dispatch = t0
            flat = graph(*batch.arrays)
            rb = None if sp is None else _tracing.tracer().begin(
                "serving.readback", parent=sp, activate=False)
            # response materialization: ONE batched device→host transfer
            # per BATCH (results are host values by contract), not per
            # request
            outs = [_np.asarray(v) for v in flat]  # mxlint: disable=hidden-host-sync,hot-path-purity — batched response readback, one transfer (and one buffer) per batch
        except BaseException as exc:
            # a failed batch must still record its dispatch span — the
            # postmortem trace of exactly the batch that died
            if sp is not None:
                sp.annotate(error=type(exc).__name__)
                if rb is not None:
                    rb.finish()
                sp.finish()
            raise
        if rb is not None:
            rb.finish()
            sp.finish()
        # inc(), not .n bumps: N workers finish batches concurrently and
        # the direct-bump idiom is reserved for single-threaded hot loops
        self._h_dispatch.observe((time.monotonic() - t0) * 1e6)
        self._c_batches.inc()
        self._c_real.inc(batch.real)
        self._c_padded.inc(batch.tokens_padded)
        self._c_slots_padded.inc(batch.slots_padded)
        for i, req in enumerate(batch.requests):
            req.batch_size = batch.batch
            row = self._unpad_row(tuple(o[i] for o in outs), req)
            self._finish(req, result=row[0] if len(row) == 1 else row)

    def _unpad_row(self, row, req: Request):
        """Undo length-bucket padding on a request's outputs: slice axis
        ``pad_axis`` (per-sample) back to the request's real length when
        its size equals the padded bucket — a per-position output like
        BERT's MLM logits trims; a pooled output with a different
        ``pad_axis`` extent passes through.  A pooled dim that
        COINCIDES with a bucket size (e.g. a 64-wide embedding under a
        64-token bucket) is indistinguishable from a length axis —
        construct with ``unpad_outputs=False`` and slice client-side
        for such models.  The padded positions' VALUES remain a model
        contract: a sequence model that attends everywhere must take a
        mask/valid-length input (pass it as part of the request) — the
        server cannot invent one."""
        bkt = self._bucketer
        if not bkt.length_buckets or not self.unpad_outputs:
            return row
        ax = bkt.pad_axis
        padded = req.key[0][0][ax]
        real = req.inputs[0].shape[ax]
        if real == padded:
            return row
        out = []
        for o in row:
            if o.ndim > ax and o.shape[ax] == padded:
                sl = [slice(None)] * o.ndim
                sl[ax] = slice(0, real)
                o = o[tuple(sl)]
            out.append(o)
        return tuple(out)

    def _finish(self, req: Request, result=None, error=None) -> None:
        """Complete one request: latency histogram, counters, flight
        record, wake the client."""
        req.t_done = time.monotonic()
        req._result = result
        req._error = error
        dur_us = (req.t_done - req.t_enqueue) * 1e6
        trace_id = None
        if req.trace is not None:
            trace_id = req.trace.trace_id
            if error is not None:
                req.trace.annotate(error=type(error).__name__)
            req.trace.finish()
        if error is None:
            # the explicit trace_id puts the exemplar on THIS request's
            # trace (no contextvar crosses the worker-thread hop)
            self._h_request.observe(dur_us, trace_id=trace_id)
            self._c_done.inc()
        self._flight.record_request(
            request_id=req.rid,
            enqueue=round(req.t_enqueue, 6),
            assemble=round(req.t_assemble, 6),
            dispatch=round(req.t_dispatch, 6),
            done=round(req.t_done, 6),
            bucket=_key_str(req.key),
            batch_size=req.batch_size,
            us=round(dur_us, 1),
            # causal cross-reference: a crash dump's request ring points
            # into the span ring / JSONL stream
            trace_id=trace_id,
            ok=error is None)
        req._event.set()

    def _expire(self, req: Request) -> None:
        self._c_rej_deadline.inc()
        self._finish(req, error=DeadlineExceeded(
            f"request {req.rid} spent its deadline queued (429-style); "
            f"the server is over capacity — back off"))

    def _fail(self, req: Request, error: BaseException) -> None:
        """Assembly-failure path: same accounting as every other
        completion (flight record, timestamps), just with an error."""
        self._finish(req, error=error)


class GenerationServer:
    """ModelServer's generation mode: an **iteration-level** (token-level
    continuous-batching) decode scheduler over a paged KV cache.

    The whole-sequence :class:`ModelServer` batches one compiled call
    per request set — fine for one-shot inference, but an autoregressive
    decode loop batched that way strands the chip on the longest request
    in every batch.  Here the schedulable unit is ONE DECODE STEP:

    - ``submit_generate(prompt)`` enqueues a generation (bounded queue,
      429 past the depth — same backpressure contract as ``submit``);
    - admission into the *running batch* gates on **KV block
      availability** (a worst-case reservation against the
      :class:`~mxnet_tpu.serving.kv_cache.BlockKVCache` pool), not just
      queue depth — an admitted request can never exhaust the pool
      mid-decode;
    - each admitted prompt runs ONE compiled **prefill** (batch 1,
      padded to a length bucket — the existing bucketing discipline)
      that scatters prompt K/V into the request's blocks and yields the
      first token (TTFT is measured exactly here);
    - every iteration dispatches ONE compiled **decode step** over all
      running slots (signature = (slot-count, max-blocks), compiled
      once, persistent-cache warm); finished requests leave their slot
      and queued prefills join at the very next iteration — no request
      ever waits for another's tail.

    ``MXTPU_SERVING_PREFILL_MODE`` picks the prefill interleave:
    ``"interleave"`` admits at most one prefill per decode iteration
    (smooth decode cadence for running requests), ``"step"`` prefills
    every admissible queued request before the next decode step (fastest
    burst drain).  Read live per iteration; nothing on a chip has
    measured either against the other.

    The model contract is three compiled entries sharing one parameter
    set (see ``gluon.model_zoo.transformer.CausalLM``):
    ``hybrid_forward`` (whole-sequence baseline), ``hybrid_prefill`` and
    ``hybrid_decode`` (paged), plus ``init_kv_pool``.  Greedy decode
    here is bitwise-reproducible per request regardless of batch
    composition: every decode-step op is row-independent and the
    additive mask underflows foreign/garbage keys to exact zero weight.
    """

    def __init__(self, block, *, slots: Optional[int] = None,
                 kv_block: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 max_new_tokens: Optional[int] = None,
                 prompt_buckets: Sequence[int] = (16, 32, 64),
                 eos: Optional[int] = None,
                 flight=None):
        for need in ("hybrid_prefill", "hybrid_decode", "init_kv_pool"):
            if not callable(getattr(block, need, None)):
                raise MXNetError(
                    f"generation serving needs a block with {need}() — "
                    f"see gluon.model_zoo.transformer.CausalLM")
        self._block = block
        self._slots = max(1, int(get_env(DECODE_SLOTS_ENV)
                                 if slots is None else slots))
        self._target_slots = self._slots
        self.queue_depth = int(get_env(QUEUE_DEPTH_ENV)
                               if queue_depth is None else queue_depth)
        self.deadline_ms = float(get_env(DEADLINE_MS_ENV)
                                 if deadline_ms is None else deadline_ms)
        self.max_new_cap = max(1, int(get_env(MAX_NEW_ENV)
                                      if max_new_tokens is None
                                      else max_new_tokens))
        self.eos = eos
        self._buckets = tuple(sorted(set(int(b) for b in prompt_buckets)))
        self._kv = BlockKVCache(kv_blocks, kv_block)
        # decode table width: worst-case blocks for the largest prompt
        # bucket plus the generation cap — ONE decode signature per
        # slot count
        bs = self._kv.block_size
        self._max_blocks = -(-(self._buckets[-1] + self.max_new_cap) // bs)
        self._pool = block.init_kv_pool(self._kv.n_blocks, bs)
        self._tables: Dict[int, object] = {}
        reg = registry()
        self._g_depth = reg.gauge(
            "serving.queue_depth",
            help="admission-queue depth (requests waiting for assembly)")
        self._h_request = reg.histogram(
            "serving.request_us",
            help="per-request end-to-end latency (enqueue to done)")
        self._h_ttft = reg.histogram(
            "serving.ttft_us",
            help="time to first token: generation enqueue to the "
                 "prefill's first emitted token")
        self._h_step = reg.histogram(
            "serving.decode_step_us",
            help="one iteration-level decode step: compiled call + "
                 "batched logits readback over all running slots")
        self._c_requests = reg.counter(
            "serving.requests", help="requests admitted")
        self._c_done = reg.counter(
            "serving.requests_done", help="requests completed ok")
        self._c_rej_429 = reg.counter(
            "serving.rejected_429",
            help="requests rejected at admission (queue full)")
        self._c_rej_deadline = reg.counter(
            "serving.rejected_deadline",
            help="requests rejected at assembly (deadline expired)")
        self._c_tokens = reg.counter(
            "serving.tokens_generated",
            help="tokens emitted by the generation scheduler (prefill "
                 "first-tokens included)")
        self._c_steps = reg.counter(
            "serving.decode_steps", help="decode iterations dispatched")
        self._flight = _flight_recorder() if flight is None else flight
        self._queue = []
        self._running = [None] * self._slots
        self._lock = threading.Condition()
        self._prefill_graphs: Dict[int, object] = {}
        self._decode_graphs: Dict[int, object] = {}
        # per-slot-count reusable decode-step assembly buffers (tokens,
        # positions, tables), built with the graph so the per-step hot
        # path allocates nothing
        self._step_bufs: Dict[int, tuple] = {}
        self._compile_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._abort = False
        self._rid = itertools.count()
        self._prev_sigterm = None
        # progress heartbeat for the watchdog: bumped every scheduler
        # iteration AND inside the idle condition-wait, thresholded on
        # the decode-step histogram's recent p99 — a wedged decode
        # dispatch goes silent, a merely-idle scheduler never does
        self._tp_decode = _touchpoint("serving.decode",
                                      hist="serving.decode_step_us")
        _maybe_start_sampler()

    # -- lifecycle ----------------------------------------------------
    def start(self) -> "GenerationServer":
        with self._lock:
            if self._thread is not None:
                return self
            if self._closed:
                raise ServerClosed("server already stopped")
            self._thread = threading.Thread(
                target=self._run, name="mxtpu-serving-decode-scheduler",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, drain: bool = True,
             timeout: Optional[float] = None) -> None:
        """Close admission; ``drain=True`` finishes every queued and
        running generation through the normal path, else they fail with
        ServerClosed (their KV blocks released either way)."""
        with self._lock:
            if self._closed and self._thread is None:
                return
            self._closed = True
            if not drain:
                self._abort = True
            self._lock.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout)
        if t is None or not t.is_alive():
            # never started (or fully joined): fail whatever remains
            with self._lock:
                shed, self._queue = self._queue, []
                run = [r for r in self._running if r is not None]
                self._running = [None] * self._slots
            for r in shed + run:
                self._finish_gen(r, error=ServerClosed(
                    "server stopped" if t is not None
                    else "server stopped before start"))
            self._g_depth.set(0)
        with self._lock:
            self._thread = None

    def __enter__(self) -> "GenerationServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop(drain=exc_type is None)

    def install_sigterm(self) -> None:
        """SIGTERM-drain parity with :meth:`ModelServer.install_sigterm`
        (the k8s/preemption graceful-shutdown contract): chain a handler
        that drains and stops the scheduler, then calls the previous
        handler.  The drain runs on its OWN non-daemon thread — the
        signal may have interrupted a frame holding the scheduler lock,
        so the handler itself never blocks in signal context; the
        non-daemon drain thread keeps the process alive until every
        queued and running generation has finished and released its KV
        blocks."""
        prev = signal.getsignal(signal.SIGTERM)
        self._prev_sigterm = prev

        def drain_then_chain(signum, frame):
            self.stop(drain=True)
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)

        def handler(signum, frame):
            threading.Thread(target=drain_then_chain,
                             args=(signum, frame),
                             name="mxtpu-serving-gen-sigterm-drain",
                             daemon=False).start()

        signal.signal(signal.SIGTERM, handler)

    def uninstall_sigterm(self) -> None:
        if self._prev_sigterm is not None:
            signal.signal(signal.SIGTERM, self._prev_sigterm)
            self._prev_sigterm = None

    # -- client surface -----------------------------------------------
    def submit_generate(self, prompt, max_new_tokens: Optional[int] = None,
                        deadline_ms: Optional[float] = None,
                        eos: Optional[int] = None) -> GenRequest:
        """Enqueue one generation: ``prompt`` is a 1-D sequence of token
        ids; returns a :class:`GenRequest` future whose ``result()`` is
        the greedy-decoded token ids (EOS included when hit).  Raises
        :class:`ServerOverloaded` past the queue depth (429),
        :class:`NoBucketError` when the prompt fits no length bucket or
        the request could never fit the KV pool, and ``MXNetError`` past
        the server's ``max_new_tokens`` cap (the cap sizes the compiled
        decode signature's block table)."""
        arr = _np.ascontiguousarray(_np.asarray(prompt).ravel(),
                                    dtype=_np.int32)  # mxlint: disable=hidden-host-sync — request ingestion at the serving boundary
        plen = int(arr.shape[0])
        if plen < 1:
            raise MXNetError("empty prompt")
        self._bucket_for(plen)          # raises NoBucketError past max
        mnt = self.max_new_cap if max_new_tokens is None \
            else int(max_new_tokens)
        if mnt < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if mnt > self.max_new_cap:
            raise MXNetError(
                f"max_new_tokens {mnt} exceeds the server cap "
                f"{self.max_new_cap} (the cap sizes the decode "
                f"signature; construct the server with a larger "
                f"max_new_tokens)")
        if not self._kv.fits(plen, mnt):
            raise NoBucketError(
                f"prompt of {plen} + {mnt} new tokens needs "
                f"{self._kv.blocks_needed(plen, mnt)} KV blocks; the "
                f"pool holds {self._kv.capacity}")
        ms = self.deadline_ms if deadline_ms is None else float(deadline_ms)
        deadline = (time.monotonic() + ms / 1e3) if ms > 0 else None
        req = GenRequest(next(self._rid), arr, mnt, deadline,
                         self.eos if eos is None else eos)
        req.trace = _tracing.tracer().begin(
            "serving.generate", activate=False,
            args={"rid": req.rid, "prompt": plen, "max_new": mnt})
        try:
            with self._lock:
                if self._closed:
                    raise ServerClosed("server is shut down")
                if len(self._queue) >= self.queue_depth:
                    self._c_rej_429.inc()
                    raise ServerOverloaded(
                        f"admission queue full ({self.queue_depth} deep)"
                        f" — retry with backoff (429)")
                self._queue.append(req)
                self._g_depth.set(len(self._queue))
                self._lock.notify_all()
        except BaseException as exc:
            # rejected before entering the pipeline: nobody downstream
            # holds the span, so close it here or it leaks open forever
            if req.trace is not None:
                req.trace.annotate(error=type(exc).__name__)
                req.trace.finish()
            raise
        self._c_requests.inc()
        return req

    def generate(self, prompt, timeout: Optional[float] = None, **kw):
        """Blocking convenience: submit + wait; returns the generated
        token ids."""
        return self.submit_generate(prompt, **kw).result(timeout)

    def cancel(self, req: GenRequest) -> bool:
        """Cancel an in-flight generation (the stream-disconnect path):
        a still-queued request is failed immediately; a running one is
        marked and leaves the batch at the next iteration boundary —
        either way :meth:`_finish_gen` releases its KV blocks, so a
        client hanging up mid-stream returns the pool to zero.  Returns
        False when the request had already completed."""
        with self._lock:
            if req.done():
                return False
            queued = req in self._queue
            if queued:
                self._queue.remove(req)
                self._g_depth.set(len(self._queue))
            else:
                # running (or mid-admission): the scheduler owns it —
                # flag it and let the iteration edge retire it
                req.cancelled = True
        if queued:
            self._finish_gen(req, error=RequestCancelled(
                f"generation {req.rid} cancelled while queued"))
        return True

    def warmup(self) -> int:
        """Precompile the decode-step signature and every prompt-bucket
        prefill, so no live generation pays a compile.  On a warm
        process with ``MXTPU_COMPILE_CACHE_DIR`` set this deserializes
        instead of compiling (compiles==0).  Returns the number of
        executables resident."""
        self._decode_graph(self._slots)
        for b in self._buckets:
            self._prefill_graph(b)
        return len(self._prefill_graphs) + len(self._decode_graphs)

    def stats(self) -> dict:
        with self._lock:
            occupied = sum(1 for r in self._running if r is not None)
            depth = len(self._queue)
        return {
            "requests": self._c_requests.n,
            "done": self._c_done.n,
            "rejected_429": self._c_rej_429.n,
            "rejected_deadline": self._c_rej_deadline.n,
            "queue_depth": depth,
            "slots": self._slots,
            "slots_occupied": occupied,
            "tokens_generated": self._c_tokens.n,
            "decode_steps": self._c_steps.n,
            "kv_blocks_used": self._kv.used(),
            "kv_blocks_total": self._kv.capacity,
            "executables": len(self._prefill_graphs) +
            len(self._decode_graphs),
        }

    # -- slot-count control (DecodeSlotController seam) ----------------
    @property
    def decode_slots(self) -> int:
        return self._slots

    def set_decode_slots(self, n: int) -> None:
        """Retarget the running-batch slot count.  Takes effect between
        iterations: growth immediately, shrink once occupancy allows —
        running requests are never evicted.  A new slot count is a new
        compiled decode signature (the recompile the
        DecodeSlotController's bracketing stop economizes); previously
        used counts stay cached."""
        with self._lock:
            self._target_slots = max(1, int(n))
            self._lock.notify_all()

    # -- compiled-graph resolution (cold path) -------------------------
    def _bucket_for(self, plen: int) -> int:
        for b in self._buckets:
            if b >= plen:
                return b
        raise NoBucketError(
            f"prompt length {plen} exceeds the largest prompt bucket "
            f"{self._buckets[-1]}")

    def _prefill_graph(self, bucket: int):
        g = self._prefill_graphs.get(bucket)
        if g is not None:
            return g
        with self._compile_lock:
            g = self._prefill_graphs.get(bucket)
            if g is None:
                bs = self._kv.block_size
                w = -(-bucket // bs)
                g = self._block.cached_graph(
                    _np.zeros((1, bucket), _np.int32),
                    _np.zeros((1,), _np.int32),
                    _np.zeros((1, w), _np.int32),
                    self._pool, entry="prefill")
                self._prewarm_locked(
                    g, _np.zeros((1, bucket), _np.int32),
                    _np.ones((1,), _np.int32),
                    _np.zeros((1, w), _np.int32))
                self._prefill_graphs[bucket] = g
            return g

    def _decode_graph(self, slots: int):
        g = self._decode_graphs.get(slots)
        if g is not None:
            return g
        with self._compile_lock:
            g = self._decode_graphs.get(slots)
            if g is None:
                g = self._block.cached_graph(
                    _np.zeros((slots,), _np.int32),
                    _np.zeros((slots,), _np.int32),
                    _np.zeros((slots, self._max_blocks), _np.int32),
                    self._pool, entry="decode")
                self._prewarm_locked(
                    g, _np.zeros((slots,), _np.int32),
                    _np.zeros((slots,), _np.int32),
                    _np.zeros((slots, self._max_blocks), _np.int32))
                self._step_bufs[slots] = (
                    _np.zeros((slots,), _np.int32),
                    _np.zeros((slots,), _np.int32),
                    _np.zeros((slots, self._max_blocks), _np.int32))
                self._decode_graphs[slots] = g
            return g

    def _prewarm_locked(self, graph, *host_args) -> None:
        """Two throwaway ``raw`` dispatches with HOST (numpy) argument
        types.  The cached-graph build warms the executable against
        device-committed example arrays, but jax keys the lowering on
        argument sharding — without this the FIRST live call would pay
        a second lowering+compile (~700ms on the transformer).  Called
        twice because the first flips ``self._pool`` from its initial
        host array to the committed pool the graph returns, which is a
        third signature; the second call IS steady state.  All-zero
        block tables route the dummy KV writes into the scratch block,
        which no real table row references."""
        for _ in range(2):
            logits, pool = graph.raw(*host_args, self._pool)
            self._pool = pool
        _np.asarray(logits)  # mxlint: disable=hidden-host-sync — cold-path warmup barrier, not a live request

    # -- the scheduler loop --------------------------------------------
    def _run(self) -> None:
        tp = self._tp_decode
        while True:
            tp.beat()
            with self._lock:
                while (not self._queue
                       and not any(r is not None for r in self._running)
                       and not self._closed):
                    self._lock.wait(0.1)
                    tp.beat()   # healthy-idle keeps the heartbeat alive
                if self._abort:
                    shed, self._queue = self._queue, []
                    run = [r for r in self._running if r is not None]
                    self._running = [None] * self._slots
                    self._g_depth.set(0)
                else:
                    self._retarget_slots_locked()
                    admit, expired = self._admit_locked()
            if self._abort:
                for r in shed + run:
                    self._finish_gen(r, error=ServerClosed(
                        "server stopped without draining"))
                return
            for r in expired:
                self._expire_gen(r)
            # graph/bucket resolution OUTSIDE the hot per-step root:
            # first use compiles under the lock; after warmup these are
            # dict hits
            # each iteration's work lies in a profiler's trace under the
            # scheduler thread's own spans (``mx.serving.*``), whether or
            # not request tracing (MXTPU_TRACE) is on: a device's idle gap
            # then has an owner
            for req in admit:
                bucket = self._bucket_for(len(req.prompt))
                graph = self._prefill_graph(bucket)
                with _span("serving.prefill", histogram=False):
                    self._prefill(graph, req, bucket)
            occupied = any(r is not None for r in self._running)
            if occupied:
                graph = self._decode_graph(self._slots)
                with _span("serving.decode_step", histogram=False):
                    self._decode_step(graph)
            elif not admit and not expired:
                if self._closed:
                    with self._lock:
                        idle = not self._queue and not any(
                            r is not None for r in self._running)
                    if idle:
                        return
                else:
                    # nothing flowed (e.g. pool exhausted by an earlier
                    # admission wave): don't spin the condition hot
                    time.sleep(0.002)

    def _retarget_slots_locked(self) -> None:
        tgt = self._target_slots
        if tgt == self._slots:
            return
        occ = [r for r in self._running if r is not None]
        if tgt < self._slots and len(occ) > tgt:
            return          # shrink waits for occupancy, never evicts
        self._running = occ + [None] * (tgt - len(occ))
        self._slots = tgt

    def _admit_locked(self):
        """Sweep deadline-expired queued requests, then pop the FIFO
        head while (a) a slot is open, (b) the KV pool honors the
        worst-case block reservation, and (c) the live prefill-mode
        budget allows — ``interleave`` admits at most one per decode
        iteration, ``step`` fills every open slot."""
        now = time.monotonic()
        expired = [r for r in self._queue
                   if r.deadline is not None and r.deadline < now]
        if expired:
            self._queue = [r for r in self._queue if r not in expired]
        free = sum(1 for r in self._running if r is None)
        mode = str(get_env(PREFILL_MODE_ENV)).lower()
        budget = free if mode == "step" else min(free, 1)
        admit = []
        while budget > 0 and self._queue:
            head = self._queue[0]
            table = self._kv.reserve(head.rid, len(head.prompt),
                                     head.max_new_tokens)
            if table is None:
                break           # blocks exhausted: FIFO holds the line
            self._tables[head.rid] = table
            self._queue.pop(0)
            admit.append(head)
            budget -= 1
        self._g_depth.set(len(self._queue))
        return admit, expired

    # -- dispatch (hot path) -------------------------------------------
    @hot_path("dispatch")
    def _prefill(self, graph, req: GenRequest, bucket: int) -> None:
        """One prompt prefill (batch 1, padded to ``bucket``): scatters
        prompt K/V into the request's reserved blocks, emits the first
        token (the TTFT measurement point), and seats the request in a
        running-batch slot."""
        sp = None if req.trace is None else _tracing.tracer().begin(
            "serving.prefill", parent=req.trace, activate=False,
            args={"bucket": bucket})
        plen = len(req.prompt)
        try:
            # the prep is fallible too (ensure() asserts pool-table
            # agreement) — it must fail the request AND close the span,
            # exactly like a compiled-call failure
            table = self._kv.ensure(req.rid, plen)
            bs = self._kv.block_size
            toks = _np.zeros((1, bucket), _np.int32)  # mxlint: disable=hot-path-purity — per-prefill pad buffer, amortized over the prompt
            toks[0, :plen] = req.prompt
            tb = _np.asarray([table.padded(-(-bucket // bs))], _np.int32)  # mxlint: disable=hot-path-purity — per-prefill block-table row, amortized over the prompt
            req.t_prefill = time.monotonic()
            logits, pool = graph.raw(
                toks, _np.asarray([plen], _np.int32), tb, self._pool)  # mxlint: disable=hot-path-purity — per-prefill scalar wrap, amortized over the prompt
            self._pool = pool  # mxlint: disable=lock-discipline — scheduler-thread-owned; the lock-held writes happen in pre-start warmup
            tok = int(_np.asarray(logits)[0].argmax())  # mxlint: disable=hidden-host-sync,hot-path-purity — first-token readback: TTFT is measured on host arrival
        except BaseException as exc:
            if sp is not None:
                sp.annotate(error=type(exc).__name__)
                sp.finish()
            self._finish_gen(req, error=exc
                             if isinstance(exc, Exception) else
                             MXNetError(str(exc)))
            if not isinstance(exc, Exception):
                raise
            return
        req.t_first = time.monotonic()
        # close the span at the TTFT point: it measures the prefill
        # (prep + compiled call + first-token readback), and closing
        # before the fan-out bookkeeping means a failure there can no
        # longer strand it open
        if sp is not None:
            sp.finish()
        trace_id = None if req.trace is None else req.trace.trace_id
        self._h_ttft.observe((req.t_first - req.t_enqueue) * 1e6,
                             trace_id=trace_id)
        req.push_token(tok)
        req.pos = plen          # the new token decodes at position plen
        self._c_tokens.inc()
        if req.cancelled:
            self._finish_gen(req, error=RequestCancelled(
                f"generation {req.rid} cancelled mid-stream"))
            return
        if (req.eos is not None and tok == req.eos) \
                or len(req.tokens) >= req.max_new_tokens:
            self._finish_gen(req)
            return
        with self._lock:
            slot = self._running.index(None)
            self._running[slot] = req

    @hot_path("dispatch")
    def _decode_step(self, graph) -> None:
        """ONE iteration of the decode scheduler: a single compiled call
        advances every running slot by one token, then one batched
        logits readback fans results out — finished requests free their
        slot (and KV blocks) before the next iteration's admissions."""
        occupied = [(i, r) for i, r in enumerate(self._running)
                    if r is not None]
        sp = None
        for _, r in occupied:
            if r.trace is not None:
                sp = _tracing.tracer().begin(
                    "serving.decode_step", parent=r.trace,
                    activate=False,
                    args={"occupied": len(occupied),
                          "slots": self._slots})
                for _, o in occupied:
                    if o.trace is not None and o is not r:
                        sp.link(o.trace)
                break
        try:
            # reused per-slot-count assembly buffers (built with the
            # graph); zeroed every step so empty slots and table tails
            # land in the scratch block, never a live request's blocks.
            # Assembly is inside the try: a failed ensure() must fail
            # the batch AND close the step span like a compiled-call
            # failure would
            with _span("serving.decode_assemble", histogram=False):
                tokens, positions, tables = self._step_bufs[self._slots]
                tokens.fill(0)
                positions.fill(0)
                tables.fill(0)
                for i, r in occupied:
                    # lazy block growth: back the write position;
                    # infallible under the admission-time reservation
                    table = self._kv.ensure(r.rid, r.pos + 1)
                    tokens[i] = r.tokens[-1]
                    positions[i] = r.pos
                    tables[i, :] = table.padded(self._max_blocks)
            t0 = time.monotonic()
            with _span("serving.decode_dispatch", histogram=False):
                logits, pool = graph.raw(tokens, positions, tables,
                                         self._pool)
            self._pool = pool  # mxlint: disable=lock-discipline — scheduler-thread-owned; the lock-held writes happen in pre-start warmup
            with _span("serving.decode_readback", histogram=False):
                lg = _np.asarray(logits)  # mxlint: disable=hidden-host-sync,hot-path-purity — ONE batched logits readback per decode step (results are host tokens by contract)
        except BaseException as exc:
            if sp is not None:
                sp.annotate(error=type(exc).__name__)
                sp.finish()
            with self._lock:
                for i, _ in occupied:
                    self._running[i] = None
            for _, r in occupied:
                self._finish_gen(r, error=exc
                                 if isinstance(exc, Exception) else
                                 MXNetError(str(exc)))
            if not isinstance(exc, Exception):
                raise
            return
        trace_id = None if sp is None else sp.trace_id
        # the step span measures the compiled call + batched readback;
        # close it before the fan-out so a failure in per-request
        # bookkeeping can no longer strand it open
        if sp is not None:
            sp.finish()
        self._h_step.observe((time.monotonic() - t0) * 1e6,
                             trace_id=trace_id)
        self._c_steps.inc()
        finished = []
        for i, r in occupied:
            tok = int(lg[i].argmax())  # mxlint: disable=hidden-host-sync — lg is already host memory; this argmax is numpy, not a device round-trip
            r.push_token(tok)
            r.pos += 1
            self._c_tokens.inc()
            if (r.cancelled or (r.eos is not None and tok == r.eos)
                    or len(r.tokens) >= r.max_new_tokens):
                finished.append((i, r))
        if finished:
            with self._lock:
                for i, _ in finished:
                    self._running[i] = None
            for _, r in finished:
                self._finish_gen(r, error=RequestCancelled(
                    f"generation {r.rid} cancelled mid-stream")
                    if r.cancelled else None)

    # -- completion paths ----------------------------------------------
    def _finish_gen(self, req: GenRequest, error=None) -> None:
        """Every generation exit path lands here — finish, deadline,
        abort, dispatch failure — so KV blocks (and the unused tail of
        the reservation) can never leak."""
        self._kv.release(req.rid)
        with self._lock:
            self._tables.pop(req.rid, None)
        req.t_done = time.monotonic()
        req._error = error
        dur_us = (req.t_done - req.t_enqueue) * 1e6
        trace_id = None
        if req.trace is not None:
            trace_id = req.trace.trace_id
            if error is not None:
                req.trace.annotate(error=type(error).__name__)
            req.trace.annotate(tokens=len(req.tokens))
            req.trace.finish()
        if error is None:
            self._h_request.observe(dur_us, trace_id=trace_id)
            self._c_done.inc()
        self._flight.record_request(
            request_id=req.rid,
            enqueue=round(req.t_enqueue, 6),
            assemble=round(req.t_prefill, 6),
            dispatch=round(req.t_first, 6),
            done=round(req.t_done, 6),
            bucket=f"gen:{len(req.prompt)}+{len(req.tokens)}",
            batch_size=self._slots,
            us=round(dur_us, 1),
            trace_id=trace_id,
            ok=error is None)
        req._event.set()
        req._wake_stream()

    def _expire_gen(self, req: GenRequest) -> None:
        self._c_rej_deadline.inc()
        self._finish_gen(req, error=DeadlineExceeded(
            f"generation {req.rid} spent its deadline queued "
            f"(429-style); the server is over capacity — back off"))
