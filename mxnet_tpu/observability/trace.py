"""Lightweight trace spans over the metrics registry.

``span(name)`` is a context manager that records the wall-time of its
body (in µs) into the histogram ``name`` — the per-step / per-flush
timing surface the ROADMAP's observability follow-up asks for.  Two
integration points:

- **Registry**: every exit observes the duration into
  ``registry().histogram(name)``, so percentiles surface through
  ``snapshot()`` / the Prometheus endpoint with zero extra plumbing.
- **The profiler's clock**: every span is also a
  ``jax.profiler.TraceAnnotation("mx.<name>")``, so while ``jax.profiler``
  (or ``mx.profiler`` with ``profile_all``) is tracing, the span lies in
  the host plane of the ``.xplane.pb`` on the same clock as the device's
  operations, and an idle gap on the device can be given to the span
  that covers it.  With no profiler attached a ``TraceMe`` is one atomic
  flag read.

Spans nest as ``with`` blocks do, and the trace shows them nested; a span
body that raises still records its duration.

Cost discipline: entering a span is a perf_counter() call and the
annotation's flag read; exiting is a perf_counter() and one histogram
observe (bisect + int adds under a lock).  No formatting (the ``mx.``
name is built once, at construction).  Spans guard paths that run per
step / per flush / per batch — not per op; the op hot path keeps its
existing listener-gated timing.
"""
from __future__ import annotations

from time import perf_counter
from typing import List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from . import tracing as _tracing
from .registry import registry

__all__ = ["span", "add_span_listener", "remove_span_listener"]

# span sinks: fn(name, t_end_seconds, duration_us, args) called on
# every span exit (``args`` is the span's metadata dict or None).  The
# profiler installs one so spans land on its chrome-trace timeline as
# PROPER duration events (pid=host, tid=thread, chrome-trace ``args``
# carrying step/batch ids) next to op events.  Installing a span
# listener does NOT suspend bulked dispatch (spans wrap steps/flushes,
# not ops, so they need no per-op outputs).
_span_listeners: List = []


def add_span_listener(fn) -> None:
    """Install a span sink: ``fn(name, t_end, duration_us, args)`` with
    ``t_end`` in ``time.perf_counter()`` seconds and ``args`` the
    span's metadata dict (or None)."""
    if fn not in _span_listeners:
        _span_listeners.append(fn)


def remove_span_listener(fn) -> None:
    if fn in _span_listeners:
        _span_listeners.remove(fn)


class span:
    """``with span("resilience.step_us"): ...`` — record the body's
    wall-time into the histogram of that name.

    ``histogram=False`` keeps the nesting/bookkeeping (and the profiler's
    annotation) without creating a registry metric — for a site whose
    owner observes the duration itself, and for ad-hoc scoping.
    The measured duration is available afterwards as ``.duration_us``.

    ``args`` is an optional metadata dict (step number, batch id, ...):
    it never touches the histogram (labels would explode cardinality)
    but rides to span listeners and onto the annotation, so both the
    chrome trace and the ``.xplane.pb`` say WHICH step it was.  Cost:
    one attribute store when unused.
    """

    __slots__ = ("name", "duration_us", "args", "t_end", "_t0",
                 "_record", "_ann")

    def __init__(self, name: str, histogram: bool = True,
                 args: Optional[dict] = None):
        self.name = name
        self.duration_us = 0.0
        self.t_end = 0.0
        self.args = args
        self._record = histogram
        # create (or fetch) the histogram at construction, not exit —
        # name errors surface where the span is written, and __exit__
        # stays allocation-free
        if histogram:
            registry().histogram(name)
        self._ann = _TraceAnnotation("mx." + name, **(args or {}))

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t_end = self.t_end = perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        self.duration_us = (t_end - self._t0) * 1e6
        if self._record:
            registry().get(self.name).observe(self.duration_us)
        # causal tracing: inside a traced region (an active tracing
        # context) every measured span ALSO lands in the trace as a
        # child — the jit step, checkpoint commit, and collective spans
        # join the step trace with zero call-site changes.  Idle cost:
        # one ContextVar.get.
        _tracing.record_child(self.name, t_end, self.duration_us,
                              self.args)
        for fn in _span_listeners:
            # the profiler's timeline sink: proper duration events with
            # real start/end timestamps on the host/thread lanes (and
            # the span's args as chrome-trace event args)
            fn(self.name, t_end, self.duration_us, self.args)
        return None
