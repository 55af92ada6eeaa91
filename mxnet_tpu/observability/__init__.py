"""Unified observability: ONE pull-based metrics surface for the stack.

PR 1 (resilience) and PR 2 (bulked dispatch) each grew an ad-hoc counter
dict (``ResilientTrainer.counters``, ``engine().stats()``); this package
merges them — and every future metric — into a single process-global
registry (ROADMAP follow-up for both PRs):

- :mod:`.registry` — thread-safe ``Counter`` / ``Gauge`` / ``Histogram``
  primitives under namespaced names (``engine.ops_dispatched``,
  ``resilience.steps_skipped``, ``loader.batches``) with one
  ``registry().snapshot()`` returning every metric in one dict.
- :mod:`.trace` — lightweight ``span(name)`` context managers recording
  wall-time into histograms, each also a ``jax.profiler.TraceAnnotation``
  (``mx.<name>``) on the profiler's clock.
- :mod:`.export` — a Prometheus-text-format HTTP endpoint (opt-in via
  ``MXTPU_METRICS_PORT``; ``MXTPU_METRICS_AGGREGATE`` serves the
  host-labeled fleet view) and a JSONL periodic writer for headless
  runs (``MXTPU_METRICS_JSONL``).
- :mod:`.flight` — a crash flight recorder: a bounded ring of per-step
  records dumped (with a full snapshot) to JSON on unhandled exception
  / preemption / retry exhaustion (``MXTPU_FLIGHT_STEPS`` /
  ``MXTPU_FLIGHT_PATH``).
- :mod:`.sampler` — live introspection half 1: a continuous
  stack-sampling profiler (``MXTPU_PROF_SAMPLE_HZ``) folding all-thread
  stacks into collapsed/flamegraph counts in rotating windows, plus
  on-demand ``thread_stacks()``/``profile()`` for the ``/debug/*``
  endpoints (served by the HttpFrontend and the metrics exporter,
  gated on ``MXTPU_DEBUG_ENDPOINTS``).
- :mod:`.watchdog` — live introspection half 2: heartbeat touchpoints
  in the trainer/serving progress loops, a monitor that flags a
  touchpoint silent past ``MXTPU_WATCHDOG_FACTOR`` × its recent p99
  interval, and a one-shot hang-postmortem bundle (stacks + flight
  rings + span ring + profile window); plus the ``MXTPU_STACKS_SIGNAL``
  (SIGQUIT) manual stack-dump probe.

The fleet view: ``registry().snapshot(all_hosts=True)`` gathers every
host's metrics over the DCN ``allgather_host`` path and merges them
with ``host=<process_index>`` labels (local-only fallback when the
process group is not initialized).

The legacy surfaces stay as thin back-compat views: ``engine().stats()``
and ``ResilientTrainer.counters`` read the same registry metrics.

Import discipline: this ``__init__`` eagerly exposes only the
dependency-free :mod:`.registry` (the engine imports it at module load);
:mod:`.trace` and :mod:`.export` load lazily because they import the
engine back — eager imports here would cycle.
"""
from __future__ import annotations

from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       registry)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
           "trace", "export", "span", "flight", "tracing", "sampler",
           "watchdog"]


def __getattr__(name):
    # importlib, not `from . import X`: the latter re-enters this
    # __getattr__ while the attribute is still unbound and recurses
    import importlib
    if name in ("trace", "span"):
        mod = importlib.import_module(".trace", __name__)
        return mod if name == "trace" else mod.span
    if name in ("export", "flight", "tracing", "sampler", "watchdog"):
        return importlib.import_module("." + name, __name__)
    raise AttributeError(
        f"module 'mxnet_tpu.observability' has no attribute {name!r}")
