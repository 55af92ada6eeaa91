"""Self-tuning runtime: feedback controllers + a persistent compile
cache.

The two halves of ROADMAP direction #4, closing the loops the
observability spine already measures:

- :mod:`.controllers` — a :class:`Controller` base (guard rails,
  hysteresis, dry-run, every decision recorded as ``tuning.*`` metrics
  and a flight-recorder tuning record) and four concrete controllers:
  :class:`~.controllers.BulkSizeController` (``MXNET_ENGINE_BULK_SIZE``
  from ``engine.flush_us``), :class:`~.controllers.PrefetchController`
  (loader prefetch depth from its queue gauge),
  :class:`~.controllers.BatchWindowController`
  (``MXTPU_SERVING_BATCH_WINDOW_US`` from the serving queue gauge +
  request p99), :class:`~.controllers.FleetGatherController`
  (timer-thread fleet metric gather over the barrier-free KV
  transport), :class:`~.controllers.DevicePrefetchController` (the
  loader's device double-buffer depth vs HBM from the
  ``loader.device_put_us`` jitter) and — constructed per live
  instance, not stock — :class:`~.controllers.CommBucketController`
  (``MXTPU_COMM_BUCKET_MB`` hill-climb on ``resilience.step_us``) and
  :class:`~.controllers.DecodeSlotController` (a GenerationServer's
  decode-slot width hill-climbed on interval tokens/s, with the same
  bracketing stop — every move is a recompile) and
  :class:`~.controllers.SloController` (per-model p99 SLO defense over
  the PR-18 frontend registry: shed lowest-priority-first, scale the
  violator's dispatch workers);
- :mod:`.compile_cache` — compiled executables (exact-mode bulk
  segments, HybridBlock cached graphs) serialized to
  ``MXTPU_COMPILE_CACHE_DIR`` and reloaded by later processes, so
  auto-resume and server cold starts skip the XLA compile.

All controllers share ONE daemon timer thread
(:class:`TuningRuntime`), ticking every ``MXTPU_TUNE_INTERVAL``
seconds.  Controllers are tick-driven and wall-clock-free inside, so
tests call ``controller.tick()`` / ``runtime().tick_all()`` directly
against synthetic metric streams.

Quick start::

    from mxnet_tpu import tuning
    tuning.start()               # standard controllers, knob-gated
    ...                          # train / serve; knobs now self-tune
    tuning.stop()

Knobs: ``MXTPU_TUNE_INTERVAL``, ``MXTPU_TUNE_DRY_RUN``,
``MXTPU_TUNE_BULK`` / ``_PREFETCH`` / ``_BATCH_WINDOW`` /
``_FLEET_GATHER`` / ``_DECODE_SLOTS``, ``MXTPU_COMPILE_CACHE_DIR``,
``MXTPU_COMPILE_CACHE_JAX`` (see the README knob table).
"""
from __future__ import annotations

import threading
import warnings
from typing import List, Optional

from ..base import get_env
from ..observability.registry import registry as _metrics_registry
from . import compile_cache
from .controllers import (BatchWindowController, BulkSizeController,
                          CommBucketController, Controller, CounterDelta,
                          DecodeSlotController, DevicePrefetchController,
                          FleetGatherController, HistogramDelta,
                          PrefetchController, SloController)

__all__ = ["TuningRuntime", "runtime", "standard_controllers", "start",
           "stop", "Controller", "BulkSizeController",
           "PrefetchController", "BatchWindowController",
           "FleetGatherController", "CommBucketController",
           "DecodeSlotController", "DevicePrefetchController",
           "SloController", "HistogramDelta", "CounterDelta",
           "compile_cache"]

INTERVAL_ENV = "MXTPU_TUNE_INTERVAL"


class TuningRuntime:
    """The shared controller timer: one daemon thread ticking every
    registered controller each ``MXTPU_TUNE_INTERVAL`` seconds (read
    live per lap, so the cadence can be retuned on a running process).

    A controller whose ``tick()`` raises is counted
    (``tuning.errors``), warned about once, and *kept* — one misbehaving
    loop must not silence the other three.  ``tick_all()`` is the
    synchronous entry tests drive directly."""

    def __init__(self):
        self._controllers: List[Controller] = []
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._warned: set = set()
        self._c_errors = _metrics_registry().counter(
            "tuning.errors",
            help="controller tick() exceptions (each warned once, "
                 "controller kept)")
        self._c_ticks = _metrics_registry().counter(
            "tuning.ticks", help="runtime timer-thread tick sweeps")

    # -- membership ----------------------------------------------------------
    def add(self, controller: Controller) -> Controller:
        with self._lock:
            self._controllers.append(controller)
        return controller

    def remove(self, controller: Controller) -> None:
        with self._lock:
            if controller in self._controllers:
                self._controllers.remove(controller)

    @property
    def controllers(self) -> List[Controller]:
        with self._lock:
            return list(self._controllers)

    # -- ticking -------------------------------------------------------------
    def tick_all(self) -> List[dict]:
        """One synchronous sweep over every controller; returns the
        non-None decision records (the timer thread discards them —
        they already landed in metrics + the flight ring)."""
        self._c_ticks.inc()
        out = []
        for c in self.controllers:
            try:
                d = c.tick()
            except Exception as e:   # noqa: BLE001 — one bad controller
                self._c_errors.inc()       # must not kill the sweep
                if c.name not in self._warned:
                    self._warned.add(c.name)
                    warnings.warn(
                        f"tuning controller {c.name!r} raised "
                        f"{type(e).__name__}: {e} (counted in "
                        f"tuning.errors; controller kept)",
                        RuntimeWarning, stacklevel=2)
                continue
            if d is not None:
                out.append(d)
        return out

    def _run(self) -> None:
        while True:
            interval = max(0.05, float(get_env(INTERVAL_ENV)))
            if self._stop.wait(interval):
                return
            self.tick_all()

    # -- lifecycle -----------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "TuningRuntime":
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="mxtpu-tuning", daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = 5.0) -> None:
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            self._stop.set()
            thread.join(timeout)


_runtime_lock = threading.Lock()
_runtime_inst: Optional[TuningRuntime] = None


def runtime() -> TuningRuntime:
    """THE process-global tuning runtime (analog of ``Engine.get()``)."""
    global _runtime_inst
    inst = _runtime_inst
    if inst is not None:
        return inst
    with _runtime_lock:
        if _runtime_inst is None:
            _runtime_inst = TuningRuntime()
        return _runtime_inst


def standard_controllers(**overrides) -> List[Controller]:
    """The stock controllers, each gated by its own
    ``MXTPU_TUNE_*`` enable knob (evaluated live at every tick, so a
    controller can be switched off on a running process).  Keyword
    overrides are forwarded per controller:
    ``standard_controllers(bulk_size={"vmax": 32})``."""
    return [
        BulkSizeController(**overrides.get("bulk_size", {})),
        PrefetchController(**overrides.get("prefetch", {})),
        BatchWindowController(**overrides.get("batch_window", {})),
        FleetGatherController(**overrides.get("fleet_gather", {})),
        DevicePrefetchController(**overrides.get("device_prefetch", {})),
        # CommBucketController is NOT stock: it needs a live
        # ShardedTrainer reference (apply rebuilds that trainer's jit)
        # — construct it with the trainer and runtime().add() it
    ]


def start(controllers: Optional[List[Controller]] = None,
          **overrides) -> TuningRuntime:
    """Convenience: register ``controllers`` (default: the stock set)
    on the global runtime and start its timer thread.  Also resolves
    the persistent compile cache from the env (``configure``), so one
    call arms both halves of the self-tuning runtime."""
    rt = runtime()
    if controllers is None:
        if not rt.controllers:
            controllers = standard_controllers(**overrides)
        elif overrides:
            # silently dropping caller-specified guard rails would
            # leave the OLD rails in force while the operator believes
            # the new ones are — say so
            warnings.warn(
                "tuning.start(): the runtime already has controllers "
                "registered; the given overrides were NOT applied — "
                "remove the existing controllers (runtime().remove) or "
                "pass controllers= explicitly", RuntimeWarning,
                stacklevel=2)
    for c in controllers or ():
        rt.add(c)
    compile_cache.active()        # wire the disk tier if the env asks
    return rt.start()


def stop(timeout: Optional[float] = 5.0) -> None:
    runtime().stop(timeout)
