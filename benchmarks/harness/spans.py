"""The benchmark's own spans: host-clock durations kept in memory by name,
each also written into the profiler's trace (``TraceAnnotation``) so that an
idle gap on the device can be given an owner."""
import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.durations = {}          # name -> [seconds]

    @contextlib.contextmanager
    def span(self, name):
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations.setdefault(name, []).append(
                    time.perf_counter() - t0)

    def reset(self):
        self.durations = {}
