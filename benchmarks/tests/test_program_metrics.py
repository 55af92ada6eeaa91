"""The per-layer metrics that read the program's own spans and counters
(``mxnet_tpu.observability.registry``), each against a registry filled by
hand, and ``None`` where the program has counted nothing (as the commit
before the spans existed does).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import loader  # noqa: E402
from mxnet_tpu.observability.registry import registry  # noqa: E402

SPANS = {"host_h2d_ms.train": "trainer.h2d_us",
         "host_jit_call_ms.train": "trainer.jit_call_us"}
COUNTERS = {"train_step_build_s": "trainer.compile_call_s",
            "train_step_trace_lower_s": "trainer.trace_lower_s"}


@pytest.fixture
def reg():
    r = registry()
    r.reset("trainer.")
    yield r
    r.reset("trainer.")


@pytest.mark.parametrize("metric", sorted({**SPANS, **COUNTERS}))
def test_nothing_counted_reads_none(reg, metric):
    assert loader.load_module("metrics", metric).read(None) is None


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_span_metric_is_exact_total_over_count_in_ms(reg, metric):
    h = reg.histogram(SPANS[metric])
    for us in (1000.0, 2000.0, 6000.0):
        h.observe(us)
    # the mean of the three, not a bucket's bound: 3.0 ms exactly
    assert loader.load_module("metrics", metric).read(None) == 3.0


@pytest.mark.parametrize("metric", sorted(COUNTERS))
def test_counter_metric_reads_the_trainers_seconds(reg, metric):
    reg.counter(COUNTERS[metric]).inc(12.5)
    # seconds alone are not enough: a call has to have been counted
    assert loader.load_module("metrics", metric).read(None) is None
    reg.counter("trainer.compile_calls").inc()
    assert loader.load_module("metrics", metric).read(None) == 12.5


def test_every_new_metric_is_declared_with_its_cell():
    per_layer = {m["name"]: m for m in loader.benchmark()["per_layer"]}
    for name in {**SPANS, **COUNTERS}:
        assert per_layer[name]["workloads"] == ["bert_base.pretrain_s512"]
    assert {per_layer[n]["moves"] for n in SPANS} == {"train_samples_per_s"}
    assert {per_layer[n]["moves"] for n in COUNTERS} == {"setup_s"}
