"""``flash_attention_plain_tiles_pct.train`` reads the program's gauges
``kernels.flash_attention.tiles_plain`` / ``.tiles_visited`` (the forward
kernel last built), and nothing where the program has none (the parent of
the PR that brought them)."""
import importlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import loader  # noqa: E402

METRIC = "flash_attention_plain_tiles_pct.train"


@pytest.fixture
def reg():
    from mxnet_tpu.observability.registry import registry
    r = registry()
    r.reset("kernels.flash_attention.")
    yield r
    r.reset("kernels.flash_attention.")


def test_reads_nothing_without_the_gauges(reg):
    assert loader.load_module("metrics", METRIC).read(None) is None


@pytest.mark.parametrize("lq,d,causal,window,pct", [
    (16384, 128, True, 4096, 100.0 * 392 / 504),
    (8192, 256, True, 0, 100.0 * 240 / 272),
    (512, 64, False, 0, 0.0)],
    ids=["window_cell_window_block", "mla_cell", "bert_cell"])
def test_reads_the_share_of_the_forward_kernel_last_built(reg, lq, d, causal,
                                                          window, pct):
    fa = importlib.import_module("mxnet_tpu.kernels.flash_attention")
    fa._build_call.cache_clear()
    fa._build_call(1, lq, lq, d, causal, d ** -0.5, "float32", True,
                   window=window)
    assert loader.load_module("metrics", METRIC).read(None) == \
        pytest.approx(pct)


def test_declared_for_the_four_cells():
    bench = loader.benchmark()
    entry = loader.find(bench["per_layer"], METRIC, "metric")
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]][:4]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels", "train_samples_per_s", "program_counter")
