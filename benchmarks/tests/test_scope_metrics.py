"""The per-layer metrics that read device time by the program's scopes
(``benchmarks/harness/scope_times.py``: the traced run's self time by
instruction joined with ``mx.profiler.step_scopes()``), each against a
hand-made ``ctx.reduced["ops"]`` and a hand-made table; ``None`` without a
traced run, on a program without the table, and where the table is another
module's; ``train_step_temp_gb`` from a hand-set gauge.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import mxnet_tpu as mx  # noqa: E402
from benchmarks.harness import loader, scope_times  # noqa: E402
from mxnet_tpu.observability.registry import registry  # noqa: E402

BERT, GLM, OLMO, ST = (
    "bert_base.pretrain_s512", "glm47_flash_ep8.pretrain_s8k",
    "olmo_hybrid_7b_l4.pretrain_s2k", "smallthinker_21b_ep8.pretrain_s16k")
CELLS = {
    "flash_attention_bwd_device_ms.train": [BERT, GLM, OLMO, ST],
    "expert_layer_device_ms.train": [GLM, ST],
    "ffn_device_ms.train": [BERT, GLM, OLMO],
    "head_loss_device_ms.train": [BERT, GLM, OLMO, ST],
    "gdn_rule_device_ms.train": [OLMO],
    "remat_recompute_device_pct.train": [GLM, OLMO, ST],
    "unscoped_device_pct.train": [BERT, GLM, OLMO, ST],
    "train_step_temp_gb": [BERT, GLM, OLMO, ST],
}
LAYERS = {
    "flash_attention_bwd_device_ms.train": "kernels",
    "expert_layer_device_ms.train": "expert layer",
    "gdn_rule_device_ms.train": "linear attention",
}
TRACED = sorted(set(CELLS) - {"train_step_temp_gb"})

L = "lm/layer*/remat"
# instruction -> (table entry, self seconds over two traced steps)
STEP = {
    "flash_attention_bwd_dq.1":
        ((L + "/attn/flash_attention_bwd/flash_attention_bwd_dq", "bwd",
          False), 0.040),
    "fusion.7": ((L + "/attn/flash_attention_bwd", "bwd", False), 0.002),
    "flash_attention_fwd.1":
        ((L + "/attn/flash_attention_fwd", "fwd", False), 0.050),
    "fusion.11": ((L + "/moe/dispatch", "fwd", False), 0.010),
    "fusion.12": ((L + "/moe/dispatch", "recompute", False), 0.004),
    "ragged-dot-none.3": ((L + "/moe", "bwd", True), 0.020),
    "broadcast.9.clone": (("lm/mtp/remat/cell/moe/dispatch", "fwd", True),
                          0.006),
    "fusion.20": ((L + "/ffn/up", "fwd", False), 0.030),
    "fusion.21": ((L + "/ffn/up", "recompute", False), 0.010),
    "fusion.22": ((L + "/ffn_norm", "fwd", False), 0.003),
    "fusion.30": (("lm/head", "bwd", False), 0.012),
    "fusion.31": (("loss", "fwd", False), 0.004),
    "fusion.32": (("lm/final_norm", "fwd", False), 0.002),
    "fusion.33": (("bertmodel*/mlm_out", "fwd", False), 0.006),
    "fusion.40": ((L + "/gdn/state", "bwd", False), 0.008),
    "fusion.41": ((L + "/gdn/inverse", "recompute", False), 0.002),
    "fusion.42": ((L + "/gdn/conv", "fwd", False), 0.005),
    "fusion.43": ((L + "/gdn", "fwd", False), 0.001),
    "copy-done.5": (("", "fwd", False), 0.003),
    "fusion.50": (("optimizer", "fwd", False), 0.002),
}
BESIDE = {"fusion.split_key": 0.001}        # a small program beside the step
BUSY = sum(s for _, s in STEP.values()) + sum(BESIDE.values())
WANT = {
    "flash_attention_bwd_device_ms.train": 1e3 * 0.042 / 2,
    "expert_layer_device_ms.train": 1e3 * 0.040 / 2,
    "ffn_device_ms.train": 1e3 * 0.040 / 2,
    "head_loss_device_ms.train": 1e3 * 0.024 / 2,
    "gdn_rule_device_ms.train": 1e3 * 0.010 / 2,
    "remat_recompute_device_pct.train": 100 * 0.016 / BUSY,
    "unscoped_device_pct.train": 100 * 0.004 / BUSY,
}


def make_ctx(ops=None, traced=True):
    ops = {**{k: s for k, (_, s) in STEP.items()}, **BESIDE} \
        if ops is None else ops
    return types.SimpleNamespace(
        reduced={"ops": ops, "busy_s": sum(ops.values())} if traced
        else None,
        result={}, kind=types.SimpleNamespace(traced_batches=[0, 1]))


@pytest.fixture
def table(monkeypatch):
    entries = {k: e for k, (e, _) in STEP.items()}
    entries["fusion.never_ran"] = ("optimizer", "fwd", False)
    monkeypatch.setattr(mx.profiler, "step_scopes", lambda: entries)
    return entries


def read(metric, ctx):
    return loader.load_module("metrics", metric).read(ctx)


@pytest.mark.parametrize("metric", TRACED)
def test_metric_from_a_hand_made_trace_and_table(table, metric):
    assert read(metric, make_ctx()) == pytest.approx(WANT[metric])


def test_the_join_is_made_once_a_run_and_says_what_it_found(table):
    ctx = make_ctx()
    first = scope_times.read(ctx)
    assert scope_times.read(ctx) is first is ctx.scope_times
    assert first["covered"] == pytest.approx(1 - 0.001 / BUSY)
    assert first["steps"] == 2 and first["busy_s"] == pytest.approx(BUSY)
    assert first["inferred_s"] == pytest.approx(0.026)
    # without the inference the grouped matmul and the zero-fill were
    # nobody's either
    assert first["unstated_s"] == pytest.approx(0.030)
    notes = ctx.result["notes"]
    assert notes["scope_table_covered"] == first["covered"]
    assert notes["unscoped_before_inference_pct"] == \
        pytest.approx(100 * 0.030 / BUSY)
    assert notes["scope_table_instructions"] == len(table)
    assert notes["step_scopes_s"] >= 0.0
    assert first["seconds"][L + "/moe/dispatch", "recompute"] == 0.004
    assert scope_times.ms_a_step(ctx, "gdn/state", "gdn/conv") == \
        pytest.approx(1e3 * 0.013 / 2)
    assert scope_times.ms_a_step(ctx, "no_such_scope") is None


@pytest.mark.parametrize("metric", TRACED)
def test_none_without_a_traced_run(table, metric, capsys):
    ctx = make_ctx(traced=False)
    assert read(metric, ctx) is None
    assert read(metric, ctx) is None
    err = capsys.readouterr().err
    assert err.count("scope_times:") == 1 and "no traced run" in err


@pytest.mark.parametrize("metric", TRACED)
def test_none_on_a_program_without_the_table(monkeypatch, metric, capsys):
    monkeypatch.delattr(mx.profiler, "step_scopes")
    assert read(metric, make_ctx()) is None
    assert "has no mx.profiler.step_scopes" in capsys.readouterr().err


@pytest.mark.parametrize("metric", TRACED)
def test_none_where_no_step_was_compiled(monkeypatch, metric, capsys):
    monkeypatch.setattr(mx.profiler, "step_scopes", lambda: None)
    assert read(metric, make_ctx()) is None
    assert "no trainer compiled a step" in capsys.readouterr().err


@pytest.mark.parametrize("metric", TRACED)
def test_none_where_the_table_is_another_modules(table, metric, capsys):
    ops = {k: s for k, (_, s) in STEP.items()}
    ops["fusion.of_another_module"] = 0.06 * sum(ops.values())
    ctx = make_ctx(ops)
    assert read(metric, ctx) is None
    assert "another module's" in capsys.readouterr().err
    assert ctx.result["notes"]["scope_table_covered"] < 0.95


def test_a_reader_that_breaks_reads_none(monkeypatch, capsys):
    def broken():
        raise RuntimeError("no text")
    monkeypatch.setattr(mx.profiler, "step_scopes", broken)
    assert read("unscoped_device_pct.train", make_ctx()) is None
    assert "RuntimeError: no text" in capsys.readouterr().err


@pytest.mark.parametrize("metric,cells", [
    ("expert_layer_device_ms.train", {"fusion.20": 0.03}),
    ("gdn_rule_device_ms.train", {"fusion.42": 0.03}),
    ("remat_recompute_device_pct.train", {"fusion.20": 0.03}),
])
def test_none_in_a_model_without_the_layer(table, metric, cells):
    assert read(metric, make_ctx(cells)) is None


def test_train_step_temp_gb_reads_the_gauge():
    reg = registry()
    reg.reset("trainer.")
    try:
        assert read("train_step_temp_gb", None) is None
        reg.gauge("trainer.step_temp_bytes").set(7_610_007_552)
        assert read("train_step_temp_gb", None) == 7.610007552
    finally:
        reg.reset("trainer.")


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_every_new_metric_is_declared_with_exactly_its_cells(metric):
    entry = {m["name"]: m for m in loader.benchmark()["per_layer"]}[metric]
    assert entry["workloads"] == CELLS[metric]
    assert entry["moves"] == "train_samples_per_s"
    assert entry["better"] == "lower"
    assert entry["layer"] == LAYERS.get(metric, "compiled step")
    assert entry["source"] == ("program_counter" if metric ==
                               "train_step_temp_gb" else "device_trace")
    assert entry["unit"] == ("GB" if metric == "train_step_temp_gb" else
                             "%" if "_pct." in metric else "ms")
