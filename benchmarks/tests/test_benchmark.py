"""Tests of the benchmark itself.  Run by hand with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of the repo's tier-1 tests.  Each family goes at a tiny
size through the same ``models/``, ``traffic/`` and ``reference/`` code as a
chip run, in the rehearsal mode that the driver's four flags cannot reach
(``--rehearse FILE``), which prints no metric.
"""
import json
import math
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import compare, loader, trace_reduce  # noqa: E402

TINY = {"bert_base.pretrain_s512": os.path.join(HERE, "data", "tiny_bert.json")}


def rehearse(workload, seed=5, seconds=1.0, trace=0, faults=None):
    args = bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", str(trace),
                            "--rehearse", TINY[workload]])
    return bench_run.run(args, faults=faults)


# -- each family, end to end, at a tiny size ---------------------------------

@pytest.mark.parametrize("workload", sorted(TINY))
def test_rehearsal_is_correct_and_prints_no_metric(workload):
    line = rehearse(workload, seed=2 ** 31 + 11)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}                  # no device metric from a CPU
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert line["notes"]["compiles_in_window"] == 0


def test_every_cell_and_metric_is_found_by_name():
    bench = loader.benchmark()
    for w in bench["workloads"]:
        entry, cell, cfg = loader.cell_and_config(bench, w["name"])
        assert cell["name"] == w["name"] and cell["chips"] == w["chips"]
        assert cell["why"] == w["why"]
        loader.load_module("traffic", cell["kind"])
        for folder in ("models", "reference", "flops"):
            loader.load_module(folder, cfg["family"])
    for m in bench["per_layer"]:
        assert callable(loader.load_module("metrics", m["name"]).read)


def test_no_chip_means_no_result(capsys):
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--workload", "bert_base.pretrain_s512", "--seed",
                        "1", "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


# -- the generators give the same traffic for the same seed -----------------

def test_train_batches_same_seed_same_rows_all_rows_differ():
    from benchmarks.models import bert
    cell = loader.load_json(loader.BENCH, "workloads",
                            "bert_base.pretrain_s512.json")
    cfg = loader.load_json(loader.BENCH, "configs", "bert_base.json")
    tp = dict(cell["traffic_params"], batch=4, host_batches=3, seq=64,
              valid_len_min=8, valid_len_max=64, valid_len_median=32)
    a, b = bert.make_batches(cfg, tp, 9), bert.make_batches(cfg, tp, 9)
    c = bert.make_batches(cfg, tp, 10)
    for (xa, ya), (xb, yb) in zip(a, b):
        assert all(np.array_equal(u, v) for u, v in zip(xa + ya, xb + yb))
    rows = np.concatenate([x[0] for x, _ in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    lens = lambda s: sorted(np.concatenate([x[2] for x, _ in s]).tolist())
    assert lens(a) == lens(c) == sorted(bert.lengths(tp).tolist())
    for x, y in a:
        assert (y[1].sum(axis=1) >= 1).all()            # a label in each row
        assert (y[1] * (np.arange(64)[None] >= x[2][:, None])).sum() == 0


# -- operations and bytes against hand counts --------------------------------

def test_flops_against_hand_counts_for_one_layer():
    from benchmarks.flops import bert as fb
    from benchmarks.flops import transformer as ft
    u, h = 768, 3072
    # QKV 768x2304, proj 768x768, FFN 768x3072 and back; 2 ops a multiply-add
    assert ft.layer_matmul_flops_per_token(u, h) == \
        2 * (768 * 2304 + 768 * 768 + 768 * 3072 + 3072 * 768) == 14155776
    assert ft.attention_flops(512, 512, u) == 2 * 2 * 512 * 512 * 768
    assert ft.attention_fwd_bytes(512, 100, u, 4) == (512 * 2 + 100 * 2) * 768 * 4
    cfg = loader.load_json(loader.BENCH, "configs", "bert_base.json")
    one = dict(cfg, num_hidden_layers=1)
    per_token = 14155776 + 2 * 768 * 768 + 2 * 768 * 30522
    per_row = 2 * 768 * 768 + 4 * 768
    # every query row against the row's valid keys only, as the kernel has it
    assert fb.forward_flops(one, 512, [512, 64]) == \
        2 * (512 * per_token + per_row) + 4 * 512 * (512 + 64) * 768
    tp = {"batch": 16, "seq": 512}
    full = ((None, None, np.full(16, 512.0)), None)
    step = fb.train_step(cfg, tp, full)
    assert step == 3 * fb.forward_flops(cfg, 512, [512] * 16)
    assert 5.7e12 < step < 5.9e12                      # ISSUE: about 5.8 TFLOP
    some = ((None, None, np.array([512.0, 64.0] * 8)), None)
    assert step - fb.train_step(cfg, tp, some) == \
        3 * 12 * 8 * 4 * 512 * (512 - 64) * 768
    batch = ((None, None, np.array([512.0, 64.0])), None)
    ops, byts = fb.flash_fwd_per_step(one, tp, batch)
    assert ops == 4 * 512 * (512 + 64) * 768
    assert byts == (4 * 512 + 2 * (512 + 64)) * 768 * 4


# -- the reduction from the trace, on a trace recorded on the chip ----------

TRACE = os.path.join(HERE, "data", "bert_step_v5e.trace.json")


def test_trace_reduce_on_a_trace_recorded_on_the_chip():
    with open(TRACE) as f:
        rec = json.load(f)
    planes = [(p, [(l, [tuple(e) for e in evs]) for l, evs in lines])
              for p, lines in rec["planes"]]
    red = trace_reduce.reduce(planes, rec["window_s"])
    assert red["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    # self times add up to the busy time: nothing is counted twice
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-6)
    secs, n = trace_reduce.kernel_seconds(red, "flash_attention_fwd")
    assert n == rec["expect"]["flash_kernels"]
    assert secs == pytest.approx(rec["expect"]["flash_seconds"], rel=1e-9)
    assert trace_reduce.kernel_seconds(red, "no_such_kernel") == (None, 0)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    # two steps enqueued back to back: the chip never waits for the host
    assert all(k.startswith(("host:", "device:")) for k, _ in red["idle_gaps"])
    assert sum(v for _, v in red["idle_gaps"]) < 1e-3


def test_trace_reduce_small_cases():
    ev = [("%while.1 = x while(...)", 0.0, 100.0),
          ("%fusion.1 = x fusion(...)", 10.0, 30.0),
          ("%fusion.2 = x fusion(%fusion.1)", 50.0, 20.0),
          ("%copy.3 = x copy(...)", 200.0, 50.0)]
    planes = [("/device:TPU:0", [("XLA Ops", ev)]),
              ("/host:CPU", [("main", [("bench.step", 90.0, 120.0)])])]
    red = trace_reduce.reduce(planes, 1.0)
    assert red["busy_s"] == pytest.approx(150e-9)
    assert red["ops"]["while.1"] == pytest.approx(50e-9)
    assert red["ops"]["fusion.1"] == pytest.approx(30e-9)
    assert trace_reduce.kernel_seconds(red, "fusion.1") == \
        (pytest.approx(30e-9), 1)                    # operands do not match
    assert red["idle_gaps"] == [["device:between_ops_under_20us",
                                 pytest.approx(100e-9)]]
    assert trace_reduce.reduce([("/host:CPU", [])], 1.0) is None


# -- correct comes out false where the timed path is broken -----------------
# Each fault is planted in the timed object itself (the ``Trainer`` that
# set-up hands to the window), before its first step; the run's own readers
# and its own comparison then have to see it.

def _state_unchanged(kind):
    """A step that returns its state unchanged: the weights on the device
    are put back after every step as they were before it."""
    import jax
    import jax.numpy as jnp
    tr, step = kind.trainer, kind.trainer.step

    def broken(batch):
        # copies, since the step donates the weights; the trainer takes
        # them onto the device in its first step, from the seed's
        before = [jnp.copy(v) for v in getattr(tr.tr, "_pvals", ())]
        loss = step(batch)
        if not before:
            w0 = kind.ctx.reference.init_weights(kind.ctx.cfg, kind.ctx.seed)
            before = [jax.device_put(w0[k], v.sharding) for k, v in
                      zip(tr._leaf_names(), tr.tr._pvals)]
        tr.tr._pvals = before
        return loss
    tr.step = broken


def _half_batch_left_out(kind):
    """Half of the batch left out, the mean taken over the rest."""
    tr, step = kind.trainer, kind.trainer.step

    def broken(batch):
        x, y = batch
        half = x[0].shape[0] // 2
        return step((tuple(a[:half] for a in x), tuple(a[:half] for a in y)))
    tr.step = broken


@pytest.mark.parametrize("fault,fails", [
    (_state_unchanged, ["change_norm_worst_leaf"]),
    (_half_batch_left_out, ["grad_norm_worst_leaf",
                            "grad_vector_error.mlm_out_b",
                            "grad_vector_error.mlm_dense_w"]),
])
def test_a_broken_timed_path_is_not_correct(fault, fails):
    # the limits of the cell as committed hold at the tiny size too
    line = rehearse("bert_base.pretrain_s512", seed=77, faults=fault)
    assert line["correct"] is False
    for name in fails:
        c = line["compared"][name]
        assert not c["value"] <= c["limit"], name
    assert line["notes"]["compiles_in_window"] == 0


def test_a_sound_run_reads_far_under_every_limit():
    line = rehearse("bert_base.pretrain_s512", seed=78)
    assert line["correct"] is True
    for name, c in line["compared"].items():
        assert c["value"] <= 0.1 * c["limit"], name


# -- the control comes out as not correct ------------------------------------

def test_training_control_and_faults_are_not_correct():
    sys.path.insert(0, HERE)
    import control_train
    rows = control_train.readings("bert_base.pretrain_s512", [3, 4, 2 ** 31 + 5],
                                  rehearse=TINY["bert_base.pretrain_s512"])
    for row in rows:
        for who, _ in control_train.PLANTED:
            assert row[who]["correct"] is False, (who, row[who]["compared"])
        # the control fails the number it was added for, by the verdict's rule
        v, lim = row["control_bfloat16"]["compared"][
            "grad_vector_error.mlm_out_b"]
        assert v > lim


def test_compare_verdict_and_worst_leaf():
    got = {"a": 1.0, "b": 2.0, "c": 1e-9}
    want = {"a": 1.0, "b": 1.0, "c": 0.0}
    worst, where = compare._gap_by_worst_leaf(got, want)
    assert where["worst"] == "b" and worst == pytest.approx(1.0)
    assert compare.verdict({"x": [0.1, 0.2]})
    assert not compare.verdict({"x": [0.3, 0.2]})
    assert not compare.verdict({"x": [math.nan, 0.2]})
