"""Tests of the ``gdn_hybrid_lm`` family's benchmark files (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_gdn_hybrid_lm.py -q

The family at a tiny size through ``run.py --rehearse``, its FLOP and byte
counts against hand counts, and ``correct`` false for each planted fault
and for the bfloat16 control (the tiny file narrows the two vector limits
to what float32 on the CPU reads; the other limits are the cell's).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.flops import gdn_hybrid_lm as flops  # noqa: E402
from benchmarks.harness import loader  # noqa: E402

CELL = "olmo_hybrid_7b_l4.pretrain_s2k"
TINY = os.path.join(HERE, "data", "tiny_gdn_hybrid_lm.json")


def rehearse(seed, faults=None):
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", "1", "--trace", "0",
                            "--rehearse", TINY])
    return bench_run.run(args, faults=faults)


def test_rehearsal_is_correct_and_reads_far_under_every_limit():
    line = rehearse(2 ** 31 + 13)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["notes"]["compiles_in_window"] == 0
    for name, c in line["compared"].items():
        assert c["value"] <= 0.1 * c["limit"], name


def test_the_metric_reads_the_programs_gauge():
    """Two chunks of 64 for 80 tokens, three delta-rule layers, forward,
    recomputed and backward: 18 dependent iterations a step; nothing where
    the program has no such gauge."""
    import types
    from mxnet_tpu.observability.registry import registry
    rehearse(5)
    metric = loader.load_module("metrics", "gdn_scan_steps.train")
    assert metric.read(types.SimpleNamespace()) == 3 * 2 * 3
    registry().gauge("gdn.scan_steps").set(0)
    assert metric.read(types.SimpleNamespace()) is None


# the source's config.json, key by key (ISSUE 34 quotes it); of its
# ``layer_types`` the period, which the source repeats eight times
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def test_configuration_keeps_every_published_width():
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert set(cfg["reduced"]) == changed | {"layer_types"}
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 4
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 32 \
        and cfg["published"]["vocab_size"] == 100352
    assert set(cfg["reduced"]) <= set(cfg["published"]) \
        and set(cfg["reduced"]) <= set(cfg["assumed"])
    # one line of at most 200 characters each, or the file is refused
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]


def test_flops_against_hand_counts():
    """One token, one block of each kind, by hand, at the published
    widths."""
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    # q and k 3840x2880 each, v, the gate and the output 3840x5760 each, a
    # and b 3840x30 each: 88,704,000 multiply-adds
    assert flops.gdn_projection_flops_per_token(cfg) == 2 * 88_704_000
    # three convolutions of 4 taps over 2880 + 2880 + 5760 lanes
    assert flops.gdn_conv_flops_per_token(cfg) == 2 * 4 * 11_520
    # the recurrence: 7 operations an entry of 30 states of 96 x 192
    assert flops.delta_rule_flops_per_token(cfg) == 7 * 30 * 96 * 192
    assert flops.attention_projection_flops_per_token(cfg) == \
        2 * 4 * 3840 * 3840
    # one sequence of 2048, one block: 2048x2049/2 pairs x (2 x 3840 for
    # Q K^T + 2 x 3840 for P V)
    assert flops.attention_core_flops(cfg, 2048) == \
        (2048 * 2049 // 2) * 4 * 3840
    per_token = 3 * (2 * 88_704_000 + 92_160 + 3_870_720) \
        + 2 * 4 * 3840 * 3840 + 4 * 6 * 3840 * 11008 + 2 * 3840 * 12544
    assert per_token == 1_772_912_640
    want = 2048 * per_token + (2048 * 2049 // 2) * 4 * 3840
    assert flops.forward_flops(cfg, 1, 2048) == want
    assert flops.train_step(cfg, cell["traffic_params"], None) == 3 * want
    assert 10.98e12 < 3 * want < 11.0e12
    # the kernel: one call a step (one attention block, its output kept
    # across the checkpoint), Q, K, V read and the output written once
    ops, byts = flops.flash_fwd_per_step(cfg, cell["traffic_params"], None)
    assert ops == (2048 * 2049 // 2) * 4 * 3840
    assert byts == 4 * 2048 * 30 * 128 * 4


def test_control_and_every_fault_are_not_correct():
    import control_gdn_hybrid_lm as control
    rows = control.readings(CELL, [3, 2 ** 31 + 5], rehearse=TINY)
    names = [name for name, _ in control.planted()]
    assert len(names) == 11
    for row in rows:
        for who in names:
            assert row[who]["correct"] is False, (who, row[who]["compared"])
