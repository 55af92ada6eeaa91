"""Tests of the ``swa_moe_lm`` family's benchmark files (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_swa_moe_lm.py -q

The family at a tiny size through ``run.py --rehearse``, its FLOP and byte
counts against hand counts (the window's pairs among them), the three
metrics of the window build, and ``correct`` false for each planted fault
and for the bfloat16 control (the tiny file narrows the two vector limits
to what float32 on the CPU reads; the other limits are the cell's).
"""
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.flops import swa_moe_lm as flops  # noqa: E402
from benchmarks.harness import loader  # noqa: E402

CELL = "smallthinker_21b_ep8.pretrain_s16k"
TINY = os.path.join(HERE, "data", "tiny_swa_moe_lm.json")


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


# the catalog's ``config`` for the source, key by key; of the two layouts
# the period, which the source repeats thirteen times
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1], "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


def test_configuration_keeps_every_published_width():
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert set(cfg["reduced"]) == changed | {
        "n_routed_experts_held", "sliding_window_layout", "rope_layout"}
    assert cfg["num_hidden_layers"] == len(cfg["sliding_window_layout"]) \
        == len(cfg["rope_layout"]) == 4
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["n_routed_experts_held"] * 8 == cfg["moe_num_primary_experts"]
    assert set(cfg["reduced"]) <= set(cfg["published"]) \
        and set(cfg["reduced"]) <= set(cfg["assumed"])
    entry = loader.find(bench["configs"], cfg["name"], "configuration")
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"]
    # one line of at most 200 characters each, or the file is refused
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    assert cell["why"] == loader.find(bench["workloads"], CELL,
                                      "workload")["why"]


def test_flops_against_hand_counts():
    """One token, one block, by hand, at the published widths; the window's
    pairs exactly."""
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    # q and the output 2560 x 3584 each, k and v 2560 x 512 each
    assert flops.attention_projection_flops_per_token(cfg) == \
        2 * (2 * 9_175_040 + 2 * 1_310_720)
    # router 2 x 2560 x 64; 6 x 8 / 64 = 0.75 experts of 3 x 2560 x 768
    assert flops.expert_layer_flops_per_token(cfg) == \
        2 * 163_840 + 0.75 * 2 * 5_898_240
    # a full block weighs 16384 x 16385 / 2 pairs a head; a window block
    # the first 4096 queries' triangle and 4096 keys for each of the rest
    assert flops.attention_pairs(16384) == 134_225_920
    assert flops.attention_pairs(16384, 4096) == \
        4096 * 4097 // 2 + 12288 * 4096 == 58_722_304
    assert flops.attention_pairs(16384, 16384) == 134_225_920
    assert flops.attention_pairs(5, 2) == 1 + 2 + 2 + 2 + 2
    # 28 heads x (2 x 128 for Q K^T + 2 x 128 for P V) a pair
    assert flops.attention_core_flops(cfg, 16384, 4096) == \
        28 * 58_722_304 * 512
    per_token = 4 * (2 * 20_971_520 + 2 * 163_840 + 0.75 * 2 * 5_898_240) \
        + 2 * 2560 * 18992
    assert 301.6e6 < per_token < 301.8e6
    core = 28 * 512 * (134_225_920 + 3 * 58_722_304)
    assert 4.44e12 < core < 4.46e12
    want = 16384 * per_token + core
    assert flops.forward_flops(cfg, 1, 16384) == want
    assert flops.train_step(cfg, cell["traffic_params"], None) == 3 * want
    assert 28.1e12 < 3 * want < 28.3e12
    # the kernels: one forward call a block (the output is kept across the
    # checkpoint); q and the output 3584 lanes wide, k and v 512
    ops, byts = flops.flash_fwd_per_step(cfg, cell["traffic_params"], None)
    assert ops == core
    assert byts == 4 * 4 * 16384 * (2 * 3584 + 2 * 512)
    ops, byts = flops.flash_window_fwd_per_step(
        cfg, cell["traffic_params"], None)
    assert ops == 3 * 28 * 58_722_304 * 512
    assert byts == 3 * 4 * 16384 * (2 * 3584 + 2 * 512)
    ops, byts = flops.flash_window_bwd_per_step(
        cfg, cell["traffic_params"], None)
    assert ops == 3 * 28 * 58_722_304 * 1280
    assert byts == 3 * 4 * 16384 * (3 * 3584 + 4 * 512)


def test_the_window_metrics_read_names_and_gauges():
    """The two shares read the window build's names in a reduced trace
    against the window blocks' work alone; the tiles' share reads the
    program's gauges; each returns nothing where there is nothing to
    read."""
    from mxnet_tpu.observability.registry import registry
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    fwd, bwd, tiles = (loader.load_module("metrics", name) for name in (
        "flash_attention_window_fwd_roofline",
        "flash_attention_window_bwd_roofline",
        "flash_attention_window_key_tiles_pct.train"))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = types.SimpleNamespace(
        cfg=cfg, cell=cell, flops=flops, peaks=peaks,
        kind=types.SimpleNamespace(traced_batches=[None, None]),
        reduced={"ops": {"jvp_flash_attention_fwd_window_.1": 0.1,
                         "jvp_flash_attention_fwd_.1": 0.7,
                         "flash_attention_bwd_dq_window.1": 0.15,
                         "flash_attention_bwd_dkv_window.1": 0.25,
                         "flash_attention_bwd_dq.1": 0.9}})
    window_ops = 3 * 28 * 58_722_304 * 512
    assert abs(fwd.read(ctx) - 100 * 2 * window_ops / 197e12 / 0.1) < 1e-9
    assert abs(bwd.read(ctx)
               - 100 * 2 * 2.5 * window_ops / 197e12 / 0.4) < 1e-9
    ctx.reduced = {"ops": {"jvp_flash_attention_fwd_.1": 0.7}}
    assert fwd.read(ctx) is None and bwd.read(ctx) is None
    ctx.reduced = None
    assert fwd.read(ctx) is None and bwd.read(ctx) is None
    # a family whose flops module knows no window build
    from benchmarks.flops import gdn_hybrid_lm
    ctx.flops, ctx.reduced = gdn_hybrid_lm, {"ops": {
        "flash_attention_fwd_window": 1.0}}
    assert fwd.read(ctx) is None and bwd.read(ctx) is None

    reg = registry()
    reg.gauge("kernels.flash_attention.key_tiles").set(504)
    reg.gauge("kernels.flash_attention.key_tiles_causal").set(1056)
    assert abs(tiles.read(ctx) - 100 * 504 / 1056) < 1e-9
    reg.gauge("kernels.flash_attention.key_tiles_causal").set(0)
    assert tiles.read(ctx) is None


def test_control_and_every_fault_are_not_correct():
    import control_swa_moe_lm as control
    rows = control.readings(CELL, [3, 2 ** 31 + 5], rehearse=TINY)
    names = [name for name, _ in control.planted()]
    assert len(names) == 12
    for row in rows:
        for who in names:
            assert row[who]["correct"] is False, (who, row[who]["compared"])
