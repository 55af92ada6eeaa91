"""Tests of the ``mhc_mla_moe_lm`` family's benchmark files (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mhc_mla_moe_lm.py -q

The family at a tiny size through ``run.py --rehearse``, the configuration
against the catalog's published one, its FLOP and byte counts against hand
counts, and ``correct`` false for each planted fault that can be seen and
for the bfloat16 control (the tiny file narrows the two vector limits to
what float32 on the CPU reads; the other limits are the cell's).
"""
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.flops import mhc_mla_moe_lm as flops  # noqa: E402
from benchmarks.harness import loader  # noqa: E402

CELL = "xing4_29b_ep8.pretrain_s4k"
TINY = os.path.join(HERE, "data", "tiny_mhc_mla_moe_lm.json")


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


def test_the_hyper_connection_metric_reads_nothing_without_a_trace():
    metric = loader.load_module("metrics", "hyper_connection_device_ms.train")
    assert metric.read(types.SimpleNamespace(reduced=None, result={})) \
        is None


# the catalog's config of Xing4.0-29B-A4B, key by key (the published
# config.json the configuration's source names)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}


def test_configuration_keeps_every_published_width():
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size",
                       "first_k_dense_replace"}
    assert set(cfg["reduced"]) == changed | {"n_routed_experts_held"}
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["n_routed_experts_held"] * 8 == PUBLISHED["n_routed_experts"]
    assert set(cfg["reduced"]) <= set(cfg["published"]) \
        and set(cfg["reduced"]) <= set(cfg["assumed"])
    assert "8 chips" in cfg["deployment"] and "34 sparse layers" in \
        cfg["deployment"]
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]


def test_parameters_held_are_the_count():
    """913,645,700 parameters, of which 913,645,380 train (10.96 GB at
    12 B) and 5 x 64 are the routers' fixed biases: the reference's leaves,
    which the program is given one by one."""
    import math
    from benchmarks.reference import mhc_mla_moe_lm as ref
    _, _, cfg = loader.cell_and_config(loader.benchmark(), CELL)
    sizes = {k: math.prod(shape) for k, shape in ref.spec(cfg).items()}
    assert sum(sizes.values()) == 913_645_700
    assert sum(sizes[k] for k in ref.buffers(cfg)) == 5 * 64
    assert sum(v for k, v in sizes.items() if "_hc_" in k) == 6 * 716_854
    assert sizes["mtp.eh_proj_w"] == 7168 * 3584


def test_flops_against_hand_counts():
    """One token, by hand, at the published widths: the GLM family's count
    of MLA, SwiGLU, the experts, the MTP module and the heads, and twelve
    hyper-connections."""
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    # norm 4 x 14,336; projection 2 x 14,336 x 24; pre-mix 2 x 14,336;
    # H X 2 x 16 x 3584; h_post y 2 x 14,336 and the add 14,336;
    # exp of 16 and 20 x 2 x (16 adds + 16 divisions)
    hc = 4 * 14336 + 2 * 14336 * 24 + 2 * 14336 + 2 * 16 * 3584 \
        + 3 * 14336 + 16 + 20 * 4 * 16
    assert flops.hc_flops_per_token(cfg) == hc
    assert flops.sublayers(cfg) == 12
    # q_down 3584 x 768, q_up 768 x 6144, kv_down 3584 x 576,
    # kv_up 512 x 8192, o 4096 x 3584
    mla = 2 * (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
               + 4096 * 3584)
    moe = 2 * 3584 * 64 + 1.5 * 6 * 3584 * 1024
    per_token = 6 * mla + 6 * 3584 * 9216 + 5 * moe + 2 * 2 * 3584 * 16384 \
        + 2 * 2 * 3584 * 3584 + 12 * hc
    core = 32 * (4096 * 4097 // 2) * 2 * 320
    want = 4096 * per_token + 6 * core
    assert flops.forward_flops(cfg, 1, 4096) == want
    assert flops.train_step(cfg, cell["traffic_params"], None) == 3 * want
    assert 15.3e12 < 3 * want < 15.6e12


def test_flash_calls_are_read_from_the_compiled_step(monkeypatch):
    """The forward kernel's calls a step are the instructions of that name
    in the step the trainer compiled, not an assumed count."""
    import mxnet_tpu as mx
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    table = {f"flash_attention_fwd.{i}": ("layer/mla", "fwd", False)
             for i in range(6)}
    table["fusion.2"] = ("layer0/attn_hc/hc/mix", "fwd", False)
    monkeypatch.setattr(mx.profiler, "step_scopes", lambda: table)
    ops, byts = flops.flash_fwd_per_step(cfg, cell["traffic_params"], None)
    assert ops == 6 * flops.attention_core_flops(cfg, 4096)
    assert byts == 6 * 4 * 4096 * 32 * (2 * 192 + 2 * 128)


def test_control_and_every_fault_are_not_correct():
    import control_mhc_mla_moe_lm as control
    rows = control.readings(CELL, [3, 2 ** 31 + 5], rehearse=TINY)
    names = [name for name, _ in control.planted()]
    assert len(names) == 14
    for row in rows:
        for who in names:
            if who in control.UNSEEN:
                assert row[who]["correct"] is True, who
                continue
            assert row[who]["correct"] is False, (who, row[who]["compared"])
