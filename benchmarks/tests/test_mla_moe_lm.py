"""Tests of the ``mla_moe_lm`` family's benchmark files (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mla_moe_lm.py -q

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
from benchmarks.flops import mla_moe_lm as flops  # noqa: E402
from benchmarks.harness import loader  # noqa: E402

CELL = "glm47_flash_ep8.pretrain_s8k"
TINY = os.path.join(HERE, "data", "tiny_mla_moe_lm.json")


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


def test_batches_are_full_rows_from_the_seed():
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL, TINY)
    model = loader.load_module("models", cfg["family"])
    a, b, c = (model.make_batches(cfg, cell["traffic_params"], s)
               for s in (7, 7, 2 ** 31 + 7))
    assert len(a) == cell["traffic_params"]["host_batches"]
    for (xa, ya), (xb, _), (xc, _) in zip(a, b, c):
        assert (xa[0] == xb[0]).all() and (xa[0] != xc[0]).any()
        assert xa[0].shape == (1, cell["traffic_params"]["seq"])
        assert (ya == xa[0]).all()
        assert 0 <= xa[0].min() and xa[0].max() < cfg["vocab_size"]


def test_flops_against_hand_counts():
    """One token, one block, by hand, at the published widths."""
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, CELL)
    # MLA projections: 2048x768 + 768x(20x256) + 2048x576 + 512x(20x448)
    # + (20x256)x2048 = 21,757,952 multiply-adds
    assert flops.mla_projection_flops_per_token(cfg) == 2 * 21_757_952
    # one sequence of 8192, one block: 20 heads x 8192x8193/2 pairs x
    # (2 x 256 for Q K^T + 2 x 256 for P V)
    assert flops.attention_core_flops(cfg, 8192) == \
        20 * (8192 * 8193 // 2) * 1024
    # expert layer, a token: router 2x2048x64; the shared expert and
    # 4 x 8/64 = 0.5 routed experts of 3 x 2048 x 1536 multiply-adds
    assert flops.expert_layer_flops_per_token(cfg) == \
        2 * 2048 * 64 + 1.5 * 2 * 3 * 2048 * 1536
    # the whole forward, a token: 6 blocks of projections, the dense FFN,
    # 5 expert layers, two heads over 19360, eh_proj; then attention
    per_token = 6 * 2 * 21_757_952 + 2 * 3 * 2048 * 10240 \
        + 5 * (2 * 2048 * 64 + 1.5 * 2 * 3 * 2048 * 1536) \
        + 2 * 2 * 2048 * 19360 + 2 * 4096 * 2048
    want = 8192 * per_token + 6 * 20 * (8192 * 8193 // 2) * 1024
    assert flops.forward_flops(cfg, 1, 8192) == want
    assert flops.train_step(cfg, cell["traffic_params"], None) == 3 * want
    assert 29.6e12 < 3 * want < 29.8e12
    # the kernel: twelve calls a step (six blocks, each again under
    # rematerialisation), Q, K, V read and the output written once
    ops, byts = flops.flash_fwd_per_step(cfg, cell["traffic_params"], None)
    assert ops == 12 * 20 * (8192 * 8193 // 2) * 1024
    assert byts == 12 * 4 * 8192 * 20 * 256 * 4


def test_control_and_every_fault_are_not_correct():
    import control_mla_moe_lm as control
    rows = control.readings(CELL, [3, 2 ** 31 + 5], rehearse=TINY)
    names = [name for name, _ in control.planted()]
    assert len(names) == 9
    for row in rows:
        for who in names:
            assert row[who]["correct"] is False, (who, row[who]["compared"])
