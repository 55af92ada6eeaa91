"""The control and the planted faults of a ``swa_moe_lm`` training cell,
at the cell's own size (run on the chip; PERF.md holds the readings):

    python benchmarks/tests/control_swa_moe_lm.py --workload <cell> --seeds 1 2

As ``control_train.py`` (the bfloat16 control, half of the targets left
out, the state left unchanged), and beside them the model's own faults,
each put in the program's place through the reference's ``fault`` and
judged by the same comparison and the same limits as a run: the window
ignored in the window blocks (full causal), the window one key short, one
key long, rotary positions applied in the position-free block, none in the
window blocks, key head ``h % 4`` for ``h // 7``, the router reading the
normed state after attention, SiLU for ReLU, the gates taken from the
softmax over all experts with no renormalisation.  Each must come out NOT
correct; the exit code is 1 where one passes.  ``--only`` names the ones to
run; ``--rehearse FILE`` shrinks the sizes.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def planted():
    from benchmarks.reference import swa_moe_lm as ref
    return (("control_bfloat16", {"dtype": "bfloat16"}),) + tuple(
        (f"fault_{f}", {"fault": f}) for f in ref.FAULTS)


def readings(workload, seeds, rehearse=None, only=None, steps=3):
    """One row a seed: for the control and each fault, every number
    compared beside its limit, and the verdict."""
    from benchmarks.harness import compare, loader
    bench = loader.benchmark()
    _, cell, cfg = loader.cell_and_config(bench, workload, rehearse)
    model = loader.load_module("models", cfg["family"])
    ref = loader.load_module("reference", cfg["family"])
    limits = cell["limits"]
    leaves = tuple(limits.get("grad_vector", ()))
    out = []
    for seed in seeds:
        batches = model.make_batches(cfg, cell["traffic_params"], seed)
        lr = cfg["learning_rate"]
        want = ref.train_readings(cfg, seed, batches, lr, steps=steps,
                                  grad_leaves=leaves)
        row = {"seed": seed}
        for name, kw in planted():
            if only and name not in only:
                continue
            got = ref.train_readings(cfg, seed, batches, lr, steps=steps,
                                     grad_leaves=leaves, **kw)
            numbers, notes = compare.training(got, want, limits)
            row[name] = {"correct": compare.verdict(numbers),
                         "compared": numbers, "worst_leaves": notes}
            print(json.dumps({"seed": seed, name: row[name]}), flush=True)
        out.append(row)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--only", nargs="+", default=None)
    ap.add_argument("--rehearse", default=None)
    args = ap.parse_args()
    rows = readings(args.workload, args.seeds, args.rehearse, args.only)
    passed = [(r["seed"], who) for r in rows for who in r
              if who != "seed" and r[who]["correct"]]
    if passed:
        sys.exit(f"control_swa_moe_lm: came out correct, and must not: "
                 f"{passed}")


if __name__ == "__main__":
    main()
