"""The control and the planted faults of a training cell, at the cell's own
size (run on the chip; PERF.md holds the readings):

    python benchmarks/tests/control_train.py --workload <cell> --seeds 1 2 3

For each seed the plain reference follows the first steps in float32
(highest precision).  Then, put in the program's place and judged by the
same comparison and the same limits as a run (``compare.training`` and
``compare.verdict``): the reference in bfloat16, the nearest precision below
the float32 the configuration states (the control), the reference with
half of every batch left out, and the reference with the weights put back
after every step (the faults).  Each must come out NOT correct; the exit
code is 1 where one of them passes.  ``--rehearse FILE`` shrinks
the sizes for a CPU test.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


PLANTED = (("control_bfloat16", {"dtype": "bfloat16"}),
           ("fault_half_batch", {"fault": "half_batch"}),
           ("fault_state_unchanged", {"fault": "state_unchanged"}))


def readings(workload, seeds, rehearse=None, steps=3):
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
        for name, kw in PLANTED:
            got = ref.train_readings(cfg, seed, batches, lr, steps=steps,
                                     grad_leaves=leaves, **kw)
            numbers, notes = compare.training(got, want, limits)
            row[name] = {"correct": compare.verdict(numbers),
                         "compared": numbers, "worst_leaves": notes}
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", default=None)
    args = ap.parse_args()
    rows = readings(args.workload, args.seeds, args.rehearse)
    passed = [(r["seed"], who) for r in rows for who, _ in PLANTED
              if r[who]["correct"]]
    if passed:
        sys.exit(f"control_train: came out correct, and must not: {passed}")


if __name__ == "__main__":
    main()
