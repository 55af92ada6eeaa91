"""The comparison that decides ``correct``.  Every number compared has a
limit of its own (the cell's file holds them; PERF.md says what readings
each was set from).  Returns ``{name: [value, limit]}``; a run is correct
where every value is finite and no value passes its limit."""
import math
import statistics

import numpy as np


def _gap_by_worst_leaf(got, want, keep=None):
    """The widest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    med = statistics.median(want.values())
    gaps = {leaf: abs(got[leaf] - ref) / max(ref, med)
            for leaf, ref in want.items() if keep is None or leaf in keep}
    worst, where = 0.0, None
    for leaf, gap in gaps.items():
        if not gap <= worst:            # also catches NaN
            worst, where = gap, leaf
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    return worst, {"worst": where, "top3": [[k, float(f"{v:.3g}")]
                                            for k, v in top],
                   "median_leaf_gap": float(f"{statistics.median(gaps.values()):.3g}")}


def vector_error(got, want):
    """The norm of the difference of two vectors over the reference's
    norm: what a gap of norms averages away (rounding)."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def training(got, want, limits, last_loss=None):
    """Each step's loss (the steps the cell's file gives a limit for);
    every leaf's first gradient norm; the first gradient itself of the
    leaves the cell's file names under ``grad_vector`` (the number that
    the lower-precision control fails, PERF.md); every leaf's change after
    the steps, leaving out the leaves whose reference gradient is nought
    to rounding (under a thousandth of the median leaf's), which Adam
    moves by round-off alone."""
    out, notes = {}, {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"]), 1):
        gap = abs(a - b) / abs(b)
        if f"loss_step{i}" in limits:
            out[f"loss_step{i}"] = [gap, limits[f"loss_step{i}"]]
        else:       # no limit separates its readings (PERF.md): not compared
            notes[f"loss_step{i}_gap_not_compared"] = float(f"{gap:.3g}")
    gap, notes["grad_leaf"] = _gap_by_worst_leaf(got["grad_norm"],
                                                 want["grad_norm"])
    out["grad_norm_worst_leaf"] = [gap, limits["grad_norm"]]
    for leaf, limit in limits.get("grad_vector", {}).items():
        out[f"grad_vector_error.{leaf}"] = [
            vector_error(got["grad_vector"][leaf],
                         want["grad_vector"][leaf]), limit]
    med = statistics.median(want["grad_norm"].values())
    moved = {k for k, g in want["grad_norm"].items() if g >= 1e-3 * med}
    gap, notes["change_leaf"] = _gap_by_worst_leaf(
        got["change_norm"], want["change_norm"], keep=moved)
    out["change_norm_worst_leaf"] = [gap, limits["change_norm"]]
    if last_loss is not None:
        # the window's last loss has no reference; it only has to be a number
        out["window_last_loss_finite"] = [0.0 if math.isfinite(last_loss)
                                          else 1.0, 0.5]
    return out, notes


def verdict(numbers):
    return all(math.isfinite(v) and v <= lim for v, lim in numbers.values())
