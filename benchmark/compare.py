"""The comparison that decides ``correct``: numbers, each beside a limit of
its own (``benchmark/limits/<cell>.json``, set from readings on the chip;
``PERF.md`` gives the readings)."""

from __future__ import annotations

import json
import os
import statistics
import sys


def load_limits(root: str, cell: str, dry: bool = False) -> dict:
    """The cell's limits; for the CPU rehearsal, whose sizes are all
    rounding, the file's ``dry_cpu`` limits laid over them."""
    with open(os.path.join(root, "benchmark", "limits", cell + ".json")) as f:
        limits = json.load(f)
    over = limits.pop("dry_cpu", {}) if dry else {}
    limits.pop("dry_cpu", None)
    return {k: v for k, v in {**limits, **over}.items()
            if not k.startswith("_")}


def worst_leaf_gap(got: dict, ref: dict, leaves=None) -> tuple:
    """Over the leaves, the gap between the program's norm and the
    reference's (not the norm of their difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns ``(gap, leaf)``."""
    gaps = leaf_gaps(got, ref, leaves)
    worst, at = 0.0, ""
    for k, gap in gaps.items():
        if not gap <= worst:        # a NaN is the worst there is
            worst, at = gap, k
    return worst, at


def leaf_gaps(got: dict, ref: dict, leaves=None) -> dict:
    leaves = sorted(ref) if leaves is None else leaves
    floor = statistics.median(ref[k] for k in leaves)
    return {k: abs(got[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in leaves}


def moved_leaves(ref_grad_norm: dict) -> list:
    """Leaves whose gradient is not nought to rounding in the reference:
    at least a thousandth of the median leaf's. The others (a bias before
    a batch norm, say) move by round-off alone and are left out of the
    comparison of the parameters' change."""
    floor = 1e-3 * statistics.median(ref_grad_norm.values())
    return sorted(k for k, v in ref_grad_norm.items() if v >= floor)


def training_numbers(prog: dict, ref: dict) -> dict:
    """Each step's loss, the first gradient's norm and the parameters'
    change after the last step, the last two by the worst leaf."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        out[f"loss{i}_gap"] = abs(a - b) / max(abs(b), 1e-30)
    if len(prog["losses"]) != len(ref["losses"]):
        out["loss1_gap"] = float("nan")
    out["grad_norm_gap"], out["grad_norm_leaf"] = worst_leaf_gap(
        prog["grad_norm"], ref["grad_norm"])
    moved = moved_leaves(ref["grad_norm"])
    out["delta_norm_gap"], out["delta_norm_leaf"] = worst_leaf_gap(
        prog["delta_norm"], ref["delta_norm"], moved)
    # the median leaf's gap: steady from seed to seed where the worst leaf,
    # one small batch-norm vector, is not
    out["grad_norm_gap_median"] = statistics.median(leaf_gaps(
        prog["grad_norm"], ref["grad_norm"]).values())
    out["delta_norm_gap_median"] = statistics.median(leaf_gaps(
        prog["delta_norm"], ref["delta_norm"], moved).values())
    return out


def serving_numbers(gaps: list, n_expected: int) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample; the mean gap over all its tokens;
    and how many of the sampled requests did not come back whole."""
    widest = max((float(g.max()) for g in gaps if len(g)), default=float("nan"))
    tokens = sum(len(g) for g in gaps)
    mean = (sum(float(g.sum()) for g in gaps) / tokens if tokens
            else float("nan"))
    return {"served_logit_gap": widest, "served_logit_gap_mean": mean,
            "sample_missing": n_expected - len(gaps)}


def decide(numbers: dict, limits: dict) -> tuple:
    """``(correct, compared)``: ``compared`` maps each limited number to
    ``[value, limit]``; a number with a limit that is missing or not finite
    fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        compared[name] = [value, limit]
        if not value <= limit:
            ok = False
    return ok, compared


def report(compared: dict, extra: dict, tag: str = "") -> None:
    """Each number compared beside its limit, as the last lines on standard
    error."""
    for name, (value, limit) in compared.items():
        verdict = "ok" if value <= limit else "FAIL"
        print(f"{tag}compare {name} = {value:.6g} limit {limit:.6g} {verdict}",
              file=sys.stderr)
    for name, value in extra.items():
        print(f"{tag}compare-note {name} = {value}", file=sys.stderr)
    sys.stderr.flush()
