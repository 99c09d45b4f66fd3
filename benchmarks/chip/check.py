"""The comparison that decides ``correct``: the timed path's first steps
against the plain reference's, each number beside a limit of its own.

Both sides give ``losses`` (one per step), ``grad_norm`` and
``change_norm`` ({leaf: norm}). A leaf's gap is the distance between the
two norms (not the norm of a difference) over the reference's norm of
that leaf or of the median leaf, whichever is larger. ``grad_gap`` and
``change_gap`` are the worst leaf's: a widest gap, always a small hot
table's, which swings twofold from seed to seed. ``grad_gap_median`` and
``change_gap_median`` are the median leaf's gap, steady from seed to
seed, and what separates the fp8 control and the half-batch fault from
the program by a wide margin. Leaves whose reference gradient is under a
thousandth of the median leaf's move under Adagrad by round-off alone and
are left out of both change gaps.
"""

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "grad_gap_median", "change_gap",
           "change_gap_median")


def _leaf_gaps(prog, ref, leaves):
    """(worst gap, its leaf, median gap) over ``leaves``."""
    floor = statistics.median(ref[k] for k in ref)
    gaps = {}
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
        gaps[k] = gap if math.isfinite(gap) else math.inf
    worst = max(leaves, key=gaps.__getitem__)
    return gaps[worst], worst, statistics.median(gaps.values())


def compare(prog, ref):
    """{number: value} and {number: the leaf or step that gave it}."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError("the two sides ran different numbers of steps")
    numbers, where = {}, {}
    gaps = [abs(p - r) / abs(r) if math.isfinite(p) else math.inf
            for p, r in zip(prog["losses"], ref["losses"])]
    numbers["loss_gap"] = max(gaps)
    where["loss_gap"] = f"step {gaps.index(max(gaps)) + 1}"
    leaves = sorted(ref["grad_norm"])
    if sorted(prog["grad_norm"]) != leaves or sorted(
            prog["change_norm"]) != leaves:
        raise ValueError("the two sides name different leaves")
    median = statistics.median(ref["grad_norm"].values())
    moved = [k for k in leaves if ref["grad_norm"][k] >= 1e-3 * median]
    for name, norms, over in (("grad_gap", "grad_norm", leaves),
                              ("change_gap", "change_norm", moved)):
        worst, leaf, mid = _leaf_gaps(prog[norms], ref[norms], over)
        numbers[name], where[name] = worst, leaf
        numbers[name + "_median"] = mid
        where[name + "_median"] = f"median of {len(over)} leaves"
    return numbers, where


def judge(numbers, limits, finite_losses=True):
    """(correct, {name: {"value", "limit"}}): every number the cell's
    file gives a limit for has to lie at or under it."""
    compared, ok = {}, bool(finite_losses)
    for name, limit in limits.items():
        value = numbers[name]
        compared[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, compared
