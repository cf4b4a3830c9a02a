"""The comparisons that decide ``correct``.  Each returns
``{name: value}``; the limits live with the cell, in
``workloads/<cell>.json``, and ``PERF.md`` gives the readings each was
set from."""

import statistics


def _leaf_gaps(prog, ref, keep=None):
    """Each leaf's gap BETWEEN NORMS (not the norm of a difference),
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger: some gradients are all but zero."""
    names = [k for k in ref if keep is None or k in keep]
    median = statistics.median(ref[k] for k in names)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], median) for k in names}


def train_numbers(prog, ref):
    """``prog`` and ``ref``: ``{"loss": [..], "grad_norm": {leaf: n},
    "dparam_norm": {leaf: n}}`` of the first steps.

    loss<i>_rel      |loss - ref| / |ref| at step i
    grad1_norm_gap   worst leaf, first gradient as the optimizer got it
    dparam_norm_gap  worst leaf, the parameters' change after the last
                     step; leaves whose reference gradient is under a
                     thousandth of the median leaf's are left out: Adam
                     moves those by round-off alone
    <..>_gap_median  the median leaf's gap: steady from seed to seed
                     where the worst leaf's swings, so it is the one
                     that a lower precision fails"""
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"]), 1):
        out[f"loss{i}_rel"] = abs(a - b) / abs(b)
    if set(prog["grad_norm"]) != set(ref["grad_norm"]):
        raise ValueError("program and reference disagree on the leaves")
    floor = statistics.median(ref["grad_norm"].values()) / 1000.0
    keep = {k for k, v in ref["grad_norm"].items() if v >= floor}
    worst = {}
    for name, key, kept in (("grad1_norm_gap", "grad_norm", None),
                            ("dparam_norm_gap", "dparam_norm", keep)):
        gaps = _leaf_gaps(prog[key], ref[key], kept)
        worst[name] = max(gaps, key=gaps.get)
        out[name] = gaps[worst[name]]
        out[name + "_median"] = statistics.median(gaps.values())
    return out, {**worst, "left_out": sorted(set(ref["grad_norm"]) - keep)}


def with_limits(numbers, limits):
    """``{name: [value, limit]}`` for every number that has a limit."""
    missing = set(limits) - set(numbers)
    if missing:
        raise ValueError(f"no reading for limited numbers {sorted(missing)}")
    return {k: [float(numbers[k]), limits[k]] for k in limits}
