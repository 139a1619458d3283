"""The comparison that decides ``correct``: each number beside its limit.

Norms are compared leaf by leaf by the *gap between the norms* (not the
norm of a difference), against the reference's norm of that leaf or of the
median leaf, whichever is larger: some gradients are all but zero.
"""

from __future__ import annotations

import math
import statistics


def leaf_gaps(program: dict, reference: dict) -> dict:
    """Every leaf's gap between the norms, over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    if set(program) != set(reference):
        only = sorted(set(program) ^ set(reference))
        raise ValueError(f'leaves differ between program and reference: {only}')
    floor = statistics.median(reference.values())
    return {
        name: abs(program[name] - ref) / max(ref, floor)
        for name, ref in reference.items()
    }


def norm_gap(program: dict, reference: dict) -> tuple[float, str]:
    """Worst leaf's ``|program - reference| / max(reference, median)``."""
    worst, where = 0.0, ''
    for name, gap in leaf_gaps(program, reference).items():
        if not gap <= worst:  # a NaN gap is the worst there is
            worst, where = gap, name
    return worst, where


def loss_gap(program: list, reference: list) -> float:
    """Worst relative gap between the steps' losses."""
    return max(
        (abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
        for p, r in zip(program, reference, strict=True)
    )


def decide(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``numbers[name] <= limits[name]`` for every limit, a NaN failing.
    Returns the verdict and one row per number for the run's output."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers[name]
        passed = value <= limit
        ok = ok and passed
        rows.append({
            'number': name, 'value': value, 'limit': limit, 'ok': passed,
        })
    return ok, rows
