"""The comparison that decides `correct`.

Served answers are compared with the plain reference (`bench/refs/`), run
in float64 on the host once the window has closed and the program's state
is freed.  The number compared is the largest relative 2-norm gap,
||x - x_ref|| / ||x_ref||, over a sample of the answers drawn from the
seed; its limit lives in the configuration's file with the readings it was
set from (PERF.md).  Counts that must be 0 (answers that never came, or
that came from another path than the analog one) have the limit 0.
"""
from __future__ import annotations

import numpy as np


def rel_gap(x, ref) -> np.ndarray:
    """Per-answer ||x - ref|| / ||ref|| over the last-but-one axis."""
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return (np.linalg.norm(x - ref, axis=-2)
            / np.linalg.norm(ref, axis=-2))


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit, and finite."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        value = float(value)
        checks[name] = {"value": value, "limit": float(limit)}
        ok = ok and np.isfinite(value) and value <= limit
    return ok, checks
