"""End-to-end arithmetic: latencies from the schedule, percentiles, rates.

An open-loop request is timed from the moment it was due to be sent, not
from when the system admitted it, so a stall that holds up the generator
or the queue shows in every request due during it.  A request that failed
or never came counts as answered at `penalty_s` after it was due: it
misses any latency limit.  A rate counts the answers completed inside the
window over the window's whole length.
"""
from __future__ import annotations

import numpy as np


def latencies(due, done, failed, penalty_s: float) -> np.ndarray:
    lat = np.asarray(done, np.float64) - np.asarray(due, np.float64)
    lat = np.where(np.asarray(failed, bool) | ~np.isfinite(lat),
                   penalty_s, lat)
    return lat


def percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q,
                               method="linear"))


def rate(done, ok, t0: float, seconds: float) -> float:
    """Answers that completed inside [t0, t0 + seconds], per second."""
    done = np.asarray(done, np.float64)
    inside = np.asarray(ok, bool) & (done >= t0) & (done <= t0 + seconds)
    return float(inside.sum()) / seconds
