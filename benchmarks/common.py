"""Shared benchmark helpers: Monte-Carlo error sweeps + CSV/JSON reporting."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.metrics import relative_error
from repro.data.matrices import random_rhs, toeplitz, wishart

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")

SIZES_PAPER = (8, 16, 32, 64, 128, 256, 512)
N_SIMS_PAPER = 40                       # "40 random simulations" (Section IV)


def save_json(name: str, payload: Dict) -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def csv_row(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def matrix_of(family: str, key, n: int):
    return wishart(key, n) if family == "wishart" else toeplitz(key, n)


def _mc_problem(family: str, n: int, n_sims: int, seed: int):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = matrix_of(family, ka, n)
    b = random_rhs(kb, n)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), n_sims)
    return a, b, jnp.linalg.solve(a, b), keys


def mc_solutions(a, b, keys, cfg: AnalogConfig, solver: str, stages=None):
    """All Monte-Carlo solutions in one jit via the flat batched executor."""
    if solver == "original":
        return blockamc.solve_original_batched(a, b, keys, cfg)
    return blockamc.solve_batched(a, b, keys, cfg, stages=stages)


def mc_errors(family: str, n: int, cfg: AnalogConfig, solver: str,
              n_sims: int, stages=None, seed: int = 0) -> np.ndarray:
    """Relative errors over `n_sims` independent device-noise draws, every
    draw in one level-scheduled batched solve."""
    a, b, x_ref, keys = _mc_problem(family, n, n_sims, seed)
    xs = mc_solutions(a, b, keys, cfg, solver, stages=stages)
    errs = jax.vmap(lambda x: relative_error(x_ref, x))(xs)
    return np.asarray(errs)


# Timing protocol of the wall-clock columns that fig6 and hybrid print:
# warmup calls (compile + cache warm), then a median over measured calls.
# CPU wall time, reported only; no gate reads it.
TIMED_WARMUP = 3
TIMED_ITERS = 9


def timed(fn: Callable, *args) -> float:
    """Median wall-clock microseconds per call after warmup (CPU)."""
    for _ in range(TIMED_WARMUP):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(TIMED_ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))
