"""Hybrid analog-digital benchmark: the refinement loop made quantitative.

Sweeps condition number x device variation x wire model and records, per
combination, the iterations-to-1e-10 (and convergence flags) of

  * unpreconditioned digital CG (the all-digital baseline),
  * seed-only refinement (analog seed, plain CG - the robust serving mode),
  * BlockAMC-preconditioned CG and GMRES (the programmed cascade applied
    inside the iteration),

plus wall-clock for the first two (stalled preconditioned runs burn full
fuel, so per-row precond timings would be noise; the acceptance headline
carries the preconditioned wall-clock instead), into
`artifacts/bench/hybrid.json` - with the headline (cond ~ 1e4,
write-verified programming) asserted by tests/test_hybrid_krylov.py.
The sweep shows the whole regime map: preconditioning wins big while
sigma x cond is small, goes indefinite beyond it (PCG stalls, GMRES
degrades gracefully), and seed-only refinement always converges.

Digital refinement runs in float64 (`jax.enable_x64`); the
programmed cascade is the same noisy analog model as everywhere else.
"""
from __future__ import annotations

import argparse
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, save_json, timed
from repro import hybrid
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import random_rhs, wishart_with_cond
from repro.hybrid import AnalogPreconditioner, matvec_from_dense, pcg

SMOKE = False
N = 96
N_PAPER = 256
TOL = 1e-10
MAXITER = 20000


@partial(jax.jit, static_argnames=("tol", "maxiter"))
def _plain_cg(a, b, tol, maxiter):
    return pcg(matvec_from_dense(a), b, tol=tol, maxiter=maxiter)


def _refined(a, b, precond, method, use_precond, maxiter=MAXITER):
    return hybrid.solve_refined(a, b, precond, method=method, tol=TOL,
                                maxiter=maxiter, restart=32,
                                use_precond=use_precond)


def _sweep(n, conds, sigmas, wires, keys):
    ka, kb, kn = keys
    rows = []
    for cond in conds:
        a = wishart_with_cond(ka, n, cond, dtype=jnp.float64)
        b = random_rhs(kb, n).astype(jnp.float64)
        plain = _plain_cg(a, b, TOL, MAXITER)
        wall_plain = timed(lambda: jax.block_until_ready(
            _plain_cg(a, b, TOL, MAXITER)), iters=3)
        for sigma in sigmas:
            for r_wire in wires:
                cfg = AnalogConfig(
                    array_size=n // 2,
                    nonideal=NonidealConfig(sigma=sigma, r_wire=r_wire))
                precond = AnalogPreconditioner.program(a, kn, cfg, stages=1)
                seed = precond(b)
                seed_res = float(jnp.linalg.norm(b - a @ seed)
                                 / jnp.linalg.norm(b))
                _, seeded = _refined(a, b, precond, "cg", False)
                _, pcg_res = _refined(a, b, precond, "cg", True)
                _, gm_res = _refined(a, b, precond, "gmres", True)
                wall_seeded = timed(lambda: jax.block_until_ready(
                    _refined(a, b, precond, "cg", False)), iters=3)
                rows.append({
                    "cond": cond, "sigma": sigma, "r_wire": r_wire,
                    "seed_res": seed_res,
                    "iters_plain_cg": int(plain.iters),
                    "conv_plain_cg": bool(plain.converged),
                    "wall_us_plain_cg": wall_plain,
                    "iters_seed_cg": int(seeded.iters),
                    "conv_seed_cg": bool(seeded.converged),
                    "wall_us_seed_cg": wall_seeded,
                    "iters_precond_cg": int(pcg_res.iters),
                    "conv_precond_cg": bool(pcg_res.converged),
                    "iters_precond_gmres": int(gm_res.iters),
                    "conv_precond_gmres": bool(gm_res.converged),
                })
    return rows


def _headline(keys):
    """The acceptance configuration (mirrors test_hybrid_krylov.py):
    cond ~ 1e4, n=64, write-verified programming."""
    ka, kb, kn = keys
    n = 64
    a = wishart_with_cond(ka, n, 1e4, dtype=jnp.float64)
    b = random_rhs(kb, n).astype(jnp.float64)
    plain = _plain_cg(a, b, TOL, MAXITER)
    cfg_cg = AnalogConfig(array_size=n // 2, opa_gain=1e5)
    m_cg = AnalogPreconditioner.program(a, kn, cfg_cg, stages=1)
    _, res_cg = _refined(a, b, m_cg, "cg", True, maxiter=4000)
    cfg_gm = AnalogConfig(array_size=n // 2, nonideal=NonidealConfig(
        sigma=1e-4, r_wire=1.0, compensate_wire=True))
    m_gm = AnalogPreconditioner.program(a, kn, cfg_gm, stages=1)
    _, res_gm = _refined(a, b, m_gm, "gmres", True, maxiter=4000)
    wall_plain = timed(lambda: jax.block_until_ready(
        _plain_cg(a, b, TOL, MAXITER)), iters=3)
    wall_gm = timed(lambda: jax.block_until_ready(
        _refined(a, b, m_gm, "gmres", True, maxiter=4000)), iters=3)
    return {
        "n": n, "cond": 1e4, "tol": TOL,
        "iters_plain_cg": int(plain.iters),
        "iters_precond_cg": int(res_cg.iters),
        "conv_precond_cg": bool(res_cg.converged),
        "precond_cg_cfg": {"sigma": 0.0, "opa_gain": 1e5},
        "iters_precond_gmres": int(res_gm.iters),
        "conv_precond_gmres": bool(res_gm.converged),
        "precond_gmres_cfg": {"sigma": 1e-4, "r_wire": 1.0,
                              "compensate_wire": True},
        "wall_us_plain_cg": wall_plain,
        "wall_us_precond_gmres": wall_gm,
        "speedup_iters_gmres": int(plain.iters) / max(int(res_gm.iters), 1),
    }


def run():
    n = 48 if SMOKE else N
    conds = (1e1, 1e3) if SMOKE else (1e1, 1e3, 1e5)
    sigmas = (0.0, 0.05) if SMOKE else (0.0, 0.02, 0.05)
    wires = (0.0,) if SMOKE else (0.0, 1.0)
    with jax.enable_x64():
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        rows = _sweep(n, conds, sigmas, wires, keys)
        headline = _headline(keys)
    return {"n": n, "tol": TOL, "smoke": SMOKE, "rows": rows,
            "headline": headline}


def main():
    payload = run()
    save_json("hybrid", payload)
    h = payload["headline"]
    csv_row("hybrid_headline_cond1e4", h["wall_us_precond_gmres"],
            f"gmres={h['iters_precond_gmres']};pcg={h['iters_precond_cg']};"
            f"plain={h['iters_plain_cg']};"
            f"speedup={h['speedup_iters_gmres']:.1f}x")
    for r in payload["rows"]:
        csv_row(
            f"hybrid_cond{r['cond']:.0e}_s{r['sigma']}_w{r['r_wire']}",
            r["wall_us_seed_cg"],
            f"plain={r['iters_plain_cg']};seed={r['iters_seed_cg']};"
            f"pcg={r['iters_precond_cg']}({'+' if r['conv_precond_cg'] else '-'});"
            f"gmres={r['iters_precond_gmres']}"
            f"({'+' if r['conv_precond_gmres'] else '-'})")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: tiny grid, <1 min")
    ap.add_argument("--paper", action="store_true",
                    help="full 256-size protocol")
    args = ap.parse_args()
    if args.smoke:
        SMOKE = True
    if args.paper:
        N = N_PAPER
    main()
