"""Paper Fig. 9: variation + interconnect resistance (1 ohm/segment).

BlockAMC (one- and two-stage) vs original AMC, Wishart + Toeplitz.  Paper
claims up to ~10% relative-error reduction for one-stage and a further
improvement for two-stage (smaller arrays => shorter wire paths).

Two wire models price the interconnect (see tests/test_wire_validation.py
for the pinned envelope between them):

  * "first_order" - the O(n^2) perturbation used on the hot path;
  * "nodal"       - the exact batched MNA solve (repro.physics.nodal).

`run()` records cheap-vs-oracle columns (`*_nodal` medians + `model_gap`)
for sizes up to ORACLE_MAX_N; setting WIRE_ORACLE (run.py --wire-oracle)
switches *every* size and column to the nodal oracle instead.  The
separate `oracle_main()` suite (run.py --only fig9_oracle; nightly) sweeps
the n >= 64 regime where the first-order model leaves its validity
envelope and writes artifacts/bench/fig9_oracle.json with matrix-level
H-gap metrics plus solve-level medians under both models.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (N_SIMS_PAPER, csv_row, mc_errors, save_json)
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig

SIZES = (16, 32, 64, 128, 256, 512)
# Record the *_nodal oracle columns for sizes up to this (per-tile nodal
# readout is O(tile^4); above it the cheap model is the only affordable
# option in the fast suite - the nightly oracle sweep covers the rest).
ORACLE_MAX_N = 64
WIRE_ORACLE = False           # run.py --wire-oracle: oracle for ALL columns

ORACLE_SIZES = (64, 128, 256)
ORACLE_SIMS = 4


def _ni(sigma=0.05, model="first_order", **kw):
    return NonidealConfig(sigma=sigma, r_wire=1.0, wire_model=model, **kw)


def run(n_sims=None):
    # resolve at call time so run.py's fast-mode overrides stick
    n_sims = N_SIMS_PAPER if n_sims is None else n_sims
    base_model = "nodal" if WIRE_ORACLE else "first_order"
    ni = _ni(model=base_model)
    ni_comp = _ni(model=base_model, compensate_wire=True)
    out = {"wire_model": base_model}
    for family in ("wishart", "toeplitz"):
        rows = []
        for n in SIZES:
            cfg1 = AnalogConfig(array_size=max(n // 2, 4), nonideal=ni)
            cfg2 = AnalogConfig(array_size=max(n // 4, 4), nonideal=ni)
            cfgc = AnalogConfig(array_size=max(n // 2, 4), nonideal=ni_comp)
            e1 = mc_errors(family, n, cfg1, "blockamc", n_sims, stages=1)
            e2 = mc_errors(family, n, cfg2, "blockamc", n_sims, stages=2)
            ec = mc_errors(family, n, cfgc, "blockamc", n_sims, stages=1)
            eo = mc_errors(family, n, cfg1, "original", n_sims)
            row = {"n": n,
                   "one_stage_median": float(np.median(e1)),
                   "two_stage_median": float(np.median(e2)),
                   "one_stage_compensated": float(np.median(ec)),
                   "orig_median": float(np.median(eo))}
            if not WIRE_ORACLE and n <= ORACLE_MAX_N:
                # cheap-vs-oracle differential columns (same seeds)
                cfg1n = AnalogConfig(array_size=max(n // 2, 4),
                                     nonideal=_ni(model="nodal"))
                e1n = mc_errors(family, n, cfg1n, "blockamc", n_sims,
                                stages=1)
                med = float(np.median(e1n))
                row["one_stage_nodal"] = med
                row["model_gap"] = abs(row["one_stage_median"] - med) / med
            rows.append(row)
        out[family] = rows
    return out


def main():
    out = run()
    save_json("fig9_interconnect", out)
    for family in ("wishart", "toeplitz"):
        rows = out[family]
        r = rows[-1]
        red1 = (r["orig_median"] - r["one_stage_median"]) / r["orig_median"]
        red2 = (r["orig_median"] - r["two_stage_median"]) / r["orig_median"]
        csv_row(f"fig9_{family}_n{r['n']}", 0.0,
                f"orig={r['orig_median']:.3f};one={r['one_stage_median']:.3f};"
                f"two={r['two_stage_median']:.3f};red1={red1:.1%};red2={red2:.1%}")
        csv_row(f"fig9_{family}_compensated", 0.0,
                f"one={r['one_stage_median']:.3f};"
                f"one_comp={r['one_stage_compensated']:.3f} "
                f"(ref [29] write-verify mitigation)")
        with_gap = [x for x in rows if "model_gap" in x]
        if with_gap:
            g = with_gap[-1]
            csv_row(f"fig9_{family}_model_gap_n{g['n']}", 0.0,
                    f"first_order={g['one_stage_median']:.4f};"
                    f"nodal={g['one_stage_nodal']:.4f};"
                    f"gap={g['model_gap']:.1%}")
    return out


# ------------------- nightly oracle sweep (fig9_oracle) ---------------------

def oracle_sweep(sizes=None, n_sims=None):
    """n >= 64 differential sweep: matrix-level H-gap between the wire
    models plus solve-level medians under each, per size."""
    import jax
    import jax.numpy as jnp

    from repro.core import nonideal as ni_mod
    from repro.physics import nodal_effective_conductance

    sizes = ORACLE_SIZES if sizes is None else sizes
    n_sims = ORACLE_SIMS if n_sims is None else n_sims
    g0 = 100e-6
    rows = []
    for n in sizes:
        rng = np.random.default_rng(n)
        g_np = rng.uniform(0.0, 0.5, (n, n)) * g0
        with jax.enable_x64():
            g = jnp.asarray(g_np, dtype=jnp.float64)
            h = nodal_effective_conductance(g, 1.0)
            h_fo = ni_mod.effective_conductance(g, 1.0)
            effect = float(jnp.linalg.norm(h - g))
            gap = float(jnp.linalg.norm(h_fo - h))
            g_norm = float(jnp.linalg.norm(g))
        row = {"n": n,
               "h_gap_rel_to_effect": gap / effect,
               "wire_effect_rel": effect / g_norm}
        for model in ("first_order", "nodal"):
            cfg = AnalogConfig(array_size=max(n // 2, 4),
                               nonideal=_ni(model=model))
            errs = mc_errors("wishart", n, cfg, "blockamc", n_sims,
                             stages=1)
            row[f"median_err_{model}"] = float(np.median(errs))
        row["solve_model_gap"] = abs(
            row["median_err_first_order"] - row["median_err_nodal"]
        ) / row["median_err_nodal"]
        rows.append(row)
    return {"r_wire": 1.0, "rows": rows}


def oracle_main():
    out = oracle_sweep()
    save_json("fig9_oracle", out)
    for r in out["rows"]:
        csv_row(f"fig9_oracle_n{r['n']}", 0.0,
                f"h_gap={r['h_gap_rel_to_effect']:.2%};"
                f"fo={r['median_err_first_order']:.4f};"
                f"nodal={r['median_err_nodal']:.4f};"
                f"solve_gap={r['solve_model_gap']:.1%}")
    return out


if __name__ == "__main__":
    main()
