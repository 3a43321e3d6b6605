"""The paper's accuracy sweeps: one module per figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is CPU wall time
where a sweep prints one, 0.0 for pure-accuracy rows; no gate reads it)
and writes JSON artifacts to artifacts/bench/.  Speed is measured on the
chip by `bench/run.py`, not here.

  fig6  - ideal-mapping accuracy (finite OPA gain), step cascade
  fig7  - device variation, Wishart/Toeplitz, 40 sims
  fig8  - two-stage solver
  fig9  - variation + interconnect resistance (cheap-vs-oracle columns;
          --wire-oracle prices every column with the exact nodal model)
  fig9_oracle - opt-in n >= 64 exact-MNA sweep (nightly artifact)
  fig10 - area/power breakdown + macro timing model
  hybrid - analog-preconditioned Krylov refinement

Fast mode (default): fewer Monte-Carlo sims and capped sizes so the sweeps
finish in minutes on one CPU core; --paper runs the full 40-sim, 512-size
protocol.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import (fig6_accuracy, fig7_variation, fig8_twostage,
                        fig9_interconnect, fig10_area_power,
                        hybrid_refinement)
from repro.runtime.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full 40-sim protocol up to 512x512")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig6,fig10")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: tiniest configs, <1 min per sweep")
    ap.add_argument("--wire-oracle", action="store_true",
                    help="price interconnect with the exact nodal MNA "
                         "oracle (repro.physics) instead of the first-order "
                         "model, at every fig9 size and column")
    args = ap.parse_args()
    use_compile_cache(ROOT)

    if args.wire_oracle:
        fig9_interconnect.WIRE_ORACLE = True

    if args.paper:
        hybrid_refinement.N = hybrid_refinement.N_PAPER
    if args.smoke:
        sizes, sims = (8, 16, 32, 64), 4
        hybrid_refinement.SMOKE = True
        fig8_twostage.SIZES = (64,)
        fig9_interconnect.SIZES = (16, 32)
        fig9_interconnect.ORACLE_SIZES = (64,)
        fig9_interconnect.ORACLE_SIMS = 2
    elif not args.paper:
        sizes, sims = (8, 16, 32, 64, 128, 256), 8
        fig8_twostage.SIZES = (64, 128, 256)
        fig9_interconnect.SIZES = (16, 32, 64, 128)
        fig9_interconnect.ORACLE_SIZES = (64, 128)
    if args.smoke or not args.paper:
        fig6_accuracy.SIZES_PAPER = sizes
        fig7_variation.SIZES_PAPER = sizes
        fig7_variation.N_SIMS_PAPER = sims
        fig8_twostage.N_SIMS_PAPER = sims
        fig9_interconnect.N_SIMS_PAPER = sims

    suites = {
        "fig6": fig6_accuracy.main,
        "fig7": fig7_variation.main,
        "fig8": fig8_twostage.main,
        "fig9": fig9_interconnect.main,
        "fig9_oracle": fig9_interconnect.oracle_main,
        "fig10": fig10_area_power.main,
        "hybrid": hybrid_refinement.main,
    }
    # fig9_oracle is opt-in (--only): the exact-MNA sweep at n >= 64 is a
    # nightly artifact, too heavy for the default minutes-long run.
    default = [s for s in suites if s != "fig9_oracle"]
    chosen = (args.only.split(",") if args.only else default)
    print("name,us_per_call,derived")
    for name in chosen:
        suites[name]()


if __name__ == "__main__":
    main()
