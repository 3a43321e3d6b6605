"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is CPU wall time
where meaningful, 0.0 for pure-accuracy rows) and writes JSON artifacts to
artifacts/bench/ consumed by EXPERIMENTS.md.

  fig6  - ideal-mapping accuracy (finite OPA gain), step cascade
  fig7  - device variation, Wishart/Toeplitz, 40 sims
  fig8  - two-stage solver
  fig9  - variation + interconnect resistance (cheap-vs-oracle columns;
          --wire-oracle prices every column with the exact nodal model)
  fig9_oracle - opt-in n >= 64 exact-MNA sweep (nightly artifact)
  fig10 - area/power breakdown + macro timing model
  hybrid, distributed, kernels - beyond-figure system benchmarks
  engine - serving-engine SLOs under open-loop Poisson traffic, with and
           without a scripted chaos schedule (report-only keys)
  router - replicated-fleet SLOs + replica-loss recovery: checkpoint
           restore vs full re-programming (report-only keys)
  maint  - drift self-healing availability (scrub vs reactive) + block
           repair vs full re-program cost ratio (report-only keys)
  grad   - differentiable solver: backward-vs-forward marginal cost of the
           implicit-diff VJP + wire-calibration convergence curve

Fast mode (default): fewer Monte-Carlo sims and capped sizes so the suite
finishes in minutes on one CPU core; --paper runs the full 40-sim, 512-size
protocol.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import (common, distributed_solver, engine_bench,
                        fig6_accuracy, fig7_variation, fig8_twostage,
                        fig9_interconnect, fig10_area_power, grad_bench,
                        hybrid_refinement, kernel_bench, maint_bench,
                        router_bench)
from repro.runtime.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full 40-sim protocol up to 512x512")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig6,fig10")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: tiniest configs, <1 min per suite")
    ap.add_argument("--bench-warmup", type=int, default=None,
                    help="warmup calls before timing (default %d)"
                         % common.TIMED_WARMUP)
    ap.add_argument("--bench-iters", type=int, default=None,
                    help="timed calls per median (default %d)"
                         % common.TIMED_ITERS)
    ap.add_argument("--bench-tenants", type=int, default=None,
                    help="tenant count for the multi-tenant packed bench "
                         "(default: 4 in smoke mode, 4 and 16 otherwise)")
    ap.add_argument("--wire-oracle", action="store_true",
                    help="price interconnect with the exact nodal MNA "
                         "oracle (repro.physics) instead of the first-order "
                         "model, at every fig9 size and column")
    args = ap.parse_args()
    use_compile_cache(ROOT)

    if args.wire_oracle:
        fig9_interconnect.WIRE_ORACLE = True

    if args.bench_warmup is not None:
        common.TIMED_WARMUP = args.bench_warmup
    if args.bench_iters is not None:
        common.TIMED_ITERS = args.bench_iters
    if args.bench_tenants is not None:
        kernel_bench.TENANTS = (args.bench_tenants,)

    if args.paper:
        hybrid_refinement.N = hybrid_refinement.N_PAPER
    if not args.paper:
        common.N_SIMS_PAPER = 8
        common.SIZES_PAPER = (8, 16, 32, 64, 128, 256)
        fig7_variation.N_SIMS_PAPER = 8
        fig7_variation.SIZES_PAPER = common.SIZES_PAPER
        fig8_twostage.N_SIMS_PAPER = 8
        fig8_twostage.SIZES = (64, 128, 256)
        fig9_interconnect.N_SIMS_PAPER = 8
        fig9_interconnect.SIZES = (16, 32, 64, 128)
        fig9_interconnect.ORACLE_SIZES = (64, 128)
        fig6_accuracy.SIZES_PAPER = common.SIZES_PAPER

    if args.smoke:            # after fast-mode defaults: smoke tightens them
        kernel_bench.SMOKE = True
        hybrid_refinement.SMOKE = True
        engine_bench.SMOKE = True
        grad_bench.SMOKE = True
        router_bench.SMOKE = True
        maint_bench.SMOKE = True
        common.N_SIMS_PAPER = 4
        common.SIZES_PAPER = (8, 16, 32, 64)
        fig7_variation.N_SIMS_PAPER = 4
        fig7_variation.SIZES_PAPER = common.SIZES_PAPER
        fig8_twostage.N_SIMS_PAPER = 4
        fig8_twostage.SIZES = (64,)
        fig9_interconnect.N_SIMS_PAPER = 4
        fig9_interconnect.SIZES = (16, 32)
        fig9_interconnect.ORACLE_SIZES = (64,)
        fig9_interconnect.ORACLE_SIMS = 2
        fig6_accuracy.SIZES_PAPER = common.SIZES_PAPER

    suites = {
        "fig6": fig6_accuracy.main,
        "fig7": fig7_variation.main,
        "fig8": fig8_twostage.main,
        "fig9": fig9_interconnect.main,
        "fig9_oracle": fig9_interconnect.oracle_main,
        "fig10": fig10_area_power.main,
        "hybrid": hybrid_refinement.main,
        "distributed": distributed_solver.main,
        "kernels": kernel_bench.main,
        "engine": engine_bench.main,
        "grad": grad_bench.main,
        "router": router_bench.main,
        "maint": maint_bench.main,
    }
    # fig9_oracle is opt-in (--only): the exact-MNA sweep at n >= 64 is a
    # nightly artifact, too heavy for the default minutes-long suite.
    default = [s for s in suites if s != "fig9_oracle"]
    chosen = (args.only.split(",") if args.only else default)
    print("name,us_per_call,derived")
    for name in chosen:
        suites[name]()


if __name__ == "__main__":
    main()
