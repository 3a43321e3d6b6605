"""Hybrid refinement + distributed solver + macro/area model tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import area_energy, blockamc, distributed, hybrid, macro
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.core.metrics import relative_error
from repro.data.matrices import wishart, random_rhs

KA, KB, KN = jax.random.split(jax.random.PRNGKey(0), 3)


# ------------------------------- hybrid ----------------------------------

def test_cg_refine_converges():
    a = wishart(KA, 64)
    b = random_rhs(KB, 64)
    x_ref = jnp.linalg.solve(a, b)
    x = hybrid.cg_refine(a, b, jnp.zeros_like(b), 80)
    assert float(relative_error(x_ref, x)) < 1e-4


def test_analog_seed_saves_iterations():
    """The paper's positioning: AMC seed accelerates digital iteration.

    The seed comes from a `ProgrammedSolver` - programmed once, *outside*
    the iteration - and the refinement runs through the batched hybrid
    drivers, so this exercises the genuine analog->digital hand-off (the
    old version rebuilt the plan per call and only ever timed the digital
    path).  Richardson is the discriminating iteration: its saving is
    proportional to log(seed error), where Krylov methods barely move.

    The saving must hold for every one of several programming draws, not
    for one pinned key.  sigma=0.01 puts the n=96 seed error near 0.2
    (measured 0.17-0.22 over five keys, a 18-29 iteration saving of 186);
    at sigma=0.05 the seed error is ~0.8-1.0 and a seed cannot save
    anything by construction.
    """
    a = wishart(KA, 96)
    b = random_rhs(KB, 96)
    cfg = AnalogConfig(array_size=48, nonideal=NonidealConfig(sigma=0.01))
    _, it_zero = hybrid.iterations_to_tol(a, b, jnp.zeros_like(b), tol=1e-5,
                                          method="richardson",
                                          max_iters=20000)
    for i in range(4):
        solver = blockamc.ProgrammedSolver.program(
            a, jax.random.fold_in(KN, i), cfg, stages=1)
        x_seed = solver.solve(b)
        assert float(jnp.linalg.norm(b - a @ x_seed)) > 0.0  # noisy, not exact
        _, it_seed = hybrid.iterations_to_tol(a, b, x_seed, tol=1e-5,
                                              method="richardson",
                                              max_iters=20000)
        assert int(it_seed) < int(it_zero), (i, int(it_seed), int(it_zero))
        # and the batched driver seeded with the same x0 agrees on
        # convergence
        res = hybrid.pcg(hybrid.matvec_from_dense(a), b, x0=x_seed, tol=1e-5,
                         maxiter=500)
        assert bool(res.converged)


@pytest.mark.slow
def test_refined_256_two_stage_reaches_1e10():
    """The 256^2 paper config (Fig. 8: two stages, 64^2 arrays) refined to
    full double precision: seed-only CG from the programmed analog solve
    reaches 1e-10 where the sigma=0.05 analog cascade alone cannot."""
    with jax.enable_x64():
        n = 256
        a = wishart(KA, n, dtype=jnp.float64)
        b = random_rhs(KB, n).astype(jnp.float64)
        cfg = AnalogConfig(array_size=64,
                           nonideal=NonidealConfig(sigma=0.05))
        precond = hybrid.AnalogPreconditioner.program(a, KN, cfg, stages=2)
        raw_res = float(jnp.linalg.norm(b - a @ precond(b))
                        / jnp.linalg.norm(b))
        assert raw_res > 1e-6
        x, res = hybrid.solve_refined(a, b, precond, method="cg", tol=1e-10,
                                      maxiter=2000, use_precond=False)
        assert bool(res.converged) and float(res.resnorm) <= 1e-10


def test_richardson_reduces_residual():
    a = wishart(KA, 32)
    b = random_rhs(KB, 32)
    x0 = jnp.zeros_like(b)
    x = hybrid.richardson_refine(a, b, x0, 200)
    r0 = float(jnp.linalg.norm(b - a @ x0))
    r1 = float(jnp.linalg.norm(b - a @ x))
    assert r1 < 0.1 * r0


def test_iterations_to_tol_fuel_bound():
    a = wishart(KA, 32)
    b = random_rhs(KB, 32)
    _, k = hybrid.iterations_to_tol(a, b, jnp.zeros_like(b), tol=1e-30,
                                    max_iters=17)
    assert int(k) == 17


# ----------------------------- distributed --------------------------------

@pytest.mark.slow
def test_distributed_matches_sequential_ideal():
    n = 128
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    x_ref = jnp.linalg.solve(a, b)
    cfg = AnalogConfig(array_size=32)
    x = distributed.solve_distributed(a, b, KN, cfg, stages=2)
    assert float(relative_error(x_ref, x)) < 1e-4


def test_distributed_with_noise_finite():
    n = 64
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.05))
    x = distributed.solve_distributed(a, b, KN, cfg, stages=1)
    assert bool(jnp.all(jnp.isfinite(x)))


def test_block_inv():
    a = wishart(KA, 96)
    ai = distributed.block_inv(a, 24)
    np.testing.assert_allclose(np.asarray(ai @ a), np.eye(96),
                               atol=5e-4)


def test_mvm_tiled_vec_matches_dense():
    n = 64
    a = wishart(KA, n)
    v = random_rhs(KB, n)
    cfg = AnalogConfig(array_size=16)
    scale = 1.0 / jnp.max(jnp.abs(a))
    grid = distributed.map_tiled_vec(a, KN, cfg, scale)
    out = distributed.mvm_tiled_vec(grid, v, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(-(a * scale) @ v),
                               rtol=1e-4, atol=1e-6)


# ------------------------------ macro model --------------------------------

def test_one_stage_latency_five_cycles():
    perf = macro.solver_performance("one_stage", n_solves=1)
    assert perf["latency_cycles"] == 5.0


def test_one_stage_shared_opa_serialises():
    """One shared OPA set: initiation interval == 5 cycles per solve."""
    perf = macro.solver_performance("one_stage", n_solves=8)
    assert perf["initiation_interval"] == 5.0


def test_two_stage_pipelines_across_macros():
    """Four macros + dedicated MVM sets: II better than latency."""
    perf = macro.solver_performance("two_stage", n_solves=8)
    assert perf["latency_cycles"] > 5.0          # deeper cascade
    assert perf["initiation_interval"] < perf["latency_cycles"]


# --------------------------- area/energy model -----------------------------

def test_area_power_savings_match_paper():
    """Abstract: 48.83% area and 40% energy saving for one-stage; Fig. 10:
    12.3% / 37.4% for two-stage."""
    rep = area_energy.report()
    sav = area_energy.savings(rep)
    assert abs(sav["area"]["one_stage"] - 0.4883) < 2e-3
    assert abs(sav["area"]["two_stage"] - 0.1230) < 2e-3
    assert abs(sav["power"]["one_stage"] - 0.400) < 2e-3
    assert abs(sav["power"]["two_stage"] - 0.374) < 2e-3


def test_area_totals_match_paper():
    rep = area_energy.report()
    assert abs(rep["area"]["original"]["total"] - 0.01577) < 1e-5
    assert abs(rep["area"]["one_stage"]["total"] - 0.00807) < 1e-4
    assert abs(rep["area"]["two_stage"]["total"] - 0.01383) < 1e-4


def test_unit_costs_positive():
    cal = area_energy.solve_calibration()
    for kind in ("area", "power"):
        u = cal[kind]
        assert u.opa_fixed > 0 and u.opa_per_width > 0
        assert u.dac > 0 and u.adc > 0 and u.cell > 0
