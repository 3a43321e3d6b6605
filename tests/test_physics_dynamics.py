"""Device dynamics and stuck-at faults, unit level and end to end.

Companion to tests/test_physics_oracle.py: that file pins the nodal wire
solver against the dense MNA oracle; this one covers the *device* half of
the physics subsystem — write-verify programming, retention drift, and
stuck-at fault injection with fault-aware remapping — including full
campaigns through `ProgrammedSolver.solve` so every knob is exercised on
the exact path the serving stack uses.

Margins are calibrated (not aspirational): e.g. at p_stuck_off = 2% on a
16x16-tiled n=32 Wishart solve, the no-remap relative error is 0.04-0.85
across seeds while remapping drives it below 1e-6, because every
stuck-OFF fault can be routed onto an exact-zero differential target.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blockamc, nonideal
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart
from repro.physics import (apply_stuck_faults, drift_conductance,
                           fault_aware_permutations, nodal_effective_conductance,
                           sample_stuck_masks, write_verify)

G0 = 100e-6


def _solve_err(a, b, cfg, seed=0, stages=1):
    x_ref = jnp.linalg.solve(a, b)
    x = blockamc.solve(a, b, jax.random.PRNGKey(seed), cfg, stages=stages)
    return float(jnp.linalg.norm(x - x_ref) / jnp.linalg.norm(x_ref))


def _diff_target(n, seed):
    """gpos of a signed matrix: ~half the entries are exact zeros."""
    a = wishart(jax.random.PRNGKey(seed), n)
    return jnp.maximum(a / jnp.max(jnp.abs(a)), 0.0) * G0


# --------------------------- fault unit tests -------------------------------

def test_stuck_masks_disjoint_and_rates():
    on, off = sample_stuck_masks(jax.random.PRNGKey(0), (400, 400),
                                 0.05, 0.10)
    assert not bool(jnp.any(on & off))
    assert abs(float(jnp.mean(on)) - 0.05) < 0.01
    assert abs(float(jnp.mean(off)) - 0.10) < 0.01


def test_fault_permutations_are_valid():
    g = _diff_target(16, 3)
    on, off = sample_stuck_masks(jax.random.PRNGKey(1), g.shape, 0.02, 0.05)
    p, q = fault_aware_permutations(g, on, off, G0, 0.0)
    np.testing.assert_array_equal(np.sort(np.asarray(p)), np.arange(16))
    np.testing.assert_array_equal(np.sort(np.asarray(q)), np.arange(16))


def test_no_faults_is_identity():
    g = _diff_target(8, 5)
    for remap in (False, True):
        gf = apply_stuck_faults(g, g, jax.random.PRNGKey(2),
                                p_on=0.0, p_off=0.0, g_on=G0, g_off=0.0,
                                remap=remap)
        np.testing.assert_array_equal(np.asarray(gf), np.asarray(g))


def test_remap_routes_stuck_off_onto_zero_targets():
    """Differential targets have exact zeros; with remap every stuck-OFF
    fault should land on one, making the stamped error (essentially) zero."""
    for s in range(3):
        g = _diff_target(16, 10 + s)
        errs = {}
        for remap in (False, True):
            gf = apply_stuck_faults(g, g, jax.random.PRNGKey(40 + s),
                                    p_on=0.0, p_off=0.05, g_on=G0,
                                    g_off=0.0, remap=remap)
            errs[remap] = float(jnp.linalg.norm(gf - g) / jnp.linalg.norm(g))
        assert errs[False] > 5e-3          # faults really landed somewhere
        assert errs[True] < 1e-8 * max(1.0, errs[False] / 1e-8)
        assert errs[True] < 1e-6


def test_remap_improves_mixed_fault_error():
    """Stuck-ON faults (full-scale G0) can't always hide, but per-entry
    matching must still beat the unmapped placement on Frobenius error."""
    for s in range(3):
        g = _diff_target(16, 10 + s)
        errs = {}
        for remap in (False, True):
            gf = apply_stuck_faults(g, g, jax.random.PRNGKey(40 + s),
                                    p_on=0.01, p_off=0.05, g_on=G0,
                                    g_off=0.0, remap=remap)
            errs[remap] = float(jnp.linalg.norm(gf - g) / jnp.linalg.norm(g))
        assert errs[True] < 0.9 * errs[False]


def test_faults_batched_matches_per_tile():
    """The (..., r, c) vmap path must equal per-tile application with the
    same split keys (packed-serving stacks rely on this)."""
    g = jnp.stack([_diff_target(8, s) for s in range(3)])
    key = jax.random.PRNGKey(7)
    batched = apply_stuck_faults(g, g, key, p_on=0.01, p_off=0.05,
                                 g_on=G0, g_off=0.0, remap=True)
    keys = jax.random.split(key, 3)
    for i in range(3):
        single = apply_stuck_faults(g[i], g[i], keys[i], p_on=0.01,
                                    p_off=0.05, g_on=G0, g_off=0.0,
                                    remap=True)
        np.testing.assert_allclose(np.asarray(batched[i]),
                                   np.asarray(single), rtol=1e-6)


# --------------------- e2e stuck-at campaign (satellite) --------------------

def test_remap_recovers_solve_accuracy():
    """Stuck-at campaign through ProgrammedSolver.solve: at 2% stuck-OFF
    devices, fault-aware remapping recovers essentially fault-free accuracy
    while the unmapped solver is off by several percent or worse."""
    errs = {False: [], True: []}
    for s in range(4):
        a = wishart(jax.random.PRNGKey(100 + s), 32)
        b = jax.random.normal(jax.random.PRNGKey(200 + s), (32,))
        x_ref = jnp.linalg.solve(a, b)
        for remap in (False, True):
            ni = NonidealConfig(p_stuck_off=0.02, remap_faults=remap)
            cfg = AnalogConfig(array_size=16, nonideal=ni)
            ps = blockamc.ProgrammedSolver.program(
                a, jax.random.PRNGKey(300 + s), cfg, stages=1)
            x = ps.solve(b)
            errs[remap].append(float(jnp.linalg.norm(x - x_ref)
                                     / jnp.linalg.norm(x_ref)))
    for e_plain, e_remap in zip(errs[False], errs[True]):
        assert e_plain > 0.01              # faults visibly hurt every seed
        assert e_remap < 1e-3              # remap recovers every seed
    assert np.median(errs[True]) < 0.05 * np.median(errs[False])


# ------------------------------ drift ---------------------------------------

def test_drift_unit_power_law():
    g = _diff_target(8, 0)
    np.testing.assert_allclose(
        np.asarray(drift_conductance(g, 100.0, 0.1)),
        np.asarray(g) * 100.0 ** -0.1, rtol=1e-12)
    # identity cases: no elapsed time, t0 itself, or nu = 0
    for t, nu in ((0.0, 0.1), (1.0, 0.1), (100.0, 0.0)):
        np.testing.assert_array_equal(
            np.asarray(drift_conductance(g, t, nu)), np.asarray(g))


def test_drift_error_grows_monotonically():
    """Retention drift at readout: solve error must grow monotonically in
    elapsed time (calibrated: ~1e-7 at t=0 up to ~0.4 at t=1000 s)."""
    a = wishart(jax.random.PRNGKey(0), 32)
    b = jax.random.normal(jax.random.PRNGKey(1), (32,))
    errs = []
    for t in (0.0, 10.0, 100.0, 1000.0):
        ni = NonidealConfig(drift_t=t, drift_nu=0.05)
        cfg = AnalogConfig(array_size=16, nonideal=ni)
        errs.append(_solve_err(a, b, cfg))
    assert errs[0] < 1e-4                  # no drift -> quantization floor
    assert errs[1] > 1e-2                  # drift visibly hurts
    assert all(e1 < e2 for e1, e2 in zip(errs, errs[1:]))


# --------------------------- write-verify -----------------------------------

def test_write_verify_nodal_converges():
    """Fixed-point write-verify against the nodal oracle: three iterations
    buy >= 1e4x residual reduction at n=16, r_wire=1 (calibrated 1.3e-2 at
    one iteration down to 2.8e-6 at three, 6e-10 at five)."""
    g_t = _diff_target(16, 2)
    base = float(jnp.linalg.norm(
        nodal_effective_conductance(g_t, 1.0) - g_t) / jnp.linalg.norm(g_t))
    res = {}
    for iters in (1, 3, 5):
        g = write_verify(g_t, 1.0, model="nodal", iters=iters)
        assert bool(jnp.all(g >= 0.0))     # physical conductances only
        res[iters] = float(jnp.linalg.norm(
            nodal_effective_conductance(g, 1.0) - g_t)
            / jnp.linalg.norm(g_t))
    assert res[3] < 1e-3 * base
    assert res[5] <= res[3]               # may tie at the f32 floor


def test_write_verify_rejects_unknown_model():
    with pytest.raises(ValueError):
        write_verify(_diff_target(4, 0), 1.0, model="exact")


def test_config_write_verify_e2e():
    """compensate_wire + nodal write-verify through the full solver: the
    compensated solve lands ~1e4x below the uncompensated wire error
    (calibrated 2.3e-7 vs 2.5e-3 at n=32, r_wire=1)."""
    a = wishart(jax.random.PRNGKey(0), 32)
    b = jax.random.normal(jax.random.PRNGKey(1), (32,))
    ni_raw = NonidealConfig(r_wire=1.0, wire_model="nodal")
    ni_wv = NonidealConfig(r_wire=1.0, wire_model="nodal",
                           compensate_wire=True, wv_iters=3)
    err_raw = _solve_err(a, b, AnalogConfig(array_size=16, nonideal=ni_raw))
    err_wv = _solve_err(a, b, AnalogConfig(array_size=16, nonideal=ni_wv))
    assert err_raw > 1e-3                  # wires visibly hurt uncompensated
    assert err_wv < 1e-4
    assert err_wv < 1e-2 * err_raw


# ------------------- executor equivalence under physics ---------------------

def test_reference_vs_fused_under_physics_config():
    """The four-executor contract survives the physics pipeline: reference
    and fused-arena executors agree under nodal readout + drift, because
    both consume the same programmed state through a_eff."""
    a = wishart(jax.random.PRNGKey(0), 32)
    b = jax.random.normal(jax.random.PRNGKey(1), (32,))
    ni = NonidealConfig(sigma=0.01, r_wire=1.0, wire_model="nodal",
                        drift_t=100.0, drift_nu=0.05)
    cfg = AnalogConfig(array_size=16, nonideal=ni)
    ps = blockamc.ProgrammedSolver.program(a, jax.random.PRNGKey(2), cfg,
                                           stages=1)
    x_ref = jax.jit(blockamc.execute_finalized)(ps.finalized, b)
    x_fus = ps.solve(b)
    np.testing.assert_allclose(np.asarray(x_fus), np.asarray(x_ref),
                               rtol=1e-5, atol=1e-9)
