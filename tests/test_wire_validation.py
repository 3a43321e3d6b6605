"""Differential validation: first-order wire model vs the nodal oracle.

The serving hot path prices IR drop with the O(n^2) first-order
perturbation (`nonideal.effective_conductance`); the physics subsystem
provides the exact nodal answer (`repro.physics.nodal`).  This suite pins
the cheap model's error *envelope* against the oracle across array size n
and wire resistance r, so any future change to either model that moves
the gap gets caught.

Measured gap (‖H_fo − H‖ / ‖H − g‖, i.e. error relative to the wire
effect itself, dense uniform targets at half scale):

      n \\ r    0.25      1.0      2.0
        8     0.0005   0.0021   0.0042
       16     0.0011   0.0044   0.0113
       32     0.0052   0.0218   0.0388
       64     0.0185   0.0617   0.1202

The envelope asserts ~2x these values; the monotone tests pin the shape
(gap grows with both n and r — the first-order expansion in r·g·n leaves
its validity region as arrays scale, the reason fig9's oracle sweep runs
the nodal model at n >= 64).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blockamc, nonideal
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart
from repro.physics import nodal_effective_conductance

G0 = 100e-6


def _gap_and_effect(n, r_wire, seed=0):
    """Returns (‖H_fo − H‖/‖H − g‖, ‖H − g‖/‖g‖) in float64."""
    rng = np.random.default_rng(seed)
    g_np = rng.uniform(0.0, 0.5, (n, n)) * G0
    with jax.enable_x64():
        g = jnp.asarray(g_np, dtype=jnp.float64)
        h = nodal_effective_conductance(g, r_wire)
        h_fo = nonideal.effective_conductance(g, r_wire)
        effect = float(jnp.linalg.norm(h - g))
        gap = float(jnp.linalg.norm(h_fo - h))
        return gap / effect, effect / float(jnp.linalg.norm(g))


@pytest.mark.parametrize("n,r_wire,bound", [
    (8, 0.25, 1e-3), (8, 1.0, 5e-3), (8, 2.0, 1e-2),
    (16, 1.0, 1e-2), (16, 2.0, 2.5e-2),
    (32, 1.0, 5e-2), (32, 2.0, 8e-2),
])
def test_first_order_gap_envelope(n, r_wire, bound):
    gap, _ = _gap_and_effect(n, r_wire)
    assert gap < bound


def test_gap_grows_with_array_size():
    gaps = [_gap_and_effect(n, 1.0)[0] for n in (8, 16, 32)]
    assert all(a < b for a, b in zip(gaps, gaps[1:]))


def test_gap_grows_with_wire_resistance():
    gaps = [_gap_and_effect(16, r)[0] for r in (0.25, 1.0, 2.0)]
    assert all(a < b for a, b in zip(gaps, gaps[1:]))


def test_wire_effect_itself_is_significant():
    """Sanity anchor: the quantity the models disagree about is not noise —
    at n=32, r=1 the wire effect moves H by ~2% of ‖g‖."""
    _, effect = _gap_and_effect(32, 1.0)
    assert effect > 5e-3


@pytest.mark.slow
def test_first_order_leaves_validity_at_n64():
    """At n=64 the cheap model's error reaches >3% of the wire effect at
    r=1 and ~12% at r=2 — the regime fig9's nightly oracle sweep covers."""
    gap1, _ = _gap_and_effect(64, 1.0)
    gap2, _ = _gap_and_effect(64, 2.0)
    assert 0.03 < gap1 < 0.12
    assert 0.06 < gap2 < 0.25
    assert gap1 < gap2


# ---------------------- solver-level recalibration --------------------------

def test_solver_error_first_order_vs_nodal():
    """fig9 recalibration at solve level: inside the validity envelope
    (n=32 tiled to 16x16 arrays, r=1) pricing wires with the cheap model
    vs the oracle must give nearly the same end-to-end solve error
    (calibrated 2.523e-3 vs 2.520e-3)."""
    a = wishart(jax.random.PRNGKey(0), 32)
    b = jax.random.normal(jax.random.PRNGKey(1), (32,))
    x_ref = jnp.linalg.solve(a, b)
    errs = {}
    for model in ("first_order", "nodal"):
        ni = NonidealConfig(r_wire=1.0, wire_model=model)
        cfg = AnalogConfig(array_size=16, nonideal=ni)
        x = blockamc.solve(a, b, jax.random.PRNGKey(2), cfg, stages=1)
        errs[model] = float(jnp.linalg.norm(x - x_ref)
                            / jnp.linalg.norm(x_ref))
    assert errs["nodal"] > 1e-4            # wires actually in play
    assert abs(errs["first_order"] - errs["nodal"]) < 0.2 * errs["nodal"]


def test_wire_model_none_disables_wires():
    """wire_model='none' must ignore r_wire entirely (control for the
    differential suite: the gap measured above comes from the wire model,
    not from programming noise)."""
    a = wishart(jax.random.PRNGKey(0), 32)
    b = jax.random.normal(jax.random.PRNGKey(1), (32,))
    ni_off = NonidealConfig(r_wire=1.0, wire_model="none")
    ni_zero = NonidealConfig(r_wire=0.0)
    cfg_off = AnalogConfig(array_size=16, nonideal=ni_off)
    cfg_zero = AnalogConfig(array_size=16, nonideal=ni_zero)
    x_off = blockamc.solve(a, b, jax.random.PRNGKey(2), cfg_off, stages=1)
    x_zero = blockamc.solve(a, b, jax.random.PRNGKey(2), cfg_zero, stages=1)
    np.testing.assert_allclose(np.asarray(x_off), np.asarray(x_zero),
                               rtol=1e-6)
