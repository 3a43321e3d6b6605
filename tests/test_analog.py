"""AMC circuit primitive tests: signs, mapping, quantisation, tiling, gain."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analog
from repro.core.analog import AnalogConfig, map_matrix, map_tiled
from repro.data.matrices import wishart, random_rhs

KEY = jax.random.PRNGKey(0)
KA, KB, KN = jax.random.split(KEY, 3)
CFG = AnalogConfig(array_size=16)


def test_mvm_sign_and_value():
    a = wishart(KA, 16)
    v = random_rhs(KB, 16)
    scale = 1.0 / jnp.max(jnp.abs(a))
    pair = map_matrix(a, KN, CFG, scale)
    out = analog.amc_mvm(pair, v, CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(-(a * scale) @ v),
                               rtol=1e-4, atol=1e-6)


def test_inv_sign_and_value():
    a = wishart(KA, 16)
    v = random_rhs(KB, 16)
    scale = 1.0 / jnp.max(jnp.abs(a))
    pair = map_matrix(a, KN, CFG, scale)
    out = analog.amc_inv(pair, v, CFG)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(-jnp.linalg.solve(a * scale, v)),
        rtol=1e-3, atol=1e-5)


def test_differential_split_nonnegative():
    """A = A+ - A- with both arrays' conductances physical (>= 0)."""
    a = wishart(KA, 16) - 0.2   # force signed entries
    scale = 1.0 / jnp.max(jnp.abs(a))
    pair = map_matrix(a, KN, CFG, scale)
    assert bool(jnp.all(pair.gpos >= 0))
    assert bool(jnp.all(pair.gneg >= 0))
    # exactly one of the differential pair is nonzero per cell (ideal map)
    assert bool(jnp.all((pair.gpos * pair.gneg) == 0.0))
    np.testing.assert_allclose(np.asarray(pair.a_eff(CFG)),
                               np.asarray(a * scale), rtol=1e-5, atol=1e-7)


def test_tiled_mvm_equals_dense():
    """Partitioned MVM over 4 tiles == single-array MVM (refs [13]-[15])."""
    a = wishart(KA, 32)
    v = random_rhs(KB, 32)
    scale = 1.0 / jnp.max(jnp.abs(a))
    grid = map_tiled(a, KN, CFG, scale)   # 2x2 grid of 16-tiles
    assert len(grid) == 2 and len(grid[0]) == 2
    out = analog.amc_mvm_tiled(grid, v, CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(-(a * scale) @ v),
                               rtol=1e-4, atol=1e-6)


def test_tiled_mvm_ragged():
    """Non-multiple sizes produce edge tiles of the remainder size."""
    a = wishart(KA, 20)
    v = random_rhs(KB, 20)
    scale = 1.0 / jnp.max(jnp.abs(a))
    grid = map_tiled(a, KN, CFG, scale)   # 16+4 per side
    assert grid[0][0].shape == (16, 16)
    assert grid[1][1].shape == (4, 4)
    out = analog.amc_mvm_tiled(grid, v, CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(-(a * scale) @ v),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("bits,tol", [(4, 0.15), (8, 0.01), (12, 1e-3)])
def test_quantization_error_scales_with_bits(bits, tol):
    v = random_rhs(KB, 256)
    vq = analog.quantize(v, bits, 1.0)
    err = float(jnp.max(jnp.abs(v - vq)))
    assert err <= 2.0 / (2 ** bits - 1)
    assert err <= tol


def test_quantization_ideal_passthrough():
    v = random_rhs(KB, 64)
    np.testing.assert_array_equal(np.asarray(analog.quantize(v, None, 1.0)),
                                  np.asarray(v))


def test_finite_gain_error_grows_with_array_size():
    """Summing-node error scales with row conductance sum (paper Fig. 6c)."""
    errs = []
    for n in [16, 64, 256]:
        a = wishart(KA, n)
        v = random_rhs(KB, n)
        scale = 1.0 / jnp.max(jnp.abs(a))
        cfg = AnalogConfig(array_size=n, opa_gain=1e4)
        pair = map_matrix(a, KN, cfg, scale)
        out = analog.amc_inv(pair, v, cfg)
        ref = -jnp.linalg.solve(a * scale, v)
        errs.append(float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref)))
    assert errs[0] < errs[1] < errs[2]


def test_finite_gain_converges_to_ideal():
    a = wishart(KA, 32)
    v = random_rhs(KB, 32)
    scale = 1.0 / jnp.max(jnp.abs(a))
    ref = -jnp.linalg.solve(a * scale, v)
    prev = None
    for gain in [1e3, 1e5, 1e7]:
        cfg = AnalogConfig(array_size=32, opa_gain=gain)
        pair = map_matrix(a, KN, cfg, scale)
        err = float(jnp.linalg.norm(analog.amc_inv(pair, v, cfg) - ref))
        if prev is not None:
            assert err < prev
        prev = err


def test_quantizer_single_source_of_truth():
    """The converter quantiser has one definition (core/quantization.py):
    the circuit model, the Pallas kernel body and the jnp oracles must all
    bind the same function - and it must behave identically through each
    import path (the copy-paste-twin regression guard)."""
    from repro.core import quantization
    from repro.kernels import crossbar_mvm, ref
    assert analog.quantize is quantization.quantize
    assert crossbar_mvm._quantize is quantization.quantize
    assert ref._quantize is quantization.quantize
    v = random_rhs(KB, 128) * 1.5        # exercise clipping
    for bits in (None, 4, 8):
        np.testing.assert_array_equal(
            np.asarray(analog.quantize(v, bits, 1.0)),
            np.asarray(quantization.quantize(v, bits, 1.0)))


@pytest.mark.parametrize("lead", [(6,), (2, 3), (5, 2, 3)])
def test_tilegrid_a_eff_batched_wire_model(lead):
    """TileGrid.a_eff with leading batch axes must equal per-pair
    CrossbarPair.a_eff tile-for-tile under the first-order wire model
    (the vmapped-reshape path the flat executor's stacks rely on)."""
    s = 8
    cfg = AnalogConfig(array_size=s,
                       nonideal=analog.NonidealConfig(sigma=0.05, r_wire=1.0))
    kp, kn = jax.random.split(KN)
    gpos = jax.random.uniform(kp, lead + (s, s), maxval=cfg.g0)
    gneg = jax.random.uniform(kn, lead + (s, s), maxval=cfg.g0)
    grid = analog.TileGrid(gpos, gneg, jnp.float32(1.0), cfg.g0)
    a_eff = grid.a_eff(cfg)
    assert a_eff.shape == lead + (s, s)
    flat_p = gpos.reshape((-1, s, s))
    flat_n = gneg.reshape((-1, s, s))
    flat_eff = a_eff.reshape((-1, s, s))
    for i in range(flat_p.shape[0]):
        pair = analog.CrossbarPair(flat_p[i], flat_n[i], jnp.float32(1.0),
                                   cfg.g0)
        # a_eff = (gpos_eff - gneg_eff) / g0 subtracts two terms in [0, 1]:
        # the vmapped and direct paths may round each term differently, so
        # the absolute error is bounded by 2 f32 ulps of 1, not by the
        # (possibly tiny) difference itself.
        np.testing.assert_allclose(np.asarray(flat_eff[i]),
                                   np.asarray(pair.a_eff(cfg)),
                                   rtol=1e-6,
                                   atol=2 * np.finfo(np.float32).eps)


def test_tilegrid_a_eff_unbatched_matches_pair():
    """No leading axes: TileGrid.a_eff takes the direct (non-vmapped) wire
    path and must still equal CrossbarPair.a_eff exactly."""
    s = 8
    cfg = AnalogConfig(array_size=s,
                       nonideal=analog.NonidealConfig(sigma=0.05, r_wire=1.0))
    kp, kn = jax.random.split(KN)
    gpos = jax.random.uniform(kp, (s, s), maxval=cfg.g0)
    gneg = jax.random.uniform(kn, (s, s), maxval=cfg.g0)
    grid = analog.TileGrid(gpos, gneg, jnp.float32(1.0), cfg.g0)
    pair = analog.CrossbarPair(gpos, gneg, jnp.float32(1.0), cfg.g0)
    np.testing.assert_array_equal(np.asarray(grid.a_eff(cfg)),
                                  np.asarray(pair.a_eff(cfg)))
