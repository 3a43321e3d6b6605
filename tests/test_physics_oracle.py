"""Physics oracle contract: dense-numpy MNA vs the batched-JAX nodal solver.

Ground-truth chain (TESTING.md "physics oracle contract"):

    dense numpy f64 MNA  (O(n^6), n <= 32)      -- HSPICE stand-in
      == batched JAX nodal solve (O(n^4), any n) @ rtol 1e-6   [this file]
      >> first-order wire model (O(n^2), hot path)  [test_wire_validation.py]

Parity tests run under x64 (the conditioning gw/g ~ 1e4 makes f32 parity
meaningless at 1e-6); the dtype-regression test pins the dense oracle to
float64 *without* x64 enabled - the satellite fix for the old `jnp.asarray`
truncation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st


def property_cases(strategies, cases):
    """Hypothesis-or-deterministic property harness: with hypothesis the
    test explores the strategy space; without it the same body runs over a
    fixed case sweep (instead of skipping - the oracle contract must hold
    in the default tier on a bare container too)."""
    def deco(fn):
        if HAVE_HYPOTHESIS:
            return settings(max_examples=10, deadline=None)(
                given(**strategies)(fn))
        names = list(strategies)
        return pytest.mark.parametrize(
            ",".join(names),
            [tuple(c[k] for k in names) for c in cases])(fn)
    return deco

from repro.core import nonideal
from repro.data.matrices import random_rhs, wishart
from repro.kernels import ops, ref
from repro.physics import nodal

G0 = 100e-6


def _positive_array(n, seed=0, nc=None, dtype=np.float64):
    """Positive conductance array + drive vector as numpy (dtype-exact)."""
    rng = np.random.default_rng(seed)
    g = np.abs(rng.standard_normal((n, nc or n))).astype(dtype)
    g = g / g.max() * G0
    v = (np.abs(rng.standard_normal(nc or n)) + 0.1).astype(dtype)
    return g, v


# ---------------------------------------------------------------------------
# Dense-numpy vs batched-JAX parity (the acceptance bound: rtol <= 1e-6)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8, 16])
def test_mvm_parity_dense_vs_nodal(n):
    g, v = _positive_array(n, seed=n)
    with jax.enable_x64():
        i_dense = nonideal.mna_mvm_currents(g, v, 1.0)
        i_nodal = np.asarray(nodal.nodal_mvm_currents(
            jnp.asarray(g), jnp.asarray(v), 1.0))
    np.testing.assert_allclose(i_nodal, i_dense, rtol=1e-6)


@pytest.mark.parametrize("n", [2, 4, 8, 16])
def test_inv_parity_dense_vs_nodal(n):
    g, v = _positive_array(n, seed=100 + n)
    with jax.enable_x64():
        u_dense = nonideal.mna_inv_outputs(g, v, 1.0, G0)
        u_nodal = np.asarray(nodal.nodal_inv_outputs(
            jnp.asarray(g), jnp.asarray(v), 1.0, G0))
    np.testing.assert_allclose(u_nodal, u_dense, rtol=1e-6)


def test_parity_at_n32_both_modes():
    """The acceptance bound at the largest dense-feasible size."""
    g, v = _positive_array(32, seed=7)
    with jax.enable_x64():
        np.testing.assert_allclose(
            np.asarray(nodal.nodal_mvm_currents(jnp.asarray(g),
                                                jnp.asarray(v), 1.0)),
            nonideal.mna_mvm_currents(g, v, 1.0), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(nodal.nodal_inv_outputs(jnp.asarray(g),
                                               jnp.asarray(v), 1.0, G0)),
            nonideal.mna_inv_outputs(g, v, 1.0, G0), rtol=1e-6)


@pytest.mark.parametrize("shape", [(8, 5), (5, 8), (1, 6), (6, 1)])
def test_mvm_parity_rectangular(shape):
    """The WL-elimination handles nr != nc (and degenerate 1-wide arrays)."""
    nr, nc = shape
    g, v = _positive_array(nr, seed=nr * 31 + nc, nc=nc)
    with jax.enable_x64():
        np.testing.assert_allclose(
            np.asarray(nodal.nodal_mvm_currents(jnp.asarray(g),
                                                jnp.asarray(v), 1.0)),
            nonideal.mna_mvm_currents(g, v, 1.0), rtol=1e-6)


def test_effective_conductance_is_exact_transfer_matrix():
    """H = sense^T L^-1 drive: columns match unit-drive dense currents, and
    H @ v reproduces the nodal currents for arbitrary drives (linearity)."""
    n = 12
    g, v = _positive_array(n, seed=3)
    with jax.enable_x64():
        h = np.asarray(nodal.nodal_effective_conductance(jnp.asarray(g), 1.0))
        h_dense = np.stack(
            [nonideal.mna_mvm_currents(g, np.eye(n)[:, j], 1.0)
             for j in range(n)], axis=1)
        np.testing.assert_allclose(h, h_dense, rtol=1e-6)
        np.testing.assert_allclose(
            h @ v,
            np.asarray(nodal.nodal_mvm_currents(jnp.asarray(g),
                                                jnp.asarray(v), 1.0)),
            rtol=1e-9)


def test_multi_rhs_matches_column_loop():
    n, k = 10, 4
    g, _ = _positive_array(n, seed=5)
    rng = np.random.default_rng(6)
    vs = np.abs(rng.standard_normal((n, k))) + 0.1
    with jax.enable_x64():
        block = np.asarray(nodal.nodal_mvm_currents(
            jnp.asarray(g), jnp.asarray(vs), 1.0))
        for j in range(k):
            np.testing.assert_allclose(
                block[:, j],
                np.asarray(nodal.nodal_mvm_currents(
                    jnp.asarray(g), jnp.asarray(vs[:, j]), 1.0)),
                rtol=1e-10)


# ---------------------------------------------------------------------------
# Batch semantics: the batch axis is exactly a loop of singles
# ---------------------------------------------------------------------------

def test_batch_axis_is_loop_of_singles():
    b, n = 5, 8
    rng = np.random.default_rng(8)
    g = jnp.asarray(np.abs(rng.standard_normal((b, n, n))).astype(np.float32)
                    * G0)
    v = jnp.asarray((np.abs(rng.standard_normal(n)) + 0.1).astype(np.float32))
    batched = nodal.nodal_mvm_batched(g, v, 1.0)
    for i in range(b):
        np.testing.assert_allclose(
            np.asarray(batched[i]),
            np.asarray(nodal.nodal_mvm_currents(g[i], v, 1.0)),
            rtol=2e-5)
    # chunked execution (with a padding remainder) is the same computation
    chunked = nodal.nodal_mvm_batched(g, v, 1.0, chunk=2)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(batched),
                               rtol=1e-6)
    # batched effective conductance == per-instance H, with B == nc on
    # purpose: pins the identity-drive broadcast against the (B, nc)
    # vector/multi-drive ambiguity
    g8 = jnp.asarray(np.abs(rng.standard_normal((n, n, n))).astype(np.float32)
                     * G0)
    hb = nodal.nodal_effective_conductance_batched(g8, 1.0)
    for i in range(n):
        np.testing.assert_allclose(
            np.asarray(hb[i]),
            np.asarray(nodal.nodal_effective_conductance(g8[i], 1.0)),
            rtol=2e-5)


def test_inv_batched_matches_singles():
    b, n = 3, 8
    rng = np.random.default_rng(9)
    g = jnp.asarray(np.abs(rng.standard_normal((b, n, n))).astype(np.float32)
                    * G0)
    v = jnp.asarray((np.abs(rng.standard_normal(n)) + 0.1).astype(np.float32))
    batched = nodal.nodal_inv_batched(g, v, 1.0, G0)
    for i in range(b):
        np.testing.assert_allclose(
            np.asarray(batched[i]),
            np.asarray(nodal.nodal_inv_outputs(g[i], v, 1.0, G0)),
            rtol=2e-4)


# ---------------------------------------------------------------------------
# Pallas kernel parity (interpret mode on CPU - the tested contract)
# ---------------------------------------------------------------------------

def test_kernel_sweeps_match_jnp_scans():
    b, n = 4, 8
    rng = np.random.default_rng(10)
    g = jnp.asarray(np.abs(rng.standard_normal((b, n, n))).astype(np.float32)
                    * G0)
    v = jnp.asarray((np.abs(rng.standard_normal(n)) + 0.1).astype(np.float32))
    out_jnp = nodal.nodal_mvm_batched(g, v, 1.0)
    out_ker = nodal.nodal_mvm_batched(g, v, 1.0, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out_ker), np.asarray(out_jnp),
                               rtol=1e-5)


def test_kernel_ops_vs_ref_oracle():
    """Direct kernel wrapper vs the pure-jnp oracle, ragged (pads to 128)."""
    rng = np.random.default_rng(11)
    minv = jnp.asarray(rng.standard_normal((3, 5, 6, 6)).astype(np.float32))
    rhs = jnp.asarray(rng.standard_normal((3, 5, 6, 2)).astype(np.float32))
    out = ops.block_tridiag_solve(minv, rhs, gw=0.7)
    want = ref.block_tridiag_solve_ref(minv, rhs, gw=0.7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Dense-oracle dtype regression (satellite fix)
# ---------------------------------------------------------------------------

def test_mna_oracle_returns_float64_without_x64():
    """The dense oracle must not lose precision to jax's default f32: it
    used to return via jnp.asarray, truncating the f64 solve silently."""
    g, v = _positive_array(8, seed=12)
    i = nonideal.mna_mvm_currents(jnp.asarray(g, dtype=jnp.float32), v, 1.0)
    assert isinstance(i, np.ndarray) and i.dtype == np.float64
    u = nonideal.mna_inv_outputs(jnp.asarray(g, dtype=jnp.float32), v, 1.0, G0)
    assert isinstance(u, np.ndarray) and u.dtype == np.float64
    # and the values carry genuine f64 information (not an f32 round-trip)
    assert not np.array_equal(i, i.astype(np.float32).astype(np.float64))


# ---------------------------------------------------------------------------
# Promoted from tests/test_extensions.py (the formerly lone MNA usage)
# ---------------------------------------------------------------------------

def test_compensation_against_exact_mna():
    """Compensated programming cancels the wire error in the exact circuit."""
    n = 16
    a = jnp.abs(wishart(jax.random.PRNGKey(1), n))
    g = a / jnp.max(a) * G0
    v = jnp.abs(random_rhs(jax.random.PRNGKey(2), n)) + 0.1
    i_ideal = np.asarray(g @ v)
    i_raw = np.asarray(nonideal.mna_mvm_currents(g, v, 1.0))
    g_prog = nonideal.compensate_conductances(g, 1.0)
    i_comp = np.asarray(nonideal.mna_mvm_currents(g_prog, v, 1.0))
    raw_err = np.linalg.norm(i_raw - i_ideal)
    comp_err = np.linalg.norm(i_comp - i_ideal)
    assert comp_err < 0.2 * raw_err


# ---------------------------------------------------------------------------
# Hypothesis properties
# ---------------------------------------------------------------------------

@property_cases(
    dict(seed=st.integers(0, 2 ** 16), n=st.integers(2, 10)),
    [dict(seed=0, n=2), dict(seed=11, n=5), dict(seed=77, n=8),
     dict(seed=1234, n=10)])
def test_property_ideal_limit(seed, n):
    """r_seg -> 0 recovers the ideal MVM g @ v."""
    g, v = _positive_array(n, seed=seed)
    with jax.enable_x64():
        i = np.asarray(nodal.nodal_mvm_currents(jnp.asarray(g),
                                                jnp.asarray(v), 1e-9))
        np.testing.assert_allclose(i, g @ v, rtol=1e-5)


@property_cases(
    dict(seed=st.integers(0, 2 ** 16), n=st.integers(2, 6),
         r=st.floats(min_value=0.1, max_value=2.0)),
    [dict(seed=1, n=2, r=0.1), dict(seed=22, n=4, r=1.0),
     dict(seed=333, n=6, r=2.0)])
def test_property_laplacian_symmetric_psd(seed, n, r):
    """The full crossbar Laplacian is symmetric positive definite (the
    ground couplings through driver and sense segments kill the nullspace)."""
    g, _ = _positive_array(n, seed=seed)
    L, _, _ = nonideal._crossbar_laplacian(g, r)
    np.testing.assert_allclose(L, L.T, rtol=0, atol=0)
    assert np.linalg.eigvalsh(L).min() > 0.0


@property_cases(
    dict(seed=st.integers(0, 2 ** 16), n=st.integers(2, 6),
         r=st.floats(min_value=0.1, max_value=2.0)),
    [dict(seed=2, n=2, r=0.1), dict(seed=44, n=4, r=1.0),
     dict(seed=555, n=6, r=2.0)])
def test_property_schur_blocks_spd(seed, n, r):
    """Each WL-eliminated diagonal block S_i stays symmetric positive
    definite - the invariant the block-Thomas factor relies on."""
    g, _ = _positive_array(n, seed=seed)
    with jax.enable_x64():
        s = np.asarray(nodal.row_schur_blocks(jnp.asarray(g), r))
    for i in range(n):
        np.testing.assert_allclose(s[i], s[i].T, rtol=0, atol=1e-18)
        assert np.linalg.eigvalsh(s[i]).min() > 0.0


# ---------------------------------------------------------------------------
# Monte-Carlo scale (acceptance: 64 crossbars at n = 256, one dispatch)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mc_batch_n256_one_dispatch():
    """A 64-crossbar Monte-Carlo batch at n = 256 runs as ONE jitted
    dispatch (chunked lax.map inside the jit bounds the Minv stack to
    ~1 GB), and the chunked result matches an unchunked single solve."""
    b, n = 64, 256
    key = jax.random.PRNGKey(0)
    g = jax.random.uniform(key, (b, n, n), minval=0.0, maxval=G0)
    v = jnp.ones((n,), jnp.float32)

    solve = jax.jit(lambda gs, vs: nodal.nodal_mvm_batched(
        gs, vs, 1.0, chunk=4))
    out = np.asarray(solve(g, v))
    assert out.shape == (b, n)
    assert np.all(np.isfinite(out))
    # wire drop: currents strictly below ideal, same order of magnitude
    ideal = np.asarray(jnp.einsum("brc,c->br", g, v))
    assert np.all(out < ideal)
    assert np.median(out / ideal) > 0.1
    # spot-check one instance against the single-crossbar path
    single = np.asarray(nodal.nodal_mvm_currents(g[0], v, 1.0))
    np.testing.assert_allclose(out[0], single, rtol=1e-4)
