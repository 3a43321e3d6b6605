"""Differentiable solver contract (TESTING.md "differentiable solver
contract"): implicit-diff VJP through the arena executor and its riders.

Covers:

  * finite-difference gradient checks of `jax.grad` through
    `ProgrammedSolver.solve` across the full grid stages {0, 1, 2} x
    nonideality {ideal, sigma, wire} x rhs {(n,), (n, k)};
  * the packed (multi-tenant) executor's gradient;
  * the implicit-diff VJP around `solve_refined` against the closed-form
    adjoint (lambda = A^-T w, A_bar = -lambda x^T);
  * the backward pass re-programs nothing: the grad jaxpr contains no
    factorization (`lu`) and no `while_loop` primitives;
  * straight-through converter gradients (surrogate = gradient of the
    clip; primal bit-identical);
  * `AnalogPreconditioner` as a pytree under jit/grad/vmap: array-only
    leaves, hashable static aux, and a retrace guard across re-programmed
    instances (the PR 4 pattern);
  * seed sanitization: a fully-faulted (stuck-at) crossbar yields a
    non-finite analog seed, and `solve_refined` still converges from the
    zeroed seed;
  * wire calibration: gradient descent through the solver recovers a
    planted wire resistance from the exact nodal oracle to < 5%.

All tolerance-sensitive checks run in f64 via `enable_x64` - the contract
is about *structure* of the gradients; f32 only adds rounding noise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.calib import calibrate_wire
from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.core.quantization import quantize
from repro.data.matrices import random_rhs, wishart
from repro.hybrid import AnalogPreconditioner, pcg, pcg_fixed, solve_refined
from repro.hybrid.operators import matvec_from_dense

KEY = jax.random.PRNGKey(21)
KA, KB, KN, KW = jax.random.split(KEY, 4)

N = 8

NONIDEAL_GRID = {
    "ideal": NonidealConfig(),
    "sigma": NonidealConfig(sigma=0.05),
    "wire": NonidealConfig(sigma=0.01, r_wire=1.0),
}


def _fd_grad(f, x, eps=1e-5):
    """Central finite-difference gradient of scalar f at x, elementwise."""
    x = np.asarray(x)
    flat = x.ravel()
    g = np.zeros_like(flat)
    for i in range(flat.size):
        xp = flat.copy()
        xm = flat.copy()
        xp[i] += eps
        xm[i] -= eps
        g[i] = (f(jnp.asarray(xp.reshape(x.shape))) -
                f(jnp.asarray(xm.reshape(x.shape)))) / (2 * eps)
    return g.reshape(x.shape)


def _spd(key, n, dtype):
    a = jax.random.normal(key, (n, n), dtype)
    return a @ a.T + n * jnp.eye(n, dtype=dtype)


# ------------------- FD grid through ProgrammedSolver ----------------------

@pytest.mark.parametrize("stages", [0, 1, 2])
@pytest.mark.parametrize("ni", sorted(NONIDEAL_GRID))
@pytest.mark.parametrize("shape", ["vec", "mat"])
def test_grad_through_solve_matches_fd(stages, ni, shape):
    """jax.grad of w . solve(b) wrt b matches central differences."""
    with jax.enable_x64():
        a = _spd(KA, N, jnp.float64)
        cfg = AnalogConfig(array_size=N, nonideal=NONIDEAL_GRID[ni])
        solver = blockamc.ProgrammedSolver.program(a, KN, cfg, stages=stages)
        b = (random_rhs(KB, N) if shape == "vec"
             else jax.random.normal(KB, (N, 3))).astype(jnp.float64)
        w = jax.random.normal(KW, b.shape, jnp.float64)

        def loss(bb):
            return jnp.sum(w * solver.solve(bb))

        g = jax.grad(loss)(b)
        fd = _fd_grad(lambda bb: float(loss(bb)), b)
        np.testing.assert_allclose(np.asarray(g), fd, rtol=1e-4, atol=1e-9)


def test_grad_through_packed_executor_matches_fd():
    """The packed multi-tenant executor carries gradients per instance."""
    with jax.enable_x64():
        cfg = AnalogConfig(array_size=4)
        solvers = [
            blockamc.ProgrammedSolver.program(
                _spd(jax.random.fold_in(KA, i), N, jnp.float64),
                jax.random.fold_in(KN, i), cfg)
            for i in range(2)
        ]
        pp = blockamc.pack_arena_plans([s.arena for s in solvers])
        bs = jax.random.normal(KB, (2, N, 2), jnp.float64)
        w = jax.random.normal(KW, bs.shape, jnp.float64)

        def loss(bb):
            return jnp.sum(w * blockamc.execute_arena_packed(pp, bb))

        g = jax.grad(loss)(bs)
        fd = _fd_grad(lambda bb: float(loss(bb)), bs)
        np.testing.assert_allclose(np.asarray(g), fd, rtol=1e-4, atol=1e-9)
        # per-instance isolation: instance 0's grad is independent of
        # instance 1's rhs (block-diagonal Jacobian)
        bs2 = bs.at[1].mul(3.0)
        np.testing.assert_allclose(np.asarray(jax.grad(loss)(bs2)[0]),
                                   np.asarray(g[0]), rtol=1e-12)


# ----------------- implicit diff around solve_refined ----------------------

def test_grad_through_solve_refined_matches_analytic_adjoint():
    """IFT adjoint: d(w.x)/db = A^-T w, d(w.x)/dA = -(A^-T w) x^T."""
    with jax.enable_x64():
        n = 12
        a = _spd(KA, n, jnp.float64)
        b = random_rhs(KB, n).astype(jnp.float64)
        w = jax.random.normal(KW, (n,), jnp.float64)
        cfg = AnalogConfig(array_size=8)
        precond = AnalogPreconditioner.program(a, KN, cfg)

        def loss(aa, bb):
            x, _ = solve_refined(aa, bb, precond, method="cg", tol=1e-12,
                                 maxiter=600, use_precond=False)
            return jnp.sum(w * x)

        g_a, g_b = jax.grad(loss, argnums=(0, 1))(a, b)
        lam = np.linalg.solve(np.asarray(a).T, np.asarray(w))
        x = np.linalg.solve(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(g_b), lam, rtol=1e-7)
        np.testing.assert_allclose(np.asarray(g_a), -np.outer(lam, x),
                                   rtol=1e-6, atol=1e-10)


def test_pcg_fixed_matches_pcg_and_differentiates():
    """pcg_fixed == pcg(tol=0, maxiter=k) numerically, and grads flow."""
    with jax.enable_x64():
        n = 16
        a = _spd(KA, n, jnp.float64)
        bt = jax.random.normal(KB, (3, n), jnp.float64)
        mv = matvec_from_dense(a)
        ref = pcg(mv, bt, tol=0.0, maxiter=6)
        fix = pcg_fixed(mv, bt, iters=6)
        np.testing.assert_allclose(np.asarray(fix.x), np.asarray(ref.x),
                                   rtol=1e-12)

        g = jax.grad(lambda bb: jnp.sum(pcg_fixed(mv, bb, iters=6).x))(bt)
        assert bool(jnp.all(jnp.isfinite(g))) and float(
            jnp.abs(g).max()) > 0.0


# ----------------------- no re-programming in backward ---------------------

def _collect_primitives(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.add(eqn.primitive.name)
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for sub in vals:
                if hasattr(sub, "jaxpr"):       # ClosedJaxpr
                    _collect_primitives(sub.jaxpr, acc)
                elif hasattr(sub, "eqns"):      # raw Jaxpr
                    _collect_primitives(sub, acc)
    return acc


def test_backward_pass_reprograms_nothing():
    """The grad jaxpr through the arena executor holds no factorization
    (`lu` runs at programming/compile time only) and no while_loop - the
    backward is one transposed cascade, ~1 forward solve."""
    with jax.enable_x64():
        a = _spd(KA, N, jnp.float64)
        cfg = AnalogConfig(array_size=4, nonideal=NONIDEAL_GRID["wire"])
        solver = blockamc.ProgrammedSolver.program(a, KN, cfg)
        b = random_rhs(KB, N).astype(jnp.float64)

        def loss(bb):
            return jnp.sum(blockamc.execute_arena(solver.arena, bb))

        prims = _collect_primitives(
            jax.make_jaxpr(jax.grad(loss))(b).jaxpr, set())
        assert "lu" not in prims, prims
        assert "while" not in prims, prims


# ------------------------- straight-through converters ---------------------

def test_quantize_straight_through_gradient():
    v = jnp.asarray([-1.4, -0.6, 0.0, 0.3, 0.99, 1.7], jnp.float32)
    out = quantize(v, 8, 1.0)
    # primal: plain clip+round quantiser, bit-identical to the pre-STE form
    levels = 2 ** 8 - 1
    step = 2.0 / levels
    np.testing.assert_array_equal(
        np.asarray(out),
        np.round(np.clip(np.asarray(v), -1.0, 1.0) / step) * step)
    # surrogate: gradient of the clip (1 inside full-scale, 0 outside)
    g = jax.grad(lambda u: jnp.sum(quantize(u, 8, 1.0)))(v)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.asarray([0., 1., 1., 1., 1., 0.],
                                             np.float32))


def test_grad_flows_through_quantized_converters():
    """With real DAC/ADC bits the solver still yields finite, useful
    gradients (STE), where the exact derivative would be zero a.e."""
    with jax.enable_x64():
        a = _spd(KA, N, jnp.float64)
        cfg = AnalogConfig(array_size=N, dac_bits=10, adc_bits=10,
                           v_fullscale=4.0)
        solver = blockamc.ProgrammedSolver.program(a, KN, cfg)
        b = 0.1 * random_rhs(KB, N).astype(jnp.float64)
        g = jax.grad(lambda bb: jnp.sum(solver.solve(bb)))(b)
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.abs(g).max()) > 0.0


# ----------------------- preconditioner pytree audit -----------------------

def _program_pair():
    cfg = AnalogConfig(array_size=4)
    a = _spd(KA, N, jnp.float32)
    return (AnalogPreconditioner.program(a, jax.random.fold_in(KN, 0), cfg),
            AnalogPreconditioner.program(a, jax.random.fold_in(KN, 1), cfg))


def test_preconditioner_pytree_leaves_are_arrays_only():
    p1, p2 = _program_pair()
    leaves, treedef = jax.tree_util.tree_flatten(p1)
    # every leaf is a jax array (calibratable data or int plan arrays);
    # static metadata (mode, level/window tuples) must live in aux_data
    assert leaves and all(isinstance(l, jax.Array) for l in leaves)
    hash(treedef)  # aux_data must stay hashable (jit cache key)
    assert treedef == jax.tree_util.tree_flatten(p2)[1]
    # differentiable leaves are exactly the inexact ones; int leaves
    # (pivots, window programs) ride along but take no cotangent
    assert any(jnp.issubdtype(l.dtype, jnp.inexact) for l in leaves)


def test_preconditioner_retrace_guard_across_reprogram():
    """Re-programming (same matrix, new key) must hit the same jit cache
    entry: structure and aux are key-stable (the PR 4 executor pattern)."""
    apply = jax.jit(lambda p, v: p(v))
    if not hasattr(apply, "_cache_size"):
        pytest.skip("jax.jit cache introspection unavailable")
    p1, p2 = _program_pair()
    v = random_rhs(KB, N)
    apply(p1, v).block_until_ready()
    before = apply._cache_size()
    apply(p2, v).block_until_ready()
    apply(p1, 2.0 * v).block_until_ready()
    assert apply._cache_size() == before


def test_preconditioner_composes_with_grad_and_vmap():
    p1, _ = _program_pair()
    v = random_rhs(KB, N)
    g = jax.grad(lambda u: jnp.sum(p1(u)))(v)
    assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).max()) > 0.0
    vs = jnp.stack([v, 2.0 * v, -v])
    batched = jax.vmap(p1)(vs)
    np.testing.assert_allclose(np.asarray(batched[1]),
                               np.asarray(p1(2.0 * v)), rtol=1e-6)


# --------------------------- seed sanitization -----------------------------

def test_stuck_at_seed_is_sanitized_per_column():
    """A fully stuck-OFF crossbar programs a singular effective operator;
    the analog seed goes non-finite, and `solve_refined` must degrade to
    the zero seed instead of answering NaN."""
    with jax.enable_x64():
        n = 8
        a = _spd(KA, n, jnp.float64)
        cfg = AnalogConfig(array_size=n, nonideal=NonidealConfig(
            p_stuck_off=1.0, g_stuck_off=0.0))
        precond = AnalogPreconditioner.program(a, KN, cfg)
        b = random_rhs(KB, n).astype(jnp.float64)
        seed = precond(b)
        assert not bool(jnp.all(jnp.isfinite(seed)))   # the hazard is real
        x, res = solve_refined(a, b, precond, method="cg", tol=1e-10,
                               maxiter=400, use_precond=False)
        assert bool(jnp.all(jnp.isfinite(x)))
        assert bool(res.converged)
        np.testing.assert_allclose(np.asarray(x),
                                   np.linalg.solve(np.asarray(a),
                                                   np.asarray(b)),
                                   rtol=1e-6)


# ------------------------------ calibration --------------------------------

def test_wire_grad_matches_fd():
    """d(solver output)/d(r_wire) through finalize -> arena matches FD."""
    with jax.enable_x64():
        a = _spd(KA, N, jnp.float64)
        cfg = AnalogConfig(array_size=4)
        fplan = blockamc.compile_plan(blockamc.build_plan(a, KN, cfg))
        b = random_rhs(KB, N).astype(jnp.float64)

        def out_at(r):
            fin = blockamc.finalize(fplan, cfg, r_wire=r)
            return jnp.sum(blockamc.execute_arena(
                blockamc.compile_arena(fin), b))

        g = jax.grad(out_at)(jnp.asarray(1.0, jnp.float64))
        eps = 1e-4
        fd = (float(out_at(jnp.asarray(1.0 + eps))) -
              float(out_at(jnp.asarray(1.0 - eps)))) / (2 * eps)
        np.testing.assert_allclose(float(g), fd, rtol=1e-5)


def test_wire_calibration_recovers_planted_resistance():
    """Acceptance: descend through the differentiable solver to recover a
    planted 1 Ohm from the exact nodal oracle to < 5% relative error."""
    with jax.enable_x64():
        a = _spd(jax.random.fold_in(KA, 3), N, jnp.float64)
        cal = calibrate_wire(a, r_true=1.0, steps=120)
        assert cal.rel_err(1.0) < 0.05, (cal.r_hat, cal.loss)
        assert cal.history[-1] < cal.history[0]   # the descent descended
