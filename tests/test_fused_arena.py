"""Four-way executor equivalence: recursive / flat / finalized / fused-arena.

The contract (TESTING.md): the three reference executors agree bit-for-bit
on CPU when run eagerly (`execute` == `execute_flat` == eager
`execute_finalized` - unchanged from the three-way contract), and the
fused-arena executor (`compile_arena` / `execute_arena`, the serving fast
path) is pinned against them at float tolerance: it applies explicit
INV-bucket inverses instead of `lu_solve` and folds the summing-node
divisor into the tile operators, both of which reassociate rounding by
design.  The grid covers stages {0, 1, 2} x regimes {ideal, sigma, wire,
finite opa_gain} x rhs {(n,), (n, k)}, ragged splits included.

The arena's own structural invariants (allocator live ranges, window
containment, peak liveness) live in tests/test_plan_properties.py; the
Pallas megakernel parity (interpret=True) in tests/test_kernels.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import random_rhs, wishart

KEY = jax.random.PRNGKey(7)
KA, KB, KN = jax.random.split(KEY, 3)

STAGES = (0, 1, 2)
REGIMES = [
    ("ideal", lambda n: AnalogConfig(array_size=max(n // 2, 4))),
    ("sigma", lambda n: AnalogConfig(
        array_size=max(n // 2, 4), nonideal=NonidealConfig(sigma=0.05))),
    ("wire", lambda n: AnalogConfig(
        array_size=max(n // 2, 4),
        nonideal=NonidealConfig(sigma=0.05, r_wire=1.0))),
    ("gain", lambda n: AnalogConfig(
        array_size=max(n // 2, 4), opa_gain=1e4)),
]
# n=32 keeps power-of-two tiling (uniform whole-schedule program); n=17/33
# exercise ragged odd splits (multi-segment gathers, per-level fallback).
SIZES = (32, 17)


def _four_ways(n, stages, cfg, b):
    a = wishart(KA, n)
    plan = blockamc.build_plan(a, KN, cfg, stages=stages)
    fplan = blockamc.compile_plan(plan)
    fin = blockamc.finalize(fplan, cfg)
    ap = blockamc.compile_arena(fin)
    if b.ndim == 1:
        x_rec = blockamc.execute(plan, b, cfg)
    else:
        x_rec = jnp.stack([blockamc.execute(plan, b[:, j], cfg)
                           for j in range(b.shape[1])], axis=1)
    x_flat = blockamc.execute_flat(fplan, b, cfg)
    x_fin = blockamc.execute_finalized(fin, b)
    x_arena = blockamc.execute_arena(ap, b, use_kernel=False)
    return x_rec, x_flat, x_fin, x_arena


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("stages", STAGES)
@pytest.mark.parametrize("tag,make_cfg", REGIMES)
@pytest.mark.parametrize("multi_rhs", [False, True])
def test_four_way_equivalence(n, stages, tag, make_cfg, multi_rhs):
    cfg = make_cfg(n)
    b = jax.random.normal(KB, (n, 4)) if multi_rhs else random_rhs(KB, n)
    x_rec, x_flat, x_fin, x_arena = _four_ways(n, stages, cfg, b)
    # the existing promise: reference executors are bit-for-bit on CPU
    # (multi-rhs recursive runs column-wise, so flat batching is pinned at
    # float tolerance there - same contract as test_flat_executor)
    if jax.default_backend() == "cpu" and not multi_rhs:
        np.testing.assert_array_equal(np.asarray(x_rec), np.asarray(x_flat))
        np.testing.assert_array_equal(np.asarray(x_flat), np.asarray(x_fin))
    else:
        np.testing.assert_allclose(np.asarray(x_rec), np.asarray(x_flat),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(x_flat), np.asarray(x_fin),
                                   rtol=1e-5, atol=1e-6)
    # the fused arena executor is float-tolerance by design (explicit
    # inverse + folded divisors reassociate rounding)
    np.testing.assert_allclose(np.asarray(x_arena), np.asarray(x_fin),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("leg", ["reference", "fused"])
def test_jitted_solver_matches_eager(leg):
    """Jitted executors == the eager schedule at float tolerance, single
    and multi rhs: the finalized reference executor on the solver's plan,
    and the solver's own (arena) solve."""
    n, stages = 32, 2
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, n)
    solver = blockamc.ProgrammedSolver.program(a, KN, cfg, stages=stages)
    for b in (random_rhs(KB, n), jax.random.normal(KB, (n, 5))):
        if leg == "reference":
            x_eager = blockamc.execute_finalized(solver.finalized, b)
            x_jit = jax.jit(blockamc.execute_finalized)(solver.finalized, b)
        else:
            x_eager = blockamc.execute_arena(solver.arena, b)
            x_jit = solver.solve(b)
        np.testing.assert_allclose(np.asarray(x_jit), np.asarray(x_eager),
                                   rtol=1e-5, atol=1e-6)


def test_fused_solves_the_system():
    """End to end: the fused path still solves A x = b (ideal config)."""
    n = 64
    cfg = AnalogConfig(array_size=16)
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    solver = blockamc.ProgrammedSolver.program(a, KN, cfg, stages=2)
    # the arena plan is compiled at programming time, not on first solve
    assert isinstance(solver.arena, blockamc.ArenaPlan)
    np.testing.assert_allclose(np.asarray(solver.solve(b)),
                               np.asarray(jnp.linalg.solve(a, b)),
                               rtol=1e-3, atol=1e-4)


def test_solve_many_pads_and_slices():
    """solve_many owns the pow-2 padding: distinct k hit one compiled
    shape per doubling and padding columns never leak into results."""
    n = 32
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.02))
    a = wishart(KA, n)
    solver = blockamc.ProgrammedSolver.program(a, KN, cfg, stages=1)
    xs5 = solver.solve_many(jax.random.normal(KB, (n, 5)))
    assert xs5.shape == (n, 5)
    for k in (3, 5, 7, 8):
        bs = jax.random.normal(jax.random.fold_in(KB, k), (n, k))
        xs = solver.solve_many(bs)
        assert xs.shape == (n, k)
        # column j == single solve of column j (same jitted executor)
        np.testing.assert_allclose(np.asarray(xs[:, 0]),
                                   np.asarray(solver.solve(bs[:, 0])),
                                   rtol=1e-5, atol=1e-6)
    # unpadded dispatch still available
    xs = solver.solve_many(jax.random.normal(KB, (n, 6)), pad_to_pow2=False)
    assert xs.shape == (n, 6)


def test_solve_many_does_not_retrace_across_k():
    """Distinct queue lengths share one executor trace per pow-2 bucket:
    5, 6, 7 and 8 rhs all dispatch the warmed (n, 8) shape."""
    n = 32
    cfg = AnalogConfig(array_size=16)
    a = wishart(KA, n)
    solver = blockamc.ProgrammedSolver.program(a, KN, cfg, stages=1)
    fn = blockamc._execute_arena
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jit cache introspection not available")
    solver.solve_many(jax.random.normal(KB, (n, 8)))   # warm the bucket
    before = fn._cache_size()
    for k in (5, 6, 7, 8):
        solver.solve_many(jax.random.normal(jax.random.fold_in(KB, k),
                                            (n, k)))
    assert fn._cache_size() == before, "distinct k re-traced the executor"


def test_mc_fused_matches_reference_mode():
    """solve_batched(mode='fused') == reference mode at float tolerance,
    plain and sharded (per-key finalize + arena-compile under vmap)."""
    from repro.core.mc_sharding import make_mc_mesh
    n = 32
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    keys = jax.random.split(KN, 4)
    xs_ref = blockamc.solve_batched(a, b, keys, cfg, stages=1)
    xs_fus = blockamc.solve_batched(a, b, keys, cfg, stages=1, mode="fused")
    np.testing.assert_allclose(np.asarray(xs_fus), np.asarray(xs_ref),
                               rtol=2e-4, atol=2e-5)
    xs_sh = blockamc.solve_batched_sharded(a, b, keys, cfg, stages=1,
                                           mesh=make_mc_mesh(1),
                                           mode="fused")
    np.testing.assert_allclose(np.asarray(xs_sh), np.asarray(xs_fus),
                               rtol=1e-6, atol=1e-7)


def test_preconditioner_modes_agree():
    """AnalogPreconditioner's (arena) apply == the finalized reference
    executor on its plan (float tol), and the pytree round-trips with
    both plans attached."""
    from repro.hybrid import AnalogPreconditioner
    n = 32
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.02))
    a = wishart(KA, n)
    pre_f = AnalogPreconditioner.program(a, KN, cfg, stages=1)
    v = jax.random.normal(KB, (5, n))
    x_ref = blockamc.execute_finalized(pre_f.fin, v.T).T
    np.testing.assert_allclose(np.asarray(pre_f(v)), np.asarray(x_ref),
                               rtol=2e-4, atol=2e-5)
    leaves, td = jax.tree_util.tree_flatten(pre_f)
    pre_2 = jax.tree_util.tree_unflatten(td, leaves)
    np.testing.assert_array_equal(np.asarray(pre_f(v)),
                                  np.asarray(pre_2(v)))
    hash(td)    # jit cache key: the plans' static metadata stays hashable


def test_arena_plan_is_pytree():
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, 16)
    b = random_rhs(KB, 16)
    ap = blockamc.compile_arena(
        blockamc.finalize(blockamc.build_flat_plan(a, KN, cfg, 1), cfg))
    leaves, treedef = jax.tree_util.tree_flatten(ap)
    ap2 = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(
        np.asarray(blockamc.execute_arena(ap, b)),
        np.asarray(blockamc.execute_arena(ap2, b)))
    hash(treedef)

    # donation-capable jitted entry point works on the pytree
    xs = blockamc._execute_arena_donated(ap, jax.random.normal(KB, (16, 2)))
    assert xs.shape == (16, 2)


def test_fused_kernel_smoke_interpret():
    """The CI fused-executor smoke: the whole-schedule Pallas megakernel
    (interpret=True on CPU) reproduces the jnp slot path on a uniform
    power-of-two plan, single and multi rhs."""
    n = 16
    cfg = AnalogConfig(array_size=4, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, n)
    ap = blockamc.compile_arena(
        blockamc.finalize(blockamc.build_flat_plan(a, KN, cfg, 2), cfg))
    assert ap.program is not None and ap.kernel_ok
    for b in (random_rhs(KB, n), jax.random.normal(KB, (n, 3))):
        x_j = blockamc.execute_arena(ap, b, use_kernel=False)
        x_k = blockamc.execute_arena(ap, b, use_kernel=True)
        np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_j),
                                   rtol=1e-6, atol=1e-7)
