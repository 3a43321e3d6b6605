"""Finalization layer: ProgrammedSolver / FinalizedPlan vs the flat executor.

The reference-side contract (TESTING.md four-way contract, legs 1-3):
`finalize` precomputes exactly the operators `execute_flat` derives per
call - same LU factors, same per-tile effective matrices, same
accumulation order - so the finalized executor (`execute_finalized` on
`solver.finalized`) matches the flat one bit-for-bit on CPU when both run
the schedule eagerly (and the flat one in turn matches the recursive
reference).  The jitted reference path lets XLA merge each level's
same-shape tile dots, which reassociates final-ulp rounding only:
float-tolerance equal.  The solver's own arena executor (leg 4) is pinned
in tests/test_fused_arena.py.
"""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import random_rhs, wishart
from repro.serve import SolverService

KEY = jax.random.PRNGKey(11)
KA, KB, KN = jax.random.split(KEY, 3)

_execute_finalized = jax.jit(blockamc.execute_finalized)

# Acceptance grid: n in {8, 17, 64} x stages {0, 1, 2}, ragged splits
# included, for device variation, first-order wire model and finite
# OPA gain + 8-bit converter configs.
SIZES = (8, 17, 64)
STAGES = (0, 1, 2)
CFGS = [
    ("sigma", lambda n: AnalogConfig(
        array_size=max(n, 4), nonideal=NonidealConfig(sigma=0.05))),
    ("wire", lambda n: AnalogConfig(
        array_size=max(n // 2, 4),
        nonideal=NonidealConfig(sigma=0.05, r_wire=1.0))),
    ("gain_quant", lambda n: AnalogConfig(
        array_size=max(n // 2, 4), opa_gain=1e4, dac_bits=8, adc_bits=8)),
]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("stages", STAGES)
@pytest.mark.parametrize("tag,make_cfg", CFGS)
def test_finalized_matches_flat_bitwise(n, stages, tag, make_cfg):
    cfg = make_cfg(n)
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    fplan = blockamc.compile_plan(blockamc.build_plan(a, KN, cfg,
                                                      stages=stages))
    x_flat = blockamc.execute_flat(fplan, b, cfg)
    solver = blockamc.ProgrammedSolver.from_plan(fplan, cfg)
    x_fin = blockamc.execute_finalized(solver.finalized, b)
    if jax.default_backend() == "cpu":
        # precomputed operators == per-call derivations, op order identical
        np.testing.assert_array_equal(np.asarray(x_flat), np.asarray(x_fin))
    else:
        np.testing.assert_allclose(np.asarray(x_flat), np.asarray(x_fin),
                                   rtol=1e-6, atol=1e-6)
    # jitted reference path: XLA dot merging reassociates last-ulp only
    x_jit = _execute_finalized(solver.finalized, b)
    np.testing.assert_allclose(np.asarray(x_flat), np.asarray(x_jit),
                               rtol=1e-5, atol=1e-6)


def test_finalized_multi_rhs_bitwise_and_shapes():
    n, stages, k = 32, 2, 8
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, n)
    bs = jax.random.normal(KB, (n, k))
    fplan = blockamc.compile_plan(blockamc.build_plan(a, KN, cfg,
                                                      stages=stages))
    solver = blockamc.ProgrammedSolver.from_plan(fplan, cfg)
    xs_fin = blockamc.execute_finalized(solver.finalized, bs)
    assert xs_fin.shape == (n, k)
    np.testing.assert_array_equal(
        np.asarray(blockamc.execute_flat(fplan, bs, cfg)),
        np.asarray(xs_fin))
    np.testing.assert_allclose(
        np.asarray(_execute_finalized(solver.finalized, bs)),
        np.asarray(xs_fin), rtol=1e-5, atol=1e-6)
    # the serving-default fused path solves the same system (float tol;
    # pinned more tightly in tests/test_fused_arena.py)
    np.testing.assert_allclose(np.asarray(solver.solve_many(bs)),
                               np.asarray(xs_fin), rtol=2e-4, atol=2e-5)


def test_programmed_solver_program_endtoend():
    """program() == build + compile + finalize; solves the system."""
    n = 24
    cfg = AnalogConfig(array_size=8)
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    solver = blockamc.ProgrammedSolver.program(a, KN, cfg)
    assert solver.n == n
    assert solver.cfg is cfg
    x = solver.solve(b)
    np.testing.assert_allclose(np.asarray(x),
                               np.asarray(jnp.linalg.solve(a, b)),
                               rtol=1e-3, atol=1e-4)


def test_finalized_plan_is_pytree():
    """FinalizedPlan jits as an argument and round-trips flatten/unflatten."""
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, 16)
    b = random_rhs(KB, 16)
    fin = blockamc.finalize(blockamc.build_flat_plan(a, KN, cfg, 1), cfg)
    leaves, treedef = jax.tree_util.tree_flatten(fin)
    fin2 = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(
        np.asarray(blockamc.execute_finalized(fin, b)),
        np.asarray(blockamc.execute_finalized(fin2, b)))
    hash(treedef)   # schedule/cfg aux must stay hashable for the jit cache


def test_partition_program_split_matches_fused_build():
    """partition_system + program_system == build_plan (same noise draws)."""
    n = 33
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    parts = blockamc.partition_system(a, cfg, stages=2)
    plan_split = blockamc.program_system(parts, KN, cfg)
    plan_fused = blockamc.build_plan(a, KN, cfg, stages=2)
    np.testing.assert_array_equal(
        np.asarray(blockamc.execute(plan_split, b, cfg)),
        np.asarray(blockamc.execute(plan_fused, b, cfg)))


def test_solve_batched_sharded_matches_batched():
    """shard_map path (1-device mesh here) == plain vmapped solve_batched."""
    from repro.core.mc_sharding import make_mc_mesh
    n = 32
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, n)
    b = random_rhs(KB, n)
    keys = jax.random.split(KN, 4)
    xs_b = blockamc.solve_batched(a, b, keys, cfg, stages=1)
    xs_s = blockamc.solve_batched_sharded(a, b, keys, cfg, stages=1,
                                          mesh=make_mc_mesh(1))
    np.testing.assert_allclose(np.asarray(xs_s), np.asarray(xs_b),
                               rtol=1e-5, atol=1e-6)
    # (the num_keys divisibility error needs a >1-device mesh; covered by
    # the slow multi-device subprocess test below)


@pytest.mark.slow
def test_solve_batched_sharded_multidevice():
    """Key axis genuinely sharded over 4 host devices (subprocess: XLA
    device count must be set before jax initialises)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = """
import jax, jax.numpy as jnp
from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart, random_rhs
ka, kb, kn = jax.random.split(jax.random.PRNGKey(1), 3)
a = wishart(ka, 32); b = random_rhs(kb, 32)
cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.05))
keys = jax.random.split(kn, 8)
xs_b = blockamc.solve_batched(a, b, keys, cfg, stages=1)
xs_s = blockamc.solve_batched_sharded(a, b, keys, cfg, stages=1)
assert jnp.allclose(xs_s, xs_b, rtol=1e-5, atol=1e-6)
print('OK', xs_s.shape)
"""
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


def test_solver_service_batches_submitted_rhs():
    n = 32
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.05))
    svc = SolverService(cfg, stages=1)
    a = wishart(KA, n)
    svc.program("gram0", a, KN)
    solver = svc.solver("gram0")

    # flush solves every queued rhs exactly like individual solves
    cols = [jax.random.normal(jax.random.fold_in(KB, j), (n,))
            for j in range(5)]
    for b in cols:
        svc.submit("gram0", b)
    assert svc.pending("gram0") == 5
    xs = svc.flush("gram0")
    assert xs.shape == (n, 5) and svc.pending("gram0") == 0
    for j, b in enumerate(cols):
        np.testing.assert_allclose(np.asarray(xs[:, j]),
                                   np.asarray(solver.solve(b)),
                                   rtol=1e-5, atol=1e-6)

    # empty flush, immediate solve, stats accounting
    assert svc.flush("gram0").shape == (n, 0)
    svc.solve("gram0", cols[0])
    st = svc.stats("gram0")
    assert st.rhs_served == 6 and st.solve_calls == 2
    assert st.program_time_s > 0
    with pytest.raises(ValueError, match="rhs"):
        svc.submit("gram0", jnp.zeros((n, 2)))
    with pytest.raises(ValueError, match="rhs"):
        svc.submit("gram0", jnp.zeros((n + 1,)))   # wrong length, right ndim
    # a failing flush must not drop queued requests
    svc.submit("gram0", cols[0])
    assert svc.pending("gram0") == 1
    # re-programming over pending requests must refuse, not drop them
    with pytest.raises(RuntimeError, match="pending"):
        svc.program("gram0", a, KN)
    xs = svc.flush("gram0")
    assert xs.shape == (n, 1)


@pytest.mark.slow
def test_programmed_solver_amortizes_256_two_stage():
    """End-to-end amortization guard: after programming a 256^2 two-stage
    solver, the marginal cost of the 64th streamed rhs must be far below
    the time-to-first-solve (catches silent re-tracing/re-factorizing)."""
    n = 256
    cfg = AnalogConfig(array_size=64, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(KA, n)

    t0 = time.perf_counter()
    solver = blockamc.ProgrammedSolver.program(a, KN, cfg, stages=2)
    jax.block_until_ready(solver.solve(random_rhs(KB, n)))
    ttfs = time.perf_counter() - t0

    marginal = float("inf")
    for j in range(64):
        b = jax.random.normal(jax.random.fold_in(KB, j), (n,))
        t0 = time.perf_counter()
        jax.block_until_ready(solver.solve(b))
        dt = time.perf_counter() - t0
        if j == 63:
            marginal = dt
    # programming includes plan build + finalize + jit compile (seconds);
    # a marginal solve is sub-ms.  20x leaves headroom for CPU noise while
    # still failing instantly if solve() re-traces or re-factorizes.
    assert marginal * 20 < ttfs, (marginal, ttfs)
