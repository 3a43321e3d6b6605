"""Block-level repair equivalence (the maintenance contract, TESTING.md).

The splice invariant under test: re-programming ONLY a subset of a plan's
physical arrays under root key K (`repair_blocks` + `splice_finalized` +
`splice_arena`) must produce, for those arrays, exactly the values a FULL
re-program under K would - bit-for-bit on eager CPU - while every
untouched slice stays bit-for-bit what it was.  In particular repairing
*all* blocks under K is indistinguishable from `ProgrammedSolver
.program(a, K)` at the FlatPlan, FinalizedPlan AND ArenaPlan levels.

This is what makes block repair a safe maintenance primitive: a repaired
plan is never a third artifact to validate - it IS the re-programmed
plan, restricted to the degraded fraction (cost scales with #blocks, not
n^2).

Hypothesis drives (stages, nonideality, subset seed) when installed; a
fixed parametrized sweep keeps tier-1 coverage without it (the
_hypothesis_compat degradation contract).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart
from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

KEY = jax.random.PRNGKey(7)
N = 16

NONIDEAL = {
    "sigma": NonidealConfig(sigma=0.05),
    "wire": NonidealConfig(sigma=0.02, r_wire=1.0,
                           wire_model="first_order",
                           compensate_wire=True, wv_iters=2),
    "faults": NonidealConfig(sigma=0.02, p_stuck_off=0.05,
                             g_stuck_off=0.0, remap_faults=True),
}


def _cfg(variant: str) -> AnalogConfig:
    return AnalogConfig(array_size=8, nonideal=NONIDEAL[variant],
                        opa_gain=1e4)


def _solver(a, key, cfg, stages):
    return blockamc.ProgrammedSolver.program(a, key, cfg, stages)


def _assert_grids_equal(g1, g2):
    assert len(g1) == len(g2)
    for x, y in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(x.gpos), np.asarray(y.gpos))
        np.testing.assert_array_equal(np.asarray(x.gneg), np.asarray(y.gneg))


def _assert_fin_equal(f1, f2):
    for l1, l2 in zip(f1.lu_stacks, f2.lu_stacks):
        np.testing.assert_array_equal(np.asarray(l1[0]), np.asarray(l2[0]))
        np.testing.assert_array_equal(np.asarray(l1[1]), np.asarray(l2[1]))
    assert len(f1.mvm_levels) == len(f2.mvm_levels)
    for lv1, lv2 in zip(f1.mvm_levels, f2.mvm_levels):
        assert len(lv1.stacks) == len(lv2.stacks)
        for s1, s2 in zip(lv1.stacks, lv2.stacks):
            np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        for d1, d2 in zip(lv1.divs, lv2.divs):
            np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def _assert_arena_equal(a1, a2):
    assert len(a1.stacks) == len(a2.stacks)
    for s1, s2 in zip(a1.stacks, a2.stacks):
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def _check_repair_equivalence(stages: int, variant: str, subset_seed: int):
    cfg = _cfg(variant)
    a = wishart(jax.random.fold_in(KEY, 11), N)
    k1 = jax.random.fold_in(KEY, 1)
    k2 = jax.random.fold_in(KEY, 2)
    old = _solver(a, k1, cfg, stages)
    ref = _solver(a, k2, cfg, stages)
    refs = [r.ref for r in old.block_map()]
    assert len(refs) == old.flat.num_arrays

    # 1. repairing EVERY block under k2 == full re-program under k2,
    #    bit-for-bit at all three plan levels
    full = old.repaired(refs, k2)
    _assert_grids_equal(full.flat.inv_stacks, ref.flat.inv_stacks)
    _assert_grids_equal(full.flat.mvm_stacks, ref.flat.mvm_stacks)
    _assert_fin_equal(full._fin, ref._fin)
    _assert_arena_equal(full.arena, ref.arena)

    # 2. a strict subset: repaired slices match the k2 plan exactly,
    #    untouched slices match the original k1 plan exactly
    rng = np.random.default_rng(subset_seed)
    k_sub = max(1, len(refs) // 3)
    subset = [refs[i] for i in
              sorted(rng.choice(len(refs), size=k_sub, replace=False))]
    part = old.repaired(subset, k2)
    chosen = set(subset)
    for kind, stacks, old_stacks, ref_stacks in (
            ("inv", part.flat.inv_stacks, old.flat.inv_stacks,
             ref.flat.inv_stacks),
            ("mvm", part.flat.mvm_stacks, old.flat.mvm_stacks,
             ref.flat.mvm_stacks)):
        for b, grid in enumerate(stacks):
            for i in range(grid.gpos.shape[0]):
                want = ref_stacks[b] if (kind, b, i) in chosen \
                    else old_stacks[b]
                np.testing.assert_array_equal(
                    np.asarray(grid.gpos[i]), np.asarray(want.gpos[i]))
                np.testing.assert_array_equal(
                    np.asarray(grid.gneg[i]), np.asarray(want.gneg[i]))

    # 3. the spliced executors agree with a from-scratch finalize of the
    #    spliced flat plan (the splice never invents numbers)
    refin = blockamc.finalize(part.flat, cfg)
    _assert_fin_equal(part._fin, refin)
    _assert_arena_equal(part.arena, blockamc.compile_arena(refin))


@pytest.mark.parametrize("stages", [1, 2])
@pytest.mark.parametrize("variant", sorted(NONIDEAL))
def test_repair_equivalence_sweep(stages, variant):
    _check_repair_equivalence(stages, variant, subset_seed=0)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=12, deadline=None)
@given(stages=st.sampled_from([1, 2]),
       variant=st.sampled_from(sorted(NONIDEAL)),
       subset_seed=st.integers(min_value=0, max_value=2**16))
def test_repair_equivalence_property(stages, variant, subset_seed):
    _check_repair_equivalence(stages, variant, subset_seed)


def test_block_map_covers_plan():
    cfg = _cfg("sigma")
    solver = _solver(wishart(KEY, N), KEY, cfg, 2)
    recs = solver.block_map()
    assert len(recs) == solver.num_arrays
    assert len({r.ref for r in recs}) == len(recs)
    for rec in recs:
        kind, b, i = rec.ref
        stacks = (solver.flat.inv_stacks if kind == "inv"
                  else solver.flat.mvm_stacks)
        assert 0 <= b < len(stacks)
        assert 0 <= i < stacks[b].gpos.shape[0]
        assert stacks[b].gpos.shape[-2:] == rec.shape


def test_repair_unknown_block_raises():
    cfg = _cfg("sigma")
    solver = _solver(wishart(KEY, N), KEY, cfg, 1)
    with pytest.raises(KeyError):
        solver.repaired([("inv", 99, 0)], KEY)


def test_restored_solver_is_not_repairable():
    """A solver rebuilt from checkpointed plans (no flat plan / parts)
    refuses block repair with a ValueError - the caller falls back to a
    full re-program, never a silent no-op."""
    cfg = _cfg("sigma")
    solver = _solver(wishart(KEY, N), KEY, cfg, 1)
    bare = blockamc.ProgrammedSolver(solver._fin, solver._arena)
    assert not bare.repairable
    with pytest.raises(ValueError):
        bare.repaired([("inv", 0, 0)], KEY)
