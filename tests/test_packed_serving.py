"""Multi-tenant packed serving: the packed-vs-loop equivalence contract.

The contract (TESTING.md "packed serving contract"): packing M
same-signature arena plans on a leading instance axis and executing the
fleet with `execute_arena_packed` answers every tenant with exactly the
numbers its own `execute_arena` produces - bit-for-bit when both run
eagerly on CPU on aligned power-of-two plans (batching the stacked-tile
dots over the instance axis neither reassociates any per-instance
reduction nor changes the per-slice dot kernel), last-ulp float tolerance
on ragged odd splits and under jit (XLA dot merging).  On top sit the serving paths: `SolverService.flush_all`
groups pending queues by `plan_signature`, pads ragged per-tenant queue
lengths to one shared power-of-two width and scatters per-tenant answers
back; `AsyncSolverEngine` drives that flush (tests/test_async_engine.py).

Signature bucketing properties (same signature => identical schedule +
arena layout) live in tests/test_plan_properties.py; packed megakernel
parity in tests/test_kernels.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart
from repro.serve import SolverService

KEY = jax.random.PRNGKey(23)
KA, KB, KN = jax.random.split(KEY, 3)


def _fleet(m, n, cfg, stages):
    """M programmed instances: matrices, keys, per-instance arena plans."""
    keys = jax.random.split(KN, m)
    As = jnp.stack([wishart(jax.random.fold_in(KA, i), n) for i in range(m)])
    aps = [blockamc.compile_arena(blockamc.finalize(
        blockamc.build_flat_plan(As[i], keys[i], cfg, stages=stages), cfg))
        for i in range(m)]
    return As, keys, aps


REGIMES = [
    ("sigma", lambda n: AnalogConfig(
        array_size=max(n // 4, 4), nonideal=NonidealConfig(sigma=0.05))),
    ("wire", lambda n: AnalogConfig(
        array_size=max(n // 4, 4),
        nonideal=NonidealConfig(sigma=0.05, r_wire=1.0))),
    ("gain", lambda n: AnalogConfig(
        array_size=max(n // 4, 4), opa_gain=1e4)),
]


@pytest.mark.parametrize("n,stages", [(32, 2), (17, 1)])
@pytest.mark.parametrize("tag,make_cfg", REGIMES)
@pytest.mark.parametrize("multi_rhs", [False, True])
def test_packed_matches_per_instance_loop(n, stages, tag, make_cfg,
                                          multi_rhs):
    """Each tenant's packed solution == its own execute_arena: bit-for-bit
    eager on CPU, float tolerance jitted.  n=17 exercises ragged odd
    splits (no uniform program; levels path)."""
    cfg = make_cfg(n)
    m = 3
    _, _, aps = _fleet(m, n, cfg, stages)
    pp = blockamc.pack_arena_plans(aps)
    assert pp.num_instances == m
    bs = (jax.random.normal(KB, (m, n, 4)) if multi_rhs
          else jax.random.normal(KB, (m, n)))
    xs = blockamc.execute_arena_packed(pp, bs, use_kernel=False)
    xs_loop = jnp.stack([
        blockamc.execute_arena(aps[i], bs[i], use_kernel=False)
        for i in range(m)])
    if jax.default_backend() == "cpu" and n == 32:
        # aligned power-of-two plans: the batched dots compute each
        # instance slice with the same kernel as the unbatched dot
        np.testing.assert_array_equal(np.asarray(xs), np.asarray(xs_loop))
    else:
        # ragged odd splits: XLA:CPU's batched matmul may take a
        # different code path per slice on odd tile sizes - last-ulp only
        np.testing.assert_allclose(np.asarray(xs), np.asarray(xs_loop),
                                   rtol=1e-5, atol=1e-6)
    xs_jit = blockamc._execute_arena_packed(pp, bs)
    np.testing.assert_allclose(np.asarray(xs_jit), np.asarray(xs_loop),
                               rtol=1e-5, atol=1e-6)


def test_batched_programming_matches_sequential():
    """program_packed (one vmapped trace) == the sequential per-matrix
    pipeline at float tolerance, and still solves every system."""
    m, n, stages = 4, 32, 2
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    As, keys, aps = _fleet(m, n, cfg, stages)
    pp = blockamc.program_packed(As, keys, cfg, stages=stages)
    assert pp.num_instances == m
    bs = jax.random.normal(KB, (m, n, 2))
    xs = blockamc.execute_arena_packed(pp, bs, use_kernel=False)
    xs_seq = blockamc.execute_arena_packed(blockamc.pack_arena_plans(aps),
                                           bs, use_kernel=False)
    # same matrices, same noise keys; the batched pipeline runs under
    # jit/vmap, so agreement is float-tolerance (XLA reassociation in the
    # programming math), not bitwise
    np.testing.assert_allclose(np.asarray(xs), np.asarray(xs_seq),
                               rtol=2e-4, atol=2e-5)


def test_batched_programming_stages_align():
    """The batched pipeline builders compose: pack_partitioned +
    program_system_batched + finalize_batched + compile_arena_batched ==
    program_packed."""
    m, n, stages = 3, 16, 1
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.02))
    As, keys, _ = _fleet(m, n, cfg, stages)
    parts = blockamc.pack_partitioned(
        [blockamc.partition_system(As[i], cfg, stages) for i in range(m)])
    fplans = blockamc.program_system_batched(parts, keys, cfg)
    pp = blockamc.compile_arena_batched(
        blockamc.finalize_batched(fplans, cfg))
    pp2 = blockamc.program_packed(As, keys, cfg, stages=stages)
    bs = jax.random.normal(KB, (m, n, 2))
    np.testing.assert_allclose(
        np.asarray(blockamc.execute_arena_packed(pp, bs, use_kernel=False)),
        np.asarray(blockamc.execute_arena_packed(pp2, bs,
                                                 use_kernel=False)),
        rtol=1e-5, atol=1e-6)


def test_pack_rejects_mismatched_signatures():
    """Plans compiled from different (n, stages, cfg) cannot share one
    packed program and must be refused loudly."""
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    _, _, aps16 = _fleet(1, 16, cfg, 1)
    _, _, aps32 = _fleet(1, 32, cfg, 1)
    with pytest.raises(ValueError, match="not stackable"):
        blockamc.pack_arena_plans([aps16[0], aps32[0]])
    with pytest.raises(ValueError, match="at least one"):
        blockamc.pack_arena_plans([])


def test_packed_plan_is_pytree():
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    _, _, aps = _fleet(2, 16, cfg, 1)
    pp = blockamc.pack_arena_plans(aps)
    leaves, treedef = jax.tree_util.tree_flatten(pp)
    pp2 = jax.tree_util.tree_unflatten(treedef, leaves)
    bs = jax.random.normal(KB, (2, 16, 2))
    np.testing.assert_array_equal(
        np.asarray(blockamc.execute_arena_packed(pp, bs, use_kernel=False)),
        np.asarray(blockamc.execute_arena_packed(pp2, bs,
                                                 use_kernel=False)))
    hash(treedef)   # shared static metadata stays a valid jit cache key


def test_packed_kernel_rejects_nonuniform():
    """use_kernel=True on a plan without a whole-schedule program must
    fail loudly, exactly like the single-instance executor."""
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    _, _, aps = _fleet(2, 17, cfg, 1)      # ragged split: program is None
    pp = blockamc.pack_arena_plans(aps)
    assert pp.program_ops is None
    with pytest.raises(ValueError, match="uniform"):
        blockamc.execute_arena_packed(pp, jax.random.normal(KB, (2, 17)),
                                      use_kernel=True)


def test_packed_sharded_matches_unsharded():
    """Instance axis over a (1-device) mc mesh == the plain packed path."""
    from repro.core.mc_sharding import make_mc_mesh
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
    m, n = 4, 16
    _, _, aps = _fleet(m, n, cfg, 1)
    pp = blockamc.pack_arena_plans(aps)
    bs = jax.random.normal(KB, (m, n, 3))
    xs = blockamc.execute_arena_packed(pp, bs, use_kernel=False)
    xs_sh = blockamc.execute_arena_packed_sharded(pp, bs,
                                                  mesh=make_mc_mesh(1))
    np.testing.assert_allclose(np.asarray(xs_sh), np.asarray(xs),
                               rtol=1e-6, atol=1e-7)
    # (the num_instances divisibility error needs a >1-device mesh; the
    # slow multi-device subprocess test below covers genuine sharding)


@pytest.mark.slow
def test_packed_sharded_multidevice():
    """Instance axis genuinely sharded over 4 host devices (subprocess:
    XLA device count must be set before jax initialises)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = """
import jax, jax.numpy as jnp
from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart
ka, kb, kn = jax.random.split(jax.random.PRNGKey(3), 3)
cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
m, n = 8, 32
keys = jax.random.split(kn, m)
As = jnp.stack([wishart(jax.random.fold_in(ka, i), n) for i in range(m)])
pp = blockamc.program_packed(As, keys, cfg, stages=2)
bs = jax.random.normal(kb, (m, n, 4))
xs = blockamc.execute_arena_packed(pp, bs, use_kernel=False)
xs_sh = blockamc.execute_arena_packed_sharded(pp, bs)
assert jnp.allclose(xs_sh, xs, rtol=1e-5, atol=1e-6)
pp6 = blockamc.program_packed(As[:6], keys[:6], cfg, stages=2)
try:
    blockamc.execute_arena_packed_sharded(pp6, bs[:6])
except ValueError as e:
    assert "divide" in str(e)
else:
    raise SystemExit("divisibility error not raised")
print('OK', xs_sh.shape)
"""
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# SolverService.flush_all
# ---------------------------------------------------------------------------

N = 32
CFG = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.02))


def _service(m=4, n=N, stages=2):
    svc = SolverService(CFG, stages=stages)
    ids = [f"m{i}" for i in range(m)]
    for i, mid in enumerate(ids):
        svc.program(mid, wishart(jax.random.fold_in(KA, i), n),
                    jax.random.fold_in(KN, i))
    return svc, ids


def test_flush_all_ragged_bucket_matches_individual_solves():
    """Mixed per-tenant queue lengths: one packed dispatch answers every
    tenant with its own solver's numbers, pads never leak, counters count
    each rhs exactly once."""
    svc, ids = _service(m=4)
    counts = dict(zip(ids, (3, 5, 1, 8)))
    cols = {}
    for mid in ids:
        cols[mid] = [jax.random.normal(jax.random.fold_in(KB, 100 * int(
            mid[1:]) + j), (N,)) for j in range(counts[mid])]
        for b in cols[mid]:
            svc.submit(mid, b)
    expected = {mid: jnp.stack([svc.solver(mid).solve(b)
                                for b in cols[mid]], axis=1) for mid in ids}
    out = svc.flush_all()
    assert set(out) == set(ids)
    for mid in ids:
        assert out[mid].shape == (N, counts[mid])
        np.testing.assert_allclose(np.asarray(out[mid]),
                                   np.asarray(expected[mid]),
                                   rtol=1e-5, atol=1e-6)
        assert svc.pending(mid) == 0
        st = svc.stats(mid)
        assert st.solve_calls == 1                # one packed dispatch
        assert st.rhs_served == counts[mid]       # no double counting
    assert svc.flush_all() == {}                  # nothing left pending


def test_flush_all_matches_flush_loop():
    """flush_all == a loop of per-matrix flushes, tenant for tenant."""
    svc_a, ids = _service(m=3)
    svc_b, _ = _service(m=3)
    cols = {mid: [jax.random.normal(jax.random.fold_in(KB, 7 * i + j), (N,))
                  for j in range(4)] for i, mid in enumerate(ids)}
    for mid in ids:
        for b in cols[mid]:
            svc_a.submit(mid, b)
            svc_b.submit(mid, b)
    packed = svc_a.flush_all()
    for mid in ids:
        loop = svc_b.flush(mid)
        np.testing.assert_allclose(np.asarray(packed[mid]),
                                   np.asarray(loop), rtol=1e-5, atol=1e-6)
        assert svc_a.stats(mid).rhs_served == svc_b.stats(mid).rhs_served


def test_flush_all_mixed_signatures_and_singletons():
    """Tenants of different sizes land in different signature buckets;
    a single-tenant bucket falls back to the per-matrix flush."""
    svc = SolverService(CFG, stages=1)
    a16 = [wishart(jax.random.fold_in(KA, i), 16) for i in range(2)]
    a32 = wishart(jax.random.fold_in(KA, 9), 32)
    svc.program("s0", a16[0], jax.random.fold_in(KN, 0))
    svc.program("s1", a16[1], jax.random.fold_in(KN, 1))
    svc.program("big", a32, jax.random.fold_in(KN, 2))
    assert svc.signature("s0") == svc.signature("s1")
    assert svc.signature("s0") != svc.signature("big")
    b16 = [jax.random.normal(jax.random.fold_in(KB, j), (16,))
           for j in range(3)]
    b32 = jax.random.normal(KB, (32,))
    for b in b16:
        svc.submit("s0", b)
    svc.submit("s1", b16[0])
    svc.submit("big", b32)
    out = svc.flush_all()
    assert out["s0"].shape == (16, 3)
    assert out["s1"].shape == (16, 1)
    assert out["big"].shape == (32, 1)
    np.testing.assert_allclose(np.asarray(out["big"][:, 0]),
                               np.asarray(svc.solver("big").solve(b32)),
                               rtol=1e-5, atol=1e-6)
    # subset flush: only the requested ids are answered
    svc.submit("s0", b16[0])
    svc.submit("big", b32)
    out = svc.flush_all(matrix_ids=["big"])
    assert set(out) == {"big"} and svc.pending("s0") == 1
    # unknown ids raise like every other entry point (never silently skip)
    with pytest.raises(KeyError):
        svc.flush_all(matrix_ids=["big", "nope"])


def test_flush_all_failed_dispatch_is_all_or_nothing(monkeypatch):
    """A packed dispatch that raises inside flush_all - here the second
    of two signature buckets, after the first has already run - leaves
    every queue and counter as it was, and a plain retry answers every
    tenant with its own solver's numbers (the engine's retry ladder
    re-flushes the same batch on this contract)."""
    import repro.serve.solver_service as ss

    svc = SolverService(CFG, stages=1)
    sizes = {"a0": 32, "a1": 32, "c0": 16, "c1": 16}
    for i, (mid, n) in enumerate(sizes.items()):
        svc.program(mid, wishart(jax.random.fold_in(KA, i), n),
                    jax.random.fold_in(KN, i))
    assert svc.signature("a0") != svc.signature("c0")
    cols = {mid: [jax.random.normal(jax.random.fold_in(KB, 10 * i + j),
                                    (n,)) for j in range(i % 2 + 1)]
            for i, (mid, n) in enumerate(sizes.items())}
    for mid, bs in cols.items():
        for b in bs:
            svc.submit(mid, b)

    real = ss._execute_arena_packed_selected_donated
    calls = {"n": 0}

    def exploding(pp, idx, bs):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected device OOM")
        return real(pp, idx, bs)

    monkeypatch.setattr(ss, "_execute_arena_packed_selected_donated",
                        exploding)
    with pytest.raises(RuntimeError, match="injected device OOM"):
        svc.flush_all()
    assert calls["n"] == 2                       # one bucket had run
    for mid, bs in cols.items():
        assert svc.pending(mid) == len(bs)
        assert svc.stats(mid).rhs_served == 0

    out = svc.flush_all()                        # plain retry, no reset
    for mid, bs in cols.items():
        expected = jnp.stack([svc.solver(mid).solve(b) for b in bs], axis=1)
        np.testing.assert_allclose(np.asarray(out[mid]),
                                   np.asarray(expected),
                                   rtol=1e-5, atol=1e-6)
        assert svc.pending(mid) == 0
        assert svc.stats(mid).rhs_served == len(bs)


def test_reprogram_invalidates_pack_cache():
    """Re-programming a tenant drops the resident stack holding it, so
    the next flush_all stacks the new plan (and solves the new matrix).
    The cache holds one resident (rows, pack) per signature."""
    svc, ids = _service(m=2)
    for mid in ids:
        svc.submit(mid, jax.random.normal(KB, (N,)))
    svc.flush_all()
    assert [tuple(rows) for rows, _ in svc._packs.values()] == [tuple(ids)]
    a_new = wishart(jax.random.fold_in(KA, 77), N)
    svc.program(ids[0], a_new, jax.random.fold_in(KN, 77))
    assert not svc._packs
    b = jax.random.normal(jax.random.fold_in(KB, 5), (N,))
    for mid in ids:
        svc.submit(mid, b)
    out = svc.flush_all()
    np.testing.assert_allclose(np.asarray(out[ids[0]][:, 0]),
                               np.asarray(svc.solver(ids[0]).solve(b)),
                               rtol=1e-5, atol=1e-6)


def _assert_flush_answers_new_plan(svc, ids):
    """Both tenants pending: the stack is rebuilt and answers with the
    current solvers' numbers."""
    b = jax.random.normal(jax.random.fold_in(KB, 5), (N,))
    for mid in ids:
        svc.submit(mid, b)
    out = svc.flush_all()
    for mid in ids:
        np.testing.assert_allclose(np.asarray(out[mid][:, 0]),
                                   np.asarray(svc.solver(mid).solve(b)),
                                   rtol=1e-5, atol=1e-6)


def test_refresh_rewrites_resident_row(sink):
    """`refresh` of a member (a maintained variant of its solver) writes
    that member's row of the resident stack in place: the next flush
    reuses the stack (`service.pack` hit 1) and answers from the new
    plan, and the other members' rows are untouched."""
    svc, ids = _service(m=3)
    assert _flush_pairs(svc, [ids[:2]], sink) == [0]
    before = np.asarray(next(iter(svc._packs.values()))[1].stacks[0])
    fresh = blockamc.ProgrammedSolver.program(
        svc.dense(ids[0]), jax.random.fold_in(KN, 78), CFG, 2)
    svc.refresh(ids[0], fresh)
    rows, pp = next(iter(svc._packs.values()))
    assert pp.num_instances == 3 and rows[ids[0]] == 0
    after = np.asarray(pp.stacks[0])
    np.testing.assert_array_equal(after[0], np.asarray(fresh.arena.stacks[0]))
    np.testing.assert_array_equal(after[1:], before[1:])
    assert not np.array_equal(after[0], before[0])
    np.testing.assert_array_equal(np.asarray(pp.program_ops[0]),
                                  np.asarray(fresh.arena.program[0]))
    np.testing.assert_array_equal(np.asarray(pp.scale[0]),
                                  np.asarray(fresh.arena.scale))
    _assert_flush_answers_new_plan(svc, ids[:2])
    hits = [s[3]["hit"] for s in sink.spans if s[0] == "service.pack"]
    assert hits == [0, 1]


def test_refresh_of_non_member_leaves_stack():
    """A refresh of a tenant the resident stack does not hold (there is
    no stack yet, or the tenant joined after it was built) swaps only
    its solver handle."""
    svc, ids = _service(m=2)
    fresh = blockamc.ProgrammedSolver.program(
        svc.dense(ids[1]), jax.random.fold_in(KN, 80), CFG, 2)
    svc.refresh(ids[1], fresh)                   # no stack yet
    assert not svc._packs and svc.solver(ids[1]) is fresh
    for mid in ids:
        svc.submit(mid, jax.random.normal(KB, (N,)))
    svc.flush_all()
    (entry,) = svc._packs.values()
    a2 = wishart(jax.random.fold_in(KA, 2), N)
    svc.program("m2", a2, jax.random.fold_in(KN, 2))   # joins, not a member
    fresh2 = blockamc.ProgrammedSolver.program(
        a2, jax.random.fold_in(KN, 81), CFG, 2)
    svc.refresh("m2", fresh2)
    assert next(iter(svc._packs.values())) is entry
    assert svc.solver("m2") is fresh2


def test_install_invalidates_pack_cache():
    """`install` over a member (checkpoint restore of another plan)
    drops the resident stack, and the next flush answers from it."""
    svc, ids = _service(m=2)
    for mid in ids:
        svc.submit(mid, jax.random.normal(KB, (N,)))
    svc.flush_all()
    assert svc._packs
    a_new = wishart(jax.random.fold_in(KA, 79), N)
    restored = blockamc.ProgrammedSolver.program(
        a_new, jax.random.fold_in(KN, 79), CFG, 2)
    svc.install(ids[0], restored, a_new)
    assert not svc._packs
    _assert_flush_answers_new_plan(svc, ids)


@pytest.fixture(scope="module")
def service4():
    """Four same-signature tenants, shared by the subset tests (each
    flushes everything it submits)."""
    return _service(m=4)


def _subset_cols(subset):
    """Distinct, ragged (1, 2, 3, ...) rhs columns for each tenant."""
    return {mid: [np.asarray(jax.random.normal(
        jax.random.fold_in(KB, 10 * int(mid[1:]) + c), (N,)))
        for c in range(j + 1)] for j, mid in enumerate(subset)}


def _padded(cols, k_pad):
    out = np.zeros((N, k_pad), np.float32)
    out[:, :len(cols)] = np.stack(cols, axis=1)
    return out


@pytest.mark.parametrize("order", ["given", "reversed"])
@pytest.mark.parametrize("subset", [("m2",), ("m0", "m3"),
                                    ("m3", "m1", "m2"),
                                    ("m0", "m1", "m2", "m3")])
def test_flush_all_subset_matches_per_tenant(service4, subset, order):
    """Any pending subset, in any bucket order, selected from the
    resident stack: each tenant's answer is bit-for-bit its own jitted
    `execute_arena` on its padded columns, and the per-subset
    `pack_arena_plans` + `execute_arena_packed` of the same bucket (jnp
    path on the CPU; a one-tenant bucket takes `solve_many`)."""
    svc, _ = service4
    bucket = list(subset if order == "given" else reversed(subset))
    cols = _subset_cols(subset)
    for c in range(max(map(len, cols.values()))):   # interleaved submits
        for mid in reversed(bucket):
            if c < len(cols[mid]):
                svc.submit(mid, cols[mid][c])
    out = svc.flush_all(matrix_ids=bucket)
    assert list(out) == bucket
    k_pad = 1 << (max(map(len, cols.values())) - 1).bit_length()
    bs = np.stack([_padded(cols[mid], k_pad) for mid in bucket])
    old = np.asarray(blockamc._execute_arena_packed(
        blockamc.pack_arena_plans([svc.solver(mid).arena
                                   for mid in bucket]), jnp.asarray(bs)))
    for i, mid in enumerate(bucket):
        k = len(cols[mid])
        own = np.asarray(blockamc._execute_arena(
            svc.solver(mid).arena, jnp.asarray(bs[i]), use_kernel=False))
        np.testing.assert_array_equal(out[mid], own[:, :k])
        np.testing.assert_array_equal(out[mid], old[i, :, :k])


@pytest.mark.parametrize("subset", [("m0", "m3"), ("m3", "m1", "m2")])
def test_selected_kernel_matches_per_tenant(service4, subset):
    """The index-selected executor on the Pallas path (interpret mode
    off the chip) answers each tenant like its own `execute_arena`,
    within the packed-vs-loop tolerance."""
    svc, _ = service4
    for mid in subset:                  # build the resident stack
        svc.submit(mid, jnp.zeros((N,)))
    svc.flush_all()
    rows, pp = svc._packs[svc.signature(subset[0])]
    assert pp.program_ops is not None and pp.num_instances == 4
    idx = jnp.asarray([rows[mid] for mid in subset], jnp.int32)
    bs = jax.random.normal(KB, (len(subset), N, 2))
    xs = blockamc.execute_arena_packed_selected(pp, idx, bs,
                                                use_kernel=True)
    for i, mid in enumerate(subset):
        np.testing.assert_allclose(
            np.asarray(xs[i]),
            np.asarray(blockamc.execute_arena(svc.solver(mid).arena, bs[i],
                                              use_kernel=False)),
            rtol=1e-5, atol=1e-6)


def _flush_pairs(svc, pairs, sink):
    """Flush each pair with one rhs per tenant; the `service.pack` hits."""
    for pair in pairs:
        for mid in pair:
            svc.submit(mid, jax.random.normal(KB, (N,)))
        svc.flush_all()
    return [s[3]["hit"] for s in sink.spans if s[0] == "service.pack"]


class _Sink:
    """The span sink `tracing.enable` needs (the benchmark's Recorder)."""

    def __init__(self):
        import threading
        self.spans, self.active, self._lock = [], True, threading.Lock()


@pytest.fixture
def sink():
    from repro.runtime import tracing
    s = _Sink()
    tracing.enable(s)
    try:
        yield s
    finally:
        tracing.disable()


def test_resident_stack_reused_across_subsets(sink):
    """After the first packed flush builds the signature's resident
    stack, flushes of other subsets reuse it: `service.pack` hit 1 and
    the same stack object."""
    svc, ids = _service(m=4)
    hits = _flush_pairs(svc, [ids[:2]], sink)
    assert hits == [0]
    (entry,) = svc._packs.values()
    assert tuple(entry[0]) == tuple(ids)
    hits = _flush_pairs(svc, [ids[2:], (ids[1], ids[3]), ids], sink)
    assert hits == [0, 1, 1, 1]
    assert next(iter(svc._packs.values())) is entry


def test_equal_shapes_do_not_recompile():
    """Flushes of different subsets at the same (M, k) reuse the first
    one's executor: its jit cache and the backend compile count stay
    put after the first flush."""
    svc, ids = _service(m=4)
    compiles = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def flush(pair):
        for mid in pair:
            svc.submit(mid, jax.random.normal(KB, (N,)))
        svc.flush_all()

    executor = blockamc._execute_arena_packed_selected_donated
    flush((ids[0], ids[1]))
    cached = executor._cache_size()
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        flush((ids[2], ids[3]))
        flush((ids[1], ids[3]))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert executor._cache_size() == cached
    assert compiles == []


def test_new_tenant_rebuilds_resident_stack(sink):
    """A tenant programmed into the signature after its stack was built
    rebuilds it, at the new member count, on its first pending flush,
    and answers with its own numbers."""
    svc, ids = _service(m=3)
    assert _flush_pairs(svc, [ids[:2]], sink) == [0]
    (entry,) = svc._packs.values()
    assert entry[1].num_instances == 3 and len(entry[0]) == 3
    svc.program("m3", wishart(jax.random.fold_in(KA, 3), N),
                jax.random.fold_in(KN, 3))
    assert svc._packs                    # not a member: nothing dropped
    b = jax.random.normal(jax.random.fold_in(KB, 3), (N,))
    svc.submit("m0", b)
    svc.submit("m3", b)
    out = svc.flush_all()
    np.testing.assert_allclose(np.asarray(out["m3"][:, 0]),
                               np.asarray(svc.solver("m3").solve(b)),
                               rtol=1e-5, atol=1e-6)
    assert _flush_pairs(svc, [(ids[1], ids[2])], sink) == [0, 0, 1]
    (entry,) = svc._packs.values()
    assert tuple(entry[0]) == (*ids, "m3")
    assert entry[1].num_instances == 4
