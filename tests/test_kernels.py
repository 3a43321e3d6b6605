"""Pallas kernel tests: shape/dtype sweeps, allclose vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

G0 = 100e-6
KEY = jax.random.PRNGKey(0)


def _inputs(b, r, c, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    v = jax.random.uniform(k1, (b, c), dtype=jnp.float32, minval=-1, maxval=1)
    gpos = jax.random.uniform(k2, (r, c), dtype=jnp.float32, maxval=G0)
    gneg = jax.random.uniform(k3, (r, c), dtype=jnp.float32, maxval=G0)
    return v.astype(dtype), gpos.astype(dtype), gneg.astype(dtype)


# ------------------------------ crossbar_mvm ------------------------------

@pytest.mark.parametrize("b,r,c", [
    (128, 128, 128),     # single tile
    (128, 256, 384),     # K-accumulation over 3 steps
    (256, 128, 256),     # batch grid
    (32, 100, 72),       # ragged -> padding path
    (1, 257, 130),       # heavily ragged
])
def test_crossbar_matches_ref(b, r, c):
    v, gpos, gneg = _inputs(b, r, c)
    out = ops.crossbar_mvm(v, gpos, gneg, g0=G0)
    expect = ref.crossbar_mvm_ref(v, gpos, gneg, g0=G0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("l,b,r,c", [
    (1, 128, 128, 128),   # degenerate stack == plain batched MVM
    (4, 32, 64, 64),      # ragged trailing dims -> padding path
    (3, 5, 70, 130),      # heavily ragged, K-accumulation after padding
])
def test_crossbar_batched_matches_vmapped_ref(l, b, r, c):
    """Leading-dim entry point == per-array reference, incl. quantisers."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    v = jax.random.uniform(k1, (l, b, c), minval=-1, maxval=1)
    gpos = jax.random.uniform(k2, (l, r, c), maxval=G0)
    gneg = jax.random.uniform(k3, (l, r, c), maxval=G0)
    out = ops.crossbar_mvm_batched(v, gpos, gneg, g0=G0, dac_bits=8,
                                   adc_bits=8)
    expect = jax.vmap(lambda vv, gp, gn: ref.crossbar_mvm_ref(
        vv, gp, gn, g0=G0, dac_bits=8, adc_bits=8))(v, gpos, gneg)
    assert out.shape == (l, b, r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_crossbar_batched_matches_flat_stack():
    """The batched kernel reproduces one flat-executor INV-bucket stack."""
    from repro.core import blockamc
    from repro.core.analog import AnalogConfig
    from repro.core.nonideal import NonidealConfig
    from repro.data.matrices import wishart
    cfg = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.05))
    a = wishart(jax.random.PRNGKey(1), 64)
    fplan = blockamc.build_flat_plan(a, jax.random.PRNGKey(2), cfg, stages=2)
    grid = fplan.inv_stacks[0]              # (num, 16, 16) conductances
    num, s, _ = grid.shape
    v = jax.random.uniform(jax.random.PRNGKey(3), (num, 2, s),
                           minval=-1, maxval=1)
    out = ops.crossbar_mvm_batched(v, grid.gpos, grid.gneg, g0=cfg.g0)
    expect = jax.vmap(lambda vv, gp, gn: ref.crossbar_mvm_ref(
        vv, gp, gn, g0=cfg.g0))(v, grid.gpos, grid.gneg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_crossbar_dtypes(dtype):
    v, gpos, gneg = _inputs(128, 128, 128, dtype=dtype)
    out = ops.crossbar_mvm(v, gpos, gneg, g0=G0)
    expect = ref.crossbar_mvm_ref(v, gpos, gneg, g0=G0)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dac,adc", [(8, None), (None, 8), (6, 10), (8, 8)])
def test_crossbar_quantisation(dac, adc):
    """DAC before the sum, ADC after the complete sum - bit-exact vs oracle."""
    v, gpos, gneg = _inputs(128, 128, 256, seed=3)
    out = ops.crossbar_mvm(v, gpos, gneg, g0=G0, dac_bits=dac, adc_bits=adc)
    expect = ref.crossbar_mvm_ref(v, gpos, gneg, g0=G0, dac_bits=dac,
                                  adc_bits=adc)
    # f32 sum-order differences may flip a value across one ADC step at the
    # rounding boundary: allow <= 1 LSB.
    lsb = 2.0 / (2 ** adc - 1) if adc else 0.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=2e-5 + lsb)


def test_crossbar_matches_analog_layer():
    """Kernel == core/analog.py circuit model on the same crossbar pair."""
    from repro.core import analog
    from repro.core.analog import AnalogConfig
    cfg = AnalogConfig(array_size=64)
    a = jax.random.normal(jax.random.PRNGKey(5), (64, 64)) / 8.0
    scale = 1.0 / jnp.max(jnp.abs(a))
    pair = analog.map_matrix(a, jax.random.PRNGKey(6), cfg, scale)
    v = jax.random.uniform(jax.random.PRNGKey(7), (1, 64), minval=-1, maxval=1)
    out_kernel = ops.crossbar_mvm(v, pair.gpos, pair.gneg, g0=cfg.g0)[0]
    out_circuit = analog.amc_mvm(pair, v[0], cfg)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_circuit),
                               rtol=1e-4, atol=1e-6)


# ------------------------------- arena_mvm --------------------------------

def _arena_level_inputs(s=96, k=8, l=5, r=16, c=16, terms=2, seed=9):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    arena = jax.random.normal(k1, (s, k))
    opstack = jax.random.normal(k2, (l, r, c)) / c
    in_offs = jax.random.randint(k3, (l, terms), 0, s - c).astype(jnp.int32)
    in_signs = jnp.where(
        jax.random.bernoulli(k3, 0.5, (l, terms)), 1.0, -1.0
    ).astype(jnp.float32)
    # non-overlapping output windows, half of them accumulating pairs
    out_offs = jnp.asarray([s - (i // 2 + 1) * r for i in range(l)],
                           jnp.int32)
    out_init = jnp.asarray([1 if i % 2 == 0 else 0 for i in range(l)],
                           jnp.int32)
    return arena, opstack, in_offs, in_signs, out_offs, out_init


@pytest.mark.parametrize("dac,adc", [(None, None), (8, 8)])
def test_arena_level_matches_ref(dac, adc):
    """Megakernel (interpret on CPU) == sequential jnp oracle: signed
    multi-term gather, init-vs-accumulate windows, fused quantisers."""
    args = _arena_level_inputs()
    out = ops.arena_level_apply(*args, dac_bits=dac, adc_bits=adc)
    expect = ref.arena_level_ref(*args, dac_bits=dac, adc_bits=adc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_arena_level_preserves_untouched_cells():
    """Cells outside this level's output windows carry through unchanged."""
    arena, opstack, in_offs, in_signs, out_offs, out_init = \
        _arena_level_inputs(l=2, r=8)
    out = ops.arena_level_apply(arena, opstack, in_offs, in_signs,
                                out_offs, out_init)
    touched = set()
    for o in np.asarray(out_offs):
        touched.update(range(int(o), int(o) + 8))
    keep = np.asarray([i for i in range(arena.shape[0])
                       if i not in touched])
    np.testing.assert_array_equal(np.asarray(out)[keep],
                                  np.asarray(arena)[keep])


def test_arena_kernel_runs_whole_cascade():
    """One pallas_call executes a full uniform BlockAMC schedule (the
    single-dispatch serving form) - pinned against the slot-SSA path."""
    from repro.core import blockamc
    from repro.core.analog import AnalogConfig
    from repro.core.nonideal import NonidealConfig
    from repro.data.matrices import random_rhs, wishart
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05),
                       opa_gain=1e4)
    a = wishart(jax.random.PRNGKey(1), 32)
    ap = blockamc.compile_arena(blockamc.finalize(
        blockamc.build_flat_plan(a, jax.random.PRNGKey(2), cfg, 2), cfg))
    assert ap.program is not None
    b = random_rhs(jax.random.PRNGKey(3), 32)
    np.testing.assert_allclose(
        np.asarray(blockamc.execute_arena(ap, b, use_kernel=True)),
        np.asarray(blockamc.execute_arena(ap, b, use_kernel=False)),
        rtol=1e-6, atol=1e-7)


def _arena_packed_inputs(m=3, s=96, k=8, t=5, r=16, c=16, terms=2, seed=11):
    """Shared (T, ...) window metadata, per-instance (M, T, R, C) ops."""
    _, opstack, in_offs, in_signs, out_offs, out_init = \
        _arena_level_inputs(s=s, k=k, l=t, r=r, c=c, terms=terms, seed=seed)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    arena = jax.random.normal(k1, (m, s, k))
    ops_m = jax.random.normal(k2, (m, t, r, c)) / c
    return arena, ops_m, in_offs, in_signs, out_offs, out_init


@pytest.mark.parametrize("dac,adc", [(None, None), (8, 8)])
def test_arena_packed_matches_ref(dac, adc):
    """Instance-packed megakernel (interpret on CPU) == per-instance
    oracle replay of the shared tile program."""
    args = _arena_packed_inputs()
    out = ops.arena_packed_apply(*args, dac_bits=dac, adc_bits=adc)
    expect = ref.arena_packed_ref(*args, dac_bits=dac, adc_bits=adc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_arena_packed_matches_per_instance_level_calls():
    """The instance grid axis changes the dispatch, not the numbers: the
    packed kernel == M independent `arena_level_apply` runs of the same
    program."""
    arena, ops_m, in_offs, in_signs, out_offs, out_init = \
        _arena_packed_inputs(m=4)
    out = ops.arena_packed_apply(arena, ops_m, in_offs, in_signs,
                                 out_offs, out_init)
    for i in range(arena.shape[0]):
        one = ref.arena_level_ref(arena[i], ops_m[i], in_offs, in_signs,
                                  out_offs, out_init)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(one),
                                   rtol=1e-5, atol=1e-5)


def test_arena_packed_kernel_runs_whole_fleet():
    """One pallas_call executes the full uniform schedule of a packed
    multi-tenant fleet - pinned against the stacked slot-SSA path."""
    from repro.core import blockamc
    from repro.core.analog import AnalogConfig
    from repro.core.nonideal import NonidealConfig
    from repro.data.matrices import wishart
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05),
                       opa_gain=1e4)
    m, n = 3, 32
    keys = jax.random.split(jax.random.PRNGKey(2), m)
    As = jnp.stack([wishart(jax.random.fold_in(jax.random.PRNGKey(1), i),
                            n) for i in range(m)])
    pp = blockamc.program_packed(As, keys, cfg, stages=2)
    assert pp.program_ops is not None
    for bs in (jax.random.normal(jax.random.PRNGKey(3), (m, n)),
               jax.random.normal(jax.random.PRNGKey(4), (m, n, 3))):
        want = np.asarray(blockamc.execute_arena_packed(pp, bs,
                                                        use_kernel=False))
        # Small entries come out of cancelling sums of O(max|x|) terms, so
        # their absolute error is a few f32 ulps of max|x|, not of their own
        # magnitude: atol = 4 eps_f32 max|x| (measured: 2 ulps of max|x|).
        np.testing.assert_allclose(
            np.asarray(blockamc.execute_arena_packed(pp, bs,
                                                     use_kernel=True)),
            want, rtol=1e-6,
            atol=4 * np.finfo(np.float32).eps * np.abs(want).max())


# ------------------------------- schur_gemm -------------------------------

@pytest.mark.parametrize("i,j,k", [
    (128, 128, 128),
    (256, 128, 384),
    (100, 60, 130),      # ragged
])
def test_schur_matches_ref(i, j, k):
    k1, k2, k3 = jax.random.split(KEY, 3)
    a4 = jax.random.normal(k1, (i, j))
    a3 = jax.random.normal(k2, (i, k))
    w = jax.random.normal(k3, (k, j))
    out = ops.schur_update(a4, a3, w)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.schur_update_ref(a4, a3, w)),
                               rtol=1e-4, atol=1e-4)


def test_schur_in_blockamc_context():
    """Kernel result plugs into the actual Schur pre-processing."""
    from repro.data.matrices import wishart
    a = wishart(jax.random.PRNGKey(1), 256)
    m = 128
    a1, a2, a3, a4 = a[:m, :m], a[:m, m:], a[m:, :m], a[m:, m:]
    w = jnp.linalg.solve(a1, a2)
    out = ops.schur_update(a4, a3, w)
    expect = a4 - a3 @ w
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_schur_dtypes(dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    a4 = jax.random.normal(k1, (128, 128)).astype(dtype)
    a3 = jax.random.normal(k2, (128, 128)).astype(dtype)
    w = jax.random.normal(k3, (128, 128)).astype(dtype)
    out = ops.schur_update(a4, a3, w)
    expect = ref.schur_update_ref(a4, a3, w)
    tol = 1e-4 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


# ----------------------------- flash_attention -----------------------------

def _ref_attn_inputs(bh, s, d, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (bh, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (bh, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (bh, s, d), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("bh,s,d", [
    (2, 128, 128),     # single tile
    (1, 384, 128),     # 3x3 K blocks, causal skipping
    (2, 200, 128),     # ragged S -> causal padding path
])
def test_flash_attention_matches_ref(bh, s, d):
    q, k, v = _ref_attn_inputs(bh, s, d)
    out = ops.flash_attention(q, k, v)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    q, k, v = _ref_attn_inputs(2, 256, 128, dtype=dtype)
    out = ops.flash_attention(q, k, v)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_in_model_layer():
    """Model attention with use_flash == the q-chunked jnp path."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import attention as attn_mod
    from repro.models.attention import attention, init_attention
    cfg = dataclasses.replace(
        get_config("glm4-9b"), n_layers=1, d_model=256, n_heads=2,
        kv_heads=1, head_dim=128, vocab=64, d_ff=64,
        param_dtype="float32", compute_dtype="float32")
    params = init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 256))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    out_flash = attention(params, x, pos, cfg, use_flash=True)
    out_chunk = attention(params, x, pos, cfg, use_flash=False)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_chunk),
                               rtol=2e-4, atol=2e-4)
