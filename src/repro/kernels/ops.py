"""Jit'd public wrappers for the Pallas kernels: padding, dtype policy,
CPU-interpret fallback.

Off a TPU (the CPU test runs) `interpret=True` executes the kernel body in
Python per grid step; on a TPU the same BlockSpecs compile to Mosaic.
`arena_packed_apply` and `block_tridiag_solve` have been compiled and run
on a TPU v5e (chip_smoke.py, tests/test_tpu_compile.py); the crossbar and
Schur kernels have only been run in interpret mode.  The wrappers pad
ragged shapes up to the 128-aligned tile grid and slice the result back,
so callers never see the alignment constraint.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import arena_mvm as _arena
from repro.kernels import banded_solve as _banded
from repro.kernels import crossbar_mvm as _xbar
from repro.kernels import schur_gemm as _schur


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, mults) -> jnp.ndarray:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


@partial(jax.jit, static_argnames=("g0", "dac_bits", "adc_bits", "fullscale",
                                   "interpret"))
def crossbar_mvm(v, gpos, gneg, *, g0: float, dac_bits=None, adc_bits=None,
                 fullscale: float = 1.0, interpret: bool | None = None):
    """Batched differential crossbar MVM; see kernels/crossbar_mvm.py.

    v: (B, C), gpos/gneg: (R, C) -> (B, R).  Any shapes; pads to 128s.
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, c = v.shape
    r = gpos.shape[0]
    blk = 128
    vp = _pad_to(v, (blk, blk))
    gp = _pad_to(gpos, (blk, blk))
    gn = _pad_to(gneg, (blk, blk))
    out = _xbar.crossbar_mvm(vp, gp, gn, g0=g0, dac_bits=dac_bits,
                             adc_bits=adc_bits, fullscale=fullscale,
                             interpret=interpret)
    return out[:b, :r]


@partial(jax.jit, static_argnames=("g0", "dac_bits", "adc_bits", "fullscale",
                                   "interpret"))
def crossbar_mvm_batched(v, gpos, gneg, *, g0: float, dac_bits=None,
                         adc_bits=None, fullscale: float = 1.0,
                         interpret: bool | None = None):
    """Leading-dim batched crossbar MVM over a stack of physical arrays.

    v: (L, B, C), gpos/gneg: (L, R, C) -> (L, B, R).  The leading axis L
    (one entry per array of a flat-executor shape bucket) is a grid axis,
    never padded; trailing dims pad to 128s.
    """
    if interpret is None:
        interpret = not _on_tpu()
    l, b, c = v.shape
    r = gpos.shape[1]
    blk = 128
    vp = _pad_to(v, (1, blk, blk))
    gp = _pad_to(gpos, (1, blk, blk))
    gn = _pad_to(gneg, (1, blk, blk))
    out = _xbar.crossbar_mvm_batched(vp, gp, gn, g0=g0, dac_bits=dac_bits,
                                     adc_bits=adc_bits, fullscale=fullscale,
                                     interpret=interpret)
    return out[:, :b, :r]


@partial(jax.jit, static_argnames=("dac_bits", "adc_bits", "fullscale",
                                   "interpret"))
def arena_level_apply(arena, ops, in_offs, in_signs, out_offs, out_init, *,
                      dac_bits=None, adc_bits=None, fullscale: float = 1.0,
                      interpret: bool | None = None):
    """One arena level group (see kernels/arena_mvm.py); returns the arena.

    arena: (S, K), ops: (L, R, C), metadata per tile.  The RHS batch dim K
    is padded to the f32 lane width and sliced back; S and the tile dims
    are used as-is (arena offsets are byte positions in the register file -
    padding them would shift every window).  The kernel computes in f32
    (like every kernel in this package); the result is cast back to the
    arena's dtype so the caller's executor dtype is stable - under x64,
    accuracy is capped at f32 on this path (the jnp path keeps f64).
    """
    if interpret is None:
        interpret = not _on_tpu()
    s, k = arena.shape
    blk = 128
    ap = _pad_to(arena.astype(jnp.float32), (1, blk))
    out = _arena.arena_level_apply(
        ap, ops.astype(jnp.float32), in_offs, in_signs, out_offs, out_init,
        dac_bits=dac_bits, adc_bits=adc_bits, fullscale=fullscale,
        interpret=interpret)
    return out[:, :k].astype(arena.dtype)


@partial(jax.jit, static_argnames=("dac_bits", "adc_bits", "fullscale",
                                   "interpret"))
def arena_packed_apply(arena, ops, in_offs, in_signs, out_offs, out_init, *,
                       dac_bits=None, adc_bits=None, fullscale: float = 1.0,
                       interpret: bool | None = None):
    """Whole packed tile program (see kernels/arena_mvm.py); returns arenas.

    arena: (M, S, K) instance-stacked register arenas, ops: (M, T, R, C)
    per-instance operator sequences, window metadata (T, ...) shared across
    instances.  Same padding/dtype policy as `arena_level_apply`: the RHS
    batch dim K pads to the f32 lane width and slices back; M, S and the
    tile dims are used as-is (arena offsets are positions in the register
    file).  Computes in f32, cast back to the arena's dtype.
    """
    if interpret is None:
        interpret = not _on_tpu()
    m, s, k = arena.shape
    blk = 128
    ap = _pad_to(arena.astype(jnp.float32), (1, 1, blk))
    out = _arena.arena_packed_apply(
        ap, ops.astype(jnp.float32), in_offs, in_signs, out_offs, out_init,
        dac_bits=dac_bits, adc_bits=adc_bits, fullscale=fullscale,
        interpret=interpret)
    return out[:, :, :k].astype(arena.dtype)


@partial(jax.jit, static_argnames=("gw", "interpret"))
def block_tridiag_solve(minv, rhs, *, gw: float,
                        interpret: bool | None = None):
    """Batched block-Thomas sweeps; see kernels/banded_solve.py.

    minv: (B, nr, s, s), rhs: (B, nr, s, k) -> (B, nr, s, k).  The block
    size s and RHS width k pad to 128 and slice back; zero padding is exact
    for this kernel (zeros propagate zeros through both sweeps), so callers
    never see the alignment constraint.  Keeps the input dtype (the nodal
    oracle runs it under x64 for parity tests; interpret mode handles f64).
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, nr, s, k = rhs.shape
    blk = 128
    mp = _pad_to(minv, (1, 1, blk, blk))
    rp = _pad_to(rhs, (1, 1, blk, blk))
    out = _banded.block_tridiag_solve(mp, rp, gw=gw, interpret=interpret)
    return out[:, :, :s, :k]


@partial(jax.jit, static_argnames=("interpret",))
def schur_update(a4, a3, w, *, interpret: bool | None = None):
    """Fused A4 - A3 @ W; see kernels/schur_gemm.py.  Any shapes; pads."""
    if interpret is None:
        interpret = not _on_tpu()
    i, j = a4.shape
    blk = 128
    a4p = _pad_to(a4, (blk, blk))
    a3p = _pad_to(a3, (blk, blk))
    wp = _pad_to(w, (blk, blk))
    out = _schur.schur_update(a4p, a3p, wp, interpret=interpret)
    return out[:i, :j]


@partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    interpret: bool | None = None):
    """Blocked causal attention; see kernels/flash_attention.py.

    q, k, v: (BH, S, D).  Pads S to 128 (padded keys are masked by
    causality for the real rows; padded query rows are sliced away).
    """
    from repro.kernels import flash_attention as _fa
    if interpret is None:
        interpret = not _on_tpu()
    bh, s, d = q.shape
    blk = 128
    # padded keys sit after every real query, so causality masks them;
    # non-causal inputs must be pre-aligned.
    assert causal or s % blk == 0, "non-causal flash requires S % 128 == 0"
    qp = _pad_to(q, (1, blk, 1))
    kp = _pad_to(k, (1, blk, 1))
    vp = _pad_to(v, (1, blk, 1))
    out = _fa.flash_attention(qp, kp, vp, causal=causal,
                              interpret=interpret)
    return out[:, :s, :]
