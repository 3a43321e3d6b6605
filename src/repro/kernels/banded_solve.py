"""Pallas kernel: batched block-tridiagonal solve sweeps for the nodal oracle.

The physics-grade crossbar solve (`physics/nodal.py`) reduces each crossbar
to a block-tridiagonal SPD system - nr blocks of size s with constant
off-diagonal blocks -gw*I - factored once into an explicit-inverse stack
Minv (nr, s, s).  The remaining work, and the Monte-Carlo hot loop, is the
pair of block-Thomas sweeps

    forward:   z_i = Minv_i (rhs_i + gw * z_{i-1}),     z_{-1} = 0
    backward:  x_i = z_i + gw * Minv_i x_{i+1},         x_{nr} = 0

i.e. 2*nr dense (s x s) @ (s x k) matmuls per crossbar with a sequential
carry.  This kernel runs them for a whole batch in one pallas_call: the
grid walks the batch axis (one crossbar per grid step, its Minv stack and
rhs streamed HBM->VMEM once), and the two sweeps run inside the kernel
body as `fori_loop`s over the block rows, on the MXU.

Hybrid factor/solve split (deliberate, documented): the *factorization*
(the Minv recursion) stays in XLA - it is irreducibly sequential in i and
batched `linalg.inv` is already optimal there - so the kernel is pure
matmul sweeps over precomputed factors.  That is also what makes the
zero-padding contract trivial: padded rows/columns of Minv and rhs are
zero, zeros propagate zeros through both scans, and `ops.py` slices the
result back.

TPU alignment: ops.py pads s and k to the 128 lane width.  Each grid step
holds one crossbar's whole (nr, s, s) factor stack and (nr, s, k) rhs and
output blocks in VMEM, double-buffered.  The default scoped VMEM limit of a
v5e (16 MiB) holds that only up to nr = 32 at s = k = 128, and the oracle
runs nr = array rows (64 at the paper's arrays), so the kernel asks for
the VMEM its blocks need (`_vmem_limit`); tests/test_tpu_compile.py
compiles nr = 8 and 64.  On CPU the kernel executes
with interpret=True; interpret-mode parity against
`ref.block_tridiag_solve_ref` and the in-line jnp scans of nodal.py is the
tested contract (tests/test_physics_oracle.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import F32_DOT


def _block_tridiag_kernel(minv_ref, rhs_ref, out_ref, *, gw: float):
    # Both sweeps walk the refs one block row at a time with only the
    # (s, k) carry as a loop value: Mosaic cannot lower a scan that stacks
    # per-step outputs, so z_i lands in out_ref and the backward sweep
    # overwrites it with x_i in place.
    nr = rhs_ref.shape[1]
    dtype = out_ref.dtype
    z0 = jnp.zeros(rhs_ref.shape[2:], dtype)

    def dot(m, v):
        return jax.lax.dot_general(m, v, (((1,), (0,)), ((), ())),
                                   precision=F32_DOT,
                                   preferred_element_type=dtype)

    def fwd(i, z):
        zn = dot(minv_ref[0, i], rhs_ref[0, i] + gw * z)
        out_ref[0, i] = zn
        return zn

    jax.lax.fori_loop(0, nr, fwd, z0)

    def bwd(j, xn):
        i = nr - 1 - j
        xi = out_ref[0, i] + gw * dot(minv_ref[0, i], xn)
        out_ref[0, i] = xi
        return xi

    jax.lax.fori_loop(0, nr, bwd, z0)


def _vmem_limit(nr: int, s: int, k: int, itemsize: int) -> int:
    """Scoped VMEM for one grid step: the Minv, rhs and output blocks,
    double-buffered by the pipeline, plus 4 MiB for the (s, k) carries
    and dot temporaries."""
    return 2 * nr * s * (s + 2 * k) * itemsize + (4 << 20)


def block_tridiag_solve(minv: jnp.ndarray, rhs: jnp.ndarray, *, gw: float,
                        interpret: bool = False) -> jnp.ndarray:
    """Batched block-Thomas sweeps over precomputed inverse factors.

    Args:
      minv: (B, nr, s, s) per-crossbar explicit-inverse factor stacks.
      rhs:  (B, nr, s, k) right-hand-side blocks.
      gw:   wire segment conductance 1/r_seg (static Python float - it is
            baked into the kernel like g0 in crossbar_mvm).
    Returns:
      (B, nr, s, k) solution blocks.  s and k must be 128-aligned on TPU
      (ops.py pads); zero padding is exact (zeros propagate zeros).
    """
    b, nr, s, s2 = minv.shape
    b2, nr2, s3, k = rhs.shape
    assert (b, nr, s) == (b2, nr2, s3) and s == s2, (minv.shape, rhs.shape)
    kernel = functools.partial(_block_tridiag_kernel, gw=gw)
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nr, s, s), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, nr, s, k), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nr, s, k), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nr, s, k), rhs.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            nr, s, k, rhs.dtype.itemsize)),
        interpret=interpret,
    )(minv, rhs)
