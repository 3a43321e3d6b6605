"""Pallas level-megakernel for the arena-form BlockAMC executor.

One call executes one schedule-level group of the arena executor
(`repro.core.blockamc.execute_arena`): a stack of same-shape tiles, each
applying its precomputed operator (explicit INV inverse or sign/divisor-
folded MVM tile - see the DESIGN note in core/blockamc.py) to a signed sum
of static arena windows, writing or accumulating into its output window.
This generalises `crossbar_mvm_batched` from one conductance stack driving
private per-array inputs to shape-bucketed ragged tiles reading and writing
one shared register arena:

    v_t   = sum_j signs[t, j] * arena[in_offs[t, j] : in_offs[t, j] + C]
    out_t = ADC(ops[t] @ DAC(v_t))                      # (R, K) on the MXU
    arena[out_offs[t] : out_offs[t] + R] {=, +=} out_t  # init flag per tile

The leading grid axis walks the tiles of the group (each operator tile
streams HBM->VMEM once); the arena lives in one unblocked buffer revisited
by every step, so row-partial accumulation across the tiles of one MVM
tile-row happens in-place, in the schedule's order.  Signs, the summing-node
divisor and the circuit minus are folded into `ops` at arena-compile time;
DAC/ADC quantisation is fused into the tile loop exactly as in
`crossbar_mvm.py` (ideal converters by default - the cascade quantises once
at the input and once at the output, not per level).

`arena_packed_apply` is the multi-tenant extension: an *instance* grid
axis in front (grid = (M, T)) runs the whole shared tile program for M
packed same-signature plans over an (M, S, K) arena stack - window
metadata is one shared SMEM copy, operators carry a per-instance axis -
so one pallas_call serves an entire fleet of matrices.

On TPU the metadata arrays (offsets, signs, init flags) ride in SMEM so
the dynamic window starts are scalar reads, and the dot hits the MXU;
`interpret=True` (the CPU tests) executes the same body in Python per
grid step.  TPU alignment note: the `ops` wrappers pad the RHS-batch dim
to the 128 lanes; tile shapes and arena offsets are used as-is.  On a TPU
v5e the kernel compiles at the paper, 512^2/128^2 and four-stage 1024^2
plans (tests/test_tpu_compile.py), serves the paper fleet
(chip_smoke.py), and matches the jnp path bit for bit there, also on
plans whose window offsets are not multiples of 8 (60x60 tiles at
offsets 120, 180).  The tile dot asks for full f32: the chip's default
f32 dot is one bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import F32_DOT
# The one converter model (pure jnp, so it traces inside the kernel body).
from repro.core.quantization import quantize as _quantize


def _arena_packed_kernel(in_offs_ref, in_signs_ref, out_offs_ref,
                         out_init_ref, ops_ref, arena_ref, out_ref, *,
                         rows: int, cols: int, n_terms: int,
                         dac_bits: int | None, adc_bits: int | None,
                         fullscale: float):
    """The one arena tile-program body, instance-packed.

    grid = (M, T) walks every tile of the shared schedule (t, the fast
    axis) for each packed instance i.  Instance i owns its own (1, S, K)
    arena block - revisited across its whole t sweep, so level outputs
    accumulate in place - while the window metadata is one shared
    (T, ...) copy in SMEM and `ops` carries the per-instance operator
    sequence (M, T, R, C).  One pallas_call therefore executes the ENTIRE
    cascade of the ENTIRE fleet; the single-instance entry point
    (`arena_level_apply`) is the M=1 special case of this same body, so
    the two paths cannot diverge.
    """
    t = pl.program_id(1)

    # Carry the untouched arena cells through: the output buffer is the
    # arena, and only this level's output windows may change.  (With the
    # wrapper's input/output aliasing this lowers to a no-op self-copy.)
    @pl.when(t == 0)
    def _carry():
        out_ref[...] = arena_ref[...]

    # Signed static-window gather (the folded slice/add/catneg wiring).
    # Reads go through out_ref so tiles see this level's in-order writes
    # never needed for correctness (inputs and outputs of one level are
    # disjoint by construction) but required when the buffers alias.
    v = jnp.zeros((cols, out_ref.shape[-1]), jnp.float32)
    for j in range(n_terms):                       # static unroll
        off = in_offs_ref[t, j]
        v = v + in_signs_ref[t, j] * out_ref[0, pl.ds(off, cols), :]
    v = _quantize(v, dac_bits, fullscale)

    # (R, C) x (C, K) -> (R, K) on the MXU; sign/divisor pre-folded in ops.
    out = jax.lax.dot_general(
        ops_ref[0, 0], v, (((1,), (0,)), ((), ())), precision=F32_DOT,
        preferred_element_type=jnp.float32)
    out = _quantize(out, adc_bits, fullscale)

    o = out_offs_ref[t]

    @pl.when(out_init_ref[t] == 1)
    def _set():
        out_ref[0, pl.ds(o, rows), :] = out

    @pl.when(out_init_ref[t] == 0)
    def _accumulate():
        out_ref[0, pl.ds(o, rows), :] += out


def arena_packed_apply(arena: jnp.ndarray, ops: jnp.ndarray,
                       in_offs: jnp.ndarray, in_signs: jnp.ndarray,
                       out_offs: jnp.ndarray, out_init: jnp.ndarray, *,
                       dac_bits: int | None = None,
                       adc_bits: int | None = None, fullscale: float = 1.0,
                       interpret: bool = False) -> jnp.ndarray:
    """Run a whole packed tile program; returns the updated arena stack.

    Args:
      arena:    (M, S, K) f32 register arenas, one per packed instance.
      ops:      (M, T, R, C) operator tiles in shared schedule order.
      in_offs:  (T, J) int32 arena offsets of each tile's input windows
                (shared across instances - the stackability invariant).
      in_signs: (T, J) f32 signs (+1/-1; 0 pads unused term slots).
      out_offs: (T,) int32 output window offsets.
      out_init: (T,) int32; 1 = first write of its window, 0 = accumulate.
    """
    m, s, k = arena.shape
    _, t_steps, rows, cols = ops.shape
    assert ops.shape[0] == m, (ops.shape, m)
    assert in_offs.shape == in_signs.shape == (t_steps, in_offs.shape[1])
    assert out_offs.shape == out_init.shape == (t_steps,)
    n_terms = in_offs.shape[1]
    kernel = functools.partial(
        _arena_packed_kernel, rows=rows, cols=cols, n_terms=n_terms,
        dac_bits=dac_bits, adc_bits=adc_bits, fullscale=fullscale)
    smem = {} if interpret else {"memory_space": pltpu.SMEM}
    meta = pl.BlockSpec(in_offs.shape, lambda i, t: (0, 0), **smem)
    flat = pl.BlockSpec((t_steps,), lambda i, t: (0,), **smem)
    inst = pl.BlockSpec((1, s, k), lambda i, t: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(m, t_steps),
        in_specs=[meta, meta, flat, flat,
                  pl.BlockSpec((1, 1, rows, cols),
                               lambda i, t: (i, t, 0, 0)),
                  inst],
        out_specs=inst,
        out_shape=jax.ShapeDtypeStruct((m, s, k), jnp.float32),
        input_output_aliases={5: 0},     # each arena updates in place
        interpret=interpret,
    )(in_offs, in_signs, out_offs, out_init, ops, arena)


def arena_level_apply(arena: jnp.ndarray, ops: jnp.ndarray,
                      in_offs: jnp.ndarray, in_signs: jnp.ndarray,
                      out_offs: jnp.ndarray, out_init: jnp.ndarray, *,
                      dac_bits: int | None = None,
                      adc_bits: int | None = None, fullscale: float = 1.0,
                      interpret: bool = False) -> jnp.ndarray:
    """Apply one arena level group; returns the updated arena.

    The M=1 special case of `arena_packed_apply` (one kernel body for the
    single-tenant and packed paths - they cannot diverge).

    Args:
      arena:    (S, K) f32 register arena (K = RHS batch).
      ops:      (L, R, C) operator tiles (sign/divisor folded).
      in_offs:  (L, T) int32 arena offsets of each tile's input windows.
      in_signs: (L, T) f32 signs (+1/-1; 0 pads unused term slots).
      out_offs: (L,) int32 output window offsets.
      out_init: (L,) int32; 1 = first write of its window, 0 = accumulate.
    """
    l = ops.shape[0]
    assert in_offs.shape == in_signs.shape == (l, in_offs.shape[1])
    assert out_offs.shape == out_init.shape == (l,)
    return arena_packed_apply(
        arena[None], ops[None], in_offs, in_signs, out_offs, out_init,
        dac_bits=dac_bits, adc_bits=adc_bits, fullscale=fullscale,
        interpret=interpret)[0]
