"""BlockAMC: block-partitioned analog solver for A x = b (paper Section III).

The original matrix A is partitioned

        A = [[A1, A2],      b = [f,
             [A3, A4]]           g]

and the solve proceeds in five cascaded analog operations (Algorithm 1):

    step 1  INV(A1):   -y_t = -A1^-1 f
    step 2  MVM(A3):    g_t = -A3 (-y_t)
    step 3  INV(A4s):   z   = -A4s^-1 (-g_s),  A4s = A4 - A3 A1^-1 A2,
                                               -g_s = -g + g_t
    step 4  MVM(A2):   -f_t = -A2 z
    step 5  INV(A1):   -y   = -A1^-1 f_s,      f_s = f - f_t

    x = [y; z]

A4s (the Schur complement) is computed **digitally in advance** and programmed
into its own array - the paper's stated pre-processing overhead.  Multi-stage
solving recurses on the INV steps: every INV whose operand exceeds the
physical array size is itself solved by BlockAMC, and oversized MVM operands
use partitioned (tiled) MVM.  Two stages on a 256x256 system yields 16 arrays
of 64x64, matching paper Fig. 8.

The implementation is plan/execute:

  * `build_plan(A, key, cfg, stages)` does everything that happens at
    *programming time*: partitioning, digital Schur complements, matrix
    normalisation, conductance mapping with per-array programming noise.
  * `execute(plan, b, cfg)` runs the five-step cascade - the *analog runtime*
    - reusing the programmed arrays for any number of right-hand sides.

Both are pure functions of their inputs (vmap-able over noise keys for the
paper's 40-seed Monte Carlo, and jit-able end to end).

On top of the recursive reference executor sits the *flat* level-scheduled
executor (`compile_plan` / `execute_flat` / `solve_batched`): the recursive
plan is compiled once into shape-bucketed stacks of physical arrays (e.g. a
two-stage 256x256 solve becomes 16 arrays of 64x64, stored as a handful of
(num_arrays, 64, 64) conductance tensors - paper Fig. 8) plus a static
straight-line schedule over virtual registers.  Execution is a short loop
over schedule levels; every level is one batched analog op, so vmapping over
Monte-Carlo noise keys and right-hand sides turns the whole cascade into a
few large batched matmuls/solves instead of a per-seed tree walk.  The
recursive executor stays as the bit-level reference the flat executor is
tested against.

On top of *that* sits the finalization layer (`finalize` / `FinalizedPlan` /
`ProgrammedSolver`): once per programmed matrix, every INV bucket's effective
operator is LU-factorised and every MVM level's effective tile operators are
gathered into fused (num_tiles, r, c) stacks, so each subsequent solve is
pure batched `lu_solve`s and stacked matmuls - the paper's program-once /
solve-many cost model.  `execute_flat` remains the unfinalized reference the
finalized path is pinned to bit-for-bit.

DESIGN - the arena executor (`compile_arena` / `ArenaPlan` / `execute_arena`)
=============================================================================
The serving hot path compiles one step further.  `execute_finalized` still
runs a Python-interpreted schedule of small XLA ops: a growing register
list, `jnp.concatenate` at every "catneg", per-tile-row Python loops in
`_MvmLevel.apply`, and one `lu_solve` per INV level.  The AMC hardware view
(Sun & Ielmini 2022) is simpler: the INV macro is a one-step closed-form
inverse operator and the cascade is a handful of stacked MVMs.  The arena
form mirrors that:

  * **Static register arena.**  At compile time a live-range analysis walks
    the flat schedule.  Only *compute* results (leaf INV outputs, MVM
    outputs) and the DAC'd input vector are materialized; each gets a
    static offset in one preallocated f32 arena (trailing RHS-batch dim).
    The offline allocator (best of first-fit-in-def-order and
    greedy-by-size over the known live intervals) recycles dead slots: the
    arena extent equals the schedule's peak liveness exactly on aligned
    power-of-two schedules and stays within one slot of it on ragged odd
    splits (`tests/test_plan_properties.py` pins no-overlap, window
    containment and both bounds).
  * **Wiring ops cost zero copies.**  "slice"/"add"/"catneg" levels never
    execute: they are folded into *views* - each consumer reads its operand
    as a static list of signed slot windows (segment = (dst_lo, len,
    ((mreg, local_off, sign), ...)), arena offset = slot_offsets[mreg] +
    local_off), evaluated in the reference accumulation order, so the
    gather is bit-identical to the folded adds/negations.
  * **One stacked-tile form for INV and MVM.**  Every INV bucket's
    effective operator is explicitly inverted once at compile time (batched
    solve of the identity against the finalize-time LU factors, sign
    folded: W = -A_fx^-1), and every MVM tile's operator is stored with the
    circuit sign and its tile-row's finite-gain summing-node divisor folded
    in (W = -A_eff / div).  Each runtime level is then `out += W @ gather`
    - pure stacked matmuls.
  * **Two executions of one layout.**  On TPU the Pallas level-megakernel
    (`repro.kernels.arena_mvm`) owns the physical arena buffer - uniform
    power-of-two plans flatten to a whole-schedule tile program
    (`ArenaPlan.program`) run as ONE pallas_call; `interpret=True` runs
    the same body on CPU (the CI smoke).  The CPU fast path executes the
    identical layout in slot-SSA form (each slot its own XLA value), which
    keeps the gathers/writes fusible and skips whole-arena update copies.

Bit-compat contract: recursive == flat == finalized stays bit-for-bit on
CPU (eager) as before.  The arena mode is *float-tolerance* by design - the
explicit inverse reassociates the INV solve and the divisor is applied
before the tile dot instead of after - and is pinned against the finalized
executor by the four-way equivalence suite (tests/test_fused_arena.py,
TESTING.md).  It is the default `mode="fused"` on the serving surfaces
(`ProgrammedSolver`, `SolverService`, `AnalogPreconditioner`);
`mode="reference"` keeps the finalized path.

DESIGN - the packed instance axis (multi-tenant serving)
========================================================
A solver service fields many *different matrices* concurrently; the packed
layer adds the cross-tenant axis the per-matrix arena form lacks.

  * **Signature-stackability invariant.**  Every static artifact of the
    compile pipeline - partition split tree, bucket shapes, flat schedule,
    finalized windows, arena slot layout, whole-schedule window program -
    is a deterministic function of (n, stages, cfg) alone; matrix values
    and noise keys only ever flow into array *contents*.
    `plan_signature(n, stages, cfg)` is therefore a sufficient key: plans
    with equal signatures flatten to identical treedefs, leaf shapes and
    static metadata, and may be stacked leaf-for-leaf on a leading
    instance axis (pinned by tests/test_plan_properties.py).
  * **Instance-axis layout.**  A `PackedArenaPlan` stores the shared
    static metadata once and carries every operator stack as
    (M, L, rows, cols) - instance axis first, then the ArenaPlan layout
    unchanged - with (M,) scales and, for uniform plans, the (M, T, r, c)
    whole-schedule operator sequence over ONE shared (T, ...) window
    program.  Batched programming (`program_system_batched` /
    `finalize_batched` / `compile_arena_batched`, or `program_packed`
    end to end) vmaps the per-matrix pipeline, so programming a fleet
    costs one trace; `pack_arena_plans` stacks independently programmed
    plans (the `SolverService.flush_all` path).
  * **One dispatch over (tenants x rhs).**  `execute_arena_packed` runs
    every schedule level as stacked-tile matmuls whose batch dims carry
    the instance axis (per-tenant results bit-for-bit with that tenant's
    own `execute_arena` eagerly on CPU for aligned power-of-two plans;
    last-ulp on ragged splits), and the packed Pallas megakernel
    (`kernels/arena_mvm.py arena_packed_apply`) grows an instance grid
    axis: grid (M, T) over an (M, S, K) arena stack, the whole fleet in
    ONE pallas_call.  `sharding.partition.mc_packed_specs` shards the
    instance axis over the mc mesh (`execute_arena_packed_sharded`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import analog
from repro.core.analog import AnalogConfig, CrossbarPair, TileGrid
from repro.core.precision import F32_DOT as _F32
from repro.runtime import tracing


# ---------------------------------------------------------------------------
# Plans (pytrees)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class LeafInvPlan:
    """An INV operation small enough for one physical array."""

    def __init__(self, pair: CrossbarPair):
        self.pair = pair

    def tree_flatten(self):
        return (self.pair,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    @property
    def n(self):
        return self.pair.shape[0]


@jax.tree_util.register_pytree_node_class
class BlockPlan:
    """One BlockAMC stage: INV plans for A1/A4s, tiled MVM grids for A2/A3."""

    def __init__(self, inv1, mvm2, mvm3, inv4s, m):
        self.inv1 = inv1      # plan for A1 (LeafInvPlan or BlockPlan)
        self.mvm2 = mvm2      # tile grid for A2
        self.mvm3 = mvm3      # tile grid for A3
        self.inv4s = inv4s    # plan for A4s
        self.m = m            # split point (static)

    def tree_flatten(self):
        return (self.inv1, self.mvm2, self.mvm3, self.inv4s), (self.m,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0])

    @property
    def n(self):
        return self.inv1.n + self.inv4s.n


Plan = Union[LeafInvPlan, BlockPlan]


@dataclasses.dataclass
class SolvePlan:
    """Top-level plan: the recursive structure plus the global scale."""
    root: Plan
    scale: jnp.ndarray   # c = 1/max|A|; solution is descaled digitally


jax.tree_util.register_dataclass(
    SolvePlan, data_fields=["root", "scale"], meta_fields=[])


# ---------------------------------------------------------------------------
# Plan construction (programming time)
#
# Split into two walks so the Monte-Carlo path can hoist the expensive,
# *key-independent* digital pre-processing (partitioning, Schur complements,
# normalisation) out of the per-noise-key loop:
#
#   partition_system(a, cfg, stages)  -> PartitionedSystem   (digital, once)
#   program_system(parts, key, cfg)   -> SolvePlan           (per noise key)
#
# `build_plan` composes the two and is unchanged API-wise; the key-splitting
# order of `program_system` matches the old fused builder exactly, so noise
# draws (and therefore every downstream golden test) are bit-identical.
# ---------------------------------------------------------------------------

def required_stages(n: int, array_size: int) -> int:
    """Smallest number of partitioning stages so every INV fits one array."""
    stages = 0
    while n > array_size:
        n = -(-n // 2)
        stages += 1
    return stages


@jax.tree_util.register_pytree_node_class
class LeafTarget:
    """Partitioning leaf: one block destined for a single INV array."""

    def __init__(self, a):
        self.a = a

    def tree_flatten(self):
        return (self.a,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    @property
    def n(self):
        return self.a.shape[0]


@jax.tree_util.register_pytree_node_class
class BlockTarget:
    """One partitioning stage: INV targets for A1/A4s, raw blocks A2/A3."""

    def __init__(self, inv1, a2, a3, inv4s, m):
        self.inv1 = inv1
        self.a2 = a2
        self.a3 = a3
        self.inv4s = inv4s
        self.m = m

    def tree_flatten(self):
        return (self.inv1, self.a2, self.a3, self.inv4s), (self.m,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0])

    @property
    def n(self):
        return self.inv1.n + self.inv4s.n


Target = Union[LeafTarget, BlockTarget]


@dataclasses.dataclass
class PartitionedSystem:
    """Key-independent digital pre-processing of one system matrix."""
    root: Target
    scale: jnp.ndarray   # c = 1/max|A|


jax.tree_util.register_dataclass(
    PartitionedSystem, data_fields=["root", "scale"], meta_fields=[])


def _split_tree(n: int, stages: int):
    """The static partition split tree for (n, stages): a leaf size, or a
    pair of subtrees.

    The one definition of the split rule - `_partition` consumes this tree
    and `plan_signature` hashes it, so the packed-serving stackability key
    stays correct by construction if the rule ever changes.  A 1x1 block
    cannot be partitioned further: splitting it would produce zero-width
    A2/A3 and an empty Schur complement (physical arrays with no devices),
    so surplus stages stop there.  Paper: for odd n, A1 takes (n+1)/2; any
    square A1 works.
    """
    if stages == 0 or n <= 1:
        return int(n)
    m = -(-n // 2)
    return (_split_tree(m, stages - 1), _split_tree(n - m, stages - 1))


def _tree_size(tree) -> int:
    return tree if isinstance(tree, int) else \
        _tree_size(tree[0]) + _tree_size(tree[1])


def _partition_by(a: jnp.ndarray, tree) -> Target:
    if isinstance(tree, int):
        return LeafTarget(a)
    left, right = tree
    m = _tree_size(left)
    a1, a2 = a[:m, :m], a[:m, m:]
    a3, a4 = a[m:, :m], a[m:, m:]
    # Digital pre-processing of the Schur complement (paper Eq. 3).  Done in
    # f32 here, standing in for the host preprocessor in Fig. 3 - at full
    # f32 on every backend (a TPU's default f32 dot is one bf16 pass).
    a4s = a4 - jnp.matmul(a3, jnp.linalg.solve(a1, a2), precision=_F32)
    return BlockTarget(_partition_by(a1, left), a2, a3,
                       _partition_by(a4s, right), m)


def _partition(a: jnp.ndarray, stages: int) -> Target:
    return _partition_by(a, _split_tree(a.shape[0], stages))


def partition_system(a: jnp.ndarray, cfg: AnalogConfig,
                     stages: Optional[int] = None) -> PartitionedSystem:
    """Partition, Schur-complement and normalise A (no noise key needed).

    stages=None auto-selects the minimum depth so leaves fit cfg.array_size
    (stages=1 -> paper's one-stage solver, 2 -> two-stage, 0 -> original AMC).
    """
    n = a.shape[0]
    if stages is None:
        stages = required_stages(n, cfg.array_size)
    # Global normalisation: largest |element| of the *original* matrix -> 1.
    scale = 1.0 / jnp.max(jnp.abs(a))
    return PartitionedSystem(root=_partition(a, stages), scale=scale)


def _program(t: Target, key: jax.Array, cfg: AnalogConfig,
             scale: jnp.ndarray) -> Plan:
    if isinstance(t, LeafTarget):
        return LeafInvPlan(analog.map_matrix(t.a, key, cfg, scale))
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return BlockPlan(
        inv1=_program(t.inv1, k1, cfg, scale),
        mvm2=analog.map_tiled(t.a2, k2, cfg, scale),
        mvm3=analog.map_tiled(t.a3, k3, cfg, scale),
        inv4s=_program(t.inv4s, k4, cfg, scale),
        m=t.m,
    )


def program_system(parts: PartitionedSystem, key: jax.Array,
                   cfg: AnalogConfig) -> SolvePlan:
    """'Program' a partitioned system: conductance mapping + device noise."""
    return SolvePlan(root=_program(parts.root, key, cfg, parts.scale),
                     scale=parts.scale)


def build_plan(a: jnp.ndarray, key: jax.Array, cfg: AnalogConfig,
               stages: Optional[int] = None) -> SolvePlan:
    """Partition, pre-process, normalise and 'program' matrix A."""
    return program_system(partition_system(a, cfg, stages), key, cfg)


def build_original_plan(a: jnp.ndarray, key: jax.Array,
                        cfg: AnalogConfig) -> SolvePlan:
    """The baseline 'original AMC': one monolithic INV array of size n.

    Used by every paper comparison ('compared to a single AMC circuit
    solving the same problem').  Ignores cfg.array_size deliberately.
    """
    scale = 1.0 / jnp.max(jnp.abs(a))
    return SolvePlan(root=LeafInvPlan(analog.map_matrix(a, key, cfg, scale)),
                     scale=scale)


# ---------------------------------------------------------------------------
# Execution (analog runtime; five-step cascade per stage)
# ---------------------------------------------------------------------------

def _exec_inv(plan: Plan, v_in: jnp.ndarray, cfg: AnalogConfig) -> jnp.ndarray:
    """Run an INV plan with the circuit sign convention: returns -A^-1 v_in."""
    if isinstance(plan, LeafInvPlan):
        return analog.amc_inv(plan.pair, v_in, cfg)
    m = plan.m
    f, g = v_in[:m], v_in[m:]
    # --- Algorithm 1, signs kept exactly as the circuits produce them. ---
    neg_yt = _exec_inv(plan.inv1, f, cfg)                 # step 1: -y_t
    gt = analog.amc_mvm_tiled(plan.mvm3, neg_yt, cfg)     # step 2: -A3(-y_t) = g_t
    neg_gs = -g + gt                                      # analog summation: -g_s
    z = _exec_inv(plan.inv4s, neg_gs, cfg)                # step 3: -A4s^-1(-g_s) = +z
    neg_ft = analog.amc_mvm_tiled(plan.mvm2, z, cfg)      # step 4: -f_t
    fs = f + neg_ft                                       # f_s = f - f_t
    neg_y = _exec_inv(plan.inv1, fs, cfg)                 # step 5: -y  (A1 reused)
    # This function's contract is 'return -A^-1 v_in' = [-y; -z].
    return jnp.concatenate([neg_y, -z])


def execute(plan: SolvePlan, b: jnp.ndarray, cfg: AnalogConfig) -> jnp.ndarray:
    """Solve A x = b with the programmed plan; returns x (digitally descaled).

    With the global normalisation A' = c A (c = plan.scale), the arrays hold
    A' and the cascade's ADC output is  out = -(A')^-1 b = -(A^-1 b)/c, so the
    host recovers  x = -c * out  - one sign flip and one scalar multiply in
    the digital domain.
    """
    b_in = analog.dac(b, cfg)
    out = _exec_inv(plan.root, b_in, cfg)       # = -(cA)^-1 b = -x/c
    out = analog.adc(out, cfg)
    return -plan.scale * out


def solve(a: jnp.ndarray, b: jnp.ndarray, key: jax.Array, cfg: AnalogConfig,
          stages: Optional[int] = None) -> jnp.ndarray:
    """Convenience: build_plan + execute."""
    return execute(build_plan(a, key, cfg, stages), b, cfg)


def solve_original(a: jnp.ndarray, b: jnp.ndarray, key: jax.Array,
                   cfg: AnalogConfig) -> jnp.ndarray:
    """Baseline: original (monolithic) AMC solve."""
    return execute(build_original_plan(a, key, cfg), b, cfg)


# ---------------------------------------------------------------------------
# Flat (level-scheduled) executor
#
# compile_plan() walks a SolvePlan once at trace time and lowers it to
#   * stacked conductance tensors: every physical array of the cascade is
#     interned into a (depth, shape) bucket, so all same-shape arrays at the
#     same cascade depth live in one (num_arrays, rows, cols) TileGrid, and
#   * a static straight-line schedule of levels over virtual registers.
#
# Each schedule level is exactly one analog operation (a leaf INV, a tiled
# MVM, an analog summation, or a wiring step), so executing a plan is a short
# Python loop whose body is entirely batched jnp ops - no tree recursion at
# run time.  Because the schedule and all shapes are static, `execute_flat`
# vmaps/jits cleanly: batching over Monte-Carlo noise keys adds a leading
# axis to every stack and turns each level into one batched matmul or
# batched solve, which is how the hot Monte-Carlo path scales with the
# *number of arrays* instead of the depth of the tree.
# ---------------------------------------------------------------------------

# Schedule instruction set (all operands are static Python ints):
#   ("slice", src, lo, hi)        reg = regs[src][lo:hi]      (partition wiring)
#   ("inv",   bucket, idx, src)   reg = amc_inv(inv_stack[bucket][idx], regs[src])
#   ("mvm",   rows, src)          reg = amc_mvm_tiled(grid, regs[src]); `rows`
#                                 is a tuple of tile-rows of (bucket, idx)
#                                 refs into the MVM stacks
#   ("add",   s1, r1, s2, r2)     reg = s1*regs[r1] + s2*regs[r2], s in {+1,-1}
#                                 (analog current summation at a summing node)
#   ("catneg", r1, r2)            reg = concat([regs[r1], -regs[r2]])
#                                 (reassemble [ -y ; -z ] from cascade halves)


@jax.tree_util.register_pytree_node_class
class FlatPlan:
    """Level-scheduled form of a SolvePlan.

    `inv_stacks` / `mvm_stacks` are tuples of TileGrid, one per
    (cascade depth, array shape) bucket; entry i of a stack holds physical
    array i of that bucket as programmed (identical conductances to the
    recursive plan it was compiled from).  `schedule` is the static level
    program; `inv_keys` / `mvm_keys` record each bucket's (depth, shape)
    for introspection and tests.
    """

    def __init__(self, inv_stacks, mvm_stacks, scale, schedule, n,
                 inv_keys, mvm_keys):
        self.inv_stacks = inv_stacks
        self.mvm_stacks = mvm_stacks
        self.scale = scale
        self.schedule = schedule
        self.n = n
        self.inv_keys = inv_keys
        self.mvm_keys = mvm_keys

    def tree_flatten(self):
        return ((self.inv_stacks, self.mvm_stacks, self.scale),
                (self.schedule, self.n, self.inv_keys, self.mvm_keys))

    @classmethod
    def tree_unflatten(cls, aux, children):
        inv_stacks, mvm_stacks, scale = children
        return cls(inv_stacks, mvm_stacks, scale, *aux)

    @property
    def num_arrays(self) -> int:
        """Total physical arrays of the cascade (16 for 256^2 two-stage)."""
        return sum(g.shape[-3] for g in self.inv_stacks) + \
            sum(g.shape[-3] for g in self.mvm_stacks)

    @property
    def num_levels(self) -> int:
        return len(self.schedule)


class _Interner:
    """Dedupes physical arrays into (depth, shape)-bucketed stacking lists.

    The same CrossbarPair object can be referenced several times by the
    schedule (A1 serves cascade steps 1 and 5), but is programmed - and
    therefore stacked - exactly once.
    """

    def __init__(self):
        self.key_to_bucket = {}
        self.lists = []
        self.keys = []
        self._memo = {}

    def ref(self, key, pair) -> Tuple[int, int]:
        tag = id(pair)
        if tag in self._memo:
            return self._memo[tag]
        if key not in self.key_to_bucket:
            self.key_to_bucket[key] = len(self.lists)
            self.lists.append([])
            self.keys.append(key)
        bucket = self.key_to_bucket[key]
        self.lists[bucket].append(pair)
        out = (bucket, len(self.lists[bucket]) - 1)
        self._memo[tag] = out
        return out


def compile_plan(plan: SolvePlan) -> FlatPlan:
    """Lower a recursive SolvePlan to its level-scheduled flat form.

    Pure restructuring: the stacked conductances are exactly the recursive
    plan's (same noise draws), so both executors compute with identical
    arrays.  Traceable (works under jit/vmap over noise keys).
    """
    invs, mvms = _Interner(), _Interner()
    prog = []
    n_regs = [1]                      # register 0 is the cascade input

    def emit(instr) -> int:
        prog.append(instr)
        r = n_regs[0]
        n_regs[0] += 1
        return r

    def emit_inv(p: Plan, src: int, depth: int) -> int:
        if isinstance(p, LeafInvPlan):
            bucket, idx = invs.ref((depth, p.pair.shape), p.pair)
            return emit(("inv", bucket, idx, src))
        m, n = p.m, p.n
        f = emit(("slice", src, 0, m))
        g = emit(("slice", src, m, n))
        # Five-step cascade (Algorithm 1), one schedule level per step.
        neg_yt = emit_inv(p.inv1, f, depth + 1)                  # step 1
        rows3 = tuple(tuple(mvms.ref((depth, t.shape), t) for t in row)
                      for row in p.mvm3)
        gt = emit(("mvm", rows3, neg_yt))                        # step 2
        neg_gs = emit(("add", -1, g, 1, gt))
        z = emit_inv(p.inv4s, neg_gs, depth + 1)                 # step 3
        rows2 = tuple(tuple(mvms.ref((depth, t.shape), t) for t in row)
                      for row in p.mvm2)
        neg_ft = emit(("mvm", rows2, z))                         # step 4
        fs = emit(("add", 1, f, 1, neg_ft))
        neg_y = emit_inv(p.inv1, fs, depth + 1)                  # step 5
        return emit(("catneg", neg_y, z))

    emit_inv(plan.root, 0, 0)
    g0 = _first_pair(plan.root).g0
    inv_stacks = tuple(analog.stack_pairs(ps, plan.scale, g0)
                       for ps in invs.lists)
    mvm_stacks = tuple(analog.stack_pairs(ps, plan.scale, g0)
                       for ps in mvms.lists)
    return FlatPlan(inv_stacks, mvm_stacks, plan.scale, tuple(prog),
                    plan.root.n, tuple(invs.keys), tuple(mvms.keys))


def _first_pair(p: Plan) -> CrossbarPair:
    return p.pair if isinstance(p, LeafInvPlan) else _first_pair(p.inv1)


def build_flat_plan(a: jnp.ndarray, key: jax.Array, cfg: AnalogConfig,
                    stages: Optional[int] = None) -> FlatPlan:
    """Convenience: build_plan + compile_plan."""
    return compile_plan(build_plan(a, key, cfg, stages))


def _inv_operators(grid: TileGrid, cfg: AnalogConfig,
                   r_wire=None, drift_t=None) -> jnp.ndarray:
    """The (num, s, s) matrices one INV bucket's circuits solve with.

    Matches analog.amc_inv: effective conductance matrix plus the diagonal
    summing-node loading term under finite OPA gain.  `r_wire` optionally
    overrides the static config wire resistance with a traced scalar (the
    calibration path; see `finalize`); `drift_t` optionally overrides the
    static device age - a scalar, or a (num,) vector aging each array of
    the bucket independently (the simulated-device-clock path).
    """
    a = grid.a_eff(cfg, r_wire=r_wire, drift_t=drift_t)
    if cfg.opa_gain is not None:
        load = (cfg.g0 + jnp.sum(grid.gpos + grid.gneg, axis=-1)) \
            / (cfg.opa_gain * cfg.g0)
        a = a + load[..., :, None] * jnp.eye(a.shape[-1], dtype=a.dtype)
    return a


def execute_flat(fplan: FlatPlan, b: jnp.ndarray, cfg: AnalogConfig
                 ) -> jnp.ndarray:
    """Run the level schedule; returns x like `execute`.

    `b` may be a vector (n,) or a matrix (n, k) of k right-hand sides -
    every schedule level then computes all k solves in one batched op.

    Program-once / solve-many: every leaf INV operator is factorised once
    per bucket (one batched LU per stack), and the schedule's INV levels
    reuse the factors - cascade steps 1 and 5 share A1's factorisation
    exactly as the hardware reuses the programmed array.
    """
    lu_stacks = [jax.scipy.linalg.lu_factor(_inv_operators(g, cfg))
                 for g in fplan.inv_stacks]
    regs = [analog.dac(b, cfg)]
    for instr in fplan.schedule:
        op = instr[0]
        if op == "slice":
            _, src, lo, hi = instr
            regs.append(regs[src][lo:hi])
        elif op == "inv":
            _, bucket, idx, src = instr
            lu, piv = lu_stacks[bucket]
            regs.append(-jax.scipy.linalg.lu_solve((lu[idx], piv[idx]),
                                                   regs[src]))
        elif op == "mvm":
            _, rows, src = instr
            grid = [[fplan.mvm_stacks[bk].pair(i) for bk, i in row]
                    for row in rows]
            regs.append(analog.amc_mvm_tiled(grid, regs[src], cfg))
        elif op == "add":
            _, s1, r1, s2, r2 = instr
            x1 = regs[r1] if s1 > 0 else -regs[r1]
            x2 = regs[r2] if s2 > 0 else -regs[r2]
            regs.append(x1 + x2)
        elif op == "catneg":
            _, r1, r2 = instr
            regs.append(jnp.concatenate([regs[r1], -regs[r2]]))
        else:  # pragma: no cover - compile_plan only emits the ops above
            raise ValueError(f"unknown schedule op {op!r}")
    return -fplan.scale * analog.adc(regs[-1], cfg)


# ---------------------------------------------------------------------------
# Finalization: program-once / solve-many
#
# `execute_flat` still re-pays programming-time costs on every call: it
# re-factorises every INV bucket and re-derives every MVM tile's effective
# operator (wire model + loading) per solve.  On AMC hardware those costs are
# paid exactly once, when the arrays are programmed; each subsequent solve is
# nearly free (paper Section III; Sun et al. 2020).
#
# `finalize` mirrors that split in the simulator.  Once per programmed
# matrix it precomputes
#   * per-INV-bucket effective operator stacks (wire model + finite-gain
#     loading folded in) together with their batched LU factors, and
#   * per-MVM-level effective tile stacks in (L, rows, cols) layout, grouped
#     by tile shape, with static input-gather windows and precomputed
#     summing-node divisors,
# so every runtime level of `execute_finalized` is a pure batched `lu_solve`
# or a stacked MVM over precomputed operators (XLA's dot merger fuses each
# level's same-shape tile dots under jit) - zero per-call re-derivation.
# The numbers are the ones `execute_flat` computes (same factors, same
# per-tile operators, same accumulation order), so the two agree bit-for-bit
# on CPU when run in the same regime; `execute_flat` stays as the
# unfinalized reference.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class _MvmLevel:
    """One finalized tiled-MVM schedule level.

    `stacks[g]` holds the effective operator matrices of all same-shape tiles
    of this level as one (L, rows, cols) tensor; `windows[g]` the static
    input column windows, tile l reading v[lo:hi].  `rows` lists, per output
    tile-row, the (group, index) tile refs in original column order - the
    runtime accumulates partial products in exactly `amc_mvm_tiled`'s order,
    which keeps the finalized path bit-compatible with the flat one.  `divs`
    are the per-tile-row finite-gain summing-node divisors (empty tuple for
    an ideal OPA).
    """

    def __init__(self, stacks, divs, windows, rows):
        self.stacks = stacks      # tuple of (L, r, c) arrays, one per shape
        self.divs = divs          # () or one divisor vector per tile-row
        self.windows = windows    # tuple (per group) of ((lo, hi), ...)
        self.rows = rows          # tuple (per tile-row) of ((group, idx), ..)

    def tree_flatten(self):
        return (self.stacks, self.divs), (self.windows, self.rows)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def apply(self, v: jnp.ndarray) -> jnp.ndarray:
        """Stacked MVM level: v (cols,) or (cols, k) -> (rows,) / (rows, k).

        Each tile's partial product reads its precomputed operator out of the
        (L, r, c) stack; the reduction replays `amc_mvm_tiled`'s per-row
        accumulation order exactly (the bit-compatibility contract), and XLA's
        dot merger fuses the same-shape tile dots of one level into a single
        batched matmul under jit - a batched einsum here would reorder the
        matvec reduction and break bitwise parity with the flat executor.
        """
        divs = self.divs if self.divs else (None,) * len(self.rows)
        outs = []
        for refs, div in zip(self.rows, divs):
            acc = None
            for g, i in refs:
                lo, hi = self.windows[g][i]
                p = -(self.stacks[g][i] @ v[lo:hi])
                acc = p if acc is None else acc + p
            if div is not None:
                acc = acc / (div[:, None] if acc.ndim == 2 else div)
            outs.append(acc)
        return jnp.concatenate(outs)


@jax.tree_util.register_pytree_node_class
class FinalizedPlan:
    """A FlatPlan finalized against one AnalogConfig: ready-to-solve form.

    Holds the precomputed per-bucket LU factors (`lu_stacks`), the fused
    per-level MVM operators (`mvm_levels`), and the rewritten schedule in
    which every "mvm" level references a finalized _MvmLevel.  The config is
    baked in (aux data): the precomputed operators are only valid for the
    cfg they were derived under.
    """

    def __init__(self, lu_stacks, mvm_levels, scale, schedule, n, cfg,
                 num_arrays):
        self.lu_stacks = lu_stacks    # tuple of (lu, piv) per INV bucket
        self.mvm_levels = mvm_levels  # tuple of _MvmLevel
        self.scale = scale
        self.schedule = schedule      # "mvm" ops rewritten to ("fmvm", ...)
        self.n = n
        self.cfg = cfg
        self.num_arrays = num_arrays

    def tree_flatten(self):
        return ((self.lu_stacks, self.mvm_levels, self.scale),
                (self.schedule, self.n, self.cfg, self.num_arrays))

    @classmethod
    def tree_unflatten(cls, aux, children):
        lu_stacks, mvm_levels, scale = children
        return cls(lu_stacks, mvm_levels, scale, *aux)

    @property
    def num_levels(self) -> int:
        return len(self.schedule)


def _finalize_mvm_level(fplan: FlatPlan, rows, cfg: AnalogConfig,
                        r_wire=None, drift_t=None) -> _MvmLevel:
    """Precompute one "mvm" level's effective operators and divisors.

    Derivations match `execute_flat`'s runtime path exactly: per-tile
    `CrossbarPair.a_eff` (wire model folded in) and `amc_mvm_tiled`'s
    sequential summing-node load accumulation, evaluated once here.
    `r_wire` optionally overrides the config wire resistance with a traced
    scalar (see `finalize`); `drift_t`, when given, is one age per MVM
    bucket (a scalar or a (num,) vector indexed by the tile's bucket slot)
    feeding the per-tile readout drift.
    """
    groups: dict = {}        # (r, c) tile shape -> group index
    stacks: list = []        # per group: list of a_eff tiles
    windows: list = []       # per group: list of (lo, hi) windows
    row_refs = []
    divs = []
    for row in rows:
        col_off = 0
        refs = []
        load = cfg.g0
        for bk, i in row:
            pair = fplan.mvm_stacks[bk].pair(i)
            r, c = pair.shape
            if (r, c) not in groups:
                groups[(r, c)] = len(stacks)
                stacks.append([])
                windows.append([])
            g = groups[(r, c)]
            refs.append((g, len(stacks[g])))
            dt = None
            if drift_t is not None:
                d_b = drift_t[bk]
                dt = d_b if jnp.ndim(d_b) == 0 else d_b[i]
            stacks[g].append(pair.a_eff(cfg, r_wire=r_wire, drift_t=dt))
            windows[g].append((col_off, col_off + c))
            load = load + jnp.sum(pair.gpos + pair.gneg, axis=1)
            col_off += c
        row_refs.append(tuple(refs))
        if cfg.opa_gain is not None:
            divs.append(1.0 + load / (cfg.opa_gain * cfg.g0))
    return _MvmLevel(tuple(jnp.stack(s) for s in stacks), tuple(divs),
                     tuple(tuple(w) for w in windows), tuple(row_refs))


@dataclasses.dataclass(frozen=True)
class PlanAges:
    """Per-physical-array device ages of one FlatPlan (simulated clock).

    `inv[b]` / `mvm[b]` is bucket b's age: a scalar, or a (num,) vector
    giving each array of the bucket its own age (arrays repaired at
    different times drift by different amounts).  Ages are in the drift
    model's t0 = 1 s units; `finalize(..., drift_t=PlanAges(...))` routes
    them into every `a_eff` readout.  Like the `r_wire` override, ages are
    array *contents* - they never enter `plan_signature`.
    """
    inv: tuple
    mvm: tuple


jax.tree_util.register_dataclass(
    PlanAges, data_fields=["inv", "mvm"], meta_fields=[])


def uniform_ages(fplan: FlatPlan, t) -> PlanAges:
    """PlanAges giving every array of `fplan` the same age `t`."""
    return PlanAges(
        inv=tuple(jnp.full((g.shape[-3],), t, jnp.float32)
                  for g in fplan.inv_stacks),
        mvm=tuple(jnp.full((g.shape[-3],), t, jnp.float32)
                  for g in fplan.mvm_stacks))


def _split_ages(fplan: FlatPlan, drift_t):
    """Normalise a finalize `drift_t` argument to per-bucket age tuples."""
    if drift_t is None:
        return None, None
    if isinstance(drift_t, PlanAges):
        return drift_t.inv, drift_t.mvm
    return (tuple(drift_t for _ in fplan.inv_stacks),
            tuple(drift_t for _ in fplan.mvm_stacks))


def finalize(fplan: FlatPlan, cfg: AnalogConfig,
             r_wire=None, drift_t=None) -> FinalizedPlan:
    """Precompute all per-solve-invariant operators of a flat plan.

    Traceable (pure jnp), so it can run under jit; typically called once per
    programmed matrix via `ProgrammedSolver.program`.

    `r_wire` optionally overrides `cfg.nonideal.r_wire` with a *traced*
    scalar, routed through the differentiable first-order wire model (the
    static config keeps selecting everything else).  This is the
    calibration hook (`repro.calib`): `finalize(fplan, cfg, r_wire=r_hat)`
    -> `compile_arena` -> `execute_arena` is differentiable end-to-end in
    `r_hat`, so planted wire parameters can be recovered by gradient
    descent against the `repro.physics.nodal` oracle.  The override never
    enters `plan_signature` - it changes array contents only, never shapes
    or schedules.

    `drift_t` optionally overrides the static config device age the same
    way: None keeps `cfg.nonideal.drift_t`; a traced scalar ages the whole
    plan uniformly; a `PlanAges` ages every physical array independently
    (the simulated-device-clock serving path, where one programmed plan is
    re-finalized as it grows old and block repairs reset individual
    arrays' ages).  The stored conductances never change - drift is a
    readout effect - so re-finalizing the same FlatPlan at new ages is the
    exact aging model.
    """
    inv_ages, mvm_ages = _split_ages(fplan, drift_t)
    lu_stacks = tuple(
        jax.scipy.linalg.lu_factor(_inv_operators(
            g, cfg, r_wire=r_wire,
            drift_t=None if inv_ages is None else inv_ages[b]))
        for b, g in enumerate(fplan.inv_stacks))
    mvm_levels = []
    schedule = []
    for instr in fplan.schedule:
        if instr[0] == "mvm":
            _, rows, src = instr
            schedule.append(("fmvm", len(mvm_levels), src))
            mvm_levels.append(
                _finalize_mvm_level(fplan, rows, cfg, r_wire=r_wire,
                                    drift_t=mvm_ages))
        else:
            schedule.append(instr)
    return FinalizedPlan(lu_stacks, tuple(mvm_levels), fplan.scale,
                         tuple(schedule), fplan.n, cfg, fplan.num_arrays)


def execute_finalized(fin: FinalizedPlan, b: jnp.ndarray) -> jnp.ndarray:
    """Run a finalized schedule; returns x like `execute` / `execute_flat`.

    `b` may be (n,) or (n, k).  Every level is a batched `lu_solve` against
    precomputed factors or one fused stacked MVM - nothing is re-derived.
    """
    cfg = fin.cfg
    regs = [analog.dac(b, cfg)]
    for instr in fin.schedule:
        op = instr[0]
        if op == "slice":
            _, src, lo, hi = instr
            regs.append(regs[src][lo:hi])
        elif op == "inv":
            _, bucket, idx, src = instr
            lu, piv = fin.lu_stacks[bucket]
            regs.append(-jax.scipy.linalg.lu_solve((lu[idx], piv[idx]),
                                                   regs[src]))
        elif op == "fmvm":
            _, level, src = instr
            regs.append(fin.mvm_levels[level].apply(regs[src]))
        elif op == "add":
            _, s1, r1, s2, r2 = instr
            x1 = regs[r1] if s1 > 0 else -regs[r1]
            x2 = regs[r2] if s2 > 0 else -regs[r2]
            regs.append(x1 + x2)
        elif op == "catneg":
            _, r1, r2 = instr
            regs.append(jnp.concatenate([regs[r1], -regs[r2]]))
        else:  # pragma: no cover - finalize only emits the ops above
            raise ValueError(f"unknown schedule op {op!r}")
    return -fin.scale * analog.adc(regs[-1], cfg)


_execute_finalized = jax.jit(execute_finalized)
_execute_finalized_donated = jax.jit(execute_finalized, donate_argnums=(1,))


# ---------------------------------------------------------------------------
# Arena executor: single-dispatch fused serving form
#
# See the module docstring's DESIGN note for the layout and the
# accumulation-order contract.  Static metadata vocabulary (hashable aux
# data; every number is a Python int).  Operand windows carry *both*
# coordinate systems: the materialized register they read (slot-SSA form,
# used by the jnp executor so XLA never copies the whole arena per level)
# and the resolved arena offset (`slot_offsets[m] + local`, used by the
# Pallas megakernel, the uniform whole-schedule program and the allocator
# property tests):
#
#   term     (mreg, local_off, sign)       one signed window read
#   segment  (dst_lo, seg_len, terms)      one contiguous chunk of an operand
#   tile     (stack_id, idx, m_out, init,  one operator application, in
#             segs)                        schedule order; init=True starts
#                                          its output register / row,
#                                          False accumulates into it
#   level    tuple of tiles                one schedule compute level
# ---------------------------------------------------------------------------


# --- compile-time views: registers as signed windows over materialized regs.
# A view is a tuple of chunks (chunk_len, terms), terms = ((mreg, off, sign),
# ...): position i of the chunk reads sum_t sign_t * mreg_t[off_t + i].

def _view_slice(view, lo, hi):
    out, pos = [], 0
    for chunk_len, terms in view:
        s_lo, s_hi = max(lo, pos), min(hi, pos + chunk_len)
        if s_lo < s_hi:
            d = s_lo - pos
            out.append((s_hi - s_lo,
                        tuple((m, o + d, s) for m, o, s in terms)))
        pos += chunk_len
    return tuple(out)


def _view_scale(view, sign):
    if sign > 0:
        return view
    return tuple((n_, tuple((m, o, -s) for m, o, s in terms))
                 for n_, terms in view)


def _view_add(v1, v2):
    """Refine two equal-length views to common chunk boundaries; the term
    order (all of v1's chunk terms, then v2's) replays `x1 + x2`."""
    out = []
    v1, v2 = list(v1), list(v2)
    i = j = 0
    while i < len(v1):
        l1, t1 = v1[i]
        l2, t2 = v2[j]
        step = min(l1, l2)
        out.append((step, t1 + t2))
        if l1 > step:
            v1[i] = (l1 - step, tuple((m, o + step, s) for m, o, s in t1))
        else:
            i += 1
        if l2 > step:
            v2[j] = (l2 - step, tuple((m, o + step, s) for m, o, s in t2))
        else:
            j += 1
    return tuple(out)


def _view_len(view):
    return sum(chunk_len for chunk_len, _ in view)


@jax.tree_util.register_pytree_node_class
class ArenaPlan:
    """Arena-form of a FinalizedPlan: the single-dispatch serving executor.

    `stacks` holds every operator the schedule applies, uniformly as
    (num, rows, cols) tensors: first one stack per INV bucket (explicit
    negated inverses, finite-gain loading folded in before inversion), then
    one per (MVM level, tile shape) group (circuit sign and summing-node
    divisor folded into the rows).  `levels` / `out_spec` / `slot_offsets`
    / `slot_ranges` are static metadata (see the vocabulary note above):
    `slot_offsets[m]` is materialized register m's arena offset and
    `slot_ranges` its (offset, length, def_pos, last_use) live range (the
    allocator property tests read these).  `program`, present when every
    tile shares one shape with whole-window gathers (the power-of-two
    serving configs), is the whole schedule flattened to arena-resolved
    metadata arrays - the form the Pallas megakernel executes in ONE call.
    """

    def __init__(self, stacks, scale, program, levels, out_spec, arena_size,
                 n, in_off, cfg, kernel_ok, num_arrays, slot_offsets,
                 slot_ranges, peak_liveness):
        self.stacks = stacks
        self.scale = scale
        self.program = program    # uniform whole-schedule form, or None
        self.levels = levels
        self.out_spec = out_spec
        self.arena_size = arena_size
        self.n = n
        self.in_off = in_off
        self.cfg = cfg
        self.kernel_ok = kernel_ok
        self.num_arrays = num_arrays
        self.slot_offsets = slot_offsets
        self.slot_ranges = slot_ranges
        self.peak_liveness = peak_liveness

    def tree_flatten(self):
        return ((self.stacks, self.scale, self.program),
                (self.levels, self.out_spec, self.arena_size, self.n,
                 self.in_off, self.cfg, self.kernel_ok, self.num_arrays,
                 self.slot_offsets, self.slot_ranges, self.peak_liveness))

    @classmethod
    def tree_unflatten(cls, aux, children):
        stacks, scale, program = children
        return cls(stacks, scale, program, *aux)

    @property
    def num_levels(self) -> int:
        return len(self.levels)


def _lowest_fit(placed, length):
    """Lowest offset where `length` cells avoid every (off, len) in placed."""
    off = 0
    for lo, ln in sorted(placed):
        if off + length <= lo:
            break
        off = max(off, lo + ln)
    return off


def _allocate_slots(intervals):
    """Offline register-arena allocation over known live intervals.

    `intervals`: {mreg: (length, def_pos, last_use)}.  Two greedy layouts
    are computed - first-fit in definition order (good for the cascade's
    mostly-nested lifetimes) and greedy-by-size (the ML-compiler heap-
    simulator heuristic, better on ragged odd-split schedules) - and the
    smaller extent wins.  On aligned power-of-two schedules (the serving
    hot path) the extent equals the schedule's peak liveness exactly; odd
    splits can fragment by at most a small slack (optimal dynamic storage
    allocation can itself exceed peak liveness, so a slack-free bound is
    not attainable in general) - both pinned by test_plan_properties.py.
    """
    def extent(offsets):
        return max(o + intervals[m][0] for m, o in offsets.items())

    def overlaps(m1, m2):
        _, d1, u1 = intervals[m1]
        _, d2, u2 = intervals[m2]
        return not (u1 < d2 or u2 < d1)

    layouts = []
    for order in (
            sorted(intervals, key=lambda m: (intervals[m][1], m)),
            sorted(intervals, key=lambda m: (-intervals[m][0],
                                             intervals[m][1], m))):
        offsets = {}
        for m in order:
            placed = [(offsets[m2], intervals[m2][0])
                      for m2 in offsets if overlaps(m, m2)]
            offsets[m] = _lowest_fit(placed, intervals[m][0])
        layouts.append(offsets)
    return min(layouts, key=extent)


def compile_arena(fin: FinalizedPlan) -> ArenaPlan:
    """Lower a FinalizedPlan to its arena form (see DESIGN note).

    Static analysis (views, live ranges, offsets) runs once per schedule
    shape; the numeric work (batched explicit inversion, divisor folding)
    is pure jnp, so `compile_arena` traces under jit/vmap like `finalize`.
    """
    schedule = fin.schedule
    n_steps = len(schedule)

    # --- pass 1: views, materialized registers, compute levels ------------
    views = {0: ((fin.n, ((0, 0, 1),)),)}   # register -> view
    mreg_len = {0: fin.n}                   # materialized reg -> length
    mreg_def = {0: -1}                      # -> defining schedule position
    computes = []                           # (pos, kind, payload, def_mreg)
    next_mreg = 1
    for p, instr in enumerate(schedule):
        r, op = p + 1, instr[0]
        if op == "slice":
            _, src, lo, hi = instr
            views[r] = _view_slice(views[src], lo, hi)
        elif op == "add":
            _, s1, r1, s2, r2 = instr
            views[r] = _view_add(_view_scale(views[r1], s1),
                                 _view_scale(views[r2], s2))
        elif op == "catneg":
            _, r1, r2 = instr
            views[r] = views[r1] + _view_scale(views[r2], -1)
        elif op == "inv":
            _, bucket, idx, src = instr
            m, next_mreg = next_mreg, next_mreg + 1
            size = fin.lu_stacks[bucket][0].shape[-1]
            mreg_len[m], mreg_def[m] = size, p
            views[r] = ((size, ((m, 0, 1),)),)
            computes.append((p, "inv", (bucket, idx, src), m))
        elif op == "fmvm":
            _, li, src = instr
            lvl = fin.mvm_levels[li]
            m, next_mreg = next_mreg, next_mreg + 1
            out_len = sum(lvl.stacks[refs[0][0]].shape[-2]
                          for refs in lvl.rows)
            mreg_len[m], mreg_def[m] = out_len, p
            views[r] = ((out_len, ((m, 0, 1),)),)
            computes.append((p, "fmvm", (li, src), m))
        else:  # pragma: no cover - finalize only emits the ops above
            raise ValueError(f"unknown schedule op {op!r}")

    # --- pass 2: per-compute input views (in mreg coordinates), last uses -
    def note_uses(view, p, last_use):
        for _, terms in view:
            for m, _, _ in terms:
                last_use[m] = max(last_use.get(m, mreg_def[m]), p)

    last_use = {0: 0}
    in_views = []       # per compute: view ("inv") or per-tile views ("fmvm")
    for p, kind, payload, _ in computes:
        if kind == "inv":
            view = views[payload[2]]
            note_uses(view, p, last_use)
            in_views.append(view)
        else:
            li, src = payload
            lvl = fin.mvm_levels[li]
            tile_views = []
            for refs in lvl.rows:
                for g, i in refs:
                    lo, hi = lvl.windows[g][i]
                    tv = _view_slice(views[src], lo, hi)
                    note_uses(tv, p, last_use)
                    tile_views.append(tv)
            in_views.append(tuple(tile_views))
    out_view = views[n_steps]
    note_uses(out_view, n_steps, last_use)
    for m in mreg_def:                       # unread defs die immediately
        last_use.setdefault(m, mreg_def[m])

    # --- pass 3: offline allocation over the known live intervals ---------
    intervals = {m: (mreg_len[m], mreg_def[m], last_use[m])
                 for m in mreg_def}
    offsets = _allocate_slots(intervals)
    arena_size = max(offsets[m] + mreg_len[m] for m in mreg_def)
    peak = max(
        sum(mreg_len[m] for m in mreg_def
            if mreg_def[m] <= p <= last_use[m])
        for p in range(-1, n_steps + 1))

    def segs(view):
        """A view as static segments in (mreg, local_off, sign) terms."""
        out, dst = [], 0
        for chunk_len, terms in view:
            out.append((dst, chunk_len, tuple(terms)))
            dst += chunk_len
        return tuple(out)

    # --- pass 4: operator stacks (explicit inverses; sign/divisor folded) -
    stacks = []
    for lu, piv in fin.lu_stacks:
        eye = jnp.eye(lu.shape[-1], dtype=lu.dtype)
        stacks.append(-jax.vmap(
            lambda l_, p_: jax.scipy.linalg.lu_solve((l_, p_), eye))(lu, piv))
    mvm_stack_id = {}
    for li, lvl in enumerate(fin.mvm_levels):
        divs = lvl.divs if lvl.divs else (None,) * len(lvl.rows)
        folded = [[None] * s.shape[-3] for s in lvl.stacks]
        for refs, div in zip(lvl.rows, divs):
            for g, i in refs:
                w = -lvl.stacks[g][i]
                if div is not None:
                    w = w / div[:, None]
                folded[g][i] = w
        for g, tiles in enumerate(folded):
            mvm_stack_id[(li, g)] = len(stacks)
            stacks.append(jnp.stack(tiles))

    # --- pass 5: levels (schedule order; slot-SSA + arena coordinates) ----
    levels = []
    for (p, kind, payload, m_out), in_view in zip(computes, in_views):
        if kind == "inv":
            bucket, idx, _ = payload
            levels.append(((bucket, idx, m_out, 0, True, segs(in_view)),))
        else:
            li, _ = payload
            lvl = fin.mvm_levels[li]
            tiles, row_off, tv = [], 0, iter(in_view)
            for refs in lvl.rows:
                for pos, (g, i) in enumerate(refs):
                    tiles.append((mvm_stack_id[(li, g)], i, m_out, row_off,
                                  pos == 0, segs(next(tv))))
                row_off += lvl.stacks[refs[0][0]].shape[-2]
            levels.append(tuple(tiles))

    def whole_window(tile):
        sg = tile[5]
        return len(sg) == 1 and sg[0][0] == 0 \
            and sg[0][1] == stacks[tile[0]].shape[-1]

    kernel_ok = all(whole_window(t) for level in levels for t in level)

    # --- pass 6: uniform whole-schedule program ---------------------------
    # When every tile of the cascade shares one (r, c) shape and reads
    # whole-window gathers (true for the power-of-two serving configs: a
    # two-stage 256^2 solve is 23 applications of 64x64 operators), the
    # entire schedule lowers to ONE tile program: stacked operators in
    # execution order plus flat arena-resolved metadata arrays - the form
    # the Pallas megakernel runs as a single call, grid walking the tiles
    # in schedule order over one physical arena buffer.  Mixed shapes /
    # ragged windows fall back to the per-level form (program=None).
    program = None
    if kernel_ok and len({s.shape[-2:] for s in stacks}) == 1:
        seq, offs_l, signs_l, outs_l, init_l = [], [], [], [], []
        n_terms = max(len(t[5][0][2]) for level in levels for t in level)
        for level in levels:
            for sid, idx, m_out, out_local, init, segments in level:
                terms = segments[0][2]
                seq.append(stacks[sid][idx])
                offs_l.append([offsets[m] + o for m, o, _ in terms]
                              + [0] * (n_terms - len(terms)))
                signs_l.append([float(s) for _, _, s in terms]
                               + [0.0] * (n_terms - len(terms)))
                outs_l.append(offsets[m_out] + out_local)
                init_l.append(1 if init else 0)
        program = (jnp.stack(seq), jnp.asarray(offs_l, jnp.int32),
                   jnp.asarray(signs_l, jnp.float32),
                   jnp.asarray(outs_l, jnp.int32),
                   jnp.asarray(init_l, jnp.int32))

    slot_offsets = tuple(offsets[m] for m in range(next_mreg))
    slot_ranges = tuple(                     # indexed by materialized reg
        (offsets[m], mreg_len[m], mreg_def[m], last_use[m])
        for m in range(next_mreg))
    return ArenaPlan(tuple(stacks), fin.scale, program, tuple(levels),
                     segs(out_view), arena_size, fin.n, offsets[0], fin.cfg,
                     kernel_ok, fin.num_arrays, slot_offsets, slot_ranges,
                     peak)


def _slot_gather(vals, segments):
    """Signed static-window gather: the folded slice/add/catneg wiring.

    Terms are evaluated in segment order, first term first - exactly the
    reference executors' negation/summation order.
    """
    parts = []
    for _, seg_len, terms in segments:
        acc = None
        for m, off, sign in terms:
            w = vals[m][off:off + seg_len]
            w = -w if sign < 0 else w
            acc = w if acc is None else acc + w
        parts.append(acc)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _arena_out_spec(out_spec, slot_offsets):
    """`out_spec` with register terms rebased to physical arena offsets
    (register 0 = the whole arena buffer) - the kernel-path output gather
    form, shared by the single-instance and packed executors."""
    return tuple(
        (dst, ln, tuple((0, slot_offsets[m] + off, sign)
                        for m, off, sign in terms))
        for dst, ln, terms in out_spec)


def _apply_level_jnp(vals, stacks, level):
    """One schedule level in slot-SSA form (the CPU fast path).

    Each materialized register is its own value keyed by `slot_offsets`
    slot id - same layout contract as the physical arena, but XLA assigns
    the buffers, so level outputs never pay a whole-arena update copy.
    Tile-row accumulation replays the schedule order (init starts a row
    part, later tiles add into it); the row parts concatenate into the
    level's output register.

    A multi-tile level whose tiles share one operator stack runs as ONE
    batched dot over the tile axis instead of one dot per tile: each
    tile's matvec reduction is unchanged (per-slice identical math; the
    accumulation below still replays schedule order), but XLA:CPU's
    batched-matmul throughput scales strongly with batch size, which is
    what makes the packed multi-tenant executor - where the instance axis
    multiplies the batch again - beat the per-tenant dispatch loop.
    """
    parts, m_out = [], level[0][2]
    if len(level) > 1 and len({t[0] for t in level}) == 1:
        sid, idxs = level[0][0], tuple(t[1] for t in level)
        gathers = jnp.stack([_slot_gather(vals, t[5]) for t in level])
        lo = idxs[0]
        ops_sel = (stacks[sid][lo:lo + len(idxs)]
                   if idxs == tuple(range(lo, lo + len(idxs)))
                   else stacks[sid][jnp.asarray(idxs)])
        outs = jnp.matmul(ops_sel, gathers, precision=_F32)  # (L, rows, k)
        tile_outs = [outs[pos] for pos in range(len(level))]
    else:
        tile_outs = [jnp.matmul(stacks[sid][idx],
                                _slot_gather(vals, segments), precision=_F32)
                     for sid, idx, _, _, _, segments in level]
    for out, (_, _, _, _, init, _) in zip(tile_outs, level):
        if init:
            parts.append(out)
        else:
            parts[-1] = parts[-1] + out
    vals[m_out] = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                   axis=0)


# ---------------------------------------------------------------------------
# Differentiable cascade core (implicit-diff VJP)
#
# The whole jnp-path cascade - input register to output gather - is one
# `jax.custom_vjp` over (stacks, b_in) with the static metadata (levels,
# out_spec) as nondiff arguments.  The primal replays `_apply_level_jnp` /
# `_slot_gather` op for op, so wrapping it changes no forward bit; the
# backward pass is a reverse sweep over the SAME programmed operator stacks
# (each tile's adjoint is one transposed tile matmul), i.e. one more solve
# against the resident plan - no re-factorisation, no re-programming, no
# `lax.while_loop`.  Cotangents are produced for both the right-hand side
# (the IFT adjoint solve) and the operator stacks (per-tile outer products,
# the hook calibration loops differentiate through); when only the rhs
# gradient is consumed, XLA dead-code-eliminates the stack outer products
# under jit, so a backward costs ~1 forward arena solve (benchmarked in
# artifacts/bench/grad.json).  Contract details: TESTING.md "differentiable
# solver contract".
# ---------------------------------------------------------------------------


def _run_levels(levels, stacks, b_in):
    vals = {0: b_in}
    for level in levels:
        _apply_level_jnp(vals, stacks, level)
    return vals


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _cascade(levels, out_spec, stacks, b_in):
    """The jnp cascade as a differentiable primitive: registers in slot-SSA
    form, levels applied in schedule order, output gathered via `out_spec`.
    `levels`/`out_spec` are the hashable static metadata of an ArenaPlan."""
    return _slot_gather(_run_levels(levels, stacks, b_in), out_spec)


def _cascade_fwd(levels, out_spec, stacks, b_in):
    vals = _run_levels(levels, stacks, b_in)
    out = _slot_gather(vals, out_spec)
    # level i defines mreg i+1 (SSA), so vals is keyed 0..num_levels densely
    return out, (stacks, tuple(vals[m] for m in range(len(vals))))


def _scatter_ct(cot, vals, segments, u):
    """Adjoint of `_slot_gather`: scatter-add the cotangent `u` back through
    the signed static windows (sign per term, mirroring the gather)."""
    for dst, seg_len, terms in segments:
        piece = u[dst:dst + seg_len]
        for m, off, sign in terms:
            w = -piece if sign < 0 else piece
            prev = cot.get(m)
            if prev is None:
                prev = jnp.zeros_like(vals[m])
            cot[m] = prev.at[off:off + seg_len].add(w.astype(prev.dtype))


def _cascade_bwd(levels, out_spec, res, g):
    stacks, vals_t = res
    vals = dict(enumerate(vals_t))
    stack_bars = [jnp.zeros_like(s) for s in stacks]
    cot = {}                                 # mreg -> cotangent register
    _scatter_ct(cot, vals, out_spec, g)
    for level in reversed(levels):
        c = cot.pop(level[0][2], None)       # this level's output cotangent
        if c is None:
            continue                         # unread def: no contribution
        if len(level) > 1 and len({t[0] for t in level}) == 1:
            # mirror the forward shared-stack batched dot: one transposed
            # batched matmul for the input adjoints, one batched outer
            # product for the stack cotangents
            sid, idxs = level[0][0], tuple(t[1] for t in level)
            rows = stacks[sid].shape[-2]
            cps = jnp.stack([c[t[3]:t[3] + rows] for t in level])
            gathers = jnp.stack([_slot_gather(vals, t[5]) for t in level])
            lo = idxs[0]
            contiguous = idxs == tuple(range(lo, lo + len(idxs)))
            ops_sel = (stacks[sid][lo:lo + len(idxs)] if contiguous
                       else stacks[sid][jnp.asarray(idxs)])
            ubars = jnp.matmul(jnp.swapaxes(ops_sel, -1, -2), cps,
                               precision=_F32)               # (L, cols, k)
            wbars = jnp.matmul(cps, jnp.swapaxes(gathers, -1, -2),
                               precision=_F32
                               ).astype(stacks[sid].dtype)   # (L, rows, cols)
            stack_bars[sid] = (
                stack_bars[sid].at[lo:lo + len(idxs)].add(wbars) if contiguous
                else stack_bars[sid].at[jnp.asarray(idxs)].add(wbars))
            for pos, t in enumerate(level):
                _scatter_ct(cot, vals, t[5], ubars[pos])
        else:
            for sid, idx, _, out_local, _, segments in level:
                rows = stacks[sid].shape[-2]
                cp = c[out_local:out_local + rows]
                gat = _slot_gather(vals, segments)
                stack_bars[sid] = stack_bars[sid].at[idx].add(
                    jnp.matmul(cp, gat.T, precision=_F32
                               ).astype(stacks[sid].dtype))
                _scatter_ct(cot, vals, segments,
                            jnp.matmul(stacks[sid][idx].T, cp,
                                       precision=_F32))
    b_bar = cot.get(0)
    if b_bar is None:
        b_bar = jnp.zeros_like(vals[0])
    return tuple(stack_bars), b_bar


_cascade.defvjp(_cascade_fwd, _cascade_bwd)


def _apply_level_kernel(arena, ap, level, interpret):
    """One schedule level on the physical arena via the Pallas megakernel.

    Tiles are grouped by operator stack (shape bucket), one pallas_call
    per group; metadata resolves to arena coordinates via `slot_offsets`.
    """
    from repro.kernels import ops as kops
    so = ap.slot_offsets
    groups = {}
    for tile in level:
        groups.setdefault(tile[0], []).append(tile)
    for sid, tiles in groups.items():
        n_terms = max(len(t[5][0][2]) for t in tiles)
        offs = [[so[m] + o for m, o, _ in t[5][0][2]] for t in tiles]
        signs = [[float(s) for _, _, s in t[5][0][2]] for t in tiles]
        for o, s in zip(offs, signs):       # pad ragged term counts
            o.extend([0] * (n_terms - len(o)))
            s.extend([0.0] * (n_terms - len(s)))
        stack = ap.stacks[sid]
        ops_used = stack[jnp.asarray([t[1] for t in tiles], jnp.int32)]
        arena = kops.arena_level_apply(
            arena, ops_used,
            jnp.asarray(offs, jnp.int32), jnp.asarray(signs, jnp.float32),
            jnp.asarray([so[t[2]] + t[3] for t in tiles], jnp.int32),
            jnp.asarray([1 if t[4] else 0 for t in tiles], jnp.int32),
            interpret=interpret)
    return arena


def execute_arena(ap: ArenaPlan, b: jnp.ndarray,
                  use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """Run an arena plan; returns x like the other executors.

    `b` may be (n,) or (n, k).  Every level is a stacked-tile matmul over
    signed static gather windows - no register list, no runtime factor
    solves, no wiring copies.  use_kernel=None routes through the Pallas
    megakernel on TPU (when the plan's gather specs are whole-window,
    `ap.kernel_ok`) and the slot-SSA jnp path on CPU; use_kernel=True
    forces the kernel (interpret mode off TPU - the CI smoke), False
    forces jnp.  On the kernel path a uniform plan (`ap.program`) runs
    the ENTIRE cascade as one megakernel call over the physical arena
    buffer - the single-dispatch serving form.
    """
    cfg = ap.cfg
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu and ap.kernel_ok
    elif use_kernel and not ap.kernel_ok:
        # forcing the kernel on a plan it cannot express must fail loudly:
        # silently measuring/testing the jnp path as "the kernel" is worse
        raise ValueError(
            "use_kernel=True but this plan has ragged (multi-segment) "
            "gather windows the megakernel does not express; use the jnp "
            "path or an aligned power-of-two configuration")
    single = b.ndim == 1
    dtype = jnp.result_type(b.dtype, ap.scale.dtype)
    # Always carry an explicit RHS-batch dim: a trailing batch of 1 costs
    # nothing, while 1-D update chains defeat XLA:CPU buffer reuse.
    bk = b[:, None] if single else b
    b_in = analog.dac(bk, cfg).astype(dtype)
    if use_kernel:
        arena = jnp.zeros((ap.arena_size,) + bk.shape[1:], dtype)
        arena = arena.at[ap.in_off:ap.in_off + ap.n].set(b_in)
        if ap.program is not None:
            # the whole cascade in ONE megakernel call (the grid walks
            # tiles in schedule order; the arena carries level outputs)
            from repro.kernels import ops as kops
            ops_seq, in_offs, in_signs, out_offs, out_init = ap.program
            arena = kops.arena_level_apply(
                arena, ops_seq, in_offs, in_signs, out_offs, out_init,
                interpret=not on_tpu)
        else:
            for level in ap.levels:
                arena = _apply_level_kernel(arena, ap, level,
                                            interpret=not on_tpu)
        out = _slot_gather({0: arena},
                           _arena_out_spec(ap.out_spec, ap.slot_offsets))
    else:
        # the differentiable cascade core: identical ops to the plain level
        # loop (bit-compatible), plus the implicit-diff VJP for jax.grad
        out = _cascade(ap.levels, ap.out_spec, ap.stacks, b_in)
    if single:
        out = out[:, 0]
    return -ap.scale * analog.adc(out, cfg)


_execute_arena = jax.jit(execute_arena, static_argnames=("use_kernel",))
_execute_arena_donated = jax.jit(execute_arena, donate_argnums=(1,),
                                 static_argnames=("use_kernel",))


def pad_rhs_pow2(bs: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """Zero-pad the trailing rhs-batch axis to the next power-of-two k.

    The one padding policy of the serving layer (ProgrammedSolver.solve_many,
    SolverService's refined flush and the packed `flush_all` all route
    through it): jitted executors then compile at most one new batch shape
    per doubling instead of one per distinct queue length.  Accepts the
    single-matrix (n, k) layout or the packed (M, n, k) layout - the rhs
    axis is always the last.  Returns (padded batch, original k); slice the
    result back with `[..., :k]`.
    """
    k = bs.shape[-1]
    k_pad = 1 << (k - 1).bit_length() if k else 0
    if k_pad > k:
        bs = jnp.pad(bs, [(0, 0)] * (bs.ndim - 1) + [(0, k_pad - k)])
    return bs, k


# ---------------------------------------------------------------------------
# Block-level repair (drift-aware self-healing)
#
# The paper's accuracy argument is that partitioning confines non-idealities
# to small arrays; the maintenance flip side is that *repair* can be equally
# local.  `plan_block_map` statically enumerates every physical array of a
# plan - (kind, bucket, index) exactly as `compile_plan` interns them -
# together with the PRNG key-derivation path `_program`/`map_tiled` would
# use for that array.  `repair_blocks` then re-programs ONLY the named
# arrays (full conductance-mapping pipeline, write-verify included) under
# keys derived from a fresh root key and splices the slices into the
# FlatPlan stacks; `splice_finalized` / `splice_arena` propagate the change
# through the finalized LU factors, MVM operator stacks, summing-node
# divisors and arena inverse/folded stacks by recomputing exactly the
# affected slices with the same expressions `finalize`/`compile_arena`
# evaluate.  Repairing every block under root key k is therefore
# bit-identical (eager CPU) to fully re-programming under k, and repairing
# a subset touches nothing outside the subset's buckets/rows - repair cost
# scales with the degraded fraction, not n^2 (tests/test_block_repair.py).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockRecord:
    """One physical array of a plan: its stack slot and programming key path.

    `kind`/`bucket`/`index` address the array inside
    FlatPlan.inv_stacks/mvm_stacks (same intern order as `compile_plan`);
    `path` is the static PRNG derivation from the root programming key -
    a sequence of ("split", num, idx) / ("tile", num, idx) steps mirroring
    `_program`'s 4-way key split and `map_tiled`'s per-tile split.
    """
    kind: str          # "inv" | "mvm"
    bucket: int
    index: int
    depth: int
    shape: Tuple[int, int]
    path: tuple

    @property
    def ref(self) -> Tuple[str, int, int]:
        return (self.kind, self.bucket, self.index)


def plan_block_map(n: int, stages: Optional[int],
                   cfg: AnalogConfig) -> Tuple[BlockRecord, ...]:
    """Statically enumerate every physical array of a (n, stages, cfg) plan.

    Walks the `_split_tree` in `compile_plan`'s emission order (inv1
    subtree, mvm3 tiles row-major, inv4s subtree, mvm2 tiles row-major), so
    bucket numbering and per-bucket indices match the FlatPlan intern order
    exactly; key paths match `_program`'s split(key, 4) -> (inv1, a2, a3,
    inv4s) and `map_tiled`'s split(key, r_tiles*c_tiles) discipline.  A
    pure function of the plan signature - no arrays needed.
    """
    if stages is None:
        stages = required_stages(n, cfg.array_size)
    s = cfg.array_size
    inv_buckets: dict = {}
    mvm_buckets: dict = {}
    records = []

    def ref(buckets, key):
        if key not in buckets:
            buckets[key] = [len(buckets), 0]
        b = buckets[key]
        out = (b[0], b[1])
        b[1] += 1
        return out

    def tiles(shape, depth, path):
        rows, cols = shape
        r_t, c_t = -(-rows // s), -(-cols // s)
        for ri in range(r_t):
            for ci in range(c_t):
                tshape = (min((ri + 1) * s, rows) - ri * s,
                          min((ci + 1) * s, cols) - ci * s)
                b, i = ref(mvm_buckets, (depth, tshape))
                records.append(BlockRecord(
                    "mvm", b, i, depth, tshape,
                    path + (("tile", r_t * c_t, ri * c_t + ci),)))

    def walk(tree, depth, path):
        if isinstance(tree, int):
            b, i = ref(inv_buckets, (depth, (tree, tree)))
            records.append(BlockRecord(
                "inv", b, i, depth, (tree, tree), path))
            return
        left, right = tree
        m = _tree_size(left)
        nn = m + _tree_size(right)
        walk(left, depth + 1, path + (("split", 4, 0),))
        tiles((nn - m, m), depth, path + (("split", 4, 2),))   # mvm3 <- a3
        walk(right, depth + 1, path + (("split", 4, 3),))
        tiles((m, nn - m), depth, path + (("split", 4, 1),))   # mvm2 <- a2

    walk(_split_tree(n, stages), 0, ())
    return tuple(records)


def _path_key(root_key: jax.Array, path) -> jax.Array:
    """Derive one array's programming key from the plan's root key.

    Replays the exact split sequence of `_program` (split into 4; inv1,
    a2, a3, inv4s in that order) and `map_tiled` (split into
    r_tiles*c_tiles, row-major) so the derived key equals the one a full
    re-program under `root_key` would hand that array's `map_matrix`.
    """
    k = root_key
    for _, num, idx in path:
        k = jax.random.split(k, num)[idx]
    return k


def _target_block(root: Target, path, array_size: int) -> jnp.ndarray:
    """The digital target block a BlockRecord's array was programmed from."""
    t = root
    for kind, _, idx in path:
        if kind == "split":
            t = (t.inv1, t.a2, t.a3, t.inv4s)[idx]
        else:
            rows, cols = t.shape
            c_t = -(-cols // array_size)
            ri, ci = idx // c_t, idx % c_t
            t = t[ri * array_size:min((ri + 1) * array_size, rows),
                  ci * array_size:min((ci + 1) * array_size, cols)]
    return t.a if isinstance(t, LeafTarget) else t


def _split_changed(changed):
    """Group a changed-block set into per-bucket index lists."""
    inv: dict = {}
    mvm: dict = {}
    for kind, b, i in changed:
        (inv if kind == "inv" else mvm).setdefault(b, set()).add(i)
    return ({b: sorted(s) for b, s in inv.items()},
            {b: sorted(s) for b, s in mvm.items()})


def repair_blocks(fplan: FlatPlan, parts: PartitionedSystem,
                  cfg: AnalogConfig, blocks, key: jax.Array,
                  stages: Optional[int] = None):
    """Re-program only the named physical arrays of a programmed plan.

    `blocks` is an iterable of ("inv"|"mvm", bucket, index) refs into the
    FlatPlan stacks.  Each named array is re-derived from its digital
    target block and re-programmed through the FULL conductance pipeline
    (write-verify pre-distortion, variation, faults) under the key
    `_path_key(key, path)` - the key a whole-plan re-program under `key`
    would use for that array - then spliced into the stacks.  Returns
    (new FlatPlan, frozenset of changed refs); untouched slices are the
    original arrays, bit-for-bit.
    """
    recs = {r.ref: r for r in plan_block_map(fplan.n, stages, cfg)}
    if len(recs) != fplan.num_arrays:
        raise ValueError(
            f"block map has {len(recs)} arrays but the plan holds "
            f"{fplan.num_arrays}: wrong stages for this plan?")
    changed = frozenset((k, int(b), int(i)) for k, b, i in blocks)
    new_pairs: dict = {}
    for blk in changed:
        rec = recs.get(blk)
        if rec is None:
            raise KeyError(f"no such block in this plan: {blk}")
        a_blk = _target_block(parts.root, rec.path, cfg.array_size)
        new_pairs[blk] = analog.map_matrix(
            a_blk, _path_key(key, rec.path), cfg, parts.scale)
    changed_inv, changed_mvm = _split_changed(changed)

    def splice(stacks, per_bucket, kind):
        out = list(stacks)
        for b, idxs in per_bucket.items():
            g = out[b]
            gp, gn = g.gpos, g.gneg
            for i in idxs:
                pair = new_pairs[(kind, b, i)]
                gp = gp.at[i].set(pair.gpos)
                gn = gn.at[i].set(pair.gneg)
            out[b] = TileGrid(gp, gn, g.scale, g.g0)
        return tuple(out)

    out = FlatPlan(splice(fplan.inv_stacks, changed_inv, "inv"),
                   splice(fplan.mvm_stacks, changed_mvm, "mvm"),
                   fplan.scale, fplan.schedule, fplan.n,
                   fplan.inv_keys, fplan.mvm_keys)
    return out, changed


def _mvm_level_layout(fplan: FlatPlan):
    """Replay `_finalize_mvm_level`'s shape grouping statically.

    Per "mvm" schedule level, returns the row structure as tuples of
    (bucket, index, group, pos): the tile's FlatPlan slot plus its
    (stack-group, group-local position) inside the finalized level.  Pure
    metadata - the splice functions use it to locate a repaired tile's
    every occurrence (A1-subtree levels appear twice, steps 1 and 5).
    """
    layouts = []
    for instr in fplan.schedule:
        if instr[0] != "mvm":
            continue
        rows = instr[1]
        groups: dict = {}
        counts: list = []
        row_tiles = []
        for row in rows:
            rt = []
            for bk, i in row:
                shape = tuple(fplan.mvm_stacks[bk].shape[-2:])
                if shape not in groups:
                    groups[shape] = len(counts)
                    counts.append(0)
                g = groups[shape]
                rt.append((bk, i, g, counts[g]))
                counts[g] += 1
            row_tiles.append(tuple(rt))
        layouts.append(tuple(row_tiles))
    return tuple(layouts)


def splice_finalized(fin: FinalizedPlan, fplan: FlatPlan, changed,
                     r_wire=None, drift_t=None) -> FinalizedPlan:
    """Propagate repaired FlatPlan slices into a FinalizedPlan.

    Recomputes exactly the affected pieces with the same expressions
    `finalize` uses: the changed INV slices' effective operators + LU
    factors (batched over the changed subset only), the changed MVM tiles'
    effective operators, and the summing-node divisors of every tile-row
    containing a changed tile (the divisor sums the whole row's
    conductances, so it moves when any tile of the row is re-programmed).
    Everything else is carried over untouched - bit-for-bit the original.
    `drift_t` gives the ages the recomputed slices are evaluated at
    (finalize semantics; None = the static config age, i.e. fresh).
    """
    cfg = fin.cfg
    inv_ages, mvm_ages = _split_ages(fplan, drift_t)
    changed_inv, changed_mvm = _split_changed(changed)
    changed_set = {("mvm", b, i) for b, idxs in changed_mvm.items()
                   for i in idxs}

    lu_stacks = list(fin.lu_stacks)
    for b, idxs in changed_inv.items():
        grid = fplan.inv_stacks[b]
        sel = jnp.asarray(idxs)
        sub = TileGrid(grid.gpos[sel], grid.gneg[sel], grid.scale, grid.g0)
        dt = None
        if inv_ages is not None:
            a_b = inv_ages[b]
            dt = a_b if jnp.ndim(a_b) == 0 else a_b[sel]
        lu_s, piv_s = jax.scipy.linalg.lu_factor(
            _inv_operators(sub, cfg, r_wire=r_wire, drift_t=dt))
        lu, piv = lu_stacks[b]
        lu_stacks[b] = (lu.at[sel].set(lu_s), piv.at[sel].set(piv_s))

    mvm_levels = list(fin.mvm_levels)
    for li, row_tiles in enumerate(_mvm_level_layout(fplan)):
        lvl = mvm_levels[li]
        new_stacks = list(lvl.stacks)
        new_divs = list(lvl.divs)
        touched = False
        for r_idx, rt in enumerate(row_tiles):
            if not any(("mvm", bk, i) in changed_set for bk, i, _, _ in rt):
                continue
            touched = True
            load = cfg.g0
            for bk, i, g, pos in rt:
                pair = fplan.mvm_stacks[bk].pair(i)
                if ("mvm", bk, i) in changed_set:
                    dt = None
                    if mvm_ages is not None:
                        a_b = mvm_ages[bk]
                        dt = a_b if jnp.ndim(a_b) == 0 else a_b[i]
                    new_stacks[g] = new_stacks[g].at[pos].set(
                        pair.a_eff(cfg, r_wire=r_wire, drift_t=dt))
                load = load + jnp.sum(pair.gpos + pair.gneg, axis=1)
            if new_divs:
                new_divs[r_idx] = 1.0 + load / (cfg.opa_gain * cfg.g0)
        if touched:
            mvm_levels[li] = _MvmLevel(tuple(new_stacks), tuple(new_divs),
                                       lvl.windows, lvl.rows)
    return FinalizedPlan(tuple(lu_stacks), tuple(mvm_levels), fin.scale,
                         fin.schedule, fin.n, cfg, fin.num_arrays)


def splice_arena(ap: ArenaPlan, fin: FinalizedPlan, fplan: FlatPlan,
                 changed) -> ArenaPlan:
    """Propagate a spliced FinalizedPlan into an ArenaPlan.

    `fin` must be the already-spliced finalized plan (splice_finalized's
    result).  Recomputes the changed INV slices' explicit inverses from
    the new LU factors and re-folds the changed MVM tiles - plus every
    tile sharing a row with one (their common summing-node divisor is
    folded into the arena operators) - then patches the uniform
    whole-schedule program's operator sequence at the affected positions.
    Expressions match `compile_arena` pass 4 slice-for-slice.
    """
    cfg = ap.cfg
    changed_inv, changed_mvm = _split_changed(changed)
    changed_set = {("mvm", b, i) for b, idxs in changed_mvm.items()
                   for i in idxs}
    stacks = list(ap.stacks)
    updated = set()
    for b, idxs in changed_inv.items():
        lu, piv = fin.lu_stacks[b]
        sel = jnp.asarray(idxs)
        eye = jnp.eye(lu.shape[-1], dtype=lu.dtype)
        inv_s = -jax.vmap(
            lambda l_, p_: jax.scipy.linalg.lu_solve((l_, p_), eye))(
                lu[sel], piv[sel])
        stacks[b] = stacks[b].at[sel].set(inv_s)
        updated.update((b, i) for i in idxs)

    sid_of = {}
    next_id = len(fin.lu_stacks)
    for li, lvl in enumerate(fin.mvm_levels):
        for g in range(len(lvl.stacks)):
            sid_of[(li, g)] = next_id
            next_id += 1
    for li, row_tiles in enumerate(_mvm_level_layout(fplan)):
        lvl = fin.mvm_levels[li]
        divs = lvl.divs if lvl.divs else (None,) * len(row_tiles)
        for r_idx, rt in enumerate(row_tiles):
            if not any(("mvm", bk, i) in changed_set for bk, i, _, _ in rt):
                continue
            div = divs[r_idx]
            for bk, i, g, pos in rt:
                if div is None and ("mvm", bk, i) not in changed_set:
                    continue
                w = -lvl.stacks[g][pos]
                if div is not None:
                    w = w / div[:, None]
                sid = sid_of[(li, g)]
                stacks[sid] = stacks[sid].at[pos].set(w)
                updated.add((sid, pos))

    program = ap.program
    if program is not None and updated:
        ops_seq = program[0]
        p = 0
        for level in ap.levels:
            for tile in level:
                if (tile[0], tile[1]) in updated:
                    ops_seq = ops_seq.at[p].set(stacks[tile[0]][tile[1]])
                p += 1
        program = (ops_seq,) + program[1:]
    return ArenaPlan(tuple(stacks), ap.scale, program, ap.levels,
                     ap.out_spec, ap.arena_size, ap.n, ap.in_off, cfg,
                     ap.kernel_ok, ap.num_arrays, ap.slot_offsets,
                     ap.slot_ranges, ap.peak_liveness)


class ProgrammedSolver:
    """Program-once / solve-many handle over one finalized matrix.

    The AMC serving abstraction: `program` pays the full programming-time
    cost (partitioning, Schur complements, conductance mapping, operator
    finalization and arena compilation) exactly once; `solve` /
    `solve_many` then stream any number of right-hand sides against the
    programmed arrays at marginal cost.  All solves dispatch through one
    shared jitted executor keyed on the plan's pytree structure, so
    repeated solves never re-trace; `solve_many` pads the batch dim to the
    next power of two, so distinct queue lengths never re-trace either.

    `mode` selects the executor (overridable per call): "fused" (default)
    runs the arena-form single-dispatch executor - the serving fast path -
    while "reference" runs the finalized schedule that is pinned
    bit-for-bit against `execute_flat` (TESTING.md four-way contract).
    """

    def __init__(self, fin: FinalizedPlan, arena: Optional[ArenaPlan] = None,
                 mode: str = "fused", fplan: Optional[FlatPlan] = None,
                 parts: Optional[PartitionedSystem] = None,
                 stages: Optional[int] = None):
        if mode not in ("reference", "fused"):
            raise ValueError(f"mode must be 'reference' or 'fused', "
                             f"got {mode!r}")
        self._fin = fin
        # arena compilation (explicit bucket inversions + layout analysis)
        # is paid at programming time for fused-mode solvers and lazily on
        # first fused use otherwise - reference-mode callers never pay it.
        self._arena = arena
        if self._arena is None and mode == "fused":
            self._arena = compile_arena(fin)
        self._mode = mode
        # Maintenance state: the flat plan (raw conductance stacks - drift
        # is a readout effect, so aging re-finalizes from here without
        # re-programming) and the partitioned system + resolved stage
        # count (block repair re-derives target blocks from them).  Both
        # optional: checkpoint-restored solvers carry neither, and then
        # `aged`/`repaired` are unavailable (callers fall back to a full
        # re-program).
        self._fplan = fplan
        self._parts = parts
        self._stages = stages

    @classmethod
    def program(cls, a: jnp.ndarray, key: jax.Array, cfg: AnalogConfig,
                stages: Optional[int] = None,
                mode: str = "fused") -> "ProgrammedSolver":
        """Full programming flow for matrix A (one noise draw)."""
        parts = partition_system(a, cfg, stages)
        if stages is None:
            stages = required_stages(a.shape[0], cfg.array_size)
        return cls.from_plan(program_system(parts, key, cfg), cfg,
                             mode=mode, parts=parts, stages=stages)

    @classmethod
    def from_plan(cls, plan: Union[SolvePlan, FlatPlan], cfg: AnalogConfig,
                  mode: str = "fused",
                  parts: Optional[PartitionedSystem] = None,
                  stages: Optional[int] = None) -> "ProgrammedSolver":
        """Finalize an already-built plan (recursive or flat)."""
        fplan = plan if isinstance(plan, FlatPlan) else compile_plan(plan)
        return cls(finalize(fplan, cfg), mode=mode, fplan=fplan,
                   parts=parts, stages=stages)

    @property
    def finalized(self) -> FinalizedPlan:
        return self._fin

    @property
    def flat(self) -> Optional[FlatPlan]:
        return self._fplan

    @property
    def stages(self) -> Optional[int]:
        return self._stages

    @property
    def ageable(self) -> bool:
        """Can this solver be re-finalized at new device ages?"""
        return self._fplan is not None

    @property
    def repairable(self) -> bool:
        """Can this solver re-program individual blocks in place?"""
        return self._fplan is not None and self._parts is not None \
            and self._stages is not None

    def block_map(self) -> Tuple[BlockRecord, ...]:
        """Every physical array of this plan (requires `repairable`)."""
        if self._stages is None:
            raise ValueError("solver was built without a resolved stage "
                             "count; block map unavailable")
        return plan_block_map(self._fin.n, self._stages, self._fin.cfg)

    def aged(self, drift_t) -> "ProgrammedSolver":
        """This solver with its readout evaluated at new device ages.

        `drift_t` follows `finalize` semantics (scalar or `PlanAges`).
        The conductance stacks are shared, not copied - drift is a
        readout effect - and the returned solver has identical pytree
        structure, so existing jit caches keep hitting.
        """
        if self._fplan is None:
            raise ValueError("solver does not retain its flat plan "
                             "(checkpoint-restored?); aging unavailable")
        fin = finalize(self._fplan, self._fin.cfg, drift_t=drift_t)
        arena = compile_arena(fin) if self._arena is not None else None
        return ProgrammedSolver(fin, arena, self._mode, fplan=self._fplan,
                                parts=self._parts, stages=self._stages)

    def repaired(self, blocks, key: jax.Array,
                 drift_t=None) -> "ProgrammedSolver":
        """Block-level repair: re-program only `blocks`, splice in place.

        `blocks` are ("inv"|"mvm", bucket, index) refs (see `block_map`);
        `key` is the fresh root key the per-block programming keys are
        derived from.  `drift_t` (finalize semantics) gives the ages the
        recomputed slices are evaluated at - None means fresh.  Cost
        scales with the number of repaired blocks: nothing outside the
        affected bucket slices / tile rows is recomputed, and repairing
        every block under `key` is bit-identical to a full re-program
        under `key` (tests/test_block_repair.py).
        """
        if not self.repairable:
            raise ValueError("solver does not retain its partitioned "
                             "system (checkpoint-restored?); block repair "
                             "unavailable - fall back to a full re-program")
        fplan, changed = repair_blocks(self._fplan, self._parts,
                                       self._fin.cfg, blocks, key,
                                       stages=self._stages)
        fin = splice_finalized(self._fin, fplan, changed, drift_t=drift_t)
        arena = None
        if self._arena is not None:
            arena = splice_arena(self._arena, fin, fplan, changed)
        return ProgrammedSolver(fin, arena, self._mode, fplan=fplan,
                                parts=self._parts, stages=self._stages)

    @property
    def arena(self) -> ArenaPlan:
        if self._arena is None:
            self._arena = compile_arena(self._fin)
        return self._arena

    @property
    def mode(self) -> str:
        return self._mode

    @property
    def cfg(self) -> AnalogConfig:
        return self._fin.cfg

    @property
    def n(self) -> int:
        return self._fin.n

    @property
    def num_arrays(self) -> int:
        return self._fin.num_arrays

    def solve(self, b: jnp.ndarray, jit: bool = True,
              mode: Optional[str] = None) -> jnp.ndarray:
        """Solve A x = b for one (n,) rhs or an (n, k) batch.

        mode=None uses the solver's default.  In "reference" mode,
        jit=False runs the finalized schedule eagerly - op for op the same
        numbers as `execute_flat`, bit-for-bit on CPU (the equivalence
        contract); the jitted path lets XLA merge each level's same-shape
        tile dots (float-tolerance equal).  "fused" mode runs the arena
        executor - float-tolerance against the reference by design (see
        the DESIGN note).
        """
        mode = self._mode if mode is None else mode
        if mode == "reference":
            return (_execute_finalized if jit else execute_finalized)(
                self._fin, b)
        return (_execute_arena if jit else execute_arena)(self.arena, b)

    def solve_many(self, bs: jnp.ndarray, donate: bool = False,
                   mode: Optional[str] = None,
                   pad_to_pow2: bool = True) -> jnp.ndarray:
        """Solve an (n, k) batch of right-hand sides in one fused call.

        pad_to_pow2=True (default) zero-pads the batch dim to the next
        power of two before dispatch and slices the padding away after, so
        the jitted executor compiles at most one new shape per doubling
        instead of one per distinct k (serving queues flush at arbitrary
        lengths).  donate=True donates the rhs buffer to the computation -
        opt in from serving hot loops that never reuse bs after the call
        (XLA then aliases it for the output on backends that support
        donation; a no-op on CPU).
        """
        k = bs.shape[1]
        if k == 0:
            return jnp.zeros_like(bs)
        if pad_to_pow2:
            bs, k = pad_rhs_pow2(bs)
        k_pad = bs.shape[1]
        mode = self._mode if mode is None else mode
        if mode == "reference":
            fn = _execute_finalized_donated if donate else _execute_finalized
            xs = fn(self._fin, bs)
        else:
            fn = _execute_arena_donated if donate else _execute_arena
            xs = fn(self.arena, bs)
        return xs[:, :k] if k_pad > k else xs


# ---------------------------------------------------------------------------
# Packed multi-tenant serving: one dispatch over (instances x rhs)
#
# A production solver service fields requests for many *different* matrices
# concurrently.  Per matrix, the arena executor already collapses a solve to
# one dispatch; across matrices the service still paid one dispatch per
# tenant per flush.  The packed layer adds the missing instance axis:
#
#   plan_signature(n, stages, cfg)   the structural stackability key
#   pack_partitioned / program_system_batched / finalize_batched /
#   compile_arena_batched            the batched programming pipeline -
#                                    one vmapped trace programs M matrices
#   PackedArenaPlan                  M same-signature arena plans stacked
#                                    leaf-for-leaf: (M, L, r, c) operator
#                                    stacks, (M,) scales, one shared static
#                                    schedule / layout / window program
#   pack_arena_plans                 stack already-compiled ArenaPlans
#                                    (the serving flush_all path)
#   execute_arena_packed             the whole fleet as stacked-tile
#                                    matmuls; the Pallas megakernel grows
#                                    an instance grid axis
#
# Stackability invariant: every *static* artifact of the compile pipeline
# (partition split tree, bucket shapes, flat schedule, finalized windows,
# arena slot layout, whole-schedule window program) is a deterministic
# function of (n, stages, cfg) alone - matrix values and noise keys only
# ever flow into array *contents*, never into shapes or schedules.  Plans
# with equal `plan_signature` therefore flatten to identical treedefs with
# identical leaf shapes and identical static metadata, and may be stacked
# on a leading instance axis and executed by one program.  The signature-
# bucketing properties are pinned in tests/test_plan_properties.py; the
# packed-vs-loop equivalence in tests/test_packed_serving.py.
# ---------------------------------------------------------------------------


def plan_signature(n: int, stages: Optional[int], cfg: AnalogConfig):
    """Structural signature of the whole compile pipeline for (n, stages, cfg).

    Returns a hashable key with the property: equal signatures imply the
    flat schedule, bucket shapes, finalized windows and arena layout of two
    programmed matrices are identical (see the stackability invariant
    above), so their plans can be packed on a leading instance axis.
    stages=None resolves to `required_stages` exactly like
    `partition_system`.  The split tree hashed here is the `_split_tree`
    `_partition` itself consumes (the root static artifact every later
    stage derives from), so the signature tracks the split rule by
    construction; n, the resolved stage count and the full AnalogConfig
    make unequal problems hash apart.
    """
    if stages is None:
        stages = required_stages(n, cfg.array_size)
    return ("blockamc", int(n), int(stages), _split_tree(n, stages), cfg)


def pack_partitioned(parts_seq) -> PartitionedSystem:
    """Stack same-signature PartitionedSystems on a leading instance axis.

    The stacked system feeds `program_system_batched`; callers are expected
    to have bucketed by `plan_signature` (same treedef / leaf shapes), which
    `jnp.stack` enforces mechanically anyway.
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *parts_seq)


def program_system_batched(parts: PartitionedSystem, keys: jax.Array,
                           cfg: AnalogConfig) -> FlatPlan:
    """Program + flat-compile M instances in one vmap.

    `parts` carries a leading instance axis on every leaf (from
    `pack_partitioned`) and `keys` is (M, ...), one independent noise draw
    per instance; the result is a FlatPlan whose conductance stacks are
    (M, num_arrays, r, c) under one shared static schedule.  Programming M
    matrices costs one trace instead of M - the per-matrix loop's Python
    walk and per-plan dispatch disappear.
    """
    return jax.vmap(lambda p, k: compile_plan(program_system(p, k, cfg)))(
        parts, keys)


def finalize_batched(fplans: FlatPlan, cfg: AnalogConfig) -> FinalizedPlan:
    """`finalize` over a leading instance axis: (M, ...) LU factor stacks,
    (M, L, r, c) MVM tile stacks, one shared schedule."""
    return jax.vmap(lambda fp: finalize(fp, cfg))(fplans)


@jax.tree_util.register_pytree_node_class
class PackedArenaPlan:
    """M same-signature ArenaPlans stacked on a leading instance axis.

    `stacks[i]` is the i-th operator stack of the shared layout with shape
    (M, L, r, c) (explicit negated INV inverses first, then the
    sign/divisor-folded MVM tiles - exactly ArenaPlan's vocabulary, one
    instance axis in front); `scale` is (M,).  The static metadata (levels,
    out_spec, slot offsets, arena size) is the single shared copy every
    instance was compiled to - that sharing is what `plan_signature`
    guarantees and `pack_arena_plans` verifies.  For uniform power-of-two
    plans, `program_ops` is the (M, T, r, c) whole-schedule operator
    sequence and `program_meta` the shared (T, ...) window metadata the
    packed Pallas megakernel executes with an instance grid axis.
    """

    def __init__(self, stacks, scale, program_ops, program_meta, levels,
                 out_spec, arena_size, n, in_off, cfg, kernel_ok,
                 num_arrays, slot_offsets, num_instances):
        self.stacks = stacks
        self.scale = scale
        self.program_ops = program_ops    # (M, T, r, c) or None
        self.program_meta = program_meta  # shared (T, ...) metadata or None
        self.levels = levels
        self.out_spec = out_spec
        self.arena_size = arena_size
        self.n = n
        self.in_off = in_off
        self.cfg = cfg
        self.kernel_ok = kernel_ok
        self.num_arrays = num_arrays      # per instance
        self.slot_offsets = slot_offsets
        self.num_instances = num_instances

    def tree_flatten(self):
        return ((self.stacks, self.scale, self.program_ops,
                 self.program_meta),
                (self.levels, self.out_spec, self.arena_size, self.n,
                 self.in_off, self.cfg, self.kernel_ok, self.num_arrays,
                 self.slot_offsets, self.num_instances))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def num_levels(self) -> int:
        return len(self.levels)


# Static ArenaPlan metadata that must agree for plans to share one packed
# program (the mechanical form of the signature-stackability invariant).
_STACKABLE_FIELDS = ("levels", "out_spec", "arena_size", "n", "in_off",
                     "cfg", "kernel_ok", "slot_offsets")


def pack_arena_plans(aps) -> PackedArenaPlan:
    """Stack already-compiled same-signature ArenaPlans into a packed plan.

    The serving `flush_all` path: each tenant's matrix was programmed (and
    arena-compiled) independently at admission time; packing is a pure
    leaf-for-leaf `jnp.stack` plus a static-metadata equality check, so it
    is cheap enough to run per flush.  Raises ValueError when the plans'
    static structure diverges (different `plan_signature` - they cannot
    share one schedule).
    """
    aps = list(aps)
    if not aps:
        raise ValueError("pack_arena_plans needs at least one plan")
    ap0 = aps[0]
    for ap in aps[1:]:
        for f in _STACKABLE_FIELDS:
            if getattr(ap, f) != getattr(ap0, f):
                raise ValueError(
                    f"arena plans are not stackable: static field {f!r} "
                    f"differs (plans compiled from different "
                    f"plan_signature buckets?)")
    stacks = tuple(jnp.stack([ap.stacks[i] for ap in aps])
                   for i in range(len(ap0.stacks)))
    scale = jnp.stack([ap.scale for ap in aps])
    program_ops = program_meta = None
    if ap0.program is not None:
        program_ops = jnp.stack([ap.program[0] for ap in aps])
        program_meta = ap0.program[1:]
    return PackedArenaPlan(stacks, scale, program_ops, program_meta,
                           ap0.levels, ap0.out_spec, ap0.arena_size, ap0.n,
                           ap0.in_off, ap0.cfg, ap0.kernel_ok,
                           ap0.num_arrays, ap0.slot_offsets, len(aps))


def compile_arena_batched(fins: FinalizedPlan) -> PackedArenaPlan:
    """`compile_arena` over a leading instance axis -> PackedArenaPlan.

    `fins` is a finalized-plan stack from `finalize_batched`.  The static
    analysis (views, live ranges, offsets) traces once for the shared
    schedule; only the numeric operator work (explicit bucket inversion,
    divisor folding) is vmapped, so the packed compile costs one trace for
    all M instances.  The whole-schedule window metadata is identical
    across instances by construction and stored once.
    """
    aps = jax.vmap(compile_arena)(fins)
    program_ops = program_meta = None
    if aps.program is not None:
        # vmap broadcast the (constant) metadata arrays; keep one copy.
        ops_seq, in_offs, in_signs, out_offs, out_init = aps.program
        program_ops = ops_seq
        program_meta = (in_offs[0], in_signs[0], out_offs[0], out_init[0])
    return PackedArenaPlan(aps.stacks, aps.scale, program_ops, program_meta,
                           aps.levels, aps.out_spec, aps.arena_size, aps.n,
                           aps.in_off, aps.cfg, aps.kernel_ok,
                           aps.num_arrays, aps.slot_offsets,
                           aps.scale.shape[0])


def program_packed(As: jnp.ndarray, keys: jax.Array, cfg: AnalogConfig,
                   stages: Optional[int] = None) -> PackedArenaPlan:
    """Full batched programming flow for an (M, n, n) matrix stack.

    One jitted trace runs partitioning, Schur complements, conductance
    mapping, finalization and arena compilation for all M matrices -
    programming a fleet stops costing M traces/compiles.  All matrices
    share (n, stages, cfg), i.e. one `plan_signature`.
    """
    return _program_packed(As, keys, cfg, stages)


@partial(jax.jit, static_argnames=("cfg", "stages"))
def _program_packed(As, keys, cfg, stages):
    parts = jax.vmap(lambda a: partition_system(a, cfg, stages))(As)
    fplans = program_system_batched(parts, keys, cfg)
    return compile_arena_batched(finalize_batched(fplans, cfg))


def execute_arena_packed(pp: PackedArenaPlan, bs: jnp.ndarray,
                         use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """Run the whole packed fleet; returns per-instance solutions.

    `bs` is (M, n) - one rhs per instance - or (M, n, k): instance i's
    k-column batch.  Every schedule level of the jnp path is one stacked-
    tile matmul over the (M, L, r, c) operator stacks (the instance axis
    rides the batch dims of each dot), so the fleet costs one schedule
    walk instead of M.  On the kernel path, a uniform plan runs ALL
    instances' cascades as ONE megakernel call whose grid walks
    (instance, tile) over an (M, S, K) arena stack; use_kernel=None routes
    through the kernel on TPU when the plan is uniform, True forces it
    (interpret mode off TPU - the CI smoke), False forces jnp.

    Per-instance results equal `execute_arena` on that instance's own plan
    bit-for-bit when both run eagerly on CPU on aligned power-of-two plans
    (batching the dots over the instance axis neither reassociates a
    per-instance reduction nor changes the per-slice dot kernel); ragged
    odd splits are last-ulp float tolerance (the packed-vs-loop contract,
    tests/test_packed_serving.py).
    """
    cfg = pp.cfg
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu and pp.kernel_ok and pp.program_ops is not None
    elif use_kernel and (not pp.kernel_ok or pp.program_ops is None):
        raise ValueError(
            "use_kernel=True but this packed plan has no uniform "
            "whole-schedule program (ragged windows or mixed tile "
            "shapes); use the jnp path or a power-of-two configuration")
    single = bs.ndim == 2
    dtype = jnp.result_type(bs.dtype, pp.scale.dtype)
    bk = bs[..., None] if single else bs
    b_in = analog.dac(bk, cfg).astype(dtype)
    if use_kernel:
        from repro.kernels import ops as kops
        m = b_in.shape[0]
        arena = jnp.zeros((m, pp.arena_size) + bk.shape[2:], dtype)
        arena = arena.at[:, pp.in_off:pp.in_off + pp.n].set(b_in)
        in_offs, in_signs, out_offs, out_init = pp.program_meta
        arena = kops.arena_packed_apply(
            arena, pp.program_ops, in_offs, in_signs, out_offs, out_init,
            interpret=not on_tpu)
        out_spec = _arena_out_spec(pp.out_spec, pp.slot_offsets)
        out = jax.vmap(lambda ar: _slot_gather({0: ar}, out_spec))(arena)
    else:
        def one(stacks, b1):
            # per-instance differentiable cascade (custom_vjp vmaps cleanly)
            return _cascade(pp.levels, pp.out_spec, stacks, b1)

        out = jax.vmap(one)(pp.stacks, b_in)
    if single:
        out = out[..., 0]
    scale = pp.scale.reshape((-1,) + (1,) * (out.ndim - 1))
    return -scale * analog.adc(out, cfg)


_execute_arena_packed = jax.jit(execute_arena_packed,
                                static_argnames=("use_kernel",))
_execute_arena_packed_donated = jax.jit(execute_arena_packed,
                                        donate_argnums=(1,),
                                        static_argnames=("use_kernel",))


def execute_arena_packed_sharded(pp: PackedArenaPlan, bs: jnp.ndarray,
                                 mesh=None, axis_name: str = "mc",
                                 use_kernel: Optional[bool] = None
                                 ) -> jnp.ndarray:
    """`execute_arena_packed` with the instance axis sharded over a mesh.

    Each device runs its own shard of the packed fleet (operator stacks,
    scales and right-hand sides all carry the instance axis; the shared
    window-program metadata is replicated - specs from
    `repro.sharding.partition.mc_packed_specs`).  num_instances must
    divide evenly over the mesh axis.  mesh=None builds a 1-D mesh over
    all local devices via `repro.launch.mesh.make_mc_mesh`.
    """
    if mesh is None:
        from repro.launch.mesh import make_mc_mesh
        mesh = make_mc_mesh(axis_name=axis_name)
    n_shards = mesh.shape[axis_name]
    if pp.num_instances % n_shards:
        raise ValueError(
            f"num_instances={pp.num_instances} must divide over the "
            f"{axis_name!r} mesh axis of size {n_shards}")
    return _sharded_packed_executor(pp, bs, mesh, axis_name, use_kernel)


@partial(jax.jit, static_argnames=("mesh", "axis_name", "use_kernel"))
def _sharded_packed_executor(pp, bs, mesh, axis_name, use_kernel):
    from repro.sharding.partition import mc_packed_specs

    in_specs, out_specs = mc_packed_specs(pp, axis_name)
    mapped = jax.shard_map(
        lambda p, b: execute_arena_packed(p, b, use_kernel=use_kernel),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return mapped(pp, bs)


# ---------------------------------------------------------------------------
# Batched / sharded Monte-Carlo solving
# ---------------------------------------------------------------------------

def _mc_execute(parts: PartitionedSystem, b: jnp.ndarray, keys: jax.Array,
                cfg: AnalogConfig, mode: str = "reference") -> jnp.ndarray:
    """Per-key program + compile + execute, vmapped over noise keys.

    mode="reference" runs `execute_flat` per key (the accuracy-study path,
    bit-compatible with the recursive reference); mode="fused" finalizes
    and arena-compiles each key's plan inside the vmap and runs the arena
    executor - the serving-form Monte-Carlo sweep.
    """
    if mode == "fused":
        def one(k):
            fplan = compile_plan(program_system(parts, k, cfg))
            return execute_arena(compile_arena(finalize(fplan, cfg)), b)
        return jax.vmap(one)(keys)
    fplans = jax.vmap(lambda k: compile_plan(program_system(parts, k, cfg)))(
        keys)
    return jax.vmap(lambda fp: execute_flat(fp, b, cfg))(fplans)


def _solve_batched(a, b, keys, cfg, stages, mode):
    parts = partition_system(a, cfg, stages)
    return _mc_execute(parts, b, keys, cfg, mode)


# the compiled program keeps the public name (`jit_solve_batched`): its
# persistent-cache key and the device trace's module name stay put
_solve_batched.__name__ = _solve_batched.__qualname__ = "solve_batched"
_solve_batched = jax.jit(_solve_batched,
                         static_argnames=("cfg", "stages", "mode"))


def solve_batched(a: jnp.ndarray, b: jnp.ndarray, keys: jax.Array,
                  cfg: AnalogConfig, stages: Optional[int] = None,
                  mode: str = "reference") -> jnp.ndarray:
    """Batched Monte-Carlo BlockAMC solve in one jit.

    The key-independent digital pre-processing (partitioning, Schur
    complements, normalisation) is hoisted out of the per-key path via
    `partition_system` and traced exactly once; only conductance mapping,
    noise draws and the cascade itself are vmapped over keys, so each
    schedule level is one batched solve/matmul over (num_keys, ...) stacks.
    mode="fused" routes each key through the arena executor instead of
    `execute_flat` (float-tolerance; default keeps the reference path so
    the paper accuracy sweeps stay bit-stable).

    Args:
      a:    (n, n) system matrix.
      b:    (n,) rhs vector or (n, k) matrix of k right-hand sides.
      keys: (num_keys, ...) PRNG keys, one independent device-noise draw each.
    Returns:
      (num_keys, n) or (num_keys, n, k) solutions.

    The `blockamc.solve_batched` span times the host's part of a call:
    argument handling and the program's dispatch, not the device's work.
    """
    sp = tracing.span("blockamc.solve_batched")
    if sp:
        sp.attrs["draws"] = keys.shape[0]
    with sp:
        return _solve_batched(a, b, keys, cfg, stages, mode)


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis_name", "mode"))
def _sharded_mc_executor(parts: PartitionedSystem, b: jnp.ndarray,
                         keys: jax.Array, cfg: AnalogConfig, mesh,
                         axis_name: str, mode: str) -> jnp.ndarray:
    """shard_map executor; cfg/mesh/axis are static so jit caches per combo."""
    from repro.sharding.partition import mc_solve_specs

    in_specs, out_specs = mc_solve_specs(axis_name)
    mapped = jax.shard_map(
        lambda p, bb, kk: _mc_execute(p, bb, kk, cfg, mode),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return mapped(parts, b, keys)


def solve_batched_sharded(a: jnp.ndarray, b: jnp.ndarray, keys: jax.Array,
                          cfg: AnalogConfig, stages: Optional[int] = None,
                          mesh=None, axis_name: str = "mc",
                          mode: str = "reference") -> jnp.ndarray:
    """`solve_batched` with the Monte-Carlo key axis sharded over a mesh.

    Each device programs and solves its own shard of noise keys; the system
    matrix, partitioned pre-processing and right-hand sides are replicated.
    With mesh=None a 1-D mesh over all local devices is built via
    `repro.launch.mesh.make_mc_mesh`.  num_keys must divide evenly over the
    mesh axis.  mode="fused" runs each shard's keys through the arena
    executor (same flag as `solve_batched`).
    """
    if mesh is None:
        from repro.launch.mesh import make_mc_mesh
        mesh = make_mc_mesh(axis_name=axis_name)
    n_shards = mesh.shape[axis_name]
    if keys.shape[0] % n_shards:
        raise ValueError(
            f"num_keys={keys.shape[0]} must divide over the "
            f"{axis_name!r} mesh axis of size {n_shards}")
    parts = partition_system(a, cfg, stages)
    return _sharded_mc_executor(parts, b, keys, cfg, mesh, axis_name, mode)


@partial(jax.jit, static_argnames=("cfg",))
def solve_original_batched(a: jnp.ndarray, b: jnp.ndarray, keys: jax.Array,
                           cfg: AnalogConfig) -> jnp.ndarray:
    """Batched Monte-Carlo baseline: original (monolithic) AMC solve."""
    fplans = jax.vmap(
        lambda k: compile_plan(build_original_plan(a, k, cfg)))(keys)
    return jax.vmap(lambda fp: execute_flat(fp, b, cfg))(fplans)
