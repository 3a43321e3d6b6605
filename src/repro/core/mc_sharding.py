"""Meshes and shard_map specs of the solver's sharded executors.

`solve_batched_sharded` and `solve_refined_batched_sharded` shard a
Monte-Carlo noise-key axis, and `execute_arena_packed_sharded` a packed
plan's instance axis, over one 1-D mesh axis.  Functions, not module-level
constants: importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def make_mc_mesh(n_devices: int | None = None, axis_name: str = "mc"):
    """1-D mesh for sharding a Monte-Carlo key axis (blockamc sweeps).

    Defaults to all local devices; `solve_batched_sharded` gives every
    device its own shard of independent noise keys.
    """
    if n_devices is None:
        n_devices = jax.device_count()
    return jax.make_mesh((n_devices,), (axis_name,))


def mc_solve_specs(axis_name: str = "mc"):
    """shard_map specs for a Monte-Carlo BlockAMC sweep.

    The partitioned system and right-hand sides are replicated on every
    device; only the noise-key axis is sharded, so each device programs and
    solves its own independent draws.  Returns (in_specs, out_specs) for
    `(partitioned_system, b, keys) -> solutions`.
    """
    return (P(), P(), P(axis_name)), P(axis_name)


def mc_packed_specs(pp, axis_name: str = "mc"):
    """shard_map specs for a packed multi-tenant arena execution.

    `(packed_plan, bs) -> xs`: every instance-carrying leaf of the
    `PackedArenaPlan` (operator stacks, scales, per-instance whole-schedule
    operator sequence) and the (M, n, k) rhs stack shard their leading
    instance axis over `axis_name`; the shared window-program metadata
    (identical across instances by the signature-stackability invariant)
    is replicated.  The spec tree mirrors the plan's pytree structure, so
    it must be built from the concrete plan being dispatched.
    """
    inst, rep = P(axis_name), P()
    children, aux = pp.tree_flatten()
    stacks, scale, program_ops, program_meta = children
    spec_children = (
        tuple(inst for _ in stacks),
        inst,
        None if program_ops is None else inst,
        None if program_meta is None else tuple(rep for _ in program_meta),
    )
    plan_spec = type(pp).tree_unflatten(aux, spec_children)
    return (plan_spec, P(axis_name)), P(axis_name)


def mc_refined_specs(axis_name: str = "mc"):
    """shard_map specs for a Monte-Carlo *hybrid refined* solve.

    Same discipline as `mc_solve_specs` with the dense digital matrix along
    for the ride: `(a, partitioned_system, b, keys) -> KrylovResult`.  The
    matrix, pre-processing and right-hand sides are replicated; each device
    programs and refines its own shard of noisy preconditioners, and every
    field of the per-key KrylovResult comes back sharded on the key axis.
    """
    return (P(), P(), P(), P(axis_name)), P(axis_name)
