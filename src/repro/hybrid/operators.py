"""Linear-operator adapters bridging the analog solver into digital Krylov.

Layout contract of the Krylov drivers (`repro.hybrid.krylov`): right-hand
sides ride on *leading* axes - a vector is `(n,)`, a multi-RHS batch is
`(..., n)` - and an operator is any callable mapping `(..., n) -> (..., n)`
over the trailing axis.  This module provides the two operators the hybrid
loop needs:

  * `matvec_from_dense(a)` - the digital matrix-vector product `v -> A v`
    in the drivers' layout (the exact, full-precision residual operator).
  * `AnalogPreconditioner` - one programmed BlockAMC cascade (a
    `FinalizedPlan`: noisy conductances, wire model, finite gain and
    quantisers all folded in) applied as `M ~ A^-1`.  It is a registered
    pytree, so it passes through jit/vmap/shard_map as an argument, and it
    is *mixed precision*: inputs are cast down to the plan's compute dtype
    (the analog substrate), outputs cast back up to the caller's dtype
    (the digital iteration) - the Le Gallo et al. mixed-precision IMC
    split.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.blockamc import ArenaPlan, FinalizedPlan


def matvec_from_dense(a: jnp.ndarray) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """`v -> A v` over the trailing axis of `v` ((..., n) -> (..., n))."""
    def matvec(v: jnp.ndarray) -> jnp.ndarray:
        # (A v)_i = sum_j A_ij v_j for every leading batch index.
        return v @ a.T

    return matvec


@jax.tree_util.register_pytree_node_class
class AnalogPreconditioner:
    """A programmed analog inverse as a batched digital-domain operator.

    Wraps one `FinalizedPlan` (program-once form of a BlockAMC cascade) and
    applies it to `(..., n)` inputs: one analog solve per trailing vector,
    all leading axes batched through the executor's multi-RHS path.
    Because the plan is finalized, every application is pure batched
    `lu_solve`s / stacked matmuls - the marginal-cost analog solve the
    paper's cost model promises, which is what makes it affordable *inside*
    a Krylov iteration.

    The inner-loop apply runs the arena-form single-dispatch executor
    (core/blockamc.py DESIGN note), the serving fast path; the finalized
    schedule it is float-tolerance-pinned against (TESTING.md four-way
    contract) is `blockamc.execute_finalized(pre.fin, cols)`.

    Differentiability (TESTING.md "differentiable solver contract"): the
    apply is reverse-mode differentiable in both the input `v` and the
    plan's *array* leaves (effective-operator stacks, scale) - the fused
    path routes through the arena executor's implicit-diff `custom_vjp`,
    so the backward pass is one transposed cascade, never a re-programming.
    The pytree split is load-bearing for that: `tree_flatten` keeps every
    calibratable array in the children and only static metadata (the
    plans' hashable level/spec tuples inside their own flattening; its
    own aux_data is empty), so `jax.grad`/`jax.vmap` see exactly the
    differentiable leaves and jit caches never retrace on a re-programmed
    instance (pinned by the retrace-guard tests in tests/test_autodiff.py).
    """

    def __init__(self, fin: FinalizedPlan,
                 aplan: Optional[ArenaPlan] = None):
        self.fin = fin
        self.aplan = blockamc.compile_arena(fin) if aplan is None else aplan

    def tree_flatten(self):
        return (self.fin, self.aplan), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = cls.__new__(cls)
        obj.fin, obj.aplan = children
        return obj

    @classmethod
    def program(cls, a: jnp.ndarray, key: jax.Array, cfg: AnalogConfig,
                stages: Optional[int] = None) -> "AnalogPreconditioner":
        """Full programming flow: partition, Schur, map + noise, finalize."""
        fplan = blockamc.compile_plan(blockamc.build_plan(a, key, cfg, stages))
        return cls(blockamc.finalize(fplan, cfg))

    @classmethod
    def from_solver(cls, solver: "blockamc.ProgrammedSolver"
                    ) -> "AnalogPreconditioner":
        """Share an already-programmed `ProgrammedSolver`'s plans."""
        return cls(solver.finalized, aplan=solver.arena)

    @property
    def n(self) -> int:
        return self.fin.n

    @property
    def cfg(self) -> AnalogConfig:
        return self.fin.cfg

    @property
    def compute_dtype(self):
        """The analog substrate's dtype (set when the plan was built)."""
        return self.fin.scale.dtype

    def __call__(self, v: jnp.ndarray) -> jnp.ndarray:
        """Apply M ~ A^-1 to (..., n); returns (..., n) in v's dtype."""
        n = self.fin.n
        if v.ndim == 1:
            out = blockamc.execute_arena(self.aplan,
                                         v.astype(self.compute_dtype))
            return out.astype(v.dtype)
        lead = v.shape[:-1]
        cols = v.reshape((-1, n)).T.astype(self.compute_dtype)  # (n, k)
        out = blockamc.execute_arena(self.aplan, cols)
        return out.T.reshape(lead + (n,)).astype(v.dtype)

    # LinearOperator-flavoured alias
    apply = __call__
