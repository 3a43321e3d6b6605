"""The fused analog-seed -> Krylov-refine path, batched and sharded.

`solve_refined` is the end-to-end hybrid solve the paper's Section IV
sketches: one programmed BlockAMC cascade supplies both the *seed*
(`x0 = M b`, one analog solve) and, optionally, the *preconditioner* for a
digital Krylov iteration that polishes the seed to full digital precision.
Right-hand sides use the solver-service layout (`(n,)` or `(n, k)`
columns); internally they ride the Krylov drivers' leading axis.

Regime note (recorded by the differential tests and the hybrid benchmark):
with device noise sigma and condition number kappa, the preconditioned
operator's spectrum is perturbed by O(kappa * sigma * sqrt(n)); when that
product is large the noisy analog inverse can leave the SPD cone and PCG
stalls.  `use_precond=False` then falls back to seed-only refinement -
plain CG/GMRES from the analog seed - which always converges on the
digital side and still banks the seed's head start.

`solve_refined_batched` vmaps the whole path (per-key programming included)
over Monte-Carlo noise keys with the key-independent digital pre-processing
hoisted, exactly like `blockamc.solve_batched`; `solve_refined_batched_
sharded` shards that key axis over a device mesh via shard_map.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.blockamc import PartitionedSystem
from repro.core.mc_sharding import make_mc_mesh, mc_refined_specs
from repro.hybrid.krylov import KrylovResult, gmres, pcg
from repro.hybrid.operators import AnalogPreconditioner, matvec_from_dense


def _sanitize_seed(x0: jnp.ndarray) -> jnp.ndarray:
    """Per-column seed guard: a faulted crossbar emits non-finite analog
    seeds (stuck-at arrays can make the programmed inverse singular), and a
    single NaN in `x0` would poison the whole Krylov recurrence for that
    column.  Any column with a non-finite entry degrades to the zero seed -
    the digital iteration then simply starts cold, instead of answering
    NaN (one poisoned tenant must not poison its own refinement, let alone
    a batch-mate's; regression-pinned in tests/test_autodiff.py)."""
    finite = jnp.all(jnp.isfinite(x0), axis=-1, keepdims=True)
    return jnp.where(finite, x0, jnp.zeros_like(x0))


def _refine_core(a: jnp.ndarray, bt: jnp.ndarray,
                 precond: AnalogPreconditioner, method: str, tol: float,
                 maxiter: int, restart: int,
                 use_precond: bool) -> KrylovResult:
    """Core driver on leading-axis right-hand sides bt: (..., n)."""
    matvec = matvec_from_dense(a)
    x0 = _sanitize_seed(precond(bt))       # the analog seed, one solve
    mv_m = precond if use_precond else None
    if method == "cg":
        return pcg(matvec, bt, precond=mv_m, x0=x0, tol=tol, maxiter=maxiter)
    if method == "gmres":
        return gmres(matvec, bt, precond=mv_m, x0=x0, tol=tol,
                     restart=restart, maxiter=maxiter)
    raise ValueError(f"unknown method {method!r} (want 'cg' or 'gmres')")


# --- implicit-function-theorem VJP around the refined solve ----------------
#
# The Krylov drivers iterate inside `lax.while_loop`, which JAX cannot
# reverse-differentiate - and unrolling hundreds of CG steps would be the
# wrong gradient anyway (noisy, memory-hungry).  At convergence the output
# satisfies A x = b independently of the iteration path, so the implicit
# function theorem gives the exact adjoint:
#
#     lambda = A^-T gx,   b_bar = lambda,   A_bar = -sum_cols lambda x^T,
#
# i.e. the backward pass is ONE more (digital, seed-less) solve against the
# transposed system with the same method and fuel.  Only `x` carries
# gradients: the diagnostic fields (iters/resnorm/converged) and the analog
# preconditioner's arrays are treated as non-differentiable constants (the
# preconditioner changes the path, never the fixed point).  Second-order
# differentiation is out of contract (TESTING.md "differentiable solver
# contract").

def _zero_ct(leaf):
    """A zero cotangent of `leaf`'s dtype (float0 for int/bool leaves, as
    custom_vjp requires for non-differentiable primal inputs)."""
    if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
        return jnp.zeros_like(leaf)
    return np.zeros(jnp.shape(leaf), dtype=jax.dtypes.float0)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _refine(a, bt, precond, method, tol, maxiter, restart, use_precond):
    return _refine_core(a, bt, precond, method, tol, maxiter, restart,
                        use_precond)


def _refine_fwd(a, bt, precond, method, tol, maxiter, restart, use_precond):
    res = _refine_core(a, bt, precond, method, tol, maxiter, restart,
                       use_precond)
    return res, (a, precond, res.x)


def _refine_bwd(method, tol, maxiter, restart, use_precond, saved, ct):
    a, precond, x = saved
    gx = ct.x                      # cotangents of the diagnostics are unused
    at = jnp.swapaxes(a, -1, -2)   # cg implies A SPD, but stay exact
    lam = _fallback(at, gx, method, tol, maxiter, restart).x
    n = a.shape[-1]
    a_bar = -(lam.reshape(-1, n).T @ x.reshape(-1, n)).astype(a.dtype)
    return (a_bar, lam.astype(gx.dtype),
            jax.tree_util.tree_map(_zero_ct, precond))


_refine.defvjp(_refine_fwd, _refine_bwd)


@partial(jax.jit, static_argnames=("method", "tol", "maxiter", "restart",
                                   "use_precond"))
def _solve_refined_jit(a, bt, precond, method, tol, maxiter, restart,
                       use_precond):
    return _refine(a, bt, precond, method, tol, maxiter, restart, use_precond)


def solve_refined(a: jnp.ndarray, b: jnp.ndarray,
                  precond: AnalogPreconditioner, *, method: str = "cg",
                  tol: float = 1e-10, maxiter: int = 400, restart: int = 32,
                  use_precond: bool = True,
                  jit: bool = True) -> Tuple[jnp.ndarray, KrylovResult]:
    """Hybrid solve of A x = b: analog seed + digital Krylov refinement.

    Args:
      a:       (n, n) digital system matrix (residuals run in a's dtype -
               pass float64 under x64 for tolerances beyond f32).
      b:       (n,) one rhs or (n, k) columns (solver-service layout).
      precond: programmed analog inverse (seed source; also the Krylov
               preconditioner unless use_precond=False).
      method:  "cg" (A SPD) or "gmres" (general A).
      jit:     False runs the drivers eagerly - the reference the jitted
               multi-RHS path is pinned to (TESTING.md).
    Returns:
      (x, result): x shaped like b; result per-RHS stats in the drivers'
      leading-axis layout.
    """
    single = b.ndim == 1
    bt = (b if single else b.T).astype(a.dtype)
    run = _solve_refined_jit if jit else _refine
    res = run(a, bt, precond, method, float(tol), int(maxiter), int(restart),
              bool(use_precond))
    return (res.x if single else res.x.T), res


# ---------------------------------------------------------------------------
# Degraded-mode digital fallback (no analog operator involved)
# ---------------------------------------------------------------------------

def _fallback(a: jnp.ndarray, bt: jnp.ndarray, method: str, tol: float,
              maxiter: int, restart: int) -> KrylovResult:
    """Digital-only Krylov solve from a zero seed on leading-axis rhs."""
    matvec = matvec_from_dense(a)
    if method == "cg":
        return pcg(matvec, bt, tol=tol, maxiter=maxiter)
    if method == "gmres":
        return gmres(matvec, bt, tol=tol, restart=restart, maxiter=maxiter)
    raise ValueError(f"unknown method {method!r} (want 'cg' or 'gmres')")


@partial(jax.jit, static_argnames=("method", "tol", "maxiter", "restart"))
def _solve_fallback_jit(a, bt, method, tol, maxiter, restart):
    return _fallback(a, bt, method, tol, maxiter, restart)


def solve_fallback(a: jnp.ndarray, b: jnp.ndarray, *, method: str = "cg",
                   tol: float = 1e-8, maxiter: int = 800, restart: int = 32,
                   jit: bool = True) -> Tuple[jnp.ndarray, KrylovResult]:
    """Fully digital solve of A x = b: the degraded serving mode.

    The bottom rung of the quarantine -> re-program -> degrade ladder
    (TESTING.md "serving robustness contract"): when the analog substrate
    cannot be restored to health, the engine keeps answering from the
    stored digital matrix alone.  Unlike `solve_refined` this takes *no*
    analog seed and *no* analog preconditioner - a faulted crossbar can
    produce non-finite seeds, which would poison the Krylov recurrence -
    so it is correct whatever state the device is in, just slower (plain
    CG/GMRES from zero; the mixed-precision IMC papers' pure-digital
    baseline).  Same layout contract as `solve_refined`: b is `(n,)` or
    `(n, k)` columns, x comes back shaped like b.
    """
    single = b.ndim == 1
    bt = (b if single else b.T).astype(a.dtype)
    run = _solve_fallback_jit if jit else _fallback
    res = run(a, bt, method, float(tol), int(maxiter), int(restart))
    return (res.x if single else res.x.T), res


# ---------------------------------------------------------------------------
# Monte-Carlo batched / sharded refinement
# ---------------------------------------------------------------------------

def _refined_mc(a: jnp.ndarray, parts: PartitionedSystem, bt: jnp.ndarray,
                keys: jax.Array, cfg: AnalogConfig, method: str, tol: float,
                maxiter: int, restart: int, use_precond: bool):
    """Program + finalize + refine per noise key, vmapped over keys."""

    def one(k):
        fplan = blockamc.compile_plan(blockamc.program_system(parts, k, cfg))
        precond = AnalogPreconditioner(blockamc.finalize(fplan, cfg))
        return _refine(a, bt, precond, method, tol, maxiter, restart,
                       use_precond)

    return jax.vmap(one)(keys)    # KrylovResult with a leading key axis


@partial(jax.jit, static_argnames=("cfg", "method", "tol", "maxiter",
                                   "restart", "use_precond"))
def _refined_mc_jit(a, parts, bt, keys, cfg, method, tol, maxiter, restart,
                    use_precond):
    return _refined_mc(a, parts, bt, keys, cfg, method, tol, maxiter,
                       restart, use_precond)


def solve_refined_batched(a: jnp.ndarray, b: jnp.ndarray, keys: jax.Array,
                          cfg: AnalogConfig, *, stages: Optional[int] = None,
                          method: str = "cg", tol: float = 1e-10,
                          maxiter: int = 400, restart: int = 32,
                          use_precond: bool = True) -> KrylovResult:
    """Monte-Carlo hybrid solve: one refined solve per noise key, one jit.

    Every key programs its own noisy preconditioner (key-independent digital
    pre-processing hoisted via `partition_system`) and refines the same
    right-hand sides.  Returns a KrylovResult with a leading (num_keys, ...)
    axis on every field; `b` may be (n,) or (n, k) (x comes back as
    (num_keys, n) / (num_keys, k, n)).
    """
    parts = blockamc.partition_system(a, cfg, stages)
    bt = (b if b.ndim == 1 else b.T).astype(a.dtype)
    return _refined_mc_jit(a, parts, bt, keys, cfg, method, float(tol),
                           int(maxiter), int(restart), bool(use_precond))


@partial(jax.jit, static_argnames=("cfg", "method", "tol", "maxiter",
                                   "restart", "use_precond", "mesh",
                                   "axis_name"))
def _refined_mc_sharded(a, parts, bt, keys, cfg, method, tol, maxiter,
                        restart, use_precond, mesh, axis_name):
    in_specs, out_specs = mc_refined_specs(axis_name)
    mapped = jax.shard_map(
        lambda aa, pp, bb, kk: _refined_mc(aa, pp, bb, kk, cfg, method, tol,
                                           maxiter, restart, use_precond),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return mapped(a, parts, bt, keys)


def solve_refined_batched_sharded(a: jnp.ndarray, b: jnp.ndarray,
                                  keys: jax.Array, cfg: AnalogConfig, *,
                                  stages: Optional[int] = None,
                                  method: str = "cg", tol: float = 1e-10,
                                  maxiter: int = 400, restart: int = 32,
                                  use_precond: bool = True, mesh=None,
                                  axis_name: str = "mc") -> KrylovResult:
    """`solve_refined_batched` with the noise-key axis sharded over a mesh.

    Each device programs and refines its own shard of noisy preconditioners;
    the system matrix, partitioned pre-processing and right-hand sides are
    replicated (same composition as `blockamc.solve_batched_sharded`).
    num_keys must divide evenly over the mesh axis.
    """
    if mesh is None:
        mesh = make_mc_mesh(axis_name=axis_name)
    n_shards = mesh.shape[axis_name]
    if keys.shape[0] % n_shards:
        raise ValueError(
            f"num_keys={keys.shape[0]} must divide over the "
            f"{axis_name!r} mesh axis of size {n_shards}")
    parts = blockamc.partition_system(a, cfg, stages)
    bt = (b if b.ndim == 1 else b.T).astype(a.dtype)
    return _refined_mc_sharded(a, parts, bt, keys, cfg, method, float(tol),
                               int(maxiter), int(restart), bool(use_precond),
                               mesh, axis_name)
