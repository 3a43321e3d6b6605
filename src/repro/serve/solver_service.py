"""Linear-system serving: program a matrix once, stream right-hand sides.

The ROADMAP serving scenario for the paper's cost model (programming the
arrays is the expensive one-time step; every subsequent solve is nearly
free): a registry of `ProgrammedSolver` handles keyed by matrix id, plus a
per-matrix request queue so right-hand sides that arrive between flushes are
solved in one fused `solve_many` call instead of one cascade walk each.

Multi-tenant packing: `flush_all` is the cross-matrix analogue of the
per-matrix flush.  Pending queues are grouped by `plan_signature` (the
structural stackability key - see the packed-serving DESIGN note in
core/blockamc.py).  Every programmed tenant of a signature sits in one
resident packed plan (arena plans stacked leaf-for-leaf on a leading
instance axis, built once and kept until a member's plan changes), ragged
per-tenant queue lengths are zero-padded to one shared power-of-two rhs
width via `pad_rhs_pow2`, and the whole bucket dispatches as ONE
`execute_arena_packed_selected` call, which gathers the bucket's tenants
from the resident plan by index, instead of one dispatch per tenant.
Answers scatter back per tenant, and per-tenant counters go through the
single `_record` bookkeeping helper so packed solves are never
double-counted.

Deliberately synchronous and small - the batching discipline and the
program/solve cost split are the point; transport and scheduling live a
layer up, in serve/async_engine.py's `AsyncSolverEngine` (its size, age
and deadline flush triggers drive `flush_all`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.analog import AnalogConfig
from repro.core.blockamc import (PackedArenaPlan, ProgrammedSolver,
                                 _execute_arena_packed_selected_donated,
                                 pack_arena_plans, pad_rhs_pow2,
                                 plan_signature, replace_packed_instance)
from repro.hybrid import (AnalogPreconditioner,
                          solve_fallback as _solve_fallback,
                          solve_refined as _solve_refined)
from repro.runtime import tracing


def _require_float_dtype(name: str, arr) -> None:
    """Front-door dtype gate: analog programming and dispatch are float
    pipelines; an int/bool/complex input would be silently cast (or crash
    deep inside a packed dispatch), so reject it with the field name.

    Reads the dtype the input carries, so a host rhs is never uploaded to
    the device just to be checked; only Python scalars and lists, which
    carry none, go through `np.asarray`."""
    dtype = getattr(arr, "dtype", None)
    if dtype is None:
        dtype = np.asarray(arr).dtype
    if not jnp.issubdtype(dtype, jnp.floating):
        raise ValueError(
            f"{name} must have a floating dtype, got {dtype}"
            f" - cast explicitly if the int/bool input is intentional")


@dataclasses.dataclass
class MatrixStats:
    """Per-programmed-matrix serving counters."""
    program_time_s: float        # time-to-first-solve cost, paid once
    solve_calls: int = 0         # fused solve invocations
    rhs_served: int = 0          # individual right-hand sides solved
    refined_calls: int = 0       # hybrid analog-seed -> Krylov-refine calls
    refine_iters: int = 0        # total digital Krylov iterations spent


class SolverService:
    """Program-once / solve-many front end over `ProgrammedSolver`.

    `program` pays the full programming cost (partition, Schur complements,
    conductance mapping, operator finalization, arena compilation and the
    first jit) exactly once per matrix; `solve` answers immediately;
    `submit` + `flush` batch queued right-hand sides into one fused
    multi-RHS solve, served by the arena-form single-dispatch executor.
    """

    def __init__(self, cfg: AnalogConfig, stages: Optional[int] = None):
        self.cfg = cfg
        self.stages = stages
        self._solvers: Dict[str, ProgrammedSolver] = {}
        self._dense: Dict[str, jnp.ndarray] = {}
        self._queues: Dict[str, List[jnp.ndarray]] = {}
        self._stats: Dict[str, MatrixStats] = {}
        self._sigs: Dict[str, tuple] = {}
        self._cfgs: Dict[str, AnalogConfig] = {}   # per-matrix cfg override
        # resident packed plans: one (rows, pack) per signature, stacking
        # every programmed tenant of it (rows: matrix id -> instance row,
        # in stack order).  Flushes select their pending tenants by index,
        # so a new pending subset costs nothing and the cache is bounded
        # by the number of signatures.  program/install of a member drops
        # the entry, refresh writes the member's row in place, and a
        # pending tenant the entry lacks rebuilds it.
        self._packs: Dict[tuple, Tuple[Dict[str, int],
                                       PackedArenaPlan]] = {}

    def program(self, matrix_id: str, a: jnp.ndarray,
                key: Optional[jax.Array] = None,
                cfg: Optional[AnalogConfig] = None) -> ProgrammedSolver:
        """Program matrix `a` under `matrix_id` (replaces any previous one).

        Blocks until the first solve is hot (plan built, operators
        finalized, executor compiled for the single-rhs and smallest-batch
        shapes) so subsequent solves run at marginal cost - the measured
        wall time is recorded as the matrix's programming cost.  Refuses to
        replace a matrix that still has queued, unanswered right-hand sides
        (flush first - or `discard_pending` on a failover path that owns
        its own request replay, cf. serve/async_engine.py).

        `cfg` overrides the service config for this matrix only - the
        re-program failover path uses it to turn write-verify / fault
        remapping on for a quarantined matrix without re-bucketing healthy
        tenants.  Per-matrix configs compose with `flush_all` for free:
        the config is part of `plan_signature`, so differently-configured
        tenants simply land in different packing buckets.

        Front-door validation: `a` must be a finite square float matrix.
        A NaN/Inf entry would not fail here - it would poison the Schur
        cascade and come back as NaN *answers*, possibly for co-batched
        tenants sharing a packed dispatch - so it is rejected with a
        ValueError before any state changes.
        """
        if self._queues.get(matrix_id):
            raise RuntimeError(
                f"matrix {matrix_id!r} has {len(self._queues[matrix_id])} "
                f"pending rhs; flush before re-programming")
        _require_float_dtype("matrix", a)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"matrix must be square 2-D, got {a.shape}")
        if not bool(jnp.all(jnp.isfinite(a))):
            raise ValueError(
                f"matrix {matrix_id!r} contains non-finite entries; "
                f"refusing to program (NaN/Inf would poison every solve "
                f"dispatched against it)")
        cfg = cfg if cfg is not None else self.cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        t0 = time.perf_counter()
        solver = ProgrammedSolver.program(a, key, cfg, self.stages)
        # Warm the jitted executor (single-rhs and smallest flush batch) as
        # part of programming time; solve_many pads to powers of two, so
        # each further batch-shape compile happens at most once per
        # doubling regardless of queue length.
        jax.block_until_ready(solver.solve(jnp.zeros((solver.n,),
                                                     dtype=a.dtype)))
        jax.block_until_ready(solver.solve(jnp.zeros((solver.n, 1),
                                                     dtype=a.dtype)))
        self._solvers[matrix_id] = solver
        self._dense[matrix_id] = a   # digital copy for hybrid refinement
        self._queues[matrix_id] = []
        self._stats[matrix_id] = MatrixStats(
            program_time_s=time.perf_counter() - t0)
        self._cfgs[matrix_id] = cfg
        self._sigs[matrix_id] = plan_signature(a.shape[0], self.stages, cfg)
        self._drop_packs(matrix_id)
        return solver

    def install(self, matrix_id: str, solver: ProgrammedSolver,
                a: jnp.ndarray,
                cfg: Optional[AnalogConfig] = None) -> ProgrammedSolver:
        """Register an already-programmed solver (checkpoint restore).

        The durable-recovery counterpart of `program`: the expensive
        pipeline (partition, Schur, conductance mapping, finalize, arena
        compile) was paid earlier - possibly in another process - and the
        solver's plans were restored from a `ProgramStore` checkpoint.
        Install performs the same front-door validation and executor
        warm-up as `program` (the jit caches are global and keyed on
        treedef + shape, so a restored plan of a signature this process
        has seen is already hot) and records the same bookkeeping, with
        `program_time_s` now measuring restore+warm instead of the full
        write-verify programming cost.  Physics validation (the canary
        residual against the original calibration threshold) is the
        caller's job - the service cannot know the original trip.
        """
        if self._queues.get(matrix_id):
            raise RuntimeError(
                f"matrix {matrix_id!r} has {len(self._queues[matrix_id])} "
                f"pending rhs; flush before re-installing")
        _require_float_dtype("matrix", a)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"matrix must be square 2-D, got {a.shape}")
        if a.shape[0] != solver.n:
            raise ValueError(
                f"solver was programmed for n={solver.n}, matrix is "
                f"{a.shape}")
        cfg = cfg if cfg is not None else solver.cfg
        sig = plan_signature(a.shape[0], self.stages, cfg)
        t0 = time.perf_counter()
        jax.block_until_ready(solver.solve(jnp.zeros((solver.n,),
                                                     dtype=a.dtype)))
        jax.block_until_ready(solver.solve(jnp.zeros((solver.n, 1),
                                                     dtype=a.dtype)))
        self._solvers[matrix_id] = solver
        self._dense[matrix_id] = a
        self._queues[matrix_id] = []
        self._stats[matrix_id] = MatrixStats(
            program_time_s=time.perf_counter() - t0)
        self._cfgs[matrix_id] = cfg
        self._sigs[matrix_id] = sig
        self._drop_packs(matrix_id)
        return solver

    def refresh(self, matrix_id: str, solver: ProgrammedSolver) -> None:
        """Swap in a maintained variant of an already-programmed solver.

        The maintenance hot-path: aging re-finalizes and block repair
        splices produce a new `ProgrammedSolver` for the SAME matrix,
        config and plan signature (drift/repair never enter
        `plan_signature`), so queues, stats, sigs and the digital copy
        all stay - only the solver handle is replaced, and its row of the
        resident packed plan is rewritten in place (one small program;
        the other members are not re-stacked).  Pending right-hand
        sides are fine: they are answered by the refreshed (healthier)
        solver at the next flush, which is the whole point of repairing
        in place.
        """
        old = self._solvers[matrix_id]          # unknown ids raise KeyError
        if solver.n != old.n:
            raise ValueError(
                f"refresh for {matrix_id!r} changed n: {old.n} -> "
                f"{solver.n}")
        sig = self._sigs[matrix_id]
        entry = self._packs.get(sig)
        if entry is not None and matrix_id in entry[0]:
            rows, pp = entry
            self._packs[sig] = (rows, replace_packed_instance(
                pp, rows[matrix_id], solver.arena))
        self._solvers[matrix_id] = solver

    def _drop_packs(self, matrix_id: str) -> None:
        """Drop the resident packed plan holding `matrix_id` (its plan,
        or its signature, changed)."""
        self._packs = {sig: entry for sig, entry in self._packs.items()
                       if matrix_id not in entry[0]}

    def solver(self, matrix_id: str) -> ProgrammedSolver:
        return self._solvers[matrix_id]

    def stats(self, matrix_id: str) -> MatrixStats:
        return self._stats[matrix_id]

    def signature(self, matrix_id: str) -> tuple:
        """The matrix's `plan_signature` (the flush_all bucketing key)."""
        return self._sigs[matrix_id]

    def dense(self, matrix_id: str) -> jnp.ndarray:
        """The stored digital copy of the matrix (residual checks, hybrid
        refinement, digital fallback)."""
        return self._dense[matrix_id]

    def matrix_cfg(self, matrix_id: str) -> AnalogConfig:
        """The config this matrix was programmed under (per-matrix
        override aware; the service default when none was given)."""
        return self._cfgs[matrix_id]

    @property
    def matrix_ids(self):
        return tuple(self._solvers)

    def _record(self, matrix_id: str, n_rhs: int, info=None) -> None:
        """The one per-tenant bookkeeping path: every serving entry point
        (solve, solve_refined, flush, flush_all) counts one fused solve
        call of `n_rhs` right-hand sides here, so no path can double-count.
        `info` (a KrylovResult) marks the call as a hybrid refinement and
        adds its digital iteration count."""
        st = self._stats[matrix_id]
        st.solve_calls += 1
        st.rhs_served += n_rhs
        if info is not None:
            st.refined_calls += 1
            st.refine_iters += int(jnp.sum(info.iters))

    def solve(self, matrix_id: str, b: jnp.ndarray) -> jnp.ndarray:
        """Immediate solve of one (n,) rhs or an (n, k) batch."""
        x = self._solvers[matrix_id].solve(b)
        self._record(matrix_id, 1 if b.ndim == 1 else b.shape[1])
        return x

    def solve_refined(self, matrix_id: str, b: jnp.ndarray, *,
                      tol: float = 1e-6, method: str = "cg",
                      maxiter: int = 400, restart: int = 32,
                      use_precond: bool = False) -> jnp.ndarray:
        """Hybrid solve: analog seed from the programmed arrays + digital
        Krylov refinement against the stored digital matrix.

        One fused call per (n,) rhs or (n, k) batch: the programmed solver
        supplies the seed, and `repro.hybrid` polishes to `tol` relative
        residual.  Defaults suit the f32 serving path; program the matrix
        under x64 and pass a tighter tol for full double precision.

        use_precond=False (default) refines seed-only - always convergent
        on the digital side whatever the programming noise.  use_precond=
        True additionally applies the programmed arrays as the Krylov
        preconditioner: much faster when noise x condition is small (see
        TESTING.md), but a strongly perturbed analog inverse can leave the
        SPD cone and stall CG, so it is opt-in for serving.
        """
        x, info = self._refine(matrix_id, b, tol=tol, method=method,
                               maxiter=maxiter, restart=restart,
                               use_precond=use_precond)
        self._record(matrix_id, 1 if b.ndim == 1 else b.shape[1], info)
        return x

    def solve_fallback(self, matrix_id: str, b: jnp.ndarray, *,
                       tol: float = 1e-6, method: str = "cg",
                       maxiter: int = 800, restart: int = 32) -> jnp.ndarray:
        """Digital-only solve against the stored dense matrix (degraded
        mode - no analog seed, no analog preconditioner).

        The bottom of the quarantine -> re-program -> degrade ladder: the
        programmed arrays are not touched at all, so this answers
        correctly however faulted the device is (a broken crossbar can
        emit non-finite seeds that `solve_refined` would propagate into
        the Krylov recurrence).  Counted as a refined call in the stats -
        the digital iteration spend is the metric that matters.
        """
        a = self._dense[matrix_id]
        x, info = _solve_fallback(a, b, method=method, tol=tol,
                                  maxiter=maxiter, restart=restart)
        self._record(matrix_id, 1 if b.ndim == 1 else b.shape[1], info)
        return x

    def _refine(self, matrix_id: str, b: jnp.ndarray, *, tol: float = 1e-6,
                method: str = "cg", maxiter: int = 400, restart: int = 32,
                use_precond: bool = False):
        """Stats-free refine core shared by solve_refined and flush."""
        a = self._dense[matrix_id]
        precond = AnalogPreconditioner.from_solver(self._solvers[matrix_id])
        return _solve_refined(a, b, precond, method=method, tol=tol,
                              maxiter=maxiter, restart=restart,
                              use_precond=use_precond)

    def submit(self, matrix_id: str, b: jnp.ndarray) -> int:
        """Queue one (n,) rhs for the next flush; returns its queue slot.

        Admission copies the rhs to the host: flushes then assemble each
        batch as one numpy stack and pay a single device upload, instead
        of one stacking dispatch per queued column (which dominated the
        packed flush at production queue depths).  Always a *copy*
        (np.array, not asarray), so a caller reusing one buffer across
        submits cannot mutate an already-queued request.
        """
        n = self._solvers[matrix_id].n
        if b.shape != (n,):
            raise ValueError(f"submit takes one ({n},) rhs, got {b.shape}")
        _require_float_dtype("rhs", b)
        host = np.array(b)
        # Finite-ness is checked on the host snapshot we keep anyway (no
        # extra device sync): one NaN rhs admitted here would ride a fused
        # multi-rhs dispatch and - through the shared matmul - poison
        # nothing *numerically* for neighbours, but it would come back as
        # a NaN answer long after the caller that sent it is gone, and in
        # a packed bucket it would trip residual health tripwires for the
        # whole tenant.  Reject at the front door instead.
        if not np.all(np.isfinite(host)):
            raise ValueError(
                f"rhs for {matrix_id!r} contains non-finite entries; "
                f"rejected at admission (nothing was queued)")
        q = self._queues[matrix_id]
        q.append(host)
        return len(q) - 1

    def pending(self, matrix_id: str) -> int:
        return len(self._queues[matrix_id])

    def discard_pending(self, matrix_id: str) -> int:
        """Drop every queued rhs of one matrix; returns how many.

        The failover escape hatch: `program` refuses to replace a matrix
        with a live queue because the *service* would silently lose those
        requests.  A layer that keeps its own authoritative request copies
        (the async engine replays in-flight requests after a re-program)
        discards the service-side copies first, re-programs, and replays.
        """
        k = len(self._queues[matrix_id])
        self._queues[matrix_id] = []
        return k

    def flush(self, matrix_id: str, *, refined: bool = False,
              **refine_kw) -> jnp.ndarray:
        """Solve all queued right-hand sides in one fused call.

        Returns (n, k) solutions, column j answering the j-th submit since
        the last flush; (n, 0) when the queue is empty.  `solve_many` owns
        the power-of-two batch padding (so every caller - not just this
        service - compiles at most one new shape per doubling instead of
        one per distinct queue length); the stacked batch buffer is donated
        to the solve, since the queue is dropped once answered anyway.

        refined=True routes the batch through the fused analog-seed ->
        Krylov-refine path instead of the raw analog solve (the batch is
        padded here with zero columns, which start converged and never
        contribute iterations); `refine_kw` forwards to `solve_refined`
        (tol/method/maxiter/...).
        """
        q = self._queues[matrix_id]
        solver = self._solvers[matrix_id]
        if not q:
            return jnp.zeros((solver.n, 0),
                             dtype=self._dense[matrix_id].dtype)
        k = len(q)
        if refined:
            bs, _ = pad_rhs_pow2(self._stack_queue(matrix_id))
            xs_full, info = self._refine(matrix_id, bs, **refine_kw)
            xs = xs_full[:, :k]
            # only the k real columns count as served (padding columns are
            # zero right-hand sides: they start converged, zero iterations)
            self._record(matrix_id, k, info)
        else:
            xs = self._solve_queue(matrix_id)
            self._record(matrix_id, k)
        self._queues[matrix_id] = []    # only drop requests once answered
        return xs

    def _stack_queue(self, matrix_id: str) -> jnp.ndarray:
        """One tenant's queue as an (n, k) device batch: one host-side
        numpy stack + one upload (the flush assembly policy)."""
        return jnp.asarray(np.stack(self._queues[matrix_id], axis=1))

    def _solve_queue(self, matrix_id: str) -> jnp.ndarray:
        """The one per-matrix raw-solve body (no state mutation), shared
        by `flush` and `flush_all`'s single-tenant/reference fallback so
        the two paths cannot drift."""
        with tracing.span("service.stage"):
            bs = self._stack_queue(matrix_id)
        with tracing.span("service.execute"):
            return self._solvers[matrix_id].solve_many(bs, donate=True)

    def _packed_plan(self, sig: tuple, bucket: List[str]
                     ) -> Tuple[PackedArenaPlan, np.ndarray]:
        """The resident packed plan of `sig` and the bucket's rows in it.

        The resident plan stacks every programmed tenant of the signature,
        whichever are pending, so it is reused across flushes whatever
        subset they hold (the span's `hit`); it is rebuilt only when
        missing (dropped by program/install of a member) or when a
        pending tenant joined the signature after it was built."""
        sp = tracing.span("service.pack")
        if sp:
            sp.attrs["tenants"] = len(bucket)
        with sp:
            cached = self._packs.get(sig)
            hit = cached is not None and all(mid in cached[0]
                                             for mid in bucket)
            if sp:
                sp.attrs["hit"] = int(hit)
            if not hit:
                members = [mid for mid in self._solvers
                           if self._sigs[mid] == sig]
                cached = ({mid: i for i, mid in enumerate(members)},
                          pack_arena_plans([self._solvers[mid].arena
                                            for mid in members]))
                self._packs[sig] = cached
            rows, pp = cached
            return pp, np.asarray([rows[mid] for mid in bucket], np.int32)

    def flush_all(self, matrix_ids=None):
        """Continuous-batching flush: answer every pending rhs of every
        matrix (or of `matrix_ids`) in one fused dispatch per signature
        bucket.

        Tenants are grouped by `plan_signature`; within a bucket, each
        tenant's queued columns stack to (n, k_i), ragged k_i zero-pad to
        the bucket's shared power-of-two width (`pad_rhs_pow2` - padding
        columns are zero right-hand sides and are sliced away before
        return), the bucket's rhs stack to an (M, n, k_pad) batch, and ONE
        `execute_arena_packed_selected` call (buffer donated, like
        `flush`) gathers the bucket's M tenants from the signature's
        resident packed plan by index and answers them all.  Returns
        {matrix_id: (n, k_id) solutions}, column j answering the j-th
        submit since the last flush; ids with empty queues are omitted.
        All answers come back host-resident numpy (the delivery form: one
        device->host transfer per bucket, one small owned copy per tenant
        - so no answer pins the fleet buffer - and per-ticket column
        delivery is a free numpy view) -
        uniformly, including the fallback paths, so the result type never
        depends on how many tenants happened to be pending.
        Single-tenant buckets take the per-matrix `flush` path.
        """
        if matrix_ids is None:
            ids = tuple(self._queues)
        else:
            ids = tuple(dict.fromkeys(matrix_ids))   # dedupe, keep order
            for mid in ids:
                self._queues[mid]   # unknown ids raise KeyError, like solve
        pending = [mid for mid in ids if self._queues.get(mid)]
        sp = tracing.span("service.flush_all")
        if sp:
            ks = [len(self._queues[mid]) for mid in pending]
            sp.attrs.update(tenants=len(pending), rhs=sum(ks),
                            k_pad=1 << (max(ks, default=1) - 1).bit_length())
        with sp:
            return self._flush_pending(pending)

    def _flush_pending(self, pending: List[str]) -> Dict[str, np.ndarray]:
        buckets: Dict[tuple, List[str]] = {}
        for mid in pending:
            buckets.setdefault(self._sigs[mid], []).append(mid)
        # Phase 1 - dispatch every bucket WITHOUT touching service state,
        # so a failure in any bucket (pack error, device OOM, ...) leaves
        # every queue and counter exactly as it was: all-or-nothing.
        staged = []                     # (bucket ids, per-tenant ks, xs)
        for sig, bucket in buckets.items():
            if len(bucket) == 1:
                # single tenant: the same per-matrix solve body `flush`
                # runs, staged like the packed buckets
                (mid,) = bucket
                xs = self._solve_queue(mid)
                with tracing.span("service.fetch"):
                    xs_host = np.asarray(xs)[None]
                staged.append((bucket, [len(self._queues[mid])], xs_host))
                continue
            ks = [len(self._queues[mid]) for mid in bucket]
            k_max = max(ks)
            n = self._solvers[bucket[0]].n
            # one host-side (M, n, k_max) assembly + one device upload:
            # ragged tenants zero-pad to the bucket's widest queue; the
            # dtype promotes over every queued column (np.stack promotes
            # within a tenant), matching what per-matrix flushes would do
            with tracing.span("service.stage"):
                tenant_stacks = [np.stack(self._queues[mid], axis=1)
                                 for mid in bucket]
                stacked = np.zeros(
                    (len(bucket), n, k_max),
                    dtype=np.result_type(*(s.dtype for s in tenant_stacks)))
                for i, cols in enumerate(tenant_stacks):
                    stacked[i, :, :ks[i]] = cols
                bs, _ = pad_rhs_pow2(jnp.asarray(stacked))  # (M, n, k_pad)
            pp, idx = self._packed_plan(sig, bucket)
            with tracing.span("service.execute"):
                xs = _execute_arena_packed_selected_donated(pp, idx, bs)
            # one device->host transfer; per-tenant scatter below is one
            # (n, k_id) copy each, so no tenant's answer pins the whole
            # fleet buffer in memory after delivery
            with tracing.span("service.fetch"):
                staged.append((bucket, ks, np.asarray(xs)))
        # Phase 2 - every dispatch succeeded: commit queues and counters.
        results: Dict[str, np.ndarray] = {}
        with tracing.span("service.scatter"):
            for bucket, ks, xs_host in staged:
                for i, (mid, k) in enumerate(zip(bucket, ks)):
                    results[mid] = xs_host[i, :, :k].copy()
                    self._record(mid, k)
                    self._queues[mid] = []   # only drop once answered
        return results
