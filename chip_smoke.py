"""Chip smoke: the paper's BlockAMC serving fleet, end to end on a TPU.

    python chip_smoke.py             # one chip: serve, parity, precision
    python chip_smoke.py --chips 4   # four chips: a 4-replica fleet and the
                                     # instance-sharded packed executor

Each phase drives the public entry points at the paper's deployment size
(BlockAMC Fig. 8/9: n=256, two stages, 64x64 arrays, sigma=0.05, 1 Ohm
first-order wire resistance) and raises on the first failed check; no
error is caught.  The script runs in one process and starts no other.  It
refuses every backend but `tpu`; tests/test_chip_smoke.py rehearses the
same phases on the CPU at a tiny size.  The last line of standard output
is one JSON object, {"ok": true, "device": {...}}.

The compile cache is JAX_COMPILATION_CACHE_DIR when that is set, else
<repo>/.jax_cache (`repro.runtime.compile_cache`).  The times printed are
set-up and cold-run times, not metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core import blockamc  # noqa: E402
from repro.core.analog import AnalogConfig  # noqa: E402
from repro.core.nonideal import PAPER_FULL  # noqa: E402
from repro.data.matrices import wishart  # noqa: E402
from repro.runtime.compile_cache import (cache_entries,  # noqa: E402
                                         use_compile_cache)
from repro.serve import ReplicatedSolverFleet, SolverService  # noqa: E402

# The paper deployment (Fig. 8/9 two-stage solver) served to 16 tenants of
# one plan signature, 4 right-hand sides each.
PAPER = dict(n=256, stages=2, array_size=64, tenants=16, rhs=4)
# The precision probe: an ideal (sigma=0) 512^2 two-stage plan on 128^2
# arrays, where the only error left is the digital arithmetic.
PROBE = dict(n=512, stages=2, array_size=128, rhs=8)
# Tenant matrices, programming keys and right-hand sides all come from it.
SEED = 0

# Served answers vs float64 numpy.linalg.solve, paper Eq. 6 (L1/L1)
# relative error.  At sigma=0.05 each 64^2 block is perturbed by
# ||E|| ~ 2 sqrt(64) 0.05 sqrt(2) ~ 1.1 in normalised units, the order of
# its smallest singular value, so the two-stage solver's error is O(1):
# the Fig. 8 Monte Carlo (benchmarks/fig8_twostage.py) reads median 1.56,
# max 2.11 over 8 draws at this configuration; these 64 requests read
# median 1.57, max 2.23 on the CPU and on a TPU v5e alike.  The bound
# catches non-finite or mis-scaled answers; the tight check is the next.
SERVE_ERR_BOUND = 3.0
# Served answers vs the same programmed plan run by the flat reference
# executor in float64 on the host.  The f32 served path sits at 3.5e-7 of
# it on the CPU and 5.1e-7 on a TPU v5e; one bf16-rounded dot anywhere in
# programming or the cascade costs >= 1e-3.
PLAN_ERR_BOUND = 1e-4
# Kernel vs jnp arena path on one packed plan: the fused-arena contract
# (tests/test_fused_arena.py).
PARITY_RTOL, PARITY_ATOL = 2e-4, 2e-5
# Precision probe vs float64 numpy: cond(A) ~ 9 times f32 rounding through
# a two-stage cascade (CPU: 2.3e-7, TPU v5e: 3.3e-7); a single bf16 pass
# gives ~1e-3.
PROBE_ERR_BOUND = 1e-4


def _host():
    """Make data and host references on the CPU device: the same bits as
    the CPU rehearsal, whatever the default backend."""
    return jax.default_device(jax.devices("cpu")[0])


def _l1_rel_err(x, ref):
    """Paper Eq. 6 per column: sum |x - ref| / sum |ref|."""
    return np.abs(x - ref).sum(axis=0) / np.abs(ref).sum(axis=0)


def _l2_rel_err(x, ref):
    return np.linalg.norm(x - ref, axis=0) / np.linalg.norm(ref, axis=0)


def device_info() -> dict:
    """The backend JAX found; refuses anything but a TPU (no CPU fallback)."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(f"no TPU found: JAX backend is {backend!r}")
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_tenants(n: int, count: int, rhs: int, seed: int):
    """`count` Wishart matrices (m = 4n), their programming keys and
    (n, rhs) right-hand sides uniform in [-1, 1], all from `seed`."""
    key = jax.random.PRNGKey(seed)
    with _host():
        keys = jax.random.split(key, 2 * count)
        mats = [np.asarray(wishart(keys[i], n)) for i in range(count)]
    rng = np.random.default_rng(seed)
    bs = [rng.uniform(-1.0, 1.0, (n, rhs)).astype(np.float32)
          for _ in range(count)]
    return mats, np.asarray(keys[count:]), bs


def _flat_reference_f64(solver, b, cfg):
    """The solver's programmed plan run by the flat reference executor in
    float64 on the host: the analog answer without device rounding."""
    fplan = jax.device_get(solver.flat)
    with _host(), jax.enable_x64():
        fp64 = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float64)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
            else jnp.asarray(x), fplan)
        return np.asarray(_execute_flat(fp64, jnp.asarray(b, jnp.float64),
                                        cfg))


_execute_flat = jax.jit(blockamc.execute_flat, static_argnames=("cfg",))


def serve_phase(*, n, stages, array_size, tenants, rhs, replicas=1,
                seed=SEED, timeout_s=600.0) -> dict:
    """Program `tenants` matrices on a `replicas`-replica fleet, answer
    rhs x tenants requests, and check every answer and counter."""
    cfg = AnalogConfig(array_size=array_size, nonideal=PAPER_FULL)
    mats, keys, bs = make_tenants(n, tenants, rhs, seed)
    total = tenants * rhs
    devices = jax.devices()[:replicas]
    # The router's defaults: the one plan signature stays on a replica
    # until it has been routed a full batch, then moves to the next, so
    # with a batch of total / replicas each replica's share fills one
    # size-triggered packed dispatch.  Nothing ages out while queued.
    fleet = ReplicatedSolverFleet(
        lambda: SolverService(cfg, stages), replicas, devices=devices,
        engine_kw=dict(max_batch=-(-total // replicas), max_pending=total,
                       flush_interval=timeout_s))
    ids = [f"t{i}" for i in range(tenants)]
    t0 = time.perf_counter()
    with fleet:
        for mid, a, key in zip(ids, mats, keys):
            fleet.program(mid, a, key=key)
        t_program = time.perf_counter() - t0
        futs = [(i, j, fleet.submit(ids[i], bs[i][:, j]))
                for i in range(tenants) for j in range(rhs)]
        fleet.flush_now()
        results = [(i, j, f.result(timeout=timeout_s)) for i, j, f in futs]
        t_serve = time.perf_counter() - t0 - t_program
        engines = fleet.replica_engines()

    modes = {r.mode for _, _, r in results}
    if modes != {"analog"}:
        raise AssertionError(f"served modes {modes}, expected analog only")
    for name, eng in engines.items():
        st = eng.stats
        bad = {c: getattr(st, c) for c in ("retries", "isolations",
                                            "quarantines", "reprograms",
                                            "degraded", "fallback_rhs")
               if getattr(st, c)}
        if bad:
            raise AssertionError(f"replica {name} recovery counters {bad}")
        if st.dispatches < 1 or st.answered < 1:
            raise AssertionError(f"replica {name} served nothing "
                                 f"({st.dispatches} dispatches)")

    # every replica holds its own copy of every plan on its own device
    placed = {}
    for name, eng in engines.items():
        for mid in ids:
            ap = eng.service.solver(mid).arena
            if not (ap.kernel_ok and ap.program is not None):
                raise AssertionError(f"{mid} on {name}: plan has no "
                                     f"whole-schedule kernel program")
            leaf_devs = {d for leaf in jax.tree_util.tree_leaves(
                (ap.stacks, ap.program)) for d in leaf.devices()}
            if leaf_devs != {eng.device}:
                raise AssertionError(f"{mid} on {name} lives on {leaf_devs}, "
                                     f"replica device is {eng.device}")
        placed[name] = eng.device
    if len(set(placed.values())) != len(placed):
        raise AssertionError(f"replicas share devices: {placed}")

    # each replica's served packed executor, on its own plans and device,
    # is the Pallas megakernel on a TPU
    k_pad = blockamc.pad_rhs_pow2(jnp.zeros((1, rhs)))[0].shape[-1]
    kernel_in_program = {}
    for name, eng in engines.items():
        pp = blockamc.pack_arena_plans(
            [eng.service.solver(mid).arena for mid in ids])
        on_replica = SingleDeviceSharding(eng.device)
        idx = jax.ShapeDtypeStruct((tenants,), jnp.int32,
                                   sharding=on_replica)
        b = jax.ShapeDtypeStruct((tenants, n, k_pad), jnp.float32,
                                 sharding=on_replica)
        text = blockamc._execute_arena_packed_selected_donated.lower(
            pp, idx, b).as_text()
        kernel_in_program[name] = "tpu_custom_call" in text
        if jax.default_backend() == "tpu" and not kernel_in_program[name]:
            raise AssertionError(f"{name}: served packed executor lowered "
                                 f"without the Pallas megakernel")
    # replicas program under the same keys: r0's plans stand for all
    svc = engines["r0"].service

    xs = {(i, j): r.x for i, j, r in results}
    paper_err, plan_err = [], []
    for i, mid in enumerate(ids):
        x = np.stack([xs[(i, j)] for j in range(rhs)], axis=1)
        if not np.all(np.isfinite(x)):
            raise AssertionError(f"{mid}: non-finite answers")
        exact = np.linalg.solve(mats[i].astype(np.float64),
                                bs[i].astype(np.float64))
        paper_err.extend(_l1_rel_err(x, exact))
        plan_ref = _flat_reference_f64(svc.solver(mid), bs[i], cfg)
        plan_err.extend(_l2_rel_err(x, plan_ref))
    paper_err, plan_err = np.array(paper_err), np.array(plan_err)
    if paper_err.max() > SERVE_ERR_BOUND:
        raise AssertionError(f"analog error {paper_err.max():.3g} over "
                             f"{SERVE_ERR_BOUND} vs numpy.linalg.solve")
    if plan_err.max() > PLAN_ERR_BOUND:
        raise AssertionError(f"served answers {plan_err.max():.3g} away from "
                             f"the float64 run of their own plans "
                             f"(bound {PLAN_ERR_BOUND})")
    return {"requests": len(results), "replicas": replicas,
            "devices": [str(d) for d in placed.values()],
            "dispatches": {k: e.stats.dispatches for k, e in engines.items()},
            "answered": {k: e.stats.answered for k, e in engines.items()},
            "kernel_in_program": kernel_in_program,
            "err_vs_numpy_median": float(np.median(paper_err)),
            "err_vs_numpy_max": float(paper_err.max()),
            "err_vs_plan_f64_max": float(plan_err.max()),
            "program_s": t_program, "serve_s": t_serve}


def _packed_fleet(*, n, stages, array_size, tenants, rhs, seed):
    """The serve phase's tenants as one batch-programmed packed plan."""
    cfg = AnalogConfig(array_size=array_size, nonideal=PAPER_FULL)
    mats, keys, bs = make_tenants(n, tenants, rhs, seed)
    pp = blockamc.program_packed(jnp.asarray(np.stack(mats)), keys, cfg,
                                 stages)
    return pp, jnp.asarray(np.stack(bs))


def parity_phase(*, n, stages, array_size, tenants, rhs, seed=SEED) -> dict:
    """Kernel vs jnp path of `execute_arena_packed` on one packed plan."""
    pp, bs = _packed_fleet(n=n, stages=stages, array_size=array_size,
                           tenants=tenants, rhs=rhs, seed=seed)
    xk = np.asarray(blockamc._execute_arena_packed(pp, bs, use_kernel=True))
    xj = np.asarray(blockamc._execute_arena_packed(pp, bs, use_kernel=False))
    np.testing.assert_allclose(xk, xj, rtol=PARITY_RTOL, atol=PARITY_ATOL)
    return {"max_abs_diff": float(np.abs(xk - xj).max()),
            "max_abs": float(np.abs(xj).max())}


def precision_phase(*, n, stages, array_size, rhs, seed=SEED) -> dict:
    """An ideal plan through `ProgrammedSolver.solve_many` vs float64 numpy:
    with no analog error left, this reads the device's f32 arithmetic."""
    (a,), (key,), (b,) = make_tenants(n, 1, rhs, seed)
    solver = blockamc.ProgrammedSolver.program(
        jnp.asarray(a), key, AnalogConfig(array_size=array_size), stages)
    x = np.asarray(solver.solve_many(jnp.asarray(b)), np.float64)
    exact = np.linalg.solve(a.astype(np.float64), b.astype(np.float64))
    err = float(_l2_rel_err(x, exact).max())
    if err > PROBE_ERR_BOUND:
        raise AssertionError(f"ideal-plan error {err:.3g} over "
                             f"{PROBE_ERR_BOUND}: digital f32 precision lost")
    return {"err_vs_numpy_max": err, "bound": PROBE_ERR_BOUND}


def sharded_phase(*, n, stages, array_size, tenants, rhs, seed=SEED) -> dict:
    """`execute_arena_packed_sharded` over every device vs one device."""
    pp, bs = _packed_fleet(n=n, stages=stages, array_size=array_size,
                           tenants=tenants, rhs=rhs, seed=seed)
    one = np.asarray(blockamc._execute_arena_packed(pp, bs))
    xs = blockamc.execute_arena_packed_sharded(pp, bs)
    shards = len(xs.sharding.device_set)
    if shards != jax.device_count():
        raise AssertionError(f"sharded answers on {shards} devices, "
                             f"expected {jax.device_count()}")
    xs = np.asarray(xs)
    if not np.array_equal(xs, one):
        raise AssertionError(f"sharded answers differ from one device by "
                             f"{np.abs(xs - one).max():.3g}")
    return {"shards": shards, "max_abs_diff": 0.0}


def _run(name, fn, **kw):
    t0 = time.perf_counter()
    out = fn(**kw)
    print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s "
          f"(set-up and cold run, not a metric): {json.dumps(out)}",
          flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve, parity and precision phases on one "
                         "chip; 4: the four-chip fleet and sharded phases")
    args = ap.parse_args(argv)
    try:
        info = device_info()
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: {e}")
    if info["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{info['count']} device(s)")
    cache = use_compile_cache(ROOT)
    print(f"device: {info['platform']} {info['kind']} x{info['count']}; "
          f"compile cache {cache} ({cache_entries(cache)} entries)",
          flush=True)
    if args.chips == 1:
        _run("serve", serve_phase, **PAPER)
        _run("parity", parity_phase, **PAPER)
        _run("precision", precision_phase, **PROBE)
    else:
        _run("serve x4", serve_phase, **PAPER, replicas=4)
        _run("sharded x4", sharded_phase, **PAPER)
    print(f"compile cache {cache}: {cache_entries(cache)} entries",
          flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
