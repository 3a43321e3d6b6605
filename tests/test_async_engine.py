"""Async engine serving-robustness contract (TESTING.md).

The contract under test:

* every `submit` future resolves - to a `SolveResult` or a *typed* error
  (`DeadlineExceededError`, `EngineStoppedError`, `BackpressureError` at
  admission) - never a silent hang;
* a request whose deadline passes while queued is shed before compute;
  one answered late carries `deadline_missed=True`;
* a full bucket rejects with `BackpressureError` (backpressure, never a
  silent drop);
* the failover ladder: canary-tripped matrices quarantine, re-program
  with a fresh key, replay their in-flight requests; when health cannot
  be restored they degrade to the digital fallback with `mode="digital"`
  in every answer's metadata - and healthy co-batched tenants are never
  dragged into any of it;
* the whole ladder is exercised *deterministically* through
  `runtime.chaos.ChaosInjector` (dispatch-counter keyed, no wall-clock).

The 16-tenant scenario at the bottom is the PR's acceptance criterion
verbatim: injected stuck-at faults plus one scripted dispatch exception,
zero deadline misses among healthy tenants, quarantine + re-program of
the faulted matrix within one flush interval, every future resolved.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import random_rhs, wishart
from repro.runtime import (ChaosInjector, DeviceFault, DispatchException,
                           DispatchLatency)
from repro.serve import (AsyncSolverEngine, BackpressureError,
                         DeadlineExceededError, EngineStoppedError,
                         SolverService)

KEY = jax.random.PRNGKey(5)
N = 16
CFG = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.02))
# severe enough that no re-program key can pass the canary by luck
SEVERE = NonidealConfig(sigma=0.02, p_stuck_off=0.6, g_stuck_off=0.0)
# raw analog answers at sigma=0.02 carry ~0.1-0.2 relative residual; the
# engine health gate is calibrated against that, tests assert below 0.6
ANALOG_RES = 0.6


def _service():
    return SolverService(CFG, stages=1)


def _program(eng, mids):
    for i, mid in enumerate(mids):
        a = wishart(jax.random.fold_in(KEY, i), N)
        eng.program(mid, a, jax.random.fold_in(KEY, 100 + i))


def _rhs(i):
    return random_rhs(jax.random.fold_in(KEY, 1000 + i), N)


def _residual(svc, r, b):
    a = np.asarray(svc.dense(r.matrix_id))
    return float(np.linalg.norm(a @ r.x - np.asarray(b))
                 / np.linalg.norm(np.asarray(b)))


# ------------------------------ happy path --------------------------------

def test_happy_path_all_analog():
    svc = _service()
    eng = AsyncSolverEngine(svc, max_batch=4, flush_interval=0.02)
    _program(eng, ["m0", "m1"])
    with eng:
        subs = [("m%d" % (i % 2), _rhs(i)) for i in range(8)]
        futs = [(mid, b, eng.submit(mid, b, deadline_s=30.0))
                for mid, b in subs]
        for mid, b, f in futs:
            r = f.result(timeout=60)
            assert r.matrix_id == mid
            assert r.mode == "analog" and r.health == "healthy"
            assert not r.deadline_missed
            assert r.latency_s >= 0.0 and r.attempts >= 1
            assert _residual(svc, r, b) < ANALOG_RES
    assert eng.stats.answered == 8 and eng.stats.submitted == 8
    assert eng.stats.deadline_misses == 0
    assert eng.stats.quarantines == 0
    assert eng.pending() == 0


def test_program_after_start_routes_through_worker():
    eng = AsyncSolverEngine(_service(), max_batch=2, flush_interval=0.02)
    with eng:
        _program(eng, ["late"])       # worker-thread handoff, blocks til hot
        b = _rhs(0)
        r = eng.submit("late", b).result(timeout=60)
        assert r.mode == "analog"


def test_flush_now_forces_early_dispatch():
    eng = AsyncSolverEngine(_service(), max_batch=64, flush_interval=60.0)
    _program(eng, ["m0"])
    with eng:
        f = eng.submit("m0", _rhs(0))
        assert not f.done()
        eng.flush_now()
        f.result(timeout=60)          # without the flush this would sit 60s


@pytest.mark.parametrize("full", ["base", "override"])
def test_size_trigger_fires_one_signature_bucket(full):
    """A signature bucket dispatches the moment it holds max_batch rhs
    while a bucket of a second signature (a per-matrix cfg override)
    keeps filling; the stragglers answer on demand, each with its own
    tenant's numbers."""
    svc = _service()
    eng = AsyncSolverEngine(svc, max_batch=4, flush_interval=60.0)
    _program(eng, ["b0", "b1"])
    eng.program("o0", wishart(jax.random.fold_in(KEY, 2), N),
                jax.random.fold_in(KEY, 102),
                cfg=CFG.with_(nonideal=NonidealConfig(sigma=0.03)))
    filled, waiting = ((["b0", "b1"], ["o0"]) if full == "base"
                       else (["o0"], ["b0", "b1"]))
    with eng:
        stay = [(mid, _rhs(10 + i)) for i, mid in enumerate(waiting)]
        stay = [(mid, b, eng.submit(mid, b)) for mid, b in stay]
        fire = [(filled[i % len(filled)], _rhs(i)) for i in range(4)]
        fire = [(mid, b, eng.submit(mid, b)) for mid, b in fire]
        for mid, b, f in fire:
            f.result(timeout=60)
        assert not any(f.done() for _, _, f in stay)
        assert eng.pending() == len(stay)
        eng.flush_now()
        for mid, b, f in stay:
            f.result(timeout=60)
    for mid, b, f in fire + stay:
        r = f.result()
        assert r.matrix_id == mid and r.mode == "analog"
        np.testing.assert_allclose(
            r.x, np.asarray(svc.solver(mid).solve(jnp.asarray(b))),
            rtol=1e-5, atol=1e-6)
    assert eng.stats.answered == 4 + len(stay)


# --------------------------- deadlines / SLOs -----------------------------

def test_expired_request_is_shed_with_typed_error():
    eng = AsyncSolverEngine(_service(), max_batch=64, flush_interval=60.0)
    _program(eng, ["m0"])
    with eng:
        f = eng.submit("m0", _rhs(0), deadline_s=-1.0)   # already dead
        with pytest.raises(DeadlineExceededError):
            f.result(timeout=60)
    assert eng.stats.expired == 1
    assert eng.stats.deadline_misses == 1
    assert eng.stats.answered == 0     # shed before compute


def test_late_answer_carries_deadline_missed():
    chaos = ChaosInjector([DispatchLatency(at_dispatch=0, seconds=0.4)])
    eng = AsyncSolverEngine(_service(), max_batch=1, flush_interval=0.01,
                            deadline_margin=0.0, chaos=chaos)
    _program(eng, ["m0"])
    with eng:
        # alive at dispatch time (0.2s out), but the scripted straggler
        # makes the answer land past it
        r = eng.submit("m0", _rhs(0), deadline_s=0.2).result(timeout=60)
    assert r.deadline_missed
    assert eng.stats.deadline_misses == 1 and eng.stats.expired == 0
    assert chaos.fired == 1


# ------------------------------ backpressure ------------------------------

def test_backpressure_rejects_with_retry_after():
    eng = AsyncSolverEngine(_service(), max_batch=64, flush_interval=60.0,
                            max_pending=4)
    _program(eng, ["m0"])
    with eng:
        futs = [eng.submit("m0", _rhs(i)) for i in range(4)]
        with pytest.raises(BackpressureError) as ei:
            eng.submit("m0", _rhs(99))
        assert ei.value.retry_after_s > 0.0
        assert eng.stats.rejected == 1
        # the admitted four still answer (stop drains)
    for f in futs:
        assert f.result(timeout=60).mode == "analog"
    assert eng.stats.answered == 4


# --------------------------- admission validation -------------------------

def test_submit_validation_is_front_door():
    eng = AsyncSolverEngine(_service(), max_batch=64, flush_interval=60.0)
    _program(eng, ["m0"])
    with pytest.raises(EngineStoppedError):
        eng.submit("m0", _rhs(0))               # not started yet
    with eng:
        with pytest.raises(KeyError):
            eng.submit("nope", _rhs(0))
        with pytest.raises(ValueError):
            eng.submit("m0", jnp.zeros((N, 2)))          # wrong shape
        with pytest.raises(ValueError):
            eng.submit("m0", np.arange(N))               # int dtype
        bad = np.ones(N)
        bad[3] = np.nan
        with pytest.raises(ValueError):
            eng.submit("m0", bad)                        # non-finite
        assert eng.pending() == 0 and eng.stats.submitted == 0


# ------------------------------ stop semantics ----------------------------

def test_stop_without_drain_voids_futures_typed():
    eng = AsyncSolverEngine(_service(), max_batch=64, flush_interval=60.0)
    _program(eng, ["m0"])
    eng.start()
    futs = [eng.submit("m0", _rhs(i)) for i in range(3)]
    eng.stop(drain=False, timeout=30)
    for f in futs:
        with pytest.raises(EngineStoppedError):
            f.result(timeout=60)
    with pytest.raises(EngineStoppedError):
        eng.submit("m0", _rhs(9))                # post-stop admission


def test_stop_with_drain_answers_leftovers():
    eng = AsyncSolverEngine(_service(), max_batch=64, flush_interval=60.0)
    _program(eng, ["m0"])
    eng.start()
    futs = [eng.submit("m0", _rhs(i)) for i in range(3)]
    eng.stop(drain=True, timeout=60)
    assert all(f.result(timeout=60).mode == "analog" for f in futs)


# ------------------------- retry / isolation paths ------------------------

def test_scripted_exception_absorbed_by_retry_ladder():
    chaos = ChaosInjector([DispatchException(at_dispatch=0)])
    eng = AsyncSolverEngine(_service(), max_batch=2, flush_interval=0.02,
                            retries=2, backoff=0.0, chaos=chaos)
    _program(eng, ["m0"])
    with eng:
        futs = [eng.submit("m0", _rhs(i)) for i in range(2)]
        for f in futs:
            assert f.result(timeout=60).mode == "analog"
    assert eng.stats.retries == 1
    assert eng.stats.isolations == 0             # retry fixed it in-pack
    assert chaos.fired == 1


def test_packed_failure_falls_back_to_isolation():
    # retries=0: the one scripted failure exhausts the packed ladder, the
    # engine isolates per matrix and both tenants still answer analog
    chaos = ChaosInjector([DispatchException(at_dispatch=0)])
    eng = AsyncSolverEngine(_service(), max_batch=2, flush_interval=0.02,
                            retries=0, chaos=chaos)
    _program(eng, ["m0", "m1"])
    with eng:
        fa = eng.submit("m0", _rhs(0))
        fb = eng.submit("m1", _rhs(1))
        assert fa.result(timeout=60).mode == "analog"
        assert fb.result(timeout=60).mode == "analog"
    assert eng.stats.isolations == 1
    assert eng.stats.quarantines == 0


# --------------------- quarantine / re-program / degrade ------------------

def test_device_fault_quarantines_reprograms_and_replays():
    chaos = ChaosInjector([
        DeviceFault(at_dispatch=1, matrix_id="m0", nonideal=SEVERE)])
    svc = _service()
    eng = AsyncSolverEngine(svc, max_batch=4, flush_interval=0.05,
                            chaos=chaos)
    _program(eng, ["m0", "m1"])
    with eng:
        # dispatch 0: healthy round
        r0 = [eng.submit(m, _rhs(i), deadline_s=60.0)
              for i, m in enumerate(["m0", "m0", "m1", "m1"])]
        for f in r0:
            assert f.result(timeout=120).reprograms == 0
        # dispatch 1: the fault lands on m0; canary trips; replay answers
        subs = [("m0", _rhs(10)), ("m0", _rhs(11)),
                ("m1", _rhs(12)), ("m1", _rhs(13))]
        r1 = [(m, b, eng.submit(m, b, deadline_s=120.0)) for m, b in subs]
        for m, b, f in r1:
            r = f.result(timeout=120)
            assert r.mode == "analog" and not r.deadline_missed
            assert r.reprograms == (1 if m == "m0" else 0)
            assert _residual(svc, r, b) < ANALOG_RES
    assert eng.stats.quarantines == 1
    assert eng.stats.reprograms == 1
    assert eng.stats.replays == 2                # m0's withheld pair
    assert eng.stats.degraded == 0
    assert len(eng.stats.recovery_s) == 1
    assert eng.matrix_status("m0") == "healthy"


def test_persistent_fault_degrades_to_digital_fallback():
    chaos = ChaosInjector([
        DeviceFault(at_dispatch=0, matrix_id="p0", nonideal=SEVERE,
                    persistent=True)])
    svc = _service()
    eng = AsyncSolverEngine(svc, max_batch=2, flush_interval=0.05,
                            max_reprograms=2, chaos=chaos)
    _program(eng, ["p0"])
    with eng:
        futs = [(b, eng.submit("p0", b)) for b in [_rhs(0), _rhs(1)]]
        for b, f in futs:
            r = f.result(timeout=120)
            assert r.mode == "digital" and r.health == "degraded"
            assert r.reprograms == 2
            # the digital fallback never touches the faulted arrays: tight
            assert _residual(svc, r, b) < 1e-4
        # second round: stays on the digital path, no re-quarantine churn
        f2 = [eng.submit("p0", _rhs(10 + i)) for i in range(2)]
        assert all(f.result(timeout=120).mode == "digital" for f in f2)
    assert eng.stats.quarantines == 1            # quarantined exactly once
    assert eng.stats.degraded == 1
    assert eng.stats.fallback_rhs == 4
    assert eng.matrix_status("p0") == "degraded"


def test_chaos_schedule_is_deterministic():
    """Same scripted schedule, same submissions -> identical firing log."""
    logs = []
    for _ in range(2):
        chaos = ChaosInjector([
            DeviceFault(at_dispatch=1, matrix_id="m0", nonideal=SEVERE),
            DispatchException(at_dispatch=2)])
        eng = AsyncSolverEngine(_service(), max_batch=2, flush_interval=5.0,
                                backoff=0.0, chaos=chaos)
        _program(eng, ["m0"])
        with eng:
            for rnd in range(2):
                fs = [eng.submit("m0", _rhs(10 * rnd + i)) for i in range(2)]
                for f in fs:
                    f.result(timeout=120)
        logs.append([(idx, type(ev).__name__) for idx, ev in chaos.log])
    assert logs[0] == logs[1]
    assert logs[0] == [(1, "DeviceFault"), (2, "DispatchException")]


# ----------------------- the acceptance scenario --------------------------

def test_sixteen_tenants_chaos_acceptance():
    """ISSUE acceptance: stuck-at faults + one scripted dispatch exception
    at 16 tenants -> zero deadline misses among healthy tenants, the
    faulted matrix quarantined and re-programmed within one flush
    interval, and every future resolves."""
    m = 16
    mids = ["t%02d" % i for i in range(m)]
    flush_interval = 5.0          # flushes are size-triggered (max_batch=m)
    chaos = ChaosInjector([
        DeviceFault(at_dispatch=1, matrix_id="t00", nonideal=SEVERE),
        DispatchException(at_dispatch=2)])
    svc = _service()
    eng = AsyncSolverEngine(svc, max_batch=m, flush_interval=flush_interval,
                            max_pending=4 * m, retries=2, backoff=0.0,
                            chaos=chaos)
    _program(eng, mids)
    with eng:
        # round 1 - dispatch 0, everyone healthy
        r1 = [(mid, eng.submit(mid, _rhs(i), deadline_s=120.0))
              for i, mid in enumerate(mids)]
        for mid, f in r1:
            assert f.result(timeout=240).mode == "analog"
        # round 2 - the fault lands on t00 before dispatch 1; the scripted
        # exception hits t00's replay (dispatch 2) for good measure
        r2 = [(mid, eng.submit(mid, _rhs(100 + i), deadline_s=120.0))
              for i, mid in enumerate(mids)]
        results = {mid: f.result(timeout=240) for mid, f in r2}   # all resolve
    healthy = [results[mid] for mid in mids if mid != "t00"]
    assert all(r.mode == "analog" and not r.deadline_missed
               for r in healthy)
    assert all(r.reprograms == 0 for r in healthy)
    faulted = results["t00"]
    assert not faulted.deadline_missed
    assert faulted.mode == "analog" and faulted.reprograms >= 1
    assert eng.stats.deadline_misses == 0        # zero, healthy or not
    assert eng.stats.quarantines == 1
    assert eng.matrix_status("t00") == "healthy"
    # "within one flush interval": recovery (quarantine -> re-program ->
    # healthy) completed in less wall time than the engine's flush period
    assert len(eng.stats.recovery_s) == 1
    assert eng.stats.recovery_s[0] < flush_interval
    assert [(i, type(e).__name__) for i, e in chaos.log] == [
        (1, "DeviceFault"), (2, "DispatchException")]


# ------------------------------ thread stress -----------------------------

def test_thread_stress_concurrent_submitters():
    """Concurrent submitters racing the worker: every future resolves
    within the timeout (a deadlock fails loudly here, not by hanging CI
    - `.result(timeout=...)` raises and `stop(timeout=...)` raises)."""
    eng = AsyncSolverEngine(_service(), max_batch=8, flush_interval=0.005,
                            max_pending=256)
    _program(eng, ["s0", "s1"])
    n_threads, per_thread = 4, 12
    results, errors = [], []
    lock = threading.Lock()

    def submitter(t):
        futs = []
        for i in range(per_thread):
            mid = "s%d" % ((t + i) % 2)
            while True:
                try:
                    futs.append(eng.submit(mid, _rhs(100 * t + i)))
                    break
                except BackpressureError as e:
                    time.sleep(min(e.retry_after_s, 0.05))
        for f in futs:
            try:
                r = f.result(timeout=120)
                with lock:
                    results.append(r)
            except Exception as e:                      # noqa: BLE001
                with lock:
                    errors.append(e)

    eng.start()
    try:
        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=240)
            assert not th.is_alive(), "submitter thread hung"
    finally:
        eng.stop(drain=True, timeout=60)   # raises on worker deadlock
    assert not errors
    assert len(results) == n_threads * per_thread
    assert all(r.mode == "analog" for r in results)
    assert eng.stats.answered == n_threads * per_thread
