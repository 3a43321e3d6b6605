"""Drift-aware self-healing: the maintenance contract (TESTING.md).

Pinned here:

* the simulated `DeviceClock` + traced `drift_t` override age a programmed
  plan without touching its conductance stacks;
* the aging acceptance scenario: under continuous power-law drift the
  scrubbing engine sustains ZERO SLO canary trips and zero deadline
  misses, where the reactive baseline (scrub=False, identical otherwise)
  quarantines repeatedly;
* counter discipline: maintenance probes/repairs never consume dispatch
  indices, so a scripted chaos trace fires at identical dispatch indices
  with heavy scrubbing and with none (the determinism regression);
* chaos `HotBlock` forces a LOCALIZED repair: only the hot array is
  re-programmed, the rest of the plan is left alone;
* `submit` after `stop()` - and after a generic worker crash - raises
  `EngineStoppedError` immediately instead of enqueueing into a dead
  worker; a fully-drained fleet rejects with `NoReplicaAvailableError`
  before any counter moves;
* the fleet staggers repair windows (repair token) and a maintaining
  replica is `degraded`, never quarantined.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig, readout_conductance
from repro.data.matrices import wishart
from repro.runtime import (AcceleratedDrift, ChaosInjector,
                           DispatchException, HotBlock)
from repro.serve import (AsyncSolverEngine, BlockTrend, DeviceClock,
                         EngineStoppedError, MaintenanceConfig,
                         NoReplicaAvailableError, ReplicatedSolverFleet,
                         SolverService)

KEY = jax.random.PRNGKey(9)
N = 16
DRIFT = NonidealConfig(sigma=0.0, drift_nu=0.05)
CFG = AnalogConfig(array_size=8, nonideal=DRIFT)
MCFG = MaintenanceConfig(scrub_blocks_per_cycle=16, block_trip=0.02,
                         repair_batch=16)
RNG = np.random.default_rng(3)


def _matrix():
    return wishart(KEY, N)


def _engine(clock, scrub=True, chaos=None, **kw):
    svc = SolverService(CFG, stages=2)
    kw.setdefault("flush_interval", 0.01)
    kw.setdefault("health_floor", 0.05)
    kw.setdefault("maintenance", MCFG)
    return AsyncSolverEngine(svc, clock=clock, scrub=scrub, chaos=chaos,
                             name=f"eng-{scrub}", **kw)


def _drive(eng, clock, waves=6, per_wave=3, dt=0.6, quiesce=True):
    misses = 0
    for _ in range(waves):
        clock.advance(dt)
        if quiesce:
            assert eng.maintenance_quiesce(60.0)
        futs = [eng.submit("m", RNG.standard_normal(N).astype(np.float32))
                for _ in range(per_wave)]
        eng.flush_now()
        for f in futs:
            misses += f.result(timeout=30).deadline_missed
    return misses


# ---------------------------------------------------------------------------
# units: clock, trend detector, drift override
# ---------------------------------------------------------------------------

def test_device_clock():
    clock = DeviceClock()
    assert clock.now() == 0.0
    assert clock.advance(2.5) == 2.5
    fired = []
    clock.subscribe(lambda: fired.append(clock.now()))
    clock.advance(0.5)
    assert fired == [3.0]
    with pytest.raises(ValueError):
        clock.advance(-1.0)
    clock.unsubscribe(next(iter(clock._subs)))
    clock.advance(1.0)
    assert fired == [3.0]


def test_block_trend_extrapolates():
    tr = BlockTrend(alpha=0.5)
    assert tr.time_to_trip(0.1) == float("inf")
    tr.observe(0.0, 0.00)
    tr.observe(1.0, 0.02)       # slope 0.02 / s
    assert tr.ready(2)
    assert tr.time_to_trip(0.1) == pytest.approx((0.1 - 0.02) / 0.02)
    assert tr.cusum > 0.0
    tr.observe(2.0, 0.08)
    assert tr.time_to_trip(0.1) == pytest.approx(
        (0.1 - 0.08) / tr.slope)
    tr.observe(3.0, 0.2)
    assert tr.time_to_trip(0.1) == 0.0       # already over


def test_drift_override_matches_static_config():
    """The traced drift_t override is the SAME power law as the frozen
    drift_t config constant, and ages below 1 clamp to fresh."""
    g = jnp.abs(jax.random.normal(KEY, (5, 8, 8)))
    ni = NonidealConfig(drift_nu=0.07, drift_t=50.0)
    np.testing.assert_array_equal(
        np.asarray(readout_conductance(g, ni, drift_t=50.0)),
        np.asarray(readout_conductance(g, ni)))
    ni0 = NonidealConfig(drift_nu=0.07)
    np.testing.assert_array_equal(
        np.asarray(readout_conductance(g, ni0, drift_t=0.25)),
        np.asarray(g))
    # per-device age vector broadcasts over the stack axis
    ages = jnp.asarray([1.0, 10.0, 100.0, 1.0, 5.0])
    out = np.asarray(readout_conductance(g, ni0, drift_t=ages))
    for i, t in enumerate(np.asarray(ages)):
        np.testing.assert_allclose(
            out[i], np.asarray(g[i]) * t ** -0.07, rtol=1e-6)


def test_service_refresh_swaps_solver_keeps_bookkeeping():
    svc = SolverService(CFG, stages=2)
    svc.program("m", _matrix(), KEY)
    before = svc.stats("m").program_time_s
    svc.submit("m", np.ones(N, np.float32))
    aged = svc.solver("m").aged(30.0)
    svc.refresh("m", aged)
    assert svc.solver("m") is aged
    assert svc.pending("m") == 1            # queue survives the refresh
    assert svc.stats("m").program_time_s == before
    with pytest.raises(KeyError):
        svc.refresh("nope", aged)


# ---------------------------------------------------------------------------
# the aging acceptance scenario (ISSUE 10)
# ---------------------------------------------------------------------------

def test_self_healing_beats_reactive_baseline():
    """Continuous drift on a simulated clock: the scrubbing engine repairs
    blocks ahead of the canary and sustains zero quarantines and zero
    deadline misses; the reactive baseline quarantines repeatedly."""
    clock = DeviceClock()
    with _engine(clock, scrub=True) as eng:
        eng.program("m", _matrix(), KEY)
        misses = _drive(eng, clock)
        h = eng.health()
    assert h["quarantines"] == 0
    assert misses == 0
    assert h["repairs"] > 0 and h["scrub_probes"] > 0
    assert h["status"]["m"] == "healthy"
    gauges = h["maintenance"]["m"]
    assert gauges["blocks_repaired"] > 0
    assert gauges["scrub_backlog"] == 0.0

    clock2 = DeviceClock()
    with _engine(clock2, scrub=False) as eng2:
        eng2.program("m", _matrix(), KEY)
        _drive(eng2, clock2, quiesce=False)
        h2 = eng2.health()
    assert h2["quarantines"] > 0
    assert h2["scrub_probes"] == 0 and h2["repairs"] == 0


def test_health_exports_drift_gauges():
    clock = DeviceClock()
    with _engine(clock, scrub=True) as eng:
        eng.program("m", _matrix(), KEY)
        clock.advance(0.4)
        assert eng.maintenance_quiesce(60.0)
        h = eng.health()
    g = h["maintenance"]["m"]
    for key in ("age", "worst_dev", "trend_slope", "time_to_trip",
                "scrub_backlog", "pending_repairs", "blocks_repaired"):
        assert key in g
    assert h["scrub_probes"] > 0


# ---------------------------------------------------------------------------
# chaos: determinism + aging events
# ---------------------------------------------------------------------------

def _chaos_run(clock_steps):
    """Fixed traffic against a scripted chaos trace; returns the dispatch
    indices every scripted event fired at, plus the engine's counters."""
    chaos = ChaosInjector([DispatchException(at_dispatch=2)])
    clock = DeviceClock()
    with _engine(clock, scrub=True, chaos=chaos) as eng:
        eng.program("m", _matrix(), KEY)
        for dt in clock_steps:
            clock.advance(dt)
            assert eng.maintenance_quiesce(60.0)
            futs = [eng.submit(
                "m", RNG.standard_normal(N).astype(np.float32))
                for _ in range(2)]
            eng.flush_now()
            for f in futs:
                f.result(timeout=30)
        h = eng.health()
    return [idx for idx, _ in chaos.log], h


def test_probes_never_consume_dispatch_indices():
    """Satellite 1: replaying the same chaos trace with heavy scrubbing
    (clock advancing every wave => probes + repairs between dispatches)
    and with no maintenance at all (clock frozen) fires the scripted
    events at IDENTICAL dispatch indices."""
    fired_heavy, h_heavy = _chaos_run([0.6] * 6)
    fired_idle, h_idle = _chaos_run([0.0] * 6)
    assert fired_heavy == fired_idle
    assert h_heavy["scrub_probes"] > 0       # maintenance really ran
    assert h_idle["scrub_probes"] == 0       # and really didn't
    assert h_heavy["quarantines"] == h_idle["quarantines"] == 0


def test_hot_block_repairs_only_the_hot_array():
    """Chaos HotBlock: one array ages 10x faster; base drift stays under
    block_trip for the whole horizon and the hot block's deviation stays
    under the matrix canary floor, so every repair round touches exactly
    the hot block and nothing ever quarantines."""
    hot = ("mvm", 0, 0)
    chaos = ChaosInjector([HotBlock(at_dispatch=0, matrix_id="m",
                                    block=hot, factor=10.0)])
    clock = DeviceClock()
    with _engine(clock, scrub=True, chaos=chaos) as eng:
        eng.program("m", _matrix(), KEY)
        # first wave delivers the chaos event (dispatch-counter keyed)
        misses = _drive(eng, clock, waves=4, per_wave=2, dt=0.1)
        h = eng.health()
    assert misses == 0
    assert chaos.fired == 1
    assert h["quarantines"] == 0
    assert h["repairs"] > 0
    # every repair re-programmed exactly one array: the hot one
    assert h["blocks_repaired"] == h["repairs"]


class _PackSink:
    """What `tracing.enable` needs of a sink, keeping `service.pack`."""

    def __init__(self):
        self.spans, self.active, self._lock = [], True, threading.Lock()

    def hits(self):
        return [s[3]["hit"] for s in self.spans if s[0] == "service.pack"]


def test_clocked_tenants_keep_their_resident_stack():
    """Several tenants of one signature on a moving clock: every dispatch
    re-finalizes each tracked plan at its new age (`service.refresh`),
    which rewrites the tenant's row of the resident packed stack, so
    only the first packed flush builds the stack and every answer is
    the current aged plan's."""
    from repro.runtime import tracing
    sink = _PackSink()
    clock = DeviceClock()
    ids = ("m0", "m1", "m2")
    tracing.enable(sink)
    try:
        with _engine(clock, scrub=False) as eng:
            for i, mid in enumerate(ids):
                eng.program(mid, wishart(jax.random.fold_in(KEY, i), N),
                            jax.random.fold_in(KEY, 10 + i))
            for _ in range(3):
                clock.advance(0.1)
                bs = {mid: RNG.standard_normal(N).astype(np.float32)
                      for mid in ids}
                futs = {mid: eng.submit(mid, b) for mid, b in bs.items()}
                eng.flush_now()
                for mid, f in futs.items():
                    np.testing.assert_allclose(
                        np.asarray(f.result(timeout=30).x),
                        np.asarray(eng.service.solver(mid).solve(bs[mid])),
                        rtol=1e-5, atol=1e-6)
            refreshes = eng.stats.age_refreshes
    finally:
        tracing.disable()
    assert refreshes >= 3 * len(ids)
    hits = sink.hits()
    assert len(hits) >= 3 and hits[0] == 0 and all(hits[1:])


def test_accelerated_drift_event_fires_once():
    chaos = ChaosInjector([AcceleratedDrift(at_dispatch=0, matrix_id="m",
                                            factor=30.0)])
    assert chaos.aging_due(0) != []
    assert chaos.aging_due(1) == []          # fire-once
    assert chaos.fired == 1


# ---------------------------------------------------------------------------
# satellite 2: no enqueueing into dead workers
# ---------------------------------------------------------------------------

def test_submit_after_stop_raises_immediately():
    svc = SolverService(CFG, stages=2)
    eng = AsyncSolverEngine(svc, flush_interval=0.01)
    eng.program("m", _matrix(), KEY)
    eng.start()
    eng.stop()
    with pytest.raises(EngineStoppedError):
        eng.submit("m", np.ones(N, np.float32))


def test_submit_after_worker_crash_raises_immediately():
    """A generic (non-ReplicaDeath) exception escaping the worker loop
    must mark the engine stopped: later submits raise instead of
    enqueueing futures no thread will ever resolve."""
    svc = SolverService(CFG, stages=2)
    eng = AsyncSolverEngine(svc, flush_interval=0.01)
    eng.program("m", _matrix(), KEY)
    eng._bucket_due = lambda q, now: (_ for _ in ()).throw(
        RuntimeError("scripted worker crash"))
    eng.start()
    eng.submit("m", np.ones(N, np.float32))   # wake the worker -> crash
    deadline = time.monotonic() + 5.0
    while eng.alive and time.monotonic() < deadline:
        time.sleep(0.005)
    assert not eng.alive and eng.crashed
    with pytest.raises(EngineStoppedError):
        eng.submit("m", np.ones(N, np.float32))


def test_drained_fleet_submit_rejects_before_counting():
    fleet = ReplicatedSolverFleet(lambda: SolverService(CFG, stages=2),
                                  n_replicas=1)
    with fleet:
        fleet.program("m", _matrix(), KEY)
        with fleet._lock:
            for r in fleet._replicas:
                r.state = "drained"
        before = (fleet.stats.submitted, fleet._submits)
        with pytest.raises(NoReplicaAvailableError):
            fleet.submit("m", np.ones(N, np.float32))
        assert (fleet.stats.submitted, fleet._submits) == before
        with fleet._lock:
            for r in fleet._replicas:
                r.state = "active"


# ---------------------------------------------------------------------------
# fleet: staggered maintenance windows
# ---------------------------------------------------------------------------

def test_fleet_staggers_repairs_and_never_quarantines():
    clock = DeviceClock()
    fleet = ReplicatedSolverFleet(
        lambda: SolverService(CFG, stages=2), n_replicas=2, clock=clock,
        engine_kw=dict(flush_interval=0.01, health_floor=0.05,
                       maintenance=MCFG))
    with fleet:
        fleet.program("m", _matrix(), KEY)
        for _ in range(5):
            clock.advance(0.6)
            assert fleet.maintenance_quiesce(60.0)
            futs = [fleet.submit(
                "m", RNG.standard_normal(N).astype(np.float32))
                for _ in range(4)]
            fleet.flush_now()
            for f in futs:
                r = f.result(timeout=30)
                assert np.all(np.isfinite(r.x))
        gauges = fleet.maintenance_gauges()
        states = fleet.replica_states()
        stats = fleet.stats
    # repair windows were granted one replica at a time, both replicas
    # got to repair, and nobody was drained or quarantined for it
    assert stats.maintenance_windows > 1
    assert stats.repairs > 0
    assert stats.quarantines == 0 and stats.deaths == 0
    assert all(d["repairs"] > 0 for d in gauges.values())
    assert all(s in ("active", "degraded") for s in states.values())
