"""Replicated serving fleet contract (TESTING.md "Replicated serving").

The contract under test:

* every fleet future resolves - with a `SolveResult` or a typed error -
  through replica stalls, worker deaths and checkpoint damage; never a
  silent hang;
* replicated programming (same key on every replica) makes any replica
  able to answer any request, so a dead replica's in-flight legs replay
  on survivors and healthy tenants see ZERO deadline misses during the
  loss;
* a hedged request turns a stalled replica into one wasted dispatch: the
  duplicate leg on the next-best replica wins the race;
* the lifecycle ladder degraded -> drained -> quarantined -> replaced is
  driven by the health score (gray failure), not just liveness;
* replacement replicas restore programmed state from the `ProgramStore`
  checkpoint and re-validate it against the ORIGINAL canary trip; a
  stale or damaged checkpoint is rejected (`rejected_checkpoints`) and
  recovery falls back to full re-programming - a faulted restore can
  never grade its own homework.

Everything is driven deterministically: chaos events key on dispatch
counters, traffic comes in flush-spaced waves, and the only waits are
bounded polls on fleet counters.
"""
import time

import jax
import numpy as np
import pytest

from repro.checkpoint import ProgramStore
from repro.core.analog import AnalogConfig
from repro.core.blockamc import ProgrammedSolver, plan_signature
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart
from repro.runtime import (ChaosInjector, CheckpointCorruption, ReplicaDeath,
                           ReplicaStall)
from repro.serve import ReplicatedSolverFleet, SolverService

KEY = jax.random.PRNGKey(7)
N = 16
CFG = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.02))
# raw analog answers at sigma=0.02 carry ~0.1-0.2 relative residual;
# replayed/hedged answers come from bit-identical stacks, same bound
ANALOG_RES = 0.8
ENGINE_KW = dict(flush_interval=0.004, max_batch=4)


def _service(sigma=0.02):
    cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=sigma))
    return lambda: SolverService(cfg, stages=1)


def _matrix(i):
    g = jax.random.normal(jax.random.fold_in(KEY, i), (N, N))
    return np.asarray(g @ g.T / N + np.eye(N, dtype=np.float32))


def _resid(a, x, b):
    return float(np.linalg.norm(a @ x - b) / np.linalg.norm(b))


def _wait(cond, timeout=10.0, poll=0.02):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if cond():
            return True
        time.sleep(poll)
    return False


# ---------------------------------------------------------------------------
# basic replicated serving
# ---------------------------------------------------------------------------

def test_fleet_serves_and_spreads(tmp_path):
    """Two replicas, two tenants: every answer is finite and accurate,
    programmed state is persisted, and routing uses both replicas (the
    assignment round-robin spreads distinct signatures)."""
    store = ProgramStore(str(tmp_path))
    mats = {f"m{i}": _matrix(i) for i in range(2)}
    fleet = ReplicatedSolverFleet(_service(), 2, engine_kw=ENGINE_KW,
                                  store=store)
    with fleet:
        for mid, a in mats.items():
            fleet.program(mid, a, jax.random.fold_in(KEY, hash(mid) % 100))
        assert sorted(fleet.matrix_ids) == sorted(mats)
        assert sorted(store.matrix_ids()) == sorted(mats)

        futs = []
        for w in range(3):
            for mid in mats:
                b = np.asarray(jax.random.normal(
                    jax.random.fold_in(KEY, 50 + w), (N,)))
                futs.append((mid, b, fleet.submit(mid, b)))
            fleet.flush_now()
            time.sleep(0.03)
        for mid, b, fut in futs:
            res = fut.result(timeout=10)
            assert _resid(mats[mid], np.asarray(res.x), b) < ANALOG_RES
            assert not res.deadline_missed
    assert fleet.stats.answered == len(futs)
    assert fleet.stats.deaths == 0 and fleet.stats.replays == 0


def test_one_replica_pick_keeps_the_signature():
    """With one replica every pick is that replica: the signature's
    affinity never moves, however many full batches it routes."""
    fleet = ReplicatedSolverFleet(_service(), 1, engine_kw=ENGINE_KW)
    a = _matrix(1)
    with fleet:
        fleet.program("m", a, KEY)
        sig = plan_signature(N, 1, CFG)
        picks = {fleet._pick(sig).name for _ in range(5 * 4)}
        assert picks == {"r0"} and fleet._affinity[sig] == "r0"
        futs = [fleet.submit("m", np.full(N, 1.0 + j, np.float32))
                for j in range(12)]
        fleet.flush_now()
        for fut in futs:
            assert np.all(np.isfinite(fut.result(timeout=10).x))
    assert fleet._affinity == {sig: "r0"}
    assert fleet.stats.affinity_moves == 0
    assert fleet.stats.routed == {"r0": 12}


def test_concurrent_submits_count_every_leg():
    """More submitting threads than cores, a short switch interval: every
    leg routed is counted once, on the replica that answered it.  (The
    burst queues ~48 requests a replica, whose queue term alone would
    pass the default drain score: this test counts, it does not drain.
    Each replica admits the whole burst, so no leg is refused however
    unevenly a loaded host lets the threads run.)"""
    import sys
    import threading
    threads, each = 16, 12
    fleet = ReplicatedSolverFleet(
        _service(), 4, engine_kw=dict(ENGINE_KW, max_pending=threads * each),
        drain_score=10.0)
    futs, lock = [], threading.Lock()

    def client(t):
        for j in range(each):
            fut = fleet.submit("m", np.full(N, 1.0 + t + j, np.float32))
            with lock:
                futs.append(fut)
    with fleet:
        fleet.program("m", _matrix(2), KEY)
        pool = [threading.Thread(target=client, args=(t,))
                for t in range(threads)]
        # the short switch interval only while the clients submit: the
        # answers are then awaited at the normal pace
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for th in pool:
                th.start()
            for th in pool:
                th.join(timeout=120)
        finally:
            sys.setswitchinterval(old)
        assert not any(th.is_alive() for th in pool)
        fleet.flush_now()
        for fut in futs:
            assert np.all(np.isfinite(fut.result(timeout=120).x))
        answered = {name: e.stats.answered
                    for name, e in fleet.replica_engines().items()}
    assert len(futs) == threads * each == fleet.stats.submitted
    assert fleet.stats.routed == answered
    assert sum(answered.values()) == threads * each


def test_fleet_submit_validation():
    fleet = ReplicatedSolverFleet(_service(), 1, engine_kw=ENGINE_KW)
    with pytest.raises(RuntimeError):      # not running yet
        fleet.submit("m", np.zeros(N))
    with fleet:
        fleet.program("m", _matrix(0), KEY)
        with pytest.raises(KeyError):
            fleet.submit("nope", np.zeros(N))


# ---------------------------------------------------------------------------
# hedged requests
# ---------------------------------------------------------------------------

def test_hedged_request_beats_stalled_replica():
    """r0 stalls 0.6s on every dispatch; the hedge leg on r1 answers the
    outer future long before the primary wakes up."""
    chaos = ChaosInjector([ReplicaStall(at_dispatch=0, seconds=0.6,
                                        replica="r0")])
    fleet = ReplicatedSolverFleet(_service(), 2, engine_kw=ENGINE_KW,
                                  chaos=chaos, hedge_delay=0.03)
    a = _matrix(3)
    with fleet:
        fleet.program("m", a, KEY)
        b = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 4), (N,)))
        t0 = time.monotonic()
        fut = fleet.submit("m", b, deadline_s=5.0, hedge=True)
        fleet.flush_now()
        res = fut.result(timeout=10)
        elapsed = time.monotonic() - t0
    assert _resid(a, np.asarray(res.x), b) < ANALOG_RES
    assert not res.deadline_missed
    assert elapsed < 0.45                  # did not wait out the 0.6s stall
    assert fleet.stats.hedges >= 1
    assert fleet.stats.hedge_wins >= 1
    assert chaos.fired >= 1                # the stall really was armed


# ---------------------------------------------------------------------------
# replica death: the acceptance scenario
# ---------------------------------------------------------------------------

def test_replica_death_replay_and_checkpoint_restore(tmp_path):
    """3 replicas, 3 tenants, r0's worker dies mid-traffic: every future
    resolves, the dead replica's in-flight legs replay on survivors with
    zero deadline misses, and the replacement restores all three
    programmed matrices from checkpoint (no re-programming)."""
    store = ProgramStore(str(tmp_path))
    chaos = ChaosInjector([ReplicaDeath(at_dispatch=1, replica="r0")])
    mats = {f"m{i}": _matrix(10 + i) for i in range(3)}
    fleet = ReplicatedSolverFleet(_service(), 3, engine_kw=ENGINE_KW,
                                  store=store, chaos=chaos)
    with fleet:
        for i, (mid, a) in enumerate(mats.items()):
            fleet.program(mid, a, jax.random.fold_in(KEY, 200 + i))

        # Each request is answered before the next is sent, so each is a
        # dispatch of its own, however slow the machine.  The tenants
        # share one signature, whose first `max_batch` (4) picks go to r0,
        # the replica that takes it: the death scripted on r0's dispatch 1
        # fires by construction, with that request in flight.
        futs = []
        for wave in range(4):
            for mid in mats:
                for j in range(3):
                    b = np.asarray(jax.random.normal(
                        jax.random.fold_in(KEY, 17 * wave + j), (N,)))
                    fut = fleet.submit(mid, b, deadline_s=5.0)
                    futs.append((mid, b, fut))
                    fleet.flush_now()
                    res = fut.result(timeout=15)   # NEVER hangs
                    assert np.all(np.isfinite(np.asarray(res.x)))
                    assert _resid(mats[mid], np.asarray(res.x),
                                  b) < ANALOG_RES
                    # healthy tenants: zero misses
                    assert not res.deadline_missed
        assert _wait(lambda: fleet.stats.replacements >= 1)
        # post-recovery the fleet is whole and still serves
        assert set(fleet.replica_states().values()) == {"active"}
        b = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 5), (N,)))
        res = fleet.submit("m0", b).result(timeout=10)
        assert _resid(mats["m0"], np.asarray(res.x), b) < ANALOG_RES

    assert chaos.fired >= 1
    assert fleet.stats.deaths == 1
    assert fleet.stats.replays >= 1        # in-flight replayed on survivors
    assert fleet.stats.replacements == 1
    # durable recovery: all three matrices restored, none re-programmed
    assert fleet.stats.restores == len(mats)
    assert fleet.stats.reprogram_fallbacks == 0
    assert fleet.stats.rejected_checkpoints == 0
    assert fleet.stats.answered == len(futs) + 1


# ---------------------------------------------------------------------------
# checkpoint validation: corrupt + stale must fall back to re-programming
# ---------------------------------------------------------------------------

def _run_death_recovery(store, chaos):
    """Shared scaffold: 2 replicas, 1 tenant, scripted r0 death.

    A generator: yields the running fleet once "m" is programmed and its
    checkpoint saved (so the caller can damage the store), then drives
    12 requests through the death and recovery, asserts the universal
    invariants (every future resolves with an accurate answer; the
    recovered fleet still serves), and yields the stopped fleet for
    stats assertions.

    Each request is answered before the next is sent, so each is a
    dispatch of its own, however slow the machine.  The signature's
    first `max_batch` (4) picks go to r0, the replica that takes it, so
    a death scripted on r0's dispatch 0-3 fires by construction; the
    requests after it go to r1, then to the replacement."""
    a = _matrix(20)
    fleet = ReplicatedSolverFleet(_service(), 2, engine_kw=ENGINE_KW,
                                  store=store, chaos=chaos)
    with fleet:
        fleet.program("m", a, jax.random.fold_in(KEY, 21))
        yield fleet                        # caller damages the store here
        for wave in range(4):
            for j in range(3):
                b = np.asarray(jax.random.normal(
                    jax.random.fold_in(KEY, 31 * wave + j), (N,)))
                fut = fleet.submit("m", b)
                fleet.flush_now()
                res = fut.result(timeout=15)
                assert _resid(a, np.asarray(res.x), b) < ANALOG_RES
        assert _wait(lambda: fleet.stats.replacements >= 1)
        # the recovered fleet still serves correct answers
        b = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 6), (N,)))
        res = fleet.submit("m", b).result(timeout=10)
        assert _resid(a, np.asarray(res.x), b) < ANALOG_RES
    yield fleet


@pytest.mark.parametrize("how", ["values", "truncate"])
def test_corrupted_checkpoint_falls_back_to_reprogram(tmp_path, how):
    """how="truncate" dies at the integrity layer (manifest cross-check);
    how="values" is bytes-consistent and must be caught by the physics
    canary re-run against the ORIGINAL trip.  Both reject the restore
    and re-program from scratch - and recovery still completes."""
    store = ProgramStore(str(tmp_path))
    chaos = ChaosInjector([ReplicaDeath(at_dispatch=1, replica="r0")])
    gen = _run_death_recovery(store, chaos)
    fleet = next(gen)                      # fleet running, "m" programmed
    store.corrupt("m", how=how)
    for fleet in gen:                      # drive to completion
        pass
    assert fleet.stats.deaths == 1
    assert fleet.stats.rejected_checkpoints >= 1
    assert fleet.stats.reprogram_fallbacks >= 1
    assert fleet.stats.restores == 0
    assert len(fleet.stats.reprogram_s) >= 1


def test_stale_checkpoint_rejected(tmp_path):
    """A checkpoint from a different programming epoch (right signature,
    wrong matrix bytes) is identity-rejected before any array loads."""
    store = ProgramStore(str(tmp_path))
    chaos = ChaosInjector([ReplicaDeath(at_dispatch=1, replica="r0")])
    gen = _run_death_recovery(store, chaos)
    fleet = next(gen)
    # overwrite with a same-signature checkpoint of a DIFFERENT matrix
    other_a = _matrix(99)
    other = ProgrammedSolver.program(
        np.asarray(other_a, dtype=np.float32),
        jax.random.fold_in(KEY, 98), CFG, stages=1)
    store.save("m", other, other_a, jax.random.fold_in(KEY, 98),
               plan_signature(N, 1, CFG))
    for fleet in gen:
        pass
    assert fleet.stats.rejected_checkpoints >= 1
    assert fleet.stats.reprogram_fallbacks >= 1
    assert fleet.stats.restores == 0


def test_chaos_scripted_checkpoint_corruption(tmp_path):
    """The fleet applies `CheckpointCorruption` events from the chaos
    script (keyed on its submit counter), and the damaged checkpoint is
    then rejected on restore like any other corruption."""
    store = ProgramStore(str(tmp_path))
    chaos = ChaosInjector([
        CheckpointCorruption(at_dispatch=1, matrix_id="m", how="values"),
        ReplicaDeath(at_dispatch=2, replica="r0"),
    ])
    gen = _run_death_recovery(store, chaos)
    next(gen)
    for fleet in gen:
        pass
    corrupt_fired = [e for _, e in chaos.log
                     if isinstance(e, CheckpointCorruption)]
    assert len(corrupt_fired) == 1         # fired exactly once
    assert fleet.stats.rejected_checkpoints >= 1
    assert fleet.stats.reprogram_fallbacks >= 1


# ---------------------------------------------------------------------------
# lifecycle ladder: gray failure drains through the score, not liveness
# ---------------------------------------------------------------------------

def test_gray_failure_drains_quarantines_replaces(tmp_path):
    """A stalled-but-alive replica misses a deadline; with alpha=1 the
    miss EWMA saturates and the ladder runs degraded -> drained ->
    quarantined -> replaced while the worker is still technically alive.
    The replacement restores from checkpoint."""
    store = ProgramStore(str(tmp_path))
    chaos = ChaosInjector([ReplicaStall(at_dispatch=0, seconds=0.15,
                                        replica="r0")])
    a = _matrix(30)
    fleet = ReplicatedSolverFleet(_service(), 2, engine_kw=ENGINE_KW,
                                  store=store, chaos=chaos,
                                  ewma_alpha=1.0, drain_grace=0.05)
    with fleet:
        fleet.program("m", a, jax.random.fold_in(KEY, 31))
        b = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 32), (N,)))
        fut = fleet.submit("m", b, deadline_s=0.02)   # lands on r0
        fleet.flush_now()
        res = fut.result(timeout=10)       # answered late, not dropped
        assert res.deadline_missed
        assert _wait(lambda: fleet.stats.replacements >= 1)
        assert set(fleet.replica_states().values()) == {"active"}
    assert fleet.stats.deaths == 0         # the worker never died
    assert fleet.stats.drains >= 1
    assert fleet.stats.quarantines >= 1
    assert fleet.stats.replacements >= 1
    assert fleet.stats.restores >= 1
