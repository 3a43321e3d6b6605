"""A run with the timed path broken underneath must read `correct` false.

Each test drives a whole benchmark run at test size on the CPU (only the
look for a chip is skipped) and plants one fault where the answers are
produced: an answer altered, half of a batch left out, requests refused
for good or failed with an error instead of answered, or (for the sweep) a
call that returns the previous call's answers unchanged.  A refusal that
the client can retry is late, not wrong.
"""
from concurrent.futures import Future

import numpy as np
import pytest
from conftest import run_tiny

import repro.core.blockamc as blockamc
from repro.serve import BackpressureError, router, solver_service

FLEET = ["fig8-fleet.zipf", "fig8-fleet.hot1"]


@pytest.mark.parametrize("workload", FLEET + ["fig8d-mc512.sweep"])
def test_sound_run_is_correct(tiny_root, workload):
    r = run_tiny(tiny_root, workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


def _wrap_flush(monkeypatch, fault):
    orig = solver_service.SolverService.flush_all

    def broken(self, matrix_ids=None):
        return fault(orig(self, matrix_ids))
    monkeypatch.setattr(solver_service.SolverService, "flush_all", broken)


def _alter_one(answers):
    mid = sorted(answers)[0]
    answers[mid] = answers[mid].copy()
    answers[mid][0, 0] += 1.0
    return answers


def _drop_half(answers):
    ids = sorted(answers)
    for mid in ids[:max(1, len(ids) // 2)]:
        answers[mid] = np.zeros_like(answers[mid])
    return answers


@pytest.mark.parametrize("workload", FLEET)
@pytest.mark.parametrize("fault", [_alter_one, _drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_fleet_is_caught(tiny_root, monkeypatch, workload, fault):
    _wrap_flush(monkeypatch, fault)
    r = run_tiny(tiny_root, workload)
    assert not r["correct"], r["checks"]


def _refusing(monkeypatch, refuse):
    """Plant `refuse(matrix_id, calls)` at the fleet's front door: where
    it is true the submit raises BackpressureError."""
    orig = router.ReplicatedSolverFleet.submit
    calls = []

    def submit(self, matrix_id, *args, **kw):
        calls.append(matrix_id)
        if refuse(matrix_id, len(calls)):
            raise BackpressureError("planted refusal", 0.01)
        return orig(self, matrix_id, *args, **kw)
    monkeypatch.setattr(router.ReplicatedSolverFleet, "submit", submit)
    return calls


@pytest.mark.parametrize("workload", FLEET)
def test_refused_requests_are_caught(tiny_root, monkeypatch, workload):
    """Shedding load is not serving it: a fleet that refuses every
    request of one tenant, and answers the rest exactly, must not read
    correct, however often the client sends them again."""
    _refusing(monkeypatch, lambda mid, n: mid == "t0")
    r = run_tiny(tiny_root, workload)
    assert r["failed"] > 0
    assert r["checks"]["unanswered"]["value"] == r["failed"]
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", FLEET)
def test_refusal_is_late_not_wrong(tiny_root, monkeypatch, workload):
    """A refusal that names a retry time is answered on a later send:
    every request is answered, and the run is correct."""
    calls = _refusing(monkeypatch, lambda mid, n: n % 3 == 0)
    r = run_tiny(tiny_root, workload)
    assert len(calls) > r["attempted"] > 0
    assert r["failed"] == 0
    assert r["correct"], r["checks"]


def test_full_server_sees_one_send_per_wait(tiny_root, monkeypatch):
    """While the fleet refuses everything (a third of the window), the
    client holds its backlog and sends one request per wait, not every
    waiting request: about 0.3 s / 10 ms refused sends, then the backlog
    drains in order and every request is answered."""
    import time
    t_full = []

    def refuse(mid, n):
        if not t_full:
            t_full.append(time.perf_counter() + 0.3)
        return t_full[0] <= time.perf_counter() < t_full[0] + 0.3
    calls = _refusing(monkeypatch, refuse)
    r = run_tiny(tiny_root, "fig8-fleet.zipf")
    refused = len(calls) - r["attempted"]
    assert 5 <= refused <= 40, refused
    assert r["failed"] == 0
    assert r["correct"], r["checks"]


def test_late_client_still_sends_every_due_request(tiny_root, monkeypatch):
    """A client that runs late past the close (here one send that takes
    longer than the rest of the window) still sends every request that
    fell due in the window, and each is answered."""
    import time
    orig = router.ReplicatedSolverFleet.submit
    calls = []

    def slow(self, *args, **kw):
        calls.append(None)
        if len(calls) == 30:
            time.sleep(1.0)
        return orig(self, *args, **kw)
    monkeypatch.setattr(router.ReplicatedSolverFleet, "submit", slow)
    r = run_tiny(tiny_root, "fig8-fleet.zipf")
    assert len(calls) >= r["attempted"] > 30
    assert r["failed"] == 0
    assert r["correct"], r["checks"]


def test_errored_requests_are_caught(tiny_root, monkeypatch):
    """A request of one tenant whose answer is a typed error other than a
    refusal settles as errored: the run must not read correct."""
    orig = router.ReplicatedSolverFleet.submit

    def submit(self, matrix_id, *args, **kw):
        if matrix_id != "t0":
            return orig(self, matrix_id, *args, **kw)
        fut = Future()
        fut.set_exception(router.FleetError("planted failure"))
        return fut
    monkeypatch.setattr(router.ReplicatedSolverFleet, "submit", submit)
    r = run_tiny(tiny_root, "fig8-fleet.zipf")
    assert r["checks"]["errored"]["value"] == r["failed"] > 0
    assert not r["correct"], r["checks"]


def test_control_candidate_is_judged(tiny_root):
    """The control's numbers go through the same comparison as the
    program's, and carry its verdict."""
    import time

    from bench import run
    r = run.execute("fig8-fleet.hot1", 2**31 + 5, 1.0, False,
                    root=tiny_root, require_tpu=False,
                    candidates=("program", "control"),
                    t_start=time.perf_counter())
    for c in ("program", "control"):
        assert set(r["candidates"][c]) >= {"max_rel_gap", "errored",
                                           "correct"}
    assert r["candidates"]["program"]["correct"] == r["correct"]


def _sweep_fault(kind):
    orig = blockamc.solve_batched
    first = []

    def broken(*args, **kw):
        x = orig(*args, **kw)
        if kind == "answer_altered":
            return x.at[0, 0].add(1.0)
        if kind == "half_batch_left_out":
            return x.at[: x.shape[0] // 2].set(0.0)
        first.append(x)
        return first[0]                     # state left unchanged
    return broken


@pytest.mark.parametrize("kind", ["answer_altered", "half_batch_left_out",
                                  "stale_answer"])
def test_broken_sweep_is_caught(tiny_root, monkeypatch, kind):
    monkeypatch.setattr(blockamc, "solve_batched", _sweep_fault(kind))
    r = run_tiny(tiny_root, "fig8d-mc512.sweep")
    assert not r["correct"], r["checks"]
