"""The fleet configuration's router settings reach the fleet, and with them
a full queue alone never drains the fleet's only replica (a drain takes
the fleet offline while it re-programs, refusing every request)."""
import inspect
import json
import os

import pytest

from bench import registry
from bench.spans import Recorder
from repro.serve import AsyncSolverEngine, ReplicatedSolverFleet

from conftest import ROOT


def _defaults(fn):
    return {k: p.default for k, p in inspect.signature(fn).parameters.items()}


def _real_config():
    with open(os.path.join(ROOT, "bench", "configs", "fig8-fleet.json")) as f:
        return json.load(f)


def _fleet(root, router):
    bm = registry.load_benchmark(root)
    cfg = registry.load_config(bm, "fig8-fleet", root)
    cfg["router"] = router
    mix = registry.load_traffic("zipf", root)
    run = registry.load_driver("fleet", root).Run(cfg, mix, 2**32 + 5,
                                                   Recorder())
    run.setup()
    return run


@pytest.mark.parametrize("router,drained", [(None, False), ({}, True)],
                         ids=["configured", "router_defaults"])
def test_full_queue_alone_never_drains(tiny_root, monkeypatch, router,
                                       drained):
    """Plant the deepest queue the real configuration can hold (the
    engine's max_pending, in units of its max_batch) beside a healthy
    canary: under the configuration's router the replica stays routable,
    under the router's defaults it is drained."""
    real = _real_config()
    eng_kw = dict(_defaults(AsyncSolverEngine.__init__), **real["engine"])
    run = _fleet(tiny_root, real["router"] if router is None else router)
    try:
        fleet = run.fleet
        (replica,) = fleet._replicas
        orig = replica.engine.health_snapshot
        scale = eng_kw["max_pending"] / eng_kw["max_batch"]

        def deep_queue():
            snap = orig()
            return dict(snap, queue_depth=round(
                scale * max(1, replica.engine.max_batch)))
        monkeypatch.setattr(replica.engine, "health_snapshot", deep_queue)
        for _ in range(20):
            fleet.review()
        assert (replica.state in ("drained", "quarantined")
                or fleet.stats.drains > 0) == drained, replica.state
    finally:
        run.release()


def test_configured_drain_score_reaches_the_fleet(tiny_root):
    real = _real_config()
    run = _fleet(tiny_root, real["router"])
    try:
        assert run.fleet.drain_score == real["router"]["drain_score"]
        default = _defaults(ReplicatedSolverFleet.__init__)["drain_score"]
        # the queue's share of the score, at its deepest, sits under the
        # configured drain and over the router's default
        eng_kw = dict(_defaults(AsyncSolverEngine.__init__), **real["engine"])
        queue = 0.25 * eng_kw["max_pending"] / eng_kw["max_batch"]
        assert default <= queue < run.fleet.drain_score - 0.5
    finally:
        run.release()
