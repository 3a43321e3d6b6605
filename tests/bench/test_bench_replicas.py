"""The four-replica cell: its reader of the replicas' spread, its entries,
and a run at test size on four host devices."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest
from conftest import ROOT, make_tiny_root

from bench import registry
from bench.registry import metric_reader

CELL = "fig8-fleet-x4.zipf640"


def _four_devices(work, outside=0):
    """A trace whose device i ran one executor program inside the window
    per entry of work[i] (its duration), plus another program and
    `outside` executors after the window."""
    devices = {}
    for i, durs in enumerate(work):
        mods = [(f"jit_execute_arena_packed_selected({j})", 2000 + 100 * j,
                 d) for j, d in enumerate(durs)]
        mods += [("jit_pad(1)", 1500, 10),
                 ("jit_execute_arena(2)", 20000, 50)][:1 + outside]
        devices[i] = {"ops": [], "modules": mods}
    return {"devices": devices,
            "spans": [("bench.window", 1000, 10000, {})]}


@pytest.mark.parametrize("work, share", [
    ([[50] * 4] * 4, 25.0),
    ([[50] * 6, [50] * 2, [50] * 2, []], 60.0),
    ([[50] * 5, [], [], []], 100.0),
    # as many programs on every device, twice the device time on one:
    # the share is of device work, not of programs
    ([[80, 80], [40, 40], [40, 40], [40, 40]], 40.0),
])
def test_replica_max_share_on_a_synthetic_trace(work, share):
    read = metric_reader("replica_max_share.x4").read
    for outside in (0, 1):
        ctx = SimpleNamespace(trace=_four_devices(work, outside))
        assert read(ctx) == pytest.approx(share)


def test_replica_max_share_reads_nothing_without_executors():
    read = metric_reader("replica_max_share.x4").read
    assert read(SimpleNamespace(trace=None)) is None
    assert read(SimpleNamespace(trace=_four_devices([[], [], [], []]))) is None


def test_the_four_replica_cell_and_its_metrics():
    bm = registry.load_benchmark()
    cell = registry.find_cell(bm, CELL)
    assert cell["chips"] == 4
    cfg = registry.load_config(bm, cell["config"])
    assert cfg["replicas"] == 4
    one = registry.load_config(bm, "fig8-fleet")
    assert {k: v for k, v in cfg.items()
            if k not in ("name", "source", "replicas", "engine",
                         "deployment", "guarantees", "assumed",
                         "reduced")} == {
        k: v for k, v in one.items()
        if k not in ("name", "source", "replicas", "engine", "guarantees",
                     "assumed", "reduced")}
    # each replica admits the one batch it is filling
    assert cfg["engine"] == {"max_pending": 8} and one["engine"] == {}
    mix = registry.load_traffic(cell["traffic"])
    assert (mix["loop"], mix["rate_per_s"], mix["tenant_dist"]) == (
        "open", 640, "zipf")
    assert [m["name"] for m in registry.end_to_end_metrics(bm, CELL)] == [
        "p50_ms", "setup_s"]
    names = [m["name"] for m in registry.per_layer_metrics(bm, CELL)]
    assert sorted(names) == sorted(
        f"{m}.x4" for m in ("replica_max_share", "dispatch_rhs", "flush_ms",
                            "compiles", "idle_pct", "latency_p95"))
    for name in names:
        assert callable(metric_reader(name).read)


_RUN = r"""
import json, sys, time
sys.path[:0] = [sys.argv[2] + "/src", sys.argv[2]]
from bench import run
out = [run.execute(%r, seed, 1.0, traced, root=sys.argv[1],
                   require_tpu=False, t_start=time.perf_counter())
       for seed, traced in ((2**31 + 11, False), (2**33 + 5, True))]
print(json.dumps(out))
"""


def test_tiny_four_replica_run(tmp_path):
    """The cell at test size (n = 32, 3 tenants) on four host devices: its
    answers pass the check, the untraced run reports p50 and set-up, the
    traced run the host and counter metrics (a CPU trace has no TPU
    device, so the device readers read nothing)."""
    root = make_tiny_root(tmp_path)
    mix_path = os.path.join(root, "bench", "traffic", "zipf640.json")
    with open(mix_path) as f:
        mix = json.load(f)
    mix.update(rate_per_s=100, wait_after_s=5.0)
    with open(mix_path, "w") as f:
        json.dump(mix, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _RUN % CELL, root, ROOT],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    plain, traced = json.loads(p.stdout.strip().splitlines()[-1])
    for r in (plain, traced):
        assert r["correct"], r["checks"]
        assert r["device"]["count"] == 4
    assert set(plain["metrics"]) == {"p50_ms", "setup_s"}
    assert {"dispatch_rhs.x4", "compiles.x4", "latency_p95.x4",
            "flush_ms.x4"} <= set(traced["metrics"])
    assert "replica_max_share.x4" not in traced["metrics"]
