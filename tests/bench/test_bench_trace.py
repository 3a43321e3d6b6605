"""The reduction from a profiler trace to per-layer metrics."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import peaks, trace, work
from bench.registry import metric_reader

HERE = os.path.dirname(os.path.abspath(__file__))


def _synthetic():
    """A hand-made trace with known answers (times in ns)."""
    ops = [("opD", 500, 1000),          # clipped to [1000, 1500]
           ("opA", 2000, 1000), ("opB", 2500, 1000),   # union [2000, 3500]
           ("opA", 6000, 2000),         # [6000, 8000]
           ("opC", 12000, 500)]         # after the window
    modules = [("jit_execute_arena_packed(123)", 2000, 1500),
               ("jit_execute_arena(9)", 6000, 2000),
               ("jit_other(1)", 9000, 100)]
    spans = [("bench.window", 1000, 10000, {}),
             ("bench.flush_all", 1900, 1700, {"tenants": 4, "rhs": 8}),
             ("bench.flush_all", 5900, 1000, {"tenants": 1, "rhs": 2}),
             ("bench.client_sleep", 3600, 2300, {})]
    return {"devices": {0: {"ops": ops, "modules": modules}},
            "spans": spans}


def test_busy_idle_and_gaps():
    tr = _synthetic()
    assert trace.window(tr) == (1000, 11000)
    assert trace.busy_ns(tr) == 500 + 1500 + 2000
    gaps = trace.idle_gaps(tr)
    assert [g[0] for g in gaps] == ["none", "bench.client_sleep", "none"]
    assert [g[1] for g in gaps] == pytest.approx([3e-6, 2.5e-6, 5e-7])
    ops = trace.top_ops(tr)
    assert [o[0] for o in ops] == ["opA", "opB", "opD"]
    assert [o[1] for o in ops] == pytest.approx([3e-6, 1e-6, 5e-7])


def test_op_labels():
    hlo = ('%custom-call.28 = (f32[40,4,128,128]{3,2,1,0}) custom-call('
           'f32[40,4,128,128]{3,2,1,0} %copy.63), '
           'custom_call_target="LuDecompositionBlock"')
    assert trace.op_label(hlo) == "%custom-call.28 LuDecompositionBlock"
    assert trace.op_label("%fusion.3 = f32[64,64]{1,0} fusion(...)") == \
        "%fusion.3"
    assert trace.op_label("opA") == "opA"


def test_modules_inside_spans():
    tr = _synthetic()
    calls = trace.modules_in_spans(tr, r"execute_arena", "bench.flush_all")
    # the second executor ran past its span's end: not counted
    assert calls == [(1900, 3600, 1500, {"tenants": 4, "rhs": 8})]


def test_metric_readers_on_the_synthetic_trace():
    cfg = {"n": 256, "stages": 2, "array_size": 64}
    p = peaks.peaks_for("TPU v5 lite")
    ctx = SimpleNamespace(trace=_synthetic(), cfg=cfg, peaks=p,
                          counters={"answered": 30, "dispatches": 3},
                          spans=[("bench.flush_all", 0, 2_000_000, {}),
                                 ("bench.flush_all", 0, 4_000_000, {})],
                          compiles=0)
    assert metric_reader("executor_us.lat").read(ctx) == pytest.approx(1.5)
    flops, nbytes = work.executor_work(256, 2, 64, 4, 8)
    t_min = work.roofline_seconds(flops, nbytes, p)[0]
    assert metric_reader("executor_roofline.lat").read(ctx) == \
        pytest.approx(100 * t_min / 1500e-9)
    assert metric_reader("idle_pct.lat").read(ctx) == pytest.approx(60.0)
    assert metric_reader("dispatch_rhs.lat").read(ctx) == 10.0
    assert metric_reader("flush_ms.lat").read(ctx) == pytest.approx(3.0)
    assert metric_reader("compiles.lat").read(ctx) == 0
    # nothing to read: the metric is left out, never read as 0
    empty = SimpleNamespace(trace=None, counters={}, spans=[], cfg=cfg,
                            peaks=p, compiles=0)
    for name in ("executor_us.lat", "executor_roofline.lat",
                 "sweep_device_ms.mc", "idle_pct.lat", "dispatch_rhs.lat",
                 "flush_ms.lat"):
        assert metric_reader(name).read(empty) is None


def test_latency_p95_reader():
    """The tail read per layer: the 95th percentile of all latencies the
    window's client timed, in ms; nothing when the cell times none."""
    import numpy as np
    ctx = SimpleNamespace(latency_s=np.linspace(0.0, 1.0, 101))
    assert metric_reader("latency_p95.lat").read(ctx) == pytest.approx(950.0)
    assert metric_reader("latency_p95.lat").read(
        SimpleNamespace(latency_s=None)) is None


def _recorded():
    """The first 200 ms of a traced fig8-fleet.zipf window recorded on a
    TPU v5e (device plane TPU:0, its "XLA Ops" and "XLA Modules", and the
    benchmark's host spans), as `trace.load` reduces it."""
    with open(os.path.join(HERE, "data", "tpu_zipf_trace.json")) as f:
        raw = json.load(f)
    return {"devices": {int(k): {key: [tuple(e) for e in v[key]]
                                 for key in ("ops", "modules")}
                        for k, v in raw["devices"].items()},
            "spans": [tuple(s) for s in raw["spans"]]}


def test_reduction_of_a_recorded_tpu_trace():
    tr = _recorded()
    w0, w1 = trace.window(tr)
    assert w1 - w0 == pytest.approx(3e9, rel=1e-3)
    busy = trace.busy_ns(tr)
    assert 0 < busy < 0.2e9
    calls = trace.modules_in_spans(tr, r"execute_arena", "bench.flush_all")
    assert calls
    for s, e, dev_ns, stats in calls:
        assert 0 < dev_ns < e - s
        assert 1 <= stats["tenants"] <= stats["rhs"]
    ctx = SimpleNamespace(trace=tr, peaks=peaks.peaks_for("TPU v5 lite"),
                          cfg={"n": 256, "stages": 2, "array_size": 64})
    us = metric_reader("executor_us.lat").read(ctx)
    share = metric_reader("executor_roofline.lat").read(ctx)
    idle = metric_reader("idle_pct.lat").read(ctx)
    assert 1.0 < us < 1000.0
    assert 0.0 < share < 100.0
    assert 90.0 < idle < 100.0
    labels = [n for n, _ in trace.top_ops(tr)]
    assert any("tpu_custom_call" in n for n in labels)
    assert all(" = " not in n for n in labels)
    gaps = trace.idle_gaps(tr)
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert {g[0] for g in gaps} <= {"none", "bench.client_sleep",
                                    "bench.submit", "bench.flush_all"}
