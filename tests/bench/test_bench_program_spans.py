"""The readers of the program's own spans (`bench/program_trace.py`,
`bench/metrics/*`), the idle gaps named by the thread that drives the
device, and traced runs with the program's tracer on
(`bench/trace_program.py`)."""
import json
import os
from types import SimpleNamespace

import pytest
from conftest import make_tiny_root

from bench import peaks, program_trace, trace, trace_program
from bench.registry import load_benchmark, metric_reader

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = "amc-engine-worker-r0"
NEW_LAT = ("admit_ms", "queue_wait_ms", "canary_ms", "resolve_ms",
           "worker_busy_pct", "pack_ms", "pack_hit_pct", "stage_ms",
           "fetch_ms")


def _s(name, t0, t1, **attrs):
    attrs.setdefault("thread", WORKER)
    return (name, t0, t1, attrs)


def _spans():
    """Two engine cycles in a 1,000 ns window (times in ns), and one
    span outside it."""
    return [
        _s("fleet.submit", 10, 14, thread="MainThread", lock_wait_ns=1),
        _s("fleet.submit", 20, 26, thread="MainThread", lock_wait_ns=0),
        _s("engine.idle", -100, 100),          # started before the window
        _s("engine.queued", 20, 100, dispatch=0),
        _s("engine.queued", 40, 100, dispatch=0),
        _s("engine.cycle", 100, 300, dispatch=0, rhs=2, tenants=2,
           trigger="size"),
        _s("service.flush_all", 110, 200, tenants=2, rhs=2, k_pad=1),
        _s("service.stage", 110, 120),
        _s("service.pack", 120, 170, tenants=2, hit=0),
        _s("service.fetch", 180, 195),
        _s("engine.canary", 200, 230),
        _s("engine.canary", 230, 250),
        _s("engine.resolve", 250, 260, requests=1),
        _s("engine.idle", 300, 600),
        _s("engine.queued", 500, 600, dispatch=1),
        _s("engine.cycle", 600, 700, dispatch=1, rhs=1, tenants=1,
           trigger="age"),
        _s("service.flush_all", 610, 650, tenants=1, rhs=1, k_pad=1),
        _s("service.stage", 610, 620),
        _s("service.fetch", 630, 640),
        _s("service.pack", 620, 630, tenants=2, hit=1),
        _s("engine.canary", 650, 660),
        _s("engine.resolve", 660, 690, requests=1),
        _s("blockamc.solve_batched", 700, 702, thread="MainThread",
           draws=40),
        _s("blockamc.solve_batched", 800, 804, thread="MainThread",
           draws=40),
        _s("engine.cycle", 1200, 1300, dispatch=2, rhs=1, tenants=1,
           trigger="age"),                     # after the window
    ]


def _ctx(spans=None, **kw):
    return SimpleNamespace(spans=_spans() if spans is None else spans,
                           window_ns=(0, 1000), window_s=1e-6, **kw)


@pytest.mark.parametrize("name, value", [
    ("admit_ms.lat", (4 + 6) / 2 * 1e-6),
    ("queue_wait_ms.lat", (80 + 60 + 100) / 3 * 1e-6),
    ("canary_ms.lat", (30 + 20 + 10) / 2 * 1e-6),
    ("canary_ms.tput", (30 + 20 + 10) / 2 * 1e-6),
    ("resolve_ms.lat", (10 + 30) / 2 * 1e-6),
    ("resolve_ms.tput", (10 + 30) / 2 * 1e-6),
    # idle inside the window: 100 (clipped at 0) + 300 of 1,000 ns
    ("worker_busy_pct.lat", 60.0),
    ("pack_ms.lat", (50 + 10) / 2 * 1e-6),
    ("pack_hit_pct.lat", 50.0),
    ("stage_ms.lat", (10 + 10) / 2 * 1e-6),
    ("fetch_ms.lat", (15 + 10) / 2 * 1e-6),
    ("solve_batched_ms.mc", (2 + 4) / 2 * 1e-6),
])
def test_reader_on_synthetic_spans(name, value):
    assert metric_reader(name).read(_ctx()) == pytest.approx(value)


@pytest.mark.parametrize("reader", NEW_LAT + ("solve_batched_ms",))
def test_reader_reads_nothing_without_program_spans(reader):
    bench_only = [("bench.flush_all", 0, 10, {"tenants": 1, "rhs": 1}),
                  ("bench.submit", 0, 1, {})]
    for spans in ([], bench_only):
        assert metric_reader(f"{reader}.lat").read(_ctx(spans)) is None


def test_a_worker_that_never_waits_is_busy_throughout():
    ctx = _ctx([_s("engine.cycle", 0, 900, dispatch=0, rhs=16, tenants=1,
                   trigger="size")])
    assert metric_reader("worker_busy_pct.tput").read(ctx) == 100.0


def test_window_comes_from_seconds_without_window_ns():
    """`bench/run.py`'s context gives the window's length only; the
    readers then take every span the Recorder kept."""
    ctx = SimpleNamespace(spans=[_s("engine.idle", 0, 250)], window_s=1e-6)
    assert metric_reader("worker_busy_pct.tput").read(ctx) == \
        pytest.approx(75.0)


def _table(path):
    with open(os.path.join(HERE, "data", path)) as f:
        raw = json.load(f)
    return {"devices": {int(k): {key: [tuple(e) for e in v[key]]
                                 for key in ("ops", "modules")}
                        for k, v in raw["devices"].items()},
            "spans": [tuple(s) for s in raw["spans"]]}


def _gapped():
    """Device busy in [1000, 2000] and [5000, 6000] of a [0, 10000]
    window: gaps [0, 1000], [2000, 5000] and [6000, 10000]."""
    ops = [("op", 1000, 1000), ("op", 5000, 1000)]
    t = "/host:CPU/1"
    spans = [("bench.window", 0, 10000, {}),
             ("bench.client_sleep", 0, 10000, {"thread": "/host:CPU/0"}),
             ("fleet.submit", 3000, 1000, {"thread": "/host:CPU/0"}),
             ("engine.idle", 1800, 3000, {"thread": t}),
             ("engine.cycle", 6100, 3000, {"thread": t}),
             ("service.pack", 7000, 2000, {"thread": t})]
    return {"devices": {0: {"ops": ops, "modules": []}}, "spans": spans}


def test_gaps_are_named_by_the_driving_thread():
    tr = _gapped()
    assert program_trace.device_threads(tr) == {"/host:CPU/1"}
    gaps = program_trace.idle_gaps(tr)
    # [6000, 10000]: mid 8000, inside the worker's pack; [2000, 5000]:
    # mid 3500, the worker idles (the client's fleet.submit does not
    # count); [0, 1000]: the worker has no span, so today's rule
    assert [g[0] for g in gaps] == ["service.pack", "engine.idle",
                                    "bench.client_sleep"]
    assert [g[1] for g in gaps] == pytest.approx([4e-6, 3e-6, 1e-6])
    assert [g[0] for g in trace.idle_gaps(tr)] == \
        ["service.pack", "fleet.submit", "bench.client_sleep"]


def test_recorded_trace_without_program_spans_reads_as_before():
    tr = _table("tpu_zipf_trace.json")
    assert program_trace.device_threads(tr) == set()
    assert program_trace.idle_gaps(tr) == trace.idle_gaps(tr)


def test_recorded_trace_with_program_spans_names_gaps_by_the_worker():
    """The first 300 ms of a traced fig8-fleet.zipf window recorded on a
    TPU v5e with the program's tracer on, as `program_trace.load` reduces
    it (operation names cut to `trace.op_label`)."""
    tr = _table("tpu_zipf_trace_spans.json")
    (worker,) = program_trace.device_threads(tr)
    names = {s[0] for s in tr["spans"] if s[3].get("thread") == worker}
    assert {"engine.cycle", "engine.idle", "service.flush_all",
            "service.pack"} <= names
    assert all(n.startswith(program_trace.PROGRAM_PREFIXES) for n in names)
    gaps = program_trace.idle_gaps(tr)
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert {g[0] for g in gaps} <= names
    # the same gaps, named by today's rule, read only the client's spans
    assert [g[1] for g in trace.idle_gaps(tr)] == [g[1] for g in gaps]
    calls = trace.modules_in_spans(tr, r"execute_arena", "bench.flush_all")
    assert calls and all(0 < c[2] < c[1] - c[0] for c in calls)


def test_program_spans_change_no_existing_reading():
    """The same recorded trace with program spans laid over it: every
    existing reader and today's gap names read the same."""
    tr = _table("tpu_zipf_trace.json")
    w0, w1 = trace.window(tr)
    extra = [("engine.idle", w0 + k * 10**7, 5 * 10**6,
              {"thread": "/host:CPU/7"}) for k in range(200)]
    tr2 = {"devices": tr["devices"], "spans": tr["spans"] + extra}
    ctx = dict(peaks=peaks.peaks_for("TPU v5 lite"),
               cfg={"n": 256, "stages": 2, "array_size": 64})
    for name in ("executor_us.lat", "executor_roofline.lat",
                 "idle_pct.lat"):
        r = metric_reader(name)
        assert r.read(SimpleNamespace(trace=tr2, **ctx)) == \
            r.read(SimpleNamespace(trace=tr, **ctx))
    assert trace.busy_ns(tr2) == trace.busy_ns(tr)
    assert trace.top_ops(tr2) == trace.top_ops(tr)
    assert trace.modules_in_spans(tr2, r"execute_arena",
                                  "bench.flush_all") == \
        trace.modules_in_spans(tr, r"execute_arena", "bench.flush_all")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp("tiny"))
    return root, load_benchmark(root)


@pytest.mark.parametrize("workload, suffix, names", [
    ("fig8-fleet.zipf", "lat", NEW_LAT),
    ("fig8-fleet.hot1", "tput", ("queue_wait_ms", "canary_ms",
                                 "resolve_ms", "worker_busy_pct")),
    ("fig8d-mc512.sweep", "mc", ("solve_batched_ms",)),
])
def test_tiny_traced_run_reports_the_new_metrics(tiny, workload, suffix,
                                                 names):
    from repro.runtime import tracing
    root, bm = tiny
    rep = trace_program.one(bm, workload, 2**31 + 11, 1.0, True, root=root,
                            require_tpu=False)
    assert not tracing.on
    m = rep["metrics"]
    for name in names:
        assert f"{name}.{suffix}" in m, sorted(m)
    for name, v in m.items():
        assert v >= 0, name
        if name.split(".")[0].endswith("_pct"):
            assert 0 <= v <= 100, name
    if suffix == "lat":
        f = rep["flush"]
        # the flush's parts account for it, and it for the benchmark's
        # own span around it
        assert f["parts_sum"] == pytest.approx(f["service.flush_all"],
                                               rel=0.1)
        assert f["service.flush_all"] == pytest.approx(
            f["bench.flush_all"], rel=0.1)
        assert sum(rep["cycle"]["triggers"].values()) == \
            rep["cycle"]["cycles"]


def test_tracer_off_window_records_only_the_benchmarks_spans(tiny):
    root, bm = tiny
    rep = trace_program.one(bm, "fig8-fleet.hot1", 2**31 + 12, 0.5, False,
                            root=root, require_tpu=False)
    assert rep["metrics"].get("flush_ms.tput", 0) > 0
    assert not any(k.split(".")[0] in NEW_LAT for k in rep["metrics"])
    assert rep["flush"] == {} and rep["cycle"] == {}
