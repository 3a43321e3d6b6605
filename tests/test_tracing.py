"""Host spans inside the program (`repro.runtime.tracing`).

Off by default: a served flush then records nothing and opens no
profiler annotation.  On: the engine's cycle nests its dispatch, the
dispatch nests the service's flush, the flush nests packing, staging,
execution, fetching and scattering, all on the engine's worker thread,
and the cycle shares its dispatch index with its requests' queue waits.
"""
import sys
import threading
import time

import jax
import pytest

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import random_rhs, wishart
from repro.runtime import tracing
from repro.serve import AsyncSolverEngine, ReplicatedSolverFleet, SolverService

KEY = jax.random.PRNGKey(11)
N = 16
CFG = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.02))
FLUSH_PARTS = ("service.pack", "service.stage", "service.execute",
               "service.fetch", "service.scatter")


class Sink:
    """What `tracing.enable` needs of a sink (the benchmark's Recorder)."""

    def __init__(self, active=True):
        self.spans, self.active = [], active
        self._lock = threading.Lock()

    def named(self, name):
        return [s for s in self.spans if s[0] == name]


@pytest.fixture
def sink():
    s = Sink()
    tracing.enable(s)
    try:
        yield s
    finally:
        tracing.disable()


@pytest.fixture
def annotations(monkeypatch):
    """Counts the profiler annotations opened."""
    opened = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        opened.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    return opened


def _program(target, mids):
    """Program `mids` on a SolverService or an engine; returns it."""
    for i, mid in enumerate(mids):
        target.program(mid, wishart(jax.random.fold_in(KEY, i), N),
                       jax.random.fold_in(KEY, 100 + i))
    return target


def _service(mids):
    return _program(SolverService(CFG, stages=1), mids)


def _engine(mids, **kw):
    return _program(AsyncSolverEngine(SolverService(CFG, stages=1), **kw),
                    mids)


def _rhs(i):
    return random_rhs(jax.random.fold_in(KEY, 1000 + i), N)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_off_records_nothing_and_opens_no_annotation(annotations):
    assert not tracing.on
    rec = Sink()
    svc = _service(["a", "b"])
    svc.submit("a", _rhs(0))
    svc.submit("b", _rhs(1))
    svc.flush_all()                       # packed
    svc.submit("a", _rhs(2))
    svc.flush_all()                       # single tenant
    keys = jax.random.split(KEY, 2)
    blockamc.solve_batched(wishart(KEY, N), _rhs(3), keys, CFG, stages=1,
                           mode="fused")
    assert tracing.span("engine.cycle") is tracing.NO_SPAN
    tracing.record("engine.queued", 0, 1)
    assert rec.spans == [] and annotations == []


def test_inactive_sink_keeps_nothing(annotations):
    rec = Sink(active=False)
    tracing.enable(rec)
    try:
        with tracing.span("service.pack"):
            pass
        tracing.record("engine.queued", 0, 1)
    finally:
        tracing.disable()
    assert rec.spans == []
    assert annotations == ["service.pack"]


def test_span_carries_attrs_and_thread(sink, annotations):
    sp = tracing.span("service.flush_all", tenants=3)
    with sp:
        sp.attrs["k_pad"] = 4             # known at the end: sink only
    tracing.record("engine.queued", 5, 9, dispatch=2)
    (name, t0, t1, attrs), queued = sink.spans
    assert name == "service.flush_all" and 0 < t0 <= t1
    assert attrs == {"tenants": 3, "k_pad": 4,
                     "thread": threading.current_thread().name}
    assert queued == ("engine.queued", 5, 9,
                      {"dispatch": 2,
                       "thread": threading.current_thread().name})
    assert annotations == ["service.flush_all"]


def test_engine_cycle_nests_the_flush_and_shares_its_dispatch(sink):
    eng = _engine(["a", "b", "c"], max_batch=8, flush_interval=30.0,
                  name="e0")
    with eng:
        futs = [eng.submit(mid, _rhs(i))
                for i, mid in enumerate(["a", "a", "b"])]
        eng.flush_now()
        for f in futs:
            assert f.result(timeout=30).mode == "analog"
    worker = "amc-engine-worker-e0"
    (cycle,) = sink.named("engine.cycle")
    assert cycle[3] == {"dispatch": 0, "rhs": 3, "tenants": 2,
                        "trigger": "force", "thread": worker}
    queued = sink.named("engine.queued")
    assert len(queued) == 3
    for q in queued:
        assert q[3]["dispatch"] == 0 and q[2] == cycle[1]
        assert q[1] <= q[2]
    (dispatch,) = sink.named("engine.dispatch")
    (flush,) = sink.named("service.flush_all")
    assert dispatch[3]["attempts"] == 1
    assert flush[3]["tenants"] == 2 and flush[3]["rhs"] == 3
    assert flush[3]["k_pad"] == 2
    assert _inside(dispatch, cycle) and _inside(flush, dispatch)
    parts = [sink.named(p) for p in FLUSH_PARTS]
    assert all(len(p) == 1 for p in parts)
    parts = [p[0] for p in parts]
    for p in parts:
        assert _inside(p, flush)
    # in the order the flush runs them, one after another
    order = [sink.named(p)[0] for p in ("service.stage", "service.pack",
                                        "service.execute", "service.fetch",
                                        "service.scatter")]
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))
    canaries = sink.named("engine.canary")
    resolves = sink.named("engine.resolve")
    assert len(canaries) == 2 and len(resolves) == 2
    assert sorted(r[3]["requests"] for r in resolves) == [1, 2]
    for s in canaries + resolves:
        assert _inside(s, cycle) and s[1] >= dispatch[2]
    engine_spans = [s for s in sink.spans
                    if s[0].startswith(("engine.", "service."))]
    assert {s[3]["thread"] for s in engine_spans} == {worker}
    assert sink.named("engine.idle")


def test_size_trigger_names_the_cycle(sink):
    eng = _engine(["a"], max_batch=2, flush_interval=30.0)
    with eng:
        futs = [eng.submit("a", _rhs(i)) for i in range(2)]
        for f in futs:
            f.result(timeout=30)
    (cycle,) = sink.named("engine.cycle")
    assert cycle[3]["trigger"] == "size" and cycle[3]["tenants"] == 1
    # one tenant: solve_many, no packing
    assert not sink.named("service.pack")
    assert len(sink.named("service.execute")) == 1


def test_pack_hit_reads_the_membership(sink):
    svc = _service(["a", "b", "c"])

    def flush(mids):
        for i, mid in enumerate(mids):
            svc.submit(mid, _rhs(i))
        svc.flush_all()
        return sink.named("service.pack")[-1][3]

    assert flush(["a", "b"])["hit"] == 0
    assert flush(["a", "b"])["hit"] == 1
    attrs = flush(["a", "c"])
    assert attrs["hit"] == 0 and attrs["tenants"] == 2
    assert flush(["a", "c"])["hit"] == 1


def test_solve_batched_spans(sink):
    keys = jax.random.split(KEY, 3)
    x = blockamc.solve_batched(wishart(KEY, N), _rhs(0), keys, CFG,
                               stages=1, mode="fused")
    assert x.shape == (3, N)
    (call,) = sink.named("blockamc.solve_batched")
    assert call[3]["draws"] == 3
    assert [s[0] for s in sink.spans] == ["blockamc.solve_batched"]


def test_fleet_submit_span(sink):
    fleet = ReplicatedSolverFleet(lambda: SolverService(CFG, stages=1), 1,
                                  engine_kw=dict(flush_interval=0.004))
    with fleet:
        fleet.program("m", wishart(KEY, N), KEY)
        fleet.submit("m", _rhs(0)).result(timeout=30)
    (sub,) = sink.named("fleet.submit")
    assert 0 <= sub[3]["lock_wait_ns"] <= sub[2] - sub[1]
    assert sub[3]["thread"] == threading.current_thread().name


def test_concurrent_spans_are_all_kept(sink):
    """More threads than cores append at once: no span is lost."""
    threads, each = 32, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                with tracing.span("engine.resolve"):
                    pass
                tracing.record("engine.queued", 0, 1)

        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert len(sink.spans) == 2 * threads * each


def test_off_costs_no_clock_read(monkeypatch):
    """A span site with tracing off reads no clock."""
    calls = []
    monkeypatch.setattr(time, "perf_counter_ns",
                        lambda: calls.append(1) or 0)
    with tracing.span("engine.cycle") as sp:
        assert not sp
    tracing.record("engine.queued", 0, 1)
    assert calls == []
