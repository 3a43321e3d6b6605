"""Host spans and counters the benchmark records around the layers it calls.

Spans are kept in memory, (name, start_ns, end_ns, attrs) on
`time.perf_counter_ns`, and each is also a `jax.profiler.TraceAnnotation`
so that a traced run sees it on the profiler's clock beside the device's
operations.  Recording is on only inside the measured window.
"""
from __future__ import annotations

import threading
import time

import jax


class Recorder:
    def __init__(self):
        self.spans = []
        self.active = False
        self._lock = threading.Lock()

    def span(self, name: str, **attrs):
        return _Span(self, name, attrs)


class _Span:
    __slots__ = ("rec", "name", "attrs", "t0", "ann")

    def __init__(self, rec, name, attrs):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        if self.rec.active:
            with self.rec._lock:
                self.rec.spans.append((self.name, self.t0, t1, self.attrs))
        return False


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while active."""

    def __init__(self):
        self.active = False
        self.compiles = 0
        self.cache_loads = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    @property
    def total(self) -> int:
        return self.compiles + self.cache_loads


def traced_service_class(recorder: Recorder):
    """A `SolverService` whose `flush_all` runs inside a `flush_all` span
    that carries the bucket's tenant count and real rhs count."""
    from repro.serve import SolverService

    class TracedSolverService(SolverService):
        def flush_all(self, matrix_ids=None):
            ids = self.matrix_ids if matrix_ids is None else matrix_ids
            ks = [k for k in (self.pending(m) for m in ids) if k]
            with recorder.span("bench.flush_all", tenants=len(ks),
                               rhs=sum(ks)):
                return super().flush_all(matrix_ids)

    return TracedSolverService
