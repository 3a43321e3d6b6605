"""Host spans inside the program, on the device trace's clock.

A span is named `<layer>.<step>` (`engine.cycle`, `service.pack`, ...).
Tracing is off by default: `span()` then returns one shared no-op context
and `record()` returns at once, so a span site costs one module-level read.
`enable(sink)` turns it on.  The sink is any object with the attributes
of the benchmark's `Recorder`: an `active` flag, a `spans` list and a
`_lock`.  While it is on, a span

* is written as a `jax.profiler.TraceAnnotation`, so that a profiler trace
  shows it beside the device's operations, with the attributes it had
  when it was entered, and
* is appended to `sink.spans` as `(name, t0_ns, t1_ns, attrs)` on
  `time.perf_counter_ns`, if `sink.active` when it ends.  Its attrs also
  carry the name of the thread it ran on (`thread`).

`span()` returns the span before it is entered, so that a site computes an
attribute that costs something only when tracing is on::

    sp = tracing.span("engine.resolve")
    if sp:
        sp.attrs["requests"] = len(reqs)
    with sp:
        ...

Attributes known only at the end (`sp.attrs[...] = ...` inside the block)
reach the sink but not the profiler's annotation.  `record()` adds a span
after the fact, such as a request's wait in a queue; it has no
annotation.
"""
from __future__ import annotations

import threading
import time

import jax

on = False
_sink = None


def enable(sink) -> None:
    """Send spans to `sink` (see the module docstring) from now on."""
    global on, _sink
    _sink = sink
    on = True


def disable() -> None:
    global on, _sink
    on = False
    _sink = None


class _NoSpan:
    """The span of a site while tracing is off: does nothing, reads False."""
    __slots__ = ()

    def __bool__(self):
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


def _append(sink, name, t0, t1, attrs):
    if sink is not None and sink.active:
        with sink._lock:
            sink.spans.append((name, t0, t1, attrs))


class Span:
    __slots__ = ("name", "attrs", "t0", "_ann", "_sink")

    def __init__(self, name: str, attrs: dict, sink):
        self.name, self.attrs, self._sink = name, attrs, sink

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.attrs["thread"] = threading.current_thread().name
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _append(self._sink, self.name, self.t0, t1, self.attrs)
        return False


def span(name: str, **attrs):
    """A span of the block it is entered around (NO_SPAN while off)."""
    if not on:
        return NO_SPAN
    return Span(name, attrs, _sink)


def record(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Record a span that has already ended (perf_counter_ns times)."""
    if not on:
        return
    attrs["thread"] = threading.current_thread().name
    _append(_sink, name, t0_ns, t1_ns, attrs)
