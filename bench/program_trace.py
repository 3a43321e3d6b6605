"""The program's own spans (`repro.runtime.tracing`), read beside the
benchmark's.

The program names its spans `<layer>.<step>`: `fleet.submit`,
`engine.cycle`, `service.pack`, `blockamc.partition`, ...  While it is
enabled with the benchmark's `Recorder` as its sink, each span is in
`Recorder.spans` (perf_counter_ns, with the thread's name in its attrs)
and, in a traced run, a host event of the profiler's trace.

`load(trace_dir)` reduces a trace as `bench.trace.load` does, and also
keeps the program's host events; the stats of each carry the profiler
line (one host thread) it came from, as `thread`.  The table
keeps `bench.trace`'s form, so every function there reads it unchanged.
`idle_gaps` names each gap by what the thread that drives the device was
doing.  The rest are the arithmetic the per-layer readers under
`bench/metrics/` share.
"""
from __future__ import annotations

import glob
import os
import warnings

from bench import trace

PROGRAM_PREFIXES = ("fleet.", "engine.", "service.", "blockamc.")
# spans that only the thread driving the device opens: the engine's
# worker, or the caller of `solve_batched`
DEVICE_THREAD_SPANS = ("engine.cycle", "engine.idle", "blockamc.solve_batched")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        return {"devices": {}, "spans": []}
    pd = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        trace._read(pd, devices, spans)          # devices, bench spans
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                thread = f"{plane.name}/{i}"
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        stats = {k: v for k, v in e.stats}
                        stats["thread"] = thread
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns), stats))
    return {"devices": devices, "spans": spans}


def device_threads(tr: dict) -> set:
    """The threads whose spans say they drive the device."""
    return {s[3].get("thread") for s in tr["spans"]
            if s[0] in DEVICE_THREAD_SPANS}


def idle_gaps(tr: dict, device: int = 0, top: int = 10):
    """The longest gaps between device operations inside the window, each
    named by the innermost span open at its middle on a thread that
    drives the device; where none is open there, by the innermost
    benchmark span open at its middle (`bench.trace.idle_gaps`'s rule,
    so a trace with no program spans is named as it names it)."""
    w = trace.window(tr)
    if w is None or device not in tr["devices"]:
        return []
    busy = trace._union(trace._clip(tr["devices"][device]["ops"], *w))
    gaps, t = [], w[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w[1] > t:
        gaps.append((t, w[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    threads = device_threads(tr)
    mine, bench = [], []
    for n, s, d, stats in tr["spans"]:
        if n == "bench.window":
            continue
        if stats.get("thread") in threads and n.startswith(PROGRAM_PREFIXES):
            mine.append((n, s, s + d))
        elif n.startswith(trace.SPAN_PREFIX):
            bench.append((n, s, s + d))
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        name = "none"
        for spans in (mine, bench):
            open_ = [(e - s, n) for n, s, e in spans if s <= mid <= e]
            if open_:
                name = min(open_)[1]
                break
        out.append([name, (b - a) * 1e-9])
    return out


# -- what the per-layer readers share ------------------------------------

def spans(ctx, name: str) -> list:
    """`name`'s spans in `ctx.spans` that lie inside the window: all the
    `Recorder` kept (it keeps a span that ends while it is active), cut
    to `ctx.window_ns` where the run gives it."""
    w = getattr(ctx, "window_ns", None)
    out = [s for s in ctx.spans if s[0] == name]
    if w is not None:
        out = [s for s in out if s[1] >= w[0] and s[2] <= w[1]]
    return out


def clipped(ctx, name: str) -> list:
    """`name`'s spans cut to `ctx.window_ns` where the run gives it
    (a span that began before the window counts from its start)."""
    w = getattr(ctx, "window_ns", None)
    out = [s for s in ctx.spans if s[0] == name]
    if w is None:
        return out
    return [(n, max(t0, w[0]), min(t1, w[1]), a) for n, t0, t1, a in out
            if t1 > w[0] and t0 < w[1]]


def total_ns(ss) -> int:
    return sum(t1 - t0 for _, t0, t1, _ in ss)


def mean_ms(ctx, name: str):
    """Mean duration of `name`'s spans, in ms; None without one."""
    ss = spans(ctx, name)
    return total_ns(ss) / len(ss) * 1e-6 if ss else None


def per_ms(ctx, name: str, per: str):
    """Time in `name`'s spans per `per` span, in ms; None without one of
    each."""
    ss, n = spans(ctx, name), len(spans(ctx, per))
    return total_ns(ss) / n * 1e-6 if ss and n else None


def window_ns(ctx) -> float:
    w = getattr(ctx, "window_ns", None)
    return (w[1] - w[0]) if w is not None else ctx.window_s * 1e9
