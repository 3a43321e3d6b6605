"""Reduction of a profiler trace to the numbers the per-layer metrics read.

`load(path)` turns the `.xplane.pb` a traced run writes into a small
table: for each device plane (`/device:TPU:<i>`) its operations ("XLA
Ops" line) and programs ("XLA Modules" line), and every host event whose
name starts with `bench.` (the benchmark's own spans, written as
`TraceAnnotation`s, with the attributes they carry).  Every time is in nanoseconds on the profiler's
clock.  The functions after it work on that table alone, so a small
recorded trace tests them (tests/bench/).
"""
from __future__ import annotations

import glob
import os
import re
import warnings

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."


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
        _read(pd, devices, spans)
    return {"devices": devices, "spans": spans}


def _read(pd, devices, spans):
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns),
                                      {k: v for k, v in e.stats}))


def window(tr: dict):
    """(start, end) of the `bench.window` span, or None."""
    for name, s, d, _ in tr["spans"]:
        if name == "bench.window":
            return s, s + d
    return None


def _clip(events, w0, w1):
    out = []
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    merged = []
    for _, a, b in sorted(intervals, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(tr: dict, devices=None) -> float:
    """Device time in which some operation ran, inside the window,
    averaged over the devices used."""
    w = window(tr)
    devs = [d for d in tr["devices"] if devices is None or d in devices]
    if w is None or not devs:
        return 0.0
    total = 0
    for d in devs:
        for a, b in _union(_clip(tr["devices"][d]["ops"], *w)):
            total += b - a
    return total / len(devs)


def idle_gaps(tr: dict, device: int = 0, top: int = 10):
    """The longest gaps between device operations inside the window, each
    named by the innermost benchmark span open at its middle."""
    w = window(tr)
    if w is None or device not in tr["devices"]:
        return []
    busy = _union(_clip(tr["devices"][device]["ops"], *w))
    gaps, t = [], w[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w[1] > t:
        gaps.append((t, w[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [(n, s, s + d) for n, s, d, _ in tr["spans"]
             if n != "bench.window"]
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        open_ = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        out.append([min(open_)[1] if open_ else "none", (b - a) * 1e-9])
    return out


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(name: str) -> str:
    """A short label for an "XLA Ops" event, whose name is the whole HLO
    instruction: its result name, and its custom-call target if any."""
    label = name.split(" = ", 1)[0]
    m = _TARGET.search(name)
    return f"{label} {m.group(1)}" if m else label


def top_ops(tr: dict, device: int = 0, top: int = 10):
    """Device operations by total time inside the window."""
    w = window(tr)
    if w is None or device not in tr["devices"]:
        return []
    tot = {}
    for name, a, b in _clip(tr["devices"][device]["ops"], *w):
        name = op_label(name)
        tot[name] = tot.get(name, 0) + (b - a)
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def modules_in_spans(tr: dict, module_pattern: str, span_name: str,
                     device: int = 0):
    """For each `span_name` span in the window, the device time of the
    programs matching `module_pattern` that ran wholly inside it.
    Returns [(span start, span end, device ns, span stats)], spans with
    none left out.
    """
    w = window(tr)
    if w is None or device not in tr["devices"]:
        return []
    pat = re.compile(module_pattern)
    mods = sorted((s, s + d) for n, s, d in tr["devices"][device]["modules"]
                  if pat.search(n))
    out = []
    for n, s, d, stats in sorted(tr["spans"], key=lambda e: e[1]):
        if n != span_name or s < w[0] or s + d > w[1]:
            continue
        t = sum(b - a for a, b in mods if a >= s and b <= s + d)
        if t:
            out.append((s, s + d, t, stats))
    return out
