"""Traced runs of a cell with the program's own spans on.

    python3 bench/trace_program.py --workload <cell> --seeds <n,...> \
        --seconds <s> [--program 1,0] [--out <file.jsonl>] \
        [--keep <file.json> --keep-ms <ms>]

`bench/run.py --trace 1` records the benchmark's spans and a profiler
trace of the window.  This makes the same traced window with the
program's tracer (`repro.runtime.tracing`) enabled into the same
`Recorder` for the window's length, and reports, beside the cell's
per-layer metrics, the readers of the program's spans (READERS, named
with the cell's suffix), the device's idle gaps named by the thread that
drives it (`bench.program_trace.idle_gaps`), where a flush's host time
goes, and the window's own end-to-end numbers.  `--program 0` makes the
same traced window with the program's tracer off, for what it costs.
Every pair of seed and setting is one run, all in one process, so the
set-up after the first reuses what the first compiled.  The answers are
not compared with the reference: that is `bench/run.py`'s job.

`--keep` writes the first `--keep-ms` of the first traced window with
the program's spans as `bench.program_trace.load` reduces it (the form
of `tests/bench/data/*.json`).
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402
from repro.runtime import tracing  # noqa: E402

READERS = ("admit_ms", "queue_wait_ms", "canary_ms", "resolve_ms",
           "worker_busy_pct", "pack_ms", "pack_hit_pct", "stage_ms",
           "fetch_ms", "solve_batched_ms")
# the suffix of a cell's per-layer metrics, by the end-to-end metric
# they move
SUFFIX = {"p50_ms": "lat", "solves_per_s": "tput", "draws_per_s": "mc"}
FLUSH_PARTS = ("service.pack", "service.stage", "service.execute",
               "service.fetch", "service.scatter")


class ProgramWindow(run.Window):
    """`bench/run.py`'s window, with the program's tracer on inside it
    when `program` is set."""

    def __init__(self, recorder, compiles, trace_dir, program: bool):
        super().__init__(recorder, compiles, trace_dir)
        self.program = program

    def start(self):
        super().start()
        if self.program:
            tracing.enable(self.rec)

    def end(self):
        tracing.disable()
        super().end()


def _ms(ns):
    return ns * 1e-6


def flush_split(ctx) -> dict:
    """Mean ms per `service.flush_all` of each of its parts, of the flush
    itself and of the benchmark's own `bench.flush_all` around it."""
    from bench import program_trace as pt
    n = len(pt.spans(ctx, "service.flush_all"))
    if not n:
        return {}
    out = {p: _ms(pt.total_ns(pt.spans(ctx, p))) / n for p in FLUSH_PARTS}
    out["parts_sum"] = sum(out.values())
    out["service.flush_all"] = pt.mean_ms(ctx, "service.flush_all")
    out["bench.flush_all"] = pt.mean_ms(ctx, "bench.flush_all")
    return out


def cycle_split(ctx) -> dict:
    """The engine's cycles: how many, their triggers, and the mean ms per
    cycle of each of its parts."""
    from bench import program_trace as pt
    cycles = pt.spans(ctx, "engine.cycle")
    if not cycles:
        return {}
    triggers = {}
    for _, _, _, a in cycles:
        triggers[a["trigger"]] = triggers.get(a["trigger"], 0) + 1
    out = {"cycles": len(cycles), "triggers": triggers,
           "rhs_per_cycle": sum(a["rhs"] for *_, a in cycles) / len(cycles),
           "tenants_per_cycle": (sum(a["tenants"] for *_, a in cycles)
                                 / len(cycles))}
    for name in ("engine.cycle", "engine.dispatch", "engine.canary",
                 "engine.resolve", "engine.idle"):
        out[name] = _ms(pt.total_ns(pt.spans(ctx, name))) / len(cycles)
    return out


def cut(tr: dict, ms: float) -> dict:
    """The first `ms` of a reduced trace's window, as a JSON-ready table:
    operations, programs and spans that overlap it, the window span cut
    to it."""
    from bench import trace
    w0, w1 = trace.window(tr)
    w1 = min(w1, w0 + int(ms * 1e6))

    def keep(s, d):
        return s < w1 and s + d > w0

    devices = {str(k): {key: [list(e) for e in v[key] if keep(e[1], e[2])]
                        for key in ("ops", "modules")}
               for k, v in tr["devices"].items()}
    spans = [["bench.window", w0, w1 - w0, {}]]
    spans += [[n, s, d, {k: v if isinstance(v, (int, float)) else str(v)
                         for k, v in a.items()}]
              for n, s, d, a in tr["spans"]
              if n != "bench.window" and keep(s, d)]
    return {"devices": devices, "spans": spans}


def one(bm, workload, seed, seconds, program, *, root=run.ROOT,
        require_tpu=True, keep=None):
    """One traced window; returns its report."""
    from bench import peaks, program_trace, registry, trace
    from bench.spans import CompileCounter, Recorder
    cell = registry.find_cell(bm, workload)
    device = run.device_info(cell["chips"], require_tpu)
    cfg = registry.load_config(bm, cell["config"], root)
    mix = registry.load_traffic(cell["traffic"], root)
    system = registry.load_driver(cfg["system"], root)
    rec, cc = Recorder(), CompileCounter()
    t0 = time.perf_counter()
    r = system.Run(cfg, mix, seed, rec)
    split = r.setup()
    gc.collect()
    gc.freeze()
    trace_dir = os.path.join(root, ".bench_trace",
                             f"program-{workload}-{seed}-{int(program)}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = ProgramWindow(rec, cc, trace_dir, program)
    try:
        out = r.measure(seconds, win)
    finally:
        gc.unfreeze()
        r.release()
    t1 = time.perf_counter()
    tr = program_trace.load(trace_dir)
    load_s = time.perf_counter() - t1
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = SimpleNamespace(
        trace=tr if tr["devices"] else None,
        counters=out.get("counters", {}), spans=rec.spans, cfg=cfg,
        latency_s=out.get("latency_s"), compiles=cc.total,
        window_s=seconds,
        window_ns=(int(win.t_start * 1e9), int(win.t_end * 1e9)),
        peaks=peaks.peaks_for(device["kind"]) if require_tpu else None)
    metrics = {}
    e2e = [m["name"] for m in registry.end_to_end_metrics(bm, workload)]
    suffix = next(SUFFIX[m] for m in e2e if m in SUFFIX)
    names = [m["name"] for m in registry.per_layer_metrics(bm, workload)]
    names += [f"{r_}.{suffix}" for r_ in READERS]
    for name in names:
        v = registry.metric_reader(name, root).read(ctx)
        if v is not None:
            metrics[name] = float(v)
    report = {"workload": workload, "seed": seed, "program": int(program),
              "e2e": out["e2e"], "setup_s": win.t_start - t0,
              "setup_split": split, "load_s": load_s,
              "run_s": time.perf_counter() - t0,
              "attempted": out["attempted"], "failed": out["failed"],
              "notes": out["notes"], "metrics": metrics,
              "spans": len(rec.spans), "flush": flush_split(ctx),
              "cycle": cycle_split(ctx), "device": device}
    w = trace.window(tr)
    if w is not None and tr["devices"]:
        report["device"].update(busy_s=trace.busy_ns(tr) * 1e-9,
                                window_s=(w[1] - w[0]) * 1e-9)
        report["breakdown"] = {
            "device_ops": trace.top_ops(tr),
            "idle_gaps": program_trace.idle_gaps(tr),
            "idle_gaps_bench": trace.idle_gaps(tr)}
    if keep is not None and w is not None:
        path, ms = keep
        with open(path, "w") as f:
            json.dump(cut(tr, ms), f)
    return report


def main(argv=None) -> int:
    import argparse
    from bench import registry
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one traced window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", default="1",
                    help="comma-separated 1 (tracer on) / 0 (off), each "
                         "run for every seed")
    ap.add_argument("--out", help="append each report to this JSON-lines "
                                  "file")
    ap.add_argument("--keep", help="write the first --keep-ms of the first "
                                   "window with program spans here")
    ap.add_argument("--keep-ms", type=float, default=300.0)
    args = ap.parse_args(argv)
    import jax
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache(run.ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bm = registry.load_benchmark(run.ROOT)
    keep = (args.keep, args.keep_ms) if args.keep else None
    for seed in (int(s) for s in args.seeds.split(",")):
        for program in (bool(int(p)) for p in args.program.split(",")):
            try:
                rep = one(bm, args.workload, seed, args.seconds, program,
                          keep=keep if program else None)
            except run.NoChip as e:
                run.log(f"trace_program: {e}")
                return 2
            if program:
                keep = None
            line = json.dumps(rep)
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
