"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from BENCHMARK.json
and the files it names (bench/registry.py).  The run makes its inputs from
the seed, sets up (programming and warm-up, reported as `setup_s`),
measures for `--seconds`, then compares what the window served with the
plain reference.  `--trace 0` reports the cell's end-to-end metrics;
`--trace 1` records a profiler trace of the window and reports its
per-layer metrics.  The last line of standard output is one JSON object;
the last lines of standard error give each number compared beside its
limit.  A backend other than a TPU, or fewer chips than the cell asks
for, ends the run with exit code 2 and no result.

JAX's persistent compilation cache is JAX_COMPILATION_CACHE_DIR where that
is set, else `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SAMPLE = {"fleet": 4096, "mc_sweep": 2}   # answers / calls compared


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    backend = jax.default_backend()
    if require_tpu and backend != "tpu":
        raise NoChip(f"no TPU found: JAX backend is {backend!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Window:
    """Opens and closes the measured window: counters, spans and, in a
    traced run, the profiler."""

    def __init__(self, recorder, compiles, trace_dir=None):
        self.rec, self.cc, self.trace_dir = recorder, compiles, trace_dir
        self.t_start = self.t_end = None

    def start(self):
        import jax
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.rec.active = self.cc.active = True
        self.t_start = time.perf_counter()

    def end(self):
        import jax
        self.t_end = time.perf_counter()
        self.rec.active = self.cc.active = False
        self.ann.__exit__(None, None, None)
        if self.trace_dir:
            jax.profiler.stop_trace()


def execute(workload: str, seed: int, seconds: float, traced: bool, *,
            root: str = ROOT, require_tpu: bool = True,
            candidates=("program",), t_start: float = T_START) -> dict:
    """One run; returns the result line's fields (and, for each extra
    candidate, its compared numbers under "candidates")."""
    from bench import registry
    bm = registry.load_benchmark(root)
    cell = registry.find_cell(bm, workload)
    device = device_info(cell["chips"], require_tpu)

    import jax
    from repro.runtime.compile_cache import use_compile_cache
    cfg = registry.load_config(bm, cell["config"], root)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in names}
    try:
        cache = use_compile_cache(root)
        # every program goes to the cache, so a later run's set-up loads
        # what the first one compiled
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        return _execute(bm, workload, cell, cfg, seed, seconds, traced,
                        root, require_tpu, candidates, t_start, device,
                        cache)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def _execute(bm, workload, cell, cfg, seed, seconds, traced, root,
             require_tpu, candidates, t_start, device, cache) -> dict:
    from bench import check, registry
    from bench.spans import CompileCounter, Recorder
    mix = registry.load_traffic(cell["traffic"], root)
    driver = registry.load_driver(cfg["system"], root)
    ref = registry.load_reference(cfg["reference"], root)
    rec, cc = Recorder(), CompileCounter()
    run = driver.Run(cfg, mix, seed, rec)
    split = run.setup()
    # set-up's objects (compiled programs, warm-up garbage) move out of
    # the collector's reach, so a full collection in the window does not
    # walk them: the window sees the pauses serving itself causes
    gc.collect()
    gc.freeze()
    log(f"set-up split (not a metric): {json.dumps(split)}; "
        f"compile cache {cache}")

    trace_dir = None
    if traced:
        trace_dir = os.path.join(root, ".bench_trace", f"{workload}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = Window(rec, cc, trace_dir)
    out = run.measure(seconds, win)
    gc.unfreeze()
    setup_s = win.t_start - t_start
    out["notes"]["compiles"] = cc.total
    log(f"window notes (not metrics): {json.dumps(out['notes'])}")
    memory_peak = run.memory_peak()
    run.release()

    numbers = run.compare(ref, candidates, SAMPLE[cfg["system"]])
    correct, checks = check.judge(numbers["program"], cfg["check"])

    device["memory_peak_bytes"] = memory_peak
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {},
              "device": device}
    if not traced:
        e2e = dict(out["e2e"], setup_s=setup_s)
        for m in registry.end_to_end_metrics(bm, workload):
            result["metrics"][m["name"]] = {"value": float(e2e[m["name"]]),
                                            "unit": m["unit"]}
    else:
        from bench import peaks, trace
        tr = trace.load(trace_dir)
        w = trace.window(tr)
        ctx = SimpleNamespace(trace=tr if tr["devices"] else None,
                              counters=out.get("counters", {}),
                              spans=rec.spans, cfg=cfg,
                              latency_s=out.get("latency_s"),
                              compiles=cc.total, window_s=seconds,
                              peaks=peaks.peaks_for(device["kind"])
                              if require_tpu else None)
        for m in registry.per_layer_metrics(bm, workload):
            value = registry.metric_reader(m["name"], root).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        if w is not None and tr["devices"]:
            device["busy_s"] = trace.busy_ns(tr) * 1e-9
            device["window_s"] = (w[1] - w[0]) * 1e-9
            result["breakdown"] = {"device_ops": trace.top_ops(tr),
                                   "idle_gaps": trace.idle_gaps(tr)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    if len(candidates) > 1:
        # each candidate's numbers, judged by the same comparison
        result["candidates"] = {
            c: dict(numbers[c],
                    correct=bool(check.judge(numbers[c], cfg["check"])[0]))
            for c in candidates}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
