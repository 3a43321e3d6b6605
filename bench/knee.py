"""Find the knee of an open-loop fleet cell once, by a sweep on the chip.

    python3 bench/knee.py --workload fig8-fleet.zipf --seed 1 --seconds 4 \
        --rates 200,400,800,1200,1600

One process sets the cell up once, then offers the cell's traffic mix at
each rate in turn for `--seconds` and prints, per rate, the latency
percentiles from the scheduled send time, the requests that failed, the
requests still unanswered when the window closed (a backlog that grows
with the window), and the fleet's drains.  The knee is the highest rate
with no failures, no drains and no growing backlog; the cell runs at
about four fifths of it.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class _NoWindow:
    def start(self):
        pass

    def end(self):
        self.t_end = time.perf_counter()


def main(argv=None) -> int:
    from bench import registry, stats
    from bench.run import NoChip, device_info, log
    from bench.spans import Recorder
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bm = registry.load_benchmark()
    cell = registry.find_cell(bm, args.workload)
    try:
        device_info(cell["chips"])
    except NoChip as e:
        log(f"knee: {e}")
        return 2
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache(ROOT)
    cfg = registry.load_config(bm, cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    run = registry.load_driver(cfg["system"]).Run(cfg, mix, args.seed,
                                                  Recorder())
    log(f"set-up: {json.dumps(run.setup())}")
    for rate in (float(r) for r in args.rates.split(",")):
        run.mix = dict(mix, rate_per_s=rate)
        drains0 = run.fleet.stats.drains
        win = _NoWindow()
        out = run._open(args.seconds, win)
        unanswered_at_close = int((run.t_done > win.t_end).sum()
                                  + (~(run.t_done == run.t_done)).sum())
        row = {"rate_per_s": rate, "requests": out["attempted"],
               "failed": out["failed"], "p50_ms": out["e2e"]["p50_ms"],
               "p95_ms": stats.percentile(out["latency_s"], 95) * 1e3,
               "backlog_at_close": unanswered_at_close,
               "drains": run.fleet.stats.drains - drains0,
               **out["notes"]}
        print(json.dumps(row), flush=True)
    run.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
