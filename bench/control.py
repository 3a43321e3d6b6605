"""Readings that set a cell's limits: the program and the control.

    python3 bench/control.py --workload fig8-fleet.zipf --seconds 3 \
        --seeds 101,102,103

For each seed, one process runs the cell as a benchmark run does (inputs
from the seed, set-up, a window at the cell's own load, the sampled
answers), then compares with the float64 reference both what the program
served and what the control gives for the same sampled requests: the
reference computed one precision step below the stated one
(`bench/refs/blockamc.py` CONTROL), and judges each by the comparison
that decides `correct` (`bench/check.py`) against the configuration's
limits.  One JSON line per seed; the control has to read `correct`
false.  The program's readings over a dozen seeds or more set the lower
end of each limit, the control's the upper (PERF.md).  Not part of a
benchmark run.
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


def main(argv=None) -> int:
    from bench.run import NoChip, execute, log
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-control", action="store_true",
                    help="read the program alone")
    args = ap.parse_args(argv)
    cands = ("program",) if args.no_control else ("program", "control")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            r = execute(args.workload, seed, args.seconds, False,
                        candidates=cands, t_start=t0)
        except NoChip as e:
            log(f"control: {e}")
            return 2
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "failed": r["failed"], "attempted": r["attempted"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()},
                          **r.get("candidates", {"program": dict(
                              {k: c["value"] for k, c in r["checks"].items()},
                              correct=r["correct"])})}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
