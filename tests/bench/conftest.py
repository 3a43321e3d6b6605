"""The benchmark's own tests: its package lives at the repo root."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

TINY_FLEET = {"n": 32, "array_size": 8, "tenants": 3,
              "engine": {"max_pending": 16}}
TINY_MC = {"n": 32, "array_size": 8, "draws": 4}


def make_tiny_root(path):
    """A copy of the benchmark (bench/ and BENCHMARK.json) whose
    configurations are cut to CPU-test size and whose mixes offer a load
    a CPU sustains.  Returns the root."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for entry in bm["configs"]:
        file = os.path.join(path, entry["file"])
        with open(file) as f:
            cfg = json.load(f)
        cfg.update(TINY_FLEET if cfg["system"] == "fleet" else TINY_MC)
        with open(file, "w") as f:
            json.dump(cfg, f)
    zipf = os.path.join(path, "bench", "traffic", "zipf.json")
    with open(zipf) as f:
        mix = json.load(f)
    mix["rate_per_s"] = 50
    mix["wait_after_s"] = 5.0
    with open(zipf, "w") as f:
        json.dump(mix, f)
    hot = os.path.join(path, "bench", "traffic", "hot1.json")
    with open(hot) as f:
        mix = json.load(f)
    mix["outstanding"] = 8
    mix["wait_after_s"] = 5.0
    with open(hot, "w") as f:
        json.dump(mix, f)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return str(path)


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


def run_tiny(root, workload, traced=False, seed=2**31 + 11, seconds=1.0):
    """One benchmark run on the CPU at test size (the chip check skipped)."""
    import time

    from bench import run
    return run.execute(workload, seed, seconds, traced, root=root,
                       require_tpu=False, t_start=time.perf_counter())
