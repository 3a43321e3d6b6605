"""A configuration, a traffic mix and a per-layer metric added as new
files, with new entries in BENCHMARK.json, run without editing any file
the benchmark already has."""
import hashlib
import json
import os

from conftest import run_tiny

from bench import registry


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tiny_root):
    before = _digests(tiny_root)
    bench = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench, "configs", "fig8-fleet.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-four", tenants=4)
    with open(os.path.join(bench, "configs", "tiny-four.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "uniform2.json"), "w") as f:
        json.dump({"loop": "open", "rate_per_s": 40,
                   "tenant_dist": "uniform", "rhs_pool": 64}, f)
    with open(os.path.join(bench, "metrics", "answered.py"), "w") as f:
        f.write('UNIT = "rhs"\n\n\ndef read(ctx):\n'
                '    return ctx.counters.get("answered")\n')
    bm_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-four", "source": "test",
                          "file": "bench/configs/tiny-four.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-four.uniform2",
                            "config": "tiny-four", "traffic": "uniform2",
                            "chips": 1, "why": "test"})
    bm["end_to_end"][0].setdefault("workloads", []).append(
        "tiny-four.uniform2")
    bm["per_layer"].append({"name": "answered.new", "unit": "rhs",
                            "better": "higher", "source": "program_counter",
                            "layer": "engine", "moves": "p50_ms",
                            "workloads": ["tiny-four.uniform2"]})
    with open(bm_path, "w") as f:
        json.dump(bm, f)

    bm = registry.load_benchmark(tiny_root)
    assert registry.load_config(bm, "tiny-four", tiny_root)["tenants"] == 4
    assert registry.load_traffic("uniform2", tiny_root)["rate_per_s"] == 40
    assert [m["name"] for m in registry.per_layer_metrics(
        bm, "tiny-four.uniform2")] == ["answered.new"]
    assert registry.metric_reader("answered.new", tiny_root).UNIT == "rhs"
    # a dotted name falls back to the reader of its first part
    assert registry.metric_reader("idle_pct.any", tiny_root).UNIT == "%"

    r = run_tiny(tiny_root, "tiny-four.uniform2", traced=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["answered.new"]["value"] > 0
    r = run_tiny(tiny_root, "tiny-four.uniform2")
    assert set(r["metrics"]) == {"p50_ms", "setup_s"}
    assert _digests(tiny_root).items() >= before.items()
