"""Finds every piece of the benchmark by the name `BENCHMARK.json` gives it.

A configuration is `bench/configs/<config>.json` (the file its entry
names), a traffic mix is `bench/traffic/<mix>.json`, a per-layer metric is
`bench/metrics/<metric>.py` (or, where no file carries the full name, the
file of the part before its first dot: `idle_pct.lat` reads
`idle_pct.py`).  A configuration names the plain reference it is checked
against (`bench/refs/<reference>.py`) and the driver that serves it
(`bench/drivers/<system>.py`).  Adding a cell, a mix or a metric is adding
files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bm: dict, workload: str) -> dict:
    for cell in bm["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config_entry(bm: dict, name: str) -> dict:
    for entry in bm["configs"]:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_config(bm: dict, name: str, root: Path = ROOT) -> dict:
    with open(Path(root) / config_entry(bm, name)["file"]) as f:
        return json.load(f)


def load_traffic(name: str, root: Path = ROOT) -> dict:
    with open(Path(root) / "bench" / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    mix.setdefault("name", name)
    return mix


def _module_from(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str, root: Path = ROOT):
    return _module_from(Path(root) / "bench" / "refs" / f"{name}.py",
                        f"bench_ref_{name}")


def load_driver(name: str, root: Path = ROOT):
    return _module_from(Path(root) / "bench" / "drivers" / f"{name}.py",
                        f"bench_driver_{name}")


def metric_reader(name: str, root: Path = ROOT):
    """The module whose `read(ctx)` reads the per-layer metric `name`."""
    base = Path(root) / "bench" / "metrics"
    for stem in (name, name.split(".", 1)[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            return _module_from(path, "bench_metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                            f"under {base}")


def _reported(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end_metrics(bm: dict, workload: str) -> list:
    return [m for m in bm["end_to_end"] if _reported(m, workload)]


def per_layer_metrics(bm: dict, workload: str) -> list:
    """Per-layer metrics of a cell: those that list it, or list no cells
    and move an end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_metrics(bm, workload)}
    return [m for m in bm["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
