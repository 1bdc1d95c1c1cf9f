"""The benchmark's data files, found by the names in ``BENCHMARK.json``.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own; a later PR adds files and ``BENCHMARK.json``
entries and edits nothing that is here.
"""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, bench: dict) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have: {known})")


def load_config(name: str, bench: dict | None = None, root: str = ROOT) -> dict:
    """A configuration's file, by the path ``BENCHMARK.json`` gives it."""
    bench = bench if bench is not None else load_benchmark(root)
    for entry in bench["configs"]:
        if entry["name"] == name:
            cfg = _read_json(os.path.join(root, entry["file"]))
            cfg.setdefault("name", name)
            return cfg
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _read_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def load_layer_metric(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _read_json(os.path.join(bench_dir, "layer_metrics", f"{name}.json"))


def load_peaks(bench_dir: str = BENCH_DIR) -> dict:
    return _read_json(os.path.join(bench_dir, "peaks.json"))


def metrics_for(cell: str, bench: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    with no ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind] if "workloads" not in m or cell in m["workloads"]]
