"""``BENCHMARK.json`` and the data files against the contract's limits that
can be checked without a run."""

import json
import os
import re

import pytest

from benchmark.harness import data

BENCH = data.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    # a full check with the full 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert os.path.getsize(os.path.join(data.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert line_ok(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line_ok(c["source"]) and line_ok(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(data.ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert line_ok(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(pairs) // 4)


def test_cells_configs_and_metrics_hang_together():
    cells = {w["name"] for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs  # each used, each known
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"] and e2e["setup_s"]["bound"] == 0.1
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for cell in cells:
        reported = {m["name"] for m in data.metrics_for(cell, BENCH, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        assert data.metrics_for(cell, BENCH, "per_layer")
    # every `moves` names an end-to-end metric that the same cells report
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(target.get("workloads", cells)), m["name"]
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())  # one spelling per layer


def test_every_name_has_its_data_file_and_reader():
    for w in BENCH["workloads"]:
        traffic = data.load_traffic(w["traffic"])
        assert traffic["arrival"] in ("flood", "poisson")
    for m in BENCH["per_layer"]:
        spec = data.load_layer_metric(m["name"])
        assert os.path.exists(os.path.join(data.BENCH_DIR, "readers", spec["reader"] + ".py"))
    for c in BENCH["configs"]:
        cfg = data.load_config(c["name"], BENCH)
        assert c["reduced"] == cfg["reduced"] and line_ok(c["source"])
        assert cfg["guarantees"] and cfg["assumed"] and cfg["scalar_denominator"] >= cfg["updates_per_round"] and cfg["updates_per_round"] % cfg["batch_size"] == 0
        assert cfg["toml"]["aggregation"]["device"] is True
    peaks = data.load_peaks()
    assert all({"hbm_bytes_per_s", "hbm_bytes", "source"} <= set(row) for row in peaks.values())


def test_files_under_paths_are_named_from_the_allowed_characters():
    for folder, _, files in os.walk(data.BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(folder, name), data.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel) and len(rel) <= 200, rel


def test_configuration_files_match_the_program():
    """The sizes a configuration file states are the program's own for its
    mask: every file under ``configs/``, also one that no cell uses yet."""
    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.ops import limbs

    folder = os.path.join(data.BENCH_DIR, "configs")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["name"] + ".json" == name
        m = cfg["mask"]
        mask = MaskConfig(GroupType[m["group_type"].upper()], DataType[m["data_type"].upper()],
                          BoundType[m["bound_type"].upper()], ModelType[m["model_type"].upper()])
        assert mask.bytes_per_number == cfg["bytes_per_number"]
        assert limbs.n_limbs_for_order(mask.order) == cfg["n_limbs"]
        assert mask.order.bit_length() == cfg["order_bits"]
        assert mask.exp_shift == cfg["exp_shift"] and mask.add_shift == cfg["add_shift"]
        assert cfg["updates_per_round"] <= mask.max_nb_models
        assert cfg["updates_per_round"] % cfg["batch_size"] == 0
        assert cfg["scalar_denominator"] >= cfg["updates_per_round"]
        assert cfg["scalar_denominator"] & (cfg["scalar_denominator"] - 1) == 0  # exact in binary
