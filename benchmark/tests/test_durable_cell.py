"""The cell ``resnet50-f32m6-durable.flood`` (the two-batch ResNet-50 round
kept by a coordinator that keeps its round journal): what its file states
against the sibling's, which metrics read the journal and where, the
arithmetic of an entry's size, and its rehearsal with and without a trace on
a directory that an earlier run has left. Its plain toy rehearsal is a case
of ``test_rehearsal.py`` too (every cell of ``BENCHMARK.json`` is)."""

import json
import os
import shutil

import pytest

from benchmark.harness import data
from benchmark.tests import toy

CELL = "resnet50-f32m6-durable.flood"
SIBLING = "resnet50-f32m6-multibatch"
BENCH = data.load_benchmark()
OWN = {"name", "source", "deployment", "toml", "reduced_from", "assumed", "guarantees"}
GUARDS = ("journal.update_share", "journal.drain_ms", "journal.fetch_ms", "journal.serialise_ms",
          "journal.store_ms", "journal.tail_ms", "journal.mb_per_round")
CLOSURE = "journal.stage_closure"
N = 25_557_032
# two fold batches of 4 at toy size, as the cell has two of 12
TWO = toy.TOY + ["--set", "updates_per_round=8", "--set", "scalar_denominator=8",
                 "--seconds", "10"]


def test_the_file_equals_the_siblings_outside_the_keys_that_state_the_guarantee():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], SIBLING))
    assert (cell["traffic"], cell["chips"]) == ("flood8", 1)
    assert list(cfg) == list(sib)
    assert {key for key in cfg if cfg[key] != sib[key]} <= OWN
    assert cfg["toml"] == {
        "aggregation": {"device": True},
        "resilience": {"checkpoint_enabled": True, "checkpoint_every_batches": 1},
        "restore": {"enable": True},
        "storage": {"backend": "filesystem", "coordinator": "file",
                    "model_dir": "./.bench_cache/durable/resnet50-f32m6-durable"},
    }
    # a directory of its own, under what .gitignore lists
    with open(os.path.join(data.ROOT, ".gitignore"), encoding="utf-8") as f:
        assert ".bench_cache/" in f.read().split()
    assert cfg["guarantees"][:3] == sib["guarantees"][:3]
    assert len(cfg["guarantees"]) == 7 and "fsync" in cfg["guarantees"][-1]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"] != sib["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["updates_per_round", "sum_participants"]
    assert {"checkpoint_every_batches", "storage", "journal_share"} <= set(cfg["assumed"])


def test_an_entrys_bytes_add_up():
    cfg = data.load_config(data.load_cell(CELL, BENCH)["config"], BENCH)
    limbs, bpn, k = cfg["n_limbs"], cfg["bytes_per_number"], cfg["batch_size"]
    assert (cfg["model_length"], limbs, bpn, k, cfg["updates_per_round"]) == (N, 2, 7, 12, 24)
    planes = 4 * limbs * N  # the accumulator as the journal holds it: uint32[L, n]
    mask = bpn * N  # the vote's serialised mask
    assert (planes, mask) == (204_456_256, 178_899_224)
    # two `update` entries, Sum2's base, the vote's rewrite, the `unmask` entry
    assert 3 * planes + 2 * (planes + mask) == 1_380_079_728
    # on the chip: one staged batch and the accumulator, as the sibling
    assert k * bpn * N == 2_146_790_688


def test_the_journals_metrics_are_present_and_list_their_cells():
    cells = [c["name"] for c in BENCH["workloads"]]
    assert CELL in cells
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    missing = [name for name, m in by_name.items()
               if CELL not in m.get("workloads", [CELL])]
    assert not missing  # every per-layer metric is reported in the cell
    moves = {"journal.update_share": "updates_per_s", "journal.drain_ms": "updates_per_s",
             "journal.fetch_ms": "updates_per_s", "journal.serialise_ms": "round_tail_s",
             "journal.store_ms": "round_tail_s", "journal.tail_ms": "round_tail_s",
             "journal.mb_per_round": "round_tail_s"}
    for name in GUARDS:
        metric = by_name[name]  # present, wherever in the list
        # a guard: 0.0 where no journal is kept, so it lists every cell
        assert metric["workloads"] == cells and metric["moves"] == moves[name]
        assert (metric["layer"], metric["better"]) == ("round journal", "lower")
        spec = data.load_layer_metric(name)
        assert spec["reader"] == "prom_ratio"
        # the denominator moves in every cell
        assert spec["args"]["den"]["name"] in (
            "xaynet_event_loop_wall_seconds_total", "xaynet_unmask_seconds_count")
    closure = by_name[CLOSURE]
    assert closure["workloads"] == [CELL]  # reads nothing where no journal is kept
    assert (closure["layer"], closure["better"], closure["moves"]) \
        == ("round journal", "higher", "round_tail_s")
    spec = data.load_layer_metric(CLOSURE)["args"]
    assert spec["num"]["labels"] == {"stage": "drain|fetch|dicts|serialise|store"}
    assert spec["den"]["labels"] == {"stage": "total"}


@pytest.fixture
def clean_directory():
    cfg = data.load_config(data.load_cell(CELL, BENCH)["config"], BENCH)
    path = os.path.join(data.ROOT, cfg["toml"]["storage"]["model_dir"])
    shutil.rmtree(path, ignore_errors=True)
    yield path
    shutil.rmtree(path, ignore_errors=True)


def test_two_rehearsals_back_to_back_on_one_directory_report_the_journal(clean_directory,
                                                                          monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one device, as on the chip
    rc, result, out, err = toy.run_cell(CELL, TWO, trace=0)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert "8 accepted, 2 batches folded" in out
    assert set(result["metrics"]) == {"updates_per_s", "round_tail_s", "setup_s"}
    # what the first run left: the coordinator's state, two stored models, no journal
    left = sorted(os.listdir(clean_directory))
    assert "coordinator_state.json" in left and "coordinator_state.json.ckpt" not in left
    assert len([f for f in left if f.endswith(".bin")]) == 2
    with open(os.path.join(clean_directory, "coordinator_state.json"), encoding="utf-8") as f:
        assert json.load(f)["latest_global_model_id"]

    # the second start restores from it and serves a correct round
    rc, result, out, err = toy.run_cell(CELL, TWO, trace=1, seed=2**31 + 11)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert set(GUARDS) | {CLOSURE} <= set(values)
    assert all(values[name] > 0.0 for name in GUARDS)
    assert 50.0 < values[CLOSURE] <= 100.0  # a toy write is executor hops; at size 95 or more
    # two `update` entries, the base, the vote's rewrite, the `unmask` entry:
    # three of 8 B an element and two with the 7 B mask besides (the seal and
    # the sum participant's entry fall before the window opens)
    n = 20011
    assert 1e6 * values["journal.mb_per_round"] == pytest.approx(
        3 * 8 * n + 2 * (8 + 7) * n, rel=0.05)
    assert len([f for f in os.listdir(clean_directory) if f.endswith(".bin")]) == 4


def test_the_guards_read_zero_where_no_journal_is_kept():
    rc, result, out, err = toy.run_cell("resnet50-f32m6-multibatch.flood", TWO, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert {name: values[name] for name in GUARDS} == dict.fromkeys(GUARDS, 0.0)
    assert CLOSURE not in values
