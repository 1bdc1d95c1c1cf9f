"""The cell ``resnet50-f32m6-packedwire.flood`` (the two-batch ResNet-50 round
of a federation on the packed wire: every upload a v2 byte-planar body):
what its file states against the sibling's, the arithmetic of an upload, the
two metrics that read the wire counter and where, and its traced rehearsal
beside the sibling's. Its plain toy rehearsal is a case of
``test_rehearsal.py`` too (every cell of ``BENCHMARK.json`` is)."""

from benchmark.harness import coordinator, data
from benchmark.tests import toy

CELL = "resnet50-f32m6-packedwire.flood"
SIBLING = "resnet50-f32m6-multibatch"
BENCH = data.load_benchmark()
OWN = {"name", "source", "deployment", "wire_format", "reduced_from", "assumed", "guarantees"}
WIRE = ("wire.packed_share", "wire.copy_share")
COUNTER = "xaynet_update_wire_bytes_total"
N = 25_557_032
# two fold batches of 4 at toy size, as the cell has two of 12
TWO = toy.TOY + ["--set", "updates_per_round=8", "--set", "scalar_denominator=8",
                 "--seconds", "10"]


def test_the_file_equals_the_siblings_outside_the_keys_that_state_the_wire():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], SIBLING))
    assert (cell["traffic"], cell["chips"]) == ("flood8", 1)
    assert list(cfg) == list(sib)
    assert {key for key in cfg if cfg[key] != sib[key]} <= OWN
    assert (cfg["wire_format"], sib["wire_format"]) == ("packed", "legacy")
    assert cfg["toml"] == {"aggregation": {"device": True}}  # no key of the program but the wire
    assert "wire_format = \"packed\"" in coordinator.config_toml(cfg, 24, 1)
    assert cfg["guarantees"][:4] == sib["guarantees"]
    assert len(cfg["guarantees"]) == 5 and "v1 body would be accepted" in cfg["guarantees"][-1]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"] != sib["source"] and len(entry["source"]) <= 200
    assert all(part in entry["source"] for part in ("wire_format", "DESIGN", "1512.03385", "BASELINE"))
    assert entry["reduced"] == cfg["reduced"] == ["updates_per_round", "sum_participants"]
    assert cfg["reduced_from"] == sib["reduced_from"]
    assert {"wire_format", "upload_bytes"} <= set(cfg["assumed"])
    assert BENCH["configs"][-1] is entry and BENCH["workloads"][-1] is cell  # appended


def test_an_uploads_bytes_are_the_siblings():
    cfg = data.load_config(data.load_cell(CELL, BENCH)["config"], BENCH)
    limbs, bpn, k = cfg["n_limbs"], cfg["bytes_per_number"], cfg["batch_size"]
    assert (cfg["model_length"], limbs, bpn, k, cfg["updates_per_round"]) == (N, 2, 7, 12, 24)
    assert bpn * N == 178_899_224  # the element block, as 7 planes of N bytes
    assert bpn < 4 * limbs  # packed staging: the planes are the slot's layout
    assert 24 * bpn * N == 4_293_581_376  # through the socket in the window
    assert k * bpn * N == 2_146_790_688  # one staged batch on the chip


def test_the_wire_metrics_list_every_cell_and_the_cell_is_in_the_siblings_lists():
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells[-1] == CELL
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    sibling_cell = f"{SIBLING}.flood"
    for name, metric in by_name.items():
        listed = metric.get("workloads", cells)
        assert (CELL in listed) == (sibling_cell in listed), name
    for name in WIRE:
        metric = by_name[name]
        # guards: 0.0 where no upload is v2, so they list every cell
        assert metric["workloads"] == cells and metric["moves"] == "updates_per_s"
        assert (metric["layer"], metric["better"], metric["unit"], metric["source"]) == (
            "update phase and staged aggregator", "higher", "%", "program_counter")
        spec = data.load_layer_metric(name)
        assert spec["reader"] == "prom_ratio" and spec["args"]["span"] == ["open", "end"]
        # over all of the counter: a legacy cell reads 0.0 and not nothing
        assert spec["args"]["den"] == {"name": COUNTER}
        assert spec["args"]["num"]["name"] == COUNTER and spec["args"]["scale"] == 100.0
    assert data.load_layer_metric(WIRE[0])["args"]["num"]["labels"] == {"wire": "packed"}
    assert data.load_layer_metric(WIRE[1])["args"]["num"]["labels"] == {
        "wire": "packed", "route": "copy"}
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == list(WIRE)  # appended


def test_traced_rehearsals_read_the_wire_in_the_cell_and_in_the_sibling(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one device, as on the chip: no pad columns
    rc, result, out, err = toy.run_cell(CELL, TWO, trace=1, seed=2**31 + 13)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert "8 accepted, 2 batches folded" in out
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert (values[WIRE[0]], values[WIRE[1]]) == (100.0, 100.0)
    assert values["codec.generic_share"] == 0.0  # no transposing fallback, nothing in numpy
    assert values["stage.bytes_per_update"] == 7 * 20011  # the planes, byte for byte
    assert values["stage.at_arrival_share"] == 100.0
    assert values["pipeline.stage_closure"] > 90.0

    rc, result, out, err = toy.run_cell(f"{SIBLING}.flood", TWO, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert (values[WIRE[0]], values[WIRE[1]]) == (0.0, 0.0)
