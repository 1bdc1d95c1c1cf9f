"""The cell ``resnet50-f32m6-wireingest.flood`` (the two-batch ResNet-50 round
of v1 uploads under ``[aggregation] wire_ingest = true``: the chip parses and
checks): what its file states against the sibling's, the arithmetic of its
rows in HBM, which per-layer lists it is in and which it is left out of, the
sizing of the two rooflines, and its traced toy rehearsal beside the
sibling's. Presence and membership only: no position in ``per_layer``,
``configs`` or ``workloads`` is pinned, so a later cell breaks nothing here."""

import pytest

from benchmark.harness import coordinator, data, ingest_sizing
from benchmark.readers import trace_ingest_op
from benchmark.tests import toy

CELL = "resnet50-f32m6-wireingest.flood"
SIBLING = "resnet50-f32m6-multibatch"
BENCH = data.load_benchmark()
BY_NAME = {m["name"]: m for m in BENCH["per_layer"]}
OWN = {"name", "source", "deployment", "toml", "assumed", "guarantees"}
N = 25_557_032
NEW = ("ingest.h2d_ms", "ingest.h2d_gbps", "ingest.unpack_wait_ms", "ingest.unpack_device_ms",
       "ingest.unpack_roofline", "ingest.fold_roofline", "ingest.stack_device_ms",
       "ingest.resident_rows_max")
# nothing to read on this road: no host batch, ring, row copier or slot write;
# and the two rooflines that reckon one PACKED batch of batch_size
LEFT_OUT = ("fold_roofline", "fold.shard_roofline", "journal.stage_closure",
            "stream.h2d_gbps", "stream.shard_h2d_gbps", "stream.ring_wait_ms",
            "stream.ring_reuse_share", "stream.h2d_early_share", "stream.commit_ms",
            "update.to_planar_ms", "update.to_planar_cpu_ms", "update.to_planar_faults")
TWO = toy.TOY + ["--set", "updates_per_round=8", "--set", "scalar_denominator=8",
                 "--seconds", "10"]


def test_the_file_equals_the_siblings_outside_the_keys_that_state_the_road():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], SIBLING))
    assert (cell["traffic"], cell["chips"]) == ("flood8", 1) and len(cell["why"]) <= 200
    assert list(cfg) == list(sib)
    assert {key for key in cfg if cfg[key] != sib[key]} <= OWN
    assert cfg["toml"] == {"aggregation": {"device": True, "wire_ingest": True}}
    assert cfg["wire_format"] == sib["wire_format"] == "legacy"
    text = coordinator.config_toml(cfg, 24, 1)
    assert "wire_ingest = true" in text and 'wire_format = "legacy"' in text
    assert cfg["guarantees"][:4] == sib["guarantees"] and len(cfg["guarantees"]) == 5
    assert "before the update's seed dictionary is inserted" in cfg["guarantees"][4]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"] != sib["source"] and len(entry["source"]) <= 200
    assert all(part in entry["source"] for part in ("wire_ingest", "DESIGN", "1512.03385", "BASELINE"))
    assert entry["reduced"] == cfg["reduced"] == ["updates_per_round", "sum_participants"]
    assert cfg["reduced_from"] == sib["reduced_from"]
    assert {"batch_size", "wire_ingest", "upload_bytes"} <= set(cfg["assumed"])


def test_the_rows_in_hbm_are_what_the_file_says():
    cfg = data.load_config(data.load_cell(CELL, BENCH)["config"], BENCH)
    limbs, bpn, k = cfg["n_limbs"], cfg["bytes_per_number"], cfg["batch_size"]
    assert (cfg["model_length"], limbs, bpn) == (N, 2, 7) and cfg["updates_per_round"] % k == 0
    assert (k, cfg["updates_per_round"]) in ((12, 24), (16, 16))  # ISSUE 54: no other size
    row = 4 * limbs * N
    assert row == 204_456_256 and bpn * N == 178_899_224
    held = k * row + 8 * row + row + bpn * N  # rows, a chunk, the accumulator, a body
    assert held / 17_179_869_184 > 0.25


def test_the_cell_is_in_the_lists_that_read_a_number_on_its_road_and_in_no_other():
    cells = [c["name"] for c in BENCH["workloads"]]
    sibling_cell = f"{SIBLING}.flood"
    for name in NEW:
        metric = BY_NAME[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "updates_per_s", name
        assert metric["layer"] == "device ingest", name
        data.load_layer_metric(name)
    for name in LEFT_OUT:
        assert CELL not in BY_NAME[name]["workloads"], name
    for name, metric in BY_NAME.items():
        if name in NEW or name in LEFT_OUT:
            continue
        listed = metric.get("workloads", cells)
        assert (CELL in listed) == (sibling_cell in listed), name
    share = BY_NAME["wire.device_share"]
    assert set(share["workloads"]) >= set(cells[:7]) | {CELL}  # 0.0 in the seven, 100 here
    spec = data.load_layer_metric("wire.device_share")
    assert spec["reader"] == "prom_ratio" and spec["args"]["den"] == {
        "name": "xaynet_update_wire_bytes_total"}
    assert spec["args"]["num"]["labels"] == {"route": "device"}


def test_the_rooflines_count_the_least_the_work_can_move():
    assert ingest_sizing.unpack_bytes(7, 2, N) == (7 + 8) * N
    assert ingest_sizing.resident_fold_bytes(8, 2, N) == 10 * 8 * N
    assert ingest_sizing.chunks(12) == [8, 4] and ingest_sizing.chunks(16) == [8, 8]
    assert ingest_sizing.chunks(3) == [3]
    cfg = {"n_limbs": 2, "bytes_per_number": 7, "model_length": N, "batch_size": 12}
    peak = {"hbm_bytes_per_s": 819e9}
    # an unpack that ran at the roofline reads 100, one ten times slower 10
    at_roof = (7 + 8) * N / 819e9
    ctx = {"cfg": cfg, "peak": peak,
           "trace": {"modules": {"jit_unpack_mask(1)": {"count": 24, "seconds": 24 * at_roof}}}}
    assert trace_ingest_op.read(ctx, "unpack", "jit_unpack_mask") == pytest.approx(100.0)
    ctx["trace"]["modules"]["jit_unpack_mask(1)"]["seconds"] *= 10
    assert trace_ingest_op.read(ctx, "unpack", "jit_unpack_mask") == pytest.approx(10.0)
    # two flushes of 8 + 4: four fold executions, (10 + 6 + 10 + 6) row-units
    fold_s = 2 * (10 + 6) * 8 * N / 819e9
    ctx["trace"]["modules"] = {"jit_fold_planar_batch_pallas(7)": {"count": 4, "seconds": 4 * fold_s}}
    assert trace_ingest_op.read(ctx, "fold", "jit_fold_") == pytest.approx(25.0)
    # nothing to read: no such executable, the CPU stand-in, no peak row
    assert trace_ingest_op.read(ctx, "unpack", "jit_unpack_mask") is None
    assert trace_ingest_op.read({**ctx, "peak": None}, "fold", "jit_fold_") is None
    ctx["trace"]["device_stand_in"] = True
    assert trace_ingest_op.read(ctx, "fold", "jit_fold_") is None


def test_traced_rehearsals_read_the_road_in_the_cell_and_not_in_the_sibling(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one device, as on the chip
    rc, result, out, err = toy.run_cell(CELL, TWO, trace=1, seed=2**31 + 54)
    assert rc == 0 and result["correct"], err[-2000:] + out[-2000:]
    assert "2 batches folded" in out  # a resident fold is a batch: the window closed by count
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["wire.device_share"] == 100.0
    assert metrics["wire.copy_share"] == metrics["wire.legacy_copy_share"] == 0.0
    assert metrics["ingest.resident_rows_max"] == 4.0
    assert metrics["ingest.h2d_ms"] > 0 and metrics["ingest.unpack_wait_ms"] > 0
    assert metrics["ingest.h2d_gbps"] > 0
    assert metrics["stage.at_arrival_share"] == 0.0  # a number: no row was staged on the host
    for name in LEFT_OUT:
        assert name not in metrics
    rc, result, out, err = toy.run_cell(f"{SIBLING}.flood", TWO, trace=1, seed=2**31 + 55)
    assert rc == 0 and result["correct"], err[-2000:]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["wire.device_share"] == 0.0 and not any(k.startswith("ingest.") for k in metrics)
