"""The fan-in cell ``femnist-cnn-prime-f32m3.flood`` (the coordinator as
shipped in every knob: the default mask Prime/F32/B0/M3, a fold batch of 64,
192 uploads of a 6.6M-element vector over 64 connections): what its file
states against the sibling's, the arithmetic of its sizes, the metrics that
read the intake under a fan-in, and its control. Its toy rehearsal with and
without ``--trace`` at two batches of 4 is a case of ``test_rehearsal.py``
too (every cell of ``BENCHMARK.json`` is); the rehearsals here keep the
cell's 64 connections and send 32 uploads over them."""

import pytest

from benchmark.harness import data, replay, sizing
from benchmark.tests import toy
from xaynet_tpu.server.rest import BODY_READERS, DIRECT_BODY_MIN

CELL = "femnist-cnn-prime-f32m3.flood"
SIBLING = "resnet50-f32m6"
BENCH = data.load_benchmark()
FAN_IN = ("rest.reader_full_share", "rest.bodies_resident_max", "loop.cpu_share",
          "loop.cpu_ms_per_update")
N, HBM = 6_603_710, 17_179_869_184
# four fold batches of 8 at toy size over the cell's 64 connections, a dyadic scalar
FOUR = toy.TOY + ["--set", "batch_size=8", "--set", "updates_per_round=32",
                  "--set", "scalar_denominator=32", "--seconds", "10"]


def test_the_file_has_the_siblings_keys_and_every_knob_as_shipped():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], SIBLING))
    assert (cell["traffic"], cell["chips"]) == ("flood", 1)
    assert list(cfg) == list(sib)
    same = {key for key in cfg if cfg[key] == sib[key]}
    assert {"n_limbs", "add_shift", "exp_shift", "sum_participants", "wire_format",
            "single_message_uploads", "toml", "reduced", "guarantees"} <= same
    # configs/config.toml: the default mask, the shipped fold batch, and
    # nothing set but the device
    assert cfg["mask"] == {"group_type": "prime", "data_type": "f32", "bound_type": "b0",
                           "model_type": "m3"}
    assert cfg["toml"] == {"aggregation": {"device": True}}
    assert (cfg["batch_size"], cfg["bytes_per_number"], cfg["order_bits"]) == (64, 6, 45)
    assert cfg["reduced"] == ["updates_per_round", "sum_participants"]
    assert "3,550" in cfg["reduced_from"]["updates_per_round"]
    assert cfg["check"]["sample_positions"] == 0  # every position is compared
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"] != sib["source"]
    assert entry["reduced"] == cfg["reduced"]
    traffic = data.load_traffic(cell["traffic"])
    assert (traffic["arrival"], traffic["concurrency"], traffic["request_timeout_s"]) \
        == ("flood", 64, 120.0)


def test_the_sizes_add_up():
    cell = data.load_cell(CELL, BENCH)
    cfg = data.load_config(cell["config"], BENCH)
    # LEAF's FEMNIST CNN: conv 5x5 1->32, conv 5x5 32->64, dense 3136->2048, dense 2048->62
    layers = (5 * 5 * 1 * 32 + 32, 5 * 5 * 32 * 64 + 64, 7 * 7 * 64 * 2048 + 2048, 2048 * 62 + 62)
    assert layers == (832, 51_264, 6_424_576, 127_038) and sum(layers) == cfg["model_length"] == N
    n = replay.n_uploads(data.load_traffic(cell["traffic"]), cfg, 51.0)
    k, bpn, limbs = cfg["batch_size"], cfg["bytes_per_number"], cfg["n_limbs"]
    assert n == 192 and n // k == 3 and n % k == 0  # ISSUE 42's steadiness rule: not 128
    den = cfg["scalar_denominator"]
    assert den & (den - 1) == 0 and den >= n  # a power of two: the SDK's encode is exact
    assert n * 2 * cfg["add_shift"] * cfg["exp_shift"] < 2**63  # the reference sums in int64
    assert n <= 1_000  # M3
    # 45 bits in 6 bytes and two limbs
    assert 2**44 < 20_000_000_000_021 < 2**45 and bpn == 6 and limbs == 2
    # an upload, the round through the socket, one staged batch, the accumulator
    assert bpn * N == 39_622_260 and n * bpn * N == 7_607_473_920
    assert k * bpn * N == 2_535_824_640 and 4 * limbs * N == 52_829_680
    assert sizing.fold_bytes(k, bpn, limbs, N) == 2_535_824_640 + 2 * 52_829_680
    # two staged batches and the accumulator are over a quarter of the chip
    assert 2 * k * bpn * N + 4 * limbs * N > HBM // 4
    # four times as many connections as the server has direct readers
    assert data.load_traffic(cell["traffic"])["concurrency"] == 4 * BODY_READERS
    assert bpn * N > DIRECT_BODY_MIN


def test_every_per_layer_metric_lists_the_cell_and_the_new_ones_list_every_cell():
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells[-1] == CELL
    assert not [m["name"] for m in BENCH["per_layer"] if CELL not in m.get("workloads", [CELL])]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == list(FAN_IN)
    for name in FAN_IN:
        metric = by_name[name]
        assert metric["workloads"] == cells and metric["moves"] == "updates_per_s"
        assert (metric["layer"], metric["better"]) == ("message pipeline", "lower")
        assert data.load_layer_metric(name)["reader"] in ("prom_ratio", "prom_gauge")
    share = data.load_layer_metric("rest.reader_full_share")["args"]
    assert share["num"]["labels"] == {"reason": "no_reader"}
    assert share["den"]["labels"] == {"reason": "large|no_reader"}


def test_weights_rounded_to_bfloat16_fail_correct_on_the_fan_in_cell():
    rc, result, out, err = toy.run_cell(CELL, FOUR + ["--control", "bf16"])
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    assert "positions differing from the plain reference" in out and "FAILED" in out


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_over_64_connections_reports_the_intake(trace, monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one device, as on the chip
    rc, result, out, err = toy.run_cell(CELL, FOUR, trace=trace)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert "32 accepted, 4 batches folded" in out
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    if not trace:
        assert set(values) == {"updates_per_s", "round_tail_s", "setup_s"}
        return
    # a toy body (120 kB) is under DIRECT_BODY_MIN: every read is `small`, no
    # large body asked for a reader, and a share of none is left out
    assert set(FAN_IN) - set(values) == {"rest.reader_full_share"}
    assert 1 <= values["rest.bodies_resident_max"] <= 32
    assert 0.0 < values["loop.cpu_share"] <= 100.0
    assert values["loop.cpu_ms_per_update"] > 0.0
    assert values["stage.at_arrival_share"] == 100.0
    assert values["stage.bytes_per_update"] == 6 * 20011
