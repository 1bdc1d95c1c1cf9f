"""The four-chip cell ``resnet50-f32m6-x4.flood`` (one coordinator over the
four chips of a host, accumulator and fold batches sharded on the model
axis, a batch of 48 that one chip cannot hold): what its file changes from
the one-chip sibling's, the arithmetic of its sizes, the metrics that tell
the shards apart, and its control. Its toy rehearsal with and without
``--trace`` is a case of ``test_rehearsal.py`` (every cell of
``BENCHMARK.json`` is), on the eight CPU devices that the root
``conftest.py`` gives a test run; the rehearsals here take four, as the
host has chips."""

import pytest

from benchmark.harness import data, replay, shard_sizing, sizing
from benchmark.readers import trace_shard_op
from benchmark.tests import toy

CELL = "resnet50-f32m6-x4.flood"
SIBLING = "resnet50-f32m6"
BENCH = data.load_benchmark()
ACROSS_SHARDS = ("fold.shard_roofline", "stream.commit_ms", "stream.shard_h2d_gbps")
N, CHIPS, HBM = 25_557_032, 4, 17_179_869_184


@pytest.fixture
def four_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def test_the_file_is_the_siblings_but_for_the_batch_and_the_mesh():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], SIBLING))
    assert (cell["traffic"], cell["chips"]) == ("flood8", 4)
    assert [c["name"] for c in BENCH["workloads"] if c["chips"] == 4] == [CELL]
    changed = {key for key in set(cfg) | set(sib) if cfg.get(key) != sib.get(key)}
    assert changed == {"name", "source", "deployment", "updates_per_round", "scalar_denominator",
                       "batch_size", "reduced", "reduced_from", "assumed"}
    assert (cfg["updates_per_round"], cfg["batch_size"], cfg["scalar_denominator"]) == (48, 48, 64)
    # ring, dispatch_ahead, kernel = "auto", shard_parallel, packed staging: as shipped
    assert cfg["toml"] == {"aggregation": {"device": True}}
    assert cfg["reduced"] == ["updates_per_round", "sum_participants", "batch_size"]
    assert cfg["reduced_from"]["updates_per_round"] == 10000
    assert cfg["reduced_from"]["batch_size"].startswith("64")
    assert cfg["guarantees"] == sib["guarantees"] and cfg["check"] == sib["check"]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"] != sib["source"]


def test_one_chip_cannot_hold_the_batch_and_four_hold_the_siblings_bytes():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], SIBLING))
    n = replay.n_uploads(data.load_traffic(cell["traffic"]), cfg, 51.0)
    k, bpn, limbs = cfg["batch_size"], cfg["bytes_per_number"], cfg["n_limbs"]
    assert n == k == 48 and cfg["model_length"] == N
    den = cfg["scalar_denominator"]
    assert den & (den - 1) == 0 and den >= n  # a power of two: the SDK's encode is exact
    assert n * 2 * cfg["add_shift"] * cfg["exp_shift"] < 2**63  # the reference sums in int64
    # on one chip the worst case of the footprint passes the chip's memory
    assert min(sizing.footprint(N, limbs, bpn, k).values()) > HBM
    assert sizing.batch_size_for(N, limbs, bpn, HBM) < k
    # over four, a chip stages the one-chip sibling's bytes to the byte
    shard = shard_sizing.shard_length(N, CHIPS)
    assert shard == 6_389_258 and shard * CHIPS == N  # no pad column
    assert k * bpn * shard == sib["batch_size"] * bpn * N == 2_146_790_688
    # and folds them into a quarter of the accumulator
    assert shard_sizing.shard_fold_bytes(k, bpn, limbs, shard) \
        == 48 * 7 * shard + 2 * 4 * 2 * shard == 2_249_018_816
    assert sizing.fold_bytes(k, bpn, limbs, N) == 4 * 2_249_018_816  # what fold_roofline would count
    # 40 would stage under a quarter of a chip at the sibling's measured
    # ratio of peak to staged bytes (29.807% at 2.147 GB)
    assert 29.807 * 40 / 48 < 25.0 < 29.807 * 44 / 48
    assert shard_sizing.shard_length(20011, 8) == 2502  # a length that is padded


def test_the_metrics_across_shards_read_one_shard_or_many_and_the_rest_list_the_cell():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    cells = [c["name"] for c in BENCH["workloads"]]
    # a pipeline of one shard records what they read too (one commit a batch,
    # a copy's span, the shard's length in /healthz), so they list every cell
    for name in ACROSS_SHARDS:
        assert by_name[name]["workloads"] == cells and by_name[name]["moves"] == "updates_per_s"
    assert data.load_layer_metric("fold.shard_roofline")["reader"] == "trace_shard_op"
    assert data.load_layer_metric("stream.commit_ms")["args"]["num"]["name"] \
        == "xaynet_streaming_commit_seconds_sum"
    # the whole model's bytes against one chip's bandwidth would read four
    # times too high here; the parent's shard route has no copy span to read
    without = {m["name"] for m in BENCH["per_layer"] if CELL not in m["workloads"]}
    assert without == {"fold_roofline", "stream.h2d_gbps"}
    assert BENCH["per_layer"][-3:] == [by_name[name] for name in ACROSS_SHARDS]


def _ctx(seconds, count, fold, peak=819e9, stand_in=False):
    modules = {"jit_fold_packed_batch(123)": {"count": count, "seconds": seconds},
               "jit_unmask(9)": {"count": 4, "seconds": 1.0}}
    return {"trace": {"modules": modules, "device_stand_in": stand_in},
            "health": {"end": {"device": {"fold": fold}}},
            "peak": {"hbm_bytes_per_s": peak} if peak else None,
            "cfg": {"batch_size": 48, "bytes_per_number": 7, "n_limbs": 2, "model_length": N}}


def test_shard_roofline_counts_one_shards_bytes_over_one_shards_seconds():
    args = data.load_layer_metric("fold.shard_roofline")["args"]
    sharded = {"shards": 4, "shard_length": 6_389_258}
    floor_s = (48 * 7 * 6_389_258 + 2 * 4 * 2 * 6_389_258) / 819e9
    # four executions, one a chip, 10 ms each
    value = trace_shard_op.read(_ctx(0.040, 4, sharded), **args)
    assert value == pytest.approx(100.0 * floor_s / 0.010)
    assert 0.0 < value < 100.0
    # a program that does not say how it was sharded (the parent), no fold in
    # the window, a device that is not in peaks.json, the CPU stand-in
    assert trace_shard_op.read(_ctx(0.040, 4, {"kernel": "xla"}), **args) is None
    assert trace_shard_op.read(_ctx(0.0, 0, sharded), **args) is None
    assert trace_shard_op.read(_ctx(0.040, 4, sharded, peak=None), **args) is None
    assert trace_shard_op.read(_ctx(0.040, 4, sharded, stand_in=True), **args) is None


def test_weights_rounded_to_bfloat16_fail_correct_on_the_four_chip_cell(four_devices):
    rc, result, out, err = toy.run_cell(CELL, toy.FLOOD + ["--control", "bf16"])
    assert rc == 0, err[-2000:]
    assert result["correct"] is False and result["device"]["count"] == 4
    assert "positions differing from the plain reference" in out and "FAILED" in out


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_four_devices_stages_at_arrival_and_commits_each_batch(trace, four_devices):
    """Two batches of 4 on four shards, at a length the mesh has to pad."""
    rc, result, out, err = toy.run_cell(CELL, toy.FLOOD, trace=trace)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["device"]["count"] == 4 and "8 accepted, 2 batches folded" in out
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    if not trace:
        assert set(values) == {"updates_per_s", "round_tail_s", "setup_s"}
        return
    assert values["stage.at_arrival_share"] == 100.0
    assert values["stage.bytes_per_update"] == 7 * 20012  # once an update, pad column and all
    assert values["stream.commit_ms"] >= 0.0 and values["stream.shard_h2d_gbps"] > 0.0
    # a ring a shard: eight acquisitions, of which a shard's second may find
    # its first buffer back
    assert round(values["stream.ring_reuse_share"] * 8 / 100, 6) in (0.0, 1.0, 2.0, 3.0, 4.0)
    # a device share is never given a CPU number
    assert "fold.shard_roofline" not in values and "stream.h2d_gbps" not in values


def test_a_shard_needs_fewer_chips_than_the_host_has_or_the_run_fails_cleanly(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    rc, result, out, err = toy.run_cell(CELL, toy.FLOOD)
    assert rc != 0 and result is None
    assert "2 devices, the cell needs 4" in err
