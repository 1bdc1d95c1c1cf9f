"""The multi-batch cell ``resnet50-f32m6-multibatch.flood`` (two fold batches
a round on the chip): what its file
changes from the sibling's, its arithmetic, its control, and the metrics of
the batch boundary. Its toy rehearsal with and without ``--trace`` at two
batches is a case of ``test_rehearsal.py`` (every cell of ``BENCHMARK.json``
is); the rehearsals here run three, one more than the cell."""

from benchmark.harness import data, replay, sizing
from benchmark.tests import toy

CELL = "resnet50-f32m6-multibatch.flood"
SIBLING = "resnet50-f32m6"
BENCH = data.load_benchmark()
# three fold batches of 4 at toy size, a dyadic scalar
THREE = toy.TOY + ["--set", "updates_per_round=12", "--set", "scalar_denominator=16",
                   "--seconds", "10"]
BOUNDARY = ("stream.ring_wait_ms", "stream.ring_reuse_share", "update.accept_gap_max_s")


def test_the_file_is_the_siblings_but_for_the_rounds_length():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], SIBLING))
    assert (cell["traffic"], cell["chips"]) == ("flood8", 1)
    changed = {key for key in set(cfg) | set(sib) if cfg.get(key) != sib.get(key)}
    assert changed == {"name", "source", "deployment", "updates_per_round", "scalar_denominator",
                       "assumed"}
    assert (cfg["updates_per_round"], cfg["batch_size"], cfg["scalar_denominator"]) == (24, 12, 32)
    assert cfg["toml"] == {"aggregation": {"device": True}}  # the ring and the kernel as shipped
    assert cfg["reduced"] == ["updates_per_round", "sum_participants"]
    assert cfg["reduced_from"]["updates_per_round"] == 10000
    assert cfg["guarantees"] == sib["guarantees"]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"] != sib["source"]


def test_the_round_is_whole_batches_and_the_reference_holds_its_sum():
    cell = data.load_cell(CELL, BENCH)
    cfg = data.load_config(cell["config"], BENCH)
    n = replay.n_uploads(data.load_traffic(cell["traffic"]), cfg, 51.0)
    assert n == 24 and n % cfg["batch_size"] == 0 and n // cfg["batch_size"] == 2
    den = cfg["scalar_denominator"]
    assert den & (den - 1) == 0  # a power of two: the SDK's encode is exact
    # the reference sums encodings in int64
    assert n * 2 * cfg["add_shift"] * cfg["exp_shift"] < 2**63
    # one flush folds the sibling's batch: the same bytes, twice a round
    assert sizing.fold_bytes(cfg["batch_size"], cfg["bytes_per_number"], cfg["n_limbs"],
                             cfg["model_length"]) == 12 * 7 * 25_557_032 + 2 * 4 * 2 * 25_557_032


def test_every_per_layer_metric_lists_the_cell():
    missing = [m["name"] for m in BENCH["per_layer"] if CELL not in m.get("workloads", [CELL])]
    assert not missing
    for name in BOUNDARY:
        metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [c["name"] for c in BENCH["workloads"]]
        assert metric["moves"] == "updates_per_s"
        assert data.load_layer_metric(name)["reader"] in ("prom_ratio", "prom_gauge")


def test_weights_rounded_to_bfloat16_fail_correct_on_the_multibatch_cell():
    rc, result, out, err = toy.run_cell(CELL, THREE + ["--control", "bf16"])
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    assert "positions differing from the plain reference" in out and "FAILED" in out


def test_traced_rehearsal_of_three_batches_reports_the_batch_boundary(monkeypatch):
    # one device, as on the chip (the tests' CPU backend is given eight, and
    # a mesh of eight stages per shard), and a vector long enough for a fold
    # to outlast the next batch's first arrival
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    rc, result, out, err = toy.run_cell(CELL, THREE + ["--set", "model_length=200003"], trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert "12 accepted, 3 batches folded" in out
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert set(BOUNDARY) <= set(values)
    # one acquisition a batch, and a ring is built for each round: of three,
    # none, one or two found a buffer back in the ring
    assert round(values["stream.ring_reuse_share"] * 3, 6) in (0.0, 100.0, 200.0)
    assert values["stream.ring_wait_ms"] >= 0.0
    assert values["update.accept_gap_max_s"] > 0.0
    # the folds of the earlier batches run while the later ones stage
    assert 0.0 < values["stream.overlap_ratio"] <= 1.0
    units = {name: entry["unit"] for name, entry in result["metrics"].items()}
    assert [units[name] for name in BOUNDARY] == ["ms", "%", "s"]


def test_a_one_batch_round_reads_one_lease_and_no_overlap(monkeypatch):
    """The siblings' round: one acquisition, a lease; nothing to overlap."""
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    one = toy.TOY + ["--set", "updates_per_round=4", "--set", "scalar_denominator=4",
                     "--seconds", "10"]
    rc, result, out, err = toy.run_cell("resnet50-f32m6.flood", one, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, out[-3000:]
    values = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert values["stream.ring_reuse_share"] == 0.0
    assert values["stream.overlap_ratio"] == 0.0
