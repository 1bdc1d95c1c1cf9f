"""The 3-limb, 10-byte cell ``resnet50-f32b6m6.flood``: its control, its
fold arithmetic and its route metric. Its toy rehearsal with and without
``--trace`` is a case of ``test_rehearsal.py`` (every cell of
``BENCHMARK.json`` is)."""

from benchmark.harness import data, sizing
from benchmark.tests import toy

CELL = "resnet50-f32b6m6.flood"
BENCH = data.load_benchmark()


def test_the_cell_is_the_siblings_vector_under_the_wide_mask():
    cell = data.load_cell(CELL, BENCH)
    cfg, sib = (data.load_config(c, BENCH) for c in (cell["config"], "resnet50-f32m6"))
    assert (cell["traffic"], cell["chips"]) == ("flood8", 1)
    assert cfg["model_length"] == sib["model_length"] == 25_557_032
    assert (cfg["order_bits"], cfg["n_limbs"], cfg["bytes_per_number"]) == (75, 3, 10)
    assert cfg["toml"] == sib["toml"] == {"aggregation": {"device": True}}
    assert cfg["updates_per_round"] == cfg["batch_size"] == cfg["scalar_denominator"] == 8
    # the reference sums encodings in int64
    assert cfg["updates_per_round"] * 2 * cfg["add_shift"] * cfg["exp_shift"] < 2**63


def test_fold_bytes_of_the_wide_batch():
    n = 25_557_032
    assert sizing.fold_bytes(8, 10, 3, n) == 8 * 10 * n + 2 * 4 * 3 * n == 2_657_931_328


def test_weights_rounded_to_bfloat16_fail_correct_on_the_wide_cell():
    rc, result, out, err = toy.run_cell(CELL, toy.FLOOD + ["--control", "bf16"])
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    assert "positions differing from the plain reference" in out and "FAILED" in out


def test_traced_rehearsals_report_which_routes_ran():
    """`generic` is numpy or Python; with the native library built (the
    benchmark builds it) both widths run its kernels for every operation."""
    shares = {}
    for cell in (CELL, "resnet50-f32m6.flood"):
        rc, result, out, err = toy.run_cell(cell, toy.FLOOD, trace=1)
        assert rc == 0, err[-2000:]
        assert result["correct"] is True, out[-3000:]
        assert result["metrics"]["codec.generic_share"]["unit"] == "%"
        shares[cell] = result["metrics"]["codec.generic_share"]["value"]
    assert shares == {CELL: 0.0, "resnet50-f32m6.flood": 0.0}
