"""Each cell end to end at toy size on the CPU backend, and the runs that
have to come out not correct or not at all."""

import json
import os

import pytest

from benchmark.harness import data
from benchmark.tests import toy

BENCH = data.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def toy_args(cell: str) -> list[str]:
    return toy.FLOOD


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal_reports_the_contracts_line(cell, trace):
    rc, result, out, err = toy.run_cell(cell, toy_args(cell), trace=trace)
    assert rc == 0, err[-2000:]
    allowed = toy.RESULT_KEYS | ({"breakdown"} if trace else set())
    assert toy.RESULT_KEYS <= set(result) <= allowed
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"  # named, and said
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in data.metrics_for(cell, BENCH, kind)}
    assert result["metrics"], "no metric reported"
    for name, entry in result["metrics"].items():
        assert set(entry) == {"value", "unit"} and entry["unit"] == declared[name]
        assert isinstance(entry["value"], (int, float))
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        # device metrics are never given a CPU number
        assert not any(n.startswith(("fold", "device.idle")) for n in result["metrics"])
    else:
        assert set(result["metrics"]) == set(declared)
        assert all(e["value"] > 0 for e in result["metrics"].values())
    # every number compared is printed beside its limit
    assert out.count("check ") >= 8 and "(limit " in out


def test_same_seed_same_inputs():
    from benchmark.harness import forge, reference, replay

    traffic = {"arrival": "poisson", "rate_per_s": 40.0, "pattern_seed": 1}
    assert replay.schedule(traffic, 64, 2**31 + 5) == replay.schedule(traffic, 64, 2**31 + 5)
    assert replay.schedule(traffic, 64, 1) != replay.schedule(traffic, 64, 2)
    a, b = (sorted(t for t, _ in replay.schedule(traffic, 64, s)) for s in (1, 2))
    assert a[-1] == pytest.approx(b[-1])  # every seed spans the same time
    assert (reference.weights_fixed(2**31 + 5, 3, 100) == reference.weights_fixed(2**31 + 5, 3, 100)).all()
    assert forge.mask_seed_for(9, 1) == forge.mask_seed_for(9, 1) != forge.mask_seed_for(9, 2)


def test_weights_rounded_to_bfloat16_fail_correct():
    """The lower-precision control: the same run with every participant's
    weights rounded to bfloat16 before encoding is off by about 4e-3 and has
    to differ from the plain reference."""
    cell = CELLS[0]
    rc, result, out, err = toy.run_cell(cell, toy_args(cell) + ["--control", "bf16"])
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    assert "positions differing from the plain reference" in out and "FAILED" in out


@pytest.mark.parametrize("fault", ["fold", "answer"])
def test_broken_timed_path_fails_correct(fault, monkeypatch):
    monkeypatch.setenv("BENCH_BREAK", fault)
    cell = CELLS[0]
    broken = os.path.join(os.path.dirname(__file__), "serve_broken.py")
    rc, result, out, err = toy.run_cell(cell, toy_args(cell) + ["--serve", broken])
    assert rc == 0, err[-2000:]
    assert result["correct"] is False, out[-2000:]


def test_no_chip_and_no_named_cpu_exits_nonzero():
    cell = CELLS[0]
    rc, result, out, err = toy.run_cell(cell, toy_args(cell), platforms=None)
    assert rc != 0 and result is None
    assert not any(line.startswith("{") for line in out.splitlines())


def test_bare_checkout_exits_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` is not the system: no result, another code than 0."""
    import shutil

    shutil.copy(os.path.join(toy.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(toy.ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, result, out, err = toy.run_cell(CELLS[0], toy.FLOOD, root=str(tmp_path))
    assert rc != 0 and result is None and "{" not in out
