"""tools/bench_gate.py: the tier-2 bench regression gate (BENCH.md).

Replays a BENCH_HISTORY-shaped JSONL and must exit 1 exactly when the
latest headline round regresses more than the threshold vs the best PRIOR
round of the SAME series — mixed metric variants, torn lines and alien
records must neither crash the gate nor pollute the comparison.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

spec = importlib.util.spec_from_file_location("xn_bench_gate", REPO / "tools" / "bench_gate.py")
bench_gate = importlib.util.module_from_spec(spec)
sys.modules["xn_bench_gate"] = spec.loader.exec_module(bench_gate) or bench_gate

HEADLINE = "masked-update aggregation throughput @25M params"


def _write(tmp_path, records) -> str:
    path = tmp_path / "history.jsonl"
    lines = [json.dumps(r) if isinstance(r, dict) else r for r in records]
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def _run(path, *extra) -> int:
    argv = sys.argv
    sys.argv = ["bench_gate.py", "--history", path, *extra]
    try:
        return bench_gate.main()
    finally:
        sys.argv = argv


def _rec(ts, value, metric=HEADLINE, unit="updates/s", nested=True):
    if nested:
        return {"ts": ts, "parsed": {"metric": metric, "value": value, "unit": unit}}
    return {"ts": ts, "metric": metric, "value": value, "unit": unit}


def test_gate_passes_when_latest_holds_the_line(tmp_path, capsys):
    path = _write(
        tmp_path,
        [_rec(1, 20.0), _rec(2, 30.0, nested=False), _rec(3, 29.0)],
    )
    assert _run(path) == 0
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["result"] == "ok"
    assert verdict["best_prior"] == 30.0


def test_gate_fails_on_regression_beyond_threshold(tmp_path, capsys):
    path = _write(tmp_path, [_rec(1, 30.0), _rec(2, 31.0), _rec(3, 26.0)])
    assert _run(path) == 1  # 26 < 31 * 0.9
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["result"] == "REGRESSION"


def test_gate_threshold_is_configurable(tmp_path):
    path = _write(tmp_path, [_rec(1, 31.0), _rec(2, 26.0)])
    assert _run(path) == 1
    assert _run(path, "--threshold", "0.2") == 0  # 26 > 31 * 0.8


def test_gate_compares_within_one_exact_series(tmp_path):
    """A @200k-params round must not set the bar for the @25M series."""
    path = _write(
        tmp_path,
        [
            _rec(1, 900.0, metric="masked-update aggregation throughput @200000 params"),
            _rec(2, 30.0),
            _rec(3, 31.0),
        ],
    )
    assert _run(path) == 0


def test_gate_survives_torn_lines_and_alien_records(tmp_path):
    path = _write(
        tmp_path,
        [
            '{"ts": 1, "parsed": {"metric": "',  # torn append
            {"ts": 2, "note": "no metric at all"},
            _rec(3, 30.0),
            _rec(4, 5.0, unit="rounds/s"),  # different unit: not headline
            _rec(5, 29.5),
        ],
    )
    assert _run(path) == 0


def test_gate_with_nothing_to_compare_is_a_soft_pass(tmp_path):
    assert _run(_write(tmp_path, [_rec(1, 30.0)])) == 0
    assert _run(_write(tmp_path, [{"ts": 1, "note": "empty"}])) == 0


def test_gate_runs_clean_on_a_recorded_history():
    """A history as the appenders really wrote it (every gated family, 59
    rows kept from the pre-chip CPU record) must parse and pass."""
    assert _run(str(REPO / "tests" / "data" / "bench_history.jsonl")) == 0


def test_gate_treats_a_missing_history_as_empty(tmp_path):
    assert _run(str(tmp_path / "absent.jsonl")) == 0


# --- kernel/thread-config series identity ----------------------------------


def _cfg_rec(ts, value, metric=HEADLINE, **config):
    parsed = {"metric": metric, "value": value, "unit": "updates/s"}
    parsed.update(config)
    return {"ts": ts, "parsed": parsed}


def test_gate_treats_thread_config_change_as_new_series(tmp_path, capsys):
    """BENCH_r05's 29.46 vs r03's ~49 on the same code path came from an
    implicit thread-default shift: with the config recorded, the gate must
    start a NEW series instead of flagging a 40% regression."""
    path = _write(
        tmp_path,
        [
            _cfg_rec(1, 49.0, kernel="native-u64", native_threads=16),
            _cfg_rec(2, 48.2, kernel="native-u64", native_threads=16),
            _cfg_rec(3, 29.5, kernel="native-u64", native_threads=4),
        ],
    )
    assert _run(path) == 0
    assert "NEW series" in capsys.readouterr().err


def test_gate_kernel_change_is_a_new_series(tmp_path):
    path = _write(
        tmp_path,
        [_cfg_rec(1, 49.0, kernel="native-u64"), _cfg_rec(2, 20.0, kernel="xla")],
    )
    assert _run(path) == 0


def test_gate_still_fails_within_one_config_series(tmp_path, capsys):
    path = _write(
        tmp_path,
        [
            _cfg_rec(1, 49.0, kernel="native-u64", native_threads=16),
            _cfg_rec(2, 30.0, kernel="native-u64", native_threads=16),
        ],
    )
    assert _run(path) == 1
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["result"] == "REGRESSION"
    assert "native_threads=16" in verdict["config"]


def test_gate_mesh8_series_is_gated_independently(tmp_path, capsys):
    """The mesh=8 shard-parallel headline is its own series: its first
    round soft-passes against a taller single-device history, and a later
    mesh=8 regression fails against the mesh=8 best only."""
    mesh_metric = HEADLINE + ", mesh=8 CPU fallback (PET update phase)"
    base = [
        _cfg_rec(1, 49.0, kernel="native-u64", native_threads=16),
        _cfg_rec(2, 48.0, kernel="native-u64", native_threads=16),
    ]
    first_mesh = _cfg_rec(
        3, 34.0, metric=mesh_metric, kernel="native-u64", native_threads=4,
        shard_threads=4, mesh=8,
    )
    path = _write(tmp_path, base + [first_mesh])
    assert _run(path) == 0  # first mesh=8 round: nothing to compare

    regressed = _cfg_rec(
        4, 20.0, metric=mesh_metric, kernel="native-u64", native_threads=4,
        shard_threads=4, mesh=8,
    )
    path = _write(tmp_path, base + [first_mesh, regressed])
    assert _run(path) == 1  # 20 < 34 * 0.9, within the mesh=8 series


# --- sim headline family (participants/s) -----------------------------------


SIM_METRIC = "sim round throughput @1000 params (in-graph federated round)"


def _sim_rec(ts, value, metric=SIM_METRIC, **config):
    parsed = {"metric": metric, "value": value, "unit": "participants/s"}
    parsed.update(config)
    return {"ts": ts, "parsed": parsed}


def test_sim_series_gates_independently_of_fold_headline(tmp_path):
    """A healthy fold headline must not mask a sim regression (and vice
    versa): the two families gate as separate series in one default run."""
    fold_ok = [_rec(1, 30.0), _rec(2, 31.0)]
    sim_ok = [
        _sim_rec(3, 500.0, participants=2048, block=256, mesh=1),
        _sim_rec(4, 520.0, participants=2048, block=256, mesh=1),
    ]
    assert _run(_write(tmp_path, fold_ok + sim_ok)) == 0

    sim_bad = _sim_rec(5, 100.0, participants=2048, block=256, mesh=1)
    assert _run(_write(tmp_path, fold_ok + sim_ok + [sim_bad])) == 1

    # and a fold regression still fails even with a healthy sim series
    fold_bad = _rec(6, 10.0)
    assert _run(_write(tmp_path, fold_ok + sim_ok + [fold_bad])) == 1


def test_sim_population_shape_change_is_a_new_series(tmp_path, capsys):
    """participants/block/mesh are series identity for the sim headline —
    doubling the population is a different experiment, not a regression."""
    path = _write(
        tmp_path,
        [
            _sim_rec(1, 500.0, participants=2048, block=256, mesh=1),
            _sim_rec(2, 180.0, participants=8192, block=512, mesh=1),
        ],
    )
    assert _run(path) == 0
    assert "NEW series" in capsys.readouterr().err


def test_explicit_metric_prefix_gates_single_family(tmp_path):
    """--metric-prefix keeps the old single-family behavior: a sim
    regression is invisible when only the fold family is requested."""
    records = [
        _rec(1, 30.0),
        _rec(2, 31.0),
        _sim_rec(3, 500.0, participants=2048, block=256),
        _sim_rec(4, 100.0, participants=2048, block=256),
    ]
    path = _write(tmp_path, records)
    assert _run(path, "--metric-prefix", bench_gate.HEADLINE_PREFIX) == 0
    assert (
        _run(path, "--metric-prefix", bench_gate.SIM_PREFIX, "--unit", "participants/s")
        == 1
    )


def test_metric_prefix_infers_unit_for_known_families(tmp_path):
    """A bare --metric-prefix for the sim family must infer participants/s
    (not fall back to updates/s, match nothing, and soft-pass a regression)."""
    records = [
        _sim_rec(1, 500.0, participants=2048, block=256),
        _sim_rec(2, 100.0, participants=2048, block=256),
    ]
    path = _write(tmp_path, records)
    assert _run(path, "--metric-prefix", bench_gate.SIM_PREFIX) == 1


def test_unknown_metric_prefix_without_unit_is_an_error(tmp_path):
    """An unknown family must demand --unit, not silently default to
    updates/s, match zero records, and soft-pass a regression."""
    import pytest

    path = _write(tmp_path, [_rec(1, 10.0, metric="long-haul soak", unit="rounds/s")])
    with pytest.raises(SystemExit) as exc:
        _run(path, "--metric-prefix", "long-haul soak")
    assert exc.value.code == 2  # argparse usage error
    assert _run(path, "--metric-prefix", "long-haul soak", "--unit", "rounds/s") == 0


# --- bytes-moved family: lower is better (round 13, packed reduction) -------

BYTES_METRIC = "bytes moved per fold @25M params (packed staging)"


def test_bytes_family_lower_is_better_pass_and_fail(tmp_path, capsys):
    # moving FEWER bytes than the best prior round is an improvement
    path = _write(
        tmp_path,
        [
            _rec(1, 1000.0, metric=BYTES_METRIC, unit="bytes/fold"),
            _rec(2, 800.0, metric=BYTES_METRIC, unit="bytes/fold"),
        ],
    )
    assert _run(path, "--metric-prefix", "bytes moved per fold") == 0
    # moving MORE than threshold above the best (smallest) prior fails
    path = _write(
        tmp_path,
        [
            _rec(1, 800.0, metric=BYTES_METRIC, unit="bytes/fold"),
            _rec(2, 1000.0, metric=BYTES_METRIC, unit="bytes/fold"),
        ],
    )
    assert _run(path, "--metric-prefix", "bytes moved per fold") == 1
    out = capsys.readouterr()
    assert "lower-is-better" in out.out


def test_bytes_family_within_threshold_passes(tmp_path):
    path = _write(
        tmp_path,
        [
            _rec(1, 1000.0, metric=BYTES_METRIC, unit="bytes/fold"),
            _rec(2, 1050.0, metric=BYTES_METRIC, unit="bytes/fold"),
        ],
    )
    assert _run(path, "--metric-prefix", "bytes moved per fold") == 0


def test_bytes_family_unit_inferred_and_gated_by_default(tmp_path):
    # unit inference for the new family (no --unit needed)
    path = _write(
        tmp_path,
        [
            _rec(1, 500.0, metric=BYTES_METRIC, unit="bytes/fold"),
            _rec(2, 499.0, metric=BYTES_METRIC, unit="bytes/fold"),
        ],
    )
    assert _run(path, "--metric-prefix", "bytes moved per fold @25M params") == 0
    # and the default (no-prefix) run gates the family alongside the others
    path = _write(
        tmp_path,
        [
            _rec(1, 20.0),
            _rec(2, 21.0),
            _rec(3, 500.0, metric=BYTES_METRIC, unit="bytes/fold"),
            _rec(4, 900.0, metric=BYTES_METRIC, unit="bytes/fold"),
        ],
    )
    assert _run(path) == 1


# --- host core count in the series fingerprint (PR 18) ----------------------

WALL_METRIC = "round wall @25000000 params"


def test_gate_cpu_count_change_starts_new_rate_series(tmp_path, capsys):
    """A 1-cpu container re-measuring a 4-cpu record is the BENCH_r05
    thread-shift incident in hardware form: the rate series must split on
    the recorded core count instead of flagging a regression."""
    path = _write(
        tmp_path,
        [
            _cfg_rec(1, 45.0, kernel="host", cpus=4),
            _cfg_rec(2, 44.0, kernel="host", cpus=4),
            _cfg_rec(3, 23.0, kernel="host", cpus=1),
        ],
    )
    assert _run(path) == 0
    assert "NEW series" in capsys.readouterr().err


def test_gate_still_fails_within_one_cpu_series(tmp_path, capsys):
    path = _write(
        tmp_path,
        [
            _cfg_rec(1, 45.0, kernel="host", cpus=4),
            _cfg_rec(2, 23.0, kernel="host", cpus=4),
        ],
    )
    assert _run(path) == 1
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "cpus=4" in verdict["config"]


def test_gate_legacy_records_without_cpus_keep_their_series(tmp_path):
    # older writers never recorded cpus: their series fingerprints (and
    # regressions) must be unaffected by the new field
    path = _write(
        tmp_path,
        [_cfg_rec(1, 45.0, kernel="host"), _cfg_rec(2, 23.0, kernel="host")],
    )
    assert _run(path) == 1


def test_gate_round_wall_splits_on_cpu_count_too(tmp_path, capsys):
    """Walls scale with cores exactly like rates: a wall measured on a
    different core count starts a NEW s/round series (soft pass), while a
    regression within one core count still fails with the inverted floor."""
    moved = _write(
        tmp_path,
        [
            _cfg_rec(1, 60.0, metric=WALL_METRIC, unit="s/round", kernel="host", cpus=4),
            _cfg_rec(2, 90.0, metric=WALL_METRIC, unit="s/round", kernel="host", cpus=1),
        ],
    )
    assert _run(moved, "--metric-prefix", "round wall") == 0
    assert "NEW series" in capsys.readouterr().err
    same_box = _write(
        tmp_path,
        [
            _cfg_rec(1, 60.0, metric=WALL_METRIC, unit="s/round", kernel="host", cpus=1),
            _cfg_rec(2, 90.0, metric=WALL_METRIC, unit="s/round", kernel="host", cpus=1),
        ],
    )
    assert _run(same_box, "--metric-prefix", "round wall") == 1
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["direction"] == "lower-is-better"
    assert verdict["best_prior"] == 60.0
