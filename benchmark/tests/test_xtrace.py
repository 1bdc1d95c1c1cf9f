"""The reduction from a trace to numbers: interval arithmetic on made-up
planes, and the whole of it on a small trace recorded on a TPU v5e
(``recorded_v5e.xplane.pb``, cut from a traced run of this benchmark by
``make_fixture.py``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "recorded_v5e.xplane.pb")


def test_union_and_gaps():
    assert xtrace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    busy = xtrace.union([(1, 2), (4, 5)])
    assert xtrace.gaps(busy, 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert xtrace.gaps(busy, 1, 5) == [(2, 4)]
    assert xtrace.gaps([], 0, 3) == [(0, 3)]


def planes():
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [(1.0, 2.0, "jit_fold_packed_batch(7)"),
                                               (5.0, 5.5, "jit_fold_packed_batch(7)"),
                                               (8.0, 8.25, "jit_p_mod_sub(9)")]},
            {"name": "XLA Ops", "events": [(1.0, 1.5, "fusion.1"), (1.25, 2.0, "fusion.2"),
                                           (5.0, 5.5, "fusion.1"), (8.0, 8.25, "subtract.3")]},
            {"name": "Steps", "events": [(0.0, 10.0, "0")]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [(2.0, 4.9, "PjitFunction(fold)"), (0.0, 0.9, "shard_args")]},
            {"name": "tf_pjrt_thread", "events": [(5.6, 7.9, "TransferToDevice")]},
            {"name": "tf_XLAEigen/1", "events": [(0.0, 10.0, "busy-cpu-threadpool")]}]},
        {"name": "/host:metadata", "lines": []},
    ]


def test_reduce_busy_ops_modules_and_gap_attribution():
    r = xtrace.reduce_planes(planes())
    assert r["devices"] == 1 and r["device_stand_in"] is False
    assert r["busy_s"] == pytest.approx(1.0 + 0.5 + 0.25)  # the union of the XLA Ops line only
    assert r["span_s"] == pytest.approx(10.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(1.0)]
    assert r["modules"]["jit_fold_packed_batch(7)"] == {"count": 2, "seconds": pytest.approx(1.5)}
    gaps = {name: seconds for name, seconds in r["idle_gaps"]}
    assert gaps["python:PjitFunction(fold) (97% of the gap)"] == pytest.approx(3.0)  # 2.0 .. 5.0
    assert gaps["tf_pjrt_thread:TransferToDevice (92% of the gap)"] == pytest.approx(2.5)  # 5.5 .. 8.0
    assert gaps["python:shard_args (90% of the gap)"] == pytest.approx(1.0)  # 0 .. 1.0
    assert r["idle_gaps"][0][1] >= r["idle_gaps"][-1][1]


def test_window_span_sets_the_window_and_is_not_blamed_for_gaps():
    marked = planes()
    marked[1]["lines"].append({"name": "bench-profiler", "events": [(-20.0, 12.0, xtrace.WINDOW_SPAN)]})
    r = xtrace.reduce_planes(marked)
    assert r["span_s"] == pytest.approx(32.0) and r["busy_s"] == pytest.approx(1.75)
    assert r["idle_gaps"][0] == ["python:shard_args (4% of the gap)", pytest.approx(21.0)]  # -20 .. 1
    assert not any(xtrace.WINDOW_SPAN in name for name, _ in r["idle_gaps"])


def test_no_device_plane_means_a_stand_in_never_a_device_number():
    host_only = [p for p in planes() if not p["name"].startswith("/device")]
    r = xtrace.reduce_planes(host_only)
    assert r["device_stand_in"] is True and r["modules"] == {}
    assert xtrace.reduce_planes([])["busy_s"] == 0.0


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_v5e_trace():
    done = subprocess.run([sys.executable, "-m", "benchmark.harness.xtrace", FIXTURE],
                          cwd=os.path.dirname(os.path.dirname(HERE)),
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-1000:]
    r = json.loads(done.stdout.strip().splitlines()[-1])
    with open(os.path.join(HERE, "recorded_v5e.expected.json"), encoding="utf-8") as f:
        want = json.load(f)
    assert r["devices"] == want["devices"] and r["device_stand_in"] is False
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["span_s"]
    assert {n: m["count"] for n, m in r["modules"].items()} == want["module_counts"]
    assert [n for n, _ in r["device_ops"]] == want["device_ops"]
    assert [n for n, _ in r["idle_gaps"]] == want["idle_gaps"]
