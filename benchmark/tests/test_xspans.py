"""The program's spans against the device's idle time: interval arithmetic
on made-up planes (a gap fully, half and not covered), the whole reduction
on the recorded v5e trace (which predates the mirror: no program span in
it), and the readers that return nothing where there is nothing to read."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import coordinator, data, xspans, xtrace
from benchmark.readers import prom_ratio, trace_span

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "recorded_v5e.xplane.pb")
NEW_STAGE_METRICS = ("rest.read_body_ms", "pipeline.pool_wait_ms", "pipeline.open_ms",
                     "pipeline.verify_ms", "pipeline.parse_ms", "pipeline.resume_wait_ms",
                     "update.request_wait_ms", "update.validate_ms", "update.to_planar_ms",
                     "update.verdict_wait_ms")


def planes():
    """Device busy 2..3, 5..6 and 9..10 in a window 0..10: idle gaps 0..2
    (fully covered by a program span), 3..5 (half covered), 6..9 (covered
    by runtime events only)."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [(2.0, 3.0, "fusion.1"), (5.0, 6.0, "fusion.1"),
                                           (9.0, 10.0, "fusion.2")]},
            {"name": "Steps", "events": [(0.0, 10.0, "0")]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [(-1.0, 2.5, "update.await_request"),
                                          (4.0, 5.5, "rest.read_body"),
                                          (4.5, 4.75, "pipeline.verify"),
                                          (6.5, 8.5, "PjitFunction(fold)")]},
            {"name": "tf_pjrt_thread", "events": [(6.0, 9.0, "TransferToDevice")]},
            {"name": "bench-profiler", "events": [(0.0, 10.0, xtrace.WINDOW_SPAN)]}]},
    ]


NAMES = ["update.await_request", "rest.read_body", "pipeline.verify", "stream.h2d"]


def test_overlap_of_interval_lists():
    assert xspans.overlap([(0, 2), (3, 5)], [(1, 4)]) == 2.0
    assert xspans.overlap([(0, 2)], [(2, 3)]) == 0.0
    assert xspans.overlap([], [(0, 1)]) == 0.0


def test_gaps_fully_half_and_not_covered():
    r = xspans.reduce_planes(planes(), NAMES)
    assert r["device_stand_in"] is False
    assert r["idle_s"] == pytest.approx(2.0 + 2.0 + 3.0)
    assert r["covered_s"] == pytest.approx(2.0 + 1.0 + 0.0)
    assert r["covered_share"] == pytest.approx(3.0 / 7.0)
    assert r["spans"]["update.await_request"] == {
        "count": 1, "seconds": pytest.approx(3.5), "idle_covered_s": pytest.approx(2.0)}
    assert r["spans"]["rest.read_body"]["idle_covered_s"] == pytest.approx(1.0)
    # inside read_body: counted for itself, not twice in the total
    assert r["spans"]["pipeline.verify"]["idle_covered_s"] == pytest.approx(0.25)
    assert "stream.h2d" not in r["spans"] and "PjitFunction(fold)" not in r["spans"]


def test_runtime_events_are_not_program_spans():
    r = xspans.reduce_planes(planes(), [])
    assert r["covered_s"] == 0.0 and r["covered_share"] == 0.0 and r["spans"] == {}


def test_a_trace_with_no_idle_time_has_no_share():
    busy = planes()
    busy[0]["lines"][0]["events"] = [(0.0, 10.0, "fusion.1")]
    assert xspans.reduce_planes(busy, NAMES)["covered_share"] is None


def test_recorded_trace_in_a_process_of_its_own():
    """The recorded v5e trace is from before the program mirrored anything:
    the device's idle time is there, and none of it is covered."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.xspans", FIXTURE, *NAMES],
        cwd=data.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300)
    assert done.returncode == 0, done.stderr[-400:]
    r = json.loads(done.stdout.strip().splitlines()[-1])
    whole = xtrace.reduce_file(FIXTURE)
    assert r["device_stand_in"] is False and r["spans"] == {} and r["covered_s"] == 0.0
    assert r["idle_s"] == pytest.approx(whole["span_s"] - whole["busy_s"], rel=1e-6)


def ctx(trace, health):
    return {"trace": trace, "health": health, "metrics": {}}


def test_trace_span_reader_returns_nothing_without_a_trace_or_a_list():
    listed = {"open": {"trace": {"mirrored_spans": NAMES}}}
    assert trace_span.read(ctx(None, listed)) is None
    assert trace_span.read(ctx({"device_stand_in": True, "file": FIXTURE}, listed)) is None
    assert trace_span.read(ctx({"device_stand_in": False, "file": "/nonexistent.pb"}, listed)) is None
    # a program from before this PR: /healthz has no such section
    assert trace_span.read(ctx({"device_stand_in": False, "file": FIXTURE}, {"open": {}})) is None
    assert trace_span.read(ctx({"device_stand_in": False, "file": FIXTURE}, {})) is None


def test_trace_span_reader_reads_the_recorded_trace():
    listed = {"open": {"trace": {"mirrored_spans": NAMES}}}
    assert trace_span.read(ctx({"device_stand_in": False, "file": FIXTURE}, listed)) == 0.0


PARENT_METRICS = """xaynet_message_pipeline_seconds_sum{stage="decrypt_parse"} 28.8
xaynet_message_pipeline_seconds_count{stage="decrypt_parse"} 12
xaynet_message_pipeline_seconds_sum{stage="total"} 168.0
xaynet_message_pipeline_seconds_count{stage="total"} 12
"""


@pytest.mark.parametrize("name", NEW_STAGE_METRICS + ("stream.h2d_gbps", "loop.lag_ms"))
def test_new_counter_metrics_return_nothing_from_a_program_without_them(name):
    spec = data.load_layer_metric(name)
    assert spec["reader"] == "prom_ratio"
    zero = [(n, l, 0.0) for n, l, _ in coordinator.parse_metrics(PARENT_METRICS)]
    samples = coordinator.parse_metrics(PARENT_METRICS)
    c = {"metrics": {"open": zero, "close": samples, "end": samples}}
    assert prom_ratio.read(c, **spec["args"]) is None


def test_stage_metrics_and_closure_on_made_up_counters():
    text = PARENT_METRICS + "".join(
        f'xaynet_message_pipeline_seconds_sum{{stage="{stage}"}} {seconds}\n'
        f'xaynet_message_pipeline_seconds_count{{stage="{stage}"}} 12\n'
        for stage, seconds in (("read_body", 24.0), ("pool_wait", 60.0), ("open", 6.0),
                               ("verify", 12.0), ("parse", 10.8), ("request_wait", 36.0),
                               ("validate", 24.0), ("seed_dict", 0.12), ("stage", 0.012),
                               ("flush", 6.0), ("to_planar", 9.6), ("resume_wait", 1.2),
                               ("verdict_wait", 0.6)))
    text += ("xaynet_streaming_h2d_bytes_total 2.1e9\nxaynet_streaming_h2d_seconds_sum 0.5\n"
             "xaynet_streaming_h2d_seconds_count 1\n")
    samples = coordinator.parse_metrics(text)
    zero = [(n, l, 0.0) for n, l, _ in samples]
    c = {"metrics": {"open": zero, "close": samples, "end": samples}}

    def read(name):
        return prom_ratio.read(c, **data.load_layer_metric(name)["args"])

    assert read("pipeline.verify_ms") == pytest.approx(1000.0)
    assert read("update.to_planar_ms") == pytest.approx(800.0)
    assert read("stream.h2d_gbps") == pytest.approx(4.2)
    # the chain's twelve stages over read_body + total; to_planar and the
    # decrypt_parse lump are in neither
    assert read("pipeline.stage_closure") == pytest.approx(100.0 * 180.732 / 192.0)
    assert read("pipeline.decrypt_parse_ms") == pytest.approx(2400.0)  # reads what it read
