"""The reader of the harness's own process (``benchmark/readers/own_span.py``)
on a made-up ring, and the fifteen per-layer metrics that PR 52 appended:
each has its data file, lists every cell and names an end-to-end metric."""

import importlib
import time

import pytest

from benchmark.harness import data
from benchmark.readers import own_span
from xaynet_tpu.telemetry.tracing import Span

BENCH = data.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
END_TO_END = {m["name"] for m in BENCH["end_to_end"]}
COUNTERS = ("rest.read_body_cpu_ms", "rest.read_body_faults", "pipeline.open_cpu_ms",
            "pipeline.parse_cpu_ms", "pipeline.verify_beside_cpu_ms",
            "pipeline.preempted_per_update", "update.to_planar_cpu_ms",
            "update.to_planar_faults", "update.flush_cpu_ms", "unmask.faults_per_round")
SPANS = ("sum2.derive_ms", "sum2.derive_oncore_share", "sum2.derive_preempted",
         "sum2.compose_ms", "sum2.send_ms")


def span(name: str, start: float, duration: float, **attrs) -> Span:
    s = Span(name, "t", f"{name}@{start}", None, start, attrs)
    s.duration = duration
    return s


RING = [
    span("sum2.derive", 1.0, 9.0, threads=4, cpu_s=30.0, sys_s=1.0, nivcsw=7),  # the warm-up's
    span("message.compose", 10.0, 0.5, part=0),
    span("sum2.derive", 20.0, 2.0, threads=8, cpu_s=11.5, sys_s=0.5, nivcsw=3, route="fused"),
    span("message.compose", 22.0, 0.25, part=0),
    span("sum2.send", 22.3, 0.125, part=0, bytes=10),
    span("mask.sum", 23.0, 1.0),
]


@pytest.mark.parametrize("args,want", [
    (dict(span="sum2.derive", field="dur", scale=1000.0), 2000.0),  # the last of its name
    (dict(span="sum2.derive", field="nivcsw"), 3.0),
    # 12 CPU seconds over 2 s of 8 threads: on a core three quarters of the time
    (dict(span="sum2.derive", field=["cpu_s", "sys_s"], per="threads", scale=100.0), 75.0),
    (dict(span="message.compose", field="dur", scale=1000.0), 250.0),
    (dict(span="sum2.send", field="dur", scale=1000.0), 125.0),
    (dict(span="sum2.open_seeds", field="dur"), None),  # no such span in the ring
    (dict(span="mask.sum", field="cpu_s"), None),  # a span that read no usage
    (dict(span="sum2.send", field="dur", per="threads"), None),  # nothing to divide by
    (dict(span="sum2.derive", field="route"), None),  # not a number
])
def test_the_reader_on_a_made_up_ring(args, want):
    assert own_span.reduce(RING, **args) == want
    assert own_span.reduce([], **args) is None


def test_the_reader_reads_the_ring_of_its_own_process():
    from xaynet_tpu.telemetry import tracing

    name = "benchmark.test_own_span"
    if name not in tracing.declared_span_names():
        tracing.declare_span(name, usage="thread")
    tracer = tracing.get_tracer()
    mode = tracer.mode
    tracer.configure(mode="on")
    try:
        with tracer.span(name, threads=1):
            # long against the kernel's tick: a thread's seconds are
            # apportioned from its ticks, and lumpy over a millisecond
            end = time.thread_time() + 0.2
            while time.thread_time() < end:
                sum(range(2000))
    finally:
        tracer.configure(mode=mode)
    ms = own_span.read({}, span=name, field="dur", scale=1000.0)
    share = own_span.read({}, span=name, field=["cpu_s", "sys_s"], per="threads", scale=100.0)
    assert ms >= 200.0 and 20.0 <= share <= 110.0
    assert own_span.read({}, span="benchmark.absent", field="dur") is None


@pytest.mark.parametrize("name", COUNTERS + SPANS)
def test_every_new_metric_has_its_file_lists_every_cell_and_moves_an_end_to_end_metric(name):
    metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert metric["workloads"] == CELLS and len(CELLS) == 7
    assert metric["moves"] in END_TO_END
    assert metric["source"] == ("program_counter" if name in COUNTERS else "program_span")
    assert metric["better"] == ("higher" if name == "sum2.derive_oncore_share" else "lower")
    spec = data.load_layer_metric(name)
    assert spec["reader"] == ("prom_ratio" if name in COUNTERS else "own_span")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    if name in COUNTERS:
        # the denominator is the usage counter, which a program without it
        # does not have: its line leaves the metric out, and prints no 0.0
        assert spec["args"]["den"]["name"] == "xaynet_span_usage_total"
        assert spec["args"]["num"]["name"].startswith("xaynet_span_")
        assert reader.read({"metrics": {"open": [], "close": [], "end": []}},
                           **spec["args"]) is None
    else:
        assert own_span.reduce(RING, **spec["args"]) is not None


def test_the_fifteen_are_appended_and_nothing_before_them_moved():
    assert [m["name"] for m in BENCH["per_layer"][-15:]] == list(COUNTERS + SPANS)
