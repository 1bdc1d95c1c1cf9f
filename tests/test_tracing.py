"""Distributed round tracing + flight recorder (docs/DESIGN.md §16).

Covers the span layer's contracts (closed name registry, context
propagation, bounded buffers, header round-trip, Chrome-trace export
validity via the SAME validator CI runs), the flight recorder (trigger
dump with ring + metric deltas, rate limiting), the SDK retry-as-child-
spans shape, and the acceptance-criterion forensics: an injected shard
fold poison produces a flight dump whose ring contains the poisoning
batch's per-shard spans.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

pytest.importorskip("jax")

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools import trace_report  # noqa: E402
from xaynet_tpu.telemetry import recorder as recorder_mod, tracing  # noqa: E402
from xaynet_tpu.telemetry.registry import get_registry  # noqa: E402

# test-only span names, declared once at module import (the registry is
# process-wide, so tests reuse these instead of re-declaring per test)
S_A = tracing.declare_span("test.alpha")
S_B = tracing.declare_span("test.beta")
S_RETRO = tracing.declare_span("test.retro")


@pytest.fixture
def tracer():
    """A fresh, isolated tracer (the process singleton stays untouched)."""
    return tracing.Tracer(mode="on", ring_size=64, round_cap=128, trace_dir="")


# --- registry discipline ----------------------------------------------------


def test_declare_span_duplicate_raises():
    with pytest.raises(tracing.SpanNameError, match="already declared"):
        # S_A belongs to THIS module; fake a different declaring module
        exec(
            "from xaynet_tpu.telemetry import tracing\n"
            "tracing.declare_span('test.alpha')",
            {"__name__": "other.module"},
        )


def test_span_requires_declared_name(tracer):
    with pytest.raises(tracing.SpanNameError, match="never declared"):
        tracer.span("test.never_declared_name")
    with pytest.raises(tracing.SpanNameError, match="never declared"):
        tracer.record_span("test.never_declared_name", time.monotonic(), 0.0)


# --- context propagation ----------------------------------------------------


def test_span_nesting_and_ambient_context(tracer):
    tracer.begin_round(7, tracing.round_trace_id(b"s" * 32))
    root_ctx = tracer.round_ctx()
    with tracer.span(S_A) as outer:
        assert tracing.current_ctx().span_id == outer.ctx.span_id
        with tracer.span(S_B) as inner:
            assert inner.ctx.trace_id == root_ctx.trace_id
        # ambient context restored after the inner span exits
        assert tracing.current_ctx().span_id == outer.ctx.span_id
    assert tracing.current_ctx() is None
    spans = {s.name: s for s in tracer.end_round()}
    assert spans["test.beta"].parent_id == spans["test.alpha"].span_id
    assert spans["test.alpha"].parent_id == spans["round"].span_id
    assert (
        spans["test.alpha"].trace_id
        == spans["round"].trace_id
        == tracing.round_trace_id(b"s" * 32)
    )


def test_span_exit_records_error_on_exception(tracer):
    with pytest.raises(ValueError):
        with tracer.span(S_A):
            raise ValueError("boom")
    (span,) = [s for s in tracer.ring_spans() if s.name == "test.alpha"]
    assert "ValueError: boom" in span.error


def test_link_adopts_trace_without_parent(tracer):
    remote = tracing.TraceContext("ab" * 8, "cd" * 8)
    with tracer.span(S_A, link=remote):
        pass
    (span,) = [s for s in tracer.ring_spans() if s.name == "test.alpha"]
    assert span.trace_id == remote.trace_id
    assert span.parent_id is None
    assert span.attrs["link"] == remote.span_id


def test_trace_only_context_has_no_parent(tracer):
    with tracer.span(S_A, ctx=tracing.TraceContext("12" * 8)):
        pass
    (span,) = [s for s in tracer.ring_spans() if s.name == "test.alpha"]
    assert span.trace_id == "12" * 8 and span.parent_id is None


def test_record_span_retroactive(tracer):
    t0 = time.monotonic() - 0.5
    tracer.record_span(S_RETRO, start=t0, duration=0.5, shard=3)
    (span,) = [s for s in tracer.ring_spans() if s.name == "test.retro"]
    assert span.duration == pytest.approx(0.5)
    assert span.attrs["shard"] == 3


# --- header / wire ----------------------------------------------------------


def test_header_roundtrip_and_garbage_rejected():
    ctx = tracing.TraceContext(tracing.new_id(), tracing.new_id())
    parsed = tracing.parse_header(tracing.format_header(ctx))
    assert (parsed.trace_id, parsed.span_id) == (ctx.trace_id, ctx.span_id)
    for bad in ("", "zz", "deadbeef-cafe", "x" * 33, "g" * 16 + "-" + "a" * 16, None):
        assert tracing.parse_header(bad) is None


def test_round_trace_id_deterministic():
    seed = b"q" * 32
    assert tracing.round_trace_id(seed) == tracing.round_trace_id(seed)
    assert tracing.round_trace_id(seed) != tracing.round_trace_id(b"r" * 32)
    assert len(tracing.round_trace_id(seed)) == 16


# --- buffers / modes --------------------------------------------------------


def test_ring_and_round_buffer_bounds():
    tracer = tracing.Tracer(mode="on", ring_size=8, round_cap=4)
    tracer.begin_round(1, tracing.new_id())
    for _ in range(20):
        with tracer.span(S_A):
            pass
    assert len(tracer.ring_spans()) == 8  # ring keeps the most recent
    spans = tracer.end_round()
    # cap + the round root (the root always lands)
    assert len(spans) == 4 + 1


def test_off_mode_is_noop(tracer):
    tracer.configure(mode="off")
    with tracer.span(S_A) as span:
        assert span.ctx is None  # the null span
        span.set(anything=1)
    tracer.record_span(S_A, time.monotonic(), 0.1)
    assert tracer.ring_spans() == []


def test_failure_mode_keeps_ring_skips_export(tmp_path):
    tracer = tracing.Tracer(mode="failure", trace_dir=str(tmp_path))
    tracer.begin_round(3, tracing.new_id())
    with tracer.span(S_A):
        pass
    tracer.end_round()
    assert [s.name for s in tracer.ring_spans()].count("test.alpha") == 1
    assert list(tmp_path.glob("*.trace.json")) == []


# --- chrome export + validator ---------------------------------------------


def _one_round(tracer):
    import importlib

    importlib.import_module("xaynet_tpu.server.phases.base")  # declares phase.* spans

    tracer.begin_round(5, tracing.round_trace_id(b"z" * 32))
    for phase in ("sum", "update", "sum2", "unmask"):
        with tracer.span(f"phase.{phase}"):
            with tracer.span(S_B, phase=phase):
                pass
    return tracer.end_round()


def test_chrome_export_passes_ci_validator(tmp_path, tracer):
    tracer.configure(trace_dir=str(tmp_path))
    _one_round(tracer)
    # filename carries the pid so co-located processes exporting the same
    # round id (coordinator + edges sharing an env-inherited dir) never
    # clobber each other
    (path,) = list(tmp_path.glob("round_5.*.trace.json"))
    events = trace_report.load_events(str(path))
    assert trace_report.validate(events) == []
    # subsystem process metadata present for the viewer
    doc = json.loads(path.read_text())
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert {e["args"]["name"] for e in meta} >= {"phase", "round", "test"}


def test_validator_flags_orphans_and_coverage(tracer):
    spans = _one_round(tracer)
    events = tracing.to_chrome_trace(spans)["traceEvents"]
    events = [e for e in events if e.get("ph") == "X"]
    # break a parent link
    victim = next(e for e in events if e["name"] == "test.beta")
    victim["args"]["parent"] = "f" * 16
    problems = trace_report.validate(events)
    assert any("orphan parent" in p for p in problems)
    # drop a required phase
    events = [e for e in events if e["name"] != "phase.sum2"]
    problems = trace_report.validate(events)
    assert any("no phase.sum2" in p for p in problems)


def test_report_cross_check_tolerates_and_flags(tracer):
    spans = _one_round(tracer)
    events = [e for e in tracing.to_chrome_trace(spans)["traceEvents"] if e["ph"] == "X"]
    walls = trace_report.phase_walls(events)
    ok_report = {"phase_durations": {k: v for k, v in walls.items()}}
    assert trace_report.cross_check(events, ok_report) == []
    bad_report = {"phase_durations": {"update": walls.get("update", 0.0) + 30.0}}
    assert trace_report.cross_check(events, bad_report)


# --- flight recorder --------------------------------------------------------


def test_flight_dump_contains_ring_and_metric_deltas(tmp_path, monkeypatch):
    monkeypatch.setattr(recorder_mod, "_recorder", None)
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path))
    rec = recorder_mod.get_recorder()
    tracer = tracing.get_tracer()
    tracer.begin_round(11, tracing.new_id())
    counter = get_registry().counter("xaynet_test_flight_moves_total", "test")
    counter.inc(3)
    with tracer.span(S_A, batch=42):
        pass
    path = rec.dump("pipeline-poison", "batch 42 lost", batch=42)
    assert path is not None and Path(path).exists()
    bundle = json.loads(Path(path).read_text())
    assert bundle["trigger"] == "pipeline-poison"
    assert bundle["round_id"] == 11
    assert any(
        s["name"] == "test.alpha" and s.get("attrs", {}).get("batch") == 42
        for s in bundle["ring"]
    )
    delta = bundle["metrics_delta"]["xaynet_test_flight_moves_total"]
    assert delta["now"] - delta["before"] == 3
    # rate limit: an immediate second dump for the same trigger is dropped
    assert rec.dump("pipeline-poison", "again") is None
    tracer.end_round()


def test_flight_dump_never_raises(tmp_path, monkeypatch):
    monkeypatch.setattr(recorder_mod, "_recorder", None)
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", "/proc/definitely/not/writable")
    assert recorder_mod.flight_dump("degraded-close", "nope") is None


# --- SDK: retries become child spans ---------------------------------------


def test_sdk_retries_are_child_spans(monkeypatch):
    import asyncio

    from xaynet_tpu.resilience.policy import RetryPolicy
    from xaynet_tpu.sdk.client import ClientTransientError, ResilientClient

    class Flaky:
        def __init__(self):
            self.calls = 0

        async def send_message(self, blob):
            self.calls += 1
            if self.calls < 3:
                raise ClientTransientError("flap")

    tracer = tracing.Tracer(mode="on", ring_size=64)
    monkeypatch.setattr(tracing, "_tracer", tracer)
    client = ResilientClient(
        Flaky(), policy=RetryPolicy(max_attempts=5, base_delay_s=0.001, max_delay_s=0.002)
    )
    client.set_round_trace(b"w" * 32)
    asyncio.run(client.send_message(b"payload"))
    spans = tracer.ring_spans()
    send = [s for s in spans if s.name == "sdk.send"]
    attempts = [s for s in spans if s.name == "sdk.attempt"]
    assert len(send) == 1 and send[0].attrs["attempts"] == 3
    assert len(attempts) == 3
    trace_id = tracing.round_trace_id(b"w" * 32)
    assert send[0].trace_id == trace_id
    assert all(a.parent_id == send[0].span_id and a.trace_id == trace_id for a in attempts)
    # the two failed attempts carry their errors; the third is clean
    assert [bool(a.error) for a in sorted(attempts, key=lambda a: a.start)] == [
        True,
        True,
        False,
    ]


# --- acceptance: injected fold poison -> flight dump with shard spans -------


def test_streaming_poison_flight_dump_has_poisoning_batch_shard_spans(
    tmp_path, monkeypatch
):
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device CPU mesh")
    from xaynet_tpu.core.mask import (
        BoundType, DataType, GroupType, Masker, MaskConfig, ModelType, Scalar,
    )
    from xaynet_tpu.parallel.aggregator import ShardedAggregator
    from xaynet_tpu.parallel.mesh import make_mesh
    from xaynet_tpu.parallel.shards import ShardPlan
    from xaynet_tpu.parallel.streaming import StreamingAggregator, StreamingError

    monkeypatch.setattr(recorder_mod, "_recorder", None)
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path))
    cfg = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
    n, bs = 48, 3
    rng = np.random.default_rng(17)
    stacks = []
    for _ in range(6):
        w = rng.uniform(-1, 1, size=n).astype(np.float32)
        _, masked = Masker(cfg.pair()).mask(Scalar(1, 6), w)
        stacks.append(masked.vect.data)

    agg = ShardedAggregator(cfg, n, mesh=make_mesh(jax.devices()[:8]), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    real_fold = ShardPlan.fold_shard
    real_fold_packed = ShardPlan.fold_shard_packed

    def always_broken(self, d, batch):
        if d == 5:
            raise RuntimeError("shard 5 is on fire")
        return real_fold(self, d, batch)

    def always_broken_packed(self, d, batch):
        if d == 5:
            raise RuntimeError("shard 5 is on fire")
        return real_fold_packed(self, d, batch)

    try:
        ShardPlan.fold_shard = always_broken
        ShardPlan.fold_shard_packed = always_broken_packed
        stream.submit_batch(np.stack(stacks[0:3]))
        with pytest.raises(StreamingError, match="poisoned"):
            stream.drain()
    finally:
        ShardPlan.fold_shard = real_fold
        ShardPlan.fold_shard_packed = real_fold_packed
        stream.close()

    dumps = sorted(tmp_path.glob("flight_*_pipeline-poison.json"))
    assert dumps, "poisoning must write a flight-recorder bundle"
    bundle = json.loads(dumps[-1].read_text())
    assert "batch 1" in bundle["detail"]
    shard_folds = [
        s
        for s in bundle["ring"]
        if s["name"] == "stream.fold"
        and s.get("attrs", {}).get("batch") == 1
        and "shard" in s.get("attrs", {})
    ]
    # the poisoning batch's per-shard fold spans are IN the ring, the
    # failing shard's span carrying the root cause
    assert {s["attrs"]["shard"] for s in shard_folds} == set(range(8))
    assert any(
        s["attrs"]["shard"] == 5 and s["attrs"].get("outcome") == "failed"
        for s in shard_folds
    )


# --- satellite: mask-kernel calibration verdicts in the round report --------


def test_mask_calibration_verdicts_land_in_round_report(tmp_path):
    from xaynet_tpu.telemetry.report import (
        RoundReporter,
        drain_mask_calibrations,
        record_mask_calibration,
    )

    drain_mask_calibrations()  # isolate from whatever ran before
    rep = RoundReporter(str(tmp_path / "r.jsonl"))
    rep.begin_round(2)
    record_mask_calibration(
        {
            "winner": "host-threaded",
            "backend": "cpu",
            "length": 64,
            "bucket": 4,
            "mesh": None,
            "probe_length": 64,
            "probe_walls": {"host-threaded": 0.01, "batch": 0.05},
        }
    )
    rep.begin_round(3)  # flushes round 2's report
    line = json.loads((tmp_path / "r.jsonl").read_text().splitlines()[0])
    assert line["round_id"] == 2
    (entry,) = line["mask_calibration"]
    assert entry["winner"] == "host-threaded"
    assert entry["probe_walls"]["batch"] == 0.05
    rep.flush()
    # drained: the verdict is attributed to ONE report, not repeated
    lines = (tmp_path / "r.jsonl").read_text().splitlines()
    assert "mask_calibration" not in json.loads(lines[-1])


def test_calibrate_mask_kernel_records_auditable_verdict():
    """The real auto-calibration race records its verdict (winner +
    per-candidate probe walls) for the round report — a headline shift
    caused by a verdict flip is auditable without a re-run."""
    from xaynet_tpu.core.mask.config import (
        BoundType, DataType, GroupType, MaskConfig, ModelType,
    )
    from xaynet_tpu.ops import masking_jax
    from xaynet_tpu.telemetry.report import drain_mask_calibrations

    drain_mask_calibrations()
    cfg = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3).pair()
    seeds = [bytes([i]) * 32 for i in range(3)]
    length = 97  # unusual length: a fresh (backend, shape) cache key
    winner = masking_jax.calibrate_mask_kernel(seeds, length, cfg, seed_batch=3)
    entries = [e for e in drain_mask_calibrations() if e["length"] == length]
    assert entries, "a fresh calibration must record its verdict"
    entry = entries[-1]
    assert entry["winner"] == winner
    assert entry["backend"] == masking_jax.jax.default_backend()
    assert winner in entry["probe_walls"] or entry["winner"] == "host-chunked"
    # memoized second resolution records nothing new
    assert masking_jax.calibrate_mask_kernel(seeds, length, cfg, seed_batch=3) == winner
    assert [e for e in drain_mask_calibrations() if e["length"] == length] == []


# --- the mirror sink (the same spans on the profiler's clock) ----------------

S_MIRRORED = tracing.declare_span("test.mirrored", mirror=True)
S_MIRRORED_B = tracing.declare_span("test.mirrored_b", mirror=True)


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: a context manager
    built from ``(name, **attrs)`` that logs its enter and exit."""

    log: list = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.log.append(("enter", self.name, self.attrs.get("rid")))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.attrs.get("rid")))


@pytest.fixture
def mirror_log():
    _FakeAnnotation.log = []
    return _FakeAnnotation.log


def test_mirror_receives_only_spans_declared_for_it(tracer, mirror_log):
    tracer.set_mirror(_FakeAnnotation)
    assert tracer.mirrored
    with tracer.span(S_A):  # declared without mirror=True
        with tracer.span(S_MIRRORED, rid="r1", bytes=3):
            pass
    tracer.record_span(S_MIRRORED, time.monotonic() - 0.1, 0.1, rid="retro")
    assert mirror_log == [("enter", S_MIRRORED, "r1"), ("exit", S_MIRRORED, "r1")]
    tracer.set_mirror(None)
    with tracer.span(S_MIRRORED, rid="r2"):
        pass
    assert len(mirror_log) == 2 and not tracer.mirrored
    assert len([s for s in tracer.ring_spans() if s.name == S_MIRRORED]) == 3


@pytest.mark.parametrize(
    "name", ["round", "rest.request", "phase.update", "phase.sum2", "pipeline.pool_wait",
             "pipeline.resume_wait", "update.request_wait", "update.verdict_wait"])
def test_covering_and_retroactive_spans_are_never_mirrored(name):
    import importlib

    for module in ("rest", "stages", "phases.base"):  # the modules that declare them
        importlib.import_module(f"xaynet_tpu.server.{module}")
    assert name in tracing.declared_span_names()
    assert name not in tracing.mirrored_span_names()


def test_mirror_is_never_called_with_tracing_off(mirror_log):
    tracer = tracing.Tracer(mode="off", trace_dir="")
    tracer.set_mirror(_FakeAnnotation)
    with tracer.span(S_MIRRORED, rid="x") as span:
        assert span.ctx is None
    assert mirror_log == []


_BRACKETS = [("message", "score")] + [
    ("unmask", label) for label in
    ("elect", "validate", "mask_put", "subtract", "fetch", "decode", "save", "proof", "retire")]


@pytest.mark.parametrize("table,label", _BRACKETS)
def test_stage_brackets_off_make_no_span_and_still_observe(table, label, mirror_log, monkeypatch):
    """The brackets outside the Update window (``server/stages.py``'s
    ``score``, ``telemetry/unmask.py``'s nine) with the tracer ``off``: no
    ``Span``, no call of the mirror, and the histogram observed all the
    same, so ``/metrics`` keeps its stage tables whatever the trace mode."""
    from xaynet_tpu.server import stages
    from xaynet_tpu.telemetry import unmask as unmask_stages

    if table == "message":
        child = stages.SECONDS.labels(stage=label, phase="sum2")
        bracket = lambda: stages.stage(label, phase="sum2", bytes=7)  # noqa: E731
    else:
        child = unmask_stages.SECONDS.labels(stage=label)
        bracket = lambda: unmask_stages.stage(label, bytes=7)  # noqa: E731
    process_tracer = tracing.get_tracer()
    mode, mirror = process_tracer.mode, process_tracer._mirror
    process_tracer.configure(mode="off")
    process_tracer.set_mirror(_FakeAnnotation)
    made = []
    try:
        with monkeypatch.context() as patched:
            patched.setattr(tracing, "Span", lambda *a, **k: made.append(a))
            ring, count, total = len(process_tracer.ring_spans()), child.count, child.sum
            with bracket() as span:
                span.set(more=1)  # the null span takes attributes and drops them
                time.sleep(0.002)
        assert child.count == count + 1 and child.sum - total >= 0.002
        assert len(process_tracer.ring_spans()) == ring and made == [] and mirror_log == []
        # and on: one span of the stage's name beside the observation
        process_tracer.configure(mode="on")
        with bracket():
            pass
        assert child.count == count + 2 and len(mirror_log) == 2
        assert process_tracer.ring_spans()[-1].attrs["bytes"] == 7
    finally:
        process_tracer.configure(mode=mode)
        process_tracer.set_mirror(mirror)


def test_validator_holds_the_stage_spans_to_their_place(tracer):
    """``unmask.*`` lies under ``phase.unmask`` and ``sum2.score`` under its
    ``rest.request`` (tools/trace_report.py); a request still open when the
    round's window flushed rides as a link and is let through."""
    import importlib

    for module in ("server.rest", "server.stages", "server.phases.base", "telemetry.unmask"):
        importlib.import_module(f"xaynet_tpu.{module}")
    tracer.begin_round(6, tracing.round_trace_id(b"y" * 32))
    for phase in ("sum", "update"):
        with tracer.span(f"phase.{phase}"):
            pass
    with tracer.span("phase.sum2"):
        with tracer.span("rest.request", method="POST", path="/message"):
            with tracer.span("sum2.score"):
                pass
    with tracer.span("phase.unmask"):
        with tracer.span("unmask.elect"):
            pass
        with tracer.span("unmask.save"):
            pass
    events = [e for e in tracing.to_chrome_trace(tracer.end_round())["traceEvents"]
              if e.get("ph") == "X"]
    assert trace_report.validate(events) == []
    by_name = {e["name"]: e for e in events}
    # a stage of the Unmask phase hung under another phase
    by_name["unmask.save"]["args"]["parent"] = by_name["phase.sum2"]["args"]["span"]
    by_name["unmask.save"]["ts"] = by_name["phase.sum2"]["ts"]
    by_name["unmask.save"]["dur"] = 0.0
    problems = trace_report.validate(events)
    assert len(problems) == 1 and "unmask.save" in problems[0] and "phase.unmask" in problems[0]
    by_name["unmask.save"]["args"]["parent"] = by_name["phase.unmask"]["args"]["span"]
    by_name["unmask.save"]["ts"] = by_name["phase.unmask"]["ts"]
    # the score hung under the phase, not under its message's request
    score = by_name["sum2.score"]["args"]
    score["parent"] = by_name["phase.sum2"]["args"]["span"]
    problems = trace_report.validate(events)
    assert len(problems) == 1 and "sum2.score" in problems[0] and "rest.request" in problems[0]
    # its request was still open at the flush: a link, no parent
    score["link"], score["parent"] = by_name["rest.request"]["args"]["span"], None
    assert trace_report.validate(events) == []


def test_a_failing_mirror_never_fails_the_span(tracer):
    def broken(name, **attrs):
        raise RuntimeError("sink down")

    tracer.set_mirror(broken)
    with tracer.span(S_MIRRORED):
        pass
    assert tracer.ring_spans()[-1].name == S_MIRRORED


def test_interleaved_coroutine_spans_pair_on_the_mirror(tracer, mirror_log):
    """Two coroutines on one loop thread: B opens and closes inside A's
    await. Each annotation is entered and exited once, by its own span."""
    import asyncio

    tracer.set_mirror(_FakeAnnotation)

    async def co(name, rid, delay, dur):
        await asyncio.sleep(delay)
        with tracer.span(name, rid=rid):
            await asyncio.sleep(dur)

    async def main():
        await asyncio.gather(co(S_MIRRORED, "a", 0.0, 0.06), co(S_MIRRORED_B, "b", 0.02, 0.08))

    asyncio.run(main())
    assert mirror_log == [
        ("enter", S_MIRRORED, "a"), ("enter", S_MIRRORED_B, "b"),
        ("exit", S_MIRRORED, "a"), ("exit", S_MIRRORED_B, "b"),
    ]
    spans = {s.attrs["rid"]: s for s in tracer.ring_spans()}
    assert spans["a"].parent_id is None and spans["b"].parent_id is None  # siblings, not nested


def test_overlapping_coroutine_annotations_keep_their_own_ends_in_a_profile(tmp_path, tracer):
    """What the mirror relies on, shown under the CPU profiler: a
    ``TraceAnnotation`` is recorded whole when it exits, so two spans that
    overlap on the loop thread come out with their own starts and ends."""
    import asyncio
    import glob

    import jax
    from jax.profiler import ProfileData

    tracer.set_mirror(jax.profiler.TraceAnnotation)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2

    async def co(name, rid, delay, dur):
        await asyncio.sleep(delay)
        with tracer.span(name, rid=rid):
            await asyncio.sleep(dur)

    async def main():
        await asyncio.gather(co(S_MIRRORED, "a", 0.0, 0.20), co(S_MIRRORED_B, "b", 0.05, 0.30))

    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        asyncio.run(main())
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert files
    found = {}
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name in (S_MIRRORED, S_MIRRORED_B):
                    found[event.name] = (event.start_ns * 1e-9, event.duration_ns * 1e-9,
                                         dict(event.stats))
    assert set(found) == {S_MIRRORED, S_MIRRORED_B}
    (a_lo, a_dur, a_stats), (b_lo, b_dur, b_stats) = found[S_MIRRORED], found[S_MIRRORED_B]
    assert a_stats["rid"] == "a" and b_stats["rid"] == "b"
    assert 0.18 < a_dur < 0.5 and 0.28 < b_dur < 0.6
    assert a_lo < b_lo < a_lo + a_dur < b_lo + b_dur  # they overlap, neither is cut
    by_rid = {s.attrs["rid"]: s for s in tracer.ring_spans()}
    assert abs(by_rid["a"].duration - a_dur) < 0.02 and abs(by_rid["b"].duration - b_dur) < 0.02
