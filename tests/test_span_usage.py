"""What a stage's thread spent (ISSUE 52; docs/DESIGN.md §16): a span declared
with ``usage`` reads the kernel's count of its thread's (its crew's, its
process's) CPU seconds, page faults and context switches at entry and exit,
onto four counters of the registry and into its own attributes. Held here:
that the numbers are the kernel's (a busy loop, a sleep, a first touch, a
worker thread, a sender that keeps ``recv`` waiting), that nothing is counted
where there is nothing to read, and that in a served round every message
counts once in each stage of its chain, on both carriers of a large body.
"""

import asyncio
import os
import socket
import threading
import time

import jax
import numpy as np
import pytest
from test_fanin_round import (  # the served round of the fan-in tests, at toy size
    _served_round,
    _settings,
    weights_fixed,
)

from xaynet_tpu.core.message import encoder as message_encoder
from xaynet_tpu.ops import limbs as host_limbs
from xaynet_tpu.parallel import aggregator as aggregator_mod
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.sdk import state_machine as participant
from xaynet_tpu.server import rest as rest_mod, stages
from xaynet_tpu.telemetry import journal as journal_stages, tracing, unmask as unmask_stages
from xaynet_tpu.telemetry.registry import get_registry
from xaynet_tpu.utils import native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

THREAD = tracing.declare_span("test.usage_thread", usage="thread")
CREW = tracing.declare_span("test.usage_crew", usage="crew")
PROCESS = tracing.declare_span("test.usage_process", usage="process")
CARRIER = tracing.declare_span("test.usage_carrier", usage="carrier")
PLAIN = tracing.declare_span("test.usage_none")
SECONDS = get_registry().histogram("xaynet_test_usage_seconds", "test", ("stage",))

KEYS = ("cpu_s", "sys_s", "minflt", "majflt", "nvcsw", "nivcsw")


def counters(span: str) -> dict:
    """The four counters' values at ``span``, under the attributes' names."""
    value = get_registry().sample_value
    return {
        "cpu_s": value("xaynet_span_cpu_seconds_total", {"span": span, "mode": "user"}) or 0.0,
        "sys_s": value("xaynet_span_cpu_seconds_total", {"span": span, "mode": "system"}) or 0.0,
        "minflt": value("xaynet_span_page_faults_total", {"span": span, "kind": "minor"}) or 0.0,
        "majflt": value("xaynet_span_page_faults_total", {"span": span, "kind": "major"}) or 0.0,
        "nvcsw": value("xaynet_span_context_switches_total",
                       {"span": span, "kind": "voluntary"}) or 0.0,
        "nivcsw": value("xaynet_span_context_switches_total",
                        {"span": span, "kind": "involuntary"}) or 0.0,
        "n": value("xaynet_span_usage_total", {"span": span}) or 0.0,
    }


def moved(span: str, before: dict) -> dict:
    return {k: v - before[k] for k, v in counters(span).items()}


@pytest.fixture
def tracer():
    t = tracing.get_tracer()
    mode = t.mode
    t.configure(mode="on")
    yield t
    t.configure(mode=mode)


def last(t, name: str):
    return next(s for s in reversed(t.ring_spans()) if s.name == name)


def burn(cpu_seconds: float) -> None:
    """Keep this thread on a core until the kernel has charged it that much."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        sum(range(2000))


# --- the numbers are the kernel's ------------------------------------------------


@pytest.mark.parametrize("work,lo,hi", [
    ("busy", 0.8, 1.2),  # a loop that stays on its core: CPU is its wall
    ("sleep", 0.0, 0.1),  # a sleep: wall and no CPU
])
def test_a_thread_span_counts_cpu_where_the_thread_worked(tracer, work, lo, hi):
    before = counters(THREAD)
    with tracer.span(THREAD) as span:
        if work == "busy":
            burn(0.15)
        else:
            time.sleep(0.15)
    done = last(tracer, THREAD)
    assert span.ctx.span_id == done.span_id
    assert set(KEYS) <= set(done.attrs)
    cpu = done.attrs["cpu_s"] + done.attrs["sys_s"]
    delta = moved(THREAD, before)
    assert delta["n"] == 1
    assert delta["cpu_s"] + delta["sys_s"] == pytest.approx(cpu, abs=1e-9)
    assert cpu <= 1.05 * done.duration + 0.002
    if work == "busy":
        assert 0.8 * 0.15 <= cpu <= 1.2 * 0.15 + 0.01
        if done.attrs["nivcsw"] == 0:  # never taken off its core: CPU is the wall
            assert lo * done.duration <= cpu <= hi * done.duration
    else:
        assert cpu <= hi * done.duration
        assert done.attrs["nvcsw"] >= 1  # it slept, of its own will


def test_first_touch_is_minor_faults_and_a_second_touch_is_none(tracer):
    size, page = 64 << 20, 4096
    # as rest.py makes a body's buffer: mapped, no page of it touched
    # (``bytearray(size)`` would zero-fill it, and touch it, here)
    buf = native.uninitialised_bytearray(None, size)
    ones = b"\x01" * (size // page)
    with tracer.span(THREAD):
        buf[::page] = ones
    first = last(tracer, THREAD).attrs["minflt"]
    with tracer.span(THREAD):
        buf[::page] = ones
    again = last(tracer, THREAD).attrs["minflt"]
    # 16,384 faults at 4 KiB a page, 32 where every fault maps a huge page
    assert size // (2 << 20) <= first <= 1.1 * size // page
    assert again <= first // 16


def test_a_bracket_left_on_another_thread_counts_nothing_and_raises_nothing(tracer):
    before = counters(CARRIER)
    bracket = tracing.usage_of(CARRIER)
    spent = bracket.__enter__()
    burn(0.02)
    other = threading.Thread(target=bracket.__exit__, args=(None, None, None))
    other.start()
    other.join()
    assert spent == {} and moved(CARRIER, before) == dict.fromkeys((*KEYS, "n"), 0.0)
    # and the same bracket, left where it was entered, counts
    with tracing.usage_of(CARRIER) as spent:
        burn(0.02)
    assert spent["cpu_s"] + spent["sys_s"] >= 0.015 and moved(CARRIER, before)["n"] == 1


@pytest.mark.parametrize("mode", ["on", "off"])
def test_without_rusage_thread_spans_still_open_close_and_observe(tracer, monkeypatch, mode):
    """A platform whose ``resource`` has no ``RUSAGE_THREAD``: what a span is
    asked is settled where it is declared, so the name is declared here."""
    monkeypatch.setitem(tracing._RUSAGE_WHO, "thread", None)
    name = tracing.declare_span(f"test.usage_no_rusage_{mode}", usage="thread")
    tracer.configure(mode=mode)
    seen = SECONDS.labels(stage="no_rusage").count
    with tracing.timed_span(name, SECONDS.labels(stage="no_rusage"), batch=3) as span:
        span.set(outcome="ok")
        burn(0.005)
    if mode == "on":
        done = last(tracer, name)
        assert done.attrs == {"batch": 3, "outcome": "ok"} and done.duration >= 0.004
    assert SECONDS.labels(stage="no_rusage").count == seen + 1
    assert counters(name) == dict.fromkeys((*KEYS, "n"), 0.0)
    with tracing.usage_of(name) as spent:
        pass
    assert spent == {} and counters(name)["n"] == 0


@pytest.mark.parametrize("name", [THREAD, PROCESS])
def test_the_tracer_off_still_counts(tracer, name):
    tracer.configure(mode="off")
    before, seen = counters(name), SECONDS.labels(stage="off").count
    ring = len(tracer.ring_spans())
    with tracing.timed_span(name, SECONDS.labels(stage="off")) as span:
        assert span.ctx is None  # the null span: no Span is made
        burn(0.02)
    assert len(tracer.ring_spans()) == ring
    assert SECONDS.labels(stage="off").count == seen + 1
    delta = moved(name, before)
    assert delta["n"] == 1 and delta["cpu_s"] + delta["sys_s"] >= 0.015


def test_a_span_with_no_usage_reads_nothing(tracer):
    with tracer.span(PLAIN, batch=1):
        burn(0.005)
    assert last(tracer, PLAIN).attrs == {"batch": 1}
    assert get_registry().sample_value("xaynet_span_usage_total", {"span": PLAIN}) is None
    with pytest.raises(tracing.SpanNameError):
        tracing.usage_of(PLAIN)
    with pytest.raises(tracing.SpanNameError):
        tracing.declare_span("test.usage_bad", usage="core")


def test_a_carrier_span_reads_nothing_itself_and_its_carrier_counts_under_its_name(tracer):
    before = counters(CARRIER)
    with tracer.span(CARRIER) as span:
        burn(0.01)  # the opening thread's own CPU is not the stage's
        assert moved(CARRIER, before)["n"] == 0
        got = {}
        worker = threading.Thread(
            target=lambda: got.update(stages_carried(CARRIER, burn, 0.03)))
        worker.start()
        worker.join()
        span.set(**got)
    done, delta = last(tracer, CARRIER), moved(CARRIER, before)
    assert delta["n"] == 1
    assert 0.025 <= done.attrs["cpu_s"] + done.attrs["sys_s"] <= 0.06
    assert delta["cpu_s"] + delta["sys_s"] == pytest.approx(
        done.attrs["cpu_s"] + done.attrs["sys_s"], abs=1e-9)


def stages_carried(name: str, work, *args) -> dict:
    """``stages.carried`` for a span of this file."""
    with tracing.usage_of(name) as spent:
        work(*args)
    return spent


def test_a_process_span_sees_a_worker_thread_that_a_thread_span_does_not(tracer):
    def with_a_worker(name: str) -> float:
        with tracer.span(name):
            worker = threading.Thread(target=burn, args=(0.1,))
            worker.start()
            worker.join()
        attrs = last(tracer, name).attrs
        return attrs["cpu_s"] + attrs["sys_s"]

    assert with_a_worker(THREAD) <= 0.03  # the caller slept in the join
    assert with_a_worker(PROCESS) >= 0.08  # every thread of the process


@pytest.mark.skipif(native.load() is None, reason="native library unavailable")
def test_a_crew_is_its_thread_and_the_native_workers_it_joined(tracer):
    """A plane copy over the element axis runs on the library's workers: the
    calling thread sleeps in their join, and the crew's reading has their
    CPU and the first touch of the destination."""
    src = np.full((6, 8 << 20), 7, dtype=np.uint8)

    def copy(name: str) -> dict:
        dst = np.empty_like(src)  # fresh: the copy touches it first
        with tracer.span(name):
            host_limbs.copy_planes(src, dst)
        assert np.array_equal(dst, src)
        return last(tracer, name).attrs

    alone, tally = copy(THREAD), tracing._workers()
    crew = copy(CREW)
    joined = [b - a for a, b in zip(tally, tracing._workers())]
    # the caller's own faults are the workers' stacks it maps; the destination's
    # first touch is the workers': 24 faults where each maps a huge page, 12,288
    # at 4 KiB a page
    assert crew["minflt"] - alone["minflt"] >= src.nbytes // (2 << 20)
    # and their CPU, which is the crew's beside the caller's own (a fresh
    # thread's seconds are exact at its end; the caller's are the kernel's
    # apportioning of its ticks, and may read 0 over a millisecond)
    assert joined[0] + joined[1] > 0.0 and joined[2] >= src.nbytes // (2 << 20)
    assert crew["cpu_s"] + crew["sys_s"] >= joined[0] + joined[1] - 1e-9
    assert crew["minflt"] >= joined[2]
    # a tally only rises, and another thread's is its own
    mine = tracing._workers()
    seen = []
    other = threading.Thread(target=lambda: seen.append(tracing._workers()))
    other.start()
    other.join()
    assert all(v > 0 for v in mine[:1]) and mine[2] >= crew["minflt"]
    assert seen == [(0.0, 0.0, 0, 0, 0, 0)]


# --- the stage read on its carrier: a body off the socket ---------------------------


@pytest.mark.parametrize("road", ["native", "python"])
def test_recv_exactly_reads_cpu_far_under_wall_while_the_sender_sleeps(road, monkeypatch):
    if road == "python":
        monkeypatch.setattr(native, "load", lambda: None)
    elif native.load() is None:
        pytest.skip("native library unavailable")
    # over the allocator's 32 MiB ceiling for serving a request from memory it
    # had back: this buffer is mapped fresh, and the read touches it first
    size, pause = 40 << 20, 0.4
    payload = os.urandom(size)
    ours, theirs = socket.socketpair()
    ours.setblocking(False)

    def sender():
        theirs.sendall(payload[: size // 2])
        time.sleep(pause)
        theirs.sendall(payload[size // 2:])
        theirs.close()

    before = counters("rest.read_body")
    peer = threading.Thread(target=sender)
    body, spent = native.uninitialised_bytearray(None, size), {}
    peer.start()
    t0 = time.monotonic()
    got = rest_mod._recv_exactly(ours, body, 0, time.monotonic() + 30.0, spent)
    wall = time.monotonic() - t0
    peer.join()
    assert got == size and bytes(body) == payload
    assert wall >= pause
    cpu = spent["cpu_s"] + spent["sys_s"]
    assert 0.0 < cpu < 0.5 * wall
    assert spent["nvcsw"] >= 1  # it slept in poll while the sender did
    assert 1 <= spent["minflt"] <= 1.1 * size // 4096 + 64  # the buffer's first touch
    delta = moved("rest.read_body", before)
    assert delta["n"] == 1
    assert delta["cpu_s"] + delta["sys_s"] == pytest.approx(cpu, abs=1e-9)
    assert delta["minflt"] == spent["minflt"]


# --- the counters, as /metrics shows them -----------------------------------------


def test_the_four_counters_render_with_exactly_the_declared_labels(tracer):
    with tracer.span(THREAD):
        native.uninitialised_bytearray(None, 40 << 20)[::4096] = b"\x01" * (10 << 10)
        time.sleep(0.001)
    text = get_registry().render()
    families = {
        "xaynet_span_cpu_seconds_total": ("span", "mode"),
        "xaynet_span_page_faults_total": ("span", "kind"),
        "xaynet_span_context_switches_total": ("span", "kind"),
        "xaynet_span_usage_total": ("span",),
    }
    for name, labels in families.items():
        family = get_registry().get(name)
        assert family.kind == "counter" and family.labelnames == labels
        assert f"# TYPE {name} counter" in text
    assert f'xaynet_span_cpu_seconds_total{{span="{THREAD}",mode="user"}}' in text
    assert f'xaynet_span_page_faults_total{{span="{THREAD}",kind="minor"}}' in text
    assert f'xaynet_span_context_switches_total{{span="{THREAD}",kind="voluntary"}}' in text
    assert f'xaynet_span_usage_total{{span="{THREAD}"}}' in text
    shown = {line.split("{")[1].split("}")[0] for line in text.splitlines()
             if line.startswith("xaynet_span_") and THREAD in line}
    modes = {part for labels in shown for part in labels.split(",")[1:]}
    assert modes <= {'mode="user"', 'mode="system"', 'kind="minor"', 'kind="major"',
                     'kind="voluntary"', 'kind="involuntary"'}


# --- the declarations and the DESIGN table ---------------------------------------

SHIPPED = {
    "rest.read_body": "carrier", "update.validate": "carrier", "update.flush": "carrier",
    "journal.store": "carrier",
    "pipeline.open": "thread", "pipeline.verify": "thread", "pipeline.verify_beside": "thread",
    "update.stage": "thread", "sum2.score": "thread", "unmask.elect": "thread",
    "unmask.validate": "thread", "journal.dicts": "thread", "journal.serialise": "thread",
    "message.serialise": "thread", "message.sign": "thread", "message.seal": "thread",
    "pipeline.parse": "crew", "update.to_planar": "crew", "unmask.mask_put": "crew",
    "unmask.decode": "process", "unmask.save": "process", "sum2.derive": "process",
}


@pytest.mark.parametrize("name,word", sorted(SHIPPED.items()))
def test_every_usage_span_is_in_the_design_table_with_its_word(name, word):
    from tools.analysis import spans

    # the modules that declare the names
    assert stages and unmask_stages and journal_stages and message_encoder and participant
    declared = {k: v for k, v in tracing.usage_span_names().items() if not k.startswith("test.")}
    assert declared == SHIPPED
    with open(os.path.join(ROOT, "docs", "DESIGN.md"), encoding="utf-8") as f:
        told = spans.documented_usage(f.read())
    assert told[name] == word
    assert set(told) == set(SHIPPED)  # and the table gives no other span a usage


# --- a served round: every message counts once in each stage of its chain -------------

CHAIN = ("rest.read_body", "pipeline.open", "pipeline.parse", "update.to_planar")


@pytest.fixture
def one_device(monkeypatch, tmp_path):
    """The chip has one device; the tests' CPU backend has eight."""
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))


@pytest.mark.parametrize("route", ["direct", "overflow"])
def test_usage_rises_by_one_a_message_in_a_served_round(route, one_device, monkeypatch):
    """Bodies over ``DIRECT_BODY_MIN``, all sent at one instant. ``direct``:
    a ``rest-body`` reader for every connection. ``overflow``: two readers
    for six connections, and the readers keep their bodies until the other
    four have gone to the ``rest-overflow`` thread, which takes each in
    several turns and counts it once."""
    length, batch = 180_001, 6
    connections = batch
    assert 6 * length > rest_mod.DIRECT_BODY_MIN
    if route == "overflow":
        readers = 2
        monkeypatch.setattr(rest_mod, "BODY_READERS", readers)
        monkeypatch.setattr(rest_mod, "OVERFLOW_TURN_BYTES", 1 << 18)
        recv, receive = rest_mod._recv_exactly, rest_mod._OverflowReader.receive
        asked, all_asked = [], threading.Event()

        def held_reader(*args):
            all_asked.wait(120)
            return recv(*args)

        def counted(self, *args):
            asked.append(args)
            if len(asked) == connections - readers:
                all_asked.set()
            return receive(self, *args)

        monkeypatch.setattr(rest_mod, "_recv_exactly", held_reader)
        monkeypatch.setattr(rest_mod._OverflowReader, "receive", counted)
    before = {name: counters(name) for name in (*CHAIN, "update.flush", "update.validate")}
    began = time.monotonic()
    fixed = [weights_fixed(300 + i, length) for i in range(batch)]
    out = asyncio.run(asyncio.wait_for(
        _served_round(_settings(length, batch, batch, "auto"), fixed, 8,
                      connections=connections, together=True), 150))
    update = out["update"]
    assert update["accepted"] == batch
    if route == "direct":
        assert (update["large"], update["overflow"]) == (batch, 0)
    else:
        assert (update["large"], update["overflow"]) == (2, batch - 2)
    for name in CHAIN:
        delta = moved(name, before[name])
        # the Sum2 message carries a mask of the model's length: its body is
        # large too, and read after the update window on a `rest-body` thread;
        # the Sum message's is streamed and has no carrier. Both are opened
        # and parsed with the updates; neither has a slot to be written to
        extra = {"rest.read_body": 1, "update.to_planar": 0}.get(name, 2)
        assert delta["n"] == batch + extra, name
    assert moved("update.validate", before["update.validate"])["n"] == batch
    assert moved("update.flush", before["update.flush"])["n"] == 1
    # no usage span of the chain was dropped for a thread mismatch: every one in
    # the ring carries its reading (a toy body's CPU is under the kernel's tick
    # and may read 0, and its buffer may be memory the allocator had back)
    mine = [s for s in tracing.get_tracer().ring_spans()
            if s.start >= began and s.name in CHAIN and s.attrs.get("phase") == "update"]
    for s in mine:
        assert set(KEYS) <= set(s.attrs), (s.name, s.attrs)
    assert sorted(s.name for s in mine) == sorted(CHAIN * batch)
