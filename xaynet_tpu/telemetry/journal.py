"""The stages of one round-journal write, and of a resume (docs/DESIGN.md §9, §16).

A coordinator that keeps its round journal (``[resilience]
checkpoint_enabled``) writes one phase-tagged entry at every commit point
of the round, on the state machine's serial path: the message that filled
a fold batch, the first Sum2 vote, the way into Unmask all wait for it.
Each write is written down twice by one call here (``tracing.timed_span``,
as a message's stages are in ``server/stages.py`` and the Unmask phase's in
``telemetry/unmask.py``): as spans ``journal.<stage>`` under one
``journal.total`` and as observations on ``xaynet_journal_seconds{stage,
phase}``, ``phase`` the entry's tag (``sum``, ``update``, ``sum2``,
``unmask``), bracketed where the work happens:

- ``drain``: the streaming pipeline's barrier, call -> drained (an entry
  that carries an aggregate; Sum2's base entry brackets the phase's own
  drain, which without a journal runs beside the vote window);
- ``fetch``: ``StagedAggregator.snapshot_journal`` after the barrier: the
  accumulator device to host, shard by shard (the copy alone);
- ``dicts``: the store's sum and seed dictionaries read and inverted into
  the journal's replay form;
- ``serialise``: the SHA-256 of each section no earlier entry of the round
  hashed, over the buffer in place, and the JSON header
  (``resilience/checkpoint.py::_serialise``): nothing of a section's size is
  allocated in it;
- ``store``: ``set_round_checkpoint(head, sections)`` to its return, retries
  included (the file store: the sections it has no file of, then the head);
- ``total``: one observation a write, around all of them.

The five stages are mirrored into the profiler's trace (``total`` is not:
it would cover them all and say nothing), so the device's clock sees the
barrier and the copy, and the idle time under a write has a name
(``idle.attributed_share`` read 74% with ``drain`` and ``fetch`` alone: my
chip run, PR 46). An entry runs the stages it
has: a ``sum`` entry, the seal at Sum -> Update and a rewrite for a vote
have no aggregate to drain or fetch.

``xaynet_journal_bytes_total{phase}`` is what the writes handed the store
(the head, and the sections no earlier entry of the round handed it),
``xaynet_journal_section_bytes_total{section, route}`` every section an
entry carried, by name (``vect``, ``unit``, ``votes``, ``planes``) and by
what became of it: ``written`` (hashed and handed to the store by this
entry) or ``reused`` (the digest and the stored bytes of an earlier entry of
the round kept), counted where ``write_entry`` makes the choice; and
``xaynet_journal_writes_total{phase, outcome}`` the writes, ``saved`` or
``failed``: a write that fails is skipped, not raised, so a journal that
broke reads as a faster round unless someone looks here. ``/healthz``
``journal`` says the same (``report``).

The resume's side: ``resume.load`` (the blob read and parsed, digests
checked), ``resume.validate`` (identity, watermark, the dictionaries
replayed into the store) and ``resume.restore`` (the aggregate back onto
the device), recorded into the first round's window beside ``startup.*``.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from . import tracing as trace
from .registry import get_registry

_registry = get_registry()
# a toy round's microseconds up to the seconds a 360 MB entry takes
SECONDS = _registry.histogram(
    "xaynet_journal_seconds",
    "Wall time of one stage of one round-journal write, by stage (drain, "
    "fetch, dicts, serialise, store, total) and by the entry's phase tag "
    "(telemetry/journal.py).",
    ("stage", "phase"),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
)
BYTES = _registry.counter(
    "xaynet_journal_bytes_total",
    "Bytes of round-journal entries handed to the store, by the entry's "
    "phase tag (telemetry/journal.py).",
    ("phase",),
)
SECTION_BYTES = _registry.counter(
    "xaynet_journal_section_bytes_total",
    "Bytes of the payload sections that saved round-journal entries "
    "carried, by section (vect | unit | votes | planes) and route: written "
    "= hashed and handed to the store by this entry; reused = the digest "
    "and the stored bytes kept from an earlier entry of the round "
    "(telemetry/journal.py).",
    ("section", "route"),
)
WRITES = _registry.counter(
    "xaynet_journal_writes_total",
    "Round-journal writes, by the entry's phase tag and outcome (saved | "
    "failed: skipped after the retry policy, the round goes on) "
    "(telemetry/journal.py).",
    ("phase", "outcome"),
)

# stage label -> span name; spelled out (not built in a loop) so the
# analysis `span` pass reads the literal set against the DESIGN §16 table
# (`usage`, telemetry/tracing.py: `dicts` is the loop thread's own work and
# `serialise` its executor thread's, entry to exit: the SHA-256 runs on the
# thread that calls it. `store` is opened on the loop around the store's
# `await`; the file store writes on an executor thread, which reads itself
# under the stage's name: `writing`)
_SPANS: dict[str, str] = {
    "drain": trace.declare_span("journal.drain", mirror=True),
    "fetch": trace.declare_span("journal.fetch", mirror=True),
    "dicts": trace.declare_span("journal.dicts", mirror=True, usage="thread"),
    "serialise": trace.declare_span("journal.serialise", mirror=True, usage="thread"),
    "store": trace.declare_span("journal.store", mirror=True, usage="carrier"),
    "total": trace.declare_span("journal.total"),
}
_RESUME_SPANS: dict[str, str] = {
    "load": trace.declare_span("resume.load"),
    "validate": trace.declare_span("resume.validate"),
    "restore": trace.declare_span("resume.restore"),
}

_lock = threading.Lock()
_writes: dict[str, int] = {}  # phase -> saved; guarded-by: _lock
_failed: dict[str, int] = {}  # phase -> failed; guarded-by: _lock
_last: dict | None = None  # guarded-by: _lock
_resume: list[tuple[str, float, float, dict]] = []  # guarded-by: _lock


class Write:
    """One journal write in flight: its phase tag and the context of its
    ``journal.total`` span, which the stages on executor threads (whose
    ambient context is empty) parent to."""

    __slots__ = ("phase", "ctx", "bytes", "routes", "outcome")

    def __init__(self, phase: str, ctx):
        self.phase = phase
        self.ctx = ctx
        self.bytes = 0
        self.routes = {"written": 0, "reused": 0}  # section bytes, by route
        self.outcome = "failed"  # until the store has the entry

    def stage(self, label: str, **attrs):
        """Bracket one stage of this write where it runs."""
        return trace.timed_span(
            _SPANS[label], SECONDS.labels(stage=label, phase=self.phase),
            ctx=self.ctx, phase=self.phase, **attrs,
        )

    def saved(self, nbytes: int, sections=()) -> None:
        """The store returned: ``nbytes`` is what it was handed (the head
        and the sections written), ``sections`` every section the entry
        carried as ``(name, route, bytes)``."""
        self.bytes = int(nbytes)
        self.outcome = "saved"
        for name, route, size in sections:
            if size:
                SECTION_BYTES.labels(section=name, route=route).inc(size)
                self.routes[route] += int(size)


@contextmanager
def write(phase: str, **attrs):
    """One journal write: ``journal.total`` around it (a child of whatever
    span is ambient: the phase's, or the message's stage that pays), one
    count by outcome when it ends. Fail-soft writers swallow their error
    inside the block and leave the outcome ``failed``."""
    t0 = time.monotonic()
    with trace.timed_span(
        _SPANS["total"], SECONDS.labels(stage="total", phase=phase), phase=phase, **attrs
    ) as span:
        w = Write(phase, span.ctx)
        try:
            yield w
        finally:
            span.set(bytes=w.bytes, outcome=w.outcome)
            WRITES.labels(phase=phase, outcome=w.outcome).inc()
            if w.outcome == "saved":
                BYTES.labels(phase=phase).inc(w.bytes)
            global _last
            with _lock:
                tally = _writes if w.outcome == "saved" else _failed
                tally[phase] = tally.get(phase, 0) + 1
                _last = {"phase": phase, "outcome": w.outcome, "bytes": w.bytes,
                         **w.routes, "seconds": round(time.monotonic() - t0, 6)}


def writing():
    """What the calling thread spends inside the block, credited to the
    ``store`` stage (``tracing.usage_of``): for the thread that writes an
    entry's files while the loop, which opened the stage, waits for it."""
    return trace.usage_of(_SPANS["store"])


def report(enabled: bool, every_batches: int) -> dict:
    """The ``journal`` section of ``/healthz``."""
    with _lock:
        return {"enabled": bool(enabled), "every_batches": int(every_batches),
                "writes": dict(_writes), "failed": dict(_failed), "last": _last}


@contextmanager
def resume_stage(label: str, **attrs):
    """Bracket one step of a boot-time resume. The steps run before any
    round window is open, so they are kept and recorded into the first
    round's window (as ``startup.*`` are), in a trace of their own."""
    t0 = time.monotonic()
    try:
        yield
    finally:
        with _lock:
            _resume.append((label, t0, time.monotonic() - t0, attrs))
        trace.get_tracer().add_round_hook(_record_resume)  # once: the tracer keeps one


def _record_resume(_round_id: int) -> None:
    with _lock:
        steps, _resume[:] = list(_resume), []
    if not steps:
        return
    ctx = trace.TraceContext(trace.new_id())
    for label, start, took, attrs in steps:
        trace.get_tracer().record_span(
            _RESUME_SPANS[label], start=start, duration=took, ctx=ctx, **attrs
        )
