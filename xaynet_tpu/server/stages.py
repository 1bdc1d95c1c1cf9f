"""The stages of one message, socket to verdict (docs/DESIGN.md §16).

A message's residence in the coordinator is a chain of stages on several
threads and two queues. Each stage is written down twice by one call here:
as a span of the tracer (``telemetry/tracing.py``; child of the message's
``rest.request`` span, attribute ``rid`` = the request id of
``utils/tracing.py``) and as one observation on
``xaynet_message_pipeline_seconds{stage=..., phase=...}``. The spans say
where one message's seconds went; the histogram says it for a window of
``/metrics``.

``phase`` is the phase the message's own coordinator was in **when the
message arrived**: read once from that coordinator's published state where
the message's ``rest.request`` span opens (``server/rest.py``; callers that
skip the socket: ``PetMessageHandler.handle_message``) and carried with the
message as its ``rid`` is (:func:`use_phase`, the request envelope), so all
stages of one message carry one phase: the last update's ``verdict_wait``
ends after the phase has moved on and is still an ``update`` observation,
and the Sum2 message is ``sum2`` from its first byte. It is no process-wide
variable: one process can serve several tenants. ``-`` = no message is
being handled (work staged by a test or a tool).

Work is bracketed where it happens (:func:`stage`, a ``with`` block). The
waits start on one task or thread and end on another, so they are recorded
when they end (:func:`waited`): for a free ``pet-msg`` worker
(``pool_wait``), for the event loop to resume the message's coroutine once
the worker has returned (``resume_wait``), in the request channel
(``request_wait``), and for the loop again once the phase has resolved the
verdict (``verdict_wait``). Those reach the tracer and the histogram, not
the mirror sink, which has no call for an interval that is already over.

What a phase does with the message is a stage of the chain too:
``validate``, ``seed_dict``, ``stage`` and ``flush`` are the Update
phase's, ``score`` the Sum2 phase's.

Under ``[aggregation] wire_ingest`` two more lie INSIDE ``validate`` and are
no part of the chain's sum either: ``ingest_h2d`` (the element block, a view
of the body, put to the device until the transfer is done) and
``ingest_unpack`` (the device's de-interleave and order check, dispatch to
the verdict on the host), bracketed where they run
(``StagedAggregator._validate_on_device``, on the executor's thread that
``validate`` carries its context to).

Two stages run beside the chain and are no part of its sum: ``to_planar``
(the slot write, on the ``xn-ingest`` pool) and ``verify_beside`` (a long
message's whole signature pass, on a ``pet-verify`` thread while the worker
parses). For such a message ``verify`` is what the chain waits for the
verdict once the parse has returned; for a short one, whose signature is
checked on the worker before the parse, it is the pass itself.

The labels ``total`` (a message's whole handling after its body is read)
and ``decrypt_parse`` / ``decrypt_parse_batch`` (the pool hop, wait
included) keep their older meaning; no stage label here starts with
``decrypt_parse``.
"""

from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager
from typing import Optional

from ..telemetry import tracing as trace
from ..telemetry.registry import get_registry
from ..utils.tracing import current_request_id

# 0.5 ms for a small message's crypto up to minutes: a 179 MB update spends
# seconds in single stages and tens of seconds in `total` under a flood
SECONDS = get_registry().histogram(
    "xaynet_message_pipeline_seconds",
    "Wall time of one stage of a message's handling, by stage: read_body, "
    "pool_wait, open, verify, parse, resume_wait, request_wait, validate, "
    "seed_dict, stage, flush, score, verdict_wait; inside validate under wire "
    "ingest ingest_h2d, ingest_unpack; beside the chain to_planar, "
    "verify_beside (server/stages.py); decrypt_parse[_batch] = the pool hop (pool_wait to "
    "resume_wait); total = body read to the state machine's verdict. phase = "
    "the phase the message's coordinator was in when the message arrived.",
    ("stage", "phase"),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
)

# stage label -> span name; spelled out (not built in a loop) so the
# analysis `span` pass reads the literal set against the DESIGN §16 table
# (`usage`: whose CPU seconds, page faults and context switches the stage's
# brackets read, `telemetry/tracing.py`. `thread` = the work runs on the
# opening thread from entry to exit; `crew` = on it and on the workers the
# native library starts for it over the element axis (a v1 body's relayout
# and a v2 body's check in the parse, the slot's plane copy); `carrier` =
# the stage is opened on the loop around an `await` while another thread
# works, which reads itself and its crew under the stage's name: `carried`,
# `usage`)
_SPANS: dict[str, str] = {
    "read_body": trace.declare_span("rest.read_body", mirror=True, usage="carrier"),
    "pool_wait": trace.declare_span("pipeline.pool_wait"),
    "open": trace.declare_span("pipeline.open", mirror=True, usage="thread"),
    "verify": trace.declare_span("pipeline.verify", mirror=True, usage="thread"),
    "verify_beside": trace.declare_span("pipeline.verify_beside", mirror=True, usage="thread"),
    "parse": trace.declare_span("pipeline.parse", mirror=True, usage="crew"),
    "resume_wait": trace.declare_span("pipeline.resume_wait"),
    "request_wait": trace.declare_span("update.request_wait"),
    "validate": trace.declare_span("update.validate", mirror=True, usage="carrier"),
    "ingest_h2d": trace.declare_span("ingest.h2d", mirror=True),
    "ingest_unpack": trace.declare_span("ingest.unpack", mirror=True),
    "seed_dict": trace.declare_span("update.seed_dict", mirror=True),
    "stage": trace.declare_span("update.stage", mirror=True, usage="thread"),
    "to_planar": trace.declare_span("update.to_planar", mirror=True, usage="crew"),
    "flush": trace.declare_span("update.flush", mirror=True, usage="carrier"),
    "score": trace.declare_span("sum2.score", mirror=True, usage="thread"),
    "verdict_wait": trace.declare_span("update.verdict_wait"),
}
# the state machine with nothing to do: waiting for the next request
SPAN_AWAIT_REQUEST = trace.declare_span("update.await_request", mirror=True)

_phase: contextvars.ContextVar[str] = contextvars.ContextVar("xaynet_message_phase", default="-")


def current_phase() -> str:
    """The arrival phase of the message being handled (``-`` outside one)."""
    return _phase.get()


@contextmanager
def _carried(var: contextvars.ContextVar, value):
    token = var.set(value)
    try:
        yield
    finally:
        var.reset(token)


def use_phase(phase: str):
    """Name the arrival phase of the message handled inside (where it
    arrives) or re-enter it on the far side of a queue or a thread hop."""
    return _carried(_phase, phase)


_held: contextvars.ContextVar = contextvars.ContextVar("xaynet_message_held", default=None)


def current_held():
    """The resident-body count's hold on the message being handled
    (``telemetry/intake.py``), for the worker that opens it to release;
    ``None`` where the REST layer counted nothing."""
    return _held.get()


def use_held(held):
    """Carry ``held`` with the message handled inside, as its phase is."""
    return _carried(_held, held)


def seconds(label: str, phase: Optional[str] = None):
    """The histogram child of ``label`` for ``phase`` (default: the message
    being handled), for the lumps that are timed with no span."""
    return SECONDS.labels(stage=label, phase=phase or _phase.get())


def stage(label: str, ctx: Optional[trace.TraceContext] = None,
          link: Optional[trace.TraceContext] = None, **attrs):
    """Bracket one stage where it runs. ``ctx`` names the parent on a worker
    thread (the ambient context does not cross ``run_in_executor``);
    ``rid`` and ``phase`` default to the ambient ones, so pass them there
    too."""
    attrs.setdefault("rid", current_request_id())
    phase = attrs.setdefault("phase", _phase.get())
    return trace.timed_span(_SPANS[label], SECONDS.labels(stage=label, phase=phase),
                            ctx=ctx, link=link, **attrs)


def usage(label: str, count: bool = True, spent: Optional[dict] = None):
    """What the calling thread spends inside the block, credited to stage
    ``label`` (``tracing.usage_of``): for the thread that does the work of a
    stage opened elsewhere. ``count=False`` where one message's stage takes
    several blocks; :func:`counted` then counts the message, once."""
    return trace.usage_of(_SPANS[label], count, spent)


def counted(label: str) -> None:
    """One message's stage ``label`` has its usage on the counters."""
    trace.count_usage(_SPANS[label])


def carried(label: str, work, *args) -> dict:
    """Run ``work(*args)`` on this thread, an executor's, for a stage
    ``label`` that the loop opened around the ``await``: what the thread
    spent is counted under the stage's name and returned, for the span's
    attributes."""
    with usage(label) as spent:
        work(*args)
    return spent


def waited(label: str, since: float, ctx: Optional[trace.TraceContext] = None,
           **attrs) -> None:
    """Record a queue wait that began at ``since`` (``time.monotonic()``, on
    another task or thread) and ends now."""
    attrs.setdefault("rid", current_request_id())
    phase = attrs.setdefault("phase", _phase.get())
    took = max(0.0, time.monotonic() - since)
    trace.get_tracer().record_span(_SPANS[label], start=since, duration=took,
                                   ctx=ctx, **attrs)
    SECONDS.labels(stage=label, phase=phase).observe(took)
