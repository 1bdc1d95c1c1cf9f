"""The stages of one Update message, socket to fold (docs/DESIGN.md §16).

A message's residence in the coordinator is a chain of stages on several
threads and two queues. Each stage is written down twice by one call here:
as a span of the tracer (``telemetry/tracing.py``; child of the message's
``rest.request`` span, attribute ``rid`` = the request id of
``utils/tracing.py``) and as one observation on
``xaynet_message_pipeline_seconds{stage=...}``. The spans say where one
message's seconds went; the histogram says it for a window of ``/metrics``.

Work is bracketed where it happens (:func:`stage`, a ``with`` block). The
waits start on one task or thread and end on another, so they are recorded
when they end (:func:`waited`): for a free ``pet-msg`` worker
(``pool_wait``), for the event loop to resume the message's coroutine once
the worker has returned (``resume_wait``), in the request channel
(``request_wait``), and for the loop again once the phase has resolved the
verdict (``verdict_wait``). Those reach the tracer and the histogram, not
the mirror sink, which has no call for an interval that is already over.

The labels ``total`` (a message's whole handling after its body is read)
and ``decrypt_parse`` / ``decrypt_parse_batch`` (the pool hop, wait
included) keep their older meaning; no stage label here starts with
``decrypt_parse``.
"""

from __future__ import annotations

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
    "seed_dict, stage, to_planar, flush, verdict_wait (server/stages.py); "
    "decrypt_parse[_batch] = the pool hop (pool_wait to resume_wait); "
    "total = body read to the state machine's verdict.",
    ("stage",),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
)

# stage label -> span name; spelled out (not built in a loop) so the
# analysis `span` pass reads the literal set against the DESIGN §16 table
_SPANS: dict[str, str] = {
    "read_body": trace.declare_span("rest.read_body", mirror=True),
    "pool_wait": trace.declare_span("pipeline.pool_wait"),
    "open": trace.declare_span("pipeline.open", mirror=True),
    "verify": trace.declare_span("pipeline.verify", mirror=True),
    "parse": trace.declare_span("pipeline.parse", mirror=True),
    "resume_wait": trace.declare_span("pipeline.resume_wait"),
    "request_wait": trace.declare_span("update.request_wait"),
    "validate": trace.declare_span("update.validate", mirror=True),
    "seed_dict": trace.declare_span("update.seed_dict", mirror=True),
    "stage": trace.declare_span("update.stage", mirror=True),
    "to_planar": trace.declare_span("update.to_planar", mirror=True),
    "flush": trace.declare_span("update.flush", mirror=True),
    "verdict_wait": trace.declare_span("update.verdict_wait"),
}
# the state machine with nothing to do: waiting for the next request
SPAN_AWAIT_REQUEST = trace.declare_span("update.await_request", mirror=True)


@contextmanager
def stage(label: str, ctx: Optional[trace.TraceContext] = None,
          link: Optional[trace.TraceContext] = None, **attrs):
    """Bracket one stage where it runs. ``ctx`` names the parent on a worker
    thread (the ambient context does not cross ``run_in_executor``);
    ``rid`` defaults to the ambient request id, so pass it there too."""
    attrs.setdefault("rid", current_request_id())
    t0 = time.monotonic()
    try:
        with trace.get_tracer().span(_SPANS[label], ctx=ctx, link=link, **attrs) as span:
            yield span
    finally:
        SECONDS.labels(stage=label).observe(time.monotonic() - t0)


def waited(label: str, since: float, ctx: Optional[trace.TraceContext] = None,
           **attrs) -> None:
    """Record a queue wait that began at ``since`` (``time.monotonic()``, on
    another task or thread) and ends now."""
    attrs.setdefault("rid", current_request_id())
    seconds = max(0.0, time.monotonic() - since)
    trace.get_tracer().record_span(_SPANS[label], start=since, duration=seconds,
                                   ctx=ctx, **attrs)
    SECONDS.labels(stage=label).observe(seconds)
