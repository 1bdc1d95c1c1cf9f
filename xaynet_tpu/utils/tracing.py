"""Request ids: correlation across the service/state-machine boundary.

The reference instruments every request with a tracing span that travels
through the request channel so state-machine-side logs correlate with the
HTTP request that caused them (reference:
rust/xaynet-server/src/state_machine/requests.rs:120,157-165). Here that
is a contextvar-scoped request id: the REST layer (or the message pipeline,
for callers that skip the socket) assigns one per message, the request
envelope carries it across the queue, and the phase restores it while
handling — so a single grep on the id yields the full path of one message
through the system. The spans themselves are ``telemetry/tracing.py``'s
(the one tracer); every per-message stage span carries this id as ``rid``.
"""

from __future__ import annotations

import contextvars
import logging
import uuid
from contextlib import contextmanager

request_id: contextvars.ContextVar[str] = contextvars.ContextVar("xaynet_request_id", default="-")


def make_request_id() -> str:
    """A fresh id, not yet anyone's (pair with :func:`use_request_id`)."""
    return uuid.uuid4().hex[:12]


def new_request_id() -> str:
    rid = make_request_id()
    request_id.set(rid)
    return rid


def current_request_id() -> str:
    return request_id.get()


def request_id_or_fresh() -> str:
    """The ambient id where the REST layer has named the message, else a
    fresh one (callers that skip the socket: in-process clients, tests)."""
    rid = request_id.get()
    return rid if rid != "-" else make_request_id()


@contextmanager
def use_request_id(rid: str):
    token = request_id.set(rid)
    try:
        yield
    finally:
        request_id.reset(token)


class RequestIdFilter(logging.Filter):
    """Attach ``%(request_id)s`` to log records for formatter use."""

    def filter(self, record: logging.LogRecord) -> bool:
        record.request_id = request_id.get()
        return True
