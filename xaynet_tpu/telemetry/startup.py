"""Start-up as a timeline: process start to serving, step by step
(docs/DESIGN.md §16).

How long a (re)started coordinator takes to serve is the operator's restart
time. ``server/runner.py`` marks the steps as it passes them, in seconds
since the process started: ``imports`` (entry of ``serve()``: the
interpreter's start, the imports, the settings), ``backend``
(``init_device_backend`` returned: JAX up, devices known, compile cache
placed), ``store``, ``machine`` (``StateMachineInitializer.init()``
returned: aggregator and pools built) and ``serving`` (``rest.start``
returned). ``imports`` begins a timeline (a process that serves again, as
tests do, starts a new one); every other step is marked once in it: where
several tenants are built, the first one's marks stand.

They are published as ``xaynet_startup_seconds{step}`` (a gauge: the step's
own duration), as the ``startup`` section of ``/healthz`` (``at`` = seconds
since the process started when the step ended, ``took`` = its own
duration) and as spans ``startup.<step>``, recorded into the first round's
window so that the tracer's export of that round holds them.

The process's start is the kernel's (``/proc/self/stat``, field 22, against
``CLOCK_BOOTTIME``) where that gives an instant shortly before this module's
first clock read, else that read (``origin`` says which: ``proc`` or
``import``).
"""

from __future__ import annotations

import os
import threading
import time

from . import tracing as trace
from .registry import get_registry

_FIRST_READ = time.monotonic()
_MAX_PRELUDE_S = 600.0  # exec to this module's import, at the very most

SECONDS = get_registry().gauge(
    "xaynet_startup_seconds",
    "Duration of one step of the last start-up, by step: imports (process "
    "start to the entry of serve()), backend, store, machine, serving "
    "(telemetry/startup.py).",
    ("step",),
)

# step -> span name; spelled out so the analysis `span` pass reads the
# literal set against the DESIGN §16 table
_SPANS: dict[str, str] = {
    "imports": trace.declare_span("startup.imports"),
    "backend": trace.declare_span("startup.backend"),
    "store": trace.declare_span("startup.store"),
    "machine": trace.declare_span("startup.machine"),
    "serving": trace.declare_span("startup.serving"),
}


def _process_start() -> tuple[float, str]:
    """``time.monotonic()`` of the process's start, and where it is from."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        start = time.monotonic() - age
        # a sandboxed /proc may give no start time, or one on another clock:
        # an instant after the first read, or minutes before it, is neither
        if ticks > 0 and 0.0 <= _FIRST_READ - start <= _MAX_PRELUDE_S:
            return start, "proc"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return _FIRST_READ, "import"


class Timeline:
    """The marks of one process's start-up."""

    def __init__(self):
        self._lock = threading.Lock()
        self.start, self.origin = _process_start()
        self._marks: dict[str, tuple[float, float]] = {}  # step -> (at, took); guarded-by: _lock
        self._last = 0.0  # guarded-by: _lock
        self._exported = False  # guarded-by: _lock

    def mark(self, step: str) -> None:
        """``step`` has just ended (its first end since ``imports`` counts)."""
        at = time.monotonic() - self.start
        with self._lock:
            if step == "imports":
                self._marks, self._last, self._exported = {}, 0.0, False
            elif step in self._marks:
                return
            took = at - self._last
            self._marks[step] = (at, took)
            self._last = at
        SECONDS.labels(step=step).set(took)
        if step == "serving":
            # the spans go into the first round window to open (the state
            # machine starts after the API does); the exports' clock starts
            # with the process, so that no start-up span lies before it
            tracer = trace.get_tracer()
            tracer.anchor = min(tracer.anchor, self.start)
            tracer.add_round_hook(self._record_spans)

    def seconds(self, since: str, until: str) -> float:
        """From the end of ``since`` to the end of ``until``."""
        with self._lock:
            return self._marks[until][0] - self._marks[since][0]

    def _record_spans(self, _round_id: int) -> None:
        with self._lock:
            if self._exported:
                return
            self._exported = True
            marks = dict(self._marks)
        # a trace of their own: they began before any round's root did
        ctx = trace.TraceContext(trace.new_id())
        for step, (at, took) in marks.items():
            trace.get_tracer().record_span(
                _SPANS[step], start=self.start + at - took, duration=took, ctx=ctx,
                at=round(at, 6),
            )

    def report(self) -> dict | None:
        """The ``startup`` section of ``/healthz`` (None before any mark)."""
        with self._lock:
            marks = dict(self._marks)
        if not marks:
            return None
        out: dict = {step: {"at": round(at, 6), "took": round(took, 6)}
                     for step, (at, took) in marks.items()}
        out["origin"] = self.origin
        return out


_timeline = Timeline()


def get_timeline() -> Timeline:
    """The process's start-up timeline."""
    return _timeline
