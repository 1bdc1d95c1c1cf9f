"""Distributed round tracing: spans across coordinator, ingest, shard
workers, edge tier and SDK (docs/DESIGN.md §16).

PR 1's telemetry is aggregate-only — counters and gauges with no causal
story. This module adds the causal layer: **spans** (name, trace id, span
id, optional parent, monotonic wall) recorded around every stage of a
round, so "where did batch 37 spend its time" is one artifact instead of a
print-debugging session. Stdlib only, same discipline as the registry.

Identity model
--------------

- The **round trace id** is derived deterministically from the round seed
  (``round_trace_id``): the coordinator, every edge, and every SDK
  participant compute the SAME id independently, so one two-tier round
  yields ONE stitched trace without a coordination protocol.
- Cross-process hops (SDK -> REST, edge -> coordinator) additionally carry
  an explicit ``trace_id-span_id`` pair — the ``X-Xaynet-Trace`` header
  and the ``XNEDGE1`` envelope ``trace`` field. The receiver ADOPTS the
  trace id and records the remote span id as a ``link`` attribute (not as
  ``parent``): within one process's export every ``parent`` resolves, so
  the validator can stay strict about orphans.
- Span NAMES are a closed set: every name is registered exactly once via
  :func:`declare_span` (duplicate registration raises), ``Tracer.span``
  refuses undeclared names, and the analysis framework cross-checks the
  declared set against the DESIGN §16 span table (rule ``span``).

Buffers and sampling
--------------------

Spans land in two bounded places:

- the **flight-recorder ring** (``deque(maxlen=ring_size)``) — always on
  while tracing isn't ``off``; this is the "what led up to this" forensic
  buffer the recorder dumps on failure triggers;
- the **per-round buffer** (bounded; overflow counted on
  ``xaynet_trace_spans_dropped_total``) — drained into a Chrome-trace
  (Perfetto-loadable) JSON per round when a ``trace_dir`` is configured.

``XAYNET_TRACE`` picks the mode: ``on`` (default — record + export),
``failure`` (ring only: spans exist for the flight recorder, no per-round
export), ``off`` (spans are no-ops). Failed/degraded rounds are always
covered by the ring regardless of sampling — the ring never samples.

What a span's thread spent
--------------------------

A wall clock cannot tell a stage that worked from one that waited, faulted
pages in or stood without a core. The kernel keeps all of that for every
thread; a span declared ``declare_span(name, usage=...)`` reads it at entry
and exit (``getrusage``) and writes the differences down twice, as the
histograms are: on four counters of the registry (``xaynet_span_*``, by
span name) and, at its end, in its attributes (``cpu_s``, ``sys_s``,
``minflt``, ``majflt``, ``nvcsw``, ``nivcsw``). ``"thread"``: the work runs
on the opening thread from entry to exit. ``"crew"``: on the opening thread
and on worker threads that the native library starts and joins for it
inside the span (a copy or a relayout over the element axis), whose tally
the library keeps for the thread that joined them (``set_workers_reader``).
``"process"``: it fans out over threads nobody tallies and the process has
nothing else of that size in flight. ``"carrier"``: the span is opened
around an ``await`` while another thread works; the span itself reads
nothing and the thread that works brackets itself with :func:`usage_of`
under the span's name, its crew included. A bracket closed on another
thread than it was opened on, and a platform without the call, count
nothing and raise nothing (docs/DESIGN.md §16, "What a stage's thread
spent").
"""

from __future__ import annotations

import contextvars
import json
import logging
import os
import random
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Iterable, Optional

from .registry import get_registry

try:
    import resource
except ImportError:  # not on this platform: usage spans count nothing
    resource = None

logger = logging.getLogger("xaynet.telemetry")

TRACE_HEADER = "X-Xaynet-Trace"

_registry = get_registry()
SPANS_TOTAL = _registry.counter(
    "xaynet_trace_spans_total",
    "Spans finished, by subsystem (the prefix before the first dot of the "
    "span name — closed set, see docs/DESIGN.md §16).",
    ("subsystem",),
)
SPANS_DROPPED = _registry.counter(
    "xaynet_trace_spans_dropped_total",
    "Spans dropped because the per-round buffer hit its bound (the "
    "flight-recorder ring still keeps the most recent ones).",
)
TRACE_EXPORTS = _registry.counter(
    "xaynet_trace_exports_total",
    "Per-round Chrome-trace exports, by outcome (written | failed).",
    ("outcome",),
)
SPAN_CPU = _registry.counter(
    "xaynet_span_cpu_seconds_total",
    "CPU seconds the kernel charged between the entry and the exit of the "
    "spans declared with usage, by span name and mode (user | system): the "
    "opening thread's, with its native workers' for a span declared `crew`, "
    "the whole process's for one declared `process` (telemetry/tracing.py; "
    "docs/DESIGN.md §16).",
    ("span", "mode"),
)
SPAN_FAULTS = _registry.counter(
    "xaynet_span_page_faults_total",
    "Page faults inside the spans declared with usage, by span name and "
    "kind: minor (a page mapped without I/O: the first touch of fresh "
    "memory) | major.",
    ("span", "kind"),
)
SPAN_SWITCHES = _registry.counter(
    "xaynet_span_context_switches_total",
    "Context switches inside the spans declared with usage, by span name "
    "and kind: voluntary (the thread slept or waited) | involuntary (it was "
    "taken off its core).",
    ("span", "kind"),
)
SPAN_USAGE = _registry.counter(
    "xaynet_span_usage_total",
    "Spans that recorded their usage, by span name: what the three "
    "counters beside it are divided by. A stage read on its carrier counts "
    "one a message, however many brackets its body took.",
    ("span",),
)


class SpanNameError(ValueError):
    """Span name declared twice, or used without a declaration."""


# the process-wide span-name registry: name -> declaring module (for the
# duplicate-declaration diagnostic). The analysis `span` pass mirrors this
# statically and cross-checks it against the DESIGN §16 table.
_SPAN_NAMES: dict[str, str] = {}
# the subset a mirror sink also receives (``Tracer.set_mirror``): chosen at
# the declaration, never at a call site. Spans that would cover every idle
# gap whole (``round``, ``phase.*``, ``rest.request``) stay out of it.
_MIRRORED: set[str] = set()
# name -> whose usage its brackets read (``thread`` | ``crew`` | ``process``
# | ``carrier``), and for the names the span itself reads where it opens and
# closes, how (``_how``: resolved once, a bracket looks nothing else up)
_USAGE: dict[str, str] = {}
_USAGE_ON_SPAN: dict[str, tuple] = {}
_names_lock = threading.Lock()

# whose usage -> the `who` of getrusage; None where the platform has no such call
_RUSAGE_WHO: dict[str, Optional[int]] = {
    "thread": getattr(resource, "RUSAGE_THREAD", None),
    "process": getattr(resource, "RUSAGE_SELF", None),
}
_RUSAGE_WHO["crew"] = _RUSAGE_WHO["carrier"] = _RUSAGE_WHO["thread"]
# the words whose reading takes in the native workers of the thread
_WITH_WORKERS = frozenset({"crew", "carrier"})
# () -> what the native workers that the calling thread started and joined
# have spent so far, as a reading's six numbers; None until the library is
# loaded (utils/native.py), and a crew is then its thread alone
_workers = None


def set_workers_reader(reader) -> None:
    """Install the tally of native worker threads (``xn_workers_spent``).
    This module stays stdlib-only: the loader of the library hands it in."""
    global _workers
    _workers = reader


def _how(usage: str) -> tuple:
    """``(who, with_workers)`` of a usage word: what ``getrusage`` is asked
    (None where the platform has no such call) and whether the reading
    takes in the native workers of the thread."""
    return _RUSAGE_WHO[usage], usage in _WITH_WORKERS


def declare_span(name: str, mirror: bool = False, usage: Optional[str] = None) -> str:
    """Register one span name exactly once (module import time).

    Returns the name so modules can bind it: ``SPAN_X = declare_span("x.y")``.
    ``mirror=True`` also hands the span, when opened with ``with``, to the
    tracer's mirror sink (the profiler's clock; docs/DESIGN.md §16).
    ``usage`` says whose CPU seconds, page faults and context switches its
    brackets read (the module docstring has the four words).
    """
    if not name or any(c.isspace() for c in name):
        raise SpanNameError(f"bad span name {name!r}")
    if usage is not None and usage not in _RUSAGE_WHO:
        raise SpanNameError(f"span {name!r}: usage is one of {sorted(_RUSAGE_WHO)}, not {usage!r}")
    import inspect

    frame = inspect.currentframe()
    module = "?"
    if frame is not None and frame.f_back is not None:
        module = frame.f_back.f_globals.get("__name__", "?")
    with _names_lock:
        owner = _SPAN_NAMES.get(name)
        if owner is not None and owner != module:
            raise SpanNameError(
                f"span name {name!r} already declared by {owner}; "
                "one module owns a span name — import its constant instead"
            )
        _SPAN_NAMES[name] = module
        if mirror:
            _MIRRORED.add(name)
        if usage is not None:
            _USAGE[name] = usage
            if usage != "carrier":
                _USAGE_ON_SPAN[name] = _how(usage)
    return name


def declared_span_names() -> dict[str, str]:
    """Snapshot of the declared span names (tests, the analysis pass)."""
    with _names_lock:
        return dict(_SPAN_NAMES)


def mirrored_span_names() -> list[str]:
    """The declared names a mirror sink receives, sorted (``/healthz``
    publishes them so a trace reader can tell program spans from the
    runtime's own events without a copied list)."""
    with _names_lock:
        return sorted(_MIRRORED)


def usage_span_names() -> dict[str, str]:
    """The names declared with ``usage`` and the word each was given."""
    with _names_lock:
        return dict(_USAGE)


# the root span every phase span parents to; declared here because the
# tracer itself records it at round end
SPAN_ROUND = declare_span("round")

# a usage reading as it is written on a span, from struct_rusage's fields
# 0, 1, 6, 7, 14 and 15 (ru_utime, ru_stime, ru_minflt, ru_majflt, ru_nvcsw,
# ru_nivcsw)
_USAGE_KEYS = ("cpu_s", "sys_s", "minflt", "majflt", "nvcsw", "nivcsw")
_usage_children: dict[str, tuple] = {}  # name -> its children of the four counters


def _children_of(name: str) -> tuple:
    """The counter children of one span name, in ``_USAGE_KEYS``' order and
    the usage count last (looked up once a name: a bracket costs no label
    lookups)."""
    children = _usage_children.get(name)
    if children is None:
        children = _usage_children[name] = (
            SPAN_CPU.labels(span=name, mode="user"),
            SPAN_CPU.labels(span=name, mode="system"),
            SPAN_FAULTS.labels(span=name, kind="minor"),
            SPAN_FAULTS.labels(span=name, kind="major"),
            SPAN_SWITCHES.labels(span=name, kind="voluntary"),
            SPAN_SWITCHES.labels(span=name, kind="involuntary"),
            SPAN_USAGE.labels(span=name),
        )
    return children


def count_usage(name: str) -> None:
    """One more span of ``name`` has its usage on the counters: for a stage
    whose brackets were opened with ``count=False`` (the ``rest-overflow``
    thread takes a body in many turns, and the body counts once, whole)."""
    _children_of(name)[6].inc()


class usage_of:
    """What the calling thread (its crew, for a name declared ``crew`` or
    ``carrier``; the process, for one declared so) spends inside the block,
    added to the counters of span ``name`` and to ``spent``, which the block
    gets: ``cpu_s``, ``sys_s``, ``minflt``, ``majflt``, ``nvcsw``,
    ``nivcsw``, there once the block has ended. A span declared ``thread``,
    ``crew`` or ``process`` is read by its own handle; this is for the
    thread that works under a span opened elsewhere (``carrier``). Given a
    ``spent`` of its own, the block adds to what that holds: one dict over
    several blocks sums them. Left on another thread than it was entered
    on, or where the platform has no reading, it adds nothing anywhere."""

    __slots__ = ("spent", "_name", "_how", "_count", "_adds", "_start")

    def __init__(self, name: str, count: bool = True, spent: Optional[dict] = None):
        try:
            self._how = _how(_USAGE[name])
        except KeyError:
            raise SpanNameError(f"span name {name!r} was never declared with usage") from None
        self._name = name
        self._count = count
        self._adds = spent is not None
        self.spent = spent if self._adds else {}
        self._start = None

    def __enter__(self) -> dict:
        self._start = _usage_start(*self._how)
        return self.spent

    def __exit__(self, exc_type=None, exc=None, tb=None) -> None:
        if self._start is not None:
            _spend(self._name, self._start, self.spent, self._count, self._adds)


def _usage_start(who: Optional[int], with_workers: bool) -> Optional[tuple]:
    """The start of a bracket: whose usage, the thread, its reading and its
    workers' tally; None where the platform has no reading."""
    if who is None:
        return None
    crew = _workers() if with_workers and _workers is not None else None
    return who, threading.get_ident(), resource.getrusage(who), crew


def _spend(name: str, start: tuple, spent: dict, count: bool = True,
           adds: bool = False) -> None:
    """The end of the usage bracket of ``name`` that began with ``start``:
    nothing if this is another thread, else the differences onto the
    counters and into ``spent`` (added to what it holds, with ``adds``)."""
    who, thread, at, crew = start
    if threading.get_ident() != thread:
        return
    now = resource.getrusage(who)
    # spelled out, not looped: a bracket costs two readings and this
    cpu, sys_, minflt = now[0] - at[0], now[1] - at[1], now[6] - at[6]
    majflt, nvcsw, nivcsw = now[7] - at[7], now[14] - at[14], now[15] - at[15]
    if crew is not None:
        joined = _workers()
        cpu, sys_, minflt = cpu + joined[0] - crew[0], sys_ + joined[1] - crew[1], \
            minflt + joined[2] - crew[2]
        majflt, nvcsw, nivcsw = majflt + joined[3] - crew[3], nvcsw + joined[4] - crew[4], \
            nivcsw + joined[5] - crew[5]
    to = _usage_children.get(name) or _children_of(name)
    if cpu > 0:
        to[0].inc(cpu)
    if sys_ > 0:
        to[1].inc(sys_)
    if minflt:
        to[2].inc(minflt)
    if majflt:
        to[3].inc(majflt)
    if nvcsw:
        to[4].inc(nvcsw)
    if nivcsw:
        to[5].inc(nivcsw)
    if count:
        to[6].inc()
    if adds and spent:
        for key, d in zip(_USAGE_KEYS, (cpu, sys_, minflt, majflt, nvcsw, nivcsw)):
            spent[key] += d
    else:
        spent["cpu_s"], spent["sys_s"], spent["minflt"] = cpu, sys_, minflt
        spent["majflt"], spent["nvcsw"], spent["nivcsw"] = majflt, nvcsw, nivcsw


# span ids are correlation handles, not secrets: a module-level PRNG
# seeded from the OS beats uuid4 by ~25x per id (uuid4 dominated the
# original ~70 us/span cost on the bench box). getrandbits is one C call
# under the GIL, so concurrent recorders never tear it.
_id_rng = random.Random(int.from_bytes(os.urandom(16), "little"))


def new_id() -> str:
    """A fresh 16-hex trace/span id."""
    return f"{_id_rng.getrandbits(64):016x}"


_new_id = new_id


def round_trace_id(round_seed: bytes) -> str:
    """The deterministic per-round trace id every tier derives on its own
    from the public round seed — the stitching key of a distributed round."""
    import hashlib

    return hashlib.sha256(b"xaynet-trace\x00" + round_seed).hexdigest()[:16]


class TraceContext:
    """(trace_id, span_id) — what propagates, ambient or on the wire.

    An empty ``span_id`` pins the TRACE without claiming a parent span
    (e.g. the SDK's round-derived context): children adopt the trace id
    and record no ``parent``, so strict orphan validation holds.
    """

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str = ""):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"TraceContext({self.trace_id}-{self.span_id})"


def format_header(ctx: TraceContext) -> str:
    return f"{ctx.trace_id}-{ctx.span_id}"


def parse_header(value: str | None) -> Optional[TraceContext]:
    """Parse an ``X-Xaynet-Trace`` value; None on anything malformed (an
    attacker-controlled header must never raise out of the REST path)."""
    if not value:
        return None
    trace_id, _, span_id = value.strip().partition("-")
    if not (
        len(trace_id) == 16
        and len(span_id) == 16
        and all(c in "0123456789abcdef" for c in trace_id + span_id)
    ):
        return None
    return TraceContext(trace_id, span_id)


_ctx: contextvars.ContextVar[Optional[TraceContext]] = contextvars.ContextVar(
    "xaynet_trace_ctx", default=None
)


def current_ctx() -> Optional[TraceContext]:
    """The ambient trace context of this task/thread (None outside spans)."""
    return _ctx.get()


class use_ctx:
    """Re-enter a context carried across a queue (the request envelope):
    spans opened inside parent to ``ctx`` as if opened where it was taken.
    ``None`` leaves the ambient context as it is."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx
        self._token = None

    def __enter__(self) -> None:
        if self._ctx is not None:
            self._token = _ctx.set(self._ctx)

    def __exit__(self, *exc) -> None:
        if self._token is not None:
            _ctx.reset(self._token)


class Span:
    """One finished (or in-flight) span. Walls are monotonic."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "start", "duration",
        "attrs", "error", "thread",
    )

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], start: float, attrs: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start  # time.monotonic()
        self.duration: float = 0.0
        self.attrs = attrs
        self.error: Optional[str] = None
        self.thread = threading.current_thread().name

    @property
    def subsystem(self) -> str:
        return self.name.split(".", 1)[0]

    def to_json(self, anchor: float = 0.0) -> dict:
        out = {
            "name": self.name,
            "trace": self.trace_id,
            "span": self.span_id,
            "ts": round(self.start - anchor, 6),
            "dur": round(self.duration, 6),
            "thread": self.thread,
        }
        if self.parent_id:
            out["parent"] = self.parent_id
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.error:
            out["error"] = self.error
        return out


class _SpanHandle:
    """Context manager for one span: enter/exit is the ONLY way a span
    opens and closes, so every enter has a matching exit on every
    exception path by construction (the analysis ``span`` pass rejects
    non-``with`` uses)."""

    __slots__ = ("_tracer", "_span", "_token", "_mirror", "_usage")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._token = None
        self._mirror = None
        self._usage = None

    @property
    def ctx(self) -> TraceContext:
        return TraceContext(self._span.trace_id, self._span.span_id)

    def set(self, **attrs) -> None:
        """Attach attributes mid-span (e.g. the outcome)."""
        self._span.attrs.update(attrs)

    def __enter__(self) -> "_SpanHandle":
        self._token = _ctx.set(self.ctx)
        factory = self._tracer._mirror
        if factory is not None and self._span.name in _MIRRORED:
            try:
                self._mirror = factory(self._span.name, **self._span.attrs)
                self._mirror.__enter__()
            except Exception:  # a telemetry consumer must never fail the work
                self._mirror = None
                logger.exception("trace mirror failed to open %s", self._span.name)
        how = _USAGE_ON_SPAN.get(self._span.name)
        if how is not None:
            # last in, first out: the reading brackets the work alone
            self._usage = _usage_start(how[0], how[1])
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._usage is not None:
            # set at the end: in the tracer's export, not in the mirror,
            # which took the attributes at the start
            _spend(self._span.name, self._usage, self._span.attrs)
        if self._mirror is not None:
            try:
                self._mirror.__exit__(exc_type, exc, tb)
            except Exception:
                logger.exception("trace mirror failed to close %s", self._span.name)
        _ctx.reset(self._token)
        self._span.duration = time.monotonic() - self._span.start
        if exc is not None:
            self._span.error = f"{type(exc).__name__}: {exc}"
        self._tracer._finish(self._span)


class _NullSpan:
    """The ``off``-mode span: no allocation beyond the singleton, no ctx."""

    __slots__ = ()
    ctx = None

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()

_MODES = ("on", "failure", "off")


class Tracer:
    """Process-wide span recorder: bounded ring + per-round export buffer.

    Thread-safe: producers on the event loop, fold workers, and the SDK's
    private loops all record through one lock-guarded append.
    """

    def __init__(
        self,
        mode: str | None = None,
        ring_size: int = 4096,
        round_cap: int = 8192,
        trace_dir: str | None = None,
    ):
        mode = mode or os.environ.get("XAYNET_TRACE", "on")
        if mode not in _MODES:
            logger.warning("unknown XAYNET_TRACE=%r; tracing on", mode)
            mode = "on"
        self.mode = mode
        self.trace_dir = (
            trace_dir if trace_dir is not None else os.environ.get("XAYNET_TRACE_DIR", "")
        )
        self._lock = threading.Lock()
        self._ring: deque[Span] = deque(maxlen=ring_size)  # guarded-by: _lock
        self._round_cap = round_cap
        self._round_spans: list[Span] = []  # guarded-by: _lock
        self._round_id: Optional[int] = None  # guarded-by: _lock
        self._round_trace: Optional[str] = None  # guarded-by: _lock
        self._round_root: Optional[str] = None  # guarded-by: _lock
        self._round_start: float = 0.0  # guarded-by: _lock
        # follow_round's windows: whether the open one is a participant's,
        # and how many it has opened (their round ids)
        self._round_followed = False  # guarded-by: _lock
        self._followed = 0  # guarded-by: _lock
        # monotonic anchor for export timestamps (one per process)
        self.anchor = time.monotonic()
        # round-boundary listeners (the flight recorder snapshots registry
        # counters here); fail-soft by contract
        self._round_hooks: list = []
        # round-flush listeners: called with (round_id, spans) when a round
        # window closes (the timeline fold consumes the span buffer here);
        # fail-soft by contract
        self._flush_hooks: list = []
        # optional second sink for mirror=True spans (set_mirror)
        self._mirror = None

    # -- configuration -----------------------------------------------------

    def configure(self, mode: str | None = None, trace_dir: str | None = None,
                  ring_size: int | None = None) -> None:
        """Runtime (re)configuration — the runner applies settings here."""
        if mode is not None:
            if mode not in _MODES:
                raise ValueError(f"trace mode must be one of {_MODES}, got {mode!r}")
            self.mode = mode
        if trace_dir is not None:
            self.trace_dir = trace_dir
        if ring_size is not None:
            with self._lock:
                self._ring = deque(self._ring, maxlen=ring_size)

    def set_mirror(self, factory) -> None:
        """Install (or with ``None`` remove) the mirror sink: spans declared
        ``mirror=True`` and opened with ``with`` also enter and exit
        ``factory(name, **attrs)``, a context manager on another clock. The
        runner installs ``jax.profiler.TraceAnnotation`` so that the
        program's stages lie on the device trace's timeline; this module
        stays stdlib-only. Retroactive ``record_span``s are not mirrored:
        the profiler has no call for an interval that is already over."""
        self._mirror = factory

    @property
    def mirrored(self) -> bool:
        """Whether a mirror sink is installed."""
        return self._mirror is not None

    def add_round_hook(self, hook) -> None:
        if hook not in self._round_hooks:
            self._round_hooks.append(hook)

    def add_flush_hook(self, hook) -> None:
        """Register ``hook(round_id, spans)``, called every time a round
        window flushes (``end_round``) with the round's span buffer —
        parents already resolved, ready for in-process analysis."""
        if hook not in self._flush_hooks:
            self._flush_hooks.append(hook)

    # -- recording ---------------------------------------------------------

    def span(self, name: str, ctx: Optional[TraceContext] = None,
             link: Optional[TraceContext] = None, **attrs):
        """Open one span as a context manager.

        Parentage: explicit ``ctx`` wins (worker threads, whose ambient
        context is empty), else the ambient context, else the current
        round's root; a span with no context at all starts a fresh trace.
        ``link`` is a REMOTE context (header/envelope hop): its trace id is
        adopted but the remote span rides in the ``link`` attribute instead
        of ``parent`` — within one process's export every parent resolves.
        """
        if self.mode == "off":
            return _NULL_SPAN
        if name not in _SPAN_NAMES:
            raise SpanNameError(
                f"span name {name!r} was never declared (declare_span)"
            )
        if link is not None:
            attrs["link"] = link.span_id
            span = Span(name, link.trace_id, _new_id(), None, time.monotonic(), attrs)
            return _SpanHandle(self, span)
        parent = ctx if ctx is not None else _ctx.get()
        if parent is None:
            parent = self.round_ctx()
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id or None
        else:
            trace_id, parent_id = _new_id(), None
        span = Span(name, trace_id, _new_id(), parent_id, time.monotonic(), attrs)
        return _SpanHandle(self, span)

    def record_span(self, name: str, start: float, duration: float,
                    ctx: Optional[TraceContext] = None, **attrs) -> None:
        """Record a retroactive span (a wait measured across tasks — e.g.
        the intake queue wait — where enter/exit bracketing is impossible).
        ``start`` is a ``time.monotonic()`` reading."""
        if self.mode == "off":
            return
        if name not in _SPAN_NAMES:
            raise SpanNameError(f"span name {name!r} was never declared (declare_span)")
        parent = ctx if ctx is not None else _ctx.get()
        if parent is None:
            parent = self.round_ctx()
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id or None
        else:
            trace_id, parent_id = _new_id(), None
        span = Span(name, trace_id, _new_id(), parent_id, start, attrs)
        span.duration = max(0.0, duration)
        self._finish(span)

    def _finish(self, span: Span) -> None:
        SPANS_TOTAL.labels(subsystem=span.subsystem).inc()
        with self._lock:
            self._ring.append(span)
            # the round buffer only accumulates while a round window is
            # open: a process that never calls begin_round (SDK client
            # side) keeps just the bounded ring instead of permanently
            # retaining cap spans and counting phantom drops
            if self._round_id is None:
                return
            if len(self._round_spans) < self._round_cap:
                self._round_spans.append(span)
            else:
                SPANS_DROPPED.inc()

    # -- round windows -----------------------------------------------------

    def begin_round(self, round_id: int, trace_id: str) -> None:
        """Open a round window (flushing the previous round's export) and
        pin the round's trace id + root span. Idempotent for the SAME
        (round, trace): in-process multi-tier tests run the coordinator
        and the edge tier on one tracer, and the edge's round sync must
        not reset the window the coordinator already opened."""
        with self._lock:
            if self._round_id == round_id and self._round_trace == trace_id:
                return
        self.end_round()
        if self.mode == "off":
            return
        with self._lock:
            self._round_id = round_id
            self._round_trace = trace_id
            self._round_root = _new_id()
            self._round_start = time.monotonic()
            self._round_spans = []
        for hook in self._round_hooks:
            try:
                hook(round_id)
            except Exception:  # a telemetry consumer must never fail a round
                logger.exception("trace round hook failed")

    def follow_round(self, trace_id: str) -> None:
        """A participant's :meth:`begin_round`, where an export is configured
        (else its spans stay in the ring, as before). It knows a round by
        its seed alone, so its window is keyed by the trace id: nothing
        happens while a window of that trace is open (its own, or an
        in-process coordinator's, which owns its windows); else the window
        before is flushed and one opened whose round id is this process's
        count of such windows (a round has more than one where a participant
        left, ``end_followed``, and another went on)."""
        if not (self.trace_dir and self.mode == "on"):
            return
        with self._lock:
            if self._round_trace == trace_id:
                return
            self._followed += 1
            round_id = self._followed
        self.begin_round(round_id, trace_id)
        with self._lock:
            self._round_followed = self._round_trace == trace_id

    def end_followed(self) -> None:
        """Flush a window :meth:`follow_round` opened (the participant is
        leaving); a coordinator's window is not touched."""
        with self._lock:
            followed = self._round_followed
        if followed:
            self.end_round()

    def round_ctx(self) -> Optional[TraceContext]:
        """The current round's root context (worker threads parent here)."""
        with self._lock:
            if self._round_trace is None:
                return None
            return TraceContext(self._round_trace, self._round_root)

    def end_round(self) -> list[Span]:
        """Close the round window: record the root ``round`` span, export
        the Chrome trace when configured, and return the round's spans."""
        with self._lock:
            if self._round_id is None:
                return []
            root = Span(
                SPAN_ROUND,
                self._round_trace,
                self._round_root,
                None,
                self._round_start,
                {"round_id": self._round_id},
            )
            root.duration = time.monotonic() - self._round_start
            self._ring.append(root)
            # the root always lands (it anchors the export), even when the
            # round buffer hit its cap
            self._round_spans.append(root)
            spans, self._round_spans = self._round_spans, []
            round_id = self._round_id
            self._round_id = None
            self._round_trace = None
            self._round_root = None
            self._round_followed = False
        SPANS_TOTAL.labels(subsystem=root.subsystem).inc()
        # export contract: every `parent` resolves WITHIN the bundle. A span
        # that started under the previous window (its parent was exported
        # there) demotes the dangling parent to a `link` attribute — same
        # representation as a cross-process hop
        ids = {s.span_id for s in spans}
        for s in spans:
            if s.parent_id and s.parent_id not in ids:
                s.attrs.setdefault("link", s.parent_id)
                s.parent_id = None
        for hook in self._flush_hooks:
            try:
                hook(round_id, spans)
            except Exception:  # a telemetry consumer must never fail a round
                logger.exception("trace flush hook failed")
        if self.trace_dir and self.mode == "on":
            self._export(round_id, spans)
        return spans

    def ring_spans(self) -> list[Span]:
        """Snapshot of the flight-recorder ring, oldest first."""
        with self._lock:
            return list(self._ring)

    def round_spans_snapshot(self) -> tuple[Optional[int], list[Span]]:
        """The open round window's id and a copy of its buffered spans —
        for in-process consumers that need the buffer BEFORE the window
        flushes (the round report's timeline section fires one phase
        earlier than ``end_round``). ``(None, [])`` outside a window."""
        with self._lock:
            if self._round_id is None:
                return None, []
            return self._round_id, list(self._round_spans)

    # -- export ------------------------------------------------------------

    def _export(self, round_id: int, spans: list[Span]) -> None:
        try:
            os.makedirs(self.trace_dir, exist_ok=True)
            # pid discriminator: a coordinator and its edge processes may
            # share one trace_dir (env-inherited in soaks) and both export
            # the SAME round id — without it, last writer wins
            path = os.path.join(
                self.trace_dir, f"round_{round_id}.{os.getpid()}.trace.json"
            )
            with open(path, "w") as f:
                json.dump(to_chrome_trace(spans, anchor=self.anchor), f)
            TRACE_EXPORTS.labels(outcome="written").inc()
            logger.info("[trace] round %d trace written: %s", round_id, path)
        except OSError as err:
            TRACE_EXPORTS.labels(outcome="failed").inc()
            logger.warning("round trace export failed: %s", err)


def to_chrome_trace(spans: Iterable[Span], anchor: float = 0.0) -> dict:
    """Spans -> ``chrome://tracing`` / Perfetto JSON object format.

    One complete (``ph: "X"``) event per span; ``pid`` is the subsystem,
    ``tid`` the recording thread, and the span/trace/parent identities ride
    in ``args`` so the text report and the CI validator can rebuild the
    tree from the export alone.
    """
    from .redact import scrub_attrs

    pids: dict[str, int] = {}
    tids: dict[tuple[int, str], int] = {}
    events: list[dict] = []
    for span in spans:
        pid = pids.setdefault(span.subsystem, len(pids) + 1)
        tid = tids.setdefault((pid, span.thread), len(tids) + 1)
        args = {"trace": span.trace_id, "span": span.span_id}
        if span.parent_id:
            args["parent"] = span.parent_id
        # deny-list scrub before the export hits disk (DESIGN §18): span
        # attrs whose key names secret material leave only a redacted
        # length/digest projection in the Chrome trace
        args.update(scrub_attrs(span.attrs, "trace"))
        if span.error:
            args["error"] = span.error
        events.append(
            {
                "name": span.name,
                "cat": span.subsystem,
                "ph": "X",
                "ts": round((span.start - anchor) * 1e6, 1),
                "dur": round(span.duration * 1e6, 1),
                "pid": pid,
                "tid": tid,
                "args": args,
            }
        )
    for subsystem, pid in pids.items():
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": subsystem},
            }
        )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer every subsystem records into by default."""
    return _tracer


@contextmanager
def timed_span(name: str, seconds, ctx: Optional[TraceContext] = None,
               link: Optional[TraceContext] = None, **attrs):
    """One stage written down twice by one call: as a span of the tracer and
    as one observation on ``seconds`` (a histogram child of the registry).
    The spans say where one message's or one phase's seconds went; the
    histogram says it for a window of ``/metrics``. With the tracer ``off``
    no ``Span`` is made and the histogram is still observed, and so are the
    usage counters of a name declared ``thread`` or ``process``. The stage
    tables built on it: ``server/stages.py`` (a message's chain),
    ``telemetry/unmask.py`` (the Unmask phase) and ``telemetry/journal.py``
    (a journal write)."""
    t0 = time.monotonic()
    try:
        with get_tracer().span(name, ctx=ctx, link=link, **attrs) as span:
            if span is _NULL_SPAN and name in _USAGE_ON_SPAN:
                with usage_of(name):
                    yield span
            else:
                yield span
    finally:
        seconds.observe(time.monotonic() - t0)
