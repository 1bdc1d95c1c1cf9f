"""What a high fan-in asks of the REST intake (docs/DESIGN.md §16 "The
intake under a fan-in").

With more connections than ``rest-body`` readers a round's large bodies are
received on two carriers at once (a ``rest-body`` thread each while one is
free, the one event-driven ``rest-overflow`` thread for all the others),
every connection holds a body while its message waits for a worker, and the
API's loop, which runs the serial part of every message, becomes the bound
on intake. Five things are counted, each where it happens:

- ``xaynet_rest_body_reads_total{route, reason}``: one a request body read
  in full, by the carrier that read it and why it was that one. Of the three
  carriers ``route="direct"`` (a ``rest-body`` thread) has the one reason
  ``large`` and ``route="overflow"`` (the ``rest-overflow`` thread) the one
  reason ``no_reader``: a large body on a plain connection that found every
  reader busy. ``route="stream"`` (the loop's StreamReader) says ``small``
  (under ``rest.DIRECT_BODY_MIN``), ``tls`` or ``no_socket`` (the transport
  gave no descriptor to read from); ``stream``/``no_reader`` is declared and
  stays 0: no large plain body that had a socket is gathered on the loop.
- ``xaynet_rest_body_buffers_total{pages}``: one a body read in full on
  ``direct`` or ``overflow`` (the StreamReader's bodies have no buffer of
  their own), by what it was received into: ``kept`` = a buffer kept from an
  earlier body that nothing referred to any more, its pages mapped already;
  ``fresh`` = a new allocation, whose pages ``recv`` touched first (none that
  was free fitted: a first round, more bodies alive at once than ever
  before, another length). ``rest.py::_BodyBuffers`` chooses; ``kept`` over
  the two is the benchmark's ``rest.body_kept_share``.
- ``xaynet_rest_overflow_bodies``: the bodies the ``rest-overflow`` thread
  holds at this instant (also ``/healthz`` ``overflow_bodies``).
- ``xaynet_rest_bodies_resident`` and ``..._resident_max``: POSTed message
  bodies held sealed (being read, or read and waiting for a ``pet-msg``
  worker to open them), now and at the most since the Update phase's first
  message: sealed bytes in memory are this count times the body size.
- ``xaynet_event_loop_cpu_seconds_total`` beside
  ``xaynet_event_loop_wall_seconds_total`` (``server/rest.py``'s lag
  watcher): the loop thread's own CPU time (``time.thread_time()`` read on
  that thread) over the wall time it was watched. CPU seconds a message is
  what the loop can carry whatever the workers do.
"""

from __future__ import annotations

import threading

from .registry import MetricsRegistry

# every (route, reason) a large body is counted under: the two direct carriers,
# then why one went through the StreamReader, in the order a round's log names them
LARGE = (("direct", "large"), ("overflow", "no_reader"),
         ("stream", "no_reader"), ("stream", "tls"), ("stream", "no_socket"))


class Held:
    """One message body counted resident until :meth:`release` (any thread,
    any number of times)."""

    __slots__ = ("_bodies", "_held")

    def __init__(self, bodies: "BodyIntake"):
        self._bodies, self._held = bodies, True

    def release(self) -> None:
        if self._held:
            self._held = False
            self._bodies._leave()


class BodyIntake:
    """The REST server's body counters: made by ``RestServer`` against the
    registry it renders."""

    def __init__(self, registry: MetricsRegistry):
        self._reads = registry.counter(
            "xaynet_rest_body_reads_total",
            "Request bodies read in full, by route and why: direct/large = a "
            "rest-body thread; overflow/no_reader = the rest-overflow thread "
            "(a large plain-TCP body that found all readers busy); "
            "stream/small, stream/tls, stream/no_socket = the event loop's "
            "StreamReader; stream/no_reader stays 0 (telemetry/intake.py).",
            ("route", "reason"),
        )
        self._buffers = registry.counter(
            "xaynet_rest_body_buffers_total",
            "Large bodies read in full by a rest-body thread or the "
            "rest-overflow thread, by the pages they were received into: "
            "kept = a buffer kept from an earlier body that nothing referred "
            "to any more, fresh = a new allocation (telemetry/intake.py).",
            ("pages",),
        )
        # declared (each reads 0, not absent), and a round's log counts from
        # here: the registry may have served another server before this one
        self._logged = self._large_reads()
        self._buffers.labels(pages="fresh")
        self._kept_logged = self._buffers.labels(pages="kept").value
        self.overflow_bodies = registry.gauge(
            "xaynet_rest_overflow_bodies",
            "Large request bodies the rest-overflow thread is receiving at "
            "this instant (one registration each, no thread).",
        )
        self._resident = registry.gauge(
            "xaynet_rest_bodies_resident",
            "POSTed message bodies held sealed: being read, or read and not "
            "yet opened by a pet-msg worker.",
        )
        self._resident_max = registry.gauge(
            "xaynet_rest_bodies_resident_max",
            "The most message bodies held sealed at one instant since the "
            "first message of the latest Update phase arrived.",
        )
        self._lock = threading.Lock()
        self._n = 0
        self._high = 0

    def read(self, route: str, reason: str, pages: str | None = None) -> None:
        """One body read in full; ``pages`` where it was received into a
        buffer of its own (``direct``, ``overflow``): ``kept`` | ``fresh``."""
        self._reads.labels(route=route, reason=reason).inc()
        if pages is not None:
            self._buffers.labels(pages=pages).inc()

    def hold(self) -> Held:
        with self._lock:
            self._n += 1
            self._resident.set(self._n)
            if self._n > self._high:
                self._high = self._n
                self._resident_max.set(self._high)
        return Held(self)

    def _leave(self) -> None:
        with self._lock:
            self._n -= 1
            self._resident.set(self._n)

    def new_window(self) -> None:
        """An Update phase's first message is here: the high-water mark
        starts again from what is held now."""
        with self._lock:
            self._high = self._n
            self._resident_max.set(self._high)

    def _large_reads(self) -> dict[tuple[str, str], float]:
        return {key: self._reads.labels(route=key[0], reason=key[1]).value for key in LARGE}

    def since_last(self) -> tuple[int, int, dict[str, int], int, int]:
        """(large bodies read by ``rest-body`` threads, by the
        ``rest-overflow`` thread, {reason: large bodies through the
        StreamReader, where any}, the high-water mark, how many of the first
        two were received into kept pages) since the previous call: what a
        round's log line says."""
        now = self._large_reads()
        grown = {key: int(v - self._logged[key]) for key, v in now.items()}
        self._logged = now
        direct, overflow = grown.pop(LARGE[0]), grown.pop(LARGE[1])
        kept, self._kept_logged = self._kept_logged, self._buffers.labels(pages="kept").value
        turned = {reason: n for (_, reason), n in grown.items() if n}
        return direct, overflow, turned, self._high, int(self._kept_logged - kept)
