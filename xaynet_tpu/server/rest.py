"""Coordinator REST API.

Functional port of the reference's HTTP surface (reference:
rust/xaynet-server/src/rest.rs:40-315):

- ``POST /message`` — opaque sealed-box message bytes
- ``GET /params``   — current round parameters
- ``GET /sums``     — sum dictionary (204 while absent)
- ``GET /seeds?pk=<hex>`` — a sum participant's seed slice (204 while absent)
- ``GET /model``    — latest global model bytes (204 while absent)
- ``GET /metrics``  — telemetry registry, Prometheus text exposition
- ``GET /healthz``  — liveness JSON (status, phase, round id, uptime)
- ``GET /statusz``  — live operator console, self-contained HTML (§20)
- ``GET /alerts``   — SLO engine state: active alerts + transition ring

Responses are JSON (parameters, dictionaries) or raw bytes (model) — a
readable stand-in for the reference's bincode bodies; both ends of the wire
are this framework. Implemented directly on asyncio streams (no third-party
HTTP dependency); optional TLS via ``ssl.SSLContext``.
"""

from __future__ import annotations

import asyncio
import ctypes
import heapq
import json
import logging
import math
import os
import select
import selectors
import socket
import ssl
import sys
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional
from urllib.parse import parse_qs, urlparse

import numpy as np

from ..telemetry import tracing as trace
from ..telemetry import wire as wire_stats
from ..telemetry.intake import BodyIntake
from ..telemetry.registry import MetricsRegistry, get_registry
from ..telemetry.startup import get_timeline
from ..utils import native, tracing
from . import stages
from .events import PhaseName
from .requests import RequestError
from .services import Fetcher, PetMessageHandler, ServiceError

logger = logging.getLogger("xaynet.rest")


@dataclass
class TenantRoutes:
    """One tenant's REST surface: what ``/t/<tenant>/...`` dispatches to.

    The default tenant's routes double as the bare legacy paths
    (``/params`` == ``/t/<default>/params``), so single-tenant deployments
    and old SDKs keep working unchanged (docs/DESIGN.md §19).
    """

    fetcher: Fetcher
    handler: PetMessageHandler
    pipeline: object = None  # ingest.IngestPipeline
    edge_api: object = None  # edge.api.EdgeCoordinatorApi
    health_extra: object = None  # zero-arg callable merged into /healthz

MAX_BODY = 1 << 32  # u32 length field ceiling, as in the reference
# A body of at least this many bytes, on a plain-TCP connection, is received
# by a `rest-body` thread straight into one buffer of its Content-Length
# (docs/DESIGN.md §16); below it the thread hop costs more than the
# StreamReader's copies (PERF.md §6, PR 27: where the two cross).
DIRECT_BODY_MIN = 1 << 20
# Readers that may block on a socket at once. A large body that finds them
# all busy is received by the one event-driven `rest-overflow` thread, beside
# any number of others: a connection never waits for a reader while its peer
# is sending, and a slow peer there costs a registration and no thread.
BODY_READERS = 16
# The most bytes the `rest-overflow` thread takes from one body before it
# turns to the next readable one, so that bodies registered together advance
# together. 48 bodies of 39.6 MB at once on the idle chip host: 0.56 GB/s at
# 64 KiB, 0.80 at 256 KiB, 0.93-0.98 at 1 MiB, 1.03 at 4 MiB, 1.01 at 16 MiB
# (one thread's first touch of fresh pages is the bound from here on), where
# a longer turn only keeps the others waiting (PERF.md §6, PR 43).
OVERFLOW_TURN_BYTES = 1 << 20
# The most bytes of received bodies' buffers a server keeps for the bodies to
# come (`_BodyBuffers`). It keeps only what its intake held at once (8 bodies
# of 179-256 MB in the benchmark's `flood8` cells and one Sum2 body, 1.6-2.3
# GB; 64 of 39.6 MB in the fan-in cell, 2.5 GB), so the cap binds only where
# lengths change from round to round by more than a buffer's slack.
BODY_BUFFERS_MAX_BYTES = 4 << 30
# A fresh body buffer is allocated this much over its length (untouched pages
# cost nothing), so that a later body a little longer fits it too: an Update
# body grows by 112 bytes a sum participant in the round's seed dictionary.
BODY_BUFFER_SLACK = 1 << 16

SPAN_REQUEST = trace.declare_span("rest.request")

# polled endpoints are untraced: monitoring (/metrics, /healthz) and the
# round-state reads the SDK polls every tick (/params at tens of Hz in a
# soak, /sums and /seeds while waiting for dictionaries). Their spans
# would crowd the bounded round buffer and — because the buffer drops the
# NEWEST spans at its cap — could evict the end-of-round phase spans the
# CI validator requires. The causal story lives in the traced writes:
# POST /message and the /edge/* hops.
_UNTRACED_PATHS = {
    "/metrics", "/health", "/healthz", "/params", "/sums", "/seeds", "/model",
    "/statusz", "/alerts",
}

# known routes/methods keep the http counter's labels closed-cardinality —
# both tokens are attacker-controlled, and every distinct label value is a
# permanent registry child
_KNOWN_PATHS = {"/message", "/params", "/sums", "/seeds", "/model",
                "/health", "/healthz", "/metrics", "/statusz", "/alerts",
                "/edge/round", "/edge/envelope", "/admin/tenants"}
_KNOWN_METHODS = {"GET", "POST", "HEAD", "PUT", "DELETE", "OPTIONS", "PATCH"}


def _recv_exactly(sock: socket.socket, body: bytearray, start: int, deadline: float,
                  spent: dict | None = None) -> int:
    """Fill ``body[start:]`` from ``sock`` (non-blocking: it shares the
    transport's open file) and return how far ``body`` is filled: short
    when the peer closed or reset, when ``deadline`` (``time.monotonic()``)
    passed, or when the socket was shut down under it to abort the read.
    Runs on a ``rest-body`` thread, which owns ``sock`` and closes it. The
    native library loops ``recv`` and ``poll`` with the interpreter lock
    released once for the whole body; without it the same loop runs here and
    takes the lock back after each of a body's several hundred calls.

    This thread does the work of the message's ``read_body`` stage, which the
    loop opened around its wait for it, so what the stage spent is read here
    and left in ``spent``: CPU is ``recv``'s copy and the first touch of the
    buffer's pages, wall less CPU the wait in ``poll`` for the sender's bytes."""
    try:
        with stages.usage("read_body", spent=spent):
            lib = native.load()
            if lib is not None:
                first = ctypes.c_uint8.from_buffer(body)  # pins the buffer for the call
                return lib.xn_recv_exactly(
                    sock.fileno(), ctypes.byref(first), start, len(body),
                    deadline - time.monotonic(),
                )
            view = memoryview(body)
            poller = select.poll()
            poller.register(sock, select.POLLIN)
            got = start
            while got < len(body):
                try:
                    n = sock.recv_into(view[got:])
                except (BlockingIOError, InterruptedError):
                    left = deadline - time.monotonic()
                    if left <= 0 or not poller.poll(left * 1000.0):
                        break
                    continue
                except OSError:
                    break  # reset by the peer
                if n == 0:
                    break
                got += n
            return got
    finally:
        sock.close()


def _abort_read(sock: socket.socket) -> None:
    """Wake the thread that reads ``sock``: its ``recv_into`` returns 0. The
    connection is shut down with it, so only for a connection being dropped."""
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass  # the reader finished and closed it first


# ``PyByteArray_Resize``: within the allocation and over half of it, a new
# length and no byte moved or written (``del body[n:]`` only shrinks)
_resize_bytearray = ctypes.PYFUNCTYPE(ctypes.c_int, ctypes.py_object, ctypes.c_ssize_t)(
    ("PyByteArray_Resize", ctypes.pythonapi)
)


class _BodyBuffers:
    """The buffers large bodies are received into, on pages kept from
    earlier bodies (docs/DESIGN.md §16, "How a body is read").

    A body of ``DIRECT_BODY_MIN`` bytes or more gets one ``bytearray`` of its
    length. A fresh one is an ``mmap`` of untouched pages which ``free`` gives
    back, and ``recv`` into it spends several times the copy on page faults
    (PERF.md §6, PR 53), so the buffers handed out are kept, up to ``cap``
    bytes of them, and handed out again. Nobody gives one back: whatever
    reads a body's bytes (the request's frames, the ``memoryview`` the open
    returns, a ``np.frombuffer`` view of a vector that waits for its slot
    copy, the ``ctypes`` pin of a ``recv`` in flight) holds a reference to the
    ``bytearray``, so a kept one that nothing but this object refers to is
    free, and is found so by its reference count. One still referred to is
    left alone, and where none that is free fits, fresh pages are handed out
    as before. A kept buffer serves another length where ``bytearray`` need
    not move it to resize: within its allocation and at least half of it.
    Its old contents (the last message's opened bytes, as the heap held them
    after ``free``) are the next body's to overwrite; nothing reads them."""

    def __init__(self, cap: int = BODY_BUFFERS_MAX_BYTES):
        self._cap = cap
        self._lock = threading.Lock()
        # least recently taken first  # guarded-by: _lock
        self._kept: list[bytearray] = [bytearray()]
        # what the scan below reads of a buffer nothing else refers to
        self._unshared = self._shared_by()[0]
        self._kept.clear()

    def _shared_by(self) -> list[int]:
        return [sys.getrefcount(buf) for buf in self._kept]

    def take(self, length: int) -> tuple[bytearray, bool]:
        """A ``bytearray`` of ``length`` bytes, contents undefined, the
        caller's until its last reference to it and view of it is gone, and
        whether its pages are kept ones (mapped already) or fresh."""
        with self._lock:
            free = [n == self._unshared for n in self._shared_by()]
            rooms = [buf.__alloc__() for buf in self._kept]
            # of the free ones it fits unmoved, the smallest, least recently taken
            fits = [
                (room, at) for at, room in enumerate(rooms)
                if free[at] and room // 2 <= length < room
            ]
            if fits:
                body = self._kept.pop(min(fits)[1])
                self._kept.append(body)
                if len(body) != length:
                    _resize_bytearray(body, length)  # no export, and in place: see above
                return body, True
            body = native.uninitialised_bytearray(None, length + BODY_BUFFER_SLACK)
            _resize_bytearray(body, length)
            room = body.__alloc__()
            if room <= self._cap:
                self._kept.append(body)
                held = sum(rooms) + room
                while held > self._cap:
                    held -= self._kept.pop(0).__alloc__()
            return body, False


class _OverflowBody:
    """One body the ``rest-overflow`` thread is receiving: ``view`` is filled
    as far as ``got``; ``sock`` is ``None`` once the thread has let it go;
    ``spent`` is what the thread's turns at this body have spent so far."""

    __slots__ = ("sock", "view", "got", "deadline", "loop", "done", "spent")

    def __init__(self, sock, body: bytearray, start: int, deadline: float, loop, done, spent):
        self.sock, self.view, self.got = sock, memoryview(body), start
        self.deadline, self.loop, self.done = deadline, loop, done
        self.spent = {} if spent is None else spent


class _OverflowReader:
    """The ``rest-overflow`` thread: any number of bodies at once, each
    received from its own socket (non-blocking: it shares its transport's
    open file) straight into its one buffer. The thread owns a selector and
    every socket handed to it, and closes each where that body ends: whole,
    the peer closed or reset, the socket shut down under it (``_abort_read``)
    or its deadline passed. Each ends by resolving its future, on its loop,
    with how far the buffer is filled, as ``_recv_exactly`` returns it.
    ``recv_into`` releases the interpreter lock for the copy and for the
    first touch of the buffer's pages, so both land here and not on the loop.
    ``holds`` is the gauge of the bodies the thread holds."""

    def __init__(self, holds):
        self._holds = holds
        self._selector = selectors.DefaultSelector()
        # registrations and close() reach a thread asleep in select() here
        self._wake_recv, self._wake_send = socket.socketpair()
        self._wake_recv.setblocking(False)
        self._wake_send.setblocking(False)
        self._selector.register(self._wake_recv, selectors.EVENT_READ)
        self._arrivals: deque[_OverflowBody] = deque()
        self._closing = False
        threading.Thread(target=self._run, name="rest-overflow", daemon=True).start()

    def receive(
        self, sock: socket.socket, body: bytearray, start: int, deadline: float,
        spent: dict | None = None,
    ) -> "asyncio.Future[int]":
        """Hand ``sock`` to the thread, to fill ``body[start:]`` by
        ``deadline`` (``time.monotonic()``). Called on the request's loop.
        ``spent`` is filled as ``_recv_exactly`` fills it."""
        loop = asyncio.get_running_loop()
        done = loop.create_future()
        self._holds.inc()
        self._arrivals.append(_OverflowBody(sock, body, start, deadline, loop, done, spent))
        self._wake()
        return done

    def _resolve(self, done: asyncio.Future, got: int) -> None:
        """On the request's loop: the thread has let the body go."""
        self._holds.dec()
        if not done.done():  # a cancelled request waits no longer
            done.set_result(got)

    def close(self) -> None:
        """The thread ends once it holds no body (``stop()`` has shut their
        sockets down: each returns short at once)."""
        self._closing = True
        self._wake()

    def _wake(self) -> None:
        try:
            self._wake_send.send(b"\0")
        except BlockingIOError:
            pass  # as many wake-ups unread as the socket holds: it will wake

    def _run(self) -> None:
        deadlines, order = [], 0  # a heap: (deadline, order of arrival, body)
        while True:
            timeout = None
            if deadlines:  # the nearest one; a body that ended sooner wakes it for nothing
                timeout = max(0.0, deadlines[0][0] - time.monotonic())
            for key, _ in self._selector.select(timeout):
                if key.data is not None:
                    self._advance(key.data)
                else:
                    try:
                        self._wake_recv.recv(4096)
                    except BlockingIOError:
                        pass
            while self._arrivals:
                body = self._arrivals.popleft()
                order += 1
                heapq.heappush(deadlines, (body.deadline, order, body))
                try:
                    self._selector.register(body.sock, selectors.EVENT_READ, body)
                except OSError:
                    self._finish(body, registered=False)
            now = time.monotonic()
            while deadlines and (deadlines[0][2].sock is None or deadlines[0][0] <= now):
                body = heapq.heappop(deadlines)[2]
                if body.sock is not None:
                    self._finish(body)  # out of time: short
            if self._closing and not deadlines and not self._arrivals:
                break
        self._selector.close()
        self._wake_recv.close()
        self._wake_send.close()

    def _advance(self, body: _OverflowBody) -> None:
        """``body``'s socket is readable: one ``recv_into`` of what is there,
        never past the body's end nor over a turn's bytes. A short count says
        the socket is drained; the selector says when it no longer is.

        The thread serves many bodies, so what the ``read_body`` stage spent
        is read a turn at a time, around the ``recv_into`` alone, under the
        stage's name; the message counts once, when its body is whole. CPU,
        faults and switches are a body's own; the select between its turns
        is nobody's, so wall less CPU holds the other bodies' turns too."""
        end = min(len(body.view), body.got + OVERFLOW_TURN_BYTES)
        try:
            with stages.usage("read_body", count=False, spent=body.spent):
                n = body.sock.recv_into(body.view[body.got:end])
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            n = 0  # reset by the peer
        body.got += n
        if body.got == len(body.view):
            stages.counted("read_body")
        if n == 0 or body.got == len(body.view):
            self._finish(body)

    def _finish(self, body: _OverflowBody, registered: bool = True) -> None:
        sock, body.sock = body.sock, None
        if registered:
            self._selector.unregister(sock)
        sock.close()
        body.view.release()
        try:
            body.loop.call_soon_threadsafe(self._resolve, body.done, body.got)
        except RuntimeError:
            pass  # the loop is closed: nobody waits


class RestServer:
    def __init__(
        self,
        fetcher: Fetcher,
        handler: PetMessageHandler,
        read_timeout: float = 120.0,
        registry: Optional[MetricsRegistry] = None,
        pipeline=None,
        edge_api=None,
        health_extra=None,
        tenants: Optional[dict[str, TenantRoutes]] = None,
        lifecycle=None,
        admin_token: str = "",
        default_tenant: str = "",
    ):
        # `registry` selects what GET /metrics renders. Hot-path modules
        # (request queue, message pipeline, kernel profiling, dispatcher)
        # record into the PROCESS registry at import time, so a custom
        # registry exposes only the families created against it (unit
        # tests); production keeps the default.
        # `pipeline` (ingest.IngestPipeline) switches POST /message to the
        # admission-controlled path: 429 + Retry-After under saturation, and
        # /healthz gains the intake section. None keeps the direct path.
        # `edge_api` (edge.api.EdgeCoordinatorApi) serves the edge tier:
        # GET /edge/round (round params + round keys for trusted edges) and
        # POST /edge/envelope (partial-aggregate intake).
        # `health_extra` is a zero-arg callable whose dict is merged into
        # the /healthz payload (the edge runner reports its upstream link
        # and envelope backlog through this hook).
        # `tenants` maps tenant id -> TenantRoutes for /t/<tenant>/...
        # routing; the positional args above stay the DEFAULT tenant (and
        # the bare legacy routes). None = single-tenant, as before.
        # `lifecycle` (tenancy.TenantLifecycle) turns the tenant set
        # elastic: mutating traffic consults its admission verdicts
        # (draining / quarantined tenants shed with 429) and `admin_token`
        # enables the authenticated /admin/tenants surface (constant-time
        # compare, like the edge tier; "" keeps it fully disabled).
        # `default_tenant` is the real id behind the bare legacy routes so
        # lifecycle admission applies to them too.
        self.fetcher = fetcher
        self.handler = handler
        self.pipeline = pipeline
        self.edge_api = edge_api
        self.health_extra = health_extra
        self._default_routes = TenantRoutes(
            fetcher=fetcher,
            handler=handler,
            pipeline=pipeline,
            edge_api=edge_api,
            health_extra=health_extra,
        )
        # the lifecycle manager mutates this dict at runtime (onboard
        # registers, offboard pops) — it must stay the SAME object the
        # manager holds, so adopt a provided dict instead of copying it
        self.tenants: dict[str, TenantRoutes] = (
            tenants if tenants is not None else {}
        )
        self.lifecycle = lifecycle
        self.admin_token = admin_token
        self.default_tenant = default_tenant
        self.read_timeout = read_timeout  # slow-client defense
        self.registry = registry if registry is not None else get_registry()
        self._started_at = time.monotonic()
        self._http_requests = self.registry.counter(
            "xaynet_http_requests_total",
            "REST requests by method, route, status code and tenant "
            "('' = the bare single-tenant routes).",
            ("method", "path", "status", "tenant"),
        )
        self._loop_lag = self.registry.histogram(
            "xaynet_event_loop_lag_seconds",
            "How late a 100 ms sleep on the loop that serves the API woke: "
            "the time a ready task waits for the loop (bodies being read, "
            "handlers between awaits) as opposed to for a worker or a queue.",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0),
        )
        self._body_bytes = self.registry.counter(
            "xaynet_rest_body_bytes_total",
            "Request body bytes read in full, by route: direct = received by "
            "a rest-body thread into one buffer of the Content-Length, "
            "overflow = received into such a buffer by the rest-overflow "
            "thread (every rest-body thread was busy), stream = gathered by "
            "the event loop's StreamReader (small bodies, TLS, no socket).",
            ("route",),
        )
        self._loop_cpu = self.registry.counter(
            "xaynet_event_loop_cpu_seconds_total",
            "CPU seconds of the thread that runs the API's event loop "
            "(time.thread_time(), read on that thread every 100 ms).",
        )
        self._loop_wall = self.registry.counter(
            "xaynet_event_loop_wall_seconds_total",
            "Wall seconds over which xaynet_event_loop_cpu_seconds_total was "
            "read: the two grow together, so their ratio over any span is the "
            "loop thread's CPU share.",
        )
        self._intake = BodyIntake(self.registry)
        self._sum2_first_arrival = self.registry.histogram(
            "xaynet_sum2_first_arrival_seconds",
            "Sum2 phase announced -> the headers of the first message POSTed "
            "in it parsed, once a round: the sum participant's fetch, derive, "
            "sum, encode and seal as the coordinator sees them; the rest of "
            "the phase is that message's path through the coordinator.",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0, 160.0),
        )
        # the phase event whose first message was seen, a tenant
        self._phase_seen: dict[str, object] = {}
        self._lag_task: Optional[asyncio.Task] = None
        self._server: Optional[asyncio.AbstractServer] = None
        # the bounded rest-body pool and the rest-overflow thread (each made
        # when a body first needs it), and the sockets each is reading:
        # stop() shuts those down
        self._body_pool: Optional[ThreadPoolExecutor] = None
        self._direct_reads: set[socket.socket] = set()
        self._overflow: Optional[_OverflowReader] = None
        self._overflow_reads: set[socket.socket] = set()
        # what both carriers receive into: one pool a server, its tenants'
        # too (a buffer nothing refers to carries no tenant's state)
        self._body_buffers = _BodyBuffers()
        # live connections: stop() closes them — an idle keep-alive peer
        # would otherwise hold the process for read_timeout seconds
        self._writers: set[asyncio.StreamWriter] = set()

    async def start(
        self, host: str = "127.0.0.1", port: int = 8081, tls: Optional[ssl.SSLContext] = None
    ) -> tuple[str, int]:
        self._server = await asyncio.start_server(self._handle_conn, host, port, ssl=tls)
        self._lag_task = asyncio.create_task(self._watch_loop_lag(), name="rest-loop-lag")
        addr = self._server.sockets[0].getsockname()
        logger.info("REST API listening on %s:%d", addr[0], addr[1])
        return addr[0], addr[1]

    async def stop(self) -> None:
        """Stop listening AND close every live connection. Since Python 3.12
        ``Server.wait_closed()`` waits for all connections to finish; an idle
        keep-alive handler sits in ``readline()`` for ``read_timeout``, so
        without closing them a SIGTERM'd coordinator outlives its signal by
        minutes — and still owns its accelerator."""
        if self._server is None:
            return
        if self._lag_task is not None:
            self._lag_task.cancel()
            await asyncio.gather(self._lag_task, return_exceptions=True)
            self._lag_task = None
        self._server.close()
        for sock in [*self._direct_reads, *self._overflow_reads]:
            _abort_read(sock)  # a body in mid-read: its carrier returns short
        for writer in list(self._writers):
            writer.close()
        await self._server.wait_closed()
        if self._body_pool is not None:
            self._body_pool.shutdown(wait=False)
            self._body_pool = None
        if self._overflow is not None:
            self._overflow.close()
            self._overflow = None

    async def _watch_loop_lag(self, period: float = 0.1) -> None:
        """Observe, every ``period`` seconds, how late this loop ran a task
        that was due: what tells "many bodies on one loop" from "the loop is
        idle and the workers are the queue"; and read this thread's CPU
        clock beside the wall clock: how much of the loop is in use."""
        loop = asyncio.get_running_loop()
        cpu, wall = time.thread_time(), loop.time()
        while True:
            due = loop.time() + period
            await asyncio.sleep(period)
            self._loop_lag.observe(max(0.0, loop.time() - due))
            cpu_now, wall_now = time.thread_time(), loop.time()
            self._loop_cpu.inc(cpu_now - cpu)
            self._loop_wall.inc(wall_now - wall)
            cpu, wall = cpu_now, wall_now

    # --- request handling -------------------------------------------------

    async def _handle_conn(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        self._writers.add(writer)
        try:
            while True:
                request_line = await asyncio.wait_for(reader.readline(), self.read_timeout)
                if not request_line:
                    break
                try:
                    method, target, _ = request_line.decode().split(None, 2)
                except ValueError:
                    await self._respond(writer, 400, b"bad request")
                    break
                headers = {}
                while True:
                    line = await asyncio.wait_for(reader.readline(), self.read_timeout)
                    if line in (b"\r\n", b"\n", b""):
                        break
                    name, _, value = line.decode().partition(":")
                    headers[name.strip().lower()] = value.strip()
                length = int(headers.get("content-length", "0"))
                if length > MAX_BODY:
                    await self._respond(writer, 413, b"body too large")
                    break

                async def read_body(span=None, length=length) -> bytes | bytearray:
                    return await self._read_body(reader, writer, length, span)

                keep_alive = headers.get("connection", "keep-alive").lower() != "close"
                status, payload, ctype, extra = await self._route(
                    method, target, read_body, headers
                )
                await self._respond(writer, status, payload, ctype, keep_alive, extra)
                if not keep_alive:
                    break
        except (asyncio.IncompleteReadError, ConnectionResetError, asyncio.TimeoutError):
            pass
        finally:
            self._writers.discard(writer)
            writer.close()
            try:
                await writer.wait_closed()
            except Exception:  # lint: swallow-ok (best-effort socket teardown)
                pass

    async def _read_body(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter, length: int,
        span=None,
    ) -> bytes | bytearray:
        """The request's body, whole within ``read_timeout`` or an exception
        that drops the connection unanswered. One algorithm (receive
        ``length`` bytes) on the carrier the request calls for: a large body
        on plain TCP goes straight from the socket into one buffer, on a
        ``rest-body`` thread while one is free and on the ``rest-overflow``
        thread beside the others there while none is; everything else
        through the StreamReader on the loop. Never reads past the body.
        ``span`` is the message's ``read_body`` stage, which gets what the
        thread that received the body spent on it."""
        if not length:
            return b""
        sock, reason = self._direct_socket(reader, writer, length)
        if sock is None:
            body = await asyncio.wait_for(reader.readexactly(length), self.read_timeout)
            self._body_bytes.labels(route="stream").inc(length)
            self._intake.read("stream", reason)
            return body
        deadline = time.monotonic() + self.read_timeout
        transport = writer.transport
        # nothing below suspends until a thread has the socket, so no byte
        # reaches the StreamReader between here and resume_reading()
        transport.pause_reading()
        body, kept = self._body_buffers.take(length)
        spent: dict = {}
        buffered = len(reader._buffer)
        if buffered:
            # the segment that carried the headers carried these; the buffer
            # holds them, so read() returns at once. It resumes a transport
            # the StreamReader had paused itself: pause again
            body[:buffered] = await reader.read(buffered)  # lint: wirecopy-ok (a store)
            transport.pause_reading()
        if reason == "large":
            if self._body_pool is None:
                self._body_pool = ThreadPoolExecutor(BODY_READERS, thread_name_prefix="rest-body")
            route, reads = "direct", self._direct_reads
            whole = asyncio.get_running_loop().run_in_executor(
                self._body_pool, _recv_exactly, sock, body, buffered, deadline, spent
            )
        else:
            if self._overflow is None:
                self._overflow = _OverflowReader(self._intake.overflow_bodies)
            route, reads = "overflow", self._overflow_reads
            whole = self._overflow.receive(sock, body, buffered, deadline, spent)
        reads.add(sock)
        try:
            got = await whole
        except BaseException:
            _abort_read(sock)  # cancelled: the read must not outlive the request
            raise
        finally:
            reads.discard(sock)
        if got < length:  # closed, reset, aborted or out of time: all drop the connection
            raise asyncio.IncompleteReadError(b"", length)
        transport.resume_reading()
        if span is not None:
            span.set(**spent)
        self._body_bytes.labels(route=route).inc(length)
        self._intake.read(route, reason, pages="kept" if kept else "fresh")
        return body

    def _direct_socket(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter, length: int
    ) -> tuple[Optional[socket.socket], str]:
        """A duplicate of the connection's descriptor if this body is to be
        received straight from the socket, else ``None``, and the reason
        either way: ``large`` (for a ``rest-body`` thread: one is free) or
        ``no_reader`` (for the ``rest-overflow`` thread: none is) with a
        socket, ``small``, ``tls`` or ``no_socket`` without. A duplicate, so
        that a transport closed in mid-body (``stop()``) cannot hand the
        reading thread a recycled descriptor; it shares the transport's
        non-blocking mode."""
        if length < DIRECT_BODY_MIN:
            return None, "small"
        if writer.get_extra_info("ssl_object") is not None:
            return None, "tls"
        trsock = writer.get_extra_info("socket")
        buffer = getattr(reader, "_buffer", None)
        if trsock is None or not isinstance(buffer, bytearray) or len(buffer) >= length:
            return None, "no_socket"
        try:
            sock = socket.socket(fileno=os.dup(trsock.fileno()))
        except OSError:
            return None, "no_socket"  # the transport is already closed: the stream path says how
        return sock, "large" if len(self._direct_reads) < BODY_READERS else "no_reader"

    def _resolve_tenant(self, path: str):
        """Split a ``/t/<tenant>/<sub>`` target into (tenant id, sub path,
        routes); bare paths resolve to the default tenant's routes with an
        empty tenant label. Unknown tenants resolve to ``routes=None``."""
        if path != "/t" and not path.startswith("/t/"):
            return "", path, self._default_routes
        parts = path.split("/", 3)  # ["", "t", tenant, rest]
        tid = parts[2] if len(parts) > 2 else ""
        routes = self.tenants.get(tid)
        sub = "/" + (parts[3] if len(parts) > 3 else "")
        return tid, sub, routes

    async def _route(self, method: str, target: str, body, headers=None):
        """``body`` is the request's bytes, or (from ``_handle_conn``) the
        coroutine function that reads them off the socket. Only a traced
        POST of a message defers the read, into its ``rest.request`` span,
        so that the read is that span's first stage; every other request's
        body is read here, before anything is decided, as it always was."""
        url = urlparse(target)
        headers = headers or {}
        admin = url.path == "/admin/tenants" or url.path.startswith("/admin/tenants/")
        read_body = None
        if callable(body):
            if method == "POST" and url.path.endswith("/message") and not admin:
                read_body = body
            else:
                body = await body()
        if admin:
            status, payload, ctype, extra = await self._admin_route(
                method, url.path, body, headers
            )
            self._http_requests.labels(
                method=method if method in _KNOWN_METHODS else "other",
                path="/admin/tenants",  # subpath ids stay out of the labels
                status=status,
                tenant="",
            ).inc()
            return status, payload, ctype, extra
        tenant, path, routes = self._resolve_tenant(url.path)
        if read_body is not None and (routes is None or path in _UNTRACED_PATHS):
            body, read_body = await read_body(), None
        if routes is None:
            # unknown tenant: closed-cardinality labels (the id is
            # attacker-controlled), no dispatch
            self._http_requests.labels(
                method=method if method in _KNOWN_METHODS else "other",
                path="other",
                status=404,
                tenant="other",
            ).inc()
            return 404, b"unknown tenant", "text/plain", None
        # elastic-lifecycle admission (docs/DESIGN.md §23): a draining or
        # quarantined tenant's MUTATING traffic sheds at the door with 429
        # (GET polls stay served — a draining tenant's in-flight round
        # still needs its participants to fetch params/sums/seeds)
        if (
            self.lifecycle is not None
            and method == "POST"
            and path in ("/message", "/edge/envelope")
        ):
            admitted, retry_after = self.lifecycle.admit(
                tenant or self.default_tenant
            )
            if not admitted:
                if read_body is not None:
                    await read_body()  # shed, but the connection stays in step
                extra = (
                    {"Retry-After": str(max(1, math.ceil(retry_after)))}
                    if retry_after
                    else None
                )
                self._http_requests.labels(
                    method=method,
                    path=path,
                    status=429,
                    tenant=tenant,
                ).inc()
                return 429, b"tenant not accepting traffic", "text/plain", extra
        if method == "POST" and path == "/message":
            self._note_phase_arrival(tenant, routes)
        # handlers return (status, payload, ctype) or + an extra-headers dict
        if path in _UNTRACED_PATHS:
            result = await self._dispatch(method, path, url.query, body, headers, routes)
        else:
            # the request span adopts the caller's trace (X-Xaynet-Trace:
            # SDK / edge hop) and sets the ambient context, so the ingest
            # admission span below lands in the same trace
            remote = trace.parse_header(headers.get(trace.TRACE_HEADER.lower()))
            # a message is named here, before its body is read: every stage
            # span from the socket to the fold carries this id as `rid`, and
            # as `phase` the phase its own tenant's coordinator is in now (a
            # server that only takes messages leaves that to its handler)
            arrived = (
                {"phase": routes.fetcher.phase().value}
                if method == "POST" and path == "/message" and routes.fetcher is not None
                else {}
            )
            with tracing.use_request_id(tracing.make_request_id()), stages.use_phase(
                arrived.get("phase", "-")
            ), trace.get_tracer().span(
                SPAN_REQUEST, link=remote, method=method, path=path, tenant=tenant, **arrived
            ) as span:
                # a message's body is counted sealed from its first byte
                # until a worker has opened it (or it is dropped unopened)
                held = self._intake.hold() if read_body is not None else None
                try:
                    if read_body is not None:
                        with stages.stage(
                            "read_body", bytes=int(headers.get("content-length", "0"))
                        ) as reading:
                            body = await read_body(reading)
                    with stages.use_held(held):
                        result = await self._dispatch(
                            method, path, url.query, body, headers, routes
                        )
                finally:
                    if held is not None:
                        held.release()
                span.set(status=result[0])
        status, payload, ctype = result[:3]
        extra = result[3] if len(result) > 3 else None
        self._http_requests.labels(
            method=method if method in _KNOWN_METHODS else "other",
            path=path if path in _KNOWN_PATHS else "other",
            status=status,
            # tenant ids come from the operator's [tenancy] config (a
            # validated closed set), never from the wire: unknown ids
            # bounced above with tenant="other"
            tenant=tenant,
        ).inc()
        return status, payload, ctype, extra

    def _note_phase_arrival(self, tenant: str, routes: TenantRoutes) -> None:
        """A message's headers are parsed and no message of this phase came
        before. In Update: the high-water mark of resident bodies starts
        again. In Sum2, once a round: one observation of how long the sum
        participant took, and one log line of how the Update phase's bodies
        were read (the counters are the process's: with several tenants, all
        bodies since the line before)."""
        if routes.fetcher is None:  # a server that only takes messages (tools, tests)
            return
        entered = routes.fetcher.events.phase.get_latest()
        if self._phase_seen.get(tenant) is entered:
            return
        self._phase_seen[tenant] = entered
        if entered.event is PhaseName.UPDATE:
            self._intake.new_window()
        elif entered.event is PhaseName.SUM2:
            self._sum2_first_arrival.observe(time.monotonic() - entered.at)
            direct, overflow, turned, high, kept = self._intake.since_last()
            if direct or overflow or turned:
                logger.info(
                    "large bodies since the last Sum2: %d read by rest-body threads, %d by the "
                    "rest-overflow thread, %d through the StreamReader (%s); at most %d message "
                    "bodies held sealed at once; %d of the two carriers' bodies were received "
                    "into pages kept from earlier bodies",
                    direct, overflow, sum(turned.values()),
                    ", ".join(f"{reason} {n}" for reason, n in turned.items()) or "none", high,
                    kept,
                )
            staged = wire_stats.since_last()
            if staged["packed"] or staged["legacy"]:
                logger.info(
                    "update vectors staged since the last Sum2: %d on the packed wire (v2), %d "
                    "on the legacy wire (v1); %d of them copied into their slots as planes",
                    staged["packed"], staged["legacy"], staged["copied"],
                )

    async def _dispatch(self, method: str, path: str, query: str, body: bytes,
                        headers, routes: TenantRoutes):
        try:
            if method == "POST" and path == "/message":
                return await self._post_message(body, routes)
            if routes.edge_api is not None and path.startswith("/edge/"):
                return await self._edge_route(method, path, body, headers or {}, routes)
            if method == "GET" and path == "/params":
                return 200, json.dumps(routes.fetcher.round_params().to_dict()).encode(), "application/json"
            if method == "GET" and path == "/sums":
                sums = routes.fetcher.sum_dict()
                if sums is None:
                    return 204, b"", "text/plain"
                return (
                    200,
                    json.dumps({k.hex(): v.hex() for k, v in sums.items()}).encode(),
                    "application/json",
                )
            if method == "GET" and path == "/seeds":
                qs = parse_qs(query)
                pk_hex = (qs.get("pk") or [""])[0]
                if not pk_hex:
                    return 400, b"missing pk", "text/plain"
                seeds = routes.fetcher.seeds_for(bytes.fromhex(pk_hex))
                if seeds is None:
                    return 204, b"", "text/plain"
                if (qs.get("fmt") or [""])[0] == "bin":
                    # batched binary fan-out (§21): 112 B/entry fixed
                    # frames, ~half the bytes of the hex-JSON shape — the
                    # response the loadgen fleet and new SDKs request
                    from ..core.mask.seed import pack_seed_entries

                    return 200, pack_seed_entries(seeds), "application/octet-stream"
                return (
                    200,
                    json.dumps({k.hex(): v.as_bytes().hex() for k, v in seeds.items()}).encode(),
                    "application/json",
                )
            if method == "GET" and path == "/metrics":
                return (
                    200,
                    self.registry.render().encode(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            if method == "GET" and path == "/statusz":
                # live operator console (§20): rendered from registry /
                # timeline / SLO state only — no jax import on this path
                from .console import render_statusz

                return (
                    200,
                    render_statusz(self).encode(),
                    "text/html; charset=utf-8",
                )
            if method == "GET" and path == "/alerts":
                from ..telemetry.slo import get_engine

                return (
                    200,
                    json.dumps(get_engine().alerts_payload()).encode(),
                    "application/json",
                )
            if method == "GET" and path == "/healthz":
                # liveness + the coarse round position, cheap enough to poll
                payload = self._health_payload(routes)
                payload["status"] = "ok"
                payload["uptime_seconds"] = round(time.monotonic() - self._started_at, 3)
                payload["overflow_bodies"] = int(self._intake.overflow_bodies.value)
                if routes.pipeline is not None:
                    ingest = routes.pipeline.health()
                    # the ingress boundary gets its own top-level section
                    # (§21): acceptance rates, shard occupancy, wire mix
                    payload["ingress"] = ingest.pop("ingress", None)
                    payload["ingest"] = ingest
                    if ingest["saturated"]:
                        payload["status"] = "saturated"
                streaming = self._streaming_health()
                if streaming is not None:
                    payload["pipeline"] = streaming
                tenancy = self._tenancy_health()
                if tenancy is not None:
                    payload["tenancy"] = tenancy
                # which spans also go to the mirror sink (the profiler's
                # clock): a trace reader tells the program's spans from the
                # runtime's own events by this closed set
                tracer = trace.get_tracer()
                payload["trace"] = {
                    "mode": tracer.mode,
                    "mirror": tracer.mirrored,
                    "mirrored_spans": trace.mirrored_span_names(),
                }
                # process start to serving, step by step (where a runner
                # has marked the steps: telemetry/startup.py)
                startup = get_timeline().report()
                if startup is not None:
                    payload["startup"] = startup
                if routes.health_extra is not None:
                    # role-specific sections (the edge runner reports its
                    # upstream link + envelope backlog here); an extra
                    # "status" key overrides ok (e.g. upstream unreachable)
                    payload.update(routes.health_extra())
                return 200, json.dumps(payload).encode(), "application/json"
            if method == "GET" and path == "/health":
                return 200, json.dumps(self._health_payload(routes)).encode(), "application/json"
            if method == "GET" and path == "/model":
                model = routes.fetcher.model()
                if model is None:
                    return 204, b"", "text/plain"
                # model DOWNLOAD response, not a request body
                body = np.asarray(model, np.float64).tobytes()  # lint: wirecopy-ok
                return 200, body, "application/octet-stream"
            return 404, b"not found", "text/plain"
        except Exception as err:
            logger.exception("request failed: %s %s", method, path)
            return 500, str(err).encode(), "text/plain"

    async def _admin_route(self, method: str, path: str, body: bytes, headers: dict):
        """The authenticated tenant-lifecycle surface (docs/DESIGN.md §23).

        - ``GET    /admin/tenants``        — lifecycle states of every tenant
        - ``POST   /admin/tenants``        — onboard: ``{"tenant": "<id>"}``
        - ``POST   /admin/tenants/<id>``   — reconfigure: ``{"weight", "tier"}``
        - ``DELETE /admin/tenants/<id>``   — graceful drain (+ hard-kill
          escalation after the drain budget)

        Fully disabled (404, indistinguishable from an unknown route)
        unless BOTH a lifecycle manager and a ``[tenancy] admin_token``
        are configured; the token check is constant-time like the edge
        tier's. Status mapping: 400 malformed id/body, 401 bad token, 409
        incompatible lifecycle state (already serving, not drainable).
        """
        import hmac

        if self.lifecycle is None or not self.admin_token:
            return 404, b"not found", "text/plain", None
        supplied = headers.get("x-admin-token", "")
        if not hmac.compare_digest(supplied.encode(), self.admin_token.encode()):
            return 401, b"bad admin token", "text/plain", None
        from ..tenancy import LifecycleError

        sub = path[len("/admin/tenants"):].strip("/")
        try:
            if method == "GET" and not sub:
                return (
                    200,
                    json.dumps({"tenants": self.lifecycle.states()}).encode(),
                    "application/json",
                    None,
                )
            if method == "POST" and not sub:
                spec = json.loads(body or b"{}")
                result = await self.lifecycle.onboard(str(spec.get("tenant", "")))
                return 200, json.dumps(result).encode(), "application/json", None
            if method in ("POST", "PATCH") and sub:
                spec = json.loads(body or b"{}")
                result = self.lifecycle.reconfigure(
                    sub, weight=spec.get("weight"), tier=spec.get("tier")
                )
                return 200, json.dumps(result).encode(), "application/json", None
            if method == "DELETE" and sub:
                result = await self.lifecycle.offboard(sub)
                return 200, json.dumps(result).encode(), "application/json", None
            return 404, b"not found", "text/plain", None
        except LifecycleError as err:
            return 409, str(err).encode(), "text/plain", None
        except (ValueError, KeyError) as err:  # bad tenant id / bad JSON body
            return 400, str(err).encode(), "text/plain", None
        except Exception as err:
            logger.exception("admin request failed: %s %s", method, path)
            return 500, str(err).encode(), "text/plain", None

    def _tenancy_health(self) -> dict | None:
        """The multi-tenant /healthz section: registered tenants, each
        tenant's phase/round, and the shared pool's page accounting.
        ``None`` (no section) for single-tenant deployments."""
        if not self.tenants:
            return None
        from ..tenancy.pool import get_pool

        return {
            "tenants": {
                tid: {
                    "phase": r.fetcher.phase().value,
                    "round_id": r.fetcher.events.params.get_latest().round_id,
                }
                for tid, r in self.tenants.items()
            },
            "pool": get_pool().stats(),
        }

    def _streaming_health(self) -> dict | None:
        """The streaming-fold ``pipeline`` section of /healthz, read from
        the telemetry registry (no jax import on the REST path): the
        global pipeline gauges plus, for shard-parallel folds, the
        per-shard staging depth / in-flight folds / overlap ratio keyed by
        shard index. ``None`` when no streaming pipeline ever ran in this
        process (host aggregation) — the section simply doesn't appear."""
        depth = self.registry.sample_value("xaynet_streaming_staging_depth")
        if depth is None:
            return None
        reg = self.registry
        section = {
            "staging_depth": depth,
            "inflight_folds": reg.sample_value("xaynet_streaming_inflight_folds") or 0,
            "overlap_ratio": reg.sample_value("xaynet_streaming_overlap_ratio") or 0.0,
            "degraded": bool(reg.sample_value("xaynet_streaming_degraded") or 0),
        }
        shards: dict[str, dict] = {}
        for metric, field in (
            ("xaynet_streaming_shard_staging_depth", "staging_depth"),
            ("xaynet_streaming_shard_inflight_folds", "inflight_folds"),
            ("xaynet_streaming_shard_overlap_ratio", "overlap_ratio"),
        ):
            family = reg.get(metric)
            if family is None:
                continue
            for key, child in family.children():
                shards.setdefault(key[0], {})[field] = child.value
        if shards:
            section["shards"] = {
                k: shards[k]
                for k in sorted(shards, key=lambda s: int(s) if s.isdigit() else -1)
            }
        return section

    async def _edge_route(self, method: str, path: str, body: bytes, headers: dict,
                          routes: TenantRoutes):
        """Edge-tier endpoints (served only with ``[edge] enabled = true``).

        Status mapping for POST /edge/envelope keeps the edge's retry
        decision unambiguous: 200 folded, 400 unparseable, 401 bad token,
        409 protocol rejection (PERMANENT — drop the envelope, its members
        fall back to uploading upstream directly), 503 the state machine
        could not take the request right now (transient — retry).
        """
        from ..edge.envelope import EnvelopeError

        edge_api = routes.edge_api
        if not edge_api.authorized(headers):
            return 401, b"bad edge token", "text/plain"
        if method == "GET" and path == "/edge/round":
            # the round handoff IS the protocol: a trusted edge needs the
            # round's secret key to act as the decrypt/verify tier (§11),
            # behind the constant-time token check above
            return (
                200,
                json.dumps(edge_api.round_info()).encode(),  # lint: taint-ok: edge round handoff
                "application/json",
            )
        if method == "POST" and path == "/edge/envelope":
            try:
                accepted, detail = await edge_api.submit_envelope(body)
            except EnvelopeError as err:
                return 400, f"bad envelope: {err}".encode(), "text/plain"
            except RequestError as err:
                # INTERNAL: channel closed / machine mid-transition — the
                # envelope was NOT folded; the edge retries it
                return 503, str(err).encode(), "text/plain", {"Retry-After": "1"}
            if not accepted:
                return 409, (detail or "envelope rejected").encode(), "text/plain"
            return 200, b"", "text/plain"
        return 404, b"not found", "text/plain"

    def _health_payload(self, routes: TenantRoutes) -> dict:
        """Shared by /health (legacy shape) and /healthz (superset)."""
        return {
            "phase": routes.fetcher.phase().value,
            "round_id": routes.fetcher.events.params.get_latest().round_id,
        }

    async def _post_message(self, body: bytes, routes: TenantRoutes):
        if routes.pipeline is not None:
            verdict = await routes.pipeline.submit(body)
            if verdict.shed:
                retry = str(max(1, math.ceil(verdict.retry_after)))
                return (
                    429,
                    b"intake saturated; retry later",
                    "text/plain",
                    {"Retry-After": retry},
                )
            # admitted (processed asynchronously) or pre-filter drop: both
            # answer 200 — the reference reports drops via round
            # progression, not the POST status
            return 200, b"", "text/plain"
        try:
            await routes.handler.handle_message(body)
        except (ServiceError, RequestError) as err:
            # the reference answers 200 regardless and logs the drop —
            # clients learn outcomes from round progression, not the POST
            logger.debug("message dropped: %s", err)
        return 200, b"", "text/plain"

    @staticmethod
    async def _respond(
        writer: asyncio.StreamWriter,
        status: int,
        payload: bytes,
        ctype: str = "text/plain",
        keep_alive: bool = False,
        extra_headers: Optional[dict] = None,
    ) -> None:
        reason = {
            200: "OK",
            204: "No Content",
            400: "Bad Request",
            401: "Unauthorized",
            404: "Not Found",
            409: "Conflict",
            413: "Payload Too Large",
            429: "Too Many Requests",
            500: "Internal Server Error",
            502: "Bad Gateway",
            503: "Service Unavailable",
        }.get(status, "")
        extra = "".join(
            f"{name}: {value}\r\n" for name, value in (extra_headers or {}).items()
        )
        head = (
            f"HTTP/1.1 {status} {reason}\r\n"
            f"Content-Type: {ctype}\r\n"
            f"Content-Length: {len(payload)}\r\n"
            f"{extra}"
            f"Connection: {'keep-alive' if keep_alive else 'close'}\r\n\r\n"
        ).encode()
        writer.write(head + payload)
        await writer.drain()
