"""The REST server's body read (docs/DESIGN.md §16): a large body on plain
TCP is received straight into one buffer of its ``Content-Length``, by a
``rest-body`` thread while one is free and by the one event-driven
``rest-overflow`` thread, beside any number of others, while none is;
everything else goes through the StreamReader. Whichever carries it,
``_dispatch`` gets the bytes that were sent and nothing of the next request,
a slow or vanished peer is dropped unanswered within ``read_timeout``, and
``stop()`` does not wait for a body.

Each test starts its own server on an ephemeral port with its own registry
and talks to it over a raw connection, so that what one TCP segment carries
is the test's choice.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import ssl
import threading
import time
from types import SimpleNamespace

import pytest

from xaynet_tpu.server import rest
from xaynet_tpu.server.rest import DIRECT_BODY_MIN, RestServer
from xaynet_tpu.telemetry.registry import MetricsRegistry

MB = 1 << 20


class _Capture:
    """Stands where ``PetMessageHandler`` does: keeps what ``_dispatch`` gave it."""

    def __init__(self):
        self.bodies: list[tuple[type, int, str]] = []

    async def handle_message(self, body) -> None:
        self.bodies.append((type(body), len(body), hashlib.sha256(body).hexdigest()))


class _Fetcher:
    """What ``/healthz`` asks of a ``Fetcher``."""

    events = SimpleNamespace(params=SimpleNamespace(get_latest=lambda: SimpleNamespace(round_id=1)))

    def phase(self):
        return SimpleNamespace(value="update")


class _Shedding:
    """A lifecycle manager whose tenant is draining: every POST sheds."""

    def admit(self, tenant):
        return False, 2.0


class _SaturatedIngest:
    """An ``[ingest] enabled`` pipeline that sheds what it is handed."""

    def __init__(self):
        self.seen: list[int] = []

    async def submit(self, body):
        from xaynet_tpu.ingest.admission import Admission, Verdict

        self.seen.append(len(body))
        return Admission(Verdict.SHED, retry_after=1.5)


def _payload(n: int, salt: int = 0) -> bytes:
    block = hashlib.sha256(bytes([salt])).digest() * 2048  # 64 KiB
    return (block * (n // len(block) + 1))[:n]


def _digest(body: bytes) -> tuple[int, str]:
    return len(body), hashlib.sha256(body).hexdigest()


def _head(length: int, path: str = "/message") -> bytes:
    return (
        f"POST {path} HTTP/1.1\r\nHost: t\r\nContent-Length: {length}\r\n"
        "Connection: keep-alive\r\n\r\n"
    ).encode()


async def _response(reader: asyncio.StreamReader) -> tuple[int, dict, bytes]:
    status = int((await reader.readline()).split()[1])
    headers = {}
    while (line := await reader.readline()) not in (b"\r\n", b""):
        name, _, value = line.decode().partition(":")
        headers[name.strip().lower()] = value.strip()
    return status, headers, await reader.readexactly(int(headers.get("content-length", "0")))


class _Served:
    def __init__(self, tls=None, **kwargs):
        self.handler = _Capture()
        self.registry = MetricsRegistry()
        self.server = RestServer(None, self.handler, registry=self.registry, **kwargs)
        self.tls = tls

    async def __aenter__(self):
        _, self.port = await self.server.start("127.0.0.1", 0, tls=self.tls)
        return self

    async def __aexit__(self, *exc):
        await self.server.stop()

    def connect(self, tls=None):
        return asyncio.open_connection("127.0.0.1", self.port, ssl=tls)

    def read_bytes(self, route: str) -> int:
        value = self.registry.sample_value("xaynet_rest_body_bytes_total", {"route": route})
        return int(value or 0)

    def received(self) -> list[tuple[int, str]]:
        return [(n, digest) for _, n, digest in self.handler.bodies]


def _reader_threads(prefix: str = "rest-") -> list[threading.Thread]:
    """The ``rest-body`` and ``rest-overflow`` threads alive now."""
    return [t for t in threading.enumerate() if t.name.startswith(prefix)]


def _open_descriptors() -> int:
    return len(os.listdir("/proc/self/fd"))


def _wait_until(predicate, seconds: float = 5.0) -> bool:
    deadline = time.monotonic() + seconds
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.01)
    return predicate()


def _reading(server: RestServer) -> int:
    """Bodies a direct carrier is receiving at this instant."""
    return len(server._direct_reads) + len(server._overflow_reads)


# the carriers of a body: the two that receive a large one straight from the
# socket, and the StreamReader
ROUTE = {"thread": "direct", "overflow": "overflow", "stream": "stream"}
ROUTES = sorted(ROUTE.values())
DIRECT = ["thread", "overflow"]


@pytest.fixture(params=DIRECT)
def direct(request, monkeypatch) -> str:
    """A large body's carrier: a ``rest-body`` thread, or, with no reader to
    be had, the ``rest-overflow`` thread. Returns its ``route`` label."""
    return _carried_by(request.param, monkeypatch)


@pytest.fixture(params=["stream", *DIRECT])
def carrier(request, monkeypatch) -> str:
    """Any of the three carriers; a test sizes its body with ``_size_for``."""
    return _carried_by(request.param, monkeypatch)


def _carried_by(carrier: str, monkeypatch) -> str:
    if carrier == "overflow":
        monkeypatch.setattr(rest, "BODY_READERS", 0)
    return ROUTE[carrier]


def _size_for(route: str) -> int:
    return 4096 if route == "stream" else 4 * MB


@pytest.mark.parametrize(
    "size, route",
    [
        (1, "stream"),
        (4096, "stream"),
        (DIRECT_BODY_MIN - 1, "stream"),
        (DIRECT_BODY_MIN, "direct"),
        (DIRECT_BODY_MIN + 1, "direct"),
        (5 * MB + 3, "direct"),
        (32 * MB, "direct"),
        (DIRECT_BODY_MIN, "overflow"),
        (DIRECT_BODY_MIN + 1, "overflow"),
        (5 * MB + 3, "overflow"),
        (32 * MB, "overflow"),
    ],
)
def test_dispatch_gets_the_bytes_that_were_sent(size, route, monkeypatch):
    body = _payload(size)
    route = _carried_by("thread" if route == "direct" else route, monkeypatch)

    async def run():
        async with _Served() as s:
            reader, writer = await s.connect()
            writer.write(_head(size) + body)
            await writer.drain()
            status, _, _ = await _response(reader)
            writer.close()
            assert status == 200
            assert s.received() == [_digest(body)]
            assert {r: s.read_bytes(r) for r in ROUTES} == {r: size * (r == route) for r in ROUTES}
            # a direct carrier hands the one buffer on; it is not copied back
            assert s.handler.bodies[0][0] is (bytes if route == "stream" else bytearray)

    asyncio.run(run())


@pytest.mark.parametrize("with_headers", [0, 1, 1000, 65536, 300_000, 2 * MB])
def test_body_bytes_that_came_with_the_headers_are_kept(with_headers, direct):
    """The segment that carries the headers carries the body's first bytes
    (300,000 of them make the StreamReader pause the transport itself; 2 MiB
    arrive over several reads before the handler first runs)."""
    size = 3 * MB + 17
    body = _payload(size, salt=1)

    async def run():
        async with _Served() as s:
            reader, writer = await s.connect()
            writer.write(_head(size) + body[:with_headers])
            await writer.drain()
            await asyncio.sleep(0.05)
            writer.write(body[with_headers:])
            await writer.drain()
            status, _, _ = await _response(reader)
            writer.close()
            assert status == 200
            assert s.received() == [_digest(body)]
            assert s.read_bytes(direct) == size

    asyncio.run(run())


@pytest.mark.parametrize("piece", [1000, 100_000])
def test_a_peer_that_trickles_is_waited_for(piece, direct):
    """The body comes in pieces with pauses between them, as from a phone:
    the carrier waits for each and the whole is what was sent."""
    size = DIRECT_BODY_MIN + 7 * piece + 1
    body = _payload(size, salt=4)

    async def run():
        async with _Served() as s:
            reader, writer = await s.connect()
            writer.write(_head(size) + body[: size - 7 * piece])
            for at in range(size - 7 * piece, size, piece):
                await writer.drain()
                await asyncio.sleep(0.02)
                assert s.handler.bodies == []
                writer.write(body[at: at + piece])
            status, _, _ = await asyncio.wait_for(_response(reader), 10.0)
            writer.close()
            assert status == 200
            assert s.received() == [_digest(body)]
            assert s.read_bytes(direct) == size

    asyncio.run(run())


@pytest.mark.parametrize(
    "sizes",
    [
        (4 * MB, 300),
        (300, 4 * MB),
        (4 * MB + 1, 2 * MB + 5),
        (2 * MB, 0, 700, 2 * MB + 9),
    ],
    ids=["large-small", "small-large", "large-large", "large-get-small-large"],
)
def test_pipelined_requests_are_answered_in_order(sizes, direct):
    """Requests written back to back on one keep-alive connection: a direct
    read takes its own body and not a byte of the request behind it."""
    bodies = [_payload(n, salt=i) for i, n in enumerate(sizes)]
    wire = b"".join(
        _head(len(b)) + b if b else b"GET /nowhere HTTP/1.1\r\nHost: t\r\n\r\n" for b in bodies
    )

    async def run():
        async with _Served() as s:
            reader, writer = await s.connect()
            writer.write(wire)
            await writer.drain()
            statuses = [(await _response(reader))[0] for _ in bodies]
            writer.close()
            assert statuses == [200 if b else 404 for b in bodies]
            assert s.received() == [_digest(b) for b in bodies if b]
            assert s.read_bytes(direct) == sum(n for n in sizes if n >= DIRECT_BODY_MIN)
            assert s.read_bytes("stream") == sum(n for n in sizes if n < DIRECT_BODY_MIN)

    asyncio.run(run())


@pytest.mark.parametrize("peer", ["closes", "resets", "stalls"])
def test_short_body_is_dropped_unanswered(peer, carrier):
    """The slow-client defence on every carrier: the whole body within
    ``read_timeout`` or the connection goes, with no response, no message
    dispatched, nothing counted as read and no reader left behind."""
    size = _size_for(carrier)

    async def run():
        async with _Served(read_timeout=0.5) as s:
            reader, writer = await s.connect()
            writer.write(_head(size) + _payload(size // 2))
            await writer.drain()
            await asyncio.sleep(0.1)
            t0 = time.monotonic()
            if peer == "closes":
                writer.write_eof()
            elif peer == "resets":
                writer.transport.abort()
            if peer != "resets":
                assert await asyncio.wait_for(reader.read(), 5.0) == b""  # closed, no 200
            assert time.monotonic() - t0 < 2.0
            writer.close()
            await asyncio.sleep(0.1)
            assert s.handler.bodies == []
            assert [s.read_bytes(r) for r in ROUTES] == [0, 0, 0]
            assert _wait_until(lambda: not _reading(s.server) and not s.server._writers)
        assert _wait_until(lambda: not _reader_threads())

    asyncio.run(run())


@pytest.mark.parametrize("why", ["tls", "no-free-reader", "no-native-library"])
def test_what_the_request_shows_chooses_the_carrier(why, tmp_path, monkeypatch):
    """A large body over TLS is read through the StreamReader and one that
    finds every reader busy by the ``rest-overflow`` thread, and the counters
    say so; without the native library a ``rest-body`` thread's read loops
    in Python instead."""
    size = 3 * MB + 1
    body = _payload(size, salt=2)
    server_ctx = client_ctx = None
    if why == "tls":
        pytest.importorskip("cryptography")
        from test_tls import _self_signed  # the TLS tests' private CA

        cert_path, key_path = _self_signed(tmp_path)
        server_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        server_ctx.load_cert_chain(cert_path, key_path)
        client_ctx = ssl.create_default_context(cafile=cert_path)
    elif why == "no-free-reader":
        monkeypatch.setattr(rest, "BODY_READERS", 1)
    else:
        monkeypatch.setattr(rest.native, "load", lambda: None)

    async def run():
        async with _Served(tls=server_ctx) as s:
            held = None
            if why == "no-free-reader":
                # the one reader is held by a peer that sends half a body
                _, held = await s.connect()
                held.write(_head(size) + body[: size // 2])
                await held.drain()
                assert await asyncio.to_thread(_wait_until, lambda: len(s.server._direct_reads) == 1)
            reader, writer = await s.connect(tls=client_ctx)
            writer.write(_head(size) + body)
            await writer.drain()
            status, _, _ = await asyncio.wait_for(_response(reader), 10.0)
            writer.close()
            if held is not None:
                held.close()
            assert status == 200
            assert s.received() == [_digest(body)]
            route, reason = {"tls": ("stream", "tls"), "no-free-reader": ("overflow", "no_reader"),
                             "no-native-library": ("direct", "large")}[why]
            assert {r: s.read_bytes(r) for r in ROUTES} == {r: size * (r == route) for r in ROUTES}
            reads = s.registry.get("xaynet_rest_body_reads_total")
            assert {key: child.value for key, child in reads.children() if child.value} \
                == {(route, reason): 1}
            # declared, and 0: no large plain body that had a socket is gathered on the loop
            assert s.registry.sample_value(
                "xaynet_rest_body_reads_total", {"route": "stream", "reason": "no_reader"}) == 0

    asyncio.run(run())


def test_stop_does_not_wait_for_a_body(carrier):
    """PR 21's repair, on every carrier: a connection in mid-body neither
    holds ``stop()`` for ``read_timeout`` nor leaves a thread reading, a
    reader registered or a descriptor open."""
    size = _size_for(carrier)

    async def run():
        descriptors = _open_descriptors()
        s = _Served(read_timeout=120.0)
        await s.__aenter__()
        reader, writer = await s.connect()
        writer.write(_head(size) + _payload(size // 2))
        await writer.drain()
        await asyncio.sleep(0.2)
        assert (len(s.server._direct_reads), len(s.server._overflow_reads)) \
            == (carrier == "direct", carrier == "overflow")
        assert s.registry.sample_value("xaynet_rest_overflow_bodies") == (carrier == "overflow")
        t0 = time.monotonic()
        await s.server.stop()
        assert time.monotonic() - t0 < 2.0
        assert await asyncio.wait_for(reader.read(), 5.0) == b""
        writer.close()
        await writer.wait_closed()
        assert s.handler.bodies == []
        assert await asyncio.to_thread(
            _wait_until, lambda: not _reading(s.server) and not _reader_threads())
        await asyncio.sleep(0)  # the thread's last word is a callback on this loop
        assert s.registry.sample_value("xaynet_rest_overflow_bodies") == 0
        assert await asyncio.to_thread(_wait_until, lambda: _open_descriptors() <= descriptors)

    asyncio.run(run())


@pytest.mark.parametrize("shed_by", ["lifecycle", "ingest"])
def test_shed_body_leaves_the_connection_in_step(shed_by, carrier):
    """A 429 still consumes its body exactly: the next request on the
    connection is read from its own first byte."""
    size = _size_for(carrier)
    body = _payload(size, salt=3)

    async def run():
        s = _Served()
        ingest = _SaturatedIngest()
        if shed_by == "lifecycle":
            s.server.lifecycle = _Shedding()
        else:
            s.server._default_routes.pipeline = ingest
        async with s:
            reader, writer = await s.connect()
            writer.write(_head(size) + body)
            await writer.drain()
            status, headers, _ = await _response(reader)
            assert status == 429 and int(headers["retry-after"]) == 2
            writer.write(b"GET /nowhere HTTP/1.1\r\nHost: t\r\n\r\n" + _head(10) + b"0123456789")
            await writer.drain()
            assert (await _response(reader))[0] == 404
            assert (await _response(reader))[0] == 429
            writer.close()
            assert s.handler.bodies == []
            assert ingest.seen == ([size, 10] if shed_by == "ingest" else [])
            assert s.read_bytes(carrier) >= size

    asyncio.run(run())


def _handlers() -> list[asyncio.Task]:
    return [t for t in asyncio.all_tasks() if t.get_coro().__name__ == "_handle_conn"]


def test_a_cancelled_request_takes_its_read_with_it(direct):
    """The handler of a connection in mid-body is cancelled: its carrier lets
    the socket go at once (no thread, registration or descriptor is held for
    ``read_timeout``), nothing is dispatched, and the server serves on."""
    size = 4 * MB
    body = _payload(size, salt=5)

    async def post(s):
        reader, writer = await s.connect()
        writer.write(_head(size) + body)
        await writer.drain()
        status, _, _ = await asyncio.wait_for(_response(reader), 10.0)
        writer.close()
        await writer.wait_closed()
        return status

    async def run():
        async with _Served(read_timeout=120.0) as s:
            assert await post(s) == 200  # the carrier's own descriptors are open from here on
            assert await asyncio.to_thread(_wait_until, lambda: not s.server._writers)
            descriptors = _open_descriptors()
            reader, writer = await s.connect()
            writer.write(_head(size) + body[: size // 2])
            await writer.drain()
            assert await asyncio.to_thread(_wait_until, lambda: _reading(s.server) == 1)
            (handler,) = _handlers()
            handler.cancel()
            assert await asyncio.wait_for(reader.read(), 5.0) == b""
            writer.close()
            await writer.wait_closed()
            assert await asyncio.to_thread(
                _wait_until, lambda: not _reading(s.server) and _open_descriptors() <= descriptors)
            assert await post(s) == 200
            assert s.received() == [_digest(body)] * 2 and s.read_bytes(direct) == 2 * size

    asyncio.run(run())


@pytest.mark.parametrize("readers", [0, 16])
def test_48_bodies_at_once_arrive_whole_and_none_waits_for_another(readers, monkeypatch):
    """48 connections send a large body at one instant, 47 of them whole and
    one a piece at a time: the 47 are answered while the slow one is still in
    mid-body, on the one ``rest-overflow`` thread (with sixteen ``rest-body``
    readers beside it: they take the first sixteen), and the slow one is
    answered when its last byte is in."""
    monkeypatch.setattr(rest, "BODY_READERS", readers)
    monkeypatch.setattr(rest, "OVERFLOW_TURN_BYTES", 64 * 1024)  # a body is many turns
    size, n = 2 * MB + 11, 48
    bodies = [_payload(size, salt=10 + i) for i in range(n)]

    async def run():
        async with _Served() as s:
            peers = [await s.connect() for _ in range(n)]
            slow_reader, slow = peers[-1]  # the last to ask: it finds no reader
            for (_, writer), body in zip(peers[:-1], bodies):
                writer.write(_head(size) + body[: size // 3])
            await asyncio.gather(*(writer.drain() for _, writer in peers[:-1]))
            assert await asyncio.to_thread(_wait_until, lambda: _reading(s.server) == n - 1)
            slow.write(_head(size) + bodies[-1][:1000])
            assert await asyncio.to_thread(_wait_until, lambda: _reading(s.server) == n)
            assert len(s.server._direct_reads) == readers
            assert s.registry.sample_value("xaynet_rest_overflow_bodies") == n - readers
            assert len(_reader_threads("rest-overflow")) == 1
            for (_, writer), body in zip(peers[:-1], bodies):
                writer.write(body[size // 3:])
            statuses = await asyncio.wait_for(
                asyncio.gather(*(_response(reader) for reader, _ in peers[:-1])), 30.0)
            assert [status for status, _, _ in statuses] == [200] * (n - 1)
            assert sorted(s.received()) == sorted(_digest(b) for b in bodies[:-1])
            assert len(s.server._overflow_reads) == 1  # the slow one, still waited for
            for at in range(1000, size, 300_000):
                slow.write(bodies[-1][at: at + 300_000])
                await slow.drain()
                await asyncio.sleep(0.01)
            assert (await asyncio.wait_for(_response(slow_reader), 10.0))[0] == 200
            assert s.received()[-1] == _digest(bodies[-1])
            assert (s.read_bytes("direct"), s.read_bytes("overflow"), s.read_bytes("stream")) \
                == (readers * size, (n - readers) * size, 0)
            for _, writer in peers:
                writer.close()

    asyncio.run(run())


def test_the_overflow_thread_is_made_once_and_stop_ends_it(monkeypatch):
    """No thread until a body needs it; the same one for every later body,
    together or one after another; ``stop()`` ends it."""
    monkeypatch.setattr(rest, "BODY_READERS", 0)
    size = DIRECT_BODY_MIN + 5
    body = _payload(size, salt=6)

    async def post(s):
        reader, writer = await s.connect()
        writer.write(_head(size) + body)
        await writer.drain()
        status, _, _ = await asyncio.wait_for(_response(reader), 10.0)
        writer.close()
        return status, {t.ident for t in _reader_threads("rest-overflow")}

    async def run():
        async with _Served() as s:
            # an earlier test's readers may still be on their way out
            assert await asyncio.to_thread(_wait_until, lambda: not _reader_threads())
            assert s.server._overflow is None
            seen = [await post(s), await post(s), *await asyncio.gather(*(post(s) for _ in range(8)))]
            assert [status for status, _ in seen] == [200] * 10
            (thread,) = {frozenset(threads) for _, threads in seen}  # one, and the same
            assert len(thread) == 1 and s.read_bytes("overflow") == 10 * size
            health = await s.server._dispatch(
                "GET", "/healthz", "", b"", {}, rest.TenantRoutes(_Fetcher(), s.handler))
            assert health[0] == 200 and json.loads(health[1])["overflow_bodies"] == 0
        assert _wait_until(lambda: not _reader_threads())

    asyncio.run(run())


def test_native_recv_stops_at_the_body_and_at_the_deadline():
    """``xn_recv_exactly`` by itself, on a socket pair: it fills from
    ``start`` to the buffer's end and leaves what follows in the socket; it
    returns short when the time runs out, and when the peer has closed."""
    import ctypes
    import socket

    from xaynet_tpu.utils import native

    lib = native.load()
    if lib is None:
        pytest.skip("native library unavailable")
    a, b = socket.socketpair()
    a.setblocking(False)
    try:
        b.sendall(b"x" * 1000 + b"next request")
        body = bytearray(b"head" + bytes(1000))
        buf = (ctypes.c_uint8 * len(body)).from_buffer(body)
        assert lib.xn_recv_exactly(a.fileno(), buf, 4, len(body), 5.0) == 1004
        assert bytes(body) == b"head" + b"x" * 1000
        assert a.recv(100) == b"next request"
        b.sendall(b"y" * 10)
        t0 = time.monotonic()
        assert lib.xn_recv_exactly(a.fileno(), buf, 0, len(body), 0.2) == 10
        assert 0.15 < time.monotonic() - t0 < 2.0
        b.sendall(b"z" * 7)
        b.close()
        assert lib.xn_recv_exactly(a.fileno(), buf, 0, len(body), 5.0) == 7
    finally:
        a.close()
