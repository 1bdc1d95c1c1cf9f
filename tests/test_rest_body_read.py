"""The REST server's body read (docs/DESIGN.md §16): a large body on plain
TCP is received by a ``rest-body`` thread straight into one buffer of its
``Content-Length``; everything else goes through the StreamReader. Whichever
carries it, ``_dispatch`` gets the bytes that were sent and nothing of the
next request, a slow or vanished peer is dropped unanswered within
``read_timeout``, and ``stop()`` does not wait for a body.

Each test starts its own server on an ephemeral port with its own registry
and talks to it over a raw connection, so that what one TCP segment carries
is the test's choice.
"""

from __future__ import annotations

import asyncio
import hashlib
import ssl
import threading
import time

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


def _reader_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate() if t.name.startswith("rest-body")]


def _wait_until(predicate, seconds: float = 5.0) -> bool:
    deadline = time.monotonic() + seconds
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.01)
    return predicate()


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
    ],
)
def test_dispatch_gets_the_bytes_that_were_sent(size, route):
    body = _payload(size)

    async def run():
        async with _Served() as s:
            reader, writer = await s.connect()
            writer.write(_head(size) + body)
            await writer.drain()
            status, _, _ = await _response(reader)
            writer.close()
            assert status == 200
            assert s.received() == [_digest(body)]
            other = "stream" if route == "direct" else "direct"
            assert (s.read_bytes(route), s.read_bytes(other)) == (size, 0)
            # the direct path hands the one buffer on; it is not copied back
            assert s.handler.bodies[0][0] is (bytearray if route == "direct" else bytes)

    asyncio.run(run())


@pytest.mark.parametrize("with_headers", [0, 1, 1000, 65536, 300_000, 2 * MB])
def test_body_bytes_that_came_with_the_headers_are_kept(with_headers):
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
            assert s.read_bytes("direct") == size

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
def test_pipelined_requests_are_answered_in_order(sizes):
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
            assert s.read_bytes("direct") == sum(n for n in sizes if n >= DIRECT_BODY_MIN)
            assert s.read_bytes("stream") == sum(n for n in sizes if n < DIRECT_BODY_MIN)

    asyncio.run(run())


@pytest.mark.parametrize("peer", ["closes", "resets", "stalls"])
@pytest.mark.parametrize("size", [4096, 4 * MB], ids=["stream", "direct"])
def test_short_body_is_dropped_unanswered(peer, size):
    """The slow-client defence on either carrier: the whole body within
    ``read_timeout`` or the connection goes, with no response, no message
    dispatched, nothing counted as read and no reader left behind."""

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
            assert (s.read_bytes("direct"), s.read_bytes("stream")) == (0, 0)
            assert _wait_until(lambda: not s.server._direct_reads and not s.server._writers)
        assert _wait_until(lambda: not _reader_threads())

    asyncio.run(run())


@pytest.mark.parametrize("why", ["tls", "no-free-reader", "no-native-library"])
def test_what_the_request_shows_chooses_the_carrier(why, tmp_path, monkeypatch):
    """A large body over TLS, or with every reader busy, is read through the
    StreamReader, and the counter says so; without the native library the
    direct read loops in Python instead."""
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
            route = "direct" if why == "no-native-library" else "stream"
            assert s.read_bytes(route) == size
            assert s.read_bytes("stream" if route == "direct" else "direct") == 0

    asyncio.run(run())


@pytest.mark.parametrize("size", [4096, 4 * MB], ids=["stream", "direct"])
def test_stop_does_not_wait_for_a_body(size):
    """PR 21's repair, on either carrier: a connection in mid-body neither
    holds ``stop()`` for ``read_timeout`` nor leaves a thread reading."""

    async def run():
        s = _Served(read_timeout=120.0)
        await s.__aenter__()
        reader, writer = await s.connect()
        writer.write(_head(size) + _payload(size // 2))
        await writer.drain()
        await asyncio.sleep(0.2)
        assert len(s.server._direct_reads) == (1 if size >= DIRECT_BODY_MIN else 0)
        t0 = time.monotonic()
        await s.server.stop()
        assert time.monotonic() - t0 < 2.0
        assert await asyncio.wait_for(reader.read(), 5.0) == b""
        writer.close()
        assert s.handler.bodies == []
        assert await asyncio.to_thread(_wait_until, lambda: not _reader_threads())

    asyncio.run(run())


@pytest.mark.parametrize("shed_by", ["lifecycle", "ingest"])
@pytest.mark.parametrize("size", [4096, 4 * MB], ids=["stream", "direct"])
def test_shed_body_leaves_the_connection_in_step(shed_by, size):
    """A 429 still consumes its body exactly: the next request on the
    connection is read from its own first byte."""
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
            route = "direct" if size >= DIRECT_BODY_MIN else "stream"
            assert s.read_bytes(route) >= size

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
