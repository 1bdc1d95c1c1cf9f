"""The buffers large bodies are received into (docs/DESIGN.md §16, "How a
body is read"; ISSUE 53): ``rest.py`` hands ``recv`` a buffer kept from an
earlier body, whose pages are mapped already, and takes a fresh one only
where none that is free fits. Nobody gives a buffer back: one is free when
nothing but the pool refers to it, so whatever still reads a body (a
``memoryview`` slice, a numpy view, a ``ctypes`` pin) keeps it out of the
next body's way, and a kept buffer is overwritten by the read before
anything reads it.

Each test has its own pool or its own server on an ephemeral port with its
own registry; the served rounds are ``test_packed_wire_round``'s at a length
whose bodies are over ``DIRECT_BODY_MIN``.
"""

from __future__ import annotations

import asyncio
import ctypes
import gc
import logging
import ssl
import threading
from fractions import Fraction

import numpy as np
import pytest

import test_packed_wire_round as round_mod
from benchmark.harness import reference
from test_rest_body_read import (
    MB,
    _head,
    _payload,
    _response,
    _Served,
    _wait_until,
    direct,  # noqa: F401  (the fixture)
)
from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, PhaseKind, StateMachine as ParticipantSM
from xaynet_tpu.server import rest
from xaynet_tpu.server.aggregation import slots_take_planes
from xaynet_tpu.server.rest import BODY_BUFFER_SLACK, DIRECT_BODY_MIN, RestServer, _BodyBuffers
from xaynet_tpu.server.services import Fetcher, PetMessageHandler
from xaynet_tpu.server.state_machine import StateMachineInitializer
from xaynet_tpu.storage.memory import (
    InMemoryCoordinatorStorage,
    InMemoryModelStorage,
    NoOpTrustAnchor,
)
from xaynet_tpu.storage.traits import Store
from xaynet_tpu.telemetry.intake import BodyIntake
from xaynet_tpu.telemetry.registry import MetricsRegistry
from xaynet_tpu.utils import native

PAGES = ("kept", "fresh")


def _address(body: bytearray) -> int:
    """Where ``body``'s bytes lie (the pin is gone when this returns)."""
    return ctypes.addressof(ctypes.c_char.from_buffer(body))


def _held(pool: _BodyBuffers) -> int:
    """The bytes the pool's kept buffers have allocated: what its cap bounds."""
    return sum(buf.__alloc__() for buf in pool._kept)


def _pages(registry: MetricsRegistry) -> dict[str, int]:
    return {p: int(registry.sample_value("xaynet_rest_body_buffers_total", {"pages": p}) or 0)
            for p in PAGES}


# --- the pool alone ------------------------------------------------------------


def test_a_second_body_of_the_length_lands_on_the_firsts_memory_once_nothing_refers_to_it():
    pool, n = _BodyBuffers(), 3 * MB + 11
    first, kept = pool.take(n)
    assert (type(first), len(first), kept) == (bytearray, n, False)
    at = _address(first)
    first[:4] = first[-4:] = b"\xaa" * 4  # writable to its last byte
    other, kept = pool.take(n)  # the first is still the caller's: fresh pages
    assert not kept and _address(other) != at and len(other) == n
    del first
    again, kept = pool.take(n)
    assert kept and _address(again) == at and len(again) == n
    # what a kept buffer holds is the last body's bytes, the next body's to overwrite
    assert again[:4] == again[-4:] == b"\xaa" * 4


HOLDERS = {
    "memoryview-slice": lambda body: memoryview(body)[5:-7],
    "slice-of-a-slice": lambda body: memoryview(body)[1 * MB:][:100],
    "numpy-view": lambda body: np.frombuffer(body, dtype=np.uint8, count=1000, offset=64),
    "numpy-view-of-a-view": lambda body: np.frombuffer(memoryview(body)[32:], dtype="<u4", count=9)[2:],
    "ctypes-pin": lambda body: ctypes.c_uint8.from_buffer(body),
    "ctypes-array": lambda body: (ctypes.c_char * 16).from_buffer(body, 4096),
    "a-name": lambda body: body,
    "in-a-frame": lambda body: (lambda: body),
}


def _read_through(held) -> bytes:
    """What a holder sees of the body it holds."""
    return bytes(memoryview(held() if callable(held) else held).cast("B"))


@pytest.mark.parametrize("holder", list(HOLDERS))
def test_a_body_still_referred_to_is_never_handed_out_and_is_once_the_view_is_gone(holder):
    pool, n = _BodyBuffers(), 2 * MB
    body, _ = pool.take(n)
    at = _address(body)
    body[:] = _payload(n, salt=9)
    held = HOLDERS[holder](body)
    del body
    saw = _read_through(held)
    for _ in range(3):  # however often it is asked, and whatever else is free
        other, kept = pool.take(n)
        assert _address(other) != at
        other[:] = b"\xff" * n  # as a read writes over all it was given
        del other
    assert kept  # the second buffer went round; the held one stayed out
    assert _read_through(held) == saw
    del held
    gc.collect()
    both = [pool.take(n) for _ in range(2)]
    assert [kept for _, kept in both] == [True, True]
    assert at in {_address(b) for b, _ in both}


def test_two_lengths_are_kept_side_by_side():
    """A round's two large lengths (the Update body's, the Sum2 body's): the
    round after the one that first saw them takes both from kept pages."""
    pool, update, sum2 = _BodyBuffers(), 7 * MB, 8 * MB + 3
    first = [pool.take(update)[0] for _ in range(3)] + [pool.take(sum2)[0]]
    at = {_address(b): len(b) for b in first}
    del first
    again = [pool.take(n) for n in (update, sum2, update, update)]
    assert all(kept for _, kept in again)
    assert {_address(b): len(b) for b, _ in again} == at  # each on a buffer of its own length
    fifth, kept = pool.take(update)
    assert not kept and _address(fifth) not in at


@pytest.mark.parametrize(
    "delta, kept",
    [
        (0, True),
        (112, True),  # one sum participant more in the seed dictionary
        (-112, True),
        (560, True),
        (BODY_BUFFER_SLACK, True),  # to the allocation's last byte
        (BODY_BUFFER_SLACK + 1, False),
        (-(2 * MB), True),
        (-(3 * MB), False),  # under half: bytearray would move it to shrink it
    ],
)
def test_a_kept_buffer_serves_another_length_where_it_need_not_move(delta, kept):
    pool, n = _BodyBuffers(), 5 * MB
    first, _ = pool.take(n)
    at, room = _address(first), first.__alloc__()
    assert room == n + BODY_BUFFER_SLACK + 1  # bytearray's own trailing byte
    del first
    body, was_kept = pool.take(n + delta)
    assert len(body) == n + delta and was_kept is kept
    assert (_address(body) == at) is kept
    if kept:
        assert body.__alloc__() == room  # resized in place: nothing allocated, nothing moved
        body[-1:] = b"\x01"
        del body
        back, was_kept = pool.take(n)  # and serves its first length again
        assert was_kept and _address(back) == at and len(back) == n


def test_of_two_that_fit_the_smaller_is_taken():
    pool = _BodyBuffers()
    large, small = pool.take(8 * MB)[0], pool.take(7 * MB)[0]
    at_large, at_small = _address(large), _address(small)
    del large, small
    body, kept = pool.take(7 * MB - 300)
    assert kept and _address(body) == at_small
    other, kept = pool.take(7 * MB - 300)  # the small one is in use: the large one fits too
    assert kept and _address(other) == at_large


def test_the_cap_in_bytes_holds_and_lets_the_least_recently_taken_go():
    n = 2 * MB
    room = n + BODY_BUFFER_SLACK + 1
    pool = _BodyBuffers(cap=3 * room)
    a, b, c = (pool.take(n)[0] for _ in range(3))
    at_a, at_b, at_c = (_address(x) for x in (a, b, c))
    assert _held(pool) == 3 * room and len(pool._kept) == 3
    del b
    b, kept = pool.take(n)  # taken again: the order of recency is a, c, b
    assert kept and _address(b) == at_b
    d, kept = pool.take(n)  # all three in use: fresh pages, kept, and a is let go
    assert not kept and _held(pool) == 3 * room
    assert [_address(x) for x in pool._kept] == [at_c, at_b, _address(d)]
    # a buffer let go while something reads it is its reader's alone, and whole
    a[:3] = a[-3:] = b"abc"
    assert _address(a) == at_a and len(a) == n
    del c
    e, kept = pool.take(n)
    assert kept and _address(e) == at_c  # and what stayed is handed out as before
    assert _held(pool) == 3 * room


def test_a_body_longer_than_the_cap_is_served_and_not_kept():
    pool = _BodyBuffers(cap=4 * MB)
    small, _ = pool.take(MB)
    big, kept = pool.take(6 * MB)
    assert not kept and len(big) == 6 * MB and len(pool._kept) == 1 and _held(pool) < 4 * MB
    big[-1:] = b"\x07"
    del big
    again, kept = pool.take(6 * MB)
    assert not kept and len(again) == 6 * MB
    del small, again
    assert pool.take(MB)[1]  # what was kept is still there


def test_a_pool_that_may_keep_nothing_hands_out_fresh_pages_as_before():
    pool = _BodyBuffers(cap=0)
    for _ in range(3):
        body, kept = pool.take(MB)
        assert not kept and len(body) == MB
        del body
    assert pool._kept == []


def test_no_buffer_is_in_two_hands_at_once_under_contending_threads():
    """Takers on many threads, each holding what it took for a while behind
    a view: whatever the interleaving, nobody is handed memory that another
    still reads, and the pool keeps no more than was ever held at once."""
    import sys

    pool, n, threads, rounds = _BodyBuffers(), MB, 12, 60
    in_use: set[int] = set()
    guard, clashes = threading.Lock(), []

    def churn(seed: int):
        rng = np.random.default_rng(seed)
        for _ in range(rounds):
            body, _ = pool.take(n + int(rng.integers(0, 400)))
            at = _address(body)
            view = memoryview(body)[8:]
            del body  # only the view holds it now
            with guard:
                if at in in_use:
                    clashes.append(at)
                in_use.add(at)
            view[:8] = seed.to_bytes(8, "little")
            if bytes(view[:8]) != seed.to_bytes(8, "little"):
                clashes.append(-at)
            with guard:
                in_use.discard(at)
            del view

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=churn, args=(i + 1,)) for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers) and clashes == []
    # what was held at once, and one more wherever a taker met a reference on its way out
    assert 1 <= len(pool._kept) <= 2 * threads


def test_the_intake_counts_pages_and_the_rounds_line_says_how_many_were_kept():
    registry = MetricsRegistry()
    intake = BodyIntake(registry)
    assert _pages(registry) == {"kept": 0, "fresh": 0}  # declared: 0, not absent
    intake.read("direct", "large", pages="fresh")
    intake.read("overflow", "no_reader", pages="kept")
    intake.read("direct", "large", pages="kept")
    intake.read("stream", "small")  # the StreamReader's bodies have no buffer of their own
    intake.read("stream", "tls")
    assert _pages(registry) == {"kept": 2, "fresh": 1}
    assert intake.since_last() == (2, 1, {"tls": 1}, 0, 2)
    intake.read("direct", "large", pages="kept")
    assert intake.since_last() == (1, 0, {}, 0, 1)
    assert intake.since_last() == (0, 0, {}, 0, 0)
    # a registry that served another server before: the line counts from this one's start
    assert BodyIntake(registry).since_last() == (0, 0, {}, 0, 0)


def test_the_benchmarks_metric_reads_the_counter():
    """``rest.body_kept_share`` as the benchmark computes it: its own data
    file, run by the shipped reader over two reads of ``/metrics``."""
    import importlib

    from benchmark.harness import data
    from benchmark.harness.coordinator import parse_metrics

    spec = data.load_layer_metric("rest.body_kept_share")
    read = importlib.import_module(f"benchmark.readers.{spec['reader']}").read
    registry = MetricsRegistry()
    intake = BodyIntake(registry)
    intake.read("direct", "large", pages="fresh")  # the warm-up round's
    scrapes = {"open": parse_metrics(registry.render())}
    assert read({"metrics": {**scrapes, "end": scrapes["open"]}}, **spec["args"]) is None
    for pages in ("kept", "kept", "kept", "fresh"):
        intake.read("direct", "large", pages=pages)
    scrapes["end"] = parse_metrics(registry.render())
    assert read({"metrics": scrapes}, **spec["args"]) == 75.0
    # a program without the counter (the parent): nothing, and no 0.0
    bare = parse_metrics(MetricsRegistry().render())
    assert read({"metrics": {"open": bare, "end": bare}}, **spec["args"]) is None
    entry = next(m for m in data.load_benchmark()["per_layer"] if m["name"] == "rest.body_kept_share")
    assert entry == {
        "name": "rest.body_kept_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "message pipeline", "moves": "updates_per_s",
        "workloads": [c["name"] for c in data.load_benchmark()["workloads"]],
    }


# --- through the server --------------------------------------------------------


class _Keeps:
    """Stands where ``PetMessageHandler`` does: notes where each body lies
    and what it holds, and keeps ``hold(body)`` of it while told to."""

    def __init__(self):
        self.seen: list[tuple[int, bytes]] = []  # (address, the body's bytes)
        self.hold = None
        self.held: list = []

    async def handle_message(self, body) -> None:
        assert type(body) is bytearray
        self.seen.append((_address(body), bytes(body)))
        if self.hold is not None:
            self.held.append(self.hold(body))


class _ServedKeeping(_Served):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.handler = self.server.handler = self.server._default_routes.handler = _Keeps()


async def _post(s: _Served, body: bytes, tls=None) -> int:
    reader, writer = await s.connect(tls=tls)
    writer.write(_head(len(body)) + body)
    await writer.drain()
    status, _, _ = await asyncio.wait_for(_response(reader), 20.0)
    writer.close()
    # the carrier lets go of the buffer a moment after the request has it
    await asyncio.to_thread(_wait_until, lambda: _all_free(s.server))
    return status


def _all_free(server: RestServer) -> bool:
    """Nothing but the pool refers to a kept buffer, those the handler holds
    apart (the test's view of what ``take`` observes)."""
    pool = server._body_buffers
    with pool._lock:
        busy = sum(n != pool._unshared for n in pool._shared_by())
    return busy <= len(getattr(server.handler, "held", []))


def test_both_carriers_receive_into_the_pools_buffers(direct):
    """A second body of the length lands on the first one's memory, on a
    ``rest-body`` thread and on the ``rest-overflow`` thread alike, and the
    counter reads one ``fresh``, one ``kept``."""
    n = 3 * MB + 5
    bodies = [_payload(n, salt=i) for i in range(3)]

    async def run():
        async with _ServedKeeping() as s:
            for body in bodies[:2]:
                assert await _post(s, body) == 200
            (at0, got0), (at1, got1) = s.handler.seen
            assert (got0, got1) == (bodies[0], bodies[1]) and at0 == at1
            assert _pages(s.registry) == {"kept": 1, "fresh": 1}
            assert s.read_bytes(direct) == 2 * n
            # beside the bytes counter and the reads counter in what the server renders
            assert 'xaynet_rest_body_buffers_total{pages="kept"} 1' in s.registry.render()
            # while something reads the second body, the third goes elsewhere
            s.handler.hold = lambda body: memoryview(body)[100:200]
            assert await _post(s, bodies[2]) == 200
            assert s.handler.seen[2] == (at0, bodies[2])
            s.handler.hold = None
            assert await _post(s, bodies[0]) == 200
            assert s.handler.seen[3][0] != at0 and s.handler.seen[3][1] == bodies[0]
            assert bytes(s.handler.held[0]) == bodies[2][100:200]  # and was not written over
            assert _pages(s.registry) == {"kept": 2, "fresh": 2}
            s.handler.held.clear()
            assert await _post(s, bodies[1]) == 200
            assert _pages(s.registry) == {"kept": 3, "fresh": 2}

    asyncio.run(run())


def test_one_pool_serves_both_carriers(monkeypatch):
    """What a ``rest-body`` thread received into, the ``rest-overflow``
    thread receives into next (``BODY_READERS`` busy -> ``rest-overflow``)."""
    n = 2 * MB
    first, second = _payload(n, salt=1), _payload(n, salt=2)

    async def run():
        async with _ServedKeeping() as s:
            assert await _post(s, first) == 200
            monkeypatch.setattr(rest, "BODY_READERS", 0)
            assert await _post(s, second) == 200
            assert [at for at, _ in s.handler.seen] == [s.handler.seen[0][0]] * 2
            assert [got for _, got in s.handler.seen] == [first, second]
            assert (s.read_bytes("direct"), s.read_bytes("overflow")) == (n, n)
            assert _pages(s.registry) == {"kept": 1, "fresh": 1}

    asyncio.run(run())


@pytest.mark.parametrize("holder", ["memoryview-slice", "numpy-view", "ctypes-pin"])
def test_a_body_the_handler_still_reads_is_left_alone_by_the_server(holder):
    n = 2 * MB
    bodies = [_payload(n, salt=10 + i) for i in range(4)]

    async def run():
        async with _ServedKeeping() as s:
            s.handler.hold = HOLDERS[holder]
            for body in bodies[:3]:
                assert await _post(s, body) == 200
            assert len({at for at, _ in s.handler.seen}) == 3  # each held: each on pages of its own
            assert _pages(s.registry) == {"kept": 0, "fresh": 3}
            for held, body in zip(s.handler.held, bodies):
                view = memoryview(held).cast("B")
                assert bytes(view[:64]) in body  # nothing wrote over what is held
            s.handler.hold = None
            s.handler.held.clear()
            gc.collect()
            assert await _post(s, bodies[3]) == 200
            assert s.handler.seen[3][0] in {at for at, _ in s.handler.seen[:3]}
            assert _pages(s.registry) == {"kept": 1, "fresh": 3}

    asyncio.run(run())


class _Opens:
    """A handler that opens the sealed box over its own ciphertext, as
    ``services.py`` does with the ``bytearray`` a direct carrier hands on."""

    def __init__(self, keys: EncryptKeyPair):
        self.keys, self.opened, self.at = keys, [], []

    async def handle_message(self, body) -> None:
        self.at.append(_address(body))
        raw = self.keys.secret.decrypt_in_place(body, self.keys.public)
        self.opened.append(bytes(raw))


def test_a_short_read_drops_the_connection_and_the_next_body_on_that_buffer_opens(direct):
    """The peer closes in mid-body: no answer, nothing dispatched, and the
    buffer, half written over, goes nowhere but back to the pool; the next
    body of the length is received into it whole and its box opens (every
    byte of it authenticated), so nothing of the short one was read."""
    keys = EncryptKeyPair.generate()
    plain = [_payload(5 * MB + 123, salt=20 + i) for i in range(3)]
    sealed = [keys.public.encrypt(p) for p in plain]
    n = len(sealed[0])

    async def run():
        async with _Served(read_timeout=5.0) as s:
            handler = _Opens(keys)
            s.server.handler = s.server._default_routes.handler = handler
            assert await _post(s, sealed[0]) == 200
            reader, writer = await s.connect()
            writer.write(_head(n) + sealed[1][: n // 2])
            await writer.drain()
            await asyncio.sleep(0.2)
            writer.write_eof()
            assert await asyncio.wait_for(reader.read(), 5.0) == b""  # closed, no 200
            writer.close()
            assert len(handler.opened) == 1
            assert _pages(s.registry) == {"kept": 0, "fresh": 1}  # a body read in full counts
            await asyncio.to_thread(_wait_until, lambda: _all_free(s.server))
            assert len(s.server._body_buffers._kept) == 1  # the short one took the kept buffer
            assert await _post(s, sealed[2]) == 200
            assert handler.opened == [plain[0], plain[2]]
            assert handler.at[0] == handler.at[1]
            assert _pages(s.registry) == {"kept": 1, "fresh": 1}
            assert s.read_bytes(direct) == 2 * n

    asyncio.run(run())


@pytest.mark.parametrize("why", ["small", "tls"])
def test_the_stream_readers_bodies_leave_the_counter_alone(why, tmp_path):
    size = DIRECT_BODY_MIN - 1 if why == "small" else 3 * MB
    server_ctx = client_ctx = None
    if why == "tls":
        pytest.importorskip("cryptography")
        from test_tls import _self_signed  # the TLS tests' private CA

        cert_path, key_path = _self_signed(tmp_path)
        server_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        server_ctx.load_cert_chain(cert_path, key_path)
        client_ctx = ssl.create_default_context(cafile=cert_path)

    async def run():
        async with _Served(tls=server_ctx) as s:
            for salt in range(2):
                assert await _post(s, _payload(size, salt=salt), tls=client_ctx) == 200
            assert s.read_bytes("stream") == 2 * size
            assert s.handler.bodies[0][0] is bytes
            assert _pages(s.registry) == {"kept": 0, "fresh": 0}
            assert s.server._body_buffers._kept == []

    asyncio.run(run())


def test_a_servers_pool_is_its_own_and_is_capped_beside_the_threshold():
    a, b = (RestServer(None, None, registry=MetricsRegistry()) for _ in range(2))
    assert a._body_buffers is not b._body_buffers
    assert a._body_buffers._cap == rest.BODY_BUFFERS_MAX_BYTES == 4 << 30
    # the fan-in cell's 64 bodies of 39.6 MB and the flood8 cells' 8 of 256 MB and a Sum2 body fit
    assert 64 * (39_622_260 + BODY_BUFFER_SLACK + 1) < rest.BODY_BUFFERS_MAX_BYTES
    assert 9 * (255_570_400 + BODY_BUFFER_SLACK + 1) < rest.BODY_BUFFERS_MAX_BYTES


# --- served rounds ---------------------------------------------------------------


async def _two_rounds(settings, senders: list[str], **pipeline) -> list[dict]:
    """Two PET rounds, one after the other, over the REST API of one server
    (``test_packed_wire_round._served_round``'s round and participants): the
    model of each, and what the page counter moved by over each."""
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(settings, store).init()
    fetcher = Fetcher(events)
    handler = PetMessageHandler(events, request_tx, **pipeline)
    registry = MetricsRegistry()
    server = RestServer(fetcher, handler, registry=registry)
    host, port = await server.start("127.0.0.1", 0)
    url = f"http://{host}:{port}"
    machine_task = asyncio.create_task(machine.run())
    clients, out, seed = [], [], None

    def client(kind=HttpClient):
        clients.append(kind(url))
        return clients[-1]

    try:
        for _ in range(2):
            while fetcher.phase().value != "sum" or fetcher.round_params().seed.as_bytes() == seed:
                await asyncio.sleep(0.005)
            seed, stale = fetcher.round_params().seed.as_bytes(), fetcher.model()
            prob = (round_mod.SUM_PROB, round_mod.UPDATE_PROB)
            summer = ParticipantSM(
                PetSettings(keys=keys_for_task(seed, *prob, "sum"),
                            device_sum2=False, max_message_size=None),
                client(), round_mod._Store(None))
            updaters = [
                ParticipantSM(
                    PetSettings(keys=keys_for_task(seed, *prob, "update", start=(10 + i) * 1000),
                                scalar=Fraction(1, round_mod.DEN), max_message_size=None),
                    client(round_mod._LegacyClient if kind == "legacy" else HttpClient),
                    round_mod._Store(reference.to_f32(
                        reference.weights_fixed(round_mod.SEED, i, round_mod.MODEL_LEN))))
                for i, kind in enumerate(senders)]

            async def drive_summer():
                while fetcher.model() is stale:
                    await summer.transition()
                    await asyncio.sleep(0.005)

            sum_task = asyncio.create_task(drive_summer())
            while fetcher.phase().value != "update":
                await asyncio.sleep(0.005)
            before = _pages(registry)
            for sm in updaters:
                sent = False
                while not (sent and sm.phase is PhaseKind.AWAITING):
                    await sm.transition()
                    sent = sent or sm.phase is PhaseKind.UPDATE
            await sum_task
            out.append({"model": np.asarray(fetcher.model(), dtype=np.float64),
                        "pages": {p: n - before[p] for p, n in _pages(registry).items()}})
        return out
    finally:
        machine_task.cancel()
        for c in clients:
            c.close()
        await server.stop()
        await asyncio.gather(machine_task, return_exceptions=True)


ROADS = {
    # what the runner tells the handler of each coordinator, and who sends
    "v1-planes": ("legacy", {}, "sdk"),  # the benchmark's v1 cells: relaid to planes in the parse
    "v1-limb-rows": ("legacy", None, "sdk"),  # a handler told nothing
    "packed-v2": ("packed", {}, "sdk"),  # a view of the body until its slot copy
    "v1-into-a-packed-round": ("packed", {}, "legacy"),
    "wire-ingest": ("legacy", {"wire_ingest": True}, "sdk"),  # parsed lazily: a view of the body
}


@pytest.mark.parametrize("road", list(ROADS))
def test_a_served_rounds_bodies_lie_on_kept_pages_and_the_model_is_the_references(
        road, monkeypatch, tmp_path, caplog):
    """Rounds through ``RestServer`` with bodies over ``DIRECT_BODY_MIN``,
    whatever the parse makes of an Update's vector (a copy, planes, a view
    of the body that waits for its slot copy, a lazily parsed view that the
    device unpacks): the second round, whose bodies are all received into
    what the first left, gives the model the plain reference gives, as the
    first does."""
    import jax

    from xaynet_tpu.parallel import aggregator as aggregator_mod
    from xaynet_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))
    monkeypatch.setattr(round_mod, "MODEL_LEN", 160_001)  # 7 wire bytes each: 1.12 MB a body
    caplog.set_level(logging.INFO, logger="xaynet.rest")
    assert native.load() is not None
    wire_format, told, sender = ROADS[road]
    config, n_update = round_mod.MASKS["integer-b0m6"], 2 * round_mod.K  # two fold batches
    settings = round_mod._settings(config, n_update, wire_format)
    if told is not None:
        settings.aggregation.wire_ingest = told.get("wire_ingest", False)
        told = {"wire_ingest": settings.aggregation.wire_ingest,
                "update_planes": slots_take_planes(settings)}  # as server/runner.py does
    first, second = asyncio.run(asyncio.wait_for(
        _two_rounds(settings, [sender] * n_update, **(told or {})), 240))

    want = round_mod._reference(config, list(range(n_update)))
    assert round_mod._same_bits(first["model"], want)
    assert round_mod._same_bits(second["model"], want)
    # every Update body and the Sum2 body is large and was counted once
    assert sum(first["pages"].values()) == sum(second["pages"].values()) == n_update + 1
    assert first["pages"]["fresh"] >= 1  # somebody was first
    # the second wave: nothing allocated, every body into pages the first round mapped
    assert second["pages"] == {"kept": n_update + 1, "fresh": 0}
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("large bodies since the last Sum2")]
    # the line a round: the first round's Sum2 body and this round's Update bodies
    assert len(lines) == 2 and f"{n_update + 1} read by rest-body threads" in lines[1]
    assert lines[1].endswith(f"; {n_update + 1} of the two carriers' bodies were received into "
                             "pages kept from earlier bodies")
