"""Host benchmark of the REST body read, carrier by carrier.

One process serves ``RestServer`` with a handler that drops every message;
a second process sends ``--conns`` bodies of ``--size`` bytes at one
instant, each over its own connection, exactly as
``sdk/client.py::_exchange`` does (``writer.write(head + body); await
writer.drain()``). A case is one carrier for every body:

- ``stream``: the threshold raised above every body, so that the loop's
  StreamReader gathers them (what a large body that found no reader took
  until PR 43);
- ``thread``: a ``rest-body`` thread each (``BODY_READERS`` raised to the
  connections);
- ``overflow``: all on the one ``rest-overflow`` thread (``BODY_READERS``
  0), at each ``--turn`` (``rest.OVERFLOW_TURN_BYTES``);
- ``coroutine``: the carrier that was weighed and not taken (ISSUE 43): the
  same receive as a coroutine on the loop, ``loop.sock_recv_into`` into the
  one buffer, ``--turn`` bytes and then the loop given back; it lives here
  alone;
- ``direct``: ``rest.py`` as it ships (sixteen threads, the rest overflow).

Every carrier that receives into a buffer of the body's length runs once a
``--buffers`` case: ``kept`` = the server's own pool (``rest._BodyBuffers``),
``fresh`` = a pool that keeps nothing, so that every body is received into
pages never touched, as every body was until PR 53. Either way a first wave
(one body a connection, at one instant) goes unmeasured: it is what fills
the pool, as a round fills it for the next.

Printed a case: seconds from headers parsed to body in memory (what
``rest.read_body`` brackets), mean and longest over the bodies; what the
thread that received a body spent on it (``stages.usage``, as the
``rest.read_body`` span carries it: CPU, of that the kernel's, and minor
faults, a body; nothing for ``stream`` and ``coroutine``, whose carrier is
the loop); the bodies that lay on kept pages; the loop
thread's CPU a body (``time.thread_time()`` on the loop, sender started ->
last body in memory); the sealed GB/s (all bodies over first headers parsed
-> last body in memory); how late a 10 ms sleep on the loop woke, mean and
longest; the sender's wall. ``--gil N`` runs N threads of pure Python beside
the server's loop, which is what a served round's own Python does to a
thread that takes the interpreter lock back after every receive. No chip,
no jax: a host number, and quoted as one (PERF.md section 6, PR 27, PR 43).

Run:  python tools/bench_body_read.py [--size 39622260] [--conns 16,48,64]
          [--carriers stream,thread,overflow,coroutine,direct] [--buffers kept,fresh]
          [--turn 262144,1048576,4194304] [--gil 0,1] [--repeat 1]
      python tools/bench_body_read.py --sweep     # where the thread hop pays
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xaynet_tpu.server import rest  # noqa: E402
from xaynet_tpu.telemetry.registry import MetricsRegistry  # noqa: E402


TAKES_TURNS = ("overflow", "coroutine", "direct")  # the carriers --turn applies to
USAGE = ("cpu_s", "sys_s", "minflt")  # of what a carrier thread spent on a body, the keys summed


class _Drop:
    async def handle_message(self, body) -> None:
        return None


class _Spent:
    """Stands where a ``read_body`` stage does: takes what the carrier spent."""

    def __init__(self):
        self.spent: dict = {}

    def set(self, **spent) -> None:
        self.spent = spent


class _TimedServer(rest.RestServer):
    def __init__(self, coroutine: bool, keeps: bool):
        super().__init__(fetcher=None, handler=_Drop(), registry=MetricsRegistry())
        self.coroutine = coroutine
        if not keeps:
            self._body_buffers = rest._BodyBuffers(cap=0)
        # (headers parsed, body in memory, what its carrier thread spent)
        self.reads: list[tuple[float, float, dict]] = []

    async def _read_body(self, reader, writer, length, span=None):
        t0, stage = time.monotonic(), _Spent()
        carrier = self._read_on_the_loop if self.coroutine else super()._read_body
        body = await carrier(reader, writer, length, stage)
        self.reads.append((t0, time.monotonic(), stage.spent))
        return body

    async def _read_on_the_loop(self, reader, writer, length, span=None):
        """``_read_body``'s preparation, then the receive as a coroutine."""
        sock, _ = self._direct_socket(reader, writer, length)
        assert sock is not None
        with sock:
            loop, transport = asyncio.get_running_loop(), writer.transport
            transport.pause_reading()
            body, kept = self._body_buffers.take(length)
            got = len(reader._buffer)
            if got:
                body[:got] = await reader.read(got)
                transport.pause_reading()
            view = memoryview(body)
            while got < length:
                n = await loop.sock_recv_into(sock, view[got:got + rest.OVERFLOW_TURN_BYTES])
                if n == 0:
                    raise asyncio.IncompleteReadError(b"", length)
                got += n
                await asyncio.sleep(0)  # a readable socket never suspends sock_recv_into
        transport.resume_reading()
        self._intake.read("direct", "large", pages="kept" if kept else "fresh")
        return body


async def _watch_lag(late: list[float], period: float = 0.01) -> None:
    loop = asyncio.get_running_loop()
    while True:
        due = loop.time() + period
        await asyncio.sleep(period)
        late.append(max(0.0, loop.time() - due))


async def _send(port: int, size: int, conns: int, rounds: int) -> None:
    from xaynet_tpu.sdk.client import HttpClient

    body = os.urandom(1 << 20) * (size >> 20) + os.urandom(size & ((1 << 20) - 1))
    clients = [HttpClient(f"http://127.0.0.1:{port}", timeout=600.0) for _ in range(conns)]

    async def one(client):
        for _ in range(rounds):
            status, _, _ = await client._request("POST", "/message", body)
            assert status == 200, status

    t0 = time.monotonic()
    await asyncio.gather(*(one(c) for c in clients))
    print(json.dumps({"sender_s": time.monotonic() - t0}))
    for c in clients:
        c.close()


def _spin(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        for i in range(10000):
            x += i * i


def _counted(server: rest.RestServer) -> tuple[dict, dict]:
    """(bodies by the pages they were received into, bytes by route) so far."""
    value = server.registry.sample_value
    return (
        {p: int(value("xaynet_rest_body_buffers_total", {"pages": p}) or 0)
         for p in ("kept", "fresh")},
        {r: int(value("xaynet_rest_body_bytes_total", {"route": r}) or 0)
         for r in ("direct", "overflow", "stream")},
    )


async def _sender(port: int, size: int, conns: int, rounds: int) -> float:
    """One sender process, ``conns`` connections, ``rounds`` bodies each: its wall."""
    sender = await asyncio.create_subprocess_exec(
        sys.executable, os.path.abspath(__file__), "--send", str(port),
        "--size", str(size), "--conns", str(conns), "--rounds", str(rounds),
        stdout=subprocess.PIPE,
    )
    out, _ = await sender.communicate()
    assert sender.returncode == 0, sender.returncode
    return json.loads(out.decode().strip().splitlines()[-1])["sender_s"]


async def _case(
    carrier: str, size: int, conns: int, rounds: int, gil: int, turn: int, buffers: str | None
) -> dict:
    shipped = rest.DIRECT_BODY_MIN, rest.BODY_READERS, rest.OVERFLOW_TURN_BYTES
    if carrier == "stream":
        rest.DIRECT_BODY_MIN = rest.MAX_BODY + 1
    rest.BODY_READERS = {"thread": conns, "overflow": 0}.get(carrier, rest.BODY_READERS)
    rest.OVERFLOW_TURN_BYTES = turn
    server = _TimedServer(coroutine=carrier == "coroutine", keeps=buffers != "fresh")
    _, port = await server.start("127.0.0.1", 0)
    await _sender(port, size, conns, 1)  # the wave that fills the pool, where one keeps
    before = _counted(server)
    server.reads.clear()
    stop, late = threading.Event(), []
    spinners = [threading.Thread(target=_spin, args=(stop,), daemon=True) for _ in range(gil)]
    for t in spinners:
        t.start()
    lag = asyncio.create_task(_watch_lag(late))
    try:
        cpu = time.thread_time()
        sender_s = await _sender(port, size, conns, rounds)
        cpu = time.thread_time() - cpu
    finally:
        stop.set()
        lag.cancel()
        await server.stop()
        rest.DIRECT_BODY_MIN, rest.BODY_READERS, rest.OVERFLOW_TURN_BYTES = shipped
    reads = [end - start for start, end, _ in server.reads]
    wall = max(end for _, end, _ in server.reads) - min(start for start, _, _ in server.reads)
    spent = {key: sum(s.get(key, 0) for _, _, s in server.reads) / len(reads) for key in USAGE}
    by_pages, by_route = (
        {key: n - was[key] for key, n in now.items()} for now, was in zip(_counted(server), before)
    )
    return {
        "carrier": carrier, "size": size, "conns": conns, "rounds": rounds, "gil_threads": gil,
        "turn_bytes": turn if carrier in TAKES_TURNS else None, "buffers": buffers,
        "bodies": len(reads), "read_mean_s": sum(reads) / len(reads), "read_max_s": max(reads),
        "reader_cpu_ms_per_body": 1e3 * (spent["cpu_s"] + spent["sys_s"]),
        "reader_sys_ms_per_body": 1e3 * spent["sys_s"],
        "reader_minflt_per_body": spent["minflt"], "bodies_by_pages": by_pages,
        "loop_cpu_ms_per_body": 1e3 * cpu / len(reads), "sealed_gbps": len(reads) * size / wall / 1e9,
        "loop_lag_mean_ms": 1e3 * sum(late) / max(1, len(late)), "loop_lag_max_ms": 1e3 * max(late, default=0.0),
        "sender_s": sender_s,
        "bytes_by_route": by_route,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--send", type=int, help=argparse.SUPPRESS)  # the sender child: a port
    ap.add_argument("--size", type=int, default=39_622_260)
    ap.add_argument("--conns", default="16,48,64")
    ap.add_argument("--rounds", type=int, default=1, help="bodies a connection sends in turn")
    ap.add_argument("--carriers", default="stream,thread,overflow,coroutine,direct")
    ap.add_argument("--turn", default=str(rest.OVERFLOW_TURN_BYTES),
                    help="bytes a body takes in one turn (overflow, coroutine, direct)")
    ap.add_argument("--buffers", default="kept",
                    help="kept,fresh: what a body is received into (not for stream)")
    ap.add_argument("--gil", default="0")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--sweep", action="store_true",
                    help="one connection, 64 KiB to 16 MiB: where direct overtakes stream")
    args = ap.parse_args()
    if args.send is not None:
        asyncio.run(_send(args.send, args.size, int(args.conns), args.rounds))
        return
    if args.sweep:
        cases = [(r, 1 << p, 1, 40, 0, rest.OVERFLOW_TURN_BYTES, None if r == "stream" else "kept")
                 for p in range(16, 25) for r in ("stream", "direct")]
        rest.DIRECT_BODY_MIN = 1  # every size of the sweep may go direct
    else:
        turns = [int(t) for t in args.turn.split(",")]
        cases = [
            (r, args.size, int(c), args.rounds, int(g), t, b)
            for g in args.gil.split(",") for c in args.conns.split(",")
            for r in args.carriers.split(",")
            for t in (turns if r in TAKES_TURNS else turns[:1])
            for b in ([None] if r == "stream" else args.buffers.split(","))
        ]
    for case in cases:
        for _ in range(args.repeat):
            print(json.dumps(asyncio.run(_case(*case))), flush=True)


if __name__ == "__main__":
    main()
