"""Host benchmark of the REST body read: StreamReader against direct.

One process serves ``RestServer`` with a handler that drops every message;
a second process sends ``--conns`` bodies of ``--size`` bytes at once, each
over its own connection, exactly as ``sdk/client.py::_exchange`` does
(``writer.write(head + body); await writer.drain()``). Printed per case:
seconds from headers parsed to body in memory (what ``rest.read_body``
brackets), mean and max over the bodies, and the sender's wall. ``stream``
raises the threshold above every body so that the StreamReader carries
them; ``direct`` leaves ``rest.py`` as it ships. ``--gil N`` runs N threads
of pure Python beside the server's loop, which is what the served round's
workers do to it. No chip, no jax: a CPU number, and quoted as one.

Run:  python tools/bench_body_read.py [--size 178899224] [--conns 1,8]
          [--routes stream,direct] [--gil 0,3] [--repeat 1]
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


class _Drop:
    async def handle_message(self, body) -> None:
        return None


class _TimedServer(rest.RestServer):
    def __init__(self):
        super().__init__(fetcher=None, handler=_Drop(), registry=MetricsRegistry())
        self.reads: list[float] = []

    async def _read_body(self, reader, writer, length):
        t0 = time.monotonic()
        body = await super()._read_body(reader, writer, length)
        self.reads.append(time.monotonic() - t0)
        return body


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


async def _case(route: str, size: int, conns: int, rounds: int, gil: int) -> dict:
    shipped = rest.DIRECT_BODY_MIN
    rest.DIRECT_BODY_MIN = rest.MAX_BODY + 1 if route == "stream" else shipped
    server = _TimedServer()
    _, port = await server.start("127.0.0.1", 0)
    stop = threading.Event()
    spinners = [threading.Thread(target=_spin, args=(stop,), daemon=True) for _ in range(gil)]
    for t in spinners:
        t.start()
    try:
        sender = await asyncio.create_subprocess_exec(
            sys.executable, os.path.abspath(__file__), "--send", str(port),
            "--size", str(size), "--conns", str(conns), "--rounds", str(rounds),
            stdout=subprocess.PIPE,
        )
        out, _ = await sender.communicate()
        assert sender.returncode == 0, sender.returncode
    finally:
        stop.set()
        await server.stop()
        rest.DIRECT_BODY_MIN = shipped
    reads = server.reads
    direct = server.registry.sample_value("xaynet_rest_body_bytes_total", {"route": "direct"}) or 0
    return {
        "route": route, "size": size, "conns": conns, "rounds": rounds, "gil_threads": gil,
        "bodies": len(reads), "read_mean_s": sum(reads) / len(reads), "read_max_s": max(reads),
        "sender_s": json.loads(out.decode().strip().splitlines()[-1])["sender_s"],
        "direct_bytes": int(direct),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--send", type=int, help=argparse.SUPPRESS)  # the sender child: a port
    ap.add_argument("--size", type=int, default=178_899_224)
    ap.add_argument("--conns", default="1,8")
    ap.add_argument("--rounds", type=int, default=1, help="bodies a connection sends in turn")
    ap.add_argument("--routes", default="stream,direct")
    ap.add_argument("--gil", default="0")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--sweep", action="store_true",
                    help="one connection, 64 KiB to 16 MiB: where direct overtakes stream")
    args = ap.parse_args()
    if args.send is not None:
        asyncio.run(_send(args.send, args.size, int(args.conns), args.rounds))
        return
    if args.sweep:
        cases = [(r, 1 << p, 1, 40, 0) for p in range(16, 25) for r in ("stream", "direct")]
        rest.DIRECT_BODY_MIN = 1  # every size of the sweep may go direct
    else:
        cases = [
            (r, args.size, int(c), args.rounds, int(g))
            for g in args.gil.split(",") for c in args.conns.split(",")
            for r in args.routes.split(",")
        ]
    for case in cases:
        for _ in range(args.repeat):
            print(json.dumps(asyncio.run(_case(*case))), flush=True)


if __name__ == "__main__":
    main()
