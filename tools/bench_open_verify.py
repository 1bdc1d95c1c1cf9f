"""Host benchmark of the sealed-box open and the Ed25519 verify, by library.

The two passes a ``pet-msg`` worker makes over a message's body, timed
outside a round: ChaCha20-Poly1305-IETF open of a box and Ed25519 verify of
a signature over the same number of bytes, through

- ``wheel``: the ``cryptography`` wheel, which holds the interpreter lock
  for the call (``decrypt`` also allocates its output);
- ``libcrypto``: ``core/crypto/unlocked.py``, the system's ``libcrypto.so.3``
  through ``ctypes``, the open in place; what the coordinator runs for long
  inputs since PR 32;
- ``libsodium``: ``libsodium.so.23`` through ``ctypes`` (bound here only),
  the open in place; the candidate that was not chosen.

A case is ``--at-once`` threads making one call each on a buffer of their
own, started together, while the main thread spins in pure Python. Printed
a case: seconds a call with nothing else running (``alone``), seconds a
call beside the main thread (mean over threads and repeats), the wall of all
of them, and the **main thread's longest stall**: how long its loop could
not run while the calls were made, which is what the event loop, the other
workers and the state machine feel. ``--sweep`` is one thread making 100
calls beside the spinning main thread at 4 KiB to 8 MiB: where the foreign
call starts to pay (``unlocked.UNLOCKED_MIN``). No chip, no jax: a host number, and quoted
as one (PERF.md section 6, PR 32).

``--side-by-side`` is the worker stage whole: ``--messages`` sealed Sum2
messages of ``--elements`` elements at ``--bytes`` wire bytes each, all
handed at once to ``PetMessageHandler._decrypt_parse_one`` on a ``pet-msg``
pool of ``--workers`` threads, in both orders: ``beside`` (the shipped
route: a long message's signature pass on a ``pet-verify`` thread while the
worker parses) and ``serial`` (verify, then parse, on the worker: the order
until PR 41, made here by running the pass where it is submitted). Printed a
case: the wall until the last message is parsed, and the mean of each stage
from ``xaynet_message_pipeline_seconds`` (PERF.md section 6, PR 41).

Run:  python tools/bench_open_verify.py [--sizes 178899224,255570320]
          [--at-once 1,4,8] [--routes wheel,libcrypto,libsodium] [--repeat 3]
      python tools/bench_open_verify.py --sweep
      python tools/bench_open_verify.py --side-by-side [--messages 8]
          [--workers 4,8,12] [--bytes 7,10] [--elements 25557032] [--repeat 3]
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cryptography.hazmat.primitives.asymmetric.ed25519 import (  # noqa: E402
    Ed25519PrivateKey,
    Ed25519PublicKey,
)
from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305  # noqa: E402

from xaynet_tpu.core.crypto import unlocked  # noqa: E402

_KEY, _NONCE = bytes(range(32)), b"\x00" * 12


def _wheel():
    def open_(box: bytearray) -> None:
        ChaCha20Poly1305(_KEY).decrypt(_NONCE, box, None)

    def verify(public: bytes, signature: bytes, data) -> bool:
        try:
            Ed25519PublicKey.from_public_bytes(public).verify(signature, data)
            return True
        except Exception:
            return False

    return open_, verify


def _libcrypto():
    if unlocked.load() is None:
        return None

    def open_(box: bytearray) -> None:
        assert unlocked.open_into(_KEY, _NONCE, box, box)

    return open_, unlocked.ed25519_verify


def _libsodium():
    try:
        lib = ctypes.CDLL("libsodium.so.23")
    except OSError:
        return None
    vp, ull = ctypes.c_void_p, ctypes.c_ulonglong
    lib.crypto_aead_chacha20poly1305_ietf_decrypt.argtypes = [
        vp, vp, vp, vp, ull, vp, ull, ctypes.c_char_p, ctypes.c_char_p]
    lib.crypto_sign_verify_detached.argtypes = [ctypes.c_char_p, vp, ull, ctypes.c_char_p]
    assert lib.sodium_init() >= 0

    def open_(box: bytearray) -> None:
        at = ctypes.addressof(ctypes.c_uint8.from_buffer(box))
        assert lib.crypto_aead_chacha20poly1305_ietf_decrypt(
            at, None, None, at, len(box), None, 0, _NONCE, _KEY) == 0

    def verify(public: bytes, signature: bytes, data) -> bool:
        at = ctypes.addressof(ctypes.c_uint8.from_buffer(data))
        return lib.crypto_sign_verify_detached(signature, at, len(data), public) == 0

    return open_, verify


ROUTES = {"wheel": _wheel, "libcrypto": _libcrypto, "libsodium": _libsodium}


def _timed_beside_main(calls: list) -> tuple[list[float], float, float]:
    """Run every call on a thread of its own, all released together, while
    this (the main) thread spins in pure Python. Returns each call's
    seconds, the wall of all of them, and the main thread's longest stall."""
    seconds = [0.0] * len(calls)
    gate = threading.Barrier(len(calls) + 1)

    def run(i: int) -> None:
        gate.wait()
        t0 = time.perf_counter()
        calls[i]()
        seconds[i] = time.perf_counter() - t0

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(calls))]
    for t in threads:
        t.start()
    # from before the gate opens: a thread that takes the lock at once and
    # keeps it holds this one inside ``wait()``
    start = last = time.perf_counter()
    gate.wait()
    stall = 0.0
    while any(t.is_alive() for t in threads):
        now = time.perf_counter()
        stall = max(stall, now - last)
        last = now
    wall = time.perf_counter() - start
    for t in threads:
        t.join()
    return seconds, wall, stall


def _case(route: str, op: str, size: int, at_once: int, repeat: int, calls_each: int = 1) -> dict:
    open_, verify = ROUTES[route]()
    sk = Ed25519PrivateKey.generate()
    public = sk.public_key().public_bytes_raw()
    plain = (os.urandom(min(size, 1 << 20)) * ((size >> 20) + 1))[:size]
    sealed = ChaCha20Poly1305(_KEY).encrypt(_NONCE, plain, None)
    signature = sk.sign(plain)

    def make_calls() -> list:
        if op == "open":  # an open in place eats its box: a fresh copy a call
            boxes = [[bytearray(sealed) for _ in range(calls_each)] for _ in range(at_once)]
            return [lambda b=b: [open_(x) for x in b] for b in boxes]
        data = [bytearray(plain) for _ in range(at_once)]
        return [
            lambda d=d: [verify(public, signature, d) or sys.exit("bad verdict")
                         for _ in range(calls_each)]
            for d in data
        ]

    # alone: this thread makes the calls itself, nothing else runs
    alone = []
    for call in make_calls():
        t0 = time.perf_counter()
        call()
        alone.append((time.perf_counter() - t0) / calls_each)
    per_call, walls, stalls = [], [], []
    for _ in range(repeat):
        seconds, wall, stall = _timed_beside_main(make_calls())
        per_call += [s / calls_each for s in seconds]
        walls.append(wall)
        stalls.append(stall)
    return {
        "route": route, "op": op, "bytes": size, "at_once": at_once,
        "alone_call_s": min(alone), "call_s": sum(per_call) / len(per_call),
        "call_min_s": min(per_call), "wall_s": min(walls), "main_stall_s": max(stalls),
    }


class _WhereSubmitted:
    """Stands in for the ``pet-verify`` executor: the pass runs on the
    thread that submits it, before the parse: the serial order."""

    def submit(self, fn, *args):
        from concurrent.futures import Future

        done: Future = Future()
        try:
            done.set_result(fn(*args))
        except Exception as err:  # the caller reads it from the future, as from a thread's
            done.set_exception(err)
        return done

    def shutdown(self, wait: bool = True) -> None:
        pass


def _sealed_sum2(elements: int, bpn: int):
    """(the coordinator's keys, one sealed Sum2 message of that size)."""
    from bench_compose import _message  # tools/ is this script's directory

    from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
    from xaynet_tpu.utils import native

    native.load()  # built on first use: not a message's cost
    message, signer = _message(elements, bpn)
    keys = EncryptKeyPair.derive_from_seed(bytes(range(32, 64)))
    message.coordinator_pk = keys.public.as_bytes()
    return keys, keys.public.encrypt(message.to_bytes(signer.secret))


def _side_by_side(keys, box: bytes, messages: int, n_workers: int, order: str,
                  repeat: int) -> dict:
    from concurrent.futures import wait

    from xaynet_tpu.server import stages
    from xaynet_tpu.server.events import PhaseName
    from xaynet_tpu.server.services import MessageWorkers, PetMessageHandler

    workers = MessageWorkers(n_workers)
    if order == "serial":
        workers.verdicts = _WhereSubmitted()
    handler = PetMessageHandler(events=None, request_tx=None, workers=workers)
    labels = ("open", "verify", "parse", "verify_beside")

    def seconds() -> dict:
        return {key[0]: (child.sum, child.count)
                for key, child in stages.SECONDS.children() if key[1] == "sum2"}

    walls, means = [], {label: [] for label in labels}
    try:
        for _ in range(repeat):
            boxes = [bytearray(box) for _ in range(messages)]  # opened in place: a copy each
            before, t0 = seconds(), time.perf_counter()
            parsed = [workers.pool.submit(handler._decrypt_parse_one, b, keys, PhaseName.SUM2)
                      for b in boxes]
            wait(parsed)
            walls.append(time.perf_counter() - t0)
            for future in parsed:
                future.result()  # a drop would be the tool's fault: raise it
            after = seconds()
            for label in labels:
                s1, n1 = after.get(label, (0.0, 0))
                s0, n0 = before.get(label, (0.0, 0))
                if n1 > n0:
                    means[label].append((s1 - s0) / (n1 - n0))
            del parsed, boxes
    finally:
        workers.close()
    row = {"body_bytes": len(box), "messages": messages,
           "workers": n_workers, "order": order, "wall_s": min(walls),
           "wall_median_s": sorted(walls)[len(walls) // 2]}
    row.update({f"{label}_ms": 1e3 * sum(v) / len(v) for label, v in means.items() if v})
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="178899224,255570320")
    ap.add_argument("--at-once", default="1,4,8")
    ap.add_argument("--routes", default="wheel,libcrypto,libsodium")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--sweep", action="store_true",
                    help="one call beside a spinning main thread, 4 KiB to 8 MiB")
    ap.add_argument("--side-by-side", action="store_true",
                    help="whole messages through _decrypt_parse_one on a pool, both orders")
    ap.add_argument("--messages", type=int, default=8)
    ap.add_argument("--workers", default="4,8,12")
    ap.add_argument("--bytes", default="7,10", help="wire bytes an element (--side-by-side)")
    ap.add_argument("--elements", type=int, default=25_557_032)
    ap.add_argument("--json", default=None, help="also write the rows here")
    args = ap.parse_args()
    if args.side_by_side:
        print(f"host CPUs this process may run on: {len(os.sched_getaffinity(0))}; "
              f"libcrypto loads: {unlocked.load() is not None}", flush=True)
        rows = []
        for bpn in (int(b) for b in args.bytes.split(",")):
            keys, box = _sealed_sum2(args.elements, bpn)
            for n_workers in (int(w) for w in args.workers.split(",")):
                for order in ("serial", "beside"):
                    row = _side_by_side(keys, box, args.messages, n_workers, order, args.repeat)
                    rows.append(row)
                    print(f"{row['body_bytes']:>10} B x{row['messages']}  W={n_workers:<2} "
                          f"{order:<6} wall {row['wall_s'] * 1e3:8.1f} ms "
                          f"(median {row['wall_median_s'] * 1e3:8.1f})  "
                          + "  ".join(f"{label} {row[label + '_ms']:7.1f}"
                                      for label in ("open", "verify", "parse", "verify_beside")
                                      if label + "_ms" in row), flush=True)
        _write_json(args.json, rows)
        return
    routes = [r for r in args.routes.split(",") if ROUTES[r]() is not None]
    print(f"host CPUs this process may run on: {len(os.sched_getaffinity(0))}; "
          f"routes that load: {', '.join(routes)}", flush=True)
    if args.sweep:
        # a call beside the spinning main thread may wait a switch interval
        # (5 ms) for the lock: 100 calls a case bound the sweep's time
        cases = [(r, op, 1 << p, 1, 1, 100 if p < 22 else 20)
                 for p in range(12, 24) for op in ("open", "verify") for r in routes]
    else:
        cases = [(r, op, int(size), int(n), args.repeat, 1)
                 for size in args.sizes.split(",") for n in args.at_once.split(",")
                 for op in ("open", "verify") for r in routes]
    rows = []
    for case in cases:
        row = _case(*case)
        rows.append(row)
        print(f"{row['bytes']:>10} B  {row['op']:<6} {row['route']:<9} x{row['at_once']}  "
              f"alone {row['alone_call_s'] * 1e3:9.3f} ms  beside main: call "
              f"{row['call_s'] * 1e3:9.3f} ms (min {row['call_min_s'] * 1e3:9.3f})  "
              f"wall {row['wall_s'] * 1e3:9.3f} ms  main stall {row['main_stall_s'] * 1e3:9.3f} ms",
              flush=True)
    _write_json(args.json, rows)


def _write_json(path, rows: list) -> None:
    if path:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
