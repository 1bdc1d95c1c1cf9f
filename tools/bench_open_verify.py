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

Run:  python tools/bench_open_verify.py [--sizes 178899224,255570320]
          [--at-once 1,4,8] [--routes wheel,libcrypto,libsodium] [--repeat 3]
      python tools/bench_open_verify.py --sweep
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


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="178899224,255570320")
    ap.add_argument("--at-once", default="1,4,8")
    ap.add_argument("--routes", default="wheel,libcrypto,libsodium")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--sweep", action="store_true",
                    help="one call beside a spinning main thread, 4 KiB to 8 MiB")
    ap.add_argument("--json", default=None, help="also write the rows here")
    args = ap.parse_args()
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
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
