"""Host benchmark of an Update vector's road from the opened message to its
staging slot, by wire: what ``[ingest] wire_format = "packed"`` saves the
coordinator a message (docs/DESIGN.md §21; PERF.md section 6, PR 50).

One vector of ``elements`` group elements at ``bytes`` wire bytes each
(25,557,032 at 7 or 10: the benchmark's 179 and 256 MB; 6,603,710 at 6: the
fan-in cell's), each pass timed alone on an idle host, best of ``--repeat``:

- ``v1``: the interleaved body as every cell but the packed one sends it:
  ``parse`` (``bytes_le_to_limbs``: wire bytes -> ``uint32[n, L]``), ``scan``
  (``all_lt_order`` on the limb rows; the served path runs it twice, in the
  parse and in ``validate_aggregation``), ``slot`` (``pack_wire_slice``: limb
  rows -> the slot's byte planes);
- ``v1-planes``: the same body on a coordinator whose slots are byte planes
  (PR 51): ``planes`` (``wire_to_planes``: wire bytes -> checked byte planes in
  one pass, on pages kept from the last message as the parse takes them from
  its ``PlaneBuffers``; ``planes_fresh``: the same into a new array, every page
  of it touched for the first time), then ``v2``'s ``slot``;
- ``v2``: the byte-planar body: ``scan`` (``planes_lt_order``: the planes
  against the order, top plane down), ``slot`` (``copy_planes``: the planes
  into the slot);
- ``fallback``: what a v2 body cost before PR 50 and still costs where limb
  rows are asked for: ``planar_to_interleaved`` (numpy's transpose), then all
  of ``v1``.

- ``device`` (``--device``; a child that holds the accelerator, the default
  JAX platform as the caller's ``JAX_PLATFORMS`` leaves it: the chip on a chip
  host, and it says which): the road of ``[aggregation] wire_ingest``
  (docs/DESIGN.md §3) for one vector of either wire, each leg until it is
  done: ``h2d`` (the view of the body, at an offset as a message holds it,
  ``device_put`` until the transfer is complete), ``unpack`` (the device's
  de-interleave and order check, or for a v2 body the check alone, dispatch to
  the verdict on the host), and both in a row. The kernel alone, to set
  against the served turn (``ingest.h2d_ms``, ``ingest.unpack_wait_ms``).

Each shape runs twice in children of its own: on one thread of the native
library (``XAYNET_NATIVE_THREADS=1``) and on ``fold_threads()``. GB/s are the
element block's bytes over the pass's time. ``x8`` rows are the pass called
by eight Python threads at once, each on a vector of its own (the ``pet-msg``
workers of a 13-core host under a flood), timed until the last returns: what
a kernel that threads by default costs where many callers meet. No chip, no
jax: host numbers, and quoted as such.

Run:  python tools/bench_wire_routes.py [--shapes 25557032x7,...] [--repeat 3] [--device]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _best(fn, repeat: int) -> float:
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _at_once(fn, callers: int) -> float:
    """Seconds until the last of ``callers`` threads has run ``fn(i)`` once."""
    import threading

    gate = threading.Barrier(callers + 1)

    def run(i):
        gate.wait()
        fn(i)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(callers)]
    for t in threads:
        t.start()
    gate.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    return time.perf_counter() - t0


def _case(elements: int, bpn: int, repeat: int) -> dict:
    import numpy as np

    from xaynet_tpu.core.mask.serialization import planar_to_interleaved
    from xaynet_tpu.ops import limbs as limb_ops

    rng = np.random.default_rng(50)
    order = (1 << (8 * bpn - 1)) + 12345  # its top byte is 0x80
    rows = np.frombuffer(rng.bytes(elements * bpn), dtype=np.uint8).reshape(elements, bpn).copy()
    rows[:, -1] &= 0x7F  # every element under the order; 1 in 128 ties its top byte
    v1 = rows.reshape(-1)
    v2 = np.ascontiguousarray(rows.T)
    n_limb = limb_ops.n_limbs_for_bytes(bpn)
    limbs = limb_ops.bytes_le_to_limbs(v1, elements, bpn, op=None)
    slot = np.zeros((1, bpn, elements + 128), dtype=np.uint8)  # touched: a ring buffer is reused
    assert limb_ops.all_lt_order(limbs, order) and limb_ops.planes_lt_order(v2, order)
    kept = limb_ops.PlaneBuffers(keep=8)
    passes = {
        "v1.parse": lambda: limb_ops.bytes_le_to_limbs(v1, elements, bpn, op=None),
        "v1.scan": lambda: limb_ops.all_lt_order(limbs, order),
        "v1.slot": lambda: limb_ops.pack_wire_slice(limbs[None], 0, elements, bpn, slot),
        "v1p.planes": lambda: limb_ops.wire_to_planes(
            v1, elements, bpn, order, out=kept.take(bpn, elements), n_threads=0),
        "v1p.planes_fresh": lambda: limb_ops.wire_to_planes(v1, elements, bpn, order, n_threads=0),
        "v2.scan": lambda: limb_ops.planes_lt_order(v2, order),
        "v2.slot": lambda: limb_ops.copy_planes(v2, slot[0, :, :elements]),
        "fallback.transpose": lambda: planar_to_interleaved(v2.reshape(-1), elements, bpn),
    }
    ms = {name: 1e3 * _best(fn, repeat) for name, fn in passes.items()}
    limb_ops.copy_planes(v2, slot[0, :, :elements])
    assert np.array_equal(slot[0, :, :elements], v2)
    planes, bad = limb_ops.wire_to_planes(v1, elements, bpn, order)
    assert bad == 0 and np.array_equal(planes, v2)
    del planes
    # eight callers at once, a body each (a copy: no two read the same pages)
    bodies = [v1.copy() for _ in range(8)]
    ms["v1.parse_x8"] = 1e3 * _best(lambda: _at_once(
        lambda i: limb_ops.bytes_le_to_limbs(bodies[i], elements, bpn, op=None), 8), repeat)
    ms["v1p.planes_x8"] = 1e3 * _best(lambda: _at_once(
        lambda i: limb_ops.wire_to_planes(
            bodies[i], elements, bpn, order, out=kept.take(bpn, elements), n_threads=0), 8),
        repeat + 1)  # the first turn touches the kept pages
    ms["v1p.planes_fresh_x8"] = 1e3 * _best(lambda: _at_once(
        lambda i: limb_ops.wire_to_planes(bodies[i], elements, bpn, order, n_threads=0), 8), repeat)
    block = elements * bpn
    return {
        "elements": elements, "bytes": bpn, "limbs": n_limb, "block_mb": block / 1e6,
        "threads": os.environ.get("XAYNET_NATIVE_THREADS", "fold_threads()"),
        "ms": {k: round(v, 2) for k, v in ms.items()},
        "gb_per_s": {k: round(block / 1e6 / v, 2) for k, v in ms.items()},
        "a_message_ms": {
            "v1": round(ms["v1.parse"] + 2 * ms["v1.scan"] + ms["v1.slot"], 1),
            "v1-planes": round(ms["v1p.planes"] + ms["v2.slot"], 1),
            "v2": round(ms["v2.scan"] + ms["v2.slot"], 1),
            "fallback": round(ms["fallback.transpose"] + ms["v1.parse"] + 2 * ms["v1.scan"]
                              + ms["v1.slot"], 1),
        },
    }


_CATALOGUE = {6: ("PRIME", "B0", "M3"), 7: ("INTEGER", "B0", "M6"), 10: ("INTEGER", "B6", "M6")}


def _device_case(elements: int, bpn: int, repeat: int) -> dict:
    """One vector through the device road: the catalogue's mask of ``bpn``
    wire bytes (its own order: the device's program is built for one)."""
    import jax
    import numpy as np

    from xaynet_tpu.core.mask import config as mask_config
    from xaynet_tpu.parallel.aggregator import ShardedAggregator

    group, bound, model = _CATALOGUE[bpn]
    config = mask_config.MaskConfig(
        mask_config.GroupType[group], mask_config.DataType.F32,
        mask_config.BoundType[bound], mask_config.ModelType[model])
    rng = np.random.default_rng(54)
    rows = np.frombuffer(rng.bytes(elements * bpn), dtype=np.uint8).reshape(elements, bpn).copy()
    rows[:, -1] %= config.order.to_bytes(bpn, "little")[-1]  # every element under the order
    agg = ShardedAggregator(config, elements, kernel="xla")
    ms = {}
    for wire, block in (("v1", rows.reshape(-1)), ("v2", np.ascontiguousarray(rows.T).reshape(-1))):
        body = bytearray(64) + bytearray(block.tobytes())  # the block lies at an offset
        view = np.frombuffer(body, dtype=np.uint8, count=elements * bpn, offset=64)
        if wire == "v1":
            put, check = agg.put_wire_update, agg.unpack_put_update
        else:
            view, put, check = view.reshape(bpn, elements), agg.put_planar_update, agg.check_put_update
        assert check(put(view)) is not None  # compiled, and the verdict is "accepted"
        staged = put(view)
        ms[f"{wire}.h2d"] = 1e3 * _best(lambda: put(view), repeat)
        ms[f"{wire}.unpack"] = 1e3 * _best(lambda: check(staged), repeat)
        ms[f"{wire}.total"] = 1e3 * _best(lambda: check(put(view)), repeat)
        del staged
    block_bytes = elements * bpn
    device = jax.devices()[0]
    return {
        "route": "device", "elements": elements, "bytes": bpn, "limbs": agg.n_limbs,
        "block_mb": block_bytes / 1e6, "platform": device.platform, "device_kind": device.device_kind,
        "devices": agg.mesh.devices.size,
        "ms": {k: round(v, 2) for k, v in ms.items()},
        "gb_per_s": {k: round(block_bytes / 1e6 / v, 2) for k, v in ms.items()},
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="25557032x7,25557032x10,6603710x6",
                    help="elements x wire bytes, comma-separated")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--device", action="store_true",
                    help="also the device road of wire ingest, on the default JAX platform")
    ap.add_argument("--case", default=None, help=argparse.SUPPRESS)  # a child's one shape
    ap.add_argument("--device-case", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if args.case or args.device_case:
        elements, bpn = (int(x) for x in (args.case or args.device_case).split("x"))
        case = _case if args.case else _device_case
        print(json.dumps(case(elements, bpn, args.repeat)))
        return
    for shape in args.shapes.split(","):
        if args.device:
            # one child a shape: the accelerator has one owner at a time
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--device-case", shape,
                 "--repeat", str(args.repeat)], capture_output=True, text=True)
            print(done.stdout.strip().splitlines()[-1] if done.returncode == 0
                  else json.dumps({"shape": shape, "route": "device", "error": done.stderr[-400:]}),
                  flush=True)
        for threads in ("1", None):
            env = {k: v for k, v in os.environ.items() if k != "XAYNET_NATIVE_THREADS"}
            env["JAX_PLATFORMS"] = "cpu"
            if threads:
                env["XAYNET_NATIVE_THREADS"] = threads
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--case", shape,
                 "--repeat", str(args.repeat)], env=env, capture_output=True, text=True)
            if done.returncode != 0:
                print(json.dumps({"shape": shape, "error": done.stderr[-400:]}))
                continue
            print(done.stdout.strip().splitlines()[-1], flush=True)


if __name__ == "__main__":
    main()
