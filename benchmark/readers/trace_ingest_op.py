"""The share of the memory roofline that a kernel of the device-ingest road
(``[aggregation] wire_ingest``) reaches: the bytes ``ingest_sizing`` says the
work must move, over the device's HBM bandwidth from ``peaks.json``, over the
device seconds of the executables whose name matches ``match`` (the trace's
``XLA Modules`` line, as ``trace_op`` reads it). ``kind`` = ``unpack``: one
execution is one update's de-interleave and order check. ``kind`` = ``fold``:
the executions are the chunks of the window's flushes, each flush of
``batch_size`` resident rows folded in chunks of eight (12 = 8 + 4); the bytes
of all of them over the seconds of all of them, and where the traced part of
the window holds no whole number of flushes, the mean chunk stands for each.
Both kernels are bound by memory bandwidth, not by arithmetic. Nothing where
no such executable ran in the traced window, and on the CPU stand-in."""

import re

from benchmark.harness.ingest_sizing import chunks, resident_fold_bytes, unpack_bytes


def read(ctx: dict, kind: str, match: str):
    trace = ctx.get("trace")
    if not trace or trace.get("device_stand_in") or ctx.get("peak") is None:
        return None
    count, seconds = 0, 0.0
    for name, stat in trace["modules"].items():
        if re.search(match, name):
            count, seconds = count + stat["count"], seconds + stat["seconds"]
    if count == 0 or seconds <= 0:
        return None
    cfg = ctx["cfg"]
    limbs, n = cfg["n_limbs"], cfg["model_length"]
    if kind == "unpack":
        moved = count * unpack_bytes(cfg["bytes_per_number"], limbs, n)
    else:
        sizes = chunks(cfg["batch_size"])
        moved = count * sum(resident_fold_bytes(k, limbs, n) for k in sizes) / len(sizes)
    return 100.0 * moved / ctx["peak"]["hbm_bytes_per_s"] / seconds
