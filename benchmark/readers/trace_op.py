"""Device time of the executables whose name matches ``match``, from the
trace's ``XLA Modules`` line, per execution of those matching ``per`` (one
flush runs one program on the XLA route and two, an unpack and a fold, on
the Pallas route; ``per`` names the one that runs once per flush):
milliseconds, or with
``roofline`` the share of the memory roofline a fold reaches: the bytes
``fold_bytes`` says one fold of the cell's batch must move, over the
device's HBM bandwidth from ``peaks.json``, over the mean device seconds of
one execution. The fold is bound by memory bandwidth, not by arithmetic."""

import re

from benchmark.harness.sizing import fold_bytes


def read(ctx: dict, match: str, per: str | None = None, roofline: bool = False):
    trace = ctx.get("trace")
    if not trace or trace.get("device_stand_in"):
        return None
    count, seconds = 0, 0.0
    for name, stat in trace["modules"].items():
        if re.search(match, name):
            seconds += stat["seconds"]
        if re.search(per or match, name):
            count += stat["count"]
    if count == 0 or seconds <= 0:
        return None
    per_call = seconds / count
    if not roofline:
        return 1e3 * per_call
    if ctx.get("peak") is None:
        return None
    cfg = ctx["cfg"]
    floor = fold_bytes(cfg["batch_size"], cfg["bytes_per_number"], cfg["n_limbs"],
                       cfg["model_length"]) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor / per_call
