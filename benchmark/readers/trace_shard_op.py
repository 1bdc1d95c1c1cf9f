"""The share of the memory roofline that ONE SHARD's fold reaches where the
model is sharded over the chips and every chip folds its own columns
(DESIGN section 12): the bytes ``shard_fold_bytes`` says a fold of the
cell's batch must move on one chip, at the shard length the coordinator's
``/healthz`` reports (``device.fold.shards`` and ``shard_length``), over the
chip's HBM bandwidth from ``peaks.json``, over the mean device seconds of one
such execution (``trace_op``'s: the trace's ``XLA Modules`` lines of every
device, a chip's execution counted once). Nothing where the program does
not say how it was sharded, where no fold ran in the traced window, and on
the CPU stand-in."""

from benchmark.harness.shard_sizing import shard_fold_bytes
from benchmark.readers import trace_op


def read(ctx: dict, match: str, per: str | None = None):
    fold = ((ctx["health"].get("end") or {}).get("device") or {}).get("fold") or {}
    shards, shard_len = fold.get("shards"), fold.get("shard_length")
    if not shards or not shard_len or ctx.get("peak") is None:
        return None
    per_call_ms = trace_op.read(ctx, match, per)
    if per_call_ms is None:
        return None
    cfg = ctx["cfg"]
    floor_s = shard_fold_bytes(cfg["batch_size"], cfg["bytes_per_number"], cfg["n_limbs"],
                               shard_len) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (1e-3 * per_call_ms)
