"""Which wire an Update's vector came on, and the road it took to its slot
(docs/DESIGN.md §21).

``[ingest] wire_format = "packed"`` advertises wire v2: a participant sends
its masked vector as ``bytes_per_number`` byte planes, the layout of a
packed staging slot. The message's own flag decides the road, one message at
a time, so a round may mix both. Counted once an update, where
``StagedAggregator.stage`` chooses (``server/aggregation.py``):

- ``xaynet_update_wire_bytes_total{wire, route}``: the element-block bytes
  of each staged Update. ``wire`` = ``packed`` (the v2 flag) | ``legacy``.
  ``route`` = ``copy`` (checked planes copied into the slot, no limb row: a
  v2 body's own, or the ones the parse wrote from a v1 body where the slots
  are byte planes) | ``relayout`` (through uint32 limb rows and the plane
  pack; for a v2 body the transposing fallback before them) | ``device``
  (wire ingest: unpacked and checked on the accelerator).
- wire ingest, where the accelerator parses and checks (``route="device"``;
  docs/DESIGN.md §3): ``xaynet_ingest_device_bytes_total``, the element-block
  bytes put to the device as they lay in their message (over the seconds of
  the ``ingest_h2d`` stage: the link's rate); ``xaynet_ingest_verdicts_total
  {outcome}``, the device's verdicts (``accepted`` | ``rejected``: an element
  at or over the order, found before the seed-dict insert); and
  ``xaynet_ingest_resident_rows_max``, the most accepted rows a flush of the
  round found resident in device memory (what ``batch_size`` bounds there).
- the same by bodies, for the log line a round (``since_last``, printed where
  the first Sum2 message arrives) and for ``/healthz`` ``device.fold.wire``
  (``last_batch``: the fold batch closed last).
"""

from __future__ import annotations

import threading

from .registry import get_registry

BYTES = get_registry().counter(
    "xaynet_update_wire_bytes_total",
    "Element-block bytes of staged Update vectors, by the wire they came on "
    "(packed = v2 byte-planar, legacy = v1 interleaved) and the route to the "
    "staging slot: copy = checked planes copied in, no limb row; relayout = through "
    "limb rows and the plane pack; device = wire ingest (telemetry/wire.py).",
    ("wire", "route"),
)

DEVICE_BYTES = get_registry().counter(
    "xaynet_ingest_device_bytes_total",
    "Element-block bytes of Update vectors put to the accelerator as they lay "
    "in their message (wire ingest: a view of the body, one transfer, no host "
    "parse, scan or copy).",
)
VERDICTS = get_registry().counter(
    "xaynet_ingest_verdicts_total",
    "Verdicts of the accelerator's element check under wire ingest, one an "
    "update, before its seed-dict insert: accepted | rejected (an element at "
    "or over the group order).",
    ("outcome",),
)
RESIDENT_ROWS_MAX = get_registry().gauge(
    "xaynet_ingest_resident_rows_max",
    "The most accepted update rows that a flush of the current round found "
    "resident in device memory (wire ingest; batch_size bounds it).",
)

_lock = threading.Lock()
_ZERO = {"packed": 0, "legacy": 0, "copied": 0}
_batch = dict(_ZERO)  # the fold batch filling now  # guarded-by: _lock
_last_batch: dict | None = None  # guarded-by: _lock
_since = dict(_ZERO)  # since the last log line  # guarded-by: _lock


def staged(wire: str, route: str, nbytes: int) -> None:
    """One Update staged: ``nbytes`` of element block on ``wire`` by ``route``."""
    BYTES.labels(wire=wire, route=route).inc(nbytes)
    with _lock:
        for tally in (_batch, _since):
            tally[wire] += 1
            if route == "copy":
                tally["copied"] += 1


def device_verdict(nbytes: int, accepted: bool) -> None:
    """The accelerator has checked one Update whose ``nbytes`` of element
    block were put to it."""
    DEVICE_BYTES.inc(nbytes)
    VERDICTS.labels(outcome="accepted" if accepted else "rejected").inc()


def batch_closed() -> None:
    """The staged updates were handed to the fold: they are the last batch."""
    global _batch, _last_batch
    with _lock:
        _last_batch, _batch = _batch, dict(_ZERO)


def last_batch() -> dict | None:
    """``{packed, legacy, copied}`` bodies of the fold batch closed last
    (None before the first)."""
    with _lock:
        return dict(_last_batch) if _last_batch is not None else None


def since_last() -> dict:
    """The same since the previous call: what a round's log line says."""
    global _since
    with _lock:
        out, _since = _since, dict(_ZERO)
    return out
