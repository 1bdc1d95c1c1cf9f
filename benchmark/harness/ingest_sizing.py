"""Arithmetic on shapes for the device-ingest road (``[aggregation]
wire_ingest``): the bytes the chip's unpack of one update and its fold of one
chunk of resident rows have to move. Functions of sizes only, beside
``sizing.py``, whose ``fold_bytes`` counts one PACKED batch of ``batch_size``
rows: on this road the rows are uint32 limb planes already, resident since
each was accepted, and a flush folds them in chunks of eight.
"""

from __future__ import annotations

RESIDENT_CHUNK = 8  # rows a resident fold stacks and folds at once (parallel/aggregator.py)


def unpack_bytes(bpn: int, n_limbs: int, n: int) -> int:
    """HBM bytes the unpack of ONE v1 update must move: read its ``bpn * n``
    wire bytes once, write its ``[n_limbs, n]`` uint32 planes once. The
    order compare and the mask ride on the same pass; the least the work
    can move."""
    return (bpn + 4 * n_limbs) * n


def resident_fold_bytes(k: int, n_limbs: int, n: int) -> int:
    """HBM bytes one fold of a chunk of ``k`` resident planar rows must
    move: read the ``[k, n_limbs, n]`` uint32 chunk once, read and write the
    ``[n_limbs, n]`` uint32 accumulator once each."""
    return (k + 2) * 4 * n_limbs * n


def chunks(rows: int, chunk: int = RESIDENT_CHUNK) -> list[int]:
    """The chunk sizes one flush of ``rows`` resident rows is folded in."""
    return [chunk] * (rows // chunk) + ([rows % chunk] if rows % chunk else [])
