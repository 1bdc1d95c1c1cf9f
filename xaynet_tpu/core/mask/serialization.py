"""Wire serialization of mask objects.

Layouts (reference: rust/xaynet-core/src/mask/object/serialization/):

- ``MaskVect``: config(4) ‖ count(u32 BE) ‖ count fixed-width little-endian
  integers of ``bytes_per_number`` each (vect.rs:24-80);
- ``MaskUnit``: config(4) ‖ one fixed-width little-endian integer (unit.rs);
- ``MaskObject``: vect ‖ unit (mod.rs).

The element block converts directly between wire bytes and the uint32 limb
tensors (a vectorized numpy pad/view — no per-element loop), which is what
makes parsing a 25M-element update a memcpy-class operation.

Wire format v2 (packed planar, docs/DESIGN.md §21): the top bit of the
count word (``WIRE_PLANAR_FLAG``) marks the element block as BYTE-PLANAR —
``bytes_per_number`` contiguous planes of ``count`` bytes each, plane ``b``
holding byte ``b`` of every element — instead of the v1 interleaved
per-element layout. Same byte budget, but the planar block is already the
packed staging layout: the eager parse keeps it as a view, checks every
element against the order on the planes, and a packed-staging coordinator
copies it into the staging slot; a device-ingest coordinator uploads it
without the byte-gather relayout. Neither materializes uint32 limbs.
Element counts are bounded far below 2^31 (``MAX_BODY`` caps the message),
so the flag bit can never collide with a real count.

A v1 block whose consumer's slots are byte planes (``planes``, the buffers
to write them in: a packed-staging device coordinator's Update vectors) is
relaid ONCE, here:
one native pass writes the planes from the interleaved bytes and compares
every element with the order, and the vector is the checked plane object a
v2 body gives, flagged as having come on the legacy wire.
"""

from __future__ import annotations

import struct

import numpy as np

from ...ops import limbs as limb_ops
from ...telemetry import codec
from ...utils import native
from .config import MASK_CONFIG_LENGTH, MaskConfig
from .object import MaskObject, MaskUnit, MaskVect


class DecodeError(ValueError):
    """Malformed wire bytes."""


# config(4) + count(u32 BE): everything before the element block
VECT_HEADER_LENGTH = MASK_CONFIG_LENGTH + 4

# top bit of the count word: element block is byte-planar (wire format v2)
WIRE_PLANAR_FLAG = 0x8000_0000


def _split_count_word(word: int) -> tuple[int, bool]:
    """(element count, planar?) from the wire count word."""
    return word & ~WIRE_PLANAR_FLAG, bool(word & WIRE_PLANAR_FLAG)


def planar_to_interleaved(block: np.ndarray, count: int, bpn: int) -> np.ndarray:
    """Byte-planar element block ``uint8[bpn * count]`` -> the v1 interleaved
    layout: one materializing numpy transpose, counted ``op="parse",
    route="generic"``. The FALLBACK of a v2 vector whose limb rows someone
    asks for (``LazyWireMaskVect.data``: the host aggregator, unpacked
    staging, the exact path, tests); no parse calls it, and a packed-staging
    device coordinator never does: it copies the planes (docs/DESIGN.md §21)."""
    codec.count("parse", False, count)
    return np.ascontiguousarray(
        np.asarray(block).reshape(bpn, count).T
    ).reshape(-1)


def serialized_vect_length(config: MaskConfig, count: int) -> int:
    return VECT_HEADER_LENGTH + count * config.bytes_per_number


def vect_element_block(wire: bytes) -> np.ndarray:
    """The raw fixed-width element block of a serialized MaskVect as a
    zero-copy uint8 view — the device-ingest input
    (``ShardedAggregator.add_wire_batch``).

    Validates the header and the exact framed length like
    ``parse_mask_vect`` does (a truncated buffer or a full MaskObject
    wire — vect ‖ unit — raises ``DecodeError`` here, at the parse
    boundary, not as an opaque shape error downstream)."""
    if len(wire) < VECT_HEADER_LENGTH:
        raise DecodeError("mask vector buffer too short")
    try:
        config = MaskConfig.from_bytes(wire[:MASK_CONFIG_LENGTH])
    except ValueError as e:
        raise DecodeError(f"invalid mask config: {e}") from e
    (word,) = struct.unpack_from(">I", wire, MASK_CONFIG_LENGTH)
    count, planar = _split_count_word(word)
    if planar:
        raise DecodeError("planar (v2) element block where interleaved expected")
    if len(wire) != VECT_HEADER_LENGTH + count * config.bytes_per_number:
        raise DecodeError("wire length does not match the framed element count")
    return np.frombuffer(wire, dtype=np.uint8)[VECT_HEADER_LENGTH:]


def compose_buffer(length: int, write) -> bytearray:
    """One buffer of ``length`` bytes (no zero fill) filled by
    ``write(buf, 0) -> end``, which has to end at ``length``: a serialiser
    that disagrees with its ``serialized_length()`` would leave bytes of the
    buffer unwritten."""
    buf = native.uninitialised_bytearray(None, length)
    # a view cannot be resized by a slice assignment of another length
    if write(memoryview(buf), 0) != length:
        raise ValueError("serialized length disagrees with the serialiser")
    return buf


def compose_bytes(length: int, write) -> bytes:
    """``to_bytes`` in terms of the write-into form."""
    return bytes(compose_buffer(length, write))


def write_mask_vect(vect: MaskVect, buf, offset: int, planar: bool = False) -> int:
    """Serialise ``vect`` into the writable buffer ``buf`` at ``offset``;
    returns the offset behind it. The element block goes from the limbs to
    its place in ``buf`` in one pass (``limbs_into_wire``)."""
    bpn, count = vect.config.bytes_per_number, len(vect)
    start = offset + VECT_HEADER_LENGTH
    end = start + count * bpn
    buf[offset : offset + MASK_CONFIG_LENGTH] = vect.config.to_bytes()
    struct.pack_into(
        ">I", buf, offset + MASK_CONFIG_LENGTH, count | WIRE_PLANAR_FLAG if planar else count
    )
    block = np.frombuffer(buf, dtype=np.uint8, count=count * bpn, offset=start)
    if planar and getattr(vect, "planar", False) and not vect.materialized:
        # a planar block never touched (a v2 body's, or the planes the v1
        # parse wrote) asked for as planes: re-emit the block
        block[...] = np.asarray(vect.wire_block)
    else:
        limb_ops.limbs_into_wire(vect.data, bpn, block, planar=planar)
    return end


def serialize_mask_vect(vect: MaskVect, planar: bool = False) -> bytes:
    return compose_bytes(
        serialized_vect_length(vect.config, len(vect)),
        lambda buf, offset: write_mask_vect(vect, buf, offset, planar=planar),
    )


def parse_mask_vect(
    data: bytes,
    offset: int = 0,
    lazy: bool = False,
    planes: "limb_ops.PlaneBuffers | None" = None,
) -> tuple[MaskVect, int]:
    """Parse a MaskVect at ``offset``; returns (vect, bytes consumed).

    ``lazy=True`` (device-ingest coordinators) skips the host limb
    materialization AND the host element-validity check, returning a
    ``LazyWireMaskVect`` that carries the raw element block; element
    validity then happens on device in ``validate_aggregation`` (or on
    first host materialization), one stage later than the eager parse's
    ``DecodeError``.

    A v2 (byte-planar) block under ``lazy=False`` is also returned as a
    ``LazyWireMaskVect`` over the body's bytes, but a CHECKED one: its
    planes are scanned against the order here, an element out of the group
    raises the same ``DecodeError`` a v1 body's would, and no interleaved
    block or limb row is made unless a caller asks for ``.data``.

    ``planes`` (the consumer's slots are byte planes: a packed-staging
    device coordinator's Update vectors; the ``PlaneBuffers`` to take the
    pages from) gives a v1 block the same shape in one pass: checked planes
    of the object's own, ``packed_wire`` false, the same ``DecodeError`` for
    the same bodies. ``lazy`` goes first.
    """
    if len(data) - offset < MASK_CONFIG_LENGTH + 4:
        raise DecodeError("mask vector buffer too short")
    try:
        config = MaskConfig.from_bytes(data[offset : offset + MASK_CONFIG_LENGTH])
    except ValueError as e:
        raise DecodeError(f"invalid mask config: {e}") from e
    (word,) = struct.unpack_from(">I", data, offset + MASK_CONFIG_LENGTH)
    count, planar = _split_count_word(word)
    bpn = config.bytes_per_number
    start = offset + MASK_CONFIG_LENGTH + 4
    end = start + count * bpn
    if len(data) < end:
        raise DecodeError("mask vector data truncated")
    raw = np.frombuffer(data, dtype=np.uint8, count=count * bpn, offset=start)
    if lazy or planar:
        return _wire_vect(config, raw, count, planar, checked=not lazy), end - offset
    if planes is not None:
        block, bad = limb_ops.wire_to_planes(
            raw, count, bpn, config.order, out=planes.take(bpn, count)
        )
        return _relaid_vect(config, block, count, bad), end - offset
    limbs = limb_ops.bytes_le_to_limbs(raw, count, bpn)
    vect = MaskVect(config, limbs)
    if not vect.is_valid():
        raise DecodeError("mask vector element >= group order")
    return vect, end - offset


def _wire_vect(config: MaskConfig, raw: np.ndarray, count: int, planar: bool, checked: bool):
    """The vector as a view of its wire block. ``checked`` (the eager parse
    of a v2 block): the planes are scanned against the order first."""
    from .object import LazyWireMaskVect

    vect = LazyWireMaskVect(config, raw, count, planar=planar)
    if checked and not vect.check_planes():
        raise DecodeError("mask vector element >= group order")
    return vect


def _relaid_vect(config: MaskConfig, block: np.ndarray, count: int, bad: int):
    """The vector of a v1 block over the planes ``wire_to_planes`` wrote
    from it, ``bad`` being that pass's count of elements out of the group."""
    from .object import LazyWireMaskVect

    if bad:
        raise DecodeError("mask vector element >= group order")
    return LazyWireMaskVect(
        config, block.reshape(-1), count, planar=True, packed_wire=False, checked=True
    )


def write_mask_unit(unit: MaskUnit, buf, offset: int) -> int:
    bpn = unit.config.bytes_per_number
    start = offset + MASK_CONFIG_LENGTH
    buf[offset:start] = unit.config.to_bytes()
    limb_ops.limbs_into_wire(
        unit.data[None, :], bpn, np.frombuffer(buf, dtype=np.uint8, count=bpn, offset=start)
    )
    return start + bpn


def serialize_mask_unit(unit: MaskUnit) -> bytes:
    return compose_bytes(
        MASK_CONFIG_LENGTH + unit.config.bytes_per_number,
        lambda buf, offset: write_mask_unit(unit, buf, offset),
    )


def parse_mask_unit(data: bytes, offset: int = 0) -> tuple[MaskUnit, int]:
    if len(data) - offset < MASK_CONFIG_LENGTH:
        raise DecodeError("mask unit buffer too short")
    try:
        config = MaskConfig.from_bytes(data[offset : offset + MASK_CONFIG_LENGTH])
    except ValueError as e:
        raise DecodeError(f"invalid mask config: {e}") from e
    bpn = config.bytes_per_number
    start = offset + MASK_CONFIG_LENGTH
    if len(data) < start + bpn:
        raise DecodeError("mask unit data truncated")
    limbs = limb_ops.bytes_le_to_limbs(
        np.frombuffer(data, dtype=np.uint8, count=bpn, offset=start), 1, bpn
    )
    unit = MaskUnit(config, limbs[0])
    if not unit.is_valid():
        raise DecodeError("mask unit element >= group order")
    return unit, MASK_CONFIG_LENGTH + bpn


def parse_mask_vect_stream(
    reader, lazy: bool = False, planes: "limb_ops.PlaneBuffers | None" = None
) -> MaskVect:
    """Streaming MaskVect parse from a ``ChunkReader``.

    The element block is copied chunk-by-chunk into one staging array
    (consumed chunk buffers are freed as the reader advances), so peak
    memory is ~1x the element block instead of the 2x of a concatenate-
    then-parse (reference streaming parse:
    rust/xaynet-core/src/mask/object/serialization/vect.rs + traits.rs).

    ``lazy=True``: the element bytes are gathered with ONE bounded-memory
    byte copy (no limb conversion, no host validity — a plain memcpy
    instead of the parse hot loop) into a ``LazyWireMaskVect`` for the
    device-ingest coordinator; see ``parse_mask_vect``.

    ``planes``: each segment of a v1 block fills its columns of every plane
    (``wire_to_planes`` a segment at a time), as ``parse_mask_vect``.
    """
    head = reader.read(MASK_CONFIG_LENGTH + 4)
    try:
        config = MaskConfig.from_bytes(head[:MASK_CONFIG_LENGTH])
    except ValueError as e:
        raise DecodeError(f"invalid mask config: {e}") from e
    (word,) = struct.unpack_from(">I", head, MASK_CONFIG_LENGTH)
    count, planar = _split_count_word(word)
    bpn = config.bytes_per_number
    nbytes = count * bpn
    if nbytes > reader.remaining:
        raise DecodeError("mask vector data truncated")
    if lazy or planar:
        # planar blocks gather as one byte copy either way: the segmented
        # interleaved convert below walks element-major segments, which a
        # plane-major block cannot feed without a full-block staging anyway
        raw = np.empty(nbytes, dtype=np.uint8)
        reader.read_into(raw)
        return _wire_vect(config, raw, count, planar, checked=not lazy)
    # segmented convert: fixed-size wire segments go straight into the limb
    # tensor (or the planes), so the transient staging is bounded (never
    # O(payload))
    if planes is not None:
        block, bad = planes.take(bpn, count), 0
        for s, k, staging in _wire_segments(reader, count, bpn):
            bad += limb_ops.wire_to_planes(staging, k, bpn, config.order, out=block, column=s)[1]
        return _relaid_vect(config, block, count, bad)
    limbs = np.empty((count, limb_ops.n_limbs_for_bytes(bpn)), dtype=np.uint32)
    for s, k, staging in _wire_segments(reader, count, bpn):
        limbs[s : s + k] = limb_ops.bytes_le_to_limbs(staging, k, bpn)
    vect = MaskVect(config, limbs)
    if not vect.is_valid():
        raise DecodeError("mask vector element >= group order")
    return vect


def _wire_segments(reader, count: int, bpn: int):
    """``(first element, elements, their wire bytes)`` of an interleaved
    block read from ``reader`` in segments of about 2 MiB."""
    seg_elems = max(1, (2 << 20) // max(bpn, 1))
    for s in range(0, count, seg_elems):
        k = min(seg_elems, count - s)
        staging = np.empty(k * bpn, dtype=np.uint8)
        reader.read_into(staging)
        yield s, k, staging


def parse_mask_unit_stream(reader) -> MaskUnit:
    """Streaming MaskUnit parse from a ``ChunkReader``."""
    head = reader.read(MASK_CONFIG_LENGTH)
    try:
        config = MaskConfig.from_bytes(head)
    except ValueError as e:
        raise DecodeError(f"invalid mask config: {e}") from e
    bpn = config.bytes_per_number
    if bpn > reader.remaining:
        raise DecodeError("mask unit data truncated")
    data = np.frombuffer(reader.read(bpn), dtype=np.uint8)
    limbs = limb_ops.bytes_le_to_limbs(data, 1, bpn)
    unit = MaskUnit(config, limbs[0])
    if not unit.is_valid():
        raise DecodeError("mask unit element >= group order")
    return unit


def write_mask_object(obj: MaskObject, buf, offset: int, planar_vect: bool = False) -> int:
    """``planar_vect`` emits the VECTOR part in the v2 byte-planar layout
    (the unit part is one element — planes would be a no-op relabel)."""
    offset = write_mask_vect(obj.vect, buf, offset, planar=planar_vect)
    return write_mask_unit(obj.unit, buf, offset)


def serialize_mask_object(obj: MaskObject, planar_vect: bool = False) -> bytes:
    return compose_bytes(
        serialized_object_length(obj.config, len(obj)),
        lambda buf, offset: write_mask_object(obj, buf, offset, planar_vect=planar_vect),
    )


def parse_mask_object(
    data: bytes, offset: int = 0, lazy_vect: bool = False, planes_vect=None
) -> tuple[MaskObject, int]:
    vect, n1 = parse_mask_vect(data, offset, lazy=lazy_vect, planes=planes_vect)
    unit, n2 = parse_mask_unit(data, offset + n1)
    return MaskObject(vect, unit), n1 + n2


def serialized_object_length(config, count: int) -> int:
    return (
        serialized_vect_length(config.vect, count)
        + MASK_CONFIG_LENGTH
        + config.unit.bytes_per_number
    )
