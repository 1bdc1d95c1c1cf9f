"""PET message payloads: Sum, Update, Sum2, Chunk.

Layouts (reference: rust/xaynet-core/src/message/payload/):

- Sum (sum.rs): sum_signature(64) ‖ ephm_pk(32)
- Update (update.rs): sum_signature(64) ‖ update_signature(64) ‖
  masked model (MaskObject) ‖ local seed dict (LV-encoded, 112 B/entry)
- Sum2 (sum2.rs): sum_signature(64) ‖ aggregated mask (MaskObject)
- Chunk (chunk.rs): id(u16 BE) ‖ message_id(u16 BE) ‖ flags(1, bit0 =
  LAST_CHUNK) ‖ reserved(3) ‖ data

Length-Value items use a 4-byte big-endian length that *includes* the
length field itself (reference: rust/xaynet-core/src/message/traits.rs:126-160).
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Union

from ..mask.object import MaskObject
from ..mask.seed import ENCRYPTED_MASK_SEED_LENGTH, EncryptedMaskSeed
from ..mask.serialization import (
    DecodeError,
    compose_bytes,
    parse_mask_object,
    parse_mask_unit_stream,
    parse_mask_vect_stream,
    serialized_object_length,
    write_mask_object,
)

SIGNATURE_LENGTH = 64
PK_LENGTH = 32
SEED_DICT_ENTRY_LENGTH = PK_LENGTH + ENCRYPTED_MASK_SEED_LENGTH  # 112
CHUNK_HEADER_LENGTH = 8

LocalSeedDict = dict  # bytes (sum pk, 32) -> EncryptedMaskSeed


# --- Length-Value helpers ---------------------------------------------------


def lv_encode(value: bytes) -> bytes:
    return struct.pack(">I", len(value) + 4) + value


def lv_decode(data: bytes, offset: int = 0) -> tuple[bytes, int]:
    """Returns (value, total bytes consumed incl. the length field)."""
    if len(data) - offset < 4:
        raise DecodeError("LV item truncated (no length field)")
    (length,) = struct.unpack_from(">I", data, offset)
    if length < 4:
        raise DecodeError("LV length below minimum")
    if len(data) - offset < length:
        raise DecodeError("LV value truncated")
    return data[offset + 4 : offset + length], length


def serialized_seed_dict_length(seed_dict: dict) -> int:
    return 4 + SEED_DICT_ENTRY_LENGTH * len(seed_dict)


def write_local_seed_dict(seed_dict: dict, buf, offset: int) -> int:
    """Serialise the LV-encoded dictionary into ``buf`` at ``offset``;
    returns the offset behind it."""
    struct.pack_into(">I", buf, offset, serialized_seed_dict_length(seed_dict))
    offset += 4
    for pk, seed in seed_dict.items():
        if len(pk) != PK_LENGTH:
            raise ValueError("seed dict key must be a 32-byte public key")
        seed_bytes = seed.as_bytes() if isinstance(seed, EncryptedMaskSeed) else bytes(seed)
        if len(seed_bytes) != ENCRYPTED_MASK_SEED_LENGTH:
            raise ValueError("seed dict value must be an 80-byte encrypted seed")
        buf[offset : offset + PK_LENGTH] = pk
        buf[offset + PK_LENGTH : offset + SEED_DICT_ENTRY_LENGTH] = seed_bytes
        offset += SEED_DICT_ENTRY_LENGTH
    return offset


def serialize_local_seed_dict(seed_dict: dict) -> bytes:
    return compose_bytes(
        serialized_seed_dict_length(seed_dict),
        lambda buf, offset: write_local_seed_dict(seed_dict, buf, offset),
    )


def parse_local_seed_dict(data: bytes, offset: int = 0) -> tuple[dict, int]:
    value, consumed = lv_decode(data, offset)
    return _seed_dict_from_value(value), consumed


def _seed_dict_from_value(value) -> dict:
    value = bytes(value)  # keys and seeds are hashed and kept: never views of a body
    if len(value) % SEED_DICT_ENTRY_LENGTH != 0:
        raise DecodeError("seed dict length not a multiple of the entry size")
    out: dict = {}
    for i in range(0, len(value), SEED_DICT_ENTRY_LENGTH):
        pk = value[i : i + PK_LENGTH]
        seed = EncryptedMaskSeed(value[i + PK_LENGTH : i + SEED_DICT_ENTRY_LENGTH])
        if pk in out:
            raise DecodeError("duplicate sum pk in seed dict")
        out[pk] = seed
    return out


def parse_local_seed_dict_stream(reader) -> dict:
    (length,) = struct.unpack(">I", reader.read(4))
    if length < 4:
        raise DecodeError("LV length below minimum")
    if length - 4 > reader.remaining:
        raise DecodeError("LV value truncated")
    return _seed_dict_from_value(reader.read(length - 4))


# --- payloads ---------------------------------------------------------------
#
# One serialiser a payload: ``write_into(buf, offset) -> end`` writes it into
# a writable buffer (a message is composed once, in the buffer it is sealed
# and sent from); ``to_bytes()`` is that, into a buffer of its own.


def _write_fields(buf, offset: int, *fields) -> int:
    """Byte fields, one behind the other."""
    for field in fields:
        buf[offset : offset + len(field)] = field
        offset += len(field)
    return offset


@dataclass
class Sum:
    sum_signature: bytes
    ephm_pk: bytes

    def serialized_length(self) -> int:
        return SIGNATURE_LENGTH + PK_LENGTH

    def write_into(self, buf, offset: int) -> int:
        return _write_fields(buf, offset, self.sum_signature, self.ephm_pk)

    def to_bytes(self) -> bytes:
        return compose_bytes(self.serialized_length(), self.write_into)

    @classmethod
    def from_bytes(cls, data: bytes) -> "Sum":
        if len(data) < SIGNATURE_LENGTH + PK_LENGTH:
            raise DecodeError("sum payload too short")
        return cls(
            sum_signature=bytes(data[:SIGNATURE_LENGTH]),
            ephm_pk=bytes(data[SIGNATURE_LENGTH : SIGNATURE_LENGTH + PK_LENGTH]),
        )


@dataclass
class Update:
    sum_signature: bytes
    update_signature: bytes
    masked_model: MaskObject
    local_seed_dict: dict
    # serialize the masked model's vector part in the v2 byte-planar wire
    # layout (negotiated via RoundParameters.wire_format; the parse side
    # auto-detects from the count-word flag, so this only shapes to_bytes)
    wire_planar: bool = False

    def serialized_length(self) -> int:
        return (
            2 * SIGNATURE_LENGTH
            + serialized_object_length(self.masked_model.config, len(self.masked_model))
            + serialized_seed_dict_length(self.local_seed_dict)
        )

    def write_into(self, buf, offset: int) -> int:
        offset = _write_fields(buf, offset, self.sum_signature, self.update_signature)
        offset = write_mask_object(
            self.masked_model, buf, offset, planar_vect=self.wire_planar
        )
        return write_local_seed_dict(self.local_seed_dict, buf, offset)

    def to_bytes(self) -> bytes:
        return compose_bytes(self.serialized_length(), self.write_into)

    @classmethod
    def from_bytes(
        cls, data: bytes, lazy_vect: bool = False, planes_vect=None
    ) -> "Update":
        if len(data) < 2 * SIGNATURE_LENGTH:
            raise DecodeError("update payload too short")
        masked, consumed = parse_mask_object(
            data, 2 * SIGNATURE_LENGTH, lazy_vect=lazy_vect, planes_vect=planes_vect
        )
        seed_dict, _ = parse_local_seed_dict(data, 2 * SIGNATURE_LENGTH + consumed)
        return cls(
            sum_signature=bytes(data[:SIGNATURE_LENGTH]),
            update_signature=bytes(data[SIGNATURE_LENGTH : 2 * SIGNATURE_LENGTH]),
            masked_model=masked,
            local_seed_dict=seed_dict,
            wire_planar=bool(getattr(masked.vect, "packed_wire", False)),
        )

    @classmethod
    def from_stream(cls, reader, lazy_vect: bool = False, planes_vect=None) -> "Update":
        sigs = reader.read(2 * SIGNATURE_LENGTH)
        vect = parse_mask_vect_stream(reader, lazy=lazy_vect, planes=planes_vect)
        unit = parse_mask_unit_stream(reader)
        seed_dict = parse_local_seed_dict_stream(reader)
        return cls(
            sum_signature=sigs[:SIGNATURE_LENGTH],
            update_signature=sigs[SIGNATURE_LENGTH:],
            masked_model=MaskObject(vect, unit),
            local_seed_dict=seed_dict,
            wire_planar=bool(getattr(vect, "packed_wire", False)),
        )


@dataclass
class Sum2:
    sum_signature: bytes
    model_mask: MaskObject

    def serialized_length(self) -> int:
        return SIGNATURE_LENGTH + serialized_object_length(
            self.model_mask.config, len(self.model_mask)
        )

    def write_into(self, buf, offset: int) -> int:
        offset = _write_fields(buf, offset, self.sum_signature)
        return write_mask_object(self.model_mask, buf, offset)

    def to_bytes(self) -> bytes:
        return compose_bytes(self.serialized_length(), self.write_into)

    @classmethod
    def from_bytes(cls, data: bytes) -> "Sum2":
        if len(data) < SIGNATURE_LENGTH:
            raise DecodeError("sum2 payload too short")
        mask, _ = parse_mask_object(data, SIGNATURE_LENGTH)
        return cls(sum_signature=bytes(data[:SIGNATURE_LENGTH]), model_mask=mask)

    @classmethod
    def from_stream(cls, reader) -> "Sum2":
        sig = reader.read(SIGNATURE_LENGTH)
        vect = parse_mask_vect_stream(reader)
        unit = parse_mask_unit_stream(reader)
        return cls(sum_signature=sig, model_mask=MaskObject(vect, unit))


@dataclass
class Chunk:
    """One part of a multipart message.

    ``tag`` carries the enclosing message's tag (the type of the message
    being reassembled).
    """

    id: int
    message_id: int
    last: bool
    data: bytes
    tag: "object" = None  # Tag; typed loosely to avoid a circular import

    def serialized_length(self) -> int:
        return CHUNK_HEADER_LENGTH + len(self.data)

    def write_into(self, buf, offset: int) -> int:
        struct.pack_into(
            ">HHB3x", buf, offset,
            self.id & 0xFFFF, self.message_id & 0xFFFF, 1 if self.last else 0,
        )
        return _write_fields(buf, offset + CHUNK_HEADER_LENGTH, self.data)

    def to_bytes(self) -> bytes:
        return compose_bytes(self.serialized_length(), self.write_into)

    @classmethod
    def from_bytes(cls, data: bytes, tag=None) -> "Chunk":
        if len(data) < CHUNK_HEADER_LENGTH:
            raise DecodeError("chunk payload too short")
        cid, mid, flags = struct.unpack_from(">HHB", data)
        return cls(
            id=cid, message_id=mid, last=bool(flags & 1),
            data=bytes(data[CHUNK_HEADER_LENGTH:]), tag=tag,
        )


Payload = Union[Sum, Update, Sum2, Chunk]


def parse_payload(
    tag,
    is_multipart: bool,
    data: bytes,
    lazy_update_vect: bool = False,
    planes_update_vect=None,
) -> Payload:
    if is_multipart:
        return Chunk.from_bytes(data, tag=tag)
    from .message import Tag  # local import to avoid cycle

    if tag == Tag.SUM:
        return Sum.from_bytes(data)
    if tag == Tag.UPDATE:
        return Update.from_bytes(
            data, lazy_vect=lazy_update_vect, planes_vect=planes_update_vect
        )
    if tag == Tag.SUM2:
        return Sum2.from_bytes(data)
    raise DecodeError(f"unknown tag {tag}")


def parse_payload_stream(
    tag, reader, lazy_update_vect: bool = False, planes_update_vect=None
) -> Payload:
    """Streaming payload parse from a ``ChunkReader`` (multipart reassembly).

    Reference analogue: the stream variants of ``FromBytes``
    (rust/xaynet-core/src/message/traits.rs) used by the multipart service.
    """
    from .message import Tag  # local import to avoid cycle

    try:
        if tag == Tag.SUM:
            return Sum.from_bytes(reader.read(reader.remaining))
        if tag == Tag.UPDATE:
            return Update.from_stream(
                reader, lazy_vect=lazy_update_vect, planes_vect=planes_update_vect
            )
        if tag == Tag.SUM2:
            return Sum2.from_stream(reader)
    except ValueError as e:
        if isinstance(e, DecodeError):
            raise
        raise DecodeError(str(e)) from e
    raise DecodeError(f"unknown tag {tag}")
