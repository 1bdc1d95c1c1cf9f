"""Signed message envelope for the PET protocol.

Header layout, 136 bytes (reference:
rust/xaynet-core/src/message/message.rs:24-49):

    signature(64) ‖ participant_pk(32) ‖ coordinator_pk(32) ‖
    length(u32 BE, whole message incl. header) ‖ tag(1) ‖ flags(1) ‖
    reserved(2) ‖ payload

The Ed25519 signature covers ``bytes[64:length]`` (everything after the
signature, message.rs:336-358). Tags: Sum=1, Update=2, Sum2=3
(message.rs:441-468); flag bit 0 marks multipart messages.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from enum import IntEnum, IntFlag

from ..crypto import sign as crypto_sign
from ..mask.serialization import DecodeError, compose_buffer
from .payloads import Chunk, Payload, Sum, Sum2, Update, parse_payload

SIGNATURE_LENGTH = 64
PK_LENGTH = 32
HEADER_LENGTH = SIGNATURE_LENGTH + 2 * PK_LENGTH + 4 + 1 + 1 + 2  # 136

# protocol minimums per round (reference: message.rs:18-21)
SUM_COUNT_MIN = 1
UPDATE_COUNT_MIN = 3


class Tag(IntEnum):
    SUM = 1
    UPDATE = 2
    SUM2 = 3


class Flags(IntFlag):
    NONE = 0
    MULTIPART = 1


@dataclass
class Message:
    """A signed PET message (header + payload)."""

    participant_pk: bytes
    coordinator_pk: bytes
    payload: Payload
    tag: Tag | None = None
    is_multipart: bool = False
    signature: bytes | None = None

    def __post_init__(self):
        if self.tag is None:
            self.tag = _payload_tag(self.payload)

    def payload_length(self) -> int:
        return self.payload.serialized_length()

    def serialized_length(self) -> int:
        return HEADER_LENGTH + self.payload_length()

    def write_into(self, buf, offset: int = 0) -> int:
        """Serialise header and payload into the writable buffer ``buf`` at
        ``offset`` (the signature field holds ``self.signature``, or zeros
        until :meth:`sign_into`); returns the offset behind the message. The
        payload is written where it stays: no buffer of its own."""
        total = self.serialized_length()
        at = offset + SIGNATURE_LENGTH
        # through a view: a field of another length than its slot is refused,
        # where a ``bytearray`` would grow or shrink and shift what follows
        view = memoryview(buf)
        view[offset:at] = self.signature or bytes(SIGNATURE_LENGTH)
        view[at : at + PK_LENGTH] = self.participant_pk
        view[at + PK_LENGTH : at + 2 * PK_LENGTH] = self.coordinator_pk
        struct.pack_into(
            ">IBBxx", view, at + 2 * PK_LENGTH,
            total, int(self.tag), int(Flags.MULTIPART) if self.is_multipart else 0,
        )
        if self.payload.write_into(view, offset + HEADER_LENGTH) != offset + total:
            raise ValueError("payload length disagrees with its serialiser")
        return offset + total

    @staticmethod
    def sign_into(buf, offset: int, end: int, secret_signing_key: bytes) -> None:
        """Sign the message serialised at ``buf[offset:end]`` over a view of
        its signed bytes (signing a 150 MB update must not copy the payload)
        and write the signature in front."""
        view = memoryview(buf)
        at = offset + SIGNATURE_LENGTH
        view[offset:at] = crypto_sign.sign_detached(secret_signing_key, view[at:end])

    def to_bytes(self, secret_signing_key: bytes | None = None) -> bytes:
        """Serialize; signs on serialize when a secret key is given."""
        buf = compose_buffer(self.serialized_length(), self.write_into)
        if secret_signing_key is not None:
            self.sign_into(buf, 0, len(buf), secret_signing_key)
        return bytes(buf)

    @classmethod
    def from_bytes(
        cls,
        data: bytes,
        verify: bool = True,
        lazy_update_vect: bool = False,
        planes_update_vect=None,
    ) -> "Message":
        """Parse and (by default) verify the signature.

        ``lazy_update_vect``: device-ingest coordinators defer the Update
        payload's element parse/validity to the accelerator (see
        ``parse_mask_vect``); all other payloads parse eagerly.
        ``planes_update_vect`` (a ``PlaneBuffers``): the coordinator's
        staging slots are byte planes, so the Update payload's vector is
        parsed into checked planes taken from it
        (``parse_mask_vect(planes=)``); no other payload's is."""
        length = cls._declared_length(data)
        # ``data`` may be a view of the buffer a sealed box was opened into:
        # the header's fields are copied out, the payload is sliced as a view
        # (no second copy of a 179 MB body, whatever ``data`` is)
        data = memoryview(data)
        signature = bytes(data[:SIGNATURE_LENGTH])
        participant_pk = bytes(data[SIGNATURE_LENGTH : SIGNATURE_LENGTH + PK_LENGTH])
        coordinator_pk = bytes(
            data[SIGNATURE_LENGTH + PK_LENGTH : SIGNATURE_LENGTH + 2 * PK_LENGTH]
        )
        tag_raw = data[SIGNATURE_LENGTH + 2 * PK_LENGTH + 4]
        flags_raw = data[SIGNATURE_LENGTH + 2 * PK_LENGTH + 5]
        try:
            tag = Tag(tag_raw)
        except ValueError as e:
            raise DecodeError(f"invalid tag {tag_raw}") from e
        is_multipart = bool(flags_raw & Flags.MULTIPART)
        if verify:
            cls.verify_bytes(data)
        payload = parse_payload(
            tag,
            is_multipart,
            data[HEADER_LENGTH:length],
            lazy_update_vect=lazy_update_vect,
            planes_update_vect=planes_update_vect,
        )
        return cls(
            participant_pk=participant_pk,
            coordinator_pk=coordinator_pk,
            payload=payload,
            tag=tag,
            is_multipart=is_multipart,
            signature=signature,
        )

    @staticmethod
    def _declared_length(data: bytes) -> int:
        if len(data) < HEADER_LENGTH:
            raise DecodeError("message shorter than header")
        (length,) = struct.unpack_from(">I", data, SIGNATURE_LENGTH + 2 * PK_LENGTH)
        if length < HEADER_LENGTH or length > len(data):
            raise DecodeError("invalid message length field")
        return length

    @classmethod
    def verify_bytes(cls, data: bytes) -> None:
        """The signature check of :meth:`from_bytes` on its own (one Ed25519
        pass over the signed bytes, no copy of them), for callers that time
        it apart from the parse and then parse with ``verify=False``."""
        length = cls._declared_length(data)
        data = memoryview(data)
        if not crypto_sign.verify_detached(
            bytes(data[SIGNATURE_LENGTH : SIGNATURE_LENGTH + PK_LENGTH]),
            bytes(data[:SIGNATURE_LENGTH]),
            data[SIGNATURE_LENGTH:length],
        ):
            raise DecodeError("invalid message signature")


def _payload_tag(payload: Payload) -> Tag:
    if isinstance(payload, Sum):
        return Tag.SUM
    if isinstance(payload, Update):
        return Tag.UPDATE
    if isinstance(payload, Sum2):
        return Tag.SUM2
    if isinstance(payload, Chunk):
        return payload.tag
    raise TypeError(f"unknown payload type {type(payload)}")


def peek_header(data: bytes) -> tuple[bytes, Tag, bool]:
    """Cheap header inspection without payload parsing or verification.

    Returns (participant_pk, tag, is_multipart) — what the phase filter
    needs before paying for signature verification.
    """
    if len(data) < HEADER_LENGTH:
        raise DecodeError("message shorter than header")
    tag_raw = data[SIGNATURE_LENGTH + 2 * PK_LENGTH + 4]
    try:
        tag = Tag(tag_raw)
    except ValueError as e:
        raise DecodeError(f"invalid tag {tag_raw}") from e
    flags_raw = data[SIGNATURE_LENGTH + 2 * PK_LENGTH + 5]
    return (
        bytes(data[SIGNATURE_LENGTH : SIGNATURE_LENGTH + PK_LENGTH]),
        tag,
        bool(flags_raw & Flags.MULTIPART),
    )
