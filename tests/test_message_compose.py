"""A message is composed once: serialised into one buffer laid out as its
sealed box, signed over a view of it, sealed in place, sent from it
(docs/DESIGN.md section 16, "How a message is composed").

The wire is unchanged, so the reference here is the wire format spelled out
in plain Python (one element at a time, concatenation, the wheel's one-shot
calls): what ``Message.to_bytes(secret)`` and ``MessageEncoder`` produced
before the write-into forms existed, independent of them.
"""

from __future__ import annotations

import asyncio
import random
import struct
import tracemalloc

import numpy as np
import pytest

from xaynet_tpu.core.common import RoundParameters, RoundSeed
from xaynet_tpu.core.crypto import _purecrypto, encrypt, unlocked
from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
from xaynet_tpu.core.crypto.sign import SigningKeyPair, sign_detached
from xaynet_tpu.core.mask.config import (
    BoundType,
    DataType,
    GroupType,
    MaskConfig,
    MaskConfigPair,
    ModelType,
)
from xaynet_tpu.core.mask.object import MaskObject, MaskUnit, MaskVect
from xaynet_tpu.core.mask.seed import EncryptedMaskSeed, MaskSeed
from xaynet_tpu.core.message import Message, Sum, Sum2, Tag, Update
from xaynet_tpu.core.message.encoder import MessageBuilder, MessageEncoder
from xaynet_tpu.core.message.payloads import parse_payload_stream
from xaynet_tpu.sdk.client import HttpClient, InProcessClient
from xaynet_tpu.sdk.state_machine import (
    PetSettings,
    PhaseKind,
    StateMachine,
    TransitionOutcome,
    _PendingSend,
)
from xaynet_tpu.sdk.traits import ModelStore, XaynetClient
from xaynet_tpu.telemetry import tracing

SIGNER = SigningKeyPair.derive_from_seed(bytes(range(32)))
COORD = EncryptKeyPair.derive_from_seed(bytes(range(32, 64)))
EPHEMERAL_SEED = bytes(range(64, 96))

# 1, 2, 3 and 4 limbs: 4, 7, 10 and 13 wire bytes an element
CONFIGS = {
    1: MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3, quant=6),
    2: MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6),
    3: MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6),
    4: MaskConfig(GroupType.INTEGER, DataType.F64, BoundType.B0, ModelType.M9),
}
LENGTHS = [0, 1, 37]


# --- the wire format, spelled out ---------------------------------------------


def ref_vect(config: MaskConfig, values: list[int], planar: bool) -> bytes:
    bpn = config.bytes_per_number
    rows = [v.to_bytes(bpn, "little") for v in values]
    if planar:
        block = b"".join(bytes(row[b] for row in rows) for b in range(bpn))
    else:
        block = b"".join(rows)
    word = len(values) | (0x8000_0000 if planar else 0)
    return config.to_bytes() + struct.pack(">I", word) + block


def ref_object(config: MaskConfig, values: list[int], unit: int, planar: bool = False) -> bytes:
    return (
        ref_vect(config, values, planar)
        + config.to_bytes()
        + unit.to_bytes(config.bytes_per_number, "little")
    )


def ref_seed_dict(seed_dict: dict) -> bytes:
    body = b"".join(pk + seed.as_bytes() for pk, seed in seed_dict.items())
    return struct.pack(">I", len(body) + 4) + body


def ref_message(tag: int, payload: bytes, multipart: bool = False) -> bytes:
    signed = (
        SIGNER.public
        + COORD.public.as_bytes()
        + struct.pack(">IBBxx", 136 + len(payload), tag, 1 if multipart else 0)
        + payload
    )
    return sign_detached(SIGNER.secret, signed) + signed


def ref_parts(tag: int, payload: bytes, max_message_size: int, message_id: int) -> list[bytes]:
    if 136 + len(payload) <= max_message_size:
        return [ref_message(tag, payload)]
    budget = max_message_size - 136 - 8
    chunks = [payload[i : i + budget] for i in range(0, len(payload), budget)]
    return [
        ref_message(
            tag,
            struct.pack(">HHB3x", i + 1, message_id, 1 if i == len(chunks) - 1 else 0) + chunk,
            multipart=True,
        )
        for i, chunk in enumerate(chunks)
    ]


def _case(kind: str, n_limbs: int, length: int):
    """(message, its payload's reference bytes) of one kind and size."""
    config = CONFIGS[n_limbs]
    rng = random.Random(f"{kind}/{n_limbs}/{length}")
    values = [rng.randrange(config.order) for _ in range(length)]
    if values:
        values[-1] = config.order - 1  # the widest element
    unit = rng.randrange(config.order)
    obj = MaskObject(MaskVect.from_ints(config, values), MaskUnit.from_int(config, unit))
    sig1, sig2 = bytes(rng.randrange(256) for _ in range(64)), bytes(range(64))
    if kind == "sum2":
        payload = Sum2(sum_signature=sig1, model_mask=obj)
        want = sig1 + ref_object(config, values, unit)
    else:
        seeds = {
            bytes([i]) * 32: EncryptedMaskSeed(bytes(rng.randrange(256) for _ in range(80)))
            for i in range(3)
        }
        planar = kind == "update-planar"
        payload = Update(
            sum_signature=sig1, update_signature=sig2, masked_model=obj,
            local_seed_dict=seeds, wire_planar=planar,
        )
        want = sig1 + sig2 + ref_object(config, values, unit, planar) + ref_seed_dict(seeds)
    message = Message(
        participant_pk=SIGNER.public, coordinator_pk=COORD.public.as_bytes(), payload=payload
    )
    return message, want


def _sum_message() -> tuple[Message, bytes]:
    payload = Sum(sum_signature=b"\x07" * 64, ephm_pk=b"\x09" * 32)
    message = Message(
        participant_pk=SIGNER.public, coordinator_pk=COORD.public.as_bytes(), payload=payload
    )
    return message, b"\x07" * 64 + b"\x09" * 32


def _pending(message: Message, max_message_size=None, message_id=None) -> _PendingSend:
    encoder = MessageEncoder(message, SIGNER.secret, max_message_size, message_id=message_id)
    return _PendingSend(encoder, COORD.public.as_bytes())


@pytest.fixture
def pinned_ephemeral(monkeypatch):
    """Every sealed box of the test under one ephemeral key: boxes of one
    plaintext are then equal bit for bit, whatever sealed them."""
    pair = EncryptKeyPair.derive_from_seed(EPHEMERAL_SEED)
    monkeypatch.setattr(EncryptKeyPair, "generate", classmethod(lambda cls: pair))
    return pair


def _seal_moves() -> dict:
    return {
        key: child.value for key, child in unlocked.BYTES.children() if key[0] == "seal"
    }


def _delta(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _seal_moves().items() if v != before.get(k, 0)}


# --- composed once, and the bytes are the wire's ------------------------------------


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("n_limbs", sorted(CONFIGS))
@pytest.mark.parametrize("kind", ["sum2", "update", "update-planar"])
def test_the_composed_plaintext_is_the_wire_format_byte_for_byte(kind, n_limbs, length):
    message, payload = _case(kind, n_limbs, length)
    want = ref_message(int(message.tag), payload)
    assert message.serialized_length() == len(want)
    assert message.payload.to_bytes() == payload
    assert message.to_bytes(SIGNER.secret) == want
    # the sealed box of the send path: the same plaintext, opened either way
    box = _pending(message).sealed_part()
    assert isinstance(box, bytearray) and len(box) == len(want) + encrypt.SEALBYTES
    assert bytes(COORD.secret.decrypt(box, COORD.public)) == want
    assert bytes(COORD.secret.decrypt_in_place(bytearray(box), COORD.public)) == want
    # and it parses to the same message
    parsed = Message.from_bytes(bytes(COORD.secret.decrypt(box)))
    if kind == "update-planar":  # an eager parse forgets the layout it came in
        parsed.payload.wire_planar = True
    assert parsed.payload.to_bytes() == payload and parsed.tag == message.tag


def test_a_sum_message_is_composed_alike():
    message, payload = _sum_message()
    want = ref_message(int(Tag.SUM), payload)
    assert message.to_bytes(SIGNER.secret) == want
    assert bytes(COORD.secret.decrypt(_pending(message).sealed_part())) == want


def test_an_unsigned_message_keeps_the_signature_it_was_parsed_with():
    message, payload = _case("sum2", 2, 37)
    wire = ref_message(int(Tag.SUM2), payload)
    assert Message.from_bytes(wire).to_bytes() == wire
    assert message.to_bytes()[:64] == bytes(64)  # never signed: zeros, as before


def test_a_field_of_the_wrong_length_is_refused_and_shifts_nothing():
    message, _ = _sum_message()
    message.payload.sum_signature = b"\x07" * 63
    with pytest.raises(ValueError):
        message.payload.to_bytes()
    with pytest.raises(ValueError):
        message.to_bytes(SIGNER.secret)
    with pytest.raises(ValueError):
        _pending(message).sealed_part()
    message, _ = _sum_message()
    message.participant_pk = SIGNER.public + b"\x00"
    with pytest.raises(ValueError):
        message.to_bytes(SIGNER.secret)


def test_a_planar_vector_parsed_lazily_is_written_back_as_it_came():
    message, payload = _case("update-planar", 3, 37)
    lazy = Message.from_bytes(ref_message(int(Tag.UPDATE), payload), lazy_update_vect=True)
    assert not lazy.payload.masked_model.vect.materialized
    assert lazy.payload.to_bytes() == payload
    assert not lazy.payload.masked_model.vect.materialized


@pytest.mark.parametrize("kind,n_limbs", [("sum2", 2), ("update", 3), ("update-planar", 2)])
def test_the_coordinators_pipeline_parses_the_composed_box(kind, n_limbs):
    """``PetMessageHandler._decrypt_parse_one`` (open, phase filter, verify,
    parse) on the box as the HTTP server reads it (a ``bytearray``, opened in
    place) and as an in-process client hands it over."""
    from xaynet_tpu.server.events import PhaseName
    from xaynet_tpu.server.services import MessageWorkers, PetMessageHandler

    message, payload = _case(kind, n_limbs, 37)
    phase = PhaseName.SUM2 if kind == "sum2" else PhaseName.UPDATE
    box = _pending(message).sealed_part()
    workers = MessageWorkers(1)  # its own, closed below: the process's stay up
    handler = PetMessageHandler(events=None, request_tx=None, workers=workers)
    try:
        for given in (bytearray(box), memoryview(box).toreadonly()):
            parsed = handler._decrypt_parse_one(given, COORD, phase)
            assert parsed.participant_pk == SIGNER.public
            if kind == "update-planar":  # an eager parse forgets the layout it came in
                parsed.payload.wire_planar = True
            assert parsed.payload.to_bytes() == payload
    finally:
        workers.close()


# --- the seal: one box whichever route sealed it -------------------------------------


def _no_library(monkeypatch):
    def refuse():
        raise OSError("libcrypto.so.3: cannot open shared object file")

    monkeypatch.setattr(unlocked, "_open_library", refuse)
    monkeypatch.setattr(unlocked, "_tried", False)
    monkeypatch.setattr(unlocked, "_lib", None)


def _pure_python(monkeypatch):
    monkeypatch.setattr(encrypt, "_HAVE_CRYPTO", False)
    monkeypatch.setattr(encrypt, "_purecrypto", _purecrypto, raising=False)


def _wheel_box(plain: bytes) -> bytes:
    """The sealed box by the wheel's one-shot calls, under the pinned key."""
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305

    ephemeral = EncryptKeyPair.derive_from_seed(EPHEMERAL_SEED)
    eph_pk = ephemeral.public.as_bytes()
    shared = encrypt._agree(ephemeral.secret.as_bytes(), COORD.public.as_bytes())
    key = encrypt._derive_key(shared, eph_pk, COORD.public.as_bytes())
    return eph_pk + ChaCha20Poly1305(key).encrypt(b"\x00" * 12, plain, None)


_BOX_LENGTHS = [0, 1, 4096, unlocked.UNLOCKED_MIN - 17, unlocked.UNLOCKED_MIN - 16]


@pytest.mark.parametrize(
    "n,sealer",
    [(n, sealer) for sealer in ("by-length", "unlocked", "wheel", "no-library") for n in _BOX_LENGTHS]
    # the pure-Python cipher takes minutes a MiB: its short boxes hold it
    + [(n, "pure-python") for n in _BOX_LENGTHS[:3]],
)
def test_every_sealer_gives_the_same_box(n, sealer, pinned_ephemeral, monkeypatch):
    if sealer in ("by-length", "unlocked") and unlocked.load() is None:
        pytest.skip("the system's libcrypto does not load here")
    block = np.random.default_rng(n).integers(0, 256, max(n, 1), dtype=np.uint8).tobytes()
    plain = block[:n]
    want = _wheel_box(plain)
    if sealer == "unlocked":
        monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 0)
    elif sealer == "wheel":
        monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 1 << 62)
    elif sealer == "no-library":
        _no_library(monkeypatch)
    elif sealer == "pure-python":
        _no_library(monkeypatch)
        _pure_python(monkeypatch)
    before = _seal_moves()
    assert COORD.public.encrypt(plain) == want
    box = bytearray(32) + bytearray(plain) + bytearray(16)
    route = COORD.public.encrypt_in_place(box)
    assert bytes(box) == want
    # the box the route is chosen on: plaintext and tag; on both sides of the
    # crossover when the length decides
    foreign = {"by-length": n + 16 >= unlocked.UNLOCKED_MIN, "unlocked": True}.get(sealer, False)
    assert route == ("unlocked" if foreign else "wheel")
    assert _delta(before) == {("seal", route): 2 * (n + 16)}


def test_a_view_into_a_larger_buffer_is_sealed_where_it_lies(pinned_ephemeral, monkeypatch):
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 0)
    plain = bytes(range(200))
    whole = bytearray(b"\xaa" * 7 + bytes(32) + plain + bytes(16) + b"\xbb" * 5)
    COORD.public.encrypt_in_place(memoryview(whole)[7:-5])
    assert bytes(whole) == b"\xaa" * 7 + _wheel_box(plain) + b"\xbb" * 5


def test_encrypt_in_place_refuses_what_it_cannot_write():
    with pytest.raises(TypeError):
        COORD.public.encrypt_in_place(bytes(64))
    with pytest.raises(ValueError):
        COORD.public.encrypt_in_place(bytearray(47))


RFC8439_PLAIN = (
    b"Ladies and Gentlemen of the class of '99: If I could offer you only one tip for "
    b"the future, sunscreen would be it."
)
RFC8439_KEY = bytes(range(0x80, 0xA0))
RFC8439_NONCE = bytes.fromhex("070000004041424344454647")
RFC8439_AAD = bytes.fromhex("50515253c0c1c2c3c4c5c6c7")
RFC8439_BOX = bytes.fromhex(
    "d31a8d34648e60db7b86afbc53ef7ec2a4aded51296e08fea9e2b5a736ee62d6"
    "3dbea45e8ca9671282fafb69da92728b1a71de0a9e060b2905d6a5b67ecd3b36"
    "92ddbd7f2d778b8c9803aee328091b58fab324e4fad675945585808b4831d7bc"
    "3ff4def08e4b7a9de576d26586cec64b6116"
    "1ae10b594f09e26a7e902ecbd0600691"
)


@pytest.mark.parametrize("in_place", [False, True])
def test_rfc_8439_section_2_8_2_is_sealed_as_published(in_place):
    if unlocked.load() is None:
        pytest.skip("the system's libcrypto does not load here")
    n = len(RFC8439_PLAIN)
    if in_place:
        out = bytearray(RFC8439_PLAIN + bytes(16))
        plain = memoryview(out)[:n]
    else:
        out, plain = bytearray(n + 16), RFC8439_PLAIN
    assert unlocked.seal_into(RFC8439_KEY, RFC8439_NONCE, plain, out, aad=RFC8439_AAD)
    assert bytes(out) == RFC8439_BOX
    opened = bytearray(n)
    assert unlocked.open_into(RFC8439_KEY, RFC8439_NONCE, out, opened, aad=RFC8439_AAD)
    assert bytes(opened) == RFC8439_PLAIN
    # the pure-Python stand-in agrees
    assert (
        _purecrypto.chacha20poly1305_encrypt(RFC8439_KEY, RFC8439_NONCE, RFC8439_PLAIN, RFC8439_AAD)
        == RFC8439_BOX
    )


def test_seal_into_refuses_a_destination_without_room():
    if unlocked.load() is None:
        pytest.skip("the system's libcrypto does not load here")
    with pytest.raises(ValueError):
        unlocked.seal_into(RFC8439_KEY, RFC8439_NONCE, b"abc", bytearray(18))
    with pytest.raises(ValueError):
        unlocked.seal_into(RFC8439_KEY, RFC8439_NONCE, b"abc", bytes(19))


def test_a_library_that_cannot_seal_is_not_used(monkeypatch):
    """The sealing vector is among the known answers asked at load."""
    if unlocked.load() is None:
        pytest.skip("the system's libcrypto does not load here")

    def crippled():
        lib = unlocked._Lib("libcrypto.so.3")
        lib.EVP_EncryptInit_ex = lambda *args: 0
        return lib

    monkeypatch.setattr(unlocked, "_open_library", crippled)
    monkeypatch.setattr(unlocked, "_tried", False)
    monkeypatch.setattr(unlocked, "_lib", None)
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 0)
    assert unlocked.load() is None
    assert COORD.public.encrypt_in_place(bytearray(100)) == "wheel"


# --- multipart ---------------------------------------------------------------------


@pytest.mark.parametrize("kind,n_limbs", [("sum2", 2), ("update", 3), ("update-planar", 4)])
@pytest.mark.parametrize("max_message_size", [145, 400, 4096])
def test_multipart_parts_are_the_wire_format_under_a_pinned_message_id(
    kind, n_limbs, max_message_size
):
    message, payload = _case(kind, n_limbs, 37)
    want = ref_parts(int(message.tag), payload, max_message_size, message_id=0xBEEF)
    encoder = MessageEncoder(message, SIGNER.secret, max_message_size, message_id=0xBEEF)
    assert encoder.n_parts == len(want)
    assert [encoder.part_length(i) for i in range(encoder.n_parts)] == [len(p) for p in want]
    assert list(encoder) == want
    assert encoder.part(len(want) - 1) == want[-1]  # on demand, in any order
    # the sealed parts of the send path, reassembled as the coordinator does
    pending = _pending(message, max_message_size, message_id=0xBEEF)
    builder = MessageBuilder()
    for expected in want:
        part = bytes(COORD.secret.decrypt(pending.sealed_part()))
        assert part == expected
        pending.delivered()
        if len(want) > 1:
            builder.add(Message.from_bytes(part).payload)
    assert pending.next_index == encoder.n_parts
    if len(want) > 1:
        rebuilt = parse_payload_stream(message.tag, builder.take_reader())
        if kind == "update-planar":  # an eager parse forgets the layout it came in
            rebuilt.wire_planar = True
        assert rebuilt.to_bytes() == payload


def test_the_encoder_serialises_nothing_until_a_part_is_asked_for(monkeypatch):
    message, payload = _case("sum2", 2, 37)
    calls = []
    real = Sum2.write_into
    monkeypatch.setattr(
        Sum2, "write_into", lambda self, buf, offset: calls.append(1) or real(self, buf, offset)
    )
    one = MessageEncoder(message, SIGNER.secret, None)
    many = MessageEncoder(message, SIGNER.secret, 400, message_id=1)
    assert (one.n_parts, many.n_parts) == (1, -(-len(payload) // (400 - 144)))
    assert calls == []
    list(many)
    assert calls == [1]  # once for all its parts
    assert bytes(many.payload_bytes()) == payload and calls == [1]
    assert one.payload_bytes() == payload  # on demand, and not kept
    assert one._payload is None


def test_out_of_range_parts_are_refused():
    message, _ = _case("sum2", 2, 37)
    encoder = MessageEncoder(message, SIGNER.secret, 400, message_id=1)
    for i in (-1, encoder.n_parts):
        with pytest.raises(IndexError):
            encoder.part(i)
        with pytest.raises(IndexError):
            encoder.part_length(i)


# --- the state machine: retry, save and restore ------------------------------------


class _NoModel(ModelStore):
    async def load_model(self):
        return None


class _Client(XaynetClient):
    """Records what it is given; fails the attempts named in ``fail``."""

    def __init__(self, params: RoundParameters, fail=()):
        self.params, self.fail = params, set(fail)
        self.attempts, self.given, self.seeds = 0, [], None

    async def get_round_params(self):
        return self.params

    async def get_sums(self):
        return {}

    async def get_seeds(self, pk):
        return self.seeds

    async def get_model(self):
        return None

    async def send_message(self, encrypted):
        self.attempts += 1
        self.given.append((encrypted, bytes(encrypted)))
        if self.attempts in self.fail:
            raise ConnectionError("simulated drop")


def _sum_machine(max_message_size, fail=(), model_length=64):
    params = RoundParameters(
        pk=COORD.public.as_bytes(), sum=1.0, update=0.0,
        seed=RoundSeed(b"\x05" * 32),
        mask_config=MaskConfigPair(vect=CONFIGS[2], unit=CONFIGS[2]),
        model_length=model_length,
    )
    client = _Client(params, fail)
    machine = StateMachine(
        PetSettings(keys=SIGNER, max_message_size=max_message_size, device_sum2=False),
        client, _NoModel(),
    )
    return machine, client


def _tick(machine, n=1):
    async def drive():
        return [await machine.transition() for _ in range(n)]

    return asyncio.run(drive())


def _to_sum2(machine, client):
    _tick(machine, 2)  # NewRound -> Sum: the ephemeral key goes out
    assert machine.phase is PhaseKind.SUM2
    seed = MaskSeed(b"\x2a" * 32)
    client.seeds = {b"\x01" * 32: seed.encrypt(machine.ephm_keys.public)}


def test_a_transient_failure_sends_the_same_sealed_bytes_again_and_seals_once():
    machine, client = _sum_machine(None, fail={2})
    _to_sum2(machine, client)
    before = _seal_moves()
    assert _tick(machine) == [TransitionOutcome.PENDING]  # the Sum2 message fails
    assert machine._pending is not None and machine.phase is PhaseKind.SUM2
    assert _tick(machine) == [TransitionOutcome.COMPLETE]
    (first_obj, first), (second_obj, second) = client.given[1:]
    assert second_obj is first_obj and second == first  # the kept box, not a new one
    assert _delta(before) == {("seal", "wheel"): len(first) - 32}
    assert machine._pending is None and machine.phase is PhaseKind.AWAITING
    opened = Message.from_bytes(bytes(COORD.secret.decrypt(first)))
    assert opened.tag is Tag.SUM2 and len(opened.payload.model_mask) == 64


def test_a_multipart_send_keeps_one_box_and_resumes_at_the_failed_part():
    machine, client = _sum_machine(400, fail={4})
    _to_sum2(machine, client)
    before = _seal_moves()
    assert _tick(machine) == [TransitionOutcome.PENDING]
    pending = machine._pending
    assert pending.next_index == 2 and pending._sealed is client.given[-1][0]
    assert _tick(machine) == [TransitionOutcome.COMPLETE]
    sent = [data for _, data in client.given[1:]]
    assert sent[2] == sent[3]  # the failed part again, the same bytes
    parts = pending.encoder.n_parts
    assert len(sent) == parts + 1
    assert sum(_delta(before).values()) == sum(len(s) - 32 for s in sent) - (len(sent[2]) - 32)
    builder = MessageBuilder()
    for data in sent[:3] + sent[4:]:
        builder.add(Message.from_bytes(bytes(COORD.secret.decrypt(data))).payload)
    assert builder.is_complete()


@pytest.mark.parametrize("max_message_size", [None, 400])
def test_save_and_restore_mid_send(max_message_size):
    machine, client = _sum_machine(max_message_size, fail={2, 3})
    _to_sum2(machine, client)
    _tick(machine)  # the first part of the Sum2 message fails
    assert machine._pending is not None
    payload = bytes(machine._pending.encoder.payload_bytes())
    next_before = machine._pending.next_index
    restored = StateMachine.restore(machine.save(), client, _NoModel())
    assert restored._pending.next_index == next_before
    assert bytes(restored._pending.encoder.payload_bytes()) == payload
    assert restored._pending.encoder.n_parts == machine._pending.encoder.n_parts
    asyncio.run(restored.transition())  # fails once more
    assert asyncio.run(restored.transition()) is TransitionOutcome.COMPLETE
    assert restored._pending is None and restored.phase is PhaseKind.AWAITING
    sent = [data for _, data in client.given[1:]]
    distinct = [s for i, s in enumerate(sent) if i == 0 or s != sent[i - 1]]
    builder, opened = MessageBuilder(), None
    for data in distinct[1:] if max_message_size else distinct[-1:]:
        opened = Message.from_bytes(bytes(COORD.secret.decrypt(data)))
        if opened.is_multipart:
            builder.add(opened.payload)
    if max_message_size:
        assert builder.payload_bytes() == payload
    else:
        assert opened.payload.to_bytes() == payload


def test_the_in_process_client_leaves_the_kept_box_intact(monkeypatch):
    """The coordinator's pipeline opens a ``bytearray`` in place; the box a
    sender keeps for a retry must come back whole."""
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 64)
    message, payload = _case("sum2", 2, 37)
    opened = []

    class _Handler:
        async def handle_message(self, encrypted):
            if isinstance(encrypted, bytearray):
                raw = COORD.secret.decrypt_in_place(encrypted, COORD.public)
            else:
                raw = COORD.secret.decrypt(encrypted, COORD.public)
            opened.append(bytes(raw))

    pending = _pending(message)
    box = pending.sealed_part()
    kept = bytes(box)
    asyncio.run(InProcessClient(None, _Handler()).send_message(box))
    assert opened == [ref_message(int(Tag.SUM2), payload)]
    assert bytes(box) == kept and pending.sealed_part() is box


# --- the request: no concatenation ---------------------------------------------------


def test_exchange_hands_the_body_to_the_writer_as_the_object_it_was_given():
    written = []

    class _Writer:
        def write(self, data):
            written.append(data)

        async def drain(self):
            pass

    class _Reader:
        def __init__(self):
            self.lines = [b"HTTP/1.1 200 OK\r\n", b"Content-Length: 0\r\n", b"\r\n"]

        async def readline(self):
            return self.lines.pop(0)

    body = bytearray(b"\x5a" * 1000)
    client = HttpClient("http://127.0.0.1:1")
    status, _, _ = asyncio.run(
        client._exchange(_Reader(), _Writer(), "POST", "/message", body)
    )
    assert status == 200
    assert len(written) == 2 and written[1] is body
    assert written[0].endswith(b"\r\n\r\n") and b"Content-Length: 1000\r\n" in written[0]
    written.clear()
    asyncio.run(client._exchange(_Reader(), _Writer(), "GET", "/params", None))
    assert len(written) == 1 and b"Content-Length: 0\r\n" in written[0]


@pytest.mark.parametrize("kind", ["bytearray", "bytes", "memoryview"])
def test_an_8_mib_body_arrives_whole_over_loopback(kind):
    n = 8 << 20
    data = np.random.default_rng(8).integers(0, 256, n, dtype=np.uint8).tobytes()
    body = {"bytearray": bytearray, "bytes": bytes, "memoryview": memoryview}[kind](data)
    got = []

    async def serve(reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        length = int(head.split(b"Content-Length: ")[1].split(b"\r\n")[0])
        got.append(await reader.readexactly(length))
        writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: 0\r\n\r\n")
        await writer.drain()
        writer.close()

    async def run():
        server = await asyncio.start_server(serve, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = HttpClient(f"http://127.0.0.1:{port}", timeout=30.0)
        try:
            await client.send_message(body)
        finally:
            client.close()
            server.close()
            await server.wait_closed()

    asyncio.run(asyncio.wait_for(run(), timeout=60))
    assert len(got) == 1 and got[0] == data
    assert bytes(body) == data  # sent from, not written to


# --- one buffer of the message's length, and no other --------------------------------


def test_composing_and_sealing_8_mib_peaks_under_five_quarters_of_its_length():
    """The next concatenation on this path fails here: the parent's chain
    (serialise to learn the length, serialise again, ``bytes(buf)``, the
    wheel's ``encrypt``, ``eph_pk + ct``) peaked at over four lengths."""
    config = CONFIGS[2]
    count = (8 << 20) // config.bytes_per_number + 1
    data = np.random.default_rng(34).integers(0, 1 << 22, (count, 2), dtype=np.uint32)
    mask = MaskObject(MaskVect(config, data), MaskUnit.from_int(config, 5))
    message = Message(
        participant_pk=SIGNER.public, coordinator_pk=COORD.public.as_bytes(),
        payload=Sum2(sum_signature=b"\x01" * 64, model_mask=mask),
    )
    length = message.serialized_length()
    assert length >= 8 << 20 and length + 16 >= unlocked.UNLOCKED_MIN
    _pending(message).sealed_part()  # warm: libraries loaded, spans declared
    tracemalloc.start()
    try:
        pending = _pending(message)
        box = pending.sealed_part()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert len(box) == length + encrypt.SEALBYTES
    assert peak < 1.25 * length, f"peak {peak} for a message of {length}"
    assert bytes(COORD.secret.decrypt(box))[:200] == message.to_bytes(SIGNER.secret)[:200]


# --- the participant's spans and their export ------------------------------------------


def test_compose_spans_carry_the_bytes_and_the_route(monkeypatch):
    tracer = tracing.get_tracer()
    if tracer.mode == "off":
        pytest.skip("tracing is off in this environment")
    message, _ = _case("sum2", 2, 37)
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 1 << 62)
    _pending(message).sealed_part()
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 0 if unlocked.load() else 1 << 62)
    box = _pending(message).sealed_part()
    spans = [s for s in tracer.ring_spans() if s.name.startswith("message.")][-8:]
    assert [s.name for s in spans] == [
        "message.serialise", "message.sign", "message.seal", "message.compose"] * 2
    short, long_ = spans[3], spans[7]
    assert short.attrs["route"] == "wheel" and spans[2].attrs["route"] == "wheel"
    assert long_.attrs["route"] == ("unlocked" if unlocked.load() else "wheel")
    assert long_.attrs["bytes"] == len(box) and long_.attrs["part"] == 0
    for child in spans[4:7]:
        assert child.parent_id == long_.span_id


def test_a_participant_process_exports_its_rounds_where_a_trace_dir_is_set(tmp_path):
    """``follow_round``: windows keyed by the trace id, flushed at the next
    round and at ``end_followed``; never over a coordinator's own window."""
    import json

    tracer = tracing.Tracer(mode="on", trace_dir=str(tmp_path))
    span = tracing.declared_span_names()
    assert "message.compose" in span
    tracer.follow_round("aaaa")
    tracer.follow_round("aaaa")  # the same round: nothing
    with tracer.span("message.compose", part=0):
        pass
    tracer.follow_round("bbbb")  # flushes the first window
    files = sorted(p.name for p in tmp_path.iterdir())
    assert len(files) == 1 and files[0].startswith("round_1.")
    events = json.loads((tmp_path / files[0]).read_text())["traceEvents"]
    assert {e["name"] for e in events if e["ph"] == "X"} == {"message.compose", "round"}
    assert {e["args"]["trace"] for e in events if e["ph"] == "X"} == {"aaaa"}
    tracer.end_followed()
    assert len(list(tmp_path.iterdir())) == 2
    # a coordinator's window is its own: not reopened, not flushed
    tracer.begin_round(7, "cccc")
    tracer.follow_round("cccc")
    tracer.end_followed()
    assert tracer.round_ctx() is not None and len(list(tmp_path.iterdir())) == 2
    tracer.end_round()
    # with no directory configured a participant opens no window at all
    quiet = tracing.Tracer(mode="on", trace_dir="")
    quiet.follow_round("dddd")
    assert quiet.round_ctx() is None
