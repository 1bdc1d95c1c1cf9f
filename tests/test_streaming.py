"""Streaming multipart reassembly and chunk-level send retry.

Reference behaviors covered:
- streaming re-parse of reassembled multipart payloads without a second
  contiguous copy (rust/xaynet-core/src/message/utils/chunkable_iterator.rs,
  multipart/service.rs:26-117);
- chunk-level send retry: only the failed part is re-sent
  (rust/xaynet-sdk/src/state_machine/phases/sending.rs:96-113).
"""

import asyncio
import tracemalloc
from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.core.crypto.sign import SigningKeyPair
from xaynet_tpu.core.mask.config import (
    BoundType,
    DataType,
    GroupType,
    MaskConfig,
    ModelType,
)
from xaynet_tpu.core.mask.masking import Masker
from xaynet_tpu.core.mask.model import Scalar
from xaynet_tpu.core.mask.seed import MaskSeed
from xaynet_tpu.core.message import Message, Sum2, Tag, Update
from xaynet_tpu.core.message.encoder import ChunkReader, MessageBuilder, MessageEncoder
from xaynet_tpu.core.message.payloads import Chunk, parse_payload_stream

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3)


def _masked(length: int):
    masker = Masker(CFG.pair(), MaskSeed(b"\x31" * 32))
    weights = np.linspace(-0.5, 0.5, length, dtype=np.float32)
    _, obj = masker.mask(Scalar.unit(), weights)
    return obj


def _chunks_of(message: Message, sk, max_size: int) -> list[Chunk]:
    parts = list(MessageEncoder(message, sk, max_size))
    out = []
    for raw in parts:
        m = Message.from_bytes(raw, verify=True)
        assert m.is_multipart
        out.append(m.payload)
    return out


def _roundtrip_stream(payload, tag: Tag, max_size: int = 512):
    keys = SigningKeyPair.generate()
    msg = Message(
        participant_pk=keys.public,
        coordinator_pk=b"\x02" * 32,
        payload=payload,
        tag=tag,
    )
    builder = MessageBuilder()
    chunks = _chunks_of(msg, keys.secret, max_size)
    # deliver out of order: odd ids first, then even
    for c in sorted(chunks, key=lambda c: (c.id % 2 == 0, c.id)):
        complete = builder.add(c)
    assert complete
    return parse_payload_stream(tag, builder.take_reader())


def test_stream_parse_update_matches_direct():
    obj = _masked(300)
    seeds = {bytes([i]) * 32: b"\x07" * 80 for i in range(5)}
    from xaynet_tpu.core.mask.seed import EncryptedMaskSeed

    seeds = {k: EncryptedMaskSeed(v) for k, v in seeds.items()}
    payload = Update(
        sum_signature=b"\x0a" * 64,
        update_signature=b"\x0b" * 64,
        masked_model=obj,
        local_seed_dict=seeds,
    )
    got = _roundtrip_stream(payload, Tag.UPDATE)
    assert isinstance(got, Update)
    assert got.sum_signature == payload.sum_signature
    assert got.update_signature == payload.update_signature
    assert np.array_equal(got.masked_model.vect.data, obj.vect.data)
    assert np.array_equal(got.masked_model.unit.data, obj.unit.data)
    assert {k: v.as_bytes() for k, v in got.local_seed_dict.items()} == {
        k: v.as_bytes() for k, v in seeds.items()
    }


def test_stream_parse_sum2_matches_direct():
    obj = _masked(200)
    payload = Sum2(sum_signature=b"\x0c" * 64, model_mask=obj)
    got = _roundtrip_stream(payload, Tag.SUM2)
    assert isinstance(got, Sum2)
    assert np.array_equal(got.model_mask.vect.data, obj.vect.data)


def test_stream_parse_frees_chunks_progressively():
    reader = ChunkReader([b"ab", b"cdef", b"g"])
    assert reader.remaining == 7
    assert reader.read(3) == b"abc"
    assert len(reader._chunks) == 2
    out = np.empty(3, dtype=np.uint8)
    reader.read_into(out)
    assert bytes(out) == b"def"
    assert len(reader._chunks) == 1
    assert reader.read(1) == b"g"
    assert reader.remaining == 0
    with pytest.raises(ValueError):
        reader.read(1)


def test_stream_parse_peak_memory_bounded():
    """A large reassembled payload must not be concatenated a second time."""
    obj = _masked(2_000_000)  # 12 MB of wire bytes at 6 B/element
    payload = Sum2(sum_signature=b"\x0d" * 64, model_mask=obj)
    raw = payload.to_bytes()
    budget = 1 << 16
    chunks = [
        Chunk(id=i + 1, message_id=7, last=(i == (len(raw) - 1) // budget),
              data=raw[i * budget : (i + 1) * budget])
        for i in range(-(-len(raw) // budget))
    ]
    builder = MessageBuilder()
    for c in chunks:
        builder.add(c)
    del raw, chunks

    tracemalloc.start()
    parsed = parse_payload_stream(Tag.SUM2, builder.take_reader())
    current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    wire = 2_000_000 * CFG.bytes_per_number
    # the retained result is the limb tensor (~1.33x wire); the *transient*
    # overhead above it must stay below one wire copy — a concat-then-parse
    # would allocate the full joined payload (1x wire) plus a full-size
    # padded conversion buffer (1.33x wire) on top.
    assert peak - current < wire, f"transient {peak - current} vs wire {wire}"
    assert np.array_equal(parsed.model_mask.vect.data, obj.vect.data)


def test_stream_parsed_update_is_staged_into_its_ring_slot_as_it_arrives():
    """The reassembled (multipart) update takes the same route as a
    one-part message: its wire rows go straight into the open batch's
    ring slot on the ingest pool, and the flush relays nothing out."""
    import jax

    from xaynet_tpu.core.mask.masking import Aggregation
    from xaynet_tpu.parallel.mesh import make_mesh
    from xaynet_tpu.parallel.streaming import ROWS_STAGED
    from xaynet_tpu.server.aggregation import StagedAggregator

    obj = _masked(300)
    payload = Update(
        sum_signature=b"\x0a" * 64,
        update_signature=b"\x0b" * 64,
        masked_model=obj,
        local_seed_dict={},
    )
    got = _roundtrip_stream(payload, Tag.UPDATE).masked_model
    dev = StagedAggregator(
        CFG.pair(), 300, device=True, batch_size=2, kernel="xla",
        mesh=make_mesh(jax.devices()[:1]),
    )
    arrival, flush = (ROWS_STAGED.labels(route=r) for r in ("arrival", "flush"))
    before = arrival.value, flush.value
    for update in (got, obj):
        dev.validate_aggregation(update)
        dev.aggregate(update)  # the second fills the batch: flush
    dev.drain()
    assert (arrival.value, flush.value) == (before[0] + 2, before[1])
    host = Aggregation(CFG.pair(), 300)
    host.aggregate(obj)
    host.aggregate(obj)
    assert dev.finalize().object == host.object


# --- chunk-level send retry -------------------------------------------------


class _FlakyClient:
    """In-memory client whose Nth send fails once; records every send."""

    def __init__(self, params, fail_at: int):
        self.params = params
        self.fail_at = fail_at
        self.sent: list[bytes] = []
        self.attempts = 0

    async def get_round_params(self):
        return self.params

    async def get_sums(self):
        return {}

    async def get_seeds(self, pk):
        return {}

    async def get_model(self):
        return None

    async def send_message(self, data: bytes) -> None:
        self.attempts += 1
        if self.attempts == self.fail_at:
            raise ConnectionError("simulated chunk drop")
        self.sent.append(data)


def test_chunk_level_send_retry():
    from xaynet_tpu.core.common import RoundParameters, RoundSeed
    from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
    from xaynet_tpu.sdk.state_machine import (
        PetSettings,
        PhaseKind,
        StateMachine,
        TransitionOutcome,
    )
    from xaynet_tpu.sdk.traits import ModelStore

    class _NoModel(ModelStore):
        async def load_model(self):
            return None

    coord = EncryptKeyPair.generate()
    params = RoundParameters(
        pk=coord.public.as_bytes(),
        sum=Fraction(1),  # everyone is a sum participant
        update=Fraction(0),
        seed=RoundSeed(b"\x05" * 32),
        mask_config=CFG.pair(),
        model_length=256,  # the sum2 mask spans several 400-byte chunks
    )
    machine = StateMachine(
        PetSettings(keys=SigningKeyPair.generate(), max_message_size=400),
        _FlakyClient(params, fail_at=10**9),
        _NoModel(),
    )
    client = machine.client

    async def drive(n):
        outcomes = []
        for _ in range(n):
            outcomes.append(await machine.transition())
        return outcomes

    asyncio.run(drive(2))  # NewRound -> Sum (sends ephm key)
    assert machine.phase is PhaseKind.SUM2
    sum_parts = len(client.sent)
    assert sum_parts >= 1

    # force the sum2 step to produce a multipart message and drop one part:
    # seeds response with one seed; mask of length 64 with max_message_size
    # 400 gives several chunks
    seed = MaskSeed(b"\x2a" * 32)
    enc = seed.encrypt(machine.ephm_keys.public)
    client.get_seeds = lambda pk: _async(enc)
    client.fail_at = client.attempts + 3  # third part of the sum2 message fails

    async def _drive_until_awaiting(limit=10):
        outcomes = []
        for _ in range(limit):
            out = await machine.transition()
            outcomes.append(out)
            if machine.phase is PhaseKind.AWAITING and machine._pending is None:
                break
        return outcomes

    outcomes = asyncio.run(_drive_until_awaiting())
    assert TransitionOutcome.PENDING in outcomes  # the dropped part paused us
    assert machine.phase is PhaseKind.AWAITING
    assert machine._pending is None
    # every part was delivered exactly once, in order: reassembling them
    # yields a complete message (delivered = sent list after the sum parts)
    delivered = client.sent[sum_parts:]
    opened = [coord.secret.decrypt(p) for p in delivered]
    msgs = [Message.from_bytes(r, verify=True) for r in opened]
    assert all(m.is_multipart for m in msgs)
    ids = [m.payload.id for m in msgs]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    builder = MessageBuilder()
    complete = False
    for m in msgs:
        complete = builder.add(m.payload)
    assert complete


def _async(value):
    async def _inner():
        return {b"\x01" * 32: value} if value is not None else None

    return _inner()


def test_pending_send_survives_save_restore():
    """An in-flight multipart send serializes as ONE payload copy + cursor
    and resumes from the exact part it stopped at."""
    from xaynet_tpu.core.common import RoundParameters, RoundSeed
    from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
    from xaynet_tpu.sdk.state_machine import PetSettings, PhaseKind, StateMachine
    from xaynet_tpu.sdk.traits import ModelStore

    class _NoModel(ModelStore):
        async def load_model(self):
            return None

    class _FailingClient(_FlakyClient):
        pass

    coord = EncryptKeyPair.generate()
    params = RoundParameters(
        pk=coord.public.as_bytes(),
        sum=1.0,
        update=0.0,
        seed=RoundSeed(b"\x06" * 32),
        mask_config=CFG.pair(),
        model_length=256,
    )
    keys = SigningKeyPair.generate()
    machine = StateMachine(
        PetSettings(keys=keys, max_message_size=400),
        _FailingClient(params, fail_at=10**9),
        _NoModel(),
    )
    client = machine.client

    asyncio.run(_drive_n(machine, 2))  # -> SUM2
    # produce a multipart sum2 message and fail on its third part
    seed = MaskSeed(b"\x2b" * 32)
    enc = seed.encrypt(machine.ephm_keys.public)
    client.get_seeds = lambda pk: _async(enc)
    client.fail_at = client.attempts + 3
    asyncio.run(_drive_n(machine, 1))
    assert machine._pending is not None
    delivered_before = len(client.sent)
    next_before = machine._pending.next_index
    assert next_before == 2  # two parts through, third failed

    state = machine.save()
    assert len(state) < 64 * 1024  # cursor + one payload copy, not part list
    restored = StateMachine.restore(state, client, _NoModel())
    assert restored._pending is not None
    assert restored._pending.next_index == next_before
    client.fail_at = 10**9  # network healthy again
    asyncio.run(_drive_n(restored, 2))
    assert restored._pending is None
    assert restored.phase is PhaseKind.AWAITING

    # the full message reassembles from pre-save + post-restore parts
    opened = [coord.secret.decrypt(p) for p in client.sent[1:]]  # skip the sum msg
    msgs = [Message.from_bytes(r, verify=True) for r in opened]
    builder = MessageBuilder()
    complete = False
    for m in msgs:
        complete = builder.add(m.payload)
    assert complete


async def _drive_n(machine, n):
    for _ in range(n):
        await machine.transition()
