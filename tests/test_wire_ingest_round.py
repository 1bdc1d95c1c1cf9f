"""A coordinator that parses and checks on the chip (ISSUE 54): served rounds
under ``[aggregation] wire_ingest = true``, the road of the benchmark's cell
``resnet50-f32m6-wireingest.flood`` at a small length. The Update's vector is
parsed lazily (a view of the body), its element block goes to the device as
it lies, the device de-interleaves it and compares every element with the
order BEFORE the seed-dict insert, the accepted row stays on the device and
the flush folds the resident rows in place, in chunks of eight.

Held to the plain integer reference of ``benchmark/harness/reference.py`` bit
for bit (served rounds) and to the host ``Aggregation`` (the aggregator
alone); the device's unpack to the host's parse (``core/mask/serialization.py``,
``lazy=False``) at the order's edges.
"""

import asyncio
import dataclasses
import tracemalloc
from fractions import Fraction

import jax
import numpy as np
import pytest

from benchmark.harness import reference
from xaynet_tpu.core.crypto.encrypt import PublicEncryptKey
from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.core.mask import serialization
from xaynet_tpu.core.mask.masking import Aggregation, AggregationError, Masker
from xaynet_tpu.core.mask.model import Scalar
from xaynet_tpu.core.mask.object import LazyWireMaskVect, MaskObject
from xaynet_tpu.core.mask.seed import MaskSeed
from xaynet_tpu.core.mask.serialization import DecodeError
from xaynet_tpu.core.message import Message, Update
from xaynet_tpu.ops import limbs as host_limbs
from xaynet_tpu.ops.fold_jax import wire_to_planar
from xaynet_tpu.parallel import aggregator as aggregator_mod
from xaynet_tpu.parallel import streaming
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, PhaseKind, StateMachine as ParticipantSM
from xaynet_tpu.sdk.traits import ModelStore
from xaynet_tpu.server import stages
from xaynet_tpu.server.aggregation import StagedAggregator
from xaynet_tpu.server.requests import RequestError
from xaynet_tpu.server.rest import RestServer
from xaynet_tpu.server.services import Fetcher, PetMessageHandler, ServiceError
from xaynet_tpu.server.settings import (
    CountSettings,
    PhaseSettings,
    PetSettings as ServerPet,
    Settings,
    SettingsError,
    Sum2Settings,
    TimeSettings,
)
from xaynet_tpu.server.state_machine import StateMachineInitializer
from xaynet_tpu.storage.memory import (
    InMemoryCoordinatorStorage,
    InMemoryModelStorage,
    NoOpTrustAnchor,
)
from xaynet_tpu.storage.traits import Store
from xaynet_tpu.telemetry import tracing
from xaynet_tpu.telemetry import wire as wire_stats
from xaynet_tpu.telemetry.registry import get_registry

MASKS = {
    "integer-b0m6": MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6),  # 7 B
    "integer-b6m6": MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6),  # 10 B
    "prime-b0m3": MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3),  # 6 B
}
MODEL_LEN, DEN, SEED = 1031, 32, 54  # 1031: four devices pad a column
SUM_PROB, UPDATE_PROB = 0.4, 0.5
MIXES = {"v1": "legacy", "v2": "sdk", "mixed": None}


class _Store(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


class _LegacyClient(HttpClient):
    """A participant that predates wire v2: it serialises v1 whatever the
    round advertises."""

    async def get_round_params(self):
        return dataclasses.replace(await super().get_round_params(), wire_format=1)


@pytest.fixture(params=[1, 4], ids=["one-device", "four-devices"])
def devices(request, monkeypatch, tmp_path):
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    n = request.param
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:n]))
    return n


def _settings(config: MaskConfig, n_update: int, batch: int) -> Settings:
    window = TimeSettings(min=0.0, max=120.0)
    s = Settings(pet=ServerPet(
        sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(1, 1), time=window),
        update=PhaseSettings(prob=UPDATE_PROB, time=window,
                             count=CountSettings(n_update, n_update, quorum=batch)),
        sum2=Sum2Settings(count=CountSettings(1, 1), time=window),
    ))
    s.model.length = MODEL_LEN
    s.mask.group_type, s.mask.data_type = config.group_type, config.data_type
    s.mask.bound_type, s.mask.model_type = config.bound_type, config.model_type
    s.aggregation.device = True
    s.aggregation.wire_ingest = True
    s.aggregation.batch_size = batch
    s.ingest.wire_format = "packed"  # the SDK sends v2; a _LegacyClient v1 all the same
    s.validate()
    assert s.aggregation.packed_staging and s.aggregation.kernel == "auto"  # as shipped
    return s


def _sample(name: str, labels: dict | None = None) -> float:
    return get_registry().sample_value(name, labels) or 0.0


def _counters() -> dict:
    out = {("wire", w, r): _sample("xaynet_update_wire_bytes_total", {"wire": w, "route": r})
           for w in ("packed", "legacy") for r in ("copy", "relayout", "device")}
    for stage in ("staged", "folded", "failed"):
        out[stage] = streaming.BATCHES_TOTAL.labels(stage=stage).value
    for outcome in ("accepted", "rejected"):
        out[outcome] = _sample("xaynet_messages_total", {"phase": "update", "outcome": outcome})
        out["verdict", outcome] = wire_stats.VERDICTS.labels(outcome=outcome).value
    out["device_bytes"] = wire_stats.DEVICE_BYTES.value
    for stage in ("ingest_h2d", "ingest_unpack", "validate"):
        out["stage", stage] = stages.SECONDS.labels(stage=stage, phase="update").count
    out["rows_arrival"] = streaming.ROWS_STAGED.labels(route="arrival").value
    out["parse"] = _sample("xaynet_codec_elements_total", {"op": "parse", "route": "fast"}) \
        + _sample("xaynet_codec_elements_total", {"op": "parse", "route": "generic"})
    return out


def _with_element(position: int, value: int):
    def tamper(obj):
        obj.vect.data[position] = host_limbs.int_to_limbs(value, obj.vect.data.shape[1])
        return obj
    return tamper


def _forged(config: MaskConfig, params, sums: dict, index: int, planar: bool, tamper):
    """Participant ``index``'s sealed Update composed as the benchmark's forge
    does, its masked limbs passed through ``tamper`` first. Returns the sealed
    bytes and the participant's public key."""
    round_seed = params.seed.as_bytes()
    keys = keys_for_task(round_seed, params.sum, params.update, "update", start=(500 + index) * 1000)
    w = reference.to_f32(reference.weights_fixed(SEED, index, MODEL_LEN))
    mseed = MaskSeed(bytes([index % 251]) * 32)
    _, obj = Masker(config.pair(), mseed).mask(Scalar.from_fraction(Fraction(1, DEN)), w)
    payload = Update(
        sum_signature=keys.sign(round_seed + b"sum").as_bytes(),
        update_signature=keys.sign(round_seed + b"update").as_bytes(),
        masked_model=tamper(obj),
        local_seed_dict={pk: mseed.encrypt(PublicEncryptKey(e)) for pk, e in sums.items()},
        wire_planar=planar,
    )
    message = Message(participant_pk=keys.public, coordinator_pk=params.pk, payload=payload)
    return PublicEncryptKey(params.pk).encrypt(message.to_bytes(keys.secret)), keys.public


async def _served_round(settings: Settings, senders: list[str], forged=None) -> dict:
    """One PET round over the REST API on localhost, the message handler told
    of the settings as ``server/runner.py`` tells it. ``senders[i]``: ``"sdk"``
    (follows the round's ``wire_format``: v2) or ``"legacy"`` (v1).
    ``forged(params, sums)`` gives ``[(sealed, pk)]`` to POST after the first
    sender. Returns the model, what the counters moved by, what the handler
    said of each forged message and whose it was, and the round's spans."""
    from xaynet_tpu.server.aggregation import slots_take_planes

    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(settings, store).init()
    fetcher = Fetcher(events)
    handler = PetMessageHandler(events, request_tx, wire_ingest=settings.aggregation.wire_ingest,
                                update_planes=slots_take_planes(settings))
    rest = RestServer(fetcher, handler)
    host, port = await rest.start("127.0.0.1", 0)
    url = f"http://{host}:{port}"
    machine_task = asyncio.create_task(machine.run())
    clients = []

    def client(kind=HttpClient):
        clients.append(kind(url))
        return clients[-1]

    tracer = tracing.get_tracer()
    mode = tracer.mode
    tracer.configure(mode="on")
    try:
        while fetcher.phase().value != "sum":
            await asyncio.sleep(0.005)
        params = fetcher.round_params()
        seed = params.seed.as_bytes()
        summer = ParticipantSM(
            PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum"),
                        device_sum2=False, max_message_size=None),
            client(), _Store(None))
        updaters = [
            ParticipantSM(
                PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update",
                                               start=(10 + i) * 1000),
                            scalar=Fraction(1, DEN), max_message_size=None),
                client(_LegacyClient if kind == "legacy" else HttpClient),
                _Store(reference.to_f32(reference.weights_fixed(SEED, i, MODEL_LEN))))
            for i, kind in enumerate(senders)]

        async def drive_summer():
            while fetcher.model() is None:
                await summer.transition()
                await asyncio.sleep(0.005)

        sum_task = asyncio.create_task(drive_summer())
        while fetcher.phase().value != "update":
            await asyncio.sleep(0.005)
        t0, before, answers, forged_pks = tracing.time.monotonic(), _counters(), [], []
        for i, sm in enumerate(updaters):
            sent = False
            while not (sent and sm.phase is PhaseKind.AWAITING):
                await sm.transition()
                sent = sent or sm.phase is PhaseKind.UPDATE
            if i == 0 and forged is not None:
                for sealed, pk in forged(params, fetcher.sum_dict()):
                    forged_pks.append(pk)
                    try:
                        await handler.handle_message(sealed)
                        answers.append(None)
                    except (ServiceError, RequestError) as err:
                        answers.append((type(err).__name__, str(err)))
        await sum_task
        moved = {key: value - before[key] for key, value in _counters().items()}
        return {"model": np.asarray(fetcher.model(), dtype=np.float64), "moved": moved,
                "answers": answers, "forged_pks": forged_pks,
                "healthz": aggregator_mod.fold_kernel_report(),
                "resident_max": wire_stats.RESIDENT_ROWS_MAX.value,
                "spans": [s for s in tracer.ring_spans() if s.start >= t0]}
    finally:
        tracer.configure(mode=mode)
        machine_task.cancel()
        for c in clients:
            c.close()
        await rest.stop()
        await asyncio.gather(machine_task, return_exceptions=True)


def _run(settings: Settings, senders: list[str], forged=None) -> dict:
    return asyncio.run(asyncio.wait_for(_served_round(settings, senders, forged), 240))


def _senders(mix: str, n: int) -> list[str]:
    if MIXES[mix] is not None:
        return [MIXES[mix]] * n
    return [("sdk", "legacy", "legacy", "sdk", "legacy")[i % 5] for i in range(n)]  # both in every batch


def _reference(config: MaskConfig, accepted: list[int]) -> np.ndarray:
    model, _mean = reference.reference_model(
        SEED, accepted, MODEL_LEN, DEN, int(config.add_shift), config.exp_shift,
        np.arange(MODEL_LEN))
    return model


def _same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and np.array_equal(a.view(np.uint64), b.view(np.uint64))


# --- served rounds --------------------------------------------------------------


@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("mask", list(MASKS))
def test_served_wire_ingest_round_equals_the_plain_reference(mask, mix, devices):
    """Two flushes of three on either wire and mixed in one batch: the model
    is the reference's bit for bit, every vector went to the device as a view
    (no host parse of a vector, no slot, no ring), each flush is one batch of
    the pipeline's counters, and the turn's two stages are on the histogram."""
    config, batch = MASKS[mask], 3
    n_update = 2 * batch
    senders = _senders(mix, n_update)
    out = _run(_settings(config, n_update, batch), senders)
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))

    moved, block = out["moved"], config.bytes_per_number * MODEL_LEN
    n_v2 = senders.count("sdk")
    assert (moved["accepted"], moved["rejected"], moved["failed"]) == (n_update, 0, 0)
    assert moved["wire", "packed", "device"] == n_v2 * block
    assert moved["wire", "legacy", "device"] == (n_update - n_v2) * block
    assert sum(v for k, v in moved.items() if k[0] == "wire") == n_update * block  # wire.device_share 100
    assert moved["device_bytes"] == n_update * block
    assert (moved["verdict", "accepted"], moved["verdict", "rejected"]) == (n_update, 0)
    # one batch a flush, not one a chunk or a layout
    assert (moved["staged"], moved["folded"]) == (2, 2)
    assert moved["rows_arrival"] == 0  # nothing was staged on the host
    assert moved["stage", "ingest_h2d"] == moved["stage", "ingest_unpack"] \
        == moved["stage", "validate"] == n_update
    assert out["resident_max"] == batch
    # /healthz device.fold says what folded, as it does of a queued batch
    fold = out["healthz"]
    assert (fold["model_length"], fold["n_limbs"], fold["bytes_per_number"]) == (
        MODEL_LEN, host_limbs.n_limbs_for_order(config.order), config.bytes_per_number)
    assert fold["kernel"] == "xla" and fold["shards"] == devices
    assert fold["wire"] == {"packed": senders[-batch:].count("sdk"),
                            "legacy": senders[-batch:].count("legacy"), "copied": 0}


@pytest.mark.parametrize("mix", ["v1", "mixed"])
def test_a_batch_of_twelve_folds_in_chunks_of_eight_and_four_twice(mix, devices):
    """The cell's own shape at a small length: 24 uploads, two flushes of 12
    resident rows, each folded as 8 + 4."""
    config, batch = MASKS["integer-b0m6"], 12
    chunks = []
    real = ShardedAggregator._make_stack_fn

    def spied(self):
        fn = real(self)
        return lambda *rows: chunks.append(len(rows)) or fn(*rows)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ShardedAggregator, "_make_stack_fn", spied)
        senders = _senders(mix, 2 * batch)
        out = _run(_settings(config, 2 * batch, batch), senders)
    assert _same_bits(out["model"], _reference(config, list(range(2 * batch))))
    if mix == "v1":
        assert chunks == [8, 4, 8, 4]
    else:  # a layout at a time: the packed rows' chunks, then the planar rows'
        assert sorted(chunks) == sorted(
            c for lo in (0, batch) for kind in ("sdk", "legacy")
            for c in _chunks(senders[lo:lo + batch].count(kind)))
    assert (out["moved"]["staged"], out["moved"]["folded"], out["moved"]["failed"]) == (2, 2, 0)
    assert out["resident_max"] == 12
    folds = [s for s in out["spans"] if s.name == "stream.fold"]
    assert [(s.attrs["how"], s.attrs["k"]) for s in folds] == [("resident", 12)] * 2


def _chunks(n: int) -> list[int]:
    return [8] * (n // 8) + ([n % 8] if n % 8 else [])


def test_the_turns_stages_are_spans_inside_validate_with_their_labels(devices):
    config, batch = MASKS["integer-b0m6"], 3
    out = _run(_settings(config, batch, batch), ["legacy", "sdk", "legacy"])
    by_id = {s.span_id: s for s in out["spans"]}
    h2d = [s for s in out["spans"] if s.name == "ingest.h2d"]
    unpack = [s for s in out["spans"] if s.name == "ingest.unpack"]
    assert len(h2d) == len(unpack) == batch
    block = config.bytes_per_number * MODEL_LEN
    for put, check in zip(h2d, unpack):
        assert put.attrs["bytes"] == block and put.attrs["route"] == check.attrs["route"] == "device"
        assert put.attrs["wire"] == check.attrs["wire"] and put.attrs["phase"] == "update"
        assert put.attrs["rid"] and put.attrs["rid"] == check.attrs["rid"]
        # children of the message's update.validate, the link before the kernel
        parent = by_id[put.parent_id]
        assert parent.name == "update.validate" and check.parent_id == put.parent_id
        assert parent.attrs["route"] == "device" and parent.attrs["rid"] == put.attrs["rid"]
        assert parent.start <= put.start <= check.start
        assert check.start + check.duration <= parent.start + parent.duration + 1e-3
    assert [s.attrs["wire"] for s in h2d] == ["legacy", "packed", "legacy"]
    # mirrored into the profiler's trace like the stages that exist
    assert {"ingest.h2d", "ingest.unpack"} <= set(tracing.mirrored_span_names())
    assert stages._SPANS["ingest_h2d"] == "ingest.h2d"


@pytest.mark.parametrize("wire", ["v1", "v2"])
def test_an_invalid_member_is_rejected_before_its_seed_dict_insert(wire, devices):
    """One element equal to the order (at the first, at the last position):
    the device says so, the message is answered rejected, its sender is in no
    seed dictionary and adds nothing to the sum; its neighbours in the batch
    are the reference's."""
    config, batch = MASKS["integer-b0m6"], 3
    n_update, planar = 2 * batch, wire == "v2"
    inserted = []
    real_add = InMemoryCoordinatorStorage.add_local_seed_dict

    async def add(self, pk, local):
        inserted.append(pk)
        return await real_add(self, pk, local)

    def forged(params, sums):
        return [_forged(config, params, sums, 90 + j, planar, _with_element(pos, value))
                for j, (pos, value) in enumerate([(0, config.order), (MODEL_LEN - 1, config.order + 1)])]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(InMemoryCoordinatorStorage, "add_local_seed_dict", add)
        out = _run(_settings(config, n_update, batch), _senders(wire, n_update), forged)
    assert out["answers"] == [("RequestError", "message rejected: InvalidObject")] * 2 \
        or all(a and a[0] == "RequestError" and "InvalidObject" in a[1] for a in out["answers"])
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))
    moved = out["moved"]
    assert (moved["accepted"], moved["rejected"], moved["failed"]) == (n_update, 2, 0)
    assert (moved["verdict", "accepted"], moved["verdict", "rejected"]) == (n_update, 2)
    # the seed dictionary took the accepted senders' entries and no other
    assert len(set(inserted)) == len(inserted) == n_update
    assert len(out["forged_pks"]) == 2 and not set(out["forged_pks"]) & set(inserted)
    assert (moved["staged"], moved["folded"]) == (2, 2)


# --- the aggregator alone, against the host Aggregation ------------------------


def _masked(config: MaskConfig, index: int) -> MaskObject:
    w = reference.to_f32(reference.weights_fixed(SEED, index, MODEL_LEN))
    _, obj = Masker(config.pair(), MaskSeed(bytes([index + 1]) * 32)).mask(
        Scalar.from_fraction(Fraction(1, DEN)), w)
    return obj


def _lazy(obj: MaskObject, planar: bool) -> MaskObject:
    """``obj`` as the lazy parse of its serialised form gives it."""
    blob = serialization.serialize_mask_vect(obj.vect, planar=planar)
    vect, _ = serialization.parse_mask_vect(blob, lazy=True)
    assert isinstance(vect, LazyWireMaskVect) and not vect.checked and vect.planar is planar
    return MaskObject(vect, obj.unit)


@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("mask", list(MASKS))
def test_staged_aggregator_under_wire_ingest_equals_the_host_aggregation(mask, mix, devices):
    config = MASKS[mask]
    pair, n = config.pair(), 13  # 12 + 1: a flush of 8 + 4 and a remainder
    host = Aggregation(pair, MODEL_LEN)
    agg = StagedAggregator(pair, MODEL_LEN, device=True, batch_size=12, wire_ingest=True)
    planar = {"v1": [False] * n, "v2": [True] * n, "mixed": [i % 3 == 0 for i in range(n)]}[mix]
    for i in range(n):
        obj = _masked(config, i)
        host.validate_aggregation(obj)
        host.aggregate(obj)
        lazy = _lazy(obj, planar[i])
        agg.validate_aggregation(lazy)
        assert not lazy.vect.materialized and lazy.vect._staged_planar is not None
        agg.aggregate(lazy)
    vect, unit, nb = agg.snapshot_state()
    assert nb == host.nb_models == n
    assert np.array_equal(vect, host.object.vect.data) and np.array_equal(unit, host.object.unit.data)


# --- the device's unpack against the host's parse -------------------------------


def _block_with(config: MaskConfig, n: int, position: int, value: int) -> np.ndarray:
    """``n`` valid elements as a v1 element block ``uint8[n * bpn]``, ``value``
    at ``position``."""
    bpn, order = config.bytes_per_number, config.order
    rng = np.random.default_rng([n, position, bpn])
    rows = np.frombuffer(rng.bytes(n * bpn), dtype=np.uint8).reshape(n, bpn).copy()
    rows[:, -1] %= order.to_bytes(bpn, "little")[-1]  # the top byte under the order's
    rows[position] = np.frombuffer(value.to_bytes(bpn, "little"), dtype=np.uint8)
    return rows.reshape(-1)


@pytest.mark.parametrize("wire", ["v1", "v2"])
@pytest.mark.parametrize("what", ["order-1", "order", "order+1", "all-ones"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_device_unpack_equals_the_host_parse_at_the_orders_edges(mask, what, wire, devices):
    config, n = MASKS[mask], 4099
    order, bpn = config.order, config.bytes_per_number
    value = {"order-1": order - 1, "order": order, "order+1": order + 1,
             "all-ones": (1 << (8 * bpn)) - 1}[what]
    block = _block_with(config, n, n - 1 if what == "order" else 17, value)
    # the plain reference: the host's eager parse of the same vector
    head = config.to_bytes() + (n).to_bytes(4, "big")
    try:
        host, _ = serialization.parse_mask_vect(head + block.tobytes(), lazy=False)
        want = wire_to_planar(host.data[None])[0]
    except DecodeError:
        want = None
    assert (want is not None) == (value < order)
    agg = ShardedAggregator(config, n, kernel="xla")
    pad = agg.padded_length - n
    if wire == "v1":
        row = agg.validate_wire_update(block)
    else:
        planes = np.ascontiguousarray(block.reshape(n, bpn).T)
        row = agg.validate_planar_update(planes)
        if row is not None:
            assert row.dtype == np.uint8 and row.shape == (bpn, agg.padded_length)
            assert np.array_equal(np.asarray(row)[:, :n], planes)
            row = host_limbs.unpack_planar(np.asarray(row), agg.n_limbs)
    if want is None:
        assert row is None
    else:
        assert row.shape == (agg.n_limbs, agg.padded_length)
        assert np.array_equal(np.asarray(row)[:, :n], want)
        assert not np.asarray(row)[:, n:].any() and pad == (-n) % devices


@pytest.mark.parametrize("lead", [(), (3,)], ids=["one-update", "a-group-of-three"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_the_deinterleave_loop_places_every_pass_the_rest_and_the_tail(mask, lead, monkeypatch):
    """Three whole passes of the loop, five rows past them and 40 elements past
    the last whole row (25,557,032 leaves 40 too), against numpy."""
    from xaynet_tpu.ops import limbs_jax

    monkeypatch.setattr(limbs_jax, "_BLOCK_ROWS", 8)
    config = MASKS[mask]
    bpn, n_limbs = config.bytes_per_number, host_limbs.n_limbs_for_bytes(config.bytes_per_number)
    n = (3 * 8 + 5) * 128 + 40
    data = np.frombuffer(
        np.random.default_rng([bpn, len(lead)]).bytes(int(np.prod(lead, dtype=int)) * n * bpn),
        dtype=np.uint8,
    ).reshape(*lead, n * bpn)
    wide = np.zeros((*lead, n, 4 * n_limbs), dtype=np.uint8)
    wide[..., :bpn] = data.reshape(*lead, n, bpn)
    want = np.moveaxis(wide.view("<u4"), -1, -2)  # [..., L, n]
    got = jax.jit(lambda d: limbs_jax.wire_bytes_to_planar(d, n, bpn))(data)
    assert got.shape == (*lead, n_limbs, n) and got.dtype == np.uint32
    assert np.array_equal(np.asarray(got), want)


# --- a resident fold is a batch --------------------------------------------------


def _resident_rows(agg: ShardedAggregator, config: MaskConfig, layouts: list[bool]):
    rows, host = [], Aggregation(config.pair(), MODEL_LEN)
    for i, planar in enumerate(layouts):
        obj = _masked(config, i)
        host.aggregate(obj)
        block = _lazy(obj, planar).vect
        rows.append(agg.validate_planar_update(block.planar_block) if planar
                    else agg.validate_wire_update(block.wire_block))
    return rows, host


@pytest.mark.parametrize("layouts", ["planar", "packed", "mixed"])
def test_a_resident_fold_counts_one_batch_and_folded_only_when_the_result_is_ready(
        layouts, devices, monkeypatch):
    config = MASKS["integer-b0m6"]
    agg = ShardedAggregator(config, MODEL_LEN, kernel="auto")
    stream = streaming.StreamingAggregator(agg, max_batch=12)
    which = {"planar": [False] * 12, "packed": [True] * 12,
             "mixed": [i % 2 == 0 for i in range(12)]}[layouts]
    rows, host = _resident_rows(agg, config, which)
    folded = streaming.BATCHES_TOTAL.labels(stage="folded")
    staged = streaming.BATCHES_TOTAL.labels(stage="staged")
    f0, s0 = folded.value, staged.value
    seen, real = [], jax.block_until_ready

    def ready(x):
        seen.append((staged.value - s0, folded.value - f0, agg.nb_models))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", ready)
    tracer = tracing.get_tracer()
    mode, t0 = tracer.mode, tracing.time.monotonic()
    tracer.configure(mode="on")
    try:
        stream.fold_resident_rows_now(rows)
        spans = [s for s in tracer.ring_spans() if s.name == "stream.fold" and s.start >= t0]
    finally:
        tracer.configure(mode=mode)
    # the last wait is the batch's own: staged counted, every chunk's fold
    # dispatched and credited, folded not yet
    assert seen[-1] == (1, 0, 12)
    assert (staged.value - s0, folded.value - f0) == (1, 1)
    assert [(s.attrs["how"], s.attrs["k"]) for s in spans] == [("resident", 12)]
    stream.drain()
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    stream.close()


def test_a_resident_fold_that_raises_counts_failed_and_not_folded(devices, monkeypatch):
    config = MASKS["integer-b0m6"]
    agg = ShardedAggregator(config, MODEL_LEN, kernel="xla")
    stream = streaming.StreamingAggregator(agg, max_batch=4)
    rows, _host = _resident_rows(agg, config, [False] * 4)
    before = {s: streaming.BATCHES_TOTAL.labels(stage=s).value for s in ("staged", "folded", "failed")}

    def boom(*_a, **_k):
        raise RuntimeError("the fold failed")

    monkeypatch.setattr(ShardedAggregator, "_make_stack_fn", lambda self: boom)
    with pytest.raises(RuntimeError, match="the fold failed"):
        stream.fold_resident_rows_now(rows)
    moved = {s: streaming.BATCHES_TOTAL.labels(stage=s).value - v for s, v in before.items()}
    assert moved == {"staged": 1, "folded": 0, "failed": 1}
    stream.close()


# --- the body goes to the chip as it lies ------------------------------------------


@pytest.mark.parametrize("road", ["v1-one-device", "v2-one-device", "v1-four-devices"])
def test_the_k1_road_makes_no_host_array_of_the_bodys_size(road, monkeypatch):
    """The message's view is put as it is: no stack, no copy, no host pad.
    numpy's allocations are traced; a copy of the body would be one of 7 MB.
    (A v2 body over a mesh goes a device's columns at a time, strided views
    which the runtime lays out itself: not numpy's, and not held to this.)"""
    n_dev = 4 if road.endswith("four-devices") else 1
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:n_dev]))
    config, n = MASKS["integer-b0m6"], 1_000_003  # four devices pad a column
    bpn = config.bytes_per_number
    block = _block_with(config, n, 5, config.order - 1)
    if road.startswith("v2"):
        block = np.ascontiguousarray(block.reshape(n, bpn).T).reshape(-1)
    body = bytearray(64) + bytearray(block.tobytes())  # a message: the block lies at an offset
    view = np.frombuffer(body, dtype=np.uint8, count=n * bpn, offset=64)
    if road.startswith("v2"):
        view = view.reshape(bpn, n)
    agg = ShardedAggregator(config, n, kernel="xla")
    validate = agg.validate_planar_update if road.startswith("v2") else agg.validate_wire_update
    assert validate(view) is not None  # compiled and warm
    grouped = agg.validate_planar_updates if road.startswith("v2") else agg.validate_wire_updates
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[0]
        row = validate(view)
        one = tracemalloc.get_traced_memory()[1] - base
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[0]
        assert grouped([view])[0] is not None
        group = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert row is not None
    assert one < 0.02 * view.nbytes, one
    assert group >= view.nbytes  # the coalescer's entry stacks its members, as it did


# --- HBM is bounded before the round -------------------------------------------------


N_RESNET, GIB16 = 25_557_032, 16 * 2**30


def test_resident_footprint_is_the_rows_a_chunk_the_fold_and_a_body():
    row = 4 * 2 * N_RESNET  # a v1 row at 2 limbs: 204.5 MB
    assert row == 204_456_256
    at12 = aggregator_mod.resident_footprint(12, 2, 7, N_RESNET)
    assert at12 == 12 * row + 8 * row + 4 * row + int(1.1 * 9 * row) + 7 * N_RESNET
    assert 0.25 * GIB16 < at12 < 0.5 * GIB16
    assert aggregator_mod.resident_footprint(64, 2, 7, N_RESNET) > GIB16
    fits = aggregator_mod.resident_rows_that_fit(GIB16, 2, 7, N_RESNET)
    assert 12 < fits < 64
    assert aggregator_mod.resident_footprint(fits, 2, 7, N_RESNET) <= GIB16 \
        < aggregator_mod.resident_footprint(fits + 1, 2, 7, N_RESNET)
    # over four devices each holds a quarter of every row
    assert aggregator_mod.resident_rows_that_fit(GIB16, 2, 7, N_RESNET // 4) > 4 * fits


@pytest.mark.parametrize("batch,fits", [(64, False), (12, True)])
def test_the_aggregator_refuses_a_batch_size_the_device_cannot_hold(batch, fits, monkeypatch):
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))
    monkeypatch.setattr(aggregator_mod, "device_memory_limit", lambda mesh: GIB16)
    pair = MASKS["integer-b0m6"].pair()
    if fits:
        agg = StagedAggregator(pair, N_RESNET, device=True, batch_size=batch, wire_ingest=True)
        assert agg.batch_size == 12
        agg._stream.close()
        return
    with pytest.raises(SettingsError) as refused:
        StagedAggregator(pair, N_RESNET, device=True, batch_size=batch, wire_ingest=True)
    largest = aggregator_mod.resident_rows_that_fit(GIB16, 2, 7, N_RESNET)
    assert f"batch_size = 64 at model length {N_RESNET}" in str(refused.value)
    assert str(refused.value).endswith(f"the largest aggregation.batch_size that fits is {largest}")


def test_the_check_is_wire_ingests_alone_and_needs_a_reported_limit(monkeypatch):
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))
    pair = MASKS["integer-b0m6"].pair()
    # the CPU backend reports no limit: nothing is checked
    assert aggregator_mod.device_memory_limit(make_mesh(jax.devices()[:1])) is None
    StagedAggregator(pair, MODEL_LEN, device=True, batch_size=64, wire_ingest=True)._stream.close()
    # the copy road stages on the host and is not held to it
    monkeypatch.setattr(aggregator_mod, "device_memory_limit", lambda mesh: 1024)
    StagedAggregator(pair, MODEL_LEN, device=True, batch_size=64)._stream.close()
    with pytest.raises(SettingsError, match="largest aggregation.batch_size that fits is 0"):
        StagedAggregator(pair, MODEL_LEN, device=True, batch_size=64, wire_ingest=True)


def test_a_rejected_update_raises_invalid_object_and_stages_nothing(devices):
    config = MASKS["prime-b0m3"]
    agg = StagedAggregator(config.pair(), MODEL_LEN, device=True, batch_size=4, wire_ingest=True)
    bad = _masked(config, 1)
    bad.vect.data[MODEL_LEN // 2] = host_limbs.int_to_limbs(config.order, bad.vect.data.shape[1])
    for planar in (False, True):
        lazy = _lazy(bad, planar)
        with pytest.raises(AggregationError, match="InvalidObject"):
            agg.validate_aggregation(lazy)
        assert lazy.vect._staged_planar is None and not lazy.vect.materialized
    assert agg.pending == 0 and agg.nb_models == 0
    agg._stream.close()
