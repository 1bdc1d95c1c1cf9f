"""A federation on the packed wire (ISSUE 50): served rounds under ``[ingest]
wire_format = "packed"`` on the coordinator as the benchmark's cells run it
(``[aggregation] device = true``, packed staging, ``wire_ingest = false``),
held to the plain integer reference of ``benchmark/harness/reference.py``
bit for bit and to the all-v1 round byte for byte. It is the benchmark's
cell ``resnet50-f32m6-packedwire.flood`` at a small length: real sealed
Update messages, serialised ``wire_planar`` by the SDK's own path because
the round parameters say ``wire_format = 2``, two fold batches, on one
device and on four.

A v2 vector goes from the message into its staging slot as planes: parsed as
a view, every element checked against the order on the planes, copied into
the slot. The transposing fallback (``planar_to_interleaved``) and the limb
parse never run for it; bodies that are invalid, truncated or of the wrong
length are refused where a v1 body is, with the same answer.
"""

import asyncio
import dataclasses
import logging
from fractions import Fraction

import jax
import numpy as np
import pytest

from benchmark.harness import reference
from xaynet_tpu.core.crypto.encrypt import PublicEncryptKey
from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.core.mask import serialization
from xaynet_tpu.core.mask.masking import Masker
from xaynet_tpu.core.mask.model import Scalar
from xaynet_tpu.core.mask.seed import MaskSeed
from xaynet_tpu.core.mask.serialization import DecodeError
from xaynet_tpu.core.message import Message, Update
from xaynet_tpu.ops import limbs as host_limbs
from xaynet_tpu.parallel import aggregator as aggregator_mod
from xaynet_tpu.parallel import streaming
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, PhaseKind, StateMachine as ParticipantSM
from xaynet_tpu.sdk.traits import ModelStore
from xaynet_tpu.server.requests import RequestError
from xaynet_tpu.server.rest import RestServer
from xaynet_tpu.server.services import Fetcher, PetMessageHandler, ServiceError
from xaynet_tpu.server.settings import (
    CountSettings,
    PhaseSettings,
    PetSettings as ServerPet,
    Settings,
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
from xaynet_tpu.telemetry import wire as wire_stats
from xaynet_tpu.telemetry.registry import get_registry
from xaynet_tpu.tenancy.pool import get_pool
from xaynet_tpu.utils import native

MASKS = {
    "integer-b0m6": MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6),
    "integer-b6m6": MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6),
    "prime-b0m3": MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3),
}
K, MODEL_LEN, DEN, SEED = 3, 1031, 8, 50
SUM_PROB, UPDATE_PROB = 0.4, 0.5
WIRES = ("packed", "legacy")
ROUTES = ("copy", "relayout", "device")


class _Store(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


class _LegacyClient(HttpClient):
    """A participant that predates wire v2: whatever the round advertises, it
    serialises v1 (a packed round accepts it: the parse reads each message's
    own flag)."""

    async def get_round_params(self):
        return dataclasses.replace(await super().get_round_params(), wire_format=1)


@pytest.fixture(params=[1, 4], ids=["one-device", "four-devices"])
def devices(request, monkeypatch, tmp_path):
    """The chip has one device and a four-chip host four; the tests' CPU
    backend has eight."""
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    n = request.param
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:n]))
    return n


@pytest.fixture
def fallback_calls(monkeypatch):
    """How often the transposing fallback ran."""
    calls, real = [], serialization.planar_to_interleaved

    def counted(block, count, bpn):
        calls.append(count)
        return real(block, count, bpn)

    monkeypatch.setattr(serialization, "planar_to_interleaved", counted)
    return calls


def _settings(config: MaskConfig, n_update: int, wire_format: str) -> Settings:
    window = TimeSettings(min=0.0, max=120.0)
    s = Settings(pet=ServerPet(
        sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(1, 1), time=window),
        update=PhaseSettings(prob=UPDATE_PROB, time=window,
                             count=CountSettings(n_update, n_update, quorum=K)),
        sum2=Sum2Settings(count=CountSettings(1, 1), time=window),
    ))
    s.model.length = MODEL_LEN
    s.mask.group_type, s.mask.data_type = config.group_type, config.data_type
    s.mask.bound_type, s.mask.model_type = config.bound_type, config.model_type
    s.aggregation.device = True
    s.aggregation.batch_size = K
    s.ingest.wire_format = wire_format
    assert s.aggregation.packed_staging and not s.aggregation.wire_ingest  # as shipped
    return s


def _sample(name: str, labels: dict | None = None) -> float:
    return get_registry().sample_value(name, labels) or 0.0


def _counters() -> dict:
    out = {("wire", w, r): _sample("xaynet_update_wire_bytes_total", {"wire": w, "route": r})
           for w in WIRES for r in ROUTES}
    out.update({("codec", op, route): _sample("xaynet_codec_elements_total",
                                              {"op": op, "route": route})
                for op in ("parse", "validate", "stage") for route in ("fast", "generic")})
    out["rows"] = streaming.ROWS_STAGED.labels(route="arrival").value
    out["accepted"] = _sample("xaynet_messages_total", {"phase": "update", "outcome": "accepted"})
    out["rejected"] = _sample("xaynet_messages_total", {"phase": "update", "outcome": "rejected"})
    out["failed"] = streaming.BATCHES_TOTAL.labels(stage="failed").value
    out["folded"] = streaming.BATCHES_TOTAL.labels(stage="folded").value
    return out


def _forged(config: MaskConfig, params, sums: dict, index: int, planar: bool,
            tamper=None) -> bytes:
    """Participant ``index``'s sealed Update composed as the benchmark's forge
    does (``Update`` > ``Message`` > sealed box), its masked limbs passed
    through ``tamper`` first."""
    round_seed = params.seed.as_bytes()
    keys = keys_for_task(round_seed, params.sum, params.update, "update", start=(500 + index) * 1000)
    w = reference.to_f32(reference.weights_fixed(SEED, index, MODEL_LEN))
    mseed = MaskSeed(bytes([index % 251]) * 32)
    _, obj = Masker(config.pair(), mseed).mask(Scalar.from_fraction(Fraction(1, DEN)), w)
    if tamper is not None:
        obj = tamper(obj)
    payload = Update(
        sum_signature=keys.sign(round_seed + b"sum").as_bytes(),
        update_signature=keys.sign(round_seed + b"update").as_bytes(),
        masked_model=obj,
        local_seed_dict={pk: mseed.encrypt(PublicEncryptKey(e)) for pk, e in sums.items()},
        wire_planar=planar,
    )
    message = Message(participant_pk=keys.public, coordinator_pk=params.pk, payload=payload)
    return PublicEncryptKey(params.pk).encrypt(message.to_bytes(keys.secret))


async def _served_round(settings: Settings, senders: list[str], forged=(), **pipeline) -> dict:
    """One PET round over the REST API on localhost. ``senders[i]`` says what
    participant ``i`` runs: ``"sdk"`` (the SDK as shipped: it follows the
    round's ``wire_format``) or ``"legacy"`` (an SDK that sends v1 whatever the
    round says). ``forged(config, params, sums)`` may give sealed messages to
    POST after the first sender: ``[(sealed, expected error stage or None)]``.
    ``pipeline``: what the runner would tell the message handler of these
    settings (``wire_ingest``, ``update_planes``); nothing, as these tests ran
    before the handler could be told of its consumer's slots.
    Returns the model, what the counters moved by over the round, and what
    the handler said of each forged message."""
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(settings, store).init()
    fetcher = Fetcher(events)
    handler = PetMessageHandler(events, request_tx, **pipeline)
    rest = RestServer(fetcher, handler)
    host, port = await rest.start("127.0.0.1", 0)
    url = f"http://{host}:{port}"
    machine_task = asyncio.create_task(machine.run())
    clients = []

    def client(kind=HttpClient):
        clients.append(kind(url))
        return clients[-1]

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
        before, answers = _counters(), []
        for i, sm in enumerate(updaters):
            sent = False
            while not (sent and sm.phase is PhaseKind.AWAITING):
                await sm.transition()
                sent = sent or sm.phase is PhaseKind.UPDATE
            if i == 0 and forged:
                poster = client()
                for sealed in forged(params, fetcher.sum_dict()):
                    # over the socket: the sender's answer; beside it, what
                    # the handler says of the same bytes
                    await poster.send_message(sealed)
                    try:
                        await handler.handle_message(sealed)
                        answers.append(None)
                    except (ServiceError, RequestError) as err:
                        answers.append((type(err).__name__, str(err)))
        await sum_task
        moved = {key: value - before[key] for key, value in _counters().items()}
        return {"model": np.asarray(fetcher.model(), dtype=np.float64), "moved": moved,
                "answers": answers, "depth": streaming.STAGING_DEPTH.value,
                "healthz": aggregator_mod.fold_kernel_report()}
    finally:
        machine_task.cancel()
        for c in clients:
            c.close()
        await rest.stop()
        await asyncio.gather(machine_task, return_exceptions=True)


def _run(settings: Settings, senders: list[str], forged=(), **pipeline) -> dict:
    return asyncio.run(
        asyncio.wait_for(_served_round(settings, senders, forged, **pipeline), 150))


def _reference(config: MaskConfig, accepted: list[int]) -> np.ndarray:
    model, _mean = reference.reference_model(
        SEED, accepted, MODEL_LEN, DEN, int(config.add_shift), config.exp_shift,
        np.arange(MODEL_LEN))
    return model


def _same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and np.array_equal(a.view(np.uint64), b.view(np.uint64))


# --- (a), (b), (e): served rounds --------------------------------------------


@pytest.mark.parametrize("mix", ["all-v2", "v1-and-v2"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_served_packed_round_equals_the_reference_and_the_legacy_round(
        mask, mix, devices, fallback_calls, caplog):
    config, n_update = MASKS[mask], 2 * K  # two fold batches
    assert native.load() is not None
    caplog.set_level(logging.INFO, logger="xaynet.rest")
    # v1 and v2 senders in one batch, and a batch that starts with either
    senders = ["sdk"] * n_update if mix == "all-v2" else ["sdk", "legacy", "sdk", "legacy", "legacy", "sdk"]
    n_v2 = senders.count("sdk")
    wire_stats.since_last()  # the round's log line counts from here
    depth0, leases0 = streaming.STAGING_DEPTH.value, get_pool().stats()["leases"]
    out = _run(_settings(config, n_update, "packed"), senders)

    want = _reference(config, list(range(n_update)))
    assert _same_bits(out["model"], want)
    legacy = _run(_settings(config, n_update, "legacy"), ["sdk"] * n_update)
    assert out["model"].tobytes() == legacy["model"].tobytes()

    moved, block = out["moved"], config.bytes_per_number * MODEL_LEN
    assert (moved["accepted"], moved["rejected"], moved["failed"]) == (n_update, 0, 0)
    assert moved["folded"] >= 2  # more than one fold batch
    assert moved["rows"] == n_update  # every update staged at its arrival, by either road
    # (e) every byte of every v2 body was copied into its slot as planes; a
    # v1 body took the road it takes in a legacy round
    assert moved["wire", "packed", "copy"] == n_v2 * block
    assert moved["wire", "legacy", "relayout"] == (n_update - n_v2) * block
    assert sum(moved["wire", w, r] for w in WIRES for r in ROUTES) == n_update * block
    assert legacy["moved"]["wire", "legacy", "relayout"] == n_update * block
    assert sum(legacy["moved"]["wire", "packed", r] for r in ROUTES) == 0
    # what wire.packed_share and wire.copy_share read
    total = n_update * block
    assert 100.0 * moved["wire", "packed", "copy"] / total == pytest.approx(100.0 * n_v2 / n_update)
    # no transpose, no limb row, nothing in numpy: the v2 bodies were scanned
    # and copied by the library, the v1 bodies parsed by it
    assert fallback_calls == []
    assert moved["codec", "parse", "generic"] == moved["codec", "validate", "generic"] \
        == moved["codec", "stage", "generic"] == 0
    # against the legacy round (whose Sum2 message and units parse alike): a v2
    # vector is never parsed into limbs, and is checked once, on its planes, in
    # its parse, where a v1 vector is checked in its parse and again in
    # validate_aggregation
    was = legacy["moved"]
    assert was["codec", "parse", "fast"] - moved["codec", "parse", "fast"] == n_v2 * MODEL_LEN
    assert was["codec", "validate", "fast"] - moved["codec", "validate", "fast"] == n_v2 * MODEL_LEN
    # the plane copy counts the columns it copied, a shard at a time
    assert moved["codec", "stage", "fast"] == was["codec", "stage", "fast"] == n_update * MODEL_LEN
    # every buffer went back
    assert out["depth"] == depth0 and get_pool().stats()["leases"] == leases0
    last = out["healthz"]["wire"]
    assert last["packed"] + last["legacy"] == K and last["copied"] == last["packed"]
    line = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("update vectors staged since the last Sum2")]
    assert line and (f"{n_v2} on the packed wire (v2), {n_update - n_v2} on the legacy wire (v1); "
                     f"{n_v2} of them copied") in line[0]


def test_unpacked_staging_and_the_host_aggregator_take_a_v2_body_through_the_counted_fallback(
        monkeypatch, tmp_path, fallback_calls):
    """The routes that stay: ``packed_staging = false`` and ``device = false``
    materialise limb rows from a v2 view (``relayout``), and the transposing
    fallback is counted as a generic parse."""
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))
    config, n_update = MASKS["integer-b0m6"], K
    want = _reference(config, list(range(n_update)))
    for unpacked_device in (True, False):
        settings = _settings(config, n_update, "packed")
        settings.aggregation.device = unpacked_device
        settings.aggregation.packed_staging = False
        fallback_calls.clear()
        out = _run(settings, ["sdk"] * n_update)
        assert _same_bits(out["model"], want)
        moved = out["moved"]
        assert moved["wire", "packed", "relayout"] == n_update * 7 * MODEL_LEN
        assert moved["wire", "packed", "copy"] == 0
        assert fallback_calls == [MODEL_LEN] * n_update
        assert moved["codec", "parse", "generic"] == n_update * MODEL_LEN


# --- (c): validity on planes ---------------------------------------------------


def _planes_with(config: MaskConfig, n: int, position: int, value: int) -> np.ndarray:
    """``n`` valid elements as ``uint8[bpn, n]`` planes, ``value`` at ``position``."""
    bpn, order = config.bytes_per_number, config.order
    rng = np.random.default_rng([n, position])
    rows = np.frombuffer(rng.bytes(n * bpn), dtype=np.uint8).reshape(n, bpn).copy()
    rows[:, -1] %= order.to_bytes(bpn, "little")[-1]  # the top byte under the order's
    rows[position] = np.frombuffer(value.to_bytes(bpn, "little"), dtype=np.uint8)
    return np.ascontiguousarray(rows.T)


@pytest.mark.parametrize("library", ["native", "numpy"])
@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("what", ["order", "order+1", "order-1", "largest"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_plane_validity_equals_the_limb_scan(mask, what, where, library, monkeypatch):
    config, n = MASKS[mask], 600_011  # more than one thread's slice
    order, bpn = config.order, config.bytes_per_number
    value = {"order": order, "order+1": order + 1, "order-1": order - 1,
             "largest": (1 << (8 * bpn)) - 1}[what]
    position = {"first": 0, "middle": n // 2, "last": n - 1}[where]
    planes = _planes_with(config, n, position, value)
    limbs = host_limbs.bytes_le_to_limbs(np.ascontiguousarray(planes.T).reshape(-1), n, bpn)
    want = host_limbs.all_lt_order(limbs, order)
    assert want == (value < order)
    if library == "numpy":
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
    route = "fast" if library == "native" else "generic"
    before = _sample("xaynet_codec_elements_total", {"op": "validate", "route": route})
    assert host_limbs.planes_lt_order(planes, order) == want
    assert _sample("xaynet_codec_elements_total", {"op": "validate", "route": route}) - before == n
    # a column range of a wider block, as a view: the strides are the block's
    assert host_limbs.planes_lt_order(planes[:, 1:n - 1], order) == (
        want or where in ("first", "last"))


def test_an_order_that_fills_its_bytes_admits_every_element():
    planes = np.full((4, 9), 0xFF, dtype=np.uint8)
    assert host_limbs.planes_lt_order(planes, 1 << 32)
    assert not host_limbs.planes_lt_order(planes, (1 << 32) - 1)


def _with_element(position: int, value: int):
    def tamper(obj):
        obj.vect.data[position] = host_limbs.int_to_limbs(value, obj.vect.data.shape[1])
        return obj
    return tamper


@pytest.mark.parametrize("mask", list(MASKS))
def test_a_round_rejects_an_invalid_v2_member_as_it_rejects_a_v1_one(mask, devices):
    """An element equal to the order in a v2 body and in a v1 body: both are
    dropped by the parse (``ServiceError`` of stage ``parse``, answered 200 as
    every dropped message is), neither reaches a slot, and the aggregate is the
    reference over the others."""
    config, n_update = MASKS[mask], 2 * K
    depth0, leases0 = streaming.STAGING_DEPTH.value, get_pool().stats()["leases"]

    def forged(params, sums):
        return [_forged(config, params, sums, 90 + j, planar, _with_element(pos, config.order))
                for j, (planar, pos) in enumerate([(True, 0), (True, MODEL_LEN - 1), (False, 5)])]

    out = _run(_settings(config, n_update, "packed"), ["sdk"] * n_update, forged)
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))
    v2_first, v2_last, v1 = out["answers"]
    assert v2_first == v2_last == v1 \
        == ("ServiceError", "parse: mask vector element >= group order")
    moved = out["moved"]
    assert (moved["accepted"], moved["rows"], moved["failed"]) == (n_update, n_update, 0)
    assert moved["wire", "packed", "copy"] == n_update * config.bytes_per_number * MODEL_LEN
    assert out["depth"] == depth0 and get_pool().stats()["leases"] == leases0


# --- (d): truncated and mis-sized bodies ----------------------------------------


@pytest.mark.parametrize("planar", [True, False], ids=["v2", "v1"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_a_truncated_body_is_a_decode_error_on_either_wire(mask, planar):
    config = MASKS[mask]
    _, obj = Masker(config.pair()).mask(
        Scalar.from_fraction(Fraction(1, DEN)),
        reference.to_f32(reference.weights_fixed(SEED, 1, MODEL_LEN)))
    blob = Update(sum_signature=b"\1" * 64, update_signature=b"\2" * 64, masked_model=obj,
                  local_seed_dict={}, wire_planar=planar).to_bytes()
    whole = Update.from_bytes(blob)
    assert whole.wire_planar is planar and whole.masked_model.vect == obj.vect
    # cut inside the element block: in a v2 body, inside its last plane
    cut = 128 + serialization.VECT_HEADER_LENGTH + config.bytes_per_number * MODEL_LEN - 3
    with pytest.raises(DecodeError, match="mask vector data truncated"):
        Update.from_bytes(blob[:cut])


def _shortened(obj):
    obj.vect.data = obj.vect.data[:-1].copy()
    return obj


def test_a_count_word_that_disagrees_with_the_model_length_is_a_model_mismatch(devices):
    """A well-framed body of ``length - 1`` elements on either wire reaches the
    state machine and is rejected there (``ModelMismatch``), before any scan of
    the planes could matter to a slot."""
    config, n_update = MASKS["integer-b0m6"], K

    def forged(params, sums):
        return [_forged(config, params, sums, 95 + j, planar, _shortened)
                for j, planar in enumerate([True, False])]

    out = _run(_settings(config, n_update, "packed"), ["sdk"] * n_update, forged)
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))
    v2, v1 = out["answers"]
    assert v2 == v1 and v2[0] == "RequestError" and "ModelMismatch" in v2[1]
    # over the socket and through the handler: twice each
    assert (out["moved"]["rejected"], out["moved"]["rows"]) == (4, n_update)
