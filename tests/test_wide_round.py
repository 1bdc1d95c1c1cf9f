"""A whole small PET round at Integer/F32/B6/M6 (75-bit order, 3 limbs, 10
wire bytes) through the coordinator's path, against a plain reference.

The reference below is the published rule in Python integers and
``Fraction``: it imports nothing of the program's encode, decode, limb or
fold code. The round runs the real SDK state machines and the real phase
state machine (sealed box, signatures, parse, validate, stage, fold, Sum2,
unmask, decode), once with the host ``Aggregation`` and once with the
device pipeline, and the published model has to equal the reference bit
for bit either way. The same round at B0/M6 (2 limbs) runs beside it, and
``xaynet_codec_elements_total`` has to say which routes each one took.
"""

import asyncio
from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.sdk.client import InProcessClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, StateMachine as ParticipantSM
from xaynet_tpu.sdk.traits import ModelStore
from xaynet_tpu.server.services import Fetcher, PetMessageHandler
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
from xaynet_tpu.telemetry import codec
from xaynet_tpu.utils import native

N_SUM, N_UPDATE, MODEL_LEN = 1, 4, 257
SUM_PROB, UPDATE_PROB = 0.4, 0.5
SCALAR = Fraction(1, 4)  # dyadic: exact in the SDK's double-double encode
OPS = ("parse", "validate", "stage", "derive", "decode")
BOUNDS = {"2limb-b0m6": BoundType.B0, "3limb-b6m6": BoundType.B6}


# --- the plain reference: Python integers and Fractions only ----------------


def reference_model(weights: list[np.ndarray], add_shift: int, exp_shift: int) -> list[float]:
    """``floor((clamp(s*w, -A, A) + A) * E)`` summed over the participants,
    decoded by ``((S / E) - nb*A) / scalar_sum`` to the nearest float64. The
    unit's add_shift is the vector's (one bound type)."""
    nb = len(weights)
    a, e = Fraction(add_shift), exp_shift

    def encode(x: Fraction) -> int:
        t = (max(-a, min(a, x)) + a) * e
        return t.numerator // t.denominator

    scalar_sum = Fraction(nb * encode(SCALAR), e) - nb * a
    out = []
    for column in zip(*(w.tolist() for w in weights)):
        total = sum(encode(SCALAR * Fraction(w)) for w in column)
        out.append(float((Fraction(total, e) - nb * a) / scalar_sum))
    return out


class _Store(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


def _weights(bound_value: float) -> list[np.ndarray]:
    """Over the whole bound and past it (the clamp binds for some), so that
    at B6 encodings fall on both sides of 2^53."""
    rng = np.random.default_rng(26)
    return [rng.uniform(-5 * bound_value, 5 * bound_value, MODEL_LEN).astype(np.float32)
            for _ in range(N_UPDATE)]


def _settings(bound: BoundType, device: bool) -> Settings:
    window = TimeSettings(min=0.0, max=30.0)
    s = Settings(pet=ServerPet(
        sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(N_SUM, N_SUM), time=window),
        update=PhaseSettings(prob=UPDATE_PROB, count=CountSettings(N_UPDATE, N_UPDATE), time=window),
        sum2=Sum2Settings(count=CountSettings(N_SUM, N_SUM), time=window),
    ))
    s.model.length = MODEL_LEN
    s.mask.group_type, s.mask.data_type = GroupType.INTEGER, DataType.F32
    s.mask.bound_type, s.mask.model_type = bound, ModelType.M6
    s.aggregation.device = device
    s.aggregation.batch_size = 3  # a full batch and one that drain() closes
    s.aggregation.kernel = "xla"
    return s


async def _run_round(settings: Settings, weights: list[np.ndarray]) -> np.ndarray:
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(settings, store).init()
    handler, fetcher = PetMessageHandler(events, request_tx), Fetcher(events)
    machine_task = asyncio.create_task(machine.run())
    try:
        while fetcher.phase().value != "sum":
            await asyncio.sleep(0.01)
        seed = fetcher.round_params().seed.as_bytes()
        participants = [ParticipantSM(
            PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum"), device_sum2=False),
            InProcessClient(fetcher, handler), _Store(None))]
        for i, w in enumerate(weights):
            keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update", start=(10 + i) * 1000)
            participants.append(ParticipantSM(
                PetSettings(keys=keys, scalar=SCALAR), InProcessClient(fetcher, handler), _Store(w)))

        async def drive(sm):
            for _ in range(2000):
                await sm.transition()
                if fetcher.model() is not None and sm.phase.value == "awaiting":
                    return
                await asyncio.sleep(0.005)

        await asyncio.gather(*(drive(p) for p in participants))
        while fetcher.model() is None:
            await asyncio.sleep(0.01)
        return np.asarray(fetcher.model(), dtype=np.float64)
    finally:
        machine_task.cancel()
        try:
            await machine_task
        except (asyncio.CancelledError, Exception):
            pass


def _routes() -> dict:
    return {(op, route): codec.ELEMENTS.labels(op=op, route=route).value
            for op in OPS for route in ("fast", "generic", "fused")}


@pytest.mark.parametrize("device", [False, True], ids=["host-aggregation", "device-pipeline"])
@pytest.mark.parametrize("width", list(BOUNDS))
def test_round_equals_the_plain_reference_bit_for_bit(width, device):
    bound = BOUNDS[width]
    config = MaskConfig(GroupType.INTEGER, DataType.F32, bound, ModelType.M6)
    assert (config.bytes_per_number, config.order.bit_length()) == {
        "2limb-b0m6": (7, 55), "3limb-b6m6": (10, 75)}[width]
    weights = _weights(float(config.add_shift))
    before = _routes()
    model = asyncio.run(asyncio.wait_for(_run_round(_settings(bound, device), weights), 120))
    moved = {key: value - before[key] for key, value in _routes().items()}

    want = np.array(reference_model(weights, int(config.add_shift), config.exp_shift))
    assert model.shape == want.shape
    assert np.array_equal(model.view(np.uint64), want.view(np.uint64))
    clamped = sum(int((np.abs(w) * float(SCALAR) > float(config.add_shift)).sum()) for w in weights)
    assert 0 < clamped < N_UPDATE * MODEL_LEN  # the clamp bound for some, not for all

    # which routes the round took: the native library's kernels (`fast`) at
    # either width, numpy and Python (`generic`) without the library
    took = {op: "fast" if native.load() is not None else "generic" for op in OPS}
    if native.load() is not None:
        # the CPU sum participant sums its masks as it samples them, with no
        # mask in memory (its unit draws, one a seed, stay `fast`)
        took["derive"] = "fused"
    other = {"fast": ("generic", "fused"), "generic": ("fast", "fused"), "fused": ("generic",)}
    assert not any(moved[op, o] for op, route in took.items() for o in other[route]), moved
    # every update is parsed and validated; the sum participant derives one
    # mask an update; the model is decoded once; only the device stages
    assert moved["parse", took["parse"]] >= N_UPDATE * MODEL_LEN
    assert moved["validate", took["validate"]] >= N_UPDATE * MODEL_LEN
    assert moved["derive", took["derive"]] >= N_UPDATE * MODEL_LEN
    assert moved["decode", took["decode"]] == MODEL_LEN
    assert (moved["stage", took["stage"]] > 0) is device


def test_counter_names_the_route_by_who_ran_it(monkeypatch):
    """``fast`` is the native library at any width (f32/Bmax: 38 bytes, 10
    limbs, on its per-byte and per-limb loops); ``generic`` is numpy and
    Python, which is everything without the library."""
    from xaynet_tpu.core.crypto.prng import StreamSampler
    from xaynet_tpu.core.mask.encode import decode_vect_any, decode_vect_fast, has_fast_path
    from xaynet_tpu.ops import limbs as limb_ops

    wide = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.BMAX, ModelType.M6)
    narrow = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6)
    assert (wide.bytes_per_number, narrow.bytes_per_number) == (38, 10)

    def run(config):
        before = _routes()
        draws = StreamSampler(b"\x07" * 32).draw_limbs(40, config.order)
        raw = np.frombuffer(limb_ops.limbs_to_bytes_le(draws, config.bytes_per_number), np.uint8)
        limbs = limb_ops.bytes_le_to_limbs(raw, 40, config.bytes_per_number)
        assert limb_ops.all_lt_order(limbs, config.order)
        limb_ops.pack_wire(limbs[None], config.bytes_per_number)
        decode = decode_vect_fast if has_fast_path(config) else decode_vect_any
        decode(limbs, config, 1, Fraction(1))
        return {key: value - before[key] for key, value in _routes().items() if value != before[key]}

    ops = ("derive", "parse", "validate", "stage", "decode")
    if native.load() is not None:
        for config in (narrow, wide):
            assert run(config) == {(op, "fast"): 40 for op in ops}
    monkeypatch.setattr(native, "load", lambda: None)
    for config in (narrow, wide):
        assert run(config) == {(op, "generic"): 40 for op in ops}
