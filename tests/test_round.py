"""End-to-end PET round: coordinator + N in-process participants.

The reference proves the whole protocol is testable in-process by injecting
messages straight into the request channel (SURVEY §4.3). Here we go one
layer further out: participants run the real SDK state machine, messages go
through the full service pipeline (sealed box, signature, task validation),
and the coordinator runs the real phase state machine — only the network is
replaced by direct calls.
"""

import asyncio
from fractions import Fraction

import numpy as np
import pytest

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

N_SUM = 2
N_UPDATE = 3
MODEL_LEN = 13
SUM_PROB = 0.4
UPDATE_PROB = 0.5


class ArrayModelStore(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


def _settings() -> Settings:
    s = Settings(
        pet=ServerPet(
            sum=PhaseSettings(
                prob=SUM_PROB,
                count=CountSettings(min=N_SUM, max=N_SUM),
                time=TimeSettings(min=0.0, max=20.0),
            ),
            update=PhaseSettings(
                prob=UPDATE_PROB,
                count=CountSettings(min=N_UPDATE, max=N_UPDATE),
                time=TimeSettings(min=0.0, max=20.0),
            ),
            sum2=Sum2Settings(
                count=CountSettings(min=N_SUM, max=N_SUM),
                time=TimeSettings(min=0.0, max=20.0),
            ),
        )
    )
    s.model.length = MODEL_LEN
    return s


async def _run_round(
    settings: Settings,
    n_rounds: int = 1,
    sum_pet_kwargs: dict | None = None,
    raise_in_drive: bool = False,
):
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    init = StateMachineInitializer(settings, store)
    machine, request_tx, events = await init.init()
    handler = PetMessageHandler(events, request_tx)
    fetcher = Fetcher(events)

    machine_task = asyncio.create_task(machine.run())

    models = {}
    try:
        for round_no in range(n_rounds):
            # wait for the sum phase of the current round so the published
            # round seed is final
            while fetcher.phase().value != "sum":
                await asyncio.sleep(0.01)
            params = fetcher.round_params()
            seed = params.seed.as_bytes()

            model_len = settings.model.length
            rng = np.random.default_rng(42 + round_no)
            participants = []
            expected = np.zeros(model_len)
            for i in range(N_SUM):
                keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum", start=i * 1000)
                sm = ParticipantSM(
                    PetSettings(keys=keys, **(sum_pet_kwargs or {})),
                    InProcessClient(fetcher, handler),
                    ArrayModelStore(None),
                )
                participants.append(sm)
            for i in range(N_UPDATE):
                keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update", start=(10 + i) * 1000)
                local = rng.uniform(-1, 1, model_len).astype(np.float32)
                expected += local.astype(np.float64) / N_UPDATE
                sm = ParticipantSM(
                    PetSettings(keys=keys, scalar=Fraction(1, N_UPDATE)),
                    InProcessClient(fetcher, handler),
                    ArrayModelStore(local),
                )
                participants.append(sm)

            async def drive(sm):
                for _ in range(500):
                    try:
                        await sm.transition()
                    except Exception:
                        if raise_in_drive:
                            raise
                    if fetcher.model() is not None and sm.phase.value == "awaiting":
                        return
                    await asyncio.sleep(0.01)

            await asyncio.gather(*(drive(p) for p in participants))

            while fetcher.model() is None:
                await asyncio.sleep(0.01)
            models[round_no] = (np.asarray(fetcher.model()), expected)

            # let the machine move into the next round's sum phase
            if round_no + 1 < n_rounds:
                while fetcher.round_params().seed.as_bytes() == seed:
                    await asyncio.sleep(0.01)
    finally:
        machine_task.cancel()
        try:
            await machine_task
        except (asyncio.CancelledError, Exception):
            pass
    return models


def test_full_pet_round():
    models = asyncio.run(asyncio.wait_for(_run_round(_settings()), timeout=60))
    got, expected = models[0]
    assert got.shape == (MODEL_LEN,)
    np.testing.assert_allclose(got, expected, atol=1e-9)


@pytest.mark.parametrize("kernel", ["auto", "pallas-interpret"])
def test_round_with_chunked_updates_and_device_aggregation(kernel, monkeypatch):
    """Multipart update messages + TPU-mesh aggregation, end to end.

    ``auto`` resolves to the XLA fold on the CPU backend; the
    ``pallas-interpret`` leg drives the whole round through the Pallas
    grid/BlockSpec path (via shard_map on the 8-device mesh) so the fused
    kernel is continuously exercised, with a spy proving it folded.
    """
    import xaynet_tpu.ops.fold_pallas as fold_pallas
    import xaynet_tpu.parallel.aggregator as agg_mod

    pallas_calls = []
    if kernel == "pallas-interpret":
        # the process-wide fold-fn cache only re-reads the (spied) module
        # attribute on a retrace; start from a clean cache so the spy is
        # guaranteed to observe the fold
        agg_mod._FOLD_FN_CACHE.clear()
        real = fold_pallas.fold_planar_batch_pallas

        def spy(acc, stack, order, interpret=False, tile_size=None):
            pallas_calls.append(interpret)
            return real(acc, stack, order, interpret=interpret, tile_size=tile_size)

        monkeypatch.setattr(fold_pallas, "fold_planar_batch_pallas", spy)

    async def run():
        settings = _settings()
        settings.model.length = 600  # update payload >> max_message_size
        settings.aggregation.device = True
        settings.aggregation.batch_size = 2
        settings.aggregation.kernel = kernel
        store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
        machine, request_tx, events = await StateMachineInitializer(settings, store).init()
        handler = PetMessageHandler(events, request_tx)
        fetcher = Fetcher(events)
        machine_task = asyncio.create_task(machine.run())
        try:
            while fetcher.phase().value != "sum":
                await asyncio.sleep(0.01)
            params = fetcher.round_params()
            seed = params.seed.as_bytes()
            rng = np.random.default_rng(3)
            expected = np.zeros(600)
            participants = []
            for i in range(N_SUM):
                keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum", start=i * 1000)
                sm = ParticipantSM(
                    PetSettings(keys=keys, max_message_size=1024),
                    InProcessClient(fetcher, handler),
                    ArrayModelStore(None),
                )
                participants.append(sm)
            for i in range(N_UPDATE):
                keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update", start=(10 + i) * 1000)
                local = rng.uniform(-1, 1, 600).astype(np.float32)
                expected += local.astype(np.float64) / N_UPDATE
                sm = ParticipantSM(
                    PetSettings(
                        keys=keys, scalar=Fraction(1, N_UPDATE), max_message_size=1024
                    ),
                    InProcessClient(fetcher, handler),
                    ArrayModelStore(local),
                )
                participants.append(sm)

            async def drive(sm):
                for _ in range(500):
                    try:
                        await sm.transition()
                    except Exception:
                        pass
                    if fetcher.model() is not None and sm.phase.value == "awaiting":
                        return
                    await asyncio.sleep(0.01)

            await asyncio.gather(*(drive(p) for p in participants))
            while fetcher.model() is None:
                await asyncio.sleep(0.01)
            return np.asarray(fetcher.model()), expected
        finally:
            machine_task.cancel()
            try:
                await machine_task
            except (asyncio.CancelledError, Exception):
                pass

    got, expected = asyncio.run(asyncio.wait_for(run(), timeout=180))
    np.testing.assert_allclose(got, expected, atol=1e-9)
    if kernel == "pallas-interpret":
        assert pallas_calls and all(pallas_calls), "round did not fold through the Pallas kernel"


def test_round_with_wire_ingest(monkeypatch):
    """Full round with ``aggregation.wire_ingest = true``: Update masked
    models parse LAZILY (raw element block kept through the multipart
    stream parse), element unpack + validity run on the device BEFORE the
    seed-dict insert, and the fold consumes device-resident planars — the
    coordinator never executes the host element parse. A spy proves every
    accepted update went through the device validation; the global model
    is still the exact mean."""
    from xaynet_tpu.parallel.aggregator import ShardedAggregator

    validated = []
    real_validate = ShardedAggregator.unpack_put_update

    def spy(self, staged):
        out = real_validate(self, staged)
        validated.append(out is not None)
        return out

    monkeypatch.setattr(ShardedAggregator, "unpack_put_update", spy)

    async def run():
        settings = _settings()
        settings.model.length = 600  # update payload >> max_message_size
        settings.aggregation.device = True
        settings.aggregation.batch_size = 2
        settings.aggregation.kernel = "xla"
        settings.aggregation.wire_ingest = True
        settings.validate()
        store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
        machine, request_tx, events = await StateMachineInitializer(settings, store).init()
        handler = PetMessageHandler(events, request_tx, wire_ingest=True)
        fetcher = Fetcher(events)
        machine_task = asyncio.create_task(machine.run())
        try:
            while fetcher.phase().value != "sum":
                await asyncio.sleep(0.01)
            params = fetcher.round_params()
            seed = params.seed.as_bytes()
            rng = np.random.default_rng(11)
            expected = np.zeros(600)
            participants = []
            for i in range(N_SUM):
                keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum", start=i * 1000)
                participants.append(
                    ParticipantSM(
                        PetSettings(keys=keys, max_message_size=1024),
                        InProcessClient(fetcher, handler),
                        ArrayModelStore(None),
                    )
                )
            for i in range(N_UPDATE):
                keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update", start=(10 + i) * 1000)
                local = rng.uniform(-1, 1, 600).astype(np.float32)
                expected += local.astype(np.float64) / N_UPDATE
                participants.append(
                    ParticipantSM(
                        PetSettings(keys=keys, scalar=Fraction(1, N_UPDATE), max_message_size=1024),
                        InProcessClient(fetcher, handler),
                        ArrayModelStore(local),
                    )
                )

            async def drive(sm):
                for _ in range(500):
                    try:
                        await sm.transition()
                    except Exception:
                        pass
                    if fetcher.model() is not None and sm.phase.value == "awaiting":
                        return
                    await asyncio.sleep(0.01)

            await asyncio.gather(*(drive(p) for p in participants))
            while fetcher.model() is None:
                await asyncio.sleep(0.01)
            return np.asarray(fetcher.model()), expected
        finally:
            machine_task.cancel()
            try:
                await machine_task
            except (asyncio.CancelledError, Exception):
                pass

    got, expected = asyncio.run(asyncio.wait_for(run(), timeout=180))
    np.testing.assert_allclose(got, expected, atol=1e-9)
    assert len(validated) >= N_UPDATE and all(validated), (
        f"device wire validation did not run for every update: {validated}"
    )


def test_sum_participant_save_restore_mid_round():
    """A sum participant suspended after Sum resumes and completes Sum2
    (the ephemeral decryption key must survive serialization)."""

    async def run():
        settings = _settings()
        store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
        machine, request_tx, events = await StateMachineInitializer(settings, store).init()
        handler = PetMessageHandler(events, request_tx)
        fetcher = Fetcher(events)
        machine_task = asyncio.create_task(machine.run())
        try:
            while fetcher.phase().value != "sum":
                await asyncio.sleep(0.01)
            params = fetcher.round_params()
            seed = params.seed.as_bytes()
            rng = np.random.default_rng(7)

            # one extra summer that will be suspended/resumed
            keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum", start=50_000)
            suspended = ParticipantSM(
                PetSettings(keys=keys), InProcessClient(fetcher, handler), ArrayModelStore(None)
            )
            # drive it through NewRound + Sum (it sends its ephemeral key)
            for _ in range(10):
                await suspended.transition()
                if suspended.phase.value == "sum2":
                    break
            assert suspended.phase.value == "sum2"
            blob = suspended.save()

            participants = []
            for i in range(1, N_SUM):
                k2 = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum", start=i * 1000)
                participants.append(
                    ParticipantSM(PetSettings(keys=k2), InProcessClient(fetcher, handler), ArrayModelStore(None))
                )
            expected = np.zeros(MODEL_LEN)
            for i in range(N_UPDATE):
                k2 = keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update", start=(10 + i) * 1000)
                local = rng.uniform(-1, 1, MODEL_LEN).astype(np.float32)
                expected += local.astype(np.float64) / N_UPDATE
                participants.append(
                    ParticipantSM(
                        PetSettings(keys=k2, scalar=Fraction(1, N_UPDATE)),
                        InProcessClient(fetcher, handler),
                        ArrayModelStore(local),
                    )
                )

            # resume the suspended summer in a "new process"
            resumed = ParticipantSM.restore(
                blob, InProcessClient(fetcher, handler), ArrayModelStore(None)
            )
            assert resumed.phase.value == "sum2"
            participants.append(resumed)

            async def drive(sm):
                for _ in range(500):
                    try:
                        await sm.transition()
                    except Exception:
                        pass
                    if fetcher.model() is not None:
                        return
                    await asyncio.sleep(0.01)

            await asyncio.gather(*(drive(p) for p in participants))
            while fetcher.model() is None:
                await asyncio.sleep(0.01)
            np.testing.assert_allclose(np.asarray(fetcher.model()), expected, atol=1e-9)
        finally:
            machine_task.cancel()
            try:
                await machine_task
            except (asyncio.CancelledError, Exception):
                pass

    asyncio.run(asyncio.wait_for(run(), timeout=60))


@pytest.mark.slow  # ~2 min per config on the 8-device virtual CPU mesh
@pytest.mark.parametrize(
    "group_type,data_type,model_type",
    [
        ("prime", "f32", "m3"),
        ("integer", "f32", "m6"),
        ("power2", "f32", "m3"),
    ],
)
def test_round_with_device_sum2_strict(monkeypatch, group_type, data_type, model_type):
    """Full federated round with Sum2 on the JAX device path, strict,
    swept over three finite-group config families (VERDICT r03 item 8).

    The model length equals the real ``DEVICE_SUM2_THRESHOLD`` (no
    threshold fudging), ``device_sum2_strict`` turns the silent
    warn-and-fallback into a hard failure, and a spy proves the device
    kernel actually ran for every sum participant (VERDICT r02 item 6).
    """
    from xaynet_tpu.core.mask.config import DataType, GroupType, ModelType
    from xaynet_tpu.ops import masking_jax

    length = ParticipantSM.DEVICE_SUM2_THRESHOLD  # 262,144
    calls = []
    real = masking_jax.sum_masks

    def spy(seeds, n, config):
        calls.append((len(seeds), n))
        return real(seeds, n, config)

    s = _settings()
    s.model.length = length
    s.mask.group_type = GroupType[group_type.upper()]
    s.mask.data_type = DataType[data_type.upper()]
    s.mask.model_type = ModelType[model_type.upper()]
    # headroom for the first-run jit compile of the derivation kernel
    s.pet.update.time = TimeSettings(min=0.0, max=90.0)
    s.pet.sum2.time = TimeSettings(min=0.0, max=90.0)

    # warm the jit cache at the exact shapes the round will use (before the
    # spy is installed), so the in-round sum2 leg measures the protocol,
    # not XLA compilation
    cfg = s.mask.to_config()
    masking_jax.sum_masks([b"\x11" * 32], length, cfg.pair())

    monkeypatch.setattr(masking_jax, "sum_masks", spy)

    models = asyncio.run(
        asyncio.wait_for(
            _run_round(
                s,
                sum_pet_kwargs={
                    "device_sum2": True,
                    "device_sum2_strict": True,
                    "max_message_size": None,  # single-message sends
                },
                raise_in_drive=True,
            ),
            timeout=240,
        )
    )
    got, expected = models[0]
    assert got.shape == (length,)
    np.testing.assert_allclose(got, expected, atol=1e-6)
    # both sum participants took the device path over all update seeds
    assert len(calls) == N_SUM
    assert all(c == (N_UPDATE, length) for c in calls)
