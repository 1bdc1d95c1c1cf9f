"""The aggregated mask is laid out for the device once a phase
(docs/DESIGN.md §22, "Ask before the relayout"): ``mask_planar`` writes the
transposition into the padded array in one pass, the eager unmask asks the
pipeline whether it can stage before it relays the mask out, and a pipeline
that cannot (one device) leaves the one relayout to ``unmask_limbs``."""

import asyncio
from fractions import Fraction

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from xaynet_tpu.core.mask import (  # noqa: E402
    BoundType, DataType, GroupType, MaskConfig, Masker, ModelType, Scalar)
from xaynet_tpu.core.mask.masking import Aggregation  # noqa: E402
from xaynet_tpu.ops import limbs as host_limbs  # noqa: E402
from xaynet_tpu.ops.fold_jax import wire_to_planar  # noqa: E402
from xaynet_tpu.parallel import aggregator as aggregator_mod  # noqa: E402
from xaynet_tpu.parallel.aggregator import ShardedAggregator  # noqa: E402
from xaynet_tpu.parallel.mesh import make_mesh  # noqa: E402
from xaynet_tpu.parallel.streaming import StreamingAggregator  # noqa: E402
from xaynet_tpu.server.aggregation import DeviceAggregation, StagedAggregator  # noqa: E402
from xaynet_tpu.telemetry import unmask as unmask_stages  # noqa: E402

# 2 limbs (7 wire bytes) and 3 limbs (10): the benchmark's two widths
BOUNDS = {2: BoundType.B0, 3: BoundType.B6}
# on the eight CPU devices of the conftest: a length that is and one that is
# not a multiple of the mesh (the second is padded)
LENGTHS = {"whole": 1_000, "padded": 1_003}
SHAPES = [(limbs, name) for limbs in BOUNDS for name in LENGTHS]


def _config(n_limbs: int) -> MaskConfig:
    config = MaskConfig(GroupType.INTEGER, DataType.F32, BOUNDS[n_limbs], ModelType.M6)
    assert host_limbs.n_limbs_for_order(config.order) == n_limbs
    return config


def _stage_counts() -> dict:
    return {key[0]: child.count for key, child in unmask_stages.SECONDS.children()}


def _observed(before: dict) -> dict:
    after = _stage_counts()
    return {label: after[label] - before.get(label, 0) for label in after}


@pytest.mark.parametrize("n_limbs,length", SHAPES)
def test_mask_planar_is_one_pass_and_equals_the_padded_transposition(n_limbs, length):
    n = LENGTHS[length]
    agg = ShardedAggregator(_config(n_limbs), n, mesh=make_mesh(jax.devices()))
    assert (agg.padded_length != n) == (length == "padded")
    rng = np.random.default_rng(n_limbs)
    wire = rng.integers(0, 1 << 32, size=(n, n_limbs), dtype=np.uint64).astype(np.uint32)
    expected = np.pad(wire_to_planar(wire), ((0, 0), (0, agg.padded_length - n)))
    planar = agg.mask_planar(wire)
    assert planar.dtype == np.uint32 and planar.flags.c_contiguous
    assert planar.tobytes() == expected.tobytes()
    # an unpadded planar is padded alike; a padded planar passes through untouched
    assert agg.mask_planar(wire_to_planar(wire)).tobytes() == expected.tobytes()
    assert agg.mask_planar(planar) is planar
    assert planar.tobytes() == expected.tobytes()


def _staged_round(config, n: int, mesh, rng):
    """``k`` masked updates staged on the device and the sum of their masks."""
    k = 3
    agg = StagedAggregator(config.pair(), n, device=True, batch_size=2, kernel="xla", mesh=mesh)
    masks = Aggregation(config.pair(), n)
    weights = rng.uniform(-1, 1, size=(k, n)).astype(np.float32)
    for row in weights:
        seed, masked = Masker(config.pair()).mask(Scalar(1, k), row)
        agg.validate_aggregation(masked)
        agg.stage(masked)
        mask = seed.derive_mask(n, config.pair())
        masks.validate_aggregation(mask)
        masks.aggregate(mask)
    return agg, masks.object, weights.astype(np.float64).mean(axis=0)


@pytest.fixture
def relayouts(monkeypatch):
    calls = []
    real = ShardedAggregator.mask_planar

    def spy(self, mask_vect):
        calls.append(np.asarray(mask_vect).shape)
        return real(self, mask_vect)

    monkeypatch.setattr(ShardedAggregator, "mask_planar", spy)
    return calls


@pytest.fixture
def staged_jobs(monkeypatch):
    jobs = []
    real = StreamingAggregator.stage_unmask

    def spy(self, mask_planar):
        job = real(self, mask_planar)
        jobs.append(job)
        return job

    monkeypatch.setattr(StreamingAggregator, "stage_unmask", spy)
    return jobs


@pytest.mark.parametrize("n_limbs,length", SHAPES)
def test_one_device_with_a_stream_open_relays_the_mask_out_once(
    n_limbs, length, relayouts, staged_jobs
):
    """The arm the benchmark's cells run: one device, the pipeline still open
    at Unmask. It cannot stage, is asked before any planar is built, and the
    drain-time subtract makes the phase's one relayout."""
    n = LENGTHS[length]
    agg, mask, mean = _staged_round(
        _config(n_limbs), n, make_mesh(jax.devices()[:1]), np.random.default_rng(7))
    view = agg.finalize_inplace(defer_drain=True)
    assert isinstance(view, DeviceAggregation) and view._stream is not None
    assert not view._stream.can_stage_unmask()
    view.validate_unmasking(mask)
    before = _stage_counts()
    model = view.unmask_array(mask)
    observed = _observed(before)
    assert observed["mask_put"] == 1 and observed["fetch"] == 1
    assert relayouts == [(n, n_limbs)] and staged_jobs == []
    assert view._stream is None  # settled
    np.testing.assert_allclose(model, mean, atol=1e-9)


@pytest.mark.parametrize("n_limbs,length", SHAPES)
def test_on_a_mesh_the_eager_arm_still_runs_and_matches_the_drain_time_arm(
    n_limbs, length, relayouts, staged_jobs
):
    n = LENGTHS[length]
    config, mesh = _config(n_limbs), make_mesh(jax.devices())
    models = {}
    for arm in ("eager", "drain_time"):
        agg, mask, mean = _staged_round(config, n, mesh, np.random.default_rng(9))
        view = agg.finalize_inplace(defer_drain=arm == "eager")
        view.validate_unmasking(mask)
        del relayouts[:], staged_jobs[:]
        before = _stage_counts()
        models[arm] = view.unmask_array(mask)
        assert _observed(before)["mask_put"] == 1, arm
        assert relayouts == [(n, n_limbs)], arm
        if arm == "eager":
            assert len(staged_jobs) == 1 and staged_jobs[0] is not None
        else:
            assert staged_jobs == []
        np.testing.assert_allclose(models[arm], mean, atol=1e-9)
    assert models["eager"].tobytes() == models["drain_time"].tobytes()


@pytest.mark.parametrize("n_limbs,length", SHAPES)
def test_a_served_round_on_one_device_observes_mask_put_once(
    n_limbs, length, relayouts, staged_jobs, monkeypatch
):
    """A whole PET round through the state machine, device aggregation on one
    CPU device, the overlap engines on (the shipped default): the Unmask phase
    observes ``mask_put`` once on ``xaynet_unmask_seconds`` and relays the
    elected mask out once; the model is the mean."""
    from xaynet_tpu.sdk.client import InProcessClient
    from xaynet_tpu.sdk.simulation import keys_for_task
    from xaynet_tpu.sdk.state_machine import PetSettings, StateMachine as ParticipantSM
    from xaynet_tpu.sdk.traits import ModelStore
    from xaynet_tpu.server.services import Fetcher, PetMessageHandler
    from xaynet_tpu.server.settings import (
        CountSettings, PhaseSettings, PetSettings as ServerPet, Settings, Sum2Settings,
        TimeSettings)
    from xaynet_tpu.server.state_machine import StateMachineInitializer
    from xaynet_tpu.storage.memory import (
        InMemoryCoordinatorStorage, InMemoryModelStorage, NoOpTrustAnchor)
    from xaynet_tpu.storage.traits import MASK_VOTES, Store

    one_device = make_mesh(jax.devices()[:1])
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda *a, **kw: one_device)

    class ArrayModelStore(ModelStore):
        def __init__(self, model):
            self.model = model

        async def load_model(self):
            return self.model

    n_sum, n_update, n = 1, 3, LENGTHS[length]
    config = _config(n_limbs)

    def window(count, prob=None):
        kw = dict(count=CountSettings(min=count, max=count), time=TimeSettings(min=0.0, max=30.0))
        return Sum2Settings(**kw) if prob is None else PhaseSettings(prob=prob, **kw)

    async def run():
        settings = Settings(
            pet=ServerPet(sum=window(n_sum, 0.4), update=window(n_update, 0.5), sum2=window(n_sum)))
        settings.model.length = n
        settings.mask.group_type = config.group_type
        settings.mask.bound_type = config.bound_type
        settings.mask.model_type = config.model_type
        settings.aggregation.device = True
        settings.aggregation.batch_size = 2
        settings.aggregation.kernel = "xla"
        settings.validate()
        store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
        machine, request_tx, events = await StateMachineInitializer(settings, store).init()
        handler, fetcher = PetMessageHandler(events, request_tx), Fetcher(events)
        machine_task = asyncio.create_task(machine.run())
        try:
            while fetcher.phase().value != "sum":
                await asyncio.sleep(0.01)
            seed = fetcher.round_params().seed.as_bytes()
            rng = np.random.default_rng(5)
            weights = rng.uniform(-1, 1, size=(n_update, n)).astype(np.float32)
            participants = [
                ParticipantSM(
                    PetSettings(keys=keys_for_task(seed, 0.4, 0.5, "sum"), max_message_size=None),
                    InProcessClient(fetcher, handler), ArrayModelStore(None))
            ] + [
                ParticipantSM(
                    PetSettings(
                        keys=keys_for_task(seed, 0.4, 0.5, "update", start=(10 + i) * 1000),
                        scalar=Fraction(1, n_update), max_message_size=None),
                    InProcessClient(fetcher, handler), ArrayModelStore(row))
                for i, row in enumerate(weights)
            ]

            async def drive(sm):
                for _ in range(1000):
                    try:
                        await sm.transition()
                    except Exception:
                        pass
                    if fetcher.model() is not None:
                        return
                    await asyncio.sleep(0.005)

            await asyncio.gather(*(drive(p) for p in participants))
            assert fetcher.model() is not None
            return np.asarray(fetcher.model()), weights.astype(np.float64).mean(axis=0)
        finally:
            machine_task.cancel()
            await asyncio.gather(machine_task, return_exceptions=True)

    before, kept = _stage_counts(), MASK_VOTES.labels(route="kept").value
    model, mean = asyncio.run(asyncio.wait_for(run(), timeout=120))
    observed = _observed(before)
    for label in ("elect", "validate", "mask_put", "fetch", "decode", "save"):
        assert observed[label] == 1, label
    assert relayouts == [(n, n_limbs)] and staged_jobs == []
    assert MASK_VOTES.labels(route="kept").value - kept == n_sum
    np.testing.assert_allclose(model, mean, atol=1e-9)
