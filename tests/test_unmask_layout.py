"""The aggregated mask is laid out for the device once a phase
(docs/DESIGN.md §22, "Ask before the relayout"): ``mask_planar`` writes the
transposition into the padded array in one pass, the eager unmask asks the
pipeline whether it can stage before it relays the mask out, and a pipeline
that cannot (one device) leaves the one relayout to ``unmask_planar``.

And the model's side (docs/DESIGN.md §16, "One layout to the decode, one
buffer to the store"): every device arm hands the decode the planes it
fetched, the float64 is written once and serialised once, and the store,
the trust anchor and the broadcast hold what the decoder returned."""

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


def _staged_round(config, n: int, mesh, rng, device: bool = True):
    """``k`` masked updates staged (on the device) and the sum of their masks."""
    k = 3
    agg = StagedAggregator(config.pair(), n, device=device, batch_size=2, kernel="xla", mesh=mesh)
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
    CPU device, the pipeline riding into Unmask (docs/DESIGN.md §22): the Unmask phase
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


# --------------------------------------------------------------------------
# one layout from every device arm to the decode, one buffer from the decode
# to the store, the anchor and the broadcast (docs/DESIGN.md §16)
# --------------------------------------------------------------------------

# arm -> (device, devices of the mesh, the pipeline rides into Unmask open)
ARMS = {
    "host": (False, 0, False),
    "one_device": (True, 1, True),
    "mesh_drain_time": (True, None, False),
    "mesh_eager": (True, None, True),
    # the two drain-time arms that no caller's argument chooses (docs/DESIGN.md
    # §22): a shard's tail job fails under the open pipeline, and a journal
    # resume re-enters Unmask (phases/resume.py) with no pipeline to ride
    "mesh_failed_shard": (True, None, True),
    "mesh_resumed": (True, None, False),
}


def _failing_shard(monkeypatch, shard: int = 1):
    real = ShardedAggregator.unmask_shard

    def failing(self, plan, d, mask_planar, out):
        if d == shard:
            raise RuntimeError("injected shard fault")
        return real(self, plan, d, mask_planar, out)

    monkeypatch.setattr(ShardedAggregator, "unmask_shard", failing)


def _pass_bytes() -> dict:
    return {key[0]: child.value for key, child in unmask_stages.MODEL_BYTES.children()}


def _run_unmask_phase(arm: str, n_limbs: int, n: int, sound: bool = True):
    """The Unmask phase itself over one arm's aggregation of the same three
    updates: returns the phase, what the store and the anchor were handed,
    the broadcast model and the bytes each host pass wrote."""
    from test_resilience import _settings

    from xaynet_tpu.server.coordinator import CoordinatorState
    from xaynet_tpu.server.events import EventPublisher, PhaseName
    from xaynet_tpu.resilience.checkpoint import entry
    from xaynet_tpu.server.phases.base import Shared
    from xaynet_tpu.server.phases.resume import resume_phase
    from xaynet_tpu.server.phases.unmask import Unmask
    from xaynet_tpu.server.requests import RequestReceiver
    from xaynet_tpu.storage.memory import (
        InMemoryCoordinatorStorage, InMemoryModelStorage, NoOpTrustAnchor)
    from xaynet_tpu.storage.traits import Store

    device, n_devices, open_stream = ARMS[arm]
    config = _config(n_limbs)
    mesh = make_mesh(jax.devices()[:n_devices]) if device else None
    agg, mask, mean = _staged_round(config, n, mesh, np.random.default_rng(11), device=device)
    resumed = arm == "mesh_resumed"
    view = None if resumed else agg.finalize_inplace(defer_drain=open_stream)

    handed = {"store": [], "anchor": []}

    class Models(InMemoryModelStorage):
        async def set_global_model(self, round_id, round_seed, model_data):
            handed["store"].append(model_data)
            return await super().set_global_model(round_id, round_seed, model_data)

    class Anchor(NoOpTrustAnchor):
        async def publish_proof(self, model_data):
            handed["anchor"].append(model_data)

    settings = _settings(model_len=n)
    settings.aggregation.device = device
    settings.aggregation.kernel = "xla"
    settings.mask.group_type = config.group_type
    settings.mask.bound_type = config.bound_type
    settings.mask.model_type = config.model_type
    state = CoordinatorState.from_settings(settings)
    assert state.round_params.mask_config == config.pair()

    async def run():
        coord = InMemoryCoordinatorStorage()
        pk = b"\x01" * 32
        assert await coord.add_sum_participant(pk, b"e" * 32) is None
        assert await coord.incr_mask_score(pk, mask) is None
        models = Models()
        events = EventPublisher(
            round_id=0, keys=state.keys, params=state.round_params, phase=PhaseName.IDLE)
        shared = Shared(
            state=state, request_rx=RequestReceiver(), events=events,
            store=Store(coord, models, Anchor()), settings=settings, metrics=None)
        if resumed:
            # the journal's way back in: the aggregate restored into a new
            # aggregator, which hands over with no pipeline opened
            phase = resume_phase(shared, entry(shared, "unmask", agg.snapshot_journal()))
        else:
            phase = Unmask(shared, view)
        assert isinstance(phase.model_agg, DeviceAggregation) == device
        assert (getattr(phase.model_agg, "_stream", None) is not None) == open_stream
        before = _pass_bytes()
        with pytest.MonkeyPatch.context() as patch:
            if arm == "mesh_failed_shard":
                _failing_shard(patch)
            await phase.process()
        phase.broadcast()
        after = _pass_bytes()
        stored = await models.global_model(await coord.latest_global_model_id())
        return phase, stored, events.model.get_latest().event.model, {
            name: after.get(name, 0) - before.get(name, 0)
            for name in ("transpose", "decode", "serialise")}

    phase, stored, broadcast, passes = asyncio.run(run())
    if sound:
        np.testing.assert_allclose(phase.global_model, mean, atol=1e-9)
    return phase, stored, handed, broadcast, passes


@pytest.mark.parametrize("n_limbs,length", SHAPES)
def test_every_arm_publishes_the_same_model_to_the_bit(n_limbs, length, monkeypatch):
    n = LENGTHS[length]
    taken = []
    for owner, name in ((ShardedAggregator, "unmask_planar"), (ShardedAggregator, "_unmask_plan"),
                        (StreamingAggregator, "finish_unmask")):
        def spy(self, *args, _real=getattr(owner, name), _name=name):
            taken.append(_name)
            return _real(self, *args)

        monkeypatch.setattr(owner, name, spy)
    drain_time = ["unmask_planar", "_unmask_plan"]
    routes = {"host": [], "one_device": ["unmask_planar"],
              "mesh_drain_time": drain_time, "mesh_eager": ["finish_unmask"],
              "mesh_failed_shard": ["finish_unmask", *drain_time],
              # restored into the mesh array: no shard plan to subtract by
              "mesh_resumed": ["unmask_planar"]}
    models = {}
    for arm in ARMS:
        del taken[:]
        phase, stored, _handed, broadcast, _passes = _run_unmask_phase(arm, n_limbs, n)
        assert taken == routes[arm], arm
        assert phase.global_model.dtype == np.float64 and phase.global_model.shape == (n,)
        assert broadcast is phase.global_model
        models[arm] = stored
    assert len(models["host"]) == 8 * n
    for arm in ARMS:
        assert models[arm] == models["host"], arm


@pytest.mark.parametrize("arm", list(ARMS))
@pytest.mark.parametrize("n_limbs", list(BOUNDS))
def test_the_model_is_decoded_once_and_serialised_once(arm, n_limbs):
    """Between the kernel's result and the end of the phase: one decode, no
    transposition, one serialisation, and the store and the trust anchor are
    handed the same ``bytes`` (``xaynet_unmask_model_bytes_total``)."""
    n = LENGTHS["padded"]
    phase, stored, handed, _broadcast, passes = _run_unmask_phase(arm, n_limbs, n)
    assert passes == {"transpose": 0, "decode": 8 * n, "serialise": 8 * n}
    assert len(handed["store"]) == len(handed["anchor"]) == 1
    assert type(handed["store"][0]) is bytes and handed["anchor"][0] is handed["store"][0]
    # a `bytes` is kept, not copied again, and it is the model's float64
    assert stored is handed["store"][0]
    assert stored == phase.global_model.tobytes()


@pytest.mark.parametrize("arm", list(ARMS))
def test_an_element_altered_where_it_is_decoded_is_stored_and_broadcast(arm, monkeypatch):
    """What ``benchmark/tests/serve_broken.py`` does: the decoder is looked
    up in its module when the phase runs, and the array it returns is the
    one whose values are stored, proved and broadcast."""
    from xaynet_tpu.core.mask import encode

    n = LENGTHS["whole"]
    sound_bytes = _run_unmask_phase(arm, 2, n)[1]
    real_decode = encode.decode_vect_fast

    def decode_altered(*args, **kwargs):
        out = real_decode(*args, **kwargs)
        out[len(out) // 2] += 2.0 ** -20
        return out

    monkeypatch.setattr(encode, "decode_vect_fast", decode_altered)
    phase, stored, handed, broadcast, _passes = _run_unmask_phase(arm, 2, n, sound=False)
    altered = np.frombuffer(stored, dtype=np.float64)
    differing = np.flatnonzero(altered != np.frombuffer(sound_bytes, dtype=np.float64))
    assert differing.tolist() == [n // 2]
    assert handed["anchor"][0] is handed["store"][0] is stored
    assert np.array_equal(np.asarray(broadcast), altered)


@pytest.mark.parametrize("n", [0, 1, 1003, (1 << 19) - 1, 1 << 19, (1 << 19) + 4097, 3 * (1 << 19) + 7])
def test_the_threaded_serialisation_is_numpys(n):
    """``utils/native.py::tobytes``: under, at and over the size from which
    the library's threads make the copy (4 MiB = 2^19 float64)."""
    from xaynet_tpu.utils import native

    model = np.random.default_rng(n).standard_normal(n)
    data = native.tobytes(model)
    assert type(data) is bytes and data == model.tobytes() and hash(data) == hash(model.tobytes())
    # a view that is not contiguous takes numpy's copy
    assert native.tobytes(model[::2]) == model[::2].tobytes()
