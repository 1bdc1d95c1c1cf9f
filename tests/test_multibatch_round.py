"""A round of more than one fold batch, served over the socket with
``[aggregation] device = true``, against a plain integer reference.

The round is the benchmark's three-batch cell at a small length: the REST
server on localhost, the SDK's participants over ``HttpClient``, the message
pipeline, the Update phase, ``StagedAggregator`` and the streaming ring on
one device (the CPU backend's first, as the chip's one), Sum2, unmask. The
reference below is the published rule in Python integers and ``Fraction``
and imports nothing of the program's encode, decode, limb or fold code. The
published model has to equal it bit for bit at 2 and 3 limbs, with a ring of
2 and of 3 buffers, for a round of whole batches and one with a remainder
batch (closed degraded), in two arrival orders: so the result does not
depend on which update landed in which batch. Around the Update phase the
counters have to say what the ring did: the folded batches, and the ring's
acquisitions by kind, which add up to the batches. The same round on a mesh
of four of the CPU backend's devices (ISSUE 40): the shard-parallel pipeline,
every update written into its four per-shard slots as it arrives.
"""

import asyncio
import threading
from fractions import Fraction

import jax
import numpy as np
import pytest

from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.core.mask.masking import Aggregation, Masker
from xaynet_tpu.core.mask.model import Scalar
from xaynet_tpu.parallel import aggregator as aggregator_mod
from xaynet_tpu.parallel import streaming
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, PhaseKind, StateMachine as ParticipantSM
from xaynet_tpu.sdk.traits import ModelStore
from xaynet_tpu.server.phases.base import ACCEPT_GAP_MAX
from xaynet_tpu.server.rest import RestServer
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

K, MODEL_LEN = 3, 257
SUM_PROB, UPDATE_PROB = 0.4, 0.5
SCALAR = Fraction(1, 16)  # dyadic: exact in the SDK's double-double encode
BOUNDS = {"2limb-b0m6": BoundType.B0, "3limb-b6m6": BoundType.B6}
HOWS = ("free", "leased", "waited")


# --- the plain reference: Python integers and Fractions only ----------------


def reference_model(weights: list[np.ndarray], add_shift: int, exp_shift: int) -> np.ndarray:
    """Each participant's ``floor((s*w + A) * E)`` summed in integers and
    decoded by the published rule ``((S / E) - nb*A) / scalar_sum`` to the
    nearest float64 (no weight here reaches the clamp)."""
    nb = len(weights)
    a, e = Fraction(add_shift), exp_shift

    def encode(x: Fraction) -> int:
        t = (x + a) * e
        return t.numerator // t.denominator

    scalar_sum = Fraction(nb * encode(SCALAR), e) - nb * a
    out = []
    for column in zip(*(w.tolist() for w in weights)):
        total = sum(encode(SCALAR * Fraction(w)) for w in column)
        out.append(float((Fraction(total, e) - nb * a) / scalar_sum))
    return np.array(out)


class _Store(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


def _config(width: str) -> MaskConfig:
    return MaskConfig(GroupType.INTEGER, DataType.F32, BOUNDS[width], ModelType.M6)


def _weights(n_update: int, bound_value: float) -> list[np.ndarray]:
    rng = np.random.default_rng(33)
    return [rng.uniform(-bound_value, bound_value, MODEL_LEN).astype(np.float32)
            for _ in range(n_update)]


@pytest.fixture
def one_device(monkeypatch, tmp_path):
    """The chip has one device; the tests' CPU backend has eight. The served
    round takes the first, so that its pipeline is the single-device one
    (a ring of whole-width buffers, slots written at arrival). A degraded
    close writes its flight dump under the test's own directory."""
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))


@pytest.fixture
def four_devices(monkeypatch, tmp_path):
    """A four-chip host: the served round takes four of the CPU backend's
    eight devices, so its pipeline is the shard-parallel one as shipped."""
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:4]))


def _settings(width: str, staging_buffers: int, n_update: int, count_min: int) -> Settings:
    window = TimeSettings(min=0.0, max=60.0)
    s = Settings(pet=ServerPet(
        sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(1, 1), time=window),
        # where the round sends fewer than count.min, it closes degraded once
        # nothing has been accepted for liveness.stall_grace_s (the
        # benchmark's warm-up round closes so)
        update=PhaseSettings(prob=UPDATE_PROB, time=window,
                             count=CountSettings(count_min, count_min, quorum=min(K, count_min))),
        sum2=Sum2Settings(count=CountSettings(1, 1), time=window),
    ))
    s.liveness.stall_grace_s = 0.4
    s.model.length = MODEL_LEN
    s.mask.group_type, s.mask.data_type = GroupType.INTEGER, DataType.F32
    s.mask.bound_type, s.mask.model_type = BOUNDS[width], ModelType.M6
    s.aggregation.device = True
    s.aggregation.batch_size = K
    s.aggregation.staging_buffers = staging_buffers
    return s


def _pipeline_counters() -> dict:
    out = {("batches", stage): streaming.BATCHES_TOTAL.labels(stage=stage).value
           for stage in ("staged", "folded", "failed")}
    out.update({("ring", how): streaming.RING_WAIT_SECONDS.labels(how=how).count for how in HOWS})
    out["rows", "arrival"] = streaming.ROWS_STAGED.labels(route="arrival").value
    out["rows", "flush"] = streaming.ROWS_STAGED.labels(route="flush").value
    out["commits"] = streaming.COMMIT_SECONDS.count
    out["staged_bytes"] = sum(aggregator_mod.BYTES_STAGED.labels(layout=layout).value
                              for layout in ("packed", "unpacked", "wire"))
    out["h2d_bytes"] = streaming.H2D_BYTES.value
    out["h2d_early_bytes"] = streaming.H2D_EARLY_BYTES.value
    return out


async def _copied(target: float) -> None:
    """The pipeline's copier has put ``target`` bytes of rows on the devices
    (the counter a copy moves when it ends; there is nothing else to wait on
    from outside the coordinator)."""
    deadline = asyncio.get_running_loop().time() + 30
    while streaming.H2D_BYTES.value < target:
        assert asyncio.get_running_loop().time() < deadline
        await asyncio.sleep(0.002)


async def _served_round(settings: Settings, weights: list[np.ndarray], order: list[int],
                        row_bytes: int = 0) -> dict:
    """One PET round over the REST API on localhost; the updaters send one
    after another in ``order``, and where ``row_bytes`` is given the next one
    only once the answered ones' rows are on the devices. Returns the
    published model and how far the pipeline's counters moved over the
    Update phase."""
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(settings, store).init()
    fetcher = Fetcher(events)
    rest = RestServer(fetcher, PetMessageHandler(events, request_tx))
    host, port = await rest.start("127.0.0.1", 0)  # a port of its own
    url = f"http://{host}:{port}"
    machine_task = asyncio.create_task(machine.run())
    clients = []

    def client():
        clients.append(HttpClient(url))
        return clients[-1]

    try:
        while fetcher.phase().value != "sum":
            await asyncio.sleep(0.005)
        seed = fetcher.round_params().seed.as_bytes()
        summer = ParticipantSM(
            PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum"),
                        device_sum2=False, max_message_size=None),
            client(), _Store(None))
        updaters = [
            ParticipantSM(
                PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update",
                                               start=(10 + i) * 1000),
                            scalar=SCALAR, max_message_size=None),
                client(), _Store(w))
            for i, w in enumerate(weights)]

        async def drive_summer():
            while fetcher.model() is None:
                await summer.transition()
                await asyncio.sleep(0.005)

        sum_task = asyncio.create_task(drive_summer())
        while fetcher.phase().value != "update":
            await asyncio.sleep(0.005)
        before = _pipeline_counters()
        for done, i in enumerate(order, 1):  # one upload at a time, in `order`
            sm, sent = updaters[i], False
            while not (sent and sm.phase is PhaseKind.AWAITING):
                await sm.transition()
                sent = sent or sm.phase is PhaseKind.UPDATE
            if row_bytes:
                await _copied(before["h2d_bytes"] + done * row_bytes)
        gap = ACCEPT_GAP_MAX.value
        # to the model's publication: the phase's last flush is drained under
        # Sum2's window (docs/DESIGN.md §22)
        await sum_task
        moved = {key: value - before[key] for key, value in _pipeline_counters().items()}
        return {"model": np.asarray(fetcher.model(), dtype=np.float64), "moved": moved,
                "gap": gap, "depth": streaming.STAGING_DEPTH.value}
    finally:
        machine_task.cancel()
        for c in clients:
            c.close()
        await rest.stop()
        await asyncio.gather(machine_task, return_exceptions=True)


@pytest.mark.parametrize("arrival", ["in-order", "shuffled"])
@pytest.mark.parametrize("shape", ["whole", "remainder"])
@pytest.mark.parametrize("staging_buffers", [2, 3])
@pytest.mark.parametrize("width", list(BOUNDS))
def test_served_multibatch_round_equals_the_plain_reference(
        width, staging_buffers, shape, arrival, one_device):
    config = _config(width)
    assert config.bytes_per_number == {"2limb-b0m6": 7, "3limb-b6m6": 10}[width]
    n_update = 3 * K + (shape == "remainder")
    batches = 3 + (shape == "remainder")
    # whole: the phase closes by count; remainder: 3K + 1 of 4K, so it closes
    # degraded and drain() folds the batch of one
    count_min = n_update if shape == "whole" else 4 * K
    weights = _weights(n_update, float(config.add_shift))
    order = list(range(n_update))
    if arrival == "shuffled":
        order = [int(i) for i in np.random.default_rng(7).permutation(n_update)]
        assert order[:K] != list(range(K))  # another first batch
    depth0 = streaming.STAGING_DEPTH.value
    out = asyncio.run(asyncio.wait_for(
        _served_round(_settings(width, staging_buffers, n_update, count_min), weights, order), 180))

    want = reference_model(weights, int(config.add_shift), config.exp_shift)
    assert out["model"].shape == want.shape
    assert np.array_equal(out["model"].view(np.uint64), want.view(np.uint64))

    moved = out["moved"]
    assert moved["batches", "folded"] == moved["batches", "staged"] == batches
    assert moved["batches", "failed"] == 0
    assert moved["commits"] == batches  # one a batch on one shard too
    # every row was written into its slot as it arrived, none at a flush
    assert (moved["rows", "arrival"], moved["rows", "flush"]) == (n_update, 0)
    # and went to the device from there, row by row, each byte once
    assert aggregator_mod.fold_kernel_report()["h2d_route"] == "row"
    assert moved["h2d_bytes"] == moved["staged_bytes"]
    assert 0 <= moved["h2d_early_bytes"] <= moved["h2d_bytes"]
    # one acquisition a batch; a ring of `staging_buffers` leases no more
    # than that, so a round of more batches took a buffer again
    ring = {how: moved["ring", how] for how in HOWS}
    assert sum(ring.values()) == batches, ring
    assert 1 <= ring["leased"] <= staging_buffers, ring
    assert ring["free"] + ring["waited"] >= batches - staging_buffers, ring
    assert out["depth"] == depth0  # every buffer went back
    assert out["gap"] > 0.0  # the longest gap between two accepted updates


def _compile_the_mesh_pipeline(config: MaskConfig) -> None:
    """One batch of ``K`` and one of one through a pipeline of the served
    round's shapes on the four devices, so that the round compiles nothing:
    its uploads, which wait for each other's copies here, then stay well
    inside ``stall_grace_s``, which closes the remainder round."""
    agg = aggregator_mod.ShardedAggregator(
        config, MODEL_LEN, mesh=make_mesh(jax.devices()[:4]), kernel="xla")
    stream = streaming.StreamingAggregator(agg, staging_buffers=2, max_batch=K)
    row = np.zeros((MODEL_LEN, agg.n_limbs), dtype=np.uint32)
    for k in (K, 1):
        bufs = stream.open_batch()
        for i in range(k):
            stream.stage_row(bufs, i, row)
        stream.submit_staged(bufs, k)
    stream.close()


@pytest.mark.parametrize("shape", ["whole", "remainder"])
@pytest.mark.parametrize("width", list(BOUNDS))
def test_served_round_on_a_mesh_stages_every_update_at_arrival(width, shape, four_devices):
    """Two batches (and a remainder batch of one) on four shards: the model
    is the plain reference's bit for bit, every accepted update was staged
    at arrival and once (its packed bytes, no planar row beside them) and
    copied to the shards' devices from its slot, row by row (some of it
    before its batch's flush: the counters are read once the model is
    published, so nothing here waits for a copy), each shard's ring was asked
    once a batch, and each batch committed once."""
    config = _config(width)
    n_update = 2 * K + (shape == "remainder")
    batches = 2 + (shape == "remainder")
    count_min = n_update if shape == "whole" else 3 * K
    weights = _weights(n_update, float(config.add_shift))
    order = [int(i) for i in np.random.default_rng(11).permutation(n_update)]
    padded = -(-MODEL_LEN // 4) * 4
    row_bytes = config.bytes_per_number * padded
    _compile_the_mesh_pipeline(config)
    depth0 = streaming.STAGING_DEPTH.value
    out = asyncio.run(asyncio.wait_for(
        _served_round(_settings(width, 2, n_update, count_min), weights, order, row_bytes), 180))

    want = reference_model(weights, int(config.add_shift), config.exp_shift)
    assert np.array_equal(out["model"].view(np.uint64), want.view(np.uint64))
    fold = aggregator_mod.fold_kernel_report()
    assert (fold["shards"], fold["shard_length"]) == (4, padded // 4)
    assert fold["h2d_route"] == "row"
    moved = out["moved"]
    assert moved["batches", "folded"] == moved["batches", "staged"] == batches
    assert moved["batches", "failed"] == 0
    assert (moved["rows", "arrival"], moved["rows", "flush"]) == (n_update, 0)
    assert moved["staged_bytes"] == n_update * config.bytes_per_number * padded
    assert moved["h2d_bytes"] == moved["staged_bytes"]
    # every upload was sent once its predecessors' rows were on the devices:
    # a flush can have found at most its own, last row's copy outstanding
    assert (n_update - batches) * row_bytes <= moved["h2d_early_bytes"] <= moved["h2d_bytes"]
    assert moved["commits"] == batches
    ring = {how: moved["ring", how] for how in HOWS}
    assert sum(ring.values()) == 4 * batches, ring
    assert 4 <= ring["leased"] <= 4 * 2, ring
    assert out["depth"] == depth0


def test_ring_acquisitions_are_counted_by_kind():
    """free: a buffer lay in the ring; leased: a new one was leased;
    waited: every buffer was owned by a batch in flight."""
    ring = streaming._StagingRing(2, (4, 8), np.uint8)
    try:
        before = {how: streaming.RING_WAIT_SECONDS.labels(how=how).count for how in HOWS}

        def kinds():
            return tuple(streaming.RING_WAIT_SECONDS.labels(how=how).count - before[how]
                         for how in HOWS)

        a = ring.acquire()
        assert kinds() == (0, 1, 0)
        b = ring.acquire()
        assert kinds() == (0, 2, 0)
        got = []
        waiter = threading.Thread(target=lambda: got.append(ring.acquire(timeout=30)))
        waiter.start()
        waiter.join(0.2)
        assert waiter.is_alive() and kinds() == (0, 2, 0)  # observed when it ends
        ring.release(a)
        waiter.join(30)
        assert got and got[0] is a and kinds() == (0, 2, 1)
        ring.release(b)
        assert ring.acquire() is b and kinds() == (1, 2, 1)
    finally:
        ring.close()


@pytest.mark.parametrize("failing_slot", [0, 2], ids=["first-write", "last-write"])
@pytest.mark.parametrize("width", list(BOUNDS))
def test_failed_slot_write_in_batch_two_raises_from_the_flush(width, failing_slot):
    """A slot write of the second batch fails: the flush that closes the
    batch raises that error, every buffer is back in the ring, and the first
    batch's fold is in the accumulator, whole."""
    from xaynet_tpu.server.aggregation import StagedAggregator

    config, n = _config(width), 103
    rng = np.random.default_rng(5)
    objs = [Masker(config.pair()).mask(
                Scalar(1, 8), rng.uniform(-1, 1, n).astype(np.float32))[1] for _ in range(2 * K)]
    dev = StagedAggregator(config.pair(), n, device=True, batch_size=K, kernel="xla",
                           mesh=make_mesh(jax.devices()[:1]), staging_buffers=2)
    stream = dev._stream
    depth0 = streaming.STAGING_DEPTH.value
    failed0 = streaming.BATCHES_TOTAL.labels(stage="failed").value
    for obj in objs[:K]:  # batch 1: filled, flushed, with the pipeline
        dev.validate_aggregation(obj)
        dev.aggregate(obj)
    real = stream.stage_row

    def stage_row(buf, i, wire):
        if i == failing_slot:
            raise OSError("slot write failed")
        real(buf, i, wire)

    stream.stage_row = stage_row
    for obj in objs[K:-1]:
        dev.validate_aggregation(obj)
        dev.aggregate(obj)
    dev.validate_aggregation(objs[-1])
    with pytest.raises(OSError, match="slot write failed"):
        dev.aggregate(objs[-1])  # fills batch 2: its flush raises
    assert dev.pending == 0 and not dev._open
    dev.drain()  # batch 1's fold may still hold its buffer until here
    assert streaming.STAGING_DEPTH.value == depth0
    ring = stream._ring(stream._host_kind)
    assert not ring._inflight
    assert streaming.BATCHES_TOTAL.labels(stage="failed").value == failed0
    got = dev.finalize()
    want = Aggregation(config.pair(), n)
    for obj in objs[:K]:
        want.aggregate(obj)
    assert got.nb_models == want.nb_models == K
    assert got.object == want.object
