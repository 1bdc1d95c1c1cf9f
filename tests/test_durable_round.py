"""A coordinator that keeps its round journal, served over the socket with
``[aggregation] device = true``, against a plain integer reference.

The round is the benchmark's ``resnet50-f32m6-durable.flood`` at a small
length: two fold batches, ``[resilience] checkpoint_enabled`` with a journal
write after every batch, ``[restore] enable``, coordinator state, journal and
stored models in files under the test's own directory (the store the runner
builds: ``init_store`` + ``wrap_store``). The reference is the published
rule in Python integers and ``Fraction`` and imports nothing of the program's
encode, decode, limb or fold code.

(a) the journalled round publishes the reference's model bit for bit and the
unjournalled round's byte for byte, under the three masks the benchmark has,
on one device and on a mesh of four; (b) killed at each of four points (the
kill is ``resilience.chaos``'s, with the process's death replaced by the
death of the state machine's task) and restarted on the same directory, the
round resumes into the killed phase and publishes the reference's model over
every counted update; (c) one round writes the expected entries by phase and
``xaynet_journal_*`` and ``/healthz`` say so; (d) a write that exhausts its
retries is counted and the round goes on; (e) what an earlier process left
in the directory never stops a start; (f) the sections a round's entries
carry are counted by what became of them, and each is hashed once; (g) the
single-file journal a process of the program before left is resumed.
"""

import asyncio
import gc
import json
import os
import urllib.request
from fractions import Fraction

import jax
import numpy as np
import pytest

from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.parallel import aggregator as aggregator_mod
from xaynet_tpu.parallel import streaming
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.resilience import FaultPlan, chaos, clear_plan, install_plan, wrap_store
from xaynet_tpu.resilience import checkpoint as ckpt_mod
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, PhaseKind, StateMachine as ParticipantSM
from xaynet_tpu.sdk.traits import ModelStore
from xaynet_tpu.server.rest import RestServer
from xaynet_tpu.server.runner import _health_sections, init_store
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
from xaynet_tpu.storage.memory import FileCoordinatorStorage
from xaynet_tpu.storage.traits import join_entry
from xaynet_tpu.telemetry import journal

from journal_reference import CountingHashlib, reference_to_bytes

K, MODEL_LEN, N_UPDATE = 3, 257, 6
SUM_PROB, UPDATE_PROB = 0.4, 0.5
SCALAR = Fraction(1, 16)  # dyadic: exact in the SDK's double-double encode
# the three masks BENCHMARK.json has
MASKS = {
    "int-b0m6": (GroupType.INTEGER, BoundType.B0, ModelType.M6),
    "int-b6m6": (GroupType.INTEGER, BoundType.B6, ModelType.M6),
    "prime-b0m3": (GroupType.PRIME, BoundType.B0, ModelType.M3),
}
STAGES = ("drain", "fetch", "dicts", "serialise", "store", "total")
PHASES = ("sum", "update", "sum2", "unmask")


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


def _config(mask: str) -> MaskConfig:
    group, bound, model = MASKS[mask]
    return MaskConfig(group, DataType.F32, bound, model)


def _weights(bound_value: float, seed: int = 46) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.uniform(-bound_value, bound_value, MODEL_LEN).astype(np.float32)
            for _ in range(N_UPDATE)]


def _want(mask: str, weights: list[np.ndarray]) -> np.ndarray:
    config = _config(mask)
    return reference_model(weights, int(config.add_shift), config.exp_shift)


def _bits_equal(model: np.ndarray, want: np.ndarray) -> bool:
    return model.shape == want.shape and np.array_equal(model.view(np.uint64), want.view(np.uint64))


def _settings(mask: str, model_dir, journal_on: bool = True, model_len: int = MODEL_LEN) -> Settings:
    window = TimeSettings(min=0.0, max=60.0)
    s = Settings(pet=ServerPet(
        sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(1, 1), time=window),
        update=PhaseSettings(prob=UPDATE_PROB, time=window,
                             count=CountSettings(N_UPDATE, N_UPDATE)),
        sum2=Sum2Settings(count=CountSettings(1, 1), time=window),
    ))
    group, bound, model = MASKS[mask]
    s.model.length = model_len
    s.mask.group_type, s.mask.data_type = group, DataType.F32
    s.mask.bound_type, s.mask.model_type = bound, model
    s.aggregation.device = True
    s.aggregation.batch_size = K
    # the deployment: configs/config.toml's stores, the journal after every
    # fold batch, restore at boot
    s.storage.backend, s.storage.coordinator = "filesystem", "file"
    s.storage.model_dir = str(model_dir)
    s.restore.enable = True
    s.resilience.checkpoint_enabled = journal_on
    s.resilience.checkpoint_every_batches = 1
    s.resilience.retry_base_ms, s.resilience.retry_max_ms = 1.0, 5.0
    return s


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.delenv(chaos.ENV, raising=False)
    chaos._visits.clear()
    clear_plan()
    yield
    clear_plan()
    chaos._visits.clear()


@pytest.fixture(params=[1, 4], ids=["one-device", "four-devices"])
def devices(request, monkeypatch):
    """The chip's one device, or a four-chip host's mesh, of the CPU
    backend's eight."""
    n = request.param
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:n]))
    return n


@pytest.fixture
def one_device(monkeypatch):
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))


class _Killed(asyncio.CancelledError):
    """The coordinator's death, as far as one process can play it: the state
    machine's task ends where ``maybe_kill`` stands, and nothing after it
    runs (no acknowledgement, no flush, no retire)."""


@pytest.fixture
def kill_at(monkeypatch):
    def die():
        raise _Killed()

    monkeypatch.setattr(chaos, "_die", die)

    def arm(point: str) -> None:
        chaos._visits.clear()
        monkeypatch.setenv(chaos.ENV, point)

    return arm


class Coordinator:
    """One coordinator process in miniature: the store as the runner builds
    it, the initializer (which restores and resumes), the REST server."""

    def __init__(self, settings: Settings):
        self.settings = settings
        self.clients: list = []

    async def start(self) -> "Coordinator":
        store = wrap_store(init_store(self.settings), self.settings.resilience)
        self.store = store
        self.machine, request_tx, events = await StateMachineInitializer(
            self.settings, store).init()
        self.fetcher = Fetcher(events)
        handler = PetMessageHandler(events, request_tx)
        self.rest = RestServer(
            self.fetcher, handler,
            health_extra=_health_sections(handler, None, self.settings.resilience))
        host, port = await self.rest.start("127.0.0.1", 0)
        self.url = f"http://{host}:{port}"
        self.task = asyncio.create_task(self.machine.run())
        return self

    def client(self) -> HttpClient:
        self.clients.append(HttpClient(self.url))
        return self.clients[-1]

    async def phase(self, name: str) -> None:
        while self.fetcher.phase().value != name:
            assert not self.task.done(), self.task
            await asyncio.sleep(0.005)

    async def healthz(self) -> dict:
        def get():
            with urllib.request.urlopen(self.url + "/healthz", timeout=30) as resp:
                return json.loads(resp.read())

        return await asyncio.get_running_loop().run_in_executor(None, get)

    async def stop(self) -> None:
        self.task.cancel()
        for c in self.clients:
            c.close()
        await self.rest.stop()
        await asyncio.gather(self.task, return_exceptions=True)


def _summer(coord: Coordinator, seed: bytes) -> ParticipantSM:
    return ParticipantSM(
        PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum"),
                    device_sum2=False, max_message_size=None),
        coord.client(), _Store(None))


def _updater(coord: Coordinator, seed: bytes, i: int, w: np.ndarray) -> ParticipantSM:
    return ParticipantSM(
        PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update",
                                       start=(10 + i) * 1000),
                    scalar=SCALAR, max_message_size=None),
        coord.client(), _Store(w))


async def _send(sm: ParticipantSM) -> None:
    """Drive one updater until its upload was answered."""
    sent = False
    while not (sent and sm.phase is PhaseKind.AWAITING):
        await sm.transition()
        sent = sent or sm.phase is PhaseKind.UPDATE


async def _drive(sm: ParticipantSM, until) -> None:
    while not until():
        try:
            await sm.transition()
        except Exception:  # a coordinator that died under the request
            pass
        await asyncio.sleep(0.005)


async def _round(settings: Settings, weights: list[np.ndarray]) -> dict:
    """One whole round on a fresh start; the published model and the boot."""
    coord = await Coordinator(settings).start()
    try:
        await coord.phase("sum")
        seed = coord.fetcher.round_params().seed.as_bytes()
        published = coord.fetcher.model()  # what a restore found, if anything
        summer = asyncio.create_task(_drive(
            _summer(coord, seed), lambda: coord.fetcher.model() is not published))
        await coord.phase("update")
        for i, w in enumerate(weights):
            await _send(_updater(coord, seed, i, w))
        await summer
        return {"model": np.asarray(coord.fetcher.model(), dtype=np.float64),
                "health": await coord.healthz()}
    finally:
        await coord.stop()


def _journal_on_disk(model_dir):
    """The entry the directory holds, as a restart's store reads it."""
    blob = FileCoordinatorStorage(
        os.path.join(model_dir, "coordinator_state.json"))._read_ckpt()
    return None if blob is None else ckpt_mod.RoundCheckpoint.from_bytes(blob)


def _beside_the_head(model_dir) -> list[str]:
    """The section files of the journal, and whatever else lies beside its head."""
    return sorted(f for f in os.listdir(model_dir)
                  if f.startswith("coordinator_state.json.ckpt."))


def _run(coro, timeout: float = 150.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# --- (a) the journal changes nothing of the result ---------------------------


@pytest.mark.parametrize("mask", list(MASKS))
def test_journalled_round_equals_the_reference_and_the_unjournalled_round(
        mask, devices, tmp_path):
    config = _config(mask)
    weights = _weights(float(config.add_shift))
    saved0 = sum(journal.WRITES.labels(phase=p, outcome="saved").value for p in PHASES)
    kept = _run(_round(_settings(mask, tmp_path / "kept"), weights))
    saved1 = sum(journal.WRITES.labels(phase=p, outcome="saved").value for p in PHASES)
    bare = _run(_round(_settings(mask, tmp_path / "bare", journal_on=False), weights))
    saved2 = sum(journal.WRITES.labels(phase=p, outcome="saved").value for p in PHASES)
    assert _bits_equal(kept["model"], _want(mask, weights))
    assert kept["model"].tobytes() == bare["model"].tobytes()
    assert saved1 - saved0 == 7 and saved2 == saved1  # and only one of them kept a journal
    fold = aggregator_mod.fold_kernel_report()
    assert fold["shards"] == devices
    # the stored model is the published one, and the journal is retired
    stored = [f for f in os.listdir(tmp_path / "kept") if f.endswith(".bin")]
    assert len(stored) == 1
    with open(tmp_path / "kept" / stored[0], "rb") as f:
        assert f.read() == kept["model"].tobytes()
    assert not os.path.exists(tmp_path / "kept" / "coordinator_state.json.ckpt")
    assert _beside_the_head(tmp_path / "kept") == []  # nor a section of it


# --- (b) killed and restarted on the same directory --------------------------

# point -> (the phase the restart resumes into, updates journalled at the kill)
KILLS = {
    f"update:{K + 2}": ("update", K),
    "sum2:base:1": ("sum2", N_UPDATE),
    "sum2:1": ("sum2", N_UPDATE),
    "unmask:start:1": ("unmask", N_UPDATE),
    # the file store's own point, sections written and the head not renamed,
    # at its second visit (the second fold batch's entry: the first batch's
    # is what the restart finds) and its fourth (the vote's: Sum2's base)
    "journal:sections:2": ("update", K),
    "journal:sections:4": ("sum2", N_UPDATE),
}


def _killed_then_restarted(point, kill_at, tmp_path, between=None):
    """One round killed at ``point`` and restarted on the same directory
    (``between`` may touch the directory while no process lives): asserts
    what the journal holds at the death and that the restart resumes into
    the killed phase; returns the published model, the reference's, and the
    ring buffers still leased."""
    mask = "int-b0m6"
    phase, journalled = KILLS[point]
    weights = _weights(1.0)
    settings = _settings(mask, tmp_path)
    failed0 = sum(journal.report(True, 1)["failed"].values())

    async def killed_run():
        coord = await Coordinator(settings).start()
        try:
            await coord.phase("sum")
            seed = coord.fetcher.round_params().seed.as_bytes()
            summer = _summer(coord, seed)
            sum_task = asyncio.create_task(_drive(summer, coord.task.done))
            await coord.phase("update")
            staged0 = streaming.ROWS_STAGED.labels(route="arrival").value
            kill_at(point)
            sends = []
            for i, w in enumerate(weights):
                if coord.task.done():
                    break
                send = asyncio.create_task(_send(_updater(coord, seed, i, w)))
                sends.append(send)
                await asyncio.wait({send, coord.task}, return_when=asyncio.FIRST_COMPLETED)
            await asyncio.wait({coord.task}, timeout=60)
            assert coord.task.cancelled()  # it died where the point stands
            # the killed upload's slot write runs on the ingest pool, beside
            # the request that died: give it its moment to land
            for _ in range(500):
                staged = streaming.ROWS_STAGED.labels(route="arrival").value - staged0
                if phase != "update" or staged >= K + 2:
                    break
                await asyncio.sleep(0.01)
            for task in (*sends, sum_task):
                task.cancel()
            await asyncio.gather(*sends, sum_task, return_exceptions=True)
            return seed, summer.save(), staged
        finally:
            await coord.stop()

    async def restarted_run(seed, summer_blob):
        os.environ.pop(chaos.ENV, None)
        resumed0 = ckpt_mod.RESUME_TOTAL.labels(phase=phase, outcome="resumed").value
        gc.collect()  # what the dead process held goes with it
        depth0 = streaming.STAGING_DEPTH.value
        coord = await Coordinator(settings).start()
        try:
            assert coord.machine.phase.NAME.value == phase
            assert ckpt_mod.RESUME_TOTAL.labels(phase=phase, outcome="resumed").value \
                == resumed0 + 1
            assert coord.fetcher.round_params().seed.as_bytes() == seed  # the same round
            summer = ParticipantSM.restore(summer_blob, coord.client(), _Store(None))
            sum_task = asyncio.create_task(_drive(
                summer, lambda: coord.fetcher.model() is not None))
            # every silo whose upload the journal does not hold sends again:
            # the one that was answered 200 after the last entry, the one
            # that died with its request, and the ones that never sent
            for i in range(journalled, N_UPDATE):
                await _send(_updater(coord, seed, i, weights[i]))
            await sum_task
            model = np.asarray(coord.fetcher.model(), dtype=np.float64)
            for _ in range(400):  # the journal retires once the model is stored
                if await coord.store.coordinator.round_checkpoint() is None:
                    break
                await asyncio.sleep(0.01)
            assert await coord.store.coordinator.round_checkpoint() is None
            health = await coord.healthz()
            return model, streaming.STAGING_DEPTH.value - depth0, health
        finally:
            await coord.stop()

    seed, summer_blob, staged = _run(killed_run())
    if point.startswith("update:"):
        # the first batch is journalled; two rows of the second were written
        # into their slots as they arrived (the first of them was answered)
        assert staged == K + 2
    entry = _journal_on_disk(tmp_path)
    assert (entry.phase, entry.nb_models, entry.seed_watermark) == (
        phase, journalled, journalled)
    named = {f"coordinator_state.json.ckpt.{s.digest}" for s in entry.sections() if s.nbytes}
    if point.startswith("journal:sections:"):
        # the entry that died left its sections beside the live entry's, and
        # no vote: the head that names them was never renamed into place
        assert named < set(_beside_the_head(tmp_path)) and not entry.mask_votes
    else:
        assert named == set(_beside_the_head(tmp_path))
    if between is not None:
        between(entry)
    model, leased, health = _run(restarted_run(seed, summer_blob))
    assert _beside_the_head(tmp_path) == []  # the retire took every section
    # no write failed but the one that died in the store's hands (which a
    # process that dies does not live to count)
    failed = sum(health["journal"]["failed"].values()) - failed0
    assert failed == (1 if point.startswith("journal:sections:") else 0)
    assert health["journal"]["enabled"] is True
    return model, _want(mask, weights), leased


@pytest.mark.parametrize("point", list(KILLS))
def test_killed_and_restarted_round_publishes_the_reference(point, one_device, kill_at, tmp_path):
    model, want, leased = _killed_then_restarted(point, kill_at, tmp_path)
    # every update is in the model once: the journalled ones from the
    # journal, the others from their second sending
    assert _bits_equal(model, want)
    assert leased == 0  # no ring buffer stays leased


# --- (g) the journal a process of the program before left --------------------


@pytest.mark.parametrize("point", [f"update:{K + 2}", "sum2:1"])
def test_a_single_file_journal_written_by_the_reference_serialiser_resumes(
        point, one_device, kill_at, tmp_path):
    journal_path = tmp_path / "coordinator_state.json.ckpt"

    def as_the_parent_left_it(entry):
        for name in _beside_the_head(tmp_path):
            os.remove(tmp_path / name)
        journal_path.write_bytes(reference_to_bytes(entry))

    model, want, leased = _killed_then_restarted(
        point, kill_at, tmp_path, between=as_the_parent_left_it)
    assert _bits_equal(model, want)
    assert leased == 0 and not journal_path.exists()


# --- (c) what one round writes ----------------------------------------------


def test_one_round_writes_the_expected_entries_and_counts_them(one_device, tmp_path, monkeypatch):
    blobs: list[bytes] = []  # each entry as `round_checkpoint()` would return it
    handed: list[int] = []  # and the bytes its write handed the store
    real = FileCoordinatorStorage._write_ckpt

    def write_ckpt(self, head, sections=()):
        blobs.append(join_entry(head, sections))
        handed.append(len(head) + sum(s.nbytes for s in sections if not s.stored))
        real(self, head, sections)

    monkeypatch.setattr(FileCoordinatorStorage, "_write_ckpt", write_ckpt)

    def counters():
        out = {("writes", p, o): journal.WRITES.labels(phase=p, outcome=o).value
               for p in PHASES for o in ("saved", "failed")}
        out.update({("bytes", p): journal.BYTES.labels(phase=p).value for p in PHASES})
        out.update({("stage", s, p): journal.SECONDS.labels(stage=s, phase=p).count
                    for s in STAGES for p in PHASES})
        return out

    mask = "int-b0m6"
    weights = _weights(1.0)
    before = counters()
    health0 = journal.report(True, 1)
    out = _run(_round(_settings(mask, tmp_path), weights))
    moved = {key: value - before[key] for key, value in counters().items()}
    assert _bits_equal(out["model"], _want(mask, weights))

    tags = [ckpt_mod.RoundCheckpoint.from_bytes(b) for b in blobs]
    # one a sum participant, the seal at Sum -> Update, one a fold batch,
    # Sum2's base, one a vote, and the way into Unmask
    assert [(t.phase, t.nb_models, len(t.mask_votes)) for t in tags] == [
        ("sum", 0, 0), ("update", 0, 0), ("update", K, 0), ("update", 2 * K, 0),
        ("sum2", 2 * K, 0), ("sum2", 2 * K, 1), ("unmask", 2 * K, 1)]
    want_writes = {"sum": 1, "update": 3, "sum2": 2, "unmask": 1}
    for p in PHASES:
        assert moved["writes", p, "saved"] == want_writes[p]
        assert moved["writes", p, "failed"] == 0
        assert moved["bytes", p] == sum(n for n, t in zip(handed, tags) if t.phase == p)
        # every stage a write has is observed once a write
        assert moved["stage", "total", p] == want_writes[p]
        assert moved["stage", "serialise", p] == moved["stage", "store", p] == want_writes[p]
    # the entries that carry an aggregate ran the barrier and the copy
    carried = {"sum": 0, "update": 2, "sum2": 1, "unmask": 0}
    read_dicts = {"sum": 1, "update": 3, "sum2": 1, "unmask": 0}
    for p in PHASES:
        assert moved["stage", "drain", p] == moved["stage", "fetch", p] == carried[p]
        assert moved["stage", "dicts", p] == read_dicts[p]
    # an entry with the aggregate holds its packed planes whole
    config = _config(mask)
    limbs = 2
    assert all(len(b) > 4 * limbs * MODEL_LEN for b, t in zip(blobs, tags) if t.nb_models)
    assert config.bytes_per_number == 7
    # a write hands the store what no earlier entry handed it: the entries
    # of the fold batches and Sum2's base their aggregate, the vote's entry
    # the vote, the `unmask` entry a head
    assert [n == len(b) for n, b in zip(handed, blobs)] == [True] * 5 + [False] * 2
    planes = sum(plane.nbytes for _, _, plane in tags[6].planes)
    vote = len(tags[6].mask_votes[0][1])
    assert handed[5] == len(blobs[5]) - planes - tags[5].unit.nbytes
    assert handed[6] == len(blobs[6]) - planes - tags[6].unit.nbytes - vote
    assert blobs[6] == reference_to_bytes(tags[6])

    section = out["health"]["journal"]
    assert (section["enabled"], section["every_batches"]) == (True, 1)
    for p in PHASES:
        assert section["writes"][p] - health0["writes"].get(p, 0) == want_writes[p]
    assert section["failed"] == health0["failed"]
    assert section["last"]["phase"] == "unmask" and section["last"]["outcome"] == "saved"
    assert section["last"]["bytes"] == handed[-1] and section["last"]["seconds"] > 0.0
    # the last entry carried the aggregate, the unit and the vote, and wrote none of them
    assert section["last"]["written"] == 0
    assert section["last"]["reused"] == len(blobs[-1]) - handed[-1]
    mirrored = out["health"]["trace"]["mirrored_spans"]
    assert {f"journal.{stage}" for stage in STAGES if stage != "total"} <= set(mirrored)
    assert "journal.total" not in mirrored  # it would cover the five and say nothing


# --- (f) what became of each section, and how often it was hashed ------------


def test_one_rounds_sections_are_counted_by_route_and_each_is_hashed_once(
        devices, tmp_path, monkeypatch):
    counting = CountingHashlib()
    monkeypatch.setattr(ckpt_mod, "hashlib", counting)
    entries: list = []
    real = FileCoordinatorStorage._write_ckpt

    def write_ckpt(self, head, sections=()):
        entries.append({s.name: (s.nbytes, s.stored, s.digest) for s in sections})
        real(self, head, sections)

    monkeypatch.setattr(FileCoordinatorStorage, "_write_ckpt", write_ckpt)

    def counters():
        return {(name, route): journal.SECTION_BYTES.labels(section=name, route=route).value
                for name in ("vect", "unit", "votes", "planes")
                for route in ("written", "reused")}

    mask = "int-b0m6"
    weights = _weights(1.0)
    before = counters()
    out = _run(_round(_settings(mask, tmp_path), weights))
    moved = {key: value - before[key] for key, value in counters().items()}
    assert _bits_equal(out["model"], _want(mask, weights))

    # sum, the seal | batch, batch | Sum2's base, the vote, `unmask`
    assert len(entries) == 7
    agg = entries[2]["planes"][0]  # the accumulator as the journal holds it
    unit = entries[2]["unit"][0]
    vote = entries[5]["votes"][0]
    assert agg >= 4 * 2 * MODEL_LEN and unit == 4 * 2 and vote > 7 * MODEL_LEN
    # written: an aggregate twice in Update and once in Sum2, the vote once;
    # reused: the finished aggregate twice and the vote once
    assert moved == {
        ("planes", "written"): 3 * agg, ("planes", "reused"): 2 * agg,
        ("unit", "written"): 3 * unit, ("unit", "reused"): 2 * unit,
        ("votes", "written"): vote, ("votes", "reused"): vote,
        ("vect", "written"): 0, ("vect", "reused"): 0,  # a device round journals planes
    }
    assert [e["planes"][1] for e in entries[2:]] == [False, False, False, True, True]
    assert [e["votes"][1] for e in entries[5:]] == [False, True]
    # Sum2's three entries carry one aggregate; so does the last batch's
    # (the drain found nothing left to fold), whose file the base entry's
    # write finds there
    assert len({e["planes"][2] for e in entries[3:]}) == 1
    assert entries[2]["planes"][2] != entries[3]["planes"][2]
    # each distinct section met one hash object and was fed to it once: the
    # three snapshots' planes and units, the vote (what is empty feeds none)
    assert counting.sizes() == sorted([agg] * 3 + [unit] * 3 + [vote])
    last = out["health"]["journal"]["last"]
    assert (last["phase"], last["written"], last["reused"]) == ("unmask", 0, agg + unit + vote)
    fold = aggregator_mod.fold_kernel_report()
    assert fold["shards"] == devices  # four planes a snapshot on the mesh


# --- (d) a write that fails is counted, and the round goes on ----------------


def test_a_write_that_exhausts_its_retries_is_counted_and_the_round_publishes(
        one_device, tmp_path):
    mask = "int-b0m6"
    weights = _weights(1.0)
    settings = _settings(mask, tmp_path)
    # the third write is the first fold batch's: all four attempts of it fail
    attempts = settings.resilience.retry_max_attempts
    calls = "/".join(str(3 + i) for i in range(attempts))
    install_plan(FaultPlan.parse(
        f"seed=1;storage.coordinator.set_round_checkpoint:error,nth={calls}"))
    failed0 = journal.WRITES.labels(phase="update", outcome="failed").value
    saved0 = journal.WRITES.labels(phase="update", outcome="saved").value
    bytes0 = journal.BYTES.labels(phase="update").value
    skipped0 = ckpt_mod.SAVE_FAILURES.value
    health0 = journal.report(True, 1)
    out = _run(_round(settings, weights))
    assert _bits_equal(out["model"], _want(mask, weights))
    assert journal.WRITES.labels(phase="update", outcome="failed").value == failed0 + 1
    assert journal.WRITES.labels(phase="update", outcome="saved").value == saved0 + 2
    assert ckpt_mod.SAVE_FAILURES.value == skipped0 + 1
    assert journal.BYTES.labels(phase="update").value > bytes0  # the saved ones alone
    section = out["health"]["journal"]
    assert section["failed"].get("update", 0) == health0["failed"].get("update", 0) + 1
    assert section["writes"]["update"] == health0["writes"].get("update", 0) + 2


# --- (e) what an earlier process left never stops a start --------------------


def _rewrite_journal(path, **changes) -> None:
    entry = _journal_on_disk(os.path.dirname(path))
    for key, value in changes.items():
        setattr(entry, key, value)
    with open(path, "wb") as f:
        f.write(entry.to_bytes())  # whole, in the one file


LEFTOVERS = ("finished-run", "other-seed", "other-mask", "other-length", "torn-tmp",
             "short-section", "no-section")


@pytest.mark.parametrize("left", LEFTOVERS)
def test_boot_on_what_an_earlier_run_left_starts_at_idle_and_serves_a_round(
        left, one_device, kill_at, tmp_path):
    mask = "int-b0m6"
    settings = _settings(mask, tmp_path)
    journal_path = tmp_path / "coordinator_state.json.ckpt"
    first = _weights(1.0, seed=7)

    async def dies_in_update():
        coord = await Coordinator(settings).start()
        try:
            await coord.phase("sum")
            seed = coord.fetcher.round_params().seed.as_bytes()
            sum_task = asyncio.create_task(_drive(_summer(coord, seed), coord.task.done))
            await coord.phase("update")
            kill_at(f"update:{K + 1}")
            sends = [asyncio.create_task(_send(_updater(coord, seed, i, w)))
                     for i, w in enumerate(first[:K + 1])]
            await asyncio.wait({coord.task}, timeout=60)
            assert coord.task.cancelled()
            for task in (*sends, sum_task):
                task.cancel()
            await asyncio.gather(*sends, sum_task, return_exceptions=True)
        finally:
            await coord.stop()

    if left == "finished-run":
        done = _run(_round(settings, first))
        assert _bits_equal(done["model"], _want(mask, first))
        assert not journal_path.exists()
    else:
        _run(dies_in_update())
        os.environ.pop(chaos.ENV, None)
        assert journal_path.exists()  # the first batch's entry of a dead round
        if left == "other-seed":
            _rewrite_journal(journal_path, round_seed=b"\x5a" * 32)
        elif left == "other-mask":
            _rewrite_journal(journal_path, mask_config=[["PRIME", "F32", "B0", "M3"]] * 2)
        elif left == "other-length":
            _rewrite_journal(journal_path, model_length=MODEL_LEN + 1)
        elif left == "torn-tmp":
            blob = journal_path.read_bytes()
            journal_path.write_bytes(blob[: len(blob) // 2])  # a torn entry
            (tmp_path / "coordinator_state.json.ckpt.tmp").write_bytes(blob[:100])
        elif left == "short-section":
            largest = max(_beside_the_head(tmp_path), key=lambda f: os.path.getsize(tmp_path / f))
            raw = (tmp_path / largest).read_bytes()
            (tmp_path / largest).write_bytes(raw[: len(raw) // 2])
        elif left == "no-section":
            for name in _beside_the_head(tmp_path):
                os.remove(tmp_path / name)

    resumed0 = sum(ckpt_mod.RESUME_TOTAL.labels(phase=p, outcome="resumed").value
                   for p in PHASES)
    invalid0 = ckpt_mod.RESUME_TOTAL.labels(phase="update", outcome="invalid").value
    weights = _weights(1.0, seed=8)

    async def next_start():
        coord = await Coordinator(settings).start()
        try:
            assert coord.machine.phase.NAME.value == "idle"  # never resumed
        finally:
            await coord.stop()
        return await _round(settings, weights)

    out = _run(next_start())
    assert _bits_equal(out["model"], _want(mask, weights))
    assert sum(ckpt_mod.RESUME_TOTAL.labels(phase=p, outcome="resumed").value
               for p in PHASES) == resumed0
    refused = ckpt_mod.RESUME_TOTAL.labels(phase="update", outcome="invalid").value - invalid0
    # a journal of another round, mask or length is read and refused (at each
    # of the two starts here, until a round overwrites it); a torn one is
    # never parsed, and a finished run leaves none
    assert (refused >= 1) == (left in ("other-seed", "other-mask", "other-length"))
    assert not journal_path.exists()  # the served round retired its own
    assert _beside_the_head(tmp_path) == []  # and took what the dead one left
