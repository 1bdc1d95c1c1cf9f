"""Whole-round durable journal (docs/DESIGN.md §9).

Pins the crash-anywhere contracts layered on top of the PR-4 update-only
checkpoint:

1. **XNCKPT2 wire format** — round dictionaries, mask votes and packed
   per-shard planes roundtrip byte-exact; XNCKPT1 blobs still read (and
   stay update-only);
2. **reseed replay** — boot-time validation replays the journaled
   dictionaries into an empty store and prunes accepted-but-unjournaled
   orphans, so cross-process resume works on volatile backends;
3. **fail-soft journal writes** — a write that exhausts the storage retry
   policy is skipped and metered, never raised into the phase;
4. **resume budget & phase guards** — Failure burns ``resume_attempts``
   then restarts at Idle (``xaynet_resume_total{outcome=
   "budget_exhausted"}``); a journal entry for another phase restarts
   instead of resuming;
5. **lifecycle interplay** — a journal resume is NOT a round boundary:
   quarantine/probe accounting only moves on true round outcomes;
6. **multi-phase boot restore** — a coordinator killed mid-sum2 re-enters
   Sum2 with the aggregate and votes restored and finishes the round with
   the correct model.
"""

import asyncio
from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.resilience import FaultPlan, ResilientStore, RetryPolicy, clear_plan, install_plan
from xaynet_tpu.resilience import checkpoint as ckpt_mod
from xaynet_tpu.server.coordinator import CoordinatorState
from xaynet_tpu.server.events import EventPublisher, PhaseName
from xaynet_tpu.server.phases.base import Shared, reduce_count_window
from xaynet_tpu.server.phases.failure import Failure
from xaynet_tpu.server.phases.idle import Idle
from xaynet_tpu.server.phases.update import UpdatePhase
from xaynet_tpu.server.requests import RequestReceiver
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


@pytest.fixture(autouse=True)
def _no_leftover_fault_plan():
    clear_plan()
    yield
    clear_plan()


def _mem_store() -> Store:
    return Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())


def _settings(n_sum=2, n_update=3, model_len=13) -> Settings:
    s = Settings(
        pet=ServerPet(
            sum=PhaseSettings(
                prob=0.4,
                count=CountSettings(min=n_sum, max=n_sum),
                time=TimeSettings(min=0.0, max=30.0),
            ),
            update=PhaseSettings(
                prob=0.5,
                count=CountSettings(min=n_update, max=n_update),
                time=TimeSettings(min=0.0, max=30.0),
            ),
            sum2=Sum2Settings(
                count=CountSettings(min=n_sum, max=n_sum),
                time=TimeSettings(min=0.0, max=30.0),
            ),
        )
    )
    s.model.length = model_len
    s.resilience.retry_base_ms = 1.0
    s.resilience.retry_max_ms = 20.0
    return s


def _pk(i: int) -> bytes:
    return bytes([i]) * 32


def _seed(i: int) -> bytes:
    return bytes([i]) * 80  # ENCRYPTED_MASK_SEED_LENGTH


def _ckpt(**kw) -> ckpt_mod.RoundCheckpoint:
    rng = np.random.default_rng(3)
    base = dict(
        round_id=4,
        phase="update",
        round_seed=b"\x11" * 32,
        mask_config=[["PRIME", "F32", "B0", "M3"], ["PRIME", "F32", "B0", "M3"]],
        model_length=7,
        nb_models=2,
        seed_watermark=2,
        vect=rng.integers(0, 2**32, size=(7, 6), dtype=np.uint32),
        unit=rng.integers(0, 2**32, size=(6,), dtype=np.uint32),
    )
    base.update(kw)
    return ckpt_mod.RoundCheckpoint(**base)


# --------------------------------------------------------------------------
# Wire format
# --------------------------------------------------------------------------


def test_v2_roundtrip_dicts_votes_and_planes():
    rng = np.random.default_rng(9)
    planes = [
        (0, 4, rng.integers(0, 2**32, size=(6, 4), dtype=np.uint32)),
        (4, 8, rng.integers(0, 2**32, size=(6, 4), dtype=np.uint32)),
    ]
    ck = _ckpt(
        phase="sum2",
        sum_dict={_pk(1): b"e" * 32},
        seed_dicts={_pk(10): {_pk(1): _seed(10)}, _pk(11): {_pk(1): _seed(11)}},
        mask_votes=[(_pk(1), b"\x05" * 21)],
        vect=np.zeros((0, 0), dtype=np.uint32),
        planes=planes,
    )
    again = ckpt_mod.RoundCheckpoint.from_bytes(ck.to_bytes())
    assert again.version == 2 and again.phase == "sum2"
    assert again.sum_dict == {_pk(1): b"e" * 32}
    assert again.seed_dicts == {
        _pk(10): {_pk(1): _seed(10)},
        _pk(11): {_pk(1): _seed(11)},
    }
    assert again.mask_votes == [(_pk(1), b"\x05" * 21)]
    assert len(again.planes) == 2
    for (lo, hi, plane), (lo2, hi2, plane2) in zip(planes, again.planes):
        assert (lo, hi) == (lo2, hi2)
        assert np.array_equal(plane, plane2)
    # the planes ARE the aggregate: wire reassembly honors model_length
    wire = again.wire_vect()
    assert wire.shape == (7, 6)
    full = np.concatenate([planes[0][2], planes[1][2]], axis=1)
    assert np.array_equal(wire, full[:, :7].T)


def test_sum_entry_roundtrips_with_empty_aggregate():
    ck = _ckpt(
        phase="sum",
        nb_models=0,
        seed_watermark=0,
        vect=np.zeros((0, 0), dtype=np.uint32),
        unit=np.zeros((0,), dtype=np.uint32),
        sum_dict={_pk(1): b"e" * 32, _pk(2): b"f" * 32},
    )
    again = ckpt_mod.RoundCheckpoint.from_bytes(ck.to_bytes())
    assert again.phase == "sum" and again.nb_models == 0
    assert again.sum_dict == {_pk(1): b"e" * 32, _pk(2): b"f" * 32}
    assert again.vect.size == 0 and again.unit.size == 0


def test_v1_blob_reads_as_update_only():
    ck = _ckpt(version=1)
    blob = ck.to_bytes()
    assert blob.startswith(ckpt_mod.MAGIC)
    again = ckpt_mod.RoundCheckpoint.from_bytes(blob)
    assert again.version == 1
    assert again.sum_dict == {} and again.seed_dicts == {} and again.mask_votes == []
    assert np.array_equal(again.vect, ck.vect)


# --------------------------------------------------------------------------
# Reseed replay (boot restore on volatile backends)
# --------------------------------------------------------------------------


def _round_identity(settings):
    state = CoordinatorState.from_settings(settings)
    state.round_id = 4
    return (
        state,
        ckpt_mod.mask_config_names(state.round_params.mask_config),
        state.round_params.seed.as_bytes(),
    )


def test_validate_reseed_replays_journal_into_empty_store():
    settings = _settings(model_len=7)
    state, names, seed = _round_identity(settings)
    store = _mem_store()
    ck = _ckpt(
        round_seed=seed,
        mask_config=names,
        sum_dict={_pk(1): b"e" * 32},
        seed_dicts={_pk(10): {_pk(1): _seed(10)}, _pk(11): {_pk(1): _seed(11)}},
    )

    async def run():
        # the store is EMPTY (process died, memory backend): without the
        # replay the watermark check would reject; with it the journal
        # repopulates the dictionaries through the protocol primitives
        assert await ckpt_mod.validate(ck, state, store) is not None
        assert await ckpt_mod.validate(ck, state, store, reseed=True) is None
        seed_dict = await store.coordinator.seed_dict()
        assert ckpt_mod.seed_dict_watermark(seed_dict) == 2
        assert (await store.coordinator.sum_dict()) == {_pk(1): b"e" * 32}
        # idempotent: a second reseed validation still passes
        assert await ckpt_mod.validate(ck, state, store, reseed=True) is None

    asyncio.run(run())


def test_validate_reseed_prunes_orphan_update_participants():
    settings = _settings(model_len=7)
    state, names, seed = _round_identity(settings)
    store = _mem_store()
    ck = _ckpt(
        round_seed=seed,
        mask_config=names,
        sum_dict={_pk(1): b"e" * 32},
        seed_dicts={_pk(10): {_pk(1): _seed(10)}, _pk(11): {_pk(1): _seed(11)}},
    )

    async def run():
        from xaynet_tpu.core.mask.seed import EncryptedMaskSeed

        # the store holds one MORE update than the journal: accepted after
        # the last journal write, its masked model died with the process —
        # the prune drops it so its un-acked client can resend
        await store.coordinator.add_sum_participant(_pk(1), b"e" * 32)
        for upk in (_pk(10), _pk(11), _pk(12)):
            await store.coordinator.add_local_seed_dict(
                upk, {_pk(1): EncryptedMaskSeed(_seed(9))}
            )
        assert await ckpt_mod.validate(ck, state, store, reseed=True) is None
        seed_dict = await store.coordinator.seed_dict()
        pks = {pk for inner in seed_dict.values() for pk in inner}
        assert pks == {_pk(10), _pk(11)}  # the orphan is gone

    asyncio.run(run())


def test_reduce_count_window_clamps_at_zero():
    params = PhaseSettings(
        prob=0.5,
        count=CountSettings(min=2, max=4),
        time=TimeSettings(min=0.0, max=30.0),
    )
    reduced = reduce_count_window(params, 3)
    assert reduced.count.min == 0 and reduced.count.max == 1
    assert reduce_count_window(params, 0) is params


# --------------------------------------------------------------------------
# Per-shard planes: device snapshot/restore roundtrip
# --------------------------------------------------------------------------


def test_sharded_aggregator_snapshot_restore_shards_roundtrip():
    from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.ops import limbs as host_limbs
    from xaynet_tpu.parallel.aggregator import ShardedAggregator

    cfg = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
    n = 103
    L = host_limbs.n_limbs_for_order(cfg.order)
    rng = np.random.default_rng(5)
    batch = rng.integers(0, 2**32, size=(4, n, L), dtype=np.uint32)
    batch[:, :, -1] = 0  # keep every element below the group order

    agg = ShardedAggregator(cfg, n)
    agg.add_batch(batch)
    planes = agg.snapshot_shards()
    assert planes is not None and planes

    fresh = ShardedAggregator(cfg, n)
    fresh.restore_shards(planes, agg.nb_models)
    assert fresh.nb_models == 4
    assert np.array_equal(fresh.snapshot(), agg.snapshot())


# --------------------------------------------------------------------------
# Fail-soft journal writes (satellite: save through the retry policy)
# --------------------------------------------------------------------------


def test_journal_write_exhausting_retries_skips_not_raises():
    class _SharedStub:
        pass

    install_plan(FaultPlan.parse("seed=1;storage.coordinator.set_round_checkpoint:error"))
    store = ResilientStore(
        _mem_store(),
        policy=RetryPolicy(max_attempts=2, base_delay_s=0.001, max_delay_s=0.002),
    )
    shared = _SharedStub()
    shared.store = store
    shared.round_id = 7

    before_skip = ckpt_mod.SAVE_FAILURES.value
    before_fail = ckpt_mod.CHECKPOINTS.labels(outcome="failed").value
    ok = asyncio.run(ckpt_mod.write_entry(shared, _ckpt()))
    assert ok is False  # skipped — the phase it protects never sees a raise
    assert ckpt_mod.SAVE_FAILURES.value == before_skip + 1
    assert ckpt_mod.CHECKPOINTS.labels(outcome="failed").value == before_fail + 1

    clear_plan()
    assert asyncio.run(ckpt_mod.write_entry(shared, _ckpt())) is True
    assert asyncio.run(store.coordinator.round_checkpoint()) is not None


# --------------------------------------------------------------------------
# Failure-phase resume guards
# --------------------------------------------------------------------------


def _failure_shared(settings, store, resume_attempts=0, tenant="default") -> Shared:
    state = CoordinatorState.from_settings(settings)
    state.round_id = 4
    shared = Shared(
        state=state,
        request_rx=RequestReceiver(),
        events=EventPublisher(4, None, None, PhaseName.UPDATE),
        store=store,
        settings=settings,
        tenant=tenant,
    )
    shared.resume_attempts = resume_attempts
    return shared


def test_failure_burns_resume_budget_then_restarts_at_idle():
    settings = _settings(model_len=7)
    settings.resilience.checkpoint_enabled = True
    settings.resilience.max_resume_attempts = 2
    store = _mem_store()
    shared = _failure_shared(settings, store, resume_attempts=2)

    before = ckpt_mod.RESUME_TOTAL.labels(phase="update", outcome="budget_exhausted").value
    failure = Failure(shared, RuntimeError("boom"), failed_phase=PhaseName.UPDATE)
    nxt = asyncio.run(asyncio.wait_for(failure.run_phase(), timeout=30))
    assert isinstance(nxt, Idle)
    after = ckpt_mod.RESUME_TOTAL.labels(phase="update", outcome="budget_exhausted").value
    assert after == before + 1


def test_failure_journal_phase_mismatch_restarts_round():
    settings = _settings(model_len=7)
    settings.resilience.checkpoint_enabled = True
    store = _mem_store()
    shared = _failure_shared(settings, store)
    names = ckpt_mod.mask_config_names(shared.state.round_params.mask_config)
    seed = shared.state.round_params.seed.as_bytes()
    ck = _ckpt(round_seed=seed, mask_config=names, nb_models=0, seed_watermark=0)
    asyncio.run(store.coordinator.set_round_checkpoint(ck.to_bytes()))

    before = ckpt_mod.RESUME_TOTAL.labels(phase="update", outcome="invalid").value
    # sum2 failed but the journal still says "update": sum2 participants
    # would never resend into a re-entered update window — restart instead
    failure = Failure(shared, RuntimeError("boom"), failed_phase=PhaseName.SUM2)
    resumed = asyncio.run(failure._try_resume())
    assert resumed is None
    assert (
        ckpt_mod.RESUME_TOTAL.labels(phase="update", outcome="invalid").value
        == before + 1
    )


def test_failure_resume_reenters_update_with_budget_spent():
    settings = _settings(model_len=7)
    settings.resilience.checkpoint_enabled = True
    settings.resilience.max_resume_attempts = 2
    store = _mem_store()
    shared = _failure_shared(settings, store)
    names = ckpt_mod.mask_config_names(shared.state.round_params.mask_config)
    seed = shared.state.round_params.seed.as_bytes()
    ck = _ckpt(round_seed=seed, mask_config=names, nb_models=0, seed_watermark=0)
    asyncio.run(store.coordinator.set_round_checkpoint(ck.to_bytes()))

    failure = Failure(shared, RuntimeError("boom"), failed_phase=PhaseName.UPDATE)
    resumed = asyncio.run(failure._try_resume())
    assert isinstance(resumed, UpdatePhase)
    assert shared.resume_attempts == 1


# --------------------------------------------------------------------------
# Lifecycle interplay: a resume is not a round boundary
# --------------------------------------------------------------------------


def test_journal_resume_does_not_move_quarantine_accounting():
    from xaynet_tpu.server.settings import TenancySettings
    from xaynet_tpu.tenancy import lifecycle as lc_mod
    from xaynet_tpu.tenancy.lifecycle import QUARANTINED, TenantLifecycle
    from xaynet_tpu.tenancy.registry import TenantRegistry

    lc = TenantLifecycle(
        TenancySettings(
            enabled=True,
            admin_token="test-admin-token",
            quarantine_failures=1,
            quarantine_reset_s=60.0,
        ),
        TenantRegistry(),
        {},
    )
    lc.mark_serving("acme")
    lc.note_round_failed("acme")  # threshold 1: straight to quarantine
    assert lc.state("acme") == QUARANTINED
    boundaries_at_quarantine = lc._boundaries.get("acme", 0)

    settings = _settings(model_len=7)
    settings.resilience.checkpoint_enabled = True
    settings.resilience.max_resume_attempts = 2
    store = _mem_store()
    shared = _failure_shared(settings, store, tenant="acme")
    names = ckpt_mod.mask_config_names(shared.state.round_params.mask_config)
    seed = shared.state.round_params.seed.as_bytes()
    ck = _ckpt(round_seed=seed, mask_config=names, nb_models=0, seed_watermark=0)
    asyncio.run(store.coordinator.set_round_checkpoint(ck.to_bytes()))

    lc_mod.install_manager(lc)
    try:
        # resume path: the round is still ALIVE — neither a breaker strike
        # nor a round boundary; quarantine probe accounting must not move
        failure = Failure(shared, RuntimeError("boom"), failed_phase=PhaseName.UPDATE)
        nxt = asyncio.run(asyncio.wait_for(failure.run_phase(), timeout=30))
        assert isinstance(nxt, UpdatePhase)
        assert lc.state("acme") == QUARANTINED
        assert lc._boundaries.get("acme", 0) == boundaries_at_quarantine

        # restart path (budget exhausted): a true round failure — the
        # boundary counts, and the open breaker keeps the quarantine held
        shared.resume_attempts = settings.resilience.max_resume_attempts
        failure = Failure(shared, RuntimeError("boom"), failed_phase=PhaseName.UPDATE)
        nxt = asyncio.run(asyncio.wait_for(failure.run_phase(), timeout=30))
        assert isinstance(nxt, Idle)
        assert lc._boundaries.get("acme", 0) == boundaries_at_quarantine + 1
        assert lc.state("acme") == QUARANTINED
    finally:
        lc_mod.install_manager(None)


# --------------------------------------------------------------------------
# Boot restore into Sum2 (in-process; the subprocess SIGKILL matrix lives
# in tools/soak.py --kill-matrix)
# --------------------------------------------------------------------------


def test_boot_restore_resumes_sum2_phase_and_finishes_round():
    from xaynet_tpu.sdk.client import InProcessClient
    from xaynet_tpu.sdk.simulation import keys_for_task
    from xaynet_tpu.sdk.state_machine import PetSettings, StateMachine as ParticipantSM
    from xaynet_tpu.sdk.traits import ModelStore
    from xaynet_tpu.server.phases.sum2 import Sum2Phase
    from xaynet_tpu.server.services import Fetcher, PetMessageHandler

    class ArrayModelStore(ModelStore):
        def __init__(self, model):
            self.model = model

        async def load_model(self):
            return self.model

    n_sum, n_update = 2, 3
    settings = _settings(n_sum=n_sum, n_update=n_update)
    settings.restore.enable = True
    settings.resilience.checkpoint_enabled = True
    settings.resilience.checkpoint_every_batches = 1
    settings.aggregation.batch_size = 1
    model_len = settings.model.length
    store = _mem_store()
    rng = np.random.default_rng(21)
    locals_ = [rng.uniform(-1, 1, model_len).astype(np.float32) for _ in range(n_update)]
    expected = sum(w.astype(np.float64) / n_update for w in locals_)

    async def drive_until(sm, fetcher, stop, steps=400):
        for _ in range(steps):
            try:
                await sm.transition()
            except Exception:
                pass
            if await stop():
                return True
            await asyncio.sleep(0.01)
        return False

    async def phase_one():
        """Sum + update + ONE of two sum2 votes, then kill the machine."""
        machine, request_tx, events = await StateMachineInitializer(settings, store).init()
        handler = PetMessageHandler(events, request_tx)
        fetcher = Fetcher(events)
        machine_task = asyncio.create_task(machine.run())
        try:
            while fetcher.phase().value != "sum":
                await asyncio.sleep(0.01)
            params = fetcher.round_params()
            seed = params.seed.as_bytes()
            summers = []
            for i in range(n_sum):
                sm = ParticipantSM(
                    PetSettings(
                        keys=keys_for_task(seed, params.sum, params.update, "sum", start=i * 1000)
                    ),
                    InProcessClient(fetcher, handler),
                    ArrayModelStore(None),
                )
                summers.append(sm)
                assert await drive_until(
                    sm, fetcher, lambda sm=sm: _ret(sm.phase.value == "sum2")
                )
            summer_blobs = [sm.save() for sm in summers]
            while fetcher.phase().value != "update":
                await asyncio.sleep(0.01)
            for i in range(n_update):
                sm = ParticipantSM(
                    PetSettings(
                        keys=keys_for_task(
                            seed, params.sum, params.update, "update", start=(10 + i) * 1000
                        ),
                        scalar=Fraction(1, n_update),
                    ),
                    InProcessClient(fetcher, handler),
                    ArrayModelStore(locals_[i]),
                )
                assert await drive_until(
                    sm, fetcher, lambda sm=sm: _ret(sm.phase.value == "awaiting")
                )
            while fetcher.phase().value != "sum2":
                await asyncio.sleep(0.01)
            # exactly ONE summer votes (window needs 2 → the phase stalls),
            # then wait for its vote to be journal-durable
            restored = ParticipantSM.restore(
                summer_blobs[0], InProcessClient(fetcher, handler), ArrayModelStore(None)
            )

            async def vote_journaled():
                blob = await store.coordinator.round_checkpoint()
                if blob is None:
                    return False
                ck = ckpt_mod.RoundCheckpoint.from_bytes(blob)
                return ck.phase == "sum2" and len(ck.mask_votes) >= 1

            assert await drive_until(restored, fetcher, vote_journaled)
            return seed, summer_blobs[1]
        finally:
            machine_task.cancel()
            try:
                await machine_task
            except (asyncio.CancelledError, Exception):
                pass

    async def _ret(v):
        return v

    async def phase_two(seed, summer_blob):
        before = ckpt_mod.RESUME_TOTAL.labels(phase="sum2", outcome="resumed").value
        machine, request_tx, events = await StateMachineInitializer(settings, store).init()
        # the machine restarts INSIDE sum2, one vote already restored
        phase = machine.phase
        assert isinstance(phase, Sum2Phase)
        assert len(phase._votes) == 1
        assert (
            ckpt_mod.RESUME_TOTAL.labels(phase="sum2", outcome="resumed").value
            == before + 1
        )
        handler = PetMessageHandler(events, request_tx)
        fetcher = Fetcher(events)
        assert fetcher.round_params().seed.as_bytes() == seed  # same round
        machine_task = asyncio.create_task(machine.run())
        try:
            second = ParticipantSM.restore(
                summer_blob, InProcessClient(fetcher, handler), ArrayModelStore(None)
            )

            async def model_published():
                return fetcher.model() is not None

            assert await drive_until(second, fetcher, model_published, steps=800)
            # the journal retires once the model is published
            for _ in range(200):
                if await store.coordinator.round_checkpoint() is None:
                    break
                await asyncio.sleep(0.01)
            assert await store.coordinator.round_checkpoint() is None
            return np.asarray(fetcher.model())
        finally:
            machine_task.cancel()
            try:
                await machine_task
            except (asyncio.CancelledError, Exception):
                pass

    async def run():
        seed, summer_blob = await phase_one()
        return await phase_two(seed, summer_blob)

    model = asyncio.run(asyncio.wait_for(run(), timeout=120))
    # all three updates survived the kill inside the restored aggregate
    np.testing.assert_allclose(model, expected, atol=1e-9)


# --------------------------------------------------------------------------
# An entry is a head and its sections (PR 47): what a store returns for it,
# how often a section is hashed and written, what a torn write leaves
# --------------------------------------------------------------------------


def _planes(n: int, width: int = 12, seed: int = 5) -> list:
    rng = np.random.default_rng(seed)
    step = width // n
    return [
        (i * step, (i + 1) * step, rng.integers(0, 2**32, size=(2, step), dtype=np.uint32))
        for i in range(n)
    ]


def _votes(n: int = 2) -> list:
    return [(_pk(40 + i), bytes([i + 1]) * (9 + i)) for i in range(n)]


_EMPTY = dict(
    vect=np.zeros((0, 0), dtype=np.uint32), unit=np.zeros((0,), dtype=np.uint32),
    nb_models=0, seed_watermark=0,
)
# kind -> what the entry carries beside the identity of its round
ENTRIES = {
    "wire-vect": lambda: dict(seed_dicts={_pk(20): {_pk(1): _seed(7)}, _pk(21): {_pk(1): _seed(8)}}),
    "one-plane": lambda: dict(vect=_EMPTY["vect"], planes=_planes(1), model_length=11),
    "four-planes": lambda: dict(vect=_EMPTY["vect"], planes=_planes(4), model_length=11,
                                sum_dict={_pk(1): _pk(2)}),
    "votes": lambda: dict(phase="sum2", vect=_EMPTY["vect"], planes=_planes(2),
                          model_length=11, mask_votes=_votes()),
    "all-empty": lambda: dict(phase="sum", sum_dict={_pk(1): _pk(2)}, **_EMPTY),
}


class _DefaultStore:
    """Nothing but the trait's own three methods."""

    from xaynet_tpu.storage.traits import CoordinatorStorage as _T

    set_round_checkpoint = _T.set_round_checkpoint
    round_checkpoint = _T.round_checkpoint
    delete_round_checkpoint = _T.delete_round_checkpoint


def _journal_store(kind: str, tmp_path):
    if kind == "file":
        from xaynet_tpu.storage.memory import FileCoordinatorStorage

        return FileCoordinatorStorage(str(tmp_path / "state.json"))
    if kind == "memory":
        return InMemoryCoordinatorStorage()
    if kind == "redis":
        from xaynet_tpu.storage.redis import RedisCoordinatorStorage

        class _Resp:
            def __init__(self):
                self.kv = {}

            async def command(self, verb, key, *rest):
                if verb == b"SET":
                    self.kv[key] = bytes(rest[0])
                elif verb == b"DEL":
                    self.kv.pop(key, None)
                return self.kv.get(key)

        store = RedisCoordinatorStorage(key_prefix="t:x:")
        store.client = _Resp()
        return store
    return _DefaultStore()


async def _put(store, ck) -> tuple:
    sections = ck.sections()
    head = ck.head(sections)
    await store.set_round_checkpoint(head, sections)
    return head, sections


def _beside(tmp_path) -> list:
    """The section files beside the head, and whatever else lies there."""
    import os

    return sorted(f for f in os.listdir(tmp_path) if f.startswith("state.json.ckpt."))


@pytest.mark.parametrize("store_kind", ["file", "memory", "default", "redis"])
@pytest.mark.parametrize("kind", list(ENTRIES))
def test_a_store_returns_for_an_entry_what_the_reference_serialiser_makes(
        kind, store_kind, tmp_path):
    from journal_reference import reference_to_bytes

    ck = _ckpt(**ENTRIES[kind]())
    want = reference_to_bytes(ck)  # before anything of the program has touched the entry
    store = _journal_store(store_kind, tmp_path)

    async def run():
        head, sections = await _put(store, ck)
        first = await store.round_checkpoint()
        # written again with one header field changed, as Sum2 -> Unmask does
        ck.phase = "unmask"
        await _put(store, ck)
        second = await store.round_checkpoint()
        await store.delete_round_checkpoint()
        return head, sections, first, second, await store.round_checkpoint()

    head, sections, first, second, gone = asyncio.run(run())
    assert first == want == ck_bytes_of(first)
    assert second == reference_to_bytes(ck) != want
    assert gone is None
    assert [s.name for s in sections] == ["vect", "unit", "votes", "planes"]
    assert want == head + b"".join(bytes(v) for s in sections for v in s.views())
    parsed = ckpt_mod.RoundCheckpoint.from_bytes(first)
    assert (parsed.phase, parsed.nb_models, parsed.mask_votes) == (
        ENTRIES[kind]().get("phase", "update"), ck.nb_models, ck.mask_votes)
    np.testing.assert_array_equal(parsed.wire_vect(), ck.wire_vect())
    if store_kind == "file":
        assert _beside(tmp_path) == []  # the retire leaves nothing beside the state


def ck_bytes_of(blob: bytes) -> bytes:
    """A blob parsed and serialised again by the program: the same bytes."""
    return ckpt_mod.RoundCheckpoint.from_bytes(blob).to_bytes()


@pytest.mark.parametrize("planes", [1, 4], ids=["one-plane", "four-planes"])
def test_a_section_is_hashed_once_written_once_and_read_only_after(planes, tmp_path, monkeypatch):
    """Sum2's three entries over one aggregate, as the phase writes them."""
    from journal_reference import CountingHashlib, reference_to_bytes

    counting = CountingHashlib()
    monkeypatch.setattr(ckpt_mod, "hashlib", counting)
    store = _journal_store("file", tmp_path)
    agg = _planes(planes)
    agg_bytes = sum(p.nbytes for _, _, p in agg)
    base = _ckpt(phase="sum2", vect=_EMPTY["vect"], planes=agg, model_length=11)
    vote = _votes(1)
    wants = []

    async def run():
        written = []
        for phase, votes in (("sum2", []), ("sum2", vote), ("unmask", vote)):
            base.phase, base.mask_votes = phase, list(votes)
            wants.append(reference_to_bytes(base))
            before = {f: (tmp_path / f).stat().st_mtime_ns for f in _beside(tmp_path)}
            _, sections = await _put(store, base)
            after = {f: (tmp_path / f).stat().st_mtime_ns for f in _beside(tmp_path)}
            written.append(sorted(f for f in after if after[f] != before.get(f)))
            assert await store.round_checkpoint() == wants[-1]
        return written, sections

    written, sections = asyncio.run(run())
    by_name = {s.name: s for s in sections}
    # each distinct section met one hash object: the aggregate, the unit, the vote
    assert counting.sizes() == sorted([agg_bytes, base.unit.nbytes, len(vote[0][1])])
    # and was written once: the base entry its two, the vote's entry the vote, `unmask` none
    assert [len(w) for w in written] == [2, 1, 0]
    assert written[1] == [f"state.json.ckpt.{by_name['votes'].digest}"]
    assert _beside(tmp_path) == sorted(
        f"state.json.ckpt.{by_name[n].digest}" for n in ("unit", "votes", "planes"))
    # the arrays an entry has hashed are read-only: a write raises
    for _, _, plane in agg:
        assert not plane.flags.writeable
        with pytest.raises(ValueError, match="read-only"):
            plane[0, 0] = 1
    with pytest.raises(ValueError, match="read-only"):
        base.unit[0] = 1
    # other buffers are another section: hashed afresh, never the kept digest
    base.planes = [(lo, hi, plane.copy()) for lo, hi, plane in agg]
    base.planes[0][2][0, 0] ^= 1
    assert base.sections()[3] is not by_name["planes"]
    assert base.to_bytes() == reference_to_bytes(base) != wants[-1]


def test_a_wire_vect_is_read_only_after_its_first_entry():
    ck = _ckpt()
    ck.vect[0, 0] = 5  # before: an aggregate like any other
    blob = ck.to_bytes()
    with pytest.raises(ValueError, match="read-only"):
        ck.vect[0, 0] = 6
    assert ck.to_bytes() == blob


def _torn_write(store, ck) -> None:
    """The state a process leaves that dies with the sections of ``ck``
    written and the head not yet renamed: ``_write_ckpt`` run up to its
    kill point."""
    from xaynet_tpu.resilience import chaos

    class Died(Exception):
        pass

    def die():
        raise Died()

    import os

    real, chaos._die = chaos._die, die
    os.environ[chaos.ENV] = "journal:sections:1"
    chaos._visits.clear()
    try:
        sections = ck.sections()
        with pytest.raises(Died):
            store._write_ckpt(ck.head(sections), sections)
    finally:
        chaos._die = real
        del os.environ[chaos.ENV]
        chaos._visits.clear()


def test_killed_between_the_sections_and_the_head_the_previous_entry_is_what_loads(tmp_path):
    from journal_reference import reference_to_bytes

    store = Store(_journal_store("file", tmp_path), InMemoryModelStorage(), NoOpTrustAnchor())
    first = _ckpt(vect=_EMPTY["vect"], planes=_planes(2, seed=1), model_length=11)
    second = _ckpt(vect=_EMPTY["vect"], planes=_planes(2, seed=2), model_length=11,
                   nb_models=4, seed_watermark=4)
    third = _ckpt(phase="sum2", vect=_EMPTY["vect"], planes=_planes(2, seed=3),
                  model_length=11, nb_models=6, seed_watermark=6)
    asyncio.run(_put(store.coordinator, first))
    held = _beside(tmp_path)
    _torn_write(store.coordinator, second)
    # the dead write's sections lie beside the live entry's, which is whole
    assert set(held) < set(_beside(tmp_path))
    loaded = asyncio.run(ckpt_mod.load(store))
    assert loaded is not None and loaded.nb_models == first.nb_models
    assert asyncio.run(store.coordinator.round_checkpoint()) == reference_to_bytes(first)
    # the next entry takes what no head names away with it
    _, sections = asyncio.run(_put(store.coordinator, third))
    assert _beside(tmp_path) == sorted(
        f"state.json.ckpt.{s.digest}" for s in sections if s.nbytes)
    assert asyncio.run(ckpt_mod.load(store)).phase == "sum2"
    # and so does the retire, a dead write's leavings included
    _torn_write(store.coordinator, second)
    asyncio.run(store.coordinator.delete_round_checkpoint())
    assert _beside(tmp_path) == [] and asyncio.run(ckpt_mod.load(store)) is None


@pytest.mark.parametrize("harm", ["removed", "truncated", "flipped-byte", "torn-head"])
def test_a_harmed_section_file_never_loads(harm, tmp_path):
    store = Store(_journal_store("file", tmp_path), InMemoryModelStorage(), NoOpTrustAnchor())
    ck = _ckpt(phase="sum2", vect=_EMPTY["vect"], planes=_planes(2), model_length=11,
               mask_votes=_votes())
    _, sections = asyncio.run(_put(store.coordinator, ck))
    assert asyncio.run(ckpt_mod.load(store)) is not None
    path = tmp_path / f"state.json.ckpt.{sections[3].digest}"
    if harm == "removed":
        path.unlink()
    elif harm == "truncated":
        path.write_bytes(path.read_bytes()[:-4])
    elif harm == "flipped-byte":
        raw = bytearray(path.read_bytes())
        raw[len(raw) // 2] ^= 0x10
        path.write_bytes(bytes(raw))
    else:
        head = tmp_path / "state.json.ckpt"
        head.write_bytes(head.read_bytes()[:40])
    assert asyncio.run(ckpt_mod.load(store)) is None
    # a whole entry written over it loads again
    asyncio.run(_put(store.coordinator, _ckpt()))
    assert asyncio.run(ckpt_mod.load(store)).phase == "update"


@pytest.mark.parametrize("kind", ["wire-vect", "four-planes", "votes"])
def test_a_single_file_journal_of_the_program_before_is_read_by_the_file_store(kind, tmp_path):
    from journal_reference import CountingHashlib, reference_to_bytes

    ck = _ckpt(**ENTRIES[kind]())
    blob = reference_to_bytes(ck)
    (tmp_path / "state.json.ckpt").write_bytes(blob)
    store = Store(_journal_store("file", tmp_path), InMemoryModelStorage(), NoOpTrustAnchor())
    assert asyncio.run(store.coordinator.round_checkpoint()) == blob
    loaded = asyncio.run(ckpt_mod.load(store))
    assert (loaded.phase, loaded.nb_models, loaded.mask_votes) == (
        ck.phase, ck.nb_models, ck.mask_votes)
    np.testing.assert_array_equal(loaded.wire_vect(), ck.wire_vect())
    # what was read keeps its digests: the next entry over it hashes nothing
    counting = CountingHashlib()
    ckpt_mod.hashlib, real = counting, ckpt_mod.hashlib
    try:
        loaded.phase = "unmask"
        asyncio.run(_put(store.coordinator, loaded))
    finally:
        ckpt_mod.hashlib = real
    assert counting.sizes() == []
    ck.phase = "unmask"
    assert asyncio.run(store.coordinator.round_checkpoint()) == reference_to_bytes(ck)
