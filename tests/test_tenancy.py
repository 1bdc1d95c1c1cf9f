"""Multi-tenancy: paged accumulator pool, tenant scheduler, and the
two-tenant byte-identity acceptance (docs/DESIGN.md §19).

The structural criterion of the multi-tenant coordinator: every tenant's
round is BYTE-IDENTICAL to its single-tenant control run while other
tenants — with different mask configs and model sizes — run concurrent
rounds on the same mesh, with the pool's page accounting exactly
balanced at round end (zero leaked leases).
"""

from __future__ import annotations

import asyncio
import threading
import time
from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.core.mask.config import GroupType
from xaynet_tpu.tenancy import (
    PagePool,
    PoolExhausted,
    TenantAdmissionBudget,
    TenantScheduler,
    validate_tenant_id,
)
from xaynet_tpu.tenancy.pool import get_pool

SUM_PROB = 0.4
UPDATE_PROB = 0.5
N_SUM = 1
N_UPDATE = 3


# --------------------------------------------------------------------------
# PagePool units
# --------------------------------------------------------------------------


def test_pool_lease_release_roundtrip_and_accounting():
    pool = PagePool(page_bytes=4096, slab_pages=8)
    lease = pool.lease_host("a", (16, 64), np.uint32)
    assert lease.array.shape == (16, 64)
    assert lease.array.dtype == np.uint32
    assert not lease.array.any()  # zeroed on lease
    assert lease.pages == pool.pages_for(16 * 64 * 4)
    assert not pool.balanced("a")
    table = pool.page_table("a")
    assert table[lease.lease_id]["pages"] == lease.pages
    assert table[lease.lease_id]["slab"] == lease.slab == 0
    pool.release(lease)
    pool.release(lease)  # idempotent
    assert pool.balanced("a")
    stats = pool.stats()
    assert stats["host_pages_in_use"] == 0
    assert stats["leases"] == 0


def test_pool_contiguous_reuse_and_coalescing():
    pool = PagePool(page_bytes=4096, slab_pages=8)
    a = pool.lease_host("a", (4096,), np.uint8)  # 1 page
    b = pool.lease_host("a", (4096,), np.uint8)  # 1 page
    c = pool.lease_host("a", (4096,), np.uint8)  # 1 page
    assert pool.stats()["slabs"] == 1  # all pack into one slab
    pool.release(a)
    pool.release(b)  # adjacent runs coalesce
    big = pool.lease_host("a", (2 * 4096,), np.uint8)  # needs the merged run
    assert pool.stats()["slabs"] == 1
    assert big.offset == 0  # reused the coalesced front run
    pool.release(big)
    pool.release(c)
    assert pool.balanced("a")


def test_pool_zeroes_cross_tenant_reuse():
    pool = PagePool(page_bytes=4096, slab_pages=4)
    a = pool.lease_host("a", (1024,), np.uint32)
    a.array[:] = 0xDEADBEEF  # tenant A's masked bytes
    pool.release(a)
    b = pool.lease_host("b", (1024,), np.uint32)  # same physical pages
    assert b.offset == 0 and b.slab == 0
    assert not b.array.any()  # never leaked across tenants
    pool.release(b)


def test_pool_leaves_a_slab_of_its_own_untouched_and_zero():
    """A lease that gets a slab made for it is handed the slab's zero pages
    as they are (no pass over them on the caller's thread); the next lease
    of the same pages is filled, as ever."""
    pool = PagePool(page_bytes=4096, slab_pages=4)
    fills = []

    class Spy(np.ndarray):
        def fill(self, value):
            fills.append(self.nbytes)
            super().fill(value)

    real = np.zeros
    try:
        np.zeros = lambda *a, **kw: real(*a, **kw).view(Spy)
        big = pool.lease_host("a", (8 * 4096,), np.uint8)  # a slab of its own
    finally:
        np.zeros = real
    assert pool.stats()["slabs"] == 1 and not fills
    assert not np.asarray(big.array).any()
    big.array[:] = 0xAB
    pool.release(big)
    again = pool.lease_host("b", (8 * 4096,), np.uint8)  # the same pages, dirty
    assert again.slab == 0 and fills == [8 * 4096]
    assert not np.asarray(again.array).any()
    pool.release(again)


def test_pool_capacity_cap_and_overflow():
    pool = PagePool(page_bytes=4096, slab_pages=4, host_pages=4)
    lease = pool.lease_host("a", (3 * 4096,), np.uint8)
    with pytest.raises(PoolExhausted):
        pool.lease_host("b", (2 * 4096,), np.uint8)
    pool.release(lease)
    ok = pool.lease_host("b", (2 * 4096,), np.uint8)  # fits after release
    pool.release(ok)


def test_pool_reclaim_force_releases_a_leaked_lease():
    pool = PagePool(page_bytes=4096, slab_pages=8, host_pages=8)
    leaked = pool.lease_host("a", (5 * 4096,), np.uint8)
    assert leaked.pages == 5
    with pytest.raises(PoolExhausted):
        pool.lease_host("b", (4 * 4096,), np.uint8)
    # a crashed round leaks the lease; reclaim force-releases and counts
    assert pool.reclaim("a") == 1
    assert pool.balanced("a") and leaked.released and leaked.array is None
    assert pool.reclaim("a") == 0  # healthy path reclaims nothing
    ok = pool.lease_host("b", (4 * 4096,), np.uint8)  # the pages are back
    pool.release(ok)
    assert pool.stats()["host_pages_in_use"] == 0


def test_pool_grows_by_slabs_and_big_leases_get_dedicated_slabs():
    pool = PagePool(page_bytes=4096, slab_pages=2)
    small = pool.lease_host("a", (4096,), np.uint8)
    big = pool.lease_host("a", (5 * 4096,), np.uint8)  # > slab_pages
    assert pool.stats()["slabs"] == 2
    assert big.pages == 5
    pool.release(small)
    pool.release(big)
    assert pool.balanced("a")


def test_tenant_id_validation():
    assert validate_tenant_id("alpha-1") == "alpha-1"
    for bad in ("", "UPPER", "has space", "x" * 33, "-lead", "a/b"):
        with pytest.raises(ValueError):
            validate_tenant_id(bad)


# --------------------------------------------------------------------------
# TenantScheduler
# --------------------------------------------------------------------------


def _wait_for(predicate, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.005)
    return False


def test_scheduler_backpressure_bound():
    sched = TenantScheduler(max_inflight=2)
    owner = sched.new_owner()
    sched.acquire("a", owner)
    sched.acquire("a", owner)
    blocked = threading.Event()
    acquired = threading.Event()

    def third():
        blocked.set()
        sched.acquire("a", owner)
        acquired.set()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    assert blocked.wait(2.0)
    time.sleep(0.05)
    assert not acquired.is_set()  # bounded: the third slot waits
    sched.release(owner)
    assert acquired.wait(2.0)
    sched.release_owner(owner)


def test_scheduler_fairness_least_served_wins():
    sched = TenantScheduler(max_inflight=1)
    owner_a = sched.new_owner()
    owner_b = sched.new_owner()
    sched.acquire("a", owner_a)  # a holds the only slot (served: a=1)
    order: list[str] = []

    def waiter(tenant, owner):
        sched.acquire(tenant, owner)
        order.append(tenant)

    # a's SECOND request arrives BEFORE b's first...
    ta = threading.Thread(target=waiter, args=("a", owner_a), daemon=True)
    ta.start()
    assert _wait_for(lambda: len(sched._waiting) == 1)
    tb = threading.Thread(target=waiter, args=("b", owner_b), daemon=True)
    tb.start()
    assert _wait_for(lambda: len(sched._waiting) == 2)
    # ...but the freed slot goes to b: fewest slots served wins over FIFO
    sched.release(owner_a)
    assert _wait_for(lambda: order == ["b"])
    sched.release(owner_b)
    assert _wait_for(lambda: order == ["b", "a"])
    sched.release(owner_a)
    split = sched.split()
    assert split["a"] == 2 and split["b"] == 1
    sched.release_owner(owner_a)
    sched.release_owner(owner_b)


def test_scheduler_release_owner_returns_held_slots():
    sched = TenantScheduler(max_inflight=2)
    owner = sched.new_owner()
    sched.acquire("a", owner)
    sched.acquire("a", owner)
    sched.release_owner(owner)  # abandoned pipeline: both slots return
    other = sched.new_owner()
    sched.acquire("b", other)  # would deadlock if slots leaked
    sched.acquire("b", other)
    sched.release_owner(other)
    sched.release_owner(owner)  # idempotent


# --------------------------------------------------------------------------
# TenantAdmissionBudget
# --------------------------------------------------------------------------


def test_admission_budget_caps_one_tenants_share():
    budget = TenantAdmissionBudget(capacity=4, max_share=0.5)
    assert budget.charge("a") and budget.charge("a")
    assert not budget.charge("a")  # over a's 50% share
    assert budget.charge("b")  # b unaffected
    budget.discharge("a", 1)
    assert budget.charge("a")  # drain restores headroom
    budget.discharge("a", 99)  # over-discharge clamps
    assert budget.held("a") == 0


# --------------------------------------------------------------------------
# Streaming pipeline page accounting
# --------------------------------------------------------------------------


def test_streaming_pipeline_leases_and_releases_pool_pages():
    from xaynet_tpu.core.mask.config import (
        BoundType, DataType, MaskConfig, ModelType,
    )
    from xaynet_tpu.parallel.aggregator import ShardedAggregator
    from xaynet_tpu.parallel.streaming import StreamingAggregator

    config = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3)
    pool = PagePool(page_bytes=4096, slab_pages=16)
    sched = TenantScheduler(max_inflight=4)
    agg = ShardedAggregator(config.pair().vect, 64, kernel="auto")
    stream = StreamingAggregator(
        agg, staging_buffers=2, dispatch_ahead=1, max_batch=4,
        tenant="tenant-x", pool=pool, scheduler=sched,
    )
    rng = np.random.default_rng(0)
    # group elements (< order, top limb < 4656): the kernel=auto race
    # compares its candidates, and they only agree on valid input
    stack = rng.integers(
        0, 2**12, size=(3, 64, agg.n_limbs), dtype=np.uint32
    )
    stream.submit_batch(stack)
    stream.drain()
    assert agg.nb_models == 3
    assert not pool.balanced("tenant-x")  # the rings hold leases
    stream.close()
    assert pool.balanced("tenant-x")  # leases == releases at round end
    assert sched.split().get("tenant-x", 0) >= 1


# --------------------------------------------------------------------------
# Two-tenant concurrent rounds: byte-identity vs single-tenant controls
# --------------------------------------------------------------------------


def _tenant_settings(model_length: int, group_type: GroupType):
    from xaynet_tpu.server.settings import (
        CountSettings,
        PhaseSettings,
        PetSettings as ServerPet,
        Settings,
        Sum2Settings,
        TimeSettings,
    )

    settings = Settings(
        pet=ServerPet(
            sum=PhaseSettings(
                prob=SUM_PROB,
                count=CountSettings(min=N_SUM, max=N_SUM),
                time=TimeSettings(min=0.0, max=60.0),
            ),
            update=PhaseSettings(
                prob=UPDATE_PROB,
                count=CountSettings(min=N_UPDATE, max=N_UPDATE),
                time=TimeSettings(min=0.0, max=60.0),
            ),
            sum2=Sum2Settings(
                count=CountSettings(min=N_SUM, max=N_SUM),
                time=TimeSettings(min=0.0, max=60.0),
            ),
        )
    )
    settings.model.length = model_length
    settings.mask.group_type = group_type
    settings.aggregation.device = True  # the pool/scheduler path
    settings.aggregation.batch_size = 2
    return settings


async def _drive_tenant_round(tenant: str, settings, seed: int) -> bytes:
    """One full in-process PET round for ``tenant`` (the oracle's driver
    shape, tenant-scoped); returns the float64 global model bytes."""
    from xaynet_tpu.sdk.client import InProcessClient
    from xaynet_tpu.sdk.simulation import keys_for_task
    from xaynet_tpu.sdk.state_machine import PetSettings, StateMachine as ParticipantSM
    from xaynet_tpu.sdk.traits import ModelStore
    from xaynet_tpu.server.services import Fetcher, PetMessageHandler
    from xaynet_tpu.server.state_machine import StateMachineInitializer
    from xaynet_tpu.storage.memory import (
        InMemoryCoordinatorStorage,
        InMemoryModelStorage,
        NoOpTrustAnchor,
    )
    from xaynet_tpu.storage.traits import Store

    class _ArrayModelStore(ModelStore):
        def __init__(self, model):
            self.model = model

        async def load_model(self):
            return self.model

    rng = np.random.default_rng(seed)
    mask_seeds = [rng.bytes(32) for _ in range(N_UPDATE)]
    weights = rng.uniform(
        -1, 1, (N_UPDATE, settings.model.length)
    ).astype(np.float32)

    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(
        settings, store, tenant=tenant
    ).init()
    handler = PetMessageHandler(events, request_tx)
    fetcher = Fetcher(events)
    machine_task = asyncio.create_task(machine.run())
    try:
        while fetcher.phase().value != "sum":
            await asyncio.sleep(0.01)
        round_seed = fetcher.round_params().seed.as_bytes()
        participants = []
        for i in range(N_SUM):
            keys = keys_for_task(round_seed, SUM_PROB, UPDATE_PROB, "sum", start=i * 1000)
            participants.append(
                ParticipantSM(
                    PetSettings(keys=keys),
                    InProcessClient(fetcher, handler),
                    _ArrayModelStore(None),
                )
            )
        for i in range(N_UPDATE):
            keys = keys_for_task(
                round_seed, SUM_PROB, UPDATE_PROB, "update", start=(10 + i) * 1000
            )
            participants.append(
                ParticipantSM(
                    PetSettings(
                        keys=keys,
                        scalar=Fraction(1, N_UPDATE),
                        mask_seed=mask_seeds[i],
                    ),
                    InProcessClient(fetcher, handler),
                    _ArrayModelStore(weights[i]),
                )
            )

        async def drive(sm):
            for _ in range(2000):
                # looked at before the transition too: once the round's model
                # is out, one more transition of an idle participant can enter
                # the NEXT round, whose open batch the teardown below would
                # cancel with its ring buffers still leased (seen under load)
                if fetcher.model() is not None and sm.phase.value == "awaiting":
                    return
                try:
                    await sm.transition()
                except asyncio.CancelledError:
                    raise
                except Exception:
                    pass
                await asyncio.sleep(0.01)

        await asyncio.gather(*(drive(p) for p in participants))
        while fetcher.model() is None:
            await asyncio.sleep(0.01)
        return np.asarray(fetcher.model(), dtype=np.float64).tobytes()
    finally:
        machine_task.cancel()
        try:
            await machine_task
        except (asyncio.CancelledError, Exception):  # lint: swallow-ok (teardown)
            pass


_TENANT_CASES = {
    # different mask configs AND model sizes on the one mesh
    "alpha": (37, GroupType.INTEGER, 11),
    "beta": (64, GroupType.PRIME, 22),
}


def _control(tenant: str) -> bytes:
    length, group, seed = _TENANT_CASES[tenant]
    return asyncio.run(
        asyncio.wait_for(
            _drive_tenant_round(tenant, _tenant_settings(length, group), seed),
            timeout=180.0,
        )
    )


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_two_tenants_concurrent_rounds_byte_identical_to_controls():
    controls = {t: _control(t) for t in _TENANT_CASES}

    async def concurrent():
        return await asyncio.gather(
            *(
                _drive_tenant_round(
                    t, _tenant_settings(c[0], c[1]), c[2]
                )
                for t, c in _TENANT_CASES.items()
            )
        )

    results = asyncio.run(asyncio.wait_for(concurrent(), timeout=300.0))
    for (tenant, _case), model in zip(_TENANT_CASES.items(), results):
        assert model == controls[tenant], (
            f"tenant {tenant} diverged from its single-tenant control"
        )
    # pool page accounting exactly balanced: zero leaked leases per tenant
    pool = get_pool()
    for tenant in _TENANT_CASES:
        assert pool.balanced(tenant), (
            f"tenant {tenant} leaked pool leases: {pool.page_table(tenant)}"
        )


# --------------------------------------------------------------------------
# Tenant-scoped durable storage: the round checkpoint never crosses tenants
# --------------------------------------------------------------------------


def test_round_checkpoint_storage_is_tenant_scoped(tmp_path):
    """Regression for the elastic-lifecycle PR: the PR-4 mid-round
    checkpoint must live under the tenant's scoped key space (file backends
    get a ``t-<tenant>`` subtree, redis a ``t:<tenant>:`` prefix), so a
    tenant's kill-and-restore can never resume into ANOTHER tenant's
    round — the resume entry point for tenant B sees no checkpoint at all
    when only tenant A saved one."""
    from xaynet_tpu.resilience import checkpoint as ckpt_mod
    from xaynet_tpu.server.runner import init_store

    async def run():
        settings = _tenant_settings(32, GroupType.INTEGER)
        settings.storage.coordinator = "file"
        settings.storage.model_dir = str(tmp_path)
        store_a = init_store(settings, "alpha")
        store_b = init_store(settings, "beta")
        blob = b"alpha mid-update aggregate"
        await store_a.coordinator.set_round_checkpoint(blob)
        # tenant A round-trips its own checkpoint; tenant B's restart sees
        # nothing to resume — checkpoint.load degrades it to a round restart
        assert await store_a.coordinator.round_checkpoint() == blob
        assert await store_b.coordinator.round_checkpoint() is None
        assert await ckpt_mod.load(store_b) is None
        # on disk the blob lives only under alpha's t- subtree
        holders = {
            p.relative_to(tmp_path).parts[0]
            for p in tmp_path.rglob("*")
            if p.is_file() and p.read_bytes() == blob
        }
        assert holders == {"t-alpha"}
        # deletion is scoped the same way
        await store_a.coordinator.delete_round_checkpoint()
        assert await store_a.coordinator.round_checkpoint() is None

    asyncio.run(run())
