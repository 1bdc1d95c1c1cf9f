"""The round's one shape (docs/DESIGN.md §22, ISSUE 18, ISSUE 45).

The Update phase's fold tail rides into Sum2 and each shard's subtract
rides behind its own last fold, so the round wall comes in below the
serial sum of phase walls; everything rests on **byte-identity with the
drain-time pass**, and no setting chooses an arm. These tests pin:

- eager per-shard unmask (`parallel.streaming._UnmaskJob`): identical to
  the drain-then-subtract serial pass on the native and XLA shard
  routes, correct fallback on a single-device mesh, and two tenants
  pipelined through the shared scheduler concurrently;
- what the phases observe: without a journal the drain ends inside
  Sum2's window, with one it has ended before the window opens; a fold
  error the drain raises fails the round at Sum2's exit;
- a settings file (or environment) that still names the retired
  `[overlap]` section and `[tenancy] device_pages` loads as one without;
- persisted calibration verdicts (`utils.calibcache`): cold→warm
  round-trip, fingerprint invalidation, corrupt-file fail-soft, and a
  warm verdict short-circuiting the mask probe race;
- the `xaynet_round_wall_seconds` log bucket ladder over a live render;
- `tools/trace_report.py --overlap`: concurrency lanes + the timeline
  identity assertion on synthetic traces.
"""

import json
import os
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

import jax

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from xaynet_tpu.core.mask import (
    Aggregation,
    BoundType,
    DataType,
    GroupType,
    Masker,
    MaskConfig,
    ModelType,
    Scalar,
)
from xaynet_tpu.ops import limbs as host_limbs
from xaynet_tpu.ops import masking_jax
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.parallel.streaming import StreamingAggregator
from xaynet_tpu.server.settings import Settings
from xaynet_tpu.tenancy.scheduler import TenantScheduler
from xaynet_tpu.utils import calibcache

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)

LEN = 257  # odd on purpose: uneven shard slices + a padded tail


def _seeds(n, tag=0):
    return [bytes([i & 0xFF, i >> 8, tag]) + b"\x5a" * 29 for i in range(n)]


# --- eager per-shard unmask ------------------------------------------------


def _updates(n, total, seed=0):
    rng = np.random.default_rng(seed)
    host = Aggregation(CFG.pair(), n)
    stacks = []
    for _ in range(total):
        w = rng.uniform(-1, 1, size=n).astype(np.float32)
        _, masked = Masker(CFG.pair()).mask(Scalar(1, total), w)
        host.aggregate(masked)
        stacks.append(masked.vect.data)
    return stacks, host


def _random_mask_vect(n, seed=7):
    rng = np.random.default_rng(seed)
    n_limb = host_limbs.n_limbs_for_order(CFG.order)
    top = int(CFG.order >> (32 * (n_limb - 1)))
    vect = rng.integers(0, 1 << 32, size=(n, n_limb), dtype=np.uint32)
    vect[:, n_limb - 1] = rng.integers(0, top, size=n, dtype=np.uint32)
    return vect


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_eager_unmask_byte_identical_sharded(kernel):
    stacks, host = _updates(LEN, 9)
    mask_vect = _random_mask_vect(LEN)
    ol = host_limbs.order_limbs_for(CFG.order)
    expected = host_limbs.mod_sub(host.object.vect.data, mask_vect, ol)

    agg = ShardedAggregator(CFG, LEN, mesh=make_mesh(jax.devices()), kernel=kernel)
    stream = StreamingAggregator(agg, max_batch=4)
    for i in range(0, len(stacks), 4):
        stream.submit_batch(np.stack(stacks[i : i + 4]))
    job = stream.stage_unmask(agg.mask_planar(mask_vect))
    assert job is not None, "sharded pipeline must take the eager path"
    stream.drain()
    out = stream.finish_unmask(job)
    assert out is not None, "no shard error -> the eager result must land"
    # the eager arm hands over the planes it fetched; a wire caller asks
    assert out.planes.shape == (expected.shape[1], LEN) and out.length == LEN
    np.testing.assert_array_equal(out.wire(), expected)
    stream.close()


def test_eager_unmask_single_device_falls_back():
    stacks, host = _updates(LEN, 5)
    agg = ShardedAggregator(CFG, LEN, mesh=make_mesh(jax.devices()[:1]), kernel="xla")
    stream = StreamingAggregator(agg, max_batch=4)
    for i in range(0, len(stacks), 4):
        stream.submit_batch(np.stack(stacks[i : i + 4]))
    mask_vect = _random_mask_vect(LEN)
    assert stream.stage_unmask(agg.mask_planar(mask_vect)) is None
    stream.drain()
    # the serial pass the caller falls back to is still exact
    ol = host_limbs.order_limbs_for(CFG.order)
    expected = host_limbs.mod_sub(host.object.vect.data, mask_vect, ol)
    np.testing.assert_array_equal(agg.unmask_limbs(mask_vect), expected)
    stream.close()


def test_eager_unmask_failure_falls_back_serial(monkeypatch):
    """A shard failure during the eager subtract must surface as a None
    from finish_unmask (fall back to the serial pass), never a wrong
    array and never a poisoned pipeline."""
    stacks, host = _updates(LEN, 4)
    agg = ShardedAggregator(CFG, LEN, mesh=make_mesh(jax.devices()), kernel="xla")
    stream = StreamingAggregator(agg, max_batch=4)
    stream.submit_batch(np.stack(stacks))
    real = ShardedAggregator.unmask_shard

    def boom(self, plan, d, mask_planar, out):
        if d == 1:
            raise RuntimeError("injected shard fault")
        return real(self, plan, d, mask_planar, out)

    monkeypatch.setattr(ShardedAggregator, "unmask_shard", boom)
    mask_vect = _random_mask_vect(LEN)
    job = stream.stage_unmask(agg.mask_planar(mask_vect))
    assert job is not None
    stream.drain()
    assert stream.finish_unmask(job) is None
    monkeypatch.setattr(ShardedAggregator, "unmask_shard", real)
    ol = host_limbs.order_limbs_for(CFG.order)
    expected = host_limbs.mod_sub(host.object.vect.data, mask_vect, ol)
    np.testing.assert_array_equal(agg.unmask_limbs(mask_vect), expected)
    stream.close()


def test_two_tenant_pipelined_eager_unmask_byte_identical():
    """Two tenants' rounds pipelined through the SHARED deficit-round-robin
    scheduler, each finishing with an eager per-shard unmask — both
    byte-identical to their serial controls."""
    sched = TenantScheduler(max_inflight=4)
    mesh = make_mesh(jax.devices())
    cases = {}
    for tag, tenant in ((10, "a"), (11, "b")):
        stacks, host = _updates(LEN, 8, seed=tag)
        mask_vect = _random_mask_vect(LEN, seed=tag)
        agg = ShardedAggregator(CFG, LEN, mesh=mesh, kernel="xla")
        stream = StreamingAggregator(
            agg, max_batch=4, tenant=tenant, scheduler=sched
        )
        cases[tenant] = (stacks, host, mask_vect, agg, stream)

    def run(tenant):
        stacks, _, mask_vect, agg, stream = cases[tenant]
        for i in range(0, len(stacks), 4):
            stream.submit_batch(np.stack(stacks[i : i + 4]))
        job = stream.stage_unmask(agg.mask_planar(mask_vect))
        stream.drain()
        out = stream.finish_unmask(job) if job is not None else None
        return out.wire() if out is not None else None

    results = {}
    errs = []

    def worker(tenant):
        try:
            results[tenant] = run(tenant)
        except BaseException as e:  # surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in cases]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not errs, errs
    ol = host_limbs.order_limbs_for(CFG.order)
    for tenant, (_, host, mask_vect, agg, stream) in cases.items():
        expected = host_limbs.mod_sub(host.object.vect.data, mask_vect, ol)
        got = results[tenant]
        if got is None:  # eager leg unavailable -> serial fallback is exact
            got = agg.unmask_limbs(mask_vect)
        np.testing.assert_array_equal(got, expected)
        stream.close()
    # both tenants' fold batches went through the shared fairness split
    split = sched.split()
    assert split.get("a", 0) > 0 and split.get("b", 0) > 0


# --- retired settings still load -------------------------------------------


def test_retired_overlap_and_device_pages_keys_load_as_if_absent(tmp_path):
    """An operator's file written for an earlier release keeps loading:
    `[overlap]` and `[tenancy] device_pages` are ignored, as every unknown
    table and key is, and the round it configures is the shipped one."""
    shipped = (REPO / "configs" / "config.toml").read_text()
    assert "[overlap]" not in shipped and "device_pages" not in shipped
    old = tmp_path / "old.toml"
    old.write_text(
        shipped.replace("[tenancy]\n", "[tenancy]\ndevice_pages = 8\n", 1)
        + "\n[overlap]\nenabled = false\neager_unmask = false\nspec_group = 0\n"
    )
    assert "device_pages = 8" in old.read_text()
    new = Settings.load(str(REPO / "configs" / "config.toml"), env={})
    assert Settings.load(str(old), env={}) == new
    env = {"XAYNET__OVERLAP__ENABLED": "false", "XAYNET__TENANCY__DEVICE_PAGES": "8"}
    assert Settings.load(str(REPO / "configs" / "config.toml"), env=env) == new
    assert not hasattr(new, "overlap") and not hasattr(new.tenancy, "device_pages")


# --- persisted calibration verdicts ---------------------------------------


@pytest.fixture
def calib_path(tmp_path):
    path = str(tmp_path / "calib.json")
    yield path
    calibcache.configure(None)  # never leak a cache into other tests


def test_calibcache_cold_warm_roundtrip(calib_path):
    calibcache.configure(calib_path)
    key = ("cpu", 123, "cfg", 8, None)
    assert calibcache.get("fold", key) is None  # cold
    calibcache.put("fold", key, "xla")
    calibcache.put("mask", key, "host-threaded")
    # a fresh "process": reload from disk
    calibcache.configure(calib_path)
    assert calibcache.get("fold", key) == "xla"
    assert calibcache.get("mask", key) == "host-threaded"
    raw = json.loads(Path(calib_path).read_text())
    assert raw["fingerprint"] == calibcache.fingerprint()


def test_calibcache_fingerprint_invalidates(calib_path, monkeypatch):
    calibcache.configure(calib_path)
    key = ("cpu", 1, None)
    calibcache.put("fold", key, "xla")
    monkeypatch.setattr(calibcache, "fingerprint", lambda: "other-machine")
    calibcache.configure(calib_path)
    assert calibcache.get("fold", key) is None


def test_calibcache_corrupt_file_fail_soft(calib_path):
    Path(calib_path).write_text("{not json")
    calibcache.configure(calib_path)  # must not raise
    assert calibcache.get("fold", ("k",)) is None
    calibcache.put("fold", ("k",), "xla")  # and recovers by rewriting
    calibcache.configure(calib_path)
    assert calibcache.get("fold", ("k",)) == "xla"


def test_calibcache_disabled_is_inert(calib_path):
    calibcache.configure(None)
    calibcache.put("fold", ("k",), "xla")
    assert calibcache.get("fold", ("k",)) is None
    assert not os.path.exists(calib_path)


def test_warm_mask_verdict_skips_probe_race(calib_path, monkeypatch):
    """A persisted verdict must short-circuit `_resolve_mask_kernel` —
    no probe race (sum_masks during resolution would be a cold race)."""
    seeds = _seeds(4, tag=9)
    length = LEN * 3
    calibcache.configure(calib_path)
    # cold race once to learn the exact verdict key + winner
    monkeypatch.setattr(masking_jax, "_MASK_KERNEL_CACHE", {})
    winner = masking_jax.calibrate_mask_kernel(seeds, length, CFG.pair())
    raw = json.loads(Path(calib_path).read_text())
    assert winner in raw["verdicts"]["mask"].values()
    # fresh process: empty in-process memo, warm disk tier; every probe
    # candidate runs through _mask_route -> spy it to prove none ran
    monkeypatch.setattr(masking_jax, "_MASK_KERNEL_CACHE", {})
    calibcache.configure(calib_path)
    calls = []
    real_route = masking_jax._mask_route

    def spy(*a, **k):
        calls.append(a[0])
        return real_route(*a, **k)

    monkeypatch.setattr(masking_jax, "_mask_route", spy)
    got = masking_jax.calibrate_mask_kernel(seeds, length, CFG.pair())
    assert got == winner
    assert calls == [], f"probe race ran despite a warm verdict: {calls}"


# --- round-wall bucket ladder ---------------------------------------------


def test_round_wall_buckets_log_ladder_live_render():
    from xaynet_tpu.telemetry.registry import get_registry
    from xaynet_tpu.telemetry.timeline import ROUND_WALL, ROUND_WALL_BUCKETS

    assert ROUND_WALL_BUCKETS[0] == 0.05 and ROUND_WALL_BUCKETS[-1] == 120.0
    # a log ladder: every step multiplies by at most ~2.5x — the seed's
    # sparse default tail (30 -> +Inf) put a 61s round in a bucket with
    # no resolution; this pins the regression shut
    for lo, hi in zip(ROUND_WALL_BUCKETS, ROUND_WALL_BUCKETS[1:]):
        assert 1.0 < hi / lo <= 2.5
    ROUND_WALL.labels(tenant="bucket-test").observe(61.0)
    text = get_registry().render()
    lines = [
        l
        for l in text.splitlines()
        if l.startswith("xaynet_round_wall_seconds_bucket")
        and 'tenant="bucket-test"' in l
    ]
    rendered_les = {l.split('le="')[1].split('"')[0] for l in lines}
    for b in ROUND_WALL_BUCKETS:
        assert any(float(le) == b for le in rendered_les - {"+Inf"}), b
    # the 61s observation lands between 60 and 90 — real resolution there
    by_le = {
        float(le): float(l.rsplit(" ", 1)[1])
        for l in lines
        for le in [l.split('le="')[1].split('"')[0]]
        if le != "+Inf"
    }
    assert by_le[60.0] == 0.0 and by_le[90.0] == 1.0


# --- trace_report --overlap ------------------------------------------------


def _span(name, ts_us, dur_us, **attrs):
    return {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us, "args": attrs}


def _round_events(with_overlap):
    # idle closes at 1.0s; serial phases sum=1s update=1s sum2=1s
    # unmask=0.2s; the overlap span is 0.5s of update-work under sum2
    ev = [
        _span("phase.idle", 0, 1_000_000, round_id=1),
        _span("round", 900_000, 3_400_000, round_id=1),
        _span("phase.sum", 1_000_000, 1_000_000, round_id=1),
        _span("phase.update", 2_000_000, 1_000_000, round_id=1),
        _span("phase.sum2", 3_000_000, 1_000_000, round_id=1),
        _span("phase.unmask", 4_000_000, 200_000, round_id=1),
    ]
    if with_overlap:
        ev.append(
            _span("overlap.drain", 3_100_000, 500_000, phase="update", tenant="t")
        )
    return ev


def test_trace_report_overlap_identity_balances():
    from tools import trace_report

    lanes, problems = trace_report.overlap_report(_round_events(True))
    assert problems == []
    assert "overlap.drain" in lanes and "under sum2" in lanes
    # update's wall grew by the reattributed 0.5s -> sum(walls) > wall,
    # negative slack measured
    assert "phase update: wall 1.5000s" in lanes
    assert "negative slack: -0.5000s" in lanes


def test_trace_report_overlap_serial_round_no_slack():
    from tools import trace_report

    lanes, problems = trace_report.overlap_report(_round_events(False))
    assert problems == []
    assert "no overlap.* spans" in lanes
    assert "negative slack: +0.0000s" in lanes


def test_trace_report_overlap_flags_missing_phase_attr():
    from tools import trace_report

    ev = _round_events(False)
    ev.append(_span("overlap.eager_unmask", 3_000_000, 100_000, shard=0))
    lanes, problems = trace_report.overlap_report(ev)
    assert any("without a work-phase" in p for p in problems)


def _mk_span(name, start, dur, **attrs):
    from xaynet_tpu.telemetry.tracing import Span

    s = Span(name, "deadbeef", f"s{start}", None, start, attrs)
    s.duration = dur
    return s


def _fold_input():
    t = 100.0
    return [
        _mk_span("phase.idle", t, 1.0, round_id=1, tenant="t"),
        _mk_span("round", t + 0.9, 3.3, round_id=1),
        _mk_span("phase.sum", t + 1.0, 1.0, round_id=1, tenant="t"),
        _mk_span("phase.update", t + 2.0, 1.0, round_id=1, tenant="t"),
        _mk_span("phase.sum2", t + 3.0, 1.0, round_id=1, tenant="t"),
        # 0.6s of update-phase work (the drain) ran INSIDE sum2's window
        _mk_span("overlap.drain", t + 3.1, 0.6, phase="update", tenant="t"),
        _mk_span("phase.unmask", t + 4.0, 0.2, round_id=1, tenant="t"),
    ]


def test_trace_report_overlap_cli_on_exported_trace(tmp_path):
    """End to end: a round's Chrome-trace export through the --overlap CLI
    (the CI trace-step invocation) — exit 0, identity balanced."""
    from xaynet_tpu.telemetry.tracing import to_chrome_trace

    from tools import trace_report

    doc = to_chrome_trace(_fold_input(), anchor=100.0)
    path = tmp_path / "round.trace.json"
    path.write_text(json.dumps(doc))
    assert trace_report.main(["--overlap", str(path)]) == 0


# --- server round: what the phases observe decides where the drain ends ---


@pytest.mark.parametrize("checkpoint_enabled", [False, True])
def test_server_round_overlap_engines(checkpoint_enabled, monkeypatch):
    """A real device-aggregation PET round end to end, and what selects
    where its drain ends. The Update phase always leaves through flush
    and Sum2 always starts the drain off the loop. Without a journal the
    drain ends INSIDE Sum2's window: held open here until a vote is
    acknowledged, it can only return if the window opened beside it. With
    a journal it has ended before the window opens: held open the same
    way, it sees no vote. Unmask goes through the eager per-shard path
    (stage_unmask on the still-live stream), and both publish the exact
    mean."""
    import asyncio
    from fractions import Fraction

    from xaynet_tpu.sdk.client import InProcessClient
    from xaynet_tpu.sdk.simulation import keys_for_task
    from xaynet_tpu.sdk.state_machine import (
        PetSettings,
        StateMachine as ParticipantSM,
    )
    from xaynet_tpu.sdk.traits import ModelStore
    from xaynet_tpu.server.aggregation import StagedAggregator
    from xaynet_tpu.server.phases.sum2 import Sum2Phase
    from xaynet_tpu.server.services import Fetcher, PetMessageHandler
    from xaynet_tpu.server.settings import (
        CountSettings,
        PhaseSettings,
        PetSettings as ServerPet,
        Settings as ServerSettings,
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

    staged, order, vote_seen_by_drain = [], [], []
    first_vote = threading.Event()
    real_stage = StreamingAggregator.stage_unmask
    real_flush = StagedAggregator.flush
    real_drain = StagedAggregator.drain
    real_sum2_drain = Sum2Phase._drain_overlapped
    real_vote = Sum2Phase.handle_request

    def stage_spy(self, mask_planar):
        job = real_stage(self, mask_planar)
        staged.append(job is not None)
        return job

    def flush_spy(self):
        order.append("flush")
        return real_flush(self)

    def drain_spy(self):
        order.append("drain")
        return real_drain(self)

    def sum2_drain_spy(self):
        order.append(("sum2_drain", threading.current_thread().name))
        # hold the barrier open until a vote is acknowledged: at once where
        # the window is open beside the drain, never where the phase waits
        # for the drain first
        vote_seen_by_drain.append(
            first_vote.wait(timeout=1.0 if checkpoint_enabled else 60.0)
        )
        return real_sum2_drain(self)

    async def vote_spy(self, req):
        await real_vote(self, req)
        first_vote.set()

    monkeypatch.setattr(StreamingAggregator, "stage_unmask", stage_spy)
    monkeypatch.setattr(StagedAggregator, "flush", flush_spy)
    monkeypatch.setattr(StagedAggregator, "drain", drain_spy)
    monkeypatch.setattr(Sum2Phase, "_drain_overlapped", sum2_drain_spy)
    monkeypatch.setattr(Sum2Phase, "handle_request", vote_spy)

    class ArrayModelStore(ModelStore):
        def __init__(self, model):
            self.model = model

        async def load_model(self):
            return self.model

    n_sum, n_update, model_len = 2, 3, 600

    async def run():
        settings = ServerSettings(
            pet=ServerPet(
                sum=PhaseSettings(
                    prob=0.4,
                    count=CountSettings(min=n_sum, max=n_sum),
                    time=TimeSettings(min=0.0, max=20.0),
                ),
                update=PhaseSettings(
                    prob=0.5,
                    count=CountSettings(min=n_update, max=n_update),
                    time=TimeSettings(min=0.0, max=20.0),
                ),
                sum2=Sum2Settings(
                    count=CountSettings(min=n_sum, max=n_sum),
                    time=TimeSettings(min=0.0, max=20.0),
                ),
            )
        )
        settings.model.length = model_len
        settings.aggregation.device = True
        settings.aggregation.batch_size = 2
        settings.aggregation.kernel = "xla"
        settings.resilience.checkpoint_enabled = checkpoint_enabled
        settings.validate()
        store = Store(
            InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor()
        )
        machine, request_tx, events = await StateMachineInitializer(
            settings, store
        ).init()
        handler = PetMessageHandler(events, request_tx)
        fetcher = Fetcher(events)
        machine_task = asyncio.create_task(machine.run())
        try:
            while fetcher.phase().value != "sum":
                await asyncio.sleep(0.01)
            seed = fetcher.round_params().seed.as_bytes()
            rng = np.random.default_rng(5)
            expected = np.zeros(model_len)
            participants = []
            for i in range(n_sum):
                keys = keys_for_task(seed, 0.4, 0.5, "sum", start=i * 1000)
                participants.append(
                    ParticipantSM(
                        PetSettings(keys=keys, max_message_size=1024),
                        InProcessClient(fetcher, handler),
                        ArrayModelStore(None),
                    )
                )
            for i in range(n_update):
                keys = keys_for_task(seed, 0.4, 0.5, "update", start=(10 + i) * 1000)
                local = rng.uniform(-1, 1, model_len).astype(np.float32)
                expected += local.astype(np.float64) / n_update
                participants.append(
                    ParticipantSM(
                        PetSettings(
                            keys=keys,
                            scalar=Fraction(1, n_update),
                            max_message_size=1024,
                        ),
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
    assert staged and staged[-1], "unmask did not take the eager path"
    sum2_drains = [e for e in order if isinstance(e, tuple)]
    # one drain a round, started by Sum2 OFF the event loop
    assert len(sum2_drains) == 1 and sum2_drains[0][1] != "MainThread"
    before_sum2 = order[: order.index(sum2_drains[0])]
    assert "flush" in before_sum2, "the Update phase did not leave through flush"
    if checkpoint_enabled:
        # the barrier ended before the window opened: no vote while it held
        assert vote_seen_by_drain == [False]
    else:
        # nothing drained before Sum2 did, and the window was open beside it
        assert "drain" not in before_sum2
        assert vote_seen_by_drain == [True]


def test_fold_error_in_the_drain_fails_the_round_at_sum2_exit():
    """The drain rides into Sum2; a fold error it raises must fail the
    round as Sum2 exits (a Failure for phase sum2), after the window, and
    the hand-off to Unmask (`finalize_inplace`) must never run."""
    import asyncio

    from test_round_journal import _failure_shared, _mem_store, _settings

    from xaynet_tpu.server.events import PhaseName
    from xaynet_tpu.server.phases.failure import Failure
    from xaynet_tpu.server.phases.sum2 import Sum2Phase
    from xaynet_tpu.server.settings import CountSettings, TimeSettings

    calls = []

    class FailingFold:
        def drain(self):
            calls.append("drain")
            raise RuntimeError("injected fold fault")

        def finalize_inplace(self, defer_drain=False):
            calls.append("finalize_inplace")
            raise AssertionError("Unmask was reached past a failed drain")

    settings = _settings()
    # an empty window: the phase ends as soon as it has opened
    settings.pet.sum2.count = CountSettings(min=0, max=0)
    settings.pet.sum2.time = TimeSettings(min=0.0, max=0.2)
    phase = Sum2Phase(_failure_shared(settings, _mem_store()), FailingFold())
    nxt = asyncio.run(asyncio.wait_for(phase.run_phase(), timeout=30))
    assert isinstance(nxt, Failure) and nxt.failed_phase is PhaseName.SUM2
    assert "injected fold fault" in str(nxt.error)
    assert calls == ["drain"]


# --- negative slack through the in-process timeline fold -------------------


def test_timeline_fold_negative_slack_from_overlap_spans():
    """The tentpole's measured identity: an `overlap.*` retro span merged
    into its home phase makes wall < sum(phase walls), and the §20
    identity still balances."""
    from xaynet_tpu.telemetry.timeline import fold_spans

    decomp = fold_spans(1, _fold_input())
    assert decomp is not None
    walls = sum(p["wall_s"] for p in decomp["phases"].values())
    wall = decomp["wall_s"]
    overlap = decomp["overlap_s"]
    gap = decomp["gap_s"]
    assert decomp["phases"]["update"]["wall_s"] == pytest.approx(1.6, abs=1e-6)
    assert overlap == pytest.approx(0.6, abs=1e-6)
    assert wall < walls  # negative slack: the identity's measured win
    assert walls - overlap + gap == pytest.approx(wall, abs=1e-6)
