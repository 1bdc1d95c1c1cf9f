"""Phase overlap & speculation (docs/DESIGN.md §22, ISSUE 18).

The overlap engines shrink the round wall below the serial sum of phase
walls; everything rests on **byte-identity with the serial path**. These
tests pin:

- speculative sum2 mask derivation (`ops.speculation`): hit / miss /
  discard reconciliation byte-identical to `sum_masks`, including
  mis-speculation (a speculated participant dropping before sum2),
  across mesh={1,8} and the host/device derive routes;
- eager per-shard unmask (`parallel.streaming._UnmaskJob`): identical to
  the drain-then-subtract serial pass on the native and XLA shard
  routes, correct fallback on a single-device mesh, and two tenants
  pipelined through the shared scheduler concurrently;
- `TenantScheduler.try_acquire_idle`: never blocks, never starves a
  real waiter, never distorts the fairness split;
- the `[overlap]` settings surface (defaults, env override, master
  gate);
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
import time
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
from xaynet_tpu.ops.speculation import SpeculativeMaskSession
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.parallel.streaming import StreamingAggregator
from xaynet_tpu.server.settings import OverlapSettings, Settings
from xaynet_tpu.tenancy.scheduler import TenantScheduler
from xaynet_tpu.utils import calibcache

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)

LEN = 257  # odd on purpose: uneven shard slices + a padded tail


def _seeds(n, tag=0):
    return [bytes([i & 0xFF, i >> 8, tag]) + b"\x5a" * 29 for i in range(n)]


def _settle_all(spec, n, deadline_s=60.0):
    """Wait until the background worker folded all n offered seeds (the
    deterministic all-hit setup; compile time makes a fixed sleep flaky)."""
    t0 = time.monotonic()
    while spec.speculated() < n:
        if time.monotonic() - t0 > deadline_s:
            pytest.fail(f"speculation folded {spec.speculated()}/{n} seeds")
        time.sleep(0.01)


# --- speculative mask derivation ------------------------------------------


def test_speculation_all_hits_byte_identical():
    seeds = _seeds(6)
    unit_ref, vect_ref = masking_jax.sum_masks(seeds, LEN, CFG.pair())
    spec = SpeculativeMaskSession(LEN, CFG.pair())
    spec.offer(seeds)
    _settle_all(spec, len(seeds))
    unit, vect = spec.settle(seeds)
    np.testing.assert_array_equal(np.asarray(vect), np.asarray(vect_ref))
    np.testing.assert_array_equal(np.asarray(unit), np.asarray(unit_ref))


def test_speculation_settle_without_worker_progress_is_serial():
    # settle may run before the worker derives anything (or after it only
    # got part way): every un-folded seed is a miss = the serial path
    seeds = _seeds(5, tag=1)
    unit_ref, vect_ref = masking_jax.sum_masks(seeds, LEN, CFG.pair())
    spec = SpeculativeMaskSession(LEN, CFG.pair())
    spec.offer(seeds)
    unit, vect = spec.settle(seeds)  # immediately: any mix of hit/miss
    np.testing.assert_array_equal(np.asarray(vect), np.asarray(vect_ref))
    np.testing.assert_array_equal(np.asarray(unit), np.asarray(unit_ref))


@pytest.mark.parametrize("kernel", ["host-threaded", "batch"])
@pytest.mark.parametrize("mesh_devices", [1, 8])
def test_misspeculation_discard_byte_identical(kernel, mesh_devices):
    """PR-5 churn as mis-speculation: a speculated sum participant drops
    before sum2 — its folded mask must be subtracted back out exactly, on
    host and device derive routes, single-device and 8-device meshes."""
    mesh = make_mesh(jax.devices()[:mesh_devices]) if mesh_devices > 1 else None
    offered = _seeds(5, tag=2)
    dropped = offered[2]
    actual = [s for s in offered if s != dropped]  # + one never-offered miss
    actual.append(_seeds(1, tag=3)[0])
    unit_ref, vect_ref = masking_jax.sum_masks(
        actual, LEN, CFG.pair(), kernel=kernel, mesh=mesh
    )
    spec = SpeculativeMaskSession(LEN, CFG.pair(), kernel=kernel, mesh=mesh)
    spec.offer(offered)
    _settle_all(spec, len(offered))  # the dropped seed IS folded -> discard
    unit, vect = spec.settle(actual)
    np.testing.assert_array_equal(np.asarray(vect), np.asarray(vect_ref))
    np.testing.assert_array_equal(np.asarray(unit), np.asarray(unit_ref))


def test_speculation_records_outcomes(monkeypatch):
    from xaynet_tpu.telemetry import timeline

    recorded = []
    monkeypatch.setattr(
        "xaynet_tpu.ops.speculation.record_spec_outcomes",
        lambda hits=0, misses=0, discards=0: recorded.append(
            (hits, misses, discards)
        ),
    )
    offered = _seeds(4, tag=4)
    actual = offered[:3] + _seeds(1, tag=5)
    spec = SpeculativeMaskSession(LEN, CFG.pair())
    spec.offer(offered)
    _settle_all(spec, len(offered))
    spec.settle(actual)
    assert recorded == [(3, 1, 1)]
    # and the real counter exists with the registered outcome labels
    assert timeline.SPEC_DERIVE is not None


def test_speculation_idle_slots_only():
    """A busy scheduler (waiter pending) denies the worker; every seed
    becomes a miss and settle still returns the exact aggregate."""
    sched = TenantScheduler(max_inflight=1)
    blocker = sched.new_owner()
    sched.acquire("real", blocker)  # the mesh is busy for the whole test
    try:
        seeds = _seeds(4, tag=6)
        unit_ref, vect_ref = masking_jax.sum_masks(seeds, LEN, CFG.pair())
        spec = SpeculativeMaskSession(
            LEN, CFG.pair(), tenant="spec", scheduler=sched
        )
        spec.offer(seeds)
        time.sleep(0.2)  # give the worker a chance to (wrongly) grab a slot
        assert spec.speculated() == 0
        unit, vect = spec.settle(seeds)
        np.testing.assert_array_equal(np.asarray(vect), np.asarray(vect_ref))
        np.testing.assert_array_equal(np.asarray(unit), np.asarray(unit_ref))
        assert "spec" not in sched.split()  # idle grants never charge fairness
    finally:
        sched.release_owner(blocker)


# --- scheduler idle slots --------------------------------------------------


def test_try_acquire_idle_semantics():
    sched = TenantScheduler(max_inflight=2)
    a, b, c = sched.new_owner(), sched.new_owner(), sched.new_owner()
    # idle mesh: granted, but NOT charged to the fairness split
    assert sched.try_acquire_idle("bg", a)
    assert sched.split() == {}
    # at capacity: denied
    sched.acquire("fg", b)
    assert not sched.try_acquire_idle("bg", a)
    sched.release(a)
    # capacity free but a regular waiter pending: denied (never starve)
    waited = threading.Event()

    def waiter():
        sched.acquire("fg", c)
        waited.set()

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    deadline = time.monotonic() + 5.0
    while not sched._waiting and not waited.is_set():
        if time.monotonic() > deadline:
            pytest.fail("waiter never queued")
        time.sleep(0.005)
    if not waited.is_set():
        assert not sched.try_acquire_idle("bg", a)
    sched.release(b)
    t.join(timeout=5.0)
    assert waited.is_set()
    assert sched.split() == {"fg": 2}
    sched.release_owner(c)
    sched.release_owner(a)


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


# --- [overlap] settings ----------------------------------------------------


def test_overlap_settings_defaults_and_master_gate():
    o = OverlapSettings()
    assert o.enabled and o.spec_group == 8
    for f in ("speculative_derive", "eager_unmask", "sum2_drain"):
        assert o.feature(f)
    o.enabled = False
    for f in ("speculative_derive", "eager_unmask", "sum2_drain"):
        assert not o.feature(f)
    with pytest.raises(Exception):
        OverlapSettings(spec_group=0).validate()


def test_overlap_settings_config_and_env():
    s = Settings.load(str(REPO / "configs" / "config.toml"))
    assert s.overlap.enabled and s.overlap.eager_unmask
    s2 = Settings.load(
        str(REPO / "configs" / "config.toml"),
        env={"XAYNET__OVERLAP__EAGER_UNMASK": "false"},
    )
    assert not s2.overlap.feature("eager_unmask")
    assert s2.overlap.feature("sum2_drain")
    s3 = Settings.load(
        str(REPO / "configs" / "config.toml"),
        env={"XAYNET__OVERLAP__ENABLED": "false"},
    )
    assert not s3.overlap.feature("sum2_drain")


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


# --- server round: the phase machine engages the overlap engines ----------


@pytest.mark.parametrize("enabled", [True, False])
def test_server_round_overlap_engines(enabled, monkeypatch):
    """A real device-aggregation PET round end to end. With `[overlap]`
    enabled (the default) the unmask phase must go through the eager
    per-shard path (stage_unmask on the still-live stream) and the update
    phase must exit via flush (the drain rides into sum2); disabled, the
    round is fully serial — and both produce the exact mean."""
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

    staged, drained = [], []
    real_stage = StreamingAggregator.stage_unmask
    real_drain = StagedAggregator.drain

    def stage_spy(self, mask_planar):
        job = real_stage(self, mask_planar)
        staged.append(job is not None)
        return job

    def drain_spy(self):
        drained.append(threading.current_thread().name)
        return real_drain(self)

    monkeypatch.setattr(StreamingAggregator, "stage_unmask", stage_spy)
    monkeypatch.setattr(StagedAggregator, "drain", drain_spy)

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
        settings.overlap.enabled = enabled
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
    if enabled:
        assert staged and staged[-1], "unmask did not take the eager path"
        # the sum2-window drain ran OFF the event loop (executor thread)
        assert any(name != "MainThread" for name in drained)
    else:
        assert not staged, "disabled overlap must stay fully serial"


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
