"""Shard-parallel streaming fold (parallel.shards + streaming shard mode).

The property everything rests on: the shard-parallel pipeline — one fold
worker per mesh device, per-shard staging rings, donated per-shard
accumulators, drain() as the cross-shard barrier — is **byte-identical to
the sequential single-device path** across kernels (xla, the Pallas kernel
through its interpreter, auto) × mesh sizes (1, 2, 8) × element widths
(2 limbs / 7 wire bytes, 3 limbs / 10 wire bytes) × planar/wire submit
paths, including
dispatch-ahead out-of-order schedules, and its per-shard degradation
ladder (fold failure → per-shard sync retry → pipeline-wide sync mode →
sticky poison) keeps the shards consistent: a batch commits only when
every shard folded it.
"""

import time

import numpy as np
import pytest

import jax

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
from xaynet_tpu.core.mask.serialization import serialize_mask_vect, vect_element_block
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.parallel.shards import ShardPlan
from xaynet_tpu.parallel.streaming import (
    SHARD_INFLIGHT,
    SHARD_STAGING_DEPTH,
    StreamingAggregator,
    StreamingError,
)

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
# the wide end of the bounded-f32 catalogue: 75-bit order, 3 limbs, 10 wire
# bytes; weights up to 1e6 put the encodings on both sides of 2^53
CFG3 = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6)
WIDTHS = pytest.mark.parametrize("cfg", [CFG, CFG3], ids=["2limb-7B", "3limb-10B"])

# pallas-interpret is the kernel the chip's race picks, run through the
# Pallas interpreter; "auto" on this CPU backend resolves to xla
KERNELS = ("xla", "pallas-interpret", "auto")
MESH_SIZES = (1, 2, 8)


def _mesh(n):
    return make_mesh(jax.devices()[:n])


def _updates(n, total, seed=0, cfg=CFG):
    rng = np.random.default_rng(seed)
    host = Aggregation(cfg.pair(), n)
    bound = float(cfg.add_shift)
    stacks, raws = [], []
    for _ in range(total):
        w = rng.uniform(-bound, bound, size=n).astype(np.float32)
        _, masked = Masker(cfg.pair()).mask(Scalar(1, total), w)
        host.aggregate(masked)
        stacks.append(masked.vect.data)
        raws.append(
            np.frombuffer(
                vect_element_block(serialize_mask_vect(masked.vect)), dtype=np.uint8
            )
        )
    return stacks, raws, host


def _sequential_oracle(n, stacks, bs, cfg=CFG):
    seq = ShardedAggregator(cfg, n, mesh=_mesh(1), kernel="xla")
    for i in range(0, len(stacks), bs):
        seq.add_batch(np.stack(stacks[i : i + bs]))
    return seq


# --- the core property: kernels x mesh sizes x widths x planar/wire --------


@WIDTHS
@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("mesh_size", MESH_SIZES)
def test_sharded_planar_byte_identical_to_sequential(kernel, mesh_size, cfg):
    n, total, bs = 103, 13, 4  # n not divisible by 8: padding columns in play
    stacks, _, host = _updates(n, total, cfg=cfg)
    seq = _sequential_oracle(n, stacks, bs, cfg)

    agg = ShardedAggregator(cfg, n, mesh=_mesh(mesh_size), kernel=kernel)
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    assert stream._sharded == (mesh_size > 1)
    for i in range(0, total, bs):
        stream.submit_batch(np.stack(stacks[i : i + bs]))
    stream.drain()

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == seq.nb_models == total
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    stream.close()


@WIDTHS
@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("mesh_size", MESH_SIZES)
def test_sharded_wire_byte_identical_with_deferred_acceptance(kernel, mesh_size, cfg):
    """Wire path: the per-shard fold must preserve the psum-consistent
    validity semantics (an update invalid anywhere is excluded everywhere),
    the acceptance vectors stay deferred until drain, and the aggregate +
    nb_models equal the sequential path."""
    n, total, bs = 57, 11, 4
    _, raws, _ = _updates(n, total, seed=3, cfg=cfg)
    bad = raws[5].copy()
    bad[: cfg.bytes_per_number] = 0xFF  # element >= order -> member rejected
    wires = raws[:5] + [bad] + raws[6:]

    seq = ShardedAggregator(cfg, n, mesh=_mesh(1), kernel="xla")
    seq_oks = [
        seq.add_wire_batch(np.stack(wires[i : i + bs])) for i in range(0, total, bs)
    ]

    agg = ShardedAggregator(cfg, n, mesh=_mesh(mesh_size), kernel=kernel)
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    tickets = [
        stream.submit_wire_batch(np.stack(wires[i : i + bs]))
        for i in range(0, total, bs)
    ]
    if mesh_size > 1:
        # acceptance is deferred: no ticket resolves before the barrier
        assert all(t.accepted is None for t in tickets)
    stream.drain()

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == seq.nb_models == total - 1
    got = np.concatenate([t.accepted for t in tickets])
    assert np.array_equal(got, np.concatenate(seq_oks))
    assert not got[5]
    stream.close()


@pytest.mark.parametrize("kernel", ("xla", "pallas-interpret"))
def test_sharded_mixed_paths_across_drain_cycles(kernel):
    """Planar and wire batches interleaved over several drain cycles: the
    plan decomposes/reassembles per cycle and the result stays pinned to
    the sequential single-device fold."""
    n, total, bs = 103, 12, 3
    stacks, raws, _ = _updates(n, total, seed=11)

    seq = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
    seq.add_wire_batch(np.stack(raws[0:3]))
    seq.add_batch(np.stack(stacks[3:6]))
    seq.add_wire_batch(np.stack(raws[6:9]))
    seq.add_batch(np.stack(stacks[9:12]))

    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel=kernel)
    stream = StreamingAggregator(agg, staging_buffers=2, dispatch_ahead=2, max_batch=bs)
    stream.submit_wire_batch(np.stack(raws[0:3]))
    stream.submit_batch(np.stack(stacks[3:6]))
    stream.drain()  # cycle 1: reassemble
    stream.submit_wire_batch(np.stack(raws[6:9]))  # cycle 2: re-decompose
    stream.submit_batch(np.stack(stacks[9:12]))
    stream.drain()

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == seq.nb_models == total
    stream.close()


def test_sharded_dispatch_ahead_out_of_order_stress():
    """Producer runs several batches ahead of shard folds that complete
    late with per-shard jitter (shard progress skew): every batch must
    commit exactly once, the per-shard gauges must return to zero, and the
    aggregate must stay byte-identical."""
    n, total, bs = 64, 36, 3
    stacks, _, host = _updates(n, total, seed=7)
    seq = _sequential_oracle(n, stacks, bs)

    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=4, dispatch_ahead=3, max_batch=bs)

    rng = np.random.default_rng(0)
    jitters = {d: rng.uniform(0.0, 0.004, size=64) for d in range(8)}
    counts = {d: 0 for d in range(8)}
    real_fold = ShardPlan.fold_shard

    def slow_fold(self, d, batch):
        i = counts[d]
        counts[d] += 1
        time.sleep(float(jitters[d][i % 64]))
        return real_fold(self, d, batch)

    try:
        ShardPlan.fold_shard = slow_fold
        for i in range(0, total, bs):
            stream.submit_batch(np.stack(stacks[i : i + bs]))
        stream.drain()
    finally:
        ShardPlan.fold_shard = real_fold

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    assert agg.nb_models == total
    for d in range(8):
        assert SHARD_INFLIGHT.labels(shard=str(d)).value == 0
        assert SHARD_STAGING_DEPTH.labels(shard=str(d)).value == 0
    stream.close()


# --- degradation ladder ----------------------------------------------------


def test_shard_failure_degrades_then_completes_byte_identical():
    """One shard's fold fails once (accumulator untouched): that shard
    retries synchronously, the pipeline flips to the sync path, and the
    round completes with the exact sequential aggregate."""
    n, total, bs = 48, 12, 3
    stacks, _, _ = _updates(n, total, seed=5)
    seq = _sequential_oracle(n, stacks, bs)

    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    real_fold = ShardPlan.fold_shard
    real_fold_packed = ShardPlan.fold_shard_packed
    state = {"failed": False}

    def flaky(self, d, batch):
        if d == 3 and not state["failed"]:
            state["failed"] = True
            raise RuntimeError("transient shard fault")
        return real_fold(self, d, batch)

    def flaky_packed(self, d, batch):
        if d == 3 and not state["failed"]:
            state["failed"] = True
            raise RuntimeError("transient shard fault")
        return real_fold_packed(self, d, batch)

    try:
        ShardPlan.fold_shard = flaky
        ShardPlan.fold_shard_packed = flaky_packed
        for i in range(0, total, bs):
            stream.submit_batch(np.stack(stacks[i : i + bs]))
        stream.drain()
    finally:
        ShardPlan.fold_shard = real_fold
        ShardPlan.fold_shard_packed = real_fold_packed

    assert stream.degraded
    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == total
    stream.close()


def test_shard_failure_twice_poisons_with_batch_diagnostics():
    """The same shard failing on the retry too loses the batch: the
    pipeline poisons permanently, every later submit AND drain keeps
    raising with the poisoning batch index and root cause."""
    n, bs = 48, 3
    stacks, _, _ = _updates(n, 9, seed=6)

    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    real_fold = ShardPlan.fold_shard
    real_fold_packed = ShardPlan.fold_shard_packed

    def always_broken(self, d, batch):
        if d == 5:
            raise RuntimeError("shard 5 is on fire")
        return real_fold(self, d, batch)

    def always_broken_packed(self, d, batch):
        if d == 5:
            raise RuntimeError("shard 5 is on fire")
        return real_fold_packed(self, d, batch)

    try:
        ShardPlan.fold_shard = always_broken
        ShardPlan.fold_shard_packed = always_broken_packed
        stream.submit_batch(np.stack(stacks[0:3]))
        with pytest.raises(StreamingError, match="batch 1.*shard 5 is on fire"):
            stream.drain()
    finally:
        ShardPlan.fold_shard = real_fold
        ShardPlan.fold_shard_packed = real_fold_packed
    # sticky: healthy folds cannot resurrect a poisoned pipeline
    with pytest.raises(StreamingError, match="poisoned"):
        stream.submit_batch(np.stack(stacks[3:6]))
    with pytest.raises(StreamingError, match="batch 1"):
        stream.drain()
    assert stream.in_flight_models == 0
    stream.close()


# --- sequential multi-device fold ------------------------------------------


@WIDTHS
@pytest.mark.parametrize("kernel", ("xla", "pallas-interpret"))
def test_sequential_multidevice_fold_and_unmask(kernel, cfg):
    """add_batch on an 8-device mesh (one program over the mesh, the Pallas
    kernel under shard_map): the aggregate and the unmasked result must
    equal the single-device XLA fold's."""
    n, total, bs = 103, 8, 4
    stacks, _, _ = _updates(n, total, seed=9, cfg=cfg)
    ref = _sequential_oracle(n, stacks, bs, cfg)

    agg = ShardedAggregator(cfg, n, mesh=_mesh(8), kernel=kernel)
    for i in range(0, total, bs):
        agg.add_batch(np.stack(stacks[i : i + bs]))
    assert agg.kernel_used == kernel
    assert np.array_equal(agg.snapshot(), ref.snapshot())

    mask = _updates(n, 1, seed=13, cfg=cfg)[0][0]
    assert np.array_equal(agg.unmask_limbs(mask), ref.unmask_limbs(mask))


# --- ShardPlan units ---------------------------------------------------------


def test_shard_plan_requires_resolved_kernel():
    agg = ShardedAggregator(CFG, 64, mesh=_mesh(2), kernel="auto")
    with pytest.raises(ValueError, match="resolved"):
        ShardPlan(agg)


def test_shard_plan_reassemble_roundtrip():
    """decompose -> per-shard folds -> reassemble equals the sequential
    fold, for both kernels, starting from a non-zero accumulator."""
    n, total, bs = 96, 4, 4
    stacks, _, _ = _updates(n, total, seed=17)
    base = _updates(n, 2, seed=18)[0]

    for kernel in ("xla", "pallas-interpret"):
        ref = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
        ref.add_batch(np.stack(base))
        ref.add_batch(np.stack(stacks))

        agg = ShardedAggregator(CFG, n, mesh=_mesh(4), kernel=kernel)
        agg.add_batch(np.stack(base))  # resolves the kernel, non-zero acc
        plan = ShardPlan(agg)
        planar = np.zeros((total, agg.n_limbs, agg.padded_length), np.uint32)
        from xaynet_tpu.ops.fold_jax import wire_to_planar

        planar[:, :, :n] = wire_to_planar(np.stack(stacks))
        for d, (lo, hi) in enumerate(plan.slices):
            piece = jax.device_put(
                np.ascontiguousarray(planar[:, :, lo:hi]), plan.devices[d]
            )
            plan.fold_shard(d, piece)
        plan.block_until_ready()
        agg.acc = plan.reassemble()
        assert np.array_equal(agg.snapshot(), ref.snapshot()), kernel


# --- surfaces --------------------------------------------------------------


def test_shard_parallel_settings_surface():
    from xaynet_tpu.server.settings import Settings

    s = Settings.default()
    assert s.aggregation.shard_parallel is True
    s.validate()


def test_shard_parallel_opt_out_forces_single_worker():
    n, total, bs = 64, 6, 3
    stacks, _, _ = _updates(n, total, seed=21)
    seq = _sequential_oracle(n, stacks, bs)
    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel="xla")
    stream = StreamingAggregator(
        agg, staging_buffers=2, dispatch_ahead=2, max_batch=bs, shard_parallel=False
    )
    assert not stream._sharded
    for i in range(0, total, bs):
        stream.submit_batch(np.stack(stacks[i : i + bs]))
    stream.drain()
    assert np.array_equal(agg.snapshot(), seq.snapshot())
    stream.close()


def test_healthz_pipeline_section_reports_shards():
    """The REST /healthz builder reads the streaming + per-shard gauges
    straight from the telemetry registry (no jax import on that path)."""
    n, bs = 64, 3
    stacks, _, _ = _updates(n, 6, seed=23)
    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=2, dispatch_ahead=2, max_batch=bs)
    stream.submit_batch(np.stack(stacks[0:3]))
    stream.drain()
    stream.close()

    from xaynet_tpu.server.rest import RestServer

    rest = RestServer.__new__(RestServer)  # only _streaming_health is exercised
    from xaynet_tpu.telemetry.registry import get_registry

    rest.registry = get_registry()
    section = rest._streaming_health()
    assert section is not None
    assert section["degraded"] in (False, True)
    assert "shards" in section
    for d in range(8):
        shard = section["shards"][str(d)]
        assert shard["staging_depth"] == 0
        assert shard["inflight_folds"] == 0


@pytest.mark.parametrize("kernel", ("xla", "pallas-interpret"))
def test_sharded_fold_planar_rows_now_device_resident(kernel):
    """The server wire-ingest flush path: device-resident planars cached by
    validate_wire_updates fold synchronously per shard (the stacked chunk
    re-pinned to the batch sharding) and stay byte-identical."""
    n, total = 96, 10
    _, raws, _ = _updates(n, total, seed=29)

    seq = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
    seq.add_wire_batch(np.stack(raws))

    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel=kernel)
    stream = StreamingAggregator(agg, staging_buffers=2, dispatch_ahead=2, max_batch=4)
    planars = agg.validate_wire_updates([np.asarray(r) for r in raws])
    assert all(p is not None for p in planars)
    stream.fold_resident_rows_now(planars)
    stream.drain()

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == seq.nb_models == total
    stream.close()


def test_healthz_pipeline_section_degraded_shard():
    """Satellite (ISSUE 12): after the PR-7 single-shard sync-retry path
    fires, /healthz's pipeline section must surface the global degraded
    flag AND the per-shard triple — the first place an operator looks when
    the mesh goes degraded."""
    n, total, bs = 48, 6, 3
    stacks, _, _ = _updates(n, total, seed=31)
    seq = _sequential_oracle(n, stacks, bs)

    agg = ShardedAggregator(CFG, n, mesh=_mesh(8), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    real_fold = ShardPlan.fold_shard
    real_fold_packed = ShardPlan.fold_shard_packed
    state = {"failed": False}

    def flaky(self, d, batch):
        if d == 2 and not state["failed"]:
            state["failed"] = True
            raise RuntimeError("transient shard fault")
        return real_fold(self, d, batch)

    def flaky_packed(self, d, batch):
        if d == 2 and not state["failed"]:
            state["failed"] = True
            raise RuntimeError("transient shard fault")
        return real_fold_packed(self, d, batch)

    try:
        ShardPlan.fold_shard = flaky
        ShardPlan.fold_shard_packed = flaky_packed
        for i in range(0, total, bs):
            stream.submit_batch(np.stack(stacks[i : i + bs]))
        stream.drain()
    finally:
        ShardPlan.fold_shard = real_fold
        ShardPlan.fold_shard_packed = real_fold_packed

    assert stream.degraded  # the sync-retry path fired
    from xaynet_tpu.server.rest import RestServer
    from xaynet_tpu.telemetry.registry import get_registry

    rest = RestServer.__new__(RestServer)  # only _streaming_health is exercised
    rest.registry = get_registry()
    section = rest._streaming_health()
    assert section is not None
    assert section["degraded"] is True
    assert section["inflight_folds"] == 0  # drained
    for d in range(8):
        shard = section["shards"][str(d)]
        assert shard["staging_depth"] == 0
        assert shard["inflight_folds"] == 0
        assert "overlap_ratio" in shard
    # the degraded round still completed byte-identically (PR-7 ladder)
    assert np.array_equal(agg.snapshot(), seq.snapshot())
    stream.close()
    # close resets the flag for the next healthy pipeline's healthz
    assert rest._streaming_health()["degraded"] is False
