"""Streaming aggregation pipeline (parallel.streaming) correctness.

The pipeline moves staging, folding, and acceptance syncs off the caller's
critical path; these tests pin the property everything rests on —
**byte-identity with the sequential path** — across fold kernels
(including the native host kernel), for both planar and raw-wire submits,
under dispatch-ahead schedules where the producer runs several batches
ahead of late-completing folds, plus the batch-prevalidation single-
dispatch contract and the settings/metrics surface.
"""

import threading
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
from xaynet_tpu.parallel.streaming import (
    BATCHES_TOTAL,
    INFLIGHT_FOLDS,
    STAGING_DEPTH,
    StreamingAggregator,
    StreamingError,
)

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
# the wide end of the bounded-f32 catalogue: 75-bit order, 3 limbs, 10 wire
# bytes; weights up to 1e6 put the encodings on both sides of 2^53
CFG3 = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6)
WIDTHS = pytest.mark.parametrize("cfg", [CFG, CFG3], ids=["2limb-7B", "3limb-10B"])

# these tests pin device 0 explicitly (the conftest forces 8 virtual CPU
# devices) to exercise the SINGLE-WORKER pipeline; the shard-parallel
# multi-device mode has its own suite in tests/test_shard_parallel.py
KERNELS = ("xla", "pallas-interpret", "auto")


def _mesh1():
    return make_mesh(jax.devices()[:1])


def _weights(rng, n, cfg):
    bound = float(cfg.add_shift)
    return rng.uniform(-bound, bound, size=n).astype(np.float32)


def _updates(n, total, seed=0, cfg=CFG):
    rng = np.random.default_rng(seed)
    host = Aggregation(cfg.pair(), n)
    stacks, raws = [], []
    for _ in range(total):
        w = _weights(rng, n, cfg)
        _, masked = Masker(cfg.pair()).mask(Scalar(1, total), w)
        host.aggregate(masked)
        stacks.append(masked.vect.data)
        raws.append(
            np.frombuffer(
                vect_element_block(serialize_mask_vect(masked.vect)), dtype=np.uint8
            )
        )
    return stacks, raws, host


def _kernel_cases():
    """Every fold kernel at both widths (the Pallas fold interpreted)."""
    return [
        pytest.param(cfg, kernel, id=f"{width}-{kernel}")
        for cfg, width in ((CFG, "2limb-7B"), (CFG3, "3limb-10B"))
        for kernel in KERNELS
    ]


@pytest.mark.parametrize("cfg,kernel", _kernel_cases())
def test_streaming_planar_byte_identical_to_sequential(cfg, kernel):
    n, total, bs = 103, 13, 4
    stacks, _, host = _updates(n, total, cfg=cfg)
    seq = ShardedAggregator(cfg, n, mesh=_mesh1(), kernel=kernel)
    for i in range(0, total, bs):
        seq.add_batch(np.stack(stacks[i : i + bs]))

    agg = ShardedAggregator(cfg, n, mesh=_mesh1(), kernel=kernel)
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    for i in range(0, total, bs):
        stream.submit_batch(np.stack(stacks[i : i + bs]))
    stream.drain()

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == seq.nb_models == total
    # both equal the host oracle, not merely each other
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    assert agg.kernel_used == seq.kernel_used
    stream.close()


@pytest.mark.parametrize("cfg,kernel", _kernel_cases())
def test_streaming_wire_deferred_acceptance_matches_sequential(cfg, kernel):
    """Raw-wire streaming: accumulator, nb_models AND the per-member
    acceptance vectors (fetched in one deferred sync at drain) must equal
    the sequential add_wire_batch path, invalid members included."""
    n, total, bs = 57, 11, 4
    _, raws, _ = _updates(n, total, seed=3, cfg=cfg)
    bad = raws[5].copy()
    bad[: cfg.bytes_per_number] = 0xFF  # element >= order -> member rejected
    wires = raws[:5] + [bad] + raws[6:]

    seq = ShardedAggregator(cfg, n, mesh=_mesh1(), kernel=kernel)
    seq_oks = [
        seq.add_wire_batch(np.stack(wires[i : i + bs])) for i in range(0, total, bs)
    ]

    agg = ShardedAggregator(cfg, n, mesh=_mesh1(), kernel=kernel)
    stream = StreamingAggregator(agg, staging_buffers=3, dispatch_ahead=2, max_batch=bs)
    tickets = [
        stream.submit_wire_batch(np.stack(wires[i : i + bs]))
        for i in range(0, total, bs)
    ]
    # deferred: before drain no ticket has resolved acceptance
    stream.drain()

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == seq.nb_models == total - 1
    got = np.concatenate([t.accepted for t in tickets])
    assert np.array_equal(got, np.concatenate(seq_oks))
    assert not got[5] and int(got.sum()) == total - 1
    stream.close()


def test_dispatch_ahead_out_of_order_completion_stress():
    """Producer races several batches ahead of folds that complete late and
    with jittered timing: the ring/queue bounds must hold (gauges return to
    zero), every batch must fold exactly once, and the aggregate must stay
    byte-identical to the sequential schedule."""
    n, total, bs = 64, 36, 3
    stacks, _, host = _updates(n, total, seed=7)
    seq = ShardedAggregator(CFG, n, mesh=_mesh1(), kernel="xla")
    for i in range(0, total, bs):
        seq.add_batch(np.stack(stacks[i : i + bs]))

    agg = ShardedAggregator(CFG, n, mesh=_mesh1(), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=4, dispatch_ahead=3, max_batch=bs)
    # resolve the kernel on the first batch, then wrap the fold with jitter
    stream.submit_batch(np.stack(stacks[0:bs]))
    stream.drain()
    # packed staging is the default layout, so the worker folds through
    # _packed_fold_fn — wrap whichever entry the pipeline actually uses
    packed = stream._packed
    real_fold = agg._packed_fold_fn if packed else agg._fold_fn
    jitter = iter(np.random.default_rng(1).uniform(0.0, 0.004, size=total))
    folded_sizes = []

    def slow_fold(acc, staged):
        time.sleep(float(next(jitter)))
        folded_sizes.append(int(staged.shape[0]))
        return real_fold(acc, staged)

    if packed:
        agg._packed_fold_fn = slow_fold
    else:
        agg._fold_fn = slow_fold
    staged_before = BATCHES_TOTAL.labels(stage="staged").value
    for i in range(bs, total, bs):
        stream.submit_batch(np.stack(stacks[i : i + bs]))
    stream.drain()

    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    assert agg.nb_models == seq.nb_models == total
    # every submitted batch folded exactly once, none dropped or duplicated
    assert sum(folded_sizes) == total - bs
    assert (
        BATCHES_TOTAL.labels(stage="staged").value - staged_before
        == (total - bs) / bs
    )
    # bounds released: nothing left in flight, no ring buffer leaked
    assert INFLIGHT_FOLDS.value == 0
    assert STAGING_DEPTH.value == 0
    stream.close()


def test_worker_failure_surfaces_at_drain():
    n, bs = 32, 2
    stacks, _, _ = _updates(n, 4, seed=9)
    agg = ShardedAggregator(CFG, n, mesh=_mesh1(), kernel="xla")
    stream = StreamingAggregator(agg, staging_buffers=2, dispatch_ahead=1, max_batch=bs)
    stream.submit_batch(np.stack(stacks[0:bs]))
    stream.drain()

    def boom(acc, staged):
        raise RuntimeError("fold died (stand-in)")

    agg._fold_fn = boom
    agg._packed_fold_fn = boom  # packed staging is the default layout
    stream.submit_batch(np.stack(stacks[bs : 2 * bs]))
    with pytest.raises(StreamingError):
        stream.drain()
    # the poison is PERMANENT: a later drain (the finalize/close path) must
    # keep failing rather than hand out a snapshot whose accumulator and
    # nb_models no longer describe the same update set
    with pytest.raises(StreamingError):
        stream.drain()
    stream.close()  # cleanup still works on a poisoned pipeline


def test_prevalidate_wire_batch_one_dispatch_per_group():
    """StagedAggregator.prevalidate_wire_batch: one wire_unpack dispatch +
    one acceptance fetch for the whole micro-batch; validate_aggregation
    then consumes the cached per-member verdicts (invalid member rejected,
    valid members staged) with NO further device round-trips."""
    from xaynet_tpu.core.mask.masking import AggregationError
    from xaynet_tpu.core.mask.object import LazyWireMaskVect, MaskObject
    from xaynet_tpu.server.aggregation import StagedAggregator
    from xaynet_tpu.telemetry import profiling

    n, k = 57, 5
    rng = np.random.default_rng(11)
    host = StagedAggregator(CFG.pair(), n, device=False, batch_size=8)
    dev = StagedAggregator(CFG.pair(), n, device=True, batch_size=8, kernel="xla")
    objs = []
    for i in range(k):
        w = rng.uniform(-1, 1, n).astype(np.float32)
        _, masked = Masker(CFG.pair()).mask(Scalar(1, k), w)
        raw = np.array(vect_element_block(serialize_mask_vect(masked.vect)))
        if i == 2:
            raw[: CFG.bytes_per_number] = 0xFF  # invalid member
        else:
            host.validate_aggregation(masked)
            host.aggregate(masked)
        objs.append(MaskObject(LazyWireMaskVect(CFG, raw, n), masked.unit))

    unpacks = profiling.KERNEL_CALLS.labels(op="wire_unpack")
    before = unpacks.value
    dev.prevalidate_wire_batch(objs)
    assert unpacks.value - before == 1  # ONE dispatch for the group
    for i, obj in enumerate(objs):
        if i == 2:
            with pytest.raises(AggregationError):
                dev.validate_aggregation(obj)
        else:
            dev.validate_aggregation(obj)
            assert obj.vect._staged_planar is not None
            dev.aggregate(obj)
    assert unpacks.value - before == 1  # cached verdicts, no re-dispatch
    a, b = host.finalize(), dev.finalize()
    assert a.nb_models == b.nb_models == k - 1
    assert a.object == b.object


def test_staged_aggregator_flush_is_submit_drain_is_sync():
    """flush() submits without losing updates; nb_models counts staged +
    in-flight + folded at every point; drain() is the synchronization."""
    from xaynet_tpu.server.aggregation import StagedAggregator

    n, k = 40, 6
    rng = np.random.default_rng(13)
    host = StagedAggregator(CFG.pair(), n, device=False, batch_size=2)
    dev = StagedAggregator(
        CFG.pair(), n, device=True, batch_size=2, kernel="xla",
        dispatch_ahead=2, staging_buffers=3,
    )
    for _ in range(k):
        w = rng.uniform(-1, 1, n).astype(np.float32)
        _, masked = Masker(CFG.pair()).mask(Scalar(1, k), w)
        for s in (host, dev):
            s.validate_aggregation(masked)
            s.aggregate(masked)
        assert dev.nb_models == host.nb_models  # staged/in-flight included
    dev.drain()
    assert dev.nb_models == host.nb_models == k
    a, b = host.finalize(), dev.finalize()
    assert a.nb_models == b.nb_models == k
    assert a.object == b.object


def test_streaming_settings_surface():
    from xaynet_tpu.server.settings import Settings, SettingsError

    s = Settings.load(env={"XAYNET__AGGREGATION__DISPATCH_AHEAD": "4",
                           "XAYNET__AGGREGATION__STAGING_BUFFERS": "5",
                           "XAYNET__AGGREGATION__KERNEL": "pallas"})
    assert s.aggregation.dispatch_ahead == 4
    assert s.aggregation.staging_buffers == 5
    assert s.aggregation.kernel == "pallas"
    with pytest.raises(SettingsError):
        Settings.load(env={"XAYNET__AGGREGATION__DISPATCH_AHEAD": "0"})
    with pytest.raises(SettingsError):
        Settings.load(env={"XAYNET__AGGREGATION__STAGING_BUFFERS": "1"})


def test_prevalidate_skips_count_mismatched_member():
    """A member whose declared count mismatches the round's model length
    must be SKIPPED by batch prevalidation (ragged np.stack would otherwise
    abort the whole micro-batch with an internal error) and rejected alone
    by the per-member ModelMismatch check, exactly like the sequential
    path."""
    from xaynet_tpu.core.mask.masking import AggregationError
    from xaynet_tpu.core.mask.object import LazyWireMaskVect, MaskObject
    from xaynet_tpu.server.aggregation import StagedAggregator

    n = 57
    rng = np.random.default_rng(17)
    dev = StagedAggregator(CFG.pair(), n, device=True, batch_size=8, kernel="xla")
    w = rng.uniform(-1, 1, n).astype(np.float32)
    _, good_masked = Masker(CFG.pair()).mask(Scalar(1, 2), w)
    good = MaskObject(
        LazyWireMaskVect(
            CFG,
            np.array(vect_element_block(serialize_mask_vect(good_masked.vect))),
            n,
        ),
        good_masked.unit,
    )
    w_short = rng.uniform(-1, 1, n - 3).astype(np.float32)
    _, short_masked = Masker(CFG.pair()).mask(Scalar(1, 2), w_short)
    short = MaskObject(
        LazyWireMaskVect(
            CFG,
            np.array(vect_element_block(serialize_mask_vect(short_masked.vect))),
            n - 3,
        ),
        short_masked.unit,
    )

    dev.prevalidate_wire_batch([good, short])  # must not raise on ragged rows
    dev.validate_aggregation(good)
    assert good.vect._staged_planar is not None
    dev.aggregate(good)
    with pytest.raises(AggregationError):  # ModelMismatch for THAT member only
        dev.validate_aggregation(short)
    assert dev.nb_models == 1


# -- staging at arrival (ISSUE 25) ------------------------------------------
#
# The pipeline lends an open batch one ring buffer a shard and every
# accepted update is written into its own slot of each by its ``xn-ingest``
# task; ``flush()`` only waits for the writes and submits the buffers. One
# device is the case of one shard (ISSUE 40: the mesh, further down).


def _mesh2():
    return make_mesh(jax.devices()[:2])


def _masked_updates(n, total, seed, cfg=CFG):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(total):
        out.append(Masker(cfg.pair()).mask(Scalar(1, total), _weights(rng, n, cfg))[1])
    return out


def _oracle(n, objs, cfg=CFG):
    host = Aggregation(cfg.pair(), n)
    for obj in objs:
        host.aggregate(obj)
    return host


def _staged(n, batch_size=4, cfg=CFG, **kw):
    """A device StagedAggregator whose pipeline is NOT shard-parallel on a
    2-device mesh, so ``padded_length`` (104 at n = 103) exceeds the model
    length and the slots have pad columns."""
    from xaynet_tpu.server.aggregation import StagedAggregator

    kw.setdefault("mesh", _mesh2())
    kw.setdefault("shard_parallel", False)
    kw.setdefault("kernel", "xla")
    return StagedAggregator(cfg.pair(), n, device=True, batch_size=batch_size, **kw)


def _rows_staged():
    from xaynet_tpu.parallel.streaming import ROWS_STAGED

    return (
        ROWS_STAGED.labels(route="arrival").value,
        ROWS_STAGED.labels(route="flush").value,
    )


def _await_writes(dev):
    """Every slot write of the open batches has ended, and every row's copy
    to the device behind it."""
    for batch in dev._open:
        for write in batch.writes:
            write.exception(timeout=30)
        if batch.bufs is not None:
            dev._stream.wait_rows(batch.bufs)


@WIDTHS
@pytest.mark.parametrize("shape", ["full", "part", "reused", "overrun"])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
def test_arrival_staging_bit_equal_to_host_oracle(packed, shape, cfg):
    """full: one batch closed by its last update; part: a batch of 3 of 4
    closed by drain(); reused: three batches through two DIRTY ring
    buffers; overrun: 11 updates staged before the one flush, so the third
    open batch waits for a ring buffer that the flush has to free. Model
    length 103 on a padded length of 104."""
    n = 103
    total = {"full": 4, "part": 3, "reused": 12, "overrun": 11}[shape]
    objs = _masked_updates(n, total, seed=21, cfg=cfg)
    dev = _staged(n, cfg=cfg, packed_staging=packed, staging_buffers=2)
    stream = dev._stream
    assert stream._packed is packed and stream._n_shards == 1
    assert stream.agg.padded_length == 104
    if shape == "reused":
        ring = stream._ring(stream._host_kind)
        bufs = [ring.acquire(), ring.acquire()]
        for buf in bufs:
            buf.fill(0xFF)
            ring.release(buf)
    arrival0, flush0 = _rows_staged()
    depth0 = STAGING_DEPTH.value
    for obj in objs:
        dev.validate_aggregation(obj)
        if shape == "overrun":
            dev.stage(obj)
        else:
            dev.aggregate(obj)
    drain = threading.Thread(target=dev.drain, daemon=True)
    drain.start()
    drain.join(timeout=60)
    assert not drain.is_alive()
    assert _rows_staged() == (arrival0 + total, flush0)
    assert STAGING_DEPTH.value == depth0
    got, want = dev.finalize(), _oracle(n, objs, cfg)
    assert got.nb_models == want.nb_models == total
    assert got.object == want.object


@WIDTHS
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
def test_arrival_staging_through_the_pallas_fold(packed, cfg):
    """The slot route into the Pallas fold (interpreted): a full batch, then
    a batch of 2 of 4 closed by drain(), at both element widths."""
    n, total = 103, 6
    objs = _masked_updates(n, total, seed=24, cfg=cfg)
    dev = _staged(n, cfg=cfg, packed_staging=packed, kernel="pallas-interpret")
    for obj in objs:
        dev.validate_aggregation(obj)
        dev.aggregate(obj)
    dev.drain()
    assert dev.kernel_used == "pallas-interpret"
    got, want = dev.finalize(), _oracle(n, objs, cfg)
    assert got.nb_models == want.nb_models == total
    assert got.object == want.object


@WIDTHS
@pytest.mark.parametrize("route", ["fold_partial", "wire_ingest", "mesh2_partial"])
def test_other_routes_stage_as_before(route, cfg):
    """What does not take the slot route: an edge partial (one row through
    ``submit_batch``, on one device and on a shard-parallel mesh: opened,
    filled and submitted in the one call) and device-resident wire-ingest
    parts (never staged on the host)."""
    from xaynet_tpu.core.mask.object import LazyWireMaskVect, MaskObject
    from xaynet_tpu.server.aggregation import StagedAggregator

    n, k = 103, 3
    objs = _masked_updates(n, k, seed=23, cfg=cfg)
    host = StagedAggregator(cfg.pair(), n, device=False, batch_size=4)
    arrival0, flush0 = _rows_staged()
    if route in ("fold_partial", "mesh2_partial"):
        dev = _staged(n, cfg=cfg, shard_parallel=route == "mesh2_partial")
        assert dev._stream._n_shards == (2 if route == "mesh2_partial" else 1)
        for s in (host, dev):
            s.fold_partial(objs[0], 2)
        assert _rows_staged() == (arrival0, flush0 + 1)
        want_models = 2
    else:
        dev = _staged(n, cfg=cfg)
        for obj in objs:
            host.aggregate(obj)
            raw = np.array(vect_element_block(serialize_mask_vect(obj.vect)))
            lazy = MaskObject(LazyWireMaskVect(cfg, raw, n), obj.unit)
            dev.validate_aggregation(lazy)
            dev.aggregate(lazy)
        assert not dev._open
        dev.drain()
        assert _rows_staged() == (arrival0, flush0)
        want_models = k
    a, b = host.finalize(), dev.finalize()
    assert a.nb_models == b.nb_models == want_models
    assert a.object == b.object


def test_stage_returns_without_waiting_when_every_ring_buffer_is_busy():
    n = 64
    objs = _masked_updates(n, 2, seed=24)
    dev = _staged(n, mesh=_mesh1(), staging_buffers=2)
    ring = dev._stream._ring(dev._stream._host_kind)
    busy = [ring.acquire(), ring.acquire()]
    t0 = time.monotonic()
    for obj in objs:
        dev.stage(obj)
    assert time.monotonic() - t0 < 1.0
    time.sleep(0.2)
    writes = dev._open[0].writes
    assert len(writes) == 2 and not any(w.done() for w in writes)
    ring.release(busy.pop())  # the first write of the batch takes it
    dev.drain()
    ring.release(busy.pop())
    assert dev.finalize().object == _oracle(n, objs).object


@pytest.mark.parametrize("exit_", ["close", "abandon"])
def test_open_batch_lease_is_released_on(exit_):
    import gc

    from xaynet_tpu.tenancy.pool import get_pool

    n = 64
    tenant = f"open-batch-{exit_}"
    dev = _staged(n, mesh=_mesh1(), tenant=tenant)
    depth0 = STAGING_DEPTH.value
    for obj in _masked_updates(n, 2, seed=26):
        dev.stage(obj)
    _await_writes(dev)
    assert STAGING_DEPTH.value == depth0 + 1
    assert not get_pool().balanced(tenant)
    if exit_ == "close":
        dev._stream.close()
    else:
        del dev
        gc.collect()
    assert STAGING_DEPTH.value == depth0
    assert get_pool().balanced(tenant)


# -- staging at arrival on a mesh (ISSUE 40) ---------------------------------
#
# A shard-parallel pipeline takes the same route: an open batch holds one
# buffer of every shard's ring, and an update's column range [lo, hi) of a
# shard is written into that shard's slot as it arrives. The aggregate has to
# be the one-device route's and the plain integer sum, bit for bit.


def _mesh(n_dev):
    return make_mesh(jax.devices()[:n_dev])


def _as_ints(wire) -> list[int]:
    return [sum(limb << (32 * i) for i, limb in enumerate(row))
            for row in np.asarray(wire).tolist()]


def _int_sum(objs, cfg) -> list[int]:
    """The plain reference: every element summed in Python integers modulo
    the group order, with nothing of the program's limb or fold code."""
    total = [0] * len(objs[0].vect)
    for obj in objs:
        total = [(a + b) % cfg.order for a, b in zip(total, _as_ints(obj.vect.data))]
    return total


@WIDTHS
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
@pytest.mark.parametrize("n", [96, 103, 9], ids=["divides", "padded", "all-pad-shards"])
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_mesh_arrival_staging_equals_one_device_and_the_integer_sum(n_dev, n, packed, cfg):
    """A full batch of 4 and a batch of 3 closed by drain(), at a length the
    devices divide, one they do not (pad columns in the last shard) and one
    shorter than the mesh's padding (whole shards of pad columns)."""
    total = 7
    objs = _masked_updates(n, total, seed=40, cfg=cfg)
    one = _staged(n, cfg=cfg, mesh=_mesh1(), packed_staging=packed)
    mesh = _staged(n, cfg=cfg, mesh=_mesh(n_dev), shard_parallel=True, packed_staging=packed)
    stream = mesh._stream
    assert stream._n_shards == n_dev and stream._packed is packed
    assert stream._slices[-1][1] == stream.agg.padded_length == -(-n // n_dev) * n_dev
    arrival0, flush0 = _rows_staged()
    depth0 = STAGING_DEPTH.value
    for dev in (one, mesh):
        for obj in objs:
            dev.validate_aggregation(obj)
            dev.aggregate(obj)
        dev.drain()
    # one row an update on either route, not one a shard
    assert _rows_staged() == (arrival0 + 2 * total, flush0)
    assert STAGING_DEPTH.value == depth0
    a, b = one.finalize(), mesh.finalize()
    assert a.nb_models == b.nb_models == total
    assert np.array_equal(a.object.vect.data, b.object.vect.data)
    assert _as_ints(b.object.vect.data) == _int_sum(objs, cfg)


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-device", "mesh4"])
def test_rows_land_in_arrival_order_whatever_order_the_pool_finishes_in(n_dev):
    """Slots written out of order and from several threads, into dirty
    buffers: a row's columns lie in its slot of every shard's buffer."""
    from xaynet_tpu.ops import limbs as host_limbs

    n, k = 103, 8
    objs = _masked_updates(n, k, seed=41)
    dev = _staged(n, batch_size=k, mesh=_mesh(n_dev), shard_parallel=True, staging_buffers=2)
    stream = dev._stream
    for d in range(n_dev):  # every shard's first buffer is dirty
        ring = stream._ring(stream._host_kind, d)
        dirty = ring.acquire()
        dirty.fill(0xFF)
        ring.release(dirty)
    real, finished, writers = stream.stage_row, [], set()

    def late_first(bufs, i, wire):
        time.sleep(0.03 * (k - i))  # slot 0 lands last
        real(bufs, i, wire)
        finished.append(i)
        writers.add(threading.get_ident())

    stream.stage_row = late_first
    for obj in objs:
        dev.stage(obj)
    _await_writes(dev)
    assert finished != sorted(finished) and len(writers) > 1
    bufs = dev._open[0].bufs
    assert len(bufs) == n_dev
    for i, obj in enumerate(objs):
        want = host_limbs.pack_wire(obj.vect.data[None], stream.agg.packed_width)[0]
        got = np.concatenate([buf[i] for buf in bufs], axis=-1)
        assert np.array_equal(got[:, :n], want)
        assert not got[:, n:].any()  # the dirty buffer's pad columns
    dev.drain()
    assert dev.finalize().object == _oracle(n, objs).object


@WIDTHS
def test_mesh_round_of_three_batches_takes_each_shards_buffer_again(cfg):
    """Three batches of 4 through rings of 2 on four shards: every shard's
    ring is asked three times and leases no more than two buffers; a batch
    counts once, when its last shard has folded."""
    from xaynet_tpu.parallel.streaming import (
        COMMIT_SECONDS, RING_WAIT_SECONDS, SHARD_STAGING_DEPTH)

    n, k, n_dev, batches = 103, 4, 4, 3
    objs = _masked_updates(n, k * batches, seed=42, cfg=cfg)
    dev = _staged(n, batch_size=k, cfg=cfg, mesh=_mesh(n_dev), shard_parallel=True,
                  staging_buffers=2)
    stream = dev._stream
    hows = ("free", "leased", "waited")
    ring0 = {how: RING_WAIT_SECONDS.labels(how=how).count for how in hows}
    commits0, folded0 = COMMIT_SECONDS.count, BATCHES_TOTAL.labels(stage="folded").value
    gate, real = threading.Event(), stream._fold_shard_item

    def last_shard_waits(job, d, payload):
        if d == n_dev - 1:
            assert gate.wait(30)
        real(job, d, payload)

    stream._fold_shard_item = last_shard_waits
    for obj in objs[:k]:
        dev.validate_aggregation(obj)
        dev.aggregate(obj)  # the 4th closes batch 1: one item a shard worker
    deadline = time.monotonic() + 30
    while sum(q.unfinished_tasks for q in stream._shard_queues) > 1:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    # three shards have folded their slice of batch 1: it does not count yet
    assert dev._device.nb_models == 0 and stream.counted_models() == k
    assert BATCHES_TOTAL.labels(stage="folded").value == folded0
    gate.set()
    for obj in objs[k:]:
        dev.validate_aggregation(obj)
        dev.aggregate(obj)
    dev.drain()
    assert dev._device.nb_models == k * batches
    assert BATCHES_TOTAL.labels(stage="folded").value == folded0 + batches
    assert COMMIT_SECONDS.count == commits0 + batches
    ring = {how: RING_WAIT_SECONDS.labels(how=how).count - ring0[how] for how in hows}
    assert sum(ring.values()) == batches * n_dev, ring
    assert n_dev <= ring["leased"] <= 2 * n_dev, ring  # a ring of 2 a shard
    for d in range(n_dev):
        assert SHARD_STAGING_DEPTH.labels(shard=str(d)).value == 0
        assert not stream._ring(stream._host_kind, d)._inflight
    got, want = dev.finalize(), _oracle(n, objs, cfg)
    assert got.nb_models == want.nb_models == k * batches
    assert got.object == want.object


@pytest.mark.parametrize("route", ["row", "batch"])
def test_mesh_shards_copy_host_to_device_one_at_a_time(monkeypatch, route):
    """No two host-to-device copies overlap (``shards.H2D_GATE``), and each
    span says whose shard's and by which route. row: a batch staged at
    arrival, every row's slice of every shard copied by the one copier as
    its slot is written. batch: a batch staged inside one call, where the
    shards' fold workers start together and each copies its slice whole."""
    from xaynet_tpu.telemetry import tracing

    n, k, n_dev = 103, 4, 4
    objs = _masked_updates(n, k, seed=44)
    dev = _staged(n, batch_size=k, mesh=_mesh(n_dev), shard_parallel=True)
    real = jax.device_put
    copiers = {"row": "xn-h2d", "batch": "xn-stream-fold-"}

    def slow_put(x, device=None, **kw):
        if threading.current_thread().name.startswith(copiers[route]):
            time.sleep(0.05)  # a copy long enough to overlap another
        return real(x, device, **kw)

    monkeypatch.setattr(jax, "device_put", slow_put)
    t0 = time.monotonic()
    if route == "row":
        for obj in objs:
            dev.validate_aggregation(obj)
            dev.aggregate(obj)
    else:
        dev._stream.submit_batch(np.stack([obj.vect.data for obj in objs]))
    dev.drain()
    spans = [s for s in tracing.get_tracer().ring_spans()
             if s.name == "stream.h2d" and s.start >= t0]
    assert {s.attrs["route"] for s in spans} == {route}
    copies = k if route == "row" else 1  # of each shard's slice
    assert sorted(s.attrs["shard"] for s in spans) == sorted(list(range(n_dev)) * copies)
    if route == "row":
        assert sorted(s.attrs["slot"] for s in spans) == sorted(list(range(k)) * n_dev)
    spans.sort(key=lambda s: s.start)
    for earlier, later in zip(spans, spans[1:]):
        assert earlier.duration >= 0.05
        assert later.start >= earlier.start + earlier.duration
    # the vector alone: the pipeline's own entry point stages no unit
    assert dev.finalize().object.vect == _oracle(n, objs).object.vect


@pytest.mark.parametrize("failing_slot", [0, 2], ids=["first-write", "last-write"])
@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-device", "mesh4"])
def test_failed_slot_write_raises_at_flush_and_gives_back_every_buffer(n_dev, failing_slot):
    n = 64
    objs = _masked_updates(n, 5, seed=43)
    dev = _staged(n, mesh=_mesh(n_dev), shard_parallel=True)
    stream = dev._stream
    depth0 = STAGING_DEPTH.value
    failed0 = BATCHES_TOTAL.labels(stage="failed").value
    boom = RuntimeError("slot write died (stand-in)")
    real = stream.stage_row

    def failing(bufs, i, wire):
        if i == failing_slot:
            raise boom
        real(bufs, i, wire)

    stream.stage_row = failing
    for obj in objs[:3]:
        dev.stage(obj)
    with pytest.raises(RuntimeError) as err:
        dev.flush()
    assert err.value is boom
    assert STAGING_DEPTH.value == depth0
    assert all(not stream._ring(stream._host_kind, d)._inflight for d in range(n_dev))
    assert dev.pending == 0 and dev.nb_models == 0
    # nothing is poisoned: what is staged next folds on every shard
    stream.stage_row = real
    for obj in objs[3:]:
        dev.aggregate(obj)
    dev.drain()
    assert STAGING_DEPTH.value == depth0
    assert BATCHES_TOTAL.labels(stage="failed").value == failed0
    assert not stream.degraded
    assert dev.finalize().object == _oracle(n, objs[3:]).object


def test_staging_ring_grows_on_demand_up_to_size_then_blocks():
    import queue

    from xaynet_tpu.parallel.streaming import _StagingRing
    from xaynet_tpu.tenancy.pool import get_pool

    pool, tenant = get_pool(), "lazy-ring"
    ring = _StagingRing(2, (4, 2, 64), np.uint32, pool=pool, tenant=tenant)
    assert len(pool.outstanding(tenant)) == 0  # nothing leased up front
    a = ring.acquire()
    assert len(pool.outstanding(tenant)) == 1
    ring.release(a)
    a = ring.acquire()  # a free buffer is reused before the ring grows
    assert len(pool.outstanding(tenant)) == 1
    b = ring.acquire()
    assert len(pool.outstanding(tenant)) == 2
    with pytest.raises(queue.Empty):
        ring.acquire(timeout=0.05)  # at size: blocks, as the eager ring did
    assert len(pool.outstanding(tenant)) == 2
    ring.release(b)
    assert ring.acquire(timeout=1.0) is b
    ring.close()
    assert pool.balanced(tenant)
    del a


def test_staging_ring_stays_within_size_under_concurrent_acquires():
    """More threads than cores take and return buffers of a ring of 3 under
    a short switch interval: never more than 3 leases, every buffer has
    one holder at a time, and the depth gauge comes back."""
    import sys

    from xaynet_tpu.parallel.streaming import _StagingRing
    from xaynet_tpu.tenancy.pool import get_pool

    pool, tenant = get_pool(), "ring-stress"
    ring = _StagingRing(3, (2, 2, 32), np.uint32, pool=pool, tenant=tenant)
    depth0 = STAGING_DEPTH.value
    held, most, errors = set(), [0], []
    guard = threading.Lock()

    def churn():
        try:
            for _ in range(150):
                buf = ring.acquire(timeout=30)
                with guard:
                    assert id(buf) not in held
                    held.add(id(buf))
                    most[0] = max(most[0], len(pool.outstanding(tenant)))
                with guard:
                    held.discard(id(buf))
                ring.release(buf)
        except BaseException as e:  # surfaced through the assertion below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=churn, daemon=True) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors
    assert most[0] <= 3 and len(pool.outstanding(tenant)) <= 3
    assert STAGING_DEPTH.value == depth0
    ring.close()
    assert pool.balanced(tenant)


# -- a batch staged at arrival goes to the device row by row (ISSUE 48) ------
#
# As a slot write ends the pipeline's copier puts that row on the device and
# places it in the batch's device batch, so the flush folds rows that are
# resident and waits for the copies still outstanding. The aggregate has to
# be the one-copy route's and the host reference's, bit for bit, and the host
# ring buffer stays the source of truth until the fold has returned.


def _h2d_counters():
    from xaynet_tpu.parallel.streaming import H2D_BYTES, H2D_EARLY_BYTES

    return H2D_EARLY_BYTES.value, H2D_BYTES.value


def _pipeline(n, cfg, n_dev, packed=True, max_batch=4, **kw):
    agg = ShardedAggregator(cfg, n, mesh=make_mesh(jax.devices()[:n_dev]), kernel="xla")
    return agg, StreamingAggregator(agg, max_batch=max_batch, packed=packed, **kw)


def _stage_rows(stream, stacks, slots):
    """Open a batch and write ``stacks[i]`` into slot ``i`` for ``i`` in
    ``slots``, in that order; the buffers."""
    bufs = stream.open_batch()
    for i in slots:
        stream.stage_row(bufs, i, stacks[i])
    return bufs


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
@pytest.mark.parametrize("k", [4, 3], ids=["full", "partial"])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
@WIDTHS
def test_rows_copied_at_arrival_fold_bit_equal_to_the_one_copy_route(cfg, packed, k, n_dev):
    """One batch through ``stage_row`` (rows finishing out of slot order; the
    row route), the same rows through ``submit_batch`` (one copy at the
    fold) and the host reference: one aggregate."""
    from xaynet_tpu.parallel.aggregator import fold_kernel_report

    n = 103
    stacks, _, host = _updates(n, k, seed=48, cfg=cfg)
    agg, stream = _pipeline(n, cfg, n_dev, packed)
    early0, all0 = _h2d_counters()
    bufs = _stage_rows(stream, stacks, [i for i in (2, 0, 3, 1) if i < k])
    assert stream.wait_rows(bufs) == k
    stream.submit_staged(bufs, k)
    stream.drain()
    assert fold_kernel_report()["h2d_route"] == "row"
    early, copied = (now - was for now, was in zip(_h2d_counters(), (early0, all0)))
    assert early == copied == sum(buf[:k].nbytes for buf in bufs)

    ref, one_copy = _pipeline(n, cfg, n_dev, packed)
    one_copy.submit_batch(np.stack(stacks))
    one_copy.drain()
    assert fold_kernel_report()["h2d_route"] == "batch"
    assert agg.nb_models == ref.nb_models == k
    assert np.array_equal(agg.snapshot(), ref.snapshot())
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    stream.close()
    one_copy.close()


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-device", "mesh4"])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "unpacked"])
def test_rows_of_a_batch_are_not_read_after_its_buffer_is_lent_again(packed, n_dev):
    """Five batches through a ring of the shipped size, rows finishing out of
    slot order and no drain between the batches, so that every buffer is
    written again while earlier batches are still in flight. On the CPU
    backend a ``device_put`` may alias the slot's memory: a row read after
    its buffer went to a later batch would show as a wrong sum."""
    n, k, batches = 103, 4, 5
    stacks, _, host = _updates(n, k * batches, seed=49)
    agg, stream = _pipeline(n, CFG, n_dev, packed, staging_buffers=3)
    orders = [(3, 1, 0, 2), (0, 2, 1, 3), (2, 3, 1, 0), (1, 0, 3, 2), (3, 2, 1, 0)]
    leased = set()
    for b, order in enumerate(orders):
        bufs = _stage_rows(stream, stacks[b * k:(b + 1) * k], order)
        leased.add(id(bufs[0]))
        stream.submit_staged(bufs, k)  # its last copies may still be running
    stream.drain()
    assert len(leased) <= 3  # buffers were taken again
    assert agg.nb_models == k * batches
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    stream.close()


@pytest.mark.parametrize("second", ["degrades", "poisons"])
@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-device", "mesh4"])
def test_failed_row_copy_falls_back_to_one_copy_of_the_batch(n_dev, second):
    """A row's copy raises (fault site ``streaming.h2d_row``): the batch is
    folded once, from the ring buffer, by the ladder's synchronous retry;
    the count is exact, the pipeline reads degraded and not poisoned, and
    what comes after is copied a batch at a time. Where the retry fails too
    the pipeline is poisoned and names the batch."""
    from xaynet_tpu.parallel.aggregator import fold_kernel_report
    from xaynet_tpu.parallel.shards import ShardPlan
    from xaynet_tpu.parallel.streaming import DEGRADATIONS
    from xaynet_tpu.resilience import FaultPlan, clear_plan, install_plan

    n, k = 103, 4
    stacks, _, host = _updates(n, 2 * k, seed=50)
    agg, stream = _pipeline(n, CFG, n_dev)
    degraded0 = DEGRADATIONS.value
    real_packed = ShardPlan.fold_shard_packed

    def boom(*_a):
        raise RuntimeError("fold died (stand-in)")

    install_plan(FaultPlan.parse("streaming.h2d_row:error,nth=2"))
    try:
        bufs = _stage_rows(stream, stacks, range(k))
        assert stream.wait_rows(bufs) == 1  # the second copy failed, the rest were skipped
        if second == "poisons":
            agg.kernel_used = "xla"
            agg._packed_fold_fn = boom
            ShardPlan.fold_shard_packed = boom
        stream.submit_staged(bufs, k)
        if second == "poisons":
            with pytest.raises(StreamingError, match=r"batch 1.*fold died"):
                stream.drain()
            assert stream.in_flight_models == 0
            stream.close()
            return
        stream.drain()
    finally:
        clear_plan()
        ShardPlan.fold_shard_packed = real_packed
    assert stream.degraded and stream._poisoned() is None
    assert DEGRADATIONS.value == degraded0 + 1
    assert agg.nb_models == k and fold_kernel_report()["h2d_route"] == "batch"
    # degraded: rows are no longer copied as they arrive
    early0, all0 = _h2d_counters()
    bufs = _stage_rows(stream, stacks[k:], range(k))
    assert stream.wait_rows(bufs) == 0
    stream.submit_staged(bufs, k)
    stream.drain()
    early, copied = (now - was for now, was in zip(_h2d_counters(), (early0, all0)))
    assert (early, copied) == (0, sum(buf.nbytes for buf in bufs))
    assert agg.nb_models == 2 * k
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    stream.close()


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-device", "mesh4"])
def test_released_batch_leaves_no_row_on_the_device(n_dev):
    """``release_batch`` (a failed slot write) drops the rows already copied:
    the device's live arrays are what they were, the ring is whole, and the
    next batch folds."""
    import gc

    n, k = 103, 4
    stacks, _, host = _updates(n, k, seed=51)
    agg, stream = _pipeline(n, CFG, n_dev)
    # a first batch through, so that every executable and constant exists
    bufs = _stage_rows(stream, stacks, range(k))
    stream.submit_staged(bufs, k)
    stream.drain()
    gc.collect()
    live0, depth0 = len(jax.live_arrays()), STAGING_DEPTH.value
    bufs = _stage_rows(stream, stacks, range(3))
    assert stream.wait_rows(bufs) == 3
    assert len(jax.live_arrays()) > live0  # a device batch a shard
    stream.release_batch(bufs)
    del bufs
    gc.collect()
    assert len(jax.live_arrays()) <= live0  # nothing of the batch is left
    assert STAGING_DEPTH.value == depth0
    assert not any(ring._inflight for ring in stream._rings.values())
    bufs = _stage_rows(stream, stacks, range(k))
    stream.submit_staged(bufs, k)
    stream.drain()
    assert agg.nb_models == 2 * k
    twice = Aggregation(CFG.pair(), n)
    for _ in range(2):
        twice.aggregate(host.object)
    assert np.array_equal(agg.snapshot(), twice.object.vect.data)
    stream.close()


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-device", "mesh4"])
def test_h2d_counters_tell_rows_copied_before_the_submit_from_the_rest(n_dev):
    """``h2d_early_bytes_total`` moves by the rows whose copy had ended when
    their batch was submitted, ``h2d_bytes_total`` by all of them; a batch
    staged inside one call (``submit_batch``) moves the second alone."""
    from xaynet_tpu.parallel.shards import H2D_GATE

    n, k = 103, 4
    stacks, _, host = _updates(n, 2 * k, seed=52)
    agg, stream = _pipeline(n, CFG, n_dev)
    early0, all0 = _h2d_counters()
    bufs = _stage_rows(stream, stacks, (1, 0))
    assert stream.wait_rows(bufs) == 2
    row = sum(buf[0].nbytes for buf in bufs)
    with H2D_GATE:  # the link is busy: the next two rows' copies wait
        for i in (3, 2):
            stream.stage_row(bufs, i, stacks[i])
        stream.submit_staged(bufs, k)
        assert _h2d_counters() == (early0 + 2 * row, all0 + 2 * row)
    stream.drain()
    assert _h2d_counters() == (early0 + 2 * row, all0 + 4 * row)
    stream.submit_batch(np.stack(stacks[k:]))
    stream.drain()
    assert _h2d_counters() == (early0 + 2 * row, all0 + 8 * row)
    assert agg.nb_models == 2 * k
    assert np.array_equal(agg.snapshot(), host.object.vect.data)
    stream.close()
