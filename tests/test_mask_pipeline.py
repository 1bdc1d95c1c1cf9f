"""The fused mask pipeline (ISSUE 11 / DESIGN §15).

Golden-vector acceptance: every production ``sum_masks`` route — the
in-graph batched derive streamed through the shard pipeline, the fused
Pallas keystream→reject→fold kernel (interpret), the threaded native
sampler, and the legacy host-chunked path — is BYTE-identical to folding
the scalar ``MaskSeed.derive_mask`` reference per seed, across all three
finite-group families, including deliberately tiny chunk budgets that
force the multi-trip rejection ``while_loop`` and the count-th-accept
byte-cursor handoff. Plus the coordinator side: ``finalize_inplace``'s
``DeviceAggregation`` unmasks per-shard slices in place, byte-identical
to the gathered host path.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from xaynet_tpu.core.crypto.prng import StreamSampler
from xaynet_tpu.core.mask.config import (
    BoundType,
    DataType,
    GroupType,
    MaskConfig,
    ModelType,
)
from xaynet_tpu.core.mask.masking import Aggregation, Masker
from xaynet_tpu.core.mask.model import Scalar
from xaynet_tpu.core.mask.seed import MaskSeed
from xaynet_tpu.ops import fold_pallas, limbs as host_limbs, masking_jax
from xaynet_tpu.ops.fold_jax import planar_to_wire

CONFIGS = [
    MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3),
    MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3),
    MaskConfig(GroupType.POWER2, DataType.F32, BoundType.B0, ModelType.M3),
]


def _reference_sum(seeds: list[bytes], n: int, pair) -> Aggregation:
    agg = Aggregation(pair, n)
    for s in seeds:
        agg.aggregate(MaskSeed(s).derive_mask(n, pair))
    return agg


def _seed_words_offsets(seeds: list[bytes], pair):
    kws, offs = [], []
    for s in seeds:
        sampler = StreamSampler(s)
        sampler.draw_limbs(1, pair.unit.order)
        offs.append(sampler.consumed_bytes)
        kws.append(np.frombuffer(s, dtype="<u4"))
    return np.stack(kws), np.asarray(offs, np.int32)


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.group_type.name)
def test_fused_kernel_golden_vs_scalar_derive(cfg):
    """mask_fold_planar_pallas == sum of MaskSeed.derive_mask vects, and
    the end cursors equal the scalar sampler's consumed-bytes handoff."""
    pair = cfg.pair()
    n = 53
    seeds = [bytes([i, i ^ 0x3C]) * 16 for i in range(1, 6)]
    ref = _reference_sum(seeds, n, pair)

    kws, offs = _seed_words_offsets(seeds, pair)
    L = host_limbs.n_limbs_for_order(pair.vect.order)
    acc = jnp.zeros((L, n), jnp.uint32)
    acc, ends = fold_pallas.mask_fold_planar_pallas(
        acc, jnp.asarray(kws), offs, n, pair.vect.order, interpret=True
    )
    assert np.array_equal(planar_to_wire(acc), ref.object.vect.data)

    # count-th-accept cursor handoff: the kernel's end cursor must equal
    # the scalar sampler's cursor after the SAME unit + n-vector draws
    for seed, end in zip(seeds, np.asarray(ends)):
        sampler = StreamSampler(seed)
        sampler.draw_limbs(1, pair.unit.order)
        sampler.draw_limbs(n, pair.vect.order)
        assert sampler.consumed_bytes == int(end)


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.group_type.name)
def test_fused_kernel_multi_trip_tiny_chunks(cfg):
    """A chunk budget far below the element count forces the multi-trip
    rejection while_loop INSIDE the kernel; result and cursors must not
    depend on the chunking."""
    pair = cfg.pair()
    n = 41
    seeds = [bytes([9, i]) * 16 for i in range(1, 4)]
    ref = _reference_sum(seeds, n, pair)
    kws, offs = _seed_words_offsets(seeds, pair)
    L = host_limbs.n_limbs_for_order(pair.vect.order)

    acc_big = jnp.zeros((L, n), jnp.uint32)
    acc_big, ends_big = fold_pallas.mask_fold_planar_pallas(
        acc_big, jnp.asarray(kws), offs, n, pair.vect.order, interpret=True
    )
    acc_tiny = jnp.zeros((L, n), jnp.uint32)
    acc_tiny, ends_tiny = fold_pallas.mask_fold_planar_pallas(
        acc_tiny,
        jnp.asarray(kws),
        offs,
        n,
        pair.vect.order,
        chunk_candidates=7,
        interpret=True,
    )
    assert np.array_equal(planar_to_wire(acc_tiny), ref.object.vect.data)
    assert np.array_equal(np.asarray(ends_big), np.asarray(ends_tiny))


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.group_type.name)
# host-chunked (the legacy path) is pinned by the slow-marked
# tests/test_jax_kernels.py sum_masks tests — re-running it here would pay
# its ~25s-per-shape unrolled-keystream XLA compile three more times
@pytest.mark.parametrize("kernel", ["batch", "fused-pallas-interpret", "host-threaded"])
def test_sum_masks_routes_byte_identical(cfg, kernel):
    """Every production route of the promoted entry point returns the
    exact (unit, vect) the scalar reference computes."""
    pair = cfg.pair()
    n = 37
    seeds = [bytes([i, i ^ 0x5A]) * 16 for i in range(1, 11)]
    ref = _reference_sum(seeds, n, pair)
    unit, vect = masking_jax.sum_masks(seeds, n, pair, seed_batch=4, kernel=kernel)
    assert np.array_equal(unit, ref.object.unit.data)
    assert np.array_equal(np.asarray(vect), ref.object.vect.data)
    assert masking_jax.resolved_mask_kernel() == kernel


def test_sum_masks_fused_tiny_chunks_multi_trip():
    """The fused ROUTE (not just the kernel) with a tiny chunk budget:
    multi-trip derivation composed with the group loop stays exact."""
    pair = CONFIGS[0].pair()
    n = 29
    seeds = [bytes([i, 0x77]) * 16 for i in range(1, 8)]
    ref = _reference_sum(seeds, n, pair)
    unit, vect = masking_jax._sum_masks_fused(
        seeds, n, pair, seed_batch=3, interpret=True, chunk_candidates=5
    )
    assert np.array_equal(unit, ref.object.unit.data)
    assert np.array_equal(np.asarray(vect), ref.object.vect.data)


def test_sum_masks_batch_on_mesh_matches_reference():
    """The batch route streaming mask planes through the PR-7 shard
    pipeline on the full device mesh (mesh=8 under the CI virtual-device
    flags; degenerates to mesh=1 on a single device)."""
    from xaynet_tpu.parallel.mesh import make_mesh

    pair = CONFIGS[0].pair()
    n = 43  # deliberately not divisible by the mesh size
    seeds = [bytes([i, 0x11]) * 16 for i in range(1, 10)]
    ref = _reference_sum(seeds, n, pair)
    unit, vect = masking_jax.sum_masks(
        seeds, n, pair, seed_batch=4, kernel="batch", mesh=make_mesh()
    )
    assert np.array_equal(unit, ref.object.unit.data)
    assert np.array_equal(np.asarray(vect), ref.object.vect.data)


def test_auto_calibration_memoizes_and_reports():
    """auto resolves once per (backend, shape) and the verdict is reused;
    the resolved route is observable for the bench."""
    pair = CONFIGS[0].pair()
    n = 31
    seeds = [bytes([i, 0x42]) * 16 for i in range(1, 6)]
    first = masking_jax.calibrate_mask_kernel(seeds, n, pair, seed_batch=4)
    assert first in ("batch", "fused-pallas-interpret", "fused-pallas", "host-threaded")
    unit, vect = masking_jax.sum_masks(seeds, n, pair, seed_batch=4, kernel="auto")
    assert masking_jax.resolved_mask_kernel() == first
    ref = _reference_sum(seeds, n, pair)
    assert np.array_equal(np.asarray(vect), ref.object.vect.data)


def test_compile_cache_gauge_bounded_and_published():
    from xaynet_tpu.telemetry.registry import get_registry

    pair = CONFIGS[0].pair()
    seeds = [bytes([i, 0x21]) * 16 for i in range(1, 4)]
    masking_jax.sum_masks(seeds, 19, pair, kernel="batch")
    reg = get_registry()
    value = reg.sample_value("xaynet_mask_derive_compile_cache")
    assert value is not None and 1 <= value <= 3 * masking_jax._COMPILE_CACHE_MAX
    # the lru caches are bounded: maxsize is the declared constant
    assert masking_jax._mask_batch_fn.cache_info().maxsize == masking_jax._COMPILE_CACHE_MAX
    assert masking_jax._unit_offsets_fn.cache_info().maxsize == masking_jax._COMPILE_CACHE_MAX


def test_pinned_mask_kernel_engages_promoted_path(monkeypatch):
    """PetSettings.mask_kernel's contract: a pinned route ENGAGES the
    routed pipeline at any model size; only an explicit device_sum2=False
    overrides the pin back to the legacy host path."""
    import xaynet_tpu.ops.masking_jax as mj
    from xaynet_tpu.sdk.state_machine import StateMachine

    sm = StateMachine.__new__(StateMachine)
    sm.device_sum2 = None
    sm.device_sum2_strict = True
    sm.mask_kernel = "host-threaded"
    seeds = [MaskSeed(bytes([i]) * 32) for i in range(1, 4)]
    calls = []
    real = mj.sum_masks

    def spy(s, n, c, **kw):
        calls.append(kw.get("kernel"))
        return real(s, n, c, **kw)

    monkeypatch.setattr(mj, "sum_masks", spy)
    pair = CONFIGS[0].pair()
    obj = StateMachine._aggregate_masks(sm, seeds, 16, pair)
    assert calls == ["host-threaded"]
    sm.device_sum2 = False  # explicit False wins over the pin
    calls.clear()
    host_obj = StateMachine._aggregate_masks(sm, seeds, 16, pair)
    assert not calls
    assert obj == host_obj  # both paths byte-identical either way


def test_finalize_inplace_device_view_unmasks_per_shard():
    """DeviceAggregation: validation without gathering, per-shard in-place
    subtract byte-identical to the gathered host finalize()."""
    from xaynet_tpu.core.mask.masking import UnmaskingError
    from xaynet_tpu.server.aggregation import DeviceAggregation, StagedAggregator

    cfg = CONFIGS[0]
    n, k = 103, 6  # not divisible by the 8-device mesh
    rng = np.random.default_rng(7)
    host = StagedAggregator(cfg.pair(), n, device=False)
    dev = StagedAggregator(cfg.pair(), n, device=True, batch_size=4)
    mask_agg = Aggregation(cfg.pair(), n)
    for _ in range(k):
        w = rng.uniform(-1, 1, n).astype(np.float32)
        seed, masked = Masker(cfg.pair()).mask(Scalar(1, k), w)
        mask_agg.aggregate(MaskSeed(seed.as_bytes()).derive_mask(n, cfg.pair()))
        for a in (host, dev):
            a.validate_aggregation(masked)
            a.aggregate(masked)
    host_agg = host.finalize_inplace()
    dev_view = dev.finalize_inplace()
    assert isinstance(dev_view, DeviceAggregation)
    assert dev_view.nb_models == host_agg.nb_models == k
    assert len(dev_view) == n and dev_view.config == cfg.pair()

    mask = mask_agg.object
    dev_view.validate_unmasking(mask)
    got = dev_view.unmask_array(mask)
    want = host_agg.unmask_array(mask)
    assert got.tobytes() == want.tobytes()
    # the gathered-object escape hatch still works (checkpoints/tests)
    assert np.array_equal(dev_view.object.vect.data, host_agg.object.vect.data)
    # validation failures surface without touching the accumulator
    empty = StagedAggregator(cfg.pair(), n, device=True).finalize_inplace()
    with pytest.raises(UnmaskingError, match="NoModel"):
        empty.validate_unmasking(mask)


def test_sum2_hands_unmask_the_open_pipeline_and_the_unmask_settles_it():
    """The shipped hand-off: ``Sum2Phase.next`` gives Unmask the in-place
    view with the streaming pipeline still open (the drain barrier is the
    unmask's), the view's unmask is exact, and it leaves the pipeline
    drained, closed and the model count pinned."""
    import asyncio

    from xaynet_tpu.server.aggregation import DeviceAggregation, StagedAggregator
    from xaynet_tpu.server.phases.sum2 import Sum2Phase

    cfg = CONFIGS[0]
    n, k = 24, 3
    dev = StagedAggregator(cfg.pair(), n, device=True, batch_size=2)
    mask_agg = Aggregation(cfg.pair(), n)
    rng = np.random.default_rng(3)
    expected = np.zeros(n)
    for _ in range(k):
        w = rng.uniform(-1, 1, n).astype(np.float32)
        expected += w.astype(np.float64) / k
        seed, masked = Masker(cfg.pair()).mask(Scalar(1, k), w)
        mask_agg.aggregate(MaskSeed(seed.as_bytes()).derive_mask(n, cfg.pair()))
        dev.aggregate(masked)

    phase = Sum2Phase.__new__(Sum2Phase)
    phase.aggregator = dev
    phase._base = None  # no round journal: next() must skip the unmask entry
    phase._votes = []
    phase.shared = object()  # next() reads no setting

    view = asyncio.run(Sum2Phase.next(phase)).model_agg
    assert isinstance(view, DeviceAggregation)
    # k = 3 at a batch of 2: one update is still staged when Sum2 ends, so
    # the hand-off has to submit it and leave the barrier to the unmask
    assert view._stream is dev._stream and not dev._stream._closed
    got = view.unmask_array(mask_agg.object)
    np.testing.assert_allclose(got, expected, atol=1e-9)
    assert view._stream is None and dev._stream._closed
    assert view.nb_models == k
