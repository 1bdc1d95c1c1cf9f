"""Packed-limb reduction (docs/DESIGN.md §17): the byte-planar codec, the
packed staging pipeline, the reduce-scatter accumulator, and pre-mask
quantization.

The properties everything rests on:

- the packed planar codec is a LOSSLESS re-representation for validated
  group elements (``element < order <= 2^(8*bpn)``) across every group
  family, including non-byte-aligned and non-limb-aligned orders;
- a packed-staging round is **byte-identical** to the unpacked control
  across mesh={1,2,8} × kernel={xla, pallas-interpret, auto} × element
  width (2 limbs / 7 bytes, 3 limbs / 10 bytes) — the fold is the same
  exact modular sum, only the staged representation changes;
- the reduce-scatter plan persists across drain windows and the per-shard
  unmask produces the exact gathered-subtract result;
- quantized configs derive protocol-consistent orders (the catalogue's
  own construction at the coarser scale), serialize wire-compatibly, and
  keep the fixed-point error inside the analytic ``nb_models/exp_shift``
  bound — the accuracy gate's foundation.
"""

from fractions import Fraction

import numpy as np
import pytest

import jax

from xaynet_tpu.core.mask.config import (
    BoundType,
    DataType,
    GroupType,
    InvalidMaskConfigError,
    MaskConfig,
    ModelType,
)
from xaynet_tpu.core.mask.masking import Aggregation, Masker
from xaynet_tpu.core.mask.model import Scalar
from xaynet_tpu.ops import limbs as host_limbs
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.parallel.streaming import BYTES_STAGED, StreamingAggregator

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
# the wide end of the bounded-f32 catalogue: 75-bit order, 3 limbs, 10 wire bytes
CFG3 = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6)
WIDTHS = pytest.mark.parametrize("cfg", [CFG, CFG3], ids=["2limb-7B", "3limb-10B"])

# one config per group family, deliberately covering non-limb-aligned
# (bpn=7: M6) and byte-boundary (Power2) widths, plus quantized orders
# for the odd widths (bpn=5, 4, 3) no catalogue entry produces
FAMILY_CONFIGS = [
    MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6),  # bpn 7
    MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3),  # bpn 6
    MaskConfig(GroupType.POWER2, DataType.F32, BoundType.B0, ModelType.M3),  # bpn 6
    MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3, 2),  # bpn 5
    MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3, 7),  # bpn 3
]


def _rand_limbs(rng, order, k, n):
    """uint32[k, L, n] planar elements uniform in [0, order)."""
    n_limb = host_limbs.n_limbs_for_order(order)
    if order <= 2**63:
        vals = rng.integers(0, order, size=k * n, dtype=np.uint64)
        wire = np.zeros((k * n, n_limb), dtype=np.uint32)
        wire[:, 0] = (vals & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        if n_limb > 1:
            wire[:, 1] = (vals >> np.uint64(32)).astype(np.uint32)
    else:  # wide synthetic orders: python ints (small test sizes only)
        vals = [int.from_bytes(rng.bytes(2 * n_limb * 4), "little") % order
                for _ in range(k * n)]
        wire = host_limbs.ints_to_limbs(vals, n_limb)
    wire = wire.reshape(k, n, n_limb)
    return np.ascontiguousarray(wire.transpose(0, 2, 1)), wire


# --- codec roundtrip property tests ----------------------------------------


@pytest.mark.parametrize("cfg", FAMILY_CONFIGS, ids=lambda c: f"{c.group_type.name}-q{c.quant}")
def test_pack_roundtrip_property(cfg):
    order = cfg.order
    bpn = host_limbs.wire_width_for(order)
    assert bpn == cfg.bytes_per_number
    n_limb = host_limbs.n_limbs_for_order(order)
    rng = np.random.default_rng(order % (2**32))
    for trial in range(3):
        k, n = int(rng.integers(1, 6)), int(rng.integers(1, 400))
        planar, wire = _rand_limbs(rng, order, k, n)
        packed = host_limbs.pack_planar(planar, bpn)
        assert packed.shape == (k, bpn, n)
        assert np.array_equal(host_limbs.unpack_planar(packed, n_limb), planar)
        # the wire pack is the same bytes
        assert np.array_equal(host_limbs.pack_wire(wire, bpn), packed)
        # strided (non-contiguous) input packs identically
        assert np.array_equal(
            host_limbs.pack_planar(wire.transpose(0, 2, 1), bpn), packed
        )


def test_pack_roundtrip_synthetic_widths():
    """Every pack width 1..12 bytes (beyond what the catalogue produces),
    including widths that don't align to limbs or bytes-of-order."""
    rng = np.random.default_rng(7)
    for bpn in range(1, 13):
        order = (1 << (8 * bpn)) - int(rng.integers(1, 250))
        n_limb = host_limbs.n_limbs_for_order(order)
        assert host_limbs.wire_width_for(order) == bpn
        planar, _ = _rand_limbs(rng, order, 3, 61)
        packed = host_limbs.pack_planar(planar, bpn)
        assert np.array_equal(host_limbs.unpack_planar(packed, n_limb), planar)


@pytest.mark.parametrize(
    "cfg",
    FAMILY_CONFIGS + [CFG3],
    ids=lambda c: f"{c.group_type.name}-{c.bound_type.name}-q{c.quant}",
)
def test_packed_device_fold_matches_planar(cfg):
    """The fused in-graph unpack + fold over byte planes equals the planar
    fold and the host reference sum, at every packed width."""
    from xaynet_tpu.ops.fold_jax import fold_packed_batch, fold_planar_batch

    order = cfg.order
    ol = host_limbs.order_limbs_for(order)
    bpn = host_limbs.wire_width_for(order)
    n_limb = host_limbs.n_limbs_for_order(order)
    rng = np.random.default_rng(5)
    planar, wire = _rand_limbs(rng, order, 4, 515)
    packed = host_limbs.pack_planar(planar, bpn)
    acc = np.zeros((n_limb, 515), dtype=np.uint32)
    ref = np.asarray(fold_planar_batch(acc.copy(), planar, order))
    out = np.asarray(fold_packed_batch(acc.copy(), packed, n_limb, order))
    assert np.array_equal(out, ref)
    assert np.array_equal(out.T, host_limbs.batch_mod_sum(wire, ol))


# --- packed staging byte-identity across mesh x kernel ---------------------


def _mesh(n):
    return make_mesh(jax.devices()[:n])


def _wire_updates(cfg, n, k, seed):
    rng = np.random.default_rng(seed)
    wire, _ = _rand_limbs(rng, cfg.order, k, n)
    return np.ascontiguousarray(wire.transpose(0, 2, 1))  # [K, n, L]


@WIDTHS
@pytest.mark.parametrize("mesh_n", (1, 2, 8))
@pytest.mark.parametrize("kernel", ("xla", "pallas-interpret", "auto"))
def test_packed_round_byte_identical_to_unpacked_control(mesh_n, kernel, cfg):
    n, k, batches = 515, 4, 2
    stack = _wire_updates(cfg, n, k, seed=mesh_n * 31 + len(kernel))

    def run(packed):
        agg = ShardedAggregator(cfg, n, mesh=_mesh(mesh_n), kernel=kernel)
        st = StreamingAggregator(
            agg, staging_buffers=2, dispatch_ahead=2, max_batch=k, packed=packed
        )
        for _ in range(batches):
            st.submit_batch(stack)
        st.drain()
        snap, nm = agg.snapshot(), agg.nb_models
        st.close()
        return snap, nm

    ref, nm_ref = run(packed=False)
    out, nm = run(packed=True)
    assert nm == nm_ref == k * batches
    assert np.array_equal(out, ref)


def test_packed_staging_counts_fewer_bytes():
    n, k = 2048, 4
    stack = _wire_updates(CFG, n, k, seed=9)
    moved = {}
    for packed in (False, True):
        label = "packed" if packed else "unpacked"
        before = BYTES_STAGED.labels(layout=label).value
        agg = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
        st = StreamingAggregator(agg, max_batch=k, packed=packed)
        st.submit_batch(stack)
        st.drain()
        st.close()
        moved[label] = BYTES_STAGED.labels(layout=label).value - before
    bpn = host_limbs.wire_width_for(CFG.order)
    n_limb = host_limbs.n_limbs_for_order(CFG.order)
    assert moved["packed"] > 0
    assert moved["packed"] / moved["unpacked"] == pytest.approx(bpn / (4 * n_limb))


def test_packed_staging_auto_skips_boundary_orders():
    """At order == 2^(32L) (bpn == 4L) packing is a no-op and auto-disables."""
    cfg = None
    for g, d, b, m in [
        (GroupType.POWER2, DataType.F32, BoundType.B4, ModelType.M12),
        (GroupType.POWER2, DataType.F64, BoundType.B0, ModelType.M9),
    ]:
        c = MaskConfig(g, d, b, m)
        if c.order == 1 << (32 * host_limbs.n_limbs_for_order(c.order)):
            cfg = c
            break
    if cfg is None:
        pytest.skip("no 2^(32L)-boundary order in the probed configs")
    agg = ShardedAggregator(cfg, 64, kernel="xla")
    assert not agg.packed_staging_usable()
    st = StreamingAggregator(agg, max_batch=2, packed=True)
    assert not st._packed  # forced on but not usable -> unpacked layout
    st.close()


# --- reduce-scatter accumulator --------------------------------------------


@pytest.mark.parametrize("kernel", ("xla", "pallas-interpret"))
def test_plan_persists_across_drain_windows(kernel):
    n, k = 1031, 3
    stack = _wire_updates(CFG, n, k, seed=17)
    agg = ShardedAggregator(CFG, n, mesh=make_mesh(), kernel=kernel)
    st = StreamingAggregator(agg, max_batch=k)
    st.submit_batch(stack)
    st.drain()
    plan1 = agg._live_plan
    assert plan1 is not None  # adopted, not reassembled away
    st.submit_batch(stack)
    st.drain()
    assert agg._live_plan is plan1  # the SAME plan served both windows
    # acc reads reassemble on demand and match the sequential oracle
    seq = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
    seq.add_batch(stack)
    seq.add_batch(stack)
    assert np.array_equal(agg.snapshot(), seq.snapshot())
    assert agg.nb_models == 2 * k
    st.close()
    # the adopted plan still serves reads after close (finalize path)
    assert np.array_equal(agg.snapshot(), seq.snapshot())


def test_plan_unmask_matches_gathered_subtract():
    n, k = 1031, 3
    stack = _wire_updates(CFG, n, k, seed=19)
    ol = host_limbs.order_limbs_for(CFG.order)
    rng = np.random.default_rng(23)
    _, mask_wire = _rand_limbs(rng, CFG.order, 1, n)
    mask = mask_wire[0]
    for kernel in ("xla", "pallas-interpret"):
        agg = ShardedAggregator(CFG, n, mesh=make_mesh(), kernel=kernel)
        st = StreamingAggregator(agg, max_batch=k)
        st.submit_batch(stack)
        st.drain()
        assert agg._live_plan is not None
        got = agg.unmask_limbs(mask)
        ref = host_limbs.mod_sub(host_limbs.batch_mod_sum(stack, ol), mask, ol)
        assert np.array_equal(got, ref)
        st.close()


def test_acc_write_supersedes_plan():
    n, k = 515, 2
    stack = _wire_updates(CFG, n, k, seed=29)
    agg = ShardedAggregator(CFG, n, mesh=make_mesh(), kernel="xla")
    st = StreamingAggregator(agg, max_batch=k)
    st.submit_batch(stack)
    st.drain()
    assert agg._live_plan is not None
    agg.reset()
    assert agg._live_plan is None
    assert not np.asarray(agg.acc).any()
    # the pipeline rebuilds a fresh plan instead of folding into the stale one
    st.submit_batch(stack)
    st.drain()
    seq = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
    seq.add_batch(stack)
    assert np.array_equal(agg.snapshot(), seq.snapshot())
    st.close()


def test_mid_round_snapshot_then_more_folds():
    """A checkpoint read (snapshot) between drain windows must not corrupt
    later folds (device plans donate their buffers per fold)."""
    n, k = 1031, 3
    stack = _wire_updates(CFG, n, k, seed=31)
    agg = ShardedAggregator(CFG, n, mesh=make_mesh(), kernel="xla")
    st = StreamingAggregator(agg, max_batch=k)
    st.submit_batch(stack)
    st.drain()
    snap1 = agg.snapshot()
    st.submit_batch(stack)
    st.drain()
    seq = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
    seq.add_batch(stack)
    assert np.array_equal(snap1, seq.snapshot())
    seq.add_batch(stack)
    assert np.array_equal(agg.snapshot(), seq.snapshot())
    st.close()


# --- pre-mask quantization -------------------------------------------------


def test_quantized_order_construction():
    for g in (GroupType.INTEGER, GroupType.PRIME, GroupType.POWER2):
        for q in (0, 1, 4, 7, 10):
            c = MaskConfig(g, DataType.F32, BoundType.B0, ModelType.M3, q)
            base = 2 * int(c.add_shift) * c.exp_shift * c.max_nb_models + 1
            assert c.order >= base
            assert c.exp_shift == 10 ** (10 - q)
            if g is GroupType.INTEGER:
                assert c.order == base
            elif g is GroupType.POWER2:
                assert c.order == 1 << (base - 1).bit_length()
            else:
                assert c.order & 1  # odd
                # every quantized prime is a strong probable prime
                from xaynet_tpu.core.mask.config import _is_probable_prime

                assert _is_probable_prime(c.order)
    # quant=0 must be the exact catalogue entry
    assert (
        MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3, 0).order
        == MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3).order
    )


def test_quantized_config_wire_roundtrip_and_backward_compat():
    for q in (0, 3, 10):
        c = MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M12, q)
        assert MaskConfig.from_bytes(c.to_bytes()) == c
    # quant=0 serializes byte-identically to the reference format
    assert MaskConfig(
        GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3
    ).to_bytes() == bytes([1, 0, 0, 3])
    # old readers' bytes parse to quant=0 configs
    assert MaskConfig.from_bytes(bytes([0, 0, 0, 6])).quant == 0


def test_quant_ceiling_validated():
    with pytest.raises(InvalidMaskConfigError):
        MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3, 11)
    with pytest.raises(InvalidMaskConfigError):
        MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3, -1)
    # BMAX f32 allows deeper levels (exp_shift 10^45) up to the wire
    # nibble ceiling — 16..45 would pass the scale check but have no wire
    # encoding, so construction (and thus Settings.validate()) rejects
    # them instead of letting the round-params serialization blow up
    # mid-round
    MaskConfig(GroupType.PRIME, DataType.F32, BoundType.BMAX, ModelType.M3, 15)
    with pytest.raises(InvalidMaskConfigError):
        MaskConfig(GroupType.PRIME, DataType.F32, BoundType.BMAX, ModelType.M3, 16)


@pytest.mark.parametrize("quant", (0, 4, 7))
def test_quantized_round_accuracy_bound(quant):
    """The accuracy gate's analytic core: a full mask -> aggregate ->
    unmask round at quant level q recovers the true weighted mean within
    nb_models / exp_shift_q per weight."""
    cfg = MaskConfig(
        GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3, quant
    ).pair()
    rng = np.random.default_rng(41)
    nb, n = 4, 257
    weights = [rng.uniform(-1, 1, n).astype(np.float32) for _ in range(nb)]
    agg, magg = Aggregation(cfg, n), Aggregation(cfg, n)
    for w in weights:
        seed, obj = Masker(cfg).mask(Scalar(Fraction(1, nb)), w)
        agg.aggregate(obj)
        magg.aggregate(seed.derive_mask(n, cfg))
    out = agg.unmask_array(magg.object)
    true = sum(w.astype(np.float64) for w in weights) / nb
    assert np.abs(out - true).max() <= nb / cfg.vect.exp_shift + 1e-12


def test_quantized_round_through_device_pipeline():
    """A quantized config (1-limb order, bpn=4) runs the packed streaming
    pipeline byte-identically to its own sequential fold."""
    cfg = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3, 4)
    assert host_limbs.n_limbs_for_order(cfg.order) == 1
    n, k = 1031, 4
    stack = _wire_updates(cfg, n, k, seed=43)
    agg = ShardedAggregator(cfg, n, mesh=make_mesh(), kernel="auto")
    st = StreamingAggregator(agg, max_batch=k)
    st.submit_batch(stack)
    st.drain()
    seq = ShardedAggregator(cfg, n, mesh=_mesh(1), kernel="xla")
    seq.add_batch(stack)
    assert np.array_equal(agg.snapshot(), seq.snapshot())
    st.close()


def test_settings_quant_load_and_validation():
    from xaynet_tpu.server.settings import Settings, SettingsError

    s = Settings.load(env={"XAYNET__MASK__QUANT": "4"})
    assert s.mask.quant == 4
    assert s.mask.to_config().quant == 4
    with pytest.raises(SettingsError):
        Settings.load(env={"XAYNET__MASK__QUANT": "11"})
    # packed staging knob
    s2 = Settings.load(env={"XAYNET__AGGREGATION__PACKED_STAGING": "false"})
    assert s2.aggregation.packed_staging is False
    assert Settings.default().aggregation.packed_staging is True


def test_round_report_bytes_section_carries_deltas():
    """The per-round report's `bytes` section reports THIS round's staged/
    reduced byte deltas, not process totals."""
    from xaynet_tpu.telemetry.report import RoundReporter

    rep = RoundReporter(path=None)
    rep.begin_round(1)
    n, k = 515, 2
    stack = _wire_updates(CFG, n, k, seed=47)
    agg = ShardedAggregator(CFG, n, mesh=_mesh(1), kernel="xla")
    st = StreamingAggregator(agg, max_batch=k, packed=True)
    st.submit_batch(stack)
    st.drain()
    st.close()
    rep.flush()
    first = rep.last_report
    assert first["bytes"]["staged"]["packed"] > 0
    # a round that moves nothing reports no bytes section (deltas, not totals)
    rep.begin_round(2)
    rep.flush()
    assert "bytes" not in rep.last_report or not rep.last_report["bytes"].get("staged")
