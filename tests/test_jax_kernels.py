"""Device kernels vs numpy host oracle (run on a virtual 8-device CPU mesh)."""

import random

import numpy as np
import pytest

from xaynet_tpu.core.crypto.prng import StreamSampler, uniform_ints
from xaynet_tpu.core.mask import (
    Aggregation,
    BoundType,
    DataType,
    GroupType,
    Masker,
    MaskConfig,
    MaskSeed,
    ModelType,
    Scalar,
)
from xaynet_tpu.ops import chacha_jax, limbs as host_limbs, limbs_jax, masking_jax

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
ORDERS = [20_000_000_000_001, 2**45, 2**96, 200_000_000_000_000_000_000_000_000_017]


@pytest.mark.parametrize("order", ORDERS)
def test_mod_add_sub_device(order):
    rng = random.Random(11)
    n_limb = host_limbs.n_limbs_for_order(order)
    ol = host_limbs.order_limbs_for(order)
    a = [rng.randrange(order) for _ in range(64)]
    b = [rng.randrange(order) for _ in range(64)]
    aa = host_limbs.ints_to_limbs(a, n_limb)
    bb = host_limbs.ints_to_limbs(b, n_limb)

    got_add = np.asarray(limbs_jax.mod_add(aa, bb, ol))
    assert np.array_equal(got_add, host_limbs.mod_add(aa, bb, ol))
    got_sub = np.asarray(limbs_jax.mod_sub(aa, bb, ol))
    assert np.array_equal(got_sub, host_limbs.mod_sub(aa, bb, ol))


@pytest.mark.parametrize("k", [1, 2, 5, 16, 33])
def test_batch_mod_sum_device(k):
    order = ORDERS[0]
    rng = random.Random(k)
    n_limb = host_limbs.n_limbs_for_order(order)
    ol = host_limbs.order_limbs_for(order)
    stack = np.stack(
        [host_limbs.ints_to_limbs([rng.randrange(order) for _ in range(24)], n_limb) for _ in range(k)]
    )
    got = np.asarray(limbs_jax.batch_mod_sum(stack, ol))
    assert np.array_equal(got, host_limbs.batch_mod_sum(stack, ol))


def test_device_keystream_matches_host():
    from xaynet_tpu.core.crypto.chacha import keystream_blocks
    import jax.numpy as jnp

    key = bytes(range(32))
    words = chacha_jax.keystream_words(jnp.asarray(np.frombuffer(key, dtype="<u4")), 0, 8)
    host = np.frombuffer(bytes(keystream_blocks(key, 0, 8)), dtype="<u4").reshape(8, 16)
    assert np.array_equal(np.asarray(words), host)


@pytest.mark.slow  # minutes on the CPU-emulated mesh
@pytest.mark.parametrize("order", ORDERS)
def test_device_sampler_matches_host(order):
    seed = b"\x05" * 32
    got = host_limbs.limbs_to_ints(np.asarray(chacha_jax.derive_uniform_limbs(seed, 200, order)))
    assert got == uniform_ints(seed, 200, order)


def test_device_sampler_with_offset():
    seed = b"\x09" * 32
    order = CFG.order
    sampler = StreamSampler(seed)
    sampler.draw_limbs(1, MaskConfig(GroupType.PRIME, DataType.F64, BoundType.B2, ModelType.M3).order)
    offset = sampler.consumed_bytes
    expected = host_limbs.limbs_to_ints(sampler.draw_limbs(50, order))
    got = host_limbs.limbs_to_ints(
        np.asarray(chacha_jax.derive_uniform_limbs(seed, 50, order, byte_offset=offset))
    )
    assert got == expected


@pytest.mark.slow  # minutes on the CPU-emulated mesh
def test_device_sampler_chunked_multi_chunk():
    """A tiny chunk size forces many chunks; result must stay bit-exact."""
    seed = b"\x0c" * 32
    for order in (ORDERS[0], ORDERS[2]):
        want = host_limbs.limbs_to_ints(StreamSampler(seed).draw_limbs(500, order))
        got = host_limbs.limbs_to_ints(
            np.asarray(chacha_jax.derive_uniform_limbs(seed, 500, order, chunk_candidates=97))
        )
        assert got == want


def test_device_sampler_chunked_memory_bound():
    """Chunk size is capped independently of count (the Sum2 memory fix)."""
    order = ORDERS[0]
    bpn = (order.bit_length() + 7) // 8
    assert chacha_jax._CHUNK_BYTES_CAP // bpn < chacha_jax.provision_candidates(10**9, order)


@pytest.mark.slow  # minutes on the CPU-emulated mesh
def test_derive_mask_device_matches_host():
    seed = MaskSeed(b"\x21" * 32)
    mask_host = seed.derive_mask(100, CFG.pair())
    unit, vect = masking_jax.derive_mask_limbs(seed.as_bytes(), 100, CFG.pair())
    assert np.array_equal(unit, mask_host.unit.data)
    assert np.array_equal(np.asarray(vect), mask_host.vect.data)


def test_sharded_aggregator_full_round():
    """Masked updates -> sharded aggregation -> unmask == host Aggregation."""
    from xaynet_tpu.parallel.aggregator import ShardedAggregator

    n, k = 103, 9  # deliberately not divisible by 8 devices
    rng = np.random.default_rng(2)
    cfg = CFG
    agg_host = Aggregation(cfg.pair(), n)
    mask_agg = Aggregation(cfg.pair(), n)
    stacks = []
    for _ in range(k):
        w = rng.uniform(-1, 1, size=n).astype(np.float32)
        seed, masked = Masker(cfg.pair()).mask(Scalar(1, k), w)
        mask = seed.derive_mask(n, cfg.pair())
        agg_host.aggregate(masked)
        mask_agg.aggregate(mask)
        stacks.append(masked.vect.data)

    dev = ShardedAggregator(cfg, n)
    dev.add_batch(np.stack(stacks[:4]))
    dev.add_batch(np.stack(stacks[4:]))
    assert dev.nb_models == k
    assert np.array_equal(dev.snapshot(), agg_host.object.vect.data)

    unmasked_limbs = dev.unmask_limbs(mask_agg.object.vect.data)
    host_limbs_ref, _ = agg_host._unmasked_limbs(mask_agg.object)
    assert np.array_equal(unmasked_limbs, host_limbs_ref)


@pytest.mark.slow  # minutes on the CPU-emulated mesh
def test_sum_masks_device():
    seeds = [bytes([i]) * 32 for i in range(1, 6)]
    n = 40
    got_unit, got_vect = masking_jax.sum_masks(seeds, n, CFG.pair(), kernel="host-chunked")

    agg = Aggregation(CFG.pair(), n)
    for s in seeds:
        agg.aggregate(MaskSeed(s).derive_mask(n, CFG.pair()))
    assert np.array_equal(got_unit, agg.object.unit.data)
    assert np.array_equal(np.asarray(got_vect), agg.object.vect.data)


@pytest.mark.slow  # minutes on the CPU-emulated mesh
def test_sum_masks_device_multi_group():
    """More seeds than one seed_batch: the group-accumulate path (sum2 at
    protocol scale runs #updates/seed_batch of these)."""
    seeds = [bytes([i, i ^ 0x5A]) * 16 for i in range(1, 20)]
    n = 33
    got_unit, got_vect = masking_jax.sum_masks(
        seeds, n, CFG.pair(), seed_batch=4, kernel="host-chunked"
    )

    agg = Aggregation(CFG.pair(), n)
    for s in seeds:
        agg.aggregate(MaskSeed(s).derive_mask(n, CFG.pair()))
    assert np.array_equal(got_unit, agg.object.unit.data)
    assert np.array_equal(np.asarray(got_vect), agg.object.vect.data)


@pytest.mark.slow  # minutes on the CPU-emulated mesh
def test_derive_uniform_limbs_batch_matches_single():
    """Each row of the batched derivation is bit-identical to the single-seed
    kernel at the same byte offset, including the multi-chunk case."""
    order = CFG.order
    seeds = [bytes([7 + i]) * 32 for i in range(5)]
    offsets = [0, 10, 64, 130, 7]
    n = 700
    # small chunks force several chunk rounds with per-seed cursors
    got = np.asarray(
        chacha_jax.derive_uniform_limbs_batch(
            seeds, n, order, byte_offsets=offsets, chunk_candidates=256
        )
    )
    for i, (s, off) in enumerate(zip(seeds, offsets)):
        want = np.asarray(chacha_jax.derive_uniform_limbs(s, n, order, byte_offset=off))
        assert np.array_equal(got[i], want), f"seed {i} diverges from single-seed derive"


@pytest.mark.parametrize(
    "cfg",
    [
        MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6),
        MaskConfig(GroupType.POWER2, DataType.I32, BoundType.BMAX, ModelType.M9),  # 2^96
        MaskConfig(GroupType.PRIME, DataType.F64, BoundType.B6, ModelType.M3),
    ],
)
@pytest.mark.parametrize("k", [1, 2, 13, 64])
def test_fold_planar_batch(cfg, k):
    """Single-pass lazy-carry fold == python big-int oracle."""
    import jax.numpy as jnp

    from xaynet_tpu.ops.fold_jax import fold_planar_batch, wire_to_planar

    order = cfg.order
    n_limb = host_limbs.n_limbs_for_order(order)
    rng = random.Random(k)
    n = 50
    rows = [[rng.randrange(order) for _ in range(n)] for _ in range(k)]
    stack = np.stack([host_limbs.ints_to_limbs(r, n_limb) for r in rows])
    acc0 = [rng.randrange(order) for _ in range(n)]
    acc = jnp.asarray(wire_to_planar(host_limbs.ints_to_limbs(acc0, n_limb)))

    out = fold_planar_batch(acc, jnp.asarray(wire_to_planar(stack)), order)
    got = host_limbs.limbs_to_ints(np.ascontiguousarray(np.asarray(out).T))
    want = [(acc0[j] + sum(rows[i][j] for i in range(k))) % order for j in range(n)]
    assert got == want


@pytest.mark.parametrize("k", [1, 2, 13])
def test_fold_pallas_matches_oracle(k):
    """Pallas fold (interpret mode on CPU) == python big-int oracle."""
    import jax.numpy as jnp

    from xaynet_tpu.ops.fold_jax import wire_to_planar
    from xaynet_tpu.ops.fold_pallas import fold_planar_batch_pallas

    cfg = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
    order = cfg.order
    n_limb = host_limbs.n_limbs_for_order(order)
    rng = random.Random(k)
    n = 256
    rows = [[rng.randrange(order) for _ in range(n)] for _ in range(k)]
    stack = np.stack([host_limbs.ints_to_limbs(r, n_limb) for r in rows])
    acc0 = [rng.randrange(order) for _ in range(n)]
    acc = jnp.asarray(wire_to_planar(host_limbs.ints_to_limbs(acc0, n_limb)))

    out = fold_planar_batch_pallas(acc, jnp.asarray(wire_to_planar(stack)), order, interpret=True)
    got = host_limbs.limbs_to_ints(np.ascontiguousarray(np.asarray(out).T))
    want = [(acc0[j] + sum(rows[i][j] for i in range(k))) % order for j in range(n)]
    assert got == want


@pytest.mark.parametrize(
    "cfg",
    [
        MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6),  # 2 limbs, bpn 6
        MaskConfig(GroupType.POWER2, DataType.I32, BoundType.BMAX, ModelType.M9),  # 2^96 boundary
        MaskConfig(GroupType.PRIME, DataType.F64, BoundType.B6, ModelType.M3),  # multi-limb
    ],
)
def test_wire_bytes_to_planar_matches_host_parse(cfg):
    """Device wire unpack == host parser limb-for-limb (raw element block)."""
    import random as pyrandom

    import jax.numpy as jnp

    from xaynet_tpu.core.mask.object import MaskVect
    from xaynet_tpu.core.mask.serialization import (
        parse_mask_vect,
        serialize_mask_vect,
        vect_element_block,
    )
    from xaynet_tpu.ops.fold_jax import wire_to_planar

    order = cfg.order
    n_limb = host_limbs.n_limbs_for_order(order)
    bpn = cfg.bytes_per_number
    rng = pyrandom.Random(3)
    n = 57
    rows = [rng.randrange(order) for _ in range(n)]
    wire = serialize_mask_vect(MaskVect(cfg, host_limbs.ints_to_limbs(rows, n_limb)))
    raw = vect_element_block(wire)
    assert raw.shape[0] == n * bpn

    got = np.asarray(limbs_jax.wire_bytes_to_planar(jnp.asarray(raw), n, bpn))
    want_limbs, _ = parse_mask_vect(wire)
    assert np.array_equal(got[: n_limb], wire_to_planar(want_limbs.data)), (
        "device unpack diverges from host parse"
    )
    # validity kernel agrees with the host rule (the 2^(32L) boundary case
    # is owned inside the kernel, like limbs.elements_lt_order)
    assert bool(limbs_jax.planar_all_lt_const(got[:n_limb], order))


def test_vect_element_block_rejects_malformed_wire():
    """The device-ingest entry point validates at the parse boundary, like
    parse_mask_vect (truncated buffers and over-long MaskObject wires fail
    with DecodeError, not as shape errors downstream)."""
    from xaynet_tpu.core.mask.object import MaskVect
    from xaynet_tpu.core.mask.serialization import (
        DecodeError,
        serialize_mask_vect,
        vect_element_block,
    )

    wire = serialize_mask_vect(
        MaskVect(CFG, host_limbs.ints_to_limbs([1, 2, 3], host_limbs.n_limbs_for_order(CFG.order)))
    )
    assert vect_element_block(wire).shape == (3 * CFG.bytes_per_number,)
    with pytest.raises(DecodeError, match="too short"):
        vect_element_block(wire[:5])
    with pytest.raises(DecodeError, match="framed element count"):
        vect_element_block(wire[:-1])  # truncated element block
    with pytest.raises(DecodeError, match="framed element count"):
        vect_element_block(wire + b"\x00\x00")  # trailing bytes (e.g. unit part)
    with pytest.raises(DecodeError, match="invalid mask config"):
        vect_element_block(b"\xff\xff\xff\xff" + wire[4:])


def test_sharded_aggregator_wire_ingest():
    """add_wire_batch (device unpack+validity+fold) == host parse + host agg."""
    from xaynet_tpu.core.mask.object import MaskVect
    from xaynet_tpu.core.mask.serialization import serialize_mask_vect, vect_element_block
    from xaynet_tpu.parallel.aggregator import ShardedAggregator

    n, k = 103, 5  # not divisible by the 8-device mesh
    rng = np.random.default_rng(5)
    cfg = CFG
    bpn = cfg.bytes_per_number
    agg_host = Aggregation(cfg.pair(), n)
    raws = []
    for _ in range(k):
        w = rng.uniform(-1, 1, size=n).astype(np.float32)
        _, masked = Masker(cfg.pair()).mask(Scalar(1, k), w)
        agg_host.aggregate(masked)
        wire = serialize_mask_vect(masked.vect)
        raws.append(vect_element_block(wire))

    dev = ShardedAggregator(cfg, n)
    ok = dev.add_wire_batch(np.stack(raws[:2]))
    assert ok.tolist() == [True, True]
    ok = dev.add_wire_batch(np.stack(raws[2:]))
    assert ok.tolist() == [True, True, True]
    assert dev.nb_models == k
    assert np.array_equal(dev.snapshot(), agg_host.object.vect.data)

    # per-update rejection: an update with an element >= order is excluded
    # from the fold and the count, and the others in the batch still land —
    # the aggregate must equal the host aggregate of only the valid ones
    dev2 = ShardedAggregator(cfg, n)
    bad = np.stack([raws[0], raws[1].copy(), raws[2]])
    bad[1, :bpn] = 0xFF  # max fixed-width value >= every non-boundary order
    ok = dev2.add_wire_batch(bad)
    assert ok.tolist() == [True, False, True]
    assert dev2.nb_models == 2
    # the aggregate equals the host aggregate of only the two valid updates
    from xaynet_tpu.core.mask.serialization import parse_mask_vect

    host2 = Aggregation(cfg.pair(), n)
    valid_limbs = []
    for r in (raws[0], raws[2]):
        wire = cfg.to_bytes() + (len(r) // bpn).to_bytes(4, "big") + r.tobytes()
        valid_limbs.append(parse_mask_vect(wire)[0].data)
    unit_l = host_limbs.n_limbs_for_order(cfg.pair().unit.order)
    host2.aggregate_batch(np.stack(valid_limbs), np.zeros((2, unit_l), dtype=np.uint32))
    assert np.array_equal(dev2.snapshot(), host2.object.vect.data)


def test_sharded_aggregator_wire_ingest_fused(monkeypatch):
    """The accelerator-only FUSED ingest jit (unpack+validity+fold in one
    XLA program) — forced on via a monkeypatched backend, same stand-in
    pattern as test_kernel_auto — matches the host aggregate and keeps the
    per-update exclusion semantics."""
    import jax

    from xaynet_tpu.core.mask.serialization import serialize_mask_vect, vect_element_block
    from xaynet_tpu.parallel.aggregator import ShardedAggregator

    n, k = 103, 4
    rng = np.random.default_rng(9)
    cfg = CFG
    bpn = cfg.bytes_per_number
    raws = []
    for _ in range(k):
        w = rng.uniform(-1, 1, size=n).astype(np.float32)
        _, masked = Masker(cfg.pair()).mask(Scalar(1, k), w)
        raws.append((vect_element_block(serialize_mask_vect(masked.vect)), masked))

    dev = ShardedAggregator(cfg, n)
    dev.add_wire_batch(np.stack([r for r, _ in raws[:2]]))  # two-step (resolve)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bad = np.stack([raws[2][0], raws[3][0].copy()])
    bad[1, -bpn:] = 0xFF  # invalid in the fused batch
    ok = dev.add_wire_batch(bad)  # fused path
    assert ok.tolist() == [True, False]
    assert dev.nb_models == 3

    host = Aggregation(cfg.pair(), n)
    unit_l = host_limbs.n_limbs_for_order(cfg.pair().unit.order)
    host.aggregate_batch(
        np.stack([m.vect.data for _, m in raws[:3]]), np.zeros((3, unit_l), dtype=np.uint32)
    )
    assert np.array_equal(dev.snapshot(), host.object.vect.data)
