"""The TPU compiler accepts the device aggregation path at full width.

libtpu can describe a v5e topology without a chip, and
``jit(f).lower(...).compile()`` against its devices runs the real XLA:TPU +
Mosaic compiler — so "the compiler refuses it" (an HBM-exceeding layout, an
op Mosaic cannot lower) is caught here in seconds instead of on the chip.
Every function ``ShardedAggregator`` builds for the default device path is
compiled at the flagship shape of ``chip_smoke.py`` (n = 25M, f32/B0/M6 ->
2 limbs, 7 wire bytes), on one v5e device and sharded over four, and its
temporaries are held under a multiple of its argument bytes: the layouts
this guards against (a minor dimension of 2 or 7 under the (8,128) tiling)
cost 18-64x the input.

Named to sort early: tier-1 runs alphabetically under a wall-clock limit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.parallel import aggregator as agg_mod
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import MODEL_AXIS

import chip_smoke

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
_SMOKE = chip_smoke.sizes(cpu=False)
N = _SMOKE.model_length  # 25M
K = _SMOKE.batch_size  # the fold batch the smoke runs on the chip
L, BPN = 2, 7
# the wide end of the bounded-f32 catalogue (75-bit order, 3 limbs, 10 wire
# bytes): what the benchmark's resnet50-f32b6m6 cell folds, at its batch of 8
CFG_WIDE = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6)
HBM = 16 * 2**30

# temp bytes allowed per argument byte. Measured at this shape, K = 4..8:
# the XLA folds 0.6-1.4x (the [L+1, n] carry/reduce passes), the Pallas
# fold 0.8-1.2x planar and 2.7-3.0x packed (the unpacked planar plus its
# per-call pad of the whole stack to a tile multiple), validity and wire
# unpack 2.3x (the unpacked planar), unmask 0.8-1.0x. The refused layouts
# were 18-64x.
MAX_TEMP_PER_ARG = 3.5


@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a topology
        pytest.skip(f"no TPU topology description available: {type(e).__name__}")
    assert len(topo.devices) == 4
    return topo.devices


@pytest.fixture(autouse=True)
def _keep_fold_fn_cache_clean():
    """The builders memoize by mesh DEVICE IDS; topology devices reuse the
    ids of this process's CPU devices, so nothing built here may leak."""
    before = dict(agg_mod._FOLD_FN_CACHE)
    agg_mod._FOLD_FN_CACHE.clear()
    yield
    agg_mod._FOLD_FN_CACHE.clear()
    agg_mod._FOLD_FN_CACHE.update(before)


def _builder(devices, cfg=CFG) -> ShardedAggregator:
    """A ShardedAggregator shell over topology devices: the real builder
    methods, none of the constructor's device allocations."""
    from xaynet_tpu.ops import limbs as host_limbs

    agg = object.__new__(ShardedAggregator)
    agg.config, agg.order = cfg, cfg.order
    agg.n_limbs = host_limbs.n_limbs_for_order(cfg.order)
    agg.mesh = Mesh(np.asarray(devices), (MODEL_AXIS,))
    agg.packed_width = cfg.bytes_per_number
    return agg


def _specs(devices, L=L, BPN=BPN, K=K):
    """(acc, planar batch, packed batch, wire batch) argument specs."""
    if len(devices) == 1:
        acc_s = batch_s = wire_s = SingleDeviceSharding(devices[0])
    else:
        mesh = Mesh(np.asarray(devices), (MODEL_AXIS,))
        acc_s = NamedSharding(mesh, P(None, MODEL_AXIS))
        batch_s = NamedSharding(mesh, P(None, None, MODEL_AXIS))
        wire_s = NamedSharding(mesh, P(None, MODEL_AXIS))
    return (
        jax.ShapeDtypeStruct((L, N), jnp.uint32, sharding=acc_s),
        jax.ShapeDtypeStruct((K, L, N), jnp.uint32, sharding=batch_s),
        jax.ShapeDtypeStruct((K, BPN, N), jnp.uint8, sharding=batch_s),
        jax.ShapeDtypeStruct((K, N * BPN), jnp.uint8, sharding=wire_s),
    )


def _compile(fn, *args):
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    per_device = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    )
    assert per_device < HBM, f"{per_device / 2**30:.1f} GiB does not fit one v5e"
    ratio = mem.temp_size_in_bytes / max(mem.argument_size_in_bytes, 1)
    assert ratio <= MAX_TEMP_PER_ARG, (
        f"temp {mem.temp_size_in_bytes / 1e9:.2f} GB is {ratio:.1f}x the "
        f"{mem.argument_size_in_bytes / 1e9:.2f} GB of arguments"
    )
    return mem


@pytest.mark.parametrize("n_dev,cfg,k", [
    pytest.param(1, CFG, K, id="1-2limb-7B"),
    pytest.param(4, CFG, K, id="4-2limb-7B"),
    pytest.param(1, CFG_WIDE, 8, id="1-3limb-10B"),
])
def test_default_device_path_compiles_for_v5e_at_25m(v5e, n_dev, cfg, k):
    devices = v5e[:n_dev]
    agg = _builder(devices, cfg)
    acc, planar, packed, _wire = _specs(devices, agg.n_limbs, agg.packed_width, k)
    # the race's two candidates fold the planar batch...
    _compile(agg._make_fold_fn("xla"), acc, planar)
    _compile(agg._make_fold_fn("pallas"), acc, planar)
    # ...production folds the packed staging batch with either winner
    _compile(agg._make_packed_fold_fn("xla"), acc, packed)
    _compile(agg._make_packed_fold_fn("pallas"), acc, packed)
    # wire-v2 validity, and the unmask subtract
    _compile(agg._make_planar_ok_fn(), packed)
    _compile(lambda a, m: agg_mod._unmask_kernel(a, m, cfg.order), acc, acc)


def test_shard_parallel_folds_compile_per_device(v5e):
    """``shard_parallel = true`` (the default) folds each device's slice
    with the single-device programs at 1/4 width."""
    from xaynet_tpu.ops.fold_jax import fold_packed_batch, fold_planar_batch

    s = SingleDeviceSharding(v5e[3])
    w = N // 4
    acc = jax.ShapeDtypeStruct((L, w), jnp.uint32, sharding=s)
    planar = jax.ShapeDtypeStruct((K, L, w), jnp.uint32, sharding=s)
    packed = jax.ShapeDtypeStruct((K, BPN, w), jnp.uint8, sharding=s)
    _compile(lambda a, b: fold_planar_batch(a, b, CFG.order), acc, planar)
    _compile(lambda a, b: fold_packed_batch(a, b, L, CFG.order), acc, packed)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_wire_v1_ingest_compiles_for_v5e_at_25m(v5e, n_dev):
    """``[aggregation] wire_ingest = true``: the element-major wire bytes
    de-interleave on device with stride-bpn slices."""
    devices = v5e[:n_dev]
    agg = _builder(devices)
    acc, _planar, _packed, wire = _specs(devices)
    _compile(agg._make_unpack_fn(), wire)
    _compile(agg._make_ingest_fn(), acc, wire)


@pytest.mark.parametrize("n_dev,k,rows,width", [
    pytest.param(1, 12, BPN, N, id="1-k12-7B"),  # resnet50-f32m6's batch
    pytest.param(1, 8, 10, N, id="1-k8-10B"),  # resnet50-f32b6m6's
    pytest.param(1, 48, BPN, N // 4, id="shard-k48-7B"),  # a shard's slice of -x4's
    pytest.param(1, 64, 6, 6_603_710, id="1-k64-6B"),  # femnist-cnn-prime-f32m3's
    pytest.param(4, 12, BPN, N, id="4-k12-7B"),  # the one-worker pipeline on a mesh
])
def test_row_placement_compiles_in_place_for_v5e(v5e, n_dev, k, rows, width):
    """A batch staged at arrival is assembled on the device row by row
    (``shards.place_row``, the batch donated): the compiler has to alias the
    whole batch to its result, or every row would cost a copy of 2.1 GB. A
    row whose home is one device arrives flat and is relaid out into the
    batch's tiling plane by plane, through a temporary of at most four rows
    (where the chip keeps the batch with K on the sublanes, K a multiple of
    eight, a tile of 4 x 128 around the one row); over a mesh it arrives as
    ``[planes, width]``, model axis sharded."""
    from xaynet_tpu.parallel.shards import place_row

    devices = v5e[:n_dev]
    acc, _planar, packed, _wire = _specs(devices, L, rows, k)
    batch = jax.ShapeDtypeStruct((k, rows, width), jnp.uint8, sharding=packed.sharding)
    row = jax.ShapeDtypeStruct((rows * width,) if n_dev == 1 else (rows, width), jnp.uint8,
                               sharding=acc.sharding)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    mem = place_row.lower(batch, row, slot).compile().memory_analysis()
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes > 0
    assert mem.temp_size_in_bytes <= 4.2 * rows * width / n_dev


# --- the per-update road of [aggregation] wire_ingest (ISSUE 54) --------------
#
# The cell resnet50-f32m6-wireingest.flood at its own size: one update's
# element block on the device as its message held it (no batch axis), the
# de-interleave and order check over it, the stack of a chunk of resident
# rows and the fold of the chunk (12 rows a flush = chunks of 8 and 4).

N_RESNET = 25_557_032


def _one_update_specs(devices, n=N_RESNET):
    """(v1 raw block, v2 planes, resident planar row) of ONE update."""
    if len(devices) == 1:
        flat = rows = SingleDeviceSharding(devices[0])
    else:
        mesh = Mesh(np.asarray(devices), (MODEL_AXIS,))
        flat, rows = NamedSharding(mesh, P(MODEL_AXIS)), NamedSharding(mesh, P(None, MODEL_AXIS))
    return (
        jax.ShapeDtypeStruct((n * BPN,), jnp.uint8, sharding=flat),
        jax.ShapeDtypeStruct((BPN, n), jnp.uint8, sharding=rows),
        jax.ShapeDtypeStruct((L, n), jnp.uint32, sharding=rows),
    )


@pytest.mark.parametrize("n_dev", [1, 4])
def test_one_update_unpack_and_check_compile_for_v5e_at_the_cells_size(v5e, n_dev):
    devices = v5e[:n_dev]
    agg = _builder(devices)
    raw, planes, _row = _one_update_specs(devices)
    mem = _compile(agg._make_unpack_fn(one=True), raw)
    # what stays is the row alone: [L, n] uint32 and the verdict
    assert mem.output_size_in_bytes >= 4 * L * N_RESNET // n_dev
    assert mem.output_size_in_bytes < 4 * L * N_RESNET // n_dev + 4096
    _compile(agg._make_planar_ok_fn(one=True), planes)


@pytest.mark.parametrize("k", [8, 4])
def test_a_chunk_of_resident_rows_stacks_and_folds_for_v5e_at_the_cells_size(v5e, k):
    devices = v5e[:1]
    agg = _builder(devices)
    agg._batch_sharding = SingleDeviceSharding(devices[0])
    _raw, _planes, row = _one_update_specs(devices)
    stack = agg._make_stack_fn()
    mem = stack.lower(*[row] * k).compile().memory_analysis()
    chunk = k * 4 * L * N_RESNET
    # one copy of the rows into the chunk, no second one beside it
    assert chunk <= mem.output_size_in_bytes < 1.001 * chunk  # (the tiles pad the last columns)
    assert mem.temp_size_in_bytes <= 0.05 * chunk
    acc = jax.ShapeDtypeStruct((L, N_RESNET), jnp.uint32, sharding=row.sharding)
    batch = jax.ShapeDtypeStruct((k, L, N_RESNET), jnp.uint32, sharding=row.sharding)
    _compile(agg._make_fold_fn("xla"), acc, batch)
    _compile(agg._make_fold_fn("pallas"), acc, batch)
