"""Device-side masking operations: mask expansion, aggregation, unmask.

Device counterparts of the reference hot loops (reference:
rust/xaynet-core/src/mask/seed.rs:61-78 derive_mask,
rust/xaynet-sdk/src/state_machine/phases/sum2.rs:170-193 mask aggregation,
rust/xaynet-server/src/state_machine/phases/unmask.rs unmask subtract).

Composes the ChaCha20 and limb kernels into the protocol-level device ops the
coordinator and sum participants run:

- ``derive_mask_limbs``: seed -> (unit element, vector limb tensor), the
  device version of ``MaskSeed.derive_mask`` (bit-identical keystream
  consumption: one unit draw on the host cursor, vector draws on device from
  the handed-off byte offset);
- ``unmask_vect_limbs``: modular subtract of the aggregated mask from the
  aggregated masked model (the Unmask-phase kernel);
- ``sum_masks``: aggregate many seed-derived masks (the Sum2 participant hot
  loop: #updates x model_length group elements). Since the fused-pipeline
  promotion this routes through one of the ``MASK_KERNELS``
  (``utils.kernels``): the in-graph batched derive streamed through the
  PR-7 shard pipeline, the fused Pallas keystream→reject→fold kernel, or
  the pre-promotion host-chunked path — ``auto`` races them once per
  process on a probe group and memoizes the winner, exactly like the fold
  kernels' auto-calibration.
"""

from __future__ import annotations

import logging
import os
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..core.crypto.prng import StreamSampler
from ..core.mask.config import MaskConfigPair
from ..core.mask.derive_sum import derive_and_sum
from ..core.mask.encode import clamp_scalar, encode_unit, encode_vect_limbs
from ..telemetry import profiling, report as round_report
from ..telemetry import tracing as trace
from ..telemetry.registry import get_registry
from ..utils.kernels import MASK_KERNELS
from . import chacha_jax, limbs as host_limbs, limbs_jax

logger = logging.getLogger(__name__)

SPAN_MASK_CALIBRATE = trace.declare_span("mask.calibrate")
SPAN_MASK_SUM = trace.declare_span("mask.sum", mirror=True)

# Compiled-program cache bound for the pow2-lane batched derive (and the
# other jitted mask-pipeline builders below). Each entry retains a full XLA
# executable specialized on (length, config, lane bucket); an unbounded
# cache on a long-running participant serving many round shapes would
# retain one program per shape forever.
_COMPILE_CACHE_MAX = 16

MASK_DERIVE_COMPILE_CACHE = get_registry().gauge(
    "xaynet_mask_derive_compile_cache",
    "Compiled mask-derivation programs currently held by the bounded "
    "pow2-lane lru caches (batched derive + unit-draw + planarize).",
)


def derive_mask_limbs(
    seed: bytes, length: int, config: MaskConfigPair
) -> tuple[np.ndarray, jax.Array]:
    """Expand a 32-byte seed into (unit limbs [L1], vector limbs [length, L])."""
    sampler = StreamSampler(seed)
    unit = sampler.draw_limbs(1, config.unit.order)[0]
    offset = sampler.consumed_bytes
    vect = chacha_jax.derive_uniform_limbs(seed, length, config.vect.order, byte_offset=offset)
    return unit, vect


def derive_mask_ingraph(
    key_words: jax.Array,
    length: int,
    config: MaskConfigPair,
    unit_chunk: int | None = None,
    vect_chunk: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fully in-graph ``MaskSeed.derive_mask``: (unit [L1], vect [length, L]).

    Pure traced code (no host syncs), composable under ``jit``/``vmap`` —
    the per-participant kernel the federated simulation maps across its
    participant axis. Keystream semantics are bit-identical to the scalar
    ``core/mask/seed.py`` reference: one unit-order draw first, then the
    vector draws resume at the traced in-graph byte cursor the unit draw
    handed off. The chunk knobs bound per-lane device memory; pass
    ``chacha_jax.provisioned_chunk(length, order, n_lanes)`` when vmapping
    ``n_lanes`` participants so the batch stays inside the chunk budget.
    """
    unit, offset = chacha_jax.derive_uniform_limbs_ingraph(
        key_words, jnp.int32(0), 1, config.unit.order, unit_chunk
    )
    vect, _ = chacha_jax.derive_uniform_limbs_ingraph(
        key_words, offset, length, config.vect.order, vect_chunk
    )
    return unit[0], vect


def seed_words(seeds: list[bytes]) -> np.ndarray:
    """32-byte seeds -> ``uint32[B, 8]`` little-endian ChaCha key words."""
    if not seeds:
        return np.zeros((0, 8), dtype=np.uint32)
    return np.stack([np.frombuffer(s, dtype="<u4") for s in seeds])


def derive_chunk_budgets(
    length: int, config: MaskConfigPair, lanes: int
) -> tuple[int, int]:
    """(unit_chunk, vect_chunk) keystream budgets for ``lanes`` concurrent
    in-graph derivations — the ONE provisioning rule shared by the batched
    production derive and the simulation's participant-axis vmap."""
    return (
        chacha_jax.provisioned_chunk(1, config.unit.order, lanes),
        chacha_jax.provisioned_chunk(length, config.vect.order, lanes),
    )


def _publish_compile_cache_gauge() -> None:
    MASK_DERIVE_COMPILE_CACHE.set(
        _mask_batch_fn.cache_info().currsize
        + _unit_offsets_fn.cache_info().currsize
        + _planarize_fn.cache_info().currsize
    )


@lru_cache(maxsize=_COMPILE_CACHE_MAX)
def _mask_batch_fn(length: int, config: MaskConfigPair, lane_bucket: int):
    unit_chunk, vect_chunk = derive_chunk_budgets(length, config, lane_bucket)

    def one(kw):
        return derive_mask_ingraph(kw, length, config, unit_chunk, vect_chunk)

    return jax.jit(jax.vmap(one))


@lru_cache(maxsize=_COMPILE_CACHE_MAX)
def _unit_offsets_fn(config: MaskConfigPair):
    """Jitted batched unit draw: ``uint32[B, 8]`` key words ->
    (unit limbs ``uint32[B, L1]``, byte cursors ``int32[B]`` the vector
    draws resume at) — the in-graph replacement for the per-seed host
    ``StreamSampler`` unit loop."""
    unit_chunk = chacha_jax.provisioned_chunk(1, config.unit.order, 1)

    def one(kw):
        unit, off = chacha_jax.derive_uniform_limbs_ingraph(
            kw, jnp.int32(0), 1, config.unit.order, unit_chunk
        )
        return unit[0], off

    return jax.jit(jax.vmap(one))


@lru_cache(maxsize=_COMPILE_CACHE_MAX)
def _planarize_fn(length: int, padded: int):
    """Jitted wire ``[B, len, L]`` -> planar padded ``[B, L, padded]``
    relayout (the shard pipeline's batch shape), done on device so the
    derived masks never round-trip the host before folding."""

    def f(vects):
        planar = jnp.transpose(vects, (0, 2, 1))
        if padded != length:
            planar = jnp.pad(planar, ((0, 0), (0, 0), (0, padded - length)))
        return planar

    return jax.jit(f)


def derive_mask_limbs_batch(
    seeds: list[bytes], length: int, config: MaskConfigPair
) -> tuple[jax.Array, jax.Array]:
    """``derive_mask_limbs`` for many seeds in ONE jitted program.

    Returns (units ``uint32[B, L1]``, vects ``uint32[B, length, L]``);
    every row is bit-identical to ``MaskSeed.derive_mask`` with that seed
    (golden-pinned in tests/test_sim_round.py). Unlike ``sum_masks`` this
    never walks the seeds on the host — unit draws, cursor handoffs and
    vector draws are all in-graph — so it is the building block for
    whole-round simulation rather than the Sum2 aggregate.

    Compiled programs are cached per (length, config, pow2 lane bucket);
    the lane bucket also scales the chunk budget so large batches don't
    multiply the keystream footprint past the device-memory cap.
    """
    if not seeds:
        raise ValueError("no seeds")
    lane_bucket = 1 << (len(seeds) - 1).bit_length()
    fn = _mask_batch_fn(length, config, lane_bucket)
    return fn(jnp.asarray(seed_words(seeds)))


def encode_models_batch(
    weights: np.ndarray, scalar, config: MaskConfigPair
) -> tuple[np.ndarray, np.ndarray]:
    """Fixed-point encode a population of models in ONE vectorized pass.

    ``weights`` is ``[B, length]`` (every participant shares ``scalar``, the
    homogeneous-simulation shape); returns (unit limbs ``uint32[L1]`` — the
    encoded clamped scalar, identical for every lane — and vect limbs
    ``uint32[B, length, L]``). Byte-identical to ``B`` independent
    ``Masker.mask`` encodes because the fixed-point map is elementwise: the
    flattened array goes through the SAME production ``encode_vect_limbs``
    (double-double fast path for bounded f32, exact Fractions otherwise)
    that a single participant runs, then reshapes. Pinned against the
    scalar path in tests/test_sim_round.py.
    """
    weights = np.asarray(weights)
    if weights.ndim != 2:
        raise ValueError("weights must be [participants, length]")
    s_clamped = clamp_scalar(scalar, config.unit)
    flat = encode_vect_limbs(weights.reshape(-1), s_clamped, config.vect)
    vect = flat.reshape(weights.shape[0], weights.shape[1], -1)
    unit_int = encode_unit(s_clamped, config.unit)
    unit = host_limbs.int_to_limbs(unit_int, host_limbs.n_limbs_for_order(config.unit.order))
    return unit, vect


def unmask_vect_limbs(
    masked: jax.Array, mask: jax.Array, order: int
) -> jax.Array:
    """``(masked - mask) mod order`` elementwise over limb tensors."""
    return limbs_jax.mod_sub(masked, mask, host_limbs.order_limbs_for(order))


# -- promoted Sum2 pipeline: kernel routing + auto-calibration --------------

# auto verdicts, process-wide (the fold kernels' `_AUTO_KERNEL_CACHE` idiom):
# a participant resolves the route once per (backend, shape) and every later
# Sum2 leg reuses it
_MASK_KERNEL_CACHE: dict[tuple, str] = {}
# observability: the route the last sum_masks call actually took
_LAST_MASK_KERNEL: str | None = None

# auto-calibration probe: candidates race on a seed group derived at
# min(length, _PROBE_LENGTH) elements. Unlike the fold race (which times the
# real first batch it must fold anyway), re-deriving a 25M-element group per
# candidate would triple the first Sum2 leg — the relative kernel speeds are
# shape-stable well below that, so the probe caps the one-time cost.
_PROBE_LENGTH = 1 << 18


def resolved_mask_kernel() -> str | None:
    """The mask kernel the last ``sum_masks`` call used (bench/telemetry)."""
    return _LAST_MASK_KERNEL


def calibrate_mask_kernel(
    seeds, length: int, config: MaskConfigPair, seed_batch: int = 8, mesh=None
) -> str:
    """Resolve (and memoize) the auto route for this shape NOW.

    ``sum_masks(kernel="auto")`` calibrates lazily inside its first call;
    steady-state measurements call this first so the one-time probe race
    stays out of the per-round wall — exactly how a long-running participant
    amortizes it."""
    return _resolve_mask_kernel(seeds, length, config, seed_batch, mesh)


def _acc_unit(unit_acc, group_unit: np.ndarray, ol_u: np.ndarray) -> np.ndarray:
    """Fold one group's unit-limb sum into the running unit accumulator —
    the ONE accumulate idiom every route shares."""
    if unit_acc is None:
        return group_unit
    return host_limbs.mod_add(unit_acc[None, :], group_unit[None, :], ol_u)[0]


def _mask_route(used: str, seeds, length, config, seed_batch, mesh):
    if used == "host-chunked":
        return _sum_masks(seeds, length, config, seed_batch)
    if used == "host-threaded":
        # the one host derive-and-sum, the SDK's CPU sum participant's too
        return derive_and_sum(seeds, length, config)
    if used in ("fused-pallas", "fused-pallas-interpret"):
        return _sum_masks_fused(
            seeds, length, config, seed_batch, interpret=used == "fused-pallas-interpret"
        )
    return _sum_masks_batched(seeds, length, config, seed_batch, mesh)


def _resolve_mask_kernel(
    seeds, length: int, config: MaskConfigPair, seed_batch: int, mesh
) -> str:
    backend = jax.default_backend()
    bucket = min(max(1, seed_batch), len(seeds))
    # the mesh is part of the verdict's identity: the batch route's cost is
    # mesh-dependent, so a winner probed without a mesh must not be reused
    # for mesh-sharded calls (and vice versa)
    mesh_key = (
        None
        if mesh is None
        else (tuple(mesh.devices.shape), tuple(int(d.id) for d in mesh.devices.flat))
    )
    key = (backend, length, config, bucket, mesh_key)
    cached = _MASK_KERNEL_CACHE.get(key)
    if cached is not None:
        return cached
    # disk tier (utils.calibcache): a winner raced by a previous process
    # under the same environment fingerprint skips the probe race
    from ..utils import calibcache

    warm = calibcache.get("mask", key)
    if warm is not None:
        _MASK_KERNEL_CACHE[key] = warm
        logger.info("mask kernel resolved: %s (auto, persisted verdict)", warm)
        return warm
    probe_len = min(length, _PROBE_LENGTH)
    probe = list(seeds[:bucket])
    if backend == "cpu":
        # the interpret route is the CPU/CI leg of the fused kernel — raced
        # for real, so the fused pipeline stays continuously exercised and
        # wins exactly when it is actually faster; the threaded native
        # sampler is the CPU incumbent the in-graph routes must beat
        candidates = ["host-threaded", "batch", "fused-pallas-interpret"]
    else:
        candidates = ["batch", "fused-pallas", "host-threaded"]
    timings: dict[str, float] = {}
    with trace.get_tracer().span(
        SPAN_MASK_CALIBRATE, backend=backend, length=length, probe=probe_len
    ) as span:
        for name in candidates:
            try:
                fn = lambda name=name: _mask_route(name, probe, probe_len, config, seed_batch, mesh)
                fn()  # compile / first touch
                _, dt = profiling.measure(fn)
                timings[name] = dt
                profiling.record_calibration(f"mask-{name}", dt)
            except Exception as e:  # Mosaic/compile failure -> keep the others
                logger.warning(
                    "mask kernel %s unavailable: %s: %s", name, type(e).__name__, e
                )
        winner = min(timings, key=timings.get) if timings else "host-chunked"
        span.set(winner=winner)
    _MASK_KERNEL_CACHE[key] = winner
    from ..utils import calibcache

    calibcache.put("mask", key, winner)
    # the verdict is round-report material: a headline shift caused by a
    # verdict flip must be auditable from the report, not require a re-run
    round_report.record_mask_calibration(
        {
            "winner": winner,
            "backend": backend,
            "length": length,
            "bucket": bucket,
            "mesh": None if mesh_key is None else list(mesh_key[0]),
            "probe_length": probe_len,
            "probe_walls": {k: round(v, 6) for k, v in timings.items()},
        }
    )
    logger.info(
        "mask kernel auto-calibration (%s backend, probe %d): %s -> %s",
        backend,
        probe_len,
        {k: round(v, 4) for k, v in timings.items()},
        winner,
    )
    return winner


def sum_masks(
    seeds: list[bytes],
    length: int,
    config: MaskConfigPair,
    seed_batch: int = 8,
    kernel: str | None = None,
    mesh=None,
) -> tuple[np.ndarray, jax.Array]:
    """Derive and modularly sum the masks of many seeds (Sum2 hot loop).

    Returns (unit limbs, vector limbs) of the aggregated mask; every route
    is bit-identical to folding ``MaskSeed.derive_mask`` per seed.

    ``kernel`` picks the route (``utils.kernels.MASK_KERNELS``; ``None``
    honors ``XAYNET_MASK_KERNEL`` then defaults to ``auto``):

    - ``batch`` — ALL of a seed group's derivations (unit draws, cursor
      handoffs, vector draws) run in ONE jitted in-graph program
      (``derive_mask_limbs_batch``), and the resulting mask planes stream
      through the PR-7 shard pipeline (per-shard fold workers on a mesh);
    - ``fused-pallas[-interpret]`` — the Pallas keystream→reject→fold
      kernel: masks never materialize in HBM
      (``fold_pallas.mask_fold_planar_pallas``);
    - ``host-threaded`` — the host's streaming derive-and-sum
      (``core.mask.derive_sum``: no mask in memory, every core), the SDK's
      CPU sum participant's route too;
    - ``host-chunked`` — the pre-promotion path (host unit draws + chunked
      device vector derivation + ``aggregate_batch`` folds);
    - ``auto`` — races the candidates once per (backend, shape) on a probe
      group and memoizes the winner process-wide.

    Device memory is bounded by ``seed_batch * length`` mask elements
    (``batch``), one mask's chunk budget (``fused``), and device-synced
    timing is recorded as the ``mask_expand`` kernel op either way.
    """
    if not seeds:
        raise ValueError("no seeds to aggregate")
    if kernel is None:
        kernel = os.environ.get("XAYNET_MASK_KERNEL") or "auto"
    if kernel not in MASK_KERNELS:
        raise ValueError(f"kernel must be one of {MASK_KERNELS}, got {kernel!r}")
    if kernel == "auto":
        kernel = _resolve_mask_kernel(seeds, length, config, seed_batch, mesh)
    global _LAST_MASK_KERNEL
    _LAST_MASK_KERNEL = kernel
    with trace.get_tracer().span(
        SPAN_MASK_SUM, kernel=kernel, seeds=len(seeds), length=length
    ):
        return profiling.timed_kernel(
            "mask_expand",
            len(seeds) * length,
            lambda: _mask_route(kernel, seeds, length, config, seed_batch, mesh),
        )


def _sum_masks_batched(
    seeds: list[bytes], length: int, config: MaskConfigPair, seed_batch: int, mesh
) -> tuple[np.ndarray, np.ndarray]:
    """The promoted route: one jitted in-graph program per seed group, mask
    planes streamed through the PR-7 shard pipeline.

    Each group's units/cursors/vectors derive in ONE compiled program (no
    per-seed host loop), the group's wire-layout masks relayout to planar
    on device, and the shard pipeline folds them into the (mesh-sharded)
    planar accumulator — on a multi-device mesh each device folds its own
    model-axis slice, so the aggregated mask is reduced on-shard exactly
    like the update fold."""
    from ..parallel.aggregator import ShardedAggregator
    from ..parallel.streaming import StreamingAggregator

    step = max(1, seed_batch)
    agg = ShardedAggregator(config.vect, length, mesh=mesh, kernel="xla")
    stream = StreamingAggregator(agg, max_batch=max(2, step))
    ol_u = host_limbs.order_limbs_for(config.unit.order)
    unit_acc: np.ndarray | None = None
    try:
        for g0 in range(0, len(seeds), step):
            group = seeds[g0 : g0 + step]
            units, vects = derive_mask_limbs_batch(group, length, config)
            planar = _planarize_fn(length, agg.padded_length)(vects)
            _publish_compile_cache_gauge()
            stream.fold_planar_stack_now(planar)
            group_unit = host_limbs.batch_mod_sum(np.asarray(units)[:, None, :], ol_u)[0]
            unit_acc = _acc_unit(unit_acc, group_unit, ol_u)
        stream.drain()
        vect = agg.snapshot()
    finally:
        stream.close()
    assert unit_acc is not None
    return unit_acc, vect


def _sum_masks_fused(
    seeds: list[bytes],
    length: int,
    config: MaskConfigPair,
    seed_batch: int,
    interpret: bool,
    chunk_candidates: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """The fused route: keystream→reject→fold in one Pallas kernel per seed
    group; the per-seed masks never materialize in HBM. Unit draws and the
    byte-cursor handoff run in-graph (``_unit_offsets_fn``) — no scalar
    host derivation anywhere on this path."""
    from . import fold_pallas
    from .fold_jax import planar_to_wire

    n_limb = host_limbs.n_limbs_for_order(config.vect.order)
    ol_u = host_limbs.order_limbs_for(config.unit.order)
    step = max(1, seed_batch)
    # seeds fold sequentially inside the kernel, so one seed's chunk budget
    # is the whole keystream footprint
    chunk = (
        chunk_candidates
        if chunk_candidates is not None
        else chacha_jax.provisioned_chunk(length, config.vect.order, 1)
    )
    acc = jnp.zeros((n_limb, length), dtype=jnp.uint32)
    unit_acc: np.ndarray | None = None
    unit_fn = _unit_offsets_fn(config)
    _publish_compile_cache_gauge()
    for g0 in range(0, len(seeds), step):
        group = seeds[g0 : g0 + step]
        kw = jnp.asarray(seed_words(group))
        units, offsets = unit_fn(kw)
        acc, _ends = fold_pallas.mask_fold_planar_pallas(
            acc,
            kw,
            offsets,
            length,
            config.vect.order,
            chunk_candidates=chunk,
            interpret=interpret,
        )
        group_unit = host_limbs.batch_mod_sum(np.asarray(units)[:, None, :], ol_u)[0]
        unit_acc = _acc_unit(unit_acc, group_unit, ol_u)
    assert unit_acc is not None
    return unit_acc, planar_to_wire(np.asarray(acc))


def _sum_masks(
    seeds: list[bytes], length: int, config: MaskConfigPair, seed_batch: int
) -> tuple[np.ndarray, jax.Array]:
    order_limbs_u = host_limbs.order_limbs_for(config.unit.order)
    order_limbs_v = host_limbs.order_limbs_for(config.vect.order)

    unit_acc: np.ndarray | None = None
    vect_acc: jax.Array | None = None
    for g0 in range(0, len(seeds), max(1, seed_batch)):
        group = seeds[g0 : g0 + max(1, seed_batch)]
        units, offsets = [], []
        for seed in group:
            # host unit draw first, exactly as MaskSeed.derive_mask orders
            # the keystream; the vector draw continues at the handed-off
            # byte cursor
            sampler = StreamSampler(seed)
            units.append(sampler.draw_limbs(1, config.unit.order)[0])
            offsets.append(sampler.consumed_bytes)
        vects = chacha_jax.derive_uniform_limbs_batch(
            group, length, config.vect.order, byte_offsets=offsets
        )
        group_unit = units[0]
        for u in units[1:]:
            group_unit = host_limbs.mod_add(group_unit[None, :], u[None, :], order_limbs_u)[0]
        if vect_acc is None:
            vect_acc = (
                limbs_jax.batch_mod_sum(vects, order_limbs_v) if len(group) > 1 else vects[0]
            )
            unit_acc = group_unit
        else:
            # one jitted kernel: tree-sum the group and fold it into the
            # donated accumulator (aggregate_batch), instead of eager
            # batch_mod_sum + mod_add dispatches per group
            vect_acc = limbs_jax.aggregate_batch(vect_acc, vects, order_limbs_v)
            unit_acc = host_limbs.mod_add(
                unit_acc[None, :], group_unit[None, :], order_limbs_u
            )[0]
    assert unit_acc is not None and vect_acc is not None
    return unit_acc, vect_acc
