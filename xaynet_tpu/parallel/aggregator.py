"""Sharded device-resident aggregation of masked updates.

The coordinator-side hot path (reference analogue:
rust/xaynet-server/src/state_machine/phases/update.rs:119-152, which does one
sequential big-int pass per accepted update). Here the running aggregate is
an HBM-resident **planar** ``uint32[L, padded_len]`` buffer sharded over the
model-length axis of a device mesh; incoming masked updates are staged into
``[K, L, padded_len]`` batches and folded in with the single-pass lazy-carry
kernel (``ops.fold_jax``) — one full read of the batch plus a handful of
tiny passes, no collectives (the length axis is embarrassingly parallel).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.mask.config import MaskConfig
from ..ops import limbs as host_limbs
from ..ops.limbs import PlanarLimbs
from ..ops.fold_jax import (
    MAX_LAZY_BATCH,
    fold_packed_batch,
    fold_planar_batch,
    p_mod_sub,
    wire_to_planar,
)
from ..telemetry import profiling
from ..telemetry import unmask as unmask_stages
from ..telemetry import wire as wire_stats
from ..telemetry.registry import get_registry
from ..utils.kernels import FOLD_KERNELS
from .mesh import MODEL_AXIS, make_mesh, pad_to_multiple

logger = logging.getLogger(__name__)

# cross-shard combine traffic (bytes actually copied), by path: "scatter" =
# decomposing the global accumulator into per-shard buffers (zero-copy, so
# nothing counts it), "gather" = materializing the unmasked result on the
# host (the final model download). The reduce-scatter layout keeps the
# accumulator per-shard ACROSS drain windows, so the counter advances once
# per round instead of twice per drain.
BYTES_REDUCED = get_registry().counter(
    "xaynet_bytes_reduced_total",
    "Accumulator bytes copied on the cross-shard combine path, by "
    "direction (scatter = global -> per-shard, gather = per-shard -> "
    "global/host).",
    ("path",),
)

# same family streaming.py registers for its ring staging (the registry
# dedupes by name): the wire-ingest staging uploads are accounted here so
# the ingress bench can read bytes-moved-per-accepted-update straight off
# /metrics — "wire" = v1 interleaved element blocks, "wire-planar" = v2
# byte-planar blocks that stay packed through the fold (docs/DESIGN.md §21)
BYTES_STAGED = get_registry().counter(
    "xaynet_bytes_staged_total",
    "Bytes copied into host staging rings (and later across host->device), "
    "by layout: packed = byte-planar wire-width planes, unpacked = full "
    "uint32 limb planes, wire = raw serialized element blocks, "
    "wire-planar = v2 byte-planar element blocks staged packed.",
    ("layout",),
)

_unmask_kernel = jax.jit(p_mod_sub, static_argnames=("order",))


def _shard_map(fn, mesh, in_specs, out_specs):
    # pallas_call's out_shape carries no varying-mesh-axes info, so the
    # check is off for every per-shard body this module maps
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# auto-calibration verdicts, process-wide: a long-running coordinator builds
# a fresh aggregator every round but the (backend, shape, order) question has
# the same answer every time
_AUTO_KERNEL_CACHE: dict[tuple, str] = {}

# what the most recent kernel resolution in this process found — which
# kernel, how it was chosen and, for a race, every candidate's outcome. The
# aggregator that raced is gone by the next round, so the record lives here
# for the coordinator's start-up report and /healthz (fold_kernel_report)
_LAST_RESOLUTION: dict = {}

# how the last host batch folded in this process reached the device: "row"
# (copied row by row while it filled) or "batch" (one copy at its fold: a
# batch staged inside one call, or the fall-back after a failed row copy)
_LAST_H2D_ROUTE: str | None = None

_RACE_DRAWS = 3  # timed folds per race candidate; the fastest counts

# compiled fold callables, process-wide. jit caches by FUNCTION IDENTITY, so
# a per-aggregator closure would retrace and leak one executable per round
# on a long-running coordinator (observed ~4 MB RSS/round in the pallas
# soak before this cache); keyed by everything the closure captures
_FOLD_FN_CACHE: dict[tuple, object] = {}


def fold_kernel_report() -> dict:
    """The last fold-kernel resolution: ``kernel``, ``source`` (configured |
    race | only-candidate | cached | persisted), the vector and element it
    folded (``model_length``, ``n_limbs``, ``bytes_per_number``), the mesh
    decomposition it ran on (``acc_slices``: one ``[lo, hi)`` slice per device;
    ``shards`` of them, each ``shard_length`` columns with the padding),
    and for a race ``race`` (per candidate ``status`` = ``ok`` or
    ``failed: <ExceptionType>``, first-call and steady ``seconds``) plus
    ``results_equal``; and ``h2d_route``, the route the last folded host
    batch's bytes took to the device (``row`` | ``batch``, None before the
    streaming pipeline has folded one); and ``wire``, how many update vectors
    of the fold batch closed last came ``packed`` (wire v2) and ``legacy``
    (v1), and how many were ``copied`` into their slots as planes
    (``telemetry/wire.py``). Empty before the first fold."""
    if not _LAST_RESOLUTION:
        return {}
    return {**_LAST_RESOLUTION, "h2d_route": _LAST_H2D_ROUTE, "wire": wire_stats.last_batch()}


def note_h2d_route(route: str) -> None:
    """The streaming pipeline's fold workers say which route the batch they
    are about to fold took (``fold_kernel_report``)."""
    global _LAST_H2D_ROUTE
    _LAST_H2D_ROUTE = route


def _mesh_key(mesh) -> tuple:
    """Cache identity of a mesh: (axis shape, flat device ids).

    The ``Mesh`` object itself must NOT be the key: a coordinator that
    rebuilds its mesh every round (fresh ``make_mesh()`` per aggregator)
    would then grow the process-wide caches — and the compiled executables
    they hold — without bound, one entry per round, even though two meshes
    over the same devices in the same shape compile to the same program.
    """
    return (tuple(mesh.devices.shape), tuple(int(d.id) for d in mesh.devices.flat))


def _build_wire_unpack(bpn: int, order: int, multi_device: bool):
    """The ONE wire unpack + per-update validity + exclusion body, shared by
    the two-step and fused ingest builders so the accelerator-only fused
    path can never silently diverge from the CPU-tested two-step path.

    Runs inside jit (and, when ``multi_device``, inside shard_map, where the
    psum makes an update invalid on ANY shard excluded on every shard).
    ``raw`` is a batch ``uint8[K, bytes]`` (``ok`` is ``bool[K]``) or one
    update ``uint8[bytes]`` as its message held it (``ok`` is a scalar and
    the planar ``[L, n]``: the per-update road puts no batch axis on it).
    The function's name is the executable's (``jit_unpack_mask``), which a
    trace reader matches.
    """
    from ..ops import limbs_jax

    def unpack_mask(raw):
        count = raw.shape[-1] // bpn
        planar = limbs_jax.wire_bytes_to_planar(raw, count, bpn)
        ok = limbs_jax.planar_all_lt_const(planar, order)  # per update
        if multi_device:
            bad = jax.lax.psum((~ok).astype(jnp.uint32), MODEL_AXIS)
            ok = bad == jnp.uint32(0)
        planar = jnp.where(ok[..., None, None], planar, jnp.uint32(0))
        return planar, ok

    return unpack_mask


def _build_planar_ok(n_limbs: int, order: int, multi_device: bool):
    """Wire-v2 twin of ``_build_wire_unpack``, validity only: the input is
    already the byte-planar ``uint8[K, bpn, n]`` packed layout
    (serialization.py ``WIRE_PLANAR_FLAG``), so limb assembly reads
    contiguous planes (``limbs_jax.packed_planar_to_limbs``) — and only
    *transiently*, inside this jit. The caller keeps the packed bytes as
    the staged representation; no resident uint32 planar exists on the v2
    path until the fused packed fold. Same per-update validity + psum
    exclusion semantics as v1, and as there one update ``uint8[bpn, n]``
    gives a scalar. The executable is ``jit_planar_order_check``.
    """
    from ..ops import limbs_jax

    def planar_order_check(raw):
        planar = limbs_jax.packed_planar_to_limbs(raw, n_limbs)
        ok = limbs_jax.planar_all_lt_const(planar, order)  # per update
        if multi_device:
            bad = jax.lax.psum((~ok).astype(jnp.uint32), MODEL_AXIS)
            ok = bad == jnp.uint32(0)
        return ok

    return planar_order_check


# rows a resident fold stacks and folds at once (``StreamingAggregator.
# fold_resident_rows_now``): what a flush holds beside the rows themselves
RESIDENT_CHUNK = 8


def resident_footprint(rows: int, n_limbs: int, bpn: int, shard_len: int) -> int:
    """Device bytes, on one device, that a flush of ``rows`` updates accepted
    under wire ingest may hold at once: the rows, resident since each was
    accepted (``4 * n_limbs`` bytes an element: a v1 row, and a v1 body may
    come in any round; a v2 row is ``bpn``), one stacked chunk of them, the
    fold over that chunk at its worst (the start-up race of the fold kernels:
    the accumulator, a scratch, two kept results and temporaries up to 1.1x
    the arguments, as ``benchmark/harness/sizing.py::footprint`` reckons them
    from what the v5e compiler reports), and one raw body on its way in."""
    row = 4 * n_limbs * shard_len
    chunk = min(rows, RESIDENT_CHUNK) * row
    return rows * row + chunk + 4 * row + int(1.1 * (chunk + row)) + bpn * shard_len


def resident_rows_that_fit(limit: int, n_limbs: int, bpn: int, shard_len: int) -> int:
    """The largest flush, of those one fold may take, that
    ``resident_footprint`` puts within ``limit`` bytes."""
    rows = 0
    while rows < MAX_LAZY_BATCH and resident_footprint(rows + 1, n_limbs, bpn, shard_len) <= limit:
        rows += 1
    return rows


def device_memory_limit(mesh) -> int | None:
    """The least ``memory_stats()["bytes_limit"]`` of the mesh's devices;
    ``None`` where the backend reports none (the CPU backend)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in mesh.devices.flat]
    return None if not limits or any(not n for n in limits) else int(min(limits))


class ShardedAggregator:
    """Accumulates masked updates on-device, sharded over the model axis.

    ``kernel`` picks the fold implementation: ``"xla"`` (``ops.fold_jax``),
    ``"pallas"`` (the fused VMEM kernel, ``ops.fold_pallas``),
    ``"pallas-interpret"`` (same kernel through the Pallas interpreter — the
    CI path that keeps the grid/BlockSpec layout continuously exercised
    without a Mosaic compiler), or ``"auto"``: on accelerator backends the
    first fold times XLA vs Pallas on the real staged batch and keeps the
    winner; on CPU it short-circuits to XLA (interpret-mode Pallas is an
    oracle, not a production kernel). The choice actually taken is reported
    in ``kernel_used``.
    """

    def __init__(
        self,
        config: MaskConfig,
        model_length: int,
        mesh=None,
        kernel: str = "xla",
    ):
        if kernel not in FOLD_KERNELS:
            raise ValueError(f"kernel must be one of {FOLD_KERNELS}, got {kernel!r}")
        self.kernel = kernel
        self.kernel_used: str | None = None  # resolved on first fold
        self._fold_fn = None  # built once kernel_used resolves
        self.config = config
        self.model_length = model_length
        self.mesh = mesh if mesh is not None else make_mesh()
        n_dev = self.mesh.devices.size
        self.padded_length = pad_to_multiple(model_length, n_dev)
        self.n_limbs = host_limbs.n_limbs_for_order(config.order)
        self.order = config.order
        # planar shardings: model axis is the innermost (lane) dimension
        self._acc_sharding = NamedSharding(self.mesh, P(None, MODEL_AXIS))
        self._batch_sharding = NamedSharding(self.mesh, P(None, None, MODEL_AXIS))
        # raw wire bytes shard over the same model axis: padded_length is a
        # multiple of the mesh size, so every device's byte slice is
        # element-aligned (count/n elements x bpn bytes)
        self._batch_bytes_sharding = NamedSharding(self.mesh, P(None, MODEL_AXIS))
        # packed byte-planar staging batches [K, bpn, padded] shard over the
        # same model (lane) axis as the planar layout
        self._batch_packed_sharding = NamedSharding(self.mesh, P(None, None, MODEL_AXIS))
        # the single-source-of-truth pack width (ops/limbs.wire_width_for):
        # the streaming pipeline stages bpn bytes per element instead of
        # 4*L whenever that is actually narrower
        self.packed_width = host_limbs.wire_width_for(self.order)
        self._packed_fold_fn = None  # built once kernel_used resolves
        # reduce-scatter ownership: while a ShardPlan is adopted, the
        # per-shard buffers ARE the accumulator and `_acc` is stale — the
        # `acc` property reassembles on demand (the only gathers left are
        # explicit reads: snapshot/checkpoint/final download)
        self._live_plan = None
        self._acc = jax.device_put(
            jnp.zeros((self.n_limbs, self.padded_length), dtype=jnp.uint32), self._acc_sharding
        )
        self.nb_models = 0

    # -- reduce-scatter accumulator ownership -------------------------------

    @property
    def acc(self):
        """The global planar accumulator, a ``jax.Array`` always. With a
        live (adopted) shard plan the per-shard buffers are authoritative
        and this READ reassembles them on demand, zero-copy. The
        reduce-scatter contract:
        nothing gathers per drain window anymore; only explicit reads
        (snapshot, checkpoint, the final model download) pay the gather."""
        plan = self._live_plan
        if plan is not None:
            return plan.reassemble()
        return self._acc

    @acc.setter
    def acc(self, value):
        # an explicit accumulator write (restore/reset/non-sharded fold)
        # supersedes any adopted plan — the per-shard buffers are stale
        if self._live_plan is not None:
            self._live_plan = None
        self._acc = value

    def adopt_plan(self, plan) -> None:
        """Adopt a :class:`~xaynet_tpu.parallel.shards.ShardPlan` as the
        authoritative accumulator (the streaming pipeline's reduce-scatter
        handoff). The plan persists across drain windows; ``acc`` reads
        reassemble on demand."""
        self._live_plan = plan

    def _to_planar_padded(self, stack: np.ndarray) -> np.ndarray:
        """Wire ``[K, n, L]`` -> planar padded ``[K, L, padded_len]`` (host)."""
        planar = wire_to_planar(stack)
        if self.padded_length != planar.shape[2]:
            planar = np.pad(planar, ((0, 0), (0, 0), (0, self.padded_length - planar.shape[2])))
        return planar

    def add_batch(self, stack) -> None:
        """Fold wire-layout ``uint32[K, model_len, L]`` updates into the aggregate.

        Zero padding columns are valid group elements, so padding never
        affects the real slice.
        """
        stack = np.asarray(stack, dtype=np.uint32)
        if stack.ndim != 3 or stack.shape[2] != self.n_limbs:
            raise ValueError("expected uint32[K, model_len, L]")
        if stack.shape[1] != self.model_length:
            raise ValueError("model length mismatch")
        if stack.shape[0] > MAX_LAZY_BATCH:
            raise ValueError("batch too large for lazy-carry fold")
        planar = self._to_planar_padded(stack)
        staged = jax.device_put(planar, self._batch_sharding)
        self.acc = self._fold(self.acc, staged)
        self.nb_models += stack.shape[0]

    def _stage_raw_bytes(self, raw: np.ndarray):
        """Shared guard + pad + upload for raw wire element blocks: validate
        dtype/shape, zero-pad to the padded length (zero bytes decode to
        zero elements — valid and fold-neutral), and device_put with the
        element-aligned byte-axis sharding. Used by the batch ingest AND
        the per-update validate path so the two can never diverge."""
        bpn = self.config.bytes_per_number
        raw = np.asarray(raw)
        if raw.dtype != np.uint8 or raw.ndim != 2 or raw.shape[1] != self.model_length * bpn:
            raise ValueError("expected uint8[K, model_len * bytes_per_number]")
        if raw.shape[0] > MAX_LAZY_BATCH:
            raise ValueError("batch too large for lazy-carry fold")
        if self.padded_length != self.model_length:
            raw = np.pad(raw, ((0, 0), (0, (self.padded_length - self.model_length) * bpn)))
        BYTES_STAGED.labels(layout="wire").inc(raw.nbytes)
        return jax.device_put(raw, self._batch_bytes_sharding)

    def add_wire_batch(self, raw: np.ndarray) -> np.ndarray:
        """Fold RAW wire element blocks ``uint8[K, model_len * bpn]``.

        The device-ingest fast path: ships the serialized little-endian
        element block as-is (``bpn/(4 L)`` of the limb-tensor size — 75%
        for the 6-byte f32/M3 configs, 87.5% for 7-byte M6), then unpacks,
        validity-checks, and folds entirely on device — the coordinator
        never runs a host-side element parse (the second hot loop after
        the fold; reference parses per element, vect.rs:24-80).

        Validity is per update: an update with any element >= the group
        order is EXCLUDED from the fold (zeroed — the additive identity)
        and not counted in ``nb_models``, mirroring the reference's
        per-message rejection (the coordinator must reject it before its
        seed-dict insert). Returns the ``bool[K]`` acceptance vector.
        """
        return self._ingest_staged_bytes(self._stage_raw_bytes(raw))

    # -- one update, as its message holds it (the K = 1 road) ---------------
    #
    # What ``StagedAggregator.validate_aggregation`` runs for every update
    # under ``[aggregation] wire_ingest``: the element block goes to the
    # device as the view of the body that the lazy parse made (no stack, no
    # copy, no host pad), the device de-interleaves it and compares every
    # element with the order, and the verdict comes back before the caller's
    # seed-dict insert. Two steps, so that the caller can time the link and
    # the kernel apart (``ingest.h2d`` / ``ingest.unpack``).

    def _put_update(self, block: np.ndarray, bpe: int):
        """``block`` (a host view whose LAST axis is the model axis, ``bpe``
        bytes an element along it) on the mesh, padded to the padded length,
        the transfer complete when this returns: the body's pages may be
        taken for another body the moment its message is answered. One
        device takes the view whole. On a mesh every device takes its own
        columns of the view, and the one that holds the end of the model
        pads its piece with zeros itself (zero bytes decode to zero
        elements, valid and fold-neutral)."""
        lead = block.shape[:-1]
        sharding = NamedSharding(self.mesh, P(*([None] * len(lead)), MODEL_AXIS))
        if self.mesh.devices.size == 1:
            return jax.block_until_ready(jax.device_put(block, sharding))  # lint: sync-ok
        shape = (*lead, self.padded_length * bpe)
        pieces = []
        for device, index in sharding.addressable_devices_indices_map(shape).items():
            lo, hi = index[-1].start or 0, index[-1].stop or shape[-1]
            piece = jax.device_put(block[..., lo : min(hi, block.shape[-1])], device)
            if piece.shape[-1] < hi - lo:
                pad = [(0, 0)] * len(lead) + [(0, hi - lo - piece.shape[-1])]
                piece = jnp.pad(piece, pad)
            pieces.append(piece)
        return jax.block_until_ready(  # lint: sync-ok
            jax.make_array_from_single_device_arrays(shape, sharding, pieces)
        )

    def put_wire_update(self, raw: np.ndarray):
        """ONE raw v1 element block ``uint8[model_len * bpn]`` on the device
        as it lies: ``uint8[padded_len * bpn]``."""
        bpn = self.config.bytes_per_number
        raw = np.asarray(raw)  # an array as it is: no copy  # lint: sync-ok
        if raw.dtype != np.uint8 or raw.shape != (self.model_length * bpn,):
            raise ValueError("expected uint8[model_len * bytes_per_number]")
        BYTES_STAGED.labels(layout="wire").inc(raw.nbytes)
        return self._put_update(raw, bpn)

    def unpack_put_update(self, staged):
        """De-interleave + validity-check the update ``put_wire_update`` put.
        Returns the device-resident planar ``[L, padded_len]`` (already
        validity-masked) for later staging, or ``None`` if any element is
        >= the group order."""
        planar, ok = profiling.timed_kernel(
            "wire_unpack", self.padded_length, lambda: self._make_unpack_fn(one=True)(staged)
        )
        return planar if bool(ok) else None  # the verdict's sync  # lint: sync-ok

    def validate_wire_update(self, raw: np.ndarray):
        """Unpack + validity-check ONE raw wire update on device.

        The coordinator's per-update validation step when wire ingest is on
        (reference ordering: validate BEFORE the seed-dict insert,
        update.rs:119-152): ``put_wire_update`` then ``unpack_put_update``.
        """
        return self.unpack_put_update(self.put_wire_update(raw))

    def put_planar_update(self, planes: np.ndarray):
        """Wire-v2: ONE byte-planar element block ``uint8[bpn, model_len]``
        (the body's planes viewed 2-D) on the device as it lies:
        ``uint8[bpn, padded_len]``, which is also the row that stays."""
        planes = np.asarray(planes)  # an array as it is: no copy  # lint: sync-ok
        if planes.dtype != np.uint8 or planes.shape != (
            self.config.bytes_per_number,
            self.model_length,
        ):
            raise ValueError("expected uint8[bytes_per_number, model_len]")
        BYTES_STAGED.labels(layout="wire-planar").inc(planes.nbytes)
        return self._put_update(planes, 1)

    def check_put_update(self, staged):
        """Validity-check the update ``put_planar_update`` put. Returns it, or
        ``None`` if any element is >= the group order. The accepted row stays
        PACKED (``uint8[bpn, padded_len]``): the uint32 limb expansion only
        ever happens transiently inside the validity/fold jits, never as a
        resident buffer."""
        ok = profiling.timed_kernel(
            "wire_unpack", self.padded_length, lambda: self._make_planar_ok_fn(one=True)(staged)
        )
        return staged if bool(ok) else None  # the verdict's sync  # lint: sync-ok

    def validate_planar_update(self, raw: np.ndarray):
        """Wire-v2 twin of ``validate_wire_update``: ``put_planar_update``
        then ``check_put_update``."""
        return self.check_put_update(self.put_planar_update(raw))

    def validate_wire_updates(self, raws) -> list:
        """Unpack + validity-check a GROUP of raw wire updates in ONE device
        round-trip: one staged upload, one unpack+validity dispatch, one
        acceptance-vector fetch — where the per-update path pays a full
        dispatch + blocking ``np.asarray(ok)`` sync per update. Semantics
        are per update and identical to ``validate_wire_update``: the
        returned list is parallel to ``raws``, holding the validity-masked
        device planar ``[L, padded_len]`` for accepted updates and ``None``
        for any whose element is >= the group order.
        """
        if not raws:
            return []
        block = np.stack([np.asarray(r) for r in raws])
        # bucket K to the next power of two: the unpack jit specializes on
        # the batch dimension, and coalescer linger timeouts produce ragged
        # group sizes — without bucketing every new K would stall the
        # update phase on a fresh XLA compile mid-round. Zero pad rows
        # decode to zero elements (valid group members) and are sliced off
        # below; at most log2(batch) programs ever compile.
        k = len(raws)
        bucket = min(1 << max(0, k - 1).bit_length(), MAX_LAZY_BATCH)
        if bucket > k:
            block = np.concatenate(
                [block, np.zeros((bucket - k, block.shape[1]), dtype=block.dtype)]
            )
        staged = self._stage_raw_bytes(block)
        planar, ok = profiling.timed_kernel(
            "wire_unpack",
            staged.shape[0] * self.padded_length,
            lambda: self._make_unpack_fn()(staged),
        )
        ok_host = np.asarray(ok)
        return [planar[i] if ok_host[i] else None for i in range(k)]

    def validate_planar_updates(self, raws) -> list:
        """Wire-v2 twin of ``validate_wire_updates``: one staged upload +
        validity dispatch + acceptance fetch for a group of byte-planar
        element blocks. The upload IS the packed staging layout — no byte
        gather on either side of the transfer — and the returned rows are
        the staged PACKED device slices (``uint8[bpn, padded_len]``), so an
        accepted v2 update occupies ``bpn`` bytes/element until the packed
        fold consumes it, where the v1 path parks a ``4L``-byte planar.
        ``None`` marks members with an element >= the group order.
        """
        if not raws:
            return []
        bpn = self.config.bytes_per_number
        block = np.stack([np.asarray(r) for r in raws])
        if block.dtype != np.uint8 or block.ndim != 3 or block.shape[1:] != (
            bpn,
            self.model_length,
        ):
            raise ValueError("expected uint8[K, bytes_per_number, model_len]")
        if self.padded_length != self.model_length:
            block = np.pad(
                block, ((0, 0), (0, 0), (0, self.padded_length - self.model_length))
            )
        # same power-of-two bucketing as the v1 path (ragged coalescer
        # groups must not recompile the unpack mid-round); zero planes
        # decode to zero elements, valid and sliced off below
        k = len(raws)
        bucket = min(1 << max(0, k - 1).bit_length(), MAX_LAZY_BATCH)
        if bucket > k:
            block = np.concatenate(
                [block, np.zeros((bucket - k, *block.shape[1:]), dtype=block.dtype)]
            )
        BYTES_STAGED.labels(layout="wire-planar").inc(block.nbytes)
        staged = jax.device_put(block, self._batch_packed_sharding)
        ok = profiling.timed_kernel(
            "wire_unpack",
            staged.shape[0] * self.padded_length,
            lambda: self._make_planar_ok_fn()(staged),
        )
        ok_host = np.asarray(ok)
        return [staged[i] if ok_host[i] else None for i in range(k)]

    def dispatch_staged_bytes(self, staged):
        """Unpack + validity + fold a staged raw-byte batch WITHOUT syncing
        the acceptance vector: returns the device ``ok`` array still in
        flight. The caller owns the deferred accounting — it must fetch the
        vector eventually and credit ``nb_models`` (what
        ``_ingest_staged_bytes`` does inline, and the streaming pipeline
        does once per drain instead of once per batch)."""
        n_elements = staged.shape[0] * self.padded_length
        if (
            self._fold_fn is not None
            and self.kernel_used == "xla"
            and jax.default_backend() != "cpu"
        ):
            # steady state on accelerators: one fused jit — unpack, validity
            # mask, and fold in a single XLA program, so the intermediate
            # planar tensor (K*L*padded*4 bytes, 8/bpn x the wire bytes)
            # never round-trips HBM. On CPU the two-step path measures ~8%
            # faster (no HBM economics), so fusion stays accelerator-only.
            self.acc, ok = profiling.timed_kernel(
                "wire_ingest",
                n_elements,
                lambda: self._make_ingest_fn()(self.acc, staged),
            )
        else:
            # first call (kernel not yet resolved — auto calibration needs a
            # planar staged batch), a Pallas fold (pallas_call reads its
            # operand from HBM, so fusion would not help), or a CPU backend:
            # two-step path
            planar, ok = profiling.timed_kernel(
                "wire_unpack", n_elements, lambda: self._make_unpack_fn()(staged)
            )
            # dispatch the fold BEFORE syncing the acceptance vector: the
            # fold then overlaps the host-side ok fetch (when kernel
            # profiling is on, the sync points serialize this overlap —
            # XAYNET_KERNEL_PROFILE=0 restores it exactly)
            self.acc = self._fold(self.acc, planar)
        return ok

    def _ingest_staged_bytes(self, staged) -> np.ndarray:
        """Unpack + validity + fold an already device/mesh-resident raw-byte
        batch (``add_wire_batch`` after device_put) with an immediate
        acceptance sync."""
        ok_host = np.asarray(self.dispatch_staged_bytes(staged))
        self.nb_models += int(ok_host.sum())
        return ok_host

    # -- kernel selection ---------------------------------------------------

    def _zero_acc(self):
        return jax.device_put(
            jnp.zeros((self.n_limbs, self.padded_length), dtype=jnp.uint32), self._acc_sharding
        )

    def _make_fold_fn(self, kernel: str):
        """The fold callable for ``kernel``, memoized process-wide.

        jit caches by function identity: building a fresh closure per
        aggregator (one per round) would recompile every round and retain
        every old executable.
        """
        if kernel in ("pallas", "pallas-interpret"):
            interpret = kernel == "pallas-interpret"
            key = (kernel, _mesh_key(self.mesh), self.order)
            fn = _FOLD_FN_CACHE.get(key)
            if fn is None:
                from ..ops import fold_pallas

                order = self.order

                def call(a, s):
                    # late module-attribute lookup so test spies see the call
                    return fold_pallas.fold_planar_batch_pallas(
                        a, s, order, interpret=interpret
                    )

                if self.mesh.devices.size > 1:
                    # the fold is elementwise along the model axis, so each
                    # device runs the Pallas kernel on its local shard —
                    # shard_map makes the kernel multichip without a custom
                    # partitioner; the outer jit restores accumulator donation
                    fn = jax.jit(
                        _shard_map(
                            call,
                            mesh=self.mesh,
                            in_specs=(P(None, MODEL_AXIS), P(None, None, MODEL_AXIS)),
                            out_specs=P(None, MODEL_AXIS),
                        ),
                        donate_argnums=(0,),
                    )
                else:
                    fn = call
                _FOLD_FN_CACHE[key] = fn
            return fn
        key = ("xla", self.order)
        fn = _FOLD_FN_CACHE.get(key)
        if fn is None:
            order = self.order
            fn = _FOLD_FN_CACHE[key] = lambda a, s: fold_planar_batch(a, s, order)
        return fn

    def packed_staging_usable(self) -> bool:
        """Whether packed byte-planar staging actually shrinks anything
        (``ops/limbs.py::packed_staging_usable``)."""
        return host_limbs.packed_staging_usable(self.order)

    def _make_packed_fold_fn(self, kernel: str):
        """The packed-batch fold callable for ``kernel`` (byte-planar
        ``uint8[K, bpn, padded]`` input), memoized process-wide like the
        planar fold fns. Device kernels fuse the in-graph unpack with the
        fold in one jit (``ops.fold_jax.fold_packed_batch``) so only packed
        bytes cross host->device; Pallas kernels unpack in a separate jit
        (``pallas_call`` reads its operand from HBM — fusion buys nothing)."""
        n_limbs, order = self.n_limbs, self.order
        if kernel in ("pallas", "pallas-interpret"):
            from ..ops.limbs_jax import packed_planar_to_limbs_jit

            base_fold = self._make_fold_fn(kernel)
            return lambda a, p: base_fold(a, packed_planar_to_limbs_jit(p, n_limbs))
        key = ("xla-packed", _mesh_key(self.mesh), n_limbs, order)
        fn = _FOLD_FN_CACHE.get(key)
        if fn is None:
            if self.mesh.devices.size > 1:

                def call(a, p):
                    return fold_packed_batch(a, p, n_limbs, order)

                fn = jax.jit(
                    _shard_map(
                        call,
                        mesh=self.mesh,
                        in_specs=(P(None, MODEL_AXIS), P(None, None, MODEL_AXIS)),
                        out_specs=P(None, MODEL_AXIS),
                    ),
                    donate_argnums=(0,),
                )
            else:
                fn = lambda a, p: fold_packed_batch(a, p, n_limbs, order)
            _FOLD_FN_CACHE[key] = fn
        return fn

    def _fold_packed(self, acc, staged_packed):
        """Fold a packed byte-planar staged batch (same ``masked_add``
        telemetry op as the planar fold: one /metrics series answers 'how
        fast is the masked add' whichever staging layout fed it). Callers
        resolve ``kernel_used`` first — packed staging never drives the
        auto-calibration (that races on a planar batch)."""
        if self._packed_fold_fn is None:
            if self.kernel_used is None:
                raise RuntimeError("kernel must be resolved before a packed fold")
            self._packed_fold_fn = self._make_packed_fold_fn(self.kernel_used)
        return profiling.timed_kernel(
            "masked_add",
            staged_packed.shape[0] * staged_packed.shape[-1],
            lambda: self._packed_fold_fn(acc, staged_packed),
        )

    def _make_unpack_fn(self, one: bool = False):
        """Device wire-unpack + validity callable, memoized process-wide
        (same identity-caching rationale as the fold fns). ``one``: for one
        update with no batch axis (the per-update road)."""
        bpn = self.config.bytes_per_number
        key = ("unpack", _mesh_key(self.mesh), bpn, self.order, one)
        fn = _FOLD_FN_CACHE.get(key)
        if fn is not None:
            return fn
        multi = self.mesh.devices.size > 1
        unpack_mask = _build_wire_unpack(bpn, self.order, multi)
        if multi:
            batch = () if one else (None,)
            fn = jax.jit(
                _shard_map(
                    unpack_mask,
                    mesh=self.mesh,
                    in_specs=(P(*batch, MODEL_AXIS),),
                    out_specs=(P(*batch, None, MODEL_AXIS), P()),
                )
            )
        else:
            fn = jax.jit(unpack_mask)
        _FOLD_FN_CACHE[key] = fn
        return fn

    def _make_planar_ok_fn(self, one: bool = False):
        """Device planar (wire-v2) validity callable, memoized process-wide
        (same identity-caching rationale as ``_make_unpack_fn``, and its
        ``one``). Output is only ``ok[K]`` — the staged packed bytes
        themselves are the result."""
        key = ("planar-ok", _mesh_key(self.mesh), self.n_limbs, self.order, one)
        fn = _FOLD_FN_CACHE.get(key)
        if fn is not None:
            return fn
        multi = self.mesh.devices.size > 1
        check = _build_planar_ok(self.n_limbs, self.order, multi)
        if multi:
            batch = () if one else (None,)
            fn = jax.jit(
                _shard_map(
                    check,
                    mesh=self.mesh,
                    in_specs=(P(*batch, None, MODEL_AXIS),),
                    out_specs=P(),
                )
            )
        else:
            fn = jax.jit(check)
        _FOLD_FN_CACHE[key] = fn
        return fn

    def _make_stack_fn(self):
        """``jnp.stack`` of resident rows into the chunk ``[k, ...]`` as the
        fold takes it (the model axis over the mesh; planar and packed chunks
        are both ``[k, planes, padded]``): one device copy, under a name a
        trace reader can match (``jit_stack_resident_rows``). Memoized
        process-wide, as the fold fns are."""
        key = ("stack", _mesh_key(self.mesh))
        fn = _FOLD_FN_CACHE.get(key)
        if fn is None:

            def stack_resident_rows(*rows):
                return jnp.stack(rows)

            fn = _FOLD_FN_CACHE[key] = jax.jit(
                stack_resident_rows, out_shardings=self._batch_sharding
            )
        return fn

    def _make_ingest_fn(self):
        """Fused wire ingest: the shared unpack+validity body composed with
        the XLA fold in ONE jit (donated accumulator), memoized
        process-wide."""
        bpn = self.config.bytes_per_number
        key = ("ingest", _mesh_key(self.mesh), bpn, self.order)
        fn = _FOLD_FN_CACHE.get(key)
        if fn is not None:
            return fn
        multi = self.mesh.devices.size > 1
        unpack_mask = _build_wire_unpack(bpn, self.order, multi)
        order = self.order

        def ingest(acc, raw):
            planar, ok = unpack_mask(raw)
            return fold_planar_batch(acc, planar, order), ok

        if multi:
            fn = jax.jit(
                _shard_map(
                    ingest,
                    mesh=self.mesh,
                    in_specs=(P(None, MODEL_AXIS), P(None, MODEL_AXIS)),
                    out_specs=(P(None, MODEL_AXIS), P()),
                ),
                donate_argnums=(0,),
            )
        else:
            fn = jax.jit(ingest, donate_argnums=(0,))
        _FOLD_FN_CACHE[key] = fn
        return fn

    def _auto_cache_key(self, k: int) -> tuple:
        """Auto-verdict memo key. K is part of it: a verdict timed on a
        small remainder flush must not bind the steady-state batch size
        (and vice versa); the mesh size too — same padded_length on
        different meshes means a different per-device shard (ADVICE r04)."""
        return (
            jax.default_backend(),
            self.mesh.devices.size,
            self.n_limbs,
            self.padded_length,
            self.order,
            k,
        )

    def _record_resolution(self, source: str, **extra) -> None:
        from .mesh import shard_slices

        global _LAST_RESOLUTION
        _LAST_RESOLUTION = {
            "kernel": self.kernel_used,
            "source": source,
            "model_length": self.model_length,
            "n_limbs": self.n_limbs,
            "bytes_per_number": self.config.bytes_per_number,
            "acc_slices": shard_slices(self.padded_length, self.mesh.devices.size),
            "shards": self.mesh.devices.size,
            "shard_length": self.padded_length // self.mesh.devices.size,
            **extra,
        }

    def _resolve_kernel_cheap(self, k: int) -> None:
        """Resolve ``kernel_used`` when no timing run is needed — explicit
        kernel, or an auto verdict already memoized for this shape."""
        if self.kernel_used is not None:
            return
        if self.kernel != "auto":
            self.kernel_used = self.kernel
            self._record_resolution("configured")
            return
        key = self._auto_cache_key(k)
        cached = _AUTO_KERNEL_CACHE.get(key)
        if cached is not None:
            self.kernel_used = cached
            self._record_resolution("cached")
            logger.info("aggregation kernel resolved: %s (auto, cached verdict)", cached)
            return
        # disk tier (utils.calibcache): a verdict a PREVIOUS process raced
        # under the same environment fingerprint — the fresh process's
        # first round skips the probe race entirely
        from ..utils import calibcache

        warm = calibcache.get("fold", key)
        if warm is not None:
            _AUTO_KERNEL_CACHE[key] = warm
            self.kernel_used = warm
            self._record_resolution("persisted")
            logger.info("aggregation kernel resolved: %s (auto, persisted verdict)", warm)

    def _fold(self, acc, staged):
        if self._fold_fn is None:
            self._resolve_kernel(staged)  # may already set _fold_fn (winner)
            if self._fold_fn is None:
                self._fold_fn = self._make_fold_fn(self.kernel_used)
        # device-synced timing of the masked modular add (the hot path);
        # staged is planar [K, L, padded_len] -> K x padded group elements
        return profiling.timed_kernel(
            "masked_add",
            staged.shape[0] * staged.shape[-1],
            lambda: self._fold_fn(acc, staged),
        )

    def _resolve_kernel(self, staged) -> None:
        """Fix ``kernel_used`` for the aggregator's lifetime.

        ``auto`` races the candidate kernels on the first real staged batch
        and keeps the faster steady-state time. Every candidate starts from
        its own fresh zero accumulator (the folds donate their input, so a
        leg that dies must not hand a possibly-consumed scratch to the
        next), every outcome is kept in the resolution record
        (:func:`fold_kernel_report`), and the candidates' results are
        compared once: the kernels are interchangeable only if they agree
        bit for bit. A candidate that fails is an ERROR, not a fallback —
        the round goes on with a survivor, but the verdict of such a race
        is never memoized or persisted, so the failure shows again on the
        next round instead of hiding behind a cached winner. No survivor at
        all raises.
        """
        self._resolve_kernel_cheap(staged.shape[0])
        if self.kernel_used is not None:
            return
        backend = jax.default_backend()
        key = self._auto_cache_key(staged.shape[0])
        # interpret-mode Pallas is an oracle, not a production kernel
        candidates = ["xla"] if backend == "cpu" else ["xla", "pallas"]
        if len(candidates) == 1:
            self.kernel_used = candidates[0]
            self._record_resolution("only-candidate")
        else:
            race, results, fns = {}, {}, {}
            for name in candidates:
                try:
                    fold = self._make_fold_fn(name)
                    scratch = self._zero_acc()
                    # compile / first touch
                    scratch, first = profiling.measure(lambda: fold(scratch, staged))
                    # best of three: one draw is not a verdict (on the v5e a
                    # single timed Pallas fold read 14 ms in one process and
                    # 176 ms in the next, flipping the winner between runs)
                    dt = float("inf")
                    for _ in range(_RACE_DRAWS):
                        scratch, draw = profiling.measure(lambda: fold(scratch, staged))
                        dt = min(dt, draw)
                    profiling.record_calibration(name, dt)
                    race[name] = {
                        "status": "ok",
                        "first_call_seconds": round(first, 4),
                        "seconds": round(dt, 6),
                    }
                    results[name], fns[name] = scratch, fold
                except Exception as e:
                    logger.error(
                        "aggregation kernel candidate %s FAILED: %s: %s",
                        name, type(e).__name__, e, exc_info=True,
                    )
                    race[name] = {"status": f"failed: {type(e).__name__}"}
            if not results:
                self._record_resolution("race", race=race)
                raise RuntimeError(f"no fold kernel candidate ran on {backend}: {race}")
            # every survivor folded the same batch the same number of times,
            # from zeros
            outs = list(results.values())
            equal = all(bool(jnp.array_equal(outs[0], o)) for o in outs[1:])
            if not equal:
                self._record_resolution("race", race=race, results_equal=False)
                raise RuntimeError(
                    f"fold kernel candidates disagree on the same batch: {sorted(results)}"
                )
            self.kernel_used = min(results, key=lambda n: race[n]["seconds"])
            self._record_resolution("race", race=race, results_equal=True)
            # keep the winner's already-compiled callable
            self._fold_fn = fns[self.kernel_used]
            logger.info("aggregation kernel auto-calibration: %s -> %s", race, self.kernel_used)
            if len(results) < len(candidates):
                return  # a candidate failed: this verdict is never memoized
        _AUTO_KERNEL_CACHE[key] = self.kernel_used
        from ..utils import calibcache

        calibcache.put("fold", key, self.kernel_used)
        logger.info(
            "aggregation kernel resolved: %s (auto on %s backend)", self.kernel_used, backend
        )

    def mask_planar(self, mask_vect) -> np.ndarray:
        """Normalize an aggregated mask (wire or planar) to the padded
        planar layout every unmask path subtracts in — shared by
        :meth:`unmask_limbs` and the eager per-shard unmask staging
        (docs/DESIGN.md §22), which needs the planar before the drain."""
        mask = np.asarray(mask_vect, dtype=np.uint32)
        if mask.shape == (self.n_limbs, self.padded_length):
            return mask
        if mask.shape == (self.model_length, self.n_limbs):
            mask = mask.T
        # one pass: the transposition is written straight into the padded
        # array, of which only the padding columns are zeroed (not a
        # contiguous transpose that a pad then copies)
        planar = np.empty((self.n_limbs, self.padded_length), dtype=np.uint32)
        planar[:, mask.shape[1] :] = 0
        planar[:, : mask.shape[1]] = mask
        return planar

    def unmask_limbs(self, mask_vect) -> np.ndarray:
        """Subtract the aggregated mask; returns host wire ``uint32[model_len, L]``
        (:meth:`unmask_planar` and the transposition a wire caller asks for)."""
        return self.unmask_planar(mask_vect).wire()

    def unmask_planar(self, mask_vect) -> PlanarLimbs:
        """Subtract the aggregated mask and fetch the result as it lies on
        the device: host planes ``uint32[L, stride]``, the layout every
        device arm hands to the decode (docs/DESIGN.md §16)."""
        if self._live_plan is not None:
            # reduce-scatter unmask: each shard subtracts ITS slice of the
            # mask against its own accumulator buffer — the aggregate is
            # never reassembled before subtraction, and the only gather is
            # the unmasked result crossing to the host for decode (the
            # final model download)
            return profiling.timed_kernel(
                "unmask",
                self.padded_length,
                lambda: self._unmask_plan(self._live_plan, mask_vect),
            )
        # three stages of the Unmask phase (telemetry/unmask.py), each ended
        # by a wait for its own result: what would otherwise be paid inside
        # the fetch is told apart, and nothing runs beside them to overlap
        with unmask_stages.stage("mask_put", bytes=np.asarray(mask_vect).nbytes):
            planar = self.mask_planar(mask_vect)
            mask_dev = jax.block_until_ready(  # lint: sync-ok
                jax.device_put(jnp.asarray(planar), self._acc_sharding)
            )
        with unmask_stages.stage("subtract", bytes=planar.nbytes):
            out = jax.block_until_ready(  # lint: sync-ok
                profiling.timed_kernel(
                    "unmask",
                    self.padded_length,
                    lambda: _unmask_kernel(self.acc, mask_dev, self.order),
                )
            )
        # device to host, the copy alone: the padded planes as they are
        with unmask_stages.stage("fetch", bytes=self.model_length * self.n_limbs * 4):
            return PlanarLimbs(np.asarray(out), self.model_length)

    def unmask_out(self) -> PlanarLimbs:
        """The host planes the mesh arms assemble their shards' unmasked
        slices in (a contiguous copy a limb plane each)."""
        return PlanarLimbs(
            np.empty((self.n_limbs, self.model_length), dtype=np.uint32), self.model_length
        )

    def unmask_shard(self, plan, d: int, mask_planar: np.ndarray, out: PlanarLimbs) -> None:
        """One shard's leg of the reduce-scatter unmask: subtract shard
        ``d``'s slice of the aggregated mask against its own accumulator
        buffer and write the unmasked planar slice into ``out``. Shared by
        the drain-time ``_unmask_plan`` pass and the eager per-shard
        unmask tail jobs (docs/DESIGN.md §22), which run it concurrently
        from the shard workers — distinct ``out`` column ranges per shard,
        no synchronization needed."""
        lo, hi = plan.slices[d]
        real_hi = min(hi, self.model_length)
        if lo >= real_hi:
            return
        mask_dev = jax.device_put(
            np.ascontiguousarray(mask_planar[:, lo:hi]), plan.devices[d]
        )
        res = _unmask_kernel(plan.accs[d], mask_dev, self.order)  # lint: guarded-ok: drain barrier read
        # deliberate barrier: the unmasked slice is this shard's FINAL device
        # read of the round — the eager tail job (or the drain pass) fetches
        # it here so Unmask never touches the device again  # lint: sync-ok
        out.planes[:, lo:real_hi] = np.asarray(res)[:, : real_hi - lo]  # lint: sync-ok

    def _unmask_plan(self, plan, mask_vect) -> PlanarLimbs:
        """Per-shard in-place unmask against a live reduce-scatter plan:
        one subtract per device (all in flight before the first fetch) —
        only the UNMASKED per-shard slices move, once, into the host
        planes."""
        out = self.unmask_out()
        # the stages of the one-device arm (``unmask_planar``), a shard each:
        # every slice is put before any subtract is dispatched, and every
        # subtract dispatched before any result is fetched, so the
        # per-device transfers and kernels overlap within their stage
        with unmask_stages.stage("mask_put", bytes=np.asarray(mask_vect).nbytes):
            mask_planar = self.mask_planar(mask_vect)
            masks = jax.block_until_ready([  # lint: sync-ok
                jax.device_put(np.ascontiguousarray(mask_planar[:, lo:hi]), plan.devices[d])
                for d, (lo, hi) in enumerate(plan.slices)
            ])
        with unmask_stages.stage("subtract", bytes=mask_planar.nbytes):
            results = jax.block_until_ready([  # lint: sync-ok
                _unmask_kernel(acc, mask_dev, self.order)  # lint: guarded-ok: drain barrier read
                for acc, mask_dev in zip(plan.accs, masks)
            ])
        with unmask_stages.stage("fetch", bytes=out.nbytes):
            for (lo, hi), res in zip(plan.slices, results):
                real_hi = min(hi, self.model_length)
                if lo < real_hi:
                    out.planes[:, lo:real_hi] = np.asarray(res)[:, : real_hi - lo]
            BYTES_REDUCED.labels(path="gather").inc(out.nbytes)
            return out

    def snapshot(self) -> np.ndarray:
        """Host wire-layout copy of the aggregate (checkpoints / tests)."""
        return np.ascontiguousarray(np.asarray(self.acc)[:, : self.model_length].T)

    def restore(self, wire: np.ndarray, nb_models: int) -> None:
        """Restore from a host wire-layout snapshot."""
        planar = self._to_planar_padded(wire[None, :, :])[0]
        self.acc = jax.device_put(jnp.asarray(planar), self._acc_sharding)
        self.nb_models = nb_models

    def snapshot_shards(self) -> list[tuple[int, int, np.ndarray]]:
        """Packed per-shard planes ``[(lo, hi, uint32[L, hi-lo])]`` of the
        PADDED model axis — the journal form that lets a device round
        checkpoint without reassembling the global accumulator (each plane
        is one device/shard slice, fetched independently)."""
        plan = self._live_plan
        if plan is not None:
            return [
                (lo, hi, np.asarray(acc))  # lint: guarded-ok: drain barrier read
                for (lo, hi), acc in zip(plan.slices, plan.accs)
            ]
        planes: dict[int, tuple[int, int, np.ndarray]] = {}
        for s in self._acc.addressable_shards:
            col = s.index[1]
            lo = col.start if col.start is not None else 0
            hi = col.stop if col.stop is not None else self.padded_length
            if lo not in planes:  # replicated shardings repeat slices
                planes[lo] = (lo, hi, np.asarray(s.data))
        return [planes[lo] for lo in sorted(planes)]

    def restore_shards(self, planes: list[tuple[int, int, np.ndarray]], nb_models: int) -> None:
        """Restore the planar accumulator from journal planes, shard-exact
        when the current mesh decomposition matches the journaled one (one
        ``device_put`` per plane, no host-side global assembly), host-side
        concat + scatter otherwise (mesh shape changed across the restart)."""
        shape = (self.n_limbs, self.padded_length)
        target = None
        try:
            index_map = self._acc_sharding.addressable_devices_indices_map(shape)
            by_lo = {lo: np.ascontiguousarray(p, dtype=np.uint32) for lo, _hi, p in planes}
            arrays = []
            for dev, idx in index_map.items():
                col = idx[1]
                lo = col.start if col.start is not None else 0
                hi = col.stop if col.stop is not None else self.padded_length
                plane = by_lo[lo]  # KeyError -> decomposition mismatch -> fallback
                if plane.shape != (self.n_limbs, hi - lo):
                    raise ValueError(f"plane [{lo},{hi}) shape {plane.shape}")
                arrays.append(jax.device_put(plane, dev))
            target = jax.make_array_from_single_device_arrays(
                shape, self._acc_sharding, arrays
            )
        except (KeyError, ValueError, TypeError) as exc:
            logger.info("shard-exact restore unavailable (%s); reassembling on host", exc)
        if target is None:
            planar = np.zeros(shape, dtype=np.uint32)
            for lo, hi, plane in planes:
                planar[:, lo:hi] = plane
            target = jax.device_put(jnp.asarray(planar), self._acc_sharding)
        self.acc = target  # setter drops any stale plan; streaming re-leases
        self.nb_models = nb_models

    def reset(self) -> None:
        self.acc = jax.device_put(
            jnp.zeros((self.n_limbs, self.padded_length), dtype=jnp.uint32), self._acc_sharding
        )
        self.nb_models = 0
