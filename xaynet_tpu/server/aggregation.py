"""Update-phase aggregation strategies: host numpy or TPU mesh.

The reference aggregates each accepted update inline with a sequential
big-int loop (reference:
rust/xaynet-server/src/state_machine/phases/update.rs:119-152). Here updates
are staged and folded in batches:

- **host**: vectorized numpy limb kernels (``core.mask.Aggregation``);
- **device**: the sharded single-pass fold on the TPU mesh
  (``parallel.ShardedAggregator``) for the vector part, host for the tiny
  unit part. Device folds flow through the streaming pipeline
  (``parallel.streaming``): ``flush()`` *submits* the staged micro-batch
  into a bounded producer/consumer (ring-buffer staging overlaps the
  in-flight folds) and ``drain()`` — called at phase end and in
  ``finalize`` — blocks for the result. The fold math is an exact modular
  sum, so the aggregate is byte-identical to the synchronous path.

Validation still happens per-update at accept time (the client-visible
protocol behavior is unchanged); only the arithmetic is deferred into
batches.
"""

from __future__ import annotations

import threading

import numpy as np

from ..core.mask.config import MaskConfigPair
from ..core.mask.masking import Aggregation, AggregationError, UnmaskingError
from ..core.mask.object import LazyWireMaskVect, MaskObject, MaskUnit, MaskVect, wire_route
from ..ops import limbs as limb_ops
from ..ops.limbs import PlanarLimbs
from ..resilience.checkpoint import AggSnapshot
from ..telemetry import journal, profiling
from ..telemetry import tracing as trace
from ..telemetry import unmask as unmask_stages
from ..telemetry import wire as wire_stats
from ..utils.tracing import current_request_id
from . import stages


def build_staged_aggregator(shared) -> "StagedAggregator":
    """The ONE way a phase builds the round's aggregator from settings —
    shared by the update phase's normal entry and the journal-resume
    factories re-entering sum2/unmask (docs/DESIGN.md §9), so a resumed
    round folds and unmasks with exactly the configuration it crashed
    under."""
    settings = shared.settings
    return StagedAggregator(
        config=shared.state.round_params.mask_config,
        object_size=shared.state.round_params.model_length,
        device=settings.aggregation.device,
        batch_size=settings.aggregation.batch_size,
        kernel=settings.aggregation.kernel,
        dispatch_ahead=settings.aggregation.dispatch_ahead,
        staging_buffers=settings.aggregation.staging_buffers,
        shard_parallel=settings.aggregation.shard_parallel,
        packed_staging=settings.aggregation.packed_staging,
        wire_ingest=settings.aggregation.wire_ingest,
        tenant=shared.tenant,
    )


def slots_take_planes(settings) -> bool:
    """Whether the staging slots of the aggregator these settings build
    (:func:`build_staged_aggregator`) are byte planes
    (``StreamingAggregator.takes_planes``: the device path under packed
    staging, where the mask's wire width is under its limb width). What the
    message pipeline needs to know of its consumer before a round has one:
    it then parses an Update's v1 vector into the planes its slot will hold
    (``PetMessageHandler.update_planes``)."""
    aggregation = settings.aggregation
    return bool(
        aggregation.device
        and aggregation.packed_staging
        and limb_ops.packed_staging_usable(settings.mask.to_config().order)
    )


class DeviceAggregation(Aggregation):
    """Aggregation view over the still-sharded device accumulator.

    ``finalize()`` materializes a host ``Aggregation`` — it GATHERS the
    whole mesh accumulator into one wire-layout host array before the
    Unmask phase has even subtracted the mask. This view keeps the
    accumulator where it is: ``unmask_array``/``unmask`` subtract the
    elected mask per-shard in place (``ShardedAggregator.unmask_planar`` —
    each mesh device subtracts its own model-axis slice), and only the
    *unmasked* result crosses to the host for the fixed-point decode.
    Validation and the tiny unit channel need no accumulator read at all;
    ``object`` stays available for checkpoint/test paths that genuinely
    want the gathered aggregate.
    """

    def __init__(self, config: MaskConfigPair, object_size: int, device, unit_acc, stream=None):
        # deliberately NOT calling super().__init__: it would allocate an
        # empty host MaskObject of the full model size just to carry configs
        self._nb_models = device.nb_models
        self.object_size = object_size
        self._config = config
        self._device = device
        self._unit_acc = np.asarray(unit_acc)
        # deferred-drain handoff (docs/DESIGN.md §22): when the streaming
        # pipeline rides into Unmask still open, the eager per-shard
        # unmask subtracts each shard the moment ITS last fold commits
        self._stream = stream

    @property
    def nb_models(self) -> int:
        if self._stream is not None:
            # deferred drain: folds may still be in flight — read the
            # count atomically with the worker handoff, exactly as the
            # update phase's capacity checks did (it is exact once the
            # eager unmask's drain has settled the pipeline)
            return self._stream.counted_models()
        return self._nb_models

    @property
    def config(self) -> MaskConfigPair:
        return self._config

    @property
    def object(self) -> MaskObject:
        """Gathered host aggregate (checkpoints/tests only — the unmask
        path never calls this)."""
        if self._stream is not None:
            self._stream.drain()
        return MaskObject(
            MaskVect(self._config.vect, self._device.snapshot()),
            MaskUnit(self._config.unit, self._unit_acc),
        )

    def validate_unmasking(self, mask: MaskObject) -> None:
        if self.nb_models == 0:
            raise UnmaskingError("NoModel")
        if self.nb_models > self._config.vect.max_nb_models:
            raise UnmaskingError("TooManyModels")
        if self.nb_models > self._config.unit.max_nb_models:
            raise UnmaskingError("TooManyScalars")
        if self._config.vect != mask.vect.config or self.object_size != len(mask.vect):
            raise UnmaskingError("MaskManyMismatch")
        if self._config.unit != mask.unit.config:
            raise UnmaskingError("MaskOneMismatch")
        if not mask.is_valid():
            raise UnmaskingError("InvalidMask")

    def _settle_stream(self) -> None:
        """Close a deferred-drain pipeline and pin the final model count
        (everything has settled by now: drain ran, close re-drains)."""
        stream, self._stream = self._stream, None
        if stream is not None:
            stream.close()
            self._nb_models = self._device.nb_models

    def _eager_unmask(self, mask_obj: MaskObject) -> PlanarLimbs | None:
        """The unmask of a pipeline that rode into Unmask still open
        (docs/DESIGN.md §22). Where the pipeline can take the mask now
        (``can_stage_unmask``: it folds on more than one shard and is
        neither degraded, poisoned nor closed) the subtract is staged as
        per-shard tail jobs BEHIND the round's last fold batches, so each
        shard unmasks the moment its own last fold commits, and the
        planes those jobs fetched are returned. Otherwise (one device,
        above all), or where a shard's job failed, the pipeline is
        drained and settled and ``None`` returned: the caller runs the
        drain-time subtract, byte-identical either way, since a failed
        shard's accumulator is untouched."""
        stream = self._stream
        job = None
        if stream.can_stage_unmask():
            # the device's part runs on the shard workers; what this task
            # does meanwhile carries the stages' names (telemetry/unmask.py)
            with unmask_stages.stage("mask_put", bytes=mask_obj.vect.data.nbytes):
                job = stream.stage_unmask(self._device.mask_planar(mask_obj.vect.data))
        try:
            # the completion barrier: the shard jobs staged above end
            # under it, and a fold error still pending fails the round here
            with unmask_stages.stage("subtract"):
                stream.drain()
        except Exception:
            self._settle_stream()
            raise
        out = None
        if job is not None:
            with unmask_stages.stage("fetch", bytes=job.out.nbytes):
                out = stream.finish_unmask(job)
        self._settle_stream()
        return out

    # ``unmask`` and ``unmask_array`` are the base's: they read the carried
    # config pair (``self.config``) and these two, never ``self.object``

    def _unmasked_vect(self, mask_obj: MaskObject) -> PlanarLimbs:
        # mask_put, subtract and fetch are bracketed where they run
        # (ShardedAggregator.unmask_planar, or the eager arm above).
        # per-shard in-place subtract: the mask planes upload with the
        # accumulator's sharding and each device subtracts its own slice;
        # the gather happens AFTER the subtraction, on the unmasked result,
        # which every arm hands over as the planes it fetched
        n_vect = self._eager_unmask(mask_obj) if self._stream is not None else None
        if n_vect is None:
            n_vect = self._device.unmask_planar(mask_obj.vect.data)
        return n_vect

    def _unmasked_unit(self, mask_obj: MaskObject) -> int:
        ol_u = limb_ops.order_limbs_for(self._config.unit.order)
        n_unit = limb_ops.mod_sub(
            self._unit_acc[None, :], np.asarray(mask_obj.unit.data)[None, :], ol_u
        )[0]
        return limb_ops.limbs_to_int(n_unit)


class _OpenBatch:
    """One fold batch still filling: the ring buffers of the streaming
    pipeline (one a shard) and the slot writes submitted into them, in
    arrival order. The first write to run borrows the buffers (which may
    wait for free ones); the other writes of the batch wait for it on the
    lock."""

    __slots__ = ("writes", "bufs", "_lock")

    def __init__(self):
        self.writes: list = []  # futures; write i fills slot i
        self.bufs = None  # guarded-by: _lock
        self._lock = threading.Lock()

    def buffers(self, stream) -> list:
        with self._lock:
            if self.bufs is None:
                self.bufs = stream.open_batch()
            return self.bufs


class StagedAggregator:
    """Stages validated masked updates and folds them in batches."""

    def __init__(
        self,
        config: MaskConfigPair,
        object_size: int,
        device: bool = False,
        batch_size: int = 64,
        ingest_workers: int = 4,
        mesh=None,
        kernel: str = "auto",
        dispatch_ahead: int = 2,
        staging_buffers: int = 3,
        shard_parallel: bool = True,
        packed_staging: bool = True,
        wire_ingest: bool = False,
        tenant: str = "default",
    ):
        self.config = config
        self.object_size = object_size
        self.tenant = tenant
        self.batch_size = max(1, batch_size)
        # device: device-resident planars (wire ingest) only; a host update
        # lives in its slot of an open batch
        self._staged_vect: list = []
        self._resident_max = 0  # the most of them a flush of this round took
        self._open: list[_OpenBatch] = []  # device: batches filling in the ring
        self._staged_unit: list[np.ndarray] = []
        self._count = 0
        self._host = Aggregation(config, object_size)
        self._device = None
        self._stream = None
        self._ingest_pool = None
        if device:
            from concurrent.futures import ThreadPoolExecutor

            from ..ops import limbs as limb_ops
            from ..parallel.aggregator import ShardedAggregator
            from ..parallel.streaming import StreamingAggregator

            self._device = ShardedAggregator(config.vect, object_size, mesh=mesh, kernel=kernel)
            if wire_ingest:
                self._check_resident_fits()
            # flush() submits micro-batches here; drain()/finalize() sync.
            # On a multi-device mesh the pipeline runs shard-parallel (one
            # fold worker per device, per-shard staging rings + donated
            # accumulators) unless [aggregation] shard_parallel = false
            self._stream = StreamingAggregator(
                self._device,
                staging_buffers=staging_buffers,
                dispatch_ahead=dispatch_ahead,
                max_batch=self.batch_size,
                shard_parallel=shard_parallel,
                packed=packed_staging,
                tenant=tenant,
            )
            # tiny unit part stays on host
            self._unit_acc = np.zeros(
                limb_ops.n_limbs_for_order(config.unit.order), dtype=np.uint32
            )
            # wire->planar transposes overlap across workers: at 25M params
            # each update is a ~200MB relayout, which would serialize the
            # ingest path if done at flush time on one thread
            self._ingest_pool = ThreadPoolExecutor(
                max_workers=max(1, ingest_workers), thread_name_prefix="xn-ingest"
            )

    def _check_resident_fits(self) -> None:
        """Under wire ingest every accepted update stays in device memory
        until its batch's flush, so ``batch_size`` bounds that memory: refuse
        a round whose flush cannot fit the device before its first update,
        not at its fortieth. Where the backend reports no limit (the CPU
        backend) nothing is checked."""
        from ..parallel import aggregator as device_agg
        from .settings import SettingsError

        device = self._device
        limit = device_agg.device_memory_limit(device.mesh)
        if limit is None:
            return
        sizes = (
            device.n_limbs,
            device.config.bytes_per_number,
            device.padded_length // device.mesh.devices.size,
        )
        need = device_agg.resident_footprint(self.batch_size, *sizes)
        if need > limit:
            fits = device_agg.resident_rows_that_fit(limit, *sizes)
            raise SettingsError(
                f"aggregation.wire_ingest keeps every accepted update in device memory until "
                f"its batch is folded: batch_size = {self.batch_size} at model length "
                f"{self.object_size} may hold {need} bytes on a device of {limit}; the largest "
                f"aggregation.batch_size that fits is {fits}"
            )

    def _validate_on_device(self, vect: LazyWireMaskVect):
        """One update's element block to the device as the view of the body
        that the lazy parse made, and the device's verdict on it: the row that
        stays there (``ShardedAggregator.put_*_update`` and its check), or
        ``None`` for a vector with an element at or over the order. The link
        and the kernel are stages of the message apart (``ingest_h2d``: put
        to transfer done; ``ingest_unpack``: dispatch to the verdict on the
        host), inside ``validate``."""
        device = self._device
        if vect.planar:
            # wire v2: the body is already the packed byte-planar layout
            block, put, check = vect.planar_block, device.put_planar_update, device.check_put_update
        else:
            block, put, check = vect.wire_block, device.put_wire_update, device.unpack_put_update
        where = {"wire": "packed" if vect.packed_wire else "legacy", "route": "device"}
        with stages.stage("ingest_h2d", bytes=block.nbytes, **where):
            staged = put(block)
        with stages.stage("ingest_unpack", **where):
            row = check(staged)
        wire_stats.device_verdict(block.nbytes, row is not None)
        return row

    @property
    def kernel_used(self) -> str:
        """Which fold kernel actually ran (``host`` off-device; on device the
        resolved choice, or the configured one before the first fold)."""
        if self._device is None:
            return "host"
        return self._device.kernel_used or self._device.kernel

    @property
    def nb_models(self) -> int:
        if self._device is not None:
            # staged + (in-flight + folded, read atomically with the fold
            # worker's handoff): every accepted update counts the moment it
            # is staged, exactly as before streaming
            return self._count + self._stream.counted_models()
        return self._count + self._host.nb_models

    def validate_aggregation(self, obj: MaskObject) -> None:
        """Per-update protocol validation (same checks as the reference,
        masking.rs:253-279) without materializing a probe accumulator."""
        if self.config.vect != obj.vect.config:
            raise AggregationError("ModelMismatch")
        if self.config.unit != obj.unit.config:
            raise AggregationError("ScalarMismatch")
        if self.object_size != len(obj.vect):
            raise AggregationError("ModelMismatch")
        if self.nb_models >= self.config.vect.max_nb_models:
            raise AggregationError("TooManyModels")
        if self.nb_models >= self.config.unit.max_nb_models:
            raise AggregationError("TooManyScalars")
        vect = obj.vect
        if (
            self._device is not None
            and isinstance(vect, LazyWireMaskVect)
            and not vect.materialized
            and not vect.checked
        ):
            # device wire ingest: unpack + element validity run on the
            # accelerator, and the resulting planar is cached on the object
            # so stage() never re-uploads. Ordering is preserved — this runs
            # before the caller's seed-dict insert (update.rs:119-152). A
            # prior prevalidate_wire_batch may already have cached the
            # verdict (one device round-trip for the whole micro-batch);
            # only un-prevalidated updates pay the per-update sync here.
            planar = vect._staged_planar
            if planar is None and not vect._wire_invalid:
                planar = self._validate_on_device(vect)
            if planar is None or not obj.unit.is_valid():
                raise AggregationError("InvalidObject")
            vect._staged_planar = planar
        elif not obj.is_valid():
            # a wire v2 vector the parse has checked on its planes repeats
            # that verdict here and scans nothing (LazyWireMaskVect.is_valid)
            raise AggregationError("InvalidObject")

    def prevalidate_wire_batch(self, objs) -> None:
        """Batch device validation for a micro-batch about to be processed
        member-wise: ONE staged upload + unpack dispatch + acceptance fetch
        for the whole group (``ShardedAggregator.validate_wire_updates``),
        where the per-member path pays a full device round-trip sync each.
        Results are cached on the vect objects; ``validate_aggregation``
        consumes them per member in order, so the protocol's
        validate-before-seed-dict-insert sequencing is unchanged (caching a
        verdict earlier has no observable side effect). Non-wire members
        and host mode are untouched."""
        if self._device is None:
            return
        # only members the device branch would actually validate: matching
        # config and declared length (a count/config-mismatched member must
        # fall through to the per-member path, which rejects IT alone with
        # ModelMismatch — a ragged np.stack here would instead blow up the
        # whole micro-batch with an internal error)
        want_bytes = self.object_size * self.config.vect.bytes_per_number
        lazies = [
            obj.vect
            for obj in objs
            if isinstance(obj.vect, LazyWireMaskVect)
            and not obj.vect.materialized
            and not obj.vect.checked
            and obj.vect._staged_planar is None
            and not obj.vect._wire_invalid
            and obj.vect.config == self.config.vect
            and np.asarray(obj.vect.wire_block).size == want_bytes
        ]
        # v1 (interleaved) and v2 (planar) members batch separately — the
        # two unpack programs take different layouts — but a mixed group
        # still validates in at most two device round-trips
        for planar_wire in (False, True):
            group = [v for v in lazies if v.planar is planar_wire]
            for start in range(0, len(group), self.batch_size):
                chunk = group[start : start + self.batch_size]
                if planar_wire:
                    planars = self._device.validate_planar_updates(
                        [v.planar_block for v in chunk]
                    )
                else:
                    planars = self._device.validate_wire_updates(
                        [np.asarray(v.wire_block) for v in chunk]
                    )
                for vect, planar in zip(chunk, planars):
                    wire_stats.device_verdict(vect.wire_block.nbytes, planar is not None)
                    if planar is None:
                        vect._wire_invalid = True
                    else:
                        vect._staged_planar = planar

    def validate_partial(self, obj: MaskObject, members: int) -> None:
        """Protocol validation for an edge PARTIAL aggregate of ``members``
        updates: same config/length checks as a single update, but the
        model-count headroom must fit the whole member count (the envelope
        is atomic — it folds entirely or not at all)."""
        if members < 1:
            raise AggregationError("EmptyPartial")
        if self.config.vect != obj.vect.config:
            raise AggregationError("ModelMismatch")
        if self.config.unit != obj.unit.config:
            raise AggregationError("ScalarMismatch")
        if self.object_size != len(obj.vect):
            raise AggregationError("ModelMismatch")
        if self.nb_models + members > self.config.vect.max_nb_models:
            raise AggregationError("TooManyModels")
        if self.nb_models + members > self.config.unit.max_nb_models:
            raise AggregationError("TooManyScalars")
        if not obj.is_valid():
            raise AggregationError("InvalidObject")

    def fold_partial(self, obj: MaskObject, members: int) -> None:
        """Fold a pre-aggregated partial of ``members`` updates as ONE
        ``masked_add`` dispatch and advance ``nb_models`` by ``members``.

        Ordering: any singly-staged updates flush first, so the aggregate
        stays the plain modular sum of everything accepted so far (order
        never changes the result — this just keeps the accounting simple).
        """
        if members < 1:
            raise AggregationError("EmptyPartial")
        if self._device is not None:
            # drain() is the device sync point: with nothing in flight the
            # model-count adjustment below cannot race the fold worker
            self.drain()
            from ..ops import limbs as limb_ops

            self._stream.submit_batch(np.asarray(obj.vect.data)[None])
            self._stream.drain()
            # the partial counts as `members` models, not the one row folded
            self._device.nb_models += members - 1
            order_limbs = limb_ops.order_limbs_for(self.config.unit.order)
            self._unit_acc = limb_ops.mod_add(
                self._unit_acc[None, :], np.asarray(obj.unit.data)[None, :], order_limbs
            )[0]
        else:
            self.flush()
            profiling.timed_kernel(
                "masked_add",
                self.object_size,
                lambda: self._host.aggregate_partial(obj, members),
            )

    @property
    def pending(self) -> int:
        """Updates staged but not yet folded."""
        return self._count

    def wire_route(self, vect: MaskVect) -> tuple[str, str]:
        """``(wire, route)`` of a validated Update vector on this aggregator
        (``telemetry/wire.py``): what ``core.mask.object.wire_route`` says of
        the object, but that a checked plane view goes by ``copy`` only into
        byte-planar slots (the device path under packed staging) and by
        ``relayout`` everywhere else, and that a vector the device has
        unpacked is ``device`` whatever it was."""
        wire, route = wire_route(vect)
        if isinstance(vect, LazyWireMaskVect) and vect._staged_planar is not None:
            return wire, "device"
        if route == "copy" and self._stream is not None and self._stream.takes_planes:
            return wire, "copy"
        return wire, "relayout"

    def stage(self, obj: MaskObject) -> None:
        """Stage an update without folding (caller controls flush timing).

        Never waits: on the device path the relayout (and, for the first
        row of a batch, the wait for a free ring buffer) runs on the
        ``xn-ingest`` pool."""
        vect = obj.vect
        wire, route = self.wire_route(vect)
        wire_stats.staged(wire, route, len(vect) * vect.config.bytes_per_number)
        if self._ingest_pool is not None:
            planar_dev = vect._staged_planar if isinstance(vect, LazyWireMaskVect) else None
            # the relayout outlives this call (and may outlive the request
            # that staged it), so its span LINKS the caller's span instead
            # of parenting to it
            caller, rid, arrived = trace.current_ctx(), current_request_id(), stages.current_phase()
            if planar_dev is not None:
                # wire ingest: validate_aggregation already unpacked this
                # update on device — stage the device-resident planar
                self._staged_vect.append(planar_dev)
            else:
                # straight from the wire layout into this update's slot of
                # the open batch's ring buffers, a shard's columns into that
                # shard's (slot = arrival order), so the flush that closes
                # the batch relays nothing out
                batch = self._open[-1] if self._open else None
                if batch is None or len(batch.writes) >= self._stream.max_batch:
                    batch = _OpenBatch()
                    self._open.append(batch)
                stream, slot = self._stream, len(batch.writes)

                if route == "copy":
                    # wire v2: the body's planes are the slot's layout
                    data, write = vect.planar_block, stream.stage_planes
                else:
                    data, write = vect.data, stream.stage_row

                def write_slot():
                    bufs = batch.buffers(stream)
                    with stages.stage(
                        "to_planar", link=caller, rid=rid, phase=arrived, bytes=data.nbytes,
                        wire=wire, route=route,
                    ):
                        write(bufs, slot, data)

                batch.writes.append(self._ingest_pool.submit(write_slot))
        else:
            self._staged_vect.append(vect.data)
        self._staged_unit.append(obj.unit.data)
        self._count += 1

    def aggregate(self, obj: MaskObject) -> None:
        self.stage(obj)
        if self._count >= self.batch_size:
            self.flush()

    def flush(self) -> None:
        """Hand the staged micro-batch to the fold backend.

        Device mode SUBMITS into the streaming pipeline and returns without
        waiting for the fold (the pipeline's dispatch-ahead/ring bounds
        provide backpressure); call :meth:`drain` to synchronize. Host mode
        folds inline as before.
        """
        if self._count == 0:
            return
        wire_stats.batch_closed()
        stack = None if self._ingest_pool is not None else np.stack(self._staged_vect)
        units = np.stack(self._staged_unit)
        if self._device is not None:
            from ..ops import limbs as limb_ops

            self._submit_open_batches()
            parts, self._staged_vect = self._staged_vect, []  # consumed: free as we fold
            if parts:
                # wire ingest: every row is already device-resident and
                # validity-checked (a v1 update as a uint32 planar, a v2 one
                # still PACKED uint8[bpn, padded]: bpn bytes an element
                # against the 4L a planar pins) — folded INLINE (not queued:
                # parking device-resident batches behind dispatch_ahead
                # would pin several full batches in HBM at once, ~13 GB each
                # at 25M/batch 64, where XLA's async dispatch already
                # overlaps device folds). Chunked stack+fold keeps peak HBM
                # at the staged rows + one chunk-sized copy; the flush is one
                # batch of the pipeline's counters whatever its layouts.
                self._resident_max = max(self._resident_max, len(parts))
                wire_stats.RESIDENT_ROWS_MAX.set(self._resident_max)
                self._stream.fold_resident_rows_now(parts)
                parts.clear()
            order_limbs = limb_ops.order_limbs_for(self.config.unit.order)
            batch_unit = limb_ops.batch_mod_sum(units[:, None, :], order_limbs)[0]
            self._unit_acc = limb_ops.mod_add(
                self._unit_acc[None, :], batch_unit[None, :], order_limbs
            )[0]
        else:
            # same op label as the device fold: one /metrics series answers
            # "how fast is the masked add", whichever backend ran it
            profiling.timed_kernel(
                "masked_add",
                stack.shape[0] * self.object_size,
                lambda: self._host.aggregate_batch(stack, units),
            )
        self._staged_vect.clear()
        self._staged_unit.clear()
        self._count = 0

    def _submit_open_batches(self) -> None:
        """Hand each open batch to the pipeline once its slot writes have
        landed, oldest first (a later batch may be waiting for the ring
        buffer an earlier one gives back). After a failed write nothing
        more is submitted: every buffer goes back to the ring and the
        first error is raised."""
        batches, self._open = self._open, []
        failed = None
        for batch in batches:
            for write in batch.writes:
                error = write.exception()  # waits for the write to end
                failed = failed or error
            if batch.bufs is None:
                continue  # no write got as far as borrowing the buffers
            if failed is None:
                self._stream.submit_staged(batch.bufs, len(batch.writes))
            else:
                self._stream.release_batch(batch.bufs)
        if failed is not None:
            # what was staged since the last flush is lost with it
            self._staged_vect.clear()
            self._staged_unit.clear()
            self._count = 0
            raise failed

    def drain(self) -> None:
        """Flush, then block until every in-flight fold has completed (the
        phase-transition synchronization point)."""
        self.flush()
        if self._stream is not None:
            self._stream.drain()

    def snapshot_state(self) -> tuple[np.ndarray, np.ndarray, int]:
        """Exact host copy of the aggregate for a mid-round checkpoint.

        Drains first — the streaming pipeline's in-flight folds must land
        before the accumulator is read — then returns ``(vect wire
        uint32[model_len, L], unit uint32[L_unit], nb_models)``.
        """
        self.drain()
        if self._device is not None:
            return self._device.snapshot(), np.array(self._unit_acc), self._device.nb_models
        return (
            np.array(self._host.object.vect.data),
            np.array(self._host.object.unit.data),
            self._host.nb_models,
        )

    def snapshot_journal(self) -> AggSnapshot:
        """Exact host copy of the aggregate for a journal entry.

        Drains first, like :meth:`snapshot_state` — then, on the device
        path, reads the accumulator shard by shard (packed per-shard
        planar planes) instead of reassembling the mesh array into one
        global wire buffer: each shard's plane crosses to the host once,
        and no device-side concat/relayout runs at all.
        """
        self.drain()
        if self._device is not None:
            return AggSnapshot(
                nb_models=self._device.nb_models,
                unit=np.array(self._unit_acc),
                planes=self._device.snapshot_shards(),
            )
        return AggSnapshot(
            nb_models=self._host.nb_models,
            unit=np.array(self._host.object.unit.data),
            vect=np.array(self._host.object.vect.data),
        )

    def restore_journal(self, ckpt) -> None:
        """Restore a journal entry (``RoundCheckpoint``) into an EMPTY
        aggregator. Per-shard planes restore shard-by-shard on the device
        path (``ShardedAggregator.restore_shards`` — no host concat when
        the plane geometry matches the mesh); everything else goes through
        the wire-layout :meth:`restore_state`. An empty entry (``nb_models
        == 0``: the sealed-sum-dict entry written at the Sum→Update
        transition) restores to the zero accumulator the constructor
        already built."""
        if ckpt.nb_models == 0:
            return
        with journal.resume_stage("restore", phase=ckpt.phase, nb_models=ckpt.nb_models):
            if self._device is not None and ckpt.planes:
                if self._count or self.nb_models:
                    raise RuntimeError("restore_journal requires an empty aggregator")
                self._device.restore_shards(ckpt.planes, ckpt.nb_models)
                self._unit_acc = np.ascontiguousarray(ckpt.unit, dtype=np.uint32)
                return
            self.restore_state(ckpt.wire_vect(), ckpt.unit, ckpt.nb_models)

    def restore_state(self, vect: np.ndarray, unit: np.ndarray, nb_models: int) -> None:
        """Restore a checkpoint snapshot into an EMPTY aggregator (resume)."""
        if self._count or self.nb_models:
            raise RuntimeError("restore_state requires an empty aggregator")
        vect = np.ascontiguousarray(vect, dtype=np.uint32)
        unit = np.ascontiguousarray(unit, dtype=np.uint32)
        if self._device is not None:
            self._device.restore(vect, nb_models)
            self._unit_acc = unit
        else:
            self._host.object = MaskObject(
                MaskVect(self.config.vect, vect), MaskUnit(self.config.unit, unit)
            )
            self._host.nb_models = nb_models

    def finalize(self) -> Aggregation:
        """Materialize the protocol-level ``Aggregation`` (for Unmask)."""
        self.drain()
        if self._device is None:
            return self._host
        self._stream.close()
        agg = Aggregation(self.config, self.object_size)
        agg.object = MaskObject(
            MaskVect(self.config.vect, self._device.snapshot()),
            MaskUnit(self.config.unit, self._unit_acc),
        )
        agg.nb_models = self._device.nb_models
        return agg

    def finalize_inplace(self, defer_drain: bool = False) -> Aggregation:
        """The Unmask handoff WITHOUT gathering the accumulator.

        Host mode is unchanged (the accumulator is host-resident — its
        ``mod_sub`` is the right unmask). Device mode returns a
        :class:`DeviceAggregation` view over the still-sharded accumulator,
        so the Unmask phase subtracts the elected mask per-shard in place
        and only the unmasked result crosses to the host for decode —
        ``finalize()`` (kept for snapshot/test callers) gathers first and
        subtracts after, a full extra accumulator round-trip at 25M params.

        ``defer_drain`` is the caller saying that a pipeline is open
        (docs/DESIGN.md §22). Sum2 hands over with it set: the device
        pipeline rides into Unmask still OPEN, the staged remainder
        submitted, and the drain barrier moves into the unmask, which on
        a mesh stages the subtract behind each shard's own last fold
        (``DeviceAggregation._eager_unmask``). A journal resume into
        Unmask restored its aggregate into an aggregator that never
        opened a pipeline and leaves it unset: drain, close, and the
        view's drain-time subtract (``ShardedAggregator.unmask_planar``),
        which is also what one device and a failed shard job come to.
        """
        if defer_drain and self._device is not None:
            self.flush()
            return DeviceAggregation(
                self.config, self.object_size, self._device, self._unit_acc,
                stream=self._stream,
            )
        self.drain()
        if self._device is None:
            return self._host
        self._stream.close()
        return DeviceAggregation(
            self.config, self.object_size, self._device, self._unit_acc
        )
