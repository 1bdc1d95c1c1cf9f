"""Streaming aggregation: bounded producer/consumer over the sharded fold.

``ShardedAggregator``'s batch entry points serialize the three legs of every
fold — host staging (pad + transpose + ``device_put``), the fold dispatch,
and (on the wire path) a blocking acceptance-vector fetch — so the host and
the device take turns idling. This module turns that into a pipeline:

- **staging buffer ring** — a small, bounded set of host buffers, leased
  as they are first needed; batch N+1 is padded/copied into a ring buffer
  while batch N folds, and the per-batch ``np.pad``/``np.stack``
  allocations (plus their page-fault tax, ~0.15 s per 200 MB at 25M
  params) disappear entirely. A buffer is reused only after the fold that
  consumed it has finished reading host memory (after the ``device_put``
  transfer is complete). The pipeline lends the buffer to a batch that
  is still filling (``open_batch``), so its rows are written into their
  slots as they arrive (``stage_row``) and submitting the batch
  (``submit_staged``) relays nothing out. There is one ring a shard: a
  single-device pipeline is the case of one shard as wide as the model.
- **rows go to the device as their slots are written** — a batch that fills
  through ``open_batch``/``stage_row`` has an arrival interval to copy in:
  when a slot write ends the row is queued to the pipeline's one copier
  thread (``xn-h2d``), which ``device_put``s each shard's slice of it to
  that shard's device, one copy at a time, and places it in a device batch
  of the ring buffer's shape (``shards.place_row``, in place). The fold at
  the flush waits for the copies still outstanding (after the last write:
  one row's) and runs once, over the resident batch; slots no row went to
  are zeros, which add nothing. The host ring buffer stays the batch's
  source of truth until that fold has returned: a failed row copy is the
  ladder's first failure, and the retry copies the batch whole from the
  ring buffer, which is also how a batch staged inside one call
  (``submit_batch``, raw wire batches) and every batch of a degraded
  pipeline reach the device.
- **dispatch-ahead depth** — up to ``dispatch_ahead`` batches are queued to
  a single fold worker thread, so XLA's asynchronous dispatch keeps
  multiple folds in flight behind one another while the producer stages
  ahead (DrJAX-style MapReduce pipelining, arxiv 2403.07128).
- **deferred acceptance syncs** — wire batches collect their ``ok`` arrays
  as in-flight device values; ``drain()`` fetches them all in ONE sync at
  flush/phase end instead of one blocking ``np.asarray(ok)`` per batch.
  Per-member accept/reject semantics and ``nb_models`` are byte-identical
  to the sequential path — invalid updates are zeroed inside the fold
  either way, and the deferred fetch only moves *when* the host learns the
  verdict, never what it is.

Fold order is FIFO (single worker), and the lazy-carry fold is an exact
modular sum, so the aggregate is byte-identical to sequential
``add_batch``/``add_wire_batch`` calls over the same updates regardless of
how far the pipeline runs ahead.

**Shard-parallel mode (multi-device meshes).** On a mesh of D devices the
pipeline runs ONE FOLD WORKER PER SHARD instead of the single FIFO worker:
each mesh device owns its contiguous model-axis plane slice with a donated
per-shard accumulator (``shards.ShardPlan``), an open batch holds one
buffer of every shard's staging ring, every row is written into its column
range of each (once, as it arrives), and each shard's host→device transfer
overlaps the other shards' in-flight folds.
A batch COMMITS — counts
toward ``nb_models`` / leaves flight — only when EVERY shard folded its
slice (``_BatchJob``), so per-shard progress skew never shows up in the
accounting; ``drain()`` is the cross-shard barrier that performs the one
deferred acceptance sync and reassembles the per-shard accumulators into
the aggregator's global ``acc``. Wire batches keep their single
mesh-program unpack (the psum-consistent validity mask of the sequential
path) and fan only the FOLD out per shard, so acceptance semantics are
byte-identical to ``add_wire_batch``. The degradation ladder is per-shard:
a shard's fold failure with a provably untouched shard accumulator retries
once synchronously on that shard alone (the other shards' slices of the
batch fold normally — consistency comes from the commit barrier), flips
the whole pipeline to the synchronous path on success, and poisons it
permanently on a second failure.

**Degradation ladder (streaming -> sync -> fail).** A fold failure in the
worker does NOT immediately poison the round: the accumulator is only
reassigned after a fold returns, so the failed batch is retried once
*synchronously*; on success the pipeline switches to the synchronous fold
path for the rest of the round (submits fold on the caller's thread,
logged + ``xaynet_streaming_degraded``) — the round completes with the
exact same aggregate, just without overlap. Only when the synchronous
retry ALSO fails is the pipeline poisoned — permanently, because the
batch's updates are lost and the accumulator no longer corresponds to any
consistent update set. Every poisoned-pipeline error names the poisoning
batch index and the original exception. Failures surfacing at ``drain()``
(XLA's asynchronous dispatch) skip the retry: the accumulator may already
reference the failed computation, so no consistent retry exists.
"""

from __future__ import annotations

import logging
import queue as queue_mod
import threading
import time
import weakref
from contextlib import contextmanager, nullcontext

import numpy as np

from ..ops.fold_jax import MAX_LAZY_BATCH
from ..ops.limbs import PlanarLimbs
from ..resilience.faults import maybe_fail
from ..telemetry import profiling
from ..telemetry import tracing as trace
from ..telemetry.recorder import flight_dump
from ..telemetry.registry import get_registry
from ..telemetry.timeline import intersection, measure, merge_intervals
from ..tenancy.pool import get_pool
from ..tenancy.scheduler import get_scheduler
# BYTES_STAGED: one module owns the xaynet_bytes_staged_total family —
# aggregator.py registers it (wire-ingest staging accounts there too) and
# the streaming rings account through the shared symbol
from .aggregator import (
    BYTES_REDUCED,
    BYTES_STAGED,
    RESIDENT_CHUNK,
    ShardedAggregator,
    note_h2d_route,
)
from .mesh import shard_slices
from .shards import H2D_GATE, place_row, settle

logger = logging.getLogger(__name__)

# mirror=True: also written into the profiler's trace when the runner has
# installed its sink. stream.commit and SPAN_EAGER_UNMASK are recorded
# after the fact (record_span), which no mirror can carry: a commit barrier
# begins on the thread of the first shard to fold and ends on the last's.
SPAN_STAGE = trace.declare_span("stream.stage", mirror=True)
SPAN_RING_WAIT = trace.declare_span("stream.ring_wait", mirror=True)
SPAN_H2D = trace.declare_span("stream.h2d", mirror=True)
SPAN_FOLD = trace.declare_span("stream.fold", mirror=True)
SPAN_COMMIT = trace.declare_span("stream.commit")
SPAN_DRAIN = trace.declare_span("stream.drain", mirror=True)
SPAN_EAGER_UNMASK = trace.declare_span("overlap.eager_unmask")

_registry = get_registry()
STAGING_DEPTH = _registry.gauge(
    "xaynet_streaming_staging_depth",
    "Staging ring buffers currently owned by in-flight batches.",
)
INFLIGHT_FOLDS = _registry.gauge(
    "xaynet_streaming_inflight_folds",
    "Fold batches submitted to the streaming pipeline and not yet folded.",
)
OVERLAP_RATIO = _registry.gauge(
    "xaynet_streaming_overlap_ratio",
    "Fraction of the shorter pipeline leg (staging vs folding) that ran "
    "concurrently with the other leg during the last drain window "
    "(1 = perfect overlap, 0 = fully serialized). A batch's staging leg is "
    "the time its ring buffer is lent to it, buffer in hand to hand-over; "
    "its folding leg is its fold worker item. Time in which neither leg "
    "runs (waiting for arrivals) counts for nothing.",
)
BATCHES_TOTAL = _registry.counter(
    "xaynet_streaming_batches_total",
    "Streaming pipeline batches, by stage (staged = submitted, "
    "folded = fold completed).",
    ("stage",),
)
DEGRADED = _registry.gauge(
    "xaynet_streaming_degraded",
    "1 while the streaming pipeline has degraded to the synchronous fold "
    "path after a fold failure (resets with the next pipeline).",
)
DEGRADATIONS = _registry.counter(
    "xaynet_streaming_degradations_total",
    "Times a streaming pipeline degraded to the synchronous fold path.",
)
SHARD_STAGING_DEPTH = _registry.gauge(
    "xaynet_streaming_shard_staging_depth",
    "Per-shard staging ring buffers currently owned by in-flight batches "
    "(shard-parallel pipelines).",
    ("shard",),
)
SHARD_INFLIGHT = _registry.gauge(
    "xaynet_streaming_shard_inflight_folds",
    "Per-shard fold items queued to or executing in the shard's worker.",
    ("shard",),
)
SHARD_OVERLAP = _registry.gauge(
    "xaynet_streaming_shard_overlap_ratio",
    "Per-shard fraction of the shorter pipeline leg (staging vs folding) "
    "that ran concurrently with the other leg during the last drain window.",
    ("shard",),
)
H2D_SECONDS = _registry.histogram(
    "xaynet_streaming_h2d_seconds",
    "One host-to-device copy of staged rows, device_put until the array is "
    "ready: one row (a shard's slice of it) of a batch staged at arrival, "
    "copied by the pipeline's copier while the batch fills; or a whole batch "
    "(a shard's slice of it) copied by a fold worker, where on one shard the "
    "fold's dispatch (and, while XAYNET_KERNEL_PROFILE keeps its per-fold "
    "sync, that fold's device time) lies in between.",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0),
)
H2D_BYTES = _registry.counter(
    "xaynet_streaming_h2d_bytes_total",
    "Bytes of staged rows copied host to device, row by row as their batch "
    "filled or a batch at a time by a fold worker.",
)
H2D_EARLY_BYTES = _registry.counter(
    "xaynet_streaming_h2d_early_bytes_total",
    "Bytes of staged rows whose host-to-device copy was complete before "
    "their batch was submitted (the flush had no copy of them to wait for).",
)
ROWS_STAGED = _registry.counter(
    "xaynet_streaming_rows_staged_total",
    "Host rows relaid out into a staging ring buffer, by where the relayout "
    "ran (arrival = into the open batch's slot as the update arrived, "
    "flush = inside the submit call that closed the batch).",
    ("route",),
)
RING_WAIT_SECONDS = _registry.histogram(
    "xaynet_streaming_ring_wait_seconds",
    "One acquisition of a staging ring buffer, from the call to the buffer "
    "in hand, by how it was met (free = a buffer lay in the ring, leased = "
    "a new buffer was leased and zero-filled, waited = every buffer was "
    "owned by a batch in flight). _count is the acquisitions by kind.",
    ("how",),
    buckets=(0.0001, 0.001, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0, 30.0, 60.0),
)
COMMIT_SECONDS = _registry.histogram(
    "xaynet_streaming_commit_seconds",
    "One batch's commit (stream.commit): the first shard's fold item done "
    "until the last one's, when the batch counts: what the slowest shard "
    "adds to a batch. On one shard, the hand-back of its slot and buffer.",
    buckets=(0.0001, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0),
)
_SHUTDOWN = object()


@contextmanager
def _h2d(kind: str, nbytes: int, route: str, **where):
    """One host-to-device copy of staged rows, from ``device_put`` to the
    array ready. ``route="row"``: one row of a batch staged at arrival
    (``_put_row``; ``slot=i``, and ``shard=d`` for a shard's slice of it),
    and the time is the copy's. ``route="batch"``: a whole batch copied by
    a fold worker. There a shard's slice (``_fold_shard_item``,
    ``shard=d``) is copied before its fold is dispatched and the time is
    the copy's; in ``_fold_payload`` the fold's dispatch lies between the
    two (the order is the hot path's, and this adds no sync), so the time
    is an upper bound of the copy alone."""
    t0 = time.monotonic()
    with trace.get_tracer().span(SPAN_H2D, kind=kind, bytes=nbytes, route=route, **where):
        yield
    H2D_SECONDS.observe(time.monotonic() - t0)
    H2D_BYTES.inc(nbytes)


def _overlap_ratio(stage, fold) -> float | None:
    """Seconds in which a staging leg and a folding leg both ran, over the
    shorter leg's seconds (each leg the union of its batches' intervals);
    None while either leg is empty."""
    a, b = merge_intervals(stage), merge_intervals(fold)
    shorter = min(measure(a), measure(b))
    return intersection(a, b) / shorter if shorter > 0 else None


class StreamingError(RuntimeError):
    """The fold pipeline failed; the aggregate is unusable."""


class _UnsafeFoldError(Exception):
    """A fold failed at a point where the accumulator may already have been
    reassigned (post-dispatch transfer wait / acceptance fetch): no
    consistent synchronous retry exists, the pipeline must poison.
    ``__cause__`` is the real failure. ``settled`` is True when the batch's
    in-flight count was already handed off (planar ``_credit`` ran) so the
    poison handler must not subtract it again."""

    def __init__(self, settled: bool = False):
        super().__init__()
        self.settled = settled


class StreamTicket:
    """Handle for one submitted batch.

    ``accepted`` resolves at the next ``drain()``: a ``bool[K]`` per-member
    acceptance vector for wire batches, all-True for pre-validated planar
    batches. (In degraded/sync mode it resolves at submit time.)
    """

    __slots__ = ("k", "accepted", "_ok")

    def __init__(self, k: int):
        self.k = k
        self.accepted: np.ndarray | None = None
        self._ok = None  # in-flight device acceptance vector (wire batches)


class _BatchJob:
    """Cross-shard accounting for ONE batch in shard-parallel mode.

    Each of the D shard workers folds its slice independently; the batch
    COMMITS — ``nb_models`` credit for planar batches, ring-buffer release
    for the shared wire buffer, the folded/failed metric — only when the
    LAST shard finishes (``remaining`` hits zero under the pipeline lock).
    ``failed`` is sticky: one shard's loss fails the whole batch, because a
    batch folded on some shards but not others corresponds to no
    consistent update set (the pipeline is poisoned by then anyway).
    """

    __slots__ = ("kind", "k", "ticket", "seq", "remaining", "failed", "retried",
                 "first_done", "staged", "global_release", "rows")

    def __init__(self, kind: str, k: int, ticket, seq: int, n_shards: int, rows=None):
        self.kind = kind
        self.k = k
        self.ticket = ticket
        self.seq = seq
        self.remaining = n_shards  # guarded-by: _lock (the owning pipeline's)
        self.failed = False  # guarded-by: _lock
        self.retried = False  # guarded-by: _lock
        self.first_done = None  # when the first shard settled  # guarded-by: _lock
        # the batch's rows on the devices (_RowCopies), None where it was
        # staged inside one call, the pipeline has degraded, or a shard's
        # retry sent the rest to the ring buffer  # guarded-by: _lock
        self.rows = rows
        # staged/global_release are NOT lock-guarded: after `remaining`
        # hits zero under the lock, exactly ONE worker (the last shard)
        # reaches the commit tail that touches them — ownership handoff
        # through the counter, not mutual exclusion
        self.staged = None  # wire: the mesh-staged byte array (transfer barrier)
        self.global_release = None  # wire: (ring, buf) released at commit


class _UnmaskJob:
    """One eager per-shard unmask pass riding the shard queues
    (docs/DESIGN.md §22): each shard worker subtracts ITS mask slice
    against its own accumulator buffer as soon as the shard's last queued
    fold commits (queue FIFO is the ordering guarantee — the unmask item
    sits behind every fold item of the round). Workers write disjoint column
    ranges of ``out``'s planes; ``error`` is first-failure sticky and the caller
    falls back to the drain-time unmask pass (the subtract is functional —
    a failed shard leaves its accumulator untouched)."""

    __slots__ = ("mask_planar", "out", "remaining", "error", "done")

    def __init__(self, mask_planar, out, n_shards: int):
        self.mask_planar = mask_planar
        self.out = out
        self.remaining = n_shards  # guarded-by: _lock (the owning pipeline's)
        self.error = None  # guarded-by: _lock
        self.done = threading.Event()


class _RowCopies:
    """The device half of one batch staged at arrival. While the batch
    fills, the pipeline's copier puts every row on the device as its slot
    write ends (``_copy_row``), a shard's slice of the row into slot ``i``
    of that shard's device batch ``dev[d]`` (the ring buffer's shape, zeros
    where no row went), so the flush finds the fold's operand resident and
    waits for the copies still outstanding: after the last write, one row's.
    The ring buffers stay the source of truth until the fold has returned: a
    copy that failed (``error``, raised to the fold worker, whose ladder
    copies the batch whole), rows that do not cover the submitted slots, or
    a batch given back unfolded leave the device rows dropped and nothing
    else to undo."""

    __slots__ = ("bufs", "dev", "slots", "queued", "ended", "nbytes", "error",
                 "dropped", "_cond")

    def __init__(self, bufs: list[np.ndarray]):
        self.bufs = bufs
        self._cond = threading.Condition()
        # a device batch a shard. NOT lock-guarded: the copier's alone while
        # a copy is outstanding; take() and drop() touch it once every
        # queued copy has ended — ownership handoff through the counters
        self.dev: list | None = None
        self.slots: set[int] = set()  # slots whose row is resident  # guarded-by: _cond
        self.queued = 0  # copies handed to the copier  # guarded-by: _cond
        self.ended = 0  # of those, ended (well, badly or skipped)  # guarded-by: _cond
        self.nbytes = 0  # bytes of the resident rows  # guarded-by: _cond
        self.error: BaseException | None = None  # first failed copy  # guarded-by: _cond
        self.dropped = False  # guarded-by: _cond

    def queue(self) -> None:
        with self._cond:
            self.queued += 1

    def wanted(self) -> bool:
        """Whether a queued copy should still run."""
        with self._cond:
            return not self.dropped and self.error is None

    def end(self, slot: int, nbytes: int, error: BaseException | None) -> None:
        """One queued copy has ended: ``nbytes`` of slot ``slot`` resident,
        or nothing (skipped), or ``error``."""
        with self._cond:
            self.ended += 1
            if error is not None:
                self.error = self.error or error
            elif nbytes:
                self.slots.add(slot)
                self.nbytes += nbytes
            self._cond.notify_all()

    def wait(self) -> int:
        """Block until every queued copy has ended; the rows resident."""
        with self._cond:
            while self.ended < self.queued:
                self._cond.wait()
            return len(self.slots)

    def resident_bytes(self) -> int:
        with self._cond:
            return self.nbytes

    def take(self, d: int, k: int):
        """Shard ``d``'s device batch for the fold of the first ``k`` slots,
        handed over (the fold's reference is the last): waits for the
        outstanding copies, raises the first one's failure, and returns None
        where the resident rows are not exactly those slots."""
        with self._cond:
            while self.ended < self.queued:
                self._cond.wait()
            if self.error is not None:
                raise self.error
            if self.dropped or self.dev is None or self.slots != set(range(k)):
                self.dev = None
                return None
            batch, self.dev[d] = self.dev[d], None
            return batch

    def drop(self) -> None:
        """Let the device rows go (after the copy in flight, if any, so
        that no copy reads a ring buffer that has gone back)."""
        with self._cond:
            self.dropped = True
            while self.ended < self.queued:
                self._cond.wait()
            self.dev = None


def _release_ring(pool, leases: list, inflight: dict, gauge) -> None:
    """Give a ring's pages back and settle the depth gauges for buffers
    still checked out (an open batch that was never submitted). Idempotent;
    module-level so a ring's GC finalizer holds no ring reference."""
    STAGING_DEPTH.dec(len(inflight))
    if gauge is not None:
        gauge.dec(len(inflight))
    inflight.clear()
    for lease in leases:
        pool.release(lease)


def _ring_migrator(view) -> None:
    """Compaction swap hook for a QUIESCENT ring lease (docs/DESIGN.md
    §23): the pool already rewrote ``lease.array`` to the migrated view
    under its lock, and the ring's free queue holds the lease object —
    not the stale array — so there is no ring state left to fix up.
    Module-level so a lease never strongly references its ring (the GC
    finalizer backstop must still fire for abandoned pipelines)."""


class _StagingRing:
    """Bounded pool of host staging buffers, grown on demand up to ``size``.

    ``acquire`` hands out a free buffer, leases a new one while fewer than
    ``size`` exist, and otherwise blocks until an in-flight batch returns
    one — this is the pipeline's memory bound (the producer can run at
    most ``size`` batches ahead of the fold worker). A round that never
    has more than one batch in flight leases (and zero-fills) one buffer,
    not ``size``.

    Buffers are page runs LEASED from the shared accumulator pool
    (``tenancy.pool``) under the ring's tenant — staging planes (packed
    byte-planar included) page exactly like the shard accumulators, so
    concurrent tenants' rings pack into one arena. ``close()`` releases
    the leases; a GC finalizer backstops abandoned pipelines.

    Free buffers opt into pool compaction (§23): while a lease sits in
    the free queue it carries a migrator, so another tenant's
    between-round defrag may slide it; ``acquire`` clears the migrator
    through the pool lock BEFORE reading the array, making every
    in-flight buffer an immovable barrier, and ``release`` re-registers
    it on the way back in.
    """

    def __init__(self, size: int, shape: tuple, dtype, gauge=None,
                 pool=None, tenant: str = "default", shard: int | None = None):
        self._free: queue_mod.Queue = queue_mod.Queue()
        self.size = size
        self._shape = shape
        self._dtype = dtype
        self._tenant = tenant
        # a shard-parallel pipeline's rings say whose they are on the wait span
        self._span_attrs = {"tenant": tenant, **({} if shard is None else {"shard": shard})}
        # per-shard rings report on the shard-labelled gauge; the global
        # depth gauge keeps counting every owned buffer either way
        self._gauge = gauge
        self._pool = pool if pool is not None else get_pool()
        self._grow_lock = threading.Lock()
        self._granted = 0  # leases taken or being taken  # guarded-by: _grow_lock
        self._leases: list = []
        self._inflight: dict[int, object] = {}  # id(view) -> lease, checked-out buffers
        # abandoned pipelines (dropped without close()) give their pages
        # back when the ring is collected — by then nothing can alias them
        weakref.finalize(
            self, _release_ring, self._pool, self._leases, self._inflight, gauge
        )

    def close(self) -> None:
        """Release the ring's page leases (idempotent). Submitted batches
        must no longer be in flight — the pipeline drains before closing;
        a buffer lent to a batch that was never submitted leaves the depth
        gauges here."""
        _release_ring(self._pool, self._leases, self._inflight, self._gauge)

    def _grow(self):
        """A new lease while the ring is under ``size``, else None. The
        slot is reserved under the lock and the pages are leased (and
        zero-filled) outside it."""
        with self._grow_lock:
            if self._granted >= self.size:
                return None
            self._granted += 1
        try:
            lease = self._pool.lease_host(self._tenant, self._shape, self._dtype)
        except BaseException:
            with self._grow_lock:
                self._granted -= 1
            raise
        self._leases.append(lease)
        return lease

    def acquire(self, timeout: float | None = None) -> np.ndarray:
        t0 = time.monotonic()
        with trace.get_tracer().span(SPAN_RING_WAIT, **self._span_attrs) as wait:
            how = "free"
            try:
                lease = self._free.get_nowait()
            except queue_mod.Empty:
                how = "leased"
                lease = self._grow()
                if lease is None:
                    how = "waited"
                    lease = self._free.get(timeout=timeout)
            wait.set(how=how)
        RING_WAIT_SECONDS.labels(how=how).observe(time.monotonic() - t0)
        # pin first, read second: set_migrator takes the pool lock, so a
        # compaction mid-flight either finished (lease.array is the new
        # view) or will now skip this lease entirely
        self._pool.set_migrator(lease, None)
        buf = lease.array
        self._inflight[id(buf)] = lease
        STAGING_DEPTH.inc()
        if self._gauge is not None:
            self._gauge.inc()
        return buf

    def release(self, buf: np.ndarray) -> None:
        lease = self._inflight.pop(id(buf), None)
        if lease is None:
            return  # close() raced a late release; the lease is gone
        STAGING_DEPTH.dec()
        if self._gauge is not None:
            self._gauge.dec()
        self._pool.set_migrator(lease, _ring_migrator)
        self._free.put(lease)


def _worker_main(ref: "weakref.ref[StreamingAggregator]", q: queue_mod.Queue) -> None:
    """Fold worker loop. Holds NO strong reference to the pipeline between
    items: an abandoned pipeline (e.g. a round that died before drain) is
    garbage-collected normally, and its ``weakref.finalize`` wakes this
    thread with the shutdown sentinel so it exits instead of leaking."""
    while True:
        item = q.get()
        try:
            if item is _SHUTDOWN:
                return
            self = ref()
            if self is None:
                return
            self._process(item)
            del self
        finally:
            q.task_done()


class StreamingAggregator:
    """Bounded streaming front-end over a :class:`ShardedAggregator`.

    One fold worker consumes staged batches FIFO; the caller's thread only
    stages. ``submit_*`` may block — on the staging ring when the producer
    is ``staging_buffers`` batches ahead, on the dispatch queue when it is
    ``dispatch_ahead`` folds ahead — which is the pipeline's backpressure.
    ``drain()`` waits for in-flight work, performs the one deferred
    acceptance sync, credits ``nb_models`` for wire batches, and publishes
    the overlap ratio.

    NOT thread-safe for concurrent producers: submits must come from one
    thread at a time (the coordinator's executor serializes them; tests and
    the bench are single-producer by construction). ``open_batch`` and
    ``stage_row`` are the exception: the rows of an open batch are written
    by pool threads, one writer a slot.
    """

    def __init__(
        self,
        agg: ShardedAggregator,
        staging_buffers: int = 3,
        dispatch_ahead: int = 2,
        max_batch: int = 64,
        shard_parallel: bool | None = None,
        packed: bool | None = None,
        tenant: str = "default",
        pool=None,
        scheduler=None,
    ):
        if staging_buffers < 2:
            raise ValueError("staging_buffers must be >= 2 (no overlap below that)")
        if dispatch_ahead < 1:
            raise ValueError("dispatch_ahead must be >= 1")
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.agg = agg
        self.staging_buffers = staging_buffers
        self.dispatch_ahead = dispatch_ahead
        self.max_batch = min(max_batch, MAX_LAZY_BATCH)
        # shard-parallel: one fold worker per mesh device, on by default
        # whenever the mesh actually has more than one (None = auto)
        n_dev = agg.mesh.devices.size
        self._sharded = n_dev > 1 and (shard_parallel is None or shard_parallel)
        self._n_shards = n_dev if self._sharded else 1
        # the model-axis column range [lo, hi) a shard's staging buffers
        # hold: the mesh devices' own slices, or the padded model whole
        self._slices = shard_slices(agg.padded_length, self._n_shards)
        # packed staging (on by default wherever it shrinks anything): the
        # planar submit paths stage byte-planar uint8[K, bpn, width] planes
        # — bpn/(4L) of the unpacked ring/transfer bytes — and the fold
        # unpacks in-graph. The fold math is the exact same modular sum over
        # the exact same (validated, < order) elements, so the aggregate is
        # byte-identical to unpacked staging.
        self._packed = (
            agg.packed_staging_usable() if packed is None
            else bool(packed) and agg.packed_staging_usable()
        )
        # multi-tenant seam (docs/DESIGN.md §19): the tenant id labels this
        # pipeline's page leases, scheduler slots, spans and flight dumps;
        # the shared pool backs the staging rings;
        # the scheduler interleaves this tenant's fold batches with other
        # tenants' on the one mesh (fairness + global in-flight bound)
        self.tenant = tenant
        self._pool = pool if pool is not None else get_pool()
        self._sched = scheduler if scheduler is not None else get_scheduler()
        self._sched_owner = self._sched.new_owner()
        # abandoned pipelines give their slots back at collection time
        weakref.finalize(self, self._sched.release_owner, self._sched_owner)
        self._plan = None  # shards.ShardPlan while accs live  # guarded-by: _lock
        self._shard_queues: list[queue_mod.Queue] | None = None
        self._shard_workers: list[threading.Thread | None] = []
        self._queue: queue_mod.Queue = queue_mod.Queue(maxsize=dispatch_ahead)
        # the row copier (``xn-h2d``): one thread, one copy in flight, the
        # rows of batches staged at arrival in the order their writes ended
        self._h2d_queue: queue_mod.Queue = queue_mod.Queue()
        self._copier: threading.Thread | None = None  # guarded-by: _lock
        self._row_copies: dict[int, _RowCopies] = {}  # id(open buffer) -> its rows  # guarded-by: _lock
        # where a shard's slice of a row, and its device batch, live: the
        # shard's device, or on one worker the mesh. A row whose home is one
        # device travels flat: as ``[planes, width]`` the chip would pad its
        # planes to a tile of eight (7 -> 8, 10 -> 16) and the link would
        # carry the padding (3.7-4.4 GB/s against 6.4-6.6 flat, PERF.md
        # section 6, PR 48); over a mesh it keeps its model axis, sharded
        devices = list(agg.mesh.devices.flat)
        self._flat_rows = self._n_shards == n_dev  # every shard is one device
        self._row_at = devices if self._flat_rows else [agg._acc_sharding]
        self._batch_at = devices if self._sharded else [
            agg._batch_packed_sharding if self._packed else agg._batch_sharding
        ]
        # every donating device dispatch of this pipeline's threads (a fold,
        # a row's placement into its device batch) runs under it, see
        # ``shards.settle``; the shard plan folds under the same lock
        self._device_lock = threading.Lock()
        # lazy: "wire", or a shard's index (its host batches)  # guarded-by: _lock
        self._rings: dict[str | int, _StagingRing] = {}
        self._pending: list[StreamTicket] = []  # awaiting ok sync  # guarded-by: _lock
        self._in_flight_models = 0  # submitted, not yet folded  # guarded-by: _lock
        self._error: BaseException | None = None  # guarded-by: _lock
        self._poison_seq: int | None = None  # poisoning batch index  # guarded-by: _lock
        self._flight_dumped = False  # one flight dump per pipeline  # guarded-by: _lock
        self._degraded = False  # sync path for the rest of the round  # guarded-by: _lock
        self._batch_seq = 0  # submit-order index: producer-thread confined
        self._worker: threading.Thread | None = None
        self._closed = False
        self._lock = threading.Lock()  # worker-shared counters/pending
        # a fresh pipeline is never degraded — reset the gauge here, not
        # only in close(): a degraded pipeline abandoned on phase failure
        # must not leave the gauge stuck at 1 for later healthy rounds
        DEGRADED.set(0)
        # overlap accounting, reset per drain window: (start, end) of every
        # batch's legs, under "stage" / "fold" and, shard-parallel, under
        # ("stage", d) / ("fold", d) as well
        self._legs: dict = {}  # guarded-by: _lock
        self._lent_since: dict[int, float] = {}  # id(open buffer) -> lent at  # guarded-by: _lock

    # -- lifecycle ---------------------------------------------------------

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=_worker_main,
                args=(weakref.ref(self), self._queue),
                name="xn-stream-fold",
                daemon=True,
            )
            self._worker.start()
            # wake the worker if this pipeline is dropped without close()
            weakref.finalize(self, self._queue.put, _SHUTDOWN)

    def _ensure_copier(self) -> None:
        with self._lock:
            if self._copier is not None and self._copier.is_alive():
                return
            fresh = self._copier is None
            self._copier = threading.Thread(
                target=_worker_main,
                args=(weakref.ref(self), self._h2d_queue),
                name="xn-h2d",
                daemon=True,
            )
            self._copier.start()
        if fresh:
            # wake the copier if this pipeline is dropped without close()
            weakref.finalize(self, self._h2d_queue.put, _SHUTDOWN)

    def close(self) -> None:
        """Drain, then stop the fold worker. Idempotent. A poisoned
        pipeline (worker failure) still shuts down — the error has already
        surfaced (or will) through drain()/submit, and close() is the
        cleanup path."""
        if self._closed:
            return
        try:
            self.drain()
        except StreamingError:
            logger.warning("closing poisoned streaming pipeline")
        self._closed = True
        if self._degraded:  # lint: guarded-ok: post-drain, workers joined below
            DEGRADED.set(0)
        if self._worker is not None and self._worker.is_alive():
            self._queue.put(_SHUTDOWN)
            self._worker.join(timeout=60.0)
        if self._shard_queues is not None:
            for q in self._shard_queues:
                q.put(_SHUTDOWN)
            for w in self._shard_workers:
                if w is not None and w.is_alive():
                    w.join(timeout=60.0)
        with self._lock:
            copier, unsubmitted = self._copier, list(self._row_copies.values())
            self._row_copies.clear()
        for rows in unsubmitted:  # open batches never submitted
            rows.drop()
        if copier is not None and copier.is_alive():
            self._h2d_queue.put(_SHUTDOWN)
            copier.join(timeout=60.0)
        # the per-shard buffers stay ADOPTED by the aggregator
        # (reduce-scatter) so finalize/unmask/snapshot after close still
        # read the accumulator — on a poisoned pipeline they surface the
        # error through drain() first
        self._plan = None  # lint: guarded-ok: post-drain, workers joined above
        # staging pages go back to the pool (nothing is in flight past the
        # drain/joins above)
        with self._lock:
            rings = list(self._rings.values())
            self._rings.clear()
        for ring in rings:
            ring.close()
        self._sched.release_owner(self._sched_owner)

    # -- producer side -----------------------------------------------------

    @property
    def in_flight_models(self) -> int:
        """Submitted-but-uncredited update count (an upper bound for wire
        batches until their acceptance vector syncs at drain)."""
        with self._lock:
            return self._in_flight_models

    def counted_models(self) -> int:
        """``in_flight + agg.nb_models`` read atomically with the worker's
        per-batch handoff (credit nb_models / drop in-flight under the same
        lock), so a caller's capacity check (TooManyModels) never sees a
        batch double-counted mid-fold or dropped between fold and drain."""
        with self._lock:
            return self._in_flight_models + self.agg.nb_models

    @property
    def degraded(self) -> bool:
        """True once a fold failure switched the pipeline to the
        synchronous fold path (the round still completes)."""
        with self._lock:
            return self._degraded

    def _ring(self, kind: str, d: int = 0) -> _StagingRing:
        """The ring of raw wire batches (``kind == "wire"``, whole width on
        any mesh), else shard ``d``'s ring of host batches in the
        pipeline's staging layout, as wide as the shard's column range."""
        key = kind if kind == "wire" else d
        with self._lock:
            ring = self._rings.get(key)
            if ring is None:
                agg = self.agg
                if kind == "wire":
                    shape: tuple = (self.max_batch, agg.padded_length * agg.config.bytes_per_number)
                    dtype = np.uint8
                elif self._packed:
                    # byte-planar packed planes: bpn/(4L) of the planar ring
                    shape = (self.max_batch, agg.packed_width, agg.padded_length // self._n_shards)
                    dtype = np.uint8
                else:
                    shape = (self.max_batch, agg.n_limbs, agg.padded_length // self._n_shards)
                    dtype = np.uint32
                sharded = self._sharded and kind != "wire"
                ring = self._rings[key] = _StagingRing(
                    self.staging_buffers, shape, dtype,
                    gauge=SHARD_STAGING_DEPTH.labels(shard=str(d)) if sharded else None,
                    pool=self._pool, tenant=self.tenant, shard=d if sharded else None,
                )
            return ring

    # -- tenant fold-batch slots (docs/DESIGN.md §19) ----------------------
    #
    # Every batch holds ONE scheduler slot from dispatch until its fold
    # settles (worker completion / last-shard commit / the degraded-path
    # finally). The slot is the cross-tenant interleave point: the
    # scheduler grants it fairly across tenants and bounds the mesh-wide
    # in-flight total, which is the multi-tenant backpressure.

    def _slot_acquire(self) -> None:
        self._sched.acquire(self.tenant, self._sched_owner)

    def _slot_release(self) -> None:
        self._sched.release(self._sched_owner)

    def _flight_poison(self, cause: BaseException, seq: int | None) -> None:
        """ONE forensic dump per pipeline (idempotent under the lock): the
        span ring holds the poisoning batch's stage/fold (and per-shard)
        spans. Worker paths call this AFTER the failing batch's spans have
        closed — a dump taken inside the open span would miss exactly the
        spans it exists to capture."""
        with self._lock:
            if self._flight_dumped:
                return
            self._flight_dumped = True
        flight_dump(
            "pipeline-poison",
            f"batch {seq}: {type(cause).__name__}: {cause}",
            batch=seq,
            tenant=self.tenant,
        )

    def _poison_error(self) -> StreamingError:
        """The sticky error, always naming the poisoning batch and cause."""
        with self._lock:
            cause = self._error
            seq = self._poison_seq
        where = f"batch {seq}" if seq is not None else "deferred sync"
        return StreamingError(
            f"streaming pipeline poisoned at {where}: "
            f"{type(cause).__name__}: {cause}"
        )

    def _poisoned(self) -> BaseException | None:
        """Locked read of the sticky error (producer-side checks)."""
        with self._lock:
            return self._error

    def _check_usable(self) -> None:
        if self._closed:
            raise StreamingError("pipeline is closed")
        err = self._poisoned()
        if err is not None:
            raise self._poison_error() from err

    def _check(self, k: int) -> None:
        self._check_usable()
        if k > self.max_batch:
            raise ValueError(f"batch of {k} exceeds max_batch={self.max_batch}")

    def _dispatch(self, item: tuple) -> None:
        """Queue to the fold worker — or, once degraded, fold synchronously
        on the caller's thread (same math, no overlap)."""
        buf, payload, kind, k, ticket, seq, rows = item
        self._slot_acquire()  # released when the fold settles (_process)
        with self._lock:
            self._in_flight_models += k
            degraded = self._degraded
        BATCHES_TOTAL.labels(stage="staged").inc()
        if not degraded:
            self._ensure_worker()
            INFLIGHT_FOLDS.inc()
            self._queue.put(item)
            return
        t0 = time.monotonic()
        if rows is not None:
            rows.drop()  # the degraded path copies the batch whole
        try:
            # serialize with the worker: batches queued BEFORE degradation
            # (including the retry that flipped the flag) must finish before
            # a caller-thread fold touches agg.acc — two unsynchronized
            # mutators would lose updates
            self._queue.join()
            err = self._poisoned()
            if err is not None:
                raise self._poison_error() from err
            self._fold_payload(payload, kind, k, ticket, defer_ok=False)
        except StreamingError:
            # already-poisoned pipeline: this batch just leaves flight
            with self._lock:
                self._in_flight_models -= k
            BATCHES_TOTAL.labels(stage="failed").inc()
            raise
        except BaseException as e:
            unsafe = isinstance(e, _UnsafeFoldError)
            cause = (e.__cause__ or e) if unsafe else e
            with self._lock:
                first = self._error is None
                self._error = cause
                self._poison_seq = seq
                if not (unsafe and e.settled):
                    self._in_flight_models -= k
            if first:
                self._flight_poison(cause, seq)
            BATCHES_TOTAL.labels(stage="failed").inc()
            raise self._poison_error() from cause
        finally:
            self._slot_release()
            self._ring(kind).release(buf)
            self._leg(t0, "fold")
        BATCHES_TOTAL.labels(stage="folded").inc()

    # -- host batches: open, fill slot by slot, submit ----------------------
    #
    # The pipeline lends a batch one buffer of every shard's ring when the
    # batch OPENS. Rows are written into their slots while the batch is
    # still filling (by the caller's own threads: the update phase writes
    # each accepted update as it arrives), each row's column range
    # ``[lo, hi)`` into that shard's buffer, and submitting is bookkeeping:
    # one item to the fold worker, or one to each shard's. A single-device
    # pipeline is the case of one shard.

    @property
    def _host_kind(self) -> str:
        return "packed" if self._packed else "planar"

    def open_batch(self) -> list[np.ndarray]:
        """Take the ring buffers the next host batch is staged into, one a
        shard: columns ``[lo, hi)`` of the batch's row ``i`` belong in
        ``bufs[d][i]``. Blocks while every buffer of a ring is owned by a
        batch in flight (the first call of a round leases the first
        buffers). The buffers go back through ``submit_staged`` or
        ``release_batch``."""
        self._check_usable()
        bufs: list[np.ndarray] = []
        try:
            for d in range(self._n_shards):
                bufs.append(self._ring(self._host_kind, d).acquire())
        except BaseException:
            self.release_batch(bufs)
            raise
        with self._lock:
            self._lent_since[id(bufs[0])] = time.monotonic()
            self._row_copies[id(bufs[0])] = _RowCopies(bufs)
        return bufs

    def _relay_wire_rows(self, view: np.ndarray, stack: np.ndarray, lo: int, hi: int) -> None:
        """Columns ``[lo, hi)`` of wire-layout ``uint32[k, model_len, L]``
        rows into ``k`` slots of a ring buffer, in the ring's layout, pad
        columns included (a reused buffer is dirty)."""
        from ..ops import limbs as host_limbs

        real_hi = min(hi, self.agg.model_length)
        if lo < real_hi:
            if self._packed:
                # pack straight into the byte-planar slots (the native
                # plane-pack kernel): one strided transpose of the first bpn
                # wire bytes per element — the same copy class as the planar
                # transpose below, writing bpn/(4L) of the bytes
                host_limbs.pack_wire_slice(stack, lo, real_hi, self.agg.packed_width, view)
            else:
                # transpose straight into the slots (numpy strided copy, no
                # wire_to_planar intermediate)
                view[:, :, : real_hi - lo] = stack[:, lo:real_hi, :].transpose(0, 2, 1)
        if real_hi < hi:
            view[:, :, max(0, real_hi - lo):] = 0

    def stage_row(self, bufs: list[np.ndarray], i: int, wire: np.ndarray) -> None:
        """Write one wire-layout ``uint32[model_len, L]`` update into slot
        ``i`` of an open batch's buffers, each shard's column range into
        that shard's. Any thread; a slot has one writer."""
        if wire.shape != (self.agg.model_length, self.agg.n_limbs):
            raise ValueError("expected uint32[model_len, L]")
        for buf, (lo, hi) in zip(bufs, self._slices):
            self._relay_wire_rows(buf[i : i + 1], wire[None], lo, hi)
        self._row_written(bufs, i)

    @property
    def takes_planes(self) -> bool:
        """Whether a slot of this pipeline's rings is byte planes, so that a
        wire v2 body's planes go in by copy (:meth:`stage_planes`)."""
        return self._packed

    def stage_planes(self, bufs: list[np.ndarray], i: int, planes: np.ndarray) -> None:
        """:meth:`stage_row` for an update that arrived as byte planes
        ``uint8[bpn, model_len]`` (wire v2, every element checked against the
        order by its parse): each shard's column range of every plane is
        copied into slot ``i`` of that shard's buffer and the pad columns
        zeroed, as the plane pack leaves them. No relayout: the body's
        layout is the slot's."""
        if not self._packed or planes.dtype != np.uint8 or planes.shape != (
            self.agg.packed_width, self.agg.model_length
        ):
            raise ValueError("expected uint8[bpn, model_len] planes and packed staging")
        from ..ops import limbs as host_limbs

        for buf, (lo, hi) in zip(bufs, self._slices):
            real_hi = min(hi, self.agg.model_length)
            if lo < real_hi:
                host_limbs.copy_planes(planes[:, lo:real_hi], buf[i, :, : real_hi - lo])
            if real_hi < hi:
                buf[i, :, max(0, real_hi - lo):] = 0
        self._row_written(bufs, i)

    def _row_written(self, bufs: list[np.ndarray], i: int) -> None:
        """Slot ``i`` of an open batch holds its update, by either road."""
        ROWS_STAGED.labels(route="arrival").inc()
        # the slot is written: its copy to the device starts now, behind
        # the copies queued before it, and not on this thread. A degraded
        # pipeline copies a batch whole at its fold, as before
        with self._lock:
            rows = None if self._degraded else self._row_copies.get(id(bufs[0]))
        if rows is not None:
            rows.queue()
            self._ensure_copier()
            self._h2d_queue.put((rows, i))

    def wait_rows(self, bufs: list[np.ndarray]) -> int:
        """Block until every row copy queued so far for an open batch has
        ended (what its fold waits for); the number of rows resident."""
        with self._lock:
            rows = self._row_copies.get(id(bufs[0]))
        return rows.wait() if rows is not None else 0

    def _close_open(self, bufs: list[np.ndarray]) -> tuple:
        """An open batch stops being open: when its buffers were lent, and
        its device rows (either may be None)."""
        if not bufs:
            return None, None
        with self._lock:
            return (self._lent_since.pop(id(bufs[0]), None),
                    self._row_copies.pop(id(bufs[0]), None))

    def release_batch(self, bufs: list[np.ndarray]) -> None:
        """Return an open batch's buffers unfolded (a failed slot write);
        the rows already copied are dropped first."""
        _lent, rows = self._close_open(bufs)
        if rows is not None:
            rows.drop()
        for d, buf in enumerate(bufs):
            self._ring(self._host_kind, d).release(buf)

    def submit_staged(self, bufs: list[np.ndarray], k: int) -> StreamTicket:
        """Stream-fold the first ``k`` slots of an open batch's buffers,
        already in the ring's layout. Owns the buffers from here on, an
        error included."""
        kind = self._host_kind
        try:
            self._check(k)
            with trace.get_tracer().span(
                SPAN_STAGE, batch=self._batch_seq + 1, kind=kind, k=k, shards=self._n_shards
            ):
                views = [buf[:k] for buf in bufs]
                plan = self._ensure_plan(k, lambda: self._planar_of(views)) if self._sharded else None
                BYTES_STAGED.labels(layout="packed" if self._packed else "unpacked").inc(
                    sum(view.nbytes for view in views)
                )
                ticket = StreamTicket(k)
        except BaseException:
            self.release_batch(bufs)
            raise
        self._batch_seq += 1
        lent, rows = self._close_open(bufs)
        if lent is not None:
            # the buffers in hand -> handed over, every shard's leg alike
            shards = range(self._n_shards) if self._sharded else ()
            self._leg(lent, "stage", *(("stage", d) for d in shards))
        if rows is not None:
            # what the flush finds on the device already; its fold waits for
            # the rest (after the last slot write: one row's copy)
            H2D_EARLY_BYTES.inc(rows.resident_bytes())
        if plan is None:
            self._dispatch((bufs[0], views[0], kind, k, ticket, self._batch_seq, rows))
            return ticket
        job = _BatchJob(kind, k, ticket, self._batch_seq, self._n_shards, rows=rows)
        self._dispatch_sharded(job, [
            (job, d, view, self._ring(kind, d), buf)
            for d, (view, buf) in enumerate(zip(views, bufs))
        ])
        return ticket

    def _planar_of(self, views: list[np.ndarray]) -> np.ndarray:
        """The staged planar ``uint32[k, L, padded]`` batch that the shards'
        views hold between them: what the start-up race of the fold kernels
        times, once, where no verdict is known yet."""
        if self._packed:
            from ..ops import limbs as host_limbs

            views = [host_limbs.unpack_planar(view, self.agg.n_limbs) for view in views]
        return np.concatenate(views, axis=-1)

    def submit_batch(self, stack: np.ndarray) -> StreamTicket:
        """Stage + stream-fold wire-layout ``uint32[K, model_len, L]``
        updates (the pre-validated path: all members count immediately):
        a batch opened, filled and submitted in this one call, each shard's
        relayout under a ``stream.stage`` span of its own."""
        stack = np.asarray(stack, dtype=np.uint32)  # host input, no device sync  # lint: sync-ok
        if stack.ndim != 3 or stack.shape[2] != self.agg.n_limbs:
            raise ValueError("expected uint32[K, model_len, L]")
        if stack.shape[1] != self.agg.model_length:
            raise ValueError("model length mismatch")
        k = stack.shape[0]
        self._check(k)
        bufs = self.open_batch()
        try:
            for d, (buf, (lo, hi)) in enumerate(zip(bufs, self._slices)):
                with trace.get_tracer().span(
                    SPAN_STAGE, batch=self._batch_seq + 1, kind=self._host_kind, k=k,
                    route="flush", shard=d,
                ):
                    self._relay_wire_rows(buf[:k], stack, lo, hi)
        except BaseException:
            self.release_batch(bufs)
            raise
        ROWS_STAGED.labels(route="flush").inc(k)
        return self.submit_staged(bufs, k)

    # -- resident rows: folded where they lie, on the caller's thread --------

    @contextmanager
    def _resident_batch(self, k: int, result):
        """One flush's ``k`` rows, on the device since each was accepted
        (wire ingest), folded on the caller's thread inside: the batch it is
        on ``xaynet_streaming_batches_total``, as a queued one is. ``staged``
        when the flush hands the rows over; ``folded`` when ``result()``, the
        accumulator that the last chunk's fold returned, is ready on the
        device (a fold's dispatch returns before the device has run it, and
        whoever reads the counter takes ``folded`` to mean the fold
        completed); ``failed`` if anything inside raised. One count a flush,
        however many chunks it is folded in, under one ``stream.fold`` span."""
        import jax

        self._batch_seq += 1
        BATCHES_TOTAL.labels(stage="staged").inc()
        try:
            with trace.get_tracer().span(SPAN_FOLD, batch=self._batch_seq, how="resident", k=k):
                yield
                jax.block_until_ready(result())  # lint: sync-ok
        except BaseException:
            BATCHES_TOTAL.labels(stage="failed").inc()
            raise
        BATCHES_TOTAL.labels(stage="folded").inc()

    def fold_resident_rows_now(self, rows: list) -> None:
        """Fold already device-resident, validity-checked updates on the
        CALLER's thread (the wire-ingest server path: the rows that
        ``ShardedAggregator.validate_wire_update(s)`` /
        ``validate_planar_update(s)`` left on the device): planar
        ``uint32[L, padded_len]`` from the v1 wire, PACKED byte-planar
        ``uint8[bpn, padded_len]`` from v2 (``bpn`` bytes an element instead
        of the ``4L`` a resident uint32 planar pins), in any mix.

        Deliberately NOT queued: these rows already occupy device memory,
        so parking them behind ``dispatch_ahead`` would pin up to
        ``dispatch_ahead + 1`` full batches in HBM (~13 GB each at
        25M/batch 64) — and XLA's own asynchronous dispatch already
        overlaps device-side folds without our queue. Waits out queued
        work first (``agg.acc`` has exactly one mutator at a time), then
        stacks + folds in chunks of ``RESIDENT_CHUNK``, a layout at a time,
        so peak device memory stays at the staged rows + one chunk-sized
        copy. A packed chunk folds through the fused packed kernel
        (``agg._fold_packed``: the uint32 expansion only ever exists
        transiently inside the jit); where no fold kernel is resolved yet, or
        the pipeline is shard-parallel, it is unpacked on the device, a chunk
        at a time, and folded as a planar one is: the start-up race of the
        kernels times a planar batch, and the shard plan folds planar ones.
        The whole flush is ONE batch of the pipeline's counters
        (:meth:`_resident_batch`)."""
        if not rows:
            return
        if self._sharded:
            self._join_shard_queues()
        else:
            self._queue.join()
        err = self._poisoned()
        if err is not None:
            raise self._poison_error() from err
        if self._closed:
            raise StreamingError("pipeline is closed")
        from ..ops.limbs_jax import packed_planar_to_limbs_jit

        agg = self.agg
        packed = [r for r in rows if r.dtype == np.uint8]
        chunks = [
            group[i : i + RESIDENT_CHUNK]
            for group in (packed, [r for r in rows if r.dtype != np.uint8])
            for i in range(0, len(group), RESIDENT_CHUNK)
        ]
        n_packed = -(-len(packed) // RESIDENT_CHUNK)
        del rows, packed

        def planar_of(piece, is_packed):
            staged = agg._make_stack_fn()(*piece)
            if not is_packed:
                return staged
            import jax

            # pinned: the shard plan reads addressable shards by column start
            return jax.device_put(
                packed_planar_to_limbs_jit(staged, agg.n_limbs), agg._batch_sharding
            )

        if self._sharded:
            raced = []  # the first chunk, where the start-up race stacked it

            def first_chunk():
                raced.append(planar_of(chunks[0], n_packed > 0))
                return raced[0]

            plan = self._ensure_plan(len(chunks[0]), first_chunk)
            with self._resident_batch(sum(map(len, chunks)), lambda: plan.accs):
                for i in range(len(chunks)):
                    piece, chunks[i] = chunks[i], None  # consumed: free as we fold
                    stacked = raced.pop() if raced else planar_of(piece, i < n_packed)
                    self._fold_pinned_stack(plan, stacked, len(piece))
            return
        with self._resident_batch(sum(map(len, chunks)), lambda: agg.acc):
            for i in range(len(chunks)):
                piece, chunks[i] = chunks[i], None  # consumed: free as we fold
                n_piece = len(piece)
                is_packed = i < n_packed
                if is_packed:
                    agg._resolve_kernel_cheap(n_piece)
                if is_packed and agg.kernel_used is not None:
                    staged, fold = agg._make_stack_fn()(*piece), agg._fold_packed
                else:
                    staged, fold = planar_of(piece, is_packed), agg._fold
                del piece
                # caller-thread folds hold a scheduler slot per chunk too, so
                # the device-resident fast path cannot starve other tenants
                self._slot_acquire()
                try:
                    agg.acc = fold(agg.acc, staged)
                finally:
                    self._slot_release()
                with self._lock:
                    agg.nb_models += n_piece

    def fold_planar_stack_now(self, stacked) -> None:
        """Fold an already device-resident planar ``[K, L, padded_len]``
        BATCH on the CALLER's thread — the fused-mask-pipeline shape
        (``ops.masking_jax``): a whole seed group's mask planes come out of
        one jitted derive as a single stacked array, so re-slicing it into
        rows only to re-stack them would buy two copies. Same rationale and
        accounting as :meth:`fold_resident_rows_now` (device-resident batches
        are never queued; ``agg.acc`` has one mutator at a time); in
        shard-parallel mode each shard folds its addressable slice."""
        if stacked.shape[0] == 0:
            return
        k = int(stacked.shape[0])
        import jax

        agg = self.agg
        if self._sharded:
            self._join_shard_queues()
            err = self._poisoned()
            if err is not None:
                raise self._poison_error() from err
            if self._closed:
                raise StreamingError("pipeline is closed")
            plan = self._ensure_plan(k, lambda: stacked)
            # pin the mesh layout: the derive emits a single-device array,
            # and the per-shard fan-out reads addressable shards
            stacked = jax.device_put(stacked, agg._batch_sharding)
            self._fold_pinned_stack(plan, stacked, k)
            return
        self._queue.join()
        err = self._poisoned()
        if err is not None:
            raise self._poison_error() from err
        if self._closed:
            raise StreamingError("pipeline is closed")
        agg._resolve_kernel_cheap(k)
        self._slot_acquire()
        try:
            new_acc = agg._fold(agg.acc, stacked)
        finally:
            self._slot_release()
        with self._lock:
            agg.acc = new_acc
            agg.nb_models += k

    def submit_wire_batch(self, raw: np.ndarray) -> StreamTicket:
        """Stage + stream-fold RAW wire element blocks
        ``uint8[K, model_len * bpn]``. Acceptance is DEFERRED: the per-member
        ``bool[K]`` lands on the ticket at the next ``drain()`` (the fold
        itself excludes invalid members either way)."""
        agg = self.agg
        bpn = agg.config.bytes_per_number
        raw = np.asarray(raw)  # host input, no device sync  # lint: sync-ok
        if raw.dtype != np.uint8 or raw.ndim != 2 or raw.shape[1] != agg.model_length * bpn:
            raise ValueError("expected uint8[K, model_len * bytes_per_number]")
        k = raw.shape[0]
        self._check(k)
        with trace.get_tracer().span(
            SPAN_STAGE, batch=self._batch_seq + 1, kind="wire", k=k
        ):
            t0 = time.monotonic()
            ring = self._ring("wire")
            buf = ring.acquire()
            view = buf[:k]
            view[:, : raw.shape[1]] = raw
            if agg.padded_length != agg.model_length:
                view[:, raw.shape[1] :] = 0  # zero bytes decode to zero elements
            BYTES_STAGED.labels(layout="wire").inc(view.nbytes)
            ROWS_STAGED.labels(route="flush").inc(k)
            ticket = StreamTicket(k)
            self._leg(t0, "stage")
        if self._sharded:
            return self._dispatch_sharded_wire(ring, buf, view, k, ticket)
        self._batch_seq += 1
        self._dispatch((buf, view, "wire", k, ticket, self._batch_seq, None))
        return ticket

    # -- fold worker -------------------------------------------------------

    def _credit(self, staged, k: int, packed: bool = False) -> None:
        """Fold a planar (or packed byte-planar) batch and hand its count
        over atomically: the nb_models credit and the in-flight drop happen
        under one lock, so ``counted_models()`` never observes the batch
        twice (double count → spurious TooManyModels near the cap) or zero
        times."""
        agg = self.agg
        fold = agg._fold_packed if packed else agg._fold
        with self._device_lock:  # the copier's placements donate too
            new_acc = settle(fold(agg.acc, staged))
        with self._lock:
            agg.acc = new_acc
            agg.nb_models += k
            self._in_flight_models -= k

    def _fold_payload(self, payload, kind: str, k: int, ticket, defer_ok: bool,
                      rows: _RowCopies | None = None) -> None:
        """Fold one staged batch. ``defer_ok=True`` (worker path) leaves a
        wire batch's acceptance vector in flight for drain's single sync;
        ``defer_ok=False`` (degraded sync path) resolves it immediately.

        A host batch whose rows went to the device as it filled (``rows``)
        is folded from there once the copies still outstanding have ended;
        a failed copy is raised before anything is folded, so the ladder's
        retry (which passes no rows) copies the batch whole from the ring
        buffer, as a batch staged inside one call always is.

        Failure classes matter here: the accumulator is reassigned only
        when a fold call RETURNS, so an exception raised before/inside the
        fold leaves ``agg.acc`` consistent (the degrade path may retry the
        batch). Failures after that point — the ring-buffer transfer wait
        and the acceptance fetch — are wrapped in ``_UnsafeFoldError``:
        retrying them would double-fold the batch."""
        import jax

        agg = self.agg
        if kind == "wire":
            # the copy has a wait of its own only on the worker path; the
            # degraded path's one sync is the acceptance fetch below
            with _h2d(kind, payload.nbytes) if defer_ok else nullcontext():
                staged = jax.device_put(payload, agg._batch_bytes_sharding)
                ok = agg.dispatch_staged_bytes(staged)
                # -- acc now references this batch: no retry beyond this line --
                if defer_ok:
                    ticket._ok = ok
                    with self._lock:
                        self._pending.append(ticket)
                    try:
                        # the transfer out of the ring buffer must complete
                        # before reuse; the fold itself stays in flight behind it
                        jax.block_until_ready(staged)  # lint: sync-ok
                    except BaseException as e:
                        with self._lock:
                            if ticket in self._pending:
                                self._pending.remove(ticket)
                        ticket._ok = None
                        raise _UnsafeFoldError() from e
                    return
            try:
                ok_host = np.asarray(ok)  # acceptance sync (and fold barrier)  # lint: sync-ok
            except BaseException as e:
                raise _UnsafeFoldError() from e
            ticket.accepted = ok_host
            with self._lock:
                agg.nb_models += int(ok_host.sum())
                self._in_flight_models -= k
            return
        packed = kind == "packed"
        agg._resolve_kernel_cheap(k)
        if packed and agg.kernel_used is None:
            # the auto race calibrates on a PLANAR staged batch (both
            # candidate folds take that shape): unpack this batch once on
            # the host for the one-time timing run, then fold the packed
            # original through the winner
            from ..ops import limbs as host_limbs

            planar = host_limbs.unpack_planar(
                np.asarray(payload), agg.n_limbs  # host ring view  # lint: sync-ok
            )
            agg._resolve_kernel(jax.device_put(planar, agg._batch_sharding))
        staged = rows.take(0, k) if rows is not None else None
        note_h2d_route("batch" if staged is None else "row")
        with _h2d(kind, payload.nbytes, "batch") if staged is None else nullcontext():
            if staged is None:
                staged = jax.device_put(payload, self._batch_at[0])
            self._credit(staged, k, packed=packed)
            try:
                # host buffer free to reuse: the batch's copy, or its rows'
                # placements into the device batch, have read it
                jax.block_until_ready(staged)  # lint: sync-ok
            except BaseException as e:
                # _credit already handed the count off: settled
                raise _UnsafeFoldError(settled=True) from e
        ticket.accepted = np.ones(k, dtype=bool)

    def _degrade_and_retry(self, payload, kind: str, k: int, ticket, seq: int,
                           first: BaseException) -> str:
        """First fold failure with a consistent accumulator: switch the
        pipeline to the synchronous path and retry the batch once. Returns
        the outcome label; a second failure poisons permanently."""
        logger.warning(
            "streaming fold failed at batch %d (%s: %s); retrying on the "
            "synchronous path and degrading the pipeline",
            seq,
            type(first).__name__,
            first,
        )
        with self._lock:
            self._degraded = True
        DEGRADED.set(1)
        DEGRADATIONS.inc()
        try:
            self._fold_payload(payload, kind, k, ticket, defer_ok=False)
            return "folded-degraded"
        except BaseException as second:
            # the batch is lost: the accumulator no longer matches any
            # consistent update set — poison permanently, with the batch
            # index and root cause on every later error (the caller fires
            # the flight dump once its span has closed)
            unsafe = isinstance(second, _UnsafeFoldError)
            cause = (second.__cause__ or second) if unsafe else second
            cause.__context__ = first
            with self._lock:
                self._error = cause
                self._poison_seq = seq
                if not (unsafe and second.settled):
                    self._in_flight_models -= k
            logger.exception("streaming fold batch %d lost; pipeline poisoned", seq)
            return "failed"

    def _process(self, item: tuple) -> None:
        """Worker-side fold with the degradation ladder: streaming fold ->
        one synchronous retry (switching the pipeline to sync mode) ->
        sticky poison naming the batch and the original exception."""
        if isinstance(item[0], _UnmaskJob):  # eager unmask tail item
            return self._process_unmask(item)
        if isinstance(item[0], _BatchJob):  # shard-parallel item
            return self._process_shard(item)
        if isinstance(item[0], _RowCopies):  # the copier's item
            return self._copy_row(item)
        buf, payload, kind, k, ticket, seq, rows = item
        agg_t0 = time.monotonic()
        outcome = "folded"
        with trace.get_tracer().span(SPAN_FOLD, batch=seq, kind=kind, k=k) as fold_span:
            try:
                try:
                    maybe_fail("streaming.fold")
                    self._fold_payload(payload, kind, k, ticket, defer_ok=True, rows=rows)
                except BaseException as first:
                    if isinstance(first, _UnsafeFoldError):
                        # acc may already reference the batch: retrying would
                        # double-fold it — poison straight away
                        cause = first.__cause__ or first
                        with self._lock:
                            self._error = cause
                            self._poison_seq = seq
                            if not first.settled:
                                self._in_flight_models -= k
                        outcome = "failed"
                        logger.exception(
                            "streaming fold batch %d failed post-dispatch; pipeline poisoned",
                            seq,
                        )
                    else:
                        outcome = self._degrade_and_retry(payload, kind, k, ticket, seq, first)
            finally:
                done = time.monotonic()
                self._slot_release()
                if buf is not None:
                    self._ring(kind).release(buf)
                self._leg(agg_t0, "fold")
                INFLIGHT_FOLDS.dec()
                # one shard: nobody to wait for, the commit is the hand-back
                self._record_commit(done, seq, outcome)
                # a failed fold is NOT folded: dashboards comparing staged vs
                # folded must be able to see the loss
                BATCHES_TOTAL.labels(stage=outcome).inc()
                fold_span.set(outcome=outcome)
        if outcome == "failed":
            # the dump fires AFTER the batch's fold span closed, so the
            # ring it snapshots contains the poisoning batch's spans
            with self._lock:
                cause, pseq = self._error, self._poison_seq
            if cause is not None:
                self._flight_poison(cause, pseq)

    # -- row copier --------------------------------------------------------

    def _copy_row(self, item: tuple) -> None:
        """The copier's item: slot ``i`` of an open batch has been written
        and goes to the device, every shard's slice of it. Nothing raises
        here: a failure is the batch's (``_RowCopies.error``), found by its
        fold worker, and the rows queued behind it are skipped."""
        rows, i = item
        nbytes, error = 0, None
        try:
            if rows.wanted():
                maybe_fail("streaming.h2d_row")
                nbytes = self._put_row(rows, i)
        except BaseException as e:
            error = e
            logger.warning("row copy of slot %d failed (%s: %s)", i, type(e).__name__, e)
        finally:
            rows.end(i, nbytes, error)

    def _put_row(self, rows: _RowCopies, i: int) -> int:
        """Copy slot ``i`` of every shard's ring buffer to that shard's
        device, one copy at a time (``shards.H2D_GATE``), and place it in
        the shard's device batch (made, zeroed, with the batch's first row).
        Returns the bytes copied."""
        import jax
        import jax.numpy as jnp

        if rows.dev is None:
            rows.dev = [
                jnp.zeros(buf.shape, buf.dtype, device=at)
                for buf, at in zip(rows.bufs, self._batch_at)
            ]
        nbytes = 0
        for d, buf in enumerate(rows.bufs):
            row = buf[i].reshape(-1) if self._flat_rows else buf[i]  # a view
            where = {"shard": d} if self._sharded else {}
            with H2D_GATE, _h2d(self._host_kind, row.nbytes, "row", slot=i, **where):
                # enqueue only; on a mesh under the dispatch lock, as a
                # shard's whole-batch copy is (_fold_shard_item)
                with self._device_lock if self._sharded else nullcontext():
                    staged = jax.device_put(row, self._row_at[d])
                jax.block_until_ready(staged)  # lint: sync-ok
            with self._device_lock:
                rows.dev[d] = settle(place_row(rows.dev[d], staged, i))
            nbytes += row.nbytes
        return nbytes

    # -- drain -------------------------------------------------------------

    def drain(self) -> int:
        """Wait for every in-flight fold, then perform the ONE deferred
        acceptance sync: fetch all pending ``ok`` vectors, resolve their
        tickets, credit ``nb_models``. Returns the number of updates
        accepted from deferred wire batches in this window.

        In shard-parallel mode this is the CROSS-SHARD BARRIER: every
        shard queue drains, every shard's device folds complete, and the
        per-shard accumulators reassemble into the aggregator's global
        ``acc`` before anything reads it."""
        with trace.get_tracer().span(SPAN_DRAIN, sharded=self._sharded):
            return self._drain_inner()

    def _drain_inner(self) -> int:
        if self._sharded:
            return self._drain_sharded()
        self._queue.join()
        err = self._poisoned()
        if err is not None:
            # the pipeline is poisoned — PERMANENTLY: once the degraded
            # retry has also failed the accumulator no longer corresponds
            # to any consistent update set, so every later drain (finalize,
            # close) must keep failing rather than let a snapshot with
            # missing/uncounted updates escape as a valid round result.
            # The deferred state is discarded once (stale tickets must not
            # resolve and their counts must leave flight).
            with self._lock:
                stale, self._pending = self._pending, []
                self._in_flight_models -= sum(t.k for t in stale)
            for ticket in stale:
                ticket._ok = None
            raise self._poison_error() from err
        with self._lock:
            pending, self._pending = self._pending, []
        accepted = 0
        try:
            for ticket in pending:
                ok_host = np.asarray(ticket._ok)
                ticket._ok = None
                ticket.accepted = ok_host
                accepted += int(ok_host.sum())
            # a true completion barrier: the worker only blocks on staged
            # INPUTS (ring-buffer reuse), so with profiling off the last
            # folds may still be executing behind XLA's async dispatch —
            # and their errors surface here, not in the worker
            import jax

            jax.block_until_ready(self.agg.acc)
        except Exception as e:
            # an asynchronously-dispatched fold failed (e.g. device OOM):
            # the accumulator may already reference the failed computation,
            # so no consistent synchronous retry exists — poison exactly
            # like an exhausted worker retry (drop the deferred counts and
            # keep every later drain failing)
            with self._lock:
                fresh = self._error is None
                self._error = e
                self._in_flight_models -= sum(t.k for t in pending)
            for ticket in pending:
                ticket._ok = None
            if fresh:
                self._flight_poison(e, None)
            raise self._poison_error() from e
        if pending:
            # the ONE deferred credit: the accepted count lands and the
            # optimistic in-flight count drops in the same locked step, so
            # counted_models() never dips (folded-but-uncredited) nor
            # double-counts
            with self._lock:
                self.agg.nb_models += accepted
                self._in_flight_models -= sum(t.k for t in pending)
        self._publish_overlap()
        return accepted

    def _record_commit(self, first_done: float, seq: int, outcome: str) -> None:
        """``stream.commit`` and its histogram for one batch, by the thread
        that settles it: ``first_done`` (the first shard's fold item done;
        on one shard, the only one's) until now, when the batch counts.
        Recorded after the fact: on a mesh no one thread is there for both
        ends, so no mirror can carry it."""
        waited = time.monotonic() - first_done
        COMMIT_SECONDS.observe(waited)
        trace.get_tracer().record_span(
            SPAN_COMMIT, start=first_done, duration=waited, batch=seq, outcome=outcome,
            shards=self._n_shards,
        )

    def _leg(self, start: float, *keys) -> None:
        """A leg that began at ``start`` ends now: kept under each key for
        the drain window's overlap ratio."""
        leg = (start, time.monotonic())
        with self._lock:
            for key in keys:
                self._legs.setdefault(key, []).append(leg)

    def _publish_overlap(self) -> None:
        with self._lock:  # the drain barrier already quiesced the workers
            legs, self._legs = self._legs, {}
        ratio = _overlap_ratio(legs.get("stage", ()), legs.get("fold", ()))
        if ratio is not None:
            OVERLAP_RATIO.set(ratio)
        if self._sharded:
            for d in range(self._n_shards):
                ratio = _overlap_ratio(legs.get(("stage", d), ()), legs.get(("fold", d), ()))
                if ratio is not None:
                    SHARD_OVERLAP.labels(shard=str(d)).set(ratio)

    # -- shard-parallel mode ----------------------------------------------
    #
    # One fold worker per mesh shard. The producer slices each padded
    # batch once on the host into per-shard staging rings; the batch
    # commits only when EVERY shard folded its slice (_BatchJob); drain()
    # is the cross-shard barrier that reassembles the per-shard donated
    # accumulators (shards.ShardPlan) into the aggregator's global acc.

    def _ensure_plan(self, k: int, calib_staged):
        """Resolve the fold kernel (on the first real batch, exactly like
        the sequential path) and build the shard plan. ``calib_staged``
        lazily produces a full staged planar ``[K, L, padded]`` (host or
        device) — only invoked when an auto verdict is not already memoized
        for this shape."""
        agg = self.agg
        if agg.kernel_used is None:
            agg._resolve_kernel_cheap(k)
            if agg.kernel_used is None:
                import jax

                staged = calib_staged()
                if not isinstance(staged, jax.Array):
                    staged = jax.device_put(staged, agg._batch_sharding)
                agg._resolve_kernel(staged)
        with self._lock:
            plan = self._plan
        if plan is not None and agg._live_plan is not plan:
            # an explicit accumulator write (restore/reset) superseded the
            # adopted plan: the per-shard buffers are stale (only this
            # producer folds into it, so nothing is in flight) — rebuild
            plan = None
        if plan is None:
            from .shards import ShardPlan

            # built outside the lock (device work); the single producer is
            # the only creator, the lock just publishes the reference.
            # The plan is ADOPTED by the aggregator (reduce-scatter): it
            # persists across drain windows as the authoritative
            # accumulator, so the per-drain reassemble+decompose round
            # trip is gone — the only gathers left are explicit acc reads
            plan = ShardPlan(agg, dispatch_lock=self._device_lock)
            agg.adopt_plan(plan)
            with self._lock:
                self._plan = plan
        return plan

    def _ensure_shard_workers(self) -> None:
        if self._shard_queues is None:
            self._shard_queues = [
                queue_mod.Queue(maxsize=self.dispatch_ahead)
                for _ in range(self._n_shards)
            ]
            self._shard_workers = [None] * self._n_shards
            for q in self._shard_queues:
                # wake the worker if this pipeline is dropped without close()
                weakref.finalize(self, q.put, _SHUTDOWN)
        for i, q in enumerate(self._shard_queues):
            w = self._shard_workers[i]
            if w is None or not w.is_alive():
                w = threading.Thread(
                    target=_worker_main,
                    args=(weakref.ref(self), q),
                    name=f"xn-stream-fold-{i}",
                    daemon=True,
                )
                self._shard_workers[i] = w
                w.start()

    def _join_shard_queues(self) -> None:
        for q in self._shard_queues or []:
            q.join()

    def _poison(self, cause: BaseException, seq: int) -> None:
        with self._lock:
            if self._error is None:
                self._error = cause
                self._poison_seq = seq

    def _dispatch_sharded(self, job: _BatchJob, items: list) -> None:
        """Queue one item per shard worker — or, once degraded, fold every
        shard on the caller's thread after a full queue barrier (same math,
        no overlap; the batch still commits atomically)."""
        self._slot_acquire()  # one slot per BATCH; the last shard releases
        with self._lock:
            self._in_flight_models += job.k
            degraded = self._degraded
        BATCHES_TOTAL.labels(stage="staged").inc()
        if not degraded:
            self._ensure_shard_workers()
            INFLIGHT_FOLDS.inc()
            for item, q in zip(items, self._shard_queues):
                SHARD_INFLIGHT.labels(shard=str(item[1])).inc()
                q.put(item)
            return
        t0 = time.monotonic()
        released = [False] * len(items)
        with self._lock:
            rows, job.rows = job.rows, None
        if rows is not None:
            rows.drop()  # the degraded path copies the batch whole
        try:
            # serialize with the shard workers: batches queued BEFORE the
            # degradation must land before caller-thread folds touch the
            # per-shard accumulators
            self._join_shard_queues()
            err = self._poisoned()
            if err is not None:
                raise self._poison_error() from err
            for i, (jb, d, payload, ring, buf) in enumerate(items):
                try:
                    self._fold_shard_item(jb, d, payload)
                finally:
                    if ring is not None:
                        ring.release(buf)
                    released[i] = True
            with self._lock:
                self.agg.nb_models += job.k
                self._in_flight_models -= job.k
        except StreamingError:
            with self._lock:
                self._in_flight_models -= job.k
            BATCHES_TOTAL.labels(stage="failed").inc()
            raise
        except BaseException as e:
            self._poison(e, job.seq)
            with self._lock:
                self._in_flight_models -= job.k
            BATCHES_TOTAL.labels(stage="failed").inc()
            raise self._poison_error() from e
        finally:
            self._slot_release()
            for i, (_jb, _d, _p, ring, buf) in enumerate(items):
                if not released[i] and ring is not None:
                    ring.release(buf)
            self._leg(t0, "fold")
        BATCHES_TOTAL.labels(stage="folded").inc()

    def _dispatch_sharded_wire(
        self, ring: _StagingRing, buf, view, k: int, ticket: StreamTicket
    ) -> StreamTicket:
        """Wire batches keep ONE mesh unpack program (the psum-consistent
        per-update validity mask of the sequential path — an update invalid
        on ANY shard is excluded on EVERY shard) and fan only the fold out
        to the per-shard workers: each worker folds its addressable shard
        of the already-masked planar. Acceptance stays deferred: the ``ok``
        vector rides in flight until drain's single sync."""
        import jax

        agg = self.agg
        self._batch_seq += 1
        seq = self._batch_seq
        self._slot_acquire()  # covers the mesh unpack below; the last
        # shard's commit (or a failure here) releases it
        try:
            staged = jax.device_put(view, agg._batch_bytes_sharding)
            planar_mesh, ok = profiling.timed_kernel(
                "wire_unpack",
                staged.shape[0] * agg.padded_length,
                lambda: agg._make_unpack_fn()(staged),
            )
            plan = self._ensure_plan(k, lambda: planar_mesh)
        except BaseException as e:
            self._slot_release()
            ring.release(buf)
            self._poison(e, seq)
            BATCHES_TOTAL.labels(stage="failed").inc()
            raise self._poison_error() from e
        by_start = {
            s.index[-1].start or 0: s.data for s in planar_mesh.addressable_shards
        }
        job = _BatchJob("wire", k, ticket, seq, self._n_shards)
        job.staged = staged
        job.global_release = (ring, buf)
        with self._lock:
            self._in_flight_models += k
            degraded = self._degraded
        BATCHES_TOTAL.labels(stage="staged").inc()
        if degraded:
            released = False
            try:
                self._join_shard_queues()
                err = self._poisoned()
                if err is not None:
                    raise self._poison_error() from err
                ok_host = np.asarray(ok)  # acceptance sync (degraded path)  # lint: sync-ok
                ticket.accepted = ok_host
                for d, (lo, _hi) in enumerate(plan.slices):
                    self._fold_shard_item(job, d, by_start[lo])
                jax.block_until_ready(staged)  # lint: sync-ok
                ring.release(buf)
                released = True
                with self._lock:
                    self.agg.nb_models += int(ok_host.sum())
                    self._in_flight_models -= k
            except StreamingError:
                with self._lock:
                    self._in_flight_models -= k
                BATCHES_TOTAL.labels(stage="failed").inc()
                raise
            except BaseException as e:
                self._poison(e, seq)
                with self._lock:
                    self._in_flight_models -= k
                BATCHES_TOTAL.labels(stage="failed").inc()
                raise self._poison_error() from e
            finally:
                self._slot_release()
                if not released:
                    ring.release(buf)
            BATCHES_TOTAL.labels(stage="folded").inc()
            return ticket
        ticket._ok = ok
        with self._lock:
            self._pending.append(ticket)
        self._ensure_shard_workers()
        INFLIGHT_FOLDS.inc()
        for d, (lo, _hi) in enumerate(plan.slices):
            SHARD_INFLIGHT.labels(shard=str(d)).inc()
            self._shard_queues[d].put((job, d, by_start[lo], None, None))
        return ticket

    def _fold_shard_item(self, job: _BatchJob, d: int, payload) -> None:
        """Fold one shard's slice of one batch: the slice resident on the
        shard's device, then its fold. A batch staged at arrival went up row
        by row as it filled (``job.rows``) and the wait is for the copies
        still outstanding; otherwise, and on the retry after a failure, the
        slice is copied whole here, one shard's at a time
        (``shards.H2D_GATE``). The copy is complete before the fold is
        dispatched and the shard's accumulator is reassigned only after the
        fold returns, so an exception here leaves it consistent (the
        per-shard retry relies on that)."""
        with self._lock:
            plan = self._plan
            rows = job.rows
        if job.kind == "wire":
            plan.fold_shard(d, payload)
            return
        packed = job.kind == "packed"
        import jax

        staged = rows.take(d, job.k) if rows is not None else None
        note_h2d_route("batch" if staged is None else "row")
        if staged is None:
            with H2D_GATE, _h2d(job.kind, payload.nbytes, "batch", shard=d):
                with plan._device_dispatch_lock:
                    # host-side transfer enqueue only — the copy itself
                    # proceeds async and the barrier below stays outside the
                    # lock (packed staging: only bpn-byte planes cross here,
                    # the unpack runs in-graph on the shard's device)
                    staged = jax.device_put(payload, plan.devices[d])
                # the transfer out of the ring buffer completes before the
                # next shard's begins (the gate) and before this shard's
                # fold is dispatched, so a failure here leaves the
                # accumulator untouched
                jax.block_until_ready(staged)  # lint: sync-ok
        if packed:
            plan.fold_shard_packed(d, staged)
        else:
            plan.fold_shard(d, staged)

    def _retry_shard(self, job: _BatchJob, d: int, payload, first: BaseException) -> bool:
        """Per-shard leg of the degradation ladder: the failed shard's
        accumulator is provably untouched, so retry ITS slice once
        synchronously (the other shards' slices of this batch fold
        normally — the commit barrier keeps the accounting consistent) and
        flip the whole pipeline to the sync path. A second failure loses
        the batch and poisons permanently."""
        logger.warning(
            "streaming shard %d fold failed at batch %d (%s: %s); retrying on "
            "this shard and degrading the pipeline",
            d,
            job.seq,
            type(first).__name__,
            first,
        )
        with self._lock:
            fresh, self._degraded = not self._degraded, True
            job.retried = True
            job.rows = None  # what is left of the batch is copied whole
        DEGRADED.set(1)
        if fresh:  # a failed row copy is met by every shard: one degradation
            DEGRADATIONS.inc()
        try:
            self._fold_shard_item(job, d, payload)
            return True
        except BaseException as second:
            second.__context__ = first
            self._poison(second, job.seq)
            logger.exception(
                "streaming shard %d lost batch %d; pipeline poisoned", d, job.seq
            )
            return False

    def _process_shard(self, item: tuple) -> None:
        """One shard worker's fold of its slice of one batch, with the
        per-shard degradation ladder and the cross-shard commit handoff."""
        job, d, payload, ring, buf = item
        t0 = time.monotonic()
        failed = False
        try:
            with trace.get_tracer().span(
                SPAN_FOLD, batch=job.seq, shard=d, kind=job.kind, k=job.k
            ) as fold_span:
                try:
                    with self._lock:
                        poisoned = self._error is not None
                    if poisoned:
                        # the pipeline is already lost: drop the fold (the
                        # shards are inconsistent either way), release
                        # resources fast
                        failed = True
                        return
                    try:
                        maybe_fail("streaming.fold")
                        maybe_fail(f"streaming.shard{d}.fold")
                        self._fold_shard_item(job, d, payload)
                    except BaseException as first:
                        failed = not self._retry_shard(job, d, payload, first)
                finally:
                    if ring is not None:
                        ring.release(buf)
                    # D workers run concurrently: the global fold leg is
                    # the union of their intervals
                    self._leg(t0, "fold", ("fold", d))
                    SHARD_INFLIGHT.labels(shard=str(d)).dec()
                    fold_span.set(outcome="failed" if failed else "folded")
        finally:
            # the commit barrier runs AFTER this shard's fold span closed:
            # when the LAST shard settles a failed batch, every shard span
            # of the batch is already in the ring the flight dump snapshots
            self._shard_job_done(job, failed)

    def _shard_job_done(self, job: _BatchJob, failed: bool) -> None:
        """Per-batch commit barrier: the LAST shard to finish settles the
        accounting — planar batches credit ``nb_models`` and leave flight
        atomically (or just leave flight when the batch failed); wire
        batches release the shared byte buffer once the mesh transfer
        completed (their credit waits for drain's acceptance sync)."""
        now = time.monotonic()
        with self._lock:
            if failed:
                job.failed = True
            if job.first_done is None:
                job.first_done = now
            job.remaining -= 1
            last = job.remaining == 0
            if last and job.kind != "wire":  # planar AND packed batches
                self._in_flight_models -= job.k
                if not job.failed:
                    self.agg.nb_models += job.k
        if not last:
            return
        if job.global_release is not None:
            ring, buf = job.global_release
            job.global_release = None
            try:
                # commit-tail accesses: only the LAST shard (remaining hit
                # zero under the lock above) executes this branch, so the
                # job is single-owner here — ownership handoff through the
                # counter, not mutual exclusion
                if job.staged is not None and not job.failed:  # lint: guarded-ok: last-shard tail, single owner
                    import jax

                    # the wire bytes must be fully consumed by the mesh
                    # before the host buffer recycles
                    jax.block_until_ready(job.staged)  # lint: sync-ok
            except BaseException as e:
                self._poison(e, job.seq)
                job.failed = True  # lint: guarded-ok: last-shard tail, single owner
            finally:
                job.staged = None
                ring.release(buf)
        self._slot_release()
        INFLIGHT_FOLDS.dec()
        failed = job.failed  # lint: guarded-ok: last-shard tail, single owner
        retried = job.retried  # lint: guarded-ok: last-shard tail, single owner
        outcome = "failed" if failed else ("folded-degraded" if retried else "folded")
        # the commit barrier as the round feels it: the first shard's fold
        # item done -> the batch counts (the last shard records it)
        self._record_commit(job.first_done, job.seq, outcome)  # lint: guarded-ok: last-shard tail, single owner
        BATCHES_TOTAL.labels(stage=outcome).inc()
        if failed:
            with self._lock:
                cause, pseq = self._error, self._poison_seq
            if cause is not None:
                self._flight_poison(cause, pseq)

    def _fold_pinned_stack(self, plan, stacked, k: int) -> None:
        """Fold ONE batch-sharding-pinned device batch through the shard
        plan on the caller's thread and credit ``nb_models`` under the
        lock — the per-shard fan-out idiom shared by the stacked and
        row-chunked caller-thread paths (one copy, not three: the
        ``by_start`` shard addressing and the credit ordering are exactly
        the PR-7-hardened sequence a missed divergent copy would break)."""
        self._slot_acquire()
        try:
            by_start = {
                s.index[-1].start or 0: s.data for s in stacked.addressable_shards
            }
            for d, (lo, _hi) in enumerate(plan.slices):
                plan.fold_shard(d, by_start[lo])
        finally:
            self._slot_release()
        with self._lock:
            self.agg.nb_models += k

    def _drain_sharded(self) -> int:
        """The cross-shard barrier: every shard queue drains, the one
        deferred acceptance sync resolves the pending wire tickets, every
        shard's in-flight device folds complete, and the per-shard
        accumulators reassemble into the aggregator's global ``acc``."""
        self._join_shard_queues()
        # every worker is quiesced behind the queue join: the locked reads
        # below are for the discipline (and for late poisons from close())
        with self._lock:
            err = self._error
            plan = self._plan
        if err is not None:
            with self._lock:
                stale, self._pending = self._pending, []
                self._in_flight_models -= sum(t.k for t in stale)
            for ticket in stale:
                ticket._ok = None
            raise self._poison_error() from err
        with self._lock:
            pending, self._pending = self._pending, []
        accepted = 0
        try:
            for ticket in pending:
                ok_host = np.asarray(ticket._ok)
                ticket._ok = None
                ticket.accepted = ok_host
                accepted += int(ok_host.sum())
            if plan is not None:
                # per-shard completion barrier (device folds dispatch
                # asynchronously; their errors surface here, not in the
                # workers)
                plan.block_until_ready()
        except Exception as e:
            with self._lock:
                fresh = self._error is None
                self._error = e
                self._in_flight_models -= sum(t.k for t in pending)
            for ticket in pending:
                ticket._ok = None
            if fresh:
                self._flight_poison(e, None)
            raise self._poison_error() from e
        if pending:
            with self._lock:
                self.agg.nb_models += accepted
                self._in_flight_models -= sum(t.k for t in pending)
        # reduce-scatter: the plan PERSISTS across drain windows — the
        # per-shard accumulators stay authoritative (agg.acc reads
        # reassemble on demand; unmask subtracts per shard)
        self._publish_overlap()
        return accepted

    # -- eager per-shard unmask (docs/DESIGN.md §22) ------------------------

    def can_stage_unmask(self) -> bool:
        """Whether :meth:`stage_unmask` would take a mask now: a sharded
        pipeline with a live plan, neither degraded, poisoned nor closed.
        The caller asks before it relays the mask out, so a pipeline that
        cannot stage (one device, above all) costs it no planar."""
        with self._lock:
            return (
                self._sharded
                and self._plan is not None
                and not self._degraded
                and self._error is None
                and not self._closed
            )

    def stage_unmask(self, mask_planar: np.ndarray) -> "_UnmaskJob | None":
        """Enqueue the round's unmask as per-shard tail jobs: each shard
        subtracts its mask slice as soon as ITS last queued fold commits,
        instead of after the global drain barrier plus a separate serial
        unmask pass. Returns ``None`` when the pipeline cannot run the
        eager path (not sharded, no live plan, degraded, or poisoned) —
        the caller falls back to the drain-time unmask. The returned job
        settles in :meth:`finish_unmask`."""
        if not self.can_stage_unmask():
            return None
        job = _UnmaskJob(mask_planar, self.agg.unmask_out(), self._n_shards)
        self._ensure_shard_workers()
        for d, q in enumerate(self._shard_queues):
            q.put((job, d))
        return job

    def _process_unmask(self, item: tuple) -> None:
        """One shard worker's eager unmask leg: runs after the shard's
        last fold (queue FIFO), subtracts that shard's mask slice, and
        records the hidden seconds as a ``SPAN_EAGER_UNMASK`` span
        (home phase ``unmask``) so the timeline fold measures them as
        negative slack."""
        job, d = item
        t0 = time.monotonic()
        try:
            with self._lock:
                plan = self._plan
                poisoned = self._error is not None
            if not poisoned and plan is not None:
                self.agg.unmask_shard(plan, d, job.mask_planar, job.out)
                trace.get_tracer().record_span(
                    SPAN_EAGER_UNMASK,
                    start=t0,
                    duration=time.monotonic() - t0,
                    phase="unmask",
                    shard=d,
                    tenant=self.tenant,
                )
            elif job.error is None:
                with self._lock:
                    if job.error is None:
                        job.error = self._error or StreamingError(
                            "eager unmask skipped: plan gone"
                        )
        except BaseException as e:
            # the subtract is functional — the shard accumulator is
            # untouched on failure, so the caller's fallback to the
            # drain-time unmask pass stays byte-correct
            with self._lock:
                if job.error is None:
                    job.error = e
        finally:
            with self._lock:
                job.remaining -= 1
                last = job.remaining == 0
            if last:
                job.done.set()

    def finish_unmask(self, job: "_UnmaskJob") -> PlanarLimbs | None:
        """Settle an eager unmask: wait for every shard's tail job (most
        of the work has already run, hidden behind the fold/drain wall),
        then hand back the assembled host planes — or ``None`` if any
        shard failed (caller falls back to the drain-time pass). Records
        the same ``unmask`` kernel op and gather accounting as the
        drain-time pass — what shrinks is the measured wall, which is
        exactly the point."""

        def settle():
            job.done.wait()
            with self._lock:
                err = job.error
            if err is not None:
                logger.warning(
                    "eager unmask fell back to the drain-time pass: %s: %s",
                    type(err).__name__,
                    err,
                )
                return None
            BYTES_REDUCED.labels(path="gather").inc(job.out.nbytes)
            return job.out

        return profiling.timed_kernel("unmask", self.agg.padded_length, settle)
