"""Per-shard decomposition of the aggregation accumulator.

The mesh-sharded fold (``ShardedAggregator``) runs ONE program over the
whole mesh per batch: a single dispatch, a single accumulator, a single
host sync at drain. That shape cannot overlap per-device work — every
device waits for the slowest transfer.

A :class:`ShardPlan` decomposes the aggregator's planar accumulator into
per-shard owned buffers — one per mesh device, each covering that device's
contiguous model-axis column slice (``mesh.shard_slices``) — so the
streaming pipeline can run ONE FOLD WORKER PER SHARD with independent
queues, donated per-shard accumulators, and per-shard host→device
transfers that overlap other shards' in-flight folds (the DrJAX-style
MapReduce pipelining of arxiv 2403.07128, applied across the mesh instead
of across batches).

One shard-fold backend: per-device single-device arrays folded by the
aggregator's resolved kernel (xla/pallas) — the already-jitted
``fold_planar_batch`` (its ``donate_argnums=(0,)`` is the per-shard
accumulator donation); the executable is shared across shards (same
shapes, same program).

Exactness: the fold is an exact modular sum and the model axis is
embarrassingly parallel, so any decomposition of the column axis folds to
the byte-identical aggregate — per-shard progress skew (shard A two
batches ahead of shard B) changes nothing once every shard has folded
every batch, which is what the streaming pipeline's per-batch commit
barrier guarantees.

Ownership contract: while a plan is ACTIVE (built and not yet
reassembled), the per-shard buffers are the authoritative accumulator and
the aggregator's global ``acc`` is stale — the first donated fold
actually invalidates it (the zero-copy decomposition aliases its
buffers). ``reassemble()`` publishes the per-shard state back as the
global accumulator; the streaming pipeline calls it from ``drain()``, its
cross-shard barrier.
"""

from __future__ import annotations

import threading
from functools import partial

import jax

from .mesh import shard_slices


# one host->device copy of staged rows at a time, whatever the shard and
# whichever pipeline: the copies share the host's path to the chips. Four
# 2.15 GB copies started together on a four-chip v5e host ran two at 5.3 GB/s
# and two at 0.3 GB/s, 5.9-10.2 s for the batch where four in a row need
# under 2 (PERF.md section 6, PR 40). Held by a pipeline's row copier for one
# row's slice (``streaming._copy_row``: a batch staged at arrival goes up row
# by row while it fills) and by a shard's fold worker for its slice of a
# whole batch (the batch route); a shard's fold still runs beside the next
# copy.
H2D_GATE = threading.Lock()


def settle(out):
    """What a donating device dispatch returned, as its caller may publish
    it while it still holds the dispatch lock. jax's dispatch/execution path
    is not reliably thread-safe for concurrent donating jit calls on the
    virtual-device CPU backend (~1 in 40k folds lands a torn shard slice
    under scheduler contention — reproduced with no fault injection), so
    there the lock is held through COMPLETION: the virtual devices share the
    physical cores, and serialized executions lose no real parallelism. On
    real accelerators only the host-side dispatch serializes; per-device
    execution stays concurrent."""
    if jax.default_backend() == "cpu":
        out = jax.block_until_ready(out)  # lint: sync-ok
    return out


@partial(jax.jit, donate_argnums=(0,))
def place_row(batch, row, i):
    """One staged row into slot ``i`` of a device-resident batch of rows, in
    place (the batch is donated): how a batch staged at arrival is assembled
    on its device while it fills, so that the fold finds its ``[K, ...]``
    operand resident (``streaming._put_row``). ``row`` has a slot's shape or
    is the slot flat, as it crossed the link. One executable a batch shape,
    whatever the slot; no fold, and not named like one."""
    return jax.lax.dynamic_update_slice_in_dim(
        batch, row.reshape((1,) + batch.shape[1:]), i, axis=0
    )


class ShardPlan:
    """Per-shard accumulator state + fold entry points for one aggregator.

    Built against a resolved kernel (``agg.kernel_used``).
    """

    def __init__(self, agg, dispatch_lock: threading.Lock | None = None):
        if agg.kernel_used is None:
            raise ValueError("kernel must be resolved before building a shard plan")
        self.agg = agg
        self.slices = shard_slices(agg.padded_length, agg.mesh.devices.size)
        self.devices = list(agg.mesh.devices.flat)
        # serializes the donating device dispatches issued from the D worker
        # threads (see ``settle``). A streaming pipeline hands in
        # its own lock, which its row copier takes too: a row's placement
        # into the device batch donates as a fold does
        self._device_dispatch_lock = dispatch_lock or threading.Lock()
        # zero-copy decomposition: the addressable shards of the
        # mesh-sharded accumulator ARE the per-device slices; the first
        # donated fold invalidates the global array, which is exactly the
        # ownership handoff documented above. accs carries a guarded-by
        # annotation (the PR-7 torn-slice class: concurrent donating jit
        # calls)
        by_start = {
            s.index[-1].start or 0: s.data for s in agg.acc.addressable_shards
        }
        self.accs = [  # guarded-by: _device_dispatch_lock
            by_start[lo] for lo, _ in self.slices
        ]

    # -- folds ------------------------------------------------------------

    def fold_shard(self, d: int, batch) -> None:
        """Fold a per-shard batch ``[K, L, width]``, a ``device[d]``-resident
        array, into shard ``d``'s accumulator with the jitted kernel
        (accumulator donated).

        The accumulator is reassigned only after the fold call returns, so
        an exception leaves the shard consistent — the streaming pipeline's
        per-shard sync-retry relies on this."""
        if self.agg.kernel_used in ("pallas", "pallas-interpret"):
            from ..ops import fold_pallas

            # late module-attribute lookup so test spies see the call, same
            # as the aggregator's fold builder; the kernel is elementwise
            # along the model axis, so each shard runs it on its own slice
            def call(acc):
                return fold_pallas.fold_planar_batch_pallas(
                    acc,
                    batch,
                    self.agg.order,
                    interpret=self.agg.kernel_used == "pallas-interpret",
                )

            self._locked_device_fold(d, call)
        else:
            from ..ops.fold_jax import fold_planar_batch

            self._locked_device_fold(
                d, lambda acc: fold_planar_batch(acc, batch, self.agg.order)
            )

    def fold_shard_packed(self, d: int, packed) -> None:
        """Fold a per-shard PACKED byte-planar batch ``uint8[K, bpn, width]``
        into shard ``d``'s accumulator (the packed-staging streaming path):
        the fused unpack+fold jit (``ops.fold_jax.fold_packed_batch``) on
        the shard's device — only packed bytes ever cross host->device.
        Consistency contract matches :meth:`fold_shard` exactly (the
        accumulator is reassigned only after the fold returns)."""
        from ..ops.fold_jax import fold_packed_batch

        n_limbs, order = self.agg.n_limbs, self.agg.order
        if self.agg.kernel_used in ("pallas", "pallas-interpret"):
            from ..ops import fold_pallas, limbs_jax

            interpret = self.agg.kernel_used == "pallas-interpret"

            def call(acc):
                # the module-level jitted unpack: one shared trace cache
                # across calls/shards instead of a fresh retrace per batch
                planar = limbs_jax.packed_planar_to_limbs_jit(packed, n_limbs)
                return fold_pallas.fold_planar_batch_pallas(
                    acc, planar, order, interpret=interpret
                )

            self._locked_device_fold(d, call)
            return
        self._locked_device_fold(
            d, lambda acc: fold_packed_batch(acc, packed, n_limbs, order)
        )

    def _locked_device_fold(self, d: int, call) -> None:
        """Run one shard's device fold under the dispatch lock; on the CPU
        backend hold it through completion (see the lock's construction
        note). The shard accumulator is reassigned only after ``call``
        returns — an exception leaves the shard consistent."""
        with self._device_dispatch_lock:
            # reassign INSIDE the lock: the slot write itself must not
            # interleave with another shard's donating dispatch (the PR-7
            # torn-slice hazard this lock exists for)
            self.accs[d] = settle(call(self.accs[d]))

    # -- barrier / reassembly ---------------------------------------------

    def block_until_ready(self) -> None:
        """Wait for every shard's in-flight device fold."""
        # lint: guarded-ok: drain barrier — workers quiesced behind the queue join
        jax.block_until_ready(self.accs)  # lint: sync-ok  # lint: guarded-ok: drain barrier read

    def reassemble(self):
        """The global planar accumulator assembled from the per-shard
        state, zero-copy (``make_array_from_single_device_arrays`` over the
        per-device buffers, which ARE the mesh sharding's shards).
        Reduce-scatter contract (DESIGN §17): this is a READ — an
        adopted plan stays authoritative afterwards and keeps folding into
        the same per-shard buffers (``ShardedAggregator.acc`` calls this
        on demand for snapshot/checkpoint/final download). Only an
        explicit ``acc`` WRITE supersedes the plan."""
        return jax.make_array_from_single_device_arrays(
            (self.agg.n_limbs, self.agg.padded_length),
            self.agg._acc_sharding,
            list(self.accs),  # lint: guarded-ok: drain barrier read
        )
