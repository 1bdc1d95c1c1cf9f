"""Unmask phase: elect the winning mask and reveal the new global model.

Reference behavior
(rust/xaynet-server/src/state_machine/phases/unmask.rs:56-219): fetch the
two best-scored masks; the winner must be the *unique* maximum (equal top
scores are ambiguous -> round failure); validate and unmask the aggregate;
persist the global model under ``{round_id}_{hex(seed)}`` with the latest-id
pointer; publish proof to the trust anchor; broadcast the new model.

The unmask subtract runs on the vectorized limb kernels, and what the
phase is handed selects where (docs/DESIGN.md §22; no setting does). A
host round hands over the host ``Aggregation`` and its ``mod_sub``. A
device round hands over a ``DeviceAggregation`` view
(``aggregation.finalize_inplace``) over the still-sharded accumulator,
which is never gathered before the subtraction: where the view's pipeline
is open and folds on a mesh, each shard subtracts its slice of the mask
behind its own last fold; on one device, after a journal resume (no
pipeline) and where a shard's job failed, one drain-time subtract runs
over the whole accumulator. The fixed-point decode uses the double-double
fast path for f32 configs (core/mask/encode.py): it reads the planes a
device arm fetched where they lie, on the native library's threads, and
the float64 it returns is ``global_model``: serialised once, and those bytes handed to
the store and to the trust anchor (docs/DESIGN.md §16).
"""

from __future__ import annotations

import asyncio
import logging

import numpy as np

from ...core.mask.masking import Aggregation, UnmaskingError
from ...core.mask.object import MaskObject
from ...resilience.chaos import maybe_kill
from ...telemetry import profiling
from ...telemetry import unmask as unmask_stages
from ...telemetry.registry import get_registry
from ...utils import native
from ..events import ModelUpdate, PhaseName
from .base import PhaseError, PhaseState

logger = logging.getLogger("xaynet.coordinator")

POINTER_UPDATE_FAILURES = get_registry().counter(
    "xaynet_model_pointer_update_failures_total",
    "latest_global_model_id pointer updates abandoned after retries "
    "(the model blob IS stored; only the latest-pointer is stale).",
)


class Unmask(PhaseState):
    NAME = PhaseName.UNMASK

    def __init__(self, shared, model_agg: Aggregation):
        super().__init__(shared)
        self.model_agg = model_agg
        self.global_model: np.ndarray | None = None
        # the model's float64 bytes: made once, in `save`, and handed to the
        # store and to the trust anchor as the same object
        self._model_bytes: bytes | None = None

    async def process(self) -> None:
        # chaos hook (kill-matrix harness): the journal says `unmask`, and
        # nothing of the model has been computed or stored
        maybe_kill("unmask:start")
        # the phase, stage by stage (telemetry/unmask.py): the brackets here,
        # in the aggregation's ``unmask_array`` (mask_put, subtract, fetch,
        # decode) and nothing between them
        with unmask_stages.stage("elect"):
            if self.shared.metrics is not None:
                n_masks = await self.shared.store.coordinator.number_of_unique_masks()
                self.shared.metrics.masks_total(self.shared.round_id, n_masks)
            best = await self.shared.store.coordinator.best_masks()
            if best is None:
                raise PhaseError("NoMask", "no masks submitted")
            mask = self._freeze_mask_dict(best)
        with unmask_stages.stage("validate", bytes=mask.vect.data.nbytes):
            try:
                self.model_agg.validate_unmasking(mask)
            except UnmaskingError as err:
                raise PhaseError("Unmasking", err.kind) from err
        from ..aggregation import DeviceAggregation

        if isinstance(self.model_agg, DeviceAggregation):
            # the sharded in-place subtract records the `unmask` kernel op
            # itself (ShardedAggregator.unmask_limbs) — wrapping it again
            # here would double-count the op in /metrics
            self.global_model = self.model_agg.unmask_array(mask)
        else:
            self.global_model = profiling.timed_kernel(
                "unmask", len(self.model_agg), lambda: self.model_agg.unmask_array(mask)
            )
        with unmask_stages.stage("save", bytes=8 * len(self.global_model)):
            await self._save_global_model()
        # chaos hook (kill-matrix harness): the publish window — the model
        # is persisted but the journal not yet retired; a restart must
        # republish idempotently (ModelStorage contract), never corrupt
        maybe_kill("unmask:publish")
        if self.shared.store.trust_anchor is not None:
            with unmask_stages.stage("proof", bytes=8 * len(self.global_model)):
                await self._publish_proof()
        if self.shared.settings.resilience.checkpoint_enabled:
            # retire the round journal: the model is published — nothing
            # left for a resume to redo
            # (Idle's delete is the backstop for disabled-journal configs)
            with unmask_stages.stage("retire"):
                await self.shared.store.coordinator.delete_round_checkpoint()

    def broadcast(self) -> None:
        assert self.global_model is not None
        self.shared.events.broadcast_model(ModelUpdate.new(self.global_model))

    async def next(self):
        if self.shared.round_ctl is not None:
            # the round is complete: feed the controller's hysteresis (full
            # vs degraded is derived from the per-phase window outcomes)
            self.shared.round_ctl.round_completed()
        # tenant lifecycle (docs/DESIGN.md §23): a completed round is the
        # breaker's probe success (quarantine lift) and a drain boundary
        from ...tenancy import lifecycle as _lifecycle

        _lifecycle.note_round_completed(self.shared.tenant)
        from .idle import Idle

        return Idle(self.shared)

    # --- internals --------------------------------------------------------

    @staticmethod
    def _freeze_mask_dict(best: list[tuple[MaskObject, int]]) -> MaskObject:
        """Unique-maximum election (unmask.rs:96-115)."""
        winner, winner_count = None, 0
        for mask, count in best:
            if count > winner_count:
                winner, winner_count = mask, count
            elif count == winner_count:
                winner = None
        if winner is None:
            raise PhaseError("AmbiguousMasks", "top masks share the same score")
        return winner

    def _serialised_model(self) -> bytes:
        """The phase's one serialisation of the decoded model (a ``bytes``,
        which a store that keeps it does not copy again)."""
        if self._model_bytes is None:
            assert self.global_model is not None
            self._model_bytes = native.tobytes(np.asarray(self.global_model, dtype=np.float64))
            unmask_stages.count_pass("serialise", len(self._model_bytes))
        return self._model_bytes

    async def _save_global_model(self) -> None:
        model_id = await self.shared.store.models.set_global_model(
            self.shared.state.round_id,
            self.shared.state.round_params.seed.as_bytes(),
            self._serialised_model(),
        )
        # best-effort per the reference (unmask.rs:191-198) — the retry
        # itself lives in the ResilientStore layer every storage call flows
        # through (stacking a second schedule here would retry up to
        # attempts² times against a backend the breaker already declared
        # dead). What this phase adds is the COUNT: a permanently broken
        # pointer must be visible on /metrics, not buried in a warning log.
        # The phase still completes either way (clients fall back to
        # fetching the model by explicit id).
        try:
            await self.shared.store.coordinator.set_latest_global_model_id(model_id)
        except asyncio.CancelledError:
            raise
        except Exception as err:
            POINTER_UPDATE_FAILURES.inc()
            logger.warning("failed to update latest global model id: %s", err)

    async def _publish_proof(self) -> None:
        await self.shared.store.trust_anchor.publish_proof(self._serialised_model())
