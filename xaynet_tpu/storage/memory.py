"""In-process storage backend.

Implements the same atomic conditional-insert semantics the reference
enforces with Redis Lua scripts (reference:
rust/xaynet-server/src/storage/coordinator_storage/redis/mod.rs:208-343):
seed-dict inserts validate length against the sum dict, membership and
single submission before writing; mask scores require sum membership and a
single submission per participant. Atomicity here comes from the asyncio
single-thread execution model (no awaits inside the critical sections).

Mask votes are counted under the mask's canonical content (both
configurations, every element of the limb array, the unit): two votes share
a count exactly when their ``MaskObject``s are equal, which is when their
canonical serialisations (the Redis sorted set's members) are byte-equal.
The first parsed object seen for a mask is the one kept and the one
``best_masks`` hands to the election: nothing is serialised to score and
nothing parsed to elect (docs/DESIGN.md §16 "The vote key").
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..core.common import LocalSeedDict, SeedDict, SumDict
from ..core.mask.object import MaskObject
from .traits import (
    MASK_VOTES,
    CoordinatorStorage,
    LocalSeedDictAddError,
    MaskScoreIncrError,
    ModelStorage,
    StorageError,
    SumPartAddError,
    TrustAnchor,
)


# what a journal head file starts with when its sections lie in files of
# their own (FileCoordinatorStorage): this, a u32-le length, a JSON listing
# [[sha256 hex, bytes], ...] in wire order, then the head itself
_SECTIONED = b"XNCKSEC1"

# elements of the vector that go into a vote's prefilter: what an honest
# mask of other seeds differs in with all but one chance in the group's order
_PREFILTER_ELEMENTS = 16


def _vote_prefilter(mask: MaskObject) -> bytes:
    """A few dozen bytes two equal masks must share: the configurations,
    the length, the unit and the vector's first elements. Never the test
    itself: a vote is counted with a kept mask only after the full
    comparison (``MaskObject.__eq__``), which the prefilter spares for a
    round's first vote and for masks of other seeds."""
    return b"".join(
        (
            mask.vect.config.to_bytes(),
            mask.unit.config.to_bytes(),
            len(mask.vect).to_bytes(8, "big"),
            # one dtype: equal masks must never differ in their prefilter
            np.asarray(mask.unit.data, dtype=np.uint32).tobytes(),
            np.asarray(mask.vect.data[:_PREFILTER_ELEMENTS], dtype=np.uint32).tobytes(),
        )
    )


@dataclass
class _ScoredMask:
    """One distinct mask of the round: the first parsed object voted for it
    (owned by the store until ``delete_dicts``) and its count of votes."""

    prefilter: bytes
    mask: MaskObject
    score: int = 1


class InMemoryCoordinatorStorage(CoordinatorStorage):
    def __init__(self):
        self._state: Optional[bytes] = None
        self._sum_dict: dict[bytes, bytes] = {}
        self._seed_dict: dict[bytes, dict[bytes, object]] = {}
        self._update_submitted: set[bytes] = set()
        self._mask_scores: list[_ScoredMask] = []  # in order of first vote
        self._mask_submitted: set[bytes] = set()
        self._latest_global_model_id: Optional[str] = None

    async def set_coordinator_state(self, state: bytes) -> None:
        self._state = bytes(state)

    async def coordinator_state(self) -> Optional[bytes]:
        return self._state

    async def add_sum_participant(self, pk: bytes, ephm_pk: bytes) -> Optional[SumPartAddError]:
        if pk in self._sum_dict:
            return SumPartAddError.ALREADY_EXISTS
        self._sum_dict[pk] = ephm_pk
        return None

    async def sum_dict(self) -> Optional[SumDict]:
        return dict(self._sum_dict) if self._sum_dict else None

    async def add_local_seed_dict(
        self, update_pk: bytes, local_seed_dict: LocalSeedDict
    ) -> Optional[LocalSeedDictAddError]:
        # same validations as the reference's Lua script (redis/mod.rs:208-267)
        if len(local_seed_dict) != len(self._sum_dict):
            return LocalSeedDictAddError.LENGTH_MISMATCH
        if any(pk not in self._sum_dict for pk in local_seed_dict):
            return LocalSeedDictAddError.UNKNOWN_SUM_PARTICIPANT
        if update_pk in self._update_submitted:
            return LocalSeedDictAddError.UPDATE_PK_ALREADY_SUBMITTED
        for sum_pk in local_seed_dict:
            if update_pk in self._seed_dict.get(sum_pk, {}):
                return LocalSeedDictAddError.UPDATE_PK_ALREADY_EXISTS_IN_UPDATE_SEED_DICT
        for sum_pk, seed in local_seed_dict.items():
            self._seed_dict.setdefault(sum_pk, {})[update_pk] = seed
        self._update_submitted.add(update_pk)
        return None

    async def seed_dict(self) -> Optional[SeedDict]:
        if not self._seed_dict:
            return None
        return {sum_pk: dict(inner) for sum_pk, inner in self._seed_dict.items()}

    async def incr_mask_score(self, pk: bytes, mask: MaskObject) -> Optional[MaskScoreIncrError]:
        # same validations as the reference's Lua script (redis/mod.rs:303-343)
        if pk not in self._sum_dict:
            return MaskScoreIncrError.UNKNOWN_SUM_PK
        if pk in self._mask_submitted:
            return MaskScoreIncrError.MASK_ALREADY_SUBMITTED
        prefilter = _vote_prefilter(mask)
        for kept in self._mask_scores:
            if kept.prefilter == prefilter and kept.mask == mask:
                kept.score += 1
                break
        else:
            self._mask_scores.append(_ScoredMask(prefilter, mask))
        self._mask_submitted.add(pk)
        MASK_VOTES.labels(route="kept").inc()
        return None

    async def best_masks(self) -> Optional[list[tuple[MaskObject, int]]]:
        if not self._mask_scores:
            return None
        top = sorted(self._mask_scores, key=lambda kept: kept.score, reverse=True)[:2]
        return [(kept.mask, kept.score) for kept in top]

    async def number_of_unique_masks(self) -> int:
        return len(self._mask_scores)

    async def delete_coordinator_data(self) -> None:
        self._state = None
        self._latest_global_model_id = None
        await self.delete_round_checkpoint()
        await self.delete_dicts()

    async def delete_dicts(self) -> None:
        self._sum_dict.clear()
        self._seed_dict.clear()
        self._update_submitted.clear()
        self._mask_scores.clear()
        self._mask_submitted.clear()

    async def set_latest_global_model_id(self, model_id: str) -> None:
        self._latest_global_model_id = model_id

    async def latest_global_model_id(self) -> Optional[str]:
        return self._latest_global_model_id

    async def prune_update_participants(self, keep_pks) -> bool:
        keep = set(keep_pks)
        for inner in self._seed_dict.values():
            for pk in [p for p in inner if p not in keep]:
                del inner[pk]
        self._update_submitted = {pk for pk in self._update_submitted if pk in keep}
        return True

    async def is_ready(self) -> None:
        return None


class InMemoryModelStorage(ModelStorage):
    def __init__(self):
        self._models: dict[str, bytes] = {}

    async def set_global_model(self, round_id: int, round_seed: bytes, model_data: bytes) -> str:
        model_id = self.create_global_model_id(round_id, round_seed)
        existing = self._models.get(model_id)
        if existing is not None:
            if existing == bytes(model_data):
                return model_id  # publish-window resume: idempotent republish
            raise StorageError(f"global model {model_id} already exists")
        self._models[model_id] = bytes(model_data)
        return model_id

    async def global_model(self, model_id: str) -> Optional[bytes]:
        return self._models.get(model_id)

    async def is_ready(self) -> None:
        return None


class FilesystemModelStorage(ModelStorage):
    """Model blobs on a local/NFS/FUSE path (the S3/Minio analogue)."""

    def __init__(self, root: str):
        import os

        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, model_id: str) -> str:
        import os

        safe = model_id.replace("/", "_")
        return os.path.join(self.root, safe + ".bin")

    async def set_global_model(self, round_id: int, round_seed: bytes, model_data: bytes) -> str:
        import os

        model_id = self.create_global_model_id(round_id, round_seed)
        path = self._path(model_id)
        if os.path.exists(path):
            with open(path, "rb") as f:
                if f.read() == bytes(model_data):
                    return model_id  # publish-window resume: idempotent republish
            raise StorageError(f"global model {model_id} already exists")
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(model_data)
        os.replace(tmp, path)
        return model_id

    async def global_model(self, model_id: str) -> Optional[bytes]:
        import os

        path = self._path(model_id)
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            return f.read()

    async def is_ready(self) -> None:
        import os

        if not os.path.isdir(self.root):
            raise StorageError(f"model store root {self.root} missing")


class NoOpModelStorage(ModelStorage):
    """Persistence disabled (reference: model_storage/noop.rs)."""

    async def set_global_model(self, round_id: int, round_seed: bytes, model_data: bytes) -> str:
        return self.create_global_model_id(round_id, round_seed)

    async def global_model(self, model_id: str) -> Optional[bytes]:
        return None

    async def is_ready(self) -> None:
        return None


class NoOpTrustAnchor(TrustAnchor):
    async def publish_proof(self, model_data: bytes) -> None:
        return None

    async def is_ready(self) -> None:
        return None


class FileCoordinatorStorage(InMemoryCoordinatorStorage):
    """In-memory round dictionaries + file-persisted durable state.

    The reference keeps everything in Redis; for single-node deployments
    without an external store, the *durable* subset (coordinator state and
    the latest-global-model pointer — exactly what restore reads,
    reference: initializer.rs:162-271) persists to a JSON file. Round
    dictionaries live in memory only — but the round JOURNAL (the binary
    ``.ckpt`` sibling and the section files beside it) carries its own copy
    of them, and a boot restore
    replays them back through ``restore_round_dicts``, so a crash
    anywhere in the round resumes instead of restarting it.
    """

    def __init__(self, path: str):
        super().__init__()
        import json
        import os

        self.path = path
        if os.path.exists(path):
            with open(path) as f:
                saved = json.load(f)
            if saved.get("state") is not None:
                self._state = bytes.fromhex(saved["state"])
            self._latest_global_model_id = saved.get("latest_global_model_id")

    def _persist(self) -> None:
        import json
        import os

        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "state": self._state.hex() if self._state else None,
                    "latest_global_model_id": self._latest_global_model_id,
                },
                f,
            )
        os.replace(tmp, self.path)

    async def set_coordinator_state(self, state: bytes) -> None:
        await super().set_coordinator_state(state)
        self._persist()

    async def set_latest_global_model_id(self, model_id: str) -> None:
        await super().set_latest_global_model_id(model_id)
        self._persist()

    async def delete_coordinator_data(self) -> None:
        await super().delete_coordinator_data()
        self._persist()

    # --- round journal: a head file and one file a section -----------------
    # An entry's sections can be of the model's size, and most of what the
    # round's tail journals (the finished aggregate, a vote) does not change
    # from one entry to the next. So `<path>.ckpt` holds the head alone,
    # behind a listing of the sections it goes with, and each non-empty
    # section lies in `<path>.ckpt.<sha256>`, written once: an entry that
    # carries a section whose file is there writes the head and nothing else.

    def _ckpt_path(self) -> str:
        return self.path + ".ckpt"

    async def set_round_checkpoint(self, head: bytes, sections=()) -> None:
        import asyncio

        # model-sized sections: the file writes go through the executor so
        # the event loop keeps serving the API during a checkpoint
        await asyncio.get_running_loop().run_in_executor(
            None, self._write_ckpt_counted, head, sections
        )

    def _write_ckpt_counted(self, head: bytes, sections=()) -> None:
        """``_write_ckpt`` on its executor thread, which says what it spent
        under the ``store`` stage that the loop opened around the wait."""
        from ..telemetry import journal

        with journal.writing():
            self._write_ckpt(head, sections)

    def _write_ckpt(self, head: bytes, sections=()) -> None:
        """Sections first, each under a temporary name until it is whole;
        then the head, whose rename is the commit: until it, the previous
        entry and its sections are what ``round_checkpoint()`` returns.
        Sections the new head does not name go only after it."""
        import json
        import os
        import struct

        from ..resilience.chaos import maybe_kill

        base = self._ckpt_path()
        listing = []
        for section in sections:
            if not section.nbytes:
                continue
            listing.append([section.digest, section.nbytes])
            path = f"{base}.{section.digest}"
            try:
                if os.path.getsize(path) == section.nbytes:
                    continue  # an earlier entry of the round wrote it
            except OSError:
                pass
            with open(path + ".tmp", "wb") as f:
                for view in section.views():
                    f.write(view)
            os.replace(path + ".tmp", path)
        if listing:
            # chaos hook (kill-matrix harness): the sections are on disk
            # and the head still says the previous entry
            maybe_kill("journal:sections")
            index = json.dumps(listing).encode()
            head = _SECTIONED + struct.pack("<I", len(index)) + index + head
        with open(base + ".tmp", "wb") as f:
            f.write(head)
        os.replace(base + ".tmp", base)
        self._sweep_ckpt(keep={digest for digest, _ in listing})

    def _sweep_ckpt(self, keep=()) -> None:
        """Remove what lies beside the head and no head names: the sections
        of entries gone by, and what a process that died left half written."""
        import os

        folder, name = os.path.split(self._ckpt_path())
        for found in os.listdir(folder or "."):
            if found.startswith(name + ".") and found[len(name) + 1 :] not in keep:
                try:
                    os.remove(os.path.join(folder, found))
                except FileNotFoundError:
                    pass

    async def round_checkpoint(self) -> Optional[bytes]:
        import asyncio

        return await asyncio.get_running_loop().run_in_executor(None, self._read_ckpt)

    def _read_head(self) -> Optional[tuple[bytes, list]]:
        """The head file alone: the head, and the listing of the sections
        that follow it in files of their own. A file without a listing is
        whole as it lies (an entry with no payload, a journal an older
        program left, or a torn head, which no magic the journal knows
        starts): it comes back as it is, with nothing to follow."""
        import json
        import struct

        try:
            with open(self._ckpt_path(), "rb") as f:
                data = f.read()
        except FileNotFoundError:
            return None
        if not data.startswith(_SECTIONED):
            return data, []
        try:
            (n,) = struct.unpack_from("<I", data, len(_SECTIONED))
            at = len(_SECTIONED) + 4
            listing = [(str(digest), int(nbytes)) for digest, nbytes in json.loads(data[at : at + n])]
        except (struct.error, ValueError, TypeError):
            return data, []
        return data[at + n :], listing

    def _read_ckpt(self) -> Optional[bytes]:
        """The entry as one blob: the head and the sections it lists, in
        order. A section file that is missing adds nothing, and one that is
        short or altered adds what it holds: the blob then fails its length
        or digest check."""
        found = self._read_head()
        if found is None:
            return None
        head, listing = found
        parts = [head]
        for digest, _nbytes in listing:
            try:
                with open(f"{self._ckpt_path()}.{digest}", "rb") as f:
                    parts.append(f.read())
            except FileNotFoundError:
                pass
        return b"".join(parts)

    async def delete_round_checkpoint(self) -> None:
        import os

        # the head first: from here on there is no entry, whatever is left
        try:
            os.remove(self._ckpt_path())
        except FileNotFoundError:
            pass
        self._sweep_ckpt()
