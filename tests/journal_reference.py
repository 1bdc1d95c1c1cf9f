"""The plain reference serialiser of a round-journal entry: the ``to_bytes``
of the program before a journal entry became a head and its sections
(``xaynet_tpu/resilience/checkpoint.py`` at PR 46), copied here. It builds the
``XNCKPT2`` blob the straightforward way, a ``tobytes()`` of every array, a
join, a hash of the copy, a concatenation with the header, and knows nothing
of sections, kept digests or files. The tests hold what a store returns for an
entry to this, byte for byte, and hand its blobs to the program as the journal
an older process left. Beside it, a ``hashlib`` that counts, for the tests of
"each section is hashed once"."""

import hashlib
import json
import struct

import numpy as np

MAGIC2 = b"XNCKPT2"


def reference_to_bytes(ck) -> bytes:
    vect = np.ascontiguousarray(ck.vect, dtype=np.uint32)
    unit = np.ascontiguousarray(ck.unit, dtype=np.uint32)
    vect_raw = vect.tobytes()
    unit_raw = unit.tobytes()
    votes_raw = b"".join(bytes(mask) for _, mask in ck.mask_votes)
    planes_meta = None
    planes_raw = b""
    if ck.planes is not None:
        planes_meta = []
        chunks = []
        for lo, hi, plane in ck.planes:
            plane = np.ascontiguousarray(plane, dtype=np.uint32)
            planes_meta.append([int(lo), int(hi), *map(int, plane.shape)])
            chunks.append(plane.tobytes())
        planes_raw = b"".join(chunks)
    header = json.dumps(
        {
            "version": 2,
            "round_id": ck.round_id,
            "phase": ck.phase,
            "round_seed": ck.round_seed.hex(),
            "mask_config": ck.mask_config,
            "model_length": ck.model_length,
            "nb_models": ck.nb_models,
            "seed_watermark": ck.seed_watermark,
            "vect_shape": list(vect.shape),
            "unit_shape": list(unit.shape),
            "vect_sha256": hashlib.sha256(vect_raw).hexdigest(),
            "unit_sha256": hashlib.sha256(unit_raw).hexdigest(),
            "sum_dict": {pk.hex(): ephm.hex() for pk, ephm in ck.sum_dict.items()},
            "seed_dicts": {
                pk.hex(): {spk.hex(): bytes(seed).hex() for spk, seed in local.items()}
                for pk, local in ck.seed_dicts.items()
            },
            "votes": [[pk.hex(), len(bytes(mask))] for pk, mask in ck.mask_votes],
            "votes_sha256": hashlib.sha256(votes_raw).hexdigest(),
            "planes": planes_meta,
            "planes_sha256": hashlib.sha256(planes_raw).hexdigest(),
        }
    ).encode()
    return (
        MAGIC2
        + struct.pack("<I", len(header))
        + header
        + vect_raw
        + unit_raw
        + votes_raw
        + planes_raw
    )


class CountingHashlib:
    """``hashlib`` with its SHA-256 counted: one record a hash object, the
    bytes it was fed."""

    def __init__(self):
        self.fed: list[list[int]] = []

    def sha256(self, data=b""):
        record = [len(data)]
        self.fed.append(record)
        real = hashlib.sha256(data)

        class Counted:
            def update(_, view):
                record[0] += memoryview(view).nbytes
                real.update(view)

            def hexdigest(_):
                return real.hexdigest()

        return Counted()

    def sizes(self) -> list[int]:
        """What each hash object that was fed anything was fed, sorted."""
        return sorted(n[0] for n in self.fed if n[0])
