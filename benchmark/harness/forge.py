"""The forge: a round's sealed uploads, made from ``--seed`` on the host's
CPUs by the SDK's production encode path.

Each worker process owns the participants ``i`` with ``i % workers ==
worker``. It masks their weights as soon as it starts (native
``Masker.mask``: a mask depends on the mask seed, the weights and the mask
configuration only), while the coordinator is still starting; what needs
the round — the task signatures under the round seed, the seed dictionary
sealed to the sum participants' ephemeral keys, the envelope sealed to the
coordinator's round key — waits for ``seal`` once the round's Sum phase
has closed. The sealing half is a copy of ``loadgen/build.py::UpdateForge``
without its ``PopulationEngine`` import, which would bring a JAX program
into every worker. Workers never touch JAX (``JAX_PLATFORMS=cpu`` is set
for them all the same): the chip has one owner, the coordinator.
"""

from __future__ import annotations

import multiprocessing as mp
import multiprocessing.connection  # noqa: F401 - mp.connection.wait
import os
import time
from fractions import Fraction

import numpy as np

from . import reference

KEY_SPACING = 1000  # keys_for_task search stride per participant (sdk.flood's)


def mask_seed_for(seed: int, index: int) -> bytes:
    return np.random.default_rng([int(seed), int(index), 0x6D61736B]).bytes(32)


def _mask_config(mask: dict):
    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType

    return MaskConfig(
        GroupType[mask["group_type"].upper()], DataType[mask["data_type"].upper()],
        BoundType[mask["bound_type"].upper()], ModelType[mask["model_type"].upper()],
    ).pair()


def _worker(worker: int, workers: int, spec: dict, conn) -> None:
    """One forge process: mask its share at once, then serve ``seal``."""
    try:
        from xaynet_tpu.core.common import RoundParameters
        from xaynet_tpu.core.crypto.encrypt import PublicEncryptKey
        from xaynet_tpu.core.mask.masking import Masker
        from xaynet_tpu.core.mask.model import Scalar
        from xaynet_tpu.core.mask.seed import MaskSeed
        from xaynet_tpu.core.message import Message, Update
        from xaynet_tpu.sdk.simulation import keys_for_task
        from xaynet_tpu.utils import native

        if native.load() is None:
            raise RuntimeError("native library did not load in the forge worker")
        config = _mask_config(spec["mask"])
        scalar = Scalar.from_fraction(Fraction(1, spec["scalar_den"]))
        masked: dict[int, tuple] = {}
        t0 = time.monotonic()
        for index in (i for i in spec["order"] if i % workers == worker):
            w = reference.to_f32(reference.weights_fixed(spec["seed"], index, spec["model_length"]))
            if spec.get("control") == "bf16":
                w = reference.round_to_bf16(w)
            mseed = MaskSeed(mask_seed_for(spec["seed"], index))
            _, obj = Masker(config, mseed).mask(scalar, w)
            masked[index] = (mseed, obj)
        conn.send(("masked", worker, len(masked), time.monotonic() - t0))
        while True:
            job = conn.recv()
            if job is None:
                return
            params = RoundParameters.from_dict(job["params"])
            sums = {bytes.fromhex(k): PublicEncryptKey(bytes.fromhex(v))
                    for k, v in job["sums"].items()}
            coordinator_pk = PublicEncryptKey(params.pk)
            round_seed = params.seed.as_bytes()
            wire_planar = params.wire_format >= 2
            for index in job["indices"]:
                if index % workers != worker:
                    continue
                mseed, obj = masked.pop(index)
                keys = keys_for_task(round_seed, params.sum, params.update, "update",
                                     start=index * KEY_SPACING)
                payload = Update(
                    sum_signature=keys.sign(round_seed + b"sum").as_bytes(),
                    update_signature=keys.sign(round_seed + b"update").as_bytes(),
                    masked_model=obj,
                    local_seed_dict={pk: mseed.encrypt(e) for pk, e in sums.items()},
                    wire_planar=wire_planar,
                )
                message = Message(participant_pk=keys.public, coordinator_pk=params.pk,
                                  payload=payload)
                sealed = coordinator_pk.encrypt(message.to_bytes(keys.secret))
                conn.send(("sealed", index, keys.public))
                conn.send_bytes(sealed)
                del sealed, message, payload, obj
            conn.send(("done", worker))
    except BaseException as exc:  # noqa: BLE001 - report to the parent, never hang it
        try:
            conn.send(("error", worker, f"{type(exc).__name__}: {exc}"))
        except OSError:
            pass


class ForgeError(RuntimeError):
    pass


class Forge:
    """``workers`` CPU processes that mask the participants in ``order``
    (their weights and mask seeds drawn from ``seed``) and seal them for a
    round on demand."""

    def __init__(self, *, seed: int, order: list[int], scalar_den: int, model_length: int,
                 mask: dict, workers: int, control: str | None = None):
        self.workers = max(1, min(workers, len(order)))
        spec = {"seed": seed, "order": list(order), "scalar_den": scalar_den,
                "model_length": model_length, "mask": mask, "control": control}
        ctx = mp.get_context("spawn")
        self._procs, self._conns = [], []
        for w in range(self.workers):
            parent, child = ctx.Pipe()
            proc = ctx.Process(target=_worker, args=(w, self.workers, spec, child), daemon=True)
            proc.start()
            child.close()
            self._procs.append(proc)
            self._conns.append(parent)
        self._masked = False
        self.mask_seconds = 0.0

    def _recv(self, conn):
        msg = conn.recv()
        if msg[0] == "error":
            raise ForgeError(f"forge worker {msg[1]}: {msg[2]}")
        return msg

    def wait_masked(self) -> None:
        if self._masked:
            return
        for conn in self._conns:
            msg = self._recv(conn)
            assert msg[0] == "masked", msg
            self.mask_seconds = max(self.mask_seconds, msg[3])
        self._masked = True

    def seal(self, params_dict: dict, sums: dict, indices: list[int]) -> tuple[dict, dict]:
        """Seal participants ``indices`` for the round; returns
        ({index: sealed bytes}, {index: participant public key})."""
        self.wait_masked()
        job = {"params": params_dict, "sums": {k.hex(): v.hex() for k, v in sums.items()},
               "indices": list(indices)}
        for conn in self._conns:
            conn.send(job)
        sealed, pks = {}, {}
        pending = set(self._conns)
        while pending:
            for conn in mp.connection.wait(list(pending)):
                msg = self._recv(conn)
                if msg[0] == "done":
                    pending.discard(conn)
                    continue
                _, index, pk = msg
                sealed[index] = conn.recv_bytes()
                pks[index] = pk
        missing = set(indices) - set(sealed)
        if missing:
            raise ForgeError(f"forge lost participants {sorted(missing)[:5]}")
        return sealed, pks

    def close(self) -> None:
        for conn in self._conns:
            try:
                conn.send(None)
            except (OSError, BrokenPipeError):
                pass
        for proc in self._procs:
            proc.join(5)
            if proc.is_alive():
                proc.kill()
                proc.join()
        for conn in self._conns:
            conn.close()


def default_workers() -> int:
    """Forge processes: the host's cores less two (the coordinator's event
    loop and this process), at least one, at most twelve."""
    return max(1, min(12, (os.cpu_count() or 2) - 2))
