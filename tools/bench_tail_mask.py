"""Host benchmark of what the round's tail does to the one aggregated mask
between a ``pet-msg`` worker's parse and the subtract kernel, and to the
unmasked model between the kernel's result and the store.

Beside ``tools/bench_compose.py``. One mask of each of ``--shapes`` (group
elements x wire bytes each; 25,557,032 at 7 and 10 and 6,603,710 at 6: the
benchmark's vectors at 2 and 3 limbs, 204, 307 and 53 MB of limbs), and
every vector-sized pass the tail made or makes over it, each timed alone on
an idle host:

- ``serialise_and_hash``: ``serialize_mask_object`` and the bytes hashed as a
  dictionary key: what ``incr_mask_score`` of the in-memory store cost a vote
  until PR 36;
- ``sha256``: ``hashlib.sha256`` over the canonical content (configurations,
  limb array, unit): a digest as the vote's key;
- ``equal``: ``MaskObject.__eq__`` (``np.array_equal``) against an equal mask
  held elsewhere in memory: the comparison a second vote for a kept mask pays;
- ``parse``: ``parse_mask_object`` of the serialised mask: what ``best_masks``
  cost the election until PR 36;
- ``store_first_vote`` / ``store_second_vote`` / ``store_best_masks``: the
  in-memory store's own calls on the tree imported (``--root`` for another);
- ``planar_parent`` / ``planar``: the relayout on one device, where the padded
  length is the length (the benchmark's cells): ``mask_planar`` as it stood
  until PR 36 (``wire_to_planar``, and ``np.pad`` where a pad is needed; the
  served arm made it twice a phase) and ``ShardedAggregator.mask_planar`` of
  the tree imported (once a phase);
- ``planar_parent_padded`` / ``planar_padded``: the same two on a mesh of three
  CPU devices, which 25,557,032 elements do not divide: one column of padding,
  the two-pass case.

Then the model's side, on the limbs as the subtract kernel leaves them
(planes ``uint32[L, n]``), since PR 44:

- ``transpose_to_wire``: planes to wire rows, the strided pass the phase's
  ``fetch`` made for the decode until PR 44 (``PlanarLimbs.wire`` now, and
  only for a caller that asks);
- ``decode_wire_one_thread``: ``decode_vect_fast`` over wire rows in a child
  with ``XAYNET_NATIVE_THREADS=1``: the decode as it stood until PR 44;
- ``decode_planes``: ``decode_vect_fast`` over the planes, on the library's
  threads (``cores``: how many the process may run on);
- ``tobytes``: ``model.tobytes()``, the serialisation the phase made twice
  until PR 44;
- ``tobytes_threads``: ``utils/native.py::tobytes``, the same bytes copied
  on the library's threads into an uninitialised ``bytes``: the one
  serialisation the phase makes since.

A shape is run in a child process of its own, on the CPU backend. No chip: a
host number, and quoted as one (PERF.md section 6, PR 36 and PR 44).

Run:  python tools/bench_tail_mask.py [--shapes 25557032x7,25557032x10,6603710x6]
          [--repeat 3] [--root /path/to/another/checkout]
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import subprocess
import sys
import time


def _mask(elements: int, bpn: int):
    import numpy as np

    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.core.mask.object import MaskObject, MaskUnit, MaskVect
    from xaynet_tpu.ops import limbs as limb_ops

    # the first f32 mask of the catalogue at that width: B0/M3 at 6, B0/M6 at
    # 7, B0/M12 at 10
    config = next(
        c
        for c in (
            MaskConfig(GroupType.INTEGER, DataType.F32, bound, model)
            for bound in BoundType for model in ModelType
        )
        if c.bytes_per_number == bpn
    )
    n_limb = limb_ops.n_limbs_for_bytes(bpn)
    rng = np.random.default_rng(bpn)
    data = rng.integers(0, 1 << 32, size=(elements, n_limb), dtype=np.uint64).astype(np.uint32)
    # every element under the order: the parse and the store see a valid mask
    data[:, -1] &= (1 << (config.order.bit_length() - 1 - 32 * (n_limb - 1))) - 1
    unit = np.zeros(limb_ops.n_limbs_for_order(config.order), dtype=np.uint32)
    return MaskObject(MaskVect(config, data), MaskUnit(config, unit))


def _timed(fn) -> tuple[float, object]:
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def _store_votes(memory, mask, twin) -> dict[str, float]:
    async def run():
        store = memory.InMemoryCoordinatorStorage()
        for pk in (b"a" * 32, b"b" * 32):
            await store.add_sum_participant(pk, pk)
        t0 = time.perf_counter()
        await store.incr_mask_score(b"a" * 32, mask)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        await store.incr_mask_score(b"b" * 32, twin)
        second = time.perf_counter() - t0
        t0 = time.perf_counter()
        best = await store.best_masks()
        elect = time.perf_counter() - t0
        assert best is not None and best[0][1] == 2
        return {"store_first_vote": first, "store_second_vote": second, "store_best_masks": elect}

    return asyncio.run(run())


def _model_passes(mask, one_thread: bool) -> dict[str, float]:
    """The passes over the unmasked model: the mask's own limbs stand in for
    the kernel's result (uniform elements under the order)."""
    from fractions import Fraction

    import numpy as np

    from xaynet_tpu.core.mask.encode import decode_vect_fast

    config, wire = mask.vect.config, mask.vect.data
    if one_thread:
        seconds, _ = _timed(lambda: decode_vect_fast(wire, config, 12, Fraction(3, 4)))
        return {"decode_wire_one_thread": seconds}
    steps: dict[str, float] = {}
    planes = np.ascontiguousarray(wire.T)
    steps["transpose_to_wire"], _ = _timed(lambda: np.ascontiguousarray(planes.T))
    try:
        from xaynet_tpu.ops.limbs import PlanarLimbs
        from xaynet_tpu.utils import native
    except ImportError:  # a checkout from before PR 44 (--root): rows only
        return steps
    steps["decode_planes"], model = _timed(
        lambda: decode_vect_fast(PlanarLimbs(planes, len(wire)), config, 12, Fraction(3, 4)))
    steps["tobytes"], _ = _timed(model.tobytes)
    steps["tobytes_threads"], _ = _timed(lambda: native.tobytes(model))
    return steps


def _case(elements: int, bpn: int, repeat: int) -> dict:
    import jax
    import numpy as np

    from xaynet_tpu.core.mask.object import MaskObject, MaskUnit, MaskVect
    from xaynet_tpu.core.mask.serialization import parse_mask_object, serialize_mask_object
    from xaynet_tpu.parallel.aggregator import ShardedAggregator
    from xaynet_tpu.parallel.mesh import make_mesh
    from xaynet_tpu.storage import memory
    from xaynet_tpu.utils import native

    native.load()  # built on first use: not the mask's cost
    mask = _mask(elements, bpn)
    result = {
        "bytes_per_number": bpn, "n_limbs": int(mask.vect.data.shape[1]), "elements": elements,
        "limb_bytes": int(mask.vect.data.nbytes), "unit": "ms",
        "cores": len(os.sched_getaffinity(0)),
    }
    in_ms = lambda steps: {k: round(v * 1000.0, 1) for k, v in steps.items()}  # noqa: E731
    if os.environ.get("XAYNET_NATIVE_THREADS") == "1":
        # the child that times the decode as it stood, on one library thread
        result["runs"] = [in_ms(_model_passes(mask, True)) for _ in range(repeat)]
        return result
    twin = MaskObject(
        MaskVect(mask.vect.config, mask.vect.data.copy()),
        MaskUnit(mask.unit.config, mask.unit.data.copy()),
    )
    devices = jax.devices()
    aggs = {
        "": ShardedAggregator(mask.vect.config, elements, mesh=make_mesh(devices[:1])),
        "_padded": ShardedAggregator(mask.vect.config, elements, mesh=make_mesh(devices)),
    }
    runs = []
    for _ in range(repeat):
        steps: dict[str, float] = {}
        t_ser, wire = _timed(lambda: serialize_mask_object(mask))
        t_hash, _ = _timed(lambda: {wire: 1})
        steps["serialise_and_hash"] = t_ser + t_hash
        steps["sha256"], _ = _timed(lambda: _sha256(mask))
        steps["equal"], same = _timed(lambda: mask == twin)
        assert same
        steps["parse"], parsed = _timed(lambda: parse_mask_object(wire)[0])
        assert parsed == mask
        del wire, parsed
        steps.update(_store_votes(memory, mask, twin))
        for suffix, agg in aggs.items():
            steps["planar_parent" + suffix], two = _timed(
                lambda: _planar_parent(agg, mask.vect.data)
            )
            steps["planar" + suffix], one = _timed(lambda: agg.mask_planar(mask.vect.data))
            assert np.array_equal(one, two)
            del one, two
        steps.update(_model_passes(mask, False))
        runs.append(in_ms(steps))
    result["runs"] = runs
    return result


def _planar_parent(agg, mask_vect):
    """``ShardedAggregator.mask_planar`` as it stood until PR 36."""
    import numpy as np

    from xaynet_tpu.ops.fold_jax import wire_to_planar

    mask = np.asarray(mask_vect, dtype=np.uint32)
    planar = wire_to_planar(mask) if mask.shape == (agg.model_length, agg.n_limbs) else mask
    if planar.shape[1] != agg.padded_length:
        planar = np.pad(planar, ((0, 0), (0, agg.padded_length - planar.shape[1])))
    return planar


def _sha256(mask) -> bytes:
    digest = hashlib.sha256()
    digest.update(mask.vect.config.to_bytes() + mask.unit.config.to_bytes())
    digest.update(mask.unit.data.tobytes())
    digest.update(memoryview(mask.vect.data).cast("B"))  # releases the lock over 2 KiB
    return digest.digest()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="25557032x7,25557032x10,6603710x6",
                    help="elements x wire bytes an element, comma-separated")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--root", default=None, help="another checkout to import xaynet_tpu from")
    ap.add_argument("--case", default=None, help=argparse.SUPPRESS)  # a child's one shape
    args = ap.parse_args()
    root = os.path.abspath(args.root or os.path.join(os.path.dirname(__file__), ".."))
    if args.case:
        sys.path.insert(0, root)
        elements, bpn = args.case.split("x")
        print(json.dumps(_case(int(elements), int(bpn), args.repeat)))
        return
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=3"}
    env.pop("XAYNET_NATIVE_THREADS", None)
    for shape in args.shapes.split(","):
        result = None
        # the shape with the library's threads, then its decode with one
        for threads in ({}, {"XAYNET_NATIVE_THREADS": "1"}):
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--case", shape,
                 "--repeat", str(args.repeat), "--root", root],
                capture_output=True, text=True, env={**env, **threads},
            )
            line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
            if out.returncode != 0 or not line.startswith("{"):
                result = {"shape": shape, "error": out.stderr[-800:]}
                break
            child = json.loads(line)
            if result is None:
                result = child
            else:
                for run, one in zip(result["runs"], child["runs"]):
                    run.update(one)
        result["root"] = root
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
