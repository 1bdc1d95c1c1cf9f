"""Host benchmark of the sending side: one large Sum2 message composed,
signed and sealed, the way a CPU sum participant sends it.

The mirror of ``tools/bench_open_verify.py``. Two ways through the same
public surface, on one message of ``--elements`` group elements at
``--bytes`` wire bytes each (25,557,032 at 7 or 10: the benchmark's 179 and
256 MB):

- ``chain``: what ``sdk/state_machine.py::_send`` did before PR 34, step by
  step: ``MessageEncoder(message, ...)``, ``encoder.part(0)``,
  ``PublicEncryptKey.encrypt(part)``, ``head + body``. Every step returns a
  fresh buffer of the message's length. These calls keep their contracts, so
  the chain runs on any tree; **run it on the parent's tree** (``--root``) for
  the parent's numbers, since on this tree the same calls are written on the
  one-buffer forms and cost less.
- ``one``: ``_PendingSend(...).sealed_part()``: one buffer laid out as the
  sealed box, serialised into, signed over a view, sealed in place; its steps
  are read from the tracer's ``message.*`` spans. Absent on the parent's tree
  (reported as such).

A case is run in a child process of its own, so that peak RSS (``ru_maxrss``)
is the case's and a fresh buffer is a fresh mapping, as in a participant that
sends one message a round; the tool's own construction of the limbs (an
8-byte draw narrowed to 4) is in every case's high-water mark, so compare
the peaks, not their level. No chip, no jax: a host number, and quoted as one
(PERF.md section 6, PR 34).

Run:  python tools/bench_compose.py [--bytes 7,10] [--elements 25557032]
          [--repeat 2] [--root /path/to/another/checkout]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time


def _peak_rss_mb() -> float:
    # not /proc/self/status: the chip's sealed machine serves no VmHWM line
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _message(elements: int, bpn: int):
    import numpy as np

    from xaynet_tpu.core.crypto.sign import SigningKeyPair
    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.core.mask.object import MaskObject, MaskUnit, MaskVect
    from xaynet_tpu.core.message import Message, Sum2
    from xaynet_tpu.ops import limbs as limb_ops

    # the first f32 mask of the catalogue at that width: B0/M6 at 7, B0/M12 at 10
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
    # any limbs serialise alike; the top limb is kept under the order's
    data = rng.integers(0, 1 << 32, size=(elements, n_limb), dtype=np.uint64).astype(np.uint32)
    data[:, -1] &= (1 << (config.order.bit_length() - 1 - 32 * (n_limb - 1))) - 1
    unit = np.zeros(limb_ops.n_limbs_for_order(config.order), dtype=np.uint32)
    keys = SigningKeyPair.derive_from_seed(bytes(range(32)))
    message = Message(
        participant_pk=keys.public,
        coordinator_pk=bytes(32),
        payload=Sum2(
            sum_signature=bytes(64),
            model_mask=MaskObject(MaskVect(config, data), MaskUnit(config, unit)),
        ),
    )
    return message, keys


def _case(way: str, elements: int, bpn: int, repeat: int) -> dict:
    from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
    from xaynet_tpu.core.message.encoder import MessageEncoder
    from xaynet_tpu.sdk import state_machine
    from xaynet_tpu.utils import native

    native.load()  # built on first use: not the message's cost
    message, keys = _message(elements, bpn)
    coordinator = EncryptKeyPair.derive_from_seed(bytes(range(32, 64)))
    runs = []
    for _ in range(repeat):
        steps: dict[str, float] = {}
        t_all = time.perf_counter()
        if way == "chain":
            t0 = time.perf_counter()
            encoder = MessageEncoder(message, keys.secret, None)
            steps["encoder"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            part = encoder.part(0)
            steps["part"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            sealed = coordinator.public.encrypt(part)
            steps["encrypt"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            request = b"POST /message HTTP/1.1\r\n\r\n" + sealed
            steps["head_plus_body"] = time.perf_counter() - t0
            sent = len(request)
            del encoder, part, sealed, request
        else:
            pending = state_machine._PendingSend(
                MessageEncoder(message, keys.secret, None), coordinator.public.as_bytes()
            )
            try:
                sealed = pending.sealed_part()
            except TypeError:  # the parent's tree: sealed_part(i) is the chain
                return {"way": way, "bytes_per_number": bpn, "absent": True}
            sent = len(sealed)
            from xaynet_tpu.telemetry import tracing

            for span in tracing.get_tracer().ring_spans()[-4:]:
                steps[span.name.removeprefix("message.")] = span.duration
                if "route" in span.attrs:
                    steps["route"] = span.attrs["route"]
            del pending, sealed
        steps["total"] = time.perf_counter() - t_all
        runs.append(steps)
    return {
        "way": way, "bytes_per_number": bpn, "elements": elements, "sent_bytes": sent,
        "runs": runs, "peak_rss_mb": round(_peak_rss_mb(), 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bytes", default="7,10", help="wire bytes an element, comma-separated")
    ap.add_argument("--elements", type=int, default=25_557_032)
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--ways", default="chain,one")
    ap.add_argument("--root", default=None, help="another checkout to import xaynet_tpu from")
    ap.add_argument("--case", default=None, help=argparse.SUPPRESS)  # a child's one case
    args = ap.parse_args()
    root = os.path.abspath(args.root or os.path.join(os.path.dirname(__file__), ".."))
    if args.case:
        sys.path.insert(0, root)
        way, bpn = args.case.split(":")
        print(json.dumps(_case(way, args.elements, int(bpn), args.repeat)))
        return
    for bpn in args.bytes.split(","):
        for way in args.ways.split(","):
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--case", f"{way}:{bpn}",
                 "--elements", str(args.elements), "--repeat", str(args.repeat), "--root", root],
                capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
            if out.returncode != 0 or not line.startswith("{"):
                print(json.dumps({"way": way, "bytes_per_number": int(bpn),
                                  "error": out.stderr[-800:]}), flush=True)
                continue
            result = json.loads(line)
            result["root"] = root
            print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
