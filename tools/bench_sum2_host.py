"""Host benchmark of the CPU sum participant's derive-and-sum.

Seconds and peak resident memory of summing the masks of K seeds of ``--n``
elements through ``core/mask/derive_sum.derive_and_sum``, the one host route
(PR 29), by thread count (``XAYNET_NATIVE_THREADS`` in the child), one case
a child process so that each peak is its own. The arm it replaced was kept
here as the comparison until PR 29's row was in the ledger (PERF.md section
6, PR 29, has its numbers).

``K = 1`` beside the cell's K shows whether memory grows with K. No chip, no
jax: a host number, and quoted as one.

Run:  python tools/bench_sum2_host.py [--n 25557032] [--cases 7:12,10:8]
          [--threads 1,4,8,13] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# draw width in bytes -> the catalogue's configuration the benchmark's cells use
CONFIGS = {7: ("B0", "M6"), 10: ("B6", "M6")}


def _pair(bpn: int):
    from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType

    bound, model = CONFIGS[bpn]
    return MaskConfig(GroupType.INTEGER, DataType.F32, BoundType[bound], ModelType[model]).pair()


def _child(bpn: int, k: int, n: int) -> None:
    import zlib

    from xaynet_tpu.core.mask.derive_sum import derive_and_sum, host_threads

    pair = _pair(bpn)
    seeds = [bytes([i + 1, bpn]) * 16 for i in range(k)]
    t0 = time.perf_counter()
    unit, vect = derive_and_sum(seeds, n, pair)
    seconds = time.perf_counter() - t0
    print(json.dumps({
        "draw_bytes": bpn, "k": k, "n": n,
        "threads": host_threads(),
        "seconds": seconds,
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
        "crc32": zlib.crc32(vect.tobytes(), zlib.crc32(unit.tobytes())),
    }))


def _run(bpn: int, k: int, n: int, threads: int | None) -> dict:
    """One case in a child of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XAYNET_NATIVE_THREADS", None)
    if threads is not None:
        env["XAYNET_NATIVE_THREADS"] = str(threads)
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", f"{bpn}:{k}:{n}"],
        env=env, stdout=subprocess.PIPE, text=True,
    )
    if child.returncode != 0:
        raise SystemExit(f"{bpn}:{k} failed with exit code {child.returncode}")
    return json.loads(child.stdout.strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", help=argparse.SUPPRESS)  # bytes:k:n
    ap.add_argument("--n", type=int, default=25_557_032)
    ap.add_argument("--cases", default="7:12,10:8", help="draw bytes:K, comma-separated")
    ap.add_argument("--threads", default="", help="thread counts of the stream route; "
                    "default: the CPUs this process may run on")
    ap.add_argument("--json", default=None, help="also write the rows here")
    args = ap.parse_args()
    if args.child:
        _child(*(int(x) for x in args.child.split(":")))
        return
    threads = [int(t) for t in args.threads.split(",") if t] or [None]
    rows = []
    print(f"host CPUs this process may run on: {len(os.sched_getaffinity(0))}", flush=True)
    for case in args.cases.split(","):
        bpn, k = (int(x) for x in case.split(":"))
        for kk in sorted({1, k}):
            for t in threads:
                row = _run(bpn, kk, args.n, t)
                rows.append(row)
                print(f"{bpn:>2} bytes  K={kk:<3} threads={row['threads']:<3} "
                      f"{row['seconds']:8.3f} s  peak RSS {row['peak_rss_mb']:8.0f} MB", flush=True)
        sums = {r["crc32"] for r in rows if (r["draw_bytes"], r["k"]) == (bpn, k)}
        if len(sums) > 1:
            raise SystemExit(f"thread counts disagree on the sum at {bpn} bytes, K={k}")
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
