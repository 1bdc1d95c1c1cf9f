"""Host benchmark of the CPU sum participant's derive-and-sum.

Seconds and peak resident memory of summing the masks of K seeds of ``--n``
elements, one case a child process so that each peak is its own:

- ``stream``: ``core/mask/derive_sum.derive_and_sum``, the one host route
  (PR 29), by thread count (``XAYNET_NATIVE_THREADS`` in the child);
- ``parent``: the host arm ``sdk/state_machine.py::_aggregate_masks`` had
  before PR 29, kept here as the comparison: a pool of ``min(8, K)`` threads
  derives every mask, all K stay alive, each is scanned for validity, they
  are stacked and folded once. It is checked to give the same sum.

``K = 1`` beside the cell's K shows whether memory grows with K. No chip, no
jax: a host number, and quoted as one (PERF.md section 6, PR 29).

Run:  python tools/bench_sum2_host.py [--n 25557032] [--cases 7:12,10:8]
          [--threads 1,4,8,13] [--routes stream,parent] [--json out.json]
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


def _parent_arm(seeds, length, pair):
    """``_aggregate_masks``' host arm as PR 28 left it."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from xaynet_tpu.core.mask import Aggregation, MaskSeed

    stages, t0 = {}, time.perf_counter()

    def lap(name: str) -> None:
        nonlocal t0
        stages[name], t0 = round(time.perf_counter() - t0, 3), time.perf_counter()
        print(f"  parent arm: {name} {stages[name]} s", file=sys.stderr, flush=True)

    mask_seeds = [MaskSeed(s) for s in seeds]
    agg = Aggregation(pair, length)
    if len(mask_seeds) > 1:
        with ThreadPoolExecutor(max_workers=min(8, len(mask_seeds))) as pool:
            masks = list(pool.map(lambda s: s.derive_mask(length, pair), mask_seeds))
    else:
        masks = [s.derive_mask(length, pair) for s in mask_seeds]
    lap("derive")
    for i, mask in enumerate(masks):
        agg.nb_models = i
        agg.validate_aggregation(mask)
    agg.nb_models = 0
    lap("validate")
    stack = np.stack([m.vect.data for m in masks])
    lap("stack")
    agg.aggregate_batch(stack, np.stack([m.unit.data for m in masks]))
    lap("fold")
    return agg.object.unit.data, agg.object.vect.data, stages


def _child(route: str, bpn: int, k: int, n: int) -> None:
    import zlib

    from xaynet_tpu.core.mask.derive_sum import derive_and_sum, host_threads

    pair = _pair(bpn)
    seeds = [bytes([i + 1, bpn]) * 16 for i in range(k)]
    t0 = time.perf_counter()
    stages = None
    if route == "parent":
        unit, vect, stages = _parent_arm(seeds, n, pair)
    else:
        unit, vect = derive_and_sum(seeds, n, pair)
    seconds = time.perf_counter() - t0
    print(json.dumps({
        "stages": stages,
        "route": route, "draw_bytes": bpn, "k": k, "n": n,
        "threads": host_threads() if route == "stream" else min(8, k),
        "seconds": seconds,
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
        "crc32": zlib.crc32(vect.tobytes(), zlib.crc32(unit.tobytes())),
    }))


def _mem_available_gb() -> float:
    with open("/proc/meminfo") as fh:
        for line in fh:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) / (1 << 20)
    return float("inf")


def _run(route: str, bpn: int, k: int, n: int, threads: int | None, floor_gb: float) -> dict:
    """One case in a child of its own. A child is stopped, and the row says
    so, when the machine's available memory falls under ``floor_gb``: on a
    host that returns freed pages late, the parent's arm can take the
    machine with it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XAYNET_NATIVE_THREADS", None)
    if threads is not None:
        env["XAYNET_NATIVE_THREADS"] = str(threads)
    t0 = time.perf_counter()
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", f"{route}:{bpn}:{k}:{n}"],
        env=env, stdout=subprocess.PIPE, text=True,
    )
    while child.poll() is None:
        if _mem_available_gb() < floor_gb:
            child.kill()
            child.wait()
            return {"route": route, "draw_bytes": bpn, "k": k, "n": n, "threads": min(8, k),
                    "seconds": time.perf_counter() - t0, "peak_rss_mb": float("nan"),
                    "crc32": None, "stages": f"stopped: under {floor_gb} GB available"}
        time.sleep(0.2)
    if child.returncode != 0:
        raise SystemExit(f"{route} {bpn}:{k} failed with exit code {child.returncode}")
    return json.loads(child.stdout.read().strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", help=argparse.SUPPRESS)  # route:bytes:k:n
    ap.add_argument("--n", type=int, default=25_557_032)
    ap.add_argument("--cases", default="7:12,10:8", help="draw bytes:K, comma-separated")
    ap.add_argument("--threads", default="", help="thread counts of the stream route; "
                    "default: the CPUs this process may run on")
    ap.add_argument("--routes", default="stream,parent")
    ap.add_argument("--mem-floor-gb", type=float, default=6.0,
                    help="stop a case when the machine has less than this available")
    ap.add_argument("--json", default=None, help="also write the rows here")
    args = ap.parse_args()
    if args.child:
        route, bpn, k, n = args.child.split(":")
        _child(route, int(bpn), int(k), int(n))
        return
    threads = [int(t) for t in args.threads.split(",") if t] or [None]
    routes = args.routes.split(",")
    rows = []
    print(f"host CPUs this process may run on: {len(os.sched_getaffinity(0))}", flush=True)
    for case in args.cases.split(","):
        bpn, k = (int(x) for x in case.split(":"))
        for kk in sorted({1, k}):
            plan = [("stream", t) for t in threads] if "stream" in routes else []
            if "parent" in routes:
                plan.append(("parent", None))
            for route, t in plan:
                row = _run(route, bpn, kk, args.n, t, args.mem_floor_gb)
                rows.append(row)
                print(f"{bpn:>2} bytes  K={kk:<3} {route:<6} threads={row['threads']:<3} "
                      f"{row['seconds']:8.3f} s  peak RSS {row['peak_rss_mb']:8.0f} MB"
                      + (f"  {row['stages']}" if row["stages"] else ""), flush=True)
        sums = {r["crc32"] for r in rows if (r["draw_bytes"], r["k"]) == (bpn, k)} - {None}
        if len(sums) > 1:
            raise SystemExit(f"routes disagree on the sum at {bpn} bytes, K={k}")
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
