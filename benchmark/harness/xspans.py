"""The program's own spans in a profiler trace, against the device's idle time.

    JAX_PLATFORMS=cpu python -m benchmark.harness.xspans <file.xplane.pb> <span name> ...

The coordinator mirrors a closed set of its tracer's spans into the
profiler's trace (``Tracer.set_mirror``, docs/DESIGN.md §16) and lists their
names on ``/healthz`` (``trace.mirrored_spans``); those names are the
arguments here, so that a program span is told from the runtime's own
events by the program's list and not by one copied into the benchmark.

Prints one JSON object: per span name its count, its summed seconds and the
seconds of device idle time it covers; the device's idle seconds inside the
window (as ``xtrace`` takes window and busy intervals); and the idle seconds
that at least one program span covers. Run as a process of its own, like
``xtrace``, so that the benchmark's parent never imports JAX.
"""

from __future__ import annotations

import json
import sys

from benchmark.harness.xtrace import (
    CPU_EXEC_LINE, DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, gaps, load_planes, union)


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Seconds that two merged, ascending interval lists have in common."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_planes(planes: list[dict], names: list[str]) -> dict:
    """``planes`` as ``xtrace.load_planes`` gives them."""
    wanted = set(names)
    host = [l for p in planes if p["name"].startswith("/host:CPU") for l in p["lines"]]
    window = None
    spans: dict[str, list[tuple[float, float]]] = {}
    for line in host:
        for lo, hi, name in line["events"]:
            if name == WINDOW_SPAN:
                window = (lo, hi)
            elif name in wanted:
                spans.setdefault(name, []).append((lo, hi))
    device_lines = []
    for p in planes:
        if DEVICE_PLANE.match(p["name"]):
            device_lines.extend([l for l in p["lines"] if l["name"] == OPS_LINE] or p["lines"])
    stand_in = not device_lines
    if stand_in:  # the CPU rehearsal: the threads XLA:CPU executes on stand in
        device_lines = [l for l in host if CPU_EXEC_LINE.match(l["name"])]
    busy = union([(lo, hi) for l in device_lines for lo, hi, _ in l["events"]])
    if window is None:
        ends = [(lo, hi) for p in planes for l in p["lines"] for lo, hi, _ in l["events"]]
        window = (min(e[0] for e in ends), max(e[1] for e in ends)) if ends else (0.0, 0.0)
    idle = gaps(busy, *window) if busy else []
    idle_s = sum(hi - lo for lo, hi in idle)
    covered = overlap(idle, union([iv for ivs in spans.values() for iv in ivs]))
    return {
        "device_stand_in": stand_in,
        "idle_s": idle_s,
        "covered_s": covered,
        "covered_share": covered / idle_s if idle_s > 0 else None,
        "spans": {name: {"count": len(ivs), "seconds": sum(hi - lo for lo, hi in ivs),
                         "idle_covered_s": overlap(idle, union(ivs))}
                  for name, ivs in sorted(spans.items())},
    }


if __name__ == "__main__":
    json.dump(reduce_planes(load_planes(sys.argv[1]), sys.argv[2:]), sys.stdout)
    print()
