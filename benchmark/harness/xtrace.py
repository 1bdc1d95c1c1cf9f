"""From a profiler trace (``.xplane.pb``) to numbers.

    JAX_PLATFORMS=cpu python -m benchmark.harness.xtrace <file.xplane.pb>

prints one JSON object: the device's busy seconds (the union of the
intervals in which an operation ran on it, averaged over the devices), the
operations that took most device time, the executables ("XLA Modules") with
their counts and device seconds, and the longest idle gaps of the traced
window (``benchmark/serve.py`` lays one host span, ``bench.window``, over it;
without it the window is taken from the first to the last event) with what
the host was doing in them. Run as a process of its own so that the benchmark's
parent never imports JAX; reading a trace initialises no backend.

A device is a plane named ``/device:...``; its busy intervals are the
events of its ``XLA Ops`` line (all of its lines where it has none). Where
a trace has no device plane (the CPU rehearsal) the threads XLA:CPU executes
on stand in, so that the same code runs; such a number is a host number and
the result line that carries it says ``cpu``.
"""

from __future__ import annotations

import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(?!.*(?:CUSTOM|host)).*", re.IGNORECASE)
CPU_EXEC_LINE = re.compile(r"^tf_XLA(PjRtCpuClient|Eigen)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.window"  # the host span benchmark/serve.py lays over the traced window


def _events(line) -> list[tuple[float, float, str]]:
    """(start, end, name) in seconds, events with a duration only."""
    out = []
    for e in line.events:
        if e.duration_ns > 0:
            out.append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, ascending, non-overlapping intervals."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` that ``busy`` (merged) leaves."""
    out, cursor = [], lo
    for b_lo, b_hi in busy:
        if b_lo > cursor:
            out.append((cursor, min(b_lo, hi)))
        cursor = max(cursor, b_hi)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


def attribute(gap: tuple[float, float], host_lines: dict) -> str:
    """What the host was doing in ``gap``: the thread (trace line) and event
    that overlap it longest, with the share of the gap that event covers;
    the program writes no annotations of its own yet, so the runtime's
    thread and event names are what there is, and a long gap is mostly
    covered by none."""
    best, best_overlap = "no host event", 0.0
    for line_name, events in host_lines.items():
        for lo, hi, name in events:
            overlap = min(hi, gap[1]) - max(lo, gap[0])
            if overlap > best_overlap:
                best, best_overlap = f"{line_name}:{name}", overlap
    if best_overlap <= 0:
        return best
    share = 100.0 * best_overlap / (gap[1] - gap[0])
    return f"{best[:100]} ({share:.0f}% of the gap)"


def reduce_planes(planes: list[dict], top: int = 10) -> dict:
    """``planes``: ``[{"name", "lines": [{"name", "events": [(lo, hi, name)]}]}]``."""
    device_planes = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    host_lines: dict[str, list] = {}
    window = None
    for p in planes:
        if p["name"].startswith("/host:CPU"):
            for line in p["lines"]:
                marks = [e for e in line["events"] if e[2] == WINDOW_SPAN]
                if marks:
                    window = (marks[0][0], marks[0][1])
                events = [e for e in line["events"] if e[2] != WINDOW_SPAN]
                if events and not CPU_EXEC_LINE.match(line["name"]):
                    host_lines.setdefault(line["name"], []).extend(events)
    stand_in = not device_planes
    if stand_in:
        lines = [l for p in planes if p["name"].startswith("/host:CPU")
                 for l in p["lines"] if CPU_EXEC_LINE.match(l["name"])]
        device_planes = [{"name": "xla-cpu-threads", "lines": lines}] if lines else []
    ops: dict[str, float] = {}
    modules: dict[str, list] = {}
    busy_per_device, all_busy = [], []
    t_lo, t_hi = float("inf"), float("-inf")
    for p in planes:
        for line in p["lines"]:
            for lo, hi, _ in line["events"]:
                t_lo, t_hi = min(t_lo, lo), max(t_hi, hi)
    if window is not None:  # idle time counts from the window's start, traced events or none
        t_lo, t_hi = window
    for p in device_planes:
        op_lines = [l for l in p["lines"] if l["name"] == OPS_LINE] or p["lines"]
        merged = union([(lo, hi) for l in op_lines for lo, hi, _ in l["events"]])
        busy_per_device.append(sum(hi - lo for lo, hi in merged))
        all_busy.extend(merged)
        for l in op_lines:
            for lo, hi, name in l["events"]:
                ops[name] = ops.get(name, 0.0) + (hi - lo)
        for l in p["lines"]:
            if l["name"] == MODULES_LINE:
                for lo, hi, name in l["events"]:
                    modules.setdefault(name, []).append(hi - lo)
    busy_s = sum(busy_per_device) / len(busy_per_device) if busy_per_device else 0.0
    idle = []
    if all_busy and t_hi > t_lo:
        for gap in sorted(gaps(union(all_busy), t_lo, t_hi), key=lambda g: g[0] - g[1])[:top]:
            idle.append([attribute(gap, host_lines), gap[1] - gap[0]])
    return {
        "devices": len(busy_per_device),
        "device_stand_in": stand_in,
        "busy_s": busy_s,
        "span_s": (t_hi - t_lo) if t_hi > t_lo else 0.0,
        "device_ops": [[n, s] for n, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "modules": {n: {"count": len(d), "seconds": sum(d)} for n, d in modules.items()},
        "idle_gaps": idle,
    }


def load_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": l.name, "events": _events(l)} for l in p.lines]}
            for p in data.planes]


def reduce_file(path: str) -> dict:
    return reduce_planes(load_planes(path))


if __name__ == "__main__":
    json.dump(reduce_file(sys.argv[1]), sys.stdout)
    print()
