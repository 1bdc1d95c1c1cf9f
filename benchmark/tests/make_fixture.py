"""Cut a recorded ``.xplane.pb`` down to a fixture small enough to commit.

    JAX_PLATFORMS=cpu python benchmark/tests/make_fixture.py <in.xplane.pb> <out.xplane.pb> [events per line]

Keeps every device plane and the host's thread lines, the first so many
events with a duration of each line (names, starts and durations as
recorded), and drops statistics. Writes ``<out>`` and, beside it,
``<out stem>.expected.json`` with what ``xtrace`` reads from it.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def text_proto(planes: list[dict], per_line: int) -> str:
    out = []
    for pid, plane in enumerate(planes, 1):
        names: dict[str, int] = {}
        lines = []
        for lid, line in enumerate(plane["lines"], 1):
            events = sorted(line["events"])[:per_line]
            if not events:
                continue
            body = []
            for lo, hi, name in events:
                mid = names.setdefault(name, len(names) + 1)
                body.append(f"events {{ metadata_id: {mid} offset_ps: {round(lo * 1e12)} "
                            f"duration_ps: {round((hi - lo) * 1e12)} }}")
            lines.append(f"lines {{ id: {lid} name: {json.dumps(line['name'])} timestamp_ns: 0 "
                         + " ".join(body) + " }")
        if not lines:
            continue
        meta = " ".join(f"event_metadata {{ key: {mid} value {{ id: {mid} name: {json.dumps(n)} }} }}"
                        for n, mid in names.items())
        out.append(f"planes {{ id: {pid} name: {json.dumps(plane['name'])} " + " ".join(lines)
                   + " " + meta + " }")
    return "\n".join(out)


if __name__ == "__main__":
    from jax.profiler import ProfileData

    from benchmark.harness import xtrace

    src, dst = sys.argv[1], sys.argv[2]
    per_line = int(sys.argv[3]) if len(sys.argv) > 3 else 400
    planes = [p for p in xtrace.load_planes(src)
              if xtrace.DEVICE_PLANE.match(p["name"]) or p["name"].startswith("/host:CPU")]
    t0 = min(lo for p in planes for l in p["lines"] for lo, _, _ in l["events"])
    for p in planes:
        for l in p["lines"]:
            l["events"] = [(lo - t0, hi - t0, n) for lo, hi, n in l["events"]]
    with open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text_proto(planes, per_line)))
    r = xtrace.reduce_file(dst)
    expected = {"devices": r["devices"], "busy_s": r["busy_s"],
                "module_counts": {n: m["count"] for n, m in r["modules"].items()},
                "device_ops": [n for n, _ in r["device_ops"]],
                "idle_gaps": [n for n, _ in r["idle_gaps"]]}
    with open(dst.rsplit(".xplane.pb", 1)[0] + ".expected.json", "w", encoding="utf-8") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected))
