"""Round-trace consumer: text timeline, critical path, and CI validation.

Reads the per-round Chrome-trace JSON the tracer exports
(``[metrics] trace_dir`` / ``XAYNET_TRACE_DIR``; loadable as-is in
``chrome://tracing`` / Perfetto) and renders what an operator actually
asks of it:

- ``timeline``  — a per-round text timeline: spans ordered by start,
  indented by parent depth, with wall offsets and durations;
- ``summary``   — per-stage (span-name) totals and the round's
  critical-path decomposition: how much of the round wall each phase span
  accounts for, and inside the update/sum2 phases how much the streaming
  stage/fold legs overlap;
- ``--validate`` — the CI schema gate: timestamps monotonic and finite,
  no orphan parents (every ``parent`` resolves within the bundle — remote
  hops ride ``link`` attributes precisely so this stays strict), children
  inside their parents' windows (small tolerance), the stage spans with one
  place in the tree under it (``unmask.*`` inside ``phase.unmask``,
  ``sum2.score`` inside its ``rest.request``), and the round's phase spans
  covering the round span;
- ``--round-report`` — cross-check the trace's phase walls against the
  round report JSONL (``[metrics] round_report_path``): the two artifacts
  measure the same bracket, so a drift beyond tolerance means one of them
  is lying.
- ``--slo <config>`` — the offline §20 check: recompute the round wall
  (Idle-close -> Unmask-complete) from the trace events, require it to
  agree with the report's in-process ``round_wall`` fold to within the
  span clock's resolution, and flag a breach of the ``[slo]`` target.

Usage:
  python tools/trace_report.py round_3.trace.json
  python tools/trace_report.py --validate round_3.trace.json
  python tools/trace_report.py --round-report reports.jsonl round_3.trace.json
  python tools/trace_report.py --slo config.toml --round-report r.jsonl round_3.trace.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# child may start marginally before its parent's first sample or end after
# (thread scheduling between the monotonic reads); anything past this is a
# real containment violation
_NEST_TOLERANCE_US = 50_000.0

# phase spans the round must contain to count as covered (idle/failure/
# shutdown are round-boundary or error phases and legitimately absent)
_REQUIRED_PHASES = ("phase.sum", "phase.update", "phase.sum2", "phase.unmask")

# stage spans that have one place in the tree: name (or prefix ending in a
# dot) -> the name their parent must carry. The generic check above holds
# them inside whatever parent they name; this one says which parent that is.
# A parent that was still open when the round's window flushed rides as a
# `link` (the Sum2 message's request outlives the round it closes) and is
# not held to it.
_STAGE_PARENTS = {"unmask.": "phase.unmask", "sum2.score": "rest.request"}

# round-report cross-check tolerance: the trace span and the report wall
# bracket the same process+purge region, so they agree to scheduling noise
_PHASE_WALL_REL_TOL = 0.25
_PHASE_WALL_ABS_TOL_S = 0.25

# --slo wall agreement: the in-process fold and the Chrome export read the
# SAME monotonic samples, so the only drift is quantization — the export's
# 0.1 us grid and the decomposition's 1 us rounding. Two ticks of the
# coarser (1 us) clock covers both edges' rounding compounding.
_SLO_WALL_TOL_S = 2e-6


def load_events(path: str) -> list[dict]:
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    return [e for e in events if e.get("ph") == "X"]


def _span_id(event: dict) -> str | None:
    return (event.get("args") or {}).get("span")


def _parent_id(event: dict) -> str | None:
    return (event.get("args") or {}).get("parent")


def validate(events: list[dict]) -> list[str]:
    """Schema checks; returns human-readable problems (empty = valid)."""
    problems: list[str] = []
    if not events:
        return ["trace contains no complete (ph=X) events"]
    by_span: dict[str, dict] = {}
    for e in events:
        ts, dur = e.get("ts"), e.get("dur")
        if not isinstance(ts, (int, float)) or not isinstance(dur, (int, float)):
            problems.append(f"{e.get('name')}: non-numeric ts/dur")
            continue
        if ts < 0 or dur < 0 or ts != ts or dur != dur:
            problems.append(f"{e.get('name')}: negative or NaN ts/dur ({ts}, {dur})")
        sid = _span_id(e)
        if sid:
            if sid in by_span:
                problems.append(f"duplicate span id {sid} ({e.get('name')})")
            by_span[sid] = e
    for e in events:
        pid = _parent_id(e)
        if not pid:
            continue
        parent = by_span.get(pid)
        if parent is None:
            problems.append(
                f"{e.get('name')} (span {_span_id(e)}): orphan parent {pid}"
            )
            continue
        if e["ts"] + _NEST_TOLERANCE_US < parent["ts"] or (
            e["ts"] + e["dur"]
            > parent["ts"] + parent["dur"] + _NEST_TOLERANCE_US
        ):
            problems.append(
                f"{e.get('name')} (span {_span_id(e)}) escapes its parent "
                f"{parent.get('name')}'s window"
            )
    for e in events:
        name = str(e.get("name", ""))
        want = _STAGE_PARENTS.get(name) or _STAGE_PARENTS.get(name.split(".")[0] + ".")
        parent = by_span.get(_parent_id(e) or "")
        if want is None or (parent is None and (e.get("args") or {}).get("link")):
            continue
        if parent is None or parent.get("name") != want:
            problems.append(
                f"{name} (span {_span_id(e)}) is not under a {want} span "
                f"(parent: {parent.get('name') if parent else None})"
            )
    rounds = [e for e in events if e.get("name") == "round"]
    if len(rounds) != 1:
        problems.append(f"expected exactly one round span, found {len(rounds)}")
        return problems
    rnd = rounds[0]
    lo, hi = rnd["ts"] - _NEST_TOLERANCE_US, rnd["ts"] + rnd["dur"] + _NEST_TOLERANCE_US
    names = {e.get("name") for e in events}
    for required in _REQUIRED_PHASES:
        if required not in names:
            problems.append(f"round not covered: no {required} span")
    for e in events:
        if not str(e.get("name", "")).startswith("phase.") or e.get("name") in (
            "phase.idle",
        ):
            continue
        if e["ts"] < lo or e["ts"] + e["dur"] > hi:
            problems.append(f"{e['name']} lies outside the round span")
    return problems


def phase_walls(events: list[dict]) -> dict[str, float]:
    """Seconds per phase span name (summed — a resumed phase runs twice)."""
    out: dict[str, float] = {}
    for e in events:
        name = str(e.get("name", ""))
        if name.startswith("phase."):
            out[name[len("phase."):]] = out.get(name[len("phase."):], 0.0) + (
                e["dur"] / 1e6
            )
    return out


def cross_check(events: list[dict], report: dict) -> list[str]:
    """Trace phase walls vs the round report's phase_durations."""
    problems: list[str] = []
    walls = phase_walls(events)
    for phase, reported in (report.get("phase_durations") or {}).items():
        traced = walls.get(phase)
        if traced is None:
            if reported > _PHASE_WALL_ABS_TOL_S:
                problems.append(
                    f"report has {phase} at {reported:.3f}s but the trace has "
                    "no such phase span"
                )
            continue
        if abs(traced - reported) > max(
            _PHASE_WALL_ABS_TOL_S, reported * _PHASE_WALL_REL_TOL
        ):
            problems.append(
                f"{phase}: trace wall {traced:.3f}s vs report {reported:.3f}s "
                "(beyond tolerance)"
            )
    return problems


def trace_round_wall(events: list[dict]) -> float | None:
    """The round wall recomputed from trace events alone: Idle-close ->
    Unmask-complete, the exact bracket the in-process timeline fold uses
    (docs/DESIGN.md §20); ``None`` when the trace never reached unmask."""
    unmask_end = max(
        (e["ts"] + e["dur"] for e in events if e.get("name") == "phase.unmask"),
        default=None,
    )
    if unmask_end is None:
        return None
    idle_end = max(
        (e["ts"] + e["dur"] for e in events if e.get("name") == "phase.idle"),
        default=None,
    )
    if idle_end is None:
        # same fallback as the fold: a buffer that lost idle brackets from
        # the earliest work-phase start
        idle_end = min(
            (
                e["ts"]
                for e in events
                if str(e.get("name", "")).startswith("phase.")
                and e.get("name") != "phase.unmask"
            ),
            default=unmask_end,
        )
    return max(0.0, (unmask_end - idle_end) / 1e6)


def slo_check(
    events: list[dict], report: dict | None, config_path: str
) -> list[str]:
    """Offline SLO cross-check (§20): the trace-recomputed round wall must
    match the report's in-process ``round_wall`` fold to within the span
    clock's quantization, and a wall over the ``[slo]`` target is flagged
    as a breach."""
    from xaynet_tpu.server.settings import Settings

    problems: list[str] = []
    settings = Settings.load(config_path)
    wall = trace_round_wall(events)
    if wall is None:
        return ["slo: trace has no phase.unmask span — no round wall to check"]
    tenant = (report or {}).get("tenant") or "default"
    section = (report or {}).get("round_wall")
    if section is not None:
        folded = float(section.get("wall_s", -1.0))
        if abs(wall - folded) > _SLO_WALL_TOL_S:
            problems.append(
                f"slo: trace round wall {wall:.6f}s disagrees with the "
                f"report's timeline fold {folded:.6f}s (beyond the span "
                f"clock's {_SLO_WALL_TOL_S * 1e6:.0f} us tolerance)"
            )
    elif report is not None:
        problems.append(
            "slo: round report carries no round_wall section (timeline fold "
            "missing or tracing off)"
        )
    target = settings.slo.tenant_targets().get(tenant, settings.slo.round_wall_s)
    if settings.slo.enabled and wall > target:
        problems.append(
            f"slo: BREACH — round wall {wall:.3f}s exceeds tenant "
            f"{tenant!r} target {target:.3f}s"
        )
    else:
        print(
            f"slo: round wall {wall:.6f}s within tenant {tenant!r} "
            f"target {target:.3f}s",
            file=sys.stderr,
        )
    return problems


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted union of half-open intervals (the timeline fold's idiom)."""
    merged: list[tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def _length(intervals: list[tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in intervals)


# the identity re-derivation below re-does the in-process fold's float
# arithmetic from 0.1 us-quantized trace timestamps; a few microseconds
# per contributing span of drift is quantization, anything more is a bug
_IDENTITY_TOL_S = 1e-3

_WORK_PHASES = ("sum", "update", "sum2", "unmask")


def overlap_report(events: list[dict]) -> tuple[str, list[str]]:
    """Cross-phase concurrency lanes + the timeline identity assertion
    (docs/DESIGN.md §22). Each ``overlap.*`` span carries a ``phase``
    attribute naming its HOME phase (whose work it is); merging it into
    that phase's interval set makes phases genuinely intersect, and the
    identity ``sum(phase walls) − overlap + gap == wall`` must still
    balance — wall < sum of phase walls (negative slack) is the measured
    win, not an accounting error."""
    problems: list[str] = []
    lines: list[str] = []
    phase_iv: dict[str, list[tuple[float, float]]] = {}
    for e in events:
        name = str(e.get("name", ""))
        if name.startswith("phase."):
            p = name[len("phase."):]
            if p in _WORK_PHASES:
                phase_iv.setdefault(p, []).append(
                    (e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
                )
    ov_spans = [e for e in events if str(e.get("name", "")).startswith("overlap.")]
    plain_walls = {p: _length(_merge(iv)) for p, iv in phase_iv.items()}
    lines.append("cross-phase concurrency lanes:")
    if not ov_spans:
        lines.append("  (no overlap.* spans — no drain and no shard subtract ran beside a phase)")
    for e in sorted(ov_spans, key=lambda e: e["ts"]):
        home = str((e.get("args") or {}).get("phase") or "")
        lo, hi = e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6
        if home not in _WORK_PHASES:
            problems.append(
                f"{e['name']}: overlap span without a work-phase 'phase' "
                f"attribute (got {home!r})"
            )
            continue
        if e["dur"] > 0:
            phase_iv.setdefault(home, []).append((lo, hi))
        # the lane: which OTHER phases' walls this span actually ran under
        hidden_under = [
            p
            for p, iv in phase_iv.items()
            if p != home
            and any(lo < phi and plo < hi for plo, phi in iv)
        ]
        lines.append(
            "  {name:<22} {dur:9.4f}s  {home}-work under {under}".format(
                name=e["name"],
                dur=e["dur"] / 1e6,
                home=home,
                under=", ".join(sorted(hidden_under)) or "its own phase",
            )
        )
    merged = {p: _merge(iv) for p, iv in phase_iv.items()}
    walls = {p: _length(iv) for p, iv in merged.items()}
    union = _merge([t for iv in merged.values() for t in iv])
    union_len = _length(union)
    overlap = sum(walls.values()) - union_len
    wall = trace_round_wall(events)
    if wall is None:
        problems.append("overlap: trace has no phase.unmask span — no round wall")
        return "\n".join(lines), problems
    gap = max(0.0, wall - union_len)
    residual = sum(walls.values()) - overlap + gap - wall
    slack = wall - sum(walls.values())
    lines.append(
        "\ntimeline identity: sum(walls) {s:.4f}s − overlap {o:.4f}s + "
        "gap {g:.4f}s == wall {w:.4f}s (residual {r:+.6f}s)".format(
            s=sum(walls.values()), o=overlap, g=gap, w=wall, r=residual
        )
    )
    lines.append(
        "negative slack: {sl:+.4f}s ({verdict})".format(
            sl=slack,
            verdict=(
                "wall beat the serial sum of phase walls"
                if slack < 0
                else "no measured cross-phase overlap win"
            ),
        )
    )
    for p in _WORK_PHASES:
        if p in walls and walls[p] - plain_walls.get(p, 0.0) > 1e-9:
            lines.append(
                "  phase {p}: wall {w:.4f}s (+{d:.4f}s of its work ran under "
                "other phases)".format(
                    p=p, w=walls[p], d=walls[p] - plain_walls.get(p, 0.0)
                )
            )
    if abs(residual) > _IDENTITY_TOL_S:
        problems.append(
            f"overlap: timeline identity does not balance (residual "
            f"{residual:+.6f}s beyond {_IDENTITY_TOL_S}s)"
        )
    if gap > 0 and overlap > 0 and gap < 1e-9:
        pass  # both sides active: nothing further to assert
    return "\n".join(lines), problems


def _children(events: list[dict]) -> dict[str | None, list[dict]]:
    kids: dict[str | None, list[dict]] = {}
    for e in events:
        kids.setdefault(_parent_id(e), []).append(e)
    for lst in kids.values():
        lst.sort(key=lambda e: e["ts"])
    return kids


def timeline(events: list[dict], limit: int = 200) -> str:
    """Indented per-round text timeline (earliest ``limit`` spans)."""
    if not events:
        return "(empty trace)"
    t0 = min(e["ts"] for e in events)
    kids = _children(events)
    by_span = {_span_id(e): e for e in events if _span_id(e)}
    lines: list[str] = []

    def emit(e: dict, depth: int) -> None:
        if len(lines) >= limit:
            return
        attrs = {
            k: v
            for k, v in (e.get("args") or {}).items()
            if k not in ("trace", "span", "parent")
        }
        extra = " ".join(f"{k}={v}" for k, v in attrs.items())
        lines.append(
            f"{(e['ts'] - t0) / 1e6:10.4f}s {'  ' * depth}{e['name']:<24} "
            f"{e['dur'] / 1e6:9.4f}s  {extra}"
        )
        for child in kids.get(_span_id(e), []):
            emit(child, depth + 1)

    roots = [e for e in events if _parent_id(e) not in by_span]
    roots.sort(key=lambda e: e["ts"])
    for root in roots:
        emit(root, 0)
    if len(events) > limit:
        lines.append(f"... ({len(events) - limit} more spans)")
    return "\n".join(lines)


def summary(events: list[dict]) -> str:
    """Per-stage totals + the round's critical-path decomposition."""
    if not events:
        return "(empty trace)"
    per_name: dict[str, tuple[int, float]] = {}
    for e in events:
        n, s = per_name.get(e["name"], (0, 0.0))
        per_name[e["name"]] = (n + 1, s + e["dur"] / 1e6)
    lines = ["per-stage totals:"]
    for name, (n, secs) in sorted(per_name.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"  {name:<24} {n:6d} spans  {secs:10.4f}s")
    rounds = [e for e in events if e["name"] == "round"]
    if rounds:
        wall = rounds[0]["dur"] / 1e6
        lines.append(f"\ncritical path (round wall {wall:.4f}s):")
        walls = phase_walls(events)
        accounted = 0.0
        for phase in ("sum", "update", "sum2", "unmask", "failure"):
            if phase in walls:
                accounted += walls[phase]
                lines.append(
                    f"  phase.{phase:<18} {walls[phase]:10.4f}s "
                    f"({100 * walls[phase] / wall:5.1f}% of round)"
                    if wall > 0
                    else f"  phase.{phase:<18} {walls[phase]:10.4f}s"
                )
        if wall > 0:
            lines.append(
                f"  (other: idle/transitions) {max(0.0, wall - accounted):10.4f}s"
            )
        stage = sum(e["dur"] for e in events if e["name"] == "stream.stage") / 1e6
        fold = sum(e["dur"] for e in events if e["name"] == "stream.fold") / 1e6
        if fold > 0:
            lines.append(
                f"  streaming legs: stage {stage:.4f}s, fold {fold:.4f}s "
                "(overlapped; per-shard folds run concurrently)"
            )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="round trace report / validator")
    ap.add_argument("trace", help="per-round Chrome-trace JSON (tracer export)")
    ap.add_argument(
        "--validate",
        action="store_true",
        help="schema gate: exit 1 on monotonicity/orphan/coverage violations",
    )
    ap.add_argument(
        "--round-report",
        default=None,
        metavar="JSONL",
        help="cross-check phase walls against this round-report JSONL "
        "(matched on round_id when present, else the last line)",
    )
    ap.add_argument(
        "--slo",
        default=None,
        metavar="CONFIG",
        help="offline SLO check against this config's [slo] section: trace "
        "round wall vs the report's timeline fold (needs --round-report "
        "for the fold comparison) + target-breach flagging",
    )
    ap.add_argument(
        "--overlap",
        action="store_true",
        help="cross-phase concurrency lanes for overlap.* spans + assert the "
        "timeline identity sum(walls) − overlap + gap == wall still balances",
    )
    ap.add_argument("--limit", type=int, default=200, help="timeline rows")
    args = ap.parse_args(argv)

    events = load_events(args.trace)
    problems: list[str] = []
    report = None
    if args.validate:
        problems.extend(validate(events))
    if args.round_report:
        round_ids = {
            (e.get("args") or {}).get("round_id")
            for e in events
            if e.get("name") == "round"
        }
        matched = False
        with open(args.round_report) as f:
            for line in f:
                if not line.strip():
                    continue
                candidate = json.loads(line)
                if candidate.get("round_id") in round_ids:
                    report, matched = candidate, True
                elif not matched:
                    report = candidate  # fallback: the LAST line wins
        if report is None:
            problems.append("round report file has no reports")
        else:
            problems.extend(cross_check(events, report))
    if args.slo:
        problems.extend(slo_check(events, report, args.slo))
    if args.overlap:
        lanes, ov_problems = overlap_report(events)
        print(lanes)
        print()
        problems.extend(ov_problems)

    if not args.validate:
        print(timeline(events, args.limit))
        print()
        print(summary(events))
    if problems:
        for p in problems:
            print(f"PROBLEM: {p}", file=sys.stderr)
        print(f"trace INVALID: {len(problems)} problem(s)", file=sys.stderr)
        return 1
    if args.validate:
        print(
            f"trace valid: {len(events)} spans, "
            f"{len({(e.get('args') or {}).get('trace') for e in events})} trace id(s)"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
