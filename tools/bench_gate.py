"""Bench regression gate: replay BENCH_HISTORY.jsonl, fail on regression.

The perf trajectory (BENCH.md) must only move up: this gate replays the
bench history and exits 1 when, for any gated HEADLINE FAMILY, the latest
recorded round regresses more than ``--threshold`` (default 10%) against
the best prior round of the SAME series. Two families gate independently
by default:

  - the fold headline — masked-update aggregation throughput, updates/s;
  - the sim headline — in-graph federated simulation, participants/s.

Wire it as a tier-2 check after appending a fresh bench round:

  python bench.py ... && python tools/bench_gate.py

Entries are heterogeneous (several generations of writers appended here);
a record contributes when its metric/value/unit can be found either at the
top level or under ``parsed``. Unmatched lines are skipped, never fatal —
the gate must keep working as writers evolve.

Usage:
  python tools/bench_gate.py [--history BENCH_HISTORY.jsonl]
                             [--metric-prefix "masked-update aggregation throughput"
                              --unit "updates/s"]
                             [--threshold 0.10] [--list] [--with-analysis]

``--with-analysis`` additionally runs the static-analysis gate
(tools/analysis, same checks as ``python tools/lint.py --strict``,
including the cross-file deep passes — locks/purity/invariants/metrics/
spans and the secret-flow taint analysis, DESIGN §18) through its
persistent result cache — in CI the lint job has already warmed
``.lint-cache.json`` for the checkout, so the bench leg re-verifies the
tree (taint artifacts included: the deep passes memoize as one unit
keyed by the whole-tree digest) for effectively free instead of
re-analyzing it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

DEFAULT_HISTORY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "BENCH_HISTORY.jsonl"
)
HEADLINE_PREFIX = "masked-update aggregation throughput"
HEADLINE_UNIT = "updates/s"
SIM_PREFIX = "sim round throughput"
SIM_UNIT = "participants/s"
# full-round-path families (tools/bench_round.py): the sum2 mask
# derive+sum and unmask+decode walls recorded as element rates, so the
# higher-is-better floor logic applies unchanged
SUM2_PREFIX = "e2e sum2 mask throughput"
UNMASK_PREFIX = "e2e unmask throughput"
ELEMENTS_UNIT = "elements/s"
# packed-reduction family (bench.py:bytes): staging + cross-shard combine
# traffic per fold. LOWER is better — the floor logic inverts (see
# LOWER_IS_BETTER_UNITS): the gate fails when the latest round MOVES MORE
# bytes than the best (smallest) prior round tolerates.
BYTES_PREFIX = "bytes moved per fold"
BYTES_UNIT = "bytes/fold"
# round-wall family (tools/bench_round.py, DESIGN §20): the end-to-end
# round wall the SLO engine budgets in production. LOWER is better, like
# the bytes family — the gate fails when the latest round takes LONGER
# than the best (fastest) prior round tolerates.
ROUND_WALL_PREFIX = "round wall"
ROUND_WALL_UNIT = "s/round"
# crash-recovery family (tools/soak.py --kill-matrix, DESIGN §9): the
# restarted coordinator's boot-to-serving wall (``xaynet_recovery_seconds``)
# per kill coordinate. LOWER is better — the gate fails when a restart
# takes LONGER than the best (fastest) prior recovery tolerates.
RECOVERY_PREFIX = "restart recovery wall"
RECOVERY_UNIT = "s/recovery"
LOWER_IS_BETTER_UNITS = frozenset(
    {BYTES_UNIT, ROUND_WALL_UNIT, "s/onboard", RECOVERY_UNIT}
)
# multi-tenant interleaved fold (bench.py:multi_tenant, DESIGN §19): two
# tenants' concurrent folds through the paged pool + tenant scheduler,
# in 25M-equivalent updates/s (tenant B's updates scaled by its length
# fraction); the record also carries the scheduler's fairness split
TENANT_PREFIX = "multi-tenant interleaved fold"
# coordinator-ingress family (tools/loadgen_soak.py, DESIGN §21): accepted
# updates/s at the REST boundary for a loadgen-driven round — the
# million-participant ingress headline. Its sibling series, "ingress
# staging bytes per accepted update", is recorded alongside for the
# packed-vs-legacy comparison but not gated (bytes/update depends on the
# negotiated wire mix, which the soak varies deliberately).
INGRESS_PREFIX = "ingress accepted updates"
# tenant-lifecycle family (tools/bench_tenancy.py, DESIGN §23): seconds
# from the authenticated admin onboard POST to the new tenant's first
# completed round. LOWER is better; cold/warm/density legs are distinct
# metric names so each gates against its own history.
ONBOARD_PREFIX = "tenant onboard-to-first-round latency"
ONBOARD_UNIT = "s/onboard"
# families gated independently when no explicit --metric-prefix is given
DEFAULT_FAMILIES = (
    (HEADLINE_PREFIX, HEADLINE_UNIT),
    (SIM_PREFIX, SIM_UNIT),
    (SUM2_PREFIX, ELEMENTS_UNIT),
    (UNMASK_PREFIX, ELEMENTS_UNIT),
    (BYTES_PREFIX, BYTES_UNIT),
    (TENANT_PREFIX, HEADLINE_UNIT),
    (ROUND_WALL_PREFIX, ROUND_WALL_UNIT),
    (INGRESS_PREFIX, HEADLINE_UNIT),
    (ONBOARD_PREFIX, ONBOARD_UNIT),
    (RECOVERY_PREFIX, RECOVERY_UNIT),
)


def extract(record: dict) -> tuple[str, float, str, str] | None:
    """(metric, value, unit, config) from one history record, wherever the
    writer put it; None when the record carries no scalar metric.

    ``config`` is the measurement-configuration fingerprint: the fold
    kernel plus the pinned thread counts (and mesh size) when the writer
    recorded them — extended with the sim series' population/block shape.
    A kernel or thread-config change is a DIFFERENT experiment —
    BENCH_r05 re-measured 29.46 updates/s where r03 recorded ~49 on the
    same code purely from an implicit thread-default shift — so the gate
    compares only within one exact (metric, config) series instead of
    flagging the config change as a regression."""
    for node in (record, record.get("parsed") or {}):
        metric = node.get("metric")
        value = node.get("value")
        unit = node.get("unit")
        if metric and isinstance(value, (int, float)):
            parts = []
            for field in (
                "kernel",
                "native_threads",
                "shard_threads",
                "mesh",
                "participants",
                "block",
                # loadgen_soak ingress records: the driver-tier shape and
                # negotiated wire format are the experiment (absent from
                # every older writer's records, so existing series keep
                # their fingerprints)
                "drivers",
                "tenants",
                "wire",
                # host core count: a 1-cpu container re-measuring a 4-cpu
                # record is the BENCH_r05 thread-shift incident in hardware
                # form — walls and rates alike scale with the cores the
                # kernels thread across, so a cpus change is a different
                # experiment, not a regression. Absent from every older
                # writer's records, so existing series keep their
                # fingerprints (the drivers/tenants/wire precedent).
                "cpus",
            ):
                if node.get(field) is not None:
                    parts.append(f"{field}={node[field]}")
            return str(metric), float(value), str(unit or ""), ",".join(parts)
    return None


def load_series(
    path: str, metric_prefix: str, unit: str
) -> list[tuple[float, str, float, str]]:
    """Chronological (ts, metric, value, config) for one headline family."""
    series = []
    if not os.path.exists(path):
        return series  # no history yet: an empty one
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except json.JSONDecodeError:
                continue  # a torn append must not kill the gate
            found = extract(record)
            if found is None:
                continue
            metric, value, rec_unit, config = found
            if metric.startswith(metric_prefix) and rec_unit == unit:
                series.append((float(record.get("ts", 0.0)), metric, value, config))
    series.sort(key=lambda item: item[0])
    return series


def gate_family(
    history: str, metric_prefix: str, unit: str, threshold: float
) -> int:
    """Gate one headline family; returns a process exit code."""
    series = load_series(history, metric_prefix, unit)
    if len(series) < 2:
        # nothing to gate against: a fresh repo (or a renamed headline) must
        # not hard-fail CI, but say so loudly
        print(
            f"bench-gate: only {len(series)} '{metric_prefix}' round(s) in "
            f"{history}; nothing to compare",
            file=sys.stderr,
        )
        return 0

    # gate within ONE exact series: the prefix family carries variants
    # (@25M params vs @200k params) whose absolute numbers are worlds
    # apart, and a kernel/thread-config change is a different experiment —
    # the latest record picks which (metric, config) series is being gated
    latest_metric, latest_config = series[-1][1], series[-1][3]
    same_metric = [item for item in series if item[1] == latest_metric]
    series = [item for item in same_metric if item[3] == latest_config]
    if len(series) < 2:
        if len(same_metric) >= 2:
            print(
                f"bench-gate: first round of '{latest_metric}' with config "
                f"[{latest_config or 'none recorded'}] — a kernel/thread-config "
                "change starts a NEW series, not a regression; nothing to compare",
                file=sys.stderr,
            )
        else:
            print(
                f"bench-gate: first round of '{latest_metric}'; nothing to compare",
                file=sys.stderr,
            )
        return 0
    *prior, (_, _, latest, _) = series
    lower_better = unit in LOWER_IS_BETTER_UNITS
    if lower_better:
        # bytes-style family: best prior is the SMALLEST, the gate fails
        # when the latest moves more than threshold ABOVE it
        best_ts, best_metric, best, _best_cfg = min(prior, key=lambda item: item[2])
        floor = best * (1.0 + threshold)
        regressed = latest > floor
    else:
        best_ts, best_metric, best, _best_cfg = max(prior, key=lambda item: item[2])
        floor = best * (1.0 - threshold)
        regressed = latest < floor
    verdict = {
        "latest": latest,
        "best_prior": best,
        "floor": round(floor, 3),
        "threshold": threshold,
        "unit": unit,
        "rounds": len(series),
        "metric": latest_metric,
        "config": latest_config,
        "direction": "lower-is-better" if lower_better else "higher-is-better",
    }
    if regressed:
        verdict["result"] = "REGRESSION"
        print(json.dumps(verdict))
        pct = abs(1 - latest / best) * 100
        word = "above" if lower_better else "below"
        print(
            f"bench-gate: FAIL — latest {latest:.2f} {unit} is "
            f"{pct:.1f}% {word} the best prior round "
            f"({best:.2f} @ ts {best_ts:.0f}, '{best_metric}'); "
            f"tolerated: {threshold * 100:.0f}%",
            file=sys.stderr,
        )
        return 1
    verdict["result"] = "ok"
    print(json.dumps(verdict))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--history", default=DEFAULT_HISTORY)
    ap.add_argument(
        "--metric-prefix",
        default=None,
        help="gate ONLY this headline family (metric name prefix); the "
        "default gates every known family independently",
    )
    ap.add_argument("--unit", default=None)
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.10,
        help="maximum tolerated fractional regression vs the best prior round",
    )
    ap.add_argument(
        "--list", action="store_true", help="print the headline series and exit 0"
    )
    ap.add_argument(
        "--with-analysis",
        action="store_true",
        help="also run the static-analysis gate, reusing its result cache",
    )
    args = ap.parse_args()
    if not (0.0 < args.threshold < 1.0):
        ap.error("--threshold must be in (0, 1)")

    if args.metric_prefix is not None:
        unit = args.unit
        if unit is None:
            # infer the unit for known families — a bare
            # `--metric-prefix "sim round throughput"` must not fall back
            # to updates/s, match zero records, and soft-pass a regression.
            # Unknown prefixes must say their unit: a silent default would
            # reintroduce exactly that match-nothing soft-pass for them.
            unit = next(
                (
                    u
                    for p, u in DEFAULT_FAMILIES
                    if args.metric_prefix.startswith(p) or p.startswith(args.metric_prefix)
                ),
                None,
            )
            if unit is None:
                ap.error(
                    f"cannot infer the unit for metric prefix {args.metric_prefix!r}; "
                    "pass --unit explicitly"
                )
        families = [(args.metric_prefix, unit)]
    else:
        if args.unit is not None:
            ap.error("--unit without --metric-prefix is ambiguous")
        families = list(DEFAULT_FAMILIES)

    if args.list:
        for prefix, unit in families:
            for ts, metric, value, config in load_series(args.history, prefix, unit):
                suffix = f"  [{config}]" if config else ""
                print(f"{ts:.0f}  {value:10.2f} {unit}  {metric}{suffix}")
        return 0

    analysis_rc = 0
    if args.with_analysis:
        repo = Path(__file__).resolve().parent.parent
        if str(repo) not in sys.path:
            sys.path.insert(0, str(repo))
        from tools.analysis import driver as analysis_driver

        # cached (content-hash keyed): a warm .lint-cache.json from the
        # lint job makes this a sub-second re-verification
        analysis_rc = analysis_driver.run(repo, strict=True)

    # every family gates independently; any regression fails the run
    return max(
        analysis_rc,
        *(
            gate_family(args.history, prefix, unit, args.threshold)
            for prefix, unit in families
        ),
    )


if __name__ == "__main__":
    sys.exit(main())
