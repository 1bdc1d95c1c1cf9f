"""Prometheus text-exposition conformance (ISSUE 16 satellite).

A strict parser over the registry's full ``render()`` — the same text
``GET /metrics`` serves — enforcing the exposition-format v0.0.4
grammar: every family announces ``# TYPE`` before its samples, sample
names stay inside the family's legal suffix set, label values round-trip
through the ``\\\\``/``\\n``/``\\"`` escapes, values parse as floats
(``+Inf``/``-Inf``/``NaN`` included), histograms expose ascending ``le``
bounds with monotone cumulative bucket counts, a ``+Inf`` bucket equal
to ``_count``, and the body ends in a newline. Run against the LIVE
process registry, so every metric any imported subsystem registered —
round-wall histogram and SLO gauges included — must conform, not just a
synthetic fixture.
"""

from __future__ import annotations

import math
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from xaynet_tpu.telemetry.registry import get_registry  # noqa: E402

# exercise the escaping path: label values carrying every escaped char
AWKWARD = get_registry().counter(
    "test_prom_awkward_total",
    "test-only counter with label values that need escaping",
    ("path",),
)
AWKWARD.labels(path='C:\\dir\n"quoted"').inc()

EDGE_GAUGE = get_registry().gauge(
    "test_prom_edge_values", "test-only gauge for non-finite rendering", ("kind",)
)
EDGE_GAUGE.labels(kind="inf").set(math.inf)
EDGE_GAUGE.labels(kind="neg").set(-math.inf)

HISTO = get_registry().histogram(
    "test_prom_conformance_seconds", "test-only histogram", ("leg",)
)
for v in (0.001, 0.02, 0.3, 4.0, 1e6):
    HISTO.labels(leg="a").observe(v)

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(rf"^({_NAME})(?:\{{(.*)\}})? (\S+)$")
# label pairs with escape-aware values: backslash, quote, n after backslash
_LABEL_RE = re.compile(rf'({_NAME})="((?:[^"\\]|\\["\\n])*)"(?:,|$)')


def _parse_value(token: str) -> float:
    if token == "+Inf":
        return math.inf
    if token == "-Inf":
        return -math.inf
    if token == "NaN":
        return math.nan
    return float(token)  # raises on malformed


def _parse_labels(raw: str) -> dict[str, str]:
    labels: dict[str, str] = {}
    pos = 0
    while pos < len(raw):
        m = _LABEL_RE.match(raw, pos)
        assert m, f"malformed label segment: {raw[pos:]!r} in {raw!r}"
        value = m.group(2)
        labels[m.group(1)] = (
            value.replace("\\\\", "\0")
            .replace('\\"', '"')
            .replace("\\n", "\n")
            .replace("\0", "\\")
        )
        pos = m.end()
    return labels


_HISTO_SUFFIXES = ("_bucket", "_sum", "_count")


def _family_of(sample_name: str, types: dict[str, str]) -> str:
    if sample_name in types:
        return sample_name
    for suffix in _HISTO_SUFFIXES:
        base = sample_name.removesuffix(suffix)
        if base != sample_name and types.get(base) == "histogram":
            return base
    raise AssertionError(f"sample {sample_name!r} has no preceding # TYPE")


def test_full_registry_render_conforms():
    text = get_registry().render()
    assert text.endswith("\n")
    types: dict[str, str] = {}
    helps: set[str] = set()
    # per (family, labelset-minus-le): ascending le bounds + running counts
    buckets: dict[tuple[str, tuple], list[tuple[float, float]]] = {}
    samples: dict[str, float] = {}
    for line in text.splitlines():
        assert line == line.strip(), f"stray whitespace: {line!r}"
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in helps, f"duplicate HELP for {name}"
            helps.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram"), line
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment line: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        sample_name, raw_labels, value_token = m.groups()
        labels = _parse_labels(raw_labels or "")
        value = _parse_value(value_token)
        family = _family_of(sample_name, types)
        if sample_name.endswith("_bucket") and types[family] == "histogram":
            le = labels.pop("le")
            bound = _parse_value(le)
            # in the order rendered (the family's labelnames, which need not
            # be alphabetical: xaynet_message_pipeline_seconds{stage,phase})
            key = (family, tuple(labels.items()))
            series = buckets.setdefault(key, [])
            if series:
                assert bound > series[-1][0], f"le not ascending in {family}"
                assert value >= series[-1][1], f"bucket counts not monotone in {family}"
            series.append((bound, value))
        else:
            samples[f"{sample_name}{{{raw_labels or ''}}}"] = value

    # histogram cross-checks: +Inf bucket == _count for every labelset
    for (family, labelset), series in buckets.items():
        assert series[-1][0] == math.inf, f"{family} missing +Inf bucket"
        raw = ",".join(f'{k}="{v}"' for k, v in labelset)
        count = samples.get(f"{family}_count{{{raw}}}")
        assert count is not None, f"{family} missing _count for {raw!r}"
        assert series[-1][1] == count, f"{family} +Inf bucket != _count"
        assert f"{family}_sum{{{raw}}}" in samples, f"{family} missing _sum"

    # the awkward label value survived the escape round-trip
    assert 'path="C:\\\\dir\\n\\"quoted\\""' in text
    # the §20 families render through the same grammar
    assert types.get("xaynet_round_wall_seconds") == "histogram"
    assert types.get("xaynet_slo_burn_rate") == "gauge"
    assert types.get("xaynet_slo_alerts_total") == "counter"


def test_every_family_has_help_and_type():
    text = get_registry().render()
    announced = {
        line.split()[2] for line in text.splitlines() if line.startswith("# TYPE ")
    }
    helped = {
        line.split()[2] for line in text.splitlines() if line.startswith("# HELP ")
    }
    assert announced == helped
