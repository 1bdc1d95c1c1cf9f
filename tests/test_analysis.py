"""tools/analysis: the pass-based static-analysis framework (ISSUE 9).

Fixture-corpus tests per deep pass (positive finding, suppressed finding,
baseline-masked finding), the PR-7 race-pattern acceptance fixture for the
lock-discipline lint, the regression fixture proving the old `_prog*`
name-prefix heuristic missed helpers one call deep (and the call-graph
pass catches them), the result cache, `--changed` plumbing, and the
self-gate: the real tree analyzes clean with the checked-in baseline.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analysis import (  # noqa: E402
    Baseline,
    CallGraph,
    Finding,
    ResultCache,
    SourceCache,
    SymbolTable,
    check_file_info,
    driver,
    suppressed,
)
from tools.analysis import invariants, locks, metricscheck, purity, taint  # noqa: E402


def _graph(tmp_path, files: dict[str, str]) -> CallGraph:
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(source)
    cache = SourceCache(tmp_path)
    infos = [cache.get(tmp_path / rel) for rel in files]
    return CallGraph(SymbolTable(infos))


# --- lock-discipline pass ---------------------------------------------------

# The PR-7 access pattern, distilled: per-shard accumulators annotated as
# guarded by the device-dispatch lock, a worker thread folding a shard and
# writing the accumulator slot OUTSIDE the lock. The 1,425-trial stress
# hunt becomes a compile-time finding.
PR7_RACE = """
import threading

class Plan:
    def __init__(self):
        self.accs = [0, 0]  # guarded-by: _dispatch_lock
        self._dispatch_lock = threading.Lock()

class Pipeline:
    def __init__(self, plan: Plan):
        self.plan = plan
        self._queue = []

    def start(self):
        self._worker = threading.Thread(target=self._worker_loop)

    def _worker_loop(self):
        for item in self._queue:
            self._fold_shard(item)

    def _fold_shard(self, item):
        d, batch = item
        plan = self.plan
        new_acc = plan.accs[d] + batch   # read outside the lock
        plan.accs[d] = new_acc           # torn-slice write outside the lock
"""


def test_lock_pass_reports_pr7_race_pattern(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/foo.py": PR7_RACE})
    findings = locks.run(graph)
    msgs = [f.message for f in findings]
    assert any("Plan.accs" in m and "_dispatch_lock" in m for m in msgs)
    # both the unlocked read and the unlocked write are reported
    assert len([f for f in findings if "Plan.accs" in f.message]) >= 2


def test_lock_pass_quiet_when_lock_held(tmp_path):
    fixed = PR7_RACE.replace(
        """        plan = self.plan
        new_acc = plan.accs[d] + batch   # read outside the lock
        plan.accs[d] = new_acc           # torn-slice write outside the lock""",
        """        plan = self.plan
        with plan._dispatch_lock:
            new_acc = plan.accs[d] + batch
            plan.accs[d] = new_acc""",
    )
    assert fixed != PR7_RACE
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/foo.py": fixed})
    assert locks.run(graph) == []


def test_lock_pass_suppression_requires_rationale(tmp_path):
    bare = PR7_RACE.replace(
        "plan.accs[d] = new_acc           # torn-slice write outside the lock",
        "plan.accs[d] = new_acc  # lint: guarded-ok",
    )
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/foo.py": bare})
    store_findings = [f for f in locks.run(graph) if "missing its rationale" in f.message]
    assert store_findings, "a bare guarded-ok must not suppress"

    with_rationale = PR7_RACE.replace(
        "plan.accs[d] = new_acc           # torn-slice write outside the lock",
        "plan.accs[d] = new_acc  # lint: guarded-ok: single-owner slot",
    ).replace(
        "new_acc = plan.accs[d] + batch   # read outside the lock",
        "new_acc = plan.accs[d] + batch  # lint: guarded-ok: single-owner slot",
    )
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/foo.py": with_rationale})
    assert locks.run(graph) == []


def test_lock_pass_event_loop_guard(tmp_path):
    source = """
import threading

class Controller:
    def __init__(self):
        self.depth = 0  # guarded-by: event-loop

    def observe(self):
        self.depth += 1

def _sync_worker(ctl: Controller):
    ctl.observe()

async def _coro_worker(ctl: Controller):
    ctl.observe()

def spawn(ctl):
    threading.Thread(target=_sync_worker, args=(ctl,))

def spawn_loop_host(loop, ctl):
    # a thread that runs an event loop: its coroutines execute ON the loop
    threading.Thread(target=lambda: loop.run_until_complete(_coro_worker(ctl)))
"""
    graph = _graph(tmp_path, {"xaynet_tpu/ingest/foo.py": source})
    findings = locks.run(graph)
    # the sync chain is a foreign-thread touch; the coroutine chain is not
    assert any("event-loop-confined" in f.message for f in findings)
    assert all("_coro_worker" not in f.message for f in findings)


# --- call-graph host-sync/purity pass ---------------------------------------

# The old heuristic's documented false negative: tools/lint.py only walked
# functions whose NAME starts with _prog, so a module-level helper called
# FROM a program body escaped the purity check entirely.
SIM_HELPER_LEAK = """
import numpy as np
import jax.numpy as jnp

def leaky_helper(x):
    return np.asarray(x)  # host sync, one call deep

def traced_helper(x):
    return jnp.asarray(x)  # trace-safe: jax.numpy, not numpy

def _prog_round(x):
    a = leaky_helper(x)
    b = traced_helper(x)
    return a, b
"""


def test_old_prefix_heuristic_misses_helper_one_call_deep(tmp_path):
    """Regression fixture: the per-file rule (the pre-framework check)
    reports NOTHING for a host sync inside a helper called from a _prog*
    body — the false negative ISSUE 9 closes with the call-graph pass."""
    path = tmp_path / "xaynet_tpu/sim/leak.py"
    path.parent.mkdir(parents=True)
    path.write_text(SIM_HELPER_LEAK)
    info = SourceCache(tmp_path).get(path)
    assert not [f for f in check_file_info(info) if f.rule == "sync"]


def test_callgraph_purity_pass_catches_the_helper(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/sim/leak.py": SIM_HELPER_LEAK})
    findings = purity.run(graph)
    assert any(
        f.rule == "sync" and "leaky_helper" in f.message for f in findings
    ), findings
    # jnp.asarray is trace-safe and must NOT be flagged
    assert not any("traced_helper" in f.message for f in findings)


def test_bare_name_resolution_not_shadowed_by_out_of_scope_nested_def(tmp_path):
    """A nested def in an UNRELATED method must not capture a bare-name
    call (closure scoping is dot-boundary, not startswith) — otherwise a
    module-level host-syncing helper called from a program body resolves
    to the wrong function and the purity finding is silently lost."""
    source = """
import numpy as np

def helper(x):
    return np.asarray(x)  # the real callee: a host sync

class SimRound:
    def other(self):
        def helper():  # same name, different (unreachable) scope
            return 1
        return helper()

    def _prog_body(self, x):
        return helper(x)  # must bind to the MODULE-level helper
"""
    findings = purity.run(_graph(tmp_path, {"xaynet_tpu/sim/shadow.py": source}))
    assert any(
        f.rule == "sync" and "'helper'" in f.message for f in findings
    ), findings


def test_purity_pass_cross_file_and_suppression(tmp_path):
    files = {
        "xaynet_tpu/sim/round.py": (
            "from xaynet_tpu.ops.helpers import deep_helper\n"
            "def _prog_round(x):\n"
            "    return deep_helper(x)\n"
        ),
        "xaynet_tpu/ops/helpers.py": (
            "def deep_helper(x):\n"
            "    return x.item()\n"
        ),
    }
    findings = purity.run(_graph(tmp_path, files))
    assert any(
        f.file == "xaynet_tpu/ops/helpers.py" and f.rule == "sync" for f in findings
    ), findings

    files["xaynet_tpu/ops/helpers.py"] = (
        "def deep_helper(x):\n"
        "    return x.item()  # lint: sync-ok\n"
    )
    assert purity.run(_graph(tmp_path, files)) == []


def test_purity_fold_worker_leg(tmp_path):
    source = """
import threading
import numpy as np

class Pipe:
    def start(self):
        threading.Thread(target=self._loop)

    def _loop(self):
        self.helper_with_odd_name()

    def helper_with_odd_name(self):
        return np.asarray([1])  # matches no worker prefix: old rule missed it

    def drain(self):
        return np.asarray([2])  # the sanctioned sync point
"""
    findings = purity.run(_graph(tmp_path, {"xaynet_tpu/parallel/pipe.py": source}))
    assert any("helper_with_odd_name" in f.message for f in findings)
    assert not any("'Pipe.drain'" in f.message for f in findings)


# The Pallas-kernel leg (ISSUE 11): kernel bodies (*_kernel defs in ops
# files importing pallas) must stay pure traced code down the call graph —
# a host sync there lowers nowhere on real hardware, but interpret mode
# would silently run it, so the CPU CI has to catch it statically.
PALLAS_KERNEL_LEAK = """
import numpy as np
from jax.experimental import pallas as pl

def _chunk_helper(x):
    return np.asarray(x)  # host sync, one call deep from a kernel body

def _my_fold_kernel(ref, out):
    out[...] = _chunk_helper(ref[...])
"""


def test_purity_pallas_kernel_leg(tmp_path):
    files = {"xaynet_tpu/ops/fold_pallas.py": PALLAS_KERNEL_LEAK}
    findings = purity.run(_graph(tmp_path, files))
    assert any(
        f.rule == "sync" and "_chunk_helper" in f.message and "Pallas" in f.message
        for f in findings
    ), findings

    # suppression: an annotated trace-time constant passes
    files["xaynet_tpu/ops/fold_pallas.py"] = PALLAS_KERNEL_LEAK.replace(
        "np.asarray(x)  # host sync, one call deep from a kernel body",
        "np.asarray(x)  # lint: sync-ok",
    )
    leg = [
        f
        for f in purity.run(_graph(tmp_path, files))
        if "_chunk_helper" in f.message
    ]
    assert leg == []


def test_purity_pallas_leg_ignores_files_without_pallas_import(tmp_path):
    """The *_kernel name alone (e.g. an XLA jit builder) must not root the
    leg — only files that import jax.experimental.pallas hold kernel
    bodies."""
    source = (
        "import numpy as np\n"
        "def _aggregate_batch_kernel(acc, order_tuple):\n"
        "    return np.asarray(order_tuple)\n"
    )
    findings = purity.run(_graph(tmp_path, {"xaynet_tpu/ops/limbs_x.py": source}))
    assert not any("_aggregate_batch_kernel" in f.message for f in findings)


# --- accounting-invariant pass ----------------------------------------------


def test_invariant_pass_flags_unsanctioned_nb_models_mutation(tmp_path):
    source = (
        "def sneak_credit(agg, k):\n"
        "    agg.nb_models += k\n"
    )
    findings = invariants.run(_graph(tmp_path, {"xaynet_tpu/server/sneak.py": source}))
    assert any(f.rule == "invariant" and "nb_models" in f.message for f in findings)


def test_invariant_pass_respects_whitelist_and_suppression(tmp_path):
    # a whitelisted (file, qualname) site — mirrors the real masking.py entry
    ok = (
        "class Aggregation:\n"
        "    def aggregate(self, obj):\n"
        "        self.nb_models += 1\n"
    )
    findings = invariants.run(
        _graph(tmp_path, {"xaynet_tpu/core/mask/masking.py": ok})
    )
    assert findings == []

    suppressed_src = (
        "def experiment(agg):\n"
        "    agg.nb_models = 0  # lint: invariant-ok: scratch probe, not a round path\n"
    )
    findings = invariants.run(
        _graph(tmp_path, {"xaynet_tpu/server/x.py": suppressed_src})
    )
    assert findings == []


def test_invariant_pass_watches_edge_watermarks(tmp_path):
    source = (
        "def rewind(shared, edge):\n"
        "    shared.edge_watermarks[edge] = 0\n"
        "def wipe(shared):\n"
        "    shared.edge_watermarks.clear()\n"
    )
    findings = invariants.run(_graph(tmp_path, {"xaynet_tpu/server/wm.py": source}))
    assert len([f for f in findings if "watermark" in f.message]) == 2


# --- metrics cross-check ----------------------------------------------------


def _metrics_fixture(tmp_path, code: str, doc_rows: str):
    src = tmp_path / "xaynet_tpu/mod.py"
    src.parent.mkdir(parents=True, exist_ok=True)
    src.write_text(code)
    design = tmp_path / "DESIGN.md"
    design.write_text(
        "<!-- metrics-table:begin -->\n| Series | Type |\n|---|---|\n"
        + doc_rows
        + "\n<!-- metrics-table:end -->\n"
    )
    info = SourceCache(tmp_path).get(src)
    return metricscheck.run([info], design)


def test_metrics_parity_ok(tmp_path):
    code = (
        "from reg import get_registry\n"
        "A = get_registry().counter('xaynet_foo_total', 'help')\n"
        "B = get_registry().gauge('xaynet_bar_depth', 'help', ('shard',))\n"
    )
    rows = "| `xaynet_foo_total` | counter |\n| `xaynet_bar_depth{shard}` | gauge |"
    assert _metrics_fixture(tmp_path, code, rows) == []


def test_metrics_undocumented_and_stale_and_duplicate(tmp_path):
    code = (
        "from reg import get_registry\n"
        "A = get_registry().counter('xaynet_foo_total', 'help')\n"
        "B = get_registry().counter('xaynet_foo_total', 'help again')\n"
    )
    rows = "| `xaynet_gone_total` | counter |"
    findings = _metrics_fixture(tmp_path, code, rows)
    msgs = " | ".join(f.message for f in findings)
    assert "registered more than once" in msgs
    assert "not in the DESIGN.md metric tables" in msgs
    assert "xaynet_gone_total" in msgs and "not registered" in msgs


def test_metrics_brace_shorthand_expansion(tmp_path):
    code = (
        "from reg import get_registry\n"
        "A = get_registry().gauge('xaynet_s_depth', 'h')\n"
        "B = get_registry().gauge('xaynet_s_ratio', 'h')\n"
    )
    rows = "| `xaynet_s_{depth,ratio}` | gauge |"
    assert _metrics_fixture(tmp_path, code, rows) == []


# --- span-discipline pass (ISSUE 12) ----------------------------------------


def _spans_fixture(tmp_path, code: str, doc_rows: str):
    from tools.analysis import spans

    src = tmp_path / "xaynet_tpu/mod.py"
    src.parent.mkdir(parents=True, exist_ok=True)
    src.write_text(code)
    design = tmp_path / "DESIGN.md"
    design.write_text(
        "<!-- span-table:begin -->\n| Span | Where |\n|---|---|\n"
        + doc_rows
        + "\n<!-- span-table:end -->\n"
    )
    info = SourceCache(tmp_path).get(src)
    return spans.run([info], design)


def test_span_parity_and_with_discipline_ok(tmp_path):
    code = (
        "from ..telemetry import tracing as trace\n"
        "S = trace.declare_span('mod.work')\n"
        "def f():\n"
        "    with trace.get_tracer().span(S, batch=1):\n"
        "        pass\n"
        "    tracer = trace.get_tracer()\n"
        "    with tracer.span('mod.work'):\n"
        "        pass\n"
    )
    rows = "| `mod.work` | mod.py |"
    assert _spans_fixture(tmp_path, code, rows) == []


def test_span_bare_call_and_undeclared_flagged(tmp_path):
    code = (
        "from ..telemetry import tracing as trace\n"
        "S = trace.declare_span('mod.work')\n"
        "def f():\n"
        "    h = trace.get_tracer().span(S)\n"  # not a with-item
        "    with trace.get_tracer().span('mod.undeclared'):\n"
        "        pass\n"
    )
    rows = "| `mod.work` | mod.py |\n| `mod.undeclared` | nowhere |"
    msgs = " | ".join(f.message for f in _spans_fixture(tmp_path, code, rows))
    assert "must be used as a `with` item" in msgs
    assert "never declared" in msgs
    assert "not declared anywhere" in msgs  # the stale doc row for mod.undeclared


def test_span_duplicate_declaration_and_table_drift(tmp_path):
    code = (
        "from ..telemetry import tracing as trace\n"
        "A = trace.declare_span('mod.dup')\n"
        "B = trace.declare_span('mod.dup')\n"
        "C = trace.declare_span('mod.solo')\n"
    )
    rows = "| `mod.dup` | mod.py |"
    msgs = " | ".join(f.message for f in _spans_fixture(tmp_path, code, rows))
    assert "declared more than once" in msgs
    assert "'mod.solo' is not in the DESIGN.md §16 span table" in msgs


def test_span_brace_shorthand_rows(tmp_path):
    code = (
        "from ..telemetry import tracing as trace\n"
        "A = trace.declare_span('mod.one')\n"
        "B = trace.declare_span('mod.two')\n"
        "def f():\n"
        "    with trace.get_tracer().span(A):\n"
        "        with trace.get_tracer().span(B):\n"
        "            pass\n"
    )
    rows = "| `mod.{one,two}` | mod.py |"
    assert _spans_fixture(tmp_path, code, rows) == []


_USAGE_CODE = (
    "from ..telemetry import tracing as trace\n"
    "A = trace.declare_span('mod.one', usage='thread')\n"
    "B = trace.declare_span('mod.two', mirror=True, usage='crew')\n"
    "C = trace.declare_span('mod.three')\n"
    "D = trace.declare_span('mod.lone', usage='carrier')\n"
)


@pytest.mark.parametrize("rows,found", [
    # a group whose names differ says which word is whose; one word is every name's
    ("| `mod.{one,two,three}` | mod.py | `thread`: one; `crew`: two | work |\n"
     "| `mod.lone` | mod.py | `carrier` | a wait |", []),
    # a declared word the row does not give
    ("| `mod.{one,two,three}` | mod.py | `thread`: one | work |\n"
     "| `mod.lone` | mod.py | `carrier` | a wait |",
     ["span 'mod.two' is declared usage='crew' and its row of the DESIGN.md §16 span table "
      "says '-'"]),
    # another word than the declared one
    ("| `mod.{one,two,three}` | mod.py | `thread`: one; `crew`: two | work |\n"
     "| `mod.lone` | mod.py | `process` | a wait |",
     ["span 'mod.lone' is declared usage='carrier' and its row of the DESIGN.md §16 span "
      "table says 'process'"]),
    # a word on a span that declares none
    ("| `mod.{one,two,three}` | mod.py | `thread`: one, three; `crew`: two | work |\n"
     "| `mod.lone` | mod.py | `carrier` | a wait |",
     ["the span table gives 'mod.three' the usage 'thread' and its declaration has none"]),
])
def test_span_usage_words_match_the_table(tmp_path, rows, found):
    msgs = [f.message for f in _spans_fixture(tmp_path, _USAGE_CODE, rows)]
    assert len(msgs) == len(found)
    for want, got in zip(found, msgs):
        assert want in got


# --- secret-flow taint pass (ISSUE 14) ---------------------------------------

# The acceptance fixture: a mask seed formatted by one helper, emitted by
# another — the leak crosses TWO interprocedural hops before it reaches
# the logging call, which is exactly what a lexical grep can never see.
TAINT_2HOP_LEAK = """
import logging
logger = logging.getLogger("x")

def fmt(tag, material):
    return f"{tag}: {material.hex()}"

def emit(line):
    logger.warning("phase note: %s", line)

def close_window():
    seed = MaskSeed.generate()
    emit(fmt("seed", seed.as_bytes()))
"""


def test_taint_catches_planted_seed_to_log_through_two_hops(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/server/phases/leak.py": TAINT_2HOP_LEAK})
    findings = taint.run(graph)
    assert any(
        f.rule == "taint"
        and "mask seed" in f.message
        and "logging call" in f.message
        and "via emit" in f.message
        for f in findings
    ), findings


def test_taint_container_and_attr_propagation_across_methods(tmp_path):
    # the seed-dict shape: a secret stored into a container attribute in
    # one method leaks through a sibling method's log call
    source = """
import logging
logger = logging.getLogger("x")

class SeedVault:
    def __init__(self):
        self.seeds = {}

    def remember(self, pk):
        self.seeds[pk] = MaskSeed.generate()

    def debug_dump(self):
        logger.info("vault contents: %s", self.seeds)
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/vault.py": source}))
    assert any(
        f.rule == "taint" and "logging call" in f.message for f in findings
    ), findings


def test_taint_sink_variety(tmp_path):
    source = """
import json
from ..telemetry import tracing as trace
from ..telemetry.recorder import flight_dump

def spans(tracer):
    s = MaskSeed.generate()
    with tracer.span("x.y", batch=1) as h:
        h.set(leaked=s.as_bytes())

def flights():
    s = MaskSeed.generate()
    flight_dump("trigger", detail=s.as_bytes().hex())

def labels(counter):
    s = MaskSeed.generate()
    counter.labels(trigger=s.as_bytes().hex()).inc()

def dumps():
    s = MaskSeed.generate()
    return json.dumps({"seed": s.as_bytes().hex()})

def raises():
    s = MaskSeed.generate()
    raise ValueError(f"bad seed {s.as_bytes().hex()}")
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/sinks.py": source}))
    msgs = " | ".join(f.message for f in findings)
    assert "span attribute" in msgs
    assert "flight-recorder" in msgs
    assert "metric label" in msgs
    assert "serialized JSON dump" in msgs
    assert "exception message" in msgs


def test_taint_log_sink_catches_chained_and_attr_loggers(tmp_path):
    # logging.getLogger(...).warning(...) and self.logger.warning(...)
    # are log sinks too — not just the bound module-level `logger` name
    source = """
import logging

class Phase:
    def __init__(self):
        self.logger = logging.getLogger("x")

    def chained(self):
        s = MaskSeed.generate()
        logging.getLogger("x").warning("s=%s", s.as_bytes().hex())

    def attr(self):
        s = MaskSeed.generate()
        self.logger.info("s=%s", s.as_bytes().hex())
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/chain.py": source}))
    lines = {f.line for f in findings if "logging call" in f.message}
    assert len(lines) == 2, findings


def test_taint_scrub_attrs_is_not_a_declassifier(tmp_path):
    # scrub_attrs only redacts deny-listed KEYS: a secret under a
    # non-denied key passes through verbatim, so taint must survive it
    source = """
import json
from ..telemetry.redact import scrub_attrs

def export(fh):
    s = MaskSeed.generate()
    json.dump(scrub_attrs({"d": s.as_bytes().hex()}, "x"), fh)
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/scrub.py": source}))
    assert any("serialized JSON dump" in f.message for f in findings), findings


def test_taint_exception_sink_scoped_to_server_sdk_edge(tmp_path):
    source = """
def raises():
    s = MaskSeed.generate()
    raise ValueError(f"bad seed {s.as_bytes().hex()}")
"""
    # core/ raises are not an attacker/operator-facing surface (ISSUE 14)
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/core/mask/x.py": source}))
    assert not any("exception message" in f.message for f in findings)


def test_taint_declassifiers_terminate_flows(tmp_path):
    source = """
import logging
from .hash import sha256
from ..telemetry.redact import redact
logger = logging.getLogger("x")

def ok_projections(pk):
    seed = MaskSeed.generate()
    logger.info("seed: %d bytes, digest %s", len(seed.as_bytes()),
                sha256(seed.as_bytes()).hex())
    logger.warning("redacted: %s", redact(seed.as_bytes()))
    logger.info("sealed: %s", pk.encrypt(seed.as_bytes()).hex())
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/clean.py": source}))
    assert findings == [], findings


def test_taint_suppression_requires_rationale(tmp_path):
    bare = """
import logging
logger = logging.getLogger("x")

def leak():
    s = MaskSeed.generate()
    logger.info("s=%s", s.as_bytes().hex())  # lint: taint-ok
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/supp.py": bare}))
    assert any(f.rule == "taint" for f in findings), "a bare taint-ok must not suppress"
    assert any("missing its rationale" in f.message for f in findings)

    with_rationale = bare.replace(
        "# lint: taint-ok", "# lint: taint-ok: test fixture, sanctioned"
    )
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/supp.py": with_rationale}))
    assert findings == [], findings


def test_taint_source_suppression_sanctions_downstream_flow(tmp_path):
    # suppressing at the SOURCE read declares a declassification boundary:
    # the durable-state idiom (one reviewed suppression, no cascade)
    source = """
import json
import logging
logger = logging.getLogger("x")

def save(self):
    blob = json.dumps({"seed": MaskSeed.generate().as_bytes().hex()})  # lint: taint-ok: durable blob
    return blob.encode()

def caller(self, store):
    logger.info("saving %d bytes", len(save(self)))
    store.put(save(self))
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/state.py": source}))
    assert findings == [], findings


def test_taint_known_clean_fixture_zero_findings(tmp_path):
    # representative telemetry usage over non-secret values: must be silent
    source = """
import json
import logging
logger = logging.getLogger("x")

class Phase:
    def __init__(self, tracer):
        self.tracer = tracer
        self.accepted = 0

    def handle(self, envelope):
        self.accepted += 1
        with self.tracer.span("phase.fold", members=len(envelope)) as h:
            h.set(outcome="folded")
        logger.info("round note: %d accepted", self.accepted)

    def report(self):
        return json.dumps({"accepted": self.accepted})
"""
    findings = taint.run(_graph(tmp_path, {"xaynet_tpu/server/phases/clean.py": source}))
    assert findings == [], findings


def _taint_design(tmp_path, sources_rows=None, declass_rows=None, sink_rows=None):
    reg = taint._registry_tokens()

    def rows(kind, override):
        if override is not None:
            return override
        return "\n".join(f"| `{t}` | doc |" for t in sorted(reg[kind]))

    design = tmp_path / "DESIGN.md"
    design.write_text(
        "<!-- taint-source-table:begin -->\n| Token | What |\n|---|---|\n"
        + rows("source", sources_rows)
        + "\n<!-- taint-source-table:end -->\n"
        "<!-- taint-declassifier-table:begin -->\n| Callee | Why |\n|---|---|\n"
        + rows("declassifier", declass_rows)
        + "\n<!-- taint-declassifier-table:end -->\n"
        "<!-- taint-sink-table:begin -->\n| Token | Surface |\n|---|---|\n"
        + rows("sink", sink_rows)
        + "\n<!-- taint-sink-table:end -->\n"
    )
    return design


def test_taint_design_parity_ok(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/empty.py": "x = 1\n"})
    assert taint.run(graph, _taint_design(tmp_path)) == []


def test_taint_design_parity_drift_both_directions(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/empty.py": "x = 1\n"})
    # a stale doc row and a missing registry row, in one table
    rows = "\n".join(
        f"| `{t}` | doc |"
        for t in sorted(taint._registry_tokens()["sink"] - {"log-call"})
    ) + "\n| `carrier-pigeon` | doc |"
    findings = taint.run(graph, _taint_design(tmp_path, sink_rows=rows))
    msgs = " | ".join(f.message for f in findings)
    assert "taint sink 'log-call'" in msgs and "is not in the DESIGN.md" in msgs
    assert "'carrier-pigeon' is not in the tools/analysis/taint.py registry" in msgs


def test_taint_cold_and_warm_timing_pins_the_gate():
    """The <1s warm full-tree budget (ISSUE 9, re-pinned by ISSUE 14): a
    cached re-verification of the whole tree — taint artifacts included —
    stays under a second; the cold deep passes stay within CI sanity."""
    import time

    # cold-ish: force the deep passes to run in-process (no result cache)
    t0 = time.perf_counter()
    rc = driver.run(REPO, strict=True, use_cache=False)
    cold = time.perf_counter() - t0
    assert rc == 0
    assert cold < 120.0, f"cold full-tree analysis took {cold:.1f}s"

    # warm: the persistent cache answers; best-of-two damps machine noise
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        rc = driver.run(REPO, strict=True)
        walls.append(time.perf_counter() - t0)
        assert rc == 0
    warm = min(walls)
    assert warm < 1.0, f"warm cached gate took {warm:.2f}s (budget: <1s)"


# --- suppression / baseline mechanics ---------------------------------------


def test_legacy_suppression_tokens_still_work():
    assert suppressed("telemetry", "t = perf_counter()  # telemetry-exempt")
    assert suppressed("sync", "x = np.asarray(y)  # lint: sync-ok")
    assert not suppressed("guarded", "x = 1  # lint: guarded-ok")  # no rationale
    assert suppressed("guarded", "x = 1  # lint: guarded-ok: single owner")


def test_baseline_masks_known_findings(tmp_path):
    f1 = Finding("sync", "a.py", 10, "host sync in helper")
    f2 = Finding("sync", "a.py", 20, "host sync in helper")  # same key, 2nd slot
    f3 = Finding("guarded", "b.py", 5, "unguarded access")
    path = tmp_path / "baseline.json"
    Baseline.write(path, [f1, f2])
    baseline = Baseline.load(path)
    new, masked = baseline.split([f1, f2, f3])
    assert masked == [f1, f2] and new == [f3]
    # one slot consumed per occurrence: a third identical finding is NEW
    new, masked = baseline.split([f1, f2, Finding("sync", "a.py", 30, "host sync in helper")])
    assert len(masked) == 2 and len(new) == 1


def test_baseline_masked_findings_do_not_fail_the_driver(tmp_path, capsys):
    repo = tmp_path / "repo"
    (repo / "pkg").mkdir(parents=True)
    (repo / "pkg" / "bad.py").write_text("import os\n")  # unused import
    baseline = tmp_path / "baseline.json"
    # first run fails, records the baseline, then passes
    assert (
        driver.run(repo, ["pkg"], use_cache=False, baseline_path=baseline) == 1
    )
    assert (
        driver.run(
            repo, ["pkg"], use_cache=False, baseline_path=baseline, update_baseline=True
        )
        == 0
    )
    assert driver.run(repo, ["pkg"], use_cache=False, baseline_path=baseline) == 0
    out = capsys.readouterr()
    assert "unused import" in out.out


# --- result cache -----------------------------------------------------------


def test_result_cache_roundtrip_and_invalidation(tmp_path):
    cache_path = tmp_path / "cache.json"
    cache = ResultCache(cache_path)
    finding = Finding("fmt", "x.py", 3, "trailing whitespace")
    cache.put_file("x.py", "key1", [finding])
    cache.put_project("treekey", [])
    cache.save()

    fresh = ResultCache(cache_path)
    assert fresh.get_file("x.py", "key1") == [finding]
    assert fresh.get_file("x.py", "key2") is None  # content changed
    assert fresh.get_project("treekey") == []
    assert fresh.get_project("other") is None

    disabled = ResultCache(cache_path, enabled=False)
    assert disabled.get_file("x.py", "key1") is None


def test_cached_run_is_fast_and_identical(tmp_path, capsys):
    repo = tmp_path / "repo"
    (repo / "pkg").mkdir(parents=True)
    (repo / "pkg" / "a.py").write_text("import os\nx = 1\n")
    baseline = tmp_path / "baseline.json"
    rc1 = driver.run(repo, ["pkg"], baseline_path=baseline)
    first = capsys.readouterr().out
    rc2 = driver.run(repo, ["pkg"], baseline_path=baseline)
    second = capsys.readouterr().out
    assert (rc1, first) == (rc2, second)
    assert (repo / ".lint-cache.json").exists()


# --- --changed mode ---------------------------------------------------------


def test_changed_files_sees_worktree_and_commit_diffs(tmp_path):
    import shutil
    import subprocess

    if shutil.which("git") is None:
        import pytest

        pytest.skip("git unavailable")

    repo = tmp_path / "r"
    repo.mkdir()

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=repo, check=True, capture_output=True,
            env={"HOME": str(tmp_path), "PATH": "/usr/bin:/bin:/usr/local/bin",
                 "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"},
        )

    git("init", "-q")
    (repo / "a.py").write_text("x = 1\n")
    git("add", "a.py")
    git("commit", "-qm", "seed")
    (repo / "a.py").write_text("x = 2\n")  # modified vs HEAD
    (repo / "b.py").write_text("y = 1\n")  # untracked
    changed = driver.changed_files(repo)
    assert changed is not None and {"a.py", "b.py"} <= changed


# --- the self-gate ----------------------------------------------------------


def test_repo_tree_analyzes_clean_with_checked_in_baseline(capsys):
    """The acceptance gate: the real tree passes --strict with zero
    unsuppressed findings (and the checked-in baseline is empty, so they
    are not baseline-masked either)."""
    baseline = json.loads((REPO / "tools" / "analysis" / "baseline.json").read_text())
    assert baseline["findings"] == {}, "the checked-in baseline must stay empty"
    rc = driver.run(REPO, strict=True)
    out = capsys.readouterr()
    assert rc == 0, f"tree not clean:\n{out.out}"


def test_strict_cli_flag_parses():
    assert driver.main(["--strict"], repo=REPO) == 0


# --- tenant-scope pass (docs/DESIGN.md §19) --------------------------------

from tools.analysis import tenantscope  # noqa: E402

_TENANT_UNKEYED = """
class Phase:
    def handle(self, shared, req):
        last = shared.edge_watermarks.get(req.edge_id)
        return last
"""

_TENANT_KEYED = """
class Phase:
    def handle(self, shared, req):
        last = shared.edge_watermarks.get(req.edge_id)
        log(shared.tenant, last)
        return last
"""


def test_tenant_pass_flags_unkeyed_scoped_state_read(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/server/phases/foo.py": _TENANT_UNKEYED})
    findings = tenantscope.run(graph)
    assert any("edge_watermarks" in f.message and "tenant key" in f.message
               for f in findings)


def test_tenant_pass_quiet_with_tenant_key_in_scope(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/server/phases/foo.py": _TENANT_KEYED})
    assert tenantscope.run(graph) == []
    # a `tenant` PARAMETER also keys the scope
    param = _TENANT_UNKEYED.replace(
        "def handle(self, shared, req):", "def handle(self, shared, req, tenant):"
    )
    graph = _graph(tmp_path, {"xaynet_tpu/server/phases/foo.py": param})
    assert tenantscope.run(graph) == []


def test_tenant_pass_scoped_to_server_and_parallel_trees(tmp_path):
    # the same read under sim/ (not a coordinator tree) is not a finding
    graph = _graph(tmp_path, {"xaynet_tpu/sim/foo.py": _TENANT_UNKEYED})
    assert tenantscope.run(graph) == []


def test_tenant_pass_suppression_requires_rationale(tmp_path):
    bare = _TENANT_UNKEYED.replace(
        "last = shared.edge_watermarks.get(req.edge_id)",
        "last = shared.edge_watermarks.get(req.edge_id)  # lint: tenant-ok",
    )
    graph = _graph(tmp_path, {"xaynet_tpu/server/phases/foo.py": bare})
    assert any("missing its rationale" in f.message for f in tenantscope.run(graph))
    with_rationale = _TENANT_UNKEYED.replace(
        "last = shared.edge_watermarks.get(req.edge_id)",
        "last = shared.edge_watermarks.get(req.edge_id)  # lint: tenant-ok: per-tenant Shared",
    )
    graph = _graph(tmp_path, {"xaynet_tpu/server/phases/foo.py": with_rationale})
    assert tenantscope.run(graph) == []


_LEASE_ROGUE = """
def grab(pool):
    return pool.lease_host("t", (4, 4), "uint32")
"""


def test_tenant_pass_lease_site_whitelist(tmp_path):
    # a lease call outside the sanctioned sites is the static half of the
    # leases == releases round invariant
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/rogue.py": _LEASE_ROGUE})
    findings = tenantscope.run(graph)
    assert any("lease_host" in f.message and "sanctioned" in f.message
               for f in findings)
    # the whitelist covers the real sites (file + qualname exact)
    graph = _graph(
        tmp_path,
        {"xaynet_tpu/parallel/streaming.py":
         "class _StagingRing:\n    def _grow(self, pool):\n"
         "        return pool.lease_host(self.tenant, (4, 4), 'uint32')\n"},
    )
    assert tenantscope.run(graph) == []
    # pool-internal code is exempt wholesale
    graph = _graph(tmp_path, {"xaynet_tpu/tenancy/pool.py": _LEASE_ROGUE})
    assert tenantscope.run(graph) == []


# --- tenant-scope pass: admin-path lock discipline (leg 3, §23) -------------

_ADMIN_UNLOCKED = """
class TenantLifecycle:
    def teardown(self, tenant):
        self.routes.pop(tenant, None)
        self.registry.remove(tenant)
"""

_ADMIN_LOCKED = """
class TenantLifecycle:
    def teardown(self, tenant):
        with self._lock:
            self.routes.pop(tenant, None)
            self.registry.remove(tenant)
"""


def test_tenant_pass_admin_mutation_outside_lock_flagged(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/tenancy/lifecycle.py": _ADMIN_UNLOCKED})
    findings = tenantscope.run(graph)
    assert any("pop()" in f.message and "admin-path" in f.message for f in findings)
    assert any("remove()" in f.message for f in findings)


def test_tenant_pass_admin_mutation_under_lock_quiet(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/tenancy/lifecycle.py": _ADMIN_LOCKED})
    assert tenantscope.run(graph) == []


def test_tenant_pass_admin_guarded_by_annotation_quiet(tmp_path):
    annotated = _ADMIN_UNLOCKED.replace(
        "self.registry.remove(tenant)",
        "self.registry.remove(tenant)  # guarded-by: registry._lock",
    ).replace(
        "self.routes.pop(tenant, None)",
        "self.routes.pop(tenant, None)  # guarded-by: _lock",
    )
    graph = _graph(tmp_path, {"xaynet_tpu/tenancy/lifecycle.py": annotated})
    assert tenantscope.run(graph) == []


def test_tenant_pass_admin_locked_suffix_exempt(tmp_path):
    # *_locked helpers run with the caller already holding the lock — the
    # repo-wide convention the pool/scheduler use too
    code = (
        "class TenantLifecycle:\n"
        "    def _set_state_locked(self, tenant, state):\n"
        "        self._states.pop(tenant, None)\n"
    )
    graph = _graph(tmp_path, {"xaynet_tpu/tenancy/lifecycle.py": code})
    assert tenantscope.run(graph) == []


def test_tenant_pass_admin_leg_only_covers_lifecycle(tmp_path):
    # the same unlocked mutations in another tenancy module are that
    # module's own discipline (locks pass), not the admin leg's
    graph = _graph(tmp_path, {"xaynet_tpu/tenancy/registry.py": _ADMIN_UNLOCKED})
    assert tenantscope.run(graph) == []


# --- tenant-scope pass: sanctioned migration sites (leg 4, §23) -------------

_MIGRATOR_ROGUE = """
def pin(pool, lease):
    pool.set_migrator(lease, None)
"""


def test_tenant_pass_migration_site_whitelist(tmp_path):
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/rogue.py": _MIGRATOR_ROGUE})
    findings = tenantscope.run(graph)
    assert any("set_migrator" in f.message and "sanctioned" in f.message
               for f in findings)
    # a direct .migrator store is the same hole
    store = "def pin(lease):\n    lease.migrator = None\n"
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/rogue.py": store})
    assert any(".migrator" in f.message for f in tenantscope.run(graph))
    # the real ring sites are whitelisted (file + qualname exact)
    ring = (
        "class _StagingRing:\n"
        "    def acquire(self, timeout=None):\n"
        "        lease = self._free.get(timeout=timeout)\n"
        "        self._pool.set_migrator(lease, None)\n"
        "        return lease.array\n"
    )
    graph = _graph(tmp_path, {"xaynet_tpu/parallel/streaming.py": ring})
    assert tenantscope.run(graph) == []
    # pool-internal code is exempt wholesale
    graph = _graph(tmp_path, {"xaynet_tpu/tenancy/pool.py": _MIGRATOR_ROGUE})
    assert tenantscope.run(graph) == []
