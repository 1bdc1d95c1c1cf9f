"""Accounting-invariant pass: sanctioned mutators of ``nb_models`` and the
per-edge seed watermark.

The unmask linchpin (docs/DESIGN.md §9–§11) is ``nb_models ==
seed-watermark``: the update count credited into the aggregate must equal
the seed-dictionary watermark the Sum2/unmask legs reconstruct against.
Every code path that mutates either side is therefore load-bearing — a
new ``agg.nb_models += k`` dropped into a convenient spot is how the
invariant silently drifts (double credit near the cap, undercount after a
degraded retry, replayed edge envelopes counted twice).

This pass whitelists the *sanctioned mutation sites* by (file, function
qualname) with a recorded rationale, and flags every other attribute
store/aug-store of ``nb_models`` and every mutation of the per-edge
watermark map (``edge_watermarks``) under ``xaynet_tpu/``. Adding a
legitimate site means extending the whitelist here — with a rationale —
in the same diff, which is exactly the review nudge the invariant needs;
a one-off experiment can carry ``# lint: invariant-ok: <why>`` instead.
"""

from __future__ import annotations

import ast

from .callgraph import CallGraph, iter_owned_nodes
from .core import Finding, suppressed, suppression_pending_rationale

# (file, function qualname) -> rationale. Qualnames are exact; a rename or
# move is a (deliberate) finding until the whitelist follows it.
NB_MODELS_SITES: dict[tuple[str, str], str] = {
    # the protocol-level aggregator: the reference implementation's own
    # accounting (one credit per aggregate()d mask object / batch member)
    ("xaynet_tpu/core/mask/masking.py", "Aggregation.__init__"): "fresh aggregation starts at zero",
    ("xaynet_tpu/core/mask/masking.py", "Aggregation.aggregate"): "per-object credit",
    ("xaynet_tpu/core/mask/masking.py", "Aggregation.aggregate_batch"): "per-batch credit",
    ("xaynet_tpu/core/mask/masking.py", "Aggregation.aggregate_partial"):
        "edge partial-aggregate credit (members - 1 on top of the object credit)",
    # the device aggregator: same contract, device accumulator
    ("xaynet_tpu/parallel/aggregator.py", "ShardedAggregator.__init__"): "fresh accumulator",
    ("xaynet_tpu/parallel/aggregator.py", "ShardedAggregator.add_batch"): "pre-validated batch credit",
    ("xaynet_tpu/parallel/aggregator.py", "ShardedAggregator._ingest_staged_bytes"):
        "wire batch credit from the synced acceptance vector",
    ("xaynet_tpu/parallel/aggregator.py", "ShardedAggregator.restore"):
        "checkpoint resume restores the persisted count",
    ("xaynet_tpu/parallel/aggregator.py", "ShardedAggregator.restore_shards"):
        "journal resume restores the persisted count (per-shard planes path)",
    ("xaynet_tpu/parallel/aggregator.py", "ShardedAggregator.reset"): "round reset",
    # the streaming pipeline: every credit sits under the pipeline lock,
    # paired with the in-flight decrement (counted_models() atomicity)
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator.fold_resident_rows_now"):
        "caller-thread fold credit (device-resident rows of either layout, wire ingest)",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator.fold_planar_stack_now"):
        "caller-thread fold credit (stacked device batch, fused mask pipeline)",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._fold_pinned_stack"):
        "the ONE shared caller-thread shard fan-out credit (stacked + row-chunked paths)",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._credit"):
        "worker fold credit + in-flight handoff under one lock",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._fold_payload"):
        "degraded-path wire credit from the synced acceptance vector",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._drain_inner"):
        "the ONE deferred wire credit at the drain barrier (drain()'s body; "
        "the public method only wraps it in the stream.drain trace span)",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._dispatch_sharded"):
        "degraded shard-parallel batch credit",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._dispatch_sharded_wire"):
        "degraded shard-parallel wire credit",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._shard_job_done"):
        "cross-shard commit barrier: last shard credits the batch",
    ("xaynet_tpu/parallel/streaming.py", "StreamingAggregator._drain_sharded"):
        "deferred wire credit at the cross-shard barrier",
    # the server-side aggregation facade
    ("xaynet_tpu/server/aggregation.py", "StagedAggregator.fold_partial"):
        "edge envelope: members - 1 on top of the per-object device credit",
    ("xaynet_tpu/server/aggregation.py", "StagedAggregator.restore_state"):
        "checkpoint resume restores the persisted count",
    ("xaynet_tpu/server/aggregation.py", "StagedAggregator.finalize"):
        "host handoff copies the device count verbatim",
    ("xaynet_tpu/server/aggregation.py", "DeviceAggregation.__init__"):
        "in-place unmask view copies the device count verbatim",
}

WATERMARK_SITES: dict[tuple[str, str], str] = {
    ("xaynet_tpu/server/phases/update.py", "UpdatePhase.handle_partial"):
        "the one commit site: watermark advances with the folded envelope",
    ("xaynet_tpu/server/phases/idle.py", "Idle.process"):
        "round-scoped reset (window sequences restart per round)",
}

_WATERMARK_ATTR = "edge_watermarks"
_MUTATING_MAP_METHODS = frozenset({"clear", "pop", "popitem", "update", "setdefault"})


def _qualname_chain(qualname: str) -> list[str]:
    """Every enclosing qualname ("A.b.c" -> ["A.b.c", "A.b", "A"]) — a
    whitelisted function covers its nested helpers/lambdas."""
    parts = qualname.split(".")
    return [".".join(parts[:i]) for i in range(len(parts), 0, -1)]


def run(graph: CallGraph) -> list[Finding]:
    findings: list[Finding] = []
    for fi in graph.symbols.functions:
        rel = fi.file.rel
        if not rel.startswith("xaynet_tpu/"):
            continue
        allowed_nb = any(
            (rel, q) in NB_MODELS_SITES for q in _qualname_chain(fi.qualname)
        )
        allowed_wm = any(
            (rel, q) in WATERMARK_SITES for q in _qualname_chain(fi.qualname)
        )
        for node in iter_owned_nodes(fi.node):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute) and t.attr == "nb_models" and not allowed_nb:
                    line = fi.file.line(t.lineno)
                    if suppressed("invariant", line):
                        continue
                    msg = (
                        f"mutation of nb_models outside the sanctioned "
                        f"accounting sites (in '{fi.qualname}') — nb_models "
                        "must stay equal to the seed watermark at unmask "
                        "(DESIGN §9–§11); add the site to "
                        "tools/analysis/invariants.py with a rationale, or "
                        "annotate '# lint: invariant-ok: <rationale>'"
                    )
                    if suppression_pending_rationale("invariant", line):
                        msg += " [suppression present but missing its rationale]"
                    findings.append(Finding("invariant", rel, t.lineno, msg))
                # shared.edge_watermarks[edge] = seq  (subscript store)
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Attribute)
                    and t.value.attr == _WATERMARK_ATTR
                    and not allowed_wm
                ):
                    line = fi.file.line(t.lineno)
                    if suppressed("invariant", line):
                        continue
                    findings.append(
                        Finding(
                            "invariant",
                            rel,
                            t.lineno,
                            f"mutation of the per-edge seed watermark outside "
                            f"its sanctioned sites (in '{fi.qualname}') — the "
                            "watermark is the replay fence for the nb_models "
                            "invariant; whitelist the site in "
                            "tools/analysis/invariants.py or annotate "
                            "'# lint: invariant-ok: <rationale>'",
                        )
                    )
            # shared.edge_watermarks.clear() / .pop(...) / .update(...)
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATING_MAP_METHODS
                and isinstance(node.func.value, ast.Attribute)
                and node.func.value.attr == _WATERMARK_ATTR
                and not allowed_wm
            ):
                line = fi.file.line(node.lineno)
                if suppressed("invariant", line):
                    continue
                findings.append(
                    Finding(
                        "invariant",
                        rel,
                        node.lineno,
                        f"mutation of the per-edge seed watermark outside its "
                        f"sanctioned sites (in '{fi.qualname}', "
                        f".{node.func.attr}()) — whitelist the site in "
                        "tools/analysis/invariants.py or annotate "
                        "'# lint: invariant-ok: <rationale>'",
                    )
                )
    return findings
