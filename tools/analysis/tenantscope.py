"""Tenant-scope pass: tenant-keyed access to round state + sanctioned
page-lease sites.

Multi-tenancy (docs/DESIGN.md §19) turns formerly process-global round
state into per-tenant state: ``Shared``'s round fields (the per-edge seed
watermarks, the resume budget), the accumulator pool's pages, and the
scheduler's fold slots are all keyed by tenant id. A helper that reads
one of these without a tenant in scope is exactly how cross-tenant bleed
starts — an edge watermark checked against the wrong tenant's map, a
page-table probe that aggregates across tenants, a reclaim that frees a
neighbour's pages.

Two legs:

1. **tenant-key-in-scope** — functions under ``xaynet_tpu/server/`` and
   ``xaynet_tpu/parallel/`` that touch tenant-scoped state (the
   ``Shared`` round fields ``edge_watermarks``/``resume_attempts``, or
   the pool's tenant-keyed surface ``page_table``/``balanced``/
   ``reclaim``) must have a tenant key in scope: a parameter named
   ``tenant``, or a ``tenant`` attribute/name read anywhere in the
   function (``self.tenant``, ``shared.tenant``). Sites where the scoping
   is structural (the object itself is per-tenant and no key exists to
   thread) carry ``# lint: tenant-ok: <rationale>`` — the rationale is
   the review record.

2. **sanctioned lease sites** — every ``lease_host`` call outside
   ``xaynet_tpu/tenancy/`` must appear in
   :data:`LEASE_SITES` with a rationale naming its paired release. This
   is the static half of the *leases == releases at round end* invariant:
   the whitelist below is the closed set of places pages enter
   circulation, each reviewed to give them back (unmask release, ring
   close, GC-finalizer backstop, Idle reclaim).

3. **admin-path lock discipline** — the elastic lifecycle manager
   (``tenancy/lifecycle.py``, §23) mutates the registry, the live routing
   dict, the scheduler's weight/tier/demotion maps and the pool from the
   admin REST path *while rounds are running*. Every such mutation must
   be lexically inside a ``with``/``async with`` on a lock-named
   attribute (``*_lock`` / ``*_cond``), or carry a ``# guarded-by:
   <lock>`` annotation recording which lock the callee takes internally.
   Functions named ``*_locked`` are exempt (the caller holds the lock —
   the repo-wide convention).

4. **sanctioned migration sites** — compaction moves a page run and
   swaps ``lease.array`` under the pool lock, so every place *outside*
   ``xaynet_tpu/tenancy/`` that registers or clears a lease's
   ``migrator`` (``set_migrator`` calls, ``.migrator`` stores) must
   appear in :data:`MIGRATION_SITES` with a rationale proving the buffer
   is quiescent when movable and pinned before any access.
"""

from __future__ import annotations

import ast
import re

from .callgraph import CallGraph, iter_owned_nodes
from .core import Finding, suppressed, suppression_pending_rationale

# Shared round fields + pool surface reads that are tenant-keyed
_SCOPED_ATTRS = frozenset({"edge_watermarks", "resume_attempts"})
_SCOPED_POOL_CALLS = frozenset({"page_table", "balanced", "reclaim"})

_LEASE_CALLS = frozenset({"lease_host"})

# (file, function qualname) -> rationale naming the paired release.
LEASE_SITES: dict[tuple[str, str], str] = {
    ("xaynet_tpu/parallel/streaming.py", "_StagingRing._grow"):
        "staging ring buffers, leased as acquire() needs them up to the "
        "ring's size; released by ring.close() from the pipeline's "
        "close(), GC finalizer as the crash backstop",
}

_PREFIXES = ("xaynet_tpu/server/", "xaynet_tpu/parallel/")

# -- leg 3: admin-path lock discipline ----------------------------------------

_ADMIN_FILE = "xaynet_tpu/tenancy/lifecycle.py"
# attribute calls that mutate shared registry/routes/scheduler/pool/budget
# state from the admin path
_ADMIN_MUTATORS = frozenset({
    "add", "remove", "pop", "set_weight", "set_tier", "set_demoted",
    "forget_tenant", "reclaim", "compact", "discharge",
})
# accepts dotted guards ("pool._lock") unlike the locks pass — here the
# annotation is a review record of which lock the CALLEE takes internally
_GUARDED_ANNOT_RE = re.compile(r"#\s*guarded-by:\s*([\w.\-]+)")
_LOCK_NAME_RE = re.compile(r"(_lock|_cond)$")

# -- leg 4: sanctioned migration sites ----------------------------------------

# (file, function qualname) -> rationale proving the quiescence protocol.
MIGRATION_SITES: dict[tuple[str, str], str] = {
    ("xaynet_tpu/parallel/streaming.py", "_StagingRing.acquire"):
        "clears the migrator THROUGH the pool lock before reading "
        "lease.array — an in-flight buffer is an immovable barrier",
    ("xaynet_tpu/parallel/streaming.py", "_StagingRing.release"):
        "re-registers the migrator as the buffer re-enters the free "
        "queue (quiescent again)",
}


def _lockish_with_held(fn_node) -> dict[int, bool]:
    """node id -> whether the node sits lexically inside a ``with`` /
    ``async with`` whose context expression's terminal name looks like a
    lock (``*_lock`` / ``*_cond``)."""
    held_at: dict[int, bool] = {}

    def terminal_name(expr):
        if isinstance(expr, ast.Attribute):
            return expr.attr
        if isinstance(expr, ast.Name):
            return expr.id
        if isinstance(expr, ast.Call):
            return terminal_name(expr.func)
        return None

    def walk(node, held: bool):
        for child in ast.iter_child_nodes(node):
            child_held = held
            if isinstance(child, (ast.With, ast.AsyncWith)):
                for item in child.items:
                    name = terminal_name(item.context_expr)
                    if name and _LOCK_NAME_RE.search(name):
                        child_held = True
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue  # separate FuncInfo, analyzed on its own
            held_at[id(child)] = child_held
            walk(child, child_held)

    held_at[id(fn_node)] = False
    walk(fn_node, False)
    return held_at


def _qualname_chain(qualname: str) -> list[str]:
    parts = qualname.split(".")
    return [".".join(parts[:i]) for i in range(len(parts), 0, -1)]


def _has_tenant_key(fi) -> bool:
    """A tenant key in scope: a param named ``tenant``, or any read of a
    ``tenant`` name/attribute inside the function body."""
    args = fi.node.args
    for a in (
        *args.posonlyargs, *args.args, *args.kwonlyargs,
        *( [args.vararg] if args.vararg else [] ),
        *( [args.kwarg] if args.kwarg else [] ),
    ):
        if a.arg == "tenant":
            return True
    for node in iter_owned_nodes(fi.node):
        if isinstance(node, ast.Attribute) and node.attr == "tenant":
            return True
        if isinstance(node, ast.Name) and node.id == "tenant":
            return True
    return False


def _admin_lock_findings(fi) -> list[Finding]:
    """Leg 3: every admin-path mutation in the lifecycle manager must be
    under a lock-named ``with`` or carry a ``# guarded-by:`` record."""
    if fi.name == "__init__" or fi.name.endswith("_locked"):
        return []
    findings: list[Finding] = []
    held_at = _lockish_with_held(fi.node)
    for node in iter_owned_nodes(fi.node):
        mutator = None
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _ADMIN_MUTATORS
        ):
            mutator = f"{node.func.attr}()"
        elif (
            isinstance(node, ast.Subscript)
            and isinstance(node.ctx, (ast.Store, ast.Del))
            and isinstance(node.value, ast.Attribute)
        ):
            mutator = f"{node.value.attr}[...]"
        if mutator is None:
            continue
        if held_at.get(id(node), False):
            continue
        line = fi.file.line(node.lineno)
        if _GUARDED_ANNOT_RE.search(line):
            continue
        if suppressed("tenant", line):
            continue
        msg = (
            f"admin-path mutation ({mutator}) in '{fi.qualname}' outside "
            "any lock-named 'with' block — the lifecycle mutates live "
            "routing/registry/scheduler/pool state while rounds run "
            "(DESIGN §23); hold the lock, or annotate the line "
            "'# guarded-by: <lock>' naming the lock the callee takes, or "
            "'# lint: tenant-ok: <rationale>'"
        )
        if suppression_pending_rationale("tenant", line):
            msg += " [suppression present but missing its rationale]"
        findings.append(Finding("tenant", fi.file.rel, node.lineno, msg))
    return findings


def run(graph: CallGraph) -> list[Finding]:
    findings: list[Finding] = []
    for fi in graph.symbols.functions:
        rel = fi.file.rel
        if rel.startswith("xaynet_tpu/tenancy/"):
            if rel == _ADMIN_FILE:
                findings.extend(_admin_lock_findings(fi))
            continue  # the pool/scheduler themselves
        in_scope_tree = rel.startswith(_PREFIXES)
        lease_allowed = any(
            (rel, q) in LEASE_SITES for q in _qualname_chain(fi.qualname)
        )
        migration_allowed = any(
            (rel, q) in MIGRATION_SITES for q in _qualname_chain(fi.qualname)
        )
        tenant_keyed: bool | None = None  # computed lazily per function
        for node in iter_owned_nodes(fi.node):
            # -- leg 2: sanctioned lease sites (whole xaynet_tpu tree) ----
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _LEASE_CALLS
                and not lease_allowed
            ):
                line = fi.file.line(node.lineno)
                if suppressed("tenant", line):
                    continue
                msg = (
                    f"page lease ({node.func.attr}) outside the sanctioned "
                    f"sites (in '{fi.qualname}') — every lease site must "
                    "pair with a release for the leases == releases round "
                    "invariant (DESIGN §19); add the site to "
                    "tools/analysis/tenantscope.py LEASE_SITES with its "
                    "paired release, or annotate "
                    "'# lint: tenant-ok: <rationale>'"
                )
                if suppression_pending_rationale("tenant", line):
                    msg += " [suppression present but missing its rationale]"
                findings.append(Finding("tenant", rel, node.lineno, msg))
                continue
            # -- leg 4: sanctioned migration sites (whole xaynet_tpu tree)
            migration = None
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "set_migrator"
            ):
                migration = "set_migrator()"
            elif (
                isinstance(node, ast.Attribute)
                and node.attr == "migrator"
                and isinstance(node.ctx, (ast.Store, ast.Del))
            ):
                migration = ".migrator ="
            if migration is not None and not migration_allowed:
                line = fi.file.line(node.lineno)
                if suppressed("tenant", line):
                    continue
                msg = (
                    f"compaction migrator toggled ({migration}) outside the "
                    f"sanctioned sites (in '{fi.qualname}') — a migrator "
                    "marks a page run MOVABLE, so the site must prove the "
                    "buffer is quiescent while registered and pinned before "
                    "any access (DESIGN §23); add the site to "
                    "tools/analysis/tenantscope.py MIGRATION_SITES with its "
                    "quiescence rationale, or annotate "
                    "'# lint: tenant-ok: <rationale>'"
                )
                if suppression_pending_rationale("tenant", line):
                    msg += " [suppression present but missing its rationale]"
                findings.append(Finding("tenant", rel, node.lineno, msg))
                continue
            if not in_scope_tree:
                continue
            # -- leg 1: tenant key in scope ------------------------------
            scoped = None
            if isinstance(node, ast.Attribute) and node.attr in _SCOPED_ATTRS:
                # skip the dataclass field DEFINITIONS (AnnAssign targets
                # at class scope are not owned by any function, so they
                # never reach here anyway)
                scoped = node.attr
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _SCOPED_POOL_CALLS
            ):
                scoped = f"{node.func.attr}()"
            if scoped is None:
                continue
            if tenant_keyed is None:
                tenant_keyed = _has_tenant_key(fi)
            if tenant_keyed:
                continue
            line = fi.file.line(node.lineno)
            if suppressed("tenant", line):
                continue
            msg = (
                f"tenant-scoped state ({scoped}) read in '{fi.qualname}' "
                "with no tenant key in scope — thread the tenant id (or "
                "read it: self.tenant / shared.tenant) so the access is "
                "visibly scoped, or annotate "
                "'# lint: tenant-ok: <rationale>' (DESIGN §19)"
            )
            if suppression_pending_rationale("tenant", line):
                msg += " [suppression present but missing its rationale]"
            findings.append(Finding("tenant", rel, node.lineno, msg))
    return findings
