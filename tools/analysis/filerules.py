"""The per-file rules — the pre-framework ``tools/lint.py`` checks, ported.

Each rule keeps its original message text (CI logs, the older tests and
muscle memory all grep for it) and its original suppression annotation;
the framework only adds the shared parse (:class:`cache.FileInfo`), rule
names for the baseline, and JSON output.

The two name-prefix host-sync heuristics (``_WORKER_SYNC_PREFIXES`` under
``xaynet_tpu/parallel`` and ``_prog*`` under ``xaynet_tpu/sim``) stay here
as fast lexical checks; their known false negative — helpers defined
*outside* the prefixed function but called from it — is closed by the
call-graph pass in :mod:`purity`, which shares the ``sync`` rule and the
``# lint: sync-ok`` annotation.
"""

from __future__ import annotations

import ast

from .cache import FileInfo
from .core import Finding, suppressed

MAX_LINE = 120


class _ImportVisitor(ast.NodeVisitor):
    """Collects module-scope imports and every name used anywhere."""

    def __init__(self):
        self.imports: dict[str, tuple[int, str]] = {}  # local name -> (line, display)
        self.used: set[str] = set()
        self.star_imports: list[int] = []

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            local = alias.asname or alias.name.split(".")[0]
            if alias.asname == alias.name:
                continue  # `import x as x` is an explicit re-export
            self.imports[local] = (node.lineno, alias.name)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            return
        for alias in node.names:
            if alias.name == "*":
                self.star_imports.append(node.lineno)
                continue
            if alias.asname == alias.name:
                continue  # explicit re-export idiom
            local = alias.asname or alias.name
            self.imports[local] = (node.lineno, alias.name)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        # record the root name of attribute chains (module.attr)
        root = node
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name):
            self.used.add(root.id)
        self.generic_visit(node)


def _used_in_annotations(tree: ast.AST) -> set[str]:
    """Names referenced inside *string* type annotations (``x: "Foo"``).

    Only annotation positions count — a module name mentioned in a docstring
    or assert message must NOT exempt a dead import.
    """
    out: set[str] = set()

    def collect(ann) -> None:
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            try:
                expr = ast.parse(ann.value, mode="eval")
            except SyntaxError:
                return
            for n in ast.walk(expr):
                if isinstance(n, ast.Name):
                    out.add(n.id)

    for node in ast.walk(tree):
        if isinstance(node, ast.AnnAssign):
            collect(node.annotation)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            collect(node.returns)
            for arg in (
                node.args.args + node.args.posonlyargs + node.args.kwonlyargs
                + ([node.args.vararg] if node.args.vararg else [])
                + ([node.args.kwarg] if node.args.kwarg else [])
            ):
                collect(arg.annotation)
    return out


def _is_unbounded_queue(node: ast.Call) -> bool:
    """True for ``asyncio.Queue()`` / ``Queue()`` constructed without a size,
    or with a literal non-positive one (asyncio treats ``maxsize <= 0`` as
    unbounded). Non-constant sizes are trusted — the rule is syntactic."""
    func = node.func
    if isinstance(func, ast.Attribute):
        is_queue = func.attr == "Queue" and (
            isinstance(func.value, ast.Name) and func.value.id == "asyncio"
        )
    elif isinstance(func, ast.Name):
        is_queue = func.id == "Queue"
    else:
        is_queue = False
    if not is_queue:
        return False
    size = node.args[0] if node.args else None
    if size is None:
        for kw in node.keywords:
            if kw.arg == "maxsize":
                size = kw.value
                break
    if size is None:
        return True
    if isinstance(size, ast.Constant) and isinstance(size.value, (int, float)):
        return size.value <= 0
    if isinstance(size, ast.UnaryOp) and isinstance(size.op, ast.USub):
        return isinstance(size.operand, ast.Constant)
    return False


def _is_silent_broad_swallow(node: ast.ExceptHandler) -> bool:
    """True for a handler that (a) catches Exception/BaseException —
    directly or inside a tuple — and (b) whose body does nothing but
    ``pass``/``...``/``continue``. Narrow handlers and handlers that log,
    meter, assign or re-raise are fine."""

    def names(t) -> list:
        if t is None:
            return []
        if isinstance(t, ast.Tuple):
            return [n for elt in t.elts for n in names(elt)]
        if isinstance(t, ast.Name):
            return [t.id]
        if isinstance(t, ast.Attribute):
            return [t.attr]
        return []

    if not any(n in ("Exception", "BaseException") for n in names(node.type)):
        return False
    for stmt in node.body:
        if isinstance(stmt, ast.Pass) or isinstance(stmt, ast.Continue):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue  # docstring / Ellipsis
        return False
    return True


# transport entry points that bypass the resilient client wrapper when
# called directly from SDK code
_RAW_HTTP_CALLEES = frozenset(
    {"urlopen", "urlretrieve", "open_connection", "create_connection", "socket"}
)


def _is_raw_http_call(node: ast.Call) -> bool:
    """True for direct transport constructions (urllib/socket/asyncio
    streams) — syntactic, like the queue rule: any spelling that resolves
    to one of the raw entry points counts."""
    func = node.func
    if isinstance(func, ast.Attribute):
        return func.attr in _RAW_HTTP_CALLEES
    return isinstance(func, ast.Name) and func.id in _RAW_HTTP_CALLEES


# fold entry points that bypass the EdgeAggregator accounting path when
# called directly from edge code: a modular add without the matching
# member/seed-dict accounting ships an envelope whose nb_models disagrees
# with its content and breaks the coordinator's nb_models == seed-watermark
# unmask invariant (docs/DESIGN.md §11)
_FOLD_CALLEES = frozenset(
    {
        "aggregate",
        "aggregate_batch",
        "aggregate_partial",
        "fold_partial",
        "mod_add",
        "batch_mod_sum",
        "fold_wire_batch_host",
        "masked_add",
    }
)


def _is_fold_call(node: ast.Call) -> bool:
    """True for any spelling that resolves to a masked-add/fold entry point
    (syntactic, like the queue rule)."""
    func = node.func
    if isinstance(func, ast.Attribute):
        return func.attr in _FOLD_CALLEES
    return isinstance(func, ast.Name) and func.id in _FOLD_CALLEES


# fold-worker call-graph function-name prefixes under xaynet_tpu/parallel:
# the producers (submit_*), the per-batch/per-shard fold paths (_fold*,
# fold*, _credit, _dispatch*, _retry*, _shard*), and the worker loops
# (_process*, _worker*). drain()/_drain* are the sanctioned sync points and
# deliberately NOT listed. (Lexical fast path; the reachability closure
# lives in tools/analysis/purity.py.)
_WORKER_SYNC_PREFIXES = (
    "_process",
    "_fold",
    "fold",
    "_dispatch",
    "_credit",
    "_retry",
    "_shard",
    "_worker",
    "submit",
    "_submit",
)

# host-blocking entry points: np.asarray materializes a device value on the
# host; block_until_ready is an explicit device barrier
_SYNC_CALLEES = frozenset({"asarray", "block_until_ready"})

# simulation program bodies: functions with these name prefixes under
# xaynet_tpu/sim are jitted whole-round program code — pure traced JAX
_SIM_PROGRAM_PREFIXES = ("_prog",)

# Python-int limb math: pulls group elements out of the graph one integer
# at a time (the pattern the in-graph simulation exists to eliminate)
_HOST_INT_CALLEES = frozenset(
    {"limbs_to_int", "limbs_to_ints", "int_to_limbs", "ints_to_limbs", "item", "tolist", "int"}
)


def _is_host_roundtrip(node: ast.Call) -> bool:
    """True for host syncs AND Python-int limb math (syntactic, any
    spelling that resolves to one of the entry points)."""
    if _is_blocking_sync(node):
        return True
    func = node.func
    if isinstance(func, ast.Attribute):
        return func.attr in _HOST_INT_CALLEES
    return isinstance(func, ast.Name) and func.id in _HOST_INT_CALLEES


def _is_blocking_sync(node: ast.Call) -> bool:
    """True for any spelling of ``np.asarray(...)`` /
    ``jax.block_until_ready(...)`` / ``x.block_until_ready()`` (syntactic,
    like the other rules)."""
    func = node.func
    if isinstance(func, ast.Attribute):
        return func.attr in _SYNC_CALLEES
    return isinstance(func, ast.Name) and func.id in _SYNC_CALLEES


def _is_width_expr(node: ast.BinOp) -> bool:
    """True for the hand-computed width idioms ``(x + 7) // 8`` (bits/bytes
    -> bytes) and ``(x + 3) // 4`` (bytes -> uint32 limbs) — the two
    expressions the codec module (``ops/limbs.py``) owns. Purely
    syntactic, commutative in the addition."""
    if not isinstance(node.op, ast.FloorDiv):
        return False
    if not (isinstance(node.right, ast.Constant) and node.right.value in (4, 8)):
        return False
    want = 7 if node.right.value == 8 else 3
    left = node.left
    if not (isinstance(left, ast.BinOp) and isinstance(left.op, ast.Add)):
        return False
    return (
        isinstance(left.right, ast.Constant) and left.right.value == want
    ) or (isinstance(left.left, ast.Constant) and left.left.value == want)


def _is_device_put(node: ast.Call) -> bool:
    """True for ``jax.device_put(...)`` / ``device_put(...)`` calls (the
    rule is syntactic, like the queue rule: any spelling that resolves to
    the jax transfer entry point counts)."""
    func = node.func
    if isinstance(func, ast.Attribute):
        return func.attr == "device_put"
    return isinstance(func, ast.Name) and func.id == "device_put"


_WIRECOPY_PAYLOAD_NAMES = frozenset(
    {"body", "payload", "raw", "blob", "buf", "wire", "msg", "message"}
)


def _wire_copy_kind(node: ast.AST) -> str | None:
    """Classify whole-body copy idioms on the ingress path: ``bytes()`` /
    ``bytearray()`` materializations, ``.tobytes()`` exports, and
    slice-copies of payload-named buffers (slicing ``bytes`` copies; the
    zero-copy spelling slices a ``memoryview``, which doesn't)."""
    if isinstance(node, ast.Call):
        func = node.func
        if (
            isinstance(func, ast.Name)
            and func.id in ("bytes", "bytearray")
            and node.args
        ):
            return f"{func.id}() materialization"
        if isinstance(func, ast.Attribute) and func.attr == "tobytes":
            return ".tobytes() export"
    if isinstance(node, ast.Subscript) and isinstance(node.slice, ast.Slice):
        target = node.value
        name = (
            target.id
            if isinstance(target, ast.Name)
            else target.attr if isinstance(target, ast.Attribute) else ""
        )
        low = name.lower()
        if low in _WIRECOPY_PAYLOAD_NAMES or any(
            low.endswith("_" + n) for n in _WIRECOPY_PAYLOAD_NAMES
        ):
            return f"slice-copy of payload buffer '{name}'"
    return None


def check_file_info(info: FileInfo) -> list[Finding]:
    """Run every per-file rule over one parsed file."""
    problems: list[Finding] = list(info.problems)
    rel = info.rel
    if info.text is None:
        return problems
    text = info.text

    def add(rule: str, line: int, message: str) -> None:
        problems.append(Finding(rule, rel, line, message))

    # --- format-level checks ----------------------------------------------
    generated = "generated by" in text[:200]
    if text and not text.endswith("\n"):
        add("fmt", text.count(chr(10)) + 1, "missing final newline")
    for i, line in enumerate(text.splitlines(), 1):
        stripped = line.rstrip("\n")
        indent = stripped[: len(stripped) - len(stripped.lstrip())]
        if "\t" in indent:
            add("fmt", i, "tab in indentation")
        if stripped != stripped.rstrip():
            add("fmt", i, "trailing whitespace")
        if len(stripped) > MAX_LINE and "http" not in stripped and not generated:
            add("fmt", i, f"line longer than {MAX_LINE} chars ({len(stripped)})")

    # --- AST checks --------------------------------------------------------
    tree = info.tree
    if tree is None:
        return problems

    visitor = _ImportVisitor()
    visitor.visit(tree)

    for line in visitor.star_imports:
        add("star-import", line, "star import")

    if info.path.name != "__init__.py":  # __init__ files are re-export indexes
        exported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        if isinstance(node.value, (ast.List, ast.Tuple)):
                            for elt in node.value.elts:
                                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                                    exported.add(elt.value)
        string_refs = _used_in_annotations(tree)
        for name, (line, display) in sorted(visitor.imports.items()):
            if name in visitor.used or name in exported or name in string_refs:
                continue
            add("unused-import", line, f"unused import '{display}'")

    # hot-path trees: raw perf_counter timing bypasses the telemetry layer
    hot_path = rel.startswith(("xaynet_tpu/parallel", "xaynet_tpu/server"))
    # coordinator queue trees: unbounded queues defeat admission control
    bounded_tree = rel.startswith(
        ("xaynet_tpu/server", "xaynet_tpu/ingest", "xaynet_tpu/edge")
    )
    # edge tree: every fold must flow through the EdgeAggregator accounting
    # path (admit/seal), never a direct masked_add
    edge_tree = rel.startswith("xaynet_tpu/edge")
    # coordinator/storage trees: silent broad swallows hide infrastructure
    # failures from the resilience layer and the operator
    no_swallow_tree = rel.startswith(("xaynet_tpu/server", "xaynet_tpu/storage"))
    # SDK tree: raw transports bypass the resilient client wrapper
    sdk_tree = rel.startswith("xaynet_tpu/sdk")
    # width rule: every wire/pack width must come from the codec module
    # (ops/limbs.py — wire_width_for / draw_width_for / n_limbs_for_bytes);
    # a hand-computed copy drifting from the codec is exactly how a packed
    # plane and its unpack disagree by one byte
    width_tree = (
        rel.startswith("xaynet_tpu/") and rel != "xaynet_tpu/ops/limbs.py"
    )
    # ingress path: request bodies must stay zero-copy memoryview views
    # from socket read to staging — a stray bytes()/tobytes()/slice copy
    # doubles the per-update byte traffic the packed wire exists to cut
    wirecopy_tree = (
        rel.startswith("xaynet_tpu/ingest/") or rel == "xaynet_tpu/server/rest.py"
    )

    line_of = info.line

    # sim tree: host round-trips inside jitted program bodies reintroduce
    # the per-phase host syncs the in-graph round exists to eliminate
    if rel.startswith("xaynet_tpu/sim"):
        flagged_sim: set[int] = set()
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not fn.name.startswith(_SIM_PROGRAM_PREFIXES):
                continue
            for node in ast.walk(fn):
                if (
                    isinstance(node, ast.Call)
                    and _is_host_roundtrip(node)
                    and node.lineno not in flagged_sim
                ):
                    flagged_sim.add(node.lineno)
                    if not suppressed("sync", line_of(node.lineno)):
                        add(
                            "sync",
                            node.lineno,
                            f"host round-trip in sim program "
                            f"body '{fn.name}' (np.asarray/block_until_ready/"
                            "Python-int limb math must stay outside jitted round "
                            "programs; move it to the host boundary or annotate a "
                            "deliberate materialization with '# lint: sync-ok')",
                        )

    # parallel tree: blocking host syncs inside fold-worker code paths
    # serialize the pipeline overlap; drain() is the sanctioned sync point
    if rel.startswith("xaynet_tpu/parallel"):
        flagged: set[int] = set()
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not fn.name.startswith(_WORKER_SYNC_PREFIXES):
                continue
            for node in ast.walk(fn):
                if (
                    isinstance(node, ast.Call)
                    and _is_blocking_sync(node)
                    and node.lineno not in flagged
                ):
                    flagged.add(node.lineno)
                    if not suppressed("sync", line_of(node.lineno)):
                        add(
                            "sync",
                            node.lineno,
                            f"blocking host sync in fold-worker "
                            f"code path '{fn.name}' (synchronize in drain(), or "
                            "annotate a deliberate transfer barrier / host-kernel "
                            "materialization with '# lint: sync-ok')",
                        )

    for node in ast.walk(tree):
        if hot_path and isinstance(node, ast.Call):
            func = node.func
            callee = (
                func.attr
                if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else ""
            )
            if callee == "perf_counter":
                if not suppressed("telemetry", line_of(node.lineno)):
                    add(
                        "telemetry",
                        node.lineno,
                        "raw perf_counter timing bypasses the "
                        "telemetry registry (use xaynet_tpu.telemetry.profiling or a "
                        "registry histogram timer)",
                    )
        if bounded_tree and isinstance(node, ast.Call) and _is_unbounded_queue(node):
            if not suppressed("unbounded", line_of(node.lineno)):
                add(
                    "unbounded",
                    node.lineno,
                    "unbounded asyncio.Queue() in the "
                    "coordinator tree (pass a maxsize, or annotate a deliberate "
                    "sentinel/upstream-bounded channel with '# lint: unbounded-ok')",
                )
        if sdk_tree and isinstance(node, ast.Call) and _is_raw_http_call(node):
            if not suppressed("raw-http", line_of(node.lineno)):
                add(
                    "raw-http",
                    node.lineno,
                    "raw HTTP/socket call in the SDK tree "
                    "bypasses the resilient client wrapper (route coordinator "
                    "traffic through sdk.client.HttpClient/ResilientClient, or "
                    "annotate the transport itself with '# lint: raw-http-ok')",
                )
        if edge_tree and isinstance(node, ast.Call) and _is_fold_call(node):
            if not suppressed("fold", line_of(node.lineno)):
                add(
                    "fold",
                    node.lineno,
                    "direct masked_add/fold call in the edge "
                    "tree bypasses the partial-aggregate accounting path (fold "
                    "through EdgeAggregator.admit/seal, or annotate the accounting "
                    "path's own fold site with '# lint: fold-ok')",
                )
        if width_tree and isinstance(node, ast.BinOp) and _is_width_expr(node):
            if not suppressed("width", line_of(node.lineno)):
                add(
                    "width",
                    node.lineno,
                    "hand-computed wire/pack width expression "
                    "(use ops.limbs.wire_width_for / draw_width_for / "
                    "n_limbs_for_bytes — the codec module is the single "
                    "source of truth — or annotate a non-wire byte-length "
                    "computation with '# lint: width-ok')",
                )
        if wirecopy_tree:
            kind = _wire_copy_kind(node)
            if kind is not None and not suppressed("wirecopy", line_of(node.lineno)):
                add(
                    "wirecopy",
                    node.lineno,
                    f"whole-body copy on the ingress path ({kind}) — "
                    "request payloads must stay zero-copy memoryview views "
                    "end to end; annotate a deliberate boundary "
                    "materialization with '# lint: wirecopy-ok'",
                )
        if bounded_tree and isinstance(node, ast.Call) and _is_device_put(node):
            if not suppressed("device-put", line_of(node.lineno)):
                add(
                    "device-put",
                    node.lineno,
                    "direct jax.device_put in the coordinator "
                    "tree (stage update batches through the streaming pipeline's "
                    "buffer ring — parallel.streaming — or annotate a deliberate "
                    "non-update-tensor upload with '# lint: device-put-ok')",
                )
        if (
            no_swallow_tree
            and isinstance(node, ast.ExceptHandler)
            and _is_silent_broad_swallow(node)
        ):
            if not suppressed("swallow", line_of(node.lineno)):
                add(
                    "swallow",
                    node.lineno,
                    "silent broad-exception swallow in the "
                    "coordinator/storage tree (log, meter, retry or re-raise — or "
                    "annotate a deliberate best-effort cleanup with "
                    "'# lint: swallow-ok')",
                )
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for default in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None
            ]:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                    add(
                        "mutable-default",
                        default.lineno,
                        f"mutable default argument in '{node.name}'",
                    )
        elif isinstance(node, ast.ExceptHandler) and node.type is None:
            add("bare-except", node.lineno, "bare 'except:'")
        elif isinstance(node, ast.Dict):
            seen: set[object] = set()
            for key in node.keys:
                if isinstance(key, ast.Constant):
                    marker = (type(key.value).__name__, key.value)
                    if marker in seen:
                        add("dup-key", key.lineno, f"duplicate dict key {key.value!r}")
                    seen.add(marker)
    return problems
