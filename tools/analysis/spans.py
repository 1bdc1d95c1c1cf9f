"""Span-discipline pass: tracing spans <-> docs/DESIGN.md §16 parity.

The tracing layer's correctness contract (docs/DESIGN.md §16) has three
machine-checkable legs, mirrored here as rule ``span``:

1. **context-manager enforcement** — every ``<tracer>.span(...)`` call
   must be a ``with``-item: the context manager is the ONLY construct that
   guarantees a span exit on every exception path. A bare call leaks an
   unfinished span (and, worse, never resets the ambient context).
   ``record_span`` (retroactive spans) is exempt by design — it records a
   finished span atomically.
2. **declare-once** — every span name is registered via
   ``declare_span("literal")`` exactly once across the tree (the runtime
   registry enforces this per process; the pass makes it a compile-time
   finding), and declarations must be string LITERALS so the table check
   below can see them.
3. **DESIGN-table parity** — the declared name set matches the §16 span
   table between ``<!-- span-table:begin -->`` / ``<!-- span-table:end -->``
   markers, both directions (the metrics-table cross-check idiom).
4. **usage parity** — a span declared ``declare_span(..., usage="word")``
   (whose CPU seconds, page faults and context switches its brackets read)
   carries that word in its row's ``Usage`` cell, and a row names no usage
   that the declaration does not have. The cell is ``-``, one word in
   backticks for every name of the row, or ``word: member, member; word:
   member`` where the names of a ``{...}`` group differ (a member is the
   part of the name after its last dot).

The pass is lexical + single-module-resolution only: a span-name argument
may be a literal (checked against the declared set) or a reference to a
module-level ``declare_span`` binding / table (trusted — the runtime check
in ``Tracer.span`` hard-fails an undeclared name either way).
"""

from __future__ import annotations

import ast
import re

from .cache import FileInfo
from .core import Finding, suppressed

_BEGIN = "<!-- span-table:begin -->"
_END = "<!-- span-table:end -->"
_TOKEN_RE = re.compile(r"`([a-z0-9_.{},]+)`")


def _expand(token: str) -> list[str]:
    """``phase.{sum,update}`` -> concrete names (metricscheck's shorthand)."""
    m = re.search(r"\{([^{}]*)\}", token)
    if m is None:
        return [token]
    before, group, after = token[: m.start()], m.group(1), token[m.end():]
    return [name for part in group.split(",") for name in _expand(before + part + after)]


def _table_rows(design_text: str):
    """``(line number, line)`` of the rows between the span-table markers."""
    active = False
    for i, line in enumerate(design_text.splitlines(), 1):
        if _BEGIN in line:
            active = True
        elif _END in line:
            active = False
        elif active and line.lstrip().startswith("|"):
            yield i, line


def documented(design_text: str) -> dict[str, int]:
    """span name -> first documenting line, from marked table rows."""
    out: dict[str, int] = {}
    for i, line in _table_rows(design_text):
        for token in _TOKEN_RE.findall(line):
            for name in _expand(token):
                if "." in name or name == "round":  # span names, not prose
                    out.setdefault(name, i)
    return out


_USAGE_RE = re.compile(r"`([a-z]+)`(?:\s*:\s*([a-z0-9_, ]+))?")


def documented_usage(design_text: str) -> dict[str, str]:
    """span name -> the usage word its row gives it (rows whose third cell
    is a usage cell; names with ``-`` or no word are left out)."""
    out: dict[str, str] = {}
    for _, line in _table_rows(design_text):
        cells = line.split("|")
        if len(cells) < 5:
            continue
        names = [n for token in _TOKEN_RE.findall(cells[1]) for n in _expand(token)]
        for word, members in _USAGE_RE.findall(cells[3]):
            chosen = {m.strip() for m in members.split(",") if m.strip()}
            for name in names:
                if not chosen or name.rsplit(".", 1)[-1] in chosen:
                    out[name] = word
    return out


def _is_declare_call(node: ast.Call) -> bool:
    func = node.func
    if isinstance(func, ast.Name):
        return func.id == "declare_span"
    return isinstance(func, ast.Attribute) and func.attr == "declare_span"


def _is_get_tracer(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    func = node.func
    if isinstance(func, ast.Name):
        return func.id == "get_tracer"
    return isinstance(func, ast.Attribute) and func.attr == "get_tracer"


class _ModuleScan(ast.NodeVisitor):
    """One module's declare sites, tracer span calls, and with-items."""

    def __init__(self):
        self.declares: list[tuple[str | None, int]] = []  # (literal name | None, line)
        self.usages: dict[str, tuple[str, int]] = {}  # name -> (usage word, line)
        self.span_calls: list[ast.Call] = []
        self.with_items: set[int] = set()  # id() of context expressions
        self._tracer_names: set[str] = set()

    def visit_Assign(self, node: ast.Assign) -> None:
        if _is_get_tracer(node.value):
            for target in node.targets:
                if isinstance(target, ast.Name):
                    self._tracer_names.add(target.id)
        self.generic_visit(node)

    def visit_With(self, node: ast.With) -> None:
        for item in node.items:
            self.with_items.add(id(item.context_expr))
        self.generic_visit(node)

    def visit_AsyncWith(self, node: ast.AsyncWith) -> None:
        for item in node.items:
            self.with_items.add(id(item.context_expr))
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if _is_declare_call(node):
            name = None
            if node.args and isinstance(node.args[0], ast.Constant) and isinstance(
                node.args[0].value, str
            ):
                name = node.args[0].value
            self.declares.append((name, node.lineno))
            for kw in node.keywords:
                if kw.arg == "usage" and isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, str
                ) and name is not None:
                    self.usages[name] = (kw.value.value, node.lineno)
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "span":
            value = func.value
            if _is_get_tracer(value) or (
                isinstance(value, ast.Name) and value.id in self._tracer_names
            ):
                self.span_calls.append(node)
        self.generic_visit(node)


def run(files: list[FileInfo], design_path) -> list[Finding]:
    findings: list[Finding] = []
    declares: dict[str, list[tuple[str, int]]] = {}  # name -> [(rel, line)]
    usages: dict[str, tuple[str, str, int]] = {}  # name -> (usage word, rel, line)
    scans: list[tuple[FileInfo, _ModuleScan]] = []
    for info in files:
        if info.tree is None or not info.rel.startswith("xaynet_tpu/"):
            continue
        scan = _ModuleScan()
        scan.visit(info.tree)
        scans.append((info, scan))
        for name, (word, line) in scan.usages.items():
            usages[name] = (word, info.rel, line)
        for name, line in scan.declares:
            if name is None:
                if not suppressed("span", info.line(line)):
                    findings.append(
                        Finding(
                            "span",
                            info.rel,
                            line,
                            "declare_span argument must be a string literal "
                            "(the DESIGN §16 table check reads it statically)",
                        )
                    )
                continue
            declares.setdefault(name, []).append((info.rel, line))

    for name, sites in sorted(declares.items()):
        for rel, line in sites[1:]:
            findings.append(
                Finding(
                    "span",
                    rel,
                    line,
                    f"span name '{name}' is declared more than once (first in "
                    f"{sites[0][0]}) — one module owns a span name; import "
                    "its constant instead",
                )
            )

    for info, scan in scans:
        for call in scan.span_calls:
            if id(call) not in scan.with_items:
                if suppressed("span", info.line(call.lineno)):
                    continue
                findings.append(
                    Finding(
                        "span",
                        info.rel,
                        call.lineno,
                        "tracer span() must be used as a `with` item — the "
                        "context manager is what guarantees the exit on "
                        "every exception path (DESIGN §16)",
                    )
                )
                continue
            if call.args and isinstance(call.args[0], ast.Constant):
                name = call.args[0].value
                if isinstance(name, str) and name not in declares:
                    if not suppressed("span", info.line(call.lineno)):
                        findings.append(
                            Finding(
                                "span",
                                info.rel,
                                call.lineno,
                                f"span name '{name}' is used but never "
                                "declared via declare_span",
                            )
                        )

    try:
        design_text = design_path.read_text()
    except OSError:
        findings.append(Finding("span", "docs/DESIGN.md", 1, "docs/DESIGN.md is unreadable"))
        return findings
    docs = documented(design_text)
    if not docs:
        findings.append(
            Finding(
                "span",
                "docs/DESIGN.md",
                1,
                "no marked span table found (expected "
                f"'{_BEGIN}' ... '{_END}' around the §16 span table)",
            )
        )
        return findings
    for name, sites in sorted(declares.items()):
        if name not in docs:
            rel, line = sites[0]
            findings.append(
                Finding(
                    "span",
                    rel,
                    line,
                    f"span '{name}' is not in the DESIGN.md §16 span table "
                    "(add a row inside the span-table markers)",
                )
            )
    for name, line in sorted(docs.items()):
        if name not in declares:
            findings.append(
                Finding(
                    "span",
                    "docs/DESIGN.md",
                    line,
                    f"documented span '{name}' is not declared anywhere "
                    "under xaynet_tpu/ (stale table row?)",
                )
            )
    told = documented_usage(design_text)
    for name, (word, rel, line) in sorted(usages.items()):
        if name in docs and told.get(name) != word:
            findings.append(
                Finding(
                    "span",
                    rel,
                    line,
                    f"span '{name}' is declared usage='{word}' and its row of the "
                    f"DESIGN.md §16 span table says '{told.get(name, '-')}' in the "
                    "Usage cell",
                )
            )
    for name, word in sorted(told.items()):
        if name in declares and name not in usages:
            findings.append(
                Finding(
                    "span",
                    "docs/DESIGN.md",
                    docs.get(name, 1),
                    f"the span table gives '{name}' the usage '{word}' and its "
                    "declaration has none",
                )
            )
    return findings
