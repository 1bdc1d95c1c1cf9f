"""Analysis driver: discovery, parallel per-file stage, deep passes,
baseline gating, ``--changed`` mode, JSON/human output.

``tools/lint.py`` is the CLI entry point (the tier-1/CI invocation is
unchanged); it delegates here. Flow:

1. discover files (the classic lint targets);
2. per-file rules, in parallel, through the persistent result cache
   (content-hash keyed, invalidated by the analyzer's own digest);
3. when the run covers the default full tree: the four deep passes
   (lock discipline, call-graph purity, accounting invariants, metrics
   cross-check), memoized as one unit keyed by the whole-tree digest;
4. baseline split: baselined findings report as *masked* and don't fail
   the gate; everything else does.

``--changed`` restricts *reporting and per-file work* to files differing
from the merge-base with the upstream (or the working-tree diff when
there is no upstream); the deep passes still see the whole tree — they
are cross-file by definition — but their findings are filtered the same
way, and the caches keep the whole thing fast.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

from . import filerules, invariants, locks, metricscheck, purity, spans, taint, tenantscope
from .cache import ResultCache, SourceCache
from .callgraph import CallGraph, SymbolTable
from .core import Baseline, Finding

DEFAULT_TARGETS = [
    "xaynet_tpu",
    "tests",
    "tools",
    "examples",
    "__graft_entry__.py",
    "conftest.py",
]

CACHE_NAME = ".lint-cache.json"
BASELINE_PATH = Path(__file__).resolve().parent / "baseline.json"


def discover(repo: Path, targets: list[str] | None) -> list[Path]:
    files: list[Path] = []
    for t in targets or DEFAULT_TARGETS:
        p = (repo / t) if not Path(t).is_absolute() else Path(t)
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        elif p.exists():
            files.append(p)
    return files


def changed_files(repo: Path) -> set[str] | None:
    """Repo-relative paths differing from the upstream merge-base, plus
    working-tree modifications; None when git is unavailable (treat
    everything as changed)."""
    def git(*args: str) -> str | None:
        try:
            res = subprocess.run(
                ["git", *args], cwd=repo, capture_output=True, text=True, timeout=30
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        return res.stdout if res.returncode == 0 else None

    base = None
    for upstream in ("@{upstream}", "origin/main", "origin/master"):
        out = git("merge-base", "HEAD", upstream)
        if out:
            base = out.strip()
            break
    changed: set[str] = set()
    diff = git("diff", "--name-only", base) if base else git("diff", "--name-only", "HEAD")
    if diff is None:
        return None
    changed.update(line.strip() for line in diff.splitlines() if line.strip())
    status = git("status", "--porcelain")
    if status:
        for line in status.splitlines():
            parts = line[3:].split(" -> ")
            changed.add(parts[-1].strip().strip('"'))
    return changed


def _file_worker(args: tuple[str, str]) -> list[dict]:
    """Process-pool leg of the per-file stage: parse + run the per-file
    rules for one path, returning JSON-able findings (module-level so it
    pickles; each worker re-reads the file, which is what makes the stage
    embarrassingly parallel)."""
    repo, path = args
    from .cache import FileInfo  # local import: cheap in forked workers

    info = FileInfo(Path(repo), Path(path))
    return [f.to_json() for f in filerules.check_file_info(info)]


class Analyzer:
    def __init__(self, repo: Path, use_cache: bool = True, jobs: int = 0):
        self.repo = Path(repo)
        self.sources = SourceCache(self.repo)
        self.results = ResultCache(self.repo / CACHE_NAME, enabled=use_cache)
        self.jobs = jobs or min(8, os.cpu_count() or 1)

    # -- per-file stage ----------------------------------------------------

    def file_findings(self, paths: list[Path]) -> list[Finding]:
        """Per-file rules through the result cache; cache misses fan out to
        a PROCESS pool (ast.parse + AST walks are GIL-bound, so threads buy
        nothing). Cache reads/writes stay on this process. Any pool failure
        falls back to the serial loop."""
        out: list[Finding] = []
        misses: list[Path] = []
        for path in paths:
            info = self.sources.get(path)
            cached = self.results.get_file(info.rel, info.content_key)
            if cached is not None:
                out.extend(cached)
            else:
                misses.append(path)

        def serial(path: Path) -> list[Finding]:
            info = self.sources.get(path)
            found = filerules.check_file_info(info)
            self.results.put_file(info.rel, info.content_key, found)
            return found

        if self.jobs > 1 and len(misses) > 8:
            results: list[list[dict]] | None = None
            try:
                with concurrent.futures.ProcessPoolExecutor(self.jobs) as pool:
                    results = list(
                        pool.map(
                            _file_worker,
                            [(str(self.repo), str(p)) for p in misses],
                            chunksize=8,
                        )
                    )
            except (OSError, concurrent.futures.process.BrokenProcessPool):
                results = None  # sandboxed/fork-less environments: go serial
            if results is not None:
                for path, objs in zip(misses, results):
                    found = [Finding.from_json(o) for o in objs]
                    info = self.sources.get(path)
                    self.results.put_file(info.rel, info.content_key, found)
                    out.extend(found)
                return out
        for path in misses:
            out.extend(serial(path))
        return out

    # -- deep passes -------------------------------------------------------

    def project_findings(self, paths: list[Path]) -> list[Finding]:
        design = self.repo / "docs" / "DESIGN.md"
        h = hashlib.sha1()
        infos = []
        for path in paths:
            info = self.sources.get(path)
            # the deep passes reason about the production tree; tests and
            # tooling would double the graph for zero rule surface
            if info.rel.startswith("xaynet_tpu/"):
                infos.append(info)
                h.update(info.rel.encode())
                h.update(info.content_key.encode())
        if design.exists():
            h.update(design.read_bytes())
        tree_key = h.hexdigest()
        cached = self.results.get_project(tree_key)
        if cached is not None:
            return cached
        symbols = SymbolTable(infos)
        graph = CallGraph(symbols)
        findings = []
        findings.extend(locks.run(graph))
        findings.extend(purity.run(graph))
        findings.extend(invariants.run(graph))
        findings.extend(tenantscope.run(graph))
        findings.extend(taint.run(graph, design))
        findings.extend(metricscheck.run(infos, design))
        findings.extend(spans.run(infos, design))
        self.results.put_project(tree_key, findings)
        return findings


def run(
    repo: Path,
    targets: list[str] | None = None,
    *,
    strict: bool = False,
    changed: bool = False,
    jobs: int = 0,
    use_cache: bool = True,
    json_out: bool = False,
    update_baseline: bool = False,
    deep: bool | None = None,
    baseline_path: Path | None = None,
) -> int:
    baseline_file = Path(baseline_path) if baseline_path else BASELINE_PATH
    if update_baseline and changed:
        # a baseline recorded from a filtered view would silently DROP
        # every entry outside the diff; the next --strict run then fails
        # on findings that were deliberately baselined
        print(
            "--update-baseline records what this invocation analyzed; "
            "combine it with the full tree, not --changed",
            file=sys.stderr,
        )
        return 2
    analyzer = Analyzer(repo, use_cache=use_cache, jobs=jobs)
    full_tree = not targets
    paths = discover(repo, list(targets) if targets else None)
    all_paths = paths if full_tree else None  # one tree walk, reused below

    report_set: set[str] | None = None
    if changed and not strict:
        rels = changed_files(repo)
        if rels is not None:
            report_set = rels
            # per-file work shrinks to the diff; the deep passes below
            # still see the whole tree (they are cross-file by definition)
            paths = [
                p for p in paths if p.relative_to(repo).as_posix() in report_set
            ]

    findings = analyzer.file_findings(paths)
    # the deep passes are cross-file: they run on full-tree invocations
    # (CI, the bare default) and are skipped when linting an explicit
    # subset, where a partial view would fabricate drift findings
    if deep if deep is not None else full_tree:
        findings.extend(
            analyzer.project_findings(
                all_paths if all_paths is not None else discover(repo, None)
            )
        )
    analyzer.results.save()

    if report_set is not None:
        findings = [
            f for f in findings if f.file in report_set or f.file == "docs/DESIGN.md"
        ]

    if update_baseline:
        Baseline.write(baseline_file, findings)
        print(
            f"baseline: recorded {len(findings)} finding(s) to {baseline_file}",
            file=sys.stderr,
        )
        return 0

    baseline = Baseline.load(baseline_file)
    new, masked = baseline.split(findings)

    if json_out:
        print(
            json.dumps(
                {
                    "files": len(paths),
                    "findings": [f.to_json() for f in new],
                    "masked": [f.to_json() for f in masked],
                    "strict": strict,
                },
                indent=2,
            )
        )
    else:
        for f in new:
            print(f.legacy())
    summary = f"lint: {len(paths)} files, {len(new)} problems"
    if masked:
        summary += f" ({len(masked)} baselined)"
    print(summary, file=sys.stderr)
    return 1 if new else 0


def main(argv: list[str], repo: Path) -> int:
    ap = argparse.ArgumentParser(
        prog="tools/lint.py",
        description=(
            "pass-based static analysis gate (tools/analysis/): per-file "
            "hygiene rules plus the cross-file deep passes — lock "
            "discipline, host-sync purity, accounting invariants, "
            "metrics/span DESIGN parity, and the secret-flow taint pass "
            "(rule 'taint': key material must not reach logs, span attrs, "
            "metric labels, JSON dumps, flight-recorder payloads or raised "
            "exception messages; suppress with '# lint: taint-ok: "
            "<rationale>' — docs/DESIGN.md §18)"
        ),
    )
    ap.add_argument("paths", nargs="*", help="files/dirs (default: the repo tree)")
    ap.add_argument(
        "--strict",
        action="store_true",
        help="the CI gate: always the full tree + all passes (--changed and "
        "path filtering ignored); the baseline applies in every mode",
    )
    ap.add_argument(
        "--changed",
        action="store_true",
        help="report only files differing from the upstream merge-base",
    )
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument("--jobs", type=int, default=0, help="parallel file analysis width")
    ap.add_argument("--no-cache", action="store_true", help="ignore and don't write the result cache")
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="record current findings as the accepted baseline",
    )
    args = ap.parse_args(argv)
    if args.update_baseline and (args.paths or args.changed):
        ap.error(
            "--update-baseline records the FULL tree; drop --changed/paths "
            "(a baseline written from a filtered view would discard every "
            "entry outside it)"
        )
    targets = args.paths or None
    if args.strict:
        targets = None  # the gate always sees the whole tree
    return run(
        repo,
        targets,
        strict=args.strict,
        changed=args.changed,
        jobs=args.jobs,
        use_cache=not args.no_cache,
        json_out=args.json,
        update_baseline=args.update_baseline,
    )
