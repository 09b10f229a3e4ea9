"""Analysis driver: discover files, parse each exactly once, run every
registered rule, apply the baseline, render text or JSON.

Two modes:

- **repo mode** (no paths given): scans the repo's source roots with the
  checked-in ``tools/analysis/baseline.json``, the JX rules rooted at
  the serving hot path (serve/, models/, ops/, parallel/) and the CC
  rules scoped to serve/ + obs/;
- **explicit-path mode** (paths given, e.g. the test fixture corpus):
  scans every ``*.py`` under the given paths with no scoping and no
  baseline unless ``--baseline`` is passed.
"""

from __future__ import annotations

import argparse
import ast
import json
import time
from dataclasses import dataclass, field
from pathlib import Path

from tools.analysis import baseline as baseline_mod
from tools.analysis import rules as _rules  # noqa: PY01 — registers rules
from tools.analysis.engine import (
    FileContext, Finding, ProjectContext, RULES, parse_suppressions, run_rules,
)

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
ROOTS = ("igaming_platform_tpu", "tests", "tools")
TOP_FILES = ("__graft_entry__.py", "chip_smoke.py")
# proto_gen is generated; the fixture corpus under tests/ is a zoo of
# deliberate violations the driver must not trip over in repo mode.
EXCLUDED_PARTS = {"proto_gen", "fixtures"}

REPO_CONFIG = {
    "jx_scope": (
        "igaming_platform_tpu/serve/", "igaming_platform_tpu/models/",
        "igaming_platform_tpu/ops/", "igaming_platform_tpu/parallel/",
    ),
    "cc_scope": ("igaming_platform_tpu/serve/", "igaming_platform_tpu/obs/"),
    # JX07 sharding discipline: jit roots must take the big state tables
    # (feature table / session ring / served params) as traced arguments
    # with explicit layouts — scoped to where those tables live.
    "jx07_scope": (
        "igaming_platform_tpu/serve/", "igaming_platform_tpu/models/",
    ),
    # CC07 param-mutation discipline: anywhere a served param tree could
    # be rebound — the serving layer, the training/promotion side, and
    # the harnesses that assemble engines.
    "paramswap_scope": (
        "igaming_platform_tpu/serve/", "igaming_platform_tpu/train/",
        "tools/",
    ),
    # CC08 session-state-mutation discipline: anywhere the session ring
    # state could be rebound — the serving layer plus the harnesses and
    # tools that assemble session-enabled engines.
    "sessionstate_scope": (
        "igaming_platform_tpu/serve/", "tools/",
    ),
    # MX07 bounded-handoff findings stay inside the production serving +
    # observability code (the reachability walk itself crosses files).
    "handoff_scope": ("igaming_platform_tpu/serve/", "igaming_platform_tpu/obs/"),
    # CC09 mandatory-seam contract table (rules/seams.py). Each scoring
    # PATH is declared as the set of functions one request flows through
    # — members span thread hand-offs (gRPC handler -> batcher loop ->
    # engine callbacks; pipeline submit -> stage/readback workers) — and
    # must-reach of every seam is computed over the union. Degraded /
    # heuristic tiers are exempt HERE, in config, never silently in
    # code. Registering a new scoring path: docs/operations.md, "Seam
    # contracts".
    "seam_contracts": {
        "seams": {
            "ledger": ("note_decisions",),
            "drift": ("_note_drift", "_note_drift_cached"),
            "session": ("_note_session_bypass", "prepare_chunk"),
            # PR 14: the fused program's launch core must still hand its
            # in-graph shadow/sketch outputs through the declared seams —
            # _note_shadow is the single shadow hand-off chokepoint
            # (fused outputs AND the echo-fed fallback both flow here).
            "shadow": ("_note_shadow",),
        },
        "paths": {
            "row": (
                "igaming_platform_tpu/serve/grpc_server.py::RiskGrpcService.ScoreTransaction",
                "igaming_platform_tpu/serve/batcher.py::ContinuousBatcher._loop",
                "igaming_platform_tpu/serve/batcher.py::ContinuousBatcher._finalize_batch",
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine._dispatch_requests",
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine._collect_requests",
            ),
            "batch": (
                "igaming_platform_tpu/serve/grpc_server.py::RiskGrpcService.ScoreBatch",
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine.score_batch",
            ),
            "wire-lockstep": (
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine.score_batch_wire",
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine.score_batch_wire_bytes",
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine._score_rows_encode",
            ),
            "wire-pipelined": (
                "igaming_platform_tpu/serve/pipeline_engine.py::HostPipeline.score_rows_to_wire",
                "igaming_platform_tpu/serve/pipeline_engine.py::HostPipeline._stage_loop",
                "igaming_platform_tpu/serve/pipeline_engine.py::HostPipeline._readback_loop",
            ),
            "index": (
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine.score_batch_wire_index",
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine.score_columns_cached",
                "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine._indexed_outputs",
            ),
        },
        "exempt": (
            "igaming_platform_tpu/serve/supervisor.py::HeuristicScorer.score_requests",
            "igaming_platform_tpu/serve/supervisor.py::SupervisedScoringEngine._degraded_rows_to_wire",
        ),
        "cover_files": (
            "igaming_platform_tpu/serve/scorer.py",
            "igaming_platform_tpu/serve/batcher.py",
            "igaming_platform_tpu/serve/grpc_server.py",
            "igaming_platform_tpu/serve/pipeline_engine.py",
            "igaming_platform_tpu/serve/supervisor.py",
        ),
        "terminal_calls": ("encode_score_batch", "ScoreResponse"),
    },
    # CC10-CC12 thread-role model (rules/races.py over threadroles.py).
    # thread_roles: hand-offs static spawn discovery cannot see — the
    # engine's dispatch/collect callbacks are injected into the batcher
    # as plain callables, so the roles those threads lend them are
    # declared here (same config-extension idiom as seam_contracts).
    "thread_roles": {
        "continuous-batcher": (
            "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine._dispatch_requests",
        ),
        "batch-collector": (
            "igaming_platform_tpu/serve/scorer.py::TPUScoringEngine._collect_requests",
        ),
    },
    # CC12 role contracts: which roles may call each scoring-path seam.
    # A call from an undeclared role fails loudly (a thread quietly
    # joined the scoring path); an entry naming a vanished role or
    # callee fails as drift, like CC09's seam table.
    "role_contracts": {
        # Decisions enter the ledger from request threads and the two
        # batcher-side callback roles declared above — nothing else.
        "note_decisions": ("main", "continuous-batcher", "batch-collector"),
        # The sampler registry is read by the hostprof sampler, by the
        # heartbeat's stall watch (to name the threads it samples while an
        # RPC is held) and by snapshot()/export endpoints on caller
        # threads only.
        "registered_threads": ("main", "hostprof-sampler",
                               "hostprof-heartbeat"),
    },
}

DEFAULT_BASELINE = Path(__file__).resolve().parent / "baseline.json"


@dataclass
class Report:
    files: int
    new: list[Finding]
    baselined: list[Finding]
    stale: list[dict]
    syntax_errors: list[Finding]
    elapsed_s: float = 0.0
    # Per-rule wall time (ms). Shared graphs are cached, so their build
    # cost lands on whichever rule touches them first — attribution,
    # not isolated cost (see engine.run_rules).
    rule_timings_ms: dict[str, float] = field(default_factory=dict)

    @property
    def failed(self) -> bool:
        return bool(self.new or self.stale or self.syntax_errors)

    def all_findings(self) -> list[Finding]:
        return sorted(self.syntax_errors + self.new + self.baselined,
                      key=lambda f: (f.path, f.line, f.rule, f.message))


@dataclass
class _Discovery:
    root: Path
    files: list[Path] = field(default_factory=list)


def _discover_repo() -> _Discovery:
    d = _Discovery(REPO_ROOT)
    d.files = [REPO_ROOT / f for f in TOP_FILES if (REPO_ROOT / f).exists()]
    for root in ROOTS:
        d.files.extend(sorted((REPO_ROOT / root).rglob("*.py")))
    d.files = [f for f in d.files if not (EXCLUDED_PARTS & set(f.parts))]
    return d


def _discover_paths(paths: list[Path]) -> _Discovery:
    root = paths[0] if paths[0].is_dir() else paths[0].parent
    d = _Discovery(root.resolve())
    for p in paths:
        p = p.resolve()
        if p.is_dir():
            d.files.extend(sorted(p.rglob("*.py")))
        else:
            d.files.append(p)
    return d


def _module_name(relpath: str) -> str:
    parts = relpath[:-3].split("/")  # strip .py
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def build_project(discovery: _Discovery,
                  config: dict | None = None) -> tuple[ProjectContext, list[Finding]]:
    """Parse every file once. Returns the project plus PY00 findings for
    files that don't parse (those are excluded from the project)."""
    contexts: list[FileContext] = []
    syntax_errors: list[Finding] = []
    for path in discovery.files:
        try:
            relpath = path.relative_to(discovery.root).as_posix()
        except ValueError:
            relpath = path.name
        src = path.read_text(encoding="utf-8")
        try:
            tree = ast.parse(src, filename=str(path))
        except SyntaxError as exc:
            syntax_errors.append(Finding(
                "PY00", relpath, exc.lineno or 0, f"syntax error: {exc.msg}"))
            continue
        suppressions, bare = parse_suppressions(src)
        contexts.append(FileContext(
            path=path, relpath=relpath, module=_module_name(relpath),
            src=src, tree=tree, suppressions=suppressions,
            bare_noqa_lines=bare))
    project = ProjectContext(root=discovery.root, files=contexts)
    project.caches["config"] = dict(config or {})
    return project, syntax_errors


def run_analysis(paths: list[Path] | None = None,
                 baseline_path: Path | None = None,
                 config: dict | None = None,
                 no_baseline: bool = False,
                 changed_only: set[str] | None = None) -> Report:
    """``changed_only`` (the --changed-only incremental mode) is a set of
    scan-root-relative posix paths: the WHOLE project is still parsed —
    cross-file rules (jit reachability, lock graph, seam contracts) need
    the full graph to stay sound — but file-scoped rules skip unchanged
    files and every reported finding is filtered to the changed set. The
    shrink-only stale-baseline contract is NOT enforced in this mode (a
    fix in an unchanged file would look stale); full runs enforce it."""
    t0 = time.perf_counter()
    if paths:
        discovery = _discover_paths(paths)
        cfg = config if config is not None else {}
        entries = baseline_mod.load(baseline_path) if baseline_path else []
    else:
        discovery = _discover_repo()
        cfg = config if config is not None else REPO_CONFIG
        entries = baseline_mod.load(baseline_path or DEFAULT_BASELINE)
    if no_baseline:
        entries = []
    project, syntax_errors = build_project(discovery, cfg)
    rule_timings: dict[str, float] = {}
    findings = run_rules(project, file_rule_paths=changed_only,
                         rule_timings=rule_timings)
    if changed_only is not None:
        findings = [f for f in findings if f.path in changed_only]
        syntax_errors = [f for f in syntax_errors if f.path in changed_only]
    matched = baseline_mod.match(findings, entries)
    return Report(
        files=(len(changed_only) if changed_only is not None
               else len(discovery.files)),
        new=matched.new,
        baselined=matched.baselined,
        stale=[] if changed_only is not None else matched.stale,
        syntax_errors=syntax_errors,
        elapsed_s=time.perf_counter() - t0,
        rule_timings_ms={rid: round(s * 1000, 2)
                         for rid, s in sorted(rule_timings.items())})


def changed_files(ref: str | None = None) -> set[str]:
    """Repo-root-relative paths of changed files for --changed-only:
    unstaged + staged + untracked; when the working tree is clean, the
    last commit's files (so a post-commit CI lint-changed still checks
    something). ``ref`` overrides the diff base entirely."""
    import subprocess

    def _git(*args: str) -> list[str]:
        res = subprocess.run(
            ["git", *args], cwd=REPO_ROOT, capture_output=True, text=True)
        if res.returncode != 0:
            return []
        return [line.strip() for line in res.stdout.splitlines() if line.strip()]

    if ref:
        files = _git("diff", "--name-only", ref)
    else:
        files = (_git("diff", "--name-only")
                 + _git("diff", "--name-only", "--cached")
                 + _git("ls-files", "--others", "--exclude-standard"))
        if not files:
            files = _git("diff", "--name-only", "HEAD~1", "HEAD")
    return {f for f in files if f.endswith(".py")}


def _finding_order(f: Finding):
    return (f.path, f.line, f.rule, f.message)


def _render_text(report: Report) -> str:
    lines = [f.render() for f in sorted(report.syntax_errors + report.new,
                                        key=_finding_order)]
    for e in report.stale:
        lines.append(
            f"{e.get('path')}: stale baseline entry {e.get('fingerprint')} "
            f"({e.get('rule')}: {e.get('message', '')[:60]}...) — the "
            "finding is gone; remove it via --update-baseline")
    summary = (
        f"analysis: {report.files} files, "
        f"{len(report.new) + len(report.syntax_errors)} problems")
    if report.baselined:
        summary += f", {len(report.baselined)} baselined"
    if report.stale:
        summary += f", {len(report.stale)} stale baseline entries"
    summary += f" ({report.elapsed_s:.2f}s)"
    lines.append(summary)
    return "\n".join(lines)


def _render_json(report: Report) -> str:
    # Findings and the rule catalog are emitted in a total, stable order
    # — (path, line, rule, message) and rule id — so JSON output is
    # diffable and independent of rule registration order.
    return json.dumps({
        "files": report.files,
        "elapsed_s": round(report.elapsed_s, 3),
        "rule_timings_ms": report.rule_timings_ms,
        "findings": [f.to_json() for f in sorted(
            report.syntax_errors + report.new, key=_finding_order)],
        "baselined": [f.to_json() for f in sorted(
            report.baselined, key=_finding_order)],
        "stale_baseline": report.stale,
        "rules": {
            r.id: {"name": r.name, "scope": r.scope,
                   "aliases": sorted(r.aliases)}
            for r in sorted(RULES.values(), key=lambda r: r.id)
        },
        "exit_code": 1 if report.failed else 0,
    }, indent=2)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.analysis",
        description="In-tree static analyzer (rule catalog: "
                    "docs/static-analysis.md)")
    parser.add_argument("paths", nargs="*", type=Path,
                        help="files/dirs to scan (default: the repo roots)")
    parser.add_argument("--format", choices=("text", "json", "sarif"),
                        default="text")
    parser.add_argument("--baseline", type=Path, default=None,
                        help="baseline JSON (default: tools/analysis/"
                             "baseline.json in repo mode, none otherwise)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore any baseline")
    parser.add_argument("--update-baseline", action="store_true",
                        help="rewrite the baseline to the current findings "
                             "and exit 0")
    parser.add_argument("--changed-only", action="store_true",
                        help="incremental mode: report only findings in "
                             "git-changed files (cross-file rules still see "
                             "the whole repo; stale-baseline enforcement is "
                             "skipped)")
    parser.add_argument("--changed-ref", default=None,
                        help="diff base for --changed-only (default: working "
                             "tree, falling back to HEAD~1 when clean)")
    args = parser.parse_args(argv)

    changed: set[str] | None = None
    if args.changed_only:
        if args.paths:
            parser.error("--changed-only only applies to repo mode")
        changed = changed_files(args.changed_ref)
        if not changed:
            print("analysis: --changed-only found no changed python files")
            return 0

    report = run_analysis(args.paths or None, baseline_path=args.baseline,
                          no_baseline=args.no_baseline, changed_only=changed)

    if args.update_baseline:
        target = args.baseline or DEFAULT_BASELINE
        baseline_mod.write(target, report.new + report.baselined)
        print(f"baseline: wrote {len(report.new) + len(report.baselined)} "
              f"entries to {target}")
        return 0

    if args.format == "sarif":
        from tools.analysis import sarif

        print(sarif.render(report))
    else:
        print(_render_text(report) if args.format == "text"
              else _render_json(report))
    return 1 if report.failed else 0
