"""Rule engine for the in-tree static analyzer.

The analyzer grew out of a single file of inlined checks into a
framework: each check is a :class:`Rule` with a stable ID
(``JX*`` jit/tracing, ``CC*`` concurrency, ``MX*`` metrics/measurement,
``PY*`` general hygiene), every file is parsed exactly once into a
:class:`FileContext`, and cross-file rules see the whole parse forest
through a :class:`ProjectContext`.

Suppression is scoped: ``# noqa: JX02`` silences exactly one rule on one
line (legacy flake8 codes are honored through per-rule aliases, e.g.
``F401`` for PY01). A bare ``# noqa`` still silences the line for
backward compatibility but is itself reported as PY06, so blanket
suppressions can only ever shrink.
"""

from __future__ import annotations

import ast
import hashlib
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterable

# ---------------------------------------------------------------------------
# Findings


_LINE_REF = re.compile(r":\d+")


@dataclass(frozen=True)
class Finding:
    """One diagnostic: a rule firing at a location.

    ``fingerprint`` identifies the finding across line-number drift (for
    baseline matching): it hashes rule + path + the message with every
    ``:<line>`` reference blanked.
    """

    rule: str
    path: str  # scan-root-relative posix path
    line: int
    message: str

    @property
    def fingerprint(self) -> str:
        norm = _LINE_REF.sub(":_", self.message)
        h = hashlib.sha1(f"{self.rule}|{self.path}|{norm}".encode()).hexdigest()
        return h[:12]

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "fingerprint": self.fingerprint,
        }


# ---------------------------------------------------------------------------
# Suppression comments

# A noqa marker, optionally followed by `: CODE1, CODE2`. The code list
# accepts both our IDs (JX02) and legacy flake8-style codes (BLE001) —
# unknown codes simply never match a rule. Only genuine COMMENT tokens
# are scanned (tokenize), so docstrings *describing* suppression — like
# this analyzer's own — don't suppress anything.
_NOQA = re.compile(r"#\s*noqa(?P<codes>\s*:\s*[A-Za-z0-9_, ]+)?", re.IGNORECASE)


def parse_suppressions(src: str) -> tuple[dict[int, frozenset[str] | None], set[int]]:
    """Returns (line -> codes | None-for-blanket, bare-noqa lines)."""
    import io
    import tokenize

    suppressions: dict[int, frozenset[str] | None] = {}
    bare: set[int] = set()
    if "noqa" not in src:
        # Tokenizing every file cost more than every rule combined;
        # without the substring no COMMENT can match.
        return suppressions, bare
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return suppressions, bare
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _NOQA.search(tok.string)
        if not m:
            continue
        lineno = tok.start[0]
        codes = m.group("codes")
        if codes is None:
            suppressions[lineno] = None  # blanket: silences every rule
            bare.add(lineno)
        else:
            parsed = frozenset(
                c.strip().upper() for c in codes.lstrip(" :").split(",") if c.strip()
            )
            suppressions[lineno] = parsed or None
    return suppressions, bare


# ---------------------------------------------------------------------------
# Parse contexts


@dataclass
class FileContext:
    """One parsed source file; built exactly once per run."""

    path: Path  # absolute
    relpath: str  # scan-root-relative, posix separators
    module: str  # dotted module name relative to the scan root
    src: str
    tree: ast.Module
    suppressions: dict[int, frozenset[str] | None]
    bare_noqa_lines: set[int]

    def walk(self, node: ast.AST | None = None) -> tuple[ast.AST, ...]:
        """Flat pre-order node list, computed once per (sub)tree per run.

        ``ast.walk`` re-traverses the tree on every call; with ~20 rules
        each sweeping every file that traversal dominated the run. A
        cached flat tuple turns each sweep into a plain list iteration.
        With ``node`` given, the same cache covers a subtree — rules
        walking the same function body repeatedly (JX06, the MX family,
        CC10) hit the cache after the first pass. Keying by ``id`` is
        sound because this context owns ``tree`` and keeps every node
        alive for its own lifetime."""
        if node is None:
            nodes = self.__dict__.get("_nodes")
            if nodes is None:
                nodes = tuple(ast.walk(self.tree))
                self.__dict__["_nodes"] = nodes
            return nodes
        cache = self.__dict__.get("_subtree_nodes")
        if cache is None:
            cache = self.__dict__["_subtree_nodes"] = {}
        nodes = cache.get(id(node))
        if nodes is None:
            nodes = cache[id(node)] = tuple(ast.walk(node))
        return nodes

    def lines(self) -> list[str]:
        """``src.splitlines()``, computed once — marker scans are per
        function, and re-splitting the file for each was measurable."""
        lines = self.__dict__.get("_lines")
        if lines is None:
            lines = self.__dict__["_lines"] = self.src.splitlines()
        return lines

    def is_suppressed(self, rule: "Rule", line: int) -> bool:
        codes = self.suppressions.get(line, ...)
        if codes is ...:
            return False
        if codes is None:  # blanket noqa
            # PY06 reports the blanket itself; it can only be silenced by
            # naming it (`# noqa: PY06`), never by the blanket it flags.
            return rule.id != "PY06"
        return rule.id in codes or bool(codes & rule.aliases)


@dataclass
class ProjectContext:
    """The whole parse forest plus per-run caches shared between rules
    (call graphs, lock inventories) keyed by the module that builds them."""

    root: Path
    files: list[FileContext]
    caches: dict[str, object] = field(default_factory=dict)

    def by_module(self) -> dict[str, FileContext]:
        cache = self.caches.get("_by_module")
        if cache is None:
            cache = {f.module: f for f in self.files}
            self.caches["_by_module"] = cache
        return cache

    def resolve_module(self, dotted: str) -> FileContext | None:
        """Resolve an imported dotted path to an in-project file, tolerant
        of the scan root not being the package root (suffix match).

        Memoized: call-graph construction resolves the same few dotted
        paths thousands of times, and the miss path is a linear scan."""
        cache = self.caches.setdefault("_resolve_module", {})
        if dotted in cache:
            return cache[dotted]
        mods = self.by_module()
        result = mods.get(dotted)
        if result is None:
            suffix = "." + dotted
            for name, ctx in mods.items():
                if name.endswith(suffix) or ("." + name).endswith(suffix):
                    result = ctx
                    break
        cache[dotted] = result
        return result


# ---------------------------------------------------------------------------
# Rules

# File rules yield (line, message); project rules yield (ctx, line, message).
FileCheck = Callable[[FileContext], Iterable[tuple[int, str]]]
ProjectCheck = Callable[[ProjectContext], Iterable[tuple[FileContext, int, str]]]


@dataclass(frozen=True)
class Rule:
    id: str
    name: str
    rationale: str
    scope: str  # "file" | "project"
    check: Callable
    aliases: frozenset[str] = frozenset()

    @property
    def category(self) -> str:
        return self.id[:2]


RULES: dict[str, Rule] = {}


def rule(id: str, name: str, rationale: str, scope: str = "file",
         aliases: Iterable[str] = ()) -> Callable:
    """Decorator: register a check function as a rule."""

    def deco(fn: Callable) -> Callable:
        if id in RULES:
            raise ValueError(f"duplicate rule id {id}")
        RULES[id] = Rule(
            id=id, name=name, rationale=rationale, scope=scope, check=fn,
            aliases=frozenset(a.upper() for a in aliases),
        )
        return fn

    return deco


def run_rules(project: ProjectContext,
              file_rule_paths: set[str] | None = None,
              rule_timings: dict[str, float] | None = None) -> list[Finding]:
    """Run every registered rule; returns non-suppressed findings in a
    TOTAL order — (path, line, rule, message) — so output never depends
    on rule registration order (the PR 13 ordering bugfix).

    ``file_rule_paths`` (incremental mode) restricts file-scoped rules
    to those relpaths; project-scoped rules always see the whole parse
    forest (their graphs must stay complete to be sound).

    ``rule_timings`` (optional, rule id -> seconds) records per-rule
    wall time so the next rule author can see what each check costs.
    Shared graphs (lock graph, call graph, role graph) are built lazily
    and cached in ``project.caches``, so their construction cost lands
    on whichever rule touches them FIRST in registration order — read
    the table as attribution, not as isolated cost."""
    import time

    findings: list[Finding] = []
    for r in RULES.values():
        t0 = time.perf_counter()
        if r.scope == "file":
            for ctx in project.files:
                if (file_rule_paths is not None
                        and ctx.relpath not in file_rule_paths):
                    continue
                for line, msg in r.check(ctx):
                    if not ctx.is_suppressed(r, line):
                        findings.append(Finding(r.id, ctx.relpath, line, msg))
        else:
            for ctx, line, msg in r.check(project):
                if not ctx.is_suppressed(r, line):
                    findings.append(Finding(r.id, ctx.relpath, line, msg))
        if rule_timings is not None:
            rule_timings[r.id] = (
                rule_timings.get(r.id, 0.0) + time.perf_counter() - t0)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings


# ---------------------------------------------------------------------------
# Small shared AST helpers


def call_name(node: ast.Call) -> str | None:
    """Rightmost name of the callee: ``a.b.c()`` -> ``c``, ``f()`` -> ``f``."""
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def dotted_name(node: ast.AST) -> str | None:
    """``a.b.c`` -> "a.b.c" for pure Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def is_stringish(node: ast.AST | None) -> bool:
    return isinstance(node, ast.JoinedStr) or (
        isinstance(node, ast.Constant) and isinstance(node.value, str))
