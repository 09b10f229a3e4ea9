"""The repository's accounts of itself, held to the tree.

A Makefile target, a path in a document, an option in the operations
table and a drill's flag are all claims about files and names; nothing
failed when eight `bench-*` targets, a 676-line performance report and 21
captures outlived the code they described. Each case here reads one such
claim and looks it up: no clock, no port, no rate.
"""

from __future__ import annotations

import ast
import functools
import importlib.util
import inspect
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "igaming_platform_tpu"


# ---------------------------------------------------------------------------
# (a) every Makefile target names files that exist


def _makefile() -> tuple[list[str], dict[str, tuple[list[str], list[str]]]]:
    """(.PHONY names, target -> (prerequisites, recipe tokens)), variables
    expanded."""
    text = (REPO / "Makefile").read_text().replace("\\\n", " ")
    variables = dict(re.findall(r"^(\w+)[ \t]*\??=[ \t]*(.*)$", text, re.M))

    def expand(s: str) -> str:
        for _ in range(8):
            s, n = re.subn(r"\$\((\w+)\)",
                           lambda m: variables.get(m.group(1), ""), s)
            if not n:
                break
        return s

    phony = re.search(r"^\.PHONY:(.*)$", text, re.M).group(1).split()
    rules: dict[str, tuple[list[str], list[str]]] = {}
    for m in re.finditer(r"^([\w-]+):(?!=)([^\n]*)\n((?:\t[^\n]*\n?)*)",
                         text, re.M):
        recipe = " ; ".join(line.strip() for line in m.group(3).splitlines())
        rules[m.group(1)] = (m.group(2).split(), expand(recipe).split())
    return phony, rules


_PHONY, _RULES = _makefile()


def _module_exists(module: str) -> bool:
    top = module.split(".")[0]
    if not (REPO / top).exists():
        return importlib.util.find_spec(top) is not None
    path = REPO.joinpath(*module.split("."))
    return path.with_suffix(".py").is_file() or (path / "__main__.py").is_file()


@pytest.mark.parametrize("target", _PHONY)
def test_make_target_names_files_that_exist(target):
    assert target in _RULES, f"`{target}` is .PHONY and has no rule"
    prerequisites, tokens = _RULES[target]
    for name in prerequisites:
        assert name in _RULES, f"`{target}` needs `{name}`, which has no rule"
    missing = []
    for before, prev, tok in zip(["", ""] + tokens, [""] + tokens, tokens):
        tok = tok.strip("'\"")
        if prev == "-m" and before.startswith("python"):
            if not _module_exists(tok):
                missing.append(f"-m {tok}")
        elif (tok.endswith((".py", ".proto")) or prev in ("sh", "-f")) \
                and not (REPO / tok).is_file():
            missing.append(tok)
    assert not missing, f"`make {target}` names {missing}, not in the tree"


# ---------------------------------------------------------------------------
# (b) every repository path a document cites in backticks exists

_DOCS = ("README.md", "docs/architecture.md", "docs/operations.md",
         "docs/static-analysis.md", "docs/switching.md")
_FILE_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".sh", ".cpp", ".proto",
                  ".yml", ".yaml", ".toml")
# Where a document's relative citations are rooted: the checkout, the
# package (`serve/scorer.py`), the analyzer (`rules/metrics.py`) and the
# fixture tree its quoted reports name (`cc/deadlock.py`).
_ROOTS = (REPO, PACKAGE, REPO / "tools" / "analysis",
          REPO / "tests" / "fixtures" / "static_analysis")


def _ignored_prefixes() -> tuple[str, ...]:
    """Directories git ignores: what running leaves behind is cited by the
    documents and is not in the tree."""
    lines = (REPO / ".gitignore").read_text().split()
    return tuple(line for line in lines if line.endswith("/"))


def _cited_paths(text: str):
    ignored = _ignored_prefixes()
    for token in re.findall(r"`([^`\n]+)`", text):
        for word in token.split():
            word = re.sub(r"[,;.)]+$", "", word.lstrip("("))
            word = re.sub(r":[\w.\-<>]+$", "", word)  # file.py:func, file.go:12-34
            if (not re.fullmatch(r"[\w./\-]+", word) or word.startswith("/")
                    or word.startswith(ignored) or "//" in word):
                continue
            is_dir = word.endswith("/") and word.count("/") >= 1
            is_file = word.endswith(_FILE_SUFFIXES) and (
                "/" in word or word.endswith((".md", ".json", ".jsonl")))
            if is_dir or is_file:
                yield word


@pytest.mark.parametrize("doc", _DOCS)
def test_document_cites_paths_that_exist(doc):
    missing = sorted({
        path for path in _cited_paths((REPO / doc).read_text())
        if not any((root / path).exists() for root in _ROOTS)})
    assert not missing, f"{doc} cites paths that are not in the tree: {missing}"


# ---------------------------------------------------------------------------
# (c) the options the package reads and the options the runbook tables hold


def _is_environ(node: ast.AST) -> bool:
    return ((isinstance(node, ast.Attribute) and node.attr == "environ")
            or (isinstance(node, ast.Name) and node.id == "environ"))


def _environment_keys(tree: ast.AST):
    """The AST node that holds the key of every read of the environment."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            f = node.func
            if node.args and (f.attr == "getenv" or (
                    f.attr in ("get", "setdefault", "pop")
                    and _is_environ(f.value))):
                yield node.args[0]
        elif isinstance(node, ast.Subscript) and _is_environ(node.value):
            yield node.slice
        elif (isinstance(node, ast.Compare) and len(node.ops) == 1
              and isinstance(node.ops[0], (ast.In, ast.NotIn))
              and _is_environ(node.comparators[0])):
            yield node.left


@functools.cache
def _options_read_by_the_package() -> dict[str, str]:
    """name -> where it is read. A read is `os.environ` with a literal key,
    a module constant as the key, or a call of a function of the package
    that reads `os.environ` by one of its parameters (`getenv_int`)."""
    trees = {p: ast.parse(p.read_text()) for p in sorted(PACKAGE.rglob("*.py"))
             if "proto_gen" not in p.parts}
    readers: dict[str, int] = {}
    for tree in trees.values():
        for fn in ast.walk(tree):
            if isinstance(fn, ast.FunctionDef):
                params = [a.arg for a in fn.args.args]
                for key in _environment_keys(fn):
                    if isinstance(key, ast.Name) and key.id in params:
                        readers[fn.name] = params.index(key.id)
    names: dict[str, str] = {}
    for path, tree in trees.items():
        constants = {
            t.id: node.value.value for node in tree.body
            if isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
            for t in node.targets if isinstance(t, ast.Name)}

        def note(key: ast.AST) -> None:
            where = f"{path.relative_to(REPO)}:{key.lineno}"
            if isinstance(key, ast.Constant) and isinstance(key.value, str):
                names.setdefault(key.value, where)
            elif isinstance(key, ast.Name) and key.id in constants:
                names.setdefault(constants[key.id], where)

        for key in _environment_keys(tree):
            note(key)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                f = node.func
                name = (f.id if isinstance(f, ast.Name)
                        else f.attr if isinstance(f, ast.Attribute) else None)
                if name in readers and len(node.args) > readers[name]:
                    note(node.args[readers[name]])
    return names


def _options_in_the_runbook_tables() -> set[str]:
    """The names in the first cell of every row of the option tables of
    docs/operations.md (the tables headed `Var` or `Knob`)."""
    names: set[str] = set()
    in_table = False
    for line in (REPO / "docs" / "operations.md").read_text().splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if not line.startswith("|"):
            in_table = False
        elif cells[0].lower() in ("var", "knob"):
            in_table = True
        elif in_table:
            names.update(re.findall(r"`([A-Z][A-Z0-9_]+)`", cells[0]))
    return names


def test_every_option_the_package_reads_is_in_the_runbook():
    text = (REPO / "docs" / "operations.md").read_text()
    read = _options_read_by_the_package()
    assert len(read) > 100, "the scan of the package found too few reads"
    missing = {name: where for name, where in read.items()
               if not re.search(rf"(?<![A-Z0-9_]){name}(?![A-Z0-9_])", text)}
    assert not missing, (
        "read by the package and not named in docs/operations.md "
        f"(add a row to its table): {missing}")


def test_every_option_in_the_runbook_tables_is_read_by_the_package():
    documented = _options_in_the_runbook_tables()
    assert len(documented) > 80, "the option tables were not found"
    # JAX reads this one itself; the package asks `jax.config.jax_platforms`.
    documented.discard("JAX_PLATFORMS")
    unread = sorted(documented - set(_options_read_by_the_package()))
    assert not unread, (
        "in an option table of docs/operations.md and read by nothing "
        f"under igaming_platform_tpu/: {unread}")


# ---------------------------------------------------------------------------
# (d) every drill is reachable, and no flag is no drill

_DRILL_FLAGS = ("--chaos", "--fleet-chaos", "--chaos-ledger", "--slo-chaos",
                "--online-chaos", "--drift-chaos", "--session-chaos",
                "--deadline")


@pytest.mark.parametrize("flag", _DRILL_FLAGS)
def test_drill_flag_reaches_its_drill(flag, monkeypatch):
    from tools.drills import soak

    by_flag = {f: (env, fn) for f, env, fn in soak.DRILLS}
    assert set(by_flag) == set(_DRILL_FLAGS)
    env_name, drill = by_flag[flag]
    assert inspect.isfunction(drill) and drill.__module__ == soak.__name__
    required = [p.name for p in inspect.signature(drill).parameters.values()
                if p.default is p.empty
                and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD,
                               p.KEYWORD_ONLY)]
    assert not required, f"{drill.__name__} is called with no argument"

    # The dispatcher starts this drill and no other, from the flag and
    # from the variable; the drills themselves are not run.
    started: list[str] = []
    monkeypatch.setattr(soak, "DRILLS", tuple(
        (f, env, lambda f=f: started.append(f)) for f, env, _ in soak.DRILLS))
    for _, env, _ in soak.DRILLS:
        monkeypatch.delenv(env, raising=False)
    soak.main([flag])
    monkeypatch.setenv(env_name, "1")
    soak.main([])
    assert started == [flag, flag]

    # And `make` has a target that runs it.
    assert any(tokens[i:i + 3] == ["-m", "tools.drills.soak", flag]
               for _, tokens in _RULES.values() for i in range(len(tokens))), \
        f"no Makefile target runs `-m tools.drills.soak {flag}`"


def test_no_drill_flag_lists_the_drills_and_runs_none():
    from tools.drills import soak

    env = {k: v for k, v in os.environ.items()
           if k not in {name for _, name, _ in soak.DRILLS}}
    proc = subprocess.run(
        [sys.executable, "-m", "tools.drills.soak", "--wire"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    for flag in _DRILL_FLAGS:
        assert flag in proc.stderr, proc.stderr
