"""PY* — general hygiene rules, ported from the original single-file linter.

Behavior is unchanged from the single-file linter except that
suppression is now rule-scoped (PY06 makes a blanket ``# noqa`` itself a
finding) and each check carries a stable ID.
"""

from __future__ import annotations

import ast

from tools.analysis.engine import FileContext, rule


def _imported_names(node: ast.AST):
    """Yields (bound name, dedupe key, lineno). For ``import a.b`` the
    bound name is ``a`` but the dedupe key is the full dotted path —
    ``import urllib.parse`` + ``import urllib.request`` is not a dup."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            bound = alias.asname or alias.name.split(".")[0]
            yield bound, (alias.asname or alias.name), node.lineno
    elif isinstance(node, ast.ImportFrom):
        for alias in node.names:
            if alias.name != "*":
                name = alias.asname or alias.name
                yield name, name, node.lineno


def _used_names(ctx) -> set[str]:
    used: set[str] = set()
    for node in ctx.walk():
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            base = node
            while isinstance(base, ast.Attribute):
                base = base.value
            if isinstance(base, ast.Name):
                used.add(base.id)
    return used


def _exports(tree: ast.Module) -> set[str]:
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "__all__"
                        for t in node.targets)
                and isinstance(node.value, (ast.List, ast.Tuple))):
            return {e.value for e in node.value.elts
                    if isinstance(e, ast.Constant)}
    return set()


@rule("PY01", "unused-import",
      "A module-level import nothing references is dead weight and hides "
      "real dependencies. Deliberate side-effect imports (descriptor-pool "
      "registration, plugin hooks) alias to an underscore name "
      "(``import x.y_pb2 as _y_pb2``) or carry ``# noqa: PY01``.",
      aliases=("F401",))
def unused_import(ctx: FileContext):
    # Import hygiene is checked at MODULE level only: function-scope
    # re-imports are a deliberate idiom here (lazy imports for optional
    # deps and jax-initialization ordering). __init__.py re-exports are
    # exempt wholesale.
    if ctx.path.name == "__init__.py":
        return
    used = _used_names(ctx)
    exports = _exports(ctx.tree)
    for node in ctx.tree.body:
        for name, _key, lineno in _imported_names(node):
            if (name != "annotations" and name not in used
                    and name not in exports and not name.startswith("_")):
                yield lineno, f"unused import {name!r}"


@rule("PY02", "duplicate-import",
      "Importing the same module twice at module level is a merge-conflict "
      "scar; one of the two is stale.")
def duplicate_import(ctx: FileContext):
    seen: dict[str, int] = {}
    for node in ctx.tree.body:
        for _name, key, lineno in _imported_names(node):
            if key in seen and seen[key] != lineno:
                yield lineno, (f"duplicate module-level import of {key!r} "
                               f"(first at line {seen[key]})")
            seen.setdefault(key, lineno)


@rule("PY03", "bare-except",
      "``except:`` swallows KeyboardInterrupt and SystemExit; catch "
      "Exception (or narrower) instead.",
      aliases=("E722",))
def bare_except(ctx: FileContext):
    for node in ctx.walk():
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield node.lineno, "bare `except:`"


@rule("PY04", "none-comparison",
      "``== None`` invokes __eq__ (numpy arrays broadcast it); identity "
      "checks must use ``is None``.",
      aliases=("E711",))
def none_comparison(ctx: FileContext):
    for node in ctx.walk():
        if isinstance(node, ast.Compare):
            for op, comp in zip(node.ops, node.comparators):
                if (isinstance(op, (ast.Eq, ast.NotEq))
                        and isinstance(comp, ast.Constant)
                        and comp.value is None):
                    yield node.lineno, "use `is None` / `is not None`"


@rule("PY05", "mutable-default",
      "A list/dict/set default is shared across every call of the "
      "function; use None and construct inside.",
      aliases=("B006",))
def mutable_default(ctx: FileContext):
    for node in ctx.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for default in list(node.args.defaults) + [
                    d for d in node.args.kw_defaults if d is not None]:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                    yield default.lineno, (
                        f"mutable default argument in {node.name}()")


@rule("PY06", "bare-noqa",
      "A bare ``# noqa`` silences every rule on the line with no record "
      "of which one was intended, so new findings on that line vanish "
      "silently. Scope it: ``# noqa: <RULE-ID>``.")
def bare_noqa(ctx: FileContext):
    for lineno in sorted(ctx.bare_noqa_lines):
        yield lineno, ("bare `# noqa` suppresses ALL rules on this line — "
                       "scope it to the intended rule: `# noqa: <RULE-ID>`")
