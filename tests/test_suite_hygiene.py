"""Guards on the suite itself, beside the analyzer's gate on the code.

The tier-1 suite ran out of the driver's clock for two reasons (PERF.md,
PR 25): tests built the native feature store at its default 1,000,000
accounts (4.48 GB, touched eagerly), and tests compared a time or a rate
measured on the test machine with a constant, which a starved machine
fails. Both are easy to bring back without noticing; these tests read
``tests/*.py`` as syntax trees and refuse them.

A CPU timing is a count or a parity check, never a speed. Where a bound
on a measured duration must stay it is gross (``GROSS_CEILING_S``), there
to catch an accidental quadratic or a hang, or the line says why the
number is not the machine's speed: ``# timing-ok: <reason>``.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

TESTS = Path(__file__).resolve().parent

# Under this, an upper bound on a measured duration is a speed assert.
GROSS_CEILING_S = 150.0

_SIZED_CALLS = {
    # callable -> the keyword that sizes the native store it builds
    "NativeFeatureStore": "max_accounts",
    "best_feature_store": "max_accounts",
    "RiskServer": "store_max_accounts",
}
_CLOCKS = {"time", "monotonic", "perf_counter", "monotonic_ns", "perf_counter_ns"}
# Program-reported times and rates, by the name they are read under.
_REPORTED = re.compile(
    r"(^|_)(elapsed|wall|duration|latency|response_time)(_m?s)?$"
    r"|(^|_)p(50|95|99)_m?s$|_per_s(ec)?$")
_WAIVER = "# timing-ok:"


def _test_files() -> list[Path]:
    return sorted(p for p in TESTS.glob("*.py") if p.name != Path(__file__).name)


def _callee(call: ast.Call) -> str:
    f = call.func
    return f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")


def test_no_test_builds_a_default_sized_native_store():
    bad = []
    for path in _test_files():
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if not isinstance(node, ast.Call) or _callee(node) not in _SIZED_CALLS:
                continue
            kw = _SIZED_CALLS[_callee(node)]
            sized = (any(k.arg == kw or k.arg is None for k in node.keywords)
                     or (_callee(node) != "RiskServer" and node.args))
            if not sized:
                bad.append(f"{path.name}:{node.lineno} {_callee(node)}() "
                           f"without {kw}=")
    assert not bad, "default-sized (4.48 GB) native stores:\n" + "\n".join(bad)


def _is_clock_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _callee(node) in _CLOCKS and not node.args


def _number(node: ast.AST) -> float | None:
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = _number(node.operand)
        return None if inner is None else -inner
    if isinstance(node, ast.Constant) and type(node.value) in (int, float):
        return float(node.value)
    return None


def _reported_name(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Subscript) and isinstance(node.slice, ast.Constant):
        return str(node.slice.value)
    return ""


class _Measured:
    """Which names of one function hold a time or a rate measured by the
    clock: a value computed from a clock call, or from such a value. A
    measured value under a division bar makes a rate."""

    def __init__(self, func: ast.AST):
        self.kind: dict[str, str] = {}  # name -> "duration" | "rate"
        assigns = [n for n in ast.walk(func)
                   if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign))
                   and n.value is not None]
        changed = True
        while changed:
            changed = False
            for a in assigns:
                kind = self.of(a.value)
                if kind is None:
                    continue
                targets = a.targets if isinstance(a, ast.Assign) else [a.target]
                for t in targets:
                    if isinstance(t, ast.Name) and self.kind.get(t.id) != kind \
                            and self.kind.get(t.id) != "rate":
                        self.kind[t.id] = kind
                        changed = True

    def of(self, expr: ast.AST) -> str | None:
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Div) \
                and self.of(expr.right) == "duration":
            return "rate"
        found = None
        # `resp.score` of a measured `resp` is not the measurement.
        bases = {id(n.value) for n in ast.walk(expr) if isinstance(n, ast.Attribute)}
        for n in ast.walk(expr):
            if id(n) in bases and isinstance(n, ast.Name):
                continue
            if _is_clock_call(n) or (isinstance(n, ast.Name)
                                     and self.kind.get(n.id) == "duration"):
                found = found or "duration"
            elif isinstance(n, ast.Name) and self.kind.get(n.id) == "rate":
                found = "rate"
            elif isinstance(n, ast.BinOp) and isinstance(n.op, ast.Div) \
                    and n is not expr and self.of(n) == "rate":
                found = "rate"
        if found is None:
            name = _reported_name(expr)
            if _REPORTED.search(name):
                found = "rate" if "_per_s" in name else "duration"
        return found


def _scale(expr: ast.AST) -> float:
    """Seconds per unit of ``expr``, read off its name (``*_ms``) or off
    a ``* 1000`` / ``* 1e3`` in it."""
    text = ast.unparse(expr)
    if re.search(r"(_|\b)ms\b|ms\]|\*\s*(1000(\.0)?|1e3)\b", text):
        return 1e-3
    if re.search(r"(_|\b)us\b|\*\s*1e6\b", text):
        return 1e-6
    return 1.0


def _speed_asserts(path: Path) -> list[str]:
    src = path.read_text()
    lines = src.splitlines()
    tree = ast.parse(src, str(path))
    out = []
    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for func in funcs:
        measured = _Measured(func)
        for stmt in ast.walk(func):
            if not isinstance(stmt, ast.Assert):
                continue
            # On the assert's own lines, or the comment line above it.
            if any(_WAIVER in lines[i] for i in
                   range(max(0, stmt.lineno - 2), stmt.end_lineno)):
                continue
            for cmp in (n for n in ast.walk(stmt.test) if isinstance(n, ast.Compare)):
                sides = [cmp.left, *cmp.comparators]
                for (lhs, op, rhs) in zip(sides, cmp.ops, sides[1:]):
                    for value, const, above in ((lhs, rhs, (ast.Lt, ast.LtE)),
                                                (rhs, lhs, (ast.Gt, ast.GtE))):
                        c, kind = _number(const), measured.of(value)
                        if c is None or kind is None:
                            continue
                        bounded_above = isinstance(op, above)
                        if kind == "duration" and bounded_above \
                                and c * _scale(value) < GROSS_CEILING_S:
                            out.append(f"{path.name}:{stmt.lineno} measured time "
                                       f"`{ast.unparse(value)}` held under {c:g}")
                        if kind == "rate" and not bounded_above and c > 0 \
                                and isinstance(op, (ast.Lt, ast.LtE, ast.Gt, ast.GtE)):
                            out.append(f"{path.name}:{stmt.lineno} measured rate "
                                       f"`{ast.unparse(value)}` held over {c:g}")
    return sorted(set(out))


def test_no_test_holds_a_measured_time_or_rate_to_a_constant():
    bad = [line for path in _test_files() for line in _speed_asserts(path)]
    assert not bad, (
        "a time or rate measured on the test machine is compared with a "
        f"constant under the gross ceiling ({GROSS_CEILING_S:g} s); assert the "
        "event or the count, or mark the line `# timing-ok: <reason>`:\n"
        + "\n".join(bad))


def test_the_guard_sees_what_it_guards(tmp_path):
    """The two shapes that cost the suite its clock, and their repairs."""
    bad = tmp_path / "test_bad.py"
    bad.write_text(
        "import time\n"
        "def test_speed():\n"
        "    t0 = time.perf_counter()\n"
        "    work()\n"
        "    elapsed = time.perf_counter() - t0\n"
        "    best = 2000 / elapsed\n"
        "    assert elapsed < 15.0\n"
        "    assert best >= 10_000\n"
        "    assert report.elapsed_s < 15.0\n"
        "    assert (time.monotonic() - t0) * 1000 < 500\n")
    found = _speed_asserts(bad)
    assert [f.split()[0] for f in found] == [
        "test_bad.py:10", "test_bad.py:7", "test_bad.py:8", "test_bad.py:9"]
    good = tmp_path / "test_good.py"
    good.write_text(
        "import time\n"
        "def test_events():\n"
        "    t0 = time.perf_counter()\n"
        "    work()\n"
        "    elapsed = time.perf_counter() - t0\n"
        "    assert elapsed < 600.0\n"
        "    assert elapsed >= 0.03\n"
        "    assert 2000 / elapsed > 0\n"
        "    assert elapsed < 2.0  # timing-ok: the program's own 1 s timer\n"
        "    assert calls == 100 and model.predict_ms(512) < 20.0\n")
    assert _speed_asserts(good) == []
