"""CC08 — session ring state mutates ONLY through the append seam.

The per-account session ring (serve/session_state.py) is replay-bearing
state: every fused scoring step appends to it through DONATED device
buffers whose rebind must stay in lock-step with the host session index
commit and the ledger's ``session_state_hash`` — that triple happens
under the manager's lock inside functions marked
``# analysis: session-append-seam`` (``prepare_chunk`` / ``adopt`` /
``on_admit``). A bare rebind of the ring state anywhere else desyncs the
device window from the host index: every later decision on that slot
scores against a window the ledger cannot reconstruct, and
``tools/replay.py`` reports hash mismatches that look like corruption
but are really a coding bug.

This rule flags assignments/rebinds of the session state attributes
(``session_ring``, ``session_cursor``, ``session_length``) anywhere in
the session-state scope EXCEPT:

- inside a function marked ``# analysis: session-append-seam`` (on any
  line of its signature);
- ``self.<attr> = ...`` inside ``__init__`` (construction, not mutation).

The host session index (``_twin``) is the other half of the same state,
and it is held to more: any touch of it, a read too, has to sit in a
seam function (its caller holds the lock), under a lexical
``with <x>.lock:``, or in ``__init__``. What a chunk's ids decide on
their own - ``group_chunk``, which runs BEFORE the lock is taken -
therefore carries no seam tag and can read no twin state: the rule
fires on the first ``._twin`` that finds its way into it.

Same shape as CC07 (param-mutation discipline): the discipline is the
point, the marker is the audit trail.
"""

from __future__ import annotations

import ast
import re

from tools.analysis.engine import FileContext, ProjectContext, rule

_SESSION_ATTRS = {"session_ring", "session_cursor", "session_length"}
_TWIN_ATTR = "_twin"
_SEAM_MARKER = re.compile(r"#\s*analysis:\s*session-append-seam")


def _scoped_files(project: ProjectContext) -> list[FileContext]:
    config = project.caches.get("config", {})
    prefixes = config.get("sessionstate_scope")
    if not prefixes:
        return list(project.files)
    return [f for f in project.files
            if any(f.relpath.startswith(p) for p in prefixes)]


def _seam_ranges(ctx: FileContext) -> list[tuple[int, int]]:
    seam_lines = {
        lineno
        for lineno, line in enumerate(ctx.src.splitlines(), start=1)
        if _SEAM_MARKER.search(line)
    }
    if not seam_lines:
        return []
    ranges = []
    for node in ctx.walk():
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # the marker may close a signature that runs over several lines
        marker_lines = set(range(
            node.lineno, max(node.lineno + 1, node.body[0].lineno)))
        marker_lines |= {d.lineno for d in node.decorator_list}
        if marker_lines & seam_lines:
            ranges.append((node.lineno, node.end_lineno or node.lineno))
    return ranges


def _init_self_ranges(ctx: FileContext) -> list[tuple[int, int]]:
    return [
        (node.lineno, node.end_lineno or node.lineno)
        for node in ctx.walk()
        if isinstance(node, ast.FunctionDef) and node.name == "__init__"
    ]


def _lock_ranges(ctx: FileContext) -> list[tuple[int, int]]:
    """Bodies of ``with <x>.lock:`` statements (the manager's lock)."""
    return [
        (node.lineno, node.end_lineno or node.lineno)
        for node in ctx.walk()
        if isinstance(node, ast.With) and any(
            isinstance(i.context_expr, ast.Attribute)
            and i.context_expr.attr == "lock" for i in node.items)
    ]


def _session_targets(node: ast.AST):
    if isinstance(node, ast.Assign):
        targets = node.targets
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
    else:
        return
    for t in targets:
        for el in (t.elts if isinstance(t, ast.Tuple) else [t]):
            if isinstance(el, ast.Attribute) and el.attr in _SESSION_ATTRS:
                is_self = isinstance(el.value, ast.Name) and el.value.id == "self"
                yield el, is_self


@rule("CC08", "session-state-mutation-discipline",
      "Session ring state (`session_ring` / `session_cursor` / "
      "`session_length`) was written outside the append seam (a "
      "`# analysis: session-append-seam` function). The ring only stays "
      "replayable while device appends, the host session index and the "
      "ledger's session_state_hash move together under the manager's "
      "lock — a bare rebind desyncs them and every later decision on "
      "the slot becomes a silent replay mismatch. Route the write "
      "through the seam functions (prepare_chunk/adopt/on_admit), or "
      "mark a genuine new seam with `# analysis: session-append-seam`. "
      "The host session index (`._twin`) is read and written only "
      "there or under `with <x>.lock:` - the grouping of a chunk that "
      "runs before the lock is taken (group_chunk) touches neither.",
      scope="project")
def session_state_mutation_discipline(project: ProjectContext):
    for ctx in _scoped_files(project):
        seam = _seam_ranges(ctx)
        inits = _init_self_ranges(ctx)
        locked = _lock_ranges(ctx)

        def _in(ranges: list[tuple[int, int]], lineno: int) -> bool:
            return any(lo <= lineno <= hi for lo, hi in ranges)

        for node in ctx.walk():
            if isinstance(node, ast.Attribute) and node.attr == _TWIN_ATTR:
                if not (_in(seam, node.lineno) or _in(locked, node.lineno)
                        or _in(inits, node.lineno)):
                    yield ctx, node.lineno, (
                        "host session index `._twin` touched outside the "
                        "append seam and outside `with <x>.lock:` - what "
                        "runs before the session lock (group_chunk) reads "
                        "the chunk's ids alone; move the access behind a "
                        "`# analysis: session-append-seam` function or "
                        "take the lock")
                continue
            for attr, is_self in _session_targets(node):
                if _in(seam, attr.lineno):
                    continue
                if is_self and _in(inits, attr.lineno):
                    continue
                yield ctx, attr.lineno, (
                    f"write to session ring state `.{attr.attr}` outside "
                    "the append seam — device window, host session index "
                    "and ledger hash fall out of lock-step and replay "
                    "breaks; use the `# analysis: session-append-seam` "
                    "functions instead")
