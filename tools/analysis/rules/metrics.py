"""MX* — metrics and measurement-integrity rules.

Ports of the round-5/PR-2 checks of the single-file linter, behavior-preserving
except for one deliberate fix (ISSUE 3 satellite): the help-text check
used to require the metric *name* to be a positional string literal, so
``registry.counter(name="x", help_text="")`` — or any non-literal name,
like the f-strings ServiceMetrics uses — skipped the check entirely.
The rule now keys on the factory method alone and resolves the help
argument from either position or keyword.
"""

from __future__ import annotations

import ast
import re

from tools.analysis.engine import (FileContext, ProjectContext, call_name,
                                   dotted_name, rule)

_CLOCK_CALLS = {"perf_counter", "monotonic", "perf_counter_ns", "monotonic_ns"}

_METRIC_CLASSES = {"Counter", "Gauge", "Histogram"}
_METRIC_FACTORIES = {"counter", "gauge", "histogram"}

# MX04: the registered hot-loop functions — the per-batch serving loop
# whose host allocations the arena pools (serve/arena.py) exist to
# remove. Keyed by repo-relative path suffix -> qualnames. New hot loops
# register here, or mark the def line with `# analysis: hot-loop`.
_HOT_LOOP_REGISTRY: dict[str, frozenset[str]] = {
    "igaming_platform_tpu/serve/scorer.py": frozenset({
        "TPUScoringEngine._launch_device",
        "TPUScoringEngine._launch_padded",
        "TPUScoringEngine._launch_cached",
    }),
    "igaming_platform_tpu/serve/pipeline_engine.py": frozenset({
        "HostPipeline._dispatch_chunk",
        "HostPipeline._stage_loop",
        "HostPipeline._readback_loop",
    }),
    "igaming_platform_tpu/serve/batcher.py": frozenset({"pad_batch"}),
}
_HOT_LOOP_MARKER = "analysis: hot-loop"
_NP_ALIASES = {"np", "numpy", "onp"}
_NP_ALLOCATORS = {"zeros", "empty", "ones", "full", "zeros_like",
                  "empty_like", "ones_like", "ascontiguousarray"}


def _calls_by_scope(tree: ast.Module) -> dict[int, list[ast.Call]]:
    """Call nodes grouped by enclosing scope (module = ``id(tree)``,
    else the innermost enclosing def) in ONE traversal — each function
    is its own timing scope, so nested defs start a new group."""
    scopes: dict[int, list[ast.Call]] = {id(tree): []}

    def visit(node: ast.AST, scope: int) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.setdefault(id(child), [])
                visit(child, id(child))
                continue
            if isinstance(child, ast.Call):
                scopes[scope].append(child)
            visit(child, scope)

    visit(tree, id(tree))
    return scopes


@rule("MX01", "timed-block-until-ready",
      "block_until_ready() bracketed by clock reads folds dispatch and "
      "readback overhead into the figure (or, unfenced in a loop, times "
      "the enqueue); a step's time is read from the profiler's trace "
      "(chipbench/trace_reduce.py), never from a host stopwatch.")
def timed_block_until_ready(ctx: FileContext):
    if "block_until_ready" not in ctx.src:
        return  # cheap text prescreen before the scope traversal
    for calls in _calls_by_scope(ctx.tree).values():
        clock_lines: list[int] = []
        bur_lines: list[int] = []
        for call in calls:
            name = call_name(call)
            if name in _CLOCK_CALLS:
                clock_lines.append(call.lineno)
            elif name == "block_until_ready":
                bur_lines.append(call.lineno)
        if not clock_lines or not bur_lines:
            continue
        lo, hi = min(clock_lines), max(clock_lines)
        for line in bur_lines:
            if lo < line < hi:
                yield line, (
                    "block_until_ready() inside a timed region — read a "
                    "step's time from the profiler's trace "
                    "(chipbench/trace_reduce.py)")


def _help_argument(node: ast.Call) -> ast.AST | None:
    """The help-text argument of a registry factory call, wherever it
    sits: second positional (after a positional name), first positional
    (when the name went by keyword), or the ``help_text`` keyword."""
    for kw in node.keywords:
        if kw.arg == "help_text":
            return kw.value
    has_name_kwarg = any(kw.arg == "name" for kw in node.keywords)
    positional_help_idx = 0 if has_name_kwarg else 1
    if len(node.args) > positional_help_idx:
        return node.args[positional_help_idx]
    return None


@rule("MX02", "metric-help-text",
      "Every registry.counter/gauge/histogram call must pass non-empty "
      "help text — a series without HELP is unreadable on a dashboard "
      "six months later. Applies however the name is spelled (positional, "
      "keyword, f-string, variable).")
def metric_help_text(ctx: FileContext):
    if ctx.path.name == "metrics.py" and ctx.path.parent.name == "obs":
        return
    for node in ctx.walk():
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr in _METRIC_FACTORIES):
            continue
        # Only treat it as a registry factory when it plausibly passes a
        # metric name (any first arg / name kwarg); `x.counter()` with no
        # args is something else entirely.
        if not node.args and not any(kw.arg == "name" for kw in node.keywords):
            continue
        help_arg = _help_argument(node)
        empty = help_arg is None or (
            isinstance(help_arg, ast.Constant) and not help_arg.value)
        if empty:
            yield node.lineno, (
                "metric registered without help text — pass a non-empty "
                "description so the series is readable on /metrics")


def _function_qualnames(ctx: FileContext):
    """(qualname, FunctionDef) for every function, with class nesting
    reflected dotted (`Cls.method`, `Cls.method.inner`) — computed once
    per file (MX04 and MX08 both consume it)."""
    cached = ctx.__dict__.get("_func_quals")
    if cached is not None:
        return cached

    # Defs only ever appear in statement positions, so descend through
    # statement-body fields and skip expression subtrees entirely — the
    # bulk of the node count.
    def child_stmts(node):
        for name in ("body", "orelse", "finalbody"):
            yield from getattr(node, name, ())
        for handler in getattr(node, "handlers", ()):
            yield from handler.body
        for case in getattr(node, "cases", ()):
            yield from case.body

    def walk(node, prefix):
        for child in child_stmts(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                yield qual, child
                yield from walk(child, f"{qual}.")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.")
            else:
                yield from walk(child, prefix)

    cached = tuple(walk(ctx.tree, ""))
    ctx.__dict__["_func_quals"] = cached
    return cached


def _has_hot_loop_marker(ctx: FileContext, node: ast.AST) -> bool:
    lines = ctx.lines()
    for lineno in (node.lineno, node.lineno - 1):
        if 1 <= lineno <= len(lines) and _HOT_LOOP_MARKER in lines[lineno - 1]:
            return True
    return False


@rule("MX04", "hot-loop-alloc",
      "Per-batch numpy allocations (np.zeros/np.empty/np.full/"
      "np.ascontiguousarray/...) inside a registered hot-loop function "
      "put the allocator back on the serving loop the staging arenas "
      "removed. Acquire buffers from an arena pool (serve/arena.py) or "
      "pad via pad_batch(out=...); a deliberate cold path carries a "
      "scoped `# noqa: MX04`. Functions register in _HOT_LOOP_REGISTRY "
      "or with an `# analysis: hot-loop` marker on the def line.")
def hot_loop_alloc(ctx: FileContext):
    registered = frozenset()
    for suffix, quals in _HOT_LOOP_REGISTRY.items():
        if ctx.relpath.endswith(suffix):
            registered = quals
            break
    for qual, node in _function_qualnames(ctx):
        if qual not in registered and not _has_hot_loop_marker(ctx, node):
            continue
        for sub in ctx.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            if not (isinstance(fn, ast.Attribute)
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in _NP_ALIASES
                    and fn.attr in _NP_ALLOCATORS):
                continue
            yield sub.lineno, (
                f"per-batch {fn.value.id}.{fn.attr}() allocation in "
                f"hot-loop `{qual}` — source the buffer from an arena "
                "pool (serve/arena.py) or pass pad_batch(out=...)")


# MX05: metric *labels* are a cartesian dimension — every distinct value
# mints a new time series forever. Identifier-shaped values (account ids,
# decision ids, trace ids, ...) are unbounded, so one busy day melts the
# scrape. The sanctioned high-cardinality channel is the EXEMPLAR (one
# trace id per bucket, bounded by construction) — the `exemplar=` kwarg
# is exempt.
_METRIC_WRITE_METHODS = {"inc", "set", "observe", "observe_many"}
_NON_LABEL_KWARGS = {"exemplar", "value", "timeout"}
_UNBOUNDED_IDENTIFIERS = {
    "account_id", "player_id", "decision_id", "trace_id", "span_id",
    "parent_id", "session_id", "request_id", "transaction_id", "tx_id",
    "idempotency_key", "device_id", "fingerprint", "round_id", "game_id",
}


def _unbounded_mention(node: ast.AST) -> str | None:
    """An identifier-shaped name appearing anywhere in a label-value
    expression (bare name, attribute access, f-string interpolation)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _UNBOUNDED_IDENTIFIERS:
            return sub.id
        if isinstance(sub, ast.Attribute) and sub.attr in _UNBOUNDED_IDENTIFIERS:
            return sub.attr
    return None


@rule("MX05", "metric-label-cardinality",
      "Metric labels must be bounded enumerations: a per-account/"
      "per-decision/per-trace label value mints a new time series per "
      "value and melts the scrape within a day. High-cardinality "
      "click-through belongs in the exemplar channel (`exemplar=`, "
      "bounded at one per bucket), the flight recorder, or the ledger — "
      "never in a label.")
def metric_label_cardinality(ctx: FileContext):
    if "igaming_platform_tpu" not in ctx.path.parts:
        return
    for node in ctx.walk():
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr in _METRIC_WRITE_METHODS):
            continue
        # `self.observe(...)` is a method of the enclosing class (the
        # SLO engine's sample intake, a detector, ...), not a metric
        # write: metric objects are always attributes of something
        # (`self.metrics.x.inc`, `txns.inc`), never `self` itself.
        if isinstance(fn.value, ast.Name) and fn.value.id == "self":
            continue
        for kw in node.keywords:
            if kw.arg is None or kw.arg in _NON_LABEL_KWARGS:
                continue
            if kw.arg in _UNBOUNDED_IDENTIFIERS:
                yield node.lineno, (
                    f"unbounded metric label `{kw.arg}`: one time series "
                    "per value — use a bounded enumeration, or carry the "
                    "id as an exemplar/flight/ledger field")
                continue
            hit = _unbounded_mention(kw.value)
            if hit is not None:
                yield node.lineno, (
                    f"metric label `{kw.arg}` carries unbounded "
                    f"identifier `{hit}`: one time series per value — "
                    "use a bounded enumeration, or carry the id as an "
                    "exemplar/flight/ledger field")


# MX06: wall-clock in deadline/timeout arithmetic. time.time() steps
# backwards under NTP and jumps on slew; a deadline computed from it can
# revive an expired request or expire a live one (and breaks CC06 replay
# determinism when the result is ledgered). The serving path's deadline
# discipline (serve/deadline.py) is monotonic-only.
#
# Scoped per package: serve/ keys on deadline vocabulary; obs/ (the
# measurement plane — tracing spans, the host profiler, cost
# accounting) additionally keys on duration/cost vocabulary, because a
# span duration or µs/row figure computed from two time.time() reads
# inherits every NTP step as a phantom cost spike. Recording a wall
# TIMESTAMP (`created_unix`, `start_unix_s`, exemplar ts) stays quiet in
# both scopes — those names don't match, and tracing.Span carries the
# perf_counter companion clock (mono_start/mono_end) for arithmetic.
_MX06_SCOPES: dict[str, re.Pattern[str]] = {
    "serve": re.compile(r"deadline|timeout|expir|remaining|time_left", re.I),
    "obs": re.compile(
        r"deadline|timeout|expir|remaining|time_left"
        r"|duration|elapsed|pause|latency|(^|_)(ms|us|ns)$", re.I),
}


def _is_wall_clock_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "time"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time")


def _wall_clock_in_arithmetic(stmt: ast.stmt) -> bool:
    """True when a time.time() call sits inside arithmetic or a
    comparison — computing WITH the wall clock rather than recording it.
    Distinguishes `duration_ms = (time.time() - t0) * 1e3` (bad) from
    `{"t_unix": round(time.time(), 3), "duration_ms": dur}` (a record
    statement that merely sits next to a duration field)."""
    for sub in ast.walk(stmt):
        if isinstance(sub, (ast.BinOp, ast.Compare, ast.AugAssign)):
            if any(_is_wall_clock_call(s) for s in ast.walk(sub)):
                return True
    return False


def _mx06_deadline_mention(stmt: ast.stmt, name_re: re.Pattern[str]) -> str | None:
    """A deadline-ish (or, in obs/, duration/cost-ish) identifier
    anywhere in the statement: assignment targets, names, attributes, or
    keyword-argument names."""
    for sub in ast.walk(stmt):
        if isinstance(sub, ast.Name) and name_re.search(sub.id):
            return sub.id
        if isinstance(sub, ast.Attribute) and name_re.search(sub.attr):
            return sub.attr
        if isinstance(sub, ast.keyword) and sub.arg and name_re.search(sub.arg):
            return sub.arg
    return None


@rule("MX06", "wall-clock-deadline",
      "time.time() in deadline/timeout arithmetic on the serving path, "
      "or in duration/cost arithmetic on the measurement plane: the "
      "wall clock steps backwards under NTP and jumps on slew, so a "
      "deadline anchored to it can revive an expired request or expire a "
      "live one (and, ledgered, breaks CC06 replay determinism), and a "
      "span duration / µs-per-row figure computed from it turns every "
      "NTP step into a phantom cost spike. serve/ deadline computations "
      "must use time.monotonic() (serve/deadline.py is the reference "
      "discipline); obs/ profiler and cost arithmetic must use "
      "time.perf_counter() (tracing.Span's mono_start/mono_end "
      "companion clock). Event timestamps that merely RECORD wall time "
      "are fine — the rule keys on the statement also naming a "
      "deadline/timeout/expiry (or, in obs/, duration/elapsed/pause/"
      "latency/*_ms/*_us) quantity.")
def wall_clock_deadline(ctx: FileContext):
    parts = ctx.path.parts
    if "igaming_platform_tpu" not in parts:
        return
    if "time.time" not in ctx.src:
        return  # the rule keys on time.time() only — cheap prescreen
    scope = next((s for s in _MX06_SCOPES if s in parts), None)
    if scope is None:
        return
    name_re = _MX06_SCOPES[scope]
    for node in ctx.walk():
        if not isinstance(node, ast.stmt):
            continue
        calls = [sub for sub in ast.walk(node)
                 if _is_wall_clock_call(sub)
                 # own statement only, not nested statements' calls
                 ]
        if not calls:
            continue
        # Anchor on the narrowest statement containing the call so one
        # function body doesn't multi-report through its parents.
        if any(isinstance(child, ast.stmt) for child in ast.walk(node)
               if child is not node and any(
                   _is_wall_clock_call(s) for s in ast.walk(child))):
            continue
        # obs/ additionally requires the wall clock to participate in
        # the arithmetic: the measurement plane legitimately RECORDS
        # wall timestamps (`t_unix`) right next to already-computed
        # `*_ms` fields, and those record statements must stay quiet.
        if scope == "obs" and not _wall_clock_in_arithmetic(node):
            continue
        hit = _mx06_deadline_mention(node, name_re)
        if hit is not None:
            kind, fix = (
                ("deadline-ish", "time.monotonic() (serve/deadline.py)")
                if scope == "serve" else
                ("duration/cost", "time.perf_counter() "
                 "(tracing.Span.mono_start)"))
            yield calls[0].lineno, (
                f"time.time() feeding {kind} quantity `{hit}` — "
                f"wall clock steps under NTP; anchor to {fix}")


@rule("MX03", "orphan-metric",
      "Production code must construct metrics via "
      "Registry.counter/gauge/histogram: a bare Counter()/Gauge()/"
      "Histogram() never joins a Registry, so it silently never renders "
      "on /metrics. Tests may (unit-testing the classes is their job).")
def orphan_metric(ctx: FileContext):
    if ctx.path.name == "metrics.py" and ctx.path.parent.name == "obs":
        return
    if "igaming_platform_tpu" not in ctx.path.parts:
        return
    metric_imports: set[str] = set()
    for node in ctx.walk():
        if (isinstance(node, ast.ImportFrom) and node.module
                and node.module.endswith("obs.metrics")):
            for alias in node.names:
                if alias.name in _METRIC_CLASSES:
                    metric_imports.add(alias.asname or alias.name)
    if not metric_imports:
        return
    for node in ctx.walk():
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in metric_imports):
            yield node.lineno, (
                "orphan metric: construct via Registry.counter/gauge/"
                f"histogram (a bare {node.func.id}() never renders "
                "on /metrics)")


# MX08: placement of profiling hooks. The observatory (obs/hostprof.py)
# exists precisely so that nobody ever has to reach for these:
#
#   * sys.setprofile/settrace + threading.setprofile/settrace install a
#     callback on EVERY call/line bytecode event process-wide — a 2-10x
#     interpreter tax on the scoring loop while "just measuring";
#     tracemalloc.start() hooks the allocator the same way.
#   * sys._current_frames() snapshots every thread's stack under the
#     GIL; gc.callbacks run inside the collector's pause window.
#
# Inside a jit root the hook additionally fires at TRACE time (it
# measures compilation, then bakes nothing into the graph); inside a
# registered hot loop (MX04's registry / `# analysis: hot-loop`) it
# turns the per-batch path into a profiler. The sanctioned seam is
# obs/hostprof.py: a sampler THREAD reads frames only for threads in the
# explicit scoring-thread registry, at a bounded HOSTPROF_HZ, and the
# one gc.callbacks hook does O(1) bookkeeping.
_MX08_GLOBAL_HOOKS = {
    "sys.setprofile", "sys.settrace",
    "threading.setprofile", "threading.settrace",
    "tracemalloc.start",
}
_MX08_SAMPLING_HOOKS = {"sys._current_frames", "gc.callbacks.append"}
_MX08_SANCTIONED_SUFFIX = "igaming_platform_tpu/obs/hostprof.py"
# Raw-text gate: every hook's attribute tail. A file whose source never
# mentions one of these cannot contain a hook call, so the rule skips
# its tree walks entirely (the hooks are vanishingly rare — this keeps
# a project-scope rule out of the <15s tier-1 analysis budget).
_MX08_TEXT_HINTS = ("setprofile", "settrace", "tracemalloc",
                    "_current_frames", "callbacks")


def _mx08_may_contain(src: str) -> bool:
    return any(hint in src for hint in _MX08_TEXT_HINTS)


def _mx08_hook(node: ast.AST) -> str | None:
    if not isinstance(node, ast.Call):
        return None
    dn = dotted_name(node.func)
    if dn in _MX08_GLOBAL_HOOKS or dn in _MX08_SAMPLING_HOOKS:
        return dn
    return None


@rule("MX08", "profiling-hook-placement",
      "Profiling hooks never go on the scoring path. "
      "sys.setprofile/settrace (and threading's) tax every bytecode "
      "event process-wide; tracemalloc hooks the allocator; "
      "sys._current_frames() snapshots all stacks under the GIL; "
      "gc.callbacks run inside the collector's pause. Inside a jit root "
      "they fire at trace time and measure compilation; inside a "
      "registered hot loop they turn the per-batch path into a "
      "profiler. Host profiling goes through obs/hostprof.py — the "
      "registry-gated sampling thread (register_scoring_thread + "
      "HOSTPROF_HZ) and its single GC callback — which is the one "
      "production file sanctioned to own these hooks.",
      scope="project")
def profiling_hook_placement(project: ProjectContext):
    from tools.analysis.jaxgraph import jax_graph

    graph = jax_graph(project)
    seen: set[tuple[str, int]] = set()

    def fresh(ctx, lineno) -> bool:
        key = (ctx.relpath, lineno)
        if key in seen:
            return False
        seen.add(key)
        return True

    # (a) Hooks inside jit-traced code — wrong everywhere, including the
    # sanctioned profiler module itself.
    for info in graph.reachable.values():
        if not _mx08_may_contain(info.ctx.src):
            continue
        for sub in info.ctx.walk(info.node):
            hook = _mx08_hook(sub)
            if hook is not None and fresh(info.ctx, sub.lineno):
                yield info.ctx, sub.lineno, (
                    f"profiling hook {hook}() in jit-traced "
                    f"`{info.qualname}` ({info.root_reason}) — it fires "
                    "at trace time and measures compilation; sample from "
                    "outside via obs/hostprof's scoring-thread registry")

    for ctx in project.files:
        if "igaming_platform_tpu" not in ctx.path.parts:
            continue
        if not _mx08_may_contain(ctx.src):
            continue
        registered = frozenset()
        for suffix, quals in _HOT_LOOP_REGISTRY.items():
            if ctx.relpath.endswith(suffix):
                registered = quals
                break
        # (b) Hooks inside a hot-loop region (MX04's registry or the
        # `# analysis: hot-loop` marker) — per-batch profiling inline in
        # the loop, wrong even in obs/.
        hot_hook_owner: dict[int, str] = {}
        for qual, fn_node in _function_qualnames(ctx):
            if qual not in registered and not _has_hot_loop_marker(ctx, fn_node):
                continue
            for sub in ctx.walk(fn_node):
                if _mx08_hook(sub) is not None:
                    hot_hook_owner.setdefault(id(sub), qual)
        sanctioned = ctx.relpath.endswith(_MX08_SANCTIONED_SUFFIX)
        for sub in ctx.walk():
            hook = _mx08_hook(sub)
            if hook is None:
                continue
            if id(sub) in hot_hook_owner:
                if fresh(ctx, sub.lineno):
                    yield ctx, sub.lineno, (
                        f"profiling hook {hook}() in hot-loop "
                        f"`{hot_hook_owner[id(sub)]}` — the per-batch "
                        "path must not profile itself; the hostprof "
                        "sampler thread observes it from outside")
                continue
            # (c) Placement outside jit/hot-loop: process-global hooks
            # are banned in all production code; sampling/GC hooks are
            # allowed only in the sanctioned observatory seam.
            if hook in _MX08_GLOBAL_HOOKS:
                if fresh(ctx, sub.lineno):
                    yield ctx, sub.lineno, (
                        f"process-global profiling hook {hook}() in "
                        "production code — it taxes every call/alloc "
                        "event process-wide; use the registry-gated "
                        "sampler (obs/hostprof.py, HOSTPROF_HZ)")
            elif not sanctioned:
                if fresh(ctx, sub.lineno):
                    yield ctx, sub.lineno, (
                        f"{hook}() outside the sanctioned profiler seam "
                        "— stack snapshots and GC callbacks belong to "
                        "obs/hostprof.py (register_scoring_thread + "
                        "HostProfiler), not ad hoc in production code")
