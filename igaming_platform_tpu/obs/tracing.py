"""Profiling / tracing hooks.

The reference deploys Jaeger but emits no spans (SURVEY.md §5 "tracing is
infrastructure-ready, not wired"); per-request latency is hand-measured.
Here tracing is wired two ways:

- device side: `jax.profiler` trace capture + named step annotations
  (``annotate``) that show up on the TPU timeline;
- host side: lightweight spans (``span``) collected into an in-process
  buffer exportable as JSON — the OTLP-shaped record without requiring an
  OTLP endpoint in the image.

Spans form real traces: a ``span()`` opened while another span is active
on the same thread becomes its CHILD (same trace id, ``parent_id`` set),
and a remote parent can be adopted from a W3C ``traceparent`` header
(``parse_traceparent``/``format_traceparent``) — the propagation contract
the gRPC layer and the multihost work channel use so client, front and
follower spans share one trace. Each ROOT span accumulates the summed
duration of its descendant stages (``stage_totals``), which is what the
flight recorder (obs/flight.py) snapshots per request.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import random
import re
import threading
import time
from collections import deque
from typing import Callable

import jax


# Span ids: a process-random 64-bit base plus a counter (``next`` on an
# ``itertools.count`` is one GIL-atomic step), formatted to 16 hex digits
# only when somebody reads ``span_id``. Trace ids come from a generator
# seeded once from the OS at import: no span pays a system call for an id.
_SPAN_ID_MASK = (1 << 64) - 1
_SPAN_SEQ = itertools.count(1)
_TRACE_RNG = random.Random()
_SPAN_ID_BASE = 0


def _seed_ids() -> None:
    global _SPAN_ID_BASE
    seed = random.SystemRandom().getrandbits(192)
    _SPAN_ID_BASE = seed & _SPAN_ID_MASK
    _TRACE_RNG.seed(seed >> 64)


_seed_ids()
os.register_at_fork(after_in_child=_seed_ids)  # a forked child mints its own


def _new_trace_id() -> str:
    return "%032x" % (_TRACE_RNG.getrandbits(128) or 1)


# Thread CPU is read on one trace in CPU_SAMPLE_EVERY (the root decides,
# its spans follow) and counted CPU_SAMPLE_EVERY-fold by whoever sums it.
# ``time.thread_time()`` is a real system call: 0.4 us on a bare kernel but
# 6 us in the sandboxed machines the chips sit in (PERF.md, PR 38), twice a
# span. Seven shares no period with frame sizes that come in pairs.
CPU_SAMPLE_EVERY = 7
_ROOT_SEQ = itertools.count()


class Span:
    """One host span. ``with span(...)`` builds it, and it is its own
    context manager (no generator frame per span).

    ``start``/``end`` are wall stamps for display ("when did this
    happen"), derived from the monotonic pair and the root's one
    wall-clock reading; DURATIONS come from the monotonic pair alone —
    time.time() steps under NTP, and a slew mid-span would mint a
    negative or inflated stage cost that the µs/row accounting
    (obs/hostprof.py) would then publish as fact. MX06 (obs scope)
    enforces this split going forward.

    Exclusive accounting: a span that ends on its parent's thread adds
    its duration and its thread CPU to the parent's ``child_s`` /
    ``child_cpu_s``, so ``self_s`` (duration minus children) and
    ``self_cpu_s`` (thread CPU minus children's) are what THIS span's
    own code cost. A child attached with ``parent=`` from another thread
    runs beside its parent and is not subtracted. Thread CPU is read
    only where ``cpu_sampled`` says so (``CPU_SAMPLE_EVERY``).

    Root spans only: ``stage_totals`` holds summed child-stage durations
    (ms) by span name — the per-request decomposition the flight
    recorder snapshots — and ``stage_windows`` the (start, end) of each
    completed descendant stage on the MONOTONIC clock. With pipelined
    serving, stages of one request run CONCURRENTLY on different worker
    threads, so the busy-time sum (stage_totals) can exceed the
    request's wall time; the interval union of these windows is the
    honest "time attributed to stages" figure, and 1 - union/sum is the
    request's host-stage overlap ratio.
    """

    __slots__ = (
        "name", "start", "end", "mono_start", "mono_end", "trace_id",
        "attributes", "stage_totals", "stage_windows", "root",
        "child_s", "child_cpu_s", "children", "cpu_s", "cpu_sampled",
        "_sid", "_span_id", "_parent", "_parent_id", "_collector",
        "_cpu_start", "_tid", "_token", "_wall_offset")

    def __init__(self, name: str, start: float = 0.0, end: float = 0.0,
                 mono_start: float = 0.0, mono_end: float = 0.0,
                 trace_id: str = "", span_id: str = "", parent_id: str = "",
                 attributes: dict | None = None):
        self.name = name
        self.start = start            # wall stamps (unix seconds)
        self.end = end
        self.mono_start = mono_start  # time.perf_counter()
        self.mono_end = mono_end
        self.trace_id = trace_id
        self.attributes = {} if attributes is None else attributes
        self.stage_totals = None
        self.stage_windows = None
        self.root = None
        self.child_s = 0.0       # same-thread children's summed duration
        self.child_cpu_s = 0.0   # ... and thread CPU
        self.children = 0
        self.cpu_s = 0.0         # this span's thread CPU, children included
        self.cpu_sampled = False  # whether this trace reads thread CPU
        self._sid = 0            # span id as a number; span_id formats it
        self._span_id = span_id
        self._parent = None
        self._parent_id = parent_id  # a remote parent's (traceparent)
        self._collector = None
        self._tid = 0            # ident of the thread it was entered on
        self._wall_offset = 0.0  # wall clock minus perf_counter, per trace

    def __repr__(self) -> str:
        return (f"Span(name={self.name!r}, trace_id={self.trace_id!r}, "
                f"span_id={self.span_id!r}, parent_id={self.parent_id!r}, "
                f"duration_ms={self.duration_ms:.3f})")

    @property
    def span_id(self) -> str:
        sid = self._span_id
        if not sid and self._sid:
            sid = self._span_id = "%016x" % self._sid
        return sid

    @property
    def parent_id(self) -> str:
        parent = self._parent
        return parent.span_id if parent is not None else self._parent_id

    @property
    def duration_ms(self) -> float:
        if self.mono_end:
            return (self.mono_end - self.mono_start) * 1000.0
        return (self.end - self.start) * 1000.0

    @property
    def self_s(self) -> float:
        """Exclusive wall seconds: duration minus same-thread children."""
        return max(0.0, self.mono_end - self.mono_start - self.child_s)

    @property
    def self_cpu_s(self) -> float:
        """Exclusive thread-CPU seconds."""
        return max(0.0, self.cpu_s - self.child_cpu_s)

    # -- the context manager ------------------------------------------------

    def __enter__(self) -> "Span":
        self._token = _CURRENT.set(self)
        tid = self._tid = threading.get_ident()
        _ACTIVE_BY_THREAD[tid] = self
        if self.cpu_sampled:
            self._cpu_start = time.thread_time()
        mono = self.mono_start = time.perf_counter()
        self.start = mono + self._wall_offset
        return self

    def __exit__(self, *exc) -> bool:
        mono_end = self.mono_end = time.perf_counter()
        if self.cpu_sampled:
            self.cpu_s = time.thread_time() - self._cpu_start
        self.end = mono_end + self._wall_offset
        _CURRENT.reset(self._token)
        # span() and carry() set the context and the thread's entry
        # together, so what the context holds again is what was active
        tid = self._tid
        prior = _CURRENT.get()
        if prior is not None:
            _ACTIVE_BY_THREAD[tid] = prior
        else:
            _ACTIVE_BY_THREAD.pop(tid, None)
        parent = self._parent
        if parent is not None and parent._tid == tid:
            # only this thread touches its own chain: no lock
            parent.child_s += mono_end - self.mono_start
            parent.child_cpu_s += self.cpu_s
            parent.children += 1
        self._complete()
        return False

    def _complete(self) -> None:
        """Everything a finished span feeds: the ring, its root's stage
        accounting, the span sinks, and for a root the root sinks. A
        failing sink must never fail the traced request."""
        collector = self._collector or DEFAULT_COLLECTOR
        collector.add(self)
        root = self.root
        if root is not self and root is not None and root.stage_totals is not None:
            name = self.name
            with _STAGE_LOCK:
                totals = root.stage_totals
                totals[name] = totals.get(name, 0.0) + (
                    self.mono_end - self.mono_start) * 1000.0
                windows = root.stage_windows
                if windows is not None and len(windows) < _MAX_STAGE_WINDOWS:
                    windows.append((self.mono_start, self.mono_end))
        if _SPAN_SINK is not None:
            try:
                _SPAN_SINK(self)
            except Exception:  # noqa: BLE001 — sinks must not fail requests
                pass
        for sink in _EXTRA_SPAN_SINKS:
            try:
                sink(self)
            except Exception:  # noqa: BLE001 — sinks must not fail requests
                pass
        if root is self:
            collector.report_drops()
            if _ROOT_SINK is not None:
                try:
                    _ROOT_SINK(self)
                except Exception:  # noqa: BLE001 — sinks must not fail requests
                    pass
            for sink in _EXTRA_ROOT_SINKS:
                try:
                    sink(self)
                except Exception:  # noqa: BLE001 — sinks must not fail requests
                    pass


# -- W3C trace context -------------------------------------------------------

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$")


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """W3C ``traceparent`` header -> (trace_id, parent_span_id), or None
    when absent/malformed (a bad header must never fail a request)."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip())
    if m is None:
        return None
    version, trace_id, span_id = m.group(1), m.group(2), m.group(3)
    if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    """(trace_id, span_id) -> W3C ``traceparent`` header (sampled)."""
    return f"00-{trace_id}-{span_id}-01"


# Per-thread active span (contextvars: gRPC worker threads and the
# batcher's launcher/collector threads each carry their own chain).
_CURRENT: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "igaming_current_span", default=None)

# Cross-thread mirror of the active span per thread ident. A contextvar
# is only readable from its own thread; the hostprof stack sampler
# (obs/hostprof.py) needs to ask "what span is thread T inside right
# now?" from the SAMPLER thread to key folded stacks by stage, and the
# GC watch uses it to count rpc.* roots in flight during a pause.
# Plain dict with GIL-atomic get/set/del per key; entries are removed on
# span exit so an idle thread holds no stale span.
_ACTIVE_BY_THREAD: dict[int, Span] = {}


def active_span_of_thread(ident: int) -> Span | None:
    """The span thread ``ident`` is currently inside, from any thread."""
    return _ACTIVE_BY_THREAD.get(ident)


def active_spans_by_thread() -> dict[int, Span]:
    """Snapshot of every thread's active span (sampler/GC attribution)."""
    return dict(_ACTIVE_BY_THREAD)

# Roots accumulate stage_totals/stage_windows from EVERY thread their
# stages run on (pipeline workers included) — one cheap module lock
# serializes those two updates.
_STAGE_LOCK = threading.Lock()
# Bound per-root window accounting: a pathological request with more
# stages than this keeps its totals but stops collecting windows.
_MAX_STAGE_WINDOWS = 4096


def union_duration_ms(windows: list | None) -> float:
    """Total length (ms) of the UNION of (start, end) second intervals —
    wall time covered by at least one stage, immune to double-counting
    when stages overlap."""
    if not windows:
        return 0.0
    total = 0.0
    cur_start = cur_end = None
    for start, end in sorted(windows):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    total += cur_end - cur_start
    return total * 1000.0

# Completion hooks. _SPAN_SINK fires for EVERY completed span (the metrics
# layer feeds per-stage latency histograms from it); _ROOT_SINK fires for
# completed ROOT spans only (the flight recorder). Both are best-effort:
# a failing sink must never fail the traced request. The _EXTRA_* lists
# let independent observers (the SLO engine, device-runtime telemetry)
# ride the same completion events without fighting over the primary
# slot — add/remove are idempotent, and extras fire AFTER the primary.
_SPAN_SINK: Callable[[Span], None] | None = None
_ROOT_SINK: Callable[[Span], None] | None = None
_EXTRA_SPAN_SINKS: tuple[Callable[[Span], None], ...] = ()
_EXTRA_ROOT_SINKS: tuple[Callable[[Span], None], ...] = ()


def set_span_sink(fn: Callable[[Span], None] | None) -> None:
    global _SPAN_SINK
    _SPAN_SINK = fn


def set_root_sink(fn: Callable[[Span], None] | None) -> None:
    global _ROOT_SINK
    _ROOT_SINK = fn


def add_span_sink(fn: Callable[[Span], None]) -> None:
    global _EXTRA_SPAN_SINKS
    if fn not in _EXTRA_SPAN_SINKS:
        _EXTRA_SPAN_SINKS += (fn,)


def remove_span_sink(fn: Callable[[Span], None]) -> None:
    global _EXTRA_SPAN_SINKS
    _EXTRA_SPAN_SINKS = tuple(f for f in _EXTRA_SPAN_SINKS if f != fn)


def add_root_sink(fn: Callable[[Span], None]) -> None:
    global _EXTRA_ROOT_SINKS
    if fn not in _EXTRA_ROOT_SINKS:
        _EXTRA_ROOT_SINKS += (fn,)


def remove_root_sink(fn: Callable[[Span], None]) -> None:
    global _EXTRA_ROOT_SINKS
    _EXTRA_ROOT_SINKS = tuple(f for f in _EXTRA_ROOT_SINKS if f != fn)


def current_span() -> Span | None:
    return _CURRENT.get()


def current_traceparent() -> str | None:
    """W3C header for the active span, or None outside any span — what
    gets injected into outbound hops (multihost work frames)."""
    s = _CURRENT.get()
    if s is None:
        return None
    return format_traceparent(s.trace_id, s.span_id)


def set_root_attribute(key: str, value) -> None:
    """Attach an attribute to the CURRENT trace's root span (e.g. the row
    count, known only deep in a handler). No-op outside a span."""
    s = _CURRENT.get()
    if s is not None and s.root is not None:
        s.root.attributes[key] = value


def bump_root_attribute_of(s: "Span | None", key: str, delta: float = 1) -> None:
    """Numerically increment an attribute on ``s``'s ROOT span, safely
    across threads (pipeline stage workers and the RPC handler both touch
    the same root). Used for per-request accounting like the device
    dispatches an RPC issued — the flight recorder snapshots the final
    value when the root completes."""
    if s is None:
        return
    root = s.root if s.root is not None else s
    with _STAGE_LOCK:
        root.attributes[key] = root.attributes.get(key, 0) + delta


class SpanCollector:
    """In-process span buffer (bounded ring)."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._spans: deque[Span] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        # Past capacity the OLDEST span is evicted per add (O(1): the
        # deque drops it); that loss is counted (and surfaced as
        # <service>_spans_dropped_total via on_drop) so a sampling gap in
        # /debug/spans or the OTLP export is visible.
        self.dropped_total = 0
        self.on_drop: Callable[[int], None] | None = None
        self._unreported = 0  # drops on_drop has not been told of yet

    def add(self, span: Span) -> None:
        with self._lock:
            spans = self._spans
            if len(spans) == self.capacity:
                self.dropped_total += 1
                self._unreported += 1
            spans.append(span)

    def report_drops(self) -> None:
        """Tell ``on_drop`` of the drops since it was last told: once per
        completed root and whenever the ring is read, not once per span
        (a full ring drops one span per add)."""
        if not self._unreported:
            return
        with self._lock:
            dropped, self._unreported = self._unreported, 0
            on_drop = self.on_drop
        if dropped and on_drop is not None:
            try:
                on_drop(dropped)
            except Exception:  # noqa: BLE001 — metrics must not fail tracing
                pass

    def recent(self) -> list[Span]:
        """The ring as it stands, oldest first; nothing is taken out."""
        with self._lock:
            return list(self._spans)

    def drain(self) -> list[Span]:
        self.report_drops()
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
            return out

    def to_json(self) -> str:
        self.report_drops()
        with self._lock:
            spans = list(self._spans)
        return json.dumps([
            {
                "name": s.name,
                "trace_id": s.trace_id,
                "span_id": s.span_id,
                "parent_id": s.parent_id,
                "start_unix_s": s.start,
                "duration_ms": s.duration_ms,
                "attributes": s.attributes,
            }
            for s in spans
        ])


DEFAULT_COLLECTOR = SpanCollector()


def span(name: str, collector: SpanCollector | None = None, *,
         traceparent: str | None = None, parent: Span | None = None,
         **attributes) -> Span:
    """Host-side span around a serving stage: ``with span(...) as s``.

    Nested use on one thread links parent/child automatically; a root
    span may instead adopt a remote parent from a ``traceparent`` header
    (client->front->follower propagation). An explicit ``parent``
    attaches a stage running on ANOTHER thread (a pipeline stage worker)
    to its request's span — same trace id, and its duration still lands
    in that root's stage accounting. Roots accumulate child-stage
    durations into ``stage_totals`` (plus their (start, end) windows for
    overlap accounting) and fire the flight-recorder sink.
    """
    s = Span(name, attributes=attributes)
    s._collector = collector
    s._sid = (_SPAN_ID_BASE + next(_SPAN_SEQ)) & _SPAN_ID_MASK or 1
    ctx_parent = _CURRENT.get()
    if ctx_parent is None:
        ctx_parent = parent
    if ctx_parent is not None:
        s._parent = ctx_parent
        s.trace_id = ctx_parent.trace_id
        root = s.root = ctx_parent.root or ctx_parent
        s._wall_offset = root._wall_offset
        s.cpu_sampled = root.cpu_sampled
        return s
    if traceparent is not None:
        parsed = parse_traceparent(traceparent)
        if parsed is not None:
            s.trace_id, s._parent_id = parsed
    if not s.trace_id:
        s.trace_id = _new_trace_id()
    s.stage_totals = {}
    s.stage_windows = []
    s.root = s
    s.cpu_sampled = next(_ROOT_SEQ) % CPU_SAMPLE_EVERY == 0
    # the trace's one reading of the wall clock
    s._wall_offset = time.time() - time.perf_counter()
    return s


def emit_span(name: str, mono_start: float, mono_end: float,
              **attributes) -> Span:
    """A span of something that was watched from outside and never
    entered: it gets a trace of its own and the given ``perf_counter``
    stamps, and goes to the ring and the span sinks as a completed span
    does. No root sink sees it (it is no request), and it touches no
    thread's chain."""
    s = Span(name, mono_start=mono_start, mono_end=mono_end,
             trace_id=_new_trace_id(), attributes=attributes)
    s._sid = (_SPAN_ID_BASE + next(_SPAN_SEQ)) & _SPAN_ID_MASK or 1
    s._tid = threading.get_ident()
    offset = s._wall_offset = time.time() - time.perf_counter()
    s.start, s.end = mono_start + offset, mono_end + offset
    s._complete()
    return s


@contextlib.contextmanager
def carry(parent: "Span | None"):
    """Re-enter a span context on ANOTHER thread (worker pools): stage
    spans opened inside nest under ``parent`` — same trace id, durations
    landing in its root's stage accounting — exactly as if they ran on
    the originating thread. The supervised engine's watchdog pool uses
    this so a guarded wire batch keeps its RPC root (stage attribution
    and the ledger's decision-id root attribute both depend on it)."""
    if parent is None:
        yield
        return
    token = _CURRENT.set(parent)
    ident = threading.get_ident()
    prior = _ACTIVE_BY_THREAD.get(ident)
    _ACTIVE_BY_THREAD[ident] = parent
    try:
        yield
    finally:
        _CURRENT.reset(token)
        if prior is not None:
            _ACTIVE_BY_THREAD[ident] = prior
        else:
            _ACTIVE_BY_THREAD.pop(ident, None)


def annotate(name: str):
    """Named region on the device profile timeline (a context manager)."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Capture a jax.profiler trace (TensorBoard-compatible) for a block."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
