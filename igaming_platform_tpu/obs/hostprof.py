"""Host-plane cost observatory — where the microseconds went.

The device side of the serving stack is accounted to death (telemetry,
step models, MFU); the HOST side only had span-level p50/p99. This
module closes the gap with two tiers, both off the hot path:

Tier A (always on): per-stage **µs/row** accounting. Every completed
``score.*`` stage span (decode, gather, cache_lookup, pad, dispatch,
readback, session, ledger_note, encode, ...) is folded — via a tracing
span sink, so the serving code is untouched — into per-stage
cost-per-row distributions: cumulative totals plus a bounded reservoir
of recent per-span samples. Durations ride the spans' monotonic clock
(``perf_counter``; MX06 enforces this in obs/). Beside each stage's
inclusive ``total_us`` stand its EXCLUSIVE wall (``self_us``: the span's
duration minus the same-thread child spans inside it) and exclusive
thread CPU (``cpu_us``: read on one trace in ``tracing.CPU_SAMPLE_EVERY``
and counted that many times, an estimate that settles over a few hundred
requests); a stage that has had a child also gets a row
``<stage>.self`` whose ``total_us`` is that exclusive wall, so a reader
that sums rows by name can tile an envelope. The same tier watches
the collector: a ``gc.callbacks`` hook records collection counts and
pause-ms per generation, attributing each pause to the rpc.* roots in
flight when it hit (read off the tracing thread-active table), plus
heap gauges (allocated blocks, per-generation counts, peak RSS).

Tier B (on demand / ``HOSTPROF_HZ``): a threading stack sampler over an
explicit scoring-path thread registry. Handler threads auto-register on
their first completed rpc.* root; pipeline stage workers, readback /
ledger / drift / shadow workers call ``register_scoring_thread(role)``.
The sampler reads ``sys._current_frames()`` at HOSTPROF_HZ, keys each
registered thread's stack by its ACTIVE SPAN (so a frame inside
``prepare_chunk`` folds under ``span:score.session``), and accumulates
collapsed-stack (flamegraph) counts exportable as folded text or
speedscope JSON at ``/debug/hostprofz?format=...``. Sampling a thread
NOT in the registry is an analyzer violation (MX08): the registry is
the contract that keeps profiling hooks off jit roots and hot loops.

The heartbeat (always on with Tier A): everything above is recorded when
a span ENDS, so a request that is stuck leaves nothing until it is no
longer stuck. One daemon thread sleeps a fixed 50 ms and reads how late
it woke (the OS's wake-up plus the wait for the interpreter: what every
handler thread pays when it comes back from a lock, a device wait or a
native call), and on the same tick looks for ``rpc.*`` roots open longer
than ``STALL_DUMP_MS``: while one is, it records every thread's stack,
open spans and CPU time (:class:`Heartbeat`, ``/debug/stallz``).

Overhead contract: Tier A is one dict update per completed stage span
(the bench artifact's profiler-on/off A/B holds the e2e ratio ≥ 0.90);
Tier B costs only while running and only for registered threads; the
heartbeat is 20 wakes a second on its own thread and nothing on a
request's path.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import sys
import tempfile
import threading
import time
import weakref
from collections import deque

from igaming_platform_tpu.obs import runtime_telemetry, tracing

logger = logging.getLogger(__name__)

_STAGE_PREFIX = "score."
# Per-stage reservoir of recent per-span µs/row samples — the "rolling
# window" the distributions are computed over. Bounded so an unbounded
# soak cannot grow the profiler.
_SAMPLE_RESERVOIR = 2048
# Folded-stack table bound: pathological stack diversity aggregates
# into the "<other>" key instead of growing without limit.
_MAX_FOLDED_KEYS = 20000
_MAX_STACK_DEPTH = 48
# Bounded ring of recent GC pauses (generation, pause_ms, in-flight
# rpc count, trace ids) for the hostprofz page.
_GC_PAUSE_RING = 256


# ---------------------------------------------------------------------------
# Scoring-path thread registry (Tier B's sampling contract)

_REGISTRY_LOCK = threading.Lock()
# ident -> (role, weak reference to the Thread registered under it). The
# OS hands an ident out again once its thread has ended, so an entry is
# only as good as its thread: one whose thread has ended is dropped
# (``_live``), never inherited by the stranger that got the ident next.
_THREAD_ROLES: dict[int, tuple[str, "weakref.ref | None"]] = {}


def _thread_of(ident: int):
    if ident == threading.get_ident():
        return threading.current_thread()
    return next((t for t in threading.enumerate() if t.ident == ident), None)


def _live(entry) -> bool:
    ref = entry[1]
    if ref is None:  # a thread ``threading`` never saw: nothing to ask
        return True
    thread = ref()
    return thread is not None and thread.is_alive()


def register_scoring_thread(role: str, ident: int | None = None) -> int:
    """Register the calling (or given) thread as a scoring-path thread
    the sampler may profile. ``role`` is a short bounded label
    (``grpc_handler``, ``pipeline_stage``, ``readback``, ``ledger``,
    ``drift``, ``shadow``, ...) that prefixes its folded stacks.
    Idempotent; returns the registered ident. The registration ends
    with the thread: a later thread that the OS gives the same ident is
    not registered by it."""
    if ident is None:
        ident = threading.get_ident()
    thread = _thread_of(ident)
    ref = weakref.ref(thread) if thread is not None else None
    with _REGISTRY_LOCK:
        _THREAD_ROLES[ident] = (str(role), ref)
    return ident


def unregister_scoring_thread(ident: int | None = None) -> None:
    if ident is None:
        ident = threading.get_ident()
    with _REGISTRY_LOCK:
        _THREAD_ROLES.pop(ident, None)


def registered_threads() -> dict[int, str]:
    """Snapshot of {thread ident: role} over the registered threads that
    are still running; the others are dropped here."""
    with _REGISTRY_LOCK:
        for ident in [i for i, e in _THREAD_ROLES.items() if not _live(e)]:
            del _THREAD_ROLES[ident]
        return {ident: e[0] for ident, e in _THREAD_ROLES.items()}


# ---------------------------------------------------------------------------
# Tier B: the stack sampler


def _format_frame(frame) -> str:
    code = frame.f_code
    base = os.path.basename(code.co_filename)
    if base.endswith(".py"):
        base = base[:-3]
    return f"{base}.{code.co_name}"


def _format_stack(frame) -> list[str]:
    """A thread's frames root first (flamegraph convention), the
    innermost ``_MAX_STACK_DEPTH`` of them."""
    parts: list[str] = []
    while frame is not None and len(parts) < _MAX_STACK_DEPTH:
        parts.append(_format_frame(frame))
        frame = frame.f_back
    parts.reverse()
    return parts


class StackSampler:
    """HOSTPROF_HZ stack sampler over the registered scoring threads.

    Folds each sample into ``role;span:<active span>;frame;...;leaf``
    collapsed-stack form. Start/stop on demand (the /debug/profilez
    pattern): one sampler at a time, 409-style refusal handled by the
    caller. The sampler thread itself is a daemon and never touches
    unregistered threads.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._folded: dict[str, int] = {}
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self.hz = 0.0
        self.samples_total = 0
        self.threads_seen: set[str] = set()
        self._started_mono: float | None = None
        self.last_duration_s = 0.0

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self, hz: float) -> bool:
        """Begin sampling at ``hz``. False if already running."""
        if not hz > 0:
            return False
        with self._lock:
            if self.running:
                return False
            self._stop.clear()
            self.hz = float(hz)
            self._started_mono = time.monotonic()
            self._thread = threading.Thread(
                target=self._run, name="hostprof-sampler", daemon=True)
            self._thread.start()
        return True

    def stop(self) -> dict:
        """Stop sampling; returns a summary block."""
        thread = self._thread
        self._stop.set()
        if thread is not None:
            thread.join(timeout=2.0)
        with self._lock:
            if self._started_mono is not None:
                self.last_duration_s = time.monotonic() - self._started_mono
                self._started_mono = None
            self._thread = None
        return self.snapshot()

    def reset(self) -> None:
        with self._lock:
            self._folded.clear()
            self.samples_total = 0
            self.threads_seen.clear()

    def _run(self) -> None:
        interval = 1.0 / self.hz
        while not self._stop.is_set():
            t0 = time.monotonic()
            try:
                self._sample_once()
            except Exception:  # noqa: BLE001 — a sampler bug must never hurt serving
                pass
            elapsed = time.monotonic() - t0
            # A sampling profiler WANTS a fixed cadence — jitter here
            # would bias the stack histogram toward quiet periods.
            self._stop.wait(max(0.001, interval - elapsed))  # noqa: CC05 — deliberate fixed-cadence sampler

    def _sample_once(self) -> None:
        roles = registered_threads()
        if not roles:
            return
        # Sampling seam: reading every thread's frame is the documented,
        # GIL-atomic profiling hook; it runs on the SAMPLER thread only
        # and touches registered scoring threads' frames read-only.
        frames = sys._current_frames()  # noqa: MX08 — the registry-gated sampler itself
        actives = tracing.active_spans_by_thread()
        with self._lock:
            for ident, role in roles.items():
                frame = frames.get(ident)
                if frame is None:
                    continue
                parts = _format_stack(frame)
                span = actives.get(ident)
                span_name = span.name if span is not None else "idle"
                key = ";".join([role, f"span:{span_name}", *parts])
                if key not in self._folded and len(self._folded) >= _MAX_FOLDED_KEYS:
                    key = "<other>"
                self._folded[key] = self._folded.get(key, 0) + 1
                self.samples_total += 1
                self.threads_seen.add(role)

    # -- exports ------------------------------------------------------------

    def folded(self) -> dict[str, int]:
        with self._lock:
            return dict(self._folded)

    @staticmethod
    def _rank(folded: dict[str, int], n: int) -> list[dict]:
        total = sum(folded.values()) or 1
        ranked = sorted(folded.items(), key=lambda kv: kv[1], reverse=True)
        return [{"stack": k, "samples": v, "share": round(v / total, 4)}
                for k, v in ranked[:n]]

    def top_stacks(self, n: int = 20) -> list[dict]:
        return self._rank(self.folded(), n)

    def to_folded_text(self) -> str:
        """Classic collapsed-stack format (``stack count`` per line) —
        pipe straight into flamegraph.pl / inferno."""
        folded = self.folded()
        return "\n".join(
            f"{stack} {count}" for stack, count in sorted(folded.items()))

    def to_speedscope(self) -> dict:
        """speedscope.app 'sampled' profile of the folded table."""
        folded = self.folded()
        frame_index: dict[str, int] = {}
        frames: list[dict] = []
        samples: list[list[int]] = []
        weights: list[int] = []
        for stack, count in sorted(folded.items()):
            idxs: list[int] = []
            for name in stack.split(";"):
                idx = frame_index.get(name)
                if idx is None:
                    idx = frame_index[name] = len(frames)
                    frames.append({"name": name})
                idxs.append(idx)
            samples.append(idxs)
            weights.append(count)
        total = sum(weights)
        return {
            "$schema": "https://www.speedscope.app/file-format-schema.json",
            "shared": {"frames": frames},
            "profiles": [{
                "type": "sampled",
                "name": "hostprof",
                "unit": "none",
                "startValue": 0,
                "endValue": total,
                "samples": samples,
                "weights": weights,
            }],
            "exporter": "igaming-platform-tpu hostprof",
        }

    def snapshot(self) -> dict:
        # One lock hold for ALL sampler-written state: samples_total /
        # threads_seen are mutated by the hostprof-sampler thread under
        # _lock, and iterating threads_seen unlocked can raise
        # "set changed size during iteration" mid-sample. Ranking runs
        # on the copies outside the lock (top_stacks re-acquires it).
        with self._lock:
            folded = dict(self._folded)
            samples_total = self.samples_total
            roles_seen = sorted(self.threads_seen)
            last_duration_s = self.last_duration_s
        return {
            "running": self.running,
            "hz": self.hz,
            "samples_total": samples_total,
            "distinct_stacks": len(folded),
            "roles_seen": roles_seen,
            "registered_threads": len(registered_threads()),
            "last_duration_s": round(last_duration_s, 3),
            "top_stacks": self._rank(folded, 20),
        }


# ---------------------------------------------------------------------------
# The heartbeat: how late a runnable thread runs, and who holds a stuck RPC

# One period, no knob: the lateness is only comparable between processes
# that sleep the same time.
HEARTBEAT_S = 0.05
_STALL_MAX_SAMPLES = 40    # samples an incident keeps (identical ones folded)
_STALL_RING = 16           # incidents /debug/stallz keeps, and files on disk
_STALL_MAX_SPANS = 16      # open spans listed for one thread
STALL_KINDS = ("waiting", "interpreter_blocked")


def _stall_threshold_s() -> float:
    try:
        return max(0.0, float(os.environ.get("STALL_DUMP_MS", "500"))) / 1e3
    except ValueError:
        return 0.5


class _Incident:
    """An incident while it is open: the roots it has seen held (the
    Span objects, so the record can say how each one ended) beside what
    the record will keep."""

    __slots__ = ("seq", "start", "opened_unix", "roots", "samples",
                 "signature", "dropped", "late_max", "gap")

    def __init__(self, seq: int, start: float):
        self.seq = seq
        self.start = start
        self.opened_unix = time.time()
        self.roots: dict[int, "tracing.Span"] = {}
        self.samples: list[dict] = []
        self.signature: tuple = ()
        self.dropped = 0
        self.late_max = 0.0
        # wall and process CPU between the two ticks around the latest wake
        self.gap = (0.0, 0.0)


class Heartbeat:
    """A thread that times its own wake-ups and watches for held RPCs.

    Every ``HEARTBEAT_S`` it adds one tick and how late it woke
    (``risk_host_heartbeat_{ticks,late_seconds}_total``). With
    ``STALL_DUMP_MS`` > 0 the same tick looks through the threads' open
    spans for ``rpc.*`` roots older than that. While there is one, an
    INCIDENT is open and each tick records, for every thread of the
    process, its Python stack, its chain of open spans with their ages
    and its CPU clock, until the last such root has closed or
    ``_STALL_MAX_SAMPLES`` samples are kept (a sample equal to the one
    before it is counted, not kept). What it costs is paid on this
    thread and only while an RPC is held: a request's path has no line
    of it.

    An incident says its ``kind``. ``waiting``: the ticks kept time, so
    the interpreter ran and the stacks name who held what (a lock's
    holder, a device wait, a socket). ``interpreter_blocked``: a tick
    itself woke later than the threshold, so no Python thread could run
    (the GIL held in native code, or the process not scheduled); the
    process's CPU over that gap tells the two apart, and each thread's
    own CPU clock over it (``ran``) names who computed. This thread's
    samples come just after the block, not during it: stacks DURING it
    would take ``faulthandler.dump_traceback_later``, whose C thread
    walks the frames of every thread without the GIL. That timer also
    fires when this thread was merely starved, or when a stopped process
    resumes, and then reads threads that are running: it killed 3 of the
    73 boots it was armed in under the chip benchmark (PERF.md, PR 54),
    so the server does not arm it.
    """

    def __init__(self, profiler: "HostProfiler"):
        self._profiler = profiler
        self.stall_s = _stall_threshold_s()
        self.dump_dir = os.environ.get("STALL_DUMP_DIR") or tempfile.gettempdir()
        self.ticks = 0
        self.late_s = 0.0
        self.stalls = dict.fromkeys(STALL_KINDS, 0)
        self.stall_seconds = 0.0  # the held roots' time past the threshold
        # what flush() has already added to the /metrics counters
        self._flushed = {"ticks": 0, "late_s": 0.0, "stall_seconds": 0.0,
                         **dict.fromkeys(STALL_KINDS, 0)}
        self.incidents: deque = deque(maxlen=_STALL_RING)
        self._open: _Incident | None = None
        self._seq = 0
        # the ring, the open incident and what flush() has taken
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._woke = 0.0
        self._cpu_seen = 0.0

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> None:
        if self.running:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="hostprof-heartbeat", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        thread = self._thread
        self._stop.set()
        if thread is not None:
            thread.join(timeout=2.0)
        self._thread = None
        with self._lock:
            self._open = None

    # -- the tick --------------------------------------------------------------

    def _run(self) -> None:
        clock, wait = time.perf_counter, self._stop.wait
        self._woke, self._cpu_seen = clock(), time.process_time()
        while True:
            due = clock() + HEARTBEAT_S
            # A fixed sleep is the measurement: how much later than asked
            # does a thread that becomes runnable get to run.
            if wait(HEARTBEAT_S):  # noqa: CC05 — a heartbeat keeps its period
                return
            self.tick(due, clock())

    def tick(self, due: float, woke: float) -> None:
        """One wake: asked for at ``due``, got at ``woke``."""
        late = max(0.0, woke - due)
        self.ticks += 1
        self.late_s += late
        if self.stall_s:
            try:
                self._watch(due, woke, late)
            except Exception:  # the heartbeat goes on, and says so
                logger.exception("stall watch failed")

    def _watch(self, due: float, woke: float, late: float) -> None:
        cpu = time.process_time()
        gap = (woke - self._woke, cpu - self._cpu_seen)
        self._woke, self._cpu_seen = woke, cpu
        held = live = self._overdue(woke)
        blocked = late >= self.stall_s
        if blocked:
            # nothing ran since the block began: a root that was held may
            # have ended before this thread got its turn
            held = live | self._ended_since(due)
        inc = self._open
        if inc is not None or held:
            closed = None
            with self._lock:
                if inc is None:
                    self._seq += 1
                    first = min(r.mono_start for r in held.values())
                    inc = self._open = _Incident(
                        self._seq, min(woke, first + self.stall_s))
                inc.roots.update(held)
                if late >= inc.late_max:
                    inc.late_max, inc.gap = late, gap
                if live or blocked:  # held now, or over the gap this tick ends
                    self._sample(inc, woke, late, cpu)
                if not live:
                    closed = self._close(inc, woke)
                    self._open = None
            if closed is not None:
                self._publish(closed, inc.start, woke)

    def _overdue(self, now: float) -> dict:
        """The ``rpc.*`` roots open since before ``now - stall_s``."""
        limit = now - self.stall_s
        out = {}
        for span in tracing.active_spans_by_thread().values():
            root = span.root if span.root is not None else span
            if (0.0 < root.mono_start <= limit and not root.mono_end
                    and root.name.startswith("rpc.")):
                out[id(root)] = root
        return out

    def _ended_since(self, due: float) -> dict:
        """The ``rpc.*`` roots past the threshold that ended after this
        tick was due, off the span ring (newest last)."""
        out = {}
        for span in reversed(tracing.DEFAULT_COLLECTOR.recent()):
            if span.mono_end < due:
                break
            if (span.root is span and span.name.startswith("rpc.")
                    and span.mono_end - span.mono_start >= self.stall_s):
                out[id(span)] = span
        return out

    def _sample(self, inc: _Incident, now: float, late: float,
                process_cpu: float) -> None:
        if len(inc.samples) >= _STALL_MAX_SAMPLES:
            inc.dropped += 1
            return
        # EVERY thread, the ones outside the scoring registry too: what a
        # handler waits for may be held by any thread of the process, and
        # this is the one place that may read them. On the heartbeat's
        # thread, read-only, and only while an RPC is held.
        frames = sys._current_frames()  # noqa: MX08 — the stall watch; see above
        cpu_of: dict[int, float] = {}
        for ident in frames:  # first: a thread that ends takes its clock along
            try:
                cpu_of[ident] = time.clock_gettime(
                    time.pthread_getcpuclockid(ident))
            except (AttributeError, OSError, OverflowError):  # noqa: CC04 — no such clock here, or the thread has just ended: it reads None
                pass
        actives = tracing.active_spans_by_thread()
        roles = registered_threads()
        names = {t.ident: t.name for t in threading.enumerate()}
        me = threading.get_ident()
        threads = []
        for ident, frame in frames.items():
            if ident == me:
                continue
            chain = []
            span = actives.get(ident)
            while span is not None and len(chain) < _STALL_MAX_SPANS:
                chain.append(span)
                span = span._parent
            threads.append({
                "thread": roles.get(ident) or f"other:{names.get(ident, ident)}",
                "ident": ident,
                "cpu_ms": (round(cpu_of[ident] * 1e3, 3)
                           if ident in cpu_of else None),
                "spans": [{"name": s.name,
                           "age_ms": round((now - s.mono_start) * 1e3, 1)}
                          for s in reversed(chain)],
                "stack": ";".join(_format_stack(frame)),
            })
        threads.sort(key=lambda t: (not t["spans"], t["thread"], t["ident"]))
        signature = tuple(
            (t["ident"], t["stack"], tuple(s["name"] for s in t["spans"]))
            for t in threads)
        t_ms = round((now - inc.start) * 1e3, 1)
        rt = runtime_telemetry.DEFAULT
        state = {
            "late_ms": round(late * 1e3, 3),
            "process_cpu_ms": round(process_cpu * 1e3, 3),
            "gc_running": bool(self._profiler._gc_start_ns),
            "gc_collections": sum(g["collections"] for g in gc.get_stats()),
            "compiles": (rt.compile_watcher.compiles_total
                         if rt is not None else None),
        }
        if signature == inc.signature:
            last = inc.samples[-1]
            last["count"] += 1
            last["until"] = {"t_ms": t_ms, **state}
            for kept, seen in zip(last["threads"], threads):
                kept["cpu_ms_until"] = seen["cpu_ms"]
            return
        inc.signature = signature
        inc.samples.append({"t_ms": t_ms, "count": 1, **state,
                            "threads": threads})

    # -- an incident's end -------------------------------------------------------

    def _close(self, inc: _Incident, now: float) -> dict:
        """The record of a closed incident, written out, counted and in
        the ring (under ``_lock``)."""
        blocked = inc.late_max >= self.stall_s
        kind = "interpreter_blocked" if blocked else "waiting"
        held, past = [], 0.0
        for root in inc.roots.values():
            duration = (root.mono_end or now) - root.mono_start
            past += max(0.0, duration - self.stall_s)
            held.append({"trace_id": root.trace_id, "method": root.name[4:],
                         "rows": root.attributes.get("rows"),
                         "duration_ms": round(duration * 1e3, 3)})
        record = {
            "id": inc.seq,
            "kind": kind,
            "opened_unix": round(inc.opened_unix, 3),
            "length_ms": round((now - inc.start) * 1e3, 1),
            "threshold_ms": self.stall_s * 1e3,
            "late_max_ms": round(inc.late_max * 1e3, 3),
            "held": held,
            "ran": _who_ran(inc.samples),
            "samples": inc.samples,
            "samples_dropped": inc.dropped,
            "file": None,
        }
        if blocked:
            wall, cpu = inc.gap
            record["blocked"] = {
                "gap_ms": round(wall * 1e3, 1),
                "process_cpu_ms": round(cpu * 1e3, 1),
                # CPU that kept pace with the gap: somebody computed with
                # the GIL held; CPU that stood still: a call that kept the
                # GIL slept, or the whole process was not scheduled
                "reading": ("computing with the GIL held" if cpu > 0.5 * wall
                            else "the GIL held by a call that slept, or the "
                                 "process stopped"),
            }
        # before anyone can read the record: its file is part of it
        record["file"] = self._write_file(record)
        self.stalls[kind] += 1
        self.stall_seconds += past
        self.incidents.append(record)
        return record

    def _write_file(self, record: dict) -> str | None:
        path = os.path.join(
            self.dump_dir,
            f"stall-{os.getpid()}-{record['id'] % _STALL_RING:02d}.txt")
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
            with open(path, "w", encoding="utf-8") as f:
                f.write(render_incident(record))
        except OSError:
            logger.warning("stall watch: could not write %s", path,
                           exc_info=True)
            return None
        return path

    def _publish(self, record: dict, start: float, end: float) -> None:
        """Where a closed incident goes beside the ring and its file: one
        WARNING line and one ``host.stall`` span."""
        trace_ids = [h["trace_id"] for h in record["held"]]
        logger.warning(
            "rpc stall #%d kind=%s length_ms=%.0f held=%d trace_ids=%s file=%s",
            record["id"], record["kind"], record["length_ms"], len(trace_ids),
            ",".join(trace_ids), record["file"])
        # on the spans' clock, so /debug/spans, OTLP and a device trace's
        # idle gaps see the stall where it was
        tracing.emit_span("host.stall", start, end, kind=record["kind"],
                          samples=len(record["samples"]),
                          trace_ids=",".join(trace_ids))

    # -- readers -----------------------------------------------------------------

    def flush(self, m) -> None:
        """What has grown since the last render, onto the four counters.
        Two renders at once (the sidecar serves each on a thread of its
        own) must not both take the same growth."""
        with self._lock:
            seen, kept = {"ticks": self.ticks, "late_s": self.late_s,
                          "stall_seconds": self.stall_seconds,
                          **self.stalls}, self._flushed
            grown = {k: v - kept[k] for k, v in seen.items()}
            self._flushed = seen
        if grown["ticks"]:
            m.host_heartbeat_ticks_total.inc(grown["ticks"])
            m.host_heartbeat_late_seconds_total.inc(grown["late_s"])
        for kind in STALL_KINDS:
            if grown[kind]:
                m.rpc_stalls_total.inc(grown[kind], kind=kind)
        if grown["stall_seconds"]:
            m.rpc_stall_seconds_total.inc(grown["stall_seconds"])

    def summary(self) -> dict:
        """The counts, without the incidents (``/debug/hostprofz``)."""
        ticks = self.ticks
        return {
            "running": self.running,
            "heartbeat_ms": HEARTBEAT_S * 1e3,
            "threshold_ms": self.stall_s * 1e3,
            "dump_dir": self.dump_dir,
            "ticks": ticks,
            "wake_late_us": round(self.late_s / ticks * 1e6, 3) if ticks else None,
            "stalls": dict(self.stalls),
            "stall_seconds": round(self.stall_seconds, 6),
        }

    def snapshot(self) -> dict:
        """``/debug/stallz``: the counts, the incident that is open now
        and the ring of closed ones."""
        with self._lock:
            incidents = list(self.incidents)
            inc = self._open
            open_now = None if inc is None else {
                "id": inc.seq,
                "open_ms": round((time.perf_counter() - inc.start) * 1e3, 1),
                "trace_ids": [r.trace_id for r in inc.roots.values()],
                "samples": list(inc.samples),
            }
        return {**self.summary(), "open": open_now, "incidents": incidents}


def _who_ran(samples: list[dict]) -> list[dict]:
    """The threads whose CPU clock moved over the incident, most first:
    a thread that ran, told from the ones that slept."""
    first: dict[int, tuple] = {}
    last: dict[int, float] = {}
    for sample in samples:
        for t in sample["threads"]:
            if t["cpu_ms"] is None:
                continue
            first.setdefault(t["ident"], (t["cpu_ms"], t["thread"], t["stack"]))
            last[t["ident"]] = t.get("cpu_ms_until") or t["cpu_ms"]
    ran = [{"thread": thread, "cpu_ms": round(last[ident] - cpu0, 3),
            "leaf": stack.rsplit(";", 1)[-1]}
           for ident, (cpu0, thread, stack) in first.items()
           if last[ident] - cpu0 >= 1.0]
    return sorted(ran, key=lambda r: -r["cpu_ms"])[:5]


def render_incident(record: dict) -> str:
    """An incident as text: what the file under ``STALL_DUMP_DIR`` holds."""
    lines = [
        f"rpc stall #{record['id']} kind={record['kind']} "
        f"length_ms={record['length_ms']} threshold_ms={record['threshold_ms']} "
        f"late_max_ms={record['late_max_ms']} pid={os.getpid()} "
        f"opened_unix={record['opened_unix']}"]
    if "blocked" in record:
        lines.append("blocked: " + json.dumps(record["blocked"]))
    for h in record["held"]:
        lines.append("held: " + json.dumps(h))
    for r in record["ran"]:
        lines.append("ran: " + json.dumps(r))
    for i, sample in enumerate(record["samples"]):
        head = {k: v for k, v in sample.items() if k != "threads"}
        lines.append(f"sample {i}: " + json.dumps(head))
        for t in sample["threads"]:
            spans = " > ".join(f"{s['name']} ({s['age_ms']} ms)"
                               for s in t["spans"]) or "-"
            cpu = t["cpu_ms"]
            if "cpu_ms_until" in t:
                cpu = f"{cpu} -> {t['cpu_ms_until']}"
            lines.append(f"  {t['thread']} [{t['ident']}] cpu_ms {cpu}")
            lines.append(f"    spans: {spans}")
            lines.append(f"    stack: {t['stack']}")
    if record["samples_dropped"]:
        lines.append(f"ticks not sampled (the incident had its "
                     f"{_STALL_MAX_SAMPLES}): {record['samples_dropped']}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Tier A: µs/row stage accounting + GC/heap watch


class _StageAcc:
    __slots__ = ("key", "spans", "rows", "total_us", "self_us", "cpu_us",
                 "has_children", "flushed_self_us", "flushed_cpu_us",
                 "samples")

    def __init__(self, stage: str = ""):
        self.key = (("stage", stage),)  # its labelset on /metrics
        self.spans = 0
        self.rows = 0
        self.total_us = 0.0
        # Exclusive both: what the stage's own code cost, its same-thread
        # child spans taken out (tracing.Span.child_s / child_cpu_s).
        self.self_us = 0.0
        self.cpu_us = 0.0
        # A span of this stage has had a child: the stage also shows as
        # ``<stage>.self``.
        self.has_children = False
        # What flush_counters has already added to the /metrics counters.
        self.flushed_self_us = 0.0
        self.flushed_cpu_us = 0.0
        # Recent per-span µs/row samples (rolling window).
        self.samples: deque = deque(maxlen=_SAMPLE_RESERVOIR)


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class HostProfiler:
    """Always-on Tier A accounting + the Tier B sampler, one object.

    Installed once (``install()``/``get_default()``): rides the tracing
    module's extra span sink — never wraps serving code — and a
    ``gc.callbacks`` hook. ``HOSTPROF=0`` disables Tier A entirely, the
    heartbeat with it; ``HOSTPROF_HZ>0`` starts the sampler at boot.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._stages: dict[str, _StageAcc] = {}
        self._rpc = _StageAcc()
        self.sampler = StackSampler()
        self.heartbeat = Heartbeat(self)
        self.metrics = None
        self._installed = False
        self._gc_installed = False
        # GC accounting (all guarded by _lock except the start stamp,
        # which only the collecting thread touches while it holds the GIL,
        # and the queue between the GC hook and _drain_gc; bounded, so a
        # process that completes no span and takes no snapshot cannot
        # grow it without end).
        self._gc_start_ns: dict[int, int] = {}
        self._gc_pending: deque = deque(maxlen=65_536)
        self._gc_collections: dict[int, int] = {}
        self._gc_pause_ms_total: dict[int, float] = {}
        self._gc_pauses: deque = deque(maxlen=_GC_PAUSE_RING)
        self._gc_pauses_in_rpc = 0
        self._gc_pause_in_rpc_ms = 0.0

    # -- install -------------------------------------------------------------

    def install(self, metrics=None) -> "HostProfiler":
        if metrics is not None:
            self.bind_metrics(metrics)
        if not self.enabled or self._installed:
            return self
        self._installed = True
        tracing.add_span_sink(self._on_span)
        self.install_gc_watch()
        self.heartbeat.start()
        return self

    def uninstall(self) -> None:
        if self._installed:
            tracing.remove_span_sink(self._on_span)
            self._installed = False
        if self._gc_installed:
            try:
                gc.callbacks.remove(self._gc_callback)
            except ValueError:
                pass
            self._gc_installed = False
        if self.sampler.running:
            self.sampler.stop()
        self.heartbeat.stop()

    def bind_metrics(self, metrics) -> None:
        """Attach a ServiceMetrics so stage costs / GC pauses land on
        /metrics next to the rest of the serving series."""
        old = self.metrics
        if old is not None and old is not metrics and hasattr(old, "registry"):
            old.registry.remove_refresher(self.flush_counters)
        self.metrics = metrics
        if hasattr(metrics, "registry"):
            metrics.registry.add_refresher(self.flush_counters)

    def flush_counters(self) -> None:
        """Registry refresher: the exclusive wall and CPU each stage has
        gathered since the last render, onto
        ``host_stage_{self,cpu}_seconds_total``, and the heartbeat's ticks,
        lateness and stalls onto their four. Off the request path."""
        m = self.metrics
        if m is None:
            return
        self.heartbeat.flush(m)
        with self._lock:
            grown = []
            for stage, acc in self._stages.items():
                grown.append((stage, acc.self_us - acc.flushed_self_us,
                              acc.cpu_us - acc.flushed_cpu_us))
                acc.flushed_self_us, acc.flushed_cpu_us = acc.self_us, acc.cpu_us
        for stage, self_us, cpu_us in grown:
            if self_us > 0:
                m.host_stage_self_seconds_total.inc(self_us / 1e6, stage=stage)
            if cpu_us > 0:
                m.host_stage_cpu_seconds_total.inc(cpu_us / 1e6, stage=stage)

    def install_gc_watch(self) -> None:
        if self._gc_installed:
            return
        self._gc_installed = True
        gc.callbacks.append(self._gc_callback)

    # -- Tier A intake -------------------------------------------------------

    def _on_span(self, span) -> None:
        """Extra span sink (tracing): every completed span lands here.
        Must stay O(1) and never raise — it runs on serving threads."""
        if self._gc_pending:
            self._drain_gc()
        name = span.name
        us = span.duration_ms * 1000.0
        if name.startswith("rpc."):
            # Auto-register the handler thread for the sampler: the span
            # completes on the thread that served the RPC.
            ident = threading.get_ident()
            entry = _THREAD_ROLES.get(ident)
            if entry is None or not _live(entry):
                register_scoring_thread("grpc_handler", ident)
            rows = span.attributes.get("rows")
            with self._lock:
                self._rpc.spans += 1
                self._rpc.total_us += us
                if isinstance(rows, int) and rows > 0:
                    self._rpc.rows += rows
                    self._rpc.samples.append(us / rows)
            return
        if not name.startswith(_STAGE_PREFIX):
            return
        stage = name[len(_STAGE_PREFIX):]
        rows = span.attributes.get("batch")
        per_row = None
        if isinstance(rows, int) and rows > 0:
            per_row = us / rows
        # exclusive both (tracing.Span.self_s / self_cpu_s, in us); thread
        # CPU is read on one trace in CPU_SAMPLE_EVERY and stands for all
        self_us = us - span.child_s * 1e6
        cpu_us = ((span.cpu_s - span.child_cpu_s) * 1e6
                  * tracing.CPU_SAMPLE_EVERY) if span.cpu_sampled else 0.0
        with self._lock:
            acc = self._stages.get(stage)
            if acc is None:
                acc = self._stages[stage] = _StageAcc(stage)
            acc.spans += 1
            acc.total_us += us
            if self_us > 0:
                acc.self_us += self_us
            if cpu_us > 0:
                acc.cpu_us += cpu_us
            if span.children:
                acc.has_children = True
            if per_row is not None:
                acc.rows += rows
                acc.samples.append(per_row)
        m = self.metrics
        if m is not None and per_row is not None:
            m.host_stage_us_per_row.observe_key(
                acc.key, per_row, span.trace_id)

    # -- GC watch ------------------------------------------------------------

    def _gc_callback(self, phase: str, info: dict) -> None:
        """Takes no lock: a collection starts wherever an allocation
        happens, including on a thread that holds ``_lock`` or a metric's
        lock (a snapshot building its lists, a /metrics render), and none
        of them is reentrant. The pause is queued (``deque.append`` is
        atomic) and folded in by :meth:`_drain_gc` from ordinary code."""
        try:
            gen = int(info.get("generation", 0))
            if phase == "start":
                self._gc_start_ns[gen] = time.perf_counter_ns()
                return
            start_ns = self._gc_start_ns.pop(gen, None)
            if start_ns is None:
                return
            pause_ms = (time.perf_counter_ns() - start_ns) / 1e6
            # Attribute the pause: which rpc.* roots were in flight when
            # the world stopped? (The GIL is held during collection, so
            # every in-flight RPC ate this pause.)
            inflight: dict[str, str] = {}
            for span in tracing.active_spans_by_thread().values():
                root = span.root if span.root is not None else span
                if root.name.startswith("rpc."):
                    inflight[root.span_id] = root.trace_id
            self._gc_pending.append(
                (gen, pause_ms, info.get("collected"), sorted(inflight.values())))
        except Exception:  # noqa: BLE001 — a GC hook must never break collection
            pass

    def _drain_gc(self) -> None:
        """Fold queued GC pauses into the accounting and the metrics."""
        while True:
            try:
                gen, pause_ms, collected, trace_ids = self._gc_pending.popleft()
            except IndexError:
                return
            with self._lock:
                self._gc_collections[gen] = self._gc_collections.get(gen, 0) + 1
                self._gc_pause_ms_total[gen] = (
                    self._gc_pause_ms_total.get(gen, 0.0) + pause_ms)
                self._gc_pauses.append({
                    "generation": gen,
                    "pause_ms": round(pause_ms, 4),
                    "collected": collected,
                    "inflight_rpcs": len(trace_ids),
                    "trace_ids": trace_ids[:4],
                })
                if trace_ids:
                    self._gc_pauses_in_rpc += 1
                    self._gc_pause_in_rpc_ms += pause_ms
            m = self.metrics
            if m is not None:
                m.gc_collections_total.inc(generation=str(gen))
                m.gc_pause_ms.observe(pause_ms, generation=str(gen))

    # -- snapshots -----------------------------------------------------------

    @staticmethod
    def _heap_block() -> dict:
        block = {
            "allocated_blocks": sys.getallocatedblocks(),
            "gc_counts": list(gc.get_count()),
            "gc_thresholds": list(gc.get_threshold()),
        }
        try:
            import resource

            block["ru_maxrss_kb"] = int(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
        except Exception:  # noqa: BLE001 — resource is POSIX-only
            block["ru_maxrss_kb"] = None
        return block

    def _stage_block(self) -> dict:
        with self._lock:
            snap = {
                stage: (acc.spans, acc.rows, acc.total_us, list(acc.samples),
                        acc.self_us, acc.cpu_us, acc.has_children)
                for stage, acc in self._stages.items()
            }
            rpc = (self._rpc.spans, self._rpc.rows, self._rpc.total_us,
                   list(self._rpc.samples))
        out: dict[str, dict] = {}
        for stage, (spans, rows, total_us, samples, self_us, cpu_us,
                    has_children) in snap.items():
            samples.sort()
            out[stage] = {
                "spans": spans,
                "rows": rows,
                "total_us": round(total_us, 1),
                "self_us": round(self_us, 1),
                "cpu_us": round(cpu_us, 1),
                "us_per_row": ({
                    "mean": round(total_us / rows, 4),
                    "p50": round(_percentile(samples, 0.50), 4),
                    "p99": round(_percentile(samples, 0.99), 4),
                } if rows > 0 else None),
            }
            if has_children:
                # What the envelope's own code cost, as a row a reader can
                # take by name: total_us IS the exclusive wall here.
                out[f"{stage}.self"] = {
                    **out[stage],
                    "total_us": round(self_us, 1),
                    "us_per_row": ({"mean": round(self_us / rows, 4)}
                                   if rows > 0 else None),
                }
        out = dict(sorted(out.items()))
        spans, rows, total_us, samples = rpc
        samples.sort()
        rpc_block = {
            "rpcs": spans,
            "rows": rows,
            "total_us": round(total_us, 1),
            "us_per_row": ({
                "mean": round(total_us / rows, 4),
                "p50": round(_percentile(samples, 0.50), 4),
                "p99": round(_percentile(samples, 0.99), 4),
            } if rows > 0 else None),
        }
        return {"stages": out, "rpc": rpc_block}

    def gc_snapshot(self) -> dict:
        self._drain_gc()
        with self._lock:
            return {
                "collections": {str(g): n for g, n
                                in sorted(self._gc_collections.items())},
                "pause_ms_total": {str(g): round(v, 3) for g, v
                                   in sorted(self._gc_pause_ms_total.items())},
                "pauses_in_rpc": self._gc_pauses_in_rpc,
                "pause_in_rpc_ms": round(self._gc_pause_in_rpc_ms, 3),
                "recent_pauses": list(self._gc_pauses)[-20:],
            }

    def snapshot(self) -> dict:
        block = self._stage_block()
        return {
            "enabled": self.enabled,
            **block,
            "gc": self.gc_snapshot(),
            "heap": self._heap_block(),
            "sampler": self.sampler.snapshot(),
            "heartbeat": self.heartbeat.summary(),
        }

    def reset(self) -> None:
        """Zero the accounting (bench arms isolate their windows)."""
        self._gc_pending.clear()
        with self._lock:
            self._stages.clear()
            self._rpc = _StageAcc()
            self._gc_collections.clear()
            self._gc_pause_ms_total.clear()
            self._gc_pauses.clear()
            self._gc_pauses_in_rpc = 0
            self._gc_pause_in_rpc_ms = 0.0
        self.sampler.reset()

    def to_json(self) -> str:
        return json.dumps(self.snapshot())


# ---------------------------------------------------------------------------
# Module default (the /debug/hostprofz + bench singleton)

_DEFAULT: HostProfiler | None = None
_DEFAULT_LOCK = threading.Lock()


def get_default() -> HostProfiler:
    """The process-wide profiler. Created on first use; Tier A installs
    unless HOSTPROF=0, and the sampler starts at boot when HOSTPROF_HZ
    is set to a positive rate."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            enabled = os.environ.get("HOSTPROF", "1") != "0"
            _DEFAULT = HostProfiler(enabled=enabled).install()
            try:
                boot_hz = float(os.environ.get("HOSTPROF_HZ", "0"))
            except ValueError:
                boot_hz = 0.0
            if enabled and boot_hz > 0:
                _DEFAULT.sampler.start(boot_hz)
        return _DEFAULT


def stall_incidents_total() -> int:
    """The default profiler's closed incidents of both kinds; 0 where no
    profiler has been made (a read makes none)."""
    profiler = _DEFAULT
    return 0 if profiler is None else sum(profiler.heartbeat.stalls.values())


def install(metrics=None) -> HostProfiler:
    """Idempotent: bind (or rebind) metrics onto the default profiler."""
    return get_default().install(metrics)


def reinstall_from_env() -> HostProfiler:
    """Tear down and rebuild the default from the current ``HOSTPROF`` /
    ``HOSTPROF_HZ`` environment — the bench A/B arms flip these between
    arms and need the flip to actually take (the default is otherwise
    created once per process)."""
    _reset_default_for_tests()
    return get_default()


def _reset_default_for_tests() -> None:
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is not None:
            _DEFAULT.uninstall()
        _DEFAULT = None
