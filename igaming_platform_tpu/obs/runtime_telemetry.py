"""Device-runtime telemetry — the signals the flight recorder is blind to.

The flight recorder (obs/flight.py) decomposes a request's latency into
stages, but three classes of device-runtime trouble never show up there:

- **Recompile storms.** A drifting batch shape (or a params hot-swap
  that changes a static arg) silently re-traces and re-compiles the
  serving program; the only symptom is a mysterious multi-second stage.
  :class:`CompileWatcher` listens at the jax monitoring seam
  (``/jax/core/compile/backend_compile_duration`` etc.) for compile
  count + wall ms, and the scorer notes a *shape signature* at every
  launch — a compile is attributed to the signature that triggered it,
  and a NEW signature after warmup is a recompile-storm tripwire
  (``risk_compile_signatures_total`` fires exactly once per signature).

- **Dispatch amplification.** The flight entry shows a slow RPC; it
  does not show that the RPC issued 9 device dispatches instead of 2.
  Every jit LAUNCH — not every span — bumps a per-request
  ``dispatches`` attribute on its RPC root (visible in /debug/flightz)
  plus the global ``risk_device_dispatches_total``: the launch seam
  (``serve/scorer._device_dispatch``) calls :func:`note_dispatch`, so
  side launches a stage span never wrapped (the split drift sketch,
  the shadow scorer's fallback step, the session-ring admission sync,
  the cache delta scatter, the abuse sequence model) count honestly.
  Before PR 14 the counter was span-derived and undercounted exactly
  those launches.

- **Step-time anomalies.** :class:`StepTimeAnomalyDetector` keeps an
  EWMA + EW-variance of per-stage device step time; a step beyond
  ``mean + k*sigma`` (and an absolute floor) stamps the flight entry
  (``anomaly`` root attribute) and fires the profile trigger — the
  server binds it to the existing /debug/profilez capture path with a
  cooldown, so the FIRST anomaly of an incident records a device
  profile keyed by the trace id, and a storm doesn't record fifty.

HBM-side occupancy gauges (arena pool buffers, device memory stats
where the backend exposes them, device feature-cache occupancy is
already covered by PR 1's gauges) refresh on every /metrics scrape.

- **Who else had the CPU.** A run that is slow from boot to exit on a
  shared-core machine looks like a slow program. :func:`read_host_cpu`
  reads the ``cpu`` line of ``/proc/stat`` (steal, and all columns) and
  this process's ``schedstat`` (seconds on a CPU, seconds runnable
  without one); :meth:`RuntimeTelemetry.refresh_host_counters` folds
  them into four counters whenever the registry renders — never on a
  request.
"""

from __future__ import annotations

import glob
import logging
import os
import threading
import time
from collections import deque
from typing import Callable

from igaming_platform_tpu.obs import tracing

logger = logging.getLogger(__name__)

# Stage spans whose durations feed the step-time anomaly detectors:
# dispatch launches the compiled step; readback is the D2H drain;
# score.device is the fused dispatch+readback of the request paths.
# (Dispatch COUNTING is launch-driven via note_dispatch, not span-driven.)
_STEP_STAGES = ("score.dispatch", "score.readback", "score.device")


def read_host_cpu(proc: str = "/proc") -> dict[str, float]:
    """Seconds, cumulative: ``steal`` and ``all`` from the ``cpu`` line of
    ``/proc/stat``; ``oncpu`` and ``runqueue`` from the first two fields
    of every live thread's ``schedstat``. A sandboxed kernel shows a
    ``cpu`` line of zeros and keeps no schedstat (the machines the chips
    sit in: PERF.md, PR 38): ``steal``, ``all`` and ``runqueue`` then stay
    0, which says "cannot be read" (a share over a zero denominator is
    no number), and ``oncpu`` is ``time.process_time()``, the same
    quantity from another clock."""
    out = {"steal": 0.0, "all": 0.0, "oncpu": 0.0, "runqueue": 0.0}
    try:
        with open(f"{proc}/stat", encoding="ascii") as f:
            ticks = [float(v) for v in f.readline().split()[1:]]
        hz = os.sysconf("SC_CLK_TCK")
        out["all"] = sum(ticks) / hz
        if len(ticks) > 7:
            out["steal"] = ticks[7] / hz
    except (OSError, ValueError):  # noqa: CC04 — no /proc (not Linux): the columns read 0
        pass
    oncpu_ns = wait_ns = 0
    for path in glob.glob(f"{proc}/self/task/*/schedstat"):
        try:
            with open(path, encoding="ascii") as f:
                fields = f.read().split()
            oncpu_ns += int(fields[0])
            wait_ns += int(fields[1])
        except (OSError, ValueError, IndexError):  # noqa: CC04 — the thread ended between the listing and the read
            continue
    if oncpu_ns:
        out["oncpu"], out["runqueue"] = oncpu_ns / 1e9, wait_ns / 1e9
    else:
        out["oncpu"] = time.process_time()
    return out


class CompileWatcher:
    """Compile/recompile accounting at the jax monitoring seam.

    jax fires duration events per lowering/compile; this listener counts
    them and records wall ms. Shape attribution: the launch seams call
    :meth:`note_signature` right before dispatch; a signature seen for
    the first time is remembered (thread-locally) so a compile event
    landing on the same thread is attributed to it. ``note_signature``
    returns True exactly once per new signature — the recompile-storm
    counter's contract, pinned by tests.
    """

    _COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
    _CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

    def __init__(self, metrics=None, max_events: int = 64):
        self.metrics = metrics
        self._lock = threading.Lock()
        self._signatures: set[str] = set()
        self._local = threading.local()
        self.compiles_total = 0
        self.compile_wall_ms_total = 0.0
        self.new_signatures_total = 0
        # Persistent compile cache (core/devices.py): a compile request
        # served from disk is a hit, one written to disk a miss.
        self.cache_hits_total = 0
        self.cache_misses_total = 0
        self.events: deque = deque(maxlen=max_events)
        self._listener_installed = False

    def install_listener(self) -> None:
        """Register with jax.monitoring (idempotent; tolerated missing on
        stripped builds — signature accounting still works without it)."""
        if self._listener_installed:
            return
        try:
            from jax._src import monitoring
        except Exception:  # noqa: BLE001 — monitoring seam is optional
            return
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        self._listener_installed = True

    def _on_event(self, name: str, **_kw) -> None:
        if name == self._CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits_total += 1
        elif name == self._CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses_total += 1

    def _on_duration(self, name: str, duration_s: float, **_kw) -> None:
        if name != self._COMPILE_EVENT:
            return
        ms = duration_s * 1000.0
        sig = getattr(self._local, "pending_signature", None)
        with self._lock:
            self.compiles_total += 1
            self.compile_wall_ms_total += ms
            self.events.append({
                "t_unix": round(time.time(), 3),
                "wall_ms": round(ms, 3),
                "signature": sig,
            })
        if self.metrics is not None:
            self.metrics.compile_events_total.inc(kind="backend_compile")
            self.metrics.compile_wall_ms.observe(ms)

    def note_signature(self, name: str, shape=None, dtype=None) -> bool:
        """Record the shape signature about to launch; True IFF new.
        Called on the launching thread so a triggered compile event is
        attributable to this signature."""
        sig = f"{name}:{tuple(shape) if shape is not None else ()}:{dtype}"
        self._local.pending_signature = sig
        with self._lock:
            if sig in self._signatures:
                return False
            self._signatures.add(sig)
            self.new_signatures_total += 1
        if self.metrics is not None:
            self.metrics.compile_signatures_total.inc()
        return True

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles_total": self.compiles_total,
                "compile_wall_ms_total": round(self.compile_wall_ms_total, 3),
                "persistent_cache_hits": self.cache_hits_total,
                "persistent_cache_misses": self.cache_misses_total,
                "signatures": self.new_signatures_total,
                # Which programs have launched (name:shape:dtype) — the
                # host-tier and device-tier steps carry different names,
                # so this says which tier has answered.
                "signature_names": sorted(self._signatures)[:128],
                "recent_events": list(self.events),
            }


class StepTimeAnomalyDetector:
    """EWMA + EW-variance step-time anomaly detection for one stage.

    It is fed completed spans, so it sees a slow stage AFTER it ended,
    and the profile it triggers records what the device does next. What
    sees a stage that is stuck, while it lasts, is the stall watch
    (obs/hostprof.Heartbeat, ``/debug/stallz``); :meth:`RuntimeTelemetry.
    snapshot` points there.

    A sample is anomalous when it exceeds ``mean + k*sigma`` AND the
    absolute floor (``min_ms``) AND the warmup count has passed — the
    floor keeps microsecond-scale jitter from paging, warmup keeps the
    first compiles out of the baseline."""

    def __init__(self, *, alpha: float = 0.15, k_sigma: float = 4.0,
                 min_ms: float = 5.0, warmup: int = 30):
        self.alpha = alpha
        self.k_sigma = k_sigma
        self.min_ms = min_ms
        self.warmup = warmup
        self.mean = 0.0
        self.var = 0.0
        self.n = 0

    def observe(self, ms: float) -> bool:
        self.n += 1
        if self.n <= self.warmup:
            # Seed the baseline without judging.
            delta = ms - self.mean
            self.mean += delta / self.n
            self.var += (delta * (ms - self.mean) - self.var) / self.n
            return False
        sigma = self.var ** 0.5
        anomalous = (ms > self.min_ms
                     and ms > self.mean + self.k_sigma * sigma)
        # Anomalous samples update the baseline with a damped weight so
        # a sustained fault is still anomalous request after request
        # (an undamped EWMA would adopt the fault as the new normal
        # within ~1/alpha steps).
        alpha = self.alpha * (0.1 if anomalous else 1.0)
        delta = ms - self.mean
        self.mean += alpha * delta
        self.var = (1 - alpha) * (self.var + alpha * delta * delta)
        return anomalous

    def snapshot(self) -> dict:
        return {"mean_ms": round(self.mean, 3),
                "sigma_ms": round(self.var ** 0.5, 3), "samples": self.n}


class RuntimeTelemetry:
    """The assembled plane: span-sink accounting + anomaly → profile.

    ``install()`` binds one instance per process to the tracing span
    fan-out. The server binds a profile trigger (its /debug/profilez
    capture path); anomalies within ``cooldown_s`` of a capture only
    count — they never re-trigger."""

    def __init__(self, metrics=None, *,
                 cooldown_s: float | None = None,
                 profile_enabled: bool | None = None):
        self.metrics = metrics
        self.compile_watcher = CompileWatcher(metrics)
        self.compile_watcher.install_listener()
        if cooldown_s is None:
            cooldown_s = float(os.environ.get(
                "ANOMALY_PROFILE_COOLDOWN_S", "120"))
        if profile_enabled is None:
            profile_enabled = os.environ.get("ANOMALY_PROFILE", "1") != "0"
        self.cooldown_s = cooldown_s
        self.profile_enabled = profile_enabled
        self._lock = threading.Lock()
        # The dispatch counter gets a dedicated LEAF lock: note_dispatch
        # is called from launch seams that may hold scoring-path locks
        # (session ring, cache) — a leaf held only for the increment can
        # never participate in a lock-order cycle with them.
        self._dispatch_lock = threading.Lock()
        self._detectors: dict[str, StepTimeAnomalyDetector] = {}
        self._detector_kwargs = dict(
            k_sigma=float(os.environ.get("ANOMALY_K_SIGMA", "4.0")),
            min_ms=float(os.environ.get("ANOMALY_MIN_STEP_MS", "5.0")),
            warmup=int(os.environ.get("ANOMALY_WARMUP_STEPS", "30")),
        )
        self.dispatches_total = 0
        # last reading of read_host_cpu, so each refresh adds the growth
        self._host_cpu_seen = dict.fromkeys(
            ("steal", "all", "oncpu", "runqueue"), 0.0)
        self.anomalies_total = 0
        self.anomalies: deque = deque(maxlen=64)
        self.profile_captures: list[dict] = []
        self._last_profile_at = float("-inf")
        self._profile_trigger: Callable[[str, str, float], dict | None] | None = None
        self._engine = None

    # -- wiring --------------------------------------------------------------

    def bind_profile_trigger(
            self, fn: Callable[[str, str, float], dict | None]) -> None:
        """fn(trace_id, stage, duration_ms) -> capture info dict (or
        None). Called OFF the serving path (the caller must not block);
        the server's binding spawns a capture thread."""
        self._profile_trigger = fn

    def bind_engine(self, engine) -> None:
        """Engine whose arena/cache occupancy the gauges read."""
        self._engine = engine

    # -- span sink -----------------------------------------------------------

    def note_dispatch(self, count: int = 1) -> None:
        """One real jit launch (the ``serve/scorer._device_dispatch``
        seam). Bumps the global counter, the metric, and the CURRENT
        root span's ``dispatches`` attribute — launch-driven, so the
        count equals the true number of device programs started, not the
        number of ``score.dispatch`` spans that happened to wrap them."""
        with self._dispatch_lock:
            self.dispatches_total += count
        if self.metrics is not None:
            self.metrics.device_dispatches_total.inc(count)
        span = tracing.current_span()
        if span is not None:
            tracing.bump_root_attribute_of(span, "dispatches", count)

    def note_h2d(self, transfers: int, nbytes: int) -> None:
        """The host arguments one launch of the index-mode program was
        handed (``serve/scorer._launch_cached``): count and bytes."""
        if self.metrics is not None:
            self.metrics.h2d_transfers_total.inc(transfers)
            self.metrics.h2d_bytes_total.inc(nbytes)

    def note_padded_rows(self, rows: int) -> None:
        """The padded rows one launch of the index-mode program ran (its
        ladder rung), at the same seam."""
        if self.metrics is not None:
            self.metrics.launch_padded_rows_total.inc(rows)

    def note_occupancy(self, rows: int) -> None:
        """The real rows of that launch: over its rung, the ladder's
        occupancy (``risk_batch_occupancy``)."""
        if self.metrics is not None:
            self.metrics.batch_occupancy.observe(rows)

    def observe_span(self, span) -> None:
        name = getattr(span, "name", "")
        if name not in _STEP_STAGES:
            return
        with self._lock:
            det = self._detectors.get(name)
            if det is None:
                det = self._detectors.setdefault(
                    name, StepTimeAnomalyDetector(**self._detector_kwargs))
            anomalous = det.observe(span.duration_ms)
        if anomalous:
            self._note_anomaly(span, name)

    def _note_anomaly(self, span, stage: str) -> None:
        with self._lock:
            self.anomalies_total += 1
            self.anomalies.append({
                "t_unix": round(time.time(), 3),
                "stage": stage,
                "duration_ms": round(span.duration_ms, 3),
                "trace_id": span.trace_id,
            })
        if self.metrics is not None:
            self.metrics.step_anomalies_total.inc(stage=stage)
        # Stamp the flight entry: the root completes after its stages,
        # so the recorder snapshots the attribute.
        root = span.root if span.root is not None else span
        with_stamp = root.attributes
        with_stamp.setdefault("anomaly", stage)
        self._maybe_profile(span.trace_id, stage, span.duration_ms)

    def _maybe_profile(self, trace_id: str, stage: str,
                       duration_ms: float) -> None:
        trigger = self._profile_trigger
        if trigger is None or not self.profile_enabled:
            return
        now = time.monotonic()
        with self._lock:
            if now - self._last_profile_at < self.cooldown_s:
                return
            self._last_profile_at = now
        try:
            info = trigger(trace_id, stage, duration_ms)
        except Exception:  # noqa: BLE001 — profiling must not fail scoring
            logger.warning("anomaly profile trigger failed", exc_info=True)
            return
        with self._lock:
            self.profile_captures.append({
                "t_unix": round(time.time(), 3),
                "trace_id": trace_id,
                "stage": stage,
                "duration_ms": round(duration_ms, 3),
                **(info or {}),
            })
        if self.metrics is not None:
            self.metrics.anomaly_profiles_total.inc()

    def note_capture_result(self, trace_id: str, info: dict) -> None:
        """Async capture completion: fold the artifact location (or the
        failure) back into the capture record so /debug/telemetryz shows
        where the trace-keyed profile landed."""
        with self._lock:
            for rec in reversed(self.profile_captures):
                if rec.get("trace_id") == trace_id:
                    rec.update(info)
                    return

    # -- gauges + snapshot ---------------------------------------------------

    def refresh_host_counters(self) -> None:
        """Steal, run-queue wait and CPU seconds onto the bound registry;
        a registry refresher (every render), so no request pays for the
        reads. Threads that ended took their schedstat with them: a sum
        that fell adds nothing."""
        m = self.metrics
        if m is None:
            return
        now = read_host_cpu()
        with self._lock:
            seen, self._host_cpu_seen = self._host_cpu_seen, now
        for key, counter in (
                ("steal", m.host_cpu_steal_seconds_total),
                ("all", m.host_cpu_seconds_total),
                ("runqueue", m.process_runqueue_wait_seconds_total),
                ("oncpu", m.process_cpu_seconds_total)):
            counter.inc(max(0.0, now[key] - seen[key]))

    def refresh_gauges(self) -> None:
        """Arena / HBM occupancy onto the bound metrics registry —
        called on each /metrics scrape so the gauges are scrape-fresh."""
        if self.metrics is None:
            return
        engine = self._engine
        pipeline = getattr(engine, "pipeline", None) if engine else None
        if pipeline is not None and hasattr(pipeline, "arena_stats"):
            stats = pipeline.arena_stats()
            for kind in ("allocated", "reused", "idle"):
                self.metrics.arena_buffers.set(
                    float(stats.get(kind, 0)), kind=kind)
        try:
            import jax

            mem = jax.devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 — CPU/older backends expose no stats
            mem = None
        if mem:
            for src, kind in (("bytes_in_use", "in_use"),
                              ("bytes_limit", "limit"),
                              ("peak_bytes_in_use", "peak")):
                if src in mem:
                    self.metrics.hbm_bytes.set(float(mem[src]), kind=kind)

    def snapshot(self) -> dict:
        with self._dispatch_lock:
            dispatches = self.dispatches_total
        with self._lock:
            detectors = {name: det.snapshot()
                         for name, det in self._detectors.items()}
            out = {
                "dispatches_total": dispatches,
                "anomalies_total": self.anomalies_total,
                "recent_anomalies": list(self.anomalies),
                "profile_captures": list(self.profile_captures),
                "profile_cooldown_s": self.cooldown_s,
                "step_time": detectors,
            }
        out["compile"] = self.compile_watcher.snapshot()
        # an anomaly is a stage that WAS slow; an RPC that is stuck now, or
        # was for seconds, is the stall watch's, with every thread's stack
        from igaming_platform_tpu.obs import hostprof
        out["stalls"] = {
            "incidents_total": hostprof.stall_incidents_total(),
            "see": "/debug/stallz",
        }
        engine = self._engine
        pipeline = getattr(engine, "pipeline", None) if engine else None
        if pipeline is not None and hasattr(pipeline, "arena_stats"):
            out["arena"] = pipeline.arena_stats()
        return out


# ---------------------------------------------------------------------------
# Process default

DEFAULT: RuntimeTelemetry | None = None


def install(metrics=None) -> RuntimeTelemetry:
    """Bind a fresh RuntimeTelemetry to the tracing span fan-out as the
    process default (replacing the previous one — the most recently
    constructed risk service owns the sinks, same contract as metrics)."""
    global DEFAULT
    uninstall()
    DEFAULT = RuntimeTelemetry(metrics)
    tracing.add_span_sink(DEFAULT.observe_span)
    if metrics is not None:
        metrics.registry.add_refresher(DEFAULT.refresh_host_counters)
    return DEFAULT


def uninstall() -> None:
    global DEFAULT
    if DEFAULT is not None:
        tracing.remove_span_sink(DEFAULT.observe_span)
        if DEFAULT.metrics is not None:
            DEFAULT.metrics.registry.remove_refresher(
                DEFAULT.refresh_host_counters)
        DEFAULT = None


def get_default() -> RuntimeTelemetry | None:
    return DEFAULT


def note_compile_signature(name: str, shape=None, dtype=None) -> bool:
    """Launch-seam helper (serve/scorer.py): note the shape signature
    about to dispatch on the process-default watcher. True IFF new."""
    t = DEFAULT
    if t is None:
        return False
    return t.compile_watcher.note_signature(name, shape, dtype)


def note_dispatch(count: int = 1) -> None:
    """Launch-seam helper (serve/scorer._device_dispatch): one real jit
    launch on the process-default telemetry. No-op without one."""
    t = DEFAULT
    if t is not None:
        t.note_dispatch(count)


def note_h2d(transfers: int, nbytes: int) -> None:
    """Launch-seam helper (serve/scorer._launch_cached). No-op without a
    process-default telemetry."""
    t = DEFAULT
    if t is not None:
        t.note_h2d(transfers, nbytes)


def note_padded_rows(rows: int) -> None:
    """Launch-seam helper (serve/scorer._launch_cached). No-op without a
    process-default telemetry."""
    t = DEFAULT
    if t is not None:
        t.note_padded_rows(rows)


def note_occupancy(rows: int) -> None:
    """Launch-seam helper (serve/scorer._note_launch). No-op without a
    process-default telemetry."""
    t = DEFAULT
    if t is not None:
        t.note_occupancy(rows)
