"""gRPC front: wire-compatible risk.v1 and wallet.v1 servers.

The reference exposes RiskService (risk.proto:10-32) and WalletService
(wallet.proto:10-26) over grpc-go with a logging -> recovery -> metrics
interceptor chain and the gRPC health protocol
(risk/cmd/main.go:133-147, wallet/cmd/main.go:137-151). This module serves
the same contracts from Python: method handlers are registered generically
against the protoc-generated message classes (no grpc_tools plugin
needed), so any reference client — including `grpcurl` and the Go wallet
service — talks to these servers unchanged.

Interceptor parity: handlers time every RPC into ServiceMetrics (the
reference's metrics interceptor is an unimplemented TODO — SURVEY.md §5),
recover from handler panics into INTERNAL (recovery interceptor), and the
health service flips NOT_SERVING before drain (graceful shutdown).
"""

from __future__ import annotations

import importlib.util
import logging
import os
import threading
import time
from concurrent import futures
from typing import Callable

import grpc

from igaming_platform_tpu.core.enums import ReasonCode
from igaming_platform_tpu.obs import flight as _flight
from igaming_platform_tpu.obs import hostprof as _hostprof
from igaming_platform_tpu.obs import drift as _drift
from igaming_platform_tpu.obs import runtime_telemetry as _runtime_telemetry
from igaming_platform_tpu.obs import slo as _slo
from igaming_platform_tpu.obs import tracing
from igaming_platform_tpu.obs.metrics import ServiceMetrics
from igaming_platform_tpu.obs.tracing import span
from igaming_platform_tpu.serve import deadline as _deadline
from igaming_platform_tpu.serve.deadline import (
    LANE_BACKGROUND,
    BurnShedGate,
    DeadlineExpired,
    QueueFullError,
)
from igaming_platform_tpu.serve.reflection import reflection_handler
from igaming_platform_tpu.serve.supervisor import (
    RETRY_PUSHBACK_MS,
    DeviceWedgedError,
    ServingUnavailable,
)

# Always-on flight recorder: every completed rpc.* root span lands in the
# bounded ring served at /debug/flightz (obs/flight.py).
_flight.install()
# Host-plane cost observatory: Tier A µs/row stage accounting + GC watch
# ride the tracing span sinks from boot (HOSTPROF=0 disables); metrics
# bind at service construction below.
_hostprof.install()
from igaming_platform_tpu.serve.wire import (
    INDEX_WIRE_MAGIC,
    RawProtoMessage,
    native_wire_available,
)

# Lazily resolved on the first ScoreBatch (native_wire_available may build
# the .so — that side effect must not run at import). Tri-state: None =
# undecided, then pinned. Disable with WIRE_FAST_PATH=0 to force the
# per-row proto path (debug escape hatch).
_WIRE_FAST_PATH: bool | None = None


def _use_wire_fast_path() -> bool:
    global _WIRE_FAST_PATH
    if _WIRE_FAST_PATH is None:
        _WIRE_FAST_PATH = (
            os.environ.get("WIRE_FAST_PATH", "1") != "0" and native_wire_available()
        )
    return _WIRE_FAST_PATH

logger = logging.getLogger(__name__)


class RpcAbort(Exception):
    """Typed abort raised inside handlers; mapped to a status by _rpc.

    grpcio's context.abort raises an opaque Exception that the recovery
    wrapper cannot distinguish from a crash, so handlers raise this
    instead. ``trailing`` metadata (e.g. the standard
    ``grpc-retry-pushback-ms`` hint on supervisor sheds) is attached
    before the abort."""

    def __init__(self, code, details: str, trailing: tuple = (),
                 shed: bool = False):
        super().__init__(details)
        self.code = code
        self.details = details
        self.trailing = tuple(trailing)
        # Deliberate backpressure (deadline/burn/admission sheds): the
        # root span carries a `shed` attribute so the SLO engine never
        # burns error budget for admission control doing its job.
        self.shed = shed


def _pushback_trailing() -> tuple:
    return (("grpc-retry-pushback-ms", str(RETRY_PUSHBACK_MS)),)

_PROTO_GEN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "proto_gen")


def _load_module(name: str, rel_path: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(_PROTO_GEN, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# risk/wallet pb2 are proper packages on sys.path (igaming_platform_tpu
# appends proto_gen); health_pb2 must NOT be imported as "grpc.health..."
# (it would shadow grpcio), so it loads by file path.
from risk.v1 import risk_pb2  # noqa: E402
from wallet.v1 import wallet_pb2  # noqa: E402

health_pb2 = _load_module("igaming_health_pb2", "grpc/health/v1/health_pb2.py")

SERVING = health_pb2.HealthCheckResponse.SERVING
NOT_SERVING = health_pb2.HealthCheckResponse.NOT_SERVING


class HealthServicer:
    """Standard grpc.health.v1 implementation (hand-registered)."""

    def __init__(self):
        self._status: dict[str, int] = {"": SERVING}
        self._lock = threading.Lock()

    def set(self, service: str, status: int) -> None:
        with self._lock:
            self._status[service] = status

    def set_all_not_serving(self) -> None:
        with self._lock:
            for k in self._status:
                self._status[k] = NOT_SERVING

    def check(self, request, context):
        with self._lock:
            status = self._status.get(request.service)
        if status is None:
            context.abort(grpc.StatusCode.NOT_FOUND, "unknown service")
        return health_pb2.HealthCheckResponse(status=status)


class _AdaptiveBulkGate:
    """Bounded bulk-admission gate with p99 feedback.

    A plain semaphore at BULK_MAX_INFLIGHT holds the configured limit even
    when the host is slower than the one it was measured on. This gate
    additionally watches the single-txn latencies the limit exists to
    protect: every ``window`` observations it takes the window's ~p99 and
    TIGHTENS the in-flight limit by one (down to ``min_limit``) when the
    SLO is breached, relaxing one step back toward the configured maximum
    only after ``relax_after`` consecutive comfortably-under-SLO windows.
    """

    def __init__(self, limit: int, *, p99_slo_ms: float = 50.0,
                 window: int = 32, min_limit: int = 1, relax_after: int = 4):
        self.max_limit = max(1, limit)
        self.limit = self.max_limit
        self.p99_slo_ms = p99_slo_ms
        self._window = window
        self._min = min_limit
        self._relax_after = relax_after
        self._good_windows = 0
        self._lat: list[float] = []
        self._held = 0
        self._cv = threading.Condition()
        self.on_limit_change = None  # callable(limit) — metrics hook

    def acquire(self, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._held >= self.limit:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(remaining)
            self._held += 1
            return True

    def release(self) -> None:
        with self._cv:
            self._held -= 1
            self._cv.notify()

    def _set_limit(self, limit: int) -> None:
        self.limit = limit
        if self.on_limit_change is not None:
            self.on_limit_change(limit)

    def observe_single_ms(self, ms: float) -> None:
        """Feed one single-txn latency sample; adjusts the limit at
        window boundaries. Disabled when p99_slo_ms <= 0."""
        if self.p99_slo_ms <= 0:
            return
        with self._cv:
            self._lat.append(float(ms))
            if len(self._lat) < self._window:
                return
            lat = sorted(self._lat)
            self._lat = []
            p99 = lat[max(0, int(len(lat) * 0.99) - 1)]
            if p99 > self.p99_slo_ms:
                self._good_windows = 0
                if self.limit > self._min:
                    self._set_limit(self.limit - 1)
            elif p99 <= 0.5 * self.p99_slo_ms:
                self._good_windows += 1
                if self._good_windows >= self._relax_after and self.limit < self.max_limit:
                    self._set_limit(self.limit + 1)
                    self._good_windows = 0
                    self._cv.notify_all()
            else:
                self._good_windows = 0


class _FixedWindowRateLimiter:
    """Per-account fixed-window counter — the INCR+EXPIRE semantics of the
    reference's CheckRateLimit (redis_store.go:196-203), enforced at the
    RPC edge (the reference reads the limit from env but never calls it)."""

    def __init__(self, per_minute: int):
        self.per_minute = per_minute
        self._lock = threading.Lock()
        self._windows: dict[str, tuple[int, int]] = {}

    def allow(self, account_id: str) -> bool:
        if not self.per_minute:
            return True
        now_min = int(time.time() // 60)
        with self._lock:
            win, count = self._windows.get(account_id, (now_min, 0))
            if win != now_min:
                win, count = now_min, 0
            count += 1
            self._windows[account_id] = (win, count)
            if len(self._windows) > 100_000:  # bound memory: drop stale windows
                self._windows = {a: wc for a, wc in self._windows.items() if wc[0] == now_min}
            return count <= self.per_minute


def _traceparent_from_metadata(context) -> str | None:
    """W3C trace context off the gRPC metadata (grpc lowercases keys).
    A missing/malformed header is normal — the span starts a new trace."""
    if context is None:
        return None
    try:
        for key, value in context.invocation_metadata() or ():
            if key == "traceparent":
                return value
    except Exception:  # noqa: BLE001 — tracing must not fail the RPC
        pass
    return None


def _rpc(metrics: ServiceMetrics, method: str, fn: Callable):
    """Wrap a handler with metrics + panic recovery (the interceptor chain
    of wallet/cmd/main.go:274-311 collapsed into one decorator)."""

    def handler(request, context):
        start = time.monotonic()
        # Per-RPC host span (the OTel spans the reference deploys Jaeger
        # for but never emits — SURVEY.md §5); status lands as an attribute
        # so sampled traces show which calls aborted. The caller's
        # `traceparent` metadata (W3C) parents this span, so client, front
        # and follower spans share one trace id; stage spans opened inside
        # the handler nest under it and decompose the RPC's latency, and
        # the completed root lands in the flight recorder (/debug/flightz).
        with span(f"rpc.{method}",
                  traceparent=_traceparent_from_metadata(context)) as s:
            # Serving-state annotation (obs/slo.py): the supervisor's
            # state AT SCORE TIME rides the root span, so flight entries
            # and SLO samples attribute degraded-tier latency honestly.
            state = _slo.current_state()
            if state is not None:
                s.attributes["serving_state"] = state
            try:
                resp = fn(request, context)
                metrics.observe_rpc(method, start)
                s.attributes["code"] = "OK"
                return resp
            except RpcAbort as abort:
                metrics.observe_rpc(method, start, code=abort.code.name)
                s.attributes["code"] = abort.code.name
                if abort.shed:
                    s.attributes["shed"] = 1
                if abort.trailing and context is not None:
                    context.set_trailing_metadata(abort.trailing)
                context.abort(abort.code, abort.details)
            except DeadlineExpired as exc:
                # A request whose budget ran out while queued in the
                # scheduler (serve/deadline.py): shed with the caller's
                # own status — DEADLINE_EXCEEDED — plus the standard
                # pushback hint. A shed, never an error: the scheduler
                # already counted it (risk_deadline_expired_total) and
                # the `shed` attribute keeps it out of the SLO budget.
                metrics.observe_rpc(method, start, code="DEADLINE_EXCEEDED")
                s.attributes["code"] = "DEADLINE_EXCEEDED"
                s.attributes["shed"] = 1
                if context is not None:
                    context.set_trailing_metadata(_pushback_trailing())
                context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(exc))
            except QueueFullError as exc:
                # Scheduler admission queue at capacity: loud bounded
                # backpressure, the bulk-gate discipline.
                metrics.observe_rpc(method, start, code="RESOURCE_EXHAUSTED")
                s.attributes["code"] = "RESOURCE_EXHAUSTED"
                s.attributes["shed"] = 1
                if context is not None:
                    context.set_trailing_metadata(_pushback_trailing())
                context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(exc))
            except (DeviceWedgedError, ServingUnavailable) as exc:
                # Supervisor sheds (wedged device window, BROWNOUT): LOUD
                # UNAVAILABLE with the standard retry-pushback hint so
                # clients back off exactly one breaker window — never a
                # silent hang on a dead collective, never INTERNAL.
                metrics.observe_rpc(method, start, code="UNAVAILABLE")
                s.attributes["code"] = "UNAVAILABLE"
                if context is not None:
                    context.set_trailing_metadata(_pushback_trailing())
                context.abort(grpc.StatusCode.UNAVAILABLE, str(exc))
            except grpc.RpcError:
                metrics.observe_rpc(method, start, code="ERROR")
                s.attributes["code"] = "ERROR"
                raise
            except Exception as exc:  # noqa: BLE001 — recovery interceptor
                logger.exception("handler panic in %s", method)
                metrics.observe_rpc(method, start, code="INTERNAL")
                s.attributes["code"] = "INTERNAL"
                context.abort(grpc.StatusCode.INTERNAL, f"internal error: {exc}")

    return handler


def _unary(fn, req_cls, resp_cls, raw_request: bool = False):
    # Duck-typed serializer (not resp_cls.SerializeToString): handlers on
    # the wire fast path return serve.wire.RawProtoMessage — pre-serialized
    # bytes from the native batch encoder — through the same seam.
    # raw_request skips Python protobuf parsing entirely and hands the
    # handler the request's wire bytes (the native decode path).
    return grpc.unary_unary_rpc_method_handler(
        fn,
        request_deserializer=(lambda b: b) if raw_request else req_cls.FromString,
        response_serializer=lambda m: m.SerializeToString(),
    )


# ---------------------------------------------------------------------------
# Risk service
# ---------------------------------------------------------------------------


class RiskGrpcService:
    """risk.v1.RiskService against the TPU scoring engine + LTV + abuse."""

    def __init__(self, engine, ltv_source=None, abuse_detector=None, metrics: ServiceMetrics | None = None,
                 rate_limit_per_minute: int = 0):
        """
        engine: serve.scorer.TPUScoringEngine
        ltv_source: callable(account_id) -> [25]-dim LTV feature row or None
        abuse_detector: callable(account_id, bonus_id) -> (score, signals, linked)
        rate_limit_per_minute: per-account ScoreTransaction cap (0 disables;
            redis_store.go:196-203 CheckRateLimit, enforced here rather than
            declared-only as in the reference)
        """
        self.engine = engine
        self.ltv_source = ltv_source
        self.abuse_detector = abuse_detector
        self.metrics = metrics or ServiceMetrics("risk")
        _hostprof.install(self.metrics)
        self._rate_limiter = _FixedWindowRateLimiter(rate_limit_per_minute)
        # Server-side overload control: bulk ScoreBatch work is admitted
        # through a bounded gate. Beyond BULK_MAX_INFLIGHT concurrent bulk
        # RPCs (after a short BULK_ADMIT_WAIT_S queue-wait), the server
        # SHEDS with RESOURCE_EXHAUSTED instead of queueing unboundedly —
        # a burst above capacity degrades bulk callers (who retry with
        # backoff) while the single-txn Score fast lane keeps its p99:
        # the remaining gRPC workers and the host CPU stay available for
        # interactive traffic instead of drowning in bulk encode/decode.
        # The reference has no admission control at all (its flat-out
        # tail is unbounded queueing). The default gate of 2 in-flight
        # trades bulk throughput for the interactive tail (the knee on
        # the current chip: not measured). On hosts where even 2 is
        # too generous, the p99-feedback controller (_AdaptiveBulkGate)
        # tightens further: single-txn latencies above BULK_P99_SLO_MS
        # (default 50, 0 disables) shrink the limit toward 1, and it
        # relaxes back only after sustained headroom.
        self._bulk_gate = _AdaptiveBulkGate(
            max(1, int(os.environ.get("BULK_MAX_INFLIGHT", "2"))),
            p99_slo_ms=float(os.environ.get("BULK_P99_SLO_MS", "50")),
        )
        self.metrics.bulk_gate_limit.set(self._bulk_gate.limit)
        self._bulk_gate.on_limit_change = self.metrics.bulk_gate_limit.set
        # Short admit wait: a shed must not PARK a gRPC worker — with a
        # flood wider than the worker pool, long waits would occupy every
        # worker and starve the interactive lane the gate protects
        # (shed capacity ~= workers / wait). 20 ms absorbs scheduling
        # jitter without tying up the pool.
        self._bulk_admit_wait_s = float(os.environ.get("BULK_ADMIT_WAIT_S", "0.02"))
        # Resolve (and if needed g++-build) the native codec NOW, at
        # construction — never inside the first live ScoreBatch RPC, where
        # a cold build would stall callers for the compile duration.
        # With the native response encoder present, ScoreBatch runs in raw
        # mode: the server hands the handler the request's wire bytes.
        # Index-mode frames (device-resident feature cache) are detected
        # by magic there; protobuf requests take the one-call native
        # decode+gather when the store has it, or are parsed in the
        # handler otherwise — same seam, same risk.v1 surface.
        self.raw_request_methods: tuple[str, ...] = ()
        if (
            _use_wire_fast_path()
            and hasattr(engine, "score_batch_wire_bytes")
        ):
            self.raw_request_methods = ("ScoreBatch",)
        if hasattr(engine, "score_observer"):
            # Batch paths feed the score-distribution histogram vectorized
            # (per-row observe() would be a Python loop on the hot path).
            engine.score_observer = self.metrics.score_distribution.observe_many
        if hasattr(engine, "bind_cache_metrics"):
            # HBM feature-cache hit/miss/evict/occupancy land in this
            # service's registry (obs/metrics.py) whether the cache is
            # already built or materializes on the first index-mode RPC.
            engine.bind_cache_metrics(self.metrics)
        if hasattr(engine, "bind_pipeline_metrics"):
            # Host-pipeline gauges (inflight depth, overlap ratio) —
            # bound now or at the pipeline's lazy build, same pattern.
            engine.bind_pipeline_metrics(self.metrics)
        if hasattr(engine, "bind_session_metrics"):
            # Session-state plane (serve/session_state.py): warm/cold/
            # bypass rows, ring appends, rehydrations, HBM budget —
            # bound now or when ensure_cache builds the session plane.
            engine.bind_session_metrics(self.metrics)
        if hasattr(engine, "bind_supervisor_metrics"):
            # Self-healing supervisor (serve/supervisor.py): serving
            # state, breaker states, degraded/watchdog/rebuild counters.
            engine.bind_supervisor_metrics(self.metrics)
        # Request-lifecycle observability: every completed stage span feeds
        # risk_stage_latency_ms (with trace-id exemplars), span-ring
        # evictions count in risk_spans_dropped_total, and the continuous
        # batcher reports per-request queue wait + queue depth. Sinks are
        # process-global; the most recently constructed risk service owns
        # them (one serving engine per process in every deployment shape).
        tracing.set_span_sink(self.metrics.observe_stage_span)
        tracing.DEFAULT_COLLECTOR.on_drop = self.metrics.spans_dropped_total.inc
        # SLO engine (obs/slo.py, SLO=0 opts out) + device-runtime
        # telemetry (obs/runtime_telemetry.py): both ride the tracing
        # fan-out and follow the same ownership contract as the sinks
        # above. The server layer binds the supervisor state provider
        # and the anomaly->profile trigger on top.
        if os.environ.get("SLO", "1") != "0":
            _slo.install(_slo.SLOEngine(metrics=self.metrics))
        else:
            _slo.uninstall()
        # Drift observatory (obs/drift.py, DRIFT=0 opts out): on-path
        # feature/score sketches vs a pinned reference, calibration vs
        # mined outcomes, and the drift_quiet promotion gate's alert
        # state. Same ownership contract as the SLO plane; the engine
        # compiles + warms its sketch kernels at bind time.
        self.drift = None
        if os.environ.get("DRIFT", "1") != "0" and hasattr(engine,
                                                           "bind_drift"):
            self.drift = _drift.install(_drift.DriftEngine(
                metrics=self.metrics))
            engine.bind_drift(self.drift)
        else:
            _drift.uninstall()
        self.telemetry = None
        if os.environ.get("RUNTIME_TELEMETRY", "1") != "0":
            self.telemetry = _runtime_telemetry.install(self.metrics)
            self.telemetry.bind_engine(engine)
        else:
            _runtime_telemetry.uninstall()
        # Deadline passthrough is duck-typed: production engines
        # (TPUScoringEngine, SupervisedScoringEngine) take deadline=,
        # but the engine seam is a plain callable contract and test
        # doubles/legacy engines may not — detect once, never TypeError
        # a live RPC over it.
        import inspect

        try:
            sig = inspect.signature(engine.score)
            self._score_takes_deadline = (
                "deadline" in sig.parameters
                or any(p.kind == p.VAR_KEYWORD
                       for p in sig.parameters.values()))
        except (TypeError, ValueError):
            self._score_takes_deadline = True
        # Closed loop on the SLO plane (serve/deadline.py): while the
        # fast-window burn alert is active, bulk ScoreBatch admissions
        # shed with BULK_SHED + pushback so the interactive lane's p99
        # recovers; bulk resumes the moment the alert clears. Reads the
        # SLOEngine installed above lazily — SLO=0 leaves it inert.
        self.burn_gate = BurnShedGate()
        batcher = getattr(engine, "_batcher", None)
        if batcher is not None:
            batcher.on_batch = self._observe_batcher_batch
            # Deadline-plane metrics (all labels bounded per MX05):
            # expiry sheds by stage, per-lane queue depth, the planned
            # batch shape per tick, and each dispatched request's
            # remaining budget.
            batcher.on_plan = self.metrics.batch_size_chosen.observe
            batcher.on_dispatch_deadlines = (
                self.metrics.deadline_remaining_ms.observe_many)
            sched = getattr(batcher, "scheduler", None)
            if sched is not None:
                sched.on_expired = (
                    lambda n, stage, lane:
                    self.metrics.deadline_expired_total.inc(n, stage=stage))
                sched.on_depth = (
                    lambda lane, depth:
                    self.metrics.lane_depth.set(depth, lane=lane))

    def _observe_batcher_batch(self, waits_ms: list, depth: int) -> None:
        """Batcher hook: time-in-queue histogram + queue-depth gauge, and
        the queue wait as a `score.queue` stage so the batching window
        shows up in the same per-stage breakdown as decode/gather/step."""
        self.metrics.batcher_queue_depth.set(depth)
        self.metrics.batcher_time_in_queue_ms.observe_many(waits_ms)
        self.metrics.stage_latency_ms.observe_many(waits_ms, stage="score.queue")

    # -- scoring --

    def _score_to_proto(self, resp) -> risk_pb2.ScoreTransactionResponse:
        f = resp.features
        return risk_pb2.ScoreTransactionResponse(
            score=resp.score,
            action={"approve": 1, "review": 2, "block": 3}[resp.action],
            reason_codes=[r.value for r in resp.reason_codes],
            rule_score=resp.rule_score,
            ml_score=resp.ml_score,
            response_time_ms=int(resp.response_time_ms),
            features=risk_pb2.FeatureVector(
                tx_count_1m=int(f.tx_count_1m),
                tx_count_5m=int(f.tx_count_5m),
                tx_count_1h=int(f.tx_count_1h),
                tx_sum_1h=int(f.tx_sum_1h),
                tx_avg_1h=f.tx_avg_1h,
                unique_devices_24h=int(f.unique_devices_24h),
                unique_ips_24h=int(f.unique_ips_24h),
                ip_country_changes_7d=int(f.ip_country_changes),
                device_age_days=int(f.device_age_days),
                account_age_days=int(f.account_age_days),
                total_deposits=int(f.total_deposits),
                total_withdrawals=int(f.total_withdrawals),
                net_deposit=int(f.net_deposit),
                deposit_count=int(f.deposit_count),
                withdraw_count=int(f.withdraw_count),
                time_since_last_tx_sec=int(f.time_since_last_tx),
                session_duration_sec=int(f.session_duration),
                avg_bet_size=f.avg_bet_size,
                win_rate=f.win_rate,
                is_vpn=f.is_vpn > 0,
                is_proxy=f.is_proxy > 0,
                is_tor=f.is_tor > 0,
                disposable_email=f.disposable_email > 0,
                bonus_claim_count=int(f.bonus_claim_count),
                bonus_wager_completion_rate=f.bonus_wager_rate,
                bonus_only_player=f.bonus_only_player > 0,
            ),
        )

    def _request_from_proto(self, req):
        from igaming_platform_tpu.serve.scorer import ScoreRequest

        return ScoreRequest(
            account_id=req.account_id,
            player_id=req.player_id,
            amount=req.amount,
            tx_type=req.transaction_type or "deposit",
            currency=req.currency or "USD",
            game_id=req.game_id,
            ip=req.ip_address,
            device_id=req.device_id,
            fingerprint=req.fingerprint,
            user_agent=req.user_agent,
            session_id=req.session_id,
        )

    def _admit_deadline(self, context, stage: str = "admission"):
        """Parse the request's deadline (risk-deadline-ms metadata > gRPC
        context deadline > DEADLINE_DEFAULT_MS) and shed an
        already-expired request up front — scoring a row its caller will
        never receive only steals capacity from live ones."""
        ddl = _deadline.from_grpc(context)
        if ddl.expired():
            self.metrics.deadline_expired_total.inc(stage=stage)
            raise RpcAbort(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                "DEADLINE_SHED: request budget "
                f"({ddl.budget_ms:.0f} ms, source={ddl.source}) already "
                "spent at admission",
                trailing=_pushback_trailing(), shed=True)
        return ddl

    def ScoreTransaction(self, request, context):
        # Per-account scoring cap; the batch path (ScoreBatch / event
        # replay) is internal and exempt.
        if not self._rate_limiter.allow(request.account_id):
            raise RpcAbort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                           "RATE_LIMITED: per-account scoring rate limit exceeded")
        ddl = self._admit_deadline(context)
        # Arms the burn->shed loop: bulk only sheds while there is
        # interactive traffic to protect (serve/deadline.BurnShedGate).
        self.burn_gate.note_interactive()
        kwargs = {"deadline": ddl} if self._score_takes_deadline else {}
        resp = self.engine.score(self._request_from_proto(request), **kwargs)
        if ddl.source != "default" and ddl.expired():
            # The caller set an EXPLICIT deadline and it passed while we
            # scored: per the deadline contract the caller has given up —
            # answer DEADLINE_EXCEEDED (a shed), not a stale OK. Requests
            # without an explicit deadline keep their answer: the default
            # budget shapes scheduling, not the response contract.
            self.metrics.deadline_expired_total.inc(stage="response")
            raise RpcAbort(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                "DEADLINE_SHED: scored result ready after the request's "
                f"budget ({ddl.budget_ms:.0f} ms) expired",
                trailing=_pushback_trailing(), shed=True)
        self.metrics.score_distribution.observe(resp.score)
        self.metrics.txns_scored_total.inc()
        trailing: list[tuple[str, str]] = []
        if getattr(resp, "decision_id", ""):
            # Join key across the observability surfaces: the flight
            # entry, the trace root and the ledger record share this id.
            # Exposed in trailing metadata so label-backfill callers
            # (the outcome feed posting chargebacks/dispute verdicts to
            # /debug/outcomes) can reference the decision without a
            # wire-schema change.
            tracing.set_root_attribute("decision_id", resp.decision_id)
            trailing.append(("risk-decision-id", resp.decision_id))
        # p99-feedback for the bulk admission gate: the single-txn fast
        # lane's latency is the SLO the gate protects.
        self._bulk_gate.observe_single_ms(resp.response_time_ms)
        if ReasonCode.DEGRADED_CPU_HEURISTIC in resp.reason_codes:
            # Degraded-tier answer: wire-compatible, but the caller can
            # SEE it — model-version suffix in trailing metadata plus the
            # reason code already on the response (never an error).
            trailing.append((
                "risk-model-version",
                getattr(self.engine, "model_version", "degraded-heuristic")))
        if trailing and context is not None:
            context.set_trailing_metadata(tuple(trailing))
        return self._score_to_proto(resp)

    def ScoreBatch(self, request, context):
        # Admission control (overload shedding): see __init__. A caller
        # whose deadline is already spent is rejected up front — running
        # a batch it will never receive only steals capacity. The bulk
        # lane keeps a small slack floor: a batch with under 50 ms left
        # cannot finish decode+score+encode, so it sheds as bulk
        # backpressure even though not strictly expired yet.
        ddl = self._admit_deadline(context)
        if ddl.source != "default" and ddl.remaining_ms() < 50.0:
            self.metrics.bulk_shed_total.inc()
            raise RpcAbort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                           "BULK_SHED: deadline nearly exhausted before start",
                           trailing=_pushback_trailing(), shed=True)
        # Closed loop on the SLO plane: while the fast window burns,
        # bulk admissions shed with pushback (interactive traffic is
        # what the error budget protects; bulk callers retry with
        # backoff and resume the moment the alert clears).
        if self.burn_gate.shedding():
            self.burn_gate.note_shed()
            self.metrics.bulk_shed_total.inc()
            raise RpcAbort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "BULK_SHED: error budget burning (fast-window SLO alert "
                "active); bulk lane shedding until it clears",
                trailing=_pushback_trailing(), shed=True)
        # The admission wait is a lifecycle stage: under overload it is
        # real queueing the RPC span would otherwise carry unattributed.
        with span("score.admission"):
            admitted = self._bulk_gate.acquire(timeout=self._bulk_admit_wait_s)
        if not admitted:
            self.metrics.bulk_shed_total.inc()
            raise RpcAbort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "BULK_SHED: bulk admission limit reached; retry with backoff",
            )
        try:
            return self._score_batch_admitted(request, context)
        finally:
            self._bulk_gate.release()

    def _score_batch_admitted(self, request, context):
        if isinstance(request, (bytes, memoryview)):
            buf = bytes(request)
            if buf[:4] == INDEX_WIRE_MAGIC:
                # Index-mode frame: ship slot indices + per-txn deltas to
                # the device-resident feature table — never a [N, 30]
                # feature matrix (serve/device_cache.py). Response stays
                # a wire-compatible risk.v1 ScoreBatchResponse.
                try:
                    payload, n = self.engine.score_batch_wire_index(buf)
                except ValueError as exc:
                    raise RpcAbort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        f"bad index-mode ScoreBatch frame: {exc}") from exc
                except RuntimeError as exc:
                    raise RpcAbort(
                        grpc.StatusCode.UNIMPLEMENTED,
                        f"index-mode ScoreBatch unavailable: {exc}") from exc
                self.metrics.txns_scored_total.inc(n)
                tracing.set_root_attribute("rows", n)
                return RawProtoMessage(payload)
            if not hasattr(getattr(self.engine, "features", None), "decode_gather"):
                # Raw mode was enabled for index frames but this is a
                # protobuf request and the store has no native decoder:
                # parse here and fall through to the standard paths.
                try:
                    with span("score.decode"):
                        request = risk_pb2.ScoreBatchRequest.FromString(buf)
                except Exception as exc:  # noqa: BLE001 — malformed proto
                    raise RpcAbort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        f"bad ScoreBatchRequest: {exc}") from exc
                return self._score_batch_parsed(request)
            # Fully native path: the server's deserializer was identity
            # (raw_request_methods), so these are the request's wire bytes.
            try:
                payload, n = self.engine.score_batch_wire_bytes(buf)
            except ValueError as exc:
                raise RpcAbort(
                    grpc.StatusCode.INVALID_ARGUMENT, f"bad ScoreBatchRequest: {exc}"
                ) from exc
            self.metrics.txns_scored_total.inc(n)
            tracing.set_root_attribute("rows", n)
            return RawProtoMessage(payload)
        return self._score_batch_parsed(request)

    def _score_batch_parsed(self, request):
        txs = request.transactions
        tracing.set_root_attribute("rows", len(txs))
        if _use_wire_fast_path() and hasattr(self.engine, "score_batch_wire"):
            # Errors propagate: once the codec is confirmed available, any
            # failure here (device error, encoder bug) is a real serving
            # failure — silently re-running the batch on the per-row path
            # would double device load exactly when the device is sick.
            # Column extraction is the proto half of wire decode — spanned
            # so large-batch RPCs don't carry it as unattributed latency.
            with span("score.decode", batch=len(txs)):
                cols = (
                    [t.account_id for t in txs],
                    [t.amount for t in txs],
                    [t.transaction_type or "deposit" for t in txs],
                    [t.ip_address for t in txs],
                    [t.device_id for t in txs],
                    [t.fingerprint for t in txs],
                )
            payload = self.engine.score_batch_wire(
                cols[0], cols[1], cols[2],
                ips=cols[3], devices=cols[4], fingerprints=cols[5],
            )
            self.metrics.txns_scored_total.inc(len(txs))
            return RawProtoMessage(payload)
        with span("score.decode", batch=len(txs)):
            reqs = [self._request_from_proto(t) for t in txs]
        responses = self.engine.score_batch(reqs)
        self.metrics.txns_scored_total.inc(len(responses))
        # Metric parity with the fast path: the per-row fallback feeds the
        # score histogram too (WIRE_FAST_PATH=0 must not flatline it).
        self.metrics.score_distribution.observe_many([r.score for r in responses])
        with span("score.encode", batch=len(responses)):
            return risk_pb2.ScoreBatchResponse(
                results=[self._score_to_proto(r) for r in responses])

    # -- LTV --

    def _ltv_row(self, account_id: str):
        import numpy as np

        from igaming_platform_tpu.models.ltv import NUM_LTV_FEATURES

        if self.ltv_source is not None:
            row = self.ltv_source(account_id)
            if row is not None:
                return np.asarray(row, dtype=np.float32).reshape(1, NUM_LTV_FEATURES)
        return np.zeros((1, NUM_LTV_FEATURES), dtype=np.float32)

    def _background_dispatch_turn(self) -> None:
        """LTV/background device work rides the BACKGROUND lane of the
        dispatch gate: it yields briefly to a launching interactive
        batch (bounded by the lane's aging budget — never starved)."""
        gate = getattr(self.engine, "lane_gate", None)
        if gate is not None:
            gate.acquire(LANE_BACKGROUND)

    def PredictLTV(self, request, context):
        from google.protobuf.timestamp_pb2 import Timestamp

        from igaming_platform_tpu.models.ltv import ACTIONS, predict_batch_jit

        self._background_dispatch_turn()
        out = predict_batch_jit(self._ltv_row(request.account_id))
        ts = Timestamp()
        ts.GetCurrentTime()
        self.metrics.ltv_segment_total.inc(segment=str(int(out["segment"][0])))
        return risk_pb2.PredictLTVResponse(
            account_id=request.account_id,
            predicted_ltv=float(out["ltv"][0]),
            segment=int(out["segment"][0]),
            churn_risk=float(out["churn_risk"][0]),
            predicted_active_days=int(out["survival_days"][0]),
            confidence=float(out["confidence"][0]),
            next_best_action=ACTIONS[int(out["action"][0])],
            predicted_at=ts,
        )

    def GetPlayerSegment(self, request, context):
        from igaming_platform_tpu.models.ltv import ACTIONS, predict_batch_jit

        self._background_dispatch_turn()
        out = predict_batch_jit(self._ltv_row(request.account_id))
        return risk_pb2.GetPlayerSegmentResponse(
            account_id=request.account_id,
            segment=int(out["segment"][0]),
            ltv=float(out["ltv"][0]),
            churn_risk=float(out["churn_risk"][0]),
            recommended_actions=[ACTIONS[int(out["action"][0])]],
        )

    # -- bonus abuse --

    def CheckBonusAbuse(self, request, context):
        if self.abuse_detector is not None:
            from igaming_platform_tpu.serve.abuse import AbuseShed

            try:
                score, signals, linked = self.abuse_detector(
                    request.account_id, request.bonus_id)
            except AbuseShed as exc:
                # Loud shed, never a silent 80 seq/s: UNAVAILABLE plus a
                # dedicated counter (errors_total itself is incremented
                # by the RPC wrapper — incrementing it here too would
                # double-count).
                self.metrics.abuse_shed_total.inc()
                raise RpcAbort(grpc.StatusCode.UNAVAILABLE, str(exc)) from exc
        else:
            # Scalar fallback: the bonus-only-player heuristic.
            import numpy as np

            from igaming_platform_tpu.core.features import F, NUM_FEATURES

            row = np.zeros(NUM_FEATURES, dtype=np.float32)
            self.engine.features.fill_row(row, request.account_id, 0, "bet")
            score = 0.8 if row[F.BONUS_ONLY_PLAYER] > 0 else 0.1
            signals = ["BONUS_ONLY_PLAYER"] if score > 0.5 else []
            linked = []
        return risk_pb2.CheckBonusAbuseResponse(
            is_abuser=score >= 0.5,
            abuse_score=score,
            signals=signals,
            linked_accounts=linked,
        )

    # -- blacklist --

    def AddToBlacklist(self, request, context):
        try:
            self.engine.features.add_to_blacklist(request.type, request.value)
        except ValueError as exc:
            raise RpcAbort(grpc.StatusCode.INVALID_ARGUMENT, str(exc)) from exc
        return risk_pb2.AddToBlacklistResponse(success=True, id=f"{request.type}:{request.value}")

    def CheckBlacklist(self, request, context):
        hit = self.engine.features.check_blacklist(
            device_id=request.device_id, fingerprint=request.fingerprint, ip=request.ip_address
        )
        return risk_pb2.CheckBlacklistResponse(is_blacklisted=hit)

    # -- features / thresholds --

    def GetFeatures(self, request, context):
        import numpy as np

        from google.protobuf.timestamp_pb2 import Timestamp

        from igaming_platform_tpu.core.features import FeatureVector, NUM_FEATURES

        row = np.zeros(NUM_FEATURES, dtype=np.float32)
        self.engine.features.fill_row(row, request.account_id, 0, "deposit")
        f = FeatureVector.from_array(row)
        ts = Timestamp()
        ts.GetCurrentTime()
        return risk_pb2.GetFeaturesResponse(
            account_id=request.account_id,
            features=risk_pb2.FeatureVector(
                tx_count_1m=int(f.tx_count_1m),
                tx_count_5m=int(f.tx_count_5m),
                tx_count_1h=int(f.tx_count_1h),
                tx_sum_1h=int(f.tx_sum_1h),
                tx_avg_1h=f.tx_avg_1h,
                unique_devices_24h=int(f.unique_devices_24h),
                unique_ips_24h=int(f.unique_ips_24h),
                account_age_days=int(f.account_age_days),
                total_deposits=int(f.total_deposits),
                total_withdrawals=int(f.total_withdrawals),
                net_deposit=int(f.net_deposit),
                deposit_count=int(f.deposit_count),
                withdraw_count=int(f.withdraw_count),
                time_since_last_tx_sec=int(f.time_since_last_tx),
                session_duration_sec=int(f.session_duration),
                bonus_claim_count=int(f.bonus_claim_count),
                bonus_wager_completion_rate=f.bonus_wager_rate,
                bonus_only_player=f.bonus_only_player > 0,
            ),
            computed_at=ts,
        )

    def UpdateThresholds(self, request, context):
        self.engine.set_thresholds(request.block_threshold, request.review_threshold)
        return risk_pb2.UpdateThresholdsResponse(
            success=True,
            block_threshold=request.block_threshold,
            review_threshold=request.review_threshold,
        )

    def GetThresholds(self, request, context):
        block, review = self.engine.get_thresholds()
        return risk_pb2.GetThresholdsResponse(block_threshold=block, review_threshold=review)


_RISK_METHODS = {
    "ScoreTransaction": (risk_pb2.ScoreTransactionRequest, risk_pb2.ScoreTransactionResponse),
    "ScoreBatch": (risk_pb2.ScoreBatchRequest, risk_pb2.ScoreBatchResponse),
    "PredictLTV": (risk_pb2.PredictLTVRequest, risk_pb2.PredictLTVResponse),
    "GetPlayerSegment": (risk_pb2.GetPlayerSegmentRequest, risk_pb2.GetPlayerSegmentResponse),
    "CheckBonusAbuse": (risk_pb2.CheckBonusAbuseRequest, risk_pb2.CheckBonusAbuseResponse),
    "AddToBlacklist": (risk_pb2.AddToBlacklistRequest, risk_pb2.AddToBlacklistResponse),
    "CheckBlacklist": (risk_pb2.CheckBlacklistRequest, risk_pb2.CheckBlacklistResponse),
    "GetFeatures": (risk_pb2.GetFeaturesRequest, risk_pb2.GetFeaturesResponse),
    "UpdateThresholds": (risk_pb2.UpdateThresholdsRequest, risk_pb2.UpdateThresholdsResponse),
    "GetThresholds": (risk_pb2.GetThresholdsRequest, risk_pb2.GetThresholdsResponse),
}


# ---------------------------------------------------------------------------
# Wallet service
# ---------------------------------------------------------------------------


def _ts_to_float(ts) -> float:
    """protobuf Timestamp → float epoch, keeping sub-second precision
    (Transaction.created_at is a float; ToSeconds() would truncate)."""
    return ts.seconds + ts.nanos / 1e9


class WalletGrpcService:
    """wallet.v1.WalletService against platform.wallet.WalletService."""

    def __init__(self, wallet, metrics: ServiceMetrics | None = None):
        self.wallet = wallet
        self.metrics = metrics or ServiceMetrics("wallet")

    def _record_txn(self, res) -> None:
        """Per-type flow counters (count + cents volume) — the series the
        bonus-conversion and throughput dashboards chart."""
        tx = res.transaction
        self.metrics.transactions_total.inc(type=tx.type.value)
        self.metrics.transaction_amount_cents.inc(tx.amount, type=tx.type.value)

    def _tx_to_proto(self, tx) -> wallet_pb2.Transaction:
        from google.protobuf.timestamp_pb2 import Timestamp

        msg = wallet_pb2.Transaction(
            id=tx.id,
            account_id=tx.account_id,
            idempotency_key=tx.idempotency_key,
            type=tx.type.value,
            amount=tx.amount,
            balance_before=tx.balance_before,
            balance_after=tx.balance_after,
            status=tx.status.value,
            reference=tx.reference,
            game_id=tx.game_id or "",
            round_id=tx.round_id or "",
            risk_score=tx.risk_score or 0,
        )
        created = Timestamp()
        created.FromSeconds(int(tx.created_at))
        msg.created_at.CopyFrom(created)
        if tx.completed_at:
            completed = Timestamp()
            completed.FromSeconds(int(tx.completed_at))
            msg.completed_at.CopyFrom(completed)
        return msg

    def _account_to_proto(self, a) -> wallet_pb2.Account:
        from google.protobuf.timestamp_pb2 import Timestamp

        msg = wallet_pb2.Account(
            id=a.id, player_id=a.player_id, currency=a.currency,
            balance=a.balance, bonus=a.bonus, status=a.status.value,
        )
        ts = Timestamp()
        ts.FromSeconds(int(a.created_at))
        msg.created_at.CopyFrom(ts)
        ts2 = Timestamp()
        ts2.FromSeconds(int(a.updated_at))
        msg.updated_at.CopyFrom(ts2)
        return msg

    def _domain_error(self, context, exc):
        from igaming_platform_tpu.platform import domain as d

        code_map = {
            d.AccountNotFoundError: grpc.StatusCode.NOT_FOUND,
            d.AccountSuspendedError: grpc.StatusCode.FAILED_PRECONDITION,
            d.InsufficientBalanceError: grpc.StatusCode.FAILED_PRECONDITION,
            d.DuplicateTransactionError: grpc.StatusCode.ALREADY_EXISTS,
            d.InvalidAmountError: grpc.StatusCode.INVALID_ARGUMENT,
            d.ConcurrentUpdateError: grpc.StatusCode.ABORTED,
            d.RiskBlockedError: grpc.StatusCode.PERMISSION_DENIED,
            d.RiskReviewError: grpc.StatusCode.PERMISSION_DENIED,
            d.RiskUnavailableError: grpc.StatusCode.UNAVAILABLE,
            d.BonusRestrictionError: grpc.StatusCode.FAILED_PRECONDITION,
        }
        code = code_map.get(type(exc), grpc.StatusCode.INTERNAL)
        raise RpcAbort(code, f"{getattr(exc, 'code', 'WALLET_ERROR')}: {exc}") from exc

    def CreateAccount(self, request, context):
        acct = self.wallet.create_account(request.player_id, request.currency or "USD")
        return wallet_pb2.CreateAccountResponse(account=self._account_to_proto(acct))

    def GetAccount(self, request, context):
        from igaming_platform_tpu.platform.domain import AccountNotFoundError

        try:
            if request.WhichOneof("identifier") == "player_id":
                acct = self.wallet.accounts.get_by_player_id(request.player_id)
                if acct is None:
                    raise AccountNotFoundError(request.player_id)
            else:
                acct = self.wallet.accounts.get_by_id(request.account_id)
        except AccountNotFoundError as exc:
            self._domain_error(context, exc)
        return wallet_pb2.GetAccountResponse(account=self._account_to_proto(acct))

    def GetBalance(self, request, context):
        from igaming_platform_tpu.platform.domain import AccountNotFoundError

        try:
            acct = self.wallet.get_balance(request.account_id)
        except AccountNotFoundError as exc:
            self._domain_error(context, exc)
        return wallet_pb2.GetBalanceResponse(
            account_id=acct.id,
            balance=acct.balance,
            bonus=acct.bonus,
            total=acct.total_balance,
            withdrawable=acct.available_for_withdraw,
            currency=acct.currency,
        )

    def Deposit(self, request, context):
        from igaming_platform_tpu.platform.domain import WalletError

        try:
            res = self.wallet.deposit(
                request.account_id, request.amount, request.idempotency_key,
                payment_method=request.payment_method, reference=request.reference,
                ip=request.ip_address, device_id=request.device_id,
                fingerprint=request.fingerprint,
            )
        except WalletError as exc:
            self._domain_error(context, exc)
        self._record_txn(res)
        return wallet_pb2.DepositResponse(
            transaction=self._tx_to_proto(res.transaction),
            new_balance=res.new_balance,
            risk_score=res.risk_score or 0,
        )

    def Withdraw(self, request, context):
        from igaming_platform_tpu.platform.domain import WalletError

        try:
            res = self.wallet.withdraw(
                request.account_id, request.amount, request.idempotency_key,
                payout_method=request.payout_method, ip=request.ip_address,
                device_id=request.device_id,
            )
        except WalletError as exc:
            self._domain_error(context, exc)
        self._record_txn(res)
        return wallet_pb2.WithdrawResponse(
            transaction=self._tx_to_proto(res.transaction),
            new_balance=res.new_balance,
            risk_score=res.risk_score or 0,
            payout_status="completed",
        )

    def Bet(self, request, context):
        from igaming_platform_tpu.platform.domain import WalletError

        try:
            res = self.wallet.bet(
                request.account_id, request.amount, request.idempotency_key,
                game_id=request.game_id, round_id=request.round_id,
                game_category=request.game_category, ip=request.ip_address,
                device_id=request.device_id,
            )
        except WalletError as exc:
            self._domain_error(context, exc)
        self._record_txn(res)
        return wallet_pb2.BetResponse(
            transaction=self._tx_to_proto(res.transaction),
            new_balance=res.new_balance,
            risk_score=res.risk_score or 0,
            real_deducted=res.real_deducted,
            bonus_deducted=res.bonus_deducted,
        )

    def Win(self, request, context):
        from igaming_platform_tpu.platform.domain import WalletError

        try:
            res = self.wallet.win(
                request.account_id, request.amount, request.idempotency_key,
                game_id=request.game_id, round_id=request.round_id,
                bet_tx_id=request.bet_transaction_id, win_type=request.win_type or "normal",
            )
        except WalletError as exc:
            self._domain_error(context, exc)
        self._record_txn(res)
        return wallet_pb2.WinResponse(
            transaction=self._tx_to_proto(res.transaction), new_balance=res.new_balance
        )

    def Refund(self, request, context):
        from igaming_platform_tpu.platform.domain import WalletError

        try:
            res = self.wallet.refund(
                request.account_id, request.original_transaction_id,
                request.idempotency_key, reason=request.reason,
            )
        except WalletError as exc:
            self._domain_error(context, exc)
        self._record_txn(res)
        return wallet_pb2.RefundResponse(
            transaction=self._tx_to_proto(res.transaction), new_balance=res.new_balance
        )

    def GetTransaction(self, request, context):
        tx = self.wallet.transactions.get_by_id(request.transaction_id)
        if tx is None:
            raise RpcAbort(grpc.StatusCode.NOT_FOUND, "transaction not found")
        return wallet_pb2.GetTransactionResponse(transaction=self._tx_to_proto(tx))

    def GetTransactionHistory(self, request, context):
        # Clamp both ends: a negative int32 limit would reach SQLite as
        # LIMIT -1 (= unlimited) and dump the whole history.
        limit = max(1, min(request.limit or 50, 100))
        offset = max(0, request.offset)
        # Filters apply before pagination (wallet.proto:172-186); `total`
        # is the filtered count, `has_more` whether a further page exists.
        filters = dict(
            types=list(request.types) or None,
            # The proto field is named `from` (wallet.proto:177) — a Python
            # keyword, hence getattr. created_at is a float epoch, so keep
            # the Timestamp's sub-second precision.
            from_ts=_ts_to_float(getattr(request, "from")) if request.HasField("from") else None,
            to_ts=_ts_to_float(request.to) if request.HasField("to") else None,
            game_id=request.game_id or None,
        )
        txs = self.wallet.get_transaction_history(
            request.account_id, limit, offset, **filters
        )
        total = self.wallet.count_transactions(request.account_id, **filters)
        return wallet_pb2.GetTransactionHistoryResponse(
            transactions=[self._tx_to_proto(t) for t in txs],
            total=total,
            has_more=offset + len(txs) < total,
        )


_WALLET_METHODS = {
    "CreateAccount": (wallet_pb2.CreateAccountRequest, wallet_pb2.CreateAccountResponse),
    "GetAccount": (wallet_pb2.GetAccountRequest, wallet_pb2.GetAccountResponse),
    "GetBalance": (wallet_pb2.GetBalanceRequest, wallet_pb2.GetBalanceResponse),
    "Deposit": (wallet_pb2.DepositRequest, wallet_pb2.DepositResponse),
    "Withdraw": (wallet_pb2.WithdrawRequest, wallet_pb2.WithdrawResponse),
    "Bet": (wallet_pb2.BetRequest, wallet_pb2.BetResponse),
    "Win": (wallet_pb2.WinRequest, wallet_pb2.WinResponse),
    "Refund": (wallet_pb2.RefundRequest, wallet_pb2.RefundResponse),
    "GetTransaction": (wallet_pb2.GetTransactionRequest, wallet_pb2.GetTransactionResponse),
    "GetTransactionHistory": (
        wallet_pb2.GetTransactionHistoryRequest,
        wallet_pb2.GetTransactionHistoryResponse,
    ),
}


# ---------------------------------------------------------------------------
# Server / stub assembly
# ---------------------------------------------------------------------------


def _generic_handler(service_name: str, servicer, methods: dict, metrics: ServiceMetrics):
    raw_methods = getattr(servicer, "raw_request_methods", ())
    handlers = {
        name: _unary(
            _rpc(metrics, name, getattr(servicer, name)), req, resp,
            raw_request=name in raw_methods,
        )
        for name, (req, resp) in methods.items()
    }
    return grpc.method_handlers_generic_handler(service_name, handlers)


def _health_handler(health: HealthServicer):
    handlers = {
        "Check": _unary(health.check, health_pb2.HealthCheckRequest, health_pb2.HealthCheckResponse)
    }
    return grpc.method_handlers_generic_handler("grpc.health.v1.Health", handlers)


def serve_risk(service: RiskGrpcService, port: int, max_workers: int = 32):
    """Build + start the risk.v1 server; returns (server, health)."""
    health = HealthServicer()
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=max_workers))
    server.add_generic_rpc_handlers((
        _generic_handler("risk.v1.RiskService", service, _RISK_METHODS, service.metrics),
        _health_handler(health),
        # grpcurl-without-protos parity (risk/cmd/main.go:150).
        reflection_handler(("risk.v1.RiskService", "grpc.health.v1.Health")),
    ))
    bound = server.add_insecure_port(f"[::]:{port}")
    server.start()
    return server, health, bound


def serve_wallet(service: WalletGrpcService, port: int, max_workers: int = 32):
    health = HealthServicer()
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=max_workers))
    server.add_generic_rpc_handlers((
        _generic_handler("wallet.v1.WalletService", service, _WALLET_METHODS, service.metrics),
        _health_handler(health),
        # grpcurl-without-protos parity (wallet/cmd/main.go:154).
        reflection_handler(("wallet.v1.WalletService", "grpc.health.v1.Health")),
    ))
    bound = server.add_insecure_port(f"[::]:{port}")
    server.start()
    return server, health, bound


def graceful_stop(server, health: HealthServicer, grace: float = 30.0,
                  engine=None) -> None:
    """NOT_SERVING before drain (risk/cmd/main.go:249), then the engine.

    Order matters for zero-loss shutdown: flip health first (load
    balancers stop routing), stop the server with ``grace`` (new RPCs
    rejected, ADMITTED handlers run to completion against the still-live
    engine), and only then close the engine — which drains the continuous
    batcher and flushes the host pipeline's in-flight window
    (HostPipeline.close completes pending jobs). Closing the engine
    before the gRPC drain would strand admitted requests on a dead
    batcher; SIGTERM under load must lose zero admitted requests
    (tests/test_supervisor_chaos.py pins it)."""
    health.set_all_not_serving()
    server.stop(grace).wait()
    if engine is not None:
        engine.close()


def _make_stub(channel, service_name: str, methods: dict):
    class _Stub:
        pass

    stub = _Stub()
    for name, (req_cls, resp_cls) in methods.items():
        setattr(stub, name, channel.unary_unary(
            f"/{service_name}/{name}",
            request_serializer=req_cls.SerializeToString,
            response_deserializer=resp_cls.FromString,
        ))
    return stub


def make_risk_stub(channel):
    return _make_stub(channel, "risk.v1.RiskService", _RISK_METHODS)


def make_wallet_stub(channel):
    return _make_stub(channel, "wallet.v1.WalletService", _WALLET_METHODS)


def make_health_stub(channel):
    return _make_stub(
        channel, "grpc.health.v1.Health",
        {"Check": (health_pb2.HealthCheckRequest, health_pb2.HealthCheckResponse)},
    )
