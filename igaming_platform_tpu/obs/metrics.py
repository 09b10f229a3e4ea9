"""Prometheus-style metrics — implementing what the reference stubs.

The reference deploys Prometheus+Grafana but its metrics interceptors are
TODOs (wallet/cmd/main.go:306-311; risk/cmd/main.go:344-353 lists the
intended series without recording them). This registry records that exact
set — request counts, latency histograms, error counts, score distribution
— plus the BASELINE series (txns/sec, batch occupancy) and renders the
Prometheus text exposition format for the /metrics sidecar.

Dependency-free: counters/gauges/histograms over a lock, no client lib.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Callable, Iterable

_DEFAULT_BUCKETS = (0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500)


def _label_key(labels: dict[str, str]) -> tuple:
    # no labels or one (most series): nothing to sort
    return tuple(labels.items()) if len(labels) < 2 else tuple(sorted(labels.items()))


def _fmt_labels(key: tuple) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in key)
    return "{" + inner + "}"


class Counter:
    def __init__(self, name: str, help_text: str = ""):
        self.name = name
        self.help = help_text
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def inc(self, value: float = 1.0, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def value(self, **labels: str) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} counter"
        # Snapshot under the lock: a concurrent inc() during a /metrics
        # scrape must not race the dict iteration.
        with self._lock:
            values = sorted(self._values.items())
        for key, v in values:
            yield f"{self.name}{_fmt_labels(key)} {v}"


class Gauge:
    def __init__(self, name: str, help_text: str = ""):
        self.name = name
        self.help = help_text
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels: str) -> None:
        with self._lock:
            self._values[_label_key(labels)] = value

    def value(self, **labels: str) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} gauge"
        with self._lock:
            values = sorted(self._values.items())
        for key, v in values:
            yield f"{self.name}{_fmt_labels(key)} {v}"


class Histogram:
    def __init__(self, name: str, help_text: str = "", buckets: tuple = _DEFAULT_BUCKETS):
        self.name = name
        self.help = help_text
        self.buckets = tuple(sorted(buckets))
        # Per labelset, observations PER BUCKET (not cumulative; the last
        # slot is +Inf): an observe touches one slot, and render /
        # percentile cumulate. Spans feed two of these per completed span.
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}
        self._totals: dict[tuple, int] = {}
        # Latest exemplar per (labelset, bucket index): (trace_id, value,
        # unix_ts). Rendered OpenMetrics-style on the bucket line, so a
        # p99 breach on the dashboard links straight to a trace id in the
        # flight recorder / Jaeger. len(buckets) indexes the +Inf bucket.
        self._exemplars: dict[tuple, dict[int, tuple[str, float, float]]] = {}
        self._lock = threading.Lock()

    def _slots(self, key: tuple) -> list[int]:
        """Caller holds the lock."""
        counts = self._counts.get(key)
        if counts is None:
            counts = self._counts[key] = [0] * (len(self.buckets) + 1)
            self._sums[key] = 0.0
            self._totals[key] = 0
            self._exemplars[key] = {}
        return counts

    def observe(self, value: float, exemplar: str | None = None, **labels: str) -> None:
        self.observe_key(_label_key(labels), value, exemplar)

    def observe_key(self, key: tuple, value: float,
                    exemplar: str | None = None) -> None:
        """``observe`` for a caller that keeps its labelset's key (the
        sorted ``(name, value)`` pairs): the span sinks, once per span."""
        index = bisect_left(self.buckets, value)  # first bound >= value
        with self._lock:
            counts = self._counts.get(key) or self._slots(key)
            counts[index] += 1
            self._sums[key] += value
            self._totals[key] += 1
            if exemplar is not None:
                self._exemplars[key][index] = (exemplar, value, time.time())

    def observe_many(self, values, exemplar: str | None = None, **labels: str) -> None:
        """Vectorized observe for batch paths: one lock hold + one
        histogram pass for N values (a per-row observe() on an 8192-row
        wire batch would put Python loops back on the hot path)."""
        import numpy as np

        arr = np.asarray(values, dtype=np.float64).ravel()
        if arr.size == 0:
            return
        key = _label_key(labels)
        # how many values <= buckets[i], then per bucket; the rest is +Inf
        le_counts = np.searchsorted(np.sort(arr), self.buckets, side="right")
        per_bucket = np.diff(le_counts, prepend=0, append=arr.size)
        with self._lock:
            counts = self._slots(key)
            for i, c in enumerate(per_bucket):
                counts[i] += int(c)
            self._sums[key] += float(arr.sum())
            self._totals[key] += int(arr.size)
            if exemplar is not None:
                # One exemplar per batch: the worst value is the one a
                # latency investigation wants to click through to.
                worst = float(arr.max())
                self._exemplars[key][bisect_left(self.buckets, worst)] = (
                    exemplar, worst, time.time())

    def percentile(self, q: float, **labels: str) -> float:
        """Approximate percentile from bucket boundaries (upper bound)."""
        key = _label_key(labels)
        with self._lock:
            total = self._totals.get(key, 0)
            if total == 0:
                return 0.0
            target = q * total
            seen = 0
            for bound, c in zip(self.buckets, self._counts[key]):
                seen += c
                if seen >= target:
                    return bound
            return float("inf")

    def count(self, **labels: str) -> int:
        # Same discipline as percentile()/render(): _totals is written
        # under _lock from scorer threads, so read it under _lock too.
        with self._lock:
            return self._totals.get(_label_key(labels), 0)

    @staticmethod
    def _exemplar_suffix(ex: tuple[str, float, float] | None) -> str:
        if ex is None:
            return ""
        trace_id, value, ts = ex
        return f' # {{trace_id="{trace_id}"}} {value} {round(ts, 3)}'

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} histogram"
        with self._lock:
            snap = {
                key: (list(self._counts[key]), self._sums[key],
                      self._totals[key], dict(self._exemplars.get(key, {})))
                for key in self._totals
            }
        for key in sorted(snap):
            counts, _sum, _total, exemplars = snap[key]
            seen = 0
            for i, (bound, c) in enumerate(zip(self.buckets, counts)):
                seen += c
                lk = key + (("le", str(bound)),)
                yield (f"{self.name}_bucket{_fmt_labels(tuple(sorted(lk)))} {seen}"
                       f"{self._exemplar_suffix(exemplars.get(i))}")
            lk = key + (("le", "+Inf"),)
            yield (f"{self.name}_bucket{_fmt_labels(tuple(sorted(lk)))} {_total}"
                   f"{self._exemplar_suffix(exemplars.get(len(self.buckets)))}")
            yield f"{self.name}_sum{_fmt_labels(key)} {_sum}"
            yield f"{self.name}_count{_fmt_labels(key)} {_total}"


class Registry:
    def __init__(self):
        self._metrics: list = []
        self._lock = threading.Lock()
        # Called before every render, off any request: series whose source
        # is read rather than pushed (/proc counters, the host profiler's
        # exclusive-time accumulators) are brought up to date here.
        self._refreshers: list[Callable[[], None]] = []

    def add_refresher(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn not in self._refreshers:
                self._refreshers.append(fn)

    def remove_refresher(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn in self._refreshers:
                self._refreshers.remove(fn)

    def counter(self, name: str, help_text: str = "") -> Counter:
        m = Counter(name, help_text)
        with self._lock:
            self._metrics.append(m)
        return m

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        m = Gauge(name, help_text)
        with self._lock:
            self._metrics.append(m)
        return m

    def histogram(self, name: str, help_text: str = "", buckets: tuple = _DEFAULT_BUCKETS) -> Histogram:
        m = Histogram(name, help_text, buckets)
        with self._lock:
            self._metrics.append(m)
        return m

    def render_text(self) -> str:
        with self._lock:
            refreshers = list(self._refreshers)
        for fn in refreshers:
            try:
                fn()
            except Exception:  # noqa: BLE001 — a stale series must not fail the scrape
                pass
        lines: list[str] = []
        with self._lock:
            for m in self._metrics:
                lines.extend(m.render())
        return "\n".join(lines) + "\n"


class ServiceMetrics:
    """The series the reference's stubs name (risk/cmd/main.go:344-353)."""

    def __init__(self, service: str, registry: Registry | None = None):
        self.registry = registry or Registry()
        self._stage_keys: dict[str, tuple] = {}  # span name -> labelset key
        self.requests_total = self.registry.counter(
            f"{service}_grpc_requests_total", "gRPC requests by method and code"
        )
        self.request_duration_ms = self.registry.histogram(
            f"{service}_grpc_request_duration_ms", "gRPC request latency (ms)"
        )
        self.errors_total = self.registry.counter(
            f"{service}_grpc_errors_total", "gRPC errors by method"
        )
        self.score_distribution = self.registry.histogram(
            f"{service}_risk_score", "Fraud score distribution",
            buckets=(10, 20, 30, 40, 50, 60, 70, 80, 90, 100),
        )
        self.txns_scored_total = self.registry.counter(
            f"{service}_txns_scored_total", "Transactions fraud-scored"
        )
        self.batch_occupancy = self.registry.histogram(
            f"{service}_batch_occupancy", "Rows per device batch",
            buckets=(1, 8, 32, 64, 128, 256, 512, 1024),
        )
        self.abuse_shed_total = self.registry.counter(
            f"{service}_abuse_shed_total",
            "CheckBonusAbuse requests shed with UNAVAILABLE "
            "(ABUSE_CPU_POLICY=shed on a degraded deployment)",
        )
        self.bulk_shed_total = self.registry.counter(
            f"{service}_bulk_shed_total",
            "Bulk ScoreBatch RPCs rejected RESOURCE_EXHAUSTED by admission "
            "control (BULK_MAX_INFLIGHT) so the single-txn fast lane keeps "
            "its latency SLO under overload",
        )
        self.bulk_gate_limit = self.registry.gauge(
            f"{service}_bulk_gate_limit",
            "Current bulk-admission in-flight limit (p99-feedback controller "
            "tightens it below BULK_MAX_INFLIGHT when single-txn latency "
            "breaches BULK_P99_SLO_MS)",
        )
        # Device-resident HBM feature cache (serve/device_cache.py): the
        # index-mode wire ships int32 slot indices instead of feature rows;
        # these series are the cache's health dashboard.
        self.feature_cache_hits_total = self.registry.counter(
            f"{service}_feature_cache_hits_total",
            "ScoreBatch rows served from the device-resident feature table",
        )
        self.feature_cache_misses_total = self.registry.counter(
            f"{service}_feature_cache_misses_total",
            "Rows host-gathered and promoted into the device table (cold "
            "account or capacity miss)",
        )
        self.feature_cache_evictions_total = self.registry.counter(
            f"{service}_feature_cache_evictions_total",
            "Resident rows reclaimed by the CLOCK hand to admit new accounts",
        )
        self.feature_cache_deltas_total = self.registry.counter(
            f"{service}_feature_cache_deltas_total",
            "Per-account delta rows folded into HBM by the jitted scatter",
        )
        self.feature_cache_occupancy = self.registry.gauge(
            f"{service}_feature_cache_occupancy",
            "Device feature-table slots currently resident",
        )
        # Slot-sharded state (parallel/state_sharding.py): per-shard
        # breakdowns, labels bounded by the mesh data-axis size (<= 8 on
        # a v5e-8 — MX05-clean).
        self.cache_shard_occupancy = self.registry.gauge(
            f"{service}_cache_shard_occupancy",
            "Resident feature-table slots per mesh shard ({shard} = "
            "data-axis index; one series when the table is replicated) "
            "— a skewed spread means the CLOCK hand is fighting a hot "
            "key range, see docs/operations.md 'Pod-as-unit fleet'",
        )
        # Per-shard state bytes ride the existing {service}_hbm_bytes
        # gauge (registered with the runtime-telemetry block below) as
        # {shard, table} series beside its backend {kind} series.
        # Business-level series backing the Grafana dashboards the reference
        # README promises (README.md:196-202) but ships no data for: per-type
        # transaction flow (bonus conversion = bonus_grant rate vs deposit
        # rate) and LTV segment assignment counts.
        self.transactions_total = self.registry.counter(
            f"{service}_transactions_total", "Completed transactions by type"
        )
        self.transaction_amount_cents = self.registry.counter(
            f"{service}_transaction_amount_cents_total", "Transaction volume in cents by type"
        )
        self.ltv_segment_total = self.registry.counter(
            f"{service}_ltv_segment_total", "LTV segment assignments by segment"
        )
        self.reconciliation_checked = self.registry.gauge(
            f"{service}_reconciliation_checked", "Accounts checked by the last reconciliation sweep"
        )
        self.reconciliation_mismatched = self.registry.gauge(
            f"{service}_reconciliation_mismatched", "Balance/ledger mismatches in the last sweep"
        )
        # Request-lifecycle tracing (obs/tracing.py): every stage span on
        # the serving path lands here by stage name, with the worst sample
        # per bucket carrying its trace id as an exemplar — a p99 breach
        # on the dashboard links straight to a flight-recorder entry.
        self.stage_latency_ms = self.registry.histogram(
            f"{service}_stage_latency_ms",
            "Serving-path stage latency (ms) by lifecycle stage "
            "(score.admission/decode/gather/cache_lookup/dispatch/"
            "readback/encode/queue, follower.device_step); bucket lines "
            "carry trace-id exemplars",
            buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000),
        )
        self.batcher_queue_depth = self.registry.gauge(
            f"{service}_batcher_queue_depth",
            "Requests still waiting in the continuous batcher's queue at "
            "the moment a batch was assembled",
        )
        self.batcher_time_in_queue_ms = self.registry.histogram(
            f"{service}_batcher_time_in_queue_ms",
            "Per-request wait (ms) between batcher enqueue and batch "
            "assembly — the batching-window share of single-txn latency",
            buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250),
        )
        # Deadline scheduler (serve/deadline.py + serve/batcher.py): the
        # admission→dispatch deadline plane. Labels are bounded
        # enumerations per MX05: lane ∈ {interactive, bulk, background},
        # stage ∈ {admission, dispatch, router}.
        self.deadline_remaining_ms = self.registry.histogram(
            f"{service}_deadline_remaining_ms",
            "Remaining per-request deadline budget (ms) at the moment its "
            "batch dispatched — the headroom the scheduler left the device "
            "step + readback + encode; mass near 0 means admitted requests "
            "are barely making it",
            buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000),
        )
        self.deadline_expired_total = self.registry.counter(
            f"{service}_deadline_expired_total",
            "Requests shed because their deadline budget was already spent, "
            "by {stage}: admission = rejected at the RPC edge before any "
            "work, dispatch = expired while queued in the scheduler (shed "
            "at batch assembly, never scored dead), router = rejected at "
            "the L7 router hop — all DEADLINE_EXCEEDED + retry-pushback, "
            "counted as sheds, never SLO budget burn",
        )
        self.lane_depth = self.registry.gauge(
            f"{service}_lane_depth",
            "Queued requests per scheduler priority {lane} (interactive "
            "ScoreTransaction > bulk ScoreBatch > background jobs) at the "
            "last submit/assembly — the per-lane view of "
            "batcher_queue_depth",
        )
        self.batch_size_chosen = self.registry.histogram(
            f"{service}_batch_size_chosen",
            "Padded batch shape the deadline scheduler planned per tick "
            "against the tightest admitted deadline and the online "
            "step-time model — small tiers under tight budgets, the "
            "throughput shape when there is slack",
            buckets=(1, 8, 32, 64, 128, 256, 512, 1024, 2048, 4096),
        )
        # Pipelined host engine (serve/pipeline_engine.py): stage-worker
        # health for the wire batch paths.
        self.pipeline_inflight = self.registry.gauge(
            f"{service}_pipeline_inflight",
            "Device batches currently in flight in the staged host "
            "pipeline (dispatched, readback pending); bounded by the "
            "configured pipeline depth plus the batch each stage worker "
            "holds in hand",
        )
        self.pipeline_overlap_ratio = self.registry.gauge(
            f"{service}_pipeline_overlap_ratio",
            "Host-stage overlap ratio of the pipelined wire path "
            "(1 - active wall / summed stage busy time): 0 = stages run "
            "back-to-back, higher = gather/dispatch/readback/encode "
            "genuinely concurrent",
        )
        # Self-healing supervisor (serve/supervisor.py): the serving state
        # machine, per-dependency circuit breakers, and the degraded
        # scoring tier — the availability dashboard for chaos soaks.
        self.serving_state = self.registry.gauge(
            f"{service}_serving_state",
            "Serving state machine: 0=SERVING (all dependencies healthy), "
            "1=DEGRADED (a dependency circuit is open; answers flow via "
            "the heuristic tier / single-host mesh, flagged not errored), "
            "2=BROWNOUT (degraded tier failing too; scoring sheds "
            "UNAVAILABLE and health reports NOT_SERVING)",
        )
        self.breaker_state = self.registry.gauge(
            f"{service}_breaker_state",
            "Per-dependency circuit breaker state by {dep}: 0=closed, "
            "1=half_open (probing), 2=open (calls short-circuited)",
        )
        self.degraded_responses_total = self.registry.counter(
            f"{service}_degraded_responses_total",
            "Scoring responses served by a degraded tier by {tier} "
            "(heuristic = CPU conservative scorer while the device "
            "circuit is open; single_host = multihost front stepping "
            "locally while a follower resurrects) — flagged responses, "
            "never errors",
        )
        self.watchdog_trips_total = self.registry.counter(
            f"{service}_watchdog_trips_total",
            "Device-step watchdog expirations (dispatch->readback over "
            "DEVICE_STEP_DEADLINE_S): each fails its in-flight window "
            "with UNAVAILABLE + retry-pushback and triggers an engine "
            "rebuild with warmup replay",
        )
        self.engine_rebuilds_total = self.registry.counter(
            f"{service}_engine_rebuilds_total",
            "Scoring-engine tear-down+rebuild cycles completed after a "
            "watchdog trip (the wedged-device recovery path)",
        )
        self.follower_resurrections_total = self.registry.counter(
            f"{service}_follower_resurrections_total",
            "Multihost followers that rejoined through the supervised "
            "reconnect loop (hello/fingerprint + param re-sync) after "
            "dying or wedging",
        )
        # Scale-out scoring fleet (serve/router.py): ring membership,
        # failover retries, and hedged-RPC accounting — the dashboard a
        # fleet chaos soak (FLEET_CHAOS artifacts) reads.
        self.ring_replicas = self.registry.gauge(
            f"{service}_ring_replicas",
            "Scoring replicas by ring {state}: serving and degraded "
            "replicas are IN the consistent-hash ring (degraded answers "
            "are flagged, not errored); brownout (replica health "
            "NOT_SERVING) and dead (probe/forward failures) replicas are "
            "evicted until the health watcher re-admits them",
        )
        self.router_retries_total = self.registry.counter(
            f"{service}_router_retries_total",
            "Router forward retries onto the next ring owner by {reason}: "
            "pushback = UNAVAILABLE carrying the server's "
            "grpc-retry-pushback-ms hint (honored with jitter), "
            "unavailable = UNAVAILABLE without a hint, link_drop = "
            "router->replica link fault (chaos seam router.forward)",
        )
        self.hedge_total = self.registry.counter(
            f"{service}_hedge_total",
            "Hedged ScoreTransaction RPCs by {outcome}: launched = a "
            "straggling primary crossed the latency-percentile hedge "
            "deadline and a copy went to the secondary ring owner; "
            "win_primary / win_hedge = which copy answered first (the "
            "loser is cancelled); both_failed = neither answered. Every "
            "launched hedge lands in exactly one terminal outcome",
        )
        # Durable decision ledger (serve/ledger.py): WAL append health,
        # fsync cadence cost, and the sink drain's backlog — the audit
        # pipeline's dashboard.
        self.ledger_records_total = self.registry.counter(
            f"{service}_ledger_records_total",
            "Decision records durably appended to the ledger WAL "
            "(CRC-framed, batched fsync off the scoring hot path)",
        )
        self.ledger_dropped_total = self.registry.counter(
            f"{service}_ledger_dropped_total",
            "Decision records dropped by {reason}: queue_full = the "
            "bounded append queue was full (scoring is never blocked), "
            "write_error = the WAL write failed (fs outage; the ledger "
            "breaker opens)",
        )
        self.ledger_fsync_ms = self.registry.histogram(
            f"{service}_ledger_fsync_ms",
            "Ledger WAL fsync latency (ms) — batched on a cadence "
            "(LEDGER_FSYNC_MS) so durability cost never rides a "
            "ScoreTransaction",
            buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250),
        )
        self.ledger_sink_queue_depth = self.registry.gauge(
            f"{service}_ledger_sink_queue_depth",
            "Decision records durable in the WAL but not yet delivered "
            "to the analytical sink (the drain's lag; grows through a "
            "sink outage, shrinks as the cursor catches up from disk)",
        )
        self.ledger_sink_sent_total = self.registry.counter(
            f"{service}_ledger_sink_sent_total",
            "Decision records delivered to the ClickHouse/PG sink "
            "(at-least-once: a cursor replay after SIGKILL may re-send)",
        )
        # Fleet-wide SLO plane (obs/slo.py): attainment against the
        # latency objective, multi-window burn rate, and which stage
        # consumed the error budget on violating requests.
        self.slo_requests_total = self.registry.counter(
            f"{service}_slo_requests_total",
            "Scoring RPCs counted against the latency SLO by {state} "
            "(the supervisor serving state each sample was scored under)",
        )
        self.slo_violations_total = self.registry.counter(
            f"{service}_slo_violations_total",
            "SLO-violating scoring RPCs by {state}: latency above the "
            "objective (SLO_OBJECTIVE_MS) or a server-fault status — "
            "sheds and caller errors never burn budget",
        )
        self.slo_burn_rate = self.registry.gauge(
            f"{service}_slo_burn_rate",
            "Error-budget burn rate by {window} (fast ~1 min / slow "
            "~1 h): violating fraction over the window divided by the "
            "budget fraction (1 - SLO_TARGET); 1.0 = budget consumed "
            "exactly at the sustainable rate, 10 = 10x too fast",
        )
        self.slo_attainment = self.registry.gauge(
            f"{service}_slo_attainment",
            "Fraction of scoring RPCs meeting the latency objective over "
            "the {window} (1.0 with no traffic — an idle replica is not "
            "a violating replica)",
        )
        self.slo_alert = self.registry.gauge(
            f"{service}_slo_alert",
            "Burn-rate alert state by {window}: 1 while the window's "
            "burn rate is at/above its alert threshold "
            "(SLO_FAST_BURN_ALERT / SLO_SLOW_BURN_ALERT), else 0",
        )
        self.slo_alerts_total = self.registry.counter(
            f"{service}_slo_alerts_total",
            "Burn-rate alert RAISE transitions by {window} — one per "
            "incident, not one per violating request",
        )
        self.slo_budget_stage_ms_total = self.registry.counter(
            f"{service}_slo_budget_stage_ms_total",
            "Stage busy-time (ms) accumulated on SLO-VIOLATING requests "
            "by {stage} — the budget-attribution table: the stage with "
            "the largest share is where the budget went",
        )
        # Fleet aggregation plane (obs/fleetview.py): scrape health of
        # the cross-replica rollup served at /debug/fleetz.
        self.fleet_replicas_scraped = self.registry.gauge(
            f"{service}_fleet_replicas_scraped",
            "Replicas in the fleet view by {freshness}: fresh = last "
            "scrape within the staleness horizon, stale = dead/hung/"
            "failing replicas still shown from last-good state",
        )
        self.fleet_scrape_failures_total = self.registry.counter(
            f"{service}_fleet_scrape_failures_total",
            "Failed sidecar scrape passes by {replica} (bounded-timeout "
            "fetch of /metrics + debug surfaces; the plane keeps serving "
            "last-good state)",
        )
        self.fleet_scrape_ms = self.registry.histogram(
            f"{service}_fleet_scrape_ms",
            "Wall time (ms) of one successful replica sidecar scrape "
            "(all endpoints)",
            buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000),
        )
        # Device-runtime telemetry (obs/runtime_telemetry.py): the
        # signals the flight recorder is blind to — recompiles, dispatch
        # amplification, step-time anomalies, HBM-side occupancy.
        self.compile_events_total = self.registry.counter(
            f"{service}_compile_events_total",
            "Device program compilations observed at the jax monitoring "
            "seam by {kind} — a non-zero steady-state rate is a "
            "recompile storm (shape drift or static-arg churn)",
        )
        self.compile_wall_ms = self.registry.histogram(
            f"{service}_compile_wall_ms",
            "Wall time (ms) of each backend compile — the latency cliff "
            "a recompiling request falls off",
            buckets=(1, 5, 25, 100, 500, 1000, 5000, 15000, 60000),
        )
        self.compile_signatures_total = self.registry.counter(
            f"{service}_compile_signatures_total",
            "Distinct launch shape signatures seen since boot — fires "
            "exactly once per new (fn, shape, dtype); growth after "
            "warmup means the batcher is feeding uncompiled shapes",
        )
        self.device_dispatches_total = self.registry.counter(
            f"{service}_device_dispatches_total",
            "Compiled-step dispatches (score.dispatch/score.device "
            "stages) — with txns_scored_total this is the "
            "dispatch-amplification ratio; per-request counts ride the "
            "flight entries' `dispatches` attribute",
        )
        self.h2d_transfers_total = self.registry.counter(
            f"{service}_h2d_transfers_total",
            "Host (numpy) arguments handed to the index-mode scoring "
            "program, one host-to-device transfer each (the packed chunk: "
            "one a launch, placed once a device): reckoned once per "
            "(program, padded shape), added per launch",
        )
        self.h2d_bytes_total = self.registry.counter(
            f"{service}_h2d_bytes_total",
            "Bytes of those host arguments (their nbytes) — what crosses "
            "the link on the way in, per launch",
        )
        self.launch_padded_rows_total = self.registry.counter(
            f"{service}_launch_padded_rows_total",
            "Padded rows of every launch of the index-mode scoring "
            "program (the ladder rung the chunk ran): over the rows "
            "acknowledged it is what padding to a rung costs, 1.0 where "
            "every frame fills its rung",
        )
        self.host_cpu_steal_seconds_total = self.registry.counter(
            f"{service}_host_cpu_steal_seconds_total",
            "Steal column of /proc/stat's cpu line: seconds the "
            "hypervisor ran somebody else while this machine had work; "
            "refreshed at render, never on a request",
        )
        self.host_cpu_seconds_total = self.registry.counter(
            f"{service}_host_cpu_seconds_total",
            "All columns of /proc/stat's cpu line (every CPU's seconds, "
            "idle included): the denominator of the steal share. A "
            "sandboxed kernel shows that line as zeros: both counters then "
            "stay 0 and the share cannot be read, which is not 'no steal'",
        )
        self.process_runqueue_wait_seconds_total = self.registry.counter(
            f"{service}_process_runqueue_wait_seconds_total",
            "Seconds this process's threads stood runnable on a run queue "
            "without a CPU (second field of /proc/self/task/*/schedstat, "
            "summed over the live threads); stays 0 where the kernel "
            "keeps no schedstat, which is 'cannot be read', not 'no wait'",
        )
        self.process_cpu_seconds_total = self.registry.counter(
            f"{service}_process_cpu_seconds_total",
            "Seconds this process's threads ran on a CPU (first field of "
            "schedstat, summed; time.process_time where the kernel keeps "
            "no schedstat): the denominator of the run-queue share",
        )
        self.step_anomalies_total = self.registry.counter(
            f"{service}_step_anomalies_total",
            "Device step-time EWMA anomalies by {stage}: a sample beyond "
            "mean + k*sigma (ANOMALY_K_SIGMA) and the absolute floor — "
            "each stamps its flight entry with the anomalous stage",
        )
        self.anomaly_profiles_total = self.registry.counter(
            f"{service}_anomaly_profiles_total",
            "Automatic device-profile captures triggered by step-time "
            "anomalies (cooldown-limited: one per "
            "ANOMALY_PROFILE_COOLDOWN_S, keyed by the anomalous trace id)",
        )
        self.arena_buffers = self.registry.gauge(
            f"{service}_arena_buffers",
            "Staging-arena buffer accounting by {kind}: allocated = "
            "fresh allocations since boot, reused = recycled handouts, "
            "idle = buffers parked on free lists (serve/arena.py); "
            "refreshed on every /metrics scrape",
        )
        self.hbm_bytes = self.registry.gauge(
            f"{service}_hbm_bytes",
            "Device memory: {kind} series (in_use/limit/peak) from the "
            "backend's memory_stats — absent on backends that do not "
            "report (CPU) — plus {shard, table} series for the "
            "slot-sharded state tables (feature_cache / session_ring "
            "bytes per mesh shard, the per-chip capacity accounting of "
            "docs/architecture.md 'Sharded state')",
        )
        # Online learning loop (train/online.py, serve/shadow.py,
        # train/promote.py): shadow-scoring evidence, mined training
        # examples, and the promotion/rollback event stream.
        self.shadow_rows_total = self.registry.counter(
            f"{service}_shadow_rows_total",
            "Live rows handled by the shadow scorer by {outcome}: scored "
            "= candidate params re-scored them next to production, "
            "dropped = the bounded shadow queue was full (production is "
            "never blocked), skipped = no host feature snapshot "
            "(index-mode / heuristic-tier rows)",
        )
        self.shadow_action_flips_total = self.registry.counter(
            f"{service}_shadow_action_flips_total",
            "Shadow-scored rows whose candidate action differs from the "
            "action production actually took — the numerator of the "
            "promotion flip-rate gate",
        )
        self.shadow_score_divergence = self.registry.histogram(
            f"{service}_shadow_score_divergence",
            "Absolute candidate-vs-production risk-score divergence per "
            "shadow-scored row (0-100 scale)",
            buckets=(0, 1, 2, 5, 10, 20, 40, 60, 80, 100),
        )
        self.online_mined_total = self.registry.counter(
            f"{service}_online_mined_total",
            "Training examples mined from the decision WAL by {kind}: "
            "hard = hard negatives (scored risky, outcome legitimate) "
            "plus missed fraud, labeled = other outcome-labeled rows",
        )
        self.online_train_steps_total = self.registry.counter(
            f"{service}_online_train_steps_total",
            "Incremental learner steps taken by the online loop on the "
            "serving device budget (train/serve coexistence)",
        )
        self.promotions_total = self.registry.counter(
            f"{service}_promotions_total",
            "Param-set transitions on the serving engine by {event}: "
            "promote (all gates passed), rollback (post-promotion gate "
            "regressed), forced_promote / forced_rollback (operator "
            "knobs) — each also lands a PromotionRecord in the ledger",
        )
        self.promotion_gate_failures_total = self.registry.counter(
            f"{service}_promotion_gate_failures_total",
            "Candidate promotions held back by {gate} (train/gates.py "
            "bounds: probe-AUC floor, no-regression margin, shadow "
            "rows/flip-rate, SLO-quiet) — a persistently failing gate "
            "is the drift dashboard's first stop",
        )
        # Streaming drift & data-quality observatory (obs/drift.py):
        # on-path feature/score sketches compared against a pinned
        # reference, score calibration against mined outcomes, and the
        # raise/clear drift alerts the drift_quiet promotion gate reads.
        self.drift_rows_total = self.registry.counter(
            f"{service}_drift_rows_total",
            "Scored rows handled by the drift observatory by {outcome}: "
            "sketched = folded into the rolling window by the drift "
            "worker, dropped = the bounded sketch queue was full "
            "(scoring is never blocked), skipped = unsketchable rows "
            "(int8-compressed wire, heuristic tier)",
        )
        self.drift_window_rows = self.registry.gauge(
            f"{service}_drift_window_rows",
            "Rows currently inside the drift engine's rolling window "
            "(evaluation needs DRIFT_MIN_ROWS before it trusts PSI)",
        )
        self.drift_psi = self.registry.gauge(
            f"{service}_drift_psi",
            "Per-feature Population Stability Index of the rolling "
            "window vs the pinned reference by {feature} (bounded: the "
            "30-name feature schema); > DRIFT_PSI_ALERT raises the "
            "input drift alert",
        )
        self.drift_ks = self.registry.gauge(
            f"{service}_drift_ks",
            "Per-feature Kolmogorov-Smirnov statistic (binned, exact to "
            "bucket resolution) of the rolling window vs the pinned "
            "reference by {feature}",
        )
        self.drift_output_psi = self.registry.gauge(
            f"{service}_drift_output_psi",
            "PSI of the model OUTPUT distributions vs the pinned "
            "reference by {dist} (score = the 0-100 risk-score "
            "histogram, action = approve/review/block counts) — output "
            "shift with quiet inputs is concept drift",
        )
        self.drift_calibration_error = self.registry.gauge(
            f"{service}_drift_calibration_error",
            "Weighted |observed - reference| fraud rate across score "
            "bins over the calibration window (outcomes mined from the "
            "decision WAL); > DRIFT_CAL_ALERT raises the calibration "
            "drift alert",
        )
        self.drift_shadow_divergence = self.registry.gauge(
            f"{service}_drift_shadow_divergence",
            "Mean |candidate - production| score delta of shadow-scored "
            "rows over the drift window — candidate divergence trended "
            "next to input drift so a drifting candidate is visible "
            "before any promotion gate runs",
        )
        self.drift_alert = self.registry.gauge(
            f"{service}_drift_alert",
            "Drift alert state by {kind} (input / score / calibration): "
            "1 while the kind's divergence is at/above its raise "
            "threshold (hysteresis clears at half) — any active kind "
            "holds promotion via the drift_quiet gate",
        )
        self.drift_alerts_total = self.registry.counter(
            f"{service}_drift_alerts_total",
            "Drift alert RAISE transitions by {kind} — one per "
            "incident, not one per drifted batch",
        )
        # Stateful sequence scoring (serve/session_state.py): the
        # per-account session ring beside the feature cache, its fused
        # session head, and the honest cold/bypass accounting.
        self.session_rows_total = self.registry.counter(
            f"{service}_session_rows_total",
            "Rows scored while session state is enabled by {outcome}: "
            "warm = the post-append window reached SESSION_MIN_EVENTS "
            "and the session head spoke, cold = window still too short "
            "(SESSION_COLD reason bit set — the honest stateless "
            "fallback), bypass = scored on a non-session path (row wire "
            "mode, batcher, heuristic tier) so the window did not "
            "advance",
        )
        self.session_appends_total = self.registry.counter(
            f"{service}_session_appends_total",
            "Events appended to per-account session windows by the fused "
            "scoring step's donated in-place ring scatter (one per "
            "session-scored row)",
        )
        self.session_twin_bytes = self.registry.gauge(
            f"{service}_session_twin_bytes",
            "Host bytes held by the per-account session buffers (sum of "
            "their capacities; a buffer grows with the events its "
            "account holds, up to 4 * SESSION_EVENTS rows) - over "
            "accounts_tracked of /debug/sessionz it is what an account "
            "costs the host",
        )
        self.session_twin_regrows_total = self.registry.counter(
            f"{service}_session_twin_regrows_total",
            "Session appends that had to reallocate an account's host "
            "buffer after its first allocation (growth or compaction) - "
            "over session_appends_total it is the share of events that "
            "pay a copy",
        )
        self.session_head_positions_total = self.registry.counter(
            f"{service}_session_head_positions_total",
            "Window positions the session head computed for session-scored "
            "rows: rows x SESSION_EVENTS (every row is one whole window, "
            "whatever it holds)",
        )
        self.session_head_real_positions_total = self.registry.counter(
            f"{service}_session_head_real_positions_total",
            "Of those positions, the ones that held an event (the sum of "
            "the rows' post-append window lengths): over "
            "session_head_positions_total it is what the padding of short "
            "windows costs the head",
        )
        self.session_head_key_blocks_visited_total = self.registry.counter(
            f"{service}_session_head_key_blocks_visited_total",
            "The area, in (query, key) pairs, of the key blocks the "
            "attention cores of a head that goes over its keys in blocks "
            "scored for session-scored rows, a query head: rows x the pairs "
            "one window's layers score at the traced window, each layer in "
            "the blocks of the form it runs (a banded layer skips what lies "
            "before its band, every layer what lies past the diagonal); 0 "
            "for every other head",
        )
        self.session_head_key_blocks_square_total = self.registry.counter(
            f"{service}_session_head_key_blocks_square_total",
            "Pairs of the same layers' whole squares (rows x layers x "
            "padded window^2): over it "
            "session_head_key_blocks_visited_total is the share of the "
            "square the cores score",
        )
        self.session_head_layer_positions_computed_total = self.registry.counter(
            f"{service}_session_head_layer_positions_computed_total",
            "Layer-positions a head whose stack narrows computed for "
            "session-scored rows: rows x (the layers that mix positions x "
            "SESSION_EVENTS + the layers after them x the one position that "
            "is scored); 0 for every head that runs every layer at every "
            "position",
        )
        self.session_head_layer_positions_whole_total = self.registry.counter(
            f"{service}_session_head_layer_positions_whole_total",
            "Layer-positions of the same rows with every layer at every "
            "position (rows x layers x SESSION_EVENTS): over it "
            "session_head_layer_positions_computed_total is the share of the "
            "stack a scored row pays for",
        )
        self.session_head_resident_bytes = self.registry.gauge(
            f"{service}_session_head_resident_bytes",
            "Device bytes of the session head's parameter tree (0 for a "
            "head without parameters), set once at boot: beside "
            "session_hbm_bytes and the feature table it is what the chip "
            "holds at rest",
        )
        self.session_head_experts_held = self.registry.gauge(
            f"{service}_session_head_experts_held",
            "Routed experts a layer of the session head held on this chip "
            "(0 for a head without an expert layer), set once at boot",
        )
        self.session_head_experts_routed = self.registry.gauge(
            f"{service}_session_head_experts_routed",
            "Experts the session head's router chooses among: with "
            "session_head_experts_held under it the chip holds a share of "
            "each layer and computes that share's part of the result",
        )
        self.session_head_layers = self.registry.gauge(
            f"{service}_session_head_layers",
            "Layers of the session head's stack by kind, set once at boot: "
            "kind=conv|attention|window|ssm|linear|memory|cross is a layer's "
            "operator (window: attention inside a band of keys; linear: "
            "linear attention; memory: a gate over an earlier layer's scan "
            "output; cross: attention over an earlier layer's keys and "
            "values; a layer that runs two, a state-space mixer beside "
            "attention, counts under both), kind=dense|moe its feed-forward, "
            "kind=mtp a multi-token-prediction module behind the stack "
            "(counted once; its own layer's operators count under their "
            "kinds besides; a head without layers reads 0 for all ten). A "
            "layer of the longcat head holds two attention and two dense "
            "sublayers and one moe branch across them: 8 / 8 / 4 for 4 layers",
        )
        self.session_head_residual_streams = self.registry.gauge(
            f"{service}_session_head_residual_streams",
            "Residual streams a layer of the session head carries, set once "
            "at boot: 1 for a head that threads one stream through h + "
            "f(norm(h)) (and for one without layers), more where a layer "
            "mixes several around each sublayer (hyper-connections)",
        )
        self.session_lock_wait_seconds_total = self.registry.counter(
            f"{service}_session_lock_wait_seconds_total",
            "Seconds index-mode chunks waited for the session lock "
            "before their host-index commit and device dispatch - over "
            "session_appends_total it is what the serial section costs a "
            "row of the other clients",
        )
        self.session_lock_held_seconds_total = self.registry.counter(
            f"{service}_session_lock_held_seconds_total",
            "Seconds index-mode chunks held the session lock (host-index "
            "commit, pad, dispatch of the fused step, adoption of the "
            "donated ring): the serial section itself",
        )
        self.session_rehydrations_total = self.registry.counter(
            f"{service}_session_rehydrations_total",
            "Session windows restored into HBM from the host session "
            "index on feature-cache admission — an evicted account that "
            "returns gets its window back, never a silent cold start",
        )
        self.session_hbm_bytes = self.registry.gauge(
            f"{service}_session_hbm_bytes",
            "Device bytes held by the session ring (ring + cursors + "
            "lengths) — budget it against the feature table "
            "(docs/operations.md 'Session state')",
        )
        # Host-plane cost observatory (obs/hostprof.py): per-stage
        # µs/row cost distributions and the GC pause accounting — the
        # capacity-math series ("what does one row cost on the host, by
        # stage") behind /debug/hostprofz.
        self.host_stage_us_per_row = self.registry.histogram(
            f"{service}_host_stage_us_per_row",
            "Host cost per row (µs/row) by serving {stage} (decode/"
            "gather/cache_lookup/pad/dispatch/readback/session/"
            "ledger_note/encode), from the monotonic span clock; bucket "
            "lines carry trace-id exemplars — the per-row capacity "
            "figure docs/architecture.md 'Reading a host flamegraph' "
            "explains",
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250),
        )
        self.host_stage_self_seconds_total = self.registry.counter(
            f"{service}_host_stage_self_seconds_total",
            "Exclusive wall seconds by serving {stage}: each span's "
            "duration minus the same-thread child spans inside it, so the "
            "stages tile the attributed host time with nothing counted "
            "twice; brought up to date at every render",
        )
        self.host_stage_cpu_seconds_total = self.registry.counter(
            f"{service}_host_stage_cpu_seconds_total",
            "Exclusive thread-CPU seconds by serving {stage}, an "
            "ESTIMATE: time.thread_time is read at span entry and exit "
            "(children subtracted) on one trace in seven "
            "(tracing.CPU_SAMPLE_EVERY) and counted seven-fold, so read it "
            "over windows of many requests (+-10% over 20 s; a tiny span "
            "reads multiples of the clock's tick). Over "
            "host_stage_self_seconds_total it is the share of the "
            "attributed wall during which the thread was on a CPU; the "
            "rest is the *_wait stages (device, lock, lane), GIL wait, "
            "preemption, steal",
        )
        # The heartbeat and its stall watch (obs/hostprof.Heartbeat),
        # brought up to date at every render like the two above.
        self.host_heartbeat_ticks_total = self.registry.counter(
            f"{service}_host_heartbeat_ticks_total",
            "Wakes of the heartbeat thread, which sleeps a fixed 50 ms: "
            "about 20 a second; the denominator of the mean wake-up "
            "lateness",
        )
        self.host_heartbeat_late_seconds_total = self.registry.counter(
            f"{service}_host_heartbeat_late_seconds_total",
            "Seconds the heartbeat thread woke later than it asked to, "
            "summed: the OS's timer and wake-up plus the wait for the "
            "interpreter lock. Over host_heartbeat_ticks_total it is the "
            "mean lateness of a TIMED wake: an upper indicator of what a "
            "handler thread pays when it comes back from a lock, a device "
            "wait or a native call (a signalled wake pays less), 0.7 ms "
            "on an idle process under a sandboxed kernel, 1-2.4 ms under "
            "load, and it swings by about half between runs",
        )
        self.rpc_stalls_total = self.registry.counter(
            f"{service}_rpc_stalls_total",
            "Incidents in which an rpc.* root stayed open past "
            "STALL_DUMP_MS, by {kind}: waiting (the interpreter ran; the "
            "sampled stacks name the holder) or interpreter_blocked (a "
            "heartbeat tick itself woke later than the threshold: the GIL "
            "held in native code, or the process not scheduled). Each one "
            "is at /debug/stallz with every thread's stack",
        )
        self.rpc_stall_seconds_total = self.registry.counter(
            f"{service}_rpc_stall_seconds_total",
            "Seconds the RPCs held in stall incidents spent past "
            "STALL_DUMP_MS, summed over the held RPCs",
        )
        self.gc_collections_total = self.registry.counter(
            f"{service}_gc_collections_total",
            "Python GC collections by {generation} — a hot gen-2 rate "
            "on a scoring replica means allocation churn is reaching "
            "the old generation and paying full-heap pauses",
        )
        self.gc_pause_ms = self.registry.histogram(
            f"{service}_gc_pause_ms",
            "Python GC stop-the-world pause (ms) by {generation}; the "
            "hostprofz page attributes each pause to the rpc.* roots "
            "in flight when it hit",
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100),
        )
        self.spans_dropped_total = self.registry.counter(
            f"{service}_spans_dropped_total",
            "Host spans evicted from the bounded span ring before export "
            "(a non-zero rate means /debug/spans and the OTLP drain are "
            "sampling, not complete)",
        )
        self.otlp_export_failures_total = self.registry.counter(
            f"{service}_otlp_export_failures_total",
            "OTLP/HTTP span export batches dropped on endpoint errors "
            "(spans are diagnostics: failures drop the batch, never block "
            "serving)",
        )

    def observe_rpc(self, method: str, start_time: float, code: str = "OK") -> None:
        self.requests_total.inc(method=method, code=code)
        self.request_duration_ms.observe((time.monotonic() - start_time) * 1000.0, method=method)
        if code != "OK":
            self.errors_total.inc(method=method)

    def observe_stage_span(self, span) -> None:
        """Span-sink adapter (obs/tracing.set_span_sink): stage spans feed
        the per-stage histogram keyed by span name; rpc.* roots are the
        whole-request spans already covered by request_duration_ms."""
        name = span.name
        if name.startswith("rpc."):
            return
        key = self._stage_keys.get(name)
        if key is None:
            key = self._stage_keys[name] = (("stage", name),)
        self.stage_latency_ms.observe_key(key, span.duration_ms, span.trace_id)
