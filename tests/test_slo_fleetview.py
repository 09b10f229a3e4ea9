"""Fleet-wide SLO plane: burn-rate math, bucket-wise histogram merge,
fleet scrape liveness, and device-runtime telemetry.

Five layers:

1. burn-rate / attainment window math against hand-computed fixtures
   (fake clock — no sleeps);
2. budget attribution: violating requests' stage busy-time ranks the
   injected stage first, serving-state annotation splits the counts;
3. property tests for the bucket-wise histogram merge (sum preservation
   over random observations, exemplar retained from the worst bucket,
   mixed bucket layouts rejected loudly);
4. the fleet view against three fake replica sidecars — one healthy,
   one DEAD (connection refused), one HUNG (the SIGSTOP shape: accepts,
   never answers): the scrape must bound its wall time and the snapshot
   must stay non-blocking with staleness stamps;
5. the compile watcher (exactly once per new shape signature), the
   step-time anomaly detector, and the anomaly->profile cooldown.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from igaming_platform_tpu.obs import fleetview as fv
from igaming_platform_tpu.obs import slo as slo_mod
from igaming_platform_tpu.obs.metrics import Histogram, ServiceMetrics
from igaming_platform_tpu.obs.runtime_telemetry import (
    CompileWatcher,
    RuntimeTelemetry,
    StepTimeAnomalyDetector,
)


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_engine(clock, **cfg_kwargs) -> slo_mod.SLOEngine:
    defaults = dict(objective_ms=50.0, target=0.99, fast_window_s=60.0,
                    slow_window_s=3600.0, fast_burn_alert=10.0,
                    slow_burn_alert=1.0)
    defaults.update(cfg_kwargs)
    return slo_mod.SLOEngine(slo_mod.SLOConfig(**defaults), clock=clock)


# ---------------------------------------------------------------------------
# 1. burn-rate window math — hand-computed fixtures


def test_burn_rate_hand_computed():
    clock = FakeClock()
    eng = make_engine(clock)
    # 200 requests over 40 s, 10 violating: bad fraction 5%, budget
    # fraction 1% -> burn 5.0 in both windows; attainment 0.95.
    for i in range(200):
        eng.observe(120.0 if i % 20 == 0 else 10.0, trace_id=f"t{i}")
        clock.advance(0.2)
    assert eng.burn_rate(60.0) == pytest.approx(5.0)
    assert eng.burn_rate(3600.0) == pytest.approx(5.0)
    assert eng.attainment(60.0) == pytest.approx(0.95)
    assert eng.requests_total == 200 and eng.violations_total == 10

    # 90 s later the fast window is empty (burn 0, attainment 1.0 by
    # convention — idle is not violating); the slow window still burns.
    clock.advance(90.0)
    assert eng.burn_rate(60.0) == 0.0
    assert eng.attainment(60.0) == 1.0
    assert eng.burn_rate(3600.0) == pytest.approx(5.0)


def test_errors_burn_budget_but_sheds_do_not():
    clock = FakeClock()
    eng = make_engine(clock)

    class Root:
        name = "rpc.ScoreTransaction"
        trace_id = "tr-err"
        duration_ms = 1.0
        stage_totals = None

    # A fast UNAVAILABLE burns budget; a fast RESOURCE_EXHAUSTED shed
    # and a wallet RPC do not.
    r = Root()
    r.attributes = {"code": "UNAVAILABLE"}
    eng.observe_root(r)
    r2 = Root()
    r2.attributes = {"code": "RESOURCE_EXHAUSTED"}
    eng.observe_root(r2)
    r3 = Root()
    r3.name = "rpc.Deposit"
    r3.attributes = {"code": "UNAVAILABLE"}
    eng.observe_root(r3)
    assert eng.requests_total == 2  # wallet RPC out of scope
    assert eng.violations_total == 1


def test_alert_raises_once_and_clears():
    clock = FakeClock()
    eng = make_engine(clock, fast_window_s=10.0, fast_burn_alert=10.0)
    # Every request violating -> burn 100 >> 10: alert raises once.
    for i in range(30):
        eng.observe(200.0, trace_id=f"v{i}")
        clock.advance(0.5)
    eng.refresh()
    assert eng.alerts_active()["fast"] is True
    raised = [e for e in eng.snapshot()["alert_events"]
              if e["window"] == "fast" and e["event"] == "raised"]
    assert len(raised) == 1
    # Window drains -> alert clears, with a cleared event.
    clock.advance(30.0)
    eng.refresh()
    assert eng.alerts_active()["fast"] is False
    cleared = [e for e in eng.snapshot()["alert_events"]
               if e["window"] == "fast" and e["event"] == "cleared"]
    assert len(cleared) == 1


# ---------------------------------------------------------------------------
# 2. budget attribution + serving-state annotation


def test_budget_attribution_ranks_injected_stage():
    clock = FakeClock()
    eng = make_engine(clock)
    # Violating requests dominated by dispatch; healthy requests have a
    # different stage mix which must NOT pollute the attribution.
    for i in range(50):
        eng.observe(10.0, stages={"score.gather": 8.0}, trace_id=f"ok{i}")
    for i in range(10):
        eng.observe(180.0, stages={"score.dispatch": 150.0,
                                   "score.gather": 5.0,
                                   "score.queue": 12.0},
                    trace_id=f"bad{i}")
    att = eng.attribution(3600.0)
    assert att["top_stage"] == "score.dispatch"
    assert att["stages"]["score.dispatch"]["ms"] == pytest.approx(1500.0)
    shares = [s["share"] for s in att["stages"].values()]
    assert sum(shares) == pytest.approx(1.0, abs=0.01)
    # Healthy gather time (50 * 8 ms) never entered the table.
    assert att["stages"]["score.gather"]["ms"] == pytest.approx(50.0)


def test_serving_state_annotation_splits_samples():
    clock = FakeClock()
    eng = make_engine(clock)
    for _ in range(5):
        eng.observe(10.0, state="serving")
    for _ in range(3):
        eng.observe(200.0, state="degraded")
    snap = eng.snapshot()
    assert snap["by_state"]["serving"]["requests"] == 5
    assert snap["by_state"]["serving"]["violations"] == 0
    assert snap["by_state"]["degraded"]["requests"] == 3
    assert snap["by_state"]["degraded"]["violations"] == 3
    assert snap["violating_exemplars"][-1]["state"] == "degraded"


# ---------------------------------------------------------------------------
# 3. bucket-wise histogram merge — property tests


def _render_parse(hist: Histogram) -> fv.HistogramSnapshot:
    parsed = fv.parse_histograms("\n".join(hist.render()))
    fam = parsed[hist.name]
    assert len(fam) == 1
    return next(iter(fam.values()))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_merge_preserves_sums_and_counts(seed):
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted(rng.choice(
        [0.5, 1, 2.5, 5, 10, 25, 50, 100, 250], size=5, replace=False)))
    hists = []
    totals = 0
    total_sum = 0.0
    for r in range(3):
        h = Histogram("risk_stage_latency_ms", "t", buckets=buckets)
        values = rng.uniform(0.1, 300.0, size=rng.integers(1, 200))
        for v in values:
            h.observe(float(v), exemplar=f"r{r}", stage="score.dispatch")
        totals += len(values)
        total_sum += float(values.sum())
        hists.append(_render_parse(h))
    merged = fv.merge_histograms(hists)
    assert merged.count == totals
    assert merged.sum == pytest.approx(total_sum, rel=1e-9)
    # Cumulative counts are monotone and end at the total.
    assert merged.counts == sorted(merged.counts)
    assert merged.counts[-1] == totals
    # The merged percentile is a valid bucket bound (or inf).
    p99 = merged.percentile(0.99)
    assert p99 == float("inf") or any(
        p99 == float(b) for b in merged.buckets if b != "+Inf")


def test_merge_retains_worst_exemplar():
    h1 = Histogram("risk_stage_latency_ms", "t", buckets=(1, 10, 100))
    h2 = Histogram("risk_stage_latency_ms", "t", buckets=(1, 10, 100))
    h1.observe(5.0, exemplar="mid", stage="s")
    h2.observe(500.0, exemplar="worst", stage="s")
    h2.observe(4.0, exemplar="mid2", stage="s")
    merged = fv.merge_histograms([_render_parse(h1), _render_parse(h2)])
    assert merged.worst_exemplar()[0] == "worst"
    # Per-bucket: the (1,10] bucket keeps the higher of the two values.
    bucket_idx = merged.buckets.index("10")
    assert merged.exemplars[bucket_idx][0] == "mid"


def test_merge_rejects_mixed_layouts_loudly():
    h1 = Histogram("risk_stage_latency_ms", "t", buckets=(1, 10, 100))
    h2 = Histogram("risk_stage_latency_ms", "t", buckets=(1, 5, 100))
    h1.observe(2.0, stage="s")
    h2.observe(2.0, stage="s")
    with pytest.raises(ValueError, match="bucket layout mismatch"):
        fv.merge_histograms([_render_parse(h1), _render_parse(h2)])


# ---------------------------------------------------------------------------
# 4. fleet view vs dead + hung replicas


def _sidecar(metrics_text: str, sloz: dict, flight: list,
             hang: bool = False):
    """A fake replica HTTP sidecar. ``hang`` reproduces the SIGSTOP
    shape: the socket accepts, the handler never answers."""

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if hang:
                time.sleep(30)
                return
            if self.path == "/metrics":
                body, ctype = metrics_text, "text/plain"
            elif self.path == "/debug/sloz":
                body, ctype = json.dumps(sloz), "application/json"
            elif self.path == "/debug/flightz":
                body, ctype = json.dumps(flight), "application/json"
            elif self.path == "/debug/supervisorz":
                body, ctype = '{"state": "serving"}', "application/json"
            else:
                self.send_response(404)
                self.end_headers()
                return
            data = body.encode()
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"127.0.0.1:{httpd.server_address[1]}"


def test_fleetz_survives_dead_and_hung_replica():
    h = Histogram("risk_stage_latency_ms", "t", buckets=(1, 10, 100))
    h.observe(7.0, exemplar="tr-slow", stage="score.dispatch")
    sloz = {"windows": {"fast": {"burn_rate": 3.0, "alert": False,
                                 "attainment": 0.97,
                                 "budget_attribution": {
                                     "top_stage": "score.dispatch"}},
                        "slow": {"burn_rate": 1.2, "alert": True}},
            "violations_total": 4}
    flight = [{"trace_id": "tr-slow", "method": "ScoreBatch",
               "duration_ms": 88.0, "stages_ms": {"score.dispatch": 80.0}}]
    healthy, healthy_addr = _sidecar("\n".join(h.render()), sloz, flight)
    hung, hung_addr = _sidecar("", {}, [], hang=True)
    # Dead replica: bind a port, then close it -> connection refused.
    dead_sock, dead_addr = _sidecar("", {}, [])
    dead_sock.shutdown()
    dead_sock.server_close()

    view = fv.FleetView(
        {"r0": healthy_addr, "r1": dead_addr, "r2": hung_addr},
        interval_s=0.2, timeout_s=0.3, stale_after_s=1.0,
        metrics=ServiceMetrics("risk"))
    try:
        t0 = time.monotonic()
        view.scrape_once()
        scrape_wall = time.monotonic() - t0
        # Bounded: ~4 endpoints x 0.3 s for the hung replica, concurrent
        # across replicas — never a 30 s hang.
        # timing-ok: the view's own 0.3 s timeouts, against a 30 s hang
        assert scrape_wall < 15.0, f"scrape blocked for {scrape_wall:.1f}s"

        scrapes = view.scrapes_total
        snap = view.snapshot()
        assert view.scrapes_total == scrapes, "snapshot must not scrape"

        by_rid = {r["replica"]: r for r in snap["replicas"]}
        assert by_rid["r0"]["stale"] is False
        assert by_rid["r1"]["stale"] is True
        assert by_rid["r1"]["last_error"]
        assert by_rid["r2"]["stale"] is True
        # Healthy replica's data flowed through the merge.
        assert by_rid["r0"]["slo"]["fast_burn_rate"] == 3.0
        assert by_rid["r0"]["slo"]["top_budget_stage"] == "score.dispatch"
        stage = snap["fleet_stage_latency_ms"]["score.dispatch"]
        assert stage["count"] == 1
        assert stage["exemplar_trace_id"] == "tr-slow"
        assert snap["slowest_traces"][0]["trace_id"] == "tr-slow"
        assert snap["slowest_traces"][0]["hops"][0]["replica"] == "r0"
    finally:
        view.stop()
        healthy.shutdown()
        healthy.server_close()
        hung.shutdown()
        hung.server_close()


def test_fleetz_merges_stage_histograms_across_replicas():
    def render(vals):
        h = Histogram("risk_stage_latency_ms", "t",
                      buckets=(1, 10, 100))
        for v in vals:
            h.observe(v, stage="score.gather")
        return "\n".join(h.render())

    s1, a1 = _sidecar(render([0.5, 2.0]), {}, [])
    s2, a2 = _sidecar(render([50.0, 2.0, 0.7]), {}, [])
    view = fv.FleetView({"a": a1, "b": a2}, interval_s=0.2, timeout_s=0.5)
    try:
        view.scrape_once()
        stage = view.snapshot()["fleet_stage_latency_ms"]["score.gather"]
        assert stage["count"] == 5
        # 4/5 <= 10ms -> p50 bucket bound well below the p99 bound.
        assert stage["p50_ms"] <= 10.0  # timing-ok: a bucket bound of seeded samples
        assert stage["p99_ms"] == 100.0
    finally:
        view.stop()
        for s in (s1, s2):
            s.shutdown()
            s.server_close()


# ---------------------------------------------------------------------------
# 5. runtime telemetry: compile signatures, anomalies, profile cooldown


def test_recompile_counter_fires_once_per_signature():
    w = CompileWatcher()
    assert w.note_signature("packed_step", (256, 30), "float32") is True
    assert w.note_signature("packed_step", (256, 30), "float32") is False
    assert w.note_signature("packed_step", (512, 30), "float32") is True
    assert w.note_signature("cached_step", (256, 30), "float32") is True
    assert w.note_signature("packed_step", (256, 30), "bfloat16") is True
    assert w.new_signatures_total == 4


def test_compile_watcher_counts_real_jax_compiles():
    import jax
    import jax.numpy as jnp

    w = CompileWatcher()
    w.install_listener()
    before = w.compiles_total
    w.note_signature("probe_fn", (7,), "float32")
    fn = jax.jit(lambda x: x * 3 + 1)
    jax.block_until_ready(fn(jnp.ones((7,))))
    assert w.compiles_total >= before + 1
    latest = w.snapshot()["recent_events"][-1]
    assert latest["wall_ms"] > 0
    assert latest["signature"] == "probe_fn:(7,):float32"


def test_anomaly_detector_flags_spike_not_jitter():
    det = StepTimeAnomalyDetector(min_ms=5.0, warmup=10, k_sigma=4.0)
    flagged = [det.observe(3.0 + 0.3 * (i % 4)) for i in range(50)]
    assert not any(flagged), "stable steps must not page"
    assert det.observe(200.0) is True
    # A sustained fault keeps flagging (damped adoption).
    assert det.observe(200.0) is True


def test_anomaly_profile_trigger_respects_cooldown():
    telemetry = RuntimeTelemetry(cooldown_s=60.0, profile_enabled=True)
    calls: list[str] = []
    telemetry.bind_profile_trigger(
        lambda tid, stage, ms: calls.append(tid) or {"log_dir": "/tmp/p"})

    class Span:
        def __init__(self, ms):
            self.name = "score.dispatch"
            self.duration_ms = ms
            self.trace_id = f"tr-{ms}"
            self.root = None
            self.attributes = {}

    for _ in range(40):
        telemetry.observe_span(Span(4.0))
    telemetry.observe_span(Span(300.0))
    telemetry.observe_span(Span(310.0))
    assert telemetry.anomalies_total == 2
    assert len(calls) == 1, "cooldown must keep a storm to one capture"
    assert len(telemetry.profile_captures) == 1
    cap = telemetry.profile_captures[0]
    assert cap["trace_id"] == "tr-300.0" and cap["log_dir"] == "/tmp/p"
    # Async completion folds the artifact location into the record.
    telemetry.note_capture_result("tr-300.0", {"ok": True, "seconds": 0.5})
    assert telemetry.profile_captures[0]["ok"] is True


def test_dispatch_launches_bump_root_and_counter():
    # PR 14: the dispatch counter is LAUNCH-driven (note_dispatch at the
    # scorer's _device_dispatch seam), not span-driven — a span that
    # wraps two launches counts 2, a launch outside any dispatch span
    # still counts 1, and the RPC root's `dispatches` attribute tracks
    # the same truth.
    from igaming_platform_tpu.obs import runtime_telemetry as rt_mod
    from igaming_platform_tpu.obs import tracing

    # Park any process-default telemetry (installed by gRPC services in
    # earlier tests) so this instance is the only dispatch counter.
    prev = rt_mod.get_default()
    if prev is not None:
        tracing.remove_span_sink(prev.observe_span)
    telemetry = RuntimeTelemetry()
    tracing.add_span_sink(telemetry.observe_span)
    try:
        with tracing.span("rpc.ScoreBatch") as root:
            with tracing.span("score.dispatch"):
                telemetry.note_dispatch()  # the fused step
                telemetry.note_dispatch()  # a split sketch kernel
            telemetry.note_dispatch()      # a between-steps scatter
        assert root.attributes.get("dispatches") == 3
        assert telemetry.dispatches_total == 3
        # Spans alone no longer count as dispatches.
        with tracing.span("rpc.ScoreBatch") as root2:
            with tracing.span("score.dispatch"):
                pass
        assert root2.attributes.get("dispatches") is None
        assert telemetry.dispatches_total == 3
    finally:
        tracing.remove_span_sink(telemetry.observe_span)
        if prev is not None:
            tracing.add_span_sink(prev.observe_span)
