"""/metrics exposition-format validity under concurrent scoring load:
parseable sample lines, unique # TYPE/# HELP per family, monotone
histogram buckets, and well-formed trace-id exemplars."""

import re
import threading

from igaming_platform_tpu.core.config import BatcherConfig
from igaming_platform_tpu.obs.metrics import Histogram, ServiceMetrics
from igaming_platform_tpu.serve.grpc_server import RiskGrpcService, _rpc
from igaming_platform_tpu.serve.scorer import TPUScoringEngine

from risk.v1 import risk_pb2

# name{labels} value [# {trace_id="..."} value ts]  — the classic text
# format plus the OpenMetrics exemplar clause our histograms render.
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' -?[0-9eE+.infa-]+'  # a small float prints as 2.7e-05
    r'( # \{trace_id="[0-9a-f]+"\} -?[0-9eE+.-]+ [0-9.]+)?$')
_COMMENT_RE = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+$")


def _validate_exposition(text: str) -> None:
    types_seen: set[str] = set()
    helps_seen: set[str] = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert _COMMENT_RE.match(line), f"bad comment line: {line!r}"
            kind, name = line.split(" ")[1], line.split(" ")[2]
            if kind == "TYPE":
                assert name not in types_seen, f"duplicate # TYPE {name}"
                types_seen.add(name)
            else:
                assert name not in helps_seen, f"duplicate # HELP {name}"
                helps_seen.add(name)
        else:
            assert _SAMPLE_RE.match(line), f"unparseable sample line: {line!r}"


def _validate_histogram_buckets(text: str, family: str) -> None:
    """Bucket counts must be non-decreasing in le order per label set."""
    series: dict[str, list[tuple[float, float]]] = {}
    for line in text.splitlines():
        if not line.startswith(f"{family}_bucket"):
            continue
        body = line.split(" # ")[0]
        labels, value = body.rsplit(" ", 1)
        le = re.search(r'le="([^"]+)"', labels).group(1)
        rest = re.sub(r'le="[^"]+",?', "", labels)
        bound = float("inf") if le == "+Inf" else float(le)
        series.setdefault(rest, []).append((bound, float(value)))
    assert series, f"no buckets rendered for {family}"
    for key, buckets in series.items():
        buckets.sort()
        counts = [c for _, c in buckets]
        assert counts == sorted(counts), f"{family}{key}: non-monotone {counts}"


def test_exemplar_syntax_on_bucket_lines():
    h = Histogram("x_latency_ms", "latency", buckets=(1, 10, 100))
    h.observe(42.0, exemplar="deadbeefdeadbeef", stage="score.decode")
    h.observe(2000.0, exemplar="cafebabecafebabe", stage="score.decode")
    lines = list(h.render())
    ex = [l for l in lines if "#" in l and "_bucket" in l]
    assert len(ex) == 2
    assert any('le="100"' in l and 'trace_id="deadbeefdeadbeef"' in l and
               " 42.0 " in l for l in ex)
    # Over-the-top value exemplars land on the +Inf bucket.
    assert any('le="+Inf"' in l and 'trace_id="cafebabecafebabe"' in l
               for l in ex)
    for l in lines:
        if not l.startswith("#"):
            assert _SAMPLE_RE.match(l), l


def test_observe_many_attaches_exemplar_to_worst_value():
    h = Histogram("y_ms", "y", buckets=(1, 10, 100))
    h.observe_many([0.5, 3.0, 55.0], exemplar="feedface")
    rendered = "\n".join(h.render())
    m = re.search(r'le="100"[^\n]*trace_id="feedface"\} 55\.0', rendered)
    assert m, rendered


def test_exposition_valid_under_concurrent_scoring_load():
    """Hammer ScoreTransaction through the wrapped RPC handler from
    several threads while repeatedly rendering /metrics text: every
    render must parse (no torn lines, no duplicate TYPE headers, buckets
    monotone) — the scrape a real Prometheus would do mid-load."""
    engine = TPUScoringEngine(
        batcher_config=BatcherConfig(batch_size=16, max_wait_ms=1.0))
    service = RiskGrpcService(engine)
    handler = _rpc(service.metrics, "ScoreTransaction", service.ScoreTransaction)
    stop = threading.Event()
    errors: list[BaseException] = []

    def score_worker(k: int) -> None:
        i = 0
        try:
            while not stop.is_set():
                handler(risk_pb2.ScoreTransactionRequest(
                    account_id=f"exp-{k}-{i % 7}", amount=100 + i,
                    transaction_type="deposit"), None)
                i += 1
        except BaseException as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=score_worker, args=(k,)) for k in range(4)]
    try:
        for t in threads:
            t.start()
        for _ in range(25):
            text = service.metrics.registry.render_text()
            _validate_exposition(text)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        # Load actually flowed, and the new lifecycle series filled in.
        final = service.metrics.registry.render_text()
        _validate_exposition(final)
        assert service.metrics.txns_scored_total.value() > 0
        _validate_histogram_buckets(final, "risk_stage_latency_ms")
        _validate_histogram_buckets(final, "risk_grpc_request_duration_ms")
        assert "risk_batcher_time_in_queue_ms_count" in final
        assert "risk_spans_dropped_total" in final
        assert "risk_otlp_export_failures_total" in final
    finally:
        stop.set()
        engine.close()


def test_observe_stage_span_filters_rpc_roots():
    from igaming_platform_tpu.obs.tracing import Span

    m = ServiceMetrics("risk")
    m.observe_stage_span(Span(name="rpc.ScoreBatch", start=0.0, end=1.0,
                              trace_id="a" * 32))
    assert m.stage_latency_ms.count(stage="rpc.ScoreBatch") == 0
    m.observe_stage_span(Span(name="score.decode", start=0.0, end=0.01,
                              trace_id="b" * 32))
    assert m.stage_latency_ms.count(stage="score.decode") == 1
