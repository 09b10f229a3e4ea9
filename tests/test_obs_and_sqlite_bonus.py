"""Metrics/tracing + durable bonus repository tests."""

import time

from igaming_platform_tpu.core.enums import BonusStatus
from igaming_platform_tpu.obs.metrics import Registry, ServiceMetrics
from igaming_platform_tpu.obs.tracing import SpanCollector, span
from igaming_platform_tpu.platform.bonus import (
    BonusEngine,
    BonusRule,
    SQLiteBonusRepository,
)
from igaming_platform_tpu.platform.repository import SQLiteStore


def test_counter_gauge_histogram_render():
    reg = Registry()
    c = reg.counter("requests_total", "reqs")
    g = reg.gauge("queue_depth", "depth")
    h = reg.histogram("latency_ms", "lat", buckets=(1, 10, 100))

    c.inc(method="Score")
    c.inc(2, method="Score")
    g.set(7)
    h.observe(5.0)
    h.observe(50.0)

    text = reg.render_text()
    assert 'requests_total{method="Score"} 3.0' in text
    assert "queue_depth 7" in text
    assert 'latency_ms_bucket{le="10"} 1' in text
    assert 'latency_ms_bucket{le="100"} 2' in text
    assert "latency_ms_count 2" in text
    assert h.percentile(0.5) == 10
    assert h.percentile(0.99) == 100


def test_service_metrics_observe_rpc():
    m = ServiceMetrics("test")
    start = time.monotonic()
    m.observe_rpc("Score", start)
    m.observe_rpc("Score", start, code="INTERNAL")
    assert m.requests_total.value(method="Score", code="OK") == 1
    assert m.errors_total.value(method="Score") == 1
    assert m.request_duration_ms.count(method="Score") == 2


def test_span_collector():
    col = SpanCollector()
    with span("gather", col, batch=32):
        time.sleep(0.01)
    spans = col.drain()
    assert len(spans) == 1
    assert spans[0].name == "gather"
    assert spans[0].duration_ms >= 10
    assert spans[0].attributes["batch"] == 32


def test_sqlite_bonus_repo_full_lifecycle():
    store = SQLiteStore()
    repo = SQLiteBonusRepository(store)
    rule = BonusRule(id="r1", match_percent=100, max_bonus=10_000,
                     wagering_multiplier=2, expiry_days=1)
    t = [1000.0]
    eng = BonusEngine([rule], repo=repo, now_fn=lambda: t[0])

    bonus = eng.award_bonus("sq-acct", "r1", deposit_amount=5_000)
    assert repo.get_by_id(bonus.id).bonus_amount == 5_000
    assert repo.count_by_rule_and_account("r1", "sq-acct") == 1

    eng.process_wager("sq-acct", 10_000, "slots")
    got = repo.get_by_id(bonus.id)
    assert got.status == BonusStatus.COMPLETED
    assert got.wagering_progress == 10_000

    # New bonus expires via the sweep.
    b2 = eng.award_bonus("sq-acct", "r1", deposit_amount=1_000)
    t[0] += 2 * 86400
    assert eng.expire_old_bonuses() == 1
    assert repo.get_by_id(b2.id).status == BonusStatus.EXPIRED
    store.close()


def test_scorer_emits_stage_spans():
    """The serving hot path emits gather/dispatch/readback spans per batch
    (the OTel wiring the reference deploys Jaeger for but never emits)."""
    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.obs.tracing import DEFAULT_COLLECTOR
    from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

    DEFAULT_COLLECTOR.drain()
    engine = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=8, max_wait_ms=1.0))
    try:
        engine.score(ScoreRequest("span-acct", amount=1000, tx_type="deposit"))
        names = {s.name for s in DEFAULT_COLLECTOR.drain()}
        assert {"score.gather", "score.dispatch", "score.readback"} <= names
    finally:
        engine.close()


def test_rpc_handler_emits_span_with_status_code():
    import grpc

    from igaming_platform_tpu.obs.tracing import DEFAULT_COLLECTOR
    from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2
    from igaming_platform_tpu.platform.repository import (
        InMemoryAccountRepository,
        InMemoryLedgerRepository,
        InMemoryTransactionRepository,
    )
    from igaming_platform_tpu.platform.wallet import WalletService
    from igaming_platform_tpu.serve.grpc_server import (
        WalletGrpcService,
        make_wallet_stub,
        serve_wallet,
    )

    wallet = WalletService(
        InMemoryAccountRepository(), InMemoryTransactionRepository(),
        InMemoryLedgerRepository(),
    )
    server, _, port = serve_wallet(WalletGrpcService(wallet), 0)
    channel = grpc.insecure_channel(f"localhost:{port}")
    stub = make_wallet_stub(channel)
    try:
        DEFAULT_COLLECTOR.drain()
        stub.CreateAccount(wallet_pb2.CreateAccountRequest(player_id="span-p"))
        try:
            stub.GetAccount(wallet_pb2.GetAccountRequest(account_id="missing"))
        except grpc.RpcError:
            pass
        spans = {s.name: s for s in DEFAULT_COLLECTOR.drain()}
        assert spans["rpc.CreateAccount"].attributes["code"] == "OK"
        assert spans["rpc.GetAccount"].attributes["code"] == "NOT_FOUND"
        assert spans["rpc.CreateAccount"].duration_ms >= 0.0
    finally:
        channel.close()
        server.stop(0)


def test_transaction_type_counters_recorded_over_grpc():
    """The wallet gRPC layer feeds the per-type flow counters the bonus
    dashboard charts (wallet_transactions_total / _amount_cents_total)."""
    import grpc

    from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2
    from igaming_platform_tpu.platform.repository import (
        InMemoryAccountRepository,
        InMemoryLedgerRepository,
        InMemoryTransactionRepository,
    )
    from igaming_platform_tpu.platform.wallet import WalletService
    from igaming_platform_tpu.serve.grpc_server import (
        WalletGrpcService,
        make_wallet_stub,
        serve_wallet,
    )

    wallet = WalletService(
        InMemoryAccountRepository(), InMemoryTransactionRepository(),
        InMemoryLedgerRepository(),
    )
    svc = WalletGrpcService(wallet)
    server, _, port = serve_wallet(svc, 0)
    channel = grpc.insecure_channel(f"localhost:{port}")
    stub = make_wallet_stub(channel)
    try:
        acct = stub.CreateAccount(wallet_pb2.CreateAccountRequest(player_id="m-p")).account
        stub.Deposit(wallet_pb2.DepositRequest(account_id=acct.id, amount=10_000, idempotency_key="m-d"))
        stub.Bet(wallet_pb2.BetRequest(account_id=acct.id, amount=2_500, idempotency_key="m-b"))
        assert svc.metrics.transactions_total.value(type="deposit") == 1
        assert svc.metrics.transactions_total.value(type="bet") == 1
        assert svc.metrics.transaction_amount_cents.value(type="deposit") == 10_000
        assert svc.metrics.transaction_amount_cents.value(type="bet") == 2_500
        rendered = svc.metrics.registry.render_text()
        assert 'wallet_transactions_total{type="deposit"} 1' in rendered
    finally:
        channel.close()
        server.stop(0)


def test_grafana_dashboards_are_valid_and_reference_real_series():
    """Every provisioned dashboard parses and only charts metric families
    the services actually export."""
    import json
    import re
    from pathlib import Path

    families = {
        "grpc_requests_total", "grpc_request_duration_ms", "grpc_errors_total",
        "risk_score", "txns_scored_total", "batch_occupancy",
        "transactions_total", "transaction_amount_cents_total", "ltv_segment_total",
    }
    suffixes = ("", "_bucket", "_sum", "_count")
    valid = {f"{svc}_{fam}{sfx}" for svc in ("risk", "wallet")
             for fam in families for sfx in suffixes}

    dashboards = sorted(Path("deploy/grafana/dashboards").glob("*.json"))
    assert len(dashboards) == 5
    for path in dashboards:
        doc = json.loads(path.read_text())
        assert doc["uid"] and doc["panels"], path.name
        for p in doc["panels"]:
            for t in p["targets"]:
                for name in re.findall(r"[a-z][a-z0-9_]{4,}", t["expr"]):
                    if name in ("histogram_quantile", "rate", "sum", "by", "le",
                                "method", "code", "type", "segment", "job"):
                        continue
                    if re.fullmatch(r"(risk|wallet)_[a-z0-9_]+", name):
                        assert name in valid, f"{path.name}: unknown series {name}"


def test_histogram_observe_many_matches_scalar_observe():
    import numpy as np

    from igaming_platform_tpu.obs.metrics import Histogram

    buckets = (10, 25, 50, 75, 90, 100)
    h1 = Histogram("a", buckets=buckets)
    h2 = Histogram("b", buckets=buckets)
    vals = np.random.default_rng(0).integers(0, 101, 500)
    for v in vals:
        h1.observe(float(v))
    h2.observe_many(vals)
    assert h1._counts[()] == h2._counts[()]
    assert h1._totals[()] == h2._totals[()]
    assert abs(h1._sums[()] - h2._sums[()]) < 1e-6
    h2.observe_many([])  # no-op


def test_wire_batch_feeds_score_distribution():
    """The raw ScoreBatch path records the score histogram (the per-row
    proto path's metric parity)."""
    import grpc
    import pytest as _pytest

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from igaming_platform_tpu.serve import native_store
    from igaming_platform_tpu.serve.grpc_server import RiskGrpcService, serve_risk
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    if not native_store.native_available():
        _pytest.skip("native feature store unavailable")
    engine = TPUScoringEngine(
        ScoringConfig(), batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1.0),
        feature_store=native_store.NativeFeatureStore(max_accounts=4096),
    )
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    try:
        ch = grpc.insecure_channel(f"localhost:{port}")
        call = ch.unary_unary(
            "/risk.v1.RiskService/ScoreBatch",
            request_serializer=risk_pb2.ScoreBatchRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreBatchResponse.FromString,
        )
        txs = [risk_pb2.ScoreTransactionRequest(account_id=f"h-{i}", amount=100 + i)
               for i in range(20)]
        call(risk_pb2.ScoreBatchRequest(transactions=txs), timeout=30)
        # Both routes must feed the histogram — raw native path (when the
        # codec built) and the per-row fallback alike.
        assert service.metrics.score_distribution.count() == 20
        ch.close()
    finally:
        server.stop(0)
        engine.close()
