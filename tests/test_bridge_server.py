"""Bridge + assembled-server tests: event replay, abuse detector, sidecar."""

import json
import urllib.request

import numpy as np

from igaming_platform_tpu.core.config import BatcherConfig, RiskServiceConfig, ScoringConfig
from igaming_platform_tpu.core.enums import (
    EXCHANGE_WALLET,
    QUEUE_ANALYTICS,
)
from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector
from igaming_platform_tpu.serve.bridge import ScoringBridge
from igaming_platform_tpu.serve.events import Publisher, default_broker, new_transaction_event
from igaming_platform_tpu.serve.scorer import TPUScoringEngine


def make_engine(batch=64):
    return TPUScoringEngine(batcher_config=BatcherConfig(batch_size=batch, max_wait_ms=1))


def tx_event(account, amount, tx_type, device=""):
    e = new_transaction_event("transaction.completed", {
        "id": f"t-{account}-{amount}", "account_id": account, "type": tx_type,
        "amount": amount, "status": "completed",
    })
    if device:
        e.data["device_id"] = device
    return e


def test_bridge_replay_scores_and_updates_features():
    engine = make_engine()
    broker = default_broker()
    bridge = ScoringBridge(engine, broker)
    try:
        events = [tx_event("r1", 1000 + i, "deposit") for i in range(100)]
        stats = bridge.replay(events, batch_size=32)
        assert stats["events_scored"] == 100
        assert stats["txns_per_sec"] > 0
        # features folded in
        import numpy as np

        from igaming_platform_tpu.core.features import F, NUM_FEATURES

        row = np.zeros(NUM_FEATURES, dtype=np.float32)
        engine.features.fill_row(row, "r1", 0, "bet")
        assert row[F.DEPOSIT_COUNT] == 100
    finally:
        engine.close()


def test_bridge_publishes_block_events():
    engine = make_engine()
    broker = default_broker()
    bridge = ScoringBridge(engine, broker)
    try:
        engine.features.add_to_blacklist("device", "evil")
        engine.set_thresholds(20, 10)  # force blocks
        events = [tx_event("bad1", 5000, "deposit", device="evil")]
        stats = bridge.replay(events)
        assert stats["blocked"] == 1
        # risk.blocked + fraud.detected land in analytics via risk exchange
        assert broker.queue_depth(QUEUE_ANALYTICS) >= 2
    finally:
        engine.close()


def test_bridge_consumer_path():
    engine = make_engine()
    broker = default_broker()
    bridge = ScoringBridge(engine, broker)
    try:
        pub = Publisher(broker)
        pub.publish(EXCHANGE_WALLET, tx_event("c1", 2000, "bet"))
        pub.publish(EXCHANGE_WALLET, tx_event("c1", 3000, "deposit"))
        processed = bridge.drain()
        assert processed == 2
        assert bridge.events_processed == 2
    finally:
        engine.close()


def test_bridge_skips_non_money_events():
    engine = make_engine()
    broker = default_broker()
    bridge = ScoringBridge(engine, broker)
    try:
        from igaming_platform_tpu.serve.events import Event

        broker.publish_raw(EXCHANGE_WALLET, "account.created",
                           Event(type="account.created", aggregate_id="x").to_json())
        bridge.drain()
        assert bridge.events_skipped == 1
        assert bridge.events_processed == 0
    finally:
        engine.close()


def test_abuse_detector_history_and_linking():
    det = SequenceAbuseDetector()
    for i in range(20):
        det.record_event("a1", 1000, "bonus_wager", device_id="shared-dev", timestamp=1000.0 + i)
    det.record_event("a2", 500, "bet", device_id="shared-dev", timestamp=2000.0)
    assert det.history_length("a1") == 20
    score, signals, linked = det.check("a1")
    assert 0.0 <= score <= 1.0
    assert linked == ["a2"]
    score2, signals2, linked2 = det.check("a2")
    assert "MULTI_ACCOUNT" in signals2


def test_abuse_detector_batch_scores():
    det = SequenceAbuseDetector()
    det.record_event("b1", 100, "bet")
    scores = det.check_batch(["b1", "b2-empty"])
    assert scores.shape == (2,)


def test_pallas_is_imported_in_the_background_and_only_on_a_tpu_boot(monkeypatch):
    """The thread a TPU boot starts before the native store allocates
    imports the kernels' toolchain and ends; a CPU boot starts none."""
    import sys

    from igaming_platform_tpu.serve import server as server_mod

    thread = server_mod.import_pallas_in_background()
    assert thread.daemon and thread.name == "import-pallas"
    thread.join(timeout=120)
    assert not thread.is_alive()
    assert "jax.experimental.pallas.tpu" in sys.modules

    started = []
    monkeypatch.setattr(server_mod, "import_pallas_in_background",
                        lambda: started.append("started"))
    cfg = RiskServiceConfig(scoring=ScoringConfig(),
                            batcher=BatcherConfig(batch_size=32, max_wait_ms=1))
    server = server_mod.RiskServer(cfg, grpc_port=0, http_port=0,
                                   store_max_accounts=4096)
    server.shutdown(grace=1)
    assert started == []  # JAX_PLATFORMS=cpu: nothing here traces a kernel


def test_risk_server_assembled():
    from igaming_platform_tpu.serve.server import RiskServer

    cfg = RiskServiceConfig(
        scoring=ScoringConfig(),
        batcher=BatcherConfig(batch_size=32, max_wait_ms=1),
    )
    server = RiskServer(cfg, grpc_port=0, http_port=0, store_max_accounts=4096)
    try:
        base = f"http://localhost:{server.http_port}"
        with urllib.request.urlopen(f"{base}/health") as r:
            assert json.load(r)["status"] == "healthy"
        with urllib.request.urlopen(f"{base}/ready") as r:
            assert json.load(r)["ready"] is True
        with urllib.request.urlopen(f"{base}/debug/thresholds") as r:
            assert json.load(r) == {"block": 80, "review": 50}

        req = urllib.request.Request(
            f"{base}/debug/score",
            data=json.dumps({"account_id": "http-acct", "amount": 5000,
                             "transaction_type": "deposit"}).encode(),
            method="POST",
        )
        with urllib.request.urlopen(req) as r:
            body = json.load(r)
        assert body["action"] in ("approve", "review", "block")

        # events flow end-to-end through the live consumer
        pub = Publisher(server.broker)
        pub.publish(EXCHANGE_WALLET, tx_event("srv-acct", 4000, "deposit"))
        import time

        deadline = time.time() + 60
        while time.time() < deadline and server.bridge.events_processed < 1:
            time.sleep(0.05)
        assert server.bridge.events_processed >= 1

        with urllib.request.urlopen(f"{base}/metrics") as r:
            text = r.read().decode()
        assert "risk_grpc_requests_total" in text
    finally:
        server.shutdown(grace=1)


def test_risk_server_with_multi_device_mesh(monkeypatch):
    """MESH_DEVICES=-1 builds a DP serving mesh over all visible devices
    (8 virtual CPU devices in tests) and scoring works over gRPC."""
    import grpc

    from igaming_platform_tpu.core.config import RiskServiceConfig
    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from igaming_platform_tpu.serve.grpc_server import make_risk_stub
    from igaming_platform_tpu.serve.server import RiskServer

    monkeypatch.setenv("MESH_DEVICES", "-1")
    monkeypatch.setenv("BATCH_SIZE", "64")
    monkeypatch.setenv("GRPC_PORT", "0")
    monkeypatch.setenv("HTTP_PORT", "0")
    server = RiskServer(RiskServiceConfig.from_env(), store_max_accounts=4096)
    try:
        import jax
        assert server.engine._mesh is not None
        assert server.engine._mesh.shape["data"] == len(jax.devices())
        channel = grpc.insecure_channel(f"localhost:{server.grpc_port}")
        stub = make_risk_stub(channel)
        r = stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
            account_id="mesh-acct", amount=5_000, transaction_type="deposit"))
        assert 0 <= r.score <= 100
        channel.close()
    finally:
        server.shutdown(grace=1.0)


def test_ready_reflects_device_liveness(monkeypatch):
    import json
    import urllib.request

    from igaming_platform_tpu.core.config import RiskServiceConfig
    from igaming_platform_tpu.serve.server import RiskServer

    monkeypatch.setenv("BATCH_SIZE", "16")
    monkeypatch.setenv("GRPC_PORT", "0")
    monkeypatch.setenv("HTTP_PORT", "0")
    monkeypatch.delenv("MESH_DEVICES", raising=False)
    server = RiskServer(RiskServiceConfig.from_env(), store_max_accounts=4096)
    try:
        body = json.load(urllib.request.urlopen(
            f"http://localhost:{server.http_port}/ready", timeout=30))
        assert body == {"ready": True, "device": True}

        # Device probe failing -> 503, not a hang.
        server.device_alive = lambda timeout_s=2.0: False
        try:
            urllib.request.urlopen(f"http://localhost:{server.http_port}/ready", timeout=30)
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.load(e) == {"ready": False, "device": False}
    finally:
        server.shutdown(grace=1.0)


def test_risk_server_with_sequence_parallel_abuse(monkeypatch):
    """MESH_DEVICES + MESH_SEQ builds a data x seq mesh: scoring shards
    over data, the abuse detector ring-shards histories over seq — both
    served over gRPC from one process."""
    import grpc

    from igaming_platform_tpu.core.config import RiskServiceConfig
    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from igaming_platform_tpu.serve.grpc_server import make_risk_stub
    from igaming_platform_tpu.serve.server import RiskServer

    monkeypatch.setenv("MESH_DEVICES", "-1")
    monkeypatch.setenv("MESH_SEQ", "2")
    monkeypatch.setenv("BATCH_SIZE", "64")
    monkeypatch.setenv("GRPC_PORT", "0")
    monkeypatch.setenv("HTTP_PORT", "0")
    server = RiskServer(RiskServiceConfig.from_env(), store_max_accounts=4096)
    try:
        import jax
        assert server.engine._mesh.shape["seq"] == 2
        assert server.engine._mesh.shape["data"] == len(jax.devices()) // 2
        channel = grpc.insecure_channel(f"localhost:{server.grpc_port}")
        stub = make_risk_stub(channel)
        # Feed a history, then run the sequence detector over the wire.
        for i in range(8):
            server.abuse.record_event("sp-acct", 1_000 + i, "bet", timestamp=float(i))
        r = stub.CheckBonusAbuse(risk_pb2.CheckBonusAbuseRequest(
            account_id="sp-acct", bonus_id="b1"))
        assert 0.0 <= r.abuse_score <= 1.0
        s = stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
            account_id="sp-acct", amount=2_000, transaction_type="deposit"))
        assert 0 <= s.score <= 100
        channel.close()
    finally:
        server.shutdown(grace=1.0)
