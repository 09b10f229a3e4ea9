"""Native wire-codec parity: the C++ batch encoder must be byte-equal to
the Python protobuf serializer, and the gRPC ScoreBatch fast path must
return the same message the per-row path would."""

import time

import numpy as np
import pytest

from igaming_platform_tpu.core.enums import REASON_BIT_ORDER, decode_reason_mask
from igaming_platform_tpu.core.features import NUM_FEATURES, FeatureVector
from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
from igaming_platform_tpu.serve import wire

pytestmark = pytest.mark.skipif(
    not wire.native_wire_available(), reason="native toolchain unavailable"
)


def _py_reference(score, action, mask, rule, ml, rtms, feats):
    out = risk_pb2.ScoreBatchResponse()
    for i in range(len(score)):
        f = FeatureVector.from_array(feats[i]) if feats is not None else None
        msg = out.results.add(
            score=int(score[i]), action=int(action[i]),
            reason_codes=[c.value for c in decode_reason_mask(int(mask[i]))],
            rule_score=int(rule[i]), ml_score=float(ml[i]),
            response_time_ms=int(rtms[i]),
        )
        if f is not None:
            msg.features.CopyFrom(risk_pb2.FeatureVector(
                tx_count_1m=int(f.tx_count_1m), tx_count_5m=int(f.tx_count_5m),
                tx_count_1h=int(f.tx_count_1h), tx_sum_1h=int(f.tx_sum_1h),
                tx_avg_1h=f.tx_avg_1h, unique_devices_24h=int(f.unique_devices_24h),
                unique_ips_24h=int(f.unique_ips_24h),
                ip_country_changes_7d=int(f.ip_country_changes),
                device_age_days=int(f.device_age_days),
                account_age_days=int(f.account_age_days),
                total_deposits=int(f.total_deposits),
                total_withdrawals=int(f.total_withdrawals),
                net_deposit=int(f.net_deposit), deposit_count=int(f.deposit_count),
                withdraw_count=int(f.withdraw_count),
                time_since_last_tx_sec=int(f.time_since_last_tx),
                session_duration_sec=int(f.session_duration),
                avg_bet_size=f.avg_bet_size, win_rate=f.win_rate,
                is_vpn=f.is_vpn > 0, is_proxy=f.is_proxy > 0, is_tor=f.is_tor > 0,
                disposable_email=f.disposable_email > 0,
                bonus_claim_count=int(f.bonus_claim_count),
                bonus_wager_completion_rate=f.bonus_wager_rate,
                bonus_only_player=f.bonus_only_player > 0,
            ))
    return out.SerializeToString()


def _random_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    score = rng.integers(0, 101, n).astype(np.int32)
    action = rng.integers(1, 4, n).astype(np.int32)
    mask = rng.integers(0, 1 << len(REASON_BIT_ORDER), n).astype(np.int32)
    rule = rng.integers(0, 101, n).astype(np.int32)
    ml = rng.random(n).astype(np.float32)
    rtms = rng.integers(0, 5000, n).astype(np.int64)
    feats = (rng.random((n, NUM_FEATURES)) * 1000).astype(np.float32)
    return score, action, mask, rule, ml, rtms, feats


def test_byte_parity_random():
    score, action, mask, rule, ml, rtms, feats = _random_batch(512)
    # Exercise the edge cases the varint/default-skipping logic must get
    # right: all-zero rows, negatives, large magnitudes, zero ml_score.
    feats[0] = 0.0
    feats[:, 12] -= 500.0           # negative net_deposit -> 10-byte varint
    feats[3, 15] = 3.2e7            # large time_since_last_tx
    ml[1] = 0.0
    mask[2] = 0
    native = wire.encode_score_batch(score, action, mask, rule, ml, rtms, feats)
    assert native == _py_reference(score, action, mask, rule, ml, rtms, feats)


def test_byte_parity_no_features():
    score, action, mask, rule, ml, rtms, _ = _random_batch(64, seed=7)
    native = wire.encode_score_batch(score, action, mask, rule, ml, rtms, None)
    ref = _py_reference(score, action, mask, rule, ml, rtms, None)
    # Per-row paths always set the features submessage; the no-echo variant
    # omits field 7 entirely — compare semantically after decode.
    a = risk_pb2.ScoreBatchResponse.FromString(native)
    b = risk_pb2.ScoreBatchResponse.FromString(ref)
    assert len(a.results) == len(b.results)
    for ra, rb in zip(a.results, b.results):
        assert (ra.score, ra.action, list(ra.reason_codes), ra.rule_score,
                ra.response_time_ms) == (
            rb.score, rb.action, list(rb.reason_codes), rb.rule_score,
            rb.response_time_ms)
        assert ra.ml_score == pytest.approx(rb.ml_score)


def test_empty_batch():
    z = np.zeros((0,), np.int32)
    native = wire.encode_score_batch(
        z, z, z, z, np.zeros((0,), np.float32), np.zeros((0,), np.int64),
        np.zeros((0, NUM_FEATURES), np.float32),
    )
    assert native == b""
    assert len(risk_pb2.ScoreBatchResponse.FromString(native).results) == 0


def test_grpc_scorebatch_fast_path_matches_per_row_path():
    """ScoreBatch through the native encoder == the per-row proto path,
    field for field, over a live gRPC socket."""
    import grpc

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import grpc_server as gs
    from igaming_platform_tpu.serve.grpc_server import RiskGrpcService, serve_risk
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    engine = TPUScoringEngine(
        ScoringConfig(), ml_backend="mock",
        batcher_config=BatcherConfig(batch_size=64, max_wait_ms=1.0),
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
        txs = [
            risk_pb2.ScoreTransactionRequest(
                account_id=f"wp-{i % 17}", amount=1000 + 997 * i,
                transaction_type=("deposit", "bet", "withdraw")[i % 3],
                ip_address=f"10.0.0.{i % 251}", device_id=f"dev-{i % 5}",
            )
            for i in range(150)  # > batch_size: exercises chunking
        ]
        req = risk_pb2.ScoreBatchRequest(transactions=txs)

        assert gs._use_wire_fast_path(), "native codec should be active in tests"
        fast = call(req, timeout=30)

        gs._WIRE_FAST_PATH = False
        try:
            slow = call(req, timeout=30)
        finally:
            gs._WIRE_FAST_PATH = True

        assert len(fast.results) == len(slow.results) == 150
        for rf, rs in zip(fast.results, slow.results):
            assert rf.score == rs.score
            assert rf.action == rs.action
            assert list(rf.reason_codes) == list(rs.reason_codes)
            assert rf.rule_score == rs.rule_score
            assert rf.ml_score == pytest.approx(rs.ml_score, abs=1e-6)
            assert rf.features == rs.features

        # Fingerprint blacklist must hit through the fast path exactly like
        # the per-row path (KNOWN_FRAUDSTER rule weight + reason code,
        # redis_store.go:267-293) — the columnar gather must not drop the
        # fingerprint column.
        engine.features.add_to_blacklist("fingerprint", "fp-evil")
        bad = risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(
                account_id="wp-bad", amount=100, transaction_type="deposit",
                fingerprint="fp-evil"),
            risk_pb2.ScoreTransactionRequest(
                account_id="wp-ok", amount=100, transaction_type="deposit"),
        ])
        fast_bl = call(bad, timeout=30)
        gs._WIRE_FAST_PATH = False
        try:
            slow_bl = call(bad, timeout=30)
        finally:
            gs._WIRE_FAST_PATH = True
        assert "KNOWN_FRAUDSTER" in list(fast_bl.results[0].reason_codes)
        assert "KNOWN_FRAUDSTER" not in list(fast_bl.results[1].reason_codes)
        for rf, rs in zip(fast_bl.results, slow_bl.results):
            assert rf.score == rs.score
            assert rf.action == rs.action
            assert list(rf.reason_codes) == list(rs.reason_codes)
    finally:
        server.stop(0)
        engine.close()


def _native_store_or_skip():
    from igaming_platform_tpu.serve import native_store

    if not native_store.native_available():
        pytest.skip("native feature store unavailable")
    return native_store.NativeFeatureStore(max_accounts=4096)


def test_decode_gather_matches_python_parse_path():
    """Native request decode+gather == Python protobuf parse + columnar
    gather, element for element (VERDICT r03 item 2 parity pin)."""
    import time

    from igaming_platform_tpu.serve.feature_store import TransactionEvent

    store = _native_store_or_skip()
    now = time.time()
    for a in range(20):
        for e in range(4):
            store.update(TransactionEvent(
                account_id=f"dg-{a}", amount=100 * a + e,
                tx_type=("deposit", "bet", "win")[e % 3],
                ip=f"10.0.0.{a}", device_id=f"d-{a % 5}",
                timestamp=now - 60 * e,
            ))
    store.add_to_blacklist("ip", "10.9.9.9")
    store.add_to_blacklist("device", "bad-dev")
    store.add_to_blacklist("fingerprint", "fp-bad")

    txs = [
        risk_pb2.ScoreTransactionRequest(
            account_id=f"dg-{(i * 7) % 25}",  # some ids unknown to the store
            amount=1 + 977 * i,
            transaction_type=["deposit", "bet", "withdraw", "win", "bonus", ""][i % 6],
            ip_address="10.9.9.9" if i % 7 == 0 else f"10.0.0.{i}",
            device_id="bad-dev" if i % 11 == 0 else f"d-{i % 5}",
            fingerprint="fp-bad" if i % 13 == 0 else f"fp-{i}",
            player_id=f"p-{i}", currency="USD", game_id="g",
            user_agent="ua", session_id="s",
        )
        for i in range(80)
    ]
    payload = risk_pb2.ScoreBatchRequest(transactions=txs).SerializeToString()

    x_native, bl_native = store.decode_gather(payload, now=now)

    req = risk_pb2.ScoreBatchRequest.FromString(payload)
    x_py, bl_py = store.gather_columns(
        [t.account_id for t in req.transactions],
        [t.amount for t in req.transactions],
        [t.transaction_type or "deposit" for t in req.transactions],
        ips=[t.ip_address for t in req.transactions],
        devices=[t.device_id for t in req.transactions],
        fingerprints=[t.fingerprint for t in req.transactions],
        now=now,
    )
    np.testing.assert_array_equal(x_native, x_py)
    np.testing.assert_array_equal(bl_native, bl_py)
    assert bl_native.sum() > 0  # blacklist actually exercised


def test_decode_gather_malformed_and_empty():
    store = _native_store_or_skip()
    with pytest.raises(ValueError):
        store.decode_gather(b"\x0a\xff\xff\xff\xff\xff")  # bad length
    x, bl = store.decode_gather(b"")
    assert x.shape == (0, 30) and bl.shape == (0,)


def test_grpc_scorebatch_raw_native_path():
    """The raw-bytes ScoreBatch route (native decode + native encode, no
    Python protobuf anywhere) returns the same fields as the per-row
    path, and rejects malformed requests with INVALID_ARGUMENT."""
    import grpc

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import native_store
    from igaming_platform_tpu.serve.grpc_server import RiskGrpcService, serve_risk
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    if not native_store.native_available():
        pytest.skip("native feature store unavailable")

    engine = TPUScoringEngine(
        ScoringConfig(), ml_backend="mock",
        batcher_config=BatcherConfig(batch_size=64, max_wait_ms=1.0),
        feature_store=native_store.NativeFeatureStore(max_accounts=4096),
    )
    service = RiskGrpcService(engine)
    assert service.raw_request_methods == ("ScoreBatch",)
    server, health, port = serve_risk(service, 0)
    try:
        ch = grpc.insecure_channel(f"localhost:{port}")
        call = ch.unary_unary(
            "/risk.v1.RiskService/ScoreBatch",
            request_serializer=risk_pb2.ScoreBatchRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreBatchResponse.FromString,
        )
        txs = [
            risk_pb2.ScoreTransactionRequest(
                account_id=f"raw-{i % 9}", amount=500 + 31 * i,
                transaction_type=("deposit", "bet", "withdraw")[i % 3],
                ip_address=f"10.1.0.{i % 251}", device_id=f"dev-{i % 4}",
            )
            for i in range(150)  # > batch_size: exercises pipelined chunking
        ]
        t0 = time.monotonic()
        resp = call(risk_pb2.ScoreBatchRequest(transactions=txs), timeout=120)
        rpc_wall_ms = (time.monotonic() - t0) * 1000.0
        assert len(resp.results) == 150

        # Same rows through the engine's object path for comparison.
        from igaming_platform_tpu.serve.scorer import ScoreRequest

        direct = engine.score_batch([
            ScoreRequest(account_id=t.account_id, amount=t.amount,
                         tx_type=t.transaction_type, ip=t.ip_address,
                         device_id=t.device_id)
            for t in txs
        ])
        for rf, rd in zip(resp.results, direct):
            assert rf.score == rd.score
            assert rf.rule_score == rd.rule_score
            assert rf.ml_score == pytest.approx(rd.ml_score, abs=1e-6)
            assert list(rf.reason_codes) == [c.value for c in rd.reason_codes]

        # Per-chunk response_time_ms: each row carries the time ITS chunk
        # (batch_size=64) was read back, within the RPC's own wall time.
        # Nothing orders the chunks: two stage workers dispatch them, so
        # under load the last chunk can land before the first.
        rtms = [r.response_time_ms for r in resp.results]
        for lo in (0, 64, 128):
            assert len(set(rtms[lo:lo + 64])) == 1, (lo, rtms)
        assert 0 <= min(rtms) and max(rtms) <= rpc_wall_ms, (rtms, rpc_wall_ms)

        raw_call = ch.unary_unary(
            "/risk.v1.RiskService/ScoreBatch",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        with pytest.raises(grpc.RpcError) as exc_info:
            raw_call(b"\x0a\xff\xff\xff\xff\xff", timeout=30)
        assert exc_info.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        ch.close()
    finally:
        server.stop(0)
        engine.close()


def test_decode_gather_adversarial_bytes_never_crash():
    """Deterministic fuzz of the native C++ decoder: every truncation of a
    valid payload, seeded random byte flips, and pure garbage. Untrusted
    wire bytes reach fs_decode_gather directly from the raw ScoreBatch
    route, so the decoder must either raise ValueError or return a
    well-shaped result — a bounds bug here would segfault the server
    process, not just one request."""
    store = _native_store_or_skip()
    txs = [
        risk_pb2.ScoreTransactionRequest(
            account_id=f"fz-{i}", amount=31 * i, transaction_type="bet",
            ip_address=f"10.1.0.{i}", device_id=f"d{i}", fingerprint=f"f{i}",
            player_id=f"p{i}", currency="USD", game_id="g", session_id="s",
        )
        for i in range(8)
    ]
    valid = risk_pb2.ScoreBatchRequest(transactions=txs).SerializeToString()

    def probe(buf: bytes) -> None:
        try:
            x, bl = store.decode_gather(buf)
        except ValueError:
            return  # rejected cleanly
        assert x.ndim == 2 and x.shape[1] == 30
        assert bl.shape == (x.shape[0],)
        assert np.isfinite(x).all()

    for k in range(len(valid)):  # every truncation point
        probe(valid[:k])

    rng = np.random.default_rng(0xC0DEC)
    for _ in range(2000):  # seeded random byte flips over the valid payload
        buf = bytearray(valid)
        for _ in range(int(rng.integers(1, 9))):
            buf[int(rng.integers(0, len(buf)))] = int(rng.integers(0, 256))
        probe(bytes(buf))

    for _ in range(500):  # unstructured garbage
        n = int(rng.integers(0, 64))
        probe(rng.integers(0, 256, size=n, dtype=np.uint8).tobytes())
