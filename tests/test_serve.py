"""Serving layer tests: feature store, HLL, batcher, TPU scoring engine."""

import time

import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
from igaming_platform_tpu.core.enums import ReasonCode
from igaming_platform_tpu.core.features import F
from igaming_platform_tpu.serve.batcher import ContinuousBatcher, pad_batch
from igaming_platform_tpu.serve.feature_store import InMemoryFeatureStore, TransactionEvent
from igaming_platform_tpu.serve.hll import HyperLogLog
from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

T0 = 1_700_000_000.0


def test_hll_accuracy():
    hll = HyperLogLog(12)
    for i in range(10_000):
        hll.add(f"item-{i}")
    est = hll.count()
    assert abs(est - 10_000) / 10_000 < 0.05


def test_hll_small_counts_exactish():
    hll = HyperLogLog(12)
    for i in range(5):
        hll.add(f"device-{i}")
        hll.add(f"device-{i}")  # duplicates don't count
    assert hll.count() == 5


def test_feature_store_velocity_windows():
    fs = InMemoryFeatureStore()
    acct = "a1"
    # 3 txns in the last minute, 2 more within 5 min, 1 more within the hour
    for dt in (3500, 200, 150, 30, 20, 10):
        fs.update(TransactionEvent(acct, 1000, "deposit", timestamp=T0 - dt))
    c1, c5, ch = fs.velocity(acct, now=T0)
    assert (c1, c5, ch) == (3, 5, 6)


def test_feature_store_row_fill():
    fs = InMemoryFeatureStore()
    acct = "a2"
    fs.update(TransactionEvent(acct, 5000, "deposit", ip="1.1.1.1", device_id="d1", timestamp=T0 - 100))
    fs.update(TransactionEvent(acct, 2000, "bet", ip="1.1.1.1", device_id="d2", timestamp=T0 - 50))
    fs.update(TransactionEvent(acct, 1000, "win", ip="2.2.2.2", device_id="d2", timestamp=T0 - 40))

    row = np.zeros(30, dtype=np.float32)
    fs.fill_row(row, acct, 700, "withdraw", now=T0)
    assert row[F.TX_COUNT_1M] == 2
    assert row[F.TX_COUNT_1H] == 3
    assert row[F.TX_SUM_1H] == 8000
    assert row[F.UNIQUE_DEVICES_24H] == 2
    assert row[F.UNIQUE_IPS_24H] == 2
    assert row[F.TOTAL_DEPOSITS] == 5000
    assert row[F.DEPOSIT_COUNT] == 1
    assert row[F.WIN_RATE] == 1.0  # 1 win / 1 bet
    assert row[F.TIME_SINCE_LAST_TX] == 40
    # Session began at the first event (T0-100) and slid forward since.
    assert row[F.SESSION_DURATION] == 100
    assert row[F.TX_AMOUNT] == 700
    assert row[F.TX_TYPE_WITHDRAW] == 1


def test_feature_store_ttl_expiry():
    fs = InMemoryFeatureStore()
    acct = "a3"
    fs.update(TransactionEvent(acct, 1000, "deposit", timestamp=T0 - 7200))
    row = np.zeros(30, dtype=np.float32)
    fs.fill_row(row, acct, 100, "bet", now=T0)
    # 1h window and TTLs expired
    assert row[F.TX_COUNT_1H] == 0
    assert row[F.TX_SUM_1H] == 0
    # Session expired -> no duration
    assert row[F.SESSION_DURATION] == 0
    # Batch aggregates persist (ClickHouse analog)
    assert row[F.TOTAL_DEPOSITS] == 1000


def test_bonus_only_player_detection():
    fs = InMemoryFeatureStore()
    acct = "a4"
    fs.update(TransactionEvent(acct, 1000, "deposit", timestamp=T0))
    for _ in range(4):
        fs.record_bonus_claim(acct, 0.1)
    row = np.zeros(30, dtype=np.float32)
    fs.fill_row(row, acct, 100, "bet", now=T0 + 1)
    assert row[F.BONUS_ONLY_PLAYER] == 1  # >3 claims, <$50 deposited


def test_blacklist():
    fs = InMemoryFeatureStore()
    fs.add_to_blacklist("device", "bad-device")
    fs.add_to_blacklist("ip", "6.6.6.6")
    assert fs.check_blacklist(device_id="bad-device")
    assert fs.check_blacklist(ip="6.6.6.6")
    assert not fs.check_blacklist(device_id="good", ip="1.2.3.4")
    assert not fs.check_blacklist()


def test_rate_limit():
    fs = InMemoryFeatureStore()
    now = T0
    for i in range(12):
        fs.update(TransactionEvent("rl", 100, "bet", timestamp=now - 30 + i))
    # velocity uses wall-clock now; use the direct API with explicit now
    c1, _, _ = fs.velocity("rl", now=now)
    assert c1 == 12


def test_pad_batch():
    x = np.ones((3, 30), dtype=np.float32)
    padded, n = pad_batch(x, 8)
    assert padded.shape == (8, 30) and n == 3
    assert padded[3:].sum() == 0


def test_continuous_batcher_coalesces():
    calls = []

    def runner(payloads):
        calls.append(len(payloads))
        return [p * 2 for p in payloads]

    b = ContinuousBatcher(runner, BatcherConfig(batch_size=16, max_wait_ms=20)).start()
    futures = [b.submit(i) for i in range(10)]
    results = [f.result(timeout=60) for f in futures]
    assert results == [i * 2 for i in range(10)]
    b.stop()
    assert sum(calls) == 10
    assert len(calls) <= 3  # coalesced, not one call per item


def test_engine_end_to_end_clean():
    eng = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1))
    try:
        # build up some history
        eng.update_features(TransactionEvent("acct", 5000, "deposit", device_id="d1", ip="1.1.1.1"))
        t0 = time.monotonic()
        resp = eng.score(ScoreRequest("acct", amount=2000, tx_type="deposit", device_id="d1", ip="1.1.1.1"))
        wall_ms = (time.monotonic() - t0) * 1000.0
        assert resp.action in ("approve", "review", "block")
        assert 0 <= resp.score <= 100
        # In milliseconds, and inside the caller's own wall time.
        assert 0 < resp.response_time_ms <= wall_ms
        assert resp.features.total_deposits == 5000
    finally:
        eng.close()


def test_engine_blacklisted_scores_higher():
    eng = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1))
    try:
        eng.features.add_to_blacklist("device", "evil")
        clean = eng.score(ScoreRequest("u1", amount=2000, tx_type="deposit", device_id="ok"))
        dirty = eng.score(ScoreRequest("u2", amount=2000, tx_type="deposit", device_id="evil"))
        assert dirty.score >= clean.score + 20
        assert ReasonCode.KNOWN_FRAUDSTER in dirty.reason_codes
        assert dirty.rule_score >= 50
    finally:
        eng.close()


def test_engine_threshold_update_no_recompile():
    eng = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1))
    try:
        eng.features.add_to_blacklist("device", "evil")
        r1 = eng.score(ScoreRequest("u3", amount=2000, tx_type="deposit", device_id="evil"))
        assert r1.action == "approve"  # 0.4*50 = 20 < 50
        eng.set_thresholds(15, 10)
        r2 = eng.score(ScoreRequest("u3", amount=2000, tx_type="deposit", device_id="evil"))
        assert r2.action == "block"
        assert eng.get_thresholds() == (15, 10)
    finally:
        eng.close()


def test_engine_score_batch():
    eng = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=64, max_wait_ms=1))
    try:
        reqs = [ScoreRequest(f"b{i}", amount=1000 + i, tx_type="bet") for i in range(50)]
        responses = eng.score_batch(reqs)
        assert len(responses) == 50
        assert all(r.action == "approve" for r in responses)
    finally:
        eng.close()


def test_engine_ip_flags_raise_score():
    eng = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1))
    try:
        resp = eng.score(ScoreRequest("tor-user", amount=2000, tx_type="deposit", ip_flags=(0, 0, 1)))
        assert ReasonCode.VPN_DETECTED in resp.reason_codes
        assert resp.rule_score >= 15
    finally:
        eng.close()


def test_batcher_replays_batch_on_transient_device_failure():
    """A collect failure (device preempted mid-step) replays the in-flight
    batch instead of failing its requests (SURVEY.md §5 requeue)."""
    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.serve.batcher import ContinuousBatcher

    state = {"collects": 0}

    def dispatch(payloads):
        return list(payloads)

    def collect(handle):
        state["collects"] += 1
        if state["collects"] == 1:
            raise RuntimeError("device preempted")
        return [p * 10 for p in handle]

    b = ContinuousBatcher(
        cfg=BatcherConfig(batch_size=4, max_wait_ms=5.0, device_retries=1),
        dispatch=dispatch, collect=collect,
    ).start()
    try:
        assert b.score_sync(7, timeout=10.0) == 70   # succeeded via replay
        assert b.batches_replayed == 1
    finally:
        b.stop()


def test_batcher_fails_requests_after_retries_exhausted():
    import pytest
    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.serve.batcher import ContinuousBatcher

    def dispatch(payloads):
        return payloads

    def collect(handle):
        raise RuntimeError("device gone")

    b = ContinuousBatcher(
        cfg=BatcherConfig(batch_size=4, max_wait_ms=5.0, device_retries=2),
        dispatch=dispatch, collect=collect,
    ).start()
    try:
        with pytest.raises(RuntimeError, match="device gone"):
            b.score_sync(1, timeout=10.0)
    finally:
        b.stop()


def test_one_phase_runner_also_retries():
    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.serve.batcher import ContinuousBatcher

    calls = {"n": 0}

    def runner(payloads):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return [p + 1 for p in payloads]

    b = ContinuousBatcher(
        runner, BatcherConfig(batch_size=4, max_wait_ms=5.0, device_retries=1)
    ).start()
    try:
        assert b.score_sync(5, timeout=10.0) == 6
        assert b.batches_replayed == 1
    finally:
        b.stop()


def test_latency_tier_shape_selection():
    """Single-txn traffic pads to the smallest compiled tier, not the
    throughput shape (VERDICT r02 item 1)."""
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

    engine = TPUScoringEngine(
        ScoringConfig(),
        batcher_config=BatcherConfig(batch_size=1024, latency_tiers=(64, 256), max_wait_ms=1.0),
        warmup=False,
    )
    try:
        assert engine._shapes == [64, 256, 1024]
        assert engine._pick_shape(1) == 64
        assert engine._pick_shape(64) == 64
        assert engine._pick_shape(65) == 256
        assert engine._pick_shape(1000) == 1024
        assert engine._pick_shape(1024) == 1024
        # A real single score rides the smallest tier end to end.
        out, n = engine._launch_device(
            *engine.features.gather_batch([ScoreRequest(account_id="t-1", amount=500)])
        )
        assert n == 1
        assert out.shape == (5, 64)  # packed [5, B] at the smallest tier
        resp = engine.score(ScoreRequest(account_id="t-1", amount=500))
        assert 0 <= resp.score <= 100
    finally:
        engine.close()


def test_latency_tiers_disabled_and_oversize():
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    engine = TPUScoringEngine(
        ScoringConfig(),
        batcher_config=BatcherConfig(batch_size=128, latency_tiers=(), max_wait_ms=1.0),
        warmup=False,
    )
    try:
        assert engine._shapes == [128]
        assert engine._pick_shape(1) == 128
    finally:
        engine.close()


@pytest.mark.parametrize("batch_size,ladder", [
    (256, [64, 256]), (8192, [64, 256, 2048, 8192]), (1024, [64, 256, 1024]),
    (64, [64])])
def test_default_ladder_has_a_64_row_rung_under_256(batch_size, ladder):
    """The default tiers are 64 / 256 / 2048, a factor of four apart: a
    64-row frame no longer pads to 256 rows, whatever ``BATCH_SIZE``."""
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    assert BatcherConfig().latency_tiers == (64, 256, 2048)
    engine = TPUScoringEngine(
        ScoringConfig(),
        batcher_config=BatcherConfig(batch_size=batch_size, max_wait_ms=1.0),
        warmup=False,
    )
    try:
        assert engine._shapes == ladder
        assert engine._batcher._shapes == tuple(ladder)
        assert engine._pick_shape(1) == engine._pick_shape(64) == 64
        assert engine._pick_shape(65) == ladder[min(1, len(ladder) - 1)]
        assert engine._pick_shape(batch_size) == batch_size
    finally:
        engine.close()


def test_host_latency_tier_executes_and_matches(monkeypatch):
    """The host-CPU latency tier (a TPU-host-only path by default) is
    forced on and exercised: near-empty flushes ride the host executable,
    full batches ride the device fn, and both agree on actions/scores."""
    import numpy as np

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

    monkeypatch.setenv("HOST_TIER_FORCE", "1")
    engine = TPUScoringEngine(
        ScoringConfig(),
        batcher_config=BatcherConfig(batch_size=64, latency_tiers=(8,),
                                     host_tier_rows=8, max_wait_ms=1.0),
    )
    try:
        assert engine._fn_host is not None
        calls = {"host": 0}
        real_host_fn = engine._fn_host

        def counting_host_fn(*a, **k):
            calls["host"] += 1
            return real_host_fn(*a, **k)

        engine._fn_host = counting_host_fn

        reqs = [ScoreRequest(account_id=f"ht-{i}", amount=120_000 + i,
                             tx_type="withdraw") for i in range(4)]
        x, bl = engine.features.gather_batch(reqs)
        out_host, n = engine._launch_device(x, bl)          # 4 <= tier: host
        assert calls["host"] == 1 and n == 4

        x64, bl64 = engine.features.gather_batch(
            [ScoreRequest(account_id=f"ht-{i}", amount=120_000 + i,
                          tx_type="withdraw") for i in range(64)])
        out_dev, _ = engine._launch_device(x64, bl64)       # full batch: device
        assert calls["host"] == 1  # unchanged

        host = np.asarray(out_host)
        dev = np.asarray(out_dev)
        # Same rows through both executables: actions and rule scores
        # identical, ml within float32 rounding (score within 1 point).
        np.testing.assert_array_equal(host[1, :4], dev[1, :4])   # action
        np.testing.assert_array_equal(host[3, :4], dev[3, :4])   # rule_score
        assert np.abs(host[0, :4] - dev[0, :4]).max() <= 1       # score
    finally:
        engine.close()


def test_abuse_detector_long_history_ring_matches_dense():
    """The SERVING abuse wrapper at long history (S=1024) with ring
    sequence parallelism == the dense single-device wrapper on identical
    event streams — the long-context path through the production
    ingestion/padding code, not just the bare model."""
    import numpy as np

    from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh
    from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector

    mesh = create_mesh(MeshSpec(data=2, seq=4))
    ring = SequenceAbuseDetector(max_history=1024, mesh=mesh, seq_mode="ring")
    dense = SequenceAbuseDetector(max_history=1024, params=ring.params, cfg=ring.cfg)

    rng = np.random.default_rng(11)
    accounts = [f"lc-{i}" for i in range(3)]
    for det in (ring, dense):
        r = np.random.default_rng(7)  # identical stream into both
        for _ in range(1200):  # > max_history: deque rolls over
            acct = accounts[int(r.integers(0, len(accounts)))]
            det.record_event(acct, int(r.integers(100, 50_000)),
                             ("deposit", "bet", "win")[int(r.integers(0, 3))],
                             timestamp=1_000_000.0 + float(r.random()))
    del rng

    s_ring = ring.check_batch(accounts, seq_len=1024)
    s_dense = dense.check_batch(accounts, seq_len=1024)
    assert s_ring.shape == (3,)
    np.testing.assert_allclose(s_ring, s_dense, rtol=2e-4, atol=2e-5)


# -- CPU-backend abuse policies (engine.go:462-466 floor semantics) ----------


def _planted_abuser(det):
    """bonus_grant -> rapid low-weight wagering -> quick withdraw."""
    t = 1_000_000.0
    det.record_event("abuser", 5_000, "bonus_grant", timestamp=t)
    for i in range(20):
        t += 4.0
        det.record_event("abuser", 400, "bonus_wager", game_weight=0.1,
                         timestamp=t)
    det.record_event("abuser", 9_000, "withdraw", timestamp=t + 5.0)


def _normal_player(det):
    t = 1_000_000.0
    for i in range(12):
        t += 3600.0
        det.record_event("normal", 2_000, ("deposit", "bet", "win")[i % 3],
                         game_weight=1.0, timestamp=t)


def test_abuse_heuristic_policy_separates_abuser_from_normal():
    """ABUSE_CPU_POLICY=heuristic: scalar pattern-matching over the same
    ring buffers keeps the abuse path alive on a CPU boot; responses
    are flagged DEGRADED_CPU_HEURISTIC."""
    from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector

    det = SequenceAbuseDetector(policy="heuristic")
    _planted_abuser(det)
    _normal_player(det)

    score_a, signals_a, _ = det.check("abuser")
    score_n, signals_n, _ = det.check("normal")
    assert score_a >= det.threshold > score_n
    assert "DEGRADED_CPU_HEURISTIC" in signals_a
    assert "DEGRADED_CPU_HEURISTIC" in signals_n
    assert "QUICK_BONUS_CASHOUT" in signals_a
    assert "RAPID_FIRE_WAGERING" in signals_a
    assert det.is_abuser("abuser") and not det.is_abuser("normal")
    # Batch path agrees with the single path.
    batch = det.check_batch(["abuser", "normal", "no-history"])
    assert batch[0] >= det.threshold > batch[1]
    assert batch[2] == 0.0


def test_abuse_heuristic_does_constant_host_work_per_check(monkeypatch):
    """What the old >=10k checks/s floor stood for, as counts: on the
    heuristic policy a batch of N checks is N scalar passes over N
    history stacks, and never the sequence model — no jit call, no
    device dispatch. (A CPU timing is a count or a parity check, never
    a speed: the floor read 3,881 on a starved machine.)"""
    import numpy as _np

    from igaming_platform_tpu.serve import abuse as abuse_mod
    from igaming_platform_tpu.serve import scorer as scorer_mod
    from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector

    det = SequenceAbuseDetector(policy="heuristic")
    _planted_abuser(det)
    _normal_player(det)
    accounts = ["abuser", "normal"] * 50

    calls = {"model": 0, "dispatch": 0, "heuristic": 0, "stack": 0}

    def count(key, fn):
        def wrapped(*a, **k):
            calls[key] += 1
            return fn(*a, **k)
        return wrapped

    monkeypatch.setattr(det, "_fn", count("model", det._fn))
    monkeypatch.setattr(scorer_mod, "_device_dispatch",
                        count("dispatch", scorer_mod._device_dispatch))
    monkeypatch.setattr(det, "_heuristic_one",
                        count("heuristic", det._heuristic_one))
    monkeypatch.setattr(abuse_mod.np, "stack", count("stack", _np.stack))

    scores = det.check_batch(accounts)
    assert scores.shape == (len(accounts),)
    assert calls == {"model": 0, "dispatch": 0,
                     "heuristic": len(accounts), "stack": len(accounts)}


def test_abuse_shed_policy_maps_to_unavailable():
    """ABUSE_CPU_POLICY=shed: CheckBonusAbuse aborts UNAVAILABLE and the
    error is counted — never a silent collapse."""
    import grpc
    import pytest

    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve.abuse import AbuseShed, SequenceAbuseDetector
    from igaming_platform_tpu.serve.grpc_server import RiskGrpcService, RpcAbort
    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2

    det = SequenceAbuseDetector(policy="shed")
    with pytest.raises(AbuseShed):
        det.check("anyone")

    engine = TPUScoringEngine(
        batcher_config=BatcherConfig(batch_size=8, max_wait_ms=1.0))
    try:
        metrics = ServiceMetrics("risk_shed_test")
        svc = RiskGrpcService(
            engine, abuse_detector=lambda a, b: det.check(a, b),
            metrics=metrics)
        with pytest.raises(RpcAbort) as exc_info:
            svc.CheckBonusAbuse(
                risk_pb2.CheckBonusAbuseRequest(account_id="x", bonus_id="b"),
                context=None)
        assert exc_info.value.code == grpc.StatusCode.UNAVAILABLE
        assert metrics.abuse_shed_total.value() == 1.0
    finally:
        engine.close()


def test_abuse_rejects_unknown_policy():
    import pytest

    from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector

    with pytest.raises(ValueError):
        SequenceAbuseDetector(policy="bogus")
