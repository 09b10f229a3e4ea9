"""Durable decision ledger + bit-exact replay (serve/ledger.py, tools/replay.py).

Covers the robustness-PR contract end to end:

- the versioned DecisionRecord wire codec: round-trip, a pinned GOLDEN
  blob (schema drift fails tier-1), and unknown-future-version rejection;
- WAL durability: CRC framing, torn-tail truncation on recovery (the
  SIGKILL-mid-write shape), segment rotation;
- the scoring-path seam: batch / batcher / wire / heuristic decisions
  all land in the ledger with decision ids, and the flight recorder
  entry carries the same id (trace <-> flight <-> ledger join);
- the sink drain: bounded hand-off queue, spill-to-WAL catch-up on
  overflow and outage, ledger breaker feeding, cursor persistence
  (at-least-once, no resend after clean restart), ClickHouse wire shape;
- chaos: `ledger.append` faults must never fail or block scoring;
- `tools/replay.py`: the replay-verify smoke (the `make replay-verify`
  scenario) reproduces every ledgered decision bit-exact, heuristic-tier
  decisions included.
"""

from __future__ import annotations

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
from igaming_platform_tpu.serve import chaos as chaos_mod
from igaming_platform_tpu.serve import ledger as ledger_mod
from igaming_platform_tpu.serve.ledger import (
    DecisionLedger,
    DecisionRecord,
    LedgerSchemaError,
    decode_record,
    encode_record,
    iter_records,
    ledger_segments,
    recover_segment,
)
from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

GOLDEN = Path(__file__).parent / "golden" / "decision_record_v1.bin"


def _record(i: int = 0, features=True, tier="device") -> DecisionRecord:
    feats = (np.arange(30, dtype=np.float32) * 0.25 + i) if features else None
    return DecisionRecord(
        decision_id=f"d-test-{i:07x}.0",
        account_id=f"acct-{i}",
        trace_id="0af7651916cd43dd8448eb211c80319c",
        model_version="mock",
        params_fp="00aa11bb22cc33dd",
        wire_mode="batch",
        serving_state="serving",
        tier=tier,
        score=40 + i, action=1, reason_mask=5, rule_score=40,
        ml_score_bits=int(np.float32(0.25 + i).view(np.uint32)),
        amount=1000 + i, tx_type="deposit",
        block_threshold=80, review_threshold=50,
        ts_unix=1754300000.0 + i, blacklisted=bool(i % 2),
        features=feats,
    )


def _fields(r: DecisionRecord) -> dict:
    return {k: getattr(r, k) for k in (
        "decision_id", "account_id", "trace_id", "model_version",
        "params_fp", "wire_mode", "serving_state", "tier", "score",
        "action", "reason_mask", "rule_score", "ml_score_bits", "amount",
        "tx_type", "block_threshold", "review_threshold", "ts_unix",
        "blacklisted")}


@pytest.fixture(autouse=True)
def _clean_globals():
    yield
    chaos_mod.clear()
    ledger_mod.set_state_provider(None)


# ---------------------------------------------------------------------------
# Wire codec


def test_record_roundtrip_all_fields():
    rec = _record(3)
    back = decode_record(encode_record(rec))
    assert _fields(back) == _fields(rec)
    np.testing.assert_array_equal(back.features, rec.features)
    rec2 = _record(4, features=False, tier="heuristic")
    back2 = decode_record(encode_record(rec2))
    assert back2.features is None and back2.tier == "heuristic"
    assert _fields(back2) == _fields(rec2)


def test_golden_blob_pins_schema():
    """Accidental wire-schema drift must fail loudly: the committed blob
    decodes to the exact pinned record AND re-encodes byte-identical."""
    blob = GOLDEN.read_bytes()
    rec = decode_record(blob)
    assert rec.decision_id == "d-golden0001-0000001.0"
    assert rec.account_id == "acct-golden"
    assert rec.trace_id == "0af7651916cd43dd8448eb211c80319c"
    assert rec.model_version == "multitask"
    assert rec.params_fp == "0123456789abcdef"
    assert (rec.wire_mode, rec.serving_state, rec.tier) == (
        "wire_row", "degraded", "heuristic")
    assert (rec.score, rec.action, rec.reason_mask, rec.rule_score) == (
        87, 3, 0b100101, 80)
    assert rec.ml_score == pytest.approx(0.87)
    assert (rec.amount, rec.tx_type) == (125000, "withdraw")
    assert (rec.block_threshold, rec.review_threshold) == (80, 50)
    assert rec.ts_unix == 1754300000.25 and rec.blacklisted
    np.testing.assert_array_equal(
        rec.features, np.arange(30, dtype=np.float32) * 0.5)
    assert encode_record(rec) == blob, "schema drift: re-encode differs from golden"


def test_future_schema_version_rejected():
    blob = GOLDEN.read_bytes()
    with pytest.raises(LedgerSchemaError, match="unknown DecisionRecord schema"):
        decode_record(bytes([SCHEMA := 9]) + blob[1:])
    with pytest.raises(LedgerSchemaError):
        decode_record(b"")
    # A flipped body byte fails the embedded feature-hash check.
    corrupt = bytearray(blob)
    corrupt[-1] ^= 0xFF
    with pytest.raises(LedgerSchemaError, match="hash mismatch"):
        decode_record(bytes(corrupt))


# ---------------------------------------------------------------------------
# WAL durability


def test_wal_roundtrip_torn_tail_and_recovery(tmp_path):
    d = str(tmp_path / "wal")
    led = DecisionLedger(d)
    for i in range(7):
        assert led.append_record(_record(i))
    assert led.flush(5.0)
    led.close()
    assert [r.decision_id for r in iter_records(d)] == [
        f"d-test-{i:07x}.0" for i in range(7)]

    # SIGKILL-mid-write shape: a torn frame at the tail (header promises
    # more bytes than exist). Readers stop cleanly; recovery truncates.
    seq, path = ledger_segments(d)[-1]
    size_before = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(b"\x40\x00\x00\x00\x99\x99\x99\x99partial")
    assert len(list(iter_records(d))) == 7  # reader tolerates the tail
    valid_end, frames, torn = recover_segment(path)
    assert torn and frames == 7 and valid_end == size_before

    led2 = DecisionLedger(d)  # recovery truncates in place
    assert os.path.getsize(path) == size_before
    assert led2.append_record(_record(7))
    assert led2.flush(5.0)
    led2.close()
    ids = [r.decision_id for r in iter_records(d)]
    assert ids == [f"d-test-{i:07x}.0" for i in range(8)]


def test_segment_rotation_preserves_order(tmp_path):
    d = str(tmp_path / "rot")
    led = DecisionLedger(d, segment_bytes=600)  # a few records per segment
    for i in range(25):
        led.append_record(_record(i))
    assert led.flush(5.0)
    led.close()
    assert len(ledger_segments(d)) > 2
    ids = [r.decision_id for r in iter_records(d)]
    assert ids == [f"d-test-{i:07x}.0" for i in range(25)]
    stats_led = DecisionLedger(d)
    assert stats_led.stats()["durable_records"] == 25
    stats_led.close()


# ---------------------------------------------------------------------------
# Sink drain: bounded queue, spill catch-up, breaker, cursor


class _FakeSink:
    def __init__(self):
        self.batches: list[list[DecisionRecord]] = []
        self.fail = False
        self.sends = 0

    def ids(self) -> list[str]:
        return [r.decision_id for b in self.batches for r in b]

    def send(self, records):
        self.sends += 1
        if self.fail:
            raise RuntimeError("sink down (test)")
        self.batches.append(list(records))


def test_sink_drain_spill_overflow_catches_up_from_wal(tmp_path):
    sink = _FakeSink()
    sink.fail = True  # outage first: the tiny hand-off queue overflows
    led = DecisionLedger(str(tmp_path / "s"), sink=sink, sink_queue_max=4,
                         sink_batch=8)
    for i in range(40):
        led.append_record(_record(i))
    assert led.flush(5.0)
    deadline = time.monotonic() + 60.0
    while sink.sends == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    sink.fail = False  # recovery: the drainer must catch up FROM THE WAL
    assert led.drain_sink(10.0)
    led.close()
    assert sorted(sink.ids()) == sorted(f"d-test-{i:07x}.0" for i in range(40))
    s = led.stats()["sink"]
    assert s["spill_events"] >= 1, "disk catch-up episodes must be counted"
    assert s["queue_high_water"] >= 40  # lag high-water through the outage
    assert s["lag"] == 0


def test_sink_outage_feeds_breaker_then_recovers(tmp_path):
    from igaming_platform_tpu.serve.supervisor import OPEN, CircuitBreaker

    sink = _FakeSink()
    sink.fail = True
    breaker = CircuitBreaker("ledger", failure_threshold=2, open_s=0.1)
    led = DecisionLedger(str(tmp_path / "o"), sink=sink, breaker=breaker,
                         sink_batch=8)
    for i in range(10):
        led.append_record(_record(i))
    assert led.flush(5.0)
    deadline = time.monotonic() + 60.0
    while breaker.state != OPEN and time.monotonic() < deadline:
        time.sleep(0.01)
    assert breaker.state == OPEN, "sink outage must open the ledger breaker"
    assert led.stats()["sink"]["lag"] == 10  # nothing lost, nothing sent

    sink.fail = False  # outage ends; half-open probe must drain the backlog
    assert led.drain_sink(10.0)
    led.close()
    assert sorted(sink.ids()) == sorted(f"d-test-{i:07x}.0" for i in range(10))
    assert led.stats()["sink"]["failures"] >= 2


def test_partial_blob_consumption_then_disk_fallback_skips_nothing(tmp_path):
    """Regression: multi-record write blobs consumed PARTIALLY from the
    memory hand-off (sink_batch < blob frames), with send failures
    forcing the drainer back to the WAL mid-blob. The cursor must land
    on per-frame offsets — a blob-end offset here once skipped the
    blob's unconsumed tail frames on catch-up."""

    class _FlakySink(_FakeSink):
        def send(self, records):
            if self.sends % 3 == 1:
                self.sends += 1
                raise RuntimeError("intermittent sink flap (test)")
            super().send(records)

    sink = _FlakySink()
    led = DecisionLedger(str(tmp_path / "pb"), sink=sink, sink_batch=8)
    for lo in range(0, 100, 20):  # five 20-frame blobs
        led._append_ready([_record(i) for i in range(lo, lo + 20)])
    assert led.flush(5.0)
    assert led.drain_sink(15.0)
    led.close()
    assert sorted(set(sink.ids())) == sorted(
        f"d-test-{i:07x}.0" for i in range(100))


def test_sink_cursor_persists_no_resend_after_restart(tmp_path):
    d = str(tmp_path / "c")
    sink1 = _FakeSink()
    led1 = DecisionLedger(d, sink=sink1)
    for i in range(5):
        led1.append_record(_record(i))
    assert led1.flush(5.0) and led1.drain_sink(5.0)
    led1.close()
    assert len(sink1.ids()) == 5

    sink2 = _FakeSink()
    led2 = DecisionLedger(d, sink=sink2)  # cursor read from sink.cursor
    for i in range(5, 8):
        led2.append_record(_record(i))
    assert led2.flush(5.0) and led2.drain_sink(5.0)
    led2.close()
    assert sorted(sink2.ids()) == sorted(f"d-test-{i:07x}.0" for i in range(5, 8))


def test_clickhouse_sink_wire_shape():
    from igaming_platform_tpu.serve.ledger import ClickHouseDecisionSink

    requests: list[str] = []

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            size = int(self.headers.get("Content-Length", 0))
            requests.append(self.rfile.read(size).decode())
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        sink = ClickHouseDecisionSink(
            f"http://127.0.0.1:{httpd.server_address[1]}")
        sink.send([_record(0), _record(1)])
        assert requests[0].startswith("CREATE TABLE IF NOT EXISTS risk_decisions")
        insert = requests[1]
        head, _, body = insert.partition("\n")
        assert head == "INSERT INTO risk_decisions FORMAT JSONEachRow"
        rows = [json.loads(line) for line in body.splitlines()]
        assert [r["decision_id"] for r in rows] == [
            "d-test-0000000.0", "d-test-0000001.0"]
        assert rows[0]["tier"] == "device" and rows[0]["score"] == 40
        assert rows[0]["feature_hash"] == _record(0).feature_hash
        assert "features" not in rows[0]  # snapshot stays in the WAL
    finally:
        httpd.shutdown()


# ---------------------------------------------------------------------------
# Scoring-path integration


def _mock_engine(batch=32, **kwargs) -> TPUScoringEngine:
    return TPUScoringEngine(
        ScoringConfig(), ml_backend="mock",
        batcher_config=BatcherConfig(batch_size=batch, max_wait_ms=1.0),
        **kwargs)


def _seed(engine, n=24):
    from igaming_platform_tpu.serve.feature_store import TransactionEvent

    for i in range(n):
        engine.update_features(TransactionEvent(
            account_id=f"lg-{i % 12}", amount=700 + 31 * i,
            tx_type=("deposit", "bet", "withdraw")[i % 3],
            ip=f"10.1.{i % 9}.{i % 7}", device_id=f"dev-{i % 5}"))


def test_scoring_paths_record_decisions_with_snapshots(tmp_path):
    engine = _mock_engine()
    led = DecisionLedger(str(tmp_path / "eng"))
    engine.ledger = led
    try:
        _seed(engine)
        reqs = [ScoreRequest(f"lg-{i % 12}", amount=900 + i,
                             tx_type=("deposit", "bet", "withdraw")[i % 3])
                for i in range(40)]
        responses = engine.score_batch(reqs)  # direct batch path (2 chunks)
        single = engine.score(reqs[0])  # batcher path
        assert led.flush(5.0)
        assert all(r.decision_id for r in responses)
        assert single.decision_id
        # Two chunk prefixes + one batcher prefix, all rows distinct.
        recs = list(iter_records(str(tmp_path / "eng")))
        assert len(recs) == 41
        assert len({r.decision_id for r in recs}) == 41
        by_id = {r.decision_id: r for r in recs}
        first = by_id[responses[0].decision_id]
        assert first.account_id == "lg-0"
        assert first.score == responses[0].score
        assert first.features is not None and first.features.shape == (30,)
        assert first.wire_mode == "batch" and first.tier == "device"
        assert by_id[single.decision_id].wire_mode == "single"
        # The recorded snapshot hashes are self-consistent (decode checks
        # them) and params fingerprint matches the engine's.
        assert first.params_fp == engine.params_fingerprint
    finally:
        led.close()
        engine.close()


def test_wire_batch_path_records_and_flight_carries_decision_id(tmp_path):
    """gRPC e2e on the PRODUCTION shape (supervised engine — its watchdog
    pool must carry the RPC span across threads): ScoreTransaction and
    ScoreBatch flight-recorder entries carry the decision id that joins
    them to the ledger records."""
    grpc = pytest.importorskip("grpc")
    from igaming_platform_tpu.obs.flight import DEFAULT_RECORDER
    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from igaming_platform_tpu.serve.grpc_server import (
        RiskGrpcService,
        make_risk_stub,
        serve_risk,
    )
    from igaming_platform_tpu.serve.supervisor import SupervisedScoringEngine

    engine = SupervisedScoringEngine(lambda: _mock_engine(batch=64))
    led = DecisionLedger(str(tmp_path / "wire"))
    engine.inner.ledger = led
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    try:
        _seed(engine)
        DEFAULT_RECORDER.clear()
        ch = grpc.insecure_channel(f"localhost:{port}")
        stub = make_risk_stub(ch)
        stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
            account_id="lg-1", amount=1500, transaction_type="deposit"),
            timeout=30)
        stub.ScoreBatch(risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(
                account_id=f"lg-{i % 12}", amount=1000 + i,
                transaction_type="bet")
            for i in range(17)
        ]), timeout=30)
        ch.close()
        assert led.flush(5.0)
        entries = {e["method"]: e for e in DEFAULT_RECORDER.snapshot()}
        assert "decision_id" in entries["ScoreTransaction"], (
            "flight entry must carry the decision id join key")
        recs = {r.decision_id: r for r in iter_records(str(tmp_path / "wire"))}
        assert entries["ScoreTransaction"]["decision_id"] in recs
        batch_prefix = entries["ScoreBatch"]["decision_id"]
        batch_rows = [r for r in recs.values()
                      if r.decision_id.startswith(batch_prefix + ".")]
        assert len(batch_rows) == 17
        # The wire path keeps account ids (columnar path) on the records.
        assert {r.account_id for r in batch_rows} == {
            f"lg-{i % 12}" for i in range(17)}
        # Same trace id on the flight entry and its ledger records.
        assert batch_rows[0].trace_id == entries["ScoreBatch"]["trace_id"]
    finally:
        from igaming_platform_tpu.serve.grpc_server import graceful_stop

        graceful_stop(server, health, grace=5, engine=engine)
        led.close()


def test_chaos_append_faults_never_fail_scoring(tmp_path):
    from igaming_platform_tpu.serve.supervisor import OPEN, CircuitBreaker

    breaker = CircuitBreaker("ledger", failure_threshold=2, open_s=5.0)
    chaos_mod.install("seed=3;ledger.append=error:p=1.0")
    engine = _mock_engine()
    led = DecisionLedger(str(tmp_path / "chaos"), breaker=breaker)
    engine.ledger = led
    try:
        _seed(engine)
        reqs = [ScoreRequest(f"lg-{i % 12}", amount=800 + i) for i in range(20)]
        for _ in range(3):  # every append batch hits the injected fs fault
            responses = engine.score_batch(reqs)
            assert len(responses) == 20  # scoring is untouched
        led.flush(5.0)
        stats = led.stats()
        assert stats["records_dropped"] >= 20
        assert stats["append_errors"] >= 1
        assert breaker.state == OPEN
        assert stats["records_appended"] == 0
    finally:
        chaos_mod.clear()
        led.close()
        engine.close()


def test_queue_overflow_drops_counted_never_blocks(tmp_path):
    led = DecisionLedger(str(tmp_path / "q"), queue_max_rows=8)
    # Stall the writer behind a chaos delay so the queue genuinely fills.
    chaos_mod.install("seed=9;ledger.append=delay:p=1.0:ms=50")
    try:
        # O(1) appends, no blocking: an append that waited for room
        # would drop nothing.
        for i in range(64):
            led.append_record(_record(i))
        led.flush(30.0)
        stats = led.stats()
        assert stats["records_dropped"] > 0
        assert stats["records_appended"] + stats["records_dropped"] == 64
    finally:
        chaos_mod.clear()
        led.close()


# ---------------------------------------------------------------------------
# Replay (the make replay-verify scenario, in-process)


def test_replay_verify_smoke(tmp_path):
    """Score a seeded batch under CHAOS_PLAN (ledger-append faults), then
    replay the ledger and diff bit-exact — heuristic tier included."""
    from tools.replay import run_verify

    verdict = run_verify(str(tmp_path / "rv"), rows=48, batch=32)
    assert verdict["ok"], verdict
    assert verdict["mismatches"] == 0
    assert verdict["replayed"] == verdict["records_total"] > 0
    assert verdict["degraded_records_replayed"] > 0
    assert verdict["params_fingerprint_mismatch"] == 0
    assert set(verdict["replayed_by_tier"]) >= {"device", "heuristic"}


def test_replay_flags_params_fingerprint_mismatch(tmp_path):
    """A ledger scored under different params must NOT silently replay
    green against the pinned checkpoint."""
    from tools.replay import replay_directory

    d = str(tmp_path / "fp")
    led = DecisionLedger(d)
    rec = _record(0)
    rec.params_fp = "feedfacefeedface"  # not any engine's fingerprint
    led.append_record(rec)
    assert led.flush(5.0)
    led.close()
    verdict = replay_directory(d, batch=32)
    assert verdict["params_fingerprint_mismatch"] == 1
    assert not verdict["ok"]


def test_replay_detects_tampered_score(tmp_path):
    """The whole point: a record whose outputs don't match its snapshot
    fails replay. (Tamper with the score, keep the snapshot.)"""
    from tools.replay import replay_directory

    engine = _mock_engine()
    d = str(tmp_path / "tamper")
    led = DecisionLedger(d)
    engine.ledger = led
    try:
        _seed(engine)
        engine.score_batch([ScoreRequest(f"lg-{i}", amount=1000 + i)
                            for i in range(8)])
        assert led.flush(5.0)
    finally:
        led.close()
        engine.close()
    records = list(iter_records(d))
    records[3].score += 7  # the lie
    d2 = str(tmp_path / "tampered")
    led2 = DecisionLedger(d2)
    for r in records:
        led2.append_record(r)
    assert led2.flush(5.0)
    led2.close()
    verdict = replay_directory(d2, batch=32)
    assert verdict["mismatches"] == 1
    assert not verdict["ok"]
    sample = verdict["mismatch_samples"][0]
    assert sample["recorded"]["score"] == sample["recomputed"]["score"] + 7


@pytest.mark.parametrize("rounds", [3, 2 * 16 + 1, 4 * 16 + 6])
def test_replay_session_hashes_held_across_twin_growth(tmp_path, monkeypatch,
                                                       rounds):
    """Stateful decisions whose accounts' host session buffers grow (3
    rounds: 2 -> 4 -> 8 rows; 33: every step up to the steady size; 70:
    a compaction too) between the score that recorded a window snapshot
    and the writer thread that hashes it: the writer is held until every
    append has happened, then replay rebuilds each window from ledger
    event order and every session_state_hash is bit-exact."""
    from tools.replay import replay_directory

    engine = _mock_engine(batch=16, feature_cache=8, session_state=True)
    d = str(tmp_path / "growth")
    led = DecisionLedger(d)
    engine.ledger = led
    engine.ensure_cache()
    gate = threading.Event()
    write_batches = led._write_batches

    def held_write(batches):
        assert gate.wait(60.0)
        return write_batches(batches)

    monkeypatch.setattr(led, "_write_batches", held_write)
    try:
        for r in range(rounds):
            ids = ["ga", "gb", "ga", "gc"][:3 + r % 2]  # ga twice a chunk
            engine.score_columns_cached(
                ids, [400 + 7 * r + i for i in range(len(ids))],
                [("bet", "deposit", "withdraw")[(r + i) % 3]
                 for i in range(len(ids))],
                now=1_700_000_000.0 + 20.0 * r)
        snap = engine.session.snapshot()
        assert snap["twin_regrows"] >= 3
        gate.set()
        assert led.flush(30.0)
    finally:
        gate.set()
        led.close()
        engine.close()
    v = replay_directory(d, batch=16)
    assert v["session_records"] == 3 * rounds + rounds // 2
    assert v["session_verified"] == v["session_records"]
    assert v["session_hash_mismatch"] == 0
    assert v["session_chain_gaps"] == 0 and v["session_reordered"] == 0
    assert v["session_ok"] and v["ok"]
