"""Request-lifecycle tracing: parent/child span linkage, W3C traceparent
propagation (client -> front -> follower), the flight recorder ring, and
the per-stage breakdown aggregation the bench arms publish."""

import socket
import threading
import uuid

import grpc
import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig
from igaming_platform_tpu.obs import flight, tracing
from igaming_platform_tpu.obs.flight import FlightRecorder, stage_breakdown
from igaming_platform_tpu.obs.tracing import (
    DEFAULT_COLLECTOR,
    SpanCollector,
    current_traceparent,
    format_traceparent,
    parse_traceparent,
    span,
)
from igaming_platform_tpu.serve import multihost as mh
from igaming_platform_tpu.serve.grpc_server import (
    RiskGrpcService,
    make_risk_stub,
    serve_risk,
)
from igaming_platform_tpu.serve.scorer import TPUScoringEngine

from risk.v1 import risk_pb2


# -- W3C trace context -------------------------------------------------------


def test_traceparent_roundtrip():
    trace_id, span_id = uuid.uuid4().hex, uuid.uuid4().hex[:16]
    header = format_traceparent(trace_id, span_id)
    assert parse_traceparent(header) == (trace_id, span_id)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-span-01",
    "00-" + "g" * 32 + "-" + "1" * 16 + "-01",      # non-hex trace id
    "ff-" + "a" * 32 + "-" + "1" * 16 + "-01",      # forbidden version
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",      # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",      # all-zero span id
])
def test_traceparent_rejects_malformed(bad):
    assert parse_traceparent(bad) is None


# -- span nesting / linkage --------------------------------------------------


def test_nested_spans_link_parent_and_accumulate_stages():
    col = SpanCollector()
    with span("rpc.Test", col) as root:
        with span("score.gather", col) as a:
            pass
        with span("score.dispatch", col) as b:
            with span("score.inner", col) as c:
                pass
    assert a.trace_id == root.trace_id and a.parent_id == root.span_id
    assert b.trace_id == root.trace_id
    # Grandchild links its direct parent but accumulates on the ROOT.
    assert c.parent_id == b.span_id and c.trace_id == root.trace_id
    assert root.parent_id == ""
    assert set(root.stage_totals) == {"score.gather", "score.dispatch", "score.inner"}
    assert all(v >= 0 for v in root.stage_totals.values())


def test_root_adopts_remote_traceparent():
    trace_id, parent = uuid.uuid4().hex, uuid.uuid4().hex[:16]
    with span("rpc.Remote", SpanCollector(),
              traceparent=format_traceparent(trace_id, parent)) as s:
        assert s.trace_id == trace_id and s.parent_id == parent
        # The outbound hop (work channel / downstream RPC) continues the
        # SAME trace with this span as parent.
        tp = current_traceparent()
        assert parse_traceparent(tp) == (trace_id, s.span_id)
    assert current_traceparent() is None


def test_local_parent_wins_over_remote_header():
    col = SpanCollector()
    with span("rpc.Outer", col) as outer:
        with span("score.stage", col,
                  traceparent=format_traceparent("ab" * 16, "cd" * 8)) as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id


# -- collector drop accounting ----------------------------------------------


def test_span_collector_counts_drops_and_fires_hook():
    col = SpanCollector(capacity=3)
    dropped = []
    col.on_drop = dropped.append
    for i in range(5):
        with span(f"s{i}", col):
            pass
    assert col.dropped_total == 2
    assert sum(dropped) == 2
    assert len(col.drain()) == 3  # newest kept, oldest evicted


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_keeps_rpc_roots_only_and_is_bounded():
    rec = FlightRecorder(capacity=4)
    old_sink = tracing._ROOT_SINK
    tracing.set_root_sink(rec.record_root_span)
    try:
        col = SpanCollector()
        for i in range(6):
            with span("rpc.Score", col):
                with span("score.gather", col):
                    pass
        with span("score.gather", col):  # batch-level root: NOT a request
            pass
        entries = rec.snapshot()
        assert len(entries) == 4  # ring bound
        assert all(e["method"] == "Score" for e in entries)
        assert all("score.gather" in e["stages_ms"] for e in entries)
    finally:
        tracing.set_root_sink(old_sink)


def test_stage_breakdown_aggregation():
    entries = [
        {"method": "ScoreBatch", "trace_id": f"t{i}", "duration_ms": 10.0 + i,
         "stages_ms": {"score.decode": 2.0, "score.readback": 7.0 + i}}
        for i in range(10)
    ] + [{"method": "Other", "trace_id": "x", "duration_ms": 500.0,
          "stages_ms": {}}]
    bd = stage_breakdown(entries, method="ScoreBatch")
    assert bd["requests"] == 10
    assert bd["stages"]["score.decode"]["p50_ms"] == 2.0
    assert 10.0 <= bd["rpc_p50_ms"] <= 19.0  # timing-ok: seeded entries, no clock
    # Per-entry coverage is (9+i)/(10+i): 0.9 .. 0.947; the median sits
    # strictly inside that band.
    assert 0.9 <= bd["stage_coverage_p50"] <= 0.947
    assert bd["sample_trace_id"] == "t9"
    assert stage_breakdown([], method="ScoreBatch") == {"requests": 0, "stages": {}}


# -- client -> front over real gRPC ------------------------------------------


@pytest.fixture(scope="module")
def traced_risk_server():
    engine = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1))
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    channel = grpc.insecure_channel(f"localhost:{port}")
    yield service, make_risk_stub(channel)
    channel.close()
    server.stop(0)
    engine.close()


def test_rpc_span_adopts_client_traceparent_and_lands_in_flightz(traced_risk_server):
    service, stub = traced_risk_server
    DEFAULT_COLLECTOR.drain()
    flight.DEFAULT_RECORDER.clear()
    trace_id = uuid.uuid4().hex
    client_span = uuid.uuid4().hex[:16]
    md = (("traceparent", format_traceparent(trace_id, client_span)),)
    txs = [risk_pb2.ScoreTransactionRequest(
        account_id=f"tp-{i}", amount=1000, transaction_type="bet")
        for i in range(8)]
    resp = stub.ScoreBatch(risk_pb2.ScoreBatchRequest(transactions=txs), metadata=md)
    assert len(resp.results) == 8

    spans = DEFAULT_COLLECTOR.drain()
    rpc = next(s for s in spans if s.name == "rpc.ScoreBatch")
    assert rpc.trace_id == trace_id          # client and server share a trace
    assert rpc.parent_id == client_span      # server span is the client's child
    stage_spans = [s for s in spans if s.trace_id == trace_id and s is not rpc]
    assert stage_spans, "stage spans must join the client's trace"
    assert all(s.parent_id for s in stage_spans)

    entries = [e for e in flight.DEFAULT_RECORDER.snapshot()
               if e["method"] == "ScoreBatch"]
    assert entries and entries[-1]["trace_id"] == trace_id
    assert entries[-1]["stages_ms"], "flight entry must be stage-decomposed"
    assert entries[-1]["rows"] == 8


def test_stage_histogram_and_queue_metrics_populated(traced_risk_server):
    service, stub = traced_risk_server
    stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
        account_id="q-1", amount=500, transaction_type="deposit"))
    text = service.metrics.registry.render_text()
    assert "risk_stage_latency_ms_bucket" in text
    assert "risk_batcher_time_in_queue_ms_count" in text
    assert "risk_batcher_queue_depth" in text


# -- front -> follower over the work-channel protocol ------------------------


def test_workchannel_ships_traceparent_to_follower():
    """The front injects its active span's traceparent as the work
    frame's 4th array; a follower speaking the existing protocol reads it
    and parents its device-step span on the SAME trace — one trace id
    from client to follower. 3-array frames (warmup) stay valid."""
    listener = socket.socket()
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    port = listener.getsockname()[1]
    got: dict = {}
    ready = threading.Event()

    def follower():
        conn, _ = listener.accept()
        reader = mh._Reader(conn)
        frames = []
        for _ in range(2):
            magic, arrays = mh._recv_frame(reader)
            frames.append((magic, arrays))
            conn.sendall(mh.ACK_BYTE)
        got["frames"] = frames
        # The follower's span adopts the shipped header (its own thread,
        # no local parent — exactly the follower process's situation).
        tp = bytes(np.asarray(frames[1][1][3], np.uint8)).decode("ascii")
        col = SpanCollector()
        with span("follower.device_step", col, traceparent=tp) as fs:
            pass
        got["follower_span"] = fs
        conn.close()
        ready.set()

    t = threading.Thread(target=follower, daemon=True)
    t.start()
    chan = mh.WorkChannel([port], io_timeout_s=5.0)
    try:
        xp = np.zeros((8, 30), np.float32)
        blp = np.zeros((8,), bool)
        thr = np.array([80, 50], np.int32)
        chan.broadcast(xp, blp, thr)  # warmup shape: no trace
        with span("rpc.ScoreBatch", SpanCollector()) as root:
            tp = current_traceparent()
            trace = np.frombuffer(tp.encode("ascii"), dtype=np.uint8)
            chan.broadcast(xp, blp, thr, trace=trace)
        assert ready.wait(10.0)
    finally:
        chan.close()
        listener.close()

    (m0, a0), (m1, a1) = got["frames"]
    assert m0 == mh.MAGIC_WORK and len(a0) == 3
    assert m1 == mh.MAGIC_WORK and len(a1) == 4
    fs = got["follower_span"]
    assert fs.trace_id == root.trace_id      # one trace across processes
    assert fs.parent_id == root.span_id      # front span is the parent
