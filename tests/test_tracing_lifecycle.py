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


# -- exclusive time, thread CPU, ids, the ring, the sinks (PR 38) ------------


def _burn(seconds: float) -> None:
    """Hold this thread on a CPU (a loop, not a sleep)."""
    import time
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.fixture
def every_trace_reads_cpu(monkeypatch):
    """Thread CPU is read on one trace in ``CPU_SAMPLE_EVERY``; a test
    that looks at one trace's CPU has it read on every trace."""
    monkeypatch.setattr(tracing, "CPU_SAMPLE_EVERY", 1)


def test_exclusive_time_of_nested_spans_on_one_thread(every_trace_reads_cpu):
    """A parent's exclusive wall is its duration minus its same-thread
    children; a grandchild is subtracted once (from its own parent, whose
    whole duration the grandparent then takes out)."""
    col = SpanCollector()
    with span("rpc.T", col) as root:
        with span("score.dispatch", col) as parent:
            with span("score.session", col) as child:
                with span("score.inner", col) as grand:
                    _burn(0.002)
                _burn(0.001)
            with span("score.pad", col) as child2:
                pass
            _burn(0.001)

    def dur(s):
        return s.mono_end - s.mono_start

    assert parent.children == 2 and child.children == 1
    assert grand.children == 0 and root.children == 1
    assert parent.child_s == pytest.approx(dur(child) + dur(child2))
    assert child.child_s == pytest.approx(dur(grand))
    assert parent.self_s == pytest.approx(dur(parent) - dur(child) - dur(child2))
    # the grandchild sits inside `child`, so it left `parent` with it
    assert parent.self_s + child.self_s + grand.self_s + child2.self_s == (
        pytest.approx(dur(parent)))
    assert root.self_s == pytest.approx(dur(root) - dur(parent))
    # thread CPU nests the same way
    assert parent.child_cpu_s == pytest.approx(child.cpu_s + child2.cpu_s)
    assert grand.self_cpu_s == pytest.approx(grand.cpu_s)
    assert 0.0 <= parent.self_cpu_s <= parent.cpu_s


def test_child_attached_from_another_thread_is_not_subtracted():
    """A ``parent=`` child on a pipeline worker runs BESIDE its parent:
    it joins the trace and the root's stage totals, and leaves the
    parent's exclusive time alone."""
    col = SpanCollector()
    done = threading.Event()
    with span("rpc.T", col) as root:
        with span("score.dispatch", col) as parent:
            def worker():
                with span("score.readback", col, parent=parent) as s:
                    _burn(0.001)
                worker.span = s
                done.set()

            t = threading.Thread(target=worker)
            t.start()
            assert done.wait(10.0)
            t.join()
    beside = worker.span
    assert beside.trace_id == root.trace_id
    assert beside.parent_id == parent.span_id
    assert "score.readback" in root.stage_totals
    assert parent.children == 0 and parent.child_s == 0.0
    assert parent.self_s == pytest.approx(parent.mono_end - parent.mono_start)


def test_thread_cpu_is_read_on_one_trace_in_seven_and_its_spans_follow():
    """The root decides, deterministically by its number, and every span
    of its trace follows it (a worker's ``parent=`` child too); a trace
    that is not sampled makes no ``thread_time`` call and reads 0."""
    assert tracing.CPU_SAMPLE_EVERY == 7
    col = SpanCollector()
    sampled = []
    for _ in range(70):
        with span("rpc.T", col) as root:
            with span("score.a", col) as a:
                with span("score.b", col) as b:
                    _burn(0.0002)
            got = {}

            def worker(root=root, got=got):
                with span("score.c", col, parent=root) as c:
                    pass
                got["c"] = c

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        flags = {root.cpu_sampled, a.cpu_sampled, b.cpu_sampled,
                 got["c"].cpu_sampled}
        assert len(flags) == 1
        sampled.append(root.cpu_sampled)
        if root.cpu_sampled:
            assert b.cpu_s >= 0.0002 and a.child_cpu_s == b.cpu_s
        else:
            assert root.cpu_s == a.cpu_s == b.cpu_s == a.child_cpu_s == 0.0
    assert sum(sampled) == 10
    first = sampled.index(True)
    assert all(sampled[i] == ((i - first) % 7 == 0) for i in range(70))


def test_thread_cpu_is_under_wall_and_a_sleeping_span_reads_none(
        every_trace_reads_cpu):
    import time
    col = SpanCollector()
    with span("score.busy", col) as busy:
        _burn(0.005)
    with span("score.asleep", col) as asleep:
        time.sleep(0.05)
    for s in (busy, asleep):
        wall = s.mono_end - s.mono_start
        assert 0.0 <= s.cpu_s <= wall * 1.05 + 1e-4  # two clocks, read apart
    assert busy.cpu_s >= 0.005
    # asleep, the thread was on no CPU: a relation between the span's two
    # clocks, not this machine's speed
    assert asleep.cpu_s < 0.2 * (asleep.mono_end - asleep.mono_start)


def test_ring_is_a_bounded_deque_and_counts_every_drop():
    """O(1) by construction: a deque with a maxlen evicts on append and
    nothing re-slices. At capacity every add drops exactly one."""
    from collections import deque

    col = SpanCollector(capacity=8)
    assert isinstance(col._spans, deque) and col._spans.maxlen == 8
    told = []
    col.on_drop = told.append
    ring = col._spans
    for i in range(8 * 10):
        with span(f"s{i}", col):
            pass
    assert col._spans is ring, "the ring is never rebuilt on add"
    assert len(ring) == 8
    assert col.dropped_total == 72 and sum(told) == 72
    assert [s.name for s in col.drain()] == [f"s{i}" for i in range(72, 80)]
    assert len(ring) == 0 and col.dropped_total == 72
    # a child's drop is reported with its root, not lost
    small = SpanCollector(capacity=1)
    seen = []
    small.on_drop = seen.append
    with span("rpc.R", small):
        with span("score.a", small):
            pass
        with span("score.b", small):
            pass
    assert small.dropped_total == 2 and sum(seen) == 2


def test_ids_are_w3c_shaped_and_unique_over_100k_spans():
    import re
    col = SpanCollector(capacity=4)
    hex32, hex16 = re.compile(r"^[0-9a-f]{32}$"), re.compile(r"^[0-9a-f]{16}$")
    span_ids, trace_ids = set(), set()
    for _ in range(10_000):
        with span("rpc.T", col) as root:
            for _ in range(9):
                with span("score.x", col) as s:
                    pass
                span_ids.add(s.span_id)
                assert s.trace_id is root.trace_id
        span_ids.add(root.span_id)
        trace_ids.add(root.trace_id)
    assert len(span_ids) == 100_000 and len(trace_ids) == 10_000
    assert all(hex16.match(i) for i in span_ids)
    assert all(hex32.match(t) for t in trace_ids)
    assert "0" * 16 not in span_ids and "0" * 32 not in trace_ids
    # what leaves the process is still a W3C header that parses back
    with span("rpc.T", col) as root:
        with span("score.x", col) as s:
            tp = current_traceparent()
    assert parse_traceparent(tp) == (root.trace_id, s.span_id)
    assert s.parent_id == root.span_id and s.span_id == s.span_id


def test_no_urandom_inside_a_child_span(monkeypatch):
    """A child span draws nothing from the OS: its trace id is its
    root's, its span id a counter. Counted by patching, not timed."""
    import os
    import uuid

    calls = []
    real_urandom, real_uuid4 = os.urandom, uuid.uuid4

    def counting_urandom(n):
        calls.append(("urandom", n))
        return real_urandom(n)

    def counting_uuid4():
        calls.append(("uuid4",))
        return real_uuid4()

    col = SpanCollector()
    with span("rpc.T", col):
        monkeypatch.setattr(os, "urandom", counting_urandom)
        monkeypatch.setattr(uuid, "uuid4", counting_uuid4)
        for _ in range(100):
            with span("score.child", col) as s:
                with span("score.grandchild", col):
                    pass
            assert s.span_id and s.trace_id and current_traceparent() is not None
        monkeypatch.undo()
    assert calls == []


def test_every_sink_sees_every_span_once_and_a_raising_sink_fails_nothing():
    seen_primary, seen_extra, seen_root = [], [], []

    def boom(_span):
        raise RuntimeError("a sink's own fault")

    old_span_sink, old_root_sink = tracing._SPAN_SINK, tracing._ROOT_SINK
    tracing.set_span_sink(seen_primary.append)
    tracing.add_span_sink(boom)
    tracing.add_span_sink(seen_extra.append)
    tracing.add_span_sink(seen_extra.append)  # idempotent
    tracing.set_root_sink(boom)
    tracing.add_root_sink(seen_root.append)
    try:
        col = SpanCollector()
        with span("rpc.T", col) as root:
            with span("score.a", col) as a:
                with span("score.b", col) as b:
                    pass
        with pytest.raises(ValueError):
            with span("rpc.Fails", col) as failed:
                raise ValueError("the request's own fault passes through")
    finally:
        tracing.set_span_sink(old_span_sink)
        tracing.set_root_sink(old_root_sink)
        tracing.remove_span_sink(boom)
        tracing.remove_span_sink(seen_extra.append)
        tracing.remove_root_sink(seen_root.append)
    want = [b, a, root, failed]  # completion order
    mine = lambda spans: [s for s in spans if s in want]  # noqa: E731
    assert mine(seen_primary) == want and mine(seen_extra) == want
    assert mine(seen_root) == [root, failed]
    assert failed.mono_end > 0 and current_traceparent() is None
    assert seen_extra.append not in tracing._EXTRA_SPAN_SINKS


def test_span_budget_is_structural():
    """What keeps a span cheap, held as structure and counts (the
    microseconds are in PERF.md, from tools/span_cost.py): ``span`` is a
    plain function returning an object that is its own context manager
    (no generator frame), a span holds its ids as numbers until asked,
    and one child span with the server's sinks wired makes a bounded
    number of calls."""
    import inspect
    import sys

    from tools import span_cost

    assert not inspect.isgeneratorfunction(span)
    assert hasattr(tracing.Span, "__slots__") and hasattr(tracing.Span, "__exit__")
    col = SpanCollector()
    with span("rpc.T", col):
        with span("score.x", col) as s:
            pass
    assert s._span_id == "" and s._sid, "formatted only when read"
    assert len(s.span_id) == 16 and s._span_id == s.span_id

    old_span_sink, old_root_sink = tracing._SPAN_SINK, tracing._ROOT_SINK
    old_extra = (tracing._EXTRA_SPAN_SINKS, tracing._EXTRA_ROOT_SINKS)
    from igaming_platform_tpu.obs import hostprof, runtime_telemetry, slo
    slo_before = slo.get_default()
    try:
        span_cost.wire()
        for _ in range(3):
            span_cost.one_rpc()  # every stage's accumulators exist
        calls = [0]

        def count(frame, event, arg):
            if event in ("call", "c_call"):
                calls[0] += 1

        def one_root(children: int) -> int:
            calls[0] = 0
            sys.setprofile(count)
            try:
                with span("rpc.ScoreBatch"):
                    for _ in range(children):
                        with span("score.pad", batch=256):
                            pass
            finally:
                sys.setprofile(None)
            return calls[0]

        per_child = (one_root(10) - one_root(0)) / 10
    finally:
        runtime_telemetry.uninstall()
        hostprof._reset_default_for_tests()
        slo.uninstall()
        if slo_before is not None:
            slo.install(slo_before)
        tracing.set_span_sink(old_span_sink)
        tracing.set_root_sink(old_root_sink)
        tracing._EXTRA_SPAN_SINKS, tracing._EXTRA_ROOT_SINKS = old_extra
        tracing.DEFAULT_COLLECTOR.on_drop = None
    # 47 on the tree of PR 38, 85 on its parent: Python and C calls of
    # one child span through the ring, the root's accounting and all three
    # sinks.
    assert per_child <= 60, per_child


def test_a_finished_stage_shows_while_its_request_is_still_open():
    """Each span is completed when it ends: ring, root stage accounting
    and sinks see a finished stage of a request that is still in flight
    (a request hung in a later stage shows where it has been)."""
    seen = []
    tracing.add_span_sink(seen.append)
    try:
        col = SpanCollector()
        with span("rpc.T", col) as root:
            with span("score.a", col) as a:
                with span("score.b", col) as b:
                    pass
            assert [s for s in seen if s in (a, b)] == [b, a]
            assert set(root.stage_totals) == {"score.a", "score.b"}
            assert col.drain() == [b, a]
        assert seen[-1] is root and col.drain() == [root]
        with span("score.late", col, parent=root) as late:  # its root has ended
            pass
        assert seen[-1] is late and "score.late" in root.stage_totals
    finally:
        tracing.remove_span_sink(seen.append)


def test_every_span_is_completed_once_when_workers_end_around_the_root():
    """Workers attach stages with ``parent=`` and end them while the root
    itself ends: each span reaches the sinks exactly once."""
    seen = []
    lock = threading.Lock()

    def sink(s):
        with lock:
            seen.append(s)

    tracing.add_span_sink(sink)
    try:
        col = SpanCollector(capacity=100_000)
        made = []
        for _ in range(50):
            go = threading.Event()
            mine = []

            def worker(root_box=mine):
                go.wait(5.0)
                for _ in range(20):
                    with span("score.w", col, parent=root_box[0]) as s:
                        pass
                    with lock:
                        made.append(s)

            threads = [threading.Thread(target=worker) for _ in range(4)]
            with span("rpc.T", col) as root:
                mine.append(root)
                for t in threads:
                    t.start()
                go.set()
            with lock:
                made.append(root)
            for t in threads:
                t.join()
    finally:
        tracing.remove_span_sink(sink)
    ours = [s for s in seen if s.name in ("score.w", "rpc.T")]
    assert len(made) == 50 * 81
    assert len(ours) == len(made) and set(map(id, ours)) == set(map(id, made))
