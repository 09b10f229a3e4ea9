"""Deadline-aware admission & continuous batching v2 (serve/deadline.py).

Covers the PR 11 tentpole surface:

- ``risk-deadline-ms`` metadata parse (absent / garbage / zero / huge)
  and the metadata > context-deadline > default precedence;
- expired-at-admission shed: DEADLINE_EXCEEDED with the standard
  ``grpc-retry-pushback-ms`` trailing hint, counted as a shed;
- EDF order within a lane, lane priority (interactive > bulk >
  background) under a full queue, and cross-lane aging (no starvation);
- expiry shedding at dispatch assembly (never scored dead);
- dynamic per-tick batch planning against the online step model;
- hedged re-dispatch of a stalled pipeline window;
- deadline decrement across router hops (the outbound
  ``risk-deadline-ms`` is the remaining budget at send);
- the burn→shed closed loop (fast-window SLO alert sheds bulk);
- monotonic clock discipline on the admission→dispatch path;
- scoring parity: lane/deadline reordering is score-inert vs the
  lockstep batch path (bit-exact).
"""

from __future__ import annotations

import threading
import time
from concurrent import futures as _futures

import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig
from igaming_platform_tpu.obs.perfmodel import OnlineStepModel
from igaming_platform_tpu.serve.deadline import (
    DEADLINE_MAX_MS,
    DEADLINE_METADATA_KEY,
    LANE_BACKGROUND,
    LANE_BULK,
    LANE_INTERACTIVE,
    BurnShedGate,
    Deadline,
    DeadlineExpired,
    DeadlineScheduler,
    from_grpc,
    outbound_deadline_ms,
    parse_deadline_ms,
    plan_tick,
)


# ---------------------------------------------------------------------------
# Metadata parse: absent / garbage / zero / huge


class _FakeContext:
    def __init__(self, metadata=(), time_remaining=None):
        self._md = tuple(metadata)
        self._rem = time_remaining

    def invocation_metadata(self):
        return self._md

    def time_remaining(self):
        return self._rem


def test_parse_deadline_ms_garbage_zero_huge():
    assert parse_deadline_ms(None) is None
    assert parse_deadline_ms("abc") is None
    assert parse_deadline_ms("") is None
    assert parse_deadline_ms("nan") is None
    assert parse_deadline_ms("inf") is None
    assert parse_deadline_ms("0") == 0.0
    assert parse_deadline_ms("-17") == 0.0
    assert parse_deadline_ms("250") == 250.0
    assert parse_deadline_ms("1e12") == DEADLINE_MAX_MS
    assert parse_deadline_ms("37.5") == 37.5


def test_from_grpc_precedence_metadata_context_default():
    # Metadata wins over the context deadline.
    ddl = from_grpc(_FakeContext(
        metadata=((DEADLINE_METADATA_KEY, "120"),), time_remaining=9.0))
    assert ddl.source == "metadata"
    assert 110 < ddl.remaining_ms() <= 120
    # Garbage metadata falls through to the context deadline.
    ddl = from_grpc(_FakeContext(
        metadata=((DEADLINE_METADATA_KEY, "bogus"),), time_remaining=2.0))
    assert ddl.source == "context"
    assert 1900 < ddl.remaining_ms() <= 2000
    # Neither: the default applies.
    ddl = from_grpc(_FakeContext(), default_ms=75.0)
    assert ddl.source == "default"
    assert 70 < ddl.remaining_ms() <= 75
    # No context at all.
    assert from_grpc(None, default_ms=50.0).source == "default"
    # Zero metadata = already expired (sheds at admission).
    ddl = from_grpc(_FakeContext(metadata=((DEADLINE_METADATA_KEY, "0"),)))
    assert ddl.expired()


def test_monotonic_clock_discipline():
    """Deadlines anchor to time.monotonic(): a wall-clock step (NTP)
    must not move any deadline. Also pins the source files to zero
    ``time.time()`` on the admission→dispatch path (MX06's contract)."""
    import pathlib

    ddl = Deadline.after_ms(100.0)
    # The anchor IS a monotonic reading: remaining is consistent with
    # monotonic elapsed regardless of what the wall clock does.
    before = time.monotonic()
    remaining = ddl.remaining_ms()
    after = time.monotonic()
    assert (100.0 - (after - ddl.born_at) * 1000.0 - 1e-6
            <= remaining
            <= 100.0 - (before - ddl.born_at) * 1000.0 + 1e-6)
    repo = pathlib.Path(__file__).resolve().parent.parent
    for rel in ("igaming_platform_tpu/serve/deadline.py",
                "igaming_platform_tpu/serve/batcher.py"):
        src = (repo / rel).read_text()
        assert "time.time()" not in src, (
            f"{rel} uses wall clock — deadline/timeout arithmetic must be "
            "monotonic (MX06)")


# ---------------------------------------------------------------------------
# Scheduler: EDF, lanes, aging, expiry


def test_edf_order_within_lane():
    s = DeadlineScheduler()
    s.submit("slack", deadline=Deadline.after_ms(500), lane=LANE_BULK)
    s.submit("tight", deadline=Deadline.after_ms(50), lane=LANE_BULK)
    s.submit("mid", deadline=Deadline.after_ms(200), lane=LANE_BULK)
    order = [s.poll(0.1).payload for _ in range(3)]
    assert order == ["tight", "mid", "slack"]


def test_lane_priority_under_full_queue():
    """Interactive > bulk > background when every lane is loaded."""
    s = DeadlineScheduler()
    for i in range(3):
        s.submit(f"bg{i}", deadline=Deadline.after_ms(5000),
                 lane=LANE_BACKGROUND)
        s.submit(f"bulk{i}", deadline=Deadline.after_ms(5000), lane=LANE_BULK)
        s.submit(f"int{i}", deadline=Deadline.after_ms(5000),
                 lane=LANE_INTERACTIVE)
    order = [s.poll(0.1).payload for _ in range(9)]
    assert order[:3] == ["int0", "int1", "int2"]
    assert order[3:6] == ["bulk0", "bulk1", "bulk2"]
    assert order[6:] == ["bg0", "bg1", "bg2"]


def test_cross_lane_aging_prevents_starvation():
    """A bulk head older than its aging budget outranks fresh
    interactive traffic for one pop — no lane starves."""
    s = DeadlineScheduler(aging_ms={LANE_BULK: 30.0})
    s.submit("bulk-old", deadline=Deadline.after_ms(5000), lane=LANE_BULK)
    time.sleep(0.05)  # bulk head ages past 30 ms
    s.submit("int-fresh", deadline=Deadline.after_ms(5000),
             lane=LANE_INTERACTIVE)
    assert s.poll(0.1).payload == "bulk-old"
    assert s.poll(0.1).payload == "int-fresh"


def test_expired_in_queue_is_shed_not_returned():
    s = DeadlineScheduler()
    expired_counts = []
    s.on_expired = lambda n, stage, lane: expired_counts.append(
        (n, stage, lane))
    fut = s.submit("dead", deadline=Deadline.after_ms(5), lane=LANE_BULK)
    s.submit("live", deadline=Deadline.after_ms(5000), lane=LANE_BULK)
    time.sleep(0.02)  # first item expires while queued
    assert s.poll(0.1).payload == "live"
    with pytest.raises(DeadlineExpired) as ei:
        fut.result(timeout=60)
    assert ei.value.stage == "dispatch"
    assert expired_counts == [(1, "dispatch", LANE_BULK)]


def test_expired_at_submit_raises_admission():
    s = DeadlineScheduler()
    with pytest.raises(DeadlineExpired) as ei:
        s.submit("corpse", deadline=Deadline.after_ms(0))
    assert ei.value.stage == "admission"
    assert s.qsize() == 0


def test_queue_full_raises():
    from igaming_platform_tpu.serve.deadline import QueueFullError

    s = DeadlineScheduler(max_queue=2)
    s.submit(1)
    s.submit(2)
    with pytest.raises(QueueFullError):
        s.submit(3)


def test_tightest_remaining_scans_lane_heads():
    s = DeadlineScheduler()
    assert s.tightest_remaining_ms() is None
    s.submit("a", deadline=Deadline.after_ms(400), lane=LANE_BULK)
    s.submit("b", deadline=Deadline.after_ms(90), lane=LANE_INTERACTIVE)
    t = s.tightest_remaining_ms()
    assert t is not None and 60 < t <= 90


# ---------------------------------------------------------------------------
# Per-tick planning + online step model


def test_plan_tick_degrades_to_fixed_knobs_without_deadline():
    plan = plan_tick(shapes=(64, 256, 1024), tightest_ms=None,
                     max_wait_ms=2.0, step_model=None)
    assert plan.max_rows == 1024
    assert plan.window_s == pytest.approx(0.002)


def test_plan_tick_small_tier_under_tight_deadline():
    model = OnlineStepModel()
    for _ in range(5):
        model.observe(64, 2.0)
        model.observe(256, 8.0)
        model.observe(1024, 40.0)
    tight = plan_tick(shapes=(64, 256, 1024), tightest_ms=10.0,
                      max_wait_ms=2.0, step_model=model)
    assert tight.shape == 64  # 8 ms step would eat > half of 10 ms
    slack = plan_tick(shapes=(64, 256, 1024), tightest_ms=500.0,
                      max_wait_ms=2.0, step_model=model)
    assert slack.shape == 1024
    # Near-due queue: flush window collapses toward zero.
    due = plan_tick(shapes=(64, 256, 1024), tightest_ms=3.0,
                    max_wait_ms=2.0, step_model=model)
    assert due.window_s < 0.002


def test_online_step_model_predict_and_extrapolate():
    m = OnlineStepModel()
    assert m.predict_ms(256) is None
    m.observe(256, 10.0)
    assert m.predict_ms(256) == pytest.approx(10.0)
    # Smaller shape bounded by the nearest larger observation.
    assert m.predict_ms(64) == pytest.approx(10.0)
    # Larger shape extrapolates by row ratio.
    assert m.predict_ms(512) == pytest.approx(20.0)
    # EWMA tracks.
    for _ in range(50):
        m.observe(256, 20.0)
    assert 18.0 < m.predict_ms(256) <= 20.0
    # Stall threshold is well above the mean.
    assert m.stall_threshold_ms(256) >= 2 * m.predict_ms(256)


# ---------------------------------------------------------------------------
# Batcher integration: dynamic planning, dispatch shed, hedged re-dispatch


def test_batcher_sheds_expired_and_scores_live():
    from igaming_platform_tpu.serve.batcher import ContinuousBatcher

    b = ContinuousBatcher(
        lambda payloads: [p * 2 for p in payloads],
        BatcherConfig(batch_size=8, max_wait_ms=5.0),
    )
    dead = b.scheduler.submit("x", deadline=Deadline.after_ms(1))
    time.sleep(0.02)
    b.start()
    live = b.submit(21, deadline=Deadline.after_ms(5000))
    assert live.result(timeout=60) == 42
    with pytest.raises(DeadlineExpired):
        dead.result(timeout=60)
    assert b.dead_dispatched == 0
    b.stop()


def test_batcher_hedges_stalled_collect():
    """A collect stalled past the step model's threshold re-dispatches
    the batch and the hedge's result resolves the futures — bit-exact,
    first-wins, counted once."""
    from igaming_platform_tpu.serve.batcher import ContinuousBatcher

    model = OnlineStepModel()
    for _ in range(10):
        model.observe(4, 1.0)  # predicted ~1 ms -> stall threshold ~8 ms
    state = {"dispatches": 0, "collects": 0}
    first_collect_started = threading.Event()
    release_first = threading.Event()

    def dispatch(payloads):
        state["dispatches"] += 1
        return (state["dispatches"], list(payloads))

    def collect(handle):
        gen, payloads = handle
        state["collects"] += 1
        if gen == 1:
            first_collect_started.set()
            release_first.wait(timeout=10)  # wedged window
        return [p * 3 for p in payloads]

    b = ContinuousBatcher(
        cfg=BatcherConfig(batch_size=4, max_wait_ms=2.0, device_retries=0),
        dispatch=dispatch, collect=collect, shapes=(4,), step_model=model,
    ).start()
    try:
        fut = b.submit(5, deadline=Deadline.after_ms(5000))
        assert fut.result(timeout=10) == 15
        assert b.batches_hedged == 1
        assert state["dispatches"] == 2  # original + hedged re-dispatch
        release_first.set()
    finally:
        release_first.set()
        b.stop()


def test_batcher_plan_hook_reports_chosen_shape():
    from igaming_platform_tpu.serve.batcher import ContinuousBatcher

    shapes_seen = []
    b = ContinuousBatcher(
        lambda payloads: list(payloads),
        BatcherConfig(batch_size=64, max_wait_ms=1.0),
        shapes=(8, 64),
    )
    b.on_plan = shapes_seen.append
    b.start()
    try:
        b.submit(1, deadline=Deadline.after_ms(1000)).result(timeout=60)
        assert shapes_seen and all(s in (8, 64) for s in shapes_seen)
    finally:
        b.stop()


# ---------------------------------------------------------------------------
# Router hop decrement + burn gate


def test_outbound_deadline_decrements_by_elapsed():
    ddl = Deadline.after_ms(300.0)
    time.sleep(0.05)
    out = outbound_deadline_ms(ddl)
    assert 200 <= out <= 255
    assert outbound_deadline_ms(None) is None
    # Spent budget floors at 0 (the next hop sheds it at admission).
    assert outbound_deadline_ms(Deadline.after_ms(0.0)) == 0


def test_router_outbound_metadata_carries_decremented_deadline():
    from igaming_platform_tpu.serve.router import ScoringRouter

    ddl = Deadline.after_ms(500.0)
    time.sleep(0.03)
    md = dict(ScoringRouter._outbound_metadata((), ddl))
    assert DEADLINE_METADATA_KEY in md
    assert 400 <= int(md[DEADLINE_METADATA_KEY]) <= 475
    # No deadline -> no invented metadata.
    assert DEADLINE_METADATA_KEY not in dict(
        ScoringRouter._outbound_metadata(()))


def test_burn_shed_gate_follows_fast_alert():
    alerts = {"fast": False, "slow": False}
    gate = BurnShedGate(alerts_provider=lambda: alerts, enabled=True)
    gate.note_interactive()  # there is interactive traffic to protect
    assert not gate.shedding()
    alerts["fast"] = True
    assert gate.shedding()
    alerts["fast"] = False
    assert not gate.shedding()
    # Opt-out wins.
    off = BurnShedGate(alerts_provider=lambda: {"fast": True}, enabled=False)
    off.note_interactive()
    assert not off.shedding()


def test_burn_shed_gate_idle_without_interactive_traffic():
    """A pure-bulk workload burning its own latency budget has nothing
    to yield to — the shed only arms while interactive traffic exists
    (the flat-out bench arm pinned this)."""
    gate = BurnShedGate(alerts_provider=lambda: {"fast": True},
                        enabled=True, interactive_idle_s=0.05)
    assert not gate.shedding()  # never saw interactive traffic
    gate.note_interactive()
    assert gate.shedding()
    time.sleep(0.08)  # interactive traffic went away
    assert not gate.shedding()


# ---------------------------------------------------------------------------
# gRPC end-to-end: metadata parse at the edge, admission shed, burn shed,
# scoring parity under lane reordering


@pytest.fixture(scope="module")
def deadline_server():
    import grpc

    from igaming_platform_tpu.serve.grpc_server import (
        RiskGrpcService,
        make_risk_stub,
        serve_risk,
    )
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    engine = TPUScoringEngine(
        batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1))
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    channel = grpc.insecure_channel(f"localhost:{port}")
    yield engine, service, make_risk_stub(channel)
    channel.close()
    server.stop(0)
    engine.close()


def _txn_req(account="ddl-acct", amount=1500):
    from risk.v1 import risk_pb2

    return risk_pb2.ScoreTransactionRequest(
        account_id=account, amount=amount, transaction_type="deposit")


def test_expired_at_admission_sheds_with_pushback(deadline_server):
    import grpc

    _engine, service, stub = deadline_server
    before = service.metrics.deadline_expired_total.value(stage="admission")
    with pytest.raises(grpc.RpcError) as ei:
        stub.ScoreTransaction(
            _txn_req(), metadata=((DEADLINE_METADATA_KEY, "0"),))
    err = ei.value
    assert err.code() == grpc.StatusCode.DEADLINE_EXCEEDED
    trailing = dict(err.trailing_metadata() or ())
    assert trailing.get("grpc-retry-pushback-ms"), trailing
    assert service.metrics.deadline_expired_total.value(
        stage="admission") == before + 1
    # The status lands under its own code label (repo convention:
    # errors_total counts every non-OK, sheds included — the SLO plane
    # is where shed-vs-error is distinguished).
    assert service.metrics.requests_total.value(
        method="ScoreTransaction", code="DEADLINE_EXCEEDED") >= 1


def test_garbage_huge_absent_metadata_all_score_ok(deadline_server):
    _engine, _service, stub = deadline_server
    for md in (
        ((DEADLINE_METADATA_KEY, "bogus"),),
        ((DEADLINE_METADATA_KEY, "999999999999"),),
        (),
    ):
        resp = stub.ScoreTransaction(_txn_req(), metadata=md)
        assert 0 <= resp.score <= 100


def test_deadline_shed_does_not_burn_slo_budget(deadline_server):
    """Admission sheds carry the `shed` root attribute: the SLO engine
    must not count them as budget-burning violations."""
    import grpc

    from igaming_platform_tpu.obs import slo as slo_mod

    _engine, _service, stub = deadline_server
    engine_slo = slo_mod.get_default()
    assert engine_slo is not None
    before = engine_slo.violations_total
    for _ in range(3):
        with pytest.raises(grpc.RpcError):
            stub.ScoreTransaction(
                _txn_req(), metadata=((DEADLINE_METADATA_KEY, "0"),))
    assert engine_slo.violations_total == before


def test_burn_shed_loop_bulk_sheds_and_recovers(deadline_server):
    """The closed loop: fast-window alert active -> bulk ScoreBatch
    sheds BULK_SHED with pushback; alert clears -> bulk resumes."""
    import grpc

    from risk.v1 import risk_pb2

    _engine, service, stub = deadline_server
    batch = risk_pb2.ScoreBatchRequest(
        transactions=[_txn_req(f"bb{i}") for i in range(4)])
    alerts = {"fast": True}
    service.burn_gate._provider = lambda: alerts
    service.burn_gate.enabled = True
    # Arm the gate: a recent interactive admission is what bulk yields to.
    stub.ScoreTransaction(_txn_req("burn-arm"))
    try:
        with pytest.raises(grpc.RpcError) as ei:
            stub.ScoreBatch(batch)
        assert ei.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        assert "BULK_SHED" in ei.value.details()
        assert dict(ei.value.trailing_metadata() or ()).get(
            "grpc-retry-pushback-ms")
        assert service.burn_gate.sheds >= 1
        alerts["fast"] = False
        resp = stub.ScoreBatch(batch)
        assert len(resp.results) == 4
    finally:
        service.burn_gate._provider = None


def test_scoring_parity_under_lane_reordering(deadline_server):
    """Scheduling is score-inert: the same requests submitted through
    shuffled lanes/deadlines produce BIT-EXACT outputs vs the lockstep
    batch path."""
    from igaming_platform_tpu.serve.scorer import ScoreRequest

    engine, _service, _stub = deadline_server
    reqs = [
        ScoreRequest(f"par-{i}", amount=1000 + 137 * i,
                     tx_type=("deposit", "bet", "withdraw")[i % 3],
                     device_id=f"dev-{i % 5}")
        for i in range(24)
    ]
    # Lockstep reference: the direct batch path.
    ref = engine.score_batch(list(reqs))
    ref_by_req = {id(reqs[i]): ref[i] for i in range(len(reqs))}
    # Scheduled arm: interleaved lanes, shuffled deadline budgets.
    rng = np.random.default_rng(5)
    futs = []
    for i, idx in enumerate(rng.permutation(len(reqs))):
        req = reqs[int(idx)]
        lane = (LANE_INTERACTIVE, LANE_BULK, LANE_BACKGROUND)[i % 3]
        futs.append((req, engine._batcher.submit(
            req, deadline=Deadline.after_ms(float(5000 + 100 * i)),
            lane=lane)))
    for req, fut in futs:
        a, b = ref_by_req[id(req)], fut.result(timeout=30)
        assert (a.score, a.action, a.rule_score) == (
            b.score, b.action, b.rule_score)
        assert a.ml_score == b.ml_score  # bit-exact, no tolerance
        assert a.reason_codes == b.reason_codes


def test_response_time_shed_for_explicit_deadline(deadline_server,
                                                  monkeypatch):
    """An explicitly-deadlined request whose budget expires between
    admission and response answers DEADLINE_EXCEEDED (a shed), never a
    stale OK — the 'zero scored after deadline' contract. Driven at the
    handler seam with a deterministically-slow engine (a live 1 ms RPC
    can legitimately finish inside its budget on a warm path)."""
    import grpc

    from igaming_platform_tpu.serve.grpc_server import RpcAbort

    engine, service, _stub = deadline_server
    orig_score = engine.score

    def slow_score(req, timeout=30.0, **kwargs):
        resp = orig_score(req, timeout=timeout)
        time.sleep(0.03)  # outlive the 15 ms budget below
        return resp

    monkeypatch.setattr(engine, "score", slow_score)
    service._score_takes_deadline = False  # slow_score has no deadline kw
    try:
        ctx = _FakeContext(metadata=((DEADLINE_METADATA_KEY, "15"),))
        with pytest.raises(RpcAbort) as ei:
            service.ScoreTransaction(_txn_req(), ctx)
        assert ei.value.code == grpc.StatusCode.DEADLINE_EXCEEDED
        assert ei.value.shed
        assert service.metrics.deadline_expired_total.value(
            stage="response") >= 1
    finally:
        service._score_takes_deadline = True


def test_lane_depth_and_remaining_metrics_rendered(deadline_server):
    """New series render under the existing lock discipline with
    bounded labels (MX05)."""
    _engine, service, stub = deadline_server
    stub.ScoreTransaction(
        _txn_req(), metadata=((DEADLINE_METADATA_KEY, "5000"),))
    text = service.metrics.registry.render_text()
    assert "risk_deadline_remaining_ms_bucket" in text
    assert "risk_lane_depth" in text
    assert "risk_batch_size_chosen_bucket" in text
    assert "risk_deadline_expired_total" in text
