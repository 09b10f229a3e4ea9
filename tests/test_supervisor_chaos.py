"""Self-healing supervisor under deterministic chaos (serve/supervisor.py,
serve/chaos.py, multihost resurrection, graceful drain).

The acceptance bar of the supervisor PR, as tests:

- kill a follower mid-soak: the front never wedges, serves single-host
  degraded responses BIT-EXACT to the full-mesh ones, and returns to
  full-mesh SERVING within the backoff budget once the follower restarts;
- inject a readback that never returns at the readback seam: the watchdog
  fails the in-flight window with UNAVAILABLE + retry-pushback metadata,
  the engine rebuilds (warmup replay), and subsequent RPCs succeed;
- take the feature store down: ScoreTransaction keeps answering —
  conservative CPU-heuristic scores flagged via reason code, model-
  version trailing metadata and the degraded counter, with zero errors;
- two threads hammering WorkChannel.broadcast race neither the ACK reap's
  socket-mode transitions nor a resurrecting link (satellite regression);
- SIGTERM under load (graceful_stop with the engine drain) loses zero
  admitted requests.
"""

import os
import subprocess
import sys
import threading
import time

import grpc
import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
from igaming_platform_tpu.serve import chaos
from igaming_platform_tpu.serve import multihost
from igaming_platform_tpu.serve.grpc_server import (
    RiskGrpcService,
    graceful_stop,
    make_risk_stub,
    serve_risk,
)
from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine
from igaming_platform_tpu.serve.supervisor import (
    BROWNOUT,
    CLOSED,
    DEGRADED,
    HALF_OPEN,
    OPEN,
    SERVING,
    CircuitBreaker,
    ServingSupervisor,
    SupervisedScoringEngine,
    heuristic_scores,
)

@pytest.fixture(autouse=True)
def _clear_chaos():
    chaos.clear()
    yield
    chaos.clear()


def _engine_factory(batch: int = 16):
    def factory():
        return TPUScoringEngine(
            ScoringConfig(),
            batcher_config=BatcherConfig(batch_size=batch, max_wait_ms=1.0),
        )
    return factory


def _start_stub(port: int, mode: str = "ack", wedge_after: int = 0):
    args = [sys.executable, "-m", "igaming_platform_tpu.serve.multihost",
            "--stub-follower", "--port", str(port)]
    if mode != "ack":
        args += ["--mode", mode, "--wedge-after", str(wedge_after)]
    proc = subprocess.Popen(
        args, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    line = proc.stdout.readline()
    assert "READY" in line, line
    return proc


# ---------------------------------------------------------------------------
# Circuit breaker + chaos plan units


def test_circuit_breaker_transitions():
    now = [0.0]
    br = CircuitBreaker("dep", failure_threshold=3, open_s=2.0,
                        clock=lambda: now[0])
    states = []
    br.on_state_change = lambda b, s: states.append(s)

    assert br.state == CLOSED and br.allow()
    br.record_failure("e1")
    br.record_failure("e2")
    assert br.state == CLOSED  # below threshold
    br.record_failure("e3")
    assert br.state == OPEN
    assert not br.allow()  # open window not elapsed

    now[0] = 2.5
    assert br.allow()  # flips HALF_OPEN, admits the probe
    assert br.state == HALF_OPEN
    assert not br.allow()  # only one probe at a time
    br.record_success()
    assert br.state == CLOSED

    # A failure during a half-open probe reopens immediately.
    br.record_failure("e", fatal=True)
    assert br.state == OPEN
    now[0] = 5.0
    assert br.allow()
    br.record_failure("probe failed")
    assert br.state == OPEN

    # Forced open pins past the window; clear_forced goes to HALF_OPEN.
    br.force_open("operator")
    now[0] = 100.0
    assert not br.allow()
    br.clear_forced()
    assert br.state == HALF_OPEN
    br.reset()
    assert br.state == CLOSED
    assert states[0] == OPEN and OPEN in states and CLOSED in states


def test_breaker_success_closes_inline_dependency():
    """Dependencies exercised inline (feature store) close from OPEN on
    real-path success — but never while force-held."""
    br = CircuitBreaker("fs", failure_threshold=1, open_s=60.0)
    br.record_failure("boom")
    assert br.state == OPEN
    br.record_success()
    assert br.state == CLOSED
    br.force_open("rebuilding")
    br.record_success()
    assert br.state == OPEN


def test_chaos_plan_parsing_and_determinism():
    plan_str = "seed=42;device.readback=delay:p=0.5:ms=1;feature_store.gather=error:p=1.0:after=2:count=2"
    a = chaos.ChaosPlan.from_string(plan_str)
    b = chaos.ChaosPlan.from_string(plan_str)

    def run(plan):
        fired = []
        for i in range(40):
            try:
                fired.append(plan.fire("device.readback") or "-")
            except chaos.ChaosError:
                fired.append("error")
        return fired

    assert run(a) == run(b), "same seed+seam must fire identically"
    # Windowing: ops 0,1 clean; 2,3 error; rest clean.
    for i in range(6):
        if i in (2, 3):
            with pytest.raises(chaos.ChaosError):
                a.fire("feature_store.gather")
        else:
            assert a.fire("feature_store.gather") is None

    with pytest.raises(ValueError):
        chaos.ChaosPlan.from_string("device.readback=explode:p=1.0")
    with pytest.raises(ValueError):
        chaos.ChaosPlan.from_string("device.readback=delay:p=2.0")
    with pytest.raises(ValueError):
        chaos.ChaosPlan.from_string("device.readback")


def test_heuristic_scores_conservative():
    from igaming_platform_tpu.core.features import F, NUM_FEATURES

    x = np.zeros((3, NUM_FEATURES), dtype=np.float32)
    bl = np.zeros((3,), dtype=bool)
    # Row 1: blacklisted + rapid-fire -> block territory.
    bl[1] = True
    x[1, F.TX_COUNT_1M] = 20
    # Row 2: brand-new account moving big money over a VPN, bonus-only
    # pattern -> 25+20+10 = 55 points, review territory.
    x[2, F.ACCOUNT_AGE_DAYS] = 0.1
    x[2, F.TX_AMOUNT] = 90_000
    x[2, F.BONUS_ONLY_PLAYER] = 1.0
    x[2, F.IS_VPN] = 1.0
    out = heuristic_scores(x, bl, np.array([80, 50], np.int32))
    assert out["score"][0] == 0 and out["action"][0] == 1  # clean -> approve
    assert out["score"][1] >= 80 and out["action"][1] == 3  # -> block
    assert out["score"][2] == 55 and out["action"][2] == 2  # -> review
    assert out["reason_mask"][1] != 0


# ---------------------------------------------------------------------------
# Degraded scoring tier (feature-store outage) at the wire


def test_feature_store_outage_serves_degraded_heuristic():
    sup = ServingSupervisor(failure_threshold=2, open_s=0.5)
    engine = SupervisedScoringEngine(_engine_factory(), supervisor=sup,
                                     watchdog_s=20.0)
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    ch = grpc.insecure_channel(f"localhost:{port}")
    stub = make_risk_stub(ch)
    try:
        from risk.v1 import risk_pb2

        ok = stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
            account_id="pre", amount=1000, transaction_type="deposit"))
        assert "DEGRADED_CPU_HEURISTIC" not in ok.reason_codes

        chaos.install("seed=3;feature_store.gather=error:p=1.0")
        degraded = 0
        for i in range(5):
            resp, call = stub.ScoreTransaction.with_call(
                risk_pb2.ScoreTransactionRequest(
                    account_id=f"fs-{i}", amount=1000,
                    transaction_type="deposit"))
            # NEVER an error: a conservative flagged answer.
            assert 0 <= resp.score <= 100
            if "DEGRADED_CPU_HEURISTIC" in resp.reason_codes:
                degraded += 1
                trailing = dict(call.trailing_metadata() or ())
                assert "degraded-heuristic" in trailing.get(
                    "risk-model-version", "")
        assert degraded >= 3
        assert sup.state == DEGRADED
        assert service.metrics.degraded_responses_total.value(
            tier="heuristic") >= degraded
        # Zero handler errors: degradation is not an error path.
        assert service.metrics.errors_total.value(
            method="ScoreTransaction") == 0

        # Store recovers -> real scores + SERVING again.
        chaos.clear()
        deadline = time.monotonic() + 60
        while sup.state != SERVING and time.monotonic() < deadline:
            stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
                account_id="rec", amount=1000, transaction_type="deposit"))
            time.sleep(0.05)
        assert sup.state == SERVING
    finally:
        ch.close()
        graceful_stop(server, health, grace=5, engine=engine)


# ---------------------------------------------------------------------------
# Device-step watchdog (a step that never returns)


def test_wedge_trips_watchdog_then_rpcs_recover():
    sup = ServingSupervisor(failure_threshold=2, open_s=0.3)
    engine = SupervisedScoringEngine(_engine_factory(), supervisor=sup,
                                     watchdog_s=1.0)
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    ch = grpc.insecure_channel(f"localhost:{port}")
    stub = make_risk_stub(ch)
    try:
        from risk.v1 import risk_pb2

        req = risk_pb2.ScoreTransactionRequest(
            account_id="w", amount=1000, transaction_type="deposit")
        stub.ScoreTransaction(req)  # warm path

        # count=2: the batcher's stall hedge (serve/batcher.py) would
        # recover a SINGLE wedged readback by re-dispatching the batch —
        # to demonstrate the watchdog, the hedged collect must wedge too.
        chaos.install("seed=5;device.readback=wedge:p=1.0:ms=6000:count=2")
        t0 = time.monotonic()
        with pytest.raises(grpc.RpcError) as exc_info:
            stub.ScoreTransaction(req)
        err = exc_info.value
        # Loud UNAVAILABLE within ~the watchdog deadline, never a wedge.
        assert err.code() == grpc.StatusCode.UNAVAILABLE
        # timing-ok: the engine's own watchdog_s=1.0, against the 6 s wedge
        assert time.monotonic() - t0 < 5.0
        trailing = dict(err.trailing_metadata() or ())
        assert trailing.get("grpc-retry-pushback-ms"), trailing
        assert service.metrics.watchdog_trips_total.value() == 1

        # While rebuilding: degraded heuristic answers, still no wedge.
        resp = stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
            account_id="d", amount=500, transaction_type="deposit"))
        assert "DEGRADED_CPU_HEURISTIC" in resp.reason_codes

        # Rebuild completes (warmup replayed in the factory) and the
        # half-open probe closes the circuit: subsequent RPCs succeed.
        deadline = time.monotonic() + 30
        while engine.rebuilds < 1 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert engine.rebuilds == 1
        chaos.clear()
        deadline = time.monotonic() + 60
        ok = None
        while time.monotonic() < deadline:
            ok = stub.ScoreTransaction(req)
            if "DEGRADED_CPU_HEURISTIC" not in ok.reason_codes:
                break
            time.sleep(0.1)
        assert ok is not None
        assert "DEGRADED_CPU_HEURISTIC" not in ok.reason_codes
        assert sup.state == SERVING
        assert service.metrics.engine_rebuilds_total.value() == 1
    finally:
        ch.close()
        graceful_stop(server, health, grace=5, engine=engine)


# ---------------------------------------------------------------------------
# BROWNOUT: even the degraded tier failing sheds loudly


def test_brownout_sheds_unavailable_with_pushback():
    sup = ServingSupervisor(failure_threshold=2, open_s=0.5)
    engine = SupervisedScoringEngine(_engine_factory(), supervisor=sup,
                                     watchdog_s=20.0)
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    sup.bind(health=health, metrics=service.metrics)
    ch = grpc.insecure_channel(f"localhost:{port}")
    stub = make_risk_stub(ch)
    try:
        from igaming_platform_tpu.serve.grpc_server import (
            NOT_SERVING,
            SERVING as H_SERVING,
            make_health_stub,
        )
        from risk.v1 import risk_pb2

        health_stub = make_health_stub(ch)
        from igaming_platform_tpu.serve.grpc_server import health_pb2

        assert health_stub.Check(
            health_pb2.HealthCheckRequest(service="")).status == H_SERVING

        sup.force_brownout("test")
        assert sup.state == BROWNOUT
        assert health_stub.Check(
            health_pb2.HealthCheckRequest(service="")).status == NOT_SERVING
        with pytest.raises(grpc.RpcError) as exc_info:
            stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
                account_id="b", amount=100, transaction_type="deposit"))
        assert exc_info.value.code() == grpc.StatusCode.UNAVAILABLE
        trailing = dict(exc_info.value.trailing_metadata() or ())
        assert trailing.get("grpc-retry-pushback-ms")
        assert service.metrics.serving_state.value() == 2

        sup.clear_brownout()
        assert sup.state == SERVING
        stub.ScoreTransaction(risk_pb2.ScoreTransactionRequest(
            account_id="b2", amount=100, transaction_type="deposit"))
    finally:
        ch.close()
        graceful_stop(server, health, grace=5, engine=engine)


# ---------------------------------------------------------------------------
# WorkChannel: broadcast thread-safety regression (satellite 1)


def test_broadcast_concurrent_threads_ack_accounting(tmp_path, free_port):
    """Two threads hammering broadcast must not race the per-socket mode
    transitions in the ACK reap: no spurious dead-marking, consistent
    un-ACKed accounting, channel alive at the end."""
    port = free_port()
    proc = _start_stub(port)
    chan = multihost.WorkChannel([port], io_timeout_s=10.0, ack_window=4)
    errors: list[BaseException] = []
    try:
        chan.broadcast_hello(np.zeros((32,), dtype=np.uint8))
        xp = np.zeros((16, 30), np.float32)
        blp = np.zeros((16,), bool)
        thr = np.array([80, 60], np.int32)

        def hammer():
            try:
                for _ in range(100):
                    chan.broadcast(xp, blp, thr)
            except BaseException as exc:  # noqa: BLE001 — collected for the assert
                errors.append(exc)

        threads = [threading.Thread(target=hammer) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert chan.alive
        link = chan._links[0]
        assert 0 <= link.outstanding <= 200
    finally:
        chan.close()
        proc.kill()


# ---------------------------------------------------------------------------
# Follower kill -> single-host degraded -> resurrection, bit-exact


def test_follower_kill_resurrection_bit_exact(tmp_path, free_port):
    port = free_port()
    stub = _start_stub(port)
    sup = ServingSupervisor(failure_threshold=2, open_s=0.5)
    engine = multihost.multihost_engine(
        None, [port], config=ScoringConfig(),
        batcher_config=BatcherConfig(batch_size=16, max_wait_ms=1.0),
        ml_backend="mock", params=None, reconnect=True, supervisor=sup,
        channel_kwargs=dict(io_timeout_s=2.0, ack_window=4,
                            reconnect_backoff_s=(0.05, 0.5)))
    stub2 = None
    try:
        reqs = [ScoreRequest(f"mh-{i}", amount=500 + 37 * i,
                             tx_type=("deposit", "bet", "withdraw")[i % 3])
                for i in range(24)]
        baseline = [(r.score, r.ml_score) for r in engine.score_batch(reqs)]
        assert sup.state == SERVING

        stub.kill()
        stub.wait(timeout=10)

        # (a) never wedges, (b) serves degraded single-host responses
        # bit-exact to the full-mesh ones while the follower is down.
        t0 = time.monotonic()
        during = [(r.score, r.ml_score) for r in engine.score_batch(reqs)]
        # timing-ok: a wedge never returns; the channel's own io_timeout_s is 2.0
        assert time.monotonic() - t0 < 30.0, "outage scoring must not wedge"
        assert during == baseline
        assert not engine._chan.alive
        assert sup.state == DEGRADED
        assert engine.degraded_steps >= 1

        # (c) restart on the same port: resurrection within the backoff
        # budget (base 0.05s, cap 0.5s -> well under 8s), then full-mesh
        # SERVING with bit-exact scores.
        stub2 = _start_stub(port)
        t_restart = time.monotonic()
        budget_s = 60.0
        while not engine._chan.alive and time.monotonic() - t_restart < budget_s:
            time.sleep(0.05)
        assert engine._chan.alive, "follower never resurrected in budget"
        assert engine._chan.resurrections == 1
        assert sup.state == SERVING
        after = [(r.score, r.ml_score) for r in engine.score_batch(reqs)]
        assert after == baseline

        # The resurrected follower really participates again: broadcasts
        # flow (outstanding rises then reaps — no dead-marking).
        for _ in range(5):
            engine.score_batch(reqs[:8])
        assert engine._chan.alive
    finally:
        engine.close()
        for p in (stub, stub2):
            if p is not None and p.poll() is None:
                p.kill()


def test_resurrection_replays_param_hot_swap(tmp_path, free_port):
    """A param hot-swap during the outage reaches the follower at
    resurrection via the provider replay (MAGIC_PARAMS before alive)."""
    port = free_port()
    stub = _start_stub(port)
    chan = multihost.WorkChannel([port], io_timeout_s=2.0, ack_window=4,
                                 reconnect=True,
                                 reconnect_backoff_s=(0.05, 0.3))
    leaves_served = [np.zeros((4,), np.float32)]
    chan.set_params_provider(lambda: leaves_served)
    states = []
    chan.on_follower_state = lambda i, s, why: states.append(s)
    stub2 = None
    try:
        chan.broadcast_hello(np.zeros((32,), dtype=np.uint8))
        xp = np.zeros((8, 30), np.float32)
        blp = np.zeros((8,), bool)
        thr = np.array([80, 60], np.int32)
        chan.broadcast(xp, blp, thr)

        stub.kill()
        stub.wait(timeout=10)
        with pytest.raises(multihost.MultihostChannelError):
            for _ in range(10):
                chan.broadcast(xp, blp, thr)
                time.sleep(0.05)
        # Outage-time hot swap: only the provider's CURRENT leaves matter.
        leaves_served[0] = np.ones((4,), np.float32)

        stub2 = _start_stub(port)
        deadline = time.monotonic() + 60
        while not chan.alive and time.monotonic() < deadline:
            time.sleep(0.05)
        assert chan.alive
        assert states.count("dead") >= 1 and states[-1] == "alive"
        # Channel usable again end-to-end (stub absorbed the PARAMS frame).
        for _ in range(3):
            chan.broadcast(xp, blp, thr)
    finally:
        chan.close()
        for p in (stub, stub2):
            if p is not None and p.poll() is None:
                p.kill()


# ---------------------------------------------------------------------------
# Graceful shutdown under load (satellite 2)


def test_graceful_stop_drains_admitted_requests_under_load():
    engine = _engine_factory(batch=64)()
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0)
    addr = f"localhost:{port}"

    from risk.v1 import risk_pb2

    outcomes: list[str] = []
    lock = threading.Lock()
    stop_initiated = threading.Event()

    def worker(k: int) -> None:
        ch = grpc.insecure_channel(addr)
        stub = make_risk_stub(ch)
        txs = [risk_pb2.ScoreTransactionRequest(
            account_id=f"g-{k}-{i}", amount=100 + i,
            transaction_type="deposit") for i in range(150)]
        i = 0
        while not stop_initiated.is_set() or i < 4:
            # Keep submitting briefly past the stop so rejected-new vs
            # drained-admitted behaviour both appear.
            try:
                if i % 2:
                    stub.ScoreBatch(
                        risk_pb2.ScoreBatchRequest(transactions=txs),
                        timeout=30)
                else:
                    stub.ScoreTransaction(txs[0], timeout=30)
                code = "OK"
            except grpc.RpcError as exc:
                code = exc.code().name
            with lock:
                outcomes.append(code)
            i += 1
            if stop_initiated.is_set():
                time.sleep(0.05)
        ch.close()

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.7)  # real in-flight load
    stop_initiated.set()
    graceful_stop(server, health, grace=15.0, engine=engine)
    for t in threads:
        t.join(timeout=30)

    counts: dict[str, int] = {}
    for c in outcomes:
        counts[c] = counts.get(c, 0) + 1
    assert counts.get("OK", 0) > 0, counts
    # Zero admitted-request loss: every non-OK outcome is the clean
    # rejection of a NOT-admitted RPC — UNAVAILABLE from the stopped
    # server, RESOURCE_EXHAUSTED from the admission gate, or CANCELLED
    # for an RPC still queued at the server edge when stop hit (its
    # handler never started; the client retries). What must NEVER appear
    # is INTERNAL / DEADLINE_EXCEEDED / UNKNOWN — a handler stranded on
    # an engine closed before the gRPC drain (the bug graceful_stop's
    # engine parameter exists to prevent).
    bad = {c: n for c, n in counts.items()
           if c not in ("OK", "UNAVAILABLE", "RESOURCE_EXHAUSTED",
                        "CANCELLED")}
    assert not bad, counts
    assert counts.get("CANCELLED", 0) <= 8, counts  # edge-queued only, not a drain failure


# ---------------------------------------------------------------------------
# Availability block (satellite 3)


def test_availability_block_accounting():
    from tools.drills.load_gen import availability_block

    t0 = 100.0
    events = []
    # 3s healthy @4/s, 2s outage @4/s, recovery at ~105.1, healthy after.
    for i in range(12):
        events.append((t0 + 0.25 * i, True))
    for i in range(8):
        events.append((t0 + 3.0 + 0.25 * i, False))
    events.append((t0 + 5.1, True))
    for i in range(8):
        events.append((t0 + 5.2 + 0.25 * i, True))

    block = availability_block(events, t0, t0 + 8.0)
    assert block["requests"] == len(events)
    assert block["failures"] == 8
    assert block["max_consecutive_failures"] == 8
    assert abs(block["max_failure_window_s"] - 1.75) < 1e-6
    assert block["success_rate_per_window"][0] == 1.0
    assert block["success_rate_per_window"][3] == 0.0
    assert len(block["outages"]) == 1
    out = block["outages"][0]
    assert abs(out["time_to_recovery_s"] - 2.1) < 1e-6
    assert abs(block["time_to_recovery_s"] - 2.1) < 1e-6

    # An outage that never recovers reports None, not a bogus number.
    block2 = availability_block(
        [(t0, True), (t0 + 1, False), (t0 + 2, False)], t0, t0 + 3.0)
    assert block2["outages"][0]["time_to_recovery_s"] is None
    assert block2["time_to_recovery_s"] is None
