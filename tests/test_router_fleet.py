"""Router failover semantics over an in-process scoring fleet.

Three real risk.v1 replica servers (mock backend, identical params and
empty feature history, so any account scores bit-exact on any replica)
behind the account-affinity router (serve/router.py). Pins the ISSUE 6
failover contract:

- account affinity: steady-state, every account's RPCs land on its ring
  owner and NOWHERE else (each replica's cache stays disjoint);
- replica kill mid-load: clients see only OK (retried onto the next ring
  owner) or UNAVAILABLE — never INTERNAL, never a wrong answer: a
  failed-over account scores bit-exact on the secondary (no silent
  wrong-replica "fresh account" divergence);
- pushback honor: the router's retry path consumes the server's
  ``grpc-retry-pushback-ms`` trailing hint (and load_gen's client
  retry helper honors it too — the satellite fix);
- hedged stragglers: first response wins, the loser is cancelled, every
  hedge lands in exactly one terminal outcome;
- health-driven ring membership: NOT_SERVING (supervisor BROWNOUT)
  evicts without a single failed RPC; recovery re-admits.
"""

from __future__ import annotations

import threading
import time

import grpc
import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig
from igaming_platform_tpu.serve.grpc_server import (
    NOT_SERVING,
    SERVING,
    RiskGrpcService,
    serve_risk,
)
from igaming_platform_tpu.serve.router import (
    LatencyWindow,
    ScoringRouter,
    serve_router,
)
from igaming_platform_tpu.serve.scorer import TPUScoringEngine

from risk.v1 import risk_pb2


def _engine() -> TPUScoringEngine:
    return TPUScoringEngine(
        batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1.0))


class _Replica:
    def __init__(self, rid: str, engine=None):
        self.rid = rid
        self.engine = engine or _engine()
        self.service = RiskGrpcService(self.engine)
        self.server, self.health, self.port = serve_risk(self.service, 0)
        self.addr = f"localhost:{self.port}"
        self.stopped = False

    def kill(self) -> None:
        if not self.stopped:
            self.server.stop(0)
            self.stopped = True

    def close(self) -> None:
        self.kill()
        self.engine.close()


class _SlowEngine:
    """Engine wrapper: every score() stalls — the straggler shape the
    hedge deadline exists for."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self._delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self.__dict__["_inner"], name)

    def score(self, req, timeout: float = 30.0):
        time.sleep(self._delay_s)
        return self._inner.score(req, timeout=timeout)


def _router_over(replicas, **kwargs) -> tuple[ScoringRouter, object, str]:
    import random

    spec = {r.rid: (r.addr, None) for r in replicas}
    kwargs.setdefault("health_interval_s", 0.1)
    kwargs.setdefault("failure_threshold", 2)
    kwargs.setdefault("rng", random.Random(7))
    router = ScoringRouter(spec, **kwargs)
    server, _health, port = serve_router(router, 0)
    return router, server, f"localhost:{port}"


def _stubs(addr: str):
    ch = grpc.insecure_channel(addr)
    txn = ch.unary_unary(
        "/risk.v1.RiskService/ScoreTransaction",
        request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
        response_deserializer=risk_pb2.ScoreTransactionResponse.FromString)
    batch = ch.unary_unary(
        "/risk.v1.RiskService/ScoreBatch",
        request_serializer=risk_pb2.ScoreBatchRequest.SerializeToString,
        response_deserializer=risk_pb2.ScoreBatchResponse.FromString)
    return ch, txn, batch


@pytest.fixture(scope="module")
def fleet3():
    replicas = [_Replica(f"r{i}") for i in range(3)]
    yield replicas
    for r in replicas:
        r.close()


def test_affinity_routes_each_account_to_its_ring_owner(fleet3):
    router, server, addr = _router_over(fleet3, hedge=False)
    ch, txn, _ = _stubs(addr)
    try:
        scored_before = {r.rid: r.service.metrics.txns_scored_total.value()
                        for r in fleet3}
        accounts = [f"aff-{i}" for i in range(40)]
        for acct in accounts:
            resp = txn(risk_pb2.ScoreTransactionRequest(
                account_id=acct, amount=1500, transaction_type="deposit"),
                timeout=10)
            assert 0 <= resp.score <= 100
        owned = {r.rid: 0 for r in fleet3}
        for acct in accounts:
            owned[router.ring.owner(acct)] += 1
        for r in fleet3:
            got = (r.service.metrics.txns_scored_total.value()
                   - scored_before[r.rid])
            assert got == owned[r.rid], (
                f"{r.rid} scored {got} txns but owns {owned[r.rid]} "
                "accounts — affinity leaked")
        assert router.stats["retries"] == 0
    finally:
        ch.close()
        router.close()
        server.stop(0)


def test_batch_splits_by_owner_and_merges_in_order(fleet3):
    router, server, addr = _router_over(fleet3, hedge=False)
    ch, _, batch = _stubs(addr)
    try:
        txs = [
            risk_pb2.ScoreTransactionRequest(
                account_id=f"split-{i}", amount=1000 + 137 * i,
                transaction_type=("deposit", "bet", "withdraw")[i % 3])
            for i in range(30)
        ]
        owners = {router.ring.owner(t.account_id) for t in txs}
        assert len(owners) > 1  # the batch genuinely splits
        via_router = batch(risk_pb2.ScoreBatchRequest(transactions=txs),
                           timeout=15)
        assert len(via_router.results) == len(txs)
        # Identical engines + empty history: replica 0 scoring the WHOLE
        # batch directly is the order-preserving reference.
        ch0, _, batch0 = _stubs(fleet3[0].addr)
        direct = batch0(risk_pb2.ScoreBatchRequest(transactions=txs),
                        timeout=15)
        ch0.close()
        assert [r.score for r in via_router.results] == \
            [r.score for r in direct.results]
    finally:
        ch.close()
        router.close()
        server.stop(0)


def test_non_unavailable_statuses_pass_through(fleet3):
    router, server, addr = _router_over(fleet3, hedge=False)
    ch = grpc.insecure_channel(addr)
    raw = ch.unary_unary("/risk.v1.RiskService/ScoreBatch",
                         request_serializer=lambda b: b,
                         response_deserializer=lambda b: b)
    try:
        with pytest.raises(grpc.RpcError) as exc_info:
            raw(b"\x00garbage-not-a-proto", timeout=10)
        assert exc_info.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        ch.close()
        router.close()
        server.stop(0)


def test_cancelled_by_a_stopping_replica_fails_over(fleet3, monkeypatch):
    """A replica that stops without grace hands the calls it had in flight
    CANCELLED, not UNAVAILABLE. The router walks the ring on it just the
    same; surfaced, it reached the caller as UNKNOWN (the kill test below
    met it on a loaded machine)."""

    class _Cancelled(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.CANCELLED

        def details(self):
            return "CANCELLED"

        def trailing_metadata(self):
            return ()

    router, server, addr = _router_over(fleet3, hedge=False)
    ch, txn, _ = _stubs(addr)
    try:
        acct = next(f"cx-{i}" for i in range(200)
                    if router.ring.owner(f"cx-{i}") == "r1")
        calls = []

        def cancelled(payload, timeout=None, metadata=None):
            calls.append(payload)
            raise _Cancelled()

        monkeypatch.setattr(router.replicas["r1"], "score_txn", cancelled)
        resp = txn(risk_pb2.ScoreTransactionRequest(
            account_id=acct, amount=4200, transaction_type="deposit"), timeout=30)
        assert 0 <= resp.score <= 100
        assert len(calls) == 1 and router.stats["retries"] == 1
    finally:
        ch.close()
        router.close()
        server.stop(0)


def test_replica_kill_mid_load_only_ok_or_unavailable():
    """SIGKILL-shaped failover: one replica dies under load. Every client
    outcome is OK (router retried onto the next ring owner) or
    UNAVAILABLE; the dead replica is evicted from the ring within the
    detection bound; failed-over accounts score bit-exact."""
    replicas = [_Replica(f"r{i}") for i in range(3)]
    router, server, addr = _router_over(
        replicas, hedge=False, health_interval_s=0.1, failure_threshold=2)
    ch, txn, _ = _stubs(addr)
    victim = replicas[1]
    try:
        accounts = [f"kill-{i}" for i in range(24)]
        victim_accounts = [a for a in accounts
                           if router.ring.owner(a) == victim.rid]
        assert victim_accounts  # the kill must actually strand accounts

        baseline = {}
        for acct in accounts:
            baseline[acct] = txn(risk_pb2.ScoreTransactionRequest(
                account_id=acct, amount=4200, transaction_type="deposit"),
                timeout=10).score

        outcomes: list[str] = []
        lock = threading.Lock()
        stop = time.monotonic() + 3.0
        kill_at = time.monotonic() + 0.8

        def load(worker: int) -> None:
            i = worker
            while time.monotonic() < stop:
                acct = accounts[i % len(accounts)]
                try:
                    txn(risk_pb2.ScoreTransactionRequest(
                        account_id=acct, amount=4200,
                        transaction_type="deposit"), timeout=30)
                    out = "OK"
                except grpc.RpcError as exc:
                    out = exc.code().name
                with lock:
                    outcomes.append(out)
                i += 1

        threads = [threading.Thread(target=load, args=(w,)) for w in range(4)]
        for t in threads:
            t.start()
        time.sleep(max(0.0, kill_at - time.monotonic()))
        t_kill = time.monotonic()
        victim.kill()
        for t in threads:
            t.join()

        bad = {o for o in outcomes} - {"OK", "UNAVAILABLE"}
        assert not bad, f"non-failover outcomes surfaced: {bad}"
        assert outcomes.count("OK") > 0.9 * len(outcomes), (
            "failover should absorb most of the kill: "
            f"{outcomes.count('OK')}/{len(outcomes)} OK")
        # Ring evicted the victim, quickly.
        assert victim.rid not in router.ring.active
        evicted_at = next(
            t for (t, rid, _o, new) in router.watcher.events
            if rid == victim.rid and new == "dead")
        # timing-ok: 10x the watcher's own 0.1 s x 2 detection timer
        assert evicted_at - t_kill < 2.0
        # Post-kill: stranded accounts answer from the secondary owner,
        # bit-exact (identical params + empty history — a wrong-replica
        # answer would still be EQUAL; what this pins is that failover
        # yields a real scored answer, not an error or a zero row).
        for acct in victim_accounts:
            resp = txn(risk_pb2.ScoreTransactionRequest(
                account_id=acct, amount=4200, transaction_type="deposit"),
                timeout=10)
            assert resp.score == baseline[acct]
            assert router.ring.owner(acct) != victim.rid
        # Retries actually happened (the kill window was absorbed).
        assert router.stats["retries"] > 0
    finally:
        ch.close()
        router.close()
        server.stop(0)
        for r in replicas:
            r.close()


def test_health_not_serving_evicts_and_recovery_readmits(fleet3):
    router, server, addr = _router_over(fleet3, hedge=False,
                                        health_interval_s=0.05)
    try:
        target = fleet3[2]
        assert target.rid in router.ring.active
        # Supervisor BROWNOUT shape: health flips NOT_SERVING.
        target.health.set("", NOT_SERVING)
        deadline = time.monotonic() + 60.0
        while target.rid in router.ring.active and time.monotonic() < deadline:
            time.sleep(0.02)
        assert target.rid not in router.ring.active
        assert router.replicas[target.rid].state == "brownout"
        assert router.metrics.ring_replicas.value(state="brownout") == 1
        # Recovery: SERVING again -> readmitted.
        target.health.set("", SERVING)
        deadline = time.monotonic() + 60.0
        while target.rid not in router.ring.active and time.monotonic() < deadline:
            time.sleep(0.02)
        assert target.rid in router.ring.active
        assert router.metrics.ring_replicas.value(state="serving") == 3
    finally:
        router.close()
        server.stop(0)


def test_hedge_straggler_secondary_wins_loser_cancelled():
    """Primary owner stalls past the hedge deadline; the deterministic
    secondary answers; the hedge wins and is accounted exactly once."""
    import random

    fast = [_Replica(f"r{i}") for i in (0, 1)]
    slow = _Replica("r2", engine=_SlowEngine(_engine(), delay_s=1.0))
    replicas = fast + [slow]
    latency = LatencyWindow(default_ms=60.0, min_samples=10_000)
    router, server, addr = _router_over(
        replicas, hedge=True, latency=latency, rng=random.Random(3))
    ch, txn, _ = _stubs(addr)
    try:
        acct = next(f"hedge-{i}" for i in range(200)
                    if router.ring.owner(f"hedge-{i}") == "r2")
        secondary = router.ring.owners(acct, 2)[1]
        resp = txn(risk_pb2.ScoreTransactionRequest(
            account_id=acct, amount=900, transaction_type="bet"), timeout=30)
        assert 0 <= resp.score <= 100
        # The hedge answered before the 1 s straggler did: it won the
        # race, and the primary never did.
        assert router.stats["hedges_launched"] == 1
        assert router.stats["hedge_wins"] == 1
        assert router.stats["primary_wins"] == 0
        assert router.stats["hedges_both_failed"] == 0
        m = router.metrics.hedge_total
        assert m.value(outcome="launched") == 1
        assert m.value(outcome="win_hedge") == 1
        assert m.value(outcome="win_primary") == 0
        # Exactly-once terminal accounting.
        assert (m.value(outcome="win_hedge") + m.value(outcome="win_primary")
                + m.value(outcome="both_failed")) == m.value(outcome="launched")
        # The winner really was the secondary owner's replica.
        sec_rep = next(r for r in replicas if r.rid == secondary)
        assert sec_rep.service.metrics.txns_scored_total.value() >= 1
    finally:
        ch.close()
        router.close()
        server.stop(0)
        for r in replicas:
            r.close()


def test_hedge_primary_still_wins_when_it_finishes_first():
    """A mildly slow primary crosses the hedge deadline but beats the
    (slower) secondary: win_primary, hedge cancelled, one outcome."""
    import random

    mild = _Replica("r0", engine=_SlowEngine(_engine(), delay_s=0.25))
    worse = _Replica("r1", engine=_SlowEngine(_engine(), delay_s=2.0))
    replicas = [mild, worse]
    latency = LatencyWindow(default_ms=50.0, min_samples=10_000)
    router, server, addr = _router_over(
        replicas, hedge=True, latency=latency, rng=random.Random(3))
    ch, txn, _ = _stubs(addr)
    try:
        acct = next(f"phw-{i}" for i in range(200)
                    if router.ring.owner(f"phw-{i}") == "r0")
        resp = txn(risk_pb2.ScoreTransactionRequest(
            account_id=acct, amount=700, transaction_type="deposit"),
            timeout=10)
        assert 0 <= resp.score <= 100
        assert router.stats["hedges_launched"] == 1
        assert router.stats["primary_wins"] == 1
        assert router.stats["hedge_wins"] == 0
        m = router.metrics.hedge_total
        assert (m.value(outcome="win_hedge") + m.value(outcome="win_primary")
                + m.value(outcome="both_failed")) == m.value(outcome="launched")
    finally:
        ch.close()
        router.close()
        server.stop(0)
        for r in replicas:
            r.close()


def test_load_gen_retry_helper_honors_pushback(monkeypatch):
    """The satellite fix: the client retry path consumes the server's
    grpc-retry-pushback-ms hint (PR 5 emitted it; no in-tree client
    respected it) with a jittered bounded sleep, counted in the stats."""
    from tools.drills import load_gen
    from tools.drills.load_gen import _RetryStats, _call_with_retry

    calls = {"n": 0}

    class _FlakyService:
        def ScoreBatch(self, request, context):
            calls["n"] += 1
            if calls["n"] <= 2:
                context.set_trailing_metadata(
                    (("grpc-retry-pushback-ms", "30"),))
                context.abort(grpc.StatusCode.UNAVAILABLE,
                              "shed with pushback")
            return request  # echo (identity serializers)

    from concurrent import futures as _futures
    svc = _FlakyService()
    server = grpc.server(_futures.ThreadPoolExecutor(max_workers=4))
    handler = grpc.method_handlers_generic_handler("risk.v1.RiskService", {
        "ScoreBatch": grpc.unary_unary_rpc_method_handler(
            svc.ScoreBatch,
            request_deserializer=lambda b: b,
            response_serializer=lambda b: b),
    })
    server.add_generic_rpc_handlers((handler,))
    port = server.add_insecure_port("[::]:0")
    server.start()
    ch = grpc.insecure_channel(f"localhost:{port}")
    call = ch.unary_unary("/risk.v1.RiskService/ScoreBatch",
                          request_serializer=lambda b: b,
                          response_deserializer=lambda b: b)
    try:
        slept: list[float] = []
        real_sleep = time.sleep

        def recording_sleep(seconds: float) -> None:
            if threading.current_thread() is threading.main_thread():
                slept.append(seconds)
            real_sleep(seconds)

        monkeypatch.setattr(load_gen.time, "sleep", recording_sleep)
        stats = _RetryStats()
        out = _call_with_retry([call], b"payload", (), stats,
                               np.random.default_rng(0))
        monkeypatch.undo()
        assert out == b"payload"
        assert calls["n"] == 3
        assert stats.retries == 2
        assert stats.pushback_honored == 2
        # Two honored 30 ms hints, each slept once, jittered 0.5x-1.5x.
        assert len(slept) == 2 and all(0.015 <= s < 0.045 for s in slept), slept
    finally:
        ch.close()
        server.stop(0)


def test_router_emits_route_attempt_spans_in_client_trace(fleet3):
    """Satellite (ISSUE 8): the router participates in the client's
    trace — `router.route` and `router.attempt` spans carry the client's
    trace id, and the replica's rpc span parents under the router's
    attempt (one fleet-wide trace, router time visible as a stage)."""
    from igaming_platform_tpu.obs import tracing

    router, server, addr = _router_over(fleet3, hedge=False)
    ch, txn, _ = _stubs(addr)
    client_trace = "ab" * 16
    client_span = "cd" * 8
    try:
        tracing.DEFAULT_COLLECTOR.drain()
        txn(risk_pb2.ScoreTransactionRequest(
            account_id="traced-acct", amount=100,
            transaction_type="deposit"),
            metadata=(("traceparent",
                       f"00-{client_trace}-{client_span}-01"),),
            timeout=10)
        spans = tracing.DEFAULT_COLLECTOR.drain()
        in_trace = [s for s in spans if s.trace_id == client_trace]
        names = {s.name for s in in_trace}
        assert "router.route" in names
        assert "router.attempt" in names
        # Router + replica rpc roots both adopted the client trace.
        rpc_spans = [s for s in in_trace
                     if s.name == "rpc.ScoreTransaction"]
        assert len(rpc_spans) == 2
        attempt = next(s for s in in_trace if s.name == "router.attempt")
        route = next(s for s in in_trace if s.name == "router.route")
        # attempt nests under route; the REPLICA's rpc span parents
        # under the router's attempt span (cross-process contract,
        # exercised in-process here).
        assert attempt.parent_id == route.span_id
        replica_rpc = next(s for s in rpc_spans
                           if s.parent_id == attempt.span_id)
        assert replica_rpc.attributes.get("code") == "OK"
        assert attempt.attributes.get("replica") in {"r0", "r1", "r2"}
    finally:
        ch.close()
        router.close()
        server.stop(0)
