"""Pipelined serving paths: two-phase batcher + replay readback window.

The launch/readback overlap (batcher.py two-phase runners, bridge.replay
inflight deque) must not change any result — only when results become
visible. These tests pin result correctness under concurrency and the
equivalence of pipelined replay with the synchronous semantics.
"""

import threading
import time

import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig
from igaming_platform_tpu.serve.batcher import CollectorPipeline, ContinuousBatcher
from igaming_platform_tpu.serve.events import default_broker, new_transaction_event
from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine


def _make_events(n, seed=0):
    rng = np.random.default_rng(seed)
    tx_types = ("deposit", "withdraw", "bet")
    return [
        new_transaction_event(
            "transaction.completed",
            {
                "id": f"t{i}",
                "account_id": f"acct-{int(rng.integers(0, 50))}",
                "type": tx_types[int(rng.integers(0, 3))],
                "amount": int(rng.integers(100, 100_000)),
                "status": "completed",
            },
        )
        for i in range(n)
    ]


class TestCollectorPipeline:
    def test_collector_error_does_not_deadlock_producer(self):
        """If process() raises while the producer is pushing at full depth,
        put() must raise the error instead of blocking forever."""

        def process(item):
            raise RuntimeError("collector-died")

        p = CollectorPipeline(process, depth=1)
        with pytest.raises(RuntimeError, match="collector-died"):
            # First put is consumed and fails; subsequent puts must
            # surface the error promptly rather than hang.
            for i in range(50):
                p.put(i)
        p.close(raise_errors=False)

    def test_close_reraises_collector_error(self):
        def process(item):
            if item == 3:
                raise RuntimeError("late-failure")

        p = CollectorPipeline(process, depth=8)
        for i in range(4):
            p.put(i)
        with pytest.raises(RuntimeError, match="late-failure"):
            p.close()

    def test_close_idempotent_and_drains(self):
        seen = []
        p = CollectorPipeline(seen.append, depth=2)
        for i in range(10):
            p.put(i)
        p.close()
        p.close()  # second close is a no-op
        assert seen == list(range(10))

    def test_producer_abort_leaves_no_thread(self):
        """close(raise_errors=False) after a producer abort reaps the
        collector thread."""
        p = CollectorPipeline(lambda item: None, depth=2)
        p.put(1)
        p.close(raise_errors=False)
        assert not p._thread.is_alive()


class TestReplayErrorPaths:
    def test_collector_failure_propagates_and_reaps_thread(self):
        """A poisoned publish in postprocess must fail replay() rather
        than deadlock, and must not leak the collector thread."""
        from igaming_platform_tpu.serve.bridge import ScoringBridge

        engine = TPUScoringEngine(
            batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1.0)
        )
        try:
            bridge = ScoringBridge(engine, default_broker(), publish_risk_events=True)
            bridge._publish_outcomes = None  # type: ignore[assignment] — poison
            bridge.engine.set_thresholds(1, 0)  # every txn blocks -> publish path hit
            before = threading.active_count()
            with pytest.raises(TypeError):
                bridge.replay(_make_events(400), batch_size=32, pipeline_depth=2)
            time.sleep(0.2)
            assert threading.active_count() <= before
        finally:
            engine.close()


class TestTwoPhaseBatcher:
    def test_results_match_payloads(self):
        """Every future resolves to its own payload's result, in-flight
        window > 1 batch."""

        def dispatch(payloads):
            return [p * 2 for p in payloads]

        def collect(handle):
            time.sleep(0.002)  # simulate readback latency
            return handle

        b = ContinuousBatcher(
            cfg=BatcherConfig(batch_size=8, max_wait_ms=1.0, pipeline_depth=3),
            dispatch=dispatch,
            collect=collect,
        ).start()
        try:
            futs = [b.submit(i) for i in range(100)]
            assert [f.result(timeout=10) for f in futs] == [i * 2 for i in range(100)]
            assert b.batches_run >= 100 // 8
        finally:
            b.stop()

    def test_dispatch_error_propagates(self):
        def dispatch(payloads):
            raise RuntimeError("boom-dispatch")

        b = ContinuousBatcher(
            cfg=BatcherConfig(batch_size=4, max_wait_ms=1.0),
            dispatch=dispatch,
            collect=lambda h: h,
        ).start()
        try:
            with pytest.raises(RuntimeError, match="boom-dispatch"):
                b.submit(1).result(timeout=60)
        finally:
            b.stop()

    def test_collect_error_propagates(self):
        b = ContinuousBatcher(
            cfg=BatcherConfig(batch_size=4, max_wait_ms=1.0),
            dispatch=lambda p: p,
            collect=lambda h: (_ for _ in ()).throw(RuntimeError("boom-collect")),
        ).start()
        try:
            with pytest.raises(RuntimeError, match="boom-collect"):
                b.submit(1).result(timeout=60)
        finally:
            b.stop()

    def test_inflight_drained_on_stop(self):
        """Batches already dispatched still resolve after stop()."""
        release = threading.Event()

        def collect(handle):
            release.wait(timeout=60)
            return handle

        b = ContinuousBatcher(
            cfg=BatcherConfig(batch_size=4, max_wait_ms=1.0, pipeline_depth=2),
            dispatch=lambda p: p,
            collect=collect,
        ).start()
        futs = [b.submit(i) for i in range(4)]
        time.sleep(0.1)  # let the launcher dispatch
        release.set()
        b.stop()
        assert [f.result(timeout=60) for f in futs] == [0, 1, 2, 3]

    def test_requires_some_runner(self):
        with pytest.raises(ValueError):
            ContinuousBatcher(cfg=BatcherConfig())


class TestEngineBatcherPath:
    def test_concurrent_scores_coalesce_and_match_batch_path(self):
        engine = TPUScoringEngine(
            batcher_config=BatcherConfig(batch_size=32, max_wait_ms=5.0, pipeline_depth=4)
        )
        try:
            reqs = [
                ScoreRequest(f"acct-{i % 7}", amount=1000 + 137 * i, tx_type="deposit")
                for i in range(64)
            ]
            direct = engine.score_batch(list(reqs))

            results = [None] * len(reqs)

            def worker(i):
                results[i] = engine.score(reqs[i])

            threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(reqs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            for got, want in zip(results, direct):
                assert got is not None
                assert got.score == want.score
                assert got.action == want.action
                assert got.reason_codes == want.reason_codes
        finally:
            engine.close()


class TestPipelinedReplay:
    def _run(self, depth):
        from igaming_platform_tpu.serve.bridge import ScoringBridge

        engine = TPUScoringEngine(
            batcher_config=BatcherConfig(batch_size=64, max_wait_ms=1.0)
        )
        try:
            bridge = ScoringBridge(engine, default_broker(), publish_risk_events=True)
            stats = bridge.replay(_make_events(500), batch_size=64, pipeline_depth=depth)
            risk_events = sorted(
                (e.type, e.data.get("account_id"), e.data.get("score"))
                for _, e in bridge.broker.queues["risk.scoring"]
            ) if "risk.scoring" in getattr(bridge.broker, "queues", {}) else None
            return stats, risk_events
        finally:
            engine.close()

    def test_depth0_equals_depth4(self):
        """The in-flight window changes timing only, never results."""
        sync_stats, _ = self._run(depth=0)
        pipe_stats, _ = self._run(depth=4)
        assert sync_stats["events_scored"] == pipe_stats["events_scored"] == 500
        assert sync_stats["blocked"] == pipe_stats["blocked"]
