"""The five BASELINE.json benchmark configs plus platform-path configs,
as callable measurements.

Each function returns a JSON-able dict with a ``metric``/``value``/``unit``
triple (plus detail fields). `bench.py` at the repo root is the driver's
headline metric; this module measures the full matrix:

1. single-txn ScoreTransaction latency through the continuous batcher
   (the ONNX-CPU single-sample baseline path, engine.go:262-323);
2. batched fraud scoring over a 10k-txn event replay (RabbitMQ trace);
3. bonus-abuse sequence detection throughput;
4. LTV batch prediction over a player table;
5. DP multi-task training throughput;
6. wallet money-op pipeline throughput (the platform hot path,
   wallet_service.go:351-462), store-only and with the risk gate.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rate(count: float, step_s: float):
    """count/step rounded — or None when the step was below the timing
    fence's resolution (device_step_time returned NaN); publishing a
    number there would be fiction."""
    if step_s != step_s or step_s <= 0:
        return None
    return round(count / step_s, 1)


def _engine_util(engine, n_rows: int, seconds_per_batch: float) -> dict:
    """hbm_util/achieved rate fields for a scoring-engine bench line."""
    import jax

    from igaming_platform_tpu.obs.perfmodel import utilization

    util = utilization(engine.step_cost(n_rows), seconds_per_batch, jax.devices()[0])
    return {"hbm_util": util["hbm_util"],
            "achieved_hbm_gbps": util["achieved_hbm_gbps"]}


def config1_single_txn_latency(n_requests: int = 200, batch_size: int = 256) -> dict:
    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

    engine = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=batch_size, max_wait_ms=1.0))
    try:
        lat = []
        for i in range(n_requests):
            t0 = time.perf_counter()
            engine.score(ScoreRequest(f"acct-{i % 32}", amount=1000 + i, tx_type="deposit"))
            lat.append((time.perf_counter() - t0) * 1000.0)
        lat = np.array(lat[10:])  # drop warm-up

        # Device-step latency for the same compiled program, measured
        # separately: the end-to-end number is device step + batching
        # window + readback.
        import jax

        from igaming_platform_tpu.core.features import NUM_FEATURES
        from igaming_platform_tpu.obs.perfmodel import device_step_time

        x = np.zeros((batch_size, NUM_FEATURES), dtype=np.float32)
        bl = np.zeros((batch_size,), dtype=bool)
        # Two-point readback-fenced step time
        # (obs/perfmodel.device_step_time).
        step_s = device_step_time(engine.score_arrays, x, bl)
        step_ms = round(step_s * 1e3, 3) if step_s == step_s else None
        return {
            "metric": "single_txn_score_latency_p99_ms",
            "value": round(float(np.percentile(lat, 99)), 3),
            "unit": "ms",
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
            "device_step_ms": step_ms,
            "requests": int(lat.size),
            # Ensemble-step utilization at this shape ([B,30] is
            # bandwidth-bound: hbm_util is the meaningful figure).
            **_engine_util(engine, batch_size, step_s),
        }
    finally:
        engine.close()


def config2_replay_throughput(
    n_events: int = 10_000, batch_size: int = 4096, pipeline_depth: int = 8,
    store_max_accounts: int | None = None,
) -> dict:
    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.serve.bridge import ScoringBridge
    from igaming_platform_tpu.serve.events import default_broker, new_transaction_event
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    rng = np.random.default_rng(0)
    tx_types = ("deposit", "withdraw", "bet")

    def make_events(n: int, tag: str) -> list:
        return [
            new_transaction_event("transaction.completed", {
                "id": f"{tag}{i}",
                "account_id": f"acct-{int(rng.integers(0, 500))}",
                "type": tx_types[int(rng.integers(0, 3))],
                "amount": int(rng.integers(100, 100_000)),
                "status": "completed",
            })
            for i in range(n)
        ]

    from igaming_platform_tpu.serve.native_store import (
        DEFAULT_MAX_ACCOUNTS,
        best_feature_store,
    )

    engine = TPUScoringEngine(
        batcher_config=BatcherConfig(batch_size=batch_size, max_wait_ms=1.0),
        feature_store=best_feature_store(
            max_accounts=store_max_accounts or DEFAULT_MAX_ACCOUNTS),
    )
    bridge = ScoringBridge(engine, default_broker(), publish_risk_events=False)
    try:
        # Warm the transfer pipeline (device program is already AOT-warmed
        # at engine startup; the first few D2H readbacks establish the
        # transfer path) — the measured replay is the steady serving state.
        bridge.replay(make_events(4 * batch_size, "w"), batch_size=batch_size,
                      pipeline_depth=pipeline_depth)
        stats = bridge.replay(make_events(n_events, "t"), batch_size=batch_size,
                              pipeline_depth=pipeline_depth)
        return {
            "metric": "replay_fraud_score_txns_per_sec",
            "value": round(stats["txns_per_sec"], 1),
            "unit": "txns/s",
            "events": stats["events_scored"],
            "blocked": stats["blocked"],
            # Device utilization ACROSS the replay (includes host gaps —
            # how hard the chip worked for the e2e figure, not peak step).
            **_engine_util(engine, batch_size,
                           batch_size / max(stats["txns_per_sec"], 1e-9)),
        }
    finally:
        engine.close()


def config3_sequence_throughput(batch: int = 64, seq_len: int = 256, iters: int = 20,
                                long_s: int = 2048) -> dict:
    import jax

    from igaming_platform_tpu.models.sequence import (
        EVENT_DIM,
        SeqConfig,
        init_sequence_model,
        sequence_forward,
    )

    # 2 wide heads (MXU-width economics, serve/abuse.py): 4.6x the
    # measured long-context rate of the old 8x16 shape on v5e.
    cfg = SeqConfig(d_model=128, n_heads=2, n_layers=2, d_ff=256)
    params = init_sequence_model(jax.random.key(0), cfg)
    fn = jax.jit(lambda p, x: sequence_forward(p, x, cfg)["abuse"])

    # ALL step timings here are two-point readback-fenced
    # (obs/perfmodel.device_step_time): a loop of async dispatches
    # times the enqueue, not the work. Throughput = 1/step:
    # per-device execution is serial, so overlapped dispatch does not
    # add device throughput — only honest step time counts.
    from igaming_platform_tpu.obs.perfmodel import (
        cost_of,
        device_step_time,
        utilization,
    )

    x = np.random.default_rng(0).normal(size=(batch, seq_len, EVENT_DIM)).astype(np.float32)
    step_short = device_step_time(fn, params, jax.device_put(x), n=max(9, iters // 2))

    # Long-context point: S=2048 event histories through the Pallas
    # flash-attention core (BASELINE config 3's long-sequence story) —
    # smaller batch, same model. Reported alongside the short-seq figure.
    long_batch = max(8, batch // 8)
    x_long = np.random.default_rng(1).normal(
        size=(long_batch, long_s, EVENT_DIM)
    ).astype(np.float32)
    x_long_dev = jax.device_put(x_long)
    step_long = device_step_time(fn, params, x_long_dev, n=9)

    from igaming_platform_tpu.ops.pallas.flash_attention import supports as flash_supports

    # Extra-long point: S=8192 (32x the short config) — the "event
    # histories longer than one chip's HBM slice would allow densely"
    # regime the flash kernel exists for. TPU-only by default: the CPU
    # einsum fallback would time an S^2 matmul instead of the kernel.
    xlong_s = int(os.environ.get("BENCH_SEQ_XLONG_S", 8192))
    xlong: dict = {}
    if xlong_s and (jax.default_backend() == "tpu"
                    or os.environ.get("BENCH_SEQ_XLONG_FORCE") == "1"):
        xb = 2
        x_xl = np.random.default_rng(2).normal(
            size=(xb, xlong_s, EVENT_DIM)).astype(np.float32)
        step_xl = device_step_time(fn, params, jax.device_put(x_xl), n=5)
        xlong = {
            "xlong_seq_len": xlong_s,
            "xlong_batch": xb,
            "xlong_tokens_per_sec": _rate(xb * xlong_s, step_xl),
        }

    # MFU at the long-context point — the regime the flash kernel exists
    # for; the short config is dispatch-bound and would under-read.
    flash_active = jax.default_backend() == "tpu" and flash_supports(
        (long_s, cfg.d_model // cfg.n_heads))
    cost = cost_of(fn, params, x_long)
    # Analytic transformer FLOPs (qkvo projections + attention
    # scores/values + FFN, forward only): XLA cost analysis cannot see
    # inside a Pallas custom call, so whenever the flash kernel ran the
    # visible-op count is missing the DOMINANT attention term — use the
    # analytic model then, and also when cost analysis returns nothing.
    B, S = x_long.shape[0], x_long.shape[1]
    d, dff, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    analytic = float(
        L * (8 * B * S * d * d + 4 * B * S * S * d + 4 * B * S * d * dff)
    )
    if flash_active or cost["flops"] <= 0:
        cost["flops"] = analytic
    util = utilization(cost, step_long, jax.devices()[0])
    # On the CPU backend the transformer is the known ~75 seq/s collapse
    # the serving layer never exposes: ABUSE_CPU_POLICY=heuristic serves
    # scalar signals instead. Measure that path here so the artifact
    # carries the number the deployment would actually see.
    cpu_policy: dict = {}
    if jax.default_backend() != "tpu":
        from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector

        det = SequenceAbuseDetector(policy="heuristic")
        rng_h = np.random.default_rng(5)
        # Histories shaped like real bonus-abuse traffic — grant, rapid
        # low-weight wagering, withdraw — so the measurement includes the
        # heuristic's most expensive branch (the grants x withdraws
        # quick-cashout gap matrix), not just the cheap aggregate path.
        n_accounts = max(8, batch)
        for a in range(n_accounts):
            t = 1_000_000.0
            det.record_event(f"h-{a}", 5_000, "bonus_grant", timestamp=t)
            for _ in range(20):
                t += float(rng_h.integers(2, 30))
                det.record_event(f"h-{a}", int(rng_h.integers(100, 50_000)),
                                 ("bet", "bonus_wager")[int(rng_h.integers(0, 2))],
                                 game_weight=float(rng_h.random()), timestamp=t)
            det.record_event(f"h-{a}", 9_000, "withdraw", timestamp=t + 5.0)
        accounts = [f"h-{a}" for a in range(n_accounts)] * 4
        det.check_batch(accounts)  # warm
        h_iters = max(4, iters)
        t0 = time.perf_counter()
        for _ in range(h_iters):
            det.check_batch(accounts)
        cpu_policy["cpu_heuristic_checks_per_sec"] = round(
            len(accounts) * h_iters / (time.perf_counter() - t0), 1)

    return {
        "metric": "abuse_sequences_per_sec",
        "value": _rate(batch, step_short),
        "unit": "seq/s",
        "seq_len": seq_len,
        "batch": batch,
        **cpu_policy,
        "long_seq_len": long_s,
        "long_batch": long_batch,
        "long_sequences_per_sec": _rate(long_batch, step_long),
        "long_tokens_per_sec": _rate(long_batch * long_s, step_long),
        "long_mfu": util["mfu"],
        "long_achieved_tflops": util["achieved_tflops"],
        **xlong,
        # True only when the Pallas kernel actually ran: dispatch also
        # gates on the TPU backend (sequence.py takes the XLA einsum path
        # elsewhere), so a CPU run must not attribute its number to flash.
        "flash_kernel": bool(
            jax.default_backend() == "tpu"
            and flash_supports((long_s, cfg.d_model // cfg.n_heads))
        ),
    }


def config4_ltv_batch_throughput(rows: int = 100_000, iters: int = 10) -> dict:
    import jax

    from igaming_platform_tpu.models.ltv import NUM_LTV_FEATURES, predict_batch_jit
    from igaming_platform_tpu.obs.perfmodel import cost_of, utilization

    from igaming_platform_tpu.obs.perfmodel import device_step_time

    x = np.random.default_rng(0).random((rows, NUM_LTV_FEATURES)).astype(np.float32) * 100
    # Batch-JOB shape, two-point readback-fenced: device_step_time with a
    # HOST-resident batch times H2D + predict per iteration, fenced by a
    # real result readback — what the LTV job does per scan chunk. Pure
    # device compute here is ~microseconds (elementwise over [N,17]),
    # BELOW the fence's timing noise (a compute-only "step" would
    # publish a nonsense rate); the transfer-inclusive figure
    # is the honest one (the job is IO-bound).
    step = device_step_time(predict_batch_jit, x, n=max(4, iters // 2), reps=3)
    util = utilization(cost_of(predict_batch_jit, x), step, jax.devices()[0])
    return {
        "metric": "ltv_predictions_per_sec",
        "value": _rate(rows, step),
        "unit": "players/s",
        "rows": rows,
        "hbm_util": util["hbm_util"],
        "achieved_hbm_gbps": util["achieved_hbm_gbps"],
    }


def config5_training_throughput(steps: int = 30, batch_size: int = 4096) -> dict:
    """DP training throughput with the production input pipeline:
    double-buffered H2D prefetch, no per-step metric readback (five
    scalar readbacks plus a synchronous H2D per step stall the dispatch
    queue every step). Reports a per-stage
    breakdown (h2d / device step / readback) and MFU so the figure is
    normalized, not just a throughput sample."""
    import jax

    from igaming_platform_tpu.obs.perfmodel import utilization
    from igaming_platform_tpu.train.data import make_stream
    from igaming_platform_tpu.train.trainer import TrainConfig, Trainer

    cfg = TrainConfig(batch_size=batch_size)
    trainer = Trainer(cfg)
    data = make_stream(batch_size, seed=0)
    first = next(data)
    trainer.train_step(first)  # compile
    cost = trainer.step_cost(first)

    # Stage breakdown, all two-point readback-fenced
    # (obs/perfmodel.device_step_time). H2D: slope over k queued batch
    # transfers, fenced by a scalar reduce of the LAST batch (transfers
    # are in-order per device, the fence's latency cancels in the slope).
    import jax.numpy as jnp

    h2d_batch = next(data)
    probe = jax.jit(lambda b: sum(jnp.sum(t.astype(jnp.float32)) for t in b))

    def h2d_total(k: int) -> float:
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(k - 1):
                trainer.put_batch(h2d_batch)
            jax.device_get(probe(trainer.put_batch(h2d_batch)))
            best = min(best, time.perf_counter() - t0)
        return best

    jax.device_get(probe(trainer.put_batch(h2d_batch)))  # warm
    h2d_ms = max(h2d_total(5) - h2d_total(1), 1e-9) / 4 * 1e3

    # Device step: device-resident inputs, two-point fenced on the packed
    # metrics (a real step each call — state advances; that is the point).
    from igaming_platform_tpu.obs.perfmodel import device_step_time

    dev_batch = trainer.put_batch(next(data))
    step_s = device_step_time(
        trainer.train_step_device, dev_batch, n=max(9, steps // 3))
    step_ms = round(step_s * 1e3, 3) if step_s == step_s else None

    # Readback: one packed metrics transfer (a real D2H). The step must
    # FINISH first (untimed device_get completes it) or the "readback"
    # would include a whole device step.
    m = trainer.train_step_device(dev_batch)
    jax.device_get(m)
    t0 = time.perf_counter()
    jax.device_get(m)
    readback_ms = (time.perf_counter() - t0) * 1e3

    # End-to-end: the double-buffered fit loop (H2D overlapped, one
    # readback at the end).
    t0 = time.perf_counter()
    metrics = trainer.fit(steps, data=data)
    elapsed = time.perf_counter() - t0

    util = utilization(cost, elapsed / steps, jax.devices()[0])
    return {
        "metric": "train_samples_per_sec",
        "value": round(steps * batch_size / elapsed, 1),
        "unit": "samples/s",
        "steps_per_sec": round(steps / elapsed, 2),
        "final_loss": round(metrics["loss"], 4),
        "h2d_ms": round(h2d_ms, 3),
        "device_step_ms": step_ms,
        "metrics_readback_ms": round(readback_ms, 3),
        "step_flops": cost["flops"],
        "mfu": util["mfu"],
        "achieved_tflops": util["achieved_tflops"],
        "hbm_util": util["hbm_util"],
    }


def config0_grpc_e2e(wire_mode: str = "row") -> dict:
    """End-to-end ScoreBatch over a real gRPC socket (the headline path —
    see benchmarks/load_gen.py and bench.py). ``wire_mode='index'`` runs
    the device-resident feature-cache arm: the client ships index-mode
    frames and the device gathers rows from the HBM table
    (serve/device_cache.py) — no per-RPC feature matrix on the link.

    The artifact line carries a ``stage_breakdown`` block aggregated from
    the in-process flight recorder (obs/flight.py): per-stage p50/p99 for
    the last N ScoreBatch RPCs plus ``stage_coverage_p50`` — what share
    of the RPC span's duration the stage spans account for (the "where
    did the latency go" figure the link-bound-vs-device question needs)."""
    from load_gen import run_grpc_load, run_single_txn_probe, start_inprocess_server

    from igaming_platform_tpu.obs.flight import DEFAULT_RECORDER, stage_breakdown

    addr, shutdown, engine = start_inprocess_server(batch_size=8192)
    try:
        DEFAULT_RECORDER.clear()  # warm-up RPCs out of the breakdown window
        load = run_grpc_load(addr, duration_s=6.0, rows_per_rpc=8192,
                             concurrency=6, wire_mode=wire_mode)
        load["stage_breakdown"] = stage_breakdown(
            DEFAULT_RECORDER.snapshot(), method="ScoreBatch")
        pipeline = getattr(engine, "pipeline", None)
        if pipeline is not None:
            stats = pipeline.stats()
            load["pipeline_inflight_depth"] = stats["depth"]
            load["pipeline_max_inflight"] = stats["max_inflight"]
            load["host_stage_overlap_ratio"] = stats["overlap_ratio"]
        probe = run_single_txn_probe(addr, n=120)
        load["single_txn_p99_ms"] = probe["value"]
        load["single_txn_p50_ms"] = probe["p50_ms"]
        return load
    finally:
        shutdown()


def config0_grpc_e2e_index() -> dict:
    """The index-mode wire arm of the headline path (HBM feature cache)."""
    return config0_grpc_e2e(wire_mode="index")


class _DirectWalletClient:
    """The deposit/bet/win verbs against an in-process WalletService."""

    def __init__(self, wallet, tid: int):
        self._w = wallet
        self._tid = tid
        self._account_id = ""

    def create_and_seed(self) -> None:
        acct = self._w.create_account(f"bench-{self._tid}")
        self._w.deposit(acct.id, 10_000_000, f"seed-{self._tid}")
        self._account_id = acct.id

    def deposit(self, amount: int, key: str) -> None:
        self._w.deposit(self._account_id, amount, key)

    def bet(self, amount: int, key: str, game_id: str, round_id: str) -> None:
        self._w.bet(self._account_id, amount, key, game_id=game_id, round_id=round_id)

    def win(self, amount: int, key: str, game_id: str, round_id: str) -> None:
        self._w.win(self._account_id, amount, key, game_id=game_id, round_id=round_id)

    def close(self) -> None:
        pass


class _WireWalletClient:
    """The same verbs over a real wallet.v1 gRPC socket (bounded
    deadlines so a stalled handler cannot hang the harness)."""

    _TIMEOUT_S = 30

    def __init__(self, addr: str, tid: int):
        import grpc

        from igaming_platform_tpu.serve.grpc_server import make_wallet_stub

        self._ch = grpc.insecure_channel(addr)
        self._stub = make_wallet_stub(self._ch)
        self._tid = tid
        self._account_id = ""

    def create_and_seed(self) -> None:
        from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2

        acct = self._stub.CreateAccount(
            wallet_pb2.CreateAccountRequest(player_id=f"wire-{self._tid}"),
            timeout=self._TIMEOUT_S).account
        self._stub.Deposit(wallet_pb2.DepositRequest(
            account_id=acct.id, amount=10_000_000,
            idempotency_key=f"seed-{self._tid}"), timeout=self._TIMEOUT_S)
        self._account_id = acct.id

    def deposit(self, amount: int, key: str) -> None:
        from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2

        self._stub.Deposit(wallet_pb2.DepositRequest(
            account_id=self._account_id, amount=amount, idempotency_key=key),
            timeout=self._TIMEOUT_S)

    def bet(self, amount: int, key: str, game_id: str, round_id: str) -> None:
        from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2

        self._stub.Bet(wallet_pb2.BetRequest(
            account_id=self._account_id, amount=amount, idempotency_key=key,
            game_id=game_id, round_id=round_id), timeout=self._TIMEOUT_S)

    def win(self, amount: int, key: str, game_id: str, round_id: str) -> None:
        from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2

        self._stub.Win(wallet_pb2.WinRequest(
            account_id=self._account_id, amount=amount, idempotency_key=key,
            game_id=game_id, round_id=round_id), timeout=self._TIMEOUT_S)

    def close(self) -> None:
        self._ch.close()


def _wallet_mix(make_client, n_threads: int, cycles: int):
    """Drive the deposit -> bet -> win op mix (unique idempotency keys,
    per-thread accounts) from n_threads workers against any client with
    the verbs above; returns (latencies_ms, errors, wall_s). The seed
    phase counts toward errors too — a worker that cannot seed reports
    itself instead of silently shrinking the op count."""
    import threading

    errors = [0]
    lat: list[float] = []
    lock = threading.Lock()

    def worker(tid: int) -> None:
        client = make_client(tid)
        my_lat = []
        try:
            try:
                client.create_and_seed()
            except Exception:  # noqa: BLE001 — counted, fails loudly in artifacts
                with lock:
                    errors[0] += 1
                return
            for i in range(cycles):
                ops = [
                    lambda: client.deposit(2_000 + i, f"d-{tid}-{i}"),
                    lambda: client.bet(100 + (i % 50), f"b-{tid}-{i}", "slots-1", f"r{i}"),
                    lambda: client.win(150, f"w-{tid}-{i}", "slots-1", f"r{i}"),
                ]
                for op in ops:
                    t0 = time.perf_counter()
                    try:
                        op()
                    except Exception:  # noqa: BLE001 — counted
                        with lock:
                            errors[0] += 1
                        continue
                    my_lat.append((time.perf_counter() - t0) * 1e3)
            with lock:
                lat.extend(my_lat)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return np.array(lat), errors[0], wall


def config6_wallet_ops(n_threads: int = 8, cycles: int = 120) -> dict:
    """Money-op pipeline throughput — the reference's platform hot path
    (WalletService/Bet, SURVEY.md §3.2; wallet_service.go:351-462).

    Two figures from the same op mix (_wallet_mix):

    - ``store_ops_per_sec``: WalletService over the durable SQLite store
      with the risk gate off — tx row, optimistic-lock balance update,
      double-entry ledger, completion, and outbox staging, one unit of
      work per op. This is the store-of-record pipeline's capacity.
    - headline ``value``: the full topology — every deposit/bet scored
      through the serving engine's continuous batcher before money
      moves (the Deposit/Bet -> RiskService gate of SURVEY.md §3.1-3.2).
    """
    import tempfile

    from igaming_platform_tpu.platform.outbox import OutboxPublisher
    from igaming_platform_tpu.platform.repository import SQLiteStore
    from igaming_platform_tpu.platform.wallet import WalletService

    # The serving default is durable (synchronous=FULL); the bench opts
    # into batched fsync explicitly so the figure measures pipeline
    # capacity, not the disk's fsync floor. Production keeps FULL.
    os.environ.setdefault("SQLITE_SYNCHRONOUS", "NORMAL")
    with tempfile.TemporaryDirectory() as tmp:
        # Store-of-record pipeline only (risk gate off).
        store = SQLiteStore(os.path.join(tmp, "wallet_store.db"))
        wallet = WalletService(
            store.accounts, store.transactions, store.ledger,
            events=OutboxPublisher(store), audit=store.audit,
        )
        store_lat, store_errors, store_wall = _wallet_mix(
            lambda tid: _DirectWalletClient(wallet, tid), n_threads, cycles)
        store.close()

        # Full topology: risk gate scores deposits/bets through the
        # serving engine before money moves.
        from igaming_platform_tpu.platform.app import AppConfig, PlatformApp

        app = PlatformApp(AppConfig(sqlite_path=os.path.join(tmp, "wallet_full.db")))
        try:
            full_lat, full_errors, full_wall = _wallet_mix(
                lambda tid: _DirectWalletClient(app.wallet, tid), n_threads, cycles)
        finally:
            app.close()

    return {
        "metric": "wallet_ops_per_sec",
        "value": round(full_lat.size / full_wall, 1),
        "unit": "ops/s",
        "op_p50_ms": round(float(np.percentile(full_lat, 50)), 2),
        "op_p99_ms": round(float(np.percentile(full_lat, 99)), 2),
        "errors": full_errors,
        "store_ops_per_sec": round(store_lat.size / store_wall, 1),
        "store_op_p50_ms": round(float(np.percentile(store_lat, 50)), 2),
        "store_op_p99_ms": round(float(np.percentile(store_lat, 99)), 2),
        "store_errors": store_errors,
        "threads": n_threads,
        "ops": int(full_lat.size),
    }


def config7_wallet_wire(n_threads: int = 8, cycles: int = 100) -> dict:
    """Wallet money ops AT THE WIRE: wallet.v1 Deposit/Bet/Win over a
    real gRPC socket against serve_wallet + the durable SQLite store —
    the platform hot path measured the way clients see it (the reference
    serves this path as grpc-go handler -> service -> Postgres,
    wallet_service.go:240-549; here handler -> WalletService -> one
    SQLite unit of work per op with outbox staging). Risk gate off so
    the figure isolates the wallet wire + pipeline (config6 reports the
    risk-gated topology)."""
    import tempfile

    from igaming_platform_tpu.platform.outbox import OutboxPublisher
    from igaming_platform_tpu.platform.repository import SQLiteStore
    from igaming_platform_tpu.platform.wallet import WalletService
    from igaming_platform_tpu.serve.grpc_server import (
        WalletGrpcService,
        graceful_stop,
        serve_wallet,
    )

    os.environ.setdefault("SQLITE_SYNCHRONOUS", "NORMAL")  # bench opt-in; serving default is FULL
    with tempfile.TemporaryDirectory() as tmp:
        store = SQLiteStore(os.path.join(tmp, "wire.db"))
        wallet = WalletService(
            store.accounts, store.transactions, store.ledger,
            events=OutboxPublisher(store), audit=store.audit,
        )
        server, health, port = serve_wallet(WalletGrpcService(wallet), port=0)
        try:
            lat, errors, wall = _wallet_mix(
                lambda tid: _WireWalletClient(f"localhost:{port}", tid),
                n_threads, cycles)
        finally:
            graceful_stop(server, health, grace=5)
            store.close()

    return {
        "metric": "wallet_wire_ops_per_sec",
        "value": round(lat.size / wall, 1),
        "unit": "ops/s",
        "op_p50_ms": round(float(np.percentile(lat, 50)), 2) if lat.size else None,
        "op_p99_ms": round(float(np.percentile(lat, 99)), 2) if lat.size else None,
        "errors": errors,
        "threads": n_threads,
        "ops": int(lat.size),
    }


def config8_wallet_pg(n_threads: int = 8, cycles: int = 100) -> dict:
    """The wallet wire path on the POSTGRES backend: wallet.v1 gRPC ->
    WalletService (pooled connection-per-thread, pipelined extended-query
    batches) -> protocol-v3 wire client -> the in-tree PG server running
    as its OWN OS PROCESS (the deployment shape: the database is never a
    thread of the app server, and the bench must not charge the wallet
    for the rig's GIL time). Honest labeling via the ``backend`` field;
    the compose `stores` profile provides the real-PG variant of the same
    figure (docs/operations.md)."""
    import subprocess
    import sys
    import tempfile

    from igaming_platform_tpu.platform.outbox import OutboxPublisher
    from igaming_platform_tpu.platform.pg_store import PostgresStore
    from igaming_platform_tpu.platform.wallet import WalletService
    from igaming_platform_tpu.serve.grpc_server import (
        WalletGrpcService,
        graceful_stop,
        serve_wallet,
    )

    with tempfile.TemporaryDirectory() as tmp:
        rig_env = dict(os.environ, JAX_PLATFORMS="cpu")
        rig = subprocess.Popen(
            [sys.executable, "-m", "igaming_platform_tpu.platform.pg_testing",
             os.path.join(tmp, "wallet_pg.db")],
            stdout=subprocess.PIPE, text=True, env=rig_env,
        )
        try:
            try:
                ready = rig.stdout.readline().strip()
                port = int(ready.split("=", 1)[1])
            except (ValueError, IndexError) as exc:
                raise RuntimeError(f"pg rig failed to boot: {ready!r}") from exc
            store = PostgresStore(f"postgres://tester@127.0.0.1:{port}/wallet")
            wallet = WalletService(
                store.accounts, store.transactions, store.ledger,
                events=OutboxPublisher(store), audit=store.audit,
            )
            server, health, port = serve_wallet(WalletGrpcService(wallet), port=0)
            try:
                lat, errors, wall = _wallet_mix(
                    lambda tid: _WireWalletClient(f"localhost:{port}", tid),
                    n_threads, cycles)
            finally:
                graceful_stop(server, health, grace=5)
                store.close()
        finally:
            rig.terminate()
            try:
                rig.wait(timeout=10)
            except subprocess.TimeoutExpired:
                rig.kill()

    return {
        "metric": "wallet_pg_ops_per_sec",
        "value": round(lat.size / wall, 1),
        "unit": "ops/s",
        "backend": "pg-wire over in-tree sqlite-backed PG server",
        "op_p50_ms": round(float(np.percentile(lat, 50)), 2) if lat.size else None,
        "op_p99_ms": round(float(np.percentile(lat, 99)), 2) if lat.size else None,
        "errors": errors,
        "threads": n_threads,
        "ops": int(lat.size),
    }


ALL_CONFIGS = {
    "grpc_e2e": config0_grpc_e2e,
    "grpc_e2e_index": config0_grpc_e2e_index,
    "single_txn": config1_single_txn_latency,
    "replay": config2_replay_throughput,
    "sequence": config3_sequence_throughput,
    "ltv": config4_ltv_batch_throughput,
    "train": config5_training_throughput,
    "wallet": config6_wallet_ops,
    "wallet_wire": config7_wallet_wire,
    "wallet_pg": config8_wallet_pg,
}
