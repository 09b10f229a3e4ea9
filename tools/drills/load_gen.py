"""gRPC load generator — the client rig of the fault drills.

Drives what a client actually sends: risk.v1 ScoreBatch RPCs over a real
gRPC socket, through request decode, the (native) feature-store gather,
the compiled device step, and the native response encoder, and counts
what came back (errors by status code, sheds, retries, availability per
window). On this rig's CPU its rates and percentiles say that traffic
flowed, never how fast the system is: the benchmark is ``chipbench/``.

Run standalone:  python -m tools.drills.load_gen [addr] [--wire-mode=row|index]
(no addr: starts an in-process server on a free port with the native
feature store and the multitask backend — the production wiring).

``--wire-mode=index`` drives the device-resident feature cache: each RPC
ships the compact index-mode frame (serve/wire.py) instead of a protobuf
of full transactions, and the server's device step gathers feature rows
from the HBM-resident table — only int32 slot indices + per-txn context
cross the host->device link (serve/device_cache.py).

``--fleet=addr1,addr2,...`` drives a scoring FLEET through the
client-side account-affinity picker (serve/router.py
AccountAffinityPicker): accounts partition by consistent hash so each
replica's device cache holds a disjoint hot set, every RPC goes wholly
to its owner, and UNAVAILABLE fails over to the next ring owner.

Retry discipline (both modes): an UNAVAILABLE carrying the server's
``grpc-retry-pushback-ms`` trailing hint (the supervisor watchdog's
standard backoff signal, PR 5) is honored — jittered sleep of the hinted
duration, then a bounded retry — and counted in the artifact
(``pushback_honored``). Before this, the hint was emitted but no in-tree
client respected it.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import uuid

import numpy as np

import grpc

from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2


def _build_request_payloads(
    rows_per_rpc: int, n_variants: int = 4, n_accounts: int = 512,
    amount_mult: float = 1.0, amount_shift: float = 0.0,
) -> list[bytes]:
    """Pre-serialized ScoreBatchRequests (client-side proto cost is not the
    thing under test; rotating variants keeps the account mix realistic).
    ``amount_mult``/``amount_shift`` apply a drift-ramp phase's transform
    to the transaction amounts — same seed, so phase k of two identical
    runs carries byte-identical payloads (deterministic injection)."""
    rng = np.random.default_rng(7)
    tx_types = ("deposit", "bet", "withdraw")
    payloads = []
    for v in range(n_variants):
        txs = [
            risk_pb2.ScoreTransactionRequest(
                account_id=f"lg-{int(rng.integers(0, n_accounts))}",
                amount=max(1, int(int(rng.integers(100, 100_000))
                                  * amount_mult + amount_shift)),
                transaction_type=tx_types[int(rng.integers(0, 3))],
                ip_address=f"10.{v}.{i % 200}.{i % 251}",
                device_id=f"dev-{int(rng.integers(0, 64))}",
            )
            for i in range(rows_per_rpc)
        ]
        payloads.append(risk_pb2.ScoreBatchRequest(transactions=txs).SerializeToString())
    return payloads


def _build_index_payloads(
    rows_per_rpc: int, n_variants: int = 4, n_accounts: int = 512,
    amount_mult: float = 1.0, amount_shift: float = 0.0,
) -> list[bytes]:
    """Pre-serialized index-mode frames — the SAME account/amount/type mix
    as the protobuf payloads, encoded as compact columns."""
    from igaming_platform_tpu.serve.wire import encode_index_batch

    rng = np.random.default_rng(7)
    tx_types = ("deposit", "bet", "withdraw")
    payloads = []
    for v in range(n_variants):
        payloads.append(encode_index_batch(
            [f"lg-{int(rng.integers(0, n_accounts))}" for _ in range(rows_per_rpc)],
            [max(1, int(int(rng.integers(100, 100_000))
                        * amount_mult + amount_shift))
             for _ in range(rows_per_rpc)],
            [tx_types[int(rng.integers(0, 3))] for _ in range(rows_per_rpc)],
            ips=[f"10.{v}.{i % 200}.{i % 251}" for i in range(rows_per_rpc)],
            devices=[f"dev-{int(rng.integers(0, 64))}" for i in range(rows_per_rpc)],
        ))
    return payloads


def _pushback_ms(exc: "grpc.RpcError") -> int | None:
    """The server's standard retry hint off the trailing metadata, or
    None when the failure carries no hint."""
    try:
        trailing = exc.trailing_metadata() or ()
    except Exception:  # noqa: BLE001 — a dead channel may carry no metadata
        return None
    for key, value in trailing:
        if key == "grpc-retry-pushback-ms":
            try:
                return max(0, int(value))
            except ValueError:
                return None
    return None


class _RetryStats:
    """Shared retry accounting across worker threads (artifact fields)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.retries = 0
        self.pushback_honored = 0
        self.failovers = 0


def _call_with_retry(calls, payload: bytes, metadata, stats: _RetryStats,
                     rng: "np.random.Generator", timeout: float = 60,
                     max_retries: int = 2):
    """Issue an RPC with the client-side retry contract:

    - ``calls`` is an ordered list of stubs — the ring owner first, then
      failover owners (a single-server caller passes one stub, retried
      in place);
    - UNAVAILABLE with a ``grpc-retry-pushback-ms`` hint sleeps the
      hinted duration (jittered 0.5x-1.5x, capped 2 s) before retrying;
      without a hint the failover is immediate on a fleet (the next
      owner is an independent process) and a hintless single-server
      UNAVAILABLE after the last stub re-raises;
    - bounded at ``max_retries`` total retries — a client retry loop
      with no bound is the CC05 anti-pattern.
    """
    last_exc = None
    for attempt in range(max_retries + 1):
        call = calls[min(attempt, len(calls) - 1)]
        try:
            return call(payload, timeout=timeout, metadata=metadata)
        except grpc.RpcError as exc:
            if exc.code() != grpc.StatusCode.UNAVAILABLE or attempt == max_retries:
                raise
            last_exc = exc
            hint = _pushback_ms(exc)
            with stats.lock:
                stats.retries += 1
                if hint is not None:
                    stats.pushback_honored += 1
                if len(calls) > 1 and attempt + 1 < len(calls):
                    stats.failovers += 1
            if hint is None and len(calls) == 1:
                raise  # nowhere else to go and no hint: surface it
            if hint:
                time.sleep(min(hint, 2000) / 1000.0
                           * (0.5 + float(rng.random())))
    raise last_exc  # pragma: no cover — loop always returns or raises


def availability_block(events, t_start: float, t_end: float,
                       window_s: float = 1.0) -> dict:
    """Availability accounting over per-request completion samples —
    the comparable artifact chaos soaks need (satellite of the
    supervisor PR): ``events`` is an iterable of ``(t, ok)`` with ``t``
    a monotonic completion time.

    Returns per-``window_s`` success rates (so a fault window shows as a
    dented rate, not an averaged-away blip), the worst consecutive-
    failure run (count AND wall-clock span), and per-outage
    time-to-recovery — measured from the first failure of a failure run
    to the FIRST success completing after its last failure (the
    "first post-fault success" mark)."""
    evs = sorted((float(t), bool(ok)) for t, ok in events)
    n_windows = max(1, int((t_end - t_start) // window_s))
    totals = [0] * n_windows
    fails = [0] * n_windows
    for t, ok in evs:
        wi = int((t - t_start) // window_s)
        if 0 <= wi < n_windows:
            totals[wi] += 1
            if not ok:
                fails[wi] += 1
    rates = [
        round(1.0 - f / tot, 4) if tot else None
        for tot, f in zip(totals, fails)
    ]

    max_run = 0
    max_run_span_s = 0.0
    run = 0
    run_start = None
    outages: list[dict] = []
    pending: tuple[float, float, int] | None = None  # (first_fail, last_fail, count)
    for t, ok in evs:
        if ok:
            if pending is not None:
                first_fail, last_fail, count = pending
                outages.append({
                    "start_offset_s": round(first_fail - t_start, 3),
                    "failures": count,
                    "span_s": round(last_fail - first_fail, 3),
                    "time_to_recovery_s": round(t - first_fail, 3),
                })
                pending = None
            run = 0
            run_start = None
        else:
            if run == 0:
                run_start = t
            run += 1
            if run > max_run:
                max_run = run
                max_run_span_s = t - run_start
            if pending is None:
                pending = (t, t, 1)
            else:
                pending = (pending[0], t, pending[2] + 1)
    if pending is not None:  # outage never recovered inside the window
        first_fail, last_fail, count = pending
        outages.append({
            "start_offset_s": round(first_fail - t_start, 3),
            "failures": count,
            "span_s": round(last_fail - first_fail, 3),
            "time_to_recovery_s": None,
        })

    recoveries = [o["time_to_recovery_s"] for o in outages
                  if o["time_to_recovery_s"] is not None]
    return {
        "window_s": window_s,
        "success_rate_per_window": rates,
        "requests": len(evs),
        "failures": sum(fails),
        "max_consecutive_failures": max_run,
        "max_failure_window_s": round(max_run_span_s, 3),
        "outages": outages,
        "time_to_recovery_s": max(recoveries) if recoveries else None,
    }


def _client_traceparent() -> tuple[str, tuple]:
    """Fresh W3C trace context per RPC, sent as gRPC metadata — the
    client end of the client -> front (-> follower) trace the server's
    rpc.* span adopts. Returns (trace_id, metadata)."""
    trace_id = uuid.uuid4().hex
    header = f"00-{trace_id}-{uuid.uuid4().hex[:16]}-01"
    return trace_id, (("traceparent", header),)


def _seed_store(engine, n_accounts: int = 512, events_per_acct: int = 6) -> None:
    """Give the feature store history so gathers do real work."""
    from igaming_platform_tpu.serve.feature_store import TransactionEvent

    rng = np.random.default_rng(3)
    now = time.time()
    for a in range(n_accounts):
        for e in range(events_per_acct):
            engine.update_features(TransactionEvent(
                account_id=f"lg-{a}",
                amount=int(rng.integers(100, 50_000)),
                tx_type=("deposit", "bet", "win")[e % 3],
                ip=f"10.0.{a % 200}.{e}",
                device_id=f"dev-{a % 64}",
                timestamp=now - float(rng.integers(0, 3000)),
            ))


def _build_fleet_payloads(
    addrs: list[str], rows_per_rpc: int, wire_mode: str,
    n_variants: int = 4, n_accounts: int = 512,
) -> tuple[dict[str, list[bytes]], "object"]:
    """Per-replica payloads under account affinity: partition the account
    space by ring owner (serve/router.py AccountAffinityPicker — the SAME
    ring the L7 router uses), then build each replica's payload variants
    from only the accounts it owns. Returns ({addr: payloads}, picker)."""
    from igaming_platform_tpu.serve.router import AccountAffinityPicker

    from igaming_platform_tpu.serve.wire import encode_index_batch

    picker = AccountAffinityPicker(addrs)
    owned = picker.partition(f"lg-{i}" for i in range(n_accounts))
    rng = np.random.default_rng(7)
    tx_types = ("deposit", "bet", "withdraw")
    per_addr: dict[str, list[bytes]] = {}
    for addr in addrs:
        accts = owned.get(addr) or [f"lg-fleet-{addr}"]
        payloads = []
        for v in range(n_variants):
            ids = [accts[int(rng.integers(0, len(accts)))]
                   for _ in range(rows_per_rpc)]
            amounts = [int(rng.integers(100, 100_000))
                       for _ in range(rows_per_rpc)]
            types = [tx_types[int(rng.integers(0, 3))]
                     for _ in range(rows_per_rpc)]
            ips = [f"10.{v}.{i % 200}.{i % 251}" for i in range(rows_per_rpc)]
            devs = [f"dev-{int(rng.integers(0, 64))}"
                    for _ in range(rows_per_rpc)]
            if wire_mode == "index":
                payloads.append(encode_index_batch(
                    ids, amounts, types, ips=ips, devices=devs))
            else:
                txs = [
                    risk_pb2.ScoreTransactionRequest(
                        account_id=ids[i], amount=amounts[i],
                        transaction_type=types[i], ip_address=ips[i],
                        device_id=devs[i])
                    for i in range(rows_per_rpc)
                ]
                payloads.append(risk_pb2.ScoreBatchRequest(
                    transactions=txs).SerializeToString())
        per_addr[addr] = payloads
    return per_addr, picker


def run_grpc_load(
    addr: str,
    *,
    duration_s: float = 8.0,
    rows_per_rpc: int = 4096,
    concurrency: int = 4,
    warmup_rpcs: int = 3,
    wire_mode: str = "row",
    fleet_addrs: list[str] | None = None,
    drift_ramp=None,
    drift_phases: int = 8,
    fraud_ring=None,
    fraud_ring_seed: int = 29,
    fraud_ring_time_scale: float = 1.0,
) -> dict:
    """Drive ScoreBatch at ``addr`` from ``concurrency`` client threads for
    ``duration_s``; returns sustained txns/s + RPC latency percentiles.
    ``wire_mode='index'`` ships index-mode frames (HBM feature cache).
    ``fleet_addrs`` switches to fleet mode: each worker drives its
    account-affine replica through the client-side picker, failing over
    to the next ring owner on UNAVAILABLE.

    ``drift_ramp`` (a train/fraudgen.DriftRamp or its spec string)
    injects a DETERMINISTIC mean/scale drift into the transaction
    amounts: the run is cut into ``drift_phases`` payload sets, each
    pre-built with the ramp's transform at that phase's run fraction
    (same seed -> byte-identical payloads run-to-run), and the artifact
    records the injected schedule verbatim (``drift_block``).

    ``fraud_ring`` (a train/fraudgen.FraudRing or its spec string)
    additionally runs ONE injector thread pacing the ring's seeded event
    schedule in wall time (``fraud_ring_time_scale`` compresses it for
    short runs) as 1-row index-mode ScoreBatch frames — riding the
    session-state path on a WIRE_MODE=index server — and records the
    schedule verbatim in the artifact (``fraud_ring_block``, mirroring
    the --drift-ramp pattern)."""
    phase_payload_sets: list[list[bytes]] | None = None
    drift_block = None
    if drift_ramp is not None:
        from igaming_platform_tpu.train.fraudgen import DriftRamp

        if fleet_addrs:
            raise ValueError("--drift-ramp does not combine with fleet "
                             "mode (inject per-replica drift via the "
                             "soak harness instead)")
        ramp = (DriftRamp.parse(drift_ramp) if isinstance(drift_ramp, str)
                else drift_ramp)
        builder = (_build_index_payloads if wire_mode == "index"
                   else _build_request_payloads)
        phase_payload_sets = []
        for ph in range(drift_phases):
            mult, shift = ramp.factors((ph + 0.5) / drift_phases)
            phase_payload_sets.append(
                builder(rows_per_rpc, amount_mult=mult, amount_shift=shift))
        payloads = phase_payload_sets[0]
        drift_block = {
            "spec": ramp.spec_string(),
            "phases": drift_phases,
            "applied_to": ["tx_amount"],
            "schedule": ramp.schedule_block(drift_phases),
        }
    fleet_payloads: dict[str, list[bytes]] = {}
    if fleet_addrs:
        fleet_payloads, _picker = _build_fleet_payloads(
            fleet_addrs, rows_per_rpc, wire_mode)
        payloads = next(iter(fleet_payloads.values()))
    elif drift_ramp is None and wire_mode == "index":
        payloads = _build_index_payloads(rows_per_rpc)
    elif drift_ramp is None:
        payloads = _build_request_payloads(rows_per_rpc)

    stop_at = [0.0]
    results: list[list[tuple[float, float]]] = [[] for _ in range(concurrency)]
    errors = [0]
    shed = [0]
    retry_stats = _RetryStats()
    # Failures broken down by gRPC status code: a single opaque counter
    # cannot tell DEADLINE_EXCEEDED backpressure from
    # UNAVAILABLE crashes at a glance. Guarded by errors_lock — worker
    # threads share the dict.
    errors_by_code: dict[str, int] = {}
    errors_lock = threading.Lock()
    fail_times: list[float] = []  # guarded by errors_lock

    def _count_error(exc: grpc.RpcError) -> None:
        try:
            code = exc.code().name
        except Exception:  # noqa: BLE001 — a dead channel may not carry a code
            code = "UNKNOWN"
        with errors_lock:
            errors[0] += 1
            errors_by_code[code] = errors_by_code.get(code, 0) + 1
            fail_times.append(time.perf_counter())

    def worker(k: int) -> None:
        # Own channel per worker: one HTTP/2 connection each, so the test
        # measures the server, not client-side connection multiplexing.
        # Fleet mode: the worker's primary is its account-affine replica;
        # the remaining replicas (ring rotation order) are failover
        # targets for _call_with_retry.
        if fleet_addrs:
            pi = k % len(fleet_addrs)
            worker_addrs = fleet_addrs[pi:] + fleet_addrs[:pi]
            worker_payloads = fleet_payloads[worker_addrs[0]]
        else:
            worker_addrs = [addr]
            worker_payloads = payloads
        channels = [grpc.insecure_channel(a) for a in worker_addrs[:3]]
        calls = [
            ch.unary_unary(
                "/risk.v1.RiskService/ScoreBatch",
                request_serializer=lambda b: b,
                response_deserializer=lambda b: b,  # decode cost excluded: server-side measurement
            )
            for ch in channels
        ]
        retry_rng = np.random.default_rng(1000 + k)
        try:
            for i in range(warmup_rpcs):
                calls[0](worker_payloads[i % len(worker_payloads)], timeout=60)
        except grpc.RpcError as exc:
            _count_error(exc)
        finally:
            # Worker 0 starts the clock even if its warmup failed —
            # otherwise the other workers spin on stop_at forever.
            if k == 0:
                stop_at[0] = time.perf_counter() + duration_s
        spin_deadline = time.perf_counter() + 120.0
        while stop_at[0] == 0.0:
            if time.perf_counter() > spin_deadline:
                return
            time.sleep(0.001)
        i = k
        while time.perf_counter() < stop_at[0]:
            if phase_payload_sets is not None:
                # Drift-ramp phase by run fraction: deterministic given
                # the wall window (the schedule lands in the artifact).
                frac = 1.0 - (stop_at[0] - time.perf_counter()) / duration_s
                worker_payloads = phase_payload_sets[
                    min(int(max(0.0, frac) * drift_phases),
                        drift_phases - 1)]
            _, metadata = _client_traceparent()
            t0 = time.perf_counter()
            try:
                _call_with_retry(
                    calls, worker_payloads[i % len(worker_payloads)],
                    metadata, retry_stats, retry_rng)
            except grpc.RpcError as exc:
                # Shed vs failure must not conflate (the soak harness's
                # discipline, tools/drills/soak.py): RESOURCE_EXHAUSTED is
                # the admission gate's LOUD backpressure — the bulk
                # caller's contract is retry-with-backoff — while any
                # other status is a real serving failure. Folding sheds
                # into `errors` made headline artifacts report a healthy
                # gate as a sick server (VERDICT r05 Weak #2).
                if exc.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                    shed[0] += 1
                    time.sleep(0.02 * (1 + (i % 4)))
                else:
                    # Failed RPCs scored nothing — they must not count
                    # toward throughput or latency, or a failing server
                    # inflates the headline exactly when it shouldn't.
                    _count_error(exc)
            else:
                t1 = time.perf_counter()
                results[k].append((t1, (t1 - t0) * 1000.0))
            i += 1
        for ch in channels:
            ch.close()

    fraud_ring_block = None
    ring_sent = [0]
    ring_errors = [0]
    if fraud_ring is not None:
        from igaming_platform_tpu.serve.wire import encode_index_batch
        from igaming_platform_tpu.train.fraudgen import FraudRing

        ring = (FraudRing.parse(fraud_ring) if isinstance(fraud_ring, str)
                else fraud_ring)
        ring_schedule = ring.schedule(fraud_ring_seed)
        fraud_ring_block = ring.schedule_block(fraud_ring_seed)
        fraud_ring_block["time_scale"] = fraud_ring_time_scale

        def ring_injector() -> None:
            ch = grpc.insecure_channel(addr)
            call = ch.unary_unary(
                "/risk.v1.RiskService/ScoreBatch",
                request_serializer=lambda b: b,
                response_deserializer=lambda b: b)
            spin = time.perf_counter() + 120.0
            while stop_at[0] == 0.0:
                if time.perf_counter() > spin:
                    return
                time.sleep(0.001)
            t_base = stop_at[0] - duration_s
            for row in ring_schedule:
                due = t_base + row["t_s"] * fraud_ring_time_scale
                now = time.perf_counter()
                if now >= stop_at[0]:
                    break
                if due > now:
                    time.sleep(min(due - now, stop_at[0] - now))
                payload = encode_index_batch(
                    [row["account_id"]], [row["amount"]], [row["tx_type"]])
                sent = False
                for attempt in range(6):
                    try:
                        call(payload, timeout=10)
                        sent = True
                        break
                    except grpc.RpcError as exc:
                        if exc.code() != grpc.StatusCode.RESOURCE_EXHAUSTED:
                            break
                        # Bulk admission shed under flat-out background
                        # load: the ring event is the payload under test,
                        # retry with backoff like a well-behaved caller.
                        time.sleep(0.02 * (attempt + 1))
                if sent:
                    ring_sent[0] += 1
                else:
                    ring_errors[0] += 1
            ch.close()

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(concurrency)]
    if fraud_ring is not None:
        threads.append(threading.Thread(target=ring_injector,
                                        name="fraud-ring-injector"))
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    if fraud_ring_block is not None:
        fraud_ring_block["events_sent"] = ring_sent[0]
        fraud_ring_block["events_failed"] = ring_errors[0]

    # Sustained rate = completions INSIDE the window / window length. RPCs
    # that complete after stop_at would otherwise credit up to
    # concurrency × rows_per_rpc extra rows against duration_s.
    window_end = stop_at[0]
    lat = np.array([ms for r in results for (t_end, ms) in r if t_end <= window_end])
    n_rpcs = int(lat.size)
    txns = n_rpcs * rows_per_rpc
    # Availability block (chaos-soak artifact contract): every completion
    # — success or failure — as a 1s-windowed success-rate series plus
    # consecutive-failure and time-to-recovery accounting.
    events = [(t_end, True) for r in results for (t_end, _ms) in r]
    events.extend((t, False) for t in fail_times)
    availability = availability_block(
        events, window_end - duration_s if window_end else t_start,
        window_end or time.perf_counter())
    return {
        "metric": "e2e_grpc_fraud_score_txns_per_sec",
        "value": round(txns / duration_s, 1),
        "unit": "txns/s",
        "wire_mode": wire_mode,
        "rows_per_rpc": rows_per_rpc,
        "concurrency": concurrency,
        "duration_s": duration_s,
        "rpcs": n_rpcs,
        "errors": errors[0],
        "errors_by_code": dict(sorted(errors_by_code.items())),
        "bulk_shed": shed[0],
        # Client retry contract: UNAVAILABLE retries, how many honored
        # the server's grpc-retry-pushback-ms hint, and (fleet mode) how
        # many failed over to the next ring owner.
        "retries": retry_stats.retries,
        "pushback_honored": retry_stats.pushback_honored,
        "failovers": retry_stats.failovers,
        **({"fleet_replicas": len(fleet_addrs)} if fleet_addrs else {}),
        **({"drift_block": drift_block} if drift_block else {}),
        **({"fraud_ring_block": fraud_ring_block} if fraud_ring_block else {}),
        "rpc_p50_ms": round(float(np.percentile(lat, 50)), 3) if n_rpcs else None,
        "rpc_p99_ms": round(float(np.percentile(lat, 99)), 3) if n_rpcs else None,
        "wall_s": round(wall, 3),
        "availability": availability,
    }


def run_paced_load(
    addr: str,
    *,
    rate_rps: float,
    duration_s: float = 10.0,
    deadline_ms: float = 50.0,
    warmup_rpcs: int = 20,
    seed: int = 11,
    late_threshold_ms: float = 1.0,
    channels: int = 2,
) -> dict:
    """Open-loop paced ScoreTransaction load — the arrival process the
    closed-loop flat-out mode cannot produce.

    Closed-loop workers wait for each response before sending the next
    request, so a slow server *slows the offered load* and p99 flatters
    itself (coordinated omission). Here arrivals are a seeded Poisson
    process at ``rate_rps``: each RPC has a SCHEDULED send time fixed
    before the run, sends are non-blocking (gRPC futures), and latency
    is measured from the *scheduled* time — a request the sender issued
    late (because Python fell behind) still charges its full
    user-visible wait. Late sends are counted, not hidden
    (``pacing_block.late_sends``): if the generator cannot hold the
    target rate, the artifact says so instead of reporting a rate it
    didn't offer.

    Every request carries ``risk-deadline-ms: deadline_ms`` — the
    deadline scheduler's admission contract — and the artifact counts
    ``scored_after_deadline``: OK responses that arrived after their
    budget (the server should have shed them; the deadline drill's gate
    pins this at zero).
    """
    rng = np.random.default_rng(seed)
    n_sends = max(1, int(rate_rps * duration_s))
    # Poisson arrivals: exponential gaps, fixed before the run starts.
    gaps = rng.exponential(1.0 / rate_rps, size=n_sends)
    offsets = np.cumsum(gaps)

    n_senders = max(1, min(8, int(rate_rps // 250) or 1))
    channels = max(channels, n_senders)
    chs = [grpc.insecure_channel(addr) for _ in range(max(1, channels))]
    calls = [
        ch.unary_unary(
            "/risk.v1.RiskService/ScoreTransaction",
            request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreTransactionResponse.FromString,
        )
        for ch in chs
    ]
    payloads = [
        risk_pb2.ScoreTransactionRequest(
            account_id=f"lg-{int(rng.integers(0, 512))}",
            amount=int(rng.integers(100, 100_000)),
            transaction_type=("deposit", "bet", "withdraw")[i % 3],
            device_id=f"dev-{i % 64}",
        )
        for i in range(256)
    ]
    for i in range(warmup_rpcs):
        try:
            calls[0](payloads[i % len(payloads)], timeout=30)
        except grpc.RpcError:
            pass

    lock = threading.Lock()
    # (latency_from_scheduled_ms, latency_from_send_ms, ok, code)
    done_rows: list[tuple[float, float, bool, str]] = []
    outstanding = [0]
    drained = threading.Event()

    def _complete(sched_t: float, send_t: float, fut) -> None:
        t1 = time.perf_counter()
        code = "OK"
        ok = True
        try:
            fut.result()
        except grpc.RpcError as exc:
            ok = False
            try:
                code = exc.code().name
            except Exception:  # noqa: BLE001 — a dead channel may not carry a code
                code = "UNKNOWN"
        with lock:
            done_rows.append(((t1 - sched_t) * 1000.0,
                              (t1 - send_t) * 1000.0, ok, code))
            outstanding[0] -= 1
            if outstanding[0] == 0:
                drained.set()

    late_lock = threading.Lock()
    late_sends = [0]
    late_by_ms: list[float] = []
    # Sharded senders: one Python thread cannot pace >~700 sends/s (the
    # per-send ~1 ms of proto+grpc work becomes the bottleneck and the
    # measured "latency" is client backlog, not the server). Each sender
    # owns every K-th arrival — a thinned Poisson process is still
    # Poisson, and the superposition offered to the server is the
    # original schedule.
    t_start = time.perf_counter()

    def sender(k: int) -> None:
        call = calls[k % len(calls)]
        for i in range(k, n_sends, n_senders):
            sched_t = t_start + float(offsets[i])
            now = time.perf_counter()
            if sched_t > now:
                time.sleep(sched_t - now)
                now = time.perf_counter()
            behind_ms = (now - sched_t) * 1000.0
            if behind_ms > late_threshold_ms:
                with late_lock:
                    late_sends[0] += 1
                    late_by_ms.append(behind_ms)
            _, tp = _client_traceparent()
            md = tp + (("risk-deadline-ms", str(int(deadline_ms))),)
            with lock:
                outstanding[0] += 1
                drained.clear()
            fut = call.future(
                payloads[i % len(payloads)], timeout=30, metadata=md)
            fut.add_done_callback(
                lambda f, s=sched_t, t=now: _complete(s, t, f))

    threads = [threading.Thread(target=sender, args=(k,))
               for k in range(n_senders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    drained.wait(timeout=30.0)
    wall = time.perf_counter() - t_start
    for ch in chs:
        ch.close()

    with lock:
        rows = list(done_rows)
    ok_rows = [r for r in rows if r[2]]
    lat_sched = np.array([r[0] for r in ok_rows])
    codes: dict[str, int] = {}
    for _ls, _li, ok, code in rows:
        if not ok:
            codes[code] = codes.get(code, 0) + 1
    # OK responses that arrived past the budget measured from SEND.
    # Observational, not the contract: ``risk-deadline-ms`` is a
    # duration anchored at each hop's ADMISSION, so this count includes
    # transport and pre-admission gRPC queueing the server cannot see.
    # The contract's "zero scored dead" gate reads the server's
    # structural evidence (/debug/deadlinez ``dead_dispatched`` — rows
    # dispatched with a spent budget — plus the response-time shed that
    # converts late results into DEADLINE_EXCEEDED).
    ok_past_deadline = sum(1 for r in ok_rows if r[1] > deadline_ms)
    sheds = codes.get("DEADLINE_EXCEEDED", 0) + codes.get(
        "RESOURCE_EXHAUSTED", 0)
    errors = sum(n for c, n in codes.items()
                 if c not in ("DEADLINE_EXCEEDED", "RESOURCE_EXHAUSTED"))
    return {
        "metric": "e2e_grpc_paced_single_txn_p99_ms",
        "value": (round(float(np.percentile(lat_sched, 99)), 3)
                  if lat_sched.size else None),
        "unit": "ms",
        "mode": "open_loop_paced",
        "deadline_ms": deadline_ms,
        "duration_s": duration_s,
        "rpcs_sent": n_sends,
        "rpcs_completed": len(rows),
        "ok": len(ok_rows),
        "sheds": sheds,
        "errors": errors,
        "errors_by_code": dict(sorted(codes.items())),
        "ok_past_deadline_send_anchored": ok_past_deadline,
        "rpc_p50_ms": (round(float(np.percentile(lat_sched, 50)), 3)
                       if lat_sched.size else None),
        "rpc_p99_ms": (round(float(np.percentile(lat_sched, 99)), 3)
                       if lat_sched.size else None),
        "rpc_max_ms": (round(float(lat_sched.max()), 3)
                       if lat_sched.size else None),
        "pacing_block": {
            "target_rps": rate_rps,
            "offered_rps": round(n_sends / wall, 1) if wall > 0 else None,
            "achieved_rps": (round(len(ok_rows) / duration_s, 1)
                             if duration_s > 0 else None),
            "late_sends": late_sends[0],
            "late_send_p99_ms": (
                round(float(np.percentile(np.array(late_by_ms), 99)), 3)
                if late_by_ms else 0.0),
            "senders": n_senders,
            "arrivals": "poisson",
            "seed": seed,
            # Latencies are measured from the SCHEDULED arrival, so a
            # backlogged sender cannot flatter p99 (coordinated
            # omission).
            "latency_origin": "scheduled_arrival",
        },
        "wall_s": round(wall, 3),
    }


def run_single_txn_probe(addr: str, n: int = 150) -> dict:
    """Sequential ScoreTransaction probes — the per-request latency a
    single caller sees through the continuous batcher."""
    ch = grpc.insecure_channel(addr)
    call = ch.unary_unary(
        "/risk.v1.RiskService/ScoreTransaction",
        request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
        response_deserializer=risk_pb2.ScoreTransactionResponse.FromString,
    )
    lat = []
    for i in range(n):
        req = risk_pb2.ScoreTransactionRequest(
            account_id=f"lg-{i % 64}", amount=1000 + i, transaction_type="deposit")
        _, metadata = _client_traceparent()
        t0 = time.perf_counter()
        call(req, timeout=30, metadata=metadata)
        lat.append((time.perf_counter() - t0) * 1000.0)
    ch.close()
    lat = np.array(lat[10:])
    return {
        "metric": "e2e_grpc_single_txn_p99_ms",
        "value": round(float(np.percentile(lat, 99)), 3),
        "unit": "ms",
        "p50_ms": round(float(np.percentile(lat, 50)), 3),
        "requests": int(lat.size),
    }


def start_inprocess_server(
    *, batch_size: int = 4096, ml_backend: str = "multitask",
    seed_accounts: int = 512, ledger_dir: str | None = None,
    feature_cache: int | None = None, session_state: bool | None = None,
):
    """Production wiring on a free port: native feature store, multitask
    backend, native wire codec. Returns (addr, shutdown_fn, engine) —
    the engine so harnesses can read server-side pipeline stats
    (inflight depth, host-stage overlap) into their artifacts.

    ``ledger_dir`` (or the LEDGER_DIR env) binds a durable decision
    ledger (serve/ledger.py) so load runs measure the audit pipeline's
    hot-path cost — ``engine.ledger.stats_block()`` lands in artifacts
    as ``ledger_block``.

    ``feature_cache``/``session_state`` enable the device-resident
    feature table and the session plane, so index-mode load
    (``run_grpc_load(wire_mode='index')``) exercises the stateful
    scoring path — the host-cost observatory arm profiles exactly
    this wiring."""
    import jax

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.models.multitask import init_multitask
    from igaming_platform_tpu.serve.grpc_server import RiskGrpcService, serve_risk
    from igaming_platform_tpu.serve.native_store import best_feature_store
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    params = None
    if ml_backend == "multitask":
        params = {"multitask": init_multitask(jax.random.key(0))}
    engine = TPUScoringEngine(
        ScoringConfig(),
        ml_backend=ml_backend,
        params=params,
        batcher_config=BatcherConfig(batch_size=batch_size, max_wait_ms=1.0),
        feature_store=best_feature_store(),
        feature_cache=feature_cache,
        session_state=session_state,
    )
    ledger = None
    ledger_dir = ledger_dir or os.environ.get("LEDGER_DIR", "")
    if ledger_dir:
        from igaming_platform_tpu.serve import ledger as ledger_mod

        ledger = ledger_mod.DecisionLedger(
            ledger_dir, sink=ledger_mod.sink_from_env())
        engine.ledger = ledger
    _seed_store(engine, n_accounts=seed_accounts)
    service = RiskGrpcService(engine)
    server, health, port = serve_risk(service, 0, max_workers=32)

    def shutdown() -> None:
        server.stop(0)
        engine.close()
        if ledger is not None:
            ledger.close()

    return f"localhost:{port}", shutdown, engine


def main() -> None:
    wire_mode = os.environ.get("LOAD_WIRE_MODE", "row")
    addr = None
    fleet_addrs: list[str] | None = None
    drift_ramp = os.environ.get("LOAD_DRIFT_RAMP") or None
    fraud_ring = os.environ.get("LOAD_FRAUD_RING") or None
    pace_rps: float | None = None
    pace_gates = False
    for arg in sys.argv[1:]:
        if arg.startswith("--wire-mode="):
            wire_mode = arg.split("=", 1)[1]
        elif arg == "--wire-mode":
            raise SystemExit("use --wire-mode=row|index")
        elif arg.startswith("--fleet="):
            fleet_addrs = [a for a in arg.split("=", 1)[1].split(",") if a]
        elif arg.startswith("--pace="):
            # Open-loop paced-arrival mode (Poisson arrivals at RATE
            # rps, late-send accounting): run_paced_load.
            pace_rps = float(arg.split("=", 1)[1])
        elif arg == "--pace":
            raise SystemExit("use --pace=RATE_RPS")
        elif arg == "--pace-gates":
            # Exit non-zero unless p99 < the SLO bound
            # and zero requests were scored after their deadline.
            pace_gates = True
        elif arg.startswith("--drift-ramp="):
            # Seedable injected drift, e.g. --drift-ramp=mult=8:start=0.4
            # (spec grammar: train/fraudgen.DriftRamp.parse).
            drift_ramp = arg.split("=", 1)[1]
        elif arg == "--drift-ramp":
            raise SystemExit(
                "use --drift-ramp=mult=M[:shift=S:start=F:end=F]")
        elif arg.startswith("--fraud-ring="):
            # Seeded coordinated fraud-ring injection, e.g.
            # --fraud-ring=size=6:period=90:cycles=12 (spec grammar:
            # train/fraudgen.FraudRing.parse). Rides the session path;
            # the schedule lands in the artifact (fraud_ring_block).
            fraud_ring = arg.split("=", 1)[1]
        elif arg == "--fraud-ring":
            raise SystemExit(
                "use --fraud-ring=size=K:period=S[:cycles=N:amount=A]")
        else:
            addr = arg
    if wire_mode not in ("row", "index"):
        raise SystemExit(f"unknown wire mode {wire_mode!r} (row|index)")
    shutdown = None
    engine = None
    if fleet_addrs:
        addr = fleet_addrs[0]
    elif addr is None:
        addr, shutdown, engine = start_inprocess_server(
            batch_size=int(os.environ.get("LOAD_BATCH", 4096)),
        )
    if pace_rps is not None:
        try:
            paced = run_paced_load(
                addr,
                rate_rps=pace_rps,
                duration_s=float(os.environ.get("LOAD_PACE_DURATION_S", 10.0)),
                deadline_ms=float(os.environ.get(
                    "LOAD_PACE_DEADLINE_MS",
                    os.environ.get("SLO_OBJECTIVE_MS", "50"))),
            )
            if engine is not None:
                # In-process run: the server-side "zero scored dead"
                # evidence rides the artifact directly.
                paced["scored_dead"] = engine._batcher.dead_dispatched
            print(json.dumps(paced), flush=True)
            if pace_gates:
                bound = float(os.environ.get(
                    "SLO_OBJECTIVE_MS", "50"))
                p99 = paced.get("rpc_p99_ms")
                if p99 is None or p99 >= bound:
                    raise SystemExit(
                        f"paced gate FAILED: p99 {p99} ms >= "
                        f"{bound} ms bound")
                if paced.get("scored_dead", 0) != 0:
                    raise SystemExit(
                        "paced gate FAILED: "
                        f"{paced['scored_dead']} requests "
                        "scored after their deadline")
        finally:
            if shutdown is not None:
                shutdown()
        return
    try:
        load = run_grpc_load(
            addr,
            duration_s=float(os.environ.get("LOAD_DURATION_S", 8.0)),
            rows_per_rpc=int(os.environ.get("LOAD_ROWS_PER_RPC", 4096)),
            concurrency=int(os.environ.get("LOAD_CONCURRENCY", 4)),
            wire_mode=wire_mode,
            fleet_addrs=fleet_addrs,
            drift_ramp=drift_ramp,
            fraud_ring=fraud_ring,
            fraud_ring_time_scale=float(
                os.environ.get("LOAD_FRAUD_RING_TIME_SCALE", "1.0")),
        )
        pipeline = getattr(engine, "pipeline", None)
        if pipeline is not None:
            stats = pipeline.stats()
            load["pipeline_inflight_depth"] = stats["depth"]
            load["pipeline_max_inflight"] = stats["max_inflight"]
            load["host_stage_overlap_ratio"] = stats["overlap_ratio"]
        ledger = getattr(engine, "ledger", None)
        if ledger is not None:
            # Audit-pipeline health under load: records appended, fsync
            # p99, spill episodes, sink-queue high-water (serve/ledger.py).
            ledger.flush(5.0)
            load["ledger_block"] = ledger.stats_block()
        if engine is not None:
            # SLO summary for the in-process arm (obs/slo.py): attainment,
            # burn rates, top budget-eating stage.
            from igaming_platform_tpu.obs import slo as slo_mod

            if slo_mod.get_default() is not None:
                load["slo_block"] = slo_mod.get_default().summary_block()
            # Drift-observatory summary for the in-process arm
            # (obs/drift.py): rows sketched/dropped, alert state, and —
            # with a pinned reference — the headline PSIs.
            from igaming_platform_tpu.obs import drift as drift_mod

            if drift_mod.get_default() is not None:
                drift_mod.get_default().drain(2.0)
                load["drift_summary"] = drift_mod.get_default().summary_block()
        print(json.dumps(load), flush=True)
        probe = run_single_txn_probe(addr)
        print(json.dumps(probe), flush=True)
    finally:
        if shutdown is not None:
            shutdown()


if __name__ == "__main__":
    main()
