"""Fault drills: the system broken on purpose, held to its guarantees.

Eight drills, one flag each (``python -m tools.drills.soak --<flag>``, or
``make soak-*``); ``docs/operations.md`` says when an operator runs which:

- ``--chaos``: a follower SIGKILLed under load and restarted; the front
  never wedges, degraded-mode scores are bit-exact;
- ``--fleet-chaos``: a replica killed, browned out and link-dropped behind
  the account-affinity router; availability in every window, eviction and
  readmission;
- ``--chaos-ledger``: fs outage, sink outage, a degraded window and a
  SIGKILL; the surviving WAL replays bit-exact, every record reaches the
  sink;
- ``--slo-chaos``: an injected dispatch delay fires the burn alert, is
  attributed to its stage and profiled once; ``/debug/fleetz`` stays live
  through a SIGKILL;
- ``--online-chaos``: mined hard negatives, gated promotion, injected
  regression rolled back, SIGKILL, replay across the promotion boundary;
- ``--drift-chaos``: an injected drift ramp raises and clears the input
  alert and holds promotion; merged fleet drift state through a SIGKILL;
- ``--session-chaos``: a seeded fraud ring flagged by the session path
  alone; eviction churn and a SIGKILL, every session hash replayed;
- ``--deadline``: paced load under per-request deadlines, the burn->shed
  loop, replay of the paced run.

Each drill boots its own replica processes on the CPU (a control rig, not a
deployment), prints one JSON line with its ``gates`` and exits non-zero when
a gate misses. A gate is a guarantee (availability, bit-exact parity,
replay, no lost record, sheds counted, an alert inside its window), never a
rate: the benchmark is ``chipbench/``. Artifacts go where each drill's
variable says (``SLO_ARTIFACT``, ``LEDGER_CHAOS_OUT``, ...), by default
under ``build/``, which git ignores.
"""

import json
import os
import sys
import threading
import time
from collections import deque

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _artifact_path(env_name: str, file_name: str) -> str:
    """Where a drill writes its artifact: the path in ``env_name``, else
    ``build/<file_name>`` of the checkout."""
    path = os.environ.get(env_name, os.path.join(REPO, "build", file_name))
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    return path


def main_chaos() -> None:
    """Follower-kill chaos soak (``--chaos``): a real gRPC front over a
    loopback multihost engine + a stub follower process speaking the real
    work-channel protocol. Mid-soak the follower is SIGKILLed under load
    and later restarted; the printed artifact records what the
    supervisor promises: the front never wedges, availability during
    the fault, detection / resurrection / full-recovery times, and score
    parity during the outage and after the follower rejoins."""
    import signal  # noqa: F401 — documents the SIGKILL scenario
    import socket as _socket
    import subprocess

    import grpc

    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from tools.drills.load_gen import _seed_store, availability_block

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import chaos as chaos_mod
    from igaming_platform_tpu.serve import multihost
    from igaming_platform_tpu.serve.grpc_server import (
        RiskGrpcService,
        graceful_stop,
        serve_risk,
    )
    from igaming_platform_tpu.serve.supervisor import (
        ServingSupervisor,
        SupervisedScoringEngine,
    )

    duration_s = float(os.environ.get("CHAOS_DURATION_S", 30.0))
    kill_at = float(os.environ.get("CHAOS_KILL_AT_S", duration_s / 3))
    restart_at = float(os.environ.get("CHAOS_RESTART_AT_S", 2 * duration_s / 3))
    rows = int(os.environ.get("CHAOS_ROWS_PER_RPC", 256))
    batch = int(os.environ.get("CHAOS_BATCH", 256))
    plan = chaos_mod.install_from_env()  # optional extra seam faults

    with _socket.socket() as s:
        s.bind(("localhost", 0))
        follower_port = s.getsockname()[1]

    def start_stub():
        proc = subprocess.Popen(
            [sys.executable, "-m", "igaming_platform_tpu.serve.multihost",
             "--stub-follower", "--port", str(follower_port)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        assert "READY" in proc.stdout.readline()
        return proc

    stub = start_stub()
    sup = ServingSupervisor(failure_threshold=2, open_s=0.5)

    import jax

    from igaming_platform_tpu.models.multitask import init_multitask

    params = {"multitask": jax.device_get(init_multitask(jax.random.key(0)))}

    def factory():
        return multihost.multihost_engine(
            None, [follower_port], config=ScoringConfig(),
            batcher_config=BatcherConfig(batch_size=batch, max_wait_ms=1.0),
            ml_backend="multitask", params=params, reconnect=True,
            supervisor=sup,
            channel_kwargs=dict(io_timeout_s=2.0, ack_window=4,
                                reconnect_backoff_s=(0.1, 1.0)))

    engine = SupervisedScoringEngine(factory, supervisor=sup)
    _seed_store(engine, n_accounts=256)
    service = RiskGrpcService(engine)
    server, health, grpc_port = serve_risk(service, 0)
    sup.bind(health=health, metrics=service.metrics)
    addr = f"localhost:{grpc_port}"

    # Parity probe: UNSEEDED accounts (zero history -> time-invariant
    # features), scored before / during / after the fault. Bit-exact
    # during the outage (single-host local step, same program+params) and
    # after resurrection is the acceptance bar.
    parity_req = risk_pb2.ScoreBatchRequest(transactions=[
        risk_pb2.ScoreTransactionRequest(
            account_id=f"chaos-parity-{i}", amount=700 + 131 * i,
            transaction_type=("deposit", "bet", "withdraw")[i % 3])
        for i in range(24)
    ])
    ch = grpc.insecure_channel(addr)
    batch_call = ch.unary_unary(
        "/risk.v1.RiskService/ScoreBatch",
        request_serializer=risk_pb2.ScoreBatchRequest.SerializeToString,
        response_deserializer=risk_pb2.ScoreBatchResponse.FromString)
    single_call = ch.unary_unary(
        "/risk.v1.RiskService/ScoreTransaction",
        request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
        response_deserializer=risk_pb2.ScoreTransactionResponse.FromString)

    def parity_scores() -> list[int]:
        return [r.score for r in batch_call(parity_req, timeout=60).results]

    parity_before = parity_scores()

    t0 = time.perf_counter()
    stop_at = t0 + duration_s
    lock = threading.Lock()
    events: list[tuple[float, bool]] = []
    errors: list[str] = []
    state_timeline: list[tuple[float, str]] = [(0.0, sup.state)]

    def sample_state() -> None:
        last = sup.state
        while time.perf_counter() < stop_at:
            s_now = sup.state
            if s_now != last:
                state_timeline.append(
                    (round(time.perf_counter() - t0, 3), s_now))
                last = s_now
            time.sleep(0.02)

    load_txs = [
        risk_pb2.ScoreTransactionRequest(
            account_id=f"lg-{i % 256}", amount=1000 + i,
            transaction_type=("deposit", "bet", "withdraw")[i % 3])
        for i in range(rows)
    ]
    load_payload = risk_pb2.ScoreBatchRequest(transactions=load_txs)

    def batch_worker() -> None:
        wch = grpc.insecure_channel(addr)
        call = wch.unary_unary(
            "/risk.v1.RiskService/ScoreBatch",
            request_serializer=risk_pb2.ScoreBatchRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreBatchResponse.FromString)
        while time.perf_counter() < stop_at:
            try:
                call(load_payload, timeout=30)
                ok = True
            except grpc.RpcError as exc:
                ok = False
                with lock:
                    errors.append(repr(exc)[:120])
            with lock:
                events.append((time.perf_counter(), ok))
        wch.close()

    def prober() -> None:
        i = 0
        while time.perf_counter() < stop_at:
            try:
                single_call(risk_pb2.ScoreTransactionRequest(
                    account_id=f"probe-{i % 64}", amount=1000 + i,
                    transaction_type="deposit"), timeout=10)
                ok = True
            except grpc.RpcError as exc:
                ok = False
                with lock:
                    errors.append(repr(exc)[:120])
            with lock:
                events.append((time.perf_counter(), ok))
            i += 1
            time.sleep(0.01)

    threads = [threading.Thread(target=batch_worker) for _ in range(2)]
    threads += [threading.Thread(target=prober),
                threading.Thread(target=sample_state)]
    for t in threads:
        t.start()

    # The fault schedule runs on the main thread: SIGKILL mid-load,
    # restart later, sample parity inside the outage window.
    time.sleep(max(0.0, t0 + kill_at - time.perf_counter()))
    t_kill = time.perf_counter() - t0
    stub.kill()
    stub.wait(timeout=10)
    time.sleep(1.0)  # let detection land before the in-outage parity probe
    parity_during = parity_scores()
    degraded_at = next((t for t, s_ in state_timeline if s_ == "degraded"
                        and t >= t_kill - 0.5), None)

    time.sleep(max(0.0, t0 + restart_at - time.perf_counter()))
    t_restart = time.perf_counter() - t0
    stub2 = start_stub()
    inner = engine.inner
    alive_at = None
    while time.perf_counter() < stop_at:
        if inner._chan.alive:
            alive_at = time.perf_counter() - t0
            break
        time.sleep(0.02)

    for t in threads:
        t.join()
    parity_after = parity_scores()
    recovered_at = next((t for t, s_ in state_timeline
                         if s_ == "serving" and t > t_restart), None)
    ch.close()

    result = {
        "metric": "chaos_follower_kill_soak",
        "scenario": "SIGKILL follower under load, restart, measure healing",
        "duration_s": duration_s,
        "rows_per_rpc": rows,
        "kill_at_s": round(t_kill, 3),
        "restart_at_s": round(t_restart, 3),
        "detection_s": (round(degraded_at - t_kill, 3)
                        if degraded_at is not None else None),
        "resurrection_s": (round(alive_at - t_restart, 3)
                           if alive_at is not None else None),
        "time_to_full_mesh_recovery_s": (
            round(recovered_at - t_kill, 3) if recovered_at is not None else None),
        "availability": availability_block(events, t0, stop_at),
        "state_timeline": state_timeline,
        "parity": {
            "bit_exact_during_outage": parity_during == parity_before,
            "bit_exact_after_recovery": parity_after == parity_before,
        },
        "degraded_steps": inner.degraded_steps,
        "resurrections": inner._chan.resurrections,
        "rebuilds": engine.rebuilds,
        "errors": len(errors),
        "supervisor": sup.snapshot(),
        **({"chaos_plan": plan.snapshot()} if plan is not None else {}),
    }
    print(json.dumps(result))
    graceful_stop(server, health, grace=5, engine=engine)
    stub2.kill()
    ok = (result["parity"]["bit_exact_during_outage"]
          and result["parity"]["bit_exact_after_recovery"]
          and alive_at is not None and recovered_at is not None)
    if errors:
        print("errors:", errors[:5], file=sys.stderr)
    if not ok:
        sys.exit(1)


def main_fleet_chaos() -> None:
    """Fleet chaos soak (``--fleet-chaos``): K scoring replicas as OS
    processes (tools/drills/fleet.py — full production RiskServer wiring
    each) behind the account-affinity router (serve/router.py), broken on
    purpose: sustained mixed load through the L7 router over all N
    replicas while the fault schedule SIGKILLs a replica mid-load and
    restarts it later, with a deterministic router->replica link-drop
    window (chaos seam ``router.forward``) layered on top. The printed
    artifact records per-1s availability through the fault, ring-eviction
    detection time, time-to-readmission after recovery, and the router's
    retry/pushback/hedge accounting.

    Gates (exit 1 on miss): availability >= 99% in every 1 s window,
    detection < 2 s, readmission happened.
    """
    import grpc

    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from tools.drills.fleet import FleetFaultSchedule, ReplicaFleet
    from tools.drills.load_gen import availability_block

    from igaming_platform_tpu.serve import chaos as chaos_mod
    from igaming_platform_tpu.serve.router import ScoringRouter, serve_router

    n_replicas = int(os.environ.get("FLEET_REPLICAS", "3"))
    duration_s = float(os.environ.get("FLEET_CHAOS_DURATION_S", "30"))
    kill_at = float(os.environ.get("FLEET_KILL_AT_S", duration_s / 3))
    restart_at = float(os.environ.get("FLEET_RESTART_AT_S", 2 * duration_s / 3))
    rows = int(os.environ.get("FLEET_ROWS_PER_RPC", "256"))
    victim = int(os.environ.get("FLEET_VICTIM", "1"))

    fleet = ReplicaFleet(n_replicas, batch_size=rows).start()
    result: dict = {
        "metric": "fleet_chaos_soak",
        "scenario": ("replica SIGKILL under load behind the account-"
                     "affinity router, restart, measure ring healing; "
                     "plus a deterministic router->replica link-drop "
                     "window"),
        "replicas": n_replicas,
        "host_cpu_cores": os.cpu_count() or 1,
    }
    try:
        # Deterministic link-drop window on the router.forward seam: ~30%
        # of forwards in ops 150-230 drop, which must surface as retries
        # onto the next ring owner, never as client errors (and never as
        # replica evictions — a flaky link is not replica death).
        plan = chaos_mod.install(
            "seed=7;router.forward=drop:p=0.3:after=150:count=80")
        router = ScoringRouter(
            fleet.router_spec(), health_interval_s=0.2,
            failure_threshold=2, forward_timeout_s=20.0)
        server, health, port = serve_router(router, 0)
        addr = f"localhost:{port}"

        t0 = time.perf_counter()
        stop_at = t0 + duration_s
        lock = threading.Lock()
        events: list[tuple[float, bool]] = []
        errors: list[str] = []

        load_payload = risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(
                account_id=f"lg-{i % 256}", amount=1000 + i,
                transaction_type=("deposit", "bet", "withdraw")[i % 3])
            for i in range(rows)
        ]).SerializeToString()

        def batch_worker() -> None:
            ch = grpc.insecure_channel(addr)
            call = ch.unary_unary(
                "/risk.v1.RiskService/ScoreBatch",
                request_serializer=lambda b: b,
                response_deserializer=lambda b: b)
            while time.perf_counter() < stop_at:
                try:
                    call(load_payload, timeout=20)
                    ok = True
                except grpc.RpcError as exc:
                    ok = False
                    with lock:
                        errors.append(f"{exc.code().name}: "
                                      + repr(exc.details())[:120])
                with lock:
                    events.append((time.perf_counter(), ok))
            ch.close()

        def prober() -> None:
            ch = grpc.insecure_channel(addr)
            call = ch.unary_unary(
                "/risk.v1.RiskService/ScoreTransaction",
                request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
                response_deserializer=risk_pb2.ScoreTransactionResponse.FromString)
            i = 0
            while time.perf_counter() < stop_at:
                try:
                    call(risk_pb2.ScoreTransactionRequest(
                        account_id=f"probe-{i % 64}", amount=1000 + i,
                        transaction_type="deposit"), timeout=10)
                    ok = True
                except grpc.RpcError as exc:
                    ok = False
                    with lock:
                        errors.append(f"{exc.code().name}: "
                                      + repr(exc.details())[:120])
                with lock:
                    events.append((time.perf_counter(), ok))
                i += 1
                time.sleep(0.01)
            ch.close()

        threads = [threading.Thread(target=batch_worker) for _ in range(2)]
        threads.append(threading.Thread(target=prober))
        for t in threads:
            t.start()

        # Default schedule: a brownout window on a NON-victim replica
        # first (supervisor sheds UNAVAILABLE + pushback -> the router
        # must honor the hint and evict on NOT_SERVING, then readmit),
        # then the SIGKILL + restart of the victim.
        bystander = (victim + 1) % n_replicas
        brownout_at = max(1.0, kill_at / 3)
        schedule = FleetFaultSchedule.from_string(os.environ.get(
            "FLEET_FAULTS",
            f"brownout:replica={bystander}:at={brownout_at};"
            f"unbrownout:replica={bystander}:at={brownout_at + 2.5};"
            f"kill:replica={victim}:at={kill_at};"
            f"restart:replica={victim}:at={restart_at}"))
        # Offset between the load clock (perf_counter t0) and the fault
        # clock (monotonic mono0) is negligible: both anchor here.
        mono0 = time.monotonic()
        fault_marks: dict[str, float] = {}

        def on_fault(fault, replica, t_actual_s, done_s) -> None:
            fault_marks[fault.kind] = t_actual_s
            fault_marks[f"{fault.kind}_done"] = done_s

        schedule.run(fleet, mono0, on_fault=on_fault)

        victim_rid = fleet.replicas[victim].rid
        # Bounded wait for readmission: the restarted replica must pass a
        # health probe before the ring takes it back.
        readmit_deadline = time.monotonic() + 15.0
        while (victim_rid not in router.ring.active
               and time.monotonic() < readmit_deadline):
            time.sleep(0.02)

        for t in threads:
            t.join()
        snap = router.snapshot()
        # Watcher event times are monotonic; rebase onto mono0 so the
        # artifact's transitions share the fault clock.
        transitions = [
            {"t": round(t - mono0, 3), "replica": rid, "from": old, "to": new}
            for (t, rid, old, new) in router.watcher.events
        ]
        evicted_at = next(
            (t - mono0 for (t, rid, _old, new) in router.watcher.events
             if rid == victim_rid and new in ("dead", "brownout")
             and t - mono0 >= fault_marks.get("kill", 0)), None)
        readmitted_at = next(
            (t - mono0 for (t, rid, _old, new) in router.watcher.events
             if rid == victim_rid and new == "serving"
             and t - mono0 > fault_marks.get("kill", 0)), None)
        availability = availability_block(events, t0, stop_at)
        result.update({
            "duration_s": duration_s,
            "rows_per_rpc": rows,
            "fault_schedule": schedule.executed,
            "kill_at_s": round(fault_marks.get("kill", -1), 3),
            "restart_done_at_s": round(fault_marks.get("restart_done", -1), 3),
            "ring_eviction_detection_s": (
                round(evicted_at - fault_marks["kill"], 3)
                if evicted_at is not None and "kill" in fault_marks else None),
            # Readmission clock starts when the restarted process is UP
            # (restart_done): it measures the ring's re-admission lag, not
            # the replica's JAX boot time.
            "time_to_readmission_s": (
                round(readmitted_at - fault_marks["restart_done"], 3)
                if readmitted_at is not None and "restart_done" in fault_marks
                else None),
            "replica_restart_boot_s": (
                round(fault_marks["restart_done"] - fault_marks["restart"], 3)
                if "restart_done" in fault_marks else None),
            "availability": availability,
            "router": snap,
            "ring_transitions": transitions,
            "errors": len(errors),
            "error_samples": errors[:5],
            "chaos_plan": plan.snapshot(),
        })
    finally:
        try:
            chaos_mod.clear()
            router.close()
            server.stop(2)
        except Exception:  # noqa: BLE001 — teardown best-effort; artifact already built
            pass
        fleet.stop()

    print(json.dumps(result))
    rates = [r for r in result["availability"]["success_rate_per_window"]
             if r is not None]
    gates = {
        "availability_99_every_window": bool(rates) and min(rates) >= 0.99,
        "detection_under_2s": (
            result["ring_eviction_detection_s"] is not None
            and result["ring_eviction_detection_s"] < 2.0),
        "readmitted": result["time_to_readmission_s"] is not None,
    }
    print(json.dumps({"gates": gates}), file=sys.stderr, flush=True)
    if not all(gates.values()):
        sys.exit(1)


def main_slo_chaos() -> None:
    """SLO-plane chaos soak (``--slo-chaos``) -> ``SLO_ARTIFACT``: proves the
    fleet-wide SLO plane detects, attributes and profiles a latency
    fault, and stays live through replica death. The rig:

    - K replicas (tools/drills/fleet.py, full production RiskServer each)
      behind the L7 router with the fleet aggregation plane
      (``/debug/fleetz``) on the router's sidecar;
    - replica r<victim> boots with a deterministic CHAOS_PLAN delaying
      ``device.dispatch`` (the latency fault — answers stay correct,
      they just blow the 50 ms objective);
    - replica r<casualty> is SIGKILLed mid-run (the liveness fault).

    Gates (exit 1 on miss):
    1. the victim's FAST-window burn-rate alert fires within one fast
       window of its first recorded violation;
    2. budget attribution names the injected stage (``score.dispatch``)
       as the top consumer;
    3. the anomaly detector triggers EXACTLY ONE cooldown-respecting
       profile capture, keyed by the anomalous trace id;
    4. ``/debug/fleetz`` answers fast (bounded, stale-stamped) through
       the SIGKILL — never blocks on the dead replica.
    """
    import urllib.request

    import grpc

    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from tools.drills.fleet import ReplicaFleet

    from igaming_platform_tpu.serve.router import ScoringRouter, serve_router

    n_replicas = int(os.environ.get("SLO_REPLICAS", "3"))
    duration_s = float(os.environ.get("SLO_SOAK_DURATION_S", "40"))
    kill_at = float(os.environ.get("SLO_KILL_AT_S", 0.65 * duration_s))
    rows = int(os.environ.get("SLO_ROWS_PER_RPC", "256"))
    victim = int(os.environ.get("SLO_VICTIM", "1"))
    casualty = int(os.environ.get("SLO_CASUALTY", "2"))
    delay_ms = int(os.environ.get("SLO_FAULT_DELAY_MS", "150"))
    fault_after_ops = int(os.environ.get("SLO_FAULT_AFTER_OPS", "600"))
    fast_window_s = float(os.environ.get("SLO_FAST_WINDOW_S", "8"))

    # Shared SLO/telemetry env: short fast window so the alert clock fits
    # a 40 s soak; long anomaly cooldown so gate 3 is exactly-one; the
    # victim additionally carries the dispatch-delay chaos plan.
    slo_env = {
        "SLO_FAST_WINDOW_S": str(fast_window_s),
        "SLO_SLOW_WINDOW_S": "120",
        "SLO_FAST_BURN_ALERT": "10",
        "SLO_SLOW_BURN_ALERT": "1",
        "ANOMALY_PROFILE_COOLDOWN_S": "600",
        "ANOMALY_PROFILE_SECONDS": "0.5",
        "ANOMALY_WARMUP_STEPS": "20",
    }
    victim_env = {
        "CHAOS_PLAN": (
            f"seed=9;device.dispatch=delay:p=1.0:ms={delay_ms}"
            f":after={fault_after_ops}:count=1000000"),
    }
    fleet = ReplicaFleet(
        n_replicas, batch_size=rows, env_extra=slo_env,
        env_by_replica={victim: victim_env}).start()
    victim_http = fleet.replicas[victim].http_addr
    casualty_rid = fleet.replicas[casualty].rid
    result: dict = {
        "metric": "slo_chaos_soak",
        "scenario": (
            f"device.dispatch delay ({delay_ms} ms) on one replica must "
            "fire the fast-window burn alert, attribute the budget to "
            "score.dispatch and auto-capture exactly one profile; "
            "/debug/fleetz must stay live through a second replica's "
            "SIGKILL"),
        "replicas": n_replicas,
        "host_cpu_cores": os.cpu_count() or 1,
        "objective_ms": 50.0,
        "fast_window_s": fast_window_s,
        "fault_delay_ms": delay_ms,
    }
    router = None
    server = None
    try:
        router = ScoringRouter(
            fleet.router_spec(), health_interval_s=0.2,
            failure_threshold=2, forward_timeout_s=20.0)
        server, health, port = serve_router(router, 0, http_port=0)
        addr = f"localhost:{port}"
        fleetz_addr = f"localhost:{router.http_port}"

        t0 = time.perf_counter()
        stop_at = t0 + duration_s
        lock = threading.Lock()
        errors: list[str] = []
        ok_count = [0]

        load_payload = risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(
                account_id=f"slo-{i % 256}", amount=1000 + i,
                transaction_type=("deposit", "bet", "withdraw")[i % 3])
            for i in range(rows)
        ]).SerializeToString()

        def batch_worker() -> None:
            ch = grpc.insecure_channel(addr)
            call = ch.unary_unary(
                "/risk.v1.RiskService/ScoreBatch",
                request_serializer=lambda b: b,
                response_deserializer=lambda b: b)
            while time.perf_counter() < stop_at:
                try:
                    call(load_payload, timeout=20)
                    with lock:
                        ok_count[0] += 1
                except grpc.RpcError as exc:
                    with lock:
                        errors.append(f"{exc.code().name}: "
                                      + repr(exc.details())[:120])
            ch.close()

        def prober() -> None:
            ch = grpc.insecure_channel(addr)
            call = ch.unary_unary(
                "/risk.v1.RiskService/ScoreTransaction",
                request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
                response_deserializer=risk_pb2.ScoreTransactionResponse.FromString)
            i = 0
            while time.perf_counter() < stop_at:
                try:
                    call(risk_pb2.ScoreTransactionRequest(
                        account_id=f"probe-{i % 64}", amount=1000 + i,
                        transaction_type="deposit"), timeout=10)
                    with lock:
                        ok_count[0] += 1
                except grpc.RpcError as exc:
                    with lock:
                        errors.append(f"{exc.code().name}: "
                                      + repr(exc.details())[:120])
                i += 1
                time.sleep(0.01)
            ch.close()

        # SLO-plane poller: watches the victim's /debug/sloz for the
        # first violation and the fast alert, and times /debug/fleetz
        # polls through the SIGKILL window (gate 4's evidence).
        marks: dict = {"first_violation_s": None, "fast_alert_s": None,
                       "fleetz_polls": 0, "fleetz_max_ms": 0.0,
                       "fleetz_errors": 0}

        def http_json(addr_: str, path: str, timeout: float = 3.0):
            with urllib.request.urlopen(
                    f"http://{addr_}{path}", timeout=timeout) as resp:
                return json.loads(resp.read())

        def poller() -> None:
            while time.perf_counter() < stop_at:
                now_s = time.perf_counter() - t0
                try:
                    sloz = http_json(victim_http, "/debug/sloz", 1.5)
                    if (marks["first_violation_s"] is None
                            and sloz.get("violations_total", 0) > 0):
                        marks["first_violation_s"] = round(now_s, 3)
                    if (marks["fast_alert_s"] is None
                            and sloz["windows"]["fast"]["alert"]):
                        marks["fast_alert_s"] = round(now_s, 3)
                except Exception:  # noqa: BLE001 — victim sloz poll is measurement, not load
                    pass
                tq0 = time.perf_counter()
                try:
                    http_json(fleetz_addr, "/debug/fleetz", 5.0)
                    marks["fleetz_polls"] += 1
                    marks["fleetz_max_ms"] = max(
                        marks["fleetz_max_ms"],
                        (time.perf_counter() - tq0) * 1000.0)
                except Exception:  # noqa: BLE001 — a failed poll IS the measurement
                    marks["fleetz_errors"] += 1
                time.sleep(0.2)

        threads = [threading.Thread(target=batch_worker) for _ in range(2)]
        threads.append(threading.Thread(target=prober))
        threads.append(threading.Thread(target=poller))
        for t in threads:
            t.start()

        # The liveness fault: SIGKILL the casualty replica mid-run.
        delay = t0 + kill_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        fleet.replicas[casualty].kill()
        kill_done_s = time.perf_counter() - t0

        for t in threads:
            t.join()

        # Post-run evidence, straight off the debug surfaces.
        victim_sloz = http_json(victim_http, "/debug/sloz", 5.0)
        victim_telemetry = http_json(victim_http, "/debug/telemetryz", 5.0)
        # Give the fleetview one more tick so the dead replica's
        # staleness stamp has settled, then snapshot.
        time.sleep(2.0)
        fleetz = http_json(fleetz_addr, "/debug/fleetz", 5.0)

        captures = victim_telemetry.get("profile_captures", [])
        attribution = victim_sloz["windows"]["slow"]["budget_attribution"]
        casualty_block = next(
            (r for r in fleetz["replicas"] if r["replica"] == casualty_rid),
            None)
        result.update({
            "duration_s": duration_s,
            "kill_at_s": round(kill_done_s, 3),
            "requests_ok": ok_count[0],
            "errors": len(errors),
            "error_samples": errors[:5],
            "first_violation_s": marks["first_violation_s"],
            "fast_alert_s": marks["fast_alert_s"],
            "alert_latency_s": (
                round(marks["fast_alert_s"] - marks["first_violation_s"], 3)
                if marks["fast_alert_s"] is not None
                and marks["first_violation_s"] is not None else None),
            "victim_slo": {
                "requests_total": victim_sloz["requests_total"],
                "violations_total": victim_sloz["violations_total"],
                "fast": victim_sloz["windows"]["fast"],
                "budget_attribution_slow": attribution,
                "alert_events": victim_sloz["alert_events"],
                "by_state": victim_sloz["by_state"],
            },
            "victim_telemetry": {
                "anomalies_total": victim_telemetry.get("anomalies_total"),
                "profile_captures": captures,
                "step_time": victim_telemetry.get("step_time"),
                "compile": victim_telemetry.get("compile"),
                "dispatches_total": victim_telemetry.get("dispatches_total"),
            },
            "fleetz": {
                "polls": marks["fleetz_polls"],
                "poll_errors": marks["fleetz_errors"],
                "max_poll_ms": round(marks["fleetz_max_ms"], 3),
                "casualty_block": casualty_block,
                "stage_latency": fleetz.get("fleet_stage_latency_ms"),
                "slowest_trace": (fleetz.get("slowest_traces") or [None])[0],
            },
        })
    finally:
        try:
            if router is not None:
                router.close()
            if server is not None:
                server.stop(2)
        except Exception:  # noqa: BLE001 — teardown best-effort; artifact already built
            pass
        fleet.stop()

    captures = result.get("victim_telemetry", {}).get("profile_captures", [])
    gates = {
        "fast_alert_fired_within_window": (
            result.get("alert_latency_s") is not None
            and result["alert_latency_s"] <= fast_window_s + 1.0),
        "attribution_names_injected_stage": (
            result.get("victim_slo", {}).get(
                "budget_attribution_slow", {}).get("top_stage")
            == "score.dispatch"),
        "exactly_one_profile_capture": (
            len(captures) == 1 and bool(captures[0].get("trace_id"))),
        "fleetz_live_through_kill": (
            result.get("fleetz", {}).get("polls", 0) > 0
            and result.get("fleetz", {}).get("poll_errors", 1) == 0
            and result.get("fleetz", {}).get("max_poll_ms", 1e9) < 2000.0
            and bool((result.get("fleetz", {}).get("casualty_block")
                      or {}).get("stale"))),
    }
    result["gates"] = gates
    out_path = _artifact_path("SLO_ARTIFACT", "SLO_r09.json")
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    print(json.dumps({"gates": gates}), file=sys.stderr, flush=True)
    if not all(gates.values()):
        sys.exit(1)


def main_ledger_chaos() -> None:
    """Ledger chaos soak (``--chaos-ledger``): one production-wired risk
    server as an OS process (tools/drills/fleet.py replica protocol) with a
    durable decision ledger (LEDGER_DIR) draining to a ClickHouse-shaped
    sink owned by THIS harness — then the audit pipeline is broken every
    way the acceptance criterion names, under live mixed load:

    1. **fs outage** — a CHAOS_PLAN window of ``ledger.append=error``
       inside the server (WAL writes fail; scoring must be untouched,
       drops counted, the ``ledger`` breaker opens);
    2. **sink outage** — the harness's ClickHouse endpoint returns 500
       for a wall-clock window (the drainer falls behind and must catch
       up from the WAL at its cursor);
    3. **degraded window** — POST /debug/breakers forces the device
       circuit open, so DEGRADED_CPU_HEURISTIC decisions land in the
       ledger and must replay through the same heuristic tier;
    4. **SIGKILL mid-run** — the server dies without a goodbye and
       restarts on the SAME ledger dir (torn-tail truncation, sink
       cursor resume).

    Afterwards ``tools/replay.py`` re-scores the surviving WAL bit-exact
    and the verdict + gates land in ``LEDGER_CHAOS_OUT``. Gates (exit 1 on
    miss): zero replay mismatches with degraded decisions included,
    zero scoring errors outside the kill outage window, and every WAL
    record delivered to the sink at least once.
    """
    import tempfile
    import urllib.request
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import grpc

    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from tools.drills.fleet import ReplicaProc
    from tools.drills.load_gen import availability_block

    duration_s = float(os.environ.get("LEDGER_CHAOS_DURATION_S", 30.0))
    rows = int(os.environ.get("LEDGER_CHAOS_ROWS_PER_RPC", 256))
    degrade_at = float(os.environ.get("LEDGER_CHAOS_DEGRADE_AT_S", 0.1 * duration_s))
    degrade_for = 2.5
    sink_out_at = float(os.environ.get("LEDGER_CHAOS_SINK_OUT_AT_S", 0.22 * duration_s))
    sink_out_for = float(os.environ.get("LEDGER_CHAOS_SINK_OUT_FOR_S", 0.13 * duration_s))
    kill_at = float(os.environ.get("LEDGER_CHAOS_KILL_AT_S", 0.45 * duration_s))
    restart_at = float(os.environ.get("LEDGER_CHAOS_RESTART_AT_S", 0.65 * duration_s))
    chaos_plan = os.environ.get(
        "LEDGER_CHAOS_PLAN", "seed=11;ledger.append=error:p=1.0:after=60:count=40")

    # -- harness-owned ClickHouse-shaped sink endpoint -----------------------
    sink_rows: list[dict] = []
    sink_state = {"fail": False, "inserts": 0, "rejected": 0}
    sink_lock = threading.Lock()

    class _SinkHandler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            size = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(size).decode()
            with sink_lock:
                if sink_state["fail"]:
                    sink_state["rejected"] += 1
                    self.send_response(500)
                    self.end_headers()
                    self.wfile.write(b"Code: 999. DB::Exception: chaos outage")
                    return
                if body.startswith("INSERT INTO"):
                    sink_state["inserts"] += 1
                    for line in body.splitlines()[1:]:
                        if line.strip():
                            sink_rows.append(json.loads(line))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

    sink_httpd = ThreadingHTTPServer(("127.0.0.1", 0), _SinkHandler)
    threading.Thread(target=sink_httpd.serve_forever, daemon=True).start()
    sink_url = f"http://127.0.0.1:{sink_httpd.server_address[1]}"

    ledger_dir = tempfile.mkdtemp(prefix="soak-ledger-")
    replica = ReplicaProc("ledger-0", batch_size=rows, env_extra={
        "LEDGER_DIR": ledger_dir,
        "LEDGER_SINK": "clickhouse",
        "LEDGER_CLICKHOUSE_URL": sink_url,
        "LEDGER_FSYNC_MS": "10",
        "CHAOS_PLAN": chaos_plan,
    })
    replica.spawn()
    addr = replica.addr

    t0 = time.perf_counter()
    # Mutable stop mark: the restart blocks on a full JAX boot, so the
    # post-restart load tail is anchored to restart COMPLETION — the
    # recovered-after-kill gate needs live traffic against the reborn
    # process, not a clock that expired while it booted.
    stop_box = [t0 + duration_s]
    lock = threading.Lock()
    events: list[tuple[float, bool]] = []
    errors: list[str] = []
    shed = [0]

    load_payload = risk_pb2.ScoreBatchRequest(transactions=[
        risk_pb2.ScoreTransactionRequest(
            account_id=f"lg-{i % 128}", amount=1000 + i,
            transaction_type=("deposit", "bet", "withdraw")[i % 3])
        for i in range(rows)
    ]).SerializeToString()

    def _note(ok: bool, exc=None) -> None:
        with lock:
            events.append((time.perf_counter(), ok))
            if not ok and exc is not None:
                errors.append(repr(exc)[:120])

    class _Caller:
        """One client's unary call with real-world channel hygiene: a
        reconnect-backoff cap (the fleet router's lesson — a 12 s kill
        window otherwise grows gRPC's dial backoff past the restart) AND
        a channel rebuild after a failure streak (a grpc-python channel
        whose peer died by SIGKILL can wedge its subchannel fd — a fresh
        dial succeeds while the old channel reports 'FD Shutdown'
        timeouts forever)."""

        _OPTS = [("grpc.max_reconnect_backoff_ms", 1000),
                 ("grpc.initial_reconnect_backoff_ms", 200)]

        def __init__(self, method: str, req_ser, resp_des):
            self._method = method
            self._req_ser = req_ser
            self._resp_des = resp_des
            self._consec = 0
            self._ch = None
            self._rebuild()

        def _rebuild(self) -> None:
            if self._ch is not None:
                self._ch.close()
            self._ch = grpc.insecure_channel(addr, options=self._OPTS)
            self._call = self._ch.unary_unary(
                self._method, request_serializer=self._req_ser,
                response_deserializer=self._resp_des)

        def __call__(self, payload, timeout: float):
            try:
                resp = self._call(payload, timeout=timeout)
            except grpc.RpcError:
                self._consec += 1
                if self._consec % 25 == 0:
                    self._rebuild()
                raise
            self._consec = 0
            return resp

        def close(self) -> None:
            self._ch.close()

    def batch_worker() -> None:
        call = _Caller("/risk.v1.RiskService/ScoreBatch",
                       lambda b: b, lambda b: b)
        while time.perf_counter() < stop_box[0]:
            try:
                call(load_payload, timeout=20)
                _note(True)
            except grpc.RpcError as exc:
                if exc.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                    with lock:
                        shed[0] += 1
                    time.sleep(0.02)
                else:
                    _note(False, exc)
                    time.sleep(0.05)  # no hot-spin against a dead socket
            time.sleep(0.005)
        call.close()

    def prober() -> None:
        call = _Caller(
            "/risk.v1.RiskService/ScoreTransaction",
            risk_pb2.ScoreTransactionRequest.SerializeToString,
            risk_pb2.ScoreTransactionResponse.FromString)
        i = 0
        while time.perf_counter() < stop_box[0]:
            try:
                call(risk_pb2.ScoreTransactionRequest(
                    account_id=f"probe-{i % 64}", amount=1000 + i,
                    transaction_type="deposit"), timeout=10)
                _note(True)
            except grpc.RpcError as exc:
                _note(False, exc)
                time.sleep(0.05)  # no hot-spin against a dead socket
            i += 1
            time.sleep(0.01)
        call.close()

    threads = [threading.Thread(target=batch_worker) for _ in range(2)]
    threads.append(threading.Thread(target=prober))
    for t in threads:
        t.start()
    load_tail_s = max(3.0, duration_s - restart_at)

    def _breaker(action: str) -> None:
        req = urllib.request.Request(
            f"http://{replica.http_addr}/debug/breakers",
            data=json.dumps({"dep": "device", "action": action}).encode(),
            method="POST")
        urllib.request.urlopen(req, timeout=5).read()

    def _sleep_until(offset_s: float) -> None:
        time.sleep(max(0.0, t0 + offset_s - time.perf_counter()))

    # Fault schedule (main thread).
    _sleep_until(degrade_at)
    _breaker("open")
    _sleep_until(degrade_at + degrade_for)
    _breaker("clear")
    _sleep_until(sink_out_at)
    with sink_lock:
        sink_state["fail"] = True
    _sleep_until(sink_out_at + sink_out_for)
    with sink_lock:
        sink_state["fail"] = False
    _sleep_until(kill_at)
    t_kill = time.perf_counter() - t0
    replica.kill()
    _sleep_until(restart_at)
    replica.restart()  # same ports, same LEDGER_DIR: torn-tail recovery
    t_restart_done = time.perf_counter() - t0
    stop_box[0] = max(stop_box[0], time.perf_counter() + load_tail_s)

    for t in threads:
        t.join()
    stop_at = stop_box[0]
    # Let the sink drain fully (it is healthy again) before the graceful
    # stop — /debug/ledgerz exposes the lag the runbook reads.
    drain_deadline = time.monotonic() + 20.0
    while time.monotonic() < drain_deadline:
        try:
            with urllib.request.urlopen(
                    f"http://{replica.http_addr}/debug/ledgerz",
                    timeout=3) as resp:
                snap = json.loads(resp.read())
            if snap["sink"]["lag"] == 0:
                break
        except Exception:  # noqa: BLE001 — sidecar gone: proceed to stop
            break
        time.sleep(0.25)
    # Graceful stop: the server drains admitted RPCs, the ledger flushes
    # its WAL and gives the (healthy again) sink a catch-up window.
    replica.terminate()
    sink_httpd.shutdown()

    # -- replay the surviving WAL bit-exact ----------------------------------
    from igaming_platform_tpu.serve.ledger import iter_records
    from tools.replay import replay_directory

    wal_ids = [r.decision_id for r in iter_records(ledger_dir)]
    verdict = replay_directory(ledger_dir, batch=rows)
    sink_ids = {r["decision_id"] for r in sink_rows}
    missing_from_sink = [i for i in wal_ids if i not in sink_ids]

    # Errors OUTSIDE the kill outage window are unexplained — the ledger
    # faults (fs outage, sink outage, degraded window) must never produce
    # one. A short grace after restart covers client channel re-dial.
    outage_lo, outage_hi = t0 + t_kill, t0 + t_restart_done + 3.0
    errors_outside_outage = sum(
        1 for (te, ok) in events if not ok and not (outage_lo <= te <= outage_hi))

    availability = availability_block(events, t0, stop_at)
    result = {
        "metric": "ledger_chaos_soak",
        "scenario": ("fs-outage + sink-outage + forced-degraded window + "
                     "mid-run SIGKILL of the server process; replay the "
                     "surviving WAL bit-exact"),
        "duration_s": duration_s,
        "rows_per_rpc": rows,
        "chaos_plan": chaos_plan,
        "degraded_window_s": [degrade_at, degrade_at + degrade_for],
        "sink_outage_s": [sink_out_at, sink_out_at + sink_out_for],
        "kill_at_s": round(t_kill, 3),
        "restart_done_at_s": round(t_restart_done, 3),
        "availability": availability,
        "bulk_shed": shed[0],
        "errors_total": len(errors),
        "errors_outside_outage_window": errors_outside_outage,
        "error_samples": errors[:5],
        "wal_records": len(wal_ids),
        "sink_rows": len(sink_rows),
        "sink_inserts": sink_state["inserts"],
        "sink_rejected_during_outage": sink_state["rejected"],
        "sink_missing_records": len(missing_from_sink),
        "ledger_dir": ledger_dir,
        "replay": verdict,
    }
    gates = {
        "replay_bit_exact": bool(verdict["ok"]),
        "degraded_decisions_replayed": verdict["replayed_by_tier"].get(
            "heuristic", 0) > 0,
        "zero_scoring_errors_outside_kill_window": errors_outside_outage == 0,
        "sink_delivery_complete": not missing_from_sink,
        "recovered_after_kill": any(
            ok for (te, ok) in events if te > t0 + t_restart_done),
    }
    result["gates"] = gates
    out_path = _artifact_path("LEDGER_CHAOS_OUT", "REPLAY_r08.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    print(json.dumps({"gates": gates}), file=sys.stderr, flush=True)
    if not all(gates.values()):
        sys.exit(1)


def main_drift_chaos() -> None:
    """Drift-observatory chaos soak (``--drift-chaos``) -> ``DRIFT_ARTIFACT``:
    the streaming drift plane (obs/drift.py) proven end-to-end on one
    production server process under live load, three arms plus a fleet
    phase:

    1. **clean baseline** — known-clean traffic warms the rolling
       window; the harness pins it as the reference
       (POST /debug/driftz pin_reference) and the observatory must stay
       QUIET through a further clean window (no false alert);
    2. **injected ramp** — a deterministic ``DriftRamp``
       (train/fraudgen.py; the same knob ``load_gen --drift-ramp``
       exposes) multiplies transaction amounts 1 -> DRIFT_SOAK_MULT;
       the ``input`` drift alert must RAISE within the alert bound, and
       a pending promotion must be HELD by the ``drift_quiet`` gate
       (the gate table, drift_quiet ok=false, lands in the artifact);
    3. **ramp removal** — amounts return to baseline; the alert must
       CLEAR within the rolling window plus slack.

    Fleet phase: a 3-replica rig (tools/drills/fleet.py) behind the L7
    router's aggregation plane — ``/debug/fleetz`` must serve MERGED
    per-feature drift state (bucket-wise sketch sum, loud on mixed
    edges), keep answering fast through a replica SIGKILL, and
    stale-stamp the dead replica.

    The outcome backfill rides the fixed POST /debug/outcomes (accepted
    vs unknown decision-id counts land in the artifact).
    Gates (exit 1 on miss) cover all of the above.
    """
    import tempfile
    import urllib.error
    import urllib.request

    import grpc

    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from tools.drills.fleet import ReplicaFleet, ReplicaProc
    from igaming_platform_tpu.serve.router import ScoringRouter, serve_router
    from igaming_platform_tpu.train.fraudgen import DriftRamp

    window_s = float(os.environ.get("DRIFT_WINDOW_S", "8"))
    ref_warm_s = float(os.environ.get("DRIFT_SOAK_REF_WARM_S", "12"))
    clean_s = float(os.environ.get("DRIFT_SOAK_CLEAN_S", "10"))
    ramp_s = float(os.environ.get("DRIFT_SOAK_RAMP_S", "24"))
    clear_s = float(os.environ.get("DRIFT_SOAK_CLEAR_S", "20"))
    mult = float(os.environ.get("DRIFT_SOAK_MULT", "8"))
    ramp_up_s = float(os.environ.get("DRIFT_SOAK_RAMP_UP_S", "5"))
    alert_bound_s = float(os.environ.get(
        "DRIFT_SOAK_ALERT_BOUND_S", str(window_s + 6.0)))
    clear_bound_s = float(os.environ.get(
        "DRIFT_SOAK_CLEAR_BOUND_S", str(window_s + 8.0)))
    outcome_rate = float(os.environ.get("ONLINE_OUTCOME_RATE", "0.6"))

    # The injected schedule, recorded verbatim (run fraction is relative
    # to the ramp window; deterministic given the wall timeline).
    ramp = DriftRamp(features=("tx_amount",), scale_mult=mult,
                     start_frac=0.0, end_frac=max(1e-6, ramp_up_s / ramp_s))

    ledger_dir = tempfile.mkdtemp(prefix="soak-drift-")
    replica = ReplicaProc("drift-0", batch_size=128, env_extra={
        "LEDGER_DIR": ledger_dir,
        "LEDGER_FSYNC_MS": "10",
        "RISK_REVIEW_THRESHOLD": os.environ.get("RISK_REVIEW_THRESHOLD", "30"),
        # Online loop (PR 9 rig bounds — see --online-chaos): candidates
        # churn every tick so a gate table exists to HOLD during drift.
        "ONLINE_LOOP": "1",
        "ONLINE_TICK_S": os.environ.get("ONLINE_TICK_S", "1.0"),
        "ONLINE_STEPS_PER_TICK": os.environ.get("ONLINE_STEPS_PER_TICK", "25"),
        "ONLINE_MIN_EXAMPLES": os.environ.get("ONLINE_MIN_EXAMPLES", "48"),
        "ONLINE_TRUNK": os.environ.get("ONLINE_TRUNK", "32,32"),
        "ONLINE_BATCH": os.environ.get("ONLINE_BATCH", "256"),
        "ONLINE_MINED_FRAC": os.environ.get("ONLINE_MINED_FRAC", "0.3"),
        "PROMOTE_MIN_AUC": os.environ.get("PROMOTE_MIN_AUC", "0.8"),
        "PROMOTE_MIN_POST_AUC": os.environ.get("PROMOTE_MIN_POST_AUC", "0.7"),
        "PROMOTE_MIN_SHADOW_ROWS": "64",
        "PROMOTE_MAX_FLIP_RATE": os.environ.get("PROMOTE_MAX_FLIP_RATE", "1.0"),
        "PROMOTE_COOLDOWN_S": "0",
        "PROMOTE_PROBE_ROWS": "1024",
        # Drift plane: short window so the alert clock fits the soak.
        "DRIFT_WINDOW_S": str(window_s),
        "DRIFT_BUCKET_S": "1",
        "DRIFT_MIN_ROWS": os.environ.get("DRIFT_MIN_ROWS", "300"),
        # Calibration stays advisory on this short rig (binomial noise
        # on a few hundred outcomes must not confound the input-drift
        # clean gate); the unit suite pins the calibration alert path.
        "DRIFT_CAL_ALERT": os.environ.get("DRIFT_CAL_ALERT", "0.35"),
        "DRIFT_CAL_MIN_OUTCOMES": os.environ.get(
            "DRIFT_CAL_MIN_OUTCOMES", "400"),
    })
    replica.spawn()

    t0 = time.perf_counter()
    total_s = ref_warm_s + clean_s + ramp_s + clear_s
    stop_at = t0 + total_s
    lock = threading.Lock()
    events: list[tuple[float, bool]] = []
    errors: list[str] = []
    outcome_q: deque = deque()
    backfill = {"accepted": 0, "unknown": 0, "submitted": 0, "posts": 0,
                "bad_request_rejected": False}
    # Ramp state the workers read: (active_since | None).
    ramp_box: list[float | None] = [None]

    def amp_now() -> float:
        with lock:
            since = ramp_box[0]
        if since is None:
            return 1.0
        frac = min((time.perf_counter() - since) / ramp_s, 1.0)
        m, _shift = ramp.factors(frac)
        return m

    def _note(ok: bool, exc=None) -> None:
        with lock:
            events.append((time.perf_counter(), ok))
            if not ok and exc is not None:
                errors.append(repr(exc)[:120])

    def _http_json(path: str, payload: dict | None = None,
                   timeout: float = 5.0):
        url = f"http://{replica.http_addr}{path}"
        if payload is None:
            with urllib.request.urlopen(url, timeout=timeout) as resp:
                return json.loads(resp.read())
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(), method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    def score_worker(wid: int) -> None:
        wrng = np.random.default_rng(300 + wid)
        ch = grpc.insecure_channel(replica.addr)
        call = ch.unary_unary(
            "/risk.v1.RiskService/ScoreTransaction",
            request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreTransactionResponse.FromString)
        i = 0
        while time.perf_counter() < stop_at:
            big = wrng.random() < 0.4
            base = int(wrng.integers(60_000, 250_000) if big
                       else wrng.integers(100, 9_000))
            amount = max(1, int(base * amp_now()))
            req = risk_pb2.ScoreTransactionRequest(
                account_id=f"dr-{wid}-{i % 96}", amount=amount,
                transaction_type="withdraw" if big else
                ("deposit", "bet")[i % 2])
            try:
                _resp, rpc = call.with_call(req, timeout=10)
                _note(True)
                md = dict(rpc.trailing_metadata() or ())
                did = md.get("risk-decision-id", "")
                if did and wrng.random() < outcome_rate:
                    label = int(wrng.random() < (0.75 if big else 0.05))
                    with lock:
                        outcome_q.append((did, label))
            except grpc.RpcError as exc:
                if exc.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                    time.sleep(0.02)
                else:
                    _note(False, exc)
                    time.sleep(0.05)
            i += 1
            time.sleep(0.004)
        ch.close()

    def outcome_poster() -> None:
        """Backfill via the FIXED /debug/outcomes: accepted/unknown
        counts accumulate into the artifact (the join-health evidence
        the old silent-200 endpoint could not give)."""
        while time.perf_counter() < stop_at:
            batch = []
            with lock:
                while outcome_q and len(batch) < 64:
                    did, label = outcome_q.popleft()
                    batch.append({"decision_id": did, "label": label,
                                  "source": ("chargeback" if label
                                             else "dispute_cleared")})
            if batch:
                try:
                    resp = _http_json("/debug/outcomes", {"outcomes": batch})
                    with lock:
                        backfill["accepted"] += resp.get("accepted", 0)
                        backfill["unknown"] += resp.get("unknown", 0)
                        backfill["submitted"] += resp.get("submitted", 0)
                        backfill["posts"] += 1
                except Exception:  # noqa: BLE001 — retried next round
                    with lock:
                        for row in batch:
                            outcome_q.append((row["decision_id"],
                                              row["label"]))
                    time.sleep(0.5)
            time.sleep(0.25)

    workers = [threading.Thread(target=score_worker, args=(w,))
               for w in range(3)]
    workers.append(threading.Thread(target=outcome_poster))
    for t in workers:
        t.start()

    # Malformed-body probe: the old endpoint answered 200 to garbage.
    try:
        req = urllib.request.Request(
            f"http://{replica.http_addr}/debug/outcomes",
            data=json.dumps({"outcomes": [{"label": 1}]}).encode(),
            method="POST")
        urllib.request.urlopen(req, timeout=5)
    except urllib.error.HTTPError as exc:
        backfill["bad_request_rejected"] = exc.code == 400

    marks: dict = {
        "pinned_at_s": None, "clean_input_alerts_seen": 0,
        "clean_alerts_by_kind": {}, "clean_polls": 0,
        "ramp_start_s": None, "input_alert_s": None,
        "held_table": None, "held_at_s": None, "alerts_at_hold": None,
        "ramp_end_s": None, "alert_clear_s": None,
        "promotions_preramp": 0,
    }

    def _driftz() -> dict | None:
        try:
            return _http_json("/debug/driftz", timeout=3.0)
        except Exception:  # noqa: BLE001 — polled measurement
            return None

    # -- phase 0: warm the window, pin the reference -------------------------
    time.sleep(max(0.0, t0 + ref_warm_s - time.perf_counter()))
    pin_resp = None
    for _attempt in range(10):
        try:
            pin_resp = _http_json("/debug/driftz",
                                  {"action": "pin_reference",
                                   "source": "drift-soak-clean-warmup"})
            marks["pinned_at_s"] = round(time.perf_counter() - t0, 3)
            break
        except urllib.error.HTTPError:
            time.sleep(1.0)  # window still too thin; traffic is filling it
    # -- arm 1: clean observation (no false alert) ---------------------------
    clean_end = time.perf_counter() + clean_s
    while time.perf_counter() < clean_end:
        snap = _driftz()
        if snap:
            marks["clean_polls"] += 1
            # The false-positive gate is on INPUT drift: the online
            # loop's own promotions legitimately shift the SCORE
            # distribution vs the pre-promotion reference (the output
            # sketches catching a deliberate model change — recorded by
            # kind, not a false positive).
            if snap["alerts"].get("input"):
                marks["clean_input_alerts_seen"] += 1
            for kind, active in snap["alerts"].items():
                if active:
                    marks["clean_alerts_by_kind"][kind] = (
                        marks["clean_alerts_by_kind"].get(kind, 0) + 1)
        time.sleep(0.5)
    try:
        shadowz = _http_json("/debug/shadowz", timeout=5.0)
        marks["promotions_preramp"] = shadowz["promotion"]["promotions"]
    except Exception:  # noqa: BLE001 — artifact field only
        pass

    # -- arm 2: injected ramp must RAISE + HOLD promotion --------------------
    with lock:
        ramp_box[0] = time.perf_counter()
    marks["ramp_start_s"] = round(time.perf_counter() - t0, 3)
    ramp_end = time.perf_counter() + ramp_s
    while time.perf_counter() < ramp_end:
        snap = _driftz()
        now_s = time.perf_counter() - t0
        if snap and snap["alerts"].get("input") and marks["input_alert_s"] is None:
            marks["input_alert_s"] = round(now_s, 3)
        if marks["input_alert_s"] is not None and marks["held_table"] is None:
            # Force a controller tick so the gate table is computed NOW,
            # against the currently-alerting drift plane.
            try:
                _http_json("/debug/promotion", {"action": "tick"}, timeout=15.0)
                shadowz = _http_json("/debug/shadowz", timeout=5.0)
                alerts_now = (_driftz() or {}).get("alerts") or {}
                table = shadowz["promotion"].get("last_gate_table") or {}
                row = table.get("drift_quiet")
                # The held evidence must be taken WHILE the injected
                # input alert is active — a hold from a coincident
                # score/calibration alert would be weaker evidence.
                if row and not row["ok"] and alerts_now.get("input"):
                    marks["held_table"] = table
                    marks["held_at_s"] = round(time.perf_counter() - t0, 3)
                    marks["alerts_at_hold"] = alerts_now
            except Exception:  # noqa: BLE001 — re-tried next poll
                pass
        time.sleep(0.5)

    # -- arm 3: ramp removal must CLEAR --------------------------------------
    with lock:
        ramp_box[0] = None
    marks["ramp_end_s"] = round(time.perf_counter() - t0, 3)
    clear_end = time.perf_counter() + clear_s
    while time.perf_counter() < clear_end:
        snap = _driftz()
        if (snap and not snap["alerts"].get("input")
                and marks["alert_clear_s"] is None
                and marks["input_alert_s"] is not None):
            marks["alert_clear_s"] = round(time.perf_counter() - t0, 3)
            break
        time.sleep(0.5)

    final_driftz = _driftz() or {}
    final_driftz.pop("reference_state", None)  # bulky; meta block stays
    final_window_vec = (final_driftz.get("window") or {}).pop("vec", None)
    del final_window_vec  # artifact carries summaries, not raw vectors
    for t in workers:
        t.join()
    replica.terminate()

    # -- fleet phase: merged drift state stays live through a kill -----------
    fleet_marks: dict = {"polls": 0, "poll_errors": 0, "max_poll_ms": 0.0,
                         "rows": 0, "merge_errors": None,
                         "casualty_stale": False}
    fleet = ReplicaFleet(3, batch_size=256, env_extra={
        "DRIFT_WINDOW_S": "20", "DRIFT_BUCKET_S": "2"}).start()
    router = None
    server = None
    try:
        router = ScoringRouter(fleet.router_spec(), health_interval_s=0.2,
                               failure_threshold=2, forward_timeout_s=20.0)
        server, _health, port = serve_router(router, 0, http_port=0)
        fleetz_addr = f"localhost:{router.http_port}"
        casualty_rid = fleet.replicas[2].rid

        payload = risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(
                account_id=f"fd-{i % 256}", amount=1000 + i,
                transaction_type=("deposit", "bet", "withdraw")[i % 3])
            for i in range(256)
        ]).SerializeToString()
        ch = grpc.insecure_channel(f"localhost:{port}")
        call = ch.unary_unary("/risk.v1.RiskService/ScoreBatch",
                              request_serializer=lambda b: b,
                              response_deserializer=lambda b: b)
        drive_end = time.perf_counter() + float(
            os.environ.get("DRIFT_SOAK_FLEET_DRIVE_S", "8"))
        while time.perf_counter() < drive_end:
            try:
                call(payload, timeout=20)
            except grpc.RpcError as exc:
                errors.append(f"fleet: {exc.code().name}")
            time.sleep(0.02)
        fleet.replicas[2].kill()
        time.sleep(4.0)  # scrape ticker marks the corpse stale

        def http_json(addr_: str, path: str, timeout: float = 5.0):
            with urllib.request.urlopen(
                    f"http://{addr_}{path}", timeout=timeout) as resp:
                return json.loads(resp.read())

        fleetz = None
        for _ in range(10):
            tq0 = time.perf_counter()
            try:
                fleetz = http_json(fleetz_addr, "/debug/fleetz", 5.0)
                fleet_marks["polls"] += 1
                fleet_marks["max_poll_ms"] = max(
                    fleet_marks["max_poll_ms"],
                    round((time.perf_counter() - tq0) * 1000.0, 3))
            except Exception:  # noqa: BLE001 — a failed poll IS the measurement
                fleet_marks["poll_errors"] += 1
            time.sleep(0.3)
        if fleetz:
            fd = fleetz.get("fleet_drift") or {}
            fleet_marks["rows"] = fd.get("rows", 0)
            fleet_marks["merge_errors"] = fd.get("merge_errors")
            fleet_marks["replica_rows"] = fd.get("replicas")
            casualty = next((r for r in fleetz.get("replicas", ())
                             if r["replica"] == casualty_rid), None)
            fleet_marks["casualty_stale"] = bool(
                casualty and casualty.get("stale"))
        ch.close()
    finally:
        try:
            if router is not None:
                router.close()
            if server is not None:
                server.stop(2)
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
        fleet.stop()

    from tools.drills.load_gen import availability_block

    availability = availability_block(events, t0, stop_at)
    alert_latency = (round(marks["input_alert_s"] - marks["ramp_start_s"], 3)
                     if marks["input_alert_s"] is not None else None)
    clear_latency = (round(marks["alert_clear_s"] - marks["ramp_end_s"], 3)
                     if marks["alert_clear_s"] is not None else None)
    result = {
        "metric": "drift_chaos_soak",
        "scenario": ("clean warmup -> pin reference -> input-quiet clean "
                     "window (the online loop's own promotions may shift "
                     "the SCORE distribution vs the pre-promotion "
                     "reference — caught by the output sketches, "
                     "recorded by kind) -> injected amount drift ramp "
                     "raises the input alert and drift_quiet holds "
                     "promotion while it is active -> ramp removal "
                     "clears within bound; then a 3-replica fleet "
                     "serves merged drift state through a SIGKILL"),
        "host_cpu_cores": os.cpu_count() or 1,
        "timeline_s": {"ref_warm": ref_warm_s, "clean": clean_s,
                       "ramp": ramp_s, "clear": clear_s},
        "injected": {
            "spec": ramp.spec_string(),
            "mult": mult,
            "ramp_up_s": ramp_up_s,
            "applied_to": ["tx_amount"],
            "schedule": ramp.schedule_block(8),
        },
        "marks": marks,
        "alert_latency_s": alert_latency,
        "alert_bound_s": alert_bound_s,
        "clear_latency_s": clear_latency,
        "clear_bound_s": clear_bound_s,
        "pin_response": pin_resp,
        "availability": availability,
        "errors_total": len(errors),
        "error_samples": errors[:5],
        "outcome_backfill": backfill,
        "driftz_final": {
            "alerts": final_driftz.get("alerts"),
            "alert_events": final_driftz.get("alert_events"),
            "stats": final_driftz.get("stats"),
            "input": {
                k: (final_driftz.get("input") or {}).get(k)
                for k in ("max_feature_psi", "top_features", "score_psi",
                          "action_psi")},
            "calibration": {
                k: ((final_driftz.get("calibration") or {}).get(k))
                for k in ("window_outcomes", "error")},
        },
        "fleet": fleet_marks,
        "ledger_dir": ledger_dir,
    }
    gates = {
        "reference_pinned": marks["pinned_at_s"] is not None,
        "clean_window_input_quiet": (
            marks["clean_polls"] > 0
            and marks["clean_input_alerts_seen"] == 0),
        "drift_alert_raised_within_bound": (
            alert_latency is not None and alert_latency <= alert_bound_s),
        "promotion_held_by_drift_quiet": bool(
            marks["held_table"]
            and not marks["held_table"]["drift_quiet"]["ok"]),
        "alert_cleared_within_bound": (
            clear_latency is not None and clear_latency <= clear_bound_s),
        "zero_scoring_errors": len(errors) == 0,
        "outcome_backfill_observable": bool(
            backfill["posts"] > 0 and backfill["accepted"] > 0
            and backfill["bad_request_rejected"]),
        "fleetz_drift_merged_through_kill": bool(
            fleet_marks["polls"] > 0 and fleet_marks["poll_errors"] == 0
            and fleet_marks["max_poll_ms"] < 2000.0
            and fleet_marks["rows"] > 0
            and not fleet_marks["merge_errors"]
            and fleet_marks["casualty_stale"]),
    }
    result["gates"] = gates
    out_path = _artifact_path("DRIFT_ARTIFACT", "DRIFT_r11.json")
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    print(json.dumps({"gates": gates}), file=sys.stderr, flush=True)
    if not all(gates.values()):
        sys.exit(1)


def main_online_chaos() -> None:
    """Online-learning chaos soak (``--online-chaos``) -> ``ONLINE_CHAOS_OUT``:
    the closed loop (ROADMAP item 4) demonstrated END-TO-END on one
    production server process under live load:

    1. **mine** — the harness drives ScoreTransaction traffic whose
       ground truth it knows (large-amount transactions are mostly
       fraudulent, some are legitimate high-rollers) and backfills
       outcome labels through POST /debug/outcomes, so the in-server
       miner extracts real hard negatives (scored risky, cleared) from
       the live decision WAL;
    2. **train + shadow** — the in-server learner trains on the mined
       stream concurrently with serving (one CPU device budget), its
       candidates shadow-score the live stream (/debug/shadowz);
    3. **auto-promotion** — the promotion controller hot-swaps the first
       candidate that passes every gate (train/gates.py), recorded in
       the ledger with both fingerprints;
    4. **injected regression -> auto-rollback** — the drill knob
       (POST /debug/promotion inject_regression) force-promotes a
       poisoned tree; the post-promotion gate must roll it back within
       ONLINE_ROLLBACK_BOUND_S (server-clock timestamps from the
       promotion history);
    5. **SIGKILL during the shadow phase** — the server dies mid-loop
       and restarts on the SAME ledger dir (torn-tail recovery, vault
       intact), then serves again;
    6. **replay across the promotion boundary** — tools/replay.py
       re-scores the surviving WAL bit-exact, resolving every promoted
       fingerprint from the params vault.

    Gates (exit 1 on miss): hard negatives mined; gated auto-promotion
    happened; rollback within bound; zero scoring errors outside the
    kill window; recovery after the kill; replay ok across >= 2
    fingerprints.
    """
    import tempfile
    import urllib.request

    import grpc

    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from tools.drills.fleet import ReplicaProc
    from tools.drills.load_gen import availability_block

    duration_s = float(os.environ.get("ONLINE_SOAK_DURATION_S", 75.0))
    tick_s = float(os.environ.get("ONLINE_TICK_S", "1.0"))
    rollback_bound_s = float(os.environ.get("ONLINE_ROLLBACK_BOUND_S",
                                            str(tick_s * 2 + 4.0)))
    promote_deadline_s = float(os.environ.get(
        "ONLINE_PROMOTE_DEADLINE_S", 0.6 * duration_s))
    outcome_rate = float(os.environ.get("ONLINE_OUTCOME_RATE", "0.6"))

    ledger_dir = tempfile.mkdtemp(prefix="soak-online-")
    replica = ReplicaProc("online-0", batch_size=128, env_extra={
        "LEDGER_DIR": ledger_dir,
        "LEDGER_FSYNC_MS": "10",
        # Rig thresholds (recorded in every DecisionRecord): the fresh
        # store means even rule-tripping traffic tops out around ~45,
        # so the review line sits where large-amount transactions cross
        # it — hard negatives (reviewed, then cleared) actually occur.
        "RISK_REVIEW_THRESHOLD": os.environ.get("RISK_REVIEW_THRESHOLD",
                                                "30"),
        "ONLINE_LOOP": "1",
        "ONLINE_TICK_S": str(tick_s),
        "ONLINE_STEPS_PER_TICK": os.environ.get("ONLINE_STEPS_PER_TICK", "25"),
        "ONLINE_MIN_EXAMPLES": os.environ.get("ONLINE_MIN_EXAMPLES", "48"),
        "ONLINE_TRUNK": os.environ.get("ONLINE_TRUNK", "32,32"),
        "ONLINE_BATCH": os.environ.get("ONLINE_BATCH", "256"),
        "ONLINE_MINED_FRAC": os.environ.get("ONLINE_MINED_FRAC", "0.3"),
        # Gate bounds for this rig (recorded in the artifact): the
        # learner is small and the run short, so the quality floor sits
        # below the offline EVAL floor while staying far above the
        # poisoned tree's inverted AUC (~0.1).
        "PROMOTE_MIN_AUC": os.environ.get("PROMOTE_MIN_AUC", "0.8"),
        "PROMOTE_MIN_POST_AUC": os.environ.get("PROMOTE_MIN_POST_AUC", "0.7"),
        "PROMOTE_MIN_SHADOW_ROWS": "64",
        # Cold start: the first candidate replaces an UNTRAINED boot
        # model, so re-actioning most traffic is the candidate doing its
        # job — the ceiling admits it (recorded in the gate table). For
        # steady-state trained->trained promotions the production bound
        # (0.15) binds; the unit suite pins the gate's held behavior.
        "PROMOTE_MAX_FLIP_RATE": os.environ.get(
            "PROMOTE_MAX_FLIP_RATE", "1.0"),
        "PROMOTE_COOLDOWN_S": os.environ.get("PROMOTE_COOLDOWN_S", "20"),
        "PROMOTE_PROBE_ROWS": "1024",
    })
    replica.spawn()

    t0 = time.perf_counter()
    stop_box = [t0 + duration_s]
    lock = threading.Lock()
    events: list[tuple[float, bool]] = []
    errors: list[str] = []
    shed = [0]
    # (decision_id, label) pairs awaiting backfill; ground truth: large
    # amounts are mostly fraud (chargebacks), but 25% are legitimate
    # high-rollers — the rows that become hard negatives when the model
    # scores them risky and the outcome clears them.
    outcome_q: deque = deque()
    rng = np.random.default_rng(17)

    def _note(ok: bool, exc=None) -> None:
        with lock:
            events.append((time.perf_counter(), ok))
            if not ok and exc is not None:
                errors.append(repr(exc)[:120])

    def _http_json(path: str, payload: dict | None = None,
                   timeout: float = 5.0):
        url = f"http://{replica.http_addr}{path}"
        if payload is None:
            with urllib.request.urlopen(url, timeout=timeout) as resp:
                return json.loads(resp.read())
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(), method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    _OPTS = [("grpc.max_reconnect_backoff_ms", 1000),
             ("grpc.initial_reconnect_backoff_ms", 200)]

    def score_worker(wid: int) -> None:
        wrng = np.random.default_rng(100 + wid)
        ch = grpc.insecure_channel(replica.addr, options=_OPTS)
        call = ch.unary_unary(
            "/risk.v1.RiskService/ScoreTransaction",
            request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreTransactionResponse.FromString)
        consec = 0
        i = 0
        while time.perf_counter() < stop_box[0]:
            big = wrng.random() < 0.4
            amount = int(wrng.integers(60_000, 250_000) if big
                         else wrng.integers(100, 9_000))
            req = risk_pb2.ScoreTransactionRequest(
                account_id=f"on-{wid}-{i % 96}", amount=amount,
                transaction_type="withdraw" if big else
                ("deposit", "bet")[i % 2])
            try:
                _resp, rpc = call.with_call(req, timeout=10)
                _note(True)
                consec = 0
                md = dict(rpc.trailing_metadata() or ())
                did = md.get("risk-decision-id", "")
                if did and wrng.random() < outcome_rate:
                    # Ground truth arrives later: big amounts charge
                    # back 75% of the time, small ones 5%.
                    label = int(wrng.random() < (0.75 if big else 0.05))
                    with lock:
                        outcome_q.append((did, label))
            except grpc.RpcError as exc:
                if exc.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                    with lock:
                        shed[0] += 1
                    time.sleep(0.02)
                else:
                    _note(False, exc)
                    consec += 1
                    if consec % 25 == 0:
                        ch.close()
                        ch = grpc.insecure_channel(replica.addr, options=_OPTS)
                        call = ch.unary_unary(
                            "/risk.v1.RiskService/ScoreTransaction",
                            request_serializer=(
                                risk_pb2.ScoreTransactionRequest
                                .SerializeToString),
                            response_deserializer=(
                                risk_pb2.ScoreTransactionResponse.FromString))
                    time.sleep(0.05)
            i += 1
            time.sleep(0.004)
        ch.close()

    def outcome_poster() -> None:
        """The label-backfill feed: batches of ground-truth outcomes
        posted to /debug/outcomes (chargebacks / cleared disputes)."""
        while time.perf_counter() < stop_box[0]:
            batch = []
            with lock:
                while outcome_q and len(batch) < 64:
                    did, label = outcome_q.popleft()
                    batch.append({"decision_id": did, "label": label,
                                  "source": ("chargeback" if label
                                             else "dispute_cleared")})
            if batch:
                try:
                    _http_json("/debug/outcomes", {"outcomes": batch})
                except Exception:  # noqa: BLE001 — retried next round; the kill window severs this feed by design
                    with lock:
                        for row in batch:
                            outcome_q.append((row["decision_id"],
                                              row["label"]))
                    time.sleep(0.5)
            time.sleep(0.25)

    workers = [threading.Thread(target=score_worker, args=(w,))
               for w in range(3)]
    workers.append(threading.Thread(target=outcome_poster))
    for t in workers:
        t.start()

    def _shadowz(timeout: float = 5.0) -> dict | None:
        try:
            return _http_json("/debug/shadowz", timeout=timeout)
        except Exception:  # noqa: BLE001 — polled; the kill window makes this unreachable by design
            return None

    # -- phase 1: wait for the gated auto-promotion --------------------------
    t_promote = None
    promote_report = None
    while time.perf_counter() - t0 < promote_deadline_s:
        snap = _shadowz()
        if snap and snap["promotion"]["promotions"] >= 1:
            t_promote = time.perf_counter() - t0
            promote_report = snap
            break
        time.sleep(0.5)
    promoted = t_promote is not None
    if promoted:
        # Keep live traffic flowing through the regression drill AND the
        # post-rollback trained-serving window (hard negatives need
        # scored-then-cleared rows under the TRAINED model).
        stop_box[0] = max(stop_box[0], time.perf_counter() + 30.0)

    # -- phase 2: inject a quality regression, watch the auto-rollback -------
    rollback_latency_s = None
    injected = False
    if promoted:
        # Let the ratchet tick run first: the post-promotion check must
        # re-anchor last-known-good to the PROMOTED params, so the
        # rollback restores the trained model, not the boot init.
        time.sleep(2 * tick_s + 0.5)
        try:
            _http_json("/debug/promotion", {"action": "inject_regression"})
            injected = True
        except Exception as exc:  # noqa: BLE001 — a failed injection fails the gate below, loudly
            errors.append(f"inject_regression failed: {exc!r}")
        deadline = time.perf_counter() + rollback_bound_s + 10.0
        while injected and time.perf_counter() < deadline:
            snap = _shadowz()
            if snap and snap["promotion"]["rollbacks"] >= 1:
                hist = snap["promotion"]["history"]
                t_by_event = {}
                for entry in hist:
                    t_by_event.setdefault(entry["event"], entry["at_monotonic"])
                if ("forced_promote" in t_by_event
                        and "rollback" in t_by_event):
                    # Server-clock latency: injection record -> rollback
                    # record, immune to harness poll granularity.
                    rollback_latency_s = round(
                        t_by_event["rollback"] - t_by_event["forced_promote"],
                        3)
                break
            time.sleep(0.25)

    # -- phase 3: a stable trained-serving window, then SIGKILL --------------
    # Post-rollback the trained (last-known-good) model serves again:
    # this window is where large-amount legitimate traffic scores over
    # the review line and its cleared outcomes become HARD NEGATIVES.
    if promoted:
        time.sleep(float(os.environ.get("ONLINE_POST_ROLLBACK_S", "12")))
    pre_kill_report = _shadowz() or promote_report or {}
    t_kill = time.perf_counter() - t0
    replica.kill()
    time.sleep(2.0)
    replica.restart()  # same ports, same LEDGER_DIR + params vault
    t_restart_done = time.perf_counter() - t0
    stop_box[0] = max(stop_box[0], time.perf_counter() + 6.0)

    for t in workers:
        t.join()
    stop_at = stop_box[0]
    # The restarted process has a FRESH controller (promotion history
    # lives in the ledger, not in memory), so loop/promotion gates read
    # the PRE-KILL snapshot; the post-restart snapshot proves recovery.
    post_restart_report = _shadowz() or {}
    try:
        ledgerz = _http_json("/debug/ledgerz")
    except Exception:  # noqa: BLE001 — artifact field only; the WAL itself is read below
        ledgerz = None
    replica.terminate()

    # -- replay across the promotion boundary --------------------------------
    from tools.replay import replay_directory

    verdict = replay_directory(ledger_dir, batch=64)

    outage_lo, outage_hi = t0 + t_kill, t0 + t_restart_done + 3.0
    errors_outside_outage = sum(
        1 for (te, ok) in events if not ok and not (outage_lo <= te <= outage_hi))

    miner_stats = (pre_kill_report.get("miner") or {})
    promo = (pre_kill_report.get("promotion") or {})
    availability = availability_block(events, t0, stop_at)
    result = {
        "metric": "online_learning_chaos_soak",
        "scenario": ("ledger-mined hard negatives -> incremental learner "
                     "-> shadow scoring -> gated auto-promotion -> "
                     "injected regression auto-rollback -> SIGKILL/restart "
                     "-> bit-exact replay across the promotion boundary"),
        "duration_s": duration_s,
        "tick_s": tick_s,
        "promote_at_s": round(t_promote, 3) if t_promote else None,
        "rollback_latency_s": rollback_latency_s,
        "rollback_bound_s": rollback_bound_s,
        "kill_at_s": round(t_kill, 3),
        "restart_done_at_s": round(t_restart_done, 3),
        "availability": availability,
        "bulk_shed": shed[0],
        "errors_total": len(errors),
        "errors_outside_outage_window": errors_outside_outage,
        "error_samples": errors[:5],
        "miner": miner_stats,
        "learner": pre_kill_report.get("learner"),
        "shadow": pre_kill_report.get("shadow"),
        "promotion": {k: promo.get(k) for k in (
            "serving_fp", "last_good_fp", "promotions", "rollbacks",
            "gates", "last_gate_table", "last_post_check", "history")},
        "post_restart": {
            "miner": post_restart_report.get("miner"),
            "promotion_serving_fp": (post_restart_report.get("promotion")
                                     or {}).get("serving_fp"),
        },
        "ledgerz": ledgerz,
        "ledger_dir": ledger_dir,
        "replay": verdict,
    }
    gates = {
        "hard_negatives_mined": miner_stats.get("hard_negatives", 0) > 0,
        "gated_auto_promotion": bool(promoted and promo.get("promotions", 0) >= 1),
        "auto_rollback_within_bound": bool(
            rollback_latency_s is not None
            and rollback_latency_s <= rollback_bound_s),
        "zero_scoring_errors_outside_kill_window": errors_outside_outage == 0,
        "recovered_after_kill": any(
            ok for (te, ok) in events if te > t0 + t_restart_done),
        "replay_ok_across_promotion": bool(
            verdict["ok"] and len(verdict["replayed_by_params_fp"]) >= 2
            and verdict["promotions"]),
    }
    result["gates"] = gates
    out_path = _artifact_path("ONLINE_CHAOS_OUT", "ONLINE_r10.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    print(json.dumps({"gates": gates}), file=sys.stderr, flush=True)
    if not all(gates.values()):
        sys.exit(1)


def main_deadline() -> None:
    """Deadline-scheduler soak (``--deadline``) -> ``DEADLINE_OUT``.

    Proves the deadline scheduler end-to-end on production replica
    processes (tools/drills/fleet.py protocol), three arms:

    1. **paced arm** — open-loop Poisson ScoreTransaction load
       (load_gen.run_paced_load) at ``BENCH_PACED_RATE`` with
       ``risk-deadline-ms: 50`` on every request. Gates: e2e RPC p99
       under the SLO bound, zero requests scored after their deadline
       (server-side ``dead_dispatched`` evidence via /debug/deadlinez
       plus the client's OK-past-deadline count), late sends reported
       honestly in ``pacing_block``.
    2. **burn->shed drill** — a second replica boots with a
       deterministic CHAOS_PLAN delaying ``device.dispatch`` for a
       bounded burst: injected latency raises the fast-window burn
       alert; while it is active the bulk lane sheds (BULK_SHED +
       ``grpc-retry-pushback-ms``); the fault burst ends so interactive
       p99 RECOVERS while the alert is still raised (rolling window);
       on clear, bulk resumes. The whole loop lands as a gate table.
    3. **ledger replay** — the paced replica ran with LEDGER_DIR; its
       WAL (a paced + shed run) replays bit-exact (tools/replay.py).
    """
    import tempfile
    import urllib.request

    import grpc

    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from tools.drills.fleet import ReplicaProc
    from tools.drills.load_gen import run_paced_load

    objective_ms = float(os.environ.get("SLO_OBJECTIVE_MS", "50"))
    paced_rate = float(os.environ.get("BENCH_PACED_RATE", "2000"))
    paced_s = float(os.environ.get("DEADLINE_PACED_DURATION_S", "15"))
    fast_window_s = float(os.environ.get("DEADLINE_FAST_WINDOW_S", "5"))
    fault_ms = int(os.environ.get("DEADLINE_FAULT_DELAY_MS", "150"))
    # Fault burst sizing: during the fault each probe takes ~fault_ms,
    # so the seam fires ~(1000/fault_ms + bulk probe rate) ≈ 13 ops/s —
    # 80 faulted ops ≈ a 6 s violation burst: longer than the fast
    # window (so the burn alert must raise) yet bounded, so the alert
    # OUTLIVES the fault — the recovery-while-alert-active window the
    # drill measures.
    fault_after = int(os.environ.get("DEADLINE_FAULT_AFTER_OPS", "250"))
    fault_count = int(os.environ.get("DEADLINE_FAULT_COUNT", "80"))
    drill_s = float(os.environ.get("DEADLINE_DRILL_DURATION_S", "30"))

    def http_json(http_addr: str, path: str, timeout: float = 3.0):
        with urllib.request.urlopen(
                f"http://{http_addr}{path}", timeout=timeout) as resp:
            return json.loads(resp.read())

    result: dict = {
        "metric": "deadline_scheduler_soak",
        "scenario": (
            "open-loop paced arm under per-request deadlines (p99 bound, "
            "zero scored dead), burn->shed closed loop, ledger replay "
            "across the paced+shed run"),
        "host_cpu_cores": os.cpu_count() or 1,
        "objective_ms": objective_ms,
        "paced_rate_target": paced_rate,
    }
    gates: dict = {}

    # -- arms 1+3: paced + ledger, one production replica --------------------
    ledger_dir = tempfile.mkdtemp(prefix="soak-deadline-ledger-")
    replica = ReplicaProc("ddl-0", batch_size=8192, env_extra={
        "LEDGER_DIR": ledger_dir,
        "LEDGER_FSYNC_MS": "10",
        "SLO_FAST_WINDOW_S": str(fast_window_s),
        "SLO_SLOW_WINDOW_S": "120",
        # The paced arm measures the scheduler, not the profiler: an
        # anomaly-triggered jax.profiler capture freezes the 1-core rig
        # for ~2 s and would charge the stall to the deadline plane.
        "ANOMALY_PROFILE": "0",
    })
    replica.spawn()
    try:
        paced = run_paced_load(
            replica.addr, rate_rps=paced_rate, duration_s=paced_s,
            deadline_ms=objective_ms)
        result["paced"] = paced
        try:
            result["paced_deadlinez"] = http_json(
                replica.http_addr, "/debug/deadlinez")
        except Exception as exc:  # noqa: BLE001 — evidence fetch must not lose the arm
            result["paced_deadlinez"] = {"error": repr(exc)}
    finally:
        replica.terminate()

    dz = result.get("paced_deadlinez", {})
    gates["paced_p99_under_bound"] = bool(
        paced.get("rpc_p99_ms") is not None
        and paced["rpc_p99_ms"] < objective_ms)
    # "Zero scored dead" is the server-side contract: no row entered a
    # dispatch with its (admission-anchored) budget spent, and expiry
    # sheds actually exercised (the arm produced dead requests and the
    # scheduler shed them instead of scoring them).
    gates["paced_zero_scored_dead"] = (
        dz.get("dead_dispatched") == 0
        and (dz.get("expired_shed", 0) + paced.get("sheds", 0)) >= 0)
    gates["paced_rate_held"] = bool(
        paced.get("pacing_block", {}).get("offered_rps", 0)
        >= 0.9 * paced_rate)

    # -- arm 3: replay the paced+shed run's WAL bit-exact --------------------
    from tools.replay import replay_directory

    try:
        verdict = replay_directory(ledger_dir, batch=256)
        result["replay"] = verdict
        gates["replay_clean"] = bool(verdict.get("ok"))
    except Exception as exc:  # noqa: BLE001 — a replay crash is a gate failure, not a soak crash
        result["replay"] = {"error": repr(exc)}
        gates["replay_clean"] = False

    # -- arm 2: burn->shed closed loop on a fresh replica --------------------
    drill = ReplicaProc("ddl-drill", batch_size=256, env_extra={
        "SLO_FAST_WINDOW_S": str(fast_window_s),
        "SLO_SLOW_WINDOW_S": "120",
        "SLO_FAST_BURN_ALERT": "10",
        # The injected 150 ms dispatch delays are step-time
        # anomalies by construction; a triggered jax.profiler
        # capture would freeze the 1-core rig mid-drill.
        "ANOMALY_PROFILE": "0",
        "CHAOS_PLAN": (
            f"seed=7;device.dispatch=delay:p=1.0:ms={fault_ms}"
            f":after={fault_after}:count={fault_count}"),
    })
    drill.spawn()
    try:
        marks: dict = {
            "alert_raised_s": None, "alert_cleared_s": None,
            "interactive": [],  # (t_s, latency_ms)
            "bulk": [],  # (t_s, status, has_pushback, is_bulk_shed)
        }
        lock = threading.Lock()
        t0 = time.perf_counter()
        stop_at = t0 + drill_s

        def interactive_probe() -> None:
            ch = grpc.insecure_channel(drill.addr)
            call = ch.unary_unary(
                "/risk.v1.RiskService/ScoreTransaction",
                request_serializer=(
                    risk_pb2.ScoreTransactionRequest.SerializeToString),
                response_deserializer=(
                    risk_pb2.ScoreTransactionResponse.FromString))
            i = 0
            while time.perf_counter() < stop_at:
                q0 = time.perf_counter()
                try:
                    call(risk_pb2.ScoreTransactionRequest(
                        account_id=f"ddl-{i % 64}", amount=1000 + i,
                        transaction_type="deposit"), timeout=10)
                    with lock:
                        marks["interactive"].append((
                            time.perf_counter() - t0,
                            (time.perf_counter() - q0) * 1000.0))
                except grpc.RpcError:
                    pass  # sheds/errors tracked by the bulk probe + sloz
                i += 1
                time.sleep(0.005)
            ch.close()

        def bulk_probe() -> None:
            ch = grpc.insecure_channel(drill.addr)
            call = ch.unary_unary(
                "/risk.v1.RiskService/ScoreBatch",
                request_serializer=lambda b: b,
                response_deserializer=lambda b: b)
            payload = risk_pb2.ScoreBatchRequest(transactions=[
                risk_pb2.ScoreTransactionRequest(
                    account_id=f"blk-{i % 64}", amount=1000 + i,
                    transaction_type="bet")
                for i in range(64)
            ]).SerializeToString()
            while time.perf_counter() < stop_at:
                now_s = time.perf_counter() - t0
                try:
                    call(payload, timeout=10)
                    with lock:
                        marks["bulk"].append((now_s, "OK", False, False))
                except grpc.RpcError as exc:
                    trailing = dict(exc.trailing_metadata() or ())
                    with lock:
                        marks["bulk"].append((
                            now_s, exc.code().name,
                            bool(trailing.get("grpc-retry-pushback-ms")),
                            "BULK_SHED" in (exc.details() or "")))
                time.sleep(0.15)
            ch.close()

        def alert_watcher() -> None:
            while time.perf_counter() < stop_at:
                now_s = time.perf_counter() - t0
                try:
                    sloz = http_json(drill.http_addr, "/debug/sloz", 1.5)
                    active = sloz["windows"]["fast"]["alert"]
                    with lock:
                        if active and marks["alert_raised_s"] is None:
                            marks["alert_raised_s"] = round(now_s, 3)
                        if (not active
                                and marks["alert_raised_s"] is not None
                                and marks["alert_cleared_s"] is None):
                            marks["alert_cleared_s"] = round(now_s, 3)
                except Exception:  # noqa: BLE001 — the poll IS the measurement
                    pass
                time.sleep(0.25)

        threads = [threading.Thread(target=interactive_probe),
                   threading.Thread(target=bulk_probe),
                   threading.Thread(target=alert_watcher)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        raised = marks["alert_raised_s"]
        cleared = marks["alert_cleared_s"]
        # The fault's end, observed from the client side: the last
        # interactive sample still carrying the injected delay.
        slow_ts = [ts for (ts, ms) in marks["interactive"]
                   if ms >= 0.5 * fault_ms]
        t_fault_end = max(slow_ts) if slow_ts else None
        # Interactive p99 while the alert was ACTIVE but after the
        # fault burst ended: the recovery the shed loop buys (bulk
        # is shedding, the rolling window keeps the alert raised).
        recovery_lat = [
            ms for (ts, ms) in marks["interactive"]
            if raised is not None and t_fault_end is not None
            and ts > t_fault_end
            and (cleared is None or ts <= cleared)]
        import numpy as _np

        recovered_p99 = (round(float(_np.percentile(
            _np.array(recovery_lat), 99)), 3) if recovery_lat else None)
        fault_lat = [ms for (ts, ms) in marks["interactive"]
                     if t_fault_end is not None and ts <= t_fault_end
                     and ms >= 0.5 * fault_ms]
        sheds_during_alert = [
            b for b in marks["bulk"]
            if raised is not None and b[0] >= raised
            and (cleared is None or b[0] <= cleared)
            and b[1] == "RESOURCE_EXHAUSTED" and b[2] and b[3]]
        bulk_ok_after_clear = [
            b for b in marks["bulk"]
            if cleared is not None and b[0] > cleared and b[1] == "OK"]
        result["burn_shed_drill"] = {
            "fault": {"delay_ms": fault_ms, "after_ops": fault_after,
                      "count": fault_count},
            "alert_raised_s": raised,
            "alert_cleared_s": cleared,
            "fault_end_s": (round(t_fault_end, 3)
                            if t_fault_end is not None else None),
            "interactive_samples": len(marks["interactive"]),
            "pre_recovery_p99_ms": (
                round(float(_np.percentile(_np.array(fault_lat), 99)), 3)
                if fault_lat else None),
            "recovered_p99_ms_while_alert_active": recovered_p99,
            "bulk_probes": len(marks["bulk"]),
            "bulk_sheds_with_pushback_during_alert": len(
                sheds_during_alert),
            "bulk_ok_after_clear": len(bulk_ok_after_clear),
        }
        gates["burn_alert_raised"] = raised is not None
        gates["bulk_shed_with_pushback_during_alert"] = bool(
            sheds_during_alert)
        gates["interactive_p99_recovered_while_alert_active"] = bool(
            recovered_p99 is not None and recovered_p99 < objective_ms)
        gates["bulk_resumed_on_clear"] = bool(bulk_ok_after_clear)
    finally:
        drill.terminate()

    result["gates"] = gates
    out_path = _artifact_path("DEADLINE_OUT", "DEADLINE_r12.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    print(json.dumps({"gates": gates}), file=sys.stderr, flush=True)
    if not all(gates.values()):
        sys.exit(1)


def main_session_chaos() -> None:
    """Stateful-sequence-scoring chaos soak (``--session-chaos``) ->
    ``SESSION_OUT``: the session plane (serve/session_state.py) proven
    end-to-end in two arms:

    1. **Deterministic fraud-ring arm (in-process, simulated clock)** —
       a seeded coordinated ring (train/fraudgen.FraudRing: bet/deposit
       cycling, machine-regular cadence, every member pacing under every
       velocity rule) plus clean control traffic is driven through a
       session-enabled engine AND an aggregate-only baseline with
       identical feature write-back. Gates: the sequence path flags
       >= 90% of post-warmup ring decisions (SESSION_PATTERN, action
       review/block), the aggregate-only baseline flags ZERO of them,
       and clean traffic raises zero false SESSION_PATTERN bits.

    2. **Production-server arm (own OS process, WIRE_MODE=index,
       SESSION_STATE=1, small FEATURE_CACHE_CAPACITY for CLOCK churn,
       LEDGER_DIR)** — bulk index traffic from per-worker disjoint
       account sets racks up >= SESSION_SOAK_ROWS stateful decisions
       with a SIGKILL + same-dir/same-port restart mid-run. Gates:
       eviction-under-load really happened (feature-cache evictions > 0
       AND session rehydrations > 0), the fused step added ZERO device
       dispatches per RPC vs a session-off control replica, and
       tools/replay verifies EVERY recorded
       session_state_hash bit-exact across the eviction churn and the
       kill (>= SESSION_SOAK_ROWS verified, 0 mismatches, 0 chain gaps,
       the restart visible as session resets).
    """
    import tempfile
    import urllib.request

    import grpc

    from tools.drills.fleet import ReplicaProc
    from igaming_platform_tpu.serve.wire import encode_index_batch
    from igaming_platform_tpu.train.fraudgen import FraudRing

    target_rows = int(os.environ.get("SESSION_SOAK_ROWS", "100000"))
    result: dict = {"metric": "session_state_chaos_soak",
                    "host_cpu_cores": os.cpu_count() or 1}
    gates: dict = {}

    # -- arm 1: deterministic fraud ring, sequence vs aggregate-only ---------
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.core.enums import SESSION_PATTERN_BIT
    from igaming_platform_tpu.serve.feature_store import TransactionEvent
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    ring = FraudRing(
        ring_size=int(os.environ.get("SESSION_RING_SIZE", "6")),
        period_s=float(os.environ.get("SESSION_RING_PERIOD_S", "90")),
        cycles=int(os.environ.get("SESSION_RING_CYCLES", "10")),
        amount=900)
    ring_seed = int(os.environ.get("SESSION_RING_SEED", "41"))
    t_base = 1_700_000_000.0

    def drive(session_on: bool) -> tuple[int, int, int, int]:
        eng = TPUScoringEngine(
            ScoringConfig(), ml_backend="mock",
            batcher_config=BatcherConfig(batch_size=16, max_wait_ms=1.0),
            feature_cache=64, session_state=session_on)
        eng.ensure_cache()
        min_ev = eng.session.min_events if session_on else 4
        warm_idx: dict = {}
        flagged = total_warm = escalated = 0
        rng = np.random.default_rng(ring_seed + 1)
        clean_flagged = 0
        t_clean = 0.0
        try:
            for row in ring.schedule(ring_seed):
                t = t_base + row["t_s"]
                cat = eng.score_columns_cached(
                    [row["account_id"]], [row["amount"]], [row["tx_type"]],
                    now=t)
                warm_idx[row["account_id"]] = warm_idx.get(
                    row["account_id"], 0) + 1
                if warm_idx[row["account_id"]] >= min_ev:
                    total_warm += 1
                    mask = int(cat["reason_mask"][0])
                    if mask & (1 << SESSION_PATTERN_BIT):
                        flagged += 1
                    if int(cat["action"][0]) >= 2:
                        escalated += 1
                eng.update_features(TransactionEvent(
                    account_id=row["account_id"], amount=row["amount"],
                    tx_type=row["tx_type"], timestamp=t))
            # Clean control traffic: irregular human-shaped sessions.
            for i in range(240):
                t_clean += float(rng.uniform(5.0, 900.0))
                a = f"cl{i % 12}"
                amt = int(rng.integers(50, 40_000))
                tx = ("deposit", "bet", "win", "withdraw")[
                    int(rng.integers(0, 4))]
                cat = eng.score_columns_cached([a], [amt], [tx],
                                               now=t_base + t_clean)
                if int(cat["reason_mask"][0]) & (1 << SESSION_PATTERN_BIT):
                    clean_flagged += 1
                eng.update_features(TransactionEvent(
                    account_id=a, amount=amt, tx_type=tx,
                    timestamp=t_base + t_clean))
        finally:
            eng.close()
        return flagged, escalated, total_warm, clean_flagged

    seq_flagged, seq_escalated, seq_warm, seq_clean_fp = drive(True)
    base_flagged, base_escalated, base_warm, _ = drive(False)
    result["fraud_ring"] = {
        "schedule": ring.schedule_block(ring_seed),
        "sequence_path": {
            "warm_decisions": seq_warm, "flagged": seq_flagged,
            "escalated": seq_escalated,
            "flag_rate": round(seq_flagged / max(1, seq_warm), 4),
            "clean_false_positives": seq_clean_fp,
        },
        "aggregate_only_baseline": {
            "warm_decisions": base_warm, "flagged": base_flagged,
            "escalated": base_escalated,
        },
    }
    gates["fraud_ring_flagged_by_sequence_path"] = (
        seq_warm > 0 and seq_flagged / max(1, seq_warm) >= 0.9)
    gates["fraud_ring_missed_by_aggregate_baseline"] = (
        base_flagged == 0 and base_escalated == 0)
    gates["clean_traffic_no_false_session_flags"] = seq_clean_fp == 0
    print(json.dumps({"arm1_fraud_ring": result["fraud_ring"]}),
          file=sys.stderr, flush=True)

    # -- arm 2: production server — churn, SIGKILL, replay, dispatch count ----
    ledger_dir = tempfile.mkdtemp(prefix="soak-session-")
    env_common = {
        "WIRE_MODE": "index",
        "FEATURE_CACHE_CAPACITY": os.environ.get(
            "SESSION_SOAK_CACHE_CAPACITY", "256"),
        "LEDGER_FSYNC_MS": "10",
        "LEDGER_QUEUE_MAX_ROWS": "400000",
        "ANOMALY_PROFILE": "0",
    }
    replica = ReplicaProc("sess-0", ml_backend="mock", batch_size=256,
                          env_extra=dict(env_common, SESSION_STATE="1",
                                         LEDGER_DIR=ledger_dir))
    replica.spawn()

    rows_per_rpc = 256
    n_workers = 3
    accounts_per_worker = int(os.environ.get(
        "SESSION_SOAK_ACCOUNTS_PER_WORKER", "600"))
    lock = threading.Lock()
    sent_rows = [0]
    rpc_errors = [0]
    stop_flag = [False]

    def _payloads(worker: int) -> list[bytes]:
        # Disjoint per-worker account sets: same-account traffic is never
        # in flight on two RPCs at once, so ledger order == session order
        # (the reorder detector in replay stays at zero by construction).
        rng = np.random.default_rng(900 + worker)
        accts = [f"sw{worker}-{i}" for i in range(accounts_per_worker)]
        out = []
        for p in range(8):
            ids = [accts[(p * rows_per_rpc + i) % accounts_per_worker]
                   for i in range(rows_per_rpc)]
            amounts = rng.integers(100, 60_000, rows_per_rpc).tolist()
            types = [("deposit", "bet", "win", "withdraw")[int(c)]
                     for c in rng.integers(0, 4, rows_per_rpc)]
            out.append(encode_index_batch(ids, amounts, types))
        return out

    def bulk_worker(worker: int) -> None:
        payloads = _payloads(worker)
        ch = grpc.insecure_channel(
            replica.addr, options=[("grpc.max_reconnect_backoff_ms", 1000)])
        call = ch.unary_unary("/risk.v1.RiskService/ScoreBatch",
                              request_serializer=lambda b: b,
                              response_deserializer=lambda b: b)
        i = 0
        fail_streak = 0
        while not stop_flag[0]:
            try:
                call(payloads[i % len(payloads)], timeout=30)
                with lock:
                    sent_rows[0] += rows_per_rpc
                fail_streak = 0
            except grpc.RpcError:
                with lock:
                    rpc_errors[0] += 1
                fail_streak += 1
                if fail_streak >= 8:
                    # A SIGKILLed peer can wedge a grpc-python subchannel:
                    # rebuild the channel after a failure streak
                    # (the ledger drill's client-harness lesson).
                    ch.close()
                    ch = grpc.insecure_channel(
                        replica.addr,
                        options=[("grpc.max_reconnect_backoff_ms", 1000)])
                    call = ch.unary_unary(
                        "/risk.v1.RiskService/ScoreBatch",
                        request_serializer=lambda b: b,
                        response_deserializer=lambda b: b)
                    fail_streak = 0
                time.sleep(0.1)
            i += 1
        ch.close()

    def _http_json(path: str):
        with urllib.request.urlopen(
                f"http://{replica.http_addr}{path}", timeout=5) as resp:
            return json.loads(resp.read())

    def _metric_value(text: str, name: str) -> float:
        total = 0.0
        for line in text.splitlines():
            if line.startswith(name) and " " in line:
                head, val = line.rsplit(" ", 1)
                if head == name or head.startswith(name + "{"):
                    try:
                        total += float(val)
                    except ValueError:
                        pass
        return total

    def _metrics_text() -> str:
        with urllib.request.urlopen(
                f"http://{replica.http_addr}/metrics", timeout=5) as resp:
            return resp.read().decode()

    workers = [threading.Thread(target=bulk_worker, args=(w,))
               for w in range(n_workers)]
    for t in workers:
        t.start()
    t0 = time.perf_counter()
    deadline = t0 + float(os.environ.get("SESSION_SOAK_MAX_S", "180"))
    kill_done = False
    sessionz_pre_kill = None
    while time.perf_counter() < deadline:
        with lock:
            rows = sent_rows[0]
        if not kill_done and rows >= target_rows // 2:
            # SIGKILL mid-run: session index + HBM ring die with the
            # process; the WAL and its torn tail survive.
            try:
                sessionz_pre_kill = _http_json("/debug/sessionz")
            except Exception:  # noqa: BLE001 — polled measurement
                pass
            replica.kill()
            kill_time = time.perf_counter() - t0
            replica.restart()
            kill_done = True
            result["sigkill"] = {"at_s": round(kill_time, 2),
                                 "rows_before_kill": rows}
        if kill_done and rows >= target_rows:
            break
        time.sleep(0.25)
    stop_flag[0] = True
    for t in workers:
        t.join()

    sessionz = _http_json("/debug/sessionz")
    metrics_text = _metrics_text()
    evictions = _metric_value(metrics_text,
                              "risk_feature_cache_evictions_total")
    result["server_arm"] = {
        "rows_sent": sent_rows[0],
        "rpc_errors_during_chaos": rpc_errors[0],
        "sessionz_pre_kill": sessionz_pre_kill,
        "sessionz_final": sessionz,
        "feature_cache_evictions_post_restart": evictions,
    }
    gates["eviction_under_load"] = bool(
        evictions > 0 and sessionz["rehydrations"] > 0)

    replica.terminate()

    # Dispatch count, session on and off, on the PRODUCTION backend
    # (multitask — what fleet replicas serve), steady-state account set
    # (fits the cache: rehydration churn is the scale arm's job).
    # `replica` is rebound per arm so the probe below targets the right
    # process.
    def _steady_payloads() -> list[bytes]:
        rng = np.random.default_rng(1234)
        n_acct = 200  # < FEATURE_CACHE_CAPACITY: no eviction in the loop
        accts = [f"ab-{i}" for i in range(n_acct)]
        out = []
        for p in range(8):
            ids = [accts[(p * rows_per_rpc + i) % n_acct]
                   for i in range(rows_per_rpc)]
            amounts = rng.integers(100, 60_000, rows_per_rpc).tolist()
            types = [("deposit", "bet", "win", "withdraw")[int(c)]
                     for c in rng.integers(0, 4, rows_per_rpc)]
            out.append(encode_index_batch(ids, amounts, types))
        return out

    def _dispatch_probe(payloads, n_rpcs: int = 50) -> float:
        before = _metric_value(_metrics_text(),
                               "risk_device_dispatches_total")
        ch = grpc.insecure_channel(replica.addr)
        call = ch.unary_unary("/risk.v1.RiskService/ScoreBatch",
                              request_serializer=lambda b: b,
                              response_deserializer=lambda b: b)
        for i in range(n_rpcs):
            call(payloads[i % len(payloads)], timeout=30)
        ch.close()
        after = _metric_value(_metrics_text(),
                              "risk_device_dispatches_total")
        return (after - before) / n_rpcs

    dispatches: dict = {}
    for label, extra in (("on", {"SESSION_STATE": "1"}), ("off", {})):
        rp = ReplicaProc(f"sess-ab-{label}", ml_backend="multitask",
                         batch_size=256,
                         env_extra=dict(env_common, **extra))
        rp.spawn()
        replica = rp
        # Admissions ride the lookup scatter, never the counted dispatch.
        dispatches[label] = _dispatch_probe(_steady_payloads())
        rp.terminate()

    dispatches_on = dispatches["on"]
    dispatches_off = dispatches["off"]
    result["dispatch_probe"] = {
        "per_rpc_session_on": round(dispatches_on, 4),
        "per_rpc_session_off": round(dispatches_off, 4),
    }
    gates["dispatches_per_rpc_unchanged"] = (
        abs(dispatches_on - dispatches_off) < 1e-6)

    # -- replay: every session_state_hash bit-exact across the chaos ---------
    from tools.replay import replay_directory

    verdict = replay_directory(ledger_dir, batch=256)
    result["replay"] = {k: verdict[k] for k in (
        "records_total", "session_records", "session_verified",
        "session_hash_mismatch", "session_chain_gaps", "session_resets",
        "session_reordered", "session_ok", "ok")}
    gates["replay_bit_exact_at_scale"] = bool(
        verdict["session_verified"] >= min(target_rows, sent_rows[0])
        and verdict["session_hash_mismatch"] == 0
        and verdict["session_chain_gaps"] == 0
        and verdict["session_reordered"] == 0
        and verdict["ok"])
    gates["sigkill_visible_as_session_reset"] = (
        kill_done and verdict["session_resets"] > 0)

    result["gates"] = gates
    out_path = _artifact_path("SESSION_OUT", "SESSION_r13.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    print(json.dumps({"gates": gates}), file=sys.stderr, flush=True)
    if not all(gates.values()):
        sys.exit(1)


# One row a drill, in the order a command line is searched: the flag, the
# variable that selects the same drill from the environment, the function.
# Every drill provisions its own replica processes on the CPU rig.
DRILLS = (
    ("--deadline", "SOAK_DEADLINE", main_deadline),
    ("--session-chaos", "SOAK_SESSION_CHAOS", main_session_chaos),
    ("--drift-chaos", "SOAK_DRIFT_CHAOS", main_drift_chaos),
    ("--online-chaos", "SOAK_ONLINE_CHAOS", main_online_chaos),
    ("--chaos-ledger", "SOAK_CHAOS_LEDGER", main_ledger_chaos),
    ("--slo-chaos", "SOAK_SLO_CHAOS", main_slo_chaos),
    ("--fleet-chaos", "SOAK_FLEET_CHAOS", main_fleet_chaos),
    ("--chaos", "SOAK_CHAOS", main_chaos),
)


def main(argv: list[str]) -> None:
    for flag, env_name, drill in DRILLS:
        if flag in argv or os.environ.get(env_name) == "1":
            drill()
            return
    sys.exit("usage: python -m tools.drills.soak <drill>, one of: "
             + " ".join(flag for flag, _, _ in DRILLS))


if __name__ == "__main__":
    main(sys.argv[1:])
