"""Scoring-replica fleet rig: N risk-server OS processes + fault schedule.

The unit of failure is the replica process — the Podracer pod-as-unit
topology: each replica is a full production-wired
risk server (supervised engine, gRPC + health, HTTP sidecar with
/debug/supervisorz), booted as its own OS process, killed/wedged/
restarted by the harness while a router (serve/router.py) or client-side
picker keeps traffic flowing.

Replica process protocol (``--replica``): boot, then print one line
``PORT=<grpc> HTTP=<http> READY`` on stdout; serve until SIGTERM/SIGKILL.
All replicas resolve IDENTICAL params (seeded multitask init), so any
account scores bit-exact on any replica — failover correctness is
checkable, not assumed.

Fault schedule (``FleetFaultSchedule``): time-offset process faults —
``kill`` (SIGKILL, pod death), ``wedge`` (SIGSTOP: the process stops
answering but its sockets stay open — the nastier failure), ``resume``
(SIGCONT), ``restart`` (respawn on the same port, same ring identity).
Parsed from a plan string (``FLEET_FAULTS`` env in soak --fleet-chaos)::

    kill:replica=1:at=8; restart:replica=1:at=16; wedge:replica=2:at=20

Driven by ``python -m tools.drills.soak --fleet-chaos``.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# Replica process entry


def replica_main(grpc_port: int, http_port: int, ml_backend: str,
                 batch_size: int) -> None:
    """One scoring replica: the production RiskServer wiring (supervised
    engine, breakers, watchdog, degraded tier, health, sidecar)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from igaming_platform_tpu.core.config import RiskServiceConfig
    from igaming_platform_tpu.serve.server import RiskServer

    params = None
    if ml_backend == "multitask":
        from igaming_platform_tpu.models.multitask import init_multitask

        # Seeded init: every replica in the fleet resolves the SAME
        # params, so an account failing over scores bit-exact.
        params = {"multitask": jax.device_get(
            init_multitask(jax.random.key(0)))}
    config = RiskServiceConfig.from_env()
    if batch_size:
        import dataclasses

        config = dataclasses.replace(
            config, batcher=dataclasses.replace(
                config.batcher, batch_size=batch_size, max_wait_ms=1.0))
    server = RiskServer(config, ml_backend=ml_backend, params=params,
                        grpc_port=grpc_port, http_port=http_port)
    print(f"PORT={server.grpc_port} HTTP={server.http_port} READY",
          flush=True)
    server.wait_for_signal()


# ---------------------------------------------------------------------------
# Replica process handle (harness side)


class ReplicaProc:
    """One replica OS process: spawn / kill / wedge / resume / restart.
    The ring identity (``rid``) is stable across restarts — a restarted
    replica reuses its port so routers re-admit it in place."""

    def __init__(self, rid: str, *, ml_backend: str = "multitask",
                 batch_size: int = 256, boot_timeout_s: float = 120.0,
                 env_extra: dict | None = None):
        self.rid = rid
        self.ml_backend = ml_backend
        self.batch_size = batch_size
        self.boot_timeout_s = boot_timeout_s
        self.env_extra = dict(env_extra or {})
        self.proc: subprocess.Popen | None = None
        self.grpc_port = 0
        self.http_port = 0
        self.wedged = False

    @property
    def addr(self) -> str:
        return f"localhost:{self.grpc_port}"

    @property
    def http_addr(self) -> str:
        return f"localhost:{self.http_port}"

    def spawn(self, grpc_port: int = 0, http_port: int = 0) -> "ReplicaProc":
        env = dict(os.environ, JAX_PLATFORMS="cpu", **self.env_extra)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tools.drills.fleet", "--replica",
             "--port", str(grpc_port), "--http-port", str(http_port),
             "--ml-backend", self.ml_backend,
             "--batch", str(self.batch_size)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=env, cwd=REPO)
        deadline = time.monotonic() + self.boot_timeout_s
        line = ""
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                raise RuntimeError(
                    f"replica {self.rid} exited during boot "
                    f"(rc={self.proc.poll()})")
            if "READY" in line:
                break
        else:
            raise RuntimeError(f"replica {self.rid} boot timed out")
        fields = dict(kv.split("=", 1) for kv in line.split() if "=" in kv)
        self.grpc_port = int(fields["PORT"])
        self.http_port = int(fields["HTTP"])
        self.wedged = False
        return self

    def kill(self) -> None:
        """SIGKILL — pod death, no goodbye."""
        if self.proc is not None:
            self.proc.kill()
            self.proc.wait(timeout=10)

    def wedge(self) -> None:
        """SIGSTOP — the process freezes mid-whatever: sockets stay open,
        health probes time out instead of failing fast. The failure mode
        TCP cannot detect for you."""
        if self.proc is not None and self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGSTOP)
            self.wedged = True

    def resume(self) -> None:
        if self.proc is not None and self.wedged:
            os.kill(self.proc.pid, signal.SIGCONT)
            self.wedged = False

    def restart(self) -> "ReplicaProc":
        """Respawn on the SAME ports (ring identity preserved). The old
        process must be dead first (kill/terminate)."""
        old_grpc, old_http = self.grpc_port, self.http_port
        self.spawn(grpc_port=old_grpc, http_port=old_http)
        if self.grpc_port != old_grpc:
            raise RuntimeError(
                f"replica {self.rid} restarted on port {self.grpc_port}, "
                f"wanted {old_grpc} (stale socket?)")
        return self

    def brownout(self) -> None:
        """Force the replica's supervisor into BROWNOUT via its operator
        surface: scoring sheds UNAVAILABLE + grpc-retry-pushback-ms and
        health flips NOT_SERVING — the router must honor the pushback on
        in-flight forwards and evict on the next probe."""
        import urllib.request

        req = urllib.request.Request(
            f"http://{self.http_addr}/debug/breakers",
            data=b'{"brownout": "force"}', method="POST")
        urllib.request.urlopen(req, timeout=5).read()

    def unbrownout(self) -> None:
        import urllib.request

        req = urllib.request.Request(
            f"http://{self.http_addr}/debug/breakers",
            data=b'{"brownout": "clear"}', method="POST")
        urllib.request.urlopen(req, timeout=5).read()

    def terminate(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            if self.wedged:
                self.resume()
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)


class ReplicaFleet:
    """K replica processes booted concurrently (JAX init dominates boot;
    serial boots would triple the rig's setup time).

    ``env_by_replica`` layers per-replica env on top of the shared
    ``env_extra`` — how a chaos soak gives ONE replica a CHAOS_PLAN
    (the latency-fault victim) while the rest stay clean."""

    def __init__(self, k: int, *, env_by_replica: dict[int, dict] | None = None,
                 **kwargs):
        self.replicas = [ReplicaProc(f"r{i}", **kwargs) for i in range(k)]
        for idx, extra in (env_by_replica or {}).items():
            self.replicas[idx].env_extra.update(extra)

    def start(self) -> "ReplicaFleet":
        errors: list[str] = []

        def boot(r: ReplicaProc) -> None:
            try:
                r.spawn()
            except Exception as exc:  # noqa: BLE001 — collected; start() re-raises below
                errors.append(f"{r.rid}: {exc!r}")

        threads = [threading.Thread(target=boot, args=(r,))
                   for r in self.replicas]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            self.stop()
            raise RuntimeError(f"fleet boot failed: {errors}")
        return self

    def addrs(self, k: int | None = None) -> list[str]:
        return [r.addr for r in self.replicas[:k]]

    def router_spec(self, k: int | None = None) -> dict:
        """rid -> (grpc addr, http addr) for ScoringRouter."""
        return {r.rid: (r.addr, r.http_addr) for r in self.replicas[:k]}

    def stop(self) -> None:
        for r in self.replicas:
            r.terminate()


# ---------------------------------------------------------------------------
# Fault schedule


class FleetFault:
    """One scheduled process fault: (kind, replica index, offset s)."""

    KINDS = ("kill", "wedge", "resume", "restart", "brownout", "unbrownout")

    def __init__(self, kind: str, replica: int, at_s: float):
        if kind not in self.KINDS:
            raise ValueError(f"unknown fleet fault {kind!r} (use {self.KINDS})")
        self.kind = kind
        self.replica = int(replica)
        self.at_s = float(at_s)

    def __repr__(self) -> str:
        return f"FleetFault({self.kind} replica={self.replica} at={self.at_s}s)"


class FleetFaultSchedule:
    """Time-offset process faults against a ReplicaFleet. Parse errors
    are LOUD (a typo'd plan silently not injecting would fake a green
    chaos run — same contract as serve/chaos.py)."""

    def __init__(self, faults: list[FleetFault]):
        self.faults = sorted(faults, key=lambda f: f.at_s)
        # Execution log for the artifact: (kind, replica, planned, actual).
        self.executed: list[dict] = []

    @classmethod
    def from_string(cls, plan: str) -> "FleetFaultSchedule":
        faults: list[FleetFault] = []
        for raw in plan.split(";"):
            raw = raw.strip()
            if not raw:
                continue
            kind, _, rhs = raw.partition(":")
            fields: dict[str, float] = {}
            for item in rhs.split(":"):
                key, _, val = item.partition("=")
                if key not in ("replica", "at"):
                    raise ValueError(
                        f"bad FLEET_FAULTS field {item!r} in {raw!r}")
                fields[key] = float(val)
            faults.append(FleetFault(
                kind.strip(), int(fields.get("replica", 0)),
                fields.get("at", 0.0)))
        return cls(faults)

    def run(self, fleet: ReplicaFleet, t0: float,
            on_fault=None) -> None:
        """Execute the schedule against ``fleet``, offsets relative to
        monotonic ``t0``. Blocks until the last fault fired."""
        for fault in self.faults:
            delay = t0 + fault.at_s - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            replica = fleet.replicas[fault.replica]
            # The fault's timestamp is when it STARTS biting (SIGKILL is
            # delivered instantly; proc.wait afterwards is bookkeeping) —
            # detection clocks measure from here, not from when the
            # harness finished reaping.
            t_actual = time.monotonic() - t0
            getattr(replica, fault.kind)()
            done_s = time.monotonic() - t0
            self.executed.append({
                "kind": fault.kind, "replica": replica.rid,
                "planned_at_s": fault.at_s,
                "actual_at_s": round(t_actual, 3),
                "done_at_s": round(done_s, 3),
            })
            if on_fault is not None:
                on_fault(fault, replica, t_actual, done_s)


# ---------------------------------------------------------------------------
# CLI


def main() -> None:
    args = sys.argv[1:]
    if "--replica" in args:
        def opt(name: str, default: str) -> str:
            return args[args.index(name) + 1] if name in args else default

        replica_main(
            grpc_port=int(opt("--port", "0")),
            http_port=int(opt("--http-port", "0")),
            ml_backend=opt("--ml-backend", "multitask"),
            batch_size=int(opt("--batch", "256")),
        )
        return
    # Dev convenience: boot a K-fleet, print the replica table, serve
    # until interrupted.
    k = int(os.environ.get("FLEET_K", "3"))
    fleet = ReplicaFleet(k).start()
    try:
        print(json.dumps({
            "replicas": {r.rid: {"grpc": r.addr, "http": r.http_addr}
                         for r in fleet.replicas},
        }), flush=True)
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        fleet.stop()


if __name__ == "__main__":
    main()
