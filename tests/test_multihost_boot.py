"""Env-driven multi-host serving boot (the OPERATIONAL path).

tests/test_multihost_serving.py proves the multi-host engine
programmatically; this suite drives the PRODUCTION entrypoint the way a
deployment would: two `python -m igaming_platform_tpu.serve.server`
processes with MULTIHOST_ROLE=front|follower + the jax.distributed env
contract — the front boots the FULL risk server (health, sidecar, AOT
warmup over the global mesh) and serves real RPCs; SIGTERM drains both.
"""

import os
import signal
import subprocess
import sys
import textwrap
import time

import grpc

import igaming_platform_tpu  # noqa: F401 — puts proto_gen on sys.path
from risk.v1 import risk_pb2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WRAPPER = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.environ["REPO_ROOT"])
from igaming_platform_tpu.serve.server import main
main(store_max_accounts=4096)
"""


_SHUTDOWN_CEILING_S = 120


def _exit_or_kill(proc) -> bool:
    """True if ``proc`` had to be killed at the ceiling."""
    try:
        proc.wait(timeout=_SHUTDOWN_CEILING_S)
        return False
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        return True


def test_env_driven_front_follower_boot(tmp_path, free_port):
    coord, work = free_port(), free_port()
    wrapper = tmp_path / "boot.py"
    wrapper.write_text(textwrap.dedent(_WRAPPER))

    base = dict(
        os.environ,
        REPO_ROOT=REPO,
        COORDINATOR_ADDRESS=f"localhost:{coord}",
        NUM_PROCESSES="2",
        MULTIHOST_WORK_PORT=str(work),
        MULTIHOST_FOLLOWER_PORTS=str(work),
        # Keep the front's boot light: mock backend, small batch ladder.
        BATCH_SIZE="16",
    )
    base.pop("XLA_FLAGS", None)
    # Child output goes to FILES, not pipes: an undrained pipe buffer
    # would block the server mid-boot (opaque flake) once logging
    # exceeds ~64KB.
    fol_log = open(tmp_path / "follower.log", "w+")
    fro_log = open(tmp_path / "front.log", "w+")
    follower = subprocess.Popen(
        [sys.executable, str(wrapper)],
        env={**base, "MULTIHOST_ROLE": "follower", "PROCESS_ID": "1"},
        stdout=fol_log, stderr=subprocess.STDOUT, text=True,
    )
    # The SERVER picks its own gRPC/HTTP ports (0 = ephemeral) and logs
    # them — a test-side bind-then-close pick races other suites' ports.
    front = subprocess.Popen(
        [sys.executable, str(wrapper)],
        env={**base, "MULTIHOST_ROLE": "front", "PROCESS_ID": "0",
             "GRPC_PORT": "0", "HTTP_PORT": "0"},
        stdout=fro_log, stderr=subprocess.STDOUT, text=True,
    )

    def tail(f):
        f.flush()
        f.seek(0)
        return f.read()[-3000:]
    try:
        # Wait for readiness through the real sidecar, learning the
        # server-chosen ports from its own log line.
        import re
        import urllib.request

        deadline = time.time() + 240
        ready = False
        gport = hport = None
        while time.time() < deadline:
            for p, name, f in ((front, "front", fro_log),
                               (follower, "follower", fol_log)):
                if p.poll() is not None:
                    raise AssertionError(f"{name} died during boot:\n{tail(f)}")
            if hport is None:
                m = re.search(r"risk server up: grpc=(\d+) http=(\d+)", tail(fro_log))
                if m:
                    gport, hport = int(m.group(1)), int(m.group(2))
                else:
                    time.sleep(0.5)
                    continue
            try:
                with urllib.request.urlopen(
                        f"http://localhost:{hport}/ready", timeout=2) as r:
                    if b"true" in r.read():
                        ready = True
                        break
            except OSError:
                time.sleep(0.5)
        assert ready, f"front never became ready:\n{tail(fro_log)}"

        ch = grpc.insecure_channel(f"localhost:{gport}")
        score = ch.unary_unary(
            "/risk.v1.RiskService/ScoreTransaction",
            request_serializer=risk_pb2.ScoreTransactionRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreTransactionResponse.FromString)
        batch = ch.unary_unary(
            "/risk.v1.RiskService/ScoreBatch",
            request_serializer=risk_pb2.ScoreBatchRequest.SerializeToString,
            response_deserializer=risk_pb2.ScoreBatchResponse.FromString)

        r = score(risk_pb2.ScoreTransactionRequest(
            account_id="mh-boot", amount=5000, transaction_type="deposit"),
            timeout=120)
        assert 0 <= r.score <= 100

        resp = batch(risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(
                account_id=f"mh-boot-{i}", amount=1000 + i,
                transaction_type="bet")
            for i in range(24)
        ]), timeout=120)
        assert len(resp.results) == 24
        assert all(0 <= x.score <= 100 for x in resp.results)
        ch.close()
    finally:
        front.send_signal(signal.SIGTERM)
        # The front's shutdown closes the work channel -> follower exits.
        # A process still running at the ceiling is killed, and that is
        # reported as this test's timeout, never read as an exit code.
        overdue = [name for name, p in (("front", front), ("follower", follower))
                   if _exit_or_kill(p)]
        front_out, follower_out = tail(fro_log), tail(fol_log)
        fro_log.close()
        fol_log.close()

    assert not overdue, (
        f"{overdue} still running {_SHUTDOWN_CEILING_S} s after SIGTERM to the "
        f"front; killed by the test\nfront:\n{front_out}\nfollower:\n{follower_out}")
    assert front.returncode == 0, front_out
    assert "shutting down" in front_out
    assert follower.returncode == 0, follower_out
