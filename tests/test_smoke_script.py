"""`make api-test` (tools/drills/smoke.py) stays green against live
risk + wallet servers — the reference's grpcurl smoke surface."""

import os
import subprocess
import sys

from igaming_platform_tpu.core.config import (
    BatcherConfig,
    RiskServiceConfig,
    WalletServiceConfig,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_api_smoke_against_live_services():
    from igaming_platform_tpu.platform.server import WalletServer
    from igaming_platform_tpu.serve.server import RiskServer

    risk = RiskServer(
        RiskServiceConfig(batcher=BatcherConfig(batch_size=32, max_wait_ms=1.0)),
        grpc_port=0, http_port=0, store_max_accounts=4096,
    )
    wallet = None
    try:
        wallet = WalletServer(
            WalletServiceConfig(risk_service_addr=f"localhost:{risk.grpc_port}"),
            grpc_port=0, http_port=0,
        )
        proc = subprocess.run(
            [sys.executable, "-m", "tools.drills.smoke",
             f"localhost:{risk.grpc_port}", f"localhost:{wallet.grpc_port}"],
            capture_output=True, text=True, timeout=120, cwd=_REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "FAIL" not in proc.stdout
        # Every surface actually ran.
        for name in ("ScoreTransaction", "ScoreBatch", "PredictLTV",
                     "CreateAccount", "Deposit", "Bet", "GetBalance"):
            assert f"ok   {name}" in proc.stdout, proc.stdout
    finally:
        if wallet is not None:
            wallet.shutdown(grace=1)
        risk.shutdown(grace=1)
