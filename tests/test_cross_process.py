"""Cross-service topology: Wallet -> Risk over real gRPC sockets.

The reference's core runtime shape (README.md:19-36): the wallet calls
risk.v1 ScoreTransaction on every money-moving RPC. These tests boot both
servers in-process on real ports, wire the wallet's risk gate through
GrpcRiskGate (the cross-process client), and exercise the full
degradation matrix over the wire: approve, block (PERMISSION_DENIED),
fail-open during outage for deposits, fail-closed for withdrawals.
"""

import grpc
import pytest

from igaming_platform_tpu.core.config import BatcherConfig
from igaming_platform_tpu.platform.repository import (
    InMemoryAccountRepository,
    InMemoryLedgerRepository,
    InMemoryTransactionRepository,
)
from igaming_platform_tpu.platform.risk_adapter import GrpcRiskGate
from igaming_platform_tpu.platform.wallet import WalletService
from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2
from igaming_platform_tpu.serve.grpc_server import (
    RiskGrpcService,
    WalletGrpcService,
    make_wallet_stub,
    serve_risk,
    serve_wallet,
)
from igaming_platform_tpu.serve.scorer import TPUScoringEngine


@pytest.fixture(scope="module")
def stack():
    """risk server + wallet server chained through GrpcRiskGate."""
    engine = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=16, max_wait_ms=1))
    risk_service = RiskGrpcService(engine)
    risk_server, _, risk_port = serve_risk(risk_service, 0)

    wallet = WalletService(
        InMemoryAccountRepository(), InMemoryTransactionRepository(),
        InMemoryLedgerRepository(),
        risk=GrpcRiskGate(f"localhost:{risk_port}"),
    )
    wallet_server, _, wallet_port = serve_wallet(WalletGrpcService(wallet), 0)
    channel = grpc.insecure_channel(f"localhost:{wallet_port}")
    yield make_wallet_stub(channel), engine, risk_server, wallet
    channel.close()
    wallet_server.stop(0)
    risk_server.stop(0)
    engine.close()


def test_deposit_scored_through_risk_service(stack):
    stub, engine, _, _ = stack
    acct = stub.CreateAccount(wallet_pb2.CreateAccountRequest(player_id="xp1")).account
    resp = stub.Deposit(wallet_pb2.DepositRequest(
        account_id=acct.id, amount=10_000, idempotency_key="x-d1",
        ip_address="10.0.0.1", device_id="dev-1",
    ))
    assert resp.new_balance == 10_000
    # The score travelled wallet -> risk -> wallet over two sockets.
    assert 0 <= resp.risk_score <= 100


def test_block_threshold_enforced_across_processes(stack):
    stub, engine, _, wallet = stack
    acct = stub.CreateAccount(wallet_pb2.CreateAccountRequest(player_id="xp2")).account
    # The wallet blocks on the raw score against ITS OWN threshold
    # (wallet_service.go:274) — drop it so any score blocks.
    old = wallet.config.risk_threshold_block
    wallet.config.risk_threshold_block = 0
    try:
        with pytest.raises(grpc.RpcError) as exc:
            stub.Deposit(wallet_pb2.DepositRequest(
                account_id=acct.id, amount=10_000, idempotency_key="x-d2"))
        assert exc.value.code() == grpc.StatusCode.PERMISSION_DENIED
    finally:
        wallet.config.risk_threshold_block = old


def test_outage_fail_open_deposit_fail_closed_withdraw(stack):
    stub, engine, risk_server, wallet = stack
    acct = stub.CreateAccount(wallet_pb2.CreateAccountRequest(player_id="xp3")).account
    stub.Deposit(wallet_pb2.DepositRequest(
        account_id=acct.id, amount=20_000, idempotency_key="x-d3"))

    # Point the wallet's gate at a dead port: the risk service is "down".
    dead_gate = GrpcRiskGate("localhost:1", timeout=0.3)
    old_gate = wallet.risk
    wallet.risk = dead_gate
    try:
        dep = stub.Deposit(wallet_pb2.DepositRequest(
            account_id=acct.id, amount=1_000, idempotency_key="x-d4"))
        assert dep.new_balance == 21_000          # fail-open: proceeds unscored
        assert dep.risk_score == 0

        with pytest.raises(grpc.RpcError) as exc:  # fail-closed
            stub.Withdraw(wallet_pb2.WithdrawRequest(
                account_id=acct.id, amount=1_000, idempotency_key="x-w1"))
        assert exc.value.code() == grpc.StatusCode.UNAVAILABLE
    finally:
        wallet.risk = old_gate

    # Risk back up: the same withdrawal (same idempotency key) succeeds.
    wd = stub.Withdraw(wallet_pb2.WithdrawRequest(
        account_id=acct.id, amount=1_000, idempotency_key="x-w1"))
    assert wd.new_balance == 20_000


def test_wallet_events_reach_risk_bridge_over_amqp(monkeypatch):
    """The full async topology over a REAL broker socket: wallet deposit ->
    transactional outbox -> AMQP publisher (confirms) -> risk-scoring
    queue -> the risk server's bridge consumes, scores, and folds the
    event into the feature store. EVENT_TRANSPORT=amqp end to end."""
    import time

    from igaming_platform_tpu.core.config import (
        BatcherConfig,
        RiskServiceConfig,
        WalletServiceConfig,
    )
    from igaming_platform_tpu.platform.server import WalletServer
    from igaming_platform_tpu.serve.amqp_testing import FakeAmqpServer
    from igaming_platform_tpu.serve.server import RiskServer

    broker = FakeAmqpServer()
    monkeypatch.setenv("EVENT_TRANSPORT", "amqp")
    risk = None
    wallet = None
    try:
        risk = RiskServer(
            RiskServiceConfig(
                rabbitmq_url=broker.url,
                batcher=BatcherConfig(batch_size=16, max_wait_ms=1.0),
            ),
            grpc_port=0, http_port=0, store_max_accounts=4096,
        )
        wallet = WalletServer(
            WalletServiceConfig(
                rabbitmq_url=broker.url,
                risk_service_addr=f"localhost:{risk.grpc_port}",
            ),
            grpc_port=0, http_port=0,
        )
        acct = wallet.wallet.create_account("amqp-x-proc")
        wallet.wallet.deposit(acct.id, 25_000, "dep-amqp-1",
                              ip="9.9.9.9", device_id="dev-x")

        deadline = time.monotonic() + 10.0
        while risk.bridge.events_processed < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert risk.bridge.events_processed >= 1
        # The event crossed the broker and updated velocity features.
        c1, _, _ = risk.engine.features.velocity(acct.id)
        assert c1 >= 1
        assert broker.published_count >= 1
    finally:
        if wallet is not None:
            wallet.shutdown(grace=1)
        if risk is not None:
            risk.shutdown(grace=1)
        broker.close()
