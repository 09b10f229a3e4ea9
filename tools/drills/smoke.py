"""API smoke test against RUNNING services — the reference's `make
api-test` grpcurl calls (/root/reference/Makefile:231-241), as python
stubs (the image has no grpcurl; the servers do expose reflection-free
generic handlers, so stubs come from the shared method tables).

Usage: python -m tools.drills.smoke [risk_addr] [wallet_addr]
Defaults: localhost:50052 / localhost:50051; wallet checks are skipped
when no wallet server is listening.
"""

import sys
import uuid

import grpc

from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2
from igaming_platform_tpu.serve.grpc_server import (
    make_health_stub,
    make_risk_stub,
    make_wallet_stub,
    health_pb2,
)


def check(name, fn):
    try:
        out = fn()
    except grpc.RpcError as exc:
        print(f"  FAIL {name}: {exc.code().name} {exc.details()}")
        return False
    print(f"  ok   {name}: {str(out)[:80].replace(chr(10), ' ')}")
    return True


def main() -> None:
    risk_addr = sys.argv[1] if len(sys.argv) > 1 else "localhost:50052"
    wallet_addr = sys.argv[2] if len(sys.argv) > 2 else "localhost:50051"
    failures = 0

    print(f"risk @ {risk_addr}")
    ch = grpc.insecure_channel(risk_addr)
    risk = make_risk_stub(ch)
    health = make_health_stub(ch)
    failures += not check("health.Check", lambda: health.Check(
        health_pb2.HealthCheckRequest(), timeout=10))
    failures += not check("ScoreTransaction", lambda: risk.ScoreTransaction(
        risk_pb2.ScoreTransactionRequest(
            account_id="smoke-1", amount=150_000, transaction_type="withdraw",
            ip_address="1.2.3.4", device_id="dev-1"), timeout=30))
    failures += not check("ScoreBatch(3)", lambda: risk.ScoreBatch(
        risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(account_id=f"smoke-{i}", amount=1000 + i)
            for i in range(3)]), timeout=30))
    failures += not check("PredictLTV", lambda: risk.PredictLTV(
        risk_pb2.PredictLTVRequest(account_id="smoke-1"), timeout=30))
    failures += not check("GetThresholds", lambda: risk.GetThresholds(
        risk_pb2.GetThresholdsRequest(), timeout=10))
    failures += not check("CheckBlacklist", lambda: risk.CheckBlacklist(
        risk_pb2.CheckBlacklistRequest(device_id="dev-1"), timeout=10))
    ch.close()

    print(f"wallet @ {wallet_addr}")
    wch = grpc.insecure_channel(wallet_addr)
    try:
        grpc.channel_ready_future(wch).result(timeout=3)
    except grpc.FutureTimeoutError:
        print("  (no wallet server listening — skipped)")
        wch.close()
        sys.exit(1 if failures else 0)
    wallet = make_wallet_stub(wch)
    player = f"smoke-{uuid.uuid4().hex[:8]}"
    acct = None

    def create():
        nonlocal acct
        acct = wallet.CreateAccount(
            wallet_pb2.CreateAccountRequest(player_id=player, currency="USD"), timeout=10)
        return acct.account.id

    if not check("CreateAccount", create):
        print("  (remaining wallet checks need an account — aborting)")
        wch.close()
        sys.exit(1)
    failures += not check("Deposit", lambda: wallet.Deposit(
        wallet_pb2.DepositRequest(account_id=acct.account.id, amount=10_000,
                                  idempotency_key=f"{player}-dep"), timeout=30))
    failures += not check("Bet", lambda: wallet.Bet(
        wallet_pb2.BetRequest(account_id=acct.account.id, amount=1_000,
                              idempotency_key=f"{player}-bet", game_id="g1"), timeout=30))
    failures += not check("GetBalance", lambda: wallet.GetBalance(
        wallet_pb2.GetBalanceRequest(account_id=acct.account.id), timeout=10))
    failures += not check("GetTransactionHistory", lambda: wallet.GetTransactionHistory(
        wallet_pb2.GetTransactionHistoryRequest(account_id=acct.account.id, limit=10),
        timeout=10))
    wch.close()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
