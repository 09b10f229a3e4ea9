"""Pipelined extended-query batching on the Postgres wallet path.

Inside a unit of work the PG adapter buffers Parse/Bind/Execute frames and
ships the whole statement batch with ONE Sync (pgwire._Cursor docstring) —
the reference pays a full protocol round trip per statement
(/root/reference/services/wallet/internal/service/wallet_service.go:240-330
via database/sql); here the per-op store sequence costs ~3 round trips.
These tests pin that the batching is SEMANTICS-PRESERVING: conflicts,
duplicates, rollback, and the books all behave exactly as the eager path.
"""

import threading

import pytest

from igaming_platform_tpu.platform.domain import (
    ConcurrentUpdateError,
    DuplicateTransactionError,
)
from igaming_platform_tpu.platform.outbox import OutboxPublisher
from igaming_platform_tpu.platform.pg_store import PostgresStore
from igaming_platform_tpu.platform.pg_testing import PgSqliteServer
from igaming_platform_tpu.platform.wallet import WalletService


@pytest.fixture()
def pg(tmp_path):
    server = PgSqliteServer(str(tmp_path / "pipe.db"))
    yield server
    server.close()


def _wallet(store):
    return WalletService(
        store.accounts, store.transactions, store.ledger,
        events=OutboxPublisher(store), audit=store.audit,
    )


def _count_sends(conn):
    """Wrap PgConnection._send with a counter: each call is one socket
    write == one client->server round trip boundary."""
    counter = {"n": 0}
    orig = conn._send

    def counting(data):
        counter["n"] += 1
        return orig(data)

    conn._send = counting
    return counter


def test_deposit_pipeline_round_trips_and_books(pg):
    store = PostgresStore(pg.url)
    wallet = _wallet(store)
    acct = wallet.create_account("p1")
    wallet.deposit(acct.id, 10_000, "dep-1")

    counter = _count_sends(store._pg)
    wallet.deposit(acct.id, 5_000, "dep-2")
    # Eagerly this op costs ~9 socket writes (idempotency SELECT, account
    # SELECT, BEGIN, INSERT tx, UPDATE balance, INSERT ledger, UPDATE tx,
    # INSERT outbox, COMMIT). Pipelined: the UoW's writes collapse into
    # two flushes (BEGIN+INSERT+UPDATE at the rowcount check;
    # ledger+complete+outbox+COMMIT), so <= 5 total.
    assert counter["n"] <= 5, f"deposit cost {counter['n']} round trips"

    acct_now = wallet.get_balance(acct.id)
    assert acct_now.balance == 15_000
    assert store.ledger.verify_balance(acct.id, acct_now.balance)
    store.close()


def test_duplicate_idempotency_maps_through_pipeline(pg):
    """A same-key INSERT rejected by the server surfaces as
    DuplicateTransactionError even though the error is reported at flush
    time (the error_mapper travels with the statement)."""
    store = PostgresStore(pg.url)
    wallet = _wallet(store)
    acct = wallet.create_account("p2")
    wallet.deposit(acct.id, 1_000, "dup-key")

    # Bypass the replay fast path by writing a COMPLETED row through a
    # second store, then force the first wallet's pipeline to hit the
    # unique index: simulate the race where the replay check misses.
    tx = store.transactions.get_by_idempotency_key(acct.id, "dup-key")
    assert tx is not None

    # Direct store-level probe: create a conflicting row inside a UoW and
    # observe the mapped duplicate at flush.
    from igaming_platform_tpu.platform.domain import Transaction, TxType

    dup = Transaction(
        id="tx-dup", account_id=acct.id, idempotency_key="dup-key",
        type=TxType.DEPOSIT, amount=1, balance_before=0, balance_after=1,
    )
    with pytest.raises(DuplicateTransactionError):
        with store.unit_of_work():
            store.transactions.create(dup)
            # Touch a result so the pipeline flushes inside the UoW (the
            # wallet's real sequence flushes at the balance rowcount).
            store.accounts.get_by_id(acct.id)
    # The aborted UoW must leave the connection clean and usable.
    assert wallet.get_balance(acct.id).balance == 1_000
    store.close()


def test_optimistic_conflict_behavior_unchanged(pg):
    """Two stores contending over one account through the real wire: the
    loser raises ConcurrentUpdateError (or retries internally), the books
    reconcile exactly — same contract as the eager client."""
    s1 = PostgresStore(pg.url)
    s2 = PostgresStore(pg.url, bootstrap=False)
    w1, w2 = _wallet(s1), _wallet(s2)
    acct = w1.create_account("p3")
    w1.deposit(acct.id, 100_000, "seed")

    errs: list[Exception] = []
    done: list[int] = []

    def op(wallet, key):
        try:
            wallet.bet(acct.id, 100, key)
            done.append(1)
        except ConcurrentUpdateError as exc:  # loser is allowed to lose
            errs.append(exc)

    threads = [
        threading.Thread(target=op, args=(w, f"bet-{i}-{id(w)}"))
        for i in range(10) for w in (w1, w2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    acct_now = s1.accounts.get_by_id(acct.id)
    assert acct_now.balance == 100_000 - 100 * len(done)
    assert s1.ledger.verify_balance(acct.id, acct_now.balance)
    s1.close()
    s2.close()


def test_rollback_discards_unflushed_statements(pg):
    """A Python-side failure between pipelined statements must discard the
    unsent frames: nothing half-applies, the connection stays healthy."""
    store = PostgresStore(pg.url)
    wallet = _wallet(store)
    acct = wallet.create_account("p4")
    wallet.deposit(acct.id, 2_000, "seed4")

    class Boom(RuntimeError):
        pass

    with pytest.raises(Boom):
        with store.unit_of_work():
            store.audit("account", acct.id, "noop")  # buffered, never sent
            raise Boom()

    # Connection healthy, nothing applied.
    assert wallet.get_balance(acct.id).balance == 2_000
    rows = store._pg.execute(
        "SELECT COUNT(*) FROM audit_log WHERE action = ?", ("noop",)
    ).fetchone()
    assert rows[0] == 0
    store.close()


def test_failed_first_statement_skips_rest_of_batch(pg):
    """Extended-protocol error semantics: when a pipelined statement
    fails, the server skips everything until Sync — later statements of
    the batch never execute, so nothing can autocommit outside a
    transaction whose BEGIN failed (BEGIN rides the pipeline as statement
    0, pgwire.begin_pipelined)."""
    from igaming_platform_tpu.platform.pgwire import PgConnection, PgError

    conn = PgConnection(pg.url)
    conn.connect()
    conn.execute("CREATE TABLE skiptest (x BIGINT PRIMARY KEY)")
    conn.execute_pipelined("INSERT INTO no_such_table VALUES (1)")
    conn.execute_pipelined("INSERT INTO skiptest VALUES (1)")
    with pytest.raises(PgError):
        conn.flush()
    assert conn.execute("SELECT COUNT(*) FROM skiptest").fetchone()[0] == 0
    conn.close()


def test_rollback_does_not_poison_statement_cache(pg):
    """A rollback that drops never-sent frames must not leave their
    prepared-statement names in the cache — the server never saw those
    Parse frames, and binding them later would 26000 forever (review
    finding, round 5)."""
    from igaming_platform_tpu.platform.pgwire import PgConnection

    conn = PgConnection(pg.url)
    conn.connect()
    conn.execute("CREATE TABLE pc (x BIGINT)")
    conn.begin_pipelined()
    conn.execute_pipelined("INSERT INTO pc VALUES (?)", (1,))  # new SQL, never sent
    conn.rollback()  # drops the buffered batch without touching the socket
    # Same SQL must re-Parse cleanly under a fresh name and work.
    conn.execute("INSERT INTO pc VALUES (?)", (2,))
    assert conn.execute("SELECT COUNT(*) FROM pc").fetchone()[0] == 1
    conn.close()


def test_wallet_verbs_over_grpc_on_the_postgres_store(pg):
    """wallet.v1 over a real socket -> handler -> WalletService ->
    PostgresStore -> the protocol-v3 server: the one stack no other test
    drives end to end (the gRPC tests run on the in-memory repositories,
    the tests above call the service directly). Held to its books, not to
    a rate."""
    import grpc

    from igaming_platform_tpu.proto_gen.wallet.v1 import wallet_pb2
    from igaming_platform_tpu.serve.grpc_server import (
        WalletGrpcService,
        make_wallet_stub,
        serve_wallet,
    )

    store = PostgresStore(pg.url)
    server, _, port = serve_wallet(WalletGrpcService(_wallet(store)), 0)
    channel = grpc.insecure_channel(f"localhost:{port}")
    stub = make_wallet_stub(channel)
    try:
        acct = stub.CreateAccount(wallet_pb2.CreateAccountRequest(
            player_id="wire-pg", currency="USD"), timeout=30).account
        dep = stub.Deposit(wallet_pb2.DepositRequest(
            account_id=acct.id, amount=10_000, idempotency_key="wire-dep"),
            timeout=30)
        assert dep.new_balance == 10_000
        stub.Bet(wallet_pb2.BetRequest(
            account_id=acct.id, amount=1_000, idempotency_key="wire-bet",
            game_id="g1"), timeout=30)
        stub.Win(wallet_pb2.WinRequest(
            account_id=acct.id, amount=250, idempotency_key="wire-win",
            game_id="g1"), timeout=30)
        # The same key again is the same transaction, not a second one.
        again = stub.Deposit(wallet_pb2.DepositRequest(
            account_id=acct.id, amount=10_000, idempotency_key="wire-dep"),
            timeout=30)
        assert again.transaction.id == dep.transaction.id
        bal = stub.GetBalance(wallet_pb2.GetBalanceRequest(
            account_id=acct.id), timeout=30)
        assert bal.balance == 9_250
        assert store.ledger.verify_balance(acct.id, bal.balance)
    finally:
        channel.close()
        server.stop(0)
        store.close()
