"""Batch-feature refresh: the analytical-store ticker the reference
declares but never implements (risk/cmd/main.go:226-236).

The restart scenario is the one that matters: a fresh scorer has empty
incremental state; after one refresh from the wallet store its batch
aggregates reflect the full transaction history.
"""

import time

import numpy as np

from igaming_platform_tpu.core.features import F, NUM_FEATURES
from igaming_platform_tpu.platform.repository import SQLiteStore
from igaming_platform_tpu.platform.wallet import WalletService
from igaming_platform_tpu.serve.batch_refresh import (
    BatchFeatureRefreshJob,
    wallet_store_source,
)
from igaming_platform_tpu.serve.feature_store import InMemoryFeatureStore


def seeded_wallet(tmp_path):
    path = str(tmp_path / "wallet.db")
    store = SQLiteStore(path)
    wallet = WalletService(store.accounts, store.transactions, store.ledger)
    acct = wallet.create_account("batch-p")
    for i in range(4):
        wallet.deposit(acct.id, 10_000, f"bd-{i}")
    for i in range(6):
        wallet.bet(acct.id, 1_000, f"bb-{i}")
    wallet.win(acct.id, 3_000, "bw-0")
    wallet.withdraw(acct.id, 2_000, "bwd-0")
    return path, store, acct


def test_fresh_store_hydrates_from_wallet_scan(tmp_path):
    path, store, acct = seeded_wallet(tmp_path)

    fresh = InMemoryFeatureStore()  # restarted scorer: no stream history
    job = BatchFeatureRefreshJob(fresh, wallet_store_source(path))
    assert job.refresh_once() == 1

    row = np.zeros(NUM_FEATURES, dtype=np.float32)
    fresh.fill_row(row, acct.id, 0, "bet")
    assert row[F.DEPOSIT_COUNT] == 4
    assert row[F.TOTAL_DEPOSITS] == 4 * 10_000
    assert row[F.WITHDRAW_COUNT] == 1
    assert row[F.TOTAL_WITHDRAWALS] == 2_000
    assert row[F.NET_DEPOSIT] == 4 * 10_000 - 2_000
    assert row[F.AVG_BET_SIZE] == 1_000
    store.close()


def test_refresh_overwrites_drifted_aggregates(tmp_path):
    path, store, acct = seeded_wallet(tmp_path)
    fs = InMemoryFeatureStore()
    fs.load_batch_features(acct.id, total_deposits=999, deposit_count=999)
    BatchFeatureRefreshJob(fs, wallet_store_source(path)).refresh_once()
    row = np.zeros(NUM_FEATURES, dtype=np.float32)
    fs.fill_row(row, acct.id, 0, "bet")
    assert row[F.DEPOSIT_COUNT] == 4  # authoritative scan wins
    store.close()


def test_refresh_does_not_touch_realtime_windows(tmp_path):
    path, store, acct = seeded_wallet(tmp_path)
    from igaming_platform_tpu.serve.feature_store import TransactionEvent

    fs = InMemoryFeatureStore()
    fs.update(TransactionEvent(acct.id, 500, "deposit", ip="1.2.3.4",
                               device_id="d1", timestamp=time.time()))
    before = fs.velocity(acct.id)
    BatchFeatureRefreshJob(fs, wallet_store_source(path)).refresh_once()
    assert fs.velocity(acct.id) == before  # stream-fed state untouched
    store.close()


def test_ticker_runs_periodically(tmp_path):
    path, store, _ = seeded_wallet(tmp_path)
    fs = InMemoryFeatureStore()
    job = BatchFeatureRefreshJob(fs, wallet_store_source(path), interval_s=0.01)
    job.start()
    deadline = time.time() + 60.0
    while job.last_refresh_count == 0 and time.time() < deadline:
        time.sleep(0.01)
    job.stop()
    assert job.last_refresh_count == 1
    assert job.last_refresh_at > 0
    store.close()


def test_wallet_store_source_reads_postgres_backend(tmp_path):
    """The refresh source scans the Postgres store of record too (same
    dispatch as the LTV job — open_wallet_reader)."""
    from igaming_platform_tpu.platform.pg_store import PostgresStore
    from igaming_platform_tpu.platform.pg_testing import PgSqliteServer
    from igaming_platform_tpu.platform.wallet import WalletService

    pg = PgSqliteServer(str(tmp_path / "refresh_pg.db"))
    store = PostgresStore(pg.url)
    try:
        wallet = WalletService(store.accounts, store.transactions, store.ledger,
                               audit=store.audit)
        acct = wallet.create_account("refresh-pg")
        wallet.deposit(acct.id, 7_000, "d1")
        wallet.bet(acct.id, 1_500, "b1")

        rows = wallet_store_source(pg.url)()
        bf = rows[acct.id]
        assert bf.total_deposits == 7_000 and bf.deposit_count == 1
        assert bf.total_bets == 1_500 and bf.bet_count == 1
        assert bf.created_at > 0
    finally:
        store.close()
        pg.close()
