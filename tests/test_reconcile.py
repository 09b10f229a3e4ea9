"""Reconciliation sweep: clean books pass, corruption is caught + audited.

The reference ships VerifyBalance (postgres.go:371-390) and the
BalanceSnapshot type but no job ever runs them; here the sweep is a real
background job with metrics and audit output.
"""

from igaming_platform_tpu.obs.metrics import ServiceMetrics
from igaming_platform_tpu.platform.reconcile import ReconciliationJob, Reconciler
from igaming_platform_tpu.platform.repository import SQLiteStore
from igaming_platform_tpu.platform.wallet import WalletService


def seeded_store(tmp_path, name: str):
    store = SQLiteStore(str(tmp_path / name))
    wallet = WalletService(store.accounts, store.transactions, store.ledger)
    ids = []
    for i in range(5):
        acct = wallet.create_account(f"rec-{i}")
        wallet.deposit(acct.id, 10_000 + i, f"r-{i}")
        if i % 2 == 0:
            wallet.bet(acct.id, 1_000, f"rb-{i}")
        ids.append(acct.id)
    return store, wallet, ids


def test_clean_books_reconcile_with_snapshots(tmp_path):
    store, wallet, ids = seeded_store(tmp_path, "clean.db")
    metrics = ServiceMetrics("wallet")
    rec = Reconciler(store.accounts, store.ledger, metrics=metrics)
    report = rec.run_once(keep_snapshots=True)
    assert report.checked == 5
    assert report.mismatched == 0
    assert len(report.snapshots) == 5
    assert {s.account_id for s in report.snapshots} == set(ids)
    assert metrics.reconciliation_checked.value() == 5
    assert metrics.reconciliation_mismatched.value() == 0
    store.close()


def test_corruption_is_caught_and_audited(tmp_path):
    store, wallet, ids = seeded_store(tmp_path, "corrupt.db")
    # Corrupt one balance behind the ledger's back (simulating the class
    # of bug/external mutation the sweep exists to catch).
    store._conn.execute("UPDATE accounts SET balance = balance + 777 WHERE id=?", (ids[0],))
    store._conn.commit()

    rec = Reconciler(store.accounts, store.ledger, audit=store.audit)
    report = rec.run_once()
    assert report.mismatched == 1
    assert report.mismatches[0]["account_id"] == ids[0]
    assert report.mismatches[0]["recorded"] - report.mismatches[0]["ledger"] == 777

    row = store._conn.execute(
        "SELECT entity_id, action FROM audit_log WHERE action='reconciliation_mismatch'"
    ).fetchone()
    assert row == (ids[0], "reconciliation_mismatch")
    store.close()


def test_background_job_runs_and_stops(tmp_path):
    store, wallet, _ = seeded_store(tmp_path, "job.db")
    rec = Reconciler(store.accounts, store.ledger)
    job = ReconciliationJob(rec, interval_s=0.01)
    job.start()
    import time
    deadline = time.time() + 60.0
    while rec.last_report is None and time.time() < deadline:
        time.sleep(0.01)
    job.stop()
    assert rec.last_report is not None
    assert rec.last_report.checked == 5
    store.close()
