"""Native (C++) feature store: build, parity with the Python store, speed."""

import time

import numpy as np
import pytest

from igaming_platform_tpu.core.features import F, NUM_FEATURES
from igaming_platform_tpu.serve.feature_store import InMemoryFeatureStore, TransactionEvent
from igaming_platform_tpu.serve.native_store import (
    NativeFeatureStore,
    best_feature_store,
    native_available,
)

pytestmark = pytest.mark.skipif(not native_available(), reason="native toolchain unavailable")

T0 = 1_700_000_000.0


def _seed(store):
    store.update(TransactionEvent("acct", 5000, "deposit", ip="1.1.1.1", device_id="d1", timestamp=T0 - 100))
    store.update(TransactionEvent("acct", 2000, "bet", ip="1.1.1.1", device_id="d2", timestamp=T0 - 50))
    store.update(TransactionEvent("acct", 1000, "win", ip="2.2.2.2", device_id="d2", timestamp=T0 - 40))


def test_native_matches_python_store():
    py = InMemoryFeatureStore()
    nat = NativeFeatureStore(max_accounts=1000)
    _seed(py)
    _seed(nat)

    row_py = np.zeros(NUM_FEATURES, dtype=np.float32)
    row_nat = np.zeros(NUM_FEATURES, dtype=np.float32)
    py.fill_row(row_py, "acct", 700, "withdraw", now=T0)
    nat.fill_row(row_nat, "acct", 700, "withdraw", now=T0)

    # HLL estimates may differ by implementation detail at tiny cardinality;
    # everything else must match exactly.
    hll_idx = {int(F.UNIQUE_DEVICES_24H), int(F.UNIQUE_IPS_24H)}
    for i in range(NUM_FEATURES):
        if i in hll_idx:
            assert abs(row_nat[i] - row_py[i]) <= 1, FEATURE_MISMATCH(i, row_nat[i], row_py[i])
        else:
            assert row_nat[i] == pytest.approx(row_py[i], rel=1e-6), (i, row_nat[i], row_py[i])


def FEATURE_MISMATCH(i, a, b):
    return f"feature {i}: native={a} python={b}"


def test_native_velocity_and_ttl():
    nat = NativeFeatureStore(max_accounts=10)
    for dt in (3500, 200, 30):
        nat.update(TransactionEvent("v", 100, "bet", timestamp=T0 - dt))
    assert nat.velocity("v", now=T0) == (1, 2, 3)

    row = np.zeros(NUM_FEATURES, dtype=np.float32)
    nat.fill_row(row, "v", 0, "bet", now=T0 + 7200)
    assert row[F.TX_COUNT_1H] == 0  # window expired
    assert row[F.TX_SUM_1H] == 0  # TTL expired
    assert row[F.TOTAL_BETS] if hasattr(F, "TOTAL_BETS") else True


def test_native_hll_accuracy():
    nat = NativeFeatureStore(max_accounts=10)
    for i in range(2000):
        nat.update(TransactionEvent("h", 1, "bet", device_id=f"dev-{i}", ip=f"ip-{i}", timestamp=T0 + i * 0.001))
    row = np.zeros(NUM_FEATURES, dtype=np.float32)
    nat.fill_row(row, "h", 0, "bet", now=T0 + 10)
    assert abs(row[F.UNIQUE_DEVICES_24H] - 2000) / 2000 < 0.10
    assert abs(row[F.UNIQUE_IPS_24H] - 2000) / 2000 < 0.10


def test_native_bonus_only_detection():
    nat = NativeFeatureStore(max_accounts=10)
    nat.update(TransactionEvent("b", 1000, "deposit", timestamp=T0))
    for _ in range(4):
        nat.record_bonus_claim("b", 0.2)
    row = np.zeros(NUM_FEATURES, dtype=np.float32)
    nat.fill_row(row, "b", 100, "bet", now=T0 + 1)
    assert row[F.BONUS_ONLY_PLAYER] == 1
    assert row[F.BONUS_CLAIM_COUNT] == 4
    assert row[F.BONUS_WAGER_RATE] == pytest.approx(0.2)


def test_native_gather_batch_with_blacklist():
    nat = NativeFeatureStore(max_accounts=10)
    nat.update(TransactionEvent("g1", 500, "deposit", timestamp=T0))
    nat.add_to_blacklist("device", "evil")

    class Req:
        def __init__(self, acct, device=""):
            self.account_id = acct
            self.amount = 100
            self.tx_type = "bet"
            self.device_id = device
            self.fingerprint = ""
            self.ip = ""

    x, bl = nat.gather_batch([Req("g1"), Req("g2", device="evil")], now=T0 + 1)
    assert x.shape == (2, NUM_FEATURES)
    assert x[0, F.TOTAL_DEPOSITS] == 500
    assert x[1, F.TOTAL_DEPOSITS] == 0  # unknown account
    assert not bl[0] and bl[1]


def test_native_engine_integration():
    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

    nat = NativeFeatureStore(max_accounts=100)
    eng = TPUScoringEngine(
        feature_store=nat, batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1)
    )
    try:
        eng.update_features(TransactionEvent("ni", 5000, "deposit", device_id="d1"))
        resp = eng.score(ScoreRequest("ni", amount=1000, tx_type="deposit"))
        assert resp.features.total_deposits == 5000
        assert resp.action in ("approve", "review", "block")
    finally:
        eng.close()


def test_native_gather_faster_than_python():
    """The C++ gather should beat the Python store on a large batch."""
    py = InMemoryFeatureStore()
    nat = NativeFeatureStore(max_accounts=5000)
    rng = np.random.default_rng(0)
    accounts = [f"a{i}" for i in range(2000)]
    for i, acct in enumerate(accounts):
        ev = TransactionEvent(acct, int(rng.integers(100, 10000)), "deposit", timestamp=T0 + i * 0.01)
        py.update(ev)
        nat.update(ev)

    class Req:
        __slots__ = ("account_id", "amount", "tx_type", "device_id", "fingerprint", "ip")

        def __init__(self, acct):
            self.account_id = acct
            self.amount = 100
            self.tx_type = "bet"
            self.device_id = ""
            self.fingerprint = ""
            self.ip = ""

    reqs = [Req(a) for a in accounts]

    t0 = time.perf_counter()
    for _ in range(3):
        py.gather_batch(reqs, now=T0 + 100)
    t_py = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(3):
        nat.gather_batch(reqs, now=T0 + 100)
    t_nat = time.perf_counter() - t0

    assert t_nat < t_py, (t_nat, t_py)


def test_best_feature_store_returns_native():
    store = best_feature_store(max_accounts=16)
    assert isinstance(store, NativeFeatureStore)


def test_native_load_batch_features_parity():
    """load_batch_features (the batch-refresh sink) behaves identically in
    the native and Python stores."""
    import numpy as np

    from igaming_platform_tpu.core.features import F, NUM_FEATURES
    from igaming_platform_tpu.serve.feature_store import InMemoryFeatureStore
    from igaming_platform_tpu.serve.native_store import NativeFeatureStore, native_available

    if not native_available():
        import pytest
        pytest.skip("native library unavailable")

    kw = dict(total_deposits=50_000, total_withdrawals=10_000,
              deposit_count=5, withdraw_count=2, total_bets=20_000,
              total_wins=8_000, bet_count=20, win_count=6,
              bonus_claim_count=3, created_at=1000.0)
    rows = []
    for store in (InMemoryFeatureStore(), NativeFeatureStore(max_accounts=16)):
        store.load_batch_features("acct", **kw)
        row = np.zeros(NUM_FEATURES, dtype=np.float32)
        store.fill_row(row, "acct", 500, "bet", now=2000.0)
        rows.append(row)
    py, nat = rows
    for f in (F.TOTAL_DEPOSITS, F.TOTAL_WITHDRAWALS, F.DEPOSIT_COUNT,
              F.WITHDRAW_COUNT, F.NET_DEPOSIT, F.AVG_BET_SIZE, F.WIN_RATE,
              F.BONUS_CLAIM_COUNT, F.ACCOUNT_AGE_DAYS):
        assert py[f] == nat[f], f"feature {f}: python={py[f]} native={nat[f]}"


def test_batch_refresh_job_works_with_native_store(tmp_path):
    import numpy as np

    from igaming_platform_tpu.core.features import F, NUM_FEATURES
    from igaming_platform_tpu.platform.repository import SQLiteStore
    from igaming_platform_tpu.platform.wallet import WalletService
    from igaming_platform_tpu.serve.batch_refresh import (
        BatchFeatureRefreshJob,
        wallet_store_source,
    )
    from igaming_platform_tpu.serve.native_store import NativeFeatureStore, native_available

    if not native_available():
        import pytest
        pytest.skip("native library unavailable")

    path = str(tmp_path / "w.db")
    store = SQLiteStore(path)
    wallet = WalletService(store.accounts, store.transactions, store.ledger)
    acct = wallet.create_account("nb-p")
    for i in range(3):
        wallet.deposit(acct.id, 7_000, f"nb-{i}")

    fs = NativeFeatureStore(max_accounts=16)
    assert BatchFeatureRefreshJob(fs, wallet_store_source(path)).refresh_once() == 1
    row = np.zeros(NUM_FEATURES, dtype=np.float32)
    fs.fill_row(row, acct.id, 0, "bet")
    assert row[F.DEPOSIT_COUNT] == 3
    assert row[F.TOTAL_DEPOSITS] == 21_000
    store.close()
