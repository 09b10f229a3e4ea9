"""Transactional-outbox delivery guarantees.

The reference declares the event_outbox table (init-db.sql:177-188) but
never writes to it — its wallet publishes directly after commit
(wallet_service.go:319-323), dropping events when the broker is down.
These tests pin the actually-wired behavior: staged-with-commit, delivered
at-least-once, broker outages delay instead of drop, order preserved.
"""

import pytest

from igaming_platform_tpu.core.enums import EXCHANGE_WALLET, QUEUE_RISK_SCORING
from igaming_platform_tpu.platform.app import AppConfig, PlatformApp
from igaming_platform_tpu.platform.outbox import InMemoryOutbox, OutboxPublisher, OutboxRelay
from igaming_platform_tpu.platform.repository import SQLiteStore
from igaming_platform_tpu.serve.events import Event, InMemoryBroker


def make_broker() -> InMemoryBroker:
    b = InMemoryBroker()
    b.declare_exchange(EXCHANGE_WALLET)
    b.declare_queue("q")
    b.bind("q", EXCHANGE_WALLET, "#")
    return b


class FlakyBroker:
    """publish_raw fails while .down is True; delivers otherwise."""

    def __init__(self, inner: InMemoryBroker):
        self.inner = inner
        self.down = False
        self.attempts = 0

    def publish_raw(self, exchange, routing_key, payload):
        self.attempts += 1
        if self.down:
            raise ConnectionError("broker unavailable")
        self.inner.publish_raw(exchange, routing_key, payload)


def ev(i: int) -> Event:
    return Event(type="transaction.completed", source="test", aggregate_id=f"a{i}",
                 data={"seq": i})


@pytest.mark.parametrize("outbox_factory", [InMemoryOutbox, SQLiteStore],
                         ids=["memory", "sqlite"])
def test_staged_until_flush_then_delivered_in_order(outbox_factory):
    broker = make_broker()
    outbox = outbox_factory()
    pub = OutboxPublisher(outbox)
    relay = OutboxRelay(outbox, broker)

    for i in range(5):
        pub.publish(EXCHANGE_WALLET, ev(i))
    # Nothing on the wire until the relay runs.
    assert broker.queue_depth("q") == 0

    assert relay.flush() == 5
    seqs = [Event.from_json(broker.get("q", timeout=0)).data["seq"] for _ in range(5)]
    assert seqs == [0, 1, 2, 3, 4]
    # Re-flush publishes nothing: all rows are marked.
    assert relay.flush() == 0
    assert broker.queue_depth("q") == 0


def test_broker_outage_delays_instead_of_drops():
    inner = make_broker()
    broker = FlakyBroker(inner)
    outbox = InMemoryOutbox()
    pub = OutboxPublisher(outbox)
    relay = OutboxRelay(outbox, broker)

    broker.down = True
    for i in range(3):
        pub.publish(EXCHANGE_WALLET, ev(i))
    assert relay.flush() == 0          # outage: nothing delivered...
    assert relay.failed_total == 1     # ...first row failed, drain stopped
    assert inner.queue_depth("q") == 0

    broker.down = False
    assert relay.flush() == 3          # recovery: ALL rows deliver, in order
    seqs = [Event.from_json(inner.get("q", timeout=0)).data["seq"] for _ in range(3)]
    assert seqs == [0, 1, 2]


def test_at_least_once_on_crash_between_publish_and_mark():
    broker = make_broker()
    outbox = InMemoryOutbox()
    OutboxPublisher(outbox).publish(EXCHANGE_WALLET, ev(0))

    # Simulate: publish succeeded, process died before mark_published.
    rows = outbox.outbox_drain()
    assert len(rows) == 1
    _, exchange, rk, payload = rows[0]
    broker.publish_raw(exchange, rk, payload)  # delivered once...

    relay = OutboxRelay(outbox, broker)        # ...restart drains again
    assert relay.flush() == 1
    # Two copies on the wire — at-least-once, never zero; consumers dedupe
    # on the envelope id, which both copies share.
    raw1, raw2 = broker.get("q", timeout=0), broker.get("q", timeout=0)
    assert Event.from_json(raw1).id == Event.from_json(raw2).id


def test_background_relay_delivers_without_manual_flush():
    broker = make_broker()
    outbox = InMemoryOutbox()
    relay = OutboxRelay(outbox, broker, poll_interval_s=0.01)
    relay.start()
    try:
        OutboxPublisher(outbox).publish(EXCHANGE_WALLET, ev(7))
        import time
        deadline = time.time() + 60.0
        while broker.queue_depth("q") == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert broker.queue_depth("q") == 1
    finally:
        relay.stop()


def test_wallet_event_survives_broker_outage_end_to_end(tmp_path):
    """Deposit completes while the broker is down; the event arrives after
    recovery and still drives the scoring bridge's feature update."""
    app = PlatformApp(AppConfig(sqlite_path=str(tmp_path / "w.db")))
    try:
        acct = app.wallet.create_account("p1")
        app.outbox_relay.flush()

        # Take the broker down: swap the relay target for a failing one.
        real_target = app.outbox_relay.target
        app.outbox_relay.target = FlakyBroker(real_target)
        app.outbox_relay.target.down = True

        res = app.deposit(acct.id, 5_000, "dep-1")           # op succeeds
        assert res.transaction.status.value == "completed"
        assert app.broker.queue_depth(QUEUE_RISK_SCORING) == 0  # event held

        app.outbox_relay.target.down = False                  # recovery
        app.pump()
        # The bridge consumed the replayed event: deposit velocity recorded.
        import numpy as np

        from igaming_platform_tpu.core.features import F, NUM_FEATURES
        row = np.zeros(NUM_FEATURES, dtype=np.float32)
        app.engine.features.fill_row(row, acct.id, 0, "bet")
        assert row[F.DEPOSIT_COUNT] >= 1
    finally:
        app.close()


def test_sqlite_outbox_survives_reopen(tmp_path):
    """A staged-but-undelivered event survives process restart: reopening
    the store and draining delivers it (the durability the reference's
    direct-publish path lacks)."""
    path = str(tmp_path / "outbox.db")
    store = SQLiteStore(path)
    OutboxPublisher(store).publish(EXCHANGE_WALLET, ev(42))
    store.close()  # crash before any relay ran

    store2 = SQLiteStore(path)
    broker = make_broker()
    assert OutboxRelay(store2, broker).flush() == 1
    assert Event.from_json(broker.get("q", timeout=0)).data["seq"] == 42
    store2.close()


def test_sqlite_completion_and_event_commit_atomically(tmp_path):
    """SQLite wallets stage the completion event via update_with_event (one
    commit with the status update), never via a separate outbox_add."""
    store = SQLiteStore(str(tmp_path / "a.db"))
    from igaming_platform_tpu.platform.wallet import WalletService

    wallet = WalletService(
        store.accounts, store.transactions, store.ledger,
        events=OutboxPublisher(store),
    )
    acct = wallet.create_account("p-atomic")

    def boom(*a, **k):  # separate-commit staging would take this path
        raise AssertionError("outbox_add must not be used for completion events")

    store.outbox_add = boom
    res = wallet.deposit(acct.id, 2_500, "dep-atomic")
    assert res.transaction.status.value == "completed"
    # The event is staged all the same — in the same commit as the update.
    payloads = [Event.from_json(p) for _, _, _, p in store.outbox_drain()]
    assert any(e.data.get("transaction_id") == res.transaction.id for e in payloads)
    store.close()


def test_purge_reclaims_published_rows_only(tmp_path):
    store = SQLiteStore(str(tmp_path / "p.db"))
    pub = OutboxPublisher(store)
    pub.publish(EXCHANGE_WALLET, ev(1))
    pub.publish(EXCHANGE_WALLET, ev(2))
    rows = store.outbox_drain()
    store.outbox_mark_published(rows[0][0])

    assert store.outbox_purge_published(older_than_s=0.0) == 1
    remaining = store.outbox_drain()
    assert len(remaining) == 1  # the unpublished row survives
    assert Event.from_json(remaining[0][3]).data["seq"] == 2
    store.close()


def test_relay_survives_store_errors():
    """A store hiccup during drain must not kill the relay (or raise out of
    flush) — the rows deliver on the next attempt."""
    broker = make_broker()

    class FlakyOutbox(InMemoryOutbox):
        fail_next_drain = False

        def outbox_drain(self):
            if self.fail_next_drain:
                self.fail_next_drain = False
                raise RuntimeError("database is locked")
            return super().outbox_drain()

    outbox = FlakyOutbox()
    OutboxPublisher(outbox).publish(EXCHANGE_WALLET, ev(9))
    relay = OutboxRelay(outbox, broker)
    outbox.fail_next_drain = True
    assert relay.flush() == 0
    assert relay.failed_total == 1
    assert relay.flush() == 1
    assert relay.published_total == 1


def test_published_total_counts_partial_drains():
    inner = make_broker()
    broker = FlakyBroker(inner)
    outbox = InMemoryOutbox()
    pub = OutboxPublisher(outbox)
    for i in range(3):
        pub.publish(EXCHANGE_WALLET, ev(i))
    relay = OutboxRelay(outbox, broker)

    orig = broker.inner.publish_raw
    calls = {"n": 0}

    def fail_third(exchange, rk, payload):
        calls["n"] += 1
        if calls["n"] == 3:
            raise ConnectionError("mid-drain outage")
        orig(exchange, rk, payload)

    broker.inner.publish_raw = fail_third
    assert relay.flush() == 2
    assert relay.published_total == 2  # partial drains still counted
    broker.inner.publish_raw = orig
    assert relay.flush() == 1
    assert relay.published_total == 3


def test_bridge_dedupes_at_least_once_redelivery():
    """The scoring bridge must not double-count features when the outbox
    relay re-delivers an event (crash between publish and mark)."""
    import numpy as np

    from igaming_platform_tpu.core.config import BatcherConfig
    from igaming_platform_tpu.core.enums import QUEUE_RISK_SCORING
    from igaming_platform_tpu.core.features import F, NUM_FEATURES
    from igaming_platform_tpu.serve.bridge import ScoringBridge
    from igaming_platform_tpu.serve.events import default_broker, new_transaction_event
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    broker = default_broker()
    engine = TPUScoringEngine(batcher_config=BatcherConfig(batch_size=8, max_wait_ms=1.0))
    bridge = ScoringBridge(engine, broker, publish_risk_events=False)
    try:
        event = new_transaction_event("transaction.completed", {
            "id": "tx-1", "account_id": "dup-acct", "type": "deposit",
            "amount": 5_000, "status": "completed",
        })
        raw = event.to_json()
        # At-least-once: the same serialized event arrives twice.
        broker.publish_raw(EXCHANGE_WALLET, event.type, raw)
        broker.publish_raw(EXCHANGE_WALLET, event.type, raw)
        bridge.drain()

        assert bridge.events_processed == 1
        assert bridge.events_deduped == 1
        row = np.zeros(NUM_FEATURES, dtype=np.float32)
        engine.features.fill_row(row, "dup-acct", 0, "bet")
        assert row[F.DEPOSIT_COUNT] == 1          # counted once
        assert row[F.TX_COUNT_1H] == 1
        assert broker.queue_depth(QUEUE_RISK_SCORING) == 0
    finally:
        engine.close()
