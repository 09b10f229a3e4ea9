"""Protocol-level behavior of the SQLite-backed PG server
(platform/pg_testing.py), driven through the real wire client."""

import threading

import pytest

from igaming_platform_tpu.platform.pg_testing import PgSqliteServer
from igaming_platform_tpu.platform.pgwire import UNIQUE_VIOLATION, PgConnection, PgError


@pytest.fixture()
def server(tmp_path):
    s = PgSqliteServer(str(tmp_path / "proto.db"))
    yield s
    s.close()


def _connect(server):
    conn = PgConnection(server.url)
    conn.connect()
    return conn


def test_unique_violation_sqlstate_and_param_fidelity(server):
    conn = _connect(server)
    conn.execute("CREATE TABLE t (k TEXT PRIMARY KEY, v BIGINT, f DOUBLE PRECISION)")
    conn.execute("INSERT INTO t VALUES (?, ?, ?)", ("007", 42, 1.5))
    with pytest.raises(PgError) as exc_info:
        conn.execute("INSERT INTO t VALUES (?, ?, ?)", ("007", 1, 1.0))
    assert exc_info.value.sqlstate == UNIQUE_VIOLATION
    # Numeric-looking strings must round-trip VERBATIM (leading zeros
    # kept), while numeric columns come back as numbers via OID coercion.
    row = conn.execute("SELECT k, v, f FROM t").fetchone()
    assert row == ("007", 42, 1.5)
    conn.close()


def test_aborted_transaction_until_rollback(server):
    conn = _connect(server)
    conn.execute("CREATE TABLE a (x BIGINT PRIMARY KEY)")
    conn.execute("INSERT INTO a VALUES (?)", (1,))
    conn.begin()
    with pytest.raises(PgError):
        conn.execute("INSERT INTO a VALUES (?)", (1,))  # unique violation
    # PG semantics: the transaction is aborted — further statements fail
    # with 25P02 until ROLLBACK.
    with pytest.raises(PgError) as exc_info:
        conn.execute("SELECT COUNT(*) FROM a")
    assert exc_info.value.sqlstate == "25P02"
    conn.rollback()
    assert conn.execute("SELECT COUNT(*) FROM a").fetchone()[0] == 1
    conn.close()


def test_rollback_discards_transaction_writes(server):
    conn = _connect(server)
    conn.execute("CREATE TABLE b (x BIGINT)")
    conn.begin()
    conn.execute("INSERT INTO b VALUES (?)", (7,))
    conn.rollback()
    assert conn.execute("SELECT COUNT(*) FROM b").fetchone()[0] == 0
    conn.begin()
    conn.execute("INSERT INTO b VALUES (?)", (8,))
    conn.commit()
    assert conn.execute("SELECT x FROM b").fetchone()[0] == 8
    conn.close()


def test_write_transactions_serialize_across_connections(server):
    """BEGIN IMMEDIATE: a second writer blocks until the first commits
    (the arbitration the multi-replica tests rely on)."""
    c1, c2 = _connect(server), _connect(server)
    c1.execute("CREATE TABLE w (x BIGINT)")
    c1.begin()
    c1.execute("INSERT INTO w VALUES (?)", (1,))
    order: list[str] = []

    def second_writer():
        c2.begin()  # blocks on c1's write lock
        c2.execute("INSERT INTO w VALUES (?)", (2,))
        c2.commit()
        order.append("c2-committed")

    t = threading.Thread(target=second_writer)
    t.start()
    t.join(timeout=0.5)
    assert t.is_alive(), "second writer should be blocked behind c1"
    order.append("c1-committing")
    c1.commit()
    t.join(timeout=30)
    assert not t.is_alive()
    assert order == ["c1-committing", "c2-committed"]
    assert c1.execute("SELECT COUNT(*) FROM w").fetchone()[0] == 2
    c1.close()
    c2.close()


def test_advisory_lock_blocks_second_session(server):
    c1, c2 = _connect(server), _connect(server)
    c1.execute("SELECT pg_advisory_lock(99)")
    acquired: list[str] = []

    def second():
        c2.execute("SELECT pg_advisory_lock(99)")
        acquired.append("c2")

    t = threading.Thread(target=second)
    t.start()
    t.join(timeout=0.5)
    assert t.is_alive(), "advisory lock must block the second session"
    c1.execute("SELECT pg_advisory_unlock(99)")
    t.join(timeout=30)
    assert acquired == ["c2"]
    c1.close()
    c2.close()


def test_disconnect_releases_advisory_locks(server):
    c1 = _connect(server)
    c1.execute("SELECT pg_advisory_lock(123)")
    c1.close()  # session death releases its locks, like PG

    c2 = _connect(server)
    done: list[str] = []

    def grab():
        c2.execute("SELECT pg_advisory_lock(123)")
        done.append("ok")

    t = threading.Thread(target=grab)
    t.start()
    t.join(timeout=30)
    assert done == ["ok"]
    c2.close()


def test_rig_survives_adversarial_bytes(server):
    """Garbage/truncated/mutated startup and message bytes must neither
    crash the server nor poison a well-behaved connection that follows
    (the adversarial-bytes discipline of the native decoder fuzz)."""
    import socket
    import struct

    rng = __import__("numpy").random.default_rng(0)

    def blast(payload: bytes, hang_up: bool = False) -> None:
        s = socket.socket()
        s.settimeout(2.0)
        try:
            s.connect(("127.0.0.1", server.port))
            s.sendall(payload)
            if hang_up:
                # A client that sends its bytes and closes its side: the
                # server answers or drops it at once, where a silent
                # client costs the full 2 s wait (60 mutants of it were
                # 30 s of this test asleep).
                s.shutdown(socket.SHUT_WR)
            try:
                s.recv(4096)
            except OSError:
                pass
        finally:
            s.close()

    # Plain garbage, truncated startup, absurd lengths, random mutants.
    blast(b"GET / HTTP/1.1\r\n\r\n")
    blast(b"\x00\x00")
    blast(struct.pack(">I", 2**31 - 1))
    valid_startup = struct.pack(">II", 8, 196608)
    for _ in range(60):
        mutant = bytearray(valid_startup + b"user\x00tester\x00\x00")
        for _ in range(int(rng.integers(1, 4))):
            mutant[int(rng.integers(0, len(mutant)))] = int(rng.integers(0, 256))
        blast(bytes(mutant), hang_up=True)

    # After all of that, a real client must still work end-to-end.
    conn = _connect(server)
    conn.execute("CREATE TABLE IF NOT EXISTS fz (x BIGINT)")
    conn.execute("INSERT INTO fz VALUES (?)", (1,))
    assert conn.execute("SELECT COUNT(*) FROM fz").fetchone()[0] == 1
    conn.close()


def test_read_only_session_rejects_writes(server):
    """SET default_transaction_read_only=on is ENFORCED by the rig (mapped
    to SQLite query_only), so the scan jobs' write guard is exercised in
    CI, not only against live Postgres (advisor round-4 item)."""
    setup = _connect(server)
    setup.execute("CREATE TABLE ro (x BIGINT)")
    setup.execute("INSERT INTO ro VALUES (?)", (1,))

    conn = _connect(server)
    conn.execute("SET default_transaction_read_only = on")
    assert conn.execute("SELECT COUNT(*) FROM ro").fetchone()[0] == 1  # reads fine
    with pytest.raises(PgError):
        conn.execute("INSERT INTO ro VALUES (?)", (2,))
    # RESET restores writability for the same session.
    conn.execute("RESET default_transaction_read_only")
    conn.execute("INSERT INTO ro VALUES (?)", (3,))
    assert setup.execute("SELECT COUNT(*) FROM ro").fetchone()[0] == 2
    conn.close()
    setup.close()


def test_wallet_reader_cannot_write_through_rig(server, tmp_path):
    """open_wallet_reader on a postgres:// URL yields a handle that is
    incapable of writing — end-to-end through the rig's enforcement."""
    from igaming_platform_tpu.platform.repository import open_wallet_reader

    setup = _connect(server)
    setup.execute("CREATE TABLE w (x BIGINT)")

    query, close = open_wallet_reader(server.url)
    with pytest.raises(PgError):
        query("INSERT INTO w VALUES (9)")
    assert query("SELECT COUNT(*) FROM w")[0][0] == 0
    close()
    setup.close()
