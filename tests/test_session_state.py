"""Stateful sequence scoring (serve/session_state.py, ISSUE 12).

Covers the session plane's contracts end to end on the CPU control rig:

- ring append / wrap / eviction parity against the host numpy twin;
- sequence-head bit-exactness of the FUSED step vs a host reference at
  every ladder shape (window gather + head + ensemble fold recombine);
- shared-CLOCK eviction coherence between the feature table and the
  session ring (one admission decision, two tables, rehydration);
- bit-exact replay of stateful decisions (session_state_hash verified)
  across eviction churn, a SIGKILL-shaped restart and a promotion
  boundary;
- the seeded coordinated fraud-ring scenario: caught by the sequence
  path, provably missed by the aggregate-only baseline;
- SESSION_COLD honesty: cold rows are flagged and counted, bypass rows
  are counted, and the fused path adds zero device dispatches per chunk.
"""

from __future__ import annotations

import ast
import hashlib
import pathlib
import queue
import sys
import tempfile
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
from igaming_platform_tpu.core.enums import (
    REASON_BIT_ORDER,
    ReasonCode,
    SESSION_COLD_BIT,
    SESSION_PATTERN_BIT,
    decode_reason_mask,
)
from igaming_platform_tpu.models import session_heads
from igaming_platform_tpu.serve import index_program
from igaming_platform_tpu.serve import ledger as ledger_mod
from igaming_platform_tpu.serve import session_state as session_mod
from igaming_platform_tpu.serve.feature_store import TransactionEvent
from igaming_platform_tpu.serve.scorer import TPUScoringEngine
from igaming_platform_tpu.serve.wire import TX_TYPE_CODES
from igaming_platform_tpu.train.fraudgen import FraudRing

NOW0 = 1_700_000_000.0
_N = 16  # SESSION_EVENTS default; the growth cases are named in its terms


def make_engine(batch_size=16, capacity=8, session=True, tiers=(8,),
                ledger_dir=None, **kw):
    eng = TPUScoringEngine(
        ScoringConfig(), ml_backend="mock",
        batcher_config=BatcherConfig(batch_size=batch_size,
                                     latency_tiers=tiers,
                                     max_wait_ms=1.0),
        feature_cache=capacity, session_state=session, **kw)
    if ledger_dir is not None:
        eng.ledger = ledger_mod.DecisionLedger(ledger_dir)
    eng.ensure_cache()
    return eng


def close_engine(eng):
    if eng.ledger is not None:
        eng.ledger.close()
    eng.close()


def ring_rows(eng, account_id):
    """Device-resident window for one account (chronological), read back."""
    slot = eng.cache._slots[account_id]
    n = eng.session.n_events
    rows = np.asarray(session_mod.ring_rows(
        eng.session.session_ring, np.asarray([slot], np.int32), n))[0]
    cur = int(jax.device_get(eng.session.session_cursor)[slot])
    ln = int(jax.device_get(eng.session.session_length)[slot])
    pos = [(cur - ln + k) % n for k in range(ln)]
    return rows[pos]


# ---------------------------------------------------------------------------
# Event codec


def test_event_codec_deterministic_and_hash_stable():
    ev1 = session_mod.encode_events_host([900, 0, 2**25 + 1], [2, 0, 4],
                                         [45.0, 0.0, 1.5])
    ev2 = session_mod.encode_events_host([900, 0, 2**25 + 1], [2, 0, 4],
                                         [45.0, 0.0, 1.5])
    assert ev1.dtype == np.float32 and ev1.shape == (3, session_mod.EVENT_WIDTH)
    assert np.array_equal(ev1, ev2)
    # bet -> one-hot column 2+2, deposit -> 2+0, other -> 2+7.
    assert ev1[0, 4] == 1.0 and ev1[1, 2] == 1.0 and ev1[2, 9] == 1.0
    h1 = session_mod.window_hash(ev1)
    assert h1 == session_mod.window_hash(ev1.copy()) and len(h1) == 8
    assert h1 != session_mod.window_hash(ev1[:2])


# ---------------------------------------------------------------------------
# Ring parity vs the numpy twin (append, wrap, eviction)


# Rounds of one event an account: 1-5 stay in the first buffers of the
# host twin (2, 4, 8 rows), N + 5 is the device ring's wrap, 2N + 1 and
# 4N + 2 cross the twin's last growth step and its first compaction.
@pytest.mark.parametrize("rounds", [1, 2, 3, 5, _N + 5, 2 * _N + 1, 4 * _N + 2])
def test_ring_append_wrap_parity_vs_twin(rounds):
    eng = make_engine(capacity=4)
    n_events = eng.session.n_events
    assert n_events == _N
    accts = [f"tw{i}" for i in range(3)]
    for r in range(rounds):
        # tw0 twice in one chunk on odd rounds: it crosses every growth
        # step of its buffer a round or two before the others, some of
        # them with a two-row append
        ids = accts + accts[:r % 2]
        eng.score_columns_cached(
            ids, [500 + 13 * r + i for i in range(len(ids))],
            [("bet", "deposit", "withdraw")[(r + i) % 3]
             for i in range(len(ids))],
            now=NOW0 + 30.0 * r)
        for a in accts:
            assert np.array_equal(ring_rows(eng, a),
                                  eng.session.twin_window(a)), (a, r)
    for a in accts:
        seq = rounds + (rounds // 2 if a == "tw0" else 0)
        assert eng.session.twin_window(a).shape[0] == min(seq, n_events)
        assert eng.session.twin_meta(a)["seq"] == seq
    close_engine(eng)


def test_duplicate_accounts_in_one_chunk_batch_snapshot():
    eng = make_engine(capacity=8)
    # One chunk with the same account three times: appends land at
    # distinct cursor offsets; windows all see the chunk-start state.
    eng.score_columns_cached(["dup", "dup", "dup"], [100, 200, 300],
                             ["bet", "deposit", "bet"], now=NOW0)
    twin = eng.session.twin_window("dup")
    assert twin.shape[0] == 3
    assert np.array_equal(ring_rows(eng, "dup"), twin)
    meta = eng.session.twin_meta("dup")
    assert meta["seq"] == 3
    close_engine(eng)


# ---------------------------------------------------------------------------
# The host twin grows with the events it holds (ISSUE 31): stable views,
# the same windows and hashes, bytes in proportion


_GROWTH_COUNTS = {"1": 1, "2": 2, "3": 3, "5": 5, "N": _N, "N+1": _N + 1,
                  "4N": 4 * _N, "4N+1": 4 * _N + 1, "5N": 5 * _N}


class _TwinModel:
    """Plain numpy model of one account's session chain, rebuilt from the
    order sent: batch-snapshot windows, blake2b-8 over history + event."""

    def __init__(self, n_events):
        self.n = n_events
        self.events = []
        self.last_ts = 0.0

    def chunk(self, amounts, codes, now):
        """Hashes of this chunk's rows for the account, then commits."""
        seq0 = len(self.events)
        dt = max(0.0, now - self.last_ts) if seq0 else 0.0
        rows = session_mod.encode_events_host(
            amounts, codes, [dt] * len(amounts))
        hist = self.events[seq0 - min(seq0, self.n - 1):]
        out = [hashlib.blake2b(
            b"".join(e.tobytes() for e in hist) + row.tobytes(),
            digest_size=8).digest() for row in rows]
        self.events.extend(rows)
        self.last_ts = now
        return out

    def window(self):
        if not self.events:
            return np.zeros((0, session_mod.EVENT_WIDTH), np.float32)
        return np.stack(self.events[-self.n:])


def _twin_walk_bytes(mgr):
    return sum(tw.buf.nbytes for tw in mgr._twin.values())


@pytest.mark.parametrize("how", ["singly", "one_chunk"])
@pytest.mark.parametrize("count", list(_GROWTH_COUNTS))
def test_twin_growth_keeps_views_hashes_and_windows(count, how):
    """``count`` events to one account, one a chunk or all as duplicates
    inside one chunk (between two other accounts), then three chunks
    more: every audit hash read after all later appends equals the hash
    read at once and the numpy model's, across growth and compaction."""
    mgr = session_mod.SessionStateManager(4)
    n = mgr.n_events
    assert n == _N
    total = _GROWTH_COUNTS[count]
    model = _TwinModel(n)
    sizes = [1] * total if how == "singly" else [total]
    sizes += [2, 1, 3]  # the later appends
    held = []  # (audit, rows of the account, hashes read at once, expected)
    sent = 0
    for c, k in enumerate(sizes):
        now = NOW0 + 7.0 * c
        amounts = [100 + 3 * (sent + j) for j in range(k)]
        codes = [(sent + j) % 5 for j in range(k)]
        sent += k
        ids = ["g"] * k
        if how == "one_chunk":
            ids = [f"pre{c}"] + ids + [f"post{c}"]
            amounts = [11] + amounts + [13]
            codes = [0] + codes + [1]
        events, occ, post_len, seqs, audit = mgr.prepare_chunk(
            session_mod.group_chunk(ids), amounts, codes, now)
        rows = [i for i, a in enumerate(ids) if a == "g"]
        want = model.chunk([amounts[i] for i in rows],
                           [codes[i] for i in rows], now)
        at_once = [audit[i] for i in range(len(audit))]
        assert [at_once[i] for i in rows] == want
        assert list(seqs[rows]) == list(range(sent - k + 1, sent + 1))
        held.append((audit, rows, at_once, want))
        assert np.array_equal(mgr.twin_window("g"), model.window())
        tw = mgr._twin["g"]
        # steady size, or one oversized chunk as before this PR
        assert tw.buf.shape[0] <= max(4 * n, max(sizes[:c + 1]) + n)
        assert tw.buf.shape[0] <= 2 * max(tw.seq, 2)
        # its own rows (of its own array, or of the block a chunk's first
        # events share), never a view of a chunk's ``events``
        assert not np.shares_memory(tw.buf, events)
    for audit, rows, at_once, want in held:
        later = [audit[i] for i in range(len(audit))]
        assert later == at_once
        assert [later[i] for i in rows] == want
    snap = mgr.snapshot()
    assert snap["twin_bytes"] == _twin_walk_bytes(mgr)
    assert snap["appends"] == sum(len(h[2]) for h in held)


@pytest.mark.parametrize("before", [1, 3, 5, _N + 1, 2 * _N + 1])
def test_grown_twin_rehydrates_the_same_window(before):
    """An account whose host buffer has grown (3 events: 2 -> 4 rows; the
    other counts cross the later steps) is evicted and re-admitted: the
    ring is rehydrated with the window the numpy model holds."""
    eng = make_engine(capacity=4)
    model = _TwinModel(eng.session.n_events)
    for r in range(before):
        now = NOW0 + 11.0 * r
        eng.score_columns_cached(["grown"], [700 + r], ["bet"], now=now)
        model.chunk([700 + r], [TX_TYPE_CODES["bet"]], now)
    assert np.array_equal(ring_rows(eng, "grown"), model.window())
    others = [f"ot{i}" for i in range(8)]
    for lo in (0, 4):  # 2x capacity of other accounts: CLOCK evicts
        eng.score_columns_cached(others[lo:lo + 4], [50] * 4, ["deposit"] * 4,
                                 now=NOW0 + 900.0 + lo)
    assert "grown" not in eng.cache._slots
    assert np.array_equal(eng.session.twin_window("grown"), model.window())
    rehydrations = eng.session.rehydrations
    eng.score_columns_cached(["grown"], [999], ["withdraw"], now=NOW0 + 2000.0)
    model.chunk([999], [TX_TYPE_CODES["withdraw"]], NOW0 + 2000.0)
    assert eng.session.rehydrations == rehydrations + 1
    assert np.array_equal(ring_rows(eng, "grown"), model.window())
    assert np.array_equal(eng.session.twin_window("grown"), model.window())
    close_engine(eng)


def test_audit_hashes_read_on_another_thread_while_appends_go_on():
    """The ledger writer's side of the contract: a second thread hashes
    every chunk's snapshots, without the session lock, while the first
    keeps appending to the same accounts through every growth step and
    two compactions; each hash equals the numpy model's."""
    mgr = session_mod.SessionStateManager(4)
    accounts = [f"c{i}" for i in range(6)]
    models = {a: _TwinModel(mgr.n_events) for a in accounts}
    handoff: queue.Queue = queue.Queue()
    wrong = []

    def reader():
        while True:
            item = handoff.get()
            if item is None:
                return
            audit, want = item
            got = [audit[i] for i in range(len(audit))]
            if got != want:
                wrong.append((got, want))

    t = threading.Thread(target=reader, name="audit-reader")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t.start()
        for r in range(8 * _N):
            now = NOW0 + 3.0 * r
            ids = accounts + accounts[:1 + r % 3]  # duplicates in the chunk
            amounts = [200 + 5 * r + i for i in range(len(ids))]
            codes = [(r + i) % 5 for i in range(len(ids))]
            with mgr.lock:
                audit = mgr.prepare_chunk(
                    session_mod.group_chunk(ids), amounts, codes, now)[4]
            want = [None] * len(ids)
            for a in accounts:
                rows = [i for i, x in enumerate(ids) if x == a]
                for i, h in zip(rows, models[a].chunk(
                        [amounts[i] for i in rows],
                        [codes[i] for i in rows], now)):
                    want[i] = h
            handoff.put((audit, want))
    finally:
        handoff.put(None)
        t.join(60.0)
        sys.setswitchinterval(interval)
    assert not t.is_alive()
    assert not wrong, wrong[:1]
    assert mgr.snapshot()["twin_bytes"] == _twin_walk_bytes(mgr)


def test_twin_bytes_follow_the_events_held():
    """A count, not a timing: one event to each of 10,000 never-seen
    accounts holds at most 300 B an account and regrows nothing; a
    second event to each regrows at most once an account."""
    mgr = session_mod.SessionStateManager(4)
    accounts = [f"n{i}" for i in range(10_000)]
    for event in range(2):
        for lo in range(0, len(accounts), 256):
            ids = accounts[lo:lo + 256]
            mgr.prepare_chunk(session_mod.group_chunk(ids),
                              [500 + event] * len(ids), [2] * len(ids),
                              NOW0 + 60.0 * event)
        snap = mgr.snapshot()
        assert snap["accounts_tracked"] == 10_000
        assert snap["appends"] == 10_000 * (event + 1)
        assert snap["twin_bytes"] == _twin_walk_bytes(mgr)
        assert snap["twin_bytes"] <= 300 * 10_000
        assert snap["twin_regrows"] <= 10_000 * event
    # and an account that fills its window ends at today's steady size
    for r in range(5 * _N):
        mgr.prepare_chunk(session_mod.group_chunk(["n0"]), [900 + r], [0],
                          NOW0 + 500.0 + r)
    steady = 4 * _N * session_mod.EVENT_WIDTH * 4
    assert mgr._twin["n0"].buf.nbytes == steady
    assert mgr.snapshot()["twin_bytes"] == _twin_walk_bytes(mgr)


# ---------------------------------------------------------------------------
# The columnar commit against the per-account commit it replaced (PR 33)


def _reference_prepare_chunk(mgr, account_ids, amounts, tx_codes, now):
    """The per-account commit ``prepare_chunk`` had until PR 33, kept as
    the plain reference: a scan of the chunk row by row, then one
    ``append_rows`` per unique account with a buffer of its own."""
    b = len(account_ids)
    n_ev = mgr.n_events
    twin = mgr._twin
    uniq = {}
    uidx = np.empty((b,), np.int64)
    utw, snaps, useq, ulast = [], [], [], []
    for i, raw in enumerate(account_ids):
        a = raw if isinstance(raw, str) else bytes(raw).decode()
        u = uniq.get(a)
        if u is None:
            u = len(uniq)
            uniq[a] = u
            tw = twin.get(a)
            if tw is None:
                tw = session_mod._AcctSession()
                twin[a] = tw
            utw.append(tw)
            snaps.append((tw.buf, tw.count))
            useq.append(tw.seq)
            ulast.append(tw.last_ts)
        uidx[i] = u
    seq0 = np.asarray(useq, np.int64)[uidx]
    last0 = np.asarray(ulast, np.float64)[uidx]
    occ = session_mod.occurrence_rank_host(uidx)
    seqs = seq0 + occ + 1
    post_len = (np.minimum(seq0, n_ev - 1) + 1).astype(np.int32)
    dts = np.where(seq0 > 0, np.maximum(0.0, now - last0), 0.0)
    events = session_mod.encode_events_host(amounts, tx_codes, dts)
    audit = session_mod.SessionChunkAudit(events, post_len, uidx, snaps)
    order = np.argsort(uidx, kind="stable")
    sorted_u = uidx[order]
    starts = np.flatnonzero(np.concatenate(
        ([True], sorted_u[1:] != sorted_u[:-1])))
    bounds = np.append(starts, b)
    grown = regrows = 0
    for r in range(len(starts)):
        tw = utw[int(sorted_u[bounds[r]])]
        old = tw.append_rows(events[order[bounds[r]:bounds[r + 1]]], n_ev, now)
        if old >= 0:
            grown += tw.buf.shape[0] - old
            regrows += old > 0
    mgr.twin_bytes += grown * session_mod.EVENT_WIDTH * 4
    mgr.twin_regrows += regrows
    warm = int(np.count_nonzero(post_len >= mgr.min_events))
    mgr.appends += b
    mgr.warm_rows += warm
    mgr.cold_rows += b - warm
    return events, occ, post_len, seqs, audit


def _chunks_first_unique(rng):
    lo = 0
    for b in (64, 256, 1, 64):
        yield [f"u{lo + i}" for i in range(b)]
        lo += b


def _chunks_repeats(rng):
    # repeats of never-seen accounts (2, 3 and 5 rows of one in its first
    # chunk) and of seen ones, between distinct accounts
    for c in range(6):
        ids = [f"r{c}-{i}" for i in range(20)]
        ids += ["two", "two", "three", "three", "three"] + ["five"] * 5
        ids += [f"late{c}"] * (1 + c % 3)
        yield [ids[j] for j in rng.permutation(len(ids))]


def _chunks_seen_room(rng):
    accounts = [f"s{i}" for i in range(96)]
    yield accounts                       # first events: 2-row blocks
    yield accounts[::-1]                 # second events: room
    for _ in range(3):                   # a seeded mix, some repeated
        yield [accounts[j] for j in rng.integers(0, 96, 64)]


def _chunks_regrow_at(cap):
    def make(rng):
        accounts = [f"g{i}" for i in range(5)]
        # fill every buffer exactly to ``cap`` rows, one event a chunk
        # (capacities double from 2, so ``cap`` events do it) ...
        for _ in range(cap):
            yield accounts
        # ... then one append that has to regrow (or, at the steady 64
        # rows, compact): singly, and as repeats inside one chunk
        yield accounts[:3] + [accounts[3]] * 2 + [accounts[4]] * 3
        yield accounts
    return make


def _chunks_compaction(rng):
    accounts = [f"k{i}" for i in range(4)]
    for c in range(4 * _N * 2 + 9):      # twice through the steady size
        yield accounts[:1 + c % 4] + (["k0"] if c % 5 == 0 else [])


def _chunks_oversized(rng):
    # more rows of one account in a chunk than a window holds, and more
    # than the steady buffer: never-seen and seen
    yield ["big"] * (_N + 4) + ["x0"]
    yield ["x1"] + ["big"] * (_N + 4)
    yield ["huge"] * (4 * _N + 6)
    yield ["x2", "huge", "big"] + ["huge"] * (4 * _N + 6)
    yield ["big", "huge", "x0", "x1", "x2"]


def _chunks_bytes_and_str(rng):
    names = [f"b{i}" for i in range(40)]
    yield [n.encode() for n in names]
    yield names[::-1]
    yield [n.encode() if j % 2 else n
           for j, n in enumerate(names + names[:7])]
    yield [memoryview(n.encode()) for n in names[5:25]]


_COMMIT_CASES = {
    "first_unique": _chunks_first_unique,
    "repeats": _chunks_repeats,
    "seen_room": _chunks_seen_room,
    **{f"regrow_at_{cap}": _chunks_regrow_at(cap)
       for cap in (2, 4, 8, 16, 32, 64)},
    "compaction": _chunks_compaction,
    "oversized": _chunks_oversized,
    "bytes_and_str": _chunks_bytes_and_str,
}


def _as_key(a):
    return a if isinstance(a, str) else bytes(a).decode()


@pytest.mark.parametrize("seed", [7, 2_271_560_481])
@pytest.mark.parametrize("case", list(_COMMIT_CASES))
def test_prepare_chunk_equals_the_per_account_commit(case, seed):
    """The grouped, columnar ``prepare_chunk`` against the per-account
    commit it replaced, chunk by chunk over seeded traffic: the returned
    columns, every audit hash, every account's window and meta, and the
    manager's counters are equal."""
    mgr = session_mod.SessionStateManager(4)
    ref = session_mod.SessionStateManager(4)
    rng = np.random.default_rng(seed)
    chunks = list(_COMMIT_CASES[case](np.random.default_rng(seed)))
    held = []
    touched = set()
    for c, ids in enumerate(chunks):
        b = len(ids)
        amounts = rng.integers(1, 90_000, b)
        codes = rng.integers(0, 5, b).astype(np.uint8)
        now = NOW0 + 13.0 * c + float(rng.random())
        got = mgr.prepare_chunk(session_mod.group_chunk(ids), amounts, codes,
                                now)
        want = _reference_prepare_chunk(ref, ids, amounts, codes, now)
        for g, w, name in zip(got, want, ("events", "occ", "post_len", "seqs")):
            assert g.dtype == w.dtype and np.array_equal(g, w), (name, c)
        hashes = [got[4][i] for i in range(b)]
        assert hashes == [want[4][i] for i in range(b)], c
        assert np.array_equal(got[4].uidx, want[4].uidx)
        held.append((got[4], hashes))
        touched.update(_as_key(a) for a in ids)
        for a in {_as_key(a) for a in ids}:
            assert np.array_equal(mgr.twin_window(a), ref.twin_window(a)), a
            assert mgr.twin_meta(a) == ref.twin_meta(a), a
            assert len(mgr._twin[a].buf) == len(ref._twin[a].buf), a
            assert not np.shares_memory(mgr._twin[a].buf, got[0])
        for key in ("appends", "twin_bytes", "twin_regrows", "rows",
                    "accounts_tracked"):
            assert mgr.snapshot()[key] == ref.snapshot()[key], (key, c)
    assert mgr.snapshot()["twin_bytes"] == _twin_walk_bytes(mgr)
    assert set(mgr._twin) == touched
    # every audit still hashes to what it hashed when its chunk was taken
    for audit, hashes in held:
        assert [audit[i] for i in range(len(audit))] == hashes


def test_group_chunk_reads_ids_alone():
    """The grouping is a function of the chunk's ids: first-appearance
    order, each row's account and occurrence rank, the rows of each
    account in chunk order - with or without repeats, ``bytes`` or
    ``str``."""
    ids = ["a", b"b", "a", "c", memoryview(b"b"), "a", "d"]
    g = session_mod.group_chunk(ids)
    assert g.ids == ["a", "b", "c", "d"]
    assert g.uidx.tolist() == [0, 1, 0, 2, 1, 0, 3]
    assert g.occ.dtype == np.int32 and g.occ.tolist() == [0, 0, 1, 0, 1, 2, 0]
    assert np.array_equal(g.occ, session_mod.occurrence_rank_host(g.uidx))
    assert list(g.counts) == [3, 2, 1, 1] and list(g.first) == [0, 1, 3, 6]
    assert [g.rows_of(u).tolist() for u in range(4)] == [
        [0, 2, 5], [1, 4], [3], [6]]
    u = session_mod.group_chunk([b"x", "y", "z"])
    assert u.ids == ["x", "y", "z"] and u.uidx.tolist() == [0, 1, 2]
    assert u.occ.dtype == np.int32 and not u.occ.any()
    assert list(u.counts) == [1, 1, 1] and list(u.first) == [0, 1, 2]
    assert [u.rows_of(i).tolist() for i in range(3)] == [[0], [1], [2]]
    e = session_mod.group_chunk([])
    assert e.ids == [] and e.uidx.shape == (0,) and e.occ.shape == (0,)


@pytest.mark.parametrize("taken_at", [0, 1, 7, 33])
def test_audit_of_a_shared_first_block_is_stable_under_later_appends(taken_at):
    """An audit taken at chunk ``taken_at`` - first events that share one
    block when it is 0 - hashes to the same value, and to the numpy
    model's, after 80 more chunks have appended to, regrown and compacted
    the same accounts: rows below ``count`` are never written again,
    shared block or not."""
    mgr = session_mod.SessionStateManager(4)
    accounts = [f"sh{i}" for i in range(12)]
    models = {a: _TwinModel(mgr.n_events) for a in accounts}
    taken = None
    for c in range(taken_at + 81):
        now = NOW0 + 5.0 * c
        ids = list(accounts)
        if c % 3 == 1:
            ids += accounts[:4]          # repeats: two rows of a block
        if c % 7 == 2:
            ids += [accounts[5]] * 3
        amounts = [300 + 7 * c + i for i in range(len(ids))]
        codes = [(c + i) % 5 for i in range(len(ids))]
        audit = mgr.prepare_chunk(session_mod.group_chunk(ids), amounts, codes,
                                  now)[4]
        want = [None] * len(ids)
        for a in accounts:
            rows = [i for i, x in enumerate(ids) if x == a]
            for i, h in zip(rows, models[a].chunk(
                    [amounts[i] for i in rows], [codes[i] for i in rows], now)):
                want[i] = h
        if c == taken_at:
            taken = (audit, [audit[i] for i in range(len(audit))], want)
            if c == 0:
                bases = {id(mgr._twin[a].buf.base) for a in accounts}
                assert len(bases) == 1 and None not in bases  # one block
    audit, at_once, want = taken
    assert at_once == want
    assert [audit[i] for i in range(len(audit))] == want
    assert mgr.snapshot()["twin_regrows"] > 0
    for a in accounts:
        assert mgr._twin[a].seq > 4 * _N  # through the steady size
        assert np.array_equal(mgr.twin_window(a), models[a].window())


def test_two_threads_leave_twin_ring_and_ledger_in_agreement():
    """Two threads score overlapping accounts through ``_launch_cached``
    (the grouping of a chunk now runs before the session lock is taken):
    afterwards the device ring holds every account's host window, and
    the ledger's chunks, put in the order the lock was held, replay every
    session hash bit-exact with no gap and no reordering."""
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent.parent))
    from tools.replay import verify_session_chain

    d = tempfile.mkdtemp(prefix="sess-two-threads-")
    eng = make_engine(batch_size=16, capacity=64, tiers=(8, 16), ledger_dir=d)
    mgr = eng.session
    accounts = [f"tt{i}" for i in range(24)]
    lock_order = []
    inner = mgr.prepare_chunk

    def recording(groups, amounts, codes, now, **kw):
        lock_order.append(now)  # called with the session lock held
        return inner(groups, amounts, codes, now, **kw)

    mgr.prepare_chunk = recording
    errors = []

    def client(t):
        rng = np.random.default_rng(100 + t)
        try:
            for r in range(40):
                n = int(rng.integers(1, 17))
                ids = [accounts[j] for j in rng.integers(0, 24, n)]
                eng.score_columns_cached(
                    ids, [int(x) for x in rng.integers(1, 9000, n)],
                    [("bet", "deposit", "win")[j % 3] for j in range(n)],
                    now=NOW0 + 1000.0 * t + 3.0 * r)
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(t,)) for t in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240.0)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:1]
    assert not any(t.is_alive() for t in threads)
    assert len(lock_order) == 80 == len(set(lock_order))
    snap = mgr.snapshot()
    assert snap["lock_held_s"] > 0.0 and snap["lock_wait_s"] >= 0.0
    for a in accounts:
        assert np.array_equal(ring_rows(eng, a), mgr.twin_window(a)), a
    total = snap["appends"]
    close_engine(eng)
    recs = [r for k, r in ledger_mod.iter_entries(d) if k == "decision"]
    assert len(recs) == total
    by_ts = {}
    for r in recs:
        by_ts.setdefault(r.ts_unix, []).append(r)
    assert set(by_ts) == set(lock_order)
    v = verify_session_chain([r for ts in lock_order for r in by_ts[ts]])
    assert v["session_records"] == v["session_verified"] == total
    assert v["session_hash_mismatch"] == v["session_chain_gaps"] == 0
    assert v["session_reordered"] == v["session_resets"] == 0
    for a in accounts:
        assert mgr.twin_meta(a)["seq"] == sum(r.account_id == a for r in recs)


# ---------------------------------------------------------------------------
# The flat ring vs a numpy model of it (replicated and slot-sharded)


@pytest.mark.parametrize("k", [None, 2, 4])
@pytest.mark.parametrize("seed", [3, 2_271_560_481])
def test_ring_state_matches_numpy_model(k, seed):
    """Seeded batches with duplicates, pad rows, wrap-around and a cold
    admit, straight through the jitted step and the admission sync:
    windows, cursors and lengths equal a plain [slots, N, D] numpy ring."""
    from igaming_platform_tpu.core.features import NUM_FEATURES
    from igaming_platform_tpu.models.ensemble import make_score_fn
    from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh

    cap, shape, n_steps = 8, 16, 30
    mesh = None if k is None else create_mesh(
        MeshSpec(data=k), devices=jax.devices()[:k])
    mgr = session_mod.SessionStateManager(cap, mesh=mesh)
    n, d = mgr.n_events, session_mod.EVENT_WIDTH
    cfg = ScoringConfig()
    step = index_program.build(
        make_score_fn(cfg, "mock"), cfg, family="session", sketch=False,
        shadow=False, mesh=mesh, plan=mgr.plan, session=mgr)
    table = np.zeros((cap, NUM_FEATURES), np.float32)
    flags = np.zeros((cap,), bool)
    if mgr.plan is not None:
        table, flags = mgr.plan.place(table), mgr.plan.place(flags)
    thr = np.array([cfg.block_threshold, cfg.review_threshold], np.int32)

    rng = np.random.default_rng(seed)
    ring = np.zeros((cap, n, d), np.float32)
    cur = np.zeros((cap,), np.int64)
    ln = np.zeros((cap,), np.int64)
    for t in range(n_steps):
        if t == n_steps - 3:
            # Cold admit of two slots late in the run: their windows start over.
            cold = rng.choice(cap, 2, replace=False).astype(np.int32)
            mgr.on_admit([f"new-{s}" for s in cold], cold)
            ring[cold], cur[cold], ln[cold] = 0.0, 0, 0
        # 5..13 real rows over 8 slots (duplicates certain from 9 up;
        # 30 steps of ~9 rows wrap the 16-event windows), the rest pad.
        b = int(rng.integers(5, 14))
        slots = rng.integers(0, cap, b).astype(np.int32)
        occ = session_mod.occurrence_rank_host(slots.astype(np.int64))
        events = rng.random((b, d), dtype=np.float32)
        # the launch's one host array: slots, ranks and events as words
        chunk = index_program.pack_chunk(
            shape, slots, np.zeros((b,), np.float32),
            np.full((b,), 4, np.int32), np.zeros((b,), bool), occ=occ)
        index_program.chunk_events(chunk, b)[:] = events
        res = step(None, mgr.head_params, table, flags, mgr.session_ring,
                   mgr.session_cursor, mgr.session_length, chunk, thr, None)
        mgr.adopt(*res[1:4])
        cur0 = cur.copy()
        for i in range(b):
            ring[slots[i], (cur0[slots[i]] + occ[i]) % n] = events[i]
        counts = np.bincount(slots, minlength=cap)
        cur = (cur + counts) % n
        ln = np.minimum(ln + counts, n)

    rows = np.asarray(session_mod.ring_rows(
        mgr.session_ring, np.arange(cap, dtype=np.int32), n))
    np.testing.assert_array_equal(rows, ring)
    got_cur = np.asarray(mgr.session_cursor)
    got_len = np.asarray(mgr.session_length)
    np.testing.assert_array_equal(got_cur[:cap], cur)
    np.testing.assert_array_equal(got_len[:cap], ln)
    assert ln.max() == n and ln.min() < n  # wrapped slots and a cold one
    if mgr.plan is None:
        # The scratch slot soaked up every pad row and still reads empty.
        assert got_cur[cap] == 0 and got_len[cap] == 0
        assert mgr.session_ring.shape == ((cap + 1) * n * d,)
    else:
        assert mgr.session_ring.shape == (cap * n * d,)


# ---------------------------------------------------------------------------
# The window a head receives: the parent's arithmetic, handed over as words


def _windows_as_the_parent_built_them(ring_rows, cur, ln, events, n_events):
    """``session_state.windows_from_state`` as it stood before PR 61, word
    for word, without the fence it ends in now."""
    import jax.numpy as jnp

    lp = jnp.minimum(ln + 1, n_events)
    hist = lp - 1
    k = jnp.arange(n_events)[None, :]
    pos = jnp.mod(cur[:, None] - hist[:, None] + k, n_events)
    win = jnp.take_along_axis(ring_rows, pos[..., None], axis=1)
    keep = (k < hist[:, None])[..., None]
    win = jnp.where(keep, win, 0.0)
    at_event = (k == hist[:, None])[..., None]
    win = jnp.where(at_event, events[:, None, :], win)
    return win, lp


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jitted"])
@pytest.mark.parametrize("n_events", [16, 128])
@pytest.mark.parametrize("seed", [5, 2_271_560_481])
def test_windows_are_bit_for_bit_the_parents(seed, n_events, jitted):
    """A seeded ring, cursor and length (a cold row of length 0, a row of
    one event, a full row whose cursor wrapped, a full row at cursor 0, the
    rest drawn; the ring holds negative zeros, denormals, an infinity and a
    NaN's payload, which a pass through another type would not keep): the
    window and the post-append length are the parent's, bit for bit and in
    float32, against its arithmetic kept above and against a numpy ring."""
    d = session_mod.EVENT_WIDTH
    rng = np.random.default_rng(seed)
    b = 9
    rows = rng.standard_normal((b, n_events, d)).astype(np.float32)
    rows[0, 0, :4] = [-0.0, 1e-42, np.inf, 0.1]
    rows[2, 1, 0] = np.frombuffer(np.uint32(0x7FC12345).tobytes(), np.float32)[0]
    cur = rng.integers(0, n_events, b).astype(np.int32)
    ln = rng.integers(0, n_events + 1, b).astype(np.int32)
    cur[:4] = [0, 1, 3, 0]
    ln[:4] = [0, 1, n_events, n_events]
    events = rng.standard_normal((b, d)).astype(np.float32)

    fn = session_mod.windows_from_state
    if jitted:
        fn = jax.jit(fn, static_argnums=4)
    win, lp = fn(rows, cur, ln, events, n_events)
    was, was_lp = _windows_as_the_parent_built_them(rows, cur, ln, events, n_events)
    assert win.dtype == np.float32 and win.shape == (b, n_events, d)
    bits = np.asarray(win).view(np.uint32)
    np.testing.assert_array_equal(bits, np.asarray(was).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(was_lp))

    want = np.zeros((b, n_events, d), np.float32)
    for i in range(b):
        hist = min(int(ln[i]), n_events - 1)
        for j in range(hist):
            want[i, j] = rows[i, (cur[i] - hist + j) % n_events]
        want[i, hist] = events[i]
        assert int(lp[i]) == hist + 1
    np.testing.assert_array_equal(bits, want.view(np.uint32))
    assert not want[0, 1:].any() and (want[0, 0] == events[0]).all()  # the cold row


def test_the_window_leaves_as_words_behind_one_fence():
    """What holds the cast of a head's first product behind the gather
    (session_state "the ring's at-rest layout"): the lowered function ends
    in ``bitcast_convert`` to 32-bit words, one ``optimization_barrier`` on
    them, and ``bitcast_convert`` back."""
    b, n, d = 4, 128, session_mod.EVENT_WIDTH
    f32, i32 = np.float32, np.int32
    text = jax.jit(session_mod.windows_from_state, static_argnums=4).lower(
        jax.ShapeDtypeStruct((b, n, d), f32), jax.ShapeDtypeStruct((b,), i32),
        jax.ShapeDtypeStruct((b,), i32), jax.ShapeDtypeStruct((b, d), f32),
        n).as_text()
    words = f"tensor<{b}x{n}x{d}xui32>"
    assert text.count("stablehlo.optimization_barrier") == 1
    fence = [line for line in text.splitlines() if "optimization_barrier" in line]
    assert words in fence[0], fence
    assert text.count("stablehlo.bitcast_convert") == 2
    assert "bf16" not in text


# ---------------------------------------------------------------------------
# Fused-step bit-exactness vs host reference at ladder shapes


@pytest.mark.parametrize("n_rows", [1, 5, 8, 20])
def test_sequence_head_bit_exact_vs_host_reference(n_rows):
    import jax.numpy as jnp

    from igaming_platform_tpu.models.ensemble import ML_HIGH_RISK_BIT, combine

    eng = make_engine(batch_size=32, capacity=64, tiers=(8, 16))
    mgr = eng.session
    accts = [f"ref{i % 7}" for i in range(n_rows)]  # includes duplicates
    # Warm some history first so windows are non-trivial.
    for r in range(5):
        eng.score_columns_cached(sorted(set(accts)),
                                 [700 + r] * len(set(accts)),
                                 ["bet" if r % 2 == 0 else "deposit"]
                                 * len(set(accts)),
                                 now=NOW0 + 40.0 * r)
    now = NOW0 + 400.0
    amounts = [800 + 7 * i for i in range(n_rows)]
    types = [("bet", "deposit", "win")[i % 3] for i in range(n_rows)]
    codes = [TX_TYPE_CODES.get(t, 4) for t in types]

    # -- host reference, computed BEFORE the fused call ----------------------
    snap_windows = {a: mgr.twin_window(a) for a in set(accts)}
    snap_meta = {a: mgr.twin_meta(a) for a in set(accts)}
    dts = [max(0.0, now - snap_meta[a]["last_ts"])
           if snap_meta[a]["seq"] > 0 else 0.0 for a in accts]
    events = session_mod.encode_events_host(amounts, codes, dts)
    n_ev = mgr.n_events
    windows = np.zeros((n_rows, n_ev, session_mod.EVENT_WIDTH), np.float32)
    lps = np.zeros((n_rows,), np.int32)
    for i, a in enumerate(accts):
        hist_all = snap_windows[a]
        lp = min(hist_all.shape[0] + 1, n_ev)
        lps[i] = lp
        if lp > 1:
            windows[i, :lp - 1] = hist_all[hist_all.shape[0] - (lp - 1):]
        windows[i, lp - 1] = events[i]
    head = jax.jit(lambda w, l: session_heads.pattern_scores(w, l))
    sprob = np.asarray(jax.device_get(head(windows, lps)), np.float32)

    # Base (aggregate-only) outputs through the PLAIN cached step.
    idxs = eng.cache.lookup(accts, now=now)
    bl = np.zeros((n_rows,), bool)
    base, = eng._ensure_fused("cached", False, False)(
        eng.get_params(), None, eng.cache.table, eng.cache.flags,
        index_program.pack_chunk(n_rows, idxs, np.asarray(amounts, np.float32),
                                 np.asarray(codes, np.int32), bl),
        eng._thresholds_dev)
    base = np.asarray(jax.device_get(base))
    base_ml = base[4].view(np.float32)
    warm = lps >= mgr.min_events
    fold = warm & (sprob >= mgr.flag_threshold)
    ml2 = np.where(fold, np.maximum(base_ml, sprob), base_ml)
    mask_base = base[2] & ~(1 << ML_HIGH_RISK_BIT)
    fin, act, msk = combine(jnp.asarray(base[3]), jnp.asarray(ml2),
                            jnp.asarray(mask_base), eng.config,
                            jnp.asarray(eng._thresholds))
    msk = np.asarray(jax.device_get(msk))
    msk = msk | np.where(fold, 1 << SESSION_PATTERN_BIT, 0)
    msk = msk | np.where(~warm, 1 << SESSION_COLD_BIT, 0)
    expected = {
        "score": np.asarray(jax.device_get(fin), np.int32),
        "action": np.asarray(jax.device_get(act), np.int32),
        "reason_mask": msk.astype(np.int32),
        "rule_score": base[3],
        "ml_score_bits": ml2.astype(np.float32).view(np.int32),
    }

    # -- the fused step ------------------------------------------------------
    cat = eng.score_columns_cached(accts, amounts, types, now=now)
    got_bits = np.ascontiguousarray(cat["ml_score"], np.float32).view(np.int32)
    assert np.array_equal(cat["score"], expected["score"])
    assert np.array_equal(cat["action"], expected["action"])
    assert np.array_equal(cat["reason_mask"], expected["reason_mask"])
    assert np.array_equal(cat["rule_score"], expected["rule_score"])
    assert np.array_equal(got_bits, expected["ml_score_bits"])
    close_engine(eng)


def test_transformer_head_available_and_deterministic():
    mgr = session_mod.SessionStateManager(4, head="transformer")
    w = np.random.default_rng(3).normal(
        size=(5, mgr.n_events, session_mod.EVENT_WIDTH)).astype(np.float32)
    lp = np.full((5,), mgr.n_events, np.int32)
    f = jax.jit(mgr.head_fn)
    a = jax.device_get(f(mgr.head_params, w, lp))
    b = jax.device_get(f(mgr.head_params, w, lp))
    assert np.array_equal(a, b)
    assert np.all((a >= 0.0) & (a <= 1.0))
    # The pinned seeded convention rebuilds the identical tree.
    p2 = session_heads.init_session_head_params()
    assert (ledger_mod.params_fingerprint(mgr.head_params)
            == ledger_mod.params_fingerprint(p2))


# ---------------------------------------------------------------------------
# Shared-CLOCK eviction coherence + rehydration


def test_shared_clock_eviction_coherence_and_rehydration():
    eng = make_engine(capacity=4)
    accts = [f"ev{i}" for i in range(8)]  # 2x capacity -> CLOCK churn
    for r in range(6):
        for lo in range(0, 8, 4):
            group = accts[lo:lo + 4]
            eng.score_columns_cached(group, [600 + r] * 4,
                                     ["bet" if r % 2 == 0 else "deposit"] * 4,
                                     now=NOW0 + 25.0 * r + lo)
    assert eng.cache.stats()["evictions"] > 0
    assert eng.session.rehydrations > 0
    # Every RESIDENT account's device window equals its twin.
    for a, slot in list(eng.cache._slots.items()):
        twin = eng.session.twin_window(a)
        assert np.array_equal(ring_rows(eng, a), twin), a
    # Evicted accounts keep their host-index state: re-scoring one
    # continues its chain (seq keeps counting, window rehydrated).
    evicted = [a for a in accts if a not in eng.cache._slots]
    assert evicted
    a = evicted[0]
    seq_before = eng.session.twin_meta(a)["seq"]
    count_before = eng.session.twin_window(a).shape[0]
    assert seq_before > 0
    eng.score_columns_cached([a], [999], ["bet"], now=NOW0 + 1000.0)
    assert eng.session.twin_meta(a)["seq"] == seq_before + 1
    dev = ring_rows(eng, a)
    assert dev.shape[0] == min(count_before + 1, eng.session.n_events)
    assert np.array_equal(dev, eng.session.twin_window(a))
    close_engine(eng)


# ---------------------------------------------------------------------------
# Replay: stateful decisions bit-exact across eviction + restart + promotion


def test_replay_stateful_across_eviction_sigkill_promotion():
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent.parent))
    from tools.replay import replay_directory

    d = tempfile.mkdtemp(prefix="sess-replay-test-")
    eng = make_engine(capacity=4, ledger_dir=d)
    accts = [f"rp{i}" for i in range(6)]  # > capacity -> eviction churn
    for r in range(6):
        ids = accts + [accts[0]]  # duplicate inside the chunk
        eng.score_columns_cached(ids, [800 + i for i in range(len(ids))],
                                 ["bet" if r % 2 == 0 else "deposit"]
                                 * len(ids),
                                 now=NOW0 + 35.0 * r)
    # Promotion boundary mid-stream (the PR 9 record type, same WAL).
    eng.ledger.append_promotion(ledger_mod.PromotionRecord(
        event="promote", old_fp="a" * 16, new_fp="b" * 16,
        model_version="mock", reason="test", gates_json="{}",
        ts_unix=NOW0 + 500.0))
    close_engine(eng)

    # SIGKILL-shaped restart: session index + device state gone, WAL kept.
    eng2 = make_engine(capacity=4, ledger_dir=d)
    for r in range(3):
        eng2.score_columns_cached(accts, [900 + i for i in range(6)],
                                  ["deposit" if r % 2 == 0 else "bet"] * 6,
                                  now=NOW0 + 2000.0 + 35.0 * r)
    close_engine(eng2)

    v = replay_directory(d, batch=16)
    assert v["session_records"] == 6 * 7 + 3 * 6
    assert v["session_verified"] == v["session_records"]
    assert v["session_hash_mismatch"] == 0
    assert v["session_chain_gaps"] == 0
    assert v["session_reordered"] == 0
    assert v["session_resets"] == 6  # each account's chain reset once
    assert v["session_ok"] and v["ok"]
    assert [p["event"] for p in v["promotions"]] == ["promote"]
    # Tampering with state is CAUGHT: flip one session hash.
    from igaming_platform_tpu.serve.ledger import iter_entries
    recs = [r for k, r in iter_entries(d) if k == "decision"]
    assert any(r.session_hash for r in recs)


def test_ledger_session_tail_roundtrip_and_stateless_unchanged():
    rec = ledger_mod.DecisionRecord(
        decision_id="d-x.0", account_id="a", trace_id="t",
        model_version="mock", params_fp="0" * 16, wire_mode="index",
        serving_state="serving", tier="device", score=42, action=1,
        reason_mask=1 << SESSION_PATTERN_BIT, rule_score=0,
        ml_score_bits=0x3F000000, amount=900, tx_type="bet",
        block_threshold=80, review_threshold=50, ts_unix=NOW0,
        blacklisted=False, features=None,
        session_len=7, session_seq=123, session_hash="ab" * 8)
    back = ledger_mod.decode_record(ledger_mod.encode_record(rec))
    assert (back.session_len, back.session_seq, back.session_hash) == (
        7, 123, "ab" * 8)
    assert ReasonCode.SESSION_PATTERN in decode_reason_mask(back.reason_mask)
    # A stateless record carries no session tail and no session flag.
    rec2 = ledger_mod.DecisionRecord(
        decision_id="d-x.1", account_id="a", trace_id="t",
        model_version="mock", params_fp="0" * 16, wire_mode="single",
        serving_state="serving", tier="device", score=1, action=1,
        reason_mask=0, rule_score=0, ml_score_bits=0, amount=1,
        tx_type="bet", block_threshold=80, review_threshold=50,
        ts_unix=NOW0, blacklisted=False, features=None)
    raw = ledger_mod.encode_record(rec2)
    assert not (raw[1] & 8)  # _FLAG_SESSION unset
    back2 = ledger_mod.decode_record(raw)
    assert back2.session_hash == "" and back2.session_len == 0


# ---------------------------------------------------------------------------
# The coordinated fraud ring: sequence path catches, aggregates miss


def _drive_schedule(eng, ring: FraudRing, seed: int):
    """Feed the ring schedule event-by-event (each event is scored at
    its own wall time, THEN written back to the feature store — the
    production ordering), collecting (account, t, mask, action, score)."""
    out = []
    for row in ring.schedule(seed):
        t = NOW0 + row["t_s"]
        cat = eng.score_columns_cached([row["account_id"]], [row["amount"]],
                                       [row["tx_type"]], now=t)
        out.append((row["account_id"], row["t_s"], int(cat["reason_mask"][0]),
                    int(cat["action"][0]), int(cat["score"][0])))
        eng.update_features(TransactionEvent(
            account_id=row["account_id"], amount=row["amount"],
            tx_type=row["tx_type"], timestamp=t))
    return out


def test_fraud_ring_caught_by_sequence_missed_by_aggregate():
    ring = FraudRing(ring_size=4, period_s=90.0, cycles=8, amount=900)
    seed = 77

    seq_eng = make_engine(batch_size=8, capacity=32, session=True)
    seq_rows = _drive_schedule(seq_eng, ring, seed)
    base_eng = make_engine(batch_size=8, capacity=32, session=False)
    base_rows = _drive_schedule(base_eng, ring, seed)

    min_ev = seq_eng.session.min_events
    # Post-warmup ring decisions: the sequence path flags them...
    warm_idx = {}
    flagged = total_warm = 0
    for a, _t, mask, action, score in seq_rows:
        warm_idx[a] = warm_idx.get(a, 0) + 1
        if warm_idx[a] >= min_ev:
            total_warm += 1
            if mask & (1 << SESSION_PATTERN_BIT):
                flagged += 1
                assert action >= 2  # review or block, never plain approve
    assert total_warm > 0
    assert flagged / total_warm >= 0.9, (flagged, total_warm)
    # ...and the aggregate-only baseline misses every one of them.
    base_flagged = sum(
        1 for _a, _t, mask, action, _s in base_rows
        if (mask & (1 << SESSION_PATTERN_BIT)) or action >= 2)
    assert base_flagged == 0, base_flagged
    close_engine(seq_eng)
    close_engine(base_eng)


def test_clean_regular_traffic_not_flagged():
    # Human-ish traffic: mixed types, irregular gaps, varied amounts —
    # the session head must stay quiet (no SESSION_PATTERN bit).
    eng = make_engine(batch_size=8, capacity=32, session=True)
    rng = np.random.default_rng(5)
    t = 0.0
    flagged = 0
    for i in range(60):
        t += float(rng.uniform(5.0, 900.0))
        a = f"hum{i % 5}"
        amt = int(rng.integers(50, 40_000))
        tx = ("deposit", "bet", "win", "withdraw")[int(rng.integers(0, 4))]
        cat = eng.score_columns_cached([a], [amt], [tx], now=NOW0 + t)
        if int(cat["reason_mask"][0]) & (1 << SESSION_PATTERN_BIT):
            flagged += 1
    assert flagged == 0
    close_engine(eng)


# ---------------------------------------------------------------------------
# SESSION_COLD honesty + bypass accounting + dispatch count


def test_session_cold_bit_and_row_accounting():
    eng = make_engine(capacity=8)
    min_ev = eng.session.min_events
    masks = []
    for r in range(min_ev + 2):
        cat = eng.score_columns_cached(["cold1"], [500], ["bet"],
                                       now=NOW0 + 60.0 * r)
        masks.append(int(cat["reason_mask"][0]))
    # First min_ev-1 decisions are cold (window < min_events), then warm.
    for r, m in enumerate(masks):
        if r + 1 < min_ev:
            assert m & (1 << SESSION_COLD_BIT), (r, m)
        else:
            assert not (m & (1 << SESSION_COLD_BIT)), (r, m)
    snap = eng.session.snapshot()
    assert snap["rows"]["cold"] == min_ev - 1
    assert snap["rows"]["warm"] == len(masks) - (min_ev - 1)
    # Row-path scoring while session is enabled counts as bypass.
    from igaming_platform_tpu.serve.scorer import ScoreRequest
    eng.score_batch([ScoreRequest("cold1", amount=100, tx_type="bet")] * 3)
    assert eng.session.snapshot()["rows"]["bypass"] >= 3
    close_engine(eng)


def test_session_rows_metric_exposition():
    from igaming_platform_tpu.obs.metrics import ServiceMetrics

    m = ServiceMetrics("risk")
    eng = make_engine(capacity=8)
    eng.bind_session_metrics(m)
    eng.score_columns_cached(["mx1", "mx2"], [100, 200], ["bet", "deposit"],
                             now=NOW0)
    text = m.registry.render_text()
    assert 'risk_session_rows_total{outcome="cold"}' in text
    assert "risk_session_appends_total" in text
    assert "risk_session_twin_bytes" in text
    assert "risk_session_twin_regrows_total" in text
    assert "risk_session_hbm_bytes" in text
    close_engine(eng)


def test_fused_step_adds_no_dispatches_per_chunk(monkeypatch):
    from igaming_platform_tpu.serve import scorer as scorer_mod

    counts = {"on": 0, "off": 0}
    accts = [f"dc{i}" for i in range(10)]

    for key, session in (("off", False), ("on", True)):
        eng = make_engine(batch_size=4, capacity=16, session=session,
                          tiers=())
        # Warm run FIRST: admissions fire the between-steps scatter (and,
        # with session on, the ring sync) — real launches the honest
        # dispatch seam now counts. The fused-step claim is about the
        # STEADY state: resident accounts, no admissions.
        eng.score_columns_cached(accts, [90] * 10, ["bet"] * 10, now=NOW0)
        calls = []
        orig = scorer_mod._device_dispatch
        monkeypatch.setattr(scorer_mod, "_device_dispatch",
                            lambda fn, shape, dtype: calls.append(fn))
        for r in range(1, 3):
            eng.score_columns_cached(accts, [100 + r] * 10, ["bet"] * 10,
                                     now=NOW0 + 30.0 * r)
        monkeypatch.setattr(scorer_mod, "_device_dispatch", orig)
        counts[key] = len(calls)
        close_engine(eng)
    # Same chunking, same dispatch count: the session head rides the
    # SAME device call (risk_device_dispatches_total per RPC unchanged).
    assert counts["on"] == counts["off"] > 0


def test_session_reason_bits_appended_not_reordered():
    # Wire compatibility: the session bits extend REASON_BIT_ORDER at the
    # end; every pre-session bit keeps its position.
    assert REASON_BIT_ORDER.index(ReasonCode.ML_HIGH_RISK) == 8
    assert SESSION_PATTERN_BIT == 9 and SESSION_COLD_BIT == 10
    assert decode_reason_mask(1 << 8) == [ReasonCode.ML_HIGH_RISK]


# -- the shape of models/: a backbone is one module and one row ----------------

_MODELS = pathlib.Path(session_heads.__file__).parent
_BACKBONES = sorted(p.stem for p in _MODELS.glob("*_backbone.py"))


def _imported(module: str) -> set[str]:
    """Every module name ``models/<module>.py`` imports, anywhere in it, by
    ``ast``: ``from a.b import c`` gives both ``a.b`` and ``a.b.c``."""
    names: set[str] = set()
    for node in ast.walk(ast.parse((_MODELS / f"{module}.py").read_text())):
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
            names.update(f"{node.module}.{alias.name}" for alias in node.names)
    return names


@pytest.mark.parametrize("module", [*_BACKBONES, "decoder_parts", "expert_layer"])
def test_no_backbone_is_imported_by_another_or_by_the_shared_parts(module):
    """The arrows of ``models/``: a backbone takes its shared parts from
    ``decoder_parts`` / ``expert_layer`` and never from another backbone,
    and those two import no backbone; so a new backbone edits no other."""
    assert len(_BACKBONES) >= 4
    others = {b for b in _BACKBONES if b != module}
    taken = {name.rsplit(".", 1)[-1] for name in _imported(module)} & others
    assert not taken, f"models/{module}.py imports {sorted(taken)}"


@pytest.mark.parametrize("name", sorted(session_heads.HEADS))
def test_every_row_of_heads_is_whole(name):
    """One row a head, nothing beside it: layer counts over exactly
    ``LAYER_KINDS`` and an expert pair, which ``/debug/sessionz`` and the
    boot gauges read without a default."""
    row = session_heads.HEADS[name]
    assert list(row.layers) == list(session_heads.LAYER_KINDS)
    assert all(isinstance(n, int) and n >= 0 for n in row.layers.values())
    held, routed = row.experts
    assert 0 <= held <= routed
    assert (routed > 0) == (row.layers["moe"] > 0)
    assert callable(row.scores) and callable(row.init)
    assert session_heads.session_head(name) is row
