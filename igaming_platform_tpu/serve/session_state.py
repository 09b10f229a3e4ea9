"""Device-resident per-account session state — the stateful sequence head.

ROADMAP open item 3: velocity and session-pattern fraud (rapid
bet-deposit cycling, coordinated multi-account rings) needs the last-N
events per account *at score time*; aggregate features can't see a
pattern whose every individual window statistic looks benign. This
module keeps a KV-cache-style per-account ring buffer in HBM **beside
the PR 1 device feature cache** (the SNIPPETS mesh helpers come from a
KV-cache serving codebase — same shape, same discipline):

- ``session_ring``  float32, FLAT: [(capacity+1) * N_EVENTS * EVENT_WIDTH]
  — per-slot event windows, slot-aligned with the feature table, slot
  ``s`` owning elements [s*N*D, (s+1)*N*D) (slot ``capacity`` is the
  scratch slot batch padding writes into, never read for a decision).
  Flat because that is the one at-rest layout the TPU both leaves
  unpadded and writes in place: see "the ring's at-rest layout" below;
- ``session_cursor`` / ``session_length`` [capacity+1] int32 — per-slot
  write cursor and saturating event count.

The tables share the feature cache's host ``account_id -> slot`` index
and CLOCK eviction: ONE admission decision governs both. On admission
the cache calls :meth:`SessionStateManager.on_admit` and the slot is
synced (rehydrated) from the host-side session index in the same
between-steps scatter window as the feature delta fold — an evicted or
restarted slot rehydrates without any new wire surface.

The scoring step itself is FUSED (serve/index_program.py owns and builds
it; this module owns the ring it reads and writes, the host index and
the admission sync): the dispatch that gathers feature rows also runs the
session head over each account's POST-APPEND window and appends the event
in place through donated ring buffers — no extra dispatch, no host sync.

Auditability ("Rethinking LLMOps for Fraud and AML", PAPERS.md): every
stateful decision carries a ``session_state_hash`` — blake2b over the
account's post-append window, computed from the HOST session index under
the append lock — into its DecisionRecord, plus the post-append window
length. ``tools/replay.py`` reconstructs the windows from ledger event
order alone (amount, tx type, record timestamp) and verifies every hash
bit-exact; the recorded length makes replay self-synchronizing across
eviction (state persists -> length continues) and SIGKILL (host index
lost -> length drops to 1 -> replay truncates its twin).

Mutation discipline: in-place writes to the ring state
(``session_ring`` / ``session_cursor`` / ``session_length`` and the host
``_session_twin``) are only legal inside functions marked
``# analysis: session-append-seam`` — analyzer rule CC08 enforces it,
because a bare rebind skips the host-index commit and the ledger hash,
silently breaking replay for every later decision on that slot.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import threading
from typing import Any

import numpy as np

from igaming_platform_tpu.models.decoder_parts import announced_cores
from igaming_platform_tpu.models.sequence import EVENT_DIM
from igaming_platform_tpu.models.session_heads import session_head

# Per-event layout: models/sequence.encode_event — [log-amount, log-dt,
# 8-way tx-type one-hot, game-weight, balance-ratio].
EVENT_WIDTH = EVENT_DIM

# Wire tx-type codes (serve/wire.TX_TYPE_CODES: deposit=0 withdraw=1
# bet=2 win=3 other=4) -> one-hot column inside the event vector. The
# first four match models/sequence.TX_TYPE_INDEX; "other" lands on the
# adjustment column (index 7), same as encode_event's fallback.
_TX_EVENT_COL = np.array([0, 1, 2, 3, 7], dtype=np.int64)


def default_events() -> int:
    return int(os.environ.get("SESSION_EVENTS", "16"))


def default_min_events() -> int:
    return int(os.environ.get("SESSION_MIN_EVENTS", "4"))


def default_flag_threshold() -> float:
    return float(os.environ.get("SESSION_FLAG_THRESHOLD", "0.7"))


def session_enabled_env() -> bool:
    return os.environ.get("SESSION_STATE", "") not in ("", "0")


# ---------------------------------------------------------------------------
# Event encoding + window hash (host side — shared verbatim by replay)


def encode_events_host(amounts, tx_codes, dts, out=None) -> np.ndarray:
    """[B] amounts / wire tx codes / inter-event gaps -> [B, EVENT_WIDTH]
    float32 event rows. This is THE event codec: the serving path scatters
    these exact bytes into HBM, the session hash covers them, and
    tools/replay.py re-derives them from recorded values — float64
    arithmetic up to the final float32 cast so both sides agree bitwise.
    ``out`` is a ZEROED float32 [B, EVENT_WIDTH] to write the rows into
    (the event words of the launch's packed chunk,
    serve/index_program.chunk_events), else they get an array of their own.
    """
    b = len(amounts)
    ev = np.zeros((b, EVENT_WIDTH), dtype=np.float32) if out is None else out
    ev[:, 0] = np.log1p(np.maximum(np.asarray(amounts, np.float64), 0.0))
    ev[:, 1] = np.log1p(np.maximum(np.asarray(dts, np.float64), 0.0))
    codes = np.clip(np.asarray(tx_codes, np.int64), 0, len(_TX_EVENT_COL) - 1)
    ev[np.arange(b), 2 + _TX_EVENT_COL[codes]] = 1.0
    ev[:, 10] = 1.0  # game weight (unknown at the wire: neutral)
    return ev


def window_hash(window: np.ndarray) -> bytes:
    """blake2b-8 over a post-append window ([L, EVENT_WIDTH] float32,
    chronological). The ``session_state_hash`` of the DecisionRecord."""
    h = hashlib.blake2b(digest_size=8)
    h.update(np.ascontiguousarray(window, dtype=np.float32).tobytes())
    return h.digest()


# ---------------------------------------------------------------------------
# What the fused step (serve/index_program.py) reads and writes the ring with


def windows_from_state(ring_rows, cur, ln, events, n_events: int):
    """Post-append window construction from PRE-GATHERED per-row ring
    state (``ring_rows`` [B, N, D], ``cur``/``ln`` [B]): the last
    ``min(length, N-1)`` stored events in chronological order, then the
    new event, zero-padded to [B, N, D]. However the rows were gathered
    (one device or an owner-select collective), the window math is this.
    The window is handed on as 32-bit words behind one fence ("the ring's
    at-rest layout" below says why): the same float32, bit for bit."""
    import jax
    import jax.numpy as jnp

    lp = jnp.minimum(ln + 1, n_events)  # post-append window length
    hist = lp - 1                       # historical events kept
    k = jnp.arange(n_events)[None, :]
    pos = jnp.mod(cur[:, None] - hist[:, None] + k, n_events)
    win = jnp.take_along_axis(ring_rows, pos[..., None], axis=1)  # [B, N, D]
    keep = (k < hist[:, None])[..., None]
    win = jnp.where(keep, win, 0.0)
    at_event = (k == hist[:, None])[..., None]
    win = jnp.where(at_event, events[:, None, :], win)
    bits = jax.lax.optimization_barrier(
        jax.lax.bitcast_convert_type(win, jnp.uint32))
    return jax.lax.bitcast_convert_type(bits, jnp.float32), lp


# -- the ring's at-rest layout (ONE place) -----------------------------------
#
# The device ring is FLAT: float32[rows * N_EVENTS * EVENT_WIDTH], slot
# ``s`` owning the contiguous elements [s*N*D, (s+1)*N*D). A 1-D array
# pads nothing (the chip keeps [rows, 16, 12] as {0,1,2:T(8,128)} with
# the slot axis minor-most precisely to avoid padding 16 and 12 to a
# tile) and, unlike every shaped layout tried, its windowed gather and
# scatter compile IN PLACE on the TPU: a step moves O(batch) bytes, not
# two re-layout copies of the whole ring (docs/architecture.md "Session
# ring layout" has the table of compiles). That is also why a window
# leaves ``windows_from_state`` as uint32 words behind an optimization
# barrier: a head's first product casts its operand to bfloat16, and the
# TPU compiler's bfloat16 propagation carries a cast back through selects,
# gathers and a bare barrier alike, onto the ring argument (at 128 events:
# a convert of all 4 GB, a fifth of ``keye``'s step, PR 61); it stops at a
# bitcast-convert, and the barrier keeps the pair from folding away.
# Everything that reads or writes the ring goes through the three
# functions below; an index past the end reads zeros and writes nothing
# (a slot-sharded body points non-owned rows there).


def ring_size(rows: int, n_events: int, shards: int = 1) -> int:
    """Elements of a flat ring of ``rows`` slots. The int32 window
    starts address one device's block of it, so that block — not a
    slot-sharded ring's total — has to stay under 2**31 elements."""
    size = int(rows) * n_events * EVENT_WIDTH
    if size // shards >= 2**31:
        raise ValueError(
            f"session ring of {rows} slots x {n_events} events over "
            f"{shards} device(s): {size // shards} elements on one device "
            "is past int32 indexing (shard the state further)")
    return size


def ring_rows(ring, slots, n_events: int):
    """Gather the stored [B, N, D] event rows of ``slots`` ([B] int32)."""
    import jax

    row = n_events * EVENT_WIDTH
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))
    flat = jax.lax.gather(ring, (slots * row)[:, None], dn, (row,),
                          mode="fill", fill_value=0)
    return flat.reshape(slots.shape[0], n_events, EVENT_WIDTH)


def _ring_scatter(ring, starts, updates):
    import jax

    dn = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,))
    return jax.lax.scatter(ring, starts[:, None], updates, dn, mode="drop")


def ring_append(ring, slots, wpos, events, n_events: int):
    """Write one [D] event per row at ``(slot, wpos)``."""
    return _ring_scatter(
        ring, (slots * n_events + wpos) * EVENT_WIDTH, events)


def ring_put(ring, slots, windows):
    """Overwrite the whole window of each of ``slots`` ([K, N, D])."""
    k, n_events, _ = windows.shape
    return _ring_scatter(
        ring, slots * (n_events * EVENT_WIDTH),
        windows.reshape(k, n_events * EVENT_WIDTH))


def advance_counters(cursor, length, slots, ln, occ, real, n_events: int):
    """Advance the write cursor and the saturating length of the TOUCHED
    slots only (the arrays are capacity-sized; a batch is not). ``ln``
    is the gathered batch-start length, ``occ`` the within-batch
    occurrence rank: duplicates of one account advance its cursor once
    each, and the max over them of ``min(ln + occ + 1, N)`` is the
    saturated new length. Rows with ``real`` False (padding into the
    scratch slot) add nothing, so the scratch counters stay 0 and a pad
    row can never look warm."""
    import jax.numpy as jnp

    inc = real.astype(jnp.int32)
    cursor = cursor.at[slots].add(inc, mode="drop")
    total = cursor.at[slots].get(mode="fill", fill_value=0)
    cursor = cursor.at[slots].set(jnp.mod(total, n_events), mode="drop")
    length = length.at[slots].max(
        jnp.minimum(ln + occ + 1, n_events) * inc, mode="drop")
    return cursor, length


def _stable_runs(uidx: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Rows of a non-empty batch sorted by account, chunk order kept
    inside an account: (order [B], starts [B] bool, run_start [U])."""
    order = np.argsort(uidx, kind="stable")
    sorted_u = uidx[order]
    starts = np.empty(uidx.shape, dtype=bool)
    starts[0] = True
    np.not_equal(sorted_u[1:], sorted_u[:-1], out=starts[1:])
    return order, starts, np.flatnonzero(starts)


def occurrence_rank_host(uidx: np.ndarray) -> np.ndarray:
    """occ[i] = how many earlier rows of this batch target the same
    account — duplicate appends land at cursor+occ instead of
    clobbering. Computed on the host (vectorized over the stable-sorted
    runs) and shipped to the fused step as a [B] int32 column, which
    keeps an O(B^2) comparison matrix out of the graph."""
    b = uidx.shape[0]
    if b == 0:
        return np.zeros((0,), np.int32)
    order, starts, run_start = _stable_runs(uidx)
    occ = np.empty((b,), np.int32)
    occ[order] = np.arange(b) - run_start[np.cumsum(starts) - 1]
    return occ


def decoded_ids(account_ids) -> list[str]:
    """Account ids as the host index keys them: ``str``. A wire frame
    brings ``bytes``; decode a chunk once and hand the list on."""
    return [a if isinstance(a, str) else str(a, "utf-8") for a in account_ids]


class ChunkGroups:
    """What a chunk's account ids decide on their own, computed BEFORE
    the session lock is taken (:func:`group_chunk`): the unique accounts
    in order of first appearance, each row's account (``uidx``) and
    occurrence rank (``occ``), per unique account its number of rows
    (``counts``) and first row (``first``), and the stable ``order`` of
    the rows by account with the ``bounds`` of each account's run in it.
    What is indexed per account in Python (``counts``, ``first``,
    ``bounds``) is a plain sequence, not an array."""

    __slots__ = ("ids", "uidx", "occ", "counts", "first", "order", "bounds")

    def __init__(self, ids, uidx, occ, counts, first, order, bounds):
        self.ids = ids
        self.uidx = uidx
        self.occ = occ
        self.counts = counts
        self.first = first
        self.order = order
        self.bounds = bounds

    def rows_of(self, u: int) -> np.ndarray:
        """Rows of unique account ``u``, in chunk order."""
        return self.order[self.bounds[u]:self.bounds[u + 1]]


def group_chunk(account_ids) -> ChunkGroups:
    """Group a chunk's rows by account. Reads the ids and nothing else
    (no host index, no clock), so it runs outside the session lock; what
    needs the index is ``SessionStateManager.prepare_chunk``."""
    index: dict[str, int] = {}
    rows = [index.setdefault(a, len(index)) for a in decoded_ids(account_ids)]
    b = len(rows)
    if len(index) == b:  # distinct accounts: every row is its own run
        uidx = np.arange(b)
        return ChunkGroups(list(index), uidx, np.zeros((b,), np.int32),
                           [1] * b, range(b), uidx, range(b + 1))
    uidx = np.array(rows)
    order, starts, run_start = _stable_runs(uidx)
    bounds = np.append(run_start, b)
    # uidx is dense and numbered by first appearance: run r is account r
    occ = np.empty((b,), np.int32)
    occ[order] = np.arange(b) - run_start[uidx[order]]
    return ChunkGroups(list(index), uidx, occ, np.diff(bounds).tolist(),
                       order[run_start].tolist(), order, bounds.tolist())


class SessionChunkAudit:
    """Lazy per-row ``session_state_hash`` provider: holds the chunk's
    batch-start snapshot REFERENCES — ``(buffer, row_count)`` per unique
    account into the append-only twin buffers, stable by construction —
    and computes each row's blake2b-8 only when the ledger writer thread
    expands the columnar batch into records: the scoring hot path never
    hashes, never copies a window. Indexing semantics match a
    ``list[bytes]``."""

    __slots__ = ("events", "post_len", "uidx", "snaps")

    def __init__(self, events: np.ndarray, post_len: np.ndarray,
                 uidx: np.ndarray, snaps: list[tuple[np.ndarray, int]]):
        self.events = events
        self.post_len = post_len
        self.uidx = uidx
        self.snaps = snaps

    def __len__(self) -> int:
        return int(self.post_len.shape[0])

    def __getitem__(self, i: int) -> bytes:
        hist = int(self.post_len[i]) - 1
        h = hashlib.blake2b(digest_size=8)
        if hist > 0:
            buf, count = self.snaps[int(self.uidx[i])]
            h.update(np.ascontiguousarray(
                buf[count - hist:count], dtype=np.float32).tobytes())
        h.update(self.events[i].tobytes())
        return h.digest()


def make_ring_sync(mesh=None, plan=None):
    """The jitted admission sync ``(ring, cursor, length, slots [K],
    windows [K, N, D], cursors [K], lengths [K]) -> (ring', cursor',
    length')``. The ring state is DONATED (outputs alias inputs, as in
    the fused step): an admission writes K windows, it does not copy the
    ring; ``on_admit`` rebinds the results under the manager's lock."""
    import jax

    if plan is not None:
        from igaming_platform_tpu.parallel import state_sharding

        return state_sharding.make_sharded_ring_sync(plan)

    def sync(ring, cur, ln, slots, w, c, l):  # noqa: E741
        return (ring_put(ring, slots, w), cur.at[slots].set(c),
                ln.at[slots].set(l))

    if mesh is None:
        return jax.jit(sync, donate_argnums=(0, 1, 2))
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())
    return jax.jit(sync, in_shardings=(repl,) * 7, out_shardings=(repl,) * 3,
                   donate_argnums=(0, 1, 2))


# ---------------------------------------------------------------------------
# Host session index + device ring manager


_EMPTY_WINDOW = np.zeros((0, EVENT_WIDTH), np.float32)
_EMPTY_WINDOW.setflags(write=False)
_NO_HISTORY = (_EMPTY_WINDOW, 0)  # a never-seen account's snapshot

# The host twin's first buffer and how it grows, in rows (measured on
# the chip's host, PERF.md section 6, PR 31). Capacity stops growing at
# the steady ``4 * n_events`` rows.
_TWIN_FIRST_ROWS = 2
_TWIN_GROWTH = 2


class _AcctSession:
    """Host-authoritative window for one account.

    Events live in an APPEND-ONLY buffer that holds bytes in proportion
    to the events it has been given: a never-seen account shares the
    read-only ``_EMPTY_WINDOW`` and allocates nothing; the first append
    allocates ``_TWIN_FIRST_ROWS`` rows (or the rows it is given), later
    ones grow the capacity by ``_TWIN_GROWTH`` up to the steady
    ``4 * n_events`` rows, and from there the buffer is compacted when
    full. Growth and compaction both REALLOCATE (``np.empty``: every row
    below ``count`` is written before it is read) and copy the last
    ``min(count, n_events)`` rows - never a shift in place, never a view
    of a chunk's array - and rows ``[0:count]`` of an array are never
    written again, so window snapshots can be handed out as stable numpy
    VIEWS: the lazy hash audit (SessionChunkAudit) reads them on the
    ledger writer thread while later chunks keep appending. An account
    that has received ``k`` events holds at most ``2 * max(k, 2)`` rows
    and never more than the steady size (plus one oversized chunk).
    ``seq`` is the monotone total event count; the live window is the
    last ``min(seq, N)`` buffer rows."""

    __slots__ = ("buf", "count", "seq", "last_ts")

    def __init__(self, buf: np.ndarray = _EMPTY_WINDOW, count: int = 0,
                 seq: int = 0, last_ts: float = 0.0):
        self.buf = buf
        self.count = count  # rows currently stored in buf
        self.seq = seq      # total events ever appended
        self.last_ts = last_ts

    def window_view(self, n_events: int) -> np.ndarray:
        k = min(self.seq, n_events)
        return self.buf[self.count - k:self.count]

    def append_rows(self, rows: np.ndarray, n_events: int,
                    now: float) -> int:
        """Appends ``rows``; returns the row capacity of the buffer it
        left behind where it had to allocate, else -1."""
        k = rows.shape[0]
        old = -1
        if self.count + k > self.buf.shape[0]:
            old = self.buf.shape[0]
            keep = min(self.count, n_events)
            steady = 4 * n_events
            if old >= steady or keep + k > steady:
                cap = max(steady, k + n_events)
            else:
                cap = min(steady, max(_TWIN_FIRST_ROWS, _TWIN_GROWTH * old,
                                      keep + k))
            nb = np.empty((cap, EVENT_WIDTH), dtype=np.float32)
            nb[:keep] = self.buf[self.count - keep:self.count]
            self.buf = nb  # old views (audit snapshots) keep the old buf
            self.count = keep
        self.buf[self.count:self.count + k] = rows
        self.count += k
        self.seq += k
        self.last_ts = now
        return old


class SessionStateManager:
    """The engine-facing session plane: HBM ring + host index + stats.

    The host index (``_session_twin``) is authoritative — the device
    ring is its slot-resident projection, synced on admission and
    advanced by the fused step's donated append. An account's host
    buffer grows with the events it holds (``_AcctSession``): a first
    event costs a 2-row buffer, not a window; ``twin_bytes`` is the sum
    of the live buffers' capacities (kept at allocation, never by a
    walk) and ``twin_regrows`` counts the appends that had to allocate
    again after an account's first allocation. The ring is allocated
    flat ([slots * N * D] float32, under its sharding from birth) and
    every program that touches it — the step, the admission sync, their
    slot-sharded twins — donates it and goes through ``ring_rows`` /
    ``ring_append`` / ``ring_put``: no program copies the ring.
    Everything that mutates either lives behind ``lock`` and a
    ``# analysis: session-append-seam`` function (rule CC08).
    """

    def __init__(self, capacity: int, *, mesh=None,
                 n_events: int | None = None,
                 min_events: int | None = None,
                 flag_threshold: float | None = None,
                 head: str | None = None,
                 metrics: Any = None):
        import jax
        import jax.numpy as jnp

        self.capacity = int(capacity)
        self.n_events = int(n_events if n_events is not None else default_events())
        if self.n_events < 2:
            raise ValueError(f"SESSION_EVENTS must be >= 2, got {self.n_events}")
        self.min_events = int(
            min_events if min_events is not None else default_min_events())
        self.flag_threshold = float(
            flag_threshold if flag_threshold is not None
            else default_flag_threshold())
        self.head = (head or os.environ.get("SESSION_HEAD", "pattern")).lower()
        row = session_head(self.head)
        self.head_fn, self.head_params = row.scores, row.init()
        # what the head holds, fixed at boot: its tree's device bytes and,
        # where it has an expert layer, (experts held here, experts routed)
        self.head_resident_bytes = sum(
            int(a.nbytes) for a in jax.tree.leaves(self.head_params))
        self.head_experts = row.experts
        # what the stack is made of: layers by kind, a kind it lacks at 0
        self.head_layers = dict(row.layers)
        # residual streams a layer carries: one but for a hyper-connected head
        self.head_residual_streams = getattr(row.config, "streams", 1)
        # key blocks its attention cores (visit, would visit as whole
        # squares) a scored row, where the head sweeps its keys in blocks
        self.head_key_blocks = (row.key_blocks(self.n_events)
                                if row.key_blocks else (0, 0))
        # layer-positions a scored row (costs, would cost with every layer
        # at every position), where the head's stack narrows
        self.head_layer_positions = (row.layer_positions(self.n_events)
                                     if row.layer_positions else (0, 0))

        self.lock = threading.RLock()
        self._twin: dict[str, _AcctSession] = {}
        self._mesh = mesh
        self._metrics = metrics

        # Stats (exported via bind_metrics / snapshot()).
        self.appends = 0
        self.rehydrations = 0
        self.admissions = 0
        self.warm_rows = 0
        self.cold_rows = 0
        self.bypass_rows = 0
        self.twin_bytes = 0
        self.twin_regrows = 0
        self.head_real_positions = 0
        self.lock_wait_s = 0.0
        self.lock_held_s = 0.0

        from igaming_platform_tpu.parallel import state_sharding

        # Slot-sharded ring (parallel/state_sharding.py): the SAME plan
        # the feature cache derived (capacity arrives pre-rounded from
        # cache.capacity), so one slot id owns the same shard in both
        # tables. The sharded layout drops the scratch row: padding rows
        # target sidx == capacity, which no shard owns — reads clamp
        # into discarded outputs, appends scatter with mode='drop'.
        self.plan = state_sharding.plan_for(mesh)
        self.n_shards = 1 if self.plan is None else self.plan.n_shards
        ring_rows = self.capacity if self.plan is not None else self.capacity + 1
        self._ring_rows = ring_rows
        sharding = None
        if self.plan is not None:
            sharding = self.plan.named(1)
        elif mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(mesh, P())

        n_ring = ring_size(ring_rows, self.n_events, self.n_shards)
        # Born under its sharding: a slot-sharded ring never exists whole
        # on one device.
        ring, cursor, length = jax.jit(
            lambda: (jnp.zeros((n_ring,), jnp.float32),
                     jnp.zeros((ring_rows,), jnp.int32),
                     jnp.zeros((ring_rows,), jnp.int32)),
            out_shardings=sharding)()
        self._sync = make_ring_sync(mesh, self.plan)
        self.session_ring = ring
        self.session_cursor = cursor
        self.session_length = length
        self._export_head()

    # -- metrics / surfaces ---------------------------------------------------

    def bind_metrics(self, metrics: Any) -> None:
        if metrics is self._metrics:
            return
        self._metrics = metrics
        with self.lock:
            self._export(self.warm_rows, self.cold_rows, self.bypass_rows,
                         self.appends, self.rehydrations, self.twin_regrows,
                         self.head_real_positions)
            metrics.session_lock_wait_seconds_total.inc(self.lock_wait_s)
            metrics.session_lock_held_seconds_total.inc(self.lock_held_s)
        self._export_head()

    def _export_head(self) -> None:
        """What the head holds and is made of, fixed at boot: four gauges
        and one a kind of layer."""
        m = self._metrics
        if m is not None:
            m.session_head_resident_bytes.set(self.head_resident_bytes)
            m.session_head_experts_held.set(self.head_experts[0])
            m.session_head_experts_routed.set(self.head_experts[1])
            for kind, count in self.head_layers.items():
                m.session_head_layers.set(count, kind=kind)
            m.session_head_residual_streams.set(self.head_residual_streams)

    def _export(self, warm: int, cold: int, bypass: int, appends: int,
                rehydrations: int, regrows: int = 0,
                real_positions: int = 0) -> None:
        m = self._metrics
        if m is None:
            return
        if warm:
            m.session_rows_total.inc(warm, outcome="warm")
        if cold:
            m.session_rows_total.inc(cold, outcome="cold")
        if bypass:
            m.session_rows_total.inc(bypass, outcome="bypass")
        if appends:
            m.session_appends_total.inc(appends)
            # every session-scored row is one window of n_events positions
            # through the head; real_positions of them hold an event
            m.session_head_positions_total.inc(appends * self.n_events)
            m.session_head_real_positions_total.inc(real_positions)
            visited, square = self.head_key_blocks
            if square:
                m.session_head_key_blocks_visited_total.inc(appends * visited)
                m.session_head_key_blocks_square_total.inc(appends * square)
            computed, whole = self.head_layer_positions
            if whole:
                m.session_head_layer_positions_computed_total.inc(
                    appends * computed)
                m.session_head_layer_positions_whole_total.inc(appends * whole)
        if rehydrations:
            m.session_rehydrations_total.inc(rehydrations)
        if regrows:
            m.session_twin_regrows_total.inc(regrows)
        m.session_twin_bytes.set(self.twin_bytes)
        m.session_hbm_bytes.set(self.hbm_bytes())
        for s, b in enumerate(self.hbm_bytes_per_shard()):
            m.hbm_bytes.set(b, shard=str(s), table="session_ring")

    def hbm_bytes(self) -> int:
        return (self._ring_rows * self.n_events * EVENT_WIDTH * 4
                + 2 * self._ring_rows * 4)

    def hbm_bytes_per_shard(self) -> list[int]:
        """Static per-shard ring budget (equal contiguous row blocks)."""
        per = self.hbm_bytes() // self.n_shards
        return [per] * self.n_shards

    def shard_stats(self) -> dict:
        """Per-shard breakdown for /debug/sessionz + the fleet view."""
        return {
            "sharded": self.plan is not None,
            "shards": self.n_shards,
            "rows_per_shard": self._ring_rows // self.n_shards,
            "hbm_bytes": self.hbm_bytes_per_shard(),
        }

    def snapshot(self) -> dict:
        """/debug/sessionz payload (docs/operations.md 'Session state')."""
        with self.lock:
            return {
                "enabled": True,
                "head": self.head,
                "capacity": self.capacity,
                "n_events": self.n_events,
                "min_events": self.min_events,
                "flag_threshold": self.flag_threshold,
                "accounts_tracked": len(self._twin),
                "hbm_bytes": self.hbm_bytes(),
                "appends": self.appends,
                "twin_bytes": self.twin_bytes,
                "twin_regrows": self.twin_regrows,
                "head_positions": self.appends * self.n_events,
                "head_real_positions": self.head_real_positions,
                "head_resident_bytes": self.head_resident_bytes,
                "head_experts_held": self.head_experts[0],
                "head_experts_routed": self.head_experts[1],
                "head_layers": dict(self.head_layers),
                "head_residual_streams": self.head_residual_streams,
                "head_cores": announced_cores(),
                "lock_wait_s": self.lock_wait_s,
                "lock_held_s": self.lock_held_s,
                "rehydrations": self.rehydrations,
                "admissions": self.admissions,
                "rows": {"warm": self.warm_rows, "cold": self.cold_rows,
                         "bypass": self.bypass_rows},
                "sharding": self.shard_stats(),
            }

    def note_bypass(self, n: int) -> None:
        """Rows scored on a non-session path (row wire mode, batcher,
        heuristic tier) while session state is enabled — counted, never
        silently unsessioned."""
        with self.lock:
            self.bypass_rows += n
            self._export(0, 0, n, 0, 0)

    # -- admission sync (shared CLOCK: called by the feature cache) -----------

    def on_admit(self, account_ids, slots) -> None:  # analysis: session-append-seam
        """Feature-cache admission hook: the SAME admission that placed
        these accounts into feature slots places their session windows
        into the ring — rehydration from the host index for known
        accounts, a clean (cursor=0, length=0) window for new ones. Runs
        in the cache's between-steps scatter window, not on the fused
        dispatch."""
        import jax.numpy as jnp

        k = len(slots)
        if k == 0:
            return
        with self.lock:
            w = np.zeros((k, self.n_events, EVENT_WIDTH), dtype=np.float32)
            lens = np.zeros((k,), dtype=np.int32)
            rehydrated = 0
            for i, a in enumerate(decoded_ids(account_ids)):
                tw = self._twin.get(a)
                if tw is not None and tw.seq > 0:
                    win = tw.window_view(self.n_events)
                    w[i, :win.shape[0]] = win
                    lens[i] = win.shape[0]
                    rehydrated += 1
            cursors = np.mod(lens, self.n_events).astype(np.int32)
            # The admission sync is a real jit launch in the between-steps
            # window: it fires the honest dispatch seam so the
            # dispatches-per-RPC probe counts it (it shows up only when
            # admissions/rehydrations happen, never in steady state).
            from igaming_platform_tpu.serve.scorer import _device_dispatch

            _device_dispatch("session_sync", (k, self.n_events), np.float32)
            self.session_ring, self.session_cursor, self.session_length = (
                self._sync(self.session_ring, self.session_cursor,
                           self.session_length,
                           jnp.asarray(np.asarray(slots, dtype=np.int32)),
                           jnp.asarray(w), jnp.asarray(cursors),
                           jnp.asarray(lens)))
            self.admissions += k
            self.rehydrations += rehydrated
            self._export(0, 0, 0, 0, rehydrated)

    # -- the append path (fused step prepare/adopt) ---------------------------

    def prepare_chunk(self, groups: ChunkGroups, amounts, tx_codes, now: float,
                      events_out=None):  # analysis: session-append-seam
        """Under ``lock``, with the chunk already grouped by account
        (:func:`group_chunk`, before the lock): encode this chunk's
        events, compute every row's post-append window length and
        per-account event sequence number from the HOST index
        (batch-snapshot semantics: duplicate accounts in one chunk all
        see the chunk-start state), then commit the events to the index
        in row order. The caller dispatches the fused step — which
        applies the identical semantics to the device ring — before
        releasing the lock. ``events_out`` is where the events are encoded
        (``encode_events_host``'s ``out``: the launch's packed chunk), so
        the rows the device appends, the index commits and the audit
        hashes are one array. Session hashes are NOT computed here: the
        returned :class:`SessionChunkAudit` carries the snapshots and
        hashes lazily on the ledger writer thread.

        What the lock pays for is per chunk and per account that needs
        it, not per row: one index lookup per unique account; first
        events (a never-seen account, up to ``_TWIN_FIRST_ROWS`` rows of
        it in the chunk) share ONE allocation and ONE indexed copy, each
        account keeping its own block of it as its buffer; a seen
        account with room takes its rows in place; only an account that
        has to regrow or compact goes through ``append_rows``.

        Returns (events [B, EVENT_WIDTH] f32, occ [B] i32,
        post_len [B] i32, seqs [B] i64, audit)."""
        g = groups
        ids, uidx, occ, counts, first = g.ids, g.uidx, g.occ, g.counts, g.first
        b = uidx.shape[0]
        nu = len(ids)
        n_ev = self.n_events
        twin = self._twin
        # Chunk-start snapshot per unique account, read in columns: a
        # stable (buffer, count) reference into the append-only twin
        # buffer — no copy. A never-seen account reads as all zeros.
        utw = list(map(twin.get, ids))
        snaps = [_NO_HISTORY] * nu
        if utw.count(None) == nu:
            fresh, seen = range(nu), ()
            seq0 = np.zeros((b,), np.int64)
            dts = np.zeros((b,), np.float64)
        else:
            seen = [u for u, tw in enumerate(utw) if tw is not None]
            fresh = [u for u, tw in enumerate(utw) if tw is None]
            stw = [utw[u] for u in seen]
            bufs = [tw.buf for tw in stw]
            held = [tw.count for tw in stw]
            for u, snap in zip(seen, zip(bufs, held)):
                snaps[u] = snap
            useq = np.zeros((nu,), np.int64)
            ulast = np.zeros((nu,), np.float64)
            useq[seen] = [tw.seq for tw in stw]
            ulast[seen] = [tw.last_ts for tw in stw]
            seq0 = useq[uidx]
            dts = np.where(seq0 > 0, np.maximum(0.0, now - ulast[uidx]), 0.0)
        seqs = seq0 + occ + 1
        post_len = (np.minimum(seq0, n_ev - 1) + 1).astype(np.int32)
        events = encode_events_host(amounts, tx_codes, dts, out=events_out)
        audit = SessionChunkAudit(events, post_len, uidx, snaps)

        # Commit, rows of an account in chunk order (the device append
        # scatters the same rows at cursor+occ).
        grown = regrows = 0  # rows of capacity added; reallocations
        slow: list[tuple[_AcctSession, int]] = []
        if fresh:
            # First events in columns: block[j] is the first buffer of
            # the j-th never-seen account, never the chunk's ``events``
            # (which goes to the device and the audit).
            small = [u for u in fresh if counts[u] <= _TWIN_FIRST_ROWS]
            if small:
                slot = np.full((nu,), -1)
                slot[small] = np.arange(len(small))
                at = slot[uidx]
                rows = np.flatnonzero(at >= 0)
                block = np.empty((len(small), _TWIN_FIRST_ROWS, EVENT_WIDTH),
                                 dtype=np.float32)
                block[at[rows], occ[rows]] = events[rows]
                k = [counts[u] for u in small]
                twin.update(zip([ids[u] for u in small],
                                map(_AcctSession, block, k, k,
                                    itertools.repeat(now))))
                grown += len(small) * _TWIN_FIRST_ROWS
            if len(small) < len(fresh):
                for u in fresh:
                    if counts[u] > _TWIN_FIRST_ROWS:
                        tw = twin[ids[u]] = _AcctSession()
                        slow.append((tw, u))
        if seen:
            for u, tw, buf, c in zip(seen, stw, bufs, held):
                k = counts[u]
                end = c + k
                if end > len(buf):
                    slow.append((tw, u))
                    continue
                if k == 1:
                    buf[c] = events[first[u]]
                else:
                    buf[c:end] = events[g.rows_of(u)]
                tw.count = end
                tw.seq += k
                tw.last_ts = now
        for tw, u in slow:
            i = first[u]
            rows = (events[i:i + 1] if counts[u] == 1
                    else events[g.rows_of(u)])
            old = tw.append_rows(rows, n_ev, now)
            grown += len(tw.buf) - old
            regrows += old > 0
        self.twin_bytes += grown * EVENT_WIDTH * 4
        self.twin_regrows += regrows
        warm = int(np.count_nonzero(post_len >= self.min_events))
        cold = b - warm
        real = int(post_len.sum())
        self.appends += b
        self.warm_rows += warm
        self.cold_rows += cold
        self.head_real_positions += real
        self._export(warm, cold, 0, b, 0, regrows, real)
        return events, occ, post_len, seqs, audit

    def note_lock(self, waited_s: float, held_s: float) -> None:
        """What one chunk's dispatch waited for ``lock`` and then held it
        (the caller still holds it): the serial section of index-mode
        scoring, per chunk."""
        self.lock_wait_s += waited_s
        self.lock_held_s += held_s
        m = self._metrics
        if m is not None:
            m.session_lock_wait_seconds_total.inc(waited_s)
            m.session_lock_held_seconds_total.inc(held_s)

    def adopt(self, ring, cursor, length) -> None:  # analysis: session-append-seam
        """Rebind the donated-step outputs as the live ring state (the
        caller holds ``lock`` across dispatch + adopt so device order
        matches host-index order)."""
        self.session_ring = ring
        self.session_cursor = cursor
        self.session_length = length

    # -- test / debug helpers -------------------------------------------------

    def twin_window(self, account_id: str) -> np.ndarray:
        """The host index's current window for one account ([count, D]
        chronological copy) — the numpy twin tests compare the device
        ring against."""
        with self.lock:
            tw = self._twin.get(account_id)
            if tw is None:
                return np.zeros((0, EVENT_WIDTH), np.float32)
            return tw.window_view(self.n_events).copy()

    def twin_meta(self, account_id: str) -> dict:
        with self.lock:
            tw = self._twin.get(account_id)
            if tw is None:
                return {"count": 0, "seq": 0, "last_ts": 0.0}
            return {"count": min(tw.seq, self.n_events), "seq": tw.seq,
                    "last_ts": tw.last_ts}
