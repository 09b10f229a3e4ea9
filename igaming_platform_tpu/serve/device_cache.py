"""Device-resident HBM feature cache: ship indices + deltas, not rows.

Every bulk row-wire RPC ships a full `[N, 30]` float32 feature matrix
host->device for a step that leaves the chip almost idle. This is the
"keep hot state next to the accelerator, stream only the novel bytes"
pattern (arXiv:2109.09541, arXiv:2010.04804): the
per-ACCOUNT feature row lives in a device-resident table and the wire
carries only

- `int32` slot indices for cache hits (4 bytes/row vs 120),
- the per-transaction context as compact columns (amount f32, tx-type
  code i32) that the jitted step scatters into the gathered rows, and
- full rows only for misses/refreshes, folded into HBM by a jitted
  scatter (`apply_deltas`) BETWEEN scoring steps.

Semantics:

- the table holds the account-level base row exactly as the host
  feature store computed it at the last delta (`fill_row(acct, 0, "")`),
  so a cached gather is BIT-IDENTICAL to a host gather performed with
  the same `now` — pinned by tests/test_device_cache.py;
- `note_update()` marks an account dirty (the feature store calls it on
  every write-back); the next `lookup()` re-gathers dirty rows and
  scatters them in one `table.at[idxs].set(rows)` before the step, so
  scoring never reads a row older than the account's last event;
- time-derived features (TIME_SINCE_LAST_TX, SESSION_DURATION, velocity
  windows) are exact as of the last delta and drift with wall time
  between events — `max_age_s` bounds that drift by treating older rows
  as misses (docs/architecture.md, "The device-resident feature cache",
  has the staleness story);
- slot reclamation is CLOCK (second-chance): one reference bit per
  slot, a rotating hand, O(1) amortized per admission;
- `flags` is a per-slot sticky bool column (e.g. account-level block
  listing) OR'd into the per-request blacklist vector on device;
- on a multi-device mesh the TABLE is **slot-sharded** over the ``data``
  axis (parallel/state_sharding.py, STATE_SHARDING=1 default): each
  chip holds a contiguous ``capacity / K`` row block, the between-steps
  delta scatter lands each row only on its owning shard
  (``mode='drop'``) and the scoring-step gather runs an exact
  owner-select collective inside the same single dispatch — per-chip
  HBM is ~1/K and admissible slots scale with the mesh, which is the
  capacity half of the 100k-txns/s north star. Capacity rounds UP to a
  multiple of K; slot -> shard ownership is ``slot // (capacity // K)``
  so the host CLOCK index attributes every slot (per-shard occupancy
  gauges + /debug/cachez ride on that). STATE_SHARDING=0 (or a 1-wide
  data axis) keeps the old replicated layout.

Hit/miss/evict/occupancy counters export through obs.metrics
(`bind_metrics`); `stats()` returns the same numbers for tests.
"""

from __future__ import annotations

import threading
import time
from typing import Any

import numpy as np

from igaming_platform_tpu.core.features import NUM_FEATURES


class DeviceFeatureCache:
    """HBM-resident `[capacity, NUM_FEATURES]` account-feature table with
    a host-side `account_id -> slot` index and a delta-apply scatter."""

    def __init__(
        self,
        feature_store: Any,
        capacity: int = 65536,
        *,
        mesh=None,
        max_age_s: float | None = None,
        metrics: Any = None,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        import jax
        import jax.numpy as jnp

        from igaming_platform_tpu.parallel import state_sharding

        # Slot sharding (the capacity half of ROADMAP item 2): on a
        # mesh with a >1 ``data`` axis the table row-shards by slot;
        # capacity rounds up so every shard holds an equal block.
        self.plan = state_sharding.plan_for(mesh)
        if self.plan is not None:
            capacity = self.plan.round_capacity(int(capacity))
        self.capacity = int(capacity)
        self.features = feature_store
        self.max_age_s = max_age_s
        self._lock = threading.Lock()

        # Host-side slot index + CLOCK reclamation state.
        self._slots: dict[str, int] = {}
        self._slot_keys: list[str | None] = [None] * self.capacity
        self._ref = np.zeros(self.capacity, dtype=bool)
        self._row_ts = np.zeros(self.capacity, dtype=np.float64)
        self._hand = 0
        self._free = self.capacity  # slots never yet assigned
        self._dirty: set[str] = set()
        # Session-state admission hook (serve/session_state.py): called
        # under the lock with (account_ids, slots) for every slot THIS
        # lookup admitted, so the per-account session ring shares this
        # cache's admission/eviction decision — one CLOCK, two tables.
        self.session_hook = None

        # Counters (exported via bind_metrics / stats()).
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.deltas_applied = 0
        self._metrics = metrics
        # Per-shard occupancy (host-derived: the CLOCK index knows each
        # slot's owner — slot // rows_per_shard — so no device readback).
        # One bucket when unsharded, K when slot-sharded.
        self._n_shards = 1 if self.plan is None else self.plan.n_shards
        self._shard_rows = self.capacity // self._n_shards
        self._shard_occ = np.zeros(self._n_shards, dtype=np.int64)

        # The resident table: replicated on a mesh (each device gathers
        # its own batch shard locally), plain device arrays otherwise.
        table = jnp.zeros((self.capacity, NUM_FEATURES), dtype=jnp.float32)
        flags = jnp.zeros((self.capacity,), dtype=bool)
        scatter = lambda t, i, r: t.at[i].set(r)  # noqa: E731
        flag_set = lambda f, i, v: f.at[i].set(v)  # noqa: E731
        if self.plan is not None:
            # Slot-sharded layout: each device holds capacity/K rows;
            # the delta/flag scatters become shard_map programs that
            # land each row on its owning shard only.
            table = self.plan.place(table)
            flags = self.plan.place(flags)
            self._apply = state_sharding.make_sharded_scatter(self.plan, 2)
            self._apply_flags = state_sharding.make_sharded_scatter(self.plan, 1)
        elif mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            table = jax.device_put(table, repl)
            flags = jax.device_put(flags, repl)
            self._apply = jax.jit(
                scatter, in_shardings=(repl, repl, repl), out_shardings=repl
            )
            self._apply_flags = jax.jit(
                flag_set, in_shardings=(repl, repl, repl), out_shardings=repl
            )
        else:
            self._apply = jax.jit(scatter)
            self._apply_flags = jax.jit(flag_set)
        self.table = table
        self.flags = flags
        # Per-shard HBM budget is static (fixed shapes): f32 table rows
        # + bool flag column, per contiguous row block.
        self._hbm_per_shard = [
            self._shard_rows * (NUM_FEATURES * 4 + 1)
        ] * self._n_shards

    # -- metrics -------------------------------------------------------------

    def bind_metrics(self, metrics: Any) -> None:
        """Attach a ServiceMetrics (obs.metrics) sink; counters recorded
        so far are flushed into it immediately."""
        if metrics is self._metrics:
            return
        self._metrics = metrics
        with self._lock:
            self._export_metrics(self.hits, self.misses, self.evictions,
                                 self.deltas_applied)

    def _export_metrics(self, hits: int, misses: int, evicts: int, deltas: int) -> None:
        m = self._metrics
        if m is None:
            return
        if hits:
            m.feature_cache_hits_total.inc(hits)
        if misses:
            m.feature_cache_misses_total.inc(misses)
        if evicts:
            m.feature_cache_evictions_total.inc(evicts)
        if deltas:
            m.feature_cache_deltas_total.inc(deltas)
        m.feature_cache_occupancy.set(self.capacity - self._free)
        for s in range(self._n_shards):
            m.cache_shard_occupancy.set(int(self._shard_occ[s]), shard=str(s))
            m.hbm_bytes.set(self._hbm_per_shard[s], shard=str(s),
                            table="feature_cache")

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "deltas_applied": self.deltas_applied,
                "occupancy": self.capacity - self._free,
                "capacity": self.capacity,
                "shards": self._n_shards,
            }

    def shard_stats(self) -> dict:
        """Per-shard breakdown for /debug/cachez and the fleet view:
        slot ownership is host-derived (contiguous row blocks), HBM
        bytes are the static per-shard budget — what each chip actually
        holds, the number the mesh bench arm records."""
        with self._lock:
            return {
                "sharded": self.plan is not None,
                "shards": self._n_shards,
                "rows_per_shard": self._shard_rows,
                "occupancy": [int(v) for v in self._shard_occ],
                "hbm_bytes": list(self._hbm_per_shard),
            }

    # -- write-back hook -----------------------------------------------------

    def note_update(self, account_id: str) -> None:
        """Mark an account's cached row stale (feature-store write-back
        hook). O(1); the row is re-gathered and scattered on the next
        lookup — the compact per-account delta of the design."""
        with self._lock:
            if account_id in self._slots:
                self._dirty.add(account_id)

    def set_account_flag(self, account_id: str, value: bool = True) -> None:
        """Sticky per-account device flag (e.g. account-level block); OR'd
        into the per-request blacklist vector by the cached score step.
        The account is admitted if not resident."""
        import jax.numpy as jnp

        idxs = self.lookup([account_id])
        with self._lock:
            from igaming_platform_tpu.serve.scorer import _device_dispatch

            _device_dispatch("cache_flag_set", (1,), np.bool_)
            self.flags = self._apply_flags(
                self.flags, jnp.asarray(idxs), jnp.asarray([value]))

    # -- slot management -----------------------------------------------------

    def _assign_slot(self) -> int:
        """CLOCK second-chance reclamation; caller holds the lock."""
        if self._free > 0:
            # Cold start: hand over never-used slots in order.
            for _ in range(self.capacity):
                slot = self._hand
                self._hand = (self._hand + 1) % self.capacity
                if self._slot_keys[slot] is None:
                    self._free -= 1
                    # First residency of this slot; evictions reuse the
                    # same slot, so shard occupancy moves only here.
                    self._shard_occ[slot // self._shard_rows] += 1
                    return slot
        while True:
            slot = self._hand
            self._hand = (self._hand + 1) % self.capacity
            if self._ref[slot]:
                self._ref[slot] = False
                continue
            old = self._slot_keys[slot]
            if old is not None:
                del self._slots[old]
                self._dirty.discard(old)
                self.evictions += 1
            return slot

    def _gather_base_rows(self, ids: list[str], now: float) -> np.ndarray:
        """Host-gather the account-level base rows (amount=0, no tx type:
        the step overwrites the 4 context columns on device)."""
        k = len(ids)
        if hasattr(self.features, "gather_columns"):
            x, _ = self.features.gather_columns(ids, [0] * k, [""] * k, now=now)
            return np.ascontiguousarray(x, dtype=np.float32)
        x = np.zeros((k, NUM_FEATURES), dtype=np.float32)
        for i, a in enumerate(ids):
            self.features.fill_row(x[i], a, 0, "", now=now)
        return x

    # -- the hot path --------------------------------------------------------

    def lookup(self, account_ids, now: float | None = None) -> np.ndarray:
        """Resolve account ids -> `int32` slot indices, admitting misses
        and folding every pending delta (dirty rows + promotions) into
        HBM with ONE jitted scatter before returning — the between-steps
        delta-apply of the design. The returned indices are valid for
        the CURRENT `self.table`/`self.flags` snapshot."""
        import jax.numpy as jnp

        now = now or time.time()
        n = len(account_ids)
        idxs = np.empty((n,), dtype=np.int32)
        with self._lock:
            hits = misses = 0
            evicts_before = self.evictions
            refresh: dict[str, int] = {}
            admitted: dict[str, int] = {}
            stale_cut = None if self.max_age_s is None else now - self.max_age_s
            for i, raw in enumerate(account_ids):
                a = raw if isinstance(raw, str) else bytes(raw).decode()
                slot = self._slots.get(a)
                if slot is None:
                    slot = self._assign_slot()
                    self._slots[a] = slot
                    self._slot_keys[slot] = a
                    refresh[a] = slot
                    admitted[a] = slot
                    misses += 1
                elif a in self._dirty or (
                    stale_cut is not None and self._row_ts[slot] < stale_cut
                ):
                    # Resident slot, stale row: a HIT (no admission) plus
                    # a delta — deltas_applied carries the re-gather cost.
                    refresh[a] = slot
                    hits += 1
                else:
                    hits += 1
                self._ref[slot] = True
                idxs[i] = slot
            # Fold the WHOLE dirty set (not just this batch's rows): the
            # scatter is one device call either way, and it keeps every
            # resident row <= one event stale.
            for a in self._dirty:
                slot = self._slots.get(a)
                if slot is not None:
                    refresh[a] = slot
            self._dirty.clear()
            deltas = len(refresh)
            if deltas:
                ids = list(refresh)
                slots = np.fromiter(refresh.values(), np.int32, deltas)
                rows = self._gather_base_rows(ids, now)
                # A real jit launch in the between-steps window: count it
                # at the honest dispatch seam (fires only when deltas /
                # admissions are pending, never in steady state).
                from igaming_platform_tpu.serve.scorer import _device_dispatch

                _device_dispatch("cache_apply_deltas", rows.shape, rows.dtype)
                self.table = self._apply(
                    self.table, jnp.asarray(slots), jnp.asarray(rows))
                self._row_ts[slots] = now
                self.deltas_applied += deltas
            if admitted and self.session_hook is not None:
                # Same admission, second table: the session ring syncs
                # (rehydrates) the freshly admitted slots in this same
                # between-steps window — an evicted slot that comes back
                # gets its window back before the next fused step reads it.
                self.session_hook(list(admitted), list(admitted.values()))
            self.hits += hits
            self.misses += misses
            self._export_metrics(
                hits, misses, self.evictions - evicts_before, deltas)
        return idxs

    def contains(self, account_id: str) -> bool:
        with self._lock:
            return account_id in self._slots
