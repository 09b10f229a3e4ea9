"""ctypes bindings for the native (C++) feature store.

`NativeFeatureStore` mirrors the semantic core of
serve.feature_store.InMemoryFeatureStore (sliding windows, HLL
cardinalities, TTL'd sums, sessions, batch aggregates) with the per-event
update and the [B, 30] gather executed in C++ — the host-side hot path of
the ingest bridge (SURVEY.md §2.2 "native ingest bridge"). Built on
demand with g++ for this host (serve/native_build.py);
``native_available()`` is False when the toolchain is missing, and the
caller decides whether that is fatal (it is on an accelerator boot).

String account ids map to dense indices here; device/IP strings hash to
stable 64-bit values (blake2b, matching serve.hll).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import time

import numpy as np

from igaming_platform_tpu.core.features import F, NUM_FEATURES

_TX_TYPE_CODES = {"deposit": 0, "withdraw": 1, "bet": 2, "win": 3}

# Accounts a store holds unless its caller sizes it: 4.2 KB each,
# allocated and touched eagerly (4.48 GB).
DEFAULT_MAX_ACCOUNTS = 1_000_000

_hash_cache: dict[str, int] = {}


def _hash64(value: str) -> int:
    if not value:
        return 0
    h = _hash_cache.get(value)
    if h is None:
        h = int.from_bytes(hashlib.blake2b(value.encode(), digest_size=8).digest(), "little")
        h = h or 1  # 0 means "absent" on the C side
        if len(_hash_cache) < 1_000_000:
            _hash_cache[value] = h
    return h


def _load_lib():
    from igaming_platform_tpu.serve.native_build import ensure_built

    lib_dir = ensure_built()
    if lib_dir is None:
        return None
    return _bind(ctypes.CDLL(os.path.join(lib_dir, "libfeature_store.so")))


def _bind(lib):
    lib.fs_create.restype = ctypes.c_void_p
    lib.fs_create.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.fs_destroy.argtypes = [ctypes.c_void_p]
    lib.fs_capacity.restype = ctypes.c_int
    lib.fs_capacity.argtypes = [ctypes.c_void_p]
    lib.fs_update.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_double, ctypes.c_int64,
        ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
    ]
    lib.fs_update_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS"),
    ]
    lib.fs_record_bonus.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_float]
    lib.fs_load_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_double,
    ]
    lib.fs_velocity.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_double, ctypes.POINTER(ctypes.c_int)
    ]
    lib.fs_fill_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_double,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
    ]
    lib.fs_resolve.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.fs_num_accounts.restype = ctypes.c_int
    lib.fs_num_accounts.argtypes = [ctypes.c_void_p]
    lib.fs_blacklist_add.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int32
    ]
    lib.fs_wire_count.restype = ctypes.c_int64
    lib.fs_wire_count.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.fs_decode_gather.restype = ctypes.c_int64
    lib.fs_decode_gather.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_double,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        ctypes.c_int,
    ]
    return lib


_lib = None
_lib_attempted = False


def native_available() -> bool:
    global _lib, _lib_attempted
    if not _lib_attempted:
        _lib_attempted = True
        _lib = _load_lib()
    return _lib is not None


class NativeFeatureStore:
    """C++-backed feature store with the InMemoryFeatureStore interface."""

    def __init__(self, max_accounts: int = DEFAULT_MAX_ACCOUNTS, history_capacity: int = 128,
                 hll_precision: int = 10):
        if not native_available():
            raise RuntimeError("native feature store unavailable (g++ build failed)")
        self._lib = _lib
        self._handle = self._lib.fs_create(max_accounts, history_capacity, hll_precision)
        self._max_accounts = max_accounts
        # Python mirror for the string check_blacklist() API; the native
        # sets (fs_blacklist_add) are the ones the wire decoder consults.
        self._blacklists: dict[str, set[str]] = {"device": set(), "ip": set(), "fingerprint": set()}
        self._bl_codes = {"device": 0, "ip": 1, "fingerprint": 2}
        # Device-cache delta hook (see InMemoryFeatureStore.delta_listener).
        self.delta_listener = None

    def _emit_delta(self, account_id: str) -> None:
        if self.delta_listener is not None:
            self.delta_listener(account_id)

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.fs_destroy(handle)
            self._handle = None

    def _resolve_many(self, account_ids, create: bool = True) -> np.ndarray:
        """Batch string→index resolution in ONE native call. The id map
        lives in C++ (single source of truth) so the native wire decoder
        and this path can never disagree on an account's index."""
        n = len(account_ids)
        encoded = [a.encode() if isinstance(a, str) else bytes(a) for a in account_ids]
        offs = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(e) for e in encoded], out=offs[1:])
        buf = b"".join(encoded)
        out = np.empty(n, dtype=np.int32)
        self._lib.fs_resolve(self._handle, n, buf, offs, 1 if create else 0, out)
        return out

    def _idx(self, account_id: str, create: bool = True) -> int:
        return int(self._resolve_many([account_id], create)[0])

    # -- writes -------------------------------------------------------------

    def update(self, event) -> None:
        idx = self._idx(event.account_id)
        if idx < 0:
            return
        ts = event.timestamp or time.time()
        self._lib.fs_update(
            self._handle, idx, ts, int(event.amount),
            _TX_TYPE_CODES.get(event.tx_type, 4),
            _hash64(event.device_id), _hash64(event.ip),
        )
        self._emit_delta(event.account_id)

    def update_batch(self, events) -> None:
        """Batched ingest: one native call for a whole event chunk."""
        events = list(events)
        n = len(events)
        if n == 0:
            return
        now = time.time()
        idxs = self._resolve_many([e.account_id for e in events])
        ts = np.empty(n, np.float64)
        amounts = np.empty(n, np.int64)
        types = np.empty(n, np.int32)
        dev = np.empty(n, np.uint64)
        ips = np.empty(n, np.uint64)
        for i, e in enumerate(events):
            ts[i] = e.timestamp or now
            amounts[i] = int(e.amount)
            types[i] = _TX_TYPE_CODES.get(e.tx_type, 4)
            dev[i] = _hash64(e.device_id)
            ips[i] = _hash64(e.ip)
        self._lib.fs_update_batch(self._handle, n, idxs, ts, amounts, types, dev, ips)
        if self.delta_listener is not None:
            for e in events:
                self._emit_delta(e.account_id)

    def load_batch_features(
        self, account_id: str, *,
        total_deposits: int = 0, total_withdrawals: int = 0,
        deposit_count: int = 0, withdraw_count: int = 0,
        total_bets: int = 0, total_wins: int = 0,
        bet_count: int = 0, win_count: int = 0,
        bonus_claim_count: int | None = None,
        created_at: float | None = None,
    ) -> None:
        """Bulk-overwrite batch aggregates (serve/batch_refresh.py sink)."""
        self._lib.fs_load_batch(
            self._handle, self._idx(account_id),
            total_deposits, total_withdrawals, deposit_count, withdraw_count,
            total_bets, total_wins, bet_count, win_count,
            -1 if bonus_claim_count is None else bonus_claim_count,
            -1.0 if created_at is None else created_at,
        )
        self._emit_delta(account_id)

    def record_bonus_claim(self, account_id: str, wager_complete_rate: float | None = None) -> None:
        idx = self._idx(account_id)
        if idx >= 0:
            rate = -1.0 if wager_complete_rate is None else float(wager_complete_rate)
            self._lib.fs_record_bonus(self._handle, idx, rate)
            self._emit_delta(account_id)

    # -- reads --------------------------------------------------------------

    def velocity(self, account_id: str, now: float | None = None) -> tuple[int, int, int]:
        idx = self._idx(account_id, create=False)
        if idx < 0:
            return (0, 0, 0)
        out = (ctypes.c_int * 3)()
        self._lib.fs_velocity(self._handle, idx, now or time.time(), out)
        return (out[0], out[1], out[2])

    def check_rate_limit(self, account_id: str, max_per_min: int, max_per_hour: int) -> bool:
        c1, _, ch = self.velocity(account_id)
        return c1 >= max_per_min or ch >= max_per_hour

    # -- blacklist (host-side sets; set membership isn't the hot path) ------

    def add_to_blacklist(self, list_type: str, value: str) -> None:
        if list_type not in self._blacklists:
            raise ValueError(f"unknown blacklist type: {list_type}")
        self._blacklists[list_type].add(value)
        raw = value.encode()
        self._lib.fs_blacklist_add(self._handle, self._bl_codes[list_type], raw, len(raw))

    def check_blacklist(self, device_id: str = "", fingerprint: str = "", ip: str = "") -> bool:
        return (
            (bool(device_id) and device_id in self._blacklists["device"])
            or (bool(fingerprint) and fingerprint in self._blacklists["fingerprint"])
            or (bool(ip) and ip in self._blacklists["ip"])
        )

    # -- batch assembly ------------------------------------------------------

    def fill_row(self, out: np.ndarray, account_id: str, amount: int, tx_type: str,
                 now: float | None = None) -> None:
        rows = np.zeros((1, NUM_FEATURES), dtype=np.float32)
        self._fill(rows, [account_id], [amount], [tx_type], now)
        out[:] = rows[0]

    def _fill(self, out: np.ndarray, account_ids, amounts, tx_types, now=None) -> None:
        n = out.shape[0]
        idxs = self._resolve_many(account_ids, create=False)
        amts = np.asarray(amounts, dtype=np.int64)
        types = np.fromiter((_TX_TYPE_CODES.get(t, 4) for t in tx_types), np.int32, n)
        self._lib.fs_fill_rows(self._handle, n, idxs, amts, types, now or time.time(), out)

    def gather_batch(self, requests, now: float | None = None):
        from igaming_platform_tpu.serve import chaos

        chaos.fire("feature_store.gather")
        reqs = list(requests)
        x = np.zeros((len(reqs), NUM_FEATURES), dtype=np.float32)
        self._fill(
            x,
            [r.account_id for r in reqs],
            [r.amount for r in reqs],
            [r.tx_type for r in reqs],
            now,
        )
        bl = np.zeros((len(reqs),), dtype=bool)
        for i, r in enumerate(reqs):
            ip_flags = getattr(r, "ip_flags", None)
            if ip_flags is not None:
                x[i, F.IS_VPN] = float(ip_flags[0])
                x[i, F.IS_PROXY] = float(ip_flags[1])
                x[i, F.IS_TOR] = float(ip_flags[2])
            bl[i] = self.check_blacklist(
                getattr(r, "device_id", ""), getattr(r, "fingerprint", ""), getattr(r, "ip", "")
            )
        return x, bl

    # -- columnar fast path (replay/ingest: no per-row request objects) ------

    def gather_columns(self, account_ids, amounts, tx_types,
                       ips=None, devices=None, fingerprints=None,
                       now: float | None = None):
        """[B,30] gather straight from parallel columns — the per-row
        ScoreRequest objects of gather_batch() skipped entirely. The
        blacklist check covers the same three keys as check_blacklist
        (device / fingerprint / ip, redis_store.go:267-293)."""
        from igaming_platform_tpu.serve import chaos

        chaos.fire("feature_store.gather")
        n = len(account_ids)
        x = np.zeros((n, NUM_FEATURES), dtype=np.float32)
        self._fill(x, account_ids, amounts, tx_types, now)
        bl = np.zeros((n,), dtype=bool)
        if any(self._blacklists.values()):
            dev_bl = self._blacklists["device"]
            ip_bl = self._blacklists["ip"]
            fp_bl = self._blacklists["fingerprint"]
            for i in range(n):
                d = devices[i] if devices is not None else ""
                p = ips[i] if ips is not None else ""
                f = fingerprints[i] if fingerprints is not None else ""
                bl[i] = (
                    (bool(d) and d in dev_bl)
                    or (bool(f) and f in fp_bl)
                    or (bool(p) and p in ip_bl)
                )
        return x, bl

    def update_columns(self, account_ids, amounts, tx_types, ips, devices, timestamps) -> None:
        """Batched ingest from parallel columns: one native call."""
        n = len(account_ids)
        if n == 0:
            return
        idxs = self._resolve_many(account_ids)
        # Same `timestamp or now` fallback as update()/update_batch(): an
        # unset (zero) event timestamp must not land at epoch 0, where every
        # sliding window would exclude it.
        ts = np.asarray(timestamps, dtype=np.float64)
        if (ts == 0).any():
            ts = np.where(ts == 0, time.time(), ts)
        amts = np.fromiter(amounts, np.int64, n)
        types = np.fromiter((_TX_TYPE_CODES.get(t, 4) for t in tx_types), np.int32, n)
        dev = np.fromiter((_hash64(d) for d in devices), np.uint64, n)
        ip = np.fromiter((_hash64(i) for i in ips), np.uint64, n)
        self._lib.fs_update_batch(self._handle, n, idxs, ts, amts, types, dev, ip)
        if self.delta_listener is not None:
            for a in account_ids:
                self._emit_delta(a)

    def num_accounts(self) -> int:
        return int(self._lib.fs_num_accounts(self._handle))

    # -- native wire decode (ScoreBatchRequest bytes -> gather matrix) -------

    def decode_gather(
        self, payload: bytes, now: float | None = None, create: bool = False
    ) -> tuple[np.ndarray, np.ndarray]:
        """One-call request decode + feature gather: risk.v1
        ScoreBatchRequest wire bytes -> ([N,30] float32, [N] bool
        blacklist). The per-RPC host path the VERDICT r02 profile asked
        for — no Python protobuf parse, no per-row host objects
        (counterpart of the per-request decode grpc-go does for
        proto/risk/v1/risk.proto:34-58)."""
        from igaming_platform_tpu.serve import chaos

        chaos.fire("feature_store.gather")
        n = self._lib.fs_wire_count(payload, len(payload))
        if n < 0:
            raise ValueError("malformed ScoreBatchRequest")
        x = np.zeros((int(n), NUM_FEATURES), dtype=np.float32)
        bl = np.zeros((int(n),), dtype=np.uint8)
        if n == 0:
            return x, bl.astype(bool)
        rc = self._lib.fs_decode_gather(
            self._handle, payload, len(payload), now or time.time(),
            int(n), x, bl, 1 if create else 0,
        )
        if rc < 0:
            raise ValueError(f"malformed ScoreBatchRequest (rc={rc})")
        return x[:rc], bl[:rc].astype(bool)


def best_feature_store(max_accounts: int = DEFAULT_MAX_ACCOUNTS, **kwargs):
    """Native store (of ``max_accounts``) when the toolchain allows,
    Python store otherwise."""
    if native_available():
        try:
            return NativeFeatureStore(max_accounts=max_accounts)
        except RuntimeError:
            pass
    from igaming_platform_tpu.serve.feature_store import InMemoryFeatureStore

    return InMemoryFeatureStore(**kwargs)
