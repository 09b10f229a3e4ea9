"""Native wire encoding for the serving hot path.

``encode_score_batch`` serializes a whole risk.v1.ScoreBatchResponse from
the device result arrays in one C++ call (native/wire_codec.cpp) —
replacing per-row Python proto construction, which dominates the host cost
at wire-path throughput (the per-row response struct of engine.go:56-64,
built once per transaction, re-designed as one batch encode).

``RawProtoMessage`` lets a gRPC handler return pre-serialized bytes
through the normal serializer seam; byte-parity with the Python
protobuf serializer is pinned in tests/test_wire_codec.py.

``native_wire_available()`` is False when the library cannot be built
for this host (serve/native_build.py) — callers keep the per-row path in
that case.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading

import numpy as np

from igaming_platform_tpu.core.enums import REASON_BIT_ORDER

_build_lock = threading.Lock()
_lib = None
_load_failed = False

# Reason-code string table in bit order, concatenated + offsets — the C
# encoder expands the in-graph bitmask to repeated string fields directly.
_REASONS_BUF = b"".join(code.value.encode() for code in REASON_BIT_ORDER)
_REASONS_OFF = np.zeros((len(REASON_BIT_ORDER) + 1,), dtype=np.int32)
np.cumsum(
    [len(code.value.encode()) for code in REASON_BIT_ORDER], out=_REASONS_OFF[1:]
)


def _load():
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _build_lock:
        if _lib is not None or _load_failed:
            return _lib
        from igaming_platform_tpu.serve.native_build import ensure_built

        lib_dir = ensure_built()
        if lib_dir is None:
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(os.path.join(lib_dir, "libwire_codec.so"))
            lib.encode_score_batch.restype = ctypes.c_int64
            lib.encode_score_batch.argtypes = [
                ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int32),  # score
                ctypes.POINTER(ctypes.c_int32),  # action
                ctypes.POINTER(ctypes.c_int32),  # reason_mask
                ctypes.POINTER(ctypes.c_int32),  # rule_score
                ctypes.POINTER(ctypes.c_float),  # ml_score
                ctypes.POINTER(ctypes.c_int64),  # rtms
                ctypes.c_void_p,                 # features (nullable)
                ctypes.c_char_p,                 # reasons_buf
                ctypes.POINTER(ctypes.c_int32),  # reasons_off
                ctypes.c_int32,                  # n_reasons
                ctypes.POINTER(ctypes.c_uint8),  # out
                ctypes.c_int64,                  # out_cap
            ]
            _lib = lib
        except (OSError, AttributeError):  # unloadable library => per-row path
            logging.getLogger(__name__).warning(
                "native wire codec did not load", exc_info=True)
            _load_failed = True
    return _lib


def native_wire_available() -> bool:
    return _load() is not None


class RawProtoMessage:
    """Pre-serialized proto bytes behind the SerializeToString seam."""

    __slots__ = ("_payload",)

    def __init__(self, payload: bytes):
        self._payload = payload

    def SerializeToString(self, deterministic: bool = False) -> bytes:  # noqa: N802
        return self._payload


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


# ---------------------------------------------------------------------------
# Index-mode ScoreBatch frame (device-resident feature cache, ISSUE 1)
# ---------------------------------------------------------------------------
#
# A compact columnar alternative to the risk.v1 ScoreBatchRequest proto,
# carried through the SAME raw-bytes ScoreBatch seam (the server's generic
# handler hands the handler wire bytes; a 4-byte magic distinguishes the
# frame from a proto, whose first byte is always the field-1 tag 0x0A).
# Steady state the server resolves account ids against the HBM-resident
# feature table and ships only int32 slot indices + per-txn context to the
# device — no [N, 30] float32 feature matrix ever crosses the link. The
# RESPONSE stays a byte-exact risk.v1 ScoreBatchResponse (feature echo
# omitted — the cached path never materializes rows on the host), so the
# risk.v1 surface remains wire-compatible and proto clients are untouched.
#
# Layout (little-endian):
#   b"IDX1" | u32 n
#   i64 amounts[n]
#   u8  tx_type_codes[n]       (deposit=0 withdraw=1 bet=2 win=3 other=4)
#   4 string columns — account_id, ip, device_id, fingerprint — each:
#     u8 present; if present: u32 offs[n+1] (cumulative) | blob bytes

INDEX_WIRE_MAGIC = b"IDX1"

TX_TYPE_CODES = {"deposit": 0, "withdraw": 1, "bet": 2, "win": 3}
TX_TYPE_NAMES = ("deposit", "withdraw", "bet", "win", "")


def _encode_str_column(values, n: int) -> bytes:
    if values is None:
        return b"\x00"
    if len(values) != n:
        raise ValueError(f"column length {len(values)} != {n} rows")
    encoded = [v.encode() if isinstance(v, str) else bytes(v) for v in values]
    offs = np.zeros((n + 1,), dtype=np.uint32)
    np.cumsum([len(e) for e in encoded], out=offs[1:])
    return b"\x01" + offs.tobytes() + b"".join(encoded)


def encode_index_batch(
    account_ids,
    amounts,
    tx_types,
    ips=None,
    devices=None,
    fingerprints=None,
) -> bytes:
    """Serialize an index-mode ScoreBatch frame (client side / load gen)."""
    import struct as _struct

    n = len(account_ids)
    amounts_arr = np.ascontiguousarray(amounts, dtype=np.int64)
    if amounts_arr.shape != (n,):
        raise ValueError(f"amounts shape {amounts_arr.shape} != ({n},)")
    codes = np.fromiter(
        (TX_TYPE_CODES.get(t, 4) for t in tx_types), np.uint8, n)
    parts = [
        INDEX_WIRE_MAGIC,
        _struct.pack("<I", n),
        amounts_arr.tobytes(),
        codes.tobytes(),
        _encode_str_column(account_ids, n),
        _encode_str_column(ips, n),
        _encode_str_column(devices, n),
        _encode_str_column(fingerprints, n),
    ]
    return b"".join(parts)


def _decode_str_column(payload: memoryview, pos: int, n: int):
    if pos + 1 > len(payload):
        raise ValueError("index frame truncated (column flag)")
    present = payload[pos]
    pos += 1
    if present == 0:
        return None, pos
    if present != 1:
        raise ValueError(f"bad column flag {present}")
    end_offs = pos + 4 * (n + 1)
    if end_offs > len(payload):
        raise ValueError("index frame truncated (offsets)")
    offs = np.frombuffer(payload[pos:end_offs], dtype=np.uint32)
    if n and (np.diff(offs.astype(np.int64)) < 0).any():
        raise ValueError("index frame offsets not monotonic")
    blob_len = int(offs[-1])
    pos = end_offs
    if pos + blob_len > len(payload):
        raise ValueError("index frame truncated (blob)")
    blob = payload[pos:pos + blob_len]
    values = [bytes(blob[offs[i]:offs[i + 1]]) for i in range(n)]
    return values, pos + blob_len


def decode_index_batch(payload: bytes):
    """Parse an index-mode frame -> (account_ids: list[bytes],
    amounts i64[n], tx_type_codes u8[n], ips, devices, fingerprints)
    where the last three are list[bytes] or None. Raises ValueError on a
    malformed frame."""
    import struct as _struct

    mv = memoryview(payload)
    if len(mv) < 8 or bytes(mv[:4]) != INDEX_WIRE_MAGIC:
        raise ValueError("not an index-mode frame")
    (n,) = _struct.unpack_from("<I", payload, 4)
    pos = 8
    end = pos + 8 * n
    if end + n > len(mv):
        raise ValueError("index frame truncated (numeric columns)")
    amounts = np.frombuffer(mv[pos:end], dtype=np.int64)
    pos = end
    codes = np.frombuffer(mv[pos:pos + n], dtype=np.uint8)
    pos += n
    ids, pos = _decode_str_column(mv, pos, n)
    if ids is None:
        raise ValueError("index frame missing account_id column")
    ips, pos = _decode_str_column(mv, pos, n)
    devices, pos = _decode_str_column(mv, pos, n)
    fingerprints, pos = _decode_str_column(mv, pos, n)
    return ids, amounts, codes, ips, devices, fingerprints


def encode_score_batch(
    score: np.ndarray,
    action: np.ndarray,
    reason_mask: np.ndarray,
    rule_score: np.ndarray,
    ml_score: np.ndarray,
    response_time_ms: np.ndarray,
    features: np.ndarray | None,
) -> bytes:
    """Serialize a ScoreBatchResponse from result arrays (one C call).

    ``features`` is the raw [N, 30] gather matrix (first 26 columns mirror
    the wire FeatureVector) or None to omit the echo.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native wire codec unavailable")
    n = int(score.shape[0])
    score = np.ascontiguousarray(score, dtype=np.int32)
    action = np.ascontiguousarray(action, dtype=np.int32)
    reason_mask = np.ascontiguousarray(reason_mask, dtype=np.int32)
    rule_score = np.ascontiguousarray(rule_score, dtype=np.int32)
    ml_score = np.ascontiguousarray(ml_score, dtype=np.float32)
    rtms = np.ascontiguousarray(response_time_ms, dtype=np.int64)
    if features is not None:
        features = np.ascontiguousarray(features, dtype=np.float32)
        feat_ptr = features.ctypes.data_as(ctypes.c_void_p)
    else:
        feat_ptr = ctypes.c_void_p(0)

    # First try with a generous estimate; on -needed, retry exact.
    cap = 64 * n + 256 * (1 if features is not None else 0) * n + 1024
    buf = ctypes.create_string_buffer(cap)
    written = lib.encode_score_batch(
        n, _i32(score), _i32(action), _i32(reason_mask), _i32(rule_score),
        ml_score.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        rtms.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        feat_ptr, _REASONS_BUF, _i32(_REASONS_OFF), len(REASON_BIT_ORDER),
        ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8)), cap,
    )
    if written < 0:
        cap = -written
        buf = ctypes.create_string_buffer(cap)
        written = lib.encode_score_batch(
            n, _i32(score), _i32(action), _i32(reason_mask), _i32(rule_score),
            ml_score.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            rtms.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            feat_ptr, _REASONS_BUF, _i32(_REASONS_OFF), len(REASON_BIT_ORDER),
            ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8)), cap,
        )
    if written < 0:
        raise RuntimeError("wire codec sizing failed")
    return buf.raw[:written]
