"""The one general traffic generator: a mix file plus ``--seed`` in,
pre-built RPC payloads and the check sequence out.

A mix is data (``chipbench/traffic/<mix>.json``): the closed loop's
clients, RPC kind and row counts, the account, tx-type and amount
distributions. Every seed gets the same *set* of frame sizes in another
order, so the work of a run does not depend on the seed; which accounts,
amounts and types fill the frames does.

The risk.v1 wire forms are written here and not imported from the
program: the index-mode frame (``IDX1``; copied from
``serve/wire.encode_index_batch``) and the ``ScoreBatchRequest`` /
``ScoreBatchResponse`` protos (``proto/risk/v1/risk.proto``).
"""

from __future__ import annotations

import struct

import numpy as np

TX_TYPES = ("deposit", "withdraw", "bet", "win")  # wire codes 0..3
INDEX_WIRE_MAGIC = b"IDX1"


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); ``seed`` may exceed
    32 bits."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & (2**64 - 1), tag])


def account_ids(n: int, seed: int) -> list[str]:
    """``n`` distinct account ids; the text carries the seed so two seeds
    never share an id."""
    tag = f"{int(seed) & 0xFFFFFF:06x}"
    return [f"p{tag}-{i:07d}" for i in range(n)]


class Population:
    """Accounts ranked by popularity: rank 0 is the hottest. ``perm`` maps
    rank -> account index through a seeded permutation, so hot accounts
    are scattered over the id space (and over cache slots)."""

    def __init__(self, mix: dict, resident: int, seed: int):
        spec = mix["accounts"]
        if spec["distribution"] != "zipf":
            raise ValueError(f"unknown account distribution {spec}")
        self.n = int(resident)
        self.ids = account_ids(self.n, seed)
        self.perm = rng_for(seed, "perm").permutation(self.n)
        self._rank = None
        weights = np.arange(1, self.n + 1, dtype=np.float64) ** -float(
            spec["exponent"])
        self._cdf = np.cumsum(weights)
        self._cdf /= self._cdf[-1]

    def draw_ranks(self, rng: np.random.Generator, k: int) -> np.ndarray:
        return np.searchsorted(self._cdf, rng.random(k), side="right").clip(
            0, self.n - 1)

    def id_of_rank(self, rank: int) -> str:
        return self.ids[int(self.perm[int(rank)])]

    def rank_of_id(self, account_id: str) -> int:
        """The popularity rank of one of ``ids`` (the inverse of
        ``id_of_rank``; the permutation's inverse is made when first asked
        for)."""
        if self._rank is None:
            self._rank = np.empty(self.n, np.int64)
            self._rank[self.perm] = np.arange(self.n)
        return int(self._rank[int(account_id.rsplit("-", 1)[1])])


def draw_context(mix: dict, rng: np.random.Generator, k: int):
    """(amounts int64 cents, tx-type codes uint8) for ``k`` rows."""
    a = mix["amounts"]
    if a["distribution"] != "lognormal":
        raise ValueError(f"unknown amount distribution {a}")
    amounts = _clip_amounts(
        a, np.exp(rng.normal(np.log(a["median_cents"]), a["sigma"], k)))
    codes, p = _type_codes(mix)
    types = codes[rng.choice(len(codes), size=k, p=p)]
    return amounts, types


def _clip_amounts(a: dict, amounts: np.ndarray) -> np.ndarray:
    return np.clip(np.rint(amounts), a["min_cents"], a["max_cents"]).astype(
        np.int64)


def _type_codes(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """The mix's tx types as wire codes, and the share of each."""
    names = list(mix["tx_types"])
    p = np.array([mix["tx_types"][t] for t in names], np.float64)
    return np.array([TX_TYPES.index(t) for t in names], np.uint8), p / p.sum()


# -- histories: what a resident account had sent before the run ---------------

# The last round of every history arrives at this instant, five minutes
# before the earliest clock the output check puts on an RPC
# (``check_sequence``: 1,800,000,000 s and up to a day more): the first
# checked event of an account then has a gap of minutes to a day behind
# it, one a player sends and the head has not saturated on (a gap of years
# pins its probability: PERF.md, PR 56). The timed window runs on the wall
# clock, which is earlier until January 2027: there an account's first
# event reads no gap, as a never-seen account's does in every cell.
HISTORY_END = 1_799_999_700.0


def history_spec(value) -> dict | None:
    """A configuration's ``session_events_preloaded``: 0 (every window
    starts empty) gives ``None``; ``{"events": "<low>-<high>", "rounds":
    R}`` gives ``{"low", "high", "rounds"}``. ``ValueError`` for any other
    shape."""
    if isinstance(value, int) and not isinstance(value, bool) and value == 0:
        return None
    if not (isinstance(value, dict) and set(value) == {"events", "rounds"}):
        raise ValueError("session_events_preloaded is 0 or "
                         '{"events": "<low>-<high>", "rounds": R}')
    parts = str(value["events"]).split("-")
    rounds = value["rounds"]
    if (len(parts) != 2 or not all(p.isdigit() for p in parts)
            or isinstance(rounds, bool) or not isinstance(rounds, int)):
        raise ValueError('session_events_preloaded: events is "<low>-<high>" '
                         "and rounds a whole number")
    low, high = int(parts[0]), int(parts[1])
    if not (0 <= low <= high and 1 <= high <= 65536 and 1 <= rounds <= 64):
        raise ValueError("session_events_preloaded: 0 <= low <= high, "
                         "1 <= high <= 65536 and 1 <= rounds <= 64")
    return {"low": low, "high": high, "rounds": rounds}


def history_clocks(seed: int, rounds: int) -> np.ndarray:
    """The arrival time of each of the ``rounds`` rounds, oldest first: a
    seeded gap of 20 s to 15 min apart, the last at ``HISTORY_END``."""
    gaps = rng_for(seed, "histclk").uniform(20.0, 900.0, max(rounds - 1, 0))
    return HISTORY_END - np.concatenate([np.cumsum(gaps[::-1])[::-1], [0.0]])


def histories(mix: dict, seed: int, first_rank: int, n: int, spec: dict) -> dict:
    """The histories of the accounts of rank ``first_rank .. first_rank +
    n - 1``, oldest event first, account after account: ``counts`` [n]
    (``k_r``, uniform on ``[low, high]``), and per event ``account`` (0 ..
    n - 1), ``amounts`` (int64 cents) and ``types`` (wire codes) from the
    mix's own distributions, and ``round``, the round it arrives in: an
    account's events fall into ``rounds`` equal shares, the newest share
    in the last round.

    Deterministic in ``(mix, seed, rank)`` and computable for one account
    without the others: rank ``r`` owns draws ``r * (1 + 3 * high)``
    onwards of the one stream ``rng_for(seed, "history")`` (one for
    ``k_r``, three an event), which the generator reaches by ``advance``.
    So the fill asks for a chunk of accounts and the reference for one,
    and both read the same events."""
    low, high, rounds = spec["low"], spec["high"], spec["rounds"]
    per = 1 + 3 * high
    rng = rng_for(seed, "history")
    rng.bit_generator.advance(int(first_rank) * per)
    u = rng.random((int(n), per))
    counts = np.minimum(low + (u[:, 0] * (high - low + 1)).astype(np.int64), high)
    event = np.arange(high)[None, :]
    keep = event < counts[:, None]
    account, index = np.nonzero(keep)
    u1, u2, u3 = (u[:, 1 + j::3][keep] for j in range(3))
    # Box-Muller: one standard normal from two of the event's draws
    z = np.sqrt(-2.0 * np.log1p(-u1)) * np.cos(2.0 * np.pi * u2)
    a = mix["amounts"]
    if a["distribution"] != "lognormal":
        raise ValueError(f"unknown amount distribution {a}")
    amounts = _clip_amounts(a, np.exp(np.log(a["median_cents"]) + a["sigma"] * z))
    codes, p = _type_codes(mix)
    types = codes[np.searchsorted(np.cumsum(p), u3, side="right").clip(
        0, len(codes) - 1)]
    k = counts[account]
    return {"counts": counts, "account": account, "amounts": amounts,
            "types": types,
            "round": rounds - 1 - ((k - 1 - index) * rounds) // k}


def history_of(mix: dict, seed: int, rank: int, spec: dict) -> dict:
    """One account's history: ``amounts``, ``types`` and each event's
    arrival ``clocks``, oldest first."""
    h = histories(mix, seed, rank, 1, spec)
    return {"amounts": h["amounts"], "types": h["types"],
            "clocks": history_clocks(seed, spec["rounds"])[h["round"]]}


# -- wire forms --------------------------------------------------------------


def _str_column(values: list[bytes]) -> bytes:
    offs = np.zeros((len(values) + 1,), dtype=np.uint32)
    np.cumsum([len(v) for v in values], out=offs[1:])
    return b"\x01" + offs.tobytes() + b"".join(values)


def encode_index_frame(ids: list[str], amounts, types) -> bytes:
    """Index-mode ScoreBatch frame: magic, row count, int64 amounts, uint8
    type codes, the account-id column, and three absent columns (ip,
    device, fingerprint)."""
    n = len(ids)
    return b"".join([
        INDEX_WIRE_MAGIC, struct.pack("<I", n),
        np.ascontiguousarray(amounts, dtype=np.int64).tobytes(),
        np.ascontiguousarray(types, dtype=np.uint8).tobytes(),
        _str_column([i.encode() for i in ids]), b"\x00", b"\x00", b"\x00"])


def _varint(v: int) -> bytes:
    out = bytearray()
    while v > 0x7F:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def encode_proto_batch(ids: list[str], amounts, types) -> bytes:
    """risk.v1 ScoreBatchRequest: repeated ScoreTransactionRequest
    {account_id=1, amount=3, transaction_type=4}."""
    type_fields = [b"\x22" + _varint(len(t)) + t.encode() for t in TX_TYPES]
    parts = []
    for i, acct in enumerate(ids):
        a = acct.encode()
        row = (b"\x0a" + _varint(len(a)) + a + b"\x18"
               + _varint(int(amounts[i])) + type_fields[int(types[i])])
        parts.append(b"\x0a" + _varint(len(row)) + row)
    return b"".join(parts)


def _read_varint(buf: bytes, pos: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def decode_proto_response(buf: bytes) -> dict:
    """risk.v1 ScoreBatchResponse -> columns: score, action, rule_score
    (int32), ml_score (float32) and the reason codes of each row."""
    score, action, rule, ml, reasons = [], [], [], [], []
    pos = 0
    while pos < len(buf):
        tag, pos = _read_varint(buf, pos)
        if tag != 0x0A:
            raise ValueError(f"unexpected field tag {tag} in ScoreBatchResponse")
        size, pos = _read_varint(buf, pos)
        end = pos + size
        row = {1: 0, 2: 0, 4: 0}
        row_ml, row_reasons = 0.0, []
        while pos < end:
            tag, pos = _read_varint(buf, pos)
            field, wire = tag >> 3, tag & 7
            if wire == 0:
                value, pos = _read_varint(buf, pos)
                if field in row:
                    row[field] = value
            elif wire == 5:
                if field == 5:
                    row_ml = struct.unpack_from("<f", buf, pos)[0]
                pos += 4
            elif wire == 2:
                size, pos = _read_varint(buf, pos)
                if field == 3:
                    row_reasons.append(buf[pos:pos + size].decode())
                pos += size
            elif wire == 1:
                pos += 8
            else:
                raise ValueError(f"unexpected wire type {wire}")
        score.append(row[1])
        action.append(row[2])
        rule.append(row[4])
        ml.append(row_ml)
        reasons.append(frozenset(row_reasons))
    return {"score": np.array(score, np.int32),
            "action": np.array(action, np.int32),
            "rule_score": np.array(rule, np.int32),
            "ml_score": np.array(ml, np.float32),
            "reasons": reasons}


def encode_frame(rpc: str, ids, amounts, types) -> bytes:
    if rpc == "index":
        return encode_index_frame(ids, amounts, types)
    if rpc == "proto":
        return encode_proto_batch(ids, amounts, types)
    raise ValueError(f"unknown rpc kind {rpc!r}")


# -- the measured window's work ----------------------------------------------


def frame_sizes(mix: dict, seed: int) -> np.ndarray:
    """``pool_frames`` row counts: the mix's sizes in blocks, each block a
    seeded permutation of them, so that any stretch of the pool (a window
    sends only part of it) holds every size in equal shares."""
    sizes = np.array(mix["rows"], np.int64)
    rng = rng_for(seed, "sizes")
    blocks = -(-int(mix["pool_frames"]) // len(sizes))
    out = np.concatenate([rng.permutation(sizes) for _ in range(blocks)])
    return out[:int(mix["pool_frames"])]


def build_pool(mix: dict, pop: Population, seed: int) -> list[tuple[bytes, int]]:
    """The pre-built payloads of a run, ``(bytes, rows)`` each. A closed
    loop's client ``c`` of ``k`` takes frames ``c, c+k, c+2k, ...`` and
    starts over when it has sent them all."""
    sizes = frame_sizes(mix, seed)
    total = int(sizes.sum())
    rng = rng_for(seed, "rows")
    ranks = pop.draw_ranks(rng, total)
    amounts, types = draw_context(mix, rng, total)
    accts = pop.perm[ranks]
    pool, lo = [], 0
    ids = pop.ids
    for n in sizes:
        hi = lo + int(n)
        pool.append((encode_frame(mix["rpc"], [ids[j] for j in accts[lo:hi]],
                                  amounts[lo:hi], types[lo:hi]), int(n)))
        lo = hi
    return pool


# -- the check sequence ------------------------------------------------------


def check_sequence(mix: dict, pop: Population, seed: int, *,
                   loaded: int, stored: int) -> list[dict]:
    """The seeded RPCs the output check sends, each ``{"ids", "amounts",
    "types", "clock"}``. Accounts come in equal parts from the hottest
    ``loaded`` ranks (batch aggregates in the store), from the ranks the
    store holds without aggregates, and from past the store (default
    row); they are revisited until windows are warm and wrap. ``ring``
    accounts alternate bet and deposit of one amount at one cadence: the
    shape the pattern head exists to flag. ``clock`` is the arrival time
    the harness puts on the program's clock seam for that RPC."""
    spec = mix["check"]
    rng = rng_for(seed, "check")
    n_acct, n_ring = int(spec["accounts"]), int(spec.get("ring_accounts", 0))
    third = max(1, (n_acct - n_ring) // 3)
    loaded = min(loaded, pop.n)
    stored = min(max(stored, loaded), pop.n)
    bands = [(0, loaded), (loaded, stored), (stored, pop.n)]
    ranks = []
    for lo, hi in bands:
        if hi > lo:
            ranks.extend(rng.choice(np.arange(lo, hi), size=min(third, hi - lo),
                                    replace=False).tolist())
    ranks = ranks[:n_acct - n_ring]
    ring = rng.choice(np.arange(0, loaded), size=n_ring, replace=False).tolist()
    ring = [r for r in ring if r not in set(ranks)]
    ring_amount = rng.integers(500, 50_000, len(ring))
    sizes = np.resize(np.array(mix["rows"], np.int64), int(spec["rpcs"]))
    clock0 = 1_800_000_000.0 + float(rng.integers(0, 86_400))
    gap = float(rng.uniform(0.4, 2.5))
    out = []
    for k, n in enumerate(sizes):
        n = int(n)
        n_plain = n - len(ring)
        picks = rng.integers(0, len(ranks), n_plain)  # repeats intended
        ids = [pop.id_of_rank(ranks[j]) for j in picks]
        amounts, types = draw_context(mix, rng, n_plain)
        ring_type = 2 if k % 2 == 0 else 0  # bet, deposit, bet, ...
        ids += [pop.id_of_rank(r) for r in ring]
        amounts = np.concatenate([amounts, ring_amount]).astype(np.int64)
        types = np.concatenate(
            [types, np.full(len(ring), ring_type, np.uint8)]).astype(np.uint8)
        order = rng.permutation(n)
        out.append({"ids": [ids[j] for j in order], "amounts": amounts[order],
                    "types": types[order], "clock": clock0 + k * gap})
    return out
