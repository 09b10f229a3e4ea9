"""The plain reference of the risk.v1 scoring path, and the comparison
that decides ``correct``.

numpy only: no cache, no ring, no fused step, nothing imported from the
program. Inputs are what the harness made from the seed (parameters,
the RPCs in the order sent, the clock it put on each) and the base
feature rows of the accounts as the feature store's host gather gives
them (the store's own arithmetic is covered by the repo's tier-1
tests). From those it rebuilds each account's event window, overwrites
the context columns, and runs normalisation, the multitask fraud head,
the eight rules, the session head, the score blend and the thresholds.
The session head is a file of its own under ``chipbench/heads/``, found
by the name the configuration gives (``validate.head_name``): its
parameters from the seed and its forward pass over the windows.

Precision. The deployment states float32 parameters and state, with
matrix products on bfloat16-rounded operands accumulated in float32
(``models/mlp._dense`` casts explicitly; the session head's ``x @ w``
gets the same from the MXU's default precision). ``operand_dtype``
is that rounding: ``bfloat16`` is the reference, ``float8_e4m3fn`` the
control one step below it, ``float32`` no rounding (what XLA's CPU
backend does for the session head in a rehearsal).
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

F32 = np.float32
N_FEATURES = 30
# Feature schema (core/features.F), by index.
TX_COUNT_1M, UNIQUE_DEVICES_24H, UNIQUE_IPS_24H = 0, 5, 6
ACCOUNT_AGE_DAYS, TOTAL_DEPOSITS, TOTAL_WITHDRAWALS = 9, 10, 11
DEPOSIT_COUNT, TIME_SINCE_LAST_TX = 13, 15
IS_VPN, IS_PROXY, IS_TOR, BONUS_ONLY_PLAYER = 19, 20, 21, 25
TX_AMOUNT, TX_TYPE_DEPOSIT, TX_TYPE_WITHDRAW, TX_TYPE_BET = 26, 27, 28, 29
LOG_FEATURES = (3, 10, 11, 26)
MINMAX = {0: 20.0, 1: 50.0, 2: 200.0, 5: 10.0, 6: 20.0, 9: 365.0, 15: 86400.0}
SQUASHED = (4, 7, 8, 12, 13, 14, 16, 17, 23)

# Scoring knobs (core/config.ScoringConfig defaults = engine.go:215-228).
BLOCK, REVIEW = 80, 50
MAX_TX_PER_MINUTE, NEW_ACCOUNT_DAYS, LARGE_AMOUNT = 10, 7, 100_000
MAX_DEVICES, MAX_IPS = 3, 5
RULE_WEIGHT, ML_WEIGHT, TRUNC_EPS = 0.4, 0.6, 1e-4
# Rule weights in reason-bit order: velocity, new account + large tx,
# devices, ips (as country mismatch), vpn, rapid deposit->withdraw, bonus
# abuse, known fraudster.
RULE_WEIGHTS = np.array([20, 30, 15, 25, 15, 25, 20, 50], np.int32)

# Session plane (serve/session_state.py defaults).
EVENT_WIDTH, MIN_EVENTS, FLAG_THRESHOLD = 12, 4, 0.7
TX_EVENT_COL = np.array([0, 1, 2, 3, 7])


def rounder(operand_dtype: str):
    if operand_dtype == "float32":
        return lambda a: np.asarray(a, F32)
    dt = {"bfloat16": ml_dtypes.bfloat16,
          "float8_e4m3fn": ml_dtypes.float8_e4m3fn}[operand_dtype]
    return lambda a: np.asarray(a, F32).astype(dt).astype(F32)


def _sigmoid(z):
    return (1.0 / (1.0 + np.exp(-z.astype(F32)))).astype(F32)


# -- parameters from the seed -------------------------------------------------


def make_params(seed: int, trunk=(256, 256)) -> dict:
    """The served multitask tree, He-initialised from the seed with small
    non-zero biases."""
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 0x706172616D73])

    def dense(d_in, d_out, scale):
        return {"w": (rng.standard_normal((d_in, d_out)) * scale).astype(F32),
                "b": (rng.standard_normal(d_out) * 0.05).astype(F32)}

    dims = (N_FEATURES, *trunk)
    layers = [dense(a, b, math.sqrt(2.0 / a)) for a, b in zip(dims[:-1], dims[1:])]
    d = dims[-1]
    return {"multitask": {
        "trunk": {"layers": layers},
        "fraud_head": dense(d, 1, math.sqrt(1.0 / d)),
        "ltv_head": dense(d, 1, math.sqrt(1.0 / d)),
        "churn_head": dense(d, 1, math.sqrt(1.0 / d)),
    }}


# -- the stateless score ------------------------------------------------------


def normalize(x: np.ndarray) -> np.ndarray:
    """Reference normalisation (log1p on four magnitudes, min-max on seven
    counts) and the signed-log squash of what it leaves unbounded."""
    x = np.array(x, F32)
    for i in LOG_FEATURES:
        x[:, i] = np.where(x[:, i] <= 0, 0, np.log1p(np.maximum(x[:, i], 0)))
    for i, hi in MINMAX.items():
        x[:, i] = np.clip(x[:, i] * F32(1.0 / hi), 0.0, 1.0)
    for i in SQUASHED:
        x[:, i] = np.sign(x[:, i]) * np.log1p(np.abs(x[:, i]))
    return x.astype(F32)


def fraud_prob(params: dict, xn: np.ndarray, rnd) -> np.ndarray:
    mt = params["multitask"]
    h = xn
    for layer in mt["trunk"]["layers"]:
        h = np.maximum(rnd(h) @ rnd(layer["w"]) + layer["b"], 0).astype(F32)
    logit = (rnd(h) @ rnd(mt["fraud_head"]["w"]) + mt["fraud_head"]["b"])[:, 0]
    return _sigmoid(logit)


def rule_scores(x: np.ndarray, blacklisted: np.ndarray):
    """The eight rules over raw features -> (score capped at 100, mask)."""
    amount = x[:, TX_AMOUNT]
    wd_floor = np.floor(x[:, TOTAL_DEPOSITS] * F32(80.0) / F32(100.0))
    hits = np.stack([
        x[:, TX_COUNT_1M] > MAX_TX_PER_MINUTE,
        (x[:, ACCOUNT_AGE_DAYS] < NEW_ACCOUNT_DAYS) & (amount > LARGE_AMOUNT),
        x[:, UNIQUE_DEVICES_24H] > MAX_DEVICES,
        x[:, UNIQUE_IPS_24H] > MAX_IPS,
        (x[:, IS_VPN] > 0) | (x[:, IS_PROXY] > 0) | (x[:, IS_TOR] > 0),
        (x[:, TIME_SINCE_LAST_TX] < 300) & (x[:, TX_TYPE_WITHDRAW] > 0)
        & (x[:, DEPOSIT_COUNT] > 0) & (x[:, TOTAL_WITHDRAWALS] > wd_floor),
        x[:, BONUS_ONLY_PLAYER] > 0,
        np.asarray(blacklisted, bool),
    ], axis=-1)
    score = np.minimum((hits * RULE_WEIGHTS).sum(-1), 100).astype(np.int32)
    mask = (hits * (1 << np.arange(8))).sum(-1).astype(np.int32)
    return score, mask


def combine(rule: np.ndarray, ml: np.ndarray):
    final = np.floor(F32(RULE_WEIGHT) * rule.astype(F32)
                     + F32(ML_WEIGHT) * ml.astype(F32) * F32(100.0)
                     + F32(TRUNC_EPS)).astype(np.int32)
    final = np.minimum(final, 100)
    action = np.where(final >= BLOCK, 3, np.where(final >= REVIEW, 2, 1))
    return final, action.astype(np.int32)


def with_context(base: np.ndarray, amounts, types) -> np.ndarray:
    """Base account rows with the four context columns of this transaction
    (the amount arrives as int64 cents and is scored as float32)."""
    x = np.array(base, F32)
    types = np.asarray(types)
    x[:, TX_AMOUNT] = np.asarray(amounts).astype(F32)
    x[:, TX_TYPE_DEPOSIT] = types == 0
    x[:, TX_TYPE_WITHDRAW] = types == 1
    x[:, TX_TYPE_BET] = types == 2
    return x


# -- the stateful replay ------------------------------------------------------


def encode_events(amounts, types, gaps) -> np.ndarray:
    """Event rows ``[n, EVENT_WIDTH]`` float32 of transactions that came
    ``gaps`` seconds after their account's previous arrival: log1p of the
    amount (scored as float32) and of the gap, the type's column, the
    neutral game weight."""
    n = len(amounts)
    ev = np.zeros((n, EVENT_WIDTH), F32)
    amounts32 = np.asarray(amounts).astype(F32)
    ev[:, 0] = np.log1p(np.maximum(amounts32.astype(np.float64), 0.0))
    ev[:, 1] = np.log1p(np.maximum(np.asarray(gaps, np.float64), 0.0))
    types = np.asarray(types).astype(np.int64)
    ev[np.arange(n), 2 + TX_EVENT_COL[np.clip(types, 0, 4)]] = 1.0
    ev[:, 10] = 1.0
    return ev


def encode_history(history: dict) -> tuple[np.ndarray, float]:
    """An account's preloaded history (``amounts``, ``types`` and each
    event's arrival ``clocks``, oldest first, as
    ``traffic.history_of`` gives it) as event rows, and its last arrival.
    Events that share an arrival came in one chunk: each sees the gap to
    the arrival before theirs, and those of the first arrival see none."""
    clocks = np.asarray(history["clocks"], np.float64)
    arrivals = np.unique(clocks)
    before = np.concatenate([arrivals[:1], arrivals[:-1]])  # first: gap 0
    gaps = clocks - before[np.searchsorted(arrivals, clocks)]
    return (encode_events(history["amounts"], history["types"], gaps),
            float(clocks[-1]) if len(clocks) else 0.0)


class Reference:
    """Scores RPCs in the order the harness sent them, keeping each
    account's events as a plain list."""

    def __init__(self, params: dict, *, head, head_params: dict | None,
                 n_events: int = 16, operand_dtype: str = "bfloat16",
                 head_operand_dtype: str | None = None, history=None):
        """``head`` is a module of ``chipbench/heads/`` (or anything with
        its ``forward``), ``head_params`` what its ``make_params`` gave.
        ``history``, where the deployment preloads session events, gives
        an account's history (``encode_history``'s argument) by its id:
        the account starts from it the first time it is met."""
        self.history = history
        self.params = params
        self.head = head
        self.head_params = head_params
        self.n_events = n_events
        self.rnd = rounder(operand_dtype)
        self.head_rnd = rounder(head_operand_dtype or operand_dtype)
        self.events: dict[str, list[np.ndarray]] = {}
        self.last_ts: dict[str, float] = {}

    def _stateless(self, base, amounts, types):
        x = with_context(base, amounts, types)
        ml = fraud_prob(self.params, normalize(x), self.rnd)
        rule, _ = rule_scores(x, np.zeros(len(x), bool))
        return rule, ml

    def score_rows(self, base, amounts, types) -> dict:
        """The row path: no session state is read or written."""
        rule, ml = self._stateless(base, amounts, types)
        score, action = combine(rule, ml)
        z = np.zeros(len(ml), bool)
        return {"rule_score": rule, "ml_score": ml, "score": score,
                "action": action, "cold": z, "fold": z, "ml_base": ml,
                "sprob": np.zeros(len(ml), F32)}

    def score_index(self, ids, base, amounts, types, clock: float) -> dict:
        """One index-mode chunk: every row sees its account's window as it
        stood when the chunk arrived plus its own event (repeats of an
        account inside a chunk do not see each other), then all the
        chunk's events are appended in row order."""
        n, n_ev = len(ids), self.n_events
        if self.history is not None:
            for a in set(ids) - self.events.keys():
                events, last = encode_history(self.history(a))
                self.events[a] = list(events[-n_ev:])
                self.last_ts[a] = last
        gaps = [max(0.0, clock - self.last_ts[a]) if self.events.get(a) else 0.0
                for a in ids]
        ev = encode_events(amounts, types, gaps)
        win = np.zeros((n, n_ev, EVENT_WIDTH), F32)
        lengths = np.zeros((n,), np.int64)
        for i, a in enumerate(ids):
            hist = self.events.get(a, [])[-(n_ev - 1):]
            if hist:
                win[i, :len(hist)] = hist
            win[i, len(hist)] = ev[i]
            lengths[i] = len(hist) + 1
        for i, a in enumerate(ids):
            self.events.setdefault(a, []).append(ev[i])
            del self.events[a][:-n_ev]
            self.last_ts[a] = clock

        rule, ml = self._stateless(base, amounts, types)
        sprob = self.head.forward(self.head_params, win, lengths, self.head_rnd)
        warm = lengths >= MIN_EVENTS
        fold = warm & (sprob >= FLAG_THRESHOLD)
        ml2 = np.where(fold, np.maximum(ml, sprob), ml).astype(F32)
        score, action = combine(rule, ml2)
        return {"rule_score": rule, "ml_score": ml2, "score": score,
                "action": action, "cold": ~warm, "fold": fold,
                "ml_base": ml, "sprob": sprob, "warm": warm,
                "lengths": lengths}


def concat(parts: list[dict]) -> dict:
    """The outputs of consecutive chunks as one RPC's."""
    return {key: np.concatenate([p[key] for p in parts]) for key in parts[0]}


# -- the comparison -----------------------------------------------------------

# A row whose session probability is this close to the fold threshold may
# land on either side of it in another arithmetic; it is compared with the
# nearer branch. Far narrower than the distance between the branches.
FOLD_BAND = 0.03


def as_reply(out: dict) -> dict:
    """A reference's outputs in the form of a decoded reply, so that it can
    stand in the program's place (the control)."""
    reasons = [frozenset(n for n, on in (("SESSION_COLD", c), ("SESSION_PATTERN", f))
                         if on) for c, f in zip(out["cold"], out["fold"])]
    return {k: out[k] for k in ("score", "action", "rule_score", "ml_score")
            } | {"reasons": reasons}


def compare(got: dict, want: dict, exact: dict) -> dict:
    """Numbers compared for one RPC: ``got`` is the decoded reply of the
    program (or of the control in its place), ``want`` the reference's
    outputs, ``exact`` those of the reference with operands left in
    float32. Sums and maxima, to be merged over RPCs."""
    ml_err = np.abs(got["ml_score"].astype(np.float64) - want["ml_score"])
    score_err = np.abs(got["score"].astype(np.int64) - want["score"])
    # What the stated rounding itself costs on the probability that is
    # judged: the reference at the stated precision against the reference
    # with float32 operands, session head included. Where the two fold
    # differently (a fold moves the probability by tenths) the stateless
    # probability stands in, so a flip between the references cannot swamp it.
    base_rounding = want["ml_base"].astype(np.float64) - exact["ml_base"]
    rounding = np.where(want["fold"] == exact["fold"],
                        want["ml_score"].astype(np.float64) - exact["ml_score"],
                        base_rounding)
    got_cold = np.array(["SESSION_COLD" in r for r in got["reasons"]])
    got_fold = np.array(["SESSION_PATTERN" in r for r in got["reasons"]])
    near = (np.abs(want["sprob"] - FLAG_THRESHOLD) < FOLD_BAND) & ~want["cold"]
    if near.any():
        alt_fold = want["fold"] ^ near
        alt_ml = np.where(alt_fold, np.maximum(want["ml_base"], want["sprob"]),
                          want["ml_base"]).astype(F32)
        alt_score, alt_action = combine(want["rule_score"], alt_ml)
        use_alt = near & (got_fold == alt_fold)
        ml_err = np.where(use_alt, np.abs(got["ml_score"] - alt_ml), ml_err)
        score_err = np.where(use_alt, np.abs(got["score"] - alt_score), score_err)
        want = dict(want, action=np.where(use_alt, alt_action, want["action"]),
                    fold=np.where(use_alt, alt_fold, want["fold"]))
    same_score = score_err == 0
    return {
        "rows": len(ml_err),
        "fraud_prob_sq_sum": float((ml_err ** 2).sum()),
        "rounding_sq_sum": float((rounding ** 2).sum()),
        "stateless_rounding_sq_sum": float((base_rounding ** 2).sum()),
        "fraud_prob_max_err": float(ml_err.max()),
        "score_max_err": int(score_err.max()),
        "rule_score_mismatch": int((got["rule_score"] != want["rule_score"]).sum()),
        "action_mismatch_same_score": int(
            (got["action"][same_score] != want["action"][same_score]).sum()),
        "session_bit_mismatch": int(((got_cold != want["cold"])
                                     | (got_fold != want["fold"])).sum()),
        "folded_rows": int(want["fold"].sum()),
        "warm_rows": int((~want["cold"]).sum()),
    }


def merge(parts: list[dict]) -> dict:
    rows = sum(p["rows"] for p in parts)
    err = sum(p["fraud_prob_sq_sum"] for p in parts)
    def in_roundings(key: str) -> float:
        rounding = sum(p[key] for p in parts)
        return math.sqrt(err / rounding) if rounding > 0 else math.inf

    out = {"rows": rows,
           "fraud_prob_rms_err": math.sqrt(err / max(rows, 1)),
           # The same error in units of what the stated rounding itself costs
           # (reference at the stated precision against float32 operands):
           # how sensitive a seed's parameters are cancels out, in the trunk
           # and in the session head, so this is the number that is steady
           # from seed to seed.
           "fraud_prob_err_in_roundings": in_roundings("rounding_sq_sum"),
           # In units of the rounding's cost on the stateless probability
           # alone, which was judged until PR 30: a seed whose head is far
           # more sensitive than its trunk reads high here (PERF.md).
           "fraud_prob_err_in_stateless_roundings": in_roundings(
               "stateless_rounding_sq_sum")}
    for key in ("fraud_prob_max_err", "score_max_err"):
        out[key] = max(p[key] for p in parts)
    for key in ("rule_score_mismatch", "action_mismatch_same_score",
                "session_bit_mismatch", "folded_rows", "warm_rows"):
        out[key] = sum(p[key] for p in parts)
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """Every compared number beside its limit, and whether all hold."""
    lines, ok = [], True
    for key, limit in limits.items():
        value = numbers[key]
        good = value <= limit
        ok = ok and good
        lines.append(f"check {key} = {value!r} limit {limit!r} "
                     f"{'ok' if good else 'FAILED'}")
    for key in sorted(set(numbers) - set(limits)):
        lines.append(f"check {key} = {numbers[key]!r} (not judged)")
    return ok, lines
