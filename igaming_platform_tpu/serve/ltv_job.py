"""LTV batch job — the analytical-table scan the reference loops over.

The reference's `BatchPredict` is a sequential per-account loop and
`SegmentPlayers` groups its results (ltv.go:385-414) — the SURVEY §3.4
"scaling gap". Here the batch path is the TPU-native version: one scan of
the wallet store builds the [N, 25] feature matrix, ONE jitted forward
pass predicts LTV / churn / segment / survival / next-best-action for
every player, and the job emits segment groupings plus per-account
records (JSON), with segment counts fed to the metrics registry.

Usage:
    python -m igaming_platform_tpu.serve.ltv_job <wallet.db> [out.json]
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from igaming_platform_tpu.models.ltv import (
    ACTIONS,
    NUM_LTV_FEATURES,
    L,
    predict_batch_jit,
)

_SECONDS_PER_DAY = 86_400.0


def ltv_features_from_wallet(db_path: str, now: float | None = None) -> tuple[list[str], np.ndarray]:
    """Scan a wallet store into the [N, 25] LTV feature matrix.

    Behavioral features the wallet schema can't know (sessions, push/email
    opt-ins, support tickets) stay zero — exactly the degraded-confidence
    case the model's data-quality term handles (ltv.go:346-382).
    """
    from igaming_platform_tpu.platform.repository import open_wallet_reader

    now = now or time.time()
    query, close = open_wallet_reader(db_path)
    try:
        accounts = query("SELECT id, created_at FROM accounts")
        rows = query(
            "SELECT account_id, type, COUNT(*), COALESCE(SUM(amount),0),"
            " COALESCE(MAX(amount),0), COALESCE(MAX(completed_at),0)"
            " FROM transactions WHERE status='completed' GROUP BY account_id, type"
        )
        # floor(), not CAST(... AS INTEGER): CAST truncates in SQLite but
        # ROUNDS in PostgreSQL — the day buckets must agree on both.
        active = dict(query(
            "SELECT account_id, COUNT(DISTINCT floor(created_at / 86400))"
            " FROM transactions WHERE status='completed' GROUP BY account_id"
        ))
    finally:
        close()

    agg: dict[str, dict] = {a: {} for a, _ in accounts}
    for account_id, tx_type, count, total, largest, last_ts in rows:
        agg.setdefault(account_id, {})[tx_type] = (count, total, largest, last_ts)

    ids = [a for a, _ in accounts]
    x = np.zeros((len(ids), NUM_LTV_FEATURES), dtype=np.float32)
    for i, (account_id, created_at) in enumerate(accounts):
        per_type = agg.get(account_id, {})
        dep = per_type.get("deposit", (0, 0, 0, 0.0))
        bet = per_type.get("bet", (0, 0, 0, 0.0))
        win = per_type.get("win", (0, 0, 0, 0.0))
        wd = per_type.get("withdraw", (0, 0, 0, 0.0))
        bonus = per_type.get("bonus_grant", (0, 0, 0, 0.0))

        age_days = max(0.0, (now - created_at) / _SECONDS_PER_DAY)
        x[i, L.DAYS_SINCE_REGISTRATION] = age_days
        x[i, L.DAYS_SINCE_LAST_DEPOSIT] = (
            (now - dep[3]) / _SECONDS_PER_DAY if dep[3] else age_days
        )
        x[i, L.DAYS_SINCE_LAST_BET] = (
            (now - bet[3]) / _SECONDS_PER_DAY if bet[3] else age_days
        )
        x[i, L.TOTAL_ACTIVE_DAYS] = active.get(account_id, 0)
        x[i, L.TOTAL_DEPOSITS] = dep[1] / 100.0          # cents -> dollars
        x[i, L.TOTAL_WITHDRAWALS] = wd[1] / 100.0
        # net_revenue = deposits - withdrawals - bonuses (ltv.go:50) — the
        # quantity LTV projection and segmentation key on; NOT bets-wins.
        x[i, L.NET_REVENUE] = (dep[1] - wd[1] - bonus[1]) / 100.0
        x[i, L.AVG_DEPOSIT_AMOUNT] = (dep[1] / dep[0] / 100.0) if dep[0] else 0.0
        x[i, L.DEPOSIT_FREQUENCY] = dep[0] / max(age_days / 30.0, 1.0)  # per month
        x[i, L.LARGEST_DEPOSIT] = dep[2] / 100.0
        x[i, L.TOTAL_BETS] = bet[1] / 100.0
        x[i, L.TOTAL_WINS] = win[1] / 100.0
        x[i, L.BET_COUNT] = bet[0]
        x[i, L.WIN_RATE] = win[0] / bet[0] if bet[0] else 0.0
        x[i, L.AVG_BET_SIZE] = (bet[1] / bet[0] / 100.0) if bet[0] else 0.0
    return ids, x


def run_batch_job(db_path: str, now: float | None = None, metrics=None) -> dict:
    """Scan -> ONE device pass -> segment groupings + per-account records."""
    ids, x = ltv_features_from_wallet(db_path, now=now)
    if not ids:
        return {"players": [], "segments": {}, "count": 0}
    out = predict_batch_jit(x)
    segments = np.asarray(out["segment"])
    records = [
        {
            "account_id": account_id,
            "predicted_ltv": round(float(out["ltv"][i]), 2),
            "segment": int(segments[i]),
            "churn_risk": round(float(out["churn_risk"][i]), 4),
            "survival_days": int(out["survival_days"][i]),
            "confidence": round(float(out["confidence"][i]), 4),
            "next_best_action": ACTIONS[int(out["action"][i])],
        }
        for i, account_id in enumerate(ids)
    ]
    grouped: dict[str, list[str]] = {}
    for rec in records:
        grouped.setdefault(str(rec["segment"]), []).append(rec["account_id"])
    if metrics is not None:
        for seg, members in grouped.items():
            metrics.ltv_segment_total.inc(len(members), segment=seg)
    return {"players": records, "segments": grouped, "count": len(records)}


def main() -> None:
    if len(sys.argv) < 2:
        print("usage: python -m igaming_platform_tpu.serve.ltv_job <wallet.db | postgres://…> [out.json]",
              file=sys.stderr)
        sys.exit(2)
    from igaming_platform_tpu.core.devices import require_device

    require_device()
    result = run_batch_job(sys.argv[1])
    import jax

    result["device"] = str(jax.devices()[0])
    payload = json.dumps(result, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(payload)
        print(json.dumps({"players_segmented": result["count"],
                          "segments": {k: len(v) for k, v in result["segments"].items()},
                          "out": sys.argv[2]}))
    else:
        print(payload)


if __name__ == "__main__":
    main()
