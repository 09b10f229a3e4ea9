"""TPU-vs-CPU numerics parity for TRAINED models.

The golden suites pin the MOCK backend bit-for-bit against the
reference (tests/test_scoring_parity.py, onnx_model.go:258-308), but
trained checkpoints run through bf16 MXU matmuls on device — their
TPU-vs-CPU score deltas need pinning too, at eval scale, or "0.9999
AUC" measured on one backend is an unverified claim on the other.

This CLI trains the serving multitask net and the GBDT on labeled
synthetic fraud (train/fraudgen.py — the same generator `make eval`
uses), scores one held-out batch on BOTH backends in one process
(inputs/params committed to each device; the host-CPU backend always
exists alongside the TPU), and writes one JSON line with the deltas:

    python -m igaming_platform_tpu.train.device_parity [--out FILE]

Bounds (asserted here and by the env-gated test in
tests/test_device_parity.py): max |fraud-prob delta| <= 1e-2, AUC delta
<= 1e-3, and >= 99% of the derived integer ensemble scores within +-1.
The prob bound allows for bf16 MXU accumulation across the multitask
trunk; the score/AUC bounds keep the operative contract tight (no
decision impact). Run on a TPU host; under JAX_PLATFORMS=cpu it reports
both "backends" as CPU and trivially passes (``same_backend`` in the
artifact says so).
"""

from __future__ import annotations

import argparse
import json
import sys


def _auc(y: "np.ndarray", p: "np.ndarray") -> float:
    import numpy as np

    order = np.argsort(p)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(p) + 1)
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if not n_pos or not n_neg:
        return float("nan")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def run(n_rows: int = 40_000, steps: int = 300, seed: int = 0) -> dict:
    import jax
    import numpy as np

    from igaming_platform_tpu.core.features import normalize, standardize_for_model
    from igaming_platform_tpu.models.gbdt import gbdt_predict
    from igaming_platform_tpu.models.multitask import multitask_forward
    from igaming_platform_tpu.train.eval import (
        train_gbdt_on_labels,
        train_multitask_on_labels,
    )
    from igaming_platform_tpu.train.fraudgen import generate_labeled

    x, y, _arche = generate_labeled(np.random.default_rng(seed), n_rows)
    split = int(0.8 * n_rows)
    x_train, y_train = x[:split], y[:split]
    x_test, y_test = x[split:], y[split:]

    mt_params = train_multitask_on_labels(x_train, y_train, steps=steps, seed=seed)
    gbdt_params = train_gbdt_on_labels(x_train, y_train, steps=steps, seed=seed)

    default = jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    xn = np.asarray(standardize_for_model(normalize(x_test)), np.float32)

    def mt_prob(device):
        p = jax.device_put(mt_params, device)
        xb = jax.device_put(xn, device)
        return np.asarray(jax.jit(
            lambda pp, xx: multitask_forward(pp, xx)["fraud"])(p, xb), np.float64)

    def gb_prob(device):
        p = jax.device_put(gbdt_params, device)
        xb = jax.device_put(np.asarray(x_test, np.float32), device)
        return np.asarray(jax.jit(gbdt_predict)(p, xb), np.float64)

    out: dict = {
        "metric": "trained_model_device_parity",
        "device": str(default),
        "cpu_control": str(cpu),
        "rows": int(x_test.shape[0]),
        "same_backend": default.platform == cpu.platform,
    }
    worst_prob, worst_auc, worst_score_agree = 0.0, 0.0, 1.0
    for name, fn in (("multitask", mt_prob), ("gbdt", gb_prob)):
        p_dev = fn(default)
        p_cpu = fn(cpu)
        delta = float(np.max(np.abs(p_dev - p_cpu)))
        auc_dev, auc_cpu = _auc(y_test, p_dev), _auc(y_test, p_cpu)
        # The ensemble's ML contribution is int(p * 100 * 0.6): the
        # integer score the wire actually carries.
        s_dev = np.floor(p_dev * 100.0 * 0.6)
        s_cpu = np.floor(p_cpu * 100.0 * 0.6)
        agree1 = float(np.mean(np.abs(s_dev - s_cpu) <= 1.0))
        out[name] = {
            "max_prob_delta": round(delta, 6),
            "auc_device": round(auc_dev, 6),
            "auc_cpu": round(auc_cpu, 6),
            "auc_delta": round(abs(auc_dev - auc_cpu), 6),
            "score_within_1": round(agree1, 5),
        }
        worst_prob = max(worst_prob, delta)
        worst_auc = max(worst_auc, abs(auc_dev - auc_cpu))
        worst_score_agree = min(worst_score_agree, agree1)
    out.update({
        "max_prob_delta": round(worst_prob, 6),
        "max_auc_delta": round(worst_auc, 6),
        "min_score_within_1": round(worst_score_agree, 5),
        "ok": bool(worst_prob <= 1e-2 and worst_auc <= 1e-3
                   and worst_score_agree >= 0.99),
    })
    return out


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="")
    parser.add_argument("--rows", type=int, default=40_000)
    parser.add_argument("--steps", type=int, default=300)
    args = parser.parse_args()

    from igaming_platform_tpu.core.devices import require_device

    require_device()
    result = run(n_rows=args.rows, steps=args.steps)
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(line + "\n")
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
