"""Generate the RELEASED golden checkpoint + scores for the default suite.

The mock backend is pinned bit-for-bit against the reference
(tests/test_scoring_parity.py, onnx_model.go:258-308's golden
discipline), but trained checkpoints had no equivalent: a numerics
regression in the model stack, the normalize/standardize pipeline, or
the int8 quantizer would only surface as a silent AUC drift. This tool
trains a small released multitask checkpoint on labeled synthetic fraud
(seeded, CPU — reproducible anywhere), scores a fixed feature batch
through the REAL serving score fn (f32 and int8-quantized backends),
and commits both as goldens:

    tests/golden/released_multitask.msgpack   (flax-serialized params)
    tests/golden/released_features.npz        (the fixed [64, 30] batch)
    tests/golden/released_scores.json         (expected outputs)

tests/test_release_golden.py asserts the committed checkpoint still
produces these exact scores (f32, CPU-deterministic) and that the int8
path stays within its ±1-point envelope — so hot-swap, quantize, and
numerics regressions are caught in every CI run, no TPU needed.

Regenerate (ONLY when the model stack changes intentionally):
    JAX_PLATFORMS=cpu python tools/make_release_golden.py
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "tests", "golden")
TRUNK = (64, 64)
SEED = 7
N_GOLDEN_ROWS = 64


def main() -> None:
    import jax
    from flax import serialization

    if jax.default_backend() != "cpu":
        raise SystemExit("release goldens pin CPU numerics: run with "
                         "JAX_PLATFORMS=cpu")

    from igaming_platform_tpu.core.config import ScoringConfig
    from igaming_platform_tpu.models.ensemble import make_score_fn
    from igaming_platform_tpu.ops.quantize import quantize_multitask_fraud
    from igaming_platform_tpu.train.eval import train_multitask_on_labels
    from igaming_platform_tpu.train.fraudgen import generate_labeled

    x, y, _pattern = generate_labeled(np.random.default_rng(SEED), 20_000, fraud_rate=0.12)
    params = train_multitask_on_labels(
        x, y, steps=150, batch_size=512, trunk=TRUNK, seed=SEED)

    # The fixed golden batch: raw features drawn from the SAME generator
    # (stored verbatim — goldens must not depend on generator stability).
    gx, gy, _ = generate_labeled(np.random.default_rng(SEED + 1), N_GOLDEN_ROWS, fraud_rate=0.3)
    gx = gx.astype(np.float32)

    cfg = ScoringConfig()
    blacklisted = np.zeros((N_GOLDEN_ROWS,), dtype=bool)
    f32 = make_score_fn(cfg, "multitask")(
        {"multitask": params}, gx, blacklisted)
    from igaming_platform_tpu.core.features import normalize, standardize_for_model

    # Calibrate on what the quantized layers actually see: the
    # normalized+standardized features, not the raw wire batch.
    q = quantize_multitask_fraud(
        params, calibration_x=standardize_for_model(normalize(gx)))
    int8 = make_score_fn(cfg, "multitask_int8")(
        {"multitask_int8": q}, gx, blacklisted)

    os.makedirs(GOLDEN_DIR, exist_ok=True)
    with open(os.path.join(GOLDEN_DIR, "released_multitask.msgpack"), "wb") as f:
        f.write(serialization.to_bytes(jax.device_get(params)))
    np.savez(os.path.join(GOLDEN_DIR, "released_features.npz"),
             x=gx, y=gy.astype(np.int32))
    golden = {
        "trunk": list(TRUNK),
        "seed": SEED,
        "f32": {
            "score": np.asarray(f32["score"]).astype(int).tolist(),
            "action": np.asarray(f32["action"]).astype(int).tolist(),
            "ml_score": np.asarray(f32["ml_score"]).astype(float).round(8).tolist(),
        },
        "int8": {
            "score": np.asarray(int8["score"]).astype(int).tolist(),
        },
    }
    with open(os.path.join(GOLDEN_DIR, "released_scores.json"), "w") as f:
        json.dump(golden, f, indent=1)
    print(f"goldens written to {GOLDEN_DIR}: "
          f"{len(golden['f32']['score'])} rows, trunk={TRUNK}")


if __name__ == "__main__":
    main()
