"""Model-quality eval: labeled fraud generator + metric math + ordering."""

import jax
import numpy as np
import pytest

from igaming_platform_tpu.train.eval import (
    average_precision,
    expected_calibration_error,
    roc_auc,
    run_eval,
)
from igaming_platform_tpu.train.fraudgen import generate_labeled


@pytest.fixture(autouse=True, scope="module")
def _optimized_programs():
    """These tests run hundreds of training steps over thousands of rows:
    here XLA's optimizer pays for itself (55 against 79 CPU-seconds for the
    file), unlike in the rest of the suite (tests/conftest.py)."""
    name = "jax_disable_most_optimizations"
    before = jax.config.values[name]
    jax.config.update(name, False)
    yield
    jax.config.update(name, before)


def test_metric_math_known_values():
    y = np.array([0, 0, 1, 1], dtype=np.float32)
    p_perfect = np.array([0.1, 0.2, 0.8, 0.9])
    p_anti = 1.0 - p_perfect
    assert roc_auc(y, p_perfect) == 1.0
    assert roc_auc(y, p_anti) == 0.0
    assert roc_auc(y, np.full(4, 0.5)) == 0.5  # ties -> chance
    assert average_precision(y, p_perfect) == 1.0
    # Perfectly calibrated: predicted prob == observed rate per bin.
    y2 = np.array([0, 1] * 50, dtype=np.float32)
    assert expected_calibration_error(y2, np.full(100, 0.5)) < 1e-9
    assert expected_calibration_error(y2, np.full(100, 0.95)) > 0.4


def test_generator_plants_separable_but_overlapping_patterns():
    rng = np.random.default_rng(0)
    x, y, kind = generate_labeled(rng, 20_000, fraud_rate=0.12)
    assert x.shape == (20_000, 30)
    rate = float(y.mean())
    assert 0.10 < rate < 0.14
    # All three archetypes present in meaningful numbers.
    for k in (1, 2, 3):
        assert (kind == k).sum() > 300
    # Patterns are real (fraud velocity higher on average)...
    from igaming_platform_tpu.core.features import F

    assert x[kind == 1, F.TX_COUNT_1M].mean() > 3 * x[kind == 0, F.TX_COUNT_1M].mean()
    # ...but overlapping: some clean rows exceed some velocity-fraud rows
    # (hard negatives), so thresholding alone cannot be perfect.
    assert (x[kind == 0, F.TX_SUM_1H].max() > np.percentile(x[kind == 1, F.TX_SUM_1H], 50))


def test_eval_ordering_trained_beats_mock_beats_rules():
    """The committed EVAL.json claim, reproduced at small scale: learning
    on labels beats the hand-tuned mock, which beats bare rules."""
    r = run_eval(n_train=8_000, n_test=4_000, steps=100, seed=3)
    m = r["models"]
    assert m["mock"]["auc"] > m["rules_only"]["auc"]
    assert m["multitask_trained"]["auc"] > m["mock"]["auc"] + 0.015
    assert m["gbdt_trained"]["auc"] > m["mock"]["auc"] + 0.015
    assert m["multitask_trained"]["average_precision"] > m["mock"]["average_precision"]
    assert r["ordering"]["trained_beats_mock"]


def test_routed_training_improves_over_untrained_bundle():
    """Joint router+experts training beats the fresh bundle by a wide
    margin, the trained router spreads load, and the bundle drops into
    the serving engine's routed backend."""
    from igaming_platform_tpu.parallel.ep import gate_probs
    from igaming_platform_tpu.train.routed import (
        RoutedTrainConfig,
        routed_prob,
        train_routed_on_labels,
    )

    rng = np.random.default_rng(7)
    x, y, _ = generate_labeled(rng, 8_000)
    x_test, y_test, _ = generate_labeled(np.random.default_rng(8), 4_000)

    from igaming_platform_tpu.models.ensemble import init_routed_params

    fresh = init_routed_params(jax.random.key(0), mlp_hidden=(64, 64),
                               n_trees=32, depth=4, trunk=(64, 64))
    auc_fresh = roc_auc(y_test, routed_prob(fresh, x_test))

    trained = train_routed_on_labels(x, y, RoutedTrainConfig(steps=120, seed=7))
    auc_trained = roc_auc(y_test, routed_prob(trained, x_test))
    assert auc_trained > auc_fresh + 0.05
    assert auc_trained > 0.9

    # Router actually discriminates: no expert monopolizes top-1.
    gates = np.asarray(gate_probs(trained["router"], x_test[:2000]))
    top1_share = np.bincount(gates.argmax(-1), minlength=4) / 2000.0
    assert top1_share.max() < 0.9

    # The bundle serves through the engine's routed backend.
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

    engine = TPUScoringEngine(
        ScoringConfig(), ml_backend="routed", params=trained,
        batcher_config=BatcherConfig(batch_size=32, max_wait_ms=1.0),
    )
    try:
        resp = engine.score(ScoreRequest(account_id="rt-1", amount=90_000,
                                         tx_type="withdraw"))
        assert 0 <= resp.score <= 100
    finally:
        engine.close()
