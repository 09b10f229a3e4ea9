"""Pipeline-parallel tests: staged execution == sequential application."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh
from igaming_platform_tpu.parallel.pipeline import (
    mlp_stage_fn,
    pipeline_apply,
    stack_stage_params,
)


def _stages(n, d, key):
    keys = jax.random.split(key, n)
    return [
        {
            "w": jax.random.normal(k, (d, d), jnp.float32) * 0.3,
            "b": jnp.zeros((d,), jnp.float32),
        }
        for k in keys
    ]


def _sequential(stages, x):
    h = x
    for p in stages:
        h = np.maximum(np.asarray(h) @ np.asarray(p["w"]) + np.asarray(p["b"]), 0.0)
    return h


@pytest.mark.parametrize("microbatches", [4, 8])
def test_pipeline_matches_sequential(microbatches):
    mesh = create_mesh(MeshSpec(data=1, model=4, seq=2))
    d = 16
    stages = _stages(4, d, jax.random.key(0))
    stacked = stack_stage_params(stages)
    x = np.asarray(jax.random.normal(jax.random.key(1), (32, d)), np.float32)

    out = jax.jit(
        lambda p, xx: pipeline_apply(mlp_stage_fn, p, xx, mesh, num_microbatches=microbatches)
    )(stacked, x)
    expected = _sequential(stages, x)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-4, atol=1e-5)


def test_pipeline_eight_stages():
    mesh = create_mesh(MeshSpec(data=1, model=8))
    d = 8
    stages = _stages(8, d, jax.random.key(2))
    stacked = stack_stage_params(stages)
    x = np.asarray(jax.random.normal(jax.random.key(3), (16, d)), np.float32)
    out = jax.jit(
        lambda p, xx: pipeline_apply(mlp_stage_fn, p, xx, mesh, num_microbatches=4)
    )(stacked, x)
    np.testing.assert_allclose(np.asarray(out), _sequential(stages, x), rtol=1e-4, atol=1e-5)


def test_pipeline_rejects_bad_microbatch():
    mesh = create_mesh(MeshSpec(data=1, model=4, seq=2))
    stages = _stages(4, 8, jax.random.key(4))
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_apply(
            mlp_stage_fn, stack_stage_params(stages),
            np.zeros((10, 8), np.float32), mesh, num_microbatches=3,
        )


def test_bf16_train_wire_loss_parity(monkeypatch):
    """TRAIN_WIRE_DTYPE=bf16 halves training H2D bytes; the compressed
    transport must be training-noise-scale —
    same data stream, same seed, final loss within a tight band of the
    f32 run."""
    import numpy as np

    from igaming_platform_tpu.train.data import Batch, make_aux_targets
    from igaming_platform_tpu.train.trainer import TrainConfig, Trainer

    def run(wire: str) -> float:
        if wire:
            monkeypatch.setenv("TRAIN_WIRE_DTYPE", wire)
        else:
            monkeypatch.delenv("TRAIN_WIRE_DTYPE", raising=False)
        rng = np.random.default_rng(3)
        trainer = Trainer(TrainConfig(batch_size=256, trunk=(32, 32), seed=3))
        if wire:
            assert trainer._wire_cast is not None

        def stream():
            from igaming_platform_tpu.train.data import sample_features

            while True:
                x = sample_features(rng, 256)
                ltv_t, churn_t = make_aux_targets(x)
                fraud = (rng.random(256) < 0.1).astype(np.float32)
                yield Batch(x=x, fraud=fraud, ltv=ltv_t, churn=churn_t)

        metrics = trainer.fit(40, data=stream(), log_every=0)
        return metrics["loss"]

    loss_f32 = run("")
    loss_bf16 = run("bf16")
    # Same stream/seed: the transport cast must not change the training
    # trajectory beyond noise scale.
    assert abs(loss_f32 - loss_bf16) < 0.05, (loss_f32, loss_bf16)
