"""Same-pod DP(+TP) training for the multi-task fraud+LTV model.

Replaces the reference's offline train -> ONNX export -> redeploy loop
(Makefile:215-225, scripts absent) with in-process JAX training on the same
mesh that serves (BASELINE.json config 5): batch axis sharded over ``data``
(gradient psum over ICI inserted by XLA), trunk hidden dims optionally
sharded over ``model`` (TP), parameters handed to the server by reference —
no serialization format hops (SURVEY.md §2.2).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from igaming_platform_tpu.core.features import normalize, standardize_for_model
from igaming_platform_tpu.models.multitask import init_multitask, multitask_forward, param_specs
from igaming_platform_tpu.parallel.mesh import AXIS_DATA
from igaming_platform_tpu.parallel.sharding import tree_shardings
from igaming_platform_tpu.train.data import Batch, make_stream


@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 1024
    learning_rate: float = 3e-4
    weight_decay: float = 1e-4
    ltv_scale: float = 1_000.0  # dollars -> unit scale for the MSE head
    fraud_loss_weight: float = 1.0
    ltv_loss_weight: float = 0.5
    churn_loss_weight: float = 0.5
    trunk: tuple[int, ...] = (256, 256)
    # Rematerialize the forward in the backward pass (jax.checkpoint):
    # trades recompute FLOPs for activation memory — the lever that lets
    # batch_size grow past HBM on big trunks (SURVEY.md hardware notes).
    remat: bool = False
    seed: int = 0


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int


def make_loss_fn(cfg: TrainConfig):
    forward = jax.checkpoint(multitask_forward) if cfg.remat else multitask_forward

    def loss_fn(params, x_raw, fraud_t, ltv_t, churn_t):
        xn = standardize_for_model(normalize(x_raw))
        out = forward(params, xn)
        # Soft-target BCE for fraud/churn, scaled Huber for LTV.
        fraud_loss = jnp.mean(optax.sigmoid_binary_cross_entropy(out["fraud_logit"], fraud_t))
        churn_loss = jnp.mean(optax.sigmoid_binary_cross_entropy(out["churn_logit"], churn_t))
        ltv_loss = jnp.mean(optax.huber_loss(out["ltv"], ltv_t / cfg.ltv_scale, delta=10.0))
        total = (
            cfg.fraud_loss_weight * fraud_loss
            + cfg.ltv_loss_weight * ltv_loss
            + cfg.churn_loss_weight * churn_loss
        )
        metrics = {
            "loss": total,
            "fraud_loss": fraud_loss,
            "ltv_loss": ltv_loss,
            "churn_loss": churn_loss,
            "fraud_mae": jnp.mean(jnp.abs(out["fraud"] - fraud_t)),
        }
        return total, metrics

    return loss_fn


class Trainer:
    """DP(+TP)-sharded trainer with param hot-swap handoff to serving."""

    def __init__(self, cfg: TrainConfig | None = None, mesh: Mesh | None = None):
        self.cfg = cfg or TrainConfig()
        self.mesh = mesh
        self.optimizer = optax.adamw(self.cfg.learning_rate, weight_decay=self.cfg.weight_decay)

        key = jax.random.key(self.cfg.seed)
        params = init_multitask(key, trunk=self.cfg.trunk)
        opt_state = self.optimizer.init(params)

        loss_fn = make_loss_fn(self.cfg)

        def train_step(params, opt_state, x, fraud_t, ltv_t, churn_t):
            # TRAIN_WIRE_DTYPE=bf16 ships x compressed; the graph
            # restores float32 before normalization (no-op for f32).
            x = jnp.asarray(x, jnp.float32)
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, x, fraud_t, ltv_t, churn_t
            )
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, metrics

        self._batch_sh = None
        self._vec_sh = None
        if mesh is not None:
            pspecs = param_specs(params)
            p_sh = tree_shardings(mesh, pspecs)
            self._batch_sh = NamedSharding(mesh, P(AXIS_DATA, None))
            self._vec_sh = NamedSharding(mesh, P(AXIS_DATA))
            params = jax.device_put(params, p_sh)
            # optax moment buffers mirror the param pytree, so re-initialising
            # from sharded params inherits the TP layout; jit infers the rest.
            opt_state = self.optimizer.init(params)
            self._step_fn = jax.jit(
                train_step,
                in_shardings=(
                    p_sh, None, self._batch_sh,
                    self._vec_sh, self._vec_sh, self._vec_sh,
                ),
                out_shardings=(p_sh, None, None),
                donate_argnums=(0, 1),
            )
        else:
            self._step_fn = jax.jit(train_step, donate_argnums=(0, 1))

        self.state = TrainState(params=params, opt_state=opt_state, step=0)

        # TRAIN_WIRE_DTYPE=bf16 (opt-in): ship the feature batch to the
        # device as bfloat16 — HALF the H2D bytes, for hosts where the
        # input transfer, not the step, bounds training throughput (not
        # measured on the current chip). Raw features keep ~3 significant digits through
        # the cast; the in-graph log1p normalization compresses that to
        # a ~4e-3 absolute error on standardized inputs — a training-
        # noise-scale perturbation (loss parity pinned by test), NOT for
        # the serving path, whose own WIRE_DTYPE carries its documented
        # envelope. Targets stay float32 (they are tiny).
        self._wire_cast = None
        wire = os.environ.get("TRAIN_WIRE_DTYPE", "").lower()
        if wire in ("bf16", "bfloat16"):
            import ml_dtypes

            self._wire_cast = ml_dtypes.bfloat16
        elif wire not in ("", "f32", "fp32", "float32"):
            # A typo would silently train at the f32 wire rate while the
            # operator believes compression is on — fail loudly instead
            # (same discipline as the serving WIRE_DTYPE).
            raise ValueError(
                f"TRAIN_WIRE_DTYPE={wire!r} not supported (use 'bf16' or 'float32')")

    def put_batch(self, batch: Batch) -> tuple:
        """Start the H2D transfer for a batch (async — device_put returns
        immediately) with the mesh's batch shardings when sharded. Feeding
        ``train_step_device`` with pre-put batches overlaps the next
        batch's transfer with the current step's compute instead of
        paying a synchronous H2D every step."""
        x = batch.x if self._wire_cast is None else batch.x.astype(self._wire_cast)
        if self._batch_sh is not None:
            return (
                jax.device_put(x, self._batch_sh),
                jax.device_put(batch.fraud, self._vec_sh),
                jax.device_put(batch.ltv, self._vec_sh),
                jax.device_put(batch.churn, self._vec_sh),
            )
        return (
            jax.device_put(x), jax.device_put(batch.fraud),
            jax.device_put(batch.ltv), jax.device_put(batch.churn),
        )

    def train_step_device(self, dev_batch: tuple):
        """One training step with NO host synchronization: inputs are
        device arrays from ``put_batch`` and the returned metrics stay on
        device. Callers materialize them every N steps (one packed D2H)
        instead of five scalar readbacks per step — each sync readback
        stalls the dispatch queue."""
        params, opt_state, metrics = self._step_fn(
            self.state.params, self.state.opt_state, *dev_batch
        )
        self.state = TrainState(params=params, opt_state=opt_state, step=self.state.step + 1)
        return metrics

    @staticmethod
    def materialize_metrics(metrics) -> dict[str, float]:
        """Device metrics tree -> host floats in ONE packed transfer —
        the single place the metrics D2H policy lives."""
        return {k: float(v) for k, v in jax.device_get(metrics).items()}

    def train_step(self, batch: Batch) -> dict[str, float]:
        return self.materialize_metrics(self.train_step_device(self.put_batch(batch)))

    def fit(
        self,
        steps: int,
        data: Iterator[Batch] | None = None,
        log_every: int = 50,
        log_fn=None,
    ) -> dict[str, float]:
        """Double-buffered training loop: batch k+1's H2D overlaps batch
        k's step; metrics are read back (one transfer) only at log points
        and at the end."""
        if steps <= 0:
            return {}
        data = data or make_stream(self.cfg.batch_size, seed=self.cfg.seed)
        metrics = None
        pending = self.put_batch(next(data))
        for i in range(steps):
            current = pending
            if i + 1 < steps:
                pending = self.put_batch(next(data))
            metrics = self.train_step_device(current)
            if log_fn is not None and (i + 1) % log_every == 0:
                log_fn(self.state.step, self.materialize_metrics(metrics))
        return self.materialize_metrics(metrics)

    def export_params(self):
        """Hand the live params to the serving engine (zero-copy on the
        same devices; the engine wraps them in {"mlp"-style} dict itself)."""
        return self.state.params
