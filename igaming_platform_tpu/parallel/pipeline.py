"""Pipeline parallelism — GPipe-style microbatching over a mesh axis.

SURVEY.md §2.3: PP is "mesh axis + microbatch loop for the multi-task
trainer (stage = feature encoder / shared trunk / task heads); low priority
for v5e-8 but part of the parallelism API". This module is that API:

- stages are the leading dim of a stacked params pytree, sharded over the
  pipeline axis so each device holds exactly one stage's weights;
- ``pipeline_apply`` runs the classic (M + S - 1)-tick schedule inside
  shard_map: every tick each stage computes on its current microbatch and
  ppermutes the activation to its successor (nearest-neighbour ICI);
- the schedule is unrolled (M and S are static mesh/config properties), so
  XLA can overlap each tick's ppermute with the next tick's compute.

The pipeline axis defaults to ``model`` — on a small mesh PP and TP share
the axis (stage-parallel vs width-parallel are alternative uses); larger
topologies can dedicate an axis by building the mesh accordingly.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from igaming_platform_tpu.parallel.mesh import AXIS_MODEL


def stack_stage_params(stage_params: list[Any]) -> Any:
    """[per-stage pytrees] -> one pytree with a leading stage dim."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *stage_params)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    *,
    num_microbatches: int,
    axis: str = AXIS_MODEL,
) -> jnp.ndarray:
    """Run x through S pipeline stages with M microbatches.

    Args:
      stage_fn: (stage_params, activation [mb, d]) -> activation [mb, d'].
        Activations must keep one shape across stages (classic GPipe).
      stacked_params: pytree with leading dim S (stage axis).
      x: [B, d] global batch; B must divide by num_microbatches.
      mesh: mesh whose ``axis`` has size S.

    Returns [B, d] outputs (replicated over the pipeline axis).
    """
    n_stages = int(mesh.shape[axis])
    b = x.shape[0]
    if b % num_microbatches != 0:
        raise ValueError(f"batch {b} not divisible by microbatches {num_microbatches}")
    mb = b // num_microbatches

    x_mb = x.reshape(num_microbatches, mb, *x.shape[1:])

    def local(params_stage, x_local):
        # params_stage: this device's stage params (leading stage dim
        # consumed by the in_spec); x_local: full microbatch tensor,
        # replicated across the pipeline axis.
        params_stage = jax.tree.map(lambda p: p[0], params_stage)
        stage = lax.axis_index(axis)
        is_first = stage == 0
        is_last = stage == n_stages - 1

        carry = jnp.zeros_like(stage_fn(jax.tree.map(jnp.zeros_like, params_stage), x_local[0]))
        outputs = jnp.zeros((num_microbatches,) + carry.shape, carry.dtype)
        recv = jnp.zeros_like(carry)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        for t in range(num_microbatches + n_stages - 1):
            feed_idx = t if t < num_microbatches else num_microbatches - 1
            inp = jnp.where(is_first & (t < num_microbatches), x_mb_select(x_local, feed_idx), recv)
            out = stage_fn(params_stage, inp)
            out_idx = t - (n_stages - 1)
            if 0 <= out_idx < num_microbatches:
                outputs = outputs.at[out_idx].set(
                    jnp.where(is_last, out, outputs[out_idx])
                )
            recv = lax.ppermute(out, axis, perm)

        # Only the last stage holds real outputs; share them along the ring.
        outputs = lax.psum(jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    def x_mb_select(x_local, idx):
        return x_local[idx]

    stage_leading_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    body = shard_map(
        local,
        mesh=mesh,
        in_specs=(stage_leading_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    out_mb = body(stacked_params, x_mb)
    return out_mb.reshape(b, *out_mb.shape[2:])


def mlp_stage_fn(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """A dense+ReLU pipeline stage (d -> d), for stage-parallel trunks."""
    return jax.nn.relu(x @ params["w"] + params["b"])
