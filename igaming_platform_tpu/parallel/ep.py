"""Expert parallelism: routed sub-batches + all-to-all combine.

SURVEY.md §2.3 EP row: the fraud ensemble's scorers (mock heuristic, MLP,
GBDT, multitask net — the experts behind engine.go:290-299's ensemble)
get a parallel execution story. Round 2 sharded the GBDT tree bank over
``expert`` (dense EP: every row visits every shard); this module adds the
ROUTED form:

- a linear router gates each row to its top-k experts;
- rows exchange over the ``expert`` mesh axis with ``lax.all_to_all``
  (the ICI collective) into capacity-bounded per-expert sub-batches —
  the GShard/Switch dispatch layout, built from one-hot dispatch masks
  so XLA lowers it to einsums + one all-to-all each way;
- each device runs ONLY its own expert (heterogeneous experts selected
  by ``lax.switch`` on the expert-axis index — every branch is traced
  once, one executes per shard);
- results return via the inverse all-to-all and combine as a
  gate-weighted sum per row.

Capacity overflow drops a row's contribution from that expert (standard
MoE semantics; the gate weight renormalizes over surviving experts).
With enough capacity nothing drops and the routed forward equals the
dense reference exactly — pinned by tests/test_ep_routing.py on the
8-device CPU mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from igaming_platform_tpu.parallel.mesh import AXIS_EXPERT


def init_router(key, in_dim: int, n_experts: int, scale: float = 0.1):
    """Linear gate weights [in_dim, n_experts]."""
    return scale * jax.random.normal(key, (in_dim, n_experts), jnp.float32)


def gate_probs(router_w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Softmax router over experts: [B, F] -> [B, E]."""
    return jax.nn.softmax(jnp.asarray(x, jnp.float32) @ router_w, axis=-1)


def _dispatch_masks(gates: jnp.ndarray, k: int, capacity: int):
    """GShard-style one-hot dispatch/combine tensors.

    Returns (dispatch [b, E, C] one-hot, combine [b, E, C] gate-weighted,
    kept [b, k] bool). Position within an expert's buffer = how many
    earlier (row, priority) picks chose that expert — computed with
    cumsums over the flattened (k, b) priority order so top-1 picks beat
    top-2 picks for capacity, like Switch routing.
    """
    b, e = gates.shape
    top_vals, top_idx = jax.lax.top_k(gates, k)  # [b, k]

    # Flatten in priority-major order: all rows' 1st choice, then 2nd...
    flat_idx = top_idx.T.reshape(-1)  # [k*b]
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)  # [k*b, E]
    pos_in_expert = jnp.cumsum(onehot, axis=0) - 1  # [k*b, E]
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # [k*b]
    kept = pos < capacity

    pos_kb = pos.reshape(k, b).T  # [b, k]
    kept_kb = kept.reshape(k, b).T  # [b, k]

    # Combine = dispatch scaled by the (renormalized) gate of each pick;
    # both built from the SAME per-pick one-hot so they cannot disagree.
    surviving = jnp.where(kept_kb, top_vals, 0.0)
    denom = jnp.maximum(surviving.sum(axis=-1, keepdims=True), 1e-9)
    weights = surviving / denom  # [b, k]
    disp = jnp.zeros((b, e, capacity), jnp.float32)
    comb = jnp.zeros((b, e, capacity), jnp.float32)
    for j in range(k):  # k is small and static — unrolled
        pick = jnp.where(
            kept_kb[:, j][:, None, None],
            jax.nn.one_hot(top_idx[:, j], e)[:, :, None]
            * jax.nn.one_hot(pos_kb[:, j], capacity)[:, None, :],
            0.0,
        )
        disp = disp + pick
        comb = comb + weights[:, j][:, None, None] * pick
    return disp, comb, kept_kb


def routed_ensemble_forward(
    router_w: jnp.ndarray,
    expert_params: tuple,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    expert_fns: Sequence[Callable[[Any, jnp.ndarray], jnp.ndarray]],
    k: int = 2,
    capacity_factor: float = 1.5,
    shard_rows_over: tuple[str, ...] = (AXIS_EXPERT,),
) -> dict[str, jnp.ndarray]:
    """Routed scoring: [B, F] -> per-row probability in [0, 1].

    ``expert_fns[i](expert_params[i], x) -> [b]`` — one scorer per expert
    shard; ``len(expert_fns)`` must equal the mesh's ``expert`` axis size,
    and B must divide by the product of ``shard_rows_over`` axis sizes.
    ``shard_rows_over``: which mesh axes split the batch's row dimension —
    pass ``(AXIS_DATA, AXIS_EXPERT)`` on a serving mesh so every device
    owns distinct rows (the GShard data x expert layout; the all_to_all
    runs within each data group); the default expert-only split suits an
    EP-only mesh. Returns {"prob": [B], "load": [E] rows received per
    expert (per data group), "dropped": [] count}.
    """
    n_experts = int(mesh.shape[AXIS_EXPERT])
    assert len(expert_fns) == n_experts, (
        f"{len(expert_fns)} expert fns for expert axis of {n_experts}"
    )
    row_split = 1
    for ax in shard_rows_over:
        row_split *= int(mesh.shape[ax])
    b_total, feat_dim = x.shape
    assert b_total % row_split == 0, (
        f"batch {b_total} must divide by the row-sharding product "
        f"({row_split}); pad the batch (serving tiers already do)"
    )
    b_local = b_total // row_split
    capacity = int(np.ceil(capacity_factor * k * b_local / n_experts))

    def shard_fn(router_w, expert_params, x_local):
        # x_local: [b_local, F] — this shard's slice of the batch.
        gates = gate_probs(router_w, x_local)
        disp, comb, kept = _dispatch_masks(gates, k, capacity)
        # Per-destination sub-batches, then ONE all-to-all each way.
        dispatched = jnp.einsum("bec,bf->ecf", disp, x_local)  # [E, C, F]
        received = jax.lax.all_to_all(
            dispatched, AXIS_EXPERT, split_axis=0, concat_axis=0
        )  # [E_src, C, F] — rows routed here from every source shard
        my_expert = jax.lax.axis_index(AXIS_EXPERT)
        flat_in = received.reshape(n_experts * capacity, feat_dim)
        branches = [
            partial(lambda fn, p, xx: fn(p, xx), fn, p)
            for fn, p in zip(expert_fns, expert_params)
        ]
        flat_out = jax.lax.switch(my_expert, branches, flat_in)  # [E*C]
        returned = jax.lax.all_to_all(
            flat_out.reshape(n_experts, capacity), AXIS_EXPERT,
            split_axis=0, concat_axis=0,
        )  # [E_dst, C] — my rows' scores back from every expert
        prob = jnp.einsum("bec,ec->b", comb, returned)  # [b_local]
        load = jnp.sum(disp, axis=(0, 2))  # rows THIS shard sent per expert
        # Totals must be identical on every device (out_specs P()): sum
        # over every axis that splits rows, plus expert.
        stat_axes = tuple(dict.fromkeys((*shard_rows_over, AXIS_EXPERT)))
        load = jax.lax.psum(load, stat_axes)
        dropped = jax.lax.psum(jnp.sum(~kept), stat_axes)
        return prob, load, dropped

    shard = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(shard_rows_over, None)),
        out_specs=(P(shard_rows_over), P(), P()),
        check_vma=False,
    )
    prob, load, dropped = shard(router_w, tuple(expert_params), jnp.asarray(x, jnp.float32))
    return {"prob": prob, "load": load, "dropped": dropped}


def topk_mix(gates: jnp.ndarray, expert_outs: jnp.ndarray, k: int) -> tuple:
    """Per-row renormalized top-k gate-weighted mix — THE mixture
    semantics, shared by serving (dense_reference) and training
    (train/routed.py) so the two forwards cannot drift. Returns
    (mix [B], top_idx [B, k])."""
    top_vals, top_idx = jax.lax.top_k(gates, k)
    weights = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    picked = jnp.take_along_axis(expert_outs, top_idx, axis=-1)  # [B, k]
    return jnp.sum(picked * weights, axis=-1), top_idx


def dense_reference(
    router_w: jnp.ndarray,
    expert_params: tuple,
    x: jnp.ndarray,
    *,
    expert_fns: Sequence[Callable],
    k: int = 2,
) -> jnp.ndarray:
    """Unrouted reference: every expert scores every row; per-row top-k
    gate-weighted mix. Equals the routed forward when capacity drops
    nothing."""
    gates = gate_probs(router_w, x)
    all_out = jnp.stack(
        [fn(p, x) for fn, p in zip(expert_fns, expert_params)], axis=-1
    )  # [B, E]
    return topk_mix(gates, all_out, k)[0]
