"""Slot-sharded device state: the feature table and session ring over a mesh.

ROADMAP open item 2 ("shard the state, not just the fleet"): before this
module every device image held the FULL HBM feature table (PR 1) and the
FULL session ring (PR 12) — cache capacity scaled only by adding whole
replicas, and aggregate fleet HBM burned one copy per chip. Here the
big per-slot arrays become **row-sharded** over the mesh ``data`` axis
(``NamedSharding(mesh, P("data", ...))`` — the MeshHelper
``allgather``/``batch_axis_spec`` shape from SNIPPETS.md [1][2]), so a
K-chip mesh holds ONE table split K ways: per-chip HBM is ~1/K and
admissible slots scale with the mesh, not the replica count.

Slot → shard ownership is derived from the existing host
``account_id -> slot`` index: shards are CONTIGUOUS row blocks (that is
how NamedSharding splits axis 0), so

    owner(slot) = slot // (capacity // K)

and the host side (CLOCK admission, per-shard occupancy gauges, the
debug surfaces) can attribute every slot without asking the device.

The device side stays SINGLE-DISPATCH: the fused mega-step's gather /
scatter / donated ring append run inside ``shard_map`` bodies composed
into the same jitted program (serve/scorer.py builds them), so PR 14's
1.0 dispatches/RPC survives sharding. Two collective patterns, both
bit-exact by construction:

- :func:`gather_slots` — each shard contributes its owned rows (others
  read as zero-filled out-of-range), ``all_gather`` over ``data``, then
  an exact owner-select. No arithmetic combine (a psum would be exact
  too for +0.0 rows, but a select cannot even raise the question).
- :func:`scatter_slots` / the in-body append — global slot ids map to
  local rows; non-owned rows redirect to one-past-the-end and scatter
  with ``mode="drop"``. Padding rows (``sidx == capacity``) are owned by
  nobody and vanish — the sharded twin of the unsharded scratch slot.

Enablement: :func:`plan_for` returns a :class:`SlotShardingPlan` when
the mesh's ``data`` axis is >1 and ``STATE_SHARDING`` != 0 (default on).
A 1-device mesh returns None and every caller keeps the replicated
layout — the SAME code path a degraded single-host rebuild compiles, so
a supervisor rebuild can never silently change program shape
(serve/multihost.py loopback builds the mesh=1 sharding for exactly
this reason).
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

from igaming_platform_tpu.parallel.mesh import AXIS_DATA


def sharding_enabled_env() -> bool:
    return os.environ.get("STATE_SHARDING", "1") not in ("0", "false")


@dataclass(frozen=True)
class SlotShardingPlan:
    """How per-slot device state splits over the mesh ``data`` axis."""

    mesh: object
    n_shards: int

    # -- capacity / ownership (host side) -------------------------------------

    def round_capacity(self, capacity: int) -> int:
        """Smallest multiple of ``n_shards`` >= capacity: NamedSharding
        needs equal row blocks, and rounding UP never shrinks what the
        operator asked for."""
        k = self.n_shards
        return ((int(capacity) + k - 1) // k) * k

    def rows_per_shard(self, capacity: int) -> int:
        if capacity % self.n_shards != 0:
            raise ValueError(
                f"capacity {capacity} not divisible by {self.n_shards} shards "
                "(round_capacity first)")
        return capacity // self.n_shards

    def owner_of(self, slots, capacity: int) -> np.ndarray:
        """Vectorized slot -> shard index (host-side attribution)."""
        return (np.asarray(slots, np.int64)
                // self.rows_per_shard(capacity)).astype(np.int32)

    # -- placement ------------------------------------------------------------

    def spec(self, ndim: int):
        from jax.sharding import PartitionSpec as P

        return P(AXIS_DATA, *([None] * (ndim - 1)))

    def named(self, ndim: int):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self.spec(ndim))

    def place(self, arr):
        import jax

        return jax.device_put(arr, self.named(arr.ndim))


def plan_for(mesh, enabled: bool | None = None) -> SlotShardingPlan | None:
    """The plan for this mesh, or None when slot sharding doesn't apply
    (no mesh / 1-wide data axis / STATE_SHARDING=0)."""
    if mesh is None:
        return None
    k = int(mesh.shape.get(AXIS_DATA, 1))
    if k <= 1:
        return None
    if enabled is None:
        enabled = sharding_enabled_env()
    if not enabled:
        return None
    return SlotShardingPlan(mesh, k)


# ---------------------------------------------------------------------------
# In-shard_map building blocks (called INSIDE a shard_map body, where the
# array arguments are the local per-shard blocks).


def local_slot_index(local_rows: int, slots):
    """Global slot ids -> (local row index, owned mask) for this shard.
    Non-owned (and out-of-range padding) slots map to ``local_rows`` —
    one past the end, which ``mode='fill'`` reads as the fill value and
    ``mode='drop'`` scatters into the void."""
    import jax
    import jax.numpy as jnp

    me = jax.lax.axis_index(AXIS_DATA)
    li = slots - me * local_rows
    owned = jnp.logical_and(li >= 0, li < local_rows)
    return jnp.where(owned, li, local_rows), owned


def select_owner(contrib, slots, local_rows: int):
    """all_gather every shard's contribution over ``data`` and select the
    owner's copy of each row (never summed)."""
    import jax
    import jax.numpy as jnp

    allc = jax.lax.all_gather(contrib, AXIS_DATA)  # [K, B, ...]
    owner = jnp.clip(slots // local_rows, 0, allc.shape[0] - 1)
    return allc[owner, jnp.arange(slots.shape[0])]


def gather_slots(local, slots):
    """Exact sharded gather: ``local`` is this shard's row block of a
    slot-sharded array; ``slots`` are GLOBAL slot ids (replicated).
    Returns the full gathered rows, identical on every shard — each
    shard contributes its owned rows (others read as zero fill) and
    :func:`select_owner` picks the owner's copy."""
    local_rows = local.shape[0]
    li, _ = local_slot_index(local_rows, slots)
    contrib = local.at[li].get(mode="fill", fill_value=0)
    return select_owner(contrib, slots, local_rows)


def gather_ring_slots(ring_l, slots, local_rows: int, n_events: int):
    """:func:`gather_slots` for the session ring, whose at-rest layout
    is flat (serve/session_state.ring_rows owns it): [B, N, D] rows."""
    from igaming_platform_tpu.serve.session_state import ring_rows

    li, _ = local_slot_index(local_rows, slots)
    return select_owner(ring_rows(ring_l, li, n_events), slots, local_rows)


def scatter_slots(local, slots, rows):
    """Sharded scatter: write ``rows`` at global ``slots``; each shard
    lands only its owned rows (``mode='drop'`` discards the rest)."""
    li, _ = local_slot_index(local.shape[0], slots)
    return local.at[li].set(rows, mode="drop")


# ---------------------------------------------------------------------------
# Standalone jitted programs (the between-steps scatters: delta apply,
# flag set, session admission sync). One jit launch each, same call
# signatures as their replicated twins in device_cache / session_state.


def make_sharded_scatter(plan: SlotShardingPlan, ndim: int):
    """jit(shard_map) twin of ``table.at[slots].set(rows)`` for a
    slot-sharded ``ndim``-D state array."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    sm = shard_map(
        scatter_slots,
        mesh=plan.mesh,
        in_specs=(plan.spec(ndim), P(), P()),
        out_specs=plan.spec(ndim),
        check_vma=False,
    )
    return jax.jit(sm)


def make_sharded_ring_sync(plan: SlotShardingPlan):
    """jit(shard_map) twin of the session admission sync: scatter window
    rows + cursors + lengths for freshly admitted slots into the
    slot-sharded ring state (donated: outputs alias inputs)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from igaming_platform_tpu.serve.session_state import ring_put

    def sync(ring_l, cur_l, len_l, slots, w, c, l):  # noqa: E741
        li, _ = local_slot_index(cur_l.shape[0], slots)
        return (ring_put(ring_l, li, w),
                scatter_slots(cur_l, slots, c),
                scatter_slots(len_l, slots, l))

    sm = shard_map(
        sync,
        mesh=plan.mesh,
        in_specs=(plan.spec(1), plan.spec(1), plan.spec(1), P(), P(), P(),
                  P()),
        out_specs=(plan.spec(1), plan.spec(1), plan.spec(1)),
        check_vma=False,
    )
    return jax.jit(sm, donate_argnums=(0, 1, 2))


# ---------------------------------------------------------------------------
# HBM accounting (the Gemma-on-TPU per-chip efficiency story: what each
# chip actually holds, measured from the committed shardings).


def per_shard_nbytes(arr) -> list[int]:
    """Bytes of ``arr`` resident per addressable device, index-ordered.
    Replicated arrays report the full size on every device — that
    asymmetry IS the measurement the bench arm records."""
    out: dict[int, int] = {}
    for s in getattr(arr, "addressable_shards", []):
        d = s.data
        out[s.device.id] = int(np.prod(d.shape)) * d.dtype.itemsize
    if not out:  # plain numpy / single-device array
        return [int(np.prod(arr.shape)) * arr.dtype.itemsize]
    return [out[k] for k in sorted(out)]
