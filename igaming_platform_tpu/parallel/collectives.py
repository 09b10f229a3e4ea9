"""The collective vocabulary — this framework's NCCL/MPI equivalent.

A thin, named layer over `jax.lax` collectives so the rest of the framework
never calls raw ``lax.p*`` directly (SURVEY.md §2.3 "Comm backend"). Every
function takes the mesh axis name it communicates over; inside
``shard_map`` these lower to XLA collectives scheduled on ICI (intra-slice)
or DCN (cross-host) — replacing the reference's gRPC+RabbitMQ-only backend
for device-side communication.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def psum(x, axis: str):
    """All-reduce sum over ``axis`` (gradient sync, ensemble reduction)."""
    return lax.psum(x, axis_name=axis)


def pmean(x, axis: str):
    """All-reduce mean over ``axis`` (metric aggregation, loss averaging)."""
    return lax.pmean(x, axis_name=axis)

def pmax(x, axis: str):
    return lax.pmax(x, axis_name=axis)


def all_gather(x, axis: str, *, tiled: bool = True, gather_axis: int = 0):
    """Gather shards along ``gather_axis`` from every device on ``axis``."""
    return lax.all_gather(x, axis_name=axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_axis: int = 0):
    """Sum then scatter — the memory-lean half of an all-reduce."""
    return lax.psum_scatter(x, axis_name=axis, scatter_dimension=scatter_axis, tiled=True)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int):
    """Transpose shard ownership: split locally on ``split_axis``, exchange,
    concatenate on ``concat_axis``. Backbone of Ulysses SP and EP routing."""
    return lax.all_to_all(x, axis_name=axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def ppermute_ring(x, axis: str, *, shift: int = 1):
    """Rotate shards around the ``axis`` ring by ``shift`` steps — the
    nearest-neighbour ICI pattern under ring attention / pipelining."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)


# -- host-facing sharding helpers -------------------------------------------


def shard_batch(mesh: Mesh, x, *, axis: str = "data"):
    """Place a host array with its leading dim sharded over ``axis``."""
    spec = P(axis, *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def replicate(mesh: Mesh, x):
    """Replicate a host array across every device of the mesh."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int, *, axis: str = "data") -> NamedSharding:
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))
