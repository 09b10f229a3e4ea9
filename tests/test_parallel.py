"""Mesh + collectives tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from igaming_platform_tpu.core.config import ScoringConfig
from igaming_platform_tpu.models.ensemble import make_score_fn
from igaming_platform_tpu.parallel import collectives as coll
from igaming_platform_tpu.parallel.mesh import (
    AXIS_DATA,
    MeshSpec,
    create_mesh,
    mesh_axis_size,
    single_device_mesh,
    validate_batch_for_mesh,
)


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8


def test_mesh_spec_resolution():
    assert MeshSpec(data=-1).resolve(8) == (8, 1, 1, 1)
    assert MeshSpec(data=-1, model=2).resolve(8) == (4, 2, 1, 1)
    assert MeshSpec(data=2, model=2, seq=2).resolve(8) == (2, 2, 2, 1)
    with pytest.raises(ValueError):
        MeshSpec(data=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(data=-1, model=3).resolve(8)


def test_create_mesh_axes():
    mesh = create_mesh(MeshSpec(data=-1, model=2))
    assert mesh_axis_size(mesh, AXIS_DATA) == 4
    assert mesh_axis_size(mesh, "model") == 2
    validate_batch_for_mesh(64, mesh)
    with pytest.raises(ValueError):
        validate_batch_for_mesh(63, mesh)


def test_psum_and_all_gather():
    mesh = create_mesh(MeshSpec(data=-1))

    @jax.jit
    def summed(x):
        def body(x):
            return coll.psum(jnp.sum(x), AXIS_DATA)

        return shard_map(body, mesh=mesh, in_specs=P(AXIS_DATA), out_specs=P())(x)

    x = np.arange(16, dtype=np.float32)
    assert float(summed(x)) == x.sum()

    @jax.jit
    def gathered(x):
        def body(x):
            return coll.all_gather(x, AXIS_DATA)

        return shard_map(body, mesh=mesh, in_specs=P(AXIS_DATA), out_specs=P(None), check_vma=False)(x)

    out = np.asarray(gathered(x))
    np.testing.assert_array_equal(out, x)


def test_ppermute_ring_rotates():
    mesh = create_mesh(MeshSpec(data=-1))

    @jax.jit
    def rotate(x):
        def body(x):
            return coll.ppermute_ring(x, AXIS_DATA, shift=1)

        return shard_map(body, mesh=mesh, in_specs=P(AXIS_DATA), out_specs=P(AXIS_DATA))(x)

    x = np.arange(8, dtype=np.float32)
    out = np.asarray(rotate(x))
    np.testing.assert_array_equal(out, np.roll(x, 1))


def test_all_to_all_transposes_ownership():
    """all_to_all re-shards rows->columns without changing the global value
    (the Ulysses/EP ownership transpose)."""
    mesh = create_mesh(MeshSpec(data=-1))
    n = 8

    @jax.jit
    def a2a(x):
        def body(x):
            # local [1, n] row -> local [n, 1] column of the same matrix
            return coll.all_to_all(x, AXIS_DATA, split_axis=1, concat_axis=0)

        return shard_map(body, mesh=mesh, in_specs=P(AXIS_DATA, None), out_specs=P(None, AXIS_DATA))(x)

    x = np.arange(n * n, dtype=np.float32).reshape(n, n)
    out = a2a(x)
    np.testing.assert_array_equal(np.asarray(out), x)
    assert out.sharding.spec == P(None, AXIS_DATA)


def test_sharded_scoring_matches_single_device():
    """The pjit'd scorer over a [B/8-per-chip] batch == unsharded results."""
    from tests.test_scoring_parity import random_batch

    cfg = ScoringConfig()
    rng = np.random.default_rng(0)
    x = random_batch(rng, 128)
    bl = rng.random(128) < 0.1

    fn = make_score_fn(cfg, "mock")

    mesh = create_mesh(MeshSpec(data=-1))
    batch_sh = NamedSharding(mesh, P(AXIS_DATA))
    row_sh = NamedSharding(mesh, P(AXIS_DATA, None))
    sharded = jax.jit(fn, in_shardings=(None, row_sh, batch_sh), out_shardings=batch_sh)

    single = jax.jit(fn)
    out_s = sharded(None, x, bl)
    out_1 = single(None, x, bl)
    for key in ("score", "action", "rule_score", "reason_mask"):
        np.testing.assert_array_equal(np.asarray(out_s[key]), np.asarray(out_1[key]), err_msg=key)
    np.testing.assert_allclose(np.asarray(out_s["ml_score"]), np.asarray(out_1["ml_score"]), atol=1e-6)


def test_replicate_and_shard_batch():
    mesh = create_mesh(MeshSpec(data=-1))
    x = np.arange(32, dtype=np.float32).reshape(16, 2)
    xs = coll.shard_batch(mesh, x)
    assert xs.sharding.spec == P(AXIS_DATA, None)
    xr = coll.replicate(mesh, x)
    assert xr.sharding.spec == P()


def test_single_device_mesh():
    mesh = single_device_mesh()
    assert mesh_axis_size(mesh, AXIS_DATA) == 1
