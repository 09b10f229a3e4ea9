"""ops tests: GBDT matmul formulation + Pallas kernel vs the gather form."""

import jax
import numpy as np
import pytest

from igaming_platform_tpu.core.features import NUM_FEATURES
from igaming_platform_tpu.models.gbdt import gbdt_raw, init_gbdt
from igaming_platform_tpu.ops.gbdt_matmul import gbdt_raw_matmul, precompute_selector


@pytest.fixture(scope="module")
def forest():
    params = init_gbdt(jax.random.key(0), n_trees=32, depth=4)
    x = np.random.default_rng(0).random((256, NUM_FEATURES)).astype(np.float32)
    return params, x


def test_selector_shape_and_onehot(forest):
    params, _ = forest
    sel = precompute_selector(np.asarray(params["feat"]), NUM_FEATURES)
    assert sel.shape == (NUM_FEATURES, 32 * 4)
    np.testing.assert_array_equal(sel.sum(axis=0), np.ones(32 * 4))


def test_matmul_formulation_matches_gather(forest):
    params, x = forest
    sel = precompute_selector(np.asarray(params["feat"]), NUM_FEATURES)
    a = np.asarray(gbdt_raw(params, x))
    b = np.asarray(jax.jit(gbdt_raw_matmul)(params, sel, x))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_pallas_kernel_matches_gather(forest):
    from igaming_platform_tpu.ops.pallas.gbdt_kernel import gbdt_raw_pallas

    params, x = forest
    a = np.asarray(gbdt_raw(params, x))
    b = np.asarray(gbdt_raw_pallas(params, x, tile_b=128, interpret=True))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_pallas_kernel_multiple_tiles(forest):
    from igaming_platform_tpu.ops.pallas.gbdt_kernel import gbdt_raw_pallas

    params, _ = forest
    x = np.random.default_rng(1).random((512, NUM_FEATURES)).astype(np.float32)
    a = np.asarray(gbdt_raw(params, x))
    b = np.asarray(gbdt_raw_pallas(params, x, tile_b=128, interpret=True))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
