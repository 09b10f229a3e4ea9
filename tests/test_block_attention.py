"""The blocked attention kernel (ops/pallas/block_attention.py) in the
Pallas interpreter against the einsum form in query blocks
(models/mellum_backbone.core_by_einsums), which is what runs off the TPU:
both layer kinds, windows that are and are not whole blocks, the band's
edge read key by key."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from igaming_platform_tpu.models import mellum_backbone as mb
from igaming_platform_tpu.ops.pallas import block_attention as ba

HEADS, KV, HD = 8, 2, 128


def operands(window: int, rows: int = 2, seed: int = 0):
    key = jax.random.key(seed)
    p = rows * window
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape)
    q = draw(1, (p, HEADS * HD))
    k = draw(2, (p, KV * HD)).astype(jnp.bfloat16)
    v = draw(3, (p, KV * HD)).astype(jnp.bfloat16)
    gain = 1.0 + 0.1 * draw(4, (HD,))
    return q, k, v, gain


def tables(window: int, kind: str = mb.FULL):
    return mb.angle_tables(mb.MellumConfig(), window)[kind]


@pytest.mark.parametrize("band", [16, 5, 40, None],
                         ids=["band16", "band5", "band40", "full"])
@pytest.mark.parametrize("window,block", [(64, 16), (64, 32), (56, 16), (40, 16),
                                          (16, 16), (24, 32)],
                         ids=["whole16", "whole32", "tail8", "tail8-of-40",
                              "one-block", "shorter-than-a-block"])
def test_kernel_equals_the_einsum_form_in_query_blocks(window, block, band):
    q, k, v, gain = operands(window)
    cos, sin = tables(window)
    widths = dict(heads=HEADS, kv_heads=KV, window=window, band=band, eps=1e-6)
    want = mb.core_by_einsums(q, k, v, cos, sin, gain, block=block, **widths)
    got = ba.block_attention(q, k, v, cos, sin, gain, block=block,
                             interpret=True, **widths)
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    # the kernel rounds its result once to the operands' dtype and its
    # probabilities before the division: a bfloat16 step of the values
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=0.02, rtol=0.02)
    # one sweep of the whole window is the same sum
    whole = mb.core_by_einsums(q, k, v, cos, sin, gain, block=window, **widths)
    np.testing.assert_allclose(np.asarray(want), np.asarray(whole), atol=2e-3)


def kept_keys(core, window: int, band, block: int):
    """Which keys each query reads, read off the result: with ``q`` zero
    every kept key weighs the same, and key ``j``'s value is one-hot at
    channel ``j``."""
    p = window
    q = jnp.zeros((p, HEADS * HD), jnp.float32)
    k = jnp.ones((p, KV * HD), jnp.bfloat16)
    v = jnp.tile(jnp.eye(window, HD, dtype=jnp.bfloat16), (1, KV))
    cos, sin = tables(window)
    out = core(q, k, v, cos, sin, jnp.ones((HD,)), heads=HEADS, kv_heads=KV,
               window=window, band=band, eps=1e-6, block=block)
    out = np.asarray(out, np.float32).reshape(window, HEADS, HD)
    assert (out == out[:, :1]).all()  # every head reads the same keys
    return out[:, 0, :window] > 0


@pytest.mark.parametrize("core", ["einsums", "kernel"])
@pytest.mark.parametrize("band,window,block", [(16, 64, 16), (16, 64, 32),
                                               (24, 56, 16), (None, 64, 16)])
def test_the_band_keeps_what_the_two_inequalities_keep(core, band, window, block):
    run = (mb.core_by_einsums if core == "einsums" else
           lambda *a, **kw: ba.block_attention(*a, interpret=True, **kw))
    kept = kept_keys(run, window, band, block)
    i, j = np.arange(window)[:, None], np.arange(window)[None, :]
    brute = (j <= i) if band is None else (j <= i) & (i - j < band)
    assert (kept == brute).all()
    if band is not None:
        last = window - 1
        # the three edges, 1022 / 1023 / 1024 scaled down: the key ``band``
        # back is the first that is not read
        assert kept[last, last - (band - 2)] and kept[last, last - (band - 1)]
        assert not kept[last, last - band]


@pytest.mark.parametrize("window,band,block,visited,square", [
    (4096, 1024, 128, 252, 1024), (4096, 1024, 256, 70, 256),
    (4096, None, 256, 136, 256), (4096, None, 128, 528, 1024),
    (1024, 1024, 256, 10, 16), (16, 1024, None, 1, 1), (64, 16, 16, 7, 16)])
def test_visited_blocks_are_what_the_sweep_visits(window, band, block, visited,
                                                  square):
    assert ba.visited_blocks(window, band, block) == (visited, square)
    blk = block or ba.block_for(window)
    n = -(-window // blk)
    i, j = np.arange(window)[:, None], np.arange(window)[None, :]
    keep = (j <= i) if band is None else (j <= i) & (i - j < band)
    keep = np.pad(keep, ((0, n * blk - window),) * 2)
    touched = keep.reshape(n, blk, n, blk).any(axis=(1, 3))
    assert touched.sum() == visited  # no block without a kept pair is visited
    for qb in range(n):
        lo, edge_end = ba.swept_blocks(qb, blk, band)
        assert touched[qb, lo:qb + 1].all() and not touched[qb, :lo].any()
        inside = keep.reshape(n, blk, n, blk)[qb, :, edge_end:qb].all()
        assert inside  # the blocks swept without a mask keep every pair


@pytest.mark.parametrize("change,why", [
    (dict(heads=6), "6 heads over 4 key heads"),
    (dict(hd=64), "head width 64 is not whole 128-lane vregs"),
    (dict(window=48), "positions are not whole windows of 48"),
    (dict(dtype=jnp.float16), "operands float16"),
    (dict(window=1 << 17, p=1 << 18), "of VMEM"),
    (dict(), "")])
def test_declines_says_what_the_kernel_takes(change, why):
    heads, kv = change.get("heads", 32), 4
    hd, window = change.get("hd", 128), change.get("window", 4096)
    p, dt = change.get("p", 8192), change.get("dtype", jnp.bfloat16)
    said = ba.declines(jax.ShapeDtypeStruct((p, heads * hd), jnp.float32),
                       jax.ShapeDtypeStruct((p, kv * hd), dt),
                       jax.ShapeDtypeStruct((p, kv * hd), dt),
                       heads=heads, kv_heads=kv, window=window)
    assert (why in said) if why else said == ""
