"""The selective-scan kernel (ops/pallas/selective_scan.py) in interpret mode
on the CPU against the chunked form that is its reference
(models/phi4flash_backbone.scan_by_chunks) and against the recurrence written
position by position; and what ``declines`` turns away."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from igaming_platform_tpu.models import phi4flash_backbone as pb
from igaming_platform_tpu.ops.pallas import selective_scan as kernel

STATE = 16


def operands(windows: int, window: int, channels: int, seed: int = 59):
    ks = jax.random.split(jax.random.key(seed), 6)
    p = windows * window
    x = jax.random.normal(ks[0], (p, channels), jnp.float32)
    # dt log-uniform in [0.001, 0.1]: some channels forget in a few positions,
    # some remember a whole window
    dt = jnp.exp(jax.random.uniform(ks[1], (p, channels), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    bm, cm = (jax.random.normal(k, (p, STATE), jnp.float32) for k in ks[2:4])
    a_t = -jnp.broadcast_to(jnp.arange(1.0, STATE + 1)[:, None],
                            (STATE, channels)) * (
        1 + 0.1 * jax.random.uniform(ks[4], (STATE, channels)))
    d = jax.random.normal(ks[5], (channels,), jnp.float32)
    return x, dt, bm, cm, a_t, d


def by_positions(x, dt, bm, cm, a_t, d, *, window: int):
    """The recurrence as it is written, one position after the other, from a
    zero state at each window's first."""
    def one_window(xw, dtw, bw, cw):
        def step(s, at):
            x_t, dt_t, b_t, c_t = at
            s = jnp.exp(dt_t[None] * a_t) * s + b_t[:, None] * (dt_t * x_t)[None]
            return s, jnp.sum(c_t[:, None] * s, axis=0) + d * x_t
        return jax.lax.scan(step, jnp.zeros_like(a_t), (xw, dtw, bw, cw))[1]

    split = lambda m: m.reshape(-1, window, m.shape[-1])
    y = jax.vmap(one_window)(*map(split, (x, dt, bm, cm)))
    return y.reshape(x.shape)


@pytest.mark.parametrize("window,block,tile", [
    (64, 16, 128), (64, 64, 256), (24, 8, 128), (128, None, None)],
    ids=["four-blocks", "one-block", "three-blocks-of-eight", "the-defaults"])
def test_kernel_equals_the_chunked_form_and_the_recurrence(window, block, tile):
    args = operands(3, window, 256)
    got = kernel.selective_scan(*args, window=window, block=block, tile=tile,
                                interpret=True)
    chunked = pb.scan_by_chunks(*args, window=window, chunk=16)
    plain = by_positions(*args, window=window)
    scale = float(jnp.abs(plain).max())
    assert float(jnp.abs(got - plain).max()) < 1e-5 * scale
    assert float(jnp.abs(got - chunked).max()) < 1e-5 * scale


@pytest.mark.parametrize("window,chunk", [(48, 16), (48, 48), (40, 16), (7, 4),
                                          (16, 128)],
                         ids=["three-chunks", "one-chunk", "a-ragged-last-chunk",
                              "seven-positions", "a-chunk-longer-than-the-window"])
def test_chunks_hand_the_state_across_their_boundaries(window, chunk):
    args = operands(2, window, 128, seed=7)
    got = pb.scan_by_chunks(*args, window=window, chunk=chunk)
    plain = by_positions(*args, window=window)
    assert float(jnp.abs(got - plain).max()) < 1e-5 * float(jnp.abs(plain).max())
    # a form that forgot the state at a chunk's boundary would differ there
    if chunk < window:
        x, dt, bm, cm, a_t, d = args
        cut = lambda m: m.reshape(2, window, -1)[:, chunk:].reshape(
            2 * (window - chunk), -1)
        forgot = pb.scan_by_chunks(cut(x), cut(dt), cut(bm), cut(cm), a_t, d,
                                   window=window - chunk, chunk=chunk)
        assert float(jnp.abs(cut(got) - forgot).max()) > 1e-3


def test_the_state_is_reset_between_windows():
    """Two windows in one call equal each window alone."""
    args = operands(2, 32, 128)
    x, dt, bm, cm, a_t, d = args
    both = kernel.selective_scan(*args, window=32, interpret=True)
    for w in range(2):
        rows = slice(32 * w, 32 * (w + 1))
        alone = kernel.selective_scan(x[rows], dt[rows], bm[rows], cm[rows],
                                      a_t, d, window=32, interpret=True)
        assert (both[rows] == alone).all()


def test_blocks_and_tiles_divide_what_they_are_given():
    assert kernel.block_for(2048) == 256 and kernel.tile_for(5120) == 512
    assert kernel.block_for(24) == 8 and kernel.block_for(64) == 64
    assert kernel.tile_for(256) == 256 and kernel.tile_for(384) == 128


@pytest.mark.parametrize("change,why", [
    ({}, ""),
    ({"window": 12}, "a window of 12 is not whole 8-position tiles"),
    ({"window": 40}, "96 positions are not whole windows of 40"),
    ({"channels": 192}, "192 channels are not whole 128-lane vregs"),
    ({"state": 12}, "a state of 12 is not whole 8-sublane tiles"),
    ({"dtype": jnp.bfloat16}, "operands bfloat16"),
    ({"dt_rows": 48}, "dt (48, 256)")],
    ids=["taken", "window-tiles", "whole-windows", "lanes", "state-tiles",
         "dtype", "shapes"])
def test_declines_says_what_the_kernel_takes(change, why):
    window = change.get("window", 48)
    p = 96 if window != 12 else 24
    channels, state = change.get("channels", 256), change.get("state", STATE)
    dtype = change.get("dtype", jnp.float32)
    wide = jax.ShapeDtypeStruct((p, channels), dtype)
    dt = jax.ShapeDtypeStruct((change.get("dt_rows", p), channels), jnp.float32)
    narrow = jax.ShapeDtypeStruct((p, state), jnp.float32)
    said = kernel.declines(wide, dt, narrow, narrow, window=window)
    assert why in said if why else said == ""
