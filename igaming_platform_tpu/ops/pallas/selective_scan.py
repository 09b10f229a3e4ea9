"""Pallas TPU kernel: Mamba-1's selective scan over deep windows, the state
held in VMEM while a window's positions stream past.

``models/phi4flash_backbone.ssm_mixer`` runs, inside windows of ``T``
positions (2,048 in its cell), the recurrence a channel ``c`` and a state
column ``n``

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] x_t[c]

from ``s = 0`` at a window's first position. The decay differs a channel
AND a column (``A`` is ``[channels, state]``), so there is no dual form with
one scalar a head to put on the MXU (ops/pallas/ssd_window.py's, Mamba-2's),
and over thousands of positions no one chunk. Materialised over a 2-row
step, ``exp(dt A)`` is ``[4096, 5120, 16]`` float32, 1.34 GB a tensor a
layer. Here nothing of ``[positions, channels, state]`` reaches HBM.

**A program** is one tile of ``tile`` channels, one window and one block of
``block`` positions; the grid walks the blocks of a window in order
(``arbitrary``), so the state ``[state, tile]`` float32 (columns along the
sublanes, channels along the lanes: 16 x 512 is eight vregs) stays in a VMEM
scratch from a window's first position to its last and is set to zero where
a window starts. ``x`` and ``dt`` come position-major ``[P, channels]``
float32, as the taps and ``W_dt``'s product left them: a block is ``[block,
tile]`` and a position one row, broadcast down the state's sublanes. ``B``
and ``C`` are ``[P, state]``, two columns of 16 a position; the state needs
``B_t`` and ``C_t`` DOWN its sublanes, so the caller's wrapper lays them out
eight positions at a time as ``[P / 8, state, 8]`` (a transpose of 0.5 MB by
XLA) and a position's column is one static lane slice, broadcast along the
lanes. Inside a block the positions go eight at a time: one aligned load of
``[8, tile]`` each of ``x`` and ``dt``, eight state updates unrolled, one
aligned store of eight rows of ``y``.

Everything is float32 on the vector unit (the exponential on the
transcendental unit); the MXU is idle. A layer's call at the cell's shape
reads ``x``, ``dt``, ``B``, ``C`` and writes ``y`` once: 0.25 GB, 0.31 ms of
the memory's time, against 0.34 G state updates of an exponential, four
multiplies and two adds each.

Same arithmetic as ``models/phi4flash_backbone.scan_by_chunks``, the
chunked ``jax.numpy`` form that is this kernel's reference
(tests/test_selective_scan.py) and what runs off the TPU; what differs is
the order of the float32 sums (there an associative scan inside a chunk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 8  # positions a step of the loop inside a block takes: one f32 tile

# Positions a program streams past, and channels it holds the state of.
# Measured on a v5e at the cell's shape, 2 windows of 2,048 x 5,120 channels
# (PERF.md, section 6, PR 59).
_BLOCK = 256
_TILE = 512


def block_for(window: int) -> int:
    """Positions a block holds at windows of ``window``: the largest of
    ``_BLOCK``, its halves down to ``_ROWS``, that divides the window."""
    block = _BLOCK
    while block > _ROWS and window % block:
        block //= 2
    return block


def tile_for(channels: int) -> int:
    """Channels a program holds the state of: the largest of ``_TILE``, its
    halves down to a vreg's 128 lanes, that divides ``channels``."""
    tile = _TILE
    while tile > _LANES and channels % tile:
        tile //= 2
    return tile


def declines(x, dt, bm, cm, *, window: int) -> str:
    """Why ``selective_scan`` does not take these operands, "" where it
    does. ``x`` and ``dt`` [P, channels], ``bm`` and ``cm`` [P, state]
    (arrays or their shapes-and-dtypes). It takes float32 operands, channels
    in whole 128-lane vregs, a state of whole 8-sublane tiles and whole
    windows of whole 8-position tiles; anything else takes the caller's
    chunked form."""
    if x.ndim != 2 or dt.shape != x.shape:
        return f"x {x.shape}, dt {dt.shape}"
    p, channels = x.shape
    if bm.ndim != 2 or bm.shape[0] != p or cm.shape != bm.shape:
        return f"B {bm.shape}, C {cm.shape} against x {x.shape}"
    if any(a.dtype != jnp.float32 for a in (x, dt, bm, cm)):
        return f"operands {x.dtype} / {dt.dtype} / {bm.dtype} / {cm.dtype}"
    if channels % _LANES:
        return f"{channels} channels are not whole {_LANES}-lane vregs"
    if bm.shape[1] % _ROWS:
        return f"a state of {bm.shape[1]} is not whole {_ROWS}-sublane tiles"
    if window <= 0 or p == 0 or p % window:
        return f"{p} positions are not whole windows of {window}"
    if window % _ROWS:
        return f"a window of {window} is not whole {_ROWS}-position tiles"
    return ""


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, s_ref, *,
            block: int):
    @pl.when(pl.program_id(2) == 0)
    def _():  # a window's first block: the state starts from zero
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    a, d = a_ref[...], d_ref[...]

    def eight(k, s):
        at = pl.ds(pl.multiple_of(k * _ROWS, _ROWS), _ROWS)
        xs, dts = x_ref[at, :], dt_ref[at, :]
        bs, cs = b_ref[k], c_ref[k]            # [state, 8]: a column a position
        rows = []
        for t in range(_ROWS):
            dt_t, x_t = dts[t:t + 1, :], xs[t:t + 1, :]
            s = jnp.exp(dt_t * a) * s + bs[:, t:t + 1] * (dt_t * x_t)
            rows.append(jnp.sum(cs[:, t:t + 1] * s, axis=0, keepdims=True))
        y_ref[at, :] = jnp.concatenate(rows, axis=0) + d * xs
        return s

    s_ref[...] = jax.lax.fori_loop(0, block // _ROWS, eight, s_ref[...])


@functools.partial(jax.jit, static_argnames=("window", "block", "tile",
                                             "interpret"))
def _selective_scan(x, dt, bm, cm, a_t, d, *, window: int, block: int,
                    tile: int, interpret: bool):
    p, channels = x.shape
    state = a_t.shape[0]
    n = window // block

    def columns(m):  # [P, state] -> [P / 8, state, 8]
        return m.reshape(p // _ROWS, _ROWS, state).transpose(0, 2, 1)

    rows = lambda c, w, t: (w * n + t, c)
    cols = lambda c, w, t: (w * n + t, 0, 0)
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct((p, channels), jnp.float32),
        grid=(channels // tile, p // window, n),
        in_specs=[pl.BlockSpec((block, tile), rows),
                  pl.BlockSpec((block, tile), rows),
                  pl.BlockSpec((block // _ROWS, state, _ROWS), cols),
                  pl.BlockSpec((block // _ROWS, state, _ROWS), cols),
                  pl.BlockSpec((state, tile), lambda c, w, t: (0, c)),
                  pl.BlockSpec((1, tile), lambda c, w, t: (0, c))],
        out_specs=pl.BlockSpec((block, tile), rows),
        scratch_shapes=[pltpu.VMEM((state, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * p * channels * state,
            transcendentals=p * channels * state,
            bytes_accessed=4 * (3 * p * channels + 2 * p * state
                                + (state + 1) * channels)),
        interpret=interpret,
    )(x, dt, columns(bm), columns(cm), a_t, d.reshape(1, channels))


def selective_scan(x, dt, bm, cm, a_t, d, *, window: int,
                   block: int | None = None, tile: int | None = None,
                   interpret: bool = False):
    """Mamba-1's recurrence inside windows of ``window`` consecutive
    positions, from a zero state at each window's first.

    ``x`` [P, channels] float32 (after the taps and ``silu``) and ``dt`` [P,
    channels] float32 (after the softplus), position-major as their products
    left them; ``bm`` and ``cm`` [P, state] float32, ``W_x``'s columns ``B``
    and ``C``; ``a_t`` [state, channels] float32, ``A`` transposed (negative:
    ``-exp(A_log)^T``); ``d`` [channels] the skip's gain -> ``y`` [P,
    channels] float32, before the gate. ``block`` and ``tile`` are
    ``block_for(window)`` and ``tile_for(channels)`` unless a test says
    otherwise. ``interpret=True`` runs the Pallas interpreter, always the
    caller's explicit choice."""
    return _selective_scan(
        x, dt, bm, cm, a_t, d, window=window,
        block=block or block_for(window), tile=tile or tile_for(x.shape[1]),
        interpret=interpret)
