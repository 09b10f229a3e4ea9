"""Pallas TPU kernel: everything of a Mamba-2 mixer between its two
projections, over short windows, on the in-projection's own layout, eight
windows to an MXU tile.

``models/falconh1_backbone.ssm_mixer`` runs a selective state-space
recurrence inside windows of ``T`` positions (16 in the cell): 32 heads of
128 channels, a state of 256 a channel, ``B`` and ``C`` in 2 groups. Its
in-projection leaves one position-major float32 matrix ``p`` ``[P, 9248]``
(P = windows x T) whose column segments ``[z | x | B | C | dt]`` start at 0,
4096, 8192, 8704 and 9216: whole 128-lane vregs but for ``dt``'s 32 columns.
By XLA (``falconh1_backbone._core_by_xla`` with ``ssd_one_chunk`` its core,
which stays: the reference this kernel is held to and what runs off the TPU)
the part between the projections is float32 slices of ``p``, the taps as a
pass over ``[256, 16, 5120]``, two ``HIGHEST`` einsums with 16-row operands
behind ``[256, 16, 32, 128]`` re-layouts, and the gate and the grouped norm
as two more passes: 2.1 ms a layer in the 256-row step for 0.6 GFLOP. Here
one call reads ``z``, ``x``, ``B``, ``C`` and ``dt`` where the product wrote
them (five block views of ``p``; ``dt``'s is the matrix's last, part-filled
128 columns, turned heads first inside: handed over as a transposed slice
it made XLA write ``p`` channels-major and copy it whole for the call) and
writes the out-projection's operand ``[P, 4096]`` in the dtype that product
rounds it to.

**Eight windows to a tile** (ops/pallas/window_attention.py's and
delta_window.py's rule). A grid step takes ``_TILE`` = 128 consecutive
positions (128 / T whole windows) and one group: its ``B`` and ``C``, its
heads' ``z`` and ``x``, and the group's share of the result, over which the
grouped norm runs. Every product is a full MXU tile masked to *same window*
by ``where`` (never a multiply). In a step, all float32:

0. the depthwise causal taps with their bias over ``[x | B | C]`` (zero
   before a window's first position: a sublane shift masked by ``position %
   T``), then ``silu``;
1. ``dt = softplus(p_dt + dt_bias)`` and ``c``, the running sum of ``dt *
   -exp(A_log)`` inside each window, for every head at once with the
   heads along the lanes (``log2 T`` shifted adds down the sublanes, masked
   by ``position % T``), and both turned heads first, so that a head's
   ``c_s`` and ``dt_s`` lie along the lanes as its ``c_t`` lies down the
   sublanes;
2. ``G = C B^T`` of the group, one ``[128, state] x [state, 128]`` product;
3. a head: ``y = (G * exp(c_t - c_s) * dt_s) x + D x`` for ``s <= t`` of
   one window, one ``[128, 128] x [128, 128]`` product (``c_t - c_s <= 0``
   wherever it is kept; what is not kept goes through ``where`` before the
   ``exp``, as ``ssd_one_chunk``'s ``-inf`` does, and once more after the
   products of the three factors, so that nothing of ``B``, ``C`` or ``dt``
   of another window is read);
4. ``g = y * silu(z)``, kept in VMEM while the group's sum of squares
   gathers; then the RMSNorm over the group's channels with its gain, and the
   rounding to the out-projection's operand dtype.

That is ``128 / T`` times the needed operations (8x at T = 16): 12 passes
of the MXU a (tile, group) and 6 a (tile, head), 6,912 a 256-row layer,
0.15 ms at the MXU's peak; the bytes are one read of ``p``'s four segments
and one write of the result at 2 bytes, 185 MB a 256-row layer.

**What bounds it** (PERF.md, sections 5 and 6, PR 55; a v5e at the cell's
shapes). Its bytes: 0.376 ms a call in the 256-row step (0.10 in the 64-row
one), 60% of the HBM's peak, where the XLA form's taps, core, gate and
slices read 2.2; the products and the vector work hide under the DMA but
for ~0.11 ms, and leaving out any one part of a head's turn moves the call
by under a tenth. The in-projection has to write ``p`` positions-major for
these block views, which costs its product 0.28 ms a layer against the
channels-major form XLA chose for the einsum path (PERF.md, Open question
23a).

**The same result, not a cheaper one.** Operands are float32 and are not
rounded: both products are Mosaic's float32 contraction at
``Precision.HIGHEST`` (six bfloat16 passes, as XLA's). Only the order of
float32 accumulation differs from ``ssd_one_chunk``: the running sum by
doubling, a product's 128 terms of which 128 - T are exact zeros, the
norm's mean as sixteen heads' partial sums. A value of ``x`` that is not
finite does not stay in its window (``0 x NaN`` is ``NaN`` in the second
product), as in the two other window kernels; ``z``, ``B``, ``C`` and
``dt`` of another window are never read.

The loop over a step's heads is rolled (a serving process traces the step
at every rung at boot: PERF.md, PRs 37 and 50).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions a grid step takes: the MXU's tile. Windows never straddle one.
_TILE = 128

_LANES = 128

# Heads that a turn of the loop takes side by side: one head's product
# overlaps another's arithmetic (the call alone at the cell's shapes: 0.93 ms
# at one, 0.84 at two, 0.82 at four at a quarter more compile: PERF.md,
# section 5, PR 55).
_HEADS_PER_TURN = 2

# What the kernel may ask of the v5e's 128 MiB of VMEM.
_VMEM_CAP = 64 * 2**20


def _vmem(group_width: int, head_dim: int, state: int, out_bytes: int) -> int:
    """Both buffers of a step's blocks (``z`` and ``x`` of a group's
    channels, ``B`` and ``C``, ``dt`` of every head, the result; the
    per-channel rows), the gated result held for the norm, a turn's float32
    tiles several times over, and room to spare."""
    blocks = (_TILE * (2 * group_width + 2 * state + _LANES) * 4
              + _TILE * group_width * out_bytes
              + 8 * (group_width + 2 * state) * 4 + 2 * _LANES * 4)
    held = _TILE * group_width * 4
    turn = (_HEADS_PER_TURN * 16 * _TILE * max(head_dim, _TILE)
            + 8 * _TILE * state) * 4
    return 2 * blocks + held + turn + 4 * 2**20


def declines(positions: int, *, heads: int, head_dim: int, state: int,
             groups: int, window: int, taps: int) -> str:
    """Why ``ssd_window`` does not take ``positions`` positions in windows
    of ``window`` with ``heads`` heads of ``head_dim`` channels, ``groups``
    groups of ``state`` and ``taps`` taps, "" where it does: the reason the
    caller announces beside the dual form. It takes heads of whole 128-lane
    vregs, a state of whole vregs, segments that start on whole blocks of
    ``p``, at most 128 heads in whole groups, windows of whole 8-row vregs
    that divide a tile and hold the taps, whole tiles, and a step's blocks
    inside VMEM; anything else takes the caller's einsums."""
    if head_dim <= 0 or head_dim % _LANES:
        return f"head width {head_dim} is not whole {_LANES}-lane vregs"
    if state <= 0 or state % _LANES:
        return f"a state of {state} is not whole {_LANES}-lane vregs"
    if groups <= 0 or heads <= 0 or heads % groups or heads > _LANES:
        return f"{heads} heads in {groups} groups (whole groups, at most {_LANES})"
    if (heads * head_dim) % state:
        return (f"B's segment starts {heads * head_dim} channels after x's, not "
                f"on a whole block of {state}")
    if window <= 0 or _TILE % window or window % 8:
        return (f"windows of {window} are not whole 8-row vregs that "
                f"divide a tile of {_TILE}")
    if not 1 <= taps <= window:
        return f"{taps} taps over windows of {window}"
    if positions <= 0 or positions % _TILE:
        return f"{positions} positions are not whole tiles of {_TILE}"
    need = _vmem(heads // groups * head_dim, head_dim, state, 4)
    if need > _VMEM_CAP:
        return f"a step's blocks take {need} of {_VMEM_CAP} bytes of VMEM"
    return ""


def _kernel(z_ref, x_ref, b_ref, c_ref, dt_ref, dt_bias_ref, rate_ref,
            tx_ref, tb_ref, tc_ref, bx_ref, bb_ref, bc_ref, skip_ref, gain_ref,
            o_ref, held_ref, sums_ref, steps_ref, *, window: int, hd: int,
            heads: int, eps: float):
    f32 = jnp.float32
    heads_here = o_ref.shape[1] // hd
    turn = max(t for t in range(1, _HEADS_PER_TURN + 1) if heads_here % t == 0)
    first = pl.program_id(1) * heads_here       # this group's first head
    row = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 1)
    # an earlier or the same position of the same window
    lower = jnp.logical_and(row // window == col // window, col <= row)

    def product(x, y, transposed=False):
        dims = (((1,), (1 if transposed else 0,)), ((), ()))
        return jax.lax.dot_general(x, y, dims, preferred_element_type=f32,
                                   precision=jax.lax.Precision.HIGHEST)

    def conv(x, taps, bias):
        """The causal taps [n_taps, C] over a window's positions, the bias,
        then ``silu`` (``decoder_parts.causal_taps``'s sum, tap by tap)."""
        n_taps = taps.shape[0]
        place = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) % window
        c = x * taps[n_taps - 1:n_taps]
        for back in range(1, n_taps):
            # ``x`` of ``back`` positions before, zero before the window's first
            earlier = jnp.where(place >= back, pltpu.roll(x, back, 0), 0.0)
            c = c + earlier * taps[n_taps - 1 - back:n_taps - back]
        c = c + bias
        return c * jax.nn.sigmoid(c)

    # every head's step and its running sum inside each window, positions
    # down the sublanes and heads along the lanes (``dt``'s block of ``p``
    # runs past the matrix's last column: what lies there is dropped before
    # anything reads it), then both turned heads first
    v = jnp.where(col < heads, dt_ref[...], 0.0) + dt_bias_ref[...]
    steps = jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))  # softplus
    place = row % window
    total, back = steps * rate_ref[...], 1
    while back < window:
        total = total + jnp.where(place >= back, pltpu.roll(total, back, 0), 0.0)
        back *= 2
    steps_ref[...] = steps.T
    sums_ref[0] = total.T
    sums_ref[1] = total

    g_all = jnp.where(lower, product(conv(c_ref[...], tc_ref[...], bc_ref[...]),
                                     conv(b_ref[...], tb_ref[...], bb_ref[...]),
                                     transposed=True), 0.0)

    def one_head(h, squares):
        cols = pl.ds(pl.multiple_of(h * hd, _LANES), hd)
        head = first + h
        x = conv(x_ref[:, cols], tx_ref[:, cols], bx_ref[:, cols])
        c_s = sums_ref[0, pl.ds(head, 1), :]                 # [1, tile]
        dt_s = steps_ref[pl.ds(head, 1), :]
        c_t = jnp.sum(jnp.where(col == head, sums_ref[1], 0.0), axis=1,
                      keepdims=True)                         # [tile, 1]
        decay = jnp.exp(jnp.where(lower, c_t - c_s, -jnp.inf))
        mix = jnp.where(lower, g_all * decay * dt_s, 0.0)
        y = product(mix, x) + skip_ref[:, cols] * x
        z = z_ref[:, cols]
        g = y * (z * jax.nn.sigmoid(z))
        held_ref[:, cols] = g
        return squares + g * g

    def one_turn(i, squares):
        # independent heads side by side in one loop body
        for j in range(turn):
            squares = one_head(i * turn + j, squares)
        return squares

    squares = jax.lax.fori_loop(0, heads_here // turn, one_turn,
                                jnp.zeros((_TILE, hd), f32))
    # the RMSNorm over the group's channels (decoder_parts.rms_norm)
    scale = jax.lax.rsqrt(jnp.sum(squares, axis=-1, keepdims=True)
                          / (heads_here * hd) + eps)
    o_ref[...] = (held_ref[...] * scale * gain_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "state", "groups", "window", "eps", "out_dtype", "interpret"))
def _ssd_window(p, dt_bias, rate, taps, bias, skip, gain, *, heads: int,
                state: int, groups: int, window: int, eps: float, out_dtype,
                interpret: bool):
    positions = p.shape[0]
    width = gain.shape[1]
    hd = width // heads
    gw = width // groups                    # a group's channels
    n_taps = taps.shape[0]
    # block columns of ``p``: [z | x | B | C] start at 0, width, 2 width and
    # 2 width + groups x state
    b_at = 2 * width // state
    group_cols = lambda at: pl.BlockSpec((_TILE, gw), lambda i, g: (i, at + g))
    state_cols = lambda at: pl.BlockSpec((_TILE, state), lambda i, g: (i, at + g))
    # the per-channel rows over the convolution's channels [x | B | C]
    row_group = lambda rows, at: pl.BlockSpec((rows, gw), lambda i, g: (0, at + g))
    row_state = lambda rows, at: pl.BlockSpec((rows, state),
                                              lambda i, g: (0, at + g))
    conv_b_at = width // state
    dt_at = (2 * width + 2 * groups * state) // _LANES
    per_head = pl.BlockSpec((1, _LANES), lambda i, g: (0, 0))
    out_bytes = jnp.dtype(out_dtype).itemsize
    return pl.pallas_call(
        functools.partial(_kernel, window=window, hd=hd, heads=heads, eps=eps),
        out_shape=jax.ShapeDtypeStruct((positions, width), out_dtype),
        grid=(positions // _TILE, groups),
        in_specs=[
            group_cols(0), group_cols(groups),                       # z, x
            state_cols(b_at), state_cols(b_at + groups),             # B, C
            pl.BlockSpec((_TILE, _LANES), lambda i, g: (i, dt_at)),  # dt
            per_head, per_head,                                      # dt_bias, A
            row_group(n_taps, 0), row_state(n_taps, conv_b_at),      # taps
            row_state(n_taps, conv_b_at + groups),
            row_group(1, 0), row_state(1, conv_b_at),                # their bias
            row_state(1, conv_b_at + groups),
            row_group(1, 0), row_group(1, 0),                        # D, gn
        ],
        out_specs=pl.BlockSpec((_TILE, gw), lambda i, g: (i, g)),
        scratch_shapes=[pltpu.VMEM((_TILE, gw), jnp.float32),
                        pltpu.VMEM((2, _LANES, _TILE), jnp.float32),
                        pltpu.VMEM((_LANES, _TILE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, _vmem(gw, hd, state, out_bytes))),
        cost_estimate=pl.CostEstimate(
            flops=2 * positions * _TILE * (groups * state + width),
            transcendentals=positions * (2 * width + 2 * groups * state
                                         + heads * _TILE),
            bytes_accessed=positions * ((2 * width + 2 * groups * state) * 4
                                        + width * out_bytes)),
        interpret=interpret,
    )(p, p, p, p, p, dt_bias, rate, taps, taps, taps, bias, bias, bias,
      skip, gain)


def ssd_window(p, taps, conv_bias, dt_bias, a_log, d_skip, gain, *, heads: int,
               state: int, groups: int, window: int, eps: float,
               out_dtype=jnp.float32, interpret: bool = False):
    """A Mamba-2 mixer between its projections, inside windows of
    ``window`` consecutive positions, from zero state.

    ``p`` [P, 2 width + 2 groups x state + heads] float32 is the
    in-projection's result with its multipliers applied, columns ``[z | x |
    B | C | dt]`` (width = heads x head_dim); ``taps`` [width + 2 groups x
    state, n_taps] and ``conv_bias`` the depthwise causal convolution over
    ``[x | B | C]``; ``dt_bias``, ``a_log``, ``d_skip`` [heads]; ``gain``
    [width], the grouped norm's -> [P, width] in ``out_dtype``: what
    ``ssd_one_chunk`` gives on ``silu(causal_taps(.))`` with ``dt =
    softplus(p_dt + dt_bias)``, times ``silu(z)``, RMS-normed over each
    group's channels with ``gain`` and ``eps``, laid where the
    out-projection reads it. P is whole tiles of 128 positions (``declines``
    says what else it takes). ``interpret=True`` runs the Pallas
    interpreter, the only way to run the kernel off the TPU, and always the
    caller's explicit choice."""
    f32 = jnp.float32
    per_head = lambda a: jnp.pad(a.astype(f32), (0, _LANES - heads))[None, :]
    per_channel = lambda a: jnp.repeat(a.astype(f32), gain.shape[0] // heads)[None, :]
    return _ssd_window(
        p, per_head(dt_bias), per_head(-jnp.exp(a_log.astype(f32))),
        taps.astype(f32).T, conv_bias.astype(f32)[None, :], per_channel(d_skip),
        gain.astype(f32)[None, :], heads=heads, state=state, groups=groups,
        window=window, eps=float(eps), out_dtype=jnp.dtype(out_dtype),
        interpret=interpret)
