"""Pallas TPU kernel: everything of a Kimi Delta Attention mixer between
its projections and its output gate, over short windows, on the
projections' own layouts, eight windows to an MXU tile.

``models/ling_backbone.kda_mixer`` runs a gated delta rule inside windows of
``T`` positions (16 in the cell), 32 heads of 128 keys and values, a decay a
channel. Its projections leave position-major, lane-dense float32 matrices
``[P, heads x 128]`` (P = windows x T). As einsums over ``[b, t, h, d]``
(``ling_backbone.kda_one_chunk``, which stays: the reference this kernel is
held to and what runs off the TPU) the core is 8,192 (window, head) problems
of 16 x 16: XLA brings heads in front of positions, loads the MXU with
16-row operands at six passes each, makes seven float32 passes over
``[256, 16, 32, 128]`` and unrolls the 16 x 16 solve into sixteen row
stacks. Here one call reads ``u Wq, u Wk, u Wv`` and the decay's projection
where the products wrote them and writes the head-normed result where the
output gate and ``Wo`` read it; no ``[b, h, t, s]`` array, no ``[.., 16,
16]`` stack and no heads-first or ``[512, 8, 32, 128]`` copy reaches HBM.

**Eight windows to a tile** (ops/pallas/window_attention.py's rule). A grid
step takes ``_TILE`` = 128 consecutive positions (128 / T whole windows)
and a group of heads; per head every product is a full ``[128, 128] x [128,
128]`` MXU tile masked to *same window* by ``where`` (never a multiply: a
masked entry may be ``exp(+80)``). In a head's turn, all float32:

0. the prologue (``taps`` given): the depthwise causal taps (zero before a
   window's first position: a sublane shift masked by ``position % T``),
   ``silu``, the L2 norm over the head's channels and ``q``'s scale;
1. the decay ``g = lower_bound * sigmoid(rate * (f + dt_bias))`` and
   ``sigmoid(beta)``;
2. ``G``, the running sum of ``g`` inside each window (``log2 T`` shifted
   adds down the sublanes, masked by ``position % T``) counted from the
   window's middle position, for the reason ``kda_one_chunk`` gives;
3. ``kk = (k exp(G)) (k exp(-G))^T`` and ``qk = (q exp(G)) (k exp(-G))^T``,
   ``L = beta_s kk`` strictly below the diagonal inside a window, ``read =
   qk`` at and below it;
4. the writes ``U = (I + L)^-1 (beta V)`` by squarings applied to the
   right-hand side: ``(I + L)^-1 = (I - L)(I + L^2)(I + L^4)(I + L^8)``,
   which ends because ``L^T = 0`` inside a window (``log2 T`` factors: three
   squarings and four products against ``[128, head_dim]``);
5. ``o = read U``, and with ``norm`` the head's RMS norm of it.

That is ``128 / T`` times the needed operations (8x at T = 16), ten
products a (tile, head); the bytes are one read of four projections and one
write of the result.

**What bounds it** (PERF.md, section 6, PR 50; a v5e at the cell's shapes).
The ten products are sixty bfloat16 passes a (tile, head), 1.25 ms a
256-row layer at the MXU's peak, and they run at it (the seven of the solve
read 0.88 ms); the arithmetic around them is ~0.9 ms and overlaps in part:
2.05 ms a layer in the 256-row step (0.52 in the 64-row one), where the
einsum path's taps, core and re-layouts read 4.6. Substitution on the
VPU, which the einsum form runs, has no cheap form here: a window's rows
lie down the sublanes and a row's coefficients along the lanes, so every
step needs a lane broadcast a window. Writing the three-way bfloat16 split
out and sharing an operand's split between its two products read the same
time as Mosaic's own (2.08 against 2.02 ms alone), so the products are
Mosaic's.

**The same result, not a cheaper one.** Operands are float32 and are not
rounded: every product is Mosaic's float32 contraction at
``Precision.HIGHEST`` (six bfloat16 passes, as XLA's). Only the order of
float32 accumulation differs from ``kda_one_chunk``: the running sum by
doubling, the solve by squarings applied to the writes where the einsum
form substitutes rows of the inverse, a product's 128 terms of which 128 - T
are exact zeros. A value that is not finite does not stay in its window
(``0 x NaN`` is ``NaN``), as in the window attention kernel.

The loop over a step's heads is rolled, two heads a turn (a serving process
traces the step at every rung at boot: PERF.md, PR 37).
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

# Heads a grid step takes at most: 4, 8, 16 and 32 read the same time on a
# v5e at the cell's shapes (PERF.md, section 6, PR 50).
_HEADS_PER_STEP = 8

# Heads that a turn of the loop takes side by side (their chains of ten
# products are independent: the scheduler overlaps one head's products with
# another's arithmetic; 2.13 ms a layer at one, 2.02 at two, 1.96 at four at
# two and a half times the kernel's compile: PERF.md, PR 50).
_HEADS_PER_TURN = 2

# What the kernel may ask of the v5e's 128 MiB of VMEM.
_VMEM_CAP = 64 * 2**20

# beside the sum of squares the L2 norm divides by, as the published kernel
_UNIT_EPS = 1e-6


def _heads_per_step(heads: int) -> int:
    return max(g for g in range(1, min(heads, _HEADS_PER_STEP) + 1)
               if heads % g == 0)


def _vmem(group: int, heads: int, hd: int) -> int:
    """Both buffers of a step's blocks (four inputs and the result, a
    group's columns each; ``beta``; the per-channel rows), a turn's heads'
    float32 tiles several times over, and room to spare."""
    blocks = _TILE * (5 * group * hd + max(heads, _LANES)) * 4 + 16 * group * hd * 4
    turn = _HEADS_PER_TURN * 24 * _TILE * max(hd, _TILE) * 4
    return 2 * blocks + turn + 4 * 2**20


def declines(positions: int, *, heads: int, head_dim: int, window: int) -> str:
    """Why ``delta_window`` does not take ``positions`` positions in windows
    of ``window`` with ``heads`` heads of ``head_dim`` keys and values, ""
    where it does: the reason the caller announces beside ``one chunk by
    einsums``. It takes heads of whole 128-lane vregs, whole windows of
    whole 8-row vregs to a tile, whole tiles, and a step's blocks inside
    VMEM; anything else takes the caller's einsums."""
    if head_dim <= 0 or head_dim % _LANES:
        return f"head width {head_dim} is not whole {_LANES}-lane vregs"
    if window <= 0 or _TILE % window or window % 8:
        return (f"windows of {window} are not whole 8-row vregs that "
                f"divide a tile of {_TILE}")
    if positions <= 0 or positions % _TILE:
        return f"{positions} positions are not whole tiles of {_TILE}"
    if heads <= 0:
        return f"{heads} heads"
    need = _vmem(_heads_per_step(heads), heads, head_dim)
    if need > _VMEM_CAP:
        return f"a step's blocks take {need} of {_VMEM_CAP} bytes of VMEM"
    return ""


def _kernel(q_ref, k_ref, v_ref, f_ref, beta_ref, rate_ref, bias_ref, *rest,
            window: int, hd: int, lower_bound: float, scale: float,
            eps: float | None):
    f32 = jnp.float32
    *rest, o_ref = rest
    # the head norm's gain, where the kernel norms; the three convolutions'
    # taps, with the prologue
    gain_ref = rest.pop() if eps is not None else None
    taps_ref = rest
    heads_here = o_ref.shape[1] // hd
    turn = max(t for t in range(1, _HEADS_PER_TURN + 1) if heads_here % t == 0)
    group = pl.program_id(1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 1)
    same = row // window == col // window
    below = jnp.logical_and(same, col < row)    # an earlier key of the window
    lower = jnp.logical_and(same, col <= row)
    # a position's place in its window, over a head's lanes
    place = jax.lax.broadcasted_iota(jnp.int32, (_TILE, hd), 0) % window
    head_lane = jax.lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)
    betas = jax.nn.sigmoid(beta_ref[...].astype(f32))       # [tile, heads]

    def product(x, y, transposed=False):
        dims = (((1,), (1 if transposed else 0,)), ((), ()))
        return jax.lax.dot_general(x, y, dims, preferred_element_type=f32,
                                   precision=jax.lax.Precision.HIGHEST)

    def earlier(x, back):
        """``x`` of ``back`` positions before, zero before the window's
        first position."""
        return jnp.where(place >= back, pltpu.roll(x, back, 0), 0.0)

    def conv(x, taps):
        """The causal taps [n_taps, hd] over a window's positions, then
        ``silu`` (``decoder_parts.causal_taps``'s sum, tap by tap)."""
        n_taps = taps.shape[0]
        c = x * taps[n_taps - 1:n_taps]
        for back in range(1, n_taps):
            c = c + earlier(x, back) * taps[n_taps - 1 - back:n_taps - back]
        return c * jax.nn.sigmoid(c)

    def unit(x):  # L2 over the head's channels
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _UNIT_EPS)

    def one_head(h):
        cols = pl.ds(pl.multiple_of(h * hd, _LANES), hd)
        q, k, v = q_ref[:, cols], k_ref[:, cols], v_ref[:, cols]
        if taps_ref:
            q = unit(conv(q, taps_ref[0][:, cols])) * scale
            k = unit(conv(k, taps_ref[1][:, cols]))
            v = conv(v, taps_ref[2][:, cols])
        g = lower_bound * jax.nn.sigmoid(
            rate_ref[:, cols] * (f_ref[:, cols] + bias_ref[:, cols]))
        # the running sum inside each window, by doubling
        total, back = g, 1
        while back < window:
            total = total + earlier(total, back)
            back *= 2
        # ... counted from the window's middle position
        mid = window // 2
        total = jnp.concatenate(
            [total[w:w + window] - total[w + mid:w + mid + 1]
             for w in range(0, _TILE, window)], axis=0)
        shrink = jnp.exp(total)
        k_out = k * jnp.exp(-total)                           # k_r exp(-G_r)
        kk = product(k * shrink, k_out, transposed=True)
        qk = product(q * shrink, k_out, transposed=True)
        # this head's beta down the positions
        beta = jnp.sum(jnp.where(head_lane == group * heads_here + h, betas, 0.0),
                       axis=1, keepdims=True)
        low = jnp.where(below, kk, 0.0) * beta                # L
        read = jnp.where(lower, qk, 0.0)
        # U = (I - L)(I + L^2)(I + L^4)... (beta V): L^window = 0
        u = v * beta
        u = u - product(low, u)
        span = 2
        while span < window:
            low = product(low, low)
            u = u + product(low, u)
            span *= 2
        o = product(read, u)
        if gain_ref is not None:  # the head's RMS norm (decoder_parts.rms_norm)
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + eps) * gain_ref[...]
        o_ref[:, cols] = o

    def one_turn(i, carry):
        # independent heads side by side in one loop body: one head's
        # products overlap another's arithmetic
        for j in range(turn):
            one_head(i * turn + j)
        return carry

    jax.lax.fori_loop(0, heads_here // turn, one_turn, 0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "window", "lower_bound", "eps", "group", "interpret"))
def _delta_window(q, k, v, f, beta, rate, bias, taps, gain, *, heads: int,
                  window: int, lower_bound: float, eps: float | None,
                  group: int, interpret: bool):
    p = q.shape[0]
    hd = q.shape[1] // heads
    by_group = lambda i, g: (i, g)
    cols_of_group = lambda i, g: (0, g)
    wide = pl.BlockSpec((_TILE, group * hd), by_group)
    operands = [q, k, v, f, beta, rate, bias]
    in_specs = [wide, wide, wide, wide,
                pl.BlockSpec((_TILE, heads), lambda i, g: (i, 0)),
                pl.BlockSpec((1, group * hd), cols_of_group),
                pl.BlockSpec((1, group * hd), cols_of_group)]
    for t in taps:
        operands.append(t)
        in_specs.append(pl.BlockSpec((t.shape[0], group * hd), cols_of_group))
    if eps is not None:
        operands.append(gain)
        in_specs.append(pl.BlockSpec((1, hd), lambda i, g: (0, 0)))
    steps = window.bit_length() - 1  # doublings: log2(window)
    return pl.pallas_call(
        functools.partial(_kernel, window=window, hd=hd,
                          lower_bound=lower_bound, scale=hd ** -0.5, eps=eps),
        out_shape=jax.ShapeDtypeStruct((p, heads * hd), jnp.float32),
        grid=(p // _TILE, heads // group),
        in_specs=in_specs,
        out_specs=wide,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, _vmem(group, heads, hd))),
        cost_estimate=pl.CostEstimate(
            flops=2 * p * heads * _TILE * ((3 + steps) * hd + (steps - 1) * _TILE),
            transcendentals=(3 + len(taps)) * p * heads * hd,
            bytes_accessed=5 * p * heads * hd * 4 + p * heads * 4),
        interpret=interpret,
    )(*operands)


def delta_window(q, k, v, f, beta, a_log, dt_bias, taps=None, norm=None, *,
                 heads: int, window: int, lower_bound: float,
                 interpret: bool = False):
    """The gated delta rule inside windows of ``window`` consecutive
    positions, from zero state, every head on its own 128-lane columns.

    ``q``, ``k``, ``v`` [P, heads x hd] float32, position-major: with
    ``taps`` None they come convolved, activated and normalised (``q``
    scaled), as ``kda_one_chunk`` takes them; with ``taps`` = the three
    depthwise convolutions' ``(tq, tk, tv)``, each [heads x hd, n_taps],
    they come raw (``u Wq, u Wk, u Wv``) and the taps, ``silu``, the L2
    norm a head and ``q``'s ``hd ** -0.5`` are applied here. ``f`` [P,
    heads x hd] is the decay's projection ``u Wf`` and ``beta`` [P, heads]
    the write strength's ``u Wb``, both raw; ``a_log`` [heads], ``dt_bias``
    [heads x hd]: ``g = lower_bound * sigmoid(exp(a_log) * (f + dt_bias))``
    a channel, ``sigmoid(beta)`` a head -> ``o`` [P, heads x hd] float32,
    what ``kda_one_chunk(q, k, v, g, sigmoid(beta))`` gives, laid where the
    gated norm reads it; with ``norm`` = ``(gain [hd], eps)`` the head norm
    of the gated norm is applied here too and the result is
    ``decoder_parts.rms_norm(o, gain, eps)`` a head (the gate and ``Wo``
    stay the caller's). P is whole tiles of 128 positions (``declines``
    says what else it takes). ``interpret=True`` runs the Pallas
    interpreter, the only way to run the kernel off the TPU, and always the
    caller's explicit choice."""
    hd = q.shape[1] // heads
    f32 = jnp.float32
    rate = jnp.repeat(jnp.exp(a_log.astype(f32)), hd)[None, :]
    taps = () if taps is None else tuple(t.astype(f32).T for t in taps)
    gain, eps = (None, None) if norm is None else (
        norm[0].astype(f32)[None, :], float(norm[1]))
    return _delta_window(q, k, v, f, beta, rate, dt_bias.astype(f32)[None, :],
                         taps, gain, heads=heads, window=window,
                         lower_bound=float(lower_bound), eps=eps,
                         group=_heads_per_step(heads), interpret=interpret)
