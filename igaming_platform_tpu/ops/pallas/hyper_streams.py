"""Pallas TPU kernels: the residual path of a hyper-connected sublayer as two
passes over tiles of positions whose ``n`` streams lie in VMEM.

``models/xing_backbone.hyper_sublayer`` keeps a position's state in ``n``
float32 streams ``[P, hidden]`` (four of 3,584 in the cell: 235 MB a copy at
4,096 positions). Everything the residual path does is a position's own: the
norm is over a position's ``n x hidden`` numbers, the maps are a position's,
the read and the write mix a position's streams. As ``jax.numpy`` functions
(``decoder_parts.hyper_maps``, ``sinkhorn``, ``hyper_read``, ``hyper_write``,
``stream_squares``, which stay: the reference these kernels are held to and
what runs off the TPU) XLA reads the streams four times a sublayer and writes
them once, each pass at the bandwidth's peak, and runs the Sinkhorn rounds as
~80 small fusions. Here a grid step holds ``TILE`` positions with all ``n``
of their streams, so a sublayer reads its streams twice and writes them once:

1. ``maps_and_read``: the product of the tile's streams with ``phi`` (laid
   columns-first, ``[2 n + n^2, n x hidden]``: its 24 columns are sublanes and
   not 24 of 128 lanes), the division by the norm, the sigmoids, the clip,
   ``exp`` and all the Sinkhorn rounds on the tile's ``[2 n + n^2, tile]``
   values, positions along the lanes, and ``u = sum_i pre[i] x[i]`` from the
   tile as it lies in VMEM -> ``u`` [P, hidden] and the maps ``[2 n + n^2,
   P]`` (rows: ``pre``, ``post``, ``res`` row-major, as ``hyper_maps`` lays
   ``z``; ``split_maps`` names them).
2. ``write``: ``x'[i] = sum_j res[i, j] x[j] + post[i] y`` for every ``i`` from
   one read of the streams and ``y``, written over the streams it read
   (``input_output_aliases``), and ``stream_squares(x')`` from the same tile
   for the next sublayer's norm.

**Layout.** The streams' tiles lie positions down the sublanes, channels
along the lanes; the maps lie positions along the lanes (a row an entry),
which is how the rounds want them: a round's sums are adds of rows. What
multiplies a stream is a position's scalar, a sublane's: the maps' block is
turned once a tile (one ``[128, 128]`` transpose) and a column of it is
broadcast along the lanes, eight positions (one vreg's sublanes) at a turn of
the sweep over the tile, ``_CHUNK`` lanes of every stream at a time.

**What bounds them** (PERF.md, sections 5 and 6, PR 53; a v5e at the cell's
shapes). The product is ``phi``'s rows against the tile, ``[24, K] x [128,
K]^T``: with ``phi`` as the left operand the result lies positions along the
lanes and the six bfloat16 passes hide under the tile's DMA whole (a read of
the four streams alone takes 0.344 ms at 4,096 positions, with the product
0.345; the other orientation 0.506). Pass 1 is then bound by what follows the
product inside a grid step, the rounds (rows of one sublane: ~1.3 us a tile)
and the sweep: 0.451 ms a call in the 256-row step where its bytes alone are
~0.43; pass 2 by its DMA, 0.624 ms. The ``head/hc`` scopes read 10.8 ms of
that step where the ``jax.numpy`` path read 16.4. At 1,024 positions XLA
keeps the 59 MB of streams in VMEM either way and the kernels gain nothing.

**The same work.** Streams float32 in HBM and in VMEM; the product on
unrounded float32 operands at ``Precision.HIGHEST``; every round two true
divisions with ``hc_eps``, no early exit; the clip before ``exp``; ``u`` and
``x'`` summed in float32 in the order the ``jax.numpy`` forms sum them. Only
the order of float32 accumulation inside the product and inside the sums of
squares differs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions a grid step holds, with all their streams.
TILE = 128

_LANES = 128

# Positions a turn of a sweep over the tile takes: one vreg's sublanes.
_ROWS = 8

# Lanes of every stream a turn takes at a time, at most.
_CHUNK = 512

# What a kernel may ask of the v5e's 128 MiB of VMEM (delta_window.py's cap).
_VMEM_CAP = 64 * 2**20


def columns(n: int) -> int:
    """Rows of the maps: ``pre`` and ``post`` [n] and ``res`` [n, n]."""
    return 2 * n + n * n


def _chunk(hidden: int) -> int:
    return max(c for c in range(_LANES, _CHUNK + 1, _LANES) if hidden % c == 0)


def _vmem(n: int, hidden: int) -> int:
    """Both buffers of the larger pass's blocks (the write: ``n`` streams
    and ``y`` in, ``n`` streams out), ``phi`` twice, the small blocks and
    room to spare."""
    block = TILE * hidden * 4
    return (2 * (2 * n + 1) * block + 2 * columns(n) * n * hidden * 4
            + 8 * 2**20)


def declines(positions: int, hidden: int, n: int, dtype) -> str:
    """Why the two kernels do not take ``n`` streams [positions, hidden] of
    ``dtype``, "" where they do: the reason the caller announces beside
    ``xla``. They take float32 streams of whole 128-lane vregs, whole tiles
    of 128 positions, maps whose rows are whole 8-row vregs (``n`` even) and
    a step's blocks inside VMEM; anything else takes the caller's
    ``jax.numpy`` functions."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"streams of {jnp.dtype(dtype).name} are not float32"
    if hidden <= 0 or hidden % _LANES:
        return f"hidden {hidden} is not whole {_LANES}-lane vregs"
    if positions <= 0 or positions % TILE:
        return f"{positions} positions are not whole tiles of {TILE}"
    if n <= 0 or columns(n) % _ROWS:
        return f"the maps of {n} streams are not whole {_ROWS}-row vregs"
    need = _vmem(n, hidden)
    if need > _VMEM_CAP:
        return f"a step's blocks take {need} of {_VMEM_CAP} bytes of VMEM"
    return ""


def split_maps(maps, n: int):
    """The maps ``[2 n + n^2, P]`` as ``hyper_maps`` returns them: ``(pre [n,
    P], post [n, P], res [n, n, P])``."""
    return maps[:n], maps[n:2 * n], maps[2 * n:].reshape(n, n, -1)


def _turned(block):
    """A block ``[rows <= 128, 128]`` of the maps turned: ``[128, 128]``
    whose column ``k`` is the block's row ``k``, positions down the
    sublanes."""
    rows = block.shape[0]
    if rows < TILE:
        block = jnp.concatenate(
            [block, jnp.zeros((TILE - rows, block.shape[1]), block.dtype)], axis=0)
    return block.T


def _across(col, width: int):
    """A column ``[rows, 1]`` along ``width`` lanes."""
    return jnp.broadcast_to(col, (col.shape[0], width))


def _maps_read_kernel(*refs, n: int, rounds: int, eps: float, hc_eps: float,
                      clip: tuple[float, float], has_squares: bool):
    f32 = jnp.float32
    xs, rest = refs[:n], list(refs[n:])
    sq_ref = rest.pop(0) if has_squares else None
    phi_ref, ab_ref, u_ref, maps_ref, cols_ref = rest
    hidden = u_ref.shape[1]
    chunk = _chunk(hidden)
    blocks = TILE // _ROWS

    # the product with phi, [2 n + n^2, tile]: positions along the lanes
    m = sum(jax.lax.dot_general(
        phi_ref[:, i * hidden:(i + 1) * hidden], xs[i][...],
        (((1,), (1,)), ((), ())), preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST) for i in range(n))

    if has_squares:
        squares = sq_ref[...]                                # [1, tile]
    else:
        # this tile's own: a sweep that sums x^2 lane by lane, then the lanes
        def squared(r, carry):
            rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
            part = jnp.zeros((_ROWS, chunk), f32)
            for x_ref in xs:
                for h in range(0, hidden, chunk):
                    x = x_ref[rows, h:h + chunk]
                    part = part + x * x
            cols_ref[rows, :] = _across(jnp.sum(part, axis=1, keepdims=True),
                                        _LANES)
            return carry

        jax.lax.fori_loop(0, blocks, squared, 0)
        squares = cols_ref[...].T[:1]                        # [1, tile]
    m = m * jax.lax.rsqrt(squares / (n * hidden) + eps)
    z = m * ab_ref[:, 0:1] + ab_ref[:, 1:2]
    row = lambda k: z[k:k + 1]                               # [1, tile]
    pre = [jax.nn.sigmoid(row(i)) for i in range(n)]
    post = [2.0 * jax.nn.sigmoid(row(n + i)) for i in range(n)]
    # the rounds on rows: res[i][j] is what stream i takes of stream j
    res = [[jnp.exp(jnp.clip(row(2 * n + i * n + j), *clip)) for j in range(n)]
           for i in range(n)]

    def total(parts):
        return functools.reduce(lambda a, b: a + b, parts[1:], parts[0])

    def one_round(_, res):
        down = [total([res[i][j] for i in range(n)]) + hc_eps for j in range(n)]
        res = [[res[i][j] / down[j] for j in range(n)] for i in range(n)]
        along = [total(res[i]) + hc_eps for i in range(n)]
        return [[res[i][j] / along[i] for j in range(n)] for i in range(n)]

    res = jax.lax.fori_loop(0, rounds, one_round, res)
    maps = jnp.concatenate(pre + post + [r for rows in res for r in rows], axis=0)
    maps_ref[...] = maps
    cols_ref[...] = _turned(maps)

    def read(r, carry):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        cols = cols_ref[rows, :]
        by = [_across(cols[:, i:i + 1], chunk) for i in range(n)]
        for h in range(0, hidden, chunk):
            u = by[0] * xs[0][rows, h:h + chunk]
            for i in range(1, n):
                u = u + by[i] * xs[i][rows, h:h + chunk]
            u_ref[rows, h:h + chunk] = u
        return carry

    jax.lax.fori_loop(0, blocks, read, 0)


def _write_kernel(*refs, n: int):
    f32 = jnp.float32
    xs, (y_ref, maps_ref) = refs[:n], refs[n:n + 2]
    outs, (sq_ref, cols_ref) = refs[n + 2:2 * n + 2], refs[2 * n + 2:]
    hidden = y_ref.shape[1]
    chunk = _chunk(hidden)
    cols_ref[...] = _turned(maps_ref[...])

    def write(r, carry):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        cols = cols_ref[rows, :]
        post = [_across(cols[:, n + i:n + i + 1], chunk) for i in range(n)]
        res = [[_across(cols[:, k:k + 1], chunk)
                for k in range(2 * n + i * n, 2 * n + (i + 1) * n)]
               for i in range(n)]
        part = jnp.zeros((_ROWS, chunk), f32)
        for h in range(0, hidden, chunk):
            x = [x_ref[rows, h:h + chunk] for x_ref in xs]
            y = y_ref[rows, h:h + chunk]
            for i in range(n):
                o = post[i] * y
                for j in range(n):
                    o = o + res[i][j] * x[j]
                outs[i][rows, h:h + chunk] = o
                part = part + o * o
        # the maps are read: their rows hold this tile's sums of squares now
        cols_ref[rows, :] = _across(jnp.sum(part, axis=1, keepdims=True), _LANES)
        return carry

    jax.lax.fori_loop(0, TILE // _ROWS, write, 0)
    sq_ref[...] = cols_ref[...].T[:1]


@functools.partial(jax.jit, static_argnames=(
    "rounds", "eps", "hc_eps", "clip", "interpret"))
def _streams_maps_read(xs, squares, phi_t, ab, *, rounds: int, eps: float,
                       hc_eps: float, clip: tuple[float, float],
                       interpret: bool):
    n = len(xs)
    p, hidden = xs[0].shape
    c = columns(n)
    tile = pl.BlockSpec((TILE, hidden), lambda i: (i, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    along = lambda rows: pl.BlockSpec((rows, TILE), lambda i: (0, i))
    has_squares = squares is not None
    operands = [*xs, *([squares] if has_squares else []), phi_t, ab]
    in_specs = [tile] * n + [along(1)] * has_squares + [whole(phi_t), whole(ab)]
    return pl.pallas_call(
        functools.partial(_maps_read_kernel, n=n, rounds=rounds, eps=eps,
                          hc_eps=hc_eps, clip=clip, has_squares=has_squares),
        out_shape=(jax.ShapeDtypeStruct((p, hidden), jnp.float32),
                   jax.ShapeDtypeStruct((c, p), jnp.float32)),
        grid=(p // TILE,),
        in_specs=in_specs,
        out_specs=(tile, along(c)),
        scratch_shapes=[pltpu.VMEM((TILE, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(_VMEM_CAP, _vmem(n, hidden))),
        cost_estimate=pl.CostEstimate(
            flops=2 * p * n * hidden * (c + 1) + p * rounds * 4 * n * n,
            transcendentals=p * c,
            bytes_accessed=(n + 1) * p * hidden * 4 + c * n * hidden * 4),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("alias", "interpret"))
def _streams_write(xs, y, maps, *, alias: bool, interpret: bool):
    n = len(xs)
    p, hidden = y.shape
    tile = pl.BlockSpec((TILE, hidden), lambda i: (i, 0))
    along = lambda rows: pl.BlockSpec((rows, TILE), lambda i: (0, i))
    stream = jax.ShapeDtypeStruct((p, hidden), jnp.float32)
    *out, squares = pl.pallas_call(
        functools.partial(_write_kernel, n=n),
        out_shape=(*[stream] * n, jax.ShapeDtypeStruct((1, p), jnp.float32)),
        grid=(p // TILE,),
        in_specs=[tile] * (n + 1) + [along(maps.shape[0])],
        out_specs=(*[tile] * n, along(1)),
        scratch_shapes=[pltpu.VMEM((TILE, _LANES), jnp.float32)],
        input_output_aliases={i: i for i in range(n)} if alias else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(_VMEM_CAP, _vmem(n, hidden))),
        cost_estimate=pl.CostEstimate(
            flops=2 * p * hidden * (n * n + 2 * n), transcendentals=0,
            bytes_accessed=(2 * n + 1) * p * hidden * 4),
        interpret=interpret,
    )(*xs, y, maps)
    return tuple(out), squares.reshape(p)


def maps_and_read(xs, hc, cfg, squares=None, *, interpret: bool = False):
    """The maps of a hyper-connected sublayer and what it reads, one pass
    over the streams ``xs`` (``n`` float32 arrays [P, hidden]): ``(u [P,
    hidden], maps [2 n + n^2, P])`` with ``u = decoder_parts.hyper_read(xs,
    pre)`` and ``split_maps(maps, n) = decoder_parts.hyper_maps(xs, hc, cfg,
    squares)``. ``hc`` and ``cfg`` as ``hyper_maps`` takes them; ``squares``
    [P] is ``stream_squares(xs)`` where the caller has it (from ``write``),
    else the kernel sums it from the tile. ``declines`` says what shapes it
    takes. ``interpret=True`` runs the Pallas interpreter, the only way to
    run the kernel off the TPU, and always the caller's explicit choice."""
    n = len(xs)
    f32 = jnp.float32
    # ``a`` a row of the maps (pre, post and res in that order) beside ``b``
    a = jnp.repeat(hc["a"].astype(f32), np.array([n, n, n * n]),
                   total_repeat_length=columns(n))
    ab = jnp.stack([a, hc["b"].astype(f32)], axis=1)
    if squares is not None:
        squares = squares.reshape(1, -1)
    return _streams_maps_read(
        tuple(xs), squares, hc["phi"].astype(f32).T, ab,
        rounds=int(cfg.hc_rounds), eps=float(cfg.eps), hc_eps=float(cfg.hc_eps),
        clip=tuple(float(c) for c in cfg.hc_clip), interpret=interpret)


def write(xs, maps, y, *, interpret: bool = False):
    """What a hyper-connected sublayer leaves, one pass over the streams
    ``xs``, the maps ``maps_and_read`` made of them and the sublayer's
    result ``y`` [P, hidden]: ``(x' (n arrays [P, hidden]), squares [P])``
    with ``x' = decoder_parts.hyper_write(xs, res, post, y)`` and ``squares =
    stream_squares(x')``. Each ``x'[i]`` is written where ``xs[i]`` lay,
    unless one array stands for several streams (the entry: the projected
    event copied into all of them), which cannot be written over ``n``
    times."""
    alias = len({id(x) for x in xs}) == len(xs)
    return _streams_write(tuple(xs), y, maps, alias=alias, interpret=interpret)
