"""Pallas TPU kernels: the grouped products of a dropless expert layer.

``models/keye_backbone.grouped_experts`` sorts its (position, expert)
pairs by expert, so each expert's rows are contiguous in ``xs`` [M,
hidden] and ``sizes`` [E] says how many each holds. The XLA path runs the
layer as three ``lax.ragged_dot`` products with two float32 [M, width]
arrays and a separate ``silu(g) * u`` fusion between them; here it is two
kernels:

- ``gate_up(xs, wg, wu, sizes) -> mid``: one read of a row tile is
  multiplied into two float32 accumulators (``x @ wg[e]``, ``x @ wu[e]``)
  and the epilogue writes ``silu(g) * u`` once, rounded to the operands'
  dtype. The gate and up weights stay two arguments: they are fused in
  the kernel, never concatenated at rest or per call.
- ``down(mid, wd, sizes) -> ys``: [M, width] x [E, width, hidden] into
  float32 [M, hidden].

Both walk the same schedule. A *visit* is one (row tile, expert) pair
whose rows intersect: ``M / tm`` tiles plus one more visit for every
group boundary that falls inside a tile, at most ``tiles + E - 1``, which
is the (static) grid. ``_schedule`` derives the visits on the device from
``sizes`` (no host round trip, nothing recompiles with the routing) and
hands them to the kernel by scalar prefetch: the row tiles' block index
maps read the visit's tile, the body multiplies the sub-tiles that hold
rows of the visit's expert, masks the rows that belong to other experts
and merges into the resident output tile. Grid steps past the last visit
repeat its block indices and do nothing.

The weights never ride the grid's own pipeline, which looks one step
ahead: at ~256 rows an expert a visit is shorter than the fetch of the
next expert's matrices. They stay in HBM and the kernel copies them into
two VMEM slots itself: an expert's first visit waits for its matrices and
starts the copy of the next expert that has rows, which then has all of
this expert's visits to land. The whole contraction dimension is one
block, so an expert's weights are fetched once a call however many tiles
it spans; an expert with no rows has no visit and no fetch. Every pair is
computed, whatever the skew: dropless stays dropless.

Same arithmetic as the reference: operands as given (bfloat16),
accumulation, ``silu`` and the gate-up product in float32, one rounding
before ``down``. Only the order of float32 accumulation inside a product
may differ. ``lax.ragged_dot`` stays the golden reference
(tests/test_grouped_experts_kernel.py) and what runs off the TPU.

``sizes`` must sum to M (the caller's ``bincount`` of M pairs does); rows
past the sum would belong to no visit and are left unwritten.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What a kernel may ask of the v5e's 128 MiB of VMEM: the default scoped
# limit is 16 MiB, under what two slots of full-contraction weights need.
_VMEM_CAP = 100 * 2**20

# Rows one product multiplies at once. Measured on a v5e at the cell's
# shapes (PERF.md, section 6, PR 35): 64 beats 128 (fewer masked rows) and
# ties with 32; at 16 the MXU starves.
_SUB_TILE = 64


def _tiles(m: int) -> tuple[int, int]:
    """(rows a visit holds in VMEM, rows a product multiplies at once),
    from the shapes alone. Every group boundary inside a tile costs one
    more visit, and a visit multiplies only its sub-tiles that hold rows
    of its expert: the sub-tile sets the masked MXU work (one sub-tile an
    expert at most), the tile the number of grid steps and the size of
    the output's write-back."""
    if m < _SUB_TILE:
        rows = 16 * pl.cdiv(m, 16)
        return rows, rows
    return (256 if m >= 256 else _SUB_TILE), _SUB_TILE


def supports(xs, w) -> bool:
    """Whether the kernels take rows ``xs`` [M, hidden] against stacked
    weights ``w`` [E, hidden, width] (arrays or their shapes-and-dtypes):
    bfloat16 on both sides, lane-aligned widths, and two slots of weights
    that fit in VMEM beside the row tiles. Anything else takes the
    ``lax.ragged_dot`` path."""
    m, hidden = xs.shape
    _, w_hidden, width = w.shape
    return (xs.dtype == jnp.bfloat16 and w.dtype == jnp.bfloat16
            and hidden == w_hidden and m > 0
            and hidden % 128 == 0 and width % 128 == 0
            and 2 * 2 * hidden * width * 2 <= _VMEM_CAP // 2)


def _schedule(sizes, m: int, tm: int):
    """``sizes`` [E] -> the visits, as scalar-prefetch operands. Per
    expert: its first row and the row past its last. Per grid step: the
    visit's expert and row tile, whether it is the expert's first visit,
    the weight slot the expert's matrices sit in (experts with rows
    alternate), and the next expert with rows (-1 after the last). And
    the number of visits (one element)."""
    e = sizes.shape[0]
    i32 = jnp.int32
    sizes = sizes.astype(i32)
    some = sizes > 0
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = jnp.where(some, (ends - 1) // tm - starts // tm + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    visit_starts = visit_ends - tiles
    n_visits = visit_ends[-1]
    step = jnp.arange(pl.cdiv(m, tm) + e - 1, dtype=i32)
    v = jnp.minimum(step, n_visits - 1)
    group = jnp.searchsorted(visit_ends, v, side="right",
                             method="compare_all").astype(i32)
    group = jnp.minimum(group, e - 1)
    tile = (starts[group] // tm + v - visit_starts[group]).astype(i32)
    first = jnp.logical_and(step == visit_starts[group],
                            step < n_visits).astype(i32)
    slot = ((jnp.cumsum(some) - 1) % 2).astype(i32)
    later = jax.lax.cummin(jnp.where(some, jnp.arange(e, dtype=i32), e),
                           reverse=True)
    following = jnp.concatenate([later[1:], jnp.full((1,), e, i32)])
    following = jnp.where(following >= e, -1, following)
    return (starts, ends, group, tile, first, slot[group], following[group],
            n_visits.reshape(1))


def _kernel(start_ref, end_ref, group_ref, tile_ref, first_ref, slot_ref,
            next_ref, n_ref, x_ref, *rest, tm: int, ts: int, nw: int,
            epilogue):
    w_hbm, o_ref = rest[:nw], rest[nw]
    slots, sem = rest[nw + 1:2 * nw + 1], rest[2 * nw + 1]
    v = pl.program_id(0)
    group, slot = group_ref[v], slot_ref[v]

    def copies(g, s):
        return [pltpu.make_async_copy(w.at[g], buf.at[s], sem.at[i, s])
                for i, (w, buf) in enumerate(zip(w_hbm, slots))]

    @pl.when(v == 0)
    def _prime():
        for c in copies(group, slot):
            c.start()

    @pl.when(first_ref[v] == 1)
    def _turn():
        for c in copies(group, slot):
            c.wait()

        @pl.when(next_ref[v] >= 0)
        def _ahead():
            for c in copies(next_ref[v], 1 - slot):
                c.start()

    start, end = start_ref[group], end_ref[group]
    tile_row0 = tile_ref[v] * tm

    def multiply(sub, carry):
        row0 = tile_row0 + sub * ts

        @pl.when((row0 < end) & (row0 + ts > start))
        def _own():
            window = pl.ds(pl.multiple_of(sub * ts, ts), ts)
            x = x_ref[window, :]
            acc = [jnp.dot(x, buf[slot], preferred_element_type=jnp.float32)
                   for buf in slots]
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
            own = jnp.logical_and(rows >= start, rows < end)
            o_ref[window, :] = jnp.where(
                own, epilogue(*acc).astype(o_ref.dtype), o_ref[window, :])

        return carry

    @pl.when(v < n_ref[0])
    def _visit():
        jax.lax.fori_loop(0, tm // ts, multiply, 0)


def _grouped(epilogue, lhs, weights, sizes, out_dtype, *, tm: int, ts: int,
             interpret: bool):
    """One grouped kernel over the visits of ``sizes``: ``lhs`` [M, K]
    against every matrix of ``weights`` (each [E, K, N]), their float32
    products through ``epilogue`` into [M, N]."""
    m, k = lhs.shape
    e, _, n = weights[0].shape
    nw = len(weights)
    row_block = lambda v, start, end, group, tile, *_: (tile[v], 0)
    out_size = jnp.dtype(out_dtype).itemsize
    w_size = weights[0].dtype.itemsize
    # the row tiles' double buffers, two slots of weights, the float32
    # accumulators of one sub-tile, and room to spare
    vmem = (2 * tm * (k * lhs.dtype.itemsize + n * out_size)
            + 2 * nw * k * n * w_size + 4 * nw * ts * n * 4 + 8 * 2**20)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, ts=ts, nw=nw, epilogue=epilogue),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(pl.cdiv(m, tm) + e - 1,),
            in_specs=[pl.BlockSpec((tm, k), row_block)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * nw,
            out_specs=pl.BlockSpec((tm, n), row_block),
            scratch_shapes=[pltpu.VMEM((2, k, n), weights[0].dtype)] * nw
            + [pltpu.SemaphoreType.DMA((nw, 2))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_CAP, vmem)),
        cost_estimate=pl.CostEstimate(
            flops=2 * nw * m * k * n,
            transcendentals=(nw - 1) * m * n,  # the gate-up epilogue's silu
            bytes_accessed=(m * k * lhs.dtype.itemsize + m * n * out_size
                            + nw * e * k * n * w_size)),
        interpret=interpret,
    )(*_schedule(sizes, m, tm), lhs, *weights)


@functools.partial(jax.jit, static_argnames=("tm", "ts", "interpret"))
def _gate_up(xs, wg, wu, sizes, *, tm: int, ts: int, interpret: bool):
    return _grouped(lambda g, u: jax.nn.silu(g) * u, xs, (wg, wu), sizes,
                    xs.dtype, tm=tm, ts=ts, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tm", "ts", "interpret"))
def _down(mid, wd, sizes, *, tm: int, ts: int, interpret: bool):
    return _grouped(lambda y: y, mid, (wd,), sizes, jnp.float32,
                    tm=tm, ts=ts, interpret=interpret)


def gate_up(xs, wg, wu, sizes, *, interpret: bool = False):
    """``silu(xs @ wg[e]) * (xs @ wu[e])`` for the expert ``e`` of each
    row: ``xs`` [M, hidden] sorted by expert, ``wg`` and ``wu`` [E, hidden,
    width], ``sizes`` int32 [E] summing to M -> ``mid`` [M, width] in
    ``xs``'s dtype (products and silu in float32, rounded once).
    ``interpret=True`` runs the Pallas interpreter, the only way to run
    the kernel off the TPU, and always the caller's explicit choice."""
    tm, ts = _tiles(xs.shape[0])
    return _gate_up(xs, wg, wu, sizes, tm=tm, ts=ts, interpret=interpret)


def down(mid, wd, sizes, *, interpret: bool = False):
    """``mid @ wd[e]`` for the expert ``e`` of each row: ``mid`` [M, width]
    x ``wd`` [E, width, hidden] -> float32 [M, hidden]."""
    tm, ts = _tiles(mid.shape[0])
    return _down(mid, wd, sizes, tm=tm, ts=ts, interpret=interpret)
