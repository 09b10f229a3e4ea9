"""Pallas TPU kernels: the grouped products of a dropless expert layer,
and its results' way back to position order.

``models/expert_layer.grouped_experts`` sorts its (position, expert)
pairs by expert, so each expert's rows are contiguous in ``xs`` [M,
hidden] and ``sizes`` [E] says how many each holds. The XLA path runs the
layer as three ``lax.ragged_dot`` products with two float32 [M, width]
arrays and a separate ``silu(g) * u`` fusion between them, then a float32
row gather back to position order and a weighted sum that reads the
gathered copy again; here it is three kernels, the two products:

- ``gate_up(xs, wg, wu, sizes) -> mid``: one read of a row tile is
  multiplied into two float32 accumulators (``x @ wg[e]``, ``x @ wu[e]``)
  and the epilogue writes ``silu(g) * u`` once, rounded to the operands'
  dtype. The gate and up weights stay two arguments: they are fused in
  the kernel, never concatenated at rest or per call.
- ``down(mid, wd, sizes) -> ys``: [M, width] x [E, width, hidden] into
  float32 [M, hidden]; or, for ``combine``, with its rows whole: [M, pitch,
  128], a row's ``hidden / 128`` lane chunks in the first sublanes of
  ``pitch`` of them, where ``pitch`` is that many rounded up to whole (8,
  128) tiles (``_pitch``: 16 at 2,048, 24 at 2,304), so that every row
  starts on a tile and is one piece of memory. The sublanes past a row's
  chunks are never written: they hold whatever the memory held.

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
a ring of VMEM slots itself, as far ahead as slots are free: the first
grid step starts the copies of the first ``slots - 1`` experts that have
rows; an expert's first visit waits for its own matrices and starts the
copy of the ``slots - 1``-th expert with rows after it into the slot the
expert before it has just left. How many slots is read from the shapes
(``_slots``: as many experts' matrices as fit in the half of the VMEM cap
that ``supports`` grants the weights, two at least, four at most), because
loads are lumpy: with two slots a small expert behind a large one waits
for its matrices while the DMA stood idle through the large one's
products, and the call costs ``sum(max(fetch, products))`` where a deeper
ring comes toward ``max(sum(fetch), sum(products))`` (PERF.md, PR 44). The
whole contraction dimension is one block, so an expert's weights are
fetched once a call however many tiles it spans; an expert with no rows
has no visit and no fetch. Every pair is computed, whatever the skew:
dropless stays dropless.

The rows come one of two ways, by the shapes (``takes_rows``).
*Gathered*: the caller sorts a copy ``xs`` [M, hidden] into expert order
(an XLA gather that writes M rows to HBM) and the row tiles ride the
grid's pipeline back in. *In-kernel*: ``gate_up`` is given the unsorted
positions ``x`` [P, hidden] and ``rows`` [M] (sorted row ``i`` is
``x[rows[i]]``), holds ``x`` whole in VMEM (16 MB at 4,096 positions of
2,048) and brings each sub-tile's rows together itself, so no sorted copy
is written or read back and nothing depends on where XLA's memory
assignment put the gather's source (in ``lfm2``'s step it was HBM, 33 ns
a row; in ``keye``'s VMEM, 6.4: PERF.md, PR 44). A bfloat16 row cannot be
addressed alone in a tiled array, so ``x`` travels as 32-bit words, the
row's two halves packed lane for lane (``_packed``): a row is then
``hidden / 256`` sublanes of 128 words, one (8, 128) tile at 2,048; a
sub-tile's rows are stored one under the other in a scratch and read back
with a sublane stride (what ``combine`` does to turn its sums), which
yields [rows, 128] pieces that unpack exactly into the [rows, hidden]
operand the products read.

Same arithmetic as the reference: operands as given (bfloat16),
accumulation, ``silu`` and the gate-up product in float32, one rounding
before ``down``. Only the order of float32 accumulation inside a product
may differ. ``lax.ragged_dot`` stays the golden reference
(tests/test_grouped_experts_kernel.py) and what runs off the TPU.

``sizes`` must sum to M (the caller's count of M pairs does); rows
past the sum would belong to no visit and are left unwritten.

And the way back, ``combine(ys, rows, weights, take=None)``: ``y[p] = sum_j
weights[p, j] * ys[rows[p, j]]`` in float32, slots in ascending ``j``. Each
row that is owed is read once and nothing is gathered into a copy first.
What it is given decides how it reads (PERF.md, PR 37, has the readings):

- every slot taken (every expert held): 8 x positions rows, all of them
  owed, so each is copied from HBM by a DMA of its own, a tile of
  positions ahead of the sum. A row of the (8, 128)-tiled [M, hidden]
  lies in hidden / 128 pieces of 512 B and Mosaic refuses to slice it;
  as a leading index of [M, pitch, 128] it is one piece, which is what
  ``down(..., whole_rows=True)`` writes. Nothing in it needs a row to
  *fill* whole tiles, only to start on one: at 2,304 (18 lane chunks) a
  row rides at a pitch of 24 sublanes, its DMA carries the six idle ones
  along (a row costs its descriptor, ~20 ns on a v5e from 2,048 to 3,072:
  PERF.md, PR 58), the sum is formed over all 24 and only the first 18
  are stored, so what the padding holds, a NaN too, reaches nothing.
  Where ``hidden / 128`` divides by 8 the pitch is the row.
- with ``take`` (a share's pass): few slots are owed (2-4% in the cell)
  and the pass's results are small enough to stay in VMEM, so they are
  copied there once, whole, and each taken slot adds its row from there;
  a slot that is not taken reads nothing, so what its row holds, a NaN
  too, reaches nothing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What a kernel may ask of the v5e's 128 MiB of VMEM: the default scoped
# limit is 16 MiB, under what two slots of full-contraction weights need.
# ``supports`` grants the weights half of it (two slots at least must fit);
# ``_slots`` fills that half.
_VMEM_CAP = 100 * 2**20

# Rows one product multiplies at once. Measured on a v5e at the cell's
# shapes (PERF.md, section 6, PR 35): 64 beats 128 (fewer masked rows) and
# ties with 32; at 16 the MXU starves.
_SUB_TILE = 64

_LANES = 128


def _pitch(chunks: int) -> int:
    """Sublanes from one whole row to the next in ``[M, pitch, 128]``: a
    row's ``chunks`` lane chunks rounded up to whole (8, 128) tiles, so
    that every row starts on a tile and ``[M * pitch, 128]`` is the same
    memory (24 at a hidden size of 2,304; ``chunks`` itself wherever it
    divides by 8)."""
    return 8 * pl.cdiv(chunks, 8)

# Rows one turn of the loop that brings a sub-tile's rows together copies
# (written out: Mosaic unrolls a loop whole or not at all).
_ROWS_A_TURN = 8


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


# The deepest ring of weight slots. Measured on a v5e under both cells'
# loads (PERF.md, PR 44): four hide every fetch that can be hidden (by the
# kernel's own rule one first visit still waits, the call's first); more
# buy nothing, and their copies share the DMA's bandwidth with the one the
# next expert is waiting for.
_MOST_SLOTS = 4


def _slots(*weights) -> int:
    """Weight slots of the ring, from the shapes: as many experts'
    matrices (each [K, N] of ``weights``' shapes-and-dtypes, fetched
    together) as fit in the half of the VMEM cap that ``supports`` grants
    the weights; two at least, ``_MOST_SLOTS`` at most."""
    each = sum(math.prod(w.shape[1:]) * jnp.dtype(w.dtype).itemsize
               for w in weights)
    return max(2, min(_MOST_SLOTS, (_VMEM_CAP // 2) // each))


def _grouped_vmem(lhs_bytes: int, tm: int, ts: int, k: int, n: int, nw: int,
                  slots: int, out_size: int, w_size: int,
                  out_n: int | None = None) -> int:
    """What one grouped kernel asks of VMEM: what it holds of its rows
    (``lhs_bytes``), the output tile's double buffer (``out_n`` a row where
    that is more than ``n``: rows whole at their pitch), the ring of weight
    slots, the float32 accumulators of one sub-tile (twice: the products
    and the epilogue's), and room to spare."""
    return (lhs_bytes + 2 * tm * (out_n or n) * out_size
            + slots * nw * k * n * w_size + 4 * nw * ts * n * 4 + 8 * 2**20)


def _held_rows_bytes(positions: int, ts: int, k: int) -> int:
    """In-kernel rows: the positions whole, and one sub-tile's rows one
    under the other, both as 32-bit words of two bfloat16."""
    return positions * k * 2 + ts * k * 2


# Sorted rows whose positions ``gate_up`` keeps in scalar memory: what
# ``combine`` keeps there for the cells' 32,768 pairs, twice over.
_ROWS_IN_SMEM = 65536


def takes_rows(x, rows, w) -> bool:
    """Whether ``gate_up`` brings its rows in itself: positions ``x`` [P,
    hidden] and ``rows`` [M] against stacked weights ``w`` [E, hidden,
    width] (arrays or their shapes-and-dtypes). A row must be whole (8,
    128) tiles of 32-bit words (hidden a multiple of 2,048), the positions
    must fit VMEM whole beside the ring of weight slots ``supports``
    allowed, and ``rows`` must fit the scalar memory that ``combine``'s
    two lists already take. Anything else has the caller gather a sorted
    copy (``xs = x[rows]``)."""
    p, hidden = x.shape
    m = rows.shape[0]
    tm, ts = _tiles(m)
    asked = _grouped_vmem(_held_rows_bytes(p, ts, hidden), tm, ts, hidden,
                          w.shape[2], 2, _slots(w, w), 2, 2)
    return (supports(jax.ShapeDtypeStruct((m, hidden), x.dtype), w)
            and hidden % (2 * 8 * _LANES) == 0
            and m <= _ROWS_IN_SMEM and asked <= _VMEM_CAP)


def _packed(x):
    """Positions ``x`` [P, hidden] (bfloat16) as 32-bit words [P, hidden /
    256, 128]: word ``(c, l)`` of a row holds its element ``128 c + l`` in
    the low half and element ``hidden / 2 + 128 c + l`` in the high half,
    so a row is whole sublanes and can be addressed alone."""
    p, hidden = x.shape
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    words = bits[:, :hidden // 2] | (bits[:, hidden // 2:] << 16)
    return words.reshape(p, hidden // 2 // _LANES, _LANES)


def _schedule(sizes, m: int, tm: int, slots: int):
    """``sizes`` [E] -> the visits, as scalar-prefetch operands. Per
    expert: its first row and the row past its last. Per grid step: the
    visit's expert and row tile, whether it is the expert's first visit,
    the weight slot the expert's matrices sit in (experts with rows take
    the ``slots`` of the ring in turn), and the expert whose matrices the
    first visit starts fetching: the ``slots - 1``-th with rows after this
    one (-1 past the last). The first ``slots - 1`` experts with rows,
    which the first grid step fetches (-1 where there are fewer). And the
    number of visits (one element)."""
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
    # an expert's rank among those with rows, and the expert of each rank
    # (a comparison summed over the experts: no sort); ``e`` past the last
    upto = jnp.cumsum(some.astype(i32))
    rank = upto - 1
    of_rank = jnp.sum((upto[None, :] <= jnp.arange(e, dtype=i32)[:, None])
                      .astype(i32), axis=1)
    of_rank = jnp.concatenate([of_rank, jnp.full((slots,), e, i32)])
    of_rank = jnp.where(of_rank >= e, -1, of_rank)
    fetch = of_rank[rank + slots - 1]
    return (starts, ends, group, tile, first, (rank % slots).astype(i32)[group],
            fetch[group], of_rank[:slots - 1], n_visits.reshape(1))


def _kernel(start_ref, end_ref, group_ref, tile_ref, first_ref, slot_ref,
            fetch_ref, prime_ref, n_ref, *rest, tm: int, ts: int, nw: int,
            slots: int, epilogue, whole_rows: bool = False,
            in_kernel: bool = False):
    if in_kernel:
        # each sorted row's position and the positions' words in HBM; the
        # positions whole in VMEM, a sub-tile's rows one under the other
        rows_ref, x_hbm, *rest = rest
        *rest, x_vmem, under, x_sem = rest
    else:
        x_ref, *rest = rest
    w_hbm, o_ref = rest[:nw], rest[nw]
    ring, sem = rest[nw + 1:2 * nw + 1], rest[2 * nw + 1]
    v = pl.program_id(0)
    group, slot = group_ref[v], slot_ref[v]

    def copies(g, s):
        return [pltpu.make_async_copy(w.at[g], buf.at[s], sem.at[i, s])
                for i, (w, buf) in enumerate(zip(w_hbm, ring))]

    @pl.when(v == 0)
    def _prime():
        if in_kernel:
            whole = pltpu.make_async_copy(x_hbm, x_vmem, x_sem)
            whole.start()
        for ahead in range(slots - 1):
            @pl.when(prime_ref[ahead] >= 0)
            def _():
                for c in copies(prime_ref[ahead], ahead):
                    c.start()
        if in_kernel:
            whole.wait()

    @pl.when(first_ref[v] == 1)
    def _turn():
        for c in copies(group, slot):
            c.wait()

        @pl.when(fetch_ref[v] >= 0)
        def _ahead():
            # into the slot the expert before this one has just left
            for c in copies(fetch_ref[v], jax.lax.rem(slot + slots - 1, slots)):
                c.start()

    start, end = start_ref[group], end_ref[group]
    tile_row0 = tile_ref[v] * tm

    def brought(row0):
        """The sub-tile's rows [ts, hidden] out of the positions held in
        VMEM: each row's ``words`` sublanes stored under the last one's,
        piece ``c`` of every row read back with a stride of a row, its low
        halves the columns ``128 c ..``, its high halves ``hidden / 2 + 128
        c ..`` (``_packed``). A bfloat16 is the high half of the float32
        of the same value, so both unpack exactly."""
        words = x_vmem.shape[1]

        def put(g, carry):
            for j in range(_ROWS_A_TURN):
                u = g * _ROWS_A_TURN + j
                under[pl.ds(pl.multiple_of(u * words, words), words), :] = (
                    x_vmem[rows_ref[row0 + u]])
            return carry

        jax.lax.fori_loop(0, ts // _ROWS_A_TURN, put, 0)
        pieces = [under[pl.ds(c, ts, stride=words), :] for c in range(words)]
        f32 = lambda w: jax.lax.bitcast_convert_type(w, jnp.float32)
        dt = ring[0].dtype
        return jnp.concatenate(
            [f32(w << 16).astype(dt) for w in pieces]
            + [f32(w & jnp.uint32(0xFFFF0000)).astype(dt) for w in pieces],
            axis=1)

    def multiply(sub, carry):
        row0 = tile_row0 + sub * ts

        @pl.when((row0 < end) & (row0 + ts > start))
        def _own():
            window = pl.ds(pl.multiple_of(sub * ts, ts), ts)
            x = brought(row0) if in_kernel else x_ref[window, :]
            acc = [jnp.dot(x, buf[slot], preferred_element_type=jnp.float32)
                   for buf in ring]
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
            own = jnp.logical_and(rows >= start, rows < end)
            if whole_rows:
                out = epilogue(*acc).astype(o_ref.dtype)
                pitch = o_ref.shape[0] // tm
                for c in range(out.shape[1] // _LANES):
                    at = pl.ds(sub * (ts * pitch) + c, ts, stride=pitch)
                    o_ref[at, :] = jnp.where(
                        own, out[:, c * _LANES:(c + 1) * _LANES], o_ref[at, :])
            else:
                o_ref[window, :] = jnp.where(
                    own, epilogue(*acc).astype(o_ref.dtype), o_ref[window, :])

        return carry

    @pl.when(v < n_ref[0])
    def _visit():
        jax.lax.fori_loop(0, tm // ts, multiply, 0)


def _grouped(epilogue, lhs, weights, sizes, out_dtype, *, tm: int, ts: int,
             slots: int, interpret: bool, whole_rows: bool = False,
             rows=None):
    """One grouped kernel over the visits of ``sizes``: ``lhs`` [M, K]
    against every matrix of ``weights`` (each [E, K, N]), their float32
    products through ``epilogue`` into [M, N]. With ``rows`` [M], ``lhs``
    is the unsorted [P, K] and sorted row ``i`` is ``lhs[rows[i]]``,
    brought together inside the kernel."""
    k = lhs.shape[1]
    m = lhs.shape[0] if rows is None else rows.shape[0]
    e, _, n = weights[0].shape
    nw = len(weights)
    tiles = pl.cdiv(m, tm)
    row_block = lambda v, start, end, group, tile, *_: (tile[v], 0)
    out_size = jnp.dtype(out_dtype).itemsize
    w_size = weights[0].dtype.itemsize
    # a row of the output: with its rows whole, a pitch of sublanes
    out_n = _pitch(n // _LANES) * _LANES if whole_rows else n
    out_shape, out_block = (
        ((m * out_n // _LANES, _LANES), (tm * out_n // _LANES, _LANES))
        if whole_rows else ((m, n), (tm, n)))
    if rows is None:
        prefetch, operands = (), (lhs,)
        in_specs = [pl.BlockSpec((tm, k), row_block)]
        scratch = []
        lhs_bytes = 2 * tm * k * lhs.dtype.itemsize
    else:
        words = k // 2 // _LANES
        # a tile's sub-tiles read whole: rows past the last name row 0
        prefetch = (jnp.pad(rows.astype(jnp.int32), (0, tiles * tm - m)),)
        operands = (_packed(lhs),)
        in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [pltpu.VMEM((lhs.shape[0], words, _LANES), jnp.uint32),
                   pltpu.VMEM((ts * words, _LANES), jnp.uint32),
                   pltpu.SemaphoreType.DMA(())]
        lhs_bytes = _held_rows_bytes(lhs.shape[0], ts, k)
    vmem = _grouped_vmem(lhs_bytes, tm, ts, k, n, nw, slots, out_size, w_size,
                         out_n)
    schedule = _schedule(sizes, m, tm, slots)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, ts=ts, nw=nw, slots=slots,
                          epilogue=epilogue, whole_rows=whole_rows,
                          in_kernel=rows is not None),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(schedule) + len(prefetch),
            grid=(tiles + e - 1,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * nw,
            out_specs=pl.BlockSpec(out_block, row_block),
            scratch_shapes=[pltpu.VMEM((slots, k, n), weights[0].dtype)] * nw
            + [pltpu.SemaphoreType.DMA((nw, slots))] + scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_CAP, vmem)),
        cost_estimate=pl.CostEstimate(
            flops=2 * nw * m * k * n,
            transcendentals=(nw - 1) * m * n,  # the gate-up epilogue's silu
            bytes_accessed=(m * k * lhs.dtype.itemsize + m * out_n * out_size
                            + nw * e * k * n * w_size)),
        interpret=interpret,
    )(*schedule, *prefetch, *operands, *weights)


@functools.partial(jax.jit,
                   static_argnames=("tm", "ts", "slots", "interpret"))
def _gate_up(xs, wg, wu, sizes, rows=None, *, tm: int, ts: int, slots: int,
             interpret: bool):
    return _grouped(lambda g, u: jax.nn.silu(g) * u, xs, (wg, wu), sizes,
                    xs.dtype, tm=tm, ts=ts, slots=slots, interpret=interpret,
                    rows=rows)


@functools.partial(jax.jit, static_argnames=("tm", "ts", "slots", "interpret",
                                             "whole_rows"))
def _down(mid, wd, sizes, *, tm: int, ts: int, slots: int, interpret: bool,
          whole_rows: bool = False):
    return _grouped(lambda y: y, mid, (wd,), sizes, jnp.float32, tm=tm, ts=ts,
                    slots=slots, interpret=interpret, whole_rows=whole_rows)


def gate_up(xs, wg, wu, sizes, *, rows=None, interpret: bool = False):
    """``silu(xs @ wg[e]) * (xs @ wu[e])`` for the expert ``e`` of each
    row: ``xs`` [M, hidden] sorted by expert, ``wg`` and ``wu`` [E, hidden,
    width], ``sizes`` int32 [E] summing to M -> ``mid`` [M, width] in
    ``xs``'s dtype (products and silu in float32, rounded once). With
    ``rows`` int32 [M] (where ``takes_rows`` holds) ``xs`` is the unsorted
    positions [P, hidden] and sorted row ``i`` is ``xs[rows[i]]``: the
    kernel brings the rows together itself and no sorted copy exists.
    ``interpret=True`` runs the Pallas interpreter, the only way to run
    the kernel off the TPU, and always the caller's explicit choice."""
    tm, ts = _tiles(xs.shape[0] if rows is None else rows.shape[0])
    return _gate_up(xs, wg, wu, sizes, rows, tm=tm, ts=ts,
                    slots=_slots(wg, wu), interpret=interpret)


def down(mid, wd, sizes, *, whole_rows: bool = False, interpret: bool = False):
    """``mid @ wd[e]`` for the expert ``e`` of each row: ``mid`` [M, width]
    x ``wd`` [E, width, hidden] -> float32 [M, hidden]; with ``whole_rows``
    the same numbers as [M, pitch, 128], in which a row is one piece of
    memory (what ``combine`` copies row by row): lane chunk ``c`` of row
    ``i`` is ``[i, c]``, and ``pitch`` is ``hidden / 128`` rounded up to
    whole sublane tiles (``_pitch``: 24 at 2,304, ``hidden / 128`` itself
    at 2,048). Sublanes ``hidden / 128 ..`` of a row are never written and
    hold whatever the memory held; ``combine`` is told ``hidden`` and reads
    none of them into a sum. The kernel stores each lane chunk of a
    sub-tile with a sublane stride of ``pitch``, at the price of a plain
    store (PERF.md, PR 37; at pitch 24, PR 58)."""
    tm, ts = _tiles(mid.shape[0])
    ys = _down(mid, wd, sizes, tm=tm, ts=ts, slots=_slots(wd),
               interpret=interpret, whole_rows=whole_rows)
    if whole_rows:
        # [M * pitch, 128] -> [M, pitch, 128]: whole tiles, nothing moves
        return ys.reshape(mid.shape[0], -1, _LANES)
    return ys


def feed(pairs: int, hidden: int, experts: int, width: int,
         positions: int | None = None, dtype=jnp.bfloat16) -> str:
    """How the kernels are fed at a shape, for a log line: ``tm=.., ts=..,
    slots=<gate_up's>/<down's>, rows=in-kernel|gathered`` (``positions``:
    how many unsorted positions ``gate_up`` would be given with ``rows``;
    None where it is given a sorted copy)."""
    shape = jax.ShapeDtypeStruct
    wg = shape((experts, hidden, width), dtype)
    wd = shape((experts, width, hidden), dtype)
    tm, ts = _tiles(pairs)
    inside = positions is not None and takes_rows(
        shape((positions, hidden), dtype), shape((pairs,), jnp.int32), wg)
    return (f"tm={tm}, ts={ts}, slots={_slots(wg, wg)}/{_slots(wd)}, "
            f"rows={'in-kernel' if inside else 'gathered'}")


def first_visits_that_wait(sizes, slots: int, fetch_us: float,
                           row_us: float) -> tuple[int, float]:
    """A count for a log line, made on the host by the kernel's own rule:
    of the experts with rows in ``sizes``, how many find at their first
    visit that their matrices have not landed, and the microseconds the
    call takes, if one expert's matrices take ``fetch_us`` to fetch (one
    copy after the other, each started ``slots - 1`` first visits ahead,
    the first ``slots - 1`` at the call's start) and a row ``row_us`` to
    multiply (an expert's rows and one masked sub-tile). The first expert
    always waits. A model: the chip's numbers are in PERF.md (PR 44)."""
    rows = [int(n) for n in sizes if n > 0]
    landed, free_at = [], 0.0  # when each expert's matrices land; the DMA's turn
    for _ in rows[:slots - 1]:
        free_at += fetch_us
        landed.append(free_at)
    waits, now = 0, 0.0
    for r, n in enumerate(rows):
        waits += landed[r] > now
        now = max(now, landed[r])
        if r + slots - 1 < len(rows):
            free_at = max(free_at, now) + fetch_us
            landed.append(free_at)
        now += (n + _SUB_TILE) * row_us
    return waits, now


# -- the way back to position order ---------------------------------------------

# Positions one grid step of ``combine`` sums.
_COMBINE_TILE = 64

# What the results of a share's pass may take of VMEM, where they stay for
# the whole call (``models/expert_layer.pass_rows`` bounds a pass by it).
HELD_RESULTS_BYTES = 64 * 2**20


def _combine_rows_kernel(rows_ref, w_ref, ys_hbm, o_ref, buf, turn, sem, *,
                         tile: int, k: int, chunks: int, n_tiles: int):
    """Every slot taken. Grid step ``i`` starts the row copies of tile ``i``
    into buffer ``i % 2`` and sums tile ``i - 1`` out of the other, eight
    positions a loop turn. A row of ``ys_hbm`` [M, pitch, 128] lands as
    [pitch, 128], whole tiles; a position's sum is formed in that shape,
    with its weights as scalars, and eight of them are turned into
    position-major [8, 128] tiles through ``turn`` (stored row after row,
    read back with a stride of a row's pitch). Only the first ``chunks``
    sublanes of a row hold results and only they are read out of ``turn``:
    what the others held is multiplied and never stored.

    The two ends run the same loop: step 0 sums a buffer nothing has
    written into the block that step 1 then writes in full, and the last
    step, with no tile left to fetch, fetches its own once more and waits
    for it. The loops over a turn's positions are rolled: unrolled they
    read 0.03 ms a call faster on a v5e and cost seven times the Python to
    trace and lower, which a serving process pays in seconds (PERF.md, PR
    37)."""
    i = pl.program_id(0)
    pitch = ys_hbm.shape[1]
    into = jax.lax.rem(i, 2)   # the buffer this step fetches into
    outof = 1 - into           # and the one it sums out of
    fetch0 = jnp.minimum(i, n_tiles - 1) * (tile * k)
    sum0 = jnp.maximum(i - 1, 0) * (tile * k)

    def landed(slot):
        # one wait for all of the buffer's bytes: every row was started
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    @pl.when(i > 0)
    def _():
        landed(outof)

    def eight_positions(g, carry):
        def start(u, c):
            for j in range(k):
                at = (g * 8 + u) * k + j
                pltpu.make_async_copy(ys_hbm.at[rows_ref[fetch0 + at]],
                                      buf.at[into, at], sem.at[into]).start()
            return c

        def add(u, c):
            at = (g * 8 + u) * k
            acc = buf[outof, at] * w_ref[sum0 + at]
            for j in range(1, k):
                acc = acc + buf[outof, at + j] * w_ref[sum0 + at + j]
            turn[pl.ds(pl.multiple_of(u * pitch, pitch), pitch), :] = acc
            return c

        jax.lax.fori_loop(0, 8, start, 0)
        jax.lax.fori_loop(0, 8, add, 0)
        rows8 = pl.ds(pl.multiple_of(g * 8, 8), 8)
        for c in range(chunks):
            o_ref[rows8, c * _LANES:(c + 1) * _LANES] = turn[
                pl.ds(c, 8, stride=pitch), :]
        return carry

    jax.lax.fori_loop(0, tile // 8, eight_positions, 0)

    @pl.when(i == n_tiles)
    def _():
        landed(into)


@functools.partial(jax.jit, static_argnames=("hidden", "tile", "interpret"))
def _combine_rows(ys3, rows, weights, *, hidden: int, tile: int,
                  interpret: bool):
    m, pitch, _ = ys3.shape
    p, k = rows.shape
    n_tiles = p // tile
    chunks = hidden // _LANES
    return pl.pallas_call(
        functools.partial(_combine_rows_kernel, tile=tile, k=k, chunks=chunks,
                          n_tiles=n_tiles),
        out_shape=jax.ShapeDtypeStruct((p, hidden), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles + 1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (tile, hidden), lambda i, *_: (jnp.maximum(i - 1, 0), 0)),
            scratch_shapes=[
                pltpu.VMEM((2, tile * k, pitch, _LANES), jnp.float32),
                pltpu.VMEM((8 * pitch, _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_CAP, _combine_rows_vmem(tile, k, hidden))),
        cost_estimate=pl.CostEstimate(
            flops=2 * p * k * hidden, transcendentals=0,
            bytes_accessed=4 * (p * k * pitch * _LANES + p * hidden
                                + 2 * p * k)),
        interpret=interpret,
    )(rows.reshape(-1), weights.reshape(-1), ys3)


def _combine_rows_vmem(tile: int, k: int, hidden: int) -> int:
    """Two buffers of a tile's rows at their pitch, the output block twice,
    and room to spare."""
    row = _pitch(hidden // _LANES) * _LANES
    return 2 * k * tile * row * 4 + 2 * tile * hidden * 4 + 4 * 2**20


def _combine_held_kernel(taken_ref, rows_ref, w_ref, ys_hbm, *rest, tile: int,
                         k: int, n_pos: int, onto: bool):
    """Some slots taken. The results stay in VMEM for the whole call;
    ``taken_ref`` lists the taken slots (position x k + slot) in ascending
    order, and grid step ``i`` walks the ones that fall into its tile of
    positions: each adds its row, times its weight, to its position's row
    of the output block, which starts from ``onto``'s or from zeros. A
    slot that is not listed is never read."""
    onto_ref = rest[0] if onto else None
    o_ref, ys_vmem, sem, cursor = rest[-4:]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _load():
        whole = pltpu.make_async_copy(ys_hbm, ys_vmem, sem)
        whole.start()
        whole.wait()
        cursor[0] = 0

    o_ref[...] = (onto_ref[...] if onto
                  else jnp.zeros(o_ref.shape, jnp.float32))
    end = jnp.minimum((i + 1) * tile, n_pos) * k

    def one(c):
        slot = taken_ref[c]
        at = pl.ds(slot // k - i * tile, 1)
        o_ref[at, :] = (o_ref[at, :]
                        + ys_vmem[pl.ds(rows_ref[slot], 1), :] * w_ref[slot])
        return c + 1

    # ``taken_ref`` has one entry more than ``ys`` has rows; a taken slot
    # past that many would have no row of its own, and ends the walk too
    last = taken_ref.shape[0] - 1
    cursor[0] = jax.lax.while_loop(
        lambda c: jnp.logical_and(c <= last,
                                  taken_ref[jnp.minimum(c, last)] < end),
        one, cursor[0])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _combine_held(ys, rows, weights, take, onto=None, *, tile: int,
                  interpret: bool):
    m, hidden = ys.shape
    p, k = rows.shape
    # the n-th taken slot is the number of slots before which at most n
    # are taken (a comparison summed over the slots: no sort, no scatter);
    # past the last one that is every slot, p * k, which ends the walk
    before = jnp.cumsum(take.reshape(-1).astype(jnp.int32))
    nth = jnp.arange(m + 1, dtype=jnp.int32)
    taken = jnp.sum((before[:, None] <= nth).astype(jnp.int32), axis=0)
    block = pl.BlockSpec((tile, hidden), lambda i, *_: (i, 0))
    carried = () if onto is None else (onto,)
    return pl.pallas_call(
        functools.partial(_combine_held_kernel, tile=tile, k=k, n_pos=p,
                          onto=onto is not None),
        out_shape=jax.ShapeDtypeStruct((p, hidden), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(p, tile),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [block] * len(carried),
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((m, hidden), jnp.float32),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        # ``onto`` (operand 4, after the three prefetched and ``ys``) is
        # the output's own memory
        input_output_aliases={4: 0} if carried else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_CAP, m * hidden * 4
                                 + 4 * tile * hidden * 4 + 4 * 2**20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * hidden, transcendentals=0,
            bytes_accessed=4 * (m * hidden + (1 + len(carried)) * p * hidden
                                + 3 * p * k)),
        interpret=interpret,
    )(taken, rows.reshape(-1), weights.reshape(-1), ys, *carried)


def combine_supports(ys, rows, take=None) -> bool:
    """Whether ``combine`` takes results ``ys`` ([M, hidden], or [M, pitch,
    128] as ``down(..., whole_rows=True)`` writes them) for ``rows`` [P, k]
    (arrays or their shapes-and-dtypes): float32 rows of whole lane tiles,
    and
    - every slot taken: positions in whole tiles, the two buffers of rows
      at their pitch (``_pitch``: any number of lane tiles a row, rounded
      up to whole sublane tiles) inside the VMEM cap;
    - with ``take``: the results themselves inside their share of VMEM.
    Anything else takes the caller's XLA expressions."""
    m = ys.shape[0]
    hidden = math.prod(ys.shape[1:])
    p, k = rows.shape
    if ys.dtype != jnp.float32 or hidden % _LANES or m == 0 or p == 0:
        return False
    if take is not None:
        return m * hidden * 4 <= HELD_RESULTS_BYTES
    return (p % _COMBINE_TILE == 0
            and _combine_rows_vmem(_COMBINE_TILE, k, hidden) <= _VMEM_CAP)


def combine(ys, rows, weights, take=None, onto=None, *,
            hidden: int | None = None, interpret: bool = False):
    """The results' way back to position order: ``y[p] = sum_j weights[p, j]
    * ys[rows[p, j]]`` over the slots taken (all of them without ``take``),
    float32 throughout, slots added in ascending ``j``; with ``take`` and
    ``onto`` [P, hidden] (a share's carry, whose memory the result takes)
    the slots are added onto it, one after the other. ``ys`` float32 [M,
    hidden] or, its rows whole, [M, pitch, 128] with ``hidden`` the length
    of a row where its pitch holds more (``down(..., whole_rows=True)`` at
    a hidden size that is not whole sublane tiles: [M, 24, 128] does not
    say whether a row is 2,304 or 3,072), ``rows`` int32 [P, k],
    ``weights`` float32 [P, k], ``take`` bool [P, k] -> float32 [P,
    hidden]. Each row that is owed is read once; a slot not taken is never
    read, so what its row holds (a NaN too) reaches nothing, and neither
    does what a row's padding holds."""
    m = ys.shape[0]
    if hidden is None:
        hidden = math.prod(ys.shape[1:])
    if take is None:
        # a row must be one piece to be copied alone: as a leading index
        # of [M, pitch, 128] it is, in the (8, 128)-tiled [M, hidden] it
        # lies in hidden / 128 pieces, 4 KB apart
        assert onto is None, "a carry comes with a share's passes (``take``)"
        if ys.ndim == 2:
            chunks = hidden // _LANES
            idle = _pitch(chunks) - chunks
            ys = ys.reshape(m, chunks, _LANES)
            if idle:
                # a copy: ``down(..., whole_rows=True)`` writes the pitch itself
                ys = jnp.pad(ys, ((0, 0), (0, idle), (0, 0)))
        return _combine_rows(ys, rows, weights, hidden=hidden,
                             tile=_COMBINE_TILE, interpret=interpret)
    return _combine_held(ys.reshape(m, hidden), rows, weights, take, onto,
                         tile=_COMBINE_TILE, interpret=interpret)
