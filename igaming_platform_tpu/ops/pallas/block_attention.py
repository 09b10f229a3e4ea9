"""Pallas TPU kernels: causal attention over deep windows in blocks of keys;
only the key blocks the mask keeps are visited. Two entries, picked by the
operands a caller has: grouped-query attention with a band
(``block_attention``, ``declines``: shared key heads, the head norm and a
rotary over the whole head inside; a narrow band's keys read in one visit),
which this header describes, and further down latent attention
(``latent_block_attention``, ``latent_declines``: per-head ``[k_nope | v]``
beside one shared rotary key, the rotary part turned inside by the pairing the
caller states), whose own header says where it differs and why.

``models/mellum_backbone.attention`` attends inside windows of ``T``
positions (4,096 in its cell) in two kinds of layer: a *full* one, where
query ``i`` reads every key ``j <= i``, and a *sliding* one, where it reads
``j <= i`` with ``i - j < band`` (1,024; ``models/kexaone_backbone``'s band
is 128 in windows of 2,048). The short-window kernels beside this one
(ops/pallas/window_attention.py) hold a whole window's scores at once and ask
that the window divide a 128-position tile; at 4,096 positions a layer's
``[b, heads, t, s]`` scores are 4.3 GB. Here nothing of ``[t, s]`` reaches
HBM: a program holds one block of queries and meets the keys its mask keeps.
One kernel function serves both kinds of layer (``band`` is ``None`` in a
full one) in two forms, chosen while tracing from ``band`` and the block
alone (``one_visit``): **the sweep** for a full layer and a band wider than
half a block, **one visit** for a band of at most half a block.

**A program** is one window, one key-value head and one block of query
positions, for the ``rep = heads / kv_heads`` query heads that share the
key-value head. ``q`` comes as ``Wq``'s product left it, position-major
float32 ``[P, heads x hd]``: each head's slice is normed (its RMS norm over
the head's ``hd`` lanes) and turned (rotate-half over the whole head: the
other half comes by a lane roll, ``sin`` carries the pair's sign) in
float32, rounded once, and the ``rep`` heads' rows are stacked into one
``[rep x rows, hd]`` operand in VMEM, so that one product against a block of
keys serves all of them. The window's keys and values of that key-value
head lie whole in VMEM (``[T, hd]`` each, 1 MB at 4,096 x 128 bfloat16):
their block index changes only with the window and the key-value head, so
they are read from HBM once a (window, key-value head) and the query blocks
of it read them where they lie.

**The sweep.** A query block holds ``block`` positions (512); block ``i``
covers positions ``i x block ..`` and the diagonal is key block ``i``. In a
full layer it visits key blocks ``0 .. i``; in a sliding one ``lo .. i``
with ``lo = max(0, i x block - band + 1) // block``: 9 of 32 blocks at
``block`` 128 and a band of 1,024, 5 of 16 at 256, 3 of 8 at 512. Three
loops: the blocks the band's edge crosses (the mask applied), the blocks
wholly inside (no mask: every pair is kept), and the diagonal (the mask
applied). Nothing else is read or multiplied. The softmax is online, in
float32: a running maximum ``m`` and sum ``l`` a row, the accumulator
``acc`` ``[rep x block, hd]`` float32; a block's ``exp(s - m)`` is rounded
once to the operands' dtype before its product with ``v``, and the division
by ``l`` comes once, at the end. A row of an edge block may have every key
masked: its maximum stays ``-inf`` and is read as 0 for the subtraction, so
the row adds exact zeros.

**One visit.** What a visit of the sweep costs is fixed by its rows, not its
keys: a pass over the accumulator, the running maximum and sum, and an
``exp`` over the block's scores (``_BLOCK``'s comment has the timings). A
band of 128 under blocks of 512 pays that for 7 of 16 blocks where its keys
fill a quarter of one. So where ``band <= block // 2`` a query block is as
tall as the band in whole 16-row tiles (``qb``, 128) and reads ONE slab of
keys: the ``keys`` positions (``qb + band - 1`` in whole 128-lane tiles,
256; the whole padded window where that is less) that end with the block's
last row, so the slab holds every key any of its rows keeps. One product
``q k^T``, the mask ``0 <= i - j < band`` over the whole slab, the row's
maximum, ``exp``, the row's sum, the product with ``v``, one division: no
loop over key blocks, no ``m``, ``l`` or ``acc`` carried, no rescale. The
first blocks' slabs start at key 0 and hold keys past their rows, which the
same mask drops; a row keeps its own key always, so no row is empty.
Measured on a v5e at ``kexaone``'s cell shape (2 windows of 2,048, 64 / 8
heads of 128, band 128; PERF.md, section 6, PR 66): a sliding layer
1.51 ms as the sweep, 0.66 ms as one visit.

``visited_blocks`` counts what either form scores, in (query, key) pairs.

Same arithmetic as the einsum form in query blocks
(``models/decoder_parts.core_by_einsums``), which stays its reference
(tests/test_block_attention.py) and what runs off the TPU: operands in the
dtype ``k`` comes in, products accumulated in float32, scale and mask in
float32. What differs is where the probabilities are rounded (here before
the division by the row's sum, there after it) and the order of float32
sums.

A window that is not whole query blocks of its form is padded here (zeros:
keys past a real query, which causality masks) and cut from the result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from igaming_platform_tpu.ops.pallas.window_attention import _unit

_LANES = 128
_SUBLANES = 16  # rows of a bfloat16 tile

# Query positions a program of the sweep takes, and keys a step of it.
# Measured on a v5e at mellum's cell shape, 2 windows of 4,096 (PERF.md,
# section 6, PR 57): a sliding layer 4.62 / 3.48 / 2.16 ms and a full one
# 9.25 / 6.18 / 3.49 ms at 128 / 256 / 512. A wider key block spreads a
# visit's pass over the accumulator and the running maximum and sum over more
# keys; it also sweeps more of the square (33% of it in a sliding layer at
# 512, 25% at 128). So smaller square blocks lose, and a band far under a
# block is not served by shrinking this: where ``band <= _BLOCK // 2``
# (``one_visit``; it turns at a band of 256) a query block as tall as the band
# makes one visit of its one slab of keys, with no accumulator to pass over.
# At kexaone's cell shape (2 windows of 2,048, band 128; PERF.md, section 6,
# PR 66) a sliding layer reads 1.51 ms as the sweep and 0.66 ms as one visit
# of 256 keys a 128-row block (0.81 ms at 256-row blocks of 384 keys, 0.86 at
# 512-row blocks of 640: a taller block scores more pairs outside the band).
_BLOCK = 512

# What the kernel may ask of the v5e's 128 MiB of VMEM.
_VMEM_CAP = 96 * 2**20


def block_for(window: int) -> int:
    """Positions a block holds at windows of ``window``: ``_BLOCK``, or the
    whole window in whole 16-row tiles where that is less."""
    return min(_BLOCK, _SUBLANES * -(-window // _SUBLANES))


def one_visit(window: int, band: int | None,
              block: int | None = None) -> tuple[int, int] | None:
    """``(rows a query block holds, keys of its slab)`` where the one-visit
    form runs, ``None`` where the sweep does: a band no wider than half the
    block (``block_for(window)`` without one). A query block is as tall as
    the band in whole 16-row tiles; its slab ends with the block's last row
    and reaches back over the band in whole 128-lane tiles, or is the whole
    padded window where that is less."""
    block = block or block_for(window)
    if band is None or band > block // 2:
        return None
    qb = _SUBLANES * -(-band // _SUBLANES)
    padded = qb * -(-window // qb)
    return qb, min(_LANES * -(-(qb + band - 1) // _LANES), padded)


def swept_blocks(i: int, block: int, band: int | None) -> tuple[int, int]:
    """``(lo, edge_end)`` for query block ``i`` of the sweep: it visits key
    blocks ``lo .. i``; those before ``edge_end`` are crossed by the band's
    edge. The kernel computes the same two numbers from its program id."""
    if band is None:
        return 0, 0
    lo = max(i * block - band + 1, 0) // block
    edge_end = (max((i + 1) * block - band, 0) + block - 1) // block
    return lo, min(max(edge_end, lo), i)


def _swept(window: int, band: int | None, block: int) -> tuple[int, int]:
    """``(key blocks one head's sweep of one window visits, key blocks of
    the square)`` at ``block`` positions a block."""
    n = -(-window // block)
    return sum(i + 1 - swept_blocks(i, block, band)[0] for i in range(n)), n * n


def visited_blocks(window: int, band: int | None,
                   block: int | None = None) -> tuple[int, int]:
    """``((query, key) pairs one head scores in one window, pairs of the
    padded square)`` in the form that runs: the area of the key blocks the
    sweep visits, or of the slabs, one a query block. Pairs and not blocks,
    so that a sum over layers whose blocks differ in shape is still a share
    of the squares: what ``risk_session_head_key_blocks_*_total`` count a
    layer."""
    block = block or block_for(window)
    form = one_visit(window, band, block)
    if form:
        qb, keys = form
        n = -(-window // qb)
        return n * qb * keys, (n * qb) ** 2
    visited, square = _swept(window, band, block)
    return visited * block * block, square * block * block


def one_row(window: int) -> tuple[int, int]:
    """``visited_blocks`` of a layer that reads ONE query a window (not this
    kernel's: a backbone's own core): the row of ``block_for(window)``
    blocks that query meets, and the padded square, in pairs."""
    block = block_for(window)
    n = -(-window // block)
    return n * block * block, (n * block) ** 2


def describe(window: int, band: int | None, *, sweep: bool = False) -> str:
    """How a layer's core goes over a window of ``window``, for the line a
    backbone announces: the form ``block_attention`` runs or, with
    ``sweep``, the key blocks a sweep by ``block_for(window)`` visits
    whatever the band (the einsum forms go in query blocks of that size)."""
    form = None if sweep else one_visit(window, band)
    if form:
        return (f"window {window}, band={band}: one visit of {form[1]} keys a "
                f"{form[0]}-row block")
    block = block_for(window)
    visited, square = _swept(window, band, block)
    return (f"window {window} in blocks of {block}, band={band}: {visited} of "
            f"{square} key blocks")


def _vmem(qb: int, keys: int, rep: int, hd: int, padded: int, q_size: int,
          size: int) -> int:
    """A program of ``qb`` query positions that scores ``keys`` keys a
    visit: both buffers of its blocks (queries, the window's keys and
    values, the result, the angles), the stacked queries and the float32
    result (the sweep's accumulator), a visit's float32 scores several
    times over, and room to spare."""
    rows = rep * qb
    blocks = (qb * rep * hd * (q_size + size) + 2 * padded * hd * size
              + 2 * qb * hd * 4)
    held = rows * hd * (size + 4) + 2 * rows * _LANES * 4
    return 2 * blocks + held + 4 * rows * keys * 4 + 4 * 2**20


def _program(window: int, band: int | None, block: int) -> tuple[int, int]:
    """``(query positions a program takes, keys it scores a visit)`` in the
    form that runs."""
    return one_visit(window, band, block) or (block, block)


def declines(q, k, v, *, heads: int, kv_heads: int, window: int,
             band: int | None = None) -> str:
    """Why ``block_attention`` does not take these operands, "" where it
    does. ``q`` [P, heads x hd], ``k`` and ``v`` [P, kv_heads x hd] (arrays
    or their shapes-and-dtypes). It takes heads of whole 128-lane vregs,
    every key head shared by as many query heads, bfloat16 or float32 keys
    and values of one dtype, whole windows, and a window's keys and values
    of one head beside a program's blocks inside VMEM, in the form a layer
    of ``band`` runs; anything else takes the caller's einsums."""
    if kv_heads <= 0 or heads <= 0 or heads % kv_heads:
        return f"{heads} heads over {kv_heads} key heads"
    if q.ndim != 2 or q.shape[1] % heads or (q.shape[1] // heads) % _LANES:
        return f"head width {q.shape[1] / heads:g} is not whole {_LANES}-lane vregs"
    hd, p = q.shape[1] // heads, q.shape[0]
    if window <= 0 or p == 0 or p % window:
        return f"{p} positions are not whole windows of {window}"
    if k.shape != (p, kv_heads * hd) or v.shape != k.shape:
        return f"k {k.shape}, v {v.shape} against q {q.shape}"
    if k.dtype not in (jnp.bfloat16, jnp.float32) or v.dtype != k.dtype:
        return f"operands {k.dtype} / {v.dtype}"
    if not jnp.issubdtype(q.dtype, jnp.floating):
        return f"q {q.dtype}"
    qb, keys = _program(window, band, block_for(window))
    need = _vmem(qb, keys, heads // kv_heads, hd, qb * -(-window // qb),
                 q.dtype.itemsize, k.dtype.itemsize)
    if need > _VMEM_CAP:
        return f"a program's blocks take {need} of {_VMEM_CAP} bytes of VMEM"
    return ""


def _stack_queries(q_ref, cos_ref, sin_ref, gain_ref, qs_ref, *, qb: int,
                   rep: int, hd: int, eps: float):
    """The ``rep`` heads of a query block, normed, turned and rounded, one
    under the other in ``qs_ref`` [rep x qb, hd]."""
    f32 = jnp.float32
    cos, sin, gain = cos_ref[...], sin_ref[...], gain_ref[...]
    for j in range(rep):
        # a head's RMS norm, then rotate-half over the whole head: the
        # pair's other half comes by a roll of half the lanes
        x = q_ref[:, j * hd:(j + 1) * hd].astype(f32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain
        x = x * cos + pltpu.roll(x, hd // 2, 1) * sin
        qs_ref[j * qb:(j + 1) * qb, :] = x.astype(qs_ref.dtype)


def _unstack(out, o_ref, *, qb: int, rep: int, hd: int):
    for j in range(rep):
        o_ref[:, j * hd:(j + 1) * hd] = (
            out[j * qb:(j + 1) * qb, :].astype(o_ref.dtype))


def _kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, gain_ref, o_ref,
            qs_ref, m_ref, l_ref, acc_ref, *, block: int, rep: int, hd: int,
            band: int | None, eps: float, scale: float):
    f32 = jnp.float32
    dt = k_ref.dtype
    i = pl.program_id(2)
    _stack_queries(q_ref, cos_ref, sin_ref, gain_ref, qs_ref, qb=block,
                   rep=rep, hd=hd, eps=eps)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
    # a stacked row's query and a column's key, each inside its block
    ahead = (jax.lax.broadcasted_iota(jnp.int32, (rep * block, block), 0) % block
             - jax.lax.broadcasted_iota(jnp.int32, (rep * block, block), 1))

    def visit(kb, masked: bool):
        at = pl.ds(pl.multiple_of(kb * block, block), block)
        s = jax.lax.dot_general(qs_ref[...], k_ref[at, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        m_prev = m_ref[...]
        if masked:
            d = ahead + (i - kb) * block  # the query's position less the key's
            keep = d >= 0 if band is None else jnp.logical_and(d >= 0, d < band)
            s = jnp.where(keep, s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a row with no key kept yet: subtract 0, every term is exp(-inf)
            m_sub = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        else:
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_sub = m_new
        alpha = jnp.exp(m_prev - m_sub)
        e = jnp.exp(s - m_sub)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            e.astype(dt), v_ref[at, :], preferred_element_type=f32)
        m_ref[...] = m_new

    def loop(lo, hi, masked: bool):
        def body(kb, carry):
            visit(kb, masked)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    if band is None:
        edge_end = 0
    else:
        lo = jnp.maximum(i * block - (band - 1), 0) // block
        edge_end = (jnp.maximum((i + 1) * block - band, 0) + block - 1) // block
        edge_end = jnp.minimum(jnp.maximum(edge_end, lo), i)
        loop(lo, edge_end, True)      # the band's edge crosses these
    loop(edge_end, i, False)          # wholly inside: every pair is kept
    visit(i, True)                    # the diagonal
    _unstack(acc_ref[...] / l_ref[...], o_ref, qb=block, rep=rep, hd=hd)


def _visit_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, gain_ref, o_ref,
                  qs_ref, *, qb: int, keys: int, rep: int, hd: int, band: int,
                  eps: float, scale: float):
    f32 = jnp.float32
    dt = k_ref.dtype
    i = pl.program_id(2)
    _stack_queries(q_ref, cos_ref, sin_ref, gain_ref, qs_ref, qb=qb, rep=rep,
                   hd=hd, eps=eps)
    # the slab ends with the block's last row; the first blocks' starts at
    # key 0 and holds keys past their rows, which the mask drops
    first = pl.multiple_of(jnp.maximum((i + 1) * qb - keys, 0), _SUBLANES)
    at = pl.ds(first, keys)
    s = jax.lax.dot_general(qs_ref[...], k_ref[at, :], (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * scale
    # the query's position less the key's; a row keeps its own key always
    d = (jax.lax.broadcasted_iota(jnp.int32, (rep * qb, keys), 0) % qb
         - jax.lax.broadcasted_iota(jnp.int32, (rep * qb, keys), 1)
         + (i * qb - first))
    s = jnp.where(jnp.logical_and(d >= 0, d < band), s, -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    out = jnp.dot(e.astype(dt), v_ref[at, :], preferred_element_type=f32)
    _unstack(out / jnp.sum(e, axis=-1, keepdims=True), o_ref, qb=qb, rep=rep,
             hd=hd)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "band", "eps", "block", "interpret"))
def _block_attention(q, k, v, cos, sin, gain, *, heads: int, kv_heads: int,
                     window: int, band: int | None, eps: float, block: int,
                     interpret: bool):
    """``window`` is whole query blocks here (``block_attention`` pads)."""
    p = q.shape[0]
    hd, rep = q.shape[1] // heads, heads // kv_heads
    size = k.dtype.itemsize
    form = one_visit(window, band, block)
    qb, keys = form or (block, block)
    n = window // qb
    pairs = (p // window) * heads * visited_blocks(window, band, block)[0]
    # the angles over the whole head's lanes, ``sin`` signed as rotate-half
    # signs it; the gain a row
    cos = jnp.concatenate([cos, cos], axis=1)
    sin = jnp.concatenate([-sin, sin], axis=1)
    rows = rep * qb
    widths = dict(rep=rep, hd=hd, band=band, eps=eps, scale=hd ** -0.5)
    scratch = [pltpu.VMEM((rows, hd), k.dtype)]  # the stacked queries
    if form:
        kernel = functools.partial(_visit_kernel, qb=qb, keys=keys, **widths)
    else:
        kernel = functools.partial(_kernel, block=block, **widths)
        scratch += [pltpu.VMEM((rows, 1), jnp.float32),   # m
                    pltpu.VMEM((rows, 1), jnp.float32),   # l
                    pltpu.VMEM((rows, hd), jnp.float32)]  # acc
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((p, heads * hd), k.dtype),
        grid=(p // window, kv_heads, n),
        in_specs=[pl.BlockSpec((qb, rep * hd), lambda b, g, i: (b * n + i, g)),
                  pl.BlockSpec((window, hd), lambda b, g, i: (b, g)),
                  pl.BlockSpec((window, hd), lambda b, g, i: (b, g)),
                  pl.BlockSpec((qb, hd), lambda b, g, i: (i, 0)),
                  pl.BlockSpec((qb, hd), lambda b, g, i: (i, 0)),
                  pl.BlockSpec((1, hd), lambda b, g, i: (0, 0))],
        out_specs=pl.BlockSpec((qb, rep * hd), lambda b, g, i: (b * n + i, g)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, _vmem(
                qb, keys, rep, hd, window, q.dtype.itemsize, size))),
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * hd,
            transcendentals=pairs,
            bytes_accessed=(q.size * q.dtype.itemsize
                            + (k.size + v.size + p * heads * hd) * size)),
        interpret=interpret,
    )(q, k, v, cos, sin, gain.astype(jnp.float32).reshape(1, hd))


def _pad_windows(x, window: int, pad: int):
    """``x`` [windows x window, C] with ``pad`` rows of zeros after every
    window."""
    x = jnp.pad(x.reshape(-1, window, x.shape[1]), ((0, 0), (0, pad), (0, 0)))
    return x.reshape(-1, x.shape[2])


def _cut_windows(x, window: int, pad: int):
    """``_pad_windows`` undone: every window's first ``window`` rows."""
    x = x.reshape(-1, window + pad, x.shape[1])[:, :window]
    return x.reshape(-1, x.shape[2])


def block_attention(q, k, v, cos, sin, gain, *, heads: int, kv_heads: int,
                    window: int, band: int | None, eps: float,
                    block: int | None = None, interpret: bool = False):
    """Causal grouped-query attention inside windows of ``window``
    consecutive positions, with a band: query head ``j`` against key head
    ``j // (heads // kv_heads)``, the query's head norm and rotary applied
    here.

    ``q`` [P, heads x hd], position-major as its projection accumulated it
    (any float dtype; float32), NOT yet normed or turned; ``k`` and ``v``
    [P, kv_heads x hd], ``k`` normed, turned and rounded, both in the
    operands' dtype. ``cos``, ``sin`` [window, hd / 2] float32, the rotary
    angles of a window's positions over the whole head (rotate-half: pair
    ``i`` is channels ``i`` and ``i + hd / 2``), the same in every window;
    ``gain`` [hd] the head norm's. ``band`` is the sliding window's width
    (query ``i`` reads key ``j`` where ``0 <= i - j < band``) or ``None`` (a
    full layer: every ``j <= i``) -> [P, heads x hd] in the operands' dtype,
    position-major as ``wo``'s product reads it: per head
    ``softmax(rot(norm(q)) k^T / sqrt(hd)) v`` over the kept keys of the
    query's window. ``block`` is ``block_for(window)`` unless a test says
    otherwise; a band of at most half of it takes the one-visit form
    (``one_visit``), any other and a full layer the sweep. A window that is
    not whole query blocks of the form is padded here and cut from the
    result. ``interpret=True`` runs the Pallas interpreter, always the
    caller's explicit choice."""
    block = block or block_for(window)
    pad = -window % _program(window, band, block)[0]
    if pad:
        q, k, v = (_pad_windows(x, window, pad) for x in (q, k, v))
        cos, sin = (jnp.pad(x, ((0, pad), (0, 0))) for x in (cos, sin))
    out = _block_attention(q, k, v, cos, sin, gain, heads=heads,
                           kv_heads=kv_heads, window=window + pad, band=band,
                           eps=eps, block=block, interpret=interpret)
    return _cut_windows(out, window, pad) if pad else out


# --- the latent form: per-head keys beside one shared rotary key -------------
#
# ``models/decoder_parts.latent_attention`` over windows deeper than one block
# (the ``longcat`` head's 2,048 events). The same blocks as a full layer's
# sweep above (``block_for``; query block ``i`` meets key blocks ``0 .. i``,
# the diagonal's pairs masked ``key <= query``: ``visited_blocks(window,
# None)`` counts them) over a latent layer's operands: every head has its own
# ``[k_nope | v]`` columns of ``kvb`` and all share the one rotary key
# ``k_rope``, so no head's rows stack on another's.
#
# **A program** is one window, one *unit* of heads and one query block. Heads
# go in units of one or two (``window_attention._unit``: at the published 128 +
# 64 every other head's ``q`` columns lie 64 lanes off a vreg boundary; the
# grid's blocks are whole vregs and the half-vreg shift happens in VMEM, by
# static slices). The window's ``kvb`` columns of the unit and ``k_rope`` lie
# whole in VMEM, their block index changing only with the window and the unit,
# so HBM gives them once.
#
# **One product a head, of contraction 256.** ``s = q_nope k_nope^T +
# rot(q_rope) k_rope^T`` is ``[q_nope | rot(q_rope) | 0] [k_nope | k_rope |
# 0]^T``: 192 channels are two passes of the 128-deep MXU either way, and the
# zeros add exact zeros. The window's first query block lays the unit's keys
# out so (``kc_ref``, whole vregs a head) and the window's other blocks read
# them where they lie (the query blocks of one window and unit run in order:
# the grid's last axis is ``arbitrary``). Measured on a v5e at ``longcat``'s
# cell shape (2 windows of 2,048, 16 heads; PERF.md, section 6, PR 69): as two
# products a head 0.76 ms a core, as one of 192 channels concatenated a visit
# 0.72.
#
# **The rotary part turns inside**, in float32 before its one rounding, BY THE
# PAIRING THE CALLER STATES: a unit's rotary parts are put side by side in
# whole vregs and a pair's other channel comes by lane rolls, ``shift`` lanes
# away: interleaved pairs (channels ``2 i`` and ``2 i + 1``: ``shift`` 1, the
# neighbour above an even lane and below an odd one) or rotate-half (``i`` and
# ``i + rope / 2``: ``shift`` ``rope / 2``). ``sin`` comes laid over the unit's
# lanes with the pair's sign.
#
# **One visit a query block**, of every key block it keeps: what a visit of the
# sweep costs is fixed by its rows, not its keys (the header's finding of a
# narrow band, PR 66), and it holds for a whole core too. As the sweep with its
# online softmax (a unit's two heads side by side in one rolled loop over key
# blocks) this form read 0.76 ms a core at blocks of 512, 1.10 at 256 and 0.55
# at 1,024, which scores a fifth more pairs: 3.7 ns a row and visit against 1.9
# ps a scored pair, four fifths of the time in the passes over the running
# maximum, the sum and the accumulator. So query block ``i`` scores its ``(i +
# 1) x block`` keys at once: one product, the mask, the row's maximum, ``exp``
# rounded once to the operands' dtype, its product with ``v``, one division;
# nothing carried, nothing rescaled: **0.45 ms a core** (the einsums in query
# blocks 3.27). The slab's length is static in each of the ``window / block``
# branches a program picks from by its block's index; the branches are what is
# unrolled (four at 2,048), never the heads of a layer. A program's scores are
# ``[block, window]`` float32 at most, which is what ``latent_declines`` holds
# against ``_VMEM_CAP``: a window too deep for it takes the caller's einsums.
#
# The order of roundings is the grouped forms': ``exp(s - m)`` rounded before
# the division by the row's sum, where the einsum reference divides first.


def _latent_widths(nope: int, rope: int, dv: int) -> tuple[int, int]:
    """``(heads a program takes, lanes a head's widened keys take)``: the
    unit, and ``nope + rope`` in whole vregs."""
    return _unit(nope, rope, dv), _LANES * -(-(nope + rope) // _LANES)


def _latent_vmem(block: int, nope: int, rope: int, dv: int, padded: int,
                 q_size: int, size: int) -> int:
    """A program of ``block`` query positions of one unit of heads: both
    buffers of its blocks (queries, the window's ``kvb`` columns of the unit
    and ``k_rope``, the result, the angles), the unit's widened keys, its
    widened queries, a head's float32 scores against the whole window
    several times over, and room to spare."""
    unit, kw = _latent_widths(nope, rope, dv)
    blocks = (block * unit * ((nope + rope) * q_size + dv * size)
              + padded * (unit * (nope + dv) + rope) * size
              + 2 * block * unit * rope * 4)
    held = unit * kw * (padded + block) * size
    return 2 * blocks + held + 4 * unit * block * padded * 4 + 4 * 2**20


def latent_declines(q, kvb, *, heads: int, nope: int, rope: int, dv: int,
                    window: int) -> str:
    """Why ``latent_block_attention`` does not take these operands, "" where
    it does. ``q`` [P, heads x (nope + rope)], ``kvb`` [P, heads x (nope +
    dv)] (arrays or their shapes-and-dtypes; ``k_rope`` [P, rope] comes in
    ``kvb``'s dtype). It takes
    ``k_nope`` and ``v`` of whole 128-lane vregs and a rotary part of whole
    64-lane halves (a unit of two heads is then whole vregs), heads in whole
    units, bfloat16 or float32 keys and values of one dtype, whole windows,
    and a query block's scores against the whole window beside a program's
    blocks inside VMEM; anything else takes the caller's einsums."""
    if (nope <= 0 or dv <= 0 or nope % _LANES or dv % _LANES or rope <= 0
            or rope % (_LANES // 2)):
        return (f"widths {nope} + {rope} / {dv} are not whole {_LANES}-lane "
                f"vregs beside a rotary part of whole {_LANES // 2}-lane halves")
    unit, _ = _latent_widths(nope, rope, dv)
    if heads <= 0 or heads % unit:
        return f"{heads} heads are not whole units of {unit}"
    p = q.shape[0]
    if window <= 0 or p == 0 or p % window:
        return f"{p} positions are not whole windows of {window}"
    if (q.shape != (p, heads * (nope + rope))
            or kvb.shape != (p, heads * (nope + dv))):
        return f"kvb {kvb.shape} against q {q.shape}"
    if kvb.dtype not in (jnp.bfloat16, jnp.float32):
        return f"operands {kvb.dtype}"
    if not jnp.issubdtype(q.dtype, jnp.floating):
        return f"q {q.dtype}"
    block = block_for(window)
    need = _latent_vmem(block, nope, rope, dv, block * -(-window // block),
                        q.dtype.itemsize, kvb.dtype.itemsize)
    if need > _VMEM_CAP:
        return f"a program's blocks take {need} of {_VMEM_CAP} bytes of VMEM"
    return ""


def _latent_kernel(q_ref, kv_ref, kr_ref, cos_ref, sin_ref, o_ref, kc_ref, *,
                   block: int, nope: int, rope: int, dv: int, shift: int,
                   scale: float):
    f32 = jnp.float32
    dt = kv_ref.dtype
    i = pl.program_id(2)
    qk, kvw = nope + rope, nope + dv
    window, unit = kc_ref.shape[0], o_ref.shape[1] // dv
    kw = kc_ref.shape[1] // unit

    def widened(parts, rows):
        """``[nope part | rotary part | 0]`` over a head's ``kw`` lanes."""
        zeros = [jnp.zeros((rows, kw - qk), dt)] if kw > qk else []
        return jnp.concatenate(parts + zeros, axis=1)

    @pl.when(i == 0)
    def _():
        # the window's keys of the unit, [k_nope | k_rope | 0] a head: laid
        # out by the window's first query block, read by all of them
        k_rope = kr_ref[...]
        for h in range(unit):
            kc_ref[:, h * kw:(h + 1) * kw] = widened(
                [kv_ref[:, h * kvw:h * kvw + nope], k_rope], window)

    # the unit's rotary parts side by side, whole vregs: a pair's other
    # channel lies ``shift`` lanes up from the pair's first and as many down
    # from its second, and ``sin`` carries the pair's sign
    first = jax.lax.broadcasted_iota(
        jnp.int32, (block, unit * rope), 1) % (2 * shift) < shift
    q = q_ref[...]
    r = jnp.concatenate([q[:, h * qk + nope:(h + 1) * qk]
                         for h in range(unit)], axis=1).astype(f32)
    other = jnp.where(first, pltpu.roll(r, unit * rope - shift, 1),
                      pltpu.roll(r, shift, 1))
    r = (r * cos_ref[...] + other * sin_ref[...]).astype(dt)
    qc = [widened([q[:, h * qk:h * qk + nope].astype(dt),
                   r[:, h * rope:(h + 1) * rope]], block) for h in range(unit)]

    def visit(j: int):
        """Query block ``j``: its ``j + 1`` key blocks at once."""
        keys = (j + 1) * block
        # the key is not after the query (the diagonal block's pairs alone
        # can fail it)
        keep = (jax.lax.broadcasted_iota(jnp.int32, (block, keys), 1)
                <= jax.lax.broadcasted_iota(jnp.int32, (block, keys), 0)
                + j * block)
        out = []
        for h in range(unit):
            s = jax.lax.dot_general(
                qc[h], kc_ref[:keys, h * kw:(h + 1) * kw],
                (((1,), (1,)), ((), ())), preferred_element_type=f32) * scale
            s = jnp.where(keep, s, -jnp.inf)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            o = jnp.dot(e.astype(dt), kv_ref[:keys, h * kvw + nope:(h + 1) * kvw],
                        preferred_element_type=f32)
            out.append((o / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype))
        o_ref[...] = jnp.concatenate(out, axis=1)

    for j in range(window // block):
        pl.when(i == j)(functools.partial(visit, j))


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "dv", "window", "interleave", "scale_by", "block",
    "interpret"))
def _latent_block_attention(q, kvb, k_rope, cos, sin, *, heads: int, nope: int,
                            rope: int, dv: int, window: int, interleave: bool,
                            scale_by: float, block: int, interpret: bool):
    """``window`` is whole query blocks here (``latent_block_attention``
    pads)."""
    p = q.shape[0]
    qk, kvw = nope + rope, nope + dv
    unit, kw = _latent_widths(nope, rope, dv)
    size = kvb.dtype.itemsize
    n = window // block
    pairs = (p // window) * heads * visited_blocks(window, None, block)[0]
    # the angles over a unit's lanes, ``sin`` signed as the pairing signs it
    if interleave:
        cos = jnp.repeat(cos, 2, axis=1)
        sin = jnp.stack([-sin, sin], axis=-1).reshape(p, rope)
    else:
        cos = jnp.concatenate([cos, cos], axis=1)
        sin = jnp.concatenate([-sin, sin], axis=1)
    cos, sin = jnp.tile(cos, (1, unit)), jnp.tile(sin, (1, unit))
    by_block = lambda b, g, i: (b * n + i, g)
    angles = pl.BlockSpec((block, unit * rope), lambda b, g, i: (b * n + i, 0))
    return pl.pallas_call(
        functools.partial(_latent_kernel, block=block, nope=nope, rope=rope,
                          dv=dv, shift=1 if interleave else rope // 2,
                          scale=qk ** -0.5 * scale_by),
        out_shape=jax.ShapeDtypeStruct((p, heads * dv), kvb.dtype),
        grid=(p // window, heads // unit, n),
        in_specs=[pl.BlockSpec((block, unit * qk), by_block),
                  pl.BlockSpec((window, unit * kvw), lambda b, g, i: (b, g)),
                  pl.BlockSpec((window, rope), lambda b, g, i: (b, 0)),
                  angles, angles],
        out_specs=pl.BlockSpec((block, unit * dv), by_block),
        scratch_shapes=[pltpu.VMEM((window, unit * kw), kvb.dtype)],  # the keys
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, _latent_vmem(
                block, nope, rope, dv, window, q.dtype.itemsize, size))),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * (qk + dv),
            transcendentals=pairs,
            bytes_accessed=(q.size * q.dtype.itemsize
                            + (kvb.size + k_rope.size + p * heads * dv) * size
                            + 2 * p * unit * rope * 4)),
        interpret=interpret,
    )(q, kvb, k_rope, cos, sin)


def latent_block_attention(q, kvb, k_rope, cos, sin, *, heads: int, nope: int,
                           rope: int, dv: int, window: int,
                           interleave: bool = False, scale_by: float = 1.0,
                           block: int | None = None, interpret: bool = False):
    """Causal latent attention inside windows of ``window`` consecutive
    positions, every head against its own keys and the one shared rotary
    key, in blocks of keys: ``decoder_parts.latent_core_by_einsums``'
    signature, which stays its reference and what runs off the TPU.

    ``q`` [P, heads x (nope + rope)], a head's ``[q_nope | q_rope]`` with
    the rotary part NOT yet turned (any float dtype; float32 as the
    projection leaves it); ``kvb`` [P, heads x (nope + dv)], a head's
    ``[k_nope | v]`` in the operands' dtype; ``k_rope`` [P, rope], turned,
    in the operands' dtype; ``cos``, ``sin`` [P, rope / 2] float32, a
    position's rotary angles -> [P, heads x dv] in the operands' dtype,
    position-major as ``Wo``'s product reads it: per head ``softmax((q_nope
    k_nope^T + rot(q_rope) k_rope^T) x (nope + rope) ** -0.5 x scale_by) v``
    over the keys of the query's window at or before it. ``rot`` turns
    interleaved pairs with ``interleave`` (channels ``2 i`` and ``2 i +
    1``), else rotate-half pairs (``i`` and ``i + rope / 2``); ``k_rope``
    comes turned by the same pairing. ``block`` is ``block_for(window)``
    unless a test says otherwise. A window that is not whole query blocks is
    padded here (zeros: keys past a real query, which causality masks) and
    cut from the result. ``interpret=True`` runs the Pallas interpreter,
    always the caller's explicit choice."""
    block = block or block_for(window)
    pad = -window % block
    if pad:
        q, kvb, k_rope, cos, sin = (_pad_windows(x, window, pad)
                                    for x in (q, kvb, k_rope, cos, sin))
    out = _latent_block_attention(
        q, kvb, k_rope, cos, sin, heads=heads, nope=nope, rope=rope, dv=dv,
        window=window + pad, interleave=interleave, scale_by=scale_by,
        block=block, interpret=interpret)
    return _cut_windows(out, window, pad) if pad else out
