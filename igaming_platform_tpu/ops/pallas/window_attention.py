"""Pallas TPU kernel: the core of attention over short windows, on the
projections' own layouts, eight windows to an MXU tile.

``models/decoder_parts.latent_attention`` (the ``pangu`` head's layer)
attends inside windows of ``T``
positions (16 in the cell) with keys and values of every head expanded from
a latent. Its projections leave position-major, lane-dense matrices: ``q``
[P, heads x (nope + rope)] (float32, as the product accumulated it), ``kv``
[P, heads x (nope + v)] (a head's ``[k_nope | v]``), and the one rotary key
``k_rope`` [P, rope] every head shares; P = windows x T. As einsums over
``[b, t, h, d]`` the core is 32,768 (window, head) products of 16 x 16:
XLA brings heads in front of positions (four to six passes over the
layer's largest arrays), stores scores with 16 of 128 lanes used and loads
the MXU with a 16-row operand per pair. Here the core reads the matrices
where the products wrote them and writes ``Wo``'s operand where ``Wo``
reads it; no ``[b, h, t, s]`` array reaches HBM.

**Eight windows to a tile.** A grid step takes ``_TILE`` = 128 consecutive
positions (128 / T whole windows) and a group of heads. Per head the scores
of all 128 queries against all 128 keys are one product, ``s = q_nope
k_nope^T + q_rope k_rope^T``, masked with ``-inf`` to *same window and key
<= query*; softmax runs in float32 along full 128-lane rows and ``p v`` is
a second [128, 128] product. A masked entry is exactly zero after the
exponential, so every kept score is the same dot product as in the einsum
form and every output the same sum plus exact zeros. That is 128 / T times
the needed operations (8x at T = 16: 43 GFLOP a layer at the published
widths, 0.22 ms at a v5e's peak); the bytes are one read of ``q`` and
``kv`` and one write of the result.

**The rotary part is applied here**, to each head's ``q_rope`` in float32
before its one rounding (``decoder_parts.rotate``'s arithmetic: pair ``i``
is channels ``i`` and ``i + rope / 2``), so the float32 ``q`` is read once,
by this kernel, and no rotated copy of it is written. ``k_rope`` comes
rotated (it is [P, rope]: 0.5 MB).

**Layout.** A head's ``q`` columns start at ``h x (nope + rope)``: at the
published 128 + 64 every other head lies 64 lanes off a vreg boundary. The
grid's blocks and the loop's slices are whole 128-lane multiples (heads
are taken in *units* of one or two, whichever makes every width a multiple
of 128), and the half-vreg shift of a unit's second head happens in VMEM,
by static slices of the loaded unit. A unit's rotary parts are put side by
side (128 lanes at the published widths), turned together (a pair's other
half comes by a lane roll; the angles come laid over the unit's lanes,
``sin`` with rotate-half's sign) and multiplied once against the shared
key laid block-diagonally, ``[unit x 128 keys, unit x rope]``: head
``h``'s rotary scores are columns ``h x 128 ..`` of that product, each the
same 64-term dot product plus exact zeros. On a v5e this form read 1.98 ms
a layer against 2.55 for a slice, four 32-lane products and a
concatenation a head (PERF.md, section 6, PR 39). The loop over a step's
units is rolled (a serving process traces the step again, so what the
kernel costs to trace is paid at boot: PERF.md, PR 37).

Same arithmetic as the einsum form, which stays the reference
(tests/test_window_attention.py) and what runs off the TPU: operands in the
dtype ``kv`` comes in, products accumulated in float32, scale, mask and
softmax (``exp(s - max) / sum``) in float32, probabilities rounded once to
the operands' dtype before ``p v``, the result rounded once to the
operands' dtype (what ``Wo``'s product casts it to). Only the order of
float32 accumulation inside a product and inside the softmax's sum (128
lanes, 128 - T of them exact zeros, against T) may differ.

A value that is not finite does not stay in its window as it does in the
einsum form: ``0 x NaN`` is ``NaN``, so a NaN in one window's values
reaches the windows that share its tile.

**Two forms, picked by the operands a caller has.** The latent form above
(``window_attention``, ``supports``: a shared rotary key and per-head
``[k_nope | v]``) and, further down, the grouped-query form
(``grouped_window_attention``, ``grouped_declines``: shared key heads, the
head norm and a rotary over the whole head inside, an optional second mask
``keep``), which ``models/keye_backbone.attention`` calls. One tile, one
window rule, the same order of roundings; the grouped form's header says
where its layout differs and why.
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

# Heads a grid step takes at most. Measured on a v5e at the cell's shapes
# (PERF.md, section 6, PR 39).
_HEADS_PER_STEP = 16

# Query heads of one key head that a turn of the grouped form's loop takes
# side by side (their chains are independent: the scheduler overlaps one
# head's products with another's arithmetic).
_HEADS_PER_TURN = 4

# What the kernel may ask of the v5e's 128 MiB of VMEM.
_VMEM_CAP = 64 * 2**20


def _unit(nope: int, rope: int, dv: int) -> int:
    """Heads a loop turn takes: the fewest whose ``q`` columns, ``kv``
    columns and rotary parts are each whole 128-lane multiples."""
    return 1 if all(w % _LANES == 0 for w in (nope + rope, nope + dv, rope)) else 2


def _heads_per_step(heads: int, unit: int) -> int:
    """The largest number of heads up to ``_HEADS_PER_STEP`` that is whole
    units and divides ``heads``."""
    return max(g for g in range(unit, max(unit, min(heads, _HEADS_PER_STEP)) + 1,
                                unit) if heads % g == 0)


def _vmem(group: int, unit: int, nope: int, rope: int, dv: int,
          q_size: int, size: int) -> int:
    """Both buffers of a step's blocks, a unit's values and one head's
    float32 scores several times over, and room to spare."""
    blocks = _TILE * (group * ((nope + rope) * q_size + (nope + 2 * dv) * size)
                      + unit * rope * (unit * size + 8))
    turn = (_TILE * unit * (nope + 2 * rope + nope + 2 * dv) * 4
            + (8 + unit) * _TILE * _TILE * 4)
    return 2 * blocks + 2 * turn + 4 * 2**20


def supports(q, kv, *, heads: int, nope: int, rope: int, dv: int,
             window: int) -> bool:
    """Whether the kernel takes ``q`` [P, heads x (nope + rope)] and ``kv``
    [P, heads x (nope + dv)] (arrays or their shapes-and-dtypes) in windows
    of ``window`` positions: whole windows to a tile, head widths in whole
    64-lane halves (so a unit of two heads is whole vregs), heads in whole
    units, bfloat16 or float32 operands, and a step's blocks inside VMEM.
    Anything else takes the caller's einsums."""
    if (window <= 0 or _TILE % window or rope % 2
            or any(w <= 0 or w % (_LANES // 2) for w in (nope, rope, dv))):
        return False
    unit = _unit(nope, rope, dv)
    if heads % unit or q.shape[0] == 0 or q.shape[0] % window:
        return False
    if (q.shape != (kv.shape[0], heads * (nope + rope))
            or kv.shape[1] != heads * (nope + dv)
            or kv.dtype not in (jnp.bfloat16, jnp.float32)):
        return False
    return _vmem(_heads_per_step(heads, unit), unit, nope, rope, dv,
                 q.dtype.itemsize, kv.dtype.itemsize) <= _VMEM_CAP


def _kernel(q_ref, kv_ref, kr_ref, cos_ref, sin_ref, o_ref, *, window: int,
            nope: int, rope: int, dv: int, unit: int, scale: float):
    f32 = jnp.float32
    dt = kv_ref.dtype
    qk, kvw, half = nope + rope, nope + dv, rope // 2
    units = o_ref.shape[1] // (unit * dv)
    row = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 1)
    # key ``col`` is in query ``row``'s window and not after it
    keep = jnp.logical_and(row // window == col // window, col <= row)
    # a lane of the unit's rotary parts lies in its pair's first half
    first = jax.lax.broadcasted_iota(
        jnp.int32, (_TILE, unit * rope), 1) % rope < half
    k_rope, cos, sin = kr_ref[...], cos_ref[...], sin_ref[...]

    def scores(x, y):
        return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    def one_unit(u, carry):
        q = q_ref[:, pl.ds(pl.multiple_of(u * (unit * qk), _LANES), unit * qk)]
        kv = kv_ref[:, pl.ds(pl.multiple_of(u * (unit * kvw), _LANES),
                             unit * kvw)]
        # the unit's rotary parts side by side, whole vregs: a pair's other
        # half comes by a lane roll, and ``sin`` carries the pair's sign
        r = jnp.concatenate([q[:, h * qk + nope:(h + 1) * qk]
                             for h in range(unit)], axis=1).astype(f32)
        other = jnp.where(first, pltpu.roll(r, unit * rope - half, 1),
                          pltpu.roll(r, half, 1))
        r = (r * cos + other * sin).astype(dt)
        # one product against the block-diagonal key: head ``h``'s rotary
        # scores are columns ``h x _TILE ..`` of it, plus exact zeros
        s_rope = scores(r, k_rope)
        out = []
        for h in range(unit):
            q_nope = q[:, h * qk:h * qk + nope].astype(dt)
            k_nope = kv[:, h * kvw:h * kvw + nope]
            v = kv[:, h * kvw + nope:(h + 1) * kvw]
            s = (scores(q_nope, k_nope)
                 + s_rope[:, h * _TILE:(h + 1) * _TILE]) * scale
            s = jnp.where(keep, s, -jnp.inf)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(dt)
            out.append(jnp.dot(p, v, preferred_element_type=f32)
                       .astype(o_ref.dtype))
        o_ref[:, pl.ds(pl.multiple_of(u * (unit * dv), _LANES), unit * dv)] = (
            jnp.concatenate(out, axis=1))
        return carry

    jax.lax.fori_loop(0, units, one_unit, 0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "dv", "window", "group", "interpret", "scale_by"))
def _window_attention(q, kv, k_rope, cos, sin, *, heads: int, nope: int,
                      rope: int, dv: int, window: int, group: int,
                      interpret: bool, scale_by: float = 1.0):
    p = q.shape[0]
    qk, kvw = nope + rope, nope + dv
    unit = _unit(nope, rope, dv)
    size = kv.dtype.itemsize
    # what the unit's rotary parts meet, a tile at a time: the shared key
    # once a head on the diagonal of [unit x _TILE, unit x rope], and the
    # angles over the unit's lanes, ``sin`` signed as rotate-half signs it
    eye = jnp.eye(unit, dtype=bool)[None, :, None, :, None]
    k_rope = jnp.where(eye, k_rope.reshape(p // _TILE, 1, _TILE, 1, rope),
                       jnp.zeros((), k_rope.dtype))
    k_rope = k_rope.reshape(p * unit, unit * rope)
    cos = jnp.tile(cos, (1, 2 * unit))
    sin = jnp.tile(jnp.concatenate([-sin, sin], axis=1), (1, unit))
    by_group = lambda i, g: (i, g)
    by_tile = lambda i, g: (i, 0)
    return pl.pallas_call(
        functools.partial(_kernel, window=window, nope=nope, rope=rope, dv=dv,
                          unit=unit, scale=qk ** -0.5 * scale_by),
        out_shape=jax.ShapeDtypeStruct((p, heads * dv), kv.dtype),
        grid=(p // _TILE, heads // group),
        in_specs=[pl.BlockSpec((_TILE, group * qk), by_group),
                  pl.BlockSpec((_TILE, group * kvw), by_group),
                  pl.BlockSpec((unit * _TILE, unit * rope), by_tile),
                  pl.BlockSpec((_TILE, unit * rope), by_tile),
                  pl.BlockSpec((_TILE, unit * rope), by_tile)],
        out_specs=pl.BlockSpec((_TILE, group * dv), by_group),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, _vmem(
                group, unit, nope, rope, dv, q.dtype.itemsize, size))),
        cost_estimate=pl.CostEstimate(
            flops=2 * p * _TILE * heads * (qk + dv),
            transcendentals=p * _TILE * heads,
            bytes_accessed=(q.size * q.dtype.itemsize
                            + (kv.size + p * heads * dv) * size
                            + p * unit * rope * (unit * size + 8))),
        interpret=interpret,
    )(q, kv, k_rope, cos, sin)


def window_attention(q, kv, k_rope, cos, sin, *, heads: int, nope: int,
                     rope: int, dv: int, window: int,
                     interpret: bool = False, scale_by: float = 1.0):
    """Causal attention inside windows of ``window`` consecutive positions,
    every head against its own keys and the one shared rotary key.

    ``q`` [P, heads x (nope + rope)], a head's ``[q_nope | q_rope]`` with
    the rotary part NOT yet applied (any float dtype; float32 as the
    projection leaves it); ``kv`` [P, heads x (nope + dv)], a head's
    ``[k_nope | v]`` in the operands' dtype; ``k_rope`` [P, rope], rotated,
    in the operands' dtype; ``cos``, ``sin`` [P, rope / 2] float32, a
    position's rotary angles -> [P, heads x dv] in the operands' dtype: per
    head ``softmax((q_nope k_nope^T + rot(q_rope) k_rope^T) / sqrt(nope +
    rope)) v`` over the keys of the query's window at or before it (the
    scores times ``scale_by`` besides: a rotary scaling's attention factor,
    1 without one). P is
    whole windows; a last tile that the windows do not fill is padded with
    zeros here and cut from the result. ``interpret=True`` runs the Pallas
    interpreter, the only way to run the kernel off the TPU, and always the
    caller's explicit choice."""
    p = q.shape[0]
    pad = -p % _TILE
    if pad:
        q, kv, k_rope, cos, sin = (jnp.pad(x, ((0, pad), (0, 0)))
                                   for x in (q, kv, k_rope, cos, sin))
    group = _heads_per_step(heads, _unit(nope, rope, dv))
    out = _window_attention(q, kv, k_rope, cos, sin, heads=heads, nope=nope,
                            rope=rope, dv=dv, window=window, group=group,
                            interpret=interpret, scale_by=scale_by)
    return out[:p] if pad else out


# --- the grouped-query form: shared key heads, rotary over the whole head ---
#
# ``models/keye_backbone.attention``: 32 query heads of 128 over 4 key heads,
# a head norm on q, M-RoPE over the whole head, and the indexer's mask. The
# same tile (128 positions, eight 16-position windows a grid step), the same
# order of roundings as the caller's einsums; what differs from the latent
# form is how a head finds its keys, and which way its operands lie.
#
# **Channel-major.** ``q``, ``v`` and the result are [channels, P], positions
# along the lanes, as ``wq^T a^T`` writes them and as ``wo`` contracts them
# (XLA's products cost the same either way: PERF.md, PR 47). Down a head's
# 128 rows the head norm's mean is a sum of vregs, rotate-half's two halves
# are the upper and lower 64 rows, and a query's scores run down a lane: the
# softmax's max and sum are vreg against vreg on the VPU, and nothing in the
# kernel crosses lanes. (Position-major, with the reductions along lanes, the
# same kernel read 0.66 ms a layer against the einsums' 0.73.)
#
# **A window's own rows.** ``k x`` gives every key of the tile (rows) against
# every query of it (lanes); query lane ``p`` needs only the 16 rows of its
# own window. Those are picked out by lane (window ``w``'s rows where the
# lane is in window ``w``) into [window, 128]: two vregs a head in place of
# sixteen, so scale, mask, ``exp`` and the division run on an eighth of the
# tile and every lane of them is used. The probabilities go back over the
# tile's keys with exact zeros outside the window for ``v p``. The mask rule
# is the latent form's (*same window and key <= query*) in this shape: a
# row is a key of the query's own window, kept where ``key <= query %
# window`` and where the caller's ``keep`` has it.
#
# **Four heads a turn.** A head is a chain (norm, rotary, product, softmax,
# product) whose two products each load a 128 x 128 operand of its own into
# an MXU for 128 rows: one head a loop turn left three MXUs idle (0.43 ms a
# layer); four independent heads side by side in one turn overlap (0.22).
# The loops over key heads and turns stay rolled.


def _kv_heads_per_step(heads: int, kv_heads: int) -> int:
    """Key heads a grid step takes: the most that divide ``kv_heads`` and
    bring at most ``_HEADS_PER_STEP`` query heads with them (one where a
    single key head's queries are already more)."""
    rep = heads // kv_heads
    return max([g for g in range(1, kv_heads + 1)
                if kv_heads % g == 0 and g * rep <= _HEADS_PER_STEP] or [1])


def _grouped_vmem(group: int, rep: int, hd: int, q_size: int, size: int) -> int:
    """Both buffers of a step's blocks (``group`` key heads with their
    queries and results, the angles, the gain, the mask), a turn's heads'
    float32 values and scores several times over, and room to spare."""
    blocks = _TILE * (group * hd * (rep * (q_size + size) + 2 * size)
                      + 2 * hd * 4 + _TILE * 4)
    turn = _HEADS_PER_TURN * (6 * _TILE * hd * 4 + 8 * _TILE * _TILE * 4)
    return 2 * blocks + turn + 4 * 2**20


def grouped_declines(q, k, v, *, heads: int, kv_heads: int, window: int,
                     keep=None) -> str:
    """Why ``grouped_window_attention`` does not take these operands, ""
    where it does: the grouped form's ``supports``, with the reason the
    caller announces beside ``einsum``. ``q`` [heads x hd, P], ``k`` [P,
    kv_heads x hd], ``v`` [kv_heads x hd, P], ``keep`` [P, window] or None
    (arrays or their shapes-and-dtypes). It takes whole windows of whole
    8-row vregs to a tile, heads of whole 128-lane vregs, every key head
    shared by as many query heads, bfloat16 or float32 operands, a step's
    blocks inside VMEM; anything else takes the caller's einsums."""
    if window <= 0 or _TILE % window or window % 8:
        return (f"windows of {window} are not whole 8-row vregs that "
                f"divide a tile of {_TILE}")
    if kv_heads <= 0 or heads <= 0 or heads % kv_heads:
        return f"{heads} heads over {kv_heads} key heads"
    if q.ndim != 2 or q.shape[0] % heads or (q.shape[0] // heads) % _LANES:
        return f"head width {q.shape[0] / heads:g} is not whole {_LANES}-lane vregs"
    hd = q.shape[0] // heads
    p = q.shape[1]
    if p == 0 or p % window:
        return f"{p} positions are not whole windows of {window}"
    if k.shape != (p, kv_heads * hd) or v.shape != (kv_heads * hd, p):
        return f"k {k.shape}, v {v.shape} against q {q.shape}"
    if k.dtype not in (jnp.bfloat16, jnp.float32) or v.dtype != k.dtype:
        return f"operands {k.dtype} / {v.dtype}"
    if not jnp.issubdtype(q.dtype, jnp.floating):
        return f"q {q.dtype}"
    if keep is not None and keep.shape != (p, window):
        return f"keep {keep.shape} against [{p}, {window}]"
    group = _kv_heads_per_step(heads, kv_heads)
    need = _grouped_vmem(group, heads // kv_heads, hd, q.dtype.itemsize,
                         k.dtype.itemsize)
    if need > _VMEM_CAP:
        return f"a step's blocks take {need} of {_VMEM_CAP} bytes of VMEM"
    return ""


def _grouped_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, gain_ref, *rest,
                    window: int, hd: int, rep: int, eps: float, scale: float):
    f32 = jnp.float32
    dt = k_ref.dtype
    *keep_ref, o_ref = rest  # the caller's mask, where it has one
    half, windows = hd // 2, _TILE // window
    turn = max(t for t in range(1, _HEADS_PER_TURN + 1) if rep % t == 0)
    # a window's scores, keys down the rows and queries along the lanes
    key = jax.lax.broadcasted_iota(jnp.int32, (window, _TILE), 0)
    query = jax.lax.broadcasted_iota(jnp.int32, (window, _TILE), 1)
    keep = key <= query % window  # the tile's rule: the key is not after
    if keep_ref:
        keep = jnp.logical_and(keep, keep_ref[0][...] != 0)
    mine = [query // window == w for w in range(windows)]
    cos, sin, gain = cos_ref[...], sin_ref[...], gain_ref[...]

    def one_head(k, v, head):
        rows = pl.ds(pl.multiple_of(head * hd, _LANES), hd)
        x = q_ref[rows, :].astype(f32)
        # the head's RMS norm, then rotate-half over the whole head: a
        # pair's halves are the head's upper and lower rows
        x = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=0, keepdims=True) + eps) * gain
        x1, x2 = x[:half], x[half:]
        x = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                            axis=0).astype(dt)
        # every key of the tile against every query of it, then each
        # query's own window's rows: [window, _TILE], nothing wasted
        s = jnp.dot(k, x, preferred_element_type=f32)
        own = s[:window]
        for w in range(1, windows):
            own = jnp.where(mine[w], s[w * window:(w + 1) * window], own)
        own = jnp.where(keep, own * scale, -jnp.inf)
        e = jnp.exp(own - jnp.max(own, axis=0, keepdims=True))
        p = e / jnp.sum(e, axis=0, keepdims=True)
        # back over the tile's keys: exact zeros outside the window
        p = jnp.concatenate([jnp.where(mine[w], p, 0.0)
                             for w in range(windows)], axis=0).astype(dt)
        o_ref[rows, :] = jnp.dot(
            v, p, preferred_element_type=f32).astype(o_ref.dtype)

    def one_key_head(g, carry):
        k = k_ref[:, pl.ds(pl.multiple_of(g * hd, _LANES), hd)]
        v = v_ref[pl.ds(pl.multiple_of(g * hd, _LANES), hd), :]

        def one_turn(i, carry):
            # independent heads side by side in one loop body: their
            # products overlap on the MXUs
            for j in range(turn):
                one_head(k, v, g * rep + i * turn + j)
            return carry

        return jax.lax.fori_loop(0, rep // turn, one_turn, carry)

    jax.lax.fori_loop(0, k_ref.shape[1] // hd, one_key_head, 0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "eps", "group", "interpret"))
def _grouped_window_attention(q, k, v, cos, sin, gain, keep, *, heads: int,
                              kv_heads: int, window: int, eps: float,
                              group: int, interpret: bool):
    p = q.shape[1]
    hd, rep = q.shape[0] // heads, heads // kv_heads
    size = k.dtype.itemsize
    by_group = lambda i, g: (g, i)
    by_tile = lambda i, g: (0, i)
    whole = lambda i, g: (0, 0)
    # the angles and the mask channel-major as ``q`` is, the gain over a
    # tile's lanes
    operands = [q, k, v, cos.T, sin.T,
                jnp.broadcast_to(gain.astype(jnp.float32)[:, None], (hd, _TILE))]
    in_specs = [pl.BlockSpec((group * rep * hd, _TILE), by_group),
                pl.BlockSpec((_TILE, group * hd), lambda i, g: (i, g)),
                pl.BlockSpec((group * hd, _TILE), by_group),
                pl.BlockSpec((hd // 2, _TILE), by_tile),
                pl.BlockSpec((hd // 2, _TILE), by_tile),
                pl.BlockSpec((hd, _TILE), whole)]
    if keep is not None:
        operands.append(keep.T.astype(jnp.int32))
        in_specs.append(pl.BlockSpec((window, _TILE), by_tile))
    return pl.pallas_call(
        functools.partial(_grouped_kernel, window=window, hd=hd, rep=rep,
                          eps=eps, scale=hd ** -0.5),
        out_shape=jax.ShapeDtypeStruct((heads * hd, p), k.dtype),
        grid=(p // _TILE, kv_heads // group),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((group * rep * hd, _TILE), by_group),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, _grouped_vmem(
                group, rep, hd, q.dtype.itemsize, size))),
        cost_estimate=pl.CostEstimate(
            flops=4 * p * _TILE * heads * hd,
            transcendentals=p * window * heads,
            bytes_accessed=(q.size * q.dtype.itemsize
                            + (k.size + v.size + p * heads * hd) * size
                            + p * (hd + window) * 4)),
        interpret=interpret,
    )(*operands)


def grouped_window_attention(q, k, v, cos, sin, gain, keep=None, *,
                             heads: int, kv_heads: int, window: int,
                             eps: float, interpret: bool = False):
    """Causal grouped-query attention inside windows of ``window``
    consecutive positions: query head ``j`` against key head ``j // (heads
    // kv_heads)``, the query's head norm and rotary applied here.

    ``q`` [heads x hd, P], CHANNEL-MAJOR as its projection accumulated it
    (``wq^T a^T``; any float dtype, float32), NOT yet normed or turned;
    ``k`` [P, kv_heads x hd], position-major, normed, turned and rounded;
    ``v`` [kv_heads x hd, P], channel-major; both in the operands' dtype.
    ``cos``, ``sin`` [P, hd / 2] float32, a position's rotary angles over
    the whole head (rotate-half: pair ``i`` is channels ``i`` and ``i + hd
    / 2``); ``gain`` [hd] the head norm's; ``keep`` [P, window] bool or
    None, a second mask: query ``p`` may read key ``s`` of its own window
    only where ``keep[p, s]`` -> [heads x hd, P] in the operands' dtype,
    channel-major as ``wo``'s product contracts it: per head
    ``softmax(rot(norm(q)) k^T / sqrt(hd)) v`` over the keys of the query's
    window at or before it that ``keep`` leaves. In float32 until the one
    rounding before each product, as the einsum form (``keye_backbone``'s)
    rounds. P is whole windows; a last tile that the windows do not fill is
    padded here (zeros, every key kept) and cut from the result. A query
    whose every key is masked reads NaN, as in the einsum form.
    ``interpret=True`` runs the Pallas interpreter, always the caller's
    explicit choice."""
    p = q.shape[1]
    pad = -p % _TILE
    if pad:
        q, v = (jnp.pad(x, ((0, 0), (0, pad))) for x in (q, v))
        k, cos, sin = (jnp.pad(x, ((0, pad), (0, 0))) for x in (k, cos, sin))
        if keep is not None:
            keep = jnp.pad(keep, ((0, pad), (0, 0)), constant_values=True)
    out = _grouped_window_attention(
        q, k, v, cos, sin, gain, keep, heads=heads, kv_heads=kv_heads,
        window=window, eps=eps, group=_kv_heads_per_step(heads, kv_heads),
        interpret=interpret)
    return out[:, :p] if pad else out
