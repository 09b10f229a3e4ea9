"""A sparse-expert decoder backbone over the session window (the
``keye`` session head, models/session_heads.py).

The block is the language model of Keye-VL-2.0-30B-A3B (a Qwen3-MoE
decoder layer with a learned-sparse-attention indexer and M-RoPE), at
the published widths by default: hidden 2048, 32 query / 4 key-value
heads of 128, 128 experts of width 768 with 8 a token, an indexer of 16
heads of 64 with one key head and ``topk`` 2048. Events enter it the way
image patches enter that model, as ``inputs_embeds`` from a projector
(``x @ W_in``, 12 -> hidden); the score is a sequence-classification
head on the last real position. Each layer, over the residual stream
``h`` [P, hidden], position-major with ``P = B x T`` from the projector to
the final norm (every product reads and writes it so):

1. ``a = RMSNorm(h)``; grouped-query attention with per-head RMSNorm on
   q and k and M-RoPE (three position-id streams, ``mrope_section``
   frequency pairs each); causal.
2. The indexer scores every causal key for every query (``sum_h w[t,h]
   * relu(qi[t,h] . ki[s]) / sqrt(d)``) and query ``t`` attends only to
   its ``min(topk, t + 1)`` best keys. On a TPU, where
   ``ops/pallas/window_attention``'s grouped-query form takes the layer
   (windows that divide 128, heads of whole 128-lane vregs: the published
   widths do), the core (q's head norm and rotary, scores, that mask,
   softmax, ``p v``) is one Pallas kernel on ``wq``'s and ``wv``'s results
   as the products wrote them; elsewhere two einsums over ``[b, t, h,
   d]``, which are its reference and what the CPU tests and replay run.
   Chosen while tracing and announced once (``attention core: ...``, with
   the kernel's reason where it declines).
3. ``b = RMSNorm(h)``; a router over ALL experts, softmax in float32,
   the ``top_k`` largest renormalised; every (position, expert) pair is
   computed — the layer is DROPLESS: pairs are sorted by expert and the
   three expert products are grouped products over the stacked expert
   weights, whatever the routing's skew. On a TPU, at lane-aligned widths
   and bfloat16, they run as two Pallas kernels (ops/pallas/
   grouped_experts.py: gate and up share one read of the sorted rows,
   silu and their product in the epilogue; then down), chosen while
   tracing and announced once (``expert core: ...``); anywhere else as
   three ``lax.ragged_dot`` calls, which stay the kernels' reference and
   what the CPU tests and replay run. The results' way back to position
   order (each position's rows times the router's weights, summed in
   float32) is chosen the same way (``combine: ...``): a third kernel of
   that file, ``combine``, which reads each row that is owed once, or
   XLA's row gather and sum. Same arithmetic on both.

Precision: parameters bfloat16 at rest (norm gains and the scoring head
float32); every product multiplies ``operand_dtype`` operands and
accumulates in float32; residual stream, norms, softmax, router and
indexer scores, top-k and the logit are float32.

``jax.named_scope`` marks the parts (``head/embed``, ``head/attn`` with
``head/attn/indexer`` and ``head/attn/core`` inside, ``head/moe/route``,
``head/moe/experts``)
so that a device trace can be read by part.
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class BackboneConfig:
    in_dim: int = 12
    hidden: int = 2048
    layers: int = 4
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    experts: int = 128
    top_k: int = 8
    expert_width: int = 768
    idx_heads: int = 16
    idx_dim: int = 64
    idx_topk: int = 2048
    mrope_section: tuple[int, ...] = (16, 24, 24)
    rope_theta: float = 1e7
    eps: float = 1e-6
    # the depth the seeded tree is initialised for: the two projections that
    # write into the residual stream are scaled by 1 / sqrt(2 * init_depth)
    # (the published 48 layers, of which ``layers`` are held)
    init_depth: int = 48
    operand_dtype: Any = jnp.bfloat16


# The most float32 normals one draw of ``_matrix`` makes (2^24: 64 MB).
_DRAW_ELEMS = 1 << 24


def row_blocks(rows: int, cols: int) -> int:
    """In how many equal row blocks a [rows, cols] matrix is drawn so that
    no draw passes ``_DRAW_ELEMS`` elements: the least divisor of ``rows``
    that leaves blocks of whole bfloat16 tiles (16 rows), 1 where the
    matrix is small or has no such divisor."""
    need = -(-rows * cols // _DRAW_ELEMS)
    if need <= 1:
        return 1
    return next((b for b in range(need, rows // 16 + 1)
                 if rows % (16 * b) == 0), 1)


@partial(jax.jit, static_argnums=(1, 2))
def _matrix(key, shape: tuple[int, ...], fan_in: int):
    """Seeded normals scaled by ``fan_in ** -0.5``, in bfloat16. A stacked
    weight ([experts, ...]) is generated slice by slice (``lax.map``), and
    a matrix of more than ``_DRAW_ELEMS`` elements row block by row block,
    so the float32 normals never exceed one expert's matrix or one
    block."""
    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(jnp.bfloat16)

    if len(shape) == 3:
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    blocks = row_blocks(*shape)
    if blocks > 1:
        return jax.lax.map(lambda k: draw(k, (shape[0] // blocks, shape[1])),
                           jax.random.split(key, blocks)).reshape(shape)
    return draw(key, shape)


def init_backbone(key, cfg: BackboneConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16: nothing of it ever exists in float32, so the build adds at
    most one matrix (the largest is one layer's stacked expert weight) to
    what the tree itself takes. Every matrix keeps its input's variance
    (``fan_in ** -0.5``); ``wo`` and ``wd``, which write into the residual
    stream, are scaled by ``1 / sqrt(2 * init_depth)`` besides, the scaled
    initialisation deep decoders are trained from. (At unit scale a
    flipped 8th expert moves the next router's input by 2% against a gap
    of 3% to the 9th, and one flip breeds the next: PERF.md, PR 34.)"""
    f32 = jnp.float32
    d, hd, f = cfg.hidden, cfg.head_dim, cfg.expert_width
    keys = iter(jax.random.split(key, 2 + 11 * cfg.layers))
    out = 2 * cfg.init_depth  # a fan-in 2 * init_depth times as large

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    layers = []
    for _ in range(cfg.layers):
        layers.append({
            "g1": jnp.ones((d,), f32), "g2": jnp.ones((d,), f32),
            "wq": matrix((d, cfg.heads * hd), d),
            "wk": matrix((d, cfg.kv_heads * hd), d),
            "wv": matrix((d, cfg.kv_heads * hd), d),
            "wo": matrix((cfg.heads * hd, d), cfg.heads * hd * out),
            "qn": jnp.ones((hd,), f32), "kn": jnp.ones((hd,), f32),
            "wqi": matrix((d, cfg.idx_heads * cfg.idx_dim), d),
            "wki": matrix((d, cfg.idx_dim), d),
            "ww": matrix((d, cfg.idx_heads), d),
            "kin": {"scale": jnp.ones((cfg.idx_dim,), f32),
                    "bias": jnp.zeros((cfg.idx_dim,), f32)},
            "wr": matrix((d, cfg.experts), d),
            "wg": matrix((cfg.experts, d, f), d),
            "wu": matrix((cfg.experts, d, f), d),
            "wd": matrix((cfg.experts, f, d), f * out),
        })
    return {
        "embed": matrix((cfg.in_dim, d), cfg.in_dim),
        "layers": layers,
        "gf": jnp.ones((d,), f32),
        "head": {"w": jax.random.normal(next(keys), (d, 1), f32)
                 * (1.0 / math.sqrt(d)),
                 "b": jnp.zeros((1,), f32)},
    }


def _mm(x, w, cfg: BackboneConfig):
    """``x @ w`` over the last axis of ``x``: operands in the stated
    dtype, accumulated in float32."""
    dt = cfg.operand_dtype
    return jax.lax.dot_general(
        x.astype(dt), w.astype(dt), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _mm_t(w, x, cfg: BackboneConfig):
    """``(x @ w)^T`` as the product ``w^T x^T``, [out, P] channel-major:
    the same operands and the same float32 sums as ``_mm``, the result
    written positions along the lanes."""
    dt = cfg.operand_dtype
    return jax.lax.dot_general(w.astype(dt), x.astype(dt),
                               (((0,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, p, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def mrope_angles(pos3, head_dim: int, sections, theta: float):
    """M-RoPE: ``pos3`` [3, B, T] (temporal, height, width ids) ->
    (cos, sin) [B, T, head_dim // 2]. Frequency pair ``i`` turns by
    ``theta ** (-2 i / head_dim)`` a step of the stream its section
    names: the first ``sections[0]`` pairs follow the temporal id, the
    next ``sections[1]`` the height id, the rest the width id."""
    half = head_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    stream = np.repeat(np.arange(len(sections)), sections)
    assert len(stream) == half, (sections, head_dim)
    pos = jnp.take(pos3.astype(jnp.float32), stream, axis=0)  # [half, B, T]
    ang = jnp.moveaxis(pos, 0, -1) * inv
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """Rotary embedding on the leading ``2 * cos.shape[-1]`` channels of
    ``x`` [B, T, H, D] (pair ``i`` is channels ``i`` and ``i + half``:
    the rotate-half convention); the rest pass through."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def indexer_keep(a, layer: Params, cos, sin, cfg: BackboneConfig, window: int):
    """The learned-sparse selection over normed hidden states ``a`` [P,
    hidden] in windows of ``window`` positions (``cos``, ``sin`` [P, half]):
    [P, window] bool, ``keep[b * window + t, s]`` iff key ``s`` of window
    ``b`` is causal for its query ``t`` and among that query's
    ``min(idx_topk, t + 1)`` best by the indexer's score. Follows
    DeepSeek-V3.2's indexer where the source's config is silent: LayerNorm
    on the one key head, rotary on the first half of its channels (by the
    temporal id), head weights scaled by ``idx_heads ** -0.5``."""
    t = window
    b = a.shape[0] // t
    nh, dh = cfg.idx_heads, cfg.idx_dim
    rot = dh // 4  # rotary pairs: half of the channels turn
    cos, sin = (x.reshape(b, t, -1)[..., :rot] for x in (cos, sin))
    qi = _mm(a, layer["wqi"], cfg).reshape(b, t, nh, dh)
    ki = layer_norm(_mm(a, layer["wki"], cfg), layer["kin"], cfg.eps)
    qi = rotate(qi, cos, sin)
    ki = rotate(ki.reshape(b, t, 1, dh), cos, sin)[:, :, 0, :]
    w = _mm(a, layer["ww"], cfg).reshape(b, t, nh) * (nh ** -0.5)
    dt = cfg.operand_dtype
    dots = jnp.einsum("bthd,bsd->bths", qi.astype(dt), ki.astype(dt),
                      preferred_element_type=jnp.float32)
    # float32 multiply-reduce over the heads, not a product on the MXU: the
    # scores decide a top-k and stay in float32
    score = jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2) * (dh ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    score = jnp.where(causal, score, -jnp.inf)
    k = min(cfg.idx_topk, t)
    _, best = jax.lax.top_k(score, k)                         # [B, T, k]
    chosen = jnp.any(best[..., None] == jnp.arange(t), axis=-2)
    return jnp.logical_and(chosen, causal).reshape(b * t, t)


def _core_by_einsums(q, k, v, cos, sin, gain, keep, *, heads: int,
                     kv_heads: int, window: int, eps: float):
    """The core of attention as two einsums over ``[b, t, h, d]``: the
    reference of the kernel's grouped form (ops/pallas/window_attention.
    grouped_window_attention, which takes ``q`` and ``v`` channel-major),
    and what runs off the TPU. ``q`` [P, heads x hd] float32 as ``wq`` left
    it (its head norm and rotary happen here), ``k`` and ``v`` [P, kv_heads
    x hd] ready and rounded, ``keep`` [P, window] -> float32 [P, heads x
    hd], which ``wo``'s product rounds."""
    dt, t = k.dtype, window
    b, hd = q.shape[0] // t, q.shape[1] // heads
    cos, sin = cos.reshape(b, t, -1), sin.reshape(b, t, -1)
    q = rotate(rms_norm(q.reshape(b, t, heads, hd), gain, eps), cos, sin)
    # query head j reads key-value head j // (heads // kv_heads)
    q = q.reshape(b, t, kv_heads, heads // kv_heads, hd)
    sc = jnp.einsum("btgjd,bsgd->bgjts", q.astype(dt),
                    k.reshape(b, t, kv_heads, hd),
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    sc = jnp.where(keep.reshape(b, 1, 1, t, t), sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bgjts,bsgd->btgjd", p.astype(dt),
                   v.reshape(b, t, kv_heads, hd),
                   preferred_element_type=jnp.float32)
    return o.reshape(b * t, heads * hd)


def _attention_core(positions: int, keep, cfg: BackboneConfig, window: int) -> bool:
    """Whether the core of attention over ``positions`` positions in
    windows of ``window`` runs as the Pallas kernel's grouped-query form
    (ops/pallas/window_attention.py: on a TPU, where it takes the
    operands' shapes) or as ``_core_by_einsums``. Picked while tracing,
    from backend and shapes, and announced once a compile, with the
    kernel's reason where it declines."""
    from igaming_platform_tpu.ops.pallas import window_attention as kernel

    backend = jax.default_backend()
    nh, nkv, hd, dt = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.operand_dtype
    why = "not a TPU" if backend != "tpu" else kernel.grouped_declines(
        jax.ShapeDtypeStruct((nh * hd, positions), jnp.float32),
        jax.ShapeDtypeStruct((positions, nkv * hd), dt),
        jax.ShapeDtypeStruct((nkv * hd, positions), dt),
        heads=nh, kv_heads=nkv, window=window, keep=keep)
    _announce_core(
        f"einsum ({why})" if why else
        f"pallas-windows (grouped {nh}/{nkv} of {hd}, window {window}, mask=keep)",
        backend, "attention core")
    return not why


def attention(h, layer: Params, cos, sin, cfg: BackboneConfig, window: int):
    """The attention sublayer over the residual stream ``h`` [P, hidden] in
    windows of ``window`` positions (``cos``, ``sin`` [P, head_dim / 2]) ->
    [P, hidden]. The core (the query's head norm and rotary, scores, the
    indexer's mask, softmax, ``p v``) is one Pallas kernel where
    ``_attention_core`` finds that it takes the layer: ``wq`` and ``wv``
    then leave their results channel-major ([channels, P]: ``w^T a^T``,
    the same products), the kernel reads them where they were written and
    writes ``wo``'s operand as ``wo`` contracts it, and nothing of ``[b,
    heads, t, s]`` and no copy of ``q`` reaches HBM. Elsewhere it is two
    einsums over ``[b, t, h, d]`` on position-major products: the same
    arithmetic at the same precision either way."""
    from igaming_platform_tpu.ops.pallas import window_attention as kernel

    p = h.shape[0]
    nh, nkv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.operand_dtype
    a = rms_norm(h, layer["g1"], cfg.eps)
    k = rms_norm(_mm(a, layer["wk"], cfg).reshape(1, p, nkv, hd), layer["kn"],
                 cfg.eps)
    k = rotate(k, cos[None], sin[None]).astype(dt).reshape(p, nkv * hd)
    with jax.named_scope("indexer"):
        keep = indexer_keep(a, layer, cos, sin, cfg, window)
    widths = dict(heads=nh, kv_heads=nkv, window=window, eps=cfg.eps)
    if _attention_core(p, keep, cfg, window):
        # q as the product leaves it: float32, since the head norm and the
        # rotary come before the rounding
        q, v = _mm_t(layer["wq"], a, cfg), _mm_t(layer["wv"], a, cfg).astype(dt)
        with jax.named_scope("core"):
            o = kernel.grouped_window_attention(q, k, v, cos, sin, layer["qn"],
                                                keep, **widths)
        return jax.lax.dot_general(o, layer["wo"].astype(dt),
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    q, v = _mm(a, layer["wq"], cfg), _mm(a, layer["wv"], cfg).astype(dt)
    with jax.named_scope("core"):
        o = _core_by_einsums(q, k, v, cos, sin, layer["qn"], keep, **widths)
    return _mm(o, layer["wo"], cfg)


def route(x, layer: Params, cfg: BackboneConfig):
    """Router over all experts: ``(experts [P, top_k] int32, weights
    [P, top_k] float32)``, the weights renormalised over the chosen."""
    p = jax.nn.softmax(_mm(x, layer["wr"], cfg), axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg.top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


# What each part last said it runs as (``/debug/sessionz``'s ``head_cores``).
_ANNOUNCED: dict[str, str] = {}


@lru_cache(maxsize=None)
def _announce_core(core: str, backend: str, part: str = "expert core") -> None:
    """Log, once per (part, core, backend), which core runs a part of the
    head (``expert core``: the expert layer's grouped products, and where
    they are the kernels how those are fed; ``combine``: the results' way
    back to position order; ``attention core``: the window kernel or the
    einsums, with the kernel's reason where it declines): the choice is
    made at trace time and is otherwise invisible. ``announced_cores``
    keeps the last word of each part."""
    _ANNOUNCED[part] = f"{core} (backend={backend})"
    logger.info("%s: %s (backend=%s)", part, core, backend)  # noqa: JX01 — deliberately a trace-time log: the core is chosen while tracing, once per compile


def announced_cores() -> dict[str, str]:
    """Part -> the core it last announced, for the steps traced so far in
    this process (empty before the first trace, and for a head that has
    no such part)."""
    return dict(_ANNOUNCED)


def _expert_products(xs, sizes, layer: Params, cfg: BackboneConfig,
                     whole_rows: bool = False, rows=None):
    """Rows ``xs`` [M, hidden] sorted by expert, ``sizes`` [E] -> float32
    [M, hidden]: ``(silu(xs @ wg[e]) * (xs @ wu[e])) @ wd[e]`` for each
    row's expert ``e``; with ``rows`` [M], ``xs`` is the positions [P,
    hidden] still unsorted and sorted row ``i`` is ``xs[rows[i]]``. On a
    TPU, at shapes the kernels support, two Pallas grouped kernels
    (ops/pallas/grouped_experts.py: gate and up share one read of the
    rows, silu and the product in the epilogue; with ``whole_rows`` the
    second writes [M, hidden / 128, 128], each row one piece of memory, for
    ``combine`` to copy row by row). How they are fed is read from the
    shapes and announced with the core: the weights through a ring of VMEM
    slots, the rows brought together inside ``gate_up`` out of the
    positions it holds in VMEM (``takes_rows``), or gathered here into a
    sorted copy. Elsewhere that gather and three ``lax.ragged_dot``
    products, which are also the kernels' golden reference."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    backend = jax.default_backend()
    m, hidden = xs.shape[0] if rows is None else rows.shape[0], xs.shape[1]
    e, _, width = layer["wg"].shape
    if backend == "tpu" and kernels.supports(
            jax.ShapeDtypeStruct((m, hidden), xs.dtype), layer["wg"]):
        if rows is not None and not kernels.takes_rows(xs, rows, layer["wg"]):
            xs, rows = xs[rows], None
        fed = kernels.feed(m, hidden, e, width,
                           None if rows is None else xs.shape[0])
        _announce_core(f"pallas-grouped ({fed})", backend)
        mid = kernels.gate_up(xs, layer["wg"], layer["wu"], sizes, rows=rows)
        return kernels.down(mid, layer["wd"], sizes, whole_rows=whole_rows)
    _announce_core("xla-ragged-dot", backend)
    dt = cfg.operand_dtype
    if rows is not None:
        xs = xs[rows]

    def grouped(lhs, w):
        return jax.lax.ragged_dot(lhs, w.astype(dt), sizes,
                                  preferred_element_type=jnp.float32)

    mid = jax.nn.silu(grouped(xs, layer["wg"])) * grouped(xs, layer["wu"])
    return grouped(mid.astype(dt), layer["wd"])


def _combine_by_kernel(results, rows, take=None) -> bool:
    """Whether the results' way back to position order runs as the Pallas
    ``combine`` (ops/pallas/grouped_experts.py) or as the XLA expressions
    that stand beside each call, which are its reference and what runs off
    the TPU. ``results`` [M, hidden] float32 and ``rows`` [P, k], arrays or
    shapes; ``take`` is given where only some slots are owed. Picked while
    tracing, from backend and shapes, and announced once a compile."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    backend = jax.default_backend()
    by_kernel = backend == "tpu" and kernels.combine_supports(results, rows, take)
    _announce_core("pallas-rows" if by_kernel else "xla-gather", backend,
                   "combine")
    return by_kernel


def expert_sizes(keys, held: int):
    """How many of ``keys`` [M] name each of the ``held`` experts: int32
    [held], ``jnp.bincount``'s integers without its scatter of M ones (1.15
    ms a step in the keye cell: PERF.md, PR 35), as a comparison of every
    key with every bin, summed over the keys. A key past the last held
    expert (an absent or a padded pair's) is counted by no bin."""
    bins = jnp.arange(held, dtype=keys.dtype)
    return jnp.sum((keys[:, None] == bins).astype(jnp.int32), axis=0)


# Rows one pass of a share's pairs is rounded up to: the expert kernels'
# row tile (ops/pallas/grouped_experts._tiles).
_PASS_TILE = 256


def pass_rows(pairs: int, held: int, experts: int, hidden: int = 0) -> int:
    """The static bound on the rows one pass over a share's pairs gathers
    and multiplies: four times the share's expected pairs at uniform
    routing, rounded up to the kernels' tile; never more than all the
    pairs (which it is where every expert is held); and, given the rows'
    ``hidden`` size, no more tiles than leave a pass's float32 results
    inside what ``combine`` keeps of them in VMEM for a whole call
    (ops/pallas/grouped_experts.HELD_RESULTS_BYTES; one tile at least)."""
    from igaming_platform_tpu.ops.pallas.grouped_experts import HELD_RESULTS_BYTES

    share = -(-4 * pairs * held // experts)
    rows = _PASS_TILE * -(-share // _PASS_TILE)
    if hidden:
        fit = HELD_RESULTS_BYTES // (4 * hidden * _PASS_TILE)
        rows = min(rows, _PASS_TILE * max(fit, 1))
    return min(pairs, rows)


def grouped_experts(x, top_e, top_w, layer: Params, cfg, first_expert: int = 0,
                    live=None):
    """Dropless expert layer over positions ``x`` [P, hidden] for the
    experts HELD HERE: the stacked weights of ``layer`` are experts
    ``first_expert ..`` of ``cfg.experts`` (all of them, or a chip's
    share; how many is the weights' leading size). The router chose over
    all experts and normalised its weights over all it chose; every
    (position, expert) pair whose expert is held is computed, whatever
    the routing, and a pair whose expert lies elsewhere is never gathered
    or multiplied: what it would add is left out, and nothing stands in
    for the chip that holds it. With ``live`` [P] bool (a share only) the
    pairs of positions that are not live, a window's padding, are left
    out the same way.

    The pairs are sorted by local expert (absent ones take a key past the
    last and sort behind), so each held expert's rows are contiguous and
    the three products run grouped over the stacked weights
    (``_expert_products``).

    - Every expert held: one pass over all pairs; the results return to
      position order by the inverse permutation and are summed over a
      position's experts in float32.
    - A share: the held pairs are worked ``pass_rows`` at a time by a loop
      whose trip count is ``ceil(held pairs / pass_rows)`` (one pass at a
      routing anywhere near uniform, more under skew, none where no pair
      is held). Each pass gathers its rows, multiplies them, and every
      position takes its own pairs' results back out of the pass, times
      the router's weight, in float32: nothing is scattered (XLA's
      scatter-add of the same rows took three times as long on a v5e:
      PERF.md, PR 36). Temporaries are bounded by ``pass_rows``, not by
      all pairs.

    The way back is ``_combine_by_kernel``'s choice, made while tracing:
    the Pallas ``combine`` (each owed row read once; a slot that is not
    taken reads nothing), or the XLA expressions written out below it (a
    row gather and a sum; for a share a row gather a slot under a
    ``where``), which are its reference and what runs off the TPU."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    n, k = top_e.shape
    held = layer["wg"].shape[0]
    everything = held == cfg.experts
    if everything:
        assert live is None, "every position is routed where every expert is held"
        flat_e = top_e.reshape(-1)
    else:
        local = top_e - first_expert
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        flat_e = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sizes = expert_sizes(flat_e, held)
    xb = x.astype(cfg.operand_dtype)
    hidden = x.shape[-1]
    rank = jnp.argsort(order).reshape(n, k)  # the sorted row of every pair
    if everything:
        results = jax.ShapeDtypeStruct((n * k, hidden), jnp.float32)
        if _combine_by_kernel(results, rank):
            ys = _expert_products(xb, sizes, layer, cfg, whole_rows=True,
                                  rows=order // k)
            return kernels.combine(ys, rank, top_w)
        ys = _expert_products(xb, sizes, layer, cfg, rows=order // k)
        y = ys[rank.reshape(-1)].reshape(n, k, -1)
        return jnp.sum(y * top_w[..., None], axis=1)

    rows = pass_rows(n * k, held, cfg.experts, hidden)
    order = jnp.pad(order, (0, -(n * k) % rows))
    ends = jnp.cumsum(sizes)
    starts, n_held = ends - sizes, ends[-1]

    def one_pass(i, y):
        lo = i * rows
        pair = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        # this pass's part of every expert's rows; rows past the held pairs
        # (the last pass's tail) belong to no expert and are read by nobody
        part = jnp.maximum(jnp.minimum(ends, lo + rows) - jnp.maximum(starts, lo), 0)
        ys = _expert_products(xb, part, layer, cfg, rows=pair // k)
        mine = (rank >= lo) & (rank < jnp.minimum(lo + rows, n_held))
        at = jnp.clip(rank - lo, 0, rows - 1)
        if _combine_by_kernel(ys, at, mine):
            return kernels.combine(ys, at, top_w, mine, onto=y)
        for j in range(k):
            y = y + (jnp.where(mine[:, j, None], ys[at[:, j]], 0.0)
                     * top_w[:, j, None])
        return y

    return jax.lax.fori_loop(0, (n_held + rows - 1) // rows, one_pass,
                             jnp.zeros((n, hidden), jnp.float32))


def backbone_hidden(params: Params, x, pos3, cfg: BackboneConfig):
    """[B, T, in_dim] events, [3, B, T] position ids -> final-normed
    hidden states [B, T, hidden] (float32)."""
    b, t, _ = x.shape
    with jax.named_scope("head/embed"):
        # the residual stream position-major, [P, hidden] with P = B x T,
        # from here to the final norm: every product reads and writes it so
        h = _mm(x.reshape(b * t, -1), params["embed"], cfg)
        cos, sin = (a.reshape(b * t, -1) for a in mrope_angles(
            pos3, cfg.head_dim, cfg.mrope_section, cfg.rope_theta))
    for layer in params["layers"]:
        with jax.named_scope("head/attn"):
            h = h + attention(h, layer, cos, sin, cfg, t)
        flat = rms_norm(h, layer["g2"], cfg.eps)
        with jax.named_scope("head/moe/route"):
            top_e, top_w = route(flat, layer, cfg)
        with jax.named_scope("head/moe/experts"):
            h = h + grouped_experts(flat, top_e, top_w, layer, cfg)
    return rms_norm(h, params["gf"], cfg.eps).reshape(b, t, -1)


def backbone_scores(params: Params, window, lengths, cfg: BackboneConfig):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability. Text-like position ids (the
    three M-RoPE streams all equal the event's index); the score reads
    the last real position, which under causal attention no padded
    position can reach."""
    b, t, _ = window.shape
    pos3 = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, b, t))
    hid = backbone_hidden(params, window, pos3, cfg)
    return score_last(params, hid, lengths)


def score_last(params: Params, hid, lengths, logit_scale=None):
    """The scoring head on final-normed hidden states ``hid`` [B, T,
    hidden]: the sigmoid of one float32 output column at each window's
    last real position. With ``logit_scale`` (a float: the ``falconh1``
    head's ``lm_head_multiplier``) the column's product is scaled before
    the bias is added, as that model scales its output head's logits."""
    t = hid.shape[1]
    last = jnp.clip(lengths.astype(jnp.int32) - 1, 0, t - 1)
    hl = jnp.take_along_axis(hid, last[:, None, None], axis=1)[:, 0, :]
    # one output column: a float32 multiply-reduce, never the MXU
    logit = jnp.sum(hl * params["head"]["w"][:, 0], axis=-1)
    if logit_scale is not None:
        logit = logit * logit_scale
    return jax.nn.sigmoid(logit + params["head"]["b"][0])
