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
   its ``min(topk, t + 1)`` best keys. It runs where it can drop a key:
   in windows longer than ``topk``. Where ``topk`` covers the window
   (2048 over the cells' 16 and 128 events) the best ``min(topk, t + 1)``
   of ``t + 1`` causal keys are all of them whatever the scores, the
   selection IS the causal mask, and the indexer is not traced: its three
   matrices stay in the tree at rest, unread by the step, and the core
   masks by the causal rule alone. Decided while tracing from the
   configuration's ``topk`` and the window's length, and said in the
   ``attention core`` line (``mask=keep``, or ``mask=causal`` with the
   reason). On a TPU, where
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
   computed — the layer is DROPLESS (models/expert_layer.py, which
   ``pangu`` and ``lfm2`` call too): pairs are sorted by expert and the
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

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from igaming_platform_tpu.models.decoder_parts import (
    Params,
    _matrix,
    announce_core,
    kernel_declines,
    mm,
    mm_t,
    mrope_angles,
    rms_norm,
    rotate,
    score_last,
    tree_around,
)
from igaming_platform_tpu.models.expert_layer import grouped_experts


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


def layer_kinds(cfg: BackboneConfig) -> dict[str, int]:
    """How many layers of each kind the stack holds: every layer is
    ``attention`` then ``moe``."""
    return {"attention": cfg.layers, "moe": cfg.layers}


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
    return tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim), next(keys), d)


def layer_norm(x, p, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


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
    qi = mm(a, layer["wqi"], cfg).reshape(b, t, nh, dh)
    ki = layer_norm(mm(a, layer["wki"], cfg), layer["kin"], cfg.eps)
    qi = rotate(qi, cos, sin)
    ki = rotate(ki.reshape(b, t, 1, dh), cos, sin)[:, :, 0, :]
    w = mm(a, layer["ww"], cfg).reshape(b, t, nh) * (nh ** -0.5)
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
    x hd] ready and rounded, ``keep`` [P, window] or None (the causal rule
    alone, as the kernel masks when it is handed none) -> float32 [P, heads
    x hd], which ``wo``'s product rounds."""
    dt, t = k.dtype, window
    b, hd = q.shape[0] // t, q.shape[1] // heads
    cos, sin = cos.reshape(b, t, -1), sin.reshape(b, t, -1)
    q = rotate(rms_norm(q.reshape(b, t, heads, hd), gain, eps), cos, sin)
    # query head j reads key-value head j // (heads // kv_heads)
    q = q.reshape(b, t, kv_heads, heads // kv_heads, hd)
    sc = jnp.einsum("btgjd,bsgd->bgjts", q.astype(dt),
                    k.reshape(b, t, kv_heads, hd),
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    mask = (jnp.tril(jnp.ones((t, t), bool)) if keep is None
            else keep.reshape(b, 1, 1, t, t))
    sc = jnp.where(mask, sc, -jnp.inf)
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
    kernel's reason where it declines. ``keep`` is the indexer's mask (or
    its shape), or None where ``attention`` did not trace the indexer: the
    line says which (``mask=keep``, or ``mask=causal`` and why)."""
    from igaming_platform_tpu.ops.pallas import window_attention as kernel

    nh, nkv, hd, dt = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.operand_dtype
    why, backend = kernel_declines(lambda: kernel.grouped_declines(
        jax.ShapeDtypeStruct((nh * hd, positions), jnp.float32),
        jax.ShapeDtypeStruct((positions, nkv * hd), dt),
        jax.ShapeDtypeStruct((nkv * hd, positions), dt),
        heads=nh, kv_heads=nkv, window=window, keep=keep))
    mask = ("mask=keep" if keep is not None else
            f"mask=causal; indexer not traced: topk {cfg.idx_topk} >= window {window}")
    announce_core(
        f"einsum ({why}; {mask})" if why else
        f"pallas-windows (grouped {nh}/{nkv} of {hd}, window {window}, {mask})",
        backend, "attention core")
    return not why


def attention(h, layer: Params, cos, sin, cfg: BackboneConfig, window: int):
    """The attention sublayer over the residual stream ``h`` [P, hidden] in
    windows of ``window`` positions (``cos``, ``sin`` [P, head_dim / 2]) ->
    [P, hidden]. The indexer is traced only where it can drop a key
    (``idx_topk < window``: both are static); where ``idx_topk`` covers the
    window its selection is the causal mask for every input, and the core
    is handed no mask and applies the causal rule itself, the same bits
    for less work. The core (the query's head norm and rotary, scores, the
    mask, softmax, ``p v``) is one Pallas kernel where
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
    k = rms_norm(mm(a, layer["wk"], cfg).reshape(1, p, nkv, hd), layer["kn"],
                 cfg.eps)
    k = rotate(k, cos[None], sin[None]).astype(dt).reshape(p, nkv * hd)
    keep = None
    if cfg.idx_topk < window:
        with jax.named_scope("indexer"):
            keep = indexer_keep(a, layer, cos, sin, cfg, window)
    widths = dict(heads=nh, kv_heads=nkv, window=window, eps=cfg.eps)
    if _attention_core(p, keep, cfg, window):
        # q as the product leaves it: float32, since the head norm and the
        # rotary come before the rounding
        q, v = mm_t(layer["wq"], a, cfg), mm_t(layer["wv"], a, cfg).astype(dt)
        with jax.named_scope("core"):
            o = kernel.grouped_window_attention(q, k, v, cos, sin, layer["qn"],
                                                keep, **widths)
        return jax.lax.dot_general(o, layer["wo"].astype(dt),
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    q, v = mm(a, layer["wq"], cfg), mm(a, layer["wv"], cfg).astype(dt)
    with jax.named_scope("core"):
        o = _core_by_einsums(q, k, v, cos, sin, layer["qn"], keep, **widths)
    return mm(o, layer["wo"], cfg)


def route(x, layer: Params, cfg: BackboneConfig):
    """Router over all experts: ``(experts [P, top_k] int32, weights
    [P, top_k] float32)``, the weights renormalised over the chosen."""
    p = jax.nn.softmax(mm(x, layer["wr"], cfg), axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg.top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def backbone_hidden(params: Params, x, pos3, cfg: BackboneConfig):
    """[B, T, in_dim] events, [3, B, T] position ids -> final-normed
    hidden states [B, T, hidden] (float32)."""
    b, t, _ = x.shape
    with jax.named_scope("head/embed"):
        # the residual stream position-major, [P, hidden] with P = B x T,
        # from here to the final norm: every product reads and writes it so
        h = mm(x.reshape(b * t, -1), params["embed"], cfg)
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

