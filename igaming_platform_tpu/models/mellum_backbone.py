"""A decoder backbone with windowed and full attention in one stack, over
the session window (the ``mellum`` session head, models/session_heads.py).

The block is Mellum2-12B-A2.5B-Instruct's decoder layer at the published
widths by default: hidden 2304, 32 query / 4 key-value heads of 128, 64
experts of width 896 with 8 a token and no shared expert. What kind of
attention a layer runs is read from ``layer_types``, one entry a layer:
``sliding_attention`` (query ``i`` reads key ``j`` where ``0 <= i - j <
sliding_window``, 1,024) or ``full_attention`` (every ``j <= i``); the four
layers held are the source's first period, three sliding and one full. Each
kind has a rotary table of its own: the sliding layers turn by the plain
rates ``theta ** (-2 c / 128)``, the full ones by YaRN's blend of those
rates (``decoder_parts.yarn_frequencies``) with cos and sin both multiplied
by the configuration's ``attention_factor``. Events enter as
``inputs_embeds`` from a projector (``x @ W_in``, 12 -> hidden); the score
is a sequence-classification head on the last real position. Each layer,
over the residual stream ``h`` [P, hidden], position-major with ``P = B x
T``:

1. ``a = RMSNorm(h)``; grouped-query attention with per-head RMSNorm on q
   and k and the rotary of the layer's kind; causal, and banded in a
   sliding layer. The band only clips where a window is deeper than
   ``sliding_window``: at 1,024 events or fewer both kinds keep every
   causal key. On a TPU, where ``ops/pallas/block_attention`` takes the
   layer (heads of whole 128-lane vregs: the published widths do), the core
   (q's head norm and rotary, scores, mask, an online softmax, ``p v``) is
   one Pallas kernel for either kind, which visits only the key blocks the
   layer's mask keeps; elsewhere ``core_by_einsums``, the same sweep in
   query blocks as two einsums a block, which is its reference and what the
   CPU tests and replay run. Chosen while tracing and announced once a kind
   (``attention core``, with the kernel's reason where it declines).
2. ``b = RMSNorm(h)``; a router over all experts, softmax in float32, the
   ``top_k`` largest renormalised (``norm_topk_prob``); every (position,
   expert) pair is computed by the dropless expert layer
   (models/expert_layer.py).

Precision: parameters bfloat16 at rest (norm gains and the scoring head
float32); every product multiplies ``operand_dtype`` operands and
accumulates in float32; residual stream, norms, softmax, router and the
logit are float32.

``jax.named_scope`` marks the parts (``head/embed``, ``head/attn/window``
and ``head/attn/full`` by the layer's kind with ``core`` inside each,
``head/moe/route``, ``head/moe/experts``) so that a device trace can be read
by part.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from igaming_platform_tpu.models.decoder_parts import (
    Params,
    _matrix,
    announce_core,
    core_by_einsums,
    kernel_declines,
    largest_by_rounds,
    mm,
    mm_t,
    rms_norm,
    rotate,
    score_last,
    tree_around,
    yarn_frequencies,
)
from igaming_platform_tpu.models.expert_layer import grouped_experts

SLIDING, FULL = "sliding_attention", "full_attention"

# A layer kind's scope under ``head/attn``.
_SCOPE = {SLIDING: "window", FULL: "full"}


@dataclass(frozen=True)
class MellumConfig:
    in_dim: int = 12
    hidden: int = 2304
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    experts: int = 64
    top_k: int = 8
    expert_width: int = 896
    # one entry a layer held: the source's first period
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 1024
    rope_theta: float = 5e5
    # the full layers' table (``rope_parameters.full_attention``): YaRN
    yarn_factor: float = 16.0
    yarn_original: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782
    eps: float = 1e-6
    # the depth the seeded tree is initialised for (the published 28 layers,
    # of which ``len(layer_types)`` are held): ``keye_backbone.init_backbone``
    init_depth: int = 28
    operand_dtype: Any = jnp.bfloat16

    @property
    def layers(self) -> int:
        return len(self.layer_types)


def layer_kinds(cfg: MellumConfig) -> dict[str, int]:
    """How many layers of each kind the stack holds: a sliding layer is
    ``window``, a full one ``attention``, every layer ``moe``."""
    return {"window": cfg.layer_types.count(SLIDING),
            "attention": cfg.layer_types.count(FULL), "moe": cfg.layers}


def band_of(kind: str, cfg: MellumConfig) -> int | None:
    """The width of a layer's band: ``sliding_window`` in a sliding layer,
    none in a full one."""
    return cfg.sliding_window if kind == SLIDING else None


def key_blocks(cfg: MellumConfig, window: int) -> tuple[int, int]:
    """``((query, key) pairs the cores of one window's layers score, pairs
    of their squares)`` a query head, the area of the key blocks each sweep
    visits (``block_attention.visited_blocks``'s unit): what the server's
    ``risk_session_head_key_blocks_*_total`` count a scored row."""
    from igaming_platform_tpu.ops.pallas.block_attention import visited_blocks

    counts = [visited_blocks(window, band_of(t, cfg)) for t in cfg.layer_types]
    return sum(v for v, _ in counts), sum(s for _, s in counts)


def init_backbone(key, cfg: MellumConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``keye_backbone.init_backbone``'s rule: every matrix keeps its
    input's variance, ``wo`` and ``wd`` are scaled by ``1 / sqrt(2 *
    init_depth)`` besides)."""
    f32 = jnp.float32
    d, hd, f = cfg.hidden, cfg.head_dim, cfg.expert_width
    keys = iter(jax.random.split(key, 2 + 8 * cfg.layers))
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
            "wr": matrix((d, cfg.experts), d),
            "wg": matrix((cfg.experts, d, f), d),
            "wu": matrix((cfg.experts, d, f), d),
            "wd": matrix((cfg.experts, f, d), f * out),
        })
    return tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim), next(keys), d)


def angle_tables(cfg: MellumConfig, window: int) -> dict[str, tuple]:
    """The rotary table of each kind of layer the stack holds: kind ->
    ``(cos, sin)`` [window, head_dim / 2] float32, position = the event's
    index in its window. A sliding layer's pair ``c`` turns by ``rope_theta
    ** (-2 c / head_dim)``; a full one's by YaRN's rate, and its cos and sin
    are both multiplied by ``attention_factor`` (so a score is scaled by its
    square), as the transformers library's YaRN initialisation applies it."""
    pos = jnp.arange(window, dtype=jnp.float32)[:, None]
    half = cfg.head_dim // 2
    tables = {}
    for kind in dict.fromkeys(cfg.layer_types):
        if kind == FULL:
            rates = yarn_frequencies(cfg.head_dim, cfg.rope_theta, {
                "factor": cfg.yarn_factor, "beta_fast": cfg.yarn_beta_fast,
                "beta_slow": cfg.yarn_beta_slow,
                "original_max_position_embeddings": cfg.yarn_original})
            scale = cfg.attention_factor
        else:
            rates = cfg.rope_theta ** (-np.arange(half) * 2.0 / cfg.head_dim)
            scale = 1.0
        ang = pos * jnp.asarray(rates, jnp.float32)
        tables[kind] = (jnp.cos(ang) * scale, jnp.sin(ang) * scale)
    return tables


def _attention_core(positions: int, kind: str, cfg: MellumConfig, window: int):
    """What runs the core of a ``kind`` layer over ``positions`` positions
    in windows of ``window``: the Pallas kernel (ops/pallas/
    block_attention.py: on a TPU, where it takes the operands' shapes) or
    ``core_by_einsums``; either way a function of ``(q, k, v, cos, sin,
    gain, **widths)``. Picked while tracing, from backend and shapes, and
    announced once a compile and kind, with the kernel's reason where it
    declines."""
    from igaming_platform_tpu.ops.pallas import block_attention as kernel

    nh, nkv, hd, dt = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.operand_dtype
    band = band_of(kind, cfg)
    why, backend = kernel_declines(lambda: kernel.declines(
        jax.ShapeDtypeStruct((positions, nh * hd), jnp.float32),
        jax.ShapeDtypeStruct((positions, nkv * hd), dt),
        jax.ShapeDtypeStruct((positions, nkv * hd), dt),
        heads=nh, kv_heads=nkv, window=window, band=band))
    swept = kernel.describe(window, band, sweep=bool(why))
    announce_core(
        f"einsum in query blocks ({swept}; {why})" if why else
        f"pallas-blocks (grouped {nh}/{nkv} of {hd}, {swept})",
        backend, f"attention core ({_SCOPE[kind]})")
    return core_by_einsums if why else kernel.block_attention


def attention(h, layer: Params, kind: str, cos, sin, cfg: MellumConfig,
              window: int):
    """The attention sublayer of a ``kind`` layer over the residual stream
    ``h`` [P, hidden] in windows of ``window`` positions (``cos``, ``sin``
    [window, head_dim / 2], the kind's table) -> [P, hidden]. ``wq``'s
    float32 result goes to the core as the product left it (its head norm
    and rotary come before its one rounding, inside the core); ``k`` is
    normed, turned and rounded here."""
    p = h.shape[0]
    nh, nkv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.operand_dtype
    a = rms_norm(h, layer["g1"], cfg.eps)
    k = rms_norm(mm(a, layer["wk"], cfg).reshape(p // window, window, nkv, hd),
                 layer["kn"], cfg.eps)
    k = rotate(k, cos[None], sin[None]).astype(dt).reshape(p, nkv * hd)
    q, v = mm(a, layer["wq"], cfg), mm(a, layer["wv"], cfg).astype(dt)
    core = _attention_core(p, kind, cfg, window)
    with jax.named_scope("core"):
        o = core(q, k, v, cos, sin, layer["qn"], heads=nh, kv_heads=nkv,
                 window=window, band=band_of(kind, cfg), eps=cfg.eps)
    return mm(o, layer["wo"], cfg)


def route(x, layer: Params, cfg: MellumConfig):
    """Router over all experts: ``(experts [P, top_k] int32, weights [P,
    top_k] float32)``, the softmax's ``top_k`` largest probabilities over
    their sum (``norm_topk_prob``). The probabilities lie experts-first
    ([experts, P], ``mm_t``) and the choice is ``top_k`` rounds of
    max-and-mask (``decoder_parts.largest_by_rounds``): ``lax.top_k``'s
    picks with no sort."""
    p = jax.nn.softmax(mm_t(layer["wr"], x, cfg), axis=0)
    top_p, top_e = largest_by_rounds(p, cfg.top_k)             # [top_k, P]
    top_p, top_e = top_p.T, top_e.T
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def backbone_hidden(params: Params, x, cfg: MellumConfig):
    """[B, T, in_dim] events -> final-normed hidden states [B, T, hidden]
    (float32)."""
    b, t, _ = x.shape
    with jax.named_scope("head/embed"):
        # the residual stream position-major, [P, hidden] with P = B x T,
        # from here to the final norm
        h = mm(x.reshape(b * t, -1), params["embed"], cfg)
        tables = angle_tables(cfg, t)
    for kind, layer in zip(cfg.layer_types, params["layers"], strict=True):
        with jax.named_scope(f"head/attn/{_SCOPE[kind]}"):
            h = h + attention(h, layer, kind, *tables[kind], cfg, t)
        flat = rms_norm(h, layer["g2"], cfg.eps)
        with jax.named_scope("head/moe/route"):
            top_e, top_w = route(flat, layer, cfg)
        with jax.named_scope("head/moe/experts"):
            h = h + grouped_experts(flat, top_e, top_w, layer, cfg)
    return rms_norm(h, params["gf"], cfg.eps).reshape(b, t, -1)


def backbone_scores(params: Params, window, lengths, cfg: MellumConfig):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability. The score reads the last real
    position, which under causal attention no padded position can reach."""
    hid = backbone_hidden(params, window, cfg)
    return score_last(params, hid, lengths)
