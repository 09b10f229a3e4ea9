"""A short-convolution hybrid decoder backbone over the session window,
with every expert held (the ``lfm2`` session head, models/session_heads.py).

The block is LFM2-24B-A2B's decoder layer at the published widths by
default: hidden 2048; a stack whose layers are of two kinds by a list
(``layer_types``: ``conv`` or ``full_attention``), here the source's layer
0 and one whole period after the leading dense layers (``conv,
full_attention, conv, conv, conv``); gated short convolutions of 3 taps;
grouped-query attention of 32 query / 8 key-value heads of 64; one leading
dense layer (SwiGLU 11,776), then 64 sigmoid-routed experts of width
1,536, 4 a token, chosen with an expert bias. Events enter as
``inputs_embeds`` through a projector (``x @ W_in``, 12 -> hidden); the
score is a sequence-classification head on the last real position. Each
layer ``l``, over the residual stream ``h`` [P, hidden] (float32, ``P = B
x T`` position-major; ``N`` an RMSNorm with a learned gain):

1. ``r = h + Op_l(N_op(h))``.

   - ``conv``: ``[B, C, X] = split3(u W_in)``; ``z = B * X``; ``c_t = sum_k
     w_k z_{t - (L - 1 - k)}`` per channel over the ``L = conv_taps``
     events up to ``t`` of the same window (``z`` before the window's first
     event is zero: a depthwise causal convolution); ``Op = (C * c) W_out``.
     No bias anywhere.
   - ``full_attention``: ``q = u Wq`` as ``heads`` of ``head_dim``, ``k = u
     Wk``, ``v = u Wv`` as ``kv_heads``; RMSNorm over each head of ``q``
     and ``k``; rotate-half rotary on every channel, position = the event's
     index; causal softmax of ``q k^T / sqrt(head_dim)``, ``heads /
     kv_heads`` query heads to a key-value head; ``Op = concat(heads) Wo``
     (``decoder_parts.attention``, which ``falconh1`` calls too).

2. ``h' = r + FF_l(N_ffn(r))``. For ``l < dense_layers`` a SwiGLU of
   ``dense_width`` (``decoder_parts.swiglu``). Else the one sigmoid router
   both ``pangu`` and this head call (``decoder_parts.route``):
   ``s = sigmoid(u Wr)``; ``top_k(s + b)`` CHOOSES, with ``b`` the expert
   bias, and ``s`` of the chosen WEIGHS: ``w = s_sel / (sum s_sel +
   renorm_eps) * routed_scale``; every (position, expert) pair goes through
   the dropless expert layer (``expert_layer.grouped_experts``, every
   expert held: one pass, on a TPU the three Pallas kernels of
   ops/pallas/grouped_experts.py).

After the last layer one more RMSNorm. A window's padding (positions past
its length) is computed with the rest of the batch and routed like any
position, as in the ``keye`` head: the convolution and the mask are
causal, so nothing that is scored can read it.

**No convolution cache.** Per-slot state is the ``[T, in_dim]`` event
window and the head recomputes its window every step; the ``conv_taps``
columns of ``z`` an account would cache in a decoder are not held.

Precision as the other backbones': parameters bfloat16 at rest (norm
gains, the convolution's taps, the router's matrix and bias and the
scoring head float32); every product multiplies ``operand_dtype`` operands
and accumulates in float32 (the router's too: its matrix is rounded where
it is multiplied); residual stream, norms, the gate and the taps of the
convolution, softmax, router scores, bias, top-k and the logit float32.

``jax.named_scope`` marks the parts: ``head/embed``, ``head/conv`` (inside
it ``in``, ``gate``, ``taps``, ``out``), ``head/attn``, ``head/mlp/dense``,
``head/moe/route``, ``head/moe/experts``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from igaming_platform_tpu.models.decoder_parts import (
    Params,
    _matrix,
    attention,
    causal_taps,
    mm,
    rms_norm,
    rope_angles,
    route,
    score_last,
    swiglu,
    tree_around,
)
from igaming_platform_tpu.models.expert_layer import grouped_experts

CONV, ATTENTION = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2Config:
    in_dim: int = 12
    hidden: int = 2048
    # one entry a layer held here, read and not assumed uniform
    layer_types: tuple[str, ...] = (CONV, ATTENTION, CONV, CONV, CONV)
    dense_layers: int = 1    # of them the leading ones with a dense MLP
    conv_taps: int = 3
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    dense_width: int = 11776
    experts: int = 64        # every one of them held
    top_k: int = 4
    expert_width: int = 1536
    routed_scale: float = 1.0
    renorm_eps: float = 1e-6  # beside the sum the chosen scores are divided by
    rope_theta: float = 1e6
    eps: float = 1e-5
    # the depth the seeded tree is initialised for: the projections that
    # write into the residual stream are scaled by 1 / sqrt(2 * init_depth)
    # (the published 40 layers, of which ``len(layer_types)`` are held)
    init_depth: int = 40
    operand_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types names {sorted(unknown)}; a layer is "
                             f"{CONV!r} or {ATTENTION!r}")


def layer_kinds(cfg: Lfm2Config) -> dict[str, int]:
    """How many layers of each kind the stack holds: by operator (``conv``,
    ``attention``) and by feed-forward (``dense``, ``moe``)."""
    return {"conv": cfg.layer_types.count(CONV),
            "attention": cfg.layer_types.count(ATTENTION),
            "dense": cfg.dense_layers,
            "moe": len(cfg.layer_types) - cfg.dense_layers}


def init_backbone(key, cfg: Lfm2Config) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``: a stacked weight slice by slice, a
    large matrix row block by row block). Every matrix keeps its input's
    variance (``fan_in ** -0.5``; the three taps of a channel ``3 **
    -0.5``); ``w_out``, ``wo`` and the down matrices, which write into the
    residual stream, are scaled by ``1 / sqrt(2 * init_depth)`` besides.
    The router's matrix is drawn like the others and held in float32; its
    bias starts at zero."""
    f32 = jnp.float32
    d, hd, f = cfg.hidden, cfg.head_dim, cfg.expert_width
    keys = iter(jax.random.split(key, 2 + 8 * len(cfg.layer_types)))
    out = 2 * cfg.init_depth  # a fan-in 2 * init_depth times as large

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    def mlp(width, stack=()):
        return {"wg": matrix((*stack, d, width), d),
                "wu": matrix((*stack, d, width), d),
                "wd": matrix((*stack, width, d), width * out)}

    layers = []
    for i, kind in enumerate(cfg.layer_types):
        layer = {"g1": jnp.ones((d,), f32), "g2": jnp.ones((d,), f32)}
        if kind == CONV:
            layer["w_in"] = matrix((d, 3 * d), d)
            layer["taps"] = (jax.random.normal(next(keys), (d, cfg.conv_taps), f32)
                             * (1.0 / math.sqrt(cfg.conv_taps)))
            layer["w_out"] = matrix((d, d), d * out)
        else:
            layer["wq"] = matrix((d, cfg.heads * hd), d)
            layer["wk"] = matrix((d, cfg.kv_heads * hd), d)
            layer["wv"] = matrix((d, cfg.kv_heads * hd), d)
            layer["wo"] = matrix((cfg.heads * hd, d), cfg.heads * hd * out)
            layer["qn"] = jnp.ones((hd,), f32)
            layer["kn"] = jnp.ones((hd,), f32)
        if i < cfg.dense_layers:
            layer["dense"] = mlp(cfg.dense_width)
        else:
            layer["wr"] = matrix((d, cfg.experts), d).astype(f32)
            layer["rb"] = jnp.zeros((cfg.experts,), f32)
            layer["routed"] = mlp(f, (cfg.experts,))
        layers.append(layer)
    return tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim), next(keys), d)


def short_conv(u, layer: Params, cfg: Lfm2Config, window: int):
    """The gated short convolution over normed hidden states ``u`` [P,
    hidden] -> [P, hidden]: ``(C * conv(B * X)) W_out`` with ``[B, C, X]``
    the three thirds of ``u W_in`` in that order."""
    d = cfg.hidden
    with jax.named_scope("in"):
        bcx = mm(u, layer["w_in"], cfg)
    with jax.named_scope("gate"):
        z = bcx[:, :d] * bcx[:, 2 * d:]
    with jax.named_scope("taps"):
        c = causal_taps(z.reshape(-1, window, d), layer["taps"]).reshape(-1, d)
        y = bcx[:, d:2 * d] * c
    with jax.named_scope("out"):
        return mm(y, layer["w_out"], cfg)


def backbone_hidden(params: Params, x, cfg: Lfm2Config):
    """[B, T, in_dim] events -> final-normed hidden states [B, T, hidden]
    (float32); position ``t`` of a window is its rotary position. Every
    position of every window goes through every layer, the experts too."""
    b, t, _ = x.shape
    with jax.named_scope("head/embed"):
        # the residual stream position-major, [P, hidden] with P = B x T
        h = mm(x.reshape(b * t, -1), params["embed"], cfg)
        cos, sin = rope_angles(b, t, cfg.head_dim, cfg.rope_theta)
    for kind, layer in zip(cfg.layer_types, params["layers"], strict=True):
        with jax.named_scope("head/conv" if kind == CONV else "head/attn"):
            u = rms_norm(h, layer["g1"], cfg.eps)
            h = h + (short_conv(u, layer, cfg, t) if kind == CONV
                     else attention(u, layer, cos, sin, cfg, t))
        flat = rms_norm(h, layer["g2"], cfg.eps)
        if "dense" in layer:
            with jax.named_scope("head/mlp/dense"):
                h = h + swiglu(flat, layer["dense"], cfg)
        else:
            with jax.named_scope("head/moe/route"):
                top_e, top_w = route(flat, layer, cfg)
            with jax.named_scope("head/moe/experts"):
                h = h + grouped_experts(flat, top_e, top_w, layer["routed"], cfg)
    return rms_norm(h, params["gf"], cfg.eps).reshape(b, t, -1)


def backbone_scores(params: Params, window, lengths, cfg: Lfm2Config):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability, read at the last real
    position, which under a causal convolution and causal attention no
    padded position can reach."""
    return score_last(params, backbone_hidden(params, window, cfg), lengths)
