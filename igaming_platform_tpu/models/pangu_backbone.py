"""A latent-attention decoder backbone over the session window, with a
chip's share of its routed experts (the ``pangu`` session head,
models/session_heads.py).

The block is openPangu-Ultra-MoE-718B's decoder layer at the published
widths by default: hidden 7680, 128 heads, a query latent of 1536 and a
key-value latent of 512, query-key width 128 + 64 (the 64 rotary, one
rotary key head shared by every head) against value width 128, sandwich
norm, a leading dense layer of width 18,432, then layers with a shared
expert and 256 routed experts of width 2,048, 8 a token. Events enter as
``inputs_embeds`` through a projector (``x @ W_in``, 12 -> hidden); the
score is a sequence-classification head on the last real position. Each
layer ``l``, over the residual stream ``h`` [B, T, hidden] (float32; ``N``
an RMSNorm):

1. ``a = N1(h)``. Query: ``cq = Nq(a Wq_a)``; ``q = cq Wq_b`` -> heads of
   ``[q_nope | q_rope]``. Key-value: ``a Wkv_a`` -> ``[ckv | k_rope]``;
   ``ckv = Nkv(ckv)``; ``ckv Wkv_b`` -> heads of ``[k_nope | v]``. Rotary
   (rotate-half) on ``q_rope`` per head and on the one ``k_rope``. Scores
   ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``, causal,
   softmax in float32, times ``v``; ``o = concat(heads) Wo``.
   ``h = h + N2(o)``: the sublayer's OUTPUT is normed before it joins the
   residual (sandwich norm).
2. ``b = N3(h)``; ``h = h + N4(MLP(b))``. For ``l < dense_layers`` the MLP
   is a SwiGLU of width ``dense_width``. Else ``s = sigmoid(b Wr)`` over
   ALL ``experts`` in float32, the ``top_k`` largest chosen, ``w =
   s_chosen / (sum s_chosen + 1e-20) * routed_scale``; ``MLP(b) =
   Shared(b) + sum over the chosen experts HELD HERE of w_e Expert_e(b)``.

**A chip's share.** The deployment divides each layer's routed experts
over ``experts / held_experts`` chips; this chip holds experts
``first_expert ..`` (attention, the dense MLP, the shared expert and the
router whole, as every chip does). The router keeps its published width
and its experts per token, the weights are normalised over all chosen
experts, held or not, and the expert layer (the one
``expert_layer.grouped_experts`` every head with experts calls) computes the held
experts' part for the pairs routed to them. What the absent experts would
add is left out, and that partial result goes on to the next layer; no
code stands in for the absent chips or their traffic. Positions past a
window's last real event are not routed (the reference leaves them out
too): nothing that is scored can read them.

**No latent cache.** Per-slot state is the ``[T, in_dim]`` event window
and the head recomputes its window every step, so the layer runs in its
expanded form (keys and values of every head from the latent, every
step); the compressed ``[ckv | k_rope]`` an account would cache in a
decoder is not held.

**Which core runs where** (``decoder_parts.latent_attention``, which the
``ling`` head's one such layer calls too). The residual stream and every
product of a layer are position-major, ``[P, channels]`` with ``P = B x T``. On a TPU,
where ``ops/pallas/window_attention.supports`` holds (``T`` divides 128,
head widths whole 64-lane halves: the published widths do), the core of
attention (the rotary part of ``q``, scores, mask, softmax, ``p v``) is
one Pallas kernel that reads ``Wq_b``'s and ``Wkv_b``'s results where the
products wrote them and writes ``Wo``'s operand where ``Wo`` reads it,
eight windows to a 128-row tile; elsewhere, and on the CPU, it is three
einsums over ``[b, t, h, d]``, which are also the kernel's reference. The
choice is made while tracing and logged once a compile (``attention core:
pallas-windows`` or ``xla-einsum``, as the expert layer logs ``expert
core`` and ``combine``). Either way it is the expanded form at the
precision stated below: no absorbed product, no cached latent, every
position of every window computed.

Precision as the ``keye`` head's: parameters bfloat16 at rest (norm gains
and the scoring head float32); every product multiplies ``operand_dtype``
operands and accumulates in float32; residual stream, norms, softmax,
router scores, top-k and the logit are float32.

``jax.named_scope`` marks the parts as the ``keye`` head's do:
``head/embed``, ``head/attn`` (inside it ``q``, ``kv``, ``core``,
``out``), ``head/mlp/dense``, ``head/moe/route``, ``head/moe/shared``,
``head/moe/experts``.
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
    latent_attention,
    mm,
    rms_norm,
    rope_angles,
    route,
    score_last,
    swiglu,
    tree_around,
)
from igaming_platform_tpu.models.expert_layer import grouped_experts


@dataclass(frozen=True)
class PanguConfig:
    in_dim: int = 12
    hidden: int = 7680
    layers: int = 5          # held here: ``dense_layers`` dense, the rest sparse
    dense_layers: int = 1
    heads: int = 128
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_width: int = 18432
    experts: int = 256       # the router's width: all of a layer's experts
    held_experts: int = 8    # the chip's share, experts first_expert ..
    first_expert: int = 0
    top_k: int = 8
    expert_width: int = 2048
    routed_scale: float = 2.5
    renorm_eps: float = 1e-20  # beside the sum the chosen scores are divided by
    rope_theta: float = 25.6e6
    eps: float = 1e-5
    # the depth the seeded tree is initialised for: the two post-norm gains
    # (N2, N4) start at 1 / sqrt(2 * init_depth), the published 61 layers
    init_depth: int = 61
    operand_dtype: Any = jnp.bfloat16


def layer_kinds(cfg: PanguConfig) -> dict[str, int]:
    """How many layers of each kind the stack holds: every layer is
    ``attention``; the leading ones ``dense``, the rest ``moe``."""
    return {"attention": cfg.layers, "dense": cfg.dense_layers,
            "moe": cfg.layers - cfg.dense_layers}


def init_backbone(key, cfg: PanguConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``: a stacked weight slice by slice, a
    large matrix row block by row block, so nothing of it exists in
    float32 beyond 64 MB). Every matrix keeps its input's variance
    (``fan_in ** -0.5``); the two post-norm gains of a layer, which scale
    what a sublayer adds to the residual stream, start at ``1 / sqrt(2 *
    init_depth)``: the depth-scaled sandwich norm."""
    f32 = jnp.float32
    d, f = cfg.hidden, cfg.expert_width
    qk = cfg.nope_dim + cfg.rope_dim
    keys = iter(jax.random.split(key, 2 + 12 * cfg.layers))
    post = 1.0 / math.sqrt(2.0 * cfg.init_depth)

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    def swiglu(width, stack=()):
        return {"wg": matrix((*stack, d, width), d),
                "wu": matrix((*stack, d, width), d),
                "wd": matrix((*stack, width, d), width)}

    layers = []
    for i in range(cfg.layers):
        layer = {
            "g1": jnp.ones((d,), f32), "g2": jnp.full((d,), post, f32),
            "g3": jnp.ones((d,), f32), "g4": jnp.full((d,), post, f32),
            "wq_a": matrix((d, cfg.q_rank), d),
            "qn": jnp.ones((cfg.q_rank,), f32),
            "wq_b": matrix((cfg.q_rank, cfg.heads * qk), cfg.q_rank),
            "wkv_a": matrix((d, cfg.kv_rank + cfg.rope_dim), d),
            "kvn": jnp.ones((cfg.kv_rank,), f32),
            "wkv_b": matrix((cfg.kv_rank, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
                            cfg.kv_rank),
            "wo": matrix((cfg.heads * cfg.v_dim, d), cfg.heads * cfg.v_dim),
        }
        if i < cfg.dense_layers:
            layer["dense"] = swiglu(cfg.dense_width)
        else:
            layer["wr"] = matrix((d, cfg.experts), d)
            layer["shared"] = swiglu(f)
            layer["routed"] = swiglu(f, (cfg.held_experts,))
        layers.append(layer)
    return tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim), next(keys), d)


def backbone_hidden(params: Params, x, lengths, cfg: PanguConfig):
    """[B, T, in_dim] events, [B] real events a window -> final-normed
    hidden states [B, T, hidden] (float32); position ``t`` of a window is
    its rotary position. A window's padding (positions past its length,
    which under causal attention no real position reads) goes through
    attention and the dense and shared MLPs with the rest of the batch
    but is not routed: it has no pair in the held experts."""
    b, t, _ = x.shape
    live = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(b * t)
    with jax.named_scope("head/embed"):
        # the residual stream position-major, [P, hidden] with P = B x T,
        # from here to the final norm: every product reads and writes it so
        h = mm(x.reshape(b * t, -1), params["embed"], cfg)
        cos, sin = rope_angles(b, t, cfg.rope_dim, cfg.rope_theta)
    for layer in params["layers"]:
        with jax.named_scope("head/attn"):
            a = rms_norm(h, layer["g1"], cfg.eps).reshape(b, t, -1)
            o = latent_attention(a, layer, cos, sin, cfg).reshape(b * t, -1)
            h = h + rms_norm(o, layer["g2"], cfg.eps)
        flat = rms_norm(h, layer["g3"], cfg.eps)
        if "dense" in layer:
            with jax.named_scope("head/mlp/dense"):
                m = swiglu(flat, layer["dense"], cfg)
        else:
            with jax.named_scope("head/moe/route"):
                top_e, top_w = route(flat, layer, cfg)
            with jax.named_scope("head/moe/shared"):
                m = swiglu(flat, layer["shared"], cfg)
            with jax.named_scope("head/moe/experts"):
                m = m + grouped_experts(flat, top_e, top_w, layer["routed"],
                                        cfg, cfg.first_expert, live)
        h = h + rms_norm(m, layer["g4"], cfg.eps)
    return rms_norm(h, params["gf"], cfg.eps).reshape(b, t, -1)


def backbone_scores(params: Params, window, lengths, cfg: PanguConfig):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability, read at the last real
    position, which under causal attention no padded position can
    reach."""
    lengths = lengths.astype(jnp.int32)
    return score_last(params, backbone_hidden(params, window, lengths, cfg), lengths)
