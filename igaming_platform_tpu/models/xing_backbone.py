"""A hyper-connected latent-attention decoder backbone over the session
window: more than one residual stream a layer (the ``xing`` session head,
models/session_heads.py).

The block is Xing4.0-29B-A4B's decoder layer at the published widths by
default: hidden 3584; multi-head latent attention of 32 heads (a query
latent of 768 and a key-value latent of 512, query-key width 128 + 64, the
64 rotary on one key head every head shares, values of 128) with YaRN on
the rotary part; a leading dense layer (SwiGLU 9,216), then layers with a
shared expert beside 64 sigmoid-routed experts of width 1,024, 4 a token,
chosen with a correction bias, every one held. Events enter as
``inputs_embeds`` through a projector (``x @ W_in``, 12 -> hidden); the
score is a sequence-classification head on the last real position.

**The residual path** is manifold-constrained hyper-connections (mHC,
arXiv 2512.24880) over ``n = streams`` streams: a position's state is ``X``
in ``R^(n x hidden)``, held as ``n`` float32 arrays ``[P, hidden]`` (``P =
B x T``; a stream is whole tiles, where ``[P, n, hidden]`` would pad four
sublanes to eight, and a pass over the streams is one fusion of ``n``
operands and ``n`` results, where a stacked ``[n, P, hidden]`` was written
a stream at a time, each time from all ``n``). The projected event is copied
into all ``n`` streams; after the last layer the streams are summed before
the final norm. Around each sublayer ``F`` (attention; the dense MLP or
shared + routed experts), with that sublayer's own ``phi`` [n x hidden, 2 n
+ n^2], ``b`` [2 n + n^2] and three scalars ``a`` (float32):

1. ``m = (vec(X) phi) (mean(vec(X)^2) + eps)^-1/2``: an RMSNorm over all
   ``n x hidden`` numbers without a gain, its division after the product;
2. ``H_pre = sigmoid(a_pre m[:n] + b[:n])``, ``H_post = 2 sigmoid(a_post
   m[n:2n] + b[n:2n])``, ``H_res = SK(clip(a_res mat(m[2n:]) + mat(b[2n:]),
   -30, 30))``: ``SK`` starts from ``exp`` and ``hc_rounds`` times divides
   each column by its sum + ``hc_eps``, then each row by its sum +
   ``hc_eps`` (``decoder_parts.hyper_maps``, ``sinkhorn``): an ``n x n``
   matrix a position and a sublayer whose rows sum to 1, and its columns
   where the rounds have converged;
3. ``u = sum_i H_pre[i] X[i]`` (``hyper_read``); ``y = F(N(u))`` with the
   layer's own input norm ``N``; ``X'[i] = sum_j H_res[i, j] X[j] +
   H_post[i] y`` (``hyper_write``).

With one stream and the three maps at 1 a sublayer is ``x + F(N(x))``, the
pre-norm block of the other backbones.

**Attention** is ``decoder_parts.latent_attention`` (which ``pangu`` and
``ling`` call too) with a query latent and rotate-half pairs, in its
expanded form every step, no latent cache. YaRN (``rope_scaling``: factor
64 over an original context of 4,096, ``beta_fast`` 32, ``beta_slow`` 1)
changes two things whatever the sequence length: the 32 rotary rates
(``decoder_parts.yarn_frequencies``: plain up to pair 10, over 64 from pair
23, a ramp between) and the softmax scale, ``192 ** -0.5`` times
``(0.1 mscale_all_dim ln 64 + 1)^2 = 2.005`` (``softmax_scale_by``). On a
TPU the core is the window kernel (ops/pallas/window_attention.py) at
``pangu``'s head widths, elsewhere the einsums.

**FF**: a SwiGLU of ``dense_width`` in the leading ``dense_layers``; else
``Shared(x) + sum over the chosen experts of w_e Expert_e(x)``:
``decoder_parts.route`` with the bias (``topk_method`` noaux_tc: the bias
chooses and does not weigh; one group, so no group limit), weights
normalised over the chosen and times ``routed_scale``;
``expert_layer.grouped_experts`` with every expert held and the ``live``
mask: a window's padding (positions past its length) is not routed, it
takes the shared expert alone, as in ``pangu`` and ``ling``.

Precision as the other backbones': parameters bfloat16 at rest (norm gains,
the expert bias, the scoring head and everything of the hyper-connections
float32); every product of a sublayer multiplies ``operand_dtype`` operands
and accumulates in float32 (``decoder_parts.mm``); the streams, every norm,
the maps (``phi``'s product on unrounded float32 operands at
``Precision.HIGHEST``), the Sinkhorn rounds, softmax, router scores, bias
and top-k and the logit are float32.

**On a TPU the residual path is two Pallas calls a sublayer**
(ops/pallas/hyper_streams.py, where its ``declines`` has nothing to say:
float32 streams of whole 128-lane vregs, whole tiles of 128 positions):
``maps_and_read`` holds a tile's ``n`` streams in VMEM and makes steps 1, 2
and ``u`` of step 3 in one pass, ``write`` makes ``X'`` and its
``stream_squares`` in another, over the streams it read: two reads and one
write of the streams a sublayer where the ``jax.numpy`` functions are four
and one. ``residual_path`` picks while tracing, from backend and shapes, and
announces ``pallas-streams (tile=128, ...)`` or ``xla (<why>; ...)``.
Elsewhere ``decoder_parts.hyper_maps``, ``hyper_read``, ``hyper_write`` and
``stream_squares`` run as they are: the CPU path, the kernels' reference,
and what one stream takes.

``jax.named_scope`` marks the parts: ``head/embed``, ``head/hc/maps`` (the
product with ``phi``, the sigmoids, the clip, ``exp`` and the rounds; on a
TPU the kernel that also makes ``u``), ``head/hc/read`` (``u`` on the
``jax.numpy`` path; on a TPU there is no such scope: the read is the maps'
pass), ``head/hc/write`` (``X'`` and its squares), ``head/attn`` (with
its norm; inside it ``q``, ``kv``, ``core``, ``out``), ``head/mlp/dense``,
``head/moe/route``, ``head/moe/shared``, ``head/moe/experts``,
``head/exit`` (the streams' sum and the final norm).
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
    announce_core,
    hyper_maps,
    hyper_read,
    hyper_write,
    kernel_declines,
    latent_attention,
    mm,
    rms_norm,
    rope_angles,
    route,
    score_last,
    stream_squares,
    swiglu,
    tree_around,
    yarn_mscale,
)
from igaming_platform_tpu.models.expert_layer import grouped_experts


@dataclass(frozen=True)
class XingConfig:
    in_dim: int = 12
    hidden: int = 3584
    layers: int = 5          # held here: ``dense_layers`` dense, the rest sparse
    dense_layers: int = 1
    heads: int = 32
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_width: int = 9216
    experts: int = 64        # every one of them held
    top_k: int = 4
    expert_width: int = 1024
    routed_scale: float = 2.0
    renorm_eps: float = 1e-20  # beside the sum the chosen scores are divided by
    rope_theta: float = 10000.0
    # ``rope_scaling`` (type yarn) key by key; a factor of 1 is no scaling
    yarn_factor: float = 64.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    # the residual path: ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps`` and
    # the two ``mhc_h_res_clamp`` bounds
    streams: int = 4
    hc_rounds: int = 20
    hc_eps: float = 1e-6
    hc_clip: tuple[float, float] = (-30.0, 30.0)
    eps: float = 1e-6
    # the depth the seeded tree is initialised for: ``wo`` and the down
    # matrices, which write into the streams, are scaled by 1 / sqrt(2 *
    # init_depth) (the published 40 layers, of which ``layers`` are held)
    init_depth: int = 40
    operand_dtype: Any = jnp.bfloat16

    @property
    def rope_scaling(self) -> dict | None:
        """The YaRN group as ``decoder_parts.rope_angles`` reads it."""
        if self.yarn_factor == 1:
            return None
        return {"factor": self.yarn_factor,
                "original_max_position_embeddings": self.yarn_original_positions,
                "beta_fast": self.yarn_beta_fast,
                "beta_slow": self.yarn_beta_slow,
                "mscale": self.yarn_mscale,
                "mscale_all_dim": self.yarn_mscale_all_dim}

    @property
    def softmax_scale_by(self) -> float:
        """What YaRN multiplies ``(nope + rope) ** -0.5`` by."""
        return yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim) ** 2


def layer_kinds(cfg: XingConfig) -> dict[str, int]:
    """How many layers of each kind the stack holds: every layer is
    ``attention``; the leading ones ``dense``, the rest ``moe``."""
    return {"attention": cfg.layers, "dense": cfg.dense_layers,
            "moe": cfg.layers - cfg.dense_layers}


def init_hyper(key, cfg: XingConfig) -> Params:
    """One sublayer's hyper-connection: ``phi`` drawn at ``(n x hidden) **
    -0.5`` (so ``m`` has unit variance), ``a`` at 1, and ``b`` so that at
    ``m = 0`` the sublayer reads the streams' mean (``H_pre = 1 / n``),
    writes its result once (``H_post = 1``) and leans each stream to
    itself (2 on the diagonal of ``H_res``'s logits)."""
    n, f32 = cfg.streams, jnp.float32
    fan_in = n * cfg.hidden
    return {
        "phi": jax.random.normal(key, (fan_in, 2 * n + n * n), f32)
        * (1.0 / math.sqrt(fan_in)),
        "b": jnp.concatenate([jnp.full((n,), -math.log(max(n - 1, 1)), f32),
                              jnp.zeros((n,), f32),
                              2.0 * jnp.eye(n, dtype=f32).reshape(-1)]),
        "a": jnp.ones((3,), f32)}


def init_backbone(key, cfg: XingConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``: a stacked weight slice by slice, a
    large matrix row block by row block). Every matrix keeps its input's
    variance (``fan_in ** -0.5``); ``wo`` and the down matrices, which write
    into the streams, are scaled by ``1 / sqrt(2 * init_depth)`` besides,
    and the routed experts' by ``1 / routed_scale`` on top: their weighted
    sum is multiplied by ``routed_scale``, so it starts at the shared
    expert's scale. The expert bias starts at zero; the hyper-connections
    as ``init_hyper``."""
    f32 = jnp.float32
    d, f = cfg.hidden, cfg.expert_width
    qk = cfg.nope_dim + cfg.rope_dim
    keys = iter(jax.random.split(key, 2 + 14 * cfg.layers))
    out = 2 * cfg.init_depth  # a fan-in 2 * init_depth times as large

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    def mlp(width, stack=(), down=1.0):
        return {"wg": matrix((*stack, d, width), d),
                "wu": matrix((*stack, d, width), d),
                "wd": matrix((*stack, width, d), width * out * down ** 2)}

    layers = []
    for i in range(cfg.layers):
        layer = {
            "g1": jnp.ones((d,), f32), "g2": jnp.ones((d,), f32),
            "hc_attn": init_hyper(next(keys), cfg),
            "hc_mlp": init_hyper(next(keys), cfg),
            "wq_a": matrix((d, cfg.q_rank), d),
            "qn": jnp.ones((cfg.q_rank,), f32),
            "wq_b": matrix((cfg.q_rank, cfg.heads * qk), cfg.q_rank),
            "wkv_a": matrix((d, cfg.kv_rank + cfg.rope_dim), d),
            "kvn": jnp.ones((cfg.kv_rank,), f32),
            "wkv_b": matrix((cfg.kv_rank, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
                            cfg.kv_rank),
            "wo": matrix((cfg.heads * cfg.v_dim, d), cfg.heads * cfg.v_dim * out),
        }
        if i < cfg.dense_layers:
            layer["dense"] = mlp(cfg.dense_width)
        else:
            layer["wr"] = matrix((d, cfg.experts), d)
            layer["rb"] = jnp.zeros((cfg.experts,), f32)
            layer["shared"] = mlp(f)
            layer["routed"] = mlp(f, (cfg.experts,), cfg.routed_scale)
        layers.append(layer)
    return tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim), next(keys), d)


def residual_path(positions: int, cfg: XingConfig) -> bool:
    """Whether the residual path of a step over ``positions`` positions runs
    as the two stream kernels (ops/pallas/hyper_streams.py): on a TPU where
    ``declines`` has nothing to say, picked while tracing from backend and
    shapes and announced once a compile, with the kernels' reason where the
    ``jax.numpy`` functions run."""
    from igaming_platform_tpu.ops.pallas import hyper_streams

    n = cfg.streams
    why, backend = kernel_declines(lambda: hyper_streams.declines(
        positions, cfg.hidden, n, jnp.float32))
    what = f"{n} streams, {cfg.hc_rounds} Sinkhorn rounds"
    announce_core(f"xla ({why}; {what})" if why else
                  f"pallas-streams (tile={hyper_streams.TILE}, {what})",
                  backend, "residual path")
    return not why


def hyper_sublayer(x, hc: Params, cfg: XingConfig, sublayer, squares=None,
                   kernels: bool = False):
    """One hyper-connected sublayer over the streams ``x`` (``n`` arrays [P,
    hidden]): the maps from the streams, what the sublayer reads,
    ``sublayer`` (its norm inside it) on that, and the streams it leaves ->
    ``(streams, their stream_squares)``: the pass that writes the streams
    also sums their squares, which the next sublayer's maps divide by
    (``squares``: the last sublayer's; the first takes its own). With
    ``kernels`` (``residual_path``) the maps and the read are one pass over
    the streams under ``head/hc/maps`` and the write with the squares
    another under ``head/hc/write``, written over the streams it read; else
    the ``jax.numpy`` functions, the read under ``head/hc/read``."""
    if kernels:
        from igaming_platform_tpu.ops.pallas import hyper_streams

        with jax.named_scope("head/hc/maps"):
            u, maps = hyper_streams.maps_and_read(x, hc, cfg, squares)
        y = sublayer(u)
        with jax.named_scope("head/hc/write"):
            return hyper_streams.write(x, maps, y)
    with jax.named_scope("head/hc/maps"):
        pre, post, res = hyper_maps(x, hc, cfg, squares)
    with jax.named_scope("head/hc/read"):
        u = hyper_read(x, pre)
    y = sublayer(u)
    with jax.named_scope("head/hc/write"):
        x = hyper_write(x, res, post, y)
        return x, stream_squares(x)


def backbone_hidden(params: Params, x, lengths, cfg: XingConfig):
    """[B, T, in_dim] events, [B] real events a window -> final-normed
    hidden states [B, T, hidden] (float32) of the streams' sum; position
    ``t`` of a window is its rotary position. A window's padding goes
    through attention, the maps and the dense and shared MLPs with the rest
    of the batch but is not routed."""
    b, t, _ = x.shape
    n = cfg.streams
    live = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(b * t)
    kernels = residual_path(b * t, cfg)
    with jax.named_scope("head/embed"):
        # the entry: the projected event copied into every stream; the
        # streams position-major, n arrays [P, hidden] with P = B x T, from
        # here to the exit sum
        h = mm(x.reshape(b * t, -1), params["embed"], cfg)
        streams, squares = (h,) * n, None
        cos, sin = rope_angles(b, t, cfg.rope_dim, cfg.rope_theta,
                               cfg.rope_scaling)

    for layer in params["layers"]:
        def attend(u, layer=layer):
            with jax.named_scope("head/attn"):
                a = rms_norm(u, layer["g1"], cfg.eps).reshape(b, t, -1)
                return latent_attention(
                    a, layer, cos, sin, cfg,
                    scale_by=cfg.softmax_scale_by).reshape(b * t, -1)

        def feed_forward(u, layer=layer):
            flat = rms_norm(u, layer["g2"], cfg.eps)
            if "dense" in layer:
                with jax.named_scope("head/mlp/dense"):
                    return swiglu(flat, layer["dense"], cfg)
            with jax.named_scope("head/moe/route"):
                top_e, top_w = route(flat, layer, cfg)
            with jax.named_scope("head/moe/shared"):
                m = swiglu(flat, layer["shared"], cfg)
            with jax.named_scope("head/moe/experts"):
                return m + grouped_experts(flat, top_e, top_w, layer["routed"],
                                           cfg, live=live)

        streams, squares = hyper_sublayer(streams, layer["hc_attn"], cfg,
                                          attend, squares, kernels)
        streams, squares = hyper_sublayer(streams, layer["hc_mlp"], cfg,
                                          feed_forward, squares, kernels)
    with jax.named_scope("head/exit"):
        h = sum(streams[1:], streams[0])
        return rms_norm(h, params["gf"], cfg.eps).reshape(b, t, -1)


def backbone_scores(params: Params, window, lengths, cfg: XingConfig):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability, read at the last real
    position, which under causal attention no padded position can reach
    (the maps, the read and the write are a position's own)."""
    lengths = lengths.astype(jnp.int32)
    return score_last(params, backbone_hidden(params, window, lengths, cfg), lengths)
