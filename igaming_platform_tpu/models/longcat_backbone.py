"""A double-layer latent-attention backbone with a shortcut expert branch,
over the session window, with a chip's share of its experts and of its
attention heads (the ``longcat`` session head, models/session_heads.py).

The block is LongCat-Flash-Omni's language model's decoder layer at the
published widths by default (transformers' ``LongcatFlashDecoderLayer``):
hidden 6144, latent attention with a query latent of 1536 and a key-value
latent of 512, query-key width 128 + 64 (the 64 rotary, by INTERLEAVED pairs,
one rotary key head shared by every head) against value width 128, dense
SwiGLUs of 12,288, and a router of 768 outputs over 512 experts of width 2,048
and 256 identity experts, 12 a position. Events enter as ``inputs_embeds``
through a projector (``x @ W_in``, 12 -> hidden). Over the residual stream
``h`` [P, hidden], position-major with ``P = B x T`` (float32; ``N`` an
RMSNorm):

**A layer is two layers with one expert branch across them**:

1. ``h += Attn_0(N_in0(h))``
2. ``u = N_post0(h)``; ``s = MoE(u)``, kept aside; ``h += MLP_0(u)``
3. ``h += Attn_1(N_in1(h))``
4. ``h += MLP_1(N_post1(h)) + s``

so the expert branch leaves the stream after the first attention and rejoins
it two sublayers later: nothing between reads it.

``Attn`` (``decoder_parts.latent_attention``, each of a layer with its own
weights): ``cq = Nq(a Wq_a)``; ``q = (cq Wq_b) x sqrt(hidden / q_rank)``,
heads of ``[q_nope | q_rope]``, both parts scaled; ``a Wkv_a`` -> ``[ckv |
k_rope]``; ``ckv = Nkv(ckv) x sqrt(hidden / kv_rank)``; ``ckv Wkv_b`` -> heads
of ``[k_nope | v]`` (the scale reaches ``k_nope`` and ``v``, not ``k_rope``).
Scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``, causal
inside the window, softmax in float32, times ``v``; ``Wo``. The expanded form,
every position of every window computed, no latent cached; over a window
deeper than one block the core runs in query blocks and nothing ``[t, t]`` of
a whole window stands at once: on a TPU as one Pallas kernel an attention
(ops/pallas/block_attention.latent_block_attention: a query block's scores
stay in VMEM and the interleaved pairs turn inside), elsewhere as
``decoder_parts.latent_core_by_einsums``; ``decoder_parts.
latent_attention_core`` picks from the window's depth and the shapes.

``MoE``: ``p = softmax(u Wr)`` over ALL ``experts`` outputs in float32; the
``top_k`` largest of ``p + rb`` chosen (the bias chooses and does not weigh);
weights ``w_e = routed_scale x p_e``, NOT renormalised. A chosen ``e <
real_experts`` adds ``w_e Expert_e(u)``, a SwiGLU of ``expert_width``; a
chosen ``e >= real_experts`` is an identity expert and adds ``w_e u``: no
weight, no product. So the real experts a position uses vary from none to
``top_k``.

**A chip's share.** Of experts: this chip holds experts ``first_expert ..``
of the ``real_experts`` (``expert_layer.grouped_experts``: a pair on another
chip's expert, or on an identity expert, is past every held one and is
neither gathered nor multiplied); the router keeps its published width and its
picks a position; what the absent experts would add is left out. The identity
experts hold no weight and are what every chip computes alike for its own
positions: they are computed here whole, and counted once when shares are
added up. Of attention: this chip holds ``heads`` of the published heads
(``Wq_b``'s and ``Wkv_b``'s columns and ``Wo``'s rows of those heads;
``Wq_a``, ``Wkv_a`` and the latent norms whole); what the other heads would
add to ``Wo``'s product is left out, and the held heads' part goes on as it
is. The dense MLPs, the router and the norms are whole. A window's padding is
not routed and takes no identity term.

**The last layer narrows** (as ``phi4flash``'s second half and ``kexaone``'s
module): the score reads the final norm at one position a row, and nothing
reads the last layer's stream after step 2 but ``Attn_1``'s keys and values.
So in the last layer steps 1 and 2's ``MLP_0`` run at every position, and
``N_in1``, ``Wkv_a``, ``Nkv`` and ``Wkv_b`` too; the router, the experts, the
identity term, ``Wq_a``, ``Wq_b``, the core, ``Wo``, ``N_post1`` and ``MLP_1``
at the scored position only. ``backbone_scores(..., narrowed=False)`` is every
part at every position, for the tests.

Precision as the other backbones': parameters bfloat16 at rest (norm gains,
the expert bias and the scoring head float32); every product multiplies
``operand_dtype`` operands and accumulates in float32; residual stream, norms,
softmaxes, the router's probabilities, the choice among them, the identity
term and the logit float32.

``jax.named_scope`` marks the parts: ``head/embed``; ``head/attn/0`` and
``head/attn/1`` with ``q``, ``kv``, ``core``, ``out`` inside; ``head/mlp/dense``;
everything of the branch under ``head/moe/``: ``route``, ``experts``, ``zero``
(the identity term).
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
    kernel_declines,
    largest_by_rounds,
    latent_attention,
    latent_keys_values,
    latent_queries,
    mm,
    mm_t,
    rms_norm,
    rope_angles,
    rotate,
    rows_at,
    score_last,
    scores_of,
    swiglu,
    tree_around,
)
from igaming_platform_tpu.models.expert_layer import grouped_experts


@dataclass(frozen=True)
class LongcatConfig:
    in_dim: int = 12
    hidden: int = 6144
    layers: int = 4          # double layers held here, each two attentions
    heads: int = 16          # held here: a chip's share of ``published_heads``
    published_heads: int = 64
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_width: int = 12288
    experts: int = 768       # the router's width: real and identity experts
    real_experts: int = 512  # those with weights; the rest multiply nothing
    held_experts: int = 8    # the chip's share, experts first_expert ..
    first_expert: int = 0
    top_k: int = 12
    expert_width: int = 2048
    routed_scale: float = 6.0
    rope_theta: float = 1e7
    eps: float = 1e-5
    operand_dtype: Any = jnp.bfloat16

    @property
    def q_scale(self) -> float:
        """``mla_scale_q_lora``: what both parts of ``q`` are multiplied by."""
        return math.sqrt(self.hidden / self.q_rank)

    @property
    def kv_scale(self) -> float:
        """``mla_scale_kv_lora``: what the normed key-value latent is
        multiplied by."""
        return math.sqrt(self.hidden / self.kv_rank)


def layer_kinds(cfg: LongcatConfig) -> dict[str, int]:
    """How many sublayers of each kind the stack holds: a layer of this head
    holds two ``attention`` and two ``dense`` sublayers and one ``moe``
    branch across them."""
    return {"attention": 2 * cfg.layers, "dense": 2 * cfg.layers,
            "moe": cfg.layers}


def key_blocks(cfg: LongcatConfig, window: int) -> tuple[int, int]:
    """``((query, key) pairs the cores of one window's attentions score,
    pairs of their squares)`` a head (``block_attention.visited_blocks``'s
    unit): every attention but the last in query blocks that meet the keys
    up to their own last, the last one's ONE query a row the row of blocks
    it meets."""
    from igaming_platform_tpu.ops.pallas.block_attention import (
        one_row,
        visited_blocks,
    )

    counts = [visited_blocks(window, None)] * (2 * cfg.layers - 1)
    counts.append(one_row(window))
    return sum(v for v, _ in counts), sum(s for _, s in counts)


def layer_positions(cfg: LongcatConfig, window: int) -> tuple[int, int]:
    """``(layer-positions one scored row costs, layer-positions of every
    layer at every position)``, a layer counted as its two halves: every
    half at all ``window`` positions but the last layer's second, which runs
    at the one that is read (its ``K, V`` products, a twelfth of the half's
    multiply-adds, at every position besides)."""
    halves = 2 * cfg.layers
    return (halves - 1) * window + 1, halves * window


# -- the seeded tree ----------------------------------------------------------


def init_backbone(key, cfg: LongcatConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``). Every matrix keeps its input's
    variance, the gains start at one and the expert bias at zero. The
    attention matrices are the held heads' alone."""
    f32 = jnp.float32
    d, qk = cfg.hidden, cfg.nope_dim + cfg.rope_dim
    keys = iter(jax.random.split(key, 2 + 24 * cfg.layers))

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    def mlp(width, stack=()):
        return {"wg": matrix((*stack, d, width), d),
                "wu": matrix((*stack, d, width), d),
                "wd": matrix((*stack, width, d), width)}

    def attention():
        return {
            "wq_a": matrix((d, cfg.q_rank), d),
            "qn": jnp.ones((cfg.q_rank,), f32),
            "wq_b": matrix((cfg.q_rank, cfg.heads * qk), cfg.q_rank),
            "wkv_a": matrix((d, cfg.kv_rank + cfg.rope_dim), d),
            "kvn": jnp.ones((cfg.kv_rank,), f32),
            "wkv_b": matrix((cfg.kv_rank, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
                            cfg.kv_rank),
            # fan-in: the published heads', whose sum the held heads' part is of
            "wo": matrix((cfg.heads * cfg.v_dim, d),
                         cfg.published_heads * cfg.v_dim),
        }

    def half():
        return {"g_in": jnp.ones((d,), f32), "g_post": jnp.ones((d,), f32),
                "attn": attention(), "dense": mlp(cfg.dense_width)}

    layers = [{"halves": [half(), half()],
               "wr": matrix((d, cfg.experts), d),
               "rb": jnp.zeros((cfg.experts,), f32),
               "routed": mlp(cfg.expert_width, (cfg.held_experts,))}
              for _ in range(cfg.layers)]
    return tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim), next(keys), d)


# -- the parts ----------------------------------------------------------------


def route(u, layer: Params, cfg: LongcatConfig):
    """The router over ALL ``cfg.experts`` outputs, real and identity
    experts alike: ``(experts [P, top_k] int32, weights [P, top_k]
    float32)``. ``p = softmax(u Wr)`` in float32, laid experts-first
    (``mm_t``: a round of the choice reduces down the sublanes); the
    ``top_k`` largest of ``p + rb`` chosen by ``largest_by_rounds`` (equal:
    the lower index); a chosen output's weight is its own probability, read
    back by ``scores_of``, times ``routed_scale``, and the
    weights are NOT renormalised: they sum to ``routed_scale`` times the
    chosen mass."""
    p = jax.nn.softmax(mm_t(layer["wr"], u, cfg), axis=0)     # [experts, P]
    _, top_e = largest_by_rounds(p + layer["rb"][:, None], cfg.top_k)
    top_p = scores_of(p, top_e)
    return top_e.T, top_p.T * cfg.routed_scale


def expert_branch(u, layer: Params, cfg: LongcatConfig, live):
    """``MoE(u)`` over normed positions ``u`` [rows, hidden] -> float32
    [rows, hidden]: the held experts' part of the chosen real experts
    (``grouped_experts``: a pair on an identity expert has ``e >=
    real_experts``, past every held expert, and is already not here) plus
    the identity experts' ``(sum of their weights) x u``, whole, in float32.
    Rows that are not ``live`` [rows] take neither."""
    with jax.named_scope("head/moe/route"):
        top_e, top_w = route(u, layer, cfg)
    with jax.named_scope("head/moe/experts"):
        s = grouped_experts(u, top_e, top_w, layer["routed"], cfg,
                            cfg.first_expert, live)
    with jax.named_scope("head/moe/zero"):
        zero_w = jnp.sum(jnp.where((top_e >= cfg.real_experts) & live[:, None],
                                   top_w, 0.0), axis=-1)
        return s + zero_w[:, None] * u


def _attention(h, half: Params, cos, sin, cfg: LongcatConfig, b: int, t: int):
    """``Attn(N_in(h))`` at every position: ``h`` [P, hidden] -> [P,
    hidden], the held heads' part of ``Wo``'s product."""
    a = rms_norm(h, half["g_in"], cfg.eps).reshape(b, t, -1)
    return latent_attention(a, half["attn"], cos, sin, cfg, interleave=True,
                            q_scale=cfg.q_scale,
                            kv_scale=cfg.kv_scale).reshape(b * t, -1)


def one_query_core(q, kvb, k_rope, cos, sin, at, cfg: LongcatConfig):
    """One query a row against its window's keys: ``q`` [B, heads x (nope +
    rope)] float32 as ``Wq_b`` left it, ``kvb`` [B, T, heads x (nope + v)]
    and ``k_rope`` [B, T, rope] ready and rounded, ``cos`` and ``sin`` [B,
    rope / 2] the angles at ``at`` [B], the query's position (it reads keys
    ``<= at``) -> float32 [B, heads x v], which ``Wo``'s product rounds."""
    b, t, _ = kvb.shape
    nope, rope, dv, dt = cfg.nope_dim, cfg.rope_dim, cfg.v_dim, kvb.dtype
    q = q.reshape(b, 1, cfg.heads, nope + rope)
    q_rope = rotate(q[..., nope:], cos[:, None], sin[:, None], True)[:, 0]
    kvb = kvb.reshape(b, t, cfg.heads, nope + dv)
    sc = (jnp.einsum("bhd,bshd->bhs", q[:, 0, :, :nope].astype(dt),
                     kvb[..., :nope], preferred_element_type=jnp.float32)
          + jnp.einsum("bhd,bsd->bhs", q_rope.astype(dt), k_rope,
                       preferred_element_type=jnp.float32))
    sc = sc * ((nope + rope) ** -0.5)
    keep = jnp.arange(t)[None, :] <= at[:, None]
    p = jax.nn.softmax(jnp.where(keep[:, None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", p.astype(dt), kvb[..., nope:],
                   preferred_element_type=jnp.float32)
    return o.reshape(b, cfg.heads * dv)


def _narrowed_attention(h, half: Params, cos, sin, at, cfg: LongcatConfig,
                        t: int):
    """``Attn(N_in(h))`` at position ``at`` [B] of each window alone: the
    norm and the keys and values at every position of ``h`` [P, hidden], the
    queries, the core and ``Wo`` at the one -> [B, hidden]."""
    b = at.shape[0]
    a = rms_norm(h, half["g_in"], cfg.eps)
    attn = half["attn"]
    with jax.named_scope("kv"):
        k_rope, kvb = latent_keys_values(a, attn, cos, sin, cfg, t, True,
                                         cfg.kv_scale)
    with jax.named_scope("q"):
        q = latent_queries(rows_at(a, at, t), attn, cfg, cfg.q_scale)
    _, backend = kernel_declines()
    announce_core(f"xla-einsum, one query a row (window {t}, {cfg.heads} "
                  "heads, interleaved rotary pairs)", backend,
                  "attention core (narrowed)")
    with jax.named_scope("core"):
        o = one_query_core(q, kvb.reshape(b, t, -1), k_rope.reshape(b, t, -1),
                           rows_at(cos, at, t), rows_at(sin, at, t), at, cfg)
    with jax.named_scope("out"):
        return mm(o, attn["wo"], cfg)


def _dense(x, half: Params, cfg: LongcatConfig):
    with jax.named_scope("head/mlp/dense"):
        return swiglu(x, half["dense"], cfg)


# -- the stack ----------------------------------------------------------------


def backbone_scores(params: Params, window, lengths, cfg: LongcatConfig,
                    narrowed: bool = True):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability, read at each window's last real
    position. A window's padding goes through attention and the dense MLPs
    with the rest of the batch but is not routed and takes no identity
    term. ``narrowed``: the last layer's second half, and its expert branch,
    at the scored position only; else every part at every position."""
    b, t, _ = window.shape
    lengths = lengths.astype(jnp.int32)
    live = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(b * t)
    last = jnp.clip(lengths - 1, 0, t - 1)
    with jax.named_scope("head/embed"):
        h = mm(window.reshape(b * t, -1), params["embed"], cfg)
        cos, sin = rope_angles(b, t, cfg.rope_dim, cfg.rope_theta)
    for i, layer in enumerate(params["layers"]):
        first, second = layer["halves"]
        here = narrowed and i == len(params["layers"]) - 1
        with jax.named_scope("head/attn/0"):
            h = h + _attention(h, first, cos, sin, cfg, b, t)
        u = rms_norm(h, first["g_post"], cfg.eps)
        if here:
            s = expert_branch(rows_at(u, last, t), layer, cfg, lengths >= 1)
        else:
            s = expert_branch(u, layer, cfg, live)
        h = h + _dense(u, first, cfg)
        with jax.named_scope("head/attn/1"):
            if here:
                o = _narrowed_attention(h, second, cos, sin, last, cfg, t)
                h = rows_at(h, last, t) + o
            else:
                h = h + _attention(h, second, cos, sin, cfg, b, t)
        h = h + _dense(rms_norm(h, second["g_post"], cfg.eps), second, cfg) + s
    hid = rms_norm(h, params["gf"], cfg.eps)
    if narrowed:
        return score_last(params, hid[:, None], jnp.ones_like(lengths))
    return score_last(params, hid.reshape(b, t, -1), lengths)
