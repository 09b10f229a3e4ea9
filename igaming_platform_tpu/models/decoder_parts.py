"""The parts of a decoder layer that two or more backbone modules call,
owned by no model: the backbones import from here (and from the dropless
expert layer beside it, models/expert_layer.py), never from each other. A
function one backbone alone calls stays in that backbone's module. ``cfg``
is any configuration with an ``operand_dtype`` (the routers read their own
fields off it besides).

Precision: every product multiplies ``operand_dtype`` operands and
accumulates in float32; norms, softmax, router scores, the convolution and
the logit are float32.
"""

from __future__ import annotations

import logging
import math
from functools import lru_cache, partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]

logger = logging.getLogger(__name__)

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


def tree_around(layers: list, embed, key, hidden: int, head_scale=1.0) -> Params:
    """The tree every backbone holds around its ``layers``: the projector
    ``embed`` its caller drew, the final norm's gain and the scoring head,
    one float32 column drawn at ``hidden ** -0.5`` (over ``head_scale``, the
    ``falconh1`` head's ``lm_head_multiplier``) and a zero bias."""
    f32 = jnp.float32
    return {
        "embed": embed,
        "layers": layers,
        "gf": jnp.ones((hidden,), f32),
        "head": {"w": jax.random.normal(key, (hidden, 1), f32)
                 * (1.0 / (math.sqrt(hidden) * head_scale)),
                 "b": jnp.zeros((1,), f32)},
    }


def mm(x, w, cfg):
    """``x @ w`` over the last axis of ``x``: operands in the stated
    dtype, accumulated in float32."""
    dt = cfg.operand_dtype
    return jax.lax.dot_general(
        x.astype(dt), w.astype(dt), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def mm_t(w, x, cfg):
    """``(x @ w)^T`` as the product ``w^T x^T``, [out, P] channel-major:
    the same operands and the same float32 sums as ``mm``, the result
    written positions along the lanes."""
    dt = cfg.operand_dtype
    return jax.lax.dot_general(w.astype(dt), x.astype(dt),
                               (((0,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


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


def rope_angles(b: int, t: int, dim: int, theta: float, scaling=None):
    """One rotary stream on all ``dim`` channels of ``b`` windows, position
    = the event's index in its window: (cos, sin) [B, T, dim // 2]. With
    ``scaling`` (a ``rope_scaling`` group of type ``yarn``: the ``xing``
    head's, models/xing_backbone.py) pair ``i`` turns by
    ``yarn_frequencies``' rate in the place of ``theta ** (-2 i / dim)``,
    and cos and sin are multiplied by ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)``; every other head passes none."""
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (1, b, t))
    if scaling is None:
        return mrope_angles(pos, dim, (dim // 2,), theta)
    ang = pos[0].astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_frequencies(dim, theta, scaling), jnp.float32)
    m = (yarn_mscale(scaling["factor"], scaling.get("mscale", 1.0))
         / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0.0)))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def yarn_frequencies(dim: int, theta: float, scaling) -> np.ndarray:
    """YaRN's ``dim // 2`` rotary rates (float64): pair ``i``'s plain rate
    ``f_i = theta ** (-2 i / dim)`` stays where the pair turns more than
    ``beta_fast`` times over the original context (``i <= low``), is
    divided by ``factor`` where it turns less than ``beta_slow`` times
    (``i >= high``), and between the two is ``f_i (1 - r_i) + f_i / factor
    r_i`` with ``r_i = (i - low) / (high - low)``: whatever the sequence
    length, so a 16-position window turns by them too."""
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)

    def pair_of(turns: float) -> float:
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (turns * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(pair_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - r) + plain / scaling["factor"] * r


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 where the
    context is not stretched). The softmax scale of a latent-attention
    layer is multiplied by ``yarn_mscale(factor, mscale_all_dim) ** 2``
    (``latent_attention``'s ``scale_by``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotate(x, cos, sin, interleave: bool = False):
    """Rotary embedding on the leading ``2 * cos.shape[-1]`` channels of
    ``x`` [B, T, H, D] (pair ``i`` is channels ``i`` and ``i + half``:
    the rotate-half convention); the rest pass through. With
    ``interleave`` (the ``ling`` head's latent attention:
    ``rope_interleave``) pair ``i`` is channels ``2 i`` and ``2 i + 1``,
    each turned where it lies."""
    half = cos.shape[-1]
    if interleave:
        pairs = x[..., :2 * half].reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        c, s = cos[:, :, None, :], sin[:, :, None, :]
        turned = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return jnp.concatenate([turned.reshape(*x.shape[:-1], 2 * half),
                                x[..., 2 * half:]], axis=-1)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def swiglu(x, w: Params, cfg, gate_scale=None):
    """``(silu(x Wg) * x Wu) Wd``; the product between is rounded once to
    the operands' dtype, as the expert kernels round theirs. With
    ``gate_scale`` (a float: the ``falconh1`` head's first MLP multiplier,
    models/falconh1_backbone.py) the gate is ``silu((x Wg) * gate_scale)``,
    scaled in float32 before the activation; ``pangu``'s and ``lfm2``'s
    MLPs have none."""
    gate = mm(x, w["wg"], cfg)
    if gate_scale is not None:
        gate = gate * gate_scale
    mid = jax.nn.silu(gate) * mm(x, w["wu"], cfg)
    return mm(mid, w["wd"], cfg)


def route(x, layer: Params, cfg, groups: int | None = None,
          kept_groups: int | None = None):
    """Sigmoid router over ALL experts: ``(experts [P, top_k] int32,
    weights [P, top_k] float32)``, the weights the chosen scores over their
    sum (held or not) plus ``cfg.renorm_eps``, times ``cfg.routed_scale``.
    Where the layer has an expert bias (``rb`` [experts] float32: the
    ``lfm2`` and ``ling`` heads', models/lfm2_backbone.py) it is added to
    the scores the choice reads and to nothing else: the bias chooses
    and does not weigh. The ``pangu`` head's layers have none. With
    ``groups`` and ``kept_groups`` (the ``ling`` head's ``n_group`` and
    ``topk_group``, models/ling_backbone.py) the experts lie in ``groups``
    equal runs; a group's score is the sum of its two largest biased
    scores, the ``kept_groups`` largest groups stay (equal sums: the lower
    index), and every expert outside them is masked out of what the choice
    reads, however large its score; the weights are the unbiased scores as
    without groups. ``pangu`` and ``lfm2`` pass none.

    The choice is ``cfg.top_k`` rounds of max-and-mask
    (``largest_by_rounds``) over the scores laid experts-first (``mm_t``:
    [experts, P], so a round reduces down the sublanes and never across
    lanes): what ``lax.top_k`` would give, with no sort. A chosen expert's
    unbiased score is read back as ``sum(where(iota == e, s, 0))`` over
    the experts, the score itself plus zeros: no gather and no product
    that could round it."""
    s = jax.nn.sigmoid(mm_t(layer["wr"], x, cfg))           # [experts, P]
    chosen_by = s + layer["rb"][:, None] if "rb" in layer else s
    if groups is not None:
        chosen_by = within_kept_groups(chosen_by, groups, kept_groups)
    top_s, top_e = largest_by_rounds(chosen_by, cfg.top_k)  # [top_k, P]
    if chosen_by is not s:
        top_s = scores_of(s, top_e)
    # the two small results position-major, as the expert layer reads them
    top_e, top_s = top_e.T, top_s.T
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + cfg.renorm_eps)
    return top_e, w * cfg.routed_scale


def scores_of(scores, chosen):
    """``scores`` [experts, P] at the experts ``chosen`` [k, P] -> [k, P]:
    each read back as ``sum(where(iota == e, scores, 0))`` over the experts,
    the score itself plus zeros: no gather and no product that could round
    it (what a router reads its weights by where a bias chose)."""
    expert = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    return jnp.stack([jnp.sum(jnp.where(expert == e, scores, 0.0), axis=0)
                      for e in chosen])


@partial(jax.jit, static_argnums=1)
def largest_by_rounds(scores, k: int):
    """``lax.top_k`` down the second-to-last axis of ``scores`` [..., n, P]
    by ``k`` rounds of max-and-mask: ``(values, indices)`` [..., k, P],
    largest first. A round takes the maximum of what is left, then the
    lowest index that holds it; what is left for the next round is every
    entry after that pick in ``top_k``'s order (a smaller value, or the
    same value at a higher index), so no round writes a masked copy and
    each reads ``scores`` alone. Round ``j`` therefore picks the ``j``-th
    entry of the sort by (value descending, index ascending), which is
    ``top_k``'s: equal values go to the lower index, and ``-inf`` entries
    come last in index order, since an entry not left reads ``-inf`` to
    the maximum and is kept out of the index by the same test. Jitted so
    that a step traces each (shape, ``k``) once for all its layers: the
    rounds are unrolled, and what a boot traces it pays for at every boot
    (PERF.md section 6, PR 50 and PR 51)."""
    index = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 2)
    n = scores.shape[-2]
    left = True                 # before the first round: every entry
    values, indices = [], []
    for _ in range(k):
        v = jnp.max(jnp.where(left, scores, -jnp.inf), axis=-2, keepdims=True)
        i = jnp.min(jnp.where(left & (scores == v), index, n), axis=-2,
                    keepdims=True)
        values.append(v)
        indices.append(i)
        left = (scores < v) | ((scores == v) & (index > i))
    return (jnp.concatenate(values, axis=-2),
            jnp.concatenate(indices, axis=-2))


def within_kept_groups(scores, groups: int, kept_groups: int):
    """``scores`` [experts, P] with every expert outside its position's
    ``kept_groups`` best groups at ``-inf``: the experts lie in ``groups``
    equal runs, a group's score is the sum of its two largest scores. Both
    selections are ``largest_by_rounds``: two rounds down each run, then
    ``kept_groups`` rounds down the groups' scores."""
    experts, p = scores.shape
    by_group = scores.reshape(groups, experts // groups, p)
    group_score = jnp.sum(largest_by_rounds(by_group, 2)[0], axis=-2)
    _, kept = largest_by_rounds(group_score, kept_groups)   # [kept_groups, P]
    keep = jnp.any(kept[:, None, :] == jnp.arange(groups)[None, :, None],
                   axis=0)                                  # [groups, P]
    return jnp.where(keep[:, None, :], by_group, -jnp.inf).reshape(experts, p)


def causal_taps(z, taps, bias=None):
    """The depthwise causal convolution over the positions of each window:
    ``z`` [B, T, C] float32, ``taps`` [C, L] -> ``c[b, t] = sum_k taps[:, k]
    * z[b, t - (L - 1 - k)]``, with ``z`` before a window's first position
    zero. ``L`` shifted products, no product on the MXU. With ``bias`` [C]
    (the ``falconh1`` head's convolution has one, models/
    falconh1_backbone.py) it is added at every position; the ``lfm2``
    head's has none."""
    n_taps = taps.shape[1]
    t = z.shape[1]
    c = z * taps[:, n_taps - 1]
    for back in range(1, n_taps):
        earlier = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
        c = c + earlier * taps[:, n_taps - 1 - back]
    return c if bias is None else c + bias


def attention(u, layer: Params, cos, sin, cfg, window: int, key_scale=None):
    """Grouped-query attention over normed hidden states ``u`` [P, hidden]
    -> [P, hidden]: per-head RMSNorm on q and k where the layer holds their
    gains (``qn``, ``kn``: the ``lfm2`` head's layers do, the ``falconh1``
    head's have no head norm), one rotary stream on every channel, causal.
    With ``key_scale`` (a float: the ``falconh1`` head's
    ``key_multiplier``) the keys are ``(u Wk) * key_scale``, scaled in
    float32; ``lfm2`` passes none. Its 16-key core runs as two einsums."""
    nh, nkv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    dt, t = cfg.operand_dtype, window
    b = u.shape[0] // t
    q = mm(u, layer["wq"], cfg).reshape(b, t, nh, hd)
    k = mm(u, layer["wk"], cfg).reshape(b, t, nkv, hd)
    v = mm(u, layer["wv"], cfg).reshape(b, t, nkv, hd)
    if key_scale is not None:
        k = k * key_scale
    normed = ((lambda x, gain: rms_norm(x, layer[gain], cfg.eps))
              if "qn" in layer else (lambda x, gain: x))
    q = rotate(normed(q, "qn"), cos, sin)
    k = rotate(normed(k, "kn"), cos, sin)
    # query head j reads key-value head j // (nh // nkv)
    q = q.reshape(b, t, nkv, nh // nkv, hd)
    sc = jnp.einsum("btgjd,bsgd->bgjts", q.astype(dt), k.astype(dt),
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bgjts,bsgd->btgjd", p.astype(dt), v.astype(dt),
                   preferred_element_type=jnp.float32)
    return mm(o.reshape(b * t, nh * hd), layer["wo"], cfg)


def core_by_einsums(q, k, v, cos, sin, gain, *, heads: int, kv_heads: int,
                    window: int, band: int | None, eps: float,
                    block: int | None = None):
    """The core of grouped-query attention over deep windows in query
    blocks, two einsums a block over ``[b, t, h, d]`` (the ``mellum`` and
    ``kexaone`` heads' layers): the reference of the kernel (ops/pallas/
    block_attention.block_attention, under its signature) and what runs off
    the TPU. ``q``
    [P, heads x hd] float32 as ``wq`` left it (its head norm and rotary
    happen here), ``k`` and ``v`` [P, kv_heads x hd] ready and rounded,
    ``cos`` and ``sin`` [window, hd / 2] -> float32 [P, heads x hd], which
    ``wo``'s product rounds. A block of queries meets the keys from the
    first its band keeps (the window's first in a full layer) to its own
    last, under the mask written as its two inequalities; no ``[t, s]``
    array of the whole window stands at once."""
    from igaming_platform_tpu.ops.pallas.block_attention import block_for

    dt, t = k.dtype, window
    b, hd = q.shape[0] // t, q.shape[1] // heads
    block = block or block_for(t)
    q = rotate(rms_norm(q.reshape(b, t, heads, hd), gain, eps), cos[None],
               sin[None])
    # query head j reads key-value head j // (heads // kv_heads)
    q = q.reshape(b, t, kv_heads, heads // kv_heads, hd).astype(dt)
    k, v = (x.reshape(b, t, kv_heads, hd) for x in (k, v))
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        first = 0 if band is None else max(lo - band + 1, 0)
        i = jnp.arange(lo, hi)[:, None]
        j = jnp.arange(first, hi)[None, :]
        keep = j <= i if band is None else (j <= i) & (i - j < band)
        sc = jnp.einsum("btgjd,bsgd->bgjts", q[:, lo:hi], k[:, first:hi],
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bgjts,bsgd->btgjd", p.astype(dt), v[:, first:hi],
                              preferred_element_type=jnp.float32))
    return jnp.concatenate(out, axis=1).reshape(b * t, heads * hd)


def latent_core_by_einsums(q, kvb, k_rope, cos, sin, *, heads: int, nope: int,
                           rope: int, dv: int, window: int,
                           interleave: bool = False, scale_by: float = 1.0,
                           block: int | None = None):
    """The core of latent attention as three einsums over ``[b, t, h, d]``,
    under the kernels' signature (ops/pallas/window_attention.
    window_attention at windows that fit one block, ops/pallas/
    block_attention.latent_block_attention at deeper ones): their
    reference, and what runs off the TPU. ->
    float32 [P, heads x dv], which ``Wo``'s product rounds. With
    ``interleave`` the rotary part of ``q`` turns by interleaved pairs
    (``rotate``); ``k_rope`` comes turned, by the same pairing. The scores
    are scaled by ``(nope + rope) ** -0.5`` times ``scale_by``.

    A window deeper than one block (``block``, else ``block_attention.
    block_for(window)`` positions: the ``longcat`` head's 2,048-event
    windows) runs in query blocks as ``core_by_einsums`` does: a block of
    queries meets the keys from the window's first to its own last under
    the mask written as its inequality, and no ``[t, t]`` array of the
    whole window stands at once. A window that fits one block (every other
    head's 16 keys) is the three einsums over the whole of it."""
    from igaming_platform_tpu.ops.pallas.block_attention import block_for

    dt, t = kvb.dtype, window
    b = q.shape[0] // t
    block = block or block_for(t)
    q = q.reshape(b, t, heads, nope + rope)
    q_rope = rotate(q[..., nope:], cos.reshape(b, t, -1), sin.reshape(b, t, -1),
                    interleave)
    kvb = kvb.reshape(b, t, heads, nope + dv)
    if block >= t:
        sc = (jnp.einsum("bthd,bshd->bhts", q[..., :nope].astype(dt),
                         kvb[..., :nope], preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,bsd->bhts", q_rope.astype(dt),
                           k_rope.reshape(b, t, rope),
                           preferred_element_type=jnp.float32))
        sc = sc * ((nope + rope) ** -0.5 * scale_by)
        causal = jnp.tril(jnp.ones((t, t), bool))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", p.astype(dt), kvb[..., nope:],
                       preferred_element_type=jnp.float32)
        return o.reshape(b * t, heads * dv)
    q_nope, q_rope = q[..., :nope].astype(dt), q_rope.astype(dt)
    k_rope = k_rope.reshape(b, t, rope)
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        keep = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        sc = (jnp.einsum("bthd,bshd->bhts", q_nope[:, lo:hi], kvb[:, :hi, :, :nope],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,bsd->bhts", q_rope[:, lo:hi], k_rope[:, :hi],
                           preferred_element_type=jnp.float32))
        sc = sc * ((nope + rope) ** -0.5 * scale_by)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhts,bshd->bthd", p.astype(dt),
                              kvb[:, :hi, :, nope:],
                              preferred_element_type=jnp.float32))
    return jnp.concatenate(out, axis=1).reshape(b * t, heads * dv)


def latent_attention_core(q, kvb, cfg, window: int, interleave: bool = False,
                          scale_by: float = 1.0):
    """What runs the core of latent attention over ``q`` [P, heads x (nope
    + rope)] and ``kvb`` [P, heads x (nope + v)] (arrays or shapes): a
    function of ``(q, kvb, k_rope, cos, sin)``, picked while tracing from
    the backend, the window's depth and the operands' shapes, and announced
    once a compile.

    A window that fits one block (``block_attention.block_for``: every
    head's 16 keys): the window kernel (ops/pallas/window_attention.py) on
    a TPU where its ``supports`` holds, else ``latent_core_by_einsums``.
    That kernel turns rotate-half pairs, so with ``interleave`` the einsums
    run there on every backend and the announcement says why: the pairs are
    never re-paired silently. A window deeper than one block (the
    ``longcat`` head's 2,048 events): the blocked kernel
    (ops/pallas/block_attention.latent_block_attention, which turns the
    pairing it is told) on a TPU where its ``latent_declines`` says
    nothing, else the einsums in query blocks, with the kernel's reason
    announced. ``scale_by`` multiplies the softmax scale in every core (the
    ``xing`` head's YaRN factor; 1 for the others)."""
    from igaming_platform_tpu.ops.pallas import block_attention as blocks
    from igaming_platform_tpu.ops.pallas import window_attention as kernel

    widths = dict(heads=cfg.heads, nope=cfg.nope_dim, rope=cfg.rope_dim,
                  dv=cfg.v_dim, window=window)
    scaled = dict(scale_by=scale_by)
    block = blocks.block_for(window)
    if block < window:
        why, backend = kernel_declines(
            lambda: blocks.latent_declines(q, kvb, **widths))
        pairs = "interleaved" if interleave else "rotate-half"
        announce_core(
            f"xla-einsum in query blocks of {block} (window {window}, "
            f"{cfg.heads} heads; {why})" if why else
            f"pallas-blocks (latent, {cfg.heads} heads of {cfg.nope_dim} + "
            f"{cfg.rope_dim} / {cfg.v_dim}, {pairs} rotary pairs, "
            f"{blocks.describe(window, None)})", backend, "attention core")
        return partial(latent_core_by_einsums if why else
                       blocks.latent_block_attention, **widths, **scaled,
                       interleave=interleave)
    if interleave:
        _, backend = kernel_declines()
        announce_core("xla-einsum (interleaved rotary pairs: the window "
                      "kernel turns by halves)", backend, "attention core")
        return partial(latent_core_by_einsums, **widths, **scaled,
                       interleave=True)
    why, backend = kernel_declines(lambda: not kernel.supports(q, kvb, **widths))
    announce_core("xla-einsum" if why else "pallas-windows", backend,
                  "attention core")
    return partial(latent_core_by_einsums if why else kernel.window_attention,
                   **widths, **scaled)


def latent_queries(a, layer: Params, cfg, scale: float | None = None):
    """The queries of latent attention over normed hidden states ``a`` [P,
    hidden] -> float32 [P, heads x (nope + rope)], heads of ``[q_nope |
    q_rope]`` as the product leaves them (the rotary part turns before it is
    rounded, inside the core): ``Nq(a Wq_a) Wq_b``, or ``a Wq`` in a layer
    without a query latent (no ``wq_a``). ``scale`` (the ``longcat`` head's
    ``mla_scale_q_lora``: ``sqrt(hidden / q_rank)``) multiplies both parts
    in float32."""
    if "wq_a" in layer:
        cq = rms_norm(mm(a, layer["wq_a"], cfg), layer["qn"], cfg.eps)
        q = mm(cq, layer["wq_b"], cfg)
    else:
        q = mm(a, layer["wq"], cfg)
    return q if scale is None else q * scale


def latent_keys_values(a, layer: Params, cos, sin, cfg, window: int,
                       interleave: bool = False, scale: float | None = None):
    """The keys and values of latent attention over normed hidden states
    ``a`` [P, hidden] in windows of ``window`` -> ``(k_rope [P, rope], kvb
    [P, heads x (nope + v)])``, both rounded: ``a Wkv_a`` -> ``[ckv |
    k_rope]``; the one rotary key head, shared by every query head, turned;
    ``Nkv(ckv) Wkv_b`` -> heads of ``[k_nope | v]``. ``scale`` (the
    ``longcat`` head's ``mla_scale_kv_lora``: ``sqrt(hidden / kv_rank)``)
    multiplies the normed latent in float32 before ``Wkv_b``'s product
    rounds it, so it reaches ``k_nope`` and ``v`` and not ``k_rope``."""
    p, dt = a.shape[0], cfg.operand_dtype
    kv = mm(a, layer["wkv_a"], cfg)
    ckv = rms_norm(kv[:, :cfg.kv_rank], layer["kvn"], cfg.eps)
    if scale is not None:
        ckv = ckv * scale
    k_rope = rotate(kv[:, cfg.kv_rank:].reshape(p // window, window, 1, -1),
                    cos, sin, interleave)
    k_rope = k_rope.astype(dt).reshape(p, -1)
    # heads of [k_nope | v]: rounded before any other use
    return k_rope, mm(ckv, layer["wkv_b"], cfg).astype(dt)


def latent_attention(a, layer: Params, cos, sin, cfg, interleave: bool = False,
                     scale_by: float = 1.0, q_scale: float | None = None,
                     kv_scale: float | None = None):
    """Multi-head latent attention over normed hidden states ``a`` [B, T,
    hidden], in its expanded form -> [B, T, hidden] (``pangu``: before its
    post-norm). The core (the rotary part of ``q``, scores, mask, softmax,
    ``p v``) is one Pallas kernel over the projections' results as they
    lie where ``latent_attention_core`` finds one that takes them on a TPU
    (the window kernel at windows that fit one block, the blocked kernel
    at deeper ones), else three einsums over ``[b, t, h, d]`` (in query
    blocks where the window is deeper than one): the same expanded form at
    the same precision either way. ``cfg`` gives ``heads`` (those held here: the ``longcat`` head's
    are a chip's share, whose part of ``Wo``'s product goes on as it is),
    ``kv_rank``, ``nope_dim``, ``rope_dim``, ``v_dim`` and ``eps``.

    What the ``ling`` head's layer differs by is read off the layer and one
    argument (``pangu`` has and passes none of it): without a query latent
    (no ``wq_a``) the queries are ``a Wq``; with ``interleave`` the rotary
    pairs are interleaved on both sides (the blocked kernel turns them, the
    window kernel does not: at a window that fits one block the einsums run
    then, on every backend); with a head-wise gate (``wgate``
    [hidden, heads]) each head's output is multiplied by ``sigmoid(a
    Wgate)`` of its head, in float32, before ``Wo`` rounds it. ``scale_by``
    (the ``xing`` head's: YaRN's attention factor squared) multiplies the
    softmax scale ``(nope + rope) ** -0.5``. ``q_scale`` and ``kv_scale``
    (the ``longcat`` head's two latent scales; every other head passes
    none) are ``latent_queries``' and ``latent_keys_values``'."""
    b, t, _ = a.shape
    # position-major from here to the last product: [P, channels], P = B x T
    a = a.reshape(b * t, -1)
    with jax.named_scope("q"):
        q = latent_queries(a, layer, cfg, q_scale)
    with jax.named_scope("kv"):
        k_rope, kvb = latent_keys_values(a, layer, cos, sin, cfg, t, interleave,
                                         kv_scale)
    core = latent_attention_core(q, kvb, cfg, t, interleave, scale_by)
    with jax.named_scope("core"):
        o = core(q, kvb, k_rope, cos.reshape(b * t, -1), sin.reshape(b * t, -1))
    if "wgate" in layer:
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(mm(a, layer["wgate"], cfg))  # [P, heads]
            o = (o.astype(jnp.float32).reshape(b * t, cfg.heads, -1)
                 * gate[:, :, None]).reshape(b * t, -1)
    with jax.named_scope("out"):
        return mm(o, layer["wo"], cfg).reshape(b, t, -1)


def stream_squares(xs):
    """``sum(vec(x)^2)`` a position over the streams ``xs`` (``n`` arrays [P,
    hidden]) -> [P]: what the maps' norm divides by, before its mean. Taken
    where the streams are written (``models/xing_backbone.hyper_sublayer``
    takes it under the write's scope), it costs no pass of its own."""
    return sum(jnp.sum(x * x, axis=-1) for x in xs)


def hyper_maps(xs, hc: Params, cfg, squares=None):
    """The three maps of a hyper-connected sublayer from its ``n`` streams
    ``xs`` (a sequence of [P, hidden] float32 arrays: each stream an array
    of its own, so that a pass over all of them is one fusion with ``n``
    operands and, where it writes them, ``n`` results) and the sublayer's own
    ``hc``: ``phi`` [n x hidden, 2 n + n^2], ``b`` [2 n + n^2] and ``a`` [3]
    (pre, post, res), all float32 -> ``(pre [n, P], post [n, P], res [n, n,
    P])``, positions along the lanes:

    ``m = (vec(x) phi) (mean(vec(x)^2) + cfg.eps)^-1/2``, an RMSNorm over
    all ``n x hidden`` numbers of a position without a gain, its division
    after the product, which multiplies unrounded float32 operands
    (``Precision.HIGHEST``: 2 n + n^2 columns, a stream's rows of ``phi``
    against that stream, summed over the streams); ``pre = sigmoid(a_pre
    m[:n] + b[:n])``, ``post = 2 sigmoid(a_post m[n:2n] + b[n:2n])``, ``res =
    sinkhorn(clip(a_res mat(m[2n:]) + mat(b[2n:]), cfg.hc_clip))``: ``res[i,
    j]`` is what stream ``i`` takes of stream ``j`` (``sinkhorn``). ``cfg``
    gives ``eps``, ``hc_clip`` (low, high), ``hc_rounds`` and ``hc_eps``.
    ``squares`` is ``stream_squares(xs)`` where the caller has it already
    (from the pass that wrote the streams). On a TPU this, ``sinkhorn`` and
    ``hyper_read`` are one kernel over tiles of positions
    (ops/pallas/hyper_streams.maps_and_read, held to these functions by
    tests/test_hyper_streams.py) where it takes the shapes."""
    n = len(xs)
    p, hidden = xs[0].shape
    phi = hc["phi"].reshape(n, hidden, -1)
    # [2 n + n^2, P], as ``mm_t`` lays a product: positions along the lanes
    m = sum(jax.lax.dot_general(phi[i], x, (((0,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
            for i, x in enumerate(xs))
    if squares is None:
        squares = stream_squares(xs)
    m = m * jax.lax.rsqrt(squares / (n * hidden) + cfg.eps)
    # ``a`` a row of ``m``: pre, post and res in that order
    a = jnp.repeat(hc["a"], np.array([n, n, n * n]),
                   total_repeat_length=2 * n + n * n)
    z = m * a[:, None] + hc["b"][:, None]
    pre = jax.nn.sigmoid(z[:n])
    post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    res = sinkhorn(jnp.clip(z[2 * n:], *cfg.hc_clip).reshape(n, n, p),
                   cfg.hc_rounds, cfg.hc_eps)
    return pre, post, res


def sinkhorn(z, rounds: int, eps: float):
    """``exp(z)`` [n, n, ...], then ``rounds`` times each column divided by
    its sum + ``eps`` and each row by its sum + ``eps``: towards a matrix
    whose rows and columns sum to 1 (the last division leaves the rows'
    sums there; the columns' follow where the alternation has converged,
    which 20 rounds do not reach where the logits lie at a clip's bounds).
    The sums are written out as adds of the ``n`` slices, so a round is
    elementwise over what follows the two leading axes and the rounds
    fuse. With no round it is ``exp(z)``."""
    def total(m, axis):
        parts = [jax.lax.index_in_dim(m, i, axis) for i in range(m.shape[axis])]
        return sum(parts[1:], parts[0])

    m = jnp.exp(z)
    for _ in range(rounds):
        m = m / (total(m, 0) + eps)   # a column: over the rows i
        m = m / (total(m, 1) + eps)   # a row: over the columns j
    return m


def hyper_read(xs, pre):
    """What a hyper-connected sublayer reads: ``u = sum_i pre[i] x[i]``,
    streams ``xs`` (``n`` arrays [P, hidden]) and ``pre`` [n, P] -> [P,
    hidden]. The result stands behind an ``optimization_barrier``: it is
    made once, in a pass of its own over the streams, where XLA would else
    recompute it from all ``n`` streams inside each of its consumers (the
    norm's sum of squares and the norm itself: my chip runs, PR 52). The
    barrier stays with this ``jax.numpy`` form, which still runs on a TPU
    where the stream kernels decline (ops/pallas/hyper_streams.py makes
    ``u`` in the maps' pass: a custom call's result is made once)."""
    u = sum(pre[i][:, None] * x for i, x in enumerate(xs))
    return jax.lax.optimization_barrier(u)


def hyper_write(xs, res, post, y):
    """What a hyper-connected sublayer leaves: ``x'[i] = sum_j res[i, j]
    x[j] + post[i] y`` over streams ``xs`` (``n`` arrays [P, hidden]),
    ``res`` [n, n, P], ``post`` [n, P] and the sublayer's result ``y`` [P,
    hidden] -> a tuple of ``n`` float32 arrays [P, hidden]. With one stream
    and the maps at 1 it is ``x + y``. ``y`` stands behind an
    ``optimization_barrier``, so the ``n`` results are siblings over the
    same operands (one pass over the streams for all of them) and none is
    the epilogue of the product that made ``y``: kept for the TPU steps the
    stream kernels decline, as ``hyper_read``'s."""
    y = jax.lax.optimization_barrier(y)
    return tuple(
        sum((res[i, j][:, None] * x for j, x in enumerate(xs)),
            post[i][:, None] * y) for i in range(len(xs)))


def rows_at(x, at, window: int):
    """``x`` [B x T, w] (or [B, T, w]) -> the row at position ``at`` [B] of
    each window of ``window`` positions, [B, w]: what a stack that narrows
    gathers where it goes on at one position a row."""
    x = x.reshape(-1, window, x.shape[-1])
    return jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]


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


# -- which core runs a part, and how that is said ------------------------------

# What each part last said it runs as (``/debug/sessionz``'s ``head_cores``).
_ANNOUNCED: dict[str, str] = {}


@lru_cache(maxsize=None)
def announce_core(core: str, backend: str, part: str = "expert core") -> None:
    """Log, once per (part, core, backend), which core runs a part of the
    head (``expert core``: the expert layer's grouped products, and where
    they are the kernels how those are fed; ``combine``: the results' way
    back to position order; ``attention core``: the window kernel or the
    einsums, with the kernel's reason where it declines; ``state-space
    core``: ``window kernel (tile=128, heads, state and groups, window,
    taps, gate and norm inside)`` where ops/pallas/ssd_window.py runs a
    Mamba-2 mixer between its projections, or ``dual form, one chunk, T <=
    chunk`` with the kernel's reason in brackets where XLA does;
    ``linear-attention core``: the form the recurrence is computed in; ``residual path``: the two stream kernels with their tile
    (``pallas-streams (tile=128, ...)``) or ``xla`` with the kernels' reason,
    and how many streams a layer carries and the rounds of its mixing map,
    where that is not the one stream):
    the choice is made at
    trace time and is otherwise invisible. ``announced_cores`` keeps the
    last word of each part."""
    _ANNOUNCED[part] = f"{core} (backend={backend})"
    logger.info("%s: %s (backend=%s)", part, core, backend)  # noqa: JX01 — deliberately a trace-time log: the core is chosen while tracing, once per compile


def announced_cores() -> dict[str, str]:
    """Part -> the core it last announced, for the steps traced so far in
    this process (empty before the first trace, and for a head that has
    no such part)."""
    return dict(_ANNOUNCED)


def kernel_declines(declines=None):
    """``(why, backend)`` for a part that picks its core while tracing: why
    its Pallas kernel does not run here (``not a TPU`` off one, else what
    ``declines()`` says, the kernel module's own predicate: nothing where
    it takes the shapes at hand) and the backend, for the caller to hand
    ``announce_core`` with the core it picks. A part with nothing to choose
    passes no predicate. The only read of the backend among the backbones
    and these two modules."""
    backend = jax.default_backend()
    return ("not a TPU" if backend != "tpu" else declines and declines()), backend
