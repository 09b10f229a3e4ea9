"""A post-norm decoder backbone with a multi-token-prediction module, over
the session window: a stack that is read at two depths (the ``kexaone``
session head, models/session_heads.py).

The block is K-EXAONE-236B-A23B's decoder layer (``model_type``
``exaone_moe``: EXAONE 4.0's layer with DeepSeek-V3's expert layer and
multi-token-prediction module) at the published widths by default: hidden
6144, 64 query / 8 key-value heads of 128, a band of 128 keys on three layers
of every four (``layer_types``: sliding, sliding, sliding, full), a leading
dense SwiGLU of 18,432, then a shared expert beside 128 sigmoid-routed experts
of width 2,048 with 8 a token. Events enter as ``inputs_embeds`` through a
projector ``E`` (``x @ W_in``, 12 -> hidden). Over the residual stream ``h``
[P, hidden], position-major with ``P = B x T``:

**A layer has no pre-norm**: ``h += RMSNorm(Attn(h)); h += RMSNorm(MLP(h))``,
each sublayer reads the stream as it stands and its OUTPUT is normed.

1. ``Attn``: ``q = h Wq``, ``k = h Wk``, ``v = h Wv``, no bias; an RMSNorm a
   head on q and k; in a ``sliding_attention`` layer q and k turn by a
   rotate-half rotary table over the whole head and query ``i`` reads key
   ``j`` where ``0 <= i - j < sliding_window``; in a ``full_attention`` layer
   they do not turn at all and ``i`` reads every ``j <= i``. The band only
   clips where a window is deeper than ``sliding_window``. On a TPU, where
   ``ops/pallas/block_attention`` takes the layer, the core is that kernel for
   either kind (handed unit cos and zero sin in a full layer, which leave
   ``q`` bit for bit); elsewhere ``decoder_parts.core_by_einsums``, its
   reference. Chosen while tracing and announced once a kind.
2. ``MLP``: the leading ``dense_layers`` a SwiGLU of ``dense_width``; every
   other ``Shared(h) + sum over the chosen experts HELD HERE of w_e
   Expert_e(h)``: ``s = sigmoid(h Wr)`` over ALL ``experts`` in float32, the
   ``top_k`` largest of ``s + bias`` chosen (``rb``: DeepSeek-V3's
   ``e_score_correction_bias``, which chooses and does not weigh), ``w =
   s_chosen / sum(s_chosen) x routed_scale`` (``decoder_parts.route``).

**A chip's share** as the ``pangu`` head's: this chip holds experts
``first_expert ..`` of each layer (attention, the dense MLP, the shared expert
and the router whole); the router keeps its published width, what the absent
experts would add is left out, and a window's padding is not routed.

**The multi-token-prediction module** (DeepSeek-V3, arXiv 2412.19437, section
2.2, depth 1) follows the stack: ``u_i = [RMSNorm_e(E(x_{i+1})) ;
RMSNorm_h(f_i)] W_eh`` with ``f = RMSNorm_f(h)`` the stack's final-normed
output, then one ``full_attention`` layer as above with a sparse MLP over the
same held share, then ``RMSNorm_m`` and the SAME scoring head. The service has
no vocabulary and drafts no token; what it reads of the module is its forward
pass: a row of ``len`` real events scores ``sigmoid((z_0 + z_1) / 2)`` with
``z_0`` the head's logit on ``f_{len-1}`` and ``z_1`` its logit on the
module's output at ``len - 2``, which is made from ``f_{len-2}`` and the
newest event's embedding. Both estimate what follows the newest event. A row
of one event has no such position and scores ``sigmoid(z_0)``.

**The module narrows** (as ``phi4flash``'s layer 17): the score reads it at
one position a row, so the join and the layer's ``K, V`` run at every position
(its one query reads them) and its ``q``, core, ``Wo``, both post-norms and
the MLP at ``len - 2`` only. ``backbone_logits(..., narrowed=False)`` is the
module at every position, for the tests. The main stack stays whole: the
module's keys are made from every ``f_i``.

Precision as the other backbones': parameters bfloat16 at rest (norm gains,
the expert bias and the scoring head float32); every product multiplies
``operand_dtype`` operands and accumulates in float32; residual stream,
norms, softmax, router and the logits float32.

``jax.named_scope`` marks the parts: ``head/embed``, ``head/attn/window`` and
``head/attn/full`` with ``core`` inside, ``head/dense``, ``head/moe/{route,
shared, experts}``, and everything of the module under ``head/mtp/``
(``join``, ``attn/full`` with ``core``, ``moe/...``), ``head/score``.
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
    core_by_einsums,
    kernel_declines,
    mm,
    rms_norm,
    rope_angles,
    rotate,
    route,
    rows_at,
    swiglu,
    tree_around,
)
from igaming_platform_tpu.models.expert_layer import grouped_experts

SLIDING, FULL = "sliding_attention", "full_attention"

# A layer kind's scope under ``head/attn``.
_SCOPE = {SLIDING: "window", FULL: "full"}


@dataclass(frozen=True)
class KExaoneConfig:
    in_dim: int = 12
    hidden: int = 6144
    heads: int = 64
    kv_heads: int = 8
    head_dim: int = 128
    # one entry a layer held: the source's first five
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL, SLIDING)
    sliding_window: int = 128
    dense_layers: int = 1    # the leading ones; the rest sparse
    dense_width: int = 18432
    experts: int = 128       # the router's width: all of a layer's experts
    held_experts: int = 8    # the chip's share, experts first_expert ..
    first_expert: int = 0
    top_k: int = 8
    expert_width: int = 2048
    routed_scale: float = 2.5
    renorm_eps: float = 1e-20  # beside the sum the chosen scores are divided by
    rope_theta: float = 1e6
    # the module's one layer (``mtp_layer_types``)
    mtp_layer_type: str = FULL
    eps: float = 1e-5
    # the depth the seeded tree is initialised for: the post-norm gains start
    # at 1 / sqrt(2 * init_depth), the published 48 layers
    init_depth: int = 48
    operand_dtype: Any = jnp.bfloat16

    @property
    def layers(self) -> int:
        return len(self.layer_types)


def layer_kinds(cfg: KExaoneConfig) -> dict[str, int]:
    """How many layers of each kind the stack and the module hold: a sliding
    layer is ``window``, a full one ``attention``; the leading layers
    ``dense``, the rest ``moe``. The module counts once as ``mtp``, and its
    one layer's operators under their kinds besides."""
    kinds = (*cfg.layer_types, cfg.mtp_layer_type)
    return {"window": kinds.count(SLIDING), "attention": kinds.count(FULL),
            "dense": cfg.dense_layers,
            "moe": cfg.layers - cfg.dense_layers + 1, "mtp": 1}


def band_of(kind: str, cfg: KExaoneConfig) -> int | None:
    """The width of a layer's band: ``sliding_window`` in a sliding layer,
    none in a full one."""
    return cfg.sliding_window if kind == SLIDING else None


def key_blocks(cfg: KExaoneConfig, window: int) -> tuple[int, int]:
    """``((query, key) pairs the cores of one window's layers score, pairs
    of their squares)`` a query head (``block_attention.visited_blocks``'s
    unit): a stack layer's by its band, in the form its core runs, and of
    the module, whose one query a row may be the last but one, the one row
    of ``block_for(window)``-key blocks it meets."""
    from igaming_platform_tpu.ops.pallas.block_attention import (
        one_row,
        visited_blocks,
    )

    counts = [visited_blocks(window, band_of(t, cfg)) for t in cfg.layer_types]
    counts.append(one_row(window))
    return sum(v for v, _ in counts), sum(s for _, s in counts)


def layer_positions(cfg: KExaoneConfig, window: int) -> tuple[int, int]:
    """``(layer-positions one scored row costs, layer-positions of every
    layer at every position)``: the stack's layers run at all ``window``
    positions, the module's one layer at the one that is read (its join and
    its ``K, V`` product, a sixth of its multiply-adds, at every position
    besides)."""
    return cfg.layers * window + 1, (cfg.layers + 1) * window


# -- the seeded tree ----------------------------------------------------------


def init_backbone(key, cfg: KExaoneConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``). Every matrix keeps its input's
    variance; the two post-norm gains of a layer, which scale what a
    sublayer adds to the residual stream, start at ``1 / sqrt(2 *
    init_depth)``; the expert bias at zero."""
    f32 = jnp.float32
    d, hd, f = cfg.hidden, cfg.head_dim, cfg.expert_width
    keys = iter(jax.random.split(key, 4 + 16 * (cfg.layers + 1)))
    post = 1.0 / math.sqrt(2.0 * cfg.init_depth)

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    def mlp(width, stack=()):
        return {"wg": matrix((*stack, d, width), d),
                "wu": matrix((*stack, d, width), d),
                "wd": matrix((*stack, width, d), width)}

    def one_layer(dense: bool) -> Params:
        layer = {
            "pa": jnp.full((d,), post, f32), "pf": jnp.full((d,), post, f32),
            "wq": matrix((d, cfg.heads * hd), d),
            "wk": matrix((d, cfg.kv_heads * hd), d),
            "wv": matrix((d, cfg.kv_heads * hd), d),
            "wo": matrix((cfg.heads * hd, d), cfg.heads * hd),
            "qn": jnp.ones((hd,), f32), "kn": jnp.ones((hd,), f32),
        }
        if dense:
            layer["dense"] = mlp(cfg.dense_width)
        else:
            layer["wr"] = matrix((d, cfg.experts), d)
            layer["rb"] = jnp.zeros((cfg.experts,), f32)
            layer["shared"] = mlp(f)
            layer["routed"] = mlp(f, (cfg.held_experts,))
        return layer

    layers = [one_layer(i < cfg.dense_layers) for i in range(cfg.layers)]
    params = tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim),
                         next(keys), d)
    params["mtp"] = {
        "ge": jnp.ones((d,), f32), "gh": jnp.ones((d,), f32),
        "w_eh": matrix((2 * d, d), 2 * d),
        "layer": one_layer(False),
        "gm": jnp.ones((d,), f32)}
    return params


# -- the parts ----------------------------------------------------------------


def angle_tables(cfg: KExaoneConfig, window: int) -> dict[str, tuple]:
    """Kind -> ``(cos, sin)`` [window, head_dim / 2] float32, position = the
    event's index in its window: a sliding layer's pair ``c`` turns by
    ``rope_theta ** (-2 c / head_dim)``; a full layer turns nothing, and its
    table is unit cos and zero sin, under which ``rotate`` and the kernel
    leave every channel as it was."""
    cos, sin = rope_angles(1, window, cfg.head_dim, cfg.rope_theta)
    return {SLIDING: (cos[0], sin[0]),
            FULL: (jnp.ones_like(cos[0]), jnp.zeros_like(sin[0]))}


def _attention_core(positions: int, kind: str, cfg: KExaoneConfig, window: int):
    """What runs the core of a ``kind`` layer over ``positions`` positions in
    windows of ``window``: the Pallas kernel (ops/pallas/block_attention.py:
    on a TPU, where it takes the operands' shapes) or ``core_by_einsums``;
    either way a function of ``(q, k, v, cos, sin, gain, **widths)``. Picked
    while tracing and announced once a compile and kind, with the kernel's
    reason where it declines."""
    from igaming_platform_tpu.ops.pallas import block_attention as kernel

    nh, nkv, hd, dt = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.operand_dtype
    band = band_of(kind, cfg)
    why, backend = kernel_declines(lambda: kernel.declines(
        jax.ShapeDtypeStruct((positions, nh * hd), jnp.float32),
        jax.ShapeDtypeStruct((positions, nkv * hd), dt),
        jax.ShapeDtypeStruct((positions, nkv * hd), dt),
        heads=nh, kv_heads=nkv, window=window, band=band))
    swept = (kernel.describe(window, band, sweep=bool(why))
             + ("" if kind == SLIDING else "; no rotary: unit cos, zero sin"))
    announce_core(
        f"einsum in query blocks ({swept}; {why})" if why else
        f"pallas-blocks (grouped {nh}/{nkv} of {hd}, {swept})",
        backend, f"attention core ({_SCOPE[kind]})")
    return core_by_einsums if why else kernel.block_attention


def keys_and_values(h, layer: Params, kind: str, cos, sin, cfg: KExaoneConfig,
                    window: int):
    """A layer's ``K, V`` at every position of the stream ``h`` [P, hidden]:
    ``k`` normed a head, turned in a sliding layer, both rounded -> [P,
    kv_heads x head_dim] each."""
    p = h.shape[0]
    nkv, hd, dt = cfg.kv_heads, cfg.head_dim, cfg.operand_dtype
    k = rms_norm(mm(h, layer["wk"], cfg).reshape(p // window, window, nkv, hd),
                 layer["kn"], cfg.eps)
    if kind == SLIDING:
        k = rotate(k, cos[None], sin[None])
    return k.astype(dt).reshape(p, nkv * hd), mm(h, layer["wv"], cfg).astype(dt)


def attention(h, layer: Params, kind: str, cos, sin, cfg: KExaoneConfig,
              window: int):
    """The attention sublayer of a ``kind`` layer over the residual stream
    ``h`` [P, hidden] as it stands (no pre-norm) in windows of ``window``
    positions -> [P, hidden], before its post-norm. ``wq``'s float32 result
    goes to the core as the product left it (its head norm and rotary come
    before its one rounding, inside the core)."""
    k, v = keys_and_values(h, layer, kind, cos, sin, cfg, window)
    q = mm(h, layer["wq"], cfg)
    core = _attention_core(h.shape[0], kind, cfg, window)
    with jax.named_scope("core"):
        o = core(q, k, v, cos, sin, layer["qn"], heads=cfg.heads,
                 kv_heads=cfg.kv_heads, window=window, band=band_of(kind, cfg),
                 eps=cfg.eps)
    return mm(o, layer["wo"], cfg)


def one_query_core(q, k, v, at, gain, cfg: KExaoneConfig):
    """One query a row against its window's keys, in a layer that turns
    nothing: ``q`` [B, heads x hd] float32 as ``wq`` left it, ``k`` and ``v``
    [B, T, kv_heads x hd] ready and rounded, ``at`` [B] the query's position
    (it reads keys ``<= at``) -> [B, heads x hd] float32, which ``wo``'s
    product rounds."""
    b, t, _ = k.shape
    nh, nkv, hd, dt = cfg.heads, cfg.kv_heads, cfg.head_dim, k.dtype
    q = rms_norm(q.reshape(b, nkv, nh // nkv, hd), gain, cfg.eps).astype(dt)
    k, v = k.reshape(b, t, nkv, hd), v.reshape(b, t, nkv, hd)
    sc = jnp.einsum("bgjd,bsgd->bgjs", q, k,
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    keep = jnp.arange(t)[None, :] <= at[:, None]
    p = jax.nn.softmax(jnp.where(keep[:, None, None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bgjs,bsgd->bgjd", p.astype(dt), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, nh * hd)


def feed_forward(x, layer: Params, cfg: KExaoneConfig, live):
    """A layer's MLP over ``x`` [rows, hidden] as it stands, before its
    post-norm: the dense SwiGLU, or the shared expert plus the held experts'
    part for the rows that are ``live`` [rows] (a window's padding is not
    routed). Its scopes (``dense``, ``moe/...``) nest under the caller's:
    ``head`` in the stack, ``head/mtp`` in the module."""
    if "dense" in layer:
        with jax.named_scope("dense"):
            return swiglu(x, layer["dense"], cfg)
    with jax.named_scope("moe/route"):
        top_e, top_w = route(x, layer, cfg)
    with jax.named_scope("moe/shared"):
        m = swiglu(x, layer["shared"], cfg)
    with jax.named_scope("moe/experts"):
        return m + grouped_experts(x, top_e, top_w, layer["routed"], cfg,
                                   cfg.first_expert, live)


def _logit(params: Params, normed):
    """The scoring head on normed hidden states [B, hidden]: one float32
    output column, a multiply-reduce, never the MXU."""
    return (jnp.sum(normed * params["head"]["w"][:, 0], axis=-1)
            + params["head"]["b"][0])


# -- the stack and the module --------------------------------------------------


def backbone_hidden(params: Params, x, lengths, cfg: KExaoneConfig):
    """The stack: [B, T, in_dim] events, [B] real events a window -> ``(e,
    f)``, the events' embeddings and the final-normed hidden states, both [P,
    hidden] float32 and position-major. A window's padding goes through
    attention and the dense and shared MLPs with the rest of the batch but
    is not routed."""
    b, t, _ = x.shape
    live = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(b * t)
    with jax.named_scope("head/embed"):
        e = mm(x.reshape(b * t, -1), params["embed"], cfg)
        tables = angle_tables(cfg, t)
    h = e
    for kind, layer in zip(cfg.layer_types, params["layers"], strict=True):
        with jax.named_scope(f"head/attn/{_SCOPE[kind]}"):
            o = attention(h, layer, kind, *tables[kind], cfg, t)
            h = h + rms_norm(o, layer["pa"], cfg.eps)
        with jax.named_scope("head"):
            h = h + rms_norm(feed_forward(h, layer, cfg, live), layer["pf"],
                             cfg.eps)
    return e, rms_norm(h, params["gf"], cfg.eps)


def _join(mtp: Params, e, f, cfg: KExaoneConfig, window: int):
    """``u_i = [RMSNorm_e(e_{i+1}) ; RMSNorm_h(f_i)] W_eh`` at every position
    of every window: ``e`` and ``f`` [P, hidden] -> [P, hidden]. A window's
    last position has no next event and takes a zero embedding; nothing that
    is scored reads it."""
    d = e.shape[-1]
    nxt = jnp.pad(e.reshape(-1, window, d)[:, 1:], ((0, 0), (0, 1), (0, 0)))
    both = jnp.concatenate(
        [rms_norm(nxt.reshape(-1, d), mtp["ge"], cfg.eps),
         rms_norm(f, mtp["gh"], cfg.eps)], axis=-1)
    return mm(both, mtp["w_eh"], cfg)


def mtp_module(params: Params, e, f, lengths, cfg: KExaoneConfig, window: int,
               narrowed: bool = True):
    """The module's output where the score reads it: ``e`` and ``f`` [P,
    hidden] (``backbone_hidden``), ``lengths`` [B] -> ``RMSNorm_m`` of its
    layer's output at position ``len - 2`` of each window (position 0 in a
    window of one event, whose row reads no depth-1 logit), [B, hidden].
    ``narrowed``: the join and the layer's ``K, V`` at every position, the
    rest at that one; else the layer whole at every position, gathered after
    it."""
    mtp, kind = params["mtp"], cfg.mtp_layer_type
    if kind != FULL:
        raise ValueError(f"the module's layer is {kind!r}: its one query a row "
                         "is written for a layer that turns nothing and keeps "
                         "every causal key")
    layer = mtp["layer"]
    b = lengths.shape[0]
    at = jnp.clip(lengths - 2, 0, window - 1)
    cos, sin = angle_tables(cfg, window)[kind]
    with jax.named_scope("head/mtp"):
        with jax.named_scope("join"):
            u = _join(mtp, e, f, cfg, window)
        if narrowed:
            with jax.named_scope("attn/full"):
                k, v = keys_and_values(u, layer, kind, cos, sin, cfg, window)
                u = rows_at(u, at, window)
                q = mm(u, layer["wq"], cfg)
                with jax.named_scope("core"):
                    o = one_query_core(q, k.reshape(b, window, -1),
                                       v.reshape(b, window, -1), at,
                                       layer["qn"], cfg)
                u = u + rms_norm(mm(o, layer["wo"], cfg), layer["pa"], cfg.eps)
            live = lengths >= 2
        else:
            with jax.named_scope("attn/full"):
                o = attention(u, layer, kind, cos, sin, cfg, window)
                u = u + rms_norm(o, layer["pa"], cfg.eps)
            live = (jnp.arange(window)[None, :] + 1 < lengths[:, None]).reshape(-1)
        u = u + rms_norm(feed_forward(u, layer, cfg, live), layer["pf"], cfg.eps)
        if not narrowed:
            u = rows_at(u, at, window)
        return rms_norm(u, mtp["gm"], cfg.eps)


def backbone_logits(params: Params, window, lengths, cfg: KExaoneConfig,
                    narrowed: bool = True):
    """``(z_0, z_1)`` [B] each: the scoring head's logit at depth 0, on the
    stack's output at each window's last real position, and at depth 1, on
    the module's at the position before it (meaningless in a window of one
    event)."""
    t = window.shape[1]
    lengths = lengths.astype(jnp.int32)
    e, f = backbone_hidden(params, window, lengths, cfg)
    m = mtp_module(params, e, f, lengths, cfg, t, narrowed)
    with jax.named_scope("head/score"):
        last = jnp.clip(lengths - 1, 0, t - 1)
        return _logit(params, rows_at(f, last, t)), _logit(params, m)


def backbone_scores(params: Params, window, lengths, cfg: KExaoneConfig):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability: the sigmoid of the mean of the
    two depths' logits, of depth 0 alone in a window of one event."""
    z0, z1 = backbone_logits(params, window, lengths, cfg)
    return jax.nn.sigmoid(jnp.where(lengths >= 2, 0.5 * (z0 + z1), z0))
