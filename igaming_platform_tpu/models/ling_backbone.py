"""A delta-rule linear-attention hybrid decoder backbone over the session
window, with a chip's share of its group-routed experts (the ``ling``
session head, models/session_heads.py).

The block is Ling-3.0-flash's decoder layer at the published widths by
default: hidden 2560; a stack whose layers are of two kinds BY RULE
(``layer_group_size`` 6: source layer ``l`` is latent attention where ``(l
+ 1) % 6 == 0``, else Kimi Delta Attention, a gated delta-rule linear
attention), here the source's layer 1 (a leading dense layer, which counts
once) and the whole period 2-7 after it (KDA, KDA, KDA, MLA, KDA, KDA); a
dense SwiGLU of 6,144 in the leading layers, then a shared expert (768)
beside 512 sigmoid-routed experts of width 768, 8 a token, chosen with an
expert bias among the 4 best of 8 groups, 64 of them held here. Events
enter as ``inputs_embeds`` through a projector (``x @ W_in``, 12 ->
hidden); the score is a sequence-classification head on the last real
position. Each layer, over the residual stream ``h`` [P, hidden] (float32,
``P = B x T`` position-major; ``N`` an RMSNorm with a learned gain), is
pre-norm: ``r = h + Mixer(N1(h))``, ``h' = r + FF(N2(r))``.

**KDA mixer** on ``u = N1(h)``, ``heads`` of ``head_dim`` keys and values:

1. ``q, k, v = silu(taps(u Wq)), silu(taps(u Wk)), silu(taps(u Wv))``: the
   taps a depthwise causal convolution of ``conv_taps`` (``decoder_parts.
   causal_taps``: zero before the window's first event, no bias); ``q`` and
   ``k`` L2-normalised a head, ``q`` times ``head_dim ** -0.5``; no rotary.
2. The decay a channel: ``g = gate_lower_bound * sigmoid(exp(A_log_head) *
   (u Wf + dt_bias))``, so ``gate_lower_bound < g < 0``; ``beta =
   sigmoid(u Wb)`` a head.
3. The state a head, ``[head_dim, head_dim]`` from zero: ``S~ =
   Diag(exp(g_t)) S_{t-1}``; ``S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T``;
   ``o_t = S_t^T q_t``: every position first READS the decayed state
   through its key and writes the difference. Computed in its ONE-CHUNK
   form (``kda_one_chunk``): the state is never formed; a window is one
   chunk, and one past ``window_limit`` positions is refused.
4. ``y = N_head(o) * sigmoid(u Wg)``: an RMSNorm over each head's channels
   (one gain of ``head_dim`` shared by the heads), gated a channel;
   ``Mixer = y Wo``.

Steps 1 to 3 and the head norm of step 4 are ONE Pallas call a layer on a
TPU (ops/pallas/delta_window.py, since PR 50) over the projections' results
where the products wrote them, ``[P, heads x head_dim]``, wherever the
kernel takes the shapes (``_core_is_the_kernel``: heads of whole 128-lane
vregs, a window that divides 128 in whole 8-row vregs, whole tiles of 128
positions, VMEM); off the TPU and where it declines they are XLA's over
``[b, t, h, d]`` (``_core_by_xla``), ``kda_one_chunk`` the core and the
reference the kernel is held to. Which one a step runs is said once a
compile (``linear-attention core``: ``pallas-windows (32 heads of 128,
window 16, prologue=taps, norm=inside)`` or ``one chunk by einsums (<why>)``).

**MLA mixer** (``decoder_parts.latent_attention``, which ``pangu`` calls
too): no query latent, a key-value latent of 512, one rotary key head of 64
turned by INTERLEAVED pairs, 32 heads of 128 + 64 against values of 128, a
head-wise output gate; expanded every step, no latent cache. The window
kernel turns rotate-half pairs, so this layer's core is the einsums on
every backend and ``attention core`` says so.

**FF**: a SwiGLU of ``dense_width`` where the SOURCE's layer index is under
``source_dense_layers``; else ``Shared(x) + sum over the chosen experts
HELD HERE of w_e Expert_e(x)``: ``decoder_parts.route`` with ``groups`` and
``kept_groups`` (the bias chooses, inside the kept groups, and does not
weigh), ``expert_layer.grouped_experts`` as a share (``first_expert``,
``live``: a window's padding is not routed, as in ``pangu``). What the
absent experts would add is left out and nothing stands in for their chips.
``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` are read
by the source's layer index and a held layer whose entry is not 0 is
refused: no key says what the clamp is.

**No state an account.** Per-slot state is the ``[T, in_dim]`` event window
and the head recomputes its window every step; the state an account would
carry in a decoder is ``32 x 128 x 128`` float32 a KDA layer, 2.1 MB.

Precision as the other backbones': parameters bfloat16 at rest (norm
gains, taps, ``A_log``, ``dt_bias``, the expert bias and the scoring head
float32); the projections, the MLPs and the experts multiply
``operand_dtype`` operands and accumulate in float32 (``decoder_parts.mm``),
the attention core's einsums too; everything of KDA between its projections
(taps, ``silu``, the L2 norm, the decay, the one-chunk core, the gated norm)
is float32, the core's products at ``Precision.HIGHEST`` on operands that
are NOT rounded, in the kernel (Mosaic's float32 contraction, six bfloat16
passes) as in the einsums: the two differ by the order of float32 sums
alone; residual stream, norms, softmax, router scores, top-k and the logit
float32.

``jax.named_scope`` marks the parts: ``head/embed``, ``head/kda`` (inside
it ``proj`` with the norm, ``core``, ``out`` with the gate, ``Wo`` and the
add; where the kernel runs, ``core`` is its one call and holds the taps,
the decay and the head norm too; on the XLA path ``conv`` and ``gate`` are
scopes of their own and the head norm lies under ``out``), ``head/attn``
(inside it ``q``, ``kv``, ``core``,
``gate``, ``out``), ``head/mlp/dense``, ``head/moe/route`` (with the norm),
``head/moe/shared``, ``head/moe/experts`` (with the add), ``head/score``.
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
    causal_taps,
    kernel_declines,
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

KDA, MLA = "kda", "mla"
SOLVES = ("substitution", "squarings")
# float32's largest finite value is e^88.7: the one-chunk form multiplies by
# exp(-G) with |G| at most positions x |gate_lower_bound|
_EXP_ROOM = 88.0


@dataclass(frozen=True)
class LingConfig:
    in_dim: int = 12
    hidden: int = 2560
    # the SOURCE's indices of the layers held here: leading dense layers
    # count once (layer 1), then one whole period of ``layer_group_size``
    held_layers: tuple[int, ...] = (1, 2, 3, 4, 5, 6, 7)
    source_layers: int = 42       # the published depth
    source_dense_layers: int = 2  # first_k_dense_replace, published
    layer_group_size: int = 6
    heads: int = 32               # both mixers'
    head_dim: int = 128           # a KDA head's keys and its values
    conv_taps: int = 4
    gate_lower_bound: float = -5.0
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_width: int = 6144
    experts: int = 512       # the router's width: all of a layer's experts
    held_experts: int = 64   # the chip's share, experts first_expert ..
    first_expert: int = 0
    top_k: int = 8
    groups: int = 8          # n_group
    kept_groups: int = 4     # topk_group
    expert_width: int = 768
    shared_width: int = 768
    routed_scale: float = 2.5
    renorm_eps: float = 1e-20  # beside the sum the chosen scores are divided by
    rope_theta: float = 6e6
    eps: float = 1e-6
    # the two ``*_swiglu_limit_list``s, one entry a SOURCE layer
    expert_limits: tuple[float, ...] = (0,) * 35 + (4,) * 7
    shared_limits: tuple[float, ...] = (0,) * 34 + (5,) * 6 + (7,) * 2
    operand_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for name in ("expert_limits", "shared_limits"):
            limits = getattr(self, name)
            if len(limits) != self.source_layers:
                raise ValueError(f"{name} has {len(limits)} entries; the "
                                 f"source has {self.source_layers} layers")
            clamped = [l for l in self.held_layers
                       if l >= self.source_dense_layers and limits[l] != 0]
            if clamped:
                raise ValueError(
                    f"source layers {clamped} are held and {name} is "
                    f"{[limits[l] for l in clamped]} there: no key of the "
                    "source says what the limit clamps, so only layers "
                    "whose entry is 0 can be held")

    @property
    def layer_types(self) -> tuple[str, ...]:
        """The mixer of each held layer, by the source's rule."""
        return tuple(mixer_of(l, self.layer_group_size) for l in self.held_layers)

    def dense(self, source_layer: int) -> bool:
        return source_layer < self.source_dense_layers


def mixer_of(source_layer: int, group: int) -> str:
    """Latent attention closes every ``group`` layers; the rest are KDA."""
    return MLA if (source_layer + 1) % group == 0 else KDA


def window_limit(lower_bound: float) -> int:
    """The longest window the one-chunk form takes: the running sum of a
    channel's decays stays inside what float32's ``exp`` holds."""
    return int(_EXP_ROOM // -lower_bound)


def refuse_past_the_limit(window: int, lower_bound: float) -> None:
    """One chunk holds a whole window or the delta rule is not computed:
    both cores factor ``exp(G_s - G_r)`` and neither carries a state from
    chunk to chunk."""
    limit = window_limit(lower_bound)
    if window > limit:
        raise ValueError(
            f"a window of {window} positions is longer than the one chunk the "
            f"delta rule is computed in ({limit} positions at a gate bounded "
            f"by {lower_bound}): exp(-G) would leave float32")


def layer_kinds(cfg: LingConfig) -> dict[str, int]:
    """How many layers of each kind the stack holds: by operator
    (``linear``, ``attention``) and by feed-forward (``dense``, ``moe``)."""
    dense = sum(cfg.dense(l) for l in cfg.held_layers)
    return {"linear": cfg.layer_types.count(KDA),
            "attention": cfg.layer_types.count(MLA),
            "dense": dense, "moe": len(cfg.held_layers) - dense}


def init_backbone(key, cfg: LingConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``: a stacked weight slice by slice).
    Every matrix keeps its input's variance (``fan_in ** -0.5``; the taps
    of a channel ``conv_taps ** -0.5``); ``wo`` and the down matrices, which
    write into the residual stream, are scaled by ``1 / sqrt(2 *
    source_layers)`` besides (the published depth). ``A_log`` and ``dt_bias`` spread the decays over
    the open interval the bounded gate leaves them; the expert bias starts
    at zero."""
    f32 = jnp.float32
    d, nh, hd = cfg.hidden, cfg.heads, cfg.head_dim
    keys = iter(jax.random.split(key, 2 + 24 * len(cfg.held_layers)))
    out = 2 * cfg.source_layers  # a fan-in 2 * source_layers times as large

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    def normal(shape, scale=1.0, mean=0.0):
        return jax.random.normal(next(keys), shape, f32) * scale + mean

    def mlp(width, stack=()):
        return {"wg": matrix((*stack, d, width), d),
                "wu": matrix((*stack, d, width), d),
                "wd": matrix((*stack, width, d), width * out)}

    layers = []
    for source_layer, kind in zip(cfg.held_layers, cfg.layer_types, strict=True):
        layer = {"g1": jnp.ones((d,), f32), "g2": jnp.ones((d,), f32)}
        if kind == KDA:
            for name in ("wq", "wk", "wv", "wf", "wg"):
                layer[name] = matrix((d, nh * hd), d)
            layer["wb"] = matrix((d, nh), d)
            for name in ("tq", "tk", "tv"):
                layer[name] = normal((nh * hd, cfg.conv_taps),
                                     1.0 / math.sqrt(cfg.conv_taps))
            layer["a_log"] = jnp.log(jax.random.uniform(next(keys), (nh,), f32,
                                                        0.5, 1.5))
            layer["dt_bias"] = normal((nh * hd,), mean=-1.0)
            layer["gn"] = jnp.ones((hd,), f32)
            layer["wo"] = matrix((nh * hd, d), nh * hd * out)
        else:
            layer["wq"] = matrix((d, nh * (cfg.nope_dim + cfg.rope_dim)), d)
            layer["wkv_a"] = matrix((d, cfg.kv_rank + cfg.rope_dim), d)
            layer["kvn"] = jnp.ones((cfg.kv_rank,), f32)
            layer["wkv_b"] = matrix((cfg.kv_rank, nh * (cfg.nope_dim + cfg.v_dim)),
                                    cfg.kv_rank)
            layer["wgate"] = matrix((d, nh), d)
            layer["wo"] = matrix((nh * cfg.v_dim, d), nh * cfg.v_dim * out)
        if cfg.dense(source_layer):
            layer["dense"] = mlp(cfg.dense_width)
        else:
            layer["wr"] = matrix((d, cfg.experts), d)
            layer["rb"] = jnp.zeros((cfg.experts,), f32)
            layer["shared"] = mlp(cfg.shared_width)
            layer["routed"] = mlp(cfg.expert_width, (cfg.held_experts,))
        layers.append(layer)
    return tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim), next(keys), d)


def _unit_lower_inverse(low, solve: str):
    """``(I + low)^-1`` for ``low`` [..., T, T] strictly lower triangular,
    float32. ``substitution``: row ``s`` of the inverse is ``e_s - sum_{r <
    s} low[s, r] row_r``, ``T`` steps of elementwise work. ``squarings``:
    ``prod_i (I + (-low)^(2^i))``, which ends because ``low^T = 0``:
    ``ceil(log2 T)`` factors of small products at ``Precision.HIGHEST``.
    On a v5e substitution is the faster at both rungs (PERF.md, PR 49: 2.58
    against 3.49 ms of a 256-row layer's core), so the head runs it; the
    squarings stay as the second form the tests hold to the recurrence."""
    if solve not in SOLVES:
        raise ValueError(f"solve={solve!r}; one of {SOLVES}")
    t = low.shape[-1]
    eye = jnp.eye(t, dtype=low.dtype)
    if solve == "substitution":
        rows = [jnp.broadcast_to(eye[0], low.shape[:-1])]
        for s in range(1, t):
            earlier = jnp.stack(rows, axis=-2)               # [..., s, T]
            rows.append(eye[s] - jnp.sum(low[..., s, :s, None] * earlier, axis=-2))
        return jnp.stack(rows, axis=-2)
    highest = jax.lax.Precision.HIGHEST
    power = -low
    inv = eye + power
    for _ in range(1, max(t - 1, 1).bit_length()):
        power = jnp.matmul(power, power, precision=highest)
        inv = inv + jnp.matmul(inv, power, precision=highest)
    return inv


def kda_one_chunk(q, k, v, g, beta, *, lower_bound: float,
                  solve: str = "substitution"):
    """The delta rule in its one-chunk form: ``q`` and ``k`` [B, T, H, dk]
    (normalised, ``q`` scaled), ``v`` [B, T, H, dv], ``g`` [B, T, H, dk]
    (the log-decay a channel, in ``(lower_bound, 0)``), ``beta`` [B, T, H]
    -> ``o`` [B, T, H, dv], all float32. The state ``S`` [H, dk, dv] a
    window is never formed. With ``G`` the running sum of ``g`` over the
    positions: ``L[s, r] = beta_s sum_c k_s[c] k_r[c] exp(G_s[c] - G_r[c])``
    for ``r < s``; the writes ``U = (I + L)^-1 Diag(beta) V`` (the WY / UT
    transform: each position's write is what its key does NOT already read
    back); ``o_t = sum_{s <= t} [sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])]
    U_s``, which is what the recurrence from ``S_{-1} = 0`` gives.
    ``exp(G_s - G_r)`` is factored as ``(k_s exp(G_s)) . (k_r exp(-G_r))``:
    ``|G| <= T x |lower_bound|``, which the bounded gate keeps inside
    float32 for ``T <= window_limit``; a longer window is refused. ``G`` is
    counted from the window's middle position (the difference ``G_s - G_r``
    is the same from anywhere), so what the factors reach is ``exp(+-(T / 2)
    x |lower_bound|)``: counted from the first position, ``exp(G)`` at the
    fast end (e^-80) times a small key falls under float32's least normal
    value, which a TPU flushes to zero."""
    t = q.shape[1]
    refuse_past_the_limit(t, lower_bound)
    highest = jax.lax.Precision.HIGHEST
    total = jnp.cumsum(g, axis=1)                       # G [B, T, H, dk]
    total = total - total[:, t // 2, None]
    shrink = jnp.exp(total)
    k_out = k * jnp.exp(-total)                         # k_r exp(-G_r)
    kk = jnp.einsum("bthc,bshc->bhts", k * shrink, k_out, precision=highest)
    qk = jnp.einsum("bthc,bshc->bhts", q * shrink, k_out, precision=highest)
    below = jnp.tril(jnp.ones((t, t), bool), -1)
    by_head = jnp.moveaxis(beta, 1, 2)                  # [B, H, T]
    low = jnp.where(below, kk, 0.0) * by_head[..., None]
    read = jnp.where(below | jnp.eye(t, dtype=bool), qk, 0.0)
    # o = read (I + L)^-1 (beta V): the two small factors first
    mix = jnp.matmul(read, _unit_lower_inverse(low, solve), precision=highest)
    return jnp.einsum("bhts,bshv->bthv", mix, v * beta[..., None],
                      precision=highest)


def _core_is_the_kernel(positions: int, cfg: LingConfig, window: int) -> bool:
    """Whether everything of a KDA mixer between its projections and its
    output gate over ``positions`` positions in windows of ``window`` runs
    as the Pallas kernel (ops/pallas/delta_window.py: on a TPU, where it
    takes the shapes) or as ``_core_by_xla``. Picked while tracing, from backend and
    shapes, and announced once a compile, with the kernel's reason where
    it declines. A window past ``window_limit`` is refused either way."""
    from igaming_platform_tpu.ops.pallas import delta_window as kernel

    refuse_past_the_limit(window, cfg.gate_lower_bound)
    nh, hd = cfg.heads, cfg.head_dim
    why, backend = kernel_declines(lambda: kernel.declines(
        positions, heads=nh, head_dim=hd, window=window))
    announce_core(
        f"one chunk by einsums ({why})" if why else
        f"pallas-windows ({nh} heads of {hd}, window {window}, prologue=taps, "
        "norm=inside)",
        backend, "linear-attention core")
    return not why


def kda_mixer(u, layer: Params, cfg: LingConfig, window: int):
    """Kimi Delta Attention over normed hidden states ``u`` [P, hidden] ->
    [P, hidden]. Everything between the projections and the output gate
    (the taps, ``silu``, the L2 norm, the decay, the one-chunk core, the
    head norm) is one Pallas kernel over the projections' results as they
    lie where ``_core_is_the_kernel`` finds that it takes the layer;
    elsewhere the same arithmetic by XLA over ``[b, t, h, d]``,
    ``kda_one_chunk`` its core: float32 with no operand rounded either
    way."""
    from igaming_platform_tpu.ops.pallas import delta_window as kernel

    nh, t = cfg.heads, window
    with jax.named_scope("proj"):
        q, k, v, f, z = (mm(u, layer[name], cfg)
                         for name in ("wq", "wk", "wv", "wf", "wg"))
        beta = mm(u, layer["wb"], cfg)
    if _core_is_the_kernel(u.shape[0], cfg, t):
        with jax.named_scope("core"):
            y = kernel.delta_window(
                q, k, v, f, beta, layer["a_log"], layer["dt_bias"],
                (layer["tq"], layer["tk"], layer["tv"]), (layer["gn"], cfg.eps),
                heads=nh, window=t, lower_bound=cfg.gate_lower_bound)
    else:
        y = _core_by_xla(q, k, v, f, beta, layer, cfg, t)
    with jax.named_scope("out"):
        return mm(y * jax.nn.sigmoid(z), layer["wo"], cfg)


def _core_by_xla(q, k, v, f, beta, layer: Params, cfg: LingConfig, t: int):
    """The projections' results [P, heads x head_dim] (``beta`` [P, heads])
    -> the head-normed core's result [P, heads x head_dim], as the kernel
    returns it: the taps, the decay, ``kda_one_chunk`` and the head norm
    over ``[b, t, h, d]``."""
    nh, hd = cfg.heads, cfg.head_dim
    b = q.shape[0] // t
    with jax.named_scope("conv"):
        def conv(x, taps):
            return jax.nn.silu(causal_taps(x.reshape(b, t, -1), layer[taps]))

        def unit(x):  # L2 over a head's channels, as the published kernel
            x = x.reshape(b, t, nh, hd)
            return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        q = unit(conv(q, "tq")) * (hd ** -0.5)
        k = unit(conv(k, "tk"))
        v = conv(v, "tv").reshape(b, t, nh, hd)
    with jax.named_scope("gate"):
        rate = jnp.exp(layer["a_log"])[:, None]         # a head
        g = cfg.gate_lower_bound * jax.nn.sigmoid(
            rate * (f + layer["dt_bias"]).reshape(b, t, nh, hd))
        beta = jax.nn.sigmoid(beta).reshape(b, t, nh)
    with jax.named_scope("core"):
        o = kda_one_chunk(q, k, v, g, beta, lower_bound=cfg.gate_lower_bound)
    with jax.named_scope("out"):
        return rms_norm(o, layer["gn"], cfg.eps).reshape(b * t, nh * hd)


def backbone_hidden(params: Params, x, lengths, cfg: LingConfig):
    """[B, T, in_dim] events, [B] real events a window -> final-normed
    hidden states [B, T, hidden] (float32); position ``t`` of a window is
    its rotary position in the latent-attention layer. A window's padding
    (positions past its length, which behind a causal convolution, a causal
    delta rule and a causal mask no real position reads) goes through the
    mixers and the dense and shared MLPs with the rest of the batch but is
    not routed: it has no pair in the held experts."""
    b, t, _ = x.shape
    live = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(b * t)
    with jax.named_scope("head/embed"):
        # the residual stream position-major, [P, hidden] with P = B x T
        h = mm(x.reshape(b * t, -1), params["embed"], cfg)
        cos, sin = rope_angles(b, t, cfg.rope_dim, cfg.rope_theta)
    for kind, layer in zip(cfg.layer_types, params["layers"], strict=True):
        if kind == KDA:
            with jax.named_scope("head/kda"):
                with jax.named_scope("proj"):
                    u = rms_norm(h, layer["g1"], cfg.eps)
                h = h + kda_mixer(u, layer, cfg, t)
        else:
            with jax.named_scope("head/attn"):
                a = rms_norm(h, layer["g1"], cfg.eps).reshape(b, t, -1)
                h = h + latent_attention(a, layer, cos, sin, cfg,
                                         interleave=True).reshape(b * t, -1)
        if "dense" in layer:
            with jax.named_scope("head/mlp/dense"):
                h = h + swiglu(rms_norm(h, layer["g2"], cfg.eps),
                               layer["dense"], cfg)
            continue
        with jax.named_scope("head/moe/route"):
            flat = rms_norm(h, layer["g2"], cfg.eps)
            top_e, top_w = route(flat, layer, cfg, cfg.groups, cfg.kept_groups)
        with jax.named_scope("head/moe/shared"):
            m = swiglu(flat, layer["shared"], cfg)
        with jax.named_scope("head/moe/experts"):
            h = h + (m + grouped_experts(flat, top_e, top_w, layer["routed"],
                                         cfg, cfg.first_expert, live))
    with jax.named_scope("head/score"):
        return rms_norm(h, params["gf"], cfg.eps).reshape(b, t, -1)


def backbone_scores(params: Params, window, lengths, cfg: LingConfig):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability, read at the last real position,
    which no padded position can reach."""
    lengths = lengths.astype(jnp.int32)
    hid = backbone_hidden(params, window, lengths, cfg)
    with jax.named_scope("head/score"):
        return score_last(params, hid, lengths)
