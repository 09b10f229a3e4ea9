"""A state-space hybrid decoder backbone over the session window: Mamba-2
heads beside grouped-query attention in every layer (the ``falconh1``
session head, models/session_heads.py).

The block is Falcon-H1-34B-Instruct's decoder layer at the published
widths by default: hidden 5120; every layer runs TWO mixers on one normed
input and adds both to the residual stream, grouped-query attention (20
query / 4 key-value heads of 128, keys scaled) and a Mamba-2 state-space
mixer (32 heads of 128 channels, a state of 256 a channel, ``B`` and ``C``
in 2 groups, a 4-tap causal convolution with a bias, a gate before a
grouped RMSNorm); then a dense SwiGLU of 21,504. No expert, no router.
The model's muP scalars sit on the branches as published
(``FalconH1Config``'s ``*_multiplier`` fields). Events enter as
``inputs_embeds`` through a projector (``x @ W_in``, 12 -> hidden) times
``embedding_multiplier``; the score is a sequence-classification head on
the last real position, its product times ``lm_head_multiplier``. Each
layer, over the residual stream ``h`` [P, hidden] (float32, ``P = B x T``
position-major; ``N`` an RMSNorm with a learned gain):

1. ``u = N_in(h)``, read by both mixers.

   - attention: ``a = u * attention_in_multiplier``; ``q = a Wq``, ``k = (a
     Wk) * key_multiplier``, ``v = a Wv``; rotate-half rotary on every
     channel, position = the event's index; causal softmax of ``q k^T /
     sqrt(head_dim)``, ``heads / kv_heads`` query heads to a key-value
     head; ``A = concat(heads) Wo * attention_out_multiplier``
     (``decoder_parts.attention`` without head norms, with a key scale).
   - state space: ``p = ((u * ssm_in_multiplier) W_in) * m`` with ``m`` the
     five ``ssm_multipliers`` over the column segments ``[z | x | B | C |
     dt]``; ``[x | B | C]`` through a depthwise causal convolution of
     ``conv_taps`` taps with a bias (``decoder_parts.causal_taps``; zero
     before the window's first event), then ``silu``; ``dt = softplus(p_dt
     + dt_bias)``, ``A = -exp(A_log)`` a head. The recurrence a head,
     ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D
     x_t`` with ``H_{-1} = 0``, is computed in its DUAL FORM over the one
     chunk a window is (``ssd_one_chunk``): ``c_t = sum_{r<=t} dt_r A``,
     ``L[t, s] = exp(c_t - c_s)`` for ``s <= t`` and 0 above, ``G = C B^T``
     a group, ``y_t = sum_s G[t, s] L[t, s] dt_s x_s + D x_t``. That is
     Mamba-2's own chunked algorithm with one chunk, exact; a window longer
     than ``chunk`` (``mamba_chunk_size``) is refused, because the state
     that a second chunk would read is never held. ``g = y * silu(z)``, an
     RMSNorm over each group's channels; ``S = (g' W_out) *
     ssm_out_multiplier``.

2. ``r = h + (S + A)``.
3. ``h' = r + (silu((f Wg) * mlp_multipliers[0]) * (f Wu)) Wd *
   mlp_multipliers[1]`` with ``f = N_ff(r)`` (``decoder_parts.swiglu``
   with a gate scale).

**Which form runs where.** On a TPU, where the shapes allow (heads and a
state of whole 128-lane vregs, windows of 8, 16, 32, 64 or 128 positions,
whole tiles of 128 positions: the cell's at both rungs), everything of the
mixer between ``W_in``'s product and ``W_out``'s is ONE Pallas call a
layer, ``ops/pallas/ssd_window.py``: it reads ``p`` where the product wrote
it and writes the gated, normed operand of ``W_out`` in the operands'
dtype. Off the TPU, or where the kernel declines, the same arithmetic runs
by XLA (``_core_by_xla``: ``causal_taps``, ``ssd_one_chunk``, the gate and
``rms_norm``), which is also what the kernel is held to. ``ssm_mixer``
picks while tracing, from the backend and the shapes alone
(``_core_is_the_kernel``), and announces the ``state-space core``:
``window kernel (tile=128, ...)`` or ``dual form, one chunk, T <= chunk
(the kernel's reason)``. Float32 with no operand rounded either way.

After the last layer one more RMSNorm. A window's padding (positions past
its length) is computed with the rest of the batch: the convolution, the
dual form's ``L`` and the attention mask are causal, so nothing that is
scored can read it.

**No state, convolution or key-value cache an account.** Per-slot state is
the ``[T, in_dim]`` event window and the head recomputes its window every
step; the recurrent state an account would carry in a decoder is ``32 x
128 x 256`` float32 a layer, 4.19 MB.

Precision as the other backbones': parameters bfloat16 at rest (norm
gains, the convolution's taps and bias, ``A_log``, ``D``, ``dt_bias`` and
the scoring head float32); the projections and the MLP multiply
``operand_dtype`` operands and accumulate in float32 (``decoder_parts.mm``), the
attention core's two einsums too; the state-space core (``dt``, the decay,
``G``, the sum over ``s``, ``D``) is float32 at ``Precision.HIGHEST`` on
operands that are NOT rounded, as the published code keeps it in float32;
residual stream, norms, the convolution, the gate, softmax, the muP
scalars and the logit float32.

``jax.named_scope`` marks the parts: ``head/embed``, ``head/ssm`` (inside
it ``in``, ``scan`` and ``out`` where the kernel runs, ``scan`` then the
whole call with the taps and the gate in it; ``in``, ``conv``, ``scan``,
``gate``, ``out`` by XLA; the norm both mixers read is computed under
``head/ssm``), ``head/attn`` (the attention branch and
the three-way add), ``head/mlp/dense`` (its norm, the SwiGLU and its add),
``head/score`` (the final norm and the scoring column).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from igaming_platform_tpu.models.decoder_parts import (
    Params,
    _matrix,
    announce_core,
    attention,
    causal_taps,
    kernel_declines,
    mm,
    rms_norm,
    rope_angles,
    score_last,
    swiglu,
    tree_around,
)


@dataclass(frozen=True)
class FalconH1Config:
    in_dim: int = 12
    hidden: int = 5120
    layers: int = 4
    heads: int = 20
    kv_heads: int = 4
    head_dim: int = 128
    ssm_heads: int = 32       # mamba_d_ssm 4096 = 32 heads of 128
    ssm_head_dim: int = 128
    ssm_state: int = 256      # a channel of a head
    ssm_groups: int = 2       # ``B`` and ``C`` are shared by heads / groups
    conv_taps: int = 4
    chunk: int = 128          # mamba_chunk_size: the longest window served
    dense_width: int = 21504
    # the muP scalars, as published
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over the segments [z | x | B | C | dt] of the in-projection's columns
    ssm_multipliers: tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    mlp_multipliers: tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    lm_head_multiplier: float = 0.0078125
    rope_theta: float = 1e11
    eps: float = 1e-5
    # the depth the seeded tree is initialised for: the three projections
    # that write into the residual stream are scaled by 1 / sqrt(2 *
    # init_depth) (the published 72 layers, of which ``layers`` are held)
    init_depth: int = 72
    operand_dtype: Any = jnp.bfloat16

    @property
    def ssm_width(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def segments(self) -> tuple[int, ...]:
        """The in-projection's column segments ``[z | x | B | C | dt]``."""
        bc = self.ssm_groups * self.ssm_state
        return (self.ssm_width, self.ssm_width, bc, bc, self.ssm_heads)


def layer_kinds(cfg: FalconH1Config) -> dict[str, int]:
    """How many layers of each kind the stack holds: every layer counts
    under both of its operators (``ssm``, ``attention``) and under its
    feed-forward (``dense``)."""
    return {"ssm": cfg.layers, "attention": cfg.layers, "dense": cfg.layers}


def mup_vector(cfg: FalconH1Config):
    """The five ``ssm_multipliers`` spread over the in-projection's
    columns, [sum(segments)] float32."""
    if len(cfg.ssm_multipliers) != len(cfg.segments):
        raise ValueError(f"ssm_multipliers has {len(cfg.ssm_multipliers)} "
                         f"entries; the in-projection has the segments "
                         f"[z | x | B | C | dt]")
    return jnp.asarray(np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                                 cfg.segments))


def init_backbone(key, cfg: FalconH1Config) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``: a large matrix row block by row
    block). The multipliers are applied as published, so every matrix is
    drawn for the multiplier that follows it: it keeps its input's
    variance THROUGH that multiplier (``fan_in ** -0.5`` over the
    multiplier; the in-projection's segments each over ``ssm_in_multiplier``
    times their own), and ``wo``, ``w_out`` and ``wd``, which write into
    the residual stream, carry ``1 / sqrt(2 * init_depth)`` besides. A tree
    drawn without regard to them would leave the stream all embedding
    (5.66 in, 0.0375 / 0.088 / 0.011 out). The convolution's taps are
    ``conv_taps ** -0.5`` and its bias a quarter of a unit; ``dt_bias``,
    ``A_log`` and ``D`` as Mamba-2's reference initialisation draws them
    (``dt`` log-uniform in [0.001, 0.1] through the inverse softplus, ``A``
    uniform in [1, 16], ``D`` one)."""
    f32 = jnp.float32
    d, hd, w = cfg.hidden, cfg.head_dim, cfg.dense_width
    keys = iter(jax.random.split(key, 2 + 17 * cfg.layers))
    out = 2 * cfg.init_depth  # a fan-in 2 * init_depth times as large
    conv_dim = sum(cfg.segments[1:4])  # [x | B | C]

    def matrix(shape, fan_in, through=1.0):
        """Drawn at ``fan_in ** -0.5 / through``."""
        return _matrix(next(keys), shape, fan_in * through * through)

    layers = []
    for _ in range(cfg.layers):
        dt = jnp.exp(jax.random.uniform(next(keys), (cfg.ssm_heads,), f32,
                                        math.log(1e-3), math.log(1e-1)))
        layers.append({
            "g1": jnp.ones((d,), f32), "g2": jnp.ones((d,), f32),
            "wq": matrix((d, cfg.heads * hd), d, cfg.attention_in_multiplier),
            "wk": matrix((d, cfg.kv_heads * hd), d,
                         cfg.attention_in_multiplier * cfg.key_multiplier),
            "wv": matrix((d, cfg.kv_heads * hd), d, cfg.attention_in_multiplier),
            "wo": matrix((cfg.heads * hd, d), cfg.heads * hd * out,
                         cfg.attention_out_multiplier),
            "w_in": jnp.concatenate(
                [matrix((d, n), d, cfg.ssm_in_multiplier * m)
                 for n, m in zip(cfg.segments, cfg.ssm_multipliers, strict=True)],
                axis=1),
            "taps": (jax.random.normal(next(keys), (conv_dim, cfg.conv_taps), f32)
                     * (1.0 / math.sqrt(cfg.conv_taps))),
            "conv_b": jax.random.normal(next(keys), (conv_dim,), f32) * 0.25,
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "a_log": jnp.log(jax.random.uniform(next(keys), (cfg.ssm_heads,),
                                                f32, 1.0, 16.0)),
            "d_skip": jnp.ones((cfg.ssm_heads,), f32),
            "gn": jnp.ones((cfg.ssm_width,), f32),
            "w_out": matrix((cfg.ssm_width, d), cfg.ssm_width * out,
                            cfg.ssm_out_multiplier),
            "dense": {"wg": matrix((d, w), d, cfg.mlp_multipliers[0]),
                      "wu": matrix((d, w), d),
                      "wd": matrix((w, d), w * out, cfg.mlp_multipliers[1])},
        })
    return tree_around(
        layers, matrix((cfg.in_dim, d), cfg.in_dim, cfg.embedding_multiplier),
        next(keys), d, cfg.lm_head_multiplier)


def _refuse_past_one_chunk(t: int, cfg: FalconH1Config) -> None:
    if t > cfg.chunk:
        raise ValueError(
            f"a window of {t} positions is longer than one chunk "
            f"(mamba_chunk_size {cfg.chunk}): the dual form over one chunk "
            "holds no state for a second one")


def ssd_one_chunk(x, bm, cm, dt, layer: Params, cfg: FalconH1Config):
    """The state-space core in its dual form over one chunk: ``x`` [B, T,
    heads, head_dim], ``bm`` and ``cm`` [B, T, groups, state], ``dt`` [B,
    T, heads] (after the softplus) -> ``y`` [B, T, heads, head_dim], all
    float32. Head ``j`` reads group ``j // (heads / groups)``. The state
    ``H`` [heads, head_dim, state] a window is never formed: with ``c`` the
    running sum of ``dt A`` over the positions, ``y_t = sum_{s <= t} (C_t .
    B_s) exp(c_t - c_s) dt_s x_s + D x_t``, which is what the recurrence
    from ``H_{-1} = 0`` gives. ``c_t - c_s <= 0`` for ``s <= t`` (``A`` is
    negative, ``dt`` positive), so nothing overflows."""
    t = x.shape[1]
    _refuse_past_one_chunk(t, cfg)
    highest = jax.lax.Precision.HIGHEST
    c = jnp.cumsum(dt * -jnp.exp(layer["a_log"]), axis=1)       # [B, T, H]
    causal = jnp.tril(jnp.ones((t, t), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, c[:, :, None] - c[:, None], -jnp.inf))
    g = jnp.einsum("btgn,bsgn->btsg", cm, bm, precision=highest)
    g = jnp.repeat(g, cfg.ssm_heads // cfg.ssm_groups, axis=-1)  # [B, T, S, H]
    y = jnp.einsum("btsh,bshp->bthp", g * decay * dt[:, None], x,
                   precision=highest)
    return y + layer["d_skip"][:, None] * x


def _core_is_the_kernel(positions: int, cfg: FalconH1Config, window: int) -> bool:
    """Whether everything of the mixer between its two projections over
    ``positions`` positions in windows of ``window`` runs as the Pallas
    kernel (ops/pallas/ssd_window.py: on a TPU, where it takes the shapes)
    or as ``_core_by_xla``. Picked while tracing, from backend and shapes,
    and announced once a compile as the ``state-space core``: ``window
    kernel (tile=128, ...)``, or the dual form with the kernel's reason. A
    window past ``chunk`` is refused either way."""
    from igaming_platform_tpu.ops.pallas import ssd_window as kernel

    _refuse_past_one_chunk(window, cfg)
    nh, hd = cfg.ssm_heads, cfg.ssm_head_dim
    why, backend = kernel_declines(lambda: kernel.declines(
        positions, heads=nh, head_dim=hd, state=cfg.ssm_state,
        groups=cfg.ssm_groups, window=window, taps=cfg.conv_taps))
    announce_core(
        f"dual form, one chunk, {window} <= {cfg.chunk} ({why})" if why else
        f"window kernel (tile=128, {nh} heads of {hd}, state {cfg.ssm_state} "
        f"in {cfg.ssm_groups} groups, window {window}, taps={cfg.conv_taps}, "
        "gate and norm inside)",
        backend, "state-space core")
    return not why


def ssm_mixer(u, layer: Params, cfg: FalconH1Config, window: int):
    """The Mamba-2 mixer over normed hidden states ``u`` [P, hidden] -> [P,
    hidden], its output multiplier applied. Everything between the two
    projections (the taps with their bias, ``silu``, ``softplus``, the
    decay, the dual form, the gate and the grouped norm) is one Pallas
    kernel over the in-projection's result as it lies where
    ``_core_is_the_kernel`` finds that it takes the layer; elsewhere the
    same arithmetic by XLA, ``ssd_one_chunk`` its core: float32 with no
    operand rounded either way."""
    from igaming_platform_tpu.ops.pallas import ssd_window as kernel

    with jax.named_scope("in"):
        p = mm(u * cfg.ssm_in_multiplier, layer["w_in"], cfg) * mup_vector(cfg)
    if _core_is_the_kernel(u.shape[0], cfg, window):
        with jax.named_scope("scan"):
            g = kernel.ssd_window(
                p, layer["taps"], layer["conv_b"], layer["dt_bias"],
                layer["a_log"], layer["d_skip"], layer["gn"],
                heads=cfg.ssm_heads, state=cfg.ssm_state,
                groups=cfg.ssm_groups, window=window, eps=cfg.eps,
                out_dtype=cfg.operand_dtype)
    else:
        g = _core_by_xla(p, layer, cfg, window)
    with jax.named_scope("out"):
        return mm(g, layer["w_out"], cfg) * cfg.ssm_out_multiplier


def _core_by_xla(p, layer: Params, cfg: FalconH1Config, t: int):
    """The in-projection's result ``p`` [P, sum(segments)] -> the gated,
    normed operand of the out-projection [P, ssm_width] float32, as the
    kernel returns it (there rounded): the taps, ``ssd_one_chunk``, the gate
    and the grouped norm over ``[b, t, ...]``."""
    width, _, bc, _, nh = cfg.segments
    b = p.shape[0] // t
    with jax.named_scope("in"):
        z, xbc, dt = p[:, :width], p[:, width:2 * width + 2 * bc], p[:, -nh:]
    with jax.named_scope("conv"):
        xbc = jax.nn.silu(causal_taps(xbc.reshape(b, t, -1), layer["taps"],
                                      layer["conv_b"]))
    with jax.named_scope("scan"):
        x = xbc[..., :width].reshape(b, t, nh, cfg.ssm_head_dim)
        bm = xbc[..., width:width + bc].reshape(b, t, cfg.ssm_groups, cfg.ssm_state)
        cm = xbc[..., width + bc:].reshape(b, t, cfg.ssm_groups, cfg.ssm_state)
        dt = jax.nn.softplus(dt + layer["dt_bias"]).reshape(b, t, nh)
        y = ssd_one_chunk(x, bm, cm, dt, layer, cfg).reshape(b * t, width)
    with jax.named_scope("gate"):
        # the gate first, then the norm over each group's channels
        g = (y * jax.nn.silu(z)).reshape(b * t, cfg.ssm_groups, -1)
        g = rms_norm(g, layer["gn"].reshape(cfg.ssm_groups, -1), cfg.eps)
    return g.reshape(b * t, width)


def backbone_hidden(params: Params, x, cfg: FalconH1Config):
    """[B, T, in_dim] events -> final-normed hidden states [B, T, hidden]
    (float32); position ``t`` of a window is its rotary position. Every position of every window goes through both
    mixers and the MLP of every layer."""
    b, t, _ = x.shape
    with jax.named_scope("head/embed"):
        # the residual stream position-major, [P, hidden] with P = B x T
        h = mm(x.reshape(b * t, -1), params["embed"], cfg) * cfg.embedding_multiplier
        cos, sin = rope_angles(b, t, cfg.head_dim, cfg.rope_theta)
    for layer in params["layers"]:
        with jax.named_scope("head/ssm"):
            u = rms_norm(h, layer["g1"], cfg.eps)  # both mixers read it
            s = ssm_mixer(u, layer, cfg, t)
        with jax.named_scope("head/attn"):
            a = attention(u * cfg.attention_in_multiplier, layer, cos, sin, cfg,
                          t, key_scale=cfg.key_multiplier)
            h = h + (s + a * cfg.attention_out_multiplier)
        with jax.named_scope("head/mlp/dense"):
            f = rms_norm(h, layer["g2"], cfg.eps)
            h = h + (swiglu(f, layer["dense"], cfg, cfg.mlp_multipliers[0])
                     * cfg.mlp_multipliers[1])
    with jax.named_scope("head/score"):
        return rms_norm(h, params["gf"], cfg.eps).reshape(b, t, -1)


def backbone_scores(params: Params, window, lengths, cfg: FalconH1Config):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability, read at the last real
    position, which under a causal convolution, a causal state-space core
    and causal attention no padded position can reach."""
    hid = backbone_hidden(params, window, cfg)
    with jax.named_scope("head/score"):
        return score_last(params, hid, lengths, cfg.lm_head_multiplier)
