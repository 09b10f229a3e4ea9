"""A decoder-hybrid-decoder backbone over the session window: a first half
that mixes positions (Mamba-1 scans and differential attention) and a second
half that mixes none of its own, handed ONE memory and ONE key-value window
by the first (the ``phi4flash`` session head, models/session_heads.py).

The stack is Phi-4-mini-flash-reasoning's (SambaY, arXiv 2507.06607) whole,
32 layers at the published widths by default: hidden 2560, 40 query / 20
key-value heads of 64, a dense SwiGLU of 10,240 in every layer, a band of 512
keys, a Mamba layer every ``mb_per_layer`` = 2. Events enter as
``inputs_embeds`` through a projector (``x @ W_in``, 12 -> hidden); the score
is a sequence-classification head on the last real position. ``LN`` is
LayerNorm with a gain and a bias; every layer is ``h += Mixer(LN1(h)); h +=
MLP(LN2(h))`` over the float32 residual stream, and what ``Mixer`` is follows
from the layer's index ``l`` by the source's rule (``layer_kind``; ``L`` the
depth, ``L / 2`` even):

- ``l`` even, ``l <= L/2`` -- **Mamba-1** (``ssm``; d_inner 5120, state 16, 4
  taps, dt_rank 160): ``[x, z] = u W_in``; ``x = silu(taps(x) + b)``; ``[r,
  B, C] = x W_x``; ``dt = softplus(r W_dt + b_dt)``; the recurrence of
  ops/pallas/selective_scan.py a channel and a state column, from zero at
  the window's first position; out ``(y * silu(z)) W_out``. **Layer L/2 also
  exports** ``m = y``, the scan's output before the gate: the memory.
- ``l`` odd, ``l < L/2`` -- **differential attention inside a band**
  (``window``): query ``i`` reads key ``j`` where ``0 <= i - j <
  sliding_window``. ``[q, k, v] = u W_qkv + b``. Query heads ``(2p, 2p+1)``
  are pair ``p``'s ``(q1, q2)``, key heads ``(2j, 2j+1)`` pair ``j``'s ``(k1,
  k2)``, value heads ``(2j, 2j+1)`` side by side the pair's 128-wide ``V_j``;
  pair ``p`` reads ``j = p // (heads / kv_heads)``. ``A_i = softmax(mask(q_i
  k_i^T / sqrt(64)))``; ``o_p = (A_1 - lambda A_2) V_j`` with ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; ``o_p = (1 - lambda_init) RMSNorm_128(o_p)``; out
  ``concat_p(o_p) W_o + b_o``. No rotary, no head norm on q or k.
- ``l = L/2 + 1`` -- the same over every causal key (``attention``), **and
  exports its** ``K, V``.
- ``l`` even, ``l >= L/2 + 2`` -- **Gated Memory Unit** (``memory``): ``(m *
  silu(u W_g)) W_out`` (2560 -> 5120 -> 2560), ``m`` layer ``L/2``'s at the
  same position.
- ``l`` odd, ``l >= L/2 + 3`` -- **cross attention** (``cross``): ``q = u W_q
  + b_q`` against layer ``L/2 + 1``'s ``K, V``, the same differential form
  with its own lambdas, norm and ``W_o``.

**The stack narrows.** Layers past ``L/2 + 1`` read other positions only
through ``m`` at their own position and through ``K, V``; the service scores
one position a window. So ``backbone_scores`` runs layers ``0 .. L/2`` over
``[P, hidden]`` (``P = B x T``), gathers ``h`` and ``m`` at each row's last
real position, and runs the rest over ``[B, hidden]``: exactly what the
all-positions pass gives there (``backbone_hidden`` is that pass, for the
tests). Layer ``L/2 + 1`` is narrowed as far as is exact: its norm and its
``K, V`` product run at every position (the cross layers and its own one
query read them), its ``q``, core, ``W_o`` and MLP at the last real position
only. Layer ``L/2`` stays whole: layer ``L/2 + 1``'s keys are made from its
output at every position. A scored row costs ``(L/2 + 1) T + (L/2 - 1)``
layer-positions of ``L T`` (``layer_positions``).

**Which core runs where.** The scan: on a TPU, where
``selective_scan.declines`` takes the shapes, one Pallas call a layer;
elsewhere ``scan_by_chunks``, chunks of ``scan_chunk`` positions that hand
the state ``[B, channels, state]`` from one to the next with an associative
scan inside each, which is the kernel's reference and what the CPU tests and
replay run. Attention: ``differential_core``, a sweep in query blocks as two
einsums a block (ops/pallas/block_attention.py does not take it: heads of 64
are half a vreg, a value is twice as wide as a key, and it norms and turns
``q`` itself). Both are picked while tracing and announced (``state-space
core``, ``attention core (window)``, ``attention core (full)``).

Precision as the other backbones': parameters bfloat16 at rest (norm gains
and biases, every projection's bias, the taps, ``A_log``, ``D``, the lambdas
and the scoring head float32); every product multiplies ``operand_dtype``
operands and accumulates in float32; residual stream, norms, the taps,
``dt``, the decay, the state, ``y``, softmax and the logit float32.

``jax.named_scope`` marks the parts: ``head/embed``, ``head/ssm/{in, conv,
scan, out}`` (``conv`` holds the taps and the two small products that make
``dt``, ``B`` and ``C``), ``head/attn/{window, full}`` with ``core`` inside,
``head/mlp/dense`` (layers ``0 .. L/2 + 1``, the last of them at the scored
position only, as the narrowed part of ``head/attn/full`` is),
``head/cross/{gmu, attn, mlp}`` (the layers past ``L/2 + 1``, ``core``
inside ``attn``), ``head/score``.
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
    mm,
    rms_norm,
    rows_at,
    score_last,
    swiglu,
    tree_around,
)

SSM, BAND, FULL, GMU, CROSS = "ssm", "window", "attention", "memory", "cross"


@dataclass(frozen=True)
class Phi4FlashConfig:
    in_dim: int = 12
    hidden: int = 2560
    layers: int = 32
    heads: int = 40
    kv_heads: int = 20
    dense_width: int = 10240
    mb_per_layer: int = 2
    sliding_window: int = 512
    # Mamba-1's own sizes: d_inner = 2 x hidden, dt_rank = ceil(hidden / 16)
    ssm_state: int = 16
    conv_taps: int = 4
    # positions a chunk of ``scan_by_chunks`` holds, where the kernel is not
    scan_chunk: int = 128
    eps: float = 1e-5
    operand_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.layers % 4 or self.mb_per_layer != 2:
            raise ValueError(
                f"{self.layers} layers with a Mamba layer every "
                f"{self.mb_per_layer}: the source's rule is written for a "
                "depth in whole fours and a Mamba layer every second")
        if self.heads % self.kv_heads or self.kv_heads % 2:
            raise ValueError(f"{self.heads} heads over {self.kv_heads} key "
                             "heads do not pair")

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def ssm_width(self) -> int:
        return 2 * self.hidden

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden // 16)

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def half(self) -> int:
        """The memory's layer: the last one that runs at every position."""
        return self.layers // 2


def layer_kind(index: int, cfg: Phi4FlashConfig) -> str:
    """What mixes in layer ``index``, by the source's rule over the index."""
    if index % cfg.mb_per_layer == 0:
        return SSM if index <= cfg.half else GMU
    if index < cfg.half:
        return BAND
    return FULL if index == cfg.half + 1 else CROSS


def kinds_of(cfg: Phi4FlashConfig) -> tuple[str, ...]:
    return tuple(layer_kind(i, cfg) for i in range(cfg.layers))


def layer_kinds(cfg: Phi4FlashConfig) -> dict[str, int]:
    """How many layers of each kind the stack holds: a layer counts under
    its mixer and, every one, under its feed-forward (``dense``)."""
    kinds = kinds_of(cfg)
    return {kind: kinds.count(kind) for kind in (SSM, BAND, FULL, GMU, CROSS)
            } | {"dense": cfg.layers}


def layer_positions(cfg: Phi4FlashConfig, window: int) -> tuple[int, int]:
    """``(layer-positions one scored row costs, layer-positions of every
    layer at every position)``: layers ``0 .. L/2`` run at all ``window``
    positions, the rest at the one that is scored (of layer ``L/2 + 1`` the
    norm and the ``K, V`` product, 7% of its multiply-adds, run at every
    position besides)."""
    whole = cfg.half + 1
    return whole * window + (cfg.layers - whole), cfg.layers * window


def key_blocks(cfg: Phi4FlashConfig, window: int) -> tuple[int, int]:
    """``((query, key) pairs the cores of one window's layers score, pairs
    of their squares)`` a query head, the area of the key blocks the sweep
    goes by (``block_attention.visited_blocks``'s unit): a band layer's
    blocks, and of the full layer, whose one query a row is the last, the
    one row of blocks it meets."""
    from igaming_platform_tpu.ops.pallas.block_attention import (
        one_row,
        visited_blocks,
    )

    bands = kinds_of(cfg).count(BAND)
    visited, square = visited_blocks(window, cfg.sliding_window)
    row, _ = one_row(window)
    return bands * visited + row, (bands + 1) * square


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


# -- the seeded tree ----------------------------------------------------------


def init_backbone(key, cfg: Phi4FlashConfig) -> Params:
    """A seeded tree, built on the device one matrix at a time and held in
    bfloat16 (``decoder_parts._matrix``): every matrix keeps its input's
    variance, and the ones that write into the residual stream (``w_out``,
    ``wo``, ``wd``) carry ``1 / sqrt(2 x layers)`` besides. Mamba's own
    initialisation for the scan: ``A[c, n] = n + 1``, ``dt`` log-uniform in
    [0.001, 0.1] through the inverse softplus, ``D`` one; the lambdas N(0,
    0.1); norms at gain one, biases zero but the taps' (a quarter of a
    unit)."""
    f32 = jnp.float32
    d, di, n, w = cfg.hidden, cfg.ssm_width, cfg.ssm_state, cfg.dense_width
    hd, kvw = cfg.head_dim, cfg.kv_width
    keys = iter(jax.random.split(key, 2 + 16 * cfg.layers))
    out = 2 * cfg.layers  # a fan-in 2 x layers times as large

    def matrix(shape, fan_in):
        return _matrix(next(keys), shape, fan_in)

    def norm():
        return {"g": jnp.ones((d,), f32), "b": jnp.zeros((d,), f32)}

    def differential():
        lam = jax.random.normal(next(keys), (4, hd), f32) * 0.1
        return {"lam": lam, "sn": jnp.ones((2 * hd,), f32),
                "wo": matrix((d, d), d * out), "bo": jnp.zeros((d,), f32)}

    layers = []
    for kind in kinds_of(cfg):
        layer = {"n1": norm(), "n2": norm(),
                 "dense": {"wg": matrix((d, w), d), "wu": matrix((d, w), d),
                           "wd": matrix((w, d), w * out)}}
        if kind == SSM:
            dt = jnp.exp(jax.random.uniform(next(keys), (di,), f32,
                                            math.log(1e-3), math.log(1e-1)))
            layer |= {
                "w_in": matrix((d, 2 * di), d),
                "taps": (jax.random.normal(next(keys), (di, cfg.conv_taps), f32)
                         * (1.0 / math.sqrt(cfg.conv_taps))),
                "conv_b": jax.random.normal(next(keys), (di,), f32) * 0.25,
                "w_x": matrix((di, cfg.dt_rank + 2 * n), di),
                "w_dt": matrix((cfg.dt_rank, di), cfg.dt_rank),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "a_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=f32)), (di, n)),
                "d_skip": jnp.ones((di,), f32),
                "w_out": matrix((di, d), di * out)}
        elif kind == GMU:
            layer |= {"w_g": matrix((d, di), d),
                      "w_out": matrix((di, d), di * out)}
        elif kind == CROSS:
            layer |= {"wq": matrix((d, d), d), "bq": jnp.zeros((d,), f32),
                      **differential()}
        else:
            layer |= {"wqkv": matrix((d, d + 2 * kvw), d),
                      "bqkv": jnp.zeros((d + 2 * kvw,), f32), **differential()}
        layers.append(layer)
    params = tree_around(layers, matrix((cfg.in_dim, d), cfg.in_dim),
                         next(keys), d)
    params["bf"] = jnp.zeros((d,), f32)  # the final LayerNorm's bias
    return params


# -- the parts ----------------------------------------------------------------


def layer_norm(x, norm: Params, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * norm["g"] + norm["b"]


def scan_by_chunks(x, dt, bm, cm, a_t, d, *, window: int, chunk: int):
    """Mamba-1's recurrence in chunks of ``chunk`` positions that hand the
    state from one to the next: the reference of the kernel (ops/pallas/
    selective_scan.selective_scan, under its signature) and what runs off
    the TPU. ``x``, ``dt`` [P, channels], ``bm``, ``cm`` [P, state], ``a_t``
    [state, channels], ``d`` [channels], all float32 -> ``y`` [P, channels].
    Inside a chunk the pairs ``(exp(dt A), dt B x)`` are composed by an
    associative scan (``(a, b) then (a', b')`` is ``(a a', a' b + b')``), so
    a chunk's ``[chunk, channels, state]`` stands at once and never a
    window's; a window that is not whole chunks is padded with positions
    that leave the state as it is (``dt`` 0) and cut from the result."""
    p, channels = x.shape
    b = p // window
    pad = -window % chunk

    def chunked(m):  # [P, w] -> [chunks, B, chunk, w]
        m = jnp.pad(m.reshape(b, window, -1), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(m.reshape(b, -1, chunk, m.shape[-1]), 1, 0)

    a = a_t.T                                           # [channels, state]

    def one(s, args):
        xs, dts, bs, cs = args                          # [B, chunk, ...]
        decay = jnp.exp(dts[..., None] * a)             # [B, chunk, C, N]
        push = (dts * xs)[..., None] * bs[:, :, None, :]
        through, added = jax.lax.associative_scan(
            lambda l, r: (l[0] * r[0], r[0] * l[1] + r[1]), (decay, push),
            axis=1)
        states = through * s[:, None] + added
        y = jnp.sum(states * cs[:, :, None, :], axis=-1)
        return states[:, -1], y

    s0 = jnp.zeros((b, channels, a.shape[1]), jnp.float32)
    _, y = jax.lax.scan(one, s0, tuple(map(chunked, (x, dt, bm, cm))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, window + pad, channels)[:, :window]
    return y.reshape(p, channels) + d * x


def _scan_core(positions: int, cfg: Phi4FlashConfig, window: int):
    """What runs the recurrence over ``positions`` positions in windows of
    ``window``: the Pallas kernel (ops/pallas/selective_scan.py: on a TPU,
    where it takes the shapes) or ``scan_by_chunks``; either way a function
    of ``(x, dt, bm, cm, a_t, d)``. Picked while tracing, from backend and
    shapes, and announced once a compile as the ``state-space core``."""
    from igaming_platform_tpu.ops.pallas import selective_scan as kernel

    f32 = jnp.float32
    wide = jax.ShapeDtypeStruct((positions, cfg.ssm_width), f32)
    narrow = jax.ShapeDtypeStruct((positions, cfg.ssm_state), f32)
    why, backend = kernel_declines(lambda: kernel.declines(
        wide, wide, narrow, narrow, window=window))
    sizes = f"{cfg.ssm_width} channels, state {cfg.ssm_state}, window {window}"
    if why:
        announce_core(f"chunks of {cfg.scan_chunk} that hand the state on "
                      f"({sizes}; {why})", backend, "state-space core")
        return lambda *args: scan_by_chunks(*args, window=window,
                                            chunk=cfg.scan_chunk)
    announce_core(
        f"pallas-scan (tile={kernel.tile_for(cfg.ssm_width)}, blocks of "
        f"{kernel.block_for(window)}, the state in VMEM; {sizes})",
        backend, "state-space core")
    return lambda *args: kernel.selective_scan(*args, window=window)


def ssm_mixer(u, layer: Params, cfg: Phi4FlashConfig, window: int):
    """The Mamba-1 mixer over normed hidden states ``u`` [P, hidden] ->
    ``(out [P, hidden], y [P, d_inner])``: ``y`` is the scan's output before
    the gate, what layer ``L/2`` exports as the memory."""
    p = u.shape[0]
    di, n, rank = cfg.ssm_width, cfg.ssm_state, cfg.dt_rank
    with jax.named_scope("in"):
        xz = mm(u, layer["w_in"], cfg)
        x, z = xz[:, :di], xz[:, di:]
    with jax.named_scope("conv"):
        x = jax.nn.silu(causal_taps(x.reshape(p // window, window, di),
                                    layer["taps"], layer["conv_b"]))
        x = x.reshape(p, di)
        rbc = mm(x, layer["w_x"], cfg)
        dt = jax.nn.softplus(mm(rbc[:, :rank], layer["w_dt"], cfg)
                             + layer["dt_bias"])
        bm, cm = rbc[:, rank:rank + n], rbc[:, rank + n:]
    core = _scan_core(p, cfg, window)
    with jax.named_scope("scan"):
        y = core(x, dt, bm, cm, -jnp.exp(layer["a_log"]).T, layer["d_skip"])
    with jax.named_scope("out"):
        return mm(y * jax.nn.silu(z), layer["w_out"], cfg), y


def lambda_of(layer: Params, index: int):
    lq1, lk1, lq2, lk2 = layer["lam"]
    return (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
            + lambda_init(index))


def _paired(q, k, v, cfg: Phi4FlashConfig):
    """The heads as differential attention pairs them. ``q`` [..., heads x
    hd] -> [..., j, r, i, hd]: query head ``4j + 2r + i`` is side ``i`` of
    query pair ``2j + r``, which reads key-value pair ``j``; ``k`` [...,
    kv_heads x hd] -> [..., j, i, hd]; ``v`` -> [..., j, 2 hd], the pair's
    two value heads side by side. (``4``: ``2 heads / kv_heads``.)"""
    hd, pairs = cfg.head_dim, cfg.kv_heads // 2
    rep = cfg.heads // cfg.kv_heads
    return (q.reshape(*q.shape[:-1], pairs, rep, 2, hd),
            k.reshape(*k.shape[:-1], pairs, 2, hd),
            v.reshape(*v.shape[:-1], pairs, 2 * hd))


def _subtract_and_norm(o, layer: Params, index: int, cfg: Phi4FlashConfig):
    """``o`` [..., j, r, i, 2 hd], both sides' weighted sums of values ->
    the pairs' ``(1 - lambda_init) RMSNorm(o_1 - lambda o_2)``, [..., hidden]
    as ``W_o`` reads it."""
    o = o[..., 0, :] - lambda_of(layer, index) * o[..., 1, :]
    o = rms_norm(o, layer["sn"], cfg.eps) * (1.0 - lambda_init(index))
    return o.reshape(*o.shape[:-3], cfg.hidden)


def differential_core(q, k, v, layer: Params, index: int, cfg: Phi4FlashConfig,
                      *, window: int, band: int | None,
                      block: int | None = None):
    """The core of differential attention in query blocks, two einsums a
    block. ``q`` [P, heads x hd] float32 with its bias, ``k`` and ``v`` [P,
    kv_heads x hd] rounded -> [P, hidden] float32, subtracted and normed,
    which ``W_o``'s product rounds. A block of queries meets the keys from
    the first its band keeps (the window's first in the full layer) to its
    own last, under the mask written as its two inequalities; no ``[t, s]``
    array of a whole window stands at once."""
    from igaming_platform_tpu.ops.pallas.block_attention import block_for

    dt, t, hd = k.dtype, window, cfg.head_dim
    b = q.shape[0] // t
    block = block or block_for(t)
    q, k, v = _paired(q.reshape(b, t, -1).astype(dt), k.reshape(b, t, -1),
                      v.reshape(b, t, -1), cfg)
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        first = 0 if band is None else max(lo - band + 1, 0)
        i = jnp.arange(lo, hi)[:, None]
        j = jnp.arange(first, hi)[None, :]
        keep = j <= i if band is None else (j <= i) & (i - j < band)
        sc = jnp.einsum("btjrid,bsjid->bjrits", q[:, lo:hi], k[:, first:hi],
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bjrits,bsje->btjrie", p.astype(dt), v[:, first:hi],
                       preferred_element_type=jnp.float32)
        out.append(_subtract_and_norm(o, layer, index, cfg))
    return jnp.concatenate(out, axis=1).reshape(b * t, cfg.hidden)


def _announce_attention(kind: str, cfg: Phi4FlashConfig, window: int) -> None:
    from igaming_platform_tpu.ops.pallas import block_attention as kernel

    _, backend = kernel_declines()
    band = cfg.sliding_window if kind == BAND else None
    announce_core(
        f"einsum in query blocks (differential, {cfg.heads}/{cfg.kv_heads} "
        f"of {cfg.head_dim}, values of {2 * cfg.head_dim}; "
        f"{kernel.describe(window, band, sweep=True)}; the block kernel takes "
        "heads of whole 128-lane vregs, values as wide as keys and its own "
        "head norm and rotary)", backend,
        f"attention core ({'window' if kind == BAND else 'full'})")


def attention(u, layer: Params, index: int, cfg: Phi4FlashConfig, window: int):
    """A band layer's attention sublayer over normed hidden states ``u`` [P,
    hidden] in windows of ``window`` -> [P, hidden]."""
    d, kvw, dt = cfg.hidden, cfg.kv_width, cfg.operand_dtype
    qkv = mm(u, layer["wqkv"], cfg) + layer["bqkv"]
    q, k, v = qkv[:, :d], qkv[:, d:d + kvw], qkv[:, d + kvw:]
    _announce_attention(BAND, cfg, window)
    with jax.named_scope("core"):
        o = differential_core(q, k.astype(dt), v.astype(dt), layer, index, cfg,
                              window=window, band=cfg.sliding_window)
    return _out(o, layer, cfg)


def cross_attention(q, k, v, last, layer: Params, index: int,
                    cfg: Phi4FlashConfig):
    """One query a row against its window's keys: ``q`` [B, heads x hd]
    float32 with its bias, ``k`` and ``v`` [B, T, kv_heads x hd] rounded,
    ``last`` [B] the query's position (it reads keys ``<= last``) -> [B,
    hidden] float32, subtracted and normed, which ``W_o``'s product rounds."""
    dt, hd = k.dtype, cfg.head_dim
    q, k, v = _paired(q.astype(dt), k, v, cfg)
    sc = jnp.einsum("bjrid,bsjid->bjris", q, k,
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    keep = jnp.arange(k.shape[1])[None, :] <= last[:, None]
    p = jax.nn.softmax(jnp.where(keep[:, None, None, None], sc, -jnp.inf),
                       axis=-1)
    o = jnp.einsum("bjris,bsje->bjrie", p.astype(dt), v,
                   preferred_element_type=jnp.float32)
    return _subtract_and_norm(o, layer, index, cfg)


def gated_memory(u, m, layer: Params, cfg: Phi4FlashConfig):
    """``(m * silu(u W_g)) W_out``: ``u`` [.., hidden] normed, ``m`` [..,
    d_inner] the memory at the same positions."""
    return mm(m * jax.nn.silu(mm(u, layer["w_g"], cfg)), layer["w_out"], cfg)


def _out(o, layer: Params, cfg: Phi4FlashConfig):
    """``o W_o + b_o``: the subtracted, normed pairs through the layer's
    out-projection."""
    return mm(o, layer["wo"], cfg) + layer["bo"]


def _final_norm(params: Params, h, cfg: Phi4FlashConfig):
    return layer_norm(h, {"g": params["gf"], "b": params["bf"]}, cfg.eps)


def _mlp(h, layer: Params, cfg: Phi4FlashConfig):
    return h + swiglu(layer_norm(h, layer["n2"], cfg.eps), layer["dense"], cfg)


# -- the stack ----------------------------------------------------------------


def _mixing_half(params: Params, x, cfg: Phi4FlashConfig):
    """Layers ``0 .. L/2`` over every position: events [B, T, in_dim] -> the
    stream ``h`` [P, hidden] after layer ``L/2`` and its memory ``m`` [P,
    d_inner]."""
    b, t, _ = x.shape
    with jax.named_scope("head/embed"):
        h = mm(x.reshape(b * t, -1), params["embed"], cfg)
    m = None
    for index, layer in enumerate(params["layers"][:cfg.half + 1]):
        u = layer_norm(h, layer["n1"], cfg.eps)
        if layer_kind(index, cfg) == SSM:
            with jax.named_scope("head/ssm"):
                mixed, m = ssm_mixer(u, layer, cfg, t)
                h = h + mixed
        else:
            with jax.named_scope("head/attn/window"):
                h = h + attention(u, layer, index, cfg, t)
        with jax.named_scope("head/mlp/dense"):
            h = _mlp(h, layer, cfg)
    return h, m


def _keys_and_values(h, layer: Params, cfg: Phi4FlashConfig):
    """The full layer's normed input and its ``K, V`` at every position:
    ``h`` [P, hidden] -> ``(u, k, v)``, ``k`` and ``v`` rounded."""
    d, dt = cfg.hidden, cfg.operand_dtype
    u = layer_norm(h, layer["n1"], cfg.eps)
    kv = mm(u, layer["wqkv"][:, d:], cfg) + layer["bqkv"][d:]
    return u, kv[:, :cfg.kv_width].astype(dt), kv[:, cfg.kv_width:].astype(dt)


def backbone_scores(params: Params, window, lengths, cfg: Phi4FlashConfig):
    """The session head: window [B, T, in_dim] (real events first, zeros
    after), lengths [B] -> [B] probability, read at the last real position.
    Layers ``0 .. L/2`` run over every position; the rest, and all of layer
    ``L/2 + 1`` but its keys and values, over each row's last real position
    only, which is all the score reads of them."""
    b, t, _ = window.shape
    d, full = cfg.hidden, cfg.half + 1
    last = jnp.clip(lengths.astype(jnp.int32) - 1, 0, t - 1)
    h, m = _mixing_half(params, window, cfg)
    layer = params["layers"][full]
    with jax.named_scope("head/attn/full"):
        u, k, v = _keys_and_values(h, layer, cfg)
        k, v = k.reshape(b, t, -1), v.reshape(b, t, -1)
        h, m, u = (rows_at(a, last, t) for a in (h, m, u))
        _announce_attention(FULL, cfg, t)
        q = mm(u, layer["wqkv"][:, :d], cfg) + layer["bqkv"][:d]
        with jax.named_scope("core"):
            o = cross_attention(q, k, v, last, layer, full, cfg)
        h = h + _out(o, layer, cfg)
    with jax.named_scope("head/mlp/dense"):
        h = _mlp(h, layer, cfg)
    for index, layer in enumerate(params["layers"][full + 1:], full + 1):
        u = layer_norm(h, layer["n1"], cfg.eps)
        if layer_kind(index, cfg) == GMU:
            with jax.named_scope("head/cross/gmu"):
                h = h + gated_memory(u, m, layer, cfg)
        else:
            with jax.named_scope("head/cross/attn"):
                q = mm(u, layer["wq"], cfg) + layer["bq"]
                with jax.named_scope("core"):
                    o = cross_attention(q, k, v, last, layer, index, cfg)
                h = h + _out(o, layer, cfg)
        with jax.named_scope("head/cross/mlp"):
            h = _mlp(h, layer, cfg)
    with jax.named_scope("head/score"):
        hid = _final_norm(params, h, cfg)
        return score_last(params, hid[:, None], jnp.ones_like(last))


def backbone_hidden(params: Params, x, cfg: Phi4FlashConfig):
    """Every layer at every position: [B, T, in_dim] events -> final-normed
    hidden states [B, T, hidden] (float32). Not the serving path
    (``backbone_scores`` narrows the second half to the position it reads);
    the tests hold the one to the other."""
    b, t, _ = x.shape
    d, full = cfg.hidden, cfg.half + 1
    h, m = _mixing_half(params, x, cfg)
    layer = params["layers"][full]
    u, k, v = _keys_and_values(h, layer, cfg)
    q = mm(u, layer["wqkv"][:, :d], cfg) + layer["bqkv"][:d]
    o = differential_core(q, k, v, layer, full, cfg, window=t, band=None)
    h = _mlp(h + _out(o, layer, cfg), layer, cfg)
    for index, layer in enumerate(params["layers"][full + 1:], full + 1):
        u = layer_norm(h, layer["n1"], cfg.eps)
        if layer_kind(index, cfg) == GMU:
            h = h + gated_memory(u, m, layer, cfg)
        else:
            q = mm(u, layer["wq"], cfg) + layer["bq"]
            o = differential_core(q, k, v, layer, index, cfg, window=t,
                                  band=None)
            h = h + _out(o, layer, cfg)
        h = _mlp(h, layer, cfg)
    return _final_norm(params, h, cfg).reshape(b, t, -1)
