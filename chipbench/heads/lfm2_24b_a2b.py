"""The ``lfm2`` session head's plain reference: LFM2-24B-A2B's decoder
block (gated short convolutions beside grouped-query attention in one
stack, a leading dense layer, sigmoid-routed experts chosen with an expert
bias, every expert held) over a session window: its tree from the seed and
its forward pass.

Nothing is imported from the program, and the block below is written from
the family's published modelling code, not from the program. The
arithmetic is float32 (``jax.numpy`` at
``jax.default_matmul_precision("highest")``; on the chip's machine that is
the chip, in a test the CPU) over weights that bfloat16 holds exactly,
every operand of a product passed through the rounder. No kernel, no sort:
the convolution is three shifted products and the experts are a loop over
all of them with a mask. The sizes are the configuration file's top-level
source keys.

With ``N(.)`` an RMSNorm with a learned gain and ``norm_eps``, layer ``l``
over the stream ``h`` [rows, 16, hidden] of a window (positions ``t`` =
0..15, causal, each window alone):

1. ``r = h + Op_l(N_op(h))``, ``u = N_op(h)``.

   - ``layer_types[l] == "conv"``: ``[B, C, X] = split3(u W_in)`` (``W_in``
     hidden x 3 hidden, no bias; the three thirds in that order); ``z = B *
     X``; ``c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t`` per channel (``w``
     [hidden, ``conv_L_cache``], a depthwise causal convolution; ``z``
     before the window's first event is zero); ``Op = (C * c) W_out``.
   - ``"full_attention"``: ``q = u Wq`` as ``num_attention_heads`` heads of
     64, ``k = u Wk``, ``v = u Wv`` as ``num_key_value_heads``; an RMSNorm
     over the 64 of every head of ``q`` and of ``k``; rotate-half rotary on
     all 64 channels (``rope_theta``, position = the event's index);
     causal softmax of ``q k^T / 8``, four query heads to a key-value
     head; ``Op = concat(heads) Wo``.

2. ``h' = r + FF_l(N_ffn(r))``, ``u = N_ffn(r)``. For ``l <
   num_dense_layers``: ``w2(silu(w1 u) * (w3 u))`` at ``intermediate_size``.
   Else ``s = sigmoid(u Wg)`` in float32; ``sel = top4(s + b)`` with ``b``
   the expert bias, WHICH CHOOSES AND DOES NOT WEIGH; ``w = s[sel]``; ``w =
   w / (sum(w) + 1e-6)``; ``w = w * routed_scaling_factor``; ``FF = sum_i
   w_i E_{sel_i}(u)``, each ``E`` a SwiGLU of ``moe_intermediate_size``. No
   shared expert; no token is dropped; every position is routed, a window's
   padding too (nothing that is scored can read it).

After the last layer one more RMSNorm (``embedding_norm``). Output:
``sigmoid(N(h)[last real position] . w_out + b_out)``.

Departures from the published model and what its config does not give,
each also under ``head.assumed`` in the configuration file:

- ``head_dim`` is not in the config: ``hidden_size / num_attention_heads``
  = 64, the family's convention.
- Events enter as ``inputs_embeds`` through a projector ``x @ W_in`` (12 ->
  hidden); no row of the 65,536-row vocabulary is held, and a
  sequence-classification head (one float32 output column) stands in the
  place of the output head. Position ids are the event's index.
- ``q_layernorm`` / ``k_layernorm`` and the final ``embedding_norm`` as the
  family's modelling code has them; the config lists neither.
- The renormalisation's ``1e-6`` is the modelling code's constant, not a
  key of the config.
- The convolution's cache (``conv_L_cache`` columns of ``z`` a layer) is
  not held per account: the service's per-slot state is the event window,
  recomputed every step.
- The router's matrix and bias, the norm gains and the convolution's taps
  are float32 at rest; the router's product rounds its operands like every
  other product, its scores, the bias, the top-k and the weights are
  float32.
- The seeded tree's scale: every matrix ``fan_in ** -0.5`` (the taps ``3
  ** -0.5``); ``out_proj``, ``Wo``, ``w2`` and the experts' down matrices
  carry ``1 / sqrt(2 x 40)`` besides (the published depth).
- **The seeded projector reads standardised events** (``_standardised``,
  as ``heads/openpangu_ultra.py``: PERF.md, PR 36): each row of ``W_in``
  whose event column varies is divided by that column's spread over
  plausible windows and the constant column's row carries the means; one
  matrix, no bias.
- **The seeded expert bias is what the model's bias is for**: the
  loss-free balancing rule run on the seeded router over the real
  positions of plausible windows (``_balancing_bias``: up for an expert
  under the mean load, down for one over it), so that each expert sees
  about ``positions x 4 / 64`` of them where a bare seeded router would
  leave some experts three times their share. It changes the chosen set on
  a large share of the positions (``_made["bias_moved"]``, a layer: PERF.md
  has the reading), so a router that ignored it, or weighed by it, fails
  the check.

A weight of more than 2^24 elements is multiplied a block of its columns
at a time (``_product``: each output element is the same dot product
either way); windows go through in blocks of ``BLOCK_ROWS``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL

CONV, ATTENTION = "conv", "full_attention"
RENORM_EPS = 1e-6  # the modelling code's constant beside the chosen scores' sum


class Dims(NamedTuple):
    hidden: int
    layer_types: tuple
    dense_layers: int
    taps: int
    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    experts: int
    top_k: int
    expert_width: int
    scale: float
    theta: float
    eps: float


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys."""
    kinds = tuple(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {CONV, ATTENTION}:
        raise ValueError("layer_types has one entry a layer, 'conv' or "
                         "'full_attention'")
    if config["conv_bias"] or not config["norm_topk_prob"] \
            or not config["use_expert_bias"]:
        raise ValueError("this reference is written for a convolution without "
                         "bias, renormalised top-k weights and an expert bias")
    return Dims(
        hidden=config["hidden_size"], layer_types=kinds,
        dense_layers=config["num_dense_layers"], taps=config["conv_L_cache"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        dense_width=config["intermediate_size"], experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        scale=float(config["routed_scaling_factor"]),
        theta=float(config["rope_parameters"]["rope_theta"]),
        eps=float(config["norm_eps"]))


# -- the tree from the seed ---------------------------------------------------

BLOCK_ELEMS = 1 << 24  # the most elements of a weight handled at once


def out_scale(config: dict) -> float:
    """What the projections that write into the residual stream are scaled
    by: ``1 / sqrt(2 x layers)`` of the PUBLISHED depth."""
    layers = config.get("head", {}).get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return 1.0 / math.sqrt(2.0 * layers)


# What the shapes of a tree do not give (the layers' kinds, experts a token,
# theta, eps, ...): ``forward`` is handed a tree and a rounder only, so it
# reads the sizes of the tree ``make_params`` made last. ``bias_moved`` is
# what that tree's expert bias does, a layer: the share of the plausible
# windows' real positions whose chosen set it changes.
_made: dict = {}


def _blocks(whole: int, other: int, unit: int) -> int:
    """In how many equal blocks of ``whole`` (each a multiple of ``unit``)
    a ``whole x other`` weight is taken so that none passes
    ``BLOCK_ELEMS``; 1 where it is small or cannot be divided so."""
    need = -(-whole * other // BLOCK_ELEMS)
    if need <= 1:
        return 1
    return next((b for b in range(need, whole // unit + 1)
                 if whole % (unit * b) == 0), 1)


@partial(jax.jit, static_argnums=(1, 2))
def _normal_bf16(key, shape, scale):
    """Seeded normals in bfloat16; a stacked weight is drawn one leading
    slice at a time and a large matrix one block of rows at a time, so
    that no float32 copy of either ever exists."""
    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * scale).astype(jnp.bfloat16)

    if len(shape) == 3:
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    blocks = _blocks(shape[0], shape[1], 16)
    if blocks > 1:
        return jax.lax.map(lambda k: draw(k, (shape[0] // blocks, shape[1])),
                           jax.random.split(key, blocks)).reshape(shape)
    return draw(key, shape)


def plausible_windows(rng, n: int):
    """``n`` windows of 4 to 16 events as the traffic's look: log-amounts,
    log-gaps, the mix of transaction types, the constant column."""
    win = np.zeros((n, 16, EVENT_WIDTH), F32)
    lengths = rng.integers(4, 17, n)
    win[..., 0] = rng.normal(7.6, 1.2, (n, 16))     # log1p of ~2000 cents
    win[..., 1] = rng.uniform(0.3, 3.0, (n, 16))    # log1p of seconds
    codes = rng.choice(4, size=(n, 16), p=[0.07, 0.03, 0.70, 0.20])
    win[np.arange(n)[:, None], np.arange(16)[None, :],
        2 + TX_EVENT_COL[codes]] = 1.0
    win[..., 10] = 1.0
    win *= (np.arange(16)[None, :] < lengths[:, None])[..., None]
    return win, lengths


def make_params(seed: int, config: dict) -> dict:
    """The head's tree (the shape of the program's), built on the device
    in bfloat16 (norm gains, taps, router and scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x6C666D32), 96))
    hid, hd, f = d.hidden, d.head_dim, d.expert_width
    out = out_scale(config)

    def w(*shape, scale=1.0):
        """Fan-in is the axis before the last."""
        return _normal_bf16(next(keys), tuple(shape),
                            scale / math.sqrt(shape[-2]))

    def mlp(width, *stack):
        return {"wg": w(*stack, hid, width), "wu": w(*stack, hid, width),
                "wd": w(*stack, width, hid, scale=out)}

    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for i, kind in enumerate(d.layer_types):
        layer = {"g1": ones(hid), "g2": ones(hid)}
        if kind == CONV:
            layer["w_in"] = w(hid, 3 * hid)
            layer["taps"] = (jax.random.normal(next(keys), (hid, d.taps),
                                               jnp.float32) / math.sqrt(d.taps))
            layer["w_out"] = w(hid, hid, scale=out)
        else:
            layer["wq"] = w(hid, d.heads * hd)
            layer["wk"] = w(hid, d.kv_heads * hd)
            layer["wv"] = w(hid, d.kv_heads * hd)
            layer["wo"] = w(d.heads * hd, hid, scale=out)
            layer["qn"], layer["kn"] = ones(hd), ones(hd)
        if i < d.dense_layers:
            layer["dense"] = mlp(d.dense_width)
        else:
            layer["wr"] = w(hid, d.experts).astype(jnp.float32)
            layer["rb"] = jnp.zeros((d.experts,), jnp.float32)
            layer["routed"] = mlp(f, d.experts)
        layers.append(layer)
    rng = np.random.default_rng([seed & (2**64 - 1), 0x6C666D32])
    params = {
        "embed": w(EVENT_WIDTH, hid),
        "layers": layers,
        "gf": ones(hid),
        "head": {"w": jnp.asarray(rng.standard_normal((hid, 1))
                                  / math.sqrt(hid), jnp.float32),
                 "b": jnp.zeros((1,), jnp.float32)},
    }
    # A random head answers nearly the same for every window, far from the
    # fold threshold on most seeds, and the check would then never see its
    # output. Scale and shift the last layer so that over plausible windows
    # the logits spread by about one and centre on the threshold: about
    # half of the warm rows fold, on every seed (as heads/keye_vl2.py). The
    # direction it reads is the one of ``HEAD_CANDIDATES`` seeded
    # directions along which these windows spread most (PERF.md, PR 34).
    win, lengths = plausible_windows(rng, 8 * BLOCK_ROWS)
    params["embed"] = _standardised(params["embed"], win, lengths)
    hidden = _bias_and_read(params, win, lengths, d)
    candidates = rng.standard_normal((hid, HEAD_CANDIDATES)) / math.sqrt(hid)
    spread = (hidden.astype(np.float64) @ candidates).std(axis=0)
    w_out = candidates[:, int(np.argmax(spread))]
    logits = hidden.astype(np.float64) @ w_out
    gain = 1.0 / max(float(logits.std()), 1e-6)
    centre = math.log(FLAG_THRESHOLD / (1.0 - FLAG_THRESHOLD))
    params["head"] = {
        "w": jnp.asarray(w_out[:, None] * gain, jnp.float32),
        "b": jnp.asarray([centre - np.median(logits) * gain], jnp.float32)}
    return params


# -- the forward pass ---------------------------------------------------------


def forward(params: dict, windows: np.ndarray, lengths: np.ndarray, rnd) -> np.ndarray:
    logits = _logits(params, np.asarray(windows, F32), lengths, _made["dims"],
                     operand_dtype(rnd))
    return (1.0 / (1.0 + np.exp(-logits.astype(F32)))).astype(F32)


def operand_dtype(rnd):
    """The dtype a harness rounder (``chipbench.reference.rounder``, a
    numpy function) rounds to, so that the same rounding can be applied
    where the operands live."""
    probe = rnd(np.array([1.0 + 2.0 ** -10, 1.0 + 2.0 ** -6], F32))
    if probe[0] != 1.0:
        return jnp.float32
    return jnp.bfloat16 if probe[1] != 1.0 else jnp.float8_e4m3fn


HEAD_CANDIDATES = 16
# The program's head rounds its operands itself (``decoder_parts.mm`` casts
# both to the stated dtype), on the CPU as on the MXU: ``harness.judge``
# reads a rehearsal's reference at the stated dtype too.
CASTS_OPERANDS = True
BLOCK_ROWS = 32  # windows a block: every shape below is one block's


def _blocks_of(windows, lengths):
    """``(windows, lengths)`` in blocks of ``BLOCK_ROWS`` windows, the last
    one padded with empty windows: one set of compiled shapes serves any
    number of rows and the temporaries stay at a block's size beside the
    resident tree."""
    n, t, _ = windows.shape
    pad = -n % BLOCK_ROWS
    windows = np.concatenate([windows, np.zeros((pad, t, EVENT_WIDTH), F32)])
    lengths = np.concatenate([np.asarray(lengths, np.int32),
                              np.ones((pad,), np.int32)])
    return [(jnp.asarray(windows[lo:lo + BLOCK_ROWS]),
             jnp.asarray(lengths[lo:lo + BLOCK_ROWS]))
            for lo in range(0, n + pad, BLOCK_ROWS)]


def _layer(kind: str, layer, x, d: Dims, dt):
    """One decoder layer over ``x`` [rows, T, hidden]."""
    x = (_short_conv if kind == CONV else _attend)(layer, x, d, dt)
    return (_dense if "dense" in layer else _moe)(layer, x, d, dt)


def _logits(params, windows, lengths, d: Dims, dt, hidden: bool = False) -> np.ndarray:
    """The pre-sigmoid score of every window; with ``hidden`` the
    final-normed hidden state of its last real position instead."""
    out = []
    with jax.default_matmul_precision("highest"):
        for win, lens in _blocks_of(windows, lengths):
            x = _embed(params["embed"], win, dt)
            for kind, layer in zip(d.layer_types, params["layers"]):
                x = _layer(kind, layer, x, d, dt)
            out.append(np.asarray(_score(params, x, lens, d, hidden)))
    return np.concatenate(out)[:windows.shape[0]]


# -- the seeded projector, standardised ---------------------------------------


def _standardised(w_in, windows: np.ndarray, lengths: np.ndarray):
    """``w_in`` [event width, hidden] so that ``event @ w_in`` reads each
    event column standardised over the plausible events: a column that
    varies has its row divided by the column's spread, and the column that
    is constant (one in every event) carries the means, ``sum_i
    (e_i - mean_i) / std_i w_i + w_const``. Columns no event sets stay as
    drawn. The projector stays one matrix without a bias."""
    real = np.arange(windows.shape[1])[None, :] < np.asarray(lengths)[:, None]
    events = windows[real].astype(np.float64)
    mean, std = events.mean(axis=0), events.std(axis=0)
    varies = std > 0
    const = int(np.flatnonzero(~varies & (mean != 0))[0])
    w = np.asarray(w_in.astype(jnp.float32)).astype(np.float64)
    out = w / np.where(varies, std, 1.0)[:, None]
    out[const] -= (mean[varies] / std[varies]) @ w[varies] / mean[const]
    return jnp.asarray(out.astype(F32), jnp.bfloat16)


# -- the seeded expert bias ---------------------------------------------------

BALANCE_TURNS = 200


def _bias_and_read(params, windows, lengths, d: Dims) -> np.ndarray:
    """The plausible windows through the tree in float32, layer by layer
    over all blocks: at each expert layer the bias is set from the router's
    scores over the real positions it sees (``_balancing_bias``; ``params``
    is updated in place) before the layer is applied. Returns the
    final-normed hidden state of each window's last real position, which
    the scoring head is then fitted to."""
    f32 = jnp.float32
    blocks = _blocks_of(windows, lengths)
    t = windows.shape[1]
    real = np.concatenate([np.arange(t)[None, :] < np.asarray(lens)[:, None]
                           for _, lens in blocks]).reshape(-1)
    moved = _made["bias_moved"] = []
    with jax.default_matmul_precision("highest"):
        xs = [_embed(params["embed"], win, f32) for win, _ in blocks]
        for kind, layer in zip(d.layer_types, params["layers"]):
            op = _short_conv if kind == CONV else _attend
            xs = [op(layer, x, d, f32) for x in xs]
            if "dense" in layer:
                xs = [_dense(layer, x, d, f32) for x in xs]
                continue
            s = np.concatenate([np.asarray(_router_scores(layer, x, d, f32))
                                for x in xs])[real]
            bias, share = _balancing_bias(s, d.top_k)
            layer["rb"] = jnp.asarray(bias, f32)
            moved.append(share)
            xs = [_moe(layer, x, d, f32) for x in xs]
        hidden = [np.asarray(_score(params, x, lens, d, True))
                  for x, (_, lens) in zip(xs, blocks)]
    return np.concatenate(hidden)[:windows.shape[0]]


def _balancing_bias(scores: np.ndarray, top_k: int):
    """The expert bias that evens the experts' loads over the positions
    ``scores`` [T, experts] (the router's sigmoid scores), by the rule the
    published model's bias is trained with: it starts at zero and moves up
    for an expert chosen less than the mean load, down for one chosen more,
    by a step that shrinks to nothing. Returns it (float32) and the share
    of the positions whose chosen set it changes."""
    s = scores.astype(np.float64)
    experts = s.shape[1]
    mean_load = s.shape[0] * top_k / experts
    bias = np.zeros(experts)
    step = 0.25 * float(s.std())

    def chosen(b):
        return np.argpartition(-(s + b), top_k - 1, axis=1)[:, :top_k]

    for turn in range(BALANCE_TURNS):
        load = np.bincount(chosen(bias).ravel(), minlength=experts)
        bias += step * (1.0 - turn / BALANCE_TURNS) * np.sign(mean_load - load)
    bias = bias.astype(F32)
    bare, biased = np.sort(chosen(0.0), 1), np.sort(chosen(bias.astype(np.float64)), 1)
    return bias, float((bare != biased).any(axis=1).mean())


def _rnd(a, dt):
    """``a`` rounded to ``dt`` and back in float32. The barrier keeps the
    compiler from dropping the pair of conversions: XLA may keep "excess
    precision" and does on a TPU (PERF.md, PR 34)."""
    if dt == jnp.float32 or a.dtype == dt:
        return a.astype(jnp.float32)
    return jax.lax.optimization_barrier(a.astype(dt)).astype(jnp.float32)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _product(x, w, dt):
    """``x @ w`` [n, k] x [k, m], both rounded to ``dt``; a large weight a
    block of its columns at a time (the same dot product an element)."""
    blocks = _blocks(w.shape[1], w.shape[0], 128)
    xr = _rnd(x, dt)
    if blocks == 1:
        return xr @ _rnd(w, dt)
    cols = w.shape[1] // blocks
    out = jax.lax.map(
        lambda i: xr @ _rnd(jax.lax.dynamic_slice_in_dim(w, i * cols, cols, 1), dt),
        jnp.arange(blocks))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], w.shape[1])


def _swiglu(u, w, dt):
    gate = _product(u, w["wg"], dt)
    mid = gate / (1.0 + jnp.exp(-gate)) * _product(u, w["wu"], dt)
    return _product(mid, w["wd"], dt)


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


def _rope(x, d: Dims):
    """Rotary embedding as the family's published code writes it, over all
    ``head_dim`` channels of ``x`` [rows, T, heads, head_dim]: angles ``t x
    theta ** (-2i / head_dim)``, ``cat(freqs, freqs)`` over the channels,
    ``x cos + rotate_half(x) sin``."""
    half = d.head_dim // 2
    inv = d.theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d.head_dim)
    freqs = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


@partial(jax.jit, static_argnums=(2, 3))
def _short_conv(layer, x, d: Dims, dt):
    """``x + (C * conv(B * X)) W_out``: the convolution as ``conv_L_cache``
    shifted products, tap ``k`` reading the event ``L - 1 - k`` before."""
    rows, t, hid = x.shape
    u = _rms(x, layer["g1"], d.eps).reshape(rows * t, hid)
    bcx = _product(u, layer["w_in"], dt).reshape(rows, t, 3 * hid)
    b, c, xg = bcx[..., :hid], bcx[..., hid:2 * hid], bcx[..., 2 * hid:]
    z = b * xg
    conv = jnp.zeros_like(z)
    for k in range(d.taps):
        back = d.taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros((rows, back, hid), z.dtype), z[:, :t - back]], axis=1)
        conv = conv + layer["taps"][:, k] * shifted
    y = (c * conv).reshape(rows * t, hid)
    return x + _product(y, layer["w_out"], dt).reshape(x.shape)


@partial(jax.jit, static_argnums=(2, 3))
def _attend(layer, x, d: Dims, dt):
    rows, t, hid = x.shape
    group = d.heads // d.kv_heads
    u = _rms(x, layer["g1"], d.eps).reshape(rows * t, hid)
    q = _product(u, layer["wq"], dt).reshape(rows, t, d.heads, d.head_dim)
    k = _product(u, layer["wk"], dt).reshape(rows, t, d.kv_heads, d.head_dim)
    v = _product(u, layer["wv"], dt).reshape(rows, t, d.kv_heads, d.head_dim)
    q = _rope(_rms(q, layer["qn"], d.eps), d)
    k = _rope(_rms(k, layer["kn"], d.eps), d)
    # query head j reads key-value head j // group
    kq = jnp.repeat(k, group, axis=2)
    vq = jnp.repeat(v, group, axis=2)
    sc = (jnp.einsum("rtjd,rsjd->rjts", _rnd(q, dt), _rnd(kq, dt))
          / math.sqrt(d.head_dim))
    sc = jnp.where(np.tril(np.ones((t, t), bool)), sc, -jnp.inf)
    sc = sc - sc.max(-1, keepdims=True)
    p = jnp.exp(sc)
    p = p / p.sum(-1, keepdims=True)
    heads = jnp.einsum("rjts,rsjd->rtjd", _rnd(p, dt), _rnd(vq, dt))
    o = _product(heads.reshape(rows * t, d.heads * d.head_dim), layer["wo"], dt)
    return x + o.reshape(x.shape)


@partial(jax.jit, static_argnums=(2, 3))
def _dense(layer, x, d: Dims, dt):
    u = _rms(x, layer["g2"], d.eps).reshape(-1, d.hidden)
    return x + _swiglu(u, layer["dense"], dt).reshape(x.shape)


def _scores(layer, u, dt):
    """The router's sigmoid scores of ``u`` [positions, hidden], float32."""
    return 1.0 / (1.0 + jnp.exp(-(_rnd(u, dt) @ _rnd(layer["wr"], dt))))


@partial(jax.jit, static_argnums=(2, 3))
def _router_scores(layer, x, d: Dims, dt):
    return _scores(layer, _rms(x, layer["g2"], d.eps).reshape(-1, d.hidden), dt)


def _choose(s, bias, d: Dims):
    """``(experts, weights)`` [positions, top_k] of scores ``s``: the bias
    chooses (equal sums: the lower index), the scores weigh."""
    _, sel = jax.lax.top_k(s + bias, d.top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (w.sum(-1, keepdims=True) + RENORM_EPS) * d.scale


@partial(jax.jit, static_argnums=(2, 3))
def _moe(layer, x, d: Dims, dt):
    """One expert at a time over EVERY position with a mask: a position
    takes expert ``e``'s result, times its weight, iff the router chose
    ``e`` for it."""
    u = _rms(x, layer["g2"], d.eps).reshape(-1, d.hidden)
    sel, w = _choose(_scores(layer, u, dt), layer["rb"], d)
    routed = layer["routed"]

    def one(m, expert):
        e, wg, wu, wd = expert
        chosen = sel == e
        weight = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1, keepdims=True)
        y = _swiglu(u, {"wg": wg, "wu": wu, "wd": wd}, dt)
        return m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(d.experts), routed["wg"], routed["wu"],
                         routed["wd"]))
    return x + m.reshape(x.shape)


@partial(jax.jit, static_argnums=(3, 4))
def _score(params, x, lengths, d: Dims, hidden: bool = False):
    """The logit of each window's last real position; with ``hidden`` the
    final-normed hidden state it is read from."""
    last = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    xl = _rms(x[jnp.arange(x.shape[0]), last], params["gf"], d.eps)
    if hidden:
        return xl
    return jnp.sum(xl * params["head"]["w"][:, 0], axis=-1) + params["head"]["b"][0]
