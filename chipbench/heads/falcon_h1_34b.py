"""The ``falconh1`` session head's plain reference: Falcon-H1-34B-Instruct's
decoder block (a Mamba-2 state-space mixer beside grouped-query attention on
one normed input in every layer, the model's muP multipliers on the
branches, a dense SwiGLU) over a session window: its tree from the seed and
its forward pass.

Nothing is imported from the program, and the block below is written from
the family's published modelling code (``transformers``'
``modeling_falcon_h1.py``, its plain torch path), not from the program. The
arithmetic is float32 (``jax.numpy`` at
``jax.default_matmul_precision("highest")``; on the chip's machine that is
the chip, in a test the CPU) over weights that bfloat16 holds exactly. Every
operand of a PROJECTION, of the MLP's three products and of attention's two
is passed through the rounder; the state-space core, the convolution, the
gate, the norms, the softmax and the multipliers are float32 on operands
that are not rounded (the published code keeps them in float32). **The
state-space core is the recurrence itself**, position by position over a
state of ``heads x head_dim x state`` a window that is held explicitly: no
dual form, no chunk, no kernel. The sizes are the configuration file's
top-level source keys.

With ``N(.)`` an RMSNorm with a learned gain and ``rms_norm_eps``, every
layer over the stream ``h`` [rows, 16, hidden] of a window (positions ``t``
= 0..15, causal, each window alone):

1. ``u = N_in(h)``, which both mixers read.

   - **attention**: ``a = u * attention_in_multiplier``; ``q = a Wq`` as
     ``num_attention_heads`` heads of ``head_dim``, ``k = (a Wk) *
     key_multiplier``, ``v = a Wv`` as ``num_key_value_heads``; rotate-half
     rotary on all ``head_dim`` channels (``rope_theta``, position = the
     event's index); causal softmax of ``q k^T / sqrt(head_dim)``, five
     query heads to a key-value head; ``A = concat(heads) Wo *
     attention_out_multiplier``. No bias, no head norm.
   - **state space**: ``s = u * ssm_in_multiplier``; ``p = (s W_in) * m``
     (``W_in`` hidden x 9248, no bias) with ``m`` the muP vector,
     ``ssm_multipliers[0..4]`` over the column segments ``[z: mamba_d_ssm |
     x: mamba_d_ssm | B: groups x state | C: groups x state | dt: heads]``
     in that order. ``[x | B | C]`` pass a depthwise causal convolution of
     ``mamba_d_conv`` taps with a bias (zero before the window's first
     event), then ``silu``. ``x`` is ``mamba_n_heads`` heads of
     ``mamba_d_head``; ``B`` and ``C`` are ``mamba_n_groups`` groups of
     ``mamba_d_state``, head ``j`` reads group ``j // (heads / groups)``.
     ``dt = softplus(p_dt + dt_bias)`` a head, ``A = -exp(A_log)`` a head.
     A head's state, ``mamba_d_head x mamba_d_state``: ``H_t = exp(dt_t A)
     H_{t-1} + dt_t x_t (x) B_t`` from ``H_{-1} = 0``; ``y_t = H_t C_t + D
     x_t``. Gate, then norm (``mamba_rms_norm`` true,
     ``mamba_norm_before_gate`` false): ``g = y * silu(z)``, an RMSNorm
     over each group's ``mamba_d_ssm / groups`` channels with a gain of
     ``mamba_d_ssm``; ``S = (g' W_out) * ssm_out_multiplier``.

2. ``r = h + (S + A)``.
3. ``f = N_ff(r)``; ``h' = r + ((silu((f Wg) * mlp_multipliers[0]) * (f
   Wu)) Wd) * mlp_multipliers[1]`` at ``intermediate_size``.

Events enter through the projector times ``embedding_multiplier``; after the
last layer one more RMSNorm (``final_layernorm``). Output:
``sigmoid((N(h)[last real position] . w_out) * lm_head_multiplier +
b_out)``.

Departures from the published model and what its config does not give,
each also under ``head.assumed`` in the configuration file:

- Events enter as ``inputs_embeds`` through a projector ``x @ W_in`` (12 ->
  hidden); no row of the 261,120-row vocabulary is held, and a
  sequence-classification head (one float32 output column) stands in the
  place of the output head. Position ids are the event's index.
- ``lm_head_multiplier`` scales that column's product as the model scales
  its output head's logits; the scoring head's bias is added after it.
- ``time_step_limit`` is not a key of the config; the modelling code's
  default is ``(0, inf)``, so ``dt`` is not clipped.
- No state, convolution or key-value cache is held per account: the
  service's per-slot state is the event window, recomputed every step, and
  every window starts from ``H_{-1} = 0`` and a zero convolution history.
- Padding (positions past a window's last real event) is computed like any
  position; the convolution, the recurrence and the attention mask are
  causal, so nothing that is scored can read it. (The modelling code zeroes
  padded positions' inputs to the mixer; behind a causal mixer that
  changes no scored value.)
- Norm gains, the convolution's taps and bias, ``A_log``, ``D``,
  ``dt_bias`` and the scoring head are float32 at rest.
- **The seeded tree's scale.** The multipliers are applied as published
  (5.66 on the embedding, 0.0375 / 0.088 / 0.011 on the branches'
  outputs), so a tree drawn at ``fan_in ** -0.5`` would leave the stream
  all embedding and the layers invisible to the output check. Every matrix
  is therefore drawn for the multiplier that follows it: it keeps its
  input's variance THROUGH that multiplier (``fan_in ** -0.5`` over the
  multiplier; each segment of ``W_in`` over ``ssm_in_multiplier`` times its
  own entry of ``ssm_multipliers``), and ``Wo``, ``W_out`` and ``Wd``,
  which write into the residual stream, carry ``1 / sqrt(2 x 72)`` besides
  (the published depth), as the other heads' files do. The taps are ``4 **
  -0.5`` and the convolution's bias a quarter of a unit.
- ``A_log``, ``D`` and ``dt_bias`` of the seeded tree are Mamba-2's
  reference initialisation: ``dt`` log-uniform in [0.001, 0.1] and
  ``dt_bias`` its inverse softplus, ``A`` uniform in [1, 16], ``D`` one.
- **The seeded projector reads standardised events** (``_standardised``,
  as ``heads/openpangu_ultra.py``: PERF.md, PR 36): each row of ``W_in``
  whose event column varies is divided by that column's spread over
  plausible windows and the constant column's row carries the means; one
  matrix, no bias.

A weight of more than 2^24 elements is multiplied a block of its columns
at a time (``_product``: each output element is the same dot product
either way); windows go through in blocks of ``BLOCK_ROWS``, which also
bounds the explicit state (4.19 MB a window a layer at the published
widths).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL


class Dims(NamedTuple):
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    state: int
    groups: int
    taps: int
    chunk: int
    dense_width: int
    embedding: float
    attn_in: float
    attn_out: float
    key: float
    ssm_in: float
    ssm_out: float
    ssm: tuple      # over [z | x | B | C | dt]
    mlp: tuple      # (gate, down)
    lm_head: float
    theta: float
    eps: float

    @property
    def ssm_width(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def segments(self) -> tuple:
        """The columns of ``W_in``: ``[z | x | B | C | dt]``."""
        bc = self.groups * self.state
        return (self.ssm_width, self.ssm_width, bc, bc, self.ssm_heads)


def dims_of(config: dict) -> Dims:
    """The sizes and the multipliers, from the configuration file's
    top-level source keys; a key that is missing is a ``KeyError``."""
    if (not config["mamba_conv_bias"] or config["mamba_proj_bias"]
            or not config["mamba_rms_norm"] or config["mamba_norm_before_gate"]
            or config["attention_bias"] or config["mlp_bias"]
            or config["projectors_bias"] or config["hidden_act"] != "silu"
            or config["rope_scaling"] is not None):
        raise ValueError("this reference is written for a convolution with a "
                         "bias, projections without, a gate before a grouped "
                         "RMSNorm, silu and an unscaled rotary")
    if config["mamba_d_ssm"] != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is mamba_n_heads heads of mamba_d_head")
    if len(config["ssm_multipliers"]) != 5 or len(config["mlp_multipliers"]) != 2:
        raise ValueError("ssm_multipliers has one entry a segment of [z | x | "
                         "B | C | dt], mlp_multipliers one for the gate and "
                         "one for the down projection")
    return Dims(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        ssm_heads=config["mamba_n_heads"], ssm_head_dim=config["mamba_d_head"],
        state=config["mamba_d_state"], groups=config["mamba_n_groups"],
        taps=config["mamba_d_conv"], chunk=config["mamba_chunk_size"],
        dense_width=config["intermediate_size"],
        embedding=float(config["embedding_multiplier"]),
        attn_in=float(config["attention_in_multiplier"]),
        attn_out=float(config["attention_out_multiplier"]),
        key=float(config["key_multiplier"]),
        ssm_in=float(config["ssm_in_multiplier"]),
        ssm_out=float(config["ssm_out_multiplier"]),
        ssm=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp=tuple(float(m) for m in config["mlp_multipliers"]),
        lm_head=float(config["lm_head_multiplier"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]))


# -- the tree from the seed ---------------------------------------------------

BLOCK_ELEMS = 1 << 24  # the most elements of a weight handled at once
DT_RANGE = (1e-3, 1e-1)  # Mamba-2's reference initialisation
A_RANGE = (1.0, 16.0)


def out_scale(config: dict) -> float:
    """What the projections that write into the residual stream are scaled
    by: ``1 / sqrt(2 x layers)`` of the PUBLISHED depth."""
    layers = config.get("head", {}).get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return 1.0 / math.sqrt(2.0 * layers)


# What the shapes of a tree do not give (the heads, the multipliers, theta,
# eps, ...): ``forward`` is handed a tree and a rounder only, so it reads
# the sizes of the tree ``make_params`` made last.
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
    """Seeded normals in bfloat16; a large matrix is drawn one block of
    rows at a time, so that no float32 copy of it ever exists."""
    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * scale).astype(jnp.bfloat16)

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
    in bfloat16 (norm gains, taps and their bias, ``A_log``, ``D``,
    ``dt_bias`` and the scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x66683134), 96))
    rng = np.random.default_rng([seed & (2**64 - 1), 0x66683134])
    hid, hd = d.hidden, d.head_dim
    out = out_scale(config)

    def w(rows, cols, through=1.0, scale=1.0):
        """``fan_in ** -0.5`` (the fan-in is the rows) over the multiplier
        the product goes ``through``."""
        return _normal_bf16(next(keys), (rows, cols),
                            scale / (math.sqrt(rows) * through))

    ones = lambda n: jnp.ones((n,), jnp.float32)
    conv_dim = sum(d.segments[1:4])
    layers = []
    for _ in range(d.layers):
        dt = np.exp(rng.uniform(math.log(DT_RANGE[0]), math.log(DT_RANGE[1]),
                                d.ssm_heads))
        layers.append({
            "g1": ones(hid), "g2": ones(hid),
            "wq": w(hid, d.heads * hd, d.attn_in),
            "wk": w(hid, d.kv_heads * hd, d.attn_in * d.key),
            "wv": w(hid, d.kv_heads * hd, d.attn_in),
            "wo": w(d.heads * hd, hid, d.attn_out, out),
            "w_in": jnp.concatenate([w(hid, n, d.ssm_in * m)
                                     for n, m in zip(d.segments, d.ssm)], axis=1),
            "taps": jnp.asarray(rng.standard_normal((conv_dim, d.taps))
                                / math.sqrt(d.taps), jnp.float32),
            "conv_b": jnp.asarray(0.25 * rng.standard_normal(conv_dim),
                                  jnp.float32),
            # softplus(dt_bias) = dt
            "dt_bias": jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),
            "a_log": jnp.asarray(np.log(rng.uniform(*A_RANGE, d.ssm_heads)),
                                 jnp.float32),
            "d_skip": ones(d.ssm_heads),
            "gn": ones(d.ssm_width),
            "w_out": w(d.ssm_width, hid, d.ssm_out, out),
            "dense": {"wg": w(hid, d.dense_width, d.mlp[0]),
                      "wu": w(hid, d.dense_width),
                      "wd": w(d.dense_width, hid, d.mlp[1], out)},
        })
    params = {
        "embed": w(EVENT_WIDTH, hid, d.embedding),
        "layers": layers,
        "gf": ones(hid),
        "head": {"w": jnp.asarray(rng.standard_normal((hid, 1))
                                  / (math.sqrt(hid) * d.lm_head), jnp.float32),
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
    hidden = _logits(params, win, lengths, d, jnp.float32, hidden=True)
    candidates = rng.standard_normal((hid, HEAD_CANDIDATES)) / math.sqrt(hid)
    spread = (hidden.astype(np.float64) @ candidates).std(axis=0)
    w_out = candidates[:, int(np.argmax(spread))]
    logits = hidden.astype(np.float64) @ w_out
    gain = 1.0 / max(float(logits.std()), 1e-6)
    centre = math.log(FLAG_THRESHOLD / (1.0 - FLAG_THRESHOLD))
    # the column's product is scaled by lm_head_multiplier on its way out
    params["head"] = {
        "w": jnp.asarray(w_out[:, None] * (gain / d.lm_head), jnp.float32),
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
    number of rows and the temporaries (the explicit state among them) stay
    at a block's size beside the resident tree."""
    n, t, _ = windows.shape
    pad = -n % BLOCK_ROWS
    windows = np.concatenate([windows, np.zeros((pad, t, EVENT_WIDTH), F32)])
    lengths = np.concatenate([np.asarray(lengths, np.int32),
                              np.ones((pad,), np.int32)])
    return [(jnp.asarray(windows[lo:lo + BLOCK_ROWS]),
             jnp.asarray(lengths[lo:lo + BLOCK_ROWS]))
            for lo in range(0, n + pad, BLOCK_ROWS)]


def _layer(layer, x, d: Dims, dt):
    """One decoder layer over ``x`` [rows, T, hidden]: both mixers read one
    normed input and both are added to the stream, then the MLP."""
    return _mlp(layer, _mixers(layer, x, d, dt), d, dt)


def _logits(params, windows, lengths, d: Dims, dt, hidden: bool = False) -> np.ndarray:
    """The pre-sigmoid score of every window; with ``hidden`` the
    final-normed hidden state of its last real position instead."""
    out = []
    with jax.default_matmul_precision("highest"):
        for win, lens in _blocks_of(windows, lengths):
            x = _embed(params["embed"], win, d, dt)
            for layer in params["layers"]:
                x = _layer(layer, x, d, dt)
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


def _rnd(a, dt):
    """``a`` rounded to ``dt`` and back in float32. The barrier keeps the
    compiler from dropping the pair of conversions: XLA may keep "excess
    precision" and does on a TPU (PERF.md, PR 34)."""
    if dt == jnp.float32 or a.dtype == dt:
        return a.astype(jnp.float32)
    return jax.lax.optimization_barrier(a.astype(dt)).astype(jnp.float32)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


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


@partial(jax.jit, static_argnums=(2, 3))
def _embed(w_in, windows, d: Dims, dt):
    return (_rnd(windows, dt) @ _rnd(w_in, dt)) * d.embedding


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


def _attend(layer, u, d: Dims, dt):
    """The attention branch over the normed input ``u`` [rows, T, hidden]
    -> [rows, T, hidden], its two multipliers and the keys' applied."""
    rows, t, hid = u.shape
    group = d.heads // d.kv_heads
    a = (u * d.attn_in).reshape(rows * t, hid)
    q = _product(a, layer["wq"], dt).reshape(rows, t, d.heads, d.head_dim)
    k = (_product(a, layer["wk"], dt) * d.key).reshape(rows, t, d.kv_heads,
                                                       d.head_dim)
    v = _product(a, layer["wv"], dt).reshape(rows, t, d.kv_heads, d.head_dim)
    q, k = _rope(q, d), _rope(k, d)
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
    return o.reshape(u.shape) * d.attn_out


def _causal_conv(x, taps, bias):
    """The depthwise causal convolution with a bias over ``x`` [rows, T,
    channels]: tap ``k`` of ``taps`` [channels, L] reads the event ``L - 1
    - k`` before, zero before the window's first event."""
    rows, t, ch = x.shape
    n_taps = taps.shape[1]
    conv = jnp.zeros_like(x) + bias
    for k in range(n_taps):
        back = n_taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros((rows, back, ch), x.dtype), x[:, :t - back]], axis=1)
        conv = conv + taps[:, k] * shifted
    return conv


def _recurrence(x, bm, cm, dt_, a, d_skip):
    """The selective scan, position by position: ``x`` [rows, T, heads,
    head_dim], ``bm`` and ``cm`` [rows, T, heads, state] (each head its
    group's), ``dt_`` [rows, T, heads], ``a`` and ``d_skip`` [heads] ->
    ``y`` [rows, T, heads, head_dim]. The state [rows, heads, head_dim,
    state] starts at zero and is held through the window."""
    rows, _, heads, hd = x.shape

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state + ((dt_t[..., None] * x_t)[..., None]
                                 * b_t[..., None, :])
        y_t = jnp.einsum("rhpn,rhn->rhp", state, c_t) + d_skip[:, None] * x_t
        return state, y_t

    first = lambda v: jnp.moveaxis(v, 1, 0)
    _, ys = jax.lax.scan(step, jnp.zeros((rows, heads, hd, bm.shape[-1]), x.dtype),
                         (first(x), first(bm), first(cm), first(dt_)))
    return jnp.moveaxis(ys, 0, 1)


def _state_space(layer, u, d: Dims, dt):
    """The Mamba-2 branch over the normed input ``u`` [rows, T, hidden] ->
    [rows, T, hidden], its multipliers applied."""
    rows, t, hid = u.shape
    width, _, bc, _, nh = d.segments
    s = (u * d.ssm_in).reshape(rows * t, hid)
    # W_in's columns a segment at a time, each times its own multiplier
    at = np.cumsum((0,) + d.segments)
    z, x, bm, cm, p_dt = (
        _product(s, layer["w_in"][:, lo:hi], dt).reshape(rows, t, hi - lo) * m
        for lo, hi, m in zip(at[:-1], at[1:], d.ssm))
    xbc = _silu(_causal_conv(jnp.concatenate([x, bm, cm], axis=-1),
                             layer["taps"], layer["conv_b"]))
    x = xbc[..., :width].reshape(rows, t, nh, d.ssm_head_dim)
    per_group = nh // d.groups  # head j reads group j // per_group
    bm = jnp.repeat(xbc[..., width:width + bc].reshape(rows, t, d.groups, d.state),
                    per_group, axis=2)
    cm = jnp.repeat(xbc[..., width + bc:].reshape(rows, t, d.groups, d.state),
                    per_group, axis=2)
    dt_ = jnp.log1p(jnp.exp(p_dt + layer["dt_bias"]))  # softplus; not clipped
    y = _recurrence(x, bm, cm, dt_, -jnp.exp(layer["a_log"]), layer["d_skip"])
    # the gate, then an RMSNorm over each group's channels
    g = (y.reshape(rows, t, width) * _silu(z)).reshape(rows, t, d.groups, -1)
    g = _rms(g, layer["gn"].reshape(d.groups, -1), d.eps)
    out = _product(g.reshape(rows * t, width), layer["w_out"], dt)
    return out.reshape(u.shape) * d.ssm_out


@partial(jax.jit, static_argnums=(2, 3, 4))
def _mixers(layer, x, d: Dims, dt, only: str | None = None):
    """``x + (S + A)`` with both branches on ``N_in(x)``; ``only`` (a test's
    handle) leaves one branch out: ``"ssm"`` or ``"attention"``."""
    u = _rms(x, layer["g1"], d.eps)
    s = 0.0 if only == "attention" else _state_space(layer, u, d, dt)
    a = 0.0 if only == "ssm" else _attend(layer, u, d, dt)
    return x + (s + a)


@partial(jax.jit, static_argnums=(2, 3))
def _mlp(layer, x, d: Dims, dt):
    w = layer["dense"]
    f = _rms(x, layer["g2"], d.eps).reshape(-1, d.hidden)
    gate = _product(f, w["wg"], dt) * d.mlp[0]
    mid = _silu(gate) * _product(f, w["wu"], dt)
    return x + (_product(mid, w["wd"], dt) * d.mlp[1]).reshape(x.shape)


@partial(jax.jit, static_argnums=(3, 4))
def _score(params, x, lengths, d: Dims, hidden: bool = False):
    """The logit of each window's last real position; with ``hidden`` the
    final-normed hidden state it is read from."""
    last = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    xl = _rms(x[jnp.arange(x.shape[0]), last], params["gf"], d.eps)
    if hidden:
        return xl
    return (jnp.sum(xl * params["head"]["w"][:, 0], axis=-1) * d.lm_head
            + params["head"]["b"][0])
