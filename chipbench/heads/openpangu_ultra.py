"""The ``pangu`` session head's plain reference: openPangu-Ultra-MoE-718B's
decoder block (multi-head latent attention, sandwich norm, a leading
dense layer, a shared expert beside sigmoid-routed experts) over a
session window, given ONE CHIP'S SHARE of the routed experts: its tree
from the seed and its forward pass.

Nothing is imported from the program, and the block below is written
from its published description, not from the program. The arithmetic is
float32 (``jax.numpy`` at ``jax.default_matmul_precision("highest")``; on
the chip's machine that is the chip, in a test the CPU) over weights that
bfloat16 holds exactly, every operand of a product passed through the
rounder. No kernel, no sort, no chunking of pairs: the held experts are a
dense loop with a mask. The sizes are the configuration file's top-level
source keys (``n_routed_experts`` there is what this chip HOLDS; the
router's width is ``head.published.n_routed_experts``).

Per layer ``l``, over the residual stream ``x`` [rows, 16, hidden]
(float32; ``N`` an RMSNorm with ``rms_norm_eps``):

1. ``a = N1(x)``. Query: ``cq = Nq(a Wq_a)`` (``q_lora_rank``); ``q = cq
   Wq_b`` -> heads of ``[q_nope | q_rope]`` (``qk_nope_head_dim``,
   ``qk_rope_head_dim``). Key-value: ``a Wkv_a`` -> ``[ckv | k_rope]``
   (``kv_lora_rank`` + ``qk_rope_head_dim``); ``ckv = Nkv(ckv)``; ``ckv
   Wkv_b`` -> heads of ``[k_nope | v]`` (``v_head_dim``). Rotary
   (``rope_theta``; pair ``i`` is channels ``i`` and ``i + 32``) on
   ``q_rope`` per head and on the one ``k_rope``, which every head
   shares: head ``j``'s key is ``[k_nope_j | k_rope]``. Scores ``q . k /
   sqrt(nope + rope)``, causal, softmax, times ``v``; ``o = concat(heads)
   Wo``. No bias. ``x = x + N2(o)`` (``sandwich_norm``).
2. ``b = N3(x)``; ``x = x + N4(MLP(b))``. For ``l <
   first_k_dense_replace``: ``MLP(b) = (silu(b Wg) * b Wu) Wd`` at
   ``intermediate_size``. Else ``s = sigmoid(b Wr)`` over ALL routed
   experts, the ``num_experts_per_tok`` largest chosen (equal scores: the
   lower index), ``w = s_chosen / (sum s_chosen + 1e-20) x
   routed_scaling_factor``; ``MLP(b) = Shared(b) + sum over the chosen
   experts HELD HERE of w_e Expert_e(b)``, each a SwiGLU of
   ``moe_intermediate_size``. What the absent experts would add is left
   out (model-configs guide, section 4) and the partial result goes on. A
   window's padding (positions past its last real event) is not routed:
   it takes ``Shared(b)`` alone; nothing that is scored can read it.

Output: ``sigmoid(N(x)[last real position] . w_out + b_out)``.

Departures from the published description and what it does not give,
each also under ``head.assumed`` in the configuration file:

- The router: sigmoid scores, no expert groups, no correction bias,
  float32 scores (the family's published code as far as known; the config
  gives no ``scoring_func``, ``n_group`` or ``topk_group``).
- RMSNorm on both latents (``q_a_layernorm`` / ``kv_a_layernorm`` of the
  DeepSeek-V2/V3 lineage this attention comes from).
- Rotary pairs by the rotate-half convention, plain (no scaling) at
  ``rope_theta``.
- Events enter as ``inputs_embeds`` through a projector ``x @ W_in`` (12
  -> hidden); no row of the 153,600-row vocabulary is held, and a
  sequence-classification head (one float32 output column) stands in the
  place of the output head.
- The multi-token-prediction module (``num_nextn_predict_layers`` 1)
  follows the last layer on the last pipeline stage and drafts tokens;
  this chip holds the first layers and the service emits none: absent.
- The latent ``[ckv | k_rope]`` is not cached per account: the service's
  per-slot state is the event window, recomputed every step, so the layer
  is written in its expanded form.
- The seeded tree's scale: every matrix ``fan_in ** -0.5``, the two
  post-norm gains (N2, N4) ``1 / sqrt(2 x 61)`` (the published depth), the
  other gains 1.
- **The seeded projector reads standardised events** (``_standardised``).
  Drawn at ``fan_in ** -0.5`` and fed raw events (a log-amount of 7.6 +-
  1.2, a constant column), it gives every position 96% the same normed
  direction: the routers then choose by margins (a median of 2.5e-4 in
  the score between the 8th and the 9th expert) under the 6e-4 by which
  the program's scores and this file's differ in bfloat16, the two
  disagree on a fifth of the positions a layer, and one disagreement on a
  held expert at the scored position moves that row's probability by
  0.031, past the harness's band around the fold threshold (PERF.md, PR
  36: the driver's seed 1290854970). A trained projector sees features of
  unit spread. So each row of ``W_in`` whose event column varies is
  divided by that column's spread over the plausible windows, and the
  constant column's row carries the means; still one matrix, no bias.
- **The seeded routers are balanced** (``_balanced``). A seeded router is
  not what a trained one is: whatever direction the positions share (all
  of it before the projector was standardised: every position of every
  window chose the same ~10 of 256 experts, 215 never chosen: PERF.md,
  PR 36) makes ``b Wr`` a fixed bias an expert beside the part that
  varies, where training with a balancing loss or
  bias leaves each expert about ``positions x 8 / 256`` rows. The router
  has no bias, so the balance is put into ``Wr`` itself: each column gets
  the multiple of the direction the plausible windows share that evens the
  experts' loads over them (the bias-update rule of loss-free balancing,
  run on the seeded weights). Nothing is added to the block.

A weight of more than 2^24 elements is multiplied a block of its columns
at a time (``_product``: each output element is the same dot product
either way), so that no float32 copy of a whole matrix exists beside the
9 GB the server holds; windows go through in blocks of ``BLOCK_ROWS``.
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
    dense_layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    dense_width: int
    experts: int       # the router's width: every routed expert of a layer
    held: int          # the routed experts this chip holds ...
    first: int         # ... starting with this one
    top_k: int
    expert_width: int
    scale: float
    theta: float
    eps: float


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys; the
    published expert count and the share's first expert from ``head``."""
    head = config.get("head", {})
    if config["n_shared_experts"] != 1 or not config["sandwich_norm"] \
            or not config["norm_topk_prob"]:
        raise ValueError("this reference is written for one shared expert, "
                         "sandwich norm and renormalised top-k weights")
    return Dims(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        heads=config["num_attention_heads"], q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        dense_width=config["intermediate_size"],
        experts=head.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        held=config["n_routed_experts"], first=head.get("first_expert", 0),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        scale=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]))


# -- the tree from the seed ---------------------------------------------------

BLOCK_ELEMS = 1 << 24  # the most elements of a weight handled at once


def post_gain(config: dict) -> float:
    """What the two post-norm gains of a layer start at: ``1 / sqrt(2 x
    layers)`` of the PUBLISHED depth (the depth-scaled sandwich norm)."""
    layers = config.get("head", {}).get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return 1.0 / math.sqrt(2.0 * layers)


# What the shapes of a tree do not give (the router's experts a token, the
# share's first expert, theta, eps, ...): ``forward`` is handed a tree and a
# rounder only, so it reads the sizes of the tree ``make_params`` made last.
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


def make_params(seed: int, config: dict) -> dict:
    """The head's tree (the shape of the program's), built on the device
    in bfloat16 (norm gains and the scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x70616E67), 96))
    hid, f = d.hidden, d.expert_width

    def w(*shape):
        """Fan-in is the axis before the last."""
        return _normal_bf16(next(keys), tuple(shape), 1.0 / math.sqrt(shape[-2]))

    def swiglu(width, *stack):
        return {"wg": w(*stack, hid, width), "wu": w(*stack, hid, width),
                "wd": w(*stack, width, hid)}

    ones = lambda n, value=1.0: jnp.full((n,), value, jnp.float32)
    post = post_gain(config)
    layers = []
    for i in range(d.layers):
        layer = {
            "g1": ones(hid), "g2": ones(hid, post),
            "g3": ones(hid), "g4": ones(hid, post),
            "wq_a": w(hid, d.q_rank), "qn": ones(d.q_rank),
            "wq_b": w(d.q_rank, d.heads * (d.nope + d.rope)),
            "wkv_a": w(hid, d.kv_rank + d.rope), "kvn": ones(d.kv_rank),
            "wkv_b": w(d.kv_rank, d.heads * (d.nope + d.v)),
            "wo": w(d.heads * d.v, hid),
        }
        if i < d.dense_layers:
            layer["dense"] = swiglu(d.dense_width)
        else:
            layer["wr"] = w(hid, d.experts)
            layer["shared"] = swiglu(f)
            layer["routed"] = swiglu(f, d.held)
        layers.append(layer)
    rng = np.random.default_rng([seed & (2**64 - 1), 0x70616E67])
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
    # directions along which these windows spread most (one random
    # direction is now and then nearly orthogonal to what varies from
    # window to window: PERF.md, PR 34).
    n = 8 * BLOCK_ROWS
    win = np.zeros((n, 16, EVENT_WIDTH), F32)
    lengths = rng.integers(4, 17, n)
    win[..., 0] = rng.normal(7.6, 1.2, (n, 16))     # log1p of ~2000 cents
    win[..., 1] = rng.uniform(0.3, 3.0, (n, 16))    # log1p of seconds
    codes = rng.choice(4, size=(n, 16), p=[0.07, 0.03, 0.70, 0.20])
    win[np.arange(n)[:, None], np.arange(16)[None, :],
        2 + TX_EVENT_COL[codes]] = 1.0
    win[..., 10] = 1.0
    win *= (np.arange(16)[None, :] < lengths[:, None])[..., None]
    params["embed"] = _standardised(params["embed"], win, lengths)
    hidden = _balance_and_read(params, win, lengths, d)
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


def _logits(params, windows, lengths, d: Dims, dt) -> np.ndarray:
    out = []
    with jax.default_matmul_precision("highest"):
        for win, lens in _blocks_of(windows, lengths):
            x = _embed(params["embed"], win, dt)
            for layer in params["layers"]:
                x = _attend(layer, x, d, dt)
                x = (_dense(layer, x, d, dt) if "dense" in layer
                     else _moe(layer, x, lens, d, dt))
            out.append(np.asarray(_score(params, x, lens, d, False)))
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


# -- the seeded routers, balanced ---------------------------------------------

BALANCE_TURNS = 200


def _balance_and_read(params, windows, lengths, d: Dims) -> np.ndarray:
    """The plausible windows through the tree in float32, layer by layer
    over all blocks: at each expert layer the router is balanced over the
    real positions it sees (``_balanced``; ``params`` is updated in place)
    before the layer is applied. Returns the final-normed hidden state of
    each window's last real position, which the scoring head is then
    fitted to."""
    f32 = jnp.float32
    blocks = _blocks_of(windows, lengths)
    t = windows.shape[1]
    real = np.concatenate([np.arange(t)[None, :] < np.asarray(lens)[:, None]
                           for _, lens in blocks]).reshape(-1)
    with jax.default_matmul_precision("highest"):
        xs = [_embed(params["embed"], win, f32) for win, _ in blocks]
        for layer in params["layers"]:
            xs = [_attend(layer, x, d, f32) for x in xs]
            if "dense" in layer:
                xs = [_dense(layer, x, d, f32) for x in xs]
                continue
            b = np.concatenate([np.asarray(_router_input(layer, x, d))
                                for x in xs])[real]
            layer["wr"] = _balanced(layer["wr"], b, d.top_k)
            xs = [_moe(layer, x, lens, d, f32) for x, (_, lens) in zip(xs, blocks)]
        hidden = [np.asarray(_score(params, x, lens, d, True))
                  for x, (_, lens) in zip(xs, blocks)]
    return np.concatenate(hidden)[:windows.shape[0]]


def _balanced(wr, b: np.ndarray, top_k: int):
    """``wr`` [hidden, experts] with each column moved along the direction
    the tokens ``b`` [T, hidden] share, by the amount that evens the
    experts' loads over them. With ``c`` the tokens' mean and ``u = b . c /
    c . c`` (about one for every token), ``b (Wr + c beta^T / c . c) = b Wr
    + u beta^T``: ``beta`` acts as a bias an expert. It starts at what
    cancels the shared part of every logit and is then moved as loss-free
    balancing moves its bias: up for an expert under the mean load, down
    for one over it, by a step that shrinks to nothing."""
    w = np.asarray(wr.astype(jnp.float32))
    c = b.mean(axis=0, dtype=np.float64).astype(F32)
    u = (b @ c).astype(np.float64) / float(c @ c)
    logits = (b @ w).astype(np.float64)
    beta = -(c @ w).astype(np.float64)
    experts = w.shape[1]
    mean_load = b.shape[0] * top_k / experts
    step = 0.25 * float((logits + u[:, None] * beta).std())
    for turn in range(BALANCE_TURNS):
        now = logits + u[:, None] * beta
        chosen = np.argpartition(-now, top_k - 1, axis=1)[:, :top_k]
        load = np.bincount(chosen.ravel(), minlength=experts)
        beta += step * (1.0 - turn / BALANCE_TURNS) * np.sign(mean_load - load)
    return jnp.asarray(w + np.outer(c, beta / float(c @ c)).astype(F32),
                       jnp.bfloat16)


@partial(jax.jit, static_argnums=(2,))
def _router_input(layer, x, d: Dims):
    return _rms(x, layer["g3"], d.eps).reshape(-1, d.hidden)


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


def _swiglu(b, w, dt):
    gate = _product(b, w["wg"], dt)
    mid = gate / (1.0 + jnp.exp(-gate)) * _product(b, w["wu"], dt)
    return _product(mid, w["wd"], dt)


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


def _rope(x, d: Dims):
    """Rotary embedding as the family's published code writes it, over all
    ``qk_rope_head_dim`` channels of ``x`` [rows, T, heads, rope]: angles
    ``t x theta ** (-2i / rope)``, ``cat(freqs, freqs)`` over the
    channels, ``x cos + rotate_half(x) sin``."""
    half = d.rope // 2
    inv = d.theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d.rope)
    freqs = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


@partial(jax.jit, static_argnums=(2, 3))
def _attend(layer, x, d: Dims, dt):
    rows, t, _ = x.shape
    a = _rms(x, layer["g1"], d.eps).reshape(rows * t, d.hidden)
    cq = _rms(_product(a, layer["wq_a"], dt), layer["qn"], d.eps)
    q = _product(cq, layer["wq_b"], dt).reshape(rows, t, d.heads, d.nope + d.rope)
    kv = _product(a, layer["wkv_a"], dt)
    ckv = _rms(kv[:, :d.kv_rank], layer["kvn"], d.eps)
    k_rope = _rope(kv[:, d.kv_rank:].reshape(rows, t, 1, d.rope), d)
    kvb = _product(ckv, layer["wkv_b"], dt).reshape(rows, t, d.heads, d.nope + d.v)
    q = jnp.concatenate([q[..., :d.nope], _rope(q[..., d.nope:], d)], axis=-1)
    # every head's key: its own k_nope beside the one shared rotary key
    k = jnp.concatenate(
        [kvb[..., :d.nope],
         jnp.broadcast_to(k_rope, (rows, t, d.heads, d.rope))], axis=-1)
    v = kvb[..., d.nope:]
    sc = (jnp.einsum("rthd,rshd->rhts", _rnd(q, dt), _rnd(k, dt))
          / math.sqrt(d.nope + d.rope))
    sc = jnp.where(np.tril(np.ones((t, t), bool)), sc, -jnp.inf)
    sc = sc - sc.max(-1, keepdims=True)
    p = jnp.exp(sc)
    p = p / p.sum(-1, keepdims=True)
    heads = jnp.einsum("rhts,rshd->rthd", _rnd(p, dt), _rnd(v, dt))
    o = _product(heads.reshape(rows * t, d.heads * d.v), layer["wo"], dt)
    return x + _rms(o, layer["g2"], d.eps).reshape(x.shape)


@partial(jax.jit, static_argnums=(2, 3))
def _dense(layer, x, d: Dims, dt):
    b = _rms(x, layer["g3"], d.eps).reshape(-1, d.hidden)
    m = _swiglu(b, layer["dense"], dt)
    return x + _rms(m, layer["g4"], d.eps).reshape(x.shape)


@partial(jax.jit, static_argnums=(3, 4))
def _moe(layer, x, lengths, d: Dims, dt):
    """The shared expert, and one held expert at a time over EVERY
    position with a mask: a position takes expert ``e``'s result, times
    its weight, iff it holds an event and the router chose ``e`` for it."""
    real = (jnp.arange(x.shape[1])[None, :] < lengths[:, None]).reshape(-1, 1)
    b = _rms(x, layer["g3"], d.eps).reshape(-1, d.hidden)
    logits = _rnd(b, dt) @ _rnd(layer["wr"], dt)
    s = 1.0 / (1.0 + jnp.exp(-logits))
    top_s, top_e = jax.lax.top_k(s, d.top_k)
    top_w = top_s / (top_s.sum(-1, keepdims=True) + 1e-20) * d.scale
    routed = layer["routed"]

    def one(m, expert):
        e, wg, wu, wd = expert
        chosen = (top_e == e) & real
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1, keepdims=True)
        y = _swiglu(b, {"wg": wg, "wu": wu, "wd": wd}, dt)
        return m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0), None

    m, _ = jax.lax.scan(one, _swiglu(b, layer["shared"], dt),
                        (d.first + jnp.arange(d.held), routed["wg"],
                         routed["wu"], routed["wd"]))
    return x + _rms(m, layer["g4"], d.eps).reshape(x.shape)


@partial(jax.jit, static_argnums=(3, 4))
def _score(params, x, lengths, d: Dims, hidden: bool = False):
    """The logit of each window's last real position; with ``hidden`` the
    final-normed hidden state it is read from."""
    last = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    xl = _rms(x[jnp.arange(x.shape[0]), last], params["gf"], d.eps)
    if hidden:
        return xl
    return jnp.sum(xl * params["head"]["w"][:, 0], axis=-1) + params["head"]["b"][0]
