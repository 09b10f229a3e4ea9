"""The ``keye`` session head's plain reference: the language model of
Keye-VL-2.0-30B-A3B (a Qwen3-MoE decoder block with a learned-sparse
attention indexer and M-RoPE) over a session window, its tree from the
seed and its forward pass.

Nothing is imported from the program. The arithmetic is float32
(``jax.numpy`` at ``jax.default_matmul_precision("highest")``, so that
the ~2k rows of a check do not take minutes of numpy; on the chip's
machine that is the chip, in a test the CPU) over weights that bfloat16
holds exactly, every operand of a product passed through the rounder.
The sizes are the configuration file's top-level source keys.

Per layer, over ``h`` [rows, 16, hidden] (eps ``rms_norm_eps``):

1. ``a = RMSNorm(h; g1)``; ``q = a Wq`` [T, 32, 128], ``k = a Wk``,
   ``v = a Wv`` [T, 4, 128], no bias; RMSNorm over the 128 of each head
   of q and k; M-RoPE on q and k: 64 frequency pairs ``theta ** (-2i /
   128)``, pair ``i`` is channels ``i`` and ``i + 64``; pairs 0-15 turn by
   the temporal id, 16-39 by the height id, 40-63 by the width id.
2. Indexer: ``qi = a Wqi`` [T, 16, 64], ``ki = LayerNorm(a Wki)`` [T, 64],
   ``w = a Ww / sqrt(16)``; ``I[t, s] = sum_h w[t, h] relu(qi[t, h] .
   ki[s]) / sqrt(64)`` for ``s <= t``; query ``t`` keeps its ``min(topk,
   t + 1)`` keys of largest ``I`` (equal scores: the earlier key).
3. Query head ``j`` reads key-value head ``j // 8``; ``softmax(q k^T /
   sqrt(128))`` over the kept keys; ``h += concat(heads) Wo``.
4. ``b = RMSNorm(h; g2)``; ``p = softmax(b Wr)`` over all 128 experts;
   the 8 largest with weights ``p_e / sum of the 8``; ``h += sum_e w_e
   Wdown_e(silu(Wgate_e b) * (Wup_e b))``. No token is dropped.

Output: ``sigmoid(RMSNorm(h; gf)[last real position] . w_out + b_out)``.

Departures from the published description, each also under
``head.assumed`` in the configuration file:

- Events enter as ``inputs_embeds`` through a projector ``x @ W_in``
  (12 -> hidden) in the place of the vision tower and the embedding; no
  row of the 151,936-row vocabulary is held. The three M-RoPE id streams
  all equal the event's index, as for text tokens.
- ``q_norm`` / ``k_norm`` are the Qwen3 family's convention; the source's
  config does not list them.
- What ``sa_config`` does not give follows DeepSeek-V3.2's published
  indexer: LayerNorm (scale and bias) on the one key head, rotary on the
  first half of the indexer's channels (16 pairs, the main rotary's first
  16 frequencies, by the temporal id), head weights scaled by ``1 /
  sqrt(indexer_num_heads)``. ``q_chunk_size`` / ``kv_chunk_size`` tile
  the computation and do not change it.
- A sequence-classification head (one output column, kept in float32)
  stands in the place of the output head over the vocabulary.
- Positions after the last real one are not passed through the experts:
  under causal attention they cannot reach the position that is scored.
  (The program computes them; they change nothing.)
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
    experts: int
    top_k: int
    expert_width: int
    idx_heads: int
    idx_dim: int
    idx_topk: int
    sections: tuple
    theta: float
    eps: float


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys."""
    sa = config["sa_config"]
    if config["num_local_experts"] != config["num_experts"]:
        raise ValueError("this reference holds every expert of a layer")
    return Dims(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        idx_heads=sa["indexer_num_heads"], idx_dim=sa["indexer_head_dim"],
        idx_topk=sa["topk"],
        sections=tuple(config["rope_scaling"]["mrope_section"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]))


# -- the tree from the seed ---------------------------------------------------


def out_scale(config: dict) -> float:
    """What the two projections that write into the residual stream
    (``o_proj``, ``down_proj``) are scaled by: ``1 / sqrt(2 x layers)`` of
    the PUBLISHED depth, the scaled initialisation deep decoders are
    trained from. Every other matrix keeps its input's variance
    (``fan_in ** -0.5``, which at 2048 is the family's 0.02)."""
    layers = config.get("head", {}).get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return 1.0 / math.sqrt(2.0 * layers)


# What the shapes of a tree do not give (experts a token, ``topk``, the
# M-RoPE sections, theta, eps): ``forward`` is handed a tree and a rounder
# only, so it reads the sizes of the tree ``make_params`` made last.
_made: dict = {}


@partial(jax.jit, static_argnums=(1, 2))
def _normal_bf16(key, shape, scale):
    """Seeded normals in bfloat16; a stacked weight is drawn one leading
    slice at a time so that no float32 copy of it ever exists."""
    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * scale).astype(jnp.bfloat16)

    if len(shape) == 3:
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    return draw(key, shape)


def make_params(seed: int, config: dict) -> dict:
    """The head's tree (the shape of the program's), built on the device
    in bfloat16 (norm gains and the scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x6B657965), 64))
    hid, hd, f = d.hidden, d.head_dim, d.expert_width

    def w(shape, fan_in, scale=1.0):
        return _normal_bf16(next(keys), tuple(shape), scale / math.sqrt(fan_in))

    out = out_scale(config)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for _ in range(d.layers):
        layers.append({
            "g1": ones(hid), "g2": ones(hid),
            "wq": w((hid, d.heads * hd), hid),
            "wk": w((hid, d.kv_heads * hd), hid),
            "wv": w((hid, d.kv_heads * hd), hid),
            "wo": w((d.heads * hd, hid), d.heads * hd, out),
            "qn": ones(hd), "kn": ones(hd),
            "wqi": w((hid, d.idx_heads * d.idx_dim), hid),
            "wki": w((hid, d.idx_dim), hid),
            "ww": w((hid, d.idx_heads), hid),
            "kin": {"scale": ones(d.idx_dim),
                    "bias": jnp.zeros((d.idx_dim,), jnp.float32)},
            "wr": w((hid, d.experts), hid),
            "wg": w((d.experts, hid, f), hid),
            "wu": w((d.experts, hid, f), hid),
            "wd": w((d.experts, f, hid), f, out),
        })
    rng = np.random.default_rng([seed & (2**64 - 1), 0x6B657965])
    params = {
        "embed": w((EVENT_WIDTH, hid), EVENT_WIDTH),
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
    # half of the warm rows fold, on every seed (as heads/transformer.py).
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
    # The direction the head reads is the one of ``HEAD_CANDIDATES`` seeded
    # directions along which these windows spread most. One random
    # direction is, on one seed in thirty, nearly orthogonal to what varies
    # from window to window; the scale that then spreads the logits by one
    # was 85 where it is 5-14, and every rounding and every router tie with
    # it (a sound run read 0.226 on one row: PERF.md, PR 34).
    hidden = _logits(params, win, lengths, d, jnp.float32, hidden=True)
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


def _logits(params, windows, lengths, d: Dims, dt, hidden: bool = False) -> np.ndarray:
    """In blocks of ``BLOCK_ROWS`` windows (the last one padded with empty
    windows), so that one set of compiled shapes serves any number of rows
    and the temporaries stay at a block's size beside the 5 GB tree."""
    n, t, _ = windows.shape
    pad = -n % BLOCK_ROWS
    windows = np.concatenate([windows, np.zeros((pad, t, EVENT_WIDTH), F32)])
    lengths = np.concatenate([np.asarray(lengths, np.int32),
                              np.ones((pad,), np.int32)])
    out = [_block_logits(params, windows[lo:lo + BLOCK_ROWS],
                         lengths[lo:lo + BLOCK_ROWS], d, dt, hidden)
           for lo in range(0, n + pad, BLOCK_ROWS)]
    return np.concatenate([np.asarray(o) for o in out])[:n]


def _block_logits(params, windows, lengths, d: Dims, dt, hidden: bool):
    rows, t, _ = windows.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    pos3 = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, rows, t))
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], jnp.asarray(windows), dt)
        for layer in params["layers"]:
            h = _attend(layer, h, pos3, d, dt)
            b, top_e, top_w, most = _route(layer, h, real, d, dt)
            cap = max(128, 1 << (int(most) - 1).bit_length())
            y = _experts(b, top_e, top_w, real, layer["wg"], layer["wu"],
                         layer["wd"], cap, dt)
            h = h + y.reshape(h.shape)
        return _score(params, h, lengths, d, hidden)


def _rnd(a, dt):
    """``a`` rounded to ``dt`` and back in float32. The barrier keeps the
    compiler from dropping the pair of conversions: XLA may keep "excess
    precision" and does on a TPU, where the bfloat16 reference then sat
    0.6-0.85 of a rounding away from the program (PERF.md, PR 34)."""
    if dt == jnp.float32 or a.dtype == dt:
        return a.astype(jnp.float32)
    return jax.lax.optimization_barrier(a.astype(dt)).astype(jnp.float32)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


def _mrope(x, pos3, d: Dims, pairs: int | None = None):
    """Rotary embedding as the family's published code writes it: the
    angles of the three id streams, ``cat(freqs, freqs)`` over the
    channels, sections ``[16, 24, 24, 16, 24, 24]`` taking the streams in
    turn, ``x cos + rotate_half(x) sin``. With ``pairs`` only the first
    ``2 * pairs`` channels turn, at the first ``pairs`` frequencies."""
    half = d.head_dim // 2
    inv = d.theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d.head_dim)
    freqs = pos3.astype(jnp.float32)[..., None] * jnp.asarray(inv, jnp.float32)
    bounds = np.cumsum((0,) + d.sections)
    chosen = jnp.concatenate(
        [freqs[i % 3, ..., lo:hi]
         for i, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:]))], axis=-1)
    if pairs is not None:
        chosen, half = chosen[..., :pairs], pairs
    emb = jnp.concatenate([chosen, chosen], axis=-1)[:, :, None, :]
    turned, rest = x[..., :2 * half], x[..., 2 * half:]
    rotated = jnp.concatenate([-turned[..., half:], turned[..., :half]], axis=-1)
    return jnp.concatenate(
        [turned * jnp.cos(emb) + rotated * jnp.sin(emb), rest], axis=-1)


@partial(jax.jit, static_argnums=(3, 4))
def _attend(layer, h, pos3, d: Dims, dt):
    rows, t, _ = h.shape
    group = d.heads // d.kv_heads
    a = _rms(h, layer["g1"], d.eps)
    ar = _rnd(a, dt)
    q = (ar @ _rnd(layer["wq"], dt)).reshape(rows, t, d.heads, d.head_dim)
    k = (ar @ _rnd(layer["wk"], dt)).reshape(rows, t, d.kv_heads, d.head_dim)
    v = (ar @ _rnd(layer["wv"], dt)).reshape(rows, t, d.kv_heads, d.head_dim)
    q = _mrope(_rms(q, layer["qn"], d.eps), pos3, d)
    k = _mrope(_rms(k, layer["kn"], d.eps), pos3, d)

    # the indexer's scores and the keys each query keeps
    qi = (ar @ _rnd(layer["wqi"], dt)).reshape(rows, t, d.idx_heads, d.idx_dim)
    ki = ar @ _rnd(layer["wki"], dt)
    mu = ki.mean(-1, keepdims=True)
    ki = ((ki - mu) / jnp.sqrt(((ki - mu) ** 2).mean(-1, keepdims=True) + d.eps)
          * layer["kin"]["scale"] + layer["kin"]["bias"])
    qi = _mrope(qi, pos3, d, pairs=d.idx_dim // 4)
    ki = _mrope(ki[:, :, None, :], pos3, d, pairs=d.idx_dim // 4)[:, :, 0, :]
    w = (ar @ _rnd(layer["ww"], dt)) / math.sqrt(d.idx_heads)
    dots = jnp.einsum("rthd,rsd->rths", _rnd(qi, dt), _rnd(ki, dt))
    index = (jnp.einsum("rth,rths->rts", w, jnp.maximum(dots, 0.0))
             / math.sqrt(d.idx_dim))
    causal = np.tril(np.ones((t, t), bool))
    index = jnp.where(causal, index, -jnp.inf)
    # rank of every key among a query's keys, best first, earlier key first
    # among equals; a query keeps ranks below min(topk, t + 1)
    order = jnp.argsort(-index, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    budget = np.minimum(d.idx_topk, np.arange(t) + 1)[None, :, None]
    keep = (rank < budget) & causal

    # query head j reads key-value head j // group
    kq = jnp.repeat(k, group, axis=2)
    vq = jnp.repeat(v, group, axis=2)
    sc = (jnp.einsum("rtjd,rsjd->rjts", _rnd(q, dt), _rnd(kq, dt))
          / math.sqrt(d.head_dim))
    sc = jnp.where(keep[:, None], sc, -jnp.inf)
    sc = sc - sc.max(-1, keepdims=True)
    p = jnp.exp(sc)
    p = p / p.sum(-1, keepdims=True)
    heads = jnp.einsum("rjts,rsjd->rtjd", _rnd(p, dt), _rnd(vq, dt))
    heads = heads.reshape(rows, t, d.heads * d.head_dim)
    return h + _rnd(heads, dt) @ _rnd(layer["wo"], dt)


@partial(jax.jit, static_argnums=(3, 4))
def _route(layer, h, real, d: Dims, dt):
    b = _rms(h, layer["g2"], d.eps).reshape(-1, d.hidden)
    logits = _rnd(b, dt) @ _rnd(layer["wr"], dt)
    logits = logits - logits.max(-1, keepdims=True)
    p = jnp.exp(logits)
    p = p / p.sum(-1, keepdims=True)
    top_p, top_e = jax.lax.top_k(p, d.top_k)
    top_w = top_p / top_p.sum(-1, keepdims=True)
    chosen = (top_e[..., None] == jnp.arange(d.experts)) & real[:, None, None]
    return b, top_e, top_w, chosen.sum((0, 1)).max()


@partial(jax.jit, static_argnums=(7, 8))
def _experts(b, top_e, top_w, real, wg, wu, wd, cap: int, dt):
    """One expert at a time over the (position, expert) pairs of the real
    positions, laid out expert by expert: expert ``e`` reads the ``cap``
    rows that start where its pairs start (``cap`` is at least the largest
    count of any expert, so its own pairs are all among them), puts them
    through its three products and writes the ``cap`` results back. What
    it writes past its own pairs belongs to later experts, which write
    their own results over it in their turn. A position's result is the
    weighted sum of its pairs' rows. No pair is left out: ``cap`` only
    sets how many rows a step computes."""
    n, k = top_e.shape
    experts = wg.shape[0]
    # pairs of padded positions sort behind every expert's and are not read
    flat_e = jnp.where(real[:, None], top_e, experts).reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    place = jnp.argsort(order, stable=True)          # pair -> its row
    starts = jnp.sum(flat_e[None, :] < jnp.arange(experts)[:, None], axis=1)
    x = jnp.concatenate([_rnd(b, dt)[order // k],
                         jnp.zeros((cap, b.shape[1]), jnp.float32)])

    def one(out, expert):
        start, g, u, dn = expert
        rows = jax.lax.dynamic_slice_in_dim(x, start, cap)
        gate = rows @ _rnd(g, dt)
        mid = gate / (1.0 + jnp.exp(-gate)) * (rows @ _rnd(u, dt))
        res = _rnd(mid, dt) @ _rnd(dn, dt)
        return jax.lax.dynamic_update_slice_in_dim(out, res, start, 0), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (starts, wg, wu, wd))
    pairs = out[place].reshape(n, k, -1)
    weight = jnp.where(real[:, None], top_w, 0.0)
    return jnp.sum(jnp.where(weight[..., None] > 0, pairs, 0.0)
                   * weight[..., None], axis=1)


@partial(jax.jit, static_argnums=(3, 4))
def _score(params, h, lengths, d: Dims, hidden: bool = False):
    """The logit of each window's last real position; with ``hidden`` the
    final-normed hidden state it is read from."""
    last = jnp.clip(lengths - 1, 0, h.shape[1] - 1)
    hl = h[jnp.arange(h.shape[0]), last]
    hl = _rms(hl, params["gf"], d.eps)
    if hidden:
        return hl
    return jnp.sum(hl * params["head"]["w"][:, 0], axis=-1) + params["head"]["b"][0]
