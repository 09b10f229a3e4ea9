"""The ``mellum`` session head's plain reference: Mellum2-12B-A2.5B-
Instruct's decoder block (grouped-query attention that is sliding-window or
full by ``layer_types``, a rotary table a kind, 64 softmax-routed experts
with 8 a token and no shared expert) over a session window, its tree from
the seed and its forward pass.

Nothing is imported from the program. The arithmetic is float32
(``jax.numpy`` at ``jax.default_matmul_precision("highest")``; on the
chip's machine that is the chip, in a test the CPU) over weights that
bfloat16 holds exactly, every operand of a product passed through the
rounder. No kernel and no skipped block: every query meets EVERY key of its
window and the mask decides, a block of queries at a time so that the 32 x
4096 x 4096 scores of a layer never stand at once. The sizes are the
configuration file's top-level source keys.

Per layer, over ``h`` [rows, T, hidden] (eps ``rms_norm_eps``; ``kind`` is
the layer's entry in ``layer_types``):

1. ``a = RMSNorm(h; g1)``; ``q = a Wq`` [T, 32, 128], ``k = a Wk``, ``v = a
   Wv`` [T, 4, 128], no bias; RMSNorm over the 128 of each head of q and k.
2. Rotary over the whole head, rotate-half pairs ``(c, c + 64)``, position =
   the event's index, with the table ``rope_parameters[kind]``:
   ``sliding_attention`` (``rope_type`` default): ``inv_freq_c = theta^(-2c /
   128)``, cos and sin as they are. ``full_attention`` (``rope_type`` yarn):
   ``inv_freq_c (1 - r_c) + inv_freq_c / factor r_c`` with ``r_c = clip((c -
   low) / (high - low), 0, 1)``, ``low = floor(d(beta_fast))``, ``high =
   ceil(d(beta_slow))``, ``d(b) = 128 ln(original_max_position_embeddings /
   (2 pi b)) / (2 ln theta)`` (low 18, high 35 here), and cos and sin BOTH
   multiplied by ``attention_factor`` (a score is scaled by its square).
3. Query head ``j`` reads key-value head ``j // 8``; ``s_ij = q_i . k_j /
   sqrt(128)``, kept where ``j <= i`` and, in a sliding layer, ``i - j <
   sliding_window``; softmax; ``h += concat(heads) Wo``.
4. ``b = RMSNorm(h; g2)``; ``p = softmax(b Wr)`` over all 64 experts; the 8
   largest with weights ``p_e / sum of the 8`` (``norm_topk_prob``); ``h +=
   sum_e w_e Wdown_e(silu(Wgate_e b) * (Wup_e b))``. No token is dropped;
   every layer is sparse (``mlp_layer_types``).

Output: ``sigmoid(RMSNorm(h; gf)[last real position] . w_out + b_out)``.

Departures from the published description and what it does not give, each
also under ``head.assumed`` in the configuration file:

- ``q_norm`` / ``k_norm`` are the Qwen3-MoE family's convention (the
  config's key set is that family's); the config does not list them.
- The mask convention of ``sliding_window`` (query ``i`` keeps key ``j``
  where ``0 <= i - j < sliding_window``) and YaRN's formula are the
  transformers library's; the config gives the numbers only.
- Events enter as ``inputs_embeds`` through a projector ``x @ W_in`` (12 ->
  hidden, seeded so that it reads each event column standardised:
  ``_standardised``) in the embedding's place; no row of the 98,304-row vocabulary and
  no multi-token-prediction module is held. ``intermediate_size`` sizes
  nothing: no layer of the source is dense.
- A sequence-classification head (one output column, kept in float32)
  stands in the place of the output head over the vocabulary.
- The seeded tree's head norms on q and k carry a gain of ``QK_GAIN`` (2),
  so that a softmax over thousands of keys concentrates as a trained
  model's does; at unit gains it is flat and the band changes no answer.
- Positions after the last real one are not passed through the experts:
  under causal attention they cannot reach the position that is scored.
  (The program computes them; they change nothing.)

``WITHOUT_BAND`` (False) is the proof's switch, never the benchmark's: set,
the sliding layers keep every causal key, and rows whose windows are deeper
than ``sliding_window`` then leave the program's answers (chipbench/aa/proof).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL

SLIDING, FULL = "sliding_attention", "full_attention"
WITHOUT_BAND = False


class Dims(NamedTuple):
    hidden: int
    kinds: tuple       # one entry a layer
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    expert_width: int
    band: int
    rope: tuple        # ((kind, inv_freq as a tuple, factor on cos and sin), ...)
    eps: float
    events: int        # the deployment's window


def inv_freq(rope: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """One ``rope_parameters`` group -> (the ``head_dim / 2`` rates a
    position turns a pair by, the factor on cos and sin)."""
    c = np.arange(head_dim // 2, dtype=np.float64)
    theta = float(rope["rope_theta"])
    plain = theta ** (-2.0 * c / head_dim)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def pair_of(turns: float) -> float:
        return (head_dim * math.log(rope["original_max_position_embeddings"]
                                    / (turns * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((c - low) / (high - low), 0.0, 1.0)
    return (plain / rope["factor"] * ramp + plain * (1.0 - ramp),
            float(rope["attention_factor"]))


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys."""
    kinds = tuple(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types has one entry a layer")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("this reference's layers are all sparse")
    if not config["use_sliding_window"] or not config["norm_topk_prob"]:
        raise ValueError("written for use_sliding_window and norm_topk_prob")
    hd = config["head_dim"]
    rope = tuple((kind, tuple(rates), factor) for kind, (rates, factor) in (
        (kind, inv_freq(config["rope_parameters"][kind], hd))
        for kind in (SLIDING, FULL)))
    return Dims(
        hidden=config["hidden_size"], kinds=kinds,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=hd,
        experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        band=config["sliding_window"], rope=rope,
        eps=float(config["rms_norm_eps"]),
        events=int(config.get("env", {}).get("SESSION_EVENTS", 16)))


# -- the tree from the seed ---------------------------------------------------


def out_scale(config: dict) -> float:
    """What the two projections that write into the residual stream
    (``o_proj``, ``down_proj``) are scaled by: ``1 / sqrt(2 x layers)`` of
    the PUBLISHED depth, the scaled initialisation deep decoders are
    trained from. Every other matrix keeps its input's variance."""
    layers = config.get("head", {}).get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return 1.0 / math.sqrt(2.0 * layers)


# What the shapes of a tree do not give (the layers' kinds, experts a token,
# the band, the rotary tables, eps): ``forward`` is handed a tree and a
# rounder only, so it reads the sizes of the tree ``make_params`` made last.
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


HEAD_CANDIDATES = 16
# The program's head rounds its operands itself (``decoder_parts.mm`` casts
# both to the stated dtype), on the CPU as on the MXU: ``harness.judge``
# reads a rehearsal's reference at the stated dtype too.
CASTS_OPERANDS = True
QUERY_BLOCK = 256      # queries that meet all keys of their window at once
CALIBRATION_WINDOWS = 32
# The seeded gain of the head norms on q and k. With unit gains a score is a
# dot product of two random unit-RMS heads over sqrt(128), ~N(0, 1), and a
# softmax over 1,024 to 4,096 such scores is nearly flat: every query reads
# the mean of its keys' values, and a band of 1,024 keys or all 4,096 give
# the same answer to within the rounding (the first proof run, PR 57: the
# reference without the band sat 1.02 roundings from the program, the one
# with it 0.95). A trained model's attention is not flat. At a gain of 2 on
# both a score is ~N(0, 16) (x 1.63 more in the full layer), past sqrt(2 ln
# 4096) = 4.1, where a few keys hold most of a softmax's weight wherever
# they lie in the window: three times in four outside the band.
QK_GAIN = 2.0


def make_params(seed: int, config: dict) -> dict:
    """The head's tree (the shape of the program's), built on the device
    in bfloat16 (norm gains and the scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x6D656C6C), 64))
    hid, hd, f = d.hidden, d.head_dim, d.expert_width

    def w(shape, fan_in, scale=1.0):
        return _normal_bf16(next(keys), tuple(shape), scale / math.sqrt(fan_in))

    out = out_scale(config)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for _ in d.kinds:
        layers.append({
            "g1": ones(hid), "g2": ones(hid),
            "wq": w((hid, d.heads * hd), hid),
            "wk": w((hid, d.kv_heads * hd), hid),
            "wv": w((hid, d.kv_heads * hd), hid),
            "wo": w((d.heads * hd, hid), d.heads * hd, out),
            "qn": ones(hd) * QK_GAIN, "kn": ones(hd) * QK_GAIN,
            "wr": w((hid, d.experts), hid),
            "wg": w((d.experts, hid, f), hid),
            "wu": w((d.experts, hid, f), hid),
            "wd": w((d.experts, f, hid), f, out),
        })
    rng = np.random.default_rng([seed & (2**64 - 1), 0x6D656C6C])
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
    # of the deployment's depth (``plausible_windows``) the logits spread by
    # about one and centre on the threshold, as heads/keye_vl2.py; the direction
    # read is the one of ``HEAD_CANDIDATES`` seeded directions along which
    # these windows spread most.
    win, lengths = plausible_windows(rng, CALIBRATION_WINDOWS, d.events)
    params["embed"] = _standardised(params["embed"], win, lengths)
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


def plausible_windows(rng, n: int, t: int):
    """``n`` windows of ``t`` positions, half full to full (the band clips in
    all of them where ``t`` is deeper than it), as the deployment's look when
    they are scored: log-amounts and the mix of transaction types as the
    traffic's; a preloaded event's gap is the one to the round before its
    own, 20 s to 15 min; the newest event, the one that is scored, arrives
    years after the preloaded history ends: an account's first event of a
    run, which most rows of a check are."""
    win = np.zeros((n, t, EVENT_WIDTH), F32)
    lengths = rng.integers(max(t // 2, 1), t + 1, n)
    win[..., 0] = rng.normal(7.6, 1.2, (n, t))     # log1p of ~2000 cents
    win[..., 1] = np.log1p(rng.uniform(20.0, 900.0, (n, t)))
    win[np.arange(n), lengths - 1, 1] = np.log1p(1e8)
    codes = rng.choice(4, size=(n, t), p=[0.07, 0.03, 0.70, 0.20])
    win[np.arange(n)[:, None], np.arange(t)[None, :],
        2 + TX_EVENT_COL[codes]] = 1.0
    win[..., 10] = 1.0
    win *= (np.arange(t)[None, :] < lengths[:, None])[..., None]
    return win, lengths


def _standardised(w_in, windows: np.ndarray, lengths: np.ndarray):
    """``w_in`` [event width, hidden] so that ``event @ w_in`` reads each
    event column standardised over the plausible events (as
    heads/xing4_29b_a4b.py's projector): a column that varies has its row
    divided by the column's spread, and the column that is constant (one in
    every event) carries the means. As drawn, an event's projection is nine
    tenths the mean event's (log-amounts of 7.6 +- 1.2 beside a constant
    one), every key of a window is nearly every other and no softmax over
    them can concentrate, whatever the gain on q and k. The projector stays
    one matrix without a bias."""
    real = np.arange(windows.shape[1])[None, :] < np.asarray(lengths)[:, None]
    events = windows[real].astype(np.float64)
    mean, std = events.mean(axis=0), events.std(axis=0)
    varies = std > 0
    const = int(np.flatnonzero(~varies & (mean != 0))[0])
    w = np.asarray(w_in.astype(jnp.float32)).astype(np.float64)
    out = w / np.where(varies, std, 1.0)[:, None]
    out[const] -= (mean[varies] / std[varies]) @ w[varies] / mean[const]
    return jnp.asarray(out.astype(F32), jnp.bfloat16)


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


def block_rows(t: int) -> int:
    """Windows a block: one at the deployment's 4,096 events, more where
    windows are short, so that a block is ~4,096 positions either way."""
    return max(1, 4096 // t)


def _logits(params, windows, lengths, d: Dims, dt, hidden: bool = False) -> np.ndarray:
    """In blocks of ``block_rows`` windows (the last one padded with empty
    windows), so that one set of compiled shapes serves any number of rows
    and the temporaries stay at a block's size beside the tree."""
    n, t, _ = windows.shape
    rows = block_rows(t)
    pad = -n % rows
    windows = np.concatenate([windows, np.zeros((pad, t, EVENT_WIDTH), F32)])
    lengths = np.concatenate([np.asarray(lengths, np.int32),
                              np.ones((pad,), np.int32)])
    out = [_block_logits(params, windows[lo:lo + rows], lengths[lo:lo + rows],
                         d, dt, hidden)
           for lo in range(0, n + pad, rows)]
    return np.concatenate([np.asarray(o) for o in out])[:n]


def _block_logits(params, windows, lengths, d: Dims, dt, hidden: bool):
    rows, t, _ = windows.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], jnp.asarray(windows), dt)
        for kind, layer in zip(d.kinds, params["layers"], strict=True):
            band = d.band if kind == SLIDING and not WITHOUT_BAND else None
            h = _attend(layer, h, kind, band, d, dt)
            b, top_e, top_w, most = _route(layer, h, real, d, dt)
            cap = max(128, 1 << (int(most) - 1).bit_length())
            y = _experts(b, top_e, top_w, real, layer["wg"], layer["wu"],
                         layer["wd"], cap, dt)
            h = h + y.reshape(h.shape)
        return _score(params, h, lengths, d, hidden)


def _rnd(a, dt):
    """``a`` rounded to ``dt`` and back in float32. The barrier keeps the
    compiler from dropping the pair of conversions: XLA may keep "excess
    precision" and does on a TPU (PERF.md, PR 34)."""
    if dt == jnp.float32 or a.dtype == dt:
        return a.astype(jnp.float32)
    return jax.lax.optimization_barrier(a.astype(dt)).astype(jnp.float32)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


def _rope(x, kind: str, d: Dims):
    """Rotary embedding as the transformers library writes it: the angles of
    the positions, ``cat(freqs, freqs)`` over the head's channels, ``x cos +
    rotate_half(x) sin``, cos and sin times the kind's factor."""
    t = x.shape[1]
    rates, factor = next((r, f) for k, r, f in d.rope if k == kind)
    freqs = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * jnp.asarray(np.array(rates), jnp.float32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    half = d.head_dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(emb) * factor) + rotated * (jnp.sin(emb) * factor)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _attend(layer, h, kind: str, band, d: Dims, dt):
    rows, t, _ = h.shape
    group = d.heads // d.kv_heads
    a = _rms(h, layer["g1"], d.eps)
    ar = _rnd(a, dt)
    q = (ar @ _rnd(layer["wq"], dt)).reshape(rows, t, d.heads, d.head_dim)
    k = (ar @ _rnd(layer["wk"], dt)).reshape(rows, t, d.kv_heads, d.head_dim)
    v = (ar @ _rnd(layer["wv"], dt)).reshape(rows, t, d.kv_heads, d.head_dim)
    q = _rnd(_rope(_rms(q, layer["qn"], d.eps), kind, d), dt)
    k = _rnd(_rope(_rms(k, layer["kn"], d.eps), kind, d), dt)
    # query head j reads key-value head j // group
    kq = jnp.repeat(k, group, axis=2)
    vq = _rnd(jnp.repeat(v, group, axis=2), dt)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(rows, -1, block, d.heads, d.head_dim), 1, 0)
    j = jnp.arange(t)[None, :]

    def one_block(args):
        qs, lo = args                           # [rows, block, heads, hd]
        i = lo + jnp.arange(block)[:, None]
        keep = j <= i
        if band is not None:
            keep = keep & (i - j < band)
        sc = jnp.einsum("rtjd,rsjd->rjts", qs, kq) / math.sqrt(d.head_dim)
        sc = jnp.where(keep, sc, -jnp.inf)
        sc = sc - sc.max(-1, keepdims=True)
        p = jnp.exp(sc)
        p = p / p.sum(-1, keepdims=True)
        return jnp.einsum("rjts,rsjd->rtjd", _rnd(p, dt), vq)

    heads = jax.lax.map(one_block, (qb, jnp.arange(qb.shape[0]) * block))
    heads = jnp.moveaxis(heads, 0, 1).reshape(rows, t + pad, -1)[:, :t]
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
