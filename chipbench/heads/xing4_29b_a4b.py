"""The ``xing`` session head's plain reference: Xing4.0-29B-A4B's decoder
block (manifold-constrained hyper-connections over four residual streams
around multi-head latent attention with YaRN, a leading dense layer, a
shared expert beside 64 bias-chosen experts, every one held) over a session
window: its tree from the seed and its forward pass.

Nothing is imported from the program, and the block below is written from
its published description (the configuration's keys; "mHC:
Manifold-Constrained Hyper-Connections", DeepSeek-AI, arXiv 2512.24880,
whose symbols the ``hc_*`` keys are), not from the program. The arithmetic
is float32 (``jax.numpy`` at ``jax.default_matmul_precision("highest")``;
on the chip's machine that is the chip, in a test the CPU) over weights
that bfloat16 holds exactly, every operand of a sublayer's products passed
through the rounder. No kernel, no sort, no chunking of pairs: the experts
are a dense loop with a mask, the hyper-connection is written stream by
stream, its Sinkhorn rounds a Python loop. The sizes are the configuration
file's top-level source keys.

The state of a position is ``X`` in ``R^(n x C)`` (``n = hc_mult`` streams
of ``C = hidden_size``), here ``[rows, 16, n, C]`` float32. Around each
sublayer ``F`` (attention; the dense MLP or shared + routed experts), with
that sublayer's own ``phi`` [n C, 2 n + n^2], ``b`` [2 n + n^2] and three
scalars ``a_pre, a_post, a_res`` (float32, never rounded):

1. ``m = (x phi) (mean(x^2) + rms_norm_eps)^-1/2`` with ``x = vec(X)`` in
   ``R^(n C)``, stream after stream: an RMSNorm without a gain, its
   division after the product.
2. ``H_pre[i] = sigmoid(a_pre m[i] + b[i])``; ``H_post[i] = 2 sigmoid(a_post
   m[n + i] + b[n + i])``; ``H_res = SK(clip(a_res mat(m[2n:]) + mat(b[2n:]),
   mhc_h_res_clamp_min, mhc_h_res_clamp_max))``, ``mat`` row-major (entry
   ``2n + i n + j`` is row ``i``, column ``j``); ``SK`` starts from
   ``exp(.)`` and ``hc_sinkhorn_iters`` times divides each column by its
   sum + ``hc_eps``, then each row by its sum + ``hc_eps``.
3. ``u = sum_i H_pre[i] X[i]``; ``y = F(N(u))`` with the layer's own input
   norm ``N`` (an RMSNorm with a gain); ``X'[i] = sum_j H_res[i, j] X[j] +
   H_post[i] y``.

The stack starts with the projected event copied into all ``n`` streams
and ends with their sum before the final norm.

**Attention** on ``a = N1(u)``: ``cq = Nq(a Wq_a)`` (``q_lora_rank``); ``q =
cq Wq_b`` -> heads of ``[q_nope | q_rope]``; ``a Wkv_a`` -> ``[ckv |
k_rope]``; ``ckv = Nkv(ckv)``; ``ckv Wkv_b`` -> heads of ``[k_nope | v]``.
Rotary on ``q_rope`` per head and on the one ``k_rope`` every head shares
(pair ``i`` is channels ``i`` and ``i + 32``), at YaRN's rates: ``f_i =
rope_theta^(-2i/64)`` becomes ``f_i (1 - r_i) + (f_i / factor) r_i``, ``r_i =
clip((i - low) / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``,
``high = ceil(c(beta_slow))``, ``c(b) = 64 ln(original_max_position_embeddings
/ (2 pi b)) / (2 ln rope_theta)`` (low 10, high 23); cos and sin times
``m(mscale) / m(mscale_all_dim)`` with ``m(s) = 0.1 s ln(factor) + 1``
(here 1). Scores ``q . k`` times ``(nope + rope)^-1/2 x m(mscale_all_dim)^2``
(2.005 here), causal, softmax, times ``v``; ``F = concat(heads) Wo``. No
bias.

**Feed-forward** on ``f = N2(u)``: for ``l < first_k_dense_replace`` ``F =
(silu(f Wg) * f Wu) Wd`` at ``intermediate_size``. Else ``s = sigmoid(f
Wr)`` over the ``n_routed_experts``; the ``num_experts_per_tok`` largest of
``s + bias`` are chosen (``topk_method`` noaux_tc with ``n_group`` 1: no
group limit; equal values: the lower index), the bias chooses and does not
weigh: ``w = s_chosen / (sum s_chosen + 1e-20) x routed_scaling_factor``;
``F = Shared(f) + sum over the chosen experts of w_e Expert_e(f)``, each a
SwiGLU of ``moe_intermediate_size``. A window's padding (positions past its
last real event) is not routed: it takes ``Shared(f)`` alone; nothing that
is scored can read it.

Output: ``sigmoid(N(sum_i X[i])[last real position] . w_out + b_out)``.

Departures from the published description and what it does not give, each
also under ``head.assumed`` in the configuration file: the streams' entry
and exit; the order inside a Sinkhorn round and where ``hc_eps`` sits; the
clip before ``exp``; the map's norm without a gain; the seeded ``a`` and
``b`` (``_hyper``); the rotary pairing; the latent norms; the projector
(standardised, ``_standardised``) and the one-column scoring head; the
seeded tree's scale; the router's balance (``_balancing_bias``); the
multi-token-prediction module and the vocabulary, which are not held.

A weight of more than 2^24 elements is multiplied a block of its columns
at a time (``_product``), and windows go through in blocks of
``BLOCK_ROWS``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL

RENORM_EPS = 1e-20  # the modelling code's constant beside the chosen scores' sum
TAG = 0x78696E67    # "xing": what the seed is folded with


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
    experts: int
    top_k: int
    expert_width: int
    scale: float
    theta: float
    eps: float
    # rope_scaling: factor, original context, beta_fast, beta_slow, mscale,
    # mscale_all_dim
    yarn: tuple
    streams: int
    rounds: int
    hc_eps: float
    clip: tuple


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys."""
    if config["n_shared_experts"] != 1 or not config["norm_topk_prob"] \
            or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["moe_layer_freq"] != 1 or config["attention_bias"]:
        raise ValueError("this reference is written for one shared expert, "
                         "sigmoid scores renormalised over the chosen, a "
                         "correction bias without groups, an expert layer "
                         "after every dense one and no attention bias")
    scaling = config["rope_scaling"]
    if scaling.get("type") != "yarn":
        raise ValueError("this reference is written for YaRN rope_scaling")
    return Dims(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        heads=config["num_attention_heads"], q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        dense_width=config["intermediate_size"],
        experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        scale=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        yarn=(float(scaling["factor"]),
              float(scaling["original_max_position_embeddings"]),
              float(scaling["beta_fast"]), float(scaling["beta_slow"]),
              float(scaling["mscale"]), float(scaling["mscale_all_dim"])),
        streams=config["hc_mult"], rounds=config["hc_sinkhorn_iters"],
        hc_eps=float(config["hc_eps"]),
        clip=(float(config["mhc_h_res_clamp_min"]),
              float(config["mhc_h_res_clamp_max"])))


# -- the tree from the seed ---------------------------------------------------

BLOCK_ELEMS = 1 << 24  # the most elements of a weight handled at once


def out_scale(config: dict) -> float:
    """What the projections that write into the streams (``Wo`` and the
    down matrices) are scaled by: ``1 / sqrt(2 x layers)`` of the PUBLISHED
    depth."""
    layers = config.get("head", {}).get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return 1.0 / math.sqrt(2.0 * layers)


# What the shapes of a tree do not give (experts a token, theta, the YaRN
# group, the Sinkhorn rounds, ...): ``forward`` is handed a tree and a
# rounder only, so it reads the sizes of the tree ``make_params`` made last.
# ``bias_moved`` is what that tree's expert bias does, a layer: the share of
# the plausible windows' real positions whose chosen set it changes.
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


# The spread of the seeded offsets ``b`` around their centres, and what the
# diagonal of ``H_res``'s logits is raised by.
HC_SPREAD, HC_DIAGONAL = 0.5, 2.0


def _hyper(key, rng, d: Dims) -> dict:
    """One sublayer's hyper-connection, drawn so that its maps MOVE with
    the position. The paper starts every ``a`` at 0.01, where the three
    maps are constants a sublayer and a check could not tell a program
    that computed them from one that did not; a trained model's are not
    published. So: ``phi`` normal at ``(n C)^-1/2`` (``m`` has unit
    variance over positions); each ``a`` uniform in (0.5, 1.5), of order 1
    on that ``m``; ``b_pre`` normal around ``logit(1 / n)`` (the sublayer
    reads about the streams' mean), ``b_post`` around 0 (``H_post`` about 1:
    the result is written about once to each stream), ``b_res`` around
    ``HC_DIAGONAL`` on the diagonal and 0 off it (each stream leans to
    itself, as the identity the paper starts from, and still mixes), all
    with spread ``HC_SPREAD``."""
    n = d.streams
    fan_in = n * d.hidden
    centre = np.concatenate([
        np.full(n, -math.log(max(n - 1, 1))), np.zeros(n),
        HC_DIAGONAL * np.eye(n).reshape(-1)])
    return {
        "phi": jax.random.normal(key, (fan_in, 2 * n + n * n), jnp.float32)
        / math.sqrt(fan_in),
        "b": jnp.asarray(centre + HC_SPREAD * rng.standard_normal(centre.shape),
                         jnp.float32),
        "a": jnp.asarray(rng.uniform(0.5, 1.5, 3), jnp.float32)}


def make_params(seed: int, config: dict) -> dict:
    """The head's tree (the shape of the program's), built on the device
    in bfloat16 (norm gains, the expert bias, the hyper-connections and the
    scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, TAG), 128))
    rng = np.random.default_rng([seed & (2**64 - 1), TAG])
    hid, f = d.hidden, d.expert_width
    out = out_scale(config)

    def w(*shape, scale=1.0):
        """Fan-in is the axis before the last."""
        return _normal_bf16(next(keys), tuple(shape),
                            scale / math.sqrt(shape[-2]))

    def mlp(width, *stack, down=1.0):
        return {"wg": w(*stack, hid, width), "wu": w(*stack, hid, width),
                "wd": w(*stack, width, hid, scale=out * down)}

    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for i in range(d.layers):
        layer = {
            "g1": ones(hid), "g2": ones(hid),
            "hc_attn": _hyper(next(keys), rng, d),
            "hc_mlp": _hyper(next(keys), rng, d),
            "wq_a": w(hid, d.q_rank), "qn": ones(d.q_rank),
            "wq_b": w(d.q_rank, d.heads * (d.nope + d.rope)),
            "wkv_a": w(hid, d.kv_rank + d.rope), "kvn": ones(d.kv_rank),
            "wkv_b": w(d.kv_rank, d.heads * (d.nope + d.v)),
            "wo": w(d.heads * d.v, hid, scale=out),
        }
        if i < d.dense_layers:
            layer["dense"] = mlp(d.dense_width)
        else:
            layer["wr"] = w(hid, d.experts)
            layer["rb"] = jnp.zeros((d.experts,), jnp.float32)
            layer["shared"] = mlp(f)
            # the routed sum is multiplied by routed_scaling_factor: its
            # experts' down matrices are drawn at one over it, so the routed
            # part starts at the shared expert's scale and one chosen expert
            # weighs what it does where the factor is 1 (PERF.md, PR 52)
            layer["routed"] = mlp(f, d.experts, down=1.0 / d.scale)
        layers.append(layer)
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


def _logits(params, windows, lengths, d: Dims, dt, hidden: bool = False) -> np.ndarray:
    """The pre-sigmoid score of every window; with ``hidden`` the
    final-normed hidden state of its last real position instead."""
    out = []
    with jax.default_matmul_precision("highest"):
        for win, lens in _blocks_of(windows, lengths):
            x = _enter(params["embed"], win, d, dt)
            for layer in params["layers"]:
                x = _attention_sublayer(layer, x, d, dt)
                x = _feed_forward_sublayer(layer, x, lens, d, dt)
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
        xs = [_enter(params["embed"], win, d, f32) for win, _ in blocks]
        for layer in params["layers"]:
            xs = [_attention_sublayer(layer, x, d, f32) for x in xs]
            if "dense" not in layer:
                s = np.concatenate([np.asarray(_router_scores(layer, x, d, f32))
                                    for x in xs])[real]
                bias, share = _balancing_bias(s, d.top_k)
                layer["rb"] = jnp.asarray(bias, f32)
                moved.append(share)
            xs = [_feed_forward_sublayer(layer, x, lens, d, f32)
                  for x, (_, lens) in zip(xs, blocks)]
        hidden = [np.asarray(_score(params, x, lens, d, True))
                  for x, (_, lens) in zip(xs, blocks)]
    return np.concatenate(hidden)[:windows.shape[0]]


def _balancing_bias(scores: np.ndarray, top_k: int):
    """The correction bias that evens the experts' loads over the positions
    ``scores`` [T, experts] (the router's sigmoid scores), by the rule the
    published model's bias is trained with (loss-free balancing): it
    starts at zero and moves up for an expert chosen less than the mean
    load, down for one chosen more, by a step that shrinks to nothing.
    Returns it (float32) and the share of the positions whose chosen set
    it changes."""
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


# -- the parts ----------------------------------------------------------------------


def _rnd(a, dt):
    """``a`` rounded to ``dt`` and back in float32. The barrier keeps the
    compiler from dropping the pair of conversions: XLA may keep "excess
    precision" and does on a TPU (PERF.md, PR 34)."""
    if dt == jnp.float32 or a.dtype == dt:
        return a.astype(jnp.float32)
    return jax.lax.optimization_barrier(a.astype(dt)).astype(jnp.float32)


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


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


@partial(jax.jit, static_argnums=(2, 3))
def _enter(w_in, windows, d: Dims, dt):
    """The projected event, copied into every stream: [rows, T, n, C]."""
    h = _rnd(windows, dt) @ _rnd(w_in, dt)
    return jnp.stack([h] * d.streams, axis=2)


# -- the hyper-connection, stream by stream -----------------------------------


@partial(jax.jit, static_argnums=(2,))
def _maps_before_the_rounds(x, hc, d: Dims):
    """``(H_pre, H_post, exp(clip(.)))`` of one sublayer from the streams
    ``x`` [P, n, C], as lists over the streams of [P] arrays (entry ``[i][j]``
    of the third: what stream ``i`` takes of stream ``j``, before
    ``SK``'s rounds)."""
    n = d.streams
    vec = x.reshape(x.shape[0], n * d.hidden)
    m = (vec @ hc["phi"]) / jnp.sqrt(
        jnp.mean(vec * vec, axis=-1, keepdims=True) + d.eps)
    a_pre, a_post, a_res = hc["a"][0], hc["a"][1], hc["a"][2]
    b = hc["b"]
    h_pre = [_sigmoid(a_pre * m[:, i] + b[i]) for i in range(n)]
    h_post = [2.0 * _sigmoid(a_post * m[:, n + i] + b[n + i]) for i in range(n)]
    lo, hi = d.clip
    h_res = [[jnp.exp(jnp.clip(a_res * m[:, 2 * n + i * n + j]
                               + b[2 * n + i * n + j], lo, hi))
              for j in range(n)] for i in range(n)]
    return h_pre, h_post, h_res


@partial(jax.jit, static_argnums=(1,))
def _sinkhorn_round(h_res, d: Dims):
    """One round of ``SK``: each column divided by its sum + ``hc_eps``,
    then each row by its sum + ``hc_eps``."""
    n = d.streams
    h_res = [list(row) for row in h_res]
    for j in range(n):
        total = sum(h_res[i][j] for i in range(n)) + d.hc_eps
        for i in range(n):
            h_res[i][j] = h_res[i][j] / total
    for i in range(n):
        total = sum(h_res[i][j] for j in range(n)) + d.hc_eps
        for j in range(n):
            h_res[i][j] = h_res[i][j] / total
    return h_res


def _maps(x, hc, d: Dims):
    """The three maps of one sublayer from the streams ``x`` [P, n, C]:
    ``(H_pre, H_post, H_res)``, ``H_res`` after ``hc_sinkhorn_iters``
    rounds, one after the other (a round is compiled once, the loop is
    Python's: twenty rounds unrolled into every sublayer took XLA's CPU
    compiler 20 s a sublayer)."""
    h_pre, h_post, h_res = _maps_before_the_rounds(x, hc, d)
    for _ in range(d.rounds):
        h_res = _sinkhorn_round(h_res, d)
    return h_pre, h_post, h_res


def _read(x, h_pre, d: Dims):
    """``u = sum_i H_pre[i] X[i]``: [P, C]."""
    return sum(h_pre[i][:, None] * x[:, i] for i in range(d.streams))


def _write(x, h_post, h_res, y, d: Dims):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: [P, n, C]."""
    n = d.streams
    return jnp.stack([
        sum(h_res[i][j][:, None] * x[:, j] for j in range(n))
        + h_post[i][:, None] * y for i in range(n)], axis=1)


# -- attention ----------------------------------------------------------------


def yarn_rates(d: Dims) -> np.ndarray:
    """The ``rope / 2`` rotary rates after YaRN (float64), from its
    formula."""
    factor, original, beta_fast, beta_slow, _, _ = d.yarn
    half = d.rope // 2
    i = np.arange(half, dtype=np.float64)
    f = d.theta ** (-2.0 * i / d.rope)

    def c(b):
        return d.rope * math.log(original / (2.0 * math.pi * b)) \
            / (2.0 * math.log(d.theta))

    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), d.rope - 1)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - r) + (f / factor) * r


def yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, d: Dims):
    """Rotary embedding as the family's published code writes it, over all
    ``qk_rope_head_dim`` channels of ``x`` [rows, T, heads, rope]: angles
    ``t x rate_i``, ``cat(freqs, freqs)`` over the channels, ``x cos +
    rotate_half(x) sin``, cos and sin times ``m(mscale) /
    m(mscale_all_dim)``."""
    half = d.rope // 2
    freqs = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(yarn_rates(d), jnp.float32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    ratio = yarn_m(d.yarn[0], d.yarn[4]) / yarn_m(d.yarn[0], d.yarn[5])
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(emb) * ratio) + rotated * (jnp.sin(emb) * ratio)


def _attention(layer, a, rows: int, t: int, d: Dims, dt):
    """Latent attention in its expanded form over normed ``a`` [rows x T,
    hidden] -> [rows x T, hidden]."""
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
    scale = (d.nope + d.rope) ** -0.5 * yarn_m(d.yarn[0], d.yarn[5]) ** 2
    sc = jnp.einsum("rthd,rshd->rhts", _rnd(q, dt), _rnd(k, dt)) * scale
    sc = jnp.where(np.tril(np.ones((t, t), bool)), sc, -jnp.inf)
    p = jnp.exp(sc - sc.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    heads = jnp.einsum("rhts,rshd->rthd", _rnd(p, dt),
                       _rnd(kvb[..., d.nope:], dt))
    o = heads.reshape(rows * t, d.heads * d.v)
    return _product(o, layer["wo"], dt)


@partial(jax.jit, static_argnums=(3, 4))
def _attend(layer, x, h_pre, d: Dims, dt):
    """What the attention sublayer computes from what it reads: [P, C]."""
    rows, t = x.shape[:2]
    flat = x.reshape(rows * t, d.streams, d.hidden)
    a = _rms(_read(flat, h_pre, d), layer["g1"], d.eps)
    return _attention(layer, a, rows, t, d, dt)


@partial(jax.jit, static_argnums=(4,))
def _leave(x, h_post, h_res, y, d: Dims):
    """The streams a sublayer leaves, in the shape they came in."""
    flat = x.reshape(-1, d.streams, d.hidden)
    return _write(flat, h_post, h_res, y, d).reshape(x.shape)


def _attention_sublayer(layer, x, d: Dims, dt):
    flat = x.reshape(-1, d.streams, d.hidden)
    h_pre, h_post, h_res = _maps(flat, layer["hc_attn"], d)
    return _leave(x, h_post, h_res, _attend(layer, x, h_pre, d, dt), d)


# -- the feed-forward -------------------------------------------------------------


def _scores(layer, f, dt):
    """The router's sigmoid scores of ``f`` [positions, hidden], float32."""
    return _sigmoid(_rnd(f, dt) @ _rnd(layer["wr"], dt))


@partial(jax.jit, static_argnums=(2, 3))
def _router_scores(layer, x, d: Dims, dt):
    flat = x.reshape(-1, d.streams, d.hidden)
    h_pre, _, _ = _maps_before_the_rounds(flat, layer["hc_mlp"], d)
    return _scores(layer, _rms(_read(flat, h_pre, d), layer["g2"], d.eps), dt)


def _choose(s, bias, d: Dims):
    """``(experts, weights)`` [positions, top_k] of scores ``s``: the bias
    chooses (equal sums: the lower index), the scores weigh."""
    _, sel = jax.lax.top_k(s + bias, d.top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (w.sum(-1, keepdims=True) + RENORM_EPS) * d.scale


def _experts(layer, f, real, d: Dims, dt):
    """The shared expert, and one routed expert at a time over EVERY
    position with a mask: a position takes expert ``e``'s result, times its
    weight, iff it holds an event and the router chose ``e`` for it."""
    sel, w = _choose(_scores(layer, f, dt), layer["rb"], d)
    routed = layer["routed"]

    def one(m, expert):
        e, wg, wu, wd = expert
        chosen = (sel == e) & real
        weight = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1, keepdims=True)
        y = _swiglu(f, {"wg": wg, "wu": wu, "wd": wd}, dt)
        return m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0), None

    m, _ = jax.lax.scan(one, _swiglu(f, layer["shared"], dt),
                        (jnp.arange(d.experts), routed["wg"], routed["wu"],
                         routed["wd"]))
    return m


@partial(jax.jit, static_argnums=(4, 5))
def _feed_forward(layer, x, h_pre, lengths, d: Dims, dt):
    """What the feed-forward sublayer computes from what it reads: [P, C]."""
    rows, t = x.shape[:2]
    flat = x.reshape(rows * t, d.streams, d.hidden)
    f = _rms(_read(flat, h_pre, d), layer["g2"], d.eps)
    if "dense" in layer:
        return _swiglu(f, layer["dense"], dt)
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1, 1)
    return _experts(layer, f, real, d, dt)


def _feed_forward_sublayer(layer, x, lengths, d: Dims, dt):
    flat = x.reshape(-1, d.streams, d.hidden)
    h_pre, h_post, h_res = _maps(flat, layer["hc_mlp"], d)
    return _leave(x, h_post, h_res,
                  _feed_forward(layer, x, h_pre, lengths, d, dt), d)


@partial(jax.jit, static_argnums=(3, 4))
def _score(params, x, lengths, d: Dims, hidden: bool = False):
    """The logit of each window's last real position, read off the sum of
    its streams; with ``hidden`` the final-normed hidden state it is read
    from."""
    last = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    streams = x[jnp.arange(x.shape[0]), last]           # [rows, n, C]
    total = sum(streams[:, i] for i in range(d.streams))
    xl = _rms(total, params["gf"], d.eps)
    if hidden:
        return xl
    return jnp.sum(xl * params["head"]["w"][:, 0], axis=-1) + params["head"]["b"][0]
