"""The ``phi4flash`` session head's plain reference: Phi-4-mini-flash-
reasoning's decoder-hybrid-decoder (SambaY, arXiv 2507.06607: Mamba-1 scans
and differential attention in the first half, Gated Memory Units and cross
attention over ONE shared memory and ONE shared key-value window in the
second) over a session window, its tree from the seed and its forward pass.

Nothing is imported from the program. The arithmetic is float32
(``jax.numpy`` at ``jax.default_matmul_precision("highest")``; on the
chip's machine that is the chip, in a test the CPU) over weights that
bfloat16 holds exactly, every operand of a product passed through the
rounder. No kernel, no chunk and no narrowing: ALL layers run at EVERY
position and the last real one is read at the end; the recurrence is a plain
``lax.scan`` over positions; every query meets EVERY key of its window and
the mask decides, a block of queries at a time. The sizes are the
configuration file's top-level source keys and what ``head.assumed`` states.

``h`` [rows, T, hidden] float32; ``LN`` = LayerNorm with gain and bias, eps
``layer_norm_eps``; every layer ``h += Mixer(LN1(h)); h += MLP(LN2(h))``,
``MLP(x) = (up * silu(gate)) W_down``, no bias. With ``L`` =
``num_hidden_layers``, by the source's rule over the layer index ``l``:

- ``l`` even, ``l <= L/2``: Mamba-1 (d_inner ``2 hidden``, state 16, 4 taps,
  dt_rank ``ceil(hidden / 16)``). ``[x, z] = u W_in``; ``x = silu(taps(x) +
  b_conv)`` (causal, zero before the window); ``[r, B_t, C_t] = x W_x``; ``dt
  = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``s_t[c,n] = exp(dt_t[c]
  A[c,n]) s_{t-1}[c,n] + dt_t[c] B_t[n] x_t[c]`` from zero; ``y_t[c] = sum_n
  C_t[n] s_t[c,n] + D[c] x_t[c]``; out ``(y * silu(z)) W_out``. Layer ``L/2``
  also exports ``m = y``.
- ``l`` odd, ``l < L/2``: differential attention, band ``sliding_window``;
  ``l = L/2 + 1``: the same over every causal key, and exports ``K, V``.
  ``[q, k, v] = u W_qkv + b``; query heads ``(2p, 2p+1)`` are pair ``p``'s
  ``(q1, q2)``, key heads ``(2j, 2j+1)`` pair ``j``'s, value heads ``(2j,
  2j+1)`` side by side the pair's ``V_j`` of twice the head width; pair ``p``
  reads ``j = p // (heads / kv_heads)``. ``A_i = softmax(mask(q_i k_i^T /
  sqrt(head width)))``; ``o_p = (A_1 - lambda A_2) V_j``; ``lambda = exp(lq1
  . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; ``o_p = (1 - lambda_init) RMSNorm(o_p)``; out ``concat_p(o_p)
  W_o + b_o``. Mask: ``0 <= i - j`` and, in a band layer, ``i - j < band``.
- ``l`` even, ``l >= L/2 + 2``: Gated Memory Unit, ``(m * silu(u W_g))
  W_out``, ``m`` at the same position.
- ``l`` odd, ``l >= L/2 + 3``: cross attention, ``q = u W_q + b_q`` against
  layer ``L/2 + 1``'s ``K, V`` under the causal mask, the same differential
  form with its own lambdas, norm and ``W_o``.

Output: ``sigmoid(LN(h)[last real position] . w_out + b_out)``.

Departures from the published description and what it does not give, each
also under ``head.assumed`` in the configuration file: Mamba's 16 / 4 / 2 /
ceil(hidden / 16) are the Mamba reference's defaults; the biases on ``Wqkv``,
``Wo``, the taps and ``dt_proj``, LayerNorm, no positional encoding and the
differential form with its pairing are the source's modelling code's as the
SambaY paper describes them; events enter through a projector in the
embedding's place and no row of the vocabulary is held; a
sequence-classification head stands in the output head's place.

Three switches are the proof's, never the benchmark's (chipbench/aa/proof):
``WITHOUT_BAND`` (the band layers keep every causal key), ``FORGET_EVERY``
(the scan's state set to zero at every position that is a multiple of it),
``LAMBDA_ZERO`` (``lambda = 0``: the second softmax is never subtracted).
With one set, rows leave the program's answers.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL

SSM, BAND, FULL, GMU, CROSS = "ssm", "window", "attention", "memory", "cross"
WITHOUT_BAND = False
FORGET_EVERY = None
LAMBDA_ZERO = False

STATE, TAPS, EXPAND = 16, 4, 2  # Mamba-1's defaults


class Dims(NamedTuple):
    hidden: int
    kinds: tuple       # one entry a layer
    heads: int
    kv_heads: int
    head_dim: int
    dense: int
    band: int
    inner: int         # Mamba's d_inner
    rank: int          # dt_rank
    eps: float
    events: int        # the deployment's window


def layer_kind(index: int, layers: int, every: int) -> str:
    """The source's rule: a Mamba layer every ``every``-th; from layer ``L/2
    + 2`` on it is a Gated Memory Unit and attention is cross attention;
    attention is banded before layer ``L/2``; layer ``L/2 + 1`` is full."""
    half = layers // 2
    if index % every == 0:
        return SSM if index <= half else GMU
    if index < half:
        return BAND
    return FULL if index == half + 1 else CROSS


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys."""
    layers, every = config["num_hidden_layers"], config["mb_per_layer"]
    if layers % 4 or every != 2:
        raise ValueError("written for a depth in whole fours and a Mamba "
                         "layer every second")
    if config["hidden_act"] != "silu" or config["mlp_bias"]:
        raise ValueError("written for silu and an MLP without bias")
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    return Dims(
        hidden=hidden,
        kinds=tuple(layer_kind(i, layers, every) for i in range(layers)),
        heads=heads, kv_heads=config["num_key_value_heads"],
        head_dim=hidden // heads, dense=config["intermediate_size"],
        band=config["sliding_window"], inner=EXPAND * hidden,
        rank=-(-hidden // 16), eps=float(config["layer_norm_eps"]),
        events=int(config.get("env", {}).get("SESSION_EVENTS", 16)))


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


# -- the tree from the seed ---------------------------------------------------

# ``forward`` is handed a tree and a rounder only, so it reads the sizes of
# the tree ``make_params`` made last.
_made: dict = {}


@partial(jax.jit, static_argnums=(1, 2))
def _normal_bf16(key, shape, scale):
    """Seeded normals in bfloat16, with no float32 copy kept."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)


HEAD_CANDIDATES = 16
# The program's head rounds its operands itself (``decoder_parts.mm`` casts
# both to the stated dtype), on the CPU as on the MXU.
CASTS_OPERANDS = True
QUERY_BLOCK = 256      # queries that meet all keys of their window at once
CALIBRATION_WINDOWS = 16
# The seeded scale on the q and k columns of ``W_qkv`` (and on ``W_q`` of a
# cross layer). There is no head norm and no rotary: at unit variance a
# score is a dot product of two 64-wide heads over 8, ~N(0, 1), and a softmax
# over 512 to 2,048 such scores is nearly flat, so that a band of 512 keys or
# all 2,048 give the same answer to within the rounding (PERF.md, PR 57: the
# mellum cell's lesson). At 2 on both a score is ~N(0, 16), past sqrt(2 ln
# 2048) = 3.9, where a few keys hold most of a softmax's weight wherever
# they lie: three times in four outside the band.
QK_GAIN = 2.0
# The seeded scale on the B and C columns of ``W_x``. As drawn (unit variance
# through ``W_x``, ``D`` one) the scan's state adds a tenth of what the skip
# ``D x`` does to ``y`` (0.06 against 0.62 rms at the cell's widths' ratio:
# the state of a channel fed incoherent inputs is ~sqrt(dt / 2A) of them),
# and a reference that forgot its state at every 128th position would stay
# within the rounding of one that did not. A trained model's scan carries
# its layer. At 4 on both (16 on their product) the state's part of ``y`` is
# 1.0 rms against the skip's 0.62.
BC_GAIN = 4.0
# The seeded gain of the RMSNorm on a pair's output. At one, a pair's output
# is ``1 - lambda_init`` rms, 0.64 in layer 1 and 0.2 from layer 9 on, a
# third of what a Mamba layer's gated scan hands its out-projection, and the
# band then moves an answer by 2.6 to 4.3 roundings: on one seed of two the
# reference without the band stayed inside the limits (my chip runs, PR 59:
# chipbench/aa/proof). At 3 attention carries as much of a layer's update as
# the scan does.
SN_GAIN = 3.0
BIAS = 0.1             # the seeded biases' spread (norms, projections)


def make_params(seed: int, config: dict) -> dict:
    """The head's tree (the shape of the program's), built on the device in
    bfloat16 (norms, biases, taps, ``A_log``, ``D``, lambdas and the scoring
    head float32). Mamba's own initialisation for the scan: ``A[c, n] = n +
    1``, ``dt`` log-uniform in [0.001, 0.1] through the inverse softplus (so
    some channels remember hundreds of positions), ``D`` one; the lambdas
    N(0, 0.1); every bias N(0, 0.1) so that a program without it fails."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x70683466), 1024))
    hid, hd, f32 = d.hidden, d.head_dim, jnp.float32
    kvw, di, n = d.kv_heads * hd, d.inner, STATE
    out = 1.0 / math.sqrt(2.0 * len(d.kinds))

    def w(shape, fan_in, scale=1.0):
        return _normal_bf16(next(keys), tuple(shape), scale / math.sqrt(fan_in))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, f32) * scale

    def norm():
        return {"g": jnp.ones((hid,), f32), "b": normal((hid,), BIAS)}

    def differential():
        return {"lam": normal((4, hd), 0.1),
                "sn": jnp.full((2 * hd,), SN_GAIN, f32),
                "wo": w((hid, hid), hid, out), "bo": normal((hid,), BIAS)}

    layers = []
    for kind in d.kinds:
        layer = {"n1": norm(), "n2": norm(),
                 "dense": {"wg": w((hid, d.dense), hid),
                           "wu": w((hid, d.dense), hid),
                           "wd": w((d.dense, hid), d.dense, out)}}
        if kind == SSM:
            dt = jnp.exp(jax.random.uniform(next(keys), (di,), f32,
                                            math.log(1e-3), math.log(1e-1)))
            layer |= {
                "w_in": w((hid, 2 * di), hid),
                "taps": normal((di, TAPS), 1.0 / math.sqrt(TAPS)),
                "conv_b": normal((di,), 0.25),
                "w_x": jnp.concatenate(
                    [w((di, d.rank), di), w((di, 2 * n), di, BC_GAIN)], axis=1),
                # Mamba draws dt_proj at dt_rank ** -0.5 uniform: a third of
                # the variance, so ``dt`` stays within a factor of its bias's
                "w_dt": w((d.rank, di), 3 * d.rank),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=f32)), (di, n)),
                "d_skip": jnp.ones((di,), f32),
                "w_out": w((di, hid), di, out)}
        elif kind == GMU:
            layer |= {"w_g": w((hid, di), hid), "w_out": w((di, hid), di, out)}
        elif kind == CROSS:
            layer |= {"wq": w((hid, hid), hid, QK_GAIN),
                      "bq": normal((hid,), BIAS), **differential()}
        else:
            layer |= {"wqkv": jnp.concatenate(
                          [w((hid, hid + kvw), hid, QK_GAIN),
                           w((hid, kvw), hid)], axis=1),
                      "bqkv": normal((hid + 2 * kvw,), BIAS), **differential()}
        layers.append(layer)
    rng = np.random.default_rng([seed & (2**64 - 1), 0x70683466])
    params = {
        "embed": w((EVENT_WIDTH, hid), EVENT_WIDTH),
        "layers": layers,
        "gf": jnp.ones((hid,), f32), "bf": jnp.zeros((hid,), f32),
        "head": {"w": jnp.asarray(rng.standard_normal((hid, 1))
                                  / math.sqrt(hid), f32),
                 "b": jnp.zeros((1,), f32)},
    }
    # A random head answers nearly the same for every window, far from the
    # fold threshold on most seeds, and the check would then never see its
    # output: scale and shift the last layer so that over plausible windows
    # of the deployment's depth the logits spread by about one and centre on
    # the threshold (heads/keye_vl2.py), along the one of ``HEAD_CANDIDATES``
    # seeded directions along which these windows spread most.
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
        "w": jnp.asarray(w_out[:, None] * gain, f32),
        "b": jnp.asarray([centre - np.median(logits) * gain], f32)}
    return params


def plausible_windows(rng, n: int, t: int):
    """``n`` windows of ``t`` positions, half full to full, as the
    deployment's look when they are scored (heads/mellum2_12b_a2_5b.py's):
    log-amounts and the mix of transaction types as the traffic's; a
    preloaded event's gap is the one to the round before its own; the newest
    event arrives years after the preloaded history ends."""
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
    event column standardised over the plausible events: a column that
    varies has its row divided by the column's spread, and the column that is
    constant carries the means (heads/mellum2_12b_a2_5b.py's: as drawn every
    event projects to nine tenths the mean event and no softmax over such
    keys concentrates). One matrix, no bias."""
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
    numpy function) rounds to."""
    probe = rnd(np.array([1.0 + 2.0 ** -10, 1.0 + 2.0 ** -6], F32))
    if probe[0] != 1.0:
        return jnp.float32
    return jnp.bfloat16 if probe[1] != 1.0 else jnp.float8_e4m3fn


def block_rows(t: int) -> int:
    """Windows a block: two at the deployment's 2,048 events, more where
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
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], jnp.asarray(windows), dt)
        m = kv = None
        for index, (kind, layer) in enumerate(
                zip(d.kinds, params["layers"], strict=True)):
            if kind == SSM:
                h, m = _mamba(layer, h, d, dt, FORGET_EVERY)
            elif kind == GMU:
                h = _memory(layer, h, m, d, dt)
            else:
                h, made = _attend(layer, h, kv, jnp.float32(lambda_init(index)),
                                  kind, d, dt, WITHOUT_BAND, LAMBDA_ZERO)
                kv = made if kind == FULL else kv
            h = _mlp(layer, h, d, dt)
        return _score(params, h, jnp.asarray(lengths, jnp.int32), d, hidden)


def _rnd(a, dt):
    """``a`` rounded to ``dt`` and back in float32. The barrier keeps the
    compiler from dropping the pair of conversions: XLA may keep "excess
    precision" and does on a TPU (PERF.md, PR 34)."""
    if dt == jnp.float32 or a.dtype == dt:
        return a.astype(jnp.float32)
    return jax.lax.optimization_barrier(a.astype(dt)).astype(jnp.float32)


def _ln(x, norm, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * norm["g"] + norm["b"]


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


@partial(jax.jit, static_argnums=(2, 3))
def _mlp(layer, h, d: Dims, dt):
    x = _rnd(_ln(h, layer["n2"], d.eps), dt)
    w = layer["dense"]
    mid = _silu(x @ _rnd(w["wg"], dt)) * (x @ _rnd(w["wu"], dt))
    return h + _rnd(mid, dt) @ _rnd(w["wd"], dt)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _mamba(layer, h, d: Dims, dt, forget_every):
    rows, t, _ = h.shape
    u = _rnd(_ln(h, layer["n1"], d.eps), dt)
    xz = u @ _rnd(layer["w_in"], dt)
    x, z = xz[..., :d.inner], xz[..., d.inner:]
    # the causal taps: tap k reads the position TAPS - 1 - k before
    c = layer["conv_b"] + sum(
        jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        * layer["taps"][:, TAPS - 1 - back] for back in range(TAPS))
    x = _silu(c)
    rbc = _rnd(x, dt) @ _rnd(layer["w_x"], dt)
    r, bm, cm = (rbc[..., :d.rank], rbc[..., d.rank:d.rank + STATE],
                 rbc[..., d.rank + STATE:])
    step = jax.nn.softplus(_rnd(r, dt) @ _rnd(layer["w_dt"], dt)
                           + layer["dt_bias"])
    a = -jnp.exp(layer["a_log"])                       # [inner, state]

    def one(s, at):
        pos, x_t, dt_t, b_t, c_t = at
        if forget_every:
            s = jnp.where(pos % forget_every == 0, 0.0, s)
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    by_time = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(one, jnp.zeros((rows, d.inner, STATE), jnp.float32),
                        (jnp.arange(t), by_time(x), by_time(step),
                         by_time(bm), by_time(cm)))
    y = by_time(y) + layer["d_skip"] * x
    return h + _rnd(y * _silu(z), dt) @ _rnd(layer["w_out"], dt), y


@partial(jax.jit, static_argnums=(3, 4))
def _memory(layer, h, m, d: Dims, dt):
    u = _rnd(_ln(h, layer["n1"], d.eps), dt)
    gate = _silu(u @ _rnd(layer["w_g"], dt))
    return h + _rnd(m * gate, dt) @ _rnd(layer["w_out"], dt)


@partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _attend(layer, h, kv, lam_init, kind: str, d: Dims, dt,
            without_band: bool, lambda_zero: bool):
    """One attention layer at every position -> ``(h', (k, v))``; a cross
    layer reads ``kv``, the others make their own. ``lam_init`` is the
    layer's ``lambda_init``, an operand and not a constant so that the layers
    of one kind share one compiled function."""
    rows, t, _ = h.shape
    hd, rep = d.head_dim, d.heads // d.kv_heads
    pairs, kvw = d.kv_heads // 2, d.kv_heads * d.head_dim
    u = _rnd(_ln(h, layer["n1"], d.eps), dt)
    if kind == CROSS:
        q = u @ _rnd(layer["wq"], dt) + layer["bq"]
        k, v = kv
    else:
        qkv = u @ _rnd(layer["wqkv"], dt) + layer["bqkv"]
        q, k, v = (qkv[..., :d.hidden], qkv[..., d.hidden:d.hidden + kvw],
                   qkv[..., d.hidden + kvw:])
        k, v = _rnd(k, dt), _rnd(v, dt)
    # query head 4j + 2r + i is side i of query pair 2j + r, which reads
    # key-value pair j: key head 2j + i, and the two value heads 2j, 2j + 1
    # side by side
    q = _rnd(q, dt).reshape(rows, t, pairs, rep, 2, hd)
    kp = k.reshape(rows, t, pairs, 2, hd)
    vp = v.reshape(rows, t, pairs, 2 * hd)
    band = d.band if kind == BAND and not without_band else None
    lq1, lk1, lq2, lk2 = layer["lam"]
    lam = (0.0 if lambda_zero else
           jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
           + lam_init)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 4)
    qb = jnp.moveaxis(qb.reshape(rows, -1, block, pairs, rep, 2, hd), 1, 0)
    j = jnp.arange(t)[None, :]

    def one_block(args):
        qs, lo = args                           # [rows, block, j, r, i, hd]
        i = lo + jnp.arange(block)[:, None]
        keep = j <= i
        if band is not None:
            keep = keep & (i - j < band)
        sc = jnp.einsum("btjrid,bsjid->bjrits", qs, kp) / math.sqrt(hd)
        sc = jnp.where(keep, sc, -jnp.inf)
        sc = sc - sc.max(-1, keepdims=True)
        p = jnp.exp(sc)
        p = p / p.sum(-1, keepdims=True)
        o = jnp.einsum("bjrits,bsje->btjrie", _rnd(p, dt), vp)
        o = o[..., 0, :] - lam * o[..., 1, :]   # [rows, block, j, r, 2 hd]
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + d.eps)
        return o * layer["sn"] * (1.0 - lam_init)

    o = jax.lax.map(one_block, (qb, jnp.arange(qb.shape[0]) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(rows, t + pad, d.hidden)[:, :t]
    return h + _rnd(o, dt) @ _rnd(layer["wo"], dt) + layer["bo"], (k, v)


@partial(jax.jit, static_argnums=(3, 4))
def _score(params, h, lengths, d: Dims, hidden: bool = False):
    """The logit of each window's last real position; with ``hidden`` the
    final-normed hidden state it is read from."""
    last = jnp.clip(lengths - 1, 0, h.shape[1] - 1)
    hl = h[jnp.arange(h.shape[0]), last]
    hl = _ln(hl, {"g": params["gf"], "b": params["bf"]}, d.eps)
    if hidden:
        return hl
    return jnp.sum(hl * params["head"]["w"][:, 0], axis=-1) + params["head"]["b"][0]
