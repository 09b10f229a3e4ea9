"""The ``kexaone`` session head's plain reference: K-EXAONE-236B-A23B's
decoder block (no pre-norm, a 128-key band on three layers of every four and
no rotary on the fourth, a shared expert beside sigmoid-routed experts) and
its multi-token-prediction module over a session window, given ONE CHIP'S
SHARE of the routed experts: its tree from the seed and its forward pass.

Nothing is imported from the program. The arithmetic is float32
(``jax.numpy`` at ``jax.default_matmul_precision("highest")``, as every
reference under ``heads/`` since PR 34: on the chip's machine that is the
chip, in a test the CPU; plain numpy would take minutes a check at hidden
6144) over weights that bfloat16 holds exactly, every operand of a product
passed through the rounder. No kernel, no skipped block, no sort and nothing
narrowed: every query meets EVERY key of its window and the mask decides (a
block of queries at a time, so that the 64 x 2048 x 2048 scores of a layer
never stand at once), the held experts are a dense loop with a mask, and the
module's layer runs at EVERY position. The sizes are the configuration file's
top-level source keys (``num_experts`` there is what this chip HOLDS; the
router's width is ``head.published.num_experts``).

Per layer ``l``, over the residual stream ``h`` [rows, T, hidden] (``N`` an
RMSNorm with ``rms_norm_eps``; ``kind = layer_types[l]``). There is NO
pre-norm: a sublayer reads ``h`` as it stands and its output is normed.

1. ``q = h Wq`` [T, 64, 128], ``k = h Wk``, ``v = h Wv`` [T, 8, 128], no bias;
   ``N`` over the 128 of each head of q and k. In a ``sliding_attention``
   layer q and k turn by rotary over the whole head (rotate-half pairs ``(c, c
   + 64)``, ``inv_freq_c = rope_theta^(-2c / 128)``, position = the event's
   index); in a ``full_attention`` layer they do not turn. Query head ``j``
   reads key-value head ``j // 8``; ``s_ij = q_i . k_j / sqrt(128)``, kept
   where ``j <= i`` and, in a sliding layer, ``i - j < sliding_window``;
   softmax; ``h += N_pa(concat(heads) Wo)``.
2. ``h += N_pf(MLP(h))``. ``mlp_layer_types[l] == "dense"``: a SwiGLU of
   ``intermediate_size``. Else ``s = sigmoid(h Wr)`` over ALL routed experts
   (``n_group`` 1: no groups), the ``num_experts_per_tok`` largest of ``s +
   bias`` chosen (equal: the lower index), ``w = s_chosen / (sum s_chosen +
   1e-20) x routed_scaling_factor``; ``MLP(h) = Shared(h) + sum over the
   chosen experts HELD HERE of w_e Expert_e(h)``, each a SwiGLU of
   ``moe_intermediate_size``. What the absent experts would add is left out
   (model-configs guide, section 4). A window's padding is not routed.

After the stack ``f = N_f(h)``. **The module** (DeepSeek-V3, arXiv 2412.19437,
section 2.2, ``num_nextn_predict_layers`` 1): ``u_i = [N_e(E(x_{i+1})) ;
N_h(f_i)] W_eh`` (``E`` the projector the stack reads its events through; a
window's last position takes a zero embedding), one layer as above of kind
``mtp_layer_types[0]`` with a sparse MLP over the same held share, ``m =
N_m(.)``. **Output**: with ``z(x) = x . w_out + b_out``, a row of ``len`` real
events answers ``sigmoid((z(f_{len-1}) + z(m_{len-2})) / 2)``, and
``sigmoid(z(f_0))`` where ``len`` is 1.

Departures from the published description and what it does not give, each
also under ``head.assumed`` in the configuration file:

- Post-norm, the head norms and no rotary on full layers are EXAONE 4.0's
  (transformers' ``modeling_exaone4.py``), the family this config extends; the
  config lists none of them.
- The router is DeepSeek-V3's, whose keys the config carries (``scoring_func``
  sigmoid, ``n_group``, ``topk_group``, ``norm_topk_prob``,
  ``routed_scaling_factor``): float32 scores, an expert bias that chooses and
  does not weigh. **The seeded bias is what the model's bias is for**
  (``_balancing_bias``, as ``heads/lfm2_24b_a2b.py``): the loss-free balancing
  rule run on the seeded router over plausible windows, so that the held
  experts see about ``positions x 8 / 128`` pairs each.
- The module's form is DeepSeek-V3's: the embedding half first, ``f`` taken
  AFTER the stack's final norm (as the public inference code hands it on), its
  MLP sparse like every layer past the first, its layer's kind and band from
  ``mtp_layer_types`` and ``mtp_sliding_windows``.
- What the service reads of the module (``mtp_read``): it has no vocabulary
  and no decode loop, so no token is drafted; the shared head is read at both
  depths and the two logits are averaged.
- Events enter as ``inputs_embeds`` through a projector ``x @ W_in`` (12 ->
  hidden, seeded so that it reads each event column standardised); no row of
  the 153,600-row vocabulary is held; a sequence-classification head (one
  float32 output column) stands in the place of the output head.
- The seeded tree (``seeded_tree_scale``): every matrix ``fan_in ** -0.5``;
  the post-norm gains 1, so every sublayer adds a unit-rms update and
  attention carries as much of a layer as its MLP; the head norms on q and k
  ``QK_GAIN`` (2), so that a softmax over 128 to 2,048 keys concentrates; the
  held experts' down matrices a fifth (``ROUTED_DOWN``), so that one expert
  chosen otherwise at the scored position moves a row by 0.01, not 0.05.

Five switches are the proof's, never the benchmark's (chipbench/aa/proof):
``WITHOUT_BAND`` (the sliding layers keep every causal key), ``ROPE_ON_FULL``
(the full layers turn by the rotary table too), ``WITHOUT_MTP`` (the depth-1
logit dropped), ``JOIN_SAME_EVENT`` (the join reads ``E(x_i)`` in the place of
``E(x_{i+1})``), ``WITHOUT_SHARED`` (no shared expert). With one set, rows
leave the program's answers.
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
ROPE_ON_FULL = False
WITHOUT_MTP = False
JOIN_SAME_EVENT = False
WITHOUT_SHARED = False


class Dims(NamedTuple):
    hidden: int
    kinds: tuple       # one entry a layer held
    sparse: tuple      # one bool a layer held
    mtp_kind: str
    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    experts: int       # the router's width: every routed expert of a layer
    held: int          # the routed experts this chip holds ...
    first: int         # ... starting with this one
    top_k: int
    expert_width: int
    scale: float
    band: int
    inv_freq: tuple
    eps: float
    events: int        # the deployment's window


def band_of(kind: str, band: int) -> int | None:
    return band if kind == SLIDING else None


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys; the
    published expert count and the share's first expert from ``head``."""
    head = config.get("head", {})
    layers = config["num_hidden_layers"]
    kinds, mlps = tuple(config["layer_types"]), tuple(config["mlp_layer_types"])
    if len(kinds) != layers or len(mlps) != layers:
        raise ValueError("layer_types and mlp_layer_types have one entry a layer")
    if (mlps != ("dense",) * config["first_k_dense_replace"]
            + ("sparse",) * (layers - config["first_k_dense_replace"])):
        raise ValueError("mlp_layer_types is first_k_dense_replace dense "
                         "layers, then sparse ones")
    band = config["sliding_window"]
    # the list the source gives beside ``layer_types`` says the same
    if list(config["sliding_windows"][:layers]) != [
            band_of(k, band) or 0 for k in kinds]:
        raise ValueError("sliding_windows disagrees with layer_types and "
                         "sliding_window")
    if (config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["num_shared_experts"] != 1
            or config["num_nextn_predict_layers"] != 1
            or config["rope_parameters"]["rope_type"] != "default"):
        raise ValueError("this reference is written for an ungrouped sigmoid "
                         "router with renormalised weights, one shared expert, "
                         "one prediction depth and plain rotary rates")
    mtp_kind = config["mtp_layer_types"][0]
    if config["mtp_sliding_windows"][0] != (band_of(mtp_kind, band) or 0):
        raise ValueError("mtp_sliding_windows disagrees with mtp_layer_types")
    hd = config["head_dim"]
    theta = float(config["rope_parameters"]["rope_theta"])
    rates = theta ** (-2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    return Dims(
        hidden=config["hidden_size"], kinds=kinds,
        sparse=tuple(m == "sparse" for m in mlps), mtp_kind=mtp_kind,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=hd,
        dense_width=config["intermediate_size"],
        experts=head.get("published", {}).get("num_experts",
                                              config["num_experts"]),
        held=config["num_experts"], first=head.get("first_expert", 0),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        scale=float(config["routed_scaling_factor"]), band=band,
        inv_freq=tuple(rates), eps=float(config["rms_norm_eps"]),
        events=int(config.get("env", {}).get("SESSION_EVENTS", 16)))


# -- the tree from the seed ---------------------------------------------------

BLOCK_ELEMS = 1 << 24  # the most elements of a weight handled at once
RENORM_EPS = 1e-20
HEAD_CANDIDATES = 16
# The program's head rounds its operands itself (``decoder_parts.mm`` casts
# both to the stated dtype), on the CPU as on the MXU: ``harness.judge``
# reads a rehearsal's reference at the stated dtype too.
CASTS_OPERANDS = True
QUERY_BLOCK = 256      # queries that meet all keys of their window at once
# Windows the scoring head is fitted on. With 8 the spread and the median of
# 16 candidate directions were read off 8 points, and on one seed of two the
# fitted head put all 60 rows of the check under the fold threshold, where a
# session probability reaches no reply but through the fold bit: the
# references without the module, with the join shifted and without the shared
# expert then read as the sound one. With 32 a boot took 215 s longer, most of
# it the bias's 200 turns over 49,000 positions a layer (my chip runs, PR 65:
# chipbench/aa/proof): 16, and the bias from ``BALANCE_POSITIONS`` of them.
CALIBRATION_WINDOWS = 16
# The seeded gain of the head norms on q and k. At unit gains a score is
# ~N(0, 1) and a softmax over 128 to 2,048 of them is nearly flat: every query
# reads the mean of its keys' values, a band of 128 keys or all 2,048 give the
# same answer to within the rounding, and so does a table that turns them or
# not (PERF.md, PR 57: the mellum cell's lesson). At 2 on both a score is
# ~N(0, 16), past sqrt(2 ln 2048) = 3.9, where a few keys hold most of a
# softmax's weight wherever they lie: fifteen times in sixteen outside the band.
QK_GAIN = 2.0
# The seeded gain of a layer's two post-norms: what a sublayer adds to the
# stream is this in rms, whatever its matrices' scale. At one, attention, the
# experts and the module's branch each carry as much as any other part.
POST_GAIN = 1.0
# What the held experts' down matrices are scaled by. Program and reference
# sum in float32 in another order, so now and then they choose another 8th
# expert; where that expert is held and the position is the one scored, the
# row moves by that expert's weighted output (0.31 of an expert's) over the
# shared expert's beside it, through a post-norm of gain one: 0.03-0.08 in
# probability at a scale of one (five sound runs of eight outside 0.03: my chip
# runs, PR 65). At a fifth, a flipped expert moves a row by a fifth of that
# (the xing file scales its routed down matrices for the same reason).
ROUTED_DOWN = 0.2

# What the shapes of a tree do not give (the layers' kinds, experts a token,
# the share's first expert, the band, theta, eps): ``forward`` is handed a
# tree and a rounder only, so it reads the sizes of the tree ``make_params``
# made last.
_made: dict = {}


def _blocks(whole: int, other: int, unit: int) -> int:
    """In how many equal blocks of ``whole`` (each a multiple of ``unit``) a
    ``whole x other`` weight is taken so that none passes ``BLOCK_ELEMS``; 1
    where it is small or cannot be divided so."""
    need = -(-whole * other // BLOCK_ELEMS)
    if need <= 1:
        return 1
    return next((b for b in range(need, whole // unit + 1)
                 if whole % (unit * b) == 0), 1)


@partial(jax.jit, static_argnums=(1, 2))
def _normal_bf16(key, shape, scale):
    """Seeded normals in bfloat16; a stacked weight is drawn one leading
    slice at a time and a large matrix one block of rows at a time, so that
    no float32 copy of either ever exists."""
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
    """The head's tree (the shape of the program's), built on the device in
    bfloat16 (norm gains, the expert bias and the scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x6B657861), 128))
    hid, hd, f = d.hidden, d.head_dim, d.expert_width

    def w(*shape, scale=1.0):
        """Fan-in is the axis before the last."""
        return _normal_bf16(next(keys), tuple(shape), scale / math.sqrt(shape[-2]))

    def mlp(width, *stack, down=1.0):
        return {"wg": w(*stack, hid, width), "wu": w(*stack, hid, width),
                "wd": w(*stack, width, hid, scale=down)}

    ones = lambda n, value=1.0: jnp.full((n,), value, jnp.float32)

    def one_layer(sparse: bool) -> dict:
        layer = {"pa": ones(hid, POST_GAIN), "pf": ones(hid, POST_GAIN),
                 "wq": w(hid, d.heads * hd), "wk": w(hid, d.kv_heads * hd),
                 "wv": w(hid, d.kv_heads * hd), "wo": w(d.heads * hd, hid),
                 "qn": ones(hd, QK_GAIN), "kn": ones(hd, QK_GAIN)}
        if sparse:
            layer |= {"wr": w(hid, d.experts), "rb": ones(d.experts, 0.0),
                      "shared": mlp(f),
                      "routed": mlp(f, d.held, down=ROUTED_DOWN)}
        else:
            layer["dense"] = mlp(d.dense_width)
        return layer

    rng = np.random.default_rng([seed & (2**64 - 1), 0x6B657861])
    params = {
        "embed": w(EVENT_WIDTH, hid),
        "layers": [one_layer(s) for s in d.sparse],
        "gf": ones(hid),
        "mtp": {"ge": ones(hid), "gh": ones(hid), "w_eh": w(2 * hid, hid),
                "layer": one_layer(True), "gm": ones(hid)},
        "head": {"w": jnp.asarray(rng.standard_normal((hid, 1))
                                  / math.sqrt(hid), jnp.float32),
                 "b": jnp.zeros((1,), jnp.float32)},
    }
    # A random head answers nearly the same for every window, far from the
    # fold threshold on most seeds, and the check would then never see its
    # output. Scale and shift the last layer so that over plausible windows
    # of the deployment's depth the logits spread by about one and centre on
    # the threshold (heads/keye_vl2.py), along the one of ``HEAD_CANDIDATES``
    # seeded directions along which these windows' read states (the mean of
    # the two depths') spread most.
    win, lengths = plausible_windows(rng, CALIBRATION_WINDOWS, d.events)
    params["embed"] = _standardised(params["embed"], win, lengths)
    n0, n1 = _bias_and_read(params, win, lengths, d)
    read = 0.5 * (n0 + n1).astype(np.float64)
    candidates = rng.standard_normal((hid, HEAD_CANDIDATES)) / math.sqrt(hid)
    w_out = candidates[:, int(np.argmax((read @ candidates).std(axis=0)))]
    logits = read @ w_out
    gain = 1.0 / max(float(logits.std()), 1e-6)
    centre = math.log(FLAG_THRESHOLD / (1.0 - FLAG_THRESHOLD))
    params["head"] = {
        "w": jnp.asarray(w_out[:, None] * gain, jnp.float32),
        "b": jnp.asarray([centre - np.median(logits) * gain], jnp.float32)}
    # how far apart the two depths' logits lie over these windows, in units
    # of the logits' spread: what dropping the module would move an answer by
    _made["depth_gap"] = float(np.abs((n0 - n1).astype(np.float64) @ w_out).mean()
                               * gain / 2.0)
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
    lengths = rng.integers(max(t // 2, 2), t + 1, n)
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
    heads/openpangu_ultra.py's projector: PERF.md, PR 36): a column that
    varies has its row divided by the column's spread, and the column that is
    constant (one in every event) carries the means. The projector stays one
    matrix without a bias."""
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
BALANCE_POSITIONS = 8192  # the most positions a layer's bias is balanced over


def _bias_and_read(params, windows, lengths, d: Dims):
    """The plausible windows through the tree in float32, layer by layer over
    all blocks: at each expert layer, the module's too, the bias is set from
    the router's scores over the positions it routes (``_balancing_bias``;
    ``params`` is updated in place) before the layer is applied. Returns the
    two normed states each window's score reads, ``(f_{len-1}, m_{len-2})``,
    which the scoring head is then fitted to. Between layers the blocks'
    states wait on the host (the harness calls this while the server still
    holds its own tree beside this one), one block on the device at a time."""
    f32 = jnp.float32
    blocks = _blocks_of(windows, lengths)
    moved = _made["bias_moved"] = []

    def each(fn, *lists):
        """``fn`` over the blocks, one on the device at a time."""
        return [np.asarray(fn(*args)) for args in zip(*lists, strict=True)]

    def balanced(layer, hs, routed):
        s = np.concatenate([
            np.asarray(_router_scores(layer, h, f32)).reshape(
                -1, d.experts)[np.asarray(r).reshape(-1)]
            for h, r in zip(hs, routed, strict=True)])
        bias, share = _balancing_bias(s, d.top_k)
        layer["rb"] = jnp.asarray(bias, f32)
        moved.append(share)

    def attend(layer, kind, hs):
        return each(lambda h: _attend(layer, h, kind, band_of(kind, d.band),
                                      False, d, f32), hs)

    def moe(layer, hs, routed):
        balanced(layer, hs, routed)
        return each(lambda h, r: _moe(layer, h, r, True, d, f32), hs, routed)

    with jax.default_matmul_precision("highest"):
        es = each(lambda win: _embed(params["embed"], win, f32),
                  [win for win, _ in blocks])
        reals = [_real(lens, win.shape[1], 0) for win, lens in blocks]
        hs = es
        for kind, layer in zip(d.kinds, params["layers"], strict=True):
            hs = attend(layer, kind, hs)
            hs = (each(lambda h: _dense(layer, h, d, f32), hs)
                  if "dense" in layer else moe(layer, hs, reals))
        fs = each(lambda h: _rms(h, params["gf"], d.eps), hs)
        mtp = params["mtp"]
        us = each(lambda e, f: _join(mtp, e, f, False, d, f32), es, fs)
        us = moe(mtp["layer"], attend(mtp["layer"], d.mtp_kind, us),
                 [_real(lens, win.shape[1], 1) for win, lens in blocks])
        ms = each(lambda u: _rms(u, mtp["gm"], d.eps), us)
    n, lens = windows.shape[0], [np.asarray(lens) for _, lens in blocks]
    return tuple(np.concatenate([np.asarray(_at(x, l - back))
                                 for x, l in zip(xs, lens, strict=True)])[:n]
                 for xs, back in ((fs, 1), (ms, 2)))


def _balancing_bias(scores: np.ndarray, top_k: int):
    """The expert bias that evens the experts' loads over the positions
    ``scores`` [T, experts] (the router's sigmoid scores), by the rule the
    published family's bias is trained with: it starts at zero and moves up
    for an expert chosen less than the mean load, down for one chosen more,
    by a step that shrinks to nothing. Returns it (float32) and the share of
    the positions whose chosen set it changes."""
    s = scores.astype(np.float64)
    if s.shape[0] > BALANCE_POSITIONS:  # an even sample of them
        s = s[np.linspace(0, s.shape[0] - 1, BALANCE_POSITIONS).astype(np.int64)]
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


# -- the forward pass ---------------------------------------------------------


def forward(params: dict, windows: np.ndarray, lengths: np.ndarray, rnd) -> np.ndarray:
    logits = _logits(params, np.asarray(windows, F32), lengths, _made["dims"],
                     operand_dtype(rnd))
    return (1.0 / (1.0 + np.exp(-logits.astype(F32)))).astype(F32)


def operand_dtype(rnd):
    """The dtype a harness rounder (``chipbench.reference.rounder``, a numpy
    function) rounds to, so that the same rounding can be applied where the
    operands live."""
    probe = rnd(np.array([1.0 + 2.0 ** -10, 1.0 + 2.0 ** -6], F32))
    if probe[0] != 1.0:
        return jnp.float32
    return jnp.bfloat16 if probe[1] != 1.0 else jnp.float8_e4m3fn


def block_rows(t: int) -> int:
    """Windows a block: two at the deployment's 2,048 events, more where
    windows are short, so that a block is ~4,096 positions either way."""
    return max(1, 4096 // t)


def _blocks_of(windows, lengths):
    """``(windows, lengths)`` in blocks of ``block_rows`` windows, the last
    one padded with one-event windows: one set of compiled shapes serves any
    number of rows and the temporaries stay at a block's size beside the
    resident tree."""
    n, t, _ = windows.shape
    rows = block_rows(t)
    pad = -n % rows
    windows = np.concatenate([windows, np.zeros((pad, t, EVENT_WIDTH), F32)])
    lengths = np.concatenate([np.asarray(lengths, np.int32),
                              np.ones((pad,), np.int32)])
    return [(jnp.asarray(windows[lo:lo + rows]), jnp.asarray(lengths[lo:lo + rows]))
            for lo in range(0, n + pad, rows)]


def _logits(params, windows, lengths, d: Dims, dt) -> np.ndarray:
    out = [np.asarray(_block_logits(params, win, lens, d, dt))
           for win, lens in _blocks_of(windows, lengths)]
    return np.concatenate(out)[:windows.shape[0]]


def _block_logits(params, windows, lengths, d: Dims, dt):
    """Every layer of the stack and the module's at every position, then the
    two reads."""
    t = windows.shape[1]
    with jax.default_matmul_precision("highest"):
        e = h = _embed(params["embed"], windows, dt)
        real = _real(lengths, t, 0)
        for kind, layer in zip(d.kinds, params["layers"], strict=True):
            band = None if WITHOUT_BAND else band_of(kind, d.band)
            h = _attend(layer, h, kind, band, ROPE_ON_FULL, d, dt)
            h = (_dense(layer, h, d, dt) if "dense" in layer
                 else _moe(layer, h, real, not WITHOUT_SHARED, d, dt))
        f = _rms(h, params["gf"], d.eps)
        z0 = _head(params, _at(f, lengths - 1))
        if WITHOUT_MTP:
            return z0
        mtp, layer = params["mtp"], params["mtp"]["layer"]
        u = _join(mtp, e, f, JOIN_SAME_EVENT, d, dt)
        band = None if WITHOUT_BAND else band_of(d.mtp_kind, d.band)
        u = _attend(layer, u, d.mtp_kind, band, ROPE_ON_FULL, d, dt)
        u = _moe(layer, u, _real(lengths, t, 1), not WITHOUT_SHARED, d, dt)
        z1 = _head(params, _at(_rms(u, mtp["gm"], d.eps), lengths - 2))
        return jnp.where(lengths >= 2, 0.5 * (z0 + z1), z0)


def _real(lengths, t: int, ahead: int):
    """[rows, T] bool: positions ``i`` with ``i + ahead < len``: the real
    events (``ahead`` 0), and those that have a next event (1), which are the
    ones the module's layer routes."""
    return jnp.arange(t)[None, :] + ahead < lengths[:, None]


def _at(x, index):
    """``x`` [rows, T, w] at position ``index`` [rows] of each window (0
    where the index is before the window's first)."""
    return x[jnp.arange(x.shape[0]), jnp.clip(index, 0, x.shape[1] - 1)]


def _head(params, x):
    return jnp.sum(x * params["head"]["w"][:, 0], axis=-1) + params["head"]["b"][0]


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
    """``x @ w`` [..., k] x [k, m], both rounded to ``dt``; a large weight a
    block of its columns at a time (the same dot product an element)."""
    lead, x = x.shape[:-1], x.reshape(-1, x.shape[-1])
    blocks = _blocks(w.shape[1], w.shape[0], 128)
    xr = _rnd(x, dt)
    if blocks == 1:
        return (xr @ _rnd(w, dt)).reshape(*lead, w.shape[1])
    cols = w.shape[1] // blocks
    out = jax.lax.map(
        lambda i: xr @ _rnd(jax.lax.dynamic_slice_in_dim(w, i * cols, cols, 1), dt),
        jnp.arange(blocks))
    return jnp.moveaxis(out, 0, 1).reshape(*lead, w.shape[1])


def _swiglu(x, w, dt):
    gate = _product(x, w["wg"], dt)
    mid = gate / (1.0 + jnp.exp(-gate)) * _product(x, w["wu"], dt)
    return _product(mid, w["wd"], dt)


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


def _rope(x, d: Dims):
    """Rotary embedding as the transformers library writes it: the angles of
    the positions, ``cat(freqs, freqs)`` over the head's channels, ``x cos +
    rotate_half(x) sin``."""
    freqs = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(np.array(d.inv_freq), jnp.float32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    half = d.head_dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _attend(layer, h, kind: str, band, rope_on_full: bool, d: Dims, dt):
    """``h + N_pa(Attn(h))``: no pre-norm; the layer's kind says whether q
    and k turn, ``band`` what the mask keeps beside causality."""
    rows, t, _ = h.shape
    group = d.heads // d.kv_heads
    q = _product(h, layer["wq"], dt).reshape(rows, t, d.heads, d.head_dim)
    k = _product(h, layer["wk"], dt).reshape(rows, t, d.kv_heads, d.head_dim)
    v = _product(h, layer["wv"], dt).reshape(rows, t, d.kv_heads, d.head_dim)
    q, k = _rms(q, layer["qn"], d.eps), _rms(k, layer["kn"], d.eps)
    if kind == SLIDING or rope_on_full:
        q, k = _rope(q, d), _rope(k, d)
    q, k = _rnd(q, dt), _rnd(k, dt)
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
    return h + _rms(_product(heads, layer["wo"], dt), layer["pa"], d.eps)


@partial(jax.jit, static_argnums=(2, 3))
def _dense(layer, h, d: Dims, dt):
    return h + _rms(_swiglu(h, layer["dense"], dt), layer["pf"], d.eps)


@partial(jax.jit, static_argnums=(2,))
def _router_scores(layer, h, dt):
    """The router's sigmoid scores of ``h`` [rows, T, hidden], float32."""
    return 1.0 / (1.0 + jnp.exp(-(_rnd(h, dt) @ _rnd(layer["wr"], dt))))


@partial(jax.jit, static_argnums=(3, 4, 5))
def _moe(layer, h, routed, shared: bool, d: Dims, dt):
    """``h + N_pf(Shared(h) + the held experts' part)``: one held expert at a
    time over EVERY position with a mask: a position takes expert ``e``'s
    result, times its weight, iff it is ``routed`` [rows, T] and the router
    chose ``e`` for it. The bias chooses (equal sums: the lower index), the
    scores weigh."""
    s = _router_scores(layer, h, dt)
    _, top_e = jax.lax.top_k(s + layer["rb"], d.top_k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    top_w = top_s / (top_s.sum(-1, keepdims=True) + RENORM_EPS) * d.scale
    held = layer["routed"]

    def one(m, expert):
        e, wg, wu, wd = expert
        chosen = (top_e == e) & routed[..., None]
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1, keepdims=True)
        y = _swiglu(h, {"wg": wg, "wu": wu, "wd": wd}, dt)
        return m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0), None

    start = _swiglu(h, layer["shared"], dt) if shared else jnp.zeros_like(h)
    m, _ = jax.lax.scan(one, start, (d.first + jnp.arange(d.held), held["wg"],
                                     held["wu"], held["wd"]))
    return h + _rms(m, layer["pf"], d.eps)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _join(mtp, e, f, same_event: bool, d: Dims, dt):
    """``[N_e(E(x_{i+1})) ; N_h(f_i)] W_eh`` at every position: ``e`` and
    ``f`` [rows, T, hidden]. A window's last position takes a zero embedding.
    ``same_event`` is the proof's switch: ``E(x_i)`` in the place of
    ``E(x_{i+1})``."""
    nxt = e if same_event else jnp.pad(e[:, 1:], ((0, 0), (0, 1), (0, 0)))
    both = jnp.concatenate([_rms(nxt, mtp["ge"], d.eps),
                            _rms(f, mtp["gh"], d.eps)], axis=-1)
    return _product(both, mtp["w_eh"], dt)
