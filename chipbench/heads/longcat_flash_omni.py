"""The ``longcat`` session head's plain reference: LongCat-Flash-Omni's
language model's decoder layer (two latent attentions and two dense MLPs a
layer, one shortcut expert branch across them whose router is wider than its
experts) over a session window, given ONE CHIP'S SHARE of the routed experts
and of the attention heads: its tree from the seed and its forward pass.

Nothing is imported from the program. The arithmetic is float32
(``jax.numpy`` at ``jax.default_matmul_precision("highest")``, as every
reference under ``heads/`` since PR 34: on the chip's machine that is the
chip, in a test the CPU) over weights that bfloat16 holds exactly, every
operand of a product passed through the rounder. No kernel, no sort, no
skipped block and nothing narrowed: every query meets EVERY key of its window
and the mask decides (a block of queries at a time, so that the 16 x 2048 x
2048 scores of an attention never stand at once), the held experts are a
dense loop with a mask, and every part of every layer runs at EVERY position;
one position a row is read at the end. The sizes are the configuration file's
top-level source keys (``n_routed_experts`` and ``num_attention_heads`` there
are what this chip HOLDS; the published counts are ``head.published``'s).

It follows transformers' ``models/longcat_flash/modeling_longcat_flash.py``
(the router 120-145, the expert module with its ``nn.Identity`` experts
148-197, latent attention 288-410, the double layer 413-494). Per layer, over
the residual stream ``h`` [rows, T, hidden] (``N`` an RMSNorm with
``rms_norm_eps``):

1. ``h += Attn_0(N_in0(h))``
2. ``u = N_post0(h)``; ``s = MoE(u)``, kept aside; ``h += MLP_0(u)``
3. ``h += Attn_1(N_in1(h))``
4. ``h += MLP_1(N_post1(h)) + s``

``MLP``: a SwiGLU of ``ffn_hidden_size``, no bias. ``Attn`` over a normed
``a``: ``cq = N_q(a Wq_a)``; ``q = cq Wq_b`` -> heads of ``[q_nope 128 |
q_rope 64]``, BOTH times ``sqrt(hidden_size / q_lora_rank)``
(``mla_scale_q_lora``); ``a Wkv_a`` -> ``[ckv 512 | k_rope 64]``; ``ckv =
N_kv(ckv) x sqrt(hidden_size / kv_lora_rank)`` (``mla_scale_kv_lora``); ``ckv
Wkv_b`` -> heads of ``[k_nope 128 | v 128]``. Rotary as the library's
``apply_rotary_pos_emb_interleave``: the 64 rotary channels of ``q`` a head
and of the one ``k_rope`` are taken apart into even and odd (pair ``c`` is
channels ``2c`` and ``2c + 1``), then turned as rotate-half pairs,
``inv_freq_c = rope_theta^(-2c / 64)``, position = the event's index. Scores
``(q_nope . k_nope + q_rope . k_rope) / sqrt(192)``, kept where ``j <= i``,
softmax, times ``v``; ``Wo``, no bias.

``MoE`` over ``u``: ``p = softmax(u Wr)`` over ``n_routed_experts +
zero_expert_num`` = 768 outputs; the ``moe_topk`` largest of ``p + b`` chosen
(equal: the lower index); ``w_e = routed_scaling_factor x p_e``, NOT
renormalised. ``s = sum over chosen e < 512 HELD HERE of w_e Expert_e(u) +
(sum over chosen e >= 512 of w_e) u``: outputs 512-767 are identity experts.

**The shares** (model-configs guide, section 4). Experts: this chip holds
experts ``first_expert ..`` of the 512; what the absent ones would add is
left out. The identity experts hold no weight and are every chip's, each for
its own positions: computed here whole (and counted once where shares are
added up). Heads: this chip holds the first ``num_attention_heads`` of the 64
(``Wq_b``'s and ``Wkv_b``'s columns and ``Wo``'s rows of those heads); what
the other heads would add to ``Wo``'s product is left out. A window's padding
is not routed and takes no identity term.

Departures from the library's code and what the source does not give, each
also under ``head.assumed`` in the configuration file:

- The router's product multiplies operands in the stated dtype like every
  other product here and in the program (the library multiplies float32
  copies); the softmax, the bias and the choice are float32.
- ``router_bias`` is absent from the source: no bias on the router's product.
  The selection bias ``b`` (``e_score_correction_bias``, a buffer of zeros in
  the library) is seeded as what it is for: the loss-free balancing rule run
  on the seeded router over plausible windows (``_balancing_bias``), so the
  768 outputs are chosen about alike, a third of the chosen pairs fall on
  identity experts and each held expert sees about ``positions x 12 / 768``.
- No ``rope_scaling`` in the source: plain rates, the softmax scale
  ``192^-0.5`` with no YaRN factor.
- Events enter as ``inputs_embeds`` through a projector ``x @ W_in`` (12 ->
  hidden, seeded so that it reads each event column standardised); no row of
  the 131,072-row vocabulary is held; a sequence-classification head (one
  float32 output column) stands in the place of the output head. The omni
  model's audio and vision encoders and its codec decoder are absent.
- The seeded tree (``seeded_tree_scale``): every matrix ``fan_in ** -0.5``
  (``Wo``'s fan-in that of all 64 heads) and every gain 1, but two: the
  router's matrix at ``ROUTER_GAIN`` times that, so that the 12 chosen carry
  most of the softmax's mass (0.71 of it at 3; 0.12 at 1, where ``6 p``
  would add 0.7 of a position and renormalising would multiply it by eight)
  and the 12th and 13th probabilities stand 7% apart; the dense MLPs' down
  matrices at ``DENSE_DOWN``, so that they carry as much of a layer as its
  attentions and where the expert branch joins matters.

Six switches are the proof's, never the benchmark's (chipbench/aa/proof):
``WITHOUT_ZERO`` (no identity term), ``SHORTCUT_EARLY`` (the expert branch
joins the stream at step 2, before the second attention), ``RENORMALISED``
(the twelve weights divided by their sum, times 6), ``WITHOUT_SCALES``
(neither latent scale), ``ROTATE_HALF`` (the rotary channels not taken apart
first: pairs ``c`` and ``c + 32``), ``HALF_THE_HEADS`` (the upper half of the
held heads left out of ``Wo``'s product). With one set, rows leave the
program's answers.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL

WITHOUT_ZERO = False
SHORTCUT_EARLY = False
RENORMALISED = False
WITHOUT_SCALES = False
ROTATE_HALF = False
HALF_THE_HEADS = False


class Switches(NamedTuple):
    without_zero: bool = False
    shortcut_early: bool = False
    renormalised: bool = False
    without_scales: bool = False
    rotate_half: bool = False
    half_the_heads: bool = False


def switches() -> Switches:
    """The proof's switches as they stand now."""
    return Switches(WITHOUT_ZERO, SHORTCUT_EARLY, RENORMALISED, WITHOUT_SCALES,
                    ROTATE_HALF, HALF_THE_HEADS)


SOUND = Switches()


class Dims(NamedTuple):
    hidden: int
    layers: int        # double layers held
    heads: int         # the attention heads this chip holds
    all_heads: int     # the published count
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    dv: int
    dense_width: int
    outputs: int       # the router's width: real and identity experts
    real: int          # the published real experts
    held: int          # the real experts this chip holds ...
    first: int         # ... starting with this one
    top_k: int
    expert_width: int
    scale: float
    q_scale: float
    kv_scale: float
    inv_freq: tuple
    eps: float
    events: int        # the deployment's window


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys; the
    published counts and the shares' first expert and head from ``head``."""
    head = config.get("head", {})
    published = head.get("published", {})
    if (config["zero_expert_type"] != "identity" or config["attention_method"] != "MLA"
            or config["attention_bias"] or config.get("rope_scaling")):
        raise ValueError("this reference is written for identity zero experts "
                         "and latent attention without a bias or rope scaling")
    hidden, rope = config["hidden_size"], config["qk_rope_head_dim"]
    real = published.get("n_routed_experts", config["n_routed_experts"])
    rates = float(config["rope_theta"]) ** (
        -2.0 * np.arange(rope // 2, dtype=np.float64) / rope)
    return Dims(
        hidden=hidden, layers=config["num_layers"],
        heads=config["num_attention_heads"],
        all_heads=published.get("num_attention_heads",
                                config["num_attention_heads"]),
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=rope, dv=config["v_head_dim"],
        dense_width=config["ffn_hidden_size"],
        outputs=real + config["zero_expert_num"], real=real,
        held=config["n_routed_experts"], first=head.get("first_expert", 0),
        top_k=config["moe_topk"], expert_width=config["expert_ffn_hidden_size"],
        scale=float(config["routed_scaling_factor"]),
        q_scale=(math.sqrt(hidden / config["q_lora_rank"])
                 if config["mla_scale_q_lora"] else 1.0),
        kv_scale=(math.sqrt(hidden / config["kv_lora_rank"])
                  if config["mla_scale_kv_lora"] else 1.0),
        inv_freq=tuple(rates), eps=float(config["rms_norm_eps"]),
        events=int(config.get("env", {}).get("SESSION_EVENTS", 16)))


# -- the tree from the seed ---------------------------------------------------

BLOCK_ELEMS = 1 << 24  # the most elements of a weight handled at once
HEAD_CANDIDATES = 16
# The program's head rounds its operands itself (``decoder_parts.mm`` casts
# both to the stated dtype), on the CPU as on the MXU: ``harness.judge``
# reads a rehearsal's reference at the stated dtype too.
CASTS_OPERANDS = True
QUERY_BLOCK = 256      # queries that meet all keys of their window at once
CALIBRATION_WINDOWS = 16
# What the router's seeded matrix is scaled by. At 1 its logits are ~N(0, 1)
# and the 12 largest of 768 probabilities hold 0.12 of the softmax's mass:
# ``6 p`` would weigh the whole branch at 0.7, and the weights renormalised
# would be eight times the source's. At 3 they hold 0.71 (0.56-0.87 over
# positions), the weights sum to about 4.3 where renormalised ones sum to 6,
# and the 12th and 13th probabilities stand 7% apart (the median), far more
# than two float32 sums in another order differ by.
ROUTER_GAIN = 3.0
# What the dense MLPs' seeded down matrices are scaled by. At unit scales a
# dense MLP adds 0.60 in rms, an attention 1.4-1.5 (``v`` carries the
# key-value latent's scale and a concentrated softmax hands it on) and the
# expert branch 0.8-1.2, nearly all of it the identity term, which lies along
# the stream as it stood after the first attention: where the expert branch
# joins then re-weighs little, and a reference that adds it two sublayers
# early read 6 roundings from the sound one (my chip runs, PR 68:
# _chip/longcat_tune.py). At 2 the two dense MLPs carry as much of a layer as
# its attentions.
DENSE_DOWN = 2.0

# What the shapes of a tree do not give (experts a position, the shares'
# first expert and head, theta, eps): ``forward`` is handed a tree and a
# rounder only, so it reads the sizes of the tree ``make_params`` made last.
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
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x6C636174), 256))
    hid, qk = d.hidden, d.nope + d.rope

    def w(*shape, scale=1.0, fan_in=None):
        """Fan-in is the axis before the last, where none is given."""
        return _normal_bf16(next(keys), tuple(shape),
                            scale / math.sqrt(fan_in or shape[-2]))

    def mlp(width, *stack, down=1.0):
        return {"wg": w(*stack, hid, width), "wu": w(*stack, hid, width),
                "wd": w(*stack, width, hid, scale=down)}

    ones = lambda n, value=1.0: jnp.full((n,), value, jnp.float32)

    def attention() -> dict:
        return {"wq_a": w(hid, d.q_rank), "qn": ones(d.q_rank),
                "wq_b": w(d.q_rank, d.heads * qk),
                "wkv_a": w(hid, d.kv_rank + d.rope), "kvn": ones(d.kv_rank),
                "wkv_b": w(d.kv_rank, d.heads * (d.nope + d.dv)),
                # the fan-in of the published heads, whose sum the held
                # heads' part is a part of
                "wo": w(d.heads * d.dv, hid, fan_in=d.all_heads * d.dv)}

    def half() -> dict:
        return {"g_in": ones(hid), "g_post": ones(hid), "attn": attention(),
                "dense": mlp(d.dense_width, down=DENSE_DOWN)}

    rng = np.random.default_rng([seed & (2**64 - 1), 0x6C636174])
    params = {
        "embed": w(EVENT_WIDTH, hid),
        "layers": [{"halves": [half(), half()],
                    "wr": w(hid, d.outputs, scale=ROUTER_GAIN),
                    "rb": ones(d.outputs, 0.0),
                    "routed": mlp(d.expert_width, d.held)}
                   for _ in range(d.layers)],
        "gf": ones(hid),
        "head": {"w": jnp.asarray(rng.standard_normal((hid, 1))
                                  / math.sqrt(hid), jnp.float32),
                 "b": jnp.zeros((1,), jnp.float32)},
    }
    # A random head answers nearly the same for every window, far from the
    # fold threshold on most seeds, and the check would then never see its
    # output. Scale and shift the last layer so that over plausible windows
    # of the deployment's depth the logits spread by about one and centre on
    # the threshold (heads/keye_vl2.py), along the one of ``HEAD_CANDIDATES``
    # seeded directions along which these windows' read states spread most.
    win, lengths = plausible_windows(rng, CALIBRATION_WINDOWS, d.events)
    params["embed"] = _standardised(params["embed"], win, lengths)
    read = _bias_and_read(params, win, lengths, d).astype(np.float64)
    candidates = rng.standard_normal((hid, HEAD_CANDIDATES)) / math.sqrt(hid)
    w_out = candidates[:, int(np.argmax((read @ candidates).std(axis=0)))]
    logits = read @ w_out
    gain = 1.0 / max(float(logits.std()), 1e-6)
    centre = math.log(FLAG_THRESHOLD / (1.0 - FLAG_THRESHOLD))
    params["head"] = {
        "w": jnp.asarray(w_out[:, None] * gain, jnp.float32),
        "b": jnp.asarray([centre - np.median(logits) * gain], jnp.float32)}
    return params


def plausible_windows(rng, n: int, t: int):
    """``n`` windows of ``t`` positions, half full to full, as the
    deployment's look when they are scored: log-amounts and the mix of
    transaction types as the traffic's; a preloaded event's gap is the one to
    the round before its own, 20 s to 15 min; the newest event, the one that
    is scored, arrives years after the preloaded history ends: an account's
    first event of a run, which most rows of a check are."""
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


# -- the seeded selection bias -------------------------------------------------

BALANCE_TURNS = 200
BALANCE_POSITIONS = 8192  # the most positions a layer's bias is balanced over


def _bias_and_read(params, windows, lengths, d: Dims) -> np.ndarray:
    """The plausible windows through the tree in float32, layer by layer over
    all blocks: at each layer the selection bias is set from the router's
    probabilities over the positions it routes (``_balancing_bias``;
    ``params`` is updated in place) before the expert branch is computed.
    Returns the normed state each window's score reads, which the scoring
    head is then fitted to. Between sublayers the blocks' states wait on the
    host (the harness calls this while the server still holds its own tree
    beside this one), one block on the device at a time."""
    f32 = jnp.float32
    blocks = _blocks_of(windows, lengths)
    reals = [_real(lens, win.shape[1]) for win, lens in blocks]
    _made["bias_moved"], _made["identity_share"] = [], []

    def each(fn, *lists):
        """``fn`` over the blocks, one on the device at a time."""
        return [np.asarray(fn(*args)) for args in zip(*lists, strict=True)]

    with jax.default_matmul_precision("highest"):
        hs = each(lambda win: _embed(params["embed"], win, f32),
                  [win for win, _ in blocks])
        for layer in params["layers"]:
            first, second = layer["halves"]
            hs = each(lambda h: _attend(first, h, d, f32, SOUND), hs)
            us = each(lambda h: _rms(h, first["g_post"], d.eps), hs)
            p = np.concatenate([
                np.asarray(_router_probabilities(layer, u, f32)).reshape(
                    -1, d.outputs)[np.asarray(r).reshape(-1)]
                for u, r in zip(us, reals, strict=True)])
            bias, moved, identity = _balancing_bias(p, d.top_k, d.real)
            layer["rb"] = jnp.asarray(bias, f32)
            _made["bias_moved"].append(moved)
            _made["identity_share"].append(identity)
            ss = each(lambda u, r: _moe(layer, u, r, d, f32, SOUND), us, reals)
            hs = each(lambda h, u: h + _swiglu(u, first["dense"], f32), hs, us)
            hs = each(lambda h: _attend(second, h, d, f32, SOUND), hs)
            hs = each(lambda h, s: _dense_add(second, h, d, f32) + s, hs, ss)
        fs = each(lambda h: _rms(h, params["gf"], d.eps), hs)
    n = windows.shape[0]
    return np.concatenate([np.asarray(_at(f, np.asarray(lens) - 1))
                           for f, (_, lens) in zip(fs, blocks, strict=True)])[:n]


@partial(jax.jit, static_argnums=(1, 2))
def _balanced(p, top_k: int, turns: int):
    """``turns`` of the balancing rule over probabilities ``p`` [positions,
    outputs]: the bias moves up for an output chosen less than the mean
    load, down for one chosen more, by a step that shrinks to nothing."""
    n, outputs = p.shape
    mean_load = n * top_k / outputs
    step = 0.25 * jnp.std(p)

    def load(bias):
        kth = jax.lax.top_k(p + bias, top_k)[0][:, -1:]
        return jnp.sum(p + bias >= kth, axis=0)

    def turn(i, bias):
        return bias + step * (1.0 - i / turns) * jnp.sign(mean_load - load(bias))

    return jax.lax.fori_loop(0, turns, turn, jnp.zeros((outputs,), jnp.float32))


def _balancing_bias(p: np.ndarray, top_k: int, real: int):
    """The selection bias that evens the outputs' loads over the positions
    ``p`` [positions, outputs] (the router's probabilities), by the rule the
    published family's bias is trained with. Returns it (float32), the share
    of the positions whose chosen set it changes, and the share of the chosen
    pairs that fall on identity experts (outputs ``real ..``) under it."""
    p = np.asarray(p, F32)
    if p.shape[0] > BALANCE_POSITIONS:  # an even sample of them
        p = p[np.linspace(0, p.shape[0] - 1, BALANCE_POSITIONS).astype(np.int64)]
    bias = np.asarray(_balanced(jnp.asarray(p), top_k, BALANCE_TURNS))

    def chosen(b):
        return np.sort(np.argpartition(-(p + b), top_k - 1, axis=1)[:, :top_k], 1)

    bare, biased = chosen(0.0), chosen(bias)
    return (bias, float((bare != biased).any(axis=1).mean()),
            float((biased >= real).mean()))


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
    out = [np.asarray(_block_logits(params, win, lens, d, dt, switches()))
           for win, lens in _blocks_of(windows, lengths)]
    return np.concatenate(out)[:windows.shape[0]]


def _block_logits(params, windows, lengths, d: Dims, dt, sw: Switches):
    """Every part of every layer at every position, then the one read."""
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], windows, dt)
        real = _real(lengths, windows.shape[1])
        for layer in params["layers"]:
            first, second = layer["halves"]
            h = _attend(first, h, d, dt, sw)
            u = _rms(h, first["g_post"], d.eps)
            s = _moe(layer, u, real, d, dt, sw)
            h = h + _swiglu(u, first["dense"], dt)
            if sw.shortcut_early:
                h = h + s
            h = _attend(second, h, d, dt, sw)
            h = _dense_add(second, h, d, dt)
            if not sw.shortcut_early:
                h = h + s
        f = _rms(h, params["gf"], d.eps)
        return _head(params, _at(f, lengths - 1))


def _real(lengths, t: int):
    """[rows, T] bool: the real events of each window."""
    return jnp.arange(t)[None, :] < lengths[:, None]


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


@partial(jax.jit, static_argnums=(2,))
def _swiglu(x, w, dt):
    gate = _product(x, w["wg"], dt)
    mid = gate / (1.0 + jnp.exp(-gate)) * _product(x, w["wu"], dt)
    return _product(mid, w["wd"], dt)


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


def _rope(x, d: Dims, rotate_half: bool):
    """Rotary embedding on ``x`` [rows, T, heads, rope] as the library's
    ``apply_rotary_pos_emb_interleave`` writes it: the channels taken apart
    into even and odd (``view(d // 2, 2).transpose``), then ``x cos +
    rotate_half(x) sin`` with ``cat(freqs, freqs)``. ``rotate_half`` is the
    proof's switch: the channels are not taken apart first."""
    if not rotate_half:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    freqs = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(np.array(d.inv_freq), jnp.float32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    half = d.rope // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _attend(half, h, d: Dims, dt, sw: Switches):
    """``h + Attn(N_in(h))`` over the held heads."""
    rows, t, _ = h.shape
    layer = half["attn"]
    a = _rms(h, half["g_in"], d.eps)
    q_scale, kv_scale = ((1.0, 1.0) if sw.without_scales
                         else (d.q_scale, d.kv_scale))
    cq = _rms(_product(a, layer["wq_a"], dt), layer["qn"], d.eps)
    q = _product(cq, layer["wq_b"], dt).reshape(rows, t, d.heads, d.nope + d.rope)
    q = q * q_scale
    kv = _product(a, layer["wkv_a"], dt)
    ckv = _rms(kv[..., :d.kv_rank], layer["kvn"], d.eps) * kv_scale
    kvb = _product(ckv, layer["wkv_b"], dt).reshape(rows, t, d.heads, d.nope + d.dv)
    q_rope = _rope(q[..., d.nope:], d, sw.rotate_half)
    k_rope = _rope(kv[..., None, d.kv_rank:], d, sw.rotate_half)
    qs = _rnd(jnp.concatenate([q[..., :d.nope], q_rope], axis=-1), dt)
    ks = _rnd(jnp.concatenate(
        [kvb[..., :d.nope],
         jnp.broadcast_to(k_rope, (rows, t, d.heads, d.rope))], axis=-1), dt)
    vs = _rnd(kvb[..., d.nope:], dt)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(qs, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(rows, -1, block, d.heads, d.nope + d.rope), 1, 0)
    j = jnp.arange(t)[None, :]

    def one_block(args):
        qi, lo = args                           # [rows, block, heads, qk]
        keep = j <= lo + jnp.arange(block)[:, None]
        sc = jnp.einsum("rthd,rshd->rhts", qi, ks) / math.sqrt(d.nope + d.rope)
        sc = jnp.where(keep, sc, -jnp.inf)
        sc = sc - sc.max(-1, keepdims=True)
        p = jnp.exp(sc)
        p = p / p.sum(-1, keepdims=True)
        return jnp.einsum("rhts,rshd->rthd", _rnd(p, dt), vs)

    heads = jax.lax.map(one_block, (qb, jnp.arange(qb.shape[0]) * block))
    heads = jnp.moveaxis(heads, 0, 1).reshape(rows, t + pad, d.heads, d.dv)[:, :t]
    wo = layer["wo"]
    if sw.half_the_heads:
        kept = d.heads // 2
        heads, wo = heads[:, :, :kept], wo[:kept * d.dv]
    return h + _product(heads.reshape(rows, t, -1), wo, dt)


@partial(jax.jit, static_argnums=(2, 3))
def _dense_add(half, h, d: Dims, dt):
    return h + _swiglu(_rms(h, half["g_post"], d.eps), half["dense"], dt)


@partial(jax.jit, static_argnums=(2,))
def _router_probabilities(layer, u, dt):
    """The router's softmax over ALL its outputs of ``u`` [rows, T, hidden],
    float32."""
    z = _rnd(u, dt) @ _rnd(layer["wr"], dt)
    z = jnp.exp(z - z.max(-1, keepdims=True))
    return z / z.sum(-1, keepdims=True)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _moe(layer, u, routed, d: Dims, dt, sw: Switches):
    """``MoE(u)``: one held expert at a time over EVERY position with a mask
    (a position takes expert ``e``'s result, times its weight, iff it is
    ``routed`` [rows, T] and the router chose ``e`` for it), plus the
    identity experts' ``(sum of their weights) u``. The bias chooses (equal
    sums: the lower index), the probabilities weigh, times the scale and NOT
    renormalised."""
    p = _router_probabilities(layer, u, dt)
    _, top_e = jax.lax.top_k(p + layer["rb"], d.top_k)
    top_w = jnp.take_along_axis(p, top_e, axis=-1)
    if sw.renormalised:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    top_w = top_w * d.scale
    held = layer["routed"]

    def one(m, expert):
        e, wg, wu, wd = expert
        chosen = (top_e == e) & routed[..., None]
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1, keepdims=True)
        y = _swiglu(u, {"wg": wg, "wu": wu, "wd": wd}, dt)
        return m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0), None

    s, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (d.first + jnp.arange(d.held), held["wg"], held["wu"],
                         held["wd"]))
    if sw.without_zero:
        return s
    zero = jnp.sum(jnp.where((top_e >= d.real) & routed[..., None], top_w, 0.0),
                   axis=-1, keepdims=True)
    return s + zero * u


def identity_share(params: dict, windows: np.ndarray, lengths: np.ndarray,
                   rnd) -> list[float]:
    """The share of the chosen pairs of real positions that fall on identity
    experts, a layer, over ``windows`` at the rounder's precision: what the
    device alone could count in the program (PERF.md Open question 21a), read
    here off the reference."""
    d, dt = _made["dims"], operand_dtype(rnd)
    counts = np.zeros((d.layers, 2))
    for win, lens in _blocks_of(np.asarray(windows, F32), lengths):
        with jax.default_matmul_precision("highest"):
            h = _embed(params["embed"], win, dt)
            real = _real(lens, win.shape[1])
            for i, layer in enumerate(params["layers"]):
                first, second = layer["halves"]
                h = _attend(first, h, d, dt, SOUND)
                u = _rms(h, first["g_post"], d.eps)
                p = _router_probabilities(layer, u, dt)
                _, top_e = jax.lax.top_k(p + layer["rb"], d.top_k)
                counts[i] += (int(((top_e >= d.real) & real[..., None]).sum()),
                              int(real.sum()) * d.top_k)
                h = h + _swiglu(u, first["dense"], dt)
                h = _attend(second, h, d, dt, SOUND)
                h = _dense_add(second, h, d, dt) + _moe(layer, u, real, d, dt, SOUND)
    return [float(a / max(b, 1)) for a, b in counts]
