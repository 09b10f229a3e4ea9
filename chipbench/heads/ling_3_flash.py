"""The ``ling`` session head's plain reference: Ling-3.0-flash's decoder
block (Kimi Delta Attention layers and latent-attention layers in one stack
by rule, a leading dense layer, a shared expert beside sigmoid-routed
experts chosen with an expert bias inside the best groups) over a session
window, given ONE CHIP'S SHARE of the routed experts: its tree from the
seed and its forward pass.

Nothing is imported from the program, and the block below is written from
the published descriptions the source's keys switch between (Kimi Delta
Attention, arXiv:2510.26692, and its public kernels' layer; DeepSeek-V2/V3's
latent attention and group-limited ``noaux_tc`` router; the Ling 2.x
family's modelling code), not from the program. The arithmetic is float32
(``jax.numpy`` at ``jax.default_matmul_precision("highest")``; on the
chip's machine that is the chip, in a test the CPU) over weights that
bfloat16 holds exactly, every operand of a projection passed through the
rounder. No kernel, no sort, no chunk: THE DELTA RULE IS THE RECURRENCE
ITSELF, position by position with the state held, and the held experts are
a loop with a mask. The sizes are the configuration file's top-level source
keys (``num_experts`` there is what this chip HOLDS, ``num_hidden_layers``
and ``first_k_dense_replace`` count the layers held; the published values
are under ``head.published`` and the source's indices of the held layers
under ``head.layers_held``).

``N(.)`` is an RMSNorm with a learned gain and ``rms_norm_eps``; the stream
``h`` is [rows, 16, hidden], positions ``t`` = 0..15, causal, each window
alone. Source layer ``l`` is pre-norm: ``r = h + Mixer(N1(h))``, ``h' = r +
FF(N2(r))``; its mixer is latent attention where ``(l + 1) %
layer_group_size == 0``, else KDA.

**KDA** on ``u = N1(h)``, ``num_attention_heads`` heads of ``head_dim`` keys
and values:

1. ``q, k, v = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))``: a
   depthwise causal convolution of ``short_conv_kernel_size`` taps (zero
   before the window's first event, no bias; ``linear_silu``). ``q`` and
   ``k`` L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times
   ``head_dim ** -0.5``. No rotary.
2. ``a = u Wf + dt_bias`` (full rank: ``no_kda_lora``); ``g_t =
   kda_lower_bound * sigmoid(exp(A_log_head) * a_t)`` a channel
   (``kda_safe_gate``); ``beta_t = sigmoid(u Wb)`` a head.
3. A head's state, ``head_dim x head_dim`` from zero: ``S~ = Diag(exp(g_t))
   S_{t-1}``; ``S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T``; ``o_t = S_t^T
   q_t`` (``_delta_rule``: a ``lax.scan`` over the positions).
4. ``y = N_head(o) * sigmoid(u Wg)`` (an RMSNorm over each head's channels,
   one gain of ``head_dim``: ``group_norm_size`` 1); ``Mixer = y Wo``.

**MLA**: ``q = u Wq`` as heads of ``[qk_nope_head_dim | qk_rope_head_dim]``
(``q_lora_rank`` null); ``[c | k_r] = u Wkv_a``; ``c' = N_kv(c)``; ``[k_nope
| v] = c' Wkv_b`` a head; rotary (``rope_theta``, position = the event's
index) on ``q``'s rotary part and on ``k_r``, which all heads share, by
INTERLEAVED pairs as the DeepSeek-V3 modelling code writes it
(``rope_interleave``: channels ``2i`` and ``2i + 1`` are brought to ``i`` and
``i + half`` and rotate-half follows; a dot product of two vectors so
re-ordered is the same sum); causal softmax of ``q k^T / sqrt(nope +
rope)``; ``o_head *= sigmoid(u Wgate)_head`` (``head_wise``); ``Mixer =
concat(o) Wo``.

**FF**: a SwiGLU of ``intermediate_size`` where the source's index is under
the published ``first_k_dense_replace``. Else ``s = sigmoid(x Wr)`` over
ALL routed experts; among ``s + bias`` a group of ``experts / n_group``
scores by the sum of its two largest, the ``topk_group`` best groups stay,
the ``num_experts_per_tok`` best experts inside them are chosen (equal
values: the lower index); ``w = s_chosen / (sum s_chosen + 1e-20) x
routed_scaling_factor``: the bias chooses and does not weigh. ``FF =
Shared(x) + sum over the chosen experts HELD HERE of w_e Expert_e(x)``.
What the absent experts would add is left out. A window's padding is not
routed: it takes ``Shared(x)`` alone; nothing that is scored can read it.
Both ``*_swiglu_limit_list``s are read by the source's index and a held
layer whose entry is not 0 is refused: no key says what they clamp.

After the last layer one RMSNorm. Output: ``sigmoid(N(h)[last real
position] . w_out + b_out)``.

What the source does not give, each also under ``head.assumed`` in the
configuration file: the layer rule; the gate's formula and the seeded
``A_log`` / ``dt_bias``; ``use_qk_norm`` as the L2 norm in KDA and as
``N_kv`` alone in MLA; no rotary in KDA; ``group_norm_size`` 1 as a head's
norm; full-rank ``Wf`` and ``Wg``; the head-wise gate's input; group scores
by top-2 sum; ``1e-20``; no vocabulary, no multi-token prediction, no state
or latent cache an account; padding not routed; the projector, standardised
(``_standardised``, as ``heads/openpangu_ultra.py``: PERF.md, PR 36); the
scoring head; the seeded tree's scale (``fan_in ** -0.5``, ``Wo`` and the
down matrices ``1 / sqrt(2 x 42)`` besides); **the seeded expert bias
balanced** over plausible windows (``_balancing_bias``, PR 43's rule run
under the group-limited choice), so each held expert sees about its share.

Windows go through in blocks of ``BLOCK_ROWS``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL

KDA, MLA = "kda", "mla"
RENORM_EPS = 1e-20  # beside the chosen scores' sum
L2_EPS = 1e-6       # the published kernel's, under the root


class Dims(NamedTuple):
    hidden: int
    held_layers: tuple  # the source's index of each layer held
    mixers: tuple       # of each held layer, by the rule
    dense: tuple        # whether each held layer's FF is the dense SwiGLU
    depth: int          # the published depth
    heads: int
    head_dim: int
    taps: int
    lower_bound: float
    kv_rank: int
    nope: int
    rope: int
    v: int
    dense_width: int
    experts: int        # the router's width: every routed expert of a layer
    held: int           # the routed experts this chip holds ...
    first: int          # ... starting with this one
    top_k: int
    groups: int
    kept_groups: int
    expert_width: int
    shared_width: int
    scale: float
    theta: float
    eps: float


_SWITCHES = {
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "linear_silu": True, "use_qk_norm": True, "rope_interleave": True,
    "q_lora_rank": None, "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
    "num_shared_experts": 1, "hidden_act": "silu", "use_bias": False,
    "use_qkv_bias": False, "scale_router_input": False, "up_proj_norm": False,
    "value_norm": False, "use_nGPT": False, "use_mla_nope": False,
    "rope_scaling": None, "num_kv_heads_for_linear_attn": 0,
}


def mixer_of(source_layer: int, group: int) -> str:
    return MLA if (source_layer + 1) % group == 0 else KDA


def dims_of(config: dict) -> Dims:
    """The sizes, from the configuration file's top-level source keys; the
    published counts, the held layers' source indices and the share's first
    expert from ``head``."""
    head = config.get("head", {})
    published = head.get("published", {})
    wrong = {k: config[k] for k, v in _SWITCHES.items()
             if k in config and (config[k] != v or type(config[k]) is not type(v))}
    if wrong:
        raise ValueError(f"this reference is written for {_SWITCHES}; the "
                         f"configuration has {wrong}")
    depth = published.get("num_hidden_layers", config["num_hidden_layers"])
    leading = published.get("first_k_dense_replace",
                            config["first_k_dense_replace"])
    held_layers = tuple(head.get("layers_held",
                                 range(config["num_hidden_layers"])))
    dense = tuple(l < leading for l in held_layers)
    if len(held_layers) != config["num_hidden_layers"] \
            or sum(dense) != config["first_k_dense_replace"]:
        raise ValueError(
            f"head.layers_held {held_layers} is not num_hidden_layers "
            f"{config['num_hidden_layers']} layers of which "
            f"first_k_dense_replace {config['first_k_dense_replace']} lie "
            f"under the source's {leading} leading dense layers")
    for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = config[name]
        if len(limits) != depth:
            raise ValueError(f"{name} has {len(limits)} entries for a source "
                             f"of {depth} layers")
        clamped = [l for l, d in zip(held_layers, dense)
                   if not d and limits[l] != 0]
        if clamped:
            raise ValueError(
                f"source layers {clamped} are held and {name} is "
                f"{[limits[l] for l in clamped]} there: no key of the source "
                "says what the limit clamps, so only layers whose entry is 0 "
                "can be held")
    if config["qk_head_dim"] != config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
            or config["rotary_dim"] != config["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim "
                         "and rotary_dim the rotary part")
    return Dims(
        hidden=config["hidden_size"], held_layers=held_layers,
        mixers=tuple(mixer_of(l, config["layer_group_size"])
                     for l in held_layers),
        dense=dense, depth=depth, heads=config["num_attention_heads"],
        head_dim=config["head_dim"], taps=config["short_conv_kernel_size"],
        lower_bound=float(config["kda_lower_bound"]),
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        dense_width=config["intermediate_size"],
        experts=published.get("num_experts", config["num_experts"]),
        held=config["num_experts"], first=head.get("first_expert", 0),
        top_k=config["num_experts_per_tok"], groups=config["n_group"],
        kept_groups=config["topk_group"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        scale=float(config["routed_scaling_factor"]),
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]))


# -- the tree from the seed ---------------------------------------------------

BLOCK_ELEMS = 1 << 24  # the most elements of a weight handled at once

# What the shapes of a tree do not give (the layers' kinds, experts a token,
# theta, eps, ...): ``forward`` is handed a tree and a rounder only, so it
# reads the sizes of the tree ``make_params`` made last. ``bias_moved`` is
# what that tree's expert bias does, a layer: the share of the plausible
# windows' real positions whose chosen set it changes; ``held_load`` the
# pairs each held expert sees of them, least and most, against the mean.
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
    in bfloat16 (norm gains, taps, ``A_log``, ``dt_bias``, the expert bias
    and the scoring head float32)."""
    d = _made["dims"] = dims_of(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x6C696E67), 256))
    hid, nh, hd = d.hidden, d.heads, d.head_dim
    out = 1.0 / math.sqrt(2.0 * d.depth)
    f32 = jnp.float32

    def w(*shape, scale=1.0):
        """Fan-in is the axis before the last."""
        return _normal_bf16(next(keys), tuple(shape),
                            scale / math.sqrt(shape[-2]))

    def normal(shape, scale=1.0, mean=0.0):
        return jax.random.normal(next(keys), shape, f32) * scale + mean

    def mlp(width, *stack):
        return {"wg": w(*stack, hid, width), "wu": w(*stack, hid, width),
                "wd": w(*stack, width, hid, scale=out)}

    ones = lambda n: jnp.ones((n,), f32)
    layers = []
    for kind, dense in zip(d.mixers, d.dense):
        layer = {"g1": ones(hid), "g2": ones(hid)}
        if kind == KDA:
            for name in ("wq", "wk", "wv", "wf", "wg"):
                layer[name] = w(hid, nh * hd)
            layer["wb"] = w(hid, nh)
            for name in ("tq", "tk", "tv"):
                layer[name] = normal((nh * hd, d.taps), 1.0 / math.sqrt(d.taps))
            # decays spread over (lower_bound, 0): the gate's sigmoid reads a
            # unit-spread product times a rate about one, centred under zero
            layer["a_log"] = jnp.log(jax.random.uniform(next(keys), (nh,), f32,
                                                        0.5, 1.5))
            layer["dt_bias"] = normal((nh * hd,), mean=-1.0)
            layer["gn"] = ones(hd)
            layer["wo"] = w(nh * hd, hid, scale=out)
        else:
            layer["wq"] = w(hid, nh * (d.nope + d.rope))
            layer["wkv_a"] = w(hid, d.kv_rank + d.rope)
            layer["kvn"] = ones(d.kv_rank)
            layer["wkv_b"] = w(d.kv_rank, nh * (d.nope + d.v))
            layer["wgate"] = w(hid, nh)
            layer["wo"] = w(nh * d.v, hid, scale=out)
        if dense:
            layer["dense"] = mlp(d.dense_width)
        else:
            layer["wr"] = w(hid, d.experts)
            layer["rb"] = jnp.zeros((d.experts,), f32)
            layer["shared"] = mlp(d.shared_width)
            layer["routed"] = mlp(d.expert_width, d.held)
        layers.append(layer)
    rng = np.random.default_rng([seed & (2**64 - 1), 0x6C696E67])
    params = {
        "embed": w(EVENT_WIDTH, hid),
        "layers": layers,
        "gf": ones(hid),
        "head": {"w": jnp.asarray(rng.standard_normal((hid, 1))
                                  / math.sqrt(hid), f32),
                 "b": jnp.zeros((1,), f32)},
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
        "w": jnp.asarray(w_out[:, None] * gain, f32),
        "b": jnp.asarray([centre - np.median(logits) * gain], f32)}
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


def _layer(kind: str, layer, x, lens, d: Dims, dt):
    """One decoder layer over ``x`` [rows, T, hidden]."""
    x = (_kda if kind == KDA else _attend)(layer, x, d, dt)
    return (_dense(layer, x, d, dt) if "dense" in layer
            else _moe(layer, x, lens, d, dt))


def _logits(params, windows, lengths, d: Dims, dt, hidden: bool = False) -> np.ndarray:
    """The pre-sigmoid score of every window; with ``hidden`` the
    final-normed hidden state of its last real position instead."""
    out = []
    with jax.default_matmul_precision("highest"):
        for win, lens in _blocks_of(windows, lengths):
            x = _embed(params["embed"], win, dt)
            for kind, layer in zip(d.mixers, params["layers"]):
                x = _layer(kind, layer, x, lens, d, dt)
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
    moved, loads = [], []
    _made["bias_moved"], _made["held_load"] = moved, loads
    with jax.default_matmul_precision("highest"):
        xs = [_embed(params["embed"], win, f32) for win, _ in blocks]
        for kind, layer in zip(d.mixers, params["layers"]):
            op = _kda if kind == KDA else _attend
            xs = [op(layer, x, d, f32) for x in xs]
            if "dense" in layer:
                xs = [_dense(layer, x, d, f32) for x in xs]
                continue
            s = np.concatenate([np.asarray(_router_scores(layer, x, d, f32))
                                for x in xs])[real]
            bias, share, load = _balancing_bias(s, d)
            layer["rb"] = jnp.asarray(bias, f32)
            moved.append(share)
            held = load[d.first:d.first + d.held]
            loads.append((int(held.min()), int(held.max()),
                          s.shape[0] * d.top_k / d.experts))
            xs = [_moe(layer, x, lens, d, f32) for x, (_, lens) in zip(xs, blocks)]
        hidden = [np.asarray(_score(params, x, lens, d, True))
                  for x, (_, lens) in zip(xs, blocks)]
    return np.concatenate(hidden)[:windows.shape[0]]


def _chosen_np(biased: np.ndarray, d: Dims) -> np.ndarray:
    """The group-limited choice on the host: ``biased`` [T, experts] ->
    the ``top_k`` experts [T, top_k] of each position (unordered)."""
    t, experts = biased.shape
    by_group = biased.reshape(t, d.groups, experts // d.groups)
    two = -np.partition(-by_group, 1, axis=-1)[..., :2]
    group_score = two.sum(-1)
    kept = np.argpartition(-group_score, d.kept_groups - 1, axis=1)[:, :d.kept_groups]
    keep = np.zeros((t, d.groups), bool)
    keep[np.arange(t)[:, None], kept] = True
    masked = np.where(keep[:, :, None], by_group, -np.inf).reshape(t, experts)
    return np.argpartition(-masked, d.top_k - 1, axis=1)[:, :d.top_k]


def _balancing_bias(scores: np.ndarray, d: Dims):
    """The expert bias that evens the experts' loads over the positions
    ``scores`` [T, experts] (the router's sigmoid scores) under the
    group-limited choice, by the rule the published model's bias is trained
    with: it starts at zero and moves up for an expert chosen less than the
    mean load, down for one chosen more, by a step that shrinks to nothing.
    Returns it (float32), the share of the positions whose chosen set it
    changes, and every expert's load with it."""
    s = scores.astype(np.float64)
    experts = s.shape[1]
    mean_load = s.shape[0] * d.top_k / experts
    bias = np.zeros(experts)
    step = 0.25 * float(s.std())
    for turn in range(BALANCE_TURNS):
        load = np.bincount(_chosen_np(s + bias, d).ravel(), minlength=experts)
        bias += step * (1.0 - turn / BALANCE_TURNS) * np.sign(mean_load - load)
    bias = bias.astype(F32)
    bare = np.sort(_chosen_np(s, d), 1)
    biased = np.sort(_chosen_np(s + bias.astype(np.float64), d), 1)
    load = np.bincount(biased.ravel(), minlength=experts)
    return bias, float((bare != biased).any(axis=1).mean()), load


# -- the parts ----------------------------------------------------------------


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


def _silu(x):
    return x * _sigmoid(x)


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
    mid = _silu(_product(u, w["wg"], dt)) * _product(u, w["wu"], dt)
    return _product(mid, w["wd"], dt)


@partial(jax.jit, static_argnums=(2,))
def _embed(w_in, windows, dt):
    return _rnd(windows, dt) @ _rnd(w_in, dt)


def _causal_conv(x, taps):
    """The depthwise causal convolution over ``x`` [rows, T, channels]: tap
    ``k`` of ``taps`` [channels, L] reads the event ``L - 1 - k`` before,
    zero before the window's first event; no bias."""
    rows, t, ch = x.shape
    n_taps = taps.shape[1]
    conv = jnp.zeros_like(x)
    for k in range(n_taps):
        back = n_taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros((rows, back, ch), x.dtype), x[:, :t - back]], axis=1)
        conv = conv + taps[:, k] * shifted
    return conv


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule, position by position: ``q``, ``k`` [rows, T,
    heads, dk], ``v`` [rows, T, heads, dv], ``g`` [rows, T, heads, dk] (the
    log-decay a channel), ``beta`` [rows, T, heads] -> ``o`` [rows, T,
    heads, dv]. The state [rows, heads, dk, dv] starts at zero and is held
    through the window: it decays a channel, is READ through the key, takes
    the difference from the value times ``beta`` as a rank-one write, and
    is read through the query."""
    rows, _, heads, dk = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("rhkv,rhk->rhv", state, k_t)
        write = (v_t - seen) * b_t[..., None]
        state = state + k_t[..., None] * write[..., None, :]
        return state, jnp.einsum("rhkv,rhk->rhv", state, q_t)

    first = lambda a: jnp.moveaxis(a, 1, 0)
    _, out = jax.lax.scan(step, jnp.zeros((rows, heads, dk, v.shape[-1]), q.dtype),
                          (first(q), first(k), first(v), first(g), first(beta)))
    return jnp.moveaxis(out, 0, 1)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@partial(jax.jit, static_argnums=(2, 3))
def _kda(layer, x, d: Dims, dt):
    """``x + KDA(N1(x))``."""
    rows, t, hid = x.shape
    nh, hd = d.heads, d.head_dim
    u = _rms(x, layer["g1"], d.eps).reshape(rows * t, hid)
    heads = lambda a: a.reshape(rows, t, nh, hd)

    def conv(name, taps):
        return heads(_silu(_causal_conv(
            _product(u, layer[name], dt).reshape(rows, t, nh * hd), layer[taps])))

    q = _l2(conv("wq", "tq")) / math.sqrt(hd)
    k = _l2(conv("wk", "tk"))
    v = conv("wv", "tv")
    a = heads(_product(u, layer["wf"], dt) + layer["dt_bias"])
    g = d.lower_bound * _sigmoid(jnp.exp(layer["a_log"])[:, None] * a)
    beta = _sigmoid(_product(u, layer["wb"], dt)).reshape(rows, t, nh)
    o = _delta_rule(q, k, v, g, beta)
    y = (_rms(o, layer["gn"], d.eps).reshape(rows * t, nh * hd)
         * _sigmoid(_product(u, layer["wg"], dt)))
    return x + _product(y, layer["wo"], dt).reshape(x.shape)


def _rope_interleaved(x, d: Dims):
    """Rotary embedding as the DeepSeek-V3 modelling code writes it under
    ``rope_interleave``, over all ``qk_rope_head_dim`` channels of ``x``
    [rows, T, heads, rope]: channels ``2i`` and ``2i + 1`` are brought to
    ``i`` and ``i + half``, then ``x cos + rotate_half(x) sin`` with angles
    ``t x theta ** (-2i / rope)`` and ``cat(freqs, freqs)``."""
    half = d.rope // 2
    x = jnp.moveaxis(x.reshape(*x.shape[:-1], half, 2), -1, -2).reshape(x.shape)
    inv = d.theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d.rope)
    freqs = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


@partial(jax.jit, static_argnums=(2, 3))
def _attend(layer, x, d: Dims, dt):
    """``x + MLA(N1(x))``."""
    rows, t, hid = x.shape
    a = _rms(x, layer["g1"], d.eps).reshape(rows * t, hid)
    q = _product(a, layer["wq"], dt).reshape(rows, t, d.heads, d.nope + d.rope)
    kv = _product(a, layer["wkv_a"], dt)
    ckv = _rms(kv[:, :d.kv_rank], layer["kvn"], d.eps)
    k_rope = _rope_interleaved(kv[:, d.kv_rank:].reshape(rows, t, 1, d.rope), d)
    kvb = _product(ckv, layer["wkv_b"], dt).reshape(rows, t, d.heads, d.nope + d.v)
    q = jnp.concatenate([q[..., :d.nope], _rope_interleaved(q[..., d.nope:], d)],
                        axis=-1)
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
    gate = _sigmoid(_product(a, layer["wgate"], dt)).reshape(rows, t, d.heads, 1)
    o = _product((heads * gate).reshape(rows * t, d.heads * d.v), layer["wo"], dt)
    return x + o.reshape(x.shape)


@partial(jax.jit, static_argnums=(2, 3))
def _dense(layer, x, d: Dims, dt):
    u = _rms(x, layer["g2"], d.eps).reshape(-1, d.hidden)
    return x + _swiglu(u, layer["dense"], dt).reshape(x.shape)


def _scores(layer, u, dt):
    """The router's sigmoid scores of ``u`` [positions, hidden], float32."""
    return _sigmoid(_rnd(u, dt) @ _rnd(layer["wr"], dt))


@partial(jax.jit, static_argnums=(2, 3))
def _router_scores(layer, x, d: Dims, dt):
    return _scores(layer, _rms(x, layer["g2"], d.eps).reshape(-1, d.hidden), dt)


def _choose(s, bias, d: Dims):
    """``(experts, weights)`` [positions, top_k] of scores ``s``: the bias
    chooses, inside the ``kept_groups`` groups whose two largest biased
    scores sum highest (equal values: the lower index), and the scores
    weigh."""
    biased = s + bias
    by_group = biased.reshape(s.shape[0], d.groups, -1)
    group_score = jax.lax.top_k(by_group, 2)[0].sum(-1)
    _, kept = jax.lax.top_k(group_score, d.kept_groups)
    keep = (kept[:, :, None] == jnp.arange(d.groups)).any(axis=1)
    masked = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(s.shape)
    _, sel = jax.lax.top_k(masked, d.top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (w.sum(-1, keepdims=True) + RENORM_EPS) * d.scale


@partial(jax.jit, static_argnums=(3, 4))
def _moe(layer, x, lengths, d: Dims, dt):
    """The shared expert, and one held expert at a time over EVERY position
    with a mask: a position takes expert ``e``'s result, times its weight,
    iff it holds an event and the router chose ``e`` for it."""
    real = (jnp.arange(x.shape[1])[None, :] < lengths[:, None]).reshape(-1, 1)
    u = _rms(x, layer["g2"], d.eps).reshape(-1, d.hidden)
    sel, w = _choose(_scores(layer, u, dt), layer["rb"], d)
    routed = layer["routed"]

    def one(m, expert):
        e, wg, wu, wd = expert
        chosen = (sel == e) & real
        weight = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1, keepdims=True)
        y = _swiglu(u, {"wg": wg, "wu": wu, "wd": wd}, dt)
        return m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0), None

    m, _ = jax.lax.scan(one, _swiglu(u, layer["shared"], dt),
                        (d.first + jnp.arange(d.held), routed["wg"],
                         routed["wu"], routed["wd"]))
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
