"""Session heads: the models the fused session step runs over an
account's post-append event window.

``HEADS`` is the one table: a ``SESSION_HEAD`` name -> its ``Head`` row.
The program (serve/index_program.py) takes the row's ``scores`` as an
argument and the parameters as a traced tree; the server's gauges
(serve/session_state.py) read the rest of the row. A new backbone is a
module with the backbones' surface (``init_backbone(key, cfg)``,
``backbone_scores(params, window, lengths, cfg)``, ``layer_kinds(cfg)``)
and one row, ``_backbone(module, cfg)``.
"""

from __future__ import annotations

from collections.abc import Callable, Mapping
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from igaming_platform_tpu.models import (
    falconh1_backbone,
    keye_backbone,
    kexaone_backbone,
    lfm2_backbone,
    ling_backbone,
    longcat_backbone,
    mellum_backbone,
    pangu_backbone,
    phi4flash_backbone,
    xing_backbone,
)
from igaming_platform_tpu.models.sequence import (
    EVENT_DIM,
    SeqConfig,
    init_sequence_model,
    sequence_forward,
)

# One-hot sub-columns of the event vector (models/sequence.encode_event:
# [log-amount, log-dt, 8-way tx-type one-hot, ...]) the pattern head reads.
_COL_DEPOSIT = 2 + 0
_COL_BET = 2 + 2


def pattern_scores(window, lengths):
    """Deterministic coordinated-cycling detector (the ``pattern`` head,
    the session analog of models.mock_model: hand-tuned, paramless,
    replay-exact by construction).

    High iff the window shows bet/deposit CYCLING at a regular cadence
    with consistent amounts — the coordinated-ring shape
    (train/fraudgen.FraudRing) — each factor in [0, 1]:

    - ``bd_frac``   fraction of events that are bets or deposits;
    - ``alt_frac``  fraction of adjacent pairs alternating bet<->deposit;
    - ``reg``       exp(-4 * var(log-dt)) over events 1.. — machine-paced
                    cycles have near-constant gaps, humans don't;
    - ``acons``     exp(-2 * var(log-amount)) — ring members push
                    near-identical amounts.
    """
    n = window.shape[1]
    k = jnp.arange(n)[None, :]
    m = (k < lengths[:, None]).astype(jnp.float32)  # [B, N] valid-event mask
    cnt = jnp.maximum(jnp.sum(m, axis=1), 1.0)

    log_amt = window[..., 0]
    log_dt = window[..., 1]
    is_dep = window[..., _COL_DEPOSIT]
    is_bet = window[..., _COL_BET]

    bd_frac = jnp.sum((is_bet + is_dep) * m, axis=1) / cnt

    pair_m = m[:, 1:] * m[:, :-1]
    pairs = jnp.maximum(jnp.sum(pair_m, axis=1), 1.0)
    alt = (is_bet[:, 1:] * is_dep[:, :-1] + is_dep[:, 1:] * is_bet[:, :-1])
    alt_frac = jnp.sum(alt * pair_m, axis=1) / pairs

    # dt regularity: skip event 0 (its gap points outside the window).
    dt_m = m[:, 1:]
    dt_cnt = jnp.maximum(jnp.sum(dt_m, axis=1), 1.0)
    dt_mu = jnp.sum(log_dt[:, 1:] * dt_m, axis=1) / dt_cnt
    dt_var = jnp.sum(((log_dt[:, 1:] - dt_mu[:, None]) ** 2) * dt_m, axis=1) / dt_cnt
    reg = jnp.exp(-4.0 * dt_var)

    a_mu = jnp.sum(log_amt * m, axis=1) / cnt
    a_var = jnp.sum(((log_amt - a_mu[:, None]) ** 2) * m, axis=1) / cnt
    acons = jnp.exp(-2.0 * a_var)

    return jnp.clip(bd_frac * alt_frac * reg * acons, 0.0, 1.0)


# SESSION_HEAD=transformer: the stock sequence model (models/sequence.py)
# over the N-event window, params from the pinned seeded convention below.
SESSION_SEQ_CONFIG = SeqConfig(d_model=32, n_heads=4, n_layers=1, d_ff=64,
                               in_dim=EVENT_DIM, max_len=256)
_SESSION_HEAD_SEED = 11


def init_session_head_params(seed: int = _SESSION_HEAD_SEED):
    """The pinned seeded init for the transformer session head (the same
    convention tools/replay.py uses for serving params)."""
    return init_sequence_model(jax.random.key(seed), SESSION_SEQ_CONFIG)


def transformer_scores(sparams, window, lengths):
    """The ``transformer`` head: the existing sequence model
    (models/sequence.sequence_forward, dense attention) over the padded
    window. Padding rows are zeroed by the window builder; positions
    beyond ``lengths`` still contribute bias/positional terms — that is
    deterministic and pinned, which is what replay needs."""
    del lengths  # deterministic padded forward; mask lives in the zeros
    return sequence_forward(sparams, window, SESSION_SEQ_CONFIG)["abuse"]


# What a layer's operators (``conv``, ``attention``, ``window``: attention
# inside a band of keys, ``ssm``, ``linear``: linear attention, ``memory``: a
# gate over an earlier layer's scan output at the same position, ``cross``:
# attention over an earlier layer's keys and values) and its feed-forward
# (``dense``, ``moe``) may be: the kinds a row's ``layers`` counts. A layer
# that runs two operators (``falconh1``: ``ssm`` beside ``attention``) counts
# under both. ``mtp`` counts multi-token-prediction modules, each once; a
# module's own layer counts under its operators' kinds besides.
LAYER_KINDS = ("conv", "attention", "window", "ssm", "linear", "memory",
               "cross", "mtp", "dense", "moe")
_NO_LAYERS = dict.fromkeys(LAYER_KINDS, 0)


@dataclass(frozen=True)
class Head:
    """One session head: everything the program and the server's gauges
    know of it."""

    scores: Callable  # (sparams, window [B, N, D], lengths [B]) -> [B] prob
    init: Callable[[], Any]  # the pinned seeded tree (nothing: paramless)
    config: Any = None  # the sizes it is built at
    # (routed experts a layer held on this chip, experts its router chooses
    # among); a head without an expert layer holds and routes none
    experts: tuple[int, int] = (0, 0)
    # layers of each kind in its stack, over exactly LAYER_KINDS (the
    # ``pattern`` head has no layer at all)
    layers: Mapping[str, int] = field(default_factory=_NO_LAYERS.copy)
    # window length -> (key blocks its attention cores visit a scored row,
    # key blocks of their squares); none for a head whose attention sweeps
    # no blocks
    key_blocks: Callable[[int], tuple[int, int]] | None = None
    # window length -> (layer-positions a scored row costs, layer-positions
    # of every layer at every position); none for a head whose every layer
    # runs at every position
    layer_positions: Callable[[int], tuple[int, int]] | None = None


def _per_window(module, name: str, cfg):
    """``module.<name>(cfg, window)`` as a function of the window, none
    where the module has no such function."""
    count = getattr(module, name, None)
    return count and (lambda window: count(cfg, window))


def _backbone(module, cfg) -> Head:
    """The row of a backbone at the sizes ``cfg``, off its module's surface:
    scored at the last real position, its pinned seeded tree built on the
    device in bfloat16, the experts it holds (``held_experts`` where that is
    a chip's share, else all of ``experts``, none without) and routes over."""
    routed = getattr(cfg, "experts", 0)
    return Head(
        scores=lambda sparams, window, lengths: module.backbone_scores(
            sparams, window, lengths, cfg),
        init=lambda: module.init_backbone(
            jax.random.key(_SESSION_HEAD_SEED), cfg),
        config=cfg,
        experts=(getattr(cfg, "held_experts", routed), routed),
        layers=_NO_LAYERS | module.layer_kinds(cfg),
        key_blocks=_per_window(module, "key_blocks", cfg),
        layer_positions=_per_window(module, "layer_positions", cfg))


HEADS = {
    "pattern": Head(lambda sparams, win, lp: pattern_scores(win, lp),
                    lambda: None),
    "transformer": Head(transformer_scores, init_session_head_params,
                        SESSION_SEQ_CONFIG,
                        layers=_NO_LAYERS | {
                            "attention": SESSION_SEQ_CONFIG.n_layers,
                            "dense": SESSION_SEQ_CONFIG.n_layers}),
    # four decoder layers of a sparse-expert backbone at its published
    # widths: 2.50 G parameters, 5.0 GB in bfloat16 beside the state (the
    # server holds each tree once)
    "keye": _backbone(keye_backbone, keye_backbone.BackboneConfig()),
    # one dense and four expert layers of a latent-attention backbone at its
    # published widths, with a chip's share of the routed experts (8 of 256
    # held, all 256 routed over): 3.11 G parameters, 6.23 GB
    "pangu": _backbone(pangu_backbone, pangu_backbone.PanguConfig()),
    # a short-convolution hybrid at its published widths: the source's layer
    # 0 and one whole period after its leading dense layers (four gated short
    # convolutions and one grouped-query attention layer by ``layer_types``;
    # one dense MLP, four expert layers of 64 sigmoid-routed experts chosen
    # with an expert bias, every one held): 2.57 G parameters, 5.13 GB
    "lfm2": _backbone(lfm2_backbone, lfm2_backbone.Lfm2Config()),
    # four layers of a state-space hybrid at its published widths: in every
    # layer a Mamba-2 mixer (32 heads of 128, a state of 256) and
    # grouped-query attention (20 / 4 heads of 128) on one normed input, both
    # added to the stream, then a dense SwiGLU of 21,504; the model's muP
    # multipliers on the branches. No expert layer: 1.72 G parameters, 3.44 GB
    "falconh1": _backbone(falconh1_backbone, falconh1_backbone.FalconH1Config()),
    # a delta-rule linear-attention hybrid at its published widths: the
    # source's layer 1 (a leading dense layer) and one whole period of six
    # after it, five Kimi Delta Attention layers (32 heads of 128 keys and
    # values, the one-chunk form) to one of latent attention by
    # ``layer_group_size``; a dense SwiGLU of 6,144, then a shared expert
    # beside 512 experts routed inside the 4 best of 8 groups, a chip's
    # share of 64 held: 2.77 G parameters, 5.53 GB
    "ling": _backbone(ling_backbone, ling_backbone.LingConfig()),
    # one dense and four expert layers of a hyper-connected latent-attention
    # backbone at its published widths: four residual streams a layer, mixed
    # around each sublayer by a map of 20 Sinkhorn rounds;
    # latent attention of 32 heads with YaRN; a shared expert beside 64
    # bias-chosen experts of width 1,024, every one held: 3.11 G parameters,
    # 6.22 GB
    "xing": _backbone(xing_backbone, xing_backbone.XingConfig()),
    # one whole period of a stack that mixes windowed and full attention, at
    # its published widths: three layers that read a band of 1,024 keys and
    # one that reads every causal key by ``layer_types``, a rotary table a
    # kind (YaRN on the full one); 64 softmax-routed experts of width 896,
    # every one held, no shared expert: 1.67 G parameters, 3.34 GB
    "mellum": _backbone(mellum_backbone, mellum_backbone.MellumConfig()),
    # a decoder-hybrid-decoder whole, all 32 layers at its published widths:
    # nine Mamba-1 scans (d_inner 5120, a state of 16 a channel) and eight
    # layers of differential attention inside a band of 512 keys, one over
    # every causal key; then seven Gated Memory Units that read layer 16's
    # scan output and seven cross-attention layers that read layer 17's keys
    # and values, run at the scored position only; a dense SwiGLU of 10,240
    # in every layer: 3.34 G parameters, 6.68 GB
    "phi4flash": _backbone(phi4flash_backbone,
                           phi4flash_backbone.Phi4FlashConfig()),
    # a post-norm stack read at two depths, at its published widths: one dense
    # and four expert layers (a band of 128 keys on four of the five, every
    # causal key and no rotary on the other; a shared expert beside 128
    # sigmoid-routed experts of width 2,048, a chip's share of 8 held), then
    # the multi-token-prediction module (a join of the stack's output with the
    # next event's embedding and one more full-attention expert layer, run at
    # the one position the score reads) under the same scoring head: 2.80 G
    # parameters, 5.59 GB
    "kexaone": _backbone(kexaone_backbone, kexaone_backbone.KExaoneConfig()),
    # four double layers of a shortcut-expert backbone at its published
    # widths: in each, two latent attentions (interleaved rotary pairs, two
    # latent scales; a chip's share of 16 of 64 heads held) and two dense
    # SwiGLUs of 12,288, and one expert branch that leaves the stream after
    # the first attention and rejoins it after the second MLP: a softmax
    # router of 768 outputs, 12 a position, over 512 experts of width 2,048 (a
    # chip's share of 8 held) and 256 identity experts that multiply nothing;
    # the last layer's second half at the scored position only: 3.30 G
    # parameters, 6.60 GB
    "longcat": _backbone(longcat_backbone, longcat_backbone.LongcatConfig()),
}


def session_head(name: str) -> Head:
    """``SESSION_HEAD`` name -> its row of ``HEADS``."""
    try:
        return HEADS[name]
    except KeyError:
        raise ValueError(
            f"SESSION_HEAD={name!r} not supported "
            f"(use one of {sorted(HEADS)})") from None
