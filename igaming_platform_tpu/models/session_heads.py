"""Session heads: the models the fused session step runs over an
account's post-append event window.

``HEADS`` maps a ``SESSION_HEAD`` name to ``head_fn(params, window
[B, N, D], lengths [B]) -> [B] prob`` (jittable) and ``init_params()``,
the pinned seeded tree replay rebuilds without a checkpoint. The program
(serve/index_program.py) takes ``head_fn`` as an argument and the
parameters as a traced tree: a new head is a function, an init and a row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from igaming_platform_tpu.models import (
    falconh1_backbone,
    lfm2_backbone,
    pangu_backbone,
)
from igaming_platform_tpu.models.keye_backbone import (
    BackboneConfig,
    backbone_scores,
    init_backbone,
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


# SESSION_HEAD=keye: four decoder layers of a sparse-expert backbone at its
# published widths (models/keye_backbone.py): 2.50 G parameters, 5.0 GB in
# bfloat16 beside the state. The server holds this tree once.
KEYE_CONFIG = BackboneConfig()


def init_keye_params(seed: int = _SESSION_HEAD_SEED):
    """The pinned seeded tree of the ``keye`` head, built on the device
    in bfloat16, a matrix at a time."""
    return init_backbone(jax.random.key(seed), KEYE_CONFIG)


def keye_scores(sparams, window, lengths):
    """The ``keye`` head: the backbone over the window, scored at the
    last real position."""
    return backbone_scores(sparams, window, lengths, KEYE_CONFIG)


# SESSION_HEAD=pangu: one dense and four expert layers of a latent-attention
# backbone at its published widths, with a chip's share of the routed
# experts (models/pangu_backbone.py: 8 of 256 held, all 256 routed over):
# 3.11 G parameters, 6.23 GB in bfloat16 beside the state.
PANGU_CONFIG = pangu_backbone.PanguConfig()


def init_pangu_params(seed: int = _SESSION_HEAD_SEED):
    """The pinned seeded tree of the ``pangu`` head, built on the device
    in bfloat16, a matrix (or a block of one) at a time."""
    return pangu_backbone.init_backbone(jax.random.key(seed), PANGU_CONFIG)


def pangu_scores(sparams, window, lengths):
    """The ``pangu`` head: the backbone over the window, scored at the
    last real position."""
    return pangu_backbone.backbone_scores(sparams, window, lengths, PANGU_CONFIG)


# SESSION_HEAD=lfm2: a short-convolution hybrid at its published widths
# (models/lfm2_backbone.py): the source's layer 0 and one whole period after
# its leading dense layers (four gated short convolutions and one
# grouped-query attention layer by ``layer_types``; one dense MLP, four
# expert layers of 64 sigmoid-routed experts chosen with an expert bias,
# every one held): 2.57 G parameters, 5.13 GB in bfloat16 beside the state.
LFM2_CONFIG = lfm2_backbone.Lfm2Config()


def init_lfm2_params(seed: int = _SESSION_HEAD_SEED):
    """The pinned seeded tree of the ``lfm2`` head, built on the device in
    bfloat16, a matrix (or a block of one) at a time."""
    return lfm2_backbone.init_backbone(jax.random.key(seed), LFM2_CONFIG)


def lfm2_scores(sparams, window, lengths):
    """The ``lfm2`` head: the backbone over the window, scored at the last
    real position."""
    return lfm2_backbone.backbone_scores(sparams, window, lengths, LFM2_CONFIG)


# SESSION_HEAD=falconh1: four layers of a state-space hybrid at its
# published widths (models/falconh1_backbone.py): in every layer a Mamba-2
# mixer (32 heads of 128, a state of 256) and grouped-query attention (20 /
# 4 heads of 128) on one normed input, both added to the stream, then a
# dense SwiGLU of 21,504; the model's muP multipliers on the branches. No
# expert layer: 1.72 G parameters, 3.44 GB in bfloat16 beside the state.
FALCONH1_CONFIG = falconh1_backbone.FalconH1Config()


def init_falconh1_params(seed: int = _SESSION_HEAD_SEED):
    """The pinned seeded tree of the ``falconh1`` head, built on the device
    in bfloat16, a matrix (or a block of one) at a time."""
    return falconh1_backbone.init_backbone(jax.random.key(seed), FALCONH1_CONFIG)


def falconh1_scores(sparams, window, lengths):
    """The ``falconh1`` head: the backbone over the window, scored at the
    last real position."""
    return falconh1_backbone.backbone_scores(sparams, window, lengths,
                                             FALCONH1_CONFIG)


# SESSION_HEAD name -> (head_fn(sparams, window, lengths), init_params()).
HEADS = {
    "pattern": (lambda sparams, win, lp: pattern_scores(win, lp),
                lambda: None),
    "transformer": (transformer_scores, init_session_head_params),
    "keye": (keye_scores, init_keye_params),
    "pangu": (pangu_scores, init_pangu_params),
    "lfm2": (lfm2_scores, init_lfm2_params),
    "falconh1": (falconh1_scores, init_falconh1_params),
}

# SESSION_HEAD name -> (routed experts a layer held on this chip, experts
# its router chooses among); a head without an expert layer has no row.
HEAD_EXPERTS = {
    "keye": (KEYE_CONFIG.experts, KEYE_CONFIG.experts),
    "pangu": (PANGU_CONFIG.held_experts, PANGU_CONFIG.experts),
    "lfm2": (LFM2_CONFIG.experts, LFM2_CONFIG.experts),
}

# What a layer's operators (``conv``, ``attention``, ``ssm``) and its
# feed-forward (``dense``, ``moe``) may be: the kinds ``HEAD_LAYERS`` counts.
# A layer that runs two operators (``falconh1``: ``ssm`` beside
# ``attention``) counts under both.
LAYER_KINDS = ("conv", "attention", "ssm", "dense", "moe")

# SESSION_HEAD name -> layers of each kind in its stack (a kind that is
# left out has none; the ``pattern`` head has no layer at all).
HEAD_LAYERS = {
    "pattern": {},
    "transformer": {"attention": SESSION_SEQ_CONFIG.n_layers,
                    "dense": SESSION_SEQ_CONFIG.n_layers},
    "keye": {"attention": KEYE_CONFIG.layers, "moe": KEYE_CONFIG.layers},
    "pangu": {"attention": PANGU_CONFIG.layers,
              "dense": PANGU_CONFIG.dense_layers,
              "moe": PANGU_CONFIG.layers - PANGU_CONFIG.dense_layers},
    "lfm2": lfm2_backbone.layer_kinds(LFM2_CONFIG),
    "falconh1": falconh1_backbone.layer_kinds(FALCONH1_CONFIG),
}


def session_head(name: str):
    """``SESSION_HEAD`` name -> (head_fn, params)."""
    try:
        head_fn, init = HEADS[name]
    except KeyError:
        raise ValueError(
            f"SESSION_HEAD={name!r} not supported "
            f"(use one of {sorted(HEADS)})") from None
    return head_fn, init()
