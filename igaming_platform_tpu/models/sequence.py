"""Bonus-abuse sequence detector — long-context SP/CP first-class.

The reference detects bonus abuse by pattern-matching scalar aggregates
(engine.go:462-466, ltv.go:336-338); BASELINE.json config 3 owes the real
version: a transformer over per-player wagering/event histories. Long
histories don't fit one chip's HBM slice, so the sequence dimension shards
over the ``seq`` mesh axis with two interchangeable attention strategies
behind one ``seq_mode`` switch (SURVEY.md §2.3 SP/CP/Ulysses):

- ``ring``    blockwise ring attention: KV blocks rotate around the ICI
              ring via ppermute with flash-style online-softmax
              accumulation — S_total never materialises on one chip;
- ``ulysses`` head-sharded all-to-all: exchange sequence shards for head
              shards, run dense attention per head subset, exchange back;
- ``dense``   single-chip reference path (golden target for both).

Everything outside attention (LN/FFN/pooling) is position-local, so XLA
propagates the [B, S/seq, D] sharding through it untouched.
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from igaming_platform_tpu.parallel.mesh import AXIS_DATA, AXIS_SEQ

Params = dict[str, Any]

logger = logging.getLogger(__name__)

# Per-event feature layout for wagering histories:
# [log-amount, log-dt, 8-way tx-type one-hot, game-weight, balance-ratio]
EVENT_DIM = 12
TX_TYPE_INDEX = {
    "deposit": 0, "withdraw": 1, "bet": 2, "win": 3,
    "refund": 4, "bonus_grant": 5, "bonus_wager": 6, "adjustment": 7,
}


def encode_event(amount: float, dt_seconds: float, tx_type: str,
                 game_weight: float = 1.0, balance_ratio: float = 0.0) -> np.ndarray:
    e = np.zeros(EVENT_DIM, dtype=np.float32)
    e[0] = math.log1p(max(amount, 0.0))
    e[1] = math.log1p(max(dt_seconds, 0.0))
    e[2 + TX_TYPE_INDEX.get(tx_type, 7)] = 1.0
    e[10] = game_weight
    e[11] = balance_ratio
    return e


@dataclass(frozen=True)
class SeqConfig:
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 256
    in_dim: int = EVENT_DIM
    max_len: int = 2048


def init_sequence_model(key: jax.Array, cfg: SeqConfig = SeqConfig()) -> Params:
    keys = iter(jax.random.split(key, 2 + cfg.n_layers * 4))

    def dense_init(k, d_in, d_out, scale=None):
        scale = scale if scale is not None else math.sqrt(2.0 / d_in)
        return {
            "w": jax.random.normal(k, (d_in, d_out), jnp.float32) * scale,
            "b": jnp.zeros((d_out,), jnp.float32),
        }

    d = cfg.d_model
    layers = []
    for _ in range(cfg.n_layers):
        layers.append(
            {
                "ln1": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
                "wqkv": dense_init(next(keys), d, 3 * d, scale=math.sqrt(1.0 / d)),
                "wo": dense_init(next(keys), d, d, scale=math.sqrt(1.0 / d)),
                "ln2": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
                "w1": dense_init(next(keys), d, cfg.d_ff),
                "w2": dense_init(next(keys), cfg.d_ff, d),
            }
        )
    return {
        "embed": dense_init(next(keys), cfg.in_dim, d),
        "ln_f": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        "head": dense_init(next(keys), d, 1, scale=math.sqrt(1.0 / d)),
        "layers": layers,
    }


def _dense(x, p):
    return x @ p["w"] + p["b"]


def _layer_norm(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _sinusoidal_positions(seq_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    angle = pos / np.power(10_000.0, 2 * dim / d_model)
    out = np.zeros((seq_len, d_model), dtype=np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


# -- attention cores ---------------------------------------------------------


@lru_cache(maxsize=None)
def _announce_core(core: str, backend: str) -> None:
    """Log, once per (core, backend), which attention core serves the
    dense path — the choice is made at trace time and is otherwise
    invisible."""
    logger.info("attention core: %s (backend=%s)", core, backend)  # noqa: JX01 — deliberately a trace-time log: the core is chosen while tracing, once per compile


def _dense_attention(q, k, v):
    """q,k,v: [B, H, S, Dh] -> [B, H, S, Dh]; full softmax attention.

    On TPU with block-divisible S the intra-chip core is the Pallas flash
    kernel (VMEM-resident online softmax, no [S, S] in HBM); elsewhere the
    XLA einsum path, which is also the golden reference for the kernel.
    """
    from igaming_platform_tpu.ops.pallas.flash_attention import flash_attention, supports

    backend = jax.default_backend()
    if backend == "tpu" and supports(q.shape):
        _announce_core("pallas-flash", backend)
        return flash_attention(q, k, v)
    _announce_core("xla-einsum", backend)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _ring_attention_local(q, k, v):
    """Ring attention body (inside shard_map over AXIS_SEQ).

    q,k,v: [B, H, S_local, Dh]. KV blocks rotate around the seq ring; the
    softmax normaliser accumulates online (flash-attention style), so no
    [S, S] matrix and no full-sequence KV ever exist on one device.
    """
    n = lax.axis_size(AXIS_SEQ)
    scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, s_loc, dh = q.shape

    m0 = jnp.full((b, h, s_loc), -jnp.inf, q.dtype)
    l0 = jnp.zeros((b, h, s_loc), q.dtype)
    o0 = jnp.zeros((b, h, s_loc, dh), q.dtype)

    def step(i, carry):
        k_cur, v_cur, m, l, o = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur) * scale
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_next = lax.ppermute(k_cur, AXIS_SEQ, perm)
        v_next = lax.ppermute(v_cur, AXIS_SEQ, perm)
        return (k_next, v_next, m_new, l, o)

    # n is a static mesh property: unrolled loop keeps ppermute schedulable
    # back-to-back with the matmuls (double-buffering over ICI).
    carry = (k, v, m0, l0, o0)
    for i in range(n):
        carry = step(i, carry)
    _, _, _, l, o = carry
    return o / l[..., None]


def _ulysses_attention_local(q, k, v, n_seq: int):
    """Ulysses body (inside shard_map over AXIS_SEQ).

    q,k,v: [B, H, S_local, Dh] with H % n_seq == 0. all_to_all trades the
    sequence shard for a head shard, dense attention runs on the full
    sequence for H/n_seq heads, then the exchange reverses.
    """
    def seq_to_heads(x):
        # [B, H, S_loc, Dh] -> [B, H/n, S, Dh]
        return lax.all_to_all(x, AXIS_SEQ, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, AXIS_SEQ, split_axis=2, concat_axis=1, tiled=True)

    out = _dense_attention(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v))
    return heads_to_seq(out)


def _attention(x, layer, cfg: SeqConfig, mesh: Mesh | None, seq_mode: str):
    """x: [B, S(, local)] x d_model -> same; dispatches the SP strategy."""
    b, s, d = x.shape
    h, dh = cfg.n_heads, d // cfg.n_heads

    qkv = _dense(x, layer["wqkv"])  # [B, S, 3d]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def to_heads(t):
        return t.reshape(b, s, h, dh).transpose(0, 2, 1, 3)  # [B, H, S, Dh]

    q, k, v = to_heads(q), to_heads(k), to_heads(v)

    if seq_mode == "dense" or mesh is None:
        out = _dense_attention(q, k, v)
    elif seq_mode == "ring":
        body = shard_map(
            _ring_attention_local,
            mesh=mesh,
            in_specs=(P(AXIS_DATA, None, AXIS_SEQ, None),) * 3,
            out_specs=P(AXIS_DATA, None, AXIS_SEQ, None),
        )
        out = body(q, k, v)
    elif seq_mode == "ulysses":
        n_seq = int(mesh.shape[AXIS_SEQ])
        if h % n_seq != 0:
            raise ValueError(f"n_heads {h} not divisible by seq axis {n_seq}")
        body = shard_map(
            partial(_ulysses_attention_local, n_seq=n_seq),
            mesh=mesh,
            in_specs=(P(AXIS_DATA, None, AXIS_SEQ, None),) * 3,
            out_specs=P(AXIS_DATA, None, AXIS_SEQ, None),
        )
        out = body(q, k, v)
    else:
        raise ValueError(f"unknown seq_mode: {seq_mode}")

    out = out.transpose(0, 2, 1, 3).reshape(b, s, d)
    return _dense(out, layer["wo"])


def sequence_forward(
    params: Params,
    x: jnp.ndarray,
    cfg: SeqConfig = SeqConfig(),
    *,
    mesh: Mesh | None = None,
    seq_mode: str = "dense",
) -> dict[str, jnp.ndarray]:
    """[B, S, EVENT_DIM] event history -> abuse score per player.

    Returns {"abuse": [B] in [0,1], "abuse_logit": [B], "hidden": [B, d]}.
    """
    x = jnp.asarray(x, jnp.float32)
    b, s, _ = x.shape
    hpos = jnp.asarray(_sinusoidal_positions(s, cfg.d_model))
    hid = _dense(x, params["embed"]) + hpos[None]

    for layer in params["layers"]:
        hid = hid + _attention(_layer_norm(hid, layer["ln1"]), layer, cfg, mesh, seq_mode)
        ff = _dense(jax.nn.gelu(_dense(_layer_norm(hid, layer["ln2"]), layer["w1"])), layer["w2"])
        hid = hid + ff

    hid = _layer_norm(hid, params["ln_f"])
    pooled = jnp.mean(hid, axis=1)  # position-local -> XLA psums over seq shards
    logit = _dense(pooled, params["head"])[..., 0]
    return {"abuse": jax.nn.sigmoid(logit), "abuse_logit": logit, "hidden": pooled}


def abuse_signals(score: float, threshold: float = 0.5) -> list[str]:
    """Decode wire-level abuse signals (risk.proto CheckBonusAbuseResponse)."""
    signals = []
    if score >= threshold:
        signals.append("SEQUENCE_MODEL_HIGH_RISK")
    if score >= 0.8:
        signals.append("WAGERING_PATTERN_ANOMALY")
    return signals
