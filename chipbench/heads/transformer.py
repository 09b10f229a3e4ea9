"""The ``transformer`` session head (``SESSION_HEAD=transformer`` at
``SESSION_SEQ_CONFIG``'s default: d_model 32, 4 heads, 1 layer, d_ff 64
over 12-wide events): its tree from the seed and its forward pass."""

from __future__ import annotations

import math

import numpy as np

from chipbench.reference import (EVENT_WIDTH, F32, FLAG_THRESHOLD, TX_EVENT_COL,
                                 _sigmoid, rounder)

SEQ_D_MODEL, SEQ_HEADS, SEQ_D_FF = 32, 4, 64


def make_params(seed: int, config: dict) -> dict:
    return make_head_params(seed)


def forward(params: dict, windows: np.ndarray, lengths: np.ndarray, rnd) -> np.ndarray:
    return transformer_head(params, windows, rnd)


def make_head_params(seed: int) -> dict:
    """The transformer session head's tree (SESSION_SEQ_CONFIG: d_model 32,
    4 heads, 1 layer, d_ff 64 over 12-wide events), from the seed."""
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 0x68656164])
    d, ff = SEQ_D_MODEL, SEQ_D_FF

    def dense(d_in, d_out, scale=None):
        scale = math.sqrt(2.0 / d_in) if scale is None else scale
        return {"w": (rng.standard_normal((d_in, d_out)) * scale).astype(F32),
                "b": (rng.standard_normal(d_out) * 0.05).astype(F32)}

    def ln():
        return {"scale": np.ones((d,), F32), "bias": np.zeros((d,), F32)}

    layer = {"ln1": ln(), "wqkv": dense(d, 3 * d, math.sqrt(1.0 / d)),
             "wo": dense(d, d, math.sqrt(1.0 / d)), "ln2": ln(),
             "w1": dense(d, ff), "w2": dense(ff, d)}
    hp = {"embed": dense(EVENT_WIDTH, d), "ln_f": ln(),
          "head": dense(d, 1, math.sqrt(1.0 / d)), "layers": [layer]}
    # A random head answers nearly the same for every window, far from the
    # fold threshold on most seeds, and the check would then never see its
    # output. Scale and shift the last layer so that over plausible windows
    # the logits spread by about one and centre on the threshold: about
    # half of the warm rows fold, on every seed.
    n = 512
    win = np.zeros((n, 16, EVENT_WIDTH), F32)
    lengths = rng.integers(4, 17, n)
    win[..., 0] = rng.normal(7.6, 1.2, (n, 16))     # log1p of ~2000 cents
    win[..., 1] = rng.uniform(0.3, 3.0, (n, 16))    # log1p of seconds
    codes = rng.choice(4, size=(n, 16), p=[0.07, 0.03, 0.70, 0.20])
    win[np.arange(n)[:, None], np.arange(16)[None, :],
        2 + TX_EVENT_COL[codes]] = 1.0
    win[..., 10] = 1.0
    win *= (np.arange(16)[None, :] < lengths[:, None])[..., None]
    logits = transformer_head(hp, win, rounder("float32"), logits=True)
    gain = 1.0 / max(float(logits.std()), 1e-6)
    centre = math.log(FLAG_THRESHOLD / (1.0 - FLAG_THRESHOLD))
    hp["head"]["w"] = (hp["head"]["w"] * gain).astype(F32)
    hp["head"]["b"] = ((hp["head"]["b"] - np.median(logits)) * gain
                       + centre).astype(F32)
    return hp


def _layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return ((x - mu) / np.sqrt(var + F32(1e-5)) * p["scale"] + p["bias"]).astype(F32)


def _gelu(x):
    return (0.5 * x * (1.0 + np.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))).astype(F32)


def transformer_head(hp: dict, win: np.ndarray, rnd,
                     logits: bool = False) -> np.ndarray:
    """One pre-norm transformer layer over the zero-padded window, mean
    pooled (models/sequence.sequence_forward at SESSION_SEQ_CONFIG)."""
    b, s, _ = win.shape
    d, h = SEQ_D_MODEL, SEQ_HEADS
    dh = d // h

    def dense(x, p):
        return (rnd(x) @ rnd(p["w"]) + p["b"]).astype(F32)

    pos = np.arange(s)[:, None]
    angle = pos / np.power(10_000.0, 2 * np.arange(d // 2)[None, :] / d)
    hpos = np.zeros((s, d), F32)
    hpos[:, 0::2], hpos[:, 1::2] = np.sin(angle), np.cos(angle)
    hid = dense(win, hp["embed"]) + hpos[None]
    for layer in hp["layers"]:
        qkv = dense(_layer_norm(hid, layer["ln1"]), layer["wqkv"])
        q, k, v = (t.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
                   for t in np.split(qkv, 3, axis=-1))
        sc = (rnd(q) @ rnd(k).transpose(0, 1, 3, 2)) * F32(1.0 / math.sqrt(dh))
        sc = sc - sc.max(-1, keepdims=True)
        p = np.exp(sc)
        p = (p / p.sum(-1, keepdims=True)).astype(F32)
        att = (rnd(p) @ rnd(v)).transpose(0, 2, 1, 3).reshape(b, s, d)
        hid = hid + dense(att, layer["wo"])
        ff = dense(_gelu(dense(_layer_norm(hid, layer["ln2"]), layer["w1"])),
                   layer["w2"])
        hid = hid + ff
    pooled = _layer_norm(hid, hp["ln_f"]).mean(1)
    # The [32] -> [1] projection is left in float32: a product with one
    # output column never reaches the MXU (XLA fuses it as a float32
    # multiply-reduce), and the logit is where a rounded operand costs
    # most. Read off the chip: with this one product rounded the worst row
    # was 0.025 off on some seeds, without it 0.005 (PERF.md, PR 24).
    logit = (pooled @ hp["head"]["w"] + hp["head"]["b"]).astype(F32)[:, 0]
    return logit if logits else _sigmoid(logit)
