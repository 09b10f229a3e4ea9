"""The ``lfm2`` session head (models/lfm2_backbone.py) against its plain
reference (chipbench/heads/lfm2_24b_a2b.py) at a small size on the CPU, part
by part, and through the served session path.

The small size keeps every mechanism: four layers of both kinds by a list
(``conv, full_attention, conv, conv``), hidden 128, 4 query / 2 key-value
heads of 32, a leading dense layer of width 256, then 8 sigmoid-routed
experts of width 64, 2 a token, chosen with an expert bias; 16-event windows
of mixed lengths, seeded weights.
"""

from __future__ import annotations

import dataclasses
import copy
import functools
import json
import os
import tempfile
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from chipbench import harness, reference, validate  # noqa: E402
from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
from igaming_platform_tpu.models import expert_layer as el  # noqa: E402
from igaming_platform_tpu.models import lfm2_backbone as lb  # noqa: E402
from igaming_platform_tpu.models import pangu_backbone as pb  # noqa: E402
from igaming_platform_tpu.models import session_heads  # noqa: E402

CONFIG = "risk-seqhead-lfm2-24b-a2b"
KINDS = ("conv", "full_attention", "conv", "conv")


def small_source(**over) -> dict:
    """The small size as a configuration file would state it."""
    source = {
        "hidden_size": 128, "num_hidden_layers": len(KINDS),
        "num_dense_layers": 1, "layer_types": list(KINDS), "conv_L_cache": 3,
        "conv_bias": False, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 256, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 64, "routed_scaling_factor": 1,
        "norm_topk_prob": True, "use_expert_bias": True, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "head": {"published": {"num_hidden_layers": 40}},
    }
    source.update(over)
    return source


def small_config(**over) -> lb.Lfm2Config:
    kw = dict(hidden=128, layer_types=KINDS, dense_layers=1, heads=4,
              kv_heads=2, head_dim=32, dense_width=256, experts=8, top_k=2,
              expert_width=64)
    kw.update(over)
    return lb.Lfm2Config(**kw)


@pytest.fixture(scope="module")
def head():
    return validate.load_code("heads", "lfm2_24b_a2b")


@pytest.fixture(scope="module")
def tree(head):
    return head.make_params(7, small_source())


def windows(n: int, lengths, seed: int = 0):
    rng = np.random.default_rng(seed)
    lengths = np.resize(np.asarray(lengths), n)
    x = rng.normal(0, 1, (n, 16, 12)).astype(np.float32)
    x *= (np.arange(16)[None, :] < lengths[:, None])[..., None]
    return x, lengths


def program_logits(cfg, params, x, lengths):
    """(pre-sigmoid score, final-normed hidden state of the scored
    position) of every window, from the program."""
    def both(p, w, l):
        hid = lb.backbone_hidden(p, w, cfg)
        last = jnp.clip(l - 1, 0, w.shape[1] - 1)
        hl = jnp.take_along_axis(hid, last[:, None, None], axis=1)[:, 0, :]
        return jnp.sum(hl * p["head"]["w"][:, 0], -1) + p["head"]["b"][0], hl

    logit, hl = jax.jit(both)(params, jnp.asarray(x),
                              jnp.asarray(lengths, jnp.int32))
    return np.asarray(logit), np.asarray(hl)


def program_scores(cfg, params, x, lengths):
    return np.asarray(jax.jit(
        lambda p, w, l: lb.backbone_scores(p, w, l, cfg))(
            params, jnp.asarray(x), jnp.asarray(lengths, jnp.int32)))


def stream(rows: int = 6, seed: int = 0, hidden: int = 128):
    """A residual stream [rows, 16, hidden] with some spread."""
    return jax.random.normal(jax.random.key(seed), (rows, 16, hidden),
                             jnp.float32) * 2.0


# -- the whole head against the reference ----------------------------------------

# With bfloat16 operands a value on a rounding boundary falls either side
# by the order of a float32 accumulation, and one such operand is 2^-8 of
# itself. The logit and every channel of the final hidden state (unit
# spread) are held to half a rounding of a unit value, 2^-9; float8
# operands (a rounding is 2^-4) miss it several times over.
ROUNDING = 2.0 ** -9


@pytest.mark.parametrize("lengths", [(1,), (4,), (16,), (1, 4, 16, 7, 9, 2)],
                         ids=["len1", "len4", "len16", "mixed"])
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_equals_the_reference(head, tree, operands, lengths):
    dt = jnp.dtype(operands)
    cfg = small_config(operand_dtype=dt)
    d = head.dims_of(small_source())
    x, lens = windows(24, lengths, seed=len(lengths))
    logit, hidden = program_logits(cfg, tree, x, lens)
    want_logit = head._logits(tree, x, lens, d, dt)
    want_hidden = head._logits(tree, x, lens, d, dt, hidden=True)
    assert logit.shape == want_logit.shape == (24,)
    assert hidden.shape == want_hidden.shape == (24, 128)
    # float32: the order of float32 sums alone
    atol = 2e-5 if operands == "float32" else ROUNDING
    np.testing.assert_allclose(logit, want_logit, atol=atol, rtol=0)
    np.testing.assert_allclose(hidden, want_hidden, atol=atol, rtol=0)
    got = program_scores(cfg, tree, x, lens)
    want = head.forward(tree, x, lens, reference.rounder(operands))
    np.testing.assert_allclose(got, want, atol=atol / 4, rtol=0)


def test_float8_operands_fail_what_bfloat16_passes(head, tree):
    """The comparison is tight enough that one precision step down fails
    it: the reference with float8 operands lies further from the bfloat16
    one than the limit the program is held to."""
    d = head.dims_of(small_source())
    x, lens = windows(24, (1, 4, 16, 7, 9, 2), seed=3)
    stated = head._logits(tree, x, lens, d, jnp.bfloat16)
    below = head._logits(tree, x, lens, d, jnp.float8_e4m3fn)
    assert np.abs(below - stated).max() > 4 * ROUNDING
    hid = head._logits(tree, x, lens, d, jnp.bfloat16, hidden=True)
    hid8 = head._logits(tree, x, lens, d, jnp.float8_e4m3fn, hidden=True)
    assert np.abs(hid8 - hid).max() > 4 * ROUNDING
    a = head.forward(tree, x, lens, reference.rounder("bfloat16"))
    b = head.forward(tree, x, lens, reference.rounder("float8_e4m3fn"))
    assert np.abs(a - b).max() > ROUNDING


def test_rounding_is_where_the_reference_puts_it(tree):
    x, lens = windows(24, (16,))
    a = program_scores(small_config(operand_dtype=jnp.float32), tree, x, lens)
    b = program_scores(small_config(operand_dtype=jnp.bfloat16), tree, x, lens)
    diff = np.abs(a - b)
    assert diff.max() > 1e-6 and np.median(diff) < 0.01


@pytest.mark.parametrize("lengths", [1, 4, 9])
def test_positions_after_the_last_real_one_change_nothing(tree, lengths):
    """Blind to padding at the scored position: whatever the positions past
    a window's length hold, the convolution and the mask are causal."""
    cfg = small_config()
    x, lens = windows(8, (lengths,))
    junk = x.copy()
    junk[:, lengths:] = np.random.default_rng(1).normal(0, 3, junk[:, lengths:].shape)
    np.testing.assert_array_equal(program_scores(cfg, tree, x, lens),
                                  program_scores(cfg, tree, junk, lens))


def test_tree_of_the_reference_is_the_programs(head, tree):
    """The harness replaces the program's tree by the reference's: one
    structure, shapes and dtypes, so the compiled step is reused. And the
    program's sizes are the configuration file's; 2.57 G parameters, 5.13
    GB at rest, router, bias, norms and taps float32."""
    cfg = small_config()
    mine = jax.eval_shape(lambda: lb.init_backbone(jax.random.key(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(tree)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    kinds = [("w_in" in layer, "wq" in layer, "dense" in layer, "rb" in layer)
             for layer in tree["layers"]]
    assert kinds == [(True, False, True, False), (False, True, False, True),
                     (True, False, False, True), (True, False, False, True)]
    for layer in tree["layers"]:
        for name in ("g1", "g2", "taps", "qn", "kn", "wr", "rb"):
            assert name not in layer or layer[name].dtype == jnp.float32, name
        for name in ("w_in", "w_out", "wq", "wk", "wv", "wo"):
            assert name not in layer or layer[name].dtype == jnp.bfloat16, name
    published = validate.load_data("configs", CONFIG)
    d, c = head.dims_of(published), session_heads.HEADS["lfm2"].config
    assert (d.hidden, d.layer_types, d.dense_layers, d.taps, d.heads, d.kv_heads,
            d.head_dim, d.dense_width, d.experts, d.top_k, d.expert_width,
            d.scale, d.theta, d.eps) == (
        c.hidden, c.layer_types, c.dense_layers, c.conv_taps, c.heads,
        c.kv_heads, c.head_dim, c.dense_width, c.experts, c.top_k,
        c.expert_width, c.routed_scale, c.rope_theta, c.eps)
    assert c.renorm_eps == head.RENORM_EPS == 1e-6
    assert c.init_depth == published["head"]["published"]["num_hidden_layers"]
    full = jax.eval_shape(session_heads.HEADS["lfm2"].init)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(full))
    assert n == 2_566_463_873
    assert sum(a.dtype.itemsize * int(np.prod(a.shape))
               for a in jax.tree.leaves(full)) == 5_134_075_396
    assert f"{n:,}" in published["head"]["parameters"]


def test_layer_types_are_read_and_an_unknown_kind_is_refused(head):
    """The stack is what the list says, in its order, not a uniform one."""
    assert lb.layer_kinds(small_config()) == {"conv": 3, "attention": 1,
                                              "dense": 1, "moe": 3}
    other = ("full_attention", "conv")
    cfg = small_config(layer_types=other, dense_layers=0)
    params = lb.init_backbone(jax.random.key(1), cfg)
    assert ["wq" in layer for layer in params["layers"]] == [True, False]
    assert all("rb" in layer for layer in params["layers"])
    assert lb.layer_kinds(cfg) == {"conv": 1, "attention": 1, "dense": 0, "moe": 2}
    with pytest.raises(ValueError, match="sliding"):
        small_config(layer_types=("conv", "sliding"))
    with pytest.raises(ValueError, match="one entry a layer"):
        head.dims_of(small_source(num_hidden_layers=5))


# -- the gated short convolution -------------------------------------------------


def _conv_loop(u, layer, taps: int):
    """``(C * conv(B * X)) W_out`` by explicit loops over windows,
    positions and taps, in float64."""
    u = np.asarray(u, np.float64)
    rows, t, hid = u.shape
    w_in, w_out = (np.asarray(layer[k].astype(jnp.float32), np.float64)
                   for k in ("w_in", "w_out"))
    w = np.asarray(layer["taps"], np.float64)
    out = np.zeros_like(u)
    for r in range(rows):
        bcx = u[r] @ w_in
        b, c, x = bcx[:, :hid], bcx[:, hid:2 * hid], bcx[:, 2 * hid:]
        z = b * x
        for pos in range(t):
            acc = np.zeros(hid)
            for k in range(taps):
                src = pos - (taps - 1 - k)
                if src >= 0:  # zero before the window's first event
                    acc += w[:, k] * z[src]
            out[r, pos] = (c[pos] * acc) @ w_out
    return out


@pytest.mark.parametrize("taps", [3, 2, 4])
def test_short_convolution_equals_an_explicit_loop(taps):
    cfg = small_config(conv_taps=taps, operand_dtype=jnp.float32)
    layer = lb.init_backbone(jax.random.key(3), cfg)["layers"][0]
    assert layer["taps"].shape == (128, taps)
    u = stream(rows=5, seed=taps)
    rows, t, hid = u.shape
    got = np.asarray(jax.jit(lambda u: lb.short_conv(
        u.reshape(rows * t, hid), layer, cfg, t))(u)).reshape(rows, t, hid)
    want = _conv_loop(u, layer, taps)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(), rtol=0)


def test_short_convolution_is_causal_and_each_window_alone():
    """An event at ``t + 1`` changes nothing at or before ``t``; it does
    reach ``t + 1`` and ``t + 2`` (three taps) and no further than the
    taps; and a window never reads its neighbour in the batch."""
    cfg = small_config(operand_dtype=jnp.float32)
    layer = lb.init_backbone(jax.random.key(3), cfg)["layers"][0]
    u = stream(rows=4, seed=9)
    rows, t, hid = u.shape
    conv = jax.jit(lambda u: lb.short_conv(u.reshape(rows * t, hid), layer, cfg,
                                           t).reshape(rows, t, hid))
    base = np.asarray(conv(u))
    moved = np.asarray(conv(u.at[1, 7].add(1.0)))
    changed = np.abs(moved - base).max(-1) > 0
    assert not changed[[0, 2, 3]].any()          # the other windows
    assert changed[1].tolist() == [False] * 7 + [True] * 3 + [False] * 6
    # zero before the window: position 0 reads its own event alone
    only_first = np.asarray(conv(u.at[:, 1:].set(0.0)))
    np.testing.assert_array_equal(only_first[:, 0], base[:, 0])
    # and the taps alone, as three shifted products
    z = np.asarray(stream(rows=2, seed=4))
    w = np.asarray(layer["taps"])
    c = np.asarray(dp.causal_taps(jnp.asarray(z), layer["taps"]))
    want = w[:, 2] * z
    want[:, 1:] += w[:, 1] * z[:, :-1]
    want[:, 2:] += w[:, 0] * z[:, :-2]
    np.testing.assert_allclose(c, want, atol=1e-5)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_operator_sublayer_alone(head, tree, operands, kind):
    """``x + Op(N_op(x))`` of one layer of each kind against the
    reference's; the attention layer is causal and turns with the rotary."""
    dt = jnp.dtype(operands)
    cfg, d = small_config(operand_dtype=dt), head.dims_of(small_source())
    layer = tree["layers"][KINDS.index(kind)]
    x = stream(seed=5)
    rows, t, hid = x.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (1, rows, t))
    cos, sin = dp.mrope_angles(pos, cfg.head_dim, (cfg.head_dim // 2,),
                               cfg.rope_theta)

    def sublayer(x, cos, sin):
        h = x.reshape(rows * t, hid)
        u = dp.rms_norm(h, layer["g1"], cfg.eps)
        o = (lb.short_conv(u, layer, cfg, t) if kind == "conv"
             else dp.attention(u, layer, cos, sin, cfg, t))
        return (h + o).reshape(x.shape)

    got = np.asarray(jax.jit(sublayer)(x, cos, sin))
    with jax.default_matmul_precision("highest"):
        want = np.asarray((head._short_conv if kind == "conv" else head._attend)(
            layer, x, d, dt))
    # the stream's values are a few units: bfloat16 operands on a rounding
    # boundary move a channel by 2^-8 of such a value
    np.testing.assert_allclose(got, want, atol=3e-5 if operands == "float32"
                               else 2e-3, rtol=0)
    later = x.at[:, 9:].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(sublayer)(later, cos, sin))[:, :9], got[:, :9])
    if kind == "full_attention":
        still = np.asarray(jax.jit(sublayer)(x, jnp.ones_like(cos),
                                             jnp.zeros_like(sin)))
        assert np.abs(still - got).max() > 1e-3  # the rotary matters


# -- the router --------------------------------------------------------------------


def _route(layer, x, cfg):
    top_e, top_w = jax.jit(lambda x: dp.route(x, layer, cfg))(x)
    return np.asarray(top_e), np.asarray(top_w)


def test_the_bias_chooses_and_never_weighs(head, tree):
    """With the bias another set is chosen on many positions; a weight is
    the chosen expert's own score over the chosen scores' sum, bias or no
    bias; the weights sum to ``routed_scaling_factor`` within 1e-6; and the
    reference gives the same ``(experts, weights)``."""
    cfg = small_config(operand_dtype=jnp.float32, routed_scale=1.0)
    d = head.dims_of(small_source())
    layer = tree["layers"][1]
    assert float(jnp.abs(layer["rb"]).max()) > 0.01  # the seeded bias is there
    x = jax.random.normal(jax.random.key(2), (200, 128), jnp.float32)
    top_e, top_w = _route(layer, x, cfg)
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                          @ np.asarray(layer["wr"], np.float64))))
    bias = np.asarray(layer["rb"], np.float64)
    best = np.argsort(-(s + bias), axis=1, kind="stable")[:, :cfg.top_k]
    np.testing.assert_array_equal(np.sort(top_e, 1), np.sort(best, 1))
    chosen = np.take_along_axis(s, top_e, 1)
    np.testing.assert_allclose(top_w, chosen / (chosen.sum(1, keepdims=True) + 1e-6),
                               rtol=1e-5)
    # within 1e-6 but for the renormalisation's own epsilon: sum / (sum +
    # 1e-6) with a sum of 0.6-1.8 is 0.5-1.7e-6 under one
    np.testing.assert_allclose(top_w.sum(1) + 1e-6 / chosen.sum(1), 1.0, atol=1e-6)
    # without the bias: another choice on many positions ...
    bare_e, bare_w = _route({k: v for k, v in layer.items() if k != "rb"}, x, cfg)
    differs = (np.sort(bare_e, 1) != np.sort(top_e, 1)).any(1)
    assert 0.2 < differs.mean() < 1.0
    # ... and where the choice is the same, the same weights to the bit
    same = ~differs
    order_a, order_b = np.argsort(top_e[same], 1), np.argsort(bare_e[same], 1)
    np.testing.assert_array_equal(
        np.take_along_axis(top_w[same], order_a, 1),
        np.take_along_axis(bare_w[same], order_b, 1))
    # a bias that is the same for every expert changes nothing at all
    shifted = dict(layer, rb=layer["rb"] * 0 + 3.0)
    e3, w3 = _route(shifted, x, cfg)
    np.testing.assert_array_equal(np.sort(e3, 1), np.sort(bare_e, 1))
    # scaled: the weights sum to the factor
    e25, w25 = _route(layer, x, small_config(operand_dtype=jnp.float32,
                                             routed_scale=2.5))
    np.testing.assert_allclose(w25.sum(1), 2.5, atol=1e-5)
    # the reference's (experts, weights)
    with jax.default_matmul_precision("highest"):
        ref_e, ref_w = head._choose(head._scores(layer, x, jnp.float32),
                                    layer["rb"], d)
    np.testing.assert_array_equal(np.asarray(ref_e), top_e)
    np.testing.assert_allclose(np.asarray(ref_w), top_w, atol=1e-6)


def _route_of_the_parent(x, layer, cfg):
    """``pangu_backbone.route`` as the parent commit has it, written out."""
    s = jax.nn.sigmoid(dp.mm(x, layer["wr"], cfg))
    top_s, top_e = jax.lax.top_k(s, cfg.top_k)
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_e, w * cfg.routed_scale


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_pangu_route_without_a_bias_is_the_parents_bit_for_bit(operands):
    cfg = pb.PanguConfig(hidden=64, layers=2, dense_layers=1, heads=4, q_rank=32,
                         kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
                         dense_width=96, experts=16, held_experts=4, top_k=4,
                         expert_width=32, operand_dtype=jnp.dtype(operands))
    assert cfg.renorm_eps == 1e-20 == pb.PanguConfig().renorm_eps
    layer = dict(pb.init_backbone(jax.random.key(1), cfg)["layers"][1])
    assert "rb" not in layer
    # since PR 51 the router's product is written experts-first (``mm_t``),
    # so the operands are such that its float32 sums are exact in any order:
    # small integers against multiples of 1/8, with equal scores among them
    rng = np.random.default_rng(2)
    layer["wr"] = jnp.asarray(rng.integers(-2, 3, (64, 16)) * 0.125, jnp.bfloat16)
    x = jnp.asarray(rng.integers(-2, 3, (300, 64)), jnp.float32)
    now = jax.jit(lambda x: dp.route(x, layer, cfg))
    then = jax.jit(lambda x: _route_of_the_parent(x, layer, cfg))
    for a, b in zip(now(x), then(x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the same values and, since PR 51, not the same program: the parent's
    # ``top_k`` is a sort, and ``route`` lowers to none (nor to a gather)
    assert "top_k" in then.lower(x).as_text()
    assert not any(op in now.lower(x).as_text()
                   for op in ("top_k", "sort", "gather"))


# -- the expert layer --------------------------------------------------------------


def _expert_loop(x, top_e, top_w, routed):
    """Every (position, expert) pair, one expert at a time, in float64."""
    x = np.asarray(x, np.float64)
    y = np.zeros_like(x)
    wg, wu, wd = (np.asarray(routed[k].astype(jnp.float32), np.float64)
                  for k in ("wg", "wu", "wd"))
    for e in range(wg.shape[0]):
        pos, slot = np.nonzero(np.asarray(top_e) == e)
        if len(pos):
            g = x[pos] @ wg[e]
            out = (g / (1 + np.exp(-g)) * (x[pos] @ wu[e])) @ wd[e]
            np.add.at(y, pos, out * np.asarray(top_w)[pos, slot][:, None])
    return y


def _steer_to_the_kernels(monkeypatch):
    """What a TPU would pick, on the CPU: the backend reports ``tpu`` and
    the kernels run through the Pallas interpreter. Steered here, in the
    test; the program has no option for it."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    dp.announce_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("gate_up", "down", "combine"):
        monkeypatch.setattr(kernels, name, functools.partial(
            getattr(kernels, name), interpret=True))


def _fed(cfg, positions: int) -> str:
    """How the kernels say they are fed at this size: hidden 1024 is not
    whole tiles of packed words, so the rows are gathered outside."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    fed = kernels.feed(positions * cfg.top_k, cfg.hidden, cfg.experts,
                       cfg.expert_width, positions=positions)
    assert fed.endswith("rows=gathered")
    return fed


@pytest.mark.parametrize("core", ["xla", "pallas"])
def test_expert_layer_equals_the_loop_over_experts(core, monkeypatch, caplog):
    """``grouped_experts`` with this head's router against the loop over
    experts: by XLA at the small size, and by the three Pallas kernels
    (interpreted) at this cell's kind of shape, which is not the one they
    were written for: 64 experts, 4 a position, a width that is a multiple
    of 128 and not 768."""
    if core == "xla":
        cfg, tolerance = small_config(operand_dtype=jnp.float32), 2e-4
        dp.announce_core.cache_clear()
    else:
        cfg = small_config(hidden=1024, experts=64, top_k=4, expert_width=384,
                           layer_types=("conv", "conv"))
        tolerance = 2e-2  # bfloat16: x, the weights and ``mid`` rounded once
        _steer_to_the_kernels(monkeypatch)
    layer = lb.init_backbone(jax.random.key(2), cfg)["layers"][1]
    layer["rb"] = jax.random.normal(jax.random.key(5), (cfg.experts,)) * 0.1
    n = 128
    x = jax.random.normal(jax.random.key(3), (n, cfg.hidden), jnp.float32)
    top_e, top_w = jax.jit(lambda x: dp.route(x, layer, cfg))(x)
    assert np.bincount(np.asarray(top_e).ravel()).sum() == n * cfg.top_k
    with caplog.at_level("INFO", logger=dp.logger.name):
        got = np.asarray(jax.jit(lambda x, e, w: el.grouped_experts(
            x, e, w, layer["routed"], cfg))(x, top_e, top_w))
    said = {r.getMessage() for r in caplog.records}
    assert said == ({"expert core: xla-ragged-dot (backend=cpu)",
                     "combine: xla-gather (backend=cpu)"} if core == "xla" else
                    {f"expert core: pallas-grouped ({_fed(cfg, n)}) (backend=tpu)",
                     "combine: pallas-rows (backend=tpu)"})
    want = _expert_loop(x, top_e, top_w, layer["routed"])
    np.testing.assert_allclose(got, want, atol=tolerance * np.abs(want).max(),
                               rtol=0)
    less_w = np.asarray(top_w).copy()
    less_w[17, 0] = 0.0
    less = _expert_loop(x, top_e, less_w, layer["routed"])
    assert np.abs(less[17] - got[17]).max() > 10 * np.abs(want - got).max()


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_ffn_sublayer_alone(head, tree, operands):
    """``x + FF(N_ffn(x))``: the leading dense layer and an expert layer
    against the reference's."""
    dt = jnp.dtype(operands)
    cfg, d = small_config(operand_dtype=dt), head.dims_of(small_source())
    x = stream(seed=3)
    for index in (0, 2):
        layer = tree["layers"][index]

        def sublayer(x):
            flat = dp.rms_norm(x.reshape(-1, cfg.hidden), layer["g2"], cfg.eps)
            if "dense" in layer:
                m = dp.swiglu(flat, layer["dense"], cfg)
            else:
                top_e, top_w = dp.route(flat, layer, cfg)
                m = el.grouped_experts(flat, top_e, top_w, layer["routed"], cfg)
            return x + m.reshape(x.shape)

        got = np.asarray(jax.jit(sublayer)(x))
        with jax.default_matmul_precision("highest"):
            want = np.asarray((head._dense if "dense" in layer else head._moe)(
                layer, x, d, dt))
        np.testing.assert_allclose(got, want, atol=3e-5 if operands == "float32"
                                   else 2e-3, rtol=0)


def test_seeded_bias_evens_the_loads_and_moves_the_choice(head):
    """The seeded bias is the balancing rule's: over the positions it was
    fitted on no expert is far from the mean load, the bare router's are,
    and the share of positions whose set it changes is kept for PERF.md."""
    rng = np.random.default_rng(5)
    logits = rng.normal(0, 1, (4000, 16)) + rng.normal(0, 0.8, (16,))
    s = 1 / (1 + np.exp(-logits))
    bias, share = head._balancing_bias(s, 4)
    assert bias.dtype == np.float32 and bias.shape == (16,)

    def loads(b):
        return np.bincount(np.argsort(-(s + b), 1)[:, :4].ravel(), minlength=16)

    mean = 4000 * 4 / 16
    assert np.abs(loads(bias) - mean).max() < 0.1 * mean
    assert np.abs(loads(0.0) - mean).max() > 0.5 * mean
    assert 0.3 < share < 1.0
    head.make_params(7, small_source())
    moved = head._made["bias_moved"]
    assert len(moved) == 3 and all(0.05 < m < 1.0 for m in moved)


# -- the gauges and the served path -------------------------------------------------


@pytest.fixture
def small_lfm2(monkeypatch):
    """``SESSION_HEAD=lfm2`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "lfm2", dataclasses.replace(
        session_heads.HEADS["lfm2"],
        scores=lambda sp, win, lp: lb.backbone_scores(sp, win, lp, cfg),
        init=lambda: lb.init_backbone(jax.random.key(11), cfg)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_lfm2, environment):
    """The new cell's own files, the source's sizes cut to the small one:
    one server, the head through ``serve/index_program.build``, index-mode
    ``ScoreBatch`` over a real socket, every reply against
    ``chipbench/reference.py``; the boot gauges of what the head holds and
    is made of, on ``/metrics`` and ``/debug/sessionz``; the two position
    counters."""
    spec = copy.deepcopy(validate.load_cell("lfm2-conv-insession"))
    small = small_source()
    spec["config"]["head"] = dict(spec["config"]["head"], **small.pop("head"))
    spec["config"].update(small)
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    run = harness.Run(spec, seed=4_300_000_007, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "lfm2"
        run.fill()
        # this head casts its operands itself on every backend, so a CPU
        # run is judged at the stated precision as on the chip
        run.device = types.SimpleNamespace(platform="as-on-the-chip")
        ok, numbers = run.check()
        c_ok, control = run.judge(
            run.config["precision"]["control_operand_dtype"], control=True)
        built = run.inner._fused_fns
        counters = run.counters()
        snap = run.inner.session.snapshot()
        text = run.server.metrics.registry.render_text()
    finally:
        run.shutdown()
    assert any(k[0] == "session" for k in built)
    assert ok, numbers
    assert not c_ok, control
    assert numbers["session_bit_mismatch"] == 0 and numbers["score_max_err"] <= 1
    assert numbers["warm_rows"] > numbers["rows"] // 2
    assert numbers["folded_rows"] > 0
    assert counters["risk_session_head_positions_total"] == 16 * numbers["rows"]
    real = counters["risk_session_head_real_positions_total"]
    assert numbers["rows"] < real < 16 * numbers["rows"]
    assert snap["head_positions"] == 16 * numbers["rows"]
    resident = sum(int(a.nbytes) for a in jax.tree.leaves(run.head_params))
    assert snap["head_resident_bytes"] == resident > 0
    c = session_heads.HEADS["lfm2"].config
    assert (snap["head_experts_held"], snap["head_experts_routed"]) == (
        c.experts, c.experts) == (64, 64)
    assert snap["head_layers"] == {"conv": 4, "attention": 1, "window": 0, "ssm": 0,
                                   "linear": 0, "memory": 0, "cross": 0, "mtp": 0, "dense": 1, "moe": 4}
    # what the step's expert layer said it runs as when it was traced (here
    # the CPU's cores; on a TPU the kernels and how they are fed)
    assert snap["head_cores"]["expert core"] == "xla-ragged-dot (backend=cpu)"
    assert snap["head_cores"]["combine"] == "xla-gather (backend=cpu)"
    text = text.replace(".0\n", "\n")
    for name, value in (("resident_bytes", resident), ("experts_held", 64),
                        ("experts_routed", 64)):
        assert f"risk_session_head_{name} {value}" in text
    for kind, value in snap["head_layers"].items():
        assert f'risk_session_head_layers{{kind="{kind}"}} {value}' in text


def test_replay_verifies_a_ledger_written_under_the_head(small_lfm2, monkeypatch):
    monkeypatch.setenv("SESSION_HEAD", "lfm2")
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import ledger as ledger_mod
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine
    from tools.replay import replay_directory

    d = tempfile.mkdtemp(prefix="lfm2-replay-test-")
    eng = TPUScoringEngine(
        ScoringConfig(), ml_backend="mock",
        batcher_config=BatcherConfig(batch_size=16, latency_tiers=(8,),
                                     max_wait_ms=1.0),
        feature_cache=8, session_state=True)
    eng.ledger = ledger_mod.DecisionLedger(d)
    eng.ensure_cache()
    try:
        accts = [f"k{i}" for i in range(5)]
        for r in range(6):
            ids = accts + [accts[r % 5]]
            out = eng.score_columns_cached(
                ids, [700 + 13 * i + r for i in range(len(ids))],
                ["bet" if r % 2 == 0 else "deposit"] * len(ids),
                now=1_700_000_000.0 + 30.0 * r)
        assert eng.session.head == "lfm2"
        assert np.all((out["ml_score"] >= 0) & (out["ml_score"] <= 1))
        # steady state (every account resident): one dispatch a chunk
        from igaming_platform_tpu.serve import scorer as scorer_mod

        calls, real = [], scorer_mod._device_dispatch
        monkeypatch.setattr(
            scorer_mod, "_device_dispatch",
            lambda fn, *a, **kw: (calls.append(fn), real(fn, *a, **kw))[1])
        eng.score_columns_cached(accts, [900] * 5, ["bet"] * 5,
                                 now=1_700_000_000.0 + 30.0 * 7)
        monkeypatch.setattr(scorer_mod, "_device_dispatch", real)
        assert len(calls) == 1
    finally:
        eng.ledger.close()
        eng.close()
    v = replay_directory(d, batch=16)
    assert v["session_records"] == 41
    assert v["session_verified"] == 41 and v["session_hash_mismatch"] == 0
    assert v["session_ok"] and v["ok"], json.dumps(v)[:400]


@pytest.mark.parametrize("name,layers", [
    ("pattern", {"conv": 0, "attention": 0, "window": 0, "ssm": 0, "linear": 0, "memory": 0, "cross": 0, "mtp": 0, "dense": 0,
                 "moe": 0}),
    ("transformer", {"conv": 0, "attention": 1, "window": 0, "ssm": 0, "linear": 0, "memory": 0, "cross": 0, "mtp": 0,
                     "dense": 1, "moe": 0})])
def test_layer_gauge_of_the_small_heads(name, layers):
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve.session_state import SessionStateManager

    metrics = ServiceMetrics("risk")
    mgr = SessionStateManager(8, head=name, metrics=metrics)
    assert mgr.snapshot()["head_layers"] == layers
    text = metrics.registry.render_text().replace(".0\n", "\n")
    for kind, value in layers.items():
        assert f'risk_session_head_layers{{kind="{kind}"}} {value}' in text


@pytest.mark.parametrize("name,layers", [
    ("pattern", {}), ("transformer", {"attention": 1, "dense": 1}),
    ("keye", {"attention": 4, "moe": 4}),
    ("pangu", {"attention": 5, "dense": 1, "moe": 4}),
    ("lfm2", {"conv": 4, "attention": 1, "dense": 1, "moe": 4}),
    ("falconh1", {"ssm": 4, "attention": 4, "dense": 4}),
    ("ling", {"linear": 6, "attention": 1, "dense": 1, "moe": 6})])
def test_every_head_says_what_its_stack_is_made_of(name, layers):
    assert session_heads.HEADS[name].layers == {
        kind: layers.get(kind, 0) for kind in session_heads.LAYER_KINDS}
    assert set(layers) <= set(session_heads.LAYER_KINDS)


def test_unknown_head_lists_the_new_name():
    with pytest.raises(ValueError) as err:
        session_heads.session_head("mamba")
    assert "'lfm2'" in str(err.value) and "'pangu'" in str(err.value)
    assert session_heads.HEADS["lfm2"].experts == (64, 64)


def test_chip_smoke_phase_runs_the_head_against_its_reference():
    """``chip_smoke.phase_backbone(head_name="lfm2")`` at the small size on
    the CPU: the head against its reference, and the cores that ran the
    expert layer and its way back."""
    import chip_smoke

    report = chip_smoke.phase_backbone(head_name="lfm2", cfg=small_config(),
                                       config=small_source(), rows=8)
    assert report["max_err"] < 1e-4 and report["rows"] == 8
    assert report["head"] == "lfm2"
    assert report["expert_core"] == "expert core: xla-ragged-dot (backend=cpu)"
    assert report["way_back"] == "combine: xla-gather (backend=cpu)"
    assert report["attention_core"] is None  # this head's core is einsums
    assert report["resident_bytes"] > 0
