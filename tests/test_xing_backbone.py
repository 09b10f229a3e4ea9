"""The ``xing`` session head (models/xing_backbone.py) against its plain
reference (chipbench/heads/xing4_29b_a4b.py) at a small size on the CPU:
the whole head, the hyper-connection's three parts, YaRN, the live mask,
and the five backbones whose shared functions were widened under them.

The small size keeps every mechanism: three layers (the source's layer 1,
dense, and two expert layers); four residual streams (and one) mixed by a
map of 20 Sinkhorn rounds; latent attention of 4 heads of 16 + 8 against 16
from a query latent of 32 and a key-value latent of 16, YaRN on the 4
rotary pairs; a SwiGLU of 96; a shared expert beside 8 bias-chosen experts
of width 32, 2 a token, every one held; hidden 64; 16-event windows of
mixed lengths, seeded weights. The program holds the streams stream-major
and the maps positions along the lanes; the reference writes them stream by
stream.
"""

from __future__ import annotations

import copy
import dataclasses
import hashlib
import math
import os
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from chipbench import harness, reference, validate  # noqa: E402
from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
from igaming_platform_tpu.models import expert_layer as el  # noqa: E402
from igaming_platform_tpu.models import session_heads  # noqa: E402
from igaming_platform_tpu.models import xing_backbone as xb  # noqa: E402

CONFIG = "risk-seqhead-xing4.0-29b-a4b"
CELL = "xing-mhc-insession"
PUBLISHED = validate.load_source(CONFIG)["config"]
EXPERTS = 8
LAYERS = {"conv": 0, "attention": 3, "window": 0, "ssm": 0, "linear": 0, "memory": 0, "cross": 0, "mtp": 0, "dense": 1, "moe": 2}


def small_source(**over) -> dict:
    """The small size as a configuration file would state it: the source's
    own keys, its switches and its ``rope_scaling`` group as published."""
    source = dict(PUBLISHED)
    source.update({
        "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": EXPERTS, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts_per_tok": 2,
        "head": {"published": {"num_hidden_layers": 40,
                               "first_k_dense_replace": 2,
                               "n_routed_experts": EXPERTS},
                 "layers_held": [1, 2, 3]}})
    source.update(over)
    return source


def program_config(source: dict, **over) -> xb.XingConfig:
    """The program's configuration of a source's keys."""
    yarn = source["rope_scaling"]
    kw = dict(
        hidden=source["hidden_size"], layers=source["num_hidden_layers"],
        dense_layers=source["first_k_dense_replace"],
        heads=source["num_attention_heads"], q_rank=source["q_lora_rank"],
        kv_rank=source["kv_lora_rank"], nope_dim=source["qk_nope_head_dim"],
        rope_dim=source["qk_rope_head_dim"], v_dim=source["v_head_dim"],
        dense_width=source["intermediate_size"],
        experts=source["n_routed_experts"],
        top_k=source["num_experts_per_tok"],
        expert_width=source["moe_intermediate_size"],
        routed_scale=float(source["routed_scaling_factor"]),
        rope_theta=float(source["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_positions=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale=float(yarn["mscale"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        streams=source["hc_mult"], hc_rounds=source["hc_sinkhorn_iters"],
        hc_eps=source["hc_eps"],
        hc_clip=(float(source["mhc_h_res_clamp_min"]),
                 float(source["mhc_h_res_clamp_max"])),
        eps=source["rms_norm_eps"],
        init_depth=source["head"]["published"]["num_hidden_layers"])
    kw.update(over)
    return xb.XingConfig(**kw)


def small_config(**over) -> xb.XingConfig:
    return program_config(small_source(), **over)


@pytest.fixture(scope="module")
def head():
    return validate.load_code("heads", "xing4_29b_a4b")


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
        hid = xb.backbone_hidden(p, w, l, cfg)
        last = jnp.clip(l - 1, 0, w.shape[1] - 1)
        hl = jnp.take_along_axis(hid, last[:, None, None], axis=1)[:, 0, :]
        return jnp.sum(hl * p["head"]["w"][:, 0], -1) + p["head"]["b"][0], hl

    logit, hl = jax.jit(both)(params, jnp.asarray(x),
                              jnp.asarray(lengths, jnp.int32))
    return np.asarray(logit), np.asarray(hl)


def program_scores(cfg, params, x, lengths):
    return np.asarray(jax.jit(
        lambda p, w, l: xb.backbone_scores(p, w, l, cfg))(
            params, jnp.asarray(x), jnp.asarray(lengths, jnp.int32)))


def streams_of(positions: int = 96, n: int = 4, hidden: int = 64, seed: int = 0):
    """Streams [n, P, hidden] with some spread, and the same position-major
    [P, n, hidden] as the reference holds them."""
    x = jax.random.normal(jax.random.key(seed), (n, positions, hidden),
                          jnp.float32) * 2.0
    return x, jnp.moveaxis(x, 0, 1)


# -- the whole head against the reference ----------------------------------------

# With bfloat16 operands a value on a rounding boundary falls either side
# by the order of a float32 accumulation, and one such operand is 2^-8 of
# itself. The logit and every channel of the final hidden state (unit
# spread) are held to half a rounding of a unit value, 2^-9; float8
# operands (a rounding is 2^-4) miss it several times over, and so does a
# map without its Sinkhorn rounds.
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
    assert hidden.shape == want_hidden.shape == (24, 64)
    # float32: the order of float32 sums alone (the maps' sums stream by
    # stream against the one contraction; the rounds' adds in another order)
    atol = 5e-5 if operands == "float32" else ROUNDING
    np.testing.assert_allclose(logit, want_logit, atol=atol, rtol=0)
    np.testing.assert_allclose(hidden, want_hidden, atol=atol, rtol=0)
    got = program_scores(cfg, tree, x, lens)
    want = head.forward(tree, x, lens, reference.rounder(operands))
    np.testing.assert_allclose(got, want, atol=atol / 4, rtol=0)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_one_stream_equals_the_reference_too(head, operands):
    """``hc_mult`` 1: one stream, a 1 x 1 map the rounds drive to 1, a
    sublayer that reads ``H_pre x`` and writes ``H_res x + H_post y``."""
    dt = jnp.dtype(operands)
    source = small_source(hc_mult=1)
    one = head.make_params(9, source)
    cfg = program_config(source, operand_dtype=dt)
    x, lens = windows(12, (16, 5, 1), seed=2)
    try:
        want = head._logits(one, x, lens, head.dims_of(source), dt)
    finally:
        head.make_params(7, small_source())  # the module's last tree again
    logit, _ = program_logits(cfg, one, x, lens)
    np.testing.assert_allclose(
        logit, want, atol=5e-5 if operands == "float32" else ROUNDING, rtol=0)


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


@pytest.mark.parametrize("control", ["no-rounds", "constant-maps",
                                     "unscaled-softmax"])
def test_a_program_without_a_mechanism_is_told_apart(head, tree, control):
    """What the seeded ``a`` and ``b`` are drawn for: a program that skipped
    the Sinkhorn rounds, held its maps constant (``a`` = 0: the paper's
    initial maps) or left the softmax scale unmultiplied lies outside the
    tolerance the real one is held to. (YaRN's rates are held to their hand
    values below: over 16 positions the pairs they slow turn by hundredths
    of a radian either way.)"""
    d = head.dims_of(small_source())
    x, lens = windows(24, (16, 9, 4, 12), seed=5)
    want = head._logits(tree, x, lens, d, jnp.bfloat16, hidden=True)
    over, params = {}, tree
    if control == "no-rounds":
        over = {"hc_rounds": 0}
    elif control == "constant-maps":
        params = dict(tree, layers=[
            dict(l, **{k: dict(l[k], a=jnp.zeros(3)) for k in ("hc_attn", "hc_mlp")})
            for l in tree["layers"]])
    else:
        over = {"yarn_mscale_all_dim": 0.0}
    _, got = program_logits(small_config(**over), params, x, lens)
    assert np.abs(got - want).max() > 4 * ROUNDING
    _, sound = program_logits(small_config(), tree, x, lens)
    assert np.abs(sound - want).max() <= ROUNDING


def test_rounding_is_where_the_reference_puts_it(tree):
    x, lens = windows(24, (16,))
    a = program_scores(small_config(operand_dtype=jnp.float32), tree, x, lens)
    b = program_scores(small_config(operand_dtype=jnp.bfloat16), tree, x, lens)
    diff = np.abs(a - b)
    assert diff.max() > 1e-6 and np.median(diff) < 0.01


# -- padding ----------------------------------------------------------------------


@pytest.mark.parametrize("lengths", [1, 4, 9])
def test_positions_after_the_last_real_one_change_nothing(tree, lengths):
    """Blind to padding at the scored position: whatever the positions past
    a window's length hold, attention is causal, the maps, the read and the
    write are a position's own, and padding is not routed."""
    cfg = small_config()
    x, lens = windows(8, (lengths,))
    junk = x.copy()
    junk[:, lengths:] = np.random.default_rng(1).normal(0, 3, junk[:, lengths:].shape)
    np.testing.assert_array_equal(program_scores(cfg, tree, x, lens),
                                  program_scores(cfg, tree, junk, lens))


def test_padding_is_not_routed_where_every_expert_is_held(tree):
    """Every expert is held and a window's padding is left out all the
    same: with the ``live`` mask the expert layer takes the share's passes,
    a padded position gets exact zeros from the routed experts (the shared
    expert alone in the backbone), and a live one what the layer without a
    mask gives it."""
    cfg = small_config(operand_dtype=jnp.float32)
    layer = tree["layers"][1]
    flat = jax.random.normal(jax.random.key(3), (96, 64), jnp.float32)
    live = jnp.asarray(np.arange(96) % 16 < 5)
    top_e, top_w = dp.route(flat, layer, cfg)
    masked = np.asarray(el.grouped_experts(flat, top_e, top_w, layer["routed"],
                                           cfg, live=live))
    whole = np.asarray(el.grouped_experts(flat, top_e, top_w, layer["routed"], cfg))
    assert np.abs(whole[~np.asarray(live)]).min(axis=-1).max() > 0
    np.testing.assert_array_equal(masked[~np.asarray(live)], 0.0)
    np.testing.assert_allclose(masked[np.asarray(live)], whole[np.asarray(live)],
                               atol=1e-5, rtol=0)
    # the sizes the products are grouped by count the live pairs alone
    local = jnp.where(live[:, None], top_e, cfg.experts).reshape(-1)
    assert int(el.expert_sizes(local, cfg.experts).sum()) == int(live.sum()) * cfg.top_k


# -- the hyper-connection's three parts -------------------------------------------


def _hyper(n: int, hidden: int, seed: int, a=(1.0, 1.0, 1.0), b=None) -> dict:
    rng = np.random.default_rng(seed)
    columns = 2 * n + n * n
    return {"phi": jnp.asarray(rng.standard_normal((n * hidden, columns))
                               / math.sqrt(n * hidden), jnp.float32),
            "b": jnp.asarray(rng.normal(0, 0.5, columns) if b is None else b,
                             jnp.float32),
            "a": jnp.asarray(a, jnp.float32)}


@pytest.mark.parametrize("a_res,why", [
    (1.0, "unclipped"), (40.0, "clipped"), (1000.0, "every-logit-at-a-bound")])
def test_the_mixing_map_after_twenty_rounds(a_res, why):
    """``H_res`` after 20 rounds, over 512 positions. The last division is
    by the rows' sums, so every row sums to 1 within 1e-5 whatever the
    logits; nothing is NaN or negative, with ``e^30`` beside ``e^-30`` in
    one matrix too. The columns follow where the alternation has
    converged: over unclipped draws (logits of spread ~1.1) within 1e-4 on
    99 positions of 100 and 1e-3 on all. Where the clip cuts, 20 rounds are
    NOT enough (two rows whose mass lies in one column converge sublinearly:
    a column may sum to 2): the map is then row-stochastic and no more,
    which is the model's as published (``hc_sinkhorn_iters`` 20), and both
    sides compute the same 20 rounds."""
    cfg = small_config()
    x, _ = streams_of(positions=512, seed=4)
    hc = _hyper(4, 64, 1, a=(1.0, 1.0, a_res))
    res = np.asarray(dp.hyper_maps(x, hc, cfg)[2])              # [i, j, P]
    assert res.shape == (4, 4, 512) and np.isfinite(res).all()
    assert (res >= 0).all() and (res <= 1.0).all()
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-5)  # a row
    columns = np.abs(res.sum(axis=0) - 1.0).max(axis=0)          # a position
    np.testing.assert_allclose(res.sum(axis=0).mean(axis=0), 1.0, atol=1e-5)
    if why == "unclipped":
        assert columns.max() < 1e-3 and np.quantile(columns, 0.99) < 1e-4
    else:
        # the clip is doing something: entries pinned at both bounds
        m = np.asarray(dp.hyper_maps(
            x, hc, dataclasses.replace(cfg, hc_rounds=0))[2])
        assert np.isclose(m.max(), math.exp(30.0), rtol=1e-5)
        assert np.isclose(m.min(), math.exp(-30.0), rtol=1e-5)
        assert columns.max() > 0.5


def test_no_round_leaves_the_exponential_and_each_round_is_two_divisions():
    z = jax.random.normal(jax.random.key(0), (4, 4, 7), jnp.float32)
    np.testing.assert_array_equal(np.asarray(dp.sinkhorn(z, 0, 1e-6)),
                                  np.asarray(jnp.exp(z)))
    m = np.exp(np.asarray(z, np.float64))
    m = m / (m.sum(axis=0, keepdims=True) + 1e-6)
    m = m / (m.sum(axis=1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(dp.sinkhorn(z, 1, 1e-6)), m, rtol=1e-5)


@pytest.mark.parametrize("n", [4, 1])
def test_maps_read_and_write_equal_the_references_stream_by_stream(head, n):
    """``decoder_parts.hyper_maps``, ``hyper_read`` and ``hyper_write`` over
    streams held stream-major with the maps positions along the lanes,
    against the reference's lists over streams: the same numbers."""
    source = small_source(hc_mult=n)
    d, cfg = head.dims_of(source), program_config(source)
    x, flat = streams_of(n=n, seed=6)
    hc = _hyper(n, 64, 2, a=(0.7, 1.3, 0.9))
    y = jax.random.normal(jax.random.key(9), (96, 64), jnp.float32)
    pre, post, res = dp.hyper_maps(x, hc, cfg)
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = head._maps(flat, hc, d)
        want_u = head._read(flat, h_pre, d)
        want_x = head._write(flat, h_post, h_res, y, d)
    np.testing.assert_allclose(np.asarray(pre), np.stack(h_pre), atol=2e-6)
    np.testing.assert_allclose(np.asarray(post), np.stack(h_post), atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(res), np.stack([np.stack(row) for row in h_res]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(dp.hyper_read(x, pre)), want_u, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(dp.hyper_write(x, res, post, y)),
        np.moveaxis(np.asarray(want_x), 1, 0), atol=1e-5)


def test_the_seeded_maps_move_with_the_position(head, tree):
    """What the draw of ``a`` and ``b`` is for (``head.assumed.
    hyper_parameters_seeded``): over plausible windows each map differs
    from position to position by tenths, around the centres it was drawn
    at, so a check can see whether a program computes them."""
    d = head.dims_of(small_source())
    win, lens = head.plausible_windows(np.random.default_rng(3), 32)
    x = head._enter(tree["embed"], jnp.asarray(win), d, jnp.float32)
    x = head._attention_sublayer(tree["layers"][0], x, d, jnp.float32)
    flat = x.reshape(-1, d.streams, d.hidden)
    real = (np.arange(16)[None, :] < lens[:, None]).reshape(-1)
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = head._maps(flat, tree["layers"][0]["hc_mlp"], d)
    pre = np.stack(h_pre)[:, real]
    post = np.stack(h_post)[:, real]
    res = np.stack([np.stack(r) for r in h_res])[:, :, real]
    assert pre.std(axis=1).min() > 0.03 and 0.1 < pre.mean() < 0.5
    assert post.std(axis=1).min() > 0.1 and 0.5 < post.mean() < 1.5
    assert res.std(axis=2).min() > 0.02
    # a stream leans to itself and still mixes
    diag = res[np.arange(4), np.arange(4)].mean()
    assert 0.4 < diag < 0.95


@pytest.mark.parametrize("part", ["attention", "dense", "moe"])
def test_one_stream_with_unit_maps_is_the_pre_norm_block(tree, part):
    """What ties the hyper-connected sublayer to the blocks the repo has:
    with ``n`` = 1 and the three maps at 1 (``a`` = 0; ``b`` large for
    ``H_pre``, 0 for ``H_post`` and ``H_res``) a sublayer is ``x +
    F(norm(x))``."""
    cfg = small_config(streams=1, operand_dtype=jnp.float32)
    unit = {"phi": jnp.zeros((64, 3), jnp.float32),
            "b": jnp.asarray([40.0, 0.0, 0.0], jnp.float32),
            "a": jnp.zeros((3,), jnp.float32)}
    b, t = 6, 16
    x = jax.random.normal(jax.random.key(5), (1, b * t, 64), jnp.float32)
    layer = tree["layers"][0 if part != "moe" else 1]
    cos, sin = dp.rope_angles(b, t, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)

    def f(u):
        if part == "attention":
            a = dp.rms_norm(u, layer["g1"], cfg.eps).reshape(b, t, -1)
            return dp.latent_attention(a, layer, cos, sin, cfg,
                                       scale_by=cfg.softmax_scale_by).reshape(b * t, -1)
        flat = dp.rms_norm(u, layer["g2"], cfg.eps)
        if part == "dense":
            return dp.swiglu(flat, layer["dense"], cfg)
        top_e, top_w = dp.route(flat, layer, cfg)
        return dp.swiglu(flat, layer["shared"], cfg) + el.grouped_experts(
            flat, top_e, top_w, layer["routed"], cfg)

    streams, squares = xb.hyper_sublayer(x, unit, cfg, f)
    got = np.asarray(streams[0])
    np.testing.assert_allclose(np.asarray(squares),
                               np.sum(got.astype(np.float64) ** 2, axis=-1),
                               rtol=1e-5)
    want = np.asarray(x[0] + f(x[0]))
    assert np.abs(np.asarray(f(x[0]))).max() > 1e-2
    # H_res = 1 / (1 + hc_eps) after the rounds: 1e-6 of a stream of spread 1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# -- YaRN ------------------------------------------------------------------------


def test_yarn_by_hand_at_the_published_group():
    """``rope_scaling`` as published (factor 64 over 4,096, ``beta_fast``
    32, ``beta_slow`` 1, theta 10,000, 64 rotary channels): the ramp runs
    from pair 10 to pair 23, pairs up to 10 keep their plain rate, pairs
    from 23 turn 64 times slower, pair 16 lies 6/13 of the way; the softmax
    scale is multiplied by (0.1 ln 64 + 1)^2 = 2.005 and cos and sin by 1."""
    scaling = PUBLISHED["rope_scaling"]
    rates = dp.yarn_frequencies(64, 10000.0, scaling)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    c = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(10000.0))
    assert (math.floor(c(32)), math.ceil(c(1))) == (10, 23)
    np.testing.assert_allclose(rates[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(rates[23:], plain[23:] / 64, rtol=1e-12)
    r = 6 / 13
    assert rates[16] == pytest.approx(plain[16] * (1 - r) + plain[16] / 64 * r)
    assert np.all(np.diff(rates) < 0)
    cfg = xb.XingConfig()
    assert cfg.softmax_scale_by == pytest.approx(2.005, abs=5e-4)
    assert cfg.softmax_scale_by == pytest.approx((0.1 * math.log(64) + 1) ** 2)
    assert dp.yarn_mscale(64, 1.0) / dp.yarn_mscale(64, 1.0) == 1.0
    cos, sin = dp.rope_angles(2, 16, 64, 10000.0, cfg.rope_scaling)
    t = np.arange(16)[:, None]
    np.testing.assert_allclose(np.asarray(cos)[1], np.cos(t * rates), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin)[0], np.sin(t * rates), atol=1e-6)


def test_a_factor_of_one_is_the_plain_rotary():
    plain = dp.rope_angles(3, 16, 64, 10000.0)
    one = dp.rope_angles(3, 16, 64, 10000.0, {
        "factor": 1.0, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    for a, b in zip(plain, one, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    assert xb.XingConfig(yarn_factor=1.0).rope_scaling is None
    assert xb.XingConfig(yarn_factor=1.0).softmax_scale_by == 1.0
    # and every other head's call, without the argument, is the parent's
    stretched = dp.rope_angles(3, 16, 64, 10000.0, xb.XingConfig().rope_scaling)
    assert np.abs(np.asarray(stretched[0]) - np.asarray(plain[0])).max() > 0.01


def test_the_references_rates_are_the_programs(head):
    d = head.dims_of(validate.load_data("configs", CONFIG))
    np.testing.assert_allclose(
        head.yarn_rates(d),
        dp.yarn_frequencies(64, 10000.0, PUBLISHED["rope_scaling"]), rtol=1e-12)
    assert head.yarn_m(64.0, 1.0) ** 2 == pytest.approx(xb.XingConfig().softmax_scale_by)


@pytest.mark.parametrize("core", ["einsums", "kernel"])
def test_the_softmax_scales_multiplier_reaches_the_core(core):
    """``scale_by`` in the einsums and in the window kernel (interpreted):
    the scores times it before the softmax, so the result is the core's at
    queries scaled by it."""
    from igaming_platform_tpu.ops.pallas import window_attention as wa

    widths = dict(heads=2, nope=64, rope=64, dv=64, window=16)
    keys = jax.random.split(jax.random.key(1), 3)
    p = 128
    q = jax.random.normal(keys[0], (p, 2 * 128), jnp.float32)
    kv = jax.random.normal(keys[1], (p, 2 * 128), jnp.float32).astype(jnp.bfloat16)
    kr = jax.random.normal(keys[2], (p, 64), jnp.float32).astype(jnp.bfloat16)
    cos, sin = (a.reshape(p, -1) for a in dp.rope_angles(8, 16, 64, 1e4))
    run = (dp.latent_core_by_einsums if core == "einsums" else
           lambda *a, **k: wa.window_attention(*a, **k, interpret=True))
    plain = np.asarray(run(q, kv, kr, cos, sin, **widths), np.float32)
    twice = np.asarray(run(q, kv, kr, cos, sin, **widths, scale_by=2.0), np.float32)
    by_hand = np.asarray(run(q * 2.0, kv, kr, cos, sin, **widths), np.float32)
    assert np.abs(twice - plain).max() > 0.05
    np.testing.assert_allclose(twice, by_hand, atol=0.02, rtol=0)
    again = np.asarray(run(q, kv, kr, cos, sin, **widths, scale_by=1.0), np.float32)
    np.testing.assert_array_equal(again, plain)


def test_the_window_kernel_takes_the_published_head_widths(monkeypatch):
    """32 heads of 128 + 64 against 128 at 4,096 and 1,024 positions
    (``pangu``'s head widths): the kernel's ``supports`` admits them, and
    on a TPU the core says ``pallas-windows`` with the multiplier handed
    on."""
    from igaming_platform_tpu.ops.pallas import window_attention as wa

    cfg = xb.XingConfig()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp.announce_core.cache_clear()
    for positions in (4096, 1024):
        q = jax.ShapeDtypeStruct((positions, 32 * 192), jnp.float32)
        kvb = jax.ShapeDtypeStruct((positions, 32 * 256), jnp.bfloat16)
        assert wa.supports(q, kvb, heads=32, nope=128, rope=64, dv=128, window=16)
        core = dp.latent_attention_core(q, kvb, cfg, 16,
                                        scale_by=cfg.softmax_scale_by)
        assert core.func is wa.window_attention
        assert core.keywords["scale_by"] == cfg.softmax_scale_by
    assert dp.announced_cores()["attention core"] == "pallas-windows (backend=tpu)"
    # without a multiplier the scale is the other heads'
    assert dp.latent_attention_core(q, kvb, cfg, 16).keywords["scale_by"] == 1.0
    dp.announce_core.cache_clear()


# -- the other backbones lower as before -------------------------------------------


def _small_of(name: str):
    """(module, the small configuration its own test file pins it at)."""
    from test_falconh1_backbone import small_config as small_falconh1
    from test_ling_backbone import _small_lfm2, _small_pangu
    from test_ling_backbone import small_config as small_ling

    from igaming_platform_tpu.models import falconh1_backbone as fb
    from igaming_platform_tpu.models import keye_backbone as kb
    from igaming_platform_tpu.models import lfm2_backbone as lfm
    from igaming_platform_tpu.models import ling_backbone as lb
    from igaming_platform_tpu.models import pangu_backbone as pb

    if name == "keye":
        return kb, kb.BackboneConfig(
            hidden=128, layers=2, heads=4, kv_heads=2, head_dim=32, experts=8,
            top_k=2, expert_width=64, idx_heads=2, idx_dim=16, idx_topk=8,
            mrope_section=(4, 6, 6))
    return {"pangu": (pb, _small_pangu()), "lfm2": (lfm, _small_lfm2()),
            "falconh1": (fb, small_falconh1()), "ling": (lb, small_ling())}[name]


@pytest.mark.parametrize("name,sha256", [
    ("keye", "97b106f2039253a0"), ("pangu", "6f25d45683d24dfe"),
    ("lfm2", "ce10c44bd93b8d8d"), ("falconh1", "512502d3a3848a1e"),
    ("ling", "2e31ca761a14c5a0")])
def test_the_backbones_under_the_widened_functions_lower_as_before(name, sha256):
    """``rope_angles`` (a ``scaling``), ``latent_attention`` and its core (a
    ``scale_by``) and ``grouped_experts`` (a ``live`` mask where every
    expert is held) were widened under the five backbones: at the small
    sizes tests/test_falconh1_backbone.py and tests/test_ling_backbone.py
    pin them at, the StableHLO of ``backbone_scores`` is byte for byte what
    the parent commit (b80f8ae) lowers, held by its sha256 (``ling``'s
    computed there for this test; the other four are those files')."""
    module, cfg = _small_of(name)
    params = jax.eval_shape(lambda: module.init_backbone(jax.random.key(0), cfg))
    text = jax.jit(lambda p, w, l: module.backbone_scores(p, w, l, cfg)).lower(
        params, jax.ShapeDtypeStruct((6, 16, 12), jnp.float32),
        jax.ShapeDtypeStruct((6,), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == sha256


# -- the seeded tree ---------------------------------------------------------------


def test_the_seeded_bias_balances_the_experts_and_moves_the_choice(head, tree):
    """PR 43's rule: the bias changes the chosen set on a good share of the
    plausible windows' positions, so a router that ignored it fails."""
    assert len(head._made["bias_moved"]) == 2
    assert min(head._made["bias_moved"]) > 0.3
    assert all(float(jnp.abs(l["rb"]).max()) > 0 for l in tree["layers"] if "rb" in l)
    cfg = small_config()
    x, lens = windows(24, (16, 9, 4))
    unbiased = copy.copy(tree)
    unbiased["layers"] = [dict(l, rb=jnp.zeros_like(l["rb"])) if "rb" in l else l
                          for l in tree["layers"]]
    assert np.abs(program_scores(cfg, tree, x, lens)
                  - program_scores(cfg, unbiased, x, lens)).max() > 1e-4


def test_the_programs_pinned_tree_has_the_references_shape(tree):
    """The harness puts the reference's seeded tree in the place of the
    program's pinned one: same keys, shapes and dtypes, so the step that
    booted serves it without a new compile."""
    pinned = jax.eval_shape(
        lambda: xb.init_backbone(jax.random.key(11), small_config()))
    want = jax.tree.map(lambda a: (a.shape, a.dtype), pinned)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert got == want
    full = jax.eval_shape(
        lambda: xb.init_backbone(jax.random.key(11), xb.XingConfig()))
    leaves = jax.tree.leaves(full)
    assert sum(math.prod(a.shape) for a in leaves) == 3_108_203_279
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves) == 6_223_387_708
    assert xb.layer_kinds(xb.XingConfig()) == {"attention": 5, "dense": 1, "moe": 4}
    row = session_heads.HEADS["xing"]
    assert row.experts == (64, 64) and row.config.streams == 4
    assert row.layers == {"conv": 0, "attention": 5, "window": 0, "ssm": 0, "linear": 0, "memory": 0, "cross": 0, "mtp": 0,
                          "dense": 1, "moe": 4}


# -- the gauges and the served path -------------------------------------------------


@pytest.fixture
def small_xing(monkeypatch):
    """``SESSION_HEAD=xing`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "xing", dataclasses.replace(
        session_heads.HEADS["xing"],
        scores=lambda sp, win, lp: xb.backbone_scores(sp, win, lp, cfg),
        init=lambda: xb.init_backbone(jax.random.key(11), cfg),
        config=cfg, experts=(EXPERTS, EXPERTS),
        layers=session_heads._NO_LAYERS | xb.layer_kinds(cfg)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_xing, environment):
    """The new cell's own files, the source's sizes cut to the small one:
    one server, the head through ``serve/index_program.build``, index-mode
    ``ScoreBatch`` over a real socket, every reply against
    ``chipbench/reference.py`` and the control told apart; the boot gauges
    of what the head holds and is made of, the residual streams among
    them, on ``/metrics`` and ``/debug/sessionz``; the cores the step said
    it runs, the residual path's line among them."""
    spec = copy.deepcopy(validate.load_cell(CELL))
    small = small_source()
    spec["config"]["head"] = dict(spec["config"]["head"], **small.pop("head"))
    spec["config"].update(small)
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    # a loaded CPU compiling this step (twenty unrolled rounds a sublayer) is
    # no stalled device: keep the supervisor's watchdog out of a rehearsal
    spec["config"]["env"]["DEVICE_STEP_DEADLINE_S"] = "600"
    # each core is announced once a process: let this step's trace say it anew
    dp.announce_core.cache_clear()
    run = harness.Run(spec, seed=5_200_000_011, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "xing"
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
    resident = sum(int(a.nbytes) for a in jax.tree.leaves(run.head_params))
    assert snap["head_resident_bytes"] == resident > 0
    assert (snap["head_experts_held"], snap["head_experts_routed"]) == (EXPERTS, EXPERTS)
    assert snap["head_layers"] == LAYERS
    assert snap["head_residual_streams"] == 4
    assert snap["head_cores"]["residual path"] == (
        "xla (not a TPU; 4 streams, 20 Sinkhorn rounds) (backend=cpu)")
    assert snap["head_cores"]["expert core"] == "xla-ragged-dot (backend=cpu)"
    assert snap["head_cores"]["combine"] == "xla-gather (backend=cpu)"
    assert snap["head_cores"]["attention core"] == "xla-einsum (backend=cpu)"
    text = text.replace(".0\n", "\n")
    for name, value in (("resident_bytes", resident), ("experts_held", EXPERTS),
                        ("experts_routed", EXPERTS), ("residual_streams", 4)):
        assert f"risk_session_head_{name} {value}" in text
    for kind, value in LAYERS.items():
        assert f'risk_session_head_layers{{kind="{kind}"}} {value}' in text


@pytest.mark.parametrize("name", ["pattern", "transformer", "pangu"])
def test_every_other_head_carries_one_residual_stream(name, monkeypatch):
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve.session_state import SessionStateManager

    row = session_heads.HEADS[name]
    monkeypatch.setitem(session_heads.HEADS, name,
                        dataclasses.replace(row, init=lambda: None))
    metrics = ServiceMetrics("risk")
    manager = SessionStateManager(8, head=name, metrics=metrics)
    assert manager.snapshot()["head_residual_streams"] == 1
    assert "risk_session_head_residual_streams 1" in (
        metrics.registry.render_text().replace(".0\n", "\n"))


def test_unknown_head_lists_the_new_name():
    with pytest.raises(ValueError) as err:
        session_heads.session_head("kimi")
    assert "'xing'" in str(err.value) and "'ling'" in str(err.value)
    assert all(set(row.layers) == set(session_heads.LAYER_KINDS)
               for row in session_heads.HEADS.values())


def test_chip_smoke_phase_runs_the_head_against_its_reference():
    """``chip_smoke.phase_backbone(head_name="xing")`` at the small size on
    the CPU: the head against its reference, and the cores it said it runs,
    the residual path's line among them."""
    import chip_smoke

    report = chip_smoke.phase_backbone(head_name="xing", cfg=small_config(),
                                       config=small_source(), rows=8)
    assert report["max_err"] < 1e-4 and report["rows"] == 8
    assert report["head"] == "xing"
    assert report["residual_path"] == (
        "residual path: xla (not a TPU; 4 streams, 20 Sinkhorn rounds) "
        "(backend=cpu)")
    assert report["expert_core"] == "expert core: xla-ragged-dot (backend=cpu)"
    assert report["way_back"] == "combine: xla-gather (backend=cpu)"
    assert report["attention_core"] == "attention core: xla-einsum (backend=cpu)"
    assert report["ssm_core"] is None and report["linear_core"] is None
    assert report["resident_bytes"] > 0
    assert set(chip_smoke.BACKBONES["xing"][3]) == {
        "residual_path", "expert_core", "way_back", "attention_core"}
