"""The ``ling`` session head (models/ling_backbone.py) against its plain
reference (chipbench/heads/ling_3_flash.py) at a small size on the CPU,
part by part, share by share, and through the served session path.

The small size keeps every mechanism: the source's layer 1 (KDA, dense) and
the whole period 2-7 (KDA, KDA, KDA, MLA, KDA, KDA; expert layers); KDA of 4
heads of 32 keys and values with 4 taps, a decay a channel bounded at -5, a
``beta`` a head and a gated head norm; latent attention of 4 heads of 32 +
16 against 32 from a key-value latent of 32, interleaved rotary pairs, a
head-wise gate, no query latent; a SwiGLU of 256; a shared expert beside 32
experts of width 64 in 4 groups of 8 of which 2 are kept, 4 a token, chosen
with an expert bias, 8 of them held (experts 8-15); hidden 128; 16-event
windows of mixed lengths, seeded weights. The program computes the delta
rule in its one-chunk form, the reference runs the recurrence.
"""

from __future__ import annotations

import copy
import dataclasses
import hashlib
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
from igaming_platform_tpu.models import keye_backbone as kb  # noqa: E402
from igaming_platform_tpu.models import lfm2_backbone as lfm  # noqa: E402
from igaming_platform_tpu.models import ling_backbone as lb  # noqa: E402
from igaming_platform_tpu.models import pangu_backbone as pb  # noqa: E402
from igaming_platform_tpu.models import session_heads  # noqa: E402

CONFIG = "risk-seqhead-ling-3.0-flash"
CELL = "ling-kda-insession"
PUBLISHED = validate.load_source(CONFIG)["config"]
EXPERTS, HELD, FIRST = 32, 8, 8
LAYERS = {"conv": 0, "attention": 1, "window": 0, "ssm": 0, "linear": 6, "memory": 0, "cross": 0, "mtp": 0, "dense": 1, "moe": 6}


def small_source(held: int = HELD, first: int = FIRST, **over) -> dict:
    """The small size as a configuration file would state it: the source's
    own keys, its switches and lists as published; what the chip holds
    under the source's keys, the published counts, the held layers' source
    indices and the share's first expert under ``head``."""
    source = dict(PUBLISHED)
    source.update({
        "hidden_size": 128, "num_hidden_layers": 7, "first_k_dense_replace": 1,
        "num_experts": held, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "qk_head_dim": 48, "rotary_dim": 16,
        "v_head_dim": 32, "intermediate_size": 256, "moe_intermediate_size": 64,
        "moe_shared_expert_intermediate_size": 64, "num_experts_per_tok": 4,
        "n_group": 4, "topk_group": 2,
        "head": {"published": {"num_hidden_layers": 42,
                               "first_k_dense_replace": 2,
                               "num_experts": EXPERTS},
                 "layers_held": [1, 2, 3, 4, 5, 6, 7], "first_expert": first}})
    source.update(over)
    return source


def program_config(source: dict, **over) -> lb.LingConfig:
    """The program's configuration of a source's keys."""
    head = source["head"]
    published = head["published"]
    kw = dict(
        hidden=source["hidden_size"], held_layers=tuple(head["layers_held"]),
        source_layers=published["num_hidden_layers"],
        source_dense_layers=published["first_k_dense_replace"],
        layer_group_size=source["layer_group_size"],
        heads=source["num_attention_heads"], head_dim=source["head_dim"],
        conv_taps=source["short_conv_kernel_size"],
        gate_lower_bound=float(source["kda_lower_bound"]),
        kv_rank=source["kv_lora_rank"], nope_dim=source["qk_nope_head_dim"],
        rope_dim=source["qk_rope_head_dim"], v_dim=source["v_head_dim"],
        dense_width=source["intermediate_size"],
        experts=published["num_experts"], held_experts=source["num_experts"],
        first_expert=head.get("first_expert", 0),
        top_k=source["num_experts_per_tok"], groups=source["n_group"],
        kept_groups=source["topk_group"],
        expert_width=source["moe_intermediate_size"],
        shared_width=source["moe_shared_expert_intermediate_size"],
        routed_scale=float(source["routed_scaling_factor"]),
        rope_theta=float(source["rope_theta"]), eps=source["rms_norm_eps"],
        expert_limits=tuple(source["expert_swiglu_limit_list"]),
        shared_limits=tuple(source["share_expert_swiglu_limit_list"]))
    kw.update(over)
    return lb.LingConfig(**kw)


def small_config(**over) -> lb.LingConfig:
    return program_config(small_source(), **over)


@pytest.fixture(scope="module")
def head():
    return validate.load_code("heads", "ling_3_flash")


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
        hid = lb.backbone_hidden(p, w, l, cfg)
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
    # float32: the order of float32 sums alone, and the one-chunk form's
    # sums against the recurrence's products of decays
    atol = 5e-5 if operands == "float32" else ROUNDING
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
    a window's length hold, the convolution, the delta rule and the mask
    are causal, and padding is not routed."""
    cfg = small_config()
    x, lens = windows(8, (lengths,))
    junk = x.copy()
    junk[:, lengths:] = np.random.default_rng(1).normal(0, 3, junk[:, lengths:].shape)
    np.testing.assert_array_equal(program_scores(cfg, tree, x, lens),
                                  program_scores(cfg, tree, junk, lens))


def test_tree_of_the_reference_is_the_programs(head, tree):
    """The harness replaces the program's tree by the reference's: one
    structure, shapes and dtypes, so the compiled step is reused. And the
    program's sizes are the configuration file's, the chip's share among
    them; 2.77 G parameters, 5.53 GB at rest."""
    cfg = small_config()
    mine = jax.eval_shape(lambda: lb.init_backbone(jax.random.key(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(tree)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    kinds = [("wf" in layer, "wkv_a" in layer, "dense" in layer)
             for layer in tree["layers"]]
    assert kinds == [(True, False, True)] + [(True, False, False)] * 3 + [
        (False, True, False)] + [(True, False, False)] * 2
    for layer in tree["layers"]:
        for name in ("g1", "g2", "tq", "tk", "tv", "a_log", "dt_bias", "gn",
                     "kvn", "rb"):
            assert name not in layer or layer[name].dtype == jnp.float32, name
        for name in ("wq", "wo", "wf", "wb", "wkv_b", "wgate", "wr"):
            assert name not in layer or layer[name].dtype == jnp.bfloat16, name
        if "routed" in layer:
            assert layer["routed"]["wg"].shape == (HELD, 128, 64)
            assert layer["wr"].shape == (128, EXPERTS)
    published = validate.load_data("configs", CONFIG)
    assert program_config(published) == session_heads.HEADS["ling"].config
    c = session_heads.HEADS["ling"].config
    assert c.source_layers == published["head"]["published"]["num_hidden_layers"] == 42
    assert session_heads.HEADS["ling"].experts == (64, 512)
    d = head.dims_of(published)
    assert d.mixers == c.layer_types == ("kda",) * 4 + ("mla",) + ("kda",) * 2
    assert d.dense == (True,) + (False,) * 6
    assert (d.experts, d.held, d.groups, d.kept_groups) == (512, 64, 8, 4)
    full = jax.eval_shape(session_heads.HEADS["ling"].init)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(full))
    assert n == 2_765_703_617
    nbytes = sum(a.dtype.itemsize * int(np.prod(a.shape))
                 for a in jax.tree.leaves(full))
    assert nbytes == 5_532_137_220
    assert f"{n:,}" in published["head"]["parameters"]
    assert f"{nbytes:,}" in published["head"]["parameters"]


def test_the_seeded_decays_spread_over_the_open_interval(tree):
    """``A_log`` and ``dt_bias`` of the seeded trees leave the decays
    spread over (-5, 0), not pinned at an end: over a unit-spread gate
    input both a fast and a slow tenth exist. The program's own tree and
    the reference's, alike."""
    mine = lb.init_backbone(jax.random.key(5), small_config())
    a = np.random.default_rng(0).normal(0, 1, (2000, 4, 32))
    for params in (mine, tree):
        layer = params["layers"][0]
        rate = np.exp(np.asarray(layer["a_log"]))[:, None]
        z = rate * (a + np.asarray(layer["dt_bias"]).reshape(4, 32))
        g = -5.0 / (1.0 + np.exp(-z))
        assert -5.0 < g.min() and g.max() < 0.0
        low, high = np.quantile(g, [0.1, 0.9])
        assert low < -2.0 and high > -0.5, (low, high)


# -- the layer rule and what is refused ---------------------------------------------


def test_the_layer_rule_gives_35_and_7_at_42_layers(head):
    kinds = [lb.mixer_of(l, PUBLISHED["layer_group_size"]) for l in range(42)]
    assert kinds.count(lb.KDA) == 35 and kinds.count(lb.MLA) == 7
    assert [l for l, k in enumerate(kinds) if k == lb.MLA] == [5, 11, 17, 23, 29, 35, 41]
    assert kinds == [head.mixer_of(l, 6) for l in range(42)]
    # the cell's seven: one dense layer and one whole period in its 5 : 1
    cfg = lb.LingConfig()
    assert cfg.layer_types == ("kda", "kda", "kda", "kda", "mla", "kda", "kda")
    assert lb.layer_kinds(cfg) == {"linear": 6, "attention": 1, "dense": 1, "moe": 6}


@pytest.mark.parametrize("which", ["expert_swiglu_limit_list",
                                   "share_expert_swiglu_limit_list"])
def test_a_held_layer_with_a_limit_entry_is_refused(head, which):
    """No key says what the clamp is: program and reference read both lists
    by the SOURCE's layer index and refuse a held expert layer whose entry
    is not 0 (a dense layer has neither expert); layers 36-41 could not be
    held, and a short list is refused too."""
    late = small_source()
    late["head"] = dict(late["head"], layers_held=[1, 36, 37, 38, 39, 40, 41])
    with pytest.raises(ValueError, match="says what the limit clamps"):
        head.dims_of(late)
    with pytest.raises(ValueError, match="says what the limit clamps"):
        program_config(late)
    limits = list(PUBLISHED[which])
    limits[4] = 3
    with pytest.raises(ValueError, match=r"\[4\].*\[3\]"):
        head.dims_of(small_source(**{which: limits}))
    with pytest.raises(ValueError, match=r"\[4\].*\[3\]"):
        program_config(small_source(**{which: limits}))
    limits[4], limits[1] = 0, 3  # the dense layer held has no expert to clamp
    head.dims_of(small_source(**{which: limits}))
    program_config(small_source(**{which: limits}))
    with pytest.raises(ValueError, match="entries"):
        head.dims_of(small_source(**{which: limits[:7]}))
    with pytest.raises(ValueError, match="entries"):
        program_config(small_source(**{which: limits[:7]}))


def test_a_switch_the_reference_is_not_written_for_is_refused(head):
    for key, value in (("kda_safe_gate", False), ("rope_interleave", False),
                       ("q_lora_rank", 1536), ("topk_method", "greedy"),
                       ("gated_attention_proj_granularity_type", "element_wise")):
        with pytest.raises(ValueError, match=key):
            head.dims_of(small_source(**{key: value}))
    with pytest.raises(ValueError, match="layers_held"):
        head.dims_of(small_source(num_hidden_layers=6))


# -- the delta rule: one chunk against the recurrence --------------------------------


def _core_inputs(t: int, ends: str, seed: int = 0, rows: int = 3, heads: int = 2,
                 dk: int = 16):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = unit(rng.normal(size=(rows, t, heads, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(rows, t, heads, dk)))
    v = rng.normal(size=(rows, t, heads, dk))
    lo, hi = {"fast": (-4.999, -4.5), "slow": (-0.1, -1e-4),
              "mixed": (-4.999, -1e-4)}[ends]
    g = rng.uniform(lo, hi, size=(rows, t, heads, dk))
    if ends == "mixed":  # channels at both ends side by side
        g[..., ::2] = rng.uniform(-4.999, -4.9, size=g[..., ::2].shape)
        g[..., 1::2] = rng.uniform(-1e-3, -1e-5, size=g[..., 1::2].shape)
    beta = rng.uniform(0, 1, size=(rows, t, heads))
    return tuple(np.asarray(a, np.float32) for a in (q, k, v, g, beta))


def _recurrence_loop(q, k, v, g, beta):
    """ISSUE 49's step 3 as a float64 loop over the positions."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    rows, t, heads, dk = q.shape
    state = np.zeros((rows, heads, dk, v.shape[-1]))
    out = np.zeros(v.shape)
    for i in range(t):
        state = np.exp(g[:, i])[..., None] * state
        seen = np.einsum("rhkv,rhk->rhv", state, k[:, i])
        state = state + (beta[:, i][..., None, None] * k[:, i][..., None]
                         * (v[:, i] - seen)[..., None, :])
        out[:, i] = np.einsum("rhkv,rhk->rhv", state, q[:, i])
    return out


@pytest.mark.parametrize("ends", ["fast", "slow", "mixed"])
@pytest.mark.parametrize("t", [1, 4, 16, 17])
@pytest.mark.parametrize("solve", lb.SOLVES)
def test_the_one_chunk_form_equals_the_explicit_recurrence(solve, t, ends):
    """The WY / UT transform over one chunk is the recurrence from ``S_-1 =
    0``, by forward substitution and by squarings, with decays at both ends
    of (-5, 0): at the fast end ``exp(-G)`` reaches e^85 at the 17th
    position and the factored product still holds."""
    inputs = _core_inputs(t, ends, seed=t)
    want = _recurrence_loop(*inputs)
    got = np.asarray(jax.jit(lambda *a: lb.kda_one_chunk(
        *a, lower_bound=-5.0, solve=solve))(*inputs))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
    assert np.abs(want).max() > 1e-3


def test_the_reference_runs_the_recurrence_the_loop_runs(head):
    inputs = _core_inputs(16, "mixed", seed=5)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(head._delta_rule(*(jnp.asarray(a) for a in inputs)))
    np.testing.assert_allclose(got, _recurrence_loop(*inputs), atol=2e-6, rtol=1e-5)


def test_the_delta_rule_reads_before_it_writes():
    """What sets it apart from a decayed sum (``falconh1``'s core): with
    ``beta`` 1 and no decay, writing the same unit key twice leaves the
    second value alone in the state, where a sum would hold both."""
    k = np.zeros((1, 2, 1, 4), np.float32)
    k[..., 0] = 1.0
    v = np.array([[[[1.0, 2, 3, 4]], [[5.0, 6, 7, 8]]]], np.float32)
    g = np.full((1, 2, 1, 4), -1e-9, np.float32)
    beta = np.ones((1, 2, 1), np.float32)
    out = np.asarray(lb.kda_one_chunk(k, k, v, g, beta, lower_bound=-5.0))
    np.testing.assert_allclose(out[0, 1, 0], v[0, 1, 0], atol=1e-5)


def test_the_core_rounds_no_operand(tree):
    """``operand_dtype`` reaches the projections and not the core: the same
    bits whatever the operands' dtype, since the core takes none."""
    inputs = _core_inputs(16, "mixed", seed=2)
    core = lambda: np.asarray(jax.jit(lambda *a: lb.kda_one_chunk(
        *a, lower_bound=-5.0))(*inputs))
    np.testing.assert_array_equal(core(), core())
    layer, u = tree["layers"][0], stream(seed=4).reshape(-1, 128)

    def mixer(operands):
        cfg = small_config(operand_dtype=jnp.dtype(operands))
        return np.asarray(jax.jit(lambda x: lb.kda_mixer(x, layer, cfg, 16))(u))

    assert np.abs(mixer("float32") - mixer("bfloat16")).max() > 1e-6


@pytest.mark.parametrize("t", [18, 32])
def test_a_window_past_17_positions_is_refused(tree, t):
    """``|G| <= 17 x 5 = 85 < 88``: one position more and ``exp(-G)`` could
    leave float32, so the one-chunk form refuses instead of overflowing
    silently."""
    assert lb.window_limit(-5.0) == 17
    with pytest.raises(ValueError, match="longer than the one chunk"):
        lb.kda_one_chunk(*_core_inputs(t, "slow"), lower_bound=-5.0)
    x = np.zeros((2, t, 12), np.float32)
    with pytest.raises(ValueError, match="17 positions"):
        jax.eval_shape(lambda w: lb.backbone_scores(
            tree, w, jnp.full((2,), t, jnp.int32), small_config()), x)
    with pytest.raises(ValueError, match="solve"):
        lb.kda_one_chunk(*_core_inputs(4, "slow"), lower_bound=-5.0, solve="cholesky")


# -- the router: group-limited, the bias chooses ---------------------------------------


def _route_by_loop(s, bias, groups, kept, top_k, scale):
    """Group-limited routing, position by position."""
    chosen, weights = [], []
    for row in s:
        biased = row + bias
        per = len(row) // groups
        score = [np.sort(biased[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(groups)]
        keep = np.argsort(-np.asarray(score), kind="stable")[:kept]
        inside = [e for e in range(len(row)) if e // per in keep]
        best = sorted(inside, key=lambda e: (-biased[e], e))[:top_k]
        w = row[best]
        chosen.append(best)
        weights.append(w / (w.sum() + 1e-20) * scale)
    return np.asarray(chosen), np.asarray(weights)


@pytest.mark.parametrize("biased", [False, True], ids=["no-bias", "bias"])
def test_group_limited_routing_equals_a_loop(head, biased):
    cfg = small_config(operand_dtype=jnp.float32)
    layer = dict(lb.init_backbone(jax.random.key(1), cfg)["layers"][1])
    rng = np.random.default_rng(3)
    layer["rb"] = jnp.asarray(rng.normal(0, 0.3, EXPERTS) if biased
                              else np.zeros(EXPERTS), jnp.float32)
    x = jax.random.normal(jax.random.key(2), (60, 128), jnp.float32)
    top_e, top_w = jax.jit(lambda x: dp.route(
        x, layer, cfg, cfg.groups, cfg.kept_groups))(x)
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                          @ np.asarray(layer["wr"].astype(jnp.float32), np.float64))))
    want_e, want_w = _route_by_loop(s, np.asarray(layer["rb"], np.float64),
                                    cfg.groups, cfg.kept_groups, cfg.top_k, 2.5)
    np.testing.assert_array_equal(np.asarray(top_e), want_e)
    np.testing.assert_allclose(np.asarray(top_w), want_w, rtol=2e-5)
    # the bias chooses and does not weigh: weights are the bare scores'
    np.testing.assert_allclose(np.asarray(top_w).sum(1), 2.5, rtol=1e-5)
    # every chosen expert lies in one of two groups of eight
    assert (np.unique(np.asarray(top_e) // 8, axis=None).size <= 4
            and all(len(set(row // 8)) <= 2 for row in np.asarray(top_e)))
    # and the reference's own choice is the loop's too
    ref_e, ref_w = head._choose(jnp.asarray(s, jnp.float32), layer["rb"],
                                head.dims_of(small_source()))
    np.testing.assert_array_equal(np.asarray(ref_e), want_e)
    np.testing.assert_allclose(np.asarray(ref_w), want_w, rtol=2e-5)
    if biased:
        bare, _ = _route_by_loop(s, np.zeros(EXPERTS), cfg.groups,
                                 cfg.kept_groups, cfg.top_k, 2.5)
        assert (np.sort(bare, 1) != np.sort(want_e, 1)).any()


def test_an_expert_outside_the_kept_groups_is_never_chosen():
    """However large its score: one expert of a group whose other scores
    are low has the single largest (biased) score of its position and is
    not chosen, because its group's top-2 sum is not among the kept."""
    scores = np.full((5, EXPERTS), 0.5, np.float32)
    scores[:, :8] = 0.05
    scores[:, 0] = 0.99                     # the largest score of all
    scores[:, 8:16] += 0.1                  # two groups clearly ahead
    scores[:, 16:24] += 0.2
    # the scores go in experts-first, [experts, P], and come back so
    kept = np.asarray(dp.within_kept_groups(jnp.asarray(scores.T), 4, 2)).T
    assert np.all(np.isneginf(kept[:, :8])) and np.all(np.isneginf(kept[:, 24:]))
    np.testing.assert_array_equal(kept[:, 8:24], scores[:, 8:24])
    top = np.asarray(jax.lax.top_k(jnp.asarray(kept), 4)[1])
    assert not (top == 0).any() and np.all((top >= 8) & (top < 24))


# -- the widened shared functions are the parents' ---------------------------------


def _body(fn, *args) -> str:
    return "\n".join(line for line in fn.lower(*args).as_text().splitlines()
                     if "func.func" not in line and "module @" not in line)


def _route_of_the_parent(x, layer, cfg, groups=None, kept_groups=None):
    """``decoder_parts.route`` and ``within_kept_groups`` as PR 50 had them:
    three ``lax.top_k`` (each a sort) and ``take_along_axis`` (a gather),
    over scores laid positions-first."""
    s = jax.nn.sigmoid(dp.mm(x, layer["wr"], cfg))
    chosen_by = s + layer["rb"] if "rb" in layer else s
    if groups is not None:
        p, experts = s.shape
        by_group = chosen_by.reshape(p, groups, experts // groups)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, kept_groups)
        keep = jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)
        chosen_by = jnp.where(keep[:, :, None], by_group,
                              -jnp.inf).reshape(p, experts)
    if chosen_by is s:
        top_s, top_e = jax.lax.top_k(s, cfg.top_k)
    else:
        _, top_e = jax.lax.top_k(chosen_by, cfg.top_k)
        top_s = jnp.take_along_axis(s, top_e, axis=-1)
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + cfg.renorm_eps)
    return top_e, w * cfg.routed_scale


def _exact_router(kind: str, hidden: int, experts: int, positions: int, seed: int):
    """``(x, wr, logits)`` whose products and sums are exact in float32 in
    any order (small integers against multiples of 1/8, exact in bfloat16
    too), so the router's product is the same number however it is laid
    out and what is compared is the choice: ``rounded`` logits are
    multiples of 1/8 with many equal pairs; ``saturated`` ones are
    multiples of 4, so the sigmoid is 1.0 in many experts of a position;
    ``few-values`` has three distinct columns in ``wr``, so every position
    holds three distinct scores at most, fewer than any ``top_k`` here."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-2, 3, (positions, hidden)).astype(np.float32)
    wr = rng.integers(-2, 3, (hidden, experts)).astype(np.float32)
    if kind == "few-values":
        wr = wr[:, rng.integers(0, 3, experts)]
    wr *= 4.0 if kind == "saturated" else 0.125
    return x, wr, x @ wr


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["rounded", "saturated", "zero-bias",
                                  "few-values"])
@pytest.mark.parametrize("caller,experts,groups,kept_groups,top_k,biased", [
    ("ling", 512, 8, 4, 8, True), ("lfm2", 64, None, None, 4, True),
    ("pangu", 256, None, None, 8, False)])
def test_route_by_rounds_is_the_sorts_and_the_gather_bit_for_bit(
        caller, experts, groups, kept_groups, top_k, biased, kind, operands):
    """``decoder_parts.route`` (PR 51: rounds of max-and-mask over scores
    laid experts-first, the chosen scores read back by compare-and-sum)
    against the formulation it replaced, at the three callers' routers
    (512 experts in 8 groups of which 4 are kept, 8 chosen, a bias; 64, 4
    chosen, a bias; 256, 8 chosen, bare): the same experts in the same
    order and the same weights, bit for bit, where scores are equal in
    pairs, where they saturate at 1.0, where the bias is zero (so equal
    scores stay equal) and where a position holds fewer distinct scores
    than ``top_k`` asks for: every tie goes to the lower index in both."""
    hidden, positions = 64, 96
    cfg = types.SimpleNamespace(operand_dtype=jnp.dtype(operands), top_k=top_k,
                                renorm_eps=1e-20, routed_scale=2.5)
    x, wr, logits = _exact_router(kind, hidden, experts, positions, seed=experts)
    layer = {"wr": jnp.asarray(wr, jnp.bfloat16)}
    if biased:
        rng = np.random.default_rng(1)
        layer["rb"] = jnp.asarray(
            np.zeros(experts) if kind == "zero-bias"
            else np.round(rng.normal(0, 0.3, experts) * 8) / 8, jnp.float32)
    # the inputs are what their names say
    distinct = [len(np.unique(row)) for row in logits]
    if kind == "few-values":
        assert max(distinct) <= 3 < top_k
    elif kind == "saturated":
        assert np.mean(logits >= 20) > 0.2       # sigmoid(20) rounds to 1.0
    else:
        assert min(distinct) < experts           # equal scores in a position
    now = jax.jit(lambda x: dp.route(x, layer, cfg, groups, kept_groups))(x)
    then = jax.jit(lambda x: _route_of_the_parent(
        x, layer, cfg, groups, kept_groups))(x)
    for a, b in zip(now, then, strict=True):
        assert a.shape == b.shape == (positions, top_k) and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if groups is not None:
        per = experts // groups
        assert all(len(set(row // per)) <= kept_groups for row in np.asarray(now[0]))


def _lowers_no_selection_by_sort(fn, *args) -> bool:
    """Neither a sort (``lax.top_k`` lowers to ``chlo.top_k`` or a sort)
    nor a gather is in the lowered text of ``fn``."""
    text = fn.lower(*args).as_text()
    return not any(op in text for op in ("top_k", "sort", "gather"))


def _rotate_of_the_parent(x, cos, sin):
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def _latent_attention_of_the_parent(a, layer, cos, sin, cfg):
    """``pangu_backbone.latent_attention`` with its einsum core, as the
    parent commit (d222b9e) wrote both out."""
    b, t, _ = a.shape
    dt = cfg.operand_dtype
    heads, nope, rope, dv = cfg.heads, cfg.nope_dim, cfg.rope_dim, cfg.v_dim
    a = a.reshape(b * t, -1)
    cq = dp.rms_norm(dp.mm(a, layer["wq_a"], cfg), layer["qn"], cfg.eps)
    q = dp.mm(cq, layer["wq_b"], cfg)
    kv = dp.mm(a, layer["wkv_a"], cfg)
    ckv = dp.rms_norm(kv[:, :cfg.kv_rank], layer["kvn"], cfg.eps)
    k_rope = _rotate_of_the_parent(kv[:, cfg.kv_rank:].reshape(b, t, 1, -1), cos, sin)
    k_rope = k_rope.astype(dt).reshape(b * t, -1)
    kvb = dp.mm(ckv, layer["wkv_b"], cfg).astype(dt)
    cos2, sin2 = cos.reshape(b * t, -1), sin.reshape(b * t, -1)
    q = q.reshape(b, t, heads, nope + rope)
    q_rope = _rotate_of_the_parent(q[..., nope:], cos2.reshape(b, t, -1),
                                   sin2.reshape(b, t, -1))
    kvb = kvb.reshape(b, t, heads, nope + dv)
    sc = (jnp.einsum("bthd,bshd->bhts", q[..., :nope].astype(dt),
                     kvb[..., :nope], preferred_element_type=jnp.float32)
          + jnp.einsum("bthd,bsd->bhts", q_rope.astype(dt),
                       k_rope.reshape(b, t, rope),
                       preferred_element_type=jnp.float32))
    sc = sc * ((nope + rope) ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p.astype(dt), kvb[..., nope:],
                   preferred_element_type=jnp.float32)
    return dp.mm(o.reshape(b * t, heads * dv), layer["wo"], cfg).reshape(b, t, -1)


def _small_pangu(dt=jnp.bfloat16):
    return pb.PanguConfig(hidden=64, layers=2, dense_layers=1, heads=4,
                          q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                          v_dim=16, dense_width=96, experts=16, held_experts=4,
                          top_k=4, expert_width=32, operand_dtype=dt)


def _small_lfm2(dt=jnp.bfloat16):
    return lfm.Lfm2Config(hidden=128, layer_types=("conv", "full_attention", "conv"),
                          dense_layers=1, heads=4, kv_heads=2, head_dim=32,
                          dense_width=256, experts=8, top_k=2, expert_width=64,
                          operand_dtype=dt)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("shared", ["route", "route-with-bias", "rotate",
                                    "latent_attention"])
def test_a_widened_function_without_its_new_argument_is_the_parents_bit_for_bit(
        shared, operands):
    """``route`` without groups (``pangu``: no bias; ``lfm2``: a bias),
    ``rotate`` by halves (``keye``, ``lfm2``, ``falconh1``, ``pangu``) and
    ``latent_attention`` with a query latent, rotate-half pairs and no gate
    (``pangu``; moved to models/decoder_parts.py): the same values and,
    names aside, the same StableHLO as the parent commit's, written out
    above. Since PR 51 ``route`` keeps the values and not the text: it
    lowers to no sort and no gather, where the parent's lowers to both."""
    dt = jnp.dtype(operands)
    if shared.startswith("route"):
        if shared == "route":
            cfg = _small_pangu(dt)
            layer = pb.init_backbone(jax.random.key(1), cfg)["layers"][1]
            assert "rb" not in layer
        else:
            cfg = _small_lfm2(dt)
            layer = dict(lfm.init_backbone(jax.random.key(1), cfg)["layers"][1])
            layer["rb"] = jax.random.normal(jax.random.key(3), (cfg.experts,)) * 0.2
        # since PR 51 ``route`` is the parent's in its values and no longer in
        # its text (rounds where the sorts were, the product experts-first),
        # so the router here has sums that are exact in any order
        x, wr, _ = _exact_router("rounded", cfg.hidden, cfg.experts, 48, seed=2)
        layer = dict(layer, wr=jnp.asarray(wr, jnp.bfloat16))
        args = (jnp.asarray(x),)
        now = jax.jit(lambda x: dp.route(x, layer, cfg))
        then = jax.jit(lambda x: _route_of_the_parent(x, layer, cfg))
    elif shared == "rotate":
        cos, sin = dp.rope_angles(3, 16, 16, 1e6)
        args = (jax.random.normal(jax.random.key(2), (3, 16, 4, 24)).astype(dt)
                .astype(jnp.float32), cos, sin)
        now = jax.jit(dp.rotate)
        then = jax.jit(_rotate_of_the_parent)
    else:
        cfg = _small_pangu(dt)
        layer = pb.init_backbone(jax.random.key(1), cfg)["layers"][0]
        cos, sin = dp.rope_angles(3, 16, cfg.rope_dim, cfg.rope_theta)
        args = (jax.random.normal(jax.random.key(2), (3, 16, 64), jnp.float32),
                cos, sin)
        now = jax.jit(lambda a, c, s: dp.latent_attention(a, layer, c, s, cfg))
        then = jax.jit(lambda a, c, s: _latent_attention_of_the_parent(
            a, layer, c, s, cfg))
    for a, b in zip(jax.tree.leaves(now(*args)), jax.tree.leaves(then(*args)),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if shared.startswith("route"):
        assert _lowers_no_selection_by_sort(now, *args)
        assert not _lowers_no_selection_by_sort(then, *args)
    else:
        assert _body(now, *args) == _body(then, *args)


@pytest.mark.parametrize("name,sha256", [
    ("keye", "97b106f2039253a0"), ("pangu", "6f25d45683d24dfe"),
    ("lfm2", "ce10c44bd93b8d8d")])
def test_the_backbones_that_share_the_widened_functions_lower_as_before(name, sha256):
    """``pangu``, ``lfm2`` and ``keye`` at the small sizes and under the
    digests tests/test_falconh1_backbone.py pins (computed at PR 45's and PR
    47's parents, held across PR 48's move; ``pangu``'s and ``lfm2``'s
    replaced at PR 51, whose ``route`` sorts nothing): ``rotate`` by halves
    and ``latent_attention`` without its new arguments leave their lowered
    ``backbone_scores`` byte for byte."""
    if name == "keye":
        cfg = kb.BackboneConfig(hidden=128, layers=2, heads=4, kv_heads=2,
                                head_dim=32, experts=8, top_k=2, expert_width=64,
                                idx_heads=2, idx_dim=16, idx_topk=8,
                                mrope_section=(4, 6, 6))
        make, scores = kb.init_backbone, kb.backbone_scores
    elif name == "pangu":
        cfg, make, scores = _small_pangu(), pb.init_backbone, pb.backbone_scores
    else:
        cfg, make, scores = _small_lfm2(), lfm.init_backbone, lfm.backbone_scores
    params = jax.eval_shape(lambda: make(jax.random.key(0), cfg))
    text = jax.jit(lambda p, w, l: scores(p, w, l, cfg)).lower(
        params, jax.ShapeDtypeStruct((6, 16, 12), jnp.float32),
        jax.ShapeDtypeStruct((6,), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == sha256


# -- each mixer and the feed-forward alone -------------------------------------------


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("part", ["kda", "mla", "dense", "moe"])
def test_each_mixer_and_the_feed_forward_alone(head, tree, operands, part):
    """One sublayer of the program against the reference's own, on a
    stream with spread: the KDA mixer (one chunk against the recurrence),
    latent attention (pairs turned where they lie against the re-ordered
    rotate-half), the dense SwiGLU, and the expert layer of a share with
    windows of mixed lengths (padding takes the shared expert alone)."""
    dt = jnp.dtype(operands)
    cfg = small_config(operand_dtype=dt)
    d = head.dims_of(small_source())
    x = stream(seed=3)
    b, t, hidden = x.shape
    lens = jnp.asarray([16, 3, 9, 1, 16, 5], jnp.int32)
    live = (jnp.arange(t)[None, :] < lens[:, None]).reshape(-1)
    layer = tree["layers"][{"kda": 1, "mla": 4, "dense": 0, "moe": 2}[part]]
    flat = x.reshape(b * t, hidden)

    def sublayer(h):
        if part == "kda":
            return h + lb.kda_mixer(dp.rms_norm(h, layer["g1"], cfg.eps), layer, cfg, t)
        if part == "mla":
            cos, sin = dp.rope_angles(b, t, cfg.rope_dim, cfg.rope_theta)
            a = dp.rms_norm(h, layer["g1"], cfg.eps).reshape(b, t, -1)
            return h + dp.latent_attention(a, layer, cos, sin, cfg,
                                           interleave=True).reshape(b * t, -1)
        u = dp.rms_norm(h, layer["g2"], cfg.eps)
        if part == "dense":
            return h + dp.swiglu(u, layer["dense"], cfg)
        top_e, top_w = dp.route(u, layer, cfg, cfg.groups, cfg.kept_groups)
        return h + (dp.swiglu(u, layer["shared"], cfg) + el.grouped_experts(
            u, top_e, top_w, layer["routed"], cfg, cfg.first_expert, live))

    got = np.asarray(jax.jit(sublayer)(flat)).reshape(x.shape)
    with jax.default_matmul_precision("highest"):
        want = np.asarray({"kda": lambda: head._kda(layer, x, d, dt),
                           "mla": lambda: head._attend(layer, x, d, dt),
                           "dense": lambda: head._dense(layer, x, d, dt),
                           "moe": lambda: head._moe(layer, x, lens, d, dt)}[part]())
    atol = 2e-5 if operands == "float32" else 8 * ROUNDING
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)
    assert np.abs(got - np.asarray(x)).max() > 1e-2  # the sublayer adds something


# -- the delta core as the window kernel (ops/pallas/delta_window.py) ------------------


def wide_source() -> dict:
    """The small size with KDA heads as wide as the kernel takes them: 2
    heads of 128 keys and values."""
    return small_source(num_attention_heads=2, num_key_value_heads=2, head_dim=128)


@pytest.fixture
def linear_core_by_kernel(monkeypatch, caplog):
    """Runs ``fn`` as a TPU would trace the KDA mixers, the delta kernel
    through the Pallas interpreter, and returns what it computed with what
    the linear-attention core said. Steered here, in the test, and for this
    part alone (the expert layer and latent attention ask ``decoder_parts``
    themselves and stay on the CPU's cores)."""
    import functools

    from igaming_platform_tpu.ops.pallas import delta_window as dw

    def run(fn):
        dp.announce_core.cache_clear()
        caplog.clear()
        with monkeypatch.context() as m, caplog.at_level("INFO", logger=dp.logger.name):
            m.setattr(lb, "kernel_declines",
                      lambda declines=None: (declines and declines(), "tpu"))
            m.setattr(dw, "delta_window", functools.partial(
                dw.delta_window, interpret=True))
            out = fn()
        said = [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("linear-attention core")]
        dp.announce_core.cache_clear()
        return out, said
    return run


KERNEL_SAYS = ("linear-attention core: pallas-windows (2 heads of 128, window "
               "16, prologue=taps, norm=inside) (backend=tpu)")


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_through_the_delta_kernel_equals_the_reference(
        head, operands, linear_core_by_kernel):
    """The whole head with every KDA mixer's core the Pallas kernel (8
    windows: one tile of 128 positions, mixed lengths) against the plain
    reference's recurrence, under the limits the einsum path meets
    (``test_head_equals_the_reference``), and against the einsum path
    itself."""
    dt = jnp.dtype(operands)
    source = wide_source()
    cfg = program_config(source, operand_dtype=dt)
    params = head.make_params(7, source)
    d = head.dims_of(source)
    x, lens = windows(8, (1, 4, 16, 7, 9, 2), seed=7)
    (logit, hidden), said = linear_core_by_kernel(
        lambda: program_logits(cfg, params, x, lens))
    assert said == [KERNEL_SAYS]
    want_logit = head._logits(params, x, lens, d, dt)
    want_hidden = head._logits(params, x, lens, d, dt, hidden=True)
    atol = 5e-5 if operands == "float32" else ROUNDING
    np.testing.assert_allclose(logit, want_logit, atol=atol, rtol=0)
    np.testing.assert_allclose(hidden, want_hidden, atol=atol, rtol=0)
    by_einsum, _ = program_logits(cfg, params, x, lens)
    np.testing.assert_allclose(logit, by_einsum, atol=atol, rtol=0)
    assert np.std(want_logit) > 0.01


def test_mixer_through_the_delta_kernel_equals_the_einsum_path(
        head, linear_core_by_kernel):
    """One KDA sublayer on a stream with spread, float32 operands: the
    projections, the gate and ``Wo`` are the same XLA either way, so what
    differs is the order of float32 sums between them."""
    source = wide_source()
    cfg = program_config(source, operand_dtype=jnp.float32)
    layer = head.make_params(7, source)["layers"][1]
    u = stream(rows=8, seed=9).reshape(-1, 128)
    mixer = lambda: np.asarray(jax.jit(
        lambda x: lb.kda_mixer(x, layer, cfg, 16))(u))
    by_kernel, said = linear_core_by_kernel(mixer)
    by_einsum = mixer()
    assert said == [KERNEL_SAYS]
    assert dp.announced_cores()["linear-attention core"] == (
        "one chunk by einsums (not a TPU) (backend=cpu)")
    scale = np.abs(by_einsum).max()
    assert scale > 1e-3
    np.testing.assert_allclose(by_kernel, by_einsum, atol=2e-6 * max(scale, 1.0),
                               rtol=0)


@pytest.mark.parametrize("backend,over,window,said", [
    ("cpu", {}, 16, "one chunk by einsums (not a TPU) (backend=cpu)"),
    ("tpu", {}, 16, "pallas-windows (32 heads of 128, window 16, prologue=taps, "
                    "norm=inside) (backend=tpu)"),
    ("tpu", {"head_dim": 96}, 16, "one chunk by einsums (head width 96 is not "
                                  "whole 128-lane vregs) (backend=tpu)"),
    ("tpu", {}, 12, "one chunk by einsums (windows of 12 are not whole 8-row "
                    "vregs that divide a tile of 128) (backend=tpu)"),
    ("tpu", {}, 8, "pallas-windows (32 heads of 128, window 8, prologue=taps, "
                   "norm=inside) (backend=tpu)"),
], ids=["off-the-tpu", "published", "head96", "window12", "window8"])
def test_linear_attention_core_is_announced_with_the_reason_it_declines(
        backend, over, window, said, monkeypatch, caplog):
    """The choice is made while tracing, from the backend and the layer's
    shapes alone; the boot's log line and ``/debug/sessionz``'s
    ``head_cores`` carry it, with the kernel's own reason beside ``one
    chunk by einsums``."""
    cfg = lb.LingConfig(**over)
    dp.announce_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with caplog.at_level("INFO", logger=dp.logger.name):
        by_kernel = lb._core_is_the_kernel(256 * window, cfg, window)
    assert by_kernel is said.startswith("pallas-windows")
    assert [r.getMessage() for r in caplog.records] == [
        f"linear-attention core: {said}"]
    assert dp.announced_cores()["linear-attention core"] == said
    dp.announce_core.cache_clear()


def test_a_part_filled_tile_takes_the_einsums(monkeypatch):
    """The 64-row rung is 1,024 positions and the 256-row one 4,096: whole
    tiles. A batch that is not (a test's, 6 windows) declines in the
    kernel's words and runs ``kda_one_chunk``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp.announce_core.cache_clear()
    assert not lb._core_is_the_kernel(6 * 16, lb.LingConfig(), 16)
    assert dp.announced_cores()["linear-attention core"] == (
        "one chunk by einsums (96 positions are not whole tiles of 128) "
        "(backend=tpu)")
    dp.announce_core.cache_clear()


def test_the_attention_core_says_why_it_is_the_einsums(tree, caplog, monkeypatch):
    """The window kernel turns rotate-half pairs: with interleaved pairs the
    core is the einsums on every backend, a TPU too, and the announcement
    says so rather than re-pairing silently."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp.announce_core.cache_clear()
    cfg = small_config()
    q = jax.ShapeDtypeStruct((48, 4 * 48), jnp.float32)
    kvb = jax.ShapeDtypeStruct((48, 4 * 64), jnp.bfloat16)
    with caplog.at_level("INFO", logger=dp.logger.name):
        core = dp.latent_attention_core(q, kvb, cfg, 16, interleave=True)
    assert core.func is dp.latent_core_by_einsums and core.keywords["interleave"]
    assert dp.announced_cores()["attention core"] == (
        "xla-einsum (interleaved rotary pairs: the window kernel turns by "
        "halves) (backend=tpu)")
    dp.announce_core.cache_clear()


def test_interleaved_pairs_turn_where_they_lie():
    """Pair ``i`` is channels ``2i`` and ``2i + 1``; a dot product of two
    vectors so turned depends on the positions' difference alone."""
    cos, sin = dp.rope_angles(1, 16, 8, 1e4)
    x = jax.random.normal(jax.random.key(0), (1, 16, 2, 12))
    y = np.asarray(dp.rotate(x, cos, sin, interleave=True))
    x = np.asarray(x)
    c, s = np.asarray(cos)[0, :, None, :], np.asarray(sin)[0, :, None, :]
    np.testing.assert_allclose(y[0, ..., 0:8:2], x[0, ..., 0:8:2] * c - x[0, ..., 1:8:2] * s, atol=1e-6)
    np.testing.assert_allclose(y[0, ..., 1:8:2], x[0, ..., 1:8:2] * c + x[0, ..., 0:8:2] * s, atol=1e-6)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    assert np.abs(y - np.asarray(dp.rotate(jnp.asarray(x), cos, sin))).max() > 1e-2


# -- the shares add up ------------------------------------------------------------


def _reference_ff(head, layer, flat, d, dt):
    """What the reference's ``_moe`` adds to the stream (shared expert + the
    held experts' part), from its own parts, every position live."""
    sel, w = head._choose(head._scores(layer, flat, dt), layer["rb"], d)
    m = head._swiglu(flat, layer["shared"], dt)
    for i in range(d.held):
        chosen = sel == d.first + i
        weight = jnp.sum(jnp.where(chosen, w, 0.0), -1, keepdims=True)
        y = head._swiglu(flat, {k: v[i] for k, v in layer["routed"].items()}, dt)
        m = m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0)
    return m


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(head):
    """Model-configs guide, section 4: at 32 experts in 4 groups, the
    routed parts that the shares of 8 give (the cell's: eight shares of 64
    of 512), with what every chip computes alike (the shared expert)
    counted once, equal what the UNCUT reference (all 32 held) gives for
    the whole layer."""
    dt = jnp.float32
    uncut = small_source(held=EXPERTS, first=0)
    whole = head.make_params(11, uncut)["layers"][2]
    d_whole = head.dims_of(uncut)
    x = stream(rows=8, seed=5)
    flat = head._rms(x, whole["g2"], d_whole.eps).reshape(-1, 128)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_reference_ff(head, whole, flat, d_whole, dt))
    shared_once = np.asarray(jax.jit(lambda f: dp.swiglu(
        f, whole["shared"], small_config(operand_dtype=dt)))(flat))
    total = shared_once.copy()
    for first in range(0, EXPERTS, HELD):
        cfg = program_config(small_source(first=first), operand_dtype=dt)
        share = {k: v[first:first + HELD] for k, v in whole["routed"].items()}
        top_e, top_w = dp.route(flat, whole, cfg, cfg.groups, cfg.kept_groups)
        part = np.asarray(jax.jit(lambda f, e, w: el.grouped_experts(
            f, e, w, share, cfg, first, jnp.ones((f.shape[0],), bool)))(
                flat, top_e, top_w))
        # a share's own reference gives the same part
        d_share = head.dims_of(small_source(first=first))
        with jax.default_matmul_precision("highest"):
            ref_part = np.asarray(_reference_ff(
                head, dict(whole, routed=share), flat, d_share, dt))
        np.testing.assert_allclose(part, ref_part - shared_once, atol=2e-5, rtol=0)
        assert np.abs(part).max() > 1e-3
        total += part
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=0)
    assert np.abs(want - shared_once).max() > 1e-2


def test_the_seeded_bias_balances_the_held_experts_and_moves_the_choice(head, tree):
    """PR 43's rule under the group-limited choice: over the plausible
    windows every held expert sees about its share, and the bias changes
    the chosen set on most positions, so a router that ignored it fails."""
    assert len(head._made["bias_moved"]) == len(head._made["held_load"]) == 6
    assert min(head._made["bias_moved"]) > 0.3
    for least, most, mean in head._made["held_load"]:
        assert 0.8 * mean <= least <= most <= 1.2 * mean
    assert all(float(jnp.abs(l["rb"]).max()) > 0 for l in tree["layers"] if "rb" in l)
    cfg = small_config()
    x, lens = windows(24, (16, 9, 4))
    unbiased = copy.copy(tree)
    unbiased["layers"] = [dict(l, rb=jnp.zeros_like(l["rb"])) if "rb" in l else l
                          for l in tree["layers"]]
    assert np.abs(program_scores(cfg, tree, x, lens)
                  - program_scores(cfg, unbiased, x, lens)).max() > 1e-4


# -- the gauges and the served path -------------------------------------------------


@pytest.fixture
def small_ling(monkeypatch):
    """``SESSION_HEAD=ling`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "ling", dataclasses.replace(
        session_heads.HEADS["ling"],
        scores=lambda sp, win, lp: lb.backbone_scores(sp, win, lp, cfg),
        init=lambda: lb.init_backbone(jax.random.key(11), cfg),
        experts=(HELD, EXPERTS)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_ling, environment):
    """The new cell's own files, the source's sizes cut to the small one:
    one server, the head through ``serve/index_program.build``, index-mode
    ``ScoreBatch`` over a real socket, every reply against
    ``chipbench/reference.py``; the boot gauges of what the head holds and
    is made of, on ``/metrics`` and ``/debug/sessionz``; the cores the step
    said it runs; the two position counters."""
    spec = copy.deepcopy(validate.load_cell(CELL))
    small = small_source()
    spec["config"]["head"] = dict(spec["config"]["head"], **small.pop("head"))
    spec["config"].update(small)
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    # each core is announced once a process: let this step's trace say it anew
    dp.announce_core.cache_clear()
    run = harness.Run(spec, seed=4_900_000_007, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "ling"
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
    assert (snap["head_experts_held"], snap["head_experts_routed"]) == (HELD, EXPERTS)
    assert snap["head_layers"] == LAYERS
    # which cores the step said it runs when it was traced
    assert snap["head_cores"]["linear-attention core"] == (
        "one chunk by einsums (not a TPU) (backend=cpu)")
    assert snap["head_cores"]["expert core"] == "xla-ragged-dot (backend=cpu)"
    assert snap["head_cores"]["combine"] == "xla-gather (backend=cpu)"
    assert snap["head_cores"]["attention core"].startswith(
        "xla-einsum (interleaved rotary pairs")
    text = text.replace(".0\n", "\n")
    for name, value in (("resident_bytes", resident), ("experts_held", HELD),
                        ("experts_routed", EXPERTS)):
        assert f"risk_session_head_{name} {value}" in text
    for kind, value in LAYERS.items():
        assert f'risk_session_head_layers{{kind="{kind}"}} {value}' in text


def test_replay_verifies_a_ledger_written_under_the_head(small_ling, monkeypatch):
    monkeypatch.setenv("SESSION_HEAD", "ling")
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import ledger as ledger_mod
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine
    from tools.replay import replay_directory

    d = tempfile.mkdtemp(prefix="ling-replay-test-")
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
        assert eng.session.head == "ling"
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
    ("pattern", {}), ("transformer", {"attention": 1, "dense": 1}),
    ("falconh1", {"ssm": 4, "attention": 4, "dense": 4}),
    ("ling", {"linear": 6, "attention": 1, "dense": 1, "moe": 6})])
def test_layer_gauge_takes_the_new_kind(name, layers, monkeypatch):
    """``risk_session_head_layers{kind="linear"}``: six of this head's layers
    are linear attention, one attention; the other heads read 0."""
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve.session_state import SessionStateManager

    assert session_heads.HEADS[name].layers == {
        kind: layers.get(kind, 0) for kind in session_heads.LAYER_KINDS}
    assert "linear" in session_heads.LAYER_KINDS
    if name in ("falconh1", "ling"):  # the gauge, not a tree of gigabytes
        monkeypatch.setitem(session_heads.HEADS, name, dataclasses.replace(
            session_heads.HEADS[name], init=lambda: None))
    metrics = ServiceMetrics("risk")
    mgr = SessionStateManager(8, head=name, metrics=metrics)
    want = {kind: layers.get(kind, 0) for kind in session_heads.LAYER_KINDS}
    assert mgr.snapshot()["head_layers"] == want
    text = metrics.registry.render_text().replace(".0\n", "\n")
    for kind, value in want.items():
        assert f'risk_session_head_layers{{kind="{kind}"}} {value}' in text


def test_unknown_head_lists_the_new_name():
    with pytest.raises(ValueError) as err:
        session_heads.session_head("kimi")
    assert "'ling'" in str(err.value) and "'falconh1'" in str(err.value)
    assert all(set(row.layers) == set(session_heads.LAYER_KINDS)
               for row in session_heads.HEADS.values())
    assert len(session_heads.HEADS) == 12  # PR 52: ``xing``; PR 57: ``mellum``; PR 59: ``phi4flash``; PR 65: ``kexaone``; PR 68: ``longcat``


def test_chip_smoke_phase_runs_the_head_against_its_reference():
    """``chip_smoke.phase_backbone(head_name="ling")`` at the small size on
    the CPU: the head against its reference, and the cores it said it
    runs."""
    import chip_smoke

    report = chip_smoke.phase_backbone(head_name="ling", cfg=small_config(),
                                       config=small_source(), rows=8)
    assert report["max_err"] < 1e-4 and report["rows"] == 8
    assert report["head"] == "ling"
    assert report["linear_core"] == (
        "linear-attention core: one chunk by einsums (not a TPU) (backend=cpu)")
    assert report["expert_core"] == "expert core: xla-ragged-dot (backend=cpu)"
    assert report["way_back"] == "combine: xla-gather (backend=cpu)"
    assert report["attention_core"].startswith(
        "attention core: xla-einsum (interleaved rotary pairs")
    assert report["ssm_core"] is None
    assert report["resident_bytes"] > 0
