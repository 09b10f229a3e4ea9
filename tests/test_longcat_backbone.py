"""The ``longcat`` session head (models/longcat_backbone.py) on the CPU at a
small size: against its plain reference (chipbench/heads/longcat_flash_omni.py,
which runs every part of every layer at EVERY position), the narrowed last
layer against the whole one, the read at ``len`` 1, 2 and a full window, the
blocked latent core against the one-block one, each mechanism shown to matter,
both shares (of experts, the identity term counted once, and of attention
heads) against the uncut parts, the router's weights, and the row of ``HEADS``
with what the server counts from it."""

import copy
import dataclasses
import hashlib
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, validate
from igaming_platform_tpu.models import decoder_parts as dp
from igaming_platform_tpu.models import longcat_backbone as lb
from igaming_platform_tpu.models import session_heads

CONFIG = "risk-seqhead-longcat-flash-omni"
CELL = "longcat-scmoe-deep2048"
EVENTS = 48
BLOCK = 512 * 512  # pairs of a block at the cell's window: the counters' unit is pairs
SWITCHES = ("WITHOUT_ZERO", "SHORTCUT_EARLY", "RENORMALISED", "WITHOUT_SCALES",
            "ROTATE_HALF", "HALF_THE_HEADS")
LAYERS = {"conv": 0, "attention": 8, "window": 0, "ssm": 0, "linear": 0,
          "memory": 0, "cross": 0, "mtp": 0, "dense": 8, "moe": 4}


def misses(got, stated, exact) -> bool:
    """Whether answers ``got`` miss one of the cell's two limits on the
    probability against the reference at the stated precision, as
    ``chipbench/reference.merge`` reckons them."""
    limits = validate.load_data("configs", CONFIG)["limits"]
    rms = lambda a: float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
    return bool(np.abs(got - stated).max() > limits["fraud_prob_max_err"]
                or rms(got - stated) / rms(stated - exact)
                > limits["fraud_prob_err_in_roundings"])


def small_config(**changes) -> lb.LongcatConfig:
    """Two double layers, 2 of 8 heads and 4 of 16 experts held beside 8
    identity experts, 3 picks a position; rotary channels as many as the
    others and a ``rope_theta`` of 100, so that every pair turns inside a
    window of 48 (at the source's 1e7 one pair of four would)."""
    return dataclasses.replace(lb.LongcatConfig(
        hidden=64, layers=2, heads=2, published_heads=8, q_rank=32, kv_rank=16,
        nope_dim=16, rope_dim=16, v_dim=16, dense_width=96, experts=24,
        real_experts=16, held_experts=4, top_k=3, expert_width=32,
        rope_theta=100.0), **changes)


def small_source(events: int = EVENTS) -> dict:
    """The source's keys at the small size."""
    source = dict(validate.load_data("configs", CONFIG))
    source.update({
        "hidden_size": 64, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
        "num_layers": 2, "num_attention_heads": 2, "kv_lora_rank": 16,
        "q_lora_rank": 32, "qk_rope_head_dim": 16, "v_head_dim": 16,
        "rope_theta": 100,
        "qk_nope_head_dim": 16, "n_routed_experts": 4, "zero_expert_num": 8,
        "moe_topk": 3,
        "head": dict(source["head"], published=dict(
            source["head"]["published"], n_routed_experts=16,
            num_attention_heads=8)),
        "env": dict(source["env"], SESSION_EVENTS=str(events))})
    return source


def sample(events: int = EVENTS, rows: int = 16, seed: int = 68):
    head = validate.load_code("heads", "longcat_flash_omni")
    params = head.make_params(seed, small_source(events))
    win, lengths = head.plausible_windows(np.random.default_rng(seed), rows, events)
    return head, params, win, lengths


@pytest.fixture(scope="module")
def small():
    """The reference's seeded tree at the small size (the program's tree has
    its shape) and plausible windows, half full to full."""
    return sample()


def program(params, win, lengths, cfg=None, **kwargs):
    cfg = cfg or small_config()
    return np.asarray(lb.backbone_scores(params, jnp.asarray(win),
                                         jnp.asarray(lengths, jnp.int32), cfg,
                                         **kwargs))


def by_reference(head, params, win, lengths, dtype="bfloat16", **switches):
    saved = {k: getattr(head, k) for k in switches}
    for k, v in switches.items():
        setattr(head, k, v)
    try:
        return head.forward(params, win, lengths, reference.rounder(dtype))
    finally:
        for k, v in saved.items():
            setattr(head, k, v)


@pytest.fixture(scope="module")
def stated(small):
    return by_reference(*small)


@pytest.fixture(scope="module")
def exact(small):
    return by_reference(*small, dtype="float32")


def test_program_equals_the_reference_at_the_stated_precision(small, stated, exact):
    _, params, win, lengths = small
    got = program(params, win, lengths)
    assert not misses(got, stated, exact)
    assert np.abs(got - stated).max() < 0.01
    # and with no rounding on either side, to float32's own
    plain = program(params, win, lengths, small_config(operand_dtype=jnp.float32))
    np.testing.assert_allclose(plain, exact, atol=2e-5)
    # the seeded tree spreads the answers about the fold threshold
    assert 0.2 < (stated >= reference.FLAG_THRESHOLD).mean() < 0.8


@pytest.mark.parametrize("length", [1, 2, 3, EVENTS - 1, EVENTS],
                         ids=["one-event", "two", "three", "last-but-one", "full"])
def test_the_read_at_each_length(small, length):
    head, params, win, _ = small
    win = win[:2] * (np.arange(EVENTS)[None, :, None] < length)
    lengths = np.array([length, length])
    cfg = small_config(operand_dtype=jnp.float32)
    want = by_reference(head, params, win, lengths, "float32")
    np.testing.assert_allclose(program(params, win, lengths, cfg), want, atol=2e-5)
    np.testing.assert_allclose(program(params, win, lengths, cfg, narrowed=False),
                               want, atol=2e-5)


def test_the_narrowed_last_layer_equals_the_whole_one(small):
    _, params, win, lengths = small
    for dtype, atol in ((jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)):
        cfg = small_config(operand_dtype=dtype)
        np.testing.assert_allclose(
            program(params, win, lengths, cfg),
            program(params, win, lengths, cfg, narrowed=False), atol=atol)
    # a layer counts as its two halves: three whole and one at one position
    assert lb.layer_positions(small_config(), EVENTS) == (3 * EVENTS + 1, 4 * EVENTS)
    assert lb.layer_positions(lb.LongcatConfig(), 2048) == (7 * 2048 + 1, 8 * 2048)


def test_the_tree_has_the_programs_shape(small):
    head, params, _, _ = small
    mine = jax.eval_shape(lambda: lb.init_backbone(jax.random.key(0), small_config()))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(params)))
    # the balanced bias changes the chosen set on many positions, so a router
    # that ignored it, or weighed by it, fails the check; under it about a
    # third of the chosen pairs (8 of 24 outputs) fall on identity experts
    assert len(head._made["bias_moved"]) == 2 and min(head._made["bias_moved"]) > 0.2
    assert all(0.2 < share < 0.5 for share in head._made["identity_share"])
    shares = head.identity_share(params, *small[2:], reference.rounder("bfloat16"))
    assert len(shares) == 2 and all(0.15 < share < 0.55 for share in shares)


@pytest.mark.parametrize("switch", SWITCHES)
def test_a_reference_with_one_mechanism_knocked_out_leaves_the_limits(
        small, stated, exact, switch):
    """Each of the proof's switches (chipbench/aa/proof) moves the answers by
    more than the rounding does: the program, which has the mechanism, is
    inside the limits against the reference and outside them against the
    reference without it."""
    head, params, win, lengths = small
    knocked = by_reference(head, params, win, lengths, **{switch: True})
    got = program(params, win, lengths)
    assert not misses(got, stated, exact)
    assert misses(got, knocked, by_reference(head, params, win, lengths, "float32",
                                             **{switch: True}))
    assert np.abs(knocked - stated).max() > 10 * np.abs(got - stated).max()


# -- the core in query blocks ----------------------------------------------------


def _core_operands(t: int, rows: int = 2, heads: int = 4):
    nope, rope, dv = 16, 8, 16
    keys = jax.random.split(jax.random.key(7), 3)
    p = rows * t
    q = jax.random.normal(keys[0], (p, heads * (nope + rope)), jnp.float32)
    kvb = jax.random.normal(keys[1], (p, heads * (nope + dv)),
                            jnp.float32).astype(jnp.bfloat16)
    k_rope = jax.random.normal(keys[2], (p, rope), jnp.float32).astype(jnp.bfloat16)
    cos, sin = dp.rope_angles(rows, t, rope, 1e4)
    widths = dict(heads=heads, nope=nope, rope=rope, dv=dv, window=t)
    return (q, kvb, k_rope, cos.reshape(p, -1), sin.reshape(p, -1)), widths


@pytest.mark.parametrize("interleave", [True, False], ids=["interleaved", "halves"])
@pytest.mark.parametrize("block", [16, 32, 40], ids=["six-blocks", "three", "ragged"])
def test_the_blocked_latent_core_equals_the_one_block_core(block, interleave):
    """A window of 96 positions in query blocks of 16, 32 and 40 (the last
    one short) against the three einsums over the whole window."""
    operands, widths = _core_operands(96)
    whole = dp.latent_core_by_einsums(*operands, **widths, interleave=interleave,
                                      block=96)
    blocked = dp.latent_core_by_einsums(*operands, **widths, interleave=interleave,
                                        block=block)
    # a softmax summed over fewer masked keys may round one probability the
    # other way on its way to bfloat16: an output moves by 2^-9 of a value
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), atol=1e-3)
    assert float(np.abs(np.asarray(blocked) - np.asarray(whole)).mean()) < 1e-6


def test_a_window_that_fits_one_block_runs_the_core_it_ran_before():
    """At every other latent-attention head's 16 keys the blocked core IS the
    one-block core: the lowered text is the same whatever block is named,
    three products and no more."""
    operands, widths = _core_operands(16, rows=8)
    texts = [jax.jit(lambda *a: dp.latent_core_by_einsums(
        *a, **widths, block=b)).lower(*operands).as_text() for b in (None, 16, 512)]
    assert texts[0] == texts[1] == texts[2]
    assert texts[0].count("dot_general") == 3
    deep, deep_widths = _core_operands(2048, rows=1, heads=2)
    text = jax.jit(lambda *a: dp.latent_core_by_einsums(
        *a, **deep_widths, interleave=True)).lower(*deep).as_text()
    assert text.count("dot_general") == 3 * 4       # four query blocks of 512
    assert "2048x2048" not in text and "512x2048" in text


# Each latent-attention head's scores at its cell's 16-event windows, lowered
# from abstract arguments at the published widths: the StableHLO of the parent
# commit (346de0d), which ``latent_attention``'s two scales, its two helper
# functions and the core's blocks leave as it was.
@pytest.mark.parametrize("name,sha256", [
    ("pangu", "2108e7eaca094c73"), ("ling", "cae66d51b4c5446d"),
    ("xing", "1d07d154c9ddd5fc")])
def test_the_other_latent_attention_heads_lower_to_the_step_they_had(name, sha256):
    head = session_heads.HEADS[name]
    text = jax.jit(head.scores).lower(
        jax.eval_shape(head.init), jax.ShapeDtypeStruct((256, 16, 12), jnp.float32),
        jax.ShapeDtypeStruct((256,), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == sha256


# -- the router and the two shares ------------------------------------------------


def test_the_weights_are_six_times_the_chosen_mass_and_not_six(small):
    _, params, _, _ = small
    cfg = small_config(operand_dtype=jnp.float32)
    layer = params["layers"][0]
    u = jax.random.normal(jax.random.key(5), (40, 64), jnp.float32)
    top_e, top_w = (np.asarray(x) for x in lb.route(u, layer, cfg))
    p = np.asarray(jax.nn.softmax(u @ layer["wr"].astype(jnp.float32), axis=-1))
    want = np.argsort(-(p + np.asarray(layer["rb"])), axis=1, kind="stable")[:, :3]
    np.testing.assert_array_equal(top_e, want)
    np.testing.assert_allclose(top_w, 6.0 * np.take_along_axis(p, want, 1), rtol=1e-5)
    mass = np.take_along_axis(p, want, 1).sum(1)
    np.testing.assert_allclose(top_w.sum(1), 6.0 * mass, rtol=1e-5)
    # 3 of 24 outputs hold anything from a third to nearly all of the mass
    assert np.abs(top_w.sum(1) - 6.0).mean() > 0.5 and mass.min() < 0.5
    # the bias chose: without it another set is chosen somewhere
    bare = np.argsort(-p, axis=1, kind="stable")[:, :3]
    assert (np.sort(bare, 1) != np.sort(want, 1)).any()


@pytest.mark.parametrize("picks", ["all-identity", "no-identity"])
def test_a_position_whose_picks_are_all_or_none_identity_experts(small, picks):
    """The selection bias pushed to one side: every pick an identity expert
    (the branch is ``w u`` and no expert runs) or none of them (no identity
    term); the program agrees with the reference either way, and the two
    differ."""
    head, params, win, lengths = small
    params = copy.copy(params)
    sign = 1.0 if picks == "all-identity" else -1.0
    bias = jnp.where(jnp.arange(24) >= 16, 2.0 * sign, 0.0)
    params["layers"] = [dict(layer, rb=bias) for layer in params["layers"]]
    cfg = small_config(operand_dtype=jnp.float32)
    top_e, _ = lb.route(jax.random.normal(jax.random.key(1), (32, 64)),
                        params["layers"][0], cfg)
    assert bool(((top_e >= 16) == (picks == "all-identity")).all())
    got = program(params, win, lengths, cfg)
    want = by_reference(head, params, win, lengths, "float32")
    np.testing.assert_allclose(got, want, atol=2e-5)
    without = by_reference(head, params, win, lengths, "float32", WITHOUT_ZERO=True)
    if picks == "all-identity":
        assert np.abs(without - want).max() > 1e-2
    else:
        np.testing.assert_array_equal(without, want)


def test_the_expert_shares_add_up_to_the_uncut_branch(small):
    """Four shares of 4 of the 16 real experts, the identity term counted
    once, are the branch with every real expert held: the router, its
    weights and what a held expert computes do not depend on who else is
    held, and the identity term is every share's alike."""
    _, params, _, _ = small
    cfg = small_config(operand_dtype=jnp.float32)
    layer = params["layers"][1]
    keys = jax.random.split(jax.random.key(3), 3)
    every = {name: jax.random.normal(k, (16, *layer["routed"][name].shape[1:]),
                                     jnp.float32) * 0.1
             for name, k in zip(("wg", "wu", "wd"), keys)}
    x = jax.random.normal(jax.random.key(4), (2 * EVENTS, 64), jnp.float32)
    live = jnp.arange(2 * EVENTS) % EVENTS < 40

    def branch(first, held):
        share = {k: v[first:first + held] for k, v in every.items()}
        c = dataclasses.replace(cfg, first_expert=first, held_experts=held)
        return lb.expert_branch(x, dict(layer, routed=share), c, live)

    top_e, top_w = lb.route(x, layer, cfg)
    zero = jnp.sum(jnp.where((top_e >= 16) & live[:, None], top_w, 0.0),
                   axis=-1)[:, None] * x
    whole = branch(0, 16)
    parts = [branch(first, 4) - zero for first in (0, 4, 8, 12)]
    np.testing.assert_allclose(np.asarray(zero + sum(parts)), np.asarray(whole),
                               atol=1e-4)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    assert float(jnp.abs(zero).max()) > 0
    # padding is not routed: neither an expert nor the identity term
    np.testing.assert_array_equal(np.asarray(whole[~live]), 0.0)


def test_the_head_shares_add_up_to_the_uncut_attention():
    """Four shares of 2 of the 8 heads (``Wq_b``'s and ``Wkv_b``'s columns
    and ``Wo``'s rows of those heads; the latent projections and norms
    whole) add up to the attention with every head held: ``Wo``'s product is
    a sum over heads."""
    whole_cfg = small_config(heads=8, operand_dtype=jnp.float32)
    attn = lb.init_backbone(jax.random.key(2), dataclasses.replace(
        whole_cfg, layers=1))["layers"][0]["halves"][0]["attn"]
    attn = jax.tree.map(lambda a: a.astype(jnp.float32), attn)
    b, t = 2, EVENTS
    a = jax.random.normal(jax.random.key(6), (b, t, 64), jnp.float32)
    cos, sin = dp.rope_angles(b, t, 16, 100.0)

    def attention(layer, cfg):
        return dp.latent_attention(a, layer, cos, sin, cfg, interleave=True,
                                   q_scale=cfg.q_scale, kv_scale=cfg.kv_scale)

    def share(first, held):
        cols = lambda w, width: w.reshape(w.shape[0], 8, width)[
            :, first:first + held].reshape(w.shape[0], held * width)
        return dict(attn, wq_b=cols(attn["wq_b"], 32), wkv_b=cols(attn["wkv_b"], 32),
                    wo=attn["wo"].reshape(8, 16, 64)[first:first + held].reshape(-1, 64))

    whole = attention(attn, whole_cfg)
    parts = [attention(share(first, 2), dataclasses.replace(whole_cfg, heads=2))
             for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=1e-4)
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    assert whole_cfg.q_scale == pytest.approx(2 ** 0.5) and whole_cfg.kv_scale == 2.0
    full = lb.LongcatConfig()
    assert (full.q_scale, full.kv_scale) == (2.0, pytest.approx(3.4641, abs=1e-4))


def test_padding_cannot_reach_the_score(small):
    _, params, win, lengths = small
    noisy = np.array(win)
    pad = np.arange(EVENTS)[None, :] >= lengths[:, None]
    noisy[pad] = np.random.default_rng(1).normal(0, 3, noisy[pad].shape)
    cfg = small_config(operand_dtype=jnp.float32)
    np.testing.assert_allclose(program(params, noisy, lengths, cfg),
                               program(params, win, lengths, cfg), atol=1e-6)


def test_the_scopes_and_the_cores_said(small):
    _, params, win, lengths = small
    dp.announce_core.cache_clear()
    text = jax.jit(lambda p, w, n: lb.backbone_scores(p, w, n, small_config())
                   ).lower(params, win, lengths).as_text(debug_info=True)
    for scope in ("head/embed", "head/attn/0/q", "head/attn/0/kv",
                  "head/attn/0/core", "head/attn/0/out", "head/attn/1/q",
                  "head/attn/1/kv", "head/attn/1/core", "head/attn/1/out",
                  "head/mlp/dense", "head/moe/route", "head/moe/experts",
                  "head/moe/zero"):
        assert scope in text, scope
    cores = dp.announced_cores()
    assert cores["attention core"] == (
        "xla-einsum (interleaved rotary pairs: the window kernel turns by "
        "halves) (backend=cpu)")
    assert cores["attention core (narrowed)"] == (
        "xla-einsum, one query a row (window 48, 2 heads, interleaved rotary "
        "pairs) (backend=cpu)")


@pytest.mark.parametrize("backend,heads,window,said", [
    # the cell's windows on a chip: the blocked kernel, told the pairing
    ("tpu", 16, 2048,
     "pallas-blocks (latent, 16 heads of 128 + 64 / 128, interleaved rotary "
     "pairs, window 2048 in blocks of 512, band=None: 10 of 16 key blocks)"),
    # off it, and where the kernel declines: the einsums in query blocks,
    # with the reason
    ("cpu", 16, 2048,
     "xla-einsum in query blocks of 512 (window 2048, 16 heads; not a TPU)"),
    ("tpu", 15, 2048,
     "xla-einsum in query blocks of 512 (window 2048, 15 heads; 15 heads are "
     "not whole units of 2)"),
    # a window that fits one block: what every latent head ran before
    ("tpu", 16, 16,
     "xla-einsum (interleaved rotary pairs: the window kernel turns by halves)"),
    ("tpu", 16, 512,
     "xla-einsum (interleaved rotary pairs: the window kernel turns by halves)"),
])
def test_the_core_is_picked_from_the_windows_depth_and_the_shapes(
        monkeypatch, backend, heads, window, said):
    """``latent_attention_core`` at the published widths, from shapes alone:
    a window deeper than one block takes the blocked kernel on a TPU where
    it takes the operands, and says which core a compile took either way."""
    from igaming_platform_tpu.ops.pallas import block_attention as ba

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = lb.LongcatConfig(heads=heads)
    p = 2 * window
    q = jax.ShapeDtypeStruct((p, heads * (cfg.nope_dim + cfg.rope_dim)), jnp.float32)
    kvb = jax.ShapeDtypeStruct((p, heads * (cfg.nope_dim + cfg.v_dim)), jnp.bfloat16)
    core = dp.latent_attention_core(q, kvb, cfg, window, interleave=True)
    assert dp.announced_cores()["attention core"] == f"{said} (backend={backend})"
    kernel = said.startswith("pallas-blocks")
    assert core.func is (ba.latent_block_attention if kernel
                         else dp.latent_core_by_einsums)
    assert core.keywords["interleave"] is True and core.keywords["window"] == window


def test_the_row_of_heads_and_what_it_holds():
    import math

    row = session_heads.HEADS["longcat"]
    assert row.config == lb.LongcatConfig() and row.experts == (8, 768)
    assert row.layers == LAYERS
    leaves = jax.tree.leaves(jax.eval_shape(row.init))
    assert sum(math.prod(a.shape) for a in leaves) == 3_297_975_297
    assert sum(math.prod(a.shape) * a.dtype.itemsize
               for a in leaves) == pytest.approx(6.596e9, rel=1e-3)
    # one attention at the held 16 heads, one dense MLP, the router, an expert
    attn = row.init and jax.eval_shape(row.init)["layers"][0]["halves"][0]["attn"]
    assert sum(math.prod(attn[k].shape) for k in (
        "wq_a", "wq_b", "wkv_a", "wkv_b", "wo")) == 32_374_784
    assert row.layer_positions(2048) == (7 * 2048 + 1, 8 * 2048)
    # in pairs, by blocks of 512 x 512: seven cores' 10 of 16 blocks, and the
    # last one's one query a row meets one row of 4
    assert row.key_blocks(2048) == ((7 * 10 + 4) * BLOCK, 8 * 16 * BLOCK)
    # the expert layer's passes at a router of 768: 4 x the expected 512 pairs
    from igaming_platform_tpu.models.expert_layer import pass_rows
    assert pass_rows(4096 * 12, 8, 768, 6144) == 2048
    assert pass_rows(2 * 12, 8, 768, 6144) == 24
    assert "'longcat'" in str(pytest.raises(
        ValueError, session_heads.session_head, "kimi").value)


def test_the_server_counts_two_halves_a_layer_and_its_blocks(monkeypatch):
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setitem(session_heads.HEADS, "longcat", dataclasses.replace(
        session_heads.HEADS["longcat"], init=lambda: None))
    metrics = ServiceMetrics("risk")
    manager = ss.SessionStateManager(8, n_events=2048, head="longcat",
                                     metrics=metrics)
    assert manager.head_layer_positions == (14337, 16384)
    assert manager.head_key_blocks == (74 * BLOCK, 128 * BLOCK)
    with manager.lock:
        manager.prepare_chunk(ss.group_chunk(["a", "b", "a"]),
                              np.array([100.0, 200.0, 300.0], np.float32),
                              np.array([2, 2, 0], np.int32), 1_700_000_000.0)
    text = metrics.registry.render_text().replace(".0\n", "\n")
    assert "risk_session_head_layer_positions_computed_total 43011" in text
    assert "risk_session_head_layer_positions_whole_total 49152" in text
    assert f"risk_session_head_key_blocks_visited_total {3 * 74 * BLOCK}" in text
    assert f"risk_session_head_key_blocks_square_total {3 * 128 * BLOCK}" in text
    for kind, count in LAYERS.items():
        assert f'risk_session_head_layers{{kind="{kind}"}} {count}' in text
    assert "risk_session_head_experts_held 8" in text
    assert "risk_session_head_experts_routed 768" in text


# -- the served path ----------------------------------------------------------------


@pytest.fixture
def small_longcat(monkeypatch):
    """``SESSION_HEAD=longcat`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "longcat", dataclasses.replace(
        session_heads.HEADS["longcat"],
        scores=lambda sp, win, lp: lb.backbone_scores(sp, win, lp, cfg),
        init=lambda: lb.init_backbone(jax.random.key(11), cfg), config=cfg,
        experts=(cfg.held_experts, cfg.experts),
        layers=session_heads._NO_LAYERS | lb.layer_kinds(cfg),
        key_blocks=lambda window: lb.key_blocks(cfg, window),
        layer_positions=lambda window: lb.layer_positions(cfg, window)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_longcat, environment):
    """The new cell's own files, the source's sizes cut to the small one and
    windows of 48 events preloaded 24 to 72 deep: one server, the head
    through ``serve/index_program.build`` at ``BATCH_SIZE=2``, index-mode
    frames of 2 and 8 rows over a real socket (an 8-row frame is four
    launches), every reply against ``chipbench/reference.py`` and the control
    told apart; the boot gauges and the counters on ``/metrics``."""
    spec = copy.deepcopy(validate.load_cell(CELL))
    spec["config"].update({k: v for k, v in small_source().items()
                           if k not in ("env",)})
    spec["config"]["env"].update(FEATURE_STORE="python", SESSION_EVENTS=str(EVENTS),
                                 DEVICE_STEP_DEADLINE_S="600")
    spec["config"]["session_events_preloaded"] = {"events": "24-72", "rounds": 4}
    dp.announce_core.cache_clear()
    run = harness.Run(spec, seed=6_800_000_011, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "longcat"
        assert run.inner._shapes == [2]  # one rung: every launch is 2 rows
        run.fill()
        run.device = types.SimpleNamespace(platform="as-on-the-chip")
        filled = run.counters()  # the preload appends through the same seam
        ok, numbers = run.check()
        c_ok, control = run.judge(
            run.config["precision"]["control_operand_dtype"], control=True)
        counters = {k: v - filled.get(k, 0.0) for k, v in run.counters().items()}
        snap = run.inner.session.snapshot()
    finally:
        run.shutdown()
    assert ok, numbers
    assert not c_ok, control
    assert numbers["rows"] == 6 * (2 + 8) and numbers["warm_rows"] == numbers["rows"]
    assert numbers["session_bit_mismatch"] == 0 and numbers["score_max_err"] <= 1
    rows = numbers["rows"]
    assert counters["risk_session_head_positions_total"] == EVENTS * rows
    computed, whole = lb.layer_positions(small_longcat, EVENTS)
    assert counters["risk_session_head_layer_positions_computed_total"] == computed * rows
    assert counters["risk_session_head_layer_positions_whole_total"] == whole * rows
    visited, square = lb.key_blocks(small_longcat, EVENTS)
    assert counters["risk_session_head_key_blocks_visited_total"] == visited * rows
    assert counters["risk_session_head_key_blocks_square_total"] == square * rows
    assert snap["head_layers"] == LAYERS | {"attention": 4, "dense": 4, "moe": 2}
    assert snap["head_cores"]["attention core"].startswith("xla-einsum (interleaved")
