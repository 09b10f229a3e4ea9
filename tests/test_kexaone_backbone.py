"""The ``kexaone`` session head (models/kexaone_backbone.py) on the CPU at a
small size, windows several times its ``sliding_window``: against its plain
reference (chipbench/heads/k_exaone_236b_a23b.py, which runs the module's
layer at EVERY position), the narrowed module against the whole one, the
read at ``len`` 1, 2 and a full window, each mechanism shown to matter, the
chip's share against the uncut layer, the einsum core against the kernel in
the Pallas interpreter, and the row of ``HEADS`` with what the server counts
from it."""

import copy
import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, validate
from igaming_platform_tpu.models import decoder_parts as dp
from igaming_platform_tpu.models import kexaone_backbone as kb
from igaming_platform_tpu.models import session_heads

CONFIG = "risk-seqhead-k-exaone-236b-a23b"
CELL = "kexaone-mtp-deep2048"
EVENTS, BAND = 48, 8
BLOCK = 512 * 512  # pairs of a block at the cell's window: the counters' unit is pairs
SWITCHES = ("WITHOUT_BAND", "ROPE_ON_FULL", "WITHOUT_MTP", "JOIN_SAME_EVENT",
            "WITHOUT_SHARED")


def misses(got, stated, exact) -> bool:
    """Whether answers ``got`` miss one of the cell's two limits on the
    probability against the reference at the stated precision, as
    ``chipbench/reference.merge`` reckons them."""
    limits = validate.load_data("configs", CONFIG)["limits"]
    rms = lambda a: float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
    return bool(np.abs(got - stated).max() > limits["fraud_prob_max_err"]
                or rms(got - stated) / rms(stated - exact)
                > limits["fraud_prob_err_in_roundings"])


def small_config(**changes) -> kb.KExaoneConfig:
    return dataclasses.replace(kb.KExaoneConfig(
        hidden=64, heads=8, kv_heads=2, head_dim=16, dense_width=96, experts=16,
        held_experts=4, top_k=4, expert_width=32, sliding_window=BAND), **changes)


def small_source(events: int = EVENTS, held: int = 4) -> dict:
    """The source's keys at the small size: the same layers, a quarter of 16
    experts held."""
    source = dict(validate.load_data("configs", CONFIG))
    source.update({
        "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts": held, "num_experts_per_tok": 4, "sliding_window": BAND,
        "sliding_windows": [BAND if w else 0 for w in source["sliding_windows"]],
        "head": dict(source["head"], published=dict(
            source["head"]["published"], num_experts=16)),
        "env": dict(source["env"], SESSION_EVENTS=str(events))})
    return source


def sample(events: int = EVENTS, rows: int = 16, seed: int = 65):
    head = validate.load_code("heads", "k_exaone_236b_a23b")
    params = head.make_params(seed, small_source(events))
    win, lengths = head.plausible_windows(np.random.default_rng(seed), rows, events)
    return head, params, win, lengths


@pytest.fixture(scope="module")
def small():
    """The reference's seeded tree at the small size (the program's tree has
    its shape) and plausible windows, half full to full: six bands deep."""
    return sample()


def program(params, win, lengths, cfg=None):
    cfg = cfg or small_config()
    return np.asarray(kb.backbone_scores(params, jnp.asarray(win),
                                         jnp.asarray(lengths, jnp.int32), cfg))


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
    """A row of one event has no depth-1 position and reads depth 0 alone; a
    row of two reads the module at position 0; a full window at ``T - 2``."""
    head, params, win, _ = small
    win = win[:2] * (np.arange(EVENTS)[None, :, None] < length)
    lengths = np.array([length, length])
    cfg = small_config(operand_dtype=jnp.float32)
    got = program(params, win, lengths, cfg)
    want = by_reference(head, params, win, lengths, "float32")
    np.testing.assert_allclose(got, want, atol=2e-5)
    z0, z1 = (np.asarray(z) for z in kb.backbone_logits(
        params, jnp.asarray(win), jnp.asarray(lengths, jnp.int32), cfg))
    sigmoid = lambda z: 1.0 / (1.0 + np.exp(-z))
    np.testing.assert_allclose(
        got, sigmoid(z0 if length == 1 else 0.5 * (z0 + z1)), atol=1e-6)
    without = by_reference(head, params, win, lengths, "float32", WITHOUT_MTP=True)
    if length == 1:
        np.testing.assert_array_equal(without, want)
    else:
        np.testing.assert_allclose(without, sigmoid(z0), atol=2e-5)
        assert np.abs(without - want).max() > 1e-3


def test_the_narrowed_module_equals_the_whole_one(small):
    _, params, win, lengths = small
    args = (params, jnp.asarray(win), jnp.asarray(lengths, jnp.int32))
    for dtype, atol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        cfg = small_config(operand_dtype=dtype)
        narrowed = kb.backbone_logits(*args, cfg)
        whole = kb.backbone_logits(*args, cfg, narrowed=False)
        np.testing.assert_array_equal(np.asarray(narrowed[0]), np.asarray(whole[0]))
        np.testing.assert_allclose(np.asarray(narrowed[1]), np.asarray(whole[1]),
                                   atol=atol)
    assert kb.layer_positions(small_config(), EVENTS) == (5 * EVENTS + 1, 6 * EVENTS)


def test_the_tree_has_the_programs_shape(small):
    _, params, _, _ = small
    mine = jax.eval_shape(lambda: kb.init_backbone(jax.random.key(0), small_config()))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(params)))
    head = small[0]
    # the balanced bias changes the chosen set on many positions, so a router
    # that ignored it, or weighed by it, fails the check
    assert len(head._made["bias_moved"]) == 5 and min(head._made["bias_moved"]) > 0.2
    # dropping the module moves an answer by a good part of the logits' spread
    assert head._made["depth_gap"] > 0.3


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


def test_a_window_inside_the_band_is_full_attention():
    """At ``sliding_window`` events or fewer a sliding layer keeps every
    causal key: the band changes nothing, in the program (a wider band) or in
    the reference (its switch)."""
    head, params, win, lengths = sample(events=BAND, rows=4)
    want = by_reference(head, params, win, lengths, "float32")
    np.testing.assert_array_equal(
        by_reference(head, params, win, lengths, "float32", WITHOUT_BAND=True), want)
    for band in (BAND, 10 * BAND):
        got = program(params, win, lengths, small_config(
            sliding_window=band, operand_dtype=jnp.float32))
        np.testing.assert_allclose(got, want, atol=2e-5)
    # every layer the whole square of the window's one 16-row block
    assert kb.key_blocks(small_config(), BAND) == (6 * 256, 6 * 256)


def test_the_band_is_the_sources_list():
    """``sliding_windows`` stays the source's 48 entries; the band of each
    layer held comes from ``layer_types`` and ``sliding_window`` and agrees
    with its first five, in the program's defaults and in the reference."""
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)["config"]
    assert cfg["sliding_windows"] == source["sliding_windows"]
    assert len(cfg["sliding_windows"]) == 48
    full = kb.KExaoneConfig()
    assert tuple(cfg["layer_types"]) == full.layer_types
    assert [kb.band_of(kind, full) or 0 for kind in full.layer_types] == \
        cfg["sliding_windows"][:5] == [128, 128, 128, 0, 128]
    assert (kb.band_of(full.mtp_layer_type, full) or 0) == cfg["mtp_sliding_windows"][0]
    assert cfg["mtp_layer_types"] == [full.mtp_layer_type]
    head = validate.load_code("heads", "k_exaone_236b_a23b")
    d = head.dims_of(cfg)
    assert (d.kinds, d.band, d.experts, d.held) == (full.layer_types, 128, 128, 8)
    with pytest.raises(ValueError, match="sliding_windows disagrees"):
        head.dims_of(dict(cfg, sliding_windows=[128] * 48))
    for key, mine in (("hidden_size", full.hidden), ("head_dim", full.head_dim),
                      ("num_attention_heads", full.heads),
                      ("num_key_value_heads", full.kv_heads),
                      ("intermediate_size", full.dense_width),
                      ("moe_intermediate_size", full.expert_width),
                      ("num_experts_per_tok", full.top_k),
                      ("routed_scaling_factor", full.routed_scale),
                      ("rms_norm_eps", full.eps),
                      ("first_k_dense_replace", full.dense_layers)):
        assert cfg[key] == source[key] == mine, key
    assert source["rope_parameters"]["rope_theta"] == full.rope_theta
    assert (source["num_experts"], cfg["num_experts"]) == (full.experts,
                                                           full.held_experts)


def test_the_shares_add_up_to_the_uncut_layer(small):
    """Four shares of 4 of the 16 experts, the shared expert counted once,
    are the layer with every expert held: the router, its weights and what a
    held expert computes do not depend on who else is held."""
    _, params, win, lengths = small
    cfg = small_config(operand_dtype=jnp.float32)
    layer = params["layers"][1]
    keys = jax.random.split(jax.random.key(3), 3)
    every = {name: jax.random.normal(k, (16, *layer["routed"][name].shape[1:]),
                                     jnp.float32) * 0.1
             for name, k in zip(("wg", "wu", "wd"), keys)}
    x = jax.random.normal(jax.random.key(4), (2 * EVENTS, 64), jnp.float32)
    live = jnp.arange(2 * EVENTS) % EVENTS < 40

    def moe(first, held):
        share = {k: v[first:first + held] for k, v in every.items()}
        c = dataclasses.replace(cfg, first_expert=first, held_experts=held)
        return kb.feed_forward(x, dict(layer, routed=share), c, live)

    shared = dp.swiglu(x, layer["shared"], cfg)
    whole = moe(0, 16)
    parts = [moe(first, 4) - shared for first in (0, 4, 8, 12)]
    np.testing.assert_allclose(np.asarray(shared + sum(parts)), np.asarray(whole),
                               atol=1e-4)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    # padding is not routed: it takes the shared expert alone
    np.testing.assert_array_equal(np.asarray(whole[~live]), np.asarray(shared[~live]))


def test_padding_cannot_reach_the_score(small):
    _, params, win, lengths = small
    noisy = np.array(win)
    pad = np.arange(EVENTS)[None, :] >= lengths[:, None]
    noisy[pad] = np.random.default_rng(1).normal(0, 3, noisy[pad].shape)
    cfg = small_config(operand_dtype=jnp.float32)
    np.testing.assert_allclose(program(params, noisy, lengths, cfg),
                               program(params, win, lengths, cfg), atol=1e-6)


@pytest.mark.parametrize("band", [BAND * 16, None], ids=["band-under-a-block", "full"])
def test_the_einsum_core_equals_the_kernel_at_64_over_8_heads(band):
    """``decoder_parts.core_by_einsums`` against ``block_attention`` in the
    Pallas interpreter at the published 64 / 8 heads of 128, a band (128)
    of half the kernel's block (here 256 of a 512-event window: the one-visit
    form, as at the cell's 512), and the full layer's unit cos and zero sin,
    which leave ``q`` as it was."""
    from igaming_platform_tpu.ops.pallas import block_attention as ba

    window, heads, kv, hd = 512, 64, 8, 128
    key = jax.random.key(0)
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape)
    q = draw(1, (window, heads * hd))
    k = draw(2, (window, kv * hd)).astype(jnp.bfloat16)
    v = draw(3, (window, kv * hd)).astype(jnp.bfloat16)
    gain = 2.0 + 0.1 * draw(4, (hd,))
    kind = kb.FULL if band is None else kb.SLIDING
    cos, sin = kb.angle_tables(kb.KExaoneConfig(), window)[kind]
    widths = dict(heads=heads, kv_heads=kv, window=window, band=band, eps=1e-5,
                  block=256)
    assert ba.declines(q, k, v, heads=heads, kv_heads=kv, window=window,
                       band=band) == ""
    assert ba.one_visit(window, band, 256) == (None if band is None else (128, 256))
    want = dp.core_by_einsums(q, k, v, cos, sin, gain, **widths)
    got = ba.block_attention(q, k, v, cos, sin, gain, interpret=True, **widths)
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=0.03, rtol=0.03)
    if band is None:
        # under the full layer's table nothing turns, bit for bit
        turned = dp.rotate(q.reshape(1, window, heads, hd), cos[None], sin[None])
        np.testing.assert_array_equal(np.asarray(turned).reshape(q.shape),
                                      np.asarray(q))
    else:
        assert ba.visited_blocks(2048, 128) == (2 * BLOCK, 16 * BLOCK)


def test_the_scopes_and_the_cores_said(small):
    _, params, win, lengths = small
    dp.announce_core.cache_clear()
    text = jax.jit(lambda p, w, n: kb.backbone_scores(p, w, n, small_config())
                   ).lower(params, win, lengths).as_text(debug_info=True)
    for scope in ("head/embed", "head/attn/window/core", "head/attn/full/core",
                  "head/dense", "head/moe/route", "head/moe/shared",
                  "head/moe/experts", "head/mtp/join", "head/mtp/attn/full/core",
                  "head/mtp/moe/route", "head/mtp/moe/shared",
                  "head/mtp/moe/experts", "head/score"):
        assert scope in text, scope
    cores = dp.announced_cores()
    assert cores["attention core (window)"] == (
        "einsum in query blocks (window 48 in blocks of 48, band=8: 1 of 1 key "
        "blocks; not a TPU) (backend=cpu)")
    assert "band=None" in cores["attention core (full)"]
    assert "no rotary: unit cos, zero sin" in cores["attention core (full)"]


def test_the_row_of_heads_and_what_it_holds():
    import math

    row = session_heads.HEADS["kexaone"]
    assert row.config == kb.KExaoneConfig() and row.experts == (8, 128)
    assert row.layers == {"conv": 0, "attention": 2, "window": 4, "ssm": 0,
                          "linear": 0, "memory": 0, "cross": 0, "mtp": 1,
                          "dense": 1, "moe": 5}
    assert "mtp" in session_heads.LAYER_KINDS
    assert all(r.layers["mtp"] == 0 for name, r in session_heads.HEADS.items()
               if name != "kexaone")
    leaves = jax.tree.leaves(jax.eval_shape(row.init))
    assert sum(math.prod(a.shape) for a in leaves) == 2_797_518_977
    assert sum(math.prod(a.shape) * a.dtype.itemsize
               for a in leaves) == pytest.approx(5.595e9, rel=1e-3)
    # the cell's window: five layers at 2,048 positions, the module's at one
    assert row.layer_positions(2048) == (5 * 2048 + 1, 6 * 2048)
    # in pairs, by blocks of 512 x 512: four band layers' one visit a query
    # block, 16 slabs of 128 x 256 (2 blocks' area where the sweep visited 7),
    # the full one's 10 of 16, and the module's one query a row meets one
    # row of 4
    assert row.key_blocks(2048) == ((4 * 2 + 10 + 4) * BLOCK, 6 * 16 * BLOCK)
    assert "'kexaone'" in str(pytest.raises(
        ValueError, session_heads.session_head, "kimi").value)


def test_the_server_counts_the_module_once_and_its_blocks(monkeypatch):
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setitem(session_heads.HEADS, "kexaone", dataclasses.replace(
        session_heads.HEADS["kexaone"], init=lambda: None))
    metrics = ServiceMetrics("risk")
    manager = ss.SessionStateManager(8, n_events=2048, head="kexaone",
                                     metrics=metrics)
    assert manager.head_layer_positions == (10241, 12288)
    assert manager.head_key_blocks == (22 * BLOCK, 96 * BLOCK)
    with manager.lock:
        manager.prepare_chunk(ss.group_chunk(["a", "b", "a"]),
                              np.array([100.0, 200.0, 300.0], np.float32),
                              np.array([2, 2, 0], np.int32), 1_700_000_000.0)
    text = metrics.registry.render_text().replace(".0\n", "\n")
    assert "risk_session_head_layer_positions_computed_total 30723" in text
    assert "risk_session_head_layer_positions_whole_total 36864" in text
    assert f"risk_session_head_key_blocks_visited_total {3 * 22 * BLOCK}" in text
    assert f"risk_session_head_key_blocks_square_total {3 * 96 * BLOCK}" in text
    for kind, count in (("window", 4), ("attention", 2), ("dense", 1), ("moe", 5),
                        ("mtp", 1), ("ssm", 0)):
        assert f'risk_session_head_layers{{kind="{kind}"}} {count}' in text
    assert "risk_session_head_experts_held 8" in text


# -- the served path ----------------------------------------------------------------


@pytest.fixture
def small_kexaone(monkeypatch):
    """``SESSION_HEAD=kexaone`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "kexaone", dataclasses.replace(
        session_heads.HEADS["kexaone"],
        scores=lambda sp, win, lp: kb.backbone_scores(sp, win, lp, cfg),
        init=lambda: kb.init_backbone(jax.random.key(11), cfg), config=cfg,
        experts=(cfg.held_experts, cfg.experts),
        layers=session_heads._NO_LAYERS | kb.layer_kinds(cfg),
        key_blocks=lambda window: kb.key_blocks(cfg, window),
        layer_positions=lambda window: kb.layer_positions(cfg, window)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_kexaone, environment):
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
    run = harness.Run(spec, seed=6_500_000_011, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "kexaone"
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
    computed, whole = kb.layer_positions(small_kexaone, EVENTS)
    assert counters["risk_session_head_layer_positions_computed_total"] == computed * rows
    assert counters["risk_session_head_layer_positions_whole_total"] == whole * rows
    visited, square = kb.key_blocks(small_kexaone, EVENTS)
    assert counters["risk_session_head_key_blocks_visited_total"] == visited * rows
    assert counters["risk_session_head_key_blocks_square_total"] == square * rows
    assert snap["head_layers"] == {"conv": 0, "attention": 2, "window": 4,
                                   "ssm": 0, "linear": 0, "memory": 0,
                                   "cross": 0, "mtp": 1, "dense": 1, "moe": 5}
    assert snap["head_cores"]["attention core (window)"].startswith(
        "einsum in query blocks (window 48")
