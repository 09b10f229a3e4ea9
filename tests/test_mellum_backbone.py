"""The ``mellum`` session head (models/mellum_backbone.py) on the CPU at a
small size, a window several times its ``sliding_window`` (64 over 16):
against its plain reference (chipbench/heads/mellum2_12b_a2_5b.py), the band
and the two rotary tables each shown to matter, and the row of ``HEADS``
with what the server counts from it."""

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
from igaming_platform_tpu.models import mellum_backbone as mb
from igaming_platform_tpu.models import session_heads

CONFIG = "risk-seqhead-mellum2-12b-a2.5b"
CELL = "mellum2-swa-deep4096"
EVENTS, BAND = 64, 16
BLOCK = 512 * 512  # pairs of a block at the cell's window: the counters' unit is pairs
# what a row's probability may differ by: the cell's per-row limit
TOLERANCE = validate.load_data("configs", CONFIG)["limits"]["fraud_prob_max_err"]


def misses(got, stated, exact) -> bool:
    """Whether answers ``got`` miss one of the cell's two limits on the
    probability against the reference at the stated precision: the largest
    error of a row, or the error in units of what the stated rounding
    itself costs (the reference against float32 operands), as
    ``chipbench/reference.merge`` reckons it."""
    limits = validate.load_data("configs", CONFIG)["limits"]
    rms = lambda a: float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
    return bool(np.abs(got - stated).max() > limits["fraud_prob_max_err"]
                or rms(got - stated) / rms(stated - exact)
                > limits["fraud_prob_err_in_roundings"])


def small_config(**changes) -> mb.MellumConfig:
    return dataclasses.replace(mb.MellumConfig(
        hidden=64, heads=4, kv_heads=2, head_dim=16, experts=8, top_k=2,
        expert_width=32, sliding_window=BAND), **changes)


def small_source(events: int = EVENTS) -> dict:
    """The source's keys at the small size: the same layers, switches and
    ``rope_parameters`` as published."""
    source = dict(validate.load_data("configs", CONFIG))
    source.update({
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "sliding_window": BAND,
        "env": dict(source["env"], SESSION_EVENTS=str(events))})
    return source


@pytest.fixture(scope="module")
def small():
    """The reference's seeded tree at the small size (the program's tree has
    its shape: ``backbone_scores`` takes it as it is) and plausible windows,
    every one deeper than the band."""
    head = validate.load_code("heads", "mellum2_12b_a2_5b")
    params = head.make_params(57, small_source())
    win, lengths = head.plausible_windows(np.random.default_rng(57), 24, EVENTS)
    assert lengths.min() >= 2 * BAND
    return head, params, win, lengths


def program(params, win, lengths, cfg=None):
    cfg = cfg or small_config()
    return np.asarray(mb.backbone_scores(params, jnp.asarray(win),
                                         jnp.asarray(lengths, jnp.int32), cfg))


@pytest.fixture(scope="module")
def stated(small):
    head, params, win, lengths = small
    return head.forward(params, win, lengths, reference.rounder("bfloat16"))


@pytest.fixture(scope="module")
def exact(small):
    head, params, win, lengths = small
    return head.forward(params, win, lengths, reference.rounder("float32"))


def test_program_equals_the_reference_at_the_stated_precision(small, stated,
                                                              exact):
    head, params, win, lengths = small
    got = program(params, win, lengths)
    assert got.shape == stated.shape == (24,)
    assert float(np.std(stated)) > 0.05  # the fitted head spreads its answers
    assert not misses(got, stated, exact), np.abs(got - stated)
    # and the reference one precision step down is told apart
    below = head.forward(params, win, lengths,
                         reference.rounder("float8_e4m3fn"))
    assert misses(below, stated, exact)


def test_the_tree_has_the_programs_shape(small):
    _, params, _, _ = small
    pinned = jax.eval_shape(
        lambda: mb.init_backbone(jax.random.key(11), small_config()))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), params)
            == jax.tree.map(lambda a: (a.shape, a.dtype), pinned))


def test_reference_without_the_band_leaves_the_limits(small, stated, exact):
    head, params, win, lengths = small
    head.WITHOUT_BAND = True
    try:
        bandless = head.forward(params, win, lengths,
                                reference.rounder("bfloat16"))
    finally:
        head.WITHOUT_BAND = False
    assert misses(bandless, stated, exact)
    # and the program's sliding layers computed as full ones sit with it
    full = program(params, win, lengths, small_config(sliding_window=10**6))
    assert not misses(full, bandless, exact)
    assert misses(full, stated, exact)


@pytest.mark.parametrize("change,times", [
    (dict(attention_factor=1.0), 10), (dict(yarn_factor=1.0), 3),
    (dict(yarn_factor=1.0, attention_factor=1.0), 10)],
    ids=["no-attention-factor", "plain-rates", "the-sliding-table"])
def test_a_full_layer_given_another_table_is_told_apart(small, stated, change,
                                                        times):
    """The full layer turns by YaRN's rates and scales cos and sin by
    ``attention_factor``; the last case hands it the sliding layers' table.
    One full layer of four over 64 events moves an answer by less than the
    cell's limits at this size, and by many times what separates the program
    from its reference: the comparison resolves the table."""
    _, params, win, lengths = small
    rms = lambda a: float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
    right = rms(program(params, win, lengths) - stated)
    wrong = rms(program(params, win, lengths, small_config(**change)) - stated)
    assert wrong > times * right, (wrong, right)


def test_a_full_layers_table_changes_what_its_attention_adds(small):
    """At the sublayer the table is plain to see: the same layer's attention
    under the two tables differs by far more than a bfloat16 step."""
    _, params, win, _ = small
    cfg = small_config()
    h = jax.random.normal(jax.random.key(3), (2 * EVENTS, cfg.hidden))
    tables = mb.angle_tables(cfg, EVENTS)
    layer = params["layers"][3]
    full = mb.attention(h, layer, mb.FULL, *tables[mb.FULL], cfg, EVENTS)
    slid = mb.attention(h, layer, mb.FULL, *tables[mb.SLIDING], cfg, EVENTS)
    scale = float(jnp.std(full))
    assert float(jnp.max(jnp.abs(full - slid))) > 0.1 * scale


def test_the_two_tables_are_the_sources(small):
    head = small[0]
    cfg = mb.MellumConfig()
    tables = mb.angle_tables(cfg, 4096)
    rope = validate.load_data("configs", CONFIG)["rope_parameters"]
    pos = np.arange(4096)[:, None]
    for kind in (mb.SLIDING, mb.FULL):
        rates, factor = head.inv_freq(rope[kind], 128)
        ang = (pos * rates.astype(np.float32)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(tables[kind][0]),
                                   np.cos(ang) * factor, atol=2e-3)
        np.testing.assert_allclose(np.asarray(tables[kind][1]),
                                   np.sin(ang) * factor, atol=2e-3)
    plain, one = head.inv_freq(rope[mb.SLIDING], 128)
    yarn, factor = head.inv_freq(rope[mb.FULL], 128)
    assert one == 1.0 and factor == 1.2772588722239782
    np.testing.assert_allclose(plain, 5e5 ** (-np.arange(64) / 64.0))
    # fast pairs turn as they did, slow ones sixteen times slower, a ramp between
    assert (yarn[:19] == plain[:19]).all()
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16)
    assert ((yarn[19:35] < plain[19:35]) & (yarn[19:35] > plain[19:35] / 16)).all()


def test_a_window_inside_the_band_is_full_attention(small):
    """At 16 events the band of 16 keeps every causal key: the same weights
    with the band taken away answer bit for bit the same."""
    _, params, win, lengths = small
    win16, len16 = win[:, :BAND], np.minimum(lengths, BAND)
    banded = program(params, win16, len16)
    full = program(params, win16, len16, small_config(sliding_window=10**6))
    assert (banded == full).all()
    assert (program(params, win, lengths)
            != program(params, win, lengths,
                       small_config(sliding_window=10**6))).any()


def test_padding_cannot_reach_the_score(small):
    _, params, win, lengths = small
    noisy = win.copy()
    tail = np.arange(EVENTS)[None, :] >= lengths[:, None]
    noisy[tail] = 7.0
    assert (program(params, noisy, lengths) == program(params, win, lengths)).all()


def test_layer_types_are_read_not_assumed(small):
    _, params, win, lengths = small
    cfg = small_config(layer_types=(mb.FULL, mb.SLIDING, mb.SLIDING, mb.SLIDING))
    assert mb.layer_kinds(cfg) == {"window": 3, "attention": 1, "moe": 4}
    assert (program(params, win, lengths, cfg)
            != program(params, win, lengths)).any()
    two = small_config(layer_types=(mb.SLIDING, mb.FULL))
    assert mb.layer_kinds(two) == {"window": 1, "attention": 1, "moe": 2}
    with pytest.raises(ValueError):
        mb.backbone_scores(params, jnp.asarray(win), jnp.asarray(lengths), two)


def test_the_scopes_and_the_cores_said(small):
    _, params, win, lengths = small
    dp.announce_core.cache_clear()
    text = jax.jit(lambda p, w, n: mb.backbone_scores(p, w, n, small_config())
                   ).lower(params, win, lengths).as_text(debug_info=True)
    for scope in ("head/embed", "head/attn/window/core", "head/attn/full/core",
                  "head/moe/route", "head/moe/experts"):
        assert scope in text, scope
    cores = dp.announced_cores()
    assert cores["attention core (window)"] == (
        "einsum in query blocks (window 64 in blocks of 64, band=16: 1 of 1 "
        "key blocks; not a TPU) (backend=cpu)")
    assert cores["attention core (full)"].startswith(
        "einsum in query blocks (window 64 in blocks of 64, band=None")


def test_the_row_of_heads_and_what_it_holds():
    import math

    row = session_heads.HEADS["mellum"]
    cfg = row.config
    assert cfg.layer_types == (mb.SLIDING,) * 3 + (mb.FULL,)
    assert row.experts == (64, 64)
    assert row.layers == {"conv": 0, "attention": 1, "window": 3, "ssm": 0,
                          "linear": 0, "memory": 0, "cross": 0, "mtp": 0, "dense": 0, "moe": 4}
    full = jax.eval_shape(row.init)
    leaves = jax.tree.leaves(full)
    assert sum(math.prod(a.shape) for a in leaves) == pytest.approx(1.671e9, rel=1e-3)
    assert sum(math.prod(a.shape) * a.dtype.itemsize
               for a in leaves) == pytest.approx(3.342e9, rel=1e-3)
    # the cell's window at the kernel's block: 3 x 21 + 36 of 4 x 64 blocks
    # (in pairs: the blocks' area)
    assert row.key_blocks(4096) == (99 * BLOCK, 256 * BLOCK)
    assert row.key_blocks(16) == (4 * 256, 4 * 256)  # inside the band nothing is skipped
    assert all(r.key_blocks is None for name, r in session_heads.HEADS.items()
               if name not in ("mellum", "phi4flash", "kexaone", "longcat"))
    with pytest.raises(ValueError) as err:
        session_heads.session_head("kimi")
    assert "'mellum'" in str(err.value)


def test_the_server_counts_key_blocks_a_scored_row(monkeypatch):
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setitem(session_heads.HEADS, "mellum", dataclasses.replace(
        session_heads.HEADS["mellum"], init=lambda: None))
    metrics = ServiceMetrics("risk")
    manager = ss.SessionStateManager(8, n_events=4096, head="mellum",
                                     metrics=metrics)
    assert manager.head_key_blocks == (99 * BLOCK, 256 * BLOCK)
    with manager.lock:
        manager.prepare_chunk(ss.group_chunk(["a", "b", "a"]),
                              np.array([100.0, 200.0, 300.0], np.float32),
                              np.array([2, 2, 0], np.int32), 1_700_000_000.0)
    text = metrics.registry.render_text().replace(".0\n", "\n")
    assert f"risk_session_head_key_blocks_visited_total {297 * BLOCK}" in text
    assert f"risk_session_head_key_blocks_square_total {768 * BLOCK}" in text
    assert 'risk_session_head_layers{kind="window"} 3' in text
    # a head that sweeps no blocks counts none
    plain = ServiceMetrics("risk")
    other = ss.SessionStateManager(8, head="pattern", metrics=plain)
    with other.lock:
        other.prepare_chunk(ss.group_chunk(["a"]), np.array([1.0], np.float32),
                            np.array([2], np.int32), 1_700_000_000.0)
    assert "key_blocks_visited_total 0" in plain.registry.render_text().replace(
        ".0\n", "\n") or "key_blocks_visited_total{" not in plain.registry.render_text()


# -- the served path ----------------------------------------------------------------


@pytest.fixture
def small_mellum(monkeypatch):
    """``SESSION_HEAD=mellum`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "mellum", dataclasses.replace(
        session_heads.HEADS["mellum"],
        scores=lambda sp, win, lp: mb.backbone_scores(sp, win, lp, cfg),
        init=lambda: mb.init_backbone(jax.random.key(11), cfg),
        config=cfg, experts=(cfg.experts, cfg.experts),
        key_blocks=lambda window: mb.key_blocks(cfg, window)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_mellum, environment):
    """The new cell's own files, the source's sizes cut to the small one and
    windows of 64 events preloaded 32 to 96 deep: one server, the head
    through ``serve/index_program.build`` at ``BATCH_SIZE=2``, index-mode
    frames of 2 and 8 rows over a real socket (an 8-row frame is four
    launches), every reply against ``chipbench/reference.py`` and the
    control told apart; the boot gauges and the two key-block counters on
    ``/metrics``."""
    spec = copy.deepcopy(validate.load_cell(CELL))
    spec["config"].update({k: v for k, v in small_source().items()
                           if k not in ("env", "head")})
    spec["config"]["env"].update(FEATURE_STORE="python", SESSION_EVENTS=str(EVENTS),
                                 DEVICE_STEP_DEADLINE_S="600")
    spec["config"]["session_events_preloaded"] = {"events": "32-96", "rounds": 4}
    dp.announce_core.cache_clear()
    run = harness.Run(spec, seed=5_700_000_011, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "mellum"
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
    assert counters["risk_session_head_positions_total"] == EVENTS * numbers["rows"]
    real = counters["risk_session_head_real_positions_total"]
    assert EVENTS // 2 * numbers["rows"] < real <= EVENTS * numbers["rows"]
    visited, square = mb.key_blocks(small_mellum, EVENTS)
    assert counters["risk_session_head_key_blocks_visited_total"] == visited * numbers["rows"]
    assert counters["risk_session_head_key_blocks_square_total"] == square * numbers["rows"]
    assert snap["head_layers"] == {"conv": 0, "attention": 1, "window": 3,
                                   "ssm": 0, "linear": 0, "memory": 0, "cross": 0, "mtp": 0, "dense": 0, "moe": 4}
    assert snap["head_cores"]["attention core (window)"].startswith(
        "einsum in query blocks (window 64 in blocks of 64, band=16")
    assert snap["head_cores"]["expert core"] == "xla-ragged-dot (backend=cpu)"


def test_chip_smoke_phase_runs_the_head_against_its_reference():
    """``chip_smoke.phase_backbone(head_name="mellum")`` at the small size on
    the CPU, windows of 64 events over a band of 16: the head against its
    reference, and the core each kind of layer said it runs."""
    import chip_smoke

    report = chip_smoke.phase_backbone(head_name="mellum", cfg=small_config(),
                                       config=small_source(), rows=4,
                                       events=EVENTS)
    assert report["max_err"] < 1e-3 and report["rows"] == 4
    assert report["window_core"].startswith(
        "attention core (window): einsum in query blocks (window 64 in blocks "
        "of 64, band=16")
    assert report["full_core"].startswith(
        "attention core (full): einsum in query blocks (window 64 in blocks of "
        "64, band=None")
    assert report["expert_core"] == "expert core: xla-ragged-dot (backend=cpu)"
    assert report["way_back"] == "combine: xla-gather (backend=cpu)"
    assert report["attention_core"] is None
    assert set(chip_smoke.BACKBONES["mellum"][3]) == {
        "window_core", "full_core", "expert_core", "way_back"}
