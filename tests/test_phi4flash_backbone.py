"""The ``phi4flash`` session head (models/phi4flash_backbone.py) on the CPU
at a small size, windows several times its ``sliding_window`` and several
chunks of its scan: against its plain reference
(chipbench/heads/phi4_mini_flash.py, which runs EVERY layer at EVERY
position), the narrowed second half against the all-positions pass, each
mechanism shown to matter, and the row of ``HEADS`` with what the server
counts from it."""

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
from igaming_platform_tpu.models import phi4flash_backbone as pb
from igaming_platform_tpu.models import session_heads

CONFIG = "risk-seqhead-phi-4-mini-flash"
CELL = "phi4flash-yoco-deep2048"
EVENTS, BAND, CHUNK = 24, 8, 8
BLOCK = 512 * 512  # pairs of a block at the cell's window: the counters' unit is pairs


def misses(got, stated, exact) -> bool:
    """Whether answers ``got`` miss one of the cell's two limits on the
    probability against the reference at the stated precision, as
    ``chipbench/reference.merge`` reckons them."""
    limits = validate.load_data("configs", CONFIG)["limits"]
    rms = lambda a: float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))
    return bool(np.abs(got - stated).max() > limits["fraud_prob_max_err"]
                or rms(got - stated) / rms(stated - exact)
                > limits["fraud_prob_err_in_roundings"])


def small_config(layers: int = 8, **changes) -> pb.Phi4FlashConfig:
    return dataclasses.replace(pb.Phi4FlashConfig(
        hidden=64, layers=layers, heads=8, kv_heads=4, dense_width=128,
        sliding_window=BAND, scan_chunk=CHUNK), **changes)


def small_source(layers: int = 8, events: int = EVENTS) -> dict:
    """The source's keys at the small size: the same rule over the layers."""
    source = dict(validate.load_data("configs", CONFIG))
    source.update({
        "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 4,
        "intermediate_size": 128, "sliding_window": BAND,
        "num_hidden_layers": layers,
        "env": dict(source["env"], SESSION_EVENTS=str(events))})
    return source


def sample(layers: int = 8, events: int = EVENTS, rows: int = 16, seed: int = 59):
    head = validate.load_code("heads", "phi4_mini_flash")
    params = head.make_params(seed, small_source(layers, events))
    win, lengths = head.plausible_windows(np.random.default_rng(seed), rows, events)
    return head, params, win, lengths


@pytest.fixture(scope="module")
def small():
    """The reference's seeded tree at the small size (the program's tree has
    its shape) and plausible windows, half full to full: three bands and
    three chunks deep, every length short of ``T`` but the full ones."""
    return sample()


def program(params, win, lengths, cfg=None):
    cfg = cfg or small_config()
    return np.asarray(pb.backbone_scores(params, jnp.asarray(win),
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


@pytest.mark.parametrize("layers,events", [(8, EVENTS), (32, EVENTS), (8, BAND),
                                           (8, BAND - 2), (12, 2 * BAND + 3)],
                         ids=["8-layers-3-bands", "32-layers", "the-band",
                              "inside-the-band", "12-layers-ragged"])
def test_program_equals_the_reference_at_the_stated_precision(layers, events):
    """The same layer rule at 8, 12 and 32 layers; windows shorter than,
    equal to and longer than the band; lengths short of ``T``."""
    head, params, win, lengths = sample(layers, events, seed=59 + layers + events)
    assert lengths.min() < events
    cfg = small_config(layers)
    got = program(params, win, lengths, cfg)
    want = by_reference(head, params, win, lengths)
    f32 = by_reference(head, params, win, lengths, dtype="float32")
    assert got.shape == want.shape == (16,)
    assert float(np.std(want)) > 0.05  # the fitted head spreads its answers
    assert not misses(got, want, f32), np.abs(got - want)
    below = by_reference(head, params, win, lengths, dtype="float8_e4m3fn")
    assert misses(below, want, f32)


def test_the_narrowed_second_half_equals_the_all_positions_pass(small, stated,
                                                                exact):
    """``backbone_scores`` runs layers past ``L/2 + 1`` (and that layer but
    its keys and values) at each row's last real position; ``backbone_hidden``
    and the reference run every layer at every position."""
    _, params, win, lengths = small
    cfg = small_config()
    whole = pb.backbone_hidden(params, jnp.asarray(win), cfg)
    assert whole.shape == (16, EVENTS, 64)
    wide = np.asarray(dp.score_last(params, whole, jnp.asarray(lengths)))
    narrow = program(params, win, lengths)
    assert np.abs(narrow - wide).max() < 2e-3
    assert not misses(narrow, stated, exact)
    assert not misses(wide, stated, exact)
    assert pb.layer_positions(cfg, EVENTS) == (5 * EVENTS + 3, 8 * EVENTS)


def test_the_tree_has_the_programs_shape(small):
    _, params, _, _ = small
    pinned = jax.eval_shape(
        lambda: pb.init_backbone(jax.random.key(11), small_config()))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), params)
            == jax.tree.map(lambda a: (a.shape, a.dtype), pinned))
    seeded = pb.init_backbone(jax.random.key(11), small_config())
    _, _, win, lengths = small
    assert np.isfinite(program(seeded, win, lengths)).all()


@pytest.mark.parametrize("switch,change", [
    ({"WITHOUT_BAND": True}, {"sliding_window": 10**6}),
    ({"FORGET_EVERY": CHUNK}, None),
    ({"LAMBDA_ZERO": True}, None)],
    ids=["no-band", "state-forgotten-at-every-chunk", "lambda-zero"])
def test_a_reference_with_one_mechanism_knocked_out_leaves_the_limits(
        small, stated, exact, switch, change):
    head, params, win, lengths = small
    knocked = by_reference(head, params, win, lengths, **switch)
    assert misses(knocked, stated, exact)
    if change:  # and the program changed the same way sits with it
        same = program(params, win, lengths, small_config(**change))
        assert not misses(same, knocked, exact) and misses(same, stated, exact)


def _silenced(params, index: int):
    """The tree with layer ``index`` adding nothing to the stream: what it
    exports is then all that is left of it."""
    layers = list(params["layers"])
    layer = dict(layers[index])
    for name in ("w_out", "wo", "bo"):
        if name in layer:
            layer[name] = jnp.zeros_like(layer[name])
    layer["dense"] = dict(layer["dense"],
                          wd=jnp.zeros_like(layer["dense"]["wd"]))
    layers[index] = layer
    return dict(params, layers=layers)


def _with(params, index: int, **leaves):
    layers = list(params["layers"])
    layers[index] = dict(layers[index], **leaves)
    return dict(params, layers=layers)


def test_a_memory_unit_reads_the_memory_and_a_cross_layer_the_keys(small):
    """Layer ``L/2`` silenced in the stream, a change to its scan moves the
    score through the memory alone, and not at all once the Gated Memory
    Units are silenced too; likewise layer ``L/2 + 1``'s keys and values and
    the cross layers."""
    _, params, win, lengths = small
    cfg = small_config()
    kinds = pb.kinds_of(cfg)
    assert kinds == ("ssm", "window", "ssm", "window", "ssm", "attention",
                     "memory", "cross")
    half = cfg.half
    quiet = _silenced(params, half)
    skip = quiet["layers"][half]["d_skip"]
    moved = _with(quiet, half, d_skip=skip * 1.5)
    assert np.abs(program(moved, win, lengths)
                  - program(quiet, win, lengths)).max() > 1e-3
    deaf = _silenced(quiet, kinds.index("memory"))
    deaf_moved = _with(deaf, half, d_skip=skip * 1.5)
    assert (program(deaf_moved, win, lengths) == program(deaf, win, lengths)).all()

    quiet = _silenced(params, half + 1)
    wqkv = quiet["layers"][half + 1]["wqkv"]
    other = wqkv.at[:, cfg.hidden:].multiply(-1)   # the k and v columns
    moved = _with(quiet, half + 1, wqkv=other)
    assert np.abs(program(moved, win, lengths)
                  - program(quiet, win, lengths)).max() > 1e-3
    deaf = _silenced(quiet, kinds.index("cross"))
    deaf_moved = _with(deaf, half + 1, wqkv=other)
    assert (program(deaf_moved, win, lengths) == program(deaf, win, lengths)).all()


def test_padding_cannot_reach_the_score(small):
    _, params, win, lengths = small
    noisy = win.copy()
    tail = np.arange(EVENTS)[None, :] >= lengths[:, None]
    noisy[tail] = 7.0
    assert (program(params, noisy, lengths) == program(params, win, lengths)).all()
    # a padded row of the batch (length 0) scores something finite
    assert np.isfinite(program(params, win, np.zeros_like(lengths))).all()


def test_the_rule_over_the_layer_index_is_the_sources():
    cfg = pb.Phi4FlashConfig()
    kinds = pb.kinds_of(cfg)
    assert [i for i, k in enumerate(kinds) if k == "ssm"] == list(range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == "window"] == list(range(1, 16, 2))
    assert kinds[17] == "attention"
    assert [i for i, k in enumerate(kinds) if k == "memory"] == list(range(18, 32, 2))
    assert [i for i, k in enumerate(kinds) if k == "cross"] == list(range(19, 32, 2))
    assert pb.layer_kinds(cfg) == {"ssm": 9, "window": 8, "attention": 1,
                                   "memory": 7, "cross": 7, "dense": 32}
    head = validate.load_code("heads", "phi4_mini_flash")
    assert head.dims_of(validate.load_data("configs", CONFIG)).kinds == kinds
    assert pb.lambda_init(17) == head.lambda_init(17) == pytest.approx(0.7963, abs=1e-4)
    assert (cfg.head_dim, cfg.ssm_width, cfg.dt_rank, cfg.kv_width) == (64, 5120, 160, 1280)
    with pytest.raises(ValueError):
        pb.Phi4FlashConfig(layers=6)


def test_the_scopes_and_the_cores_said(small):
    _, params, win, lengths = small
    dp.announce_core.cache_clear()
    text = jax.jit(lambda p, w, n: pb.backbone_scores(p, w, n, small_config())
                   ).lower(params, win, lengths).as_text(debug_info=True)
    for scope in ("head/embed", "head/ssm/in", "head/ssm/conv", "head/ssm/scan",
                  "head/ssm/out", "head/attn/window/core", "head/attn/full",
                  "head/mlp/dense", "head/cross/gmu", "head/cross/attn/core",
                  "head/cross/mlp", "head/score"):
        assert scope in text, scope
    cores = dp.announced_cores()
    assert cores["state-space core"] == (
        "chunks of 8 that hand the state on (128 channels, state 16, window "
        "24; not a TPU) (backend=cpu)")
    assert cores["attention core (window)"].startswith(
        "einsum in query blocks (differential, 8/4 of 8, values of 16; window "
        "24 in blocks of 32, band=8: 1 of 1 key blocks")
    assert "band=None" in cores["attention core (full)"]


def test_the_row_of_heads_and_what_it_holds():
    import math

    row = session_heads.HEADS["phi4flash"]
    assert row.config == pb.Phi4FlashConfig() and row.experts == (0, 0)
    assert row.layers == {"conv": 0, "attention": 1, "window": 8, "ssm": 9,
                          "linear": 0, "memory": 7, "cross": 7, "mtp": 0,
                          "dense": 32, "moe": 0}
    full = jax.eval_shape(row.init)
    leaves = jax.tree.leaves(full)
    assert sum(math.prod(a.shape) for a in leaves) == pytest.approx(3.340e9, rel=1e-3)
    assert sum(math.prod(a.shape) * a.dtype.itemsize
               for a in leaves) == pytest.approx(6.68e9, rel=2e-3)
    # the cell's window: layers 0-16 at 2,048 positions, 17-31 at one
    assert row.layer_positions(2048) == (17 * 2048 + 15, 32 * 2048)
    assert 17 * 2048 + 15 == pytest.approx(0.5315 * 32 * 2048, rel=1e-3)
    # eight band layers of 7 of 16 blocks, and the full layer's one query a
    # row meets one row of 4
    assert row.key_blocks(2048) == ((8 * 7 + 4) * BLOCK, 9 * 16 * BLOCK)
    assert all(r.layer_positions is None
               for name, r in session_heads.HEADS.items()
               if name not in ("phi4flash", "kexaone", "longcat"))
    assert "'phi4flash'" in str(pytest.raises(
        ValueError, session_heads.session_head, "kimi").value)


def test_the_server_counts_layer_positions_a_scored_row(monkeypatch):
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setitem(session_heads.HEADS, "phi4flash", dataclasses.replace(
        session_heads.HEADS["phi4flash"], init=lambda: None))
    metrics = ServiceMetrics("risk")
    manager = ss.SessionStateManager(8, n_events=2048, head="phi4flash",
                                     metrics=metrics)
    assert manager.head_layer_positions == (34831, 65536)
    with manager.lock:
        manager.prepare_chunk(ss.group_chunk(["a", "b", "a"]),
                              np.array([100.0, 200.0, 300.0], np.float32),
                              np.array([2, 2, 0], np.int32), 1_700_000_000.0)
    text = metrics.registry.render_text().replace(".0\n", "\n")
    assert "risk_session_head_layer_positions_computed_total 104493" in text
    assert "risk_session_head_layer_positions_whole_total 196608" in text
    assert f"risk_session_head_key_blocks_visited_total {180 * BLOCK}" in text
    for kind, count in (("ssm", 9), ("window", 8), ("attention", 1),
                        ("memory", 7), ("cross", 7), ("dense", 32), ("moe", 0)):
        assert f'risk_session_head_layers{{kind="{kind}"}} {count}' in text
    # a head whose every layer runs at every position counts none
    plain = ServiceMetrics("risk")
    other = ss.SessionStateManager(8, head="pattern", metrics=plain)
    assert other.head_layer_positions == (0, 0)
    with other.lock:
        other.prepare_chunk(ss.group_chunk(["a"]), np.array([1.0], np.float32),
                            np.array([2], np.int32), 1_700_000_000.0)
    text = plain.registry.render_text().replace(".0\n", "\n")
    assert ("layer_positions_whole_total 0" in text
            or "layer_positions_whole_total{" not in text)


# -- the served path ----------------------------------------------------------------


@pytest.fixture
def small_phi4flash(monkeypatch):
    """``SESSION_HEAD=phi4flash`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "phi4flash", dataclasses.replace(
        session_heads.HEADS["phi4flash"],
        scores=lambda sp, win, lp: pb.backbone_scores(sp, win, lp, cfg),
        init=lambda: pb.init_backbone(jax.random.key(11), cfg), config=cfg,
        layers=session_heads._NO_LAYERS | pb.layer_kinds(cfg),
        key_blocks=lambda window: pb.key_blocks(cfg, window),
        layer_positions=lambda window: pb.layer_positions(cfg, window)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_phi4flash, environment):
    """The new cell's own files, the source's sizes cut to the small one and
    windows of 24 events preloaded 12 to 36 deep: one server, the head
    through ``serve/index_program.build`` at ``BATCH_SIZE=2``, index-mode
    frames of 2 and 8 rows over a real socket (an 8-row frame is four
    launches), every reply against ``chipbench/reference.py`` and the control
    told apart; the boot gauges and the counters on ``/metrics``."""
    spec = copy.deepcopy(validate.load_cell(CELL))
    spec["config"].update({k: v for k, v in small_source().items()
                           if k not in ("env", "head")})
    spec["config"]["env"].update(FEATURE_STORE="python", SESSION_EVENTS=str(EVENTS),
                                 DEVICE_STEP_DEADLINE_S="600")
    spec["config"]["session_events_preloaded"] = {"events": "12-36", "rounds": 4}
    dp.announce_core.cache_clear()
    run = harness.Run(spec, seed=5_900_000_011, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "phi4flash"
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
    computed, whole = pb.layer_positions(small_phi4flash, EVENTS)
    assert counters["risk_session_head_layer_positions_computed_total"] == computed * rows
    assert counters["risk_session_head_layer_positions_whole_total"] == whole * rows
    visited, square = pb.key_blocks(small_phi4flash, EVENTS)
    assert counters["risk_session_head_key_blocks_visited_total"] == visited * rows
    assert counters["risk_session_head_key_blocks_square_total"] == square * rows
    assert snap["head_layers"] == {"conv": 0, "attention": 1, "window": 2,
                                   "ssm": 3, "linear": 0, "memory": 1,
                                   "cross": 1, "mtp": 0, "dense": 8, "moe": 0}
    assert snap["head_cores"]["state-space core"].startswith(
        "chunks of 8 that hand the state on")


def test_chip_smoke_phase_runs_the_head_against_its_reference():
    """``chip_smoke.phase_backbone(head_name="phi4flash")`` at the small size
    on the CPU: the head against its reference, and the cores it said."""
    import chip_smoke

    report = chip_smoke.phase_backbone(head_name="phi4flash", cfg=small_config(),
                                       config=small_source(), rows=4,
                                       events=EVENTS)
    assert report["max_err"] < 2e-3 and report["rows"] == 4
    assert report["ssm_core"].startswith(
        "state-space core: chunks of 8 that hand the state on")
    assert report["window_core"].startswith(
        "attention core (window): einsum in query blocks (differential")
    assert report["full_core"].startswith(
        "attention core (full): einsum in query blocks (differential")
    assert report["expert_core"] is None and report["attention_core"] is None
    assert set(chip_smoke.BACKBONES["phi4flash"][3]) == {
        "ssm_core", "window_core", "full_core"}
    assert chip_smoke.BACKBONES["phi4flash"][3]["window_core"].startswith(
        "einsum in query blocks (differential, 40/20 of 64, values of 128; "
        "window 2048 in blocks of 512, band=512: 7 of 16 key blocks")
