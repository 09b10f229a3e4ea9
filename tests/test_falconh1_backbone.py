"""The ``falconh1`` session head (models/falconh1_backbone.py) against its
plain reference (chipbench/heads/falcon_h1_34b.py) at a small size on the
CPU, part by part, and through the served session path.

The small size keeps every mechanism: two layers, each a Mamba-2 mixer (4
heads of 32 channels, a state of 16, ``B`` and ``C`` in 2 groups, 4 taps
with a bias, a gate before a grouped norm) beside grouped-query attention
(4 query / 2 key-value heads of 32) on one normed input, then a SwiGLU of
256; hidden 128; the fourteen published muP scalars; 16-event windows of
mixed lengths, seeded weights. The program computes the state-space core in
its dual form over one chunk, the reference runs the recurrence.
"""

from __future__ import annotations

import dataclasses
import copy
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
from igaming_platform_tpu.models import falconh1_backbone as fb  # noqa: E402
from igaming_platform_tpu.models import keye_backbone as kb  # noqa: E402
from igaming_platform_tpu.models import lfm2_backbone as lb  # noqa: E402
from igaming_platform_tpu.models import pangu_backbone as pb  # noqa: E402
from igaming_platform_tpu.models import session_heads  # noqa: E402

CONFIG = "risk-seqhead-falcon-h1-34b"
CELL = "falconh1-ssm-insession"
PUBLISHED = validate.load_source(CONFIG)["config"]
SCALARS = ("embedding_multiplier", "attention_in_multiplier",
           "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
           "ssm_out_multiplier", "lm_head_multiplier")
# every published scalar: a key, and for the two lists the entry
MULTIPLIERS = ([(name, None) for name in SCALARS]
               + [("ssm_multipliers", i) for i in range(5)]
               + [("mlp_multipliers", i) for i in range(2)])


def small_source(**over) -> dict:
    """The small size as a configuration file would state it: the
    source's own keys, its multipliers and switches as published."""
    source = dict(PUBLISHED)
    source.update({
        "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "mamba_n_heads": 4,
        "mamba_d_head": 32, "mamba_d_ssm": 128, "mamba_d_state": 16,
        "mamba_n_groups": 2, "intermediate_size": 256,
        "head": {"published": {"num_hidden_layers": 72}}})
    source.update(over)
    return source


def program_config(source: dict, **over) -> fb.FalconH1Config:
    """The program's configuration of a source's keys."""
    kw = dict(
        hidden=source["hidden_size"], layers=source["num_hidden_layers"],
        heads=source["num_attention_heads"],
        kv_heads=source["num_key_value_heads"], head_dim=source["head_dim"],
        ssm_heads=source["mamba_n_heads"], ssm_head_dim=source["mamba_d_head"],
        ssm_state=source["mamba_d_state"], ssm_groups=source["mamba_n_groups"],
        conv_taps=source["mamba_d_conv"], chunk=source["mamba_chunk_size"],
        dense_width=source["intermediate_size"],
        **{name: float(source[name]) for name in SCALARS},
        ssm_multipliers=tuple(source["ssm_multipliers"]),
        mlp_multipliers=tuple(source["mlp_multipliers"]),
        rope_theta=float(source["rope_theta"]), eps=source["rms_norm_eps"])
    kw.update(over)
    return fb.FalconH1Config(**kw)


def small_config(**over) -> fb.FalconH1Config:
    return program_config(small_source(), **over)


@pytest.fixture(scope="module")
def head():
    return validate.load_code("heads", "falcon_h1_34b")


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
        hid = fb.backbone_hidden(p, w, cfg)
        last = jnp.clip(l - 1, 0, w.shape[1] - 1)
        hl = jnp.take_along_axis(hid, last[:, None, None], axis=1)[:, 0, :]
        logit = jnp.sum(hl * p["head"]["w"][:, 0], -1) * cfg.lm_head_multiplier
        return logit + p["head"]["b"][0], hl

    logit, hl = jax.jit(both)(params, jnp.asarray(x),
                              jnp.asarray(lengths, jnp.int32))
    return np.asarray(logit), np.asarray(hl)


def program_scores(cfg, params, x, lengths):
    return np.asarray(jax.jit(
        lambda p, w, l: fb.backbone_scores(p, w, l, cfg))(
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
    # float32: the order of float32 sums alone, and the dual form's sums
    # against the recurrence's products of decays
    atol = 2e-5 if operands == "float32" else ROUNDING
    np.testing.assert_allclose(logit, want_logit, atol=atol, rtol=0)
    np.testing.assert_allclose(hidden, want_hidden, atol=atol, rtol=0)
    got = program_scores(cfg, tree, x, lens)
    want = head.forward(tree, x, lens, reference.rounder(operands))
    np.testing.assert_allclose(got, want, atol=atol / 4, rtol=0)


def test_float8_operands_fail_what_bfloat16_passes(head, tree):
    """The comparison is tight enough that one precision step down fails
    it: the reference with float8 operands lies further from the bfloat16
    one than the limit the program is held to. The layers are seen: it is
    the branches' products that float8 spoils, not the projector's alone."""
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


def test_the_state_space_core_rounds_no_operand():
    """``operand_dtype`` reaches the projections and not the core: the dual
    form gives the same bits whatever the operands' dtype."""
    x, bm, cm, dt, layer = _core_inputs(16, seed=2)

    def core(operands):
        cfg = small_config(operand_dtype=jnp.dtype(operands))
        return np.asarray(jax.jit(lambda *a: fb.ssd_one_chunk(*a, layer, cfg))(
            x, bm, cm, dt))

    np.testing.assert_array_equal(core("float32"), core("bfloat16"))


@pytest.mark.parametrize("lengths", [1, 4, 9])
def test_positions_after_the_last_real_one_change_nothing(tree, lengths):
    """Blind to padding at the scored position: whatever the positions past
    a window's length hold, the convolution, the state-space core and the
    mask are causal."""
    cfg = small_config()
    x, lens = windows(8, (lengths,))
    junk = x.copy()
    junk[:, lengths:] = np.random.default_rng(1).normal(0, 3, junk[:, lengths:].shape)
    np.testing.assert_array_equal(program_scores(cfg, tree, x, lens),
                                  program_scores(cfg, tree, junk, lens))


def test_tree_of_the_reference_is_the_programs(head, tree):
    """The harness replaces the program's tree by the reference's: one
    structure, shapes and dtypes, so the compiled step is reused. And the
    program's sizes and multipliers are the configuration file's; 1.72 G
    parameters, 3.44 GB at rest."""
    cfg = small_config()
    mine = jax.eval_shape(lambda: fb.init_backbone(jax.random.key(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(tree)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    for layer in tree["layers"]:
        assert layer["w_in"].shape == (128, 2 * 128 + 2 * 2 * 16 + 4)
        for name in ("g1", "g2", "taps", "conv_b", "dt_bias", "a_log", "d_skip", "gn"):
            assert layer[name].dtype == jnp.float32, name
        for name in ("w_in", "w_out", "wq", "wk", "wv", "wo"):
            assert layer[name].dtype == jnp.bfloat16, name
        assert "qn" not in layer and "wr" not in layer  # no head norm, no router
    published = validate.load_data("configs", CONFIG)
    assert program_config(published) == session_heads.HEADS["falconh1"].config
    c = session_heads.HEADS["falconh1"].config
    assert c.init_depth == published["head"]["published"]["num_hidden_layers"] == 72
    assert c.segments == (4096, 4096, 512, 512, 32) and sum(c.segments) == 9248
    d = head.dims_of(published)
    assert d.segments == c.segments and d.chunk == c.chunk == 128
    full = jax.eval_shape(session_heads.HEADS["falconh1"].init)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(full))
    assert n == 1_720_551_809
    nbytes = sum(a.dtype.itemsize * int(np.prod(a.shape))
                 for a in jax.tree.leaves(full))
    assert nbytes == 3_441_444_356
    assert f"{n:,}" in published["head"]["parameters"]
    assert f"{nbytes:,}" in published["head"]["parameters"]


def test_the_seeded_trees_keep_the_streams_scale_through_the_multipliers(tree):
    """The multipliers are applied as published, so each matrix is drawn
    for the one that follows it: through it the product keeps its input's
    variance, and the three projections into the stream add what a layer of
    72 adds. The program's own tree and the reference's, alike."""
    cfg = small_config()
    mine = fb.init_backbone(jax.random.key(5), cfg)
    std = lambda a: float(jnp.std(a.astype(jnp.float32)))
    d, out = cfg.hidden, (2 * cfg.init_depth) ** -0.5
    for params in (mine, tree):
        layer = params["layers"][0]
        at = np.cumsum((0,) + cfg.segments)
        for lo, hi, m in zip(at[:-1], at[1:], cfg.ssm_multipliers):
            got = std(layer["w_in"][:, lo:hi]) * cfg.ssm_in_multiplier * m
            assert got == pytest.approx(d ** -0.5, rel=0.12), (lo, hi)
        assert std(layer["wk"]) * cfg.key_multiplier == pytest.approx(d ** -0.5, rel=0.05)
        assert std(layer["wo"]) * cfg.attention_out_multiplier == pytest.approx(
            (cfg.heads * cfg.head_dim) ** -0.5 * out, rel=0.05)
        assert std(layer["w_out"]) * cfg.ssm_out_multiplier == pytest.approx(
            cfg.ssm_width ** -0.5 * out, rel=0.05)
        mlp = layer["dense"]
        assert std(mlp["wg"]) * cfg.mlp_multipliers[0] == pytest.approx(d ** -0.5, rel=0.05)
        assert std(mlp["wd"]) * cfg.mlp_multipliers[1] == pytest.approx(
            cfg.dense_width ** -0.5 * out, rel=0.05)
        # dt = softplus(dt_bias) in Mamba-2's range, A in its own, D one
        dt = np.log1p(np.exp(np.asarray(layer["dt_bias"], np.float64)))
        assert (dt > 0.9e-3).all() and (dt < 1.1e-1).all()
        a = np.exp(np.asarray(layer["a_log"], np.float64))
        assert (a >= 1).all() and (a <= 16).all()
        np.testing.assert_array_equal(np.asarray(layer["d_skip"]), 1.0)
    # the stream a layer adds to is of unit scale, not the multiplier's 5.66
    x, _ = windows(8, (16,))
    h0 = np.asarray(dp.mm(jnp.asarray(x).reshape(-1, 12), mine["embed"], cfg)
                    * cfg.embedding_multiplier)
    assert 0.5 < h0.std() < 2.0


# -- the state-space core: the dual form against the recurrence -----------------


def _core_inputs(t: int, seed: int = 0, rows: int = 3):
    cfg = small_config()
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (rows, t, cfg.ssm_heads, cfg.ssm_head_dim))
    bm = jax.random.normal(ks[1], (rows, t, cfg.ssm_groups, cfg.ssm_state))
    cm = jax.random.normal(ks[2], (rows, t, cfg.ssm_groups, cfg.ssm_state))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (rows, t, cfg.ssm_heads)) - 1.0)
    layer = {"a_log": jnp.log(jax.random.uniform(ks[4], (cfg.ssm_heads,),
                                                 jnp.float32, 1.0, 16.0)),
             "d_skip": jax.random.normal(ks[5], (cfg.ssm_heads,))}
    return x, bm, cm, dt, layer


def _recurrence_loop(x, bm, cm, dt, layer, groups: int):
    """``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D
    x_t`` from ``H_{-1} = 0``: explicit loops over windows, positions and
    heads, the state held, in float64."""
    x, bm, cm, dt = (np.asarray(a, np.float64) for a in (x, bm, cm, dt))
    a = -np.exp(np.asarray(layer["a_log"], np.float64))
    d_skip = np.asarray(layer["d_skip"], np.float64)
    rows, t, heads, hd = x.shape
    y = np.zeros_like(x)
    for r in range(rows):
        for h in range(heads):
            g = h // (heads // groups)  # head j reads group j // (heads / groups)
            state = np.zeros((hd, bm.shape[-1]))
            for pos in range(t):
                state = (np.exp(dt[r, pos, h] * a[h]) * state
                         + dt[r, pos, h] * np.outer(x[r, pos, h], bm[r, pos, g]))
                y[r, pos, h] = state @ cm[r, pos, g] + d_skip[h] * x[r, pos, h]
    return y


@pytest.mark.parametrize("t", [1, 4, 16])
def test_the_dual_form_equals_the_explicit_recurrence(t):
    cfg = small_config()
    x, bm, cm, dt, layer = _core_inputs(t, seed=t)
    got = np.asarray(jax.jit(lambda *a: fb.ssd_one_chunk(*a, layer, cfg))(
        x, bm, cm, dt))
    want = _recurrence_loop(x, bm, cm, dt, layer, cfg.ssm_groups)
    assert got.shape == want.shape == (3, t, 4, 32)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(), rtol=0)
    if t > 1:
        # the state carries: without it (each position alone) the answer differs
        alone = np.concatenate([_recurrence_loop(
            *(v[:, p:p + 1] for v in (x, bm, cm, dt)), layer, cfg.ssm_groups)
            for p in range(t)], axis=1)
        assert np.abs(alone - want).max() > 0.1 * np.abs(want).max()
        # and it is causal: a later position changes nothing before it
        later = np.asarray(jax.jit(lambda *a: fb.ssd_one_chunk(*a, layer, cfg))(
            x.at[:, t - 1].add(1.0), bm.at[:, t - 1].add(1.0), cm, dt))
        np.testing.assert_array_equal(later[:, :t - 1], got[:, :t - 1])


# -- the mixer's core as the window kernel (ops/pallas/ssd_window.py) ------------


def wide_source() -> dict:
    """The small size with state-space heads and a state as wide as the
    kernel takes them: 2 heads of 128 channels, a state of 128 a channel in
    2 groups."""
    return small_source(mamba_n_heads=2, mamba_d_head=128, mamba_d_ssm=256,
                        mamba_d_state=128)


@pytest.fixture
def ssm_core_by_kernel(monkeypatch, caplog):
    """Runs ``fn`` as a TPU would trace the state-space mixers, the window
    kernel through the Pallas interpreter, and returns what it computed
    with what the state-space core said. Steered here, in the test, and for
    this part alone."""
    import functools

    from igaming_platform_tpu.ops.pallas import ssd_window as sw

    def run(fn):
        dp.announce_core.cache_clear()
        caplog.clear()
        with monkeypatch.context() as m, caplog.at_level("INFO", logger=dp.logger.name):
            m.setattr(fb, "kernel_declines",
                      lambda declines=None: (declines and declines(), "tpu"))
            m.setattr(sw, "ssd_window", functools.partial(
                sw.ssd_window, interpret=True))
            out = fn()
        said = [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("state-space core")]
        dp.announce_core.cache_clear()
        return out, said
    return run


KERNEL_SAYS = ("state-space core: window kernel (tile=128, 2 heads of 128, state "
               "128 in 2 groups, window 16, taps=4, gate and norm inside) "
               "(backend=tpu)")


def test_mixer_through_the_window_kernel_equals_the_mixer_by_xla(
        head, ssm_core_by_kernel):
    """One state-space sublayer on a stream with spread, float32 operands:
    both projections are the same XLA either way, so what differs is the
    order of float32 sums between them."""
    source = wide_source()
    cfg = program_config(source, operand_dtype=jnp.float32)
    layer = head.make_params(7, source)["layers"][1]
    u = jnp.asarray(np.random.default_rng(9).normal(0, 1, (128, 128)), jnp.float32)
    mixer = lambda: np.asarray(jax.jit(
        lambda x: fb.ssm_mixer(x, layer, cfg, 16))(u))
    by_kernel, said = ssm_core_by_kernel(mixer)
    by_xla = mixer()
    assert said == [KERNEL_SAYS]
    assert dp.announced_cores()["state-space core"] == (
        "dual form, one chunk, 16 <= 128 (not a TPU) (backend=cpu)")
    scale = np.abs(by_xla).max()
    assert scale > 1e-3
    np.testing.assert_allclose(by_kernel, by_xla, atol=2e-5 * scale, rtol=0)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_through_the_window_kernel_equals_the_reference(
        head, operands, ssm_core_by_kernel):
    """The whole head with every state-space mixer's core the Pallas kernel
    (8 windows: one tile of 128 positions, mixed lengths) against the plain
    reference's recurrence, under the limits the XLA path meets, and
    against the XLA path itself; the kernel's result leaves in the
    operands' dtype, rounded where ``mm`` rounds the XLA path's."""
    dt = jnp.dtype(operands)
    source = wide_source()
    cfg = program_config(source, operand_dtype=dt)
    params = head.make_params(7, source)
    d = head.dims_of(source)
    x, lens = windows(8, (1, 4, 16, 7, 9, 2), seed=7)
    (logit, hidden), said = ssm_core_by_kernel(
        lambda: program_logits(cfg, params, x, lens))
    assert said == [KERNEL_SAYS]
    want_logit = head._logits(params, x, lens, d, dt)
    want_hidden = head._logits(params, x, lens, d, dt, hidden=True)
    atol = 2e-5 if operands == "float32" else ROUNDING
    np.testing.assert_allclose(logit, want_logit, atol=atol, rtol=0)
    np.testing.assert_allclose(hidden, want_hidden, atol=atol, rtol=0)
    by_xla, _ = program_logits(cfg, params, x, lens)
    np.testing.assert_allclose(logit, by_xla, atol=atol, rtol=0)
    assert np.std(want_logit) > 0.01


@pytest.mark.parametrize("backend,over,window,said", [
    ("cpu", {}, 16, "dual form, one chunk, 16 <= 128 (not a TPU) (backend=cpu)"),
    ("tpu", {}, 16, "window kernel (tile=128, 32 heads of 128, state 256 in 2 "
                    "groups, window 16, taps=4, gate and norm inside) "
                    "(backend=tpu)"),
    ("tpu", {}, 8, "window kernel (tile=128, 32 heads of 128, state 256 in 2 "
                   "groups, window 8, taps=4, gate and norm inside) "
                   "(backend=tpu)"),
    ("tpu", {"ssm_head_dim": 64}, 16,
     "dual form, one chunk, 16 <= 128 (head width 64 is not whole 128-lane "
     "vregs) (backend=tpu)"),
    ("tpu", {"ssm_state": 16}, 16,
     "dual form, one chunk, 16 <= 128 (a state of 16 is not whole 128-lane "
     "vregs) (backend=tpu)"),
    ("tpu", {}, 12, "dual form, one chunk, 12 <= 128 (windows of 12 are not "
                    "whole 8-row vregs that divide a tile of 128) (backend=tpu)"),
], ids=["off-the-tpu", "published", "window8", "head64", "state16", "window12"])
def test_state_space_core_is_announced_with_the_reason_it_declines(
        backend, over, window, said, monkeypatch, caplog):
    """The choice is made while tracing, from the backend and the layer's
    shapes alone; the boot's log line and ``/debug/sessionz``'s
    ``head_cores`` carry it, with the kernel's own reason beside the dual
    form."""
    cfg = fb.FalconH1Config(**over)
    dp.announce_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with caplog.at_level("INFO", logger=dp.logger.name):
        by_kernel = fb._core_is_the_kernel(256 * window, cfg, window)
    assert by_kernel is said.startswith("window kernel")
    assert [r.getMessage() for r in caplog.records] == [
        f"state-space core: {said}"]
    assert dp.announced_cores()["state-space core"] == said
    dp.announce_core.cache_clear()


@pytest.mark.parametrize("rows,takes", [(256, True), (64, True), (6, False)],
                         ids=["256-row-rung", "64-row-rung", "six-windows"])
def test_both_rungs_are_whole_tiles_and_a_part_filled_one_takes_the_einsums(
        rows, takes, monkeypatch):
    """The 64-row rung is 1,024 positions and the 256-row one 4,096: whole
    tiles, the kernel at both. A batch that is not (a test's, 6 windows)
    declines in the kernel's words and runs ``ssd_one_chunk``; a window past
    one chunk is refused whichever core would run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp.announce_core.cache_clear()
    assert fb._core_is_the_kernel(rows * 16, fb.FalconH1Config(), 16) is takes
    said = dp.announced_cores()["state-space core"]
    assert said.startswith("window kernel (tile=128, ") if takes else said == (
        "dual form, one chunk, 16 <= 128 (96 positions are not whole tiles of "
        "128) (backend=tpu)")
    with pytest.raises(ValueError, match="longer than one chunk"):
        fb._core_is_the_kernel(rows * 256, fb.FalconH1Config(), 256)
    dp.announce_core.cache_clear()


def test_the_reference_runs_the_recurrence_the_loop_runs(head):
    """The reference's own scan against the same explicit loop: the two
    sides of the cell's comparison are independent of each other and both
    are the recurrence of the published model."""
    cfg = small_config()
    x, bm, cm, dt, layer = _core_inputs(16, seed=8)
    per = cfg.ssm_heads // cfg.ssm_groups
    with jax.default_matmul_precision("highest"):
        got = np.asarray(head._recurrence(
            x, jnp.repeat(bm, per, axis=2), jnp.repeat(cm, per, axis=2), dt,
            -jnp.exp(layer["a_log"]), layer["d_skip"]))
    want = _recurrence_loop(x, bm, cm, dt, layer, cfg.ssm_groups)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(), rtol=0)


def test_a_window_longer_than_one_chunk_is_refused(tree):
    """One chunk holds no state for a second: a window past
    ``mamba_chunk_size`` is an error, never a silently wrong answer."""
    x, bm, cm, dt, layer = _core_inputs(16)
    with pytest.raises(ValueError, match="longer than one chunk"):
        fb.ssd_one_chunk(x, bm, cm, dt, layer, small_config(chunk=8))
    fb.ssd_one_chunk(x, bm, cm, dt, layer, small_config(chunk=16))
    win, lens = windows(4, (16,))
    with pytest.raises(ValueError, match="mamba_chunk_size 8"):
        program_scores(small_config(chunk=8), tree, win, lens)


# -- the convolution with its bias -----------------------------------------------


def _conv_loop(z, taps, bias):
    """``silu(bias + sum_k w_k z_{t - (L - 1 - k)})`` by explicit loops over
    windows, positions and taps, in float64."""
    z, taps, bias = (np.asarray(a, np.float64) for a in (z, taps, bias))
    rows, t, ch = z.shape
    n_taps = taps.shape[1]
    out = np.zeros_like(z)
    for r in range(rows):
        for pos in range(t):
            acc = bias.copy()
            for k in range(n_taps):
                src = pos - (n_taps - 1 - k)
                if src >= 0:  # zero before the window's first event
                    acc += taps[:, k] * z[r, src]
            out[r, pos] = acc / (1 + np.exp(-acc))
    return out


@pytest.mark.parametrize("taps", [4, 3, 2])
def test_convolution_with_bias_and_silu_equals_an_explicit_loop(taps):
    ks = jax.random.split(jax.random.key(taps), 3)
    z = jax.random.normal(ks[0], (5, 16, 24))
    w = jax.random.normal(ks[1], (24, taps)) * 0.5
    bias = jax.random.normal(ks[2], (24,)) * 0.25
    got = np.asarray(jax.jit(
        lambda z: jax.nn.silu(dp.causal_taps(z, w, bias)))(z))
    want = _conv_loop(z, w, bias)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    # the bias is read, at every position
    bare = np.asarray(jax.jit(lambda z: jax.nn.silu(dp.causal_taps(z, w)))(z))
    assert (np.abs(bare - got).max(axis=(0, 2)) > 0.01).all()


def test_convolution_is_causal_and_each_window_alone(tree):
    """Through the mixer: an event at ``t`` changes nothing before ``t``,
    and a window never reads its neighbour in the batch."""
    cfg = small_config(operand_dtype=jnp.float32)
    layer = tree["layers"][0]
    assert layer["taps"].shape == (128 + 2 * 2 * 16, 4)
    assert float(jnp.abs(layer["conv_b"]).max()) > 0.1
    u = stream(rows=4, seed=9)
    rows, t, hid = u.shape
    mix = jax.jit(lambda u: fb.ssm_mixer(u.reshape(rows * t, hid), layer, cfg,
                                         t).reshape(rows, t, hid))
    base = np.asarray(mix(u))
    moved = np.asarray(mix(u.at[1, 7].add(1.0)))
    changed = np.abs(moved - base).max(-1) > 0
    assert not changed[[0, 2, 3]].any()          # the other windows
    # the taps reach three positions on, the state every later one
    assert changed[1].tolist() == [False] * 7 + [True] * 9
    # with the state's decay at once (A huge) the taps' reach alone is left
    forgetful = dict(layer, a_log=layer["a_log"] + 50.0)
    mix = jax.jit(lambda u: fb.ssm_mixer(u.reshape(rows * t, hid), forgetful,
                                         cfg, t).reshape(rows, t, hid))
    changed = np.abs(np.asarray(mix(u.at[1, 7].add(1.0)))
                     - np.asarray(mix(u))).max(-1) > 0
    assert changed[1].tolist() == [False] * 7 + [True] * 4 + [False] * 5


# -- each branch and the MLP alone -----------------------------------------------


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("part", ["ssm", "attention", "mlp"])
def test_each_branch_and_the_mlp_alone(head, tree, operands, part):
    """``x + S(N(x))``, ``x + A(N(x))`` and ``x + FF(N(x))`` of one layer
    against the reference's; both mixers are causal, attention turns with
    the rotary, and with both on they add up on one normed input."""
    dt = jnp.dtype(operands)
    cfg, d = small_config(operand_dtype=dt), head.dims_of(small_source())
    layer = tree["layers"][1]
    x = stream(seed=5)
    rows, t, hid = x.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (1, rows, t))
    cos, sin = dp.mrope_angles(pos, cfg.head_dim, (cfg.head_dim // 2,),
                               cfg.rope_theta)

    def branch(kind, u, cos, sin):
        if kind == "ssm":
            return fb.ssm_mixer(u, layer, cfg, t)
        return (dp.attention(u * cfg.attention_in_multiplier, layer, cos, sin,
                             cfg, t, key_scale=cfg.key_multiplier)
                * cfg.attention_out_multiplier)

    def sublayer(x, cos, sin, kinds=(part,)):
        h = x.reshape(rows * t, hid)
        if part == "mlp":
            f = dp.rms_norm(h, layer["g2"], cfg.eps)
            o = (dp.swiglu(f, layer["dense"], cfg, cfg.mlp_multipliers[0])
                 * cfg.mlp_multipliers[1])
        else:
            u = dp.rms_norm(h, layer["g1"], cfg.eps)
            o = sum(branch(kind, u, cos, sin) for kind in kinds)
        return (h + o).reshape(x.shape)

    got = np.asarray(jax.jit(sublayer)(x, cos, sin))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(head._mlp(layer, x, d, dt) if part == "mlp"
                          else head._mixers(layer, x, d, dt, part))
    # the stream's values are a few units: bfloat16 operands on a rounding
    # boundary move a channel by 2^-8 of such a value
    atol = 3e-5 if operands == "float32" else 2e-3
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)
    assert np.abs(got - np.asarray(x)).max() > 20 * atol  # the part adds something
    if part == "mlp":
        return
    later = x.at[:, 9:].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(sublayer)(later, cos, sin))[:, :9], got[:, :9])
    if part == "attention":
        still = np.asarray(jax.jit(sublayer)(x, jnp.ones_like(cos),
                                             jnp.zeros_like(sin)))
        assert np.abs(still - got).max() > 1e-4  # the rotary matters
    # two operators on ONE normed input into one add
    both = np.asarray(jax.jit(lambda x, c, s: sublayer(x, c, s, ("ssm", "attention")))(
        x, cos, sin))
    with jax.default_matmul_precision("highest"):
        want_both = np.asarray(head._mixers(layer, x, d, dt))
    np.testing.assert_allclose(both, want_both, atol=atol, rtol=0)


# -- the multipliers ---------------------------------------------------------------


def _with(source: dict, name: str, index, factor: float) -> dict:
    """``source`` with one published scalar times ``factor``."""
    changed = copy.deepcopy(source)
    if index is None:
        changed[name] = source[name] * factor
    else:
        changed[name][index] = source[name][index] * factor
    return changed


@pytest.mark.parametrize("name,index", MULTIPLIERS,
                         ids=[n if i is None else f"{n}[{i}]" for n, i in MULTIPLIERS])
def test_each_multiplier_is_read_by_program_and_reference_alike(head, tree, name,
                                                                index):
    """One scalar changed in the configuration changes the program and the
    reference alike, and by far more than they differ; so a multiplier
    that either side left out (or applied in another place) is caught by
    the comparison with the other."""
    base, changed = small_source(), _with(small_source(), name, index, 1.7)
    x, lens = windows(24, (1, 4, 16, 7, 9, 2), seed=11)
    f32 = jnp.float32
    program = lambda s: program_logits(program_config(s, operand_dtype=f32),
                                       tree, x, lens)
    ref = lambda s, hidden: head._logits(tree, x, lens, head.dims_of(s), f32,
                                         hidden=hidden)
    (logit, hidden), (logit_c, hidden_c) = program(base), program(changed)
    np.testing.assert_allclose(logit_c, ref(changed, False), atol=5e-5, rtol=0)
    np.testing.assert_allclose(hidden_c, ref(changed, True), atol=5e-5, rtol=0)
    # left out of the program (it runs the base) or of the reference
    moved = max(np.abs(logit - ref(changed, False)).max(),
                np.abs(hidden - ref(changed, True)).max())
    assert moved > 1e-3, moved
    assert max(np.abs(logit_c - ref(base, False)).max(),
               np.abs(hidden_c - ref(base, True)).max()) > 1e-3


@pytest.mark.parametrize("name", sorted({n for n, _ in MULTIPLIERS}))
def test_a_multiplier_missing_from_the_configuration_is_refused(head, name):
    source = small_source()
    del source[name]
    with pytest.raises(KeyError, match=name):
        head.dims_of(source)
    with pytest.raises(KeyError, match=name):
        program_config(source)
    assert name in validate.load_data("configs", CONFIG)["source_keys"]


def test_the_multipliers_are_the_published_ones_and_a_short_list_is_refused(head):
    c = session_heads.HEADS["falconh1"].config
    for name, index in MULTIPLIERS:
        mine = getattr(c, name)
        assert (mine if index is None else mine[index]) == (
            PUBLISHED[name] if index is None else PUBLISHED[name][index]), name
    assert len(MULTIPLIERS) == 14
    with pytest.raises(ValueError, match=r"\[z \| x \| B \| C \| dt\]"):
        fb.mup_vector(small_config(ssm_multipliers=(1.0, 1.0, 1.0)))
    with pytest.raises(ValueError, match="one entry a segment"):
        head.dims_of(small_source(ssm_multipliers=[1.0, 1.0, 1.0]))
    m = np.asarray(fb.mup_vector(small_config()))
    assert m.shape == (2 * 128 + 2 * 32 + 4,)
    assert m[0] == np.float32(PUBLISHED["ssm_multipliers"][0])
    assert m[128] == np.float32(PUBLISHED["ssm_multipliers"][1])
    assert m[-1] == np.float32(PUBLISHED["ssm_multipliers"][4])


# -- the widened shared functions are the parents' ---------------------------------


def _body(fn, *args) -> str:
    return "\n".join(line for line in fn.lower(*args).as_text().splitlines()
                     if "func.func" not in line and "module @" not in line)


def _swiglu_of_the_parent(x, w, cfg):
    mid = jax.nn.silu(dp.mm(x, w["wg"], cfg)) * dp.mm(x, w["wu"], cfg)
    return dp.mm(mid, w["wd"], cfg)


def _taps_of_the_parent(z, taps):
    n_taps = taps.shape[1]
    t = z.shape[1]
    c = z * taps[:, n_taps - 1]
    for back in range(1, n_taps):
        earlier = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
        c = c + earlier * taps[:, n_taps - 1 - back]
    return c


def _attention_of_the_parent(u, layer, cos, sin, cfg, window):
    nh, nkv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
    dt, t = cfg.operand_dtype, window
    b = u.shape[0] // t
    q = dp.mm(u, layer["wq"], cfg).reshape(b, t, nh, hd)
    k = dp.mm(u, layer["wk"], cfg).reshape(b, t, nkv, hd)
    v = dp.mm(u, layer["wv"], cfg).reshape(b, t, nkv, hd)
    q = dp.rotate(dp.rms_norm(q, layer["qn"], cfg.eps), cos, sin)
    k = dp.rotate(dp.rms_norm(k, layer["kn"], cfg.eps), cos, sin)
    q = q.reshape(b, t, nkv, nh // nkv, hd)
    sc = jnp.einsum("btgjd,bsgd->bgjts", q.astype(dt), k.astype(dt),
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bgjts,bsgd->btgjd", p.astype(dt), v.astype(dt),
                   preferred_element_type=jnp.float32)
    return dp.mm(o.reshape(b * t, nh * hd), layer["wo"], cfg)


def _score_of_the_parent(params, hid, lengths):
    t = hid.shape[1]
    last = jnp.clip(lengths.astype(jnp.int32) - 1, 0, t - 1)
    hl = jnp.take_along_axis(hid, last[:, None, None], axis=1)[:, 0, :]
    logit = jnp.sum(hl * params["head"]["w"][:, 0], axis=-1) + params["head"]["b"][0]
    return jax.nn.sigmoid(logit)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("shared", ["swiglu", "causal_taps", "attention",
                                    "score_last"])
def test_a_widened_function_without_its_new_argument_is_the_parents_bit_for_bit(
        shared, operands):
    """``swiglu`` without a gate scalar (``pangu``, ``lfm2``), ``causal_taps``
    without a bias and ``attention`` with its head norms (``lfm2``),
    ``score_last`` without a scale (all three backbones): the same values
    and, names aside, the same StableHLO as the parent commit's, written
    out above."""
    dt = jnp.dtype(operands)
    if shared == "swiglu":
        cfg = pb.PanguConfig(hidden=64, layers=2, dense_layers=1, heads=4,
                             q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                             v_dim=16, dense_width=96, experts=16,
                             held_experts=4, top_k=4, expert_width=32,
                             operand_dtype=dt)
        w = pb.init_backbone(jax.random.key(1), cfg)["layers"][0]["dense"]
        args = (jax.random.normal(jax.random.key(2), (48, 64), jnp.float32),)
        now = jax.jit(lambda x: dp.swiglu(x, w, cfg))
        then = jax.jit(lambda x: _swiglu_of_the_parent(x, w, cfg))
    elif shared == "causal_taps":
        taps = jax.random.normal(jax.random.key(1), (32, 3))
        args = (jax.random.normal(jax.random.key(2), (4, 16, 32)).astype(dt)
                .astype(jnp.float32),)
        now = jax.jit(lambda z: dp.causal_taps(z, taps))
        then = jax.jit(lambda z: _taps_of_the_parent(z, taps))
    elif shared == "attention":
        cfg = lb.Lfm2Config(hidden=128, layer_types=("full_attention",),
                            dense_layers=1, heads=4, kv_heads=2, head_dim=32,
                            dense_width=256, operand_dtype=dt)
        layer = lb.init_backbone(jax.random.key(1), cfg)["layers"][0]
        assert "qn" in layer
        pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (1, 3, 16))
        cos, sin = dp.mrope_angles(pos, 32, (16,), cfg.rope_theta)
        args = (jax.random.normal(jax.random.key(2), (48, 128), jnp.float32),
                cos, sin)
        now = jax.jit(lambda u, c, s: dp.attention(u, layer, c, s, cfg, 16))
        then = jax.jit(lambda u, c, s: _attention_of_the_parent(u, layer, c, s,
                                                               cfg, 16))
    else:
        params = {"head": {"w": jax.random.normal(jax.random.key(1), (64, 1)),
                           "b": jnp.full((1,), 0.3)}}
        args = (jax.random.normal(jax.random.key(2), (5, 16, 64)).astype(dt)
                .astype(jnp.float32), jnp.array([1, 4, 16, 9, 0]))
        now = jax.jit(lambda h, l: dp.score_last(params, h, l))
        then = jax.jit(lambda h, l: _score_of_the_parent(params, h, l))
    np.testing.assert_array_equal(np.asarray(now(*args)), np.asarray(then(*args)))
    assert _body(now, *args) == _body(then, *args)


@pytest.mark.parametrize("head_scale", [None, fb.FalconH1Config().lm_head_multiplier])
def test_a_trees_ends_and_angles_are_what_each_backbone_wrote_out(head_scale):
    """``decoder_parts.tree_around`` and ``rope_angles`` (PR 48: what all
    four ``init_backbone``s, and three ``backbone_hidden``s, wrote out alike
    got one owner) give the parent commit's leaves and angles bit for bit:
    the scoring column at ``hidden ** -0.5``, over ``falconh1``'s
    ``lm_head_multiplier`` where it is given, from the same key."""
    f32, d, key = jnp.float32, 64, jax.random.key(5)
    embed, layers = jnp.ones((12, d), jnp.bfloat16), [{"g1": jnp.ones((d,), f32)}]
    over = {} if head_scale is None else {"head_scale": head_scale}
    then = {"embed": embed, "layers": layers, "gf": jnp.ones((d,), f32),
            "head": {"w": jax.random.normal(key, (d, 1), f32)
                     * (1.0 / (np.sqrt(d) * (head_scale or 1.0))),
                     "b": jnp.zeros((1,), f32)}}
    now = dp.tree_around(layers, embed, key, d, **over)
    assert jax.tree.structure(now) == jax.tree.structure(then)
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(then), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(f32)), np.asarray(b.astype(f32)))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (1, 3, 16))
    for a, b in zip(dp.rope_angles(3, 16, 32, 1e6),
                    dp.mrope_angles(pos, 32, (16,), 1e6), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name,sha256", [
    ("keye", "97b106f2039253a0"), ("pangu", "6f25d45683d24dfe"), ("lfm2", "ce10c44bd93b8d8d"),
    ("falconh1", "512502d3a3848a1e")])
def test_the_other_backbones_lower_to_the_parents_stablehlo(name, sha256):
    """The three backbones that share the widened functions, each at a small
    size of its own kinds of layer: the StableHLO of ``backbone_scores`` is
    byte for byte what the parent commit (9b653c8) lowers, held by its
    sha256 (computed there; a later PR that means to change one of them
    says so and replaces the digest). PR 47 meant to change ``keye``'s: its
    stream is [P, hidden] and its core the einsums of ``_core_by_einsums``
    (ec4e13b382434280 before; the scores' bits are held to the 3-D stream's
    in tests/test_keye_backbone.py); ``pangu``'s and ``lfm2``'s stand. PR 48,
    which moved the shared parts into modules no model owns, meant to
    change none: ``falconh1``'s was added at its parent (834d8d8), so all
    four are pinned across the move. PR 51 meant to change ``pangu``'s and
    ``lfm2``'s: ``decoder_parts.route`` chooses by rounds of max-and-mask
    over scores laid experts-first (3b1ad6dc001c49cb and 619bcfbac3323ea6
    before; what it chooses is held to the sorts' bit for bit in
    tests/test_ling_backbone.py); ``keye``'s, with a router of its own,
    and ``falconh1``'s, with none, stand."""
    f32 = jnp.float32
    if name == "keye":
        cfg = kb.BackboneConfig(hidden=128, layers=2, heads=4, kv_heads=2,
                                head_dim=32, experts=8, top_k=2, expert_width=64,
                                idx_heads=2, idx_dim=16, idx_topk=8,
                                mrope_section=(4, 6, 6))
        make, scores = kb.init_backbone, kb.backbone_scores
    elif name == "pangu":
        cfg = pb.PanguConfig(hidden=64, layers=2, dense_layers=1, heads=4,
                             q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                             v_dim=16, dense_width=96, experts=16,
                             held_experts=4, top_k=4, expert_width=32)
        make, scores = pb.init_backbone, pb.backbone_scores
    elif name == "falconh1":
        cfg = small_config()
        make, scores = fb.init_backbone, fb.backbone_scores
    else:
        cfg = lb.Lfm2Config(hidden=128, layer_types=("conv", "full_attention",
                                                     "conv"),
                            dense_layers=1, heads=4, kv_heads=2, head_dim=32,
                            dense_width=256, experts=8, top_k=2, expert_width=64)
        make, scores = lb.init_backbone, lb.backbone_scores
    params = jax.eval_shape(lambda: make(jax.random.key(0), cfg))
    text = jax.jit(lambda p, w, l: scores(p, w, l, cfg)).lower(
        params, jax.ShapeDtypeStruct((6, 16, 12), f32),
        jax.ShapeDtypeStruct((6,), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == sha256


# -- the gauges and the served path -------------------------------------------------


@pytest.fixture
def small_falconh1(monkeypatch):
    """``SESSION_HEAD=falconh1`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "falconh1", dataclasses.replace(
        session_heads.HEADS["falconh1"],
        scores=lambda sp, win, lp: fb.backbone_scores(sp, win, lp, cfg),
        init=lambda: fb.init_backbone(jax.random.key(11), cfg)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


LAYERS = {"conv": 0, "attention": 4, "window": 0, "ssm": 4, "linear": 0, "memory": 0, "cross": 0, "mtp": 0, "dense": 4, "moe": 0}


def test_score_batch_on_the_session_path_equals_the_reference(
        small_falconh1, environment):
    """The new cell's own files, the source's sizes cut to the small one:
    one server, the head through ``serve/index_program.build``, index-mode
    ``ScoreBatch`` over a real socket, every reply against
    ``chipbench/reference.py``; the boot gauges of what the head holds and
    is made of, on ``/metrics`` and ``/debug/sessionz``; the form the
    state-space core runs; the two position counters."""
    spec = copy.deepcopy(validate.load_cell(CELL))
    small = small_source()
    spec["config"]["head"] = dict(spec["config"]["head"], **small.pop("head"))
    spec["config"].update(small)
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    # each core is announced once a process: let this step's trace say it anew
    dp.announce_core.cache_clear()
    run = harness.Run(spec, seed=4_500_000_007, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "falconh1"
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
    # no expert layer: nothing held, nothing routed
    assert (snap["head_experts_held"], snap["head_experts_routed"]) == (0, 0)
    assert session_heads.HEADS["falconh1"].experts == (0, 0)
    assert snap["head_layers"] == LAYERS
    # which form the state-space core said it runs when the step was traced
    assert snap["head_cores"]["state-space core"] == (
        "dual form, one chunk, 16 <= 128 (not a TPU) (backend=cpu)")
    text = text.replace(".0\n", "\n")
    for name, value in (("resident_bytes", resident), ("experts_held", 0),
                        ("experts_routed", 0)):
        assert f"risk_session_head_{name} {value}" in text
    for kind, value in LAYERS.items():
        assert f'risk_session_head_layers{{kind="{kind}"}} {value}' in text


def test_replay_verifies_a_ledger_written_under_the_head(small_falconh1, monkeypatch):
    monkeypatch.setenv("SESSION_HEAD", "falconh1")
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import ledger as ledger_mod
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine
    from tools.replay import replay_directory

    d = tempfile.mkdtemp(prefix="falconh1-replay-test-")
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
        assert eng.session.head == "falconh1"
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
    ("falconh1", {"ssm": 4, "attention": 4, "dense": 4})])
def test_layer_gauge_takes_the_new_kind(name, layers, monkeypatch):
    """``risk_session_head_layers{kind="ssm"}``: a layer of this head counts
    under ``ssm`` AND ``attention`` AND ``dense``; the other heads read 0."""
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve.session_state import SessionStateManager

    assert session_heads.HEADS[name].layers == {
        kind: layers.get(kind, 0) for kind in session_heads.LAYER_KINDS}
    assert "ssm" in session_heads.LAYER_KINDS
    if name == "falconh1":  # the gauge, not a 3.44 GB tree
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
        session_heads.session_head("mamba")
    assert "'falconh1'" in str(err.value) and "'lfm2'" in str(err.value)
    assert all(set(row.layers) == set(session_heads.LAYER_KINDS)
               for row in session_heads.HEADS.values())
    assert fb.layer_kinds(session_heads.HEADS["falconh1"].config) == {
        "ssm": 4, "attention": 4, "dense": 4}


def test_chip_smoke_phase_runs_the_head_against_its_reference():
    """``chip_smoke.phase_backbone(head_name="falconh1")`` at the small size
    on the CPU: the head against its reference, and the form the
    state-space core said it runs; no expert layer, so no expert core."""
    import chip_smoke

    report = chip_smoke.phase_backbone(head_name="falconh1", cfg=small_config(),
                                       config=small_source(), rows=8)
    assert report["max_err"] < 1e-4 and report["rows"] == 8
    assert report["head"] == "falconh1"
    assert report["ssm_core"] == (
        "state-space core: dual form, one chunk, 16 <= 128 (not a TPU) "
        "(backend=cpu)")
    assert report["expert_core"] is None and report["way_back"] is None
    assert report["attention_core"] is None  # this head's core is einsums
    assert report["resident_bytes"] > 0


def test_chip_smoke_expects_the_window_kernel_on_a_tpu(monkeypatch):
    """What ``chip_smoke.BACKBONES`` holds the phase to on a TPU at the
    published widths is what the mixer announces there, on the phase's 32
    windows (four whole tiles)."""
    import chip_smoke

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp.announce_core.cache_clear()
    assert fb._core_is_the_kernel(32 * 16, session_heads.HEADS["falconh1"].config, 16)
    line = chip_smoke.CORE_LINES["ssm_core"]
    assert set(chip_smoke.BACKBONES["falconh1"][3]) == {"ssm_core"}
    assert dp.announced_cores()[line] == (
        f"{chip_smoke.BACKBONES['falconh1'][3]['ssm_core']} (backend=tpu)")
    dp.announce_core.cache_clear()
