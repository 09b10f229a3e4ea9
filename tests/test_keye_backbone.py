"""The ``keye`` session head (models/keye_backbone.py) against its plain
reference (chipbench/heads/keye_vl2.py) at a small size on the CPU, part
by part, and through the served session path.

The small size keeps every mechanism: 2 layers, hidden 64, 8 experts with
2 a token, 4 query / 2 key-value heads of 16, an indexer of 2 heads of 8
whose ``topk`` 4 is UNDER the window's 16 keys, so the selection prunes.
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
from igaming_platform_tpu.models import keye_backbone as kb  # noqa: E402
from igaming_platform_tpu.models import session_heads  # noqa: E402

SMALL_SOURCE = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
    "num_local_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 8, "topk": 4},
    "rope_scaling": {"mrope_section": [2, 3, 3]},
    "rope_theta": 1e7, "rms_norm_eps": 1e-6,
}


def small_config(**over) -> kb.BackboneConfig:
    kw = dict(hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, experts=8,
              top_k=2, expert_width=32, idx_heads=2, idx_dim=8, idx_topk=4,
              mrope_section=(2, 3, 3))
    kw.update(over)
    return kb.BackboneConfig(**kw)


@pytest.fixture(scope="module")
def head():
    return validate.load_code("heads", "keye_vl2")


def windows(n: int, lengths, seed: int = 0):
    rng = np.random.default_rng(seed)
    lengths = np.resize(np.asarray(lengths), n)
    x = rng.normal(0, 1, (n, 16, 12)).astype(np.float32)
    x *= (np.arange(16)[None, :] < lengths[:, None])[..., None]
    return x, lengths


def program_scores(cfg, params, x, lengths):
    return np.asarray(jax.jit(
        lambda p, w, l: kb.backbone_scores(p, w, l, cfg))(
            params, jnp.asarray(x), jnp.asarray(lengths, jnp.int32)))


# -- the whole head ------------------------------------------------------------


@pytest.mark.parametrize("lengths", [(1,), (4,), (16,), (1, 4, 16, 7, 9, 2)],
                         ids=["len1", "len4", "len16", "mixed"])
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_equals_the_reference(head, operands, lengths):
    cfg = small_config(operand_dtype=jnp.dtype(operands))
    params = head.make_params(7, SMALL_SOURCE)
    x, lens = windows(24, lengths, seed=len(lengths))
    got = program_scores(cfg, params, x, lens)
    want = head.forward(params, x, lens, reference.rounder(operands))
    assert got.shape == want.shape == (24,)
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=0)
    if max(lengths) > 4:
        # the selection prunes here: keeping every key reads differently
        loose = program_scores(small_config(
            operand_dtype=jnp.dtype(operands), idx_topk=2048), params, x, lens)
        assert np.abs(loose - got).max() > 1e-4


def test_rounding_is_where_the_reference_puts_it(head):
    """bfloat16 operands read differently from float32 ones (so the case
    above compares two roundings, not one arithmetic twice)."""
    params = head.make_params(7, SMALL_SOURCE)
    x, lens = windows(24, (16,))
    a = program_scores(small_config(operand_dtype=jnp.float32), params, x, lens)
    b = program_scores(small_config(operand_dtype=jnp.bfloat16), params, x, lens)
    diff = np.abs(a - b)
    # one row may flip an expert or a kept key and read far off; most do not
    assert diff.max() > 1e-5 and np.median(diff) < 0.01


@pytest.mark.parametrize("lengths", [1, 4, 9])
def test_positions_after_the_last_real_one_change_nothing(head, lengths):
    cfg = small_config()
    params = head.make_params(3, SMALL_SOURCE)
    x, lens = windows(8, (lengths,))
    junk = x.copy()
    junk[:, lengths:] = np.random.default_rng(1).normal(0, 3, junk[:, lengths:].shape)
    np.testing.assert_array_equal(program_scores(cfg, params, x, lens),
                                  program_scores(cfg, params, junk, lens))
    # the reference skips them outright
    np.testing.assert_array_equal(
        head.forward(params, x, lens, reference.rounder("bfloat16")),
        head.forward(params, junk, lens, reference.rounder("bfloat16")))


def test_tree_of_the_reference_is_the_programs(head):
    """The harness replaces the program's tree by the reference's: the
    two have one structure, shapes and dtypes, so the compiled step is
    reused. And the program's sizes are the configuration file's."""
    cfg = small_config()
    mine = jax.eval_shape(lambda: kb.init_backbone(jax.random.key(0), cfg))
    theirs = head.make_params(1, SMALL_SOURCE)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    published = validate.load_data("configs", "risk-seqhead-keye-vl2-30b-a3b")
    d, c = head.dims_of(published), session_heads.HEADS["keye"].config
    assert (d.hidden, d.layers, d.heads, d.kv_heads, d.head_dim, d.experts,
            d.top_k, d.expert_width, d.idx_heads, d.idx_dim, d.idx_topk,
            d.sections, d.theta, d.eps) == (
        c.hidden, c.layers, c.heads, c.kv_heads, c.head_dim, c.experts,
        c.top_k, c.expert_width, c.idx_heads, c.idx_dim, c.idx_topk,
        c.mrope_section, c.rope_theta, c.eps)
    full = jax.eval_shape(session_heads.HEADS["keye"].init)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(full))
    assert 2.50e9 < n < 2.51e9
    assert sum(a.dtype.itemsize * int(np.prod(a.shape))
               for a in jax.tree.leaves(full)) < 5.01e9


# -- M-RoPE ---------------------------------------------------------------------


@pytest.mark.parametrize("sections,head_dim", [((2, 3, 3), 16),
                                               ((16, 24, 24), 128)])
def test_mrope_with_three_unequal_streams(head, sections, head_dim):
    rng = np.random.default_rng(5)
    b, t, h = 3, 16, 2
    pos3 = np.stack([np.arange(t) + 1, rng.integers(0, 40, t),
                     rng.integers(0, 900, t)])[:, None, :].repeat(b, 1)
    pos3[:, 1] += 3
    x = rng.normal(0, 1, (b, t, h, head_dim)).astype(np.float32)
    cos, sin = dp.mrope_angles(jnp.asarray(pos3, jnp.int32), head_dim, sections, 1e7)
    got = np.asarray(dp.rotate(jnp.asarray(x), cos, sin))
    d = head.dims_of(SMALL_SOURCE)._replace(head_dim=head_dim, sections=sections)
    want = np.asarray(head._mrope(jnp.asarray(x), jnp.asarray(pos3, jnp.int32), d))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # the streams matter: equal ids turn the later sections differently
    same = np.broadcast_to(pos3[:1], pos3.shape)
    cos1, sin1 = dp.mrope_angles(jnp.asarray(same, jnp.int32), head_dim, sections, 1e7)
    assert np.abs(np.asarray(dp.rotate(jnp.asarray(x), cos1, sin1)) - got).max() > 0.1
    # pair i is channels i and i + half, and each section follows its stream
    half = head_dim // 2
    lo = sections[0]
    np.testing.assert_allclose(np.asarray(cos)[..., :lo], np.asarray(cos1)[..., :lo])
    assert not np.allclose(np.asarray(cos)[..., lo:half], np.asarray(cos1)[..., lo:half])


# -- the dropless expert layer --------------------------------------------------


def _expert_loop(x, top_e, top_w, layer):
    """Every (position, expert) pair, one expert at a time, in float64."""
    x = np.asarray(x, np.float64)
    y = np.zeros_like(x)
    wg, wu, wd = (np.asarray(layer[k].astype(jnp.float32), np.float64)
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

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("gate_up", "down", "combine"):
        monkeypatch.setattr(kernels, name, functools.partial(
            getattr(kernels, name), interpret=True))


@pytest.fixture
def expert_core(request, monkeypatch):
    """The core ``grouped_experts`` picks while tracing: (sizes of the
    layer, tolerance against the float64 loop, the core's name). ``xla``
    is what a CPU picks by itself (float32 operands at the small size);
    ``pallas`` sizes the layer so that ``supports`` takes it (lane-aligned
    widths, bfloat16)."""
    dp.announce_core.cache_clear()
    if request.param == "xla":
        return dict(operand_dtype=jnp.float32), 2e-4, "xla-ragged-dot"
    _steer_to_the_kernels(monkeypatch)
    # bfloat16 operands: x, the weights and ``mid`` are each rounded once;
    # at hidden 2048 a row is whole tiles of packed words and ``gate_up``
    # brings its rows in itself (``takes_rows``), below that the caller
    # gathers a sorted copy
    if request.param == "pallas-rows-in-kernel":
        return dict(hidden=2048, expert_width=128), 2e-2, "pallas-grouped"
    return dict(hidden=128, expert_width=128), 2e-2, "pallas-grouped"


@pytest.mark.parametrize("expert_core", ["xla", "pallas", "pallas-rows-in-kernel"],
                         indirect=True)
@pytest.mark.parametrize("experts,top_k,hot", [(8, 2, (3, 5)), (16, 8, (2, 11)),
                                               (8, 2, None)],
                         ids=["top2-all-on-two", "top8-two-in-every-set", "free"])
def test_grouped_experts_drop_nothing_under_skew(experts, top_k, hot, expert_core,
                                                 caplog):
    sizes, tolerance, core = expert_core
    cfg = small_config(experts=experts, top_k=top_k, **sizes)
    params = kb.init_backbone(jax.random.key(2), cfg)
    layer = dict(params["layers"][0])
    n = 96
    x = jax.random.normal(jax.random.key(3), (n, cfg.hidden), jnp.float32)
    if hot is not None:
        # a router that puts two experts into every position's set: one
        # constant channel, and a large weight from it to the two
        x = x.at[:, -1].set(10.0)
        layer["wr"] = layer["wr"].astype(jnp.float32).at[-1, jnp.asarray(hot)].set(5.0)
    top_e, top_w = jax.jit(lambda x: kb.route(x, layer, cfg))(x)
    counts = np.bincount(np.asarray(top_e).ravel(), minlength=cfg.experts)
    if hot is not None:
        assert counts[list(hot)].tolist() == [n, n]  # every position, both
    assert counts.sum() == n * cfg.top_k  # a pair a slot: none dropped
    np.testing.assert_allclose(np.asarray(top_w).sum(-1), 1.0, atol=1e-6)
    with caplog.at_level("INFO", logger=dp.logger.name):
        got = np.asarray(jax.jit(lambda x, e, w: el.grouped_experts(
            x, e, w, layer, cfg))(x, top_e, top_w))
    assert f"expert core: {core} (" in caplog.text  # the kernels say how they are fed
    if core == "pallas-grouped":
        rows = "in-kernel" if cfg.hidden == 2048 else "gathered"
        assert f"rows={rows}) (backend=tpu)" in caplog.text
    want = _expert_loop(x, top_e, top_w, layer)
    np.testing.assert_allclose(got, want, atol=tolerance * np.abs(want).max(), rtol=0)
    # every position got all of its experts: leaving any pair out shows
    pos, slot = 17, 0
    less_w = np.asarray(top_w).copy()
    less_w[pos, slot] = 0.0
    less = _expert_loop(x, top_e, less_w, layer)
    assert np.abs(less[pos] - got[pos]).max() > 10 * np.abs(want - got).max()


def test_expert_core_is_announced_and_falls_back_where_the_kernels_do_not_fit(
        monkeypatch, caplog):
    """Off the TPU the XLA branch runs and says so; on a (steered) TPU a
    shape ``supports`` refuses (the small size: hidden 64, width 32) still
    takes it, and a shape it accepts names the kernels."""
    def announced(cfg):
        dp.announce_core.cache_clear()
        caplog.clear()
        layer = jax.eval_shape(
            lambda: kb.init_backbone(jax.random.key(0), cfg))["layers"][0]
        xs = jax.ShapeDtypeStruct((64, cfg.hidden), cfg.operand_dtype)
        sizes = jax.ShapeDtypeStruct((cfg.experts,), jnp.int32)
        with caplog.at_level("INFO", logger=dp.logger.name):
            out = jax.eval_shape(
                lambda xs, sizes, layer: el._expert_products(xs, sizes, layer, cfg),
                xs, sizes, layer)
        assert out.shape == (64, cfg.hidden) and out.dtype == jnp.float32
        return [r.getMessage() for r in caplog.records]

    small, aligned = small_config(), small_config(hidden=128, expert_width=128)
    assert jax.default_backend() == "cpu"
    assert announced(small) == ["expert core: xla-ragged-dot (backend=cpu)"]
    assert announced(aligned) == ["expert core: xla-ragged-dot (backend=cpu)"]
    _steer_to_the_kernels(monkeypatch)
    assert announced(small) == ["expert core: xla-ragged-dot (backend=tpu)"]
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels
    fed = kernels.feed(64, 128, aligned.experts, 128)  # a sorted copy: gathered
    assert fed.endswith("rows=gathered")
    assert announced(aligned) == [f"expert core: pallas-grouped ({fed}) (backend=tpu)"]
    assert announced(small_config(hidden=128, expert_width=128,
                                  operand_dtype=jnp.float32)) == [
        "expert core: xla-ragged-dot (backend=tpu)"]
    # the last word of each part is kept for /debug/sessionz (``head_cores``)
    assert dp.announced_cores()["expert core"] == "xla-ragged-dot (backend=tpu)"
    announced(aligned)
    assert dp.announced_cores()["expert core"] == f"pallas-grouped ({fed}) (backend=tpu)"


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_with_the_kernels_on_equals_the_xla_path(operands, monkeypatch, caplog):
    """The whole head at a size every kernel takes (hidden 1024, width 128,
    128 positions), the kernels through the interpreter, against the XLA
    expressions: float32 operands leave the products on ``lax.ragged_dot``
    and only the way back on ``combine``; bfloat16 ones put all three
    kernels into the step, ``down`` handing its rows over whole."""
    cfg = small_config(hidden=1024, expert_width=128,
                       operand_dtype=jnp.dtype(operands))
    params = kb.init_backbone(jax.random.key(9), cfg)
    x, lens = windows(8, (1, 4, 16, 7, 9, 2), seed=2)
    by_xla = program_scores(cfg, params, x, lens)
    dp.announce_core.cache_clear()
    _steer_to_the_kernels(monkeypatch)
    with caplog.at_level("INFO", logger=dp.logger.name):
        by_kernels = program_scores(cfg, params, x, lens)
    said = {r.getMessage() for r in caplog.records}
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels
    # hidden 1024 is not whole tiles of packed words: the rows are gathered
    fed = kernels.feed(128 * cfg.top_k, 1024, cfg.experts, 128, positions=128)
    assert fed.endswith("rows=gathered")
    core = f"pallas-grouped ({fed})" if operands == "bfloat16" else "xla-ragged-dot"
    # heads of 16 at this size: attention's core declines and says why
    assert said == {f"expert core: {core} (backend=tpu)",
                    "combine: pallas-rows (backend=tpu)",
                    "attention core: einsum (head width 16 is not whole "
                    "128-lane vregs; mask=keep) (backend=tpu)"}
    assert np.ptp(by_xla) > 1e-3
    # float32 summation order in the way back (and, with bfloat16 operands,
    # inside the products, before ``mid`` is rounded once)
    atol = 2e-6 if operands == "float32" else 2e-4
    np.testing.assert_allclose(by_kernels, by_xla, atol=atol, rtol=0)


# -- attention's core: the window kernel's grouped form or two einsums ----------


def wide_heads(**over) -> kb.BackboneConfig:
    """The published attention (32 query / 4 key heads of 128, M-RoPE's
    sections, the indexer's 16 heads of 64) over a small hidden size, so
    that the kernel takes the layer and a CPU holds it; ``idx_topk`` 2048
    keeps every causal key, as in the cell."""
    kw = dict(hidden=256, layers=1, heads=32, kv_heads=4, head_dim=128,
              experts=2, top_k=1, expert_width=16, idx_heads=16, idx_dim=64,
              idx_topk=2048, mrope_section=(16, 24, 24))
    kw.update(over)
    return kb.BackboneConfig(**kw)


def attention_layer(cfg, windows_n: int, seed: int = 0, events: int = 16):
    """One seeded layer with gains that are not 1, a residual stream [P,
    hidden] and the angles of positions 0 .. ``events - 1`` of every window."""
    layer = dict(kb.init_backbone(jax.random.key(21 + seed), cfg)["layers"][0])
    ks = jax.random.split(jax.random.key(22 + seed), 3)
    for name, key in zip(("qn", "kn"), ks[:2], strict=True):
        layer[name] = 1 + 0.2 * jax.random.normal(key, layer[name].shape, jnp.float32)
    p = windows_n * events
    h = jax.random.normal(ks[2], (p, cfg.hidden), jnp.float32)
    pos3 = jnp.broadcast_to(jnp.arange(events, dtype=jnp.int32),
                            (3, windows_n, events))
    cos, sin = (a.reshape(p, -1) for a in dp.mrope_angles(
        pos3, cfg.head_dim, cfg.mrope_section, cfg.rope_theta))
    return layer, h, cos, sin


def run_attention(cfg, layer, h, cos, sin, attention=None, events: int = 16):
    attention = attention or kb.attention
    return np.asarray(jax.jit(
        lambda h, c, s: attention(h, layer, c, s, cfg, events))(h, cos, sin))


@pytest.fixture
def attention_by_kernel(monkeypatch, caplog):
    """Runs ``attention`` as a TPU would trace it, the window kernel through
    the Pallas interpreter (steered here, in the test), and returns what it
    computed with what ``_announce_core`` said."""
    from igaming_platform_tpu.ops.pallas import window_attention as wa

    def run(*args, **kwargs):
        dp.announce_core.cache_clear()
        caplog.clear()
        with monkeypatch.context() as m, caplog.at_level("INFO", logger=dp.logger.name):
            m.setattr(jax, "default_backend", lambda: "tpu")
            m.setattr(wa, "grouped_window_attention", functools.partial(
                wa.grouped_window_attention, interpret=True))
            out = run_attention(*args, **kwargs)
        return out, [r.getMessage() for r in caplog.records]
    return run


@pytest.mark.parametrize("windows_n", [64, 256])
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_attention_through_the_kernel_equals_the_einsum_path(
        operands, windows_n, attention_by_kernel):
    """The sublayer on the seeded tree at the cell's two rungs (1,024 and
    4,096 positions): projections and ``wo`` are the same XLA either way,
    the core is the kernel or the einsums, and at the cell's ``topk`` 2048
    neither traces the indexer: both mask by the causal rule alone, and the
    kernel's line says so. ``wo`` sums 4,096
    products of the core's results, so a result that fell to the other
    side of a bfloat16 boundary (one in a thousand, tests/
    test_window_attention.py) moves an output by 2^-8 of one term."""
    cfg = wide_heads(operand_dtype=jnp.dtype(operands))
    args = attention_layer(cfg, windows_n)
    by_einsum = run_attention(cfg, *args)
    by_kernel, said = attention_by_kernel(cfg, *args)
    assert said == ["attention core: pallas-windows (grouped 32/4 of 128, "
                    "window 16, mask=causal; indexer not traced: topk 2048 >= "
                    "window 16) (backend=tpu)"]
    assert dp.announced_cores()["attention core"] == said[0].split(": ", 1)[1]
    assert by_kernel.shape == by_einsum.shape == (windows_n * 16, cfg.hidden)
    scale = np.abs(by_einsum).max()
    assert scale > 0.01
    atol = 1e-6 if operands == "float32" else 2e-3
    np.testing.assert_allclose(by_kernel, by_einsum, atol=atol * scale, rtol=0)


@pytest.mark.parametrize("core", ["einsum", "kernel"])
def test_the_indexer_is_computed_not_assumed(core, attention_by_kernel):
    """``idx_topk`` 4 under the window's 16 keys: the keys the indexer
    drops change the output, through the einsums and through the kernel
    alike (the mask is the kernel's operand; it does not take the cell's
    ``topk`` 2048 for granted), and the two paths agree on the pruned
    layer."""
    run = ((lambda *a: attention_by_kernel(*a)[0]) if core == "kernel"
           else run_attention)
    pruned, whole = wide_heads(idx_topk=4), wide_heads()
    args = attention_layer(pruned, 16, seed=1)
    got, kept_all = run(pruned, *args), run(whole, *args)
    first = np.arange(len(got)) % 16 < 4   # up to 4 causal keys: none dropped
    np.testing.assert_array_equal(got[first], kept_all[first])
    rest = np.abs(got[~first] - kept_all[~first]).max(axis=1)
    assert (rest > 1e-3 * np.abs(kept_all).max()).all()
    np.testing.assert_allclose(got, run_attention(pruned, *args),
                               atol=2e-3 * np.abs(got).max(), rtol=0)


NOT_TRACED = "mask=causal; indexer not traced: topk 2048 >= window"


@pytest.mark.parametrize("backend,over,window,said", [
    ("cpu", {}, 16, f"einsum (not a TPU; {NOT_TRACED} 16) (backend=cpu)"),
    ("cpu", {"idx_topk": 4}, 16, "einsum (not a TPU; mask=keep) (backend=cpu)"),
    ("tpu", {}, 16, "pallas-windows (grouped 32/4 of 128, window 16, "
                    f"{NOT_TRACED} 16) (backend=tpu)"),
    ("tpu", {}, 128, "pallas-windows (grouped 32/4 of 128, window 128, "
                     f"{NOT_TRACED} 128) (backend=tpu)"),
    ("tpu", {"idx_topk": 16}, 16,
     "pallas-windows (grouped 32/4 of 128, window 16, mask=causal; indexer not "
     "traced: topk 16 >= window 16) (backend=tpu)"),
    ("tpu", {"idx_topk": 15}, 16,
     "pallas-windows (grouped 32/4 of 128, window 16, mask=keep) (backend=tpu)"),
    ("tpu", {"idx_topk": 64}, 128,
     "pallas-windows (grouped 32/4 of 128, window 128, mask=keep) (backend=tpu)"),
    ("tpu", {"heads": 4, "kv_heads": 2, "head_dim": 16, "idx_topk": 4}, 16,
     "einsum (head width 16 is not whole 128-lane vregs; mask=keep) (backend=tpu)"),
    ("tpu", {"heads": 20, "kv_heads": 3}, 16,
     f"einsum (20 heads over 3 key heads; {NOT_TRACED} 16) (backend=tpu)"),
    ("tpu", {}, 12, "einsum (windows of 12 are not whole 8-row vregs that "
                    f"divide a tile of 128; {NOT_TRACED} 12) (backend=tpu)"),
    ("tpu", {"operand_dtype": jnp.float16, "idx_topk": 4}, 16,
     "einsum (operands float16 / float16; mask=keep) (backend=tpu)"),
], ids=["off-the-tpu", "off-the-tpu-pruning", "published", "published-deep",
        "topk-is-the-window", "topk-one-under", "deep-pruning", "small-heads",
        "uneven-sharing", "window12", "float16"])
def test_attention_core_is_announced_with_the_reason_it_declines(
        backend, over, window, said, monkeypatch, caplog):
    """The choice is made while tracing, from the backend and the layer's
    shapes alone; the boot's log line and ``/debug/sessionz``'s
    ``head_cores`` carry it, with the kernel's own reason beside ``einsum``
    and, since PR 63, what masks on either core: ``mask=keep`` where the
    indexer was traced (``idx_topk`` under the window: ``attention`` hands
    its mask over), or ``mask=causal`` with the reason where it was not."""
    cfg = wide_heads(**over)
    dp.announce_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    # as ``attention`` calls it: the indexer's mask, or none
    keep = (jax.ShapeDtypeStruct((64 * window, window), jnp.bool_)
            if cfg.idx_topk < window else None)
    with caplog.at_level("INFO", logger=dp.logger.name):
        by_kernel = kb._attention_core(64 * window, keep, cfg, window)
    assert by_kernel is said.startswith("pallas-windows")
    assert [r.getMessage() for r in caplog.records] == [f"attention core: {said}"]
    assert dp.announced_cores()["attention core"] == said


# -- the selection where ``topk`` covers the window: the causal mask -------------


def _indexer_inputs(cfg, events: int, scores: str):
    """Four windows of normed-like hidden states with a seeded layer whose
    indexer scores are what ``scores`` says: ``random``; ``nan`` (head
    weights of NaN: every score NaN); ``-inf`` (head weights of -inf over
    positive inputs: every score -inf, or NaN where a head's ``relu`` left
    0); ``a row`` (one position's input NaN: that query's row of scores
    and that key's column)."""
    layer, a, cos, sin = attention_layer(cfg, 4, seed=3, events=events)
    if scores == "nan":
        layer["ww"] = jnp.full_like(layer["ww"], jnp.nan)
    elif scores == "-inf":
        layer["ww"], a = jnp.full_like(layer["ww"], -jnp.inf), jnp.abs(a) + 0.1
    elif scores == "a row":
        a = a.at[events + 5].set(jnp.nan)
    if scores != "random":  # the poison reaches the scores
        w = np.asarray(dp.mm(a, layer["ww"], cfg))
        assert not np.isfinite(w).all()
    return layer, a, cos, sin


@pytest.mark.parametrize("scores", ["random", "nan", "-inf", "a row"])
@pytest.mark.parametrize("events,topk", [(16, 16), (16, 2048), (128, 128),
                                         (128, 2048)])
def test_the_selection_is_the_causal_mask_where_topk_covers_the_window(
        events, topk, scores):
    """What ``attention`` rests on when it leaves the indexer out: with
    ``idx_topk >= window`` ``top_k`` returns all ``window`` keys of every
    query, a permutation whatever the scores are (finite, NaN or ``-inf``),
    so ``indexer_keep`` IS the causal mask, for every input."""
    cfg = wide_heads(idx_topk=topk)
    layer, a, cos, sin = _indexer_inputs(cfg, events, scores)
    keep = np.asarray(jax.jit(lambda a, c, s: kb.indexer_keep(
        a, layer, c, s, cfg, events))(a, cos, sin))
    causal = np.tile(np.tril(np.ones((events, events), bool)), (4, 1))
    np.testing.assert_array_equal(keep, causal)
    if scores == "random":  # and one key fewer is a selection: it drops keys
        pruned = np.asarray(jax.jit(lambda a, c, s: kb.indexer_keep(
            a, layer, c, s, wide_heads(idx_topk=events - 1), events))(a, cos, sin))
        last = np.arange(len(pruned)) % events == events - 1
        assert (pruned[last].sum(axis=1) == events - 1).all()
        np.testing.assert_array_equal(pruned[~last], causal[~last])


def _attention_with_the_mask_handed_over(interpret):
    """``attention`` as PR 63's parent traced it at every ``topk``: the
    indexer's mask computed and handed to the core (the einsums, or the
    kernel through the interpreter where ``interpret`` is set)."""
    from igaming_platform_tpu.ops.pallas import window_attention as wa

    def attention(h, layer, cos, sin, cfg, window):
        p, dt = h.shape[0], cfg.operand_dtype
        nh, nkv, hd = cfg.heads, cfg.kv_heads, cfg.head_dim
        a = dp.rms_norm(h, layer["g1"], cfg.eps)
        k = dp.rms_norm(dp.mm(a, layer["wk"], cfg).reshape(1, p, nkv, hd),
                        layer["kn"], cfg.eps)
        k = dp.rotate(k, cos[None], sin[None]).astype(dt).reshape(p, nkv * hd)
        keep = kb.indexer_keep(a, layer, cos, sin, cfg, window)
        widths = dict(heads=nh, kv_heads=nkv, window=window, eps=cfg.eps)
        if interpret:
            q, v = dp.mm_t(layer["wq"], a, cfg), dp.mm_t(layer["wv"], a, cfg).astype(dt)
            o = wa.grouped_window_attention(q, k, v, cos, sin, layer["qn"], keep,
                                            **widths, interpret=True)
            return jax.lax.dot_general(o, layer["wo"].astype(dt),
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        q, v = dp.mm(a, layer["wq"], cfg), dp.mm(a, layer["wv"], cfg).astype(dt)
        o = kb._core_by_einsums(q, k, v, cos, sin, layer["qn"], keep, **widths)
        return dp.mm(o, layer["wo"], cfg)
    return attention


@pytest.mark.parametrize("core", ["einsum", "kernel"])
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("events,windows_n", [(16, 64), (128, 8)])
def test_attention_without_the_indexer_is_bit_equal_to_the_mask_handed_over(
        events, windows_n, operands, core, attention_by_kernel, monkeypatch):
    """At the cells' ``topk`` 2048 over 16 and 128 events the sublayer that
    traces no indexer returns the bits of the one that computes the mask
    and hands it over, on the einsum path (the CPU tests' and replay's) and
    through the kernel (the chip's, in the Pallas interpreter): the scores
    a caller reads are the parent's."""
    cfg = wide_heads(operand_dtype=jnp.dtype(operands))
    args = attention_layer(cfg, windows_n, seed=2, events=events)
    traced = []
    monkeypatch.setattr(kb, "indexer_keep", lambda *a, _f=kb.indexer_keep: (
        traced.append(1), _f(*a))[1])
    if core == "kernel":
        now, said = attention_by_kernel(cfg, *args, events=events)
        assert f"window {events}, {NOT_TRACED} {events})" in said[0]
    else:
        now = run_attention(cfg, *args, events=events)
    assert traced == []
    was = run_attention(cfg, *args, events=events,
                        attention=_attention_with_the_mask_handed_over(
                            interpret=core == "kernel"))
    assert traced == [1] and np.isfinite(now).all() and np.ptp(now) > 0.01
    np.testing.assert_array_equal(now, was)


@pytest.mark.parametrize("topk,traced,sha256", [
    (8, True, "97b106f2039253a0"), (16, False, "5b75ef3f6eacf415"),
    (2048, False, "5b75ef3f6eacf415")])
def test_the_head_lowers_the_indexer_only_where_it_can_drop_a_key(
        topk, traced, sha256):
    """``backbone_scores`` at the small size the other backbones' files pin
    ``keye`` at (tests/test_falconh1_backbone.py), 16-event windows: with
    ``idx_topk`` 8 under the window the StableHLO is the parent's byte for
    byte (its digest since PR 47); with a ``topk`` that covers the window
    (16, or the published 2048: the same module) the indexer's ``top_k`` is
    not in it and the router's, two layers' worth, is all that is left.
    PR 63 meant to change this one; every other head's digest stands."""
    import hashlib

    cfg = kb.BackboneConfig(hidden=128, layers=2, heads=4, kv_heads=2,
                            head_dim=32, experts=8, top_k=2, expert_width=64,
                            idx_heads=2, idx_dim=16, idx_topk=topk,
                            mrope_section=(4, 6, 6))
    params = jax.eval_shape(lambda: kb.init_backbone(jax.random.key(0), cfg))
    text = jax.jit(lambda p, w, l: kb.backbone_scores(p, w, l, cfg)).lower(
        params, jax.ShapeDtypeStruct((6, 16, 12), jnp.float32),
        jax.ShapeDtypeStruct((6,), jnp.int32)).as_text()
    assert text.count("top_k") == (4 if traced else 2)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == sha256


def _hidden_with_a_3d_stream(params, x, pos3, cfg):
    """``backbone_hidden`` as it was before the stream went position-major
    (PR 47's parent): ``h`` [B, T, hidden], every product over a 3-D
    operand, attention's core the two einsums over ``[b, t, h, d]`` with
    the indexer's [B, T, T] mask."""
    b, t, _ = x.shape
    nh, nkv, hd, dt = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.operand_dtype
    h = dp.mm(x, params["embed"], cfg)
    cos, sin = dp.mrope_angles(pos3, hd, cfg.mrope_section, cfg.rope_theta)
    for layer in params["layers"]:
        a = dp.rms_norm(h, layer["g1"], cfg.eps)
        q = dp.mm(a, layer["wq"], cfg).reshape(b, t, nh, hd)
        k = dp.mm(a, layer["wk"], cfg).reshape(b, t, nkv, hd)
        v = dp.mm(a, layer["wv"], cfg).reshape(b, t, nkv, hd)
        q = dp.rotate(dp.rms_norm(q, layer["qn"], cfg.eps), cos, sin)
        k = dp.rotate(dp.rms_norm(k, layer["kn"], cfg.eps), cos, sin)
        keep = kb.indexer_keep(a.reshape(b * t, -1), layer, cos.reshape(b * t, -1),
                               sin.reshape(b * t, -1), cfg, t).reshape(b, t, t)
        q = q.reshape(b, t, nkv, nh // nkv, hd)
        sc = jnp.einsum("btgjd,bsgd->bgjts", q.astype(dt), k.astype(dt),
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(keep[:, None, None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bgjts,bsgd->btgjd", p.astype(dt), v.astype(dt),
                       preferred_element_type=jnp.float32)
        h = h + dp.mm(o.reshape(b, t, nh * hd), layer["wo"], cfg)
        flat = dp.rms_norm(h, layer["g2"], cfg.eps).reshape(b * t, -1)
        top_e, top_w = kb.route(flat, layer, cfg)
        h = h + el.grouped_experts(flat, top_e, top_w, layer, cfg).reshape(b, t, -1)
    return dp.rms_norm(h, params["gf"], cfg.eps)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_the_position_major_stream_scores_the_3d_streams_bits(operands):
    """The head's stream is [P, hidden] from the projector to the final
    norm; on the einsum path that is the same arithmetic on the same
    numbers as the [B, T, hidden] stream it replaced, bit for bit."""
    cfg = small_config(operand_dtype=jnp.dtype(operands))
    params = kb.init_backbone(jax.random.key(13), cfg)
    x, lens = windows(12, (1, 4, 16, 7, 9, 2), seed=4)
    pos3 = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (3, 12, 16))
    now = program_scores(cfg, params, x, lens)
    was = np.asarray(jax.jit(lambda p, w, l: dp.score_last(
        p, _hidden_with_a_3d_stream(p, w, pos3, cfg), l))(
            params, jnp.asarray(x), jnp.asarray(lens, jnp.int32)))
    assert np.ptp(now) > 1e-3
    np.testing.assert_array_equal(now, was)


# -- a chip's share of the experts (the layer both backbones call) --------------


def _layer_as_before(x, top_e, top_w, layer, cfg):
    """The expert layer as PR 35 left it, written out: every expert held,
    one pass, the return to position order by the inverse permutation."""
    n, k = top_e.shape
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sizes = jnp.bincount(flat_e, length=cfg.experts).astype(jnp.int32)
    xs = x.astype(cfg.operand_dtype)[order // k]
    ys = el._expert_products(xs, sizes, layer, cfg)
    back = jnp.argsort(order)
    y = ys[back].reshape(n, k, -1)
    return jnp.sum(y * top_w[..., None], axis=1)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_keye_head_is_unchanged_bit_for_bit_by_the_shared_layer(
        head, operands, monkeypatch):
    """Where every expert is held the layer that now also serves a share
    computes what it computed: the same bits from the layer and from the
    whole head."""
    cfg = small_config(operand_dtype=jnp.dtype(operands))
    params = head.make_params(5, SMALL_SOURCE)
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.key(8), (160, cfg.hidden), jnp.float32)
    top_e, top_w = kb.route(x, layer, cfg)
    now = jax.jit(lambda x, e, w: el.grouped_experts(x, e, w, layer, cfg))
    before = jax.jit(lambda x, e, w: _layer_as_before(x, e, w, layer, cfg))
    np.testing.assert_array_equal(np.asarray(now(x, top_e, top_w)),
                                  np.asarray(before(x, top_e, top_w)))
    win, lens = windows(24, (1, 4, 16, 7, 9, 2), seed=4)
    got = program_scores(cfg, params, win, lens)
    monkeypatch.setattr(kb, "grouped_experts", _layer_as_before)
    np.testing.assert_array_equal(got, program_scores(cfg, params, win, lens))


def test_pass_rows_bound_a_share_and_cover_a_whole_layer():
    # the cell's share: 32,768 pairs, 8 of 256 held -> 4 x 1,024 expected
    assert el.pass_rows(32768, 8, 256) == 4096
    # every expert held: one pass over all the pairs, as before
    assert el.pass_rows(32768, 128, 128) == 32768
    assert el.pass_rows(2048, 4, 32) == 1024
    assert el.pass_rows(192, 4, 16) == 192   # never more than all pairs
    assert el.pass_rows(4096, 1, 256) == 256  # rounded up to the kernels' tile
    # and, told how wide a row is, no more rows than leave a pass's float32
    # results in the 64 MiB that ``combine`` keeps them in: the pangu
    # cell's pass is 2,048 rows of 7,680, not 4,096; small rows change nothing
    assert el.pass_rows(32768, 8, 256, 7680) == 2048
    assert el.pass_rows(32768, 8, 256, 2048) == 4096
    assert el.pass_rows(2048, 4, 32, 128) == 1024
    assert el.pass_rows(4096, 1, 256, 1 << 20) == 256  # one tile at least


@pytest.mark.parametrize("expert_core", ["xla", "pallas", "pallas-rows-in-kernel"],
                         indirect=True)
@pytest.mark.parametrize("routing", ["all-held", "none-held", "free", "ragged"])
def test_a_share_is_dropless_at_any_routing(routing, expert_core):
    """Experts 8-11 of 32 held, 512 positions x 4: ``pass_rows`` is 1,024
    of 2,048 pairs. ``all-held``: every pair is on a held expert, two full
    passes; ``ragged``: 1,500 held pairs, the second pass part empty and
    experts split across the passes; ``none-held``: no pass at all, exact
    zeros; ``free``: the router's own choice, one pass. Each against the
    float64 loop over the held experts alone, weights as the router
    normalised them over ALL chosen experts."""
    sizes, tolerance, _ = expert_core
    experts, held, first, k, n = 32, 4, 8, 4, 512
    cfg = small_config(experts=experts, top_k=k, **sizes)
    whole = kb.init_backbone(jax.random.key(2), cfg)["layers"][0]
    share = {key: whole[key][first:first + held] for key in ("wg", "wu", "wd")}
    x = jax.random.normal(jax.random.key(3), (n, cfg.hidden), jnp.float32)
    rng = np.random.default_rng(6)
    inside = first + np.stack([rng.permutation(held) for _ in range(n)])
    outside = np.stack([rng.choice(np.r_[0:first, first + held:experts], k,
                                   replace=False) for _ in range(n)])
    if routing == "free":
        top_e, top_w = kb.route(x, whole, cfg)
    else:
        top_e = {"all-held": inside, "none-held": outside,
                 "ragged": np.where(np.arange(n * k).reshape(n, k) < 1500,
                                    inside, outside)}[routing]
        top_w = rng.dirichlet(np.ones(k), n).astype(np.float32)
        top_e, top_w = jnp.asarray(top_e, jnp.int32), jnp.asarray(top_w)
    here = (np.asarray(top_e) >= first) & (np.asarray(top_e) < first + held)
    assert int(here.sum()) == {"all-held": 2048, "none-held": 0,
                               "ragged": 1500}.get(routing, int(here.sum()))
    assert el.pass_rows(n * k, held, experts) == 1024
    got = np.asarray(jax.jit(lambda x, e, w: el.grouped_experts(
        x, e, w, share, cfg, first))(x, top_e, top_w))
    local = np.where(here, np.asarray(top_e) - first, held)  # held: no expert
    want = _expert_loop(x, local, np.where(here, np.asarray(top_w), 0.0), share)
    if routing == "none-held":
        assert not got.any()
    else:
        assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, atol=tolerance * max(np.abs(want).max(), 1),
                               rtol=0)
    # a pair of the second pass is there: leaving it out shows
    if routing in ("all-held", "ragged"):
        pos = 300  # its pairs sort behind the first 1,024 held ones or among them
        less_w = np.where(here, np.asarray(top_w), 0.0)
        less_w[pos] = 0.0
        less = _expert_loop(x, local, less_w, share)
        assert np.abs(less[pos] - got[pos]).max() > 10 * np.abs(want - got).max()


# -- the served path ------------------------------------------------------------


@pytest.fixture
def small_keye(monkeypatch):
    """``SESSION_HEAD=keye`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "keye", dataclasses.replace(
        session_heads.HEADS["keye"],
        scores=lambda sp, win, lp: kb.backbone_scores(sp, win, lp, cfg),
        init=lambda: kb.init_backbone(jax.random.key(11), cfg)))
    return cfg


@pytest.fixture
def environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_keye, environment):
    """The new cell's own files, the source's sizes cut to the small one:
    one server, the head through ``serve/index_program.build``, index-mode
    ``ScoreBatch`` over a real socket, every reply (score, rule score,
    action, session bits) against ``chipbench/reference.py``."""
    spec = copy.deepcopy(validate.load_cell("keye-backbone-insession"))
    spec["config"].update(copy.deepcopy(SMALL_SOURCE))
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    run = harness.Run(spec, seed=3_400_000_007, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "keye"
        run.fill()
        # the harness judges a CPU run's head at float32 operands, because
        # the transformer head leaves its rounding to the MXU; this head
        # casts its operands itself, on every backend, so it is judged
        # at the stated precision as on the chip
        run.device = types.SimpleNamespace(platform="as-on-the-chip")
        ok, numbers = run.check()
        built = run.inner._fused_fns
        counters = run.counters()
        snap = run.inner.session.snapshot()
    finally:
        run.shutdown()
    assert any(k[0] == "session" for k in built)
    assert ok, numbers
    assert numbers["session_bit_mismatch"] == 0 and numbers["score_max_err"] <= 1
    assert numbers["warm_rows"] > numbers["rows"] // 2
    assert numbers["folded_rows"] > 0
    # the two counters of the head's positions
    assert counters["risk_session_head_positions_total"] == 16 * numbers["rows"]
    real = counters["risk_session_head_real_positions_total"]
    assert numbers["rows"] < real < 16 * numbers["rows"]
    assert snap["head_positions"] == 16 * numbers["rows"]
    assert snap["head_real_positions"] == real


def test_replay_verifies_a_ledger_written_under_the_head(small_keye, monkeypatch):
    monkeypatch.setenv("SESSION_HEAD", "keye")
    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import ledger as ledger_mod
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine
    from tools.replay import replay_directory

    d = tempfile.mkdtemp(prefix="keye-replay-test-")
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
        assert eng.session.head == "keye"
        assert np.all((out["ml_score"] >= 0) & (out["ml_score"] <= 1))
    finally:
        eng.ledger.close()
        eng.close()
    v = replay_directory(d, batch=16)
    assert v["session_records"] == 36
    assert v["session_verified"] == 36 and v["session_hash_mismatch"] == 0
    assert v["session_ok"] and v["ok"], json.dumps(v)[:400]


def test_unknown_head_lists_the_new_name():
    with pytest.raises(ValueError) as err:
        session_heads.session_head("mamba")
    assert "'keye'" in str(err.value) and "'pattern'" in str(err.value)
