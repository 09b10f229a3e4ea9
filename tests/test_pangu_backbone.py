"""The ``pangu`` session head (models/pangu_backbone.py) against its plain
reference (chipbench/heads/openpangu_ultra.py) at a small size on the CPU,
part by part, share by share, and through the served session path.

The small size keeps every mechanism: one dense and two expert layers,
hidden 64, 4 heads of 16 + 8 (the 8 rotary) against values of 16, a query
latent of 32 and a key-value latent of 16, a shared expert beside 16
routed ones of which a chip holds 4, 4 chosen a token.
"""

from __future__ import annotations

import dataclasses
import copy
import functools
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from chipbench import harness, reference, validate  # noqa: E402
from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
from igaming_platform_tpu.models import expert_layer as el  # noqa: E402
from igaming_platform_tpu.models import pangu_backbone as pb  # noqa: E402
from igaming_platform_tpu.models import session_heads  # noqa: E402

CONFIG = "risk-seqhead-openpangu-ultra-moe-718b"
EXPERTS, HELD = 16, 4


def small_source(first: int = 4, held: int = HELD, **over) -> dict:
    """The small size as a configuration file would state it: what the
    chip holds under the source's key, the published count and the share's
    first expert under ``head``."""
    source = {
        "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "n_routed_experts": held,
        "n_shared_experts": 1, "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "routed_scaling_factor": 2.5,
        "rope_theta": 25600000, "rms_norm_eps": 1e-5, "sandwich_norm": True,
        "norm_topk_prob": True,
        "head": {"published": {"n_routed_experts": EXPERTS,
                               "num_hidden_layers": 61},
                 "first_expert": first},
    }
    source.update(over)
    return source


def small_config(first: int = 4, held: int = HELD, **over) -> pb.PanguConfig:
    kw = dict(hidden=64, layers=3, dense_layers=1, heads=4, q_rank=32,
              kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16, dense_width=96,
              experts=EXPERTS, held_experts=held, first_expert=first, top_k=4,
              expert_width=32)
    kw.update(over)
    return pb.PanguConfig(**kw)


@pytest.fixture(scope="module")
def head():
    return validate.load_code("heads", "openpangu_ultra")


def windows(n: int, lengths, seed: int = 0):
    rng = np.random.default_rng(seed)
    lengths = np.resize(np.asarray(lengths), n)
    x = rng.normal(0, 1, (n, 16, 12)).astype(np.float32)
    x *= (np.arange(16)[None, :] < lengths[:, None])[..., None]
    return x, lengths


def program_scores(cfg, params, x, lengths):
    return np.asarray(jax.jit(
        lambda p, w, l: pb.backbone_scores(p, w, l, cfg))(
            params, jnp.asarray(x), jnp.asarray(lengths, jnp.int32)))


def stream(rows: int = 6, seed: int = 0, hidden: int = 64):
    """A residual stream [rows, 16, hidden] with some spread."""
    return jax.random.normal(jax.random.key(seed), (rows, 16, hidden),
                             jnp.float32) * 2.0


def steer_to_combine(monkeypatch):
    """What a TPU would pick for the way back, on the CPU: the backend
    reports ``tpu`` and ``combine`` runs through the Pallas interpreter.
    Steered here, in the test; the program has no option for it."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    dp.announce_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "combine", functools.partial(
        kernels.combine, interpret=True))


# Head widths the attention kernel takes (whole 64-lane halves), at two
# heads: what the small size's 16 + 8 against 16 becomes where the kernel
# is to run in a CPU test.
KERNEL_WIDTHS = dict(heads=2, nope_dim=64, rope_dim=64, v_dim=64)
KERNEL_SOURCE = dict(num_attention_heads=2, qk_nope_head_dim=64,
                     qk_rope_head_dim=64, v_head_dim=64)


def steer_to_attention_kernel(monkeypatch):
    """What a TPU would pick for the core of attention, on the CPU: the
    backend reports ``tpu`` and the kernel runs through the Pallas
    interpreter (``combine`` too, where an expert layer follows). Steered
    here, in the test; the program has no option for it."""
    from igaming_platform_tpu.ops.pallas import window_attention as kernel

    steer_to_combine(monkeypatch)
    monkeypatch.setattr(kernel, "window_attention", functools.partial(
        kernel.window_attention, interpret=True))


# -- the whole head, the stack and the score -------------------------------------


@pytest.mark.parametrize("lengths", [(1,), (4,), (16,), (1, 4, 16, 7, 9, 2)],
                         ids=["len1", "len4", "len16", "mixed"])
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_equals_the_reference(head, operands, lengths):
    cfg = small_config(operand_dtype=jnp.dtype(operands))
    params = head.make_params(7, small_source())
    x, lens = windows(24, lengths, seed=len(lengths))
    got = program_scores(cfg, params, x, lens)
    want = head.forward(params, x, lens, reference.rounder(operands))
    assert got.shape == want.shape == (24,)
    # with bfloat16 operands a value on a rounding boundary falls either
    # side by the order of a float32 accumulation (two score products here,
    # one in the reference): one such operand is 0.4% of itself, and the
    # standardised projector's events are a few times the unit ones
    atol = 5e-6 if operands == "float32" else 2e-4
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)


def test_rounding_is_where_the_reference_puts_it(head):
    params = head.make_params(7, small_source())
    x, lens = windows(24, (16,))
    a = program_scores(small_config(operand_dtype=jnp.float32), params, x, lens)
    b = program_scores(small_config(operand_dtype=jnp.bfloat16), params, x, lens)
    diff = np.abs(a - b)
    assert diff.max() > 1e-6 and np.median(diff) < 0.01


@pytest.mark.parametrize("lengths", [1, 4, 9])
def test_positions_after_the_last_real_one_change_nothing(head, lengths):
    cfg = small_config()
    params = head.make_params(3, small_source())
    x, lens = windows(8, (lengths,))
    junk = x.copy()
    junk[:, lengths:] = np.random.default_rng(1).normal(0, 3, junk[:, lengths:].shape)
    np.testing.assert_array_equal(program_scores(cfg, params, x, lens),
                                  program_scores(cfg, params, junk, lens))


def test_tree_of_the_reference_is_the_programs(head):
    """The harness replaces the program's tree by the reference's: one
    structure, shapes and dtypes, so the compiled step is reused. And the
    program's sizes are the configuration file's, the chip's share among
    them; 3.11 G parameters, 6.23 GB at rest."""
    cfg = small_config()
    mine = jax.eval_shape(lambda: pb.init_backbone(jax.random.key(0), cfg))
    theirs = head.make_params(1, small_source())
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # the two post-norm gains start at the depth-scaled value, the rest at 1
    post = 1.0 / np.sqrt(2 * 61)
    for mine_layer, theirs_layer in zip(
            pb.init_backbone(jax.random.key(0), cfg)["layers"], theirs["layers"]):
        for tree in (mine_layer, theirs_layer):
            np.testing.assert_allclose(np.asarray(tree["g2"]), post, rtol=1e-6)
            np.testing.assert_allclose(np.asarray(tree["g4"]), post, rtol=1e-6)
            assert np.all(np.asarray(tree["g1"]) == 1) and np.all(
                np.asarray(tree["g3"]) == 1)
    published = validate.load_data("configs", CONFIG)
    d, c = head.dims_of(published), session_heads.HEADS["pangu"].config
    assert (d.hidden, d.layers, d.dense_layers, d.heads, d.q_rank, d.kv_rank,
            d.nope, d.rope, d.v, d.dense_width, d.experts, d.held, d.first,
            d.top_k, d.expert_width, d.scale, d.theta, d.eps) == (
        c.hidden, c.layers, c.dense_layers, c.heads, c.q_rank, c.kv_rank,
        c.nope_dim, c.rope_dim, c.v_dim, c.dense_width, c.experts,
        c.held_experts, c.first_expert, c.top_k, c.expert_width,
        c.routed_scale, c.rope_theta, c.eps)
    assert c.init_depth == published["head"]["published"]["num_hidden_layers"]
    full = jax.eval_shape(session_heads.HEADS["pangu"].init)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(full))
    assert 3.11e9 < n < 3.12e9
    assert 6.22e9 < sum(a.dtype.itemsize * int(np.prod(a.shape))
                        for a in jax.tree.leaves(full)) < 6.24e9


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_seeded_projector_reads_standardised_events(head, seed):
    """The seeded projector divides each event column that varies by its
    spread over plausible events and lets the constant column carry the
    means: one matrix, no bias, and hidden states that differ from event
    to event as a trained projector's do (no shared direction but the
    constant column's own row)."""
    rng = np.random.default_rng(seed)
    n, t = 64, 16
    lengths = rng.integers(1, t + 1, n)
    win = np.zeros((n, t, 12), np.float32)
    win[..., 0] = rng.normal(7.6, 1.2, (n, t))
    win[..., 1] = rng.uniform(0.3, 3.0, (n, t))
    win[np.arange(n)[:, None], np.arange(t)[None, :], 2 + rng.integers(0, 4, (n, t))] = 1.0
    win[..., 10] = 1.0
    win *= (np.arange(t)[None, :] < lengths[:, None])[..., None]
    w = jnp.asarray(rng.standard_normal((12, 64)) / np.sqrt(12), jnp.bfloat16)
    got = np.asarray(head._standardised(w, win, lengths).astype(jnp.float32), np.float64)
    assert got.shape == (12, 64)
    events = win[np.arange(t)[None, :] < lengths[:, None]].astype(np.float64)
    mean, std = events.mean(0), events.std(0)
    varies = std > 0
    assert varies.sum() == 6 and mean[10] == 1.0
    w64 = np.asarray(w.astype(jnp.float32), np.float64)
    want = ((events[:, varies] - mean[varies]) / std[varies]) @ w64[varies] + w64[10]
    # bfloat16 at rest: each row is rounded once more
    np.testing.assert_allclose(events @ got, want, atol=0.05, rtol=0)
    # the columns no event sets are as drawn
    np.testing.assert_array_equal(got[~varies & (mean == 0)], w64[~varies & (mean == 0)])
    # the part every event shares is the constant column's own row
    hidden = events @ got
    np.testing.assert_allclose(hidden.mean(0), w64[10], atol=0.05)
    # and it is in the tree make_params gives
    params = head.make_params(seed, small_source())
    assert params["embed"].dtype == jnp.bfloat16 and params["embed"].shape == (12, 64)


def test_large_matrices_are_drawn_in_row_blocks():
    """No draw of the seeded tree passes 2^24 float32 normals: the dense
    MLP's matrices and ``wo`` come in row blocks of whole tiles."""
    c = session_heads.HEADS["pangu"].config
    for rows, cols in ((c.hidden, c.dense_width), (c.dense_width, c.hidden),
                       (c.heads * c.v_dim, c.hidden),
                       (c.q_rank, c.heads * (c.nope_dim + c.rope_dim))):
        blocks = dp.row_blocks(rows, cols)
        assert blocks > 1 and rows % (16 * blocks) == 0
        assert rows // blocks * cols <= dp._DRAW_ELEMS
    # every matrix of the keye head is one draw, as before
    assert dp.row_blocks(2048, 4096) == dp.row_blocks(4096, 2048) == 1
    got = dp._matrix(jax.random.key(0), (64, 8), 64)
    assert got.shape == (64, 8) and got.dtype == jnp.bfloat16


# -- the parts --------------------------------------------------------------------


@pytest.mark.parametrize("core", ["xla-einsum", "pallas-windows"])
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_latent_attention_alone(head, operands, core, monkeypatch, caplog):
    """``x + N2(attention(N1(x)))`` of one layer against the reference's,
    and the one rotary key is shared: turning it turns every head. With
    the three einsums at the small size's widths, and with the kernel
    (ops/pallas/window_attention.py, interpreted) at widths it takes, as a
    TPU would pick it: the same expanded form at the same precision."""
    dt = jnp.dtype(operands)
    by_kernel = core == "pallas-windows"
    source = small_source(**(KERNEL_SOURCE if by_kernel else {}))
    cfg = small_config(operand_dtype=dt, **(KERNEL_WIDTHS if by_kernel else {}))
    d = head.dims_of(source)
    layer = head.make_params(5, source)["layers"][1]
    x = stream()
    t = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (1, x.shape[0], t))
    cos, sin = dp.mrope_angles(pos, cfg.rope_dim, (cfg.rope_dim // 2,),
                               cfg.rope_theta)
    if by_kernel:
        steer_to_attention_kernel(monkeypatch)

    def sublayer(x, cos, sin):
        o = dp.latent_attention(dp.rms_norm(x, layer["g1"], cfg.eps), layer,
                                cos, sin, cfg)
        return x + dp.rms_norm(o, layer["g2"], cfg.eps)

    with caplog.at_level("INFO", logger=dp.logger.name):
        got = np.asarray(jax.jit(sublayer)(x, cos, sin))
    if by_kernel:
        assert "attention core: pallas-windows (backend=tpu)" in caplog.text
    with jax.default_matmul_precision("highest"):
        want = np.asarray(head._attend(layer, x, d, dt))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    still = np.asarray(jax.jit(sublayer)(x, jnp.ones_like(cos), jnp.zeros_like(sin)))
    assert np.abs(still - got).max() > 1e-3  # the rotary part matters
    # causal: a later position's event does not reach an earlier one
    later = x.at[:, 9:].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(sublayer)(later, cos, sin))[:, :9], got[:, :9])


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["dense", "expert"])
def test_mlp_sublayer_alone(head, operands, kind):
    """``x + N4(MLP(N3(x)))``: the leading dense layer, and an expert layer
    (shared expert + the held experts' part) of a share."""
    dt = jnp.dtype(operands)
    cfg, d = small_config(operand_dtype=dt), head.dims_of(small_source())
    params = head.make_params(9, small_source())
    layer = params["layers"][0 if kind == "dense" else 2]
    assert ("dense" in layer) == (kind == "dense")
    x = stream(seed=3)

    lengths = jnp.asarray([16, 3, 9, 1, 16, 12], jnp.int32)
    live = (jnp.arange(16)[None, :] < lengths[:, None]).reshape(-1)

    def sublayer(x, live):
        b, t, _ = x.shape
        flat = dp.rms_norm(x, layer["g3"], cfg.eps).reshape(b * t, -1)
        if kind == "dense":
            m = dp.swiglu(flat, layer["dense"], cfg)
        else:
            top_e, top_w = dp.route(flat, layer, cfg)
            m = dp.swiglu(flat, layer["shared"], cfg) + el.grouped_experts(
                flat, top_e, top_w, layer["routed"], cfg, cfg.first_expert, live)
        return x + dp.rms_norm(m, layer["g4"], cfg.eps).reshape(x.shape)

    got = np.asarray(jax.jit(sublayer)(x, live))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(head._dense(layer, x, d, dt) if kind == "dense"
                          else head._moe(layer, x, lengths, d, dt))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    if kind == "expert":
        # a window's padding is not routed: with every position live the
        # padded positions (and only they) read differently
        every = np.asarray(jax.jit(sublayer)(x, jnp.ones_like(live)))
        moved = np.abs(every - got).max(-1).reshape(-1) > 1e-6
        assert moved.any() and not moved[np.asarray(live)].any()


def test_router_is_sigmoid_top_k_renormalised_and_scaled():
    cfg = small_config(operand_dtype=jnp.float32)
    layer = pb.init_backbone(jax.random.key(1), cfg)["layers"][1]
    x = jax.random.normal(jax.random.key(2), (40, 64), jnp.float32)
    top_e, top_w = jax.jit(lambda x: dp.route(x, layer, cfg))(x)
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                          @ np.asarray(layer["wr"].astype(jnp.float32), np.float64))))
    best = np.argsort(-s, axis=1, kind="stable")[:, :cfg.top_k]
    np.testing.assert_array_equal(np.sort(np.asarray(top_e), 1), np.sort(best, 1))
    chosen = np.take_along_axis(s, np.asarray(top_e), 1)
    np.testing.assert_allclose(
        np.asarray(top_w), chosen / chosen.sum(1, keepdims=True) * 2.5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(top_w).sum(1), 2.5, rtol=1e-5)


# -- the shares add up ------------------------------------------------------------


@pytest.mark.parametrize("way_back", ["xla-gather", "pallas-rows"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(
        head, way_back, monkeypatch, caplog):
    """Model-configs guide, section 4: at 16 experts, the routed parts that
    4 shares of 4 give, with what every chip computes alike (the shared
    expert) counted once, equal what the UNCUT reference (all 16 held)
    gives for the whole layer. Both ways back to position order: the XLA
    expressions at hidden 64, and ``combine`` (through the interpreter) at
    hidden 128, whose rows are whole lane tiles."""
    dt = jnp.float32
    size = {}
    dp.announce_core.cache_clear()
    if way_back == "pallas-rows":
        size = {"hidden": 128}
        steer_to_combine(monkeypatch)
    uncut = small_source(first=0, held=EXPERTS,
                         **{"hidden_size": v for v in size.values()})
    whole = head.make_params(11, uncut)["layers"][1]
    d_whole = head.dims_of(uncut)
    x = stream(rows=8, seed=5, **size)
    b, t, hidden = x.shape

    flat = head._rms(x, whole["g3"], d_whole.eps).reshape(-1, hidden)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_reference_mlp(head, whole, flat, d_whole, dt))
    shared_once = np.asarray(jax.jit(lambda f: dp.swiglu(
        f, whole["shared"], small_config(operand_dtype=dt, **size)))(flat))
    total = shared_once.copy()
    for first in range(0, EXPERTS, HELD):
        cfg = small_config(first=first, operand_dtype=dt, **size)
        share = {k: v[first:first + HELD] for k, v in whole["routed"].items()}
        top_e, top_w = dp.route(flat, whole, cfg)
        with caplog.at_level("INFO", logger=dp.logger.name):
            part = np.asarray(jax.jit(lambda f, e, w: el.grouped_experts(
                f, e, w, share, cfg, first, jnp.ones((f.shape[0],), bool)))(
                    flat, top_e, top_w))
        # a share's own reference gives the same part
        d_share = head.dims_of(dict(uncut, n_routed_experts=HELD, head=dict(
            uncut["head"], first_expert=first)))
        layer = dict(whole, routed=share)
        with jax.default_matmul_precision("highest"):
            ref_part = np.asarray(_reference_mlp(head, layer, flat, d_share, dt))
        np.testing.assert_allclose(part, ref_part - shared_once, atol=2e-5, rtol=0)
        assert np.abs(part).max() > 1e-3
        total += part
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=0)
    # and every pair was somebody's: the weights of a position sum to 2.5
    assert np.abs(want - shared_once).max() > 1e-2
    backend = "tpu" if way_back == "pallas-rows" else "cpu"
    assert f"combine: {way_back} (backend={backend})" in caplog.text


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_with_combine_on_equals_the_xla_path(operands, monkeypatch, caplog):
    """The whole head at hidden 128, where ``combine`` takes a share's
    results (the products stay on ``lax.ragged_dot``: the cell's pairing),
    against the XLA expressions on the same tree and windows."""
    cfg = small_config(hidden=128, operand_dtype=jnp.dtype(operands))
    params = pb.init_backbone(jax.random.key(4), cfg)
    x, lens = windows(12, (1, 4, 16, 7, 9, 2), seed=3)
    by_xla = program_scores(cfg, params, x, lens)
    steer_to_combine(monkeypatch)
    with caplog.at_level("INFO", logger=dp.logger.name):
        by_kernel = program_scores(cfg, params, x, lens)
    assert "combine: pallas-rows (backend=tpu)" in caplog.text
    assert "expert core: xla-ragged-dot (backend=tpu)" in caplog.text
    assert np.ptp(by_xla) > 1e-3
    np.testing.assert_allclose(by_kernel, by_xla, atol=2e-6, rtol=0)


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_head_with_attention_kernel_on_equals_the_xla_path(
        operands, monkeypatch, caplog):
    """The whole head at hidden 128 and head widths the attention kernel
    takes, every layer's core through the kernel (and ``combine`` for the
    way back, as a TPU pairs them), against the einsums on the same tree
    and windows: windows of every length, padding going through attention
    as it does in the cell."""
    cfg = small_config(hidden=128, operand_dtype=jnp.dtype(operands),
                       **KERNEL_WIDTHS)
    params = pb.init_backbone(jax.random.key(4), cfg)
    x, lens = windows(12, (1, 4, 16, 7, 9, 2), seed=3)
    by_xla = program_scores(cfg, params, x, lens)
    steer_to_attention_kernel(monkeypatch)
    with caplog.at_level("INFO", logger=dp.logger.name):
        by_kernel = program_scores(cfg, params, x, lens)
    assert "attention core: pallas-windows (backend=tpu)" in caplog.text
    assert "combine: pallas-rows (backend=tpu)" in caplog.text
    assert "attention core: xla-einsum" not in caplog.text
    assert np.ptp(by_xla) > 1e-3
    # float32 operands: the order of float32 sums alone; bfloat16: a
    # probability on a rounding boundary may fall to either side (0.4% of
    # itself), as in test_head_equals_the_reference
    atol = 2e-6 if operands == "float32" else 2e-4
    np.testing.assert_allclose(by_kernel, by_xla, atol=atol, rtol=0)


def _reference_mlp(head, layer, flat, d, dt):
    """What the reference's ``_moe`` hands to N4 (shared expert + the held
    experts' part), from its own parts, every position live."""
    logits = head._rnd(flat, dt) @ head._rnd(layer["wr"], dt)
    s = 1.0 / (1.0 + jnp.exp(-logits))
    top_s, top_e = jax.lax.top_k(s, d.top_k)
    top_w = top_s / (top_s.sum(-1, keepdims=True) + 1e-20) * d.scale
    m = head._swiglu(flat, layer["shared"], dt)
    for i in range(d.held):
        chosen = top_e == d.first + i
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), -1, keepdims=True)
        y = head._swiglu(flat, {k: v[i] for k, v in layer["routed"].items()}, dt)
        m = m + jnp.where(chosen.any(-1, keepdims=True), y * weight, 0.0)
    return m


# -- the gauges and the served path -------------------------------------------------


@pytest.fixture
def small_pangu(monkeypatch):
    """``SESSION_HEAD=pangu`` at the small size: the row of ``HEADS`` is
    steered here, in the test; the program has no option for it."""
    cfg = small_config()
    monkeypatch.setitem(session_heads.HEADS, "pangu", dataclasses.replace(
        session_heads.HEADS["pangu"],
        scores=lambda sp, win, lp: pb.backbone_scores(sp, win, lp, cfg),
        init=lambda: pb.init_backbone(jax.random.key(11), cfg)))
    return cfg


@pytest.fixture
def environment():
    import os

    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def test_score_batch_on_the_session_path_equals_the_reference(
        small_pangu, environment):
    """The new cell's own files, the source's sizes cut to the small one:
    one server, the head through ``serve/index_program.build``, index-mode
    ``ScoreBatch`` over a real socket, every reply against
    ``chipbench/reference.py``; and the three gauges of what the head
    holds, on ``/metrics`` and ``/debug/sessionz``."""
    spec = copy.deepcopy(validate.load_cell("pangu-mla-insession"))
    small = small_source()  # experts 4-7 of 16, as the fixture holds
    spec["config"]["head"] = dict(spec["config"]["head"], **small.pop("head"))
    spec["config"].update(small)
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    run = harness.Run(spec, seed=3_600_000_007, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        assert run.inner.session.head == "pangu"
        run.fill()
        # this head casts its operands itself on every backend, so a CPU
        # run is judged at the stated precision as on the chip
        run.device = types.SimpleNamespace(platform="as-on-the-chip")
        ok, numbers = run.check()
        built = run.inner._fused_fns
        counters = run.counters()
        snap = run.inner.session.snapshot()
        text = run.server.metrics.registry.render_text()
    finally:
        run.shutdown()
    assert any(k[0] == "session" for k in built)
    assert ok, numbers
    assert numbers["session_bit_mismatch"] == 0 and numbers["score_max_err"] <= 1
    assert numbers["warm_rows"] > numbers["rows"] // 2
    assert numbers["folded_rows"] > 0
    assert counters["risk_session_head_positions_total"] == 16 * numbers["rows"]
    resident = sum(int(a.nbytes) for a in jax.tree.leaves(run.head_params))
    assert snap["head_resident_bytes"] == resident > 0
    c = session_heads.HEADS["pangu"].config
    assert (snap["head_experts_held"], snap["head_experts_routed"]) == (
        c.held_experts, c.experts) == (8, 256)
    for name, value in (("resident_bytes", resident), ("experts_held", 8),
                        ("experts_routed", 256)):
        assert f"risk_session_head_{name} {value}" in text.replace(".0\n", "\n")


@pytest.mark.parametrize("name,held,routed", [("pattern", 0, 0),
                                              ("transformer", 0, 0)])
def test_gauges_of_a_head_without_experts(name, held, routed):
    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.serve.session_state import SessionStateManager

    metrics = ServiceMetrics("risk")
    mgr = SessionStateManager(8, head=name, metrics=metrics)
    snap = mgr.snapshot()
    assert (snap["head_experts_held"], snap["head_experts_routed"]) == (held, routed)
    want = sum(int(a.nbytes) for a in jax.tree.leaves(mgr.head_params))
    assert snap["head_resident_bytes"] == want
    assert (want == 0) == (name == "pattern")
    text = metrics.registry.render_text()
    assert "risk_session_head_resident_bytes" in text
    assert "risk_session_head_experts_routed" in text


def test_unknown_head_lists_the_new_name():
    with pytest.raises(ValueError) as err:
        session_heads.session_head("mamba")
    assert "'pangu'" in str(err.value) and "'keye'" in str(err.value)


def test_chip_smoke_phase_runs_the_head_against_its_reference():
    """``chip_smoke.phase_backbone`` at the small size on the CPU: the head
    against its reference, and the cores that ran the held experts, their
    way back and the core of attention."""
    import chip_smoke

    report = chip_smoke.phase_backbone(cfg=small_config(), config=small_source(),
                                       rows=8)
    assert report["max_err"] < 1e-4 and report["rows"] == 8
    assert report["expert_core"] == "expert core: xla-ragged-dot (backend=cpu)"
    assert report["way_back"] == "combine: xla-gather (backend=cpu)"
    assert report["attention_core"] == "attention core: xla-einsum (backend=cpu)"
    assert report["resident_bytes"] > 0
