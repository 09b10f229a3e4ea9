"""The state-space window kernel (ops/pallas/ssd_window.py) through the
Pallas interpreter on the CPU, against the XLA form it replaces on a TPU
(``falconh1_backbone.ssd_one_chunk`` fed by ``causal_taps`` and ``silu`` and
followed by the gate and the grouped norm) and against the explicit
recurrence from ``H = 0``, at the head width the kernel takes (128) with few
heads, a small state and few windows."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
from igaming_platform_tpu.models import falconh1_backbone as fb  # noqa: E402
from igaming_platform_tpu.ops.pallas import ssd_window as sw  # noqa: E402
from test_falconh1_backbone import _recurrence_loop as recurrence  # noqa: E402 — the float64 loop the dual form is held to

HEADS, HD, STATE, GROUPS, TAPS = 4, 128, 128, 2, 4
CFG = fb.FalconH1Config(ssm_heads=HEADS, ssm_head_dim=HD, ssm_state=STATE,
                        ssm_groups=GROUPS, conv_taps=TAPS,
                        operand_dtype=jnp.float32)
WIDTH, _, BC, _, _ = CFG.segments
SEGMENTS = dict(zip("z x B C dt".split(), np.cumsum((0,) + CFG.segments[:-1]),
                    strict=True))
ENDS = dict(zip("z x B C dt".split(), np.cumsum(CFG.segments), strict=True))


def layer_inputs(windows: int, t: int, seed: int = 0):
    """What the in-projection leaves for ``windows`` windows of ``t``
    positions of every real length from 1 to ``t`` (the rows past a
    window's length are what a zero event leaves: zeros), position-major
    ``p`` [P, 2 width + 2 groups x state + heads], and a layer's float32
    parameters between the projections, none at its neutral value."""
    rng = np.random.default_rng(seed)
    p = rng.normal(size=(windows, t, sum(CFG.segments)))
    p[..., SEGMENTS["dt"]:] -= 1.0
    lengths = 1 + (np.arange(windows) * 5 + seed) % t
    p *= (np.arange(t)[None, :] < lengths[:, None])[..., None]
    conv_dim = WIDTH + 2 * BC
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    layer = {"taps": f32(rng.normal(size=(conv_dim, TAPS)) * 0.5),
             "conv_b": f32(rng.normal(size=conv_dim) * 0.25),
             "dt_bias": f32(rng.normal(size=HEADS) - 1.0),
             "a_log": f32(np.log(rng.uniform(1.0, 16.0, HEADS))),
             "d_skip": f32(1.0 + 0.5 * rng.normal(size=HEADS)),
             "gn": f32(1.0 + 0.2 * rng.normal(size=WIDTH))}
    return f32(p.reshape(windows * t, -1)), layer, lengths


def core_operands(p, layer, t: int):
    """``(x, bm, cm, dt, z)`` as the mixer's XLA path hands them to
    ``ssd_one_chunk``: the taps with their bias, ``silu``, ``softplus``."""
    b = p.shape[0] // t
    xbc = jax.nn.silu(dp.causal_taps(
        p[:, SEGMENTS["x"]:ENDS["C"]].reshape(b, t, -1), layer["taps"],
        layer["conv_b"]))
    x = xbc[..., :WIDTH].reshape(b, t, HEADS, HD)
    bm = xbc[..., WIDTH:WIDTH + BC].reshape(b, t, GROUPS, STATE)
    cm = xbc[..., WIDTH + BC:].reshape(b, t, GROUPS, STATE)
    dt = jax.nn.softplus(p[:, SEGMENTS["dt"]:] + layer["dt_bias"]).reshape(b, t, HEADS)
    return x, bm, cm, dt, p[:, :WIDTH]


def gated_and_normed(y, z, gain, xp=np):
    """``y * silu(z)``, RMS-normed over each group's channels."""
    y, z, gain = (xp.asarray(a, xp.float64 if xp is np else jnp.float32)
                  for a in (y, z, gain))
    g = (y.reshape(z.shape) * z / (1.0 + xp.exp(-z))).reshape(-1, GROUPS, WIDTH // GROUPS)
    g = g / xp.sqrt(xp.mean(g * g, axis=-1, keepdims=True) + CFG.eps)
    return (g * gain.reshape(GROUPS, -1)).reshape(z.shape)


def by_dual_form(p, layer, t: int):
    x, bm, cm, dt, z = core_operands(p, layer, t)
    y = jax.jit(lambda *a: fb.ssd_one_chunk(*a, layer, CFG))(x, bm, cm, dt)
    return np.asarray(gated_and_normed(y, z, layer["gn"], xp=jnp))


def by_recurrence(p, layer, t: int):
    x, bm, cm, dt, z = core_operands(p, layer, t)
    return gated_and_normed(recurrence(x, bm, cm, dt, layer, GROUPS), z, layer["gn"])


def by_kernel(p, layer, t: int, **kw):
    return np.asarray(sw.ssd_window(
        p, layer["taps"], layer["conv_b"], layer["dt_bias"], layer["a_log"],
        layer["d_skip"], layer["gn"], heads=HEADS, state=STATE, groups=GROUPS,
        window=t, eps=CFG.eps, interpret=True, **kw))


@pytest.mark.parametrize("tiles", [1, 3])
@pytest.mark.parametrize("t", [8, 16, 32])
def test_kernel_equals_the_dual_form_and_the_recurrence(t, tiles):
    """Float32 with no operand rounded: the kernel is held to the XLA form
    by the order of float32 sums alone, and to the float64 recurrence from
    ``H = 0`` by the limit the dual form meets
    (tests/test_falconh1_backbone.py), on windows of every real length."""
    p, layer, lengths = layer_inputs(tiles * 128 // t, t, seed=t + tiles)
    assert set(range(1, t + 1)) <= set(lengths) or tiles * 128 // t < t
    got = by_kernel(p, layer, t)
    assert got.shape == (tiles * 128, WIDTH) and got.dtype == np.float32
    assert np.all(np.isfinite(got))
    want = by_recurrence(p, layer, t)
    scale = np.abs(want).max()
    assert scale > 1.0
    np.testing.assert_allclose(got, by_dual_form(p, layer, t), atol=2e-5 * scale,
                               rtol=0)
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)


@pytest.mark.parametrize("t", [8, 16, 32])
def test_nothing_past_a_position_is_read(t):
    """The taps and the dual form are causal inside a window: what a window
    holds past its real length (here: anything) leaves the bits of every
    position before it, for every real length."""
    p, layer, lengths = layer_inputs(128 // t, t, seed=t)
    padded = (np.arange(t)[None, :] >= lengths[:, None]).reshape(-1)
    noise, _, _ = layer_inputs(128 // t, t, seed=99)
    other = jnp.where(padded[:, None], 7.0 * jnp.ones_like(p) + noise, p)
    alone, crowded = by_kernel(p, layer, t), by_kernel(other, layer, t)
    np.testing.assert_array_equal(crowded[~padded], alone[~padded])
    assert padded.any() and np.abs(crowded[padded] - alone[padded]).max() > 1e-3


@pytest.mark.parametrize("mates", ["huge", "nan"])
def test_a_window_does_not_read_its_tile_mates(mates):
    """Eight windows share a tile and both products span it; what lies
    outside a window is masked by ``where`` before it is used, so a
    window's result is the same bits whatever finite values its tile-mates
    hold, however large, and whatever (a NaN too) they hold in ``z``, ``B``,
    ``C`` and ``dt``: ``x`` alone enters a product's contraction unmasked,
    where ``0 x NaN`` is ``NaN``."""
    p, layer, _ = layer_inputs(8, 16, seed=5)
    other, _, _ = layer_inputs(8, 16, seed=6)
    mine = np.arange(128) // 16 == 3
    if mates == "huge":
        theirs = other * 1e4
    else:
        spot = np.zeros(p.shape, bool)
        for name in ("z", "B", "C", "dt"):
            spot[5::7, SEGMENTS[name] + 1:ENDS[name]:3] = True
        theirs = jnp.where(spot, jnp.nan, other)
    alone = by_kernel(p, layer, 16)
    crowded = by_kernel(jnp.where(mine[:, None], p, theirs), layer, 16)
    np.testing.assert_array_equal(crowded[mine], alone[mine])
    assert np.all(np.isfinite(crowded[mine]))
    differs = crowded[~mine] != alone[~mine]
    assert differs.mean() > 0.5


@pytest.mark.parametrize("segment", ["x", "B", "C", "dt", "z"])
def test_the_kernel_rounds_no_operand(segment):
    """Its products are float32 at the highest precision and its passes
    float32: on float32 inputs it meets the float64 recurrence, and with one
    segment of ``p`` rounded to bfloat16 beforehand (a change below
    bfloat16's step) the result lies far outside that limit, which a kernel
    that rounded that operand itself could not tell apart."""
    p, layer, _ = layer_inputs(8, 16, seed=2)
    want = by_recurrence(p, layer, 16)
    scale = np.abs(want).max()
    cols = slice(SEGMENTS[segment], ENDS[segment])
    rounded = p.at[:, cols].set(p[:, cols].astype(jnp.bfloat16).astype(jnp.float32))
    assert np.abs(by_kernel(p, layer, 16) - want).max() < 2e-5 * scale
    assert np.abs(by_kernel(rounded, layer, 16) - want).max() > 2e-4 * scale


@pytest.mark.parametrize("name", ["taps", "conv_b", "dt_bias", "a_log", "d_skip", "gn"])
def test_every_parameter_between_the_projections_is_read(name):
    """The taps and their bias, ``dt_bias``, ``A_log``, ``D`` and the
    grouped norm's gain each enter the kernel: with one of them changed the
    result is the recurrence's under the changed one, and not what it was."""
    p, layer, _ = layer_inputs(8, 16, seed=3)
    rng = np.random.default_rng(4)
    changed = dict(layer)
    changed[name] = layer[name] + jnp.asarray(
        0.3 * rng.normal(size=layer[name].shape), jnp.float32)
    want = by_recurrence(p, changed, 16)
    scale = np.abs(want).max()
    np.testing.assert_allclose(by_kernel(p, changed, 16), want, atol=2e-5 * scale,
                               rtol=0)
    assert np.abs(by_kernel(p, layer, 16) - want).max() > 1e-3 * scale


def test_the_result_leaves_in_the_dtype_the_out_product_rounds_to():
    """With ``out_dtype`` the float32 result is rounded once, where
    ``decoder_parts.mm`` would round it: the same values as rounding the
    float32 result."""
    p, layer, _ = layer_inputs(8, 16, seed=7)
    full = by_kernel(p, layer, 16)
    half = sw.ssd_window(
        p, layer["taps"], layer["conv_b"], layer["dt_bias"], layer["a_log"],
        layer["d_skip"], layer["gn"], heads=HEADS, state=STATE, groups=GROUPS,
        window=16, eps=CFG.eps, out_dtype=jnp.bfloat16, interpret=True)
    assert half.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(half.astype(jnp.float32)),
        np.asarray(jnp.asarray(full).astype(jnp.bfloat16).astype(jnp.float32)))


@pytest.mark.parametrize("over,why", [
    ({}, ""),
    ({"positions": 1024}, ""),
    ({"window": 8}, ""),
    ({"window": 128}, ""),
    ({"head_dim": 64}, "head width 64 is not whole 128-lane vregs"),
    ({"state": 16}, "a state of 16 is not whole 128-lane vregs"),
    ({"groups": 3}, "32 heads in 3 groups (whole groups, at most 128)"),
    ({"heads": 256}, "256 heads in 2 groups (whole groups, at most 128)"),
    ({"heads": 6, "state": 512}, "B's segment starts 768 channels after x's, "
                                 "not on a whole block of 512"),
    ({"window": 12}, "windows of 12 are not whole 8-row vregs that divide a "
                     "tile of 128"),
    ({"window": 4}, "windows of 4 are not whole 8-row vregs that divide a "
                    "tile of 128"),
    ({"window": 256}, "windows of 256 are not whole 8-row vregs that divide a "
                      "tile of 128"),
    ({"taps": 9, "window": 8}, "9 taps over windows of 8"),
    ({"positions": 4096 + 64}, "4160 positions are not whole tiles of 128"),
    ({"heads": 128, "head_dim": 1024}, "a step's blocks take"),
], ids=["published-256-rows", "published-64-rows", "window8", "window128",
        "head64", "state16", "three-groups", "heads256", "segment-off-a-block",
        "window12", "window4", "window256", "taps9", "half-a-tile", "vmem"])
def test_declines_says_why(over, why):
    """The kernel's own predicate, each reason in the words the boot log
    and ``/debug/sessionz`` carry beside the dual form."""
    shape = dict(positions=4096, heads=32, head_dim=128, state=256, groups=2,
                 window=16, taps=4)
    shape.update(over)
    said = sw.declines(shape.pop("positions"), **shape)
    assert said.startswith(why) and bool(said) is bool(why)
