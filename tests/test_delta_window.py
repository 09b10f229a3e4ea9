"""The delta-rule window kernel (ops/pallas/delta_window.py) through the
Pallas interpreter on the CPU, against the einsum form it replaces on a TPU
(``ling_backbone.kda_one_chunk`` behind the mixer's taps and decay) and
against the explicit recurrence, at the head width the kernel takes (128)
with few heads and few windows."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
from igaming_platform_tpu.models import ling_backbone as lb  # noqa: E402
from igaming_platform_tpu.ops.pallas import delta_window as dw  # noqa: E402
from test_ling_backbone import _recurrence_loop as recurrence  # noqa: E402 — the float64 loop the einsum form is held to

HEADS, HD, BOUND = 2, 128, -5.0
ENDS = {"fast": (-4.999, -4.5), "slow": (-0.1, -1e-4), "mixed": (-4.999, -1e-4)}


def layer_inputs(windows: int, t: int, ends: str, seed: int = 0):
    """What the projections leave for ``windows`` windows of ``t``
    positions, position-major: raw ``q, k, v`` [P, heads x 128], the decay's
    projection ``f`` placed so that ``g`` lies at the named end of (-5, 0)
    (``A_log`` 0 and no bias: ``g = -5 sigmoid(f)``), ``beta`` [P, heads],
    and the three convolutions' taps."""
    rng = np.random.default_rng(seed)
    p, c = windows * t, HEADS * HD
    raw = [rng.normal(size=(p, c)) for _ in range(3)]
    lo, hi = ENDS[ends]
    g = rng.uniform(lo, hi, size=(p, c))
    if ends == "mixed":  # channels at both ends side by side
        g[:, ::2] = rng.uniform(-4.999, -4.9, size=g[:, ::2].shape)
        g[:, 1::2] = rng.uniform(-1e-3, -1e-5, size=g[:, 1::2].shape)
    share = g / BOUND
    f = np.log(share) - np.log1p(-share)
    beta = rng.normal(size=(p, HEADS))
    taps = [rng.normal(size=(c, 4)) * 0.5 for _ in range(3)]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return ([f32(a) for a in raw], f32(f), f32(beta), jnp.zeros((HEADS,), jnp.float32),
            jnp.zeros((c,), jnp.float32), [f32(a) for a in taps])


def core_operands(raw, f, beta, a_log, dt_bias, taps, t: int):
    """``(q, k, v, g, beta)`` [B, T, heads, 128] as ``kda_mixer``'s XLA
    path hands them to ``kda_one_chunk``: the taps, ``silu``, the L2 norm,
    the decay and ``sigmoid(beta)``."""
    b = raw[0].shape[0] // t

    def conv(x, tp):
        return jax.nn.silu(dp.causal_taps(x.reshape(b, t, -1), tp))

    def unit(x):
        x = x.reshape(b, t, HEADS, HD)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q = unit(conv(raw[0], taps[0])) * HD ** -0.5
    k = unit(conv(raw[1], taps[1]))
    v = conv(raw[2], taps[2]).reshape(b, t, HEADS, HD)
    g = BOUND * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (f + dt_bias).reshape(b, t, HEADS, HD))
    return q, k, v, g, jax.nn.sigmoid(beta).reshape(b, t, HEADS)


def by_kernel(raw, f, beta, a_log, dt_bias, taps, t: int, prologue: bool):
    """The kernel's ``o`` [P, heads x 128]: with the prologue from the raw
    projections, without it from what XLA's taps and norm leave."""
    if prologue:
        return np.asarray(dw.delta_window(
            *raw, f, beta, a_log, dt_bias, tuple(taps), heads=HEADS, window=t,
            lower_bound=BOUND, interpret=True))
    q, k, v, _, _ = core_operands(raw, f, beta, a_log, dt_bias, taps, t)
    flat = lambda x: x.reshape(raw[0].shape)
    return np.asarray(dw.delta_window(
        flat(q), flat(k), flat(v), f, beta, a_log, dt_bias, heads=HEADS,
        window=t, lower_bound=BOUND, interpret=True))


@pytest.mark.parametrize("prologue", [False, True], ids=["core", "prologue"])
@pytest.mark.parametrize("tiles", [1, 3])
@pytest.mark.parametrize("ends", list(ENDS))
@pytest.mark.parametrize("t", [8, 16])
def test_kernel_equals_the_one_chunk_form_and_the_recurrence(t, ends, tiles, prologue):
    """Float32 with no operand rounded: the kernel is held to the einsum
    form by the order of float32 sums alone, and to the float64 recurrence
    by the limits the einsum form meets (tests/test_ling_backbone.py), with
    decays at both ends of (-5, 0): at the fast end the factors reach
    ``exp(+-40)`` and a masked entry ``exp(+80)``."""
    inputs = layer_inputs(tiles * 128 // t, t, ends, seed=t + tiles)
    operands = core_operands(*inputs, t)
    got = by_kernel(*inputs, t, prologue)
    assert got.shape == (tiles * 128, HEADS * HD) and np.all(np.isfinite(got))
    one_chunk = np.asarray(jax.jit(lambda *a: lb.kda_one_chunk(
        *a, lower_bound=BOUND))(*operands)).reshape(got.shape)
    want = recurrence(*operands).reshape(got.shape)
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, one_chunk, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("prologue", [False, True], ids=["core", "prologue"])
def test_the_decay_takes_its_rate_and_its_bias(prologue):
    """``g = lower_bound * sigmoid(exp(A_log) * (f + dt_bias))`` with a rate
    a head and a bias a channel, computed inside the kernel."""
    raw, f, beta, _, _, taps = layer_inputs(8, 16, "mixed", seed=3)
    rng = np.random.default_rng(4)
    a_log = jnp.asarray(np.log(rng.uniform(0.5, 1.5, HEADS)), jnp.float32)
    dt_bias = jnp.asarray(rng.normal(size=HEADS * HD) - 1.0, jnp.float32)
    inputs = (raw, f * 0.3, beta, a_log, dt_bias, taps)
    want = recurrence(*core_operands(*inputs, 16)).reshape(128, -1)
    np.testing.assert_allclose(by_kernel(*inputs, 16, prologue), want,
                               atol=2e-6, rtol=1e-5)
    plain = by_kernel(raw, f * 0.3, beta, jnp.zeros_like(a_log),
                      jnp.zeros_like(dt_bias), taps, 16, prologue)
    assert np.abs(plain - want).max() > 1e-3


@pytest.mark.parametrize("prologue", [False, True], ids=["core", "prologue"])
def test_a_window_does_not_read_its_tile_mates(prologue):
    """Eight windows share a tile and every product spans it; what lies
    outside a window is masked by ``where`` before it is used, so a
    window's result is the same bits whatever (finite) its tile-mates
    hold, however large."""
    raw, f, beta, a_log, dt_bias, taps = layer_inputs(8, 16, "fast", seed=5)
    other, f2, beta2, *_ = layer_inputs(8, 16, "slow", seed=6)
    mine = np.arange(128) // 16 == 3

    def mixed(a, b, scale=1.0):
        return jnp.where(mine[:, None], a, b * scale)

    alone = by_kernel(raw, f, beta, a_log, dt_bias, taps, 16, prologue)
    crowded = by_kernel([mixed(a, b, 1e3) for a, b in zip(raw, other, strict=True)],
                        mixed(f, f2), mixed(beta, beta2, 5.0), a_log, dt_bias,
                        taps, 16, prologue)
    np.testing.assert_array_equal(crowded[mine], alone[mine])
    assert np.abs(crowded[~mine] - alone[~mine]).max() > 1e-3


@pytest.mark.parametrize("prologue", [False, True], ids=["core", "prologue"])
def test_the_kernel_rounds_no_operand(prologue):
    """Its products are float32 at the highest precision: on float32
    inputs it meets the float64 recurrence, and inputs rounded to bfloat16
    beforehand give a result far outside that limit, which a kernel that
    rounded its operands itself could not tell apart."""
    raw, f, beta, a_log, dt_bias, taps = layer_inputs(8, 16, "mixed", seed=2)
    want = recurrence(*core_operands(raw, f, beta, a_log, dt_bias, taps, 16))
    want = want.reshape(128, -1)
    full = by_kernel(raw, f, beta, a_log, dt_bias, taps, 16, prologue)
    rounded = by_kernel([a.astype(jnp.bfloat16).astype(jnp.float32) for a in raw],
                        f, beta, a_log, dt_bias, taps, 16, prologue)
    assert np.abs(full - want).max() < 2e-6
    assert np.abs(rounded - want).max() > 1e-4


@pytest.mark.parametrize("prologue", [False, True], ids=["core", "prologue"])
def test_the_head_norm_rides_in_the_kernel(prologue):
    """With ``norm`` the kernel hands over ``rms_norm(o, gain, eps)`` a
    head (``decoder_parts.rms_norm``, one gain of 128 shared by the heads):
    the same ``o``, normed before it is written."""
    raw, f, beta, a_log, dt_bias, taps = layer_inputs(16, 16, "mixed", seed=8)
    gain = jnp.asarray(1.0 + 0.1 * np.random.default_rng(9).normal(size=HD),
                       jnp.float32)
    o = by_kernel(raw, f, beta, a_log, dt_bias, taps, 16, prologue)
    want = np.asarray(dp.rms_norm(o.reshape(-1, HEADS, HD), gain, 1e-6)).reshape(o.shape)
    q, k, v = raw if prologue else (
        x.reshape(raw[0].shape)
        for x in core_operands(raw, f, beta, a_log, dt_bias, taps, 16)[:3])
    got = np.asarray(dw.delta_window(
        q, k, v, f, beta, a_log, dt_bias, tuple(taps) if prologue else None,
        (gain, 1e-6), heads=HEADS, window=16, lower_bound=BOUND, interpret=True))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("over,why", [
    ({}, ""),
    ({"head_dim": 96}, "head width 96 is not whole 128-lane vregs"),
    ({"window": 12}, "windows of 12 are not whole 8-row vregs that divide a "
                     "tile of 128"),
    ({"window": 4}, "windows of 4 are not whole 8-row vregs that divide a "
                    "tile of 128"),
    ({"positions": 4096 + 64}, "4160 positions are not whole tiles of 128"),
    ({"heads": 0}, "0 heads"),
    ({"head_dim": 128 * 512}, "a step's blocks take"),
], ids=["published", "head96", "window12", "window4", "half-a-tile", "no-heads",
        "vmem"])
def test_declines_says_why(over, why):
    """The kernel's own predicate, each reason in the words the boot log
    and ``/debug/sessionz`` carry beside ``one chunk by einsums``."""
    shape = dict(positions=4096, heads=32, head_dim=128, window=16)
    shape.update(over)
    said = dw.declines(shape.pop("positions"), **shape)
    assert said.startswith(why) and bool(said) is bool(why)
