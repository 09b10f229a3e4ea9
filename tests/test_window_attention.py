"""The core of attention over short windows as one Pallas kernel
(ops/pallas/window_attention.py) against the einsum form it replaces, on
the CPU through the Pallas interpreter.

What may differ from the einsum form, and the bound held here: the order
of float32 accumulation inside a product (192 or 128 terms on the MXU's
tiles against XLA's) and inside the softmax's sum (128 lanes, all but the
window's exact zeros, against the window's). With float32 operands that
is a few float32 roundings of values near 1: 4e-6 absolute. With bfloat16
operands a probability or a result that lies on a rounding boundary may
fall to either side, one bfloat16 rounding of the value (2^-8 of it), on
at most one entry in a thousand; every other entry is the same bits.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from igaming_platform_tpu.models import keye_backbone as kb  # noqa: E402
from igaming_platform_tpu.models import pangu_backbone as pb  # noqa: E402
from igaming_platform_tpu.ops.pallas import window_attention as wa  # noqa: E402

HEADS = 4
# (nope, rope, v): the published 128 + 64 against 128, where every other
# head's query columns start 64 lanes off a vreg boundary, and 64 + 64
# against 128, where the key-value columns do
WIDTHS = {"192/128": (128, 64, 128), "128/128": (64, 64, 128)}


def operands(windows: int, t: int, widths, dtype, seed: int = 0):
    """What the projections hand the core: ``q`` float32 with its rotary
    part unturned, ``kv`` and the turned ``k_rope`` in the operands'
    dtype, the angles of positions 0 .. t-1 of every window."""
    nope, rope, dv = widths
    p = windows * t
    ks = jax.random.split(jax.random.key(seed + p + nope), 3)
    q = jax.random.normal(ks[0], (p, HEADS * (nope + rope)), jnp.float32)
    kv = jax.random.normal(ks[1], (p, HEADS * (nope + dv)), jnp.float32)
    k_rope = jax.random.normal(ks[2], (p, rope), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (1, windows, t))
    cos, sin = kb.mrope_angles(pos, rope, (rope // 2,), 25.6e6)
    return (q, kv.astype(dtype), k_rope.astype(dtype),
            cos.reshape(p, -1), sin.reshape(p, -1))


@functools.partial(jax.jit, static_argnames=("widths", "t"))
def einsum_form(q, kv, k_rope, cos, sin, *, widths, t):
    """The program's own einsum core (what ``latent_attention`` runs where
    the kernel does not), rounded as ``Wo``'s product rounds it."""
    nope, rope, dv = widths
    return pb._core_by_einsums(q, kv, k_rope, cos, sin, heads=HEADS, nope=nope,
                               rope=rope, dv=dv, window=t).astype(kv.dtype)


def by_kernel(xs, widths, t):
    nope, rope, dv = widths
    kw = dict(heads=HEADS, nope=nope, rope=rope, dv=dv, window=t)
    assert wa.supports(xs[0], xs[1], **kw)
    return np.asarray(wa.window_attention(*xs, **kw, interpret=True), np.float32)


@pytest.mark.parametrize("windows", ["whole-tiles", "a-tile-part-filled"])
@pytest.mark.parametrize("t", [8, 16])
@pytest.mark.parametrize("widths", list(WIDTHS), ids=list(WIDTHS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_equals_the_einsum_form(dtype, widths, t, windows):
    """Window counts that fill their tiles (128 / t windows each) and that
    leave the last one part filled; the bound is the module docstring's."""
    widths = WIDTHS[widths]
    per_tile = 128 // t
    n = 2 * per_tile if windows == "whole-tiles" else per_tile + 3
    xs = operands(n, t, widths, jnp.dtype(dtype))
    got = by_kernel(xs, widths, t)
    want = np.asarray(einsum_form(*xs, widths=widths, t=t), np.float32)
    assert got.shape == want.shape == (n * t, HEADS * widths[2])
    assert np.abs(want).max() > 1.0
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=4e-6, rtol=0)
    else:
        np.testing.assert_allclose(got, want, atol=0, rtol=2.0 ** -7)
        assert (got == want).mean() >= 0.999


@pytest.mark.parametrize("t", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_windows_keys_never_reach_another_windows_queries(dtype, t):
    """Eight (sixteen) windows share a tile and one product; what keeps
    them apart is the mask alone. Everything of one window is changed:
    the windows that share its tile read the same bits as before."""
    widths = WIDTHS["192/128"]
    xs = operands(128 // t + 2, t, widths, jnp.dtype(dtype), seed=1)
    before = by_kernel(xs, widths, t)
    w = 3
    rows = slice(w * t, (w + 1) * t)
    changed = tuple(x.at[rows].set((x[rows] * 3 + 1).astype(x.dtype))
                    for x in xs[:3]) + xs[3:]
    after = by_kernel(changed, widths, t)
    assert np.abs(after[rows] - before[rows]).max() > 0.1
    others = np.ones(len(before), bool)
    others[rows] = False
    np.testing.assert_array_equal(after[others], before[others])


@pytest.mark.parametrize("t", [8, 16])
def test_a_later_key_never_reaches_an_earlier_query(t):
    widths = WIDTHS["128/128"]
    xs = operands(128 // t, t, widths, jnp.bfloat16, seed=2)
    before = by_kernel(xs, widths, t)
    later = (np.arange(len(before)) % t) >= 5
    changed = (xs[0],) + tuple(
        jnp.where(later[:, None], (x * 2 - 1).astype(x.dtype), x)
        for x in xs[1:3]) + xs[3:]
    after = by_kernel(changed, widths, t)
    np.testing.assert_array_equal(after[~later], before[~later])
    assert np.abs(after[later] - before[later]).max() > 0.1


def test_the_rotary_part_turns_inside_the_kernel():
    """``q`` comes unturned: with the angles of position 0 everywhere the
    result is another, and it is the einsum form's at those angles."""
    widths, t = WIDTHS["192/128"], 16
    xs = operands(8, t, widths, jnp.float32, seed=3)
    still = xs[:3] + (jnp.ones_like(xs[3]), jnp.zeros_like(xs[4]))
    got = by_kernel(still, widths, t)
    assert np.abs(got - by_kernel(xs, widths, t)).max() > 1e-2
    want = np.asarray(einsum_form(*still, widths=widths, t=t))
    np.testing.assert_allclose(got, want, atol=4e-6, rtol=0)


def _shapes(p, heads, nope, rope, dv, q_dtype=jnp.float32, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((p, heads * (nope + rope)), q_dtype),
            jax.ShapeDtypeStruct((p, heads * (nope + dv)), dtype))


@pytest.mark.parametrize("holds,case", [
    (True, dict()),                                   # the cell's shapes
    (True, dict(window=8)),
    (True, dict(nope=64)),
    (True, dict(dtype=jnp.float32)),
    (False, dict(window=12, p=4092)),                 # 12 does not divide 128
    (False, dict(window=256)),                        # a window over a tile
    (False, dict(nope=16, rope=8, dv=16)),            # the CPU tests' widths
    (False, dict(nope=96)),                           # not whole 64-lane halves
    (False, dict(heads=3)),                           # half a unit of two heads
    (False, dict(p=4104)),                            # not whole windows
    (False, dict(p=0)),
    (False, dict(dtype=jnp.float16)),
    (False, dict(nope=8192, dv=8192)),                # a step's blocks over VMEM
], ids=["cell", "window8", "nope64", "float32", "window12", "window256",
        "small-widths", "nope96", "heads3", "part-window", "no-rows",
        "float16", "over-vmem"])
def test_supports(holds, case):
    kw = dict(p=4096, heads=128, nope=128, rope=64, dv=128, window=16,
              dtype=jnp.bfloat16)
    kw.update(case)
    p, dtype, window = kw.pop("p"), kw.pop("dtype"), kw.pop("window")
    q, kv = _shapes(p, kw["heads"], kw["nope"], kw["rope"], kw["dv"], dtype=dtype)
    assert wa.supports(q, kv, window=window, **kw) is holds


def test_supports_asks_for_matching_shapes():
    q, kv = _shapes(4096, 128, 128, 64, 128)
    kw = dict(heads=128, nope=128, rope=64, dv=128, window=16)
    short = jax.ShapeDtypeStruct((2048, kv.shape[1]), kv.dtype)
    narrow = jax.ShapeDtypeStruct((4096, kv.shape[1] - 256), kv.dtype)
    assert wa.supports(q, kv, **kw)
    assert not wa.supports(q, short, **kw)
    assert not wa.supports(q, narrow, **kw)


def test_where_supports_is_false_the_layer_takes_its_einsums(monkeypatch, caplog):
    """On a TPU at widths the kernel does not take (the CPU tests' 16 + 8
    against 16) ``latent_attention`` announces the einsum core and computes
    what it computes off the TPU, bit for bit; the kernel is not called."""
    cfg = pb.PanguConfig(hidden=64, layers=1, heads=4, q_rank=32, kv_rank=16,
                         nope_dim=16, rope_dim=8, v_dim=16, dense_width=96)
    layer = pb.init_backbone(jax.random.key(2), cfg)["layers"][0]
    a = jax.random.normal(jax.random.key(3), (6, 16, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (1, 6, 16))
    cos, sin = kb.mrope_angles(pos, 8, (4,), cfg.rope_theta)
    run = lambda: np.asarray(jax.jit(
        lambda a, c, s: pb.latent_attention(a, layer, c, s, cfg))(a, cos, sin))
    off_tpu = run()

    def never(*args, **kwargs):
        raise AssertionError("the kernel was called at widths it does not take")

    kb._announce_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(wa, "window_attention", never)
    with caplog.at_level("INFO", logger=kb.logger.name):
        on_tpu = run()
    assert "attention core: xla-einsum (backend=tpu)" in caplog.text
    np.testing.assert_array_equal(on_tpu, off_tpu)
