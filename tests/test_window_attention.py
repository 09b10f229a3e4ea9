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
import hashlib
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
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
    cos, sin = dp.mrope_angles(pos, rope, (rope // 2,), 25.6e6)
    return (q, kv.astype(dtype), k_rope.astype(dtype),
            cos.reshape(p, -1), sin.reshape(p, -1))


@functools.partial(jax.jit, static_argnames=("widths", "t"))
def einsum_form(q, kv, k_rope, cos, sin, *, widths, t):
    """The program's own einsum core (what ``latent_attention`` runs where
    the kernel does not), rounded as ``Wo``'s product rounds it."""
    nope, rope, dv = widths
    return dp.latent_core_by_einsums(q, kv, k_rope, cos, sin, heads=HEADS, nope=nope,
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
    cos, sin = dp.mrope_angles(pos, 8, (4,), cfg.rope_theta)
    run = lambda: np.asarray(jax.jit(
        lambda a, c, s: dp.latent_attention(a, layer, c, s, cfg))(a, cos, sin))
    off_tpu = run()

    def never(*args, **kwargs):
        raise AssertionError("the kernel was called at widths it does not take")

    dp.announce_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(wa, "window_attention", never)
    with caplog.at_level("INFO", logger=dp.logger.name):
        on_tpu = run()
    assert "attention core: xla-einsum (backend=tpu)" in caplog.text
    np.testing.assert_array_equal(on_tpu, off_tpu)


# -- the grouped-query form (keye's core: shared key heads, rotary over the
#    whole head, the head norm inside, the indexer's mask an operand) ----------

G_HEADS, G_KV, G_HD, G_T = 32, 4, 128, 16
G_KW = dict(heads=G_HEADS, kv_heads=G_KV, window=G_T, eps=1e-6)
CAUSAL = np.tril(np.ones((G_T, G_T), bool))


def grouped_operands(windows: int, dtype, keep: str, seed: int = 0):
    """What keye's projections hand the core, position-major as the einsum
    form takes them (``by_grouped_kernel`` turns ``q`` and ``v``
    channel-major): ``q`` float32, neither normed nor turned, ``k`` (normed
    and turned) and ``v`` in the operands' dtype, M-RoPE angles of
    positions 0 .. 15 of every window, a head-norm gain and the mask
    ``keep`` names: ``all`` true, ``random`` (half of the causal keys
    dropped, the diagonal kept) or ``absent``."""
    p = windows * G_T
    ks = jax.random.split(jax.random.key(seed + p), 5)
    q = jax.random.normal(ks[0], (p, G_HEADS * G_HD), jnp.float32) * 3
    k = jax.random.normal(ks[1], (p, G_KV * G_HD), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (p, G_KV * G_HD), jnp.float32).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(G_T, dtype=jnp.int32), (3, windows, G_T))
    cos, sin = dp.mrope_angles(pos, G_HD, (16, 24, 24), 1e7)
    gain = 1 + 0.1 * jax.random.normal(ks[3], (G_HD,), jnp.float32)
    mask = {"all": jnp.ones((windows, G_T, G_T), bool),
            "random": (jax.random.bernoulli(ks[4], 0.5, (windows, G_T, G_T))
                       | jnp.eye(G_T, dtype=bool)) & CAUSAL,
            "absent": None}[keep]
    return (q, k, v, cos.reshape(p, -1), sin.reshape(p, -1), gain,
            None if mask is None else mask.reshape(p, G_T))


@jax.jit
def grouped_einsum_form(q, k, v, cos, sin, gain, keep):
    """The program's own einsum core (what ``keye_backbone.attention`` runs
    where the kernel does not), rounded as ``wo``'s product rounds it. The
    einsum form masks by ``keep`` alone (the indexer's is causal already);
    the kernel ANDs the tile's rule in, so a mask that is not causal is
    made so here."""
    keep = jnp.logical_and(keep.reshape(-1, G_T, G_T), CAUSAL).reshape(-1, G_T)
    return kb._core_by_einsums(q, k, v, cos, sin, gain, keep, **G_KW).astype(k.dtype)


def by_grouped_kernel(xs):
    """The kernel on ``q`` and ``v`` channel-major ([channels, P], as
    ``wq^T a^T`` leaves them), its channel-major result turned back."""
    xs = (xs[0].T, xs[1], xs[2].T) + tuple(xs[3:])
    assert wa.grouped_declines(*xs[:3], keep=xs[6], heads=G_HEADS,
                               kv_heads=G_KV, window=G_T) == ""
    out = wa.grouped_window_attention(*xs, **G_KW, interpret=True)
    assert out.shape == xs[0].shape and out.dtype == xs[1].dtype
    return np.asarray(out, np.float32).T


@pytest.mark.parametrize("keep", ["all", "random", "absent"])
@pytest.mark.parametrize("windows", [64, 11], ids=["P1024", "P176-a-tile-part-filled"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_kernel_equals_the_einsum_form(dtype, windows, keep):
    """32 query heads over 4 key heads of 128 at 1,024 positions (the cell's
    64-row rung) and at 176, which leaves the second tile part filled; the
    bound is the module docstring's (the head norm's mean is one more
    float32 sum of 128 terms whose order may differ)."""
    xs = grouped_operands(windows, jnp.dtype(dtype), keep)
    got = by_grouped_kernel(xs)
    ones = jnp.ones((windows * G_T, G_T), bool)
    want = np.asarray(grouped_einsum_form(
        *xs[:6], ones if xs[6] is None else xs[6]), np.float32)
    assert got.shape == want.shape == (windows * G_T, G_HEADS * G_HD)
    assert np.isfinite(got).all() and np.abs(want).max() > 1.0
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=4e-6, rtol=0)
    else:
        # one rounding of the value; a result near zero (a sum that
        # cancels) moves by a rounding of one of its terms instead, a
        # probability's 2^-9 times a value of a few units
        np.testing.assert_allclose(got, want, atol=2.0 ** -8, rtol=2.0 ** -7)
        assert (got == want).mean() >= 0.999


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_key_the_mask_drops_is_an_exact_zero(dtype):
    """Everything of one key (its ``k`` and ``v`` rows) is changed: a query
    whose ``keep`` drops it, and every query of another window, reads the
    same bits as before (a masked probability is exactly zero, not small);
    a query that keeps it reads another result."""
    xs = grouped_operands(24, jnp.dtype(dtype), "random", seed=5)
    before = by_grouped_kernel(xs)
    w, s = 13, 3                      # the key: position 3 of window 13
    row = w * G_T + s
    q, k, v = xs[:3]
    changed = (q, k.at[row].set((k[row] * 2 + 1).astype(k.dtype)),
               v.at[row].set((v[row] * 3 - 1).astype(v.dtype))) + xs[3:]
    after = by_grouped_kernel(changed)
    reads = np.zeros(len(before), bool)
    reads[w * G_T:(w + 1) * G_T] = np.asarray(xs[6])[w * G_T:(w + 1) * G_T, s]
    assert 1 <= reads.sum() < G_T - s  # some causal queries keep it, not all
    np.testing.assert_array_equal(after[~reads], before[~reads])
    assert (np.abs(after[reads] - before[reads]).max(axis=1) > 1e-2).all()


def test_grouped_kernel_norms_and_turns_the_query_itself():
    """``q`` comes as ``wq`` left it: another gain, or the angles of
    position 0 everywhere, give another result, and each is the einsum
    form's on the same operands."""
    xs = grouped_operands(8, jnp.float32, "random", seed=3)
    base = by_grouped_kernel(xs)
    for other in (xs[:3] + (jnp.ones_like(xs[3]), jnp.zeros_like(xs[4])) + xs[5:],
                  xs[:5] + (xs[5][::-1],) + xs[6:]):
        got = by_grouped_kernel(other)
        assert np.abs(got - base).max() > 1e-2
        np.testing.assert_allclose(got, np.asarray(grouped_einsum_form(*other)),
                                   atol=4e-6, rtol=0)


def _grouped_shapes(p=4096, heads=32, kv_heads=4, hd=128, window=16,
                    q_dtype=jnp.float32, dtype=jnp.bfloat16, v_dtype=None,
                    keep="match", k_rows=None):
    q = jax.ShapeDtypeStruct((heads * hd, p), q_dtype)
    k = jax.ShapeDtypeStruct((p if k_rows is None else k_rows, kv_heads * hd), dtype)
    v = jax.ShapeDtypeStruct((kv_heads * hd, p), v_dtype or dtype)
    mask = {"match": jax.ShapeDtypeStruct((p, window), jnp.bool_), "absent": None,
            "square": jax.ShapeDtypeStruct((p // window, window, window), jnp.bool_)}[keep]
    return (q, k, v), dict(heads=heads, kv_heads=kv_heads, window=window, keep=mask)


@pytest.mark.parametrize("reason,case", [
    ("", dict()),                                     # the cell's 256-row rung
    ("", dict(p=1024)),                               # and its 64-row rung
    ("", dict(keep="absent")),
    ("", dict(dtype=jnp.float32)),
    ("", dict(hd=256)),
    ("", dict(heads=8, kv_heads=8)),                  # no sharing at all
    ("", dict(heads=64, kv_heads=2)),                 # 32 queries a key head
    ("", dict(window=8)),
    ("windows of 12", dict(window=12, p=4092)),
    ("windows of 256", dict(window=256)),
    ("windows of 4", dict(window=4)),                 # half a vreg of rows
    ("not whole 128-lane", dict(hd=64)),              # lfm2's heads
    ("not whole 128-lane", dict(hd=16)),              # the CPU tests' widths
    ("20 heads over 3", dict(heads=20, kv_heads=3)),
    ("not whole windows", dict(p=4104)),
    ("not whole windows", dict(p=0)),
    ("operands float16", dict(dtype=jnp.float16)),
    ("operands bfloat16 / float32", dict(v_dtype=jnp.float32)),
    ("q int32", dict(q_dtype=jnp.int32)),
    ("against q", dict(k_rows=2048)),
    ("keep (256, 16, 16)", dict(keep="square")),      # the mask comes [P, window]
    ("bytes of VMEM", dict(hd=4096, heads=16, kv_heads=1)),
], ids=["cell-256", "cell-64", "no-mask", "float32", "heads-of-256", "no-sharing",
        "rep-32", "window8", "window12", "window256", "window4", "heads-of-64",
        "heads-of-16",
        "heads-not-shared-evenly", "part-window", "no-rows", "float16",
        "v-of-another-dtype", "q-int", "k-short", "mask-square", "over-vmem"])
def test_supports_grouped(reason, case):
    """The grouped form answers from its operands' shapes and dtypes, and
    says why it declines: the caller announces that beside ``einsum``."""
    xs, kw = _grouped_shapes(**case)
    said = wa.grouped_declines(*xs, **kw)
    assert (said == "") if not reason else (reason in said), said


# What the latent form traced to at the parent of the PR that added the
# grouped form (da1ef44): sha256 prefixes of its jaxpr's text at the
# ``pangu`` cell's shapes and at float32 shapes that need a padded tile
# (addresses stripped), and of its result's bits on fixed inputs through
# the interpreter (as tests/conftest.py sets the CPU compiler). The module
# is shared; these hold the form that ``pangu-mla-insession`` was measured
# on to the program it was.
LATENT_AT_DA1EF44 = {
    ("jaxpr", 4096, 128, "bfloat16"): "28e897828909caaf",
    ("jaxpr", 1008, 4, "float32"): "6324d92958bb53ff",
    ("bits", 384, 4, "bfloat16"): "2af113ad02d10569",
    ("bits", 384, 4, "float32"): "d261c550e10ccd6c",
}


@pytest.mark.parametrize("what,p,heads,dtype", list(LATENT_AT_DA1EF44),
                         ids=["-".join(map(str, k)) for k in LATENT_AT_DA1EF44])
def test_the_latent_form_is_the_program_it_was(what, p, heads, dtype):
    nope, rope, dv = WIDTHS["192/128"]
    kw = dict(heads=heads, nope=nope, rope=rope, dv=dv, window=16)
    if what == "jaxpr":
        shapes = [jax.ShapeDtypeStruct((p, heads * (nope + rope)), jnp.float32),
                  jax.ShapeDtypeStruct((p, heads * (nope + dv)), jnp.dtype(dtype)),
                  jax.ShapeDtypeStruct((p, rope), jnp.dtype(dtype)),
                  jax.ShapeDtypeStruct((p, rope // 2), jnp.float32),
                  jax.ShapeDtypeStruct((p, rope // 2), jnp.float32)]
        text = str(jax.make_jaxpr(
            lambda *xs: wa.window_attention(*xs, **kw))(*shapes))
        got = re.sub(r"0x[0-9a-f]+", "0x", text)
        assert "pallas_call" in got
    else:
        ks = jax.random.split(jax.random.key(47), 3)
        q = jax.random.normal(ks[0], (p, heads * (nope + rope)), jnp.float32)
        kv = jax.random.normal(ks[1], (p, heads * (nope + dv)),
                               jnp.float32).astype(dtype)
        kr = jax.random.normal(ks[2], (p, rope), jnp.float32).astype(dtype)
        pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (1, p // 16, 16))
        cos, sin = dp.mrope_angles(pos, rope, (rope // 2,), 25.6e6)
        out = wa.window_attention(q, kv, kr, cos.reshape(p, -1),
                                  sin.reshape(p, -1), **kw, interpret=True)
        got = np.asarray(out.astype(jnp.float32)).tobytes().hex()
    sha = hashlib.sha256(got.encode()).hexdigest()[:16]
    assert sha == LATENT_AT_DA1EF44[what, p, heads, dtype], sha
