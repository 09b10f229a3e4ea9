"""The stream kernels of the residual path (ops/pallas/hyper_streams.py)
through the Pallas interpreter on the CPU, against the ``jax.numpy``
functions they stand for on a TPU (``decoder_parts.hyper_maps`` +
``hyper_read``; ``hyper_write`` + ``stream_squares``), at a small size and at
one tile of the published widths; what the kernels decline and how the
backbone says which path runs."""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
from igaming_platform_tpu.models import xing_backbone as xb  # noqa: E402
from igaming_platform_tpu.ops.pallas import hyper_streams as hs  # noqa: E402


def sublayer_inputs(n: int, positions: int, hidden: int, a=(1.5, 0.7, 1.0),
                    seed: int = 0):
    """``n`` streams of growing spread, a sublayer's result and a seeded
    hyper-connection whose ``a`` the caller sets (``a_res`` large puts every
    logit of the mixing map at a bound of the clip)."""
    cfg = xb.XingConfig(hidden=hidden, streams=n)
    keys = jax.random.split(jax.random.key(seed), n + 3)
    xs = tuple(jax.random.normal(keys[i], (positions, hidden), jnp.float32)
               * (1.0 + i) for i in range(n))
    hc = xb.init_hyper(keys[n], cfg)
    columns = hs.columns(n)
    hc["b"] = hc["b"] + 0.3 * jax.random.normal(keys[n + 1], (columns,))
    hc["a"] = jnp.asarray(a, jnp.float32)
    y = jax.random.normal(keys[n + 2], (positions, hidden), jnp.float32)
    return cfg, xs, hc, y


def assert_the_passes_equal_the_plain_functions(cfg, xs, hc, y, handed: bool,
                                                maps_atol: float = 2e-6):
    """The maps against ``hyper_maps``; then ``u``, the streams left and
    their squares against the plain functions *over the kernel's maps*, so
    that each pass is held on its own."""
    n = len(xs)
    squares = dp.stream_squares(xs) if handed else None
    want = dp.hyper_maps(xs, hc, cfg, squares)
    u, maps = hs.maps_and_read(xs, hc, cfg, squares, interpret=True)
    got_x, got_squares = hs.write(xs, maps, y, interpret=True)
    assert maps.shape == (hs.columns(n), xs[0].shape[0])
    pre, post, res = hs.split_maps(maps, n)
    for one, got in zip(want, (pre, post, res)):
        assert got.shape == one.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(one),
                                   atol=maps_atol, rtol=2e-5)
    scale = float(max(jnp.max(jnp.abs(x)) for x in xs))
    np.testing.assert_allclose(np.asarray(u), np.asarray(dp.hyper_read(xs, pre)),
                               atol=1e-6 * scale)
    want_x = dp.hyper_write(xs, res, post, y)
    for one, got in zip(want_x, got_x):
        np.testing.assert_allclose(np.asarray(got), np.asarray(one),
                                   atol=2e-6 * scale)
    np.testing.assert_allclose(np.asarray(got_squares),
                               np.asarray(dp.stream_squares(want_x)), rtol=1e-5)
    return np.asarray(res)


@pytest.mark.parametrize("logits", ["seeded", "at-the-bounds"])
@pytest.mark.parametrize("squares", ["handed", "own"])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_two_passes_equal_the_plain_functions(n, squares, logits):
    """Two tiles of 128 positions at hidden 256: the maps, ``u``, the streams
    left and their squares as the ``jax.numpy`` functions give them, with the
    squares handed in (a later sublayer) and summed from the tile (the
    first). With every logit of the mixing map at a bound of the clip 20
    rounds do not converge (a column sums far from 1): the kernel gives the
    plain form's 20 rounds there too, not the limit (a logit between the
    bounds is then 1000 times a product whose float32 sums the two sides
    order differently: 1e-4 of an entry)."""
    bounds = logits == "at-the-bounds"
    cfg, xs, hc, y = sublayer_inputs(
        n, 256, 256, a=(1.5, 0.7, 1000.0 if bounds else 1.0), seed=n)
    res = assert_the_passes_equal_the_plain_functions(
        cfg, xs, hc, y, squares == "handed", 1e-4 if bounds else 2e-6)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-5)  # a row
    if bounds and n == 4:
        assert np.abs(res.sum(axis=0) - 1.0).max() > 0.5         # a column


@pytest.mark.parametrize("squares", ["handed", "own"])
def test_one_tile_of_the_published_widths(squares):
    """Four streams of 3,584 and 20 rounds, the cell's shapes, one tile."""
    cfg, xs, hc, y = sublayer_inputs(4, hs.TILE, 3584, seed=53)
    assert (cfg.hc_rounds, cfg.streams) == (20, 4) and not hs.declines(
        4096, cfg.hidden, cfg.streams, jnp.float32)
    assert_the_passes_equal_the_plain_functions(cfg, xs, hc, y, squares == "handed")


@pytest.mark.parametrize("rounds", [0, 1, 3])
def test_every_round_is_the_plain_forms(rounds):
    cfg, xs, hc, y = sublayer_inputs(4, 128, 128, seed=7)
    cfg = dataclasses.replace(cfg, hc_rounds=rounds)
    assert_the_passes_equal_the_plain_functions(cfg, xs, hc, y, True)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_one_array_for_every_stream_is_not_written_over():
    """Later writes put each ``x'[i]`` where ``x[i]`` lay
    (``input_output_aliases``: the step's temporaries hold the streams
    once). At the entry the projected event stands for all ``n`` streams,
    so the first write cannot: it makes the streams, and what it read is
    left as it was."""
    cfg, xs, hc, y = sublayer_inputs(4, 128, 128, seed=3)
    maps = hs.maps_and_read(xs, hc, cfg, interpret=True)[1]

    def aliases(streams):
        traced = jax.make_jaxpr(lambda h, y: hs.write(streams(h), maps, y))(xs, y)
        (call,) = _pallas_calls(traced.jaxpr)
        return tuple(call.params["input_output_aliases"])

    assert aliases(lambda h: h) == tuple((i, i) for i in range(4))
    assert aliases(lambda h: (h[0],) * 4) == ()
    same = (xs[0],) * 4
    u, maps = hs.maps_and_read(same, hc, cfg, interpret=True)
    got, _ = hs.write(same, maps, y, interpret=True)
    pre, post, res = dp.hyper_maps(same, hc, cfg)
    for want, one in zip(dp.hyper_write(same, res, post, y), got):
        np.testing.assert_allclose(np.asarray(one), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("positions,hidden,n,dtype,why", [
    (4096, 3584, 4, jnp.float32, ""),
    (1024, 3584, 4, jnp.float32, ""),
    (4096, 3584, 2, jnp.float32, ""),
    (4096, 3584, 4, jnp.bfloat16, "streams of bfloat16 are not float32"),
    (4096, 3520, 4, jnp.float32, "hidden 3520 is not whole 128-lane vregs"),
    (4000, 3584, 4, jnp.float32, "4000 positions are not whole tiles of 128"),
    (4096, 3584, 1, jnp.float32,
     "the maps of 1 streams are not whole 8-row vregs"),
    (4096, 8192, 4, jnp.float32,
     "a step's blocks take 90177536 of 67108864 bytes of VMEM")])
def test_what_the_kernels_take_and_why_not(positions, hidden, n, dtype, why):
    assert hs.declines(positions, hidden, n, dtype) == why


@pytest.mark.parametrize("backend,hidden,said,kernels", [
    ("cpu", 3584, "xla (not a TPU; 4 streams, 20 Sinkhorn rounds)", False),
    ("tpu", 3584, "pallas-streams (tile=128, 4 streams, 20 Sinkhorn rounds)",
     True),
    ("tpu", 64, "xla (hidden 64 is not whole 128-lane vregs; 4 streams, 20 "
                "Sinkhorn rounds)", False)])
def test_the_residual_path_says_which_passes_run(monkeypatch, backend, hidden,
                                                 said, kernels):
    """Picked from backend and shapes alone; ``/debug/sessionz``'s
    ``head_cores`` and the log carry the choice, with the kernels' reason
    where the ``jax.numpy`` functions run."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    dp.announce_core.cache_clear()
    cfg = xb.XingConfig(hidden=hidden)
    assert xb.residual_path(4096, cfg) is kernels
    assert dp.announced_cores()["residual path"] == f"{said} (backend={backend})"
    dp.announce_core.cache_clear()


def test_the_backbone_over_the_kernels_equals_the_backbone_over_the_plain_forms(
        monkeypatch):
    """``backbone_scores`` at small widths (hidden 128, eight windows: one
    tile) with the two kernels under every sublayer, through the
    interpreter, against the same tree over the ``jax.numpy`` functions:
    ``hyper_sublayer`` hands the squares and the maps from pass to pass as
    the plain path does."""
    cfg = xb.XingConfig(
        hidden=128, layers=2, dense_layers=1, heads=2, q_rank=32, kv_rank=32,
        nope_dim=16, rope_dim=8, v_dim=16, dense_width=64, experts=4, top_k=2,
        expert_width=32, init_depth=2, operand_dtype=jnp.float32)
    params = xb.init_backbone(jax.random.key(1), cfg)
    rng = np.random.default_rng(2)
    lengths = jnp.asarray(rng.integers(1, 17, 8), jnp.int32)
    win = jnp.asarray(rng.normal(0, 1, (8, 16, cfg.in_dim)), jnp.float32)
    plain = jax.jit(lambda p, w, l: xb.backbone_scores(p, w, l, cfg))(
        params, win, lengths)
    monkeypatch.setattr(xb, "residual_path", lambda positions, cfg: True)
    for name in ("maps_and_read", "write"):
        monkeypatch.setattr(hs, name, functools.partial(getattr(hs, name),
                                                        interpret=True))
    fused = jax.jit(lambda p, w, l: xb.backbone_scores(p, w, l, cfg))(
        params, win, lengths)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(plain), atol=1e-5)
    assert float(jnp.std(plain)) > 1e-3
