"""The Pallas grouped-expert kernels (ops/pallas/grouped_experts.py)
against their golden reference, ``lax.ragged_dot`` + ``silu`` * up, on the
CPU through the Pallas interpreter, at small shapes ``supports`` accepts
and under every kind of routing skew the schedule has a case for.

Nothing here is a time: the chip numbers are in PERF.md (PR 35), and
tests/test_chip_compile.py compiles the kernels at the cell's size for a
described v5e.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from igaming_platform_tpu.ops.pallas import grouped_experts as ge  # noqa: E402

EXPERTS, HIDDEN, WIDTH = 8, 128, 256

# rows, sizes (summing to rows); the row tile at these sizes is 256 rows
# in sub-tiles of 64 (``ge._tiles``)
SKEWS = {
    "uniform": (512, [64] * 8),
    "all-on-one": (512, [0, 0, 0, 512, 0, 0, 0, 0]),
    "all-on-the-last": (512, [0, 0, 0, 0, 0, 0, 0, 512]),
    "several-empty": (512, [0, 0, 100, 0, 300, 0, 112, 0]),
    "not-multiples-of-the-tile": (768, [130, 1, 254, 3, 127, 129, 60, 64]),
    "last-tile-ends-mid-group": (512, [200, 56, 100, 100, 0, 6, 20, 30]),
    "rows-not-a-multiple-of-the-tile": (320, [1, 2, 3, 150, 60, 70, 34, 0]),
    "fewer-rows-than-a-sub-tile": (40, [5, 0, 7, 8, 0, 10, 9, 1]),
    "many-tiles-one-boundary": (1024, [1000, 3, 0, 0, 0, 0, 0, 21]),
}


def operands(rows: int, seed: int = 0):
    k = jax.random.split(jax.random.key(seed), 4)
    bf16 = jnp.bfloat16

    def matrix(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(bf16)

    return (matrix(k[0], (rows, HIDDEN), 1.0),
            matrix(k[1], (EXPERTS, HIDDEN, WIDTH), HIDDEN),
            matrix(k[2], (EXPERTS, HIDDEN, WIDTH), HIDDEN),
            matrix(k[3], (EXPERTS, WIDTH, HIDDEN), WIDTH))


def ragged(lhs, w, sizes):
    return jax.lax.ragged_dot(lhs, w, sizes, preferred_element_type=jnp.float32)


def f32(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("skew", list(SKEWS))
def test_gate_up_equals_two_ragged_dots_and_silu(skew):
    rows, sizes = SKEWS[skew]
    xs, wg, wu, _ = operands(rows)
    sizes = jnp.asarray(sizes, jnp.int32)
    assert ge.supports(xs, wg) and int(sizes.sum()) == rows
    got = ge.gate_up(xs, wg, wu, sizes, interpret=True)
    want32 = jax.nn.silu(ragged(xs, wg, sizes)) * ragged(xs, wu, sizes)
    assert got.shape == (rows, WIDTH) and got.dtype == jnp.bfloat16
    # float32 accumulation may differ in order, and one rounding to
    # bfloat16 follows: at most one unit in the last place of bfloat16
    np.testing.assert_allclose(f32(got), np.asarray(want32),
                               atol=2e-5, rtol=2 ** -7)


@pytest.mark.parametrize("skew", list(SKEWS))
def test_down_equals_a_ragged_dot(skew):
    rows, sizes = SKEWS[skew]
    xs, wg, wu, wd = operands(rows, seed=1)
    sizes = jnp.asarray(sizes, jnp.int32)
    mid = (jax.nn.silu(ragged(xs, wg, sizes))
           * ragged(xs, wu, sizes)).astype(jnp.bfloat16)
    got = ge.down(mid, wd, sizes, interpret=True)
    want = ragged(mid, wd, sizes)
    assert got.shape == (rows, HIDDEN) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()), rtol=0)


@pytest.mark.parametrize("skew", ["several-empty", "not-multiples-of-the-tile",
                                  "all-on-one"])
def test_a_row_is_multiplied_by_its_own_expert_alone(skew):
    """Every expert's weights differ, so a row computed with a neighbour's
    (a mask off by one at a group boundary) shows at once: each row against
    a plain product with the expert the sizes give it."""
    rows, sizes = SKEWS[skew]
    xs, wg, wu, wd = operands(rows, seed=2)
    expert = np.repeat(np.arange(EXPERTS), sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    mid = ge.gate_up(xs, wg, wu, sizes, interpret=True)
    ys = ge.down(mid, wd, sizes, interpret=True)
    x64, mid64 = f32(xs).astype(np.float64), f32(mid).astype(np.float64)
    g = np.einsum("rk,rkn->rn", x64, f32(wg).astype(np.float64)[expert])
    u = np.einsum("rk,rkn->rn", x64, f32(wu).astype(np.float64)[expert])
    np.testing.assert_allclose(mid64, g / (1 + np.exp(-g)) * u,
                               atol=2e-5, rtol=2 ** -7)
    y = np.einsum("rn,rnk->rk", mid64, f32(wd).astype(np.float64)[expert])
    np.testing.assert_allclose(np.asarray(ys), y, atol=1e-5 * np.abs(y).max(),
                               rtol=0)


def test_schedule_visits_every_tile_an_expert_touches_and_no_other():
    rows, sizes = SKEWS["not-multiples-of-the-tile"]
    tm = 256
    starts, ends, group, tile, first, slot, following, n = (
        np.asarray(a) for a in ge._schedule(jnp.asarray(sizes, jnp.int32),
                                            rows, tm))
    want = [(e, t) for e in range(EXPERTS) if sizes[e]
            for t in range(starts[e] // tm, (ends[e] - 1) // tm + 1)]
    assert list(zip(group[:n[0]], tile[:n[0]])) == want
    assert len(group) == rows // tm + EXPERTS - 1 >= n[0]
    # steps past the last visit repeat it: no block moves, nothing runs
    assert set(zip(group[n[0]:], tile[n[0]:])) <= {want[-1]}
    assert first[n[0]:].sum() == 0
    # an expert's first visit is where its weights are waited for; the
    # expert after it (with rows) is fetched into the other slot
    turns = np.flatnonzero(first)
    assert group[turns].tolist() == [e for e in range(EXPERTS) if sizes[e]]
    assert following[turns].tolist() == group[turns].tolist()[1:] + [-1]
    assert (slot[turns][1:] != slot[turns][:-1]).all()


@pytest.mark.parametrize("why,rows,hidden,width,dtype", [
    ("hidden not lane-aligned", 512, 64, 128, "bfloat16"),
    ("width not lane-aligned", 512, 128, 32, "bfloat16"),
    ("float32 operands", 512, 128, 128, "float32"),
    ("weights too large for two slots in VMEM", 512, 8192, 2048, "bfloat16"),
])
def test_supports_refuses(why, rows, hidden, width, dtype):
    xs = jax.ShapeDtypeStruct((rows, hidden), jnp.dtype(dtype))
    w = jax.ShapeDtypeStruct((EXPERTS, hidden, width), jnp.dtype(dtype))
    assert not ge.supports(xs, w), why


def test_supports_the_cells_shapes():
    xs = jax.ShapeDtypeStruct((32768, 2048), jnp.bfloat16)
    assert ge.supports(xs, jax.ShapeDtypeStruct((128, 2048, 768), jnp.bfloat16))
    mid = jax.ShapeDtypeStruct((32768, 768), jnp.bfloat16)
    assert ge.supports(mid, jax.ShapeDtypeStruct((128, 768, 2048), jnp.bfloat16))
