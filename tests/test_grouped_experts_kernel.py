"""The Pallas grouped-expert kernels (ops/pallas/grouped_experts.py)
against their golden reference, ``lax.ragged_dot`` + ``silu`` * up, on the
CPU through the Pallas interpreter, at small shapes ``supports`` accepts
and under every kind of routing skew the schedule has a case for.

And ``combine``, the results' way back to position order, against the XLA
expressions it replaces in ``models/expert_layer.grouped_experts`` (a row
gather and a weighted sum where every slot is taken; a row gather a slot
under a ``where`` for a share), and the experts' sizes against
``jnp.bincount``.

Nothing here is a time: the chip numbers are in PERF.md (PR 35, PR 37), and
tests/test_chip_compile.py compiles the kernels at the cells' sizes for a
described v5e.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from igaming_platform_tpu.models import decoder_parts as dp  # noqa: E402
from igaming_platform_tpu.models import expert_layer as el  # noqa: E402
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


# The second shape the kernels run (the ``lfm2`` head's kind: PERF.md, PR
# 43): 64 experts, a width that is a multiple of 128 and not 768, 4 a
# position; its column tiles, its schedule's visits and ``combine``'s ``k``
# take other values than the shape the kernels were written for.
MANY = (64, 128, 384)  # experts, hidden, width


def _ragged_sizes(experts: int, rows: int, seed: int = 12) -> list[int]:
    """``rows`` dealt over ``experts`` unevenly, a third of them empty."""
    rng = np.random.default_rng(seed)
    p = rng.random(experts) ** 3 * (rng.random(experts) > 1 / 3)
    sizes = rng.multinomial(rows, p / p.sum())
    assert (sizes == 0).sum() >= experts // 4
    return sizes.tolist()


MANY_SKEWS = {
    "64-experts-uniform": (1024, [16] * 64),
    "64-experts-ragged": (768, _ragged_sizes(64, 768)),
    "64-experts-all-on-one": (512, [0] * 41 + [512] + [0] * 22),
    "64-experts-fewer-rows-than-experts": (40, ([1, 0, 2, 0] * 16)[:64][:-1] + [
        40 - sum(([1, 0, 2, 0] * 16)[:63])]),
}
SKEWS.update(MANY_SKEWS)


def shape_of(skew: str) -> tuple[int, int, int]:
    return MANY if skew in MANY_SKEWS else (EXPERTS, HIDDEN, WIDTH)


def operands(rows: int, seed: int = 0, shape=(EXPERTS, HIDDEN, WIDTH)):
    experts, hidden, width = shape
    k = jax.random.split(jax.random.key(seed), 4)
    bf16 = jnp.bfloat16

    def matrix(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(bf16)

    return (matrix(k[0], (rows, hidden), 1.0),
            matrix(k[1], (experts, hidden, width), hidden),
            matrix(k[2], (experts, hidden, width), hidden),
            matrix(k[3], (experts, width, hidden), width))


def ragged(lhs, w, sizes):
    return jax.lax.ragged_dot(lhs, w, sizes, preferred_element_type=jnp.float32)


def f32(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("skew", list(SKEWS))
def test_gate_up_equals_two_ragged_dots_and_silu(skew):
    rows, sizes = SKEWS[skew]
    xs, wg, wu, _ = operands(rows, shape=shape_of(skew))
    sizes = jnp.asarray(sizes, jnp.int32)
    assert ge.supports(xs, wg) and int(sizes.sum()) == rows
    assert sizes.shape == (wg.shape[0],)
    got = ge.gate_up(xs, wg, wu, sizes, interpret=True)
    want32 = jax.nn.silu(ragged(xs, wg, sizes)) * ragged(xs, wu, sizes)
    assert got.shape == (rows, wg.shape[2]) and got.dtype == jnp.bfloat16
    # float32 accumulation may differ in order, and one rounding to
    # bfloat16 follows: at most one unit in the last place of bfloat16
    np.testing.assert_allclose(f32(got), np.asarray(want32),
                               atol=2e-5, rtol=2 ** -7)


@pytest.mark.parametrize("skew", list(SKEWS))
def test_down_equals_a_ragged_dot(skew):
    rows, sizes = SKEWS[skew]
    xs, wg, wu, wd = operands(rows, seed=1, shape=shape_of(skew))
    sizes = jnp.asarray(sizes, jnp.int32)
    mid = (jax.nn.silu(ragged(xs, wg, sizes))
           * ragged(xs, wu, sizes)).astype(jnp.bfloat16)
    got = ge.down(mid, wd, sizes, interpret=True)
    want = ragged(mid, wd, sizes)
    assert got.shape == (rows, wd.shape[2]) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()), rtol=0)


@pytest.mark.parametrize("skew", ["several-empty", "not-multiples-of-the-tile",
                                  "all-on-one", "64-experts-ragged"])
def test_a_row_is_multiplied_by_its_own_expert_alone(skew):
    """Every expert's weights differ, so a row computed with a neighbour's
    (a mask off by one at a group boundary) shows at once: each row against
    a plain product with the expert the sizes give it."""
    rows, sizes = SKEWS[skew]
    xs, wg, wu, wd = operands(rows, seed=2, shape=shape_of(skew))
    expert = np.repeat(np.arange(len(sizes)), sizes)
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


@pytest.mark.parametrize("slots", [2, 3, 8])
@pytest.mark.parametrize("skew", ["not-multiples-of-the-tile",
                                  "64-experts-ragged", "64-experts-uniform"])
def test_schedule_visits_every_tile_an_expert_touches_and_no_other(skew, slots):
    rows, sizes = SKEWS[skew]
    EXPERTS = len(sizes)
    tm = 256
    starts, ends, group, tile, first, slot, fetch, prime, n = (
        np.asarray(a) for a in ge._schedule(jnp.asarray(sizes, jnp.int32),
                                            rows, tm, slots))
    want = [(e, t) for e in range(EXPERTS) if sizes[e]
            for t in range(starts[e] // tm, (ends[e] - 1) // tm + 1)]
    assert list(zip(group[:n[0]], tile[:n[0]])) == want
    assert len(group) == rows // tm + EXPERTS - 1 >= n[0]
    # steps past the last visit repeat it: no block moves, nothing runs
    assert set(zip(group[n[0]:], tile[n[0]:])) <= {want[-1]}
    assert first[n[0]:].sum() == 0
    # an expert's first visit is where its weights are waited for; the
    # experts with rows take the ring's slots in turn; the first grid step
    # fetches the first ``slots - 1`` of them and each first visit the
    # ``slots - 1``-th after its own, so every expert with rows is fetched
    # once, ``slots - 1`` first visits before it is waited for
    turns = np.flatnonzero(first)
    with_rows = [e for e in range(EXPERTS) if sizes[e]]
    assert group[turns].tolist() == with_rows
    assert slot[turns].tolist() == [r % slots for r in range(len(with_rows))]
    ahead = slots - 1
    assert prime.tolist() == (with_rows[:ahead] + [-1] * ahead)[:ahead]
    assert fetch[turns].tolist() == (with_rows[ahead:] + [-1] * ahead)[
        :len(with_rows)]


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


@pytest.mark.parametrize("pairs,experts,width", [(32768, 128, 768),
                                                 (16384, 64, 1536)],
                         ids=["keye-128x768-top8", "lfm2-64x1536-top4"])
def test_supports_the_cells_shapes(pairs, experts, width):
    xs = jax.ShapeDtypeStruct((pairs, 2048), jnp.bfloat16)
    assert ge.supports(xs, jax.ShapeDtypeStruct((experts, 2048, width),
                                                jnp.bfloat16))
    mid = jax.ShapeDtypeStruct((pairs, width), jnp.bfloat16)
    assert ge.supports(mid, jax.ShapeDtypeStruct((experts, width, 2048),
                                                 jnp.bfloat16))
    # two slots of an expert's gate and up: 12.6 and 25.2 MB of the 52.4
    assert 2 * 2 * 2048 * width * 2 <= ge._VMEM_CAP // 2


# -- the feed: a ring of weight slots, and the rows brought in by the kernel ---------

# The two cells' shapes cut down, ratios kept (keye 128 x 768 at 8 a
# position -> 8 x 256 at 2; lfm2 64 x 1,536 at 4 -> 4 x 512 at 1), at the
# hidden size the rows' way in is written for: experts, hidden, width, pairs
# a position.
FED = {"keye-cut": (8, 2048, 256, 2), "lfm2-cut": (4, 2048, 512, 1)}

# pairs, sizes: what the ring and the row tiles have cases for
FED_LOADS = {
    "keye-cut": {
        "one-holds-most": (512, [3, 2, 490, 1, 4, 5, 6, 1]),
        "runs-of-empty": (512, [0, 0, 200, 0, 0, 0, 300, 12]),
        "ends-on-tile-boundaries": (768, [256, 0, 128, 128, 0, 64, 192, 0]),
        "ends-off-tile-boundaries": (512, [130, 1, 61, 3, 127, 129, 60, 1]),
        "under-one-tile": (40, [5, 0, 7, 8, 0, 10, 9, 1]),
    },
    "lfm2-cut": {
        "one-holds-most": (320, [10, 300, 4, 6]),
        "runs-of-empty": (320, [0, 0, 320, 0]),
        "ends-on-tile-boundaries": (512, [256, 64, 0, 192]),
        "ends-off-tile-boundaries": (320, [100, 1, 199, 20]),
        "under-one-tile": (24, [1, 0, 20, 3]),
    },
}
FED_CASES = [(shape, load) for shape in FED for load in FED_LOADS[shape]]


def fed_operands(shape: str, load: str, seed: int = 3):
    """Positions, each sorted row's position (a permutation of the pairs,
    as ``order // k`` is), the three stacked weights and the sizes."""
    experts, hidden, width, k = FED[shape]
    pairs, sizes = FED_LOADS[shape][load]
    x, wg, wu, wd = operands(pairs // k, seed, (experts, hidden, width))
    rows = jnp.asarray(np.random.default_rng(seed).permutation(pairs) // k,
                       jnp.int32)
    return x, rows, wg, wu, wd, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("slots", [2, 3, "by-the-shapes"])
@pytest.mark.parametrize("shape,load", FED_CASES)
def test_the_feed_changes_no_bit_of_gate_up(shape, load, slots):
    """Rows brought together inside the kernel out of the unsorted
    positions, weights through a ring of 2, 3 or as many slots as the
    shapes give (more slots than experts with rows, and fewer): the same
    bits as the kernel fed a sorted copy through two slots, which is
    ``lax.ragged_dot``'s result to a rounding."""
    x, rows, wg, wu, _, sizes = fed_operands(shape, load)
    assert ge.takes_rows(x, rows, wg) and int(sizes.sum()) == rows.shape[0]
    tm, ts = ge._tiles(rows.shape[0])
    if slots == "by-the-shapes":
        got = ge.gate_up(x, wg, wu, sizes, rows=rows, interpret=True)
    else:
        got = ge._gate_up(x, wg, wu, sizes, rows, tm=tm, ts=ts, slots=slots,
                          interpret=True)
    xs = x[rows]
    two = ge._gate_up(xs, wg, wu, sizes, tm=tm, ts=ts, slots=2, interpret=True)
    np.testing.assert_array_equal(f32(got), f32(two))
    want32 = jax.nn.silu(ragged(xs, wg, sizes)) * ragged(xs, wu, sizes)
    np.testing.assert_allclose(f32(got), np.asarray(want32),
                               atol=2e-5, rtol=2 ** -7)


@pytest.mark.parametrize("slots", [2, 3, "by-the-shapes"])
@pytest.mark.parametrize("shape,load", FED_CASES)
def test_the_ring_changes_no_bit_of_down(shape, load, slots):
    x, rows, wg, wu, wd, sizes = fed_operands(shape, load, seed=4)
    tm, ts = ge._tiles(rows.shape[0])
    mid = ge.gate_up(x[rows], wg, wu, sizes, interpret=True)
    if slots == "by-the-shapes":
        got = ge.down(mid, wd, sizes, interpret=True)
    else:
        got = ge._down(mid, wd, sizes, tm=tm, ts=ts, slots=slots,
                       interpret=True)
    want = ragged(mid, wd, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()), rtol=0)
    two = ge._down(mid, wd, sizes, tm=tm, ts=ts, slots=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(two))


def test_packed_words_hold_a_rows_two_halves_lane_for_lane():
    x = operands(16, 5, (1, 2048, 128))[0]
    words = np.asarray(ge._packed(x))
    assert words.shape == (16, 8, 128) and words.dtype == np.uint32
    bits = np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).astype(np.uint32)
    np.testing.assert_array_equal(words.reshape(16, 1024) & 0xFFFF, bits[:, :1024])
    np.testing.assert_array_equal(words.reshape(16, 1024) >> 16, bits[:, 1024:])


@pytest.mark.parametrize("why,positions,pairs,hidden,experts,width", [
    ("a row is not whole tiles of words", 256, 512, 1024, 8, 256),
    ("more sorted rows than scalar memory holds", 65536, 131072, 2048, 8, 256),
    ("the positions do not fit beside the ring", 16384, 65536, 2048, 64, 1536),
    ("a shape the kernels refuse", 256, 512, 2048, 8, 200),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_takes_rows_refuses(why, positions, pairs, hidden, experts, width):
    x = jax.ShapeDtypeStruct((positions, hidden), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((pairs,), jnp.int32)
    w = jax.ShapeDtypeStruct((experts, hidden, width), jnp.bfloat16)
    assert not ge.takes_rows(x, rows, w), why


@pytest.mark.parametrize("pairs,experts,width,fed", [
    (32768, 128, 768, "tm=256, ts=64, slots=4/4, rows=in-kernel"),
    (16384, 64, 1536, "tm=256, ts=64, slots=4/4, rows=in-kernel"),
], ids=["keye-128x768-top8", "lfm2-64x1536-top4"])
def test_the_choices_at_the_published_shapes_are_pinned(pairs, experts, width, fed):
    """What the feed resolves to at the two cells' shapes (4,096 positions
    of 2,048), so that neither cell's kernel changes silently with the
    next edit: the chip numbers in PERF.md (PR 44) were read with these."""
    assert ge.feed(pairs, 2048, experts, width, positions=4096) == fed
    shape = jax.ShapeDtypeStruct
    wg = shape((experts, 2048, width), jnp.bfloat16)
    wd = shape((experts, width, 2048), jnp.bfloat16)
    assert ge._tiles(pairs) == (256, 64)
    assert (ge._slots(wg, wg), ge._slots(wd)) == (4, 4)
    assert ge.takes_rows(shape((4096, 2048), jnp.bfloat16),
                         shape((pairs,), jnp.int32), wg)
    # given a sorted copy (no positions) the rows are what the caller gathered
    assert ge.feed(pairs, 2048, experts, width).endswith("rows=gathered")
    # pangu's share stays off the kernels (63 MB a slot): PERF.md, Open question 14
    assert not ge.supports(shape((2048, 7680), jnp.bfloat16),
                           shape((8, 7680, 2048), jnp.bfloat16))


def test_slots_are_read_from_the_shapes():
    shape = jax.ShapeDtypeStruct
    w = lambda k, n: shape((8, k, n), jnp.bfloat16)
    assert ge._slots(w(128, 256)) == ge._MOST_SLOTS == 4  # small matrices: the cap
    assert ge._slots(w(2048, 1536), w(2048, 1536)) == 4   # 12.6 MB a slot: 4 fit
    assert ge._slots(w(2048, 2048), w(2048, 2048)) == 3   # 16.8 MB a slot
    assert ge._slots(w(2048, 3072), w(2048, 3072)) == 2   # 25.2 MB: what supports allows
    assert ge.supports(shape((512, 2048), jnp.bfloat16), w(2048, 3072))


@pytest.mark.parametrize("sizes,slots,waits", [
    ([256] * 8, 2, 1), ([256] * 8, 4, 1),          # products cover every fetch
    ([2000, 10, 10, 10, 10, 10], 2, 5),   # one ahead: the small ones wait, but the first
    ([2000, 10, 10, 10, 10, 10], 4, 3),   # three land under the large one's products
    ([0, 0, 5, 0], 4, 1), ([0, 0, 0], 2, 0),
], ids=["uniform-2", "uniform-4", "lumpy-2", "lumpy-4", "one", "none"])
def test_first_visits_that_wait_by_the_kernels_own_rule(sizes, slots, waits):
    # an expert's matrices 15 us to fetch, a row 0.064 us to multiply
    got, took = ge.first_visits_that_wait(sizes, slots, 15.0, 0.064)
    assert got == waits
    rows = sum(n + 64 for n in sizes if n)
    assert took >= max(rows * 0.064, 15.0 * (got > 0))


# -- down, its rows whole ---------------------------------------------------------


@pytest.mark.parametrize("skew,hidden", [
    ("uniform", 256), ("several-empty", 256), ("not-multiples-of-the-tile", 256),
    ("fewer-rows-than-a-sub-tile", 256), ("uniform", 1024),
    ("not-multiples-of-the-tile", 384), ("fewer-rows-than-a-sub-tile", 384),
    ("several-empty", 2304), ("not-multiples-of-the-tile", 2304)])
def test_down_writes_the_same_numbers_with_its_rows_whole(skew, hidden):
    """[rows, pitch, 128], a row's ``hidden / 128`` lane chunks in its first
    sublanes and every row on a sublane tile: pitch 8 at hidden 256 (two
    chunks) and 384 (three), 8 at 1,024 (the pitch is the row), 24 at
    mellum's 2,304 (eighteen). What the sublanes past a row's chunks hold
    is nobody's."""
    rows, sizes = SKEWS[skew]
    xs = operands(rows)[0]
    wd = operands(rows, seed=1, shape=(EXPERTS, hidden, HIDDEN))[3]  # [E, 128, hidden]
    sizes = jnp.asarray(sizes, jnp.int32)
    chunks = hidden // 128
    pitch = {256: 8, 384: 8, 1024: 8, 2304: 24}[hidden]
    assert ge._pitch(chunks) == pitch
    plain = ge.down(xs, wd, sizes, interpret=True)
    whole = ge.down(xs, wd, sizes, whole_rows=True, interpret=True)
    assert whole.shape == (rows, pitch, 128) and whole.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(whole)[:, :chunks].reshape(rows, hidden), np.asarray(plain))


# -- combine: the way back to position order --------------------------------------


def xla_every_slot(ys, rows, weights):
    """What ``grouped_experts`` does where every expert is held: a row
    gather and a weighted sum over a position's slots."""
    p, k = rows.shape
    return jnp.sum(ys[rows.reshape(-1)].reshape(p, k, -1) * weights[..., None],
                   axis=1)


def xla_some_slots(ys, rows, weights, take, onto=None):
    """What one pass of a share does: a row gather a slot, selected."""
    p, k = rows.shape
    y = jnp.zeros((p, ys.shape[1]), jnp.float32) if onto is None else onto
    for j in range(k):
        y = y + jnp.where(take[:, j, None], ys[rows[:, j]], 0.0) * weights[:, j, None]
    return y


def results(m: int, hidden: int, seed: int = 0):
    return jax.random.normal(jax.random.key(seed), (m, hidden), jnp.float32)


def uneven_weights(p: int, k: int, seed: int = 1):
    """Weights over four decades, so a slot left out or added twice shows."""
    return 10.0 ** jax.random.uniform(jax.random.key(seed), (p, k), jnp.float32,
                                      -3.0, 1.0)


def at_its_pitch(ys, filler=0.0):
    """``ys`` [M, hidden] as ``down(..., whole_rows=True)`` hands it over:
    [M, pitch, 128], the sublanes past a row's chunks holding ``filler``."""
    m, hidden = ys.shape
    chunks = hidden // 128
    return jnp.pad(ys.reshape(m, chunks, 128),
                   ((0, 0), (0, ge._pitch(chunks) - chunks), (0, 0)),
                   constant_values=filler)


@pytest.mark.parametrize("positions,k,hidden,whole_rows", [
    (128, 8, 1024, False), (128, 8, 1024, True), (64, 2, 2048, True),
    (192, 4, 2048, True), (128, 8, 2304, True), (64, 2, 640, False),
    (64, 2, 640, True), (64, 4, 384, False), (64, 4, 384, True)],
    ids=["k8", "k8-rows-whole", "k2-one-tile", "k4-rows-whole",
         "mellum-18-chunks-rows-whole", "5-chunks", "5-chunks-rows-whole",
         "3-chunks-under-a-tile", "3-chunks-rows-whole"])
def test_combine_every_slot_equals_gather_and_weighted_sum(positions, k, hidden,
                                                           whole_rows):
    """(a) a full permutation with uneven weights, from ``ys`` as [M, hidden]
    and as ``down(..., whole_rows=True)`` hands it over: at a hidden size of
    whole sublane tiles [M, hidden / 128, 128], at any other a row at its
    pitch with ``hidden`` saying how much of it is the row."""
    m = positions * k
    ys = results(m, hidden)
    rows = jnp.asarray(np.random.default_rng(3).permutation(m).reshape(
        positions, k).astype(np.int32))
    w = uneven_weights(positions, k)
    assert ge.combine_supports(ys, rows)
    given = at_its_pitch(ys) if whole_rows else ys
    assert ge.combine_supports(given, rows)
    got = ge.combine(given, rows, w, hidden=hidden, interpret=True)
    want = xla_every_slot(ys, rows, w)
    assert got.shape == (positions, hidden) and got.dtype == jnp.float32
    # float32 summation order alone: eight terms of up to 10 x |ys|
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6 * float(jnp.abs(want).max()), rtol=0)
    # a slot left out would show
    less = xla_every_slot(ys, rows, w.at[5, 1].set(0.0))
    assert float(jnp.abs(less[5] - got[5]).max()) > 1e-3
    if hidden % 1024 == 0:
        # the pitch is the row: nothing needs saying
        np.testing.assert_array_equal(
            np.asarray(ge.combine(given, rows, w, interpret=True)), np.asarray(got))


@pytest.mark.parametrize("k,hidden", [(8, 2304), (2, 640), (4, 384)])
def test_what_a_rows_padding_holds_reaches_no_output(k, hidden):
    """``down`` never writes the sublanes between a row's last chunk and its
    pitch, so they hold whatever the memory held: NaN planted in all of
    them, the result is finite and the one the zero-padded rows give, to
    the bit."""
    positions = 64
    m = positions * k
    ys = results(m, hidden, seed=11)
    rows = jnp.asarray(np.random.default_rng(5).permutation(m).reshape(
        positions, k).astype(np.int32))
    w = uneven_weights(positions, k, seed=12)
    poisoned = at_its_pitch(ys, jnp.nan)
    chunks = hidden // 128
    assert bool(jnp.isnan(poisoned[:, chunks:]).all()) and poisoned.shape[1] > chunks
    got = np.asarray(ge.combine(poisoned, rows, w, hidden=hidden, interpret=True))
    assert got.shape == (positions, hidden) and np.isfinite(got).all()
    clean = ge.combine(at_its_pitch(ys), rows, w, hidden=hidden, interpret=True)
    np.testing.assert_array_equal(got, np.asarray(clean))


def a_share(positions: int, k: int, m: int, taken: int, seed: int = 4):
    """``taken`` slots at random, each naming a row of its own; the others
    name the last row, as ``grouped_experts``'s clipped ranks do."""
    rng = np.random.default_rng(seed)
    slots = rng.permutation(positions * k)[:taken]
    take = np.zeros(positions * k, bool)
    take[slots] = True
    rows = np.full(positions * k, m - 1, np.int32)
    rows[slots] = rng.permutation(m)[:taken]
    owed = np.zeros(m, bool)
    owed[rows[slots]] = True
    return (jnp.asarray(rows.reshape(positions, k)),
            jnp.asarray(take.reshape(positions, k)), owed)


@pytest.mark.parametrize("positions,taken", [(256, 61), (256, 0), (100, 30),
                                             (192, 512)],
                         ids=["3-percent", "none", "positions-not-a-tile",
                              "as-many-as-rows"])
def test_combine_some_slots_reads_only_the_rows_that_are_owed(positions, taken):
    """(b) 3% of the slots taken and every row that no taken slot names
    poisoned with NaN: the result is finite and equal; (c) tiles with no
    slot taken (and a call with none at all) give zeros; (d) positions that
    do not fill the last tile."""
    k, m, hidden = 8, 512, 256
    ys = results(m, hidden, seed=5)
    rows, take, owed = a_share(positions, k, m, taken)
    w = uneven_weights(positions, k, seed=6)
    assert ge.combine_supports(ys, rows, take)
    poisoned = jnp.where(jnp.asarray(owed)[:, None], ys, jnp.nan)
    got = np.asarray(ge.combine(poisoned, rows, w, take, interpret=True))
    want = np.asarray(xla_some_slots(ys, rows, w, take))
    assert got.shape == (positions, hidden) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-6 * max(np.abs(want).max(), 1),
                               rtol=0)
    nothing = ~np.asarray(take).any(axis=1)
    if taken < 100:
        # whole tiles of 64 positions hold no taken slot, or nearly none
        assert nothing.sum() >= positions // 2
    assert not got[nothing].any()  # exact zeros
    if taken:
        assert np.abs(got).max() > 1e-3


def test_combine_adds_onto_the_carry_slot_after_slot():
    k, m, hidden, positions = 4, 256, 128, 96
    ys = results(m, hidden, seed=7)
    rows, take, _ = a_share(positions, k, m, 40, seed=8)
    w = uneven_weights(positions, k, seed=9)
    carry = results(positions, hidden, seed=10)
    got = ge.combine(ys, rows, w, take, onto=carry, interpret=True)
    want = xla_some_slots(ys, rows, w, take, onto=carry)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=0)
    untouched = ~np.asarray(take).any(axis=1)
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(carry)[untouched])


F32 = jnp.float32


@pytest.mark.parametrize("why,m,hidden,positions,k,dtype,share", [
    ("bfloat16 results", 1024, 1024, 128, 8, jnp.bfloat16, False),
    ("hidden not lane-aligned", 1024, 1000, 128, 8, F32, False),
    ("positions not whole tiles", 800, 1024, 100, 8, F32, False),
    ("no rows at all", 0, 1024, 128, 8, F32, False),
    ("two buffers past the VMEM cap", 8192, 32768, 1024, 8, F32, False),
    ("a share: bfloat16 results", 512, 256, 256, 8, jnp.bfloat16, True),
    ("a share: hidden not lane-aligned", 512, 200, 256, 8, F32, True),
    ("a share: results larger than their part of VMEM", 4096, 7680, 4096, 8, F32,
     True),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_combine_supports_refuses(why, m, hidden, positions, k, dtype, share):
    ys = jax.ShapeDtypeStruct((m, hidden), jnp.dtype(dtype))
    rows = jax.ShapeDtypeStruct((positions, k), jnp.int32)
    take = jax.ShapeDtypeStruct((positions, k), jnp.bool_) if share else None
    assert not ge.combine_supports(ys, rows, take), why


@pytest.mark.parametrize("results_shape,positions,k", [
    ((65536, 24, 128), 8192, 8), ((65536, 2304), 8192, 8), ((1024, 640), 128, 8)],
    ids=["mellum-rows-whole", "mellum", "5-chunks"])
def test_combine_supports_the_mellum_cells_shape(results_shape, positions, k):
    """A row needs to start on a sublane tile, not to fill whole ones: 18
    lane chunks (mellum's 2,304) ride at a pitch of 24, 5 at 8. Every slot
    of the cell's 8,192 positions at 8 a position."""
    rows = jax.ShapeDtypeStruct((positions, k), jnp.int32)
    assert ge.combine_supports(jax.ShapeDtypeStruct(results_shape, F32), rows)


def test_combine_supports_the_lfm2_cells_shape():
    # every slot of 4,096 positions at 4 a position, rows whole
    rows = jax.ShapeDtypeStruct((4096, 4), jnp.int32)
    assert ge.combine_supports(jax.ShapeDtypeStruct((16384, 16, 128), F32), rows)


def test_combine_supports_the_cells_shapes():
    rows = jax.ShapeDtypeStruct((4096, 8), jnp.int32)
    take = jax.ShapeDtypeStruct((4096, 8), jnp.bool_)
    # keye: every slot of 4,096 positions, results with their rows whole
    assert ge.combine_supports(jax.ShapeDtypeStruct((32768, 16, 128), F32), rows)
    # the buffers are reckoned at the rows' pitch: 12.6 MB at mellum's 2,304
    assert ge._combine_rows_vmem(64, 8, 2304) == (
        2 * 8 * 64 * 24 * 128 * 4 + 2 * 64 * 2304 * 4 + 4 * 2**20)
    # pangu: one pass of a share, as many rows as ``pass_rows`` allows
    assert el.pass_rows(32768, 8, 256, 7680) == 2048
    assert ge.combine_supports(jax.ShapeDtypeStruct((2048, 7680), F32), rows, take)


@pytest.mark.parametrize("case", ["refused-every-slot", "refused-share",
                                  "taken-every-slot", "taken-share", "cpu"])
def test_the_way_back_is_chosen_from_backend_and_shapes_and_announced(
        case, monkeypatch, caplog):
    """(e) the shapes ``combine_supports`` refuses take the XLA expressions,
    on a (steered) TPU too, and a compile says once which way it took."""
    dp.announce_core.cache_clear()
    if case != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hidden = 1000 if case.startswith("refused") else 1024
    share = case.endswith("share")
    ys = jax.ShapeDtypeStruct((1024, hidden), F32)
    rows = jax.ShapeDtypeStruct((128, 8), jnp.int32)
    take = jax.ShapeDtypeStruct((128, 8), jnp.bool_) if share else None
    with caplog.at_level("INFO", logger=dp.logger.name):
        first = el._combine_by_kernel(ys, rows, take)
        assert el._combine_by_kernel(ys, rows, take) == first
    assert first == case.startswith("taken")
    way = "pallas-rows" if first else "xla-gather"
    backend = "cpu" if case == "cpu" else "tpu"
    assert [r.getMessage() for r in caplog.records] == [
        f"combine: {way} (backend={backend})"]


# -- the other cells' programs ------------------------------------------------------


def pallas_calls(jaxpr, found=None):
    """Every ``pallas_call`` equation of a jaxpr, those inside its jitted
    calls too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    pallas_calls(inner, found)
    return found


def program_of(eqn):
    """What a ``pallas_call`` hands Mosaic beside its body: grid, block
    shapes, scratch shapes, result, the VMEM it asks for; and the body
    itself, as a digest of its jaxpr's text."""
    import hashlib

    mapping = eqn.params["grid_mapping"]
    return (mapping.grid,
            [tuple(getattr(d, "block_size", d) for d in b.block_shape)
             for b in mapping.block_mappings],
            [str(getattr(a, "inner_aval", a)) for a in mapping.scratch_avals],
            [str(a) for a in eqn.params["out_avals"]],
            eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes,
            hashlib.sha256(str(eqn.params["jaxpr"]).encode()).hexdigest()[:16])


# (pairs, slots a position, positions, experts, width) at hidden 2,048, and
# what ``_combine_rows`` and the whole-rows ``_down`` were there before a row
# had a pitch (traced from commit 6812441, PR 57): where ``hidden / 128``
# divides by 8 the pitch is the row and the programs are those, body and all.
# A PR that changes these kernels renews the literals and measures the three
# cells (``keye-backbone-insession``, ``lfm2-conv-insession``,
# ``keye-deep128-insession``) against its parent.
THE_CELLS_PROGRAMS = {
    "keye": ((32768, 8, 4096, 128, 768), (
        (65,), [(32768, 16, 128), (64, 2048)],
        ["float32[2,512,16,128]", "float32[128,128]", "dma_sem[2]"],
        ["float32[4096,2048]"], 13631488, "aeb9d9887e5d2495"), (
        (255,), [(256, 768), (128, 768, 2048), (4096, 128)],
        ["bfloat16[4,768,2048]", "dma_sem[1,4]"],
        ["float32[524288,128]"], 28049408, "9a74b13bc59b3365")),
    "lfm2": ((16384, 4, 4096, 64, 1536), (
        (65,), [(16384, 16, 128), (64, 2048)],
        ["float32[2,256,16,128]", "float32[128,128]", "dma_sem[2]"],
        ["float32[4096,2048]"], 9437184, "992c5ecdf225a42c"), (
        (127,), [(256, 1536), (64, 1536, 2048), (4096, 128)],
        ["bfloat16[4,1536,2048]", "dma_sem[1,4]"],
        ["float32[262144,128]"], 41418752, "0d0ae147a83609c6")),
    "keye-deep128": ((65536, 8, 8192, 128, 768), (
        (129,), [(65536, 16, 128), (64, 2048)],
        ["float32[2,512,16,128]", "float32[128,128]", "dma_sem[2]"],
        ["float32[8192,2048]"], 13631488, "a87e3e7e9c8a60e0"), (
        (383,), [(256, 768), (128, 768, 2048), (4096, 128)],
        ["bfloat16[4,768,2048]", "dma_sem[1,4]"],
        ["float32[1048576,128]"], 28049408, "eaa37a8336e76908")),
}


@pytest.mark.parametrize("kernel", ["combine_rows", "down_rows_whole"])
@pytest.mark.parametrize("cell", list(THE_CELLS_PROGRAMS))
def test_a_pitch_leaves_the_other_cells_programs_as_they_were(cell, kernel):
    (pairs, k, positions, experts, width), combine, down = THE_CELLS_PROGRAMS[cell]
    shape = jax.ShapeDtypeStruct
    if kernel == "combine_rows":
        traced = jax.make_jaxpr(lambda ys, rows, w: ge.combine(ys, rows, w))(
            shape((pairs, 16, 128), F32), shape((positions, k), jnp.int32),
            shape((positions, k), F32))
        want = combine
    else:
        traced = jax.make_jaxpr(
            lambda mid, wd, sizes: ge.down(mid, wd, sizes, whole_rows=True))(
            shape((pairs, width), jnp.bfloat16),
            shape((experts, width, 2048), jnp.bfloat16),
            shape((experts,), jnp.int32))
        assert [a.shape for a in traced.out_avals] == [(pairs, 16, 128)]
        want = down
    (call,) = pallas_calls(traced.jaxpr)
    assert program_of(call) == want


# -- the experts' sizes -------------------------------------------------------------


def keys_of(kind: str, held: int, pairs: int = 4096):
    rng = np.random.default_rng(11)
    if kind == "uniform":
        return rng.integers(0, held, pairs)
    if kind == "zipf":
        p = 1.0 / np.arange(1, held + 1) ** 1.2
        return rng.choice(held, pairs, p=p / p.sum())
    if kind == "empty-experts":
        return rng.choice([1, 4, held - 1], pairs)
    if kind == "sentinel-keys":  # absent and padded pairs carry ``held``
        return np.where(rng.random(pairs) < 0.9, held, rng.integers(0, held, pairs))
    if kind == "all-sentinel":
        return np.full(pairs, held)
    raise ValueError(kind)


@pytest.mark.parametrize("held", [8, 128])
@pytest.mark.parametrize("kind", ["uniform", "zipf", "empty-experts",
                                  "sentinel-keys", "all-sentinel"])
def test_sizes_are_bincounts_integers_without_its_scatter(kind, held):
    keys = jnp.asarray(keys_of(kind, held), jnp.int32)
    sizes = jax.jit(lambda e: el.expert_sizes(e, held))
    got = sizes(keys)
    want = jnp.bincount(keys, length=held).astype(jnp.int32)
    assert got.dtype == jnp.int32 and got.shape == (held,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if kind in ("sentinel-keys", "all-sentinel"):
        assert int(got.sum()) < keys.shape[0]  # a key past the last bin counts nowhere
    assert "scatter" not in sizes.lower(keys).as_text()
