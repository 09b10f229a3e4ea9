"""The blocked attention kernel (ops/pallas/block_attention.py) in the
Pallas interpreter against the einsum form in query blocks
(models/mellum_backbone.core_by_einsums), which is what runs off the TPU:
both layer kinds and both forms (the sweep, and the one visit of a narrow
band), windows that are and are not whole blocks, the band's edge read key by
key."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from igaming_platform_tpu.models import mellum_backbone as mb
from igaming_platform_tpu.ops.pallas import block_attention as ba

HEADS, KV, HD = 8, 2, 128


def operands(window: int, rows: int = 2, seed: int = 0, dtype=jnp.bfloat16):
    key = jax.random.key(seed)
    p = rows * window
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape)
    q = draw(1, (p, HEADS * HD))
    k = draw(2, (p, KV * HD)).astype(dtype)
    v = draw(3, (p, KV * HD)).astype(dtype)
    gain = 1.0 + 0.1 * draw(4, (HD,))
    return q, k, v, gain


def tables(window: int, kind: str = mb.FULL):
    return mb.angle_tables(mb.MellumConfig(), window)[kind]


BF16, F32 = jnp.bfloat16, jnp.float32
# (window, block, band, operands' dtype, the form that runs: the one visit's
# (query rows, slab keys) or None for the sweep)
SMALL = [(window, block, band, BF16, ...)
         for band in (16, 5, 40, None)
         for window, block in ((64, 16), (64, 32), (56, 16), (40, 16), (16, 16),
                               (24, 32))]
NARROW = [
    # kexaone's sliding layer, and a window of three query blocks
    (2048, None, 128, BF16, (128, 256)), (384, None, 128, BF16, (128, 256)),
    (384, None, 128, F32, (128, 256)),
    # bands that are not whole lanes; the windows padded to whole query
    # blocks (576, 1040) and cut
    (512, None, 96, BF16, (96, 256)), (1000, None, 200, BF16, (208, 512)),
    (1000, None, 200, F32, (208, 512)),
    # a window that is not whole query blocks
    (2000, None, 128, BF16, (128, 256)),
    # the band at half a block, and one row wider
    (1024, None, 256, BF16, (256, 512)), (1024, None, 257, BF16, None),
    (1024, None, 257, F32, None),
    # one query block: the slab is the whole window
    (128, None, 64, BF16, (64, 128)),
]


def case_id(case) -> str:
    window, block, band, dtype, _ = case
    return f"T{window}-block{block}-band{band}-{jnp.dtype(dtype).name}"


@pytest.mark.parametrize("case", SMALL + NARROW, ids=case_id)
def test_kernel_equals_the_einsum_form_in_query_blocks(case):
    window, block, band, dtype, form = case
    if form is not ...:
        assert ba.one_visit(window, band, block) == form
    q, k, v, gain = operands(window, dtype=dtype)
    cos, sin = tables(window)
    widths = dict(heads=HEADS, kv_heads=KV, window=window, band=band, eps=1e-6)
    want = mb.core_by_einsums(q, k, v, cos, sin, gain, block=block, **widths)
    got = ba.block_attention(q, k, v, cos, sin, gain, block=block,
                             interpret=True, **widths)
    assert got.shape == want.shape and got.dtype == dtype
    # the kernel rounds its result once to the operands' dtype and its
    # probabilities before the division: a bfloat16 step of the values; in
    # float32 only the order of the sums differs
    tol = 0.02 if dtype == BF16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol, rtol=tol)
    if window <= 64:
        # one sweep of the whole window is the same sum
        whole = mb.core_by_einsums(q, k, v, cos, sin, gain, block=window,
                                   **widths)
        np.testing.assert_allclose(np.asarray(want), np.asarray(whole),
                                   atol=2e-3)


def kept_keys(core, window: int, band, block: int):
    """Which keys each query reads, read off the result: with ``q`` zero
    every kept key weighs the same, and key ``j``'s value is one-hot at
    channel ``j``."""
    p = window
    q = jnp.zeros((p, HEADS * HD), jnp.float32)
    k = jnp.ones((p, KV * HD), jnp.bfloat16)
    v = jnp.tile(jnp.eye(window, HD, dtype=jnp.bfloat16), (1, KV))
    cos, sin = tables(window)
    out = core(q, k, v, cos, sin, jnp.ones((HD,)), heads=HEADS, kv_heads=KV,
               window=window, band=band, eps=1e-6, block=block)
    out = np.asarray(out, np.float32).reshape(window, HEADS, HD)
    assert (out == out[:, :1]).all()  # every head reads the same keys
    return out[:, 0, :window] > 0


@pytest.mark.parametrize("core", ["einsums", "kernel"])
@pytest.mark.parametrize("band,window,block", [(16, 64, 16), (16, 64, 32),
                                               (24, 56, 16), (None, 64, 16),
                                               (40, 128, 128), (5, 56, 16)])
def test_the_band_keeps_what_the_two_inequalities_keep(core, band, window, block):
    run = (mb.core_by_einsums if core == "einsums" else
           lambda *a, **kw: ba.block_attention(*a, interpret=True, **kw))
    kept = kept_keys(run, window, band, block)
    i, j = np.arange(window)[:, None], np.arange(window)[None, :]
    brute = (j <= i) if band is None else (j <= i) & (i - j < band)
    assert (kept == brute).all()
    if band is not None:
        last = window - 1
        # the three edges, 1022 / 1023 / 1024 scaled down: the key ``band``
        # back is the first that is not read
        assert kept[last, last - (band - 2)] and kept[last, last - (band - 1)]
        assert not kept[last, last - band]


@pytest.mark.parametrize("window,band,block,visited,square", [
    (4096, 1024, 128, 252, 1024), (4096, 1024, 256, 70, 256),
    (4096, None, 256, 136, 256), (4096, None, 128, 528, 1024),
    (1024, 1024, 256, 10, 16), (16, 1024, None, 1, 1), (64, 16, 16, 7, 16)])
def test_visited_blocks_are_what_the_sweep_visits(window, band, block, visited,
                                                  square):
    blk = block or ba.block_for(window)
    assert ba.one_visit(window, band, blk) is None
    # the blocks' area, in (query, key) pairs
    assert ba.visited_blocks(window, band, block) == (visited * blk * blk,
                                                      square * blk * blk)
    n = -(-window // blk)
    i, j = np.arange(window)[:, None], np.arange(window)[None, :]
    keep = (j <= i) if band is None else (j <= i) & (i - j < band)
    keep = np.pad(keep, ((0, n * blk - window),) * 2)
    touched = keep.reshape(n, blk, n, blk).any(axis=(1, 3))
    assert touched.sum() == visited  # no block without a kept pair is visited
    for qb in range(n):
        lo, edge_end = ba.swept_blocks(qb, blk, band)
        assert touched[qb, lo:qb + 1].all() and not touched[qb, :lo].any()
        inside = keep.reshape(n, blk, n, blk)[qb, :, edge_end:qb].all()
        assert inside  # the blocks swept without a mask keep every pair


@pytest.mark.parametrize("window,band,block,form", [
    (2048, 128, None, (128, 256)), (384, 128, None, (128, 256)),
    (1000, 200, None, (208, 512)), (1024, 256, None, (256, 512)),
    (128, 64, None, (64, 128)), (64, 5, 16, (16, 64)), (56, 5, 16, (16, 64))])
def test_one_visit_reads_every_kept_key_in_its_slab(window, band, block, form):
    """The geometry the one-visit kernel computes from its program id: a
    query block's slab holds every key its rows keep, starts on a 16-row
    tile inside the padded window, and the count is the slabs' area."""
    assert ba.one_visit(window, band, block) == form
    qb, keys = form
    n = -(-window // qb)
    assert qb % 16 == 0 and qb >= band and keys <= n * qb
    for i in range(n):
        first = max((i + 1) * qb - keys, 0)
        assert first % 16 == 0 and first + keys <= n * qb
        assert first <= max(i * qb - band + 1, 0)  # the first row's last key
        assert first + keys >= (i + 1) * qb        # the last row's own key
    assert ba.visited_blocks(window, band, block) == (n * qb * keys,
                                                      (n * qb) ** 2)


def test_a_share_summed_over_layers_of_two_forms_is_a_share_of_the_squares():
    """``visited_blocks`` counts pairs: mellum's layers all sweep by 512, so
    its share is the blocks' (99 of 256 at 4,096 events); kexaone's sliding
    layers run the one visit, 16 slabs of 128 x 256 where the sweep visited
    7 of 16 blocks of 512 x 512, beside a full layer whose count is the
    sweep's."""
    from igaming_platform_tpu.models import kexaone_backbone as kb
    from igaming_platform_tpu.models import phi4flash_backbone as pb

    cell = 512 * 512
    assert ba.visited_blocks(4096, 1024) == (21 * cell, 64 * cell)
    assert ba.visited_blocks(4096, None) == (36 * cell, 64 * cell)
    assert mb.key_blocks(mb.MellumConfig(), 4096) == (99 * cell, 256 * cell)
    assert ba.visited_blocks(2048, None) == (10 * cell, 16 * cell)
    # 16 slabs of 128 x 256: the area of 2 blocks where the sweep visited 7
    assert ba.visited_blocks(2048, 128) == (2 * cell, 16 * cell)
    assert ba.one_row(2048) == (4 * cell, 16 * cell)
    visited, square = kb.key_blocks(kb.KExaoneConfig(), 2048)
    # four sliding layers of 2 blocks' area, the full one's 10, the module's 4
    assert (visited, square) == ((4 * 2 + 10 + 4) * cell, 6 * 16 * cell)
    # phi4flash's band of 512 keeps the sweep's count: 41.667%
    assert pb.key_blocks(pb.Phi4FlashConfig(), 2048) == ((8 * 7 + 4) * cell,
                                                         9 * 16 * cell)


@pytest.mark.parametrize("change,why", [
    (dict(heads=6), "6 heads over 4 key heads"),
    (dict(hd=64), "head width 64 is not whole 128-lane vregs"),
    (dict(window=48), "positions are not whole windows of 48"),
    (dict(dtype=jnp.float16), "operands float16"),
    (dict(window=1 << 17, p=1 << 18), "of VMEM"),
    (dict(window=1 << 17, p=1 << 18, band=128), "of VMEM"),
    (dict(window=2048, band=128), ""),
    (dict(), "")])
def test_declines_says_what_the_kernel_takes(change, why):
    heads, kv = change.get("heads", 32), 4
    hd, window = change.get("hd", 128), change.get("window", 4096)
    p, dt = change.get("p", 8192), change.get("dtype", jnp.bfloat16)
    said = ba.declines(jax.ShapeDtypeStruct((p, heads * hd), jnp.float32),
                       jax.ShapeDtypeStruct((p, kv * hd), dt),
                       jax.ShapeDtypeStruct((p, kv * hd), dt),
                       heads=heads, kv_heads=kv, window=window,
                       band=change.get("band"))
    assert (why in said) if why else said == ""


# -- the latent form -------------------------------------------------------------


def latent_operands(window: int, heads: int, nope: int, rope: int, dv: int,
                    dtype, rows: int = 2, seed: int = 0):
    from igaming_platform_tpu.models import decoder_parts as dp

    key = jax.random.key(seed)
    p = rows * window
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape)
    q = draw(1, (p, heads * (nope + rope)))
    kvb = draw(2, (p, heads * (nope + dv))).astype(dtype)
    k_rope = draw(3, (p, rope)).astype(dtype)
    cos, sin = dp.rope_angles(rows, window, rope, 1e4)
    widths = dict(heads=heads, nope=nope, rope=rope, dv=dv, window=window)
    return (q, kvb, k_rope, cos.reshape(p, -1), sin.reshape(p, -1)), widths


# (window, block, heads, nope, rope, dv, interleave, scale_by, operands' dtype)
LATENT = [
    # longcat's widths: a unit of two heads, the second 64 lanes off a vreg
    (64, 16, 4, 128, 64, 128, True, 1.0, BF16),
    (64, 16, 4, 128, 64, 128, False, 1.0, BF16),
    (64, 16, 4, 128, 64, 128, True, 1.0, F32),
    (64, 16, 4, 128, 64, 128, False, 1.0, F32),
    # a softmax scale that is not the widths' alone (a rotary scaling's factor)
    (64, 32, 2, 128, 64, 128, True, 0.7, BF16),
    (64, 32, 2, 128, 64, 128, False, 1.3, F32),
    # windows padded to whole query blocks (64, 48) and cut
    (56, 16, 2, 128, 64, 128, True, 1.0, BF16),
    (40, 16, 2, 128, 64, 128, False, 0.7, BF16),
    # one query block a window: the diagonal alone
    (16, 16, 2, 128, 64, 128, True, 1.0, BF16),
    # a unit of one head (every width whole vregs), and of two with a wider
    # rotary part beside narrower values: odd lane offsets on the q side only
    (64, 16, 3, 128, 128, 128, True, 1.0, BF16),
    (64, 16, 3, 128, 128, 128, False, 1.0, BF16),
    (48, 16, 2, 256, 64, 128, True, 1.0, BF16),
    (48, 16, 2, 128, 192, 256, False, 1.0, F32),
    # the block the cell runs (512), a window of two of them and one padded
    (1024, None, 2, 128, 64, 128, True, 1.0, BF16),
    (1000, None, 2, 128, 64, 128, True, 1.0, F32),
]


def latent_id(case) -> str:
    window, block, heads, nope, rope, dv, interleave, scale_by, dtype = case
    return (f"T{window}-block{block}-{heads}x{nope}+{rope}/{dv}-"
            f"{'interleaved' if interleave else 'halves'}-x{scale_by:g}-"
            f"{jnp.dtype(dtype).name}")


@pytest.mark.parametrize("case", LATENT, ids=latent_id)
def test_latent_kernel_equals_the_three_einsums_in_query_blocks(case):
    from igaming_platform_tpu.models import decoder_parts as dp

    window, block, heads, nope, rope, dv, interleave, scale_by, dtype = case
    operands, widths = latent_operands(window, heads, nope, rope, dv, dtype,
                                       rows=2 if window <= 64 else 1)
    assert ba.latent_declines(*operands[:2], **widths) == ""
    said = dict(interleave=interleave, scale_by=scale_by, block=block)
    want = dp.latent_core_by_einsums(*operands, **widths, **said)
    got = ba.latent_block_attention(*operands, **widths, **said, interpret=True)
    assert got.shape == want.shape and got.dtype == dtype
    # the grouped kernel's tolerance: a bfloat16 step of the values where the
    # result is rounded once and the probabilities before the division; in
    # float32 only the order of the sums differs
    tol = 0.02 if dtype == BF16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol, rtol=tol)
    if window <= 64:
        # the other pairing is another function: the kernel turned the one
        # it was told
        other = dp.latent_core_by_einsums(*operands, **widths, block=block,
                                          interleave=not interleave,
                                          scale_by=scale_by)
        assert np.abs(np.asarray(got, np.float32) - np.asarray(other)).max() > 10 * tol


def test_latent_kernel_reads_the_keys_at_or_before_the_query():
    """With ``q`` zero every kept key weighs the same, and key ``j``'s value
    is one-hot at channel ``j``: the result says which keys each query
    read, every head the same."""
    window, heads, nope, rope, dv = 64, 2, 128, 64, 128
    (q, kvb, k_rope, cos, sin), widths = latent_operands(window, heads, nope, rope,
                                                        dv, BF16, rows=1)
    head = jnp.concatenate([jnp.ones((window, nope), BF16),
                            jnp.eye(window, dv, dtype=BF16)], axis=1)
    out = ba.latent_block_attention(jnp.zeros_like(q), jnp.tile(head, (1, heads)),
                                    k_rope, cos, sin, **widths, block=16,
                                    interleave=True, interpret=True)
    out = np.asarray(out, np.float32).reshape(window, heads, dv)
    assert (out == out[:, :1]).all()
    i, j = np.arange(window)[:, None], np.arange(window)[None, :]
    assert ((out[:, 0, :window] > 0) == (j <= i)).all()


@pytest.mark.parametrize("change,why", [
    (dict(nope=64), "widths 64 + 64 / 128 are not whole 128-lane vregs"),
    (dict(dv=192), "widths 128 + 64 / 192 are not whole 128-lane vregs"),
    (dict(rope=32), "beside a rotary part of whole 64-lane halves"),
    (dict(heads=15), "15 heads are not whole units of 2"),
    (dict(heads=15, rope=128), ""),
    (dict(window=48), "positions are not whole windows of 48"),
    (dict(kvb_heads=8), "against q"),
    (dict(dtype=jnp.float16), "operands float16"),
    (dict(dtype=jnp.float32), ""),
    (dict(q_dtype=jnp.int32), "q int32"),
    (dict(window=1 << 17, p=1 << 18), "of VMEM"),
    (dict(window=16, p=4096), ""),
    (dict(), "")])
def test_latent_declines_says_what_the_kernel_takes(change, why):
    heads, nope = change.get("heads", 16), change.get("nope", 128)
    rope, dv = change.get("rope", 64), change.get("dv", 128)
    window, p = change.get("window", 2048), change.get("p", 4096)
    dt = change.get("dtype", jnp.bfloat16)
    shape = jax.ShapeDtypeStruct
    said = ba.latent_declines(
        shape((p, heads * (nope + rope)), change.get("q_dtype", jnp.float32)),
        shape((p, change.get("kvb_heads", heads) * (nope + dv)), dt),
        heads=heads, nope=nope, rope=rope, dv=dv, window=window)
    assert (why in said) if why else said == ""
