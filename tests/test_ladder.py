"""The index program's ladder of padded shapes (serve/scorer.py): a 64-row
rung under the 256 one, the counter that says which rung a launch ran, and
the counters of what a launch hands over the link: one packed chunk.
"""

from __future__ import annotations

import dataclasses
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig  # noqa: E402
from igaming_platform_tpu.models import keye_backbone as kb  # noqa: E402
from igaming_platform_tpu.models import session_heads  # noqa: E402
from igaming_platform_tpu.obs import runtime_telemetry  # noqa: E402
from igaming_platform_tpu.obs.metrics import ServiceMetrics  # noqa: E402
from igaming_platform_tpu.serve import session_state as session_mod  # noqa: E402
from igaming_platform_tpu.serve.scorer import TPUScoringEngine  # noqa: E402

NOW0 = 1_700_000_000.0


def make_engine(tiers, batch_size=256, capacity=320, session_state=True, **kw):
    eng = TPUScoringEngine(
        ScoringConfig(), ml_backend="mock",
        batcher_config=BatcherConfig(batch_size=batch_size, latency_tiers=tiers,
                                     max_wait_ms=1.0),
        feature_cache=capacity, session_state=session_state, **kw)
    eng.ensure_cache()
    return eng


def frames(rounds: int, rows: int, accounts: int):
    """Seeded frames of ``rows`` rows over ``accounts`` accounts, with
    repeats inside a frame, so windows warm and wrap."""
    rng = np.random.default_rng(46)
    for r in range(rounds):
        ids = [f"a{i}" for i in rng.integers(0, accounts, rows)]
        amounts = rng.integers(100, 90_000, rows).tolist()
        types = [("bet", "win", "deposit", "withdraw")[i]
                 for i in rng.integers(0, 4, rows)]
        yield ids, amounts, types, NOW0 + 20.0 * r


def run_frames(eng, rounds=8, rows=64, accounts=16):
    outs = [eng.score_columns_cached(ids, amounts, types, now=now)
            for ids, amounts, types, now in frames(rounds, rows, accounts)]
    mgr, row = eng.session, eng.session.n_events * session_mod.EVENT_WIDTH
    cap = mgr.capacity  # the slot past it is the pad rows' scratch
    state = (np.asarray(mgr.session_ring)[:cap * row],
             np.asarray(mgr.session_cursor)[:cap],
             np.asarray(mgr.session_length)[:cap])
    return outs, state


@pytest.fixture
def small_keye(monkeypatch):
    """``SESSION_HEAD=keye`` at tests/test_keye_backbone.py's small size."""
    cfg = kb.BackboneConfig(
        hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, experts=8,
        top_k=2, expert_width=32, idx_heads=2, idx_dim=8, idx_topk=4,
        mrope_section=(2, 3, 3))
    monkeypatch.setitem(session_heads.HEADS, "keye", dataclasses.replace(
        session_heads.HEADS["keye"],
        scores=lambda sp, win, lp: kb.backbone_scores(sp, win, lp, cfg),
        init=lambda: kb.init_backbone(jax.random.key(11), cfg)))
    monkeypatch.setenv("SESSION_HEAD", "keye")


@pytest.mark.parametrize("head", ["pattern", "keye"])
def test_a_64_row_frame_scores_alike_on_either_ladder(head, request, monkeypatch):
    """64-row frames through a scorer whose ladder holds the 64 rung and
    through one whose only rung is 256: the same packed result, ring,
    cursor and length. Bit for bit under the ``pattern`` head; the small
    backbone within its test file's tolerance for another order of float32
    sums (tests/test_keye_backbone.py: 2e-5), integers exact."""
    if head == "keye":
        request.getfixturevalue("small_keye")
    else:
        monkeypatch.setenv("SESSION_HEAD", "pattern")
    results = {}
    for name, tiers in (("rung", (64,)), ("bare", ())):
        eng = make_engine(tiers)
        try:
            assert eng._shapes == ([64, 256] if tiers else [256])
            assert eng._pick_shape(64) == (64 if tiers else 256)
            assert eng.session.head == head
            results[name] = run_frames(eng)
        finally:
            eng.close()
    (outs_a, state_a), (outs_b, state_b) = results["rung"], results["bare"]
    assert any(out["reason_mask"].any() for out in outs_a)
    for a, b in zip(outs_a, outs_b):
        assert a.keys() == b.keys()
        for key in a:
            if head == "pattern" or a[key].dtype.kind in "iub":
                np.testing.assert_array_equal(a[key], b[key], err_msg=key)
            else:
                np.testing.assert_allclose(a[key], b[key], atol=2e-5, rtol=0,
                                           err_msg=key)
    for a, b in zip(state_a, state_b):
        np.testing.assert_array_equal(a, b)
    assert state_a[2].max() == 16  # windows wrapped


# -- the counter --------------------------------------------------------------


@pytest.mark.parametrize("rows,with_rung,bare", [
    (1, 64, 256), (64, 64, 256), (65, 256, 256), (256, 256, 256)])
def test_padded_rows_are_counted_at_every_launch_of_the_index_program(
        monkeypatch, rows, with_rung, bare):
    """``risk_launch_padded_rows_total`` adds the rung a frame ran, as the
    launch seam (``_note_launch``) knows the shape: a 64- and a 256-row
    frame add 320 where the 64 rung stands and 512 where it does not."""
    monkeypatch.setenv("SESSION_HEAD", "pattern")
    metrics = ServiceMetrics("risk")
    monkeypatch.setattr(runtime_telemetry, "DEFAULT",
                        runtime_telemetry.RuntimeTelemetry(metrics))
    read = metrics.launch_padded_rows_total
    for tiers, padded in (((64,), with_rung), ((), bare)):
        eng = make_engine(tiers)
        try:
            before = read.value()
            ids, amounts, types, now = next(frames(1, rows, 300))
            eng.score_columns_cached(ids, amounts, types, now=now)
            assert read.value() - before == padded == eng._pick_shape(rows)
            # the real rows beside the rung: the ladder's occupancy
            occupancy = metrics.batch_occupancy
            assert occupancy._sums[()] == rows * occupancy.count() > 0
        finally:
            eng.close()


@pytest.mark.parametrize("session", [True, False])
@pytest.mark.parametrize("rows,shapes", [
    (1, (64,)), (64, (64,)), (65, (256,)), (256, (256,)), (300, (256, 64))])
def test_a_launch_hands_over_one_host_array(monkeypatch, rows, shapes, session):
    """``risk_h2d_transfers_total`` rises by 1 a launch and
    ``risk_h2d_bytes_total`` by the packed chunk's bytes (68 a padded row),
    in the ``session`` and in the ``cached`` family: the chunk is the only
    host leaf of the call (``_note_launch`` counts whatever leaves it has),
    the thresholds are on the device."""
    from igaming_platform_tpu.serve import index_program

    monkeypatch.setenv("SESSION_HEAD", "pattern")
    metrics = ServiceMetrics("risk")
    monkeypatch.setattr(runtime_telemetry, "DEFAULT",
                        runtime_telemetry.RuntimeTelemetry(metrics))
    eng = make_engine((64,), session_state=session)
    try:
        assert isinstance(eng._thresholds_dev, jax.Array)
        t0 = metrics.h2d_transfers_total.value()
        b0 = metrics.h2d_bytes_total.value()
        d0 = metrics.device_dispatches_total.value()
        ids, amounts, types, now = next(frames(1, rows, 300))
        eng.score_columns_cached(ids, amounts, types, now=now)
        assert metrics.h2d_transfers_total.value() - t0 == len(shapes)
        chunk_bytes = [index_program.warm_columns(s).nbytes for s in shapes]
        assert chunk_bytes == [s * 4 * index_program.CHUNK_WORDS
                               for s in shapes]
        assert metrics.h2d_bytes_total.value() - b0 == sum(chunk_bytes)
        # the launches themselves, beside the admissions' own dispatches
        assert metrics.device_dispatches_total.value() - d0 >= len(shapes)
    finally:
        eng.close()
