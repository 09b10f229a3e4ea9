"""serve/index_program.py: the one owner of the index-mode device program.

- the engine's ``cached`` / ``session`` programs, for every
  ``(sketch, shadow)``, ARE the builder's (no second place builds one),
  and the session program lowers to a module named ``jit__body``: the
  name the benchmark finds it by in a trace
  (chipbench/layer_metrics/device_step_ms.json, ``^jit__body\\(``);
- ``compose_rows`` with the local and the slot-sharded ``take`` equals a
  numpy composition;
- the launch's one packed chunk (``pack_chunk`` on the host,
  ``unpack_chunk`` in the program) hands over every column bit for bit,
  and the packed step equals the parent's argument list (seventeen
  positional arguments, nine of them host arrays; kept here as the plain
  reference) in ``packed``, ring, cursor, length, sketch and shadow on
  one device, the replicated mesh and the slot-sharded mesh.
"""

from __future__ import annotations

import itertools

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
from igaming_platform_tpu.core.features import F, NUM_FEATURES
from igaming_platform_tpu.serve import index_program
from igaming_platform_tpu.serve.scorer import TPUScoringEngine


@pytest.fixture(scope="module")
def engine():
    eng = TPUScoringEngine(
        ScoringConfig(), ml_backend="mock",
        batcher_config=BatcherConfig(batch_size=16, latency_tiers=(),
                                     max_wait_ms=1.0),
        feature_cache=32, session_state=True)
    eng.ensure_cache()
    yield eng
    eng.close()


def _lower(eng, family, fn):
    chunk = index_program.warm_columns(16)
    mgr = eng.session
    if family == "cached":
        return fn.lower(None, None, eng.cache.table, eng.cache.flags, chunk,
                        eng._thresholds_dev)
    return fn.lower(None, mgr.head_params, eng.cache.table, eng.cache.flags,
                    mgr.session_ring, mgr.session_cursor, mgr.session_length,
                    chunk, eng._thresholds_dev, None)


@pytest.mark.parametrize(
    "family,sketch,shadow",
    list(itertools.product(("cached", "session"), (False, True),
                           (False, True))))
def test_engine_program_is_the_builders(engine, monkeypatch, family, sketch,
                                        shadow):
    built = []
    real_build = index_program.build

    def spy(score_fn, cfg, **kw):
        fn = real_build(score_fn, cfg, **kw)
        built.append((kw, fn))
        return fn

    monkeypatch.setattr(index_program, "build", spy)
    engine._fused_fns.pop((family, sketch, shadow), None)
    fn = engine._ensure_fused(family, sketch, shadow)
    assert len(built) == 1 and built[0][1] is fn
    kw = built[0][0]
    assert (kw["family"], kw["sketch"], kw["shadow"]) == (family, sketch,
                                                          shadow)
    assert kw["mesh"] is engine._mesh and kw["plan"] is engine._state_plan
    # memoised: a second ask builds nothing
    assert engine._ensure_fused(family, sketch, shadow) is fn
    assert len(built) == 1
    lowered = _lower(engine, family, fn)
    head = lowered.as_text().splitlines()[0]
    want = "jit__body" if family == "session" else "jit__cached_body"
    assert head.startswith(f"module @{want} "), head
    # outputs: the family's own, then [sketch][, shadow]
    n_out = (4 if family == "session" else 1) + int(sketch) + int(shadow)
    assert len(jax.tree.leaves(lowered.out_info)) == n_out


@pytest.mark.parametrize("placement", ["local", "sharded"])
def test_compose_rows_matches_numpy(placement):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh
    from igaming_platform_tpu.parallel.state_sharding import plan_for

    cap, b = 12, 9
    rng = np.random.default_rng(5)
    table = rng.normal(size=(cap, NUM_FEATURES)).astype(np.float32)
    flags = rng.random(cap) < 0.3
    idxs = rng.integers(0, cap, b).astype(np.int32)
    amounts = rng.uniform(1, 900, b).astype(np.float32)
    types = rng.integers(0, 5, b).astype(np.int32)
    bl = rng.random(b) < 0.2

    want = table[idxs].copy()
    want[:, int(F.TX_AMOUNT)] = amounts
    want[:, int(F.TX_TYPE_DEPOSIT)] = types == 0
    want[:, int(F.TX_TYPE_WITHDRAW)] = types == 1
    want[:, int(F.TX_TYPE_BET)] = types == 2
    want_bl = bl | flags[idxs]

    if placement == "local":
        acc = index_program.slot_access(None)
        fn = jax.jit(lambda *a: index_program.compose_rows(acc.take, *a))
        args = (table, flags)
    else:
        mesh = create_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
        plan = plan_for(mesh, enabled=True)
        acc = index_program.slot_access(plan)
        fn = jax.jit(shard_map(
            lambda *a: index_program.compose_rows(acc.take, *a), mesh=mesh,
            in_specs=(plan.spec(2), plan.spec(1), P(), P(), P(), P()),
            out_specs=(P(), P()), check_vma=False))
        args = (plan.place(table), plan.place(flags))
    x, blv = jax.device_get(fn(*args, idxs, amounts, types, bl))
    np.testing.assert_array_equal(x, want)
    np.testing.assert_array_equal(blv, want_bl)


# ---------------------------------------------------------------------------
# The packed chunk: one host array a launch


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def _seeded_columns(rng, n, cap):
    """A chunk's real rows: duplicates (ranks past 0), the largest index,
    amounts and events with every kind of float32 bit pattern (a negative
    zero, a subnormal, an infinity, a NaN with a payload), a True flag."""
    idxs = rng.integers(0, cap, n).astype(np.int32)
    amounts = rng.uniform(-5, 9e4, n).astype(np.float32)
    events = rng.normal(size=(n, index_program.EVENT_WIDTH)).astype(np.float32)
    types = rng.integers(0, 5, n).astype(np.int32)
    bl = rng.random(n) < 0.3
    if n:
        idxs[0], bl[0], bl[-1] = cap - 1, True, False
        odd = np.array([0x80000000, 0x00000001, 0x7F800000, 0x7FC00123],
                       np.uint32).view(np.float32)
        amounts[:4] = odd[:min(4, n)]
        events[-1, :4] = odd
    order = np.argsort(idxs, kind="stable")
    occ = np.empty((n,), np.int32)
    first = np.r_[0, np.flatnonzero(np.diff(idxs[order])) + 1]
    run = np.repeat(first, np.diff(np.r_[first, n]))
    occ[order] = np.arange(n) - run
    return idxs, amounts, types, bl, occ, events


@pytest.mark.parametrize("shape,n", [(16, 16), (16, 9), (16, 1), (64, 0),
                                     (256, 203)])
def test_packed_chunk_round_trip_is_bit_exact(shape, n):
    """``pack_chunk`` -> ``unpack_chunk`` under jit returns every column by
    its bits, on a full and a part-filled shape; pad rows read as slot 0
    of the table with distinct ranks, are not real, and ``n`` counts the
    rest. The chunk is 32-bit words, a row a padded row, and fresh."""
    cap = 2**31 - 1  # the largest index an int32 word holds is a row's too
    idxs, amounts, types, bl, occ, events = _seeded_columns(
        np.random.default_rng(shape + n), n, cap)
    chunk = index_program.pack_chunk(shape, idxs, amounts, types, bl, occ=occ)
    assert chunk.dtype == np.int32
    assert chunk.shape == (shape, index_program.CHUNK_WORDS)
    assert index_program.CHUNK_WORDS == 5 + index_program.EVENT_WIDTH
    assert chunk is not index_program.pack_chunk(shape, idxs, amounts, types,
                                                 bl, occ=occ)
    view = index_program.chunk_events(chunk, n)
    assert view.shape == (n, index_program.EVENT_WIDTH)
    assert n == 0 or np.shares_memory(view, chunk)
    assert not view.any()  # zeroed: what encode_events_host's ``out`` asks
    view[:] = events
    c = jax.device_get(jax.jit(index_program.unpack_chunk)(chunk))
    assert int(c.n) == n and c.real.tolist() == [True] * n + [False] * (shape - n)
    for got, want in ((c.idxs, idxs), (c.occ, occ), (c.amounts, amounts),
                      (c.types, types), (c.events, events), (c.bl, bl)):
        assert got.dtype == want.dtype and got.shape[0] == shape
        np.testing.assert_array_equal(_bits(got[:n]), _bits(want))
    # the pad rows: slot 0, amount 0.0, type 0, no flag, zero events, and
    # ranks that keep their appends to the scratch slot off each other
    assert not c.idxs[n:].any() and not _bits(c.amounts[n:]).any()
    assert not c.types[n:].any() and not c.bl[n:].any()
    assert not _bits(c.events[n:]).any()
    assert c.occ[n:].tolist() == list(range(shape - n))
    # the split sketch's host views are the same memory
    for col in index_program.chunk_columns(chunk):
        assert np.shares_memory(col, chunk) and col.shape == (shape,)


def test_warm_columns_touch_no_real_window():
    chunk = index_program.warm_columns(8)
    c = jax.device_get(jax.jit(index_program.unpack_chunk)(chunk))
    assert int(c.n) == 0 and not c.real.any() and not c.idxs.any()
    assert c.types.tolist() == [4] * 8 and c.occ.tolist() == list(range(8))


# The two bodies as they stood before PR 67, word for word: seventeen and
# ten positional arguments, the per-row columns an array each, ``sidx``
# and ``n`` handed over by the host.


def _parents_bodies(score_fn, cfg, spec, acc, sketch, shadow):
    import jax.numpy as jnp

    from igaming_platform_tpu.core.enums import (
        SESSION_COLD_BIT,
        SESSION_PATTERN_BIT,
    )
    from igaming_platform_tpu.models.ensemble import ML_HIGH_RISK_BIT, combine
    from igaming_platform_tpu.serve.session_state import (
        advance_counters,
        ring_append,
        windows_from_state,
    )

    compose_rows, epilogue = index_program.compose_rows, index_program.epilogue
    stack_packed = index_program.stack_packed

    def _cached_body(params, cand, table, flags, idxs, amounts, types, bl,
                     thr, n):
        x, blv = compose_rows(acc.take, table, flags, idxs, amounts, types, bl)
        packed = stack_packed(score_fn(params, x, blv, thr))
        return epilogue(
            [packed], x, packed, n, sketch,
            (lambda: stack_packed(score_fn(cand, x, blv, thr)))
            if shadow else None)

    if spec is None:
        return _cached_body
    head_fn, capacity, n_events = spec.head_fn, spec.capacity, spec.n_events
    min_events, flag_threshold = spec.min_events, spec.flag_threshold

    def _session_fold(out, sprob, fold, cold, thr):
        ml = out["ml_score"].astype(jnp.float32)
        ml2 = jnp.where(fold, jnp.maximum(ml, sprob), ml)
        mask_base = out["reason_mask"] & ~(1 << ML_HIGH_RISK_BIT)
        final, action, mask = combine(out["rule_score"], ml2, mask_base,
                                      cfg, thr)
        mask = mask | jnp.where(fold, 1 << SESSION_PATTERN_BIT, 0)
        mask = mask | jnp.where(cold, 1 << SESSION_COLD_BIT, 0)
        return stack_packed({
            "score": final, "action": action, "reason_mask": mask,
            "rule_score": out["rule_score"], "ml_score": ml2})

    def _body(params, sparams, table, flags, ring, cursor, length,
              idxs, sidx, occ, amounts, types, events, bl, thr, cand, n):
        x, blv = compose_rows(acc.take, table, flags, idxs, amounts, types, bl)
        out = score_fn(params, x, blv, thr)
        rows = cursor.shape[0]
        cur = acc.take(cursor, sidx)
        ln = acc.take(length, sidx)
        win, lp = windows_from_state(
            acc.ring(ring, sidx, rows, n_events), cur, ln, events, n_events)
        sprob = head_fn(sparams, win, lp).astype(jnp.float32)
        real = sidx < capacity
        warm = jnp.logical_and(lp >= min_events, real)
        fold = jnp.logical_and(warm, sprob >= flag_threshold)
        cold = jnp.logical_and(jnp.logical_not(warm), real)
        packed = _session_fold(out, sprob, fold, cold, thr)
        li, owned = acc.own(sidx, rows, real)
        ring2 = ring_append(ring, li, jnp.mod(cur + occ, n_events), events,
                            n_events)
        cursor2, length2 = advance_counters(cursor, length, li, ln, occ,
                                            owned, n_events)
        return epilogue(
            [packed, ring2, cursor2, length2], x, packed, n, sketch,
            (lambda: _session_fold(score_fn(cand, x, blv, thr), sprob, fold,
                                   cold, thr))
            if shadow else None)

    return _body


_PARENTS_ARGS = {
    "cached": ("tree", "tree", "table", "slot",
               "vec", "vec", "vec", "vec", "repl", "repl"),
    "session": ("tree", "tree", "table", "slot", "slot", "slot", "slot",
                "vec", "vec", "vec", "vec", "vec", "row", "vec", "repl",
                "tree", "repl"),
}


def _parents_build(score_fn, cfg, *, family, sketch, shadow, mesh, plan,
                   session=None):
    """``index_program.build`` as it stood, for the parent's bodies."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from igaming_platform_tpu.parallel.mesh import AXIS_DATA

    body = _parents_bodies(score_fn, cfg,
                           session if family == "session" else None,
                           index_program.slot_access(plan), sketch, shadow)
    kinds_in = _PARENTS_ARGS[family]
    kinds_out = (index_program._OUTS[family] + (("repl",) if sketch else ())
                 + (("packed",) if shadow else ()))
    donate = index_program._DONATE[family]
    if plan is not None:
        spec = {"table": plan.spec(2), "slot": plan.spec(1)}
        return jax.jit(
            shard_map(
                body, mesh=plan.mesh,
                in_specs=tuple(spec.get(k, P()) for k in kinds_in),
                out_specs=tuple(spec.get(k, P()) for k in kinds_out),
                check_vma=False),
            donate_argnums=donate)
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        place = {
            "tree": None, "table": repl, "slot": repl, "repl": repl,
            "vec": NamedSharding(mesh, P(AXIS_DATA)),
            "row": NamedSharding(mesh, P(AXIS_DATA, None)),
            "packed": NamedSharding(mesh, P(None, AXIS_DATA)),
        }
        return jax.jit(
            body,
            in_shardings=tuple(place[k] for k in kinds_in),
            out_shardings=tuple(place[k] for k in kinds_out),
            donate_argnums=donate)
    return jax.jit(body, donate_argnums=donate)


def _placement(name, k=2):
    """(mesh, state sharding on) of one of the three placements."""
    from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh

    if name == "one":
        return None, False
    mesh = create_mesh(MeshSpec(data=k), devices=jax.devices()[:k])
    return mesh, name == "sharded"


@pytest.mark.parametrize("placement", ["one", "replicated", "sharded"])
@pytest.mark.parametrize(
    "family,sketch,shadow",
    list(itertools.product(("cached", "session"), (False, True),
                           (False, True))))
def test_packed_step_equals_the_parents_argument_list(
        monkeypatch, placement, family, sketch, shadow):
    """The same seeded chunks (duplicates, pad rows, a full shape, a True
    flag, windows that warm and wrap) through the packed program and
    through the parent's: every output of every step is the same bits."""
    from igaming_platform_tpu.models.ensemble import make_score_fn
    from igaming_platform_tpu.models.mlp import init_mlp
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setenv("SESSION_HEAD", "pattern")
    cap, shape, steps = 8, 16, 16
    mesh, sharded = _placement(placement)
    monkeypatch.setenv("STATE_SHARDING", "1" if sharded else "0")
    mgrs = [ss.SessionStateManager(cap, mesh=mesh) for _ in range(2)]
    plan = mgrs[0].plan
    assert (plan is not None) == sharded
    cfg = ScoringConfig()
    score_fn = make_score_fn(cfg, "mlp")
    params, cand = ({"mlp": init_mlp(jax.random.key(s), hidden=(16, 16))}
                    for s in (0, 1))
    rng = np.random.default_rng(67)
    table = rng.normal(size=(cap, NUM_FEATURES)).astype(np.float32)
    flags = rng.random(cap) < 0.25
    if plan is not None:
        table, flags = plan.place(table), plan.place(flags)
    thr = np.array([cfg.block_threshold, cfg.review_threshold], np.int32)
    kw = dict(family=family, sketch=sketch, shadow=shadow, mesh=mesh,
              plan=plan)
    new = index_program.build(score_fn, cfg, session=mgrs[0], **kw)
    old = _parents_build(score_fn, cfg, session=mgrs[1], **kw)
    c = cand if shadow else None
    for t in range(steps):
        n = shape if t == 2 else int(rng.integers(5, 14))
        idxs, amounts, types, bl, occ, events = _seeded_columns(rng, n, cap)
        amounts = np.abs(np.nan_to_num(amounts, posinf=7.0)) + 1.0
        events = np.nan_to_num(events, posinf=1.0)
        chunk = index_program.pack_chunk(
            shape, idxs, amounts, types, bl,
            occ=occ if family == "session" else None)

        def pad(x, fill=0):
            p = np.full((shape, *x.shape[1:]), fill, x.dtype)
            p[:n] = x
            return p

        if family == "cached":
            got = new(params, c, table, flags, chunk, thr)
            want = old(params, c, table, flags, pad(idxs), pad(amounts),
                       pad(types), pad(bl), thr, np.int32(n))
        else:
            index_program.chunk_events(chunk, n)[:] = events
            occp = pad(occ)
            occp[n:] = np.arange(shape - n)
            a, b = mgrs
            got = new(params, a.head_params, table, flags, a.session_ring,
                      a.session_cursor, a.session_length, chunk, thr, c)
            want = old(params, b.head_params, table, flags, b.session_ring,
                       b.session_cursor, b.session_length, pad(idxs),
                       pad(idxs, cap), occp, pad(amounts), pad(types),
                       pad(events), pad(bl), thr, c, np.int32(n))
            a.adopt(*got[1:4])
            b.adopt(*want[1:4])
        assert len(got) == len(want) == (
            (4 if family == "session" else 1) + int(sketch) + int(shadow))
        for i, (g, w) in enumerate(zip(got, want)):
            g, w = np.asarray(g), np.asarray(w)
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(_bits(g), _bits(w),
                                          err_msg=f"step {t} output {i}")
    if family == "session":
        assert int(np.asarray(mgrs[0].session_length).max()) == mgrs[0].n_events
