"""serve/index_program.py: the one owner of the index-mode device program.

- the engine's ``cached`` / ``session`` programs, for every
  ``(sketch, shadow)``, ARE the builder's (no second place builds one),
  and the session program lowers to a module named ``jit__body``: the
  name the benchmark finds it by in a trace
  (chipbench/layer_metrics/device_step_ms.json, ``^jit__body\\(``);
- ``compose_rows`` with the local and the slot-sharded ``take`` equals a
  numpy composition.
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
    c = index_program.warm_columns(16, eng.cache.capacity)
    mgr, n = eng.session, np.int32(0)
    if family == "cached":
        return fn.lower(None, None, eng.cache.table, eng.cache.flags,
                        c["idxs"], c["amounts"], c["types"], c["bl"],
                        eng._thresholds, n)
    return fn.lower(None, mgr.head_params, eng.cache.table, eng.cache.flags,
                    mgr.session_ring, mgr.session_cursor, mgr.session_length,
                    c["idxs"], c["sidx"], c["occ"], c["amounts"], c["types"],
                    c["events"], c["bl"], eng._thresholds, None, n)


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
