"""Slot-sharded device state over a forced multi-device mesh (ISSUE 15).

The load-bearing property is BIT-EXACTNESS under resharding: with the
HBM feature table and the session ring row-sharded by slot over a
K-device ``data`` axis (parallel/state_sharding.py), every scoring path
must produce byte-identical outputs to the K=1 replicated baseline —
that is what makes STATE_SHARDING safe to enable by default on a mesh.
On top of that: per-chip HBM measuring ~1/K (the capacity half of the
north star), CLOCK eviction/rehydration coherence under slot-shard
ownership, dispatches-per-RPC unchanged at one per chunk, ledger replay
+ session-chain verification across a RESHARDING restart (K=2 WAL
continued by a K=4 engine), model-parallel param placement, and the
pod-as-unit router ring.

Runs on the conftest-forced 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``); K-device meshes are
carved from prefixes of that set.
"""

import tempfile

import numpy as np
import pytest

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh
from igaming_platform_tpu.serve import ledger as ledger_mod
from igaming_platform_tpu.serve.feature_store import InMemoryFeatureStore, TransactionEvent
from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

NOW0 = 1_700_000_000.0
KS = (2, 4, 8)


def _mesh(k):
    import jax

    return create_mesh(MeshSpec(data=k), devices=jax.devices()[:k])


def _seed(store, n=24):
    for a in range(n):
        for k, age in enumerate((30.0, 90.0, 400.0, 4000.0)):
            store.update(TransactionEvent(
                account_id=f"acct-{a}", amount=900 + 37 * a + 11 * k,
                tx_type=("deposit", "bet", "win")[k % 3],
                ip=f"10.7.{a}.{k}", device_id=f"dev-{a % 8}",
                timestamp=NOW0 - age))


def make_engine(k=None, *, capacity=64, session=True, batch_size=16,
                tiers=(8,), ledger_dir=None, ml_backend="mock", **kw):
    store = InMemoryFeatureStore()
    _seed(store)
    eng = TPUScoringEngine(
        ScoringConfig(), ml_backend=ml_backend, feature_store=store,
        batcher_config=BatcherConfig(batch_size=batch_size,
                                     latency_tiers=tiers, max_wait_ms=1.0),
        mesh=None if k is None else _mesh(k),
        feature_cache=capacity, session_state=session, **kw)
    if ledger_dir is not None:
        eng.ledger = ledger_mod.DecisionLedger(ledger_dir)
    eng.ensure_cache()
    return eng


def close_engine(eng):
    if eng.ledger is not None:
        eng.ledger.close()
    eng.close()


def _assert_bits(got, ref, msg=""):
    for key in ("score", "action", "reason_mask", "rule_score"):
        np.testing.assert_array_equal(got[key], ref[key],
                                      err_msg=f"{msg} {key}")
    np.testing.assert_array_equal(
        got["ml_score"].view(np.int32), ref["ml_score"].view(np.int32),
        err_msg=f"{msg} ml_score bits")


def _traffic(n, spread=10):
    ids = [f"acct-{i % spread}" for i in range(n)]
    amounts = [500 + 13 * i for i in range(n)]
    txs = [("deposit", "bet", "withdraw")[i % 3] for i in range(n)]
    return ids, amounts, txs


# ---------------------------------------------------------------------------
# The acceptance bar: bit-exact parity vs the replicated baseline


def test_sharded_state_bit_exact_vs_replicated_all_ladder_shapes():
    """K=2/4/8 slot-sharded engines reproduce the K=1 replicated
    baseline bit-for-bit on the cached/session path across every ladder
    shape (sub-tier, tier, full-chunk and multi-chunk sizes), cold AND
    warm windows."""
    base = make_engine(None)
    rounds = {}
    for size in (1, 3, 8, 16, 40):
        ids, amounts, txs = _traffic(size)
        rounds[size] = [
            base.score_columns_cached(ids, amounts, txs, now=NOW0 + r)
            for r in range(3)  # round 2+ exercises WARM windows
        ]
    close_engine(base)
    for k in KS:
        eng = make_engine(k)
        assert eng._state_plan is not None and eng.cache.plan is not None
        for size, refs in rounds.items():
            ids, amounts, txs = _traffic(size)
            for r, ref in enumerate(refs):
                got = eng.score_columns_cached(ids, amounts, txs,
                                               now=NOW0 + r)
                _assert_bits(got, ref, f"K={k} size={size} round={r}")
        close_engine(eng)


def test_row_wire_packed_path_parity_on_sharded_mesh():
    """The row-shaped packed path (batch sharded over ``data``) on the
    same mesh engines keeps its existing bit-exactness: slot sharding
    must not perturb the non-state families."""
    reqs = [ScoreRequest(account_id=f"acct-{i % 12}", amount=700 + 31 * i,
                         tx_type=("deposit", "bet")[i % 2])
            for i in range(16)]
    base = make_engine(None, session=False)
    ref = base.score_batch(reqs)
    close_engine(base)
    eng = make_engine(4, session=False)
    got = eng.score_batch(reqs)
    for g, r in zip(got, ref):
        assert (g.score, g.action, g.rule_score) == (r.score, r.action,
                                                     r.rule_score)
        assert g.reason_codes == r.reason_codes
    close_engine(eng)


def test_fused_sketch_variant_parity_on_sharded_mesh():
    """With a drift engine bound the session family compiles the FUSED
    sharded program (score + in-graph sketch in one shard_map dispatch);
    outputs stay bit-exact vs the replicated baseline and the sketch
    row counts match."""
    from igaming_platform_tpu.obs import drift as dm

    def bind(eng):
        drift = dm.DriftEngine()
        eng.bind_drift(drift)
        return drift

    ids, amounts, txs = _traffic(24)
    base = make_engine(None)
    d0 = bind(base)
    refs = [base.score_columns_cached(ids, amounts, txs, now=NOW0 + r)
            for r in range(2)]
    close_engine(base)
    eng = make_engine(4)
    d1 = bind(eng)
    assert ("session", True, False) in eng._fused_ready
    for r, ref in enumerate(refs):
        got = eng.score_columns_cached(ids, amounts, txs, now=NOW0 + r)
        _assert_bits(got, ref, f"fused round={r}")

    def sketched(d):
        import time

        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if d.rows_sketched >= 48:  # 2 rounds x 24 rows
                return d.rows_sketched
            time.sleep(0.01)
        return d.rows_sketched

    assert sketched(d1) == sketched(d0) == 48
    close_engine(eng)


@pytest.mark.parametrize("sketch", [False, True])
def test_fused_shadow_variant_parity_on_sharded_mesh(sketch):
    """The shadow branch of the one session body under slot sharding
    (K=2; with a drift engine bound, the sketch+shadow variant): the
    production AND the candidate outputs of the sharded program are
    bit-equal to the replicated program's over warm and cold windows."""
    import time

    from igaming_platform_tpu.models.mlp import init_mlp
    from igaming_platform_tpu.obs import drift as dm
    from igaming_platform_tpu.serve.shadow import ShadowScorer

    def params(seed):
        import jax

        return {"mlp": init_mlp(jax.random.key(seed), hidden=(16, 16))}

    ids, amounts, txs = _traffic(24)

    def run(k):
        eng = make_engine(k, ml_backend="mlp", params=params(0))
        drift = dm.DriftEngine() if sketch else None
        if drift is not None:
            eng.bind_drift(drift)
        cands = []
        sh = ShadowScorer(eng, params(1),
                          on_result=lambda c, p, n: cands.append(c))
        eng.shadow = sh
        try:
            deadline = time.monotonic() + 240.0
            while ("session", sketch, True) not in eng._fused_ready:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            prods = [eng.score_columns_cached(ids, amounts, txs, now=NOW0 + r)
                     for r in range(3)]
            assert sh.drain(300.0)
            rep = sh.report()
            assert rep["errors"] == 0 and rep["fused_batches"] > 0
            return prods, cands
        finally:
            sh.close()
            close_engine(eng)
            if drift is not None:
                drift.close()

    ref_prods, ref_cands = run(None)
    got_prods, got_cands = run(2)
    assert len(got_cands) == len(ref_cands) > 0
    for r, (got, ref) in enumerate(zip(got_prods, ref_prods)):
        _assert_bits(got, ref, f"production round={r}")
    for i, (got, ref) in enumerate(zip(got_cands, ref_cands)):
        _assert_bits(got, ref, f"candidate batch={i}")


# ---------------------------------------------------------------------------
# CLOCK coherence + capacity accounting under slot-shard ownership


def test_clock_eviction_rehydration_coherent_under_sharding():
    """Churn 3x the capacity through a tiny sharded cache: CLOCK
    eviction + session rehydration stay coherent (outputs == baseline
    bit-for-bit, windows survive eviction via the host index) and the
    per-shard occupancy never exceeds the shard's row block."""
    base = make_engine(None, capacity=8)
    eng = make_engine(4, capacity=8)
    accts = [f"acct-{i}" for i in range(20)]
    for r in range(4):
        for lo in range(0, 20, 5):
            ids = accts[lo:lo + 5]
            ref = base.score_columns_cached(ids, [100 + r] * 5,
                                            ["bet"] * 5, now=NOW0 + 40 * r)
            got = eng.score_columns_cached(ids, [100 + r] * 5,
                                           ["bet"] * 5, now=NOW0 + 40 * r)
            _assert_bits(got, ref, f"churn round={r} lo={lo}")
    s = eng.cache.shard_stats()
    assert s["sharded"] and s["shards"] == 4
    assert sum(s["occupancy"]) == eng.cache.stats()["occupancy"]
    assert all(o <= s["rows_per_shard"] for o in s["occupancy"])
    assert eng.cache.stats()["evictions"] > 0
    assert eng.session.rehydrations > 0
    assert eng.session.shard_stats()["shards"] == 4
    close_engine(base)
    close_engine(eng)


def test_per_chip_hbm_bytes_scale_one_over_k():
    """The point of the PR: each chip holds ~1/K of the state. Measured
    from the committed shardings, not inferred from specs."""
    from igaming_platform_tpu.parallel.state_sharding import per_shard_nbytes

    base = make_engine(None, capacity=64)
    table_full = per_shard_nbytes(base.cache.table)[0]
    ring_full = per_shard_nbytes(base.session.session_ring)[0]
    close_engine(base)
    for k in KS:
        eng = make_engine(k, capacity=64)
        tb = per_shard_nbytes(eng.cache.table)
        rb = per_shard_nbytes(eng.session.session_ring)
        assert len(tb) == k and len(set(tb)) == 1
        assert tb[0] * k == table_full, f"table bytes at K={k}"
        # The sharded ring drops the replicated layout's scratch row.
        assert rb[0] * k < ring_full and rb[0] * k >= ring_full * 0.9
        stats = eng.cache.shard_stats()
        assert stats["hbm_bytes"] == [stats["hbm_bytes"][0]] * k
        close_engine(eng)


def test_capacity_rounds_up_to_shard_multiple():
    eng = make_engine(4, capacity=10, session=False)
    assert eng.cache.capacity == 12  # ceil(10/4)*4
    assert eng.cache.shard_stats()["rows_per_shard"] == 3
    close_engine(eng)


# ---------------------------------------------------------------------------
# One dispatch per chunk survives sharding


def test_steady_state_dispatches_unchanged_by_sharding(monkeypatch):
    from igaming_platform_tpu.serve import scorer as scorer_mod

    counts = {}
    accts = [f"dc{i}" for i in range(10)]
    for key, k in (("replicated", None), ("sharded", 4)):
        eng = make_engine(k, capacity=16, batch_size=4, tiers=())
        # Warm first: admissions fire the between-steps scatters; the
        # 1.0-dispatch claim is about the steady (resident) state.
        eng.score_columns_cached(accts, [90] * 10, ["bet"] * 10, now=NOW0)
        calls = []
        orig = scorer_mod._device_dispatch
        monkeypatch.setattr(scorer_mod, "_device_dispatch",
                            lambda fn, shape, dtype: calls.append(fn))
        for r in range(1, 3):
            eng.score_columns_cached(accts, [100 + r] * 10, ["bet"] * 10,
                                     now=NOW0 + 30.0 * r)
        monkeypatch.setattr(scorer_mod, "_device_dispatch", orig)
        counts[key] = len(calls)
        close_engine(eng)
    # 10 rows / 4-row chunks = 3 chunks per RPC, 2 RPCs: 6 launches —
    # identical sharded and replicated (1.0 dispatches per chunk).
    assert counts["sharded"] == counts["replicated"] == 6


@pytest.mark.parametrize("k", [None, 2, 4])
def test_a_launch_is_one_host_leaf_on_every_placement(monkeypatch, k):
    """The packed chunk is the call's one host (numpy) leaf on one device
    and on the slot-sharded mesh, where it is placed once a device; the
    thresholds are a device array replicated over the mesh, replaced when
    an operator sets them, and the next chunk reads the new ones."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from igaming_platform_tpu.serve import index_program

    eng = make_engine(k, capacity=16, batch_size=8, tiers=())
    launches = []
    note = eng._note_launch

    def spy(label, shape, n, args):
        launches.append([a for a in jax.tree.leaves(args)
                         if isinstance(a, (np.ndarray, np.generic))])
        return note(label, shape, n, args)

    monkeypatch.setattr(eng, "_note_launch", spy)
    accts = [f"hl{i}" for i in range(11)]
    base = eng.score_columns_cached(accts, [90] * 11, ["bet"] * 11, now=NOW0)
    assert len(launches) == 2  # 8 + 3 rows
    for host in launches:
        assert len(host) == 1 and host[0].dtype == np.int32
        assert host[0].shape == (8, index_program.CHUNK_WORDS)
    assert [int((h[0][:, index_program.W_FLAGS] & 2).astype(bool).sum())
            for h in launches] == [8, 3]
    thr = eng._thresholds_dev
    assert isinstance(thr, jax.Array)
    if k is not None:
        assert thr.sharding.is_equivalent_to(
            NamedSharding(eng._mesh, P()), thr.ndim)
        assert len(thr.sharding.device_set) == k
    assert (base["action"] != 3).any()
    eng.set_thresholds(0, 0)
    assert eng._thresholds_dev is not thr and eng.get_thresholds() == (0, 0)
    np.testing.assert_array_equal(np.asarray(eng._thresholds_dev), [0, 0])
    got = eng.score_columns_cached(accts, [90] * 11, ["bet"] * 11,
                                   now=NOW0 + 30.0)
    assert (got["action"] == 3).all()  # block at 0: every row
    close_engine(eng)


def test_shard_gauges_exposed_with_bounded_labels():
    from igaming_platform_tpu.obs.metrics import ServiceMetrics

    m = ServiceMetrics("risk")
    eng = make_engine(4, capacity=16)
    eng.bind_cache_metrics(m)
    eng.bind_session_metrics(m)
    eng.score_columns_cached(["g1", "g2"], [100, 200], ["bet", "deposit"],
                             now=NOW0)
    text = m.registry.render_text()
    assert 'risk_cache_shard_occupancy{shard="0"} 2' in text
    assert 'risk_cache_shard_occupancy{shard="3"}' in text
    assert 'risk_hbm_bytes{shard="0",table="feature_cache"}' in text
    assert 'risk_hbm_bytes{shard="3",table="session_ring"}' in text
    # MX05 discipline: the shard label is bounded by the mesh size.
    import re

    shards = set(re.findall(r'cache_shard_occupancy\{shard="(\d+)"\}', text))
    assert shards == {"0", "1", "2", "3"}
    close_engine(eng)


# ---------------------------------------------------------------------------
# Replay across a RESHARDING restart (K=2 WAL continued at K=4)


def test_session_chain_replay_clean_across_resharding_restart():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent))
    from tools.replay import replay_directory

    d = tempfile.mkdtemp(prefix="mesh-reshard-replay-")
    accts = [f"rs{i}" for i in range(6)]
    eng = make_engine(2, capacity=4, ledger_dir=d)
    for r in range(4):
        eng.score_columns_cached(accts, [800 + i for i in range(6)],
                                 ["bet" if r % 2 == 0 else "deposit"] * 6,
                                 now=NOW0 + 35.0 * r)
    close_engine(eng)
    # Resharding restart: same WAL dir, K=2 -> K=4 (capacity re-rounds,
    # slot->shard ownership changes, host session index rebuilt).
    eng2 = make_engine(4, capacity=4, ledger_dir=d)
    for r in range(3):
        eng2.score_columns_cached(accts, [900 + i for i in range(6)],
                                  ["deposit" if r % 2 == 0 else "bet"] * 6,
                                  now=NOW0 + 2000.0 + 35.0 * r)
    close_engine(eng2)
    v = replay_directory(d, batch=16)
    assert v["session_records"] == 4 * 6 + 3 * 6
    assert v["session_verified"] == v["session_records"]
    assert v["session_hash_mismatch"] == 0
    assert v["session_chain_gaps"] == 0
    assert v["session_reordered"] == 0
    assert v["session_resets"] == 6  # the restart, visible per account
    assert v["session_ok"] and v["ok"]


# ---------------------------------------------------------------------------
# Model parallelism over the same mesh


def test_gbdt_tree_sharded_over_expert_axis():
    """GBDT forest tree-sharded over ``expert`` via the declarative
    param placement: per-device leaf storage shrinks to n_trees/E and
    the in-graph partial-score reduce stays within float tolerance of
    the replicated forest (re-associated adds — close, not bitwise,
    which is why the STATE parity suite runs the paramless mock)."""
    import jax

    from igaming_platform_tpu.models.gbdt import init_gbdt

    params = {"gbdt": init_gbdt(jax.random.key(7))}
    x = np.asarray(jax.random.uniform(
        jax.random.key(8), (16, 30), minval=0.0, maxval=900.0))

    base = TPUScoringEngine(
        ScoringConfig(), ml_backend="gbdt", params=params,
        batcher_config=BatcherConfig(batch_size=16, latency_tiers=(),
                                     max_wait_ms=1.0))
    ref = {k: np.asarray(v) for k, v in base.score_arrays(x).items()}
    base.close()

    mesh = create_mesh(MeshSpec(data=2, expert=4))
    eng = TPUScoringEngine(
        ScoringConfig(), ml_backend="gbdt", params=params,
        batcher_config=BatcherConfig(batch_size=16, latency_tiers=(),
                                     max_wait_ms=1.0), mesh=mesh)
    assert eng._model_sharded
    leaves = eng.get_params()["gbdt"]["leaves"]
    shard_trees = {s.data.shape[0] for s in leaves.addressable_shards}
    assert shard_trees == {leaves.shape[0] // 4}
    got = {k: np.asarray(v) for k, v in eng.score_arrays(x).items()}
    np.testing.assert_allclose(got["ml_score"], ref["ml_score"],
                               rtol=1e-5, atol=1e-6)
    assert int(np.max(np.abs(got["score"] - ref["score"]))) <= 1
    # Fingerprint is layout-independent: replay attribution survives.
    assert (ledger_mod.params_fingerprint(eng.get_params())
            == ledger_mod.params_fingerprint(params))
    eng.close()


# ---------------------------------------------------------------------------
# Pod-as-unit router ring


def test_pod_ring_pod_as_unit_membership():
    from igaming_platform_tpu.serve.router import ScoringRouter

    router = ScoringRouter(
        {"r0": ("127.0.0.1:1", None), "r1": ("127.0.0.1:2", None),
         "r2": ("127.0.0.1:3", None)},
        pods={"pod-a": ("r0", "r1"), "pod-b": ("r2",)},
        hedge=False)
    try:
        assert router.ring.members == frozenset({"pod-a", "pod-b"})
        # One member down: the pod keeps its keys (any member reaches
        # the same mesh-resident state).
        router.pod_ring.evict("r0")
        assert router.ring.active == frozenset({"pod-a", "pod-b"})
        # Last member down: pod-as-unit-of-failure, keys move.
        router.pod_ring.evict("r1")
        assert router.ring.active == frozenset({"pod-b"})
        router.pod_ring.readmit("r1")
        assert router.ring.active == frozenset({"pod-a", "pod-b"})
        # Owner resolution dials a serving member of the pod.
        router.replicas["r0"].state = "dead"
        ep = router._endpoint("pod-a")
        assert ep.id == "r1"
        snap = router.snapshot()
        assert snap["pods"]["pod-a"]["members"] == {"r0": "dead",
                                                    "r1": "serving"}
        assert snap["pods"]["pod-a"]["in_ring"]
    finally:
        router.close()


def test_default_pods_preserve_pr6_ring_mapping():
    """Without an explicit pod spec every replica is its own pod (pod id
    == replica id) — the golden PR 6 owner mapping is untouched."""
    from igaming_platform_tpu.serve.router import HashRing, ScoringRouter

    rids = [f"r{i}" for i in range(5)]
    router = ScoringRouter({r: (f"127.0.0.1:{i + 1}", None)
                            for i, r in enumerate(rids)}, hedge=False)
    try:
        plain = HashRing(rids)
        for key in (f"acct-{i}" for i in range(64)):
            assert router.ring.owner(key) == plain.owner(key)
    finally:
        router.close()


def test_unknown_pod_member_is_a_boot_error():
    from igaming_platform_tpu.serve.router import ScoringRouter

    with pytest.raises(ValueError, match="pod members"):
        ScoringRouter({"r0": ("127.0.0.1:1", None)},
                      pods={"pod-a": ("r0", "ghost")})


# ---------------------------------------------------------------------------
# Sharded scatter/gather primitives


def test_gather_scatter_slots_match_numpy():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from igaming_platform_tpu.parallel import state_sharding as ss

    mesh = _mesh(4)
    plan = ss.plan_for(mesh)
    assert plan is not None and plan.n_shards == 4
    cap = plan.round_capacity(30)
    assert cap == 32
    table = np.arange(cap * 3, dtype=np.float32).reshape(cap, 3)
    placed = plan.place(jnp.asarray(table))
    idxs = np.array([0, 31, 7, 8, 16, 16, 23, 9], np.int32)

    gather = jax.jit(shard_map(
        ss.gather_slots, mesh=mesh, in_specs=(plan.spec(2), P()),
        out_specs=P(), check_vma=False))
    np.testing.assert_array_equal(np.asarray(gather(placed, idxs)),
                                  table[idxs])

    scatter = ss.make_sharded_scatter(plan, 2)
    rows = np.full((2, 3), -5.0, np.float32)
    out = np.asarray(scatter(placed, np.array([1, 30], np.int32), rows))
    expect = table.copy()
    expect[[1, 30]] = rows
    np.testing.assert_array_equal(out, expect)
    # Ownership attribution matches the contiguous-block layout.
    np.testing.assert_array_equal(
        plan.owner_of(idxs, cap), np.array([0, 3, 0, 1, 2, 2, 2, 1]))
