"""The generator is a function of the mix file and the seed alone."""

import numpy as np
import pytest

from chipbench import traffic, validate

RESIDENT = 50_000


def _mix(name):
    mix = dict(validate.load_data("traffic", name))
    mix["pool_frames"] = 32
    return mix


@pytest.mark.parametrize("name", ["index-flatout", "row-bulk"])
def test_same_seed_same_bytes_two_seeds_differ(name):
    mix = _mix(name)
    big = 3_000_000_019  # more than 32 signed bits hold
    pools = []
    for seed in (big, big, big + 1):
        pop = traffic.Population(mix, RESIDENT, seed)
        pools.append(traffic.build_pool(mix, pop, seed))
    assert pools[0] == pools[1]
    assert [p for p, _ in pools[0]] != [p for p, _ in pools[2]]
    # every seed gets the same set of frame sizes, in another order
    assert sorted(n for _, n in pools[0]) == sorted(n for _, n in pools[2])


def test_check_sequence_is_seeded_revisits_and_repeats():
    mix = _mix("index-flatout")
    pop = traffic.Population(mix, RESIDENT, 5)
    a = traffic.check_sequence(mix, pop, 5, loaded=1000, stored=20_000)
    b = traffic.check_sequence(mix, pop, 5, loaded=1000, stored=20_000)
    assert [r["ids"] for r in a] == [r["ids"] for r in b]
    assert all(np.array_equal(x["amounts"], y["amounts"]) for x, y in zip(a, b))
    seen: dict = {}
    for rpc in a:
        assert len(rpc["ids"]) in mix["rows"]
        for i in rpc["ids"]:
            seen[i] = seen.get(i, 0) + 1
    assert max(seen.values()) > 16          # windows wrap
    assert any(len(set(r["ids"])) < len(r["ids"]) for r in a)  # repeats in a frame
    assert all(a[k]["clock"] < a[k + 1]["clock"] for k in range(len(a) - 1))


def test_zipf_over_the_permutation_touches_the_expected_accounts():
    """Zipf(0.8) over n accounts: the expected number of distinct accounts
    in k draws is sum(1 - (1 - p_r)^k); the hottest account draws p_0."""
    mix = _mix("index-flatout")
    n, k = 200_000, 100_000
    pop = traffic.Population(mix, n, 11)
    ranks = pop.draw_ranks(traffic.rng_for(11, "rows"), k)
    p = np.arange(1, n + 1, dtype=np.float64) ** -0.8
    p /= p.sum()
    expected = float((1.0 - (1.0 - p) ** k).sum())
    distinct = len(np.unique(pop.perm[ranks]))
    assert abs(distinct - expected) < 0.02 * expected
    assert abs((ranks == 0).mean() - p[0]) < 0.2 * p[0]
    assert sorted(pop.perm.tolist()) == list(range(n))   # a permutation


def test_wire_forms_round_trip():
    ids, amounts, types = ["a-1", "bb-22"], [250, 70_000], [2, 0]
    frame = traffic.encode_index_frame(ids, amounts, types)
    assert frame[:4] == b"IDX1" and int.from_bytes(frame[4:8], "little") == 2
    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from igaming_platform_tpu.serve.wire import decode_index_batch
    got = decode_index_batch(frame)
    assert [bytes(i).decode() for i in got[0]] == ids
    assert got[1].tolist() == amounts and got[2].tolist() == types
    req = risk_pb2.ScoreBatchRequest.FromString(
        traffic.encode_proto_batch(ids, amounts, types))
    assert [(t.account_id, t.amount, t.transaction_type)
            for t in req.transactions] == [("a-1", 250, "bet"),
                                           ("bb-22", 70_000, "deposit")]
    resp = risk_pb2.ScoreBatchResponse(results=[
        risk_pb2.ScoreTransactionResponse(score=61, action=2, rule_score=20,
                                          ml_score=0.875,
                                          reason_codes=["SESSION_COLD", "X"]),
        risk_pb2.ScoreTransactionResponse()])
    cols = traffic.decode_proto_response(resp.SerializeToString())
    assert cols["score"].tolist() == [61, 0] and cols["action"].tolist() == [2, 0]
    assert cols["rule_score"].tolist() == [20, 0]
    assert cols["ml_score"].tolist() == [0.875, 0.0]
    assert cols["reasons"] == [frozenset({"SESSION_COLD", "X"}), frozenset()]
