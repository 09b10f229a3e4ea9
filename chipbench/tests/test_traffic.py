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


# -- histories: what an account had sent before the run (PR 56) ---------------

SPEC = {"low": 1, "high": 64, "rounds": 4}


@pytest.mark.parametrize("name,digest", [
    ("index-flatout", "0eea1e94a6a529b2"), ("index-insession", "999f35735e04bcd7"),
    ("row-bulk", "d41eb226b1b1332f")])
def test_the_pool_is_the_one_the_ledger_was_measured_on(name, digest):
    """The pool's bytes at one seed, as the tree before PR 56 built them:
    the history generator shares ``draw_context``'s pieces and may not move
    a byte of what the eight cells send."""
    import hashlib

    mix = _mix(name)
    pool = traffic.build_pool(mix, traffic.Population(mix, RESIDENT, 3_000_000_019),
                              3_000_000_019)
    assert hashlib.sha256(b"".join(p for p, _ in pool)).hexdigest()[:16] == digest


def test_histories_are_seeded_and_one_account_needs_no_other():
    mix = _mix("index-insession")
    big = 3_000_000_019
    whole = traffic.histories(mix, big, 0, 300, SPEC)
    again = traffic.histories(mix, big, 0, 300, SPEC)
    other = traffic.histories(mix, big + 1, 0, 300, SPEC)
    for key in whole:
        assert np.array_equal(whole[key], again[key]), key
    assert not np.array_equal(whole["counts"], other["counts"])
    # a block that starts elsewhere, and one account alone, read the same events
    ends = np.cumsum(whole["counts"])
    part = traffic.histories(mix, big, 200, 100, SPEC)
    assert np.array_equal(part["counts"], whole["counts"][200:])
    assert np.array_equal(part["amounts"], whole["amounts"][ends[199]:])
    for rank in (0, 7, 299):
        one = traffic.history_of(mix, big, rank, SPEC)
        lo, hi = ends[rank] - whole["counts"][rank], ends[rank]
        assert np.array_equal(one["amounts"], whole["amounts"][lo:hi])
        assert np.array_equal(one["types"], whole["types"][lo:hi])
        clocks = traffic.history_clocks(big, SPEC["rounds"])
        assert np.array_equal(one["clocks"], clocks[whole["round"][lo:hi]])
    assert np.array_equal(whole["account"], np.repeat(np.arange(300), whole["counts"]))


def test_histories_follow_the_mix_and_fall_into_equal_rounds():
    mix = _mix("index-insession")
    spec = {"low": 1, "high": 256, "rounds": 8}
    h = traffic.histories(mix, 11, 0, 4096, spec)
    counts = h["counts"]
    assert counts.min() == 1 and counts.max() == 256
    assert abs(counts.mean() - 128.5) < 4 and abs((counts >= 128).mean() - 0.5) < 0.03
    shares = np.bincount(h["types"], minlength=4) / len(h["types"])
    for name, p in mix["tx_types"].items():
        assert abs(shares[traffic.TX_TYPES.index(name)] - p) < 0.01, name
    a = mix["amounts"]
    assert abs(np.median(h["amounts"]) - a["median_cents"]) < 0.02 * a["median_cents"]
    assert abs(np.log(h["amounts"]).std() - a["sigma"]) < 0.03
    assert h["amounts"].min() >= a["min_cents"] and h["amounts"].max() <= a["max_cents"]
    # oldest first: an account's rounds never go back, its newest event is in
    # the last round, and its shares differ by at most one event
    ends = np.cumsum(counts)
    for rank in (0, 1, 2, int(np.argmax(counts)), int(np.argmin(counts))):
        rounds = h["round"][ends[rank] - counts[rank]:ends[rank]]
        assert (np.diff(rounds) >= 0).all() and rounds[-1] == spec["rounds"] - 1
        per = np.bincount(rounds, minlength=spec["rounds"])
        if counts[rank] >= spec["rounds"]:
            assert per.max() - per.min() <= 1, per
    clocks = traffic.history_clocks(11, spec["rounds"])
    gaps = np.diff(clocks)
    assert clocks[-1] == traffic.HISTORY_END and (gaps >= 20).all() and (gaps <= 900).all()
    assert not np.array_equal(gaps, np.diff(traffic.history_clocks(12, spec["rounds"])))


@pytest.mark.parametrize("value,want", [
    (0, None),
    ({"events": "1-256", "rounds": 8}, {"low": 1, "high": 256, "rounds": 8}),
    ({"events": "2048-2048", "rounds": 16}, {"low": 2048, "high": 2048, "rounds": 16}),
    ({"events": "0-32", "rounds": 1}, {"low": 0, "high": 32, "rounds": 1}),
    (16, ValueError), (True, ValueError), ("1-16", ValueError),
    ({"events": "16", "rounds": 4}, ValueError),
    ({"events": "32-16", "rounds": 4}, ValueError),
    ({"events": "1-16", "rounds": 0}, ValueError),
    ({"events": "1-16", "rounds": 2.5}, ValueError),
    ({"events": "1-16"}, ValueError),
    ({"events": "1-16", "rounds": 4, "clock": 0}, ValueError),
], ids=["zero", "range", "fixed", "from-none", "a-count", "a-flag", "a-string",
        "no-range", "backwards", "no-round", "half-a-round", "rounds-missing",
        "a-key-more"])
def test_session_events_preloaded_is_zero_or_a_range_in_rounds(value, want):
    if want is ValueError:
        with pytest.raises(ValueError):
            traffic.history_spec(value)
    else:
        assert traffic.history_spec(value) == want


def test_an_account_without_a_history_has_none():
    mix = _mix("index-insession")
    h = traffic.histories(mix, 5, 0, 200, {"low": 0, "high": 2, "rounds": 2})
    assert (h["counts"] == 0).any() and len(h["amounts"]) == h["counts"].sum()
    empty = int(np.flatnonzero(h["counts"] == 0)[0])
    assert len(traffic.history_of(mix, 5, empty, {"low": 0, "high": 2, "rounds": 2})["clocks"]) == 0
    pop = traffic.Population(mix, 1000, 5)
    assert [pop.rank_of_id(pop.id_of_rank(r)) for r in (0, 17, 999)] == [0, 17, 999]
