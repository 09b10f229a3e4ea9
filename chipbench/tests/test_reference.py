"""The plain reference against the served path at a tiny size on the CPU,
both session heads; and the control (the reference one precision step
down, in the program's place) has to fail the same limits."""

import copy

import pytest

from chipbench import harness, validate

CELLS = {"pattern": "stateful-index-flatout", "transformer": "seqhead-index-flatout",
         # no cell sends proto rows yet (PERF.md, Open question 4a): the mix
         # file and the row path of the reference are kept true here
         "row-path": "stateful-index-flatout"}


@pytest.fixture(scope="module", params=sorted(CELLS))
def run(request):
    spec = copy.deepcopy(validate.load_cell(CELLS[request.param]))
    # one small compiled shape: the CPU compiles every ladder shape at boot
    spec["config"]["env"]["BATCH_SIZE"] = "256"
    # the native store allocates 1,000,000 accounts eagerly (4.4 GB): the
    # explicit CPU boot of a test serves from the Python store instead
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    if request.param == "row-path":
        spec["traffic"] = copy.deepcopy(validate.load_data("traffic", "row-bulk"))
        spec["traffic"]["rows"] = [256]
        spec["traffic"]["check"]["accounts"] = 256
    r = harness.Run(spec, seed=3_000_000_007, seconds=1.0, trace=False,
                    rehearse=True)
    r.boot()
    try:
        r.fill()
        yield r
    finally:
        r.shutdown()


def test_reference_agrees_and_the_control_fails(run):
    ok, numbers = run.check()
    assert ok, numbers
    assert numbers["rows"] >= 256
    if run.index_mode:
        # the check reaches warm windows and the fold, not only cold rows
        assert numbers["warm_rows"] > numbers["rows"] // 2
        assert numbers["folded_rows"] > 0
    limit = run.config["limits"]["fraud_prob_err_in_roundings"]
    assert numbers["fraud_prob_err_in_roundings"] * 3 < limit
    c_ok, control = run.judge(run.config["precision"]["control_operand_dtype"],
                              control=True)
    assert not c_ok, control
    assert control["fraud_prob_err_in_roundings"] > 2 * limit
    # and on the per-row limits: one row's probability and its final score
    for key in ("fraud_prob_max_err", "score_max_err"):
        assert numbers[key] <= run.config["limits"][key] < control[key], key


def test_every_counter_a_metric_file_names_reads_a_number(run):
    """A counter nothing has incremented yet renders no sample; it has to
    read 0 all the same, or the metric over it goes missing from the line
    (PR 24's refusal: ``bulk_shed_share`` in cells that are never shed)."""
    import glob
    import json
    import os

    counters = run.counters()
    assert counters["risk_bulk_shed_total"] == 0.0
    for path in glob.glob(os.path.join(validate.ROOT, "chipbench",
                                       "layer_metrics", "*.json")):
        m = json.load(open(path))
        for key in ("numerator", "denominator", "counter", "per"):
            names = m.get(key, [])
            for name in [names] if isinstance(names, str) else names:
                assert name.startswith("client.") or name in counters, (path, name)


def test_an_answer_altered_where_it_is_produced_is_not_correct(run, monkeypatch):
    """The rest of a run with the timed path broken underneath: the drain
    of the device's packed result adds 7 to one row's score."""
    import numpy as np

    from igaming_platform_tpu.serve import scorer

    if not run.index_mode:
        pytest.skip("no cell sends proto rows; their drain is another seam")
    real = scorer._device_readback

    def altered(out):
        packed = np.array(real(out))
        packed[0, 0] += 7
        return packed

    monkeypatch.setattr(scorer, "_device_readback", altered)
    ok, numbers = run.check()
    assert not ok and numbers["score_max_err"] >= 6, numbers


def test_the_window_goes_on_where_the_warm_up_stopped(run):
    """Warm-up and window are one closed loop over the pool, cut at t0:
    every client sends its share of the pool in order from the first
    frame, through the warm-up and on through the window, and the
    window's identities hold."""
    sent = []
    call = run.call
    run.call = lambda payload, timeout=None: (sent.append(payload),
                                              call(payload, timeout=timeout))[1]
    try:
        result = run.window()
    finally:
        run.call = call
    assert result["correct"] and result["failed"] == 0
    assert run.phase_s["warm_up"] >= 0.5
    warm = len(sent) - result["attempted"]
    assert warm >= int(run.mix["clients"])
    k = int(run.mix["clients"])
    owner = {p: (i % k, i // k) for i, (p, _) in enumerate(run.pool)}
    for c in range(k):
        order = [owner[p][1] for p in sent if owner[p][0] == c]
        laps = len(run.pool[c::k])
        assert order == [i % laps for i in range(len(order))]


def test_the_error_is_counted_in_roundings_of_what_is_judged():
    """A seed whose session head is far more sensitive to the stated
    rounding than its trunk (seed 3000002006 on the chip, PR 30): the error
    of a folded row is held against what the rounding costs on the folded
    probability, not on the stateless one alone; where the two references
    fold differently the stateless probability stands in."""
    import numpy as np

    from chipbench import reference

    f = np.float32
    n = 4
    want = {"ml_score": np.array([0.90, 0.91, 0.10, 0.80], f),
            "ml_base": np.array([0.10, 0.10, 0.10, 0.10], f),
            "sprob": np.array([0.90, 0.91, 0.20, 0.80], f),
            "fold": np.array([True, True, False, True]),
            "cold": np.zeros(n, bool), "warm": np.ones(n, bool),
            "score": np.full(n, 50), "action": np.ones(n, int),
            "rule_score": np.zeros(n, int)}
    exact = dict(want, ml_score=np.array([0.89, 0.90, 0.1001, 0.1001], f),
                 ml_base=np.array([0.1001] * n, f),
                 fold=np.array([True, True, False, False]))
    got = {"ml_score": want["ml_score"] + np.array([0.002, 0, 0, 0], f),
           "score": want["score"], "action": want["action"],
           "rule_score": want["rule_score"],
           "reasons": [frozenset(["SESSION_PATTERN"] if x else [])
                       for x in want["fold"]]}
    part = reference.compare(got, want, exact)
    # rows 0, 1: the folded probability's rounding (0.01 each); row 2: the
    # stateless one; row 3: the references fold differently, stateless too
    assert part["rounding_sq_sum"] == pytest.approx(2 * 0.01 ** 2 + 2 * 1e-4 ** 2, rel=1e-3)
    assert part["stateless_rounding_sq_sum"] == pytest.approx(4 * 1e-4 ** 2, rel=1e-2)
    numbers = reference.merge([part])
    assert numbers["fraud_prob_err_in_roundings"] == pytest.approx(
        0.002 / (0.01 * 2 ** 0.5), rel=1e-2)
    assert numbers["fraud_prob_err_in_stateless_roundings"] == pytest.approx(10.0, rel=1e-2)
    ok, lines = reference.judge(numbers, {"fraud_prob_err_in_roundings": 4.0})
    assert ok and any("in_stateless_roundings" in x and "not judged" in x for x in lines)


# -- preloaded session events (PR 56) ------------------------------------------


def test_at_preloaded_0_the_fill_makes_the_calls_it_made(run):
    """The eight cells the benchmark had before PR 56 start from empty
    windows: their fill detaches the session hook for every admission,
    puts it back, and hands the host index nothing."""
    inner = run.inner
    hook, hooks, chunks = inner.cache.session_hook, [], []
    lookup, prepare = inner.cache.lookup, inner.session.prepare_chunk
    inner.cache.lookup = lambda ids, now=None: (
        hooks.append(inner.cache.session_hook), chunks.append(len(ids)),
        lookup(ids, now=now))[2]
    inner.session.prepare_chunk = lambda *a, **k: pytest.fail("prepare_chunk")
    appends = inner.session.snapshot()["appends"]
    try:
        run.fill()
    finally:
        inner.cache.lookup, inner.session.prepare_chunk = lookup, prepare
    resident, chunk = run.config["resident_accounts"], run.config["fill_chunk"]
    assert chunks == [chunk] * (resident // chunk)
    assert hook is not None and hooks == [None] * len(chunks)
    assert inner.cache.session_hook == hook
    assert "preload" not in run.phase_s
    assert inner.session.snapshot()["appends"] == appends


@pytest.fixture(scope="module")
def deep():
    """A small deployment with warm windows: the transformer head over
    32-event windows, 512 accounts that had sent 1 to 64 events each."""
    spec = copy.deepcopy(validate.load_cell("seqhead-index-flatout"))
    # a 256-row frame runs as four chunks of 64 rows, as in the deep cell
    spec["config"]["env"].update(BATCH_SIZE="64", FEATURE_STORE="python",
                                 SESSION_EVENTS="32")
    spec["config"]["session_events_preloaded"] = {"events": "1-64", "rounds": 4}
    r = harness.Run(spec, seed=3_000_000_011, seconds=1.0, trace=False,
                    rehearse=True)
    r.config.update(resident_accounts=512, fill_chunk=128,
                    store_loaded_accounts=128)
    r.config["env"]["FEATURE_CACHE_CAPACITY"] = "512"
    r.boot()
    try:
        hooks, lookup = [], r.inner.cache.lookup
        r.inner.cache.lookup = lambda ids, now=None: (
            hooks.append(r.inner.cache.session_hook), lookup(ids, now=now))[1]
        r.fill()
        r.inner.cache.lookup = lookup
        r.hooks_seen = hooks
        yield r
    finally:
        r.shutdown()


def test_a_preloaded_fill_leaves_every_history_in_the_index_and_the_ring(deep):
    import numpy as np

    from chipbench import reference, traffic
    from igaming_platform_tpu.serve import session_state

    session, pop = deep.inner.session, deep.pop
    spec = traffic.history_spec(deep.config["session_events_preloaded"])
    snap = session.snapshot()
    whole = traffic.histories(deep.mix, deep.seed, 0, 512, spec)
    assert snap["appends"] == len(whole["amounts"]) > 512 * 20
    assert snap["admissions"] == 512 == snap["rehydrations"]
    # admitted with the hook attached, a fill_chunk at a time
    assert len(deep.hooks_seen) >= 4 and all(
        h == session.on_admit for h in deep.hooks_seen[:4])
    assert deep.phase_s["preload"] > 0
    ids = [pop.id_of_rank(r) for r in range(512)]
    slots = deep.inner.cache.lookup(ids, now=harness.FILL_NOW)
    ring = np.asarray(session_state.ring_rows(
        session.session_ring, deep.jax.numpy.asarray(slots), 32))
    lengths = np.asarray(session.session_length)[slots]
    full = 0
    for rank, account in enumerate(ids):
        want = reference.encode_history(
            traffic.history_of(deep.mix, deep.seed, rank, spec))[0][-32:]
        assert np.array_equal(session.twin_window(account), want), rank
        assert np.array_equal(ring[rank, :len(want)], want), rank
        assert not ring[rank, len(want):].any() and lengths[rank] == len(want)
        full += len(want) == 32
    assert 200 < full < 320  # about half of the accounts hold a full window


def test_a_window_the_ring_lost_is_caught_after_the_fill(deep):
    """The guarantee the preload rests on, broken underneath: the ring
    zeroed after the admission, and then the host index of one probed
    account cut short. (Before the check below sends these accounts
    events: the probe reads the state the fill left.)"""
    import jax.numpy as jnp

    from chipbench import traffic

    session = deep.inner.session
    spec = traffic.history_spec(deep.config["session_events_preloaded"])
    deep.probe_windows(spec)  # sound as it stands
    ring = session.session_ring
    session.adopt(jnp.zeros_like(ring), session.session_cursor,
                  session.session_length)
    try:
        with pytest.raises(SystemExit, match="the ring's rows"):
            deep.probe_windows(spec)
    finally:
        session.adopt(ring, session.session_cursor, session.session_length)
    ranks = traffic.rng_for(deep.seed, "probe").choice(512, size=64, replace=False)
    victim = next(r for r in ranks if len(session.twin_window(deep.pop.id_of_rank(r))) > 1)
    tw = session._twin[deep.pop.id_of_rank(victim)]
    tw.count -= 1
    try:
        with pytest.raises(SystemExit, match="the host index holds"):
            deep.probe_windows(spec)
    finally:
        tw.count += 1
    deep.probe_windows(spec)


def test_the_first_check_rpc_scores_full_windows_and_the_control_fails(deep):
    ok, numbers = deep.check()
    assert ok, numbers
    first = deep.check_log[0][0]
    from chipbench import reference

    ref = reference.Reference(
        deep.params, head=deep.head, head_params=deep.head_params, n_events=32,
        operand_dtype="bfloat16", head_operand_dtype="float32",
        history=deep.history)
    out = ref.score_index(first["ids"], deep.check_log[0][1], first["amounts"],
                          first["types"], first["clock"])
    assert (out["lengths"] == 32).sum() > len(first["ids"]) // 3
    assert out["warm"].mean() > 0.9 and out["lengths"].mean() > 20
    # every row of the check is warm but the few whose history is under
    # three events, and the later RPCs push full windows past their length
    assert numbers["warm_rows"] > 0.95 * numbers["rows"]
    assert numbers["session_bit_mismatch"] == 0 and numbers["folded_rows"] > 0
    c_ok, control = deep.judge(
        deep.config["precision"]["control_operand_dtype"], control=True)
    assert not c_ok, control
    limit = deep.config["limits"]["fraud_prob_err_in_roundings"]
    assert numbers["fraud_prob_err_in_roundings"] * 3 < limit
    assert control["fraud_prob_err_in_roundings"] > 2 * limit


def test_a_reference_that_takes_a_frame_as_one_chunk_is_not_correct(deep):
    """The server scores a 256-row frame as four chunks of BATCH_SIZE 64,
    one after the other: an account that repeats across them sees its
    earlier event, and a replay of the frame as one chunk does not."""
    env = deep.config["env"]
    ok, numbers = deep.judge(deep.config["precision"]["reference_operand_dtype"])
    assert ok, numbers
    env["BATCH_SIZE"] = "256"
    try:
        ok, numbers = deep.judge(
            deep.config["precision"]["reference_operand_dtype"])
    finally:
        env["BATCH_SIZE"] = "64"
    assert not ok and numbers["session_bit_mismatch"] > 0, numbers


def test_a_reference_that_starts_cold_is_not_correct_on_warm_windows(deep):
    """The comparison sees the preload: without the histories the
    reference calls warm rows cold."""
    history, deep.history = deep.history, None
    try:
        ok, numbers = deep.judge(
            deep.config["precision"]["reference_operand_dtype"])
    finally:
        deep.history = history
    assert not ok and numbers["session_bit_mismatch"] > 0, numbers


@pytest.mark.parametrize("counts", [[1, 1, 1, 1], [3, 1, 2], [5], [1, 4, 1, 1, 2]],
                         ids=["distinct", "runs", "one-account", "mixed"])
def test_the_groups_the_preload_writes_are_group_chunks_own(counts):
    import numpy as np

    from igaming_platform_tpu.serve import session_state

    ids = [f"acct-{i}" for i in range(len(counts))]
    mine = harness.account_major_groups(session_state, ids, counts, verify=True)
    theirs = session_state.group_chunk(
        [a for a, k in zip(ids, counts) for _ in range(k)])
    for name in mine.__slots__:
        a, b = getattr(mine, name), getattr(theirs, name)
        assert type(a) is type(b) and list(a) == list(b), name
    for u in range(len(ids)):
        assert np.array_equal(mine.rows_of(u), theirs.rows_of(u))


def test_a_history_is_encoded_with_the_gaps_between_its_rounds():
    import numpy as np

    from chipbench import reference

    h = {"amounts": np.array([100, 2000, 30, 5]), "types": np.array([2, 3, 0, 1]),
         "clocks": np.array([1000.0, 1000.0, 1060.0, 1300.0])}
    events, last = reference.encode_history(h)
    assert last == 1300.0 and events.shape == (4, 12) and events.dtype == np.float32
    assert np.array_equal(events[:, 1], np.log1p([0.0, 0.0, 60.0, 240.0]).astype(np.float32))
    assert np.array_equal(events[:, 0], np.log1p([100.0, 2000.0, 30.0, 5.0]).astype(np.float32))
    assert (events[:, 10] == 1).all() and (events.sum(1) == events[:, 0] + events[:, 1] + 2).all()
    none, last = reference.encode_history({"amounts": np.zeros(0, np.int64),
                                           "types": np.zeros(0, np.uint8),
                                           "clocks": np.zeros(0)})
    assert none.shape == (0, 12) and last == 0.0


@pytest.mark.parametrize("head,platform,want", [
    ("transformer", "cpu", "float32"), ("transformer", "tpu", "bfloat16"),
    ("pattern", "cpu", "float32"), ("keye_vl2", "cpu", "bfloat16"),
    ("keye_vl2", "tpu", "bfloat16"), ("openpangu_ultra", "cpu", "bfloat16"),
    ("lfm2_24b_a2b", "cpu", "bfloat16"), ("falcon_h1_34b", "cpu", "bfloat16"),
    ("ling_3_flash", "cpu", "bfloat16"), ("xing4_29b_a4b", "cpu", "bfloat16")])
def test_a_rehearsal_reads_a_head_that_casts_its_operands_at_the_stated_dtype(
        head, platform, want):
    """Only the MXU rounds float32 operands by itself: a CPU rehearsal
    reads the transformer head's reference at float32, and a backbone's,
    whose program casts both operands of every product, at bfloat16."""
    module = validate.load_code("heads", head)
    assert harness.head_operand_dtype(module, platform, "bfloat16") == want
