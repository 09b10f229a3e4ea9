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
