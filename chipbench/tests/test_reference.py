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
