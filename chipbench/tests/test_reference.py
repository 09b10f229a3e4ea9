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
