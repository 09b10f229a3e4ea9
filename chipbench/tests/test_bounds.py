"""The bounds of the host-clock metrics are what the committed A/A runs
give: recomputed here, and ``BENCHMARK.json`` may not state less."""

import numpy as np
import pytest

from chipbench import bounds, validate

CELLS = [w["name"] for w in validate.load_manifest()["workloads"]]
BOUNDED = [m["name"] for m in validate.load_manifest()["end_to_end"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_aa_runs_all_correct(cell):
    lines = bounds.load_runs()[cell]
    # 18 a cell until PR 56, whose chip budget gave its one-chip cell two
    # sets of six and its four-chip cell, at four times a run's cost, one
    assert len(lines) >= (bounds.SET if cell == "mesh4-index-flatout"
                          else 2 * bounds.SET if cell == "keye-deep128-insession"
                          else 18)
    assert all(line["correct"] and line["failed"] == 0 for line in lines)
    assert len({line["seed"] for line in lines}) == len(lines)
    run_seconds = validate.load_manifest()["run_seconds"]
    assert {line["run_seconds"] for line in lines} == {run_seconds}
    assert {line["cell"] for line in lines} == {cell}


@pytest.mark.parametrize("metric", BOUNDED)
def test_no_bound_is_under_what_the_aa_runs_give(metric):
    stated = {m["name"]: m["bound"]
              for m in validate.load_manifest()["end_to_end"]}[metric]
    derived = bounds.derive()[metric]
    assert set(derived["cells"]) == set(CELLS)
    assert stated >= derived["bound"], derived
    assert all(stated >= (c["aa_p95"] or 0.0) for c in derived["cells"].values())
    if metric != "setup_s":  # judged by its median alone, never by its spread
        widest = max(c["spread"] for c in derived["cells"].values())
        assert stated <= 8 * widest
        for lines in bounds.load_runs().values():
            values = [line[metric] for line in lines]
            assert bounds.too_tight_share(values, stated) < 0.1


def test_the_draws_are_seeded_and_the_sixes_disjoint():
    values = np.linspace(100.0, 123.0, 24)
    a, b = bounds.aa_differences(values), bounds.aa_differences(values)
    assert (a == b).all() and len(a) == bounds.DRAWS
    assert a.min() >= 0 and 0 < a.max() < 23 / 100
    with pytest.raises(ValueError):
        bounds.aa_differences(values[:11])
    assert (bounds.aa_differences([5.0] * 12) == 0).all()


@pytest.mark.parametrize("share, bound", [
    (0.0, 0.01), (0.0099, 0.01), (0.0301, 0.035), (0.035, 0.035),
    (0.0649, 0.065), (0.2, 0.10)])
def test_a_bound_is_rounded_up_to_the_next_half_percent(share, bound):
    assert bounds.round_up(share) == bound


def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics
    values = [9.0, 10.0, 10.5, 11.0, 12.0, 30.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert bounds.spread(values) == pytest.approx((q3 - q1) / 10.75)
    # the tightness reading drops each six's farthest run: one stalled run
    # in twelve does not make a bound too tight, a wide cell does
    calm = [100.0, 100.5, 101.0, 99.5, 99.0, 100.2] * 2
    assert bounds.too_tight_share(calm[:11] + [70.0], 0.05) == 0.0
    assert bounds.too_tight_share(list(np.linspace(90, 110, 12)), 0.05) == 1.0
    # one set of six is read as it stands, without draws
    assert bounds.too_tight_share(calm[:5] + [70.0], 0.05) == 0.0
    assert bounds.too_tight_share(list(np.linspace(90, 110, 6)), 0.05) == 1.0
