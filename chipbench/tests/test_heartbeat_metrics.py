"""The two per-layer metrics of PR 54, both data files over the
``counter_ratio`` reader: ``wake_late_us`` (the heartbeat's mean wake-up
lateness, every cell) and ``rpc_over_50ms_share`` (the SLO plane's share
of requests past its 50 ms objective, the six backbone cells). Each file
through its reader on hand-built ``Readings``, with and without the
counters (the parent's program under these files has none of the first
pair), the manifest with them in it, and one CPU rehearsal in which the
program's own heartbeat and SLO plane move the counters."""

import time
from copy import deepcopy

import pytest

from chipbench import harness, run, validate
from chipbench.readers import Readings, read_all

# tests/test_chipbench.py takes this module's names with ``import *``
__all__ = [
    "test_both_files_validate_and_read_their_counters",
    "test_a_program_without_the_counters_leaves_them_out",
    "test_the_manifest_gives_the_share_to_the_six_backbone_cells_alone",
    "test_a_rehearsed_cell_reads_its_own_heartbeat",
]

BACKBONE_CELLS = [
    "keye-backbone-insession", "keye-deep128-insession", "pangu-mla-insession",
    "lfm2-conv-insession", "falconh1-ssm-insession", "ling-kda-insession",
    "xing-mhc-insession"]
COUNTERS = {  # deltas over a 20 s window
    "risk_host_heartbeat_ticks_total": 398.0,
    "risk_host_heartbeat_late_seconds_total": 0.0995,
    "risk_slo_requests_total": 4_000.0,
    "risk_slo_violations_total": 30.0,
}
# metric -> (expected from the counters above, unit, layer, its counters)
EXPECTED = {
    "wake_late_us": (250.0, "us", "host",
                     ["risk_host_heartbeat_late_seconds_total",
                      "risk_host_heartbeat_ticks_total"]),
    "rpc_over_50ms_share": (0.75, "%", "client",
                            ["risk_slo_violations_total",
                             "risk_slo_requests_total"]),
}


def _files() -> list[dict]:
    return [validate.load_data("layer_metrics", name) for name in EXPECTED]


def _readings(counters: dict) -> Readings:
    return Readings(config={}, rows_ok=1_000, stages={}, counters=counters)


def test_both_files_validate_and_read_their_counters():
    logged = []
    got = read_all(_files(), _readings(COUNTERS), logged.append)
    assert logged == []
    for m in _files():
        want, unit, layer, sources = EXPECTED[m["name"]]
        assert got[m["name"]] == {"value": pytest.approx(want), "unit": unit}
        assert (m["unit"], m["layer"], m["better"], m["source"], m["moves"]) == (
            unit, layer, "lower", "program_counter", "txns_per_s")
        assert (m["reader"], [m["numerator"], m["denominator"]]) == (
            "counter_ratio", sources)
    # a quiet window: the SLO plane counted requests and no violation
    quiet = dict(COUNTERS, risk_slo_violations_total=0.0)
    assert read_all(_files(), _readings(quiet), logged.append)[
        "rpc_over_50ms_share"]["value"] == 0.0


@pytest.mark.parametrize("gone", sorted(COUNTERS) + ["all"])
def test_a_program_without_the_counters_leaves_them_out(gone):
    """The parent's program under this PR's files: no heartbeat counter to
    read. The metric is left out of the line and nothing raises."""
    counters = {k: v for k, v in COUNTERS.items() if gone not in (k, "all")}
    got = read_all(_files(), _readings(counters), lambda line: None)
    for name, (_, _, _, sources) in EXPECTED.items():
        assert (name in got) == (gone != "all" and gone not in sources), name


def test_the_manifest_gives_the_share_to_the_six_backbone_cells_alone():
    assert validate.check_manifest() == []
    assert run.main(["--validate"]) == 0
    manifest = validate.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-2:] == list(EXPECTED)
    assert "workloads" not in by_name["wake_late_us"]  # every cell has a heartbeat
    assert by_name["rpc_over_50ms_share"]["workloads"] == BACKBONE_CELLS
    for name, (_, unit, layer, _) in EXPECTED.items():
        assert (by_name[name]["unit"], by_name[name]["layer"]) == (unit, layer)
    cells = [w["name"] for w in manifest["workloads"]]
    # the three others score in under a millisecond a step: the pattern
    # and transformer heads on one chip, the pattern head on four
    assert sorted(set(cells) - set(BACKBONE_CELLS)) == [
        "mesh4-index-flatout", "seqhead-index-flatout", "stateful-index-flatout"]
    for cell in cells:
        got = {m["name"] for m in validate.load_cell(cell)["per_layer"]}
        assert "wake_late_us" in got, cell
        assert ("rpc_over_50ms_share" in got) == (cell in BACKBONE_CELLS), cell


def test_a_rehearsed_cell_reads_its_own_heartbeat():
    """The program itself, on the CPU at the rehearsal's size: the
    heartbeat ticked through the window, the SLO plane counted the
    window's RPCs, and the cell's line has the one metric it lists."""
    spec = deepcopy(validate.load_cell("stateful-index-flatout"))
    r = harness.Run(spec, seed=5_400_000_017, seconds=1.5, trace=False,
                    rehearse=True)
    r.boot()
    try:
        r.fill()
        ok, _ = r.check()
        c0, t0 = r.counters(), time.perf_counter()
        result = r.window()
        c1, t1 = r.counters(), time.perf_counter()
    finally:
        r.shutdown()
    assert ok and result["correct"] and result["failed"] == 0
    assert result["per_layer"]["wake_late_us"]["unit"] == "us"
    assert result["per_layer"]["wake_late_us"]["value"] >= 0.0
    assert "rpc_over_50ms_share" not in result["per_layer"]
    grown = {k: c1[k] - c0[k] for k in COUNTERS}
    # 20 wakes a second and never more: a fixed sleep only runs late
    assert 1.5 / 0.05 / 2 <= grown["risk_host_heartbeat_ticks_total"] <= (
        (t1 - t0) / 0.05 + 1)
    assert grown["risk_host_heartbeat_late_seconds_total"] >= 0.0
    assert grown["risk_slo_requests_total"] >= result["attempted"]
    assert 0.0 <= grown["risk_slo_violations_total"] <= grown["risk_slo_requests_total"]
