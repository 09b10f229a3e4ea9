"""The eight per-layer metrics of PR 38 (the leaf spans that tile
``score.dispatch``, the waits, the H2D counters, the share of the host's wall spent on a CPU):
each data file through the reader it names on hand-built ``Readings``,
the manifest with them in it, and one CPU rehearsal of a cell in which
the program really opens every span and moves every counter."""

from copy import deepcopy

import pytest

from chipbench import harness, run, validate
from chipbench.readers import READERS, Readings

# tests/test_chipbench.py takes this module's names with ``import *``:
# the tests alone, so that ``run`` here does not hide test_reference's
# fixture of that name there.
__all__ = [
    "test_each_span_metric_reads_its_stage_or_counter",
    "test_the_manifest_lists_them_last_and_validates",
    "test_a_rehearsed_cell_opens_every_span_and_the_leaves_tile_dispatch",
]

STAGES = {  # hostprof stage -> total_us over a window of 1,000 rows
    "launch": 15_000.0, "post_launch": 2_500.0, "dispatch.self": 1_250.0,
    "lane_wait": 200.0, "device_wait": 7_000.0,
    "dispatch": 45_000.0, "readback": 11_000.0,
}
COUNTERS = {
    "risk_h2d_transfers_total": 90.0, "risk_h2d_bytes_total": 176_760.0,
    "client.chunks_ok": 10.0, "client.rows_ok": 1_000.0,
    "risk_host_stage_cpu_seconds_total": 0.8,
    "risk_host_stage_self_seconds_total": 1.0,
}
# metric -> (expected from the readings above, unit, layer, what it reads)
EXPECTED = {
    "launch_us_per_row": (15.0, "us/row", "dispatch", ["launch"]),
    "post_launch_us_per_row": (2.5, "us/row", "dispatch", ["post_launch"]),
    "dispatch_self_us_per_row": (1.25, "us/row", "dispatch", ["dispatch.self"]),
    "lane_wait_us_per_row": (0.2, "us/row", "admission", ["lane_wait"]),
    "device_wait_us_per_row": (7.0, "us/row", "readback", ["device_wait"]),
    "h2d_transfers_per_chunk": (9.0, "1/chunk", "dispatch",
                                ["risk_h2d_transfers_total", "client.chunks_ok"]),
    "h2d_bytes_per_row": (176.76, "B/row", "dispatch",
                          ["risk_h2d_bytes_total", "client.rows_ok"]),
    "host_oncpu_share": (80.0, "%", "host",
                         ["risk_host_stage_cpu_seconds_total",
                          "risk_host_stage_self_seconds_total"]),
}


def _read(name: str, stages: dict, counters: dict):
    m = validate.load_data("layer_metrics", name)
    return m, READERS[m["reader"]](
        m, Readings(config={}, rows_ok=1_000, stages=stages, counters=counters))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_span_metric_reads_its_stage_or_counter(name):
    want, unit, layer, sources = EXPECTED[name]
    m, got = _read(name, STAGES, COUNTERS)
    assert got == pytest.approx(want)
    assert (m["unit"], m["layer"], m["moves"]) == (unit, layer, "txns_per_s")
    assert "workloads" not in m  # all four cells run this path
    # with any one of its sources gone (a parent that lacks the span or
    # the counter) it reads nothing and raises nothing
    for gone in sources:
        stages = {k: v for k, v in STAGES.items() if k != gone}
        counters = {k: v for k, v in COUNTERS.items() if k != gone}
        assert _read(name, stages, counters)[1] is None, gone
    assert _read(name, {}, {})[1] is None


def test_the_manifest_lists_them_last_and_validates():
    assert validate.check_manifest() == []
    assert run.main(["--validate"]) == 0
    manifest = validate.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    assert set(names[-len(EXPECTED):]) == set(EXPECTED)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (_, unit, layer, _) in EXPECTED.items():
        entry = by_name[name]
        assert (entry["unit"], entry["layer"]) == (unit, layer)
        assert "workloads" not in entry
    for cell in manifest["workloads"]:
        got = {m["name"] for m in validate.load_cell(cell["name"])["per_layer"]}
        assert set(EXPECTED) <= got, cell["name"]


def test_a_rehearsed_cell_opens_every_span_and_the_leaves_tile_dispatch():
    """The program itself, on the CPU at the rehearsal's size: every new
    stage and counter is there to read, ``score.dispatch`` is tiled by its
    leaves and its own ``.self`` row, and the wait for the step lies
    inside ``score.readback``."""
    spec = deepcopy(validate.load_cell("stateful-index-flatout"))
    r = harness.Run(spec, seed=3_800_000_021, seconds=1.5, trace=False,
                    rehearse=True)
    r.boot()
    try:
        r.fill()
        ok, _ = r.check()
        s0, c0 = r.stage_totals(), r.counters()
        result = r.window()
        s1, c1 = r.stage_totals(), r.counters()
    finally:
        r.shutdown()
    assert ok and result["correct"] and result["failed"] == 0
    for name in EXPECTED:
        assert result["per_layer"].get(name, {}).get("value") is not None, name
    stages = {k: s1[k] - s0.get(k, 0.0) for k in s1}
    tiles = ("launch", "post_launch", "session", "pad", "lock_wait",
             "dispatch.self")
    assert all(stages[k] > 0 for k in tiles + ("dispatch", "lane_wait",
                                               "device_wait", "readback"))
    assert sum(stages[k] for k in tiles) == pytest.approx(
        stages["dispatch"], rel=0.02)
    assert stages["device_wait"] <= stages["readback"]
    assert stages["readback.self"] == pytest.approx(
        stages["readback"] - stages["device_wait"], rel=0.02)
    # one launch a chunk, each handed the same host arguments: the seven
    # arrays, the thresholds and the row count of `_launch_cached`
    launches = c1["risk_device_dispatches_total"] - c0["risk_device_dispatches_total"]
    transfers = c1["risk_h2d_transfers_total"] - c0["risk_h2d_transfers_total"]
    assert launches > 0 and transfers == 9 * launches
    assert result["per_layer"]["h2d_transfers_per_chunk"]["value"] == 9.0
    grown = {k: c1[k] - c0[k] for k in COUNTERS if k.startswith("risk_")}
    assert all(v > 0 for v in grown.values()), grown
    assert (grown["risk_host_stage_cpu_seconds_total"]
            <= grown["risk_host_stage_self_seconds_total"] * 1.05)
    # who else had the CPU stays on /metrics for operators and is no
    # metric of the benchmark: the chips' kernel reports neither column
    assert c1["risk_process_cpu_seconds_total"] > c0["risk_process_cpu_seconds_total"]
    for name in ("risk_host_cpu_steal_seconds_total", "risk_host_cpu_seconds_total",
                 "risk_process_runqueue_wait_seconds_total"):
        assert c1[name] >= c0[name]
