"""The benchmark harness's own tests (``chipbench/tests/``), collected
under the tier-1 command so that the harness, every file a cell brings
and the A/A data are guarded by the run the driver makes (PERF.md Open
question 9). Thin on purpose: it holds one case of its own, beside the one
case of that directory that this tree can no longer pass (``LAST_EIGHT``),
and since PR 46 a second pair of the same kind (``LAST_NINE``), since PR 57 a
third (``LAST_TWO``) and since PR 59 a fourth (``LAST_ELEVEN``); PR 65's and
PR 68's cell tests hold their entries to no place, and the four name what they
appended.
Since PR 67 a fifth pair of another kind (``NINE_LEAVES``): a case of that
directory counts the host arrays of a launch, and the launch has one.

Every test module of that directory is imported here under its own
names, so each case counts as one. Their fixtures come with them; the
``copy`` fixture is completed as ``chipbench/conftest.py`` completes it
for a run of that directory itself. A harness run sets a deployment's
environment in this process (``harness.Run.boot``): it is put back when
the module is done, so the worker's next file boots what it asks for.
"""

import os

import pytest

from chipbench.conftest import add_sources
from chipbench.tests import test_falconh1_cell as _falconh1
from chipbench.tests import test_heartbeat_metrics as _heartbeat
from chipbench.tests import test_mellum_cell as _mellum
from chipbench.tests import test_span_metrics as _span_metrics
from chipbench.tests.conftest import copy as _bare_copy
from chipbench.tests.test_bounds import *  # noqa: F401,F403
from chipbench.tests.test_falconh1_cell import *  # noqa: F401,F403
from chipbench.tests.test_heartbeat_metrics import *  # noqa: F401,F403
from chipbench.tests.test_kexaone_cell import *  # noqa: F401,F403
from chipbench.tests.test_lfm2_cell import *  # noqa: F401,F403
from chipbench.tests.test_ling_cell import *  # noqa: F401,F403
from chipbench.tests.test_longcat_cell import *  # noqa: F401,F403
from chipbench.tests.test_manifest import *  # noqa: F401,F403
from chipbench.tests.test_mellum_cell import *  # noqa: F401,F403
from chipbench.tests.test_phi4flash_cell import *  # noqa: F401,F403
from chipbench.tests.test_reference import *  # noqa: F401,F403
from chipbench.tests.test_seam import *  # noqa: F401,F403
from chipbench.tests.test_seam import sourced as _seam_sourced
from chipbench.tests.test_span_metrics import *  # noqa: F401,F403
from chipbench.tests.test_trace_reduce import *  # noqa: F401,F403
from chipbench.tests.test_traffic import *  # noqa: F401,F403
from chipbench.tests.test_xing_cell import *  # noqa: F401,F403


LAST_EIGHT = (
    "chipbench/tests/test_span_metrics.py holds PR 38's eight metrics to the "
    "LAST eight places of per_layer; PR 43's ten follow them, because a PR "
    "that is no benchmark PR may neither edit that file nor put an entry of "
    "BENCHMARK.json anywhere but at the end of its list (the driver reads one "
    "in the middle as a change to what was there). A benchmark PR repairs the "
    "case: PERF.md Open question 9")


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=LAST_EIGHT)
def test_the_manifest_lists_them_last_and_validates():  # noqa: F811
    """That file's case of this name, run as it stands and expected to fail
    on its third assertion alone; strict, so that the repair takes this
    wrapper and the case below away."""
    _span_metrics.test_the_manifest_lists_them_last_and_validates()


def test_the_manifest_keeps_the_span_metrics_together_and_validates():
    """What the case above held that still holds: the eight follow everything
    the benchmark had before them, together and without a ``workloads`` key,
    whatever came later names its cells (but for the two metrics since that
    every cell reads too), and every cell reads the eight."""
    from chipbench import run, validate

    expected = _span_metrics.EXPECTED
    assert validate.check_manifest() == []
    assert run.main(["--validate"]) == 0
    manifest = validate.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    first = min(names.index(name) for name in expected)
    assert set(names[first:first + len(expected)]) == set(expected)
    assert first == 27  # the twenty-seven PR 37 left come before them
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (_, unit, layer, _) in expected.items():
        entry = by_name[name]
        assert (entry["unit"], entry["layer"]) == (unit, layer)
        assert "workloads" not in entry
    # PR 46's counter is read wherever the index program is launched, and
    # PR 54's heartbeat runs in every process
    assert [n for n in names[first + len(expected):]
            if "workloads" not in by_name[n]] == ["padded_rows_per_row",
                                                  "wake_late_us"]
    for cell in manifest["workloads"]:
        got = {m["name"] for m in validate.load_cell(cell["name"])["per_layer"]}
        assert set(expected) <= got, cell["name"]


NINE_LEAVES = (
    "chipbench/tests/test_span_metrics.py's rehearsal holds a launch of the "
    "index program to NINE host arguments (the seven arrays, the thresholds "
    "and the row count); since PR 67 a launch hands over ONE, the packed chunk "
    "(serve/index_program.pack_chunk), and the thresholds live on the device. "
    "A PR that is no benchmark PR may not edit that file; a benchmark PR "
    "writes 1 there: PERF.md Open question 9")


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=NINE_LEAVES)
def test_a_rehearsed_cell_opens_every_span_and_the_leaves_tile_dispatch():  # noqa: F811
    """That file's case of this name, run as it stands and expected to fail
    where it counts the transfers of a launch, every assertion before that
    one passed; strict, as above."""
    _span_metrics.test_a_rehearsed_cell_opens_every_span_and_the_leaves_tile_dispatch()


def test_a_rehearsed_cell_tiles_dispatch_and_hands_over_one_array_a_launch():
    """The case above with the launch's host arguments counted as they are
    since PR 67, one a launch and 68 bytes a padded row, and every other
    assertion of it as it stands."""
    from copy import deepcopy

    from chipbench import harness, validate

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
    for name in _span_metrics.EXPECTED:
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
    grew = lambda k: c1[k] - c0[k]  # noqa: E731
    launches = grew("risk_device_dispatches_total")
    assert launches > 0 and grew("risk_h2d_transfers_total") == launches
    assert grew("risk_h2d_bytes_total") == 68 * grew("risk_launch_padded_rows_total")
    assert result["per_layer"]["h2d_transfers_per_chunk"]["value"] == 1.0
    grown = {k: grew(k) for k in _span_metrics.COUNTERS if k.startswith("risk_")}
    assert all(v > 0 for v in grown.values()), grown
    assert (grown["risk_host_stage_cpu_seconds_total"]
            <= grown["risk_host_stage_self_seconds_total"] * 1.05)
    assert c1["risk_process_cpu_seconds_total"] > c0["risk_process_cpu_seconds_total"]
    for name in ("risk_host_cpu_steal_seconds_total", "risk_host_cpu_seconds_total",
                 "risk_process_runqueue_wait_seconds_total"):
        assert c1[name] >= c0[name]


LAST_NINE = (
    "chipbench/tests/test_falconh1_cell.py holds PR 45's nine metrics to the "
    "LAST nine places of per_layer; PR 46's padded_rows_per_row follows them "
    "(and PR 49's and PR 52's configurations, cells and ten metrics each, PR 57's and eleven, PR 59's and fifteen, PR 65's and fifteen, PR 68's and eight), for the reason "
    "LAST_EIGHT gives. A benchmark PR repairs the case: "
    "PERF.md Open question 9")


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=LAST_NINE)
def test_the_falconh1_configuration_is_held_to_its_source_and_states_its_cut():  # noqa: F811
    """That file's case of this name, run as it stands and expected to fail
    on its last assertion alone; strict, as above."""
    _falconh1.test_the_falconh1_configuration_is_held_to_its_source_and_states_its_cut()


def test_the_falconh1_configuration_holds_with_later_metrics_set_aside(monkeypatch):
    """The case above, every assertion as it stands, over the manifest cut
    off after the nine metrics, the configuration and the cell it expects
    last: what follows them is what later PRs appended, named here."""
    from chipbench import validate

    manifest = validate.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    last = max(names.index(name) for name in _falconh1.METRICS)
    assert names[last + 1] == "padded_rows_per_row"  # PR 46
    assert all(name.startswith(("ling_", "kda_")) for name in names[last + 2:last + 12])  # PR 49
    assert all(name.startswith(("xing_", "hc_")) for name in names[last + 12:last + 22])  # PR 52
    assert names[last + 22:last + 24] == ["wake_late_us", "rpc_over_50ms_share"]  # PR 54
    assert all(name.startswith("mellum_") for name in names[last + 24:last + 35])  # PR 57
    assert all(name.startswith(("phi4flash_", "selective_scan_"))
               for name in names[last + 35:last + 50])  # PR 59
    assert all(name.startswith("kexaone_") for name in names[last + 50:last + 65])  # PR 65
    assert all(name.startswith("longcat_") for name in names[last + 65:])  # PR 68
    assert len(names[last + 65:]) == 8
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[cells.index(_falconh1.CELL) + 1:] == [
        "ling-kda-insession", "xing-mhc-insession", "mellum2-swa-deep4096",
        "phi4flash-yoco-deep2048", "kexaone-mtp-deep2048",
        "longcat-scmoe-deep2048"]  # PR 49, 52, 57, 59, 65, 68
    cut = dict(manifest, per_layer=manifest["per_layer"][:last + 1],
               configs=manifest["configs"][:cells.index(_falconh1.CELL) + 1],
               workloads=manifest["workloads"][:cells.index(_falconh1.CELL) + 1])
    monkeypatch.setattr(_falconh1.validate, "load_manifest",
                        lambda *args, **kwargs: cut)
    _falconh1.test_the_falconh1_configuration_is_held_to_its_source_and_states_its_cut()


LAST_TWO = (
    "chipbench/tests/test_heartbeat_metrics.py holds PR 54's two metrics to the "
    "LAST two places of per_layer and every cell outside its BACKBONE_CELLS to "
    "the three host-bound ones; PR 57's cell and eleven metrics follow, and PR 59's "
    "and PR 65's cells and fifteen each and PR 68's and eight, for the reason LAST_EIGHT gives. A benchmark PR repairs the case: PERF.md Open "
    "question 9")


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=LAST_TWO)
def test_the_manifest_gives_the_share_to_the_six_backbone_cells_alone():  # noqa: F811
    """That file's case of this name, run as it stands and expected to fail
    on its third assertion; strict, as above."""
    _heartbeat.test_the_manifest_gives_the_share_to_the_six_backbone_cells_alone()


def test_the_manifest_gives_the_share_to_the_cells_it_named(monkeypatch):
    """The case above, every assertion as it stands, over the manifest cut
    off before what PR 57, PR 59, PR 65 and PR 68 appended (a configuration, a
    cell and eleven, fifteen, fifteen and eight metrics, which read neither of
    the two:
    ``wake_late_us`` lists no cells and is read there too,
    ``rpc_over_50ms_share`` keeps its list)."""
    from chipbench import validate

    manifest = validate.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    last = names.index("rpc_over_50ms_share")
    assert all(name.startswith("mellum_") for name in names[last + 1:last + 12])  # PR 57
    assert all(name.startswith(("phi4flash_", "selective_scan_"))
               for name in names[last + 12:last + 27])  # PR 59
    assert all(name.startswith("kexaone_") for name in names[last + 27:last + 42])  # PR 65
    assert all(name.startswith("longcat_") for name in names[last + 42:]) \
        and len(names[last + 42:]) == 8  # PR 68
    later = ["mellum2-swa-deep4096", "phi4flash-yoco-deep2048",
             "kexaone-mtp-deep2048", "longcat-scmoe-deep2048"]
    assert [w["name"] for w in manifest["workloads"][-4:]] == later
    for cell in later:
        assert "wake_late_us" in {
            m["name"] for m in validate.load_cell(cell)["per_layer"]}
    cut = dict(manifest, per_layer=manifest["per_layer"][:last + 1],
               configs=manifest["configs"][:-4],
               workloads=manifest["workloads"][:-4])
    monkeypatch.setattr(_heartbeat.validate, "load_manifest",
                        lambda *args, **kwargs: cut)
    _heartbeat.test_the_manifest_gives_the_share_to_the_six_backbone_cells_alone()


LAST_ELEVEN = (
    "chipbench/tests/test_mellum_cell.py holds PR 57's configuration, cell and "
    "eleven metrics to the LAST places of their lists; PR 59's and PR 65's configuration, "
    "cell and fifteen metrics each follow, and PR 68's and eight, for the reason LAST_EIGHT gives. A "
    "benchmark PR repairs the case: PERF.md Open question 9")


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=LAST_ELEVEN)
def test_the_mellum_configuration_is_held_to_its_source_and_states_its_cut():  # noqa: F811
    """That file's case of this name, run as it stands and expected to fail
    on the first of its last assertions; strict, as above."""
    _mellum.test_the_mellum_configuration_is_held_to_its_source_and_states_its_cut()


def test_the_mellum_configuration_holds_with_later_entries_set_aside(monkeypatch):
    """The case above, every assertion as it stands, over the manifest cut
    off after the configuration, the cell and the eleven metrics it expects
    last: what follows them is what PR 59, PR 65 and PR 68 appended, named
    here."""
    from chipbench import validate

    manifest = validate.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    last = max(names.index(name) for name in _mellum.METRICS)
    assert all(name.startswith(("phi4flash_", "selective_scan_"))
               for name in names[last + 1:last + 16])  # PR 59
    assert all(name.startswith("kexaone_") for name in names[last + 16:last + 31])  # PR 65
    assert all(name.startswith("longcat_") for name in names[last + 31:]) \
        and len(names[last + 31:]) == 8  # PR 68
    cells = [w["name"] for w in manifest["workloads"]]
    at = cells.index(_mellum.CELL)
    assert cells[at + 1:] == ["phi4flash-yoco-deep2048", "kexaone-mtp-deep2048",
                              "longcat-scmoe-deep2048"]
    assert [c["name"] for c in manifest["configs"]][at + 1:] == [
        "risk-seqhead-phi-4-mini-flash", "risk-seqhead-k-exaone-236b-a23b",
        "risk-seqhead-longcat-flash-omni"]
    cut = dict(manifest, per_layer=manifest["per_layer"][:last + 1],
               configs=manifest["configs"][:at + 1],
               workloads=manifest["workloads"][:at + 1])
    monkeypatch.setattr(_mellum.validate, "load_manifest",
                        lambda *args, **kwargs: cut)
    _mellum.test_the_mellum_configuration_is_held_to_its_source_and_states_its_cut()


@pytest.fixture
def copy(_bare_copy, request):
    if "sourced" not in request.fixturenames:
        add_sources(_bare_copy)
    return _bare_copy


@pytest.fixture
def sourced(_seam_sourced):  # test_seam's own, then the files it lacks
    add_sources(_seam_sourced)
    return _seam_sourced


@pytest.fixture(autouse=True, scope="module")
def _environment_put_back():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)
