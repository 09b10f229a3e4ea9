"""Perf-trajectory tool (tools/benchtrend.py): the committed artifacts
must normalize into the known trajectory (the numbers each PR's artifact
measured), and the regression flagger must catch a synthetically
regressed artifact while honoring the comparability discipline — same
family AND same source path only. Parser shapes (JSONL, the driver
wrapper, variant families) are pinned on ``tmp_path`` fixtures."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from tools.benchtrend import (build_trajectory, flag_regressions,
                              load_artifact, main, normalize, render_table)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def repo_rows():
    return build_trajectory(str(REPO))


def _row(rows, file):
    hit = [r for r in rows if r.get("file") == file]
    assert hit, f"{file} missing from trajectory: {[r.get('file') for r in rows]}"
    return hit[0]


# ---------------------------------------------------------------------------
# The committed trajectory


def test_trajectory_covers_every_revision(repo_rows):
    revisions = {r["revision"] for r in repo_rows if "revision" in r}
    # r14's artifact was removed with the other records of the retired
    # remote-device rig (CHANGES.md, PR 21).
    assert revisions >= set(range(1, 17)) - {14}, sorted(revisions)
    assert not [r for r in repo_rows if "error" in r]


def test_deadline_r12_row(repo_rows):
    r = _row(repo_rows, "DEADLINE_r12.json")
    assert r["flat_out_txns_per_sec"] == pytest.approx(227328.0)
    assert r["flat_out_source"] == "flat_out.txns_per_sec"
    assert r["paced_p99_ms"] == pytest.approx(19.599)
    assert r["paced_p99_source"] == "paced.rpc_p99_ms"
    # The e2e p99 column takes the closed-loop flat-out arm, NOT the
    # paced arm's 19.6 (dotted path beats the bare-key recursive search).
    assert r["e2e_p99_ms"] == pytest.approx(208.538)
    assert r["e2e_p99_source"] == "flat_out.rpc_p99_ms"


def test_paced_p99_trajectory_r12_to_r15(repo_rows):
    assert _row(repo_rows, "MESH_r15.json")["paced_p99_ms"] == pytest.approx(
        6.31)
    # The paced series improves across the PRs that measured it — the
    # trajectory the trend table exists to show.
    by_rev = {r["revision"]: r["paced_p99_ms"] for r in repo_rows
              if r.get("paced_p99_ms") is not None}
    assert by_rev[12] > by_rev[15]


def test_session_r13_stateful_flat_out(repo_rows):
    r = _row(repo_rows, "SESSION_r13.json")
    assert r["flat_out_source"] == "session_ab.rows_per_s_session_on"
    assert r["flat_out_txns_per_sec"] == pytest.approx(59690.7, rel=1e-4)


def _write(tmp, name, doc):
    (tmp / name).write_text(json.dumps(doc))


def test_jsonl_artifacts_parse_line_delimited(tmp_path):
    lines = [{"metric": "soak_concurrent_score_rps", "value": 900.0},
             {"metric": "soak_wire_txns_per_sec", "value": 475272.5,
              "rpc_p99_ms": 155.3}]
    (tmp_path / "SOAK_r03.json").write_text(
        "\n".join(json.dumps(line) for line in lines) + "\n")
    doc = load_artifact(str(tmp_path / "SOAK_r03.json"))
    assert isinstance(doc, list) and len(doc) == 2
    row = normalize(str(tmp_path / "SOAK_r03.json"), doc)
    assert row is not None and row["family"] == "SOAK"
    assert row["revision"] == 3


def test_wrapper_artifacts_unwrap_parsed(tmp_path):
    # The {cmd, parsed, rc, tail} driver shape: metrics live in `parsed`.
    _write(tmp_path, "BENCH_r03.json",
           {"cmd": "python bench.py", "rc": 0, "tail": "...",
            "parsed": {"e2e_txns_per_sec": 504832.0}})
    r = _row(build_trajectory(str(tmp_path)), "BENCH_r03.json")
    assert r["flat_out_txns_per_sec"] == pytest.approx(504832.0)
    assert r["flat_out_source"] == "e2e_txns_per_sec"


def test_variant_filenames_stay_in_their_own_family(tmp_path):
    _write(tmp_path, "BENCH_MATRIX_r03.json", {"e2e_txns_per_sec": 200000.0})
    _write(tmp_path, "BENCH_MATRIX_r03_cpu_control.json",
           {"e2e_txns_per_sec": 490000.0})
    rows = build_trajectory(str(tmp_path))
    assert _row(rows, "BENCH_MATRIX_r03.json")["family"] == "BENCH_MATRIX"
    assert (_row(rows, "BENCH_MATRIX_r03_cpu_control.json")["family"]
            == "BENCH_MATRIX_cpu_control")
    # A device run below its own CPU control is never a "regression":
    # the variant is a separate series.
    assert flag_regressions(rows, noise=0.15) == []


def test_non_artifact_json_is_skipped(repo_rows):
    files = {r.get("file") for r in repo_rows}
    assert "BASELINE.json" not in files and "EVAL.json" not in files


def test_repo_flags_are_same_family_same_source(repo_rows):
    flags = flag_regressions(repo_rows, noise=0.15)
    by_key = {}
    for r in repo_rows:
        for col, src in (("flat_out_txns_per_sec", "flat_out_source"),
                         ("paced_p99_ms", "paced_p99_source"),
                         ("e2e_p99_ms", "e2e_p99_source")):
            if r.get(col) is not None:
                by_key.setdefault((r["family"], r[src]), []).append(r)
    for f in flags:
        fam = _row(repo_rows, f["file"])["family"]
        best_fam = _row(repo_rows, f["best_file"])["family"]
        assert fam == best_fam, f


def test_historical_regression_in_one_series_is_reported(tmp_path):
    # A later revision well below an earlier best in the SAME e2e series
    # is flagged, however old it is.
    _write(tmp_path, "BENCH_r03.json", {"e2e_txns_per_sec": 504832.0})
    _write(tmp_path, "BENCH_r05.json", {"e2e_txns_per_sec": 199000.0})
    _write(tmp_path, "BENCH_r06.json", {"e2e_txns_per_sec": 500000.0})
    flags = flag_regressions(build_trajectory(str(tmp_path)), noise=0.15)
    assert [(f["file"], f["source"]) for f in flags] == [
        ("BENCH_r05.json", "e2e_txns_per_sec")]


def test_render_table_lists_every_row(repo_rows):
    table = render_table(repo_rows)
    assert "DEADLINE_r12.json" in table and "MESH_r15.json" in table
    assert "227,328" in table and "6.310" in table


# ---------------------------------------------------------------------------
# Synthetic regressions (the gate)


def test_flags_synthetic_throughput_regression(tmp_path):
    _write(tmp_path, "A_r01.json", {"e2e_txns_per_sec": 100000.0})
    _write(tmp_path, "A_r02.json", {"e2e_txns_per_sec": 95000.0})   # in noise
    _write(tmp_path, "A_r03.json", {"e2e_txns_per_sec": 50000.0})   # regressed
    rows = build_trajectory(str(tmp_path))
    flags = flag_regressions(rows, noise=0.15)
    assert len(flags) == 1
    f = flags[0]
    assert f["file"] == "A_r03.json" and f["best_file"] == "A_r01.json"
    assert f["metric"] == "flat_out_txns_per_sec"
    assert f["delta_pct"] == pytest.approx(-50.0)


def test_flags_synthetic_latency_regression_up_only(tmp_path):
    _write(tmp_path, "B_r01.json", {"paced": {"rpc_p99_ms": 20.0}})
    _write(tmp_path, "B_r02.json", {"paced": {"rpc_p99_ms": 10.0}})  # improved
    _write(tmp_path, "B_r03.json", {"paced": {"rpc_p99_ms": 30.0}})  # regressed
    flags = flag_regressions(build_trajectory(str(tmp_path)), noise=0.15)
    # Both latency columns see the same series (the bare rpc_p99_ms key
    # also feeds the e2e column's recursive search) — each flags the
    # regression against the r02 best, never the improvement itself.
    assert flags and {f["file"] for f in flags} == {"B_r03.json"}
    assert {f["metric"] for f in flags} == {"paced_p99_ms", "e2e_p99_ms"}
    assert all(f["best_so_far"] == pytest.approx(10.0) for f in flags)


def test_cross_family_and_cross_source_never_compared(tmp_path):
    # Same metric name, different families: a 10x delta, zero flags.
    _write(tmp_path, "FAST_r01.json", {"e2e_txns_per_sec": 100000.0})
    _write(tmp_path, "SLOW_r02.json", {"e2e_txns_per_sec": 10000.0})
    # Same family, different SOURCE paths for the flat-out column.
    _write(tmp_path, "MIX_r03.json", {"e2e_txns_per_sec": 90000.0})
    _write(tmp_path, "MIX_r04.json",
           {"session_ab": {"rows_per_s_session_on": 9000.0}})
    assert flag_regressions(build_trajectory(str(tmp_path)), noise=0.15) == []


def test_parse_error_rows_are_reported_not_fatal(tmp_path):
    (tmp_path / "C_r01.json").write_text("{not json")
    _write(tmp_path, "C_r02.json", {"e2e_txns_per_sec": 1.0})
    rows = build_trajectory(str(tmp_path))
    errs = [r for r in rows if "error" in r]
    assert len(errs) == 1 and errs[0]["file"] == "C_r01.json"
    assert "parse error" in render_table(rows)


def test_gate_exit_codes(tmp_path, capsys):
    _write(tmp_path, "D_r01.json", {"e2e_txns_per_sec": 100000.0})
    _write(tmp_path, "D_r02.json", {"e2e_txns_per_sec": 40000.0})
    assert main([f"--root={tmp_path}"]) == 0           # informational
    capsys.readouterr()
    assert main([f"--root={tmp_path}", "--gate"]) == 1  # fatal in CI
    capsys.readouterr()
    # --json emits machine output with the flag attached.
    assert main([f"--root={tmp_path}", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert len(out["regressions"]) == 1
    assert out["regressions"][0]["file"] == "D_r02.json"
    # A clean tree gates green.
    clean = tmp_path / "clean"
    clean.mkdir()
    _write(clean, "E_r01.json", {"e2e_txns_per_sec": 100000.0})
    assert main([f"--root={clean}", "--gate"]) == 0
    capsys.readouterr()


def test_waived_flags_do_not_trip_the_gate(tmp_path, capsys):
    """TREND_WAIVERS.json absorbs accepted historical regressions: a
    waived flag is still reported (tagged, with its reason in --json)
    but only UNWAIVED flags make --gate fatal — and a waiver for one
    metric never quiets a different series."""
    _write(tmp_path, "F_r01.json",
           {"e2e_txns_per_sec": 100000.0, "e2e_rpc_p99_ms": 10.0})
    _write(tmp_path, "F_r02.json",
           {"e2e_txns_per_sec": 40000.0, "e2e_rpc_p99_ms": 10.0})
    _write(tmp_path, "TREND_WAIVERS.json",
           [{"file": "F_r02.json", "metric": "flat_out_txns_per_sec",
             "reason": "accepted in the r02 PR"}])
    assert main([f"--root={tmp_path}", "--gate"]) == 0
    assert "[waived]" in capsys.readouterr().out
    # The waived flag is still in the machine output, reason attached.
    assert main([f"--root={tmp_path}", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert [f["waived"] for f in out["regressions"]] == [
        "accepted in the r02 PR"]
    # A NEW regression in an unwaived series still gates red.
    _write(tmp_path, "F_r03.json",
           {"e2e_txns_per_sec": 40000.0, "e2e_rpc_p99_ms": 30.0})
    assert main([f"--root={tmp_path}", "--gate"]) == 1
    capsys.readouterr()
    # The repo's own waiver file covers exactly the committed flags.
    assert main([f"--root={REPO}", "--gate"]) == 0
    capsys.readouterr()
