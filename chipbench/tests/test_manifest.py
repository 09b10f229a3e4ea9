"""The manifest and its data files pass the rules; breaking a rule is seen;
a new cell, configuration, traffic mix or counter metric is data only."""

import json
import os

import pytest

from chipbench import validate

ROOT = validate.ROOT


def test_manifest_as_committed_passes():
    assert validate.check_manifest() == []


def _edit(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    fn(m)
    with open(path, "w") as f:
        json.dump(m, f)


def _set(section, index, key, value):
    def fn(m):
        m[section][index][key] = value
    return fn


@pytest.mark.parametrize("edit, needle", [
    (_set("per_layer", 0, "layer", "wire decode"), "layer"),
    (_set("per_layer", 1, "unit", "us per row"), "unit"),
    (_set("per_layer", 1, "unit", "microseconds/row!"), "unit"),
    (_set("workloads", 0, "name", "stateful index"), "name"),
    (_set("end_to_end", 0, "name", "txns/s"), "name"),
    (_set("configs", 0, "name", "risk,stateful"), "name"),
    (lambda m: m["end_to_end"][0].__setitem__(
        "workloads", ["seqhead-index-flatout"]), "does not report"),
    (_set("per_layer", 1, "moves", "no_such_metric"), "not an end-to-end"),
    (_set("end_to_end", 0, "bound", 0.5), "bound"),
    (_set("configs", 0, "source", "x" * 201), "source"),
    (_set("workloads", 0, "why", "y" * 201), "why"),
    (_set("per_layer", 2, "why", "not allowed here"), "unknown keys"),
    (_set("workloads", 0, "chips", 2), "chips"),
    (lambda m: m["workloads"].__delitem__(1), "no cell uses it"),
    (lambda m: m["end_to_end"].__delitem__(2), "setup_s"),
    (lambda m: m["configs"][0]["reduced"].append("fill_chunk"), "reduced"),
], ids=["layer-space", "unit-space", "unit-long", "cell-space", "metric-slash",
        "config-comma", "moves-not-reported", "moves-unknown", "bound-wide",
        "source-long", "why-long", "metric-why", "chips-2", "config-unused",
        "no-setup", "reduced-unexplained"])
def test_a_broken_rule_is_reported(copy, edit, needle):
    _edit(copy, edit)
    errors = validate.check_manifest(str(copy))
    assert errors and any(needle in e for e in errors), errors


def test_a_new_cell_config_mix_and_counter_metric_are_data_only(copy):
    """Adding a deployment, a traffic mix, a cell and a per-layer metric
    over a counter and over a hostprof stage is four new JSON files and
    entries in BENCHMARK.json — no code."""
    base = copy / "chipbench"
    cfg = json.loads((base / "configs" / "risk-stateful-5m-pattern.json").read_text())
    cfg["name"] = "risk-stateful-5m-other"
    cfg["source"] = cfg["source"].replace("risk.v1", "risk.v1 (another)")
    (base / "configs" / "risk-stateful-5m-other.json").write_text(json.dumps(cfg))
    mix = json.loads((base / "traffic" / "index-flatout.json").read_text())
    mix.update(name="index-burst", rows=[2048], clients=1)
    (base / "traffic" / "index-burst.json").write_text(json.dumps(mix))
    for name, reader in (
            ("evictions_per_rpc", {"reader": "counter_delta_per",
                                   "counter": "cache.evictions",
                                   "per": "client.rpcs_ok"}),
            ("ledger_us_per_row", {"reader": "hostprof_us_per_row",
                                   "stages": ["ledger_note"]})):
        (base / "layer_metrics" / f"{name}.json").write_text(json.dumps({
            "name": name, "layer": "state-lookup", "unit": "1/rpc",
            "better": "lower", "source": "program_counter",
            "moves": "txns_per_s", **reader}))

    def add(m):
        m["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": "chipbench/configs/risk-stateful-5m-other.json",
                             "reduced": cfg["reduced"], "why": "another"})
        m["workloads"].append({"name": "other-index-burst", "config": cfg["name"],
                               "traffic": "index-burst", "chips": 1, "why": "w"})
        for name in ("evictions_per_rpc", "ledger_us_per_row"):
            m["per_layer"].append({
                "name": name, "unit": "1/rpc", "better": "lower",
                "source": "program_counter", "layer": "state-lookup",
                "moves": "txns_per_s", "workloads": ["other-index-burst"]})
    _edit(copy, add)
    assert validate.check_manifest(str(copy)) == []
    spec = validate.load_cell("other-index-burst", str(copy))
    assert spec["traffic"]["rows"] == [2048]
    assert {m["name"] for m in spec["per_layer"]} >= {"evictions_per_rpc",
                                                      "ledger_us_per_row"}
    # and the generic readers read them with no new code
    from chipbench.readers import READERS, Readings
    r = Readings(config=spec["config"], rows_ok=1000,
                 stages={"ledger_note": 2500.0},
                 counters={"cache.evictions": 4.0, "client.rpcs_ok": 8.0})
    got = {m["name"]: READERS[m["reader"]](m, r) for m in spec["per_layer"]}
    assert got["evictions_per_rpc"] == 0.5
    assert got["ledger_us_per_row"] == 2.5


def test_a_reader_with_nothing_to_read_returns_nothing():
    from chipbench.readers import READERS, Readings
    r = Readings(config={}, rows_ok=10, stages={}, counters={})
    for name in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics")):
        m = validate.load_data("layer_metrics", name[:-5])
        assert READERS[m["reader"]](m, r) is None, name
