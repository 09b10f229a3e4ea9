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


# -- PR 56: preloaded session events, and the two cells that came with them ----

DEEP_CONFIG, DEEP_CELL = "risk-seqhead-keye-vl2-30b-a3b-deep128", "keye-deep128-insession"
MESH_CONFIG, MESH_CELL = "risk-stateful-mesh4-20m", "mesh4-index-flatout"


def test_the_deep_window_configuration_is_keyes_at_128_events_and_states_its_cut():
    cfg = validate.load_data("configs", DEEP_CONFIG)
    keye = validate.load_data("configs", "risk-seqhead-keye-vl2-30b-a3b")
    source = validate.load_source(DEEP_CONFIG)
    assert source == validate.load_source("risk-seqhead-keye-vl2-30b-a3b")
    assert cfg["source"] == keye["source"] == source["source_url"]
    # every key of the source stands as in keye's file: same widths, same cut
    for key in cfg["source_keys"]:
        assert cfg[key] == keye[key], key
    assert cfg["head"]["reference"] == "keye_vl2"
    assert cfg["reduced"] == [k for k in keye["reduced"]
                              if k != "session_events_preloaded"]
    assert all(cfg["reduced_why"][k] for k in cfg["reduced"])
    assert cfg["session_events_preloaded"] == {"events": "1-256", "rounds": 8}
    assert cfg["env"]["SESSION_EVENTS"] == "128" and cfg["env"]["BATCH_SIZE"] == "64"
    assert cfg["resident_accounts"] == 655_360 == int(cfg["env"]["FEATURE_CACHE_CAPACITY"])
    assert cfg["resident_accounts"] % cfg["fill_chunk"] == 0
    assert cfg["limits"].keys() == keye["limits"].keys()
    for key in ("SESSION_EVENTS", "BATCH_SIZE", "FEATURE_CACHE_CAPACITY"):
        assert cfg["assumed"]["non_default_env"][key], key
    assert "on_admit" in cfg["assumed"]["session_events_preloaded"]
    spec = validate.load_cell(DEEP_CELL)
    assert spec["traffic"]["name"] == "index-insession" and spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= {"device_step_ms", "device_step_roofline", "moe_experts_ms",
                     "moe_experts_roofline", "head_attention_ms",
                     "head_real_position_share", "rpc_over_50ms_share"}
    # the step the cell runs is priced at its own window: 64 rows of 128 events
    assert cfg["step_cost"] == keye["step_cost"] == "keye_backbone_step"
    cost = validate.load_code("costs", cfg["step_cost"]).keye_backbone_step
    deep, flat = cost(cfg, 64, index_mode=True), cost(keye, 256, index_mode=True)
    assert 3.9e12 < deep["flops"] < 4.0e12 and 1.9e12 < flat["flops"] < 2.0e12


def test_the_mesh_configuration_is_cell_ones_on_four_chips_and_states_its_cut():
    cfg = validate.load_data("configs", MESH_CONFIG)
    one = validate.load_data("configs", "risk-stateful-5m-pattern")
    assert cfg["chips"] == 4 and cfg["env"]["MESH_DEVICES"] == "4"
    assert cfg["resident_accounts"] == 4 * one["resident_accounts"] == int(
        cfg["env"]["FEATURE_CACHE_CAPACITY"])
    assert {k: v for k, v in cfg["env"].items()
            if k not in ("MESH_DEVICES", "FEATURE_CACHE_CAPACITY")} == {
        k: v for k, v in one["env"].items() if k != "FEATURE_CACHE_CAPACITY"}
    assert cfg["session_events_preloaded"] == 0 and cfg["reduced"] == one["reduced"]
    assert cfg["limits"] == one["limits"] and cfg["guarantees"] == one["guarantees"]
    assert "four-chip" in cfg["reduced_why"]["chips"]
    assert cfg["assumed"]["non_default_env"]["MESH_DEVICES"]
    spec = validate.load_cell(MESH_CELL)
    assert spec["traffic"]["name"] == "index-flatout" and spec["cell"]["chips"] == 4
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= {"device_step_ms", "device_step_roofline",
                     "state_lookup_us_per_row", "cache_hit_share"}
    # the step every cell reads is priced here as the slot-sharded one
    assert cfg["step_cost"] == "sharded_step" and one["step_cost"] == "fused_step"
    manifest = validate.load_manifest()
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == [MESH_CELL] and len(manifest["workloads"]) // 4 >= 1
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].startswith("sharded_step")]


def test_a_chip_of_the_mesh_scores_the_whole_batch_and_receives_the_other_shards_rows():
    cfg = validate.load_data("configs", MESH_CONFIG)
    one = validate.load_data("configs", "risk-stateful-5m-pattern")
    sharded = validate.load_code("costs", "sharded_step").sharded_step
    fused = validate.load_code("costs", "fused_step").fused_step
    for batch in (64, 256):
        mine, base = sharded(cfg, batch, index_mode=True), fused(one, batch, index_mode=True)
        assert mine["flops"] == base["flops"]
        row = 30 * 4 + 1 + 16 * 12 * 4 + 8
        assert mine["bytes"] == base["bytes"] + batch * row * 3 // 4
        assert sharded(one, batch, index_mode=True) == base  # one device: nothing arrives
        assert sharded(cfg, batch, index_mode=False) == fused(one, batch, index_mode=False)


@pytest.mark.parametrize("config,change,needle", [
    (DEEP_CONFIG, {"session_events_preloaded": {"events": "1-256"}}, "rounds"),
    (DEEP_CONFIG, {"session_events_preloaded": 16}, "session_events_preloaded is 0 or"),
    (DEEP_CONFIG, {"session_events_preloaded": {"events": "256-1", "rounds": 8}}, "low <= high"),
    (DEEP_CONFIG, {"reduced+": "session_events_preloaded"}, "is no cut"),
    (DEEP_CONFIG, {"session_events_preloaded": 0}, "a cut that reduced has to name"),
    ("risk-stateful-5m-pattern",
     {"session_events_preloaded": {"events": "1-32", "rounds": 4}}, "is no cut"),
], ids=["no-rounds", "a-count", "backwards", "full-windows-called-a-cut",
        "empty-windows-not-called-one", "warm-windows-still-called-a-cut"])
def test_session_events_preloaded_is_held_to_its_shape_and_to_reduced(
        copy, config, change, needle):
    path = copy / "chipbench" / "configs" / f"{config}.json"
    cfg = json.loads(path.read_text())
    for key, value in change.items():
        if key == "reduced+":
            cfg["reduced"].append(value)
            cfg["reduced_why"][value] = "windows start short"
            _edit(copy, lambda m: next(c for c in m["configs"] if c["name"] == config)[
                "reduced"].append(value))
        else:
            cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert errors and any(needle in e for e in errors), errors


def test_a_short_preload_may_stand_in_reduced(copy):
    """Windows preloaded short of SESSION_EVENTS are still a cut."""
    config = "risk-stateful-5m-pattern"
    path = copy / "chipbench" / "configs" / f"{config}.json"
    cfg = json.loads(path.read_text())
    cfg["session_events_preloaded"] = {"events": "1-8", "rounds": 2}
    path.write_text(json.dumps(cfg))
    assert validate.check_manifest(str(copy)) == []


# -- PR 70: the step's time and roofline share as one metric each ---------------

STEP = ("device_step_ms", "device_step_roofline")
RETIRED = {"dispatches_per_chunk", "lfm2_route_ms", "fused_step_roofline",
           "backbone_step_ms", "backbone_step_roofline", "mla_step_ms",
           "mla_step_roofline", "sharded_step_ms", "sharded_step_roofline",
           "lfm2_step_ms", "lfm2_step_roofline", "falconh1_step_ms",
           "falconh1_step_roofline", "longcat_step_ms", "longcat_step_roofline",
           "lfm2_real_position_share", "falconh1_real_position_share"}
CELLS = {w["name"]: w for w in validate.load_manifest()["workloads"]}
CONFIGS = sorted({w["config"] for w in CELLS.values()})


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reads_the_step_under_the_two_shared_names(cell):
    spec = validate.load_cell(cell)
    names = [m["name"] for m in spec["per_layer"]]
    assert [names.count(n) for n in STEP] == [1, 1]
    assert not set(names) & RETIRED
    # how many positions were real is said under one name or the family's,
    # never both, and not at all where the head counts no positions
    shares = [n for n in names if n.endswith("real_position_share")]
    assert len(shares) <= (0 if validate.head_name(spec["config"])
                           in ("pattern", "transformer") else 1), shares


def test_the_manifest_holds_114_entries_and_the_two_have_no_list():
    manifest = validate.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(names) == 114 and not set(names) & RETIRED
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all("workloads" not in by_name[n] for n in STEP)
    assert by_name["head_real_position_share"]["workloads"] == [
        "keye-backbone-insession", "keye-deep128-insession",
        "lfm2-conv-insession", "falconh1-ssm-insession"]
    # a retired entry took its file with it
    assert {f[:-5] for f in os.listdir(os.path.join(
        ROOT, "chipbench", "layer_metrics"))} == set(names)
    roofline = validate.load_data("layer_metrics", "device_step_roofline")
    assert roofline["cost"] == validate.STEP_COST == {"config": "step_cost"}
    # a metric that names its own file keeps it, whatever the configuration
    assert validate.cost_name({"cost": "fused_step"},
                              {"step_cost": "sharded_step"}) == "fused_step"
    assert "cost" not in validate.load_data("layer_metrics", "device_step_ms")


@pytest.mark.parametrize("config", CONFIGS)
def test_a_configurations_step_cost_prices_its_smallest_rung_above_zero(config):
    cfg = validate.load_data("configs", config)
    name = cfg["step_cost"]
    cost = getattr(validate.load_code("costs", name), name)
    roofline = validate.load_data("layer_metrics", "device_step_roofline")
    assert validate.cost_name(roofline, cfg) == name
    for cell in (w for w in CELLS.values() if w["config"] == config):
        rows = validate.load_data("traffic", cell["traffic"])["rows"]
        rung = min(min(rows), int(cfg["env"]["BATCH_SIZE"]))
        priced = cost(cfg, rung, index_mode=True)
        assert priced["flops"] > 0 and priced["bytes"] > 0, (cell["name"], rung)
        more = cost(cfg, 2 * rung, index_mode=True)
        assert more["flops"] > priced["flops"] and more["bytes"] > priced["bytes"]


@pytest.mark.parametrize("change,needle", [
    (lambda c: c.pop("step_cost"), "missing keys ['step_cost']"),
    (lambda c: c.update(step_cost="absent_step"),
     "step_cost: no file chipbench/costs/absent_step.py"),
    (lambda c: c.update(step_cost="empty_step"),
     "step_cost: chipbench/costs/empty_step.py does not define ['empty_step']"),
    (lambda c: c.update(step_cost={"config": "step_cost"}), "step_cost: costs name"),
], ids=["no-key", "no-file", "no-function", "not-a-name"])
def test_a_configuration_without_a_cost_for_its_step_is_refused(copy, change,
                                                                needle):
    (copy / "chipbench" / "costs" / "empty_step.py").write_text("x = 1\n")
    assert validate.check_manifest(str(copy)) == []
    path = copy / "chipbench" / "configs" / "risk-seqhead-lfm2-24b-a2b.json"
    cfg = json.loads(path.read_text())
    change(cfg)
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert [e for e in errors if needle in e
            and "risk-seqhead-lfm2-24b-a2b.json" in e], errors


def test_a_metric_leaves_its_cost_to_the_configuration_in_one_way(copy):
    path = copy / "chipbench" / "layer_metrics" / "device_step_roofline.json"
    metric = json.loads(path.read_text())
    path.write_text(json.dumps(dict(metric, cost={"config": "head"})))
    errors = validate.check_manifest(str(copy))
    assert any("device_step_roofline.json: cost:" in e for e in errors), errors
