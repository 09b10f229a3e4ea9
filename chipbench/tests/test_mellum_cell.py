"""The files ``mellum2-swa-deep4096`` brings: its configuration is held to
its source, its cost functions give the figures PERF.md states, its metric
files load and read a recorded trace, and its traffic mix is the deep-review
one."""

import json

import pytest

from chipbench import readers, trace_reduce, validate

CONFIG = "risk-seqhead-mellum2-12b-a2.5b"
CELL = "mellum2-swa-deep4096"
BATCH = 2  # the cell's one rung
METRICS = {"mellum_step_ms", "mellum_step_roofline", "mellum_window_attention_ms",
           "mellum_full_attention_ms", "mellum_attention_core_ms",
           "mellum_attention_core_roofline", "mellum_experts_ms",
           "mellum_experts_roofline", "mellum_route_ms",
           "mellum_real_position_share", "mellum_key_block_share"}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "chips",
           "store_accounts", "store_loaded_accounts"]
ASSUMED = ("q_norm_k_norm", "mask_convention", "yarn", "projector", "vocabulary",
           "intermediate_size", "scoring_head", "dtype")


def cost(name: str, batch: int = BATCH) -> dict:
    cfg = validate.load_data("configs", CONFIG)
    return getattr(validate.load_code("costs", name), name)(
        cfg, batch, index_mode=True)


def test_the_mellum_configuration_is_held_to_its_source_and_states_its_cut():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert source["name"] == "Mellum2-12B-A2.5B-Instruct"
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    assert cfg["reduced"] == REDUCED
    differs = sorted(k for k, v in source["config"].items() if cfg[k] != v)
    assert differs == ["layer_types", "mlp_layer_types", "num_hidden_layers"]
    assert (cfg["num_hidden_layers"], source["config"]["num_hidden_layers"]) == (4, 28)
    # one whole period, read from the source's own list
    assert cfg["layer_types"] == source["config"]["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert source["config"]["layer_types"] == cfg["layer_types"] * 7
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    # every width, every head and all 64 experts as published
    for key, value in (("hidden_size", 2304), ("num_experts", 64),
                       ("num_experts_per_tok", 8), ("moe_intermediate_size", 896),
                       ("intermediate_size", 7168), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("sliding_window", 1024), ("max_window_layers", 0),
                       ("use_sliding_window", True), ("norm_topk_prob", True)):
        assert cfg[key] == source["config"][key] == value, key
    # the nested group whole: a rotary table a kind of layer
    assert cfg["rope_parameters"] == source["config"]["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16, "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    head = cfg["head"]
    assert head["reference"] == "mellum2_12b_a2_5b"
    assert head["published"]["num_hidden_layers"] == 28
    assert "layers 0-3 whole" in head["deployment"]
    assert "pipeline stages" in head["deployment"]
    for name in ASSUMED:
        assert head["assumed"][name], name
    assert "417.7 M" in head["parameters"] and "3.34 GB" in head["parameters"]
    env = cfg["env"]
    assert (env["SESSION_HEAD"], env["SESSION_EVENTS"], env["BATCH_SIZE"]) == (
        "mellum", "4096", "2")
    assert cfg["resident_accounts"] == 20480 == int(env["FEATURE_CACHE_CAPACITY"])
    assert cfg["assumed"]["bytes_per_resident_account"] == 4096 * 48 + 8 + 121
    # windows deeper than the band from the first RPC, half of them wrapped
    assert cfg["session_events_preloaded"] == {"events": "2048-6144", "rounds": 64}
    assert "session_events_preloaded" not in cfg["reduced"]
    assert cfg["precision"]["control_operand_dtype"] == "float8_e4m3fn"
    assert all(cfg["reduced_why"][k] for k in REDUCED)
    for exact in ("rule_score_mismatch", "action_mismatch_same_score",
                  "session_bit_mismatch"):
        assert cfg["limits"][exact] == 0
    spec = validate.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= METRICS
    assert not {n for n in names if n.startswith(
        ("lfm2_", "mla_", "moe_", "falconh1_", "ssm_", "ling_", "kda_", "xing_",
         "hc_", "backbone_", "head_"))}
    manifest = validate.load_manifest()
    mine = [m for m in manifest["per_layer"] if m["name"] in METRICS]
    assert all(m["workloads"] == [CELL] and m["moves"] == "txns_per_s"
               for m in mine) and len(mine) == 11
    # appended: the configuration, the cell and the metrics come last
    assert manifest["configs"][-1]["name"] == CONFIG
    assert manifest["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in manifest["per_layer"][-11:]} == METRICS
    assert len(manifest["configs"]) == 11 and len(manifest["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_deep_review_traffic_is_few_rows_a_frame():
    spec = validate.load_cell(CELL)
    mix, base = spec["traffic"], validate.load_data("traffic", "index-insession")
    assert mix["name"] == "index-deepreview" and mix["rows"] == [2, 8]
    assert (mix["loop"], mix["clients"], mix["rpc"], mix["pool_frames"]) == (
        "closed", 2, "index", 2048)
    for key in ("accounts", "tx_types", "amounts"):
        assert mix[key] == base[key], key
    # one ring account: ``traffic.check_sequence`` puts every ring account in
    # every check RPC, and a frame of 2 rows has no room for 8
    assert mix["check"] == {"rpcs": 12, "accounts": 96, "ring_accounts": 1}
    from chipbench import traffic

    pop = traffic.Population(mix, 20480, 57)
    seq = traffic.check_sequence(mix, pop, 57, loaded=8192, stored=1_000_000)
    assert [len(r["ids"]) for r in seq] == [2, 8] * 6
    sizes = traffic.frame_sizes(mix, 57)
    assert set(sizes.tolist()) == {2, 8} and abs((sizes == 2).mean() - 0.5) < 0.01


@pytest.mark.parametrize("key,value,needle", [
    ("moe_intermediate_size", 448, "a width may not differ"),
    ("sliding_window", 256, "a width may not differ"),
    ("num_experts_per_tok", 4, "a width may not differ"),
    ("head_dim", 64, "a width may not differ"),
    ("rope_parameters", {"full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 4,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
     "a width may not differ"),
    ("num_experts", 32, "reduced does not name it"),
    ("layer_types", ["sliding_attention"] * 3, "3 entries for num_hidden_layers 4"),
    ("num_hidden_layers", 3, "layers follow the leading dense ones"),
], ids=["expert-width", "band", "experts-a-token", "head-width", "yarn-factor",
        "experts-held-unnamed", "an-entry-a-layer", "three-layers-left"])
def test_a_mellum_copy_with_a_width_or_a_floor_changed_is_refused(
        copy, key, value, needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms,bound_by", [
    ("mellum_backbone_step", 5.284, 3.343, 26.82, "operations"),
    ("mellum_attention_core", 0.636, 0.872, 3.23, "operations"),
    ("mellum_moe_experts", 3.247, 3.624, 16.48, "operations")])
def test_the_mellum_cost_functions_give_the_cells_figures(name, tflop, gb,
                                                          least_ms, bound_by):
    from chipbench import peaks

    c = cost(name)
    assert c["flops"] / 1e12 == pytest.approx(tflop, abs=0.001)
    assert c["bytes"] / 1e9 == pytest.approx(gb, abs=0.001)
    peak = peaks.peaks_for("TPU v5 lite")
    by_ops = c["flops"] / peak["flops_per_s"]
    by_bytes = c["bytes"] / peak["bytes_per_s"]
    assert max(by_ops, by_bytes) * 1e3 == pytest.approx(least_ms, abs=0.01)
    assert (by_ops > by_bytes) == (bound_by == "operations")
    twice = cost(name, 2 * BATCH)
    assert twice["flops"] == pytest.approx(2 * c["flops"], rel=1e-3)


def test_the_mellum_step_holds_its_parts_by_hand():
    core = validate.load_code("costs", "mellum_attention_core")
    # a full layer keeps every causal pair, a sliding one a band of 1,024
    assert core.keys_kept(4096, None) == 4096 * 4097 // 2 == 8_390_656
    assert core.keys_kept(4096, 1024) == 524_800 + 3072 * 1024 == 3_670_528
    assert core.keys_kept(1024, 1024) == core.keys_kept(1024, None)
    cfg = validate.load_data("configs", CONFIG)
    assert core.core_pairs(cfg, 4096) == 3 * 3_670_528 + 8_390_656
    # 16,384 operations a kept pair: 32 heads x 128 x (scores, values) x 2
    att, step, base = (cost("mellum_attention_core"), cost("mellum_backbone_step"),
                       cost("fused_step"))
    assert att["flops"] == 2 * 16384 * core.core_pairs(cfg, 4096)
    assert 2 * 16384 * 8_390_656 / 1e9 == pytest.approx(275, abs=0.1)   # full
    assert 2 * 16384 * 3_670_528 / 1e9 == pytest.approx(120.3, abs=0.1)  # sliding
    positions = 2 * 4096
    assert att["bytes"] == 4 * positions * 128 * (32 * 6 + 2 * 4 * 2)
    attention = 2304 * 128 * (2 * 32 + 2 * 4)
    expert, router = 3 * 2304 * 896, 2304 * 64
    assert (attention, expert) == (21_233_664, 6_193_152)
    macs = positions * (12 * 2304 + 4 * (attention + router + 8 * expert))
    assert step["flops"] == base["flops"] + 2 * macs + att["flops"]
    held = 12 * 2304 + 4 * (attention + router + 64 * expert)
    assert step["bytes"] == base["bytes"] + 2 * held
    assert held == pytest.approx(1.671e9, rel=1e-3)
    # the shares the cell's ``why`` states, by the widths
    experts = cost("mellum_moe_experts")["flops"]
    assert experts / step["flops"] == pytest.approx(0.614, abs=0.002)
    assert 2 * positions * 4 * attention / step["flops"] == pytest.approx(0.263, abs=0.002)
    assert att["flops"] / step["flops"] == pytest.approx(0.120, abs=0.002)
    # as full causal layers and as masked squares the cores would be more
    causal = 2 * 16384 * 4 * 8_390_656
    square = 2 * 16384 * 4 * 4096 * 4096
    rest = step["flops"] - att["flops"]
    assert causal / (rest + causal) == pytest.approx(0.191, abs=0.002)
    assert square / (rest + square) == pytest.approx(0.321, abs=0.002)


def test_the_mellum_metric_files_load_and_name_their_readers():
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    assert set(mine) == METRICS
    for m in mine.values():
        assert m["reader"] in readers.READERS
        if "cost" in m:
            assert callable(getattr(validate.load_code("costs", m["cost"]), m["cost"]))
    assert mine["mellum_window_attention_ms"]["pattern"] == "head/attn/window"
    assert mine["mellum_full_attention_ms"]["pattern"] == "head/attn/full"
    assert mine["mellum_attention_core_ms"]["pattern"] == "head/attn/(window|full)/core"
    assert mine["mellum_attention_core_roofline"]["cost"] == "mellum_attention_core"
    assert mine["mellum_experts_roofline"]["cost"] == "mellum_moe_experts"
    assert mine["mellum_step_roofline"]["cost"] == "mellum_backbone_step"
    share = mine["mellum_key_block_share"]
    assert (share["reader"], share["better"]) == ("counter_ratio", "lower")
    assert share["numerator"] == "risk_session_head_key_blocks_visited_total"
    assert share["denominator"] == "risk_session_head_key_blocks_square_total"


def _traced(ops: list, runs: int = 2) -> trace_reduce.Trace:
    """A trace of ``runs`` executions of ``jit__body`` on device 0, the
    operations ``(name, scope path, ns)`` one after the other inside each."""
    trace = trace_reduce.Trace(device_ops={0: []}, programs={0: []},
                               op_scopes={0: []})
    step = sum(ns for _, _, ns in ops) + 1_000
    for r in range(runs):
        start = 10_000 + r * (step + 5_000)
        trace.programs[0].append((f"jit__body({r})", start, step))
        at = start + 100
        for name, scope, ns in ops:
            trace.device_ops[0].append((name, at, ns))
            trace.op_scopes[0].append(scope)
            at += ns
    return trace


def test_the_mellum_metrics_read_a_recorded_trace_and_the_counters():
    """The scope metrics over a small trace of the new scopes (three sliding
    layers and a full one, their cores inside, the router and the experts),
    the two counter shares through ``counter_ratio``; on a program that has
    neither the counters nor a trace (the parent's) each reader returns
    nothing and raises nothing."""
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    ms = 1_000_000
    layer = lambda kind, core: [
        ("fusion.q", f"jit(_body)/head/attn/{kind}/dot_general", 2 * ms),
        ("_block_attention", f"jit(_body)/head/attn/{kind}/core/pallas_call", core),
        ("fusion.r", "jit(_body)/head/moe/route/reduce_max", ms // 4),
        ("_gate_up", "jit(_body)/head/moe/experts/pallas_call", 5 * ms),
        ("_down", "jit(_body)/head/moe/experts/pallas_call", 3 * ms)]
    ops = (layer("window", 2 * ms) * 3 + layer("full", 4 * ms)
           + [("fusion.ring", "jit(_body)/convert_element_type", 6 * ms)])
    trace = _traced(ops)
    window = (0, 10**12)
    counters = {"risk_session_head_key_blocks_visited_total": 99.0 * 10,
                "risk_session_head_key_blocks_square_total": 256.0 * 10,
                "risk_session_head_real_positions_total": 3500.0,
                "risk_session_head_positions_total": 4096.0}
    r = readers.Readings(config=spec["config"], rows_ok=10, stages={},
                         counters=counters, pad_rows={2: 5},
                         device_kind="TPU v5 lite", trace=trace,
                         trace_window=window)
    got = readers.read_all(list(mine.values()), r, lambda line: None)
    assert set(got) == METRICS
    value = lambda name: got[name]["value"]
    assert value("mellum_key_block_share") == pytest.approx(38.67, abs=0.01)
    assert value("mellum_real_position_share") == pytest.approx(85.45, abs=0.01)
    assert value("mellum_window_attention_ms") == pytest.approx(3 * 4.0)
    assert value("mellum_full_attention_ms") == pytest.approx(6.0)
    assert value("mellum_attention_core_ms") == pytest.approx(3 * 2.0 + 4.0)
    assert value("mellum_experts_ms") == pytest.approx(4 * 8.0)
    assert value("mellum_route_ms") == pytest.approx(1.0)
    assert value("mellum_step_ms") == pytest.approx(57.0 + 0.001)
    # a share of a roofline is the cost file's least time over the time read
    assert value("mellum_attention_core_roofline") == pytest.approx(100 * 3.23 / 10, abs=0.1)
    assert value("mellum_experts_roofline") == pytest.approx(100 * 16.48 / 32, abs=0.1)
    assert value("mellum_step_roofline") == pytest.approx(100 * 26.82 / 57.001, abs=0.1)
    # the parent's program has no such counters and its scopes no such names
    bare = readers.Readings(config=spec["config"], rows_ok=10, stages={},
                            counters={"risk_session_head_positions_total": 4096.0,
                                      "risk_session_head_real_positions_total": 3500.0},
                            pad_rows={2: 5}, device_kind="TPU v5 lite",
                            trace=_traced([("fusion.1", "jit(_body)/head/attn/core", ms)]),
                            trace_window=window)
    assert readers.counter_ratio(mine["mellum_key_block_share"], bare) is None
    got = readers.read_all(list(mine.values()), bare, lambda line: None)
    assert set(got) == {"mellum_real_position_share", "mellum_step_ms",
                        "mellum_step_roofline"}
