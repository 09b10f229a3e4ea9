"""The files ``kexaone-mtp-deep2048`` brings: its configuration is held to its
source with exactly the cut it states, ``sliding_windows`` is the source's, its
cost functions give the figures PERF.md states, its metric files load and read
a recorded trace, and its traffic mix is the deep-review one. Its entries are
held to no place of their lists (PERF.md Open question 9)."""

import json

import pytest

from chipbench import readers, validate
from chipbench.tests.test_phi4flash_cell import _traced

CONFIG = "risk-seqhead-k-exaone-236b-a23b"
CELL = "kexaone-mtp-deep2048"
BATCH = 2  # the cell's one rung
METRICS = ["kexaone_step_ms", "kexaone_step_roofline",
           "kexaone_window_attention_ms", "kexaone_full_attention_ms",
           "kexaone_attention_core_ms", "kexaone_attention_core_roofline",
           "kexaone_dense_mlp_ms", "kexaone_experts_ms",
           "kexaone_expert_share_roofline", "kexaone_route_ms", "kexaone_mtp_ms",
           "kexaone_mtp_roofline", "kexaone_real_position_share",
           "kexaone_key_block_share", "kexaone_layer_position_share"]
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
           "chips", "store_accounts", "store_loaded_accounts"]
ASSUMED = ("norms", "positions", "mask_convention", "router", "mtp", "mtp_read",
           "projector", "vocabulary", "scoring_head", "dtype", "seeded_tree_scale")


def cost(name: str, batch: int = BATCH) -> dict:
    cfg = validate.load_data("configs", CONFIG)
    return getattr(validate.load_code("costs", name), name)(
        cfg, batch, index_mode=True)


def test_the_kexaone_configuration_is_held_to_its_source_and_states_its_cut():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert source["name"] == "K-EXAONE-236B-A23B"
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    # exactly these keys of the source differ, each named by ``reduced``
    differ = [k for k, v in source["config"].items() if cfg[k] != v]
    assert differ == ["layer_types", "mlp_layer_types", "num_experts",
                      "num_hidden_layers"]
    assert cfg["reduced"] == REDUCED
    assert set(differ) == set(REDUCED) & set(source["config"])
    assert (cfg["num_hidden_layers"], source["layers"]) == (5, 48)
    assert cfg["layer_types"] == source["config"]["layer_types"][:5] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert cfg["mlp_layer_types"] == source["config"]["mlp_layer_types"][:5] == [
        "dense"] + ["sparse"] * 4
    assert (cfg["num_experts"], source["config"]["num_experts"]) == (8, 128)
    # the list that is no count stays the source's, all 48 entries
    assert cfg["sliding_windows"] == source["config"]["sliding_windows"]
    assert len(cfg["sliding_windows"]) == 48
    for key, value in (("hidden_size", 6144), ("intermediate_size", 18432),
                       ("moe_intermediate_size", 2048), ("head_dim", 128),
                       ("num_attention_heads", 64), ("num_key_value_heads", 8),
                       ("num_experts_per_tok", 8), ("num_shared_experts", 1),
                       ("sliding_window", 128), ("first_k_dense_replace", 1),
                       ("num_nextn_predict_layers", 1), ("routed_scaling_factor", 2.5),
                       ("mtp_layer_types", ["full_attention"]),
                       ("mtp_sliding_windows", [0]), ("vocab_size", 153600)):
        assert cfg[key] == source["config"][key] == value, key
    head = cfg["head"]
    assert head["reference"] == "k_exaone_236b_a23b" and head["first_expert"] == 0
    assert head["published"]["num_hidden_layers"] == 48
    assert head["published"]["num_experts"] == 128
    assert "16 chips share each layer" in head["deployment"]
    assert "experts 0-7 of 128" in head["deployment"]
    for name in ASSUMED:
        assert head["assumed"][name], name
    assert "2,797.5 M" in head["parameters"] and "5.595 GB" in head["parameters"]
    env = cfg["env"]
    assert (env["SESSION_HEAD"], env["SESSION_EVENTS"], env["BATCH_SIZE"]) == (
        "kexaone", "2048", "2")
    assert cfg["resident_accounts"] == int(env["FEATURE_CACHE_CAPACITY"])
    assert cfg["resident_accounts"] in (24576, 16384)
    assert cfg["assumed"]["bytes_per_resident_account"] == 2048 * 48 + 8 + 121
    assert cfg["assumed"]["player_base"] and cfg["assumed"]["limits"]
    # windows deeper than the band from the first RPC, half of them wrapped
    assert cfg["session_events_preloaded"] == {"events": "1024-3072", "rounds": 64}
    assert "session_events_preloaded" not in cfg["reduced"]
    assert cfg["precision"]["control_operand_dtype"] == "float8_e4m3fn"
    assert all(cfg["reduced_why"][k] for k in REDUCED)
    for exact in ("rule_score_mismatch", "action_mismatch_same_score",
                  "session_bit_mismatch"):
        assert cfg["limits"][exact] == 0
    phi = validate.load_data("configs", "risk-seqhead-phi-4-mini-flash")
    for key in ("BULK_MAX_INFLIGHT", "FEATURE_STORE", "ANOMALY_PROFILE",
                "FEATURE_CACHE", "SESSION_STATE", "BATCH_SIZE", "SESSION_EVENTS"):
        assert env[key] == phi["env"][key], key
    spec = validate.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "index-deepreview"
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= set(METRICS)
    assert not {n for n in names if n.startswith(
        ("lfm2_", "mla_", "moe_", "falconh1_", "ssm_", "ling_", "kda_", "xing_",
         "hc_", "backbone_", "head_", "mellum_", "phi4flash_", "selective_scan_"))}
    manifest = validate.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL]
               and by_name[n]["moves"] == "txns_per_s" for n in METRICS)
    # in the manifest, together and in this order, wherever they stand
    listed = [m["name"] for m in manifest["per_layer"]]
    first = listed.index(METRICS[0])
    assert listed[first:first + len(METRICS)] == METRICS
    assert [c["name"] for c in manifest["configs"]].count(CONFIG) == 1
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the traffic file is the mellum cell's, unedited
    assert spec["traffic"] == validate.load_cell("mellum2-swa-deep4096")["traffic"]


@pytest.mark.parametrize("key,value,needle", [
    ("hidden_size", 3072, "a width may not differ"),
    ("moe_intermediate_size", 1024, "a width may not differ"),
    ("sliding_window", 64, "a width may not differ"),
    ("sliding_windows", [128, 128, 128, 0, 128], "a width may not differ"),
    ("num_experts_per_tok", 4, "a width may not differ"),
    ("num_nextn_predict_layers", 0, "a width may not differ"),
    ("num_experts", 4, "at least 8 routed experts"),
    ("num_key_value_heads", 4, "reduced does not name it"),
], ids=["hidden", "expert-width", "band", "band-list-cut", "experts-a-token",
        "no-module", "under-the-floor", "key-heads-unnamed"])
def test_a_kexaone_copy_with_a_width_or_an_unnamed_count_changed_is_refused(
        copy, key, value, needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms,bound_by", [
    ("kexaone_backbone_step", 10.230, 5.302, 51.93, "operations"),
    ("kexaone_attention_core", 0.204, 1.107, 1.35, "bytes"),
    ("kexaone_expert_share", 0.618, 2.919, 3.56, "bytes"),
    ("kexaone_mtp_module", 0.722, 0.765, 3.67, "operations")])
def test_the_kexaone_cost_functions_give_the_cells_figures(name, tflop, gb,
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


def test_the_kexaone_step_holds_its_parts_by_hand():
    """The work the output needs: five layers at 4,096 positions; of the
    module the join and ``K, V`` at 4,096 and the rest at 2; every weight of
    the stack once, and of the module's eight held experts the one a row's
    half a pair can touch."""
    core = validate.load_code("costs", "kexaone_attention_core")
    assert core.keys_kept(2048, 128) == 8_256 + 1920 * 128 == 254_016
    assert core.keys_kept(128, 128) == core.keys_kept(128, None)
    assert core.keys_kept(2048, None) == 2_098_176
    cfg = validate.load_data("configs", CONFIG)
    assert core.core_pairs(cfg, 2048) == 4 * 254_016 + 2_098_176 + 2047
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024
    mlp, expert, router, w_eh = 3 * 6144 * 18432, 3 * 6144 * 2048, 6144 * 128, 12288 * 6144
    assert (attn, mlp, expert, router, w_eh) == (
        113_246_208, 339_738_624, 37_748_736, 786_432, 75_497_472)
    positions, rows = 2 * 2048, 2
    att, share, module, step, base = (cost("kexaone_attention_core"),
                                      cost("kexaone_expert_share"),
                                      cost("kexaone_mtp_module"),
                                      cost("kexaone_backbone_step"),
                                      cost("fused_step"))
    assert att["flops"] == 2 * 2 * rows * 8192 * (4 * 254_016 + 2_098_176 + 2047)
    pairs = positions * 8 * 8 / 128          # a layer, at uniform routing
    assert pairs == 2048 and pairs / 8 == 256  # a held expert's, a step
    assert share["flops"] == 2 * 4 * pairs * expert
    assert share["bytes"] == 4 * (2 * 8 * expert + pairs * 6144 * 2
                                  + positions * 6144 * 4)
    everywhere = w_eh + 6144 * 2048
    once = 2 * 6144 * 8192 + router + expert   # its core is in the cores' file
    assert module["flops"] == 2 * (positions * everywhere + rows * once
                                   + rows * 0.5 * expert)
    assert module["flops"] / 1e12 == pytest.approx(0.7222, abs=1e-4)
    # the join is 86% of the module's operations
    assert 2 * positions * w_eh / module["flops"] == pytest.approx(0.856, abs=0.001)
    stack = 12 * 6144 + 5 * attn + mlp + 4 * (router + expert)
    assert step["flops"] == (base["flops"] + 2 * positions * stack + share["flops"]
                             + att["flops"] + module["flops"])
    assert 2 * (stack + 4 * 0.5 * expert) / 1e6 == pytest.approx(2271.4, abs=0.1)
    assert step["bytes"] == base["bytes"] + 2 * (stack + 4 * 8 * expert) + module["bytes"]
    # every matrix of the head: 2.80 G parameters
    held = stack + 4 * 8 * expert + w_eh + attn + router + 9 * expert
    assert held == pytest.approx(2.7975e9, rel=1e-4)
    # the module's layer at every position would be 1.6 TFLOP more
    more = 2 * (positions - rows) * (2 * 6144 * 8192 + router + 1.5 * expert)
    assert more / 1e12 == pytest.approx(1.29, abs=0.01)


def test_the_kexaone_metric_files_load_and_name_their_readers():
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    assert set(mine) == set(METRICS)
    for m in mine.values():
        assert m["reader"] in readers.READERS
        if "cost" in m:
            assert callable(getattr(validate.load_code("costs", m["cost"]), m["cost"]))
    for name, pattern in (("kexaone_window_attention_ms", "head/attn/window"),
                          ("kexaone_full_attention_ms", "head/attn/full"),
                          ("kexaone_attention_core_ms",
                           "head/(mtp/)?attn/(window|full)/core"),
                          ("kexaone_dense_mlp_ms", "head/dense"),
                          ("kexaone_experts_ms", "head/moe/experts|ragged-dot"),
                          ("kexaone_route_ms", "head/moe/route"),
                          ("kexaone_mtp_ms", "head/mtp/")):
        assert mine[name]["pattern"] == pattern
    for name, file in (("kexaone_step_roofline", "kexaone_backbone_step"),
                       ("kexaone_attention_core_roofline", "kexaone_attention_core"),
                       ("kexaone_expert_share_roofline", "kexaone_expert_share"),
                       ("kexaone_mtp_roofline", "kexaone_mtp_module")):
        assert mine[name]["cost"] == file
    share = mine["kexaone_layer_position_share"]
    assert (share["reader"], share["better"]) == ("counter_ratio", "lower")
    assert share["numerator"] == "risk_session_head_layer_positions_computed_total"
    assert share["denominator"] == "risk_session_head_layer_positions_whole_total"


def test_the_kexaone_metrics_read_a_recorded_trace_and_the_counters():
    """The scope metrics over a small trace of the new scopes, the three
    counter shares through ``counter_ratio``; on a program that has neither
    the counters nor the scopes (the parent's) each reader returns nothing
    and raises nothing."""
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    ms = 1_000_000
    band = [("fusion.qkv", "jit(_body)/head/attn/window/dot_general", 4 * ms),
            ("_block_attention", "jit(_body)/head/attn/window/core/pallas_call", 2 * ms)]
    full = [("fusion.qkv", "jit(_body)/head/attn/full/dot_general", 4 * ms),
            ("_block_attention", "jit(_body)/head/attn/full/core/pallas_call", 3 * ms)]
    sparse = [("fusion.r", "jit(_body)/head/moe/route/dot_general", ms // 2),
              ("fusion.s", "jit(_body)/head/moe/shared/dot_general", ms),
              ("ragged-dot.1", "jit(_body)/head/moe/experts/ragged_dot", 2 * ms),
              ("_combine_held", "jit(_body)/head/moe/experts/pallas_call", ms // 4)]
    ops = (band + [("fusion.d", "jit(_body)/head/dense/dot_general", 15 * ms)]
           + (band + sparse) * 2 + full + sparse + band + sparse
           + [("fusion.j", "jit(_body)/head/mtp/join/dot_general", 3 * ms),
              ("fusion.kv", "jit(_body)/head/mtp/attn/full/dot_general", ms // 2),
              ("fusion.one", "jit(_body)/head/mtp/attn/full/core/dot_general", ms // 4),
              ("fusion.mr", "jit(_body)/head/mtp/moe/route/dot_general", ms // 8),
              ("ragged-dot.9", "jit(_body)/head/mtp/moe/experts/ragged_dot", ms // 8),
              ("fusion.ring", "jit(_body)/convert_element_type", 2 * ms)])
    trace = _traced(ops)
    window = (0, 10**12)
    counters = {"risk_session_head_key_blocks_visited_total": 42.0 * 10,
                "risk_session_head_key_blocks_square_total": 96.0 * 10,
                "risk_session_head_real_positions_total": 1750.0,
                "risk_session_head_positions_total": 2048.0,
                "risk_session_head_layer_positions_computed_total": 10241.0 * 10,
                "risk_session_head_layer_positions_whole_total": 12288.0 * 10}
    r = readers.Readings(config=spec["config"], rows_ok=10, stages={},
                         counters=counters, pad_rows={2: 5},
                         device_kind="TPU v5 lite", trace=trace,
                         trace_window=window)
    got = readers.read_all(list(mine.values()), r, lambda line: None)
    assert set(got) == set(METRICS)
    value = lambda name: got[name]["value"]
    assert value("kexaone_layer_position_share") == pytest.approx(83.34, abs=0.01)
    assert value("kexaone_key_block_share") == pytest.approx(43.75, abs=0.01)
    assert value("kexaone_real_position_share") == pytest.approx(85.45, abs=0.01)
    assert value("kexaone_window_attention_ms") == pytest.approx(4 * 6.0)
    assert value("kexaone_full_attention_ms") == pytest.approx(7.0)
    assert value("kexaone_attention_core_ms") == pytest.approx(4 * 2.0 + 3.0 + 0.25)
    assert value("kexaone_dense_mlp_ms") == pytest.approx(15.0)
    # the module's own ragged-dot is read by both, as it lies under both
    assert value("kexaone_experts_ms") == pytest.approx(4 * 2.25 + 0.125)
    assert value("kexaone_route_ms") == pytest.approx(4 * 0.5)
    mtp_ms = 3 + 0.5 + 0.25 + 0.125 + 0.125
    assert value("kexaone_mtp_ms") == pytest.approx(mtp_ms)
    step_ms = 4 * 6 + 15 + 7 + 4 * 3.75 + mtp_ms + 2 + 0.001
    assert value("kexaone_step_ms") == pytest.approx(step_ms)
    # a share of a roofline is the cost file's least time over the time read
    assert value("kexaone_attention_core_roofline") == pytest.approx(
        100 * 1.352 / 11.25, abs=0.05)
    assert value("kexaone_expert_share_roofline") == pytest.approx(
        100 * 3.564 / 9.125, abs=0.05)
    assert value("kexaone_mtp_roofline") == pytest.approx(100 * 3.667 / mtp_ms, abs=0.05)
    assert value("kexaone_step_roofline") == pytest.approx(
        100 * 51.93 / step_ms, abs=0.05)
    # the parent's program has no such counters and its scopes no such names
    bare = readers.Readings(config=spec["config"], rows_ok=10, stages={},
                            counters={"risk_session_head_positions_total": 2048.0,
                                      "risk_session_head_real_positions_total": 1750.0},
                            pad_rows={2: 5}, device_kind="TPU v5 lite",
                            trace=_traced([("fusion.1", "jit(_body)/head/ssm/scan", ms)]),
                            trace_window=window)
    assert readers.counter_ratio(mine["kexaone_layer_position_share"], bare) is None
    got = readers.read_all(list(mine.values()), bare, lambda line: None)
    assert set(got) == {"kexaone_real_position_share", "kexaone_step_ms",
                        "kexaone_step_roofline"}
