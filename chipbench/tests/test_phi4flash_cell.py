"""The files ``phi4flash-yoco-deep2048`` brings: its configuration is held to
its source with nothing of the model cut, its cost functions give the figures
PERF.md states, its metric files load and read a recorded trace, and its
traffic mix is the deep-review one."""

import json

import pytest

from chipbench import readers, trace_reduce, validate

CONFIG = "risk-seqhead-phi-4-mini-flash"
CELL = "phi4flash-yoco-deep2048"
BATCH = 2  # the cell's one rung
METRICS = ["phi4flash_step_ms", "phi4flash_step_roofline", "phi4flash_ssm_mixer_ms",
           "selective_scan_ms", "selective_scan_roofline",
           "phi4flash_window_attention_ms", "phi4flash_full_attention_ms",
           "phi4flash_attention_core_ms", "phi4flash_attention_core_roofline",
           "phi4flash_mlp_ms", "phi4flash_cross_decoder_ms",
           "phi4flash_cross_decoder_roofline", "phi4flash_real_position_share",
           "phi4flash_key_block_share", "phi4flash_layer_position_share"]
REDUCED = ["chips", "store_accounts", "store_loaded_accounts"]
ASSUMED = ("layer_rule", "mamba", "differential_attention", "biases", "positions",
           "norms", "mask_convention", "mlp", "projector", "vocabulary",
           "scoring_head", "dtype", "seeded_tree_scale")


def cost(name: str, batch: int = BATCH) -> dict:
    cfg = validate.load_data("configs", CONFIG)
    return getattr(validate.load_code("costs", name), name)(
        cfg, batch, index_mode=True)


def test_the_phi4flash_configuration_is_its_source_whole():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert source["name"] == "Phi-4-mini-flash-reasoning"
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    # every key of the source at its published value: nothing of the model
    # is cut, all 32 layers among it
    assert [k for k, v in source["config"].items() if cfg[k] != v] == []
    assert cfg["num_hidden_layers"] == source["layers"] == 32
    assert cfg["reduced"] == REDUCED
    assert not set(cfg["reduced"]) & set(source["config"])
    for key, value in (("hidden_size", 2560), ("intermediate_size", 10240),
                       ("num_attention_heads", 40), ("num_key_value_heads", 20),
                       ("sliding_window", 512), ("mb_per_layer", 2),
                       ("layer_norm_eps", 1e-05), ("mlp_bias", False),
                       ("tie_word_embeddings", True), ("vocab_size", 200064)):
        assert cfg[key] == source["config"][key] == value, key
    head = cfg["head"]
    assert head["reference"] == "phi4_mini_flash"
    assert head["published"]["num_hidden_layers"] == 32
    assert "holds the model whole" in head["deployment"]
    for name in ASSUMED:
        assert head["assumed"][name], name
    assert "3.34 G" in head["parameters"] and "6.68 GB" in head["parameters"]
    env = cfg["env"]
    assert (env["SESSION_HEAD"], env["SESSION_EVENTS"], env["BATCH_SIZE"]) == (
        "phi4flash", "2048", "2")
    assert cfg["resident_accounts"] == 16384 == int(env["FEATURE_CACHE_CAPACITY"])
    assert cfg["assumed"]["bytes_per_resident_account"] == 2048 * 48 + 8 + 121
    # windows deeper than the band from the first RPC, half of them wrapped
    assert cfg["session_events_preloaded"] == {"events": "1024-3072", "rounds": 64}
    assert "session_events_preloaded" not in cfg["reduced"]
    assert cfg["precision"]["control_operand_dtype"] == "float8_e4m3fn"
    assert all(cfg["reduced_why"][k] for k in REDUCED)
    for exact in ("rule_score_mismatch", "action_mismatch_same_score",
                  "session_bit_mismatch"):
        assert cfg["limits"][exact] == 0
    mellum = validate.load_data("configs", "risk-seqhead-mellum2-12b-a2.5b")
    for key in ("BULK_MAX_INFLIGHT", "FEATURE_STORE", "ANOMALY_PROFILE",
                "FEATURE_CACHE", "SESSION_STATE", "BATCH_SIZE"):
        assert env[key] == mellum["env"][key], key
    spec = validate.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "index-deepreview"
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= set(METRICS)
    assert not {n for n in names if n.startswith(
        ("lfm2_", "mla_", "moe_", "falconh1_", "ssm_", "ling_", "kda_", "xing_",
         "hc_", "backbone_", "head_", "mellum_"))}
    manifest = validate.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL]
               and by_name[n]["moves"] == "txns_per_s" for n in METRICS)
    # appended where the benchmark ended at PR 58: the 12th configuration,
    # the 12th cell, and the fifteen metrics together in this order wherever
    # a `benchmark` PR leaves them (PR 70 took fourteen entries from before
    # them; PERF.md Open question 9)
    assert manifest["configs"][11]["name"] == CONFIG
    assert manifest["workloads"][11]["name"] == CELL
    listed = [m["name"] for m in manifest["per_layer"]]
    first = listed.index(METRICS[0])
    assert listed[first:first + len(METRICS)] == METRICS
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the traffic file is the mellum cell's, unedited
    assert spec["traffic"] == validate.load_cell("mellum2-swa-deep4096")["traffic"]


@pytest.mark.parametrize("key,value,needle", [
    ("hidden_size", 1280, "a width may not differ"),
    ("intermediate_size", 5120, "a width may not differ"),
    ("sliding_window", 256, "a width may not differ"),
    ("num_hidden_layers", 16, "reduced does not name it"),
    ("num_key_value_heads", 10, "reduced does not name it"),
], ids=["hidden", "mlp-width", "band", "depth-unnamed", "key-heads-unnamed"])
def test_a_phi4flash_copy_with_a_width_or_the_depth_changed_is_refused(
        copy, key, value, needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms,bound_by", [
    ("phi4flash_backbone_step", 15.577, 6.678, 79.07, "operations"),
    ("phi4flash_selective_scan", 0.018, 2.273, 2.78, "bytes"),
    ("phi4flash_attention_core", 0.226, 0.860, 1.15, "operations"),
    ("phi4flash_cross_decoder", 0.006, 2.753, 3.36, "bytes")])
def test_the_phi4flash_cost_functions_give_the_cells_figures(name, tflop, gb,
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


def test_the_phi4flash_step_holds_its_parts_by_hand():
    """The work the output needs: layers 0-16 at 4,096 positions, of layer 17
    the ``K, V`` product at 4,096 and the rest at 2, layers 18-31 at 2; every
    weight once."""
    core = validate.load_code("costs", "phi4flash_attention_core")
    scan = validate.load_code("costs", "phi4flash_selective_scan")
    cfg = validate.load_data("configs", CONFIG)
    assert (scan.mamba_layers(cfg), core.band_layers(cfg)) == (9, 8)
    assert core.keys_kept(2048, 512) == 131_328 + 1536 * 512 == 917_760
    assert core.keys_kept(512, 512) == core.keys_kept(512, None)
    assert core.pair_macs(cfg) == 40 * 3 * 64
    mlp = 3 * 2560 * 10240
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2560 * 5120 + 2560 * 2560
    gmu, cross = 2 * 2560 * 5120, 2 * 2560 * 2560
    assert (mlp, mamba, attn) == (78_643_200, 41_123_840, 19_660_800)
    positions, rows = 2 * 2048, 2
    att, sc, second, step, base = (cost("phi4flash_attention_core"),
                                   cost("phi4flash_selective_scan"),
                                   cost("phi4flash_cross_decoder"),
                                   cost("phi4flash_backbone_step"),
                                   cost("fused_step"))
    assert att["flops"] == 2 * 7680 * rows * (8 * 917_760 + 2048)
    assert sc["flops"] == 9 * 6 * positions * 5120 * 16
    assert sc["bytes"] == 9 * 4 * (3 * positions * 5120 + 2 * positions * 16
                                   + 17 * 5120)
    assert sc["bytes"] / 9 == pytest.approx(0.2525e9, rel=1e-3)  # a layer
    assert second["flops"] == 2 * rows * 7 * (gmu + cross + 2 * mlp + 2048 * 7680)
    weights = 2 * 7 * (gmu + cross + 2 * mlp)
    assert weights == pytest.approx(2.753e9, rel=1e-3)
    assert second["bytes"] == weights  # the shared K, V (21 MB) may stay in VMEM
    first = 9 * (mamba + mlp) + 8 * (attn + mlp)
    macs = (positions * (first + 12 * 2560 + 2560 * 2560)
            + rows * (2 * 2560 * 2560 + mlp))
    assert step["flops"] == (base["flops"] + 2 * macs + sc["flops"] + att["flops"]
                             + second["flops"])
    held = first + attn + mlp + 12 * 2560
    assert step["bytes"] == base["bytes"] + 2 * held + second["bytes"]
    # every matrix of the head: 3.34 G parameters, 6.68 GB
    assert held + 7 * (gmu + cross + 2 * mlp) == pytest.approx(3.339e9, rel=1e-3)
    # layers 18-31 at every position would be 11.6 TFLOP more
    more = 2 * positions * 7 * (gmu + cross + 2 * mlp)
    assert more / 1e12 == pytest.approx(11.28, abs=0.01)


def test_the_phi4flash_metric_files_load_and_name_their_readers():
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    assert set(mine) == set(METRICS)
    for m in mine.values():
        assert m["reader"] in readers.READERS
        if "cost" in m:
            assert callable(getattr(validate.load_code("costs", m["cost"]), m["cost"]))
    for name, pattern in (("phi4flash_ssm_mixer_ms", "head/ssm"),
                          ("selective_scan_ms", "head/ssm/scan"),
                          ("phi4flash_window_attention_ms", "head/attn/window"),
                          ("phi4flash_full_attention_ms", "head/attn/full"),
                          ("phi4flash_attention_core_ms", "head/attn/(window|full)/core"),
                          ("phi4flash_mlp_ms", "head/mlp/dense"),
                          ("phi4flash_cross_decoder_ms", "head/cross")):
        assert mine[name]["pattern"] == pattern
    for name, file in (("phi4flash_step_roofline", "phi4flash_backbone_step"),
                       ("selective_scan_roofline", "phi4flash_selective_scan"),
                       ("phi4flash_attention_core_roofline", "phi4flash_attention_core"),
                       ("phi4flash_cross_decoder_roofline", "phi4flash_cross_decoder")):
        assert mine[name]["cost"] == file
    share = mine["phi4flash_layer_position_share"]
    assert (share["reader"], share["better"]) == ("counter_ratio", "lower")
    assert share["numerator"] == "risk_session_head_layer_positions_computed_total"
    assert share["denominator"] == "risk_session_head_layer_positions_whole_total"


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


def test_the_phi4flash_metrics_read_a_recorded_trace_and_the_counters():
    """The scope metrics over a small trace of the new scopes, the three
    counter shares through ``counter_ratio``; on a program that has neither
    the counters nor the scopes (the parent's) each reader returns nothing
    and raises nothing."""
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    ms = 1_000_000
    mamba = [("fusion.in", "jit(_body)/head/ssm/in/dot_general", 2 * ms),
             ("fusion.taps", "jit(_body)/head/ssm/conv/mul", ms),
             ("_selective_scan", "jit(_body)/head/ssm/scan/pallas_call", 4 * ms),
             ("fusion.out", "jit(_body)/head/ssm/out/dot_general", ms),
             ("fusion.mlp", "jit(_body)/head/mlp/dense/dot_general", 3 * ms)]
    band = [("fusion.qkv", "jit(_body)/head/attn/window/dot_general", ms),
            ("fusion.sc", "jit(_body)/head/attn/window/core/dot_general", 2 * ms),
            ("fusion.mlp", "jit(_body)/head/mlp/dense/dot_general", 3 * ms)]
    ops = (mamba * 9 + band * 8
           + [("fusion.kv", "jit(_body)/head/attn/full/dot_general", ms // 2),
              ("fusion.one", "jit(_body)/head/attn/full/core/dot_general", ms // 4),
              ("fusion.g", "jit(_body)/head/cross/gmu/dot_general", ms),
              ("fusion.c", "jit(_body)/head/cross/attn/core/dot_general", ms),
              ("fusion.m", "jit(_body)/head/cross/mlp/dot_general", 3 * ms),
              ("fusion.ring", "jit(_body)/convert_element_type", 2 * ms)])
    trace = _traced(ops)
    window = (0, 10**12)
    counters = {"risk_session_head_key_blocks_visited_total": 60.0 * 10,
                "risk_session_head_key_blocks_square_total": 144.0 * 10,
                "risk_session_head_real_positions_total": 1750.0,
                "risk_session_head_positions_total": 2048.0,
                "risk_session_head_layer_positions_computed_total": 34831.0 * 10,
                "risk_session_head_layer_positions_whole_total": 65536.0 * 10}
    r = readers.Readings(config=spec["config"], rows_ok=10, stages={},
                         counters=counters, pad_rows={2: 5},
                         device_kind="TPU v5 lite", trace=trace,
                         trace_window=window)
    got = readers.read_all(list(mine.values()), r, lambda line: None)
    assert set(got) == set(METRICS)
    value = lambda name: got[name]["value"]
    assert value("phi4flash_layer_position_share") == pytest.approx(53.15, abs=0.01)
    assert value("phi4flash_key_block_share") == pytest.approx(41.67, abs=0.01)
    assert value("phi4flash_real_position_share") == pytest.approx(85.45, abs=0.01)
    assert value("phi4flash_ssm_mixer_ms") == pytest.approx(9 * 8.0)
    assert value("selective_scan_ms") == pytest.approx(9 * 4.0)
    assert value("phi4flash_window_attention_ms") == pytest.approx(8 * 3.0)
    assert value("phi4flash_full_attention_ms") == pytest.approx(0.75)
    assert value("phi4flash_attention_core_ms") == pytest.approx(8 * 2.0 + 0.25)
    assert value("phi4flash_mlp_ms") == pytest.approx(17 * 3.0)
    assert value("phi4flash_cross_decoder_ms") == pytest.approx(5.0)
    step_ms = 9 * 11 + 8 * 6 + 0.75 + 5 + 2 + 0.001
    assert value("phi4flash_step_ms") == pytest.approx(step_ms)
    # a share of a roofline is the cost file's least time over the time read
    assert value("selective_scan_roofline") == pytest.approx(100 * 2.775 / 36, abs=0.05)
    assert value("phi4flash_attention_core_roofline") == pytest.approx(
        100 * 1.145 / 16.25, abs=0.05)
    assert value("phi4flash_cross_decoder_roofline") == pytest.approx(
        100 * 3.361 / 5, abs=0.05)
    assert value("phi4flash_step_roofline") == pytest.approx(
        100 * 79.07 / step_ms, abs=0.05)
    # the parent's program has no such counters and its scopes no such names
    bare = readers.Readings(config=spec["config"], rows_ok=10, stages={},
                            counters={"risk_session_head_positions_total": 2048.0,
                                      "risk_session_head_real_positions_total": 1750.0},
                            pad_rows={2: 5}, device_kind="TPU v5 lite",
                            trace=_traced([("fusion.1", "jit(_body)/head/moe/experts", ms)]),
                            trace_window=window)
    assert readers.counter_ratio(mine["phi4flash_layer_position_share"], bare) is None
    got = readers.read_all(list(mine.values()), bare, lambda line: None)
    assert set(got) == {"phi4flash_real_position_share", "phi4flash_step_ms",
                        "phi4flash_step_roofline"}
