"""The files ``longcat-scmoe-deep2048`` brings: its configuration is held to its
source with exactly the cut it states (layers, experts held, heads held) and
``zero_expert_num`` the source's, its cost functions give the figures PERF.md
states, its metric files load and read a recorded trace, and its traffic mix
is the deep-review one. Its entries are held to no place of their lists
(PERF.md Open question 9)."""

import json

import pytest

from chipbench import readers, validate
from chipbench.tests.test_phi4flash_cell import _traced

CONFIG = "risk-seqhead-longcat-flash-omni"
CELL = "longcat-scmoe-deep2048"
BATCH = 2  # the cell's one rung
# eight, not ISSUE 68's fourteen: ``per_layer`` may hold 128 and held 120.
# Since PR 70 the step's time and roofline share are ``device_step_ms`` and
# ``device_step_roofline`` here as in every cell, and their two places hold
# the dense MLPs and the router, two of the six PR 68 left out
METRICS = ["longcat_attention_ms", "longcat_attention_roofline",
           "longcat_attention_core_ms", "longcat_dense_mlp_ms",
           "longcat_moe_branch_ms", "longcat_route_ms", "longcat_experts_ms",
           "longcat_zero_experts_ms"]
STEP = ["device_step_ms", "device_step_roofline"]
REDUCED = ["num_layers", "n_routed_experts", "num_attention_heads", "chips",
           "store_accounts", "store_loaded_accounts"]
ASSUMED = ("router", "rotary", "latent_scales", "identity_experts",
           "heads_share", "shortcut", "projector", "vocabulary", "scoring_head",
           "dtype", "seeded_tree_scale", "encoders")


def cost(name: str, batch: int = BATCH) -> dict:
    cfg = validate.load_data("configs", CONFIG)
    return getattr(validate.load_code("costs", name), name)(
        cfg, batch, index_mode=True)


def test_the_longcat_configuration_is_held_to_its_source_and_states_its_cut():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert source["name"] == "LongCat-Flash-Omni"
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    # exactly these keys of the source differ, each named by ``reduced``
    differ = sorted(k for k, v in source["config"].items() if cfg[k] != v)
    assert differ == ["n_routed_experts", "num_attention_heads", "num_layers"]
    assert cfg["reduced"] == REDUCED
    assert set(differ) == set(REDUCED) & set(source["config"])
    assert (cfg["num_layers"], source["config"]["num_layers"]) == (4, 28)
    assert (cfg["n_routed_experts"], source["config"]["n_routed_experts"]) == (8, 512)
    assert (cfg["num_attention_heads"],
            source["config"]["num_attention_heads"]) == (16, 64)
    # every width stands as published, the identity experts' count among them
    for key, value in (("hidden_size", 6144), ("ffn_hidden_size", 12288),
                       ("expert_ffn_hidden_size", 2048), ("q_lora_rank", 1536),
                       ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
                       ("qk_rope_head_dim", 64), ("v_head_dim", 128),
                       ("zero_expert_num", 256), ("zero_expert_type", "identity"),
                       ("moe_topk", 12), ("routed_scaling_factor", 6),
                       ("rope_theta", 10_000_000), ("mla_scale_q_lora", True),
                       ("mla_scale_kv_lora", True), ("vocab_size", 131072)):
        assert cfg[key] == source["config"][key] == value, key
    head = cfg["head"]
    assert head["reference"] == "longcat_flash_omni"
    assert (head["first_expert"], head["first_head"]) == (0, 0)
    assert head["published"]["num_layers"] == 28
    assert head["published"]["n_routed_experts"] == 512
    assert head["published"]["num_attention_heads"] == 64
    for words in ("64 chips share each layer", "experts 0-7", "16 of 64 a chip", "holds heads 0-15",
                  "the 4 chips of a host", "identity experts hold no weight",
                  "counted once"):
        assert words in head["deployment"], words
    for name in ASSUMED:
        assert head["assumed"][name], name
    assert "3,298.0 M" in head["parameters"] and "6.596 GB" in head["parameters"]
    env = cfg["env"]
    assert (env["SESSION_HEAD"], env["SESSION_EVENTS"], env["BATCH_SIZE"]) == (
        "longcat", "2048", "2")
    assert cfg["resident_accounts"] == int(env["FEATURE_CACHE_CAPACITY"])
    assert cfg["resident_accounts"] in (16384, 12288)
    assert cfg["assumed"]["bytes_per_resident_account"] == 2048 * 48 + 8 + 121
    assert cfg["assumed"]["player_base"] and cfg["assumed"]["limits"]
    assert cfg["session_events_preloaded"] == {"events": "1024-3072", "rounds": 64}
    assert "session_events_preloaded" not in cfg["reduced"]
    assert cfg["precision"]["control_operand_dtype"] == "float8_e4m3fn"
    assert all(cfg["reduced_why"][k] for k in REDUCED)
    for exact in ("rule_score_mismatch", "action_mismatch_same_score",
                  "session_bit_mismatch"):
        assert cfg["limits"][exact] == 0
    phi = validate.load_data("configs", "risk-seqhead-phi-4-mini-flash")
    for key in ("BULK_MAX_INFLIGHT", "FEATURE_STORE", "ANOMALY_PROFILE",
                "FEATURE_CACHE", "SESSION_STATE", "BATCH_SIZE", "SESSION_EVENTS",
                "FEATURE_CACHE_CAPACITY"):
        assert env[key] == phi["env"][key], key
    spec = validate.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "index-deepreview"
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= set(METRICS + STEP)
    assert cfg["step_cost"] == "longcat_backbone_step"
    assert not {n for n in names if n.startswith(
        ("lfm2_", "mla_", "moe_", "falconh1_", "ssm_", "ling_", "kda_", "xing_",
         "hc_", "backbone_", "head_", "mellum_", "phi4flash_", "selective_scan_",
         "kexaone_"))}
    manifest = validate.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL]
               and by_name[n]["moves"] == "txns_per_s" for n in METRICS)
    # in the manifest, together and in this order, wherever they stand
    listed = [m["name"] for m in manifest["per_layer"]]
    first = listed.index(METRICS[0])
    assert listed[first:first + len(METRICS)] == METRICS
    assert [n for n in listed if n.startswith("longcat_")] == METRICS
    assert len(listed) <= 128
    assert [c["name"] for c in manifest["configs"]].count(CONFIG) == 1
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the traffic file is the mellum cell's, unedited
    assert spec["traffic"] == validate.load_cell("mellum2-swa-deep4096")["traffic"]


@pytest.mark.parametrize("key,value,needle", [
    ("hidden_size", 3072, "a width may not differ"),
    ("ffn_hidden_size", 6144, "a width may not differ"),
    ("expert_ffn_hidden_size", 1024, "a width may not differ"),
    ("kv_lora_rank", 256, "a width may not differ"),
    ("zero_expert_num", 0, "a width may not differ"),
    ("zero_expert_num", 4, "a width may not differ"),
    ("moe_topk", 8, "a width may not differ"),
    ("mla_scale_kv_lora", False, "a width may not differ"),
    ("n_routed_experts", 4, "at least 8 routed experts"),
    ("num_layers", 3, "a cut keeps at least 4"),
], ids=["hidden", "dense-width", "expert-width", "latent", "no-identity-experts",
        "identity-experts-cut", "picks-a-position", "latent-scale",
        "under-the-floor", "three-layers"])
def test_a_longcat_copy_with_a_width_or_a_floor_broken_is_refused(
        copy, key, value, needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


def test_a_longcat_copy_whose_heads_are_cut_unnamed_is_refused(copy):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["reduced"] = [k for k in cfg["reduced"] if k != "num_attention_heads"]
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any("num_attention_heads" in e and "reduced does not name it" in e
               for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms,bound_by", [
    ("longcat_backbone_step", 15.425, 6.011, 78.30, "operations"),
    ("longcat_latent_attention", 2.204, 2.028, 11.19, "operations"),
    ("longcat_expert_share", 0.116, 2.454, 3.00, "bytes")])
def test_the_longcat_cost_functions_give_the_cells_figures(name, tflop, gb,
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


def test_the_longcat_step_holds_its_parts_by_hand():
    """The work the output needs: seven of the eight half-layers at 4,096
    positions; of the last the ``K, V`` products at 4,096 and the rest at 2;
    every weight of the stack once, and of the last layer's eight held
    experts the quarter of one that two rows' expected pairs can touch."""
    attention = validate.load_code("costs", "longcat_latent_attention")
    cfg = validate.load_data("configs", CONFIG)
    macs = attention.projection_macs(cfg)
    assert macs == {"wq_a": 9_437_184, "wq_b": 4_718_592, "wkv_a": 3_538_944,
                    "wkv_b": 2_097_152, "wo": 12_582_912}
    attn = sum(macs.values())
    assert attn == 32_374_784                   # 90.571 M at all 64 heads
    assert sum(attention.projection_macs(
        dict(cfg, num_attention_heads=64)).values()) == 90_570_752
    assert attention.causal_pairs(2048) == 2_098_176
    assert attention.pair_macs(cfg) == 16 * 320
    mlp, expert, router = 3 * 6144 * 12288, 3 * 6144 * 2048, 6144 * 768
    assert (mlp, expert, router) == (226_492_416, 37_748_736, 4_718_592)
    # a layer held: 824.44 M parameters; a whole layer's 522.45 M
    # multiply-adds a position past the experts, the narrowed one's 264.5 M
    layer = 2 * attn + 2 * mlp + router + 8 * expert
    assert layer == pytest.approx(824.44e6, rel=1e-5)
    assert 2 * attn + 2 * mlp + router == pytest.approx(522.45e6, rel=1e-5)
    kv = macs["wkv_a"] + macs["wkv_b"]
    assert attn + mlp + kv == pytest.approx(264.50e6, rel=2e-5)
    positions, rows = 2 * 2048, 2
    att, share, step, base = (cost("longcat_latent_attention"),
                              cost("longcat_expert_share"),
                              cost("longcat_backbone_step"), cost("fused_step"))
    pairs = 7 * 2_098_176 + 2048
    assert att["flops"] == 2 * (7 * positions * attn + positions * kv
                                + rows * (attn - kv) + rows * pairs * 16 * 320)
    # the seven whole cores: 0.30 TFLOP of the attentions' 2.20
    assert 2 * rows * 7 * 2_098_176 * 16 * 320 / 1e12 == pytest.approx(0.301, abs=1e-3)
    held_pairs = positions * 12 * 8 / 768        # a whole layer's, expected
    assert held_pairs == 512 and held_pairs / 8 == 64   # a held expert's, a step
    last = rows * 12 * 8 / 768
    assert share["flops"] == 2 * (3 * held_pairs + last) * expert
    assert share["bytes"] == (3 * (2 * 8 * expert + held_pairs * 6144 * 2
                                   + positions * 6144 * 8)
                              + 2 * last * expert + last * 6144 * 2
                              + rows * 6144 * 8)
    everywhere = 12 * 6144 + 7 * mlp + 3 * router
    assert step["flops"] == (base["flops"] + att["flops"] + share["flops"]
                             + 2 * (positions * everywhere + rows * (mlp + router)))
    params = 12 * 6144 + 4 * (2 * mlp + router) + 8 * attn + (3 * 8 + last) * expert
    assert step["bytes"] == base["bytes"] + 2 * params
    # every matrix of the head: 3.298 G parameters
    assert 12 * 6144 + 4 * layer == pytest.approx(3.298e9, rel=1e-4)
    # the dense MLPs are 85% of the step's operations, the attentions 14%
    # (their cores 2%), the routers 1%, the held experts under 1% of the
    # operations and, at rest, 37% of the bytes
    dense = 2 * (7 * positions + rows) * mlp
    assert dense / step["flops"] == pytest.approx(0.842, abs=0.002)
    assert att["flops"] / step["flops"] == pytest.approx(0.143, abs=0.002)
    assert 2 * (3 * positions + rows) * router / step["flops"] < 0.01
    assert share["flops"] / step["flops"] < 0.01
    assert 4 * 8 * expert / (12 * 6144 + 4 * layer) == pytest.approx(0.366, abs=0.002)
    # un-narrowed, the last half-layer and its branch would be 2.15 TFLOP more
    more = 2 * (positions - rows) * (attn - kv + mlp + router) + 2 * 512 * expert
    assert more / 1e12 == pytest.approx(2.15, abs=0.01)


def test_the_longcat_metric_files_load_and_name_their_readers():
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS + STEP}
    assert set(mine) == set(METRICS + STEP)
    for m in mine.values():
        assert m["reader"] in readers.READERS
        if "cost" in m:
            file = validate.cost_name(m, spec["config"])
            assert callable(getattr(validate.load_code("costs", file), file))
    for name, pattern in (("longcat_attention_ms", "head/attn/[01]"),
                          ("longcat_attention_core_ms", "head/attn/[01]/core"),
                          ("longcat_dense_mlp_ms", "head/mlp/dense"),
                          ("longcat_route_ms", "head/moe/route"),
                          ("longcat_moe_branch_ms", "head/moe/|ragged-dot"),
                          ("longcat_experts_ms", "head/moe/experts|ragged-dot"),
                          ("longcat_zero_experts_ms", "head/moe/zero")):
        assert mine[name]["pattern"] == pattern
    for name, file in (("device_step_roofline", "longcat_backbone_step"),
                       ("longcat_attention_roofline", "longcat_latent_attention")):
        assert validate.cost_name(mine[name], spec["config"]) == file


def test_the_longcat_metrics_read_a_recorded_trace():
    """The scope metrics over a small trace of the new scopes; on a program
    that has no such scopes (the parent's) each reader but the step's returns
    nothing and raises nothing."""
    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS + STEP}
    ms = 1_000_000

    def attention(i: int, core_ms: float) -> list:
        scope = f"jit(_body)/head/attn/{i}"
        return [("fusion.q", f"{scope}/q/dot_general", ms // 2),
                ("fusion.kv", f"{scope}/kv/dot_general", ms // 4),
                ("fusion.core", f"{scope}/core/dot_general", int(core_ms * ms)),
                ("fusion.out", f"{scope}/out/dot_general", ms // 4)]

    dense = [("fusion.d", "jit(_body)/head/mlp/dense/dot_general", 12 * ms)]
    branch = [("fusion.r", "jit(_body)/head/moe/route/dot_general", ms // 2),
              ("ragged-dot.1", "jit(_body)/head/moe/experts/while/body/ragged_dot", ms),
              ("_combine_held", "jit(_body)/head/moe/experts/while/body/pallas_call",
               ms // 4),
              ("fusion.z", "jit(_body)/head/moe/zero/mul", ms // 8)]
    layer = attention(0, 0.5) + branch + dense + attention(1, 0.5) + dense
    last = (attention(0, 0.5) + branch + dense + attention(1, 0.125)
            + [("fusion.d2", "jit(_body)/head/mlp/dense/dot_general", ms // 100)])
    ops = 3 * layer + last + [("fusion.ring", "jit(_body)/convert_element_type", 2 * ms)]
    r = readers.Readings(config=spec["config"], rows_ok=10, stages={}, counters={},
                         pad_rows={2: 5}, device_kind="TPU v5 lite",
                         trace=_traced(ops), trace_window=(0, 10**12))
    got = readers.read_all(list(mine.values()), r, lambda line: None)
    assert set(got) == set(METRICS + STEP)
    value = lambda name: got[name]["value"]
    attention_ms = 7 * 1.5 + 1.125
    assert value("longcat_attention_ms") == pytest.approx(attention_ms)
    assert value("longcat_attention_core_ms") == pytest.approx(7 * 0.5 + 0.125)
    assert value("longcat_moe_branch_ms") == pytest.approx(4 * 1.875)
    assert value("longcat_experts_ms") == pytest.approx(4 * 1.25)
    assert value("longcat_zero_experts_ms") == pytest.approx(4 * 0.125)
    assert value("longcat_dense_mlp_ms") == pytest.approx(7 * 12 + 0.01)
    assert value("longcat_route_ms") == pytest.approx(4 * 0.5)
    step_ms = attention_ms + 4 * 1.875 + 7 * 12 + 0.01 + 2 + 0.001
    assert value("device_step_ms") == pytest.approx(step_ms)
    # a share of a roofline is the cost file's least time over the time read
    assert value("longcat_attention_roofline") == pytest.approx(
        100 * 11.186 / attention_ms, abs=0.05)
    assert value("device_step_roofline") == pytest.approx(
        100 * 78.30 / step_ms, abs=0.05)
    # the parent's program has no such scopes
    bare = readers.Readings(config=spec["config"], rows_ok=10, stages={},
                            counters={}, pad_rows={2: 5}, device_kind="TPU v5 lite",
                            trace=_traced([("fusion.1", "jit(_body)/head/ssm/scan", ms)]),
                            trace_window=(0, 10**12))
    got = readers.read_all(list(mine.values()), bare, lambda line: None)
    assert set(got) == set(STEP)
