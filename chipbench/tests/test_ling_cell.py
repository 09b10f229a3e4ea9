"""The files ``ling-kda-insession`` brings: its configuration is held to its
source, its cost functions give the figures PERF.md states, and its
reference one precision step down lies outside the cell's limits."""

import json

import numpy as np
import pytest

from chipbench import reference, validate

CONFIG = "risk-seqhead-ling-3.0-flash"
CELL = "ling-kda-insession"
BATCH = 256  # the cell's upper rung
METRICS = {"ling_step_ms", "ling_step_roofline", "kda_mixer_ms",
           "kda_mixer_roofline", "kda_core_ms", "ling_mla_attention_ms",
           "ling_dense_shared_mlp_ms", "ling_route_ms", "ling_expert_share_ms",
           "ling_real_position_share"}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts", "chips",
           "store_accounts", "store_loaded_accounts", "session_events_preloaded"]


def test_the_ling_configuration_is_held_to_its_source_and_states_its_cut():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    assert cfg["reduced"] == REDUCED
    differs = sorted(k for k, v in source["config"].items() if cfg[k] != v)
    assert differs == ["first_k_dense_replace", "num_experts", "num_hidden_layers"]
    assert [(cfg[k], source["config"][k]) for k in differs] == [
        (1, 2), (64, 512), (7, 42)]
    # the nulls and the lists stand as published, all 42 entries of each
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert cfg[name] == source["config"][name] and len(cfg[name]) == 42
    assert cfg["max_window_layers"] == 20
    head = cfg["head"]
    assert head["published"]["num_hidden_layers"] == 42
    assert head["published"]["first_k_dense_replace"] == 2
    assert head["published"]["num_experts"] == 512
    assert head["layers_held"] == [1, 2, 3, 4, 5, 6, 7]
    # every held expert layer's limit entries are 0: no clamp is run
    assert all(cfg[name][l] == 0 for l in head["layers_held"][1:]
               for name in ("expert_swiglu_limit_list",
                            "share_expert_swiglu_limit_list"))
    assert "8 chips share each layer" in head["deployment"]
    assert "pipeline" in head["deployment"]
    for name in ("layer_rule", "kda_gate", "use_qk_norm", "kda_rotary",
                 "group_norm_size", "head_wise_gate", "rope_interleave", "router",
                 "swiglu_limits", "projector", "vocabulary",
                 "multi_token_prediction", "recurrent_state", "padding",
                 "final_norm", "scoring_head", "seeded_tree_scale",
                 "router_balance", "dtype"):
        assert head["assumed"][name], name
    assert "2.1 MB a KDA layer" in head["recurrent_state"]
    assert cfg["env"]["SESSION_HEAD"] == "ling"
    assert cfg["resident_accounts"] == 3_145_728
    assert all(cfg["reduced_why"][k] for k in REDUCED)
    spec = validate.load_cell(CELL)
    assert spec["traffic"]["name"] == "index-insession"
    assert spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= METRICS
    assert not {n for n in names
                if n.startswith(("lfm2_", "mla_", "moe_", "falconh1_", "ssm_"))}
    # the held experts' share has a time and no roofline: which of the 64
    # held experts a step visits is the routing's, and no cost can see it
    assert "ling_expert_share_roofline" not in names
    manifest = validate.load_manifest()
    mine = [m for m in manifest["per_layer"] if m["name"] in METRICS]
    assert all(m["workloads"] == [CELL] and m["moves"] == "txns_per_s"
               for m in mine) and len(mine) == 10
    # appended after everything the benchmark had then, falconh1's entries
    # and PR 46's counter: held to what they follow, not to a count (a
    # `benchmark` PR puts entries before them: PR 56's two cells)
    configs = [c["name"] for c in manifest["configs"]]
    cells = [w["name"] for w in manifest["workloads"]]
    assert configs[configs.index(CONFIG) - 1] == "risk-seqhead-falcon-h1-34b"
    assert cells[cells.index(CELL) - 1] == "falconh1-ssm-insession"
    per_layer = [m["name"] for m in manifest["per_layer"]]
    first = per_layer.index("padded_rows_per_row") + 1
    assert set(per_layer[first:first + 10]) == METRICS


@pytest.mark.parametrize("key,value,needle", [
    ("intermediate_size", 3072, "a width may not differ"),
    ("moe_intermediate_size", 384, "a width may not differ"),
    ("head_dim", 64, "a width may not differ"),
    ("kv_lora_rank", 256, "a width may not differ"),
    ("short_conv_kernel_size", 3, "a width may not differ"),
    ("num_experts_per_tok", 4, "a width may not differ"),
    ("n_group", 4, "a width may not differ"),
    ("layer_group_size", 4, "a width may not differ"),
    ("kda_lower_bound", -10, "a width may not differ"),
    ("expert_swiglu_limit_list", [0] * 7, "a width may not differ"),
    ("num_hidden_layers", 4, "layers follow the leading dense ones"),
    ("num_experts", 4, "routed experts"),
], ids=["mlp-width", "expert-width", "head-dim", "latent", "conv-taps",
        "experts-a-token", "groups", "period", "gate-bound", "a-cut-limit-list",
        "three-layers-left", "four-experts"])
def test_a_ling_copy_with_a_width_or_a_floor_changed_is_refused(
        copy, key, value, needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms,bound_by", [
    ("ling_backbone_step", 4.401, 5.532, 22.34, "operations"),
    ("ling_kda_mixer", 3.106, 7.376, 15.77, "operations"),
    ("ling_expert_share", 0.290, 4.907, 5.99, "bytes")])
def test_the_ling_cost_functions_give_the_cells_figures(name, tflop, gb,
                                                        least_ms, bound_by):
    from chipbench import peaks

    cfg = validate.load_data("configs", CONFIG)
    fn = getattr(validate.load_code("costs", name), name)
    cost = fn(cfg, BATCH, index_mode=True)
    assert cost["flops"] / 1e12 == pytest.approx(tflop, abs=0.001)
    assert cost["bytes"] / 1e9 == pytest.approx(gb, abs=0.001)
    peak = peaks.peaks_for("TPU v5 lite")
    by_ops = cost["flops"] / peak["flops_per_s"]
    by_bytes = cost["bytes"] / peak["bytes_per_s"]
    assert max(by_ops, by_bytes) * 1e3 == pytest.approx(least_ms, abs=0.01)
    assert (by_ops > by_bytes) == (bound_by == "operations")
    twice = fn(cfg, 2 * BATCH, index_mode=True)
    assert twice["flops"] == pytest.approx(2 * cost["flops"], rel=1e-3)
    # the weights are read once whatever the batch
    assert cost["bytes"] < twice["bytes"] < 2 * cost["bytes"]
    if name == "ling_backbone_step":
        # at the 64 rung the other way round: the weights' bytes bound it
        rung = fn(cfg, 64, index_mode=True)
        assert (rung["flops"] / peak["flops_per_s"] * 1e3
                == pytest.approx(5.58, abs=0.01))
        assert (rung["bytes"] / peak["bytes_per_s"] * 1e3
                == pytest.approx(6.75, abs=0.01))


def test_the_ling_step_holds_its_parts_and_the_shares_are_the_models():
    cfg = validate.load_data("configs", CONFIG)
    cost = lambda name: getattr(validate.load_code("costs", name), name)(
        cfg, BATCH, index_mode=True)
    kda, share, step = (cost("ling_kda_mixer"), cost("ling_expert_share"),
                        cost("ling_backbone_step"))
    positions = BATCH * 16
    assert validate.load_code("costs", "ling_kda_mixer").kda_layers(cfg) == 6
    # a mixer: six projections and Wb, and the core's products over 16 positions
    projections = 6 * 2560 * 4096 + 2560 * 32
    core = 32 * (3 * 16 * 128 + 16 * 16)
    assert kda["flops"] == 2 * positions * 6 * (projections + core)
    assert 2 * positions * core * 6 / 1e9 == pytest.approx(10.07, abs=0.01)  # a step
    # 4,096 positions x 8 / 512 x 64 held = 4,096 pairs a layer, six layers
    assert share["flops"] == 2 * 4096 * 3 * 2560 * 768 * 6
    assert kda["flops"] / step["flops"] == pytest.approx(0.706, abs=0.001)
    assert share["flops"] / step["flops"] == pytest.approx(0.066, abs=0.001)
    dense_shared = 2 * positions * (3 * 2560 * 6144 + 6 * 3 * 2560 * 768)
    assert dense_shared / step["flops"] == pytest.approx(0.154, abs=0.001)
    routers = 2 * positions * 6 * 2560 * 512
    assert routers / step["flops"] == pytest.approx(0.015, abs=0.001)
    mla = step["flops"] - kda["flops"] - share["flops"] - dense_shared - routers \
        - cost("fused_step")["flops"] - 2 * positions * 12 * 2560
    assert mla / step["flops"] == pytest.approx(0.060, abs=0.001)
    # every matrix once at two bytes: the tree at rest, but for what is float32
    weights = step["bytes"] - cost("fused_step")["bytes"]
    assert weights == pytest.approx(5_532_137_220, rel=2e-4)


def _small_source() -> dict:
    """The source's keys at a small size of the same layers, its switches
    and lists as published."""
    source = dict(validate.load_source(CONFIG)["config"])
    source.update({
        "hidden_size": 128, "num_hidden_layers": 7, "first_k_dense_replace": 1,
        "num_experts": 8, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "qk_head_dim": 48, "rotary_dim": 16,
        "v_head_dim": 32, "intermediate_size": 256, "moe_intermediate_size": 64,
        "moe_shared_expert_intermediate_size": 64, "num_experts_per_tok": 4,
        "n_group": 4, "topk_group": 2,
        "head": {"published": {"num_hidden_layers": 42,
                               "first_k_dense_replace": 2, "num_experts": 32},
                 "layers_held": [1, 2, 3, 4, 5, 6, 7], "first_expert": 8}})
    return source


@pytest.fixture(scope="module")
def ling_small():
    """The reference at the small size."""
    head = validate.load_code("heads", "ling_3_flash")
    params = head.make_params(49, _small_source())
    rng = np.random.default_rng(49)
    windows, lengths = head.plausible_windows(rng, 64)
    return head, params, windows, lengths


def test_the_ling_reference_one_precision_step_down_is_outside_the_limits(
        ling_small):
    """What the control of a chip run does, on the head alone: the
    reference under the float8 rounder differs from the one at the stated
    precision by more than the cell's per-row limit, which the stated one
    against float32 operands does not."""
    head, params, windows, lengths = ling_small
    limits = validate.load_data("configs", CONFIG)["limits"]
    exact = head.forward(params, windows, lengths, reference.rounder("float32"))
    stated = head.forward(params, windows, lengths, reference.rounder("bfloat16"))
    below = head.forward(params, windows, lengths,
                         reference.rounder("float8_e4m3fn"))
    assert stated.dtype == np.float32 and stated.shape == (64,)
    assert 0.1 < float(np.std(stated))  # the fitted head spreads its answers
    rounding = float(np.sqrt(np.mean((stated - exact) ** 2)))
    assert np.abs(stated - exact).max() < limits["fraud_prob_max_err"]
    assert np.abs(below - stated).max() > limits["fraud_prob_max_err"]
    in_roundings = float(np.sqrt(np.mean((below - stated) ** 2))) / rounding
    assert in_roundings > limits["fraud_prob_err_in_roundings"]


def test_the_ling_layers_are_seen_by_the_output(ling_small):
    """The seeded tree's scale does not hide the layers behind the
    embedding: with every projection into the stream zeroed the answers
    move by far more than the cell's per-row limit."""
    import jax

    head, params, windows, lengths = ling_small
    limits = validate.load_data("configs", CONFIG)["limits"]

    def bare(layer):
        out = dict(layer, wo=layer["wo"] * 0)
        for name in ("dense", "shared", "routed"):
            if name in layer:
                out[name] = dict(layer[name], wd=layer[name]["wd"] * 0)
        return out

    rnd = reference.rounder("bfloat16")
    stated = head.forward(params, windows, lengths, rnd)
    without = head.forward(dict(params, layers=[bare(l) for l in params["layers"]]),
                           windows, lengths, rnd)
    assert np.abs(without - stated).max() > 2 * limits["fraud_prob_max_err"]
    assert all(np.isfinite(np.asarray(a.astype(np.float32))).all()
               for a in jax.tree.leaves(params))


def test_the_ling_seed_gives_the_same_tree_and_another_seed_another(ling_small):
    head, params, windows, lengths = ling_small
    rnd = reference.rounder("bfloat16")
    again = head.forward(params, windows, lengths, rnd)
    np.testing.assert_array_equal(head.forward(params, windows, lengths, rnd), again)
    same = head.make_params(49, _small_source())
    other = head.make_params(2**31 + 49, _small_source())  # past 32 signed bits
    first = lambda p: np.asarray(p["layers"][0]["wf"].astype(np.float32))
    np.testing.assert_array_equal(first(same), first(params))
    assert np.abs(first(other) - first(params)).max() > 0
    assert np.abs(np.asarray(other["layers"][0]["dt_bias"])
                  - np.asarray(params["layers"][0]["dt_bias"])).max() > 0
    np.testing.assert_array_equal(np.asarray(same["layers"][2]["rb"]),
                                  np.asarray(params["layers"][2]["rb"]))
