"""The files ``lfm2-conv-insession`` brings: its configuration is held to
its source, its cost functions give the figures PERF.md states, and its
reference one precision step down lies outside the cell's limits."""

import json

import numpy as np
import pytest

from chipbench import reference, validate

CONFIG = "risk-seqhead-lfm2-24b-a2b"
CELL = "lfm2-conv-insession"
BATCH = 256  # the cell's one rung


def test_the_configuration_is_held_to_its_source_and_states_its_cut():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "chips", "store_accounts",
                              "store_loaded_accounts",
                              "session_events_preloaded"]
    differs = [k for k, v in source["config"].items() if cfg[k] != v]
    assert differs == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    # the source's layer 0, then one whole period after its dense layers
    kinds = source["config"]["layer_types"]
    assert cfg["layer_types"] == [kinds[0]] + kinds[2:6]
    assert cfg["num_experts"] == source["config"]["num_experts"] == 64
    head = cfg["head"]
    assert head["published"]["num_hidden_layers"] == 40
    assert "five of the forty layers" in head["deployment"]
    assert "pipeline stages" in head["deployment"]
    assert "every one of the 64 experts" in head["deployment"]
    for name in ("head_dim", "projector", "vocabulary", "position_ids",
                 "q_k_head_norms", "final_norm", "renormalisation_epsilon",
                 "convolution_cache", "scoring_head", "seeded_tree_scale",
                 "expert_bias", "padding"):
        assert head["assumed"][name], name
    assert cfg["env"]["SESSION_HEAD"] == "lfm2"
    spec = validate.load_cell(CELL)
    assert spec["traffic"]["name"] == "index-insession"
    names = {m["name"] for m in spec["per_layer"]}
    # the step's time, its roofline share and the share of real positions
    # under the names every cell reads them by (PR 70)
    assert names >= {"shortconv_ms", "shortconv_roofline", "lfm2_experts_ms",
                     "lfm2_experts_roofline", "lfm2_attention_ms",
                     "lfm2_dense_mlp_ms", "device_step_ms",
                     "device_step_roofline", "head_real_position_share"}
    assert cfg["step_cost"] == "lfm2_backbone_step"
    assert "moe_experts_ms" not in names
    assert not names & {"lfm2_step_ms", "lfm2_step_roofline",
                        "lfm2_real_position_share", "lfm2_route_ms"}


@pytest.mark.parametrize("key,value,needle", [
    ("moe_intermediate_size", 768, "a width may not differ"),
    ("conv_L_cache", 4, "a width may not differ"),
    ("num_experts_per_tok", 2, "a width may not differ"),
    ("num_hidden_layers", 4, "layers follow the leading dense ones"),
    ("layer_types", ["conv", "full_attention", "conv", "conv"], "entries for"),
    ("num_experts", 4, "a cut holds at least 8"),
], ids=["expert-width", "conv-taps", "experts-a-token", "three-layers-left",
        "one-entry-a-layer", "four-experts"])
def test_a_copy_with_a_width_or_a_floor_changed_is_refused(copy, key, value,
                                                           needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    if key == "num_experts":
        cfg["reduced"].append(key)
        cfg["reduced_why"][key] = "a share"
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms", [
    ("lfm2_backbone_step", 2.47, 5.13, 12.5),
    ("lfm2_shortconv", 0.55, 1.34, 2.79),
    ("lfm2_moe_experts", 1.24, 5.03, 6.28)])
def test_the_cost_functions_give_the_cells_figures(name, tflop, gb, least_ms):
    from chipbench import peaks

    cfg = validate.load_data("configs", CONFIG)
    cost = getattr(validate.load_code("costs", name), name)(
        cfg, BATCH, index_mode=True)
    assert cost["flops"] / 1e12 == pytest.approx(tflop, abs=0.005)
    assert cost["bytes"] / 1e9 == pytest.approx(gb, abs=0.005)
    peak = peaks.peaks_for("TPU v5 lite")
    least = max(cost["flops"] / peak["flops_per_s"],
                cost["bytes"] / peak["bytes_per_s"])
    assert least * 1e3 == pytest.approx(least_ms, abs=0.05)
    twice = getattr(validate.load_code("costs", name), name)(
        cfg, 2 * BATCH, index_mode=True)
    assert twice["flops"] == pytest.approx(2 * cost["flops"], rel=1e-3)
    # the weights are read once whatever the batch: bytes grow by the
    # positions' share alone
    assert cost["bytes"] < twice["bytes"] < 2 * cost["bytes"]


def test_the_experts_weights_are_read_once_a_layer_and_the_step_holds_its_parts():
    cfg = validate.load_data("configs", CONFIG)
    cost = lambda name, batch=BATCH: getattr(
        validate.load_code("costs", name), name)(cfg, batch, index_mode=True)
    experts, conv, step = (cost("lfm2_moe_experts"), cost("lfm2_shortconv"),
                           cost("lfm2_backbone_step"))
    weights = 4 * 64 * 3 * 2048 * 1536 * 2
    assert weights / 1e9 == pytest.approx(4.83, abs=0.005)
    assert experts["bytes"] - weights == 4 * 4096 * 2048 * 6
    assert experts["flops"] / step["flops"] == pytest.approx(0.50, abs=0.005)
    assert conv["flops"] / step["flops"] == pytest.approx(0.22, abs=0.005)
    assert step["flops"] > cost("fused_step")["flops"] + experts["flops"] + conv["flops"]


@pytest.fixture(scope="module")
def lfm2_small():
    """The reference at a small size of the same kinds of layer."""
    head = validate.load_code("heads", "lfm2_24b_a2b")
    source = {
        "hidden_size": 128, "num_hidden_layers": 4, "num_dense_layers": 1,
        "layer_types": ["conv", "full_attention", "conv", "conv"],
        "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 256, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 64,
        "routed_scaling_factor": 1, "norm_topk_prob": True,
        "use_expert_bias": True, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "head": {"published": {"num_hidden_layers": 40}}}
    params = head.make_params(43, source)
    rng = np.random.default_rng(43)
    windows, lengths = head.plausible_windows(rng, 64)
    return head, params, windows, lengths


def test_the_reference_one_precision_step_down_is_outside_the_limits(lfm2_small):
    """What the control of a chip run does, on the head alone: the
    reference under the float8 rounder differs from the one at the stated
    precision by more than the cell's per-row limit, which the stated one
    against float32 operands does not."""
    head, params, windows, lengths = lfm2_small
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


def test_the_same_seed_gives_the_same_tree_and_another_seed_another(lfm2_small):
    head, params, windows, lengths = lfm2_small
    rnd = reference.rounder("bfloat16")
    again = head.forward(params, windows, lengths, rnd)
    np.testing.assert_array_equal(head.forward(params, windows, lengths, rnd), again)
    import jax

    assert all(np.isfinite(np.asarray(a.astype(np.float32))).all()
               for a in jax.tree.leaves(params))
    biases = [np.asarray(layer["rb"]) for layer in params["layers"] if "rb" in layer]
    assert len(biases) == 3 and all(np.abs(b).max() > 0.01 for b in biases)
