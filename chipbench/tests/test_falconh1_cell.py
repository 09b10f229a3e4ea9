"""The files ``falconh1-ssm-insession`` brings: its configuration is held to
its source, its cost functions give the figures PERF.md states, and its
reference one precision step down lies outside the cell's limits."""

import json

import numpy as np
import pytest

from chipbench import reference, validate

CONFIG = "risk-seqhead-falcon-h1-34b"
CELL = "falconh1-ssm-insession"
BATCH = 256  # the cell's one rung
# six since PR 70: the step's time and roofline share and the share of real
# positions are read under the names every cell reads them by
METRICS = {"ssm_mixer_ms", "ssm_mixer_roofline", "ssm_scan_ms",
           "falconh1_attention_ms", "falconh1_mlp_ms", "falconh1_mlp_roofline"}
SHARED = {"device_step_ms", "device_step_roofline", "head_real_position_share"}


def test_the_falconh1_configuration_is_held_to_its_source_and_states_its_cut():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    assert cfg["reduced"] == ["num_hidden_layers", "chips", "store_accounts",
                              "store_loaded_accounts",
                              "session_events_preloaded"]
    differs = [k for k, v in source["config"].items() if cfg[k] != v]
    assert differs == ["num_hidden_layers"]
    assert (cfg["num_hidden_layers"], source["config"]["num_hidden_layers"]) == (4, 72)
    # the nulls and the lists stand as published
    assert cfg["attn_layer_indices"] is None and cfg["rope_scaling"] is None
    assert cfg["ssm_multipliers"] == source["config"]["ssm_multipliers"]
    assert cfg["mlp_multipliers"] == source["config"]["mlp_multipliers"]
    head = cfg["head"]
    assert head["published"]["num_hidden_layers"] == 72
    assert "four of the 72 layers whole" in head["deployment"]
    assert "pipeline" in head["deployment"]
    for name in ("projector", "vocabulary", "position_ids", "lm_head_multiplier",
                 "time_step_limit", "ssm_parameters", "recurrent_state",
                 "final_norm", "scoring_head", "seeded_tree_scale",
                 "projector_scale", "padding", "dtype"):
        assert head["assumed"][name], name
    assert "4.19 MB a layer" in head["recurrent_state"]
    assert cfg["env"]["SESSION_HEAD"] == "falconh1"
    assert cfg["resident_accounts"] == 5_242_880
    spec = validate.load_cell(CELL)
    assert spec["traffic"]["name"] == "index-insession"
    assert spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= METRICS | SHARED
    assert not {n for n in names if n.startswith(("lfm2_", "mla_", "moe_"))}
    assert cfg["step_cost"] == "falconh1_backbone_step"
    manifest = validate.load_manifest()
    mine = [m for m in manifest["per_layer"] if m["name"] in METRICS]
    assert all(m["workloads"] == [CELL] and m["moves"] == "txns_per_s"
               for m in mine) and len(mine) == 6
    # appended at the end of their lists
    assert manifest["configs"][-1]["name"] == CONFIG
    assert manifest["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in manifest["per_layer"][-6:]} == METRICS


@pytest.mark.parametrize("key,value,needle", [
    ("intermediate_size", 10752, "a width may not differ"),
    ("mamba_d_state", 128, "a width may not differ"),
    ("mamba_n_groups", 1, "a width may not differ"),
    ("mamba_d_conv", 3, "a width may not differ"),
    ("key_multiplier", 1.0, "a width may not differ"),
    ("ssm_multipliers", [1.0, 1.0, 1.0, 1.0, 1.0], "a width may not differ"),
    ("num_hidden_layers", 3, "layers follow the leading dense ones"),
], ids=["mlp-width", "state-size", "groups", "conv-taps", "a-multiplier",
        "the-mup-vector", "three-layers-left"])
def test_a_falconh1_copy_with_a_width_or_a_floor_changed_is_refused(
        copy, key, value, needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms", [
    ("falconh1_backbone_step", 14.10, 3.44, 71.57),
    ("falconh1_ssm_mixer", 2.24, 3.00, 11.38),
    ("falconh1_dense_mlp", 10.82, 3.15, 54.94)])
def test_the_falconh1_cost_functions_give_the_cells_figures(name, tflop, gb,
                                                            least_ms):
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
    # bound by operations, every one of the three
    assert cost["flops"] / peak["flops_per_s"] > 2 * cost["bytes"] / peak["bytes_per_s"]
    twice = getattr(validate.load_code("costs", name), name)(
        cfg, 2 * BATCH, index_mode=True)
    assert twice["flops"] == pytest.approx(2 * cost["flops"], rel=1e-3)
    # the weights are read once whatever the batch: bytes grow by the
    # positions' share alone
    assert cost["bytes"] < twice["bytes"] < 2 * cost["bytes"]


def test_the_falconh1_step_holds_its_parts_and_the_shares_are_the_models():
    cfg = validate.load_data("configs", CONFIG)
    cost = lambda name: getattr(validate.load_code("costs", name), name)(
        cfg, BATCH, index_mode=True)
    ssm, mlp, step = (cost("falconh1_ssm_mixer"), cost("falconh1_dense_mlp"),
                      cost("falconh1_backbone_step"))
    positions, layers = BATCH * 16, 4
    # the mixer: two projections and the core's two products over 16 positions
    projections = 5120 * 9248 + 4096 * 5120
    core = 16 * (2 * 256 + 4096)
    assert ssm["flops"] == 2 * positions * layers * (projections + core)
    assert 2 * positions * core / 1e9 == pytest.approx(0.604, abs=0.001)  # a layer
    assert mlp["flops"] == 2 * positions * layers * 3 * 5120 * 21504
    assert mlp["bytes"] - layers * 2 * 3 * 5120 * 21504 == layers * positions * 5120 * 6
    assert mlp["flops"] / step["flops"] == pytest.approx(0.768, abs=0.001)
    assert ssm["flops"] / step["flops"] == pytest.approx(0.159, abs=0.001)
    attention = step["flops"] - ssm["flops"] - mlp["flops"] - cost("fused_step")["flops"]
    assert attention / step["flops"] == pytest.approx(0.073, abs=0.001)
    # every matrix once at two bytes: the tree at rest, but for what is float32
    weights = step["bytes"] - cost("fused_step")["bytes"]
    assert weights == pytest.approx(3_441_444_356, rel=2e-4)


def _small_source() -> dict:
    """The source's keys at a small size of the same layer, its switches
    and multipliers as published."""
    source = dict(validate.load_source(CONFIG)["config"])
    source.update({
        "hidden_size": 128, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "mamba_n_heads": 4,
        "mamba_d_head": 32, "mamba_d_ssm": 128, "mamba_d_state": 16,
        "mamba_n_groups": 2, "intermediate_size": 256,
        "head": {"published": {"num_hidden_layers": 72}}})
    return source


@pytest.fixture(scope="module")
def falconh1_small():
    """The reference at the small size."""
    head = validate.load_code("heads", "falcon_h1_34b")
    params = head.make_params(45, _small_source())
    rng = np.random.default_rng(45)
    windows, lengths = head.plausible_windows(rng, 64)
    return head, params, windows, lengths


def test_the_falconh1_reference_one_precision_step_down_is_outside_the_limits(
        falconh1_small):
    """What the control of a chip run does, on the head alone: the
    reference under the float8 rounder differs from the one at the stated
    precision by more than the cell's per-row limit, which the stated one
    against float32 operands does not."""
    head, params, windows, lengths = falconh1_small
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


def test_the_falconh1_layers_are_seen_by_the_output(falconh1_small):
    """The seeded tree's scale does not hide the layers behind the
    embedding: with the three projections into the stream zeroed the
    answers move by far more than the cell's per-row limit."""
    import jax

    head, params, windows, lengths = falconh1_small
    limits = validate.load_data("configs", CONFIG)["limits"]
    bare = dict(params, layers=[
        dict(layer, wo=layer["wo"] * 0, w_out=layer["w_out"] * 0,
             dense=dict(layer["dense"], wd=layer["dense"]["wd"] * 0))
        for layer in params["layers"]])
    rnd = reference.rounder("bfloat16")
    stated = head.forward(params, windows, lengths, rnd)
    without = head.forward(bare, windows, lengths, rnd)
    assert np.abs(without - stated).max() > 2 * limits["fraud_prob_max_err"]
    assert all(np.isfinite(np.asarray(a.astype(np.float32))).all()
               for a in jax.tree.leaves(params))


def test_the_falconh1_seed_gives_the_same_tree_and_another_seed_another(
        falconh1_small):
    head, params, windows, lengths = falconh1_small
    rnd = reference.rounder("bfloat16")
    again = head.forward(params, windows, lengths, rnd)
    np.testing.assert_array_equal(head.forward(params, windows, lengths, rnd), again)
    same = head.make_params(45, _small_source())
    other = head.make_params(2**31 + 45, _small_source())  # past 32 signed bits
    first = lambda p: np.asarray(p["layers"][0]["w_in"].astype(np.float32))
    np.testing.assert_array_equal(first(same), first(params))
    assert np.abs(first(other) - first(params)).max() > 0
    assert np.abs(np.asarray(other["layers"][0]["dt_bias"])
                  - np.asarray(params["layers"][0]["dt_bias"])).max() > 0
