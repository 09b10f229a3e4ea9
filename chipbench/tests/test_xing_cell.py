"""The files ``xing-mhc-insession`` brings: its configuration is held to its
source, its cost functions give the figures PERF.md states, its metric
files load, and its reference one precision step down lies outside the
cell's limits."""

import json

import numpy as np
import pytest

from chipbench import reference, validate

CONFIG = "risk-seqhead-xing4.0-29b-a4b"
CELL = "xing-mhc-insession"
BATCH = 256  # the cell's upper rung
METRICS = {"xing_step_ms", "xing_step_roofline", "hc_streams_ms",
           "hc_streams_roofline", "hc_maps_ms", "xing_mla_attention_ms",
           "xing_dense_shared_mlp_ms", "xing_experts_ms", "xing_route_ms",
           "xing_real_position_share"}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "chips",
           "store_accounts", "store_loaded_accounts", "session_events_preloaded"]
ASSUMED = ("stream_entry_exit", "sinkhorn_round", "clip_before_exp", "map_norm",
           "hyper_parameters_seeded", "rotary", "latent_norms", "router",
           "projector", "vocabulary", "multi_token_prediction", "scoring_head",
           "seeded_tree_scale", "router_balance", "padding", "dtype")


def test_the_xing_configuration_is_held_to_its_source_and_states_its_cut():
    assert validate.check_manifest() == []
    cfg = validate.load_data("configs", CONFIG)
    source = validate.load_source(CONFIG)
    assert cfg["source"] == source["source_url"]
    assert sorted(cfg["source_keys"]) == sorted(source["config"])
    assert cfg["reduced"] == REDUCED
    differs = sorted(k for k, v in source["config"].items() if cfg[k] != v)
    assert differs == ["first_k_dense_replace", "num_hidden_layers"]
    assert [(cfg[k], source["config"][k]) for k in differs] == [(1, 2), (5, 40)]
    # every width, every head and all 64 experts as published
    for key, value in (("hidden_size", 3584), ("n_routed_experts", 64),
                       ("num_experts_per_tok", 4), ("moe_intermediate_size", 1024),
                       ("intermediate_size", 9216), ("num_attention_heads", 32),
                       ("q_lora_rank", 768), ("kv_lora_rank", 512),
                       ("hc_mult", 4), ("hc_sinkhorn_iters", 20),
                       ("hc_eps", 1e-6), ("mhc_h_res_clamp_min", -30),
                       ("mhc_h_res_clamp_max", 30)):
        assert cfg[key] == source["config"][key] == value, key
    # the nested group whole
    assert cfg["rope_scaling"] == source["config"]["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    head = cfg["head"]
    assert head["published"]["num_hidden_layers"] == 40
    assert head["published"]["first_k_dense_replace"] == 2
    assert head["published"]["n_routed_experts"] == 64
    assert head["layers_held"] == [1, 2, 3, 4, 5]
    assert "eight pipeline stages of five layers" in head["deployment"]
    for name in ASSUMED:
        assert head["assumed"][name], name
    assert "3,108,203,279" in head["parameters"]
    assert "6,223,387,708" in head["parameters"]
    assert cfg["env"]["SESSION_HEAD"] == "xing"
    assert cfg["env"]["BATCH_SIZE"] == "256"
    assert cfg["resident_accounts"] in (3_145_728, 2_097_152)
    assert cfg["precision"]["control_operand_dtype"] == "float8_e4m3fn"
    assert all(cfg["reduced_why"][k] for k in REDUCED)
    for exact in ("rule_score_mismatch", "action_mismatch_same_score",
                  "session_bit_mismatch"):
        assert cfg["limits"][exact] == 0
    spec = validate.load_cell(CELL)
    assert spec["traffic"]["name"] == "index-insession"
    assert spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    assert names >= METRICS
    assert not {n for n in names if n.startswith(
        ("lfm2_", "mla_", "moe_", "falconh1_", "ssm_", "ling_", "kda_"))}
    # the experts have a time and no roofline: which experts a step visits
    # is the routing's, and no cost can see it (PERF.md Open question 21a)
    assert "xing_experts_roofline" not in names
    manifest = validate.load_manifest()
    mine = [m for m in manifest["per_layer"] if m["name"] in METRICS]
    assert all(m["workloads"] == [CELL] and m["moves"] == "txns_per_s"
               for m in mine) and len(mine) == 10
    # appended after everything the benchmark had then, ling's entries last:
    # held to what they follow, not to a count (a `benchmark` PR puts
    # entries before them: PR 56's two cells)
    configs = [c["name"] for c in manifest["configs"]]
    cells = [w["name"] for w in manifest["workloads"]]
    assert configs[configs.index(CONFIG) - 1] == "risk-seqhead-ling-3.0-flash"
    assert cells[cells.index(CELL) - 1] == "ling-kda-insession"
    per_layer = [m["name"] for m in manifest["per_layer"]]
    first = per_layer.index("ling_real_position_share") + 1
    assert set(per_layer[first:first + 10]) == METRICS


@pytest.mark.parametrize("key,value,needle", [
    ("intermediate_size", 4608, "a width may not differ"),
    ("moe_intermediate_size", 512, "a width may not differ"),
    ("q_lora_rank", 384, "a width may not differ"),
    ("qk_rope_head_dim", 32, "a width may not differ"),
    ("num_experts_per_tok", 2, "a width may not differ"),
    ("hc_mult", 2, "a width may not differ"),
    ("hc_sinkhorn_iters", 5, "a width may not differ"),
    ("rope_scaling", {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
                      "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096, "type": "yarn"},
     "a width may not differ"),
    ("n_routed_experts", 32, "reduced does not name it"),
    ("num_hidden_layers", 4, "layers follow the leading dense ones"),
], ids=["mlp-width", "expert-width", "query-latent", "rotary-width",
        "experts-a-token", "streams", "sinkhorn-rounds", "yarn-factor",
        "experts-held-unnamed", "three-layers-left"])
def test_a_xing_copy_with_a_width_or_a_floor_changed_is_refused(
        copy, key, value, needle):
    path = copy / "chipbench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg[key] = value
    path.write_text(json.dumps(cfg))
    errors = validate.check_manifest(str(copy))
    assert any(key in e and needle in e for e in errors), errors


@pytest.mark.parametrize("name,tflop,gb,least_ms,bound_by", [
    ("xing_backbone_step", 3.829, 13.857, 19.44, "operations"),
    ("xing_hc_streams", 0.035, 7.647, 9.34, "bytes")])
def test_the_xing_cost_functions_give_the_cells_figures(name, tflop, gb,
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
    # the weights (and phi) are read once whatever the batch
    assert cost["bytes"] < twice["bytes"] < 2 * cost["bytes"]
    if name == "xing_backbone_step":
        # at the 64 rung the other way round: the bytes bound it
        rung = fn(cfg, 64, index_mode=True)
        assert (rung["flops"] / peak["flops_per_s"] * 1e3
                == pytest.approx(4.86, abs=0.01))
        assert (rung["bytes"] / peak["bytes_per_s"] * 1e3
                == pytest.approx(9.93, abs=0.01))


def test_the_xing_step_holds_its_parts_by_hand():
    cfg = validate.load_data("configs", CONFIG)
    cost = lambda name: getattr(validate.load_code("costs", name), name)(
        cfg, BATCH, index_mode=True)
    hc, step, base = cost("xing_hc_streams"), cost("xing_backbone_step"), cost("fused_step")
    positions = BATCH * 16
    assert validate.load_code("costs", "xing_hc_streams").sublayers(cfg) == 10
    # a sublayer: the four float32 streams three times (read for maps and
    # read; read and written for the write), y once, phi once
    stream = positions * 4 * 3584 * 4
    assert stream == 234_881_024
    assert hc["bytes"] == 10 * (3 * stream + positions * 3584 * 4 + 14336 * 24 * 4)
    # phi's product, the read, the write, and the rounds, a position
    assert hc["flops"] == 10 * positions * (
        2 * 14336 * 24 + 2 * 3584 * 4 + 2 * 3584 * (16 + 4) + 20 * 4 * 16)
    # the five projections of attention, by the widths
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
                 + 32 * 128 * 3584)
    assert attention == 28_409_856
    over_keys = 16 * 32 * (192 + 128)
    dense, expert = 3 * 3584 * 9216, 3 * 3584 * 1024
    assert (dense, expert) == (99_090_432, 11_010_048)
    router = 3584 * 64
    macs = (12 * 3584 + 5 * (attention + over_keys) + dense
            + 4 * (router + expert + 4 * expert))
    assert step["flops"] == base["flops"] + 2 * positions * macs + hc["flops"]
    # every matrix once at two bytes (all 64 experts of each expert layer)
    held = 12 * 3584 + 5 * attention + dense + 4 * (router + expert + 64 * expert)
    assert step["bytes"] == base["bytes"] + 2 * held + hc["bytes"]
    assert held == pytest.approx(3_108_203_279 - 10 * 14336 * 24, rel=1e-4)
    # the shares the issue reckoned: attention, the MLPs, the experts
    assert 2 * positions * 5 * (attention + over_keys) / step["flops"] == pytest.approx(0.306, abs=0.001)
    assert 2 * positions * (dense + 4 * expert) / step["flops"] == pytest.approx(0.306, abs=0.001)
    assert 2 * positions * 16 * expert / step["flops"] == pytest.approx(0.377, abs=0.001)
    assert hc["bytes"] / step["bytes"] == pytest.approx(0.552, abs=0.001)


def test_the_xing_metric_files_load_and_name_their_readers():
    from chipbench import readers

    spec = validate.load_cell(CELL)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in METRICS}
    assert set(mine) == METRICS
    for m in mine.values():
        assert m["reader"] in readers.READERS
        if "cost" in m:
            assert callable(getattr(validate.load_code("costs", m["cost"]), m["cost"]))
    assert mine["hc_streams_ms"]["pattern"] == "head/hc"
    assert mine["hc_maps_ms"]["pattern"] == "head/hc/maps"
    assert mine["hc_streams_roofline"]["cost"] == "xing_hc_streams"
    assert mine["xing_step_roofline"]["cost"] == "xing_backbone_step"
    assert mine["xing_experts_ms"]["pattern"] == "head/moe/experts|ragged-dot"
    assert mine["xing_real_position_share"]["reader"] == "counter_ratio"


def _small_source() -> dict:
    """The source's keys at a small size of the same layers, its switches
    and its ``rope_scaling`` group as published."""
    source = dict(validate.load_source(CONFIG)["config"])
    source.update({
        "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": 8, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts_per_tok": 2,
        "head": {"published": {"num_hidden_layers": 40,
                               "first_k_dense_replace": 2,
                               "n_routed_experts": 8},
                 "layers_held": [1, 2, 3]}})
    return source


@pytest.fixture(scope="module")
def xing_small():
    """The reference at the small size."""
    head = validate.load_code("heads", "xing4_29b_a4b")
    params = head.make_params(52, _small_source())
    rng = np.random.default_rng(52)
    windows, lengths = head.plausible_windows(rng, 64)
    return head, params, windows, lengths


def test_the_xing_reference_one_precision_step_down_is_outside_the_limits(
        xing_small):
    """What the control of a chip run does, on the head alone: the
    reference under the float8 rounder differs from the one at the stated
    precision by more than the cell's per-row limit, which the stated one
    against float32 operands does not."""
    head, params, windows, lengths = xing_small
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


def test_the_xing_layers_and_streams_are_seen_by_the_output(xing_small):
    """The seeded tree's scale does not hide the layers behind the
    embedding: with every projection into the streams zeroed the answers
    move by far more than the cell's per-row limit; and the streams are not
    one stream four times: with the mixing map's offsets zeroed (no lean to
    the diagonal) the answers move too."""
    import jax

    head, params, windows, lengths = xing_small
    limits = validate.load_data("configs", CONFIG)["limits"]

    def bare(layer):
        out = dict(layer, wo=layer["wo"] * 0)
        for name in ("dense", "shared", "routed"):
            if name in layer:
                out[name] = dict(layer[name], wd=layer[name]["wd"] * 0)
        return out

    def unleaning(layer):
        return dict(layer, **{k: dict(layer[k], b=layer[k]["b"].at[8:].set(0.0))
                              for k in ("hc_attn", "hc_mlp")})

    rnd = reference.rounder("bfloat16")
    stated = head.forward(params, windows, lengths, rnd)
    without = head.forward(dict(params, layers=[bare(l) for l in params["layers"]]),
                           windows, lengths, rnd)
    assert np.abs(without - stated).max() > 2 * limits["fraud_prob_max_err"]
    mixed = head.forward(dict(params, layers=[unleaning(l) for l in params["layers"]]),
                         windows, lengths, rnd)
    assert np.abs(mixed - stated).max() > limits["fraud_prob_max_err"] / 5
    assert all(np.isfinite(np.asarray(a.astype(np.float32))).all()
               for a in jax.tree.leaves(params))


def test_the_xing_seed_gives_the_same_tree_and_another_seed_another(xing_small):
    head, params, windows, lengths = xing_small
    rnd = reference.rounder("bfloat16")
    again = head.forward(params, windows, lengths, rnd)
    np.testing.assert_array_equal(head.forward(params, windows, lengths, rnd), again)
    same = head.make_params(52, _small_source())
    other = head.make_params(2**31 + 52, _small_source())  # past 32 signed bits
    first = lambda p: np.asarray(p["layers"][0]["wq_a"].astype(np.float32))
    np.testing.assert_array_equal(first(same), first(params))
    assert np.abs(first(other) - first(params)).max() > 0
    for part in ("phi", "b", "a"):
        at = lambda p: np.asarray(p["layers"][1]["hc_mlp"][part])
        np.testing.assert_array_equal(at(same), at(params))
        assert np.abs(at(other) - at(params)).max() > 0
    np.testing.assert_array_equal(np.asarray(same["layers"][2]["rb"]),
                                  np.asarray(params["layers"][2]["rb"]))


def test_a_rehearsal_of_the_xing_cell_judges_correct():
    """``python -m chipbench.run --workload xing-mhc-insession --rehearse``
    at a small size of the same layers: every phase of the cell on the CPU
    (boot, fill, the check against the reference, a short window), its last
    line ``correct`` with no device number under a device metric's name."""
    import copy as copy_mod
    import dataclasses
    import os

    import jax

    from chipbench import harness
    from igaming_platform_tpu.models import decoder_parts as dp
    from igaming_platform_tpu.models import session_heads
    from igaming_platform_tpu.models import xing_backbone as xb

    small = _small_source()
    cfg = xb.XingConfig(
        hidden=64, layers=3, dense_layers=1, heads=4, q_rank=32, kv_rank=16,
        nope_dim=16, rope_dim=8, v_dim=16, dense_width=96, experts=8, top_k=2,
        expert_width=32)
    row = session_heads.HEADS["xing"]
    saved_env, saved_row = dict(os.environ), row
    session_heads.HEADS["xing"] = dataclasses.replace(
        row, scores=lambda sp, win, lp: xb.backbone_scores(sp, win, lp, cfg),
        init=lambda: xb.init_backbone(jax.random.key(11), cfg), config=cfg,
        experts=(8, 8))
    spec = copy_mod.deepcopy(validate.load_cell(CELL))
    spec["config"]["head"] = dict(spec["config"]["head"], **small.pop("head"))
    spec["config"].update(small)
    spec["config"]["env"]["FEATURE_STORE"] = "python"
    # a loaded CPU compiling this step (twenty unrolled rounds a sublayer) is
    # no stalled device: keep the supervisor's watchdog out of a rehearsal
    spec["config"]["env"]["DEVICE_STEP_DEADLINE_S"] = "600"
    dp.announce_core.cache_clear()
    run = harness.Run(spec, seed=2**31 + 52, seconds=1.0, trace=False,
                      rehearse=True)
    try:
        run.boot()
        try:
            run.fill()
            ok, numbers = run.check()
            result = run.window()
        finally:
            run.shutdown()
    finally:
        session_heads.HEADS["xing"] = saved_row
        os.environ.clear()
        os.environ.update(saved_env)
    assert ok and result["correct"], numbers
    assert result["failed"] == 0 and result["attempted"] > 0
    assert numbers["warm_rows"] > 0 and numbers["session_bit_mismatch"] == 0
