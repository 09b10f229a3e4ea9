"""A configuration brings its session head, and a roofline metric its
kernel's cost, as NEW files: a throwaway head and a throwaway cost
function that exist only in a temporary copy are found, validated, run
through the reference and judged by ``correct``. No file the benchmark
has is patched for them."""

import json
import os
from copy import deepcopy

import numpy as np
import pytest

from chipbench import harness, reference, trace_reduce, validate
from chipbench.readers import READERS, Readings

DATA = os.path.join(os.path.dirname(__file__), "data")

TOY_HEAD = '''
import numpy as np

CALLS = []


def make_params(seed, config):
    width = config["head"]["d_model"]
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((12, width)).astype(np.float32)}


def forward(params, windows, lengths, rnd):
    CALLS.append(windows.shape)
    h = rnd(windows) @ rnd(params["w"])          # [rows, events, d_model]
    return (1.0 / (1.0 + np.exp(-h.mean((1, 2))))).astype(np.float32)
'''
BLIND_HEAD = '''
import numpy as np


def make_params(seed, config):
    return None


def forward(params, windows, lengths, rnd):
    return np.zeros(len(windows), np.float32)   # never sees a pattern
'''
TOY_COST = '''
def toy_step(config, batch, *, index_mode):
    d = config["head"]["d_model"]
    return {"flops": 2 * batch * 16 * 12 * d, "bytes": 4 * batch * 16 * 12}
'''


@pytest.fixture
def root(copy):
    """The copy of the manifest and everything it names, plus new files
    only: a configuration with a ``head`` of its own, that head, a cost
    function, a roofline metric over it and a cell."""
    tmp_path, base = copy, copy / "chipbench"
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    (base / "heads" / "toy.py").write_text(TOY_HEAD)
    (base / "heads" / "blind.py").write_text(BLIND_HEAD)
    (base / "heads" / "copycat.py").write_text(
        (base / "heads" / "pattern.py").read_text())
    (base / "costs" / "toy_step.py").write_text(TOY_COST)
    cfg = json.loads((base / "configs" / "risk-stateful-5m-pattern.json").read_text())
    cfg["name"] = "risk-stateful-5m-toy"
    cfg["source"] = "a paper that does not exist (section 3, table 2)"
    cfg["head"] = {"reference": "toy", "d_model": 8,
                   "published": {"d_model": 8, "num_hidden_layers": 4},
                   "deployment": "one layer of four"}
    (base / "configs" / "risk-stateful-5m-toy.json").write_text(json.dumps(cfg))
    metric = json.loads((base / "layer_metrics" / "device_step_roofline.json").read_text())
    metric.update(name="toy_step_roofline", cost="toy_step")
    (base / "layer_metrics" / "toy_step_roofline.json").write_text(json.dumps(metric))
    with open(tmp_path / "BENCHMARK.json") as f:
        m = json.load(f)
    m["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": "chipbench/configs/risk-stateful-5m-toy.json",
                         "reduced": cfg["reduced"], "why": "a head of its own"})
    m["workloads"].append({"name": "toy-index-flatout", "config": cfg["name"],
                           "traffic": "index-flatout", "chips": 1, "why": "w"})
    entry = next(x for x in m["per_layer"] if x["name"] == "device_step_roofline")
    m["per_layer"].append(dict(entry, name="toy_step_roofline",
                               workloads=["toy-index-flatout"]))
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    yield str(tmp_path)
    assert all(p.read_bytes() == b for p, b in before.items()), "a file was patched"


def test_a_new_head_and_cost_are_found_validated_and_run(root):
    assert validate.check_manifest(root) == []
    spec = validate.load_cell("toy-index-flatout", root)
    config = spec["config"]
    assert validate.head_name(config) == "toy"
    head = validate.load_code("heads", "toy", root)
    params = head.make_params(11, config)
    assert params["w"].shape == (12, 8)          # the configuration's own size
    # through the reference at a tiny size: windows warm up and reach the head
    ref = reference.Reference(reference.make_params(11, (16, 16)), head=head,
                              head_params=params, n_events=16)
    ids = ["a", "b", "a", "c"]
    base = np.zeros((4, reference.N_FEATURES), np.float32)
    for k in range(5):
        out = ref.score_index(ids, base, [1000, 2000, 3000, 4000], [2, 0, 2, 1],
                              clock=1_800_000_000.0 + k)
    assert head.CALLS[-1] == (4, 16, 12)
    assert out["warm"].all() and out["sprob"].shape == (4,)
    assert len(set(np.round(out["sprob"], 6))) > 1
    # and its roofline metric reads through the generic reader
    m = next(x for x in spec["per_layer"] if x["name"] == "toy_step_roofline")
    small = trace_reduce.load_json(os.path.join(DATA, "small_trace.json"))
    r = Readings(config=config, rows_ok=1, stages={}, counters={},
                 device_kind="TPU v5 lite", pad_rows={256: 10}, trace=small,
                 trace_window=(900, 2500), root=root)
    share = READERS["trace_roofline_share"](dict(m, pattern="fused_session"), r)
    cost = validate.load_code("costs", "toy_step", root).toy_step(
        config, 256, index_mode=True)
    assert share == pytest.approx(100.0 * (cost["bytes"] / 819e9) / 400e-9)


def test_the_two_configurations_resolve_to_their_heads_by_default():
    for name, head in (("risk-stateful-5m-pattern", "pattern"),
                       ("risk-stateful-5m-seqhead", "transformer")):
        config = validate.load_data("configs", name)
        assert "head" not in config
        assert validate.head_name(config) == head
        module = validate.load_code("heads", head)
        params = module.make_params(3_000_000_019, config)
        assert (params is None) == (head == "pattern")


def _config_at(root, name, fn):
    path = os.path.join(root, "chipbench", "configs", name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    fn(cfg)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return cfg


def _config(root, fn):
    _config_at(root, "risk-stateful-5m-toy", fn)


@pytest.mark.parametrize("edit, needle", [
    (lambda c: c["head"].update(reference="absent"), "no file chipbench/heads/absent.py"),
    (lambda c: c["head"].pop("reference"), "names its 'reference'"),
    (lambda c: c.update(head="toy"), "names its 'reference'"),
    (lambda c: c.update(d_model=8), "unknown keys"),
], ids=["head-file-missing", "head-unnamed", "head-not-an-object", "stray-key"])
def test_a_broken_head_entry_is_reported(root, edit, needle):
    _config(root, edit)
    errors = validate.check_manifest(root)
    assert errors and any(needle in e for e in errors), errors


def test_a_code_file_that_lacks_its_functions_is_reported(root):
    with open(os.path.join(root, "chipbench", "heads", "half.py"), "w") as f:
        f.write("def forward(params, windows, lengths, rnd):\n    return 0\n")
    _config(root, lambda c: c["head"].update(reference="half"))
    with open(os.path.join(root, "chipbench", "costs", "empty_step.py"), "w") as f:
        f.write("x = 1\n")
    path = os.path.join(root, "chipbench", "layer_metrics", "toy_step_roofline.json")
    with open(path) as f:
        metric = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(metric, cost="empty_step"), f)
    errors = validate.check_manifest(root)
    assert any("make_params" in e for e in errors), errors
    assert any("empty_step" in e and "cost" in e for e in errors), errors


def test_a_head_from_a_new_file_is_judged_by_correct(root):
    """The served pattern head against two heads that exist only as new
    files: a copy of its reference is ``correct``, one that never sees a
    pattern is not."""
    _config(root, lambda c: c["head"].update(reference="copycat"))
    spec = deepcopy(validate.load_cell("toy-index-flatout", root))
    spec["config"]["env"].update(BATCH_SIZE="256", FEATURE_STORE="python")
    run = harness.Run(spec, seed=3_000_000_011, seconds=1.0, trace=False,
                      rehearse=True)
    run.boot()
    try:
        run.fill()
        ok, numbers = run.check()
        assert run.head.__file__.startswith(root)
        assert ok and numbers["folded_rows"] > 0, numbers
        run.head = validate.load_code("heads", "blind", root)
        ok, numbers = run.judge(
            spec["config"]["precision"]["reference_operand_dtype"])
        assert not ok and numbers["session_bit_mismatch"] > 0, numbers
    finally:
        run.shutdown()


# -- a configuration taken from a published source ----------------------------
#
# The source's keys sit at the top level of the configuration file under
# the source's names, a copy of the source sits under ``sources/``, and
# ``--validate`` holds the one to the other. The source here is made up, in
# the shape of a catalog row: two nested groups, a null, lists.

SOURCE = {
    "name": "Made-Up-8B-A1B",
    "source_url": "https://example.org/made-up/Made-Up-8B-A1B/config.json",
    "layers": 12,
    "config": {
        "attn_every_n_layers": 4, "head_dim": 8, "hidden_act": "silu",
        "hidden_size": 16, "intermediate_size": 32,
        "layer_types": ["window", "window", "window", "full"] * 3,
        "moe_intermediate_size": 8, "num_attention_heads": 4,
        "num_dense_layers": 1, "num_experts": 16, "num_experts_per_tok": 2,
        "num_hidden_layers": 12, "num_key_value_heads": 2,
        "rope_parameters": {"factor": 8.0, "rope_type": "yarn",
                            "original_max_position_embeddings": 4096},
        "rope_scaling": None,
        "sparse_config": {"index_head_dim": 4, "index_num_heads": 2, "topk": 64},
        "tie_word_embeddings": False, "vocab_size": 4096}}
CUT = {"num_hidden_layers": 5, "layer_types": ["window"] * 3 + ["full", "window"],
       "num_experts": 8, "vocab_size": 512}
BACKBONE_HEAD = '''
import numpy as np


def make_params(seed, config):
    """Sizes from the top level of the file, under the source's names."""
    d, n = config["hidden_size"], config["num_hidden_layers"]
    assert len(config["layer_types"]) == n and config["rope_scaling"] is None
    assert config["head"]["experts_held"] == [0, config["num_experts"]]
    rng = np.random.default_rng(seed)
    return {"embed": rng.standard_normal((12, d)).astype(np.float32),
            "layers": [rng.standard_normal((d, d)).astype(np.float32) / d
                       for _ in range(n)]}


def forward(params, windows, lengths, rnd):
    h = rnd(windows) @ rnd(params["embed"])
    for w in params["layers"]:
        h = h + np.tanh(rnd(h) @ rnd(w))
    return (1.0 / (1.0 + np.exp(-h.mean((1, 2))))).astype(np.float32)
'''
BACKBONE_COST = '''
def toy_attention(config, batch, *, index_mode):
    """Attention over one window a row: scores and the weighted sum."""
    heads, dim = config["num_attention_heads"], config["head_dim"]
    events = int(config["env"]["SESSION_EVENTS"])
    return {"flops": 4 * batch * heads * events * events * dim,
            "bytes": 4 * 4 * batch * heads * events * dim}
'''
SOURCED = "risk-stateful-5m-sourced"


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def sourced(copy):
    """New files only, beside the untouched copy: the source, a
    configuration with its keys at the top level, a head that reads them
    there, a cost, a per-operation roofline metric and a cell."""
    tmp_path, base = copy, copy / "chipbench"
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    (base / "sources").mkdir(exist_ok=True)  # ``copy`` brings the directory
    _write(base / "sources" / f"{SOURCED}.json", SOURCE)
    (base / "heads" / "toy_backbone.py").write_text(BACKBONE_HEAD)
    (base / "costs" / "toy_attention.py").write_text(BACKBONE_COST)
    cfg = json.loads((base / "configs" / "risk-stateful-5m-pattern.json").read_text())
    cfg.update(SOURCE["config"])
    cfg.update(CUT)
    cfg.update(name=SOURCED, source=SOURCE["source_url"],
               source_keys=sorted(SOURCE["config"]),
               reduced=cfg["reduced"] + sorted(CUT))
    cfg["reduced_why"].update({k: "the chip's share" for k in CUT})
    cfg["head"] = {"reference": "toy_backbone",
                   "published": {k: SOURCE["config"][k] for k in CUT},
                   "deployment": "EP2: 8 of 16 experts, 5 of 12 layers",
                   "experts_held": [0, 8]}
    _write(base / "configs" / f"{SOURCED}.json", cfg)
    metric = {"name": "toy_attention_roofline", "layer": "kernels", "unit": "%",
              "better": "higher", "source": "device_trace", "moves": "txns_per_s",
              "reader": "trace_op_roofline_share", "pattern": "_run_resident",
              "program": "^jit__body\\(", "cost": "toy_attention"}
    _write(base / "layer_metrics" / "toy_attention_roofline.json", metric)
    with open(tmp_path / "BENCHMARK.json") as f:
        m = json.load(f)
    m["configs"].append({"name": SOURCED, "source": cfg["source"],
                         "file": f"chipbench/configs/{SOURCED}.json",
                         "reduced": cfg["reduced"], "why": "a made-up backbone"})
    m["workloads"].append({"name": "sourced-index-flatout", "config": SOURCED,
                           "traffic": "index-flatout", "chips": 1, "why": "w"})
    m["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")}
        | {"workloads": ["sourced-index-flatout"]})
    _write(tmp_path / "BENCHMARK.json", m)
    yield str(tmp_path)
    assert all(p.read_bytes() == b for p, b in before.items()), "a file was patched"


def test_a_configuration_with_its_sources_keys_validates_loads_and_runs(sourced):
    assert validate.check_manifest(sourced) == []
    spec = validate.load_cell("sourced-index-flatout", sourced)
    config = spec["config"]
    assert config["hidden_size"] == 16 and config["sparse_config"]["topk"] == 64
    head = validate.load_code("heads", validate.head_name(config), sourced)
    params = head.make_params(11, config)
    assert len(params["layers"]) == 5 and params["embed"].shape == (12, 16)
    ref = reference.Reference(reference.make_params(11, (16, 16)), head=head,
                              head_params=params, n_events=16)
    base = np.zeros((4, reference.N_FEATURES), np.float32)
    for k in range(5):
        out = ref.score_index(["a", "b", "a", "c"], base, [1000, 2000, 3000, 4000],
                              [2, 0, 2, 1], clock=1_800_000_000.0 + k)
    assert out["warm"].all() and len(set(np.round(out["sprob"], 6))) > 1
    # the per-operation roofline metric, on the step recorded on a v5e: the
    # made-up source has the heads and head size of the kernel that ran
    (m,) = [x for x in spec["per_layer"] if x["name"] == "toy_attention_roofline"]
    step = trace_reduce.load_json(os.path.join(DATA, "recorded_v5e_seqhead_step.json"))
    r = Readings(config=config, rows_ok=1, stages={}, counters={},
                 device_kind="TPU v5 lite", pad_rows={256: 9}, trace=step,
                 trace_window=(0, 2 * 10**7), root=sourced)
    share = READERS[m["reader"]](m, r)
    assert share == pytest.approx(100.0 * (4 * 4 * 256 * 4 * 16 * 8 / 819e9)
                                  / 308.14e-6, rel=1e-3)
    assert 0.0 < share <= 100.0


def _edit(root, fn):
    """Change the sourced configuration, and keep the manifest's copy of
    ``source`` and ``reduced`` in step so that only the breach is left."""
    cfg = _config_at(root, SOURCED, fn)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = next(c for c in m["configs"] if c["name"] == SOURCED)
    entry.update(source=cfg["source"], reduced=cfg["reduced"])
    _write(os.path.join(root, "BENCHMARK.json"), m)


def _name_in_reduced(c, key):
    c["reduced"].append(key)
    c["reduced_why"][key] = "named, to no avail"


def _as_pr_29_wrote_it(c):
    """The source whole under ``head.published``, ``reduced`` naming
    ``head``, a subset of the deployed sizes flat in ``head``."""
    c["head"]["published"] = {k: c.pop(k) for k in c.pop("source_keys")}
    c["head"].update(hidden_size=16, num_experts=8)
    c["reduced"] = [k for k in c["reduced"] if k not in CUT] + ["head"]
    c["reduced_why"]["head"] = "the chip's share"


BREACHES = {
    "source-key-missing": (
        lambda c: (c.pop("head_dim"), c["source_keys"].remove("head_dim")),
        ["head_dim is missing at the top level and its source gives 8"]),
    "null-where-the-source-has-a-number": (
        lambda c: c.update(attn_every_n_layers=None),
        ["attn_every_n_layers is null and its source gives 4"]),
    "nested-group-short-of-a-key": (
        lambda c: c["sparse_config"].pop("topk"),
        ["sparse_config.topk is missing and its source gives 64"]),
    "nested-group-with-a-key-of-its-own": (
        lambda c: c["rope_parameters"].update(beta_fast=32),
        ["rope_parameters.beta_fast is 32 and its source has no such key"]),
    "count-changed-and-not-in-reduced": (
        lambda c: c.update(num_key_value_heads=1),
        ["num_key_value_heads is 1 and its source gives 2: reduced does not name it"]),
    "width-changed-though-reduced-names-it": (
        lambda c: (c.update(hidden_size=8), _name_in_reduced(c, "hidden_size")),
        ["hidden_size is 8 and its source gives 16: a width may not differ"]),
    "width-in-a-group-that-reduced-names": (
        lambda c: (c["sparse_config"].update(topk=32),
                   _name_in_reduced(c, "sparse_config")),
        ["sparse_config.topk is 32 and its source gives 64: a width may not differ"]),
    "false-is-not-zero": (
        lambda c: c.update(tie_word_embeddings=0),
        ["tie_word_embeddings is 0 and its source gives false"]),
    "source-only-under-head-published": (
        _as_pr_29_wrote_it,
        ["attn_every_n_layers is missing at the top level and its source gives 4 "
         "(it is under head.published", "vocab_size is missing at the top level"]),
    "top-level-key-in-neither-set": (
        lambda c: c.update(d_model=16), ["unknown keys ['d_model']"]),
    "source-key-not-listed": (
        lambda c: c["source_keys"].remove("hidden_act"),
        ["unknown keys ['hidden_act']"]),
    "listed-key-the-source-lacks": (
        lambda c: (c.update(kv_lora_rank=4), c["source_keys"].append("kv_lora_rank")),
        ["source_keys names 'kv_lora_rank', which chipbench/sources/"]),
    "listed-key-the-file-lacks": (
        lambda c: c["source_keys"].append("q_lora_rank"),
        ["source_keys names 'q_lora_rank', which is not a top-level key"]),
    "listed-key-of-the-benchmarks-own": (
        lambda c: c["source_keys"].append("trunk"),
        ["source_keys names 'trunk', which is a key of the benchmark's own"]),
    "three-layers": (
        lambda c: c.update(num_hidden_layers=4, layer_types=c["layer_types"][:4]),
        ["num_hidden_layers is 4 and its source gives 12: 3 layers follow"]),
    "layer-list-not-cut-to-the-layers-held": (
        lambda c: c.update(layer_types=c["layer_types"][:4]),
        ["layer_types is [", "4 entries for num_hidden_layers 5"]),
    "four-experts": (
        lambda c: c.update(num_experts=4),
        ["num_experts is 4 and its source gives 16: a cut holds at least 8"]),
    "more-experts-than-the-source": (
        lambda c: c.update(num_experts=32), ["num_experts is 32 and its source gives 16"]),
    "a-sixteenth-of-the-vocabulary": (
        lambda c: c.update(vocab_size=256),
        ["vocab_size is 256 and its source gives 4096: a cut holds at least an eighth"]),
    "source-is-not-the-sources-url": (
        lambda c: c.update(source="Made-Up-8B-A1B as SESSION_HEAD of risk.v1"),
        ["is not its source's source_url 'https://example.org/"]),
}


@pytest.mark.parametrize("case", BREACHES)
def test_each_breach_of_the_source_is_one_line_that_names_the_key(sourced, case):
    edit, needles = BREACHES[case]
    _edit(sourced, edit)
    errors = validate.check_manifest(sourced)
    for needle in needles:
        assert sum(needle in e for e in errors) == 1, (needle, errors)
    if case == "source-only-under-head-published":
        # PR 29's file fails on the first key of its source, and on each
        assert "attn_every_n_layers" in errors[0]
        assert len(errors) == len(SOURCE["config"])
    else:
        assert len(errors) == 1, errors


def test_source_keys_want_their_source_beside_them(sourced):
    os.remove(os.path.join(sourced, "chipbench", "sources", SOURCED + ".json"))
    errors = validate.check_manifest(sourced)
    assert len(errors) == 1 and "source_keys without chipbench/sources/" in errors[0]
    _write(os.path.join(sourced, "chipbench", "sources", SOURCED + ".json"),
           {"name": "x", "config": {}})
    errors = validate.check_manifest(sourced)
    assert len(errors) == 1 and "the source's entry copied whole" in errors[0]


def test_the_counts_are_data_with_the_guides_sentence():
    rules = validate.source_rules()
    counts = {k for keys in rules["counts"].values() for k in keys}
    assert {"num_hidden_layers", "layer_types", "mlp_only_layers", "num_dense_layers",
            "max_window_layers", "num_experts", "num_local_experts",
            "n_routed_experts", "num_attention_heads", "num_key_value_heads",
            "vocab_size"} <= counts
    # no width by the names the builder's instructions give for one
    assert not [k for k in counts if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert "No width is ever cut" in rules["guide"]
    assert rules["floors"]["layers_after_leading_dense"] == 4
    assert rules["floors"]["routed_experts"] == 8
