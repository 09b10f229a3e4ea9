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
    metric = json.loads((base / "layer_metrics" / "fused_step_roofline.json").read_text())
    metric.update(name="toy_step_roofline", cost="toy_step")
    (base / "layer_metrics" / "toy_step_roofline.json").write_text(json.dumps(metric))
    with open(tmp_path / "BENCHMARK.json") as f:
        m = json.load(f)
    m["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": "chipbench/configs/risk-stateful-5m-toy.json",
                         "reduced": cfg["reduced"], "why": "a head of its own"})
    m["workloads"].append({"name": "toy-index-flatout", "config": cfg["name"],
                           "traffic": "index-flatout", "chips": 1, "why": "w"})
    entry = next(x for x in m["per_layer"] if x["name"] == "fused_step_roofline")
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


def _config(root, fn):
    path = os.path.join(root, "chipbench", "configs", "risk-stateful-5m-toy.json")
    with open(path) as f:
        cfg = json.load(f)
    fn(cfg)
    with open(path, "w") as f:
        json.dump(cfg, f)


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
