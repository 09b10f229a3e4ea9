"""The trace reduction on a small trace whose numbers are worked by hand
(``data/small_trace.json``), and on a slice recorded on a v5e."""

import os

import pytest

from chipbench import trace_reduce
from chipbench.readers import READERS, Readings

DATA = os.path.join(os.path.dirname(__file__), "data")
WINDOW = (900, 2500)
# Host spans on the trace's clock. Busy intervals are 1000-1400 (two
# overlapping ops) and 2000-2100, so the gaps are 900-1000, 1400-2000 and
# 2100-2500.
SPANS = [("score.dispatch", 900, 150), ("score.readback", 1350, 250),
         ("score.encode", 1500, 100)]


@pytest.fixture(scope="module")
def small():
    return trace_reduce.load_json(os.path.join(DATA, "small_trace.json"))


def test_busy_is_the_union_of_operation_intervals(small):
    assert trace_reduce.busy_seconds(small, WINDOW) == pytest.approx(500e-9)
    # clipped to the window: only 1000-1200 of the first union counts
    assert trace_reduce.busy_seconds(small, (900, 1200)) == pytest.approx(200e-9)


def test_idle_share_and_program_time_through_the_readers(small):
    r = Readings(config={}, rows_ok=1, stages={}, counters={},
                 trace=small, trace_window=WINDOW)
    assert READERS["trace_device_idle"]({}, r) == pytest.approx(
        100.0 * (1 - 500 / 1600))
    m = {"pattern": "jit_.*(step|score|fused).*"}
    assert READERS["trace_program_ms"](m, r) == pytest.approx(400e-6)
    assert READERS["trace_program_ms"]({"pattern": "jit_"}, r) == pytest.approx(250e-6)
    assert READERS["trace_program_ms"]({"pattern": "nothing"}, r) is None


def test_roofline_share_from_costs_and_peaks(small):
    from chipbench import peaks, validate
    config = validate.load_data("configs", "risk-stateful-5m-pattern")
    r = Readings(config=config, rows_ok=1, stages={}, counters={},
                 device_kind="TPU v5 lite", pad_rows={256: 10},
                 trace=small, trace_window=WINDOW)
    m = {"pattern": "fused_session", "cost": "fused_step"}
    c = validate.load_code("costs", "fused_step").fused_step(
        config, 256, index_mode=True)
    p = peaks.peaks_for("TPU v5 lite")
    least = max(c["flops"] / p["flops_per_s"], c["bytes"] / p["bytes_per_s"])
    assert READERS["trace_roofline_share"](m, r) == pytest.approx(
        100.0 * least / 400e-9)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_top_device_ops(small):
    assert trace_reduce.top_device_ops(small, WINDOW, n=2) == [
        ["fusion.2", pytest.approx(300e-9)], ["fusion.1", pytest.approx(200e-9)]]


def test_gaps_are_named_by_the_innermost_host_span(small):
    gaps = dict(trace_reduce.idle_gaps(small, WINDOW, SPANS))
    assert gaps == {"(no span)": pytest.approx(800e-9),
                    "score.dispatch": pytest.approx(100e-9),
                    "score.readback": pytest.approx(100e-9),
                    "score.encode": pytest.approx(100e-9)}
    assert sum(gaps.values()) == pytest.approx((1600 - 500) * 1e-9)


def test_recorded_v5e_slice_reduces():
    """A cut of a trace recorded on the chip: the reduction finds the
    device plane's operations and programs and the numbers hang together."""
    path = os.path.join(DATA, "recorded_v5e_slice.json")
    trace = trace_reduce.load_json(path)
    ops = trace.device_ops[0]
    window = (trace.programs[0][0][1], ops[-1][1] + ops[-1][2])
    busy = trace_reduce.busy_seconds(trace, window)
    assert 0 < busy <= (window[1] - window[0]) / 1e9
    assert busy <= sum(e[2] for e in ops) / 1e9 + 1e-12
    # by the pattern the committed metric files anchor to the fused program
    from chipbench import validate
    pattern = validate.load_data("layer_metrics", "device_step_ms")["pattern"]
    assert pattern == validate.load_data(
        "layer_metrics", "device_step_roofline")["pattern"]
    other = trace_reduce.Trace(programs={0: [("jit_sync(7)", window[0], 10)]})
    assert not trace_reduce.program_executions(other, pattern, window)
    runs = trace_reduce.program_executions(trace, pattern, window)
    # the fused session step at 5,242,880 slots: 28.1 ms an execution
    assert len(runs) == 3
    assert all(28.0e6 < e[2] < 28.2e6 for e in runs)
    # and nearly all of it is two copies of the whole ring
    top = trace_reduce.top_device_ops(trace, window, n=2)
    assert all(name.startswith("%copy.") for name, _ in top)
    assert sum(s for _, s in top) > 0.9 * busy
    gaps = trace_reduce.idle_gaps(trace, window, [])
    assert sum(s for _, s in gaps) == pytest.approx(
        (window[1] - window[0]) / 1e9 - busy)


# -- one operation inside a program -------------------------------------------

FUSED = r"^jit__body\("
XSPACE = '''
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 900000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 200000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(7)" } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput"
    stats { metadata_id: 1 str_value: "jit(step)/experts/dot_general:" } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %q)"
    stats { metadata_id: 3 int64_value: 5 } stats { metadata_id: 1 ref_value: 2 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(step)/router/copy" } }
  stat_metadata { key: 3 value { id: 3 name: "flops" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python3" events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench_mark_lo"
    stats { metadata_id: 1 str_value: "not a device plane" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
'''


@pytest.fixture(scope="module")
def step():
    """Two executions of the fused session step of ``seqhead-index-flatout``
    at 5,242,880 slots, every operation with its named-scope path (my chip
    run, PR 30, seed 3000000001)."""
    return trace_reduce.load_json(os.path.join(DATA, "recorded_v5e_seqhead_step.json"))


def _readings(trace, **kw):
    window = (0, max(s + d for _, s, d in trace.programs[0]) + 1)
    return Readings(config=kw.pop("config", {}), rows_ok=1, stages={},
                    counters={}, trace=trace, trace_window=window, **kw)


def test_the_scope_of_an_operation_is_read_from_the_profilers_file(tmp_path):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    assert trace_reduce.op_scopes(raw) == {
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput":
            "jit(step)/experts/dot_general",
        "%copy.2 = f32[8]{0} copy(f32[8]{0} %q)": "jit(step)/router/copy"}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    trace, summary = trace_reduce.load_xplane(str(path))
    assert trace.device_ops[0] == [("%fusion.1 f32[8] fusion", 1100, 300),
                                   ("%copy.2 f32[8] copy", 1500, 200)]
    assert trace.op_scopes[0] == ["jit(step)/experts/dot_general",
                                  "jit(step)/router/copy"]
    assert summary["/device:TPU:0"] == {"XLA Modules": 1, "XLA Ops": 2}
    r = _readings(trace)
    # by the operation's name, and by the scope it was traced under
    assert READERS["trace_op_ms"]({"pattern": r"^%copy\."}, r) == pytest.approx(200e-6)
    assert READERS["trace_op_ms"]({"pattern": "/experts/"}, r) == pytest.approx(300e-6)
    assert READERS["trace_op_ms"]({"pattern": r"jit\(step\)"}, r) == pytest.approx(500e-6)
    # and through a dump, as the harness keeps its cut
    trace_reduce.dump_json(trace, str(tmp_path / "cut.json"))
    again = trace_reduce.load_json(str(tmp_path / "cut.json"))
    assert again.op_scopes == trace.op_scopes and again.device_ops == trace.device_ops


def test_one_operations_time_on_the_recorded_step(step):
    r = _readings(step)
    assert len(trace_reduce.program_executions(step, FUSED, r.trace_window)) == 2
    whole = READERS["trace_program_ms"]({"pattern": FUSED}, r)
    assert whole == pytest.approx(1.367, abs=0.002)
    # the flash kernel of the transformer head: one custom call a step
    flash = READERS["trace_op_ms"]({"pattern": r"^%_run_resident", "program": FUSED}, r)
    assert flash == pytest.approx(0.308, abs=0.002)
    assert READERS["trace_op_ms"]({"pattern": r"jit\(_run_resident\)/pallas_call"},
                                  r) == flash
    # a loop and the operations of its body are both on the line: the
    # union of what matches, so nothing is counted twice
    gather = READERS["trace_op_ms"]({"pattern": r"^jit\(_body\)/gather$"}, r)
    loop = READERS["trace_op_ms"]({"pattern": r"^%while\.6 "}, r)
    both = READERS["trace_op_ms"](
        {"pattern": r"^%while\.6 |^jit\(_body\)/gather$"}, r)
    assert 0.29 < loop < 0.30 and loop < both < loop + 0.04 < loop + gather
    scatter = READERS["trace_op_ms"]({"pattern": r"^%while\.7 "}, r)
    assert flash + loop + scatter < whole
    # anchored to another program, or an operation that is not there
    assert READERS["trace_op_ms"]({"pattern": "_run_resident", "program": "^jit_sync"}, r) is None
    assert READERS["trace_op_ms"]({"pattern": "ragged-dot"}, r) is None
    untraced = Readings(config={}, rows_ok=1, stages={}, counters={})
    assert READERS["trace_op_ms"]({"pattern": "_run_resident"}, untraced) is None


def test_one_operations_roofline_share_on_the_recorded_step(step):
    from chipbench import peaks, validate
    from chipbench.readers import read_all
    config = validate.load_data("configs", "risk-stateful-5m-seqhead")
    r = _readings(step, config=config, device_kind="TPU v5 lite",
                  pad_rows={256: 70, 2048: 0})
    m = {"name": "flash_roofline", "unit": "%", "reader": "trace_op_roofline_share",
         "pattern": "_run_resident", "program": FUSED, "cost": "fused_step"}
    c = validate.load_code("costs", "fused_step").fused_step(config, 256, index_mode=True)
    p = peaks.peaks_for("TPU v5 lite")
    least = max(c["flops"] / p["flops_per_s"], c["bytes"] / p["bytes_per_s"])
    ms = READERS["trace_op_ms"](m, r)
    share = READERS["trace_op_roofline_share"](m, r)
    assert share == pytest.approx(100.0 * least / (ms / 1e3))
    assert 0.0 < share <= 100.0, "operations or bytes counted too high"
    # no padded batches recorded, or no such operation: nothing, never 0
    absent = dict(m, name="grouped_roofline", pattern="ragged-dot")
    assert READERS["trace_op_roofline_share"](absent, r) is None
    r.pad_rows = {}
    assert READERS["trace_op_roofline_share"](m, r) is None
    r.pad_rows = {256: 70}
    said = []
    out = read_all([m, absent, dict(absent, name="grouped_ms", reader="trace_op_ms")],
                   r, said.append)
    assert list(out) == ["flash_roofline"] and out["flash_roofline"]["unit"] == "%"
    assert said[0] == "flash_roofline is bound by bytes"
    assert [s.split(":")[0] for s in said[1:]] == [
        "MISSING per-layer metric grouped_roofline",
        "MISSING per-layer metric grouped_ms"]


# -- PR 70: the step's time and roofline share, one metric each ----------------

@pytest.mark.parametrize("file,config,step_ms,runs", [
    ("small_trace.json", "risk-stateful-5m-pattern", None, 0),
    ("recorded_v5e_slice.json", "risk-stateful-5m-pattern", 28.1, 3),
    ("recorded_v5e_seqhead_step.json", "risk-stateful-5m-seqhead", 1.367, 2),
], ids=["hand-built", "slice", "seqhead-step"])
def test_the_two_step_metrics_on_each_recorded_trace(file, config, step_ms, runs):
    """``device_step_ms`` and ``device_step_roofline`` through their files
    over every trace kept here: the figures the cases above hold, and for
    the share what a metric that names the configuration's cost file
    itself (as each retired ``<family>_step_roofline`` did) reads."""
    from chipbench import peaks, validate
    from chipbench.readers import read_all
    files = [validate.load_data("layer_metrics", name)
             for name in ("device_step_ms", "device_step_roofline")]
    cfg = validate.load_data("configs", config)
    trace = trace_reduce.load_json(os.path.join(DATA, file))
    r = _readings(trace, config=cfg, device_kind="TPU v5 lite",
                  pad_rows={256: 9})
    said = []
    got = read_all(files, r, said.append)
    if step_ms is None:
        # no program of the step's name ran: nothing, never 0
        assert got == {} and len(said) == 2 and all("MISSING" in s for s in said)
        return
    assert len(trace_reduce.program_executions(
        trace, files[0]["pattern"], r.trace_window)) == runs
    assert got["device_step_ms"] == {
        "value": pytest.approx(step_ms, abs=0.002 * step_ms), "unit": "ms"}
    name = cfg["step_cost"]
    c = getattr(validate.load_code("costs", name), name)(cfg, 256, index_mode=True)
    p = peaks.peaks_for("TPU v5 lite")
    least = max(c["flops"] / p["flops_per_s"], c["bytes"] / p["bytes_per_s"])
    share = got["device_step_roofline"]["value"]
    assert share == pytest.approx(
        100.0 * least / (got["device_step_ms"]["value"] / 1e3))
    assert 0.0 < share <= 100.0
    clone = dict(files[1], name="fused_step_roofline", cost=name)
    assert READERS["trace_roofline_share"](clone, r) == share
    assert said == ["device_step_roofline is bound by bytes"]
