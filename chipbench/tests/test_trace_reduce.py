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
        "layer_metrics", "fused_step_roofline")["pattern"]
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
