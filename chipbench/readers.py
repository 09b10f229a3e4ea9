"""The generic per-layer readers. A metric file under
``chipbench/layer_metrics/`` names one of them and gives its
parameters; a metric over a new hostprof stage or counter is therefore a
new data file, and only a new *kind* of reading is code.

Every reader takes the metric's file (a dict) and the run's
``Readings`` and returns a number, or ``None`` when it finds nothing to
read: the harness then leaves the metric out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from chipbench import peaks, trace_reduce, validate


@dataclass
class Readings:
    """What one run's window left behind."""
    config: dict
    rows_ok: int                      # rows in acknowledged RPCs
    stages: dict                      # hostprof stage -> total_us over the window
    counters: dict                    # name -> delta over the window
    latency_ms: np.ndarray | None = None  # reply minus due time; inf = failed
    index_mode: bool = True
    device_kind: str = ""
    pad_rows: dict = field(default_factory=dict)  # padded batch -> executions
    trace: trace_reduce.Trace | None = None
    trace_window: tuple[int, int] | None = None   # ns, on the trace's clock
    root: str = validate.ROOT         # where files named by a metric are found


def _counter(r: Readings, name) -> float | None:
    names = [name] if isinstance(name, str) else list(name)
    if any(n not in r.counters for n in names):
        return None
    return float(sum(r.counters[n] for n in names))


def hostprof_us_per_row(m: dict, r: Readings):
    """Host microseconds spent in the named hostprof stages per
    acknowledged row."""
    found = [r.stages[s] for s in m["stages"] if s in r.stages]
    if not found or r.rows_ok <= 0:
        return None
    return sum(found) / r.rows_ok


def counter_ratio(m: dict, r: Readings):
    num, den = _counter(r, m["numerator"]), _counter(r, m["denominator"])
    if num is None or not den:
        return None
    return float(m.get("scale", 1.0)) * num / den


def counter_delta_per(m: dict, r: Readings):
    num, den = _counter(r, m["counter"]), _counter(r, m["per"])
    if num is None or not den:
        return None
    return num / den


def client_latency(m: dict, r: Readings):
    """A percentile of reply time minus the time the RPC was due (when
    the client's previous reply arrived), over every RPC of the window; a
    shed or failed RPC counts as slower than any reply."""
    if r.latency_ms is None or len(r.latency_ms) == 0:
        return None
    value = float(np.percentile(r.latency_ms, m.get("percentile", 95)))
    return value if np.isfinite(value) else None


def trace_device_idle(m: dict, r: Readings):
    if r.trace is None or not r.trace.device_ops:
        return None
    lo, hi = r.trace_window
    busy = trace_reduce.busy_seconds(r.trace, r.trace_window)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))


def _executions(m: dict, r: Readings):
    if r.trace is None or not r.trace.programs:
        return []
    return trace_reduce.program_executions(r.trace, m["pattern"], r.trace_window)


def trace_program_ms(m: dict, r: Readings):
    """Mean device time of one execution of the matching programs."""
    runs = _executions(m, r)
    if not runs:
        return None
    return sum(e[2] for e in runs) / len(runs) / 1e6


def _cost(m: dict, r: Readings):
    name = validate.cost_name(m, r.config)
    return getattr(validate.load_code("costs", name, r.root), name)


def _roofline_share(m: dict, r: Readings, runs: int, measured_ns: float):
    """The least time the chip could take for ``runs`` executions (the
    larger of operations over peak FLOP/s and bytes over peak bytes/s,
    from the file under ``chipbench/costs/`` that the metric names, or that
    the cell's configuration names where the metric leaves it to it), over
    their measured device time. The padded batch of each execution is
    read from its name's row count where the harness recorded how many
    executions each padded shape had; the shares are weighted by those
    counts."""
    if not runs or not r.pad_rows:
        return None
    peak = peaks.peaks_for(r.device_kind)
    cost = _cost(m, r)
    least = 0.0
    for batch, count in r.pad_rows.items():
        c = cost(r.config, int(batch), index_mode=r.index_mode)
        least += count * max(c["flops"] / peak["flops_per_s"],
                             c["bytes"] / peak["bytes_per_s"])
    # the counts cover the whole window, the trace a slice of it
    least *= runs / sum(r.pad_rows.values())
    return 100.0 * least / (measured_ns / 1e9)


def trace_roofline_share(m: dict, r: Readings):
    """A whole program's share of its roofline (``_roofline_share``)."""
    runs = _executions(m, r)
    return _roofline_share(m, r, len(runs), sum(e[2] for e in runs))


def _operation(m: dict, r: Readings) -> tuple[int, int]:
    if r.trace is None:
        return 0, 0
    return trace_reduce.operation_executions(
        r.trace, m["pattern"], r.trace_window, m.get("program"))


def trace_op_ms(m: dict, r: Readings):
    """Mean device time, per execution of the enclosing program, of the
    operations on the XLA Ops line whose name or ``jax.named_scope`` path
    matches ``pattern`` (``program``, optional, anchors the enclosing
    program as ``trace_program_ms``'s pattern does)."""
    ns, runs = _operation(m, r)
    return ns / runs / 1e6 if runs else None


def trace_op_roofline_share(m: dict, r: Readings):
    """One operation's share of its roofline: ``trace_op_ms``'s time
    against the cost the metric names, per padded batch of the enclosing
    program as ``trace_roofline_share`` weighs them."""
    ns, runs = _operation(m, r)
    return _roofline_share(m, r, runs, ns)


def roofline_bound(m: dict, r: Readings) -> str | None:
    """Which of the two bounds sets the roofline: ``bytes`` or ``flops``."""
    if not r.pad_rows:
        return None
    peak = peaks.peaks_for(r.device_kind)
    batch = max(r.pad_rows, key=r.pad_rows.get)
    c = _cost(m, r)(r.config, int(batch), index_mode=r.index_mode)
    return ("flops" if c["flops"] / peak["flops_per_s"]
            > c["bytes"] / peak["bytes_per_s"] else "bytes")


READERS = {
    "hostprof_us_per_row": hostprof_us_per_row,
    "counter_ratio": counter_ratio,
    "counter_delta_per": counter_delta_per,
    "client_latency": client_latency,
    "trace_device_idle": trace_device_idle,
    "trace_program_ms": trace_program_ms,
    "trace_roofline_share": trace_roofline_share,
    "trace_op_ms": trace_op_ms,
    "trace_op_roofline_share": trace_op_roofline_share,
}


def read_all(metrics: list[dict], r: Readings, log) -> dict:
    """Every per-layer metric of a cell through its reader: ``{name:
    {"value", "unit"}}``. A reader that finds nothing leaves its metric
    out, and in a traced run that is said: a traced line that lacks a
    metric its cell owes is refused."""
    per_layer = {}
    for m in metrics:
        value = READERS[m["reader"]](m, r)
        if value is not None:
            per_layer[m["name"]] = {"value": float(value), "unit": m["unit"]}
            if "cost" in m:
                log(f"{m['name']} is bound by {roofline_bound(m, r)}")
        elif r.trace is not None and r.trace.device_ops:
            log(f"MISSING per-layer metric {m['name']}: its reader "
                f"{m['reader']} found nothing to read in this cell")
    return per_layer
