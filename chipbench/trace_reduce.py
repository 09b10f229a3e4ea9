"""From a profiler trace to numbers: device busy and idle time, time per
program execution, the operations that took most device time, and the
longest idle gaps named by what the host was doing.

The reduction works on a plain ``Trace`` (lists of ``(name, start_ns,
dur_ns)``), so it is checked on a small recorded trace kept as JSON in
``chipbench/tests/data/``. ``load_xplane`` turns the ``.xplane.pb`` the
JAX profiler writes into one.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

import numpy as np

Event = tuple  # (name, start_ns, dur_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclass
class Trace:
    # device ordinal -> leaf operations that ran on it
    device_ops: dict[int, list[Event]] = field(default_factory=dict)
    # device ordinal -> executions of whole jitted programs
    programs: dict[int, list[Event]] = field(default_factory=dict)
    # host annotations (TraceAnnotation), any thread
    host_marks: list[Event] = field(default_factory=list)


def load_json(path: str) -> Trace:
    with open(path, encoding="utf-8") as f:
        raw = json.load(f)
    return Trace(
        device_ops={int(k): [tuple(e) for e in v]
                    for k, v in raw["device_ops"].items()},
        programs={int(k): [tuple(e) for e in v]
                  for k, v in raw["programs"].items()},
        host_marks=[tuple(e) for e in raw.get("host_marks", [])])


def dump_json(trace: Trace, path: str, limit: int | None = None) -> None:
    cut = (lambda v: v[:limit]) if limit else (lambda v: v)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"device_ops": {k: cut(v) for k, v in trace.device_ops.items()},
                   "programs": {k: cut(v) for k, v in trace.programs.items()},
                   "host_marks": cut(trace.host_marks)}, f)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_OPCODE = re.compile(r"[}\])] ([A-Za-z][\w\-]*)\(")


def short_name(name: str, limit: int = 96) -> str:
    """The profiler names a device operation by its whole HLO line; keep
    the result's name, its shape without the layout, and the opcode."""
    if " = " in name:
        lhs, rhs = name.split(" = ", 1)
        op = _OPCODE.search(rhs)
        shape = rhs.split("{", 1)[0].split(" ", 1)[0]
        name = f"{lhs} {shape} {op.group(1) if op else ''}".rstrip()
    return name[:limit]


def load_xplane(path: str, mark_prefix: str = "chipbench") -> tuple[Trace, dict]:
    """The trace, and a summary of what the file held (plane and line names
    with event counts) for the run's log."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace, summary = Trace(), {}
    for plane in data.planes:
        lines = {}
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = [(short_name(e.name), int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            lines[line.name] = len(events)
            if m and line.name == OPS_LINE:
                trace.device_ops[int(m.group(1))] = events
            elif m and line.name == MODULES_LINE:
                trace.programs[int(m.group(1))] = events
            elif not m:
                trace.host_marks.extend(
                    e for e in events if e[0].startswith(mark_prefix))
        summary[plane.name] = lines
    return trace, summary


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def _clip(events: list[Event], lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def busy_seconds(trace: Trace, window: tuple[int, int]) -> float:
    """Seconds in which an operation ran on the device inside ``window``
    (ns): the union of its operation intervals, averaged over the devices
    that ran anything."""
    per_device = []
    for events in trace.device_ops.values():
        merged = _union(_clip(events, *window))
        per_device.append(sum(hi - lo for lo, hi in merged) / 1e9)
    return sum(per_device) / len(per_device) if per_device else 0.0


def program_executions(trace: Trace, pattern: str,
                       window: tuple[int, int]) -> list[Event]:
    """Executions on the first device of programs whose name matches
    ``pattern``, wholly inside ``window``."""
    if not trace.programs:
        return []
    rx = re.compile(pattern)
    events = trace.programs[min(trace.programs)]
    return [e for e in events if rx.search(e[0])
            and e[1] >= window[0] and e[1] + e[2] <= window[1]]


def top_device_ops(trace: Trace, window: tuple[int, int], n: int = 10) -> list:
    """The operations that took most device time: ``[name, seconds]``."""
    total: dict[str, int] = {}
    for events in trace.device_ops.values():
        for name, start, dur in events:
            if start >= window[0] and start + dur <= window[1]:
                total[name] = total.get(name, 0) + dur
    devices = max(1, len(trace.device_ops))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / devices] for name, ns in ranked]


def idle_gaps(trace: Trace, window: tuple[int, int],
              host_spans: list[Event], n: int = 10) -> list:
    """Idle time of the first device inside ``window``, by what the host
    was doing: each gap between busy intervals is split over the host
    spans that overlap it (``host_spans`` on the trace's clock; where
    several overlap, the one that started last owns the instant — the
    innermost stage), and what no span covers is ``(no span)``. Returns
    ``[name, seconds]`` for the ``n`` largest, idle seconds summed per
    name."""
    if not trace.device_ops:
        return []
    events = trace.device_ops[min(trace.device_ops)]
    busy = _union(_clip(events, *window))
    gaps, at = [], window[0]
    for lo, hi in busy:
        if lo > at:
            gaps.append((at, lo))
        at = max(at, hi)
    if window[1] > at:
        gaps.append((at, window[1]))
    total: dict[str, int] = {}
    spans = sorted(host_spans, key=lambda e: e[1])
    starts = np.array([s[1] for s in spans], np.int64)
    ends = starts + np.array([s[2] for s in spans], np.int64)
    for lo, hi in gaps:
        # boundaries inside the gap where the innermost span can change
        cuts = {lo, hi}
        live = [spans[i] for i in np.flatnonzero((starts < hi) & (ends > lo))]
        for _, start, dur in live:
            cuts.update(t for t in (start, start + dur) if lo < t < hi)
        edges = sorted(cuts)
        for a, b in zip(edges[:-1], edges[1:]):
            owner = "(no span)"
            for name, start, dur in live:  # sorted by start: last one wins
                if start <= a and start + dur >= b:
                    owner = name
            total[owner] = total.get(owner, 0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
