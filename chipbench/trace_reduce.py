"""From a profiler trace to numbers: device busy and idle time, time per
program execution, the operations that took most device time, and the
longest idle gaps named by what the host was doing.

The reduction works on a plain ``Trace`` (lists of ``(name, start_ns,
dur_ns)``), so it is checked on a small recorded trace kept as JSON in
``chipbench/tests/data/``. ``load_xplane`` turns the ``.xplane.pb`` the
JAX profiler writes into one. One operation inside a program is found by
its name or by its ``jax.named_scope`` path (``operation_executions``);
the profiler keeps that path in the operation's metadata, which
``jax.profiler.ProfileData`` does not show, so ``op_scopes`` reads it from
the file's bytes.
"""

from __future__ import annotations

import bisect
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
    # device ordinal -> the named-scope path of each operation, entry for
    # entry beside ``device_ops`` ("" where the profiler kept none)
    op_scopes: dict[int, list[str]] = field(default_factory=dict)


def load_json(path: str) -> Trace:
    with open(path, encoding="utf-8") as f:
        raw = json.load(f)
    return Trace(
        device_ops={int(k): [tuple(e) for e in v]
                    for k, v in raw["device_ops"].items()},
        programs={int(k): [tuple(e) for e in v]
                  for k, v in raw["programs"].items()},
        host_marks=[tuple(e) for e in raw.get("host_marks", [])],
        op_scopes={int(k): v for k, v in raw.get("op_scopes", {}).items()})


def dump_json(trace: Trace, path: str, limit: int | None = None) -> None:
    cut = (lambda v: v[:limit]) if limit else (lambda v: v)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"device_ops": {k: cut(v) for k, v in trace.device_ops.items()},
                   "programs": {k: cut(v) for k, v in trace.programs.items()},
                   "host_marks": cut(trace.host_marks),
                   "op_scopes": {k: cut(v) for k, v in trace.op_scopes.items()}},
                  f)


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


def _varint(buf: memoryview, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf: memoryview):
    """The fields of one protobuf message: ``(number, value)``, a varint
    as an int and a length-delimited field as a view of its bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {at}")


def op_scopes(serialized: bytes) -> dict[str, str]:
    """Full name of each device operation (its HLO line) -> the
    ``op_name`` the compiler kept for it, which is the path of
    ``jax.named_scope`` and jitted-function names it was traced under
    (``jit(_body)/jit(_run_resident)/pallas_call``). The profiler stores it
    as the stat ``tf_op`` of the event's metadata in a device plane
    (XSpace.planes=1; XPlane.name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata.name=2, stats=5; XStat.metadata_id=1, str_value=5,
    ref_value=7; XStatMetadata.name=2)."""
    found: dict[str, str] = {}
    for number, plane in _fields(memoryview(serialized)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number in (4, 5):
                entry = dict(_fields(value))  # a map entry: key 1, value 2
                if number == 4:
                    events.append(entry[2])
                else:
                    stat_names[entry[1]] = bytes(
                        dict(_fields(entry[2])).get(2, b"")).decode()
        if not DEVICE_PLANE.match(name):
            continue
        for event in events:
            op, scope = "", ""
            for number, value in _fields(event):
                if number == 2:
                    op = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        scope = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if op and scope:
                found[op] = scope.rstrip(":")
    return found


def load_xplane(path: str, mark_prefix: str = "chipbench") -> tuple[Trace, dict]:
    """The trace, and a summary of what the file held (plane and line names
    with event counts) for the run's log."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        serialized = f.read()
    data = ProfileData.from_serialized_xspace(serialized)
    scopes = op_scopes(serialized)
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
                trace.op_scopes[int(m.group(1))] = [
                    scopes.get(e.name, "") for e in line.events]
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


def operation_executions(trace: Trace, pattern: str, window: tuple[int, int],
                         program: str | None = None) -> tuple[int, int]:
    """Device time of one operation inside its program: ``(ns, runs)``.

    ``pattern`` is searched in each operation's name and in its
    named-scope path, on the first device. A matching operation belongs to
    the program execution (XLA Modules line, wholly inside ``window``, and
    matching ``program`` where one is given) that it ran inside; ``ns`` is
    the union of their intervals (a loop and the operations of its body
    are both on the line, and may both match) and ``runs`` counts the
    executions of every program that held one, so an operation that runs
    many times an execution is summed and one that a branch skips still
    divides by every execution. ``(0, 0)`` where nothing matches."""
    if not trace.programs or not trace.device_ops:
        return 0, 0
    device = min(trace.programs)
    runs = sorted(program_executions(trace, program or "", window),
                  key=lambda e: e[1])
    starts = [e[1] for e in runs]
    rx = re.compile(pattern)
    scopes = trace.op_scopes.get(device, [])
    inside, holders = [], set()
    for i, (name, start, dur) in enumerate(trace.device_ops.get(device, [])):
        if not (rx.search(name) or (i < len(scopes) and rx.search(scopes[i]))):
            continue
        at = bisect.bisect_right(starts, start) - 1
        if at >= 0 and start + dur <= runs[at][1] + runs[at][2]:
            inside.append((start, start + dur))
            holders.add(runs[at][0])
    return (sum(hi - lo for lo, hi in _union(inside)),
            sum(1 for e in runs if e[0] in holders))


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
