"""What a host span costs, with the server's sinks wired and the ring full.

    JAX_PLATFORMS=cpu python -m tools.span_cost [--repeat 2000]

Wires what ``serve/grpc_server.RiskGrpcService`` wires (the metrics span
sink, the host profiler, runtime telemetry, the flight recorder and the
SLO engine) without booting a server, fills the collector ring, and
times (a) the span skeleton of one index-mode ``ScoreBatch`` RPC of one
chunk (``SKELETON``), over all traces, and (b) one child span: a root
with ``CHILDREN`` leaf spans less the same root with none, over the
children; in a
trace that reads thread CPU and in one that does not
(``tracing.CPU_SAMPLE_EVERY``). Prints one JSON line: microseconds, the
median over ``--repeat`` rounds, and what the two clocks a span reads
cost on this machine, and (c) what the heartbeat pays a tick
(``hostprof.Heartbeat``, 20 a second on its own thread): the stall
watch's look with one RPC in flight and none held, and the process clock
it reads. A tight loop on warm caches: in a serving process a span costs
several times this (PERF.md, PR 38). A CPU stopwatch: the figures go into
PERF.md, never into a test.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

from igaming_platform_tpu.obs import (flight, hostprof, runtime_telemetry, slo,
                                      tracing)
from igaming_platform_tpu.obs.metrics import ServiceMetrics
from igaming_platform_tpu.obs.tracing import span

# (name, children): the spans one index-mode ScoreBatch RPC of one chunk
# opens under its root at the parent of PR 38 (13 with the root).
SKELETON = (
    ("score.admission", ()),
    ("score.decode", ()),
    ("score.blacklist", ()),
    ("score.cache_lookup", ()),
    ("score.dispatch", ("score.session", "score.pad",
                        "score.session", "score.pad")),
    ("score.readback", ()),
    ("score.ledger_note", ()),
    ("score.encode", ()),
)
N_SPANS = 1 + sum(1 + len(c) for _, c in SKELETON)


def wire() -> ServiceMetrics:
    metrics = ServiceMetrics("risk")
    tracing.set_span_sink(metrics.observe_stage_span)
    tracing.DEFAULT_COLLECTOR.on_drop = metrics.spans_dropped_total.inc
    flight.install()
    slo.install(slo.SLOEngine(metrics=metrics))
    runtime_telemetry.install(metrics)
    hostprof.install(metrics)
    return metrics


def one_rpc(rows: int = 256) -> None:
    with span("rpc.ScoreBatch"):
        tracing.set_root_attribute("rows", rows)
        for name, children in SKELETON:
            with span(name, batch=rows):
                for child in children:
                    with span(child, batch=rows):
                        pass


CHILDREN = 12


def _root_us(children: int) -> tuple[float, bool | None]:
    """Microseconds of one root with so many leaf spans, and whether its
    trace read thread CPU (None on a tree without the sampling)."""
    t0 = time.perf_counter()
    with span("rpc.ScoreBatch") as root:
        for _ in range(children):
            with span("score.pad", batch=256):
                pass
    return (time.perf_counter() - t0) * 1e6, getattr(root, "cpu_sampled", None)


def measure(repeat: int) -> dict:
    wire()
    for _ in range(tracing.DEFAULT_COLLECTOR.capacity // N_SPANS + 64):
        one_rpc()  # the ring is full ~2 s after a server boots
    rpc_us = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        one_rpc()
        rpc_us.append((time.perf_counter() - t0) * 1e6)
    roots: dict = {}  # (children, reads CPU) -> samples
    for i in range(4 * repeat):
        us, reads_cpu = _root_us(CHILDREN if i % 2 else 0)
        roots.setdefault((bool(i % 2), reads_cpu), []).append(us)
    out = {
        "spans_per_rpc": N_SPANS,
        "rpc_skeleton_us": round(statistics.median(rpc_us), 2),
        "rpc_skeleton_us_p90": round(statistics.quantiles(rpc_us, n=10)[8], 2),
        "ring_len": len(tracing.DEFAULT_COLLECTOR.drain()),
    }
    for reads_cpu in {k[1] for k in roots}:
        per_child = (statistics.median(roots[True, reads_cpu])
                     - statistics.median(roots[False, reads_cpu])) / CHILDREN
        out["child_span_reading_cpu_us" if reads_cpu else "child_span_us"] = (
            round(per_child, 2))
    for clock in (time.thread_time, time.perf_counter, time.process_time):
        t0 = time.perf_counter()
        for _ in range(20_000):
            clock()
        out[f"{clock.__name__}_call_us"] = round(
            (time.perf_counter() - t0) / 20_000 * 1e6, 3)
    out.update(heartbeat_tick(repeat))
    return out


def heartbeat_tick(repeat: int) -> dict:
    """One tick of a heartbeat that is not running (nothing else calls
    it), with a root open on this thread as a request in flight would be."""
    hb = hostprof.Heartbeat(hostprof.get_default())
    hb.stall_s = 0.5  # the watch on, whatever STALL_DUMP_MS says here
    tick_us = []
    with span("rpc.ScoreBatch"):
        for _ in range(repeat):
            t0 = time.perf_counter()
            hb.tick(t0, t0)
            tick_us.append((time.perf_counter() - t0) * 1e6)
    assert not hb.snapshot()["incidents"]
    return {"heartbeat_tick_us": round(statistics.median(tick_us), 2)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repeat", type=int, default=2000)
    print(json.dumps(measure(ap.parse_args().repeat)))


if __name__ == "__main__":
    main()
