"""The heartbeat and its stall watch (obs/hostprof.Heartbeat).

A thread that sleeps a fixed 50 ms, counts how late it woke and, while an
``rpc.*`` root stays open past ``STALL_DUMP_MS``, records every thread's
stack, open spans and CPU clock: the incident's kind (who could not run:
a request, or the interpreter), where it goes (the ring at
``/debug/stallz``, a file, a WARNING line, a ``host.stall`` span, four
counters) and what it must never cost (nothing without an incident).
The suite runs with ``STALL_DUMP_MS=0`` (tests/conftest.py); every test
here sets its own.
"""

from __future__ import annotations

import ctypes
import faulthandler
import itertools
import json
import logging
import os
import sys
import threading
import time
import urllib.request

import pytest

from igaming_platform_tpu.obs import hostprof, runtime_telemetry, tracing
from igaming_platform_tpu.obs.metrics import ServiceMetrics


@pytest.fixture
def watch(monkeypatch, tmp_path):
    """``watch(stall_ms)``: a private HostProfiler with its heartbeat
    running at that threshold, its files under ``tmp_path``, on an empty
    thread registry (what the worker's earlier servers registered is set
    aside and put back). Uninstalled afterwards, and no thread of it may
    be left."""
    with hostprof._REGISTRY_LOCK:
        before = dict(hostprof._THREAD_ROLES)
        hostprof._THREAD_ROLES.clear()
    made = []
    others = _heartbeat_threads()  # the process default's, of an earlier file

    def make(stall_ms: float) -> hostprof.HostProfiler:
        monkeypatch.setenv("STALL_DUMP_MS", str(stall_ms))
        # a directory that is not there yet: the first incident makes it
        monkeypatch.setenv("STALL_DUMP_DIR", str(tmp_path / "stalls"))
        hp = hostprof.HostProfiler(enabled=True).install()
        made.append(hp)
        return hp

    try:
        yield make
    finally:
        for hp in made:
            hp.uninstall()
        with hostprof._REGISTRY_LOCK:
            hostprof._THREAD_ROLES.clear()
            hostprof._THREAD_ROLES.update(before)
    assert _heartbeat_threads() <= others


def _heartbeat_threads() -> set:
    return {t for t in threading.enumerate() if t.name == "hostprof-heartbeat"}


def _wait_for(condition, seconds: float = 10.0) -> bool:
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if condition():
            return True
        time.sleep(0.01)
    return bool(condition())


def _hold_the_lock(lock: threading.Lock, taken: threading.Event,
                   seconds: float) -> None:
    with lock:
        taken.set()
        time.sleep(seconds)


def _handler_waiting_for(lock: threading.Lock) -> None:
    """What a gRPC handler thread does when the session lock is taken."""
    hostprof.register_scoring_thread("grpc_handler")
    with tracing.span("rpc.ScoreBatch"):
        tracing.set_root_attribute("rows", 256)
        with tracing.span("score.lock_wait"):
            with lock:
                pass


def _a_held_rpc(seconds: float) -> None:
    with tracing.span("rpc.ScoreBatch"):
        time.sleep(seconds)


def _run(target, *args, name: str) -> threading.Thread:
    thread = threading.Thread(target=target, args=args, name=name)
    thread.start()
    return thread


# -- (a) a request waits for what another thread holds ------------------------


def test_a_held_lock_is_one_waiting_incident_that_names_its_holder(
        watch, tmp_path, caplog):
    hp = watch(100)
    hb = hp.heartbeat
    metrics = ServiceMetrics("risk")
    hp.bind_metrics(metrics)
    seen = []
    sink = seen.append
    tracing.add_span_sink(sink)
    lock, taken = threading.Lock(), threading.Event()
    try:
        with caplog.at_level(logging.WARNING, logger=hostprof.logger.name):
            # the holder is no scoring thread: nothing registered it
            holder = _run(_hold_the_lock, lock, taken, 0.5, name="compactor")
            assert taken.wait(5)
            handler = _run(_handler_waiting_for, lock, name="handler-0")
            handler.join(10)
            holder.join(10)
            # the span is the last thing a closed incident leaves
            assert _wait_for(lambda: any(s.name == "host.stall" for s in seen))
    finally:
        tracing.remove_span_sink(sink)
    snap = hb.snapshot()
    assert snap["open"] is None and len(snap["incidents"]) == 1
    incident = snap["incidents"][0]
    assert incident["kind"] == "waiting" and "blocked" not in incident
    assert incident["threshold_ms"] == 100.0
    (held,) = incident["held"]
    assert held["method"] == "ScoreBatch" and held["rows"] == 256
    assert held["duration_ms"] >= 100.0
    # the blocked thread, by its role, with the spans it is inside of ...
    first = incident["samples"][0]
    by_thread = {t["thread"]: t for t in first["threads"]}
    waiting = by_thread["grpc_handler"]
    assert [s["name"] for s in waiting["spans"]] == [
        "rpc.ScoreBatch", "score.lock_wait"]
    assert waiting["spans"][0]["age_ms"] >= 100.0
    assert waiting["stack"].endswith("test_stall_watch._handler_waiting_for")
    # ... and the holder, which the registry has never heard of
    holder_sample = by_thread["other:compactor"]
    assert "test_stall_watch._hold_the_lock" in holder_sample["stack"]
    assert holder_sample["spans"] == []
    assert waiting["cpu_ms"] is not None  # the thread's own CPU clock
    # nothing moved between ticks: the samples are folded into a count
    ticks_held = sum(s["count"] for s in incident["samples"])
    assert first["count"] >= 2 and "until" in first
    assert len(incident["samples"]) < ticks_held
    assert "cpu_ms_until" in first["threads"][0]
    # the file holds the same, as text
    text = open(incident["file"], encoding="utf-8").read()
    assert os.path.dirname(incident["file"]) == str(tmp_path / "stalls")
    assert text.startswith(f"rpc stall #{incident['id']} kind=waiting")
    assert "other:compactor" in text and "score.lock_wait" in text
    # one WARNING line, with the kind, the trace id and the file
    (line,) = [r.getMessage() for r in caplog.records
               if r.name == hostprof.logger.name]
    assert "kind=waiting" in line and held["trace_id"] in line
    assert incident["file"] in line
    # one host.stall span through the ordinary sinks, on the spans' clock
    (stall,) = [s for s in seen if s.name == "host.stall"]
    assert stall.attributes == {"kind": "waiting",
                                "samples": len(incident["samples"]),
                                "trace_ids": held["trace_id"]}
    root = next(s for s in seen if s.name == "rpc.ScoreBatch")
    assert root.mono_start < stall.mono_start < stall.mono_end
    assert stall.mono_start - root.mono_start == pytest.approx(0.1, abs=0.06)
    assert stall.root is None  # no request: no root sink, no flight entry
    # the counters, brought up to date when the registry renders
    assert metrics.rpc_stalls_total.value(kind="waiting") == 0.0
    rendered = metrics.registry.render_text()
    assert 'risk_rpc_stalls_total{kind="waiting"} 1.0' in rendered
    assert metrics.rpc_stalls_total.value(kind="interpreter_blocked") == 0.0
    past = metrics.rpc_stall_seconds_total.value()
    assert past == pytest.approx(held["duration_ms"] / 1e3 - 0.1, abs=1e-3)
    assert metrics.host_heartbeat_ticks_total.value() >= ticks_held
    assert metrics.host_heartbeat_late_seconds_total.value() >= 0.0
    metrics.registry.render_text()  # nothing grew: nothing is added twice
    assert metrics.rpc_stalls_total.value(kind="waiting") == 1.0
    assert metrics.rpc_stall_seconds_total.value() == past


class _SlowToRead(dict):
    """A dict whose reader is taken off the CPU while it reads: what a
    thread switch does to a render, here every time."""

    def __getitem__(self, key):
        time.sleep(0.02)
        return super().__getitem__(key)


def test_two_renders_at_once_add_the_growth_once():
    """The sidecar serves each ``/metrics`` on a thread of its own and the
    registry runs its refreshers outside its lock: two renders that meet
    in ``flush`` must not both take what has grown."""
    hb = hostprof.Heartbeat(None)  # not started: nothing else counts
    hb.ticks, hb.late_s, hb.stall_seconds = 7, 0.25, 1.5
    hb.stalls.update(waiting=2, interpreter_blocked=1)
    # between reading what was flushed and noting what is flushed now
    hb._flushed = _SlowToRead(hb._flushed)
    metrics = ServiceMetrics("risk")
    go = threading.Barrier(2)

    def render():
        go.wait(5)
        hb.flush(metrics)

    renders = [_run(render, name=f"render-{i}") for i in range(2)]
    for thread in renders:
        thread.join(10)
    assert metrics.host_heartbeat_ticks_total.value() == 7.0
    assert metrics.host_heartbeat_late_seconds_total.value() == 0.25
    assert metrics.rpc_stalls_total.value(kind="waiting") == 2.0
    assert metrics.rpc_stalls_total.value(kind="interpreter_blocked") == 1.0
    assert metrics.rpc_stall_seconds_total.value() == 1.5


# -- (b) nobody can run: the GIL held in native code --------------------------


def _sleep_holding_the_gil() -> None:
    """``PyDLL`` calls keep the GIL: for that long no Python thread runs.
    The block starts with the RPC, so nothing was sampled before it."""
    with tracing.span("rpc.ScoreBatch"):
        ctypes.PyDLL(None).usleep(600_000)
        time.sleep(0.15)  # still open when the late tick looks


def _compute_holding_the_gil() -> None:
    """One call into C that computes and never asks whether another thread
    wants the interpreter, on an RPC that was already held and sampled."""
    t0 = time.perf_counter()
    sum(range(2_000_000))
    per_item = (time.perf_counter() - t0) / 2_000_000
    with tracing.span("rpc.ScoreBatch"):
        time.sleep(0.3)
        sum(range(int(0.6 / per_item)))
        time.sleep(0.15)


@pytest.mark.parametrize("holder", [_sleep_holding_the_gil,
                                    _compute_holding_the_gil])
def test_the_gil_held_in_native_code_is_interpreter_blocked(watch, holder):
    """The kind, the gap and the CPU over it are read off the heartbeat's
    own clocks; the stacks are the ones just after the block (nothing in
    the server reads a thread's frames while no Python thread can run)."""
    hp = watch(100)
    hb = hp.heartbeat
    _run(holder, name="handler-0").join(20)
    assert _wait_for(lambda: hb.snapshot()["incidents"])
    (incident,) = hb.snapshot()["incidents"]
    assert incident["kind"] == "interpreter_blocked"
    assert incident["late_max_ms"] >= 100.0
    blocked = incident["blocked"]
    assert blocked["gap_ms"] >= incident["late_max_ms"]
    (held,) = incident["held"]
    assert held["method"] == "ScoreBatch" and held["duration_ms"] >= 600.0
    assert hb.stalls == {"waiting": 0, "interpreter_blocked": 1}
    # a sample taken after the block names the RPC and where it stands
    assert any(t["spans"] and holder.__name__ in t["stack"]
               for sample in incident["samples"] for t in sample["threads"])
    text = open(incident["file"], encoding="utf-8").read()
    assert "blocked: " in text and "faulthandler" not in text
    if holder is _sleep_holding_the_gil:
        # the process computed nothing over the gap: a call that slept
        assert blocked["process_cpu_ms"] < 0.5 * blocked["gap_ms"]
        assert blocked["reading"].startswith("the GIL held by a call that slept")
        assert incident["ran"] == []
        return
    # somebody computed, and the threads' own CPU clocks say who: the
    # incident was open before the block, so its samples span it
    assert blocked["process_cpu_ms"] >= 100.0
    ran = incident["ran"][0]
    assert ran["thread"] == "other:handler-0" and ran["cpu_ms"] >= 100.0
    assert ran["leaf"].endswith("_compute_holding_the_gil")
    assert f"ran: {json.dumps(ran)}" in text


def test_a_blocked_interpreter_with_no_rpc_held_is_no_incident(watch):
    """A late tick alone is lateness, counted where lateness is counted;
    an incident is an RPC that was held."""
    hp = watch(100)
    hb = hp.heartbeat
    assert _wait_for(lambda: hb.ticks >= 1)
    late_before = hb.late_s
    ctypes.PyDLL(None).usleep(300_000)
    assert _wait_for(lambda: hb.late_s - late_before >= 0.1)
    assert _wait_for(lambda: hb.ticks >= 4)
    snap = hb.snapshot()
    assert snap["incidents"] == [] and snap["open"] is None
    assert sum(snap["stalls"].values()) == 0


# -- (c) the heartbeat's own reading --------------------------------------------


def _spin(stop: threading.Event) -> None:
    while not stop.is_set():  # pure Python: gives the GIL up only when asked
        sum(range(200))


def _mean_late_s(hb, seconds: float) -> float:
    ticks, late = hb.ticks, hb.late_s
    time.sleep(seconds)
    assert hb.ticks > ticks
    return (hb.late_s - late) / (hb.ticks - ticks)


def test_wake_lateness_is_the_wait_for_the_interpreter(watch):
    """A thread in pure Python keeps the GIL for the switch interval after
    another asks for it: the heartbeat's mean lateness rises to about that
    interval, and without such a thread it stays far under it."""
    hb = watch(0).heartbeat
    interval = 0.04
    before = sys.getswitchinterval()
    stop = threading.Event()
    idle = _mean_late_s(hb, 0.5)
    sys.setswitchinterval(interval)
    try:
        spinner = _run(_spin, stop, name="spinner")
        busy = _mean_late_s(hb, 1.0)
    finally:
        stop.set()
        sys.setswitchinterval(before)
    spinner.join(10)
    assert idle < interval / 4
    assert busy > interval / 2
    assert busy > idle + interval / 4
    snap = hb.snapshot()
    assert snap["heartbeat_ms"] == 50.0
    assert snap["wake_late_us"] == pytest.approx(
        hb.late_s / hb.ticks * 1e6, rel=0.5)


# -- (d), (g) what it must not cost -------------------------------------------


@pytest.fixture
def frame_reads(monkeypatch):
    """Counts every ``sys._current_frames()`` call of the process."""
    calls = []
    real = sys._current_frames

    def counted():
        calls.append(threading.current_thread().name)
        return real()

    monkeypatch.setattr(sys, "_current_frames", counted)
    return calls


def test_the_watch_off_opens_no_incident_and_the_heartbeat_still_counts(
        watch, frame_reads, tmp_path, monkeypatch):
    armed = []
    monkeypatch.setattr(faulthandler, "dump_traceback_later",
                        lambda *a, **k: armed.append(a))
    hb = watch(0).heartbeat
    ticks = hb.ticks
    _run(_a_held_rpc, 0.3, name="handler-0").join(10)
    assert _wait_for(lambda: hb.ticks >= ticks + 3)
    snap = hb.snapshot()
    assert snap["threshold_ms"] == 0.0 and snap["running"]
    assert snap["incidents"] == [] and snap["open"] is None
    assert frame_reads == [] and armed == []
    assert os.listdir(tmp_path) == []  # not even the directory


def test_an_rpc_under_the_threshold_never_reads_a_frame(watch, frame_reads):
    hb = watch(200).heartbeat
    ticks = hb.ticks
    for _ in range(12):  # over several ticks, each well under 200 ms
        _run(_a_held_rpc, 0.02, name="handler-0").join(10)
    assert _wait_for(lambda: hb.ticks >= ticks + 4)
    assert frame_reads == []
    assert hb.snapshot()["incidents"] == []
    # and one over it reads them on the heartbeat's thread alone
    _run(_a_held_rpc, 0.4, name="handler-0").join(10)
    assert _wait_for(lambda: hb.snapshot()["incidents"])
    assert frame_reads and set(frame_reads) == {"hostprof-heartbeat"}


# -- (e) bounds ------------------------------------------------------------------


def _rpc_that_keeps_moving(stop: threading.Event) -> None:
    """A held RPC whose innermost span changes faster than the ticks."""
    with tracing.span("rpc.ScoreBatch"):
        for i in itertools.count():
            if stop.is_set():
                return
            with tracing.span(f"score.step{i}"):
                time.sleep(0.001)


def test_an_incident_keeps_forty_samples_and_the_ring_sixteen(
        watch, tmp_path, monkeypatch):
    monkeypatch.setattr(hostprof, "HEARTBEAT_S", 0.004)
    hb = watch(10).heartbeat
    stop = threading.Event()
    mover = _run(_rpc_that_keeps_moving, stop, name="handler-0")
    assert _wait_for(lambda: (hb.snapshot()["open"] or {}).get("samples")
                     and hb._open is not None and hb._open.dropped >= 3)
    open_now = hb.snapshot()["open"]
    assert open_now["trace_ids"] and len(open_now["samples"]) == 40
    stop.set()
    mover.join(10)
    assert _wait_for(lambda: hb.snapshot()["incidents"])
    (incident,) = hb.snapshot()["incidents"]
    assert len(incident["samples"]) == hostprof._STALL_MAX_SAMPLES == 40
    assert incident["samples_dropped"] >= 3
    assert "ticks not sampled" in open(incident["file"]).read()
    for _ in range(hostprof._STALL_RING + 3):
        before = sum(hb.stalls.values())
        _run(_a_held_rpc, 0.03, name="handler-0").join(10)
        assert _wait_for(lambda: sum(hb.stalls.values()) > before)
    snap = hb.snapshot()
    assert sum(snap["stalls"].values()) >= hostprof._STALL_RING + 4
    assert len(snap["incidents"]) == hostprof._STALL_RING == 16
    ids = [i["id"] for i in snap["incidents"]]
    assert ids == sorted(ids) and ids[-1] == sum(snap["stalls"].values())
    # the files go round with the ring
    assert len(os.listdir(tmp_path / "stalls")) == hostprof._STALL_RING
    json.dumps(snap)  # what /debug/stallz sends


# -- (f) nothing left behind ---------------------------------------------------


def test_uninstall_stops_the_thread_and_faulthandlers_timer_is_not_its(
        watch, monkeypatch):
    """``faulthandler`` has ONE timer a process. It belongs to whoever
    started the process (this suite's per-test hang dump, an operator's
    ``-X faulthandler``): the heartbeat neither arms nor cancels it."""
    touched = []
    monkeypatch.setattr(faulthandler, "dump_traceback_later",
                        lambda *a, **k: touched.append("arm"))
    monkeypatch.setattr(faulthandler, "cancel_dump_traceback_later",
                        lambda: touched.append("cancel"))
    hp = watch(100)
    hb = hp.heartbeat
    assert hb.running and _wait_for(lambda: hb.ticks >= 3)
    hp.uninstall()
    assert not hb.running and hb._thread is None
    ticks = hb.ticks
    time.sleep(3 * hostprof.HEARTBEAT_S)
    assert hb.ticks == ticks  # and nothing ticks again
    hp.install()  # the default profiler is rebuilt this way between bench arms
    assert hb.running and _wait_for(lambda: hb.ticks > ticks)
    hp.uninstall()
    assert not hb.running and touched == []


def test_a_disabled_profiler_runs_no_heartbeat(monkeypatch):
    monkeypatch.setenv("STALL_DUMP_MS", "100")
    hp = hostprof.HostProfiler(enabled=False).install()
    assert not hp.heartbeat.running
    assert hp.heartbeat.snapshot()["ticks"] == 0
    hp.uninstall()


@pytest.mark.parametrize("value,seconds", [
    (None, 0.5), ("250", 0.25), ("0", 0.0), ("-5", 0.0), ("soon", 0.5)])
def test_the_threshold_is_read_from_the_environment(monkeypatch, value, seconds):
    if value is None:
        monkeypatch.delenv("STALL_DUMP_MS", raising=False)
    else:
        monkeypatch.setenv("STALL_DUMP_MS", value)
    assert hostprof.Heartbeat(None).stall_s == seconds


# -- the span a stall leaves -----------------------------------------------------


def test_emit_span_is_a_completed_span_that_no_root_sink_sees():
    spans, roots = [], []
    tracing.add_span_sink(spans.append)
    tracing.add_root_sink(roots.append)
    try:
        now = time.perf_counter()
        s = tracing.emit_span("host.stall", now - 2.0, now - 0.5, kind="waiting")
    finally:
        tracing.remove_span_sink(spans.append)
        tracing.remove_root_sink(roots.append)
    assert [x for x in spans if x is s] == [s] and s not in roots
    assert s.duration_ms == pytest.approx(1500.0)
    assert s.end - s.start == pytest.approx(1.5)
    assert abs(s.end - (time.time() - 0.5)) < 5.0  # timing-ok: a wall stamp is about now, not a speed
    assert len(s.trace_id) == 32 and len(s.span_id) == 16 and s.parent_id == ""
    assert s.attributes == {"kind": "waiting"}
    assert tracing.current_span() is None
    assert threading.get_ident() not in tracing.active_spans_by_thread()
    assert s in tracing.DEFAULT_COLLECTOR.recent()


def test_who_ran_tells_a_thread_that_computed_from_one_that_slept():
    def thread(ident, cpu, until=None, stack="a;b"):
        t = {"thread": f"other:t{ident}", "ident": ident, "cpu_ms": cpu,
             "spans": [], "stack": stack}
        if until is not None:
            t["cpu_ms_until"] = until
        return t

    samples = [
        {"threads": [thread(1, 10.0, 10.2), thread(2, 5.0, 105.0, "x;y;z")]},
        {"threads": [thread(1, 10.3), thread(2, 180.0), thread(3, None)]},
    ]
    assert hostprof._who_ran(samples) == [
        {"thread": "other:t2", "cpu_ms": 175.0, "leaf": "z"}]


# -- the operator's pages ------------------------------------------------------------


def test_reading_the_telemetry_page_makes_no_profiler():
    """``/debug/telemetryz`` points at the stall watch; in a process that
    has no profiler the read must not make one (and start its thread)."""
    hostprof._reset_default_for_tests()
    threads = _heartbeat_threads()
    stalls = runtime_telemetry.RuntimeTelemetry().snapshot()["stalls"]
    assert stalls == {"incidents_total": 0, "see": "/debug/stallz"}
    assert hostprof._DEFAULT is None and _heartbeat_threads() == threads


@pytest.fixture(scope="module")
def risk_server(tmp_path_factory):
    from igaming_platform_tpu.core.config import (BatcherConfig,
                                                  RiskServiceConfig,
                                                  ScoringConfig)
    from igaming_platform_tpu.serve.server import RiskServer

    saved = {k: os.environ.get(k) for k in ("STALL_DUMP_MS", "STALL_DUMP_DIR",
                                            "HOSTPROF")}
    os.environ["STALL_DUMP_MS"] = "100"
    os.environ["STALL_DUMP_DIR"] = str(tmp_path_factory.mktemp("stalls"))
    os.environ.pop("HOSTPROF", None)
    hostprof.reinstall_from_env()
    cfg = RiskServiceConfig(
        scoring=ScoringConfig(),
        batcher=BatcherConfig(batch_size=32, max_wait_ms=1),
    )
    server = RiskServer(cfg, grpc_port=0, http_port=0, store_max_accounts=4096)
    try:
        yield server
    finally:
        server.shutdown(grace=5)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        hostprof.reinstall_from_env()


def _get(server, path: str):
    with urllib.request.urlopen(
            f"http://localhost:{server.http_port}{path}", timeout=10) as r:
        return r.read().decode()


def test_stallz_telemetryz_and_metrics_on_a_server(risk_server):
    page = json.loads(_get(risk_server, "/debug/stallz"))
    assert page["running"] and page["threshold_ms"] == 100.0
    before = sum(page["stalls"].values())
    _run(_a_held_rpc, 0.4, name="handler-0").join(10)
    assert _wait_for(lambda: sum(json.loads(_get(
        risk_server, "/debug/stallz"))["stalls"].values()) > before)
    page = json.loads(_get(risk_server, "/debug/stallz"))
    incident = page["incidents"][-1]
    assert incident["held"][0]["method"] == "ScoreBatch"
    assert any(t["thread"] == "other:handler-0" and "_a_held_rpc" in t["stack"]
               for t in incident["samples"][0]["threads"])
    # the compile watcher's count stands beside each sample
    assert incident["samples"][0]["compiles"] == (
        runtime_telemetry.DEFAULT.compile_watcher.compiles_total)
    # the page for "something was slow" names both
    stalls = json.loads(_get(risk_server, "/debug/telemetryz"))["stalls"]
    assert stalls == {"incidents_total": sum(page["stalls"].values()),
                      "see": "/debug/stallz"}
    metrics = _get(risk_server, "/metrics")
    assert "risk_rpc_stalls_total{kind=" in metrics
    assert "# TYPE risk_rpc_stall_seconds_total counter" in metrics
    ticks = [line for line in metrics.splitlines()
             if line.startswith("risk_host_heartbeat_ticks_total ")]
    assert ticks and float(ticks[0].split()[-1]) >= page["ticks"]
    assert "risk_host_heartbeat_late_seconds_total " in metrics
    hostprofz = json.loads(_get(risk_server, "/debug/hostprofz"))
    assert hostprofz["heartbeat"]["threshold_ms"] == 100.0
    assert "incidents" not in hostprofz["heartbeat"]
    assert _wait_for(lambda: any(
        s["name"] == "host.stall"
        for s in json.loads(_get(risk_server, "/debug/spans"))))
