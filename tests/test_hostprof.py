"""Host-plane cost observatory (obs/hostprof.py).

Tier A: per-stage µs/row accounting off the tracing span sink, the GC
watch with in-flight-RPC attribution, and heap gauges. Tier B: the
registry-gated stack sampler with folded-stack / speedscope export.
Plus the serving surfaces: /debug/hostprofz GET formats and POST
sampler control on a full RiskServer, the flight recorder's host_cost
join, and the fleetview host-stage rollup."""

from __future__ import annotations

import gc
import json
import threading
import time
import urllib.error
import urllib.request
import weakref

import pytest

from igaming_platform_tpu.obs import hostprof, tracing
from igaming_platform_tpu.obs.fleetview import fleet_host_stage_block
from igaming_platform_tpu.obs.flight import FlightRecorder


@pytest.fixture()
def profiler():
    """A private HostProfiler riding the real tracing sink list, on an
    empty thread registry (what earlier files' servers registered in this
    worker is set aside and put back); uninstalled afterward so no sink
    or registry entry leaks into other tests."""
    with hostprof._REGISTRY_LOCK:
        before = dict(hostprof._THREAD_ROLES)
        hostprof._THREAD_ROLES.clear()
    hp = hostprof.HostProfiler(enabled=True).install()
    try:
        yield hp
    finally:
        hp.uninstall()
        with hostprof._REGISTRY_LOCK:
            hostprof._THREAD_ROLES.clear()
            hostprof._THREAD_ROLES.update(before)


class _FakeHist:
    def __init__(self):
        self.calls = []

    def observe(self, value, **labels):
        self.calls.append((value, labels))

    def observe_key(self, key, value, exemplar=None):
        self.observe(value, exemplar=exemplar, **dict(key))


class _FakeCounter(_FakeHist):
    def inc(self, **labels):
        self.calls.append(labels)


class _FakeMetrics:
    def __init__(self):
        self.host_stage_us_per_row = _FakeHist()
        self.gc_collections_total = _FakeCounter()
        self.gc_pause_ms = _FakeHist()


# ---------------------------------------------------------------------------
# Tier A: stage accounting


def test_stage_accounting_us_per_row(profiler):
    metrics = _FakeMetrics()
    profiler.bind_metrics(metrics)
    with tracing.span("rpc.ScoreBatch"):
        tracing.set_root_attribute("rows", 256)
        with tracing.span("score.decode") as dsp:
            dsp.attributes["batch"] = 256
        with tracing.span("score.session") as ssp:
            ssp.attributes["batch"] = 256
        # A stage span WITHOUT a batch stamp still accumulates wall
        # time, it just contributes no per-row sample.
        with tracing.span("score.encode"):
            pass
    snap = profiler.snapshot()
    stages = snap["stages"]
    assert set(stages) >= {"decode", "session", "encode"}
    for stage in ("decode", "session"):
        row = stages[stage]
        assert row["spans"] == 1 and row["rows"] == 256
        dist = row["us_per_row"]
        assert dist is not None and dist["mean"] > 0
        assert dist["p50"] <= dist["p99"] or dist["p50"] == dist["p99"]
    assert stages["encode"]["rows"] == 0
    assert stages["encode"]["us_per_row"] is None
    # The rpc.* root folded into the per-RPC block with its rows stamp.
    assert snap["rpc"]["rpcs"] == 1 and snap["rpc"]["rows"] == 256
    assert snap["rpc"]["us_per_row"]["mean"] > 0
    # Metric emission: one observation per row-stamped stage, with the
    # bounded stage label and a trace-id exemplar.
    stamped = {c[1]["stage"] for c in metrics.host_stage_us_per_row.calls}
    assert stamped == {"decode", "session"}
    assert all(c[1]["exemplar"] for c in metrics.host_stage_us_per_row.calls)


def test_disabled_profiler_installs_nothing():
    hp = hostprof.HostProfiler(enabled=False).install()
    try:
        with tracing.span("rpc.ScoreBatch"):
            with tracing.span("score.decode") as dsp:
                dsp.attributes["batch"] = 8
        assert hp.snapshot()["stages"] == {}
        assert hp.snapshot()["rpc"]["rpcs"] == 0
    finally:
        hp.uninstall()


def test_handler_thread_autoregisters_on_rpc_root(profiler):
    ident = threading.get_ident()
    hostprof.unregister_scoring_thread(ident)
    with tracing.span("rpc.ScoreTransaction"):
        pass
    try:
        assert hostprof.registered_threads().get(ident) == "grpc_handler"
    finally:
        hostprof.unregister_scoring_thread(ident)


def test_reset_zeroes_accounting(profiler):
    with tracing.span("rpc.ScoreBatch"):
        with tracing.span("score.pad") as sp:
            sp.attributes["batch"] = 16
    assert profiler.snapshot()["stages"]
    profiler.reset()
    snap = profiler.snapshot()
    assert snap["stages"] == {} and snap["rpc"]["rpcs"] == 0
    assert snap["sampler"]["samples_total"] == 0


def _burn(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.fixture
def every_trace_reads_cpu(monkeypatch):
    monkeypatch.setattr(tracing, "CPU_SAMPLE_EVERY", 1)


def test_sampled_thread_cpu_stands_for_every_trace(profiler):
    """Thread CPU is read on one trace in seven and counted seven-fold:
    over 70 like requests the stage's ``cpu_us`` is the CPU of all 70."""
    for _ in range(70):
        with tracing.span("rpc.ScoreBatch"):
            with tracing.span("score.session", batch=8):
                _burn(0.0005)
    row = profiler.snapshot()["stages"]["session"]
    assert row["spans"] == 70
    assert row["cpu_us"] == pytest.approx(70 * 500, rel=0.2)
    assert row["cpu_us"] <= row["self_us"] * 1.1


def test_exclusive_wall_and_cpu_per_stage(profiler, every_trace_reads_cpu):
    """``self_us`` is a stage's duration minus its same-thread child
    spans, ``cpu_us`` its thread CPU likewise; a ``<stage>.self`` row
    (its ``total_us`` the exclusive wall, for readers that take rows by
    name) appears only for a stage that has had a child."""
    with tracing.span("rpc.ScoreBatch"):
        for _ in range(3):
            with tracing.span("score.dispatch", batch=64):
                with tracing.span("score.session", batch=64):
                    _burn(0.002)
                with tracing.span("score.launch", batch=64):
                    with tracing.span("score.inner", batch=64):
                        _burn(0.001)
                _burn(0.001)
            with tracing.span("score.device_wait", batch=64):
                time.sleep(0.02)
    stages = profiler.snapshot()["stages"]
    assert set(stages) == {"dispatch", "dispatch.self", "session", "launch",
                           "launch.self", "inner", "device_wait"}
    d, own = stages["dispatch"], stages["dispatch.self"]
    assert d["spans"] == own["spans"] == 3 and own["rows"] == 192
    # the envelope tiles: its own code + its children (a grandchild left
    # with its parent, once)
    tiled = d["self_us"] + stages["session"]["total_us"] + stages["launch"]["total_us"]
    assert tiled == pytest.approx(d["total_us"], rel=1e-3, abs=1.0)
    assert own["total_us"] == d["self_us"] and own["self_us"] == d["self_us"]
    assert own["us_per_row"]["mean"] == pytest.approx(d["self_us"] / 192, rel=1e-3)
    assert stages["launch.self"]["total_us"] == pytest.approx(
        stages["launch"]["total_us"] - stages["inner"]["total_us"], abs=1.0)
    # a leaf's exclusive time is all of it
    for leaf in ("session", "inner", "device_wait"):
        assert stages[leaf]["self_us"] == pytest.approx(
            stages[leaf]["total_us"], abs=0.2)
    for row in stages.values():
        assert 0.0 <= row["cpu_us"] <= row["self_us"] * 1.05 + 100.0
    assert stages["session"]["cpu_us"] >= 3 * 2000 * 0.95
    # asleep, the thread was on no CPU: the span's two clocks, compared
    assert stages["device_wait"]["cpu_us"] < 0.2 * stages["device_wait"]["self_us"]
    assert d["self_us"] >= 3 * 1000 * 0.95


def test_exclusive_seconds_reach_metrics_at_render_not_per_span(
        profiler, every_trace_reads_cpu):
    """The two /metrics counters are brought up to date by the registry's
    refresher: a span adds to the profiler's accumulators only, and a
    render adds what has grown since the last one, once."""
    from igaming_platform_tpu.obs.metrics import ServiceMetrics

    m = ServiceMetrics("risk")
    profiler.bind_metrics(m)
    with tracing.span("rpc.ScoreBatch"):
        with tracing.span("score.dispatch", batch=8):
            with tracing.span("score.launch", batch=8):
                _burn(0.002)
    assert m.host_stage_self_seconds_total.value(stage="launch") == 0.0
    text = m.registry.render_text()
    self_s = m.host_stage_self_seconds_total.value(stage="launch")
    cpu_s = m.host_stage_cpu_seconds_total.value(stage="launch")
    assert self_s >= 0.0019 and 0.0019 <= cpu_s <= self_s * 1.05 + 1e-4
    assert 'risk_host_stage_self_seconds_total{stage="dispatch"}' in text
    assert "dispatch.self" not in text  # a row of the table, not a label
    m.registry.render_text()  # nothing grew: nothing is added twice
    assert m.host_stage_self_seconds_total.value(stage="launch") == self_s
    rows = profiler.snapshot()["stages"]
    assert self_s == pytest.approx(rows["launch"]["self_us"] / 1e6, abs=1e-6)
    # a profiler bound elsewhere no longer feeds this registry
    profiler.bind_metrics(ServiceMetrics("risk"))
    with tracing.span("score.launch", batch=8):
        pass
    m.registry.render_text()
    assert m.host_stage_self_seconds_total.value(stage="launch") == self_s


@pytest.mark.parametrize("kernel", ["bare", "sandboxed", "no_proc"])
def test_who_else_had_the_cpu_reads_what_the_kernel_keeps(tmp_path, kernel):
    """``read_host_cpu`` on a bare kernel (a ``cpu`` line, a schedstat a
    thread), on a sandboxed one (the line all zeros, no schedstat: the
    machines the chips sit in) and with no ``/proc``: what cannot be read
    stays 0, so a share over it has no denominator and reads nothing;
    only the process's CPU seconds fall back, to the same quantity from
    ``time.process_time``."""
    import os

    from igaming_platform_tpu.obs.metrics import ServiceMetrics
    from igaming_platform_tpu.obs.runtime_telemetry import (
        RuntimeTelemetry, read_host_cpu)

    hz = os.sysconf("SC_CLK_TCK")
    if kernel == "bare":
        (tmp_path / "stat").write_text(
            f"cpu  {30 * hz} 0 {10 * hz} {50 * hz} 0 0 0 {10 * hz} 0 0\ncpu0 1 2\n")
        for tid, (on, wait) in {"7": (4_000_000_000, 500_000_000),
                                "8": (2_000_000_000, 250_000_000)}.items():
            d = tmp_path / "self" / "task" / tid
            d.mkdir(parents=True)
            (d / "schedstat").write_text(f"{on} {wait} 12\n")
    elif kernel == "sandboxed":
        (tmp_path / "stat").write_text("cpu  0 0 0 0 0 0 0 0 0 0\n")
        (tmp_path / "self" / "task" / "7").mkdir(parents=True)
    got = read_host_cpu(str(tmp_path))
    if kernel == "bare":
        assert got == {"steal": 10.0, "all": 100.0, "oncpu": 6.0, "runqueue": 0.75}
    else:
        assert (got["steal"], got["all"], got["runqueue"]) == (0.0, 0.0, 0.0)
        assert 0.0 < got["oncpu"] <= time.process_time()
    # the live reading, folded into the counters at a render: each adds
    # its growth, and none ever falls
    m = ServiceMetrics("risk")
    t = RuntimeTelemetry(m)
    t.refresh_host_counters()
    first = m.process_cpu_seconds_total.value()
    _burn(0.002)
    t.refresh_host_counters()
    assert m.process_cpu_seconds_total.value() > first > 0.0
    assert m.host_cpu_steal_seconds_total.value() <= m.host_cpu_seconds_total.value()


# ---------------------------------------------------------------------------
# Tier A: GC watch + heap


def test_gc_pause_attributed_to_inflight_rpc(profiler):
    metrics = _FakeMetrics()
    profiler.bind_metrics(metrics)
    with tracing.span("rpc.ScoreBatch"):
        gc.collect()
    snap = profiler.gc_snapshot()
    assert sum(int(v) for v in snap["collections"].values()) >= 1
    assert snap["pause_ms_total"]
    # The collection ran with an rpc.* root active on this thread, so
    # the pause attributes to at least one in-flight RPC.
    assert snap["pauses_in_rpc"] >= 1
    assert snap["pause_in_rpc_ms"] >= 0.0
    hit = [p for p in snap["recent_pauses"] if p["inflight_rpcs"] >= 1]
    assert hit and hit[-1]["trace_ids"]
    assert metrics.gc_collections_total.calls
    assert metrics.gc_pause_ms.calls


def test_gc_hook_takes_no_lock(profiler):
    """A collection can start on a thread that already holds the
    profiler's lock (any allocation under it): the hook must queue the
    pause, not wait for a lock its own thread holds."""
    with profiler._lock:
        gc.collect()
    assert sum(profiler.gc_snapshot()["collections"].values()) >= 1


def test_heap_block_gauges(profiler):
    heap = profiler.snapshot()["heap"]
    assert heap["allocated_blocks"] > 0
    assert len(heap["gc_counts"]) == 3 and len(heap["gc_thresholds"]) == 3


# ---------------------------------------------------------------------------
# Tier B: the sampler


def _busy_worker(stop: threading.Event, ready: threading.Event):
    hostprof.register_scoring_thread("stage_worker")
    with tracing.span("score.busywork"):
        ready.set()
        x = 0
        while not stop.is_set():
            x += 1
        return x


def test_sampler_folds_registered_thread_by_active_span(profiler):
    stop, ready = threading.Event(), threading.Event()
    worker = threading.Thread(target=_busy_worker, args=(stop, ready),
                              daemon=True)
    worker.start()
    assert ready.wait(5.0)
    try:
        assert profiler.sampler.start(hz=250.0)
        # A second start while running is refused (the 409 contract).
        assert not profiler.sampler.start(hz=250.0)
        time.sleep(0.35)
        summary = profiler.sampler.stop()
    finally:
        stop.set()
        worker.join(timeout=60.0)
        hostprof.unregister_scoring_thread(worker.ident)
    assert summary["samples_total"] > 0
    assert "stage_worker" in summary["roles_seen"]
    assert summary["last_duration_s"] > 0
    folded = profiler.sampler.folded()
    ours = {k: v for k, v in folded.items()
            if k.startswith("stage_worker;span:score.busywork;")}
    assert ours, f"no folded stacks keyed by the active span: {list(folded)[:5]}"
    # Root-first frames: the leaf is the busy loop's function.
    assert any("_busy_worker" in k for k in ours)
    # Folded text round-trips as `stack count` lines.
    lines = profiler.sampler.to_folded_text().splitlines()
    assert lines and all(" " in ln and ln.rsplit(" ", 1)[1].isdigit()
                         for ln in lines)


def test_speedscope_export_shape(profiler):
    stop, ready = threading.Event(), threading.Event()
    worker = threading.Thread(target=_busy_worker, args=(stop, ready),
                              daemon=True)
    worker.start()
    assert ready.wait(5.0)
    try:
        profiler.sampler.start(hz=250.0)
        time.sleep(0.2)
        profiler.sampler.stop()
    finally:
        stop.set()
        worker.join(timeout=60.0)
        hostprof.unregister_scoring_thread(worker.ident)
    prof = profiler.sampler.to_speedscope()
    assert prof["$schema"].startswith("https://www.speedscope.app")
    frames = prof["shared"]["frames"]
    p = prof["profiles"][0]
    assert p["type"] == "sampled"
    assert len(p["samples"]) == len(p["weights"]) > 0
    assert sum(p["weights"]) == p["endValue"]
    for sample in p["samples"]:
        assert all(0 <= idx < len(frames) for idx in sample)


def test_sampler_never_touches_unregistered_threads(profiler):
    stop, ready = threading.Event(), threading.Event()

    def anonymous():
        with tracing.span("score.anon"):
            ready.set()
            while not stop.is_set():
                pass

    worker = threading.Thread(target=anonymous, daemon=True)
    worker.start()
    assert ready.wait(5.0)
    try:
        profiler.sampler.start(hz=250.0)
        time.sleep(0.2)
        profiler.sampler.stop()
    finally:
        stop.set()
        worker.join(timeout=60.0)
    assert not any("span:score.anon" in k
                   for k in profiler.sampler.folded())


def test_registration_ends_with_its_thread_not_with_its_ident(profiler):
    """The OS hands an ident out again once its thread has ended: a
    registration must not pass to the stranger that gets it next."""
    gone = threading.Thread(target=hostprof.register_scoring_thread,
                            args=("grpc_handler",))
    gone.start()
    gone.join(timeout=60.0)
    ident = gone.ident
    with hostprof._REGISTRY_LOCK:
        assert ident in hostprof._THREAD_ROLES  # nobody dropped it yet
    # the stranger: a live thread that holds the ended thread's ident
    stop, ready = threading.Event(), threading.Event()

    def anonymous():
        with tracing.span("score.reused"):
            ready.set()
            stop.wait(60.0)

    stranger = threading.Thread(target=anonymous, daemon=True)
    stranger.start()
    assert ready.wait(5.0)
    try:
        # whether or not the OS really re-used the ident, put the stale
        # entry under the stranger's: that is the state a re-use leaves
        with hostprof._REGISTRY_LOCK:
            hostprof._THREAD_ROLES[stranger.ident] = (
                hostprof._THREAD_ROLES.pop(ident))
        assert stranger.ident not in hostprof.registered_threads()
        profiler.sampler.start(hz=250.0)
        time.sleep(0.1)
        profiler.sampler.stop()
    finally:
        stop.set()
        stranger.join(timeout=60.0)
    assert not any("span:score.reused" in k for k in profiler.sampler.folded())
    # and an RPC served on the re-used ident registers its own thread anew
    with hostprof._REGISTRY_LOCK:
        hostprof._THREAD_ROLES[threading.get_ident()] = ("grpc_handler",
                                                         weakref.ref(gone))
    with tracing.span("rpc.ScoreBatch"):
        pass
    assert threading.get_ident() in hostprof.registered_threads()


# ---------------------------------------------------------------------------
# Flight recorder host_cost join


def test_flight_entry_carries_host_cost_join():
    rec = FlightRecorder(capacity=8)
    with tracing.span("rpc.ScoreBatch") as root:
        tracing.set_root_attribute("rows", 128)
        with tracing.span("score.decode") as dsp:
            dsp.attributes["batch"] = 128
            _burn(0.001)  # an empty span is ~1 us: under the entry's rounding
        with tracing.span("score.dispatch"):
            pass
    rec.record_root_span(root)
    entry = rec.snapshot()[-1]
    hc = entry["host_cost"]
    assert hc["rows"] == 128
    assert set(hc["stage_us"]) == {"score.decode", "score.dispatch"}
    assert hc["us_per_row"] is not None
    assert hc["us_per_row"]["score.decode"] == pytest.approx(
        hc["stage_us"]["score.decode"] / 128, rel=0.01)
    # Without a rows stamp the join degrades to totals-only.
    with tracing.span("rpc.ScoreBatch") as bare:
        with tracing.span("score.decode"):
            pass
    rec.record_root_span(bare)
    hc = rec.snapshot()[-1]["host_cost"]
    assert hc["rows"] is None and hc["us_per_row"] is None


# ---------------------------------------------------------------------------
# Fleetview rollup


def test_fleet_host_stage_block_merges_exactly():
    a = {"stages": {
        "decode": {"spans": 10, "rows": 1000, "total_us": 2000.0},
        "session": {"spans": 10, "rows": 1000, "total_us": 8000.0},
    }}
    b = {"stages": {
        "decode": {"spans": 30, "rows": 3000, "total_us": 3000.0},
    }}
    block = fleet_host_stage_block([("r0", a), ("r1", b), ("r2", None),
                                    ("r3", {"bogus": 1})])
    assert block["replicas_reporting"] == 2
    dec = block["stages"]["decode"]
    assert dec["spans"] == 40 and dec["rows"] == 4000
    # Fleet mean is total µs over total rows — 5000/4000, not the
    # average of per-replica means (2.0 and 1.0).
    assert dec["us_per_row_mean"] == pytest.approx(1.25)
    assert block["hottest_stage"] == "session"
    assert block["per_replica_hottest"] == {"r0": "session", "r1": "decode"}
    empty = fleet_host_stage_block([])
    assert empty["replicas_reporting"] == 0 and empty["hottest_stage"] is None


# ---------------------------------------------------------------------------
# /debug/hostprofz on a full RiskServer


@pytest.fixture(scope="module")
def risk_server():
    import os

    from igaming_platform_tpu.core.config import (BatcherConfig,
                                                  RiskServiceConfig,
                                                  ScoringConfig)
    from igaming_platform_tpu.serve.server import RiskServer

    saved = {k: os.environ.get(k) for k in ("HOSTPROF", "HOSTPROF_HZ")}
    os.environ.pop("HOSTPROF", None)
    os.environ.pop("HOSTPROF_HZ", None)
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


def _post(base: str, path: str, payload: dict) -> tuple[int, dict]:
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(), method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"{}")


def test_hostprofz_endpoint_formats_and_sampler_control(risk_server):
    from igaming_platform_tpu.serve.scorer import ScoreRequest

    base = f"http://localhost:{risk_server.http_port}"
    risk_server.engine.score_batch(
        [ScoreRequest(account_id=f"hp-{i}", amount=1000 + 7 * i)
         for i in range(64)])
    with urllib.request.urlopen(f"{base}/debug/hostprofz", timeout=10) as r:
        snap = json.load(r)
    assert snap["enabled"] is True
    assert set(snap) >= {"stages", "rpc", "gc", "heap", "sampler"}
    # Sampler control: start -> busy 409 -> stop -> reset -> 400.
    code, body = _post(base, "/debug/hostprofz",
                       {"action": "start", "hz": 199})
    assert code == 200 and body["ok"] and body["sampler"]["running"]
    code, body = _post(base, "/debug/hostprofz",
                       {"action": "start", "hz": 199})
    assert code == 409 and "sampler" in body
    risk_server.engine.score_batch(
        [ScoreRequest(account_id=f"hp2-{i}", amount=500 + 3 * i)
         for i in range(64)])
    code, body = _post(base, "/debug/hostprofz", {"action": "stop"})
    assert code == 200 and not body["sampler"]["running"]
    assert body["sampler"]["hz"] == 199
    with urllib.request.urlopen(
            f"{base}/debug/hostprofz?format=folded", timeout=10) as r:
        folded_text = r.read().decode()
    for line in folded_text.splitlines():
        assert line.rsplit(" ", 1)[1].isdigit()
    with urllib.request.urlopen(
            f"{base}/debug/hostprofz?format=speedscope", timeout=10) as r:
        prof = json.load(r)
    assert prof["profiles"][0]["type"] == "sampled"
    code, _ = _post(base, "/debug/hostprofz", {"action": "reset"})
    assert code == 200
    with urllib.request.urlopen(f"{base}/debug/hostprofz", timeout=10) as r:
        snap = json.load(r)
    assert snap["sampler"]["samples_total"] == 0
    code, body = _post(base, "/debug/hostprofz", {"action": "nope"})
    assert code == 400 and "unknown hostprofz action" in body["error"]
