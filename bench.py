"""Benchmark harness — fraud-scoring throughput, END-TO-END at the wire.

Headline: risk.v1 ScoreBatch over a real gRPC socket — request decode,
native feature-store gather, the compiled device step, native response
encode — sustained txns/s at ingress (the full request path of
engine.go:262-323, which the reference's "< 50 ms" claim applies to).
Device-only figures are reported alongside: the compiled graph's
streaming throughput and pure device-step time.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ...,
"vs_baseline": N}. Baseline: the reference publishes no throughput
(BASELINE.md); vs_baseline is against the north-star 100,000 txns/s
(BASELINE.json), so vs_baseline >= 1.0 means target met.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from igaming_platform_tpu.core.devices import (
    enable_persistent_compile_cache,
    require_device,
)

TARGET_TXNS_PER_SEC = 100_000.0


def device_pipeline_numbers() -> dict:
    """The compiled serving graph streamed with H2D transfer per batch
    (pipelined like the batcher), plus pure device-step time."""
    import jax

    from igaming_platform_tpu.core.config import ScoringConfig
    from igaming_platform_tpu.models.ensemble import make_score_fn
    from igaming_platform_tpu.models.multitask import init_multitask
    from igaming_platform_tpu.train.data import sample_features

    batch_size = int(os.environ.get("BENCH_BATCH", 16384))
    warmup_iters = int(os.environ.get("BENCH_WARMUP", 5))
    iters = int(os.environ.get("BENCH_ITERS", 50))
    pipeline_depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", 4))

    cfg = ScoringConfig()
    # Donate the batch buffer AND echo it back: a donated input is only
    # usable when an output matches its shape/dtype, and the score dict
    # never matches [B, 30] — donating without the echo is what printed
    # "Some donated buffers were not usable: float32[16384,30]" at every
    # warmup (serve/scorer._pack_outputs has the serving-side fix).
    score_fn = make_score_fn(cfg, ml_backend="multitask")
    fn = jax.jit(
        lambda p, x, bl, t: (score_fn(p, x, bl, t), x), donate_argnums=(1,))
    params = {"multitask": init_multitask(jax.random.key(0))}
    thresholds = np.array([cfg.block_threshold, cfg.review_threshold], dtype=np.int32)

    rng = np.random.default_rng(0)
    pool = [sample_features(rng, batch_size) for _ in range(4)]
    blacklisted = np.zeros((batch_size,), dtype=bool)

    for i in range(warmup_iters):
        out, _ = fn(params, pool[i % len(pool)].copy(), blacklisted, thresholds)
    jax.block_until_ready(out)

    # The stream is fenced by a REAL readback of each batch's packed
    # score array (what the serving collect thread does), so the
    # figure includes the D2H the serving path pays
    # (obs/perfmodel.device_step_time docstring).
    lat = []
    inflight = []
    start = time.perf_counter()
    for i in range(iters):
        t0 = time.perf_counter()
        out, _ = fn(params, pool[i % len(pool)].copy(), blacklisted, thresholds)
        inflight.append((t0, out))
        if len(inflight) > pipeline_depth:
            t0_old, old = inflight.pop(0)
            jax.device_get(old["score"])
            lat.append((time.perf_counter() - t0_old) * 1000.0)
    for t0_old, old in inflight:
        jax.device_get(old["score"])
        lat.append((time.perf_counter() - t0_old) * 1000.0)
    total = time.perf_counter() - start

    # Pure device-step time with device-resident inputs: two-point fit
    # with a readback fence (dispatch is asynchronous).
    from igaming_platform_tpu.obs.perfmodel import device_step_time

    fn_nd = jax.jit(make_score_fn(cfg, ml_backend="multitask"))
    xd = jax.device_put(pool[0])
    bld = jax.device_put(blacklisted)
    thrd = jax.device_put(thresholds)
    step_s = device_step_time(lambda: fn_nd(params, xd, bld, thrd)["score"])
    device_step_ms = round(step_s * 1e3, 3) if step_s == step_s else None

    # Utilization vs chip peaks (obs/perfmodel): the [B,30] ensemble is
    # bandwidth-bound, so hbm_util is the meaningful figure; mfu rides
    # along where a peak is known.
    from igaming_platform_tpu.obs.perfmodel import cost_of, utilization

    util = utilization(
        cost_of(fn_nd, params, xd, bld, thrd),
        step_s, jax.devices()[0],
    )

    lat = np.array(lat)
    return {
        "device_stream_txns_per_sec": round(batch_size * iters / total, 1),
        "device_stream_p99_batch_ms": round(float(np.percentile(lat, 99)), 3),
        "device_step_ms": device_step_ms,
        "device_txns_per_sec": (round(batch_size / step_s, 1)
                                if step_s == step_s else None),
        "batch_size": batch_size,
        "pipeline_depth": pipeline_depth,
        "hbm_util": util["hbm_util"],
        "achieved_hbm_gbps": util["achieved_hbm_gbps"],
        "mfu": util["mfu"],
    }


def e2e_numbers() -> dict:
    """ScoreBatch + ScoreTransaction over a real gRPC socket against the
    production wiring (native store, multitask backend, native encoder)."""
    from benchmarks.load_gen import (
        run_grpc_load,
        run_single_txn_probe,
        start_inprocess_server,
    )

    from igaming_platform_tpu.obs import hostprof
    from igaming_platform_tpu.obs.flight import DEFAULT_RECORDER, stage_breakdown

    addr, shutdown, engine = start_inprocess_server(
        batch_size=int(os.environ.get("BENCH_E2E_BATCH", 8192)),
    )
    try:
        DEFAULT_RECORDER.clear()  # warm-up RPCs out of the breakdown window
        # Host-plane cost observatory (obs/hostprof.py): zero the µs/row
        # accounting so the table covers exactly the measured window, and
        # sample stacks during it so the artifact carries a flamegraph.
        hp = hostprof.get_default()
        hp.reset()
        sampling = hp.enabled and hp.sampler.start(
            float(os.environ.get("BENCH_HOSTPROF_HZ", "67")))
        load = run_grpc_load(
            addr,
            duration_s=float(os.environ.get("BENCH_E2E_DURATION_S", 8.0)),
            rows_per_rpc=int(os.environ.get("BENCH_E2E_ROWS_PER_RPC", 8192)),
            concurrency=int(os.environ.get("BENCH_E2E_CONCURRENCY", 6)),
        )
        # Per-stage latency decomposition from the flight recorder
        # (obs/flight.py): where each ScoreBatch RPC's time went
        # (admission/decode/gather/dispatch/readback/encode) and what
        # share of the RPC span the stages account for.
        breakdown = stage_breakdown(DEFAULT_RECORDER.snapshot(), method="ScoreBatch")
        if sampling:
            hp.sampler.stop()
        probe = run_single_txn_probe(addr, n=120)
        result = {
            # Where the host microseconds went: per-stage µs/row (Tier A),
            # stage coverage of RPC wall, and the top folded stacks.
            "host_cost_block": _host_cost_block(hp, breakdown),
            "e2e_stage_breakdown": breakdown,
            "e2e_stage_coverage_p50": breakdown.get("stage_coverage_p50"),
            "e2e_txns_per_sec": load["value"],
            "e2e_rpc_p50_ms": load["rpc_p50_ms"],
            "e2e_rpc_p99_ms": load["rpc_p99_ms"],
            "e2e_rows_per_rpc": load["rows_per_rpc"],
            "e2e_concurrency": load["concurrency"],
            "e2e_rpc_errors": load["errors"],
            # Failures by gRPC status code: shed-vs-failure (and which
            # failure) readable at a glance in the artifact.
            "e2e_rpc_errors_by_code": load["errors_by_code"],
            # Admission-gate sheds are loud backpressure, NOT failures —
            # reported separately so a healthy gate never reads as a
            # sick server (VERDICT r05 Weak #2).
            "e2e_bulk_shed": load["bulk_shed"],
            "e2e_single_txn_p50_ms": probe["p50_ms"],
            "e2e_single_txn_p99_ms": probe["value"],
        }
        # Pipelined host engine health (serve/pipeline_engine.py): the
        # configured in-flight window, the depth actually reached, and
        # how much of the host-stage work ran concurrently.
        pipeline = getattr(engine, "pipeline", None)
        if pipeline is not None:
            stats = pipeline.stats()
            result["pipeline_inflight_depth"] = stats["depth"]
            result["pipeline_max_inflight"] = stats["max_inflight"]
            result["host_stage_overlap_ratio"] = stats["overlap_ratio"]
            result["e2e_stage_overlap_ratio_p50"] = breakdown.get(
                "stage_overlap_ratio_p50")
        # SLO block (obs/slo.py): attainment against the p99<50ms
        # objective, burn rates, and the top budget-eating stage — the
        # arm-level summary the admission-scheduler work will optimize.
        from igaming_platform_tpu.obs import slo as slo_mod

        slo_engine = slo_mod.get_default()
        if slo_engine is not None:
            result["slo_block"] = slo_engine.summary_block()
        return result
    finally:
        shutdown()


def ledger_ab_numbers() -> dict:
    """Ledger-on vs ledger-off e2e arm: the durable decision ledger
    (serve/ledger.py) promises its WAL rides OFF the hot path — two
    short identical wire runs, one with a ledger bound, must land within
    noise of each other. The artifact records both throughputs, the
    ratio, and the ledger's own counters (appended / dropped / fsync
    p99), so a regression in the O(1)-enqueue promise is visible as a
    ratio, not a vibe. BENCH_LEDGER_AB_S sizes the arms (0 disables)."""
    import tempfile

    from benchmarks.load_gen import run_grpc_load, start_inprocess_server

    duration_s = float(os.environ.get("BENCH_LEDGER_AB_S", 4.0))
    if duration_s <= 0:
        return {}
    rows = int(os.environ.get("BENCH_E2E_ROWS_PER_RPC", 8192))
    batch = int(os.environ.get("BENCH_E2E_BATCH", 8192))
    arms = {}
    ledger_block = None
    for arm in ("off", "on"):
        ledger_dir = tempfile.mkdtemp(prefix="bench-ledger-") if arm == "on" else None
        addr, shutdown, engine = start_inprocess_server(
            batch_size=batch, ledger_dir=ledger_dir)
        try:
            load = run_grpc_load(addr, duration_s=duration_s,
                                 rows_per_rpc=rows, concurrency=4)
            arms[arm] = load["value"]
            if arm == "on" and engine.ledger is not None:
                engine.ledger.flush(5.0)
                ledger_block = engine.ledger.stats_block()
        finally:
            shutdown()
    ratio = arms["on"] / arms["off"] if arms.get("off") else None
    cores = os.cpu_count() or 1
    # The hot-path contract is an O(1) enqueue — but the WRITER THREAD's
    # encode/fsync CPU is real, and on a 1-core control rig it shares
    # the scoring core, so a flat-out A/B measures that tax directly
    # (the WALLET_REPLICAS/FLEET_CHAOS honesty caveat). The bounded
    # queue caps it: drops are counted, scoring is never blocked. On
    # >=2 cores the writer rides its own core and the arm must land
    # within normal run-to-run noise.
    bar = 0.85 if cores >= 2 else 0.45
    return {
        "ledger_off_txns_per_sec": arms.get("off"),
        "ledger_on_txns_per_sec": arms.get("on"),
        "ledger_overhead_ratio": round(ratio, 4) if ratio else None,
        "ledger_overhead_within_noise": bool(ratio and ratio >= bar),
        "ledger_overhead_bar": bar,
        "ledger_cpu_control_note": (
            "1-core control rig: the ledger writer thread shares the "
            "scoring core, so the flat-out ratio records the writer's "
            "bounded CPU tax (queue drops cap it; the hot path never "
            "blocks); on a multi-core host the writer owns a core and "
            "the arm must land within noise (>=0.85)"
            if cores < 2 else
            "multi-core host: ratio reflects true hot-path overhead"),
        "ledger_block": ledger_block,
    }


def shadow_ab_numbers() -> dict:
    """Shadow-on vs shadow-off e2e arm: the shadow scorer
    (serve/shadow.py) promises its candidate steps ride a bounded queue
    OFF the response path — two short identical wire runs, one with a
    candidate shadow-scoring every batch, must land within noise. The
    artifact records both throughputs, the ratio, and the shadow's own
    counters (rows scored/dropped, flip rate) so the promotion loop's
    serving tax is a measured number. BENCH_SHADOW_AB_S sizes the arms
    (0 disables)."""
    from benchmarks.load_gen import run_grpc_load, start_inprocess_server

    duration_s = float(os.environ.get("BENCH_SHADOW_AB_S", 4.0))
    if duration_s <= 0:
        return {}
    rows = int(os.environ.get("BENCH_E2E_ROWS_PER_RPC", 8192))
    batch = int(os.environ.get("BENCH_E2E_BATCH", 8192))
    arms = {}
    shadow_block = None
    for arm in ("off", "on"):
        addr, shutdown, engine = start_inprocess_server(batch_size=batch)
        shadow = None
        try:
            if arm == "on":
                import jax

                from igaming_platform_tpu.models.multitask import (
                    init_multitask,
                )
                from igaming_platform_tpu.serve.shadow import ShadowScorer

                shadow = ShadowScorer(
                    engine,
                    {"multitask": init_multitask(jax.random.key(7))})
                engine.shadow = shadow
            load = run_grpc_load(addr, duration_s=duration_s,
                                 rows_per_rpc=rows, concurrency=4)
            arms[arm] = load["value"]
            if shadow is not None:
                shadow.drain(5.0)
                rep = shadow.report()
                shadow_block = {
                    "rows_scored": rep["total"]["rows"],
                    "rows_dropped": rep["rows_dropped"],
                    "flip_rate": rep["total"]["flip_rate"],
                    "score_delta_mean": rep["total"]["score_delta_mean"],
                }
        finally:
            if shadow is not None:
                shadow.close()
            shutdown()
    ratio = arms["on"] / arms["off"] if arms.get("off") else None
    cores = os.cpu_count() or 1
    # Same honesty contract as the ledger A/B: the shadow WORKER's device
    # steps are real compute, and on a 1-core control rig they share the
    # scoring core, so the flat-out ratio records that bounded tax (the
    # queue drops cap it; responses are never blocked). On >=2 cores the
    # worker interleaves and the arm must land within noise.
    bar = 0.85 if cores >= 2 else 0.45
    return {
        "shadow_off_txns_per_sec": arms.get("off"),
        "shadow_on_txns_per_sec": arms.get("on"),
        "shadow_overhead_ratio": round(ratio, 4) if ratio else None,
        "shadow_overhead_within_noise": bool(ratio and ratio >= bar),
        "shadow_overhead_bar": bar,
        "shadow_block": shadow_block,
    }


def drift_ab_numbers() -> dict:
    """Sketch-on vs sketch-off e2e A/B: the drift observatory
    (obs/drift.py) promises its per-batch cost is ONE fused device-side
    reduction with the tiny result drained off-path — two short
    identical wire runs, one with DRIFT=0 and one with the sketches on,
    must land within noise. The artifact records both throughputs, the
    ratio, and the observatory's own counters (rows sketched/dropped) so
    the on-path promise is a measured number. BENCH_DRIFT_AB_S sizes the
    arms (0 disables)."""
    from benchmarks.load_gen import run_grpc_load, start_inprocess_server

    from igaming_platform_tpu.obs import drift as drift_mod

    duration_s = float(os.environ.get("BENCH_DRIFT_AB_S", 4.0))
    if duration_s <= 0:
        return {}
    rows = int(os.environ.get("BENCH_E2E_ROWS_PER_RPC", 8192))
    batch = int(os.environ.get("BENCH_E2E_BATCH", 8192))
    arms = {}
    drift_block = None
    saved = os.environ.get("DRIFT")
    try:
        for arm in ("off", "on"):
            os.environ["DRIFT"] = "0" if arm == "off" else "1"
            addr, shutdown, _engine = start_inprocess_server(batch_size=batch)
            try:
                load = run_grpc_load(addr, duration_s=duration_s,
                                     rows_per_rpc=rows, concurrency=4)
                arms[arm] = load["value"]
                if arm == "on" and drift_mod.get_default() is not None:
                    drift_mod.get_default().drain(5.0)
                    drift_block = drift_mod.get_default().summary_block()
            finally:
                shutdown()
    finally:
        if saved is None:
            os.environ.pop("DRIFT", None)
        else:
            os.environ["DRIFT"] = saved
    ratio = arms["on"] / arms["off"] if arms.get("off") else None
    cores = os.cpu_count() or 1
    # Same honesty contract as the ledger/shadow A/Bs: on a 1-core
    # control rig the sketch reduction and the drift worker share the
    # scoring core, so the flat-out ratio records that bounded tax
    # directly; on >=2 cores the worker interleaves and the arm must
    # land within normal run-to-run noise.
    bar = 0.85 if cores >= 2 else 0.45
    return {
        "drift_off_txns_per_sec": arms.get("off"),
        "drift_on_txns_per_sec": arms.get("on"),
        "drift_overhead_ratio": round(ratio, 4) if ratio else None,
        "drift_overhead_within_noise": bool(ratio and ratio >= bar),
        "drift_overhead_bar": bar,
        "drift_block": drift_block,
    }


def observability_ab_numbers() -> dict:
    """Observability-overhead A/B: the SLO engine + device-runtime
    telemetry promise O(1)-per-request accounting off the hot path — two
    short identical wire runs, one with both planes disabled (SLO=0,
    RUNTIME_TELEMETRY=0) and one with them on, must land within noise.
    BENCH_OBS_AB_S sizes the arms (0 disables)."""
    from benchmarks.load_gen import run_grpc_load, start_inprocess_server

    from igaming_platform_tpu.obs import slo as slo_mod

    duration_s = float(os.environ.get("BENCH_OBS_AB_S", 4.0))
    if duration_s <= 0:
        return {}
    rows = int(os.environ.get("BENCH_E2E_ROWS_PER_RPC", 8192))
    batch = int(os.environ.get("BENCH_E2E_BATCH", 8192))
    arms = {}
    slo_block = None
    overrides = {"off": {"SLO": "0", "RUNTIME_TELEMETRY": "0"},
                 "on": {"SLO": "1", "RUNTIME_TELEMETRY": "1"}}
    saved = {k: os.environ.get(k) for k in ("SLO", "RUNTIME_TELEMETRY")}
    try:
        for arm in ("off", "on"):
            os.environ.update(overrides[arm])
            addr, shutdown, _engine = start_inprocess_server(batch_size=batch)
            try:
                load = run_grpc_load(addr, duration_s=duration_s,
                                     rows_per_rpc=rows, concurrency=4)
                arms[arm] = load["value"]
                if arm == "on" and slo_mod.get_default() is not None:
                    slo_block = slo_mod.get_default().summary_block()
            finally:
                shutdown()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    ratio = arms["on"] / arms["off"] if arms.get("off") else None
    # Same honesty contract as the ledger A/B: on a 1-core control rig
    # run-to-run noise dominates; on real cores the planes must be free.
    bar = 0.85 if (os.cpu_count() or 1) >= 2 else 0.5
    return {
        "obs_off_txns_per_sec": arms.get("off"),
        "obs_on_txns_per_sec": arms.get("on"),
        "obs_overhead_ratio": round(ratio, 4) if ratio else None,
        "obs_overhead_within_noise": bool(ratio and ratio >= bar),
        "obs_overhead_bar": bar,
        "obs_on_slo_block": slo_block,
    }


def fused_ab_numbers() -> dict:
    """Fused-vs-split A/B (PR 14, one graph / one dispatch): both arms
    run with the drift observatory ON and an ACTIVE shadow candidate, so
    the split arm pays the separate sketch-kernel launch plus the shadow
    scorer's own step per chunk while the fused arm folds both into the
    ONE scoring program. Measures (a) honest dispatches per ScoreBatch
    RPC, (b) direct device-stream step latency p99, (c) open-loop paced
    e2e RPC p99. BENCH_FUSED_AB_S sizes the arms (0 disables).

    1-core control-rig honesty caveat (docs/performance.md): the split
    arm's extra launches are tiny CPU programs here, so the step/e2e
    deltas sit inside run-to-run noise on this host — the structural win
    (3 device programs + 1 extra H2D per chunk collapsing to 1 program)
    is the dispatches/RPC row; what each launch+readback round-trip
    costs on the chip is not measured."""
    import time as _time

    import numpy as np

    from benchmarks.load_gen import run_paced_load, start_inprocess_server
    from igaming_platform_tpu.obs import drift as drift_mod
    from igaming_platform_tpu.obs import runtime_telemetry as rt_mod

    duration_s = float(os.environ.get("BENCH_FUSED_AB_S", 4.0))
    if duration_s <= 0:
        return {}
    batch = int(os.environ.get("BENCH_FUSED_BATCH", 2048))
    paced_rate = float(os.environ.get("BENCH_FUSED_PACED_RATE", "150"))
    arms: dict[str, dict] = {}
    saved = os.environ.get("FUSED")
    try:
        for arm in ("split", "fused"):
            os.environ["FUSED"] = "0" if arm == "split" else "1"
            addr, shutdown, engine = start_inprocess_server(batch_size=batch)
            shadow = None
            try:
                import jax

                from igaming_platform_tpu.models.multitask import (
                    init_multitask,
                )
                from igaming_platform_tpu.serve.shadow import ShadowScorer

                shadow = ShadowScorer(
                    engine,
                    {"multitask": init_multitask(jax.random.key(7))})
                engine.shadow = shadow
                if arm == "fused":
                    # Wait out the off-path shadow warm so the arm
                    # measures the steady state, not the warmup window.
                    deadline = _time.monotonic() + 180
                    while (_time.monotonic() < deadline
                           and ("packed", True, True)
                           not in engine._fused_ready):
                        _time.sleep(0.05)

                def _drain() -> None:
                    if shadow is not None:
                        shadow.drain(10.0)
                    d = drift_mod.get_default()
                    if d is not None:
                        d.drain(10.0)

                # (a) honest dispatches per ScoreBatch RPC (256 rows =
                # one ladder chunk), steady state.
                accts = [f"fz-{i}" for i in range(256)]
                amounts = [1000 + 7 * i for i in range(256)]
                types = ["deposit", "bet", "withdraw", "win"] * 64
                engine.score_batch_wire(accts, amounts, types)  # warm
                _drain()
                telemetry = rt_mod.get_default()
                n_rpcs = 30
                before = telemetry.dispatches_total if telemetry else 0
                for _ in range(n_rpcs):
                    engine.score_batch_wire(accts, amounts, types)
                _drain()
                after = telemetry.dispatches_total if telemetry else 0
                dispatches_per_rpc = round((after - before) / n_rpcs, 3)

                # (b) device-stream step p99: direct launch+readback of
                # one 256-row chunk (the sketch/shadow ride along or
                # launch separately depending on the arm).
                from igaming_platform_tpu.serve.scorer import (
                    _device_readback,
                )

                x = np.zeros((256, 30), dtype=np.float32)
                x[:, 0] = np.linspace(100, 50_000, 256)
                bl = np.zeros((256,), dtype=bool)
                steps = []
                for i in range(260):
                    t0 = _time.perf_counter()
                    out, _n = engine._launch_device(x, bl)
                    _device_readback(out)
                    steps.append((_time.perf_counter() - t0) * 1000.0)
                _drain()
                step_p99 = round(float(np.percentile(steps[10:], 99)), 3)

                # (c) open-loop paced e2e p99 with drift+shadow active.
                paced = run_paced_load(
                    addr, rate_rps=paced_rate, duration_s=duration_s,
                    deadline_ms=float(os.environ.get("SLO_OBJECTIVE_MS",
                                                     "50")))
                _drain()
                d = drift_mod.get_default()
                rep = shadow.report()
                arms[arm] = {
                    "dispatches_per_rpc": dispatches_per_rpc,
                    "device_step_p99_ms": step_p99,
                    "paced_rpc_p99_ms": paced["rpc_p99_ms"],
                    "paced_block": {k: paced[k] for k in
                                    ("rpcs_sent", "ok", "sheds", "errors",
                                     "rpc_p50_ms", "rpc_p99_ms")},
                    "shadow_block": {
                        "rows_scored": rep["total"]["rows"],
                        "rows_dropped": rep["rows_dropped"],
                        "fused_batches": rep["fused_batches"],
                        "errors": rep["errors"],
                    },
                    "drift_block": (d.summary_block()
                                    if d is not None else None),
                }
            finally:
                if shadow is not None:
                    shadow.close()
                shutdown()
    finally:
        if saved is None:
            os.environ.pop("FUSED", None)
        else:
            os.environ["FUSED"] = saved
    cores = os.cpu_count() or 1
    split, fused = arms.get("split", {}), arms.get("fused", {})
    step_ratio = (round(fused["device_step_p99_ms"]
                        / split["device_step_p99_ms"], 4)
                  if split.get("device_step_p99_ms") else None)
    return {
        "fused_arm": fused,
        "split_arm": split,
        "fused_dispatches_per_rpc": fused.get("dispatches_per_rpc"),
        "split_dispatches_per_rpc": split.get("dispatches_per_rpc"),
        "fused_step_p99_ratio": step_ratio,
        "control_rig_cores": cores,
        "caveat": (
            "1-core control rig: the split arm's extra launches are "
            "cheap CPU programs, so step/e2e deltas sit inside noise "
            "here; the structural win is dispatches/RPC -> 1.0; the "
            "per-launch cost on the chip is not measured "
            "(docs/performance.md)"),
    }


def fused_artifact_main() -> None:
    """`make bench-fused`: run the fused-vs-split A/B with drift AND an
    active shadow candidate -> FUSED_r14.json, gated."""
    require_device()
    import jax

    result = {"device": str(jax.devices()[0]),
              "kind": "fused_graph_ab", "revision": "r14"}
    result.update(fused_ab_numbers())
    fused = result.get("fused_arm") or {}
    split = result.get("split_arm") or {}
    noise = 1.25 if (os.cpu_count() or 1) < 2 else 1.15
    gates = {
        # The acceptance criterion: ONE dispatch per RPC with drift
        # sketching and an active shadow candidate.
        "fused_dispatches_per_rpc_is_1": fused.get(
            "dispatches_per_rpc") == 1.0,
        "dispatches_per_rpc_down_vs_split": (
            (fused.get("dispatches_per_rpc") or 9e9)
            < (split.get("dispatches_per_rpc") or 0)),
        "step_p99_no_worse_within_noise": (
            (result.get("fused_step_p99_ratio") or 9e9) <= noise),
        "paced_p99_no_worse_within_noise": (
            (fused.get("paced_rpc_p99_ms") or 9e9)
            <= noise * (split.get("paced_rpc_p99_ms") or 0) + 5.0),
        "shadow_rides_fused_program": (
            (fused.get("shadow_block") or {}).get("fused_batches", 0) > 0
            and (fused.get("shadow_block") or {}).get("errors", 1) == 0),
        "drift_rows_sketched_not_dropped": bool(
            ((fused.get("drift_block") or {}).get("rows_sketched") or 0) > 0
            and ((fused.get("drift_block") or {}).get("rows_dropped")
                 or 0) == 0),
    }
    result["gates"] = gates
    result["all_gates_green"] = all(gates.values())
    out = os.environ.get("FUSED_ARTIFACT", "FUSED_r14.json")
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps({"artifact": out, "gates": gates,
                      "all_gates_green": result["all_gates_green"],
                      "fused_dispatches_per_rpc": result.get(
                          "fused_dispatches_per_rpc"),
                      "split_dispatches_per_rpc": result.get(
                          "split_dispatches_per_rpc")}))
    if not result["all_gates_green"]:
        raise SystemExit(1)


def mesh_ab_numbers() -> dict:
    """Slot-sharded vs replicated device state over a K-device mesh
    (ISSUE 15, ROADMAP item 2): both arms run the SAME mesh with the
    index-mode session path (feature cache + session ring + fused step);
    the replicated arm keeps the pre-PR layout (STATE_SHARDING=0, full
    table per chip), the sharded arm row-shards by slot
    (parallel/state_sharding.py). Measures (a) output parity bit-exact
    over deterministic traffic, (b) per-chip capacity — admissible slots
    and table+ring HBM bytes per chip, the 1/K claim measured from the
    committed shardings, (c) honest dispatches per steady-state RPC, and
    (d) open-loop paced scoring p99 per arm (latency from SCHEDULED
    arrival, so coordinated omission can't flatter it).

    Single-core control-rig honesty caveat (ROADMAP item 2 /
    docs/performance.md): on this host every "chip" is a forced CPU
    device sharing one core, so host-side throughput/latency DECLINES
    with K (collectives + K-way program launch on one core) — the
    WALLET_REPLICAS/FLEET_CHAOS pattern. Gate on parity, per-chip
    capacity and dispatches/RPC; never on host-side scaling."""
    import time as _time

    import jax

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh
    from igaming_platform_tpu.parallel.state_sharding import per_shard_nbytes
    from igaming_platform_tpu.serve import scorer as scorer_mod
    from igaming_platform_tpu.serve.feature_store import (
        InMemoryFeatureStore,
        TransactionEvent,
    )
    from igaming_platform_tpu.serve.scorer import TPUScoringEngine

    duration_s = float(os.environ.get("BENCH_MESH_AB_S", 4.0))
    if duration_s <= 0:
        return {}
    k = int(os.environ.get("BENCH_MESH_K", min(4, len(jax.devices()))))
    if len(jax.devices()) < 2 or k < 2:
        return {"mesh_ab_skipped":
                f"{len(jax.devices())} visible device(s); run via "
                "`make bench-mesh` (forced multi-device CPU mesh)"}
    capacity = int(os.environ.get("BENCH_MESH_CAPACITY", 4096))
    batch = int(os.environ.get("BENCH_MESH_BATCH", 256))
    rate = float(os.environ.get("BENCH_MESH_PACED_RATE", 120.0))
    now0 = 1_700_000_000.0
    n_accounts = min(capacity // 2, 1024)

    def build(sharded: bool) -> TPUScoringEngine:
        os.environ["STATE_SHARDING"] = "1" if sharded else "0"
        store = InMemoryFeatureStore()
        for a in range(n_accounts):
            store.update(TransactionEvent(
                account_id=f"m{a}", amount=500 + 7 * a, tx_type="deposit",
                timestamp=now0 - 60.0 - (a % 50)))
        return TPUScoringEngine(
            ScoringConfig(), ml_backend="mock", feature_store=store,
            batcher_config=BatcherConfig(batch_size=batch, max_wait_ms=1.0,
                                         latency_tiers=(64,)),
            mesh=create_mesh(MeshSpec(data=k),
                             devices=jax.devices()[:k]),
            feature_cache=capacity, session_state=True)

    def traffic(i: int, n: int = 64):
        ids = [f"m{(i * 13 + j) % n_accounts}" for j in range(n)]
        amounts = [300 + (i + j) % 700 for j in range(n)]
        txs = [("deposit", "bet", "withdraw")[(i + j) % 3]
               for j in range(n)]
        return ids, amounts, txs

    arms: dict[str, dict] = {}
    outputs: dict[str, list] = {}
    saved = os.environ.get("STATE_SHARDING")
    try:
        for arm, sharded in (("replicated", False), ("sharded", True)):
            eng = build(sharded)
            try:
                # Warm: admit every account once (the between-steps
                # scatters fire here, not in the steady-state probe).
                for i in range(0, n_accounts, 256):
                    ids = [f"m{a}" for a in
                           range(i, min(i + 256, n_accounts))]
                    eng.score_columns_cached(
                        ids, [100] * len(ids), ["bet"] * len(ids),
                        now=now0)
                # (a) parity capture over deterministic rounds.
                outs = []
                for i in range(8):
                    ids, amounts, txs = traffic(i)
                    outs.append(eng.score_columns_cached(
                        ids, amounts, txs, now=now0 + 1 + i))
                outputs[arm] = outs
                # (c) honest dispatches per steady-state RPC.
                calls: list = []
                orig = scorer_mod._device_dispatch
                scorer_mod._device_dispatch = (
                    lambda fn, shape, dtype: calls.append(fn))
                n_rpcs = 20
                try:
                    for i in range(n_rpcs):
                        ids, amounts, txs = traffic(i)
                        eng.score_columns_cached(ids, amounts, txs,
                                                 now=now0 + 20 + i)
                finally:
                    scorer_mod._device_dispatch = orig
                # (d) open-loop paced p99 from scheduled arrivals.
                lat_ms: list[float] = []
                start = _time.monotonic() + 0.05
                n_sched = int(duration_s * rate)
                for i in range(n_sched):
                    sched = start + i / rate
                    while _time.monotonic() < sched:
                        _time.sleep(0.0002)
                    ids, amounts, txs = traffic(i)
                    eng.score_columns_cached(ids, amounts, txs,
                                             now=now0 + 60 + i)
                    lat_ms.append(
                        (_time.monotonic() - sched) * 1000.0)
                cache_shards = eng.cache.shard_stats()
                ring_shards = eng.session.shard_stats()
                table_per_chip = per_shard_nbytes(eng.cache.table)[0]
                ring_per_chip = per_shard_nbytes(
                    eng.session.session_ring)[0]
                arms[arm] = {
                    "state_sharded": sharded,
                    "mesh_devices": k,
                    "capacity_slots_total": eng.cache.capacity,
                    "slots_per_chip": (
                        cache_shards["rows_per_shard"] if sharded
                        else eng.cache.capacity),
                    "table_hbm_bytes_per_chip": table_per_chip,
                    "session_ring_hbm_bytes_per_chip": ring_per_chip,
                    "state_hbm_bytes_per_chip": (
                        table_per_chip + ring_per_chip),
                    "shard_occupancy": cache_shards["occupancy"],
                    "ring_shards": ring_shards["shards"],
                    "dispatches_per_rpc": round(len(calls) / n_rpcs, 3),
                    "paced_rate_rps": rate,
                    "paced_rpc_p99_ms": round(
                        float(np.percentile(lat_ms, 99)), 3),
                    "paced_rpc_p50_ms": round(
                        float(np.percentile(lat_ms, 50)), 3),
                }
            finally:
                eng.close()
    finally:
        if saved is None:
            os.environ.pop("STATE_SHARDING", None)
        else:
            os.environ["STATE_SHARDING"] = saved

    bit_exact = True
    rows = 0
    for a, b in zip(outputs["replicated"], outputs["sharded"]):
        for key in ("score", "action", "reason_mask", "rule_score"):
            if not np.array_equal(a[key], b[key]):
                bit_exact = False
        if not np.array_equal(a["ml_score"].view(np.int32),
                              b["ml_score"].view(np.int32)):
            bit_exact = False
        rows += len(a["score"])
    rep, sh = arms["replicated"], arms["sharded"]
    return {
        "mesh_devices": k,
        "replicated_arm": rep,
        "sharded_arm": sh,
        "parity_rows_compared": rows,
        "parity_bit_exact": bit_exact,
        "per_chip_state_hbm_ratio": round(
            sh["state_hbm_bytes_per_chip"]
            / rep["state_hbm_bytes_per_chip"], 4),
        "control_rig_cores": os.cpu_count() or 1,
        "caveat": (
            "single-core control rig: all K forced devices share one "
            "core, so host-side paced latency/throughput DECLINES with "
            "K (the WALLET_REPLICAS/FLEET_CHAOS pattern) — gate on "
            "parity, per-chip capacity and dispatches/RPC, never on "
            "host-side scaling (docs/performance.md 'Sharded state')"),
    }


def mesh_artifact_main() -> None:
    """`make bench-mesh`: sharded-vs-replicated state A/B on the forced
    K-device CPU mesh -> MESH_r15.json, gated on parity + per-chip
    capacity + dispatches/RPC (never on host-side scaling)."""
    import jax

    result = {"device": str(jax.devices()[0]),
              "visible_devices": len(jax.devices()),
              "kind": "mesh_state_sharding_ab", "revision": "r15"}
    result.update(mesh_ab_numbers())
    sh = result.get("sharded_arm") or {}
    rep = result.get("replicated_arm") or {}
    k = result.get("mesh_devices") or 0
    gates = {
        # The acceptance criteria rows (ISSUE 15).
        "parity_bit_exact": bool(result.get("parity_bit_exact")),
        "dispatches_per_rpc_unchanged": (
            sh.get("dispatches_per_rpc") is not None
            and sh.get("dispatches_per_rpc") == rep.get(
                "dispatches_per_rpc")),
        # One ladder chunk (64 rows <= tier) per RPC -> 1.0 launches.
        "sharded_dispatches_per_rpc_is_1": sh.get(
            "dispatches_per_rpc") == 1.0,
        "per_chip_hbm_is_one_over_k": (
            k > 0 and (result.get("per_chip_state_hbm_ratio") or 9e9)
            <= 1.0 / k * 1.05),
        "per_chip_slots_scale": (
            k > 0 and sh.get("slots_per_chip") is not None
            and sh["slots_per_chip"] * k == sh.get(
                "capacity_slots_total")),
    }
    result["gates"] = gates
    result["all_gates_green"] = all(gates.values())
    out = os.environ.get("MESH_ARTIFACT", "MESH_r15.json")
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps({"artifact": out, "gates": gates,
                      "all_gates_green": result["all_gates_green"],
                      "per_chip_state_hbm_ratio": result.get(
                          "per_chip_state_hbm_ratio"),
                      "paced_p99_ms": {
                          "replicated": rep.get("paced_rpc_p99_ms"),
                          "sharded": sh.get("paced_rpc_p99_ms")}}))
    if not result["all_gates_green"]:
        raise SystemExit(1)


def _host_cost_block(hp, breakdown: dict | None = None) -> dict:
    """The host-cost artifact face (obs/hostprof.py): per-stage µs/row
    table + per-RPC totals (Tier A), the interval-union stage coverage
    from the flight recorder, GC/heap accounting, and the sampler's top
    folded stacks (Tier B)."""
    snap = hp.snapshot()
    sampler = snap["sampler"]
    return {
        "enabled": snap["enabled"],
        "stages_us_per_row": snap["stages"],
        "rpc_us_per_row": snap["rpc"],
        # Interval-union coverage: share of each RPC's wall attributed
        # to stage spans (flight.stage_breakdown) — nesting-safe, so the
        # pad/session spans inside dispatch cannot double-count.
        "stage_coverage_p50": (breakdown or {}).get("stage_coverage_p50"),
        "gc": snap["gc"],
        "heap": snap["heap"],
        "sampler": {k: sampler[k] for k in
                    ("hz", "samples_total", "distinct_stacks",
                     "roles_seen", "last_duration_s")},
        "top_stacks": sampler["top_stacks"],
    }


def _stacks_mention(top_stacks: list[dict], *needles: str) -> bool:
    """True when any folded stack names any of the needles — the
    flamegraph-content gate (r16): the profile must actually show WHERE
    the host microseconds go, not just that sampling ran."""
    return any(needle in entry["stack"]
               for entry in top_stacks for needle in needles)


def hostprof_numbers() -> dict:
    """Host-plane cost observatory arm (ISSUE 16 tentpole): the full
    stateful serving path (index wire mode, device feature cache +
    session plane) profiled end to end, plus the overhead A/B/A.

    Three identical wire runs: profiler OFF (HOSTPROF=0, no sampler),
    profiler ON (Tier A µs/row accounting + Tier B sampler at
    BENCH_HOSTPROF_HZ + GC watch), then OFF again — the overhead ratio
    divides the on-arm throughput by the MEAN of the two off arms, so
    slow drift on the shared control rig cannot masquerade as profiler
    cost. The on-arm emits the whole observatory: per-stage µs/row
    table, stage coverage of RPC wall (interval union), folded-stack
    flamegraph, GC pause accounting with in-flight-RPC attribution, and
    heap gauges."""
    from benchmarks.load_gen import run_grpc_load, start_inprocess_server

    from igaming_platform_tpu.obs import hostprof
    from igaming_platform_tpu.obs.flight import DEFAULT_RECORDER, stage_breakdown

    duration_s = float(os.environ.get("BENCH_HOSTPROF_AB_S", 6.0))
    if duration_s <= 0:
        return {}
    rows = int(os.environ.get("BENCH_HOSTPROF_ROWS_PER_RPC", 4096))
    batch = int(os.environ.get("BENCH_HOSTPROF_BATCH", 4096))
    cache = int(os.environ.get("BENCH_HOSTPROF_CACHE", 2048))
    hz = float(os.environ.get("BENCH_HOSTPROF_HZ", "199"))
    arms: dict[str, float] = {}
    host_cost = None
    breakdown = None
    folded_lines = 0
    speedscope_frames = 0
    saved = {k: os.environ.get(k) for k in ("HOSTPROF", "HOSTPROF_HZ")}
    try:
        for arm in ("off", "on", "off2"):
            os.environ["HOSTPROF"] = "1" if arm == "on" else "0"
            # The sampler is started explicitly below, never at boot.
            os.environ.pop("HOSTPROF_HZ", None)
            hostprof.reinstall_from_env()
            addr, shutdown, _engine = start_inprocess_server(
                batch_size=batch, feature_cache=cache, session_state=True)
            try:
                DEFAULT_RECORDER.clear()
                hp = hostprof.get_default()
                if arm == "on":
                    hp.reset()
                    hp.sampler.start(hz)
                load = run_grpc_load(addr, duration_s=duration_s,
                                     rows_per_rpc=rows, concurrency=4,
                                     wire_mode="index")
                arms[arm] = load["value"]
                if arm == "on":
                    hp.sampler.stop()
                    # One forced full collection so the artifact always
                    # demonstrates gen-2 pause accounting (labeled — the
                    # per-generation table still shows the natural gen-0/1
                    # churn the load produced).
                    import gc as _gc

                    _gc.collect()
                    breakdown = stage_breakdown(
                        DEFAULT_RECORDER.snapshot(), method="ScoreBatch")
                    host_cost = _host_cost_block(hp, breakdown)
                    host_cost["forced_gen2_collect"] = True
                    folded_lines = len(
                        hp.sampler.to_folded_text().splitlines())
                    speedscope_frames = len(
                        hp.sampler.to_speedscope()["shared"]["frames"])
            finally:
                shutdown()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        hostprof.reinstall_from_env()
    off_mean = (arms["off"] + arms["off2"]) / 2.0 if arms.get("off") else None
    ratio = arms["on"] / off_mean if (off_mean and arms.get("on")) else None
    bar = float(os.environ.get("HOSTPROF_AB_BAR", "0.90"))
    return {
        "hostprof_off_txns_per_sec": arms.get("off"),
        "hostprof_on_txns_per_sec": arms.get("on"),
        "hostprof_off2_txns_per_sec": arms.get("off2"),
        "hostprof_overhead_ratio": round(ratio, 4) if ratio else None,
        "hostprof_overhead_within_bar": bool(ratio and ratio >= bar),
        "hostprof_overhead_bar": bar,
        "hostprof_hz": hz,
        "hostprof_ab_note": (
            "A/B/A: on-arm throughput over the MEAN of the two off arms "
            "(identical stateful wiring: index wire, feature cache, "
            "session plane) — rig drift cannot masquerade as profiler "
            "cost; Tier A is one dict update per completed stage span, "
            "Tier B samples only registered scoring threads"),
        "host_cost_block": host_cost,
        "flight_stage_breakdown": breakdown,
        "folded_stack_lines": folded_lines,
        "speedscope_frames": speedscope_frames,
    }


def hostprof_artifact_main() -> None:
    """`make bench-hostprof`: the host-plane cost observatory measured on
    the stateful serving path -> HOSTPROF_r16.json, gated on stage
    coverage, flamegraph content, GC accounting and the on/off ratio."""
    require_device()
    import jax

    result = {"device": str(jax.devices()[0]),
              "kind": "host_cost_observatory", "revision": "r16"}
    result.update(hostprof_numbers())
    hc = result.get("host_cost_block") or {}
    top = hc.get("top_stacks") or []
    gc_block = hc.get("gc") or {}
    stages = hc.get("stages_us_per_row") or {}
    gates = {
        # The acceptance criteria (ISSUE 16): >= 0.90 of e2e RPC wall
        # attributed to stages by the interval-union rule.
        "stage_coverage_ge_090": (
            (hc.get("stage_coverage_p50") or 0.0) >= 0.90),
        # The flamegraph must NAME the hot paths, not just exist:
        # session bookkeeping (the ~µs/row host cost SESSION_r13
        # measured) and RPC decode.
        "flamegraph_names_session_bookkeeping": _stacks_mention(
            top, "span:score.session", "session_state."),
        "flamegraph_names_rpc_decode": _stacks_mention(
            top, "span:score.decode", "decode_index_batch",
            "decode_gather"),
        "flamegraph_nonempty": (
            (hc.get("sampler") or {}).get("samples_total", 0) > 0
            and len(top) > 0),
        # Per-stage µs/row table present for the session path's stages.
        "stage_table_has_session_and_decode": (
            "session" in stages and "decode" in stages),
        # GC observability: collections counted per generation with
        # pause-ms accounting (the forced gen-2 collect guarantees at
        # least one full collection inside the window).
        "gc_pause_accounting_present": (
            bool(gc_block.get("collections"))
            and bool(gc_block.get("pause_ms_total"))),
        # The always-on contract: profiler-on within noise of off.
        "profiler_overhead_within_bar": bool(
            result.get("hostprof_overhead_within_bar")),
    }
    result["gates"] = gates
    result["all_gates_green"] = all(gates.values())
    out = os.environ.get("HOSTPROF_ARTIFACT", "HOSTPROF_r16.json")
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps({"artifact": out, "gates": gates,
                      "all_gates_green": result["all_gates_green"],
                      "stage_coverage_p50": hc.get("stage_coverage_p50"),
                      "hostprof_overhead_ratio": result.get(
                          "hostprof_overhead_ratio")}))
    if not result["all_gates_green"]:
        raise SystemExit(1)


def main() -> None:
    require_device()
    enable_persistent_compile_cache()
    import jax

    result = {"device": str(jax.devices()[0]), "backend": "multitask-ensemble"}
    result.update(device_pipeline_numbers())

    # Every arm runs and the line is printed whatever happens, but a
    # failed arm is the run's exit code — an `*_error` field under a
    # headline is not a pass.
    failed = []
    for arm, fn in (("e2e", e2e_numbers),
                    ("ledger_ab", ledger_ab_numbers),
                    ("obs_ab", observability_ab_numbers),
                    ("shadow_ab", shadow_ab_numbers),
                    ("drift_ab", drift_ab_numbers)):
        try:
            result.update(fn())
        except Exception as exc:  # noqa: BLE001 — record the arm and keep the device figure; the run fails below
            failed.append(arm)
            result[f"{arm}_error"] = f"{type(exc).__name__}: {exc}"
            if arm == "e2e":
                break  # the A/B arms are measured against the e2e rig
    metric, key = (("fraud_score_txns_per_sec", "device_stream_txns_per_sec")
                   if "e2e" in failed else
                   ("e2e_grpc_fraud_score_txns_per_sec", "e2e_txns_per_sec"))
    headline = float(result[key])
    result.update({
        "metric": metric,
        "value": round(headline, 1),
        "unit": "txns/s",
        "vs_baseline": round(headline / TARGET_TXNS_PER_SEC, 3),
    })
    print(json.dumps(result))
    if failed:
        raise SystemExit(f"bench arms failed: {failed}")


if __name__ == "__main__":
    if "--fused" in sys.argv[1:]:
        fused_artifact_main()
    elif "--mesh" in sys.argv[1:]:
        mesh_artifact_main()
    elif "--hostprof" in sys.argv[1:]:
        hostprof_artifact_main()
    else:
        main()
