#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the risk.v1 scoring path once, through the entry points a user
calls, at the full width of the flagship model (the multitask ensemble at
``DEFAULT_TRUNK``, serving shape ``batch_size=8192``), in ONE process that
never starts a child needing the chip:

    environment -> native -> server (one chip) -> trainer -> kernels
                -> mesh (>=4 devices) -> cache

Each phase is a plain function returning a report dict and raising on any
failed check — there is no ``try/except`` that turns a failure into a
label. ``__main__`` demands a TPU: under ``JAX_PLATFORMS=cpu``, on a
machine with no chip, or in a directory without the rest of the repo it
exits non-zero and prints no result. Tests call the phases at a tiny size
on the CPU (passing ``interpret=True`` themselves).

Last line of stdout on success, the device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The full per-phase report goes to ``chiprun_out/chip_smoke.json`` under
the working directory.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import json
import math
import os
import shutil
import socket
import sys
import time
import urllib.request
import warnings
from unittest import mock

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# Without the rest of the repo beside it this import fails, and the
# script exits non-zero having printed nothing — as it must.
from igaming_platform_tpu.core.devices import (  # noqa: E402
    device_label as device_stamp,
    enable_persistent_compile_cache,
)
from igaming_platform_tpu.serve.native_store import DEFAULT_MAX_ACCOUNTS  # noqa: E402

# Bounds the repo already states (train/device_parity.py, the host-tier
# note in serve/scorer.py): chip-vs-CPU fraud probability within 1e-2,
# integer score within +-1 (same action wherever the score agrees).
PROB_BOUND = 1e-2
# Pallas kernels multiply in bf16 on the MXU, as the XLA einsum they
# replace does at default precision; the reference here is the einsum at
# HIGHEST precision, so the bound is the bf16-product envelope measured
# on a v5e (forward <= 5e-3, backward <= 1.2e-2 on O(1) values).
ATTN_FWD_TOL = 2e-2
ATTN_BWD_TOL = 4e-2
GBDT_TOL = 1e-5
# Grouped expert products against lax.ragged_dot, as a share of the largest
# value: one unit in the last place of bfloat16 (``mid`` is rounded once;
# only the order of float32 accumulation may differ before it).
EXPERTS_TOL = 2.0 ** -7
# The pangu head against its reference, on the probability: the limit the
# cell's output check holds the worst single row to (fraud_prob_max_err
# in chipbench/configs/risk-seqhead-openpangu-ultra-moe-718b.json).
BACKBONE_TOL = 0.05
# combine against a gather and a weighted sum: float32 summation order alone
COMBINE_TOL = 1e-5
# The stream kernels against the ``jax.numpy`` residual path, as a share of
# the largest value: float32 summation order alone, through the maps (1.9e-6
# of unit values on a v5e at 4,096 positions: PERF.md, section 5, PR 53)
STREAMS_TOL = 1e-4


def check(cond: bool, message: str) -> None:
    """A failed check fails the phase (``assert`` would vanish under -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {message}")


# ---------------------------------------------------------------------------
# Phase: environment


def phase_environment(require_tpu: bool = True) -> dict:
    """Backend, versions, the compile cache in effect, and a known peak
    for this ``device_kind`` (an unknown kind is an error here, not null
    utilisation later)."""
    import jax
    import jaxlib

    from igaming_platform_tpu.obs.perfmodel import peak_for
    from igaming_platform_tpu.serve.server import device_gate

    backend = device_gate()
    if require_tpu:
        check(backend == "tpu",
              f"no TPU: jax.default_backend() is {backend!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})")
    cache_dir = enable_persistent_compile_cache()
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    report = {
        "device": device_stamp(),
        "backend": backend,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "cache_dir": cache_dir,
        "cache_dir_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "jax_platforms": jax.config.jax_platforms,
    }
    if require_tpu:
        peaks = peak_for(jax.devices()[0])
        check(peaks is not None,
              f"no peak table entry for device_kind "
              f"{jax.devices()[0].device_kind!r} (obs/perfmodel._PEAKS)")
        report["peak_flops"], report["peak_hbm_bytes_s"] = peaks
    return report


# ---------------------------------------------------------------------------
# Phase: native


def phase_native(force: bool = True) -> dict:
    """Rebuild native/lib/*.so from native/*.cpp on THIS host, then
    require both bindings. No g++ is a failure with that message — never
    a quiet run on the Python store."""
    from igaming_platform_tpu.serve import native_build

    gxx = shutil.which("g++")
    check(gxx is not None, "no g++ on this machine: native/lib cannot be "
          "built, and the scoring path must not fall back to the Python "
          "feature store / per-row codec")
    t0 = time.perf_counter()
    lib_dir = native_build.ensure_built(force=force)
    build_s = time.perf_counter() - t0
    check(lib_dir is not None, "native build failed (see the "
          "igaming_platform_tpu.serve.native_build warning above)")

    from igaming_platform_tpu.serve.native_store import native_available
    from igaming_platform_tpu.serve.wire import native_wire_available

    check(native_available(), "native feature store did not load")
    check(native_wire_available(), "native wire codec did not load")
    return {"device": device_stamp(), "gxx": gxx, "rebuilt": force,
            "build_s": round(build_s, 2), "lib_dir": lib_dir}


# ---------------------------------------------------------------------------
# Phase: server


def _http_get(port: int, path: str) -> tuple[int, str]:
    with urllib.request.urlopen(f"http://localhost:{port}{path}",
                                timeout=30) as resp:
        return resp.status, resp.read().decode()


def _metric(text: str, name: str) -> float:
    """Sum of a metric's samples in Prometheus text (0 when absent)."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _port_free(port: int) -> bool:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.settimeout(1.0)
        return s.connect_ex(("127.0.0.1", port)) != 0


def _rows(resp) -> dict:
    """ScoreBatchResponse -> columns (reason codes kept as sorted tuples)."""
    import numpy as np

    res = resp.results
    return {
        "score": np.array([r.score for r in res], np.int32),
        "action": np.array([r.action for r in res], np.int32),
        "rule_score": np.array([r.rule_score for r in res], np.int32),
        "ml_score": np.array([r.ml_score for r in res], np.float32),
        "reasons": [tuple(sorted(r.reason_codes)) for r in res],
    }


def _no_degraded(reasons, where: str) -> None:
    bad = [r for r in reasons if "DEGRADED_CPU_HEURISTIC" in r]
    check(not bad, f"{where}: {len(bad)} response(s) carry "
          "DEGRADED_CPU_HEURISTIC")


def _on_platform(tree, platform: str) -> bool:
    import jax

    leaves = [a for a in jax.tree_util.tree_leaves(tree)
              if hasattr(a, "devices")]
    return bool(leaves) and all(
        d.platform == platform for a in leaves for d in a.devices())


def phase_server(batch_size: int = 8192, *, small_rows: tuple = (64, 256),
                 mesh_devices: int = 0, abuse_events: int = 300,
                 steady_passes: int = 6, singles: int = 4,
                 train_steps: int = 5, train_batch: int | None = None,
                 reference: dict | None = None,
                 store_max_accounts: int = DEFAULT_MAX_ACCOUNTS) -> dict:
    """The assembly ``serve/server.py:main()`` makes — device_gate,
    compile cache, ``RiskServer`` — with the multitask backend at
    DEFAULT_TRUNK and seeded params, driven over a real gRPC socket.

    Traffic runs as: a first pass of every RPC kind (admissions and
    first-call compiles happen here and are reported), then a steady
    window — the same kinds again, a threshold flip, the trainer's
    hot-swap — in which the device-dispatch counter must rise by exactly
    the chunks sent and nothing may compile.

    ``mesh_devices`` boots the same server on a ``data=N`` mesh;
    ``reference`` (a one-chip report) makes the index+session trace a
    bit-exact parity check against it."""
    import grpc
    import jax
    import numpy as np

    from igaming_platform_tpu.core.config import RiskServiceConfig, ScoringConfig
    from igaming_platform_tpu.models.ensemble import make_score_fn
    from igaming_platform_tpu.models.multitask import DEFAULT_TRUNK, init_multitask
    from igaming_platform_tpu.models.sequence import EVENT_DIM
    from igaming_platform_tpu.proto_gen.risk.v1 import risk_pb2
    from igaming_platform_tpu.serve.grpc_server import make_risk_stub
    from igaming_platform_tpu.serve.server import RiskServer, device_gate
    from igaming_platform_tpu.serve.wire import encode_index_batch

    platform = jax.devices()[0].platform
    report: dict = {"device": device_stamp(), "batch_size": batch_size,
                    "mesh_devices": mesh_devices, "trunk": list(DEFAULT_TRUNK)}

    # -- boot, exactly as main() does --------------------------------------
    device_gate()
    enable_persistent_compile_cache()
    config = RiskServiceConfig.from_env()
    config = dataclasses.replace(
        config, mesh_devices=mesh_devices,
        batcher=dataclasses.replace(config.batcher, batch_size=batch_size))
    params = {"multitask": jax.device_get(init_multitask(jax.random.key(0)))}
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught, \
            mock.patch.dict(os.environ, FEATURE_CACHE="1", SESSION_STATE="1"):
        warnings.simplefilter("always")
        server = RiskServer(config, ml_backend="multitask", params=params,
                            grpc_port=0, http_port=0,
                            store_max_accounts=store_max_accounts)
    report["boot_s"] = round(time.perf_counter() - t0, 2)
    try:
        donated = [str(w.message) for w in caught
                   if "donated buffers were not usable" in str(w.message)]
        check(not donated, f"warm-up warned about unusable donations: {donated}")
        inner = server.engine.inner
        telemetry = server.telemetry
        check(telemetry is not None, "runtime telemetry is not installed")

        def compiles() -> int:
            return telemetry.compile_watcher.compiles_total

        def dispatches() -> int:
            return int(_metric(_http_get(server.http_port, "/metrics")[1],
                               "risk_device_dispatches_total"))

        def hedges() -> int:
            # A single whose collect overruns the step model's stall
            # threshold is launched a second time and the two race
            # (serve/batcher._collect_hedged): an honest second dispatch.
            return int(json.loads(_http_get(
                server.http_port, "/debug/deadlinez")[1])["batches_hedged"])

        host_tier = inner._fn_host is not None
        report["host_tier_built"] = host_tier
        report["host_tier_rows"] = inner._host_tier if host_tier else 0
        report["feature_store"] = type(inner.features).__name__
        if platform == "tpu":
            check(report["feature_store"] == "NativeFeatureStore",
                  "a TPU boot must serve from the native feature store")
        check(inner.cache is not None and inner.session is not None,
              "feature cache / session plane were not built at boot")

        # -- traffic content (seeded; time-independent features only, so
        #    every gather of a row is bit-identical whenever it happens) ---
        rng = np.random.default_rng(0)
        n = batch_size
        accounts = [f"smoke-{i}" for i in range(n)]
        for a in accounts:
            inner.features.load_batch_features(
                a,
                total_deposits=int(rng.integers(0, 500_000)),
                total_withdrawals=int(rng.integers(0, 300_000)),
                deposit_count=int(rng.integers(0, 60)),
                withdraw_count=int(rng.integers(0, 30)),
                total_bets=int(rng.integers(0, 900_000)),
                total_wins=int(rng.integers(0, 800_000)),
                bet_count=int(rng.integers(0, 400)),
                win_count=int(rng.integers(0, 200)),
                bonus_claim_count=int(rng.integers(0, 8)))
        amounts = [int(v) for v in rng.integers(100, 200_000, n)]
        types = [("deposit", "bet", "withdraw")[int(v)]
                 for v in rng.integers(0, 3, n)]
        batch_req = risk_pb2.ScoreBatchRequest(transactions=[
            risk_pb2.ScoreTransactionRequest(
                account_id=accounts[i], amount=amounts[i],
                transaction_type=types[i]) for i in range(n)])
        batch_bytes = batch_req.SerializeToString()
        for _ in range(abuse_events):
            server.abuse.record_event(
                "smoke-abuser", int(rng.integers(100, 50_000)),
                ("bonus_grant", "bonus_wager", "withdraw")[int(rng.integers(0, 3))],
                game_weight=float(rng.random()))
        check(server.abuse.history_length("smoke-abuser") >= min(abuse_events, 256),
              "abuse history was not recorded")

        channel = grpc.insecure_channel(
            f"localhost:{server.grpc_port}",
            options=[("grpc.max_receive_message_length", 64 << 20),
                     ("grpc.max_send_message_length", 64 << 20)])
        stub = make_risk_stub(channel)
        raw_batch = channel.unary_unary(
            "/risk.v1.RiskService/ScoreBatch",
            request_serializer=lambda b: b,
            response_deserializer=risk_pb2.ScoreBatchResponse.FromString)

        def index_frame(rows: int) -> bytes:
            return encode_index_batch(accounts[:rows], amounts[:rows],
                                      types[:rows])

        def chunks(rows: int) -> int:
            return math.ceil(rows / batch_size)

        index_trace: list[dict] = []

        def score_index(rows: int) -> dict:
            cols = _rows(raw_batch(index_frame(rows), timeout=120))
            check(len(cols["score"]) == rows, f"index RPC returned "
                  f"{len(cols['score'])} of {rows} rows")
            _no_degraded(cols["reasons"], f"index ScoreBatch({rows})")
            index_trace.append(cols)
            return cols

        def score_singles(count: int) -> list:
            out = []
            for i in range(count):
                resp, call = stub.ScoreTransaction.with_call(
                    risk_pb2.ScoreTransactionRequest(
                        account_id=accounts[i], amount=amounts[i],
                        transaction_type=types[i]), timeout=60)
                _no_degraded([tuple(resp.reason_codes)], "ScoreTransaction")
                versions = [v for k, v in (call.trailing_metadata() or ())
                            if k == "risk-model-version"]
                check(not any("degraded" in v for v in versions),
                      f"ScoreTransaction carried model version {versions}")
                out.append(resp)
            return out

        def check_abuse():
            resp = stub.CheckBonusAbuse(risk_pb2.CheckBonusAbuseRequest(
                account_id="smoke-abuser", bonus_id="welcome"), timeout=120)
            check("DEGRADED_CPU_HEURISTIC" not in resp.signals,
                  "CheckBonusAbuse answered from the degraded heuristic")
            check(0.0 <= resp.abuse_score <= 1.0
                  and math.isfinite(resp.abuse_score),
                  f"abuse score {resp.abuse_score} out of range")
            return resp

        def predict_ltv():
            resp = stub.PredictLTV(risk_pb2.PredictLTVRequest(
                account_id=accounts[0]), timeout=60)
            check(math.isfinite(resp.predicted_ltv), "PredictLTV not finite")
            return resp

        # -- first pass: every RPC kind once -------------------------------
        c_boot = compiles()
        first: dict = {}
        d1 = dispatches()
        row_proto = _rows(stub.ScoreBatch(batch_req, timeout=180))
        row_raw = _rows(raw_batch(batch_bytes, timeout=180))
        first["row_batches"] = dispatches() - d1
        check(first["row_batches"] == 2 * chunks(n),
              f"two {n}-row ScoreBatch RPCs made {first['row_batches']} "
              f"dispatches, expected {2 * chunks(n)}")
        check(len(row_proto["score"]) == n, "row ScoreBatch lost rows")
        _no_degraded(row_proto["reasons"] + row_raw["reasons"], "row ScoreBatch")
        for k in ("score", "action", "rule_score"):
            check(np.array_equal(row_proto[k], row_raw[k]),
                  f"proto and raw-bytes ScoreBatch disagree on {k}")
        check(np.array_equal(row_proto["ml_score"].view(np.int32),
                             row_raw["ml_score"].view(np.int32)),
              "proto and raw-bytes ScoreBatch disagree on ml_score bits")

        # Index mode at the full shape, cold sessions: the cached gather
        # claims bit-identity with the row path (serve/device_cache.py).
        d2 = dispatches()
        idx_full = score_index(n)
        first["index_full_incl_admission"] = dispatches() - d2
        for k in ("score", "action", "rule_score"):
            check(np.array_equal(idx_full[k], row_raw[k]),
                  f"index mode vs row path: {k} differs on "
                  f"{int(np.sum(idx_full[k] != row_raw[k]))} rows")
        check(np.array_equal(idx_full["ml_score"].view(np.int32),
                             row_raw["ml_score"].view(np.int32)),
              "index mode vs row path: ml_score bits differ")
        check(all("SESSION_COLD" in r for r in idx_full["reasons"]),
              "first index pass should be all session-cold rows")
        d3 = dispatches()
        for rows in small_rows:
            score_index(rows)
        first["index_small"] = dispatches() - d3
        d4 = dispatches()
        abuse = check_abuse()
        first["abuse"] = dispatches() - d4
        check(first["abuse"] == 1, f"CheckBonusAbuse made {first['abuse']} "
              "dispatches, expected 1")
        ltv = predict_ltv()
        # Interactive traffic goes LAST in each window: a single arms the
        # burn->shed gate for BURN_SHED_IDLE_S, and the slow first-call
        # RPCs above (compiles inside requests) burn the fast SLO window.
        d0, h0 = dispatches(), hedges()
        single_resps = score_singles(singles)
        first["singles"] = dispatches() - d0
        first["singles_hedged"] = hedges() - h0
        check(first["singles"] == singles + first["singles_hedged"],
              f"{singles} sequential singles made {first['singles']} "
              f"dispatches ({first['singles_hedged']} hedged)")
        launched = json.loads(_http_get(
            server.http_port, "/debug/telemetryz")[1])["compile"]["signature_names"]
        on_host = any(s.split(":")[0] in ("fused_host_step", "packed_step_host")
                      for s in launched)
        report["single_tier"] = "host-cpu" if on_host else "device"
        check(on_host == host_tier, f"singles answered by "
              f"{report['single_tier']} but host tier built={host_tier}")
        report["first_pass_dispatches"] = first
        report["first_pass_compiles"] = compiles() - c_boot
        report["abuse_score"] = round(abuse.abuse_score, 6)
        report["ltv_segment"] = int(ltv.segment)

        # -- chip vs CPU on the same rows, same graph ----------------------
        x, bl = inner.features.decode_gather(batch_bytes)
        cpu = jax.devices("cpu")[0]
        cfg = ScoringConfig()
        thr = np.array([cfg.block_threshold, cfg.review_threshold], np.int32)
        ref = jax.jit(make_score_fn(cfg, "multitask"))(
            jax.device_put(params, cpu), jax.device_put(x, cpu),
            jax.device_put(bl, cpu), jax.device_put(thr, cpu))
        ref = {k: np.asarray(v) for k, v in ref.items()}
        dscore = np.abs(row_raw["score"] - ref["score"])
        dprob = np.abs(row_raw["ml_score"] - ref["ml_score"])
        same_score = dscore == 0
        report["vs_cpu"] = {
            "rows": n,
            "max_score_delta": int(dscore.max()),
            "max_prob_delta": float(dprob.max()),
            "rows_score_differs": int((~same_score).sum()),
            "action_mismatch": int((row_raw["action"] != ref["action"]).sum()),
        }
        check(dscore.max() <= 1, f"score differs from the CPU run by "
              f"{int(dscore.max())} (> 1)")
        check(dprob.max() <= PROB_BOUND, f"fraud probability differs from the "
              f"CPU run by {dprob.max():.4g} (> {PROB_BOUND})")
        check(np.array_equal(row_raw["action"][same_score],
                             ref["action"][same_score]),
              "same score, different action vs the CPU run")
        check(np.array_equal(row_raw["rule_score"], ref["rule_score"]),
              "rule score differs from the CPU run")
        for i, resp in enumerate(single_resps):
            check(abs(resp.score - int(ref["score"][i])) <= 1,
                  f"single {i}: score {resp.score} vs CPU {int(ref['score'][i])}")

        # -- placement -----------------------------------------------------
        packed, _ = inner.launch_packed(x, bl)
        placement = {
            "params": _on_platform(inner.get_params(), platform),
            "feature_table": _on_platform(inner.cache.table, platform),
            "session_ring": _on_platform(inner.session.session_ring, platform),
            "packed_result": _on_platform(packed, platform),
        }
        jax.device_get(packed)
        report["placement"] = placement
        check(all(placement.values()),
              f"state or results not on a {platform} device: {placement}")
        seq_len = min(server.abuse.max_history, 64)
        abuse_x = np.zeros((1, seq_len, EVENT_DIM), np.float32)
        lowered = server.abuse._fn.lower(server.abuse.params, abuse_x).as_text()
        report["abuse_seq_len"] = seq_len
        report["abuse_mosaic_call"] = "tpu_custom_call" in lowered
        if platform == "tpu":
            check(report["abuse_mosaic_call"], "the abuse step's lowered "
                  "text has no Mosaic custom call: the Pallas flash kernel "
                  "is not on the serving path")
        if mesh_devices:
            from igaming_platform_tpu.parallel.state_sharding import per_shard_nbytes

            shard_report = {}
            for name, arr in (("feature_table", inner.cache.table),
                              ("session_ring", inner.session.session_ring)):
                devs = {s.device.id for s in arr.addressable_shards}
                per = per_shard_nbytes(arr)
                total = int(np.prod(arr.shape)) * arr.dtype.itemsize
                shard_report[name] = {"devices": sorted(devs),
                                      "per_shard_bytes": per, "total_bytes": total}
                check(len(devs) == mesh_devices,
                      f"{name} sits on {len(devs)} device(s), not {mesh_devices}")
                check(all(abs(b - total / mesh_devices) <= 0.02 * total
                          for b in per),
                      f"{name} per-shard bytes {per} are not total/{mesh_devices}")
            report["shards"] = shard_report

        # -- steady window: exact dispatches, no compile -------------------
        # Bulk admissions shed (by design) while the fast SLO window
        # burns AND interactive traffic was seen in the last
        # BURN_SHED_IDLE_S: wait for the gate to disarm, as a bulk
        # client honouring the pushback would.
        t_gate = time.perf_counter()
        while json.loads(_http_get(server.http_port, "/debug/deadlinez")[1]
                         )["burn_gate"]["shedding"]:
            check(time.perf_counter() - t_gate < 90.0,
                  "the burn->shed gate did not disarm within 90 s")
            time.sleep(0.5)
        report["burn_gate_wait_s"] = round(time.perf_counter() - t_gate, 1)
        c_steady, d_steady, h_steady = compiles(), dispatches(), hedges()
        ready_code, ready_body = _http_get(server.http_port, "/ready")
        sent = 0
        for _ in range(steady_passes):
            for rows in small_rows:
                score_index(rows)
                sent += chunks(rows)
        _rows(raw_batch(batch_bytes, timeout=180))
        sent += chunks(n)
        check_abuse()
        sent += 1
        predict_ltv()
        flip = stub.UpdateThresholds(risk_pb2.UpdateThresholdsRequest(
            block_threshold=0, review_threshold=0), timeout=30)
        check(flip.success, "UpdateThresholds failed")
        flipped = score_index(small_rows[0])
        sent += chunks(small_rows[0])
        check(bool(np.all(flipped["action"] == 3)),
              "block=0/review=0 did not flip every action to block")
        stub.UpdateThresholds(risk_pb2.UpdateThresholdsRequest(
            block_threshold=cfg.block_threshold,
            review_threshold=cfg.review_threshold), timeout=30)
        restored = score_index(small_rows[0])
        sent += chunks(small_rows[0])
        check(not np.all(restored["action"] == 3),
              "thresholds were not restored")

        # -- trainer: beside the live server, then hot-swap ----------------
        if train_steps:
            c_train = compiles()
            report["trainer"] = phase_trainer(
                server, lambda: _rows(raw_batch(batch_bytes, timeout=180)),
                row_raw, compiles, steps=train_steps, batch_size=train_batch)
            sent += chunks(n)
            # The trainer's own programs are new code, not a recompile
            # of anything the server warmed.
            c_steady += report["trainer"]["train_compiles"]
            check(compiles() - c_train == report["trainer"]["train_compiles"],
                  "ScoreBatch after swap_params recompiled")
        score_singles(singles)
        sent += singles
        report["steady"] = {
            "chunks_sent": sent,
            "dispatches": dispatches() - d_steady,
            "hedged": hedges() - h_steady,
            "compiles": compiles() - c_steady,
        }
        check(report["steady"]["dispatches"]
              == sent + report["steady"]["hedged"],
              f"steady window: {report['steady']['dispatches']} dispatches "
              f"for {sent} chunks sent ({report['steady']['hedged']} hedged)")
        check(report["steady"]["compiles"] == 0,
              f"{report['steady']['compiles']} compile(s) after warm-up: "
              f"{list(telemetry.compile_watcher.events)[-3:]}")
        session = json.loads(_http_get(server.http_port, "/debug/sessionz")[1])
        report["session_rows"] = session["rows"]
        check(session["rows"]["warm"] > 0, "no session ever became warm")

        # -- health --------------------------------------------------------
        metrics_text = _http_get(server.http_port, "/metrics")[1]
        sup = json.loads(_http_get(server.http_port, "/debug/supervisorz")[1])
        report["supervisor"] = sup["state"]
        report["device_breaker"] = sup["breakers"].get("device", {}).get("state")
        check(sup["state"] == "serving", f"supervisor is {sup['state']}")
        check(report["device_breaker"] in (None, "closed"),
              f"device breaker is {report['device_breaker']}")
        check(_metric(metrics_text, "risk_watchdog_trips_total") == 0,
              "the device-step watchdog tripped")
        check(_metric(metrics_text, "risk_degraded_responses_total") == 0,
              "degraded responses were served")
        check(ready_code == 200 and json.loads(ready_body)["ready"] is True,
              f"/ready said {ready_code} {ready_body}")
        report["ready"] = True

        # -- parity against the one-chip run -------------------------------
        if reference is not None:
            want = reference["index_trace"]
            check(len(want) == len(index_trace), "index traces differ in length")
            for i, (a, b) in enumerate(zip(index_trace, want)):
                for k in ("score", "action", "rule_score"):
                    check(np.array_equal(a[k], b[k]),
                          f"mesh vs one chip: RPC {i} {k} differs")
                check(np.array_equal(a["ml_score"].view(np.int32),
                                     b["ml_score"].view(np.int32)),
                      f"mesh vs one chip: RPC {i} ml_score bits differ")
                check(a["reasons"] == b["reasons"],
                      f"mesh vs one chip: RPC {i} reason codes differ")
            report["parity_vs_one_chip"] = f"bit-exact over {len(want)} RPCs"
        channel.close()
    finally:
        grpc_port, http_port = server.grpc_port, server.http_port
        server.shutdown(grace=5.0)
    check(_port_free(grpc_port) and _port_free(http_port),
          f"shutdown left a port bound (grpc {grpc_port}, http {http_port})")
    report["ports_released"] = True
    report["index_trace"] = index_trace
    return report


# ---------------------------------------------------------------------------
# Phase: trainer


def phase_trainer(server, score_batch, before: dict, compiles, *,
                  steps: int = 5, batch_size: int | None = None) -> dict:
    """A few steps of ``train.trainer.Trainer`` at the default
    ``TrainConfig`` beside the LIVE server, finite loss, then
    ``swap_params`` into the serving engine and one more ScoreBatch
    (``score_batch()`` -> columns) that must not recompile — the
    "training and serving share the pod" claim, once, on the chip.
    Called by the server phase, which owns the live server."""
    import numpy as np

    from igaming_platform_tpu.train.trainer import TrainConfig, Trainer

    c0 = compiles()
    cfg = TrainConfig() if batch_size is None else TrainConfig(batch_size=batch_size)
    trainer = Trainer(cfg)
    metrics = trainer.fit(steps)
    check(all(math.isfinite(v) for v in metrics.values()),
          f"training metrics not finite: {metrics}")
    train_compiles = compiles() - c0
    server.engine.swap_params({"multitask": trainer.export_params()})
    after = score_batch()
    _no_degraded(after["reasons"], "ScoreBatch after swap_params")
    check(not np.array_equal(after["ml_score"], before["ml_score"]),
          "swap_params did not change the served model")
    return {"device": device_stamp(), "steps": steps,
            "batch_size": cfg.batch_size, "trunk": list(cfg.trunk),
            "loss": round(metrics["loss"], 6),
            "train_compiles": train_compiles,
            "served_fingerprint": server.engine.inner.params_fingerprint}


# ---------------------------------------------------------------------------
# Phase: kernels


def _said_by_the_expert_layer(fn) -> list[str]:
    """What ``models/decoder_parts.announce_core`` says while ``fn`` runs:
    the cores picked while tracing (``expert core: ...``, ``combine: ...``,
    ``attention core: ...``, ``state-space core: ...``), each once."""
    import logging

    from igaming_platform_tpu.models import decoder_parts

    said: list[str] = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    decoder_parts.announce_core.cache_clear()
    decoder_parts.logger.addHandler(handler)
    level = decoder_parts.logger.level
    decoder_parts.logger.setLevel(logging.INFO)
    try:
        fn()
    finally:
        decoder_parts.logger.removeHandler(handler)
        decoder_parts.logger.setLevel(level)
    return said


def phase_kernels(interpret: bool = False, *,
                  attention_shapes: tuple = ((1, 2, 64, 32), (8, 2, 256, 32),
                                             (2, 8, 2048, 16), (1, 8, 8192, 16)),
                  backward_shape: tuple = (2, 8, 2048, 16),
                  gbdt_batch: int = 8192, gbdt_tile: int = 256,
                  expert_shape: tuple = (32768, 2048, 768, 128),
                  top_k: int = 8,
                  second_shape: tuple = (16384, 2048, 1536, 64, 4),
                  share_shape: tuple = (2048, 7680, 4096, 1000),
                  grouped_windows: int = 32, delta_windows: int = 32,
                  ssd_windows: int = 32, stream_tiles: int = 32,
                  block_window: int = 4096, scan_window: int = 2048,
                  latent_window: int = 2048) -> dict:
    """Every Pallas entry point at the shapes the repo uses — flash
    forward resident (S=64 is what CheckBonusAbuse serves, S=256, S=2048)
    and tiled (S=8192), backward at S=2048, the GBDT forest at
    [8192, 30], the grouped expert products at the ``keye`` head's
    (rows, hidden, width, experts), how they are fed there and at the
    ``lfm2`` head's (``second_shape``, with its pairs a position: the ring
    of weight slots, the rows brought in by ``gate_up`` itself, a seeded
    routing's first visits that wait), and their way back to position order
    (``combine``) at both backbones' shapes: every slot of the ``keye``
    head's rows / ``top_k`` positions, and a pass of the ``pangu`` head's
    share (rows, hidden, positions, slots taken) — each against its XLA
    reference, with the way back a trace would pick there; and the window
    kernel's grouped-query form at the ``keye`` head's attention (32 query
    / 4 key heads of 128, a random mask with the diagonal kept) on
    ``grouped_windows`` windows of 16 against the einsum core, with the
    core a trace would pick there, and handed no mask (as the ``keye`` cells
    call it since PR 63: the indexer not traced) to the bits of the causal
    mask handed over; and the delta-rule window kernel at the
    ``ling`` head's mixer (32 heads of 128; the taps, the decay and the head
    norm inside) on ``delta_windows`` windows of 16 against the mixer's XLA
    path (``kda_one_chunk`` its core), with the core a trace would pick
    there; and the state-space window kernel at the ``falconh1`` head's
    mixer (32 heads of 128, a state of 256 in 2 groups; the taps, the gate
    and the grouped norm inside) on ``ssd_windows`` windows of 16 against
    the mixer's XLA path (``ssd_one_chunk`` its core), with the core a trace
    would pick there; and the two stream kernels of the ``xing`` head's residual path
    (four streams of 3,584, 20 rounds) on ``stream_tiles`` tiles of 128
    positions against the ``jax.numpy`` functions, with the path a trace
    would pick there; and the blocked latent core of the ``longcat`` head's
    attention (16 heads of 128 + 64 / 128, interleaved rotary pairs) on one
    window of ``latent_window`` positions against the einsums in query
    blocks, with the core a trace would pick there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from igaming_platform_tpu.core.features import NUM_FEATURES
    from igaming_platform_tpu.models import decoder_parts, expert_layer, keye_backbone
    from igaming_platform_tpu.models.gbdt import gbdt_raw, init_gbdt
    from igaming_platform_tpu.ops.gbdt_matmul import gbdt_raw_matmul, precompute_selector
    from igaming_platform_tpu.ops.pallas import flash_attention as fa
    from igaming_platform_tpu.ops.pallas import grouped_experts as ge
    from igaming_platform_tpu.ops.pallas.gbdt_kernel import gbdt_raw_pallas

    report: dict = {"device": device_stamp(), "interpret": interpret}

    def einsum_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    def qkv(shape):
        ks = jax.random.split(jax.random.key(shape[2]), 3)
        return [jax.random.normal(k, shape, jnp.float32) for k in ks]

    for shape in attention_shapes:
        q, k, v = qkv(shape)
        out = fa.flash_attention(q, k, v, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(einsum_ref)(q, k, v)
        err = float(jnp.max(jnp.abs(out - want)))
        variant = "resident" if shape[2] <= fa._RESIDENT_MAX_S else "tiled"
        report[f"flash_fwd_{variant}_S{shape[2]}_Dh{shape[3]}"] = err
        check(bool(jnp.all(jnp.isfinite(out))) and err <= ATTN_FWD_TOL,
              f"flash forward {shape}: max err {err} > {ATTN_FWD_TOL}")

    q, k, v = qkv(backward_shape)
    grad_flash = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, interpret=interpret) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        grad_ref = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(einsum_ref(q, k, v) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
    errs = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(grad_flash, grad_ref)]
    report[f"flash_bwd_S{backward_shape[2]}_Dh{backward_shape[3]}"] = errs
    check(max(errs) <= ATTN_BWD_TOL,
          f"flash backward {backward_shape}: max err {errs} > {ATTN_BWD_TOL}")

    forest = init_gbdt(jax.random.key(0))
    x = np.random.default_rng(0).random(
        (gbdt_batch, NUM_FEATURES)).astype(np.float32)
    sel = jnp.asarray(precompute_selector(np.asarray(forest["feat"]), NUM_FEATURES))
    got = np.asarray(gbdt_raw_pallas(forest, x, sel=sel, tile_b=gbdt_tile,
                                     interpret=interpret))
    for name, fn in (("matmul", lambda: gbdt_raw_matmul(forest, sel, x)),
                     ("gather", lambda: gbdt_raw(forest, x))):
        err = float(np.max(np.abs(got - np.asarray(jax.jit(fn)()))))
        report[f"gbdt_vs_{name}"] = err
        check(err <= GBDT_TOL, f"GBDT kernel vs {name} form: {err} > {GBDT_TOL}")

    rows, hidden, width, experts = expert_shape
    ks = jax.random.split(jax.random.key(rows), 5)

    def stacked(key, fan_in, fan_out):
        return jax.lax.map(
            lambda k: (jax.random.normal(k, (fan_in, fan_out), jnp.float32)
                       * fan_in ** -0.5).astype(jnp.bfloat16),
            jax.random.split(key, experts))

    xs = jax.random.normal(ks[0], (rows, hidden), jnp.float32).astype(jnp.bfloat16)
    wg, wu = stacked(ks[1], hidden, width), stacked(ks[2], hidden, width)
    wd = stacked(ks[3], width, hidden)
    def seeded_sizes(key, rows, experts):
        """A seeded routing with its skew: every row one expert, some none."""
        return jnp.bincount(jax.random.categorical(
            key, jnp.linspace(0.0, 3.0, experts).at[1].set(-jnp.inf),
            shape=(rows,)), length=experts).astype(jnp.int32)

    sizes = seeded_sizes(ks[4], rows, experts)
    check(ge.supports(xs, wg), f"grouped experts: {expert_shape} not supported")

    def ragged(lhs, w):
        return jax.lax.ragged_dot(lhs, w, sizes,
                                  preferred_element_type=jnp.float32)

    # arguments, not closures: a captured 0.4 GB weight is a constant of
    # the executable
    mid_ref = jax.jit(lambda xs, wg, wu: (
        jax.nn.silu(ragged(xs, wg)) * ragged(xs, wu)).astype(jnp.bfloat16))(
            xs, wg, wu)
    mid = ge.gate_up(xs, wg, wu, sizes, interpret=interpret)
    ys = ge.down(mid_ref, wd, sizes, interpret=interpret)
    ys_ref = jax.jit(ragged)(mid_ref, wd)
    mid32, mid_ref32 = mid.astype(jnp.float32), mid_ref.astype(jnp.float32)
    errs = [float(jnp.max(jnp.abs(mid32 - mid_ref32)) / jnp.max(jnp.abs(mid_ref32))),
            float(jnp.max(jnp.abs(ys - ys_ref)) / jnp.max(jnp.abs(ys_ref)))]
    # the same numbers with every row one piece of memory, as ``combine`` reads them
    whole = ge.down(mid_ref, wd, sizes, whole_rows=True, interpret=interpret)
    errs.append(float(jnp.max(jnp.abs(whole.reshape(ys.shape) - ys))))
    report[f"grouped_experts_M{rows}_E{experts}"] = errs
    check(max(errs) <= EXPERTS_TOL,
          f"grouped experts {expert_shape}: max err {errs} > {EXPERTS_TOL}")

    def fed(xs, wg, wu, sizes, top_k):
        """How the kernels are fed at this shape (``ge.feed``); where
        ``gate_up`` brings its rows in itself, that it gives the bits it
        gives on a sorted copy; and step 0's count for this seeded routing
        (PERF.md, PR 44): the experts whose first visit finds their gate
        and up not landed, with two weight slots and with the ring's, by
        the kernel's own rule at a v5e's published peaks."""
        from chipbench.peaks import PEAKS

        m, hidden = xs.shape
        e, _, width = wg.shape
        positions = m // top_k
        x = xs[:positions]
        at = (jax.random.permutation(ks[4], m) // top_k).astype(jnp.int32)
        out = {"feed": ge.feed(m, hidden, e, width, positions)}
        if ge.takes_rows(x, at, wg):
            same = bool(jnp.all(
                ge.gate_up(x, wg, wu, sizes, rows=at, interpret=interpret)
                == ge.gate_up(x[at], wg, wu, sizes, interpret=interpret)))
            out["rows_in_kernel_same_bits"] = same
            check(same, f"gate_up {xs.shape}: rows in-kernel and gathered differ")
        peaks = PEAKS["TPU v5 lite"]
        fetch_us = 2 * hidden * width * 2 / peaks["bytes_per_s"] * 1e6
        row_us = 2 * 2 * hidden * width / peaks["flops_per_s"] * 1e6
        ring = ge._slots(wg, wu)
        out["first_visits_that_wait"] = {
            f"{n}_slots": ge.first_visits_that_wait(np.asarray(sizes), n,
                                                    fetch_us, row_us)[0]
            for n in sorted({2, ring})}
        out["experts_with_rows"] = int(jnp.sum(sizes > 0))
        return out

    report[f"grouped_experts_fed_M{rows}_E{experts}"] = fed(xs, wg, wu, sizes, top_k)

    def way_back(label, ys, at, weights, take, want):
        """``combine`` against the XLA expressions it replaces."""
        picked = _said_by_the_expert_layer(
            lambda: expert_layer._combine_by_kernel(ys, at, take))
        got = ge.combine(ys, at, weights, take, interpret=interpret)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        report[label] = {"max_err": err, "way_back": picked[0]}
        check(bool(jnp.all(jnp.isfinite(got))) and err <= COMBINE_TOL,
              f"{label}: max err {err} > {COMBINE_TOL}")

    ks = jax.random.split(jax.random.key(top_k), 4)
    positions = rows // top_k
    at = jax.random.permutation(ks[0], rows).astype(jnp.int32).reshape(
        positions, top_k)
    weights = jax.random.uniform(ks[1], at.shape, jnp.float32)
    way_back(f"combine_M{rows}_H{hidden}", whole, at, weights, None, jax.jit(
        lambda ys, at, w: jnp.sum(ys[at.reshape(-1)].reshape(*at.shape, -1)
                                  * w[..., None], axis=1))(ys, at, weights))
    del xs, wg, wu, wd, mid, mid_ref, ys, ys_ref, whole

    rows2, hidden2, width2, experts, k2 = second_shape
    report[f"grouped_experts_fed_M{rows2}_E{experts}"] = fed(
        jax.random.normal(ks[0], (rows2, hidden2), jnp.float32).astype(jnp.bfloat16),
        stacked(ks[1], hidden2, width2), stacked(ks[2], hidden2, width2),
        seeded_sizes(ks[3], rows2, experts), k2)

    rows, hidden, positions, taken = share_shape
    slots = jax.random.permutation(ks[2], positions * top_k)[:taken]
    take = jnp.zeros((positions * top_k,), bool).at[slots].set(True)
    at = jnp.full((positions * top_k,), rows - 1, jnp.int32).at[slots].set(
        jax.random.permutation(ks[3], rows)[:taken].astype(jnp.int32))
    take, at = take.reshape(positions, top_k), at.reshape(positions, top_k)
    ys = jax.random.normal(ks[0], (rows, hidden), jnp.float32)
    weights = jax.random.uniform(ks[1], at.shape, jnp.float32)

    def a_slot_a_gather(ys, at, take, w):
        y = jnp.zeros((positions, hidden), jnp.float32)
        for j in range(top_k):
            y = y + jnp.where(take[:, j, None], ys[at[:, j]], 0.0) * w[:, j, None]
        return y

    want = jax.jit(a_slot_a_gather)(ys, at, take, weights)
    # a row no taken slot names holds a NaN: it must reach nothing
    owed = jnp.zeros((rows,), bool).at[jnp.where(take, at, rows).reshape(-1)].set(
        True, mode="drop")
    way_back(f"combine_share_M{rows}_H{hidden}",
             jnp.where(owed[:, None], ys, jnp.nan), at, weights, take, want)

    from igaming_platform_tpu.ops.pallas import window_attention as wa

    cfg, t = keye_backbone.BackboneConfig(), 16
    n = grouped_windows * t
    ks = jax.random.split(jax.random.key(n), 5)
    q = jax.random.normal(ks[0], (n, cfg.heads * cfg.head_dim), jnp.float32) * 3
    k, v = (jax.random.normal(key, (n, cfg.kv_heads * cfg.head_dim),
                              jnp.float32).astype(jnp.bfloat16) for key in ks[1:3])
    cos, sin = (a.reshape(n, -1) for a in decoder_parts.mrope_angles(
        jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, grouped_windows, t)),
        cfg.head_dim, cfg.mrope_section, cfg.rope_theta))
    gain = 1 + 0.1 * jax.random.normal(ks[3], (cfg.head_dim,), jnp.float32)
    keep = ((jax.random.bernoulli(ks[4], 0.5, (grouped_windows, t, t))
             | jnp.eye(t, dtype=bool)) & jnp.tril(jnp.ones((t, t), bool))
            ).reshape(n, t)
    widths = dict(heads=cfg.heads, kv_heads=cfg.kv_heads, window=t, eps=cfg.eps)
    picked = _said_by_the_expert_layer(
        lambda: keye_backbone._attention_core(n, keep, cfg, t))
    # q and v channel-major, as ``wq^T a^T`` and ``wv^T a^T`` leave them
    got = wa.grouped_window_attention(q.T, k, v.T, cos, sin, gain, keep,
                                      **widths, interpret=interpret).T
    want = jax.jit(functools.partial(keye_backbone._core_by_einsums, **widths))(
        q, k, v, cos, sin, gain, keep)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    # handed no mask, as ``attention`` calls it where ``topk`` covers the
    # window and the indexer is not traced (both ``keye`` cells): the causal
    # rule alone, to the bits of the causal mask handed over
    causal = jnp.tile(jnp.tril(jnp.ones((t, t), bool)), (grouped_windows, 1))
    alone, handed = (wa.grouped_window_attention(
        q.T, k, v.T, cos, sin, gain, mask, **widths, interpret=interpret)
        for mask in (None, causal))
    not_traced = _said_by_the_expert_layer(
        lambda: keye_backbone._attention_core(n, None, cfg, t))
    same = bool(jnp.all(jnp.isfinite(alone))) and bool(jnp.array_equal(alone, handed))
    report[f"grouped_attention_W{grouped_windows}"] = {
        "max_err": err, "core": picked[0], "core_with_no_mask": not_traced[0],
        "no_mask_same_bits_as_causal": same}
    check(bool(jnp.all(jnp.isfinite(got))) and err <= BACKBONE_TOL,
          f"grouped attention on {grouped_windows} windows: max err {err} "
          f"> {BACKBONE_TOL}")
    check(same, f"grouped attention on {grouped_windows} windows: handed no mask it "
          "differs from the causal mask handed over")

    from igaming_platform_tpu.models import ling_backbone
    from igaming_platform_tpu.ops.pallas import delta_window as dw

    cfg = ling_backbone.LingConfig()
    n, c = delta_windows * t, cfg.heads * cfg.head_dim
    ks = jax.random.split(jax.random.key(n + 1), 11)
    q, k, v, f = (jax.random.normal(key, (n, c), jnp.float32) for key in ks[:4])
    beta = jax.random.normal(ks[4], (n, cfg.heads), jnp.float32)
    layer = {"gn": 1.0 + 0.1 * jax.random.normal(ks[5], (cfg.head_dim,), jnp.float32),
             "a_log": jnp.log(jax.random.uniform(ks[6], (cfg.heads,), jnp.float32,
                                                 0.5, 1.5)),
             "dt_bias": jax.random.normal(ks[7], (c,), jnp.float32) - 1.0}
    for name, key in zip(("tq", "tk", "tv"), ks[8:], strict=True):
        layer[name] = jax.random.normal(key, (c, cfg.conv_taps), jnp.float32) * 0.5
    picked = _said_by_the_expert_layer(
        lambda: ling_backbone._core_is_the_kernel(n, cfg, t))
    got = dw.delta_window(
        q, k, v, f, beta, layer["a_log"], layer["dt_bias"],
        (layer["tq"], layer["tk"], layer["tv"]), (layer["gn"], cfg.eps),
        heads=cfg.heads, window=t, lower_bound=cfg.gate_lower_bound,
        interpret=interpret)
    want = jax.jit(lambda *a: ling_backbone._core_by_xla(*a, layer, cfg, t))(
        q, k, v, f, beta)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    report[f"delta_window_W{delta_windows}"] = {"max_err": err, "core": picked[0]}
    check(bool(jnp.all(jnp.isfinite(got))) and err <= BACKBONE_TOL,
          f"delta rule on {delta_windows} windows: max err {err} > {BACKBONE_TOL}")

    from igaming_platform_tpu.models import falconh1_backbone
    from igaming_platform_tpu.ops.pallas import ssd_window as sw

    cfg = falconh1_backbone.FalconH1Config()
    n = ssd_windows * t
    width, _, bc, _, nh = cfg.segments
    ks = jax.random.split(jax.random.key(n + 3), 7)
    p = jax.random.normal(ks[0], (n, sum(cfg.segments)), jnp.float32)
    layer = {"taps": jax.random.normal(ks[1], (width + 2 * bc, cfg.conv_taps),
                                       jnp.float32) * 0.5,
             "conv_b": jax.random.normal(ks[2], (width + 2 * bc,), jnp.float32) * 0.25,
             "dt_bias": jax.random.normal(ks[3], (nh,), jnp.float32) - 1.0,
             "a_log": jnp.log(jax.random.uniform(ks[4], (nh,), jnp.float32, 1.0, 16.0)),
             "d_skip": 1.0 + 0.5 * jax.random.normal(ks[5], (nh,), jnp.float32),
             "gn": 1.0 + 0.1 * jax.random.normal(ks[6], (width,), jnp.float32)}
    picked = _said_by_the_expert_layer(
        lambda: falconh1_backbone._core_is_the_kernel(n, cfg, t))
    got = sw.ssd_window(
        p, layer["taps"], layer["conv_b"], layer["dt_bias"], layer["a_log"],
        layer["d_skip"], layer["gn"], heads=nh, state=cfg.ssm_state,
        groups=cfg.ssm_groups, window=t, eps=cfg.eps, interpret=interpret)
    want = jax.jit(lambda a: falconh1_backbone._core_by_xla(a, layer, cfg, t))(p)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    report[f"ssd_window_W{ssd_windows}"] = {"max_err": err, "core": picked[0]}
    check(bool(jnp.all(jnp.isfinite(got))) and err <= BACKBONE_TOL,
          f"state-space mixer on {ssd_windows} windows: max err {err} > "
          f"{BACKBONE_TOL}")

    from igaming_platform_tpu.models import decoder_parts, xing_backbone
    from igaming_platform_tpu.ops.pallas import hyper_streams as hs

    cfg = xing_backbone.XingConfig()
    n, p = cfg.streams, stream_tiles * hs.TILE
    ks = jax.random.split(jax.random.key(p + 2), n + 2)
    xs = tuple(jax.random.normal(key, (p, cfg.hidden), jnp.float32)
               for key in ks[:n])
    hc = xing_backbone.init_hyper(ks[n], cfg)
    y = jax.random.normal(ks[n + 1], (p, cfg.hidden), jnp.float32)
    picked = _said_by_the_expert_layer(lambda: xing_backbone.residual_path(p, cfg))
    u, maps = hs.maps_and_read(xs, hc, cfg, interpret=interpret)
    left, squares = hs.write(xs, maps, y, interpret=interpret)

    def plain(xs, hc, y):
        pre, post, res = decoder_parts.hyper_maps(xs, hc, cfg)
        left = decoder_parts.hyper_write(xs, res, post, y)
        return (decoder_parts.hyper_read(xs, pre), *left,
                decoder_parts.stream_squares(left))

    err = max(float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
              for got, want in zip((u, *left, squares), jax.jit(plain)(xs, hc, y),
                                   strict=True))
    report[f"hyper_streams_T{stream_tiles}"] = {"max_err": err, "path": picked[0]}
    check(err <= STREAMS_TOL,
          f"stream kernels on {stream_tiles} tiles: max err {err} > {STREAMS_TOL}")

    from igaming_platform_tpu.models import mellum_backbone
    from igaming_platform_tpu.ops.pallas import block_attention as ba

    # the blocked attention core at the ``mellum`` head's attention (32 / 4
    # heads of 128) on one window of ``block_window`` positions, a sliding
    # layer and a full one, against the einsum form in query blocks
    cfg, t = mellum_backbone.MellumConfig(), block_window
    ks = jax.random.split(jax.random.key(t + 5), 4)
    q = jax.random.normal(ks[0], (t, cfg.heads * cfg.head_dim), jnp.float32) * 3
    k, v = (jax.random.normal(key, (t, cfg.kv_heads * cfg.head_dim),
                              jnp.float32).astype(jnp.bfloat16) for key in ks[1:3])
    gain = 1 + 0.1 * jax.random.normal(ks[3], (cfg.head_dim,), jnp.float32)
    tables = mellum_backbone.angle_tables(cfg, t)
    cores = {}
    for kind in dict.fromkeys(cfg.layer_types):
        widths = dict(heads=cfg.heads, kv_heads=cfg.kv_heads, window=t,
                      band=mellum_backbone.band_of(kind, cfg), eps=cfg.eps)
        picked = _said_by_the_expert_layer(
            lambda kind=kind: mellum_backbone._attention_core(t, kind, cfg, t))
        got = ba.block_attention(q, k, v, *tables[kind], gain, **widths,
                                 interpret=interpret)
        want = jax.jit(functools.partial(mellum_backbone.core_by_einsums, **widths))(
            q, k, v, *tables[kind], gain)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                    / jnp.max(jnp.abs(want)))
        cores[kind] = {"max_err": err, "core": picked[0]}
        check(bool(jnp.all(jnp.isfinite(got))) and err <= BACKBONE_TOL,
              f"blocked attention ({kind}) on a window of {t}: max err {err} "
              f"> {BACKBONE_TOL}")
    report[f"block_attention_T{t}"] = cores

    from igaming_platform_tpu.models import longcat_backbone

    # the blocked latent core at the ``longcat`` head's attention (16 heads
    # of 128 + 64 / 128, interleaved rotary pairs) on one window of
    # ``latent_window`` positions against the einsums in query blocks, with
    # the core a trace would pick there
    cfg, t = longcat_backbone.LongcatConfig(), latent_window
    widths = dict(heads=cfg.heads, nope=cfg.nope_dim, rope=cfg.rope_dim,
                  dv=cfg.v_dim, window=t)
    ks = jax.random.split(jax.random.key(t + 7), 3)
    q = jax.random.normal(ks[0], (t, cfg.heads * (cfg.nope_dim + cfg.rope_dim)),
                          jnp.float32) * 2
    kvb = jax.random.normal(ks[1], (t, cfg.heads * (cfg.nope_dim + cfg.v_dim)),
                            jnp.float32).astype(jnp.bfloat16)
    k_rope = jax.random.normal(ks[2], (t, cfg.rope_dim),
                               jnp.float32).astype(jnp.bfloat16)
    cos, sin = (x.reshape(t, -1) for x in decoder_parts.rope_angles(
        1, t, cfg.rope_dim, cfg.rope_theta))
    picked = _said_by_the_expert_layer(
        lambda: decoder_parts.latent_attention_core(q, kvb, cfg, t, interleave=True))
    got = ba.latent_block_attention(q, kvb, k_rope, cos, sin, **widths,
                                    interleave=True, interpret=interpret)
    want = jax.jit(functools.partial(decoder_parts.latent_core_by_einsums, **widths,
                                     interleave=True))(q, kvb, k_rope, cos, sin)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    report[f"latent_block_attention_T{t}"] = {"max_err": err, "core": picked[0]}
    check(bool(jnp.all(jnp.isfinite(got))) and err <= BACKBONE_TOL,
          f"blocked latent attention on a window of {t}: max err {err} "
          f"> {BACKBONE_TOL}")

    from igaming_platform_tpu.models import phi4flash_backbone
    from igaming_platform_tpu.ops.pallas import selective_scan as scan

    # the selective scan at the ``phi4flash`` head's Mamba-1 mixer (5,120
    # channels, a state of 16) on two windows of ``scan_window`` positions
    # against the chunked form, with the core a trace would pick there
    cfg, t = phi4flash_backbone.Phi4FlashConfig(), scan_window
    ks = jax.random.split(jax.random.key(t + 6), 5)
    wide, narrow = (2 * t, cfg.ssm_width), (2 * t, cfg.ssm_state)
    x = jax.random.normal(ks[0], wide, jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], wide, jnp.float32) - 4.0)
    bm, cm = (jax.random.normal(key, narrow, jnp.float32) for key in ks[2:4])
    a_t = -jnp.broadcast_to(jnp.arange(1.0, cfg.ssm_state + 1)[:, None],
                            (cfg.ssm_state, cfg.ssm_width))
    d = 1 + 0.1 * jax.random.normal(ks[4], (cfg.ssm_width,), jnp.float32)
    picked = _said_by_the_expert_layer(
        lambda: phi4flash_backbone._scan_core(2 * t, cfg, t))
    got = scan.selective_scan(x, dt, bm, cm, a_t, d, window=t,
                              interpret=interpret)
    want = jax.jit(functools.partial(phi4flash_backbone.scan_by_chunks, window=t,
                                     chunk=cfg.scan_chunk))(x, dt, bm, cm, a_t, d)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    report[f"selective_scan_T{t}"] = {"max_err": err, "core": picked[0]}
    check(bool(jnp.all(jnp.isfinite(got))) and err <= STREAMS_TOL,
          f"selective scan on 2 windows of {t}: max err {err} > {STREAMS_TOL}")
    return report


# ---------------------------------------------------------------------------
# Phase: backbone


# SESSION_HEAD name -> (its configuration file, its reference under
# chipbench/heads/, its module under models/, and the parts of it that pick
# a core while tracing, each with the core it must pick on a TPU at the
# published widths): the backbones ``phase_backbone`` runs, each at the
# sizes of its row in models/session_heads.HEADS. ``pangu``'s share of wide
# experts is past the grouped kernels' VMEM (one expert's gate and up are
# 63 MB), so its products are XLA's; ``lfm2``'s attention is einsums and
# picks nothing,
# and its expert kernels say how they are fed (the ring's slots of
# ``gate_up`` / ``down``, the rows brought in by ``gate_up`` itself);
# ``falconh1`` has no expert layer, and its state-space core says which form
# it runs (since PR 55 the window kernel of ops/pallas/ssd_window.py: the
# taps, the dual form over the one chunk a window is, the gate and the
# grouped norm as one call a layer).
PHI4FLASH_EINSUMS = (
    "einsum in query blocks (differential, 40/20 of 64, values of 128; window "
    "2048 in blocks of 512, band={band}: {visited} of 16 key blocks; the block "
    "kernel takes heads of whole 128-lane vregs, values as wide as keys and "
    "its own head norm and rotary)")
BACKBONES = {
    "pangu": ("risk-seqhead-openpangu-ultra-moe-718b", "openpangu_ultra",
              "pangu_backbone",
              {"expert_core": "xla-ragged-dot", "way_back": "pallas-rows",
               "attention_core": "pallas-windows"}),
    "lfm2": ("risk-seqhead-lfm2-24b-a2b", "lfm2_24b_a2b", "lfm2_backbone",
             {"expert_core": "pallas-grouped (tm=256, ts=64, slots=4/4, "
                             "rows=in-kernel)", "way_back": "pallas-rows"}),
    "falconh1": ("risk-seqhead-falcon-h1-34b", "falcon_h1_34b",
                 "falconh1_backbone",
                 {"ssm_core": "window kernel (tile=128, 32 heads of 128, state "
                              "256 in 2 groups, window 16, taps=4, gate and "
                              "norm inside)"}),
    "ling": ("risk-seqhead-ling-3.0-flash", "ling_3_flash", "ling_backbone",
             {"linear_core": "pallas-windows (32 heads of 128, window 16, "
                             "prologue=taps, norm=inside)",
              "expert_core": "pallas-grouped (tm=256, ts=64, slots=4/4, "
                             "rows=gathered)", "way_back": "pallas-rows",
              "attention_core": "xla-einsum (interleaved rotary pairs: the "
                                "window kernel turns by halves)"}),
    # every expert held and the padding left out: the share's passes at a
    # third shape (hidden 3584: the rows gathered, 4,608 a pass)
    "xing": ("risk-seqhead-xing4.0-29b-a4b", "xing4_29b_a4b", "xing_backbone",
             {"residual_path": "pallas-streams (tile=128, 4 streams, 20 "
                               "Sinkhorn rounds)",
              "expert_core": "pallas-grouped (tm=256, ts=64, slots=3/4, "
                             "rows=gathered)", "way_back": "pallas-rows",
              "attention_core": "pallas-windows"}),
    # windows of 4,096 events, four times the band: the blocked core takes
    # both kinds of layer; hidden 2304 is 18 lane tiles, which ``down``
    # writes and ``combine`` copies at a row pitch of 24 sublanes (PR 58)
    "mellum": ("risk-seqhead-mellum2-12b-a2.5b", "mellum2_12b_a2_5b",
               "mellum_backbone",
               {"window_core": "pallas-blocks (grouped 32/4 of 128, window 4096 "
                               "in blocks of 512, band=1024: 21 of 64 key blocks)",
                "full_core": "pallas-blocks (grouped 32/4 of 128, window 4096 in "
                             "blocks of 512, band=None: 36 of 64 key blocks)",
                "expert_core": "pallas-grouped (tm=256, ts=64, slots=4/4, "
                               "rows=gathered)", "way_back": "pallas-rows"}),
    # the model whole, 32 layers: the scan as one kernel a Mamba layer, the
    # state in VMEM over windows of 2,048 events; differential attention
    # (heads of 64, values of 128) by einsums in query blocks on a TPU too
    "phi4flash": ("risk-seqhead-phi-4-mini-flash", "phi4_mini_flash",
                  "phi4flash_backbone",
                  {"ssm_core": "pallas-scan (tile=512, blocks of 256, the state "
                               "in VMEM; 5120 channels, state 16, window 2048)",
                   "window_core": PHI4FLASH_EINSUMS.format(
                       band=512, visited=7),
                   "full_core": PHI4FLASH_EINSUMS.format(
                       band=None, visited=10)}),
}
CORE_LINES = {"expert_core": "expert core", "way_back": "combine",
              "attention_core": "attention core", "ssm_core": "state-space core",
              "linear_core": "linear-attention core",
              "residual_path": "residual path",
              "window_core": "attention core (window)",
              "full_core": "attention core (full)"}


def phase_backbone(*, head_name: str = "pangu", cfg=None,
                   config: dict | None = None, rows: int = 32,
                   seed: int = 36, events: int = 16) -> dict:
    """A backbone session head at its published widths against its plain
    reference (float32 at ``highest`` over bfloat16-rounded operands) on
    one block of ``rows`` windows, and which cores ran its expert layer's
    grouped products, their way back and, where the head has one to pick,
    the core of attention (each chosen while tracing). ``pangu``: one
    dense and four expert layers of latent attention, 8 of 256 routed
    experts held, 6.23 GB (chipbench/heads/openpangu_ultra.py); ``lfm2``:
    four gated short convolutions and one grouped-query attention layer,
    a dense MLP and four layers of 64 experts, every one held, 5.13 GB
    (chipbench/heads/lfm2_24b_a2b.py), whose expert layer is the three
    Pallas kernels at their second shape; ``falconh1``: four layers of a
    Mamba-2 mixer beside grouped-query attention and a dense SwiGLU of
    21,504, no expert, 3.44 GB (chipbench/heads/falcon_h1_34b.py), whose
    state-space core runs in its dual form (on a TPU inside the window
    kernel of ops/pallas/ssd_window.py) against the reference's
    recurrence; ``ling``: one dense and six expert layers, five Kimi Delta
    Attention layers to one of latent attention, a shared expert beside 64
    of 512 group-routed experts held, 5.53 GB
    (chipbench/heads/ling_3_flash.py), whose delta rule runs in its
    one-chunk form (on a TPU inside the window kernel of
    ops/pallas/delta_window.py) against the reference's recurrence;
    ``xing``: one dense and four expert layers of latent attention with
    YaRN under hyper-connections of four residual streams, a shared expert
    beside 64 bias-chosen experts, every one held, 6.22 GB
    (chipbench/heads/xing4_29b_a4b.py), whose maps the program computes
    positions along the lanes against the reference's stream by stream;
    ``mellum``: three sliding-window layers of 1,024 keys and one full one
    with a rotary table a kind, 64 experts, every one held, 3.34 GB
    (chipbench/heads/mellum2_12b_a2_5b.py), run on ``events`` = 4,096
    positions a window so that the band clips (the others' windows are 16);
    ``phi4flash``: Phi-4-mini-flash-reasoning whole, 32 layers (nine Mamba-1
    scans, eight band layers and one full layer of differential attention,
    then seven Gated Memory Units and seven cross-attention layers at the
    scored position only), 6.68 GB (chipbench/heads/phi4_mini_flash.py, which
    runs every layer at every position), on ``events`` = 2,048."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference, validate
    from igaming_platform_tpu.models import session_heads

    config_name, reference_name, module, on_tpu = BACKBONES[head_name]
    scores = importlib.import_module(
        f"igaming_platform_tpu.models.{module}").backbone_scores
    published = cfg is None
    cfg = cfg or session_heads.HEADS[head_name].config
    config = config or validate.load_data("configs", config_name)
    head = validate.load_code("heads", reference_name)
    params = head.make_params(seed, config)
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, events + 1, rows).astype(np.int32)
    win = rng.normal(0.0, 1.0, (rows, events, cfg.in_dim)).astype(np.float32)
    win *= (np.arange(events)[None, :] < lengths[:, None])[..., None]

    got = []
    said = _said_by_the_expert_layer(lambda: got.append(np.asarray(jax.jit(
        lambda p, w, l: scores(p, w, l, cfg))(
            params, jnp.asarray(win), jnp.asarray(lengths)))))
    got = got[0]
    want = head.forward(params, win, lengths, reference.rounder(
        jnp.dtype(cfg.operand_dtype).name))
    err = float(np.max(np.abs(got - want)))
    picked = {part: next((m for m in said if m.startswith(line + ": ")), None)
              for part, line in CORE_LINES.items()}
    report = {"device": device_stamp(), "head": head_name, "rows": rows,
              "max_err": err,
              "resident_bytes": sum(int(a.nbytes) for a in jax.tree.leaves(params)),
              **picked, "scores_spread": float(np.std(want))}
    del params
    gc.collect()
    check(bool(np.all(np.isfinite(got))) and err <= BACKBONE_TOL,
          f"{head_name} head vs its reference on {rows} windows: max err {err} "
          f"> {BACKBONE_TOL}")
    for part, line in CORE_LINES.items():
        check((picked[part] is not None) == (part in on_tpu),
              f"{head_name}: {line} announced {picked[part]!r}")
    if published and jax.default_backend() == "tpu":
        for part, core in on_tpu.items():
            want_line = f"{CORE_LINES[part]}: {core} (backend=tpu)"
            check(picked[part] == want_line,
                  f"{head_name}: {picked[part]!r} where {want_line!r} was expected")
    return report


# ---------------------------------------------------------------------------
# Phase: mesh


def phase_mesh(reference: dict | None, n_devices: int = 4, **server_kwargs) -> dict:
    """The server phase again on a ``data=N`` mesh (MESH_DEVICES=N):
    state shards on N distinct devices at ~1/N bytes each, one dispatch
    per chunk, scores bit-exact against the one-chip run of the same
    RPC sequence. Fewer devices: not run — and says so."""
    import jax

    have = len(jax.devices())
    if have < n_devices:
        return {"device": device_stamp(),
                "result": f"not_run ({have} device)"}
    report = phase_server(mesh_devices=n_devices, reference=reference,
                          **server_kwargs)
    report["result"] = "passed"
    return report


# ---------------------------------------------------------------------------
# Phase: cache


def phase_cache(watcher, cache_dir: str | None) -> dict:
    """Persistent-cache hits and total compile seconds of this run, from
    a process-wide ``obs/runtime_telemetry.CompileWatcher`` (seconds
    include cache retrievals) — a second run in the same directory shows
    hits and a smaller total."""
    entries = 0
    if cache_dir and os.path.isdir(cache_dir):
        entries = sum(1 for e in os.scandir(cache_dir) if e.is_file())
    snap = watcher.snapshot()
    return {"device": device_stamp(), "cache_dir": cache_dir,
            "entries_on_disk": entries,
            "compiles": snap["compiles_total"],
            "compile_s": round(snap["compile_wall_ms_total"] / 1000.0, 2),
            "persistent_cache_hits": snap["persistent_cache_hits"],
            "persistent_cache_misses": snap["persistent_cache_misses"]}


# ---------------------------------------------------------------------------


def _summary_line(name: str, report: dict) -> str:
    dev = report.get("device", {})
    keep = {k: v for k, v in report.items()
            if k not in ("device", "index_trace") and not isinstance(v, (dict, list))}
    return (f"[chip_smoke] {name}: platform={dev.get('platform')} "
            f"kind={dev.get('kind')} count={dev.get('count')} "
            + json.dumps(keep, default=str))


def main() -> int:
    import logging

    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from igaming_platform_tpu.obs.runtime_telemetry import CompileWatcher

    t_start = time.perf_counter()
    watcher = CompileWatcher()  # process-wide: boot compiles included
    watcher.install_listener()
    reports: dict = {}

    def run(name: str, fn, *args, **kwargs) -> dict:
        t0 = time.perf_counter()
        rep = fn(*args, **kwargs)
        rep["seconds"] = round(time.perf_counter() - t0, 2)
        reports[name] = rep
        print(_summary_line(name, rep), flush=True)
        return rep

    env = run("environment", phase_environment)
    run("native", phase_native)
    one_chip = run("server", phase_server)
    reports["trainer"] = one_chip.pop("trainer")  # ran beside the live server
    print(_summary_line("trainer", reports["trainer"]), flush=True)
    run("kernels", phase_kernels)
    run("backbone", phase_backbone)
    run("backbone_lfm2", phase_backbone, head_name="lfm2")
    run("backbone_falconh1", phase_backbone, head_name="falconh1")
    run("backbone_ling", phase_backbone, head_name="ling")
    run("backbone_xing", phase_backbone, head_name="xing")
    run("backbone_mellum", phase_backbone, head_name="mellum", rows=2,
        events=4096)
    run("backbone_phi4flash", phase_backbone, head_name="phi4flash", rows=2,
        events=2048)
    run("mesh", phase_mesh, one_chip)
    run("cache", phase_cache, watcher, env["cache_dir"])

    for rep in reports.values():
        rep.pop("index_trace", None)
    summary = {
        "phases": {k: v.get("result", "passed") for k, v in reports.items()},
        "mesh": reports["mesh"]["result"],
        "wall_s": round(time.perf_counter() - t_start, 1),
        "reports": reports,
        "claim": None,
    }
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_smoke.json"), "w",
              encoding="utf-8") as f:
        json.dump(summary, f, indent=1, default=str)
    print(f"[chip_smoke] mesh: {summary['mesh']}; wall {summary['wall_s']} s; "
          f"compile {reports['cache']['compile_s']} s over "
          f"{reports['cache']['compiles']} programs, "
          f"{reports['cache']['persistent_cache_hits']} persistent-cache hits",
          flush=True)
    print(json.dumps({"ok": True, "device": env["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
