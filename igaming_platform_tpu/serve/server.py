"""Risk service process layer — the main() of the TPU scorer.

Equivalent of /root/reference/services/risk/cmd/main.go:72-258 rebuilt for
the TPU stack: env config -> engine construction -> AOT warm-up -> gRPC
server + health SERVING -> HTTP sidecar (/metrics, /health, /ready,
/debug/thresholds, /debug/score) -> event-consumer bridge -> signal-driven
graceful shutdown (health NOT_SERVING -> drain -> stop). The reference's
commented-out wiring (main.go:98-130) is implemented, not stubbed.
"""

from __future__ import annotations

import importlib
import json
import logging
import os
import signal
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax

from igaming_platform_tpu.core.config import RiskServiceConfig
from igaming_platform_tpu.obs.metrics import ServiceMetrics
from igaming_platform_tpu.serve.abuse import SequenceAbuseDetector
from igaming_platform_tpu.serve.bridge import ScoringBridge
from igaming_platform_tpu.serve.events import InMemoryBroker, resolve_transport
from igaming_platform_tpu.serve.grpc_server import (
    RiskGrpcService,
    _use_wire_fast_path,
    graceful_stop,
    serve_risk,
)
from igaming_platform_tpu.serve.native_store import DEFAULT_MAX_ACCOUNTS
from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine

logger = logging.getLogger(__name__)


def resolve_model_boot(config, ml_backend: str = "mock", params=None):
    """FRAUD_MODEL_PATH -> (ml_backend, params): Orbax checkpoint load
    with the reference's degrade-to-mock-on-missing semantics
    (risk/cmd/main.go:62-63, onnx_model.go:51-59), then the ML_BACKEND
    override (routed validated against the full expert bundle). Shared by
    the RiskServer boot and the multi-host follower — both sides of a
    multi-host mesh MUST resolve identical params."""
    if params is None and config.fraud_model_path:
        import os as _os

        from igaming_platform_tpu.train.checkpoint import restore_params_for_serving

        if _os.path.exists(config.fraud_model_path):
            try:
                params = {"multitask": restore_params_for_serving(config.fraud_model_path)}
                ml_backend = "multitask"
                logger.info("loaded fraud model from %s", config.fraud_model_path)
            except Exception:
                logger.warning(
                    "failed to load model at %s; using mock scorer",
                    config.fraud_model_path, exc_info=True,
                )
        else:
            logger.warning(
                "model path %s not found; using mock scorer", config.fraud_model_path
            )

    # Explicit backend override (ML_BACKEND env): wins over the
    # checkpoint-derived default. "routed" needs the full expert
    # bundle — fail with a config error, not a trace-time crash.
    if config.ml_backend:
        ml_backend = config.ml_backend
    if ml_backend == "routed":
        from igaming_platform_tpu.models.ensemble import ROUTED_PARAM_KEYS

        missing = [
            k for k in ROUTED_PARAM_KEYS
            if not isinstance(params, dict) or (k != "mock" and params.get(k) is None)
        ]
        if missing:
            raise RuntimeError(
                "ML_BACKEND=routed requires a checkpoint bundle with "
                f"params for {ROUTED_PARAM_KEYS}; missing {missing}. "
                "Build one from trained checkpoints (or "
                "models.ensemble.init_routed_params for dev boots)."
            )
    return ml_backend, params


def import_pallas_in_background() -> threading.Thread:
    """Every TPU boot traces Pallas kernels sooner or later (a session
    head's at warm-up, the abuse detector's attention at its first RPC),
    and importing Pallas is ~2 s of Python (measured on a v5e host,
    PERF.md, PR 35). Started just before the native store's allocation,
    seconds of C++ that hold no GIL, the import costs the boot nothing;
    whoever needs the modules first waits on their import locks."""
    thread = threading.Thread(
        target=importlib.import_module, args=("jax.experimental.pallas.tpu",),
        name="import-pallas", daemon=True)
    thread.start()
    return thread


class RiskServer:
    """Assembled risk service: TPU engine + gRPC + HTTP sidecar + bridge."""

    def __init__(
        self,
        config: RiskServiceConfig | None = None,
        *,
        ml_backend: str = "mock",
        params=None,
        mesh=None,
        broker: InMemoryBroker | None = None,
        grpc_port: int | None = None,
        http_port: int | None = None,
        engine_factory=None,
        store_max_accounts: int = DEFAULT_MAX_ACCOUNTS,
    ):
        self.config = config or RiskServiceConfig.from_env()
        self.metrics = ServiceMetrics("risk")

        ml_backend, params = resolve_model_boot(self.config, ml_backend, params)

        # Serving mesh from config: MESH_DEVICES=N shards the scoring batch
        # over the first N devices (DP over ICI); -1 takes every visible
        # device. Default stays single-chip.
        if mesh is None and self.config.mesh_devices:
            from igaming_platform_tpu.parallel.mesh import MeshSpec, create_mesh

            devs = jax.devices()
            n = len(devs) if self.config.mesh_devices == -1 else self.config.mesh_devices
            if n > len(devs):
                raise RuntimeError(f"MESH_DEVICES={n} but only {len(devs)} devices visible")
            seq = max(1, self.config.mesh_seq)
            expert = max(1, self.config.mesh_expert)
            if n % (seq * expert) != 0:
                raise RuntimeError(
                    f"MESH_SEQ({seq}) * MESH_EXPERT({expert}) must divide MESH_DEVICES={n}"
                )
            if n > 1:
                mesh = create_mesh(
                    MeshSpec(data=n // (seq * expert), seq=seq, expert=expert),
                    devices=devs[:n],
                )
                logger.info(
                    "serving mesh: data=%d seq=%d expert=%d over %d devices",
                    n // (seq * expert), seq, expert, n,
                )

        # Feature store: the native C++ core (SURVEY.md §2.2's native
        # ingest bridge). A boot on an accelerator requires it — a host
        # with no g++ must fail here, not serve the chip from the Python
        # store; only an explicit CPU boot may fall back to Python.
        backend = jax.default_backend()
        feature_store = None
        store_name = "python"
        if self.config.feature_store == "redis":
            from igaming_platform_tpu.serve.redis_store import RedisFeatureStore

            feature_store = RedisFeatureStore(self.config.redis_url)
            store_name = f"redis ({self.config.redis_url})"
        elif self.config.feature_store in ("auto", "native"):
            from igaming_platform_tpu.serve.native_store import native_available

            if native_available():
                from igaming_platform_tpu.serve.native_store import NativeFeatureStore

                if backend == "tpu":
                    import_pallas_in_background()
                feature_store = NativeFeatureStore(max_accounts=store_max_accounts)
                store_name = "native"
            elif self.config.feature_store == "native" or backend != "cpu":
                raise RuntimeError(
                    "the native C++ feature store is required "
                    f"(FEATURE_STORE={self.config.feature_store}, "
                    f"backend={backend}) but native/lib could not be "
                    "built on this host — is g++ installed?")

        # Engine (AOT warm-up happens in the constructor, before SERVING).
        # engine_factory lets a deployment swap the engine construction
        # (the multi-host front uses serve/multihost.multihost_engine)
        # while keeping EVERYTHING else — abuse detector, bridge, gRPC,
        # health, sidecar — the stock assembly.
        #
        # Chaos plans (CHAOS_PLAN env, serve/chaos.py) install BEFORE the
        # engine so even warmup runs under injection — loudly logged:
        # a production boot must never silently carry a fault plan.
        from igaming_platform_tpu.serve import chaos as _chaos

        plan = _chaos.install_from_env()
        if plan is not None:
            logger.warning("CHAOS PLAN ACTIVE (seed=%d): %s",
                           plan.seed, sorted(plan.specs))

        if engine_factory is not None:
            def build_engine():
                return engine_factory(
                    self.config.scoring, ml_backend=ml_backend, params=params,
                    batcher_config=self.config.batcher,
                    feature_store=feature_store,
                )
        else:
            def build_engine():
                return TPUScoringEngine(
                    self.config.scoring,
                    ml_backend=ml_backend,
                    params=params,
                    mesh=mesh,
                    batcher_config=self.config.batcher,
                    feature_store=feature_store,
                )

        # Self-healing supervisor (serve/supervisor.py, SUPERVISOR=0 opts
        # out): circuit breakers around the device/multihost/feature-
        # store/AMQP dependencies, a device-step watchdog that rebuilds
        # the engine through build_engine (replaying warmup), and the CPU
        # heuristic fallback tier for open-circuit windows.
        self.supervisor = None
        if os.environ.get("SUPERVISOR", "1") != "0":
            from igaming_platform_tpu.serve.supervisor import (
                ServingSupervisor,
                SupervisedScoringEngine,
            )

            self.supervisor = ServingSupervisor()
            self.engine = SupervisedScoringEngine(
                build_engine, supervisor=self.supervisor)
            inner = self.engine.inner
            if getattr(inner, "supervisor", None) is None and hasattr(
                    inner, "_chan"):
                # A multihost front built by engine_factory: wire its
                # follower-state callbacks into the multihost breaker.
                inner.supervisor = self.supervisor
        else:
            self.engine = build_engine()
        # Sequence-parallel abuse scoring when the mesh has a `seq` axis:
        # ring attention shards each event history across chips (CP).
        seq_sharded = mesh is not None and int(mesh.shape.get("seq", 1)) > 1
        # On the CPU backend the transformer collapses (~80 seq/s) —
        # the abuse path must not silently become the outage:
        # ABUSE_CPU_POLICY picks `heuristic` (default: the reference's
        # own scalar signal class, >=10k checks/s, responses flagged
        # DEGRADED_CPU_HEURISTIC) or `shed` (gRPC UNAVAILABLE + metric).
        abuse_policy = "model"
        if backend == "cpu":
            abuse_policy = os.environ.get("ABUSE_CPU_POLICY", "heuristic")
            logger.warning("abuse path degraded to policy=%s (CPU backend)",
                           abuse_policy)
        self.abuse = SequenceAbuseDetector(
            mesh=mesh if seq_sharded else None,
            seq_mode="ring" if seq_sharded else "dense",
            policy=abuse_policy,
        )
        self.broker = resolve_transport(broker, self.config.rabbitmq_url)
        self.bridge = ScoringBridge(self.engine, self.broker, abuse_detector=self.abuse)

        service = RiskGrpcService(
            self.engine,
            abuse_detector=lambda acct, bonus: self.abuse.check(acct, bonus),
            metrics=self.metrics,
            rate_limit_per_minute=self.config.rate_limit_per_minute,
        )
        # SLO plane + device-runtime telemetry (installed by the service
        # constructor): the server layer adds what only it has — the
        # anomaly->profile trigger (the /debug/profilez capture path,
        # artifacts keyed by the anomalous trace id, cooldown enforced
        # by the telemetry side).
        from igaming_platform_tpu.obs import drift as drift_mod
        from igaming_platform_tpu.obs import slo as slo_mod

        self.slo = slo_mod.get_default()
        self.drift = drift_mod.get_default()
        self.service = service
        self.telemetry = service.telemetry
        if self.telemetry is not None:
            self.telemetry.bind_profile_trigger(self._anomaly_profile_trigger)
        inner = getattr(self.engine, "inner", self.engine)
        logger.info(
            "boot: backend=%s feature_store=%s wire_codec=%s host_tier=%s",
            backend, store_name,
            "native" if _use_wire_fast_path() else "python",
            "built" if getattr(inner, "_fn_host", None) is not None
            else "not built")
        self.grpc_server, self.health, self.grpc_port = serve_risk(
            service, grpc_port if grpc_port is not None else self.config.grpc_port
        )
        if self.supervisor is not None:
            # BROWNOUT flips the gRPC health service to NOT_SERVING;
            # DEGRADED keeps answering (flagged) so LBs keep routing.
            self.supervisor.bind(health=self.health, metrics=self.metrics)
            publisher = getattr(self.bridge, "publisher", None)
            if publisher is not None and hasattr(publisher, "on_publish_result"):
                amqp_breaker = self.supervisor.breaker("amqp")

                def _amqp_result(ok: bool, exc) -> None:
                    if ok:
                        amqp_breaker.record_success()
                    else:
                        amqp_breaker.record_failure(exc)

                publisher.on_publish_result = _amqp_result
        # Durable decision ledger (serve/ledger.py): LEDGER_DIR opts in.
        # Records append to a local WAL with batched fsync off the hot
        # path and drain to the configured sink (LEDGER_SINK); failures
        # feed the supervisor's `ledger` breaker — never the scoring path.
        self.ledger = None
        ledger_dir = os.environ.get("LEDGER_DIR", "")
        if ledger_dir:
            from igaming_platform_tpu.serve import ledger as ledger_mod

            breaker = (self.supervisor.breaker("ledger")
                       if self.supervisor is not None else None)
            self.ledger = ledger_mod.DecisionLedger(
                ledger_dir, sink=ledger_mod.sink_from_env(),
                breaker=breaker, metrics=self.metrics)
            inner = getattr(self.engine, "inner", self.engine)
            inner.ledger = self.ledger
            if self.supervisor is not None:
                ledger_mod.set_state_provider(lambda: self.supervisor.state)
            logger.info("decision ledger at %s (sink=%s)", ledger_dir,
                        os.environ.get("LEDGER_SINK", "none") or "none")
        # Online learning loop (ONLINE_LOOP=1 opts in): a miner tails
        # the decision WAL for outcome-labeled hard examples, a learner
        # trains the multitask net incrementally on the same device
        # budget, a shadow scorer runs the candidate next to production,
        # and the promotion controller hot-swaps it in (and back out)
        # through the gates in train/gates.py. Config errors fail the
        # boot loudly — a silently-disabled learning loop is drift's
        # best friend.
        self.online = None
        if os.environ.get("ONLINE_LOOP", "") == "1":
            if self.ledger is None:
                raise RuntimeError(
                    "ONLINE_LOOP=1 requires LEDGER_DIR: the miner tails "
                    "the decision WAL for labeled hard examples")
            inner_engine = getattr(self.engine, "inner", self.engine)
            if getattr(inner_engine, "ml_backend", "") != "multitask":
                raise RuntimeError(
                    "ONLINE_LOOP=1 requires the trainable multitask "
                    "backend (ML_BACKEND=multitask); got "
                    f"{getattr(inner_engine, 'ml_backend', None)!r}")
            from igaming_platform_tpu.serve.shadow import ShadowScorer
            from igaming_platform_tpu.train.online import (
                LedgerMiner,
                OnlineLearner,
                OnlineLoop,
            )
            from igaming_platform_tpu.train.promote import PromotionController

            shadow = ShadowScorer(self.engine, metrics=self.metrics)
            inner_engine.shadow = shadow
            # Drift observatory join (obs/drift.py): candidate-vs-prod
            # divergence trends through the same rolling windows as
            # input drift, so a drifting candidate is on the drift
            # dashboard before any promotion gate evaluates it.
            from igaming_platform_tpu.obs import drift as _drift_mod

            drift_engine = _drift_mod.get_default()
            if drift_engine is not None:
                shadow.on_result = drift_engine.note_shadow_result
            controller = PromotionController(
                self.engine, shadow, ledger=self.ledger,
                vault_dir=os.path.join(ledger_dir, "params-vault"),
                metrics=self.metrics)
            self.online = OnlineLoop(
                miner=LedgerMiner(ledger_dir, metrics=self.metrics),
                learner=OnlineLearner(metrics=self.metrics),
                shadow=shadow, controller=controller).start()
            logger.info("online learning loop up (tick=%.1fs)",
                        self.online.tick_s)
        self.http_server, self.http_port = self._start_http(
            http_port if http_port is not None else self.config.http_port
        )
        self.bridge.start()

        # Batch-feature refresh ticker (risk/cmd/main.go:226-236, actually
        # implemented): re-hydrates per-account analytical aggregates from
        # the wallet store — with an immediate first scan so a restarted
        # scorer doesn't serve empty batch features until the first tick.
        self.batch_refresh = None
        batch_source = None
        if self.config.clickhouse_url.startswith("http"):
            # External analytical store (engine.go:127-140's schema over
            # the ClickHouse HTTP interface). tcp:// (the reference's
            # native-protocol default) is NOT selected automatically —
            # set CLICKHOUSE_URL=http://host:8123 to opt in.
            from igaming_platform_tpu.serve.clickhouse import clickhouse_source

            batch_source = clickhouse_source(self.config.clickhouse_url)
            logger.info("batch features from ClickHouse at %s", self.config.clickhouse_url)
        elif self.config.batch_feature_db:
            from igaming_platform_tpu.serve.batch_refresh import wallet_store_source

            batch_source = wallet_store_source(self.config.batch_feature_db)
        if batch_source is not None:
            from igaming_platform_tpu.serve.batch_refresh import BatchFeatureRefreshJob

            self.batch_refresh = BatchFeatureRefreshJob(
                self.engine.features,
                batch_source,
                interval_s=self.config.batch_feature_interval_s,
            )
            try:
                n = self.batch_refresh.refresh_once()
                logger.info("batch features hydrated for %d accounts", n)
            except Exception:
                logger.warning("initial batch-feature refresh failed", exc_info=True)
            self.batch_refresh.start()

        from igaming_platform_tpu.obs.otlp import exporter_from_env

        self.otlp = exporter_from_env("risk")
        if self.otlp is not None:
            # Export loss is a metric, not just a log line.
            self.otlp.on_failure = self.metrics.otlp_export_failures_total.inc
        self._stopped = threading.Event()
        # On-demand device profile capture (/debug/profilez): one at a
        # time — jax.profiler traces cannot nest.
        self._profile_lock = threading.Lock()

        # Device-liveness probe (SURVEY.md §5: "health gate tied to device
        # liveness"): one tiny compiled op, pre-warmed here so /ready never
        # pays a compile.
        import concurrent.futures as _futures

        # Warmed with the SAME argument type device_alive() passes (a
        # Python int): a numpy int32 here compiled a second program at
        # the first /ready.
        self._probe_fn = jax.jit(lambda v: v + 1)
        jax.block_until_ready(self._probe_fn(1))
        self._probe_pool = _futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="device-probe"
        )

        logger.info("risk server up: grpc=%d http=%d", self.grpc_port, self.http_port)

    def device_alive(self, timeout_s: float = 2.0) -> bool:
        """Run the pre-compiled probe op with a deadline; a hung or lost
        device turns /ready false instead of hanging the health check."""
        def probe() -> bool:
            jax.block_until_ready(self._probe_fn(1))
            return True

        try:
            return self._probe_pool.submit(probe).result(timeout=timeout_s)
        except Exception:  # noqa: BLE001 — timeout or device error
            return False

    def capture_profile(self, seconds: float, trace_id: str = "") -> dict:
        """On-demand jax.profiler capture (`/debug/profilez?seconds=S`):
        records a TensorBoard-compatible device trace for ``seconds``
        while live traffic keeps flowing, via the same ``device_trace``
        helper the offline drills use. Bounded at 30 s (the capture
        blocks its HTTP worker thread and profile buffers grow with
        duration); 409 when a capture is already running. ``trace_id``
        keys the artifact directory name so an anomaly-triggered capture
        joins back to its flight entry / SLO exemplar."""
        import re as _re
        import tempfile
        import time as _time

        from igaming_platform_tpu.obs.tracing import device_trace

        seconds = max(0.1, min(float(seconds), 30.0))
        if not self._profile_lock.acquire(blocking=False):
            return {"error": "profile capture already in progress"}
        try:
            suffix = _re.sub(r"[^0-9a-zA-Z_-]", "", trace_id)[:32]
            prefix = (f"igaming-profilez-{suffix}-" if suffix
                      else "igaming-profilez-")
            log_dir = tempfile.mkdtemp(
                prefix=prefix,
                dir=os.environ.get("ANOMALY_PROFILE_DIR") or None)
            with device_trace(log_dir):
                _time.sleep(seconds)
            return {"ok": True, "seconds": seconds, "log_dir": log_dir,
                    "hint": f"tensorboard --logdir {log_dir}"}
        except Exception as exc:  # noqa: BLE001 — capture must not kill serving
            return {"error": f"profile capture failed: {exc}"}
        finally:
            self._profile_lock.release()

    def _anomaly_profile_trigger(self, trace_id: str, stage: str,
                                 duration_ms: float) -> dict:
        """Runtime-telemetry anomaly hook: capture a device profile in
        the BACKGROUND (the hook fires on a serving thread and must not
        block), keyed by the anomalous trace id. Cooldown accounting is
        the telemetry side's job; this only does the capture."""
        seconds = float(os.environ.get("ANOMALY_PROFILE_SECONDS", "1.5"))

        def run() -> None:
            result = self.capture_profile(seconds, trace_id=trace_id)
            self.telemetry.note_capture_result(trace_id, result)
            if "error" in result:
                logger.warning("anomaly profile capture (%s, %s): %s",
                               trace_id, stage, result["error"])
            else:
                logger.warning(
                    "anomaly profile captured: stage=%s trace=%s "
                    "duration_ms=%.1f -> %s", stage, trace_id, duration_ms,
                    result["log_dir"])

        thread = threading.Thread(
            target=run, name="anomaly-profile", daemon=True)
        thread.start()
        return {"seconds": seconds, "async": True}

    # -- HTTP sidecar (main.go:160-202 equivalent) ---------------------------

    def _start_http(self, port: int):
        server_ref = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _send(self, code: int, body: str, content_type: str = "application/json"):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path == "/metrics":
                    # Occupancy gauges (arena buffers, device memory) are
                    # refreshed per scrape so they are scrape-fresh
                    # without a background ticker.
                    tel = getattr(server_ref, "telemetry", None)
                    if tel is not None:
                        tel.refresh_gauges()
                    self._send(200, server_ref.metrics.registry.render_text(), "text/plain")
                elif self.path == "/health":
                    self._send(200, '{"status":"healthy"}')
                elif self.path == "/ready":
                    ready = not server_ref._stopped.is_set()
                    device_ok = server_ref.device_alive() if ready else False
                    self._send(
                        200 if (ready and device_ok) else 503,
                        json.dumps({"ready": ready and device_ok, "device": device_ok}),
                    )
                elif self.path == "/debug/thresholds":
                    block, review = server_ref.engine.get_thresholds()
                    self._send(200, json.dumps({"block": block, "review": review}))
                elif self.path == "/debug/supervisorz":
                    # Serving state machine + per-dependency breakers —
                    # the first stop during a degraded window (runbook:
                    # docs/operations.md "Degraded modes").
                    sup = getattr(server_ref, "supervisor", None)
                    if sup is None:
                        self._send(404, '{"error":"supervisor disabled"}')
                        return
                    snap = sup.snapshot()
                    engine = server_ref.engine
                    snap["rebuilds"] = getattr(engine, "rebuilds", 0)
                    inner = getattr(engine, "inner", engine)
                    snap["degraded_steps"] = getattr(inner, "degraded_steps", 0)
                    chan = getattr(inner, "_chan", None)
                    if chan is not None:
                        snap["followers_alive"] = chan.alive
                        snap["resurrections"] = chan.resurrections
                    self._send(200, json.dumps(snap))
                elif self.path == "/debug/sloz":
                    # SLO engine: burn rates, attainment, budget
                    # attribution, alert timeline (runbook:
                    # docs/operations.md "SLO & fleet view").
                    from igaming_platform_tpu.obs import slo as _slo_mod

                    slo_engine = _slo_mod.get_default()
                    if slo_engine is None:
                        self._send(404, '{"error":"slo engine disabled"}')
                        return
                    self._send(200, json.dumps(slo_engine.snapshot()))
                elif self.path == "/debug/driftz":
                    # Drift & data-quality observatory: rolling-window
                    # sketches vs the pinned reference (PSI/KS per
                    # feature), score calibration, shadow divergence,
                    # and the raise/clear alert timeline (runbook:
                    # docs/operations.md "Drift & data quality").
                    from igaming_platform_tpu.obs import drift as _drift_mod

                    drift_engine = _drift_mod.get_default()
                    if drift_engine is None:
                        self._send(404, '{"error":"drift observatory disabled"}')
                        return
                    self._send(200, json.dumps(drift_engine.snapshot()))
                elif self.path == "/debug/cachez":
                    # Device feature cache incl. the slot-shard
                    # breakdown (per-shard occupancy + HBM budget) —
                    # what each mesh chip actually holds; the router's
                    # pod capacity advertisement scrapes this.
                    inner = getattr(server_ref.engine, "inner",
                                    server_ref.engine)
                    cache = getattr(inner, "cache", None)
                    if cache is None:
                        self._send(404, '{"error":"feature cache disabled"}')
                        return
                    snap = cache.stats()
                    snap["shards"] = cache.shard_stats()
                    sess = getattr(inner, "session", None)
                    if sess is not None:
                        snap["session_shards"] = sess.shard_stats()
                    self._send(200, json.dumps(snap))
                elif self.path == "/debug/sessionz":
                    # Stateful sequence scoring: session-ring occupancy,
                    # warm/cold/bypass row accounting, HBM budget and
                    # head config (runbook: docs/operations.md
                    # "Session state").
                    inner = getattr(server_ref.engine, "inner",
                                    server_ref.engine)
                    sess = getattr(inner, "session", None)
                    if sess is None:
                        self._send(404, '{"error":"session state disabled"}')
                        return
                    self._send(200, json.dumps(sess.snapshot()))
                elif self.path == "/debug/telemetryz":
                    # Device-runtime telemetry: compile events, dispatch
                    # counts, step-time EWMAs, anomaly + auto-profile log.
                    tel = getattr(server_ref, "telemetry", None)
                    if tel is None:
                        self._send(404, '{"error":"telemetry disabled"}')
                        return
                    self._send(200, json.dumps(tel.snapshot()))
                elif self.path == "/debug/deadlinez":
                    # Deadline scheduler plane: lane depths, expiry
                    # sheds, dead-dispatch evidence, the online
                    # step-time model and the burn->shed gate (runbook:
                    # docs/operations.md "Deadline scheduling").
                    inner = getattr(server_ref.engine, "inner",
                                    server_ref.engine)
                    snap_fn = getattr(inner, "deadline_snapshot", None)
                    if snap_fn is None:
                        self._send(404, '{"error":"deadline plane unavailable"}')
                        return
                    snap = snap_fn()
                    svc = getattr(server_ref, "service", None)
                    gate = getattr(svc, "burn_gate", None)
                    if gate is not None:
                        snap["burn_gate"] = gate.stats()
                    self._send(200, json.dumps(snap))
                elif self.path == "/debug/spans":
                    from igaming_platform_tpu.obs.tracing import DEFAULT_COLLECTOR
                    self._send(200, DEFAULT_COLLECTOR.to_json())
                elif self.path == "/debug/ledgerz":
                    # Decision-ledger health: WAL/fsync/drop counters and
                    # the sink cursor (runbook: docs/operations.md
                    # "Audit & replay").
                    led = getattr(server_ref, "ledger", None)
                    if led is None:
                        self._send(404, '{"error":"ledger disabled"}')
                        return
                    self._send(200, json.dumps(led.stats()))
                elif self.path == "/debug/shadowz":
                    # Online-learning loop: shadow divergence/flip-rate
                    # evidence, miner/learner progress, promotion
                    # history + gate tables (runbook: docs/operations.md
                    # "Online learning & promotion").
                    online = getattr(server_ref, "online", None)
                    if online is not None:
                        self._send(200, json.dumps(online.report()))
                        return
                    inner = getattr(server_ref.engine, "inner",
                                    server_ref.engine)
                    shadow = getattr(inner, "shadow", None)
                    if shadow is None:
                        self._send(404, '{"error":"online loop disabled"}')
                        return
                    self._send(200, json.dumps({"shadow": shadow.report()}))
                elif self.path == "/debug/flightz":
                    # Flight recorder: last N requests, each decomposed
                    # into stage durations with its trace id — the first
                    # stop when investigating a slow request.
                    from igaming_platform_tpu.obs.flight import DEFAULT_RECORDER
                    self._send(200, DEFAULT_RECORDER.to_json())
                elif self.path == "/debug/stallz":
                    # Stall watch: the last incidents in which an RPC
                    # stayed open past STALL_DUMP_MS, each with every
                    # thread's stack, open spans and CPU while it lasted,
                    # and the heartbeat's wake-up lateness. An RPC of
                    # seconds starts here, before /debug/flightz, which
                    # sees a request only once it has ended (runbook:
                    # docs/operations.md "Reading a slow request").
                    from igaming_platform_tpu.obs import hostprof as _hostprof_mod

                    self._send(200, json.dumps(
                        _hostprof_mod.get_default().heartbeat.snapshot()))
                elif self.path.startswith("/debug/hostprofz"):
                    # Host-plane cost observatory: per-stage µs/row
                    # table, GC pause accounting, heap gauges and the
                    # sampler's folded stacks. ?format=folded returns
                    # collapsed-stack text (flamegraph.pl/inferno);
                    # ?format=speedscope returns a speedscope.app
                    # profile; default is the JSON snapshot (runbook:
                    # docs/operations.md "Host cost observatory").
                    from urllib.parse import parse_qs, urlparse

                    from igaming_platform_tpu.obs import hostprof as _hostprof_mod

                    hp = _hostprof_mod.get_default()
                    q = parse_qs(urlparse(self.path).query)
                    fmt = q.get("format", ["json"])[0]
                    if fmt == "folded":
                        self._send(200, hp.sampler.to_folded_text(),
                                   "text/plain")
                    elif fmt == "speedscope":
                        self._send(200, json.dumps(hp.sampler.to_speedscope()))
                    else:
                        self._send(200, hp.to_json())
                elif self.path.startswith("/debug/profilez"):
                    from urllib.parse import parse_qs, urlparse

                    q = parse_qs(urlparse(self.path).query)
                    try:
                        seconds = float(q.get("seconds", ["2"])[0])
                    except ValueError:
                        self._send(400, '{"error":"bad seconds"}')
                        return
                    result = server_ref.capture_profile(seconds)
                    self._send(409 if "error" in result else 200,
                               json.dumps(result))
                else:
                    self._send(404, '{"error":"not found"}')

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length).decode() if length else "{}"
                try:
                    payload = json.loads(raw)
                except json.JSONDecodeError:
                    self._send(400, '{"error":"bad json"}')
                    return
                if self.path == "/debug/breakers":
                    # Operator force/clear (runbook): {"dep": "device",
                    # "action": "open"|"clear"|"probe"}, or
                    # {"brownout": "force"|"clear"}.
                    sup = getattr(server_ref, "supervisor", None)
                    if sup is None:
                        self._send(404, '{"error":"supervisor disabled"}')
                        return
                    try:
                        if "brownout" in payload:
                            if payload["brownout"] == "force":
                                sup.force_brownout("operator /debug/breakers")
                            else:
                                sup.clear_brownout()
                        else:
                            sup.force_breaker(str(payload.get("dep", "")),
                                              str(payload.get("action", "")))
                    except (KeyError, ValueError) as exc:
                        self._send(400, json.dumps({"error": str(exc)}))
                        return
                    self._send(200, json.dumps(sup.snapshot()))
                elif self.path == "/debug/thresholds":
                    server_ref.engine.set_thresholds(
                        int(payload.get("block", 80)), int(payload.get("review", 50))
                    )
                    self._send(200, '{"ok":true}')
                elif self.path == "/debug/promotion":
                    # Promotion-controller knobs (runbook): {"action":
                    # "pause"|"resume"|"rollback"|"tick"|
                    # "inject_regression"}. The drill knob exists so the
                    # auto-rollback path is rehearsed, not hoped for.
                    online = getattr(server_ref, "online", None)
                    if online is None:
                        self._send(404, '{"error":"online loop disabled"}')
                        return
                    ctl = online.controller
                    action = str(payload.get("action", ""))
                    try:
                        if action == "pause":
                            ctl.pause()
                        elif action == "resume":
                            ctl.resume()
                        elif action == "rollback":
                            ctl.force_rollback(
                                str(payload.get("reason",
                                                "operator /debug/promotion")))
                        elif action == "inject_regression":
                            ctl.inject_regression()
                        elif action == "tick":
                            online.tick()
                        else:
                            raise ValueError(
                                f"unknown promotion action {action!r} (use "
                                "pause|resume|rollback|inject_regression|tick)")
                    except ValueError as exc:
                        self._send(400, json.dumps({"error": str(exc)}))
                        return
                    self._send(200, json.dumps(ctl.report()))
                elif self.path == "/debug/driftz":
                    # Reference management (runbook): {"action":
                    # "pin_reference"} pins the current rolling window,
                    # {"action": "load"|"save", "path": ...} round-trips
                    # a checkpointed reference (tools/driftref.py mints
                    # one offline from a ledger segment).
                    from igaming_platform_tpu.obs import drift as _drift_mod

                    drift_engine = _drift_mod.get_default()
                    if drift_engine is None:
                        self._send(404, '{"error":"drift observatory disabled"}')
                        return
                    action = str(payload.get("action", ""))
                    try:
                        if action == "pin_reference":
                            min_rows = payload.get("min_rows")
                            ref = drift_engine.pin_reference(
                                source=str(payload.get(
                                    "source", "pinned-via-driftz")),
                                min_rows=(int(min_rows)
                                          if min_rows is not None else None))
                        elif action == "load":
                            ref = drift_engine.load_reference(
                                str(payload["path"]))
                        elif action == "save":
                            ref = drift_engine.reference
                            if ref is None:
                                raise ValueError("no reference pinned")
                            ref.save(str(payload["path"]))
                        else:
                            raise ValueError(
                                f"unknown driftz action {action!r} (use "
                                "pin_reference|load|save)")
                    except (KeyError, ValueError, OSError) as exc:  # noqa: CC04 — surfaced to the caller as a 400 body, not swallowed
                        self._send(400, json.dumps({"error": str(exc)}))
                        return
                    self._send(200, json.dumps({
                        "ok": True, "reference": ref.meta(),
                        "alerts": drift_engine.alerts_active()}))
                elif self.path == "/debug/hostprofz":
                    # Sampler control (the profilez on-demand pattern):
                    # {"action": "start", "hz": 97} begins stack
                    # sampling over the registered scoring threads;
                    # {"action": "stop"} halts it and returns the
                    # summary; {"action": "reset"} zeros the folded
                    # table and Tier A accounting. A second start while
                    # running is a 409, like a busy profilez capture.
                    from igaming_platform_tpu.obs import hostprof as _hostprof_mod

                    hp = _hostprof_mod.get_default()
                    action = str(payload.get("action", ""))
                    if action == "start":
                        try:
                            hz = float(payload.get("hz", 97.0))
                        except (TypeError, ValueError):
                            self._send(400, '{"error":"bad hz"}')
                            return
                        if not hp.sampler.start(hz):
                            self._send(409, json.dumps({
                                "error": "sampler already running or bad hz",
                                "sampler": hp.sampler.snapshot()}))
                            return
                        self._send(200, json.dumps(
                            {"ok": True, "sampler": hp.sampler.snapshot()}))
                    elif action == "stop":
                        self._send(200, json.dumps(
                            {"ok": True, "sampler": hp.sampler.stop()}))
                    elif action == "reset":
                        hp.reset()
                        self._send(200, '{"ok":true}')
                    else:
                        self._send(400, json.dumps({
                            "error": f"unknown hostprofz action {action!r} "
                                     "(use start|stop|reset)"}))
                elif self.path == "/debug/outcomes":
                    # Label backfill (the v2 ledger side-record): the
                    # operational entry for ground-truth outcomes —
                    # chargebacks, manual-review verdicts, cleared
                    # disputes — joined to decisions by decision_id.
                    # Malformed bodies are a 400, not a silent 200, and
                    # the response splits accepted vs UNKNOWN decision
                    # ids so a backfill harness can tell dropped joins
                    # from delivered ones.
                    led = getattr(server_ref, "ledger", None)
                    if led is None:
                        self._send(404, '{"error":"ledger disabled"}')
                        return
                    from igaming_platform_tpu.serve import (
                        ledger as _ledger_mod,
                    )

                    if not isinstance(payload, dict):
                        self._send(400, '{"error":"body must be a JSON object"}')
                        return
                    rows = payload.get("outcomes")
                    if rows is None:
                        rows = [payload]
                    if not isinstance(rows, list):
                        self._send(400, '{"error":"outcomes must be a list"}')
                        return
                    for row in rows:
                        if (not isinstance(row, dict)
                                or not str(row.get("decision_id", ""))):
                            self._send(400, json.dumps({
                                "error": "each outcome needs a non-empty "
                                         "decision_id",
                                "bad_row": repr(row)[:120]}))
                            return
                    accepted = 0
                    unknown = 0
                    for row in rows:
                        did = str(row["decision_id"])
                        if not led.knows_decision(did):
                            # Still appended (the WAL may hold the
                            # decision from before a restart; the miner
                            # joins at-least-once) — but counted, so the
                            # caller sees the join risk.
                            unknown += 1
                        if led.append_outcome(_ledger_mod.OutcomeRecord(
                                decision_id=did,
                                label=1 if row.get("label") else 0,
                                source=str(row.get("source", "manual")),
                                ts_unix=_ledger_mod.wall_clock())):
                            accepted += 1
                    self._send(200, json.dumps({"accepted": accepted,
                                                "unknown": unknown,
                                                "submitted": len(rows)}))
                elif self.path == "/debug/score":
                    resp = server_ref.engine.score(ScoreRequest(
                        account_id=str(payload.get("account_id", "debug")),
                        amount=int(payload.get("amount", 0)),
                        tx_type=str(payload.get("transaction_type", "deposit")),
                        ip=str(payload.get("ip", "")),
                        device_id=str(payload.get("device_id", "")),
                    ))
                    self._send(200, json.dumps({
                        "score": resp.score,
                        "action": resp.action,
                        "reasons": [r.value for r in resp.reason_codes],
                        "rule_score": resp.rule_score,
                        "ml_score": resp.ml_score,
                        "response_time_ms": resp.response_time_ms,
                    }))
                else:
                    self._send(404, '{"error":"not found"}')

        httpd = ThreadingHTTPServer(("0.0.0.0", port), Handler)
        thread = threading.Thread(target=httpd.serve_forever, name="http-sidecar", daemon=True)
        thread.start()
        return httpd, httpd.server_address[1]

    # -- lifecycle -----------------------------------------------------------

    def shutdown(self, grace: float = 30.0) -> None:
        """NOT_SERVING -> stop bridge -> drain gRPC, THEN the engine
        (batcher + host-pipeline in-flight window) -> stop HTTP. The
        engine drain rides graceful_stop so admitted requests finish
        against a live engine — SIGTERM under load loses zero of them."""
        self._stopped.set()
        if self.online is not None:
            # Stop the learner/promotion ticker before the drain: a
            # mid-shutdown hot-swap has nothing left to serve with.
            self.online.stop()
        if self.batch_refresh is not None:
            self.batch_refresh.stop()
        self.bridge.stop()
        graceful_stop(self.grpc_server, self.health, grace, engine=self.engine)
        if self.ledger is not None:
            # After the gRPC drain: every admitted request has scored and
            # enqueued its records; close() flushes the WAL and gives the
            # sink a bounded catch-up window.
            self.ledger.close()
        self.http_server.shutdown()
        # shutdown() only stops the accept loop; the listening socket
        # stays bound until it is closed.
        self.http_server.server_close()
        self._probe_pool.shutdown(wait=False)
        if self.otlp is not None:
            self.otlp.stop()

    def wait_for_signal(self) -> None:
        done = threading.Event()

        def handler(signum, frame):
            logger.info("signal %d: shutting down", signum)
            done.set()

        signal.signal(signal.SIGINT, handler)
        signal.signal(signal.SIGTERM, handler)
        done.wait()
        self.shutdown()


# The boot-time device check: serve on the accelerator JAX finds, on the
# CPU only under an explicit JAX_PLATFORMS=cpu, otherwise exit non-zero
# with one clear line before any port opens.
from igaming_platform_tpu.core.devices import (  # noqa: E402 — boot path
    enable_persistent_compile_cache,
    require_device as device_gate,
)


def _multihost_mesh():
    from igaming_platform_tpu.parallel.distributed import global_mesh, initialize_from_env
    from igaming_platform_tpu.parallel.mesh import MeshSpec

    if not initialize_from_env():
        raise RuntimeError(
            "MULTIHOST_ROLE requires the jax.distributed env contract "
            "(COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID)")
    return global_mesh(MeshSpec(data=-1))


def main(store_max_accounts: int = DEFAULT_MAX_ACCOUNTS) -> None:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(levelname)s %(message)s")

    # Multi-host serving roles (serve/multihost.py): one FRONT process
    # runs the full risk server with its device step spanning the global
    # mesh; FOLLOWER processes mirror each step via the work channel.
    # jax.distributed.initialize must run BEFORE anything touches the
    # XLA backend, so the multihost roles check the device only after
    # their mesh is up; the single-host boot checks it first.
    role = os.environ.get("MULTIHOST_ROLE", "").lower()
    if not role:
        backend = device_gate()
        cache_dir = enable_persistent_compile_cache()
        logger.info("backend=%s persistent compile cache: %s", backend,
                    cache_dir or "off")
    if role == "follower":
        from igaming_platform_tpu.serve.multihost import follower_serve

        config = RiskServiceConfig.from_env()
        port_env = os.environ.get("MULTIHOST_WORK_PORT", "")
        if not port_env:
            raise RuntimeError("MULTIHOST_ROLE=follower requires MULTIHOST_WORK_PORT")
        mesh = _multihost_mesh()
        device_gate()
        enable_persistent_compile_cache()
        ml_backend, params = resolve_model_boot(config)
        port = int(port_env)
        logger.info("multihost follower: process %d/%d, work port %d",
                    jax.process_index(), jax.process_count(), port)
        # The follower's device-step spans (parented on the front's trace
        # via the work-channel traceparent) drain to the same Jaeger as
        # the front's when OTEL_EXPORTER_OTLP_ENDPOINT is set.
        from igaming_platform_tpu.obs.otlp import exporter_from_env

        otlp = exporter_from_env("risk-follower")
        try:
            follower_serve(port, config.scoring, ml_backend, params, mesh)
        finally:
            if otlp is not None:
                otlp.stop()
        return
    if role == "front":
        import dataclasses

        from igaming_platform_tpu.serve.multihost import multihost_engine

        ports = [int(p) for p in
                 os.environ.get("MULTIHOST_FOLLOWER_PORTS", "").split(",") if p]
        if not ports:
            # An empty channel would make the first global collective
            # wait forever for followers that don't exist — a silent
            # pre-SERVING wedge. Config errors must fail at boot.
            raise RuntimeError(
                "MULTIHOST_ROLE=front requires MULTIHOST_FOLLOWER_PORTS "
                "(comma-separated follower work ports)")
        mesh = _multihost_mesh()
        device_gate()
        enable_persistent_compile_cache()

        def factory(scoring_cfg, *, ml_backend, params, batcher_config, feature_store):
            return multihost_engine(
                mesh, ports, config=scoring_cfg, ml_backend=ml_backend,
                params=params, batcher_config=batcher_config,
                feature_store=feature_store,
            )

        # The multihost engine OWNS the mesh; RiskServer must not build a
        # second one from MESH_DEVICES (the abuse detector would jit
        # collectives over it that followers never mirror — a mid-RPC
        # mesh wedge).
        config = RiskServiceConfig.from_env()
        if config.mesh_devices:
            logger.warning("MULTIHOST_ROLE=front ignores MESH_DEVICES/MESH_SEQ/"
                           "MESH_EXPERT — the multihost mesh owns the devices")
            config = dataclasses.replace(config, mesh_devices=0)
        server = RiskServer(config, engine_factory=factory,
                            store_max_accounts=store_max_accounts)
        server.wait_for_signal()
        return
    if role:
        raise RuntimeError(f"MULTIHOST_ROLE={role!r} not recognized (front|follower)")

    server = RiskServer(store_max_accounts=store_max_accounts)
    server.wait_for_signal()


if __name__ == "__main__":
    main()
