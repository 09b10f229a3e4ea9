"""TPUScoringEngine — the risk service's brain, hot path on the device.

Equivalent of the reference ScoringEngine (engine.go:179-323) re-built for
TPU serving:

- feature gather is a host-side dictionary stage (serve/feature_store.py)
  replacing the 3-goroutine Redis/ClickHouse/IP-intel fan-out;
- everything from normalization through rules, ML, ensemble and action
  decision is ONE compiled XLA program over a fixed [B, 30] batch
  (models/ensemble.py), AOT-warmed at startup before health flips to
  SERVING (SURVEY.md §3.5);
- single-request Score calls ride the continuous batcher; ScoreBatch and
  the event-stream bridge call the batch path directly;
- thresholds are runtime-tunable without recompilation (dynamic inputs);
- params hot-swap atomically (train/ hands over new checkpoints).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
from igaming_platform_tpu.serve import chaos, index_program
from igaming_platform_tpu.serve import ledger as ledger_mod
from igaming_platform_tpu.serve import session_state as session_mod
from igaming_platform_tpu.core.enums import ReasonCode, action_from_code, decode_reason_mask
from igaming_platform_tpu.core.features import NUM_FEATURES, FeatureVector
from igaming_platform_tpu.models.ensemble import make_score_fn
from igaming_platform_tpu.obs import drift as drift_mod
from igaming_platform_tpu.obs.tracing import annotate, span
from igaming_platform_tpu.parallel.mesh import AXIS_DATA, validate_batch_for_mesh
from igaming_platform_tpu.serve.batcher import ContinuousBatcher, pad_batch
from igaming_platform_tpu.serve.deadline import (
    LANE_BULK,
    LANE_INTERACTIVE,
    Deadline,
    LaneGate,
)
from igaming_platform_tpu.serve.feature_store import InMemoryFeatureStore, TransactionEvent


@dataclass(slots=True)
class ScoreRequest:
    """Mirror of scoring.ScoreRequest (engine.go:40-53)."""

    account_id: str
    amount: int = 0
    tx_type: str = "deposit"
    player_id: str = ""
    currency: str = "USD"
    game_id: str = ""
    ip: str = ""
    device_id: str = ""
    fingerprint: str = ""
    user_agent: str = ""
    session_id: str = ""
    ip_flags: tuple[int, int, int] | None = None  # (vpn, proxy, tor) when known


@dataclass(slots=True)
class ScoreResponse:
    """Mirror of scoring.ScoreResponse (engine.go:56-64)."""

    score: int
    action: str
    reason_codes: list[ReasonCode]
    rule_score: int
    ml_score: float
    response_time_ms: float
    features: FeatureVector
    # Ledger join key (serve/ledger.py): set when a decision ledger is
    # bound — the same id lands on the WAL record and the flight entry.
    decision_id: str = ""


def _row_divisor(mesh, ml_backend: str) -> int:
    """How many ways the mesh splits a batch's rows: the data axis, times
    the expert axis for the routed backend (GShard row layout)."""
    from igaming_platform_tpu.parallel.mesh import AXIS_EXPERT, mesh_axis_size

    d = mesh_axis_size(mesh, AXIS_DATA)
    if ml_backend == "routed":
        d *= mesh_axis_size(mesh, AXIS_EXPERT)
    return max(1, d)


def _pack_outputs(fn, echo_batch: bool = False):
    """Wrap a dict-output score fn into one int32 [5, B] output (one D2H
    transfer). Row order: score, action, reason_mask, rule_score,
    ml_score as IEEE-754 bits.

    ``echo_batch=True`` additionally returns the input batch unchanged.
    That echo is what makes donating the batch buffer CORRECT: a donated
    input is only reusable when some output matches its shape/dtype/
    layout, and the packed [5, B] int32 result never matches the
    [B, 30] feature matrix — donating without the echo is what produced
    the warmup-visible "Some donated buffers were not usable:
    float32[...]" warning. With the echo, XLA aliases the output onto
    the donated buffer and the staging slot is recycled in place."""

    def packed(params, x, blacklisted, thresholds):
        stacked = index_program.stack_packed(fn(params, x, blacklisted, thresholds))
        return (stacked, x) if echo_batch else stacked

    return packed


def _device_dispatch(fn_name: str, shape, dtype) -> None:
    """The launch-side chokepoint, mirroring ``_device_readback``: the
    ``device.dispatch`` chaos seam fires here (inside the dispatch stage
    span, so an injected delay attributes to ``score.dispatch`` in the
    SLO budget table), the padded shape signature is noted with the
    compile watcher (obs/runtime_telemetry.py) — a signature seen for
    the first time after warmup is the recompile-storm tripwire — and
    the honest dispatch counter bumps (``risk_device_dispatches_total``
    + the RPC root's ``dispatches`` attribute). EVERY jit launch on a
    scoring path must route through here: the split drift sketch, the
    shadow scorer's fallback step, the session-ring admission sync, the
    cache delta scatter and the abuse model all count, so the
    dispatches-per-RPC probe measures launches, not spans."""
    from igaming_platform_tpu.obs import runtime_telemetry as _rt

    chaos.fire("device.dispatch")
    _rt.note_compile_signature(fn_name, shape, dtype)
    _rt.note_dispatch()


def _device_readback(out):
    """The D2H drain, chokepointed so chaos plans (serve/chaos.py) can
    inject a readback that delays, errors, or never returns."""
    chaos.fire("device.readback")
    return jax.device_get(out)


def _unpack_host(packed) -> dict:
    """Host-side view of the packed [5, B] result as the canonical dict."""
    a = np.asarray(packed)
    return {
        "score": a[0],
        "action": a[1],
        "reason_mask": a[2],
        "rule_score": a[3],
        "ml_score": a[4].view(np.float32),
    }


class TPUScoringEngine:
    def __init__(
        self,
        config: ScoringConfig | None = None,
        *,
        ml_backend: str = "mock",
        params: Any = None,
        mesh=None,
        batcher_config: BatcherConfig | None = None,
        feature_store: InMemoryFeatureStore | None = None,
        warmup: bool = True,
        feature_cache: bool | int | None = None,
        session_state: bool | None = None,
    ):
        self.config = config or ScoringConfig()
        self.ml_backend = ml_backend
        self._params = params
        self._params_lock = threading.Lock()
        # Decision ledger (serve/ledger.py): bound by the serving layer
        # (RiskServer / harnesses); None keeps every note_decisions call
        # a single attribute check. The params fingerprint is computed
        # ONCE here (and on hot-swap) so records never hash on the hot
        # path.
        self.ledger = None
        # Shadow scorer (serve/shadow.py): bound by the online-learning
        # loop; None keeps the seam a single attribute check. Candidate
        # params score the live stream off the note_decisions seam with
        # zero effect on responses.
        self.shadow = None
        # Drift observatory (obs/drift.py): bound via bind_drift by the
        # serving layer; None keeps every launch a single attribute
        # check. When bound, each dispatch adds ONE fused device-side
        # sketch reduction over the already-resident batch (the donated
        # echo / the HBM cache rows) — the tiny result vector drains to
        # the drift worker thread, never a host sync on this path.
        self.drift = None
        self._drift_sketch_fn = None
        self._drift_cached_fn = None
        self._drift_lock = threading.Lock()
        # Fused mega-step (one graph, one dispatch): per path family
        # (packed / host; cached / session: serve/index_program.py) one
        # jitted program folds the drift sketch and — when a candidate
        # sits in shadow — the candidate re-score into the SAME dispatch.
        # Variants are keyed (family, sketch, shadow), built+AOT-warmed
        # OFF the request path (boot; _on_shadow_candidate's daemon
        # thread), and a launch only selects a variant already in
        # `_fused_ready` — until then it rides the plain program and the
        # split sketch / shadow. FUSED=0 keeps the split paths entirely;
        # SHADOW_FUSED=0 keeps the shadow on its fallback (echo-fed) path.
        self._fused_enabled = os.environ.get("FUSED", "1") not in ("0", "false")
        self._shadow_fused_enabled = (
            os.environ.get("SHADOW_FUSED", "1") not in ("0", "false"))
        self._fused_lock = threading.Lock()
        self._fused_fns: dict[tuple, Any] = {}
        self._fused_ready: set[tuple] = set()
        self._shadow_warm_thread: threading.Thread | None = None
        self.params_fingerprint = ledger_mod.params_fingerprint(params)
        self.features = feature_store or InMemoryFeatureStore()
        bcfg = batcher_config or BatcherConfig()
        self.batch_size = bcfg.batch_size
        self._pipeline_depth = max(1, bcfg.pipeline_depth)
        # Optional batch-scores hook (set by the gRPC layer): the wire
        # fast path never materializes per-row response objects, so the
        # score-distribution histogram is fed vectorized from here.
        self.score_observer: Any = None
        # Compiled shape ladder: the throughput shape plus smaller latency
        # tiers (VERDICT r02 item 1 — a single-txn flush must not pay the
        # full-shape H2D + step + readback). jax.jit compiles one
        # executable per input shape, so the ladder is just which padded
        # shapes we allow; each is AOT-warmed before SERVING.
        self._shapes = sorted(
            {t for t in bcfg.latency_tiers if 0 < t < self.batch_size}
            | {self.batch_size}
        )
        self._thresholds = np.array(
            [self.config.block_threshold, self.config.review_threshold], dtype=np.int32
        )
        # (program label, padded shape) -> (host arguments, their bytes):
        # what one launch of the index program hands over the link.
        self._h2d_cost: dict[tuple, tuple[int, int]] = {}
        self._mesh = mesh
        # Slot-sharded device state (parallel/state_sharding.py,
        # ROADMAP item 2): on a mesh with a >1 ``data`` axis the HBM
        # feature table and session ring row-shard by slot and the
        # cached/session programs compile as shard_map bodies — same
        # outputs bit-for-bit, ~1/K per-chip HBM, still one dispatch.
        from igaming_platform_tpu.parallel import state_sharding

        self._state_plan = state_sharding.plan_for(mesh)
        # Model parallelism over the SAME mesh (MODEL_SHARDING=1
        # default): wide ensemble pieces — the GBDT tree bank over
        # ``expert`` (margins partial-summed in-graph by the SPMD
        # partitioner), MLP/multitask trunks over ``model`` — so
        # aggregate HBM holds one model copy per mesh, not per chip.
        # Values never change, only layout; the routed backend owns its
        # own expert-parallel layout in parallel/ep.py and is excluded.
        self._model_sharded = False
        if (mesh is not None and params is not None
                and ml_backend != "routed"
                and os.environ.get("MODEL_SHARDING", "1") not in ("0", "false")):
            from igaming_platform_tpu.parallel.mesh import (
                AXIS_EXPERT,
                AXIS_MODEL,
                mesh_axis_size,
            )
            from igaming_platform_tpu.parallel.sharding import shard_model_params

            if (mesh_axis_size(mesh, AXIS_MODEL) > 1
                    or mesh_axis_size(mesh, AXIS_EXPERT) > 1):
                params = shard_model_params(mesh, ml_backend, params)
                self._model_sharded = True
        params = self._params = self._place_params(params)
        # What the index programs read: placed where they run when it is
        # set, never re-sent by a launch. ``_thresholds`` stays the host
        # copy (the row paths, get_thresholds, the heuristic tier).
        self._thresholds_dev = self._place_replicated(self._thresholds)

        # WIRE_DTYPE=bf16 (opt-in): ship feature batches to the device as
        # bfloat16 — half the host->device bytes; the graph casts back to
        # float32 on device (make_score_fn's jnp.asarray). For hosts
        # where per-RPC transfer is the e2e wall and the device itself
        # is ~idle. Off by default because it
        # is NOT reference-exact: features round to ~3 significant
        # digits, so a row whose feature sits within that rounding of a
        # rule threshold can flip that rule — worst case one rule's full
        # weight, ~20 score points (tests/test_scorer_chunking.py pins
        # both the typical-row envelope and the threshold-edge flip).
        # The host latency tier always keeps float32 — no link, no
        # reason to round.
        self._wire_dtype: Any = np.float32
        self._wire_encode = None  # host-side pre-H2D transform
        wire_dtype_env = os.environ.get("WIRE_DTYPE", "").lower()
        if wire_dtype_env in ("bf16", "bfloat16"):
            import ml_dtypes

            self._wire_dtype = ml_dtypes.bfloat16
            self._wire_encode = lambda x: x.astype(self._wire_dtype)
        elif wire_dtype_env == "int8":
            # WIRE_DTYPE=int8: 4x fewer H2D bytes than f32 (2x vs bf16)
            # via per-feature calibrated signed-log/linear domains
            # (ops/quantize.py); the graph dequantizes on device. Same
            # caveat class as bf16, wider step — see the table's
            # docstring for the deviation envelope.
            from igaming_platform_tpu.ops.quantize import wire_quantize_int8

            self._wire_dtype = np.int8
            self._wire_encode = wire_quantize_int8
        elif wire_dtype_env not in ("", "f32", "fp32", "float32"):
            # A typo here would silently ship float32 while the operator
            # believes compression is active — fail loudly instead.
            raise ValueError(
                f"WIRE_DTYPE={wire_dtype_env!r} not supported "
                "(use 'bf16', 'int8' or 'float32')")

        fn_f32 = make_score_fn(self.config, ml_backend, mesh=mesh)
        # Raw dict-output graph, kept for the index-mode programs
        # (serve/index_program.py composes resident rows, the session
        # head, the sketch and the shadow branch around it): they gather
        # f32 rows already resident in HBM, so they wrap the raw-f32
        # graph whatever WIRE_DTYPE says.
        self._score_fn_f32 = fn_f32
        fn = fn_f32
        if self._wire_dtype is np.int8:
            from igaming_platform_tpu.ops.quantize import wire_dequantize_int8

            fn = lambda params, xq, bl, thr: fn_f32(  # noqa: E731
                params, wire_dequantize_int8(xq), bl, thr)
        # The serving executable returns ONE packed int32 [5, B] array
        # (score / action / reason_mask / rule_score / ml_score-bits)
        # instead of a five-array dict: on a host link where readback cost
        # is per-transfer, one D2H copy replaces five (the ml_score float
        # rides as its IEEE bits via bitcast, recovered with .view on the
        # host — lossless). The batch echo makes input donation usable
        # (see _pack_outputs): the staging buffer of every step is
        # recycled in place instead of freed + reallocated per batch.
        packed_fn = _pack_outputs(fn, echo_batch=True)
        # The host tier has no device link to compress, so it always
        # serves raw float32 — it must compile the UNWRAPPED graph (the
        # int8-wrapped one would dequantize raw f32 features to inf).
        # Echoed too (uniform call shape), but NOT donated: host-tier
        # inputs may be caller-owned arrays, and on the CPU backend jax
        # can alias host memory zero-copy.
        packed_fn_host = _pack_outputs(fn_f32, echo_batch=True)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            validate_batch_for_mesh(self.batch_size, mesh)
            # The routed backend splits rows over data x expert — every
            # compiled shape must divide by that product, and the
            # throughput shape failing is a config error HERE, not a raw
            # assert buried in a jit trace during warmup.
            divisor = _row_divisor(mesh, ml_backend)
            if self.batch_size % divisor != 0:
                raise ValueError(
                    f"batch {self.batch_size} not divisible by the mesh row "
                    f"split ({divisor}: data x expert for ml_backend={ml_backend})"
                )
            # Latency tiers the mesh cannot shard are dropped, not fatal —
            # they are an optimization, and the defaults must never turn a
            # previously-valid mesh config into a startup failure.
            self._shapes = [
                s for s in self._shapes
                if s == self.batch_size or s % divisor == 0
            ]
            row = NamedSharding(mesh, P(AXIS_DATA, None))
            vec = NamedSharding(mesh, P(AXIS_DATA))
            repl = NamedSharding(mesh, P())
            self._fn = jax.jit(
                fn, in_shardings=(None, row, vec, repl), out_shardings=vec
            )
            # Donated batch + row-sharded echo: the echo's sharding
            # matches the input's, so the donated shards alias cleanly.
            self._packed_fn = jax.jit(
                packed_fn,
                in_shardings=(None, row, vec, repl),
                out_shardings=(NamedSharding(mesh, P(None, AXIS_DATA)), row),
                donate_argnums=(1,),
            )
        else:
            self._fn = jax.jit(fn)
            self._packed_fn = jax.jit(packed_fn, donate_argnums=(1,))

        # Host latency tier: the SAME score graph compiled for the host
        # CPU, used for near-empty flushes (n <= host_tier_rows). The
        # reference scores every transaction on the host (ONNX Runtime,
        # onnx_model.go:208-255); here trickle traffic gets a host-local
        # XLA executable — microseconds of compute, zero host<->device
        # link round-trips — while bulk batches ride the TPU tiers.
        # Numerics may differ from the MXU path by float32 rounding
        # (|ml_score| ~1e-3, score by at most +-1 — same thresholds,
        # same actions).
        # Host tier is keyed on ACTUAL row count, capped strictly below the
        # throughput shape: a full batch_size batch always rides the TPU
        # (a config with host_tier_rows >= batch_size cannot silently
        # route bulk traffic to the host), while a near-empty flush — even
        # at the stock batch_size=256 where no smaller tier compiles —
        # skips the device link entirely.
        # A MULTI-device mesh disables the tier (its step is a
        # collective program a lone CPU executable can't impersonate); a
        # 1-device mesh — the loopback/degraded shape multihost_engine
        # builds so rebuilds never silently drop sharding — keeps it.
        self._host_tier = (
            0 if (mesh is not None and mesh.devices.size > 1)
            else max(0, min(bcfg.host_tier_rows, self.batch_size - 1))
        )
        self._fn_host = None
        self._params_host = None
        self._thresholds_host = self._thresholds
        # HOST_TIER_FORCE=1 builds the tier even when the default backend
        # is already CPU — meaningless for performance, but it lets the
        # CPU-only test suite execute this production path (otherwise the
        # tier code would only ever run on real TPU hosts).
        force_tier = os.environ.get("HOST_TIER_FORCE") == "1"
        if self._host_tier > 0 and (jax.default_backend() != "cpu" or force_tier):
            try:
                cpu = jax.devices("cpu")[0]
            except RuntimeError:
                cpu = None
                logging.getLogger(__name__).warning(
                    "host latency tier NOT built: no CPU backend beside "
                    "%s (JAX_PLATFORMS=%s) — every flush rides the device",
                    jax.default_backend(), jax.config.jax_platforms)
            if cpu is not None:
                self._fn_host = jax.jit(packed_fn_host)
                # Committed-to-CPU params (and thresholds, for the
                # params=None mock backend) pin the compile to the host.
                self._params_host = jax.device_put(params, cpu)
                self._thresholds_host = jax.device_put(self._thresholds, cpu)
                self._host_cpu = cpu

        # Device-resident HBM feature cache (serve/device_cache.py): built
        # lazily on the first index-mode request (ensure_cache), or
        # eagerly when `feature_cache` / FEATURE_CACHE asks for it — lazy
        # keeps the extra jit compile off engines that never serve index
        # traffic. `wire_mode=index` (WIRE_MODE env) additionally routes
        # the columnar score_batch_wire path through the cached step.
        self.cache = None
        self._cache_supported = True
        self._cache_metrics_sink = None
        self._cache_lock = threading.Lock()
        if feature_cache is None:
            feature_cache = os.environ.get("FEATURE_CACHE", "") not in ("", "0")
        self._cache_capacity = (
            feature_cache if isinstance(feature_cache, int)
            and not isinstance(feature_cache, bool)
            else int(os.environ.get("FEATURE_CACHE_CAPACITY", "65536"))
        )
        self._cache_eager = bool(feature_cache)
        self.wire_mode = os.environ.get("WIRE_MODE", "row").lower()
        if self.wire_mode not in ("row", "index"):
            raise ValueError(
                f"WIRE_MODE={self.wire_mode!r} not supported (use 'row' or 'index')")

        # Stateful sequence scoring (serve/session_state.py, SESSION_STATE=1
        # or session_state=True): a per-account event ring in HBM beside
        # the feature table, scored by a session head FUSED into the
        # cached step (one dispatch, ring appended via donated buffers).
        # Built with the cache (ensure_cache) — the tables share one
        # host index and one CLOCK admission decision.
        self.session = None
        self._session_metrics_sink = None
        self._session_enabled = (
            session_mod.session_enabled_env() if session_state is None
            else bool(session_state))

        # Pipelined host engine (serve/pipeline_engine.py): stage workers
        # overlap gather/pad, device dispatch and readback/encode across
        # wire batches, with arena-pooled staging buffers. Default ON for
        # the wire paths; HOST_PIPELINE=0 (or host_pipeline=False) keeps
        # the lockstep _score_rows_encode flow — also the parity
        # reference the pipeline is pinned bit-exact against.
        env_pipe = os.environ.get("HOST_PIPELINE", "")
        self._pipeline_enabled = (
            bcfg.host_pipeline if env_pipe == "" else env_pipe not in ("0", "false")
        )
        self._host_pipeline = None
        self._host_pipeline_lock = threading.Lock()
        self._pipeline_metrics_sink = None

        # Deadline plane (serve/deadline.py): the online step-time model
        # the scheduler plans batch shape/flush against, and the lane
        # gate that gives interactive batches first access to the device
        # when bulk chunk dispatches contend.
        from igaming_platform_tpu.obs.perfmodel import OnlineStepModel

        self.step_model = OnlineStepModel()
        self.lane_gate = LaneGate()
        self._batcher = ContinuousBatcher(
            cfg=batcher_config,
            dispatch=self._dispatch_requests,
            collect=self._collect_requests,
            shapes=self._shapes,
            step_model=self.step_model,
            lane_gate=self.lane_gate,
        )
        if warmup:
            self.warmup()
        self._batcher.start()

    # -- lifecycle -----------------------------------------------------------

    def warmup(self) -> None:
        """AOT-compile the serving shape before accepting traffic, and warm
        the device->host readback path (first real transfer on some
        interconnects is far costlier than steady state) so the first
        request doesn't pay either cost."""
        for shape in self._shapes:
            x = np.zeros((shape, NUM_FEATURES), dtype=self._wire_dtype)
            bl = np.zeros((shape,), dtype=bool)
            out = self._packed_fn(self._params, x, bl, self._thresholds)
            jax.block_until_ready(out)
            jax.device_get(out)
            # Warm every host-tier shape a near-empty flush could pad to.
            # The host tier always serves float32 (no link to save).
            if self._fn_host is not None and shape <= self._pick_shape(self._host_tier):
                x32 = np.zeros((shape, NUM_FEATURES), dtype=np.float32)
                jax.device_get(
                    self._fn_host(self._params_host, x32, bl, self._thresholds_host)
                )
        if self._cache_eager or self.wire_mode == "index":
            self.ensure_cache()

    def close(self) -> None:
        self._batcher.stop()
        if self._host_pipeline is not None:
            self._host_pipeline.close()

    # -- pipelined host engine (serve/pipeline_engine.py) --------------------

    @property
    def pipeline(self):
        """The host pipeline, if built (None until the first pipelined
        wire batch, or when disabled)."""
        return self._host_pipeline

    def bind_pipeline_metrics(self, metrics) -> None:
        """Route pipeline gauges (inflight depth, overlap ratio) into a
        ServiceMetrics registry — applied now if the pipeline is built,
        at first build otherwise."""
        self._pipeline_metrics_sink = metrics
        if self._host_pipeline is not None:
            self._host_pipeline.bind_metrics(metrics)

    # -- drift observatory (obs/drift.py) ------------------------------------

    def bind_drift(self, drift_engine) -> None:
        """Attach a DriftEngine and build + AOT-warm the jitted sketch
        reductions for every ladder shape, so the first live request
        never pays the compile. The sketch consumes the batch echo the
        packed step already returns (device-resident — zero extra H2D)
        and its D2H read happens on the drift worker, keeping the hot
        path free of added syncs."""
        if drift_engine is None:
            self.drift = None
            return
        sk = jax.jit(drift_mod.sketch_kernel)
        # Warm with the dtypes the launch paths actually ship: the wire
        # dtype on the device path (f32 default, bf16 opt-in — int8 is
        # skipped at note time, its quantized domain sketches garbage)
        # plus f32 for the host latency tier.
        dtypes = {np.dtype(np.float32)}
        if self._wire_dtype is not np.int8:
            dtypes.add(np.dtype(self._wire_dtype))
        for shape in self._shapes:
            packed = np.zeros((5, shape), dtype=np.int32)
            for dt in dtypes:
                x = np.zeros((shape, NUM_FEATURES), dtype=dt)
                jax.device_get(sk(x, packed, np.int32(0)))
        self._drift_sketch_fn = sk
        self.drift = drift_engine
        if self.cache is not None:
            self._ensure_drift_cached_fn()
        if self._fused_enabled:
            # Fold the sketch into the scoring program itself (boot /
            # engine rebuild: off the request path); the split kernels
            # above stay compiled as the FUSED=0 / warm-window fallback.
            self._warm_fused("packed", True, False)
            if self._fn_host is not None:
                self._warm_fused("host", True, False)
            if self.cache is not None:
                self._warm_fused(self._index_family(), True, False)
            shadow = self.shadow
            if shadow is not None:
                # Drift bound after a candidate was already in shadow:
                # re-warm the sketch+shadow variants to match.
                self._on_shadow_candidate(shadow)

    def _ensure_drift_cached_fn(self):
        """Build (once) the index-mode sketch step — the cache rows live
        in HBM, so the sketch re-gathers them on device (the same
        composition as the cached score step) and reduces in place."""
        if self._drift_cached_fn is not None or self.cache is None:
            return self._drift_cached_fn
        with self._drift_lock:
            if self._drift_cached_fn is None:
                fn = jax.jit(drift_mod.cached_sketch_kernel)
                # AOT-warm every ladder shape against the live table.
                for shape in self._shapes:
                    jax.device_get(fn(
                        self.cache.table,
                        *index_program.chunk_columns(
                            index_program.warm_columns(shape)),
                        np.zeros((5, shape), dtype=np.int32), np.int32(0)))
                self._drift_cached_fn = fn
        return self._drift_cached_fn

    def _note_drift(self, echo, packed, n: int, sketch=None) -> None:
        """Hand one batch's sketch to the drift engine's bounded queue.
        On the fused path ``sketch`` is the vector computed INSIDE the
        scoring dispatch (int8 wire included — the program dequantizes
        in-graph before sketching); on the split path the sketch is a
        separate kernel launch over the donated-batch echo (device
        resident by construction), routed through the dispatch seam so
        it counts honestly. Never raises, never blocks, never adds a
        host sync: failures count in the engine's own report."""
        drift = self.drift
        if drift is None or n <= 0:
            return
        try:
            if sketch is not None:
                drift.submit(sketch, n)
                return
            if echo.dtype == np.int8:
                # int8 wire compression on the SPLIT path: the echo
                # carries the QUANTIZED domain; sketching it would
                # monitor codec artifacts, not traffic. Counted, not
                # silently missing. (The fused program sketches the
                # in-graph dequantized rows instead.)
                drift.note_skipped(n, "compressed")
                return
            _device_dispatch("sketch_kernel", echo.shape, echo.dtype)
            drift.submit(self._drift_sketch_fn(echo, packed, np.int32(n)), n)
        except Exception:  # noqa: CC04 — drift observability must never fail scoring; the engine counts its errors
            drift.note_error()

    def _note_drift_cached(self, chunk, packed, n: int, sketch=None) -> None:
        """Index-mode twin of ``_note_drift``: a fused cached/session
        variant computes the sketch in-graph; the split fallback re-gathers
        the HBM-resident rows with one extra, honestly-counted launch,
        fed views of the launch's packed chunk."""
        drift = self.drift
        if drift is None or n <= 0:
            return
        try:
            if sketch is not None:
                drift.submit(sketch, n)
                return
            fn = self._ensure_drift_cached_fn()
            if fn is None:
                return
            _device_dispatch("cached_sketch_kernel", chunk.shape[:1],
                             chunk.dtype)
            drift.submit(fn(self.cache.table,
                            *index_program.chunk_columns(chunk), packed,
                            np.int32(n)), n)
        except Exception:  # noqa: CC04 — drift observability must never fail scoring; the engine counts its errors
            drift.note_error()

    # -- fused program variants (one graph, one dispatch; see __init__) -------

    def _build_fused(self, family: str, sketch: bool, shadow: bool):
        """Construct + jit one program variant. The index families
        (``cached`` / ``session``) come from serve/index_program.build;
        the row families (``packed`` / ``host``) are built here. Outputs
        are a variable-length tuple: (packed[, echo | ring,cursor,length]
        [, sketch][, shadow_packed]) — the launch site knows the layout
        from the (sketch, shadow) key it selected."""
        from igaming_platform_tpu.ops.quantize import wire_dequantize_int8

        core = self._score_fn_f32

        if family in ("packed", "host"):
            int8_wire = family == "packed" and self._wire_dtype is np.int8

            def fused(params, cand, x, bl, thr, n):
                xr = wire_dequantize_int8(x) if int8_wire else x
                packed = index_program.stack_packed(core(params, xr, bl, thr))
                return index_program.epilogue(
                    [packed, x], xr, packed, n, sketch,
                    (lambda: index_program.stack_packed(
                        core(cand, xr, bl, thr))) if shadow else None)

            donate = (2,) if family == "packed" else ()
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                row = NamedSharding(self._mesh, P(AXIS_DATA, None))
                vec = NamedSharding(self._mesh, P(AXIS_DATA))
                repl = NamedSharding(self._mesh, P())
                pk = NamedSharding(self._mesh, P(None, AXIS_DATA))
                outs = [pk, row] + ([repl] if sketch else []) \
                    + ([pk] if shadow else [])
                return jax.jit(
                    fused,
                    in_shardings=(None, None, row, vec, repl, repl),
                    out_shardings=tuple(outs),
                    donate_argnums=donate)
            return jax.jit(fused, donate_argnums=donate)

        return index_program.build(
            core, self.config, family=family, sketch=sketch, shadow=shadow,
            mesh=self._mesh, plan=self._state_plan,
            session=self.session if family == "session" else None)

    def _ensure_fused(self, family: str, sketch: bool, shadow: bool):
        """Build (once) the jitted fused variant. The memo key is
        (family, sketch, shadow) ONLY — candidate params enter as a
        traced argument tree, never the key, so a new candidate reuses
        the ladder-shape executables (no per-candidate retrace; the
        JX06 analyzer check pins this discipline)."""
        key = (family, sketch, shadow)
        ffn = self._fused_fns.get(key)
        if ffn is not None:
            return ffn
        with self._fused_lock:
            ffn = self._fused_fns.get(key)
            if ffn is None:
                ffn = self._build_fused(family, sketch, shadow)
                self._fused_fns[key] = ffn
        return ffn

    def _warm_fused(self, family: str, sketch: bool, shadow: bool,
                    cand=None, cache=None):
        """AOT-compile every ladder shape of one fused variant (always
        OFF the request path: bind_drift at boot, ensure_cache's build
        window, or the shadow-candidate warm thread), then mark it
        launchable. A launch only ever selects a key in
        ``_fused_ready``, so serving never blocks on these compiles.
        ``cache``: the table ensure_cache has not yet published."""
        ffn = self._ensure_fused(family, sketch, shadow)
        with self._params_lock:
            params = self._params
        if family in ("packed", "host"):
            host = family == "host"
            p = self._params_host if host else params
            thr = self._thresholds_host if host else self._thresholds
            dt = np.float32 if host else self._wire_dtype
            for shape in self._shapes:
                if host and shape > self._pick_shape(self._host_tier):
                    continue
                x = np.zeros((shape, NUM_FEATURES), dtype=dt)
                bl = np.zeros((shape,), dtype=bool)
                jax.block_until_ready(
                    ffn(p, cand, x, bl, thr, np.int32(0)))
        else:
            cache = cache if cache is not None else self.cache
            mgr = self.session
            thr = self._thresholds_dev
            for shape in self._shapes:
                chunk = index_program.warm_columns(shape)
                if family == "cached":
                    res = ffn(params, cand, cache.table, cache.flags, chunk,
                              thr)
                else:
                    with mgr.lock:
                        res = ffn(
                            params, mgr.head_params, cache.table, cache.flags,
                            mgr.session_ring, mgr.session_cursor,
                            mgr.session_length, chunk, thr, cand)
                        mgr.adopt(res[1], res[2], res[3])
                jax.device_get(res[0])
        self._fused_ready.add((family, sketch, shadow))  # noqa: CC10 — publish-once GIL-atomic set: each key added by exactly one warm thread, after every shape compiled
        return ffn

    def _select_fused(self, family: str):
        """Pick the best READY fused variant for a launch: (fn,
        sketch_in_graph, (generation, candidate_params) | None), or None
        for the split path. Preference: sketch+shadow when a candidate
        is active and its variant warmed; sketch-only; else split.
        ``_fused_ready`` is read lock-free (GIL-atomic membership; a key
        is added only after all its ladder shapes compiled)."""
        if not self._fused_enabled:
            return None
        sketch = self.drift is not None
        shadow = self.shadow
        if shadow is not None and self._shadow_fused_enabled:
            sstate = shadow.active_state()
            if (sstate is not None
                    and (family, sketch, True) in self._fused_ready):
                return self._fused_fns[(family, sketch, True)], sketch, sstate  # noqa: CC10 — lock-free launch path: keys are publish-once under _fused_lock, read only after _fused_ready
        if sketch and (family, True, False) in self._fused_ready:
            return self._fused_fns[(family, True, False)], True, None  # noqa: CC10 — lock-free launch path: keys are publish-once under _fused_lock, read only after _fused_ready
        return None

    def _on_shadow_candidate(self, shadow) -> None:
        """ShadowScorer hook (constructor / set_candidate / supervisor
        rebind): AOT-build and warm the shadow-branch fused variants on
        a daemon thread so installing a candidate NEVER stalls serving.
        Until then dispatches ride the sketch-only program and the
        candidate scores on the echo-fed split path: one extra launch."""
        if not (self._fused_enabled and self._shadow_fused_enabled):
            return
        if shadow.active_state() is None:
            return
        # Chained behind a warm that still runs, never dropped: bind_drift
        # after a candidate wants the sketch+shadow variants as well.
        t = threading.Thread(target=self._warm_shadow_fused,
                             args=(shadow, self._shadow_warm_thread),
                             name="fused-shadow-warm", daemon=True)
        self._shadow_warm_thread = t
        t.start()

    def _warm_shadow_fused(self, shadow, after=None) -> None:
        try:
            if after is not None:
                after.join()
            state = shadow.active_state()
            if state is None:
                return
            cand = state[1]
            sketch = self.drift is not None
            fams = ["packed"]
            if self.cache is not None:
                fams.append(self._index_family())
            for fam in fams:
                if (fam, sketch, True) not in self._fused_ready:
                    self._warm_fused(fam, sketch, True, cand=cand)
        except Exception:  # noqa: CC04 — a candidate that cannot trace must not poison serving; the split shadow path counts its own errors
            logging.getLogger(__name__).warning(
                "fused shadow warm failed; candidates keep scoring on the "
                "split (echo-fed) shadow path", exc_info=True)

    def _note_shadow(self, out, echo, blp, n: int, thresholds,
                     shadow_out=None, gen=None, staging_hold=None) -> None:
        """The single shadow hand-off chokepoint (CC09 seam). Fused
        launches hand the candidate outputs computed in-graph
        (``shadow_out`` — zero extra launches, zero extra H2D); split
        launches hand the donated-batch echo so the fallback worker
        re-scores from DEVICE-resident rows instead of re-shipping x
        host->device. Index-mode split rows have no echo and stay
        counted-skipped. Never raises. Exactly one party of
        ``staging_hold`` is released here unless the shadow worker takes
        ownership of the echo."""
        shadow = self.shadow
        try:
            if shadow is None or n <= 0:
                return
            if shadow_out is not None:
                shadow.submit_scored(out, shadow_out, n, gen)
                return
            if echo is None:
                shadow.note_skipped(n)
                return
            if shadow.submit_echo(out, echo, blp, n,
                                  np.asarray(thresholds, np.int32),
                                  staging_hold):
                staging_hold = None  # the worker now owns the release
        except Exception:  # noqa: CC04 — the shadow must never fail scoring; drops are visible in its own report
            pass
        finally:
            if staging_hold is not None:
                staging_hold.release()

    def _ensure_pipeline(self):
        """Build (once) the staged host pipeline; None when disabled."""
        if not self._pipeline_enabled:
            return None
        if self._host_pipeline is None:
            with self._host_pipeline_lock:
                if self._host_pipeline is None:
                    from igaming_platform_tpu.serve.pipeline_engine import HostPipeline

                    pipe = HostPipeline(self, depth=self._pipeline_depth)
                    if self._pipeline_metrics_sink is not None:
                        pipe.bind_metrics(self._pipeline_metrics_sink)
                    self._host_pipeline = pipe
        return self._host_pipeline

    def _launch_padded(self, xp: np.ndarray, blp: np.ndarray, use_host: bool,
                       snap: tuple | None = None,
                       n_valid: int | None = None,
                       staging_hold=None):
        """Dispatch one already-padded staging batch (pipeline dispatch
        worker). The caller owns the staging buffers and must keep them
        alive until readback — jax may alias host memory zero-copy on
        the CPU backend. ``snap`` (params_snapshot) pins the params a
        multi-chunk job scores with across a concurrent hot-swap;
        ``n_valid`` (rows before padding) masks the drift sketch.
        ``staging_hold`` (serve/arena.StagingHold) defers the arena
        release of the staging buffers until both readback AND the
        echo-fed shadow fallback (when it takes the echo) are done."""
        if snap is None:
            snap = self.params_snapshot()
        if n_valid is None:
            n_valid = xp.shape[0]
        # Bulk chunk dispatch yields briefly to a launching interactive
        # batch (bounded by the bulk lane's aging budget) — the device
        # queue orders interactive steps first under contention.
        self.lane_gate.acquire(LANE_BULK)
        self._note_session_bypass(n_valid)
        return self._dispatch_packed(xp, blp, use_host, snap, n_valid,
                                     staging_hold=staging_hold)

    def _dispatch_packed(self, xp: np.ndarray, blp: np.ndarray,
                         use_host: bool, snap: tuple, n: int,
                         staging_hold=None):
        """The packed/host launch core shared by every row-shaped path.
        Selects the fused program (score + drift sketch + shadow branch
        in ONE dispatch — one launch, one readback handle) when a warm
        variant exists, else the split program with the sketch and the
        shadow fed off the donated-batch echo."""
        family = "host" if use_host else "packed"
        params = snap[1] if use_host else snap[0]
        thresholds = self._thresholds_host if use_host else self._thresholds
        fsel = self._select_fused(family)
        if fsel is not None:
            ffn, has_sketch, sstate = fsel
            cand = sstate[1] if sstate is not None else None
            _device_dispatch(f"fused_{family}_step", xp.shape, xp.dtype)
            res = ffn(params, cand, xp, blp, thresholds, np.int32(n))
            out, echo = res[0], res[1]
            sk = res[2] if has_sketch else None
            sh = res[2 + int(has_sketch)] if sstate is not None else None
            self._note_drift(echo, out, n, sketch=sk)
            self._note_shadow(out, echo, blp, n, thresholds, shadow_out=sh,
                              gen=sstate[0] if sstate is not None else None,
                              staging_hold=staging_hold)
        else:
            _device_dispatch("packed_step_host" if use_host
                             else "packed_step", xp.shape, xp.dtype)
            fn = self._fn_host if use_host else self._packed_fn
            out, echo = fn(params, xp, blp, thresholds)
            self._note_drift(echo, out, n)
            self._note_shadow(out, echo, blp, n, thresholds,
                              staging_hold=staging_hold)
        if not use_host and hasattr(out, "copy_to_host_async"):
            out.copy_to_host_async()
        return out

    # -- params / thresholds -------------------------------------------------

    def _place_params(self, params: Any) -> Any:
        """Served params live on the device(s) that score with them: a
        host (numpy) tree — what a seeded or freshly restored boot hands
        over — would otherwise be shipped host->device again by every
        dispatch. A model-sharded tree already has its layout."""
        if params is None or self._model_sharded:
            return params
        return self._place_replicated(params)

    def _place_replicated(self, tree: Any) -> Any:
        """Replicated over the mesh, on the default device without one."""
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(tree, NamedSharding(self._mesh, P()))
        return jax.device_put(tree)

    def swap_params(self, params: Any) -> None:  # analysis: param-swap-seam
        """Atomically install new model parameters (hot-swap from train/).
        The host latency tier gets its own CPU-committed copy. This is
        THE served-param mutation seam — analyzer rule CC07 flags any
        write to the served tree outside it, because a bare rebind skips
        the fingerprint refresh (breaking ledger attribution + replay)
        and the host-tier copy (splitting the tiers' models)."""
        if self._model_sharded:
            # Hot-swapped checkpoints take the same mesh layout as the
            # boot params (layout only — values and therefore the
            # fingerprint are unchanged).
            from igaming_platform_tpu.parallel.sharding import shard_model_params

            params = shard_model_params(self._mesh, self.ml_backend, params)
        params = self._place_params(params)
        params_host = (
            jax.device_put(params, self._host_cpu) if self._fn_host is not None else None
        )
        fingerprint = ledger_mod.params_fingerprint(params)
        with self._params_lock:
            self._params = params
            self.params_fingerprint = fingerprint
            if self._fn_host is not None:
                self._params_host = params_host

    def get_params(self) -> Any:
        """Snapshot the live served params (promotion controller /
        vault). Read-only: mutation goes through swap_params (CC07)."""
        with self._params_lock:
            return self._params

    def params_snapshot(self) -> tuple[Any, Any, str]:
        """(params, params_host, fingerprint) captured atomically. A
        batch dispatched from one snapshot must LEDGER the fingerprint
        of the tree that actually scored it — with online promotion a
        hot-swap can land between dispatch and the note_decisions seam,
        and a record stamped with the post-swap fingerprint would be
        silently unreplayable."""
        with self._params_lock:
            return self._params, self._params_host, self.params_fingerprint

    def get_thresholds(self) -> tuple[int, int]:
        t = self._thresholds
        return int(t[0]), int(t[1])

    def set_thresholds(self, block: int, review: int) -> None:
        """Runtime threshold tuning (engine.go:498-504) — no recompile."""
        self._thresholds = np.array([block, review], dtype=np.int32)
        self._thresholds_dev = self._place_replicated(self._thresholds)
        if self._fn_host is not None:
            self._thresholds_host = jax.device_put(self._thresholds, self._host_cpu)

    # -- scoring -------------------------------------------------------------

    def score(self, req: ScoreRequest, timeout: float = 30.0,
              deadline: Deadline | None = None,
              lane: str = LANE_INTERACTIVE) -> ScoreResponse:
        """Single-transaction scoring via the continuous batcher.
        ``deadline`` (serve/deadline.py) rides into the scheduler: EDF
        order within the lane, shed (DeadlineExpired) instead of scored
        if the budget runs out while queued."""
        start = time.monotonic()
        resp: ScoreResponse = self._batcher.score_sync(
            req, timeout=timeout, deadline=deadline, lane=lane)
        resp.response_time_ms = (time.monotonic() - start) * 1000.0
        return resp

    def deadline_snapshot(self) -> dict:
        """The deadline plane's debug surface (/debug/deadlinez): lane
        depths, expiry-shed and hedge counters, the per-shape step-time
        model, and the lane gate's yield count."""
        b = self._batcher
        return {
            "lanes": b.scheduler.depths(),
            "queued": b.scheduler.qsize(),
            "batches_run": b.batches_run,
            "rows_scored": b.rows_scored,
            "batches_replayed": b.batches_replayed,
            "batches_hedged": b.batches_hedged,
            "expired_shed": b.expired_shed,
            # Structural "zero scored dead" evidence: rows that entered a
            # dispatch with a spent budget (the assembly shed keeps this 0).
            "dead_dispatched": b.dead_dispatched,
            "lane_gate_yields": self.lane_gate.yields,
            "step_model": self.step_model.snapshot(),
        }

    def score_batch(self, reqs: list[ScoreRequest]) -> list[ScoreResponse]:
        """Direct batch path (ScoreBatch RPC / event-stream replay)."""
        start = time.monotonic()
        responses = self._run_requests(reqs)
        elapsed_ms = (time.monotonic() - start) * 1000.0
        for r in responses:
            r.response_time_ms = elapsed_ms
        return responses

    def update_features(self, event: TransactionEvent) -> None:
        """Post-transaction write-back (engine.go:486-488)."""
        self.features.update(event)

    # -- device-resident feature cache (serve/device_cache.py) ---------------

    def bind_cache_metrics(self, metrics) -> None:
        """Route cache hit/miss/evict/occupancy counters into a
        ServiceMetrics registry (called by the gRPC layer); applied to the
        cache now if built, or at ensure_cache() time otherwise."""
        self._cache_metrics_sink = metrics
        if self.cache is not None:
            self.cache.bind_metrics(metrics)

    def bind_session_metrics(self, metrics) -> None:
        """Route session-plane counters (warm/cold/bypass rows, appends,
        rehydrations, HBM bytes) into a ServiceMetrics registry — applied
        now if the session plane is built, at ensure_cache otherwise."""
        self._session_metrics_sink = metrics
        if self.session is not None:
            self.session.bind_metrics(metrics)

    def _note_session_bypass(self, n: int) -> None:
        """A row scored on a non-session path (row wire mode / batcher /
        host tier) while session state is enabled: counted as bypass in
        risk_session_rows_total — the window for that account simply does
        not advance, and that fact is visible, never silent."""
        if self.session is not None and n > 0:
            self.session.note_bypass(n)

    def ensure_cache(self):
        """Build (once) the HBM feature table (and the session plane),
        and AOT-warm the plain index programs at every ladder shape —
        called lazily on the first index-mode request or eagerly from
        warmup()."""
        if self.cache is not None:
            return self.cache
        if not self._cache_supported:
            raise RuntimeError(
                "device feature cache unsupported on this engine "
                "(multihost front: the table cannot ride the work channel)")
        with self._cache_lock:
            if self.cache is not None:
                return self.cache
            from igaming_platform_tpu.serve.device_cache import DeviceFeatureCache

            max_age = os.environ.get("FEATURE_CACHE_MAX_AGE_S")
            cache = DeviceFeatureCache(
                self.features,
                capacity=self._cache_capacity,
                mesh=self._mesh,
                max_age_s=float(max_age) if max_age else None,
                metrics=self._cache_metrics_sink,
            )
            # The store's write-back hook: every feature update enqueues a
            # compact per-account delta the next lookup folds into HBM.
            if hasattr(self.features, "delta_listener"):
                self.features.delta_listener = cache.note_update

            # The plain programs (serve/index_program.py), AOT-warmed at
            # every ladder shape; the cache is published only after they
            # ran. The session plane (serve/session_state.py: event ring +
            # host index) is built beside the table and hooked into its
            # admissions: one CLOCK decision governs both. The builder
            # reads head and sizes from the manager, so it is bound first.
            self._warm_fused("cached", False, False, cache=cache)
            if self._session_enabled:
                self.session = session_mod.SessionStateManager(
                    cache.capacity, mesh=self._mesh,
                    metrics=self._session_metrics_sink)
                self._warm_fused("session", False, False, cache=cache)
                cache.session_hook = self.session.on_admit
            self.cache = cache
        if self.drift is not None:
            # A drift engine bound before the cache existed: compile +
            # warm the index-mode sketch now, off the live request path.
            self._ensure_drift_cached_fn()
            if self._fused_enabled:
                self._warm_fused(self._index_family(), True, False)
        if self.shadow is not None and self._fused_enabled:
            # A candidate already in shadow gets its cached/session
            # fused variant warmed off-path too.
            self._on_shadow_candidate(self.shadow)
        return cache

    def _index_family(self) -> str:
        return "session" if self.session is not None else "cached"

    def _launch_cached(self, idxs: np.ndarray, amounts: np.ndarray,
                       types: np.ndarray, bl: np.ndarray,
                       snap: tuple | None = None,
                       account_ids=None, now: float | None = None):
        """Dispatch one chunk's index program (serve/index_program.py):
        the device gathers rows from the HBM-resident table; only int32
        indices + per-txn context cross the link. With session state on
        (and ``account_ids`` given) it is the ``session`` family: same
        dispatch count, plus the window gather, session head and donated
        in-place append. Returns (packed out, n, session_meta):
        per-row post-append lengths, sequence numbers and session hashes
        for the ledger (None on the ``cached`` family)."""
        n = idxs.shape[0]
        shape = self._pick_shape(n)
        mgr = self.session
        family = ("session" if mgr is not None and account_ids is not None
                  else "cached")
        if family == "session":
            # What the chunk's ids decide alone is settled before the
            # session lock is asked for: the grouping by account, and
            # from it the occurrence ranks (session_state.group_chunk).
            with span("score.session", batch=n):
                groups = session_mod.group_chunk(account_ids)
        with span("score.pad", batch=n):
            # The launch's one host array (index_program.pack_chunk):
            # every column the ids and the wire decide is written here,
            # before the lock; the event words follow under it.
            chunk = index_program.pack_chunk(
                shape, idxs, amounts, types, bl,
                occ=groups.occ if family == "session" else None)
        if snap is None:
            snap = self.params_snapshot()
        params = snap[0]
        # FUSED / the ready set decide only WHICH (sketch, shadow) variant
        # of the family's one program is launched; without a fused
        # variant the plain one runs and sketch / shadow ride their own
        # dispatches after (_note_drift_cached / _note_shadow).
        ffn, has_sketch, sstate = self._select_fused(family) or (
            self._ensure_fused(family, False, False), False, None)
        cand = sstate[1] if sstate is not None else None
        label = (("fused_" if has_sketch or sstate is not None else "")
                 + family + "_step")
        smeta = None
        if family == "session":
            # Host-index commit + device dispatch under the session lock:
            # device append order must match host (and therefore ledger /
            # replay) order, and the donated ring buffers are rebound
            # before anyone else can dispatch against them.
            held = False
            try:
                # asked / got stamp the acquire alone (the counter behind
                # session_lock_wait_us_per_row); the span's own entry and
                # exit are its cost, and its exit runs inside the try
                with span("score.lock_wait", batch=n):
                    asked = time.perf_counter()
                    mgr.lock.acquire()
                    held = True
                    got = time.perf_counter()
                ts = now if now is not None else ledger_mod.wall_clock()
                # Session bookkeeping rides its own span (hostprof us/row).
                # (the events need the host index's gaps, so they are
                # encoded under the lock, into the chunk's event words).
                with span("score.session", batch=n):
                    _, _, post_len, seqs, audit = mgr.prepare_chunk(
                        groups, amounts, types, ts,
                        events_out=index_program.chunk_events(chunk, n))
                with span("score.launch", batch=n):
                    args = (params, mgr.head_params, self.cache.table,
                            self.cache.flags, mgr.session_ring,
                            mgr.session_cursor, mgr.session_length, chunk,
                            self._thresholds_dev, cand)
                    self._note_launch(label, shape, n, args)
                    out, ring2, cur2, len2, *extra = ffn(*args)
                    mgr.adopt(ring2, cur2, len2)
                mgr.note_lock(got - asked, time.perf_counter() - got)
            finally:
                if held:
                    mgr.lock.release()
            smeta = {"ts": ts, "lens": post_len, "seqs": seqs,
                     "hashes": audit}
        else:
            with span("score.launch", batch=n):
                args = (params, cand, self.cache.table, self.cache.flags,
                        chunk, self._thresholds_dev)
                self._note_launch(label, shape, n, args)
                out, *extra = ffn(*args)
        # After the family's outputs: [sketch][, shadow_packed]. Without
        # an in-graph sketch the split kernel re-gathers the scored rows
        # on device: they never exist on the host (obs/drift.py).
        sk = extra[0] if has_sketch else None
        sh = extra[-1] if sstate is not None else None
        with span("score.post_launch", batch=n):
            self._note_drift_cached(chunk, out, n, sketch=sk)
            self._note_shadow(out, None, None, n, self._thresholds,
                              shadow_out=sh,
                              gen=sstate[0] if sstate is not None else None)
            if hasattr(out, "copy_to_host_async"):
                out.copy_to_host_async()
        return out, n, smeta

    def _note_launch(self, label: str, shape: int, n: int,
                     args: tuple) -> None:
        """The launch seam of the index program (``_device_dispatch``), and
        what the launch hands over the link: every host (numpy) leaf of
        ``args`` is its own host-to-device transfer (one leaf, the packed
        chunk; a leaf is one placement on one device and one a device on
        a mesh). Which leaves are host arrays is a property of the
        program and its padded shape, so it is reckoned once per (label,
        shape) and added per launch, beside the padded rows themselves
        (the rung the chunk ran) and the real rows among them."""
        from igaming_platform_tpu.obs import runtime_telemetry as _rt

        _device_dispatch(label, (shape,), np.int32)
        key = (label, shape)
        cost = self._h2d_cost.get(key)
        if cost is None:
            host = [a for a in jax.tree_util.tree_leaves(args)
                    if isinstance(a, (np.ndarray, np.generic))]
            cost = self._h2d_cost[key] = (
                len(host), sum(int(a.nbytes) for a in host))
        _rt.note_h2d(*cost)
        _rt.note_padded_rows(shape)
        _rt.note_occupancy(n)

    def _blacklist_flags(self, n: int, ips, devices, fingerprints) -> np.ndarray:
        """Per-request blacklist vector from the host sets — the cheap
        half of the gather the cached path keeps on the host."""
        bl = np.zeros((n,), dtype=bool)
        lists = getattr(self.features, "_blacklists", None)
        if lists is None or not any(lists.values()):
            return bl
        dev_bl, ip_bl, fp_bl = lists["device"], lists["ip"], lists["fingerprint"]

        def _s(v):
            return v.decode() if isinstance(v, (bytes, memoryview)) else v

        for i in range(n):
            d = _s(devices[i]) if devices is not None else ""
            p = _s(ips[i]) if ips is not None else ""
            f = _s(fingerprints[i]) if fingerprints is not None else ""
            bl[i] = (
                (bool(d) and d in dev_bl)
                or (bool(f) and f in fp_bl)
                or (bool(p) and p in ip_bl)
            )
        return bl

    def _indexed_outputs(self, account_ids, amounts, types, bl,
                         start: float, now: float | None = None):
        """Pipelined chunked scoring through the cached step -> (result
        dict, per-row response times). Each chunk's lookup folds pending
        deltas into HBM between device steps."""
        from collections import deque

        total = len(account_ids)
        amounts32 = np.ascontiguousarray(amounts, dtype=np.float32)
        types32 = np.ascontiguousarray(types, dtype=np.int32)
        keys = ("score", "action", "reason_mask", "rule_score", "ml_score")
        parts: dict[str, list[np.ndarray]] = {k: [] for k in keys}
        rtms = np.empty((total,), dtype=np.int64)
        inflight: deque = deque()
        snap = self.params_snapshot()
        session_on = self.session is not None

        def read_one() -> None:
            out, lo, n, smeta = inflight.popleft()
            with span("score.readback", batch=n):
                # the wait for the step apart from the copy: what is left
                # of readback is the link and the unpacking
                with span("score.device_wait", batch=n):
                    jax.block_until_ready(out)
                host = _unpack_host(_device_readback(out))
            for k in keys:
                parts[k].append(host[k][:n])
            rtms[lo:lo + n] = int((time.monotonic() - start) * 1000.0)
            if smeta is not None:
                # Stateful decisions ledger PER CHUNK: one note batch ==
                # one device dispatch == one batch-snapshot append unit,
                # so tools/replay.py can reconstruct every row's window
                # (including duplicate accounts within the chunk) from
                # ledger order + the recorded session fields.
                chunk = {k: host[k][:n] for k in keys}
                with span("score.ledger_note", batch=n):
                    ledger_mod.note_decisions(
                        self, chunk, n=n, wire_mode="index", tier="device",
                        bl=bl[lo:lo + n], account_ids=account_ids[lo:lo + n],
                        amounts=amounts32[lo:lo + n],
                        tx_codes=types32[lo:lo + n],
                        params_fp=snap[2], ts=smeta["ts"],
                        session_lens=smeta["lens"], session_seqs=smeta["seqs"],
                        session_hashes=smeta["hashes"], mark_root=(lo == 0))

        for lo in range(0, total, self.batch_size):
            hi = min(lo + self.batch_size, total)
            with span("score.cache_lookup", batch=hi - lo):
                # decoded once a chunk: the cache and the session plane
                # key their host indexes by the same ``str``
                ids = session_mod.decoded_ids(account_ids[lo:hi])
                idxs = self.cache.lookup(ids, now=now)
            with span("score.lane_wait", batch=hi - lo):
                self.lane_gate.acquire(LANE_BULK)
            with span("score.dispatch", batch=hi - lo), annotate("score_step"):
                out, n, smeta = self._launch_cached(
                    idxs, amounts32[lo:hi], types32[lo:hi], bl[lo:hi], snap,
                    account_ids=ids if session_on else None, now=now)
            inflight.append((out, lo, n, smeta))
            if len(inflight) > self._pipeline_depth:
                read_one()
        while inflight:
            read_one()

        cat = {k: np.concatenate(v) if len(v) > 1 else v[0] for k, v in parts.items()}
        if self.score_observer is not None:
            try:
                self.score_observer(cat["score"])
            except Exception:  # noqa: BLE001 — metrics must not fail scoring
                pass
        # Ledger seam (index mode): the feature rows live in HBM and never
        # materialize on the host, so records carry the per-txn context +
        # outputs without a snapshot (replay marks them unreplayable).
        # With session state on, the per-chunk notes above already carried
        # every row (plus its session fields) — no second note here.
        if not session_on:
            with span("score.ledger_note", batch=total):
                ledger_mod.note_decisions(
                    self, cat, n=total, wire_mode="index", tier="device",
                    bl=bl, account_ids=account_ids, amounts=amounts32,
                    tx_codes=types32, params_fp=snap[2])
        return cat, rtms

    def score_columns_cached(
        self, account_ids, amounts, tx_types,
        ips=None, devices=None, fingerprints=None, now: float | None = None,
    ) -> dict:
        """Columnar scoring through the device-resident table; returns the
        canonical result dict (score/action/reason_mask/rule_score/
        ml_score as host arrays). Bit-identical to the host-gather path
        for the same `now` — pinned by tests/test_device_cache.py."""
        self.ensure_cache()
        from igaming_platform_tpu.serve.wire import TX_TYPE_CODES

        n = len(account_ids)
        types = [TX_TYPE_CODES.get(t, 4) for t in tx_types]
        bl = self._blacklist_flags(n, ips, devices, fingerprints)
        cat, _ = self._indexed_outputs(
            list(account_ids), amounts, types, bl, time.monotonic(), now=now)
        return cat

    def score_batch_wire_index(self, payload: bytes) -> tuple[bytes, int]:
        """Index-mode ScoreBatch frame bytes -> risk.v1 ScoreBatchResponse
        wire bytes. The steady-state hot path ships only indices + deltas
        to the device; the feature echo is omitted (rows never exist on
        the host). Raises ValueError on a malformed frame, RuntimeError
        when the native response encoder is unavailable."""
        from igaming_platform_tpu.serve.wire import (
            decode_index_batch,
            encode_score_batch,
        )

        start = time.monotonic()
        with span("score.decode") as dsp:
            ids, amounts, codes, ips, devices, fingerprints = decode_index_batch(payload)
            # Row count is only known post-decode: stamp it so the host
            # profiler (obs/hostprof.py) can report decode in µs/row.
            dsp.attributes["batch"] = len(ids)
        if len(ids) == 0:
            return b"", 0
        self.ensure_cache()
        with span("score.blacklist", batch=len(ids)):
            bl = self._blacklist_flags(len(ids), ips, devices, fingerprints)
        cat, rtms = self._indexed_outputs(ids, amounts, codes, bl, start)
        with span("score.encode", batch=len(ids)):
            payload_out = encode_score_batch(
                cat["score"], cat["action"], cat["reason_mask"], cat["rule_score"],
                cat["ml_score"], rtms, None,
            )
        return payload_out, len(ids)

    # -- internals -----------------------------------------------------------

    def _run_requests(self, reqs: list[ScoreRequest]) -> list[ScoreResponse]:
        # Chunk to the compiled batch shape: oversized ScoreBatch RPCs run
        # as several device steps rather than recompiling a new shape.
        responses: list[ScoreResponse] = []
        for start in range(0, len(reqs), self.batch_size):
            chunk = reqs[start : start + self.batch_size]
            with span("score.gather", batch=len(chunk)):
                x, bl = self.features.gather_batch(chunk)
            snap = self.params_snapshot()
            with span("score.device", batch=len(chunk)), annotate("score_step"):
                out, n = self._run_device(x, bl, snap)
            rows = [self._row_response(out, x, i) for i in range(n)]
            self._note_decisions_requests(out, x, bl, chunk, rows, "batch",
                                          params_fp=snap[2])
            responses.extend(rows)
        return responses

    def _note_decisions_requests(self, out, x, bl, reqs, responses,
                                 wire_mode: str,
                                 params_fp: str | None = None) -> None:
        """Ledger seam for the request-object paths (batcher / direct
        batch): one columnar note per device batch, decision ids stamped
        back onto the responses. No-op without a bound ledger or shadow."""
        if self.ledger is None and self.shadow is None:
            return
        with span("score.ledger_note", batch=len(responses)):
            prefix = ledger_mod.note_decisions(
                self, out, n=len(responses), wire_mode=wire_mode,
                x=x, bl=bl, params_fp=params_fp,
                account_ids=[r.account_id for r in reqs],
                amounts=[r.amount for r in reqs],
                tx_codes=[r.tx_type for r in reqs],
            )
        if prefix is not None:
            for i, resp in enumerate(responses):
                resp.decision_id = f"{prefix}.{i}"

    def _run_device(self, x: np.ndarray, bl: np.ndarray,
                    snap: tuple | None = None):
        out, n = self._launch_device(x, bl, snap)
        return _unpack_host(_device_readback(out)), n

    def _pick_shape(self, n: int) -> int:
        """Smallest compiled shape that fits n rows (latency tiers)."""
        for shape in self._shapes:
            if n <= shape:
                return shape
        return self.batch_size

    def _launch_device(self, x: np.ndarray, bl: np.ndarray,
                       snap: tuple | None = None):
        """Dispatch the compiled step and start the async D2H copy of the
        packed int32 [5, B] result WITHOUT blocking on readback — one
        transfer, not five (readback cost is per-array, not per-byte, at
        these sizes). Near-empty batches (padded shape <= host_tier_rows)
        run the host-CPU executable of the same graph instead: no device
        link round-trip at all."""
        n = x.shape[0]
        shape = self._pick_shape(n)
        self._note_session_bypass(n)
        use_host = self._fn_host is not None and n <= self._host_tier
        if not use_host and self._wire_encode is not None:
            # Encode BEFORE padding: pad_batch preserves dtype, so the
            # pad copy is already compressed (bf16 halves H2D bytes,
            # int8 quarters them; zero pads survive both exactly).
            x = self._wire_encode(x)
        with span("score.pad", batch=n):
            xp, _ = pad_batch(x, shape)
            blp, _ = pad_batch(bl, shape)
        if snap is None:
            # Snapshot under the lock, dispatch outside it — scoring must
            # never serialize on the params mutex.
            snap = self.params_snapshot()
        # This lockstep path pads into fresh arrays, so the echo (and the
        # shadow fallback holding it) needs no staging hold; the
        # pipelined path (serve/pipeline_engine.py) passes one so its
        # arena buffers outlive every device-side consumer.
        return self._dispatch_packed(xp, blp, use_host, snap, n), n

    def launch_packed(self, x: np.ndarray, bl: np.ndarray):
        """Dispatch the score step; returns the packed int32 [5, B] device
        array (rows: score, action, reason_mask, rule_score, ml bits) with
        its D2H copy already started — the replay path reads it back in
        ONE transfer."""
        return self._launch_device(x, bl)

    # Two-phase batcher hooks: dispatch on the launcher thread, collect on
    # the collector thread, so batch k+1 launches while batch k's results
    # are still crossing the device->host link.

    def _dispatch_requests(self, reqs: list[ScoreRequest]):
        # Spans are per BATCH, not per request — tracing overhead stays off
        # the per-transaction cost. The three stage names (gather/dispatch/
        # readback) mirror the reference's goroutine fan-out + ONNX call
        # (engine.go:326-417, :277-288) as host timeline segments.
        with span("score.gather", batch=len(reqs)):
            x, bl = self.features.gather_batch(reqs)
        snap = self.params_snapshot()
        with span("score.dispatch", batch=len(reqs)), annotate("score_step"):
            out, n = self._launch_device(x, bl, snap)
        return out, x, bl, n, reqs, snap

    def _collect_requests(self, handle) -> list[ScoreResponse]:
        out, x, bl, n, reqs, snap = handle
        with span("score.readback", batch=n):
            host = _unpack_host(_device_readback(out))
        rows = [self._row_response(host, x, i) for i in range(n)]
        self._note_decisions_requests(host, x, bl, reqs, rows, "single",
                                      params_fp=snap[2])
        return rows

    def _row_response(self, out: dict, x: np.ndarray, i: int) -> ScoreResponse:
        return ScoreResponse(
            score=int(out["score"][i]),
            action=action_from_code(int(out["action"][i])).value,
            reason_codes=decode_reason_mask(int(out["reason_mask"][i])),
            rule_score=int(out["rule_score"][i]),
            ml_score=float(out["ml_score"][i]),
            response_time_ms=0.0,
            features=FeatureVector.from_array(x[i]),
        )

    # -- wire fast path (ScoreBatch RPC) -------------------------------------

    def score_batch_wire(
        self,
        account_ids: list[str],
        amounts: list[int],
        tx_types: list[str],
        ips: list[str] | None = None,
        devices: list[str] | None = None,
        fingerprints: list[str] | None = None,
        *,
        include_features: bool = True,
    ) -> bytes:
        """Columnar batch scoring straight to ScoreBatchResponse wire bytes.

        The 100k-txns/s path: no per-row ScoreRequest/ScoreResponse
        objects, no per-row proto construction. Columns gather via the
        native store's batched fill, oversize batches run as pipelined
        device chunks (chunk k+1 dispatches while chunk k's results cross
        the link), and the response serializes in ONE native call
        (serve/wire.py). Raises RuntimeError when the native codec is
        unavailable — callers fall back to score_batch().
        """
        start = time.monotonic()
        total = len(account_ids)
        if self.wire_mode == "index":
            # Server-side index mode (WIRE_MODE=index): the same columnar
            # request rides the HBM-resident table — no [N, 30] feature
            # matrix is gathered or shipped. The feature echo is omitted
            # (the rows never exist on the host).
            from igaming_platform_tpu.serve.wire import (
                TX_TYPE_CODES,
                encode_score_batch,
            )

            self.ensure_cache()
            types = [TX_TYPE_CODES.get(t, 4) for t in tx_types]
            bl = self._blacklist_flags(total, ips, devices, fingerprints)
            cat, rtms = self._indexed_outputs(
                list(account_ids), amounts, types, bl, start)
            with span("score.encode", batch=total):
                return encode_score_batch(
                    cat["score"], cat["action"], cat["reason_mask"],
                    cat["rule_score"], cat["ml_score"], rtms, None,
                )
        with span("score.gather", batch=total):
            if hasattr(self.features, "gather_columns"):
                x, bl = self.features.gather_columns(
                    account_ids, amounts, tx_types,
                    ips=ips, devices=devices, fingerprints=fingerprints,
                )
            else:
                rows = [
                    ScoreRequest(
                        account_id=account_ids[i], amount=amounts[i],
                        tx_type=tx_types[i],
                        ip=ips[i] if ips else "",
                        device_id=devices[i] if devices else "",
                        fingerprint=fingerprints[i] if fingerprints else "",
                    )
                    for i in range(total)
                ]
                x, bl = self.features.gather_batch(rows)
        return self._score_rows_to_wire(x, bl, include_features, start,
                                        account_ids=account_ids)

    def score_batch_wire_bytes(
        self, payload: bytes, *, include_features: bool = True
    ) -> tuple[bytes, int]:
        """ScoreBatchRequest wire bytes -> ScoreBatchResponse wire bytes.

        The fully native request path (VERDICT r02 item 2): ONE C++ call
        decodes the proto and gathers the [N, 30] feature matrix + the
        blacklist flags (native_store.decode_gather), the device scores in
        pipelined chunks, and ONE C++ call encodes the response. Per-RPC
        Python work is O(1) in the row count. Returns (bytes, n_rows).
        Raises ValueError on a malformed request, RuntimeError when the
        native store/codec are unavailable.
        """
        start = time.monotonic()
        if not hasattr(self.features, "decode_gather"):
            raise RuntimeError("feature store has no native wire decoder")
        with span("score.decode") as dsp:
            x, bl = self.features.decode_gather(payload)
            # Row count is only known post-decode (µs/row accounting).
            dsp.attributes["batch"] = int(x.shape[0])
        return self._score_rows_to_wire(x, bl, include_features, start), x.shape[0]

    def _score_rows_to_wire(
        self, x: np.ndarray, bl: np.ndarray, include_features: bool, start: float,
        account_ids=None,
    ) -> bytes:
        """Route a gathered [N, 30] batch to response wire bytes: through
        the staged host pipeline when enabled (stage workers overlap this
        RPC's chunks with other in-flight RPCs), else the lockstep
        chunked flow. Device outputs are bit-exact either way
        (tests/test_host_pipeline.py). ``account_ids`` (when the caller
        still has them — the columnar path) ride to the decision ledger;
        the fully-native bytes path records snapshot + hash only."""
        pipe = self._ensure_pipeline()
        if pipe is not None:
            return pipe.score_rows_to_wire(x, bl, include_features, start,
                                           account_ids=account_ids)
        return self._score_rows_encode(x, bl, include_features, start,
                                       account_ids=account_ids)

    def _score_rows_encode(
        self, x: np.ndarray, bl: np.ndarray, include_features: bool, start: float,
        account_ids=None,
    ) -> bytes:
        """Pipelined chunked scoring straight to response wire bytes: chunk
        k's readback overlaps chunk k+1's device step, with at most
        ``pipeline_depth`` chunks' outputs held (bounded memory for giant
        RPCs), and per-chunk response_time_ms — each row reports the time
        ITS chunk became available, not the whole RPC's (the per-call
        semantics of engine.go:263,312)."""
        from collections import deque

        from igaming_platform_tpu.serve.wire import encode_score_batch

        total = x.shape[0]
        if total == 0:
            return b""
        keys = ("score", "action", "reason_mask", "rule_score", "ml_score")
        parts: dict[str, list[np.ndarray]] = {k: [] for k in keys}
        rtms = np.empty((total,), dtype=np.int64)
        inflight: deque = deque()

        def read_one() -> None:
            out, lo, n = inflight.popleft()
            with span("score.readback", batch=n):
                host = _unpack_host(_device_readback(out))
            for k in keys:
                parts[k].append(host[k][:n])
            rtms[lo : lo + n] = int((time.monotonic() - start) * 1000.0)

        snap = self.params_snapshot()
        for lo in range(0, total, self.batch_size):
            hi = min(lo + self.batch_size, total)
            self.lane_gate.acquire(LANE_BULK)
            with span("score.dispatch", batch=hi - lo), annotate("score_step"):
                out, n = self._launch_device(x[lo:hi], bl[lo:hi], snap)
            inflight.append((out, lo, n))
            if len(inflight) > self._pipeline_depth:
                read_one()
        while inflight:
            read_one()

        cat = {k: np.concatenate(v) if len(v) > 1 else v[0] for k, v in parts.items()}
        if self.score_observer is not None:
            try:
                self.score_observer(cat["score"])
            except Exception:  # noqa: BLE001 — metrics must not fail scoring
                if not getattr(self, "_observer_warned", False):
                    self._observer_warned = True
                    import logging

                    logging.getLogger(__name__).warning(
                        "score_observer failed; score histogram will be "
                        "empty for wire batches", exc_info=True,
                    )
        with span("score.ledger_note", batch=total):
            ledger_mod.note_decisions(
                self, cat, n=total, wire_mode="wire_row", x=x, bl=bl,
                account_ids=account_ids, params_fp=snap[2])
        with span("score.encode", batch=total):
            return encode_score_batch(
                cat["score"], cat["action"], cat["reason_mask"], cat["rule_score"],
                cat["ml_score"], rtms, x if include_features else None,
            )

    # -- raw array path (bench / replay) -------------------------------------

    def score_arrays(self, x: np.ndarray, blacklisted: np.ndarray | None = None) -> dict:
        """Score a pre-gathered [N, 30] batch; N must equal the compiled
        batch size (bench/replay path, zero padding overhead)."""
        if blacklisted is None:
            blacklisted = np.zeros((x.shape[0],), dtype=bool)
        if self._wire_encode is not None and x.dtype != self._wire_dtype:
            x = self._wire_encode(np.asarray(x, np.float32))
        with self._params_lock:
            params = self._params
        _device_dispatch("score_arrays", x.shape, x.dtype)
        return self._fn(params, x, blacklisted, self._thresholds)
