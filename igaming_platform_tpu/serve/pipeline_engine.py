"""Pipelined host engine — stage workers overlap host work with device steps.

The device step is a small fraction of an end-to-end ScoreBatch: the
time lives in the serial Python host path (wire decode -> gather -> pad
-> H2D -> readback -> encode), not on the TPU — the "Scaling TensorFlow
to 300M predictions/sec" lesson that at high QPS the pre/post-processing
pipeline is the wall. This module
rebuilds the wire scoring hot path as a staged pipeline so host work for
batch N+1 overlaps the device step for batch N and the readback/encode of
batch N-1:

- **decode/gather** stays on the calling gRPC worker thread (the native
  one-call decode+gather); with several RPCs in flight those calls
  already run concurrently with everything below;
- a **stage worker** pads each chunk into per-shape staging arenas
  (serve/arena.py — reused buffers, no per-batch ``np.zeros``) and
  dispatches the compiled step WITHOUT blocking; the step's input buffer
  is donated and echoed (serve/scorer._pack_outputs), so the staging slot
  recycles in place instead of a per-batch HBM free+alloc;
- a bounded in-flight window (``depth`` device batches, >= 2) sits
  between dispatch and readback — the ping-pong that keeps the device fed
  while results are still crossing the link;
- a **readback worker** drains completed handles: one packed D2H
  transfer per chunk, arena buffers released (only AFTER readback — jax
  may alias host staging memory zero-copy); the native response encode
  then runs back on the submitting thread (which was blocked on its
  future anyway), so encodes of concurrent RPCs parallelize instead of
  serializing behind the drain.

Stage spans attach to the originating RPC's root across threads
(obs/tracing.py ``span(parent=...)``), so /debug/flightz and the
per-stage histograms still decompose pipelined requests — with interval-
union accounting, since concurrent stages now sum past the RPC's wall
time. Results are bit-exact with the lockstep path: same chunk
boundaries, same compiled executables, same zero padding
(tests/test_host_pipeline.py pins it).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any

import numpy as np

from igaming_platform_tpu.obs import tracing
from igaming_platform_tpu.obs.tracing import annotate, span
from igaming_platform_tpu.serve.arena import ArenaPool
from igaming_platform_tpu.serve.batcher import pad_batch

_SENTINEL = object()

_RESULT_KEYS = ("score", "action", "reason_mask", "rule_score", "ml_score")


class _Job:
    """One wire batch moving through the pipeline (one RPC's rows)."""

    __slots__ = ("x", "bl", "include_features", "start", "parent", "total",
                 "n_chunks", "parts", "rtms", "future", "done_chunks",
                 "account_ids", "snap")

    def __init__(self, x: np.ndarray, bl: np.ndarray, include_features: bool,
                 start: float, parent, n_chunks: int, account_ids=None,
                 snap=None):
        self.x = x
        self.bl = bl
        self.account_ids = account_ids
        # Params snapshot (engine.params_snapshot) captured at submit:
        # every chunk of this job scores with the SAME tree and the
        # ledger note records the fingerprint that actually scored it,
        # even when an online promotion hot-swaps params mid-job.
        self.snap = snap
        self.include_features = include_features
        self.start = start
        self.parent = parent  # originating RPC span (cross-thread anchor)
        self.total = x.shape[0]
        self.n_chunks = n_chunks
        self.parts: list[dict | None] = [None] * n_chunks
        self.rtms = np.empty((self.total,), dtype=np.int64)
        self.future: Future = Future()
        self.done_chunks = 0

    @property
    def failed(self) -> bool:
        return self.future.done()

    def fail(self, exc: BaseException) -> None:
        if not self.future.done():
            self.future.set_exception(exc)


class HostPipeline:
    """Staged wire-batch scorer over a TPUScoringEngine.

    ``score_rows_to_wire`` is a drop-in for the engine's lockstep
    ``_score_rows_encode``; multiple callers submit concurrently and
    their chunks interleave through the shared stage workers, keeping
    the device fed. Worker threads never die on a request error — the
    error lands on that request's future and the workers keep draining
    (the CollectorPipeline discipline, serve/batcher.py).
    """

    def __init__(self, engine: Any, depth: int = 2, stage_workers: int | None = None,
                 name: str = "host-pipeline"):
        # >= 2 in-flight device batches: with one, the readback of batch
        # N gates the dispatch of N+1 and the pipeline degenerates to
        # the lockstep path.
        self.depth = max(2, int(depth))
        # Stage (pad+dispatch) parallelism: one worker would serialize
        # the pad memcpys of concurrently-admitted RPCs that previously
        # ran on their own handler threads. Chunk results are stored by
        # index, and scoring is pure per-row, so dispatch order across
        # workers never changes any output. PIPELINE_STAGE_WORKERS
        # overrides; default 2 matches the bulk admission gate's
        # measured-good in-flight limit.
        if stage_workers is None:
            stage_workers = int(os.environ.get("PIPELINE_STAGE_WORKERS", "2"))
        self.stage_workers = max(1, stage_workers)
        self._engine = engine
        self._arena = ArenaPool(max_per_key=self.depth + self.stage_workers + 1)
        self._stage_q: queue.Queue = queue.Queue(max(8, 4 * self.depth))
        self._inflight_q: queue.Queue = queue.Queue(self.depth)
        self._stage_alive = self.stage_workers  # guarded by _stats_lock
        self._closed = False
        self._close_lock = threading.Lock()

        # Telemetry (guarded by _stats_lock): per-stage busy seconds,
        # active wall (time with >= 1 job in the pipeline — idle gaps
        # must not dilute the overlap ratio), in-flight depth.
        self._stats_lock = threading.Lock()
        self._busy_s = {"dispatch": 0.0, "readback": 0.0, "encode": 0.0}
        self._active_jobs = 0
        self._active_since = 0.0
        self._active_wall_s = 0.0
        self._inflight = 0
        self.max_inflight = 0
        self.batches = 0
        self.jobs = 0
        self.on_inflight = None  # callable(depth) — metrics hook
        self._metrics = None

        self._stage_threads = [
            threading.Thread(target=self._stage_loop, name=f"{name}-stage-{i}",
                             daemon=True)
            for i in range(self.stage_workers)
        ]
        self._readback_worker = threading.Thread(
            target=self._readback_loop, name=f"{name}-readback", daemon=True)
        for t in self._stage_threads:
            t.start()
        self._readback_worker.start()

    # -- metrics -------------------------------------------------------------

    def bind_metrics(self, metrics) -> None:
        """Feed the pipeline gauges of a ServiceMetrics registry."""
        self._metrics = metrics
        self.on_inflight = metrics.pipeline_inflight.set

    def _note_inflight(self, delta: int) -> None:
        with self._stats_lock:
            self._inflight += delta
            self.max_inflight = max(self.max_inflight, self._inflight)
            inflight = self._inflight
        if self.on_inflight is not None:
            try:
                self.on_inflight(inflight)
            except Exception:  # noqa: BLE001 — metrics must not fail scoring
                pass

    def _note_busy(self, stage: str, seconds: float) -> None:
        with self._stats_lock:
            self._busy_s[stage] += seconds

    def _job_enter(self) -> None:
        with self._stats_lock:
            if self._active_jobs == 0:
                self._active_since = time.monotonic()
            self._active_jobs += 1
            self.jobs += 1

    def _job_exit(self) -> None:
        overlap = None
        with self._stats_lock:
            self._active_jobs -= 1
            if self._active_jobs == 0:
                self._active_wall_s += time.monotonic() - self._active_since
                busy = sum(self._busy_s.values())
                if busy > 0:
                    overlap = max(0.0, 1.0 - self._active_wall_s / busy)
        if overlap is not None and self._metrics is not None:
            try:
                self._metrics.pipeline_overlap_ratio.set(round(overlap, 4))
            except Exception:  # noqa: BLE001 — metrics must not fail scoring
                pass

    def stats(self) -> dict:
        """Pipeline health for bench artifacts and /debug surfaces."""
        with self._stats_lock:
            busy_ms = {k: round(v * 1000.0, 3) for k, v in self._busy_s.items()}
            total_busy = sum(self._busy_s.values())
            wall = self._active_wall_s
            if self._active_jobs > 0:  # mid-flight snapshot
                wall += time.monotonic() - self._active_since
            return {
                "depth": self.depth,
                "stage_workers": self.stage_workers,
                "max_inflight": self.max_inflight,
                "batches": self.batches,
                "jobs": self.jobs,
                "stage_busy_ms": busy_ms,
                "active_wall_ms": round(wall * 1000.0, 3),
                "overlap_ratio": (
                    round(max(0.0, 1.0 - wall / total_busy), 4)
                    if total_busy > 0 else 0.0),
                "arena": self._arena.stats(),
            }

    def arena_stats(self) -> dict:
        """Staging-arena occupancy alone (allocated/reused/idle) — the
        device-runtime telemetry gauges (obs/runtime_telemetry.py)
        refresh from this on every /metrics scrape without paying for
        the full stats() snapshot."""
        return self._arena.stats()

    # -- submission ----------------------------------------------------------

    def score_rows_to_wire(
        self, x: np.ndarray, bl: np.ndarray, include_features: bool, start: float,
        account_ids=None,
    ) -> bytes:
        """Gathered [N, 30] rows -> ScoreBatchResponse wire bytes via the
        stage workers. Blocks the caller until its batch completes; other
        callers' batches overlap through the same workers meanwhile. The
        response encode runs back on THIS (otherwise future-blocked)
        thread: encodes of concurrent RPCs parallelize instead of
        serializing behind the readback worker."""
        if self._closed:
            raise RuntimeError("host pipeline is closed")
        total = x.shape[0]
        if total == 0:
            return b""
        batch = self._engine.batch_size
        n_chunks = (total + batch - 1) // batch
        job = _Job(x, bl, include_features, start,
                   tracing.current_span(), n_chunks, account_ids=account_ids,
                   snap=self._engine.params_snapshot())
        self._job_enter()
        try:
            for idx, lo in enumerate(range(0, total, batch)):
                # Blocks when the stage queue is full — backpressure on
                # the gRPC caller, same as the admission gate's intent.
                self._stage_q.put((job, idx, lo, min(lo + batch, total)))  # noqa: MX07 — deliberate bounded backpressure on the gRPC caller (admission-gate intent), never a silent drop
            job.future.result()  # all chunks read back (or job failed)
            return self._encode_job(job)
        finally:
            self._job_exit()

    def _encode_job(self, job: _Job) -> bytes:
        from igaming_platform_tpu.serve.wire import encode_score_batch

        t0 = time.monotonic()
        try:
            with span("score.encode", parent=job.parent, batch=job.total):
                cat = {
                    k: (np.concatenate([p[k] for p in job.parts])
                        if job.n_chunks > 1 else job.parts[0][k])
                    for k in _RESULT_KEYS
                }
                observer = getattr(self._engine, "score_observer", None)
                if observer is not None:
                    try:
                        observer(cat["score"])
                    except Exception:  # noqa: BLE001 — metrics must not fail scoring
                        pass
                # Ledger seam: the encode runs on the submitting (RPC
                # handler) thread, so the note lands under the RPC span
                # and stamps the decision-id prefix on its flight entry.
                from igaming_platform_tpu.serve import ledger as ledger_mod

                ledger_mod.note_decisions(
                    self._engine, cat, n=job.total, wire_mode="wire_row",
                    x=job.x, bl=job.bl, account_ids=job.account_ids,
                    params_fp=job.snap[2] if job.snap else None)
                return encode_score_batch(
                    cat["score"], cat["action"], cat["reason_mask"],
                    cat["rule_score"], cat["ml_score"], job.rtms,
                    job.x if job.include_features else None,
                )
        finally:
            self._note_busy("encode", time.monotonic() - t0)

    # -- stage worker: pad into arenas + async dispatch ----------------------

    def _dispatch_chunk(self, job: _Job, lo: int, hi: int):
        """Pad one chunk into arena staging and launch the device step;
        returns (handle, staging buffers) with the D2H copy started."""
        n = hi - lo
        chunk = job.x[lo:hi]
        blc = job.bl[lo:hi]
        engine = self._engine
        shape = engine._pick_shape(n)
        use_host = engine._fn_host is not None and n <= engine._host_tier
        if not use_host and engine._wire_encode is not None:
            chunk = engine._wire_encode(chunk)
        xp_buf = bl_buf = hold = None
        if n == shape:
            xp, blp = chunk, blc
        else:
            xp_buf = self._arena.acquire((shape, chunk.shape[1]), chunk.dtype)
            xp, _ = pad_batch(chunk, shape, out=xp_buf)
            bl_buf = self._arena.acquire((shape,), np.bool_)
            blp, _ = pad_batch(blc, shape, out=bl_buf)
            if getattr(engine, "shadow", None) is not None:
                # The shadow's fallback path scores directly from the
                # donated-batch echo, which may alias these staging
                # buffers zero-copy: a 2-party hold defers the arena
                # release until readback AND the shadow worker are both
                # done. The launch seam releases the shadow party
                # immediately when the echo isn't taken (fused mode,
                # drops).
                from igaming_platform_tpu.serve.arena import StagingHold

                hold = StagingHold(self._arena, (xp_buf, bl_buf), parties=2)
        out = engine._launch_padded(xp, blp, use_host, snap=job.snap,
                                    n_valid=n, staging_hold=hold)
        return out, xp_buf, bl_buf, hold

    def _stage_loop(self) -> None:
        from igaming_platform_tpu.obs import hostprof

        hostprof.register_scoring_thread("pipeline_stage")
        while True:
            item = self._stage_q.get()
            if item is _SENTINEL:
                # The LAST stage worker to exit forwards the sentinel so
                # the readback worker outlives every possible producer.
                with self._stats_lock:
                    self._stage_alive -= 1
                    last = self._stage_alive == 0
                if last:
                    self._inflight_q.put(_SENTINEL)  # noqa: MX07 — shutdown sentinel from the last stage worker; blocking is correct (the readback worker must outlive every producer)
                return
            job, idx, lo, hi = item
            if job.failed:
                continue
            t0 = time.monotonic()
            try:
                with span("score.dispatch", parent=job.parent, batch=hi - lo), \
                        annotate("score_step"):
                    out, xp_buf, bl_buf, hold = self._dispatch_chunk(
                        job, lo, hi)
            except BaseException as exc:  # noqa: BLE001 — belongs to the job
                job.fail(exc)
                continue
            finally:
                self._note_busy("dispatch", time.monotonic() - t0)
            self._note_inflight(+1)
            with self._stats_lock:
                self.batches += 1
            # Blocks at `depth` batches in flight: the device stays <=
            # depth steps ahead of readback (bounded memory, ping-pong).
            self._inflight_q.put(  # noqa: MX07 — the bounded in-flight window IS the ping-pong: blocking at depth is the design, not an accident
                (job, idx, lo, hi - lo, out, xp_buf, bl_buf, hold, t0))

    # -- readback worker -----------------------------------------------------

    def _readback_loop(self) -> None:
        from igaming_platform_tpu.obs import hostprof
        from igaming_platform_tpu.serve.scorer import _device_readback, _unpack_host

        hostprof.register_scoring_thread("readback")
        while True:
            item = self._inflight_q.get()
            if item is _SENTINEL:
                return
            job, idx, lo, n, out, xp_buf, bl_buf, hold, t_dispatch = item
            t0 = time.monotonic()
            try:
                with span("score.readback", parent=job.parent, batch=n):
                    host = _unpack_host(_device_readback(out))
            except BaseException as exc:  # noqa: BLE001 — belongs to the job
                self._note_inflight(-1)
                self._note_busy("readback", time.monotonic() - t0)
                job.fail(exc)
                continue
            self._note_inflight(-1)
            self._note_busy("readback", time.monotonic() - t0)
            # Bulk chunks feed the same online step model the deadline
            # scheduler plans against — the throughput shapes get real
            # evidence even when interactive traffic never pads to them.
            model = getattr(self._engine, "step_model", None)
            if model is not None:
                model.observe(self._engine._pick_shape(n),
                              (time.monotonic() - t_dispatch) * 1000.0)
            # Readback done -> the step has consumed its inputs; only now
            # may the staging buffers be rewritten (CPU zero-copy alias).
            # With a hold, the release waits for the echo-fed shadow
            # fallback's party too.
            if hold is not None:
                hold.release()
            else:
                self._arena.release(xp_buf)
                self._arena.release(bl_buf)
            if job.failed:
                continue
            job.parts[idx] = {k: host[k][:n] for k in _RESULT_KEYS}
            job.rtms[lo:lo + n] = int((time.monotonic() - job.start) * 1000.0)
            job.done_chunks += 1
            if job.done_chunks == job.n_chunks and not job.future.done():
                # All chunks landed; the CALLER thread does the encode.
                job.future.set_result(None)

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Drain both workers and join them. Idempotent; pending jobs
        complete (their chunks are already queued ahead of the
        sentinel)."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        for _ in self._stage_threads:
            self._stage_q.put(_SENTINEL)  # noqa: MX07 — shutdown sentinel; pending chunks are already queued ahead of it, blocking delivery is the drain contract
        for t in self._stage_threads:
            t.join(timeout=30)
        self._readback_worker.join(timeout=30)
