"""FLOPs/bytes cost model + device peaks: turns bench timings into
MFU / HBM-utilization figures so "fast" is normalized against what the
hardware can do (the reference publishes no such figures at all —
BASELINE.md; these make "matching-or-beating" auditable).

Costs come from XLA's own cost analysis of the compiled executable
(``compiled.cost_analysis()``: ``flops`` and ``bytes accessed``) rather
than hand-derived formulas, so they track the actual fused program.
Peaks are a small per-``device_kind`` table of published chip specs;
unknown kinds (e.g. a CPU rig) report achieved rates with null
utilization instead of inventing a denominator.
"""

from __future__ import annotations

import threading
from typing import Any

# Published per-chip peaks: (dense bf16 FLOP/s, HBM bytes/s).
# v5e: 197 bf16 TFLOP/s, 16 GB HBM2 @ 819 GB/s. v4: 275 TFLOP/s,
# 1228 GB/s. v5p: 459 TFLOP/s, 2765 GB/s. v6e (Trillium): 918 TFLOP/s,
# 1640 GB/s. Matching is by substring of jax's ``device_kind``.
_PEAKS: dict[str, tuple[float, float]] = {
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6 lite": (918e12, 1640e9),
    "v6e": (918e12, 1640e9),
}


def peak_for(device: Any) -> tuple[float, float] | None:
    """(peak FLOP/s, peak HBM B/s) for a jax device, else None."""
    kind = str(getattr(device, "device_kind", "")).lower()
    for key, peaks in _PEAKS.items():
        if key in kind:
            return peaks
    return None


def compiled_cost(compiled: Any) -> dict[str, float]:
    """{"flops": F, "bytes": B} per execution of a compiled executable,
    from XLA's cost analysis; zeros when the backend exposes none."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        cost = {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
    }


def cost_of(fn: Any, *example_args, **lower_kwargs) -> dict[str, float]:
    """Lower+compile ``fn`` (a jax-jittable callable or an existing
    jitted wrapper) on example args and return its per-call cost."""
    import jax

    wrapped = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = wrapped.lower(*example_args, **lower_kwargs).compile()
    return compiled_cost(compiled)


def utilization(
    cost: dict[str, float], seconds_per_call: float, device: Any
) -> dict[str, float | None]:
    """Achieved rates + utilization vs the device's published peaks.

    Returns achieved_tflops / achieved_hbm_gbps always (when the cost
    model has the numerator), and mfu / hbm_util only when the device
    kind has a known peak — a CPU-rig line carries nulls rather than a
    made-up denominator.
    """
    out: dict[str, float | None] = {
        "achieved_tflops": None, "achieved_hbm_gbps": None,
        "mfu": None, "hbm_util": None,
    }
    if not seconds_per_call > 0.0:  # also catches NaN (below-resolution)
        return out
    flops_s = cost.get("flops", 0.0) / seconds_per_call
    bytes_s = cost.get("bytes", 0.0) / seconds_per_call
    if flops_s > 0:
        out["achieved_tflops"] = round(flops_s / 1e12, 4)
    if bytes_s > 0:
        out["achieved_hbm_gbps"] = round(bytes_s / 1e9, 2)
    peaks = peak_for(device)
    if peaks is not None:
        peak_flops, peak_hbm = peaks
        if flops_s > 0:
            out["mfu"] = round(flops_s / peak_flops, 4)
        if bytes_s > 0:
            out["hbm_util"] = round(bytes_s / peak_hbm, 4)
    return out


class OnlineStepModel:
    """Online per-shape step-time model for the deadline scheduler.

    An EWMA of *observed* dispatch→collect wall times keyed by padded
    batch shape (rows). The deadline scheduler plans each tick against
    it: "can a 4096-row step still land inside the tightest admitted
    deadline, or should this tick flush a 256 tier now?" — and the
    batcher's hedged re-dispatch uses the same prediction as its stall
    threshold. Offline cost analysis (``compiled_cost``) can seed
    relative shape scaling, but live observations always win: the model
    must track the link actually serving, not the chip's spec sheet.

    Predictions for never-observed shapes extrapolate from the nearest
    observed shape by row ratio (step cost here is dominated by
    per-row work + a constant launch overhead; linear-in-rows is the
    conservative upper bound for smaller shapes). Thread-safe; O(1)
    per observation.
    """

    def __init__(self, alpha: float = 0.2):
        self.alpha = alpha
        self._lock = threading.Lock()
        self._ewma_ms: dict[int, float] = {}
        self._ewvar_ms: dict[int, float] = {}
        self.observations = 0

    def observe(self, shape_rows: int, ms: float) -> None:
        if not (ms >= 0.0):  # rejects NaN and negatives
            return
        shape = int(shape_rows)
        with self._lock:
            self.observations += 1
            prev = self._ewma_ms.get(shape)
            if prev is None:
                self._ewma_ms[shape] = float(ms)
                self._ewvar_ms[shape] = 0.0
            else:
                delta = float(ms) - prev
                self._ewma_ms[shape] = prev + self.alpha * delta
                self._ewvar_ms[shape] = (
                    (1 - self.alpha) * (self._ewvar_ms[shape]
                                        + self.alpha * delta * delta))

    def predict_ms(self, shape_rows: int) -> float | None:
        """Expected step wall (ms) at ``shape_rows``, or None before
        any evidence exists (callers fall back to fixed-knob policy)."""
        shape = int(shape_rows)
        with self._lock:
            if not self._ewma_ms:
                return None
            hit = self._ewma_ms.get(shape)
            if hit is not None:
                return hit
            # Nearest observed shape, scaled by row ratio only when
            # extrapolating UP (more rows can't be faster); a smaller
            # shape is bounded above by the nearest larger observation.
            known = sorted(self._ewma_ms)
            larger = [s for s in known if s >= shape]
            if larger:
                return self._ewma_ms[larger[0]]
            nearest = known[-1]
            return self._ewma_ms[nearest] * (shape / nearest)

    def stall_threshold_ms(self, shape_rows: int, mult: float = 4.0,
                           min_slack_ms: float = 5.0) -> float | None:
        """The hedge trip-wire: a batch still uncollected past this is
        a stalled pipeline window. Predicted step time times ``mult``,
        never tighter than predicted + ``min_slack_ms`` + 3 sigma —
        noise must not hedge the median batch."""
        with self._lock:
            mean = self._ewma_ms.get(int(shape_rows))
            var = self._ewvar_ms.get(int(shape_rows), 0.0)
        if mean is None:
            mean = self.predict_ms(shape_rows)
            if mean is None:
                return None
        sigma = var ** 0.5
        return max(mean * mult, mean + min_slack_ms + 3.0 * sigma)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "observations": self.observations,
                "ewma_ms": {str(k): round(v, 4)
                            for k, v in sorted(self._ewma_ms.items())},
            }


def device_step_time(fn, *args, n: int = 17, reps: int = 3) -> float:
    """Per-step device time (seconds) for a jitted ``fn(*args)``.

    Dispatch is asynchronous, so timing a loop of dispatches measures
    the enqueue, and a per-step fence folds the constant dispatch and
    readback cost into every step. This is a TWO-POINT fit with a real
    data readback as the fence: time 1 dispatch + device_get, time ``n``
    dispatches + device_get of only the last result, and take the slope.
    Per-device execution is in-order under PJRT, so the n dispatches
    execute back-to-back and the difference is (n-1) steps of pure
    device time — the constant dispatch overhead and the readback
    latency cancel.
    """
    import time as _t

    import jax as _jax

    _jax.device_get(fn(*args))  # compile + warm the readback path

    def total(k: int) -> float:
        best = float("inf")
        for _ in range(reps):
            t0 = _t.perf_counter()
            for _ in range(k - 1):
                fn(*args)
            _jax.device_get(fn(*args))
            best = min(best, _t.perf_counter() - t0)
        return best

    diff = total(n) - total(1)
    if diff <= 0:
        # Per-step time is below the fence's timing noise (a tiny
        # elementwise op behind a much longer readback). A clamp would
        # publish a nonsense rate — return NaN so callers report "below
        # timing resolution" instead.
        return float("nan")
    return diff / (n - 1)
