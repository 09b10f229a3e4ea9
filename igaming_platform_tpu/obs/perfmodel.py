"""Device peaks and the online step-time model.

Peaks are a small per-``device_kind`` table of published chip specs
(``chip_smoke.py`` refuses a device that is not in it); the benchmark's
roofline shares are computed in ``chipbench/`` from its own cost files.
``OnlineStepModel`` is what the deadline scheduler plans with.
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
