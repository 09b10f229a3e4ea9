"""Bonus-abuse detection service — sequence model over event histories.

Upgrades the reference's scalar abuse heuristics (engine.go:462-466,
bonus_engine.go:268-275) to the sequence detector BASELINE.json config 3
requires: per-player event histories are kept in fixed-size ring buffers,
encoded with models.sequence.encode_event, and scored in fixed-shape
[B, S, E] batches by the transformer (ring/Ulysses-shardable for long
histories). Device-sharing graph linking covers the MULTI_ACCOUNT signal
(risk.proto reason codes).
"""

from __future__ import annotations

import threading
import time
from collections import deque

import jax
import numpy as np

from igaming_platform_tpu.models.sequence import (
    EVENT_DIM,
    SeqConfig,
    abuse_signals,
    encode_event,
    init_sequence_model,
    sequence_forward,
)


class AbuseShed(RuntimeError):
    """Raised when the abuse path sheds load instead of serving a
    degraded score (ABUSE_CPU_POLICY=shed on a CPU-backend boot).
    The gRPC layer maps it to UNAVAILABLE — loud, countable, never a
    silently-slow or silently-different answer."""


class SequenceAbuseDetector:
    """Per-account event history + transformer scoring + device linking.

    ``policy`` selects the scoring path:

    - ``"model"`` (default): the sequence transformer — the TPU path.
    - ``"heuristic"``: vectorized scalar pattern-matching over the same
      ring buffers — the class of signals the reference itself ships
      (engine.go:462-466 / bonus_engine.go:268-275 match on scalar
      aggregates). For boots on the CPU backend (``JAX_PLATFORMS=cpu``), where
      the transformer would collapse to ~80 seq/s; responses carry a
      DEGRADED_CPU_HEURISTIC signal so the degradation is visible.
    - ``"shed"``: refuse with :class:`AbuseShed` (→ gRPC UNAVAILABLE).
    """

    def __init__(
        self,
        params=None,
        cfg: SeqConfig | None = None,
        *,
        max_history: int = 256,
        mesh=None,
        seq_mode: str = "dense",
        threshold: float = 0.5,
        policy: str = "model",
    ):
        if policy not in ("model", "heuristic", "shed"):
            raise ValueError(f"unknown abuse policy: {policy!r}")
        # 2 heads of 32, not 8 of 8: the MXU contracts 128 lanes per
        # pass, so 8-dim heads waste 16x of the array. Measured on v5e:
        # 417k vs 43k seq/s at the serving shape (9.7x), identical
        # trained accuracy (abuse_train A/B). Ulysses head-sharding still
        # divides (seq axis <= 2 covers the serving meshes).
        self.cfg = cfg or SeqConfig(d_model=64, n_heads=2, n_layers=2, d_ff=128)
        self.params = params if params is not None else init_sequence_model(
            jax.random.key(0), self.cfg
        )
        self.max_history = max_history
        self.threshold = threshold
        self.policy = policy
        self._histories: dict[str, deque] = {}
        self._last_ts: dict[str, float] = {}
        self._device_accounts: dict[str, set[str]] = {}
        self._account_devices: dict[str, set[str]] = {}
        self._lock = threading.RLock()

        mode = seq_mode if mesh is not None else "dense"
        self._batch_multiple = (
            int(mesh.shape.get("data", 1)) if (mesh is not None and mode != "dense") else 1
        )
        self._fn = jax.jit(
            lambda p, x: sequence_forward(p, x, self.cfg, mesh=mesh, seq_mode=mode)["abuse"]
        )

    # -- ingestion -----------------------------------------------------------

    def record_event(
        self, account_id: str, amount: int, tx_type: str,
        game_weight: float = 1.0, balance_ratio: float = 0.0,
        device_id: str = "", timestamp: float | None = None,
    ) -> None:
        now = timestamp or time.time()
        with self._lock:
            dt = now - self._last_ts.get(account_id, now)
            self._last_ts[account_id] = now
            hist = self._histories.setdefault(account_id, deque(maxlen=self.max_history))
            hist.append(encode_event(amount, dt, tx_type, game_weight, balance_ratio))
            if device_id:
                self._device_accounts.setdefault(device_id, set()).add(account_id)
                self._account_devices.setdefault(account_id, set()).add(device_id)

    def history_length(self, account_id: str) -> int:
        with self._lock:
            return len(self._histories.get(account_id, ()))

    # -- scoring -------------------------------------------------------------

    def _history_matrix(self, account_ids: list[str], seq_len: int) -> np.ndarray:
        x = np.zeros((len(account_ids), seq_len, EVENT_DIM), dtype=np.float32)
        with self._lock:
            for i, acct in enumerate(account_ids):
                hist = self._histories.get(acct)
                if not hist:
                    continue
                events = list(hist)[-seq_len:]
                x[i, -len(events):] = np.stack(events)  # right-aligned, left-padded
        return x

    def check(self, account_id: str, bonus_id: str = "") -> tuple[float, list[str], list[str]]:
        """(abuse_score, signals, linked_accounts) — the CheckBonusAbuse
        contract (risk.proto:140-145)."""
        if self.policy == "heuristic":
            score, signals = self._heuristic_one(account_id)
        else:
            scores = self.check_batch([account_id])
            score = float(scores[0])
            signals = abuse_signals(score, self.threshold)
        linked = self.linked_accounts(account_id)
        if linked:
            signals.append("MULTI_ACCOUNT")
        return score, signals, linked

    def check_batch(self, account_ids: list[str], seq_len: int | None = None) -> np.ndarray:
        if self.policy == "shed":
            raise AbuseShed("abuse scoring shed: sequence model unavailable "
                            "on this deployment (ABUSE_CPU_POLICY=shed)")
        if self.policy == "heuristic":
            return np.array(
                [self._heuristic_one(a)[0] for a in account_ids], dtype=np.float32
            )
        seq_len = seq_len or min(self.max_history, 64)
        x = self._history_matrix(account_ids, seq_len)
        # On a mesh, the batch axis shards over `data`: pad to a multiple
        # of the axis size (fixed-shape discipline, same as the scorer's
        # batcher) and slice the padding back off.
        n = x.shape[0]
        if self._batch_multiple > 1 and n % self._batch_multiple:
            padded = ((n + self._batch_multiple - 1) // self._batch_multiple) * self._batch_multiple
            x = np.concatenate([x, np.zeros((padded - n, *x.shape[1:]), x.dtype)])
        # The sequence model is a real jit launch: route it through the
        # honest dispatch seam so CheckBonusAbuse RPCs count their device
        # work like every scoring path does.
        from igaming_platform_tpu.serve.scorer import _device_dispatch

        _device_dispatch("abuse_seq_step", x.shape, x.dtype)
        return np.asarray(self._fn(self.params, x))[:n]

    def _heuristic_one(self, account_id: str) -> tuple[float, list[str]]:
        """Scalar pattern-matching over the encoded ring buffer — the
        reference's own abuse signal class (engine.go:462-466), kept as
        the CPU-backend scorer. O(history) numpy, no device."""
        from igaming_platform_tpu.models.sequence import TX_TYPE_INDEX

        with self._lock:
            hist = self._histories.get(account_id)
            h = np.stack(hist) if hist else None
        signals = ["DEGRADED_CPU_HEURISTIC"]
        if h is None or not len(h):
            return 0.0, signals
        dt_s = np.expm1(h[:, 1])  # encode_event stores log1p(dt)
        types = h[:, 2:10]
        # First event's dt is a 0 placeholder (no predecessor), not a
        # rapid-fire gap — a single ordinary deposit must not look fast.
        rapid = float(np.mean(dt_s[1:] < 30.0)) if len(dt_s) > 1 else 0.0
        bonus_frac = float(
            types[:, TX_TYPE_INDEX["bonus_grant"]].mean()
            + types[:, TX_TYPE_INDEX["bonus_wager"]].mean()
        )
        gi = np.flatnonzero(types[:, TX_TYPE_INDEX["bonus_grant"]] > 0)
        wi = np.flatnonzero(types[:, TX_TYPE_INDEX["withdraw"]] > 0)
        quick_cashout = 0.0
        if gi.size and wi.size:
            # Wall-clock from grant to a later withdraw (< 1 h = abuse
            # shape: grant -> burn wagering -> cash out), via cumulative
            # inter-event time — event-count gaps would miss a grant
            # followed by many rapid wagers.
            t = np.cumsum(dt_s)
            gap_s = t[wi[None, :]] - t[gi[:, None]]
            after = wi[None, :] > gi[:, None]
            quick_cashout = float((after & (gap_s < 3600.0)).any())
        low_weight = float(
            np.mean((h[:, 10] < 0.2) & (types[:, TX_TYPE_INDEX["bonus_wager"]] > 0))
        )
        score = float(min(
            1.0,
            0.45 * rapid + 0.6 * bonus_frac + 0.35 * quick_cashout + 0.3 * low_weight,
        ))
        if rapid > 0.5:
            signals.append("RAPID_FIRE_WAGERING")
        if bonus_frac > 0.5:
            signals.append("BONUS_ONLY_PLAYER")
        if quick_cashout:
            signals.append("QUICK_BONUS_CASHOUT")
        if low_weight > 0.3:
            signals.append("LOW_WEIGHT_GAME_FOCUS")
        if score >= self.threshold:
            signals.append("SEQUENCE_MODEL_HIGH_RISK")
        return score, signals

    def is_abuser(self, account_id: str) -> bool:
        """BonusEngine RiskChecker seam (bonus_engine.go:139-141)."""
        score, _, _ = self.check(account_id)
        return score >= self.threshold

    # -- linking -------------------------------------------------------------

    def linked_accounts(self, account_id: str) -> list[str]:
        """Accounts sharing any device with this one (MULTI_ACCOUNT)."""
        with self._lock:
            linked: set[str] = set()
            for device in self._account_devices.get(account_id, ()):
                linked |= self._device_accounts.get(device, set())
            linked.discard(account_id)
            return sorted(linked)

    def swap_params(self, params) -> None:
        self.params = params
