"""The ``pattern`` session head (``SESSION_HEAD=pattern``, the program's
default): no parameters, one closed-form score per window."""

from __future__ import annotations

import numpy as np

from chipbench.reference import F32

COL_DEPOSIT, COL_BET = 2, 4


def make_params(seed: int, config: dict) -> None:
    return None


def forward(params, windows: np.ndarray, lengths: np.ndarray, rnd) -> np.ndarray:
    return pattern_head(windows, lengths)


def pattern_head(win: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Bet/deposit cycling at a regular cadence with consistent amounts."""
    n = win.shape[1]
    m = (np.arange(n)[None, :] < lengths[:, None]).astype(F32)
    cnt = np.maximum(m.sum(1), 1.0)
    log_amt, log_dt = win[..., 0], win[..., 1]
    is_dep, is_bet = win[..., COL_DEPOSIT], win[..., COL_BET]
    bd = ((is_bet + is_dep) * m).sum(1) / cnt
    pair_m = m[:, 1:] * m[:, :-1]
    pairs = np.maximum(pair_m.sum(1), 1.0)
    alt = is_bet[:, 1:] * is_dep[:, :-1] + is_dep[:, 1:] * is_bet[:, :-1]
    alt_frac = (alt * pair_m).sum(1) / pairs
    dt_m = m[:, 1:]
    dt_cnt = np.maximum(dt_m.sum(1), 1.0)
    dt_mu = (log_dt[:, 1:] * dt_m).sum(1) / dt_cnt
    dt_var = (((log_dt[:, 1:] - dt_mu[:, None]) ** 2) * dt_m).sum(1) / dt_cnt
    a_mu = (log_amt * m).sum(1) / cnt
    a_var = (((log_amt - a_mu[:, None]) ** 2) * m).sum(1) / cnt
    out = bd * alt_frac * np.exp(-4.0 * dt_var) * np.exp(-2.0 * a_var)
    return np.clip(out, 0.0, 1.0).astype(F32)
