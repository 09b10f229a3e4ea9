"""Operations and bytes of the ``phi4flash`` head's selective scans for one
call of the fused step: what lies under ``head/ssm/scan`` in the program,
every Mamba layer of the stack."""

from __future__ import annotations

STATE, EXPAND = 16, 2  # Mamba-1's defaults (the configuration's head.assumed)


def mamba_layers(config: dict) -> int:
    """Layers ``0, 2, .. L/2``: a Mamba layer every ``mb_per_layer``-th up to
    the memory's."""
    return config["num_hidden_layers"] // 2 // config["mb_per_layer"] + 1


def phi4flash_selective_scan(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The least work, whatever implements it: at every position of the
    padded batch (``batch`` x ``SESSION_EVENTS``) a channel and a state
    column the decay times the state, ``dt B x`` added, and ``C``'s part of
    the sum (two multiplies for the decay's argument and the input, a
    multiply and an add for the update, a multiply and an add for the sum:
    six operations; the exponential is not counted), ``d_inner`` x 16 of them
    a position. Bytes: ``x`` and ``dt`` read and ``y`` written once in
    float32, ``B`` and ``C`` read, ``A`` and ``D``.

    ``chipbench/peaks.py`` states the MXU's rate and the memory's, and none
    for the vector unit that does all of this work (0.34 G state updates and
    as many exponentials a layer at the cell's shape), so the share
    ``readers._roofline_share`` makes of these is of the bytes' time (0.31 ms
    a layer) and reads LOW by construction: it says how far the kernel is
    from a scan that only moved its operands, not how near the vector unit's
    own limit it runs."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    inner, layers = EXPAND * config["hidden_size"], mamba_layers(config)
    return {"flops": layers * 6 * positions * inner * STATE,
            "bytes": layers * 4 * (3 * positions * inner + 2 * positions * STATE
                                   + (STATE + 1) * inner)}
