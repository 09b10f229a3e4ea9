"""Operations and bytes of the ``lfm2`` head's gated short convolutions for
one call of the fused step: every ``conv`` layer held (``head/conv`` in the
program)."""

from __future__ import annotations


def lfm2_shortconv(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) goes through the two
    projections of each ``conv`` layer (``hidden_size`` x 3 ``hidden_size``
    in, ``hidden_size`` x ``hidden_size`` out; two operations a
    multiply-add: the gate, the ``conv_L_cache`` taps and the second gate
    are a few operations a channel beside 16.8 M a position and are left
    out). Bytes: both matrices once a layer at 2 bytes (bfloat16 at rest)
    and the taps at 4; a position's normed input read once at 2 bytes a
    channel and its float32 result written once; and the three elementwise
    passes between the products over float32 channels: ``z = B * X`` (two
    read, one written), the taps over ``z`` (one read, one written: the
    shifted reads are the same rows), ``C * c`` (two read, one written at 2
    bytes, the out product's operand)."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    hidden = config["hidden_size"]
    layers = sum(1 for kind in config["layer_types"] if kind == "conv")
    macs = hidden * 3 * hidden + hidden * hidden
    weight_bytes = 2 * macs + 4 * hidden * config["conv_L_cache"]
    passes = hidden * (3 * 4 + 2 * 4 + 2 * 4 + 2)
    return {"flops": 2 * positions * macs * layers,
            "bytes": layers * (weight_bytes
                               + positions * (hidden * (2 + 4) + passes))}
