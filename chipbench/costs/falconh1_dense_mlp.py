"""Operations and bytes of the ``falconh1`` head's dense MLPs for one call
of the fused step: the SwiGLU of every layer held (``head/mlp/dense`` in
the program)."""

from __future__ import annotations


def falconh1_dense_mlp(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) goes through the gate, up
    and down products of each layer (three of ``hidden_size`` x
    ``intermediate_size``, two operations a multiply-add; the two
    multipliers, ``silu`` and the product between are a few operations a
    channel and are left out). Bytes: the three matrices once a layer at 2
    bytes (bfloat16 at rest); a position's normed input read once at 2
    bytes a channel and its float32 result written once. The activations
    of width ``intermediate_size`` between the products are a fused
    kernel's intermediates and are not counted. The scope also holds the
    layer's second norm and the residual add, which the cost does not
    count."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    hidden, layers = config["hidden_size"], config["num_hidden_layers"]
    macs = 3 * hidden * config["intermediate_size"]
    return {"flops": 2 * positions * macs * layers,
            "bytes": layers * (2 * macs + positions * hidden * (2 + 4))}
