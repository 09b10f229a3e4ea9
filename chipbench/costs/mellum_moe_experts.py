"""Operations and bytes of the ``mellum`` head's expert products for one
call of the fused step: the grouped products over the stacked expert
weights and the results' way back to position order, all layers held
(``head/moe/experts`` in the program)."""

from __future__ import annotations


def mellum_moe_experts(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) goes through its
    ``num_experts_per_tok`` experts (gate, up and down: three products of
    ``hidden_size`` x ``moe_intermediate_size``, two operations a
    multiply-add); every expert's weights (all ``num_experts`` are held)
    are read once a layer at 2 bytes (bfloat16 at rest), each position's
    input is read once at 2 bytes a channel and its float32 result written
    once. The rows sorted by expert and the products between gate and down
    are a kernel's intermediates and are not counted."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    layers, held = config["num_hidden_layers"], config["num_experts"]
    expert_macs = 3 * hidden * width
    flops = 2 * positions * config["num_experts_per_tok"] * expert_macs * layers
    weight_bytes = 2 * held * expert_macs * layers
    return {"flops": flops,
            "bytes": weight_bytes + positions * hidden * (2 + 4) * layers}
