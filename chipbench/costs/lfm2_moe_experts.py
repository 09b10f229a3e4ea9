"""Operations and bytes of the ``lfm2`` head's expert products for one
call of the fused step: the grouped products over the stacked expert
weights, every expert layer held (``head/moe/experts`` in the program)."""

from __future__ import annotations


def lfm2_moe_experts(config: dict, batch: int, *, index_mode: bool) -> dict:
    """As ``costs/keye_moe_experts.py``: every position of every row
    (``batch`` x ``SESSION_EVENTS``) goes through its
    ``num_experts_per_tok`` experts (gate, up and down: three products of
    ``hidden_size`` x ``moe_intermediate_size``, two operations a
    multiply-add) in each layer past the ``num_dense_layers`` dense ones;
    every expert's weights are read once a layer at 2 bytes (bfloat16 at
    rest, all ``num_experts`` held), each position's input is read once at
    2 bytes a channel and its float32 result written once. The rows sorted
    by expert and the products between gate and down are a kernel's
    intermediates and are not counted."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    layers = config["num_hidden_layers"] - config["num_dense_layers"]
    expert_macs = 3 * hidden * width
    flops = 2 * positions * config["num_experts_per_tok"] * expert_macs * layers
    weight_bytes = 2 * config["num_experts"] * expert_macs * layers
    return {"flops": flops,
            "bytes": weight_bytes + positions * hidden * (2 + 4) * layers}
