"""Operations and bytes of the ``ling`` head's HELD routed experts for one
call of the fused step: the chip's share of the expert layer
(``head/moe/experts`` in the program), all expert layers held."""

from __future__ import annotations


def ling_expert_share(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch, AS AN EXPECTATION AT
    UNIFORM ROUTING (as ``costs/pangu_expert_share.py``): of the
    ``positions x num_experts_per_tok`` pairs a layer, the share ``held /
    published experts`` falls on the experts this chip holds
    (``num_experts`` of the file against ``head.published.num_experts``);
    each such pair is three products of ``hidden_size`` x
    ``moe_intermediate_size``, two operations a multiply-add. A skewed
    routing brings more or fewer pairs here, and the program does not route
    a window's padding (fewer still); the count follows neither. Bytes:
    each held expert's three matrices once a layer at 2 bytes (bfloat16 at
    rest), each expected pair's input row read at 2 bytes a channel, and
    the float32 result of every position written once. The sort, the
    gathered rows and the products between gate and down are the layer's
    intermediates and are not counted."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    held = config["num_experts"]
    routed = config.get("head", {}).get("published", {}).get("num_experts", held)
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    expert_macs = 3 * hidden * width
    pairs = positions * config["num_experts_per_tok"] * held / routed
    return {"flops": 2 * pairs * expert_macs * layers,
            "bytes": layers * (2 * held * expert_macs + pairs * hidden * 2
                               + positions * hidden * 4)}
