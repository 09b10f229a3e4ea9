"""Operations and bytes of the fused session step with the ``lfm2``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def lfm2_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py``
    counts them, plus the head at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through every layer held.

    Multiply-adds a position: the projector; in each ``conv`` layer the
    two projections (``costs/lfm2_shortconv.py``); in each
    ``full_attention`` layer the q, k, v and o projections and, over the
    window's keys, the scores and the weighted sum of values; in each of
    the ``num_dense_layers`` leading layers three products of
    ``hidden_size`` x ``intermediate_size``; in each other layer the router
    over all experts and three products in each of ``num_experts_per_tok``
    experts (``costs/lfm2_moe_experts.py``). Two operations a multiply-add.
    Bytes: every matrix of the head once at 2 bytes (bfloat16 at rest;
    every expert is read whatever the routing), the routers and the taps at
    4; norm gains, biases and the scoring head are not counted."""
    costs = lambda name: getattr(validate.load_code("costs", name), name)
    base = costs("fused_step")(config, batch, index_mode=index_mode)
    conv = costs("lfm2_shortconv")(config, batch, index_mode=index_mode)
    experts = costs("lfm2_moe_experts")(config, batch, index_mode=index_mode)
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden = config["hidden_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = hidden // heads
    kinds = config["layer_types"]
    n_conv = sum(1 for kind in kinds if kind == "conv")
    n_attn = len(kinds) - n_conv
    dense = config["num_dense_layers"]
    moe = config["num_hidden_layers"] - dense
    attn = hidden * hd * (2 * heads + 2 * kv)
    over_keys = n_ev * 2 * heads * hd
    dense_mlp = 3 * hidden * config["intermediate_size"]
    router = hidden * config["num_experts"]
    macs = positions * (EVENT_WIDTH * hidden + n_attn * (attn + over_keys)
                        + dense * dense_mlp + moe * router)
    conv_weights = 2 * hidden * 4 * hidden + 4 * hidden * config["conv_L_cache"]
    expert_weights = (2 * config["num_experts"] * 3 * hidden
                      * config["moe_intermediate_size"])
    param_bytes = (2 * (EVENT_WIDTH * hidden + n_attn * attn + dense * dense_mlp)
                   + n_conv * conv_weights + moe * (4 * router + expert_weights))
    return {"flops": base["flops"] + 2 * macs + conv["flops"] + experts["flops"],
            "bytes": base["bytes"] + param_bytes}
