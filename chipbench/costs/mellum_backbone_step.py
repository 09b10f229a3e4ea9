"""Operations and bytes of the fused session step with the ``mellum``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def mellum_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py``
    counts them, plus the head at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through every layer held.

    Multiply-adds a position a layer: the q, k, v and o projections, the
    router over all experts, three products in each of
    ``num_experts_per_tok`` experts; and over the keys each layer's mask
    keeps (a band of ``sliding_window`` in a sliding layer, every causal
    key in a full one: ``costs/mellum_attention_core.py``) the attention
    scores and the weighted sum of values. Two operations a multiply-add.
    Bytes: every parameter of the head once at 2 bytes (bfloat16 at rest;
    every expert is read whatever the routing, the norm gains and the
    scoring head are not counted), and the projector."""
    base = validate.load_code("costs", "fused_step").fused_step(
        config, batch, index_mode=index_mode)
    core = validate.load_code("costs", "mellum_attention_core")
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = hidden * hd * (2 * heads + 2 * kv)
    router = hidden * config["num_experts"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * expert
    layers = config["num_hidden_layers"]
    macs = (positions * (layers * (attn + router + routed) + EVENT_WIDTH * hidden)
            + 2 * batch * heads * hd * core.core_pairs(config, n_ev))
    param_bytes = 2 * (layers * (attn + router + config["num_experts"] * expert)
                       + EVENT_WIDTH * hidden)
    return {"flops": base["flops"] + 2 * macs,
            "bytes": base["bytes"] + param_bytes}
