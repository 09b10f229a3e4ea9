"""Operations and bytes of the fused session step with the ``keye``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def keye_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py``
    counts them, plus the head at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through every layer held.

    Multiply-adds a position a layer: the q, k, v and o projections, the
    indexer's three projections, the router over all experts, three
    products in each of ``num_experts_per_tok`` experts, and over the
    window's keys the indexer's dots, the attention scores and the
    weighted sum of values. Two operations a multiply-add. Bytes: every
    parameter of the head once at 2 bytes (bfloat16 at rest; every held
    expert is read whatever the routing, the norm gains and the scoring
    head are not counted), and the projector."""
    base = validate.load_code("costs", "fused_step").fused_step(
        config, batch, index_mode=index_mode)
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    idx_heads, idx_dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    attn = hidden * hd * (2 * heads + 2 * kv)
    indexer = hidden * (idx_heads * idx_dim + idx_dim + idx_heads)
    router = hidden * config["num_experts"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * expert
    over_keys = n_ev * (2 * heads * hd + idx_heads * idx_dim)
    layers = config["num_hidden_layers"]
    macs = positions * (layers * (attn + indexer + router + routed + over_keys)
                        + EVENT_WIDTH * hidden)
    param_bytes = 2 * (layers * (attn + indexer + router
                                 + config["num_local_experts"] * expert)
                       + EVENT_WIDTH * hidden)
    return {"flops": base["flops"] + 2 * macs,
            "bytes": base["bytes"] + param_bytes}
