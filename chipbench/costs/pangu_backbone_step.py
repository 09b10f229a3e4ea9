"""Operations and bytes of the fused session step with the ``pangu``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def pangu_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py``
    counts them, plus the head at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through every layer held.

    Multiply-adds a position: the projector; in every layer the latent
    attention (``costs/pangu_mla_attention.py``); in each of the
    ``first_k_dense_replace`` dense layers three products of ``hidden_size``
    x ``intermediate_size``; in each expert layer the router over all
    published experts, the shared experts' three products, and the held
    experts' expected share (``costs/pangu_expert_share.py``: an
    expectation at uniform routing). Two operations a multiply-add. Bytes:
    every matrix of the head once at 2 bytes (bfloat16 at rest; every held
    expert is read whatever the routing; norm gains and the scoring head
    are not counted)."""
    costs = lambda name: getattr(validate.load_code("costs", name), name)
    base = costs("fused_step")(config, batch, index_mode=index_mode)
    attn = costs("pangu_mla_attention")(config, batch, index_mode=index_mode)
    share = costs("pangu_expert_share")(config, batch, index_mode=index_mode)
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden = config["hidden_size"]
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    published = config.get("head", {}).get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    dense_mlp = 3 * hidden * config["intermediate_size"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    per_moe_layer = hidden * published + config["n_shared_experts"] * expert
    macs = positions * (EVENT_WIDTH * hidden + dense * dense_mlp
                        + (layers - dense) * per_moe_layer)
    attn_param_bytes = attn["bytes"] - layers * positions * hidden * 8
    share_param_bytes = (layers - dense) * 2 * config["n_routed_experts"] * expert
    param_bytes = (2 * (EVENT_WIDTH * hidden + dense * dense_mlp
                        + (layers - dense) * per_moe_layer)
                   + attn_param_bytes + share_param_bytes)
    return {"flops": base["flops"] + 2 * macs + attn["flops"] + share["flops"],
            "bytes": base["bytes"] + param_bytes}
