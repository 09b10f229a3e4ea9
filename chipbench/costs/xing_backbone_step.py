"""Operations and bytes of the fused session step with the ``xing``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def xing_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py``
    counts them, plus the head at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through every layer held.

    Multiply-adds a position: the projector; in every layer the latent
    attention (``costs/pangu_mla_attention.attention_macs``: the same five
    projections and the window's keys, at this file's widths); in each of
    the ``first_k_dense_replace`` dense layers three products of
    ``hidden_size`` x ``intermediate_size``; in each expert layer the
    router, the shared expert's three products and ``num_experts_per_tok``
    routed experts' (every expert is held; the program does not route a
    window's padding, which the count does not follow: it is the padded
    batch's). Two operations a multiply-add, and the hyper-connections'
    few (``costs/xing_hc_streams.py``). Bytes: every matrix of the head
    once at 2 bytes (bfloat16 at rest; every expert is read whatever the
    routing; norm gains, the expert bias and the scoring head are not
    counted) and the streams' least traffic with ``phi`` in float32
    (``costs/xing_hc_streams.py``)."""
    costs = lambda name: validate.load_code("costs", name)
    base = costs("fused_step").fused_step(config, batch, index_mode=index_mode)
    hc = costs("xing_hc_streams").xing_hc_streams(config, batch,
                                                  index_mode=index_mode)
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden = config["hidden_size"]
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    projections, over_keys = costs("pangu_mla_attention").attention_macs(
        config, n_ev)
    dense_mlp = 3 * hidden * config["intermediate_size"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    router = hidden * config["n_routed_experts"]
    shared = config["n_shared_experts"] * expert
    macs = (EVENT_WIDTH * hidden + layers * (projections + over_keys)
            + dense * dense_mlp
            + (layers - dense) * (router + shared
                                  + config["num_experts_per_tok"] * expert))
    held = (EVENT_WIDTH * hidden + layers * projections + dense * dense_mlp
            + (layers - dense) * (router + shared
                                  + config["n_routed_experts"] * expert))
    return {"flops": base["flops"] + 2 * positions * macs + hc["flops"],
            "bytes": base["bytes"] + 2 * held + hc["bytes"]}
