"""Operations and bytes of the fused session step with the ``longcat``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def longcat_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py`` counts
    them, plus the head as THE OUTPUT NEEDS it, so that a program that
    computes more reads lower and none reads over 100%: every half of every
    layer at every position of the padded batch (``batch`` x
    ``SESSION_EVENTS``) but the last layer's second half and its expert
    branch, which the output reads at one position a row.

    Multiply-adds: the projector at every position; the attentions as
    ``costs/longcat_latent_attention.py`` counts them (the last one's ``K,
    V`` at every position, the rest of it at one a row; the cores over the
    causal pairs alone); a dense MLP's three products of ``hidden_size`` x
    ``ffn_hidden_size`` a half, the last at one position a row; the router's
    product over all its outputs a layer, the last at one a row; the held
    experts' expected pairs (``costs/longcat_expert_share.py``). Bytes: every
    parameter of the stack once at 2 bytes (bfloat16 at rest; norm gains,
    the selection bias and the scoring head are not counted), of the last
    layer's held experts as many as its rows' expected pairs can touch."""
    load = lambda name: getattr(validate.load_code("costs", name), name)(
        config, batch, index_mode=index_mode)
    base, attn, share = (load("fused_step"), load("longcat_latent_attention"),
                         load("longcat_expert_share"))
    experts = validate.load_code("costs", "longcat_expert_share")
    attention = validate.load_code("costs", "longcat_latent_attention")
    hidden, layers = config["hidden_size"], config["num_layers"]
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    held, _, outputs = experts.held_and_outputs(config)
    expert = experts.expert_macs(config)
    mlp = 3 * hidden * config["ffn_hidden_size"]
    router = hidden * outputs
    everywhere = (EVENT_WIDTH * hidden + (2 * layers - 1) * mlp
                  + (layers - 1) * router)
    once = mlp + router
    touched = sum(min(held, rows * config["moe_topk"] * held / outputs)
                  for rows in experts.routed_rows(config, batch))
    params = (EVENT_WIDTH * hidden + layers * (2 * mlp + router)
              + 2 * layers * sum(attention.projection_macs(config).values())
              + touched * expert)
    return {"flops": base["flops"] + attn["flops"] + share["flops"]
            + 2 * (positions * everywhere + batch * once),
            "bytes": base["bytes"] + 2 * params}
