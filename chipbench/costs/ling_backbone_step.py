"""Operations and bytes of the fused session step with the ``ling``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def ling_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py``
    counts them, plus the head at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through every layer held.

    Multiply-adds a position: the projector; in each KDA layer the mixer
    (``costs/ling_kda_mixer.py``); in each latent-attention layer its five
    projections (``Wq`` without a query latent, ``Wkv_a``, ``Wkv_b``, the
    head-wise gate, ``Wo``) and, over the window's keys, the scores (nope +
    rope a head) and the weighted sum of values; in each of the
    ``first_k_dense_replace`` dense layers three products of ``hidden_size``
    x ``intermediate_size``; in each expert layer the router over all
    published experts, the shared expert's three products, and the held
    experts' expected share (``costs/ling_expert_share.py``: an expectation
    at uniform routing). Two operations a multiply-add. Bytes: every matrix
    of the head once at 2 bytes (bfloat16 at rest; every held expert is
    read whatever the routing), the taps at 4; norm gains, ``A_log``,
    ``dt_bias``, the expert bias and the scoring head are not counted, nor
    any pass over the activations.

    Where the parts the layer metrics read leave off: each mixer's norm and
    its add to the stream are under the mixer's scope (``head/kda``,
    ``head/attn``), the second norm under ``head/mlp/dense`` or
    ``head/moe/route``, the expert layer's add under ``head/moe/experts``;
    the projector and the rotary angles are ``head/embed``, the final norm
    and the scoring column ``head/score``, and neither of those two has a
    metric of its own (PERF.md section 5 gives their time from the
    trace)."""
    costs = lambda name: getattr(validate.load_code("costs", name), name)
    base = costs("fused_step")(config, batch, index_mode=index_mode)
    kda = costs("ling_kda_mixer")(config, batch, index_mode=index_mode)
    share = costs("ling_expert_share")(config, batch, index_mode=index_mode)
    mixer = validate.load_code("costs", "ling_kda_mixer")
    kda_layers = mixer.kda_layers(config)
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    published = config.get("head", {}).get("published", {}).get(
        "num_experts", config["num_experts"])
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    rank = config["kv_lora_rank"]
    mla = (hidden * heads * (nope + rope) + hidden * (rank + rope)
           + rank * heads * (nope + dv) + hidden * heads + heads * dv * hidden)
    over_keys = n_ev * heads * (nope + rope + dv)
    dense_mlp = 3 * hidden * config["intermediate_size"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    shared = 3 * hidden * config["moe_shared_expert_intermediate_size"]
    per_moe_layer = hidden * published + config["num_shared_experts"] * shared
    weights = (EVENT_WIDTH * hidden + (layers - kda_layers) * mla
               + dense * dense_mlp + (layers - dense) * per_moe_layer)
    macs = positions * (weights + (layers - kda_layers) * over_keys)
    kda_param_bytes = kda_layers * mixer.weight_bytes(config)
    share_param_bytes = (layers - dense) * 2 * config["num_experts"] * expert
    return {"flops": base["flops"] + 2 * macs + kda["flops"] + share["flops"],
            "bytes": base["bytes"] + 2 * weights + kda_param_bytes
            + share_param_bytes}
