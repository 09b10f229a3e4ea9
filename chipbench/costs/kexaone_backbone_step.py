"""Operations and bytes of the fused session step with the ``kexaone``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def kexaone_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py`` counts
    them, plus the head as THE OUTPUT NEEDS it, so that a program that
    computes more reads lower and none reads over 100%: the stack's layers at
    every position of the padded batch (``batch`` x ``SESSION_EVENTS``), the
    module as ``costs/kexaone_mtp_module.py`` counts it (its join and ``K, V``
    at every position, the rest at one a row).

    Multiply-adds a position of the stack: the projector; ``Wq``, ``Wk``,
    ``Wv`` and ``Wo`` in every layer; the dense MLP's three products in the
    leading layers; in every other layer the router, the shared expert and
    the held experts' expected pairs (``costs/kexaone_expert_share.py``). The
    cores, the module's one-query core among them, as
    ``costs/kexaone_attention_core.py`` counts them. Bytes: every
    parameter of the stack once at 2 bytes (bfloat16 at rest; norm gains, the
    expert bias and the scoring head are not counted) and the module's."""
    load = lambda name: getattr(validate.load_code("costs", name), name)(
        config, batch, index_mode=index_mode)
    base, core, share, module = (load("fused_step"),
                                 load("kexaone_attention_core"),
                                 load("kexaone_expert_share"),
                                 load("kexaone_mtp_module"))
    code = validate.load_code("costs", "kexaone_expert_share")
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, hd = config["hidden_size"], config["head_dim"]
    qw = config["num_attention_heads"] * hd
    kvw = config["num_key_value_heads"] * hd
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    held, routed = code.held_and_routed(config)
    expert = code.expert_macs(config)
    attn = 2 * hidden * qw + 2 * hidden * kvw
    mlp = 3 * hidden * config["intermediate_size"]
    sparse = hidden * routed + expert           # router and shared expert
    stack = (EVENT_WIDTH * hidden + layers * attn + dense * mlp
             + (layers - dense) * sparse)
    params = stack + (layers - dense) * held * expert
    return {"flops": base["flops"] + 2 * positions * stack + share["flops"]
            + core["flops"] + module["flops"],
            "bytes": base["bytes"] + 2 * params + module["bytes"]}
