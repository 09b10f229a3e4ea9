"""Operations and bytes of the ``kexaone`` head's multi-token-prediction
module for one call of the fused step: everything under ``head/mtp/`` in the
program."""

from __future__ import annotations

from chipbench import validate


def kexaone_mtp_module(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the output needs, whatever implements it: the join ``[e ; f]
    W_eh`` and the layer's ``K, V`` product at EVERY position of the padded
    batch (the one query a row reads keys made from every position); ``Wq``,
    ``Wo``, the router, the shared expert and the expected pairs on the held
    experts (``num_experts_per_tok x held / published``, half a pair a row) at
    ONE position a row. The one-query core (134 MFLOP of 0.72 T) is counted
    by ``costs/kexaone_attention_core.py`` and not here. Bytes: ``W_eh``, the attention matrices, the router and
    the shared expert once at 2 bytes, as many held experts' matrices as the
    rows' expected pairs can touch, the two float32 inputs of the join read
    once and ``K, V`` written and read at 2 bytes; ``u`` itself and the norms
    are not counted."""
    share = validate.load_code("costs", "kexaone_expert_share")
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, hd = config["hidden_size"], config["head_dim"]
    qw = config["num_attention_heads"] * hd
    kvw = config["num_key_value_heads"] * hd
    held, routed = share.held_and_routed(config)
    expert = share.expert_macs(config)
    pairs = batch * config["num_experts_per_tok"] * held / routed
    everywhere = 2 * hidden * hidden + hidden * 2 * kvw
    once = 2 * hidden * qw + hidden * routed + expert
    weights = (everywhere + 2 * hidden * qw + hidden * routed + expert
               + min(held, pairs) * expert)
    return {"flops": 2 * (positions * everywhere + batch * once + pairs * expert),
            "bytes": 2 * weights + 2 * positions * hidden * 4
            + 2 * positions * 2 * kvw * 2}
