"""Operations and bytes of the ``longcat`` head's expert branch past its
router for one call of the fused step: the chip's share of the routed experts
(``head/moe/experts`` in the program) and the identity experts' term
(``head/moe/zero``), all layers held."""

from __future__ import annotations


def held_and_outputs(config: dict) -> tuple[int, int, int]:
    """``(real experts this chip holds, published real experts, the router's
    outputs)``: ``n_routed_experts`` of the file against
    ``head.published.n_routed_experts``, plus ``zero_expert_num``."""
    held = config["n_routed_experts"]
    real = config.get("head", {}).get("published", {}).get(
        "n_routed_experts", held)
    return held, real, real + config["zero_expert_num"]


def expert_macs(config: dict) -> int:
    """One expert's three products for one position."""
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"]


def routed_rows(config: dict, batch: int) -> list[int]:
    """The positions each layer held routes: every position of the padded
    batch, and in the last layer, whose branch the output reads at one
    position a row, ``batch``."""
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    return [batch * n_ev] * (config["num_layers"] - 1) + [batch]


def longcat_expert_share(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs, AS AN EXPECTATION AT UNIFORM ROUTING (which
    the seeded tree's balanced bias approaches): of a layer's ``rows x
    moe_topk`` pairs the share ``held / outputs`` falls on the experts this
    chip holds, each such pair three products of ``hidden_size`` x
    ``expert_ffn_hidden_size``, two operations a multiply-add; the share
    ``zero_expert_num / outputs`` falls on identity experts, which multiply
    nothing: a position's identity pairs cost one read of its row and one
    multiply-add a channel, and are counted as bytes. A skewed routing
    brings more or fewer pairs here, and the program does not route a
    window's padding (fewer still); the count follows neither. Bytes: each
    held expert's three matrices once a layer at 2 bytes (of the last
    layer's, as many as its rows' expected pairs can touch), each expected
    pair's input row read at 2 bytes a channel, each routed position's row
    read once in float32 for the identity term and its float32 result
    written once."""
    hidden = config["hidden_size"]
    held, _, outputs = held_and_outputs(config)
    expert = expert_macs(config)
    flops = nbytes = 0
    for rows in routed_rows(config, batch):
        pairs = rows * config["moe_topk"] * held / outputs
        flops += 2 * pairs * expert
        nbytes += (2 * min(held, pairs) * expert + pairs * hidden * 2
                   + rows * hidden * (4 + 4))
    return {"flops": flops, "bytes": nbytes}
