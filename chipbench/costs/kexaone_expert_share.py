"""Operations and bytes of the ``kexaone`` head's HELD routed experts for one
call of the fused step: the chip's share of the stack's four expert layers
(``head/moe/experts`` in the program; the module's one layer, run at one
position a row, is counted by ``costs/kexaone_mtp_module.py``)."""

from __future__ import annotations


def held_and_routed(config: dict) -> tuple[int, int]:
    """``(experts this chip holds, experts the router chooses among)``:
    ``num_experts`` of the file against ``head.published.num_experts``."""
    held = config["num_experts"]
    return held, config.get("head", {}).get("published", {}).get(
        "num_experts", held)


def expert_macs(config: dict) -> int:
    """One expert's three products for one position."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def kexaone_expert_share(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch, AS AN EXPECTATION AT
    UNIFORM ROUTING (which the seeded tree's balanced bias approaches): of
    the ``positions x num_experts_per_tok`` pairs a layer, the share ``held /
    published experts`` falls on the experts this chip holds; each such pair
    is three products of ``hidden_size`` x ``moe_intermediate_size``, two
    operations a multiply-add. A skewed routing brings more or fewer pairs
    here, and the program does not route a window's padding (fewer still);
    the count follows neither. Bytes: each held expert's three matrices once
    a layer at 2 bytes, each expected pair's input row read at 2 bytes a
    channel, and the float32 result of every position written once."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    hidden = config["hidden_size"]
    held, routed = held_and_routed(config)
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    pairs = positions * config["num_experts_per_tok"] * held / routed
    return {"flops": 2 * pairs * expert_macs(config) * layers,
            "bytes": layers * (2 * held * expert_macs(config)
                               + pairs * hidden * 2 + positions * hidden * 4)}
