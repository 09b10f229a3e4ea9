"""Operations and bytes of the ``kexaone`` head's attention cores for one
call of the fused step: what lies under ``head/attn/window/core``,
``head/attn/full/core`` and ``head/mtp/attn/full/core`` in the program, all
layers held and the module's."""

from __future__ import annotations

SLIDING = "sliding_attention"


def keys_kept(n_ev: int, band: int | None) -> int:
    """(query, key) pairs one head's mask keeps in a window of ``n_ev``
    positions: key ``j`` for query ``i`` where ``j <= i`` and, with a
    ``band``, ``i - j < band``."""
    if band is None or band >= n_ev:
        return n_ev * (n_ev + 1) // 2
    return band * (band + 1) // 2 + (n_ev - band) * band


def core_pairs(config: dict, n_ev: int) -> int:
    """Kept pairs a window and head: the stack's layers by their kind (a
    ``sliding_attention`` layer keeps a band of ``sliding_window`` keys, a
    ``full_attention`` layer every causal key), and of the module's layer
    ONE query, the window's last but one at the most, against the keys up
    to its own."""
    return sum(keys_kept(n_ev, config["sliding_window"] if kind == SLIDING
                         else None) for kind in config["layer_types"]
               ) + max(n_ev - 1, 1)


def kexaone_attention_core(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The least work, whatever implements it: every window of the padded
    batch (``batch`` x ``SESSION_EVENTS`` positions), each query head's
    scores and weighted sum of values over the keys its layer's mask keeps
    and no other (``head_dim`` multiply-adds each, two operations a
    multiply-add). Bytes: a stack layer's one read of ``q`` as its
    projection left it (float32), of ``k`` and ``v`` (2 bytes a channel)
    and one write of the result (2 bytes); of the module's layer its ``k``
    and ``v`` at every position and ``q`` and the result of one position a
    row. The head norm, the rotary and the softmax are not counted."""
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, layers = config["head_dim"], config["num_hidden_layers"]
    flops = 2 * 2 * batch * heads * hd * core_pairs(config, n_ev)
    kv_bytes = positions * hd * 2 * kv * 2
    return {"flops": flops,
            "bytes": layers * (positions * hd * heads * (4 + 2) + kv_bytes)
            + kv_bytes + batch * hd * heads * (4 + 2)}
