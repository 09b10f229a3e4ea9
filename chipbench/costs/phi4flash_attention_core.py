"""Operations and bytes of the ``phi4flash`` head's attention cores in the
layers that mix positions, for one call of the fused step: what lies under
``head/attn/window/core`` and ``head/attn/full/core`` in the program."""

from __future__ import annotations


def keys_kept(n_ev: int, band: int | None) -> int:
    """(query, key) pairs one head's mask keeps in a window of ``n_ev``
    positions: key ``j`` for query ``i`` where ``j <= i`` and, with a
    ``band``, ``i - j < band``."""
    if band is None or band >= n_ev:
        return n_ev * (n_ev + 1) // 2
    return band * (band + 1) // 2 + (n_ev - band) * band


def band_layers(config: dict) -> int:
    """Odd layers under ``L/2``."""
    return config["num_hidden_layers"] // 4


def pair_macs(config: dict) -> int:
    """Multiply-adds a kept (query, key) pair over all query heads: each
    side's score over the head's width and its weighted sum of a value twice
    as wide (both softmaxes of differential attention)."""
    heads = config["num_attention_heads"]
    return heads * 3 * (config["hidden_size"] // heads)


def phi4flash_attention_core(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The least work the output needs: in each band layer every query of
    every window of the padded batch against the keys its band keeps; in the
    full layer (``L/2 + 1``) ONE query a window, the one that is scored,
    against the window's keys (``SESSION_EVENTS`` of them: the bound a full
    window reaches). Two operations a multiply-add. Bytes: a band layer reads
    ``q`` (float32) and ``k``, ``v`` (2 bytes a channel) and writes the
    subtracted, normed result (float32); the full layer reads ``k`` and
    ``v``. The softmaxes, the subtraction and the norm are not counted."""
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden = config["hidden_size"]
    kvw = config["num_key_value_heads"] * (hidden // config["num_attention_heads"])
    bands = band_layers(config)
    pairs = batch * (bands * keys_kept(n_ev, config["sliding_window"]) + n_ev)
    return {"flops": 2 * pairs * pair_macs(config),
            "bytes": positions * (bands * (8 * hidden + 4 * kvw) + 4 * kvw)}
