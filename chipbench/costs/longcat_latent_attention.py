"""Operations and bytes of the ``longcat`` head's latent attentions for one
call of the fused step: everything under ``head/attn/0`` and ``head/attn/1``
in the program, both attentions of every layer held, at the heads this chip
holds."""

from __future__ import annotations


def projection_macs(config: dict) -> dict[str, int]:
    """Multiply-adds a position in the five products of one attention at the
    held heads (``num_attention_heads`` of the file): hidden -> query latent
    -> heads of (nope + rope); hidden -> key-value latent + the one rotary
    key; the latent -> heads of (nope + value); heads of value -> hidden."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, qr, kvr = config["v_head_dim"], config["q_lora_rank"], config["kv_lora_rank"]
    return {"wq_a": hidden * qr, "wq_b": qr * heads * (nope + rope),
            "wkv_a": hidden * (kvr + rope), "wkv_b": kvr * heads * (nope + v),
            "wo": heads * v * hidden}


def causal_pairs(n_ev: int) -> int:
    """(query, key) pairs a head's causal mask keeps in a window."""
    return n_ev * (n_ev + 1) // 2


def pair_macs(config: dict) -> int:
    """Multiply-adds a kept pair over the held heads: each head's score over
    (nope + rope) and its weighted value."""
    return config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])


def longcat_latent_attention(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The least work the output needs, whatever implements it: a layer
    holds two attentions; every one but the last layer's second runs its
    five products at every position of the padded batch (``batch`` x
    ``SESSION_EVENTS``) and its core over the causal pairs alone; the last
    layer's second, which one query a row reads, its ``Wkv_a`` and ``Wkv_b``
    at every position and ``Wq_a``, ``Wq_b``, ``Wo`` and one row of at most
    ``SESSION_EVENTS`` pairs at one position a row. Two operations a
    multiply-add. Bytes: the five matrices of each attention once at 2
    bytes, the float32 stream read and the result written once an attention
    (one row each in the narrowed one, whose keys' input is read whole).
    Latents, heads and scores are the operation's own and are not
    counted."""
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions, hidden = batch * n_ev, config["hidden_size"]
    attentions = 2 * config["num_layers"]
    macs = projection_macs(config)
    whole, kv = sum(macs.values()), macs["wkv_a"] + macs["wkv_b"]
    pairs = batch * ((attentions - 1) * causal_pairs(n_ev) + n_ev)
    flops = 2 * ((attentions - 1) * positions * whole + positions * kv
                 + batch * (whole - kv) + pairs * pair_macs(config))
    return {"flops": flops,
            "bytes": attentions * 2 * whole
            + (attentions - 1) * positions * hidden * (4 + 4)
            + positions * hidden * 4 + batch * hidden * 4}
