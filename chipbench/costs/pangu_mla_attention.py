"""Operations and bytes of the ``pangu`` head's latent attention for one
call of the fused step: everything under ``head/attn`` in the program,
all layers held."""

from __future__ import annotations


def attention_macs(config: dict, n_ev: int) -> tuple[int, int]:
    """(multiply-adds a position in the five projections of one layer,
    multiply-adds a position over the window's keys). The projections:
    hidden -> query latent -> heads of (nope + rope); hidden -> key-value
    latent + the one rotary key; the latent -> heads of (nope + value);
    heads of value -> hidden. Over ``n_ev`` keys: each head's scores over
    (nope + rope) and its weighted sum of values."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, qr, kvr = config["v_head_dim"], config["q_lora_rank"], config["kv_lora_rank"]
    projections = (hidden * qr + qr * heads * (nope + rope)
                   + hidden * (kvr + rope) + kvr * heads * (nope + v)
                   + heads * v * hidden)
    return projections, n_ev * heads * (nope + rope + v)


def pangu_mla_attention(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through the expanded latent
    attention of every layer held (no latent is cached: the window is
    recomputed each step), two operations a multiply-add. Bytes: the five
    projection matrices of each layer once at 2 bytes (bfloat16 at rest),
    the float32 residual stream read and the sublayer's result written
    once a layer. What lies between (latents, heads, scores) is the
    operation's own and is not counted."""
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    layers = config["num_hidden_layers"]
    projections, over_keys = attention_macs(config, n_ev)
    return {"flops": 2 * positions * layers * (projections + over_keys),
            "bytes": layers * (2 * projections
                               + positions * config["hidden_size"] * (4 + 4))}
