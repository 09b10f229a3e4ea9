"""Operations and bytes of the ``ling`` head's Kimi Delta Attention mixers
for one call of the fused step: the KDA layers held (``head/kda`` in the
program)."""

from __future__ import annotations


def kda_layers(config: dict) -> int:
    """How many of the held layers are KDA: by the source's rule over
    ``head.layers_held`` (latent attention where ``(l + 1) %
    layer_group_size == 0``)."""
    held = config.get("head", {}).get("layers_held",
                                      range(config["num_hidden_layers"]))
    return sum((l + 1) % config["layer_group_size"] != 0 for l in held)


def weight_bytes(config: dict) -> int:
    """One KDA mixer's matrices at rest: the six projections and ``Wb`` at
    2 bytes, the three sets of taps at 4."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    width = heads * config["head_dim"]
    return (2 * (6 * hidden * width + hidden * heads)
            + 4 * 3 * width * config["short_conv_kernel_size"])


def ling_kda_mixer(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) goes through the six
    projections of each KDA layer (``Wq Wk Wv Wf Wg`` in, ``hidden_size`` x
    heads x ``head_dim`` each, and ``Wo`` out), the small ``Wb``
    (``hidden_size`` x heads), and the one-chunk core's products over the
    window's ``T`` positions: a head's ``k k^T`` and ``q k^T`` (``T x
    head_dim`` multiply-adds a position each), the unit-lower-triangular
    solve (``T^2 / 2`` a position and head by substitution), its product
    with the reads (``T^2 / 2``) and the sum over the writes (``T x
    head_dim``); two operations a multiply-add. The taps, ``silu``, the L2
    norm, the decay, the gated norm are a few operations a channel beside
    63 M a position and are left out. Bytes: the six matrices and ``Wb``
    once a layer at 2 bytes (bfloat16 at rest), the taps at 4; a position's
    normed input read once at 2 bytes a channel and its float32 result
    written once; and the float32 passes between the products over the
    mixer's width ``W`` = heads x ``head_dim``: the convolution with
    ``silu`` and the L2 norm over ``q, k, v`` (read and written: 6 W), the
    decay (``f`` read, ``g`` written: 2 W), the core (``q, k, v, g`` read,
    ``o`` written: 5 W), the gated norm (``o`` and the gate read: 2 W, the
    out product's operand written at 2 bytes).

    The program computes the RMSNorm the mixer reads and the add of its
    result to the stream under ``head/kda``, so ``kda_mixer_ms`` holds
    their time; the cost does not count them."""
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, heads, hd = (config["hidden_size"], config["num_attention_heads"],
                         config["head_dim"])
    width, layers = heads * hd, kda_layers(config)
    projections = 6 * hidden * width + hidden * heads
    core = heads * (3 * n_ev * hd + n_ev * n_ev)
    passes = 4 * 15 * width + 2 * width
    return {"flops": 2 * positions * (projections + core) * layers,
            "bytes": layers * (weight_bytes(config)
                               + positions * (hidden * (2 + 4) + passes))}
