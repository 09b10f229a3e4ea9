"""Operations and bytes of the ``falconh1`` head's state-space mixers for
one call of the fused step: the Mamba-2 branch of every layer held
(``head/ssm`` in the program)."""

from __future__ import annotations


def falconh1_ssm_mixer(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) goes through the two
    projections of each layer's mixer (``hidden_size`` x (2 ``mamba_d_ssm``
    + 2 groups x state + heads) in, ``mamba_d_ssm`` x ``hidden_size`` out)
    and the core's two products over the window's positions (``C B^T`` a
    group: ``SESSION_EVENTS`` x groups x state multiply-adds a position;
    the sum over positions: ``SESSION_EVENTS`` x ``mamba_d_ssm``); two
    operations a multiply-add. The multipliers, the taps, ``silu``,
    ``softplus``, the decay, the gate and the grouped norm are a few
    operations a channel beside 68.4 M a position and are left out.
    Bytes: both matrices once a layer at 2 bytes (bfloat16 at rest), the
    taps and their bias at 4; a position's normed input read once at 2
    bytes a channel and its float32 result written once; and the float32
    passes between the products: the convolution with ``silu`` over ``[x |
    B | C]`` (read and written), the core (``x``, ``B``, ``C`` and ``dt``
    read, ``y`` written), the gate and norm (``y`` and ``z`` read, the out
    product's operand written at 2 bytes).

    The program computes the RMSNorm that BOTH mixers read under
    ``head/ssm`` (one pass over the stream a layer), so ``ssm_mixer_ms``
    holds its time; the cost does not count it. The add of both branches
    to the stream is under ``head/attn``."""
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, width = config["hidden_size"], config["mamba_d_ssm"]
    bc = config["mamba_n_groups"] * config["mamba_d_state"]
    heads, layers = config["mamba_n_heads"], config["num_hidden_layers"]
    conv = width + 2 * bc
    projections = hidden * (2 * width + 2 * bc + heads) + width * hidden
    core = n_ev * (bc + width)
    weight_bytes = 2 * projections + 4 * conv * (config["mamba_d_conv"] + 1)
    passes = 4 * (2 * conv + (conv + heads) + width + 2 * width) + 2 * width
    return {"flops": 2 * positions * (projections + core) * layers,
            "bytes": layers * (weight_bytes
                               + positions * (hidden * (2 + 4) + passes))}
