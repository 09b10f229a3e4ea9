"""Operations and bytes of the fused session step with the ``falconh1``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12


def falconh1_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py``
    counts them, plus the head at the padded batch: every position of
    every row (``batch`` x ``SESSION_EVENTS``) through both mixers and the
    MLP of every layer held.

    Multiply-adds a position: the projector; in each layer the q, k, v and
    o projections and, over the window's keys, the scores and the weighted
    sum of values; the state-space mixer's two projections and its core
    (``costs/falconh1_ssm_mixer.py``); the MLP's three products
    (``costs/falconh1_dense_mlp.py``). Two operations a multiply-add.
    Bytes: every matrix of the head once at 2 bytes (bfloat16 at rest),
    the taps and their bias at 4; norm gains, ``A_log``, ``D``,
    ``dt_bias`` and the scoring head are not counted, nor any pass over
    the activations (the step is bound by operations twenty times over).

    Where the parts the layer metrics read leave off: the RMSNorm that both
    mixers read is computed under ``head/ssm`` and the add of both
    branches to the stream under ``head/attn``; the projector and the
    rotary angles are ``head/embed``, the final norm and the scoring
    column ``head/score``, and neither of those two has a metric of its
    own (PERF.md section 5 gives their time from the trace)."""
    costs = lambda name: getattr(validate.load_code("costs", name), name)
    base = costs("fused_step")(config, batch, index_mode=index_mode)
    ssm = costs("falconh1_ssm_mixer")(config, batch, index_mode=index_mode)
    mlp = costs("falconh1_dense_mlp")(config, batch, index_mode=index_mode)
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    positions = batch * n_ev
    hidden, layers = config["hidden_size"], config["num_hidden_layers"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    attn = hidden * hd * (2 * heads + 2 * kv)
    over_keys = n_ev * 2 * heads * hd
    macs = positions * (EVENT_WIDTH * hidden + layers * (attn + over_keys))
    conv = config["mamba_d_ssm"] + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    ssm_weights = (2 * (hidden * (conv + config["mamba_d_ssm"] + config["mamba_n_heads"])
                        + config["mamba_d_ssm"] * hidden)
                   + 4 * conv * (config["mamba_d_conv"] + 1))
    mlp_weights = 2 * 3 * hidden * config["intermediate_size"]
    param_bytes = (2 * EVENT_WIDTH * hidden
                   + layers * (2 * attn + ssm_weights + mlp_weights))
    return {"flops": base["flops"] + 2 * macs + ssm["flops"] + mlp["flops"],
            "bytes": base["bytes"] + param_bytes}
