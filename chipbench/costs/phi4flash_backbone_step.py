"""Operations and bytes of the fused session step with the ``phi4flash``
backbone in it (``jit__body`` in the program), for one call."""

from __future__ import annotations

from chipbench import validate

EVENT_WIDTH = 12
STATE, EXPAND = 16, 2


def phi4flash_backbone_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The state, wire and trunk of the step as ``costs/fused_step.py`` counts
    them, plus the head as THE OUTPUT NEEDS it, so that a program that
    computes more reads lower and none reads over 100%: layers ``0 .. L/2``
    at every position of the padded batch (``batch`` x ``SESSION_EVENTS``);
    of layer ``L/2 + 1`` the ``K, V`` product at every position and the
    rest (``q``, ``W_o``, the MLP, a one-query core) at one position a row;
    the layers after it at one position a row.

    Multiply-adds a position: a dense MLP's three products in every layer; a
    Mamba layer's four projections; a band layer's ``W_qkv`` and ``W_o``.
    The scans (``costs/phi4flash_selective_scan.py``), the cores
    (``costs/phi4flash_attention_core.py``) and the second half
    (``costs/phi4flash_cross_decoder.py``) as their files count them. Bytes:
    every parameter of the head once at 2 bytes (bfloat16 at rest; norms,
    biases, taps, ``A_log``, ``D`` and the scoring head are not counted) and
    the projector."""
    load = lambda name: getattr(validate.load_code("costs", name), name)(
        config, batch, index_mode=index_mode)
    base, scan, core, second = (load("fused_step"),
                                load("phi4flash_selective_scan"),
                                load("phi4flash_attention_core"),
                                load("phi4flash_cross_decoder"))
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    hidden, layers = config["hidden_size"], config["num_hidden_layers"]
    kvw = config["num_key_value_heads"] * (hidden // config["num_attention_heads"])
    inner, rank = EXPAND * hidden, -(-hidden // 16)
    mlp = 3 * hidden * config["intermediate_size"]
    mamba = (hidden * 2 * inner + inner * (rank + 2 * STATE) + rank * inner
             + inner * hidden)
    attn = hidden * (hidden + 2 * kvw) + hidden * hidden
    mambas, bands = layers // 4 + 1, layers // 4
    first = mambas * (mamba + mlp) + bands * (attn + mlp)
    macs = (positions * (first + EVENT_WIDTH * hidden + hidden * 2 * kvw)
            + batch * (2 * hidden * hidden + mlp))
    params = first + attn + mlp + EVENT_WIDTH * hidden
    return {"flops": base["flops"] + 2 * macs + scan["flops"] + core["flops"]
            + second["flops"],
            "bytes": base["bytes"] + 2 * params + second["bytes"]}
