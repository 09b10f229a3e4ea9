"""Operations and bytes of the ``phi4flash`` head's second half for one call
of the fused step: the Gated Memory Units and cross-attention layers past
layer ``L/2 + 1`` (``head/cross`` in the program)."""

from __future__ import annotations

from chipbench import validate

EXPAND = 2


def layer_macs(config: dict) -> tuple[int, int]:
    """Multiply-adds a position of ``(a Gated Memory Unit layer, a cross
    layer)`` with its MLP, the cross layer's core apart."""
    hidden = config["hidden_size"]
    mlp = 3 * hidden * config["intermediate_size"]
    return 2 * hidden * EXPAND * hidden + mlp, 2 * hidden * hidden + mlp


def phi4flash_cross_decoder(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the output needs: ``L/2 - 1`` layers, half of them Gated Memory
    Units and half cross attention, at ONE position a row of the padded
    batch; a cross layer's one query against its window's
    ``SESSION_EVENTS`` keys. Bytes: every weight of these layers once at 2
    bytes (bfloat16 at rest: 2.75 GB at the published widths, which bounds
    it) and nothing else: the shared ``K, V`` of the padded batch is 21 MB
    at the cell's shape, which a compiler may keep in the chip's 128 MiB of
    VMEM from the layer that makes it through the seven that read it (as
    first written the cost counted one read a cross layer, 0.15 GB, and the
    first traced run read 101.5% of it: my chip run, PR 59)."""
    core = validate.load_code("costs", "phi4flash_attention_core")
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    each = (config["num_hidden_layers"] // 2 - 1) // 2
    gmu, cross = layer_macs(config)
    macs = batch * each * (gmu + cross + n_ev * core.pair_macs(config))
    return {"flops": 2 * macs,
            "bytes": 2 * each * (gmu + cross)}
