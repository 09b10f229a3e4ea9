"""Operations and bytes a kernel needs for one call, from its shapes.

One file a kernel under ``chipbench/costs/``, each with one function
named like the file. It takes the deployment's configuration and the
padded batch of one execution and returns ``{"flops": ..., "bytes":
...}``: what the algorithm has to do, not what a compiler's cost
analysis says it did. A roofline metric's file names it under ``cost``
and ``validate.load_code`` finds it; a new kernel, or the fused step of a
new session head, is a new file here.
"""

from __future__ import annotations

N_FEATURES = 30
EVENT_WIDTH = 12
SEQ_D_MODEL, SEQ_D_FF = 32, 64


def fused_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """The fused scoring program for one padded batch.

    Bytes: in index mode the gathered table rows and ring windows are
    read, one event per row is written back into the ring, and the int32
    slot/occurrence/type columns, float32 amounts and events come in;
    in row mode the [B, 30] float32 matrix comes in instead. The packed
    [5, B] int32 result goes out either way, and the parameters are read
    once. Flops: two per multiply-add of the trunk and fraud head, plus
    the transformer session head where the deployment has one (the
    pattern head and the rules are elementwise and left out: they are far
    below the matmuls)."""
    dims = (N_FEATURES, *config["trunk"])
    mlp_macs = sum(a * b for a, b in zip(dims[:-1], dims[1:])) + dims[-1]
    flops = 2 * batch * mlp_macs
    param_bytes = 4 * (mlp_macs + sum(dims[1:]) + 1)
    out_bytes = 5 * 4 * batch
    if not index_mode:
        return {"flops": flops,
                "bytes": batch * (N_FEATURES * 4 + 1) + out_bytes + param_bytes}
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    state_bytes = batch * (N_FEATURES * 4 + 1            # table row + flag
                           + n_ev * EVENT_WIDTH * 4 + 8  # window, cursor, length
                           + EVENT_WIDTH * 4)            # the appended event
    wire_bytes = batch * (4 + 4 + 4 + 4 + 4 + EVENT_WIDTH * 4 + 1)
    if config["env"].get("SESSION_HEAD", "pattern") == "transformer":
        d, ff = SEQ_D_MODEL, SEQ_D_FF
        per_pos = EVENT_WIDTH * d + 3 * d * d + d * d + 2 * d * ff
        attn = 2 * n_ev * d  # scores and the weighted sum, per position
        flops += 2 * batch * (n_ev * (per_pos + attn) + d)
    return {"flops": flops,
            "bytes": state_bytes + wire_bytes + out_bytes + param_bytes}

