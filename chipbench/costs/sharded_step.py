"""Operations and bytes of the slot-sharded session step (``jit__body``
under ``shard_map`` over the ``data`` axis), on ONE chip of the mesh, for
one call."""

from __future__ import annotations

from chipbench import validate

N_FEATURES = 30
EVENT_WIDTH = 12


def sharded_step(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What one of ``MESH_DEVICES`` chips has to do for one padded batch.

    The table and the ring are sharded by slot, the batch is not: every
    chip scores every row (the compute after the gather is replicated),
    so operations are ``costs/fused_step.py``'s for the whole batch. So
    are the bytes a chip's memory has to move: each row's table row, flag,
    window, cursor and length are read once on the chip that owns the
    slot, and every other chip's copy of them arrives over the
    interconnect and is written before it is read: ``(K - 1) / K`` of the
    gathered state a second time. The owner-select's ``K`` zero-filled
    contributions a row are what the program does, not what it has to."""
    base = validate.load_code("costs", "fused_step").fused_step(
        config, batch, index_mode=index_mode)
    shards = int(config["env"].get("MESH_DEVICES", 1))
    if not index_mode or shards <= 1:
        return base
    n_ev = int(config["env"].get("SESSION_EVENTS", 16))
    gathered = batch * (N_FEATURES * 4 + 1 + n_ev * EVENT_WIDTH * 4 + 8)
    return {"flops": base["flops"],
            "bytes": base["bytes"] + gathered * (shards - 1) // shards}
