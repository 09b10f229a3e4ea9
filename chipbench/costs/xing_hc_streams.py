"""Operations and bytes of the ``xing`` head's hyper-connections for one
call of the fused step: everything under ``head/hc`` in the program (the
maps, the read and the write of every sublayer held)."""

from __future__ import annotations


def sublayers(config: dict) -> int:
    """Hyper-connected sublayers held: attention and the feed-forward of
    every layer."""
    return 2 * config["num_hidden_layers"]


def xing_hc_streams(config: dict, batch: int, *, index_mode: bool) -> dict:
    """What the algorithm needs at the padded batch, a sublayer: the
    ``hc_mult`` float32 streams of every position (``batch`` x
    ``SESSION_EVENTS``) read once for the maps and the read together, read
    and written once for the write, the sublayer's float32 result read
    once, and ``phi`` once (float32 at rest): bound by bytes. What the
    sublayer reads (``u``) and the maps themselves lie between and are not
    counted. Operations, the few a channel: ``phi``'s product (``n C`` x
    ``2 n + n^2`` multiply-adds a position), ``n`` multiply-adds a channel
    for the read, ``n^2 + n`` for the write, and the Sinkhorn rounds (two
    divisions and two additions an entry a round); two operations a
    multiply-add."""
    positions = batch * int(config["env"].get("SESSION_EVENTS", 16))
    n, hidden = config["hc_mult"], config["hidden_size"]
    columns = 2 * n + n * n
    stream = positions * n * hidden * 4
    per_position = (2 * n * hidden * columns + 2 * hidden * (n + n * n + n)
                    + config["hc_sinkhorn_iters"] * 4 * n * n)
    return {"flops": sublayers(config) * positions * per_position,
            "bytes": sublayers(config) * (3 * stream + positions * hidden * 4
                                          + n * hidden * columns * 4)}
