"""The dropless expert layer the backbones with experts call (``keye``,
``lfm2``: every expert held; ``pangu``, ``ling``: a chip's share; ``xing``:
every expert held and a window's padding left out), owned by no model.

``grouped_experts`` takes positions, the router's choice and weights (each
backbone's own router made them) and the stacked expert weights held here;
every (position, expert) pair whose expert is held is computed, whatever
the routing's skew. ``cfg`` is any configuration with ``experts`` (the
router's width) and an ``operand_dtype``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from igaming_platform_tpu.models.decoder_parts import Params, announce_core, kernel_declines


def _expert_products(xs, sizes, layer: Params, cfg,
                     whole_rows: bool = False, rows=None):
    """Rows ``xs`` [M, hidden] sorted by expert, ``sizes`` [E] -> float32
    [M, hidden]: ``(silu(xs @ wg[e]) * (xs @ wu[e])) @ wd[e]`` for each
    row's expert ``e``; with ``rows`` [M], ``xs`` is the positions [P,
    hidden] still unsorted and sorted row ``i`` is ``xs[rows[i]]``. On a
    TPU, at shapes the kernels support, two Pallas grouped kernels
    (ops/pallas/grouped_experts.py: gate and up share one read of the
    rows, silu and the product in the epilogue; with ``whole_rows`` the
    second writes [M, pitch, 128], each row one piece of memory that starts
    on a sublane tile, for ``combine`` to copy row by row). How they are fed
    is read from the shapes and announced with the core: the weights through a ring of VMEM
    slots, the rows brought together inside ``gate_up`` out of the
    positions it holds in VMEM (``takes_rows``), or gathered here into a
    sorted copy. Elsewhere that gather and three ``lax.ragged_dot``
    products, which are also the kernels' golden reference."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    m, hidden = xs.shape[0] if rows is None else rows.shape[0], xs.shape[1]
    e, _, width = layer["wg"].shape
    why, backend = kernel_declines(lambda: not kernels.supports(
        jax.ShapeDtypeStruct((m, hidden), xs.dtype), layer["wg"]))
    if not why:
        if rows is not None and not kernels.takes_rows(xs, rows, layer["wg"]):
            xs, rows = xs[rows], None
        fed = kernels.feed(m, hidden, e, width,
                           None if rows is None else xs.shape[0])
        announce_core(f"pallas-grouped ({fed})", backend)
        mid = kernels.gate_up(xs, layer["wg"], layer["wu"], sizes, rows=rows)
        return kernels.down(mid, layer["wd"], sizes, whole_rows=whole_rows)
    announce_core("xla-ragged-dot", backend)
    dt = cfg.operand_dtype
    if rows is not None:
        xs = xs[rows]

    def grouped(lhs, w):
        return jax.lax.ragged_dot(lhs, w.astype(dt), sizes,
                                  preferred_element_type=jnp.float32)

    mid = jax.nn.silu(grouped(xs, layer["wg"])) * grouped(xs, layer["wu"])
    return grouped(mid.astype(dt), layer["wd"])


def _combine_by_kernel(results, rows, take=None) -> bool:
    """Whether the results' way back to position order runs as the Pallas
    ``combine`` (ops/pallas/grouped_experts.py) or as the XLA expressions
    that stand beside each call, which are its reference and what runs off
    the TPU. ``results`` [M, hidden] float32 and ``rows`` [P, k], arrays or
    shapes; ``take`` is given where only some slots are owed. Picked while
    tracing, from backend and shapes, and announced once a compile."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    why, backend = kernel_declines(
        lambda: not kernels.combine_supports(results, rows, take))
    announce_core("xla-gather" if why else "pallas-rows", backend, "combine")
    return not why


def expert_sizes(keys, held: int):
    """How many of ``keys`` [M] name each of the ``held`` experts: int32
    [held], ``jnp.bincount``'s integers without its scatter of M ones (1.15
    ms a step in the keye cell: PERF.md, PR 35), as a comparison of every
    key with every bin, summed over the keys. A key past the last held
    expert (an absent or a padded pair's) is counted by no bin."""
    bins = jnp.arange(held, dtype=keys.dtype)
    return jnp.sum((keys[:, None] == bins).astype(jnp.int32), axis=0)


# Rows one pass of a share's pairs is rounded up to: the expert kernels'
# row tile (ops/pallas/grouped_experts._tiles).
_PASS_TILE = 256


def pass_rows(pairs: int, held: int, experts: int, hidden: int = 0) -> int:
    """The static bound on the rows one pass over a share's pairs gathers
    and multiplies: four times the share's expected pairs at uniform
    routing, rounded up to the kernels' tile; never more than all the
    pairs (which it is where every expert is held); and, given the rows'
    ``hidden`` size, no more tiles than leave a pass's float32 results
    inside what ``combine`` keeps of them in VMEM for a whole call
    (ops/pallas/grouped_experts.HELD_RESULTS_BYTES; one tile at least)."""
    from igaming_platform_tpu.ops.pallas.grouped_experts import HELD_RESULTS_BYTES

    share = -(-4 * pairs * held // experts)
    rows = _PASS_TILE * -(-share // _PASS_TILE)
    if hidden:
        fit = HELD_RESULTS_BYTES // (4 * hidden * _PASS_TILE)
        rows = min(rows, _PASS_TILE * max(fit, 1))
    return min(pairs, rows)


def grouped_experts(x, top_e, top_w, layer: Params, cfg, first_expert: int = 0,
                    live=None):
    """Dropless expert layer over positions ``x`` [P, hidden] for the
    experts HELD HERE: the stacked weights of ``layer`` are experts
    ``first_expert ..`` of ``cfg.experts`` (all of them, or a chip's
    share; how many is the weights' leading size). The router chose over
    all experts and normalised its weights over all it chose; every
    (position, expert) pair whose expert is held is computed, whatever
    the routing, and a pair whose expert lies elsewhere is never gathered
    or multiplied: what it would add is left out, and nothing stands in
    for the chip that holds it. With ``live`` [P] bool the pairs of
    positions that are not live, a window's padding, are left out the same
    way: by the share's passes, also where every expert is held (the
    ``xing`` head: its share is all of them, and the pairs left out are
    the padding's alone).

    The pairs are sorted by local expert (absent ones take a key past the
    last and sort behind), so each held expert's rows are contiguous and
    the three products run grouped over the stacked weights
    (``_expert_products``).

    - Every expert held and every position routed: one pass over all
      pairs; the results return to
      position order by the inverse permutation and are summed over a
      position's experts in float32.
    - A share, or a ``live`` mask: the held pairs are worked ``pass_rows`` at a time by a loop
      whose trip count is ``ceil(held pairs / pass_rows)`` (one pass at a
      routing anywhere near uniform, more under skew, none where no pair
      is held). Each pass gathers its rows, multiplies them, and every
      position takes its own pairs' results back out of the pass, times
      the router's weight, in float32: nothing is scattered (XLA's
      scatter-add of the same rows took three times as long on a v5e:
      PERF.md, PR 36). Temporaries are bounded by ``pass_rows``, not by
      all pairs.

    The way back is ``_combine_by_kernel``'s choice, made while tracing:
    the Pallas ``combine`` (each owed row read once; a slot that is not
    taken reads nothing), or the XLA expressions written out below it (a
    row gather and a sum; for a share a row gather a slot under a
    ``where``), which are its reference and what runs off the TPU."""
    from igaming_platform_tpu.ops.pallas import grouped_experts as kernels

    n, k = top_e.shape
    held = layer["wg"].shape[0]
    everything = held == cfg.experts and live is None
    if everything:
        flat_e = top_e.reshape(-1)
    else:
        local = top_e - first_expert
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        flat_e = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sizes = expert_sizes(flat_e, held)
    xb = x.astype(cfg.operand_dtype)
    hidden = x.shape[-1]
    rank = jnp.argsort(order).reshape(n, k)  # the sorted row of every pair
    if everything:
        results = jax.ShapeDtypeStruct((n * k, hidden), jnp.float32)
        if _combine_by_kernel(results, rank):
            ys = _expert_products(xb, sizes, layer, cfg, whole_rows=True,
                                  rows=order // k)
            return kernels.combine(ys, rank, top_w, hidden=hidden)
        ys = _expert_products(xb, sizes, layer, cfg, rows=order // k)
        y = ys[rank.reshape(-1)].reshape(n, k, -1)
        return jnp.sum(y * top_w[..., None], axis=1)

    rows = pass_rows(n * k, held, cfg.experts, hidden)
    order = jnp.pad(order, (0, -(n * k) % rows))
    ends = jnp.cumsum(sizes)
    starts, n_held = ends - sizes, ends[-1]

    def one_pass(i, y):
        lo = i * rows
        pair = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        # this pass's part of every expert's rows; rows past the held pairs
        # (the last pass's tail) belong to no expert and are read by nobody
        part = jnp.maximum(jnp.minimum(ends, lo + rows) - jnp.maximum(starts, lo), 0)
        ys = _expert_products(xb, part, layer, cfg, rows=pair // k)
        mine = (rank >= lo) & (rank < jnp.minimum(lo + rows, n_held))
        at = jnp.clip(rank - lo, 0, rows - 1)
        if _combine_by_kernel(ys, at, mine):
            return kernels.combine(ys, at, top_w, mine, onto=y)
        for j in range(k):
            y = y + (jnp.where(mine[:, j, None], ys[at[:, j]], 0.0)
                     * top_w[:, j, None])
        return y

    return jax.lax.fori_loop(0, (n_held + rows - 1) // rows, one_pass,
                             jnp.zeros((n, hidden), jnp.float32))
