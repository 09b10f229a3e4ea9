"""Pallas TPU kernel: fused oblivious-forest inference per batch tile.

One kernel invocation per [TILE_B, F] batch tile does the whole forest in
VMEM with no intermediate HBM round-trips. The XLA formulation
(`ops/gbdt_matmul.py`) materialises [B, T*D] and [B, T, 2^D]
intermediates in HBM between fusions; here they never leave VMEM.

Every intermediate is a 2-D, lane-dense array — the compiler refuses the
[TB, T, D] reshape and the float iota of the textbook form — so the
per-tree structure is carried by constant 0/1 matrices instead:

    gathered = x @ sel                  [TB, T*D]   feature select (MXU)
    bits     = gathered > thr           [TB, T*D]
    leaf     = bits @ pow               [TB, T]     sum_d bit_d << d
    leaf_rep = leaf @ expand            [TB, T*L]   each tree's leaf id, L times
    onehot   = leaf_rep == leaf_ids     [TB, T*L]
    out      = leaves . onehot^T        [1, TB]     lane-dense result row

``pow``/``expand``/``leaf_ids`` hold small integers, exact in any matmul
precision; the selector and leaf contractions carry arbitrary float32
values and run at HIGHEST precision so a feature never rounds across its
threshold. Grid over the batch; the forest tensors use constant index
maps and stay resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from igaming_platform_tpu.ops.gbdt_matmul import precompute_selector

DEFAULT_TILE_B = 256

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(x_ref, sel_ref, thr_ref, pow_ref, expand_ref, leaf_ids_ref,
            leaves_ref, out_ref):
    f32 = jnp.float32
    gathered = jnp.dot(x_ref[...], sel_ref[...], precision=_HIGHEST,
                       preferred_element_type=f32)              # [TB, T*D]
    bits = (gathered > thr_ref[...]).astype(f32)
    leaf = jnp.dot(bits, pow_ref[...], precision=_HIGHEST,
                   preferred_element_type=f32)                  # [TB, T]
    leaf_rep = jnp.dot(leaf, expand_ref[...], precision=_HIGHEST,
                       preferred_element_type=f32)              # [TB, T*L]
    onehot = (leaf_rep == leaf_ids_ref[...]).astype(f32)
    out_ref[...] = jax.lax.dot_general(
        leaves_ref[...], onehot, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=f32)         # [1, TB]


def forest_constants(n_trees: int, depth: int, n_leaves: int):
    """The structure matrices of the 2-D formulation (host, once per
    forest shape): pow [T*D, T], expand [T, T*L], leaf_ids [1, T*L]."""
    eye = np.eye(n_trees, dtype=np.float32)
    pow_mat = np.kron(eye, 2.0 ** np.arange(depth, dtype=np.float32)[:, None])
    expand = np.kron(eye, np.ones((1, n_leaves), np.float32))
    leaf_ids = np.tile(np.arange(n_leaves, dtype=np.float32), n_trees)[None]
    return pow_mat, expand, leaf_ids


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def _run(x, sel, thr, leaves, bias, *, tile_b, interpret):
    b, f = x.shape
    n_trees, depth = thr.shape
    n_leaves = leaves.shape[1]
    td, tl = n_trees * depth, n_trees * n_leaves
    pow_mat, expand, leaf_ids = forest_constants(n_trees, depth, n_leaves)

    def resident(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.float32),
        grid=(b // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, f), lambda i: (i, 0)),
            resident((f, td)),
            resident((1, td)),
            resident((td, n_trees)),
            resident((n_trees, tl)),
            resident((1, tl)),
            resident((1, tl)),
        ],
        out_specs=pl.BlockSpec((1, tile_b), lambda i: (0, i)),
        interpret=interpret,
    )(x, sel, thr.reshape(1, td), pow_mat, expand, leaf_ids,
      leaves.reshape(1, tl))
    return out[0] + bias


def gbdt_raw_pallas(
    params: dict,
    x: jnp.ndarray,
    *,
    sel: jnp.ndarray | None = None,
    tile_b: int = DEFAULT_TILE_B,
    interpret: bool = False,
) -> jnp.ndarray:
    """[B, F] -> [B] raw margins via the fused Pallas kernel.

    B must be a multiple of ``tile_b`` and ``tile_b`` a multiple of 128
    (the result row is lane-dense); a batch smaller than one tile runs
    as a single block. ``interpret=True`` runs the Pallas interpreter —
    the only way to execute the kernel off-TPU, chosen by the caller.
    """
    x = jnp.asarray(x, jnp.float32)
    b, f = x.shape
    if b < tile_b:
        tile_b = b
    elif b % tile_b != 0 or tile_b % 128 != 0:
        raise ValueError(
            f"batch {b} must be a multiple of tile {tile_b}, itself a "
            "multiple of 128")
    if sel is None:
        sel = jnp.asarray(precompute_selector(np.asarray(params["feat"]), f))
    return _run(
        x, sel,
        jnp.asarray(params["thr"], jnp.float32),
        jnp.asarray(params["leaves"], jnp.float32),
        jnp.asarray(params["bias"], jnp.float32),
        tile_b=tile_b, interpret=interpret)
