"""MXU formulation of oblivious-forest inference: gather as matmul.

`models/gbdt.gbdt_raw` gathers feature columns per (tree, depth) slot. On
TPU, cross-lane gathers serialize on the VPU, while the MXU is idle; this
formulation turns the gather into a dense one-hot matmul (the Hummingbird
GEMM strategy — "A Tensor Compiler for Unified ML Prediction Serving",
PAPERS.md):

    gathered[b, t*D+d] = x[b, :] @ onehot(feat[t, d])     (one [B,F]x[F,TD]
                                                           matmul on the MXU)
    bits   = gathered > thresholds
    leaf   = bits . powers-of-2 per tree
    out[b] = sum_t leaves[t, leaf[b, t]]                  (one-hot dot)

Same math as the gather form (pinned by tests), better hardware mapping at
serving batch sizes. `precompute_selector` runs once per model swap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def precompute_selector(feat: np.ndarray, in_dim: int) -> np.ndarray:
    """[T, D] int feature ids -> [F, T*D] float32 one-hot selector."""
    feat = np.asarray(feat)
    n_trees, depth = feat.shape
    sel = np.zeros((in_dim, n_trees * depth), dtype=np.float32)
    flat = feat.reshape(-1)
    sel[flat, np.arange(flat.size)] = 1.0
    return sel


def gbdt_raw_matmul(params: dict, sel: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """[B, F] -> [B] raw margin via the matmul formulation.

    ``sel`` is precompute_selector(params["feat"], F); thresholds/leaves
    come from the same pytree as the gather form.
    """
    x = jnp.asarray(x, jnp.float32)
    thr = params["thr"]  # [T, D]
    leaves = params["leaves"]  # [T, 2^D]
    n_trees, depth = thr.shape

    # HIGHEST precision, not the backend default: on a TPU the default
    # f32 matmul multiplies in bf16, which rounds the selected feature
    # values and flips threshold compares near the boundary (measured on
    # a v5e: max margin error 0.65 at default precision, 5e-7 at
    # HIGHEST). The leaf contraction carries arbitrary f32 leaf weights
    # and needs it for the same reason.
    hi = jax.lax.Precision.HIGHEST
    gathered = jax.lax.dot_general(
        x, sel, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32,
    ).reshape(x.shape[0], n_trees, depth)

    bits = (gathered > thr[None]).astype(jnp.int32)
    pows = jnp.asarray(1 << np.arange(depth), jnp.int32)
    leaf_idx = jnp.sum(bits * pows, axis=-1)  # [B, T]

    # one-hot leaf select -> dot with the leaf table
    onehot = (leaf_idx[:, :, None] == jnp.arange(leaves.shape[1])[None, None]).astype(jnp.float32)
    vals = jnp.einsum("btl,tl->b", onehot, leaves, precision=hi)
    return vals + params["bias"]
