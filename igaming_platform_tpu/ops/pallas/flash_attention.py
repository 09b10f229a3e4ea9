"""Pallas TPU kernel: blockwise (flash) attention for the sequence model.

The XLA dense path (`models/sequence._dense_attention`) materialises the
[S, S] score matrix in HBM per head — at S=2048 that is 4 M floats per
(batch, head) touched twice, pure HBM bandwidth. This module computes the
same attention as a running online softmax (max / normaliser / weighted
sum) that never leaves VMEM, in two variants picked by sequence length:

- **resident** (S <= _RESIDENT_MAX_S): grid (batch·head, q block), each
  (batch·head)'s whole [S, Dh] K/V sits in VMEM across its query blocks
  and an in-kernel loop streams it through the MXU. Fewest grid steps —
  fastest — but Dh lane-pads to 128, so the KV footprint grows with S
  and past ~4k the double-buffered copies blow the 16 MB scoped-VMEM
  budget (observed compile-time OOM at S=8192).
- **tiled** (longer S): grid (batch·head, q block, kv block) with the
  accumulator in VMEM scratch carried across the sequential kv sweep.
  Resident memory is O(block·Dh), independent of S — S=8192/32k compile
  and run; ~more grid-step overhead, which is why it isn't the default
  for short sequences.

This is the intra-chip core; across chips the ring/Ulysses strategies of
models/sequence.py shard S over the `seq` mesh axis and this kernel runs
on each chip's local shard. Matches the dense path bit-for-bit up to
float32 associativity (pinned in tests/test_flash_attention.py).

Reference behavior being accelerated: the bonus-abuse sequence detector
(BASELINE.json config 3; engine.go:462-466 is the scalar-rule version).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256

# The resident-KV variant holds each (batch·head)'s whole [S, Dh] K and V
# in VMEM across its query blocks — far fewer grid steps, so it wins while
# it fits. Dh lane-pads to 128, so K+V double-buffered cost is
# S·128·4·4 bytes; 4096 keeps that at 8 MB, half the scoped-VMEM budget.
# Beyond it the KV-tiled variant (O(block) memory, S-independent) takes over.
_RESIDENT_MAX_S = 4096


def _kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, scale: float):
    q = q_ref[0]  # [bq, dh]
    s_total = k_ref.shape[1]
    bq, dh = q.shape

    m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, dh), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]  # [bk, dh]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # MXU
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, s_total // block_k, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # Row logsumexp: what the backward needs to recompute exact softmax
    # probabilities blockwise without the [S, S] matrix.
    lse_ref[0] = m + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def _run_resident(q, k, v, *, block_q, block_k, interpret):
    bh, s, dh = q.shape
    kernel = functools.partial(
        _kernel_resident, block_k=block_k, scale=1.0 / math.sqrt(dh)
    )
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),  # row LSE
        ],
        grid=(bh, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, dh), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        interpret=interpret,
    )(q, k, v)


def _kernel_tiled(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, nk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0]  # [bq, dh]
    k = k_ref[0]  # [bk, dh]
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # MXU

    m = m_scr[...]   # [bq, 1]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_scr[...])


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def _run_tiled(q, k, v, *, block_q, block_k, interpret):
    bh, s, dh = q.shape
    nk = s // block_k
    kernel = functools.partial(
        _kernel_tiled, scale=1.0 / math.sqrt(dh), nk=nk
    )
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),  # row LSE
        ],
        # KV tiles iterate in the LAST grid dim so the output block and
        # scratch stay resident across the sequential sweep.
        grid=(bh, s // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, jq, jk: (i, jq, 0)),
            pl.BlockSpec((1, block_k, dh), lambda i, jq, jk: (i, jk, 0)),
            pl.BlockSpec((1, block_k, dh), lambda i, jq, jk: (i, jk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, jq, jk: (i, jq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, jq, jk: (i, jq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running normaliser
            pltpu.VMEM((block_q, dh), jnp.float32),  # weighted-sum acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# -- backward (FlashAttention-2 style) --------------------------------------
#
# The forward saves O and the row logsumexp L; the backward recomputes the
# softmax probabilities blockwise (P = exp(S - L), exact — no online max
# needed since L is final) and accumulates:
#     D  = rowsum(dO * O)
#     dV = P^T dO
#     dS = P * (dO V^T - D) * scale
#     dQ = dS K          (one kernel, grid over q blocks, KV resident)
#     dK = dS^T Q        (one kernel, grid over kv blocks, Q/dO resident)
# Both backward kernels are resident-style (the non-blocked side lives in
# VMEM across the in-kernel loop). The dKV kernel keeps FOUR full-length
# arrays resident (Q, dO, LSE, dmat — the [S,1] blocks lane-pad to 128),
# twice the forward's K+V footprint, so the backward's resident budget is
# HALF the forward's. Longer sequences fall back to an XLA recompute
# backward (O(S^2) HBM for the score block, still exact).
_BWD_RESIDENT_MAX_S = _RESIDENT_MAX_S // 2


def _kernel_bwd_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, dmat_ref, dq_ref, *,
                   block_k: int, scale: float):
    q = q_ref[0]          # [bq, dh]
    do = do_ref[0]        # [bq, dh]
    lse = lse_ref[0]      # [bq, 1]
    dmat = dmat_ref[0]    # [bq, 1]
    s_total = k_ref.shape[1]
    bq, dh = q.shape

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]  # [bk, dh]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(sc - lse)                                   # exact probs
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        dsc = p * (dp - dmat) * scale
        return dq + jnp.dot(dsc, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, s_total // block_k, body, jnp.zeros((bq, dh), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _kernel_bwd_dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, dmat_ref,
                    dk_ref, dv_ref, *, block_q: int, scale: float):
    k = k_ref[0]          # [bk, dh]
    v = v_ref[0]
    s_total = q_ref.shape[1]
    bk, dh = k.shape

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]    # [bq, dh]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        dmat = dmat_ref[0, pl.ds(i * block_q, block_q), :]
        sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(sc - lse)                                   # [bq, bk]
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        dsc = p * (dp - dmat) * scale
        dk = dk + jnp.dot(dsc.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    zero = jnp.zeros((bk, dh), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, s_total // block_q, body, (zero, zero))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def _run_bwd(q, k, v, o, lse, g, *, block_q, block_k, interpret):
    bh, s, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    dmat = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)  # [bh, s, 1]

    row_q = pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0))
    row_q1 = pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))
    full = pl.BlockSpec((1, s, dh), lambda i, j: (i, 0, 0))
    full1 = pl.BlockSpec((1, s, 1), lambda i, j: (i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_kernel_bwd_dq, block_k=block_k, scale=scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, s // block_q),
        in_specs=[row_q, full, full, row_q, row_q1, row_q1],
        out_specs=row_q,
        interpret=interpret,
    )(q, k, v, g, lse, dmat)

    row_k = pl.BlockSpec((1, block_k, dh), lambda i, j: (i, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_kernel_bwd_dkv, block_q=block_q, scale=scale),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        grid=(bh, s // block_k),
        in_specs=[full, row_k, row_k, full, full1, full1],
        out_specs=[row_k, row_k],
        interpret=interpret,
    )(q, k, v, g, lse, dmat)
    return dq, dk, dv


def _xla_bwd(q, k, v, o, lse, g, scale):
    """Exact recompute backward via XLA for S past the resident budget —
    O(S^2) HBM for the score block (documented tradeoff; the tiled
    backward kernel is the future upgrade path). ``o`` comes from the
    saved residuals: dmat = rowsum(g*O) needs no recompute of O."""
    f32 = jnp.float32
    sc = jnp.einsum("bqd,bkd->bqk", q.astype(f32), k.astype(f32)) * scale
    p = jnp.exp(sc - lse)                       # [bh, s, s], exact probs
    g32 = g.astype(f32)
    dv = jnp.einsum("bqk,bqd->bkd", p, g32)
    dp = jnp.einsum("bqd,bkd->bqk", g32, v.astype(f32))
    dmat = jnp.sum(g32 * o.astype(f32), axis=-1, keepdims=True)
    dsc = p * (dp - dmat) * scale
    dq = jnp.einsum("bqk,bkd->bqd", dsc, k.astype(f32))
    dk = jnp.einsum("bqk,bqd->bkd", dsc, q.astype(f32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.lru_cache(maxsize=16)
def _flash_with_vjp(block_q: int, block_k: int, interpret: bool):
    """The differentiable flash op for one static config: forward = the
    Pallas kernels (saving LSE), backward = the blockwise flash backward
    (resident S) or the XLA recompute (longer S). Cached per config so
    jit sees one stable callable."""

    def run_fwd(q, k, v):
        run = _run_resident if q.shape[1] <= _RESIDENT_MAX_S else _run_tiled
        return run(q, k, v, block_q=_eff_block(q.shape[1], block_q),
                   block_k=_eff_block(q.shape[1], block_k), interpret=interpret)

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = run_fwd(q, k, v)
        return out

    def fwd(q, k, v):
        out, lse = run_fwd(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        s = q.shape[1]
        if s <= _BWD_RESIDENT_MAX_S:
            return _run_bwd(q, k, v, o, lse, g,
                            block_q=_eff_block(s, block_q),
                            block_k=_eff_block(s, block_k),
                            interpret=interpret)
        return _xla_bwd(q, k, v, o, lse, g, 1.0 / math.sqrt(q.shape[-1]))

    f.defvjp(fwd, bwd)
    return f


def supports(q_shape: tuple, block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K) -> bool:
    """Whether the kernel handles this shape without masking (S divisible
    by both effective block sizes). Padding keys would perturb the
    softmax, so non-divisible shapes take the dense path instead."""
    s = q_shape[-2]
    return s % _eff_block(s, block_q) == 0 and s % _eff_block(s, block_k) == 0


def _eff_block(s: int, block: int) -> int:
    return min(block, s)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """[B, H, S, Dh] q,k,v -> [B, H, S, Dh] full (non-causal) attention.

    S must be divisible by the (effective) block sizes — the serving path
    pads event histories to a fixed max_len, so this holds on the hot
    path; `supports()` lets callers fall back to the dense core otherwise.
    ``interpret=True`` runs the Pallas interpreter — the only way to
    execute the kernel off-TPU, and always the caller's explicit choice.
    """
    b, h, s, dh = q.shape
    bq, bk = _eff_block(s, block_q), _eff_block(s, block_k)
    if s % bq != 0 or s % bk != 0:
        raise ValueError(f"seq len {s} not divisible by blocks ({bq}, {bk})")
    f = _flash_with_vjp(block_q, block_k, interpret)
    out = f(q.reshape(b * h, s, dh), k.reshape(b * h, s, dh),
            v.reshape(b * h, s, dh))
    return out.reshape(b, h, s, dh)
