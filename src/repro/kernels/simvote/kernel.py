"""Pallas TPU kernels: streaming similarity-weighted voting (Algorithm 3).

TPU adaptation: the paper's torch implementation materializes the full
(N x M) similarity matrix.  Here each (BN x BM) tile lives only in VMEM;
running numerator/denominator accumulate across the M grid dimension
(flash-attention-style online reduction), so HBM traffic is O(N*D + M*D),
not O(N*M).  Numerics: exp(-d2/2tau^2) is bounded in (0,1], so no max
rebasing is needed — a plain two-accumulator sum is exact in fp32.

Two entry points:
- ``simvote_scores_pallas``: one cluster (the original kernel).
- ``simvote_scores_segmented_pallas``: all clusters of a re-clustering round
  in ONE kernel launch.  Rows are packed per cluster into block_n-aligned
  segments; a scalar-prefetched ``block_seg`` table maps each row block to
  its cluster, and the BlockSpec index maps use it to DMA that cluster's
  sample and label tiles — the grouped-matmul pattern.  The per-cluster
  bandwidths are scalar-prefetched too and read from SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _simvote_accumulate(x_ref, s_ref, y_ref, inv2t2, num_ref, den_ref):
    """Add one (BN x BM) tile's weights into the row accumulators."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    x = x_ref[...].astype(jnp.float32)  # (BN, D)
    s = s_ref[...].astype(jnp.float32)  # (BM, D)
    y = y_ref[...].astype(jnp.float32)  # (1, BM); 0/1 labels, -1 = pad
    xsq = jnp.sum(x * x, axis=-1, keepdims=True)
    ssq = jnp.sum(s * s, axis=-1)[None, :]
    # f32 throughout: HIGHEST keeps the MXU from rounding the distance
    # dot's inputs to bf16, and both sums run on the VPU
    d2 = jnp.maximum(xsq - 2.0 * lax.dot_general(
        x, s, (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + ssq, 0.0)  # (BN, BM)
    w = jnp.exp(-d2 * inv2t2)
    valid = (y >= 0.0)
    w = jnp.where(valid, w, 0.0)
    num_ref[...] += jnp.sum(w * jnp.where(valid, y, 0.0), axis=-1,
                            keepdims=True)  # (BN, 1)
    den_ref[...] += jnp.sum(w, axis=-1, keepdims=True)


def _simvote_kernel(x_ref, s_ref, y_ref, inv2t2_ref, num_ref, den_ref):
    _simvote_accumulate(x_ref, s_ref, y_ref, inv2t2_ref[0, 0], num_ref,
                        den_ref)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def simvote_scores_pallas(x, s, y, tau, block_n: int = 256,
                          block_m: int = 256, interpret: bool = False):
    """x (N,D), s (M,D), y (M,) -> scores (N,)."""
    n, d = x.shape
    m = s.shape[0]
    n_pad = (n + block_n - 1) // block_n * block_n
    m_pad = (m + block_m - 1) // block_m * block_m
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    if m_pad != m:
        s = jnp.pad(s, ((0, m_pad - m), (0, 0)))
        y = jnp.pad(y.astype(jnp.float32), (0, m_pad - m),
                    constant_values=-1.0)  # -1 marks padding
    y2 = y.astype(jnp.float32).reshape(1, m_pad)
    inv2t2 = jnp.array([[1.0 / (2.0 * tau * tau)]], jnp.float32)

    num, den = pl.pallas_call(
        _simvote_kernel,
        grid=(n_pad // block_n, m_pad // block_m),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, s, y2, inv2t2)
    return (num[:n, 0] / jnp.maximum(den[:n, 0], 1e-30))


def _simvote_segmented_kernel(seg_ref, inv2t2_ref, x_ref, s_ref, y_ref,
                              num_ref, den_ref):
    # seg_ref (block -> cluster) and inv2t2_ref (per-cluster bandwidth) are
    # scalar-prefetched into SMEM; the index maps already routed the
    # cluster's sample tiles here, so the body is the single-cluster
    # accumulator with that cluster's bandwidth.
    inv2t2 = inv2t2_ref[seg_ref[pl.program_id(0)]]
    _simvote_accumulate(x_ref, s_ref, y_ref, inv2t2, num_ref, den_ref)


def simvote_scores_segmented_pallas(x, counts, s_pad, y_pad, taus,
                                    block_n: int = 256, block_m: int = 256,
                                    interpret: bool = False):
    """All clusters of a round in one launch.

    x       (N, D)    unsampled rows grouped by cluster (counts[c] rows each)
    counts  (C,)      host ints (concrete — drives the packing layout)
    s_pad   (C, M, D) per-cluster samples, zero-padded along M
    y_pad   (C, M)    labels in {0, 1}; -1 marks M-padding
    taus    (C,)      per-cluster bandwidth
    -> scores (N,) in the same row order as x.

    Each grid row block belongs to exactly one cluster (rows are re-packed
    with per-cluster padding), so a single BlockSpec tile per input suffices;
    the scalar-prefetched ``block_seg`` selects the cluster's sample tiles.
    """
    counts = np.asarray(counts, np.int64)
    c, m, d = s_pad.shape
    n = x.shape[0]
    assert int(counts.sum()) == n, (counts.sum(), n)

    nblocks = np.maximum(1, -(-counts // block_n))  # >=1 block even if empty
    nb_total = int(nblocks.sum())
    starts = np.zeros(c + 1, np.int64)
    np.cumsum(nblocks, out=starts[1:])
    block_seg = np.repeat(np.arange(c, dtype=np.int32), nblocks)

    # pack rows: cluster c occupies rows [starts[c]*block_n, ...+counts[c])
    row_idx = np.concatenate([
        np.arange(counts[i], dtype=np.int64) + starts[i] * block_n
        for i in range(c)]) if c else np.zeros(0, np.int64)
    x_pad = jnp.zeros((nb_total * block_n, d), jnp.float32)
    x_pad = x_pad.at[jnp.asarray(row_idx)].set(x.astype(jnp.float32))

    m_pad = (m + block_m - 1) // block_m * block_m
    mblocks = m_pad // block_m
    s_flat = jnp.pad(s_pad.astype(jnp.float32),
                     ((0, 0), (0, m_pad - m), (0, 0))).reshape(c * m_pad, d)
    y_flat = jnp.pad(y_pad.astype(jnp.float32), ((0, 0), (0, m_pad - m)),
                     constant_values=-1.0).reshape(1, c * m_pad)
    inv2t2 = 1.0 / (2.0 * jnp.asarray(taus, jnp.float32) ** 2)  # (C,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb_total, mblocks),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j, seg, t: (i, 0)),
            pl.BlockSpec((block_m, d),
                         lambda i, j, seg, t: (seg[i] * mblocks + j, 0)),
            pl.BlockSpec((1, block_m),
                         lambda i, j, seg, t: (0, seg[i] * mblocks + j)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j, seg, t: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j, seg, t: (i, 0)),
        ],
    )
    num, den = pl.pallas_call(
        _simvote_segmented_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb_total * block_n, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb_total * block_n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(block_seg), inv2t2, x_pad, s_flat, y_flat)
    gather = jnp.asarray(row_idx)
    return num[gather, 0] / jnp.maximum(den[gather, 0], 1e-30)
