"""Pallas TPU kernel: tiled pairwise squared-L2 + fused argmin.

TPU adaptation of the scikit-learn CPU assignment step: the (N,K) distance
matrix is never materialized in HBM.  Each grid step streams a (BN, D) tile
of points through VMEM, forms the (BN, K) distance tile on the MXU via
-2 x @ c^T (+ norms), and reduces to (assign, dmin) in-register.  K and D
are kept whole per tile: K <= 256 clusters and D <= 4096 embedding dims fit
VMEM comfortably (BN*D*4 + K*D*4 + BN*K*4 ~ 8.5 MB at BN=256, D=4096, K=64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, c_ref, csq_ref, assign_ref, dmin_ref):
    x = x_ref[...].astype(jnp.float32)  # (BN, D)
    c = c_ref[...].astype(jnp.float32)  # (K, D)
    csq = csq_ref[...]  # (1, K)
    xsq = jnp.sum(x * x, axis=-1, keepdims=True)  # (BN, 1)
    # HIGHEST: an f32 dot at the MXU's default precision rounds its inputs
    # to bf16, which moves distances by ~1e-3 on unit-norm embeddings
    scores = lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (BN, K)
    d = jnp.maximum(xsq - 2.0 * scores + csq, 0.0)
    dmin = jnp.min(d, axis=-1, keepdims=True)  # (BN, 1)
    k = d.shape[-1]
    iota = lax.broadcasted_iota(jnp.int32, d.shape, 1)
    assign_ref[...] = jnp.min(jnp.where(d == dmin, iota, k), axis=-1,
                              keepdims=True)
    dmin_ref[...] = dmin


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def assign_clusters_pallas(x, cents, block_n: int = 256, interpret: bool = False):
    """x (N,D), cents (K,D) -> (assign (N,), dmin (N,)); N padded to block_n."""
    n, d = x.shape
    k = cents.shape[0]
    n_pad = (n + block_n - 1) // block_n * block_n
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    csq = jnp.sum(jnp.square(cents.astype(jnp.float32)), axis=-1)[None, :]

    assign, dmin = pl.pallas_call(
        _assign_kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        # (BN, 1) column blocks: a 2-D block whose last dim is the array's
        # whole last dim is a layout Mosaic accepts for any BN % 8 == 0
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, cents, csq)
    return assign[:n, 0], dmin[:n, 0]
