"""Pallas TPU kernel: flash-decoding over a KV cache (one new token).

The decode hot loop of the oracle LLM: one query row per (batch, head)
against an L-long cache.  Memory-bound by the KV stream, so the kernel's
job is to keep the KV read perfectly sequential through VMEM while the
(1 x L) score row reduces online — grid (B, KV, nL), L innermost with
(m, l, acc) scratch carried across L tiles.  Per-sequence ``lengths``
masks both ragged prefixes and ring-buffer slots.

The cross-chip half of 500k-decode (sequence-sharded KV + 3-term softmax
merge) lives in models/layers.py / GSPMD; this kernel is the per-chip leaf.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_l: int, n_l: int, G: int):
    lj = pl.program_id(2)

    @pl.when(lj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bl, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    length = len_ref[pl.program_id(0)]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale  # (G, bl)
    pos = lj * block_l + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(lj == n_l - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def decode_attention_pallas(q, k, v, lengths, *, block_l: int = 256,
                            interpret: bool = False):
    """q (B,H,hd); k/v (B,KV,L,hd); lengths (B,) -> (B,H,hd)."""
    B, H, hd = q.shape
    KV, L = k.shape[1], k.shape[2]
    G = H // KV
    bl = min(block_l, L)
    L_pad = (L + bl - 1) // bl * bl
    if L_pad != L:
        pad = ((0, 0), (0, 0), (0, L_pad - L), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    n_l = L_pad // bl
    scale = 1.0 / math.sqrt(hd)
    qr = q.reshape(B, KV, G, hd)

    kernel = functools.partial(_decode_kernel, scale=scale, block_l=bl,
                               n_l=n_l, G=G)
    # lengths (B,) is scalar-prefetched into SMEM; each program reads its
    # own sequence's entry
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_l),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, c, lj, ln: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, bl, hd), lambda b, c, lj, ln: (b, c, lj, 0)),
            pl.BlockSpec((1, 1, bl, hd), lambda b, c, lj, ln: (b, c, lj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, c, lj, ln: (b, c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, k, v)
    return out.reshape(B, H, hd)
