"""Pallas TPU kernel: causal/SWA GQA flash attention (prefill path).

TPU adaptation of FlashAttention-2 for the serving engine's prefill:
- grid (B, H / block_h, nQ, nK); K innermost so the online-softmax state
  (m, l, acc) of a block of ``block_h`` query heads lives in VMEM scratch
  across the K sweep of one Q tile;
- GQA via BlockSpec index_map: a block of ``block_h`` query heads reads the
  ``block_h // G`` KV heads it shares (no KV head replication in HBM), and
  the G query heads of one KV head go through the MXU as one
  ``(G * block_q, hd)`` operand;
- scores as (key, query) tiles: the softmax reduces over sublanes
  (elementwise across vregs) instead of across lanes, and the state m, l
  is one lane-dense row per query;
- bf16 in, f32 out of the MXU (``preferred_element_type``), the softmax
  state and its exp in f32, probabilities cast to the value dtype before
  the PV product: the precision of ``models.layers._sdpa``;
- ``lens`` (B,) true row lengths, scalar-prefetched into SMEM: a (Q, K)
  tile runs only when some real query of it sees some key of it (causal,
  window, ``k < lens[b]``, ``q < lens[b]``); the K/V and Q index_maps clamp
  to the tiles a row needs, so a skipped step repeats the previous block
  index and costs no DMA.  Rows at or past ``lens[b]`` come out zero
  (finite: they feed the next layer's K/V at padding positions).  Only
  tiles that cut the diagonal, the window's edge or ``lens[b]`` build a
  mask; V past ``lens[b]`` inside such a tile must be finite (its
  probability is 0, and 0 x NaN is NaN), as padding tokens' values are.
MXU-aligned tiles: block_q x hd and block_k x hd with hd in {64,128,256}.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _tile_bounds(qi, kj, length, *, block_q, block_k, causal, window):
    """(run, full) for tile (qi, kj) of a row of ``length`` real tokens:
    ``run`` when some real query of the tile sees some key of it, ``full``
    when every (query, key) pair of the tile is visible (no mask)."""
    q_lo, k_lo = qi * block_q, kj * block_k
    q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1
    run = (q_lo < length) & (k_lo < length)
    full = k_hi < length
    if causal:
        run = run & (k_lo <= q_hi)
        full = full & (k_hi <= q_lo)
        if window is not None:
            run = run & (k_hi > q_lo - window)
            full = full & (k_lo > q_hi - window)
    return run, full


def tile_counts(lens, seq_len: int, *, block_q: int, block_k: int,
                causal: bool = True, window=None):
    """(tiles the kernel computes, tiles of the full Q x K grid) summed over
    the rows of ``lens``, per head: host-side accounting, no device work."""
    bq, bk = min(block_q, seq_len), min(block_k, seq_len)
    qi = np.arange(seq_len // bq)[:, None, None]
    kj = np.arange(seq_len // bk)[None, :, None]
    length = np.asarray(lens, np.int64)[None, None, :]
    run, _ = _tile_bounds(qi, kj, length, block_q=bq, block_k=bk,
                          causal=causal, window=window)
    return int(run.sum()), int(qi.size * kj.size * length.size)


def _flash_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, causal: bool, window,
                  block_q: int, block_k: int, n_k: int, group: int):
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    hkv, hd = k_ref.shape[1], k_ref.shape[3]
    rows = group * block_q

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    run, full = _tile_bounds(qi, kj, length, block_q=block_q,
                             block_k=block_k, causal=causal, window=window)

    def update(masked: bool):
        # scores transposed, (keys, queries): the softmax reduces over
        # sublanes (elementwise across vregs), not across lanes; the G
        # query heads of a KV head are one (G * bq, hd) MXU operand
        q = q_ref[0].reshape(hkv, rows, hd)
        s = jnp.einsum("ckd,cqd->ckq", k_ref[0], q,
                       preferred_element_type=jnp.float32) * scale
        if masked:   # one (bk, G * bq) mask, shared by the KV heads
            kpos = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, rows), 0)
            qpos = qi * block_q + lax.rem(
                lax.broadcasted_iota(jnp.int32, (block_k, rows), 1), block_q)
            mask = kpos < length
            if causal:
                mask &= kpos <= qpos
                if window is not None:
                    mask &= kpos > qpos - window
            s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_scr[...]                                # (hkv, 1, rows)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        acc_scr[...] = acc_scr[...] * corr + jnp.einsum(    # (hkv, hd, rows)
            "ckd,ckq->cdq", v, p.astype(v.dtype),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    pl.when(run & full)(lambda: update(False))
    pl.when(run & jnp.logical_not(full))(lambda: update(True))

    @pl.when(kj == n_k - 1)
    def _finish():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        qpos = qi * block_q + lax.rem(
            lax.broadcasted_iota(jnp.int32, (hd, rows), 1), block_q)
        o = jnp.where((qpos < length)[None], o, 0.0)   # padding rows: zero
        o_ref[0] = jnp.swapaxes(o, 1, 2).reshape(
            o_ref.shape[1:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "block_h", "interpret"))
def flash_attention_pallas(q, k, v, lens=None, *, causal: bool = True,
                           window=None, block_q: int = 128,
                           block_k: int = 128, block_h=None,
                           interpret: bool = False):
    """q (B,H,S,hd); k/v (B,KV,S,hd); lens (B,) int32 or None (every row
    S long) -> (B,H,S,hd).  ``block_h`` query heads per grid step (a
    multiple of G that divides H; default all H)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    assert k.shape[2] == S, "self-attention: q and k/v share positions"
    G = H // KV
    hb = H if block_h is None else block_h
    assert hb % G == 0 and H % hb == 0, (H, G, hb)
    hkv = hb // G
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_k = S // bk
    if lens is None:
        lens = jnp.full((B,), S, jnp.int32)

    def q_index(b, h, qi, kj, lens):
        # a query tile wholly past the row's length reads nothing new
        return b, h, jnp.minimum(qi, jnp.maximum(lens[b] - 1, 0) // bq), 0

    def kv_index(b, h, qi, kj, lens):
        # clamp to the key tiles this query tile needs: a skipped step
        # repeats the block index of a step that runs, so no DMA
        last = jnp.maximum(lens[b] - 1, 0) // bk
        if causal:
            last = jnp.minimum(last, (qi * bq + bq - 1) // bk)
        first = 0
        if causal and window is not None:
            first = jnp.maximum(0, (qi * bq - window + 1) // bk)
        return b, h, jnp.minimum(jnp.maximum(kj, first), last), 0

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, block_q=bq, block_k=bk, n_k=n_k, group=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // hb, S // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, hb, bq, hd), q_index),
            pl.BlockSpec((1, hkv, bk, hd), kv_index),
            pl.BlockSpec((1, hkv, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, hb, bq, hd),
                               lambda b, h, qi, kj, lens: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, 1, G * bq), jnp.float32),
            pltpu.VMEM((hkv, 1, G * bq), jnp.float32),
            pltpu.VMEM((hkv, hd, G * bq), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens.astype(jnp.int32), q, k, v)
