"""Model layers: norms, RoPE, attention (GQA/SWA, train+decode), MLP, MoE, Mamba.

Conventions
-----------
- activations ``(B, S, D)``; q ``(B, S, H, hd)``; k/v ``(B, S, KV, hd)``;
  KV caches ``(B, L, KV, hd)``.
- GQA is computed with grouped einsums (no KV head repetition in memory).
- softmax / SSM scans run in fp32 regardless of param dtype.
- All functions are pure; params are plain nested dicts of jnp arrays.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.api import shard_act
from repro.models.config import ModelConfig

# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------


def _dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------


def init_norm(cfg: ModelConfig):
    if cfg.norm_type == "ln":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.ones((cfg.d_model,), jnp.float32)}


def apply_norm(cfg: ModelConfig, p, x):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "ln":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + cfg.norm_eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(var + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# positions
# --------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, n, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin, cos = jnp.sin(angles)[..., None, :], jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


def sinusoidal_positions(positions, d_model: int):
    """Whisper-style sinusoidal embeddings; positions (..., S) -> (..., S, D)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(10000.0) / max(1, half - 1)))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    D, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": _dense_init(ks[0], (D, H * hd), dt),
        "wk": _dense_init(ks[1], (D, KV * hd), dt),
        "wv": _dense_init(ks[2], (D, KV * hd), dt),
        "wo": _dense_init(ks[3], (H * hd, D), dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    return p


def _project_qkv(cfg, p, x, kv_x=None):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    kv_x = x if kv_x is None else kv_x
    Skv = kv_x.shape[1]
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, KV, H // KV, hd),
            k.reshape(B, Skv, KV, hd),
            v.reshape(B, Skv, KV, hd))


def _sdpa(q, k, v, mask, scale):
    """q (B,Sq,KV,G,hd), k/v (B,Sk,KV,hd), mask broadcastable (B,1,1,Sq,Sk)."""
    scores = jnp.einsum("bqcgh,bkch->bcgqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bcgqk,bkch->bqcgh", probs.astype(v.dtype), v)
    return out


def _causal_window_mask(q_pos, k_pos, window):
    """(..., Sq, Sk) bool mask: causal, optionally within sliding window."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m = m & (k_pos[..., None, :] > q_pos[..., :, None] - window)
    return m


def attention_plain(cfg: ModelConfig, p, x, *, causal: bool, window=None,
                    positions=None, kv_x=None, rope: bool = True):
    """Full-matrix attention; fine for short sequences / encoders."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if rope and cfg.pos_type == "rope" and kv_x is None:
        q = apply_rope(q.reshape(B, S, -1, hd), positions, cfg.rope_theta).reshape(q.shape)
        k = apply_rope(k, positions, cfg.rope_theta)
    mask = None
    if causal:
        kpos = jnp.arange(k.shape[1])[None, :]
        mask = _causal_window_mask(positions, kpos, window)[:, None, None]
    out = _sdpa(q, k, v, mask, 1.0 / math.sqrt(hd))
    out = out.reshape(B, S, -1)
    return out @ p["wo"]


def attention_chunked(cfg: ModelConfig, p, x, *, causal: bool, window=None,
                      positions=None):
    """Flash-style chunked attention in pure XLA (online softmax).

    Three schedules:
      - window (banded): q-chunk i attends only chunks in its band (static count)
      - causal + attn_impl=="tri": triangle-packed schedule — scan over the
        nq(nq+1)/2 (qi,kj) lower-triangle block pairs; zero wasted FLOPs
      - otherwise: rectangle schedule with masking (baseline; ~2x causal waste)
    """
    B, S, _ = x.shape
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    G = cfg.n_heads // KV
    cq = min(cfg.attn_chunk_q, S)
    ck = min(cfg.attn_chunk_kv, S)
    assert S % cq == 0 and S % ck == 0, (S, cq, ck)
    nq, nk = S // cq, S // ck
    scale = 1.0 / math.sqrt(hd)

    q, k, v = _project_qkv(cfg, p, x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if cfg.pos_type == "rope":
        q = apply_rope(q.reshape(B, S, -1, hd), positions, cfg.rope_theta).reshape(q.shape)
        k = apply_rope(k, positions, cfg.rope_theta)

    qc = q.reshape(B, nq, cq, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)  # (nq,B,cq,KV,G,hd)
    dt = x.dtype

    def block(qi_pos, kj_pos, q_blk, k_blk, v_blk, m, l, acc):
        """online-softmax update for one (q_blk, k_blk) pair."""
        s = jnp.einsum("bqcgh,bkch->bcgqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        msk = _causal_window_mask(qi_pos, kj_pos, window)[:, None, None] if causal \
            else None
        if msk is not None:
            s = jnp.where(msk, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p_ = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p_, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bcgqk,bkch->bcgqh", p_.astype(dt), v_blk).astype(jnp.float32)
        return m_new, l_new, acc_new

    if window is not None and cfg.swa_banded:
        # banded: only the last wb+1 kv chunks can intersect the window
        wb = -(-window // ck)  # ceil
        nband = min(nk, wb + -(-cq // ck))

        def q_step(_, qi):
            q_blk = qc[qi]
            qi_pos = positions[:, qi * cq:(qi + 1) * cq] if positions.shape[0] == B \
                else jnp.arange(cq)[None] + qi * cq
            m = jnp.full((B, KV, G, cq), -1e30, jnp.float32)
            l = jnp.zeros((B, KV, G, cq), jnp.float32)
            acc = jnp.zeros((B, KV, G, cq, hd), jnp.float32)

            def band_step(carry, off):
                m, l, acc = carry
                # kv chunk index = qi_chunk_in_kv - off, clamped; mask handles dups
                base = (qi * cq) // ck
                kj = jnp.maximum(base - off, 0)
                k_blk = lax.dynamic_slice_in_dim(k, kj * ck, ck, axis=1)
                v_blk = lax.dynamic_slice_in_dim(v, kj * ck, ck, axis=1)
                kj_pos = (jnp.arange(ck)[None] + kj * ck)
                # drop duplicate clamped chunks: only off==base-kj is valid
                valid = (base - off) >= 0
                m2, l2, a2 = block(qi_pos, kj_pos, q_blk, k_blk, v_blk, m, l, acc)
                m, l, acc = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(valid, new, old), (m2, l2, a2), (m, l, acc))
                return (m, l, acc), None

            (m, l, acc), _ = lax.scan(band_step, (m, l, acc), jnp.arange(nband))
            out = acc / jnp.maximum(l[..., None], 1e-30)
            return None, out.astype(dt)

        if getattr(cfg, "remat_inner", True):
            q_step = jax.checkpoint(q_step)
        _, outs = lax.scan(q_step, None, jnp.arange(nq))  # (nq,B,KV,G,cq,hd)
    elif causal and cfg.attn_impl == "tri":
        # triangle-packed: iterate lower-triangle block pairs, row-major
        qis, kjs = [], []
        for i in range(nq):
            hi = ((i + 1) * cq + ck - 1) // ck  # kv chunks covering <= q end
            for j in range(min(hi, nk)):
                qis.append(i)
                kjs.append(j)
        qis = jnp.array(qis, jnp.int32)
        kjs = jnp.array(kjs, jnp.int32)
        m0 = jnp.full((nq, B, KV, G, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((nq, B, KV, G, cq), jnp.float32)
        a0 = jnp.zeros((nq, B, KV, G, cq, hd), jnp.float32)

        def tri_step(carry, ij):
            m_all, l_all, a_all = carry
            qi, kj = ij
            q_blk = lax.dynamic_index_in_dim(qc, qi, 0, keepdims=False)
            k_blk = lax.dynamic_slice_in_dim(k, kj * ck, ck, axis=1)
            v_blk = lax.dynamic_slice_in_dim(v, kj * ck, ck, axis=1)
            qi_pos = jnp.arange(cq)[None] + qi * cq
            kj_pos = jnp.arange(ck)[None] + kj * ck
            m = lax.dynamic_index_in_dim(m_all, qi, 0, keepdims=False)
            l = lax.dynamic_index_in_dim(l_all, qi, 0, keepdims=False)
            acc = lax.dynamic_index_in_dim(a_all, qi, 0, keepdims=False)
            m, l, acc = block(qi_pos, kj_pos, q_blk, k_blk, v_blk, m, l, acc)
            m_all = lax.dynamic_update_index_in_dim(m_all, m, qi, 0)
            l_all = lax.dynamic_update_index_in_dim(l_all, l, qi, 0)
            a_all = lax.dynamic_update_index_in_dim(a_all, acc, qi, 0)
            return (m_all, l_all, a_all), None

        if getattr(cfg, "remat_inner", True):
            tri_step = jax.checkpoint(tri_step)
        (m_all, l_all, a_all), _ = lax.scan(tri_step, (m0, l0, a0), (qis, kjs))
        outs = (a_all / jnp.maximum(l_all[..., None], 1e-30)).astype(dt)
    else:
        # rectangle schedule: every q chunk scans all kv chunks with masking
        def q_step(_, qi):
            q_blk = qc[qi]
            qi_pos = jnp.arange(cq)[None] + qi * cq
            m = jnp.full((B, KV, G, cq), -1e30, jnp.float32)
            l = jnp.zeros((B, KV, G, cq), jnp.float32)
            acc = jnp.zeros((B, KV, G, cq, hd), jnp.float32)

            def kv_step(carry, kj):
                m, l, acc = carry
                k_blk = lax.dynamic_slice_in_dim(k, kj * ck, ck, axis=1)
                v_blk = lax.dynamic_slice_in_dim(v, kj * ck, ck, axis=1)
                kj_pos = jnp.arange(ck)[None] + kj * ck
                m, l, acc = block(qi_pos, kj_pos, q_blk, k_blk, v_blk, m, l, acc)
                return (m, l, acc), None

            (m, l, acc), _ = lax.scan(kv_step, (m, l, acc), jnp.arange(nk))
            out = acc / jnp.maximum(l[..., None], 1e-30)
            return None, out.astype(dt)

        if getattr(cfg, "remat_inner", True):
            q_step = jax.checkpoint(q_step)
        _, outs = lax.scan(q_step, None, jnp.arange(nq))

    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, -1)  # (B,S,H*hd)
    return out @ p["wo"]


def attention_flash(cfg: ModelConfig, p, x, *, causal=True, window=None,
                    positions=None):
    """Pallas flash-attention kernel on the prefill/forward hot path.

    ``attn_impl="flash"`` runs ``repro.kernels.flash_attention`` (compiled
    on TPU; interpret mode off-TPU, so the serving path is testable on CPU);
    ``attn_impl="flash-ref"`` runs its jnp oracle.  Mask positions are
    sequence-local 0..S-1 (same assumption as the chunked tri/rect
    schedules); ``positions`` feeds RoPE only.
    """
    from repro.kernels.flash_attention.ops import flash_attention
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(cfg, p, x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if cfg.pos_type == "rope":
        q = apply_rope(q.reshape(B, S, -1, hd), positions, cfg.rope_theta).reshape(q.shape)
        k = apply_rope(k, positions, cfg.rope_theta)
    qh = q.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)   # (B, H, S, hd)
    kh = k.transpose(0, 2, 1, 3)                         # (B, KV, S, hd)
    vh = v.transpose(0, 2, 1, 3)
    impl = "ref" if cfg.attn_impl == "flash-ref" else "pallas"
    out = flash_attention(qh, kh, vh, causal=causal, window=window,
                          impl=impl)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, -1)    # (B, S, H*hd)
    return out @ p["wo"]


def attention_apply(cfg: ModelConfig, p, x, *, causal=True, window=None,
                    positions=None, kv_x=None):
    """Dispatch plain vs chunked vs Pallas-flash by config / seq length."""
    S = x.shape[1]
    impl = cfg.attn_impl
    if kv_x is not None or not causal:
        return attention_plain(cfg, p, x, causal=causal, window=window,
                               positions=positions, kv_x=kv_x)
    if impl in ("flash", "flash-ref"):
        # the kernel tiles S by min(128, S); the engine's power-of-two
        # buckets (>= 32) always divide.  Never swap in another path: the
        # caller asked for the kernel.
        if S % min(128, S):
            raise ValueError(f"attn_impl={impl!r} needs the sequence length "
                             f"to be a multiple of 128 or below it; got {S}")
        return attention_flash(cfg, p, x, causal=causal, window=window,
                               positions=positions)
    if impl == "plain" or (impl == "auto" and S <= 4096 and window is None):
        return attention_plain(cfg, p, x, causal=causal, window=window,
                               positions=positions)
    if S % min(cfg.attn_chunk_q, S) != 0:
        return attention_plain(cfg, p, x, causal=causal, window=window,
                               positions=positions)
    return attention_chunked(cfg, p, x, causal=causal, window=window,
                             positions=positions)


# ---------------------------------------------------------------- decode


def attention_decode(cfg: ModelConfig, p, x1, cache, pos, *, window=None,
                     cross_kv=None):
    """One-token decode against a KV cache.

    cache: {"k": (B, L, KV, hd), "v": (B, L, KV, hd)}; L = full seq for global
    layers, ring size for sliding-window layers.  Keys are stored post-RoPE.
    ``pos``: (B,) current position (0-based index of the new token).
    Returns (out (B,1,D), new_cache).
    """
    B = x1.shape[0]
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    q, k_new, v_new = _project_qkv(cfg, p, x1, None if cross_kv is None else x1)
    if cross_kv is not None:
        # cross-attention: static precomputed K/V, no cache update
        k, v = cross_kv["k"], cross_kv["v"]
        scores = jnp.einsum("bqcgh,bkch->bcgqk", q, k,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bcgqk,bkch->bqcgh", probs.astype(v.dtype), v)
        return out.reshape(B, 1, -1) @ p["wo"], cache

    if cfg.pos_type == "rope":
        q = apply_rope(q.reshape(B, 1, -1, hd), pos[:, None], cfg.rope_theta).reshape(q.shape)
        k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)

    L = cache["k"].shape[1]
    slot = pos % L if window is not None else jnp.minimum(pos, L - 1)
    # write new k/v at slot (per-batch dynamic scatter)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, slot].set(v_new[:, 0].astype(cache["v"].dtype))

    if cfg.attn_impl in ("flash", "flash-ref") and window is None:
        # flash-decoding kernel: global layers keep a contiguous prefix
        # cache (slot s = position s), exactly the kernel's lengths
        # semantics.  Windowed ring buffers stay on the jnp path below.
        from repro.kernels.decode_attention.ops import decode_attention
        qd = q.reshape(B, -1, hd)                # (B, H, hd)
        kd = k_cache.transpose(0, 2, 1, 3)       # (B, KV, L, hd)
        vd = v_cache.transpose(0, 2, 1, 3)
        impl = "ref" if cfg.attn_impl == "flash-ref" else "pallas"
        out = decode_attention(qd, kd, vd, pos + 1, impl=impl)
        out = out.reshape(B, 1, -1) @ p["wo"]
        return out, {"k": k_cache, "v": v_cache}

    # validity: which cache slots hold tokens visible to this query
    slot_ids = jnp.arange(L)[None, :]  # (1, L)
    if window is None:
        valid = slot_ids <= pos[:, None]
    else:
        # ring buffer: slot s holds absolute position p' ≡ s (mod L), the
        # largest such p' ≤ pos; it is valid if pos - p' < window and p' ≥ 0
        delta = (pos[:, None] - slot_ids) % L  # age of entry in slots
        valid = (delta < jnp.minimum(window, pos[:, None] + 1))
    scores = jnp.einsum("bqcgh,bkch->bcgqk", q, k_cache,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bcgqk,bkch->bqcgh", probs.astype(v_cache.dtype), v_cache)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache}


# --------------------------------------------------------------------------
# MLP (dense)
# --------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig):
    D, F = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "gelu":
        return {"w_in": _dense_init(ks[0], (D, F), dt),
                "b_in": jnp.zeros((F,), dt),
                "w_out": _dense_init(ks[1], (F, D), dt),
                "b_out": jnp.zeros((D,), dt)}
    return {"w_gate": _dense_init(ks[0], (D, F), dt),
            "w_up": _dense_init(ks[1], (D, F), dt),
            "w_down": _dense_init(ks[2], (F, D), dt)}


def apply_mlp(cfg: ModelConfig, p, x):
    if cfg.mlp_type == "gelu":
        h = jax.nn.gelu((x @ p["w_in"] + p["b_in"]).astype(jnp.float32)).astype(x.dtype)
        return h @ p["w_out"] + p["b_out"]
    g = jax.nn.silu((x @ p["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return (g * (x @ p["w_up"])) @ p["w_down"]


# --------------------------------------------------------------------------
# MoE (token-choice top-k, capacity-bounded scatter dispatch)
# --------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (D, E), jnp.float32),
        "w_gate": _dense_init(ks[1], (E, D, F), dt, fan_in=D),
        "w_up": _dense_init(ks[2], (E, D, F), dt, fan_in=D),
        "w_down": _dense_init(ks[3], (E, F, D), dt, fan_in=F),
    }


def _round_up(x, m):
    return (x + m - 1) // m * m


def moe_ffn_tokens(cfg: ModelConfig, p, x):
    """MoE over batched capacity groups x (B, T, D) -> (B, T, D), plus aux.

    Capacity-bounded scatter dispatch with *per-sequence groups* (GShard
    'groups' = the batch dim): every sequence dispatches into its own
    (E, C, D) buffer, so no data-dependent cross-shard movement exists and
    the SPMD partitioner keeps B on the data axis and E (or the expert FFN
    dim, when E doesn't divide the model axis) on the model axis.  Explicit
    shard_act constraints pin that layout — without them GSPMD replicates
    the expert compute across data shards (measured: 8-16x FLOP waste,
    see EXPERIMENTS.md §Perf).
    Tokens that overflow an expert's per-group capacity are dropped
    (contribute zero), standard GShard semantics.
    """
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, K)  # (B, T, K)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    C = _round_up(max(1, int(K * T / E * cfg.capacity_factor)), 8)
    C = min(C, T)
    # rank of each (token, slot) within its expert, flat (T*K) per sequence
    flat_e = topi.reshape(B, T * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (B, T*K, E)
    ranks = jnp.take_along_axis(jnp.cumsum(onehot, axis=1),
                                flat_e[..., None], axis=2)[..., 0] - 1
    keep = ranks < C
    dst = jnp.where(keep, flat_e * C + ranks, E * C)  # (B, T*K); sentinel E*C

    x_rep = jnp.repeat(x, K, axis=1)  # (B, T*K, D)
    buf = jax.vmap(lambda xb, db: jnp.zeros((E * C + 1, D), x.dtype
                                            ).at[db].set(xb))(x_rep, dst)
    buf = buf[:, :E * C].reshape(B, E, C, D)
    buf = shard_act(buf, ("batch", "experts", None, None))

    g = jnp.einsum("becd,edf->becf", buf, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard_act(h, ("batch", "experts", None, "ffn"))
    out_buf = jnp.einsum("becf,efd->becd", h, p["w_down"])
    out_buf = shard_act(out_buf, ("batch", "experts", None, None))
    out_flat = jnp.concatenate(
        [out_buf.reshape(B, E * C, D),
         jnp.zeros((B, 1, D), out_buf.dtype)], axis=1)

    gathered = jnp.take_along_axis(out_flat, dst[..., None], axis=1)
    out = jnp.sum(gathered.reshape(B, T, K, D)
                  * topv[..., None].astype(x.dtype), axis=2)

    # aux: load-balance loss (Switch) — mean fraction * mean prob per expert
    frac = jnp.mean(jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32),
                    axis=(0, 1))
    imp = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac * imp)
    return out, aux


def apply_moe(cfg: ModelConfig, p, x):
    """x (B,S,D) -> (B,S,D); scanned over S-chunks to bound the dispatch
    buffers (capacity group = sequence x chunk)."""
    B, S, D = x.shape
    chunk = getattr(cfg, "moe_chunk", 8192)
    if chunk <= 0 or S <= chunk or S % chunk != 0:
        return moe_ffn_tokens(cfg, p, x)
    nch = S // chunk
    xs = x.reshape(B, nch, chunk, D).transpose(1, 0, 2, 3)  # (nch,B,ck,D)

    def step(acc, xc):
        out, a = moe_ffn_tokens(cfg, p, xc)
        return acc + a, out

    if getattr(cfg, "remat_inner", True):
        step = jax.checkpoint(step)  # dispatch buffers recomputed in bwd
    aux, outs = lax.scan(step, jnp.float32(0.0), xs)
    out = outs.transpose(1, 0, 2, 3).reshape(B, S, D)
    return out, aux / nch


# --------------------------------------------------------------------------
# Mamba-1 (selective scan)
# --------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig):
    D, di, ds, dr, dc = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                         cfg.dt_rank, cfg.ssm_conv)
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "in_proj": _dense_init(ks[0], (D, 2 * di), dt),
        "conv_w": _dense_init(ks[1], (dc, di), dt, fan_in=dc),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": _dense_init(ks[2], (di, dr + 2 * ds), dt),
        "dt_proj": _dense_init(ks[3], (dr, di), dt),
        "dt_bias": jnp.full((di,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _dense_init(ks[4], (di, D), dt),
    }


def _mamba_gates(cfg, p, xr):
    """Common pre-scan computation: xr (B,S,di) -> dt, Bc, Cc (fp32)."""
    dr, ds = cfg.dt_rank, cfg.ssm_state
    dbc = (xr @ p["x_proj"]).astype(jnp.float32)  # (B,S,dr+2ds)
    dt_low, Bc, Cc = jnp.split(dbc, [dr, dr + ds], axis=-1)
    dt = jax.nn.softplus(dt_low @ p["dt_proj"].astype(jnp.float32) + p["dt_bias"])
    return dt, Bc, Cc  # (B,S,di), (B,S,ds), (B,S,ds)


def mamba_scan(cfg: ModelConfig, p, x, h0=None, conv0=None):
    """Full-sequence Mamba: x (B,S,D) -> (y (B,S,D), (h_final, conv_state)).

    Chunked along S (cfg.ssm_chunk): within-chunk associative scan in fp32,
    sequential carry across chunks — bounds the (B,ck,di,ds) intermediate.
    """
    B, S, D = x.shape
    di, ds, dc = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    xz = shard_act(x @ p["in_proj"], ("batch", None, "inner"))
    xr, z = jnp.split(xz, 2, axis=-1)  # (B,S,di) each
    xr = shard_act(xr, ("batch", None, "inner"))
    z = shard_act(z, ("batch", None, "inner"))

    # causal depthwise conv along S
    pad = jnp.zeros((B, dc - 1, di), xr.dtype) if conv0 is None else conv0.astype(xr.dtype)
    xp = jnp.concatenate([pad, xr], axis=1)  # (B, S+dc-1, di)
    conv_state = xp[:, -(dc - 1):, :] if dc > 1 else None
    xc = sum(xp[:, i:i + S, :] * p["conv_w"][i] for i in range(dc)) + p["conv_b"]
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)
    xc = shard_act(xc, ("batch", None, "inner"))

    dt, Bc, Cc = _mamba_gates(cfg, p, xc)
    dt = shard_act(dt, ("batch", None, "inner"))
    A = -jnp.exp(p["A_log"])  # (di, ds)

    ck = min(cfg.ssm_chunk, S)
    xcf = xc.astype(jnp.float32)

    def run_chunk(h, dt_c, B_c, C_c, x_c):
        a = jnp.exp(dt_c[..., None] * A)  # (B,c,di,ds)
        b = (dt_c * x_c)[..., None] * B_c[:, :, None, :]  # (B,c,di,ds)

        def comb(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        a_cum, b_cum = lax.associative_scan(comb, (a, b), axis=1)
        h_all = a_cum * h[:, None] + b_cum  # (B,c,di,ds)
        y = jnp.einsum("bcds,bcs->bcd", h_all, C_c)  # (B,c,di)
        y = y + p["D"] * x_c
        return h_all[:, -1], y

    def chunk_step(h, idx):
        sl = lambda a: lax.dynamic_slice_in_dim(a, idx * ck, ck, axis=1)
        return run_chunk(h, sl(dt), sl(Bc), sl(Cc), sl(xcf))

    if getattr(cfg, "remat_inner", True):
        # recompute the within-chunk associative scan in backward: drops the
        # per-chunk (B,ck,di,ds) stacks from 'saved' to 'transient'
        chunk_step = jax.checkpoint(chunk_step)

    h = jnp.zeros((B, di, ds), jnp.float32) if h0 is None else h0
    n_main, tail = S // ck, S % ck
    if n_main:
        h, ys = lax.scan(chunk_step, h, jnp.arange(n_main))
        y_main = ys.transpose(1, 0, 2, 3).reshape(B, n_main * ck, di)
    else:
        y_main = jnp.zeros((B, 0, di), jnp.float32)
    if tail:
        sl = lambda a: a[:, n_main * ck:]
        h, y_tail = run_chunk(h, sl(dt), sl(Bc), sl(Cc), sl(xcf))
        y = jnp.concatenate([y_main, y_tail], axis=1)
    else:
        y = y_main
    y = shard_act(y, ("batch", None, "inner"))
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return y @ p["out_proj"], (h, conv_state)


def mamba_decode(cfg: ModelConfig, p, x1, state):
    """One-token Mamba step. state = {"h": (B,di,ds) fp32, "conv": (B,dc-1,di)}."""
    B = x1.shape[0]
    di, ds, dc = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    xz = x1 @ p["in_proj"]  # (B,1,2di)
    xr, z = jnp.split(xz, 2, axis=-1)
    window = jnp.concatenate([state["conv"].astype(xr.dtype), xr], axis=1)  # (B,dc,di)
    new_conv = window[:, 1:, :]
    xc = jnp.einsum("bcd,cd->bd", window, p["conv_w"]) + p["conv_b"]  # (B,di)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x1.dtype)[:, None, :]  # (B,1,di)

    dt, Bc, Cc = _mamba_gates(cfg, p, xc)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt[:, 0, :, None] * A)  # (B,di,ds)
    b = (dt[:, 0] * xc[:, 0].astype(jnp.float32))[..., None] * Bc[:, 0, None, :]
    h = a * state["h"] + b  # (B,di,ds)
    y = jnp.einsum("bds,bs->bd", h, Cc[:, 0]) + p["D"] * xc[:, 0].astype(jnp.float32)
    y = (y[:, None, :] * jax.nn.silu(z.astype(jnp.float32))).astype(x1.dtype)
    return y @ p["out_proj"], {"h": h, "conv": new_conv.astype(state["conv"].dtype)}
