"""Decoder LM / encoder-decoder built from superblock patterns with scan.

The layer stack is stored as *stacked* superblock params (leading dim
``n_superblocks``) and executed with ``lax.scan`` so HLO size and compile
time are O(superblock), not O(n_layers).  Remat is applied per superblock.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.api import shard_act
from repro.models import layers as L
from repro.models.config import LayerSpec, ModelConfig

# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, spec: LayerSpec, with_xattn: bool):
    ks = jax.random.split(key, 6)
    p = {"norm": L.init_norm(cfg)}
    if spec.kind == "attn":
        p["attn"] = L.init_attention(ks[0], cfg)
    else:
        p["mamba"] = L.init_mamba(ks[0], cfg)
    if with_xattn:
        p["xattn_norm"] = L.init_norm(cfg)
        p["xattn"] = L.init_attention(ks[1], cfg, cross=True)
    if spec.ffn == "dense":
        p["ffn_norm"] = L.init_norm(cfg)
        p["ffn"] = L.init_mlp(ks[2], cfg)
    elif spec.ffn == "moe":
        p["ffn_norm"] = L.init_norm(cfg)
        p["moe"] = L.init_moe(ks[2], cfg)
    return p


def _init_superblock(key, cfg: ModelConfig, pattern, with_xattn: bool):
    ks = jax.random.split(key, len(pattern))
    return {f"l{i}": _init_layer(ks[i], cfg, spec, with_xattn)
            for i, spec in enumerate(pattern)}


def init_params(cfg: ModelConfig, key) -> dict:
    kemb, kblocks, kenc, khead = jax.random.split(key, 4)
    D, Vp = cfg.d_model, cfg.padded_vocab
    table = (jax.random.normal(kemb, (Vp, D), jnp.float32) * (1.0 / math.sqrt(D))
             ).astype(cfg.dtype)
    params = {"embed": {"table": table}}
    sb_keys = jax.random.split(kblocks, cfg.n_superblocks)
    params["blocks"] = jax.vmap(
        lambda k: _init_superblock(k, cfg, cfg.pattern, cfg.is_encdec))(sb_keys)
    params["final_norm"] = L.init_norm(cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": L._dense_init(khead, (Vp, D), jnp.dtype(cfg.dtype))}
    if cfg.is_encdec:
        enc_pattern = (LayerSpec(kind="attn", ffn="dense"),)
        enc_keys = jax.random.split(kenc, cfg.encoder_layers)
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_superblock(k, cfg, enc_pattern, False))(enc_keys)
        params["enc_final_norm"] = L.init_norm(cfg)
    return params


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree — dry-run stand-in, no allocation."""
    return jax.eval_shape(partial(init_params, cfg), jax.random.key(0))


# --------------------------------------------------------------------------
# logical axes for sharding
# --------------------------------------------------------------------------

_SUFFIX_AXES = {
    ("embed", "table"): ("vocab", "embed"),
    ("lm_head", "w"): ("vocab", "embed"),
    ("attn", "wq"): ("embed", "heads"),
    ("attn", "wk"): ("embed", "kv_heads"),
    ("attn", "wv"): ("embed", "kv_heads"),
    ("attn", "wo"): ("heads", "embed"),
    ("attn", "bq"): ("heads",),
    ("attn", "bk"): ("kv_heads",),
    ("attn", "bv"): ("kv_heads",),
    ("xattn", "wq"): ("embed", "heads"),
    ("xattn", "wk"): ("embed", "kv_heads"),
    ("xattn", "wv"): ("embed", "kv_heads"),
    ("xattn", "wo"): ("heads", "embed"),
    ("ffn", "w_gate"): ("embed", "ffn"),
    ("ffn", "w_up"): ("embed", "ffn"),
    ("ffn", "w_down"): ("ffn", "embed"),
    ("ffn", "w_in"): ("embed", "ffn"),
    ("ffn", "w_out"): ("ffn", "embed"),
    ("ffn", "b_in"): ("ffn",),
    ("ffn", "b_out"): (None,),
    ("moe", "router"): ("embed", None),
    ("moe", "w_gate"): ("experts", "embed", "ffn"),
    ("moe", "w_up"): ("experts", "embed", "ffn"),
    ("moe", "w_down"): ("experts", "ffn", "embed"),
    ("mamba", "in_proj"): ("embed", "inner"),
    ("mamba", "conv_w"): (None, "inner"),
    ("mamba", "conv_b"): ("inner",),
    ("mamba", "x_proj"): ("inner", None),
    ("mamba", "dt_proj"): (None, "inner"),
    ("mamba", "dt_bias"): ("inner",),
    ("mamba", "A_log"): ("inner", None),
    ("mamba", "D"): ("inner",),
    ("mamba", "out_proj"): ("inner", "embed"),
}


def param_logical_axes(cfg: ModelConfig):
    """Tree of logical-axis tuples matching init_params structure."""
    shapes = abstract_params(cfg)

    def assign(path: str, leaf):
        parts = path.split("/")
        stacked = parts[0] in ("blocks", "enc_blocks")
        key = tuple(parts[-2:])
        axes = _SUFFIX_AXES.get(key)
        if axes is None:  # norms, biases etc -> replicated
            axes = (None,) * (leaf.ndim - (1 if stacked else 0))
        if stacked:
            axes = (None,) + tuple(axes)
        assert len(axes) == leaf.ndim, (path, axes, leaf.shape)
        return tuple(axes)

    from repro.utils.tree import tree_map_with_path_str
    return tree_map_with_path_str(assign, shapes)


# --------------------------------------------------------------------------
# forward (train / full-sequence)
# --------------------------------------------------------------------------


def _apply_layer(cfg: ModelConfig, spec: LayerSpec, p, h, positions,
                 enc_out=None, lens=None):
    """One layer, full-sequence mode.  Returns (h, aux)."""
    aux = jnp.float32(0.0)
    hn = L.apply_norm(cfg, p["norm"], h)
    if spec.kind == "attn":
        attn_out = L.attention_apply(cfg, p["attn"], hn, causal=True,
                                     window=spec.window, positions=positions,
                                     lens=lens)
    else:
        attn_out, _ = L.mamba_scan(cfg, p["mamba"], hn)
    h = h + attn_out
    if "xattn" in p and enc_out is not None:
        hx = L.apply_norm(cfg, p["xattn_norm"], h)
        h = h + L.attention_plain(cfg, p["xattn"], hx, causal=False,
                                  kv_x=enc_out)
    if spec.ffn == "dense":
        hf = L.apply_norm(cfg, p["ffn_norm"], h)
        h = h + L.apply_mlp(cfg, p["ffn"], hf)
    elif spec.ffn == "moe":
        hf = L.apply_norm(cfg, p["ffn_norm"], h)
        out, a = L.apply_moe(cfg, p["moe"], hf)
        h = h + out
        aux = aux + a
    return h, aux


def _superblock_fwd(cfg: ModelConfig, sb_params, h, positions, enc_out=None,
                    lens=None):
    aux = jnp.float32(0.0)
    for i, spec in enumerate(cfg.pattern):
        h, a = _apply_layer(cfg, spec, sb_params[f"l{i}"], h, positions,
                            enc_out, lens)
        aux = aux + a
    return h, aux


def _remat(cfg: ModelConfig, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _run_stack(cfg: ModelConfig, blocks, h, positions, enc_out=None,
               pattern=None, lens=None):
    pattern = pattern if pattern is not None else cfg.pattern

    def body(carry, sb_params):
        h, aux = carry
        cfg_local = cfg if pattern is cfg.pattern else cfg.replace(pattern=pattern)
        h2, a = _superblock_fwd(cfg_local, sb_params, h, positions, enc_out,
                                lens)
        return (h2, aux + a), None

    body = _remat(cfg, body)
    if cfg.scan_layers:
        (h, aux), _ = lax.scan(body, (h, jnp.float32(0.0)), blocks)
    else:
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        carry = (h, jnp.float32(0.0))
        for i in range(n):
            sb = jax.tree_util.tree_map(lambda x: x[i], blocks)
            carry, _ = body(carry, sb)
        h, aux = carry
    return h, aux


def _embed_tokens(cfg: ModelConfig, params, tokens):
    return params["embed"]["table"][tokens]


def _logits(cfg: ModelConfig, params, h):
    h = L.apply_norm(cfg, params["final_norm"], h)
    table = params["lm_head"]["w"] if not cfg.tie_embeddings else params["embed"]["table"]
    logits = jnp.einsum("bsd,vd->bsv", h, table, preferred_element_type=jnp.float32)
    return shard_act(logits, ("batch", None, "vocab"))


def encode(cfg: ModelConfig, params, enc_frames):
    """Whisper-style encoder over stub frame embeddings (B, Senc, D)."""
    B, Senc, _ = enc_frames.shape
    pos = jnp.arange(Senc)[None, :]
    h = enc_frames + L.sinusoidal_positions(pos, cfg.d_model).astype(enc_frames.dtype)
    enc_pattern = (LayerSpec(kind="attn", ffn="dense"),)

    def body(carry, sb_params):
        h, _ = carry
        hn = L.apply_norm(cfg, sb_params["l0"]["norm"], h)
        h = h + L.attention_plain(cfg, sb_params["l0"]["attn"], hn, causal=False,
                                  rope=False)
        hf = L.apply_norm(cfg, sb_params["l0"]["ffn_norm"], h)
        h = h + L.apply_mlp(cfg, sb_params["l0"]["ffn"], hf)
        return (h, jnp.float32(0.0)), None

    (h, _), _ = lax.scan(_remat(cfg, body), (h, jnp.float32(0.0)),
                         params["enc_blocks"])
    return L.apply_norm(cfg, params["enc_final_norm"], h)


def forward_hidden(cfg: ModelConfig, params, tokens, prefix_embeds=None,
                   enc_frames=None, lens=None):
    """Full-sequence forward up to the final hidden states -> (h, aux).

    ``lens`` (B,) true lengths of right-padded rows, for attention that
    can skip padding (the flash kernel); None: every row is full."""
    h = _embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    B, S, _ = h.shape
    if cfg.pos_type == "sinusoidal":
        h = h + L.sinusoidal_positions(jnp.arange(S)[None, :], cfg.d_model
                                       ).astype(h.dtype)
    h = shard_act(h, ("batch", None, None))
    positions = jnp.arange(S)[None, :]
    enc_out = encode(cfg, params, enc_frames) if cfg.is_encdec else None
    return _run_stack(cfg, params["blocks"], h, positions, enc_out,
                      lens=lens)


def first_logits_select(cfg: ModelConfig, params, tokens, lens, token_ids):
    """Last-position logits for selected vocab ids only -> (B, T).

    The serving fast path for yes/no oracles: same hidden states, same
    final norm, and the same per-row dot products as ``forward`` + a
    last-position gather — only the (B, padded_vocab) float32
    materialization is skipped.  ``token_ids`` is (T,) shared across the
    batch or (B, T) per prompt; ``lens`` (B,) true prompt lengths.
    """
    h, _ = forward_hidden(cfg, params, tokens, lens=lens)
    hl = h[jnp.arange(h.shape[0]), lens - 1]           # (B, D)
    hl = L.apply_norm(cfg, params["final_norm"], hl)
    table = params["lm_head"]["w"] if not cfg.tie_embeddings else params["embed"]["table"]
    rows = table[token_ids]                            # (T, D) or (B, T, D)
    if rows.ndim == 3:
        return jnp.einsum("bd,btd->bt", hl, rows,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bd,td->bt", hl, rows,
                      preferred_element_type=jnp.float32)


def forward(cfg: ModelConfig, params, tokens, prefix_embeds=None,
            enc_frames=None):
    """Full-sequence forward -> (logits (B,S,Vp), aux).

    - ``prefix_embeds`` (B, P, D): VLM stub — prepended to token embeddings;
      total sequence length = P + tokens.shape[1].
    - ``enc_frames`` (B, Senc, D): audio stub for enc-dec models.
    """
    h, aux = forward_hidden(cfg, params, tokens, prefix_embeds, enc_frames)
    return _logits(cfg, params, h), aux


# --------------------------------------------------------------------------
# KV cache + decode
# --------------------------------------------------------------------------


def _ring_len(cfg: ModelConfig, spec: LayerSpec, max_len: int) -> int:
    if spec.window is None:
        return max_len
    return min(spec.window, max_len)


def make_cache(cfg: ModelConfig, batch: int, max_len: int, kv_dtype=None):
    """Zero-initialized decode cache pytree (+ per-layer cross-attn slots)."""
    kv_dtype = kv_dtype or jnp.dtype(cfg.dtype)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    nsb = cfg.n_superblocks
    cache = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.kind == "attn":
            Lr = _ring_len(cfg, spec, max_len)
            entry = {"k": jnp.zeros((nsb, batch, Lr, KV, hd), kv_dtype),
                     "v": jnp.zeros((nsb, batch, Lr, KV, hd), kv_dtype)}
        else:
            entry = {"h": jnp.zeros((nsb, batch, cfg.d_inner, cfg.ssm_state),
                                    jnp.float32),
                     "conv": jnp.zeros((nsb, batch, cfg.ssm_conv - 1,
                                        cfg.d_inner), kv_dtype)}
        if cfg.is_encdec:
            entry["xk"] = jnp.zeros((nsb, batch, cfg.encoder_len, KV, hd), kv_dtype)
            entry["xv"] = jnp.zeros((nsb, batch, cfg.encoder_len, KV, hd), kv_dtype)
        cache[f"l{i}"] = entry
    return cache


def cache_logical_axes(cfg: ModelConfig, long_context: bool = False):
    """Logical axes tree matching make_cache structure."""
    seq_axis = "kv_seq_long" if long_context else "kv_seq"
    axes = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.kind == "attn":
            entry = {"k": (None, "kv_batch", seq_axis, "kv_heads", None),
                     "v": (None, "kv_batch", seq_axis, "kv_heads", None)}
        else:
            entry = {"h": (None, "kv_batch", "inner", None),
                     "conv": (None, "kv_batch", None, "inner")}
        if cfg.is_encdec:
            entry["xk"] = (None, "kv_batch", None, "kv_heads", None)
            entry["xv"] = (None, "kv_batch", None, "kv_heads", None)
        axes[f"l{i}"] = entry
    return axes


def _apply_layer_decode(cfg: ModelConfig, spec: LayerSpec, p, c, h, pos):
    hn = L.apply_norm(cfg, p["norm"], h)
    if spec.kind == "attn":
        out, new_kv = L.attention_decode(
            cfg, p["attn"], hn, {"k": c["k"], "v": c["v"]}, pos,
            window=spec.window)
        c = dict(c, **new_kv)
    else:
        out, new_s = L.mamba_decode(cfg, p["mamba"], hn,
                                    {"h": c["h"], "conv": c["conv"]})
        c = dict(c, **new_s)
    h = h + out
    if "xattn" in p and "xk" in c:
        hx = L.apply_norm(cfg, p["xattn_norm"], h)
        out, _ = L.attention_decode(cfg, p["xattn"], hx, None, pos,
                                    cross_kv={"k": c["xk"], "v": c["xv"]})
        h = h + out
    if spec.ffn == "dense":
        hf = L.apply_norm(cfg, p["ffn_norm"], h)
        h = h + L.apply_mlp(cfg, p["ffn"], hf)
    elif spec.ffn == "moe":
        hf = L.apply_norm(cfg, p["ffn_norm"], h)
        out, _ = L.apply_moe(cfg, p["moe"], hf)
        h = h + out
    return h, c


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One decode step.  tokens (B,) int32; pos (B,) 0-based position.

    Returns (logits (B, Vp), new_cache).
    """
    h = _embed_tokens(cfg, params, tokens[:, None])  # (B,1,D)
    if cfg.pos_type == "sinusoidal":
        h = h + L.sinusoidal_positions(pos[:, None], cfg.d_model).astype(h.dtype)

    def body(h, inp):
        sb_params, sb_cache = inp
        new_sb_cache = {}
        for i, spec in enumerate(cfg.pattern):
            h, new_sb_cache[f"l{i}"] = _apply_layer_decode(
                cfg, spec, sb_params[f"l{i}"], sb_cache[f"l{i}"], h, pos)
        return h, new_sb_cache

    h, new_cache = lax.scan(body, h, (params["blocks"], cache))
    logits = _logits(cfg, params, h)[:, 0]
    return logits, new_cache


# --------------------------------------------------------------------------
# prefill: full-sequence forward that also builds the decode cache
# --------------------------------------------------------------------------


def _project_kv_cache(cfg: ModelConfig, p, hn, positions, ring_len: int):
    """K/V for the whole sequence (post-RoPE), folded into a ring layout."""
    B, S, _ = hn.shape
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = (hn @ p["wk"])
    v = (hn @ p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.pos_type == "rope":
        k = L.apply_rope(k, positions, cfg.rope_theta)
    if ring_len >= S:
        pad = ring_len - S
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return k, v
    # keep last ring_len positions at slot p % ring_len
    kl, vl = k[:, S - ring_len:], v[:, S - ring_len:]
    slots = jnp.arange(S - ring_len, S) % ring_len
    kc = jnp.zeros_like(kl).at[:, slots].set(kl)
    vc = jnp.zeros_like(vl).at[:, slots].set(vl)
    return kc, vc


def prefill(cfg: ModelConfig, params, tokens, prefix_embeds=None,
            enc_frames=None, max_len=None, last_only: bool = False):
    """Forward over a prompt, building the decode cache.

    Returns (logits, cache, next_pos (B,)); logits are (B,S,Vp), or (B,Vp)
    for the last position only when ``last_only`` (production serving never
    needs the full (B,S,V) tensor — see EXPERIMENTS.md §Perf round 1).
    """
    h = _embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    B, S, _ = h.shape
    max_len = max_len or S
    if cfg.pos_type == "sinusoidal":
        h = h + L.sinusoidal_positions(jnp.arange(S)[None, :], cfg.d_model
                                       ).astype(h.dtype)
    positions = jnp.arange(S)[None, :]
    enc_out = encode(cfg, params, enc_frames) if cfg.is_encdec else None

    def body(carry, sb_params):
        h = carry
        sb_cache = {}
        for i, spec in enumerate(cfg.pattern):
            p = sb_params[f"l{i}"]
            entry = {}
            hn = L.apply_norm(cfg, p["norm"], h)
            if spec.kind == "attn":
                ring = _ring_len(cfg, spec, max_len)
                entry["k"], entry["v"] = _project_kv_cache(cfg, p["attn"], hn,
                                                           positions, ring)
                attn = L.attention_apply(cfg, p["attn"], hn, causal=True,
                                         window=spec.window, positions=positions)
                h = h + attn
            else:
                out, (hstate, conv) = L.mamba_scan(cfg, p["mamba"], hn)
                entry["h"], entry["conv"] = hstate, conv
                h = h + out
            if "xattn" in p and enc_out is not None:
                hx = L.apply_norm(cfg, p["xattn_norm"], h)
                h = h + L.attention_plain(cfg, p["xattn"], hx, causal=False,
                                          kv_x=enc_out)
                kx = (enc_out @ p["xattn"]["wk"]).reshape(
                    B, -1, cfg.n_kv_heads, cfg.resolved_head_dim)
                vx = (enc_out @ p["xattn"]["wv"]).reshape(
                    B, -1, cfg.n_kv_heads, cfg.resolved_head_dim)
                entry["xk"], entry["xv"] = kx, vx
            if spec.ffn == "dense":
                hf = L.apply_norm(cfg, p["ffn_norm"], h)
                h = h + L.apply_mlp(cfg, p["ffn"], hf)
            elif spec.ffn == "moe":
                hf = L.apply_norm(cfg, p["ffn_norm"], h)
                out, _ = L.apply_moe(cfg, p["moe"], hf)
                h = h + out
            sb_cache[f"l{i}"] = entry
        return h, sb_cache

    h, cache = lax.scan(_remat(cfg, body), h, params["blocks"])
    # pad ring caches to max_len layout conventions already handled above
    if last_only:
        logits = _logits(cfg, params, h[:, -1:])[:, 0]
    else:
        logits = _logits(cfg, params, h)
    next_pos = jnp.full((B,), S, jnp.int32)
    return logits, cache, next_pos
