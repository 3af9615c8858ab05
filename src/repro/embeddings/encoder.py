"""E5-style embedding encoder (paper phase-1 substrate).

Bidirectional transformer + mean pooling over non-pad positions; long
texts are split into chunks, embedded independently, and mean-merged —
exactly the paper's §4.1 long-input handling.  Reuses the model substrate's
attention/MLP layers with causal=False.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokenizer import HashTokenizer
from repro.models import layers as L
from repro.models.config import LayerSpec, ModelConfig
from repro.models.lm import _init_superblock
from repro.obs.trace import get_tracer


def init_encoder_params(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    D = cfg.d_model
    table = (jax.random.normal(k1, (cfg.padded_vocab, D), jnp.float32)
             / math.sqrt(D)).astype(cfg.dtype)
    pattern = (LayerSpec(kind="attn", ffn="dense"),)
    keys = jax.random.split(k2, cfg.n_layers)
    blocks = jax.vmap(lambda k: _init_superblock(k, cfg, pattern, False))(keys)
    return {"embed": {"table": table}, "blocks": blocks,
            "final_norm": L.init_norm(cfg)}


def encoder_forward(cfg: ModelConfig, params, tokens, mask):
    """tokens (B,S) int32, mask (B,S) bool -> pooled embeddings (B, D)."""
    h = params["embed"]["table"][tokens]
    B, S, D = h.shape
    h = h + L.sinusoidal_positions(jnp.arange(S)[None, :], D).astype(h.dtype)

    def body(carry, sb):
        h = carry
        hn = L.apply_norm(cfg, sb["l0"]["norm"], h)
        h = h + L.attention_plain(cfg, sb["l0"]["attn"], hn, causal=False,
                                  rope=False)
        hf = L.apply_norm(cfg, sb["l0"]["ffn_norm"], h)
        h = h + L.apply_mlp(cfg, sb["l0"]["ffn"], hf)
        return h, None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    h = L.apply_norm(cfg, params["final_norm"], h)
    m = mask[..., None].astype(jnp.float32)
    pooled = jnp.sum(h.astype(jnp.float32) * m, axis=1) / jnp.maximum(
        jnp.sum(m, axis=1), 1.0)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


class EmbeddingModel:
    def __init__(self, cfg: ModelConfig, params=None, seed: int = 0,
                 max_len: int = 128, tokenizer: HashTokenizer = None):
        self.cfg = cfg
        self.params = params if params is not None else init_encoder_params(
            cfg, jax.random.key(seed))
        self.max_len = max_len
        self.tok = tokenizer or HashTokenizer(cfg.vocab_size)
        self._fn = jax.jit(lambda p, t, m: encoder_forward(cfg, p, t, m))

    def encode(self, texts: Sequence[str], batch: int = 64) -> np.ndarray:
        """Chunked embedding: mean of per-chunk embeddings (paper §4.1)."""
        tr = get_tracer()
        chunks: List[List[int]] = []
        owner: List[int] = []
        with tr.work("repro.encoder.tokenize", texts=len(texts)):
            for i, t in enumerate(texts):
                ids = self.tok.encode(t)
                for s in range(0, max(1, len(ids)), self.max_len):
                    chunks.append(ids[s:s + self.max_len])
                    owner.append(i)
        out = np.zeros((len(texts), self.cfg.d_model), np.float32)
        counts = np.zeros(len(texts), np.float32)
        for s in range(0, len(chunks), batch):
            with tr.work("repro.encoder.batch"):
                group = chunks[s:s + batch]
                L_max = self.max_len
                toks = np.zeros((len(group), L_max), np.int32)
                mask = np.zeros((len(group), L_max), bool)
                for r, c in enumerate(group):
                    toks[r, :len(c)] = c
                    mask[r, :len(c)] = True
                emb = np.asarray(self._fn(self.params, jnp.asarray(toks),
                                          jnp.asarray(mask)))
                for r, o in enumerate(owner[s:s + batch]):
                    out[o] += emb[r]
                    counts[o] += 1
        out /= np.maximum(counts[:, None], 1.0)
        norms = np.linalg.norm(out, axis=1, keepdims=True)
        return out / np.maximum(norms, 1e-9)


def encode_texts(texts, cfg=None, seed=0, max_len=128):
    from repro.configs import smoke_config
    cfg = cfg or smoke_config("e5-large")
    return EmbeddingModel(cfg, seed=seed, max_len=max_len).encode(texts)
