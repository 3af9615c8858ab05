"""Batched serving engine: prefill + decode over the model zoo.

Drives the oracle-LLM side of the CSV pipeline: ``first_token_logits``
serves the semantic filter's yes/no decisions; ``generate`` serves the
example apps.  Static-shape bucketed batching keeps compile cache hits
high; per-(batch, bucket) jitted programs are cached.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import tile_counts
from repro.kernels.flash_attention.ops import flash_blocks
from repro.models import layers, lm
from repro.models.config import ModelConfig
from repro.obs.trace import get_tracer
from repro.serving.batcher import BucketBatcher


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 16,
                 pad_id: int = 0):
        self.cfg = cfg
        self.params = params
        self.batcher = BucketBatcher(max_batch=max_batch, pad_id=pad_id)
        self._prefill_cache = {}
        self._decode_fn = None
        self.stats = {"prefill_tokens": 0, "decode_tokens": 0, "batches": 0,
                      "batched_prompts": 0,
                      # mirrored from the batcher so silent prompt-head
                      # loss is visible where serving stats are read
                      "truncated_prompts": 0, "truncated_tokens": 0}

    @property
    def mean_batch_size(self) -> float:
        """Mean prompts per compiled-program invocation — grows toward
        ``max_batch`` when callers (the CSV round executor) submit
        cross-cluster round batches instead of per-cluster trickles."""
        return self.stats["batched_prompts"] / max(1, self.stats["batches"])

    # -------------------------------------------------------------- prefill
    def _prefill_fn(self, L: int, with_cache: bool):
        key = (L, with_cache)
        if key not in self._prefill_cache:
            cfg = self.cfg

            if with_cache:
                def f(params, tokens):
                    return lm.prefill(cfg, params, tokens, max_len=L + 64)
            else:
                def f(params, tokens):
                    logits, _ = lm.forward(cfg, params, tokens)
                    return logits

            self._prefill_cache[key] = jax.jit(f)
        return self._prefill_cache[key]

    def _select_fn(self, L: int, per_prompt: bool):
        key = (L, "select", per_prompt)
        if key not in self._prefill_cache:
            cfg = self.cfg

            def f(params, tokens, lens, token_ids):
                return lm.first_logits_select(cfg, params, tokens, lens,
                                              token_ids)

            self._prefill_cache[key] = jax.jit(f)
        return self._prefill_cache[key]

    def first_token_logits(self, prompts: Sequence[Sequence[int]],
                           token_ids=None) -> np.ndarray:
        """Logits at each prompt's last position.

        Without ``token_ids``: the full (n_prompts, padded_vocab) float32
        matrix.  With ``token_ids`` — (T,) shared across prompts or
        (n_prompts, T) per prompt — only those T logits per prompt come
        back to the host ((n_prompts, T)); the yes/no oracle fast path
        that never materializes the vocab axis.
        """
        if token_ids is not None:
            token_ids = np.asarray(token_ids, np.int32)
        n_tok = (self.cfg.padded_vocab if token_ids is None
                 else token_ids.shape[-1])
        out = np.zeros((len(prompts), n_tok), np.float32)
        tr = get_tracer()
        with tr.work("repro.engine.plan", prompts=len(prompts)):
            batches = self.batcher.plan(prompts)
        for idx, toks, lens in batches:
            path = layers.attention_path(self.cfg, toks.shape[1])
            with tr.span("engine_tick", kind="engine_tick", phase="prefill",
                         bucket_len=int(toks.shape[1]), batch=int(len(idx)),
                         tokens=int(lens.sum()),
                         attn_impl=self.cfg.attn_impl, attn_path=path):
                # launch: host->device copies and the jitted call, which
                # returns once the step is enqueued; fetch: the host
                # blocked until the step's result is back
                if token_ids is None:
                    with tr.work("repro.engine.launch"):
                        res = self._prefill_fn(toks.shape[1], False)(
                            self.params, jnp.asarray(toks))
                    if tr.enabled:   # host accounting while the step runs
                        self._count_attn_tiles(tr, path, toks.shape[1],
                                               np.full(len(idx),
                                                       toks.shape[1]))
                    with tr.work("repro.engine.fetch"):
                        last = np.asarray(res)[np.arange(len(idx)),
                                               lens - 1]
                else:
                    with tr.work("repro.engine.launch"):
                        tids = (token_ids if token_ids.ndim == 1
                                else token_ids[idx])
                        res = self._select_fn(
                            toks.shape[1], token_ids.ndim == 2)(
                                self.params, jnp.asarray(toks),
                                jnp.asarray(lens), jnp.asarray(tids))
                    if tr.enabled:
                        self._count_attn_tiles(tr, path, toks.shape[1], lens)
                    with tr.work("repro.engine.fetch"):
                        last = np.asarray(res)
            out[idx] = last
            self.stats["prefill_tokens"] += int(lens.sum())
            self.stats["batches"] += 1
            self.stats["batched_prompts"] += int(len(idx))
            tr.metrics.inc("engine.prefill_tokens", int(lens.sum()))
            tr.metrics.inc("engine.ticks")
            tr.metrics.observe("engine.batch_size", int(len(idx)),
                               bounds=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        tr.metrics.set_info("kernel.attn_impl", self.cfg.attn_impl)
        tr.metrics.set("engine.bucket_fill", self.batcher.fill_ratio)
        for k in ("truncated_prompts", "truncated_tokens"):
            self.stats[k] = self.batcher.stats[k]
        return out

    def _count_attn_tiles(self, tr, path: str, L: int, lens) -> None:
        """``engine.attn_tiles_run`` / ``engine.attn_tiles_full``: score
        tiles (per head and layer, in the flash kernel's tiling) that this
        step computes, against the full L x L grid of its rows; their ratio
        is the share of plain attention's work the step still does."""
        cfg = self.cfg
        blocks = flash_blocks(cfg.resolved_head_dim, cfg.n_heads,
                              cfg.n_kv_heads)
        run, full = tile_counts(lens, L, block_q=blocks["block_q"],
                                block_k=blocks["block_k"])
        tr.metrics.inc("engine.attn_tiles_run", run if path == "flash"
                       else full)
        tr.metrics.inc("engine.attn_tiles_full", full)

    # --------------------------------------------------------------- decode
    def _decode(self, params, cache, tokens, pos):
        return lm.decode_step(self.cfg, params, cache, tokens, pos)

    def generate(self, prompts: Sequence[List[int]], max_new: int = 16,
                 temperature: float = 0.0, seed: int = 0) -> List[List[int]]:
        """Greedy/temperature decoding; returns generated ids per prompt."""
        if self._decode_fn is None:
            self._decode_fn = jax.jit(self._decode)
        results: List[List[int]] = [[] for _ in prompts]
        key = jax.random.key(seed)
        tr = get_tracer()
        for idx, toks, lens in self.batcher.plan(prompts):
            L = toks.shape[1]
            with tr.span("engine_tick", kind="engine_tick", phase="generate",
                         bucket_len=int(L), batch=int(len(idx)),
                         tokens=int(lens.sum()), max_new=int(max_new),
                         attn_impl=self.cfg.attn_impl):
                logits, cache, _ = self._prefill_fn(L, True)(
                    self.params, jnp.asarray(toks))
                # next_pos per sequence = its true length (cache rows
                # beyond a prompt's length contain pad K/V — masked by
                # per-seq pos)
                pos = jnp.asarray(lens, jnp.int32)
                last = np.asarray(logits)[np.arange(len(idx)), lens - 1]
                cur = jnp.asarray(self._sample(last, temperature, key))
                for step in range(max_new):
                    for r, k in enumerate(idx):
                        results[k].append(int(cur[r]))
                    logits_d, cache = self._decode_fn(self.params, cache,
                                                      cur, pos)
                    pos = pos + 1
                    key, sub = jax.random.split(key)
                    cur = jnp.asarray(self._sample(np.asarray(logits_d),
                                                   temperature, sub))
                    self.stats["decode_tokens"] += len(idx)
            tr.metrics.inc("engine.prefill_tokens", int(lens.sum()))
            tr.metrics.inc("engine.decode_tokens", int(max_new * len(idx)))
            tr.metrics.inc("engine.ticks")
        for k in ("truncated_prompts", "truncated_tokens"):
            self.stats[k] = self.batcher.stats[k]
        return results

    @staticmethod
    def _sample(logits: np.ndarray, temperature: float, key) -> np.ndarray:
        if temperature <= 0:
            return np.argmax(logits, axis=-1).astype(np.int32)
        g = np.asarray(jax.random.gumbel(key, logits.shape))
        return np.argmax(logits / temperature + g, axis=-1).astype(np.int32)
