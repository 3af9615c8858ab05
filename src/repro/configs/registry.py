"""Central registry gluing per-arch config modules to the launcher."""
from __future__ import annotations

import importlib
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, SHAPES, ShapeCell

_ARCH_MODULES = [
    "falcon_mamba_7b",
    "mixtral_8x22b",
    "dbrx_132b",
    "internvl2_26b",
    "gemma3_12b",
    "stablelm_12b",
    "codeqwen15_7b",
    "qwen15_05b",
    "jamba_v01_52b",
    "whisper_base",
    # the paper's own backbones (oracle LLM + proxy + embedder)
    "llama31_8b",
    "llama32_3b_proxy",
    "e5_encoder",
]

ARCHS: Dict[str, "object"] = {}
for m in _ARCH_MODULES:
    mod = importlib.import_module(f"repro.configs.{m}")
    ARCHS[mod.CONFIG.name] = mod


def list_archs():
    return list(ARCHS.keys())


def get_config(name: str) -> ModelConfig:
    return ARCHS[name].CONFIG


def smoke_config(name: str) -> ModelConfig:
    return ARCHS[name].SMOKE


# ---------------------------------------------------------------------------
# long-context applicability (see DESIGN.md §5)
# ---------------------------------------------------------------------------

LONG_CONTEXT_OK = {
    "falcon-mamba-7b": "O(1) SSM state",
    "jamba-v0.1-52b": "hybrid: 4/32 attention layers, rest O(1) Mamba state",
    "mixtral-8x22b": "SWA: ring KV bounded by window=4096",
    "gemma3-12b": "5:1 local(1024-ring):global; 8 global layers keep full KV "
                  "(sharded); beyond its 128k design point — boundary case",
}

_LONG_SKIP = {
    "dbrx-132b": "pure full attention: unbounded 500k KV on all 40 layers",
    "internvl2-26b": "pure full attention on all 48 layers",
    "stablelm-12b": "pure full attention on all 40 layers",
    "codeqwen1.5-7b": "pure full attention (GQA 32/4) on all 32 layers",
    "qwen1.5-0.5b": "pure full attention (MHA kv=16) on all 24 layers",
    "whisper-base": "enc-dec with 448-token decoder design limit",
}


def long_context_skip_reason(name: str):
    return _LONG_SKIP.get(name)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeCell) -> dict:
    """Abstract inputs for the step function selected by shape.kind.

    train/prefill: token batch (+ modality stubs).  decode: one new token per
    sequence + a KV cache covering shape.seq_len.
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    dt = jnp.dtype(cfg.dtype)

    if shape.kind in ("train", "prefill"):
        P = cfg.num_prefix_embeds
        spec = {"tokens": sds((B, S - P), i32)}
        if shape.kind == "train":
            spec["targets"] = sds((B, S - P), i32)
        if P:
            spec["prefix_embeds"] = sds((B, P, cfg.d_model), dt)
        if cfg.is_encdec:
            spec["enc_frames"] = sds((B, cfg.encoder_len, cfg.d_model), dt)
        return spec

    # decode: 1 new token against a cache of S
    from repro.models import lm
    cache = jax.eval_shape(lambda: lm.make_cache(cfg, B, S))
    return {
        "tokens": sds((B,), i32),
        "pos": sds((B,), i32),
        "cache": cache,
    }
