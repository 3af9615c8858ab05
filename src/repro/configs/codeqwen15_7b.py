"""codeqwen1.5-7b [dense] — Qwen2 arch, QKV bias, GQA 32/4, untied head [hf:Qwen/CodeQwen1.5-7B; hf].

Published config.json (https://huggingface.co/Qwen/CodeQwen1.5-7B): 32
layers, hidden 4096, 32 query heads over 4 key/value heads (head_dim 128,
8 query heads per KV head), SwiGLU intermediate 13440, vocab 92416,
``rope_theta`` 1e6, ``rms_norm_eps`` 1e-5, bias on q/k/v,
``tie_word_embeddings`` false, bfloat16: 7.25B parameters.  The chip
benchmark builds its ``ModelConfig`` from
``benchmarks/chip/configs/codeqwen1.5-7b.json``; ``tests/test_smoke_archs``
keeps the two in step.
"""
from repro.models.config import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="codeqwen1.5-7b",
    family="dense",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=4,
    d_ff=13440,
    vocab_size=92416,
    pattern=(LayerSpec(kind="attn", ffn="dense"),),
    qkv_bias=True,
    rope_theta=1e6,
    norm_eps=1e-5,
    tie_embeddings=False,
    source="[hf:Qwen/CodeQwen1.5-7B; hf]",
)

# GQA (4 query heads per KV head) with an untied head, as the full model
SMOKE = CONFIG.replace(
    n_layers=2, d_model=64, n_heads=8, n_kv_heads=2, d_ff=128, vocab_size=512,
    dtype="float32", attn_chunk_q=16, attn_chunk_kv=16,
)
