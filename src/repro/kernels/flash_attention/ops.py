"""jit'd wrapper: Pallas on TPU, jnp reference elsewhere.

``impl`` selects explicitly: "auto" (Pallas on TPU, ref otherwise),
"pallas" (always the kernel — compiled on TPU, interpret mode elsewhere so
the same code path is testable on CPU), or "ref" (always the jnp oracle).
Interpret mode follows the backend alone: on TPU the kernel always
compiles.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, lens=None, *, causal: bool = True,
                    window=None, impl: str = "auto", block_q: int = 128,
                    block_k: int = 128, block_h=None):
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu):
        return flash_attention_ref(q, k, v, lens, causal=causal,
                                   window=window)
    return flash_attention_pallas(q, k, v, lens, causal=causal,
                                  window=window, block_q=block_q,
                                  block_k=block_k, block_h=block_h,
                                  interpret=not on_tpu)


# By head_dim, from the per-bucket timing of the kernel against plain
# attention on a v5e (scripts/attn_bucket_timing.py, PERF.md section 5):
# block_q, block_k, the most query-head lanes a step takes (block_h *
# head_dim), and the shortest causal prefill at which the kernel beat plain
# attention.  A head_dim not here was never timed.
_TILING = {64: (256, 256, 1024, 512), 128: (128, 128, 4096, 256)}


def flash_min_seq(head_dim: int):
    """The shortest sequence for which ``attn_impl="auto"`` takes the
    kernel at this head_dim; None (never) for a head_dim not timed."""
    return _TILING[head_dim][3] if head_dim in _TILING else None


def flash_blocks(head_dim: int, n_heads: int, n_kv_heads: int) -> dict:
    """The kernel's prefill tiling (the kernel clips block_q and block_k
    to the sequence): block_h is the most query heads, whole KV groups
    dividing ``n_heads``, within the head_dim's lane budget.  A head_dim
    not timed (reached only by an explicit ``attn_impl="flash"``) keeps the
    kernel's 128 x 128 tiles, one KV group a step."""
    group = n_heads // n_kv_heads
    if head_dim not in _TILING:
        return {"block_q": 128, "block_k": 128, "block_h": group}
    block_q, block_k, lanes, _ = _TILING[head_dim]
    block_h = group
    while n_heads % (2 * block_h) == 0 and 2 * block_h * head_dim <= lanes:
        block_h *= 2
    return {"block_q": block_q, "block_k": block_k, "block_h": block_h}
