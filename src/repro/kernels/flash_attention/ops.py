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


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    impl: str = "auto", block_q: int = 128,
                    block_k: int = 128):
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu):
        return flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=not on_tpu)
