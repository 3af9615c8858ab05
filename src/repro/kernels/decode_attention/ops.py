"""jit'd wrapper: Pallas on TPU, jnp reference elsewhere.

``impl`` selects explicitly: "auto" (Pallas on TPU, ref otherwise),
"pallas" (always the kernel — compiled on TPU, interpret mode elsewhere so
the same code path is testable on CPU), or "ref" (always the jnp oracle).
Interpret mode follows the backend alone: on TPU the kernel always
compiles.
"""
from __future__ import annotations

import jax

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref


def decode_attention(q, k, v, lengths, *, impl: str = "auto",
                     block_l: int = 256):
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu):
        return decode_attention_ref(q, k, v, lengths)
    return decode_attention_pallas(q, k, v, lengths, block_l=block_l,
                                   interpret=not on_tpu)
