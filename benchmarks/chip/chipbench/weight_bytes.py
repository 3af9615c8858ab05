"""Bytes of weights that each run of the oracle's prefill step must read
from device memory, counted from the configuration.

A run reads every layer's weights and the final norm at least once: the
matrices and the q/k/v biases in the configured dtype, the norm scales in
float32, as ``weights.py`` makes them and the program keeps them.  The
embedding rows and the head's two selected rows, the activations and the
attention scores are left out, so the count is a lower bound on a run's
traffic: a share of the memory bandwidth computed from it reads low and
never high.
"""
from __future__ import annotations

import jax.numpy as jnp

NORM_BYTES = 4  # float32 norm scales


def layer_stack_bytes(c: dict) -> int:
    """Bytes of the layer stack and the final norm of a Qwen2-architecture
    configuration file (no embedding, no head)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    w = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    if c.get("attention_bias", False):
        w += h * hd + 2 * kv * hd
    itemsize = jnp.dtype(c["torch_dtype"]).itemsize
    layer = w * itemsize + 2 * d * NORM_BYTES     # + the two RMSNorm scales
    return c["num_hidden_layers"] * layer + d * NORM_BYTES  # + the final norm
