"""Pure-jnp oracle: causal (optionally sliding-window) GQA attention."""
from __future__ import annotations

import math

import jax.numpy as jnp
import jax


def flash_attention_ref(q, k, v, lens=None, *, causal: bool = True,
                        window=None):
    """q (B,H,Sq,hd); k/v (B,KV,Sk,hd); H % KV == 0 -> out (B,H,Sq,hd).

    ``lens`` (B,): keys at or past a row's length are masked and query rows
    at or past it come out zero, as in the kernel."""
    B, H, Sq, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qf = q.reshape(B, KV, G, Sq, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bcgqh,bckh->bcgqk", qf, kf) / math.sqrt(hd)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    m = jnp.ones((Sq, k.shape[2]), bool)
    if causal:
        m = kpos <= qpos
        if window is not None:
            m &= kpos > qpos - window
    if lens is not None:
        m = m & (kpos < lens[:, None, None, None, None])
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bcgqk,bckh->bcgqh", p, vf)
    if lens is not None:
        out = jnp.where(qpos < lens[:, None, None, None, None], out, 0.0)
    return out.reshape(B, H, Sq, hd).astype(q.dtype)
