"""Pallas TPU kernels for the paper's compute hot-spots.

Four kernels, each a package with kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd wrapper with platform dispatch) and ref.py (pure-jnp oracle):

- kmeans:            tiled pairwise ||x-c||^2 + fused argmin (CSV phase 1)
- simvote:           streaming similarity-weighted vote (Algorithm 3) -- the
                     N x M similarity matrix never hits HBM
- flash_attention:   causal/SWA GQA prefill attention (serving the oracle LLM)
- decode_attention:  single-token flash-decoding over a KV cache

On TPU the ops always run the compiled kernel; on other backends they fall
back to the jnp reference (or, asked for "pallas", interpret mode).  Kernels
are validated against refs in interpret mode (tests/test_kernels.py) and
compiled for a described TPU v5e at serving shapes
(tests/test_tpu_compile.py).
"""
