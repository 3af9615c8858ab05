#!/usr/bin/env python3
"""Smoke test of the served semantic-filter path on one TPU chip.

    python3 chip_smoke.py

Runs everything in this one process (a child that needs the chip would
find it held) and starts no other process.  Phases, in order:

1. device  — prints the platform, device kind and count; exits non-zero
   unless JAX found a TPU.  There is no CPU mode.
2. kernels — the Pallas kernels (k-means assignment, single-cluster and
   segmented SimVote, flash attention, flash decode) at serving shapes, each
   compared on the chip with its ``ref.py`` under highest matmul precision.
3. served  — ``serve_concurrent``: K=4 predicates through ``FilterService``
   -> ``QueryScheduler`` -> packed ``ServingEngine`` waves -> vote, over the
   synthetic ``imdb_review`` table (n=4000), with the ``qwen1.5-0.5b``
   oracle and the ``e5-large`` encoder at their published widths (random
   weights, seed 0) and a fresh session state directory.
4. flash   — one predicate's yes/no logits again through an engine with
   ``attn_impl="flash"`` (the Pallas prefill kernel), against the served
   ``auto`` path on the same prompts.

Any failed check raises, so the process exits non-zero.  The wall times
printed are cold (compilation included): they are not a speed metric.
The last line of stdout is the JSON result and nothing else.
"""
from __future__ import annotations

import json
import pathlib
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.core.oracle import ModelOracle  # noqa: E402
from repro.kernels.decode_attention.kernel import decode_attention_pallas  # noqa: E402
from repro.kernels.decode_attention.ref import decode_attention_ref  # noqa: E402
from repro.kernels.flash_attention.kernel import flash_attention_pallas  # noqa: E402
from repro.kernels.flash_attention.ref import flash_attention_ref  # noqa: E402
from repro.kernels.kmeans.kernel import assign_clusters_pallas  # noqa: E402
from repro.kernels.kmeans.ref import assign_clusters_ref  # noqa: E402
from repro.kernels.simvote.kernel import (  # noqa: E402
    simvote_scores_pallas, simvote_scores_segmented_pallas)
from repro.kernels.simvote.ref import (  # noqa: E402
    simvote_scores_ref, simvote_scores_segmented_ref)
from repro.launch.runtime import enable_compile_cache, require_tpu  # noqa: E402
from repro.launch.serve import (SERVICE_PREDICATES, build_stack,  # noqa: E402
                                serve_concurrent)
from repro.serving import ServingEngine  # noqa: E402

# Kernel-vs-ref tolerances (max abs error).  f32 kernels on unit-norm
# embeddings: distances and scores are O(1); 1e-3 would still admit one
# bf16 pass of the MXU.  bf16 attention: one bf16 rounding of O(1) outputs.
KMEANS_DMIN_TOL = 1e-3
KMEANS_AGREE_MIN = 0.999       # share of rows with the ref's assignment
SIMVOTE_TOL = 5e-3
ATTN_BF16_TOL = 2e-2
# flash vs auto yes/no logits of the 24-layer bf16 backbone, relative to the
# logit scale max(1, max|logit|): the two paths round attention differently
# in every layer.  A depth-24, width-128 bf16 rehearsal differed by 2.9%.
FLASH_LOGIT_REL_TOL = 0.1

N_ROWS, N_PREDICATES = 4000, 4


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def _clustered(key, n, d, k, noise=0.3):
    """n unit rows around k unit centers (embedding-like geometry)."""
    kc, ka, kn = jax.random.split(key, 3)
    cents = _unit(jax.random.normal(kc, (k, d), jnp.float32))
    topic = jax.random.randint(ka, (n,), 0, k)
    noise_ = noise * jax.random.normal(kn, (n, d), jnp.float32) / np.sqrt(d)
    return _unit(cents[topic] + noise_), cents


def _check(name, err, tol, extra=""):
    print(f"[chip_smoke] kernel {name}: max abs err {err:.3g} "
          f"(tol {tol:g}){extra}", flush=True)
    if not err <= tol:
        raise AssertionError(f"{name}: max abs err {err} > tol {tol}")


def kernel_phase(seed: int = 0):
    """Each Pallas kernel on the chip against its reference."""
    keys = jax.random.split(jax.random.key(seed), 8)
    highest = jax.default_matmul_precision("highest")

    # k-means assignment: 4000 x 1024 embeddings, 8 centroids
    x, cents = _clustered(keys[0], N_ROWS, 1024, 8)
    a_k, d_k = assign_clusters_pallas(x, cents)
    with highest:
        a_r, d_r = assign_clusters_ref(x, cents)
    agree = float(np.mean(np.asarray(a_k) == np.asarray(a_r)))
    _check("kmeans_assign", float(jnp.max(jnp.abs(d_k - d_r))),
           KMEANS_DMIN_TOL, f", assignment agreement {agree:.5f}")
    if agree < KMEANS_AGREE_MIN:
        raise AssertionError(f"kmeans_assign: agreement {agree} "
                             f"< {KMEANS_AGREE_MIN}")

    # single-cluster SimVote: 4000 unsampled x 300 sampled, D=1024
    xs, _ = _clustered(keys[1], N_ROWS + 300, 1024, 4)
    y = (jax.random.uniform(keys[2], (300,)) < 0.5).astype(jnp.float32)
    s_k = simvote_scores_pallas(xs[:N_ROWS], xs[N_ROWS:], y, 0.5)
    with highest:
        s_r = simvote_scores_ref(xs[:N_ROWS], xs[N_ROWS:], y, 0.5)
    _check("simvote", float(jnp.max(jnp.abs(s_k - s_r))), SIMVOTE_TOL)

    # segmented SimVote: 4 ragged clusters in one launch
    counts = np.array([1400, 1100, 900, 600])
    ms = np.array([300, 120, 77, 25])
    s_pad = np.zeros((4, 300, 1024), np.float32)
    y_pad = -np.ones((4, 300), np.float32)
    rng = np.random.default_rng(seed)
    xc, _ = _clustered(keys[3], int(counts.sum() + ms.sum()), 1024, 4)
    xc = np.asarray(xc)
    x_rows, off = [], 0
    for c in range(4):
        x_rows.append(xc[off:off + counts[c]])
        off += counts[c]
        s_pad[c, :ms[c]] = xc[off:off + ms[c]]
        y_pad[c, :ms[c]] = rng.random(ms[c]) < 0.5
        off += ms[c]
    taus = np.array([0.4, 0.5, 0.7, 1.0])
    x_all = jnp.asarray(np.concatenate(x_rows))
    g_k = simvote_scores_segmented_pallas(x_all, counts, jnp.asarray(s_pad),
                                          jnp.asarray(y_pad), taus)
    with highest:
        g_r = simvote_scores_segmented_ref(x_all, counts, jnp.asarray(s_pad),
                                           jnp.asarray(y_pad), taus)
    _check("simvote_segmented", float(jnp.max(jnp.abs(g_k - g_r))),
           SIMVOTE_TOL)

    # flash attention (prefill): the oracle's heads at a 256-token bucket
    q, k, v = (jax.random.normal(kk, (64, 16, 256, 64), jnp.bfloat16)
               for kk in jax.random.split(keys[4], 3))
    o_k = flash_attention_pallas(q, k, v, causal=True)
    with highest:
        o_r = flash_attention_ref(q, k, v, causal=True)
    _check("flash_attention", float(jnp.max(jnp.abs(
        o_k.astype(jnp.float32) - o_r.astype(jnp.float32)))), ATTN_BF16_TOL)

    # flash decode: one new token against a ragged 512-slot cache
    q1 = jax.random.normal(keys[5], (64, 16, 64), jnp.bfloat16)
    kc, vc = (jax.random.normal(kk, (64, 16, 512, 64), jnp.bfloat16)
              for kk in jax.random.split(keys[6], 2))
    lens = jax.random.randint(keys[7], (64,), 1, 513)
    d_k = decode_attention_pallas(q1, kc, vc, lens)
    with highest:
        d_r = decode_attention_ref(q1, kc, vc, lens)
    _check("decode_attention", float(jnp.max(jnp.abs(
        d_k.astype(jnp.float32) - d_r.astype(jnp.float32)))), ATTN_BF16_TOL)


def served_phase(smoke: bool = False, n: int = N_ROWS):
    """K predicates through the concurrent service; returns the stack.

    ``smoke``/``n`` shrink the stack for a CPU rehearsal of this phase.
    """
    k = N_PREDICATES
    t0 = time.perf_counter()
    engine, tok, ds, embeddings = build_stack("qwen1.5-0.5b", smoke, n)
    print(f"[chip_smoke] built {engine.cfg.name} engine ({engine.cfg.n_layers}"
          f" layers, d_model {engine.cfg.d_model}) and embedded {n} rows "
          f"(d={embeddings.shape[1]}) in {time.perf_counter() - t0:.1f}s "
          "(cold, includes compilation)", flush=True)
    t1 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_state_") as state:
        _, results = serve_concurrent(engine, tok, ds, embeddings, k, state)
    wall = time.perf_counter() - t1
    for i, r in enumerate(results):
        if r.n_llm_calls <= 0:
            raise AssertionError(f"p{i} made no LLM calls")
    if engine.stats["prefill_tokens"] <= 0:
        raise AssertionError("engine prefilled no tokens")
    print(f"[chip_smoke] served {k} predicates x {n} rows: pass counts "
          f"{[int(r.mask.sum()) for r in results]}, LLM calls "
          f"{[int(r.n_llm_calls) for r in results]}, engine batches "
          f"{engine.stats['batches']}, prefill tokens "
          f"{engine.stats['prefill_tokens']}, bucket fill "
          f"{engine.batcher.fill_ratio:.3f}; wall {wall:.1f}s (cold, "
          "includes compilation; not a metric)", flush=True)
    return engine, tok, ds


def flash_phase(engine, tok, ds):
    """Same prompts, same weights: attn_impl="flash" vs the served path."""
    n_prompts = 128
    oracle = ModelOracle(engine, tok, SERVICE_PREDICATES[0], ds.texts)
    ids = np.arange(n_prompts)
    prompts, tids = oracle.pack_prompts(ids), oracle.pack_token_ids(n_prompts)
    flash = ServingEngine(engine.cfg.replace(attn_impl="flash"),
                          engine.params, max_batch=engine.batcher.max_batch)
    ref = engine.first_token_logits(prompts, token_ids=tids)
    got = flash.first_token_logits(prompts, token_ids=tids)
    if not (np.isfinite(ref).all() and np.isfinite(got).all()):
        raise AssertionError("non-finite yes/no logits")
    scale = max(1.0, float(np.abs(ref).max()))
    err = float(np.abs(got - ref).max())
    agree = float(np.mean((got[:, 0] > got[:, 1]) == (ref[:, 0] > ref[:, 1])))
    print(f"[chip_smoke] flash vs {engine.cfg.attn_impl}: {n_prompts} prompts, "
          f"max abs logit diff {err:.4g} (scale {scale:.3g}, tol "
          f"{FLASH_LOGIT_REL_TOL:g} x scale), decision agreement "
          f"{agree:.4f}", flush=True)
    if not err <= FLASH_LOGIT_REL_TOL * scale:
        raise AssertionError(f"flash logits differ by {err} > "
                             f"{FLASH_LOGIT_REL_TOL} x {scale}")


def main():
    dev = require_tpu("chip_smoke", allow_cpu=False)
    n_dev = len(jax.devices())
    print(f"[chip_smoke] platform {dev.platform}, kind {dev.device_kind!r}, "
          f"{n_dev} device(s)", flush=True)
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    kernel_phase()
    print(f"[chip_smoke] kernel phase done in {time.perf_counter() - t0:.1f}s"
          " (cold, includes compilation)", flush=True)
    engine, tok, ds = served_phase()
    flash_phase(engine, tok, ds)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": n_dev}}))


if __name__ == "__main__":
    main()
