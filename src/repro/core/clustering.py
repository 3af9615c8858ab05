"""K-means over tuple embeddings (paper phase 1, query-agnostic, offline).

Pure JAX: kmeans++ seeding, Lloyd iterations under lax.while_loop with an
on-device convergence test, and a mini-batch update path for incremental
table maintenance (paper §3.1 update handling).  The assignment step (the
compute hot-spot: N x K pairwise distances + argmin) goes through
``repro.kernels.kmeans.ops``, which dispatches to the Pallas TPU kernel on
TPU and the jnp reference elsewhere.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.kmeans.ops import assign_clusters


def _plusplus_init(key, x, k: int):
    """kmeans++ seeding (host loop over k; k is small)."""
    n = x.shape[0]
    keys = jax.random.split(key, k)
    first = jax.random.randint(keys[0], (), 0, n)
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    d2 = jnp.sum(jnp.square(x - cents[0]), axis=-1)
    for i in range(1, k):
        probs = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
        idx = jax.random.choice(keys[i], n, p=probs)
        cents = cents.at[i].set(x[idx])
        d2 = jnp.minimum(d2, jnp.sum(jnp.square(x - cents[i]), axis=-1))
    return cents


@partial(jax.jit, static_argnames=("k", "max_iters"))
def kmeans(key, x, k: int, max_iters: int = 50, tol: float = 1e-4):
    """Lloyd's algorithm.  x (N,D) -> (centroids (k,D), assign (N,), inertia).

    Empty clusters are re-seeded to the point farthest from its centroid.
    """
    n, d = x.shape
    cents0 = _plusplus_init(key, x, k)

    def step(state):
        cents, _, it, _ = state
        assign, dmin = assign_clusters(x, cents)
        counts = jnp.zeros((k,), x.dtype).at[assign].add(1.0)
        sums = jnp.zeros((k, d), x.dtype).at[assign].add(x)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0),
                        cents)
        # re-seed empties with the worst-fit point
        worst = jnp.argmax(dmin)
        new = jnp.where((counts[:, None] == 0), x[worst][None, :], new)
        shift = jnp.max(jnp.sum(jnp.square(new - cents), axis=-1))
        return new, assign, it + 1, shift

    def cond(state):
        _, _, it, shift = state
        return jnp.logical_and(it < max_iters, shift > tol)

    state = (cents0, jnp.zeros((n,), jnp.int32), jnp.int32(0), jnp.float32(jnp.inf))
    cents, _, _, _ = lax.while_loop(cond, step, state)
    assign, dmin = assign_clusters(x, cents)
    inertia = jnp.sum(dmin)
    return cents, assign, inertia


@jax.jit
def kmeans_predict(x, cents):
    assign, _ = assign_clusters(x, cents)
    return assign


def assign_to_nearest(embeddings, centroids) -> np.ndarray:
    """Host-side incremental assignment (paper §3.1 update handling).

    New or updated rows join the nearest *existing* centroid — no re-fit —
    so a table ``append``/``update`` patches the precluster cache instead of
    invalidating it.  Centroid drift accumulates across patches; callers
    that care can force a fresh ``kmeans`` fit under a new seed.
    """
    emb = jnp.asarray(np.asarray(embeddings, dtype=np.float32))
    return np.asarray(kmeans_predict(emb, jnp.asarray(centroids)))


@jax.jit
def minibatch_kmeans_update(cents, counts, batch):
    """Mini-batch K-means (Sculley'10) single step for incremental updates.

    counts (k,): running per-cluster sample counts.  Returns (cents, counts).
    """
    assign, _ = assign_clusters(batch, cents)
    ones = jnp.ones((batch.shape[0],), cents.dtype)
    counts = counts.at[assign].add(ones)
    lr = 1.0 / jnp.maximum(counts[assign], 1.0)  # per-sample rate
    # sequential-equivalent batched update: move each centroid toward the
    # mean of its new points scaled by accumulated count
    k = cents.shape[0]
    sums = jnp.zeros_like(cents).at[assign].add(batch * lr[:, None])
    hits = jnp.zeros((k,), cents.dtype).at[assign].add(lr)
    cents = cents * (1 - hits[:, None]) + sums + cents * 0.0
    return cents, counts


def distributed_kmeans_step(x_local, cents, mesh_axis: str = "data"):
    """One Lloyd step under ``jax.shard_map``: local partial sums + psum.

    Call inside ``jax.shard_map`` (mesh entered with ``jax.set_mesh``) with
    x sharded over ``mesh_axis``; centroids are replicated.  Returns
    updated centroids (replicated).
    """
    k, d = cents.shape
    assign, _ = assign_clusters(x_local, cents)
    sums = jnp.zeros((k, d), x_local.dtype).at[assign].add(x_local)
    counts = jnp.zeros((k,), x_local.dtype).at[assign].add(1.0)
    sums = lax.psum(sums, mesh_axis)
    counts = lax.psum(counts, mesh_axis)
    return jnp.where(counts[:, None] > 0,
                     sums / jnp.maximum(counts[:, None], 1.0), cents)
