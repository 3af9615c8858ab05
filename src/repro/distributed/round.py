"""Sharded CSV rounds: partition each round across mesh hosts.

``run_sharded_executor`` is the ``cfg.shards > 1`` execution path of
``repro.core.csv_filter.semantic_filter`` (same signature as the
single-host ``_run_round_executor``).  Each round:

1. **plan** — the round plan (sample draws) is computed once, replicated:
   every shard sees the identical plan because the driver RNG is
   deterministic and sampling happens before partitioning.
2. **shard** — the round's clusters are partitioned into ``cfg.shards``
   *contiguous* slices, balanced by sample count (``shard_clusters``).
   Contiguity in cluster order is what makes sharding invisible to the
   oracle: concatenating the shard batches in shard order reproduces the
   single-host cross-cluster batch byte for byte.
3. **oracle** — every shard's sample batch is dispatched through ONE
   shared strict-FIFO ``AsyncOracleDispatcher`` lane in shard order, so
   shard s+1's oracle prefill overlaps shard s's voting while the flip
   stream and memo commit order stay identical to single-host.
4. **vote** — each shard votes its own clusters (one segmented device
   dispatch per shard) and buffers its outputs locally.
5. **all-gather** — shard outputs are merged in shard order (== round
   cluster order) into the replicated result/decided arrays.  This is the
   collective point: on a real mesh this merge is an all-gather of
   ``(sample labels, vote outcomes)`` per shard; here shards share memory
   so the gather is a deterministic ordered write-back.
6. **partition** — the shared ``_recluster_or_fallback`` tail runs on the
   gathered state, replicated, so every shard derives the identical next
   queue.

Bit-identity contract (asserted in tests/test_distributed_round.py):
masks, oracle call counts, cluster logs, and memo state equal the
``shards=1`` run on the same seed.  Only the per-invocation batch sizes
differ — one batch per shard instead of one per wave.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.csv_filter import (RoundResult, _recluster_or_fallback,
                                   _vote_wave, plan_round)
from repro.core.oracle import AsyncOracleDispatcher, SyncOracleDispatcher
from repro.obs.trace import get_tracer
from repro.utils.timing import monotonic


def shard_clusters(clusters: list, n_shards: int) -> list:
    """Contiguous, sample-count-balanced partition of a round's clusters.

    Contiguous slices (never an interleave) so that concatenating shard
    batches in shard order equals the single-host concatenation — the
    bit-identity contract depends on this.  Balanced on ``n_sample``
    because oracle cost, not cluster size, is what each shard pays.
    """
    n_shards = max(1, min(int(n_shards), len(clusters)))
    if n_shards == 1:
        return [list(clusters)]
    weights = np.array([cp.n_sample for cp in clusters], dtype=np.float64)
    cum = np.cumsum(weights)
    total = float(cum[-1])
    bounds = [0]
    for s in range(1, n_shards):
        cut = int(np.searchsorted(cum, total * s / n_shards, side="left")) + 1
        cut = max(bounds[-1], min(cut, len(clusters)))
        bounds.append(cut)
    bounds.append(len(clusters))
    shards = [list(clusters[bounds[s]:bounds[s + 1]])
              for s in range(n_shards)]
    return [s for s in shards if s]


@dataclasses.dataclass
class ShardRoundOutput:
    """One shard's buffered round output, merged at the all-gather point."""
    shard: int
    clusters: list           # this shard's ClusterPlans, in round order
    labels_by_cluster: list  # oracle labels, parallel to ``clusters``
    votes: dict              # local cluster index -> VoteResult
    batch: int               # oracle batch size this shard submitted


def run_sharded_executor(emb, oracle, cfg, rng, xi, result, decided,
                         cluster_log, round_log, queue):
    """Drop-in for ``_run_round_executor`` with cluster-sharded rounds."""
    tr = get_tracer()
    lb, ub = cfg.lb, cfg.ub_
    n_voted = n_fallback = 0
    rounds_used = 0
    recluster_time = 0.0
    depth = 0
    while queue and depth <= cfg.max_recluster:
        with tr.span("round", kind="round", depth=depth,
                     n_clusters=len(queue), executor="round",
                     shards=int(cfg.shards)) as rsp:
            t_round = monotonic()
            with tr.work("repro.csv.plan", kind="plan"):
                plan = plan_round(queue, rng, xi, cfg, depth)
            shards = shard_clusters(plan.clusters, cfg.shards)

            dispatcher = (AsyncOracleDispatcher(oracle) if len(shards) > 1
                          else SyncOracleDispatcher(oracle))
            handles = []
            outputs = []
            try:
                for s, shard in enumerate(shards):
                    with tr.span("oracle", kind="oracle", shard=s,
                                 n_clusters=len(shard)) as osp:
                        if s == 0:
                            # submit inside the span to keep submission
                            # order submit(0), submit(1), result(0): the
                            # shared FIFO lane evaluates shard batches in
                            # shard order, so the flip stream and memo
                            # commits match the single-host concatenation
                            handles.append(dispatcher.submit(
                                np.concatenate([cp.sample_ids
                                                for cp in shards[0]])))
                        if s + 1 < len(shards):
                            # overlap: the next shard's oracle prefill is
                            # in flight while this shard votes
                            handles.append(dispatcher.submit(
                                np.concatenate([cp.sample_ids
                                                for cp in shards[s + 1]])))
                        flat_labels = handles[s].result()
                        osp.set(batch=int(len(flat_labels)))
                    offsets = np.cumsum([cp.n_sample for cp in shard])[:-1]
                    labels_by_cluster = np.split(flat_labels, offsets)
                    with tr.work("repro.csv.vote", kind="vote", shard=s,
                                 n_clusters=len(shard)):
                        votes = _vote_wave(shard, labels_by_cluster, emb,
                                           cfg, lb, ub)
                    outputs.append(ShardRoundOutput(
                        shard=s, clusters=shard,
                        labels_by_cluster=labels_by_cluster, votes=votes,
                        batch=int(len(flat_labels))))
            finally:
                dispatcher.close()

            # ---- all-gather: merge every shard's sample labels and vote
            # outcomes in shard order (== round cluster order) before the
            # replicated partition step sees any of them ----
            undetermined = []
            round_voted = 0
            with tr.span("gather", kind="gather", depth=depth,
                         shards=len(outputs)):
                for out in outputs:
                    for i, cp in enumerate(out.clusters):
                        labels = out.labels_by_cluster[i]
                        result[cp.sample_ids] = labels
                        decided[cp.sample_ids] = True
                        if len(cp.rest_ids) == 0:
                            cluster_log.append({
                                "size": cp.size, "sampled": cp.n_sample,
                                "score": float(np.mean(labels)),
                                "depth": depth, "outcome": "exhausted"})
                            continue
                        vr = out.votes[i]
                        result[cp.rest_ids[vr.decided_true]] = True
                        decided[cp.rest_ids[vr.decided_true]] = True
                        result[cp.rest_ids[vr.decided_false]] = False
                        decided[cp.rest_ids[vr.decided_false]] = True
                        voted = (len(vr.decided_true)
                                 + len(vr.decided_false))
                        n_voted += voted
                        round_voted += voted
                        if len(vr.undetermined):
                            undetermined.append(
                                cp.rest_ids[vr.undetermined])
                        cluster_log.append({
                            "size": cp.size, "sampled": cp.n_sample,
                            "score": float(np.mean(labels)),
                            "voted": int(voted),
                            "undetermined": int(len(vr.undetermined)),
                            "depth": depth,
                            "outcome": ("vote"
                                        if not len(vr.undetermined)
                                        else "recluster"),
                        })

            n_undet = int(sum(len(u) for u in undetermined))
            round_log.append(RoundResult(
                depth=depth, n_clusters=len(plan.clusters),
                n_sampled=plan.n_sampled, n_voted=round_voted,
                n_undetermined=n_undet, waves=len(outputs),
                oracle_batches=[o.batch for o in outputs],
                shards=len(outputs)))
            rsp.set(n_sampled=plan.n_sampled, n_voted=round_voted,
                    n_undetermined=n_undet, shards=len(outputs))
            tr.metrics.inc("driver.rounds")
            tr.metrics.inc("distributed.sharded_rounds")
            tr.metrics.observe("distributed.shards_per_round",
                               len(outputs))
            tr.metrics.observe("round.wall_s", monotonic() - t_round)

            if not undetermined:
                break
            pending = np.concatenate(undetermined)
            depth += 1
            rounds_used = depth
            queue, fb, dt = _recluster_or_fallback(
                emb, oracle, cfg, pending, depth, result, decided)
            n_fallback += fb
            recluster_time += dt
    return n_voted, n_fallback, rounds_used, recluster_time
