"""Work spans on the profiler's clock (``repro.obs.trace``).

Four concurrent queries at smoke size go through ``FilterService`` with
the model oracle, the engine and an encoder that embeds the table inside
the served path.  Under a JAX profiler session (on the CPU the host plane
carries the annotations) every work step of the served path shows up as a
``repro.*`` host event, the ``repro.*`` events of one host thread never
overlap, and the served masks and call counts are bit-identical with no
tracer, with the collecting tracer and under the profiler.  The service's
queue-wait counter records one wait per parked request.
"""
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.api import ExecutionPolicy, Session
from repro.configs import smoke_config
from repro.core import SyntheticOracle
from repro.core.oracle import ModelOracle
from repro.data import make_dataset
from repro.data.tokenizer import HashTokenizer
from repro.embeddings.encoder import EmbeddingModel
from repro.models import lm
from repro.obs import MetricsRegistry, Tracer, use_tracer
from repro.service import FilterService
from repro.serving import ServingEngine

N = 160
# lb 0 leaves every first-round vote undecided: each query re-splits its
# rows with k-means, then falls back to the oracle — every step runs
POL = ExecutionPolicy(n_clusters=2, min_sample=12, lb=0.0, max_recluster=1)
PREDICATES = ["is the review positive", "does it mention the acting",
              "is the plot discussed", "does it compare to another film"]
SERVED = {"repro.service.submit", "repro.query.prepare", "repro.csv.plan",
          "repro.csv.vote", "repro.csv.kmeans", "repro.oracle.memo_split",
          "repro.oracle.tokenize", "repro.oracle.commit", "repro.engine.plan",
          "repro.engine.launch", "repro.engine.fetch",
          "repro.encoder.tokenize", "repro.encoder.batch"}


@pytest.fixture(scope="module")
def stack():
    cfg = smoke_config("qwen1.5-0.5b")
    engine = ServingEngine(cfg, lm.init_params(cfg, jax.random.key(0)),
                           max_batch=16)
    encoder = EmbeddingModel(smoke_config("e5-large"), seed=0, max_len=32)
    return engine, encoder, make_dataset("imdb_review", n=N, seed=0)


def _serve(stack):
    """Serve the four queries at once; (masks, calls per query, calls per
    oracle)."""
    engine, encoder, ds = stack
    sess = Session(policy=POL, embedder=encoder.encode)
    svc = FilterService(sess)
    svc.register_tenant("default", POL)
    table = sess.table(texts=list(ds.texts), name="reviews")
    tok = HashTokenizer(engine.cfg.vocab_size)
    oracles = [ModelOracle(engine, tok, p, ds.texts) for p in PREDICATES]
    tickets = [svc.submit("default", table.filter(o, name=f"p{i}"),
                          label=f"p{i}") for i, o in enumerate(oracles)]
    results = svc.gather(*tickets)
    svc.close()
    return ([r.mask for r in results], [r.n_llm_calls for r in results],
            [o.stats.n_calls for o in oracles])


def _profiled(stack):
    """Serve under a profiler session; (served, {host line: events})."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            served = _serve(stack)
        finally:
            jax.profiler.stop_trace()
        pb = next(Path(d).glob("plugins/profile/*/*.xplane.pb"))
        data = ProfileData.from_file(str(pb))
        lines = {}
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for i, line in enumerate(plane.lines):
                    lines[(plane.name, i)] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events if e.name.startswith("repro.")]
    return served, lines


@pytest.fixture(scope="module")
def runs(stack):
    plain = _serve(stack)
    tr = Tracer(metrics=MetricsRegistry())
    with use_tracer(tr):
        traced = _serve(stack)
    profiled, lines = _profiled(stack)
    return {"plain": plain, "traced": traced, "profiled": profiled,
            "tracer": tr, "lines": lines}


def test_profiler_sees_every_work_step(runs):
    names = {n for evs in runs["lines"].values() for _, _, n in evs}
    assert SERVED <= names, SERVED - names


def test_work_spans_of_one_thread_never_overlap(runs):
    n = 0
    for evs in runs["lines"].values():
        evs = sorted(evs)
        n += len(evs)
        for (_, e0, a), (s1, _, b) in zip(evs, evs[1:]):
            assert e0 <= s1, f"{a} overlaps {b}"
    assert n > 0
    # the collecting tracer saw the same steps, as leaves
    work = [s for s in runs["tracer"].spans() if s.name.startswith("repro.")]
    assert {s.name for s in work} == SERVED
    parents = {s.parent_id for s in runs["tracer"].spans()}
    assert not parents & {s.span_id for s in work}


def test_served_path_bit_identical_across_tracers(runs):
    plain = runs["plain"]
    for mode in ("traced", "profiled"):
        masks, calls, oracle_calls = runs[mode]
        for a, b in zip(plain[0], masks):
            np.testing.assert_array_equal(a, b)
        assert calls == plain[1] and oracle_calls == plain[2]
    assert sum(plain[1]) > 0


def test_nested_work_span_is_refused():
    tr = Tracer()
    with tr.work("repro.outer"):
        with pytest.raises(AssertionError, match="repro.outer"):
            with tr.work("repro.inner"):
                pass
    with tr.work("repro.after"):   # the refused span left nothing open
        pass
    # the null tracer's work span is the bare annotation
    from repro.obs.trace import NULL_TRACER
    with NULL_TRACER.work("repro.null", n=1):
        pass


def test_queue_wait_one_per_parked_request():
    ds = make_dataset("imdb_review", n=400, seed=0)
    sess = Session(policy=ExecutionPolicy(n_clusters=4, xi=0.005))
    t = sess.table(embeddings=ds.embeddings, name="reviews")
    parked = []
    real = sess.scheduler._evaluate

    def counting(task, oracle, ids):
        parked.append(len(ids))
        return real(task, oracle, ids)
    sess.scheduler._evaluate = counting
    tr = Tracer(metrics=MetricsRegistry())
    with use_tracer(tr):
        qs = [t.filter(SyntheticOracle(ds.labels[q], flip_prob=0.02,
                                       seed=7), name=q)
              for q in ("RV-Q1", "RV-Q3")]
        with sess.scheduler.holding():
            tickets = [sess.submit(q) for q in qs]
        sess.gather(*tickets)
    st = sess.scheduler.stats
    assert st.queue_waits == len(parked) == st.merge.n_requests > 0
    h = tr.metrics.snapshot()["service.queue_wait_s"]
    assert h["count"] == len(parked) and h["min"] >= 0.0
    assert st.queue_wait_s_total >= 0.0
    assert st.metrics_view()["service.queue_waits"] == len(parked)
    view = sess.scheduler.status_view()
    assert view["queue_waits"] == len(parked)
    assert view["queue_wait_s_total"] == st.queue_wait_s_total
    sess.close()
