"""Stream watcher driver: standing semantic queries over a replayed feed.

    PYTHONPATH=src python -m repro.launch.watch --n 400 --queries 3

Like ``serve``, it needs a TPU unless ``JAX_PLATFORMS=cpu`` asks for the
CPU on purpose.

Replays a deterministic stream against K standing queries over one
session (docs/streaming.md): rows arrive per tick under a per-source
rate budget, each tick coalesced-appends them and re-votes only the
touched clusters, and every newly-matching row is pushed exactly once to
a JSONL sink.  The watcher checkpoints through a ``SessionStore`` —
rerun the same command after a kill (``--kill-after`` simulates one) and
it restores mid-stream: no already-notified row re-notifies, and the
rebuild itself costs ~0 oracle calls.

Default oracles are synthetic (seeded labels — fast, deterministic; the
CI stream-smoke leg).  ``--engine`` boots the tiny backbone instead and
answers every standing predicate with ``ModelOracle`` prompts batched
across queries through the scheduler, exactly like ``serve --service``.
"""
from __future__ import annotations

import argparse
import pathlib

import numpy as np

from repro.api import ExecutionPolicy, Session
from repro.core import SyntheticOracle
from repro.data import make_dataset
from repro.launch.runtime import enable_compile_cache, require_tpu
from repro.obs import (FlightRecorder, HealthMonitor, LogAlertSink,
                       MetricsRegistry, StatusHub, Tracer, default_rules,
                       set_flight_recorder, set_monitor, set_tracer)
from repro.service.lifecycle import GracefulShutdown
from repro.service.store import SessionStore
from repro.stream import (JsonlSink, RateBudget, StreamWatcher,
                          SyntheticSource)

WATCH_PREDICATES = [
    "the review is positive",
    "the review praises the acting",
    "the review discusses the plot",
    "the review would recommend the movie",
]
# synthetic label keys backing the K standing queries (cycled)
LABEL_KEYS = ["RV-Q1", "RV-Q3", "RV-Q2"]


def build_watcher(args):
    """Session + oracles + watcher over one deterministic stream."""
    ds = make_dataset("imdb_review", n=args.n, seed=0)
    pol = ExecutionPolicy(n_clusters=4, min_sample=25)
    sess = Session(policy=pol)
    store = SessionStore(args.state_dir)

    if args.engine:
        import jax

        from repro.configs import smoke_config
        from repro.core.oracle import ModelOracle
        from repro.data import HashTokenizer
        from repro.models import lm
        from repro.serving import ServingEngine
        cfg = smoke_config(args.arch)
        if args.attn_impl:
            cfg = cfg.replace(attn_impl=args.attn_impl)
        params = lm.init_params(cfg, jax.random.key(0))
        engine = ServingEngine(cfg, params)
        tok = HashTokenizer(cfg.vocab_size)
        # the stream table starts EMPTY; ModelOracle indexes the table's
        # live texts list, which append() extends in place, so prompts
        # always see the rows the ids name
        handle = sess.table(
            texts=[], embeddings=np.zeros((0, ds.embeddings.shape[1]),
                                          np.float32), name="feed")
        preds = (WATCH_PREDICATES
                 * ((args.queries - 1) // len(WATCH_PREDICATES) + 1))
        for i in range(args.queries):
            sess.register_oracle(f"p{i}", ModelOracle(
                engine, tok, preds[i], handle._table.texts))
    else:
        for i in range(args.queries):
            key = LABEL_KEYS[i % len(LABEL_KEYS)]
            sess.register_oracle(f"p{i}", SyntheticOracle(
                ds.labels[key], flip_prob=0.0, seed=7 + i,
                token_lens=ds.token_lens))

    watcher = StreamWatcher(sess, table_name="feed", store=store,
                            tag="watch",
                            checkpoint_every=args.checkpoint_every)
    watcher.add_source(
        SyntheticSource("feed0", texts=list(ds.texts),
                        embeddings=ds.embeddings,
                        arrive_per_tick=args.arrive_per_tick, seed=11),
        RateBudget(rows_per_tick=args.rows_per_tick))
    sink_dir = pathlib.Path(args.state_dir)
    for i in range(args.queries):
        watcher.register(f"p{i}",
                         sink=JsonlSink(sink_dir / f"notify_p{i}.jsonl"))
    return sess, watcher


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400,
                    help="total rows in the replayed stream")
    ap.add_argument("--queries", type=int, default=3, metavar="K",
                    help="number of standing queries")
    ap.add_argument("--arrive-per-tick", type=int, default=40)
    ap.add_argument("--rows-per-tick", type=int, default=40,
                    help="per-source ingestion quota (arrivals beyond it "
                         "defer to later ticks, never drop)")
    ap.add_argument("--state-dir", default="/tmp/repro_watch_state",
                    help="SessionStore + sink + checkpoint directory")
    ap.add_argument("--checkpoint-every", type=int, default=2,
                    metavar="TICKS")
    ap.add_argument("--kill-after", type=int, default=0, metavar="K",
                    help="stop after tick K as if killed (checkpoint via "
                         "the shutdown path); rerun to restore mid-stream")
    ap.add_argument("--engine", action="store_true",
                    help="ModelOracle over the tiny backbone instead of "
                         "synthetic oracles")
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "plain", "chunked", "tri", "flash",
                             "flash-ref"])
    ap.add_argument("--metrics-port", type=int, default=0, metavar="PORT",
                    help="serve live /metrics, /healthz, /statusz and "
                         "/varz on PORT (0 = off)")
    ap.add_argument("--metrics-host", default="127.0.0.1", metavar="HOST",
                    help="bind address for --metrics-port (default "
                         "loopback; pass 0.0.0.0 to expose)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="arm the flight recorder: dump a debug bundle "
                         "under DIR on unhandled exception, fatal signal, "
                         "or critical health alert")
    ap.add_argument("--trace-dir", default=None, metavar="DIR")
    args = ap.parse_args()
    require_tpu("watch")
    enable_compile_cache()

    registry = MetricsRegistry()
    tracer = None
    monitor = None
    flight = None
    hub = None
    if args.trace_dir or args.metrics_port or args.flight_dir:
        tracer = Tracer(metrics=registry)
        set_tracer(tracer)
        monitor = HealthMonitor(registry, rules=default_rules(),
                                sinks=[LogAlertSink("[watch][health]")])
        set_monitor(monitor)
    if args.flight_dir:
        flight = FlightRecorder(args.flight_dir, tracer=tracer,
                                registry=registry)
        flight.install()
        set_flight_recorder(flight)
        monitor.add_sink(flight.note_alert)
    if args.metrics_port:
        from repro.launch.serve import start_metrics_server
        hub = StatusHub(monitor=monitor, flight=flight)
        start_metrics_server(registry, args.metrics_port,
                             host=args.metrics_host, hub=hub,
                             label="watch")

    sess, watcher = build_watcher(args)
    if hub is not None:
        hub.add_provider("stream", watcher.status_view)

    resumed = False
    if watcher.has_checkpoint():
        report = watcher.restore()
        resumed = True
        print(f"[watch] restored at tick {watcher.stats.n_ticks} "
              f"({watcher.stats.n_notifications} rows already notified, "
              f"0 oracle calls to rebuild): {report}")

    # flag-mode shutdown: the tick loop stops at a tick boundary, then the
    # watcher writes its final checkpoint and flushes every sink
    shutdown = GracefulShutdown(exit_on_signal=False).install()
    shutdown.register("watch-shutdown", watcher.shutdown)
    if flight is not None:
        flight.install(shutdown=shutdown)  # signal-triggered dumps only
    try:
        while not watcher.drained and not shutdown.requested:
            summary = watcher.tick()
            print(f"[watch] tick {summary['tick']}: +{summary['rows']} rows "
                  f"({summary['backlog']} deferred), "
                  f"{summary['oracle_calls']} oracle calls, "
                  f"{summary['notified']} notified")
            if args.kill_after and summary["tick"] >= args.kill_after:
                print(f"[watch] --kill-after {args.kill_after}: stopping "
                      "mid-stream (rerun to restore)")
                break
    finally:
        shutdown.close()   # runs watcher.shutdown() once
        sess.close()

    st = watcher.stats
    print(f"[watch] {'resumed ' if resumed else ''}done: {st.n_ticks} ticks, "
          f"{st.n_rows_ingested} rows ingested, "
          f"{st.n_oracle_calls} oracle calls, "
          f"{st.n_notifications} notifications "
          f"({sum(sq.runner.stats.n_deduped for sq in watcher.queries.values())}"
          f" deduped, "
          f"{sum(sq.runner.stats.n_dead_lettered for sq in watcher.queries.values())}"
          f" dead-lettered)")
    if tracer is not None and args.trace_dir:
        from repro.launch.serve import export_trace
        export_trace(args.trace_dir, tracer, registry, watcher,
                     sess.scheduler.stats if sess._scheduler else None)


if __name__ == "__main__":
    main()
