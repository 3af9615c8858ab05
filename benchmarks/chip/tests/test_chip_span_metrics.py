"""The four readers of the program's work spans (``metrics/*`` over
``chipbench.spans``): exact values on a hand-built trace whose device ops
and host spans are known, and nothing read from a trace that holds no
``repro.`` span (the recorded ``testdata/fresh-smoke`` trace, taken before
the program had them)."""
import importlib.util
import json
import pathlib
import sys
from types import SimpleNamespace

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parents[1] / "src")]

import pytest  # noqa: E402

from chipbench import spans, xplane  # noqa: E402

METRICS = ["oracle_host_ms_per_prompt", "engine_host_ms_per_batch",
           "driver_host_ms_per_query", "idle_unattributed_share"]
MS = 1_000_000  # ns


def _reader(metric):
    path = _BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"m_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _trace(ops, host, window_s):
    tr = xplane.Trace(SimpleNamespace(planes=[]), window_s)
    tr.ops = {"/device:TPU:0": [(s * MS, e * MS, "fusion") for s, e in ops]}
    tr.host = [(s * MS, e * MS, n) for s, e, n in host]
    return tr


# a 100 ms window: the device runs [0, 10), [30, 40), [70, 100), so it
# idles in two gaps, [10, 30) and [40, 70): 50 ms, 50% of the window
OPS = [(0, 10), (30, 40), (70, 100)]
HOST = [
    (10, 16, "repro.oracle.memo_split"),
    (16, 24, "repro.oracle.tokenize"),
    (24, 25, "repro.engine.plan"),
    (25, 27, "repro.engine.launch"),
    (27, 31, "repro.engine.fetch"),     # 1 ms inside the gap
    (32, 35, "repro.engine.launch"),    # while the device runs
    (35, 38, "repro.engine.fetch"),
    (38, 40, "repro.oracle.commit"),
    (40, 45, "repro.csv.vote"),
    (44, 50, "repro.csv.plan"),         # another thread: overlaps vote
    (50, 52, "repro.query.prepare"),
    (60, 61, "repro.service.submit"),
    (0, 100, "bench.wait"),             # not the program's
    (52, 58, "PjitFunction(f)"),        # nor JAX's own
]
CTX = {"counters": {"prompts": 40}, "latencies": [1.0, 2.0]}


@pytest.fixture
def ctx():
    return dict(CTX, trace=_trace(OPS, HOST, 0.1))


def test_work_spans_keep_the_program_prefix(ctx):
    ws = spans.work_spans(ctx["trace"])
    assert len(ws) == 12 and all(n.startswith("repro.") for _, _, n in ws)
    assert spans.seconds(ws, "repro.engine.") == pytest.approx(0.013)
    assert spans.count(ws, "repro.engine.launch") == 2
    # a name without the trailing dot is one step, not a family
    assert spans.seconds(ws, "repro.engine.plan") == pytest.approx(0.001)
    assert spans.overlap_s([(0, 10), (20, 30)], [(5, 25)]) == pytest.approx(1e-8)


def test_span_metrics_exact(ctx):
    # oracle: (6 + 8 + 2) ms over 40 prompts
    assert _reader("oracle_host_ms_per_prompt")(ctx) == pytest.approx(16 / 40)
    # engine: plan 1 + launches 2 + 3 ms over 2 launches; fetch left out
    assert _reader("engine_host_ms_per_batch")(ctx) == pytest.approx(6 / 2)
    # driver: vote 5 + plan 6 + prepare 2 ms over 2 queries
    assert _reader("driver_host_ms_per_query")(ctx) == pytest.approx(13 / 2)
    # idle 50%; spans cover [10, 30) whole (20 ms) and [40, 52) and
    # [60, 61) of the second gap (13 ms): 33 ms of 100 explained
    assert _reader("idle_unattributed_share")(ctx) == pytest.approx(50 - 33)


def test_span_metrics_read_nothing_without_spans(ctx):
    bare = dict(ctx, trace=_trace(OPS, [h for h in HOST
                                        if not h[2].startswith("repro.")],
                                  0.1))
    for m in METRICS:
        assert _reader(m)(bare) is None
        assert _reader(m)(dict(ctx, trace=None)) is None


def test_recorded_trace_without_program_spans_reads_nothing():
    from jax.profiler import ProfileData
    rec = _BENCH / "testdata" / "fresh-smoke"
    meta = json.loads(rec.with_suffix(".json").read_text())
    tr = xplane.Trace(ProfileData.from_file(str(rec.with_suffix(".xplane.pb"))),
                      meta["window_s"])
    assert tr.n_chips == 1 and tr.host and not spans.work_spans(tr)
    ctx = {"trace": tr, "counters": meta["counters"], "latencies": [1.0]}
    for m in METRICS:
        assert _reader(m)(ctx) is None
