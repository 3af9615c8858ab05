"""Host milliseconds of the CSV round driver per query served in the
traced window: the summed durations of the ``repro.csv.*`` work spans
(planning, voting, the k-means re-split) and of ``repro.query.prepare``
over the queries completed.  A program without those spans reads
nothing."""
from chipbench import spans


def read(ctx):
    tr, n = ctx["trace"], len(ctx["latencies"])
    if tr is None or not n:
        return None
    ws = spans.work_spans(tr)
    if not ws:
        return None
    return 1e3 * spans.seconds(ws, "repro.csv.", "repro.query.prepare") / n
