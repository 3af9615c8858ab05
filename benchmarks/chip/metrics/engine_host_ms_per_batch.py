"""Host milliseconds the engine spends per device batch before the batch
runs: the summed durations of the ``repro.engine.plan`` (bucketing a
wave) and ``repro.engine.launch`` (host-to-device copies and the jitted
call) work spans over the number of launches.  The wait for each result
(``repro.engine.fetch``) is left out.  A program without those spans
reads nothing."""
from chipbench import spans


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ws = spans.work_spans(tr)
    n = spans.count(ws, "repro.engine.launch")
    if not n:
        return None
    return 1e3 * spans.seconds(ws, "repro.engine.plan",
                               "repro.engine.launch") / n
