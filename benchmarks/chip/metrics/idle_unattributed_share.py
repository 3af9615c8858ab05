"""Share of the traced window in which the device was idle and no program
work span explains why: ``device_idle_share`` less 100 x the time of the
first chip's idle gaps (between its busy intervals) that some
``repro.*`` work span, on any host thread, covers, over the window.  What
remains is idle spent waiting, in code without a span, or before the
first and after the last device op.  A program without those spans reads
nothing."""
from chipbench import spans


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.n_chips == 0:
        return None
    ws = spans.work_spans(tr)
    if not ws:
        return None
    covered = spans.overlap_s(spans.idle_gaps(tr), [(s, e) for s, e, _ in ws])
    idle = 100.0 * (1.0 - tr.busy_s() / tr.window_s)
    return idle - 100.0 * covered / tr.window_s
