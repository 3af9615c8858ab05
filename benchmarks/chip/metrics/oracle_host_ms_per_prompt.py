"""Host milliseconds of the service lane's oracle steps per prompt the
engine ran in the traced window: the summed durations of the
``repro.oracle.*`` work spans (memo split, tokenization, commit) over the
window's prompts.  A program without those spans reads nothing."""
from chipbench import spans


def read(ctx):
    tr, prompts = ctx["trace"], ctx["counters"]["prompts"]
    if tr is None or not prompts:
        return None
    ws = spans.work_spans(tr)
    if not ws:
        return None
    return 1e3 * spans.seconds(ws, "repro.oracle.") / prompts
