"""The oracle model step's share of the chip's memory bandwidth: the runs
of the step's programs in the trace times the weight bytes each run must
read (``chipbench.weight_bytes``: the layer stack and the final norm), over
the device time of those runs times the HBM peak.  With
``oracle_step_mfu`` it places the step on its roofline.  The count leaves
out activations and the embedding, so it reads low and never high."""
import re

from chipbench import weight_bytes

# the engine's yes/no step, as oracle_step_mfu reads it
PROGRAMS = [r"^jit_f(\(|$)"]


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    if tr is None or pk is None or not tr.modules:
        return None
    rx = [re.compile(p) for p in PROGRAMS]
    runs = sum(1 for evs in tr.modules.values() for _, _, n in evs
               if any(r.search(n) for r in rx)) / len(tr.modules)
    t = tr.program_s(PROGRAMS)
    if t <= 0 or runs == 0:
        return None
    work = runs * weight_bytes.layer_stack_bytes(ctx["config"])
    return 100.0 * work / (t * pk["hbm_bytes_per_s"])
