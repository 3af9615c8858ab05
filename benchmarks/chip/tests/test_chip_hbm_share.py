"""The weight bytes of the oracle step and ``oracle_step_hbm_share``: the
count against the arrays the benchmark makes, an exact share on a
hand-built trace, and a share of at most 100% on the recorded
``testdata/fresh-smoke`` trace (the 0.5B configuration at its smoke sizes),
with nothing read where the trace or the peaks are missing."""
import importlib.util
import json
import pathlib
import sys
from types import SimpleNamespace

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parents[1] / "src")]

import jax  # noqa: E402
import pytest  # noqa: E402

from chipbench import cells, peaks, weight_bytes, weights, xplane  # noqa: E402

CONFIGS = sorted(p.stem for p in (_BENCH / "configs").glob("*.json"))
RECORDED = _BENCH / "testdata" / "fresh-smoke"
PEAKS = peaks.peaks("TPU v5 lite")
MS = 1_000_000  # ns


def _reader():
    path = _BENCH / "metrics" / "oracle_step_hbm_share.py"
    spec = importlib.util.spec_from_file_location("m_hbm_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _config(name, smoke):
    c = json.loads((_BENCH / "configs" / f"{name}.json").read_text())
    return cells._merge(c, c["smoke"]) if smoke else c


@pytest.mark.parametrize("name", CONFIGS)
def test_bytes_are_those_of_the_layer_stack_made(name):
    """At the smoke sizes, the bytes of the stacked layers and the final
    norm that ``weights.oracle_params`` makes, embedding and head left out."""
    c = _config(name, smoke=True)
    p = weights.oracle_params(c, 5)
    made = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        [p["blocks"], p["final_norm"]]))
    assert weight_bytes.layer_stack_bytes(c) == made


def test_bytes_of_the_7b_stage():
    """16 layers of 405.8 MB in bfloat16 (norms in float32): 6.49 GB."""
    b = weight_bytes.layer_stack_bytes(_config("codeqwen1.5-7b", smoke=False))
    assert b == 16 * (2 * (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 13440
                           + 4096 + 2 * 512) + 2 * 4096 * 4) + 4096 * 4
    assert 6.4e9 < b < 6.6e9


def test_share_exact_on_a_hand_built_trace():
    """Three runs of the step, 10 ms each, and one other program."""
    tr = xplane.Trace(SimpleNamespace(planes=[]), 0.1)
    tr.modules = {"/device:TPU:0": [(0, 10 * MS, "jit_f(12)"),
                                    (20 * MS, 30 * MS, "jit_f(13)"),
                                    (40 * MS, 50 * MS, "jit_f(12)"),
                                    (60 * MS, 90 * MS, "jit_kmeans(3)")]}
    c = _config("codeqwen1.5-7b", smoke=False)
    ctx = {"trace": tr, "peaks": PEAKS, "config": c}
    want = 100 * 3 * weight_bytes.layer_stack_bytes(c) / (0.03 * 819e9)
    assert _reader()(ctx) == pytest.approx(want)


def test_recorded_trace_reads_at_most_100_and_nothing_when_missing():
    from jax.profiler import ProfileData
    meta = json.loads(RECORDED.with_suffix(".json").read_text())
    tr = xplane.Trace(ProfileData.from_file(str(RECORDED.with_suffix(".xplane.pb"))),
                      meta["window_s"])
    c = cells.load_cell("q05b-reviews", smoke=True).config
    ctx = {"trace": tr, "peaks": PEAKS, "config": c}
    share = _reader()(ctx)
    assert share is not None and 0.0 < share <= 100.0
    assert _reader()(dict(ctx, trace=None)) is None
    assert _reader()(dict(ctx, peaks=None)) is None
    empty = xplane.Trace(SimpleNamespace(planes=[]), 1.0)
    assert _reader()(dict(ctx, trace=empty)) is None
