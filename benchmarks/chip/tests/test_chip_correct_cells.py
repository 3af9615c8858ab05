"""``test_chip_correct``'s sound runs and planted faults, for the cells
whose smoke size that file does not run: each cell's own configuration
and mix, at smoke size, through ``run_cell``'s own comparison.

``cq7b-profiles`` serves grouped-query attention with an untied head, so
a sound run of it checks the reference's repeated K/V and ``lm_head``
against the program's grouped einsum and head, and the control and each
fault have to come out not correct there too.
"""
import dataclasses
import pathlib
import sys
import time

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parents[1] / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import cells, harness  # noqa: E402

CELLS = ["cq7b-profiles"]
SEED = 2 ** 34 + 321
# a seed whose three queries hold answers that float8 flips at smoke widths
CONTROL_SEED = 2 ** 35 + 9


def _run(name, seed=SEED):
    cell = cells.load_cell(name, smoke=True)
    return harness.run_cell(cell, seed, 0.5, False, time.perf_counter(),
                            dev=None, log=lambda m: None)


def _serial_window(stack, seconds):
    """Three queries served one after another: the same records as the
    closed loop's, with no dependence on the CPU's speed."""
    records, t0 = [], time.perf_counter()
    for k in range(3):
        pred = stack.preds.next()
        q, oracle, texts = stack.query(pred, f"q{k}", k + 1)
        t = time.perf_counter()
        ticket = stack.service.submit("default", q, label=f"q{k}")
        records.append({"index": k, "predicate": pred, "oracle": oracle,
                        "texts": texts, "rows": len(texts), "t_submit": t,
                        "result": stack.service.gather(ticket)[0],
                        "t_done": time.perf_counter()})
    return {"records": records, "t0": t0, "t_close": time.perf_counter(),
            "t_end": time.perf_counter()}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [1, 2 ** 33 + 5])
def test_sound_run_is_correct(name, seed):
    out = _run(name, seed)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name, monkeypatch):
    """Every call of three queries compared.  At the smoke widths few
    answers lie near a tie; this seed's queries have some that float8
    flips."""
    monkeypatch.setattr(harness, "run_window", _serial_window)
    cell = cells.load_cell(name, smoke=True)
    cell.mix["check_calls"] = 48
    out = {c: harness.run_cell(cell, CONTROL_SEED, 0, False, time.perf_counter(),
                               dev=None, log=lambda m: None, control=c)
           for c in (False, True)}
    program, control = (out[c]["checks"]["oracle_gap"] for c in (False, True))
    assert out[False]["correct"], out[False]["checks"]
    assert not out[True]["correct"]
    assert program["value"] <= program["limit"] < control["value"], (program, control)
    assert out[True]["info"]["kmeans_fits"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_produced_is_not_correct(name, monkeypatch):
    from repro.core.oracle import ModelOracle
    monkeypatch.setattr(ModelOracle, "pack_labels",
                        lambda self, pair: np.asarray(pair[:, 0] <= pair[:, 1]))
    out = _run(name)
    assert not out["correct"]
    assert out["checks"]["oracle_gap"]["value"] > out["checks"]["oracle_gap"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_cluster_assignment_altered_where_produced_is_not_correct(name, monkeypatch):
    import repro.core.csv_filter as csv_filter
    real = csv_filter.kmeans

    def moved_row(key, x, k, **kw):
        cents, assign, inertia = real(key, x, k, **kw)
        return cents, assign.at[0].set((assign[0] + 1) % k), inertia
    monkeypatch.setattr(csv_filter, "kmeans", moved_row)
    out = _run(name)
    assert out["info"]["kmeans_fits"] > 0
    assert not out["correct"]
    assert out["checks"]["kmeans_err"]["value"] > out["checks"]["kmeans_err"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_half_of_each_batch_left_out_is_not_correct(name, monkeypatch):
    """The rows left out read a fixed answer, 'yes' (column 0)."""
    from repro.serving import engine as engine_mod
    real = engine_mod.ServingEngine.first_token_logits

    def half(self, prompts, token_ids=None):
        out = real(self, prompts[: (len(prompts) + 1) // 2],
                   None if token_ids is None else token_ids[: (len(prompts) + 1) // 2])
        full = np.zeros((len(prompts), out.shape[1]), out.dtype)
        full[: len(out)] = out
        full[len(out):, 0] = 1.0
        return full
    monkeypatch.setattr(engine_mod.ServingEngine, "first_token_logits", half)
    out = _run(name)
    assert not out["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_vote_altered_where_produced_is_not_correct(name, monkeypatch):
    import repro.core.csv_filter as csv_filter
    real = csv_filter.vote_clusters

    def swapped(*args, **kw):
        return [dataclasses.replace(v, decided_true=v.decided_false,
                                    decided_false=v.decided_true)
                for v in real(*args, **kw)]
    monkeypatch.setattr(csv_filter, "vote_clusters", swapped)
    out = _run(name)
    assert out["info"]["voted_rows_checked"] > 0
    assert not out["correct"]
    assert out["checks"]["vote_vs_samples"]["value"] > 0
