"""The output check: the program passes it, the lower-precision control
and each fault a cell can have fail it.  Each runs the whole harness at
the tiny size without the look for a chip."""
import jax
import numpy as np
import pytest

import harness
from conftest import run_tiny


def _gaps(out):
    return {k: v["value"] for k, v in out["checks"].items()}


@pytest.mark.parametrize("workload", ["cnn4.sweep", "cnn4.paper_open"])
def test_program_passes_and_control_fails(tiny, workload):
    cell = harness.Cell(workload, paths=tiny, require_tpu=False,
                        compile_cache=False, log=lambda m: None)
    cell.setup(3)
    w = cell.measure(2 ** 32 + 9, 1.5)
    cell.close()
    prog = cell.check(w, 9)
    assert cell.correct(prog), prog
    assert prog["answers_checked"]["value"] >= 6
    assert prog["score_gap"]["value"] < 1e-6
    assert prog["trajectory_miss"]["value"] <= 0.25
    ctl = cell.check(w, 9, control="bfloat16")
    assert not cell.correct(ctl), ctl
    assert ctl["score_gap"]["value"] > 1e-4
    assert ctl["trajectory_miss"]["value"] > 0.5


def _broken_harvest(monkeypatch, mangle):
    from repro.core.engine import SearchEngine

    real = SearchEngine.harvest

    def harvest(self, pending):
        return mangle(real(self, pending))

    monkeypatch.setattr(SearchEngine, "harvest", harvest)


def test_state_left_unchanged_fails(tiny, monkeypatch):
    from repro.core import ga

    real = ga._make_gen_step

    def frozen(*a, **kw):
        real(*a, **kw)

        def gen(carry, k):
            return carry, (carry[0], carry[1])

        return gen

    monkeypatch.setattr(ga, "_make_gen_step", frozen)
    jax.clear_caches()
    try:
        out = run_tiny(tiny, "cnn4.sweep")
    finally:
        jax.clear_caches()
    g = _gaps(out)
    assert not out["correct"] and g["missing"] == 0
    assert g["compiles_in_window"] == 0
    assert g["trajectory_miss"] > 0.5


def test_half_the_batch_left_out_fails(tiny, monkeypatch):
    def mangle(results):
        h = len(results) // 2
        return results[:h] + results[:len(results) - h]

    _broken_harvest(monkeypatch, mangle)
    out = run_tiny(tiny, "cnn4.sweep")
    assert not out["correct"] and _gaps(out)["missing"] == 0


def test_exchange_between_chips_left_out_fails(tiny, monkeypatch):
    """The four-chip cell's gather: only the first chip's quarter of the
    slots comes back, the rest repeat it."""
    def mangle(results):
        q = max(1, len(results) // 4)
        return [results[i % q] for i in range(len(results))]

    _broken_harvest(monkeypatch, mangle)
    out = run_tiny(tiny, "cnn4.sweep.mesh4")
    assert not out["correct"] and _gaps(out)["missing"] == 0


def test_an_answer_altered_where_produced_fails(tiny, monkeypatch):
    def mangle(results):
        r = results[0]
        if len(r.top_scores):
            r.top_scores = np.asarray(r.top_scores).copy()
            r.top_scores[0] *= np.float32(1.001)
        return results

    _broken_harvest(monkeypatch, mangle)
    out = run_tiny(tiny, "cnn4.paper_open")
    g = _gaps(out)
    assert not out["correct"] and g["missing"] == 0
    assert g["score_gap"] > 1e-4


def test_fewer_generations_fails(tiny, monkeypatch):
    from repro.core import engine

    real = engine.run_ga_batched_thin

    def short(*a, generations, **kw):
        return real(*a, generations=generations - 1, **kw)

    monkeypatch.setattr(engine, "run_ga_batched_thin", short)
    out = run_tiny(tiny, "cnn4.sweep")
    assert not out["correct"]
    assert _gaps(out)["trajectory_miss"] == 1.0
