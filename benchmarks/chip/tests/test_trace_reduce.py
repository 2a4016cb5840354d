"""The reduction from a profiler trace to per-layer metrics, on
intervals and on a synthetic reduced trace.  ``record_trace.py`` records
a small trace on a TPU for a test of the whole reading."""
import pytest

import layer_metrics
import trace_reduce
import work_count
from harness import RunData


def test_union_clip_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    u = trace_reduce.union(iv)
    assert u == [(0.0, 2.0), (3.0, 4.0)]
    assert trace_reduce.length(u) == 3.0
    c = trace_reduce.clip(u, 1.0, 3.5)
    assert c == [(1.0, 2.0), (3.0, 3.5)]
    assert trace_reduce.gaps(c, 1.0, 5.0) == [(2.0, 3.0), (3.5, 5.0)]


def _synthetic():
    busy = {"/device:TPU:0": [(0.0, 0.6), (0.7, 0.9)],
            "/device:TPU:1": [(0.0, 0.8)]}
    modules = {"/device:TPU:0": [("jit__run_ga_batched_thin_jit", 0.0, 0.6),
                                 ("jit_other", 0.7, 0.9)],
               "/device:TPU:1": [("jit__run_ga_batched_thin_jit", 0.0, 0.8)]}
    spans = [("bench.harvest", 0.55, 0.75), ("bench.harvest_wait", 0.55, 0.62),
             ("bench.dispatch", 0.9, 0.95)]
    return trace_reduce.Reduced((0.0, 1.0), busy, modules,
                                {"fusion.1": 1.2, "sort.2": 0.4}, spans)


def test_synthetic_reduction():
    r = _synthetic()
    assert trace_reduce.busy_s(r) == pytest.approx(0.8)
    assert trace_reduce.program_seconds(r, "_run_ga_batched_thin_jit") == \
        (pytest.approx(1.4), 1.0)
    gaps = trace_reduce.idle_gaps(r)
    # (0.6, 0.7) lies inside harvest, past its wait; (0.9, 1.0) half in
    # dispatch
    assert gaps[0] == ["bench.harvest", pytest.approx(0.1)]
    assert gaps[1] == ["bench.dispatch", pytest.approx(0.1)]
    assert trace_reduce.top_ops(r)[0] == ["fusion.1", pytest.approx(0.6)]
    run = RunData(trace=r, launch_log=[], window=(0, 1), records=[],
                  peaks={"hbm_bytes_per_s": 819e9})
    assert layer_metrics.ga_ms_per_launch(run) == pytest.approx(700.0)
    assert layer_metrics.idle_pct(run) == pytest.approx(20.0)
    # no launch logged: no roofline rather than a roofline of 0
    assert layer_metrics.ga_roofline_pct(run) is None


def test_only_whole_executions_count_and_bytes_follow_them():
    """The window cuts the launch running at its open and at its close:
    those two are left out of the device time, and the roofline takes the
    bytes of the launches it kept (the 2nd and 3rd since the trace began),
    not of the launches dispatched in the window."""
    ga = "jit__run_ga_batched_thin_jit"
    modules = {"/device:TPU:0": [(ga, 0.5, 1.2), ("jit_seed", 1.2, 1.3),
                                 (ga, 1.3, 2.0), (ga, 2.1, 2.9),
                                 (ga, 2.95, 3.5)]}
    r = trace_reduce.Reduced((1.0, 3.0), {"/device:TPU:0": [(1.0, 3.0)]},
                             modules, {}, [])
    assert trace_reduce.program_runs(r, ga) == {
        "/device:TPU:0": [(1, 1.3, 2.0), (2, 2.1, 2.9)]}
    total, per_dev = trace_reduce.program_seconds(r, ga)
    assert (total, per_dev) == (pytest.approx(1.5), 2)
    log = [{"reqs": [0] * s, "P": 8, "G": 2, "W": s} for s in (1, 2, 3, 4)]
    run = RunData(trace=r, launch_log=log, window=(0, 1), records=[],
                  peaks={"hbm_bytes_per_s": 1e6})
    assert layer_metrics.ga_ms_per_launch(run) == pytest.approx(750.0)
    need = sum(work_count.launch_bytes(s, 8, 2, s, layer_metrics.N_GENES)
               for s in (2, 3)) / 1e6
    assert layer_metrics.ga_roofline_pct(run) == pytest.approx(100 * need / 1.5)
    # a trace with more executions than the engine logged launches: the
    # pairing does not hold, and nothing is read
    short = RunData(trace=r, launch_log=log[:2], window=(0, 1), records=[],
                    peaks={"hbm_bytes_per_s": 1e6})
    assert layer_metrics.ga_roofline_pct(short) is None


def test_no_device_plane_reads_nothing():
    r = trace_reduce.Reduced((0.0, 1.0), {}, {}, {}, [])
    run = RunData(trace=r, launch_log=[], window=(0, 1), records=[], peaks={})
    assert trace_reduce.busy_s(r) is None
    assert layer_metrics.idle_pct(run) is None
    assert layer_metrics.ga_ms_per_launch(run) is None

