"""The per-layer metrics that read the program's own spans and request
records (``program_spans``): on planted records and a synthetic trace,
and found by a harness run at a tiny size on the CPU."""
import types

import pytest

import program_spans
import trace_reduce
from conftest import run_tiny
from harness import RunData

from repro.utils.spans import Recorder

# trace clock = perf_counter + OFFSET (the profiler's own epoch)
OFFSET = 900.0
WINDOW = (100.0, 110.0)
NEW = ["engine.syncs_per_launch.sweep",
       "device.idle_in_dispatch_ms_per_launch.sweep",
       "engine.dispatch_ms_per_launch.open",
       "engine.finalize_ms_per_launch.open",
       "service.resolve_ms_per_launch.open",
       "service.wait_p95_ms.open"]


def _plant(rec, launch, t, *, disp, fin, res, reqs):
    """One launch's spans from ``t`` on: a dispatch of ``disp`` s (64
    reads), a harvest 0.4 s later (5 reads) whose finalize takes ``fin``
    s, a resolve of ``res`` s; ``reqs`` as (submit, dispatch) stamps."""
    sid = launch * 100

    def add(name, i, parent, s, e, **attrs):
        rec.spans.append((name, sid + i, parent and sid + parent, launch,
                          s, e, 1, dict(attrs, launch=launch)))

    add("dse.dispatch.pack", 2, 1, t, t + disp / 2)
    add("dse.dispatch", 1, 0, t, t + disp, syncs=64)
    h = t + disp + 0.4
    add("dse.harvest.finalize", 4, 3, h + 0.01, h + 0.01 + fin)
    add("dse.harvest", 3, 0, h, h + 0.02 + fin, syncs=5, bytes=1000)
    r = h + 0.02 + fin
    add("dse.resolve", 5, 0, r, r + res, reqs=len(reqs))
    for i, (sub, dis) in enumerate(reqs):
        rec.request(launch * 1000 + i, launch, sub, dis, r + res)


@pytest.fixture
def planted(monkeypatch):
    """Four launches around the window [100, 110): the first before it,
    the last at its (open) end; the readers read this recorder."""
    rec = Recorder()
    _plant(rec, 1, 99.0, disp=0.5, fin=0.5, res=0.5, reqs=[(98.0, 98.99)])
    _plant(rec, 2, 100.5, disp=0.1, fin=0.04, res=0.01,
           reqs=[(100.0 + 0.01 * i, 100.49) for i in range(20)])
    _plant(rec, 3, 101.5, disp=0.12, fin=0.02, res=0.03,
           reqs=[(101.2, 101.49), (101.4, 101.49)])
    _plant(rec, 4, 110.0, disp=0.5, fin=0.5, res=0.5, reqs=[(109.0, 109.99)])
    view = types.SimpleNamespace(snapshot=rec.snapshot, records=rec.records)
    monkeypatch.setattr(program_spans, "_recorder", lambda: view)
    return rec


def _trace(jitter=(3e-6, 4e-6, 2e-6)):
    """A traced window on the trace clock that opens after the first
    launch; ``bench.dispatch`` spans open a few microseconds after their
    launch's ``t_dispatch``.  The device is busy around the dispatches of
    launches 2 and 3 but for 30 and 100 ms of them."""
    lo, hi = 100.2 + OFFSET, 110.5 + OFFSET
    spans = [("bench.dispatch", t + OFFSET + j, t + OFFSET + j + 0.05)
             for t, j in zip((100.5, 101.5, 110.0), jitter)]
    # a span cut by the window's start is clipped to it and ignored
    spans.append(("bench.dispatch", lo, lo + 0.01))
    busy = {"/device:TPU:0": [(lo, 1000.55), (1000.58, 1001.5),
                              (1001.6, 1002.0)],
            "/device:TPU:1": [(lo, hi)]}
    return trace_reduce.Reduced((lo, hi), busy, {}, {}, spans)


def _run(trace=None, log=(99.0, 100.5, 101.5, 110.0)):
    return RunData(trace=trace or _trace(), window=WINDOW, records=[],
                   launch_log=[{"t_dispatch": t} for t in log], peaks={})


def test_host_readers_on_planted_records(planted):
    run = _run()
    assert program_spans.syncs_per_launch(run) == 69.0
    assert program_spans.span_ms_per_launch(run, "dse.dispatch") == \
        pytest.approx(110.0)
    assert program_spans.span_ms_per_launch(run, "dse.harvest.finalize") \
        == pytest.approx(30.0)
    assert program_spans.span_ms_per_launch(run, "dse.resolve") == \
        pytest.approx(20.0)
    # 23 requests dispatched in the window (launch 4's at 109.99, before
    # its span opens); the 22nd smallest wait (p95 by nearest rank) is
    # launch 2's longest, 0.49 s
    assert program_spans.wait_p95_ms(run) == pytest.approx(490.0)


def test_idle_in_dispatch_maps_onto_the_trace_clock(planted):
    run = _run()
    off = program_spans.clock_offset(run)
    # the in-order pairing that starts at the second launch
    assert off.pairs == 3
    assert off.median_s == pytest.approx(OFFSET + 3e-6, abs=1e-9)
    assert off.spread_s == pytest.approx(2e-6, abs=1e-9)
    # launches 2 and 3 lie in the trace window: 30 and 100 ms idle
    assert program_spans.idle_in_dispatch_ms(run) == \
        pytest.approx(65.0, abs=0.01)


def test_idle_in_dispatch_is_none_on_a_spread_offset(planted):
    run = _run(_trace(jitter=(3e-6, 4e-4, 2e-6)))
    assert program_spans.clock_offset(run).spread_s > \
        program_spans.OFFSET_SPREAD_S
    assert program_spans.idle_in_dispatch_ms(run) is None
    # a launch log that lacks the window's launches pairs nothing
    assert program_spans.idle_in_dispatch_ms(_run(log=(99.0,))) is None


def test_readers_are_none_without_launches_or_recorder(planted, monkeypatch,
                                                      tiny):
    empty = RunData(trace=_trace(), window=(200.0, 210.0), records=[],
                    launch_log=[], peaks={})
    readers = {m: tiny.reader(m) for m in NEW}
    for m, read in readers.items():
        assert read(empty) is None, m
        assert read(_run()) is not None, m
    # an older program keeps no recorder: nothing to read, no error
    monkeypatch.setattr(program_spans, "_recorder", lambda: None)
    for m, read in readers.items():
        assert read(_run()) is None, m


def test_recorder_missing_from_the_program_reads_none(monkeypatch):
    import sys

    import repro.utils

    monkeypatch.delattr(repro.utils, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    assert program_spans._recorder() is None
    assert program_spans.wait_p95_ms(_run()) is None


@pytest.mark.parametrize("workload,names", [
    ("cnn4.sweep", ["engine.syncs_per_launch.sweep"]),
    ("cnn4.paper_open", NEW[2:]),
])
def test_traced_tiny_run_reports_the_span_metrics(tiny, workload, names):
    out = run_tiny(tiny, workload, trace=True)
    assert out["correct"], out["checks"]
    for m in names:
        assert out["metrics"][m]["value"] > 0, m
    if workload == "cnn4.sweep":
        # 9 key reads at dispatch; the seed check and 4 thin fields
        assert out["metrics"]["engine.syncs_per_launch.sweep"]["value"] \
            == 9 + 5
        # the CPU trace has no TPU plane: no device idle to read
        assert "device.idle_in_dispatch_ms_per_launch.sweep" not in \
            out["metrics"]
