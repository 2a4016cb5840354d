"""Arithmetic shared by the per-layer metric readers (``metrics/*.py``).

Each reader takes a ``harness.RunData`` and returns a number, or None
where the run holds nothing to read (the harness then leaves the metric
out).  A share of a roofline is never returned as 0 for want of data.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import trace_reduce
import work_count
from harness import quantile

# the GA program's jit, as the trace's module line names it
GA_JIT = "_run_ga_batched_thin_jit"
N_GENES = 9


def ga_ms_per_launch(run) -> Optional[float]:
    """Device milliseconds of one execution of the GA program on one
    chip, averaged over the executions wholly inside the window and over
    the chips."""
    total, per_dev = trace_reduce.program_seconds(run.trace, GA_JIT)
    n_dev = len(run.trace.modules)
    if not per_dev:
        return None
    return 1e3 * total / (per_dev * n_dev)


def ga_roofline_pct(run) -> Optional[float]:
    """Least time of the required bytes at the chip's published HBM
    bandwidth over the GA program's device time, both over the same
    executions: those wholly inside the window.  The j-th execution on a
    chip since the trace began ran the j-th launch the span engine
    dispatched (one device stream, launches in dispatch order; the trace
    starts before the window's first dispatch).  A launch's bytes are
    spread over the chips that share it."""
    runs = trace_reduce.program_runs(run.trace, GA_JIT)
    bw = float(run.peaks["hbm_bytes_per_s"])
    n_dev = len(runs)
    need_s = busy = 0.0
    for execs in runs.values():
        for j, s, e in execs:
            if j >= len(run.launch_log):
                return None
            r = run.launch_log[j]
            need_s += work_count.launch_bytes(
                len(r["reqs"]), r["P"], r["G"], r["W"], N_GENES) / (n_dev * bw)
            busy += e - s
    if busy <= 0:
        return None
    return 100.0 * need_s / busy


def host_ms_per_launch(run) -> Optional[float]:
    """Host milliseconds per launch in ``dispatch`` and in ``harvest``
    less its wait for the launch's outputs."""
    launches = run.launches_in_window()
    if not launches:
        return None
    return 1e3 * float(np.mean([r["dispatch_s"] + r["harvest_s"] - r["wait_s"]
                                for r in launches]))


def idle_pct(run) -> Optional[float]:
    busy = trace_reduce.busy_s(run.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / trace_reduce.window_s(run.trace))


def queue_wait_p95_ms(run) -> Optional[float]:
    """95th percentile (nearest rank) over the window's requests of the
    time from the harness's submit to the dispatch of their launch."""
    dispatched = {}
    for r in run.launch_log:
        for rid in r["reqs"]:
            dispatched.setdefault(rid, r["t_dispatch"])
    waits = [dispatched[id(rec["req"])] - rec["t_submit"]
             for rec in run.records if id(rec["req"]) in dispatched]
    if not waits:
        return None
    return 1e3 * quantile(waits, 0.95)
