"""The program's own spans and request records, read for the per-layer
metrics of a run.

The program keeps them in a process-wide recorder (``repro.utils.spans``)
that outlives the service, so a reader reads it after the run.  A launch
is in the window when its ``dse.dispatch`` span starts in ``run.window``:
the harness and the recorder both stamp ``time.perf_counter``.  Every
reader returns None, never 0, where the program keeps no recorder (an
older checkout) or the recorder holds no launch of the window.

The device's idle time inside a launch's dispatch needs the dispatch on
the trace's clock.  ``clock_offset`` pairs the harness's ``bench.dispatch``
spans inside the traced window, in order, with the launches of its launch
log (each span opens just after its launch's ``t_dispatch``); of the
in-order pairings it keeps the one whose offsets agree best, and gives
up where they spread by more than ``OFFSET_SPREAD_S``.
"""
from __future__ import annotations

import bisect
from typing import List, NamedTuple, Optional

import numpy as np

from harness import quantile

DISPATCH_SPAN = "bench.dispatch"
# the offsets of a sound pairing agree to microseconds; a pairing shifted
# by a launch spreads by the jitter of the launch cycle, milliseconds
OFFSET_SPREAD_S = 1e-4


class Offset(NamedTuple):
    median_s: float  # trace clock minus perf_counter
    spread_s: float  # max - min over the pairs
    pairs: int


def _recorder():
    try:
        from repro.utils import spans
    except ImportError:
        return None
    return spans


def window(run):
    """The recorder's snapshot of the window's launches, or None."""
    spans = _recorder()
    if spans is None:
        return None
    snap = spans.snapshot(*run.window)
    return snap if snap.launches else None


def span_ms_per_launch(run, name: str) -> Optional[float]:
    """Mean over the window's launches that have a span ``name`` of its
    summed duration in the launch, in ms."""
    snap = window(run)
    if snap is None:
        return None
    per = {}
    for s in snap.spans:
        if s.name == name:
            per[s.launch] = per.get(s.launch, 0.0) + (s.end - s.start)
    if not per:
        return None
    return 1e3 * float(np.mean(list(per.values())))


def syncs_per_launch(run) -> Optional[float]:
    """Mean over the window's launches of the blocking device->host reads
    counted on ``dse.dispatch`` and ``dse.harvest``."""
    snap = window(run)
    if snap is None:
        return None
    per = {}
    for s in snap.spans:
        if s.name in ("dse.dispatch", "dse.harvest") and "syncs" in s.attrs:
            per.setdefault(s.launch, {})[s.name] = s.attrs["syncs"]
    both = [sum(v.values()) for v in per.values() if len(v) == 2]
    return float(np.mean(both)) if both else None


def wait_p95_ms(run) -> Optional[float]:
    """95th percentile (nearest rank) of service dispatch minus service
    submit over the requests the service dispatched in the window."""
    spans = _recorder()
    if spans is None:
        return None
    lo, hi = run.window
    waits = [r.dispatch - r.submit for r in spans.records()[1]
             if r.submit is not None and r.dispatch is not None
             and lo <= r.dispatch < hi]
    return 1e3 * quantile(waits, 0.95) if waits else None


def clock_offset(run) -> Optional[Offset]:
    """Trace clock minus ``perf_counter``, from the window's
    ``bench.dispatch`` spans paired in order with the launch log."""
    lo = run.trace.window[0]
    starts = sorted(s for n, s, _ in run.trace.spans
                    if n == DISPATCH_SPAN and s > lo)
    log = sorted(r["t_dispatch"] for r in run.launch_log)
    k = len(starts)
    if not k or len(log) < k:
        return None
    best = None
    for shift in range(len(log) - k + 1):
        offs = np.asarray(starts) - np.asarray(log[shift:shift + k])
        spread = float(offs.max() - offs.min())
        if best is None or spread < best.spread_s:
            best = Offset(float(np.median(offs)), spread, k)
    return best


def idle_s(busy: List[tuple], starts: List[float], a: float,
            b: float) -> float:
    """Idle seconds of ``a..b`` around a sorted disjoint busy list whose
    starts are ``starts``."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    covered = 0.0
    for s, e in busy[i:]:
        if s >= b:
            break
        covered += max(0.0, min(e, b) - max(s, a))
    return (b - a) - covered


def idle_in_dispatch_ms(run) -> Optional[float]:
    """Device idle time (the first device's busy complement) inside each
    window launch's ``dse.dispatch``, mean per launch in ms; only launches
    whose dispatch lies wholly inside the traced window count."""
    snap = window(run)
    if snap is None or not run.trace.busy:
        return None
    off = clock_offset(run)
    if off is None or off.spread_s > OFFSET_SPREAD_S:
        return None
    busy = run.trace.busy[sorted(run.trace.busy)[0]]
    starts = [s for s, _ in busy]
    lo, hi = run.trace.window
    idle = []
    for s in snap.spans:
        if s.name != "dse.dispatch":
            continue
        a, b = s.start + off.median_s, s.end + off.median_s
        if lo <= a and b <= hi:
            idle.append(idle_s(busy, starts, a, b))
    return 1e3 * float(np.mean(idle)) if idle else None
