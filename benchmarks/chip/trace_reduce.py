"""Profiler trace -> the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX (``jax.profiler.ProfileData``).  Device planes are the ``/device:TPU:<i>``
planes; on each, the ``XLA Ops`` line holds every operation that ran and
the ``XLA Modules`` line every program execution (named after its jit).
Host spans are the harness's own ``jax.profiler.TraceAnnotation``s, whose
names start with ``bench.``, on the same clock.  ``bench.window`` marks the
measured window.  Operations, busy intervals and host spans are clipped
to it; program executions are kept whole, from the trace's start, so a
reader can take those that lie wholly inside the window and pair each
with the launch it ran.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

Interval = Tuple[float, float]  # seconds on the trace clock

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


class Reduced(NamedTuple):
    window: Interval
    # per device plane name: the busy intervals (union of operations)
    busy: Dict[str, List[Interval]]
    # per device: (name, start, end) of every program execution in the
    # trace, in order, unclipped
    modules: Dict[str, List[Tuple[str, float, float]]]
    # device operation name -> seconds summed over devices, in window
    op_seconds: Dict[str, float]
    # host spans (name, start, end), in window
    spans: List[Tuple[str, float, float]]


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals: List[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``lo..hi`` around a sorted disjoint busy list."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(plane_lines, name):
    for line in plane_lines:
        if line.name == name:
            for ev in line.events:
                yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def reduce_file(path: str) -> Reduced:
    """Reduce one ``.xplane.pb`` (or a gzipped ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    raw_busy: Dict[str, List[Interval]] = {}
    raw_mod: Dict[str, List[Tuple[str, float, float]]] = {}
    raw_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:TPU"):
            ops = list(_events(lines, OPS_LINE))
            mods = list(_events(lines, MODULES_LINE))
            raw_ops[plane.name] = ops
            raw_mod[plane.name] = mods
            raw_busy[plane.name] = [(s, e) for _, s, e in (ops or mods)]
        elif plane.name.startswith("/host"):
            for line in lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    lo, hi = wins[0]
    busy = {d: clip(union(v), lo, hi) for d, v in raw_busy.items()}
    modules = {d: sorted(v, key=lambda x: x[1]) for d, v in raw_mod.items()}
    op_s: Dict[str, float] = defaultdict(float)
    for v in raw_ops.values():
        for n, s, e in v:
            if e > lo and s < hi:
                op_s[n] += min(e, hi) - max(s, lo)
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in spans
             if e > lo and s < hi and n != WINDOW_SPAN]
    return Reduced((lo, hi), busy, modules, dict(op_s), spans)


def window_s(r: Reduced) -> float:
    return r.window[1] - r.window[0]


def busy_s(r: Reduced) -> Optional[float]:
    """Busy seconds averaged over the device planes; None without one."""
    if not r.busy:
        return None
    return sum(length(v) for v in r.busy.values()) / len(r.busy)


def program_runs(r: Reduced, jit_name: str
                 ) -> Dict[str, List[Tuple[int, float, float]]]:
    """Per device, the executions of one program (a module whose name
    contains ``jit_name``) that lie wholly inside the window, each as
    (ordinal, start, end): the ordinal counts that program's executions on
    the device from the start of the trace."""
    lo, hi = r.window
    out: Dict[str, List[Tuple[int, float, float]]] = {}
    for dev, mods in r.modules.items():
        runs = [(s, e) for n, s, e in mods if jit_name in n]
        out[dev] = [(j, s, e) for j, (s, e) in enumerate(runs)
                    if s >= lo and e <= hi]
    return out


def program_seconds(r: Reduced, jit_name: str) -> Tuple[float, float]:
    """Device seconds of the executions of one program that lie wholly
    inside the window, summed over devices, and the number of those
    executions per device (the mean over devices)."""
    runs = program_runs(r, jit_name)
    total = sum(e - s for v in runs.values() for _, s, e in v)
    count = sum(len(v) for v in runs.values())
    return total, (count / len(runs) if runs else 0)


def top_ops(r: Reduced, k: int = 10) -> List[list]:
    per_dev = max(1, len(r.busy))
    items = sorted(r.op_seconds.items(), key=lambda kv: -kv[1])[:k]
    return [[n, s / per_dev] for n, s in items]


def idle_gaps(r: Reduced, k: int = 10) -> List[list]:
    """The longest idle gaps of the first device, each named by the host
    span that covers most of it, the innermost on a tie (``host.none``
    where no span does)."""
    if not r.busy:
        return []
    dev = sorted(r.busy)[0]
    spans = sorted(r.spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    out = []
    for s, e in gaps(r.busy[dev], *r.window):
        best, label = (0.0, 0.0), "host.none"
        lo = bisect.bisect_left(starts, s - longest)
        hi = bisect.bisect_right(starts, e)
        for n, hs, he in spans[lo:hi]:
            key = (min(e, he) - max(s, hs), hs - he)
            if key[0] > 0 and key > best:
                best, label = key, n
        out.append([label, e - s])
    out.sort(key=lambda x: -x[1])
    return out[:k]
