"""Spans and per-request records that the engine and the service keep
about their own work.

``span(name, **attrs)`` is a context manager.  While a profiler session
is active it enters a ``jax.profiler.TraceAnnotation`` of the same name,
so the span lands on the host plane, on the device trace's clock, with
its attributes as event stats.  It also appends one ``SpanRecord``
to an in-memory ring: name, span id, parent span id (a per-thread stack),
launch id, ``time.perf_counter()`` start and end, thread and attributes.
``Span.set`` adds attributes known only at the end (counts), to the
record and to the profiler event.

``request(...)`` appends one ``RequestRecord`` per served request: rid,
launch id, and the ``perf_counter`` stamps at service submit, at dispatch
(removed from the queue) and at resolution.  A request's record and its
launch's spans share the launch id (``new_launch``).

The recorder is always on and process-wide: it stays readable after the
service that wrote it is closed.  Its rings are bounded (``CAPACITY``
records each, oldest dropped), and appends are single ``deque.append``
calls, safe from any thread under the interpreter lock.  It uses
``perf_counter`` rather than a service's injectable clock, so spans of a
service running on a virtual clock still measure real time.

The spans the DSE records (``core/engine.py``, ``serve/dse.py``):

====================== ====================================================
``dse.dispatch``       ``SearchEngine.dispatch``; attrs ``launch``,
                       ``slots``, ``reqs``, ``P``, ``G``, ``W``, ``syncs``
``dse.dispatch.pack``  slot-packed workload tensors, stacked tables and
                       objective operands; attr ``hit``
``dse.dispatch.keys``  per-slot PRNG keys, their stack and split; attrs
                       ``host_keys`` (slots keyed on the host from their
                       seed, with no device read), ``host_split`` (slots
                       whose key was split on the host, with no device op)
``dse.dispatch.seed``  initial populations (seeding program enqueue)
``dse.dispatch.ga``    the GA program enqueue (or the segment chain)
``dse.harvest``        ``SearchEngine.harvest``; attrs ``launch``,
                       ``syncs``, ``bytes``, and where the seeder ran
                       ``seed_slots`` (slots it filled) and
                       ``seed_rounds`` (rounds they drew, summed)
``dse.harvest.wait``   ``block_until_ready`` on the launch's outputs
``dse.harvest.sync``   the device->host reads and the seed check
``dse.harvest.finalize`` host finalize and result-cache writes
``dse.resolve``        the service recording a launch's results and
                       resolving its requests; attrs ``launch``, ``reqs``
====================== ====================================================

``syncs`` counts blocking device->host reads (``SearchEngine._sync``
and ``_any_nan``) made inside the span, ``bytes`` what they moved.
``phase_ms`` and ``counters`` read a snapshot per launch;
``launch/search.py --serve`` prints both.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

CAPACITY = 65536
# the span whose start stamps a launch's dispatch (``snapshot`` windows)
DISPATCH = "dse.dispatch"


class SpanRecord(NamedTuple):
    name: str
    id: int
    parent: int  # 0 at the top of its thread's stack
    launch: Optional[int]
    start: float  # perf_counter seconds
    end: float
    thread: int
    attrs: dict


class RequestRecord(NamedTuple):
    rid: int
    launch: Optional[int]
    submit: Optional[float]  # perf_counter seconds
    dispatch: Optional[float]
    resolve: float


class Snapshot(NamedTuple):
    """The records of a set of launches, in the order they were kept."""

    launches: List[int]
    spans: List[SpanRecord]
    requests: List[RequestRecord]


class Span:
    """One open span (see ``Recorder.span``)."""

    __slots__ = ("_rec", "_stack", "name", "launch", "attrs", "id",
                 "parent", "start", "_ta")

    def __init__(self, rec: "Recorder", name: str, launch: Optional[int],
                 attrs: dict):
        self._rec, self.name, self.launch, self.attrs = rec, name, launch, attrs

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (counts)."""
        self.attrs.update(attrs)
        if self._ta is not None:
            self._ta.set_metadata(**attrs)

    def __enter__(self) -> "Span":
        self._stack = stack = self._rec._stack()
        if stack:
            top = stack[-1]
            self.parent = top.id
            if self.launch is None:
                self.launch = top.launch
        else:
            self.parent = 0
        self.id = next(self._rec._ids)
        stack.append(self)
        # a profiler event is only recorded if a session is active when
        # the annotation opens
        if TraceAnnotation.is_enabled():
            self._ta = TraceAnnotation(self.name, **self.attrs)
            self._ta.__enter__()
        else:
            self._ta = None
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        if self._ta is not None:
            self._ta.__exit__(*exc)
        self._stack.pop()
        # a plain tuple on the hot path; ``snapshot`` names the fields
        self._rec.spans.append((self.name, self.id, self.parent, self.launch,
                                self.start, end, threading.get_ident(),
                                self.attrs))


class Recorder:
    """Bounded rings of span and request records (see the module doc)."""

    def __init__(self, capacity: int = CAPACITY):
        # raw tuples in the field order of SpanRecord / RequestRecord
        self.spans: Deque[tuple] = deque(maxlen=capacity)
        self.requests: Deque[tuple] = deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._launches = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def new_launch(self) -> int:
        """A fresh launch id (process-unique for this recorder)."""
        return next(self._launches)

    def span(self, name: str, **attrs) -> Span:
        """A span named ``name``.  ``launch=`` tags it (and the profiler
        event) with a launch id; a span without one inherits its
        parent's."""
        launch = attrs.get("launch")
        if launch is None:
            attrs.pop("launch", None)
        return Span(self, name, launch, attrs)

    def request(self, rid: int, launch: Optional[int],
                submit: Optional[float], dispatch: Optional[float],
                resolve: float) -> None:
        self.requests.append((rid, launch, submit, dispatch, resolve))

    def records(self) -> Tuple[List[SpanRecord], List[RequestRecord]]:
        """Every span and request record kept, oldest first."""
        return ([SpanRecord._make(s) for s in list(self.spans)],
                [RequestRecord._make(r) for r in list(self.requests)])

    def snapshot(self, lo: float = float("-inf"),
                 hi: float = float("inf")) -> Snapshot:
        """The records of the launches whose ``dse.dispatch`` started in
        ``[lo, hi)`` on ``perf_counter``."""
        spans, reqs = self.records()
        launches = [s.launch for s in spans
                    if s.name == DISPATCH and lo <= s.start < hi]
        keep = set(launches)
        return Snapshot(launches, [s for s in spans if s.launch in keep],
                        [r for r in reqs if r.launch in keep])

    def clear(self) -> None:
        self.spans.clear()
        self.requests.clear()


def phase_ms(snap: Snapshot) -> Dict[str, float]:
    """Milliseconds per launch of each span name in ``snap``: the summed
    durations over the snapshot's launches."""
    n = len(snap.launches)
    out: Dict[str, float] = {}
    if not n:
        return out
    for s in snap.spans:
        out[s.name] = out.get(s.name, 0.0) + 1e3 * (s.end - s.start) / n
    return out


def counters(snap: Snapshot) -> Dict[str, float]:
    """Per-launch readings of the span counters in ``snap``: ``syncs``
    (blocking reads of a launch's dispatch and harvest), ``bytes`` (what
    its harvest moved), both means, ``pack_hit`` (the share of launches
    whose pack hit both content caches), ``host_keys`` and ``host_split``
    (the mean share of a launch's slots keyed, and whose key was split, on
    the host) and, where some launch
    seeded a slot, ``seed_rounds`` (the seeder's rounds a seeded slot)."""
    n = len(snap.launches)
    if not n:
        return {}
    out = {"syncs": 0.0, "bytes": 0.0, "pack_hit": 0.0, "host_keys": 0.0,
           "host_split": 0.0}
    slots = {s.launch: s.attrs.get("slots") for s in snap.spans
             if s.name == DISPATCH}
    seeded = [0, 0]  # slots, rounds
    for s in snap.spans:
        if s.name in (DISPATCH, "dse.harvest"):
            out["syncs"] += s.attrs.get("syncs", 0) / n
        if s.name == "dse.harvest":
            out["bytes"] += s.attrs.get("bytes", 0) / n
            seeded[0] += s.attrs.get("seed_slots", 0)
            seeded[1] += s.attrs.get("seed_rounds", 0)
        elif s.name == "dse.dispatch.pack":
            out["pack_hit"] += bool(s.attrs.get("hit")) / n
        elif s.name == "dse.dispatch.keys" and slots.get(s.launch):
            for k in ("host_keys", "host_split"):
                out[k] += s.attrs.get(k, 0) / (slots[s.launch] * n)
    if seeded[0]:
        out["seed_rounds"] = seeded[1] / seeded[0]
    return out


# the process-wide recorder the engine and the service write to
RECORDER = Recorder()
span = RECORDER.span
new_launch = RECORDER.new_launch
request = RECORDER.request
snapshot = RECORDER.snapshot
records = RECORDER.records
clear = RECORDER.clear
