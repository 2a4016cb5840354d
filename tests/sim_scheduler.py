"""Deterministic scheduler simulation harness for the DSE service.

Everything the async/priority front end claims about *scheduling* —
policy ordering, starvation-freedom under aging, deadline-miss
accounting, mid-drain preemption — is a host-side property of
``plan_batch`` + ``DSEService``, independent of XLA.  This harness makes
those claims assertable without a single device launch:

  * ``VirtualClock``  — the service's only time source; tests advance it
    explicitly, so waits, deadlines and latency stats are exact numbers,
    not wall-clock noise.
  * ``StubEngine``    — duck-types ``SearchEngine``'s ``dispatch`` /
    ``harvest`` split: a launch runs whole at harvest (``execute``),
    returns a ``SimResult`` per real request (echoing seed/names, so
    every rid can be checked against its own request), advances the
    clock by a scripted per-launch duration, and records each launch.
  * ``sim_service``   — a ``DSEService`` wired to both.
  * ``run_script``    — drives a scripted submit / advance / step
    interleaving and returns the completion record.

Workload sets are tiny host-numpy ``WorkloadSet``s (``sim_ws``) on the
``jnp`` backend, so nothing here ever touches a device; the real-engine
twin of these assertions lives in tests/test_engine.py.

Used by tests/test_scheduler_sim.py (run in both the 1-device and
fake-8-device CI jobs — the harness is device-count-independent).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.engine import BatchPlan, EngineFault, NonFiniteScoreError, SearchRequest
from repro.serve.dse import DSEService, RetryPolicy
from repro.workloads.pack import WorkloadSet


class VirtualClock:
    """Monotonic clock a test advances by hand.  Pass as the service's
    ``clock=``; every submit stamp, wait, deadline and busy figure then
    reads simulated seconds."""

    def __init__(self, t0: float = 0.0):
        self.t = float(t0)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        assert dt >= 0, f"clock can only move forward, got {dt}"
        self.t += float(dt)
        return self.t


def sim_ws(w: int = 1, l: int = 2, tag: str = "sim") -> WorkloadSet:
    """A tiny host-numpy workload set (never evaluated by the stub)."""
    return WorkloadSet(
        names=tuple(f"{tag}{i}" for i in range(w)),
        feats=np.ones((w, l, 6), np.float32),
        mask=np.ones((w, l), bool),
    )


_WS = sim_ws()


def sim_request(
    seed: int = 0,
    *,
    priority: int = 0,
    deadline_s: Optional[float] = None,
    ws: Optional[WorkloadSet] = None,
    pop_size: int = 8,
    generations: int = 2,
) -> SearchRequest:
    """A real ``SearchRequest`` on the ``jnp`` backend (no table prefill
    at submit) over a host-only workload set."""
    return SearchRequest(
        ws=ws if ws is not None else _WS,
        seed=seed,
        backend="jnp",
        pop_size=pop_size,
        generations=generations,
        priority=priority,
        deadline_s=deadline_s,
    )


@dataclasses.dataclass(frozen=True)
class SimResult:
    """Stands in for a SearchResult; echoes enough of the request that a
    test can assert every rid got the result of ITS OWN request.
    ``partial`` mirrors ``SearchResult.partial`` so the fault-injection
    tests can tell a full result from an anytime one."""

    seed: int
    workload_names: Tuple[str, ...]
    priority: int
    partial: bool = False


@dataclasses.dataclass
class SimLaunch:
    """One recorded StubEngine launch."""

    seeds: List[int]  # real requests only, slot order
    slots: int
    signature: tuple
    start_s: float
    end_s: float


class StubEngine:
    """Duck-types the half of ``SearchEngine`` the service consumes:
    ``max_slots`` and ``dispatch(plan)`` / ``harvest(pending)``.  The
    pending launch is the plan itself; ``harvest`` runs it whole through
    ``execute``, which subclasses override to script faults.  Each
    execute advances the virtual clock by ``launch_s`` (a constant, or a
    callable of the plan — scripted heterogeneous launch times) and logs
    the launch."""

    def __init__(
        self,
        clock: VirtualClock,
        *,
        max_slots: int = 4,
        launch_s: Union[float, Callable[[BatchPlan], float]] = 1.0,
    ):
        self.clock = clock
        self.max_slots = int(max_slots)
        self.launch_s = launch_s
        self.launches: List[SimLaunch] = []

    def dispatch(self, plan: BatchPlan) -> BatchPlan:
        return plan

    def harvest(self, plan: BatchPlan) -> List[SimResult]:
        return self.execute(plan)

    def execute(self, plan: BatchPlan, *, mesh=None,
                dt: Optional[float] = None) -> List[SimResult]:
        t0 = self.clock()
        if dt is None:
            dt = self.launch_s(plan) if callable(self.launch_s) else self.launch_s
        self.clock.advance(dt)
        self.launches.append(SimLaunch(
            seeds=[r.seed for r in plan.requests],
            slots=plan.slots,
            signature=plan.signature,
            start_s=t0,
            end_s=self.clock(),
        ))
        return [
            SimResult(seed=r.seed, workload_names=r.ws.names,
                      priority=r.priority)
            for r in plan.requests
        ]


@dataclasses.dataclass
class SimFault:
    """One recorded FaultyEngine fault (a launch that did NOT complete)."""

    kind: str  # "fail" | "nan"
    start_s: float
    seeds: List[int]


class FaultyEngine(StubEngine):
    """StubEngine with scripted fault injection — the zero-XLA twin of
    the segmented engine's failure modes, driven on the virtual clock.

    ``script`` is consumed one entry per ``execute`` call, in launch
    order (exhausted script -> "ok"):

      * ``"ok"``           — normal launch (``launch_s`` duration)
      * ``"fail"``         — the launch dies after ``fail_s`` virtual
        seconds with an ``EngineFault``
      * ``"nan"``          — the per-launch NaN score guard fires
        (``NonFiniteScoreError``)
      * ``("slow", dt)``   — a normal launch taking ``dt`` seconds

    ``poison_seeds``: any launch containing one of these request seeds
    fails with the NaN guard REGARDLESS of the script — a persistently
    poisoned request, the quarantine scenario: it keeps failing every
    chunk it rides in until the service isolates and quarantines it.

    ``partials=True`` attaches per-request anytime ``SimResult``s
    (``partial=True``) to every raised fault, mirroring
    ``EngineFault.partials`` from the real segmented engine."""

    def __init__(self, clock, *, script: Sequence = (), fail_s: float = 0.1,
                 poison_seeds: Sequence[int] = (), partials: bool = True, **kw):
        super().__init__(clock, **kw)
        self.script = list(script)
        self._cursor = 0
        self.fail_s = float(fail_s)
        self.poison_seeds = set(poison_seeds)
        self.partials = partials
        self.faults: List[SimFault] = []

    def _next_behavior(self):
        if self._cursor < len(self.script):
            b = self.script[self._cursor]
            self._cursor += 1
            return b if isinstance(b, tuple) else (b,)
        return ("ok",)

    def _raise_fault(self, kind: str, plan: BatchPlan):
        t0 = self.clock()
        self.clock.advance(self.fail_s)
        self.faults.append(SimFault(
            kind=kind, start_s=t0, seeds=[r.seed for r in plan.requests]))
        partials = None
        if self.partials:
            partials = [
                SimResult(seed=r.seed, workload_names=r.ws.names,
                          priority=r.priority, partial=True)
                for r in plan.requests
            ]
        cls = NonFiniteScoreError if kind == "nan" else EngineFault
        raise cls(f"injected {kind} at t={t0}", partials=partials)

    def execute(self, plan: BatchPlan, *, mesh=None) -> List[SimResult]:
        if self.poison_seeds & {r.seed for r in plan.requests}:
            self._raise_fault("nan", plan)
        b = self._next_behavior()
        if b[0] in ("fail", "nan"):
            self._raise_fault(b[0], plan)
        if b[0] == "slow":
            return super().execute(plan, mesh=mesh, dt=float(b[1]))
        return super().execute(plan, mesh=mesh)


def sim_service(
    *,
    policy="fifo",
    max_slots: int = 4,
    launch_s: Union[float, Callable[[BatchPlan], float]] = 1.0,
    t0: float = 0.0,
    retry: Optional[RetryPolicy] = None,
    partial_results: bool = False,
    engine_cls=StubEngine,
    pipelined: bool = False,
    **engine_kw,
) -> Tuple[DSEService, VirtualClock, StubEngine]:
    """A service on the virtual clock.  ``engine_cls=FaultyEngine`` (plus
    its kwargs) wires in fault injection; ``sleep`` is the clock's own
    ``advance``, so drains wait out retry backoff in simulated time.
    ``pipelined`` keeps one launch in flight across steps."""
    clock = VirtualClock(t0)
    stub = engine_cls(clock, max_slots=max_slots, launch_s=launch_s,
                      **engine_kw)
    svc = DSEService(engine=stub, policy=policy, clock=clock, retry=retry,
                     partial_results=partial_results, sleep=clock.advance,
                     pipelined=pipelined)
    return svc, clock, stub


# --------------------------------------------------------------- scripting
# Event grammar (deterministic interleavings, executed in list order):
#   ("submit", SearchRequest)  -> enqueue; records the rid
#   ("advance", dt)            -> move the virtual clock
#   ("step",)                  -> one launch (no-op on an empty queue)
#   ("drain",)                 -> step until empty
Event = tuple


@dataclasses.dataclass
class SimTrace:
    """What a script produced, in order."""

    rids: List[int]  # rid per submit event, in script order
    completions: List[Tuple[int, SimResult, float]]  # (rid, result, t_done)

    def completion_order(self) -> List[int]:
        return [rid for rid, _, _ in self.completions]

    def result(self, rid: int) -> SimResult:
        return next(res for r, res, _ in self.completions if r == rid)

    def done_at(self, rid: int) -> float:
        return next(t for r, _, t in self.completions if r == rid)


def run_script(svc: DSEService, clock: VirtualClock,
               events: Sequence[Event]) -> SimTrace:
    trace = SimTrace(rids=[], completions=[])

    def record(done):
        for rid, res in done:
            trace.completions.append((rid, res, clock()))

    for ev in events:
        kind = ev[0]
        if kind == "submit":
            trace.rids.append(svc.submit(ev[1]))
        elif kind == "advance":
            clock.advance(ev[1])
        elif kind == "step":
            record(svc.step())
        elif kind == "drain":
            while svc.pending():
                record(svc.step())
        else:
            raise ValueError(f"unknown sim event {ev!r}")
    return trace


def submit_burst(
    svc: DSEService,
    n: int,
    *,
    priorities: Sequence[int] = (0,),
    deadlines_s: Sequence[Optional[float]] = (None,),
    seed0: int = 0,
) -> List[int]:
    """n sim requests cycling priorities/deadlines; returns rids."""
    pr = itertools.cycle(priorities)
    dl = itertools.cycle(deadlines_s)
    return [
        svc.submit(sim_request(seed0 + i, priority=next(pr),
                               deadline_s=next(dl)))
        for i in range(n)
    ]
