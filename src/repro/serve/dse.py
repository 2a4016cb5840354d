"""DSE-as-a-service: continuous batching of heterogeneous search requests.

The design-search twin of ``serve.engine`` (which continuous-batches LM
prefill/decode into fixed slots): clients ``submit`` ``SearchRequest``s —
any mix of workload sets, objectives, areas, seeds and backends — and the
service drains the queue slot-packed into as few XLA launches as possible
through the shared ``core.engine.SearchEngine``:

  * ``submit``  — enqueue a request, returns a request id.  Table-backend
    requests get their factorized cost tables built (fingerprint-memoized)
    at ingest, the way the LM engine prefills on admission — the drain
    itself then launches only the cached seeding + GA programs.
  * ``step``    — run ONE launch cycle: dispatch the next plan of the
    current queue and harvest the launch in flight (with ``pipelined``,
    the previous one; otherwise this one); finished results free their
    slots immediately and newly submitted requests join the next step's
    packing.
  * ``drain``   — step until the queue is empty; returns {rid: result}.
  * ``stream``  — generator form of drain: yields (rid, SearchResult) per
    completed plan, so callers consume results while later plans run.

Scheduling is policy-driven (``core.engine.SchedulingPolicy``): ``fifo``
(submit order), ``priority`` (``SearchRequest.priority``, 0 = most
urgent, with wait-time aging so nothing starves) or ``edf``
(``SearchRequest.deadline_s`` seconds-from-submit, converted to an
absolute deadline on the service clock at ingest).  The policy reorders
the queue and the launch order; it never changes which compiled program
a request hits, nor any result bit (every search is self-contained).

``AsyncDSEService`` runs the same launch cycle behind a worker thread:
``submit`` returns a ``concurrent.futures.Future`` immediately, requests
submitted while a launch is in flight join the NEXT launch's packing
(the cycle holds the lock only around queue surgery, never around the
engine's ``dispatch``/``harvest``), and an urgent submission therefore
preempts all still-queued work at the next launch boundary.

Because the ``table`` backend's traced ctx is layer-free, requests over
*different* workload sets share one compiled program: 256 mixed requests
(subsets x objectives x seeds) drain through 4 launches of 2 cached
programs, bit-identical to running each request alone
(tests/test_engine.py).  ``mesh=`` lays every launch over the 2-D
(search, population) device mesh.

``ServiceStats`` tracks per-request queue-wait and end-to-end latency
samples on the service clock (the telemetry deadline policies need) and
deadline misses; ``tests/sim_scheduler.py`` drives all of the above
against a virtual clock and a stub engine, so every scheduling claim is
asserted without an XLA launch.  Where the host time goes is recorded by
``utils.spans``: the engine's ``dse.dispatch`` / ``dse.harvest`` spans,
the service's ``dse.resolve`` span per launch, and one record per served
request (submit, dispatch and resolution stamps on ``perf_counter``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import (
    Callable,
    Deque,
    Dict,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from repro.core.engine import (
    BatchPlan,
    EngineFault,
    RequestMeta,
    SearchEngine,
    SearchRequest,
    SearchResult,
    empty_partial_result,
    get_policy,
    plan_batch,
)
from repro.core.objectives import OBJECTIVES
from repro.utils import spans
from repro.workloads.pack import WorkloadSet


def _percentile(samples: Sequence[float], q: float) -> Optional[float]:
    # None, not NaN, on an empty window: NaN is invalid JSON and poisons
    # any bench row serializing a fresh service's summary()
    if not samples:
        return None
    return float(np.percentile(np.asarray(samples, np.float64), q))


# Per-request samples kept for percentile telemetry: a bounded recent
# window (deque maxlen), so a long-lived service's memory stays O(1) and
# the percentiles describe recent traffic rather than all-time history.
SAMPLE_WINDOW = 4096
LAUNCH_LOG_WINDOW = 4096


# a launch between dispatch and harvest: (plan, rids, dispatch stamp on
# perf_counter, the engine's PendingLaunch)
_InFlight = Tuple[BatchPlan, List[int], float, object]


def _deliver(claimed: Sequence[Tuple[Optional[Future], object]],
             exc: Optional[BaseException] = None) -> None:
    """Resolve claimed futures OUTSIDE the front end's lock (their
    done-callbacks may submit): each with its result, or with ``exc``
    where the result is ``None``.  ``None`` futures were taken by a
    timed-out ``close`` and are skipped."""
    for fut, res in claimed:
        if fut is None:
            continue
        if res is None:
            fut.set_exception(exc)
        else:
            fut.set_result(res)


@dataclasses.dataclass
class ServiceStats:
    """Running drain telemetry (the bench's requests/s row reads these).

    ``wait_samples`` (dispatch - submit) and ``latency_samples``
    (complete - submit) are per-request, on the service clock, bounded
    to the most recent ``SAMPLE_WINDOW`` completions, so
    ``wait_p``/``latency_p`` percentiles describe what clients recently
    experienced; ``deadline_misses`` counts requests completed after
    their absolute deadline (any policy — EDF just minimizes it).
    After an engine failure ``submitted`` stays ahead of ``completed``:
    failed requests are never counted as served.

    Fault telemetry: ``failures`` counts failed request-attempts (every
    rid in a failed launch, once per failed attempt), ``retries`` the
    re-queues a ``RetryPolicy`` scheduled, ``partials`` the requests
    resolved with an anytime ``partial=True`` result (quarantine or
    deadline sweep — these DO count as completed), and ``abandoned`` the
    requests dropped for good with no result (no retry policy / retries
    exhausted without partial results).

    ``cache_hits`` counts requests resolved AT SUBMIT from the result
    cache (zero launches; they count as completed with 0 wait/latency);
    ``cache_misses`` the submits that had a cache and missed it.
    ``cache_hit_rate()`` is hits over looked-up submits (0.0 before any
    lookup) — the service-level view of the cache's own
    ``CacheStats.hit_rate()``, which additionally distinguishes the
    memory and disk tiers.

    Where a launch's host time goes (dispatch, harvest and resolution
    phases) is recorded by ``utils.spans``, not here.

    Percentiles over empty sample windows are ``None`` (a fresh service
    has no telemetry) — never NaN, which is invalid JSON and poisons
    serialized bench rows."""

    submitted: int = 0
    completed: int = 0
    launches: int = 0
    deadline_misses: int = 0
    failures: int = 0
    retries: int = 0
    partials: int = 0
    abandoned: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    wait_samples: Deque[float] = dataclasses.field(
        default_factory=lambda: deque(maxlen=SAMPLE_WINDOW))
    latency_samples: Deque[float] = dataclasses.field(
        default_factory=lambda: deque(maxlen=SAMPLE_WINDOW))

    def wait_p(self, q: float) -> Optional[float]:
        """Queue-wait percentile in seconds (q in [0, 100]); ``None``
        when the sample window is empty."""
        return _percentile(self.wait_samples, q)

    def latency_p(self, q: float) -> Optional[float]:
        """End-to-end (submit -> complete) latency percentile in
        seconds; ``None`` when the sample window is empty."""
        return _percentile(self.latency_samples, q)

    def cache_hit_rate(self) -> float:
        """Fraction of cache-looked-up submits resolved at submit (0.0
        before any lookup — a cacheless or cold service reports 0)."""
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    def summary(self) -> Dict[str, Optional[float]]:
        return {
            "wait_p50_s": self.wait_p(50), "wait_p99_s": self.wait_p(99),
            "latency_p50_s": self.latency_p(50),
            "latency_p99_s": self.latency_p(99),
            "deadline_misses": self.deadline_misses,
            "failures": self.failures,
            "retries": self.retries,
            "partials": self.partials,
            "abandoned": self.abandoned,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_hit_rate": self.cache_hit_rate(),
        }


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff with deterministic jitter on the SERVICE clock.

    ``max_attempts`` is the TOTAL launch attempts a request gets (so
    ``max_attempts=3`` means the original try plus 2 retries); after the
    n-th failure the retry is scheduled ``delay_s(n, rid)`` seconds out.
    Jitter is a pure hash of (rid, attempt) — no wall-clock entropy — so
    a scripted fault drill replays to the exact same schedule."""

    max_attempts: int = 3
    backoff_s: float = 0.5
    multiplier: float = 2.0
    max_backoff_s: float = 30.0
    jitter: float = 0.1  # +/- fraction of the base delay

    def delay_s(self, attempt: int, rid: int = 0) -> float:
        base = min(self.backoff_s * self.multiplier ** (max(attempt, 1) - 1),
                   self.max_backoff_s)
        if self.jitter <= 0 or base <= 0:
            return base
        u = ((rid * 2654435761 + attempt * 40503) % 4096) / 4096.0
        return base * (1.0 + self.jitter * (2.0 * u - 1.0))


@dataclasses.dataclass
class _Retry:
    """One queued retry: dispatched alone (re-plan isolation) once the
    service clock passes ``not_before``."""

    not_before: float
    rid: int
    req: SearchRequest
    attempts: int  # failed attempts so far


class DSEService:
    """Continuous-batching front end over a ``SearchEngine``.

    ``policy`` is a name (fifo / priority / edf) or a
    ``SchedulingPolicy`` instance; ``clock`` (default ``time.monotonic``)
    is the ONLY time source of scheduling — submit stamps, waits and
    deadlines all read it, so a virtual clock makes every scheduling
    decision and every stat deterministic (tests/sim_scheduler.py).  The
    spans and request records (``utils.spans``) stamp ``perf_counter``
    beside it and decide nothing.

    Fault tolerance (both OFF by default — behaviour is then exactly the
    pre-retry service: sync ``step()`` rolls back and re-raises, the
    async worker fails futures):

      * ``retry`` (a ``RetryPolicy``): a failed launch re-queues each of
        its requests into an isolated retry lane — every retry is
        re-planned ALONE, so one poisoned request stops failing its
        chunk-mates — with exponential backoff on the service clock.  A
        request that exhausts ``max_attempts`` is quarantined: resolved
        with its best-so-far partial result (``partial_results=True``) or
        abandoned into ``self.failed``.
      * ``partial_results=True``: graceful degradation — a quarantined
        request, and any queued request observed past its deadline,
        resolves with its checkpointed/anytime best (``partial=True``,
        ``EngineFault.partials`` or an empty invalid result) instead of
        nothing.
      * ``sleep`` (default ``time.sleep``): how ``drain``/``stream`` wait
        out retry backoff; the sim passes the virtual clock's ``advance``.

    Result caching (``result_cache``, a ``serve.cache.ResultCache``): a
    submit whose ``request_key`` is cached resolves IMMEDIATELY — the
    request never queues, never launches, and counts as completed with 0
    wait/latency (``stats.cache_hits``).  Misses populate the cache at
    ``_complete`` (full results only; partials never enter), so
    re-submitting an identical mix drains with zero new GA launches and
    bit-identical results.  When the engine was built by this service
    the cache is shared with it; an explicitly passed engine keeps its
    own ``result_cache`` (and the service adopts it if not given one).

    The engine must have ``SearchEngine``'s ``dispatch``/``harvest``
    split.  Every front end drains through one launch cycle
    (``_cycle``): sweep deadlines -> ``_dispatch`` -> ``engine.dispatch``
    -> ``engine.harvest`` of the oldest launch in flight -> ``_complete``
    or the failure path.  ``pipelined=True`` keeps one launch in flight
    across cycles: each cycle DISPATCHES plan i+1 (JAX async — the device
    starts computing) before HARVESTING plan i (the host-blocking
    finalize), so host packing of one launch overlaps device compute of
    the next; otherwise a launch is harvested right after its own
    dispatch.  Results are bit-identical either way — only the launch
    interleaving changes — and a pipelined engine's results carry
    ``ga=None`` (transfer-thin; see ``SearchEngine``).  The knob is
    inherited from an explicitly passed engine's own ``pipelined`` flag
    when left ``None``.
    """

    def __init__(
        self,
        *,
        engine: Optional[SearchEngine] = None,
        mesh=None,
        max_slots: int = 64,
        policy="fifo",
        clock=time.monotonic,
        retry: Optional[RetryPolicy] = None,
        partial_results: bool = False,
        sleep=None,
        result_cache=None,
        pipelined: Optional[bool] = None,
    ):
        self.engine = engine or SearchEngine(mesh=mesh, max_slots=max_slots,
                                             result_cache=result_cache,
                                             pipelined=bool(pipelined))
        if pipelined is None:
            self.pipelined = bool(getattr(self.engine, "pipelined", False))
        else:
            self.pipelined = bool(pipelined)
        self.result_cache = (
            result_cache if result_cache is not None
            else getattr(self.engine, "result_cache", None)
        )
        self.policy = get_policy(policy)
        self.clock = clock
        # wall-clock aging horizon (PriorityPolicy only): a cached plan
        # list is ordered by priorities computed at build time, so once
        # ``aging_s`` passes, some queued request has earned a promotion
        # the cache cannot reflect — ``_dispatch`` invalidates and
        # re-plans (on the warm slot hints: zero new compiled programs).
        # Without this, aging only applied when a submit happened to
        # land, and a busy drain could starve an aged request forever.
        self._aging_s: Optional[float] = getattr(self.policy, "aging_s", None)
        self._plans_built_s: float = 0.0
        self.retry = retry
        self.partial_results = bool(partial_results)
        self._sleep = time.sleep if sleep is None else sleep
        # retry lane + per-rid fault bookkeeping
        self._retry_lane: List[_Retry] = []
        self._attempts: Dict[int, int] = {}
        self._partials: Dict[int, SearchResult] = {}  # best-so-far per rid
        self.failed: Dict[int, BaseException] = {}  # quarantined, no result
        self.queue: List[Tuple[int, SearchRequest]] = []
        self.results: Dict[int, SearchResult] = {}
        self.stats = ServiceStats()
        self.launch_log: List[List[int]] = []  # rids per launch, in order
        self._next_rid = 0
        # per-rid queue facts: submit stamp + absolute deadline (clock() +
        # SearchRequest.deadline_s at ingest) — what the policy keys on
        self._submit_s: Dict[int, float] = {}
        self._deadline_s: Dict[int, Optional[float]] = {}
        # per-rid submit stamp on perf_counter, for the request records
        self._submit_pc: Dict[int, float] = {}
        # signature -> slot size of the last plan that used it: re-plans
        # (mid-drain submits) round small residues UP to this warm program
        # size instead of compiling an exact-size one
        self._slot_hints: Dict[tuple, int] = {}
        # plans for the current queue snapshot; invalidated on submit so
        # a quiescent drain keeps plan_batch's padded-tail chunking (every
        # chunk of a group = ONE compiled program) instead of re-planning
        # the shrunken residue into a fresh program shape each step
        self._plans_cache: Optional[List[BatchPlan]] = None
        self._snapshot: List[Tuple[int, SearchRequest]] = []
        # mid-search best-so-far stream subscribers, per rid
        self._progress_cbs: Dict[int, Callable] = {}
        # launches dispatched but not yet harvested, oldest first: at most
        # one across cycles, under ``pipelined``
        self._inflight: Deque[_InFlight] = deque()
        # guards queue surgery and stats when this service is the front
        # end (``_cycle``'s ``front``); a single caller needs no lock
        self._lock = contextlib.nullcontext()

    # ------------------------------------------------------------- admission
    def submit(self, req: SearchRequest, *, on_progress=None) -> int:
        """Enqueue one request; returns its rid.  Validates the request's
        signature eagerly (bad objectives/backends fail at submit, not
        mid-drain) and pre-builds table-backend cost tables so drains only
        launch the cached seeding/GA programs.

        A result-cache hit resolves the rid right here: the result is in
        ``self.results`` before ``submit`` returns, nothing queues, and
        no launch ever runs for it.

        ``on_progress(rid, partial)`` subscribes to the request's
        mid-search best-so-far stream: called after every guarded GA
        segment with a monotone ``partial=True`` snapshot (requires an
        engine with ``segment_gens``; single-shot engines have no
        mid-search boundaries and never call it).  Callbacks run on the
        draining thread, between segment launches."""
        req.signature()
        if self.result_cache is not None:
            hit = self.result_cache.get(req)
            if hit is not None:
                rid = self._next_rid
                self._next_rid += 1
                self.results[rid] = hit
                self.stats.submitted += 1
                self.stats.completed += 1
                self.stats.cache_hits += 1
                self.stats.wait_samples.append(0.0)
                self.stats.latency_samples.append(0.0)
                return rid
            self.stats.cache_misses += 1
        if req.backend == "table":
            req.ws.tables(req.tech)  # fingerprint-memoized ingest prefill
        now = self.clock()
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append((rid, req))
        self._submit_s[rid] = now
        self._submit_pc[rid] = time.perf_counter()
        self._deadline_s[rid] = (
            None if req.deadline_s is None else now + float(req.deadline_s)
        )
        if on_progress is not None:
            self._progress_cbs[rid] = on_progress
        self.stats.submitted += 1
        self._plans_cache = None  # next step re-packs the grown queue
        return rid

    def submit_all(self, reqs: Sequence[SearchRequest]) -> List[int]:
        return [self.submit(r) for r in reqs]

    def pending(self) -> int:
        """Requests not yet resolved: queued, awaiting a retry, or in a
        launch in flight."""
        return (len(self.queue) + len(self._retry_lane)
                + sum(len(f[1]) for f in self._inflight))

    # --------------------------------------------------------------- serving
    def _plans(self) -> List[BatchPlan]:
        """Plans over the current queue snapshot, cached across steps: a
        drain executes the ONE padded chunking plan_batch produced (plan
        indices refer to the snapshot), and only a new submission forces
        a re-pack — where the slot hints keep re-planned residues on the
        warm program shapes."""
        if self._plans_cache is None:
            now = self.clock()
            self._plans_built_s = now
            self._snapshot = list(self.queue)
            meta = [
                RequestMeta(
                    seq=rid,
                    priority=int(r.priority),
                    wait_s=now - self._submit_s[rid],
                    deadline_s=self._deadline_s[rid],
                )
                for rid, r in self._snapshot
            ]
            self._plans_cache = plan_batch(
                [r for _, r in self._snapshot],
                max_slots=self.engine.max_slots,
                policy=self.policy,
                meta=meta,
                slot_hints=self._slot_hints,
            )
            for p in self._plans_cache:
                self._slot_hints[p.signature] = p.slots
        return self._plans_cache

    def _dispatch(self) -> Optional[Tuple[BatchPlan, List[int], float]]:
        """Pick the policy's next plan and remove its requests from the
        queue — the admission point: everything still queued after this
        (including anything submitted while the launch runs) is free to
        re-plan.  Returns (plan, rids, dispatch stamp); pure queue
        surgery, no device work, so the async front end holds its lock
        only across this and ``_complete``.  The dispatch stamp is on
        ``perf_counter`` (the request records'; waits use the clock).

        Due retries dispatch FIRST, one per step, each re-planned alone
        (quarantine isolation: a poisoned request can only fail its own
        launch from here on) on the warm slot hints."""
        now = self.clock()
        due = [e for e in self._retry_lane if e.not_before <= now]
        if due:
            e = min(due, key=lambda e: (e.not_before, e.rid))
            self._retry_lane.remove(e)
            plan = plan_batch([e.req], max_slots=self.engine.max_slots,
                              slot_hints=self._slot_hints)[0]
            self.stats.wait_samples.append(now - self._submit_s[e.rid])
            return plan, [e.rid], time.perf_counter()
        if not self.queue:
            return None
        if (self._plans_cache is not None and self._aging_s is not None
                and now - self._plans_built_s >= self._aging_s):
            # aging re-plan: the cached plan order is >= aging_s old, so
            # wait-time promotions have accrued that it cannot reflect —
            # rebuild with fresh wait_s (see __init__; starvation-freedom
            # is pinned on the virtual clock in tests/test_scheduler_sim.py)
            self._plans_cache = None
        plans = self._plans()
        plan = plans.pop(0)
        if not plans:
            self._plans_cache = None
        rids = [self._snapshot[qi][0] for qi in plan.indices]
        taken = set(rids)
        self.queue = [q for q in self.queue if q[0] not in taken]
        now = self.clock()
        for rid in rids:
            self.stats.wait_samples.append(now - self._submit_s[rid])
        return plan, rids, time.perf_counter()

    def _drop_wait_samples(self, n: int) -> None:
        for _ in range(min(n, len(self.stats.wait_samples))):
            self.stats.wait_samples.pop()  # newest = this dispatch's

    def _rollback(self, plan: BatchPlan, rids: List[int]) -> None:
        """Undo a dispatch whose launch failed (sync front end): the requests
        return to the queue with their original submit stamps intact —
        ``step()`` stays retryable — and the dispatch's wait samples are
        dropped (the requests were never served)."""
        self._drop_wait_samples(len(rids))
        self.queue = list(zip(rids, plan.requests)) + self.queue
        self._plans_cache = None  # the popped plan list is now stale

    def _abandon(self, rids: List[int]) -> None:
        """Drop failed in-flight requests for good (async path: their
        futures carry the exception): purge per-rid bookkeeping so a
        long-lived worker that survives engine failures leaks nothing
        and keeps wait/latency sample counts consistent.  Counted in
        ``stats.abandoned`` — never silently dropped."""
        self._drop_wait_samples(len(rids))
        for rid in rids:
            self._submit_s.pop(rid, None)
            self._submit_pc.pop(rid, None)
            self._deadline_s.pop(rid, None)
            self._attempts.pop(rid, None)
            self._partials.pop(rid, None)
            self._progress_cbs.pop(rid, None)
        self.stats.abandoned += len(rids)

    # -------------------------------------------------- fault tolerance
    def _next_retry_due(self) -> Optional[float]:
        if not self._retry_lane:
            return None
        return min(e.not_before for e in self._retry_lane)

    def _resolve_partial(self, rid: int, req: SearchRequest,
                         now: float) -> Tuple[int, SearchResult]:
        """Resolve a rid with its best-so-far anytime result (stored
        ``EngineFault`` partial, else an empty invalid one).  Partials
        count as completions — the rid has a result — and as a deadline
        miss when applicable."""
        res = self._partials.pop(rid, None)
        if res is None:
            res = empty_partial_result(req)
        elif getattr(res, "partial", True) is False:
            res = dataclasses.replace(res, partial=True)
        self.results[rid] = res
        self.stats.partials += 1
        self.stats.completed += 1
        waited = now - self._submit_s.pop(rid)
        self._submit_pc.pop(rid, None)
        self.stats.wait_samples.append(waited)
        self.stats.latency_samples.append(waited)
        dl = self._deadline_s.pop(rid, None)
        if dl is not None and now > dl:
            self.stats.deadline_misses += 1
        self._attempts.pop(rid, None)
        self._progress_cbs.pop(rid, None)
        return rid, res

    def _sweep_deadlines(self) -> List[Tuple[int, SearchResult]]:
        """Graceful degradation (``partial_results=True`` only): any
        QUEUED request — main queue or retry lane — observed past its
        absolute deadline resolves immediately with its best-so-far
        partial instead of burning a launch it already missed."""
        now = self.clock()
        out: List[Tuple[int, SearchResult]] = []

        def expired(rid: int) -> bool:
            dl = self._deadline_s.get(rid)
            return dl is not None and now > dl

        dead = [(rid, req) for rid, req in self.queue if expired(rid)]
        if dead:
            gone = {rid for rid, _ in dead}
            self.queue = [q for q in self.queue if q[0] not in gone]
            self._plans_cache = None
        dead += [(e.rid, e.req) for e in self._retry_lane if expired(e.rid)]
        self._retry_lane = [e for e in self._retry_lane if not expired(e.rid)]
        for rid, req in dead:
            out.append(self._resolve_partial(rid, req, now))
        return out

    def _handle_failure(
        self, plan: BatchPlan, rids: List[int], exc: BaseException
    ) -> Tuple[List[Tuple[int, SearchResult]], List[int]]:
        """The retry-policy failure path for one failed launch: harvest
        any anytime partials the fault carried, then per request either
        schedule an isolated backed-off retry, resolve with the partial
        best (quarantine under ``partial_results``), or abandon into
        ``self.failed``.  Returns (partial resolutions, abandoned rids)
        — the async worker fails the latter's futures."""
        assert self.retry is not None
        self._drop_wait_samples(len(rids))
        if isinstance(exc, EngineFault) and exc.partials:
            for rid, p in zip(rids, exc.partials):
                if p is not None:
                    self._partials[rid] = p
        now = self.clock()
        resolutions: List[Tuple[int, SearchResult]] = []
        failed: List[int] = []
        for rid, req in zip(rids, plan.requests):
            a = self._attempts.get(rid, 0) + 1
            self._attempts[rid] = a
            self.stats.failures += 1
            if a < self.retry.max_attempts:
                self._retry_lane.append(_Retry(
                    not_before=now + self.retry.delay_s(a, rid),
                    rid=rid, req=req, attempts=a,
                ))
                self.stats.retries += 1
            elif self.partial_results:
                resolutions.append(self._resolve_partial(rid, req, now))
            else:
                self.failed[rid] = exc
                failed.append(rid)
        for rid in failed:  # wait samples already dropped above
            self._submit_s.pop(rid, None)
            self._submit_pc.pop(rid, None)
            self._deadline_s.pop(rid, None)
            self._attempts.pop(rid, None)
            self._partials.pop(rid, None)
            self._progress_cbs.pop(rid, None)
        self.stats.abandoned += len(failed)
        return resolutions, failed

    def _complete(
        self, rids: List[int], results: Sequence[SearchResult],
        reqs: Optional[Sequence[SearchRequest]] = None,
        launch: Optional[int] = None, dispatched: Optional[float] = None,
    ) -> List[Tuple[int, SearchResult]]:
        """Record one finished launch: results, latency/deadline stats,
        result-cache population (``reqs`` aligns with ``rids``; full
        results only — ``ResultCache.put`` refuses partials itself), and
        one request record per rid under ``launch`` (the id the engine
        stamped on the plan; None from an engine that records no spans),
        with its ``dispatched`` stamp (``perf_counter``)."""
        now = self.clock()
        resolved = time.perf_counter()
        self.stats.launches += 1
        self.launch_log.append(list(rids))
        if len(self.launch_log) > LAUNCH_LOG_WINDOW:
            del self.launch_log[: len(self.launch_log) - LAUNCH_LOG_WINDOW]
        done: List[Tuple[int, SearchResult]] = []
        for i, (rid, res) in enumerate(zip(rids, results)):
            self.results[rid] = res
            if self.result_cache is not None and reqs is not None:
                self.result_cache.put(reqs[i], res)
            self.stats.latency_samples.append(now - self._submit_s[rid])
            spans.request(rid, launch, self._submit_pc.pop(rid, None),
                          dispatched, resolved)
            dl = self._deadline_s.pop(rid, None)
            self._submit_s.pop(rid, None)
            self._attempts.pop(rid, None)
            self._partials.pop(rid, None)
            self._progress_cbs.pop(rid, None)
            if dl is not None and now > dl:
                self.stats.deadline_misses += 1
            done.append((rid, res))
        self.stats.completed += len(done)
        return done

    def _progress_kw(self, rids: List[int]) -> Dict[str, Callable]:
        """The ``on_progress`` kwarg for one launch, mapping the engine's
        plan-local index to the subscribed rid — or ``{}`` when no rid in
        the plan subscribed, so engines without the parameter (stubs,
        fault-injection wrappers) are never handed an unknown kwarg."""
        cbs = [self._progress_cbs.get(rid) for rid in rids]
        if not any(cb is not None for cb in cbs):
            return {}

        def bridge(i: int, snap: SearchResult, _cbs=cbs, _rids=rids):
            cb = _cbs[i]
            if cb is not None:
                cb(_rids[i], snap)

        return {"on_progress": bridge}

    # ------------------------------------------------------ launch cycle
    def _admit(self) -> Tuple[List[Tuple[int, SearchResult]],
                              Optional[Tuple[BatchPlan, List[int], float]]]:
        """The admission half of a cycle, under the front end's lock:
        deadline-swept partial resolutions, then the next dispatch (or
        ``None``).  A front end's ``_admit`` returns ``None`` once it is
        closed; the sync front end never closes."""
        swept = self._sweep_deadlines() if self.partial_results else []
        return swept, self._dispatch()

    def _claim(self, done: Sequence[Tuple[int, SearchResult]],
               failed: Sequence[int] = ()
               ) -> List[Tuple[Optional[Future], object]]:
        """Under the front end's lock: the clients of ``done`` (rid,
        result) pairs and ``failed`` rids, as (future, result or ``None``)
        pairs for ``_deliver`` to resolve outside it.  The sync front
        end's callers read results off ``step()``, so it has none."""
        return []

    def _cycle(self, front=None, admit: bool = True
               ) -> Optional[List[Tuple[int, SearchResult]]]:
        """One launch cycle — the only drain loop body, shared by
        ``step()``/``stream()`` and the ``AsyncDSEService`` worker:
        sweep deadlines -> ``_dispatch`` -> ``engine.dispatch`` ->
        ``engine.harvest`` of the oldest launch in flight -> ``_complete``
        or the failure path.  The depth in flight after a cycle that
        dispatched is 1 under ``pipelined`` (it harvests the previous
        launch, whose host finalize overlaps this one's device compute)
        and 0 otherwise (a launch is harvested right after its own
        dispatch).  A cycle that dispatches nothing harvests whatever is
        in flight.

        ``front`` is the front end (default: this service): its
        ``_admit`` runs the admission half under its lock, and its
        ``_lock`` guards ``_complete``, the failure path and the
        ``_claim`` of the clients that ``_deliver`` resolves outside it.
        The engine calls run outside every lock.  ``admit=False`` only
        harvests (settling the launch in flight).  Returns the (rid,
        result) pairs the cycle resolved, or ``None`` when the front end
        is closed."""
        front = self if front is None else front
        out: List[Tuple[int, SearchResult]] = []
        d = None
        if admit:
            got = front._admit()
            if got is None:
                return None
            out, d = got
        if d is not None:
            plan, rids, td = d
            try:
                pend = self.engine.dispatch(plan, **self._progress_kw(rids))
            except BaseException as e:  # noqa: BLE001 — the failure path decides
                return out + self._settle(front, plan, rids, td, e)
            self._inflight.append((plan, rids, td, pend))
        if self._inflight and (d is None
                               or len(self._inflight) > self.pipelined):
            plan, rids, td, pend = self._inflight.popleft()
            try:
                results = self.engine.harvest(pend)
            except BaseException as e:  # noqa: BLE001 — the failure path decides
                results = e
            out += self._settle(front, plan, rids, td, results)
        return out

    def _settle(self, front, plan: BatchPlan, rids: List[int], td: float,
                results) -> List[Tuple[int, SearchResult]]:
        """Resolve one launch under its ``dse.resolve`` span: complete its
        harvested ``results``, or — ``results`` is then the exception —
        take the failed launch down the failure path.  With a ``retry``
        policy that is the retry lane / quarantine; without one the sync
        front end rolls the launch back and re-raises (``step()`` stays
        retryable), while a worker front end abandons its requests and
        fails their futures.  A ``BaseException`` other than
        ``Exception`` (KeyboardInterrupt & co) always rolls back and
        surfaces on the sync front end — the kill half of the kill/resume
        contract."""
        failed: List[int] = []
        with spans.span("dse.resolve", launch=plan.launch, reqs=len(rids)):
            with front._lock:
                if not isinstance(results, BaseException):
                    done = self._complete(rids, results, plan.requests,
                                          plan.launch, td)
                elif front is self and (self.retry is None or not
                                        isinstance(results, Exception)):
                    self._rollback(plan, rids)
                    raise results
                elif self.retry is None:
                    self._abandon(rids)
                    done, failed = [], rids
                else:
                    done, failed = self._handle_failure(plan, rids, results)
                claimed = front._claim(done, failed)
            _deliver(claimed, results)
        return done

    def step(self) -> List[Tuple[int, SearchResult]]:
        """Run ONE launch cycle: dispatch the policy's most urgent plan of
        the current queue and harvest the launch in flight — this one,
        or under ``pipelined`` the previous one (a step with nothing left
        to dispatch harvests it).  Returns the harvested launch's (rid,
        result) pairs — plus, under ``partial_results``, any
        deadline-swept partial resolutions.  Requests submitted while a
        step runs simply join the next plan.  With a ``retry`` policy an
        engine failure is absorbed (retry lane / quarantine) instead of
        raised."""
        return self._cycle()

    def _wait_for_retries(self) -> None:
        """Nothing dispatchable but retries are backed off: sleep the
        service clock forward to the next ``not_before``."""
        nb = self._next_retry_due()
        if nb is not None:
            dt = nb - self.clock()
            if dt > 0:
                self._sleep(dt)

    def stream(self) -> Iterator[Tuple[int, SearchResult]]:
        """Drain, yielding each plan's results as soon as its launch
        finishes — callers overlap their own post-processing with the
        remaining launches."""
        while self.pending():
            out = self.step()
            yield from out
            if (not out and not self.queue and not self._inflight
                    and self.pending()):
                self._wait_for_retries()

    def drain(self) -> Dict[int, SearchResult]:
        """Run the whole queue — waiting out retry backoff — until every
        request has resolved; returns {rid: SearchResult} for every
        request ever completed (incl. prior drains)."""
        for _ in self.stream():
            pass
        return self.results


class AsyncDSEService:
    """Non-blocking front end: a worker thread drains a ``DSEService``.

    ``submit`` enqueues and returns a ``concurrent.futures.Future``
    immediately — it never waits on a launch in flight, because the
    worker runs the service's launch cycle (``DSEService._cycle``) with
    this front end's lock held only around queue surgery and stats,
    never around the engine's ``dispatch``/``harvest``.  A request
    submitted mid-launch therefore joins the NEXT launch's packing, and
    under the priority/edf policies an urgent submission preempts every
    still-queued request at that boundary (the re-plan runs on warm
    program shapes via the service's slot hints — 0 new compiled
    programs).

    Future results are ``SearchResult``s, bit-identical to a synchronous
    ``DSEService`` drain of the same requests: scheduling only reorders
    self-contained searches.  Futures resolve on the worker thread, so a
    done-callback runs BEFORE the next dispatch — a deterministic hook
    for reacting mid-drain (the integration test submits its priority-0
    jump there).  ``paused=True`` admits submissions without launching
    until ``resume()`` — batch admission with a deterministic first plan.
    ``pipelined=True`` keeps one launch in flight (dispatch plan i+1
    before harvesting plan i — see ``DSEService``); results and
    future-resolution order are unchanged.  Use as a context manager, or
    call ``close()``."""

    def __init__(
        self,
        *,
        engine: Optional[SearchEngine] = None,
        mesh=None,
        max_slots: int = 64,
        policy="fifo",
        clock=time.monotonic,
        paused: bool = False,
        retry: Optional[RetryPolicy] = None,
        partial_results: bool = False,
        result_cache=None,
        pipelined: Optional[bool] = None,
    ):
        self.service = DSEService(
            engine=engine, mesh=mesh, max_slots=max_slots, policy=policy,
            clock=clock, retry=retry, partial_results=partial_results,
            result_cache=result_cache, pipelined=pipelined,
        )
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        self._run = threading.Event()
        if not paused:
            self._run.set()
        self._futures: Dict[int, Future] = {}
        self._closed = False
        # seconds to the next backed-off retry, set by an admission that
        # found nothing to dispatch and nothing in flight
        self._retry_wait: Optional[float] = None
        self._worker = threading.Thread(
            target=self._loop, name="dse-service", daemon=True
        )
        self._worker.start()

    @property
    def stats(self) -> ServiceStats:
        return self.service.stats

    @property
    def launch_log(self) -> List[List[int]]:
        return self.service.launch_log

    # ------------------------------------------------------------- admission
    def submit(self, req: SearchRequest, *, on_progress=None) -> Future:
        """Enqueue; returns a Future resolving to the SearchResult.
        Never blocks on device work — at most the queue lock.  A
        result-cache hit comes back as an ALREADY-RESOLVED future (the
        request never reaches the worker).  ``on_progress(rid, partial)``
        subscribes to the mid-search best-so-far stream (segmented
        engines only); callbacks run on the worker thread, between
        segment launches, and may themselves submit."""
        with self._lock:
            if self._closed:
                raise RuntimeError("AsyncDSEService is closed")
            rid = self.service.submit(req, on_progress=on_progress)
            fut: Future = Future()
            fut.rid = rid  # type: ignore[attr-defined]
            hit = self.service.results.get(rid)
            if hit is None:
                self._futures[rid] = fut
                self._idle.clear()
        # a cache hit resolves OUTSIDE the lock (done-callbacks may submit)
        if hit is not None:
            fut.set_result(hit)
            return fut
        self._wake.set()
        return fut

    def submit_all(self, reqs: Sequence[SearchRequest]) -> List[Future]:
        return [self.submit(r) for r in reqs]

    def pause(self):
        """Stop launching at the next launch boundary (in-flight work
        finishes); submissions keep queueing."""
        self._run.clear()

    def resume(self):
        self._run.set()
        self._wake.set()

    # --------------------------------------------------------------- serving
    def _admit(self):
        """The admission half of the service's launch cycle, under the
        lock (``None`` once closed).  With nothing to dispatch and nothing
        in flight the worker goes idle — the wake flag clears in the same
        lock section that saw the queue empty, so no submit is missed —
        or, with retries backed off, notes how long to nap.  Swept
        partials resolve outside the lock."""
        svc = self.service
        self._retry_wait = None
        with self._lock:
            if self._closed:
                return None
            swept, d = svc._admit()
            claimed = self._claim(swept)
            if d is None and not svc._inflight:
                nb = svc._next_retry_due()
                if nb is None:
                    self._wake.clear()
                    if not self._futures:
                        self._idle.set()
                else:
                    self._retry_wait = max(nb - svc.clock(), 0.0)
        _deliver(claimed)
        return swept, d

    def _claim(self, done: Sequence[Tuple[int, SearchResult]],
               failed: Sequence[int] = ()
               ) -> List[Tuple[Optional[Future], object]]:
        """Pop the futures of ``done`` and ``failed`` under the lock, so
        a timed-out ``close`` (which cancels what is left in the map)
        and the worker never both own one; a future it already took
        comes back as ``None``."""
        return ([(self._futures.pop(rid, None), res) for rid, res in done]
                + [(self._futures.pop(rid, None), None) for rid in failed])

    def _loop(self):
        """The worker: the service's launch cycles (one launch in flight
        under ``pipelined``).  It blocks while idle or
        paused; ``pause()`` and ``close()`` both settle the launch in
        flight first.  Progress callbacks fire inside the engine calls,
        lock-free, so they may submit."""
        svc = self.service
        while True:
            if not svc._inflight:
                self._wake.wait()
                self._run.wait()
            elif not self._run.is_set():
                # paused mid-overlap: settle the in-flight launch, then
                # block at the top of the next iteration
                svc._cycle(self, admit=False)
                continue
            if svc._cycle(self) is None:
                break
            if self._retry_wait is not None:
                # backed-off retries pending: nap on the REAL clock (a
                # virtual service clock advances externally), bounded
                # so external clock advances are picked up promptly
                time.sleep(min(self._retry_wait, 0.05) or 0.001)
        # closed with a launch still in flight (timed-out close cancelled
        # its futures): settle it so engine bookkeeping stays consistent
        while svc._inflight:
            svc._cycle(self, admit=False)

    def drain(self, timeout: Optional[float] = None) -> Dict[int, SearchResult]:
        """Block until the queue and all in-flight launches are done;
        returns the service's full {rid: result} map.  On timeout raises
        ``TimeoutError`` naming every unresolved rid."""
        if not self._idle.wait(timeout):
            with self._lock:
                unresolved = sorted(self._futures)
            raise TimeoutError(
                f"drain timed out with {len(unresolved)} unresolved "
                f"rids: {unresolved}"
            )
        return self.service.results

    def close(self, timeout: Optional[float] = None):
        """Finish in-flight work, then stop the worker.  Idempotent — a
        second close is a no-op.  With ``timeout``, a drain that cannot
        finish in time stops waiting and CANCELS every unresolved future
        (``Future.result()`` then raises ``CancelledError``), so a close
        racing an in-flight launch still leaves no future dangling."""
        with self._lock:
            if self._closed:
                return
        if self._run.is_set():
            try:
                self.drain(timeout)
            except TimeoutError:
                pass  # leftovers are cancelled below
        with self._lock:
            self._closed = True
            leftovers = list(self._futures.values())
            self._futures.clear()
        self._run.set()
        self._wake.set()
        # cancel BEFORE joining: the worker may still be inside a launch
        # (its pops see an empty future map and skip), and callers
        # blocked on result() unblock without waiting the launch out
        for f in leftovers:
            f.cancel()
        if threading.current_thread() is not self._worker:
            self._worker.join()

    def __enter__(self) -> "AsyncDSEService":
        return self

    def __exit__(self, *exc):
        self.close()


def paper_request_mix(
    ws: WorkloadSet,
    n: int,
    *,
    backend: str = "table",
    pop_size: int = 40,
    generations: int = 10,
    area_constr: float = 150.0,
    seed0: int = 0,
    priorities: Optional[Sequence[int]] = None,
    deadlines_s: Optional[Sequence[Optional[float]]] = None,
) -> List[SearchRequest]:
    """N heterogeneous requests over ``ws``: cycles through workload
    subsets (full set, singles, pairs) x objective kinds x seeds — the
    service's canonical mixed traffic (bench_dse_service, the CI
    serve-smoke leg, ``launch.search --serve``).  ``priorities`` /
    ``deadlines_s`` cycle the same way, for mixed-priority / EDF
    traffic (the async smoke + scheduler tests)."""
    W = ws.n
    subsets = [tuple(range(W))]
    subsets += [(i,) for i in range(W)]
    subsets += [(i, (i + 1) % W) for i in range(W)] if W > 1 else []
    return [
        SearchRequest(
            ws=ws.subset(list(subsets[i % len(subsets)])),
            objective=OBJECTIVES[i % len(OBJECTIVES)],
            area_constr=area_constr,
            seed=seed0 + i,
            backend=backend,
            pop_size=pop_size,
            generations=generations,
            priority=0 if priorities is None else int(priorities[i % len(priorities)]),
            deadline_s=None if deadlines_s is None
            else deadlines_s[i % len(deadlines_s)],
        )
        for i in range(n)
    ]
