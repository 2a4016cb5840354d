"""Scheduler-sim suite: policy ordering, starvation-freedom, deadline
accounting — asserted on the virtual-clock harness (tests/sim_scheduler.py),
no XLA launches.  The real-engine twins (bit-parity, compiled-program
counts, the async priority-0 jump) live in tests/test_engine.py."""
import time

import numpy as np
import pytest

from sim_scheduler import (
    StubEngine,
    VirtualClock,
    run_script,
    sim_request,
    sim_service,
    sim_ws,
    submit_burst,
)

from repro.core.engine import PriorityPolicy, get_policy
from repro.serve.dse import DSEService
from repro.utils import spans


# ---------------------------------------------------------------- policies
def test_fifo_completes_in_submit_order():
    svc, clock, stub = sim_service(policy="fifo", max_slots=1)
    trace = run_script(svc, clock, [
        ("submit", sim_request(0)), ("submit", sim_request(1)),
        ("submit", sim_request(2)), ("drain",),
    ])
    assert trace.completion_order() == trace.rids
    assert [l.seeds for l in stub.launches] == [[0], [1], [2]]


def test_priority_orders_launches_most_urgent_first():
    svc, clock, stub = sim_service(policy="priority", max_slots=1)
    trace = run_script(svc, clock, [
        ("submit", sim_request(0, priority=5)),
        ("submit", sim_request(1, priority=0)),
        ("submit", sim_request(2, priority=2)),
        ("submit", sim_request(3, priority=0)),  # ties break by submit order
        ("drain",),
    ])
    assert [l.seeds[0] for l in stub.launches] == [1, 3, 2, 0]
    assert trace.completion_order() == [trace.rids[i] for i in (1, 3, 2, 0)]


def test_edf_orders_by_absolute_deadline_deadlineless_last():
    svc, clock, stub = sim_service(policy="edf", max_slots=1, launch_s=0.25)
    # B's RELATIVE deadline is shorter but it is submitted later; absolute
    # deadlines on the clock are what EDF sorts: A=6, B=2+1=3, C=none
    trace = run_script(svc, clock, [
        ("submit", sim_request(0, deadline_s=6.0)),
        ("submit", sim_request(2)),  # no deadline -> after every deadline
        ("advance", 2.0),
        ("submit", sim_request(1, deadline_s=1.0)),
        ("drain",),
    ])
    assert [l.seeds[0] for l in stub.launches] == [1, 0, 2]
    assert trace.completion_order() == [trace.rids[2], trace.rids[0],
                                        trace.rids[1]]


def test_priority_zero_mid_drain_preempts_queued_work():
    """The acceptance criterion, sim form: a priority-0 submit lands in
    the very next launch while lower-priority queued work keeps waiting."""
    svc, clock, stub = sim_service(policy="priority", max_slots=4)
    low = submit_burst(svc, 12, priorities=(5,))
    svc.step()  # launch 1: four of the low-priority requests
    urgent = svc.submit(sim_request(99, priority=0))
    svc.step()  # launch 2 must carry the urgent request
    assert urgent in svc.launch_log[1]
    assert 99 in stub.launches[1].seeds
    still_queued = {rid for rid, _ in svc.queue}
    assert still_queued <= set(low) and len(still_queued) == 5
    svc.drain()
    assert set(svc.results) == set(low) | {urgent}


def test_priority_aging_prevents_starvation():
    """Under a saturating priority-0 stream, a priority-9 request still
    launches once its age buys 9 levels (aging_s=2 -> 18 sim-seconds),
    because aged urgency beats fresh priority 0."""
    svc, clock, stub = sim_service(
        policy=PriorityPolicy(aging_s=2.0), max_slots=4, launch_s=1.0
    )
    starved = svc.submit(sim_request(-1, priority=9))
    done_at = None
    for round_ in range(40):
        submit_burst(svc, 4, priorities=(0,), seed0=100 * round_)
        for rid, _ in svc.step():
            if rid == starved:
                done_at = clock()
    assert done_at is not None, "aged request never launched: starvation"
    # 9 levels * aging_s=2 = 18s of waiting; one extra launch of slack
    assert done_at <= 20.0


def test_priority_without_aging_starves():
    """aging_s=None is strict priority: the same saturating stream
    starves the low-priority request indefinitely — the behavior aging
    exists to rule out."""
    svc, clock, stub = sim_service(
        policy=PriorityPolicy(aging_s=None), max_slots=4, launch_s=1.0
    )
    starved = svc.submit(sim_request(-1, priority=9))
    for round_ in range(40):
        submit_burst(svc, 4, priorities=(0,), seed0=100 * round_)
        done = svc.step()
        assert starved not in [rid for rid, _ in done]
    assert starved in {rid for rid, _ in svc.queue}
    svc.drain()  # once the stream stops it does complete
    assert starved in svc.results


# ----------------------------------------------------- deadline accounting
def test_aging_replan_fires_on_stale_plan_cache():
    """The wall-clock aging trigger (ROADMAP gap): once a cached plan
    list is >= aging_s old, the next dispatch re-runs plan_batch with
    fresh wait_s instead of consuming the stale order — without a submit
    having to land.  The re-plan runs on the warm slot hints: the launch
    shape multiset is untouched (zero new compiled programs)."""
    svc, clock, stub = sim_service(
        policy=PriorityPolicy(aging_s=2.0), max_slots=1, launch_s=1.0
    )
    submit_burst(svc, 4, priorities=(3,))
    svc.step()  # builds + caches plans at t=0, consumes one
    built0 = svc._plans_built_s
    assert built0 == 0.0 and svc._plans_cache is not None
    clock.advance(5.0)  # > aging_s with NO submit landing
    svc.step()
    assert svc._plans_built_s >= 5.0, "stale plan cache was not re-planned"
    svc.drain()
    # scheduling-only: every request completes, and every launch reused
    # the one warm (signature, slots) shape — the re-plan compiled nothing
    assert svc.stats.completed == 4
    assert len({(l.signature, l.slots) for l in stub.launches}) == 1


def test_aging_replan_starvation_free_without_submit_triggers():
    """Starvation-freedom in REAL time, not just at submit boundaries: a
    priority-9 request outlives a saturating priority-0 backlog even when
    later rounds only advance the clock and step (no fresh submissions to
    invalidate the plan cache) — the aging re-plan trigger keeps the
    promotions applied.  Zero new compiled programs throughout."""
    svc, clock, stub = sim_service(
        policy=PriorityPolicy(aging_s=2.0), max_slots=4, launch_s=1.0
    )
    starved = svc.submit(sim_request(-1, priority=9))
    # saturating phase: fresh priority-0 bursts keep the queue hot
    for round_ in range(4):
        submit_burst(svc, 4, priorities=(0,), seed0=100 * round_)
        svc.step()
    # quiet phase: the clock runs, steps land, nothing is submitted —
    # the old code would consume the stale cached order here forever
    done_at = None
    for _ in range(30):
        if not svc.pending():
            break
        clock.advance(1.0)
        for rid, _res in svc.step():
            if rid == starved and done_at is None:
                done_at = clock()
    assert done_at is not None, "aged request never launched: starvation"
    assert done_at <= 40.0
    assert svc.stats.completed == 17
    assert len({(l.signature, l.slots) for l in stub.launches}) == 1


def test_aging_replan_disabled_without_aging():
    """aging_s=None (and fifo/edf) must never trip the staleness check —
    the cached plan list survives arbitrary clock advances untouched."""
    for policy in (PriorityPolicy(aging_s=None), "fifo", "edf"):
        svc, clock, stub = sim_service(policy=policy, max_slots=1)
        assert svc._aging_s is None
        submit_burst(svc, 3)
        svc.step()
        cached = svc._plans_cache
        assert cached is not None
        clock.advance(1000.0)
        svc.step()
        assert svc._plans_built_s == 0.0  # never rebuilt
        svc.drain()
        assert svc.stats.completed == 3


# ----------------------------------------------------- deadline accounting
def test_deadline_miss_accounting_exact():
    svc, clock, stub = sim_service(policy="edf", max_slots=1, launch_s=2.0)
    trace = run_script(svc, clock, [
        ("submit", sim_request(0, deadline_s=1.0)),   # misses: done at t=2
        ("submit", sim_request(1, deadline_s=10.0)),  # makes it: done at t=4
        ("submit", sim_request(2)),                   # no deadline: never a miss
        ("drain",),
    ])
    assert svc.stats.deadline_misses == 1
    assert trace.done_at(trace.rids[0]) == 2.0
    assert trace.done_at(trace.rids[1]) == 4.0
    # exact telemetry on the virtual clock: waits 0/2/4, latencies 2/4/6
    assert sorted(svc.stats.wait_samples) == [0.0, 2.0, 4.0]
    assert sorted(svc.stats.latency_samples) == [2.0, 4.0, 6.0]
    assert svc.stats.latency_p(50) == 4.0
    assert svc.stats.wait_p(0) == 0.0
    s = svc.stats.summary()
    assert s["deadline_misses"] == 1 and s["latency_p99_s"] <= 6.0


def test_deadline_met_exactly_at_boundary_is_not_a_miss():
    svc, clock, stub = sim_service(policy="edf", max_slots=1, launch_s=1.0)
    run_script(svc, clock, [
        ("submit", sim_request(0, deadline_s=1.0)), ("drain",),
    ])
    assert svc.stats.deadline_misses == 0  # done at t==deadline: on time


# ------------------------------------------------- interleaving invariants
def test_every_rid_gets_its_own_result_under_interleaving():
    svc, clock, stub = sim_service(policy="priority", max_slots=2)
    ws2 = sim_ws(2, 3, tag="alt")
    events = [
        ("submit", sim_request(10, priority=3)),
        ("step",),
        ("submit", sim_request(11, priority=0, ws=ws2)),
        ("submit", sim_request(12, priority=1)),
        ("advance", 0.5),
        ("submit", sim_request(13, priority=0)),
        ("step",), ("step",),
        ("submit", sim_request(14, priority=2)),
        ("drain",),
    ]
    trace = run_script(svc, clock, events)
    seeds = [10, 11, 12, 13, 14]
    assert sorted(trace.completion_order()) == sorted(trace.rids)
    for rid, seed in zip(trace.rids, seeds):
        res = trace.result(rid)
        assert res.seed == seed  # rid -> its OWN request's result
    assert trace.result(trace.rids[1]).workload_names == ws2.names


def test_launches_partition_the_submitted_rids():
    svc, clock, stub = sim_service(policy="priority", max_slots=3)
    rids = submit_burst(svc, 10, priorities=(2, 0, 1),
                        deadlines_s=(None, 5.0))
    svc.drain()
    flat = [rid for launch in svc.launch_log for rid in launch]
    assert sorted(flat) == sorted(rids)  # every rid exactly once


def test_mid_drain_submit_reuses_warm_slot_size():
    """The slot-hint contract, sim form: a re-plan forced by a mid-drain
    submit rounds the residue UP to the signature's warm slot size
    instead of planning a fresh smaller program shape."""
    svc, clock, stub = sim_service(policy="fifo", max_slots=4)
    submit_burst(svc, 6)
    svc.step()  # 4 launch; plans cached with tail slots=4
    svc.submit(sim_request(50))  # invalidates the plan cache: 3 remain
    svc.step()
    assert [l.slots for l in stub.launches] == [4, 4]
    assert len(stub.launches[1].seeds) == 3  # 3 real in the 4-slot shape
    svc.drain()
    assert svc.stats.completed == 7


@pytest.mark.parametrize("policy", ["fifo", "priority", "edf"])
def test_pipelined_drain_matches_sequential(policy):
    """A stub with the dispatch/harvest split drained under
    ``pipelined=True`` (one launch in flight across steps) yields the same
    (rid, result) sequence and the same launch log as the sequential
    drain: the in-flight depth changes when a launch is harvested, never
    which requests it carries or in what order they resolve."""
    def drain(pipelined):
        svc, clock, stub = sim_service(policy=policy, max_slots=3,
                                       pipelined=pipelined)
        submit_burst(svc, 11, priorities=(2, 0, 1),
                     deadlines_s=(9.0, None, 4.0))
        first = svc.step()
        # pipelined: the first launch is dispatched but not yet harvested
        assert (len(stub.launches), bool(first)) == (
            (0, False) if pipelined else (1, True))
        assert svc.pending() == 11 - len(first)
        done = first + list(svc.stream())
        assert svc.pending() == 0 and not svc._inflight
        return done, svc.launch_log, [l.seeds for l in stub.launches]

    seq = drain(False)
    pipe = drain(True)
    assert len(seq[0]) == 11 and len(seq[1]) == 4
    assert pipe == seq


def test_policy_never_changes_program_shapes():
    """Same request mix under fifo vs priority vs edf: identical multiset
    of (signature, slots) launches — scheduling reorders, never re-chunks."""
    def launches_for(policy):
        svc, clock, stub = sim_service(policy=policy, max_slots=4)
        submit_burst(svc, 11, priorities=(0, 3, 1), deadlines_s=(4.0, None))
        svc.drain()
        return sorted((l.signature, l.slots) for l in stub.launches)

    fifo = launches_for("fifo")
    assert launches_for("priority") == fifo
    assert launches_for("edf") == fifo


# ---------------------------------------------------------- failure paths
class FlakyEngine(StubEngine):
    """Fails the first ``fail_times`` launches, then behaves."""

    def __init__(self, clock, *, fail_times=1, **kw):
        super().__init__(clock, **kw)
        self.fail_times = fail_times

    def execute(self, plan, *, mesh=None):
        if self.fail_times > 0:
            self.fail_times -= 1
            raise RuntimeError("injected engine failure")
        return super().execute(plan, mesh=mesh)


def test_sync_step_engine_failure_is_retryable():
    """A failed launch must roll the dispatched requests back into the
    queue (original submit stamps intact) — step() raises but nothing is
    lost, and a retry serves everything."""
    clock = VirtualClock()
    svc = DSEService(engine=FlakyEngine(clock, fail_times=1, max_slots=2),
                     clock=clock)
    rids = submit_burst(svc, 3)
    with pytest.raises(RuntimeError, match="injected"):
        svc.step()
    assert svc.pending() == 3  # nothing silently dropped
    assert len(svc.stats.wait_samples) == 0  # failed dispatch not sampled
    out = svc.drain()
    assert set(out) == set(rids)
    assert svc.stats.completed == 3
    assert len(svc.stats.wait_samples) == len(svc.stats.latency_samples) == 3


def test_async_engine_failure_fails_futures_and_keeps_serving():
    """An engine failure fails exactly that plan's futures (done-callbacks
    fire on the exception and may SUBMIT without deadlocking — exceptions
    are set outside the service lock), purges the failed rids'
    bookkeeping, and the worker keeps serving later submissions."""
    from repro.serve.dse import AsyncDSEService

    clock = VirtualClock()
    svc = AsyncDSEService(
        engine=FlakyEngine(clock, fail_times=1, max_slots=2),
        clock=clock, paused=True,
    )
    f1 = svc.submit(sim_request(1))
    f2 = svc.submit(sim_request(2))  # packs with f1: one 2-slot plan
    resubmitted = []

    def resubmit(_fut):  # runs on the worker thread, on the FAILURE
        if not resubmitted:
            resubmitted.append(svc.submit(sim_request(3)))

    f1.add_done_callback(resubmit)
    svc.resume()
    with pytest.raises(RuntimeError, match="injected"):
        f1.result(timeout=30)
    with pytest.raises(RuntimeError, match="injected"):
        f2.result(timeout=30)
    results = svc.drain(timeout=30)  # the callback's resubmission serves
    assert resubmitted and resubmitted[0].result(timeout=30).seed == 3
    assert set(results) == {resubmitted[0].rid}
    st = svc.stats
    assert st.submitted == 3 and st.completed == 1  # failures never served
    assert len(st.wait_samples) == len(st.latency_samples) == 1
    assert not svc.service._submit_s and not svc.service._deadline_s  # no leak
    svc.close()


# ------------------------------------------------------------- misc guards
def test_get_policy_rejects_unknown():
    with pytest.raises(ValueError, match="policy"):
        get_policy("sjf")
    with pytest.raises(ValueError, match="aging_s"):
        PriorityPolicy(aging_s=0.0)
    assert get_policy("edf").name == "edf"
    p = PriorityPolicy(aging_s=1.0)
    assert get_policy(p) is p


def test_empty_step_and_stats_defaults():
    svc, clock, stub = sim_service()
    t0 = time.perf_counter()
    assert svc.step() == []
    # an empty step resolves no launch and leaves no request record
    kept, reqs = spans.records()
    assert not [s for s in kept if s.start >= t0]
    assert not [r for r in reqs if r.resolve >= t0]
    assert svc.stats.launches == 0
    # empty sample windows report None, not NaN: a fresh service's
    # summary() must serialize to valid JSON (bench rows read it)
    assert svc.stats.wait_p(50) is None
    assert svc.stats.latency_p(99) is None
    s = svc.stats.summary()
    assert s["wait_p50_s"] is None and s["latency_p99_s"] is None
    import json

    assert "NaN" not in json.dumps(s)  # NaN would serialize as bare NaN


def test_service_clock_defaults_are_real_time():
    # the default service still works without any clock injection
    svc = DSEService(engine=StubEngine(VirtualClock(), max_slots=2))
    rid = svc.submit(sim_request(7))
    out = dict(svc.drain())
    assert out[rid].seed == 7
    assert svc.stats.latency_samples and svc.stats.wait_samples
