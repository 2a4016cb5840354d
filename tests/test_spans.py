"""The in-program recorder (``repro.utils.spans``) and the spans the engine
and the service keep about their own work.

  * the recorder: nesting and parent ids, launch inheritance, the bounded
    rings and their drop order, records from several threads, windows;
  * the profiler: a ``dse.*`` span lands on the host plane of a CPU trace
    with its attributes as event stats;
  * the engine and the service at a tiny size: every launch has one
    ``dse.dispatch`` with its four phases, one ``dse.harvest`` with its
    three, and (served) one ``dse.resolve``, all under one launch id;
    ``syncs`` adds up to the reads ``_sync`` counted; every request
    record is stamped submit <= dispatch <= resolution.
"""
import dataclasses
import glob
import threading
import time
from collections import Counter

import jax
import pytest

from repro.core.engine import SearchEngine, SearchRequest, plan_batch
from repro.serve.dse import AsyncDSEService, DSEService, paper_request_mix
from repro.utils import spans
from repro.utils.spans import Recorder
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.pack import pack_workloads

POP, GENS = 12, 3
DISPATCH_PHASES = ["dse.dispatch.pack", "dse.dispatch.keys",
                   "dse.dispatch.seed", "dse.dispatch.ga"]
HARVEST_PHASES = ["dse.harvest.wait", "dse.harvest.sync",
                  "dse.harvest.finalize"]


@pytest.fixture(scope="module")
def ws():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def _reqs(ws, n, seed0=0):
    subsets = [[0, 1, 2, 3], [0], [1, 2]]
    return [SearchRequest(ws=ws.subset(subsets[i % 3]), seed=seed0 + i,
                          backend="table", pop_size=POP, generations=GENS)
            for i in range(n)]


def _children(snap, parent):
    return sorted(s.name for s in snap.spans if s.parent == parent.id)


def _check_launches(snap, *, served):
    """One dispatch and one harvest per launch (and one resolve when
    served), each phase a child of its span, all under the launch id."""
    assert snap.launches and len(set(snap.launches)) == len(snap.launches)
    for launch in snap.launches:
        mine = [s for s in snap.spans if s.launch == launch]
        top = Counter(s.name for s in mine if s.parent == 0)
        assert top == Counter({"dse.dispatch": 1, "dse.harvest": 1,
                               **({"dse.resolve": 1} if served else {})})
        disp = next(s for s in mine if s.name == "dse.dispatch")
        harv = next(s for s in mine if s.name == "dse.harvest")
        assert _children(snap, disp) == sorted(DISPATCH_PHASES)
        assert _children(snap, harv) == sorted(HARVEST_PHASES)
        assert disp.end <= harv.start
        assert disp.attrs["launch"] == harv.attrs["launch"] == launch
        assert set(disp.attrs) >= {"slots", "reqs", "P", "G", "W", "syncs"}
        assert set(harv.attrs) >= {"syncs", "bytes"}
        if served:
            res = next(s for s in mine if s.name == "dse.resolve")
            assert harv.end <= res.start and res.attrs["reqs"] >= 1
        # phases run in order inside their span
        for phases, parent in ((DISPATCH_PHASES, disp),
                               (HARVEST_PHASES, harv)):
            t = parent.start
            for name in phases:
                s = next(s for s in mine if s.name == name)
                assert t <= s.start <= s.end <= parent.end
                t = s.end


# ----------------------------------------------------------------- recorder
def test_nesting_parent_ids_and_launch_inheritance():
    rec = Recorder()
    with rec.span("a", launch=7, x=1) as a:
        with rec.span("a.b") as b:
            with rec.span("a.b.c"):
                pass
        b.set(n=3)
    with rec.span("d"):
        pass
    kept = rec.records()[0]
    by = {s.name: s for s in kept}
    assert [s.name for s in kept] == ["a.b.c", "a.b", "a", "d"]
    assert by["a"].parent == 0 and by["d"].parent == 0
    assert by["a.b"].parent == a.id and by["a.b.c"].parent == b.id
    assert by["a.b"].launch == by["a.b.c"].launch == 7
    assert by["d"].launch is None
    assert by["a"].attrs == {"launch": 7, "x": 1}
    assert by["a.b"].attrs == {"n": 3}
    assert len({s.id for s in kept}) == 4
    assert by["a"].start <= by["a.b"].start <= by["a.b"].end <= by["a"].end


def test_span_records_on_an_exception():
    rec = Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer", launch=1):
            with rec.span("inner"):
                raise ValueError("boom")
    assert [s.name for s in rec.records()[0]] == ["inner", "outer"]
    with rec.span("after") as after:
        pass
    assert after.parent == 0  # the stack unwound


def test_rings_are_bounded_and_drop_the_oldest():
    rec = Recorder(capacity=4)
    for i in range(6):
        with rec.span(f"s{i}"):
            pass
        rec.request(i, None, 0.0, 0.0, 0.0)
    kept, reqs = rec.records()
    assert [s.name for s in kept] == ["s2", "s3", "s4", "s5"]
    assert [r.rid for r in reqs] == [2, 3, 4, 5]
    rec.clear()
    assert not rec.spans and not rec.requests
    assert spans.CAPACITY == 65536
    assert spans.RECORDER.spans.maxlen == spans.RECORDER.requests.maxlen \
        == 65536


def test_records_from_two_threads():
    rec = Recorder()
    n = 300
    go = threading.Barrier(2)

    def work(tag):
        go.wait(timeout=10)
        for i in range(n):
            with rec.span(f"{tag}.outer", launch=i):
                with rec.span(f"{tag}.inner"):
                    pass
            rec.request(i, i, 0.0, 1.0, 2.0)

    threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    kept, reqs = rec.records()
    assert len(kept) == 4 * n and len(reqs) == 2 * n
    ids = {s.id: s for s in kept}
    assert len(ids) == 4 * n  # ids unique across threads
    for s in kept:
        if s.name.endswith(".inner"):
            parent = ids[s.parent]
            # a parent from the same thread and the same launch
            assert parent.name == s.name[0] + ".outer"
            assert parent.thread == s.thread and parent.launch == s.launch
        else:
            assert s.parent == 0
    assert len({s.thread for s in kept}) == 2


def test_snapshot_windows_by_dispatch_start_and_phase_means():
    rec = Recorder()
    for launch in (1, 2, 3):
        with rec.span("dse.dispatch", launch=launch):
            with rec.span("dse.dispatch.pack"):
                pass
        rec.request(10 + launch, launch, 0.0, 0.0, 0.0)
    starts = {s.launch: s.start for s in rec.records()[0]
              if s.name == "dse.dispatch"}
    snap = rec.snapshot(starts[2], starts[3])
    assert snap.launches == [2]
    assert {s.name for s in snap.spans} == {"dse.dispatch",
                                            "dse.dispatch.pack"}
    assert [r.rid for r in snap.requests] == [12]
    assert rec.snapshot(starts[3] + 1.0).launches == []
    ms = spans.phase_ms(rec.snapshot())
    assert set(ms) == {"dse.dispatch", "dse.dispatch.pack"}
    assert ms["dse.dispatch"] >= ms["dse.dispatch.pack"] >= 0.0
    assert spans.phase_ms(rec.snapshot(starts[3] + 1.0)) == {}


def test_counters_per_launch():
    rec = Recorder()
    for launch, hit in ((1, True), (2, False)):
        with rec.span("dse.dispatch", launch=launch, slots=4) as sp:
            with rec.span("dse.dispatch.pack") as pk:
                pk.set(hit=hit)
            with rec.span("dse.dispatch.keys") as ks:
                ks.set(host_keys=4 if hit else 1, host_split=4 if hit else 0)
            sp.set(syncs=4)
        with rec.span("dse.harvest", launch=launch) as sp:
            sp.set(syncs=5, bytes=100 * launch)
    assert spans.counters(rec.snapshot()) == {
        "syncs": 9.0, "bytes": 150.0, "pack_hit": 0.5, "host_keys": 0.625,
        "host_split": 0.5}
    assert spans.counters(rec.snapshot(hi=0.0)) == {}


def test_counters_seed_rounds_per_seeded_slot():
    """Rounds a seeded slot, over every launch that seeded; no reading
    where nothing was seeded."""
    rec = Recorder()
    for launch, (n, rounds) in enumerate(((4, 4), (4, 10), (0, 0)), 1):
        with rec.span("dse.dispatch", launch=launch, slots=4) as sp:
            sp.set(syncs=0)
        with rec.span("dse.harvest", launch=launch) as sp:
            if n:
                sp.set(seed_slots=n, seed_rounds=rounds)
    assert spans.counters(rec.snapshot())["seed_rounds"] == 14 / 8
    last = [s for s in rec.snapshot().spans if s.name == "dse.dispatch"][-1]
    alone = spans.counters(rec.snapshot(lo=last.start))
    assert alone["syncs"] == 0 and "seed_rounds" not in alone


# ----------------------------------------------------------------- profiler
def test_span_lands_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("dse.dispatch", launch=41, slots=8) as sp:
            jax.numpy.ones(4).block_until_ready()
            sp.set(syncs=5)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events
              if ev.name == "dse.dispatch"]
    assert len(events) == 1
    stats = dict(events[0].stats)
    assert stats["launch"] == 41 and stats["slots"] == 8
    assert stats["syncs"] == 5


# ------------------------------------------------------------------ engine
def test_pipelined_engine_run_spans_and_syncs(ws):
    eng = SearchEngine(max_slots=4, pipelined=True)
    reqs = _reqs(ws, 7)
    eng.run(reqs)  # warm: programs and caches
    eng.reset_transfer_stats()
    t0 = time.perf_counter()
    eng.run(_reqs(ws, 7, seed0=50))
    snap = spans.snapshot(t0)
    assert len(snap.launches) == eng.launches == 2
    _check_launches(snap, served=False)
    top = [s for s in snap.spans if s.name in ("dse.dispatch", "dse.harvest")]
    assert sum(s.attrs["syncs"] for s in top) == eng.syncs
    # seed-only plans move no key bytes: every byte is a harvest's
    assert sum(s.attrs["bytes"] for s in top
               if s.name == "dse.harvest") == eng.transfer_bytes
    for s in snap.spans:
        if s.name == "dse.dispatch":
            # keys built on the host, the seed check deferred to harvest
            assert s.attrs["syncs"] == 0 and s.attrs["slots"] == 4
        elif s.name == "dse.dispatch.keys":
            assert s.attrs["host_keys"] == 4
            assert s.attrs["host_split"] == 4
        elif s.name == "dse.dispatch.pack":
            assert s.attrs["hit"] is True  # the warm run packed these
        elif s.name == "dse.harvest":
            # the seed check and the four thin fields
            assert s.attrs["syncs"] == 5
            # every slot seeded, each in one round or more
            assert s.attrs["seed_slots"] == 4
            assert s.attrs["seed_rounds"] >= 4
    per = spans.counters(snap)
    assert per["syncs"] == eng.syncs / 2 == 5
    assert per["bytes"] == sum(s.attrs["bytes"] for s in top
                               if s.name == "dse.harvest") / 2
    assert per["pack_hit"] == 1.0
    assert per["host_keys"] == 1.0
    assert per["host_split"] == 1.0


def test_segmented_guard_reads_count_as_syncs(ws):
    """The segmented path's NaN guards are blocking reads: each counts in
    ``syncs`` and ``transfer_bytes``, and the spans add up to both."""
    reqs = _reqs(ws, 3, seed0=30)
    plan = plan_batch(reqs, max_slots=4)[0]
    eng = SearchEngine(max_slots=4, segment_gens=1, pipelined=True)
    t0 = time.perf_counter()
    res = eng.harvest(eng.dispatch(plan))
    snap = spans.snapshot(t0)
    assert snap.launches == [plan.launch]
    by = {s.name: s for s in snap.spans}
    # the eager seed check, the seed guard and one guard per segment (the
    # keys are built on the host); then the four thin fields
    assert by["dse.dispatch"].attrs["syncs"] == 2 + GENS
    assert by["dse.dispatch.keys"].attrs["host_keys"] == plan.slots
    assert by["dse.dispatch.keys"].attrs["host_split"] == plan.slots
    assert by["dse.harvest"].attrs["syncs"] == 4
    assert by["dse.dispatch"].attrs["syncs"] + 4 == eng.syncs
    ref = SearchEngine(max_slots=4, pipelined=True).run(reqs)
    for a, b in zip(res, ref):
        assert (a.top_scores == b.top_scores).all()


@pytest.mark.parametrize("explicit,host_split", [
    (False, True), (True, True), (False, False)],
    ids=["seed", "key", "seed-device-split"])
def test_key_reads_go_through_sync(ws, explicit, host_split):
    """An explicit key's per-slot read counts as a read and its bytes; a
    seed's key is built on the host and read nowhere.  Every key is split
    on the host, except off the threefry formula's settings, where the
    device splits it and ``host_split`` reads 0.  None changes a
    result."""
    reqs = _reqs(ws, 3, seed0=20)
    if explicit:
        reqs = [dataclasses.replace(r, key=jax.random.PRNGKey(r.seed))
                for r in reqs]
    with jax.threefry_partitionable(host_split):
        plan = plan_batch(reqs, max_slots=4)[0]
        eng = SearchEngine(max_slots=4)
        t0 = time.perf_counter()
        pend = eng.dispatch(plan)
        # sequential dispatch: one read per explicit slot key, then the eager
        # seed check (one int32 count and one int32 rounds a slot, one read)
        assert eng.syncs == (plan.slots if explicit else 0) + 1
        assert eng.transfer_bytes == (8 if explicit else 0) * plan.slots \
            + 8 * plan.slots
        keys = [s for s in spans.records()[0]
                if s.name == "dse.dispatch.keys" and s.start >= t0]
        assert [s.attrs["host_keys"] for s in keys] == [
            0 if explicit else plan.slots]
        assert [s.attrs["host_split"] for s in keys] == [
            plan.slots if host_split else 0]
        assert pend.plan is plan and plan.launch is not None
        t0 = time.perf_counter()
        res = eng.harvest(pend)
        harv = [s for s in spans.records()[0]
                if s.name == "dse.harvest" and s.start >= t0]
        assert [s.launch for s in harv] == [plan.launch]
        ref = SearchEngine(max_slots=4, pipelined=True).run(reqs)
        for a, b in zip(res, ref):
            assert (a.top_scores == b.top_scores).all()


# ------------------------------------------------------------------ service
@pytest.mark.parametrize("pipelined", [False, True])
def test_async_service_drain_spans_and_request_records(ws, pipelined):
    reqs = paper_request_mix(ws, 10, pop_size=POP, generations=GENS, seed0=3)
    with AsyncDSEService(max_slots=4, pipelined=pipelined,
                         paused=True) as svc:
        t0 = time.perf_counter()
        futs = svc.submit_all(reqs)
        svc.resume()
        for f in futs:
            f.result(timeout=600)
        eng = svc.service.engine
    snap = spans.snapshot(t0)
    assert len(snap.launches) == svc.stats.launches == 3
    _check_launches(snap, served=True)
    top = [s for s in snap.spans if s.name in ("dse.dispatch", "dse.harvest")]
    assert sum(s.attrs["syncs"] for s in top) == eng.syncs
    recs = {r.rid: r for r in snap.requests}
    assert sorted(recs) == sorted(f.rid for f in futs)
    by_launch = Counter(r.launch for r in snap.requests)
    for s in snap.spans:
        if s.name == "dse.resolve":
            assert by_launch[s.launch] == s.attrs["reqs"]
    disp = {s.launch: s for s in snap.spans if s.name == "dse.dispatch"}
    for r in snap.requests:
        assert r.submit <= r.dispatch <= r.resolve
        # the service dispatches a request before the engine's span opens
        assert r.dispatch <= disp[r.launch].start
    assert not svc.service._submit_pc  # no stamp left behind


def test_sync_service_failed_launch_leaves_no_request_record(ws):
    """A failed launch resolves no request: its rids keep their submit
    stamps for the retry, and leave no record until served."""
    reqs = _reqs(ws, 2, seed0=70)
    svc = DSEService(max_slots=4)
    real = svc.engine.harvest
    calls = []

    def flaky(pending):
        calls.append(pending.plan)
        if len(calls) == 1:
            raise RuntimeError("injected")
        return real(pending)

    svc.engine.harvest = flaky
    t0 = time.perf_counter()
    rids = svc.submit_all(reqs)
    with pytest.raises(RuntimeError, match="injected"):
        svc.step()
    assert sorted(svc._submit_pc) == sorted(rids)
    assert not [r for r in spans.records()[1] if r.resolve >= t0]
    svc.step()
    recs = [r for r in spans.records()[1] if r.resolve >= t0]
    assert sorted(r.rid for r in recs) == sorted(rids)
    assert {r.launch for r in recs} == {calls[1].launch}
