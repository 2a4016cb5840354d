"""DSE-service throughput — the requests/s row of the perf trajectory.

Drains N heterogeneous search requests (mixed workload subsets x
objective kinds x seeds on the ``table`` backend — ``serve.dse.
paper_request_mix``) through the continuous-batching ``DSEService`` and
records:

  * cold_s / warm_s        — first drain (trace + XLA compile of the
                             seeding + GA programs) vs best-of-N cached
                             drains (the steady-state service number),
  * requests_per_s         — warm END-TO-END requests/s (submit through
                             drain wall time; each request = a full
                             P x (G+1) GA search),
  * wait/latency p50/p99   — per-request queue-wait and submit-to-result
                             latency percentiles of the recorded warm
                             drain (``ServiceStats`` samples),
  * designs_per_s          — the e2e figure in designs evaluated/s,
  * launches / programs    — XLA launches in one drain, and how many NEW
                             seeding/GA programs the drain compiled (the
                             acceptance bound is <= 4; steady state is 0),
  * transfer               — host-transfer bytes and launch count of one
                             warm drain under BOTH engine modes
                             (``pipelined=True`` thin epilogue vs the
                             sequential history-syncing default), plus
                             their bytes-per-launch reduction ratio.

``--smoke`` is the CI serve-smoke leg: ~32 mixed requests at a tiny
operating point, asserting every result arrives with a finite best
score — plus an EDF leg (deadline-ordered launches on the sync service)
and an async leg (mixed-priority ``AsyncDSEService`` drain, futures all
finite).  ``--fault-smoke`` is the CI fault-tolerance leg: every chunk
launch over the REAL engine fails once with a transient ``EngineFault``
and the retry lane must recover every request to a full finite result
(see ``fault_smoke``).  ``--cache-smoke`` is the CI cache leg: a
cache-armed service drains the paper mix, then the IDENTICAL mix is
resubmitted — sync and async — and every request must resolve from the
result cache with ZERO new GA launches and bit-identical results (see
``cache_smoke``).  ``python -m benchmarks.bench_dse_service`` appends
the ``service`` row of ``experiments/search_throughput.json`` and
``--cache`` the ``cache`` row (cold populate vs hot all-hits drain —
the request-overlap throughput ceiling; see benchmarks/README.md for
the methodology).
"""
from __future__ import annotations

import sys
import time

PAPER_S_PER_DESIGN = 36.0
POP, GENS = 40, 10


def _fmt(v, spec: str = ".2f") -> str:
    """Format a possibly-``None`` percentile (empty sample window)."""
    return "n/a" if v is None else f"{v:{spec}}"


def _program_cache_sizes() -> int:
    """Compiled-program count of the two jits a drain launches (seeding +
    batched GA) — the 'programs' the acceptance criterion bounds."""
    from repro.core import engine, ga

    return ga._run_ga_batched_jit._cache_size() + engine._seed_batched_jit._cache_size()


def run(quick: bool = False, verbose: bool = True, mesh=None,
        backend: str = "table", n_requests: int = None) -> dict:
    from repro.serve.dse import DSEService, paper_request_mix
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    n = n_requests or (64 if quick else 256)
    warm_reps = 2 if quick else 3
    per_search = POP * (GENS + 1)

    def drain(seed0: int, pipelined: bool = False) -> "DSEService":
        svc = DSEService(mesh=mesh, pipelined=pipelined)
        svc.submit_all(paper_request_mix(
            ws, n, backend=backend, pop_size=POP, generations=GENS,
            seed0=seed0,
        ))
        res = svc.drain()
        assert len(res) == n
        return svc

    p0 = _program_cache_sizes()
    t0 = time.time()
    svc = drain(0)
    cold = time.time() - t0
    programs = _program_cache_sizes() - p0
    warm = float("inf")
    for rep in range(warm_reps):
        t0 = time.time()
        svc = drain(1000 * (rep + 1))
        warm = min(warm, time.time() - t0)
    st = svc.stats  # per-request telemetry of the last warm drain
    out = {
        "requests": n, "pop": POP, "gens": GENS, "backend": backend,
        "slots": svc.engine.max_slots, "launches": svc.stats.launches,
        "programs_compiled_cold": programs,
        "warm_reps": warm_reps,
        "cold_s": cold,  # includes trace + XLA compile
        "warm_s": warm,  # cached programs: the steady-state number
        "requests_per_s": n / warm,  # end-to-end: submit through drain
        "wait_p50_s": st.wait_p(50), "wait_p99_s": st.wait_p(99),
        "latency_p50_s": st.latency_p(50), "latency_p99_s": st.latency_p(99),
        "designs_per_s": n * per_search / warm,
        "speedup_vs_paper": (n * per_search / warm) * PAPER_S_PER_DESIGN,
        "paper_s_per_design": PAPER_S_PER_DESIGN,
    }
    # host-transfer footprint of one warm drain under BOTH engine modes:
    # pipelined (thin on-device top-k epilogue + overlapped dispatch/
    # harvest) vs the sequential history-syncing default
    out["transfer"] = {}
    for pipelined in (False, True):
        t0 = time.time()
        svc_x = drain(7777, pipelined=pipelined)
        dt = time.time() - t0
        eng = svc_x.engine
        mode = "pipelined" if pipelined else "sequential"
        out["transfer"][mode] = {
            "warm_s": dt,
            "launches": int(eng.launches),
            "transfer_bytes": int(eng.transfer_bytes),
            "transfer_bytes_per_launch":
                eng.transfer_bytes / max(1, eng.launches),
        }
    seq_b = out["transfer"]["sequential"]["transfer_bytes_per_launch"]
    pip_b = out["transfer"]["pipelined"]["transfer_bytes_per_launch"]
    out["transfer"]["reduction_x"] = seq_b / max(1.0, pip_b)
    if verbose:
        print(f"[dse-service] {n} mixed requests: cold {cold:.2f}s "
              f"({programs} programs), warm {warm:.2f}s -> "
              f"{n/warm:.1f} req/s e2e, "
              f"{n*per_search/warm:.0f} designs/s, latency p50/p99 "
              f"{_fmt(st.latency_p(50))}/{_fmt(st.latency_p(99))}s "
              f"({svc.stats.launches} launches/drain)")
        print(f"[dse-service] transfer/launch: sequential {seq_b:.0f} B, "
              f"pipelined {pip_b:.0f} B "
              f"({out['transfer']['reduction_x']:.1f}x thinner, "
              f"{out['transfer']['pipelined']['launches']} launches)")
    return out


def _assert_all_finite(rids, results):
    missing = [r for r in rids if r not in results]
    assert not missing, f"requests never completed: {missing}"
    import numpy as np

    bad = [
        r for r in rids
        if not (len(results[r].top_scores)
                and np.isfinite(results[r].top_scores[0]))
    ]
    assert not bad, f"requests with no finite best score: {bad}"


def smoke(n: int = 32) -> int:
    """CI serve-smoke, three legs:

    1. sync fifo  — n mixed requests drained, every result present with
       a finite best score (the original smoke),
    2. sync EDF   — the same mix with cycling deadlines at 8 slots:
       launch order must be exactly earliest-absolute-deadline-first
       (deadline-less requests last), still all finite,
    3. async priority — the mixed-PRIORITY mix through AsyncDSEService
       (paused admission -> one deterministic plan), futures all finite
       and per-request telemetry recorded.
    """
    import numpy as np

    from repro.serve.dse import AsyncDSEService, DSEService, paper_request_mix
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(nm, cnn_workload(nm)) for nm in PAPER_WORKLOADS])
    svc = DSEService()
    # the paper's P=40 population: seeded designs all fit their largest
    # workload, and at P=40 every request reliably finds a feasible
    # (area-satisfying) design within a few generations
    rids = svc.submit_all(paper_request_mix(
        ws, n, backend="table", pop_size=40, generations=6,
    ))
    results = svc.drain()
    _assert_all_finite(rids, results)
    print(f"[dse-service] smoke: {n}/{n} mixed requests drained, "
          f"all finite ({svc.stats.launches} launches)")

    # --- EDF leg: cycling deadlines, 8-slot chunks -> >=4 launches whose
    # dispatch order must be non-decreasing in absolute deadline
    deadlines = [5.0, 60.0, 30.0, None]
    edf = DSEService(policy="edf", max_slots=8)
    edf_reqs = paper_request_mix(ws, n, backend="table", pop_size=40,
                                 generations=6, deadlines_s=deadlines)
    edf_rids = edf.submit_all(edf_reqs)
    edf_results = edf.drain()
    _assert_all_finite(edf_rids, edf_results)
    by_rid = dict(zip(edf_rids, edf_reqs))
    order = [
        np.inf if by_rid[rid].deadline_s is None else by_rid[rid].deadline_s
        for launch in edf.launch_log for rid in launch
    ]
    assert order == sorted(order), f"EDF launch order violated: {order}"
    print(f"[dse-service] smoke: EDF leg ordered {len(edf.launch_log)} "
          f"launches by deadline, all finite")

    # --- async leg: mixed priorities through the threaded front end;
    # paused admission keeps it at the sync leg's one 64-slot program
    with AsyncDSEService(policy="priority", paused=True) as async_svc:
        futs = async_svc.submit_all(paper_request_mix(
            ws, n, backend="table", pop_size=40, generations=6,
            priorities=[3, 0, 1, 2],
        ))
        async_svc.resume()
        async_res = [f.result(timeout=600) for f in futs]
    assert all(
        len(r.top_scores) and np.isfinite(r.top_scores[0]) for r in async_res
    ), "async leg returned a non-finite best score"
    st = async_svc.stats
    assert len(st.latency_samples) == n and len(st.wait_samples) == n
    print(f"[dse-service] smoke: async priority leg {n}/{n} futures "
          f"finite (latency p99 {_fmt(st.latency_p(99))}s)")
    return 0


def _assert_bit_equal(a, b, ctx: str = "") -> None:
    """Two SearchResults must match bit-for-bit (the cache-hit contract:
    a cached answer is THE answer, not an approximation of it).  Thin
    full results (pipelined engines: ``ga is None``) compare on the thin
    fields; both sides must agree on thinness."""
    import numpy as np

    assert a.objective == b.objective and a.workload_names == b.workload_names
    assert a.valid == b.valid and a.partial == b.partial
    assert a.top_designs == b.top_designs, ctx
    for name in ("top_scores", "top_genomes", "convergence"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{ctx}: {name} differs")
    assert (a.ga is None) == (b.ga is None), f"{ctx}: thinness differs"
    if a.ga is None:
        return
    for name in ("genomes", "scores", "best_genome", "best_score"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.ga, name)), np.asarray(getattr(b.ga, name)),
            err_msg=f"{ctx}: ga.{name} differs")


def cache_smoke(n: int = 32) -> int:
    """CI cache-smoke: the zero-launch hot-repeat contract, end to end.

    A cache-armed sync service drains the paper mix cold, then the
    IDENTICAL mix is resubmitted — every request must resolve at submit
    (``stats.cache_hits == n``) with ZERO new GA launches and results
    bit-identical to the cold drain.  An ``AsyncDSEService`` sharing the
    same cache then repeats the mix a third time: all futures arrive
    already resolved, its service never launches at all.
    """
    from repro.core.engine import SearchEngine
    from repro.serve.cache import ResultCache
    from repro.serve.dse import AsyncDSEService, DSEService, paper_request_mix
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(nm, cnn_workload(nm)) for nm in PAPER_WORKLOADS])
    mix = lambda: paper_request_mix(  # noqa: E731 — the one mix, four times
        ws, n, backend="table", pop_size=40, generations=6)
    cache = ResultCache()
    svc = DSEService(result_cache=cache)
    rids = svc.submit_all(mix())
    cold = dict(svc.drain())
    _assert_all_finite(rids, cold)
    launches = svc.stats.launches
    assert svc.stats.cache_hits == 0 and len(cache) == n

    rids2 = svc.submit_all(mix())
    hot = svc.drain()
    assert svc.stats.launches == launches, \
        f"hot resubmit launched GA work ({svc.stats.launches - launches})"
    assert svc.stats.cache_hits == n, svc.stats.cache_hits
    for r1, r2 in zip(rids, rids2):
        _assert_bit_equal(cold[r1], hot[r2], f"sync rid {r1}->{r2}")
    print(f"[dse-service] cache-smoke: sync hot resubmit {n}/{n} hits, "
          f"0 new launches, bit-identical ({cache.stats.summary()})")

    with AsyncDSEService(result_cache=cache) as async_svc:
        futs = async_svc.submit_all(mix())
        async_res = [f.result(timeout=600) for f in futs]
    assert async_svc.stats.launches == 0, async_svc.stats.launches
    assert async_svc.stats.cache_hits == n
    for r1, res in zip(rids, async_res):
        _assert_bit_equal(cold[r1], res, f"async rid {r1}")
    print(f"[dse-service] cache-smoke: async resubmit {n}/{n} futures "
          f"pre-resolved, 0 launches, bit-identical")

    # --- pipelined leg: THE ISSUE-10 regression.  Pipelined engines
    # return thin full results (ga=None); the cache used to refuse them,
    # so a pipelined service re-ran every resubmitted GA.  Now the same
    # contract holds as above: zero new launches, bit-identical, hot.
    pcache = ResultCache()
    peng = SearchEngine(pipelined=True)
    psvc = DSEService(engine=peng, result_cache=pcache)
    prids = psvc.submit_all(mix())
    pcold = dict(psvc.drain())
    _assert_all_finite(prids, pcold)
    assert all(pcold[r].ga is None for r in prids), \
        "pipelined drain returned non-thin results"
    assert len(pcache) == n, f"thin results not cached ({len(pcache)}/{n})"
    launches_p = peng.launches
    prids2 = psvc.submit_all(mix())
    phot = dict(psvc.drain())
    assert peng.launches == launches_p, \
        f"pipelined hot resubmit launched GA work ({peng.launches - launches_p})"
    assert psvc.stats.cache_hits == n, psvc.stats.cache_hits
    assert pcache.stats.hit_rate() > 0
    for r1, r2 in zip(prids, prids2):
        _assert_bit_equal(pcold[r1], phot[r2], f"pipelined rid {r1}->{r2}")
    print(f"[dse-service] cache-smoke: pipelined thin-result resubmit "
          f"{n}/{n} hits, 0 new launches, bit-identical "
          f"({pcache.stats.summary()})")
    return 0


def cache_run(quick: bool = False, verbose: bool = True) -> dict:
    """The ``cache`` row: cold populate vs hot all-hits drain.

    Same mix and operating point as the ``service`` row, through a
    cache-armed service: the cold drain runs every GA search and fills
    the cache, then ``warm_reps`` hot drains resubmit the identical mix
    — all hits, zero launches — and the best one is the row's hot
    number.  The hot/cold ratio is the throughput ceiling request
    overlap buys (a real stream sits in between, set by its hit rate).
    """
    from repro.core.engine import SearchEngine
    from repro.serve.cache import ResultCache
    from repro.serve.dse import DSEService, paper_request_mix
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(nm, cnn_workload(nm)) for nm in PAPER_WORKLOADS])
    n = 64 if quick else 256
    warm_reps = 2 if quick else 3
    per_search = POP * (GENS + 1)
    cache = ResultCache(capacity=2 * n)
    svc = DSEService(result_cache=cache)
    mix = paper_request_mix(ws, n, backend="table", pop_size=POP,
                            generations=GENS)

    t0 = time.time()
    svc.submit_all(mix)
    svc.drain()
    cold = time.time() - t0
    launches_cold = svc.stats.launches

    hot = float("inf")
    for _ in range(warm_reps):
        t0 = time.time()
        rids = svc.submit_all(mix)
        res = svc.drain()
        hot = min(hot, time.time() - t0)
        assert all(r in res for r in rids)
    assert svc.stats.launches == launches_cold, "hot drains launched GA work"
    assert svc.stats.cache_hits == warm_reps * n

    # --- pipelined-resubmit measurement: a PIPELINED engine's thin full
    # results must populate the cache, so an identical resubmit drains
    # with zero new GA launches
    n_pipe = 32
    pcache = ResultCache(capacity=2 * n_pipe)
    peng = SearchEngine(pipelined=True)
    psvc = DSEService(engine=peng, result_cache=pcache)
    pmix = paper_request_mix(ws, n_pipe, backend="table", pop_size=POP,
                             generations=GENS, seed0=50_000)
    psvc.submit_all(pmix)
    psvc.drain()
    launches_pipe_cold = peng.launches
    psvc.submit_all(pmix)
    psvc.drain()
    pipe_resubmit_launches = peng.launches - launches_pipe_cold

    out = {
        "requests": n, "pop": POP, "gens": GENS, "backend": "table",
        "warm_reps": warm_reps,
        "cold_s": cold,  # populate: every search launched
        "hot_s": hot,  # all hits: zero launches
        "cold_requests_per_s": n / cold,
        "hot_requests_per_s": n / hot,
        "hot_designs_per_s": n * per_search / hot,
        "hot_vs_cold_speedup": cold / hot,
        "launches_cold": launches_cold,
        "launches_hot": 0,
        "cache": cache.stats.summary(),
        "pipelined_resubmit": {
            "requests": n_pipe,
            "new_launches": int(pipe_resubmit_launches),
            "cache_hits": int(psvc.stats.cache_hits),
            "hit_rate": pcache.stats.hit_rate(),
        },
    }
    if verbose:
        print(f"[dse-service] cache: {n} mixed requests cold {cold:.2f}s "
              f"({launches_cold} launches) -> hot {hot:.3f}s all-hits "
              f"({n/hot:.0f} req/s, {cold/hot:.0f}x, 0 launches)")
        print(f"[dse-service] cache: pipelined resubmit x{n_pipe}: "
              f"{pipe_resubmit_launches} new launches, "
              f"hit rate {pcache.stats.hit_rate():.2f}")
    return out


def fault_smoke(n: int = 16) -> int:
    """CI fault-smoke: the retry lane over the REAL engine.

    A wrapper engine fails every CHUNK launch (plans carrying more than
    one request) the first time it sees that rid set — a transient
    per-chunk ``EngineFault`` — so the service's retry lane must re-plan
    each member in isolation and recover ALL of them to full
    (non-partial) finite results: failures == n, retries == n,
    partials == abandoned == 0.
    """
    from repro.core.engine import EngineFault, SearchEngine
    from repro.serve.dse import DSEService, RetryPolicy, paper_request_mix
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    class ChunkLaunchFails:
        """Fails the first launch of every distinct multi-request seed
        set; isolated (single-request) retries go through — a transient
        per-chunk fault."""

        def __init__(self, inner):
            self.inner = inner
            self.max_slots = inner.max_slots
            self.seen = set()
            self.injected = 0

        def dispatch(self, plan, **kw):
            key = tuple(sorted(r.seed for r in plan.requests))
            if len(key) > 1 and key not in self.seen:
                self.seen.add(key)
                self.injected += 1
                raise EngineFault(f"injected transient fault for {key}")
            return self.inner.dispatch(plan, **kw)

        def harvest(self, pending):
            return self.inner.harvest(pending)

    ws = pack_workloads([(nm, cnn_workload(nm)) for nm in PAPER_WORKLOADS])
    eng = ChunkLaunchFails(SearchEngine(max_slots=8))
    svc = DSEService(engine=eng,
                     retry=RetryPolicy(max_attempts=2, backoff_s=0.0),
                     partial_results=True)
    rids = svc.submit_all(paper_request_mix(
        ws, n, backend="table", pop_size=40, generations=6,
    ))
    results = svc.drain()
    _assert_all_finite(rids, results)
    assert not any(results[r].partial for r in rids), \
        "retried request resolved partial instead of recovering fully"
    st = svc.stats
    assert st.retries == n, f"expected {n} retries, got {st.retries}"
    assert st.failures == n, f"expected {n} failures, got {st.failures}"
    assert st.partials == 0 and st.abandoned == 0, (st.partials, st.abandoned)
    print(f"[dse-service] fault-smoke: {n}/{n} requests recovered through "
          f"the retry lane ({st.failures} request failures over "
          f"{eng.injected} faulted chunks, {st.retries} isolated retries, "
          f"0 partials) -- {st.summary()}")
    return 0


def main(argv=None) -> int:
    import argparse

    from benchmarks.run import prepare_search_mesh, write_search_throughput

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="64 requests instead of 256")
    ap.add_argument("--smoke", action="store_true",
                    help="CI serve-smoke: drain ~32 tiny mixed requests, "
                         "assert all present + finite; records nothing")
    ap.add_argument("--fault-smoke", action="store_true",
                    help="CI fault-smoke: every chunk launch fails once "
                         "over the REAL engine; the retry lane must "
                         "recover all requests fully; records nothing")
    ap.add_argument("--cache-smoke", action="store_true",
                    help="CI cache-smoke: resubmit an identical mix "
                         "through a cache-armed service (sync + async); "
                         "zero new launches, bit-identical results; "
                         "records nothing")
    ap.add_argument("--cache", action="store_true",
                    help="record the 'cache' row: cold populate vs hot "
                         "all-hits drain of the same mix")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument(
        "--mesh", nargs="?", const="auto", default=None, metavar="SEARCHxPOP",
        help="shard the service's launches over a (search, population) mesh "
             "(layout proof on fake devices; row not recorded)",
    )
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke(args.requests or 32)
    if args.fault_smoke:
        return fault_smoke(args.requests or 16)
    if args.cache_smoke:
        return cache_smoke(args.requests or 32)
    if args.cache:
        write_search_throughput(cache_run(quick=args.quick), row="cache")
        return 0
    mesh = prepare_search_mesh(args.mesh) if args.mesh else None
    res = run(quick=args.quick, mesh=mesh, n_requests=args.requests)
    if mesh is not None:
        print("[dse-service] mesh run not recorded (fake-device layout "
              "proof; the tracked service row is the single-host number)")
        return 0
    write_search_throughput(res, row="service")
    return 0


if __name__ == "__main__":
    sys.exit(main())
