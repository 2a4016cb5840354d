"""DSE engine + service: heterogeneous packing == per-request searches.

The acceptance bar for the request -> plan -> execute stack: a batch
mixing workload sets, objectives, areas, seeds and backends must return
BIT-IDENTICAL scores and top designs vs running each request alone
(``run_search``), including under the fake-8-device (search, population)
mesh, and a 256-request drain must compile at most 4 programs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core import ga as ga_mod
from repro.core.engine import (
    SearchEngine,
    SearchRequest,
    default_engine,
    plan_batch,
)
from repro.core.objectives import OBJECTIVES
from repro.core.search import run_search
from repro.serve.dse import AsyncDSEService, DSEService, paper_request_mix
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.pack import _TABLES_MEMO, pack_workloads

POP, GENS = 16, 3


@pytest.fixture(scope="module")
def ws():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def _mixed_requests(ws, n, backend="table", pop=POP, gens=GENS, seed0=0):
    """n requests cycling subsets x objectives x areas x seeds."""
    subsets = [[0, 1, 2, 3], [0], [2], [1, 3], [3, 2, 1, 0], [0, 2]]
    areas = [150.0, 150.0, 120.0]
    return [
        SearchRequest(
            ws=ws.subset(subsets[i % len(subsets)]),
            objective=OBJECTIVES[i % len(OBJECTIVES)],
            area_constr=areas[i % len(areas)],
            seed=seed0 + i,
            backend=backend,
            pop_size=pop,
            generations=gens,
        )
        for i in range(n)
    ]


def _assert_matches_run_search(req, res):
    ref = run_search(
        req.prng_key(), req.ws, objective=req.objective,
        area_constr=req.area_constr, pop_size=req.pop_size,
        generations=req.generations, top_k=req.top_k, backend=req.backend,
    )
    np.testing.assert_array_equal(
        np.asarray(res.ga.scores), np.asarray(ref.ga.scores)
    )
    np.testing.assert_array_equal(res.top_scores, ref.top_scores)
    np.testing.assert_array_equal(res.top_genomes, ref.top_genomes)
    assert res.workload_names == ref.workload_names
    assert res.objective == ref.objective


# -------------------------------------------------------------- planning
def test_plan_batch_groups_by_signature(ws):
    reqs = _mixed_requests(ws, 6, backend="table")
    reqs += _mixed_requests(ws, 2, backend="table", pop=POP + 2)  # new pop
    # dense requests group by exact (W, L): two subsets of different W
    reqs += [SearchRequest(ws=ws.subset([0]), backend="jnp", pop_size=POP,
                           generations=GENS),
             SearchRequest(ws=ws.subset([0, 1]), backend="jnp", pop_size=POP,
                           generations=GENS)]
    plans = plan_batch(reqs)
    assert [len(p.requests) for p in plans] == [6, 2, 1, 1]
    # the table group ignores workload shape entirely; its chunk is padded
    # to the widest/deepest member
    assert plans[0].pad_w == 4 and plans[0].slots == 6
    assert {p.signature for p in plans[2:]} == {
        plans[2].signature, plans[3].signature
    }
    assert plans[2].signature != plans[3].signature


def test_plan_batch_chunks_large_groups(ws):
    reqs = _mixed_requests(ws, 150, backend="table")
    plans = plan_batch(reqs, max_slots=64)
    assert [p.slots for p in plans] == [64, 64, 64]
    assert [len(p.requests) for p in plans] == [64, 64, 22]
    assert sorted(i for p in plans for i in p.indices) == list(range(150))


def test_plan_batch_exact_fit_no_padding(ws):
    # a group that fits in one launch runs at its exact size (driver paths
    # like batched_search pay zero pad overhead)
    plans = plan_batch(_mixed_requests(ws, 20, backend="table"), max_slots=64)
    assert len(plans) == 1 and plans[0].slots == 20


def test_request_validation(ws):
    with pytest.raises(ValueError, match="objective"):
        SearchRequest(ws=ws, objective="nope").signature()
    with pytest.raises(ValueError, match="backend"):
        SearchRequest(ws=ws, backend="nope").signature()


def test_scheduling_fields_never_touch_the_signature(ws):
    """priority/deadline_s are scheduling metadata: they must not change
    which compiled program a request hits."""
    base = SearchRequest(ws=ws, backend="table", pop_size=POP,
                         generations=GENS)
    urgent = SearchRequest(ws=ws, backend="table", pop_size=POP,
                           generations=GENS, priority=0, deadline_s=0.5)
    lazy = SearchRequest(ws=ws, backend="table", pop_size=POP,
                         generations=GENS, priority=9)
    assert base.signature() == urgent.signature() == lazy.signature()


def test_plan_batch_priority_policy_orders_requests_and_plans(ws):
    reqs = [SearchRequest(ws=ws.subset([i % 4]), seed=i, backend="table",
                          pop_size=POP, generations=GENS, priority=5 - i)
            for i in range(6)]  # priorities 5,4,3,2,1,0
    plans = plan_batch(reqs, policy="priority", max_slots=2)
    flat = [i for p in plans for i in p.indices]
    assert flat == [5, 4, 3, 2, 1, 0]  # most urgent first, chunked 2 by 2
    assert sorted(flat) == list(range(6))  # exact partition
    # fifo on the same mix keeps submit order
    assert [i for p in plan_batch(reqs, max_slots=2) for i in p.indices] \
        == list(range(6))


def test_plan_batch_edf_policy_deadlines_first(ws):
    reqs = [
        SearchRequest(ws=ws, seed=0, backend="table", pop_size=POP,
                      generations=GENS),  # deadline-less -> last
        SearchRequest(ws=ws, seed=1, backend="table", pop_size=POP,
                      generations=GENS, deadline_s=9.0),
        SearchRequest(ws=ws, seed=2, backend="table", pop_size=POP,
                      generations=GENS, deadline_s=2.0),
    ]
    plans = plan_batch(reqs, policy="edf", max_slots=1)
    assert [p.indices[0] for p in plans] == [2, 1, 0]


def test_plan_batch_policy_keeps_chunk_shapes(ws):
    """A policy reorders requests across chunks but the (signature,
    slots) launch shapes — what decides compiled programs — are the
    fifo ones."""
    reqs = [dataclasses.replace(r, priority=i % 3)
            for i, r in enumerate(_mixed_requests(ws, 11, backend="table"))]
    shapes = lambda plans: sorted((p.signature, p.slots) for p in plans)  # noqa: E731
    fifo = shapes(plan_batch(reqs, max_slots=4))
    assert shapes(plan_batch(reqs, policy="priority", max_slots=4)) == fifo
    assert shapes(plan_batch(reqs, policy="edf", max_slots=4)) == fifo


def test_plan_batch_slot_hints_round_up_never_down(ws):
    reqs = _mixed_requests(ws, 3, backend="table")
    sig = reqs[0].signature()
    plans = plan_batch(reqs, max_slots=64, slot_hints={sig: 8})
    assert len(plans) == 1 and plans[0].slots == 8  # 3 real rounded up
    # a hint smaller than the natural size never shrinks the chunk
    plans = plan_batch(reqs, max_slots=64, slot_hints={sig: 2})
    assert [p.slots for p in plans] == [3]
    # a stale hint above max_slots is ignored
    plans = plan_batch(reqs, max_slots=2, slot_hints={sig: 8})
    assert [p.slots for p in plans] == [2, 2]


# ------------------------------------------------------- host-built keys
EDGE_SEEDS = [0, 1, 2**31 - 1, 2**31, 3141592653, 2**32 - 1, 2**32, 2**40,
              -1, -2**31, -2**31 - 1, np.int64(5), np.uint32(7), True]


@pytest.mark.parametrize("seed", EDGE_SEEDS, ids=repr)
def test_key_data_equals_prng_key(seed, monkeypatch):
    """The host-built key is ``PRNGKey(seed)``'s dtype and bytes, and
    builds it without calling ``PRNGKey``."""
    want = np.asarray(jax.random.PRNGKey(seed))
    monkeypatch.setattr(jax.random, "PRNGKey", None)  # any call raises
    got = SearchRequest(ws=None, seed=seed).key_data()
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("setting", ["x64", "rbg", "int_beyond_int64"])
def test_key_data_falls_back_off_the_formula(setting, monkeypatch):
    """Where the formula's preconditions fail, the helper takes the
    ``jax.random.PRNGKey`` path and gives what it gives."""
    real, calls = jax.random.PRNGKey, []

    def spy(seed):
        calls.append(seed)
        return real(seed)

    monkeypatch.setattr(jax.random, "PRNGKey", spy)
    if setting == "int_beyond_int64":
        with pytest.raises(OverflowError):
            SearchRequest(ws=None, seed=2**63).key_data()
        assert calls == [2**63]
        return
    ctx = (jax.enable_x64(True) if setting == "x64"
           else jax.default_prng_impl("rbg"))
    with ctx:
        got = SearchRequest(ws=None, seed=2**40 + 3).key_data()
        want = np.asarray(real(2**40 + 3))
    assert calls == [2**40 + 3]
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    # not the formula's [0, seed mod 2**32]: the settings change the key
    assert got.tobytes() != np.array([0, 3], np.uint32).tobytes()


def _key_data_case(case):
    """Stacked ``(S, 2)`` uint32 key data for one split-parity case."""
    if case.startswith("slots-"):
        s = int(case.split("-")[1])
        return np.random.default_rng(s).integers(
            0, 2**32, (s, 2), dtype=np.uint32)
    if case == "edge-seeds":
        return np.stack([SearchRequest(ws=None, seed=s).key_data()
                         for s in EDGE_SEEDS])
    if case == "prngkey":
        return np.stack([np.asarray(jax.random.PRNGKey(s))
                         for s in (0, 42, 2**31 + 11, 3141592653)])
    assert case == "prior-split"
    return np.asarray(jax.random.split(jax.random.PRNGKey(7), 5))


@pytest.mark.parametrize("case", [
    "slots-1", "slots-3", "slots-64", "slots-256", "edge-seeds", "prngkey",
    "prior-split"])
def test_host_split_equals_vmapped_split(case, monkeypatch):
    """The host split is ``jax.vmap(jax.random.split)``'s dtype and bits,
    and runs no device split."""
    data = _key_data_case(case)
    want = np.asarray(jax.vmap(jax.random.split)(jnp.asarray(data)))
    monkeypatch.setattr(engine_mod, "_device_split", None)  # any call raises
    got, on_host = engine_mod.split_key_data(data)
    assert on_host and isinstance(got, np.ndarray)
    assert got.dtype == want.dtype and got.shape == want.shape == (
        len(data), 2, 2)
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("setting", ["not-partitionable", "rbg"])
def test_host_split_falls_back_off_the_formula(setting):
    """Where the formula's preconditions fail, the split runs on the
    device and still gives what ``jax.vmap(jax.random.split)`` gives."""
    ctx = (jax.threefry_partitionable(False)
           if setting == "not-partitionable" else jax.default_prng_impl("rbg"))
    with ctx:
        data = np.stack([np.asarray(jax.random.PRNGKey(s))
                         for s in (0, 3, 2**31 + 5)])
        got, on_host = engine_mod.split_key_data(data)
        want = np.asarray(jax.vmap(jax.random.split)(jnp.asarray(data)))
    assert not on_host
    assert np.asarray(got).tobytes() == want.tobytes()
    # the formula's bits differ here: the fallback was needed
    if setting == "not-partitionable":
        host = np.stack(engine_mod.threefry2x32(
            data[:, :1], data[:, 1:], np.zeros(2, np.uint32),
            np.arange(2, dtype=np.uint32)), axis=-1)
        assert host.tobytes() != want.tobytes()


def test_seed_only_pipelined_launch_reads_no_key(ws, monkeypatch):
    """A seed-only pipelined launch keys its slots on the host: a read
    inside ``dse.dispatch.keys`` raises, and the answers are bit-identical
    to ``run_search`` under ``prng_key()`` and to the sequential engine."""
    reqs = [dataclasses.replace(r, seed=s) for r, s in zip(
        _mixed_requests(ws, 4), [3141592653, 2**32 + 5, -7, 11])]
    seq = SearchEngine(max_slots=4).run(reqs)
    # run_search passes an explicit key, which the engine reads back
    refs = [run_search(
        req.prng_key(), req.ws, objective=req.objective,
        area_constr=req.area_constr, pop_size=req.pop_size,
        generations=req.generations, top_k=req.top_k, backend=req.backend)
        for req in reqs]

    open_spans = []
    real_span = engine_mod.spans.span

    class _Tracked:
        def __init__(self, name, **attrs):
            self.name, self.cm = name, real_span(name, **attrs)

        def __enter__(self):
            open_spans.append(self.name)
            return self.cm.__enter__()

        def __exit__(self, *exc):
            open_spans.pop()
            return self.cm.__exit__(*exc)

    real_sync = SearchEngine._sync

    def guarded_sync(self, x):
        assert "dse.dispatch.keys" not in open_spans, "key read in dispatch"
        return real_sync(self, x)

    monkeypatch.setattr(engine_mod.spans, "span", _Tracked)
    monkeypatch.setattr(SearchEngine, "_sync", guarded_sync)
    eng = SearchEngine(max_slots=4, pipelined=True)
    plan = plan_batch(reqs, max_slots=4)[0]
    pend = eng.dispatch(plan)
    assert eng.syncs == 0  # keys on the host, seed check deferred
    pip = eng.harvest(pend)
    for a, b, ref in zip(pip, seq, refs):
        for other in (b, ref):
            np.testing.assert_array_equal(a.top_scores, other.top_scores)
            np.testing.assert_array_equal(a.top_genomes, other.top_genomes)


# ------------------------------------------------- heterogeneous parity
def test_heterogeneous_table_batch_matches_run_search(ws):
    reqs = _mixed_requests(ws, 8, backend="table")
    out = default_engine().run(reqs)
    for req, res in zip(reqs, out):
        _assert_matches_run_search(req, res)


def test_heterogeneous_dense_batch_matches_run_search(ws):
    # same (W, L) shape -> one dense group, mixed objectives/areas/seeds
    subsets = [[0, 1], [2, 3], [3, 0], [1, 2]]
    reqs = [
        SearchRequest(
            ws=ws.subset(subsets[i % 4]), objective=OBJECTIVES[i % 4],
            area_constr=[150.0, 100.0][i % 2], seed=i, backend="jnp",
            pop_size=POP, generations=GENS,
        )
        for i in range(6)
    ]
    assert len(plan_batch(reqs)) == 1
    out = default_engine().run(reqs)
    for req, res in zip(reqs, out):
        _assert_matches_run_search(req, res)


def test_mixed_backends_one_submission(ws):
    reqs = [
        SearchRequest(ws=ws, seed=0, backend="table", pop_size=POP,
                      generations=GENS),
        SearchRequest(ws=ws, seed=1, backend="jnp", pop_size=POP,
                      generations=GENS),
        SearchRequest(ws=ws.subset([1]), seed=2, backend="table",
                      pop_size=POP, generations=GENS),
    ]
    assert len(plan_batch(reqs)) == 2  # table group + dense group
    out = default_engine().run(reqs)
    for req, res in zip(reqs, out):
        _assert_matches_run_search(req, res)


def test_engine_run_preserves_request_order(ws):
    reqs = _mixed_requests(ws, 5, backend="table")
    reqs.insert(2, SearchRequest(ws=ws, seed=99, backend="jnp",
                                 pop_size=POP, generations=GENS))
    out = default_engine().run(reqs)
    for req, res in zip(reqs, out):
        assert res.workload_names == req.ws.names


def test_init_genomes_mixed_with_seeded(ws):
    """Requests with a caller init pack with seeded ones; the caller's
    array is copied (the GA donates), never consumed."""
    from repro.core.search import seed_population

    init = seed_population(jax.random.PRNGKey(7), ws, POP)
    reqs = [
        SearchRequest(ws=ws, seed=0, backend="table", pop_size=POP,
                      generations=2, init_genomes=init),
        SearchRequest(ws=ws, seed=1, backend="table", pop_size=POP,
                      generations=2),
    ]
    out = default_engine().run(reqs)
    assert len(out) == 2
    assert np.asarray(init).shape == (POP, init.shape[1])  # still readable
    ref = run_search(reqs[0].prng_key(), ws, pop_size=POP, generations=2,
                     backend="table", init_genomes=init)
    np.testing.assert_array_equal(
        np.asarray(out[0].ga.scores), np.asarray(ref.ga.scores)
    )


# --------------------------------------------------- acceptance: 256-mix
def test_256_requests_drain_through_at_most_4_programs(ws):
    """256 heterogeneous table-backend requests (mixed workload subsets,
    objectives, seeds) drain through <= 4 compiled search programs (one
    seeding jit + one GA jit entry in steady state), bit-identical to
    per-request ``run_search``."""
    pop, gens = 8, 2
    reqs = _mixed_requests(ws, 256, backend="table", pop=pop, gens=gens,
                           seed0=10_000)
    svc = DSEService()
    rids = svc.submit_all(reqs)
    n_ga0 = ga_mod._run_ga_batched_jit._cache_size()
    n_seed0 = engine_mod._seed_batched_jit._cache_size()
    results = svc.drain()
    new_programs = (
        ga_mod._run_ga_batched_jit._cache_size() - n_ga0
        + engine_mod._seed_batched_jit._cache_size() - n_seed0
    )
    assert new_programs <= 4, new_programs
    assert svc.stats.launches == 4  # 256 / 64 slots
    assert len(results) == 256 and set(rids) == set(results)
    # bit-identical spot checks across the whole mix (every 37th request
    # hits different subset/objective/area combinations)
    for i in range(0, 256, 37):
        _assert_matches_run_search(reqs[i], results[rids[i]])


# ----------------------------------------------------------- fingerprints
def test_fingerprint_content_keyed(ws):
    ws2 = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    assert ws2 is not ws and ws2.fingerprint() == ws.fingerprint()
    assert ws.subset([0]).fingerprint() != ws.fingerprint()
    assert ws.subset([0, 1]).fingerprint() == ws2.subset([0, 1]).fingerprint()


def test_tables_memo_hits_across_repacked_sets(ws):
    from repro.core import space
    from repro.imc.tech import TECH

    t1 = ws.tables()
    ws2 = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    assert ws2.tables() is t1  # content-keyed, not object-keyed
    assert (ws.fingerprint(), TECH, space.grid_token()) in _TABLES_MEMO


def test_engine_padded_table_cache_content_keyed(ws):
    eng = SearchEngine()
    r1 = SearchRequest(ws=ws.subset([0, 1]), backend="table")
    r2 = SearchRequest(ws=ws.subset([0, 1]), backend="table", seed=5)
    t1 = eng._padded_request_tables(r1, 4)
    t2 = eng._padded_request_tables(r2, 4)
    assert t1 is t2  # same fingerprint + pad width -> one padded copy
    assert t1[0].shape[0] == 4  # demand leaf padded W 2 -> 4
    np.testing.assert_array_equal(t1[0][2:], 0.0)


# -------------------------------------------------------------- service
def test_service_interleaved_submit_and_step(ws):
    svc = DSEService()
    first = svc.submit_all(_mixed_requests(ws, 3, pop=8, gens=2))
    done1 = svc.step()
    assert {rid for rid, _ in done1} == set(first)
    # a request submitted after the first step joins the next plan
    late = svc.submit(SearchRequest(ws=ws.subset([1]), seed=42,
                                    backend="table", pop_size=8,
                                    generations=2))
    assert svc.pending() == 1
    done2 = svc.step()
    assert [rid for rid, _ in done2] == [late]
    assert svc.pending() == 0 and svc.step() == []
    assert svc.stats.completed == 4 and svc.stats.launches == 2


def test_service_ragged_drain_keeps_padded_tail_program(ws):
    """A drain whose group size is not a multiple of the slot count must
    execute the ORIGINAL padded-tail chunking (one compiled program per
    group), not re-plan the shrunken residue into a fresh program shape
    each step."""
    svc = DSEService(max_slots=4)
    reqs = [SearchRequest(ws=ws, seed=100 + i, backend="table", pop_size=8,
                          generations=2) for i in range(6)]
    rids = svc.submit_all(reqs)
    # warm the 4-slot program shape so only NEW shapes would compile below
    pre = SearchEngine(max_slots=4)
    pre.run(reqs[:4])
    n_ga0 = ga_mod._run_ga_batched_jit._cache_size()
    n_seed0 = engine_mod._seed_batched_jit._cache_size()
    results = svc.drain()
    assert len(results) == 6 and svc.stats.launches == 2  # 4 + padded 2
    new = (ga_mod._run_ga_batched_jit._cache_size() - n_ga0
           + engine_mod._seed_batched_jit._cache_size() - n_seed0)
    assert new == 0, f"ragged tail compiled {new} extra program(s)"
    for req, rid in zip(reqs, rids):
        _assert_matches_run_search(req, results[rid])


def test_service_mid_drain_submit_zero_new_programs(ws):
    """Submitting WHILE plans are cached (mid-drain) must not compile:
    the re-planned residue rounds up to the signature's warm slot size
    (the service's slot hints), so the ragged tail and the post-submit
    chunk both reuse the 4-slot program — and every rid still maps to
    the result of its OWN request."""
    svc = DSEService(max_slots=4)
    reqs = [SearchRequest(ws=ws, seed=200 + i, backend="table", pop_size=8,
                          generations=2) for i in range(6)]
    rids = svc.submit_all(reqs)
    # warm the 4-slot program shape so only NEW shapes would compile below
    SearchEngine(max_slots=4).run(reqs[:4])
    n_ga0 = ga_mod._run_ga_batched_jit._cache_size()
    n_seed0 = engine_mod._seed_batched_jit._cache_size()
    svc.step()  # launch 1 of the cached [4, padded-2] plan
    late = SearchRequest(ws=ws.subset([1, 2]), seed=777, backend="table",
                         pop_size=8, generations=2)
    rids.append(svc.submit(late))  # invalidates the cache: 2 + 1 remain
    reqs.append(late)
    results = svc.drain()
    assert svc.stats.launches == 2  # 4 real, then 3 real in the 4-slot shape
    new = (ga_mod._run_ga_batched_jit._cache_size() - n_ga0
           + engine_mod._seed_batched_jit._cache_size() - n_seed0)
    assert new == 0, f"mid-drain submit compiled {new} extra program(s)"
    for req, rid in zip(reqs, rids):
        _assert_matches_run_search(req, results[rid])


def _mixed_priority_requests(ws, n, pop=8, gens=2, seed0=0):
    """Mixed subsets/objectives/seeds AND priorities 1..7 (never 0, so a
    later priority-0 submit is uniquely the most urgent)."""
    reqs = _mixed_requests(ws, n, backend="table", pop=pop, gens=gens,
                           seed0=seed0)
    return [dataclasses.replace(r, priority=1 + i % 7)
            for i, r in enumerate(reqs)]


# ----------------------------------------- acceptance: async mixed-priority
def test_async_drain_bit_identical_to_sync_with_priority_jump(ws):
    """256 mixed-priority requests drained through AsyncDSEService are
    bit-identical to the synchronous DSEService drain of the same mix,
    AND a priority-0 request submitted mid-drain (from the first launch's
    future callback — which runs on the worker thread BEFORE the next
    dispatch, so the schedule is deterministic) launches before the
    lower-priority work that is still queued."""
    n = 256
    sync_svc = DSEService(policy="priority")
    sync_rids = sync_svc.submit_all(_mixed_priority_requests(ws, n))
    sync_res = sync_svc.drain()

    async_svc = AsyncDSEService(policy="priority", paused=True)
    reqs = _mixed_priority_requests(ws, n)
    jump_req = SearchRequest(ws=ws.subset([0]), seed=31337, backend="table",
                             pop_size=8, generations=2, priority=0)
    jump: dict = {}

    def submit_urgent(_fut):
        if not jump:  # first completed future only
            jump["fut"] = async_svc.submit(jump_req)

    futs = async_svc.submit_all(reqs)
    for f in futs:
        f.add_done_callback(submit_urgent)
    async_svc.resume()
    results = async_svc.drain(timeout=600)
    async_svc.close()

    # --- the priority-0 jump: submitted after launch 1, launched next
    assert "fut" in jump
    jump_rid = jump["fut"].rid
    jump_launch = next(i for i, l in enumerate(async_svc.launch_log)
                       if jump_rid in l)
    assert jump_launch == 1, async_svc.launch_log
    later = [rid for l in async_svc.launch_log[2:] for rid in l]
    assert later, "nothing queued behind the urgent request"
    by_rid = dict(zip([f.rid for f in futs], reqs))
    assert all(by_rid[rid].priority > 0 for rid in later)

    # --- bit-identical to the synchronous drain of the same mix
    assert len(results) == n + 1
    for f, sync_rid, req in zip(futs, sync_rids, reqs):
        a, s = f.result(), sync_res[sync_rid]
        np.testing.assert_array_equal(np.asarray(a.ga.scores),
                                      np.asarray(s.ga.scores))
        np.testing.assert_array_equal(a.top_scores, s.top_scores)
        np.testing.assert_array_equal(a.top_genomes, s.top_genomes)
        assert a.workload_names == req.ws.names
    assert np.isfinite(jump["fut"].result().top_scores).all()
    # latency telemetry recorded for every request
    assert len(async_svc.stats.latency_samples) == n + 1
    assert len(async_svc.stats.wait_samples) == n + 1


def test_async_submit_returns_future_without_blocking(ws):
    with AsyncDSEService() as svc:
        fut = svc.submit(SearchRequest(ws=ws.subset([0]), seed=5,
                                       backend="table", pop_size=8,
                                       generations=2))
        res = fut.result(timeout=300)
    _assert_matches_run_search(
        SearchRequest(ws=ws.subset([0]), seed=5, backend="table",
                      pop_size=8, generations=2), res)
    assert svc.stats.completed == 1


def test_service_stream_yields_all(ws):
    svc = DSEService()
    rids = svc.submit_all(_mixed_requests(ws, 4, pop=8, gens=2))
    seen = [rid for rid, _ in svc.stream()]
    assert sorted(seen) == sorted(rids)
    assert all(len(svc.results[r].top_scores) >= 0 for r in rids)


def test_paper_request_mix_covers_all_kinds(ws):
    reqs = paper_request_mix(ws, 16, pop_size=8, generations=2)
    assert {r.objective for r in reqs} == set(OBJECTIVES)
    assert len({r.ws.names for r in reqs}) > 1
    assert len({r.seed for r in reqs}) == 16


# ------------------------------------------------------------- multidevice
@pytest.mark.multidevice
def test_heterogeneous_batch_sharded_parity(ws):
    """The packed heterogeneous drain on a (search, population) mesh is
    bit-identical to the meshless engine AND to per-request run_search."""
    from repro.core.distributed import sharded_search_engine
    from repro.launch.mesh import make_search_mesh

    reqs = _mixed_requests(ws, 8, backend="table")
    eng = sharded_search_engine(make_search_mesh(2, 4))
    out = eng.run(reqs)
    ref = SearchEngine().run(reqs)
    for req, s, r in zip(reqs, out, ref):
        np.testing.assert_array_equal(
            np.asarray(s.ga.scores), np.asarray(r.ga.scores)
        )
        np.testing.assert_array_equal(s.top_genomes, r.top_genomes)
        _assert_matches_run_search(req, s)


@pytest.mark.multidevice
def test_service_on_mesh(ws):
    # (2, 4) mirrors the table-backend layouts the sharded parity suite
    # pins; the full (incl. (4,2)-ragged) envelope characterization lives
    # in tests/test_search_sharded.py::test_table_backend_sharded_parity_
    # envelope.
    from repro.launch.mesh import make_search_mesh

    svc = DSEService(mesh=make_search_mesh(2, 4))
    reqs = _mixed_requests(ws, 6, pop=8, gens=2)
    rids = svc.submit_all(reqs)
    results = svc.drain()
    assert set(rids) == set(results)
    for rid, req in zip(rids, reqs):
        _assert_matches_run_search(req, results[rid])
