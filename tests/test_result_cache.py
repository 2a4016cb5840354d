"""Result-cache suite: the plan_key/TechParams collision regression and
the fingerprint-keyed request cache (ISSUE: cache PR).

What this file pins:

  * **plan_key regression** — ``plan_key`` hashes ``TechParams``: plans
    differing only in one tech field get distinct keys, and a checkpoint
    written under tech A is never resumed by the same plan under tech B.
  * **request_key semantics** — everything that determines a result bit
    changes the key (objective, weights, area, backend, GA params,
    top_k, tech, PRNG key bytes, init population); scheduling metadata
    (priority, deadline) never does, and ``seed=n`` equals
    ``key=PRNGKey(n)``.
  * **Cache correctness** — a hit is bit-identical to a fresh search,
    partials are refused, the memory tier evicts in LRU order, and the
    disk tier survives a process "restart" (a fresh cache over the same
    directory) with ``top_designs`` recomputed, never drifted.
  * **Service integration** — a drain with 50% repeated requests needs
    exactly half the launches (fifo and priority; virtual-clock sim),
    and an identical resubmitted mix drains with ZERO new GA launches
    and bit-identical results through both the sync and async front
    ends (real engine).
  * **Streaming** — ``on_progress`` best-so-far snapshots are monotone
    non-increasing and exactly the accumulated history's prefix;
    single-shot engines never emit.
  * **Satellites** — the ``_TABLES_MEMO`` LRU cap (env-tunable,
    eviction + rebuild) and ``ServiceStats`` None-not-NaN percentiles.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest
from sim_scheduler import StubEngine, VirtualClock, sim_request

from repro.core import engine as engine_mod
from repro.core.engine import (
    SearchEngine,
    SearchRequest,
    empty_partial_result,
    plan_batch,
    plan_key,
)
from repro.imc.tech import TECH
from repro.serve.cache import ResultCache, request_key
from repro.serve.dse import AsyncDSEService, DSEService, ServiceStats
from repro.workloads.cnn import cnn_workload
from repro.workloads.pack import pack_workloads

POP, GENS = 8, 6  # the segment suite's operating point: warm jit caches


@pytest.fixture(scope="module")
def ws():
    return pack_workloads(
        [(n, cnn_workload(n)) for n in ("resnet18", "vgg16")]
    )


def _reqs(ws, n, *, gens=GENS, seed0=0, tech=TECH):
    subsets = [[0, 1], [0], [1]]
    return [
        SearchRequest(ws=ws.subset(subsets[i % 3]), seed=seed0 + i,
                      backend="table", pop_size=POP, generations=gens,
                      tech=tech)
        for i in range(n)
    ]


@pytest.fixture(scope="module")
def one(ws):
    """One request + its uncached reference result (shared: GA runs are
    the expensive part of this suite)."""
    req = _reqs(ws, 1, seed0=11)[0]
    return req, SearchEngine().run([req])[0]


def _assert_bit_equal(a, b, ctx=""):
    assert a.objective == b.objective and a.workload_names == b.workload_names
    assert a.valid == b.valid and a.partial == b.partial
    assert a.generations == b.generations
    assert a.top_designs == b.top_designs, ctx
    for name in ("top_scores", "top_genomes", "convergence"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{ctx}: {name}")
    for name in ("genomes", "scores", "best_genome", "best_score"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.ga, name)), np.asarray(getattr(b.ga, name)),
            err_msg=f"{ctx}: ga.{name}")


# ------------------------------------------------------ plan_key regression
def _perturb(tech, field):
    v = getattr(tech, field)
    new = v + 1 if isinstance(v, int) else v * 1.5 + 1e-9
    return tech._replace(**{field: new})


def test_plan_key_distinct_under_any_single_tech_field(ws):
    """THE regression: plans identical except for ONE TechParams field
    must hash to distinct checkpoint keys — for every field.  (The
    original bug omitted ``tech`` entirely, colliding all of these.)"""
    req = _reqs(ws, 1)[0]
    base = plan_key(plan_batch([req], max_slots=64)[0])
    for field in TECH._fields:
        other = SearchRequest(
            ws=req.ws, seed=req.seed, backend=req.backend,
            pop_size=req.pop_size, generations=req.generations,
            tech=_perturb(TECH, field),
        )
        key = plan_key(plan_batch([other], max_slots=64)[0])
        assert key != base, f"plan_key collides when only tech.{field} differs"


def test_checkpoint_under_tech_a_not_resumed_under_tech_b(
    ws, tmp_path, monkeypatch
):
    """A drain killed mid-search under tech A leaves its checkpoint on
    disk; re-running the SAME plan under tech B must ignore it (fresh
    trajectory, bit-identical to an uninterrupted tech-B run) and leave
    A's state untouched for A's own restart."""
    from repro.checkpoint import store

    tech_b = TECH._replace(adc_energy_pj=TECH.adc_energy_pj * 4.0)
    req_a = _reqs(ws, 1, seed0=70)[0]
    req_b = _reqs(ws, 1, seed0=70, tech=tech_b)[0]
    ck_root = tmp_path / "ck"

    real = engine_mod.run_ga_batched_segment
    calls = {"n": 0}

    def killed_on_second(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise KeyboardInterrupt()
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", killed_on_second)
    eng_a = SearchEngine(segment_gens=2, checkpoint_dir=str(ck_root))
    with pytest.raises(KeyboardInterrupt):
        eng_a.run([req_a])
    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", real)

    key_a = plan_key(plan_batch([req_a], max_slots=eng_a.max_slots)[0])
    key_b = plan_key(plan_batch([req_b], max_slots=eng_a.max_slots)[0])
    assert key_a != key_b
    assert store.latest_step(ck_root / key_a) == 2  # A's state committed

    ref_b = SearchEngine(segment_gens=2).run([req_b])[0]
    out_b = SearchEngine(
        segment_gens=2, checkpoint_dir=str(ck_root)
    ).run([req_b])[0]
    _assert_bit_equal(out_b, ref_b, "tech-B run resumed tech-A state")
    # B completed and cleared ITS directory; A's checkpoint is untouched
    assert store.latest_step(ck_root / key_a) == 2


# -------------------------------------------------- request_key semantics
def test_request_key_stable_and_seed_equals_explicit_key(ws):
    a = _reqs(ws, 1, seed0=3)[0]
    b = _reqs(ws, 1, seed0=3)[0]  # rebuilt, equal content
    assert request_key(a) == request_key(b)
    c = SearchRequest(ws=a.ws, seed=999, key=jax.random.PRNGKey(3),
                      backend="table", pop_size=POP, generations=GENS)
    assert request_key(c) == request_key(a)  # key bytes, not the seed int


@pytest.mark.parametrize("seed", [0, 7, 2**31, 3141592653, 2**32 + 1, -1])
def test_seed_hashes_unchanged_by_host_keys(ws, seed, monkeypatch):
    """``request_key`` and ``plan_key`` of a seed-only request hash the
    host-built key: byte for byte what they hashed when they read
    ``PRNGKey(seed)`` from the device, and what an explicit key hashes."""
    req = _reqs(ws, 1, seed0=seed)[0]
    explicit = dataclasses.replace(req, key=jax.random.PRNGKey(seed))

    def hashes(r):
        return request_key(r), plan_key(plan_batch([r], max_slots=4)[0])

    host = hashes(req)
    assert hashes(explicit) == host
    monkeypatch.setattr(SearchRequest, "key_data",
                        lambda self: np.asarray(self.prng_key()))
    assert hashes(req) == host


def test_request_key_excludes_scheduling_metadata(ws):
    import dataclasses

    base = _reqs(ws, 1)[0]
    for change in ({"priority": 7}, {"deadline_s": 5.0}):
        other = dataclasses.replace(base, **change)
        assert request_key(other) == request_key(base), change


def test_request_key_distinct_per_result_bit_field(ws):
    import dataclasses

    base = _reqs(ws, 1)[0]
    changes = [
        {"objective": "edp"},
        {"obj_weights": (1.0, 2.0, 1.0)},
        {"area_constr": 151.0},
        {"backend": "jnp"},
        {"pop_size": POP + 1},
        {"generations": GENS + 1},
        {"top_k": 5},
        {"tech": _perturb(TECH, "adc_bits")},
        {"key": jax.random.PRNGKey(12345)},
        {"init_genomes": np.full((POP, 8), 0.5, np.float32)},
        {"ws": base.ws.subset([0])},
    ]
    keys = {request_key(base)}
    for change in changes:
        k = request_key(dataclasses.replace(base, **change))
        assert k not in keys, f"request_key collides on {list(change)}"
        keys.add(k)


# ------------------------------------------------------- cache correctness
def test_hit_bit_identical_to_fresh_search_and_zero_recompute(ws, one):
    req, fresh = one
    cache = ResultCache()
    eng = SearchEngine(result_cache=cache)
    a = eng.run([req])[0]
    b = eng.run([req])[0]
    assert b is a  # memory-tier hit: the stored object, nothing re-ran
    assert cache.stats.hits == 1 and cache.stats.puts == 1
    _assert_bit_equal(a, fresh, "cached vs uncached engine")


def test_put_refuses_partial_results(ws):
    req = _reqs(ws, 1)[0]
    cache = ResultCache()
    assert cache.put(req, empty_partial_result(req)) is False
    assert len(cache) == 0 and cache.get(req) is None


class _Full:
    """Duck-typed full result for tier mechanics (no GA needed)."""

    partial = False
    ga = True

    def __init__(self, tag):
        self.tag = tag


def test_lru_eviction_order_and_refresh_on_access():
    cache = ResultCache(capacity=2)
    cache.put("k1", _Full(1))
    cache.put("k2", _Full(2))
    assert cache.get("k1").tag == 1  # refresh: k2 becomes LRU
    cache.put("k3", _Full(3))  # evicts k2, not k1
    assert cache.mem_keys() == ["k1", "k3"]
    assert cache.get("k2") is None
    assert cache.stats.evictions == 1 and cache.stats.misses == 1
    with pytest.raises(ValueError):
        ResultCache(capacity=0)


def test_disk_tier_survives_restart_bit_identical(tmp_path, one):
    req, res = one
    c1 = ResultCache(disk_dir=tmp_path / "rc")
    c1.put(req, res)
    key = request_key(req)
    assert c1.disk_keys() == [key]

    c2 = ResultCache(disk_dir=tmp_path / "rc")  # "restarted process"
    hit = c2.get(req)
    assert hit is not None and hit is not res
    assert c2.stats.disk_hits == 1
    _assert_bit_equal(hit, res, "disk roundtrip")
    assert key in c2.mem_keys()  # promoted into the memory tier

    c2.clear()  # memory only: disk entry stays
    assert c2.disk_keys() == [key] and c2.get(req) is not None
    c2.clear(disk=True)
    assert c2.disk_keys() == [] and key not in c2


def _mini_full(tag: float):
    """The smallest REAL full SearchResult (disk-tier encodable)."""
    from repro.core.engine import SearchResult
    from repro.core.ga import GAResult

    n = 4
    ga = GAResult(genomes=np.full((2, 3, n), tag, np.float32),
                  scores=np.full((2, 3), tag, np.float32),
                  best_genome=np.zeros(n, np.float32),
                  best_score=np.float32(tag))
    return SearchResult(workload_names=("m",), objective="ela", ga=ga,
                        top_designs=[], top_scores=np.zeros((0,), np.float32),
                        top_genomes=np.zeros((0, n), np.float32),
                        convergence=np.full((2,), tag, np.float32),
                        valid=False, partial=False, generations=1)


def test_memory_eviction_never_touches_disk(tmp_path):
    cache = ResultCache(capacity=1, disk_dir=tmp_path / "rc")
    cache.put("k1", _mini_full(1.0))
    cache.put("k2", _mini_full(2.0))  # evicts k1 from memory ONLY
    assert cache.mem_keys() == ["k2"]
    assert sorted(cache.disk_keys()) == sorted(["k1", "k2"])
    # the evicted entry comes back from disk, intact
    back = cache.get("k1")
    assert back is not None and float(np.asarray(back.ga.best_score)) == 1.0


# ----------------------------------------------------- service integration
class _FullSim:
    """StubEngine result upgraded to what ResultCache accepts."""

    partial = False
    ga = True

    def __init__(self, seed, names):
        self.seed = seed
        self.workload_names = names


class _FullStub(StubEngine):
    def execute(self, plan, *, mesh=None):
        return [_FullSim(s.seed, s.workload_names)
                for s in super().execute(plan, mesh=mesh)]


@pytest.mark.parametrize("policy", ["fifo", "priority"])
def test_drain_with_half_repeats_exactly_halves_launches(policy):
    """The 256-request acceptance drill, sim form: after a 128-unique
    warmup drain, a 256-request drain whose half are repeats launches
    EXACTLY the 8 chunks the 128 fresh requests need — the 128 repeats
    resolve at submit, each with its own original's result."""
    clock = VirtualClock()
    stub = _FullStub(clock, max_slots=16, launch_s=1.0)
    svc = DSEService(engine=stub, policy=policy, clock=clock,
                     sleep=clock.advance, result_cache=ResultCache())
    for i in range(128):
        svc.submit(sim_request(i, priority=i % 4))
    svc.drain()
    assert svc.stats.launches == 8  # 128 / 16 slots

    expect = {}
    for i in range(128):
        # repeats carry DIFFERENT priorities than the originals:
        # scheduling metadata must not break the cache key
        expect[svc.submit(sim_request(i, priority=(i + 2) % 4))] = i
        expect[svc.submit(sim_request(1000 + i, priority=i % 4))] = 1000 + i
    svc.drain()
    assert svc.stats.launches == 16, "repeats burned launches"
    assert svc.stats.cache_hits == 128
    assert svc.stats.completed == svc.stats.submitted == 384
    for rid, seed in expect.items():
        assert svc.results[rid].seed == seed, "rid got a foreign result"


def test_identical_resubmit_zero_launches_sync_and_async(ws):
    """Real-engine acceptance: the identical mix resubmitted drains with
    ZERO new GA launches, bit-identical, sync and async."""
    cache = ResultCache()
    svc = DSEService(result_cache=cache)
    rids = svc.submit_all(_reqs(ws, 6, seed0=300))
    cold = dict(svc.drain())
    launches = svc.stats.launches
    assert launches > 0 and svc.stats.cache_hits == 0

    rids2 = svc.submit_all(_reqs(ws, 6, seed0=300))
    hot = svc.drain()
    assert svc.stats.launches == launches
    assert svc.stats.cache_hits == 6
    for r1, r2 in zip(rids, rids2):
        _assert_bit_equal(cold[r1], hot[r2], f"sync rid {r1}->{r2}")

    with AsyncDSEService(result_cache=cache) as asvc:
        futs = asvc.submit_all(_reqs(ws, 6, seed0=300))
        results = [f.result(timeout=600) for f in futs]
    assert asvc.stats.launches == 0 and asvc.stats.cache_hits == 6
    for r1, res in zip(rids, results):
        _assert_bit_equal(cold[r1], res, f"async rid {r1}")


def _assert_thin_bit_equal(a, b, ctx=""):
    """Bit-equality for transfer-thin full results (``ga is None``)."""
    assert a.ga is None and b.ga is None, ctx
    assert a.objective == b.objective and a.workload_names == b.workload_names
    assert a.valid == b.valid and not a.partial and not b.partial
    assert a.generations == b.generations
    assert a.top_designs == b.top_designs, ctx
    for name in ("top_scores", "top_genomes", "convergence"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{ctx}: {name}")


def test_thin_full_results_are_cacheable_partials_still_refused(ws):
    """THE regression (ISSUE 10 headline): pipelined engines return thin
    FULL results (``res.ga is None``), and ``ResultCache.put`` used to
    refuse exactly those — so a pipelined service never populated its
    cache and every resubmit re-ran the GA.  Thin full results now cache;
    partial snapshots (``res.partial``) stay refused."""
    req = _reqs(ws, 1, seed0=500)[0]
    thin = SearchEngine(pipelined=True).run([req])[0]
    assert thin.ga is None and not thin.partial
    cache = ResultCache()
    assert cache.put(req, thin) is True
    assert cache.get(req) is thin
    assert cache.put(req, empty_partial_result(req)) is False


def test_pipelined_resubmit_drain_zero_launches_bit_identical(ws):
    """Acceptance: a 32-request mix drained through a pipelined engine
    with a result cache, resubmitted identically, resolves with ZERO new
    GA launches, bit-identical thin results, and a positive hit rate."""
    cache = ResultCache(capacity=64)
    eng = SearchEngine(pipelined=True)
    svc = DSEService(engine=eng, result_cache=cache)
    rids = svc.submit_all(_reqs(ws, 32, seed0=600))
    cold = dict(svc.drain())
    launches = eng.launches
    assert launches > 0 and cache.stats.puts == 32

    rids2 = svc.submit_all(_reqs(ws, 32, seed0=600))
    hot = dict(svc.drain())
    assert eng.launches == launches, "resubmit burned GA launches"
    assert svc.stats.cache_hits == 32
    assert cache.stats.hit_rate() > 0
    for r1, r2 in zip(rids, rids2):
        _assert_thin_bit_equal(cold[r1], hot[r2], f"rid {r1}->{r2}")


def test_thin_entry_disk_round_trip(tmp_path, ws):
    """A thin full result survives the disk tier across a process
    'restart' with ``ga`` still None and designs recomputed, not drifted."""
    req = _reqs(ws, 1, seed0=510)[0]
    thin = SearchEngine(pipelined=True).run([req])[0]
    c1 = ResultCache(disk_dir=tmp_path / "rc")
    assert c1.put(req, thin)
    c2 = ResultCache(disk_dir=tmp_path / "rc")  # fresh process
    back = c2.get(req)
    assert back is not None and back is not thin
    assert c2.stats.disk_hits == 1
    _assert_thin_bit_equal(back, thin, "thin disk roundtrip")


# ---------------------------------------------------------------- streaming
def test_streamed_snapshots_monotone_and_prefix_of_history(ws):
    reqs = _reqs(ws, 2, seed0=40)
    svc = DSEService(engine=SearchEngine(segment_gens=2))
    snaps = {}
    rid0 = svc.submit(reqs[0],
                      on_progress=lambda r, s: snaps.setdefault(r, []).append(s))
    rid1 = svc.submit(reqs[1])  # unsubscribed chunk-mate: no callbacks
    res = svc.drain()

    assert list(snaps) == [rid0]
    got = snaps[rid0]
    assert len(got) == 2  # G=6, k=2: boundaries at gen 2 and 4; 6 is final
    final = res[rid0]
    bests = [float(np.asarray(s.ga.best_score)) for s in got]
    bests.append(float(np.asarray(final.ga.best_score)))
    assert all(a >= b for a, b in zip(bests, bests[1:])), bests
    for k, snap in enumerate(got):
        assert snap.partial and snap.generations == 2 * (k + 1)
        # the snapshot IS the final trajectory's prefix, bit-for-bit
        np.testing.assert_array_equal(
            np.asarray(snap.convergence),
            np.asarray(final.convergence)[: snap.generations + 1])
        np.testing.assert_array_equal(
            np.asarray(snap.ga.scores),
            np.asarray(final.ga.scores)[: snap.generations + 1])
    assert not final.partial


def test_single_shot_engine_never_streams(ws):
    svc = DSEService()  # no segment_gens: no mid-search boundaries
    called = []
    svc.submit(_reqs(ws, 1, seed0=60)[0],
               on_progress=lambda r, s: called.append(r))
    svc.drain()
    assert called == []


# --------------------------------------------------------------- satellites
def test_tables_memo_lru_cap(monkeypatch):
    from repro.workloads import pack

    monkeypatch.setenv("REPRO_TABLES_MEMO_CAP", "2")
    pack._TABLES_MEMO.clear()
    w1 = pack_workloads([("resnet18", cnn_workload("resnet18"))])
    w2 = pack_workloads([("alexnet", cnn_workload("alexnet"))])
    w3 = pack_workloads([("vgg16", cnn_workload("vgg16"))])

    from repro.core import space

    gt = space.grid_token()  # memo keys carry the active grid's token
    t2 = w2.tables()
    w1.tables()
    w2.tables()  # refresh w2: w1 becomes LRU
    w3.tables()  # evicts w1
    assert len(pack._TABLES_MEMO) == 2
    assert (w1.fingerprint(), TECH, gt) not in pack._TABLES_MEMO
    assert (w2.fingerprint(), TECH, gt) in pack._TABLES_MEMO

    # evicted entries simply rebuild, to identical tables
    t1b = w1.tables()  # evicts w2
    assert (w2.fingerprint(), TECH, gt) not in pack._TABLES_MEMO
    t2b = w2.tables()
    for a, b in zip(jax.tree_util.tree_leaves(t2),
                    jax.tree_util.tree_leaves(t2b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert t1b is w1.tables()  # still memoized while resident

    monkeypatch.setenv("REPRO_TABLES_MEMO_CAP", "0")
    with pytest.raises(ValueError):
        w3.tables()
    pack._TABLES_MEMO.clear()


def test_service_stats_empty_percentiles_are_none_not_nan():
    st = ServiceStats()
    assert st.wait_p(50) is None and st.latency_p(99) is None
    s = st.summary()
    assert s["wait_p50_s"] is None and s["latency_p99_s"] is None
    assert "NaN" not in json.dumps(s)  # json.dumps(nan) emits bare NaN
    st.wait_samples.append(1.0)
    st.latency_samples.append(2.0)
    assert st.wait_p(0) == 1.0 and st.latency_p(100) == 2.0


# ------------------------------------------- cost-model version + grid keying
def test_request_key_changes_on_cost_model_version_bump(ws, monkeypatch):
    """PR-8 satellite: a COST_MODEL_VERSION bump must MISS every existing
    cache entry (persisted disk tiers can outlive a model change), while
    the same version keeps hitting."""
    import repro.imc as imc

    req = _reqs(ws, 1)[0]
    k_before = request_key(req)
    assert request_key(req) == k_before  # same version -> same key
    monkeypatch.setattr(imc, "COST_MODEL_VERSION",
                        imc.COST_MODEL_VERSION + ".bumped")
    assert request_key(req) != k_before


def test_cache_misses_after_cost_model_version_bump(ws, monkeypatch):
    import repro.imc as imc

    req = _reqs(ws, 1, seed0=90)[0]
    cache = ResultCache(capacity=8)
    res = SearchEngine().run([req])[0]
    assert cache.put(req, res)
    assert cache.get(req) is not None
    monkeypatch.setattr(imc, "COST_MODEL_VERSION",
                        imc.COST_MODEL_VERSION + ".bumped")
    assert cache.get(req) is None  # old entry invisible under the new model


def test_request_key_changes_with_grid_density(ws):
    """The active grid density redefines what a genome decodes to, so it
    must enter the request key."""
    from repro.core import space

    req = _reqs(ws, 1)[0]
    k1 = request_key(req)
    try:
        space.configure_grid(2)
        assert request_key(req) != k1
    finally:
        space.configure_grid(1)
    assert request_key(req) == k1


# -------------------------------------------------- hit-rate telemetry
def test_cache_stats_hit_rate(ws):
    cache = ResultCache(capacity=8)
    req = _reqs(ws, 1, seed0=91)[0]
    assert cache.stats.hit_rate() == 0.0  # cold: 0, never NaN
    assert cache.get(req) is None
    assert cache.stats.hit_rate() == 0.0
    res = SearchEngine().run([req])[0]
    cache.put(req, res)
    assert cache.get(req) is not None
    assert cache.get(req) is not None
    s = cache.stats.summary()
    assert s["hits"] == 2 and s["misses"] == 1
    assert s["hit_rate"] == pytest.approx(2 / 3)


def test_service_stats_cache_hit_miss_counters(ws):
    """ServiceStats counts submit-time lookups: one miss then one hit,
    and the summary carries the rate."""
    cache = ResultCache(capacity=8)
    svc = DSEService(result_cache=cache)
    req = _reqs(ws, 1, seed0=92)[0]
    svc.submit(req)
    svc.drain()
    assert (svc.stats.cache_hits, svc.stats.cache_misses) == (0, 1)
    svc.submit(req)  # identical resubmit: resolves at submit
    assert (svc.stats.cache_hits, svc.stats.cache_misses) == (1, 1)
    s = svc.stats.summary()
    assert s["cache_hits"] == 1 and s["cache_misses"] == 1
    assert s["cache_hit_rate"] == pytest.approx(0.5)
    assert ServiceStats().cache_hit_rate() == 0.0  # cacheless: 0, not NaN
