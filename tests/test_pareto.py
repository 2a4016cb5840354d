"""Pareto-front DSE: NSGA-II survival + front epilogue, oracle-pinned.

The contract this module pins (ISSUE 10 tentpole):

  * **Oracle parity** — the batched in-jit non-dominated sort
    (``ga._dominance_rank``), folded-bit crowding (``ga._crowding``) and
    the full front epilogue (``ga._pareto_epilogue``) are BIT-identical
    to a brute-force numpy O(N^2) dominance oracle, under adversarial
    inputs: duplicate decoded cells, -0.0/+0.0 ties, tied all-+inf
    infeasible rows, and NaN-guarded rows.
  * **Mode invariance** — thin and history-returning runs, sequential,
    pipelined and one-slot engines, table and jnp backends all select
    the same front, bit-for-bit.
  * **Engine semantics** — ``SearchRequest(objective="pareto")`` plans
    into its own signature group, validates eagerly, returns per-member
    (E, L, A) ``objective_vectors``, and round-trips the result cache.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ga, space
from repro.core.engine import SearchEngine, SearchRequest, plan_batch
from repro.core.ga import (
    ParetoThin,
    pareto_epilogue_batched,
    run_pareto_batched,
)
from repro.core.objectives import N_PARETO, PARETO, pareto_scalar
from repro.core.search import run_search
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.pack import pack_workloads

POP, GENS, K = 12, 4, 6
SENTINEL = np.int32(0x7FFFFFFF)


@pytest.fixture(scope="module")
def ws():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


# ----------------------------------------------------------- numpy oracle
def np_fold_bits(x: np.ndarray) -> np.ndarray:
    """The sign-folded total-order int32 key, host reference of
    ``ga._fold_bits``."""
    bits = np.ascontiguousarray(np.asarray(x, np.float32)).view(np.int32)
    return np.where(bits < 0, -(bits & SENTINEL), bits).astype(np.int32)


def np_dominance_rank(objs: np.ndarray) -> np.ndarray:
    """Brute-force O(N^2) dominance mask + front peeling — the reference
    algorithm ``ga._dominance_rank`` implements in-jit, replayed in
    plain numpy."""
    o = np.asarray(objs, np.float32)
    N = o.shape[0]
    le = (o[:, None, :] <= o[None, :, :]).all(axis=-1)
    lt = (o[:, None, :] < o[None, :, :]).any(axis=-1)
    dom = le & lt
    rank = np.full(N, -1, np.int32)
    r = 0
    while (rank < 0).any():
        unassigned = rank < 0
        blocked = (dom & unassigned[:, None]).any(axis=0)
        front = unassigned & ~blocked
        rank[front] = r
        r += 1
    return rank


def np_crowding(objs: np.ndarray) -> np.ndarray:
    """Crowding distance in folded-bit space, mirroring ``ga._crowding``
    operation for operation (same f32 arithmetic, same unique sort
    order, same per-objective accumulation order)."""
    o = np.asarray(objs, np.float32)
    N, M = o.shape
    total = np.zeros(N, np.float32)
    for m in range(M):
        key = np_fold_bits(o[:, m])
        perm = np.lexsort((np.arange(N), key))  # unique (key, index) order
        kf = key[perm].astype(np.float32)
        span = np.float32(kf[-1] - kf[0])
        prev = np.concatenate([kf[:1], kf[:-1]])
        nxt = np.concatenate([kf[1:], kf[-1:]])
        with np.errstate(invalid="ignore", divide="ignore"):
            d = np.where(span > 0, (nxt - prev) / span,
                         np.float32(0.0)).astype(np.float32)
        d[0] = np.inf
        d[N - 1] = np.inf
        total[perm] += d
    return total


def np_crowded_order_keys(objs: np.ndarray):
    rank = np_dominance_rank(objs)
    crowd = np_crowding(objs)
    ckey = (-crowd.view(np.int32)).astype(np.int32)
    return rank, ckey


def np_pareto_epilogue(genomes_hist, objs_hist, top_k: int):
    """Host replay of ``ga._pareto_epilogue``: crowded-order positions
    over all evaluated designs, feasibility mask, greedy best-unseen-cell
    picks (whole decoded cell retired per pick), E*L*A convergence."""
    gh = np.asarray(genomes_hist, np.float32)
    oh = np.asarray(objs_hist, np.float32)
    G1, P, n = gh.shape
    M = oh.shape[-1]
    N = G1 * P
    flat_g = gh.reshape(N, n)
    flat_o = oh.reshape(N, M)
    flat_s = ((flat_o[:, 0] * flat_o[:, 1]) * flat_o[:, 2]).astype(np.float32)
    rank, ckey = np_crowded_order_keys(flat_o)
    feas = np.isfinite(flat_o).all(axis=-1)
    perm = np.lexsort((np.arange(N), ckey, rank))
    pos = np.empty(N, np.int64)
    pos[perm] = np.arange(N)
    okey = np.where(feas, pos, np.int64(SENTINEL))
    cells = [tuple(r) for r in space.decode_indices_np(flat_g)]
    k = min(int(top_k), N)
    top_g = np.zeros((k, n), np.float32)
    top_v = np.full((k, M), np.inf, np.float32)
    top_s = np.full((k,), np.inf, np.float32)
    kept = 0
    for i in range(k):
        j = int(np.argmin(okey))
        if okey[j] < SENTINEL:
            top_g[i] = flat_g[j]
            top_v[i] = flat_o[j]
            top_s[i] = flat_s[j]
            kept += 1
        cj = cells[j]
        for t in range(N):
            if cells[t] == cj:
                okey[t] = SENTINEL
    conv = np.minimum.accumulate(flat_s.reshape(G1, P).min(axis=1))
    return ParetoThin(top_genomes=top_g, top_vectors=top_v, top_scores=top_s,
                      n_kept=np.int32(kept), convergence=conv)


# -------------------------------------------------- adversarial objectives
def _adversarial_objs(rng: np.random.Generator, n: int) -> np.ndarray:
    """(n, 3) f32 objective vectors with the full pathology menu:
    duplicates, +/-0.0 ties, whole all-+inf infeasible rows, NaN rows."""
    o = rng.uniform(0.5, 4.0, size=(n, N_PARETO)).astype(np.float32)
    o[rng.random(n) < 0.3] = np.inf          # tied infeasible rows
    dup = rng.integers(0, n, size=n // 4)
    o[dup] = o[rng.integers(0, n, size=n // 4)]  # exact duplicates
    zero = rng.random((n, N_PARETO)) < 0.1
    o[zero] = np.float32(-0.0)               # -0.0 vs +0.0 ties
    o[zero & (rng.random((n, N_PARETO)) < 0.5)] = np.float32(0.0)
    o[rng.random(n) < 0.05] = np.nan         # NaN-guard rows
    return o


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("n", [1, 2, 7, 40])
def test_sort_keys_match_numpy_oracle(seed, n):
    """The batched non-dominated sort and folded-bit crowding are
    bit-identical to the numpy O(N^2) oracle under adversarial scores."""
    o = _adversarial_objs(np.random.default_rng(seed), n)
    rank = np.asarray(jax.jit(ga._dominance_rank)(jnp.asarray(o)))
    crowd = np.asarray(jax.jit(ga._crowding)(jnp.asarray(o)))
    jrank, jckey = (np.asarray(a) for a in
                    jax.jit(ga._crowded_order_keys)(jnp.asarray(o)))
    np.testing.assert_array_equal(rank, np_dominance_rank(o))
    # bitwise float comparison: view as int so -0.0 != 0.0 and NaN == NaN
    np.testing.assert_array_equal(crowd.view(np.int32),
                                  np_crowding(o).view(np.int32))
    nrank, nckey = np_crowded_order_keys(o)
    np.testing.assert_array_equal(jrank, nrank)
    np.testing.assert_array_equal(jckey, nckey)


def test_rank_semantics_small_case():
    """Hand-checkable front structure: rank 0 = the non-dominated set,
    dominated rows peel into later fronts, all-+inf rows land last."""
    o = np.array([
        [1.0, 4.0, 1.0],   # front 0 (best e)
        [4.0, 1.0, 1.0],   # front 0 (best l)
        [2.0, 2.0, 1.0],   # front 0 (trade-off)
        [2.0, 2.0, 2.0],   # dominated by row 2 -> front 1
        [5.0, 5.0, 5.0],   # dominated by everything finite -> front 2
        [np.inf] * 3,      # infeasible: dominated by all feasible rows
        [np.inf] * 3,      # ... and tied with its twin
    ], np.float32)
    rank = np.asarray(ga._dominance_rank(jnp.asarray(o)))
    assert rank.tolist() == [0, 0, 0, 1, 2, 3, 3]
    np.testing.assert_array_equal(rank, np_dominance_rank(o))


def test_crowding_boundaries_are_inf_interior_normalized():
    o = np.array([[1.0, 9.0], [5.0, 5.0], [9.0, 1.0]], np.float32)
    crowd = np.asarray(ga._crowding(jnp.asarray(o)))
    assert np.isinf(crowd[0]) and np.isinf(crowd[2])
    assert np.isfinite(crowd[1]) and crowd[1] > 0
    np.testing.assert_array_equal(crowd.view(np.int32),
                                  np_crowding(o).view(np.int32))


# --------------------------------------------------- ga-level front search
def _toy_eval(genomes, _ctx=None):
    """Deterministic (P, 3) objectives over real genomes: decoded-cell
    dependent (so duplicate cells collide exactly), with an infeasible
    band — everything the epilogue's dedup/masking must survive."""
    idx = space.decode_indices(genomes).astype(jnp.float32)
    e = 1.0 + idx[:, 0] + 2.0 * idx[:, 1]
    l = 1.0 + idx[:, 2] + 3.0 * idx[:, 3]
    a = 1.0 + idx[:, 4]
    feas = (idx[:, 5] > 0.0)
    objs = jnp.stack([e, l, a], axis=-1)
    return jnp.where(feas[:, None], objs, jnp.inf)


def _toy_run(history, top_k=K, B=3):
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    init = jax.vmap(lambda k: space.random_genomes(k, POP))(
        jax.random.split(jax.random.PRNGKey(1), B))
    return run_pareto_batched(
        keys, _toy_eval, pop_size=POP, generations=GENS,
        init_genomes=init, top_k=top_k, history=history)


def _assert_thin_equal(a: ParetoThin, b: ParetoThin):
    for f, g in zip(a, b):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(g))


def test_front_matches_numpy_oracle_over_evaluated_designs():
    """Acceptance: the returned k-member front is bit-identical to the
    numpy dominance oracle replayed over the SAME evaluated designs."""
    gh, oh, thin = _toy_run(history=True)
    for b in range(np.asarray(gh).shape[0]):
        oracle = np_pareto_epilogue(np.asarray(gh)[b], np.asarray(oh)[b], K)
        got = ParetoThin(*(np.asarray(f)[b] for f in thin))
        np.testing.assert_array_equal(got.top_genomes, oracle.top_genomes)
        np.testing.assert_array_equal(got.top_vectors, oracle.top_vectors)
        np.testing.assert_array_equal(got.top_scores, oracle.top_scores)
        assert int(got.n_kept) == int(oracle.n_kept)
        np.testing.assert_array_equal(got.convergence, oracle.convergence)
        # semantic spot-checks on the kept members.  Picks spill past the
        # first front when it has fewer unique cells than top_k, so the
        # invariant is rank-ORDERING, not mutual non-dominance.
        kept = int(got.n_kept)
        v = got.top_vectors[:kept]
        assert np.isfinite(v).all()
        hist_o = np.asarray(oh)[b].reshape(-1, N_PARETO)
        rank = np_dominance_rank(hist_o)
        pick_ranks = [int(rank[(hist_o == row).all(-1)].min()) for row in v]
        assert pick_ranks == sorted(pick_ranks), "picks must be rank-ordered"
        assert pick_ranks[0] == 0, "first pick must be non-dominated"
        cells = {tuple(r) for r in space.decode_indices_np(got.top_genomes[:kept])}
        assert len(cells) == kept, "front members must be cell-unique"


def test_fused_unfused_and_thin_history_parity():
    """Thin vs history-returning runs are bit-identical; the standalone
    batched epilogue over the returned history reproduces the fused-in
    thin outputs."""
    thin_f = _toy_run(history=False)
    gh, oh, thin_h = _toy_run(history=True)
    _assert_thin_equal(ParetoThin(*map(np.asarray, thin_f)),
                       ParetoThin(*map(np.asarray, thin_h)))
    standalone = pareto_epilogue_batched(np.asarray(gh), np.asarray(oh),
                                         top_k=K)
    _assert_thin_equal(ParetoThin(*map(np.asarray, thin_f)),
                       ParetoThin(*map(np.asarray, standalone)))


def test_large_k_covers_whole_first_front_before_spilling():
    """With top_k >= #evaluated designs the picks enumerate every unique
    feasible cell in crowded order: rank-0 cells first, then rank 1..."""
    gh, oh, thin = _toy_run(history=True, top_k=(GENS + 1) * POP, B=1)
    oh0 = np.asarray(oh)[0].reshape(-1, N_PARETO)
    rank = np_dominance_rank(oh0)
    kept = int(np.asarray(thin.n_kept)[0])
    v = np.asarray(thin.top_vectors)[0][:kept]
    # recover each pick's rank by matching its vector against the history
    pick_ranks = []
    for row in v:
        m = (oh0 == row).all(-1)
        pick_ranks.append(int(rank[m].min()))
    assert pick_ranks == sorted(pick_ranks), "picks must be rank-ordered"
    n_front0_cells = len({
        tuple(r) for r, rk, f in zip(
            space.decode_indices_np(np.asarray(gh)[0].reshape(-1, space.N_GENES)),
            rank, np.isfinite(oh0).all(-1)) if rk == 0 and f
    })
    assert pick_ranks.count(0) == n_front0_cells


# -------------------------------------------------------- engine end-to-end
def _pareto_reqs(ws, backend, n=3):
    return [
        SearchRequest(
            ws=ws.subset([i % ws.n, (i + 1) % ws.n]), objective=PARETO,
            backend=backend, pop_size=POP, generations=GENS,
            pareto_k=K, seed=i, area_constr=150.0 + 10.0 * (i % 2),
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("backend", ["table", "jnp"])
def test_engine_modes_bit_identical(ws, backend):
    """Sequential vs pipelined vs one-slot engines return the same front
    bits on both backends: slot packing moves no bit, and pipelined
    results are thin (ga=None) but carry identical vectors/designs."""
    reqs = _pareto_reqs(ws, backend)
    seq = SearchEngine().run(reqs)
    pipe = SearchEngine(pipelined=True).run(reqs)
    alone = SearchEngine(max_slots=1).run(reqs)
    for a, b, c in zip(seq, pipe, alone):
        assert a.objective == PARETO
        assert a.ga is not None and b.ga is None and c.ga is not None
        for other in (b, c):
            np.testing.assert_array_equal(a.top_genomes, other.top_genomes)
            np.testing.assert_array_equal(a.top_scores, other.top_scores)
            np.testing.assert_array_equal(a.objective_vectors,
                                          other.objective_vectors)
            np.testing.assert_array_equal(a.convergence, other.convergence)
            assert a.top_designs == other.top_designs
            assert a.valid == other.valid
        kept = len(a.top_scores)
        assert a.objective_vectors.shape == (kept, N_PARETO)
        assert kept <= K
        if a.valid:
            # the leading pick is non-dominated within the returned set
            # (later picks may spill into higher fronts when the first
            # front runs out of unique cells)
            v = a.objective_vectors
            dom0 = ((v <= v[0]).all(-1) & (v < v[0]).any(-1))
            assert not dom0.any()
        # the scalar proxy is the E*L*A product of the member's vector
        np.testing.assert_array_equal(
            a.top_scores,
            (a.objective_vectors[:, 0] * a.objective_vectors[:, 1])
            * a.objective_vectors[:, 2])


def test_pareto_plans_into_own_signature_group(ws):
    """Pareto requests never share a compiled program with scalar ones:
    plan_batch puts them in their own signature group."""
    reqs = [
        SearchRequest(ws=ws, objective="ela", backend="table",
                      pop_size=POP, generations=GENS),
        SearchRequest(ws=ws, objective=PARETO, backend="table",
                      pop_size=POP, generations=GENS),
    ]
    plans = plan_batch(reqs, max_slots=8)
    assert len(plans) == 2
    sigs = {p.signature for p in plans}
    assert len(sigs) == 2
    assert any(("pareto",) in s for s in sigs)


def test_signature_validation():
    ws1 = pack_workloads([(PAPER_WORKLOADS[0],
                           cnn_workload(PAPER_WORKLOADS[0]))])
    with pytest.raises(ValueError, match="obj_weights"):
        SearchRequest(ws=ws1, objective=PARETO,
                      obj_weights=(1.0, 1.0, 1.0)).signature()
    with pytest.raises(ValueError, match="pareto_k"):
        SearchRequest(ws=ws1, objective=PARETO, pareto_k=0).signature()
    with pytest.raises(ValueError, match="pareto"):
        SearchRequest(ws=ws1, objective="nope").signature()


def test_run_search_driver_and_pareto_k_slicing(ws):
    """The run_search driver threads pareto_k through; a smaller k is a
    prefix of a larger k's front (selection is prefix-stable)."""
    k1 = jax.random.PRNGKey(5)
    big = run_search(k1, ws, objective=PARETO, pop_size=POP,
                     generations=GENS, pareto_k=K, backend="table")
    small = run_search(k1, ws, objective=PARETO, pop_size=POP,
                       generations=GENS, pareto_k=2, backend="table")
    assert big.objective == PARETO and big.objective_vectors is not None
    np.testing.assert_array_equal(small.top_genomes,
                                  big.top_genomes[:len(small.top_scores)])
    np.testing.assert_array_equal(small.objective_vectors,
                                  big.objective_vectors[:len(small.top_scores)])


def test_pareto_result_cache_round_trip(ws, tmp_path):
    """Pareto results (thin and full) round-trip the result cache with
    objective_vectors intact, and pareto_k enters the request key."""
    from repro.serve.cache import ResultCache, request_key

    req = _pareto_reqs(ws, "table", n=1)[0]
    assert request_key(req) != request_key(
        dataclasses.replace(req, pareto_k=req.pareto_k + 1))
    cache = ResultCache(disk_dir=tmp_path)
    eng = SearchEngine(pipelined=True, result_cache=cache)
    first = eng.run([req])[0]
    launches = eng.launches
    again = eng.run([req])[0]
    assert eng.launches == launches
    # cold-process disk decode path
    fresh = ResultCache(disk_dir=tmp_path).get(req)
    for other in (again, fresh):
        assert other.objective == PARETO and other.ga is None
        np.testing.assert_array_equal(first.top_genomes, other.top_genomes)
        np.testing.assert_array_equal(first.objective_vectors,
                                      other.objective_vectors)
        np.testing.assert_array_equal(first.convergence, other.convergence)
        assert first.top_designs == other.top_designs


def test_pareto_scalar_matches_ela_bits(ws):
    """A pareto request's convergence curve is bit-identical to the same
    search run under the scalar 'ela' objective... is NOT required (the
    trajectories differ), but the scalar proxy of each returned vector
    must reproduce the ela formula bits: (E*L)*A in f32."""
    res = run_search(jax.random.PRNGKey(2), ws, objective=PARETO,
                     pop_size=POP, generations=GENS, pareto_k=K,
                     backend="table")
    v = jnp.asarray(res.objective_vectors)
    np.testing.assert_array_equal(np.asarray(pareto_scalar(v)),
                                  res.top_scores)


def test_pick_pareto_budget_sizes_masks_to_the_device():
    """The largest ladder rung whose two (N, N) epilogue masks fit half the
    device: P=1024, G=50 on a 16 GiB chip (G=100 needs 19.9 GiB); a device
    too small for the smallest rung is refused, not silently overrun."""
    assert ga.pareto_mask_bytes(256, 20) == 2 * (21 * 256) ** 2
    assert ga.pick_pareto_budget(16 * 2**30) == (1024, 50)
    smallest = ga.pareto_mask_bytes(*ga.PARETO_BUDGETS[0])
    assert ga.pick_pareto_budget(2 * smallest) == ga.PARETO_BUDGETS[0]
    with pytest.raises(ValueError):
        ga.pick_pareto_budget(2 * smallest - 1)
