"""Fused generation step, direct seeder, grid density.

``core.ga`` runs survival as one combined ``lax.sort`` per generation:
the (mu + lambda) step (``_elitist_survival``) carries the scores through
the sort of the folded score keys, and the NSGA-II step
(``_crowded_survival``) carries the objective columns through the sort
of the crowded-order keys.  Both are pinned here against their gather
references under adversarial ties.  The engine's ``direct_seed``
replaces the rejection seeding rounds with an inverse-CDF sampler over
the feasible cells of the largest workload; ``space.configure_grid``
densifies the hardware grid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ga, space
from repro.core.engine import SearchEngine, SearchRequest, plan_batch
from repro.core.search import run_search
from repro.imc.tables import build_tables_arrays, evaluate_genomes_tables, table_bytes
from repro.imc.tech import TECH
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.pack import pack_workloads


@pytest.fixture(scope="module")
def ws():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def _same_result(a, b):
    assert np.array_equal(np.asarray(a.ga.genomes), np.asarray(b.ga.genomes))
    assert np.array_equal(np.asarray(a.ga.scores), np.asarray(b.ga.scores))
    assert np.array_equal(np.asarray(a.top_scores), np.asarray(b.top_scores))
    assert np.array_equal(np.asarray(a.top_genomes), np.asarray(b.top_genomes))
    assert float(a.ga.best_score) == float(b.ga.best_score)


# ------------------------------------------------------------ survival
def _adversarial_scores(pattern: str, n: int, seed: int) -> np.ndarray:
    """Scores whose order a float comparator gets wrong or leaves to
    stability: repeated values with +inf infeasibles, mixed zero signs,
    and large-magnitude negatives (sign-folded keys near the int32
    extremes)."""
    rng = np.random.default_rng(seed)
    if pattern == "ties_inf":
        s = rng.choice([0.5, 1.0, 2.0], n).astype(np.float32)
        s[rng.random(n) < 0.4] = np.inf
    elif pattern == "signed_zero":
        s = rng.choice([-0.0, 0.0, -1.5, 1.5], n).astype(np.float32)
    else:  # big_negative
        s = (-rng.random(n) * 3.0e38).astype(np.float32)
        s[::3] = s[0]
        s[1::5] = -np.float32(np.finfo(np.float32).max)
    return s


@pytest.mark.parametrize("pattern", ["ties_inf", "signed_zero", "big_negative"])
@pytest.mark.parametrize("pop", [8, 15, 16, 17])
def test_elitist_survival_matches_reference(pop, pattern):
    """The combined-sort survival keeps exactly the designs and score
    bits of a gather by ``_survivor_indices`` (the stable argsort)."""
    alls = _adversarial_scores(pattern, 2 * pop, seed=pop)
    allg = np.random.default_rng(pop + 1).random(
        (2 * pop, space.N_GENES)).astype(np.float32)
    got_g, got_s = jax.jit(ga._elitist_survival)(
        allg[:pop], alls[:pop], allg[pop:], alls[pop:])
    order = np.asarray(ga._survivor_indices(jnp.asarray(alls), pop))
    np.testing.assert_array_equal(order,
                                  np.argsort(alls, kind="stable")[:pop])
    np.testing.assert_array_equal(np.asarray(got_g), allg[order])
    # bitwise: the carried scores keep their zero signs
    np.testing.assert_array_equal(np.asarray(got_s).view(np.int32),
                                  alls[order].view(np.int32))


@pytest.mark.parametrize("m", [2, 3])
def test_crowded_survival_matches_gather_reference(m):
    """The objective columns carried through the NSGA-II survival sort
    equal a gather by its index, with rank ties (duplicate vectors, tied
    all-+inf infeasibles) and crowding ties (equal boundary distances)."""
    P = 12
    rng = np.random.default_rng(m)
    allo = rng.integers(0, 3, (2 * P, m)).astype(np.float32)
    allo[rng.random(2 * P) < 0.25] = np.inf
    allg = rng.random((2 * P, space.N_GENES)).astype(np.float32)
    got_g, got_o = jax.jit(ga._crowded_survival)(
        allg[:P], allo[:P], allg[P:], allo[P:])
    rank, ckey = map(np.asarray,
                     jax.jit(ga._crowded_order_keys)(jnp.asarray(allo)))
    assert len(np.unique(rank)) < 2 * P and len(np.unique(ckey)) < 2 * P
    idx = np.lexsort((np.arange(2 * P), ckey, rank))[:P]
    np.testing.assert_array_equal(np.asarray(got_g), allg[idx])
    np.testing.assert_array_equal(np.asarray(got_o), allo[idx])


# -------------------------------------------------------- direct seeder
def test_direct_seed_designs_fit_largest_workload(ws):
    """Every directly-seeded genome fits the largest workload and is
    V/f-valid — by construction, not by rejection."""
    from repro.core.engine import _seed_direct_batched_jit

    eng = SearchEngine(direct_seed=True)
    req = SearchRequest(ws=ws, objective="ela", area_constr=1e9,
                        key=jax.random.PRNGKey(0), backend="table",
                        pop_size=64, generations=1, top_k=4, tech=TECH)
    cdf = eng._request_seed_cdf(req)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(3)])
    pools, seeded = _seed_direct_batched_jit(
        keys, jnp.asarray(np.stack([cdf] * 3)), pop_size=64, tech=TECH)
    counts, rounds = np.asarray(seeded)
    assert np.all(counts == 64) and np.all(rounds == 1)
    tables = build_tables_arrays(ws.feats, ws.mask)
    from repro.core.engine import largest_workload_index

    li = largest_workload_index(ws)
    for b in range(3):
        r = evaluate_genomes_tables(pools[b], tables)
        assert bool(np.asarray(r.fits)[:, li].all())
        assert bool(np.asarray(r.valid).all())


def test_direct_seed_engine_results_valid_and_deterministic(ws):
    kw = dict(pop_size=16, generations=3, backend="table")
    key = jax.random.PRNGKey(42)
    a = run_search(key, ws, engine=SearchEngine(direct_seed=True), **kw)
    b = run_search(key, ws, engine=SearchEngine(direct_seed=True), **kw)
    assert a.valid
    _same_result(a, b)


def test_direct_seed_is_opt_in(ws):
    """The default engine keeps the rejection seeder: direct_seed=False
    must reproduce the plain run_search bits exactly."""
    key = jax.random.PRNGKey(8)
    kw = dict(pop_size=12, generations=2, backend="table")
    a = run_search(key, ws, **kw)
    b = run_search(key, ws, engine=SearchEngine(direct_seed=False), **kw)
    _same_result(a, b)


# --------------------------------------------------------- grid density
def test_configure_grid_densify_and_restore(ws):
    """Densifying multiplies the axis sizes, changes the grid token (so
    every content cache misses), keeps the endpoints, and a search still
    runs end-to-end; restoring brings the exact baseline back."""
    base_sizes = {f: len(space.SPACE[f]) for f in space.FIELDS}
    base_token = space.grid_token()
    base_bytes = table_bytes(ws.tables())
    try:
        space.configure_grid(2)
        assert space.grid_token() != base_token
        for f in space.FIELDS:
            # exact axes (bits_cell: integral by definition) keep their
            # points; every refinable axis gains interior ones
            if space._REFINE_KIND[f] == "exact":
                assert len(space.SPACE[f]) == base_sizes[f]
            else:
                assert len(space.SPACE[f]) > base_sizes[f]
            assert space.SPACE[f][0] == pytest.approx(
                np.asarray(space._BASE_SPACE[f][0]))
        assert table_bytes(ws.tables()) > base_bytes
        # generous area: this pins end-to-end execution on the dense
        # grid, not feasibility statistics at a tiny search budget
        res = run_search(jax.random.PRNGKey(1), ws, pop_size=12,
                         generations=2, backend="table", area_constr=1e3)
        assert res.valid
        # decoded indices stay in range on the dense grid
        idx = space.decode_indices_np(np.asarray(res.ga.genomes[-1]))
        for j, f in enumerate(space.FIELDS):
            assert idx[:, j].max() < len(space.SPACE[f])
    finally:
        space.configure_grid(1)
    assert space.grid_token() == base_token
    assert {f: len(space.SPACE[f]) for f in space.FIELDS} == base_sizes


# ------------------------------------------------------ batched finalize
def test_finalize_batch_matches_finalize(ws):
    """The batched numpy finalize epilogue == the per-request reference
    on every field (single-shot engine path vs segmented path helper)."""
    from repro.core.engine import _finalize, _finalize_batch, _objective_label
    from repro.core.ga import GAResult

    reqs = [
        SearchRequest(ws=ws.subset([i % ws.n]), objective="ela",
                      area_constr=150.0, key=jax.random.PRNGKey(i),
                      backend="table", pop_size=10, generations=2,
                      top_k=5, tech=TECH)
        for i in range(3)
    ]
    plans = plan_batch(reqs, max_slots=8)
    assert len(plans) == 1
    eng = SearchEngine()
    results = eng.execute(plans[0])  # runs _finalize_batch internally
    # reference: per-request _finalize over the same GA arrays
    for req, res in zip(plans[0].requests, results):
        ga_i = GAResult(
            genomes=np.asarray(res.ga.genomes),
            scores=np.asarray(res.ga.scores),
            best_genome=np.asarray(res.ga.best_genome),
            best_score=np.asarray(res.ga.best_score),
        )
        ref = _finalize(ga_i, req.ws.names, _objective_label(req), req.top_k)
        assert np.array_equal(res.top_scores, ref.top_scores)
        assert np.array_equal(res.top_genomes, ref.top_genomes)
        assert res.top_designs == ref.top_designs
        assert np.array_equal(res.convergence, ref.convergence)
        assert res.valid == ref.valid
