"""Fused generation step, gen-step kernel, direct seeder, grid density.

The PR-8 fast path: ``core.ga`` fuses the survivor epilogue (one combined
``lax.sort``) and optionally the WHOLE generation into a single Pallas
kernel (``kernels.ga_gen_step``); the engine's ``direct_seed`` replaces
the rejection seeding rounds with an inverse-CDF sampler over the
feasible cells of the largest workload; ``space.configure_grid`` densifies
the hardware grid.  Everything here pins BIT-parity between the fast and
reference paths — the repo's invariant that a speedup must never change a
result bit (unless, like ``direct_seed``, it is explicitly opt-in).

NOTE on jit in the kernel-parity tests: both sides are compared as
COMPILED programs.  Eager op-by-op execution differs from any single
compiled program by 1 ULP on CPU (XLA contracts a*b+c into FMA when it
compiles the whole expression), so eager-vs-kernel is NOT the invariant —
jit-vs-kernel is, and ``run_ga`` always jits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ga, space
from repro.core.engine import (
    INDEXED,
    SearchEngine,
    SearchRequest,
    _ctx_eval,
    plan_batch,
)
from repro.core.search import batched_search, run_search, separate_search
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


# --------------------------------------------------- fused-vs-unfused parity
@pytest.mark.parametrize("backend", ["jnp", "table", "pallas"])
def test_fused_unfused_parity_all_backends(ws, backend):
    """The fused epilogue is a pure program-shape change: trajectories,
    top designs and scores are bit-identical on every backend."""
    key = jax.random.PRNGKey(11)
    a = run_search(key, ws, pop_size=16, generations=4, backend=backend,
                   fused=True)
    b = run_search(key, ws, pop_size=16, generations=4, backend=backend,
                   fused=False)
    _same_result(a, b)


@pytest.mark.parametrize("pop", [15, 17])
def test_fused_unfused_parity_odd_pop(ws, pop):
    key = jax.random.PRNGKey(5)
    a = run_search(key, ws, pop_size=pop, generations=3, fused=True)
    b = run_search(key, ws, pop_size=pop, generations=3, fused=False)
    _same_result(a, b)


def test_fused_unfused_parity_ragged_batch(ws):
    """Mixed workload subsets in one ragged batch: per-element parity."""
    subsets = [[0], [1, 2], [0, 1, 2, 3]]
    sets = [ws.subset(s) for s in subsets]
    W = max(s.n for s in sets)
    L = ws.feats.shape[1]
    B = len(sets)
    feats = np.zeros((B, W, L, 6), np.float32)
    mask = np.zeros((B, W, L), bool)
    for i, s in enumerate(sets):
        feats[i, : s.n] = np.asarray(s.feats)
        mask[i, : s.n] = np.asarray(s.mask)
    keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(B)])
    ra = batched_search(keys, feats, mask, pop_size=12, generations=3,
                        backend="table", fused=True)
    rb = batched_search(keys, feats, mask, pop_size=12, generations=3,
                        backend="table", fused=False)
    for a, b in zip(ra, rb):
        _same_result(a, b)


def test_fused_unfused_parity_segmented(ws):
    """Fused x segmented: the chained fused segments equal the single
    unfused launch bit-for-bit (and vice versa)."""
    key = jax.random.PRNGKey(23)
    kw = dict(pop_size=14, generations=6, backend="table")
    single = run_search(key, ws, fused=False, **kw)
    seg_fused = run_search(
        key, ws, engine=SearchEngine(segment_gens=2, fused=True), **kw)
    _same_result(single, seg_fused)


def test_separate_search_fused_parity(ws):
    key = jax.random.PRNGKey(3)
    ra = separate_search(key, ws, pop_size=12, generations=3,
                         backend="table", fused=True)
    rb = separate_search(key, ws, pop_size=12, generations=3,
                         backend="table", fused=False)
    for n in ws.names:
        _same_result(ra[n], rb[n])


# ------------------------------------------------------- gen-step kernel
def _table_eval_ctx(ws, P):
    tables = build_tables_arrays(ws.feats, ws.mask)
    eval_fn = _ctx_eval(INDEXED, 0.0, TECH, "table")
    ctx = (tables, jnp.int32(0), jnp.float32(1e9))
    return eval_fn, ctx


@pytest.mark.parametrize("pop", [8, 15, 16])
def test_kernel_gen_step_matches_lax(ws, pop):
    """One full fused-kernel generation == the lax gen step, compiled,
    for every output (survivors, scores, children, child scores)."""
    from repro.kernels.ga_gen_step import make_kernel_gen_step

    eval_fn, ctx = _table_eval_ctx(ws, pop)
    assert getattr(eval_fn, "gen_kernel_tech", None) is not None
    gen_lax = ga._make_gen_step(eval_fn, ctx, pop, space.N_GENES,
                                ga.SBX_PROB, ga.SBX_ETA, ga.MUT_ETA,
                                fused=True)
    kgen = make_kernel_gen_step(
        eval_fn, ctx, pop_size=pop, n_genes=space.N_GENES,
        sbx_prob=ga.SBX_PROB, sbx_eta=ga.SBX_ETA, mut_eta=ga.MUT_ETA)
    assert kgen is not None

    popg = space.random_genomes(jax.random.PRNGKey(7), pop)
    scores = eval_fn(popg, ctx)
    k = jax.random.fold_in(jax.random.PRNGKey(3), 0)
    (p1, s1), (c1, cs1) = jax.jit(gen_lax)((popg, scores), k)
    (p2, s2), (c2, cs2) = jax.jit(kgen)((popg, scores), k)
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert np.array_equal(np.asarray(cs1), np.asarray(cs2))


def test_kernel_gen_step_chained_generations(ws):
    """Several chained kernel generations track the lax trajectory."""
    from repro.kernels.ga_gen_step import make_kernel_gen_step

    P = 12
    eval_fn, ctx = _table_eval_ctx(ws, P)
    gen_lax = jax.jit(ga._make_gen_step(
        eval_fn, ctx, P, space.N_GENES, ga.SBX_PROB, ga.SBX_ETA,
        ga.MUT_ETA, fused=True))
    kgen = jax.jit(make_kernel_gen_step(
        eval_fn, ctx, pop_size=P, n_genes=space.N_GENES,
        sbx_prob=ga.SBX_PROB, sbx_eta=ga.SBX_ETA, mut_eta=ga.MUT_ETA))
    popg = space.random_genomes(jax.random.PRNGKey(1), P)
    ca = (popg, eval_fn(popg, ctx))
    cb = ca
    for g in range(4):
        k = jax.random.fold_in(jax.random.PRNGKey(9), g)
        ca, _ = gen_lax(ca, k)
        cb, _ = kgen(cb, k)
    assert np.array_equal(np.asarray(ca[0]), np.asarray(cb[0]))
    assert np.array_equal(np.asarray(ca[1]), np.asarray(cb[1]))


def test_kernel_hook_requires_table_eval():
    """The kernel factory declines eval callbacks without a table ctx —
    dense/jnp backends keep the lax gen step."""
    from repro.kernels.ga_gen_step import make_kernel_gen_step

    plain = lambda g, ctx: jnp.zeros(g.shape[0])  # noqa: E731
    assert make_kernel_gen_step(plain, (None,), pop_size=8,
                                n_genes=space.N_GENES, sbx_prob=0.9,
                                sbx_eta=3.0, mut_eta=3.0) is None


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


def test_dense_grid_fused_unfused_parity(ws):
    try:
        space.configure_grid(2)
        key = jax.random.PRNGKey(77)
        a = run_search(key, ws, pop_size=12, generations=2,
                       backend="table", fused=True)
        b = run_search(key, ws, pop_size=12, generations=2,
                       backend="table", fused=False)
        _same_result(a, b)
    finally:
        space.configure_grid(1)


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
