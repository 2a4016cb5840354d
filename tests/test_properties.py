"""Property-based tests (hypothesis).

Kept in their own module so ``pytest.importorskip`` can skip them cleanly
when hypothesis isn't installed, while the deterministic parity tests in
test_imc_cost.py / test_paper_core.py always run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import space
from repro.core.distributed import batch_axes, batch_spec, shape_spec, spec_axes
from repro.core.ga import _poly_mutation, _sbx
from repro.imc.cost import DesignArrays, evaluate_designs
from repro.launch.mesh import (
    make_mesh,
    make_search_mesh,
    make_test_mesh,
    mesh_axis_sizes,
)
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.pack import pack_workloads


@pytest.fixture(scope="module")
def ws():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def _design(**kw):
    base = dict(rows=128.0, cols=128.0, c_per_tile=8.0, t_per_router=8.0,
                g_per_chip=8.0, v_op=0.9, bits_cell=2.0, t_cycle_ns=2.0,
                glb_mb=1.0)
    base.update(kw)
    return DesignArrays(**{k: jnp.asarray([v], jnp.float32) for k, v in base.items()})


@given(st.sampled_from([32.0, 64.0, 128.0, 256.0, 512.0]))
@settings(max_examples=5, deadline=None)
def test_more_capacity_never_hurts_fit(ws, rows):
    small = evaluate_designs(_design(rows=rows, c_per_tile=2.0), ws)
    big = evaluate_designs(_design(rows=rows, c_per_tile=32.0), ws)
    # strictly more crossbars on chip -> fits is monotone
    assert bool((big.fits | ~small.fits).all())


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_genome_roundtrip(seed):
    g = space.random_genomes(jax.random.PRNGKey(seed), 16)
    idx = space.decode_indices(g)
    g2 = space.genome_from_indices(np.asarray(idx))
    idx2 = space.decode_indices(jnp.asarray(g2, jnp.float32))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_sbx_bounds_and_mean(seed):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    p1 = jax.random.uniform(k1, (64, space.N_GENES))
    p2 = jax.random.uniform(k2, (64, space.N_GENES))
    c1, c2 = _sbx(k3, p1, p2, eta=3.0, prob=0.95)
    assert float(c1.min()) >= 0.0 and float(c1.max()) < 1.0
    assert float(c2.min()) >= 0.0 and float(c2.max()) < 1.0
    # SBX preserves the parent-pair mean wherever the [0,1) clip didn't bind
    c1n, c2n = np.asarray(c1), np.asarray(c2)
    interior = (c1n > 1e-6) & (c1n < 1 - 1e-6) & (c2n > 1e-6) & (c2n < 1 - 1e-6)
    np.testing.assert_allclose(
        (c1n + c2n)[interior], np.asarray(p1 + p2)[interior], atol=1e-4
    )


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_poly_mutation_in_bounds(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.uniform(key, (64, space.N_GENES))
    y = _poly_mutation(key, x, eta=3.0, prob=1.0)
    assert float(y.min()) >= 0.0 and float(y.max()) < 1.0


# ------------------------------------------------- factorized-table properties
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 3),  # workloads
    st.integers(1, 12),  # padded layer-table depth
    st.floats(0.0, 1.0),  # per-layer mask density
)
@settings(max_examples=15, deadline=None)
def test_table_backend_matches_dense_oracle(seed, w, l, density):
    """imc.tables: for ANY random design population and ragged / partially-
    or fully-masked workload set, the factorized table path reproduces the
    dense (P, W, L) oracle: metrics allclose, fits/valid identical, and
    identical objective scores (incl. the +inf infeasible pattern)."""
    from repro.core.objectives import make_objective
    from repro.imc.cost import evaluate_designs_arrays
    from repro.imc.tables import build_tables_arrays, evaluate_genomes_tables

    rng = np.random.default_rng(seed)
    feats = np.zeros((w, l, 6), np.float32)
    feats[..., 0] = rng.integers(1, 4096, (w, l))  # M
    feats[..., 1] = rng.integers(1, 8192, (w, l))  # K
    feats[..., 2] = rng.integers(1, 2048, (w, l))  # N
    feats[..., 3] = rng.integers(1, 1 << 22, (w, l))  # A_in
    feats[..., 4] = rng.integers(1, 1 << 22, (w, l))  # A_out
    feats[..., 5] = rng.integers(1, 512, (w, l))  # groups
    mask = rng.random((w, l)) < density
    feats, mask = jnp.asarray(feats), jnp.asarray(mask)

    g = space.random_genomes(jax.random.PRNGKey(seed), 64)
    ref = evaluate_designs_arrays(space.decode(g), feats, mask)
    tab = evaluate_genomes_tables(g, build_tables_arrays(feats, mask))

    np.testing.assert_allclose(tab.energy_pj, ref.energy_pj, rtol=1e-5)
    np.testing.assert_allclose(tab.latency_ns, ref.latency_ns, rtol=1e-5)
    np.testing.assert_allclose(tab.area_mm2, ref.area_mm2, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(tab.fits), np.asarray(ref.fits))
    np.testing.assert_array_equal(np.asarray(tab.valid), np.asarray(ref.valid))
    obj = make_objective("ela", 150.0)
    s_ref, s_tab = np.asarray(obj(ref)), np.asarray(obj(tab))
    np.testing.assert_array_equal(np.isfinite(s_ref), np.isfinite(s_tab))
    np.testing.assert_allclose(
        s_tab[np.isfinite(s_ref)], s_ref[np.isfinite(s_ref)], rtol=1e-5
    )


# ----------------------------------------------------- batch-plan properties
@given(
    st.lists(
        st.tuples(
            st.sampled_from([(0,), (1,), (0, 1), (0, 1, 2, 3)]),  # ws subset
            st.sampled_from([8, 16]),  # pop_size -> distinct signature
            st.sampled_from(["table", "jnp"]),
            st.integers(0, 5),  # priority
            st.one_of(st.none(), st.floats(0.0, 100.0)),  # deadline_s
        ),
        min_size=1, max_size=12,
    ),
    st.randoms(use_true_random=False),  # submit-order permutation
    st.sampled_from([2, 3, 64]),
    st.sampled_from(["fifo", "priority", "edf"]),
)
@settings(max_examples=40, deadline=None)
def test_plan_batch_is_a_policy_ordered_partition(
    ws, specs, rnd, max_slots, policy
):
    """For ANY request mix (signatures, priorities, deadlines) and ANY
    submit-order permutation, plan_batch's plan indices are an exact
    partition of the queue — every request in exactly one plan — and the
    emitted order respects the policy: members of a plan are urgency-
    sorted, plans launch most-urgent-first, and each signature group's
    chunk concatenation is urgency-sorted."""
    import dataclasses as dc

    from repro.core.engine import (
        RequestMeta,
        SearchRequest,
        get_policy,
        plan_batch,
    )

    reqs = [
        SearchRequest(ws=ws.subset(list(sub)), seed=i, backend=be,
                      pop_size=pop, generations=2, priority=pr,
                      deadline_s=dl)
        for i, (sub, pop, be, pr, dl) in enumerate(specs)
    ]
    rnd.shuffle(reqs)
    pol = get_policy(policy)
    keys = [
        pol.key(r, RequestMeta(seq=i, priority=r.priority,
                               deadline_s=r.deadline_s))
        for i, r in enumerate(reqs)
    ]
    plans = plan_batch(reqs, max_slots=max_slots, policy=policy)

    flat = [i for p in plans for i in p.indices]
    assert sorted(flat) == list(range(len(reqs)))  # exact partition
    for p in plans:
        assert 0 < len(p.requests) <= p.slots <= max_slots
        assert p.requests == [reqs[i] for i in p.indices]
        ks = [keys[i] for i in p.indices]
        assert ks == sorted(ks)  # within-plan members urgency-ordered
    firsts = [keys[p.indices[0]] for p in plans]
    assert firsts == sorted(firsts)  # most urgent plan launches first
    by_sig = {}
    for p in plans:
        by_sig.setdefault(p.signature, []).append(p)
    for chunks in by_sig.values():
        assert len({p.slots for p in chunks}) == 1  # one program per group
        cat = [keys[i] for p in chunks for i in p.indices]
        assert cat == sorted(cat)  # group order respects the policy
    # scheduling metadata never perturbs the signature partition
    stripped = [dc.replace(r, priority=0, deadline_s=None) for r in reqs]
    ref = plan_batch(stripped, max_slots=max_slots)
    assert sorted((p.signature, p.slots, len(p.requests)) for p in ref) == \
        sorted((p.signature, p.slots, len(p.requests)) for p in plans)


# -------------------------------------------------- sharding-helper properties
@given(st.integers(1, 8), st.integers(1, 16), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_genome_roundtrip_any_population_and_batch_shape(b, p, seed):
    """decode∘encode is the identity on grid indices for every (B, P)
    factorization of the population pool — the invariant the vmapped and
    sharded GA paths rely on."""
    g = space.random_genomes(jax.random.PRNGKey(seed), b * p)
    idx_flat = np.asarray(space.decode_indices(g))
    # batched view decodes identically to the flat view
    idx_b = jax.vmap(space.decode_indices)(g.reshape(b, p, space.N_GENES))
    np.testing.assert_array_equal(
        np.asarray(idx_b).reshape(b * p, space.N_GENES), idx_flat
    )
    # encode -> decode round-trips exactly
    g2 = space.genome_from_indices(idx_flat)
    idx2 = np.asarray(space.decode_indices(jnp.asarray(g2, jnp.float32)))
    np.testing.assert_array_equal(idx2, idx_flat)


def _check_mesh_layout(mesh):
    """Invariants every mesh layout the repo constructs must satisfy."""
    sizes = mesh_axis_sizes(mesh)
    assert tuple(sizes) == tuple(mesh.axis_names)
    assert all(v >= 1 for v in sizes.values())
    assert int(np.prod(list(sizes.values()))) == int(mesh.devices.size)
    assert int(mesh.devices.size) <= jax.device_count()
    s_ax, p_ax = batch_axes(mesh)
    assert set(s_ax).isdisjoint(set(p_ax))
    assert set(s_ax) | set(p_ax) <= set(mesh.axis_names)
    assert all(a == "search" for a in s_ax)
    assert all(a in ("pod", "data") for a in p_ax)
    # specs: dim 0 is the search group, pop_dim the pop group, rest None
    spec = _spec_groups(batch_spec(mesh, 3, pop_dim=1))
    assert len(spec) == 3 and spec[2] == ()
    assert spec[0] in (s_ax, ()) and spec[1] in (p_ax, ())
    # shape_spec only ever shards a dim its axis-group size divides
    shape = (7, 11, 9)
    for dim, names in enumerate(_spec_groups(shape_spec(mesh, shape, pop_dim=1))):
        group = int(np.prod([sizes[a] for a in names]))
        assert shape[dim] % group == 0


def _spec_groups(spec):
    """Every entry of a PartitionSpec as a tuple of axis names (() for a
    replicated dim) — one normalization for all the layout assertions."""
    return tuple(spec_axes(part) for part in spec)


@given(st.integers(1, 8), st.integers(1, 8), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_test_mesh_layout_invariants(search, data, model):
    mesh = make_test_mesh(data=data, model=model, search=search)
    _check_mesh_layout(mesh)
    sizes = mesh_axis_sizes(mesh)
    # clamped sizes never exceed the request
    assert sizes.get("search", 1) <= max(search, 1)
    assert sizes["data"] <= data and sizes["model"] <= model


@given(
    st.one_of(st.none(), st.integers(1, 16)),
    st.one_of(st.none(), st.integers(1, 16)),
)
@settings(max_examples=20, deadline=None)
def test_search_mesh_layout_invariants(searches, pop):
    mesh = make_search_mesh(searches, pop)
    _check_mesh_layout(mesh)
    assert tuple(mesh.axis_names) == ("search", "data")


@given(st.integers(1, 12), st.integers(1, 48), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_shape_spec_shards_only_divisible_dims(b, p, nd):
    mesh = make_search_mesh()
    shape = (b, p) + (space.N_GENES,) * (nd - 1) if nd > 1 else (b,)
    spec = shape_spec(mesh, shape, pop_dim=1 if len(shape) > 1 else None)
    sizes = mesh_axis_sizes(mesh)
    assert len(spec) == len(shape)
    for dim, names in enumerate(_spec_groups(spec)):
        group = int(np.prod([sizes[a] for a in names]))
        assert shape[dim] % group == 0


def test_plain_mesh_layout_invariants():
    """Non-hypothesis anchor: the exact layouts the drivers build."""
    _check_mesh_layout(make_test_mesh(1, 1))
    _check_mesh_layout(make_search_mesh(1, 1))
    _check_mesh_layout(make_mesh((1,), ("model",)))


# ---------------------------------------------------- segmented-GA properties
def _toy_obj(genomes):
    return jnp.sum((genomes - 0.3) ** 2, axis=-1)


@given(
    st.integers(0, 2**31 - 1),
    st.lists(st.integers(1, 4), min_size=1, max_size=4),  # segment split
)
@settings(max_examples=10, deadline=None)
def test_ga_random_segment_splits_bit_exact(seed, splits):
    """For ANY split of the generation budget into segment launches, the
    chained ``run_ga_segment`` history is bit-for-bit the single-shot
    ``run_ga`` history — the anytime/checkpoint contract at the GA level."""
    from repro.core.ga import init_ga_state, run_ga, run_ga_segment

    total = sum(splits)
    key = jax.random.PRNGKey(seed)
    init = space.random_genomes(jax.random.PRNGKey(seed ^ 0x5EED), 8)
    full = run_ga(key, _toy_obj, pop_size=8, generations=total,
                  init_genomes=init + 0)  # run_ga donates: pass a copy
    st = init_ga_state(key, _toy_obj, init)
    hg = [np.asarray(st.genomes)[None]]
    hs = [np.asarray(st.scores)[None]]
    for k in splits:
        st, (g, s) = run_ga_segment(st, _toy_obj, generations=k,
                                    total_generations=total)
        hg.append(np.asarray(g))
        hs.append(np.asarray(s))
    np.testing.assert_array_equal(np.concatenate(hg), np.asarray(full.genomes))
    np.testing.assert_array_equal(np.concatenate(hs), np.asarray(full.scores))


@given(
    st.integers(0, 2**31 - 1),
    st.sampled_from([1, 2, 4, 5]),  # segment size over a 6-generation budget
    st.sampled_from(["table", "jnp"]),
)
@settings(max_examples=8, deadline=None)
def test_engine_segmented_bit_parity_across_backends(ws, seed, seg, backend):
    """Segmented engine execution — any segment size, including ragged
    final segments — is bit-identical to the single-shot engine on every
    backend, and under the active (search, population) device mesh when
    the suite runs in the fake-8-device job."""
    from repro.core.engine import SearchEngine, SearchRequest

    req = SearchRequest(ws=ws.subset([seed % 4]), seed=seed, backend=backend,
                        pop_size=8, generations=6)
    mesh = make_search_mesh() if jax.device_count() > 1 else None
    ref = SearchEngine().run([req])[0]
    out = SearchEngine(segment_gens=seg, mesh=mesh).run([req])[0]
    np.testing.assert_array_equal(np.asarray(out.ga.scores),
                                  np.asarray(ref.ga.scores))
    np.testing.assert_array_equal(np.asarray(out.ga.genomes),
                                  np.asarray(ref.ga.genomes))
    np.testing.assert_array_equal(out.top_scores, ref.top_scores)
    np.testing.assert_array_equal(out.top_genomes, ref.top_genomes)
