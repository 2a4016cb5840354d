"""Sharded (2-D search x population mesh) search stack == unsharded stack.

The ``@pytest.mark.multidevice`` tests need >=2 devices — run them with
``REPRO_FAKE_DEVICES=8 python -m pytest tests/test_search_sharded.py`` (or
the XLA flag directly; see tests/conftest.py).  Parity is asserted
BIT-IDENTICAL (``assert_array_equal``): sharding is a layout, never a
numerics change.  The unmarked tests cover graceful degradation on a
single-device host, so they also run in the tier-1 suite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import space
from repro.core.distributed import (
    batch_axes,
    batch_spec,
    place_batched,
    pop_axes,
    shape_spec,
    sharded_batched_eval_fn,
    sharded_batched_search,
    sharded_eval_fn,
    sharded_run_ga_batched,
    sharded_separate_search,
    sharded_seed_population_batched,
    spec_axes,
)
from repro.core.search import (
    _ctx_eval,
    batched_search,
    make_eval_fn,
    seed_population_batched,
    separate_search,
)
from repro.imc.tech import TECH
from repro.launch.mesh import (
    describe,
    make_mesh,
    make_search_mesh,
    make_test_mesh,
    mesh_axis_sizes,
)
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.pack import pack_workloads

POP, GENS = 16, 3
MESH_LAYOUTS = [(2, 4), (4, 2), (8, 1)]


@pytest.fixture(scope="module")
def ws():
    # 4 CNN workloads with different layer counts -> ragged (W, L) masks
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def _assert_results_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.ga.scores), np.asarray(b.ga.scores))
    np.testing.assert_array_equal(
        np.asarray(a.ga.best_genome), np.asarray(b.ga.best_genome)
    )
    np.testing.assert_array_equal(a.top_scores, b.top_scores)
    np.testing.assert_array_equal(a.top_genomes, b.top_genomes)


# ------------------------------------------------------------ parity (>=2 dev)
@pytest.mark.multidevice
@pytest.mark.parametrize("searches,pop", MESH_LAYOUTS)
def test_batched_search_sharded_parity(ws, searches, pop):
    mesh = make_search_mesh(searches, pop)
    assert mesh_axis_sizes(mesh) == {"search": searches, "data": pop}
    B = 8
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    ref = batched_search(keys, feats, mask, pop_size=POP, generations=GENS)
    sh = batched_search(keys, feats, mask, pop_size=POP, generations=GENS,
                        mesh=mesh)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


@pytest.mark.multidevice
def test_batched_search_sharded_parity_odd_pop_and_ragged_batch(ws):
    """Odd population (15) and B (6) not divisible by the search axis: the
    ragged dimensions replicate instead of sharding, scores unchanged."""
    mesh = make_search_mesh(2, 4)
    B = 6
    keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    ref = batched_search(keys, feats, mask, pop_size=15, generations=GENS)
    sh = batched_search(keys, feats, mask, pop_size=15, generations=GENS,
                        mesh=mesh)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


@pytest.mark.multidevice
def test_batched_search_sharded_parity_mixed_workload_sets(ws):
    """W>1 ragged-mask sets that DIFFER per batch element (reversed order
    flips which rows are padding)."""
    mesh = make_search_mesh(4, 2)
    rev_feats, rev_mask = ws.feats[::-1], ws.mask[::-1]
    feats = jnp.stack([ws.feats, rev_feats] * 4)  # (8, W, L, 6)
    mask = jnp.stack([ws.mask, rev_mask] * 4)
    keys = jnp.stack([jax.random.PRNGKey(200 + i) for i in range(8)])
    ref = batched_search(keys, feats, mask, pop_size=POP, generations=GENS)
    sh = sharded_batched_search(mesh, keys, feats, mask, pop_size=POP,
                                generations=GENS)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


@pytest.mark.multidevice
@pytest.mark.parametrize("searches,pop", MESH_LAYOUTS)
def test_batched_search_sharded_parity_table_backend(ws, searches, pop):
    """The factorized-table ctx (imc.tables.WorkloadTables leaves) shards
    over the search axis like any other batched leaf — bit-identical to
    the unsharded table path.  (4, 2) joined the envelope when the
    total-order survival sort landed; see
    test_table_backend_sharded_parity_envelope for the history."""
    mesh = make_search_mesh(searches, pop)
    B = 8
    keys = jnp.stack([jax.random.PRNGKey(300 + i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    ref = batched_search(keys, feats, mask, pop_size=POP, generations=GENS,
                         backend="table")
    sh = batched_search(keys, feats, mask, pop_size=POP, generations=GENS,
                        backend="table", mesh=mesh)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


@pytest.mark.multidevice
@pytest.mark.parametrize("searches,pop", MESH_LAYOUTS)
def test_table_backend_sharded_parity_envelope(ws, searches, pop):
    """Characterization: the table-backend sharded bit-parity envelope.

    History: PR 4's ROADMAP note pinned the envelope at (2,4)/(8,1) and
    documented that a (4,2) mesh with a ragged batch ULP-drifted the
    table eval on the then-current stack (static objective + plain
    argsort survival).  On the CURRENT stack — total-order-key survival
    sort (``ga._survivor_indices``) everywhere — that drift no longer
    reproduces: a 60-config sweep over (4,2) x {ragged B=6/7, odd pop,
    per-element mixed workload sets} x 20 seeds is bit-exact.  A
    strict-xfail on the old drift would therefore XPASS; the truthful
    pin is the WIDE envelope, asserted bit-identical on all three
    layouts at the adversarial shape (ragged B=6, odd pop=15,
    per-element differing ragged-mask sets).  If the drift ever comes
    back — an XLA upgrade re-fusing the table gathers, a survival-sort
    change — this fails loudly, and narrowing the envelope again must
    be a deliberate, documented decision."""
    mesh = make_search_mesh(searches, pop)
    B, P = 6, 15  # B ragged on every layout's search axis; odd population
    rev_feats, rev_mask = ws.feats[::-1], ws.mask[::-1]
    feats = jnp.stack([ws.feats if i % 2 == 0 else rev_feats for i in range(B)])
    mask = jnp.stack([ws.mask if i % 2 == 0 else rev_mask for i in range(B)])
    keys = jnp.stack([jax.random.PRNGKey(700 + i) for i in range(B)])
    ref = batched_search(keys, feats, mask, pop_size=P, generations=GENS,
                         backend="table")
    sh = batched_search(keys, feats, mask, pop_size=P, generations=GENS,
                        backend="table", mesh=mesh)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


@pytest.mark.multidevice
@pytest.mark.parametrize("searches,pop", [(4, 2), (2, 4)])
def test_separate_search_sharded_parity(ws, searches, pop):
    mesh = make_search_mesh(searches, pop)
    ref = separate_search(jax.random.PRNGKey(0), ws, pop_size=POP,
                          generations=GENS)
    sh = sharded_separate_search(mesh, jax.random.PRNGKey(0), ws,
                                 pop_size=POP, generations=GENS)
    assert set(ref) == set(sh)
    for name in ws.names:
        _assert_results_equal(ref[name], sh[name])


@pytest.mark.multidevice
def test_seed_population_batched_sharded_parity(ws):
    mesh = make_search_mesh(2, 4)
    B = 4
    keys = jnp.stack([jax.random.PRNGKey(10 + i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    ref = seed_population_batched(keys, feats, mask, 8)
    sh = sharded_seed_population_batched(mesh, keys, feats, mask, 8)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(sh))


@pytest.mark.multidevice
def test_sharded_run_ga_outputs_live_on_the_mesh(ws):
    """The layout proof: committed inputs propagate through the cached GA
    program and the results come back sharded over every mesh device."""
    mesh = make_search_mesh(4, 2)
    B = 8
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    init = seed_population_batched(keys, feats, mask, POP, mesh=mesh)
    ga = sharded_run_ga_batched(
        mesh, keys, _ctx_eval("ela", 150.0, TECH, "jnp"),
        pop_size=POP, generations=GENS, init_genomes=init, ctx=(feats, mask),
    )
    assert len(ga.scores.sharding.device_set) == len(mesh.devices.ravel())
    assert ga.scores.shape == (B, GENS + 1, POP)


@pytest.mark.multidevice
def test_place_batched_layout(ws):
    mesh = make_search_mesh(4, 2)
    x = place_batched(mesh, jnp.zeros((8, 16, 9)), pop_dim=1)
    assert x.sharding.spec == jax.sharding.PartitionSpec(
        ("search",), ("data",), None
    )
    assert len(x.sharding.device_set) == 8
    # ragged dims degrade to replication rather than erroring
    y = place_batched(mesh, jnp.zeros((6, 15, 9)), pop_dim=1)
    assert y.sharding.spec == jax.sharding.PartitionSpec(None, None, None)


@pytest.mark.multidevice
def test_sharded_batched_eval_fn_parity(ws):
    mesh = make_search_mesh(2, 4)
    B = 4
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    genomes = jax.vmap(lambda k: space.random_genomes(k, POP))(keys)
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    ev = sharded_batched_eval_fn(mesh, "ela", 150.0)
    base = _ctx_eval("ela", 150.0, TECH, "jnp")
    ref = jax.vmap(lambda g: base(g, (ws.feats, ws.mask)))(genomes)
    np.testing.assert_array_equal(
        np.asarray(ev(genomes, (feats, mask))), np.asarray(ref)
    )


# ----------------------------------------------- degradation (any device count)
def test_make_test_mesh_accepts_search_axis():
    mesh = make_test_mesh(data=2, model=1, search=8)
    sizes = mesh_axis_sizes(mesh)
    assert tuple(sizes) == ("search", "data", "model")
    # degrades down to all-1 axes on a single-device host, never raises
    assert all(s >= 1 for s in sizes.values())
    n = jax.device_count()
    assert int(np.prod(list(sizes.values()))) <= n
    assert sizes["search"] <= 8 and sizes["data"] <= 2 and sizes["model"] == 1
    # historical 2-axis layout is preserved when no search axis is requested
    assert tuple(mesh_axis_sizes(make_test_mesh(1, 1))) == ("data", "model")


def test_make_search_mesh_defaults_and_clamping():
    mesh = make_search_mesh()
    sizes = mesh_axis_sizes(mesh)
    assert tuple(sizes) == ("search", "data")
    assert sizes["search"] == jax.device_count() and sizes["data"] == 1
    assert describe(mesh) == f"search={sizes['search']}xdata=1"
    # oversubscribed requests clamp instead of asserting
    big = make_search_mesh(3 * jax.device_count(), 5)
    bs = mesh_axis_sizes(big)
    assert bs["search"] * bs["data"] <= jax.device_count()


def test_sharded_eval_fn_tolerates_meshes_without_data_axis(ws):
    g = space.random_genomes(jax.random.PRNGKey(0), 32)
    ref = np.asarray(make_eval_fn(ws, "ela", 150.0)(g))
    for axes in [("model",), ("search",)]:
        mesh = make_mesh((1,), axes)
        assert pop_axes(mesh) == ()
        f = sharded_eval_fn(mesh, ws, "ela", 150.0)
        np.testing.assert_array_equal(np.asarray(f(g)), ref)


def test_sharded_eval_fn_odd_population_replicates(ws):
    mesh = make_search_mesh(1, jax.device_count())
    f = sharded_eval_fn(mesh, ws, "ela", 150.0)
    g = space.random_genomes(jax.random.PRNGKey(1), 17)  # prime: never divides
    ref = np.asarray(make_eval_fn(ws, "ela", 150.0)(g))
    np.testing.assert_array_equal(np.asarray(f(g)), ref)


def test_batch_axes_and_specs_degrade():
    m2 = make_test_mesh(1, 1)  # no search axis: batch dim replicates
    assert batch_axes(m2) == ((), ("data",))
    assert batch_spec(m2, 3, pop_dim=1) == jax.sharding.PartitionSpec(
        None, ("data",), None
    )
    m3 = make_mesh((1,), ("model",))  # neither group present
    assert batch_axes(m3) == ((), ())
    assert batch_spec(m3, 2, pop_dim=1) == jax.sharding.PartitionSpec(None, None)
    sm = make_search_mesh(1, 1)
    s_ax, p_ax = batch_axes(sm)
    assert s_ax == ("search",) and p_ax == ("data",)
    # shape_spec never shards a ragged dim
    assert spec_axes(shape_spec(sm, (7, 13, 9), pop_dim=1)[0]) in (("search",), ())


def test_batched_search_with_trivial_mesh_parity(ws):
    """mesh= plumbing must be a no-op numerically even at 1 device."""
    mesh = make_search_mesh(1, 1)
    B = 2
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    ref = batched_search(keys, feats, mask, pop_size=8, generations=2)
    sh = batched_search(keys, feats, mask, pop_size=8, generations=2, mesh=mesh)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


def test_separate_search_mesh_requires_batched(ws):
    with pytest.raises(ValueError, match="batched"):
        separate_search(jax.random.PRNGKey(0), ws, batched=False,
                        mesh=make_search_mesh(1, 1), pop_size=8, generations=1)


# ------------------------------------------------------- table fast path
@pytest.mark.multidevice
@pytest.mark.parametrize("searches,pop", MESH_LAYOUTS)
def test_batched_search_sharded_fused_parity(ws, searches, pop):
    """Table backend x sharded with per-element workload sets that differ
    (reversed order flips which rows are padding) under every mesh
    layout: bit-identical to the unsharded table run."""
    mesh = make_search_mesh(searches, pop)
    feats = jnp.stack([ws.feats, ws.feats[::-1]] * 4)  # (8, W, L, 6)
    mask = jnp.stack([ws.mask, ws.mask[::-1]] * 4)
    keys = jnp.stack([jax.random.PRNGKey(900 + i) for i in range(8)])
    ref = batched_search(keys, feats, mask, pop_size=POP, generations=GENS,
                         backend="table")
    sh = batched_search(keys, feats, mask, pop_size=POP, generations=GENS,
                        backend="table", mesh=mesh)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


@pytest.mark.multidevice
def test_sharded_direct_seed_parity(ws):
    """The direct table seeder's precomputed CDF is just another placed
    leaf: sharded direct-seed == unsharded direct-seed, bit-identical."""
    from repro.core.engine import SearchEngine

    mesh = make_search_mesh(2, 4)
    B = 8
    keys = jnp.stack([jax.random.PRNGKey(700 + i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    kw = dict(pop_size=POP, generations=GENS, backend="table")
    ref = batched_search(keys, feats, mask,
                         engine=SearchEngine(direct_seed=True), **kw)
    sh = batched_search(keys, feats, mask,
                        engine=SearchEngine(direct_seed=True, mesh=mesh),
                        **kw)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


def test_fused_trivial_mesh_parity(ws):
    """Single-device envelope of the table x mesh cross (tier-1)."""
    mesh = make_search_mesh(1, 1)
    B = 2
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    ref = batched_search(keys, feats, mask, pop_size=8, generations=2,
                         backend="table")
    sh = batched_search(keys, feats, mask, pop_size=8, generations=2,
                        backend="table", mesh=mesh)
    for r, s in zip(ref, sh):
        _assert_results_equal(r, s)


# ------------------------------------------------------ pareto front search
def _assert_pareto_equal(a, b):
    """Pareto results: front membership, (E, L, A) vectors and the
    convergence curve must all be mesh-invariant bit-for-bit."""
    np.testing.assert_array_equal(a.top_scores, b.top_scores)
    np.testing.assert_array_equal(a.top_genomes, b.top_genomes)
    np.testing.assert_array_equal(a.objective_vectors, b.objective_vectors)
    np.testing.assert_array_equal(a.convergence, b.convergence)
    assert a.top_designs == b.top_designs


@pytest.mark.multidevice
@pytest.mark.parametrize("searches,pop", MESH_LAYOUTS)
def test_pareto_search_sharded_parity(ws, searches, pop):
    """NSGA-II front search over the fake-8-device mesh: the in-jit
    non-dominated sort, crowding passes and front epilogue are all plain
    lax ops over placed leaves, so every mesh layout must return the
    meshless front bit-for-bit (table backend, mixed per-element areas)."""
    mesh = make_search_mesh(searches, pop)
    B = 8
    keys = jnp.stack([jax.random.PRNGKey(500 + i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    kw = dict(pop_size=POP, generations=GENS, backend="table",
              objective="pareto", pareto_k=5)
    ref = batched_search(keys, feats, mask, **kw)
    sh = batched_search(keys, feats, mask, mesh=mesh, **kw)
    for r, s in zip(ref, sh):
        _assert_pareto_equal(r, s)


def test_pareto_trivial_mesh_parity(ws):
    """Single-device envelope of the pareto x mesh cross (tier-1)."""
    mesh = make_search_mesh(1, 1)
    B = 2
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    kw = dict(pop_size=8, generations=2, backend="table",
              objective="pareto", pareto_k=4)
    ref = batched_search(keys, feats, mask, **kw)
    sh = batched_search(keys, feats, mask, mesh=mesh, **kw)
    for r, s in zip(ref, sh):
        _assert_pareto_equal(r, s)
