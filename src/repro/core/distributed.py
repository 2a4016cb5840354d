"""Pod-scale design-space exploration: searches AND populations on the mesh.

The paper calls out "runtime efficiency limitations and slow optimization
speed" as an open challenge (4 h for P=40 x G=10 on 64 CPU cores, ~36 s per
design, simulator-bound).  Here the evaluator is a tensor program, so the
whole batched search stack lays out over a 2-D ``(search, population)``
mesh (``launch.mesh.make_search_mesh``):

  * the leading batch axis of ``core.ga.run_ga_batched`` (independent GAs:
    seeds, workload sets, objective weights) shards over the ``search``
    mesh axis — a fleet runs hundreds of independent searches per launch;
  * each GA's population axis shards over the ``pod``/``data`` axes — a pod
    evaluates hundreds of thousands of designs per second; the GA's
    select/survive step needs only the (P,) score vector.

Two kinds of entry points:

  * ``sharded_eval_fn`` / ``sharded_batched_eval_fn`` — drop-in evaluation
    callbacks whose population (and batch) axes carry explicit
    ``with_sharding_constraint`` annotations; used by the dry-run launcher
    (launch/dryrun.py --paper) and standalone rescoring.
  * ``sharded_run_ga_batched`` / ``sharded_batched_search`` /
    ``sharded_separate_search`` / ``sharded_seed_population_batched`` —
    the batched drivers with their inputs committed to ``NamedSharding``
    placements (``place_batched``): batch axis pinned to ``search``,
    population axis to ``pod``/``data``.  The eval callbacks already take
    workload tensors as traced ``ctx`` arguments, so this is placement +
    GSPMD propagation — the cached one-jit GA programs are reused, not
    retraced, and per-element results are bit-identical to the unsharded
    path (asserted in tests/test_search_sharded.py on a fake 8-device
    host).

Meshes without a ``search`` (or without a ``data``/``pod``) axis degrade to
replication along the missing dimension, so every helper also accepts the
historical single-GA meshes.  The DSE engine (``core.engine``) places its
slot-packed launches through the same ``place_batched`` path —
``sharded_search_engine`` / ``serve.dse.DSEService(mesh=...)`` put the
whole request->plan->execute service on the mesh.  Remaining open item:
real-TPU timings (ROADMAP.md) — this container runs Pallas in interpret
mode.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import space
from repro.core.ga import GAResult, run_ga_batched
from repro.core.objectives import make_objective
from repro.imc.cost import evaluate_designs_arrays
from repro.imc.tech import TECH, TechParams
from repro.workloads.pack import WorkloadSet

SEARCH_AXIS = "search"
POP_AXES = ("pod", "data")


# ------------------------------------------------------------- axis helpers
def pop_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the population dimension shards over (may be empty)."""
    return tuple(a for a in POP_AXES if a in mesh.axis_names)


def search_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the search batch dimension shards over (may be empty)."""
    return tuple(a for a in (SEARCH_AXIS,) if a in mesh.axis_names)


def batch_axes(mesh: Mesh) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """``(search_axes, pop_axes)`` — disjoint axis groups for the 2-D
    (search, population) layout.  Invariants (checked in test_properties):
    the groups never overlap and only name axes present on the mesh."""
    return search_axes(mesh), pop_axes(mesh)


def spec_axes(part) -> Tuple[str, ...]:
    """One ``PartitionSpec`` entry as a tuple of mesh-axis names.
    ``PartitionSpec`` stores a one-axis group as the bare name
    (``P(None, ("data",))[1] == "data"``), so readers of a spec go through
    here: ``None`` -> ``()``, ``"data"`` -> ``("data",)``."""
    if part is None:
        return ()
    return part if isinstance(part, tuple) else (part,)


def batch_spec(mesh: Mesh, ndim: int, pop_dim: Optional[int] = None) -> P:
    """PartitionSpec for a batched array: dim 0 over ``search``, optional
    ``pop_dim`` over ``pod``/``data``, everything else replicated.  Missing
    mesh axes degrade to ``None`` (replicated), never an empty ``P(())``."""
    s_ax, p_ax = batch_axes(mesh)
    parts = [s_ax or None] + [None] * (ndim - 1)
    if pop_dim is not None and p_ax and 0 < pop_dim < ndim:
        parts[pop_dim] = p_ax
    return P(*parts)


def shape_spec(
    mesh: Mesh, shape: Sequence[int], pop_dim: Optional[int] = None
) -> P:
    """``batch_spec`` refined against a concrete shape: any dimension whose
    size is not divisible by its mesh-axis-group product degrades to
    replication (odd populations, B not a multiple of the search axis),
    because ``device_put``/``with_sharding_constraint`` reject uneven
    shards.  Scores are bit-identical either way — this only trades
    parallelism on the ragged dimension."""
    spec = batch_spec(mesh, len(shape), pop_dim)
    parts = []
    for dim, part in enumerate(spec):
        names = spec_axes(part)
        group = int(np.prod([mesh.shape[a] for a in names]))
        parts.append(part if names and shape[dim] % group == 0 else None)
    return P(*parts)


def place_batched(mesh: Mesh, x, *, pop_dim: Optional[int] = None):
    """Commit ``x`` to its 2-D layout placement."""
    x = jnp.asarray(x)
    return jax.device_put(x, NamedSharding(mesh, shape_spec(mesh, x.shape, pop_dim)))


# ------------------------------------------------------------ eval callbacks
def sharded_eval_fn(
    mesh: Mesh,
    ws: WorkloadSet,
    objective: str,
    area_constr: float,
    tech: TechParams = TECH,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """eval_fn with the population axis sharded over every data-ish mesh
    axis.  On a mesh with no ``pod``/``data`` axis the constraint degrades
    to full replication instead of an empty-tuple spec."""
    axes = pop_axes(mesh)
    group = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    obj = make_objective(objective, area_constr)
    feats, mask = ws.feats, ws.mask

    @jax.jit
    def eval_fn(genomes: jnp.ndarray) -> jnp.ndarray:
        # replicate instead of shard when the population is ragged (shapes
        # are static under trace, so this costs nothing at run time)
        shard = bool(axes) and genomes.shape[0] % group == 0
        pop_sharding = NamedSharding(mesh, P(axes, None) if shard else P())
        out_sharding = NamedSharding(mesh, P(axes) if shard else P())
        genomes = jax.lax.with_sharding_constraint(genomes, pop_sharding)
        scores = obj(evaluate_designs_arrays(space.decode(genomes), feats, mask, tech))
        return jax.lax.with_sharding_constraint(scores, out_sharding)

    return eval_fn


def sharded_batched_eval_fn(
    mesh: Mesh,
    objective: Optional[str],
    area_constr: float,
    tech: TechParams = TECH,
    *,
    backend: str = "jnp",
) -> Callable[[jnp.ndarray, Any], jnp.ndarray]:
    """Batched ``eval_fn(genomes (B, P, n), ctx) -> scores (B, P)`` with the
    2-D (search, population) layout annotated via sharding constraints.

    ``ctx`` is ``(feats (B, W, L, 6), mask (B, W, L))`` — or, for
    ``backend="table"``, ``(tables,)`` with ``tables`` an
    ``imc.tables.WorkloadTables`` pytree whose every leaf carries the
    leading B axis (tables are just more batched leaves: ``place_batched``
    pins them to the ``search`` mesh axis like feats/mask).  With
    ``objective=None`` a trailing ``weights (B, 3)`` leaf selects the
    exponent-weighted objective.  Reuses the cached ``core.search`` eval
    callbacks, so the same compiled cost model backs sharded and unsharded
    paths.  Used by the fleet dry-run (launch/dryrun.py --search-mesh
    [--backend table]) and standalone batched rescoring.
    """
    from repro.core.engine import _ctx_eval  # deferred: engine places via us

    base = _ctx_eval(objective, float(area_constr), tech, backend)

    @jax.jit
    def eval_fn(genomes: jnp.ndarray, ctx) -> jnp.ndarray:
        g_sharding = NamedSharding(mesh, shape_spec(mesh, genomes.shape, pop_dim=1))
        genomes = jax.lax.with_sharding_constraint(genomes, g_sharding)
        scores = jax.vmap(base)(genomes, ctx)
        s_sharding = NamedSharding(mesh, shape_spec(mesh, scores.shape, pop_dim=1))
        return jax.lax.with_sharding_constraint(scores, s_sharding)

    return eval_fn


# ------------------------------------------------------------ batched drivers
def sharded_run_ga_batched(
    mesh: Mesh,
    keys: jnp.ndarray,
    eval_fn: Callable,
    *,
    init_genomes: jnp.ndarray,
    ctx: Any = None,
    **kw,
) -> GAResult:
    """``core.ga.run_ga_batched`` with its inputs committed to the 2-D
    layout: keys/ctx batch-sharded over ``search``, init populations over
    (``search``, ``data``).  GSPMD propagates the layout through the cached
    GA program; results match the unsharded call bit-for-bit."""
    keys = place_batched(mesh, keys)
    # copy before placing: the GA donates its init, and device_put is a
    # no-op (same buffer) when the caller already committed this layout
    init_genomes = place_batched(mesh, jnp.array(init_genomes), pop_dim=1)
    if ctx is not None:
        ctx = jax.tree_util.tree_map(lambda a: place_batched(mesh, a), ctx)
    return run_ga_batched(keys, eval_fn, init_genomes=init_genomes, ctx=ctx, **kw)


def sharded_batched_search(mesh: Mesh, keys, feats, mask, **kw):
    """``core.search.batched_search`` on a (search, population) mesh."""
    from repro.core import search

    return search.batched_search(keys, feats, mask, mesh=mesh, **kw)


def sharded_separate_search(mesh: Mesh, key, ws: WorkloadSet, **kw):
    """``core.search.separate_search`` with the W per-workload GAs sharded
    over the ``search`` axis (one mesh slice per workload)."""
    from repro.core import search

    return search.separate_search(key, ws, mesh=mesh, **kw)


def sharded_seed_population_batched(mesh: Mesh, keys, feats, mask, pop_size, **kw):
    """``core.search.seed_population_batched`` on a (search, population) mesh."""
    from repro.core import search

    return search.seed_population_batched(keys, feats, mask, pop_size, mesh=mesh, **kw)


def sharded_search_engine(mesh: Mesh, **kw) -> "SearchEngine":
    """A ``core.engine.SearchEngine`` whose every plan launch commits its
    slot-packed inputs to this (search, population) mesh — the DSE-service
    stack (``serve.dse.DSEService(mesh=...)``) on a pod.  Scores stay
    bit-identical to the meshless engine (tests/test_engine.py)."""
    from repro.core.engine import SearchEngine

    return SearchEngine(mesh=mesh, **kw)
