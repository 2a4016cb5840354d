"""Joint / separate hardware-workload search drivers (paper Sec. III-A, IV).

Since the engine refactor every driver here is a THIN wrapper: it builds
``core.engine.SearchRequest``s and hands them to the shared
``core.engine.SearchEngine``, which plans (groups by traced-shape
signature, slot-packs) and executes them as cached one-jit vmapped GA
programs.  The layering:

    serve/dse.py        continuous-batching queue over heterogeneous
                        requests (submit / step / drain / stream)
    core/engine.py      SearchRequest -> plan_batch -> SearchEngine.execute
                        (ctx/seeding/finalize plumbing lives HERE, once)
    core/ga.py          the one-jit, donated, vmapped GA
    imc/{cost,tables}   dense oracle + factorized table backends

Drivers (public API unchanged from the pre-engine stack):

``joint_search``/``run_search`` — one GA over the full workload set (the
                           paper's method): objective reduces metrics with
                           max over workloads.  One single-slot plan.
``separate_search``      — the baseline: one GA per single workload.  By
                           default all W GAs run as ONE plan
                           (``batched=False`` keeps the sequential
                           reference path; both produce identical scores).
``batched_search``       — B independent GAs (any mix of workload sets /
                           seeds / objective weights) as one plan.
``joint_search_batched`` — multi-seed joint search on top of it.
``rescore_designs``      — re-evaluate any designs on any workload set or
                           objective (the paper's "failed designs"
                           analysis).
``seed_population``      — the paper's seeding rule (configs that cannot
                           fit the *largest* workload are discarded) as a
                           jitted rejection sampler (lives in the engine).

Everything workload-dependent enters the jitted programs as traced array
arguments (string objectives become a traced kind index + area through
``objectives.make_indexed_objective``), and the evaluation callbacks are
cached per (objective-mode, tech, backend) — repeated searches of the
same shape never retrace, and heterogeneous batches (mixed workload
subsets, objectives, areas, seeds) share ONE program.  The batched
drivers take ``mesh=`` (``launch.mesh.make_search_mesh``) to lay the B
independent GAs out over a 2-D (search, population) device mesh — see
``core.distributed`` — with bit-identical scores.

Three evaluation backends (``backend=``): ``"jnp"`` (dense (P, W, L)
oracle), ``"pallas"`` (the imc_eval TPU kernel), and ``"table"`` — the
factorized cost model (``imc.tables``): the layer axis is reduced once per
workload set into grid tables that travel through the traced ``ctx``, and
every per-generation evaluation is O(W) gathers per design, independent of
workload depth L.  Because the table ctx is layer-free, the engine packs
requests over DIFFERENT workload sets into one program (zero-padded table
rows are exactly neutral under the max-reduction) — the basis of the DSE
service (``serve.dse``), which drains hundreds of heterogeneous requests
through a handful of compiled programs (tests/test_engine.py asserts
bit-identical parity with per-request ``run_search``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import space
from repro.core.engine import (  # noqa: F401 — re-exported public/test API
    BACKENDS,
    EngineFault,
    NonFiniteScoreError,
    SearchRequest,
    SearchResult,
    _ctx_eval,
    _eval_ctx,
    _finalize,
    _top_unique,
    _workload_weights,
    default_engine,
    empty_partial_result,
    largest_workload_index,
    make_eval_fn,
    seed_population,
    seed_population_batched,
)
from repro.core.objectives import make_objective
from repro.imc.cost import EvalResult, evaluate_designs
from repro.imc.tech import TECH, TechParams
from repro.workloads.pack import WorkloadSet


def _resolve_engine(engine, pipelined=None):
    """The engine a driver call runs on: an explicit ``engine`` wins (its
    own ``pipelined`` setting governs), otherwise the shared default — or,
    when the caller pins ``pipelined``, a per-call engine carrying it
    (engines are stateless apart from content caches, so this costs one
    object, not a retrace: the jit caches are global)."""
    if engine is not None:
        return engine
    if pipelined is None:
        return default_engine()
    from repro.core.engine import SearchEngine

    return SearchEngine(pipelined=bool(pipelined))


# ----------------------------------------------------------------- drivers
def run_search(
    key: jax.Array,
    ws: WorkloadSet,
    *,
    objective: str = "ela",
    area_constr: float = 150.0,
    pop_size: int = 40,
    generations: int = 10,
    top_k: int = 10,
    pareto_k: int = 10,
    init_genomes: Optional[jnp.ndarray] = None,
    tech: TechParams = TECH,
    backend: str = "jnp",
    engine=None,
    pipelined: Optional[bool] = None,
) -> SearchResult:
    """One joint search = a single-request engine plan.  ``engine``
    substitutes a configured ``SearchEngine`` (e.g. segmented execution
    with checkpoints) for the shared default.  ``pipelined`` pins the
    transfer-thin engine path: identical result fields, but ``result.ga``
    is ``None`` (the history stays on device — see ``SearchEngine``).
    ``objective="pareto"`` switches to NSGA-II front search: the result's
    ``top_*`` fields hold the ``pareto_k`` best front members in crowded
    order and ``objective_vectors`` their per-member (E, L, A) triples."""
    req = SearchRequest(
        ws=ws, objective=objective, area_constr=float(area_constr),
        key=key, backend=backend, pop_size=int(pop_size),
        generations=int(generations), top_k=int(top_k),
        pareto_k=int(pareto_k), tech=tech,
        init_genomes=init_genomes,
    )
    return _resolve_engine(engine, pipelined).run([req])[0]


def joint_search(key, ws: WorkloadSet, **kw) -> SearchResult:
    return run_search(key, ws, **kw)


def batched_search(
    keys: jnp.ndarray,
    feats: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    names: Optional[Sequence] = None,
    objective: str = "ela",
    obj_weights: Optional[jnp.ndarray] = None,
    area_constr: float = 150.0,
    pop_size: int = 40,
    generations: int = 10,
    top_k: int = 10,
    pareto_k: int = 10,
    init_genomes: Optional[jnp.ndarray] = None,
    tech: TechParams = TECH,
    backend: str = "jnp",
    mesh=None,
    engine=None,
    pipelined: Optional[bool] = None,
) -> List[SearchResult]:
    """B independent searches through the engine (one plan when shapes
    agree, chunked at the engine's slot limit for very large B).

    ``keys`` (B, 2) stacked PRNG keys; ``feats`` (B, W, L, 6) / ``mask``
    (B, W, L) per-element workload sets; ``init_genomes`` (B, P, n) or
    ``None`` (batched largest-workload rejection seeding).  With
    ``obj_weights`` (B, 3) the exponent-weighted objective scores each
    element with its own weights — one program covers every objective
    family.  Per-element RNG matches ``run_search(keys[b], ...)`` exactly,
    so batched and sequential drivers return identical scores.

    ``mesh`` (a ``launch.mesh.make_search_mesh`` layout) commits the inputs
    to the 2-D (search, population) placement: the B axis shards over the
    ``search`` mesh axis and each population over ``pod``/``data`` — GSPMD
    partitions the cached GA program accordingly (no retrace of the traced
    ctx path).  Scores stay bit-identical to ``mesh=None``
    (tests/test_search_sharded.py).
    """
    # ONE device->host transfer per input; the per-request WorkloadSets are
    # numpy-backed views, so the engine's slot packing (and fingerprinting)
    # never syncs the device again on the warm path
    keys = np.asarray(keys)
    feats = np.asarray(feats, np.float32)
    mask = np.asarray(mask, bool)
    B = keys.shape[0]
    if names is None:
        names_b = [tuple(f"w{j}" for j in range(feats.shape[1]))] * B
    elif isinstance(names[0], str):
        names_b = [tuple(names)] * B
    else:
        names_b = [tuple(n) for n in names]
    if obj_weights is not None:
        obj_weights = np.asarray(obj_weights, np.float64)
    if init_genomes is not None:
        init_genomes = np.asarray(init_genomes)
    reqs = [
        SearchRequest(
            ws=WorkloadSet(names=names_b[b], feats=feats[b], mask=mask[b]),
            objective=objective,
            obj_weights=(
                None if obj_weights is None else tuple(obj_weights[b])
            ),
            area_constr=float(area_constr),
            key=keys[b],
            backend=backend,
            pop_size=int(pop_size),
            generations=int(generations),
            top_k=int(top_k),
            pareto_k=int(pareto_k),
            tech=tech,
            init_genomes=None if init_genomes is None else init_genomes[b],
        )
        for b in range(B)
    ]
    return _resolve_engine(engine, pipelined).run(reqs, mesh=mesh)


def joint_search_batched(keys: jnp.ndarray, ws: WorkloadSet, **kw) -> List[SearchResult]:
    """Multi-seed joint search: one GA per key, all in one XLA program."""
    keys = jnp.asarray(keys)
    B = keys.shape[0]
    feats = jnp.broadcast_to(ws.feats[None], (B,) + ws.feats.shape)
    mask = jnp.broadcast_to(ws.mask[None], (B,) + ws.mask.shape)
    return batched_search(keys, feats, mask, names=ws.names, **kw)


def separate_search(
    key,
    ws: WorkloadSet,
    *,
    share_init: Optional[jnp.ndarray] = None,
    batched: bool = True,
    mesh=None,
    **kw,
) -> Dict[str, SearchResult]:
    """One single-workload GA per workload (the paper's baseline).

    ``batched=True`` (default) runs all W GAs as one engine plan;
    ``batched=False`` is the sequential reference path (one single-slot
    plan per workload).  Both derive per-workload keys from
    ``jax.random.split(key, W)`` and return identical scores (asserted in
    tests/test_search_batched.py).  ``mesh`` shards the W GAs over the
    ``search`` mesh axis (batched path only)."""
    if mesh is not None and not batched:
        raise ValueError("mesh= requires the batched path (batched=True)")
    keys = jax.random.split(key, ws.n)
    if batched:
        init = None
        if share_init is not None:
            init = jnp.tile(jnp.asarray(share_init)[None], (ws.n, 1, 1))
        res = batched_search(
            keys,
            ws.feats[:, None],  # (W, 1, L, 6): one workload per element
            ws.mask[:, None],
            names=[(n,) for n in ws.names],
            init_genomes=init,
            mesh=mesh,
            **kw,
        )
        return dict(zip(ws.names, res))
    out = {}
    for i, name in enumerate(ws.names):
        out[name] = run_search(keys[i], ws.subset([i]), init_genomes=share_init, **kw)
    return out


def rescore_designs(
    genomes: np.ndarray,
    ws: WorkloadSet,
    *,
    objective: str = "ela",
    area_constr: float = 150.0,
    tech: TechParams = TECH,
) -> Tuple[np.ndarray, EvalResult]:
    """Scores + full metrics of given designs on a (possibly different)
    workload set — the paper's cross-evaluation."""
    g = jnp.asarray(genomes)
    r = evaluate_designs(space.decode(g), ws, tech)
    s = make_objective(objective, area_constr)(r)
    return np.asarray(s), r
