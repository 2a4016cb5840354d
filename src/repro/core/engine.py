"""DSE engine: search request -> batch plan -> one cached XLA program.

The service layer of the search stack (the ROADMAP's DSE-service north
star).  Every driver in ``core.search`` is a thin wrapper over three
pieces defined here:

  * ``SearchRequest``   — one search: workload set + objective (kind or
    exponent weights) + area + seed + backend + GA params.  Requests are
    heterogeneous: any mix of workload subsets, objectives, seeds and
    backends can be submitted together.
  * ``plan_batch``      — groups compatible requests by *traced-shape
    signature* (pop, generations, backend, tech — plus the raw (W, L)
    shape for dense backends; the ``table`` backend is layer-free, so any
    workload shapes pack together) and slot-packs each group into chunks
    of at most ``max_slots``, padding the last ragged chunk with repeated
    slots so every chunk of a group traces to the SAME program.
  * ``SearchEngine``    — executes a plan as one vmapped, donated,
    cached GA jit (``core.ga.run_ga_batched``), reusing the factorized
    table ctx (``imc.tables``) and the 2-D (search, population) mesh
    placement from ``core.distributed``.

Heterogeneity inside one program:

  * **Objectives** enter as a traced per-slot kind index + area scalar
    (``objectives.make_indexed_objective``): every branch computes exactly
    the expression of the static ``make_objective`` path, so packed scores
    are bit-identical to per-request ``run_search``.  Custom exponent
    weights use the weighted objective (its own signature group).
  * **Workload sets** under ``backend="table"`` are padded along W with
    all-zero table rows: a zero-demand workload fits everywhere and
    contributes 0 to the ``max``-reduction, which is exactly neutral.
    The seeding program sees mask-padded (W, L) feats; every quantity it
    consumes (crossbar demand, fits) is integer-valued, so padded layers
    are exactly neutral there too.
  * **Seeds** are just data (stacked PRNG keys).

Parity is asserted bit-identical against per-request ``run_search`` in
tests/test_engine.py, including under the fake-8-device mesh.  256 mixed
requests drain through 2 compiled programs (one seeding jit + one GA jit
entry); the acceptance test bounds it at 4.
"""
from __future__ import annotations

import dataclasses
import hashlib
from functools import lru_cache, partial
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import space
from repro.core.ga import (
    GAResult,
    GAState,
    GAThin,
    ParetoThin,
    ga_epilogue_batched,
    init_ga_state_batched,
    run_ga_batched,
    run_ga_batched_segment,
    run_ga_batched_thin,
    run_pareto_batched,
)
from repro.core.objectives import (
    OBJECTIVE_INDEX,
    OBJECTIVE_WEIGHTS,
    PARETO,
    make_indexed_objective,
    make_objective,
    make_pareto_objective,
    make_weighted_objective,
    pareto_scalar,
)
from repro.imc.cost import VF_RTOL, evaluate_designs_arrays
from repro.imc.tech import TECH, TechParams
from repro.utils import spans
from repro.workloads.pack import WorkloadSet

BACKENDS = ("jnp", "pallas", "table")

# reserved objective name selecting the traced-kind-index objective; the
# engine uses it so one program covers every OBJECTIVES kind and area
INDEXED = "__indexed__"


@dataclasses.dataclass
class SearchResult:
    workload_names: Tuple[str, ...]
    objective: str
    ga: Optional[GAResult]  # None for empty partials (never launched) and
    # for pipelined (transfer-thin) results, whose history never reaches host
    top_designs: List[Dict[str, float]]  # decoded, deduped, best-first
    top_scores: np.ndarray
    top_genomes: np.ndarray
    convergence: np.ndarray  # best-so-far score per generation
    valid: bool = True  # False: no finite-scoring design in the history
    partial: bool = False  # True: search stopped before its full budget
    generations: int = -1  # generations actually applied (-1 = full budget)
    # objective="pareto" only: per-member (max_W E, max_W L, A) vectors,
    # (kept, 3) float32 aligned with top_genomes/top_scores; None for the
    # scalar objective families
    objective_vectors: Optional[np.ndarray] = None


class EngineFault(RuntimeError):
    """A launch failed permanently (retries exhausted, or no retry path).

    ``partials`` — when the failing plan had already advanced some
    segments — carries one anytime ``SearchResult`` (``partial=True``,
    finalized from the accumulated history) per plan request, aligned
    with ``plan.requests`` (``None`` where nothing was evaluated yet), so
    a service can resolve the affected rids with their best-so-far."""

    def __init__(self, msg: str, *, partials: Optional[List[Optional[SearchResult]]] = None,
                 generations_done: int = 0):
        super().__init__(msg)
        self.partials = partials
        self.generations_done = int(generations_done)


class NonFiniteScoreError(EngineFault):
    """The per-segment score guard tripped: a launch produced NaN scores.

    (+inf is the NORMAL encoding for an infeasible design, so the guard
    is NaN-only; an all-infeasible history is flagged on the result as
    ``valid=False`` by ``_finalize`` instead.)"""


# --------------------------------------------------------- eval callbacks
@lru_cache(maxsize=None)
def _ctx_eval(
    objective: Optional[str], area_constr: float, tech: TechParams, backend: str
) -> Callable:
    """Cached ``eval_fn(genomes, ctx)`` with ``ctx = (feats (W, L, 6),
    mask (W, L))`` — or, for ``backend="table"``, ``ctx = (tables,)`` with
    ``tables`` an ``imc.tables.WorkloadTables`` pytree (``_eval_ctx`` builds
    the right one).  ``objective`` selects the scoring tail: a kind string
    (static), ``None`` (trailing traced ``weights (3,)`` leaf, exponent-
    weighted), ``PARETO`` (trailing traced ``area`` leaf; the fn returns
    (P, 3) objective VECTORS for NSGA-II survival), or ``INDEXED``
    (trailing traced ``(kind_index, area)`` leaves — the engine's
    mixed-objective path, bit-identical per branch to the static kinds).  The cache (plus workload tensors/tables being
    traced, not closed over) is what keeps the GA jit from retracing
    across seeds, workload sets and objectives."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if objective == INDEXED:
        obj = make_indexed_objective()
    elif objective == PARETO:
        obj = make_pareto_objective()
    elif objective is None:
        obj = make_weighted_objective(area_constr)
    else:
        obj = make_objective(objective, area_constr)

    if backend == "table":
        from repro.imc.tables import evaluate_genomes_tables

        def ev(genomes, ctx):
            return evaluate_genomes_tables(genomes, ctx[0], tech)

    elif backend == "pallas":
        from repro.kernels.imc_eval.ops import evaluate_designs_kernel_arrays

        def ev(genomes, ctx):
            return evaluate_designs_kernel_arrays(
                space.decode(genomes), ctx[0], ctx[1], tech
            )

    else:

        def ev(genomes, ctx):
            return evaluate_designs_arrays(space.decode(genomes), ctx[0], ctx[1], tech)

    def eval_fn(genomes: jnp.ndarray, ctx) -> jnp.ndarray:
        r = ev(genomes, ctx)
        if objective == INDEXED:
            return obj(r, ctx[-2], ctx[-1])
        if objective == PARETO or objective is None:
            # one trailing traced leaf: the (3,) weights (weighted) or the
            # () area constraint (pareto vector objective)
            return obj(r, ctx[-1])
        return obj(r)

    return eval_fn


def _eval_ctx(
    feats: jnp.ndarray,
    mask: jnp.ndarray,
    tech: TechParams,
    backend: str,
    *,
    batched: bool = False,
) -> Tuple:
    """The workload half of an eval ``ctx`` for ``backend``: the raw
    ``(feats, mask)`` tensors, or — for the table backend — the factorized
    ``(tables,)`` statistics, reduced over the layer axis here, ONCE, so
    the per-generation evaluation never sees L again."""
    if backend != "table":
        return (feats, mask)
    from repro.imc.tables import build_tables_arrays, build_tables_batched

    build = build_tables_batched if batched else build_tables_arrays
    return (build(feats, mask, tech),)


def make_eval_fn(
    ws: WorkloadSet,
    objective: str,
    area_constr: float,
    tech: TechParams = TECH,
    *,
    backend: str = "jnp",
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """backend: "jnp" (portable), "pallas" (the imc_eval TPU kernel;
    interpret-mode off-TPU — numerically identical, see tests) or "table"
    (factorized per-workload grid tables: O(W) gathers per design, no
    layer axis — allclose to "jnp", see tests/test_tables.py)."""
    fn = _ctx_eval(objective, float(area_constr), tech, backend)
    ctx = (ws.tables(tech),) if backend == "table" else (ws.feats, ws.mask)

    def eval_fn(genomes: jnp.ndarray) -> jnp.ndarray:
        return fn(genomes, ctx)

    return eval_fn


def _workload_weights(feats: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Crossbar-demand proxy per workload (total weight count K * N * groups);
    the single definition of "largest" shared by sequential and batched
    seeding so their largest-workload picks can never diverge."""
    return (feats[..., 1] * feats[..., 2] * feats[..., 5] * mask).sum(-1)


def largest_workload_index(ws: WorkloadSet) -> int:
    """Largest = most crossbar demand at a reference design (most weights)."""
    return int(jnp.argmax(_workload_weights(ws.feats, ws.mask)))


# ----------------------------------------------------------------- seeding
def _seed_rounds(key, feats, mask, pop_size, oversample, max_rounds, tech):
    """Jit-traceable rejection sampler against ONE workload (feats (L, 6)).

    Each round draws ``pop_size * oversample`` candidates, keeps those that
    fit and are V/f-valid, and scatters them into the next free pool slots;
    a ``lax.while_loop`` repeats until the pool is full or ``max_rounds``
    is hit — the host only syncs once, on the final (pool, count).
    Returns ``(pool, count, rounds)``: ``rounds`` is how many were drawn."""
    n_cand = pop_size * oversample

    def cond(st):
        _, _, count, rnd = st
        return (count < pop_size) & (rnd < max_rounds)

    def body(st):
        key, pool, count, rnd = st
        key, k = jax.random.split(key)
        cand = space.random_genomes(k, n_cand)
        r = evaluate_designs_arrays(space.decode(cand), feats[None], mask[None], tech)
        ok = r.fits[:, 0] & r.valid
        pos = count + jnp.cumsum(ok) - 1
        idx = jnp.where(ok & (pos < pop_size), pos, pop_size)  # OOB -> dropped
        pool = pool.at[idx].set(cand, mode="drop")
        count = jnp.minimum(count + ok.sum(), pop_size)
        return key, pool, count, rnd + jnp.int32(1)

    pool0 = jnp.zeros((pop_size, space.N_GENES), jnp.float32)
    st = (key, pool0, jnp.int32(0), jnp.int32(0))
    _, pool, count, rounds = jax.lax.while_loop(cond, body, st)
    return pool, count, rounds


_SEED_STATICS = ("pop_size", "oversample", "max_rounds", "tech")


@partial(jax.jit, static_argnames=_SEED_STATICS)
def _seed_jit(key, feats, mask, *, pop_size, oversample, max_rounds, tech):
    return _seed_rounds(key, feats, mask, pop_size, oversample, max_rounds, tech)


@partial(jax.jit, static_argnames=_SEED_STATICS)
def _seed_batched_jit(keys, feats, mask, *, pop_size, oversample, max_rounds, tech):
    """keys (B, 2), feats (B, W, L, 6), mask (B, W, L).  Each element's
    largest workload is picked as a TRACED argmax+gather inside the
    program — no host-side device sync before the seeding launch.
    Returns ``(pools, seeded)``: ``seeded`` (2, B) int32 stacks each
    element's count and rounds, so one read fetches both."""

    def one(k, ft, mk):
        li = jnp.argmax(_workload_weights(ft, mk))
        return _seed_rounds(k, ft[li], mk[li], pop_size, oversample, max_rounds, tech)

    pools, counts, rounds = jax.vmap(one)(keys, feats, mask)
    return pools, jnp.stack([counts, rounds])


def _valid_vt_mask(tech: TechParams) -> np.ndarray:
    """(V, Tc) boolean mask of ``imc.cost.design_valid`` over the
    (v_op, t_cycle_ns) grid — the only two axes validity depends on.
    Host numpy mirror of the jnp formula (identical f32 arithmetic)."""
    v = np.asarray(space.SPACE["v_op"], np.float32)[:, None]
    t = np.asarray(space.SPACE["t_cycle_ns"], np.float32)[None, :]
    k = np.float32(
        (tech.v_nominal - tech.v_th) ** tech.alpha_power / tech.v_nominal
    )
    t_min = k * v / (v - np.float32(tech.v_th)) ** np.float32(tech.alpha_power)
    return t >= t_min * np.float32(1.0 - VF_RTOL)


# the six jointly-constrained fields of the direct seeder: the demand
# table's axes first, then the capacity axes — their mixed-radix order
# defines the 6-D cell index the CDF is over
_CAP_FIELDS = (
    "rows", "cols", "bits_cell", "c_per_tile", "t_per_router", "g_per_chip"
)


def _seed_cells_cdf(demand_l: np.ndarray) -> np.ndarray:
    """Host-side feasible-cell CDF of ONE workload's demand table.

    Feasibility factorizes exactly like the rejection test the direct
    seeder replaces: ``demand[rows, cols, bits] <= c_per_tile *
    t_per_router * g_per_chip`` over the 6-D grid (``glb_mb`` and the
    validity pair are handled separately).  Returns the inclusive int32
    prefix-sum over the flat (R, C, Bc, Cpt, Tpr, Gpc) cell order —
    cheap numpy on ~1e4..1e6 cells, computed once per (workload set,
    tech, grid) and cached; the jitted sampler only searchsorts it."""
    cpt = np.asarray(space.SPACE["c_per_tile"], np.float32)
    tpr = np.asarray(space.SPACE["t_per_router"], np.float32)
    gpc = np.asarray(space.SPACE["g_per_chip"], np.float32)
    cap = cpt[:, None, None] * tpr[None, :, None] * gpc[None, None, :]
    feas = demand_l[:, :, :, None, None, None] <= cap[None, None, None]
    return np.cumsum(feas.reshape(-1).astype(np.int64)).astype(np.int32)


def _seed_direct(key, cdf6, pop_size, tech):
    """Direct inverse-CDF sampler over the feasible cells of the largest
    workload — the table-backend replacement for the rejection rounds.

    ``cdf6`` is the precomputed joint-cell CDF (``_seed_cells_cdf``); the
    (v_op, t_cycle) validity mask contributes a second, trace-time CDF,
    and two uniform selectors pick cells by ``searchsorted``.  Each gene
    is then placed uniformly INSIDE its cell with a [1e-3, 1-1e-3]
    margin, so the f32 round-trip ``floor(genome * n)`` in
    ``space.decode_indices`` can never cross a cell boundary (round-trip
    error ~1e-6 against a 1e-3 margin).  Every sampled design fits the
    largest workload and is V/f-valid by construction — the paper's
    seeding rule with zero rejected draws and no data-dependent
    while-loop."""
    sizes = {f: len(space.SPACE[f]) for f in space.FIELDS}
    total6 = cdf6[-1]
    vt = _valid_vt_mask(tech)  # (V, Tc), trace-time constant
    cdf2 = jnp.asarray(np.cumsum(vt.reshape(-1).astype(np.int64)), jnp.int32)
    total2 = cdf2[-1]

    u = jax.random.uniform(key, (pop_size, space.N_GENES + 2))
    # clamp the selector below the count: f32 rounding of u*total on very
    # dense grids (total > 2^24) could otherwise land exactly on total
    k6 = jnp.minimum((u[:, -2] * total6).astype(jnp.int32), total6 - 1)
    k2 = jnp.minimum((u[:, -1] * total2).astype(jnp.int32), total2 - 1)
    sel6 = jnp.searchsorted(cdf6, k6, side="right")
    sel2 = jnp.searchsorted(cdf2, k2, side="right")
    idx = {}
    rem = sel6
    for f in reversed(_CAP_FIELDS):
        idx[f] = rem % sizes[f]
        rem = rem // sizes[f]
    idx["t_cycle_ns"] = sel2 % sizes["t_cycle_ns"]
    idx["v_op"] = sel2 // sizes["t_cycle_ns"]

    genes = []
    for j, f in enumerate(space.FIELDS):
        frac = jnp.clip(u[:, j], 1e-3, 1.0 - 1e-3)
        if f == "glb_mb":  # unconstrained axis: any cell
            genes.append(
                (jnp.floor(u[:, j] * sizes[f]) + frac) / sizes[f]
            )
        else:
            genes.append((idx[f].astype(jnp.float32) + frac) / sizes[f])
    pool = jnp.stack(genes, axis=1)
    # count mirrors the rejection seeder's contract: full unless the
    # largest workload fits NOWHERE in the space
    count = jnp.where(total6 > 0, jnp.int32(pop_size), jnp.int32(0))
    return pool, count


@partial(jax.jit, static_argnames=("pop_size", "tech"))
def _seed_direct_batched_jit(keys, cdf6, *, pop_size, tech):
    """keys (B, 2), cdf6 (B, n_cells) stacked per-slot feasible-cell CDFs
    (largest workload each, precomputed host-side and cached) feeding the
    direct cell sampler.  Returns ``(pools, seeded)`` as the rejection
    seeder does; the direct sampler draws one round."""

    def one(k, cdf):
        return _seed_direct(k, cdf, pop_size, tech)

    pools, counts = jax.vmap(one)(keys, cdf6)
    return pools, jnp.stack([counts, jnp.ones_like(counts)])


def seed_population(
    key: jax.Array,
    ws: WorkloadSet,
    pop_size: int,
    *,
    tech: TechParams = TECH,
    oversample: int = 64,
    max_rounds: int = 8,
) -> jnp.ndarray:
    """Random init; designs failing the largest workload (or V/f-invalid)
    are discarded (paper Sec. III-C).  One jitted while-loop program."""
    wi = largest_workload_index(ws)
    pool, count, _ = _seed_jit(
        key, ws.feats[wi], ws.mask[wi],
        pop_size=int(pop_size), oversample=int(oversample),
        max_rounds=int(max_rounds), tech=tech,
    )
    if int(count) < pop_size:
        raise RuntimeError(
            f"could not seed {pop_size} valid designs ({int(count)} found); "
            "largest workload may not fit anywhere in the search space"
        )
    return pool


def seed_population_batched(
    keys: jnp.ndarray,
    feats: jnp.ndarray,
    mask: jnp.ndarray,
    pop_size: int,
    *,
    tech: TechParams = TECH,
    oversample: int = 64,
    max_rounds: int = 8,
    mesh=None,
) -> jnp.ndarray:
    """Per-batch-element seeding: keys (B, 2), feats (B, W, L, 6), mask
    (B, W, L) -> pools (B, pop_size, n).  Each element rejects against its
    own largest workload — selected by a traced argmax INSIDE the jit, so
    nothing blocks on device between the call and the seeding launch — all
    under one vmapped while-loop.  With ``mesh`` (a
    ``launch.mesh.make_search_mesh`` layout) the batch axis is committed
    to the ``search`` mesh axis before the launch, so each mesh slice seeds
    its own searches."""
    if mesh is not None:
        from repro.core.distributed import place_batched

        keys = place_batched(mesh, keys)
        feats = place_batched(mesh, feats)
        mask = place_batched(mesh, mask)
    pools, seeded = _seed_batched_jit(
        keys, feats, mask,
        pop_size=int(pop_size), oversample=int(oversample),
        max_rounds=int(max_rounds), tech=tech,
    )
    counts = np.asarray(seeded)[0]
    if counts.min() < pop_size:
        bad = int(np.argmin(counts))
        raise RuntimeError(
            f"could not seed {pop_size} valid designs for batch element {bad} "
            f"({int(counts[bad])} found)"
        )
    return pools


# ------------------------------------------------------------- result prep
def _top_unique(
    genomes: np.ndarray, scores: np.ndarray, k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Best-k designs, unique in *decoded grid index* space.

    Fully vectorized host-side numpy (``np.unique`` over score-sorted grid
    indices instead of a Python loop over all G*P designs, and a host
    decode instead of per-call jnp dispatches): sorting by score first
    means each unique design's first occurrence is its best-scoring one,
    and non-finite scores (inf/nan) sort to the end, so dropping them
    equals the old truncate-at-first-non-finite rule."""
    idx = space.decode_indices_np(genomes)
    # mixed-radix encode to ONE int64 per design: 1-D np.unique is far
    # cheaper than the row-wise axis=0 variant, and the encoding is
    # injective (SPACE_SIZE < 2^63 at any realistic grid density), so the
    # unique classes — and therefore the kept designs — are identical
    sizes = space.GRID_SIZES.astype(np.int64)
    strides = np.concatenate(
        [np.cumprod(sizes[::-1])[::-1][1:], np.ones(1, np.int64)]
    )
    codes = idx.astype(np.int64) @ strides
    order = np.argsort(scores, kind="stable")
    _, first = np.unique(codes[order], return_index=True)
    first.sort()  # positions within `order`, ascending = best-first
    keep = order[first]
    keep = keep[np.isfinite(scores[keep])][:k]
    return genomes[keep], scores[keep]


def _finalize_batch(
    ga_np: GAResult, requests: Sequence["SearchRequest"],
) -> List[SearchResult]:
    """Vectorized ``_finalize`` over the real slots of one launch.

    The per-slot loop was the warm drain's host bottleneck at large B
    (160 separate argsorts, decodes and unique calls); here the decode,
    the mixed-radix design codes, the stable score argsort and the
    convergence scan run ONCE over (S, (G+1)*P) arrays, leaving only the
    tiny per-slot unique/top-k selection in Python.  Slot-for-slot
    bit-identical to ``_finalize`` on the same history (same stable
    argsort, same unique-class first occurrences, same finite filter) —
    the engine-vs-``run_search`` parity tests cover both paths."""
    S = len(requests)
    G1, P, n = ga_np.genomes.shape[1:]
    flat_g = ga_np.genomes[:S].reshape(S, G1 * P, n)
    flat_s = ga_np.scores[:S].reshape(S, G1 * P)
    idx = space.decode_indices_np(
        flat_g.reshape(-1, n)).reshape(S, G1 * P, n)
    sizes = space.GRID_SIZES.astype(np.int64)
    strides = np.concatenate(
        [np.cumprod(sizes[::-1])[::-1][1:], np.ones(1, np.int64)]
    )
    codes = idx.astype(np.int64) @ strides  # (S, G1*P)
    order = np.argsort(flat_s, axis=1, kind="stable")
    conv = np.minimum.accumulate(ga_np.scores[:S].min(axis=2), axis=1)
    finite = np.isfinite(flat_s)
    out = []
    for i, r in enumerate(requests):
        o = order[i]
        _, first = np.unique(codes[i][o], return_index=True)
        first.sort()
        keep = o[first]
        keep = keep[finite[i][keep]][: r.top_k]
        top_g, top_s = flat_g[i][keep], flat_s[i][keep]
        out.append(SearchResult(
            workload_names=tuple(r.ws.names),
            objective=_objective_label(r),
            ga=GAResult(*(f[i] for f in ga_np)),
            top_designs=space.design_dicts_from_indices(idx[i][keep]),
            top_scores=top_s,
            top_genomes=top_g,
            convergence=conv[i],
            valid=bool(len(top_s)),
            partial=False,
            generations=int(G1) - 1,
        ))
    return out


def _finalize_batch_thin(
    thin_np: GAThin, requests: Sequence["SearchRequest"],
    *, partial: bool = False,
) -> List[SearchResult]:
    """``_finalize_batch`` over the thin epilogue outputs instead of the
    full history: the device already selected each slot's top-k-unique
    designs (``ga._thin_epilogue``, K = the plan's max ``top_k``) and the
    convergence curve, so all that is left is slicing each request's own
    ``top_k`` prefix off the padded arrays and decoding the few kept
    genomes.  The selection is prefix-stable (ordered by score rank), so
    a request asking for fewer than K designs gets exactly the designs
    the history path would have kept — bit-identical fields, except
    ``ga`` is ``None``: the history never crossed the wire."""
    out = []
    for i, r in enumerate(requests):
        kept = int(min(int(thin_np.n_kept[i]), r.top_k))
        top_g = thin_np.top_genomes[i][:kept]
        top_s = thin_np.top_scores[i][:kept]
        conv = thin_np.convergence[i]
        out.append(SearchResult(
            workload_names=tuple(r.ws.names),
            objective=_objective_label(r),
            ga=None,
            top_designs=space.design_dicts_from_indices(
                space.decode_indices_np(top_g)),
            top_scores=top_s,
            top_genomes=top_g,
            convergence=conv,
            valid=bool(kept),
            partial=bool(partial),
            generations=int(conv.shape[-1]) - 1,
        ))
    return out


def _finalize_batch_pareto(
    thin_np: ParetoThin, requests: Sequence["SearchRequest"],
    *, history: Optional[tuple] = None,
) -> List[SearchResult]:
    """Host finalize of a Pareto plan: the device epilogue already picked
    each slot's crowded-order front members (K = the plan's max
    ``pareto_k``, cell-deduped exactly like the scalar thin epilogue), so
    this slices each request's own ``pareto_k`` prefix, decodes the kept
    genomes, and attaches the per-member (E, L, A) vectors.  ``history``
    is the optional synced ``(genomes_hist, objs_hist)`` pair from a
    sequential engine; its scalar-proxy scores (``pareto_scalar`` — the
    E*L*A bits of the ``ela`` objective) make the attached ``ga`` usable
    by every history consumer (rescoring, partial snapshots, caching)."""
    out = []
    gh_np = sh_np = None
    if history is not None:
        gh_np, oh_np = history
        # host numpy multiply in (E, L, A) order: same f32 products, same
        # association as the in-jit pareto_scalar — bit-identical
        sh_np = np.asarray(oh_np[..., 0] * oh_np[..., 1] * oh_np[..., 2])
    for i, r in enumerate(requests):
        kept = int(min(int(thin_np.n_kept[i]), int(r.pareto_k)))
        top_g = thin_np.top_genomes[i][:kept]
        top_v = thin_np.top_vectors[i][:kept]
        top_s = thin_np.top_scores[i][:kept]
        conv = thin_np.convergence[i]
        ga = None
        if gh_np is not None:
            ga = SearchEngine._history_result(gh_np[i], sh_np[i])
        out.append(SearchResult(
            workload_names=tuple(r.ws.names),
            objective=PARETO,
            ga=ga,
            top_designs=space.design_dicts_from_indices(
                space.decode_indices_np(top_g)),
            top_scores=top_s,
            top_genomes=top_g,
            convergence=conv,
            valid=bool(kept),
            partial=False,
            generations=int(conv.shape[-1]) - 1,
            objective_vectors=top_v,
        ))
    return out


def _finalize(
    ga: GAResult, names: Sequence[str], objective: str, top_k: int,
    *, partial: bool = False,
) -> SearchResult:
    G1, P, n = ga.genomes.shape
    flat_g = np.asarray(ga.genomes).reshape(-1, n)
    flat_s = np.asarray(ga.scores).reshape(-1)
    top_g, top_s = _top_unique(flat_g, flat_s, top_k)
    top_designs = space.design_dicts_from_indices(space.decode_indices_np(top_g))
    conv = np.minimum.accumulate(np.asarray(ga.scores).min(axis=1))
    # finite-score guard: _top_unique drops every non-finite (inf/nan)
    # score, so an empty top list means the whole history scored
    # infeasible or poisoned — flag it instead of silently returning
    return SearchResult(
        workload_names=tuple(names),
        objective=objective,
        ga=ga,
        top_designs=top_designs,
        top_scores=top_s,
        top_genomes=top_g,
        convergence=conv,
        valid=bool(len(top_s)),
        partial=bool(partial),
        generations=int(G1) - 1,
    )


def empty_partial_result(req: "SearchRequest") -> SearchResult:
    """The anytime result of a request that never got a good launch: no
    designs, ``valid=False``, ``partial=True``.  What a service resolves
    a quarantined or deadline-swept request with when no checkpointed
    best exists."""
    n = space.N_GENES
    return SearchResult(
        workload_names=tuple(req.ws.names),
        objective=_objective_label(req),
        ga=None,
        top_designs=[],
        top_scores=np.zeros((0,), np.float32),
        top_genomes=np.zeros((0, n), np.float32),
        convergence=np.zeros((0,), np.float32),
        valid=False,
        partial=True,
        generations=0,
    )


def _objective_label(req: "SearchRequest") -> str:
    """Truthful ``SearchResult.objective`` label: the kind string, or the
    kind a custom weight vector reproduces, or ``weighted(...)``."""
    if req.obj_weights is None:
        return req.objective
    inv = {v: k for k, v in OBJECTIVE_WEIGHTS.items()}
    w = tuple(float(v) for v in req.obj_weights)
    return inv.get(w, f"weighted{w}")


# ------------------------------------------------------- request -> plan
@dataclasses.dataclass(frozen=True, eq=False)
class SearchRequest:
    """One DSE query: everything ``run_search`` takes, as data.

    ``key`` overrides ``seed`` when given (drivers pass explicit PRNG
    keys; service clients usually just pick an integer seed).
    ``obj_weights`` switches the request to the exponent-weighted
    objective; otherwise ``objective`` must be one of
    ``objectives.OBJECTIVES``.

    ``priority`` and ``deadline_s`` are *scheduling metadata*, consumed
    only by ``plan_batch``'s policy layer (and the service front ends):
    priority 0 is the most urgent (larger = less urgent) and
    ``deadline_s`` is seconds-from-submit (the service converts it to an
    absolute clock deadline at ingest).  Neither enters ``signature()``
    — scheduling can never change which compiled program a request hits."""

    ws: WorkloadSet
    objective: str = "ela"
    obj_weights: Optional[Tuple[float, ...]] = None
    area_constr: float = 150.0
    seed: int = 0
    key: Optional[jax.Array] = None
    backend: str = "jnp"
    pop_size: int = 40
    generations: int = 10
    top_k: int = 10
    # objective="pareto" only: how many front members the result returns
    # (crowded order; large enough k covers the whole first front).  Not
    # part of signature() — like top_k it never changes the compiled
    # program — but request_key/plan_key hash it, so cached fronts of
    # different widths can never collide.
    pareto_k: int = 10
    tech: TechParams = TECH
    init_genomes: Optional[Any] = None  # (pop_size, n); never consumed
    priority: int = 0  # 0 = most urgent; scheduling-only, not traced
    deadline_s: Optional[float] = None  # seconds from submit; scheduling-only

    def prng_key(self) -> jax.Array:
        return self.key if self.key is not None else jax.random.PRNGKey(self.seed)

    def key_data(self) -> np.ndarray:
        """``np.asarray(prng_key())``, with no device work for a seed.

        An explicit ``key`` is read back.  Under the default
        ``threefry2x32`` implementation with x64 off, JAX 0.9 keys an
        integer seed in int64 range as ``uint32[0, seed mod 2**32]``,
        built here on the host (tests/test_engine.py pins it over edge
        seeds).  Any other implementation, x64 setting or seed falls back
        to ``PRNGKey`` itself, so the bytes never rest on the formula
        where it was not checked."""
        if self.key is not None:
            return np.asarray(self.key)
        seed = self.seed
        if (isinstance(seed, (int, np.integer))
                and -2**63 <= int(seed) < 2**63
                and jax.config.jax_default_prng_impl == "threefry2x32"
                and not jax.config.jax_enable_x64):
            return np.array([0, int(seed) % 2**32], np.uint32)
        return np.asarray(jax.random.PRNGKey(seed))

    def signature(self) -> tuple:
        """Traced-shape signature: requests with equal signatures run in
        ONE compiled program.  The ``table`` backend reduced the layer
        axis away, so its signature carries no workload shape at all —
        any mix of workload sets packs together; dense backends group by
        their exact (W, L).  ``top_k`` and ``init_genomes`` are host-side
        / data-only and deliberately absent."""
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {self.backend!r}")
        if self.objective == PARETO:
            if self.obj_weights is not None:
                raise ValueError("objective='pareto' is incompatible with obj_weights")
            if int(self.pareto_k) < 1:
                raise ValueError(f"pareto_k must be >= 1, got {self.pareto_k!r}")
            obj = ("pareto",)
        elif self.obj_weights is not None:
            obj = ("weighted", float(self.area_constr))
        elif self.objective not in OBJECTIVE_INDEX:
            raise ValueError(
                f"objective must be one of {tuple(OBJECTIVE_INDEX)} or "
                f"{PARETO!r} (or pass obj_weights), got {self.objective!r}"
            )
        else:
            obj = ("indexed",)
        shape = (
            () if self.backend == "table"
            else (int(self.ws.feats.shape[0]), int(self.ws.feats.shape[1]))
        )
        return (self.backend, int(self.pop_size), int(self.generations),
                self.tech, shape, obj)


_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(k0, k1, x0, x1):
    """JAX's 20-round ``threefry2x32`` hash in numpy: uint32 arrays
    ``(x0, x1)`` under key ``(k0, k1)``, broadcast together (array
    arithmetic wraps mod 2**32 without a warning)."""
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in _THREEFRY_ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = x0 ^ ((x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r)))
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


# the split where the host formula does not hold: traced once per shape
_device_split = jax.jit(jax.vmap(jax.random.split))


def split_key_data(data: np.ndarray) -> Tuple[Any, bool]:
    """``jax.vmap(jax.random.split)`` of stacked ``(S, 2)`` key data, as
    ``(split, on_host)`` with ``split`` of shape ``(S, 2, 2)``.

    Under the default ``threefry2x32`` implementation with
    ``jax_threefry_partitionable`` on (JAX 0.9's defaults)
    ``split(key)[i]`` is ``threefry2x32(key, (0, i))``, computed here in
    numpy with no device op (tests/test_engine.py pins it bit for bit).
    Any other implementation or setting, or key data that is not raw
    uint32 pairs, takes the jitted device split, so the bits never rest
    on the formula where it was not checked."""
    if (data.dtype == np.uint32 and data.ndim == 2 and data.shape[1] == 2
            and jax.config.jax_default_prng_impl == "threefry2x32"
            and jax.config.jax_threefry_partitionable):
        lo = np.arange(2, dtype=np.uint32)
        bits = threefry2x32(data[:, :1], data[:, 1:], np.zeros_like(lo), lo)
        return np.stack(bits, axis=-1), True
    return _device_split(jnp.asarray(data)), False


@dataclasses.dataclass
class BatchPlan:
    """One XLA launch: ``len(requests)`` real searches slot-packed into
    ``slots`` program rows (trailing pad rows repeat the first request and
    are dropped on the host).  ``pad_w``/``pad_l`` are the group-wide
    padded workload-tensor shape, shared by every chunk of the group so
    they all hit the same compiled program."""

    signature: tuple
    requests: List[SearchRequest]
    indices: List[int]  # positions in the submitted request list
    slots: int
    pad_w: int
    pad_l: int
    # the recorder's id of the launch that last ran this plan (set by
    # ``SearchEngine.dispatch``; ``utils.spans``)
    launch: Optional[int] = None


def plan_key(plan: BatchPlan) -> str:
    """Content hash of everything that determines a plan's GA trajectory
    (workload fingerprints, objective, area, tech constants, PRNG keys,
    GA params, slot shape).  Stable across processes — the checkpoint
    directory name, so a killed drain's restart finds its own saved
    state.  ``tech`` MUST be in the hash: it parameterizes the whole
    cost model, so two otherwise-identical plans under different
    ``TechParams`` follow different GA trajectories — omitting it lets a
    resume silently restore a foreign tech's state (regression-pinned in
    tests/test_result_cache.py)."""
    h = hashlib.sha256()
    for r in plan.requests:
        h.update(r.ws.fingerprint().encode())
        h.update(repr((
            r.objective, r.obj_weights, float(r.area_constr), r.backend,
            int(r.pop_size), int(r.generations), int(r.top_k),
            int(r.pareto_k), r.tech,
        )).encode())
        h.update(r.key_data().tobytes())
    h.update(repr((int(plan.slots), int(plan.pad_w), int(plan.pad_l))).encode())
    # the grid is a trace-time constant of every program in the plan: a
    # densified space follows a different trajectory from the same requests
    h.update(space.grid_token().encode())
    return h.hexdigest()[:24]


# ------------------------------------------------------ scheduling policy
@dataclasses.dataclass(frozen=True)
class RequestMeta:
    """Scheduling facts the policies key on, per queued request.

    ``seq`` is the submit order (the FIFO key and the universal
    tiebreak), ``wait_s`` how long the request has been queued (feeds
    priority aging), ``deadline_s`` the ABSOLUTE deadline on the
    scheduler's clock (``None`` = none).  ``plan_batch`` synthesizes
    defaults (seq = list position, wait 0, ``SearchRequest.deadline_s``
    read as absolute-from-0) when the caller has no queue state, so
    driver-path plans stay pure functions of the request list."""

    seq: int
    priority: int = 0
    wait_s: float = 0.0
    deadline_s: Optional[float] = None


class SchedulingPolicy:
    """Maps a queued request to a sortable urgency key (lower = sooner).

    The planner stable-sorts the queue by ``key`` before grouping, so a
    policy controls both which requests share a chunk and which chunk
    launches first — while chunking itself (fixed ``slots`` per
    signature group, padded tail) is untouched: policies can never
    change which compiled program a request hits, only when it runs."""

    name = "fifo"

    def key(self, req: SearchRequest, meta: RequestMeta) -> tuple:
        return (meta.seq,)


class PriorityPolicy(SchedulingPolicy):
    """Strict priority (0 = most urgent) with optional aging: a request
    waiting ``aging_s`` seconds gains one priority level, so any finite
    priority eventually reaches 0 and launches — the starvation-freedom
    knob the scheduler sim pins.  ``aging_s=None`` disables aging
    (pure strict priority; can starve under a hot higher-priority
    stream)."""

    name = "priority"

    def __init__(self, aging_s: Optional[float] = 30.0):
        if aging_s is not None and aging_s <= 0:
            raise ValueError(f"aging_s must be positive or None, got {aging_s}")
        self.aging_s = aging_s

    def key(self, req: SearchRequest, meta: RequestMeta) -> tuple:
        p = float(meta.priority)
        if self.aging_s is not None:
            p -= meta.wait_s / self.aging_s
        return (p, meta.seq)


class EDFPolicy(SchedulingPolicy):
    """Earliest-deadline-first: absolute deadline, then submit order;
    deadline-less requests run after every deadlined one."""

    name = "edf"

    def key(self, req: SearchRequest, meta: RequestMeta) -> tuple:
        d = float("inf") if meta.deadline_s is None else float(meta.deadline_s)
        return (d, meta.seq)


POLICIES = {"fifo": SchedulingPolicy, "priority": PriorityPolicy, "edf": EDFPolicy}


def get_policy(policy) -> SchedulingPolicy:
    """Accepts a policy name or an already-built ``SchedulingPolicy``."""
    if isinstance(policy, SchedulingPolicy):
        return policy
    cls = POLICIES.get(policy)
    if cls is None:
        raise ValueError(f"policy must be one of {tuple(POLICIES)} or a "
                         f"SchedulingPolicy, got {policy!r}")
    return cls()


def plan_batch(
    requests: Sequence[SearchRequest],
    *,
    max_slots: int = 64,
    policy="fifo",
    meta: Optional[Sequence[RequestMeta]] = None,
    slot_hints: Optional[Dict[tuple, int]] = None,
) -> List[BatchPlan]:
    """Group heterogeneous requests by signature and slot-pack each group,
    ordered by the scheduling policy.

    Packing: a group of ``total`` requests runs in chunks of
    ``slots = min(total, max_slots)`` — a single exact-size launch when it
    fits (no pad waste on the hot driver paths), fixed ``max_slots``-row
    chunks when it doesn't (the last chunk padded), so a 256-request drain
    is 4 launches of ONE compiled program.  ``slot_hints`` (signature ->
    previously-used slot count) rounds a smaller group UP to a known-warm
    program size instead of compiling an exact-size one — the service's
    fixed-slot steady state; hints never shrink a chunk below its natural
    size.

    Policy (fifo / priority / edf, or any ``SchedulingPolicy``): the
    queue is stable-sorted by urgency key before grouping, members of a
    chunk are key-ordered, and the emitted plan list is key-ordered by
    each plan's most urgent member — so ``plans[0]`` is always the launch
    the policy wants next.  One fairness caveat is inherent to
    slot-packing: a less urgent request that shares a signature with an
    urgent one may ride along in its chunk (free slots cost nothing),
    so cross-GROUP order is policy order, within-chunk admission is
    policy order + free capacity.  ``meta`` (per-request queue facts:
    submit order, wait, absolute deadline) comes from the service; bare
    calls synthesize it from the request fields."""
    pol = get_policy(policy)
    if meta is None:
        meta = [
            RequestMeta(seq=i, priority=int(r.priority), wait_s=0.0,
                        deadline_s=r.deadline_s)
            for i, r in enumerate(requests)
        ]
    keys = [pol.key(r, m) for r, m in zip(requests, meta)]
    order = sorted(range(len(requests)), key=keys.__getitem__)
    groups: Dict[tuple, List[int]] = {}
    for i in order:
        groups.setdefault(requests[i].signature(), []).append(i)
    plans: List[BatchPlan] = []
    for sig, idxs in groups.items():
        reqs = [requests[i] for i in idxs]
        pad_w = max(int(r.ws.feats.shape[0]) for r in reqs)
        pad_l = max(int(r.ws.feats.shape[1]) for r in reqs)
        slots = min(len(idxs), int(max_slots))
        hint = (slot_hints or {}).get(sig)
        if hint is not None and slots < hint <= int(max_slots):
            slots = hint  # round up to the warm program size, never down
        for lo in range(0, len(idxs), slots):
            plans.append(BatchPlan(
                signature=sig,
                requests=reqs[lo:lo + slots],
                indices=idxs[lo:lo + slots],
                slots=slots,
                pad_w=pad_w,
                pad_l=pad_l,
            ))
    # most urgent plan first: group members are key-sorted, so a plan's
    # urgency is its first member's key
    plans.sort(key=lambda p: keys[p.indices[0]])
    return plans


# ----------------------------------------------------------------- engine
@dataclasses.dataclass
class _LaunchPrep:
    """Everything ``execute`` computes before the GA launch, shared by the
    single-shot and segmented paths so both trace identical operands."""

    packed: List[SearchRequest]
    place: Callable
    k_ga: Any
    init: Any
    ctx: tuple
    eval_fn: Callable
    # the seed-feasibility check (``_init_populations``): returns the
    # seeded slots and their rounds.  Pipelined dispatch defers its read
    # to harvest time, since syncing the seeder's counts would serialize
    # back-to-back dispatches; otherwise it ran eagerly and returns what
    # it read then.  None when no slot was seeded.
    seed_check: Optional[Callable] = None


@dataclasses.dataclass
class PendingLaunch:
    """A dispatched-but-not-harvested plan: the handle ``dispatch``
    returns and ``harvest`` consumes.  Exactly one of the payload fields
    is set — ``thin`` (un-synced device ``GAThin``, pipelined single-shot
    and segmented finals), ``ga`` (un-synced device ``GAResult``,
    sequential single-shot), or ``results`` (already-finalized host
    results, sequential segmented — that path syncs per segment anyway).
    Holding the device arrays here WITHOUT ``np.asarray`` is what lets
    chunk i's host finalize overlap chunk i+1's device compute."""

    plan: BatchPlan
    thin: Optional[GAThin] = None
    ga: Optional[GAResult] = None
    results: Optional[List[SearchResult]] = None
    # pareto plans: (genomes_hist, objs_hist, ParetoThin) un-synced device
    # arrays; the history pair is (None, None) when pipelined (thin-only)
    pareto: Optional[tuple] = None
    seed_check: Optional[Callable] = None


class SearchEngine:
    """Executes batch plans as cached one-jit GA programs.

    Stateless apart from caches: the compiled programs live in the global
    jit caches (keyed by the plan signature's static half + traced
    shapes), and padded table slices are cached per
    ``(WorkloadSet.fingerprint(), tech, pad_w)`` — re-packed identical
    workload sets hit both.  ``mesh`` (``launch.mesh.make_search_mesh``)
    lays every launch out over the 2-D (search, population) device mesh
    via ``core.distributed.place_batched``; scores are bit-identical with
    or without it.

    Robustness knobs (all off by default — the single-shot path is
    byte-for-byte the original engine):

      * ``segment_gens``    — run each plan as ceil(G / k) segment
        launches of k generations through ``core.ga.run_ga_segment``
        (bit-identical to the single launch), with a NaN score guard
        after every segment.
      * ``segment_retries`` — how many times a failed/NaN segment is
        re-launched from the last good ``GAState`` before the plan gives
        up with an ``EngineFault`` carrying anytime partial results.
      * ``checkpoint_dir``  — persist the ``GAState`` + history every
        ``checkpoint_every`` segments under ``checkpoint_dir/<plan_key>``
        (atomic ``checkpoint.store``); a re-executed identical plan
        resumes from the newest committed step, and a completed plan
        clears its own directory.
      * ``result_cache``    — a ``serve.cache.ResultCache`` (or anything
        with its ``get(req)/put(req, res)`` shape): every completed
        request persists its finalized ``SearchResult`` keyed on its OWN
        content (``serve.cache.request_key`` — independent of
        chunk-mates and slot shape, unlike ``plan_key``), and ``run()``
        resolves cached requests without planning them — zero GA
        launches on a full hit.
      * ``pipelined``       — the transfer-thin fast path: the top-k
        selection and convergence curve are computed ON DEVICE by the
        thin epilogue fused onto the GA program, so a launch syncs
        (S, K, n) genomes + (S, K) scores + (S, G+1) convergence instead
        of the full (S, G+1, P, n) history, and ``run()`` dispatches
        every chunk before harvesting the first (a pipelined service
        drain keeps one launch in flight), so each chunk's host finalize
        overlaps the remaining device compute.  Result fields are
        bit-identical to the sequential path (tests/test_pipelined.py)
        EXCEPT ``SearchResult.ga`` is ``None``.
        Thin FULL results are still result-cacheable — ``ResultCache``
        round-trips ``ga=None`` entries (only ``partial=True`` results
        are refused), so ``pipelined=True`` + ``result_cache`` resolves
        a resubmitted drain with zero GA launches; fault partials /
        checkpoints stay full-history and bit-identical either way.

    ``transfer_bytes`` / ``syncs`` / ``launches`` count device->host
    bytes, blocking device->host reads and plan launches since
    construction (or ``reset_transfer_stats()``) — the benches record
    bytes/launch from them.  Every ``dispatch`` and ``harvest`` records
    its spans (``utils.spans``: ``dse.dispatch``, ``dse.harvest`` and
    their phases) under the launch id it stamps on the plan and on the
    ``PendingLaunch``.
    """

    def __init__(self, *, mesh=None, max_slots: int = 64,
                 segment_gens: Optional[int] = None, segment_retries: int = 1,
                 checkpoint_dir: Optional[str] = None, checkpoint_every: int = 1,
                 result_cache=None, direct_seed: bool = False,
                 pipelined: bool = False):
        self.mesh = mesh
        self.max_slots = int(max_slots)
        # direct_seed: table-backend-only inverse-CDF seeding (no rejection
        # rounds).  Same validity guarantees, DIFFERENT seed pools than the
        # rejection sampler, so it is opt-in: the default keeps every
        # backend on the shared rejection program (table-vs-dense
        # trajectory closeness in tests/test_tables.py depends on that).
        self.direct_seed = bool(direct_seed)
        # pipelined: thin on-device epilogue + overlapped dispatch/harvest
        # (bit-identical results with ga=None — see the class docstring)
        self.pipelined = bool(pipelined)
        # device->host transfer telemetry, read by the benches/service
        self.transfer_bytes = 0
        self.syncs = 0
        self.launches = 0
        self.segment_gens = None if segment_gens is None else int(segment_gens)
        self.segment_retries = int(segment_retries)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.result_cache = result_cache
        self._padded_tables: Dict[tuple, tuple] = {}
        # slot-packed device tensors keyed on the packed content
        # (per-slot workload fingerprints + padded shape): a warm drain
        # over the same workload mix — every driver's steady state —
        # skips the host packing and transfer entirely
        self._packed_workloads: Dict[tuple, tuple] = {}
        self._stacked_tables: Dict[tuple, Any] = {}
        # direct-seeder feasible-cell CDFs: per-request host arrays and the
        # per-plan device stack (both content-keyed; see _request_seed_cdf)
        self._seed_cdfs: Dict[tuple, np.ndarray] = {}
        self._stacked_seed_cdfs: Dict[tuple, Any] = {}

    # ------------------------------------------------------------ planning
    def run(
        self, requests: Sequence[SearchRequest], *, mesh=None
    ) -> List[SearchResult]:
        """Plan + execute; results align with ``requests`` order.  With a
        ``result_cache``, cached requests resolve without entering a plan
        (their chunk-mates pack without them) and completed ones persist
        their entries — a repeated request list is zero launches."""
        out: List[Optional[SearchResult]] = [None] * len(requests)
        todo = list(range(len(requests)))
        if self.result_cache is not None:
            todo = []
            for i, r in enumerate(requests):
                hit = self.result_cache.get(r)
                if hit is not None:
                    out[i] = hit
                else:
                    todo.append(i)
        plans = plan_batch([requests[i] for i in todo],
                           max_slots=self.max_slots)
        if self.pipelined:
            # dispatch every chunk back-to-back (JAX async dispatch: the
            # launches queue without a host sync), then harvest in order —
            # chunk i's host finalize overlaps chunk i+1's device compute
            pending = [self.dispatch(p, mesh=mesh) for p in plans]
            for plan, pend in zip(plans, pending):
                for i, res in zip(plan.indices, self.harvest(pend)):
                    out[todo[i]] = res
        else:
            for plan in plans:
                for i, res in zip(plan.indices, self.execute(plan, mesh=mesh)):
                    out[todo[i]] = res
        return out  # type: ignore[return-value]

    def reset_transfer_stats(self) -> None:
        self.transfer_bytes = 0
        self.syncs = 0
        self.launches = 0

    def _sync(self, x) -> np.ndarray:
        """The engine's device->host sync point: every blocking read of a
        launch's explicit keys, outputs and seed counts goes through here
        (or, for the segmented path's NaN guard, ``_any_nan``) so
        ``transfer_bytes`` and ``syncs`` stay exact counts of what crossed
        the wire and of the blocking reads.  A seed's key is built on the
        host and read nowhere; only ``plan_key`` (the checkpoint
        directory's name) reads explicit keys outside it."""
        a = np.asarray(x)
        self.transfer_bytes += a.nbytes
        self.syncs += 1
        return a

    def _any_nan(self, x) -> bool:
        """Whether device array ``x`` holds a NaN: one blocking one-byte
        read, counted like ``_sync`` (a ``bool`` conversion, so no
        ``np.asarray`` of the array itself)."""
        flag = jnp.isnan(x).any()
        self.transfer_bytes += flag.nbytes
        self.syncs += 1
        return bool(flag)

    # ----------------------------------------------------------- execution
    def _padded_request_tables(self, req: SearchRequest, pad_w: int):
        """Host-side table leaves of one request, zero-padded along W to
        the plan width.  Zero rows are exactly neutral: zero demand fits
        everywhere and the objective's max-reduction ignores zeros, so the
        padded slots cannot perturb real scores (tests/test_engine.py
        asserts bit-identity).  Keyed on the set's content fingerprint so
        re-packed identical sets reuse the same padded slices."""
        key = (req.ws.fingerprint(), req.tech, pad_w, space.grid_token())
        hit = self._padded_tables.get(key)
        if hit is None:
            leaves = [np.asarray(leaf) for leaf in req.ws.tables(req.tech)]
            extra = pad_w - leaves[0].shape[0]
            if extra:
                leaves = [
                    np.pad(leaf, [(0, extra)] + [(0, 0)] * (leaf.ndim - 1))
                    for leaf in leaves
                ]
            hit = self._padded_tables[key] = tuple(leaves)
        return hit

    def execute(self, plan: BatchPlan, *, mesh=None,
                on_progress: Optional[Callable[[int, SearchResult], None]] = None,
                ) -> List[SearchResult]:
        """One slot-packed XLA launch (or, with ``segment_gens``, a chain
        of guarded segment launches — same bits); returns results for the
        plan's REAL requests (pad slots dropped), in plan order.

        ``on_progress(i, partial)`` — called after every guarded segment
        with the plan-local request index and a monotone best-so-far
        snapshot (``SearchResult`` with ``partial=True``, finalized from
        the history accumulated so far).  Only the segmented path has
        mid-search boundaries to report from; the single-shot path never
        calls it.  Completed requests persist into ``result_cache``."""
        return self.harvest(self.dispatch(plan, mesh=mesh,
                                          on_progress=on_progress))

    def dispatch(self, plan: BatchPlan, *, mesh=None,
                 on_progress: Optional[Callable[[int, SearchResult], None]] = None,
                 ) -> PendingLaunch:
        """Launch a plan WITHOUT syncing its outputs to host: the GA (and,
        when ``pipelined``, the thin epilogue) is enqueued and the device
        arrays ride back inside a ``PendingLaunch`` for a later
        ``harvest``.  Dispatching several plans back-to-back queues their
        programs on the device, so the harvests' host work overlaps the
        remaining device compute.  The segmented path runs its guarded
        segment chain here (it is a synchronous loop by construction) but
        still defers its final sync/finalize to ``harvest``.

        Records a ``dse.dispatch`` span under a fresh launch id, set on
        ``plan.launch``."""
        launch = plan.launch = spans.new_launch()
        r0 = plan.requests[0]
        s0 = self.syncs
        with spans.span("dse.dispatch", launch=launch, slots=plan.slots,
                        reqs=len(plan.requests), P=int(r0.pop_size),
                        G=int(r0.generations),
                        W=sum(r.ws.n for r in plan.requests)) as sp:
            pend = self._dispatch_plan(plan, mesh, on_progress)
            sp.set(syncs=self.syncs - s0)
        return pend

    def _dispatch_plan(self, plan: BatchPlan, mesh, on_progress
                       ) -> PendingLaunch:
        mesh = self.mesh if mesh is None else mesh
        r0 = plan.requests[0]
        if r0.objective == PARETO and r0.obj_weights is None:
            # Pareto plans always run single-shot: NSGA-II survival carries
            # an (objs, sel) state the segmented GAState does not model, so
            # segment_gens/checkpointing do not apply to this family.  Both
            # engine modes run the SAME fused device epilogue — front
            # selection is bit-identical across sequential/pipelined by
            # construction; sequential additionally keeps the history.
            prep = self._prepare(plan, mesh, defer_seed=self.pipelined)
            self.launches += 1
            kw = dict(pop_size=r0.pop_size, generations=r0.generations,
                      init_genomes=prep.init, ctx=prep.ctx,
                      top_k=max(int(r.pareto_k) for r in plan.requests))
            with spans.span("dse.dispatch.ga"):
                if self.pipelined:
                    out = (None, None,
                           run_pareto_batched(prep.k_ga, prep.eval_fn, **kw))
                else:
                    out = run_pareto_batched(prep.k_ga, prep.eval_fn,
                                             history=True, **kw)
            return PendingLaunch(plan=plan, pareto=out,
                                 seed_check=prep.seed_check)
        k = self.segment_gens
        if k is not None and 0 < k < int(r0.generations):
            return self._dispatch_segmented(plan, mesh, k,
                                            on_progress=on_progress)
        prep = self._prepare(plan, mesh, defer_seed=self.pipelined)
        self.launches += 1
        kw = dict(pop_size=r0.pop_size, generations=r0.generations,
                  init_genomes=prep.init, ctx=prep.ctx)
        with spans.span("dse.dispatch.ga"):
            if self.pipelined:
                thin = run_ga_batched_thin(
                    prep.k_ga, prep.eval_fn,
                    top_k=max(int(r.top_k) for r in plan.requests), **kw)
                return PendingLaunch(plan=plan, thin=thin,
                                     seed_check=prep.seed_check)
            ga = run_ga_batched(prep.k_ga, prep.eval_fn, **kw)
        return PendingLaunch(plan=plan, ga=ga, seed_check=prep.seed_check)

    def harvest(self, pending: PendingLaunch) -> List[SearchResult]:
        """Sync a dispatched plan's (small) outputs, finalize, and persist
        completed results into the cache — the host half of ``execute``.
        Records a ``dse.harvest`` span under the launch's id: the wait for
        the outputs, the reads (and the deferred seed check), the
        finalize; ``seed_slots`` and ``seed_rounds`` count the slots the
        seeder filled and the rounds they drew."""
        reqs = pending.plan.requests
        s0, b0 = self.syncs, self.transfer_bytes
        with spans.span("dse.harvest", launch=pending.plan.launch) as sp:
            with spans.span("dse.harvest.wait"):
                jax.block_until_ready(
                    [x for x in (pending.thin, pending.ga, pending.pareto)
                     if x is not None])
            with spans.span("dse.harvest.sync"):
                if pending.seed_check is not None:
                    seed_slots, seed_rounds = pending.seed_check()
                    sp.set(seed_slots=seed_slots, seed_rounds=seed_rounds)
                if pending.results is not None:
                    finalize = lambda: pending.results  # noqa: E731
                elif pending.pareto is not None:
                    gh, oh, thin = pending.pareto
                    thin_np = ParetoThin(*(self._sync(f) for f in thin))
                    history = None
                    if gh is not None:
                        history = (self._sync(gh), self._sync(oh))
                    finalize = partial(_finalize_batch_pareto, thin_np, reqs,
                                       history=history)
                elif pending.thin is not None:
                    thin_np = GAThin(*(self._sync(f) for f in pending.thin))
                    finalize = partial(_finalize_batch_thin, thin_np, reqs)
                else:
                    # one device->host transfer per field, then numpy prep
                    ga_np = GAResult(*(self._sync(f) for f in pending.ga))
                    finalize = partial(_finalize_batch, ga_np, reqs)
            with spans.span("dse.harvest.finalize"):
                results = finalize()
                self._cache_completed(pending.plan, results)
            sp.set(syncs=self.syncs - s0, bytes=self.transfer_bytes - b0)
        return results

    def _cache_completed(self, plan: BatchPlan,
                         results: Sequence[SearchResult]) -> None:
        """Persist each finished request's result under its own content
        key — per-request, so a future submission hits regardless of
        which chunk-mates it packed with this time."""
        if self.result_cache is not None:
            for r, res in zip(plan.requests, results):
                self.result_cache.put(r, res)

    def _prepare(self, plan: BatchPlan, mesh,
                 defer_seed: bool = False) -> _LaunchPrep:
        """Pack, place and seed a plan up to (but not including) the GA
        launch.  Shared verbatim by both execution paths.  With
        ``defer_seed`` the seeder's feasibility counts are NOT synced
        here — the returned ``seed_check`` raises at harvest time instead
        — so back-to-back pipelined dispatches never block on device."""
        reqs = plan.requests
        r0 = reqs[0]
        backend, tech = r0.backend, r0.tech
        packed = list(reqs) + [r0] * (plan.slots - len(reqs))

        if mesh is None:
            place = lambda x, **_: x  # noqa: E731 — identity placement
        else:
            from repro.core.distributed import place_batched

            place = partial(place_batched, mesh)

        with spans.span("dse.dispatch.pack") as sp:
            ctx, feats, mask, tables, hit = self._pack(plan, packed, place)
            sp.set(hit=hit)
            # objective tail: pareto's traced area, traced exponent
            # weights, or traced (kind, area)
            if r0.objective == PARETO and r0.obj_weights is None:
                areas = jnp.asarray([r.area_constr for r in packed],
                                    jnp.float32)
                ctx = ctx + (place(areas),)
                eval_fn = _ctx_eval(PARETO, 0.0, tech, backend)
            elif r0.obj_weights is not None:
                w = jnp.asarray([r.obj_weights for r in packed], jnp.float32)
                ctx = ctx + (place(w),)
                eval_fn = _ctx_eval(None, float(r0.area_constr), tech, backend)
            else:
                codes = jnp.asarray(
                    [OBJECTIVE_INDEX[r.objective] for r in packed], jnp.int32
                )
                areas = jnp.asarray([r.area_constr for r in packed],
                                    jnp.float32)
                ctx = ctx + (place(codes), place(areas))
                eval_fn = _ctx_eval(INDEXED, 0.0, tech, backend)

        with spans.span("dse.dispatch.keys") as sp:
            # host-side stack and split, ONE device transfer per derived
            # key: a seed's key is built on the host (no device work, no
            # read, so the dispatch never waits on the device); an explicit
            # key is read back through ``_sync``
            ks, on_host = split_key_data(np.stack([
                r.key_data() if r.key is None else self._sync(r.key)
                for r in packed]))  # (S, 2, 2)
            sp.set(host_keys=sum(r.key is None for r in packed),
                   host_split=len(packed) if on_host else 0)
            # commit the derived keys: an uncommitted jit operand lets
            # GSPMD re-layout the whole program (bit-parity with the
            # meshless run requires the exact input placements the sharded
            # search always used); meshless, the programs get device
            # arrays, not numpy operands
            commit = jax.device_put if mesh is None else place
            k_seed, k_ga = commit(ks[:, 0]), commit(ks[:, 1])

        with spans.span("dse.dispatch.seed"):
            init, seed_check = self._init_populations(
                packed, k_seed, feats, mask, place, tables=tables,
                defer=defer_seed)

        return _LaunchPrep(packed=packed, place=place, k_ga=k_ga,
                           init=init, ctx=ctx, eval_fn=eval_fn,
                           seed_check=seed_check)

    def _pack(self, plan: BatchPlan, packed: List[SearchRequest], place):
        """The plan's workload operands: slot-packed (W, L)-padded feats
        and mask, and for the ``table`` backend the stacked per-request
        tables, each cached on content so warm drains skip the host pack
        and transfer.  Returns ``(ctx, feats, mask, tables, hit)``, ``hit``
        when every cache consulted held the plan's operands."""
        r0 = packed[0]
        S, W, L = plan.slots, plan.pad_w, plan.pad_l
        fps = tuple(r.ws.fingerprint() for r in packed)
        hit = self._packed_workloads.get((fps, W, L))
        all_hit = hit is not None
        if hit is None:
            feats = np.zeros((S, W, L, 6), np.float32)
            mask = np.zeros((S, W, L), bool)
            for i, r in enumerate(packed):
                w, l = r.ws.feats.shape[:2]
                feats[i, :w, :l] = np.asarray(r.ws.feats)
                mask[i, :w, :l] = np.asarray(r.ws.mask)
            hit = (jnp.asarray(feats), jnp.asarray(mask))
            self._packed_workloads[(fps, W, L)] = hit
        feats, mask = place(hit[0]), place(hit[1])
        if r0.backend != "table":
            return (feats, mask), feats, mask, None, all_hit
        # factorized tables, stacked per request — the SAME arrays
        # run_search would trace, so parity is exact.  Built BEFORE
        # seeding: the direct table seeder samples straight from the
        # stacked demand table.
        from repro.imc.tables import WorkloadTables

        key = (fps, W, r0.tech, space.grid_token())
        tables = self._stacked_tables.get(key)
        if tables is None:
            all_hit = False
            per_req = [self._padded_request_tables(r, W) for r in packed]
            tables = WorkloadTables(*(
                jnp.asarray(np.stack([t[f] for t in per_req]))
                for f in range(len(per_req[0]))
            ))
            self._stacked_tables[key] = tables
        tables = jax.tree_util.tree_map(place, tables)
        return (tables,), feats, mask, tables, all_hit

    # ------------------------------------------------- segmented execution
    def _place_state(self, state: GAState, place) -> GAState:
        """Commit a (possibly host-restored) batched state to the mesh
        layout the GA programs expect (identity when meshless)."""
        return GAState(
            genomes=place(jnp.asarray(state.genomes), pop_dim=1),
            scores=place(jnp.asarray(state.scores), pop_dim=1),
            key=place(jnp.asarray(state.key)),
            gen=place(jnp.asarray(state.gen)),
        )

    def _ckpt_dir(self, plan: BatchPlan) -> Optional[Path]:
        if self.checkpoint_dir is None:
            return None
        return Path(self.checkpoint_dir) / plan_key(plan)

    def _partial_results(
        self, plan: BatchPlan, gh: Optional[np.ndarray], sh: Optional[np.ndarray],
    ) -> List[Optional[SearchResult]]:
        """Anytime results from the accumulated (S, g+1, P, n) history —
        ``None`` per request when nothing was ever evaluated."""
        if gh is None:
            return [None] * len(plan.requests)
        out = []
        for i, r in enumerate(plan.requests):
            ga_i = self._history_result(gh[i], sh[i])
            out.append(_finalize(ga_i, r.ws.names, _objective_label(r),
                                 r.top_k, partial=True))
        return out

    @staticmethod
    def _history_result(gh_i: np.ndarray, sh_i: np.ndarray) -> GAResult:
        """A host-side ``GAResult`` over one slot's (g+1, P, ·) history;
        ``np.argmin`` picks the first minimum exactly like the in-jit
        ``jnp.argmin`` of the single-shot program."""
        n = gh_i.shape[-1]
        flat_s = sh_i.reshape(-1)
        b = int(np.argmin(flat_s)) if flat_s.size else 0
        return GAResult(
            genomes=gh_i, scores=sh_i,
            best_genome=gh_i.reshape(-1, n)[b] if flat_s.size else np.zeros(n),
            best_score=flat_s[b] if flat_s.size else np.float32(np.inf),
        )

    def _dispatch_segmented(
        self, plan: BatchPlan, mesh, seg: int,
        on_progress: Optional[Callable[[int, SearchResult], None]] = None,
    ) -> PendingLaunch:
        """Advance the plan ``seg`` generations per launch with a NaN
        score guard, retry-from-last-good-state, and optional on-disk
        checkpoints.  The chained segments are bit-identical to the
        single launch (tests/test_ga_segments.py).  After every good
        segment, ``on_progress`` (if given) receives each request's
        best-so-far snapshot — finalized from the same accumulated
        history the fault/deadline partials use, so the streamed best is
        monotone non-increasing and exactly the history minimum.

        The generation counter is derived HOST-side: 0 for a fresh init,
        or the restored checkpoint's (host numpy) ``state.gen`` — the
        warm loop never syncs the device counter.

        ``pipelined`` keeps the accumulated history ON DEVICE: the guard
        blocks on a 1-byte NaN scalar instead of the full per-segment
        history, ``on_progress`` snapshots flow through the thin epilogue
        (``ga_epilogue_batched``), and the final epilogue is dispatched
        un-synced for ``harvest``.  Checkpoints and fault partials still
        sync the FULL history at their (cold) boundaries, so both stay
        bit-identical to the sequential path."""
        from repro.checkpoint import store

        reqs = plan.requests
        r0 = reqs[0]
        G = int(r0.generations)
        K = max(int(r.top_k) for r in reqs)
        thin = self.pipelined
        ck_dir = self._ckpt_dir(plan)

        state: Optional[GAState] = None
        done = 0
        # accumulated history, (S, done+1, P, n) / (S, done+1, P):
        # host numpy (sequential) or device arrays (pipelined)
        gh = sh = None
        if ck_dir is not None and store.latest_step(ck_dir) is not None:
            template = {"state": GAState(0, 0, 0, 0), "gh": 0, "sh": 0}
            tree, _ = store.restore(ck_dir, template)
            state = GAState(*tree["state"])
            # restored fields are host arrays — this int() never blocks
            done = int(np.asarray(state.gen).reshape(-1)[0])
            gh, sh = np.asarray(tree["gh"]), np.asarray(tree["sh"])
            if thin:
                gh, sh = jnp.asarray(gh), jnp.asarray(sh)

        def host_hist():
            if gh is None:
                return None, None
            if thin:
                return self._sync(gh), self._sync(sh)
            return gh, sh

        try:
            prep = self._prepare(plan, mesh)
            self.launches += 1
            if state is None:
                state = init_ga_state_batched(
                    prep.k_ga, prep.eval_fn, prep.init, ctx=prep.ctx
                )
                if thin:
                    if self._any_nan(state.scores):
                        raise NonFiniteScoreError(
                            "NaN scores in the seed evaluation"
                        )
                    gh = state.genomes[:, None]
                    sh = state.scores[:, None]
                else:
                    s0 = self._sync(state.scores)
                    if np.isnan(s0).any():
                        raise NonFiniteScoreError(
                            "NaN scores in the seed evaluation"
                        )
                    gh = self._sync(state.genomes)[:, None]
                    sh = s0[:, None]
        except EngineFault:
            raise
        except Exception as e:
            raise EngineFault(
                f"segmented launch setup failed: {e}",
                partials=self._partial_results(plan, *host_hist()),
            ) from e

        seg_idx = 0
        while done < G:
            k_gens = min(seg, G - done)
            state = self._place_state(state, prep.place)
            attempt = 0
            while True:
                try:
                    with spans.span("dse.dispatch.ga"):
                        new_state, (hg, hs) = run_ga_batched_segment(
                            state, prep.eval_fn, ctx=prep.ctx,
                            generations=k_gens, total_generations=G,
                        )
                    if thin:
                        # guard on ONE reduced byte; the history stays put
                        if self._any_nan(hs):
                            raise NonFiniteScoreError(
                                f"NaN scores in segment at generation {done}"
                            )
                    else:
                        hs_np = self._sync(hs)  # (S, k, P)
                        if np.isnan(hs_np).any():
                            raise NonFiniteScoreError(
                                f"NaN scores in segment at generation {done}"
                            )
                        hg_np = self._sync(hg)
                    break
                except Exception as e:
                    attempt += 1
                    if attempt > self.segment_retries:
                        raise EngineFault(
                            f"segment at generation {done} failed after "
                            f"{attempt} attempts: {e}",
                            partials=self._partial_results(plan, *host_hist()),
                            generations_done=done,
                        ) from e
                    # retry re-launches from the SAME (undonated) state
            if thin:
                gh = jnp.concatenate([gh, hg], axis=1)
                sh = jnp.concatenate([sh, hs], axis=1)
            else:
                gh = np.concatenate([gh, hg_np], axis=1)
                sh = np.concatenate([sh, hs_np], axis=1)
            state = new_state
            done += k_gens
            seg_idx += 1
            if (ck_dir is not None and done < G
                    and seg_idx % self.checkpoint_every == 0):
                host_state = GAState(*(self._sync(f) for f in state))
                hg_ck, hs_ck = host_hist()
                store.save(ck_dir, done,
                           {"state": host_state, "gh": hg_ck, "sh": hs_ck})
            if on_progress is not None and done < G:
                # mid-search anytime stream: best-so-far per request,
                # finalized over the history up to this boundary (the
                # final segment's snapshot IS the returned result)
                if thin:
                    snap = GAThin(*(self._sync(f) for f in
                                    ga_epilogue_batched(gh, sh, top_k=K)))
                    for i, res in enumerate(
                            _finalize_batch_thin(snap, reqs, partial=True)):
                        on_progress(i, res)
                else:
                    for i, r in enumerate(reqs):
                        on_progress(i, _finalize(
                            self._history_result(gh[i], sh[i]),
                            r.ws.names, _objective_label(r), r.top_k,
                            partial=True,
                        ))

        if ck_dir is not None:
            store.clear(ck_dir)
        if thin:
            # final epilogue rides back un-synced; harvest does the rest
            return PendingLaunch(
                plan=plan, thin=ga_epilogue_batched(gh, sh, top_k=K),
                seed_check=prep.seed_check)
        results = [
            _finalize(
                self._history_result(gh[i], sh[i]),
                r.ws.names, _objective_label(r), r.top_k,
            )
            for i, r in enumerate(reqs)
        ]
        return PendingLaunch(plan=plan, results=results,
                             seed_check=prep.seed_check)

    def _request_seed_cdf(self, req: SearchRequest) -> np.ndarray:
        """One request's feasible-cell CDF for the direct seeder (host
        numpy, largest workload — the same crossbar-demand ``argmax`` rule
        as ``largest_workload_index``, mirrored in numpy).  Content-keyed
        like the padded tables: the 12ms-class 6-D mask + prefix-sum runs
        once per (workload set, tech, grid) and never on the warm path."""
        key = (req.ws.fingerprint(), req.tech, space.grid_token())
        hit = self._seed_cdfs.get(key)
        if hit is None:
            feats = np.asarray(req.ws.feats, np.float32)
            mask = np.asarray(req.ws.mask, bool)
            w = (feats[..., 1] * feats[..., 2] * feats[..., 5] * mask).sum(-1)
            demand = np.asarray(req.ws.tables(req.tech).demand)
            hit = self._seed_cdfs[key] = _seed_cells_cdf(
                demand[int(np.argmax(w))]
            )
        return hit

    def _stacked_seed_cdf(self, packed, tech):
        """(S, n_cells) device stack of the per-slot seed CDFs, cached on
        the packed fingerprints — a warm drain reuses the device array."""
        fps = tuple(r.ws.fingerprint() for r in packed)
        key = (fps, tech, space.grid_token())
        hit = self._stacked_seed_cdfs.get(key)
        if hit is None:
            hit = jnp.asarray(
                np.stack([self._request_seed_cdf(r) for r in packed])
            )
            self._stacked_seed_cdfs[key] = hit
        return hit

    def _init_populations(self, packed, k_seed, feats, mask, place,
                          tables=None, defer=False):
        """Initial populations for every slot: provided ``init_genomes``
        are copied in (the GA donates its input; callers keep theirs),
        missing ones run the batched largest-workload rejection seeder —
        one program either way, and seed failures only raise for slots
        that actually needed seeding.  With ``direct_seed`` and stacked
        tables at hand, the rejection rounds are replaced by the direct
        feasible-cell sampler (``_seed_direct``).

        Returns ``(init, check)``: ``check`` is ``None`` when no slot was
        seeded, else a callable that verifies the seeding and returns
        ``(seeded slots, rounds they drew)``.  It reads the seeder's
        counts and rounds in one ``_sync`` on its first call and raises a
        ``RuntimeError`` for a slot short of ``pop_size``; later calls
        return what the first read.  It runs here, except with ``defer``
        (all-seeded slots only), where harvest runs it — the pipelined
        dispatch path's way of keeping the read off the critical host
        path."""
        r0 = packed[0]
        P = int(r0.pop_size)
        needs = [r.init_genomes is None for r in packed]
        if not any(needs):
            init = jnp.stack([jnp.asarray(r.init_genomes) for r in packed])
            return place(init, pop_dim=1), None
        if self.direct_seed and tables is not None:
            cdf6 = place(self._stacked_seed_cdf(packed, r0.tech))
            pools, seeded = _seed_direct_batched_jit(
                k_seed, cdf6, pop_size=P, tech=r0.tech,
            )
        else:
            pools, seeded = _seed_batched_jit(
                k_seed, feats, mask,
                pop_size=P, oversample=64, max_rounds=8, tech=r0.tech,
            )
        read: List[Tuple[int, int]] = []

        def check() -> Tuple[int, int]:
            if not read:
                counts, rounds = self._sync(seeded)
                for i, (r, need) in enumerate(zip(packed, needs)):
                    if need and counts[i] < P:
                        raise RuntimeError(
                            f"could not seed {P} valid designs for request "
                            f"{i} (workloads {r.ws.names}; {int(counts[i])} "
                            "found)"
                        )
                read.append((sum(needs), int(rounds[np.asarray(needs)].sum())))
            return read[0]

        if all(needs):
            if not defer:
                check()
            return place(pools, pop_dim=1), check
        check()  # the override merge below syncs the pools anyway
        pools = self._sync(pools).copy()  # writable, for the overrides
        for i, r in enumerate(packed):
            if r.init_genomes is not None:
                pools[i] = np.asarray(r.init_genomes)
        return place(jnp.asarray(pools), pop_dim=1), check


_DEFAULT_ENGINE: Optional[SearchEngine] = None


def default_engine() -> SearchEngine:
    """Shared engine behind the ``core.search`` driver wrappers."""
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        _DEFAULT_ENGINE = SearchEngine()
    return _DEFAULT_ENGINE
