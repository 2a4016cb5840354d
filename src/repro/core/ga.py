"""Genetic algorithm (paper Sec. III-C) as a single XLA program.

pymoo-equivalent operators [33][34]:
  * binary-tournament parent selection,
  * simulated binary crossover  (p_c = 0.95, eta = 3  — the paper's values,
    "prioritizing exploration"),
  * polynomial mutation         (p_m = 1/n_genes, eta = 3),
  * (mu + lambda) elitist survival,
with the whole G-generation loop under ``lax.scan`` and the population
evaluated by the vectorized IMC cost model.  Population history (every
sampled design + score, per generation) is returned, matching the paper's
"best set selected from the stored population history".

One jit covers the entire experiment, not just one generation:

  * ``run_ga``          — eval -> select -> SBX -> mutate -> survive for all
    G generations under a single cached, donated ``jax.jit``.  Workload
    tensors enter as the traced ``ctx`` argument, so searching a different
    workload set of the same shape reuses the compiled program — no
    per-seed / per-workload retraces.
  * ``run_ga_batched``  — the same program ``vmap``-ed over a leading batch
    axis (workloads for ``separate_search``, seeds for the multi-seed
    benchmark drivers): B independent GAs in ONE XLA launch.

The evaluation callback is a parameter, so the same GA drives joint
(multi-workload) and separate (single-workload) searches, and the
population axis can be sharded over the mesh (``repro.core.distributed``).

Anytime / segmented execution: the scan carry is also exposed as a
first-class ``GAState`` (population, scores, master rng key, generation
counter), with ``init_ga_state`` / ``run_ga_segment`` (and their batched
twins) advancing k generations per launch through one cached jit.  The
segment derives its per-generation keys by splitting the SAME master key
into the run's full ``total_generations`` keys (a static count) and
dynamic-slicing out its window, so N segments of k generations are
bit-identical to one ``run_ga`` of N*k — the parity is asserted in
tests/test_ga_segments.py and as a hypothesis property.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import space
from repro.core.objectives import pareto_scalar

SBX_PROB = 0.95
SBX_ETA = 3.0
MUT_ETA = 3.0


class GAResult(NamedTuple):
    genomes: jnp.ndarray  # (G+1, P, n) every generation incl. initial
    scores: jnp.ndarray  # (G+1, P)
    best_genome: jnp.ndarray  # (n,)
    best_score: jnp.ndarray  # ()


class GAState(NamedTuple):
    """The GA scan carry as a resumable value.  ``key`` is the MASTER run
    key (never advanced — segments index into ``split(key, total)`` by
    ``gen``), ``gen`` the number of generations already applied.  Batched
    variants carry a leading (B,) axis on every field."""

    genomes: jnp.ndarray  # (P, n) current population
    scores: jnp.ndarray  # (P,)
    key: jax.Array  # master PRNG key of the whole run
    gen: jnp.ndarray  # () int32, generations completed so far


class GAThin(NamedTuple):
    """The transfer-thin GA result: what the pipelined engine syncs to
    host instead of the full (G+1, P, n) history.  ``top_genomes`` /
    ``top_scores`` hold the best ``min(top_k, (G+1)*P)`` UNIQUE designs
    (uniqueness in decoded-grid-cell space, exactly like the host
    ``engine._top_unique``) best-first; slots past ``n_kept`` are padding
    (genome 0, score +inf).  ``convergence`` is the monotone best-so-far
    curve over generations.  Batched variants carry a leading (B,) axis."""

    top_genomes: jnp.ndarray  # (K, n) best-first unique designs
    top_scores: jnp.ndarray  # (K,)
    n_kept: jnp.ndarray  # () int32, valid entries in top_*
    convergence: jnp.ndarray  # (G+1,) running best score


class ParetoThin(NamedTuple):
    """The transfer-thin Pareto-front result: ``GAThin``'s twin for
    ``objective="pareto"`` requests.  ``top_genomes`` / ``top_vectors`` /
    ``top_scores`` hold the ``min(top_k, unique feasible cells)`` best
    front members in crowded order — ascending non-domination rank,
    descending crowding within a rank, flat history index as the final
    tie-break — deduped by decoded grid cell exactly like ``GAThin``.
    ``top_vectors`` carries each member's (max_W E, max_W L, A) triple;
    ``top_scores`` its scalar E*L*A proxy (bit-identical to the ``ela``
    objective on feasible rows).  ``convergence`` is the running best of
    that proxy.  Slots past ``n_kept`` are padding (genome 0, vector and
    score +inf).  Batched variants carry a leading (B,) axis."""

    top_genomes: jnp.ndarray  # (K, n) front members, crowded order
    top_vectors: jnp.ndarray  # (K, M) per-member (E, L, A)
    top_scores: jnp.ndarray  # (K,) scalar E*L*A proxy
    n_kept: jnp.ndarray  # () int32, valid entries in top_*
    convergence: jnp.ndarray  # (G+1,) running best scalar proxy


class _IgnoreCtx:
    """Adapt a ctx-less ``eval_fn(genomes)`` to the internal
    ``eval_fn(genomes, ctx)`` convention.  Hash/eq delegate to the wrapped
    callable so the cached jits below are NOT retraced when the same
    evaluation function is reused across calls."""

    __slots__ = ("fn",)

    def __init__(self, fn: Callable):
        self.fn = fn

    def __call__(self, genomes, ctx):
        return self.fn(genomes)

    def __hash__(self):
        return hash(self.fn)

    def __eq__(self, other):
        return isinstance(other, _IgnoreCtx) and self.fn == other.fn


def _survivor_indices(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """Indices of the k lowest scores, best-first: exactly the stable
    ascending ``jnp.argsort(scores)[:k]``, without its stability-tracking
    float comparator.  The reference permutation ``_elitist_survival``
    applies in one combined sort.

    Implementation: one ``lax.sort`` over an integer key pair — the
    float32 score mapped to its total-order int32 (negatives: descending
    magnitude; both zero signs collapse to 0, matching comparison sorts),
    tie-broken by the candidate index.  Semantics are EXACTLY stable
    ascending argsort, asserted adversarially (duplicates, +inf
    infeasibles, mixed zero signs) in tests/test_search_batched.py.

    Why not ``lax.top_k`` on the negated scores: top_k breaks ties by
    index in a single shard, but a GSPMD-sharded population merges
    per-shard top-k lists and the cross-shard tie order (every infeasible
    candidate scores exactly +inf, so ties are the norm) diverges from
    the unsharded program — which would break the stack's bit-identical
    sharded-parity guarantee (tests/test_search_sharded.py).  A
    collision-free int64 composite would fix that but int64 is
    unavailable without global x64.  The unique integer key pair keeps
    the sort shard-stable, branchless, and stability-free instead."""
    n = scores.shape[-1]
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    order = jnp.where(
        bits < 0,
        -(bits & jnp.int32(0x7FFFFFFF)),  # negative floats: -magnitude
        bits,
    )
    iota = jax.lax.iota(jnp.int32, n)
    _, idx = jax.lax.sort((order, iota), num_keys=2, is_stable=False)
    return idx[:k]


def _fold_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> total-order int32: the sign-folded sort key of
    ``_survivor_indices`` as a reusable helper (negative floats map to
    -magnitude, both zero signs collapse to 0, +inf stays below the
    0x7FFFFFFF sentinel).  Ascending int order == ascending float order
    for every non-NaN value."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, -(bits & jnp.int32(0x7FFFFFFF)), bits)


# --------------------------------------------- NSGA-II building blocks
def _dominance_rank(objs: jnp.ndarray) -> jnp.ndarray:
    """(N, M) objective vectors -> (N,) int32 non-domination rank
    (0 = the Pareto front), minimization on every component.

    Brute-force O(N^2) dominance mask + iterative front peeling — the
    survival step only ever ranks 2P candidates and the epilogue
    (G+1)*P, both small enough that the dense mask beats any clever
    sort-based front construction on this stack, and the same loop IS
    the reference algorithm the numpy oracle in tests/test_pareto.py
    replays verbatim.  Rows with a NaN component compare False both
    ways, so they neither dominate nor are dominated (callers mask
    non-finite rows out of any selection); all-+inf infeasible rows tie
    with each other and are dominated by every feasible design."""
    N = objs.shape[0]
    le = (objs[:, None, :] <= objs[None, :, :]).all(axis=-1)
    lt = (objs[:, None, :] < objs[None, :, :]).any(axis=-1)
    dom = le & lt  # dom[i, j]: i strictly dominates j

    def cond(state):
        return (state[0] < 0).any()

    def body(state):
        rank, r = state
        unassigned = rank < jnp.int32(0)
        blocked = (dom & unassigned[:, None]).any(axis=0)
        front = unassigned & ~blocked
        return jnp.where(front, r, rank), r + jnp.int32(1)

    rank, _ = jax.lax.while_loop(
        cond, body, (jnp.full((N,), -1, jnp.int32), jnp.int32(0)))
    return rank


def _crowding(objs: jnp.ndarray) -> jnp.ndarray:
    """(N, M) -> (N,) float32 crowding distance, computed as one
    ``lax.sort`` pass per objective over the sign-folded total-order
    int32 bits (``_fold_bits``).

    Distances are measured in folded-bit space rather than raw float
    space: the fold is strictly monotone, every +/-inf objective maps to
    a finite int32, and the neighbour/span arithmetic (cast to float32)
    therefore never produces the inf-inf NaNs the raw values would —
    which is what keeps the adversarial all-+inf-infeasible case exact.
    Each per-objective pass sorts ``(key, iota)`` (a unique total order,
    shard-stable like ``_survivor_indices``), gives the two boundary
    designs +inf distance, interior designs their normalized
    neighbour-gap, and scatter-adds through the permutation (unique
    indices, so the scatter is deterministic)."""
    N, M = objs.shape
    iota = jax.lax.iota(jnp.int32, N)
    total = jnp.zeros((N,), jnp.float32)
    for m in range(M):
        key = _fold_bits(objs[:, m])
        skey, perm = jax.lax.sort((key, iota), num_keys=2, is_stable=False)
        kf = skey.astype(jnp.float32)
        span = kf[-1] - kf[0]
        prev = jnp.concatenate([kf[:1], kf[:-1]])
        nxt = jnp.concatenate([kf[1:], kf[-1:]])
        d = jnp.where(span > 0, (nxt - prev) / span, jnp.float32(0.0))
        d = d.at[0].set(jnp.float32(jnp.inf))
        d = d.at[N - 1].set(jnp.float32(jnp.inf))
        total = total.at[perm].add(d)
    return total


def _crowded_order_keys(objs: jnp.ndarray):
    """The (rank, -crowding) survival sort keys as an int32 pair.
    Crowding is non-negative and never NaN, so its raw float32 bit
    pattern is monotone and negating it sorts descending — ascending
    ``(rank, ckey, index)`` is exactly NSGA-II's crowded comparison."""
    rank = _dominance_rank(objs)
    crowd = _crowding(objs)
    ckey = -jax.lax.bitcast_convert_type(crowd, jnp.int32)
    return rank, ckey


def _crowded_positions(objs: jnp.ndarray) -> jnp.ndarray:
    """(P, M) -> (P,) float32 crowded-comparison position (0 = best) of
    each design WITHOUT reordering the population — the tournament
    selection key for the initial generation (survival emits later
    populations already in crowded order, so their key is just iota)."""
    P = objs.shape[0]
    rank, ckey = _crowded_order_keys(objs)
    iota = jax.lax.iota(jnp.int32, P)
    _, _, perm = jax.lax.sort((rank, ckey, iota), num_keys=3, is_stable=False)
    pos = jnp.zeros((P,), jnp.int32).at[perm].set(iota)
    return pos.astype(jnp.float32)


def _tournament(key, scores: jnp.ndarray, n: int) -> jnp.ndarray:
    """Binary tournament: n winners (indices)."""
    P = scores.shape[0]
    idx = jax.random.randint(key, (n, 2), 0, P)
    a, b = idx[:, 0], idx[:, 1]
    return jnp.where(scores[a] <= scores[b], a, b)


def _pow_recip_eta1(x: jnp.ndarray, eta: float) -> jnp.ndarray:
    """``x ** (1 / (eta + 1))``.  The paper's eta = 3 turns the
    transcendental pow — the measured hot spot of SBX/mutation on CPU —
    into two sqrts (exponent 1/4)."""
    if eta == 3.0:
        return jnp.sqrt(jnp.sqrt(x))
    return x ** (1.0 / (eta + 1.0))


def _pow_eta1(x: jnp.ndarray, eta: float) -> jnp.ndarray:
    """``x ** (eta + 1)``; eta = 3 strength-reduces to two multiplies."""
    if eta == 3.0:
        x2 = x * x
        return x2 * x2
    return x ** (eta + 1.0)


def _sbx(key, p1: jnp.ndarray, p2: jnp.ndarray, eta: float, prob: float):
    """Simulated binary crossover on [0,1] genes (Deb & Agrawal)."""
    ku, kc, kg = jax.random.split(key, 3)
    u = jax.random.uniform(ku, p1.shape)
    beta = jnp.where(
        u <= 0.5,
        _pow_recip_eta1(2.0 * u, eta),
        _pow_recip_eta1(1.0 / (2.0 * (1.0 - u)), eta),
    )
    c1 = 0.5 * ((1 + beta) * p1 + (1 - beta) * p2)
    c2 = 0.5 * ((1 - beta) * p1 + (1 + beta) * p2)
    # per-pair: apply crossover with prob; per-gene: 50% exchange (pymoo)
    do_pair = jax.random.uniform(kc, (p1.shape[0], 1)) < prob
    do_gene = jax.random.uniform(kg, p1.shape) < 0.5
    use = do_pair & do_gene
    c1 = jnp.where(use, c1, p1)
    c2 = jnp.where(use, c2, p2)
    return jnp.clip(c1, 0.0, 1.0 - 1e-7), jnp.clip(c2, 0.0, 1.0 - 1e-7)


def _poly_mutation(key, x: jnp.ndarray, eta: float, prob: float):
    """Polynomial mutation (Deb), genes in [0,1]."""
    ku, kp = jax.random.split(key)
    u = jax.random.uniform(ku, x.shape)
    lo = x  # delta to bounds (range = 1)
    hi = 1.0 - x
    d1 = _pow_recip_eta1(2 * u + (1 - 2 * u) * _pow_eta1(1 - lo, eta), eta) - 1
    d2 = 1 - _pow_recip_eta1(2 * (1 - u) + (2 * u - 1) * _pow_eta1(1 - hi, eta), eta)
    delta = jnp.where(u <= 0.5, d1, d2)
    do = jax.random.uniform(kp, x.shape) < prob
    return jnp.clip(jnp.where(do, x + delta, x), 0.0, 1.0 - 1e-7)


def _elitist_survival(pop, scores, children, child_scores):
    """(mu + lambda) elitist survival: the P best of parents + children,
    best-first, as ``(survivors (P, n), their scores (P,))``.

    One combined ``lax.sort`` over ``(folded score, candidate index,
    score)``: the sign-folded total-order key (``_fold_bits``) tie-broken
    by the index is a unique total order, so the permutation is exactly
    ``_survivor_indices``' stable ascending argsort, and the scores ride
    it instead of being gathered in a second pass (asserted against that
    reference in tests/test_fused_gen.py)."""
    P = pop.shape[0]
    allg = jnp.concatenate([pop, children], axis=0)
    alls = jnp.concatenate([scores, child_scores], axis=0)
    okey = _fold_bits(alls)
    iota = jax.lax.iota(jnp.int32, 2 * P)
    _, idx, srt = jax.lax.sort((okey, iota, alls), num_keys=2,
                               is_stable=False)
    return allg[idx[:P]], srt[:P]


def _crowded_survival(pop, objs, children, child_objs):
    """NSGA-II survival: the P best of parents + children in crowded
    order (ascending non-domination rank, descending crowding, candidate
    index), as ``(survivors (P, n), their objective vectors (P, M))``.
    The objective columns ride the ``(rank, ckey, index)`` sort, which is
    a unique total order, so they equal a gather by the sorted index
    (tests/test_fused_gen.py)."""
    P = pop.shape[0]
    allg = jnp.concatenate([pop, children], axis=0)
    allo = jnp.concatenate([objs, child_objs], axis=0)
    rank, ckey = _crowded_order_keys(allo)
    iota = jax.lax.iota(jnp.int32, 2 * P)
    cols = tuple(allo[:, m] for m in range(allo.shape[-1]))
    srt = jax.lax.sort((rank, ckey, iota) + cols, num_keys=3,
                       is_stable=False)
    return allg[srt[2][:P]], jnp.stack(srt[3:], axis=-1)[:P]


def _make_gen_step(eval_fn, ctx, pop_size, n_genes, sbx_prob, sbx_eta, mut_eta,
                   pareto=False):
    """The per-generation scan body, shared verbatim by the single-shot
    ``_ga_core`` and the segmented ``_segment_core`` so both paths compile
    the exact same generation program (the bit-parity guarantee).

    ``pareto=True`` swaps ONLY the fitness plumbing around the shared
    variation body (tournament -> SBX -> mutation, identical slicing of
    the same uniform block): the carry becomes ``(pop, objs (P, M),
    sel)`` where ``sel`` is the crowded-comparison position each
    tournament compares instead of a scalar score, ``eval_fn`` returns
    (P, M) objective vectors, and survival is ``_crowded_survival``
    instead of ``_elitist_survival``.

    All per-generation randomness comes from ONE uniform block sliced at
    static offsets — the many small threefry launches of the original
    select/SBX/mutate splits carried fixed dispatch overheads that
    dominated the generation on CPU."""
    P = pop_size
    n = n_genes
    mut_prob = 1.0 / n
    # odd P: select one extra pair and truncate the children back to P, so
    # no parent slot is silently dropped and history shapes stay (G+1, P).
    n_pairs = (P + 1) // 2
    n_contest = 2 * n_pairs
    # slice offsets into the single per-generation uniform block
    o_t = 2 * n_contest          # tournament contestants (uniform -> int)
    o_u = o_t + n_pairs * n      # SBX spread factor u
    o_p = o_u + n_pairs          # SBX per-pair gate
    o_g = o_p + n_pairs * n      # SBX per-gene gate
    o_mu = o_g + P * n           # mutation u
    o_md = o_mu + P * n          # mutation per-gene gate
    tot = o_md

    def gen(carry, k):
        if pareto:
            pop, objs, sel = carry
            scores = sel  # crowded-comparison position, lower = better
        else:
            pop, scores = carry
        u = jax.random.uniform(k, (tot,))
        # binary tournament: 2*n_pairs contests of 2 contestants each
        ti = (u[:o_t] * P).astype(jnp.int32)
        ca, cb = ti[:n_contest], ti[n_contest:]
        parents = jnp.where(scores[ca] <= scores[cb], ca, cb)
        p1 = pop[parents[:n_pairs]]
        p2 = pop[parents[n_pairs:]]
        # SBX from the pre-drawn uniforms
        ub = u[o_t:o_u].reshape(n_pairs, n)
        beta = jnp.where(
            ub <= 0.5,
            _pow_recip_eta1(2.0 * ub, sbx_eta),
            _pow_recip_eta1(1.0 / (2.0 * (1.0 - ub)), sbx_eta),
        )
        c1 = 0.5 * ((1 + beta) * p1 + (1 - beta) * p2)
        c2 = 0.5 * ((1 - beta) * p1 + (1 + beta) * p2)
        do_pair = u[o_u:o_p].reshape(n_pairs, 1) < sbx_prob
        do_gene = u[o_p:o_g].reshape(n_pairs, n) < 0.5
        use = do_pair & do_gene
        c1 = jnp.clip(jnp.where(use, c1, p1), 0.0, 1.0 - 1e-7)
        c2 = jnp.clip(jnp.where(use, c2, p2), 0.0, 1.0 - 1e-7)
        children = jnp.concatenate([c1, c2], axis=0)[:P]
        # polynomial mutation
        um = u[o_g:o_mu].reshape(P, n)
        lo = children  # delta to bounds (range = 1)
        hi = 1.0 - children
        d1 = _pow_recip_eta1(
            2 * um + (1 - 2 * um) * _pow_eta1(1 - lo, mut_eta), mut_eta) - 1
        d2 = 1 - _pow_recip_eta1(
            2 * (1 - um) + (2 * um - 1) * _pow_eta1(1 - hi, mut_eta), mut_eta)
        delta = jnp.where(um <= 0.5, d1, d2)
        do = u[o_mu:o_md].reshape(P, n) < mut_prob
        children = jnp.clip(
            jnp.where(do, children + delta, children), 0.0, 1.0 - 1e-7)
        child_scores = eval_fn(children, ctx)
        if pareto:
            new_pop, new_objs = _crowded_survival(pop, objs, children,
                                                  child_scores)
            # survival order == crowded order, so the next tournament's
            # selection key is just the position
            new_sel = jax.lax.iota(jnp.int32, P).astype(jnp.float32)
            return (new_pop, new_objs, new_sel), (children, child_scores)
        new_pop, new_scores = _elitist_survival(pop, scores, children,
                                                child_scores)
        return (new_pop, new_scores), (children, child_scores)

    return gen


def _ga_core(
    key, eval_fn, pop_size, generations, init_genomes, ctx,
    sbx_prob, sbx_eta, mut_eta,
) -> GAResult:
    n = init_genomes.shape[-1]
    s0 = eval_fn(init_genomes, ctx)
    gen = _make_gen_step(eval_fn, ctx, pop_size, n, sbx_prob, sbx_eta, mut_eta)
    keys = jax.random.split(key, generations)
    (pop, scores), (hist_g, hist_s) = jax.lax.scan(gen, (init_genomes, s0), keys)

    genomes_hist = jnp.concatenate([init_genomes[None], hist_g], axis=0)
    scores_hist = jnp.concatenate([s0[None], hist_s], axis=0)
    flat_s = scores_hist.reshape(-1)
    best = jnp.argmin(flat_s)
    return GAResult(
        genomes=genomes_hist,
        scores=scores_hist,
        best_genome=genomes_hist.reshape(-1, n)[best],
        best_score=flat_s[best],
    )


def _pareto_core(
    key, eval_fn, pop_size, generations, init_genomes, ctx,
    sbx_prob, sbx_eta, mut_eta,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The Pareto twin of ``_ga_core``: same master-key stream, same
    variation, NSGA-II survival.  ``eval_fn(genomes, ctx)`` must return
    (P, M) objective vectors.  Returns the evaluated history
    ``(genomes_hist (G+1, P, n), objs_hist (G+1, P, M))``; front
    extraction is the epilogue's job (``_pareto_epilogue``)."""
    n = init_genomes.shape[-1]
    o0 = eval_fn(init_genomes, ctx)
    sel0 = _crowded_positions(o0)
    gen = _make_gen_step(eval_fn, ctx, pop_size, n, sbx_prob, sbx_eta,
                         mut_eta, pareto=True)
    keys = jax.random.split(key, generations)
    _, (hist_g, hist_o) = jax.lax.scan(gen, (init_genomes, o0, sel0), keys)
    genomes_hist = jnp.concatenate([init_genomes[None], hist_g], axis=0)
    objs_hist = jnp.concatenate([o0[None], hist_o], axis=0)
    return genomes_hist, objs_hist


def _segment_core(
    state, eval_fn, ctx, seg_gens, total_gens, sbx_prob, sbx_eta, mut_eta,
) -> Tuple[GAState, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Advance ``seg_gens`` generations from ``state``.

    Key derivation: split the master key into the run's FULL
    ``total_gens`` keys (static, so the program caches per (seg, total)
    pair) and dynamic-slice this segment's window at the traced ``gen``
    counter.  ``jax.random.split`` is NOT prefix-stable across counts
    (``split(k, a)[:b] != split(k, b)``), so slicing the full split is the
    only derivation that reproduces ``run_ga``'s stream bit-exactly.
    """
    pop, scores = state.genomes, state.scores
    P, n = pop.shape[-2], pop.shape[-1]
    gen = _make_gen_step(eval_fn, ctx, P, n, sbx_prob, sbx_eta, mut_eta)
    all_keys = jax.random.split(state.key, total_gens)
    keys = jax.lax.dynamic_slice_in_dim(all_keys, state.gen, seg_gens)
    (pop, scores), hist = jax.lax.scan(gen, (pop, scores), keys)
    new_state = GAState(
        genomes=pop, scores=scores, key=state.key,
        gen=state.gen + jnp.int32(seg_gens),
    )
    return new_state, hist


_GA_STATICS = ("eval_fn", "pop_size", "generations", "sbx_prob", "sbx_eta",
               "mut_eta")
_SEG_STATICS = ("eval_fn", "seg_gens", "total_gens", "sbx_prob", "sbx_eta",
                "mut_eta")


@partial(jax.jit, static_argnames=_GA_STATICS, donate_argnames=("init_genomes",))
def _run_ga_jit(key, init_genomes, ctx, *, eval_fn, pop_size, generations,
                sbx_prob, sbx_eta, mut_eta):
    return _ga_core(key, eval_fn, pop_size, generations, init_genomes, ctx,
                    sbx_prob, sbx_eta, mut_eta)


@partial(jax.jit, static_argnames=_GA_STATICS, donate_argnames=("init_genomes",))
def _run_ga_batched_jit(keys, init_genomes, ctx, *, eval_fn, pop_size,
                        generations, sbx_prob, sbx_eta, mut_eta):
    def one(key, init, c):
        return _ga_core(key, eval_fn, pop_size, generations, init, c,
                        sbx_prob, sbx_eta, mut_eta)

    ctx_axes = jax.tree_util.tree_map(lambda _: 0, ctx)
    return jax.vmap(one, in_axes=(0, 0, ctx_axes))(keys, init_genomes, ctx)


@partial(jax.jit, static_argnames=("eval_fn",))
def _init_state_jit(key, init_genomes, ctx, *, eval_fn):
    return GAState(
        genomes=init_genomes, scores=eval_fn(init_genomes, ctx),
        key=key, gen=jnp.int32(0),
    )


@partial(jax.jit, static_argnames=("eval_fn",))
def _init_state_batched_jit(keys, init_genomes, ctx, *, eval_fn):
    def one(key, init, c):
        return GAState(genomes=init, scores=eval_fn(init, c),
                       key=key, gen=jnp.int32(0))

    ctx_axes = jax.tree_util.tree_map(lambda _: 0, ctx)
    return jax.vmap(one, in_axes=(0, 0, ctx_axes))(keys, init_genomes, ctx)


@partial(jax.jit, static_argnames=_SEG_STATICS)
def _run_ga_segment_jit(state, ctx, *, eval_fn, seg_gens, total_gens,
                        sbx_prob, sbx_eta, mut_eta):
    return _segment_core(state, eval_fn, ctx, seg_gens, total_gens,
                         sbx_prob, sbx_eta, mut_eta)


@partial(jax.jit, static_argnames=_SEG_STATICS)
def _run_ga_batched_segment_jit(state, ctx, *, eval_fn, seg_gens, total_gens,
                                sbx_prob, sbx_eta, mut_eta):
    def one(st, c):
        return _segment_core(st, eval_fn, c, seg_gens, total_gens,
                             sbx_prob, sbx_eta, mut_eta)

    ctx_axes = jax.tree_util.tree_map(lambda _: 0, ctx)
    return jax.vmap(one, in_axes=(0, ctx_axes))(state, ctx)


def run_ga(
    key: jax.Array,
    eval_fn: Callable,
    *,
    pop_size: int,
    generations: int,
    init_genomes: jnp.ndarray,
    ctx: Any = None,
    sbx_prob: float = SBX_PROB,
    sbx_eta: float = SBX_ETA,
    mut_eta: float = MUT_ETA,
) -> GAResult:
    """Run the GA as one cached jit.  Lower score = better.

    ``eval_fn(genomes (P, n)) -> scores (P,)`` when ``ctx`` is ``None``, or
    ``eval_fn(genomes, ctx) -> scores`` with ``ctx`` an arbitrary pytree of
    traced arrays (e.g. packed workload tensors).  Pass workload data via
    ``ctx`` and reuse the same ``eval_fn`` object to avoid retracing.

    ``init_genomes`` must already satisfy the paper's seeding rule (only
    designs that fit the largest workload — see ``search.seed_population``)
    and is DONATED to XLA: pass a copy if the caller needs it afterwards.
    """
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    with warnings.catch_warnings():
        # the full population history is returned, so no output ever aliases
        # the donated init buffer on CPU — silence only that diagnostic
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return _run_ga_jit(
            key, init_genomes, ctx,
            eval_fn=eval_fn, pop_size=int(pop_size), generations=int(generations),
            sbx_prob=float(sbx_prob), sbx_eta=float(sbx_eta), mut_eta=float(mut_eta),
        )


def run_ga_batched(
    keys: jnp.ndarray,
    eval_fn: Callable,
    *,
    pop_size: int,
    generations: int,
    init_genomes: jnp.ndarray,
    ctx: Any = None,
    sbx_prob: float = SBX_PROB,
    sbx_eta: float = SBX_ETA,
    mut_eta: float = MUT_ETA,
) -> GAResult:
    """B independent GAs in one vmapped XLA program.

    ``keys`` is a stacked (B, 2) PRNG-key array, ``init_genomes`` is
    (B, P, n) (donated), and every leaf of ``ctx`` carries a leading batch
    axis — one slice per GA (per-workload tensors for ``separate_search``,
    broadcast copies for multi-seed search).  Returns a ``GAResult`` whose
    fields all have a leading B axis.  Per-batch-element results match
    ``run_ga(keys[b], ..., ctx=ctx[b])`` exactly (same RNG stream).
    """
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return _run_ga_batched_jit(
            keys, init_genomes, ctx,
            eval_fn=eval_fn, pop_size=int(pop_size), generations=int(generations),
            sbx_prob=float(sbx_prob), sbx_eta=float(sbx_eta), mut_eta=float(mut_eta),
        )


def init_ga_state(
    key: jax.Array, eval_fn: Callable, init_genomes: jnp.ndarray,
    ctx: Any = None,
) -> GAState:
    """Evaluate the seed population into a resumable ``GAState`` at
    generation 0.  ``key`` is the run's master key — the SAME key a
    single-shot ``run_ga`` of the whole budget would receive.  Unlike
    ``run_ga``, ``init_genomes`` is NOT donated (a failed segment retries
    from the last state, which must stay alive)."""
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    return _init_state_jit(key, init_genomes, ctx, eval_fn=eval_fn)


def init_ga_state_batched(
    keys: jnp.ndarray, eval_fn: Callable, init_genomes: jnp.ndarray,
    ctx: Any = None,
) -> GAState:
    """Batched ``init_ga_state``: (B, 2) keys, (B, P, n) seeds, batched
    ctx leaves -> a ``GAState`` with a leading (B,) axis on every field."""
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    return _init_state_batched_jit(keys, init_genomes, ctx, eval_fn=eval_fn)


def run_ga_segment(
    state: GAState,
    eval_fn: Callable,
    *,
    generations: int,
    total_generations: int,
    ctx: Any = None,
    sbx_prob: float = SBX_PROB,
    sbx_eta: float = SBX_ETA,
    mut_eta: float = MUT_ETA,
) -> Tuple[GAState, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Advance ``generations`` (k) generations through ONE cached jit,
    returning ``(new_state, (children (k, P, n), child_scores (k, P)))``.

    ``total_generations`` is the run's full budget (static): the segment
    reproduces exactly the key window ``split(key, total)[gen:gen+k]``, so
    chaining segments covering the budget is bit-identical to a single
    ``run_ga(key, ..., generations=total_generations)`` — same history,
    same best.  Requires ``state.gen + k <= total_generations``.  Nothing
    is donated; a failed launch can re-run from the same ``state``.
    """
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    return _run_ga_segment_jit(
        state, ctx, eval_fn=eval_fn,
        seg_gens=int(generations), total_gens=int(total_generations),
        sbx_prob=float(sbx_prob), sbx_eta=float(sbx_eta), mut_eta=float(mut_eta),
    )


def run_ga_batched_segment(
    state: GAState,
    eval_fn: Callable,
    *,
    generations: int,
    total_generations: int,
    ctx: Any = None,
    sbx_prob: float = SBX_PROB,
    sbx_eta: float = SBX_ETA,
    mut_eta: float = MUT_ETA,
) -> Tuple[GAState, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Batched ``run_ga_segment``: state fields and ctx leaves carry a
    leading (B,) axis; histories come back as (B, k, P, n) / (B, k, P).
    Per-element results match the unbatched segment (and therefore
    ``run_ga``) exactly."""
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    return _run_ga_batched_segment_jit(
        state, ctx, eval_fn=eval_fn,
        seg_gens=int(generations), total_gens=int(total_generations),
        sbx_prob=float(sbx_prob), sbx_eta=float(sbx_eta), mut_eta=float(mut_eta),
    )


# ------------------------------------------------------- thin epilogue
def _cell_codes(flat_g: jnp.ndarray) -> list:
    """Decoded-grid-cell identity of each design as 1-2 mixed-radix
    int32 codes (columns packed greedily while the radix product fits —
    the host's single int64 code is unavailable in-jit without global
    x64; SPACE_SIZE overflows int32 at grid density >= 2).  Two designs
    share a cell iff every code matches.  Shared by the scalar and
    Pareto thin epilogues so both dedup in exactly the host
    ``engine._top_unique`` cell space."""
    n = flat_g.shape[-1]
    idx = space.decode_indices(flat_g)  # (N, n) int32 grid cells
    sizes = [len(space.SPACE[f]) for f in space.FIELDS]
    codes, grp, prod = [], None, 1
    for j in range(n):
        if grp is None or prod * sizes[j] > 0x7FFFFFFF:
            grp, prod = jnp.int32(0), 1
            codes.append(None)
        grp = grp * jnp.int32(sizes[j]) + idx[:, j]
        prod *= sizes[j]
        codes[-1] = grp
    return codes


def _thin_epilogue(genomes_hist, scores_hist, top_k: int) -> GAThin:
    """In-jit top-k-unique + convergence over one slot's full history.

    Replicates the host finalize (``engine._top_unique`` semantics) ON
    DEVICE so the pipelined engine only syncs (K, n) genomes, (K,) scores
    and the (G+1,) convergence curve instead of the whole history.  The
    selection must be BIT-identical to the host path, which is:

      stable argsort by score -> first occurrence per decoded-grid-cell
      class -> classes ordered by that first occurrence -> finite filter
      -> truncate to k.

    Step by step:
      * key every design with the sign-folded total-order sort bits from
        ``_survivor_indices`` — for finite and +/-inf scores ascending
        (key, flat index) order IS numpy's stable score argsort (both
        zero signs collapse to 0 there too), and non-finite designs are
        masked out up front: a decoded cell evaluates to ONE score, so
        NaN/inf classes are wholly non-finite and dropped by the finite
        filter on both paths — pre-masking them changes nothing the
        selection ever reads.
      * ``top_k`` rounds of masked ``argmin`` (a ``fori_loop``; XLA's
        variadic comparator sorts are an order of magnitude slower on
        CPU than k vectorized min-reductions): ``jnp.argmin`` returns
        the FIRST index attaining the minimum, i.e. exactly the stable
        tie-break, so each round yields the best-ranked design whose
        grid cell has not been seen — and overwriting the key of every
        design decoding to that cell with the sentinel afterwards
        replays the host's first-occurrence-per-class dedup in rank
        order (the key array doubles as the mask: non-finite designs
        start at the sentinel).  Cells compare as 1-2 mixed-radix int32
        codes over the decoded index columns — the host's single int64
        code is unavailable in-jit without global x64 (SPACE_SIZE
        overflows int32 at grid density >= 2), so columns are packed
        greedily while the radix product fits.
      * ``n_kept`` counts the rounds that found a fresh finite class,
        i.e. ``min(#unique finite classes, top_k)`` — all any consumer
        reads.

    Padding rows (beyond ``n_kept``) are genome 0 / score +inf; the host
    slices them off before they reach a ``SearchResult``."""
    G1, P, n = genomes_hist.shape
    N = G1 * P
    flat_g = genomes_hist.reshape(N, n)
    flat_s = scores_hist.reshape(N)
    fold = _fold_bits(flat_s)
    codes = _cell_codes(flat_g)
    k = min(int(top_k), N)
    sentinel = jnp.int32(0x7FFFFFFF)  # > every folded finite/inf key

    def pick(i, carry):
        okey, top_g, top_s, cnt = carry
        j = jnp.argmin(okey)
        valid = okey[j] < sentinel
        top_g = top_g.at[i].set(jnp.where(valid, flat_g[j], jnp.float32(0.0)))
        top_s = top_s.at[i].set(jnp.where(valid, flat_s[j], jnp.float32(jnp.inf)))
        same = codes[0] == codes[0][j]
        for c in codes[1:]:
            same = same & (c == c[j])
        okey = jnp.where(same, sentinel, okey)
        return okey, top_g, top_s, cnt + valid.astype(jnp.int32)

    _, top_g, top_s, n_kept = jax.lax.fori_loop(0, k, pick, (
        jnp.where(jnp.isfinite(flat_s), fold, sentinel),
        jnp.zeros((k, n), flat_g.dtype),
        jnp.full((k,), jnp.inf, jnp.float32),
        jnp.int32(0),
    ))
    conv = jax.lax.cummin(jnp.min(scores_hist, axis=1))
    return GAThin(top_genomes=top_g, top_scores=top_s, n_kept=n_kept,
                  convergence=conv)


@partial(jax.jit, static_argnames=_GA_STATICS + ("top_k",),
         donate_argnames=("init_genomes",))
def _run_ga_batched_thin_jit(keys, init_genomes, ctx, *, eval_fn, pop_size,
                             generations, sbx_prob, sbx_eta, mut_eta, top_k):
    def one(key, init, c):
        ga = _ga_core(key, eval_fn, pop_size, generations, init, c,
                      sbx_prob, sbx_eta, mut_eta)
        return _thin_epilogue(ga.genomes, ga.scores, top_k)

    ctx_axes = jax.tree_util.tree_map(lambda _: 0, ctx)
    return jax.vmap(one, in_axes=(0, 0, ctx_axes))(keys, init_genomes, ctx)


@partial(jax.jit, static_argnames=("top_k",))
def _epilogue_batched_jit(genomes_hist, scores_hist, *, top_k):
    return jax.vmap(
        lambda g, s: _thin_epilogue(g, s, top_k)
    )(genomes_hist, scores_hist)


def run_ga_batched_thin(
    keys: jnp.ndarray,
    eval_fn: Callable,
    *,
    pop_size: int,
    generations: int,
    init_genomes: jnp.ndarray,
    top_k: int,
    ctx: Any = None,
    sbx_prob: float = SBX_PROB,
    sbx_eta: float = SBX_ETA,
    mut_eta: float = MUT_ETA,
) -> GAThin:
    """``run_ga_batched`` with the thin epilogue fused onto the SAME
    program: one donated jit runs B GAs and reduces each full history to
    its ``GAThin`` on device, so the host never transfers the (B, G+1,
    P, n) history.  The selected designs/scores/convergence are
    bit-identical to finalizing ``run_ga_batched``'s history on host
    (tests/test_pipelined.py).  The history itself is unavailable —
    callers that need ``GAResult`` (result-cache writes, fault partials)
    must use the history path."""
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    with warnings.catch_warnings():
        # the thin outputs are far smaller than the donated seed buffer
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return _run_ga_batched_thin_jit(
            keys, init_genomes, ctx,
            eval_fn=eval_fn, pop_size=int(pop_size),
            generations=int(generations), sbx_prob=float(sbx_prob),
            sbx_eta=float(sbx_eta), mut_eta=float(mut_eta),
            top_k=int(top_k),
        )


def ga_epilogue_batched(
    genomes_hist: jnp.ndarray, scores_hist: jnp.ndarray, *, top_k: int,
) -> GAThin:
    """Standalone batched thin epilogue over accumulated histories
    ((B, G+1, P, n) / (B, G+1, P), host or device): what the segmented
    engine runs on its device-resident history to build streaming
    snapshots and the final result without syncing the history itself."""
    return _epilogue_batched_jit(
        jnp.asarray(genomes_hist), jnp.asarray(scores_hist),
        top_k=int(top_k),
    )


# ---------------------------------------------------- pareto epilogue
def _pareto_epilogue(genomes_hist, objs_hist, top_k: int) -> ParetoThin:
    """In-jit k-best-front-members + convergence over one slot's full
    evaluated history — the Pareto twin of ``_thin_epilogue``, and the
    single selection every execution mode shares (sequential engines run
    it on the device history, pipelined engines fuse it onto the GA
    program), which is what makes sequential/pipelined fronts
    bit-identical by construction.

    Selection order: ascending non-domination rank over ALL (G+1)*P
    evaluated designs (``_dominance_rank`` — the O(N^2) mask the numpy
    oracle replays), descending crowding within a rank (``_crowding``,
    folded-bit sort passes), flat history index as the final tie-break.
    Non-finite rows (infeasible all-+inf, NaN-guarded evals) are masked
    to the sentinel before selection — same role as the finite filter of
    the scalar path.  ``top_k`` masked-argmin rounds then pick the best
    unseen design and retire its whole decoded grid cell
    (``_cell_codes``), exactly the scalar epilogue's
    first-occurrence-per-class dedup but in crowded order, so a cell's
    representative is its best-crowded occurrence.  ``n_kept`` counts
    the fresh feasible cells found, i.e. ``min(#unique feasible cells,
    top_k)`` — with ``top_k`` large enough the picks cover the entire
    first front (and only then spill into rank 1, 2, ...).

    ``convergence`` tracks the running best scalar E*L*A proxy
    (``objectives.pareto_scalar``), bit-identical to an ``ela`` curve
    over the same designs.  Padding rows are genome 0 / vector + score
    +inf; the host slices them off."""
    G1, P, n = genomes_hist.shape
    M = objs_hist.shape[-1]
    N = G1 * P
    flat_g = genomes_hist.reshape(N, n)
    flat_o = objs_hist.reshape(N, M)
    flat_s = pareto_scalar(flat_o)
    rank, ckey = _crowded_order_keys(flat_o)
    feas = jnp.isfinite(flat_o).all(axis=-1)
    iota = jax.lax.iota(jnp.int32, N)
    _, _, perm = jax.lax.sort((rank, ckey, iota), num_keys=3, is_stable=False)
    pos = jnp.zeros((N,), jnp.int32).at[perm].set(iota)
    sentinel = jnp.int32(0x7FFFFFFF)  # > every position (N << 2^31)
    codes = _cell_codes(flat_g)
    k = min(int(top_k), N)

    def pick(i, carry):
        okey, top_g, top_v, top_s, cnt = carry
        j = jnp.argmin(okey)
        valid = okey[j] < sentinel
        top_g = top_g.at[i].set(jnp.where(valid, flat_g[j], jnp.float32(0.0)))
        top_v = top_v.at[i].set(
            jnp.where(valid, flat_o[j], jnp.float32(jnp.inf)))
        top_s = top_s.at[i].set(
            jnp.where(valid, flat_s[j], jnp.float32(jnp.inf)))
        same = codes[0] == codes[0][j]
        for c in codes[1:]:
            same = same & (c == c[j])
        okey = jnp.where(same, sentinel, okey)
        return okey, top_g, top_v, top_s, cnt + valid.astype(jnp.int32)

    _, top_g, top_v, top_s, n_kept = jax.lax.fori_loop(0, k, pick, (
        jnp.where(feas, pos, sentinel),
        jnp.zeros((k, n), flat_g.dtype),
        jnp.full((k, M), jnp.inf, jnp.float32),
        jnp.full((k,), jnp.inf, jnp.float32),
        jnp.int32(0),
    ))
    conv = jax.lax.cummin(jnp.min(flat_s.reshape(G1, P), axis=1))
    return ParetoThin(top_genomes=top_g, top_vectors=top_v, top_scores=top_s,
                      n_kept=n_kept, convergence=conv)


def pareto_mask_bytes(pop_size: int, generations: int) -> int:
    """Device bytes the front epilogue's dominance masks take: two (N, N)
    boolean arrays over the N = (G+1)*P history rows (the v5e compile
    rehearsal measures ~2.0 bytes per N^2 for N >= 1e4;
    tests/test_tpu_compile.py)."""
    n = (int(generations) + 1) * int(pop_size)
    return 2 * n * n


# (P, G) ladder for a Pareto request sized to the device, smallest first
PARETO_BUDGETS = ((256, 20), (512, 20), (1024, 20), (1024, 50), (1024, 100))
# the masks may take this share of device memory; the rest holds the
# history, the tables and the program's other temporaries
PARETO_MASK_SHARE = 0.5


def pick_pareto_budget(device_bytes: int) -> Tuple[int, int]:
    """The largest (P, G) of ``PARETO_BUDGETS`` whose epilogue masks take
    at most ``PARETO_MASK_SHARE`` of ``device_bytes``; raises if even the
    smallest does not fit."""
    fit = [b for b in PARETO_BUDGETS
           if pareto_mask_bytes(*b) <= PARETO_MASK_SHARE * device_bytes]
    if not fit:
        raise ValueError(
            f"no Pareto budget fits {PARETO_MASK_SHARE:.0%} of "
            f"{device_bytes} device bytes")
    return fit[-1]


_PARETO_STATICS = _GA_STATICS + ("top_k", "history")


@partial(jax.jit, static_argnames=_PARETO_STATICS,
         donate_argnames=("init_genomes",))
def _run_pareto_batched_jit(keys, init_genomes, ctx, *, eval_fn, pop_size,
                            generations, sbx_prob, sbx_eta, mut_eta, top_k,
                            history):
    def one(key, init, c):
        gh, oh = _pareto_core(key, eval_fn, pop_size, generations, init, c,
                              sbx_prob, sbx_eta, mut_eta)
        thin = _pareto_epilogue(gh, oh, top_k)
        if history:
            return gh, oh, thin
        return thin

    ctx_axes = jax.tree_util.tree_map(lambda _: 0, ctx)
    return jax.vmap(one, in_axes=(0, 0, ctx_axes))(keys, init_genomes, ctx)


@partial(jax.jit, static_argnames=("top_k",))
def _pareto_epilogue_batched_jit(genomes_hist, objs_hist, *, top_k):
    return jax.vmap(
        lambda g, o: _pareto_epilogue(g, o, top_k)
    )(genomes_hist, objs_hist)


def run_pareto_batched(
    keys: jnp.ndarray,
    eval_fn: Callable,
    *,
    pop_size: int,
    generations: int,
    init_genomes: jnp.ndarray,
    top_k: int,
    ctx: Any = None,
    sbx_prob: float = SBX_PROB,
    sbx_eta: float = SBX_ETA,
    mut_eta: float = MUT_ETA,
    history: bool = False,
):
    """B independent NSGA-II Pareto searches in one vmapped, donated XLA
    program, front extraction fused on device.

    ``eval_fn(genomes, ctx)`` must return (P, M) minimization objective
    vectors (``objectives.make_pareto_objective``).  With
    ``history=False`` (the pipelined engine) only the batched
    ``ParetoThin`` is returned/synced; ``history=True`` (sequential
    engines, which also need the history for result caching and
    partials) additionally returns ``(genomes_hist (B, G+1, P, n),
    objs_hist (B, G+1, P, M))``.  Both run the IDENTICAL program prefix
    and epilogue, so the selected front members are bit-identical across
    the two modes (tests/test_pareto.py)."""
    if ctx is None and not isinstance(eval_fn, _IgnoreCtx):
        eval_fn = _IgnoreCtx(eval_fn)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return _run_pareto_batched_jit(
            keys, init_genomes, ctx,
            eval_fn=eval_fn, pop_size=int(pop_size),
            generations=int(generations), sbx_prob=float(sbx_prob),
            sbx_eta=float(sbx_eta), mut_eta=float(mut_eta),
            top_k=int(top_k), history=bool(history),
        )


def pareto_epilogue_batched(
    genomes_hist: jnp.ndarray, objs_hist: jnp.ndarray, *, top_k: int,
) -> ParetoThin:
    """Standalone batched Pareto epilogue over accumulated histories
    ((B, G+1, P, n) / (B, G+1, P, M), host or device) — the reference
    entry point the oracle-parity tests drive directly."""
    return _pareto_epilogue_batched_jit(
        jnp.asarray(genomes_hist), jnp.asarray(objs_hist),
        top_k=int(top_k),
    )
