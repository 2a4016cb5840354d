"""Plain reference of a DSE request: the cost model and the GA search.

Imports nothing of the program under test.  Everything it needs comes
from a configuration file (``configs/<name>.json``): the workloads' layer
shapes, the design grid, the technology constants and the GA constants.

What one request means (paper Sec. III, the service's documented
semantics):

* ``key = PRNGKey(seed)``, split once into a seeding key and a GA key.
* Seeding: rounds of ``P * oversample`` uniform genomes, each round's key
  split off a running key; the first ``P`` genomes (in draw order) that
  fit the largest workload (most weights) and are V/f-valid form the
  initial population.
* ``G`` generations: binary tournament, simulated binary crossover,
  polynomial mutation and (mu + lambda) elitist survival, every random
  number of a generation drawn as one uniform block from that
  generation's key (``split(ga_key, G)``).
* The answer: the ``top_k`` best designs, unique by decoded grid cell,
  best first (stable by history position), and the running best score of
  each generation.

The arithmetic is the straightforward dense one (layer sums per
workload, ``max`` over the request's workloads).  With eta = 3 the GA's
``x ** (1/4)`` and ``x ** 4`` are written as two square roots and two
squarings, which is how the search defines them, so that a genome is the
same float wherever the trajectory is the same.

``dtype`` sets the precision of the cost model's arithmetic: float32 is
what the configuration states; bfloat16 is the control.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

FIELDS = ("rows", "cols", "c_per_tile", "t_per_router", "g_per_chip",
          "v_op", "bits_cell", "t_cycle_ns", "glb_mb")
OBJECTIVES = ("ela", "edp", "e", "l")


class Spec(NamedTuple):
    """The configuration's fixed part, hashable so jitted functions take
    it as a static argument."""

    grid: tuple  # per field, tuple of float32-representable values
    tech: tuple  # sorted (name, value) pairs
    sbx_prob: float
    sbx_eta: float
    mut_eta: float
    oversample: int
    max_rounds: int
    vf_rtol: float

    def t(self, name: str) -> float:
        return dict(self.tech)[name]


class RefAnswer(NamedTuple):
    top_genomes: np.ndarray  # (R, k, n), rows past n_kept are padding
    top_scores: np.ndarray  # (R, k), +inf past n_kept
    n_kept: np.ndarray  # (R,)
    convergence: np.ndarray  # (R, G+1)


def load_spec(config: dict) -> Spec:
    ga = config["ga"]
    return Spec(
        grid=tuple(tuple(float(v) for v in config["design_space"][f])
                   for f in FIELDS),
        tech=tuple(sorted((k, float(v)) for k, v in config["tech"].items())),
        sbx_prob=float(ga["sbx_prob"]), sbx_eta=float(ga["sbx_eta"]),
        mut_eta=float(ga["mut_eta"]), oversample=int(ga["seed_oversample"]),
        max_rounds=int(ga["seed_max_rounds"]), vf_rtol=float(ga["vf_rtol"]),
    )


def pack_layers(config: dict, subsets: Sequence[Sequence[int]]):
    """Per request, its workloads' layers padded to (4, L_max, 6) with a
    layer mask and a workload mask."""
    names = list(config["workloads"])
    tables = [np.asarray(config["workloads"][n], np.float32) for n in names]
    wmax = len(names)
    lmax = max(len(t) for t in tables)
    R = len(subsets)
    layers = np.zeros((R, wmax, lmax, 6), np.float32)
    lmask = np.zeros((R, wmax, lmax), bool)
    wmask = np.zeros((R, wmax), bool)
    for r, sub in enumerate(subsets):
        for j, w in enumerate(sub):
            t = tables[w]
            layers[r, j, :len(t)] = t
            lmask[r, j, :len(t)] = True
            wmask[r, j] = True
    return layers, lmask, wmask


# ------------------------------------------------------------ cost model
def decode(spec: Spec, genomes):
    """(..., 9) genes in [0, 1) -> per-field grid values, each (...)."""
    out = []
    for i, vals in enumerate(spec.grid):
        g = jnp.asarray(np.asarray(vals, np.float32))
        n = len(vals)
        idx = jnp.clip((genomes[..., i] * n).astype(jnp.int32), 0, n - 1)
        out.append(g[idx])
    return dict(zip(FIELDS, out))


def cell_code(spec: Spec, genomes):
    """One int32 per design naming its grid cell (the grid has fewer than
    2**31 cells at density 1)."""
    sizes = [len(v) for v in spec.grid]
    assert int(np.prod(np.asarray(sizes, np.int64))) < 2 ** 31
    code = jnp.zeros(genomes.shape[:-1], jnp.int32)
    for i, n in enumerate(sizes):
        idx = jnp.clip((genomes[..., i] * n).astype(jnp.int32), 0, n - 1)
        code = code * n + idx
    return code


def valid(spec: Spec, d):
    """V/f: t_cycle >= t_min(v_op) by the alpha-power law, with the
    configuration's relative slack for the exact tie at the nominal
    point."""
    vn, vth, a = spec.t("v_nominal"), spec.t("v_th"), spec.t("alpha_power")
    k = (vn - vth) ** a / vn
    t_min = k * d["v_op"] / (d["v_op"] - vth) ** a
    return d["t_cycle_ns"] >= t_min * (1.0 - spec.vf_rtol)


def demand_fits(spec: Spec, d, layers, lmask):
    """Crossbars a workload needs and whether the chip holds them.
    d: fields (P,); layers (W, L, 6); returns fits (P, W)."""
    K, N, G = layers[..., 1], layers[..., 2], layers[..., 5]
    cpw = jnp.ceil(spec.t("weight_bits") / d["bits_cell"])[:, None, None]
    rows = d["rows"][:, None, None]
    cols = d["cols"][:, None, None]
    per_layer = jnp.ceil(K / rows) * jnp.ceil(N * cpw / cols) * G
    need = jnp.where(lmask, per_layer, 0.0).sum(-1)
    cap = d["g_per_chip"] * d["t_per_router"] * d["c_per_tile"]
    return need <= cap[:, None]


def scores(spec: Spec, genomes, layers, lmask, wmask, kind, area_limit,
           dtype=jnp.float32):
    """Objective per design (P,), +inf where infeasible.  ``kind`` indexes
    OBJECTIVES; the workload reduction is the max over the request's own
    workloads.  Energies, latencies and area are computed in ``dtype``."""
    t = spec.t
    d32 = decode(spec, genomes)
    fits = demand_fits(spec, d32, layers, lmask)  # integer counts: exact
    ok = valid(spec, d32) & jnp.all(fits | ~wmask[None, :], axis=-1)

    c = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    d = {f: c(v) for f, v in d32.items()}
    M, K, N, Ain, Aout, G = (c(layers[..., i])[None] for i in range(6))
    m = lmask[None]

    def lsum(x):
        return jnp.where(m, x, c(0.0)).sum(-1)

    def b(x):
        return x[:, None, None]

    cpw = jnp.ceil(c(t("weight_bits")) / b(d["bits_cell"]))
    t_cyc = b(d["t_cycle_ns"])
    phases = c(t("input_bits"))
    byt = Ain + Aout

    l_comp = lsum(M * (phases * c(t("adc_share"))) * t_cyc)
    l_comm = lsum(byt / (b(d["g_per_chip"]) * c(t("router_flit_bytes"))) * t_cyc)
    spill = jnp.maximum(byt - b(d["glb_mb"]) * c(1 << 20), c(0.0))
    l_dram = lsum(spill) / c(t("dram_bw_bytes_per_ns"))
    latency = l_comp + l_comm + l_dram  # (P, W)

    g_avg = 0.5 * (1.0 / t("r_on_ohm") + 1.0 / t("r_off_ohm"))
    e_cell = b(d["v_op"]) ** 2 * c(g_avg) * t_cyc * c(1e3)
    e_analog = lsum(M * phases * (K * (N * cpw) * G) * e_cell)
    e_adc = lsum(M * phases * (N * cpw) * G * c(t("adc_energy_pj")))
    col_splits = jnp.ceil(N * cpw / b(d["cols"]))
    e_dac = lsum(M * phases * K * col_splits * G * c(t("dac_energy_pj")))
    e_route = lsum(byt * c(t("router_energy_pj_per_byte")))
    e_buf = lsum(byt * c(t("tile_buf_energy_pj_per_byte")
                         + t("glb_energy_pj_per_byte")))
    e_dram = lsum(spill * c(t("dram_energy_pj_per_byte")))

    cell_mm2 = t("cell_area_f2") * (t("feature_nm") * 1e-9) ** 2 * 1e6
    tiles = d["g_per_chip"] * d["t_per_router"]
    xbar = (d["rows"] * d["cols"] * c(cell_mm2)
            + d["rows"] * c(t("driver_area_mm2_per_row"))
            + d["cols"] / c(t("adc_share")) * c(t("adc_area_mm2")))
    tile_buf = c(t("tile_buf_kb") / 1024.0 * t("sram_area_mm2_per_mb"))
    area = (tiles * d["c_per_tile"] * xbar + tiles * tile_buf
            + d["g_per_chip"] * c(t("router_area_mm2"))
            + d["glb_mb"] * c(t("sram_area_mm2_per_mb"))) * c(1.10)

    e_leak = c(t("leak_mw_per_mm2")) * area[:, None] * latency
    energy = e_analog + e_adc + e_dac + e_route + e_buf + e_dram + e_leak

    neg = c(-jnp.inf)
    e = jnp.where(wmask[None], energy, neg).max(-1)
    lat = jnp.where(wmask[None], latency, neg).max(-1)
    branches = jnp.stack([e * lat * area, e * lat, e, lat], axis=-1)
    s = jnp.take_along_axis(branches, jnp.full(e.shape + (1,), kind), -1)[..., 0]
    feasible = ok & (area.astype(jnp.float32) <= area_limit)
    return jnp.where(feasible, s.astype(jnp.float32), jnp.float32(jnp.inf))


# -------------------------------------------------------------- seeding
def seed_population(spec: Spec, key, layers, lmask, wmask, pop_size):
    """The first ``pop_size`` drawn genomes that fit the largest workload
    and are V/f-valid.  Largest = most weights, sum of K * N * groups."""
    weights = jnp.where(lmask, layers[..., 1] * layers[..., 2] * layers[..., 5],
                        0.0).sum(-1)
    li = jnp.argmax(jnp.where(wmask, weights, -1.0))
    big, big_mask = layers[li], lmask[li]
    n_cand = pop_size * spec.oversample
    pools, oks = [], []
    for _ in range(spec.max_rounds):
        key, k = jax.random.split(key)
        cand = jax.random.uniform(k, (n_cand, len(FIELDS)))
        d = decode(spec, cand)
        ok = demand_fits(spec, d, big[None], big_mask[None])[:, 0] & valid(spec, d)
        pools.append(cand)
        oks.append(ok)
    cand = jnp.concatenate(pools)
    ok = jnp.concatenate(oks)
    idx = jnp.nonzero(ok, size=pop_size, fill_value=0)[0]
    return cand[idx]


# ------------------------------------------------------------------- GA
def _root4(x):
    return jnp.sqrt(jnp.sqrt(x))


def _pow4(x):
    x2 = x * x
    return x2 * x2


def search(spec: Spec, key, layers, lmask, wmask, kind, area_limit, *,
           pop_size, generations, dtype=jnp.float32):
    """One request's whole search; returns the evaluated history
    (G+1, P, n) genomes and (G+1, P) scores, generation 0 first."""
    assert spec.sbx_eta == 3.0 and spec.mut_eta == 3.0
    P, n = pop_size, len(FIELDS)
    k_seed, k_ga = jax.random.split(key)
    pop = seed_population(spec, k_seed, layers, lmask, wmask, P)

    def score(g):
        return scores(spec, g, layers, lmask, wmask, kind, area_limit, dtype)

    s0 = score(pop)
    n_pairs = (P + 1) // 2
    n_contest = 2 * n_pairs
    sizes = [2 * n_contest, n_pairs * n, n_pairs, n_pairs * n, P * n, P * n]
    cuts = np.cumsum(sizes)

    def generation(carry, k):
        pop, s = carry
        u = jax.random.uniform(k, (int(cuts[-1]),))
        u_t, u_b, u_p, u_g, u_m, u_d = jnp.split(u, cuts[:-1])
        contest = (u_t * P).astype(jnp.int32)
        a, b = contest[:n_contest], contest[n_contest:]
        parents = jnp.where(s[a] <= s[b], a, b)
        p1, p2 = pop[parents[:n_pairs]], pop[parents[n_pairs:]]
        ub = u_b.reshape(n_pairs, n)
        beta = jnp.where(ub <= 0.5, _root4(2.0 * ub),
                         _root4(1.0 / (2.0 * (1.0 - ub))))
        c1 = 0.5 * ((1 + beta) * p1 + (1 - beta) * p2)
        c2 = 0.5 * ((1 - beta) * p1 + (1 + beta) * p2)
        cross = (u_p.reshape(n_pairs, 1) < spec.sbx_prob) & \
            (u_g.reshape(n_pairs, n) < 0.5)
        c1 = jnp.clip(jnp.where(cross, c1, p1), 0.0, 1.0 - 1e-7)
        c2 = jnp.clip(jnp.where(cross, c2, p2), 0.0, 1.0 - 1e-7)
        kids = jnp.concatenate([c1, c2])[:P]
        um = u_m.reshape(P, n)
        d1 = _root4(2 * um + (1 - 2 * um) * _pow4(1 - kids)) - 1
        d2 = 1 - _root4(2 * (1 - um) + (2 * um - 1) * _pow4(1 - (1.0 - kids)))
        step = jnp.where(um <= 0.5, d1, d2)
        mutate = u_d.reshape(P, n) < 1.0 / n
        kids = jnp.clip(jnp.where(mutate, kids + step, kids), 0.0, 1.0 - 1e-7)
        ks = score(kids)
        both_g = jnp.concatenate([pop, kids])
        both_s = jnp.concatenate([s, ks])
        keep = jnp.argsort(both_s, stable=True)[:P]
        return (both_g[keep], both_s[keep]), (kids, ks)

    keys = jax.random.split(k_ga, generations)
    _, (hg, hs) = jax.lax.scan(generation, (pop, s0), keys)
    return (jnp.concatenate([pop[None], hg]), jnp.concatenate([s0[None], hs]))


def top_unique(spec: Spec, genomes_hist, scores_hist, top_k):
    """Best ``top_k`` finite designs, one per grid cell, best first (ties
    by history position), and the per-generation running best."""
    n = genomes_hist.shape[-1]
    g = genomes_hist.reshape(-1, n)
    s = scores_hist.reshape(-1)
    N = s.shape[0]
    order = jnp.argsort(s, stable=True)
    codes = cell_code(spec, g)[order]
    pos = jnp.arange(N, dtype=jnp.int32)
    # first occurrence of each cell in score order
    by_cell = jnp.lexsort((pos, codes))
    c_sorted = codes[by_cell]
    first = jnp.concatenate([jnp.ones((1,), bool), c_sorted[1:] != c_sorted[:-1]])
    is_first = jnp.zeros((N,), bool).at[by_cell].set(first)
    finite = jnp.isfinite(s[order])
    rank = jnp.where(is_first & finite, pos, N)
    pick = jnp.sort(rank)[:top_k]
    got = pick < N
    src = order[jnp.minimum(pick, N - 1)]
    top_g = jnp.where(got[:, None], g[src], 0.0)
    top_s = jnp.where(got, s[src], jnp.inf)
    conv = jax.lax.cummin(scores_hist.min(axis=1))
    return top_g, top_s, got.sum(), conv


@partial(jax.jit, static_argnames=("spec", "pop_size", "generations", "top_k",
                                   "dtype", "lanes"))
def _answers(spec, keys, layers, lmask, wmask, kinds, areas, *, pop_size,
             generations, top_k, dtype, lanes):
    def one(args):
        key, lay, lm, wm, kind, area = args
        gh, sh = search(spec, key, lay, lm, wm, kind, area, pop_size=pop_size,
                        generations=generations, dtype=dtype)
        return top_unique(spec, gh, sh, top_k)

    # ``lanes`` requests at a time: the seeding rounds of a whole block
    # would not fit the device at once
    return jax.lax.map(one, (keys, layers, lmask, wmask, kinds, areas),
                       batch_size=lanes)


@partial(jax.jit, static_argnames=("spec",))
def _rescore(spec, genomes, layers, lmask, wmask, kinds, areas):
    return jax.vmap(lambda g, la, lm, wm, k, a: scores(spec, g, la, lm, wm, k, a))(
        genomes, layers, lmask, wmask, kinds, areas)


class Requests(NamedTuple):
    """The requests to answer, as arrays: seeds, workload subsets (indices
    into the configuration's workloads), objective names, area limits."""

    seeds: Sequence[int]
    subsets: Sequence[Sequence[int]]
    objectives: Sequence[str]
    areas: Sequence[float]


def _arrays(config, reqs: Requests):
    layers, lmask, wmask = pack_layers(config, reqs.subsets)
    keys = np.asarray(jax.vmap(jax.random.PRNGKey)(
        jnp.asarray(np.asarray(reqs.seeds, np.int64).astype(np.int32))))
    kinds = np.asarray([OBJECTIVES.index(o) for o in reqs.objectives], np.int32)
    areas = np.asarray(reqs.areas, np.float32)
    return keys, layers, lmask, wmask, kinds, areas


def answers(config: dict, reqs: Requests, *, pop_size: int, generations: int,
            top_k: int, dtype=jnp.float32, block: int = 64,
            lanes: int = 8) -> RefAnswer:
    """Answer every request, ``block`` at a time (one compiled program for
    every block: the last one is padded with copies of the first row)."""
    spec = load_spec(config)
    arrs = _arrays(config, reqs)
    R = len(reqs.seeds)
    outs = []
    for lo in range(0, R, block):
        idx = np.arange(lo, lo + block) % R
        part = tuple(jnp.asarray(a[idx]) for a in arrs)
        res = _answers(spec, *part, pop_size=pop_size, generations=generations,
                       top_k=top_k, dtype=dtype, lanes=lanes)
        keep = min(block, R - lo)
        outs.append(tuple(np.asarray(x)[:keep] for x in res))
    cat = [np.concatenate([o[i] for o in outs]) for i in range(4)]
    return RefAnswer(*cat)


def rescore(config: dict, reqs: Requests, genomes: Sequence[np.ndarray],
            block: int = 64) -> list:
    """The float32 reference score of given designs, per request (each an
    (k_i, n) array; padded to the longest and cut back)."""
    spec = load_spec(config)
    arrs = _arrays(config, reqs)
    R = len(reqs.seeds)
    k = max(1, max((len(g) for g in genomes), default=1))
    G = np.zeros((R, k, len(FIELDS)), np.float32)
    for i, g in enumerate(genomes):
        G[i, :len(g)] = g
    out = []
    for lo in range(0, R, block):
        idx = np.arange(lo, lo + block) % R
        part = tuple(jnp.asarray(a[idx]) for a in (G,) + arrs[1:])
        res = np.asarray(_rescore(spec, *part))
        out.extend(res[:min(block, R - lo)])
    return [out[i][:len(g)] for i, g in enumerate(genomes)]


def rel_gap(a, b) -> float:
    """Largest |a - b| / |b| over matching entries; equal entries (both
    +inf included) give 0, one finite and one not gives inf."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    same = a == b
    fin = np.isfinite(a) & np.isfinite(b)
    with np.errstate(divide="ignore", invalid="ignore"):
        d = np.where(same, 0.0,
                     np.where(fin, np.abs(a - b) / np.maximum(np.abs(b), 1e-300),
                              np.inf))
    return float(d.max())


def _cell_code_np(spec: Spec, genomes) -> np.ndarray:
    """``cell_code`` on the host, same float32 arithmetic."""
    g = np.asarray(genomes, np.float32).reshape(-1, len(FIELDS))
    code = np.zeros(len(g), np.int64)
    for i, vals in enumerate(spec.grid):
        n = len(vals)
        code = code * n + np.clip((g[:, i] * np.float32(n)).astype(np.int32),
                                  0, n - 1)
    return code


def _well_formed(spec: Spec, scores, genomes) -> bool:
    """A returned list is best first, finite and one design per cell."""
    s = np.asarray(scores, np.float32)
    if len(s) == 0:
        return True
    if not np.all(np.isfinite(s)) or np.any(s[1:] < s[:-1]):
        return False
    codes = _cell_code_np(spec, genomes)
    return len(np.unique(codes)) == len(codes)


def compare(config: dict, reqs: Requests, got: Dict[str, list], ref: RefAnswer,
            rescored: list, generations: int) -> Dict[str, float]:
    """The numbers that decide ``correct``.

    * ``score_gap``: the largest relative gap, over every returned design
      of every compared request, between its returned score and the
      reference cost model's score of that same design under the
      request's own workloads, objective and area; +inf where a returned
      list is not best first, finite and one design per cell.
    * ``trajectory_miss``: the share of compared requests whose search
      left the reference's: the returned best-so-far curve (G+1 long) or
      the returned top scores differ from the reference search of the same
      request, run alone at its stated P and G, by more than 1e-5 relative
      at some position.  A sound search can leave the path on an exact
      tie broken the other way, so the share of a sound run is small and
      not always 0; a search run shorter, smaller, frozen, on another
      request's data or in lower precision leaves it on nearly every
      request."""
    spec = load_spec(config)
    score_gap = 0.0
    miss = 0
    for i in range(len(reqs.seeds)):
        ts = np.asarray(got["top_scores"][i], np.float32)
        if not _well_formed(spec, ts, got["top_genomes"][i]):
            score_gap = float("inf")
        score_gap = max(score_gap, rel_gap(ts, rescored[i]))
        conv = np.asarray(got["convergence"][i], np.float32)
        k = int(ref.n_kept[i])
        if (conv.shape != (generations + 1,)
                or max(rel_gap(conv, ref.convergence[i]),
                       rel_gap(ts, ref.top_scores[i][:k])) > 1e-5):
            miss += 1
    return {"score_gap": score_gap,
            "trajectory_miss": miss / max(1, len(reqs.seeds))}
