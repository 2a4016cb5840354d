"""Paper Fig. 2: joint vs separate search — batched one-jit drivers.

Per seed (5 random initial populations):
  * joint search top-10 scores,
  * separate per-workload searches re-scored on ALL workloads (fair
    comparison) + % of their top designs that FAIL other workloads,
  * the optimize-for-largest-workload (VGG16) baseline vs joint, per
    workload (the paper's 36/36/20/69 % improvements).

All S joint searches run as ONE vmapped XLA program
(``joint_search_batched``), and all S x W separate searches as another
(``batched_search``) — two launches for the whole figure instead of
S * (1 + W) sequentially retraced GAs (~10x end-to-end on this container).

``--mesh [SEARCHxPOP]`` lays both programs out over a 2-D (search,
population) device mesh (fake 8-device host on CPU) — same scores, the
whole figure sharded over the fleet.  ``--backend table`` runs both
programs through the factorized grid-table cost model (same top designs,
layer-depth-independent eval).
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict

# repro modules build device arrays at import; keep them lazy so main()
# can inject xla_force_host_platform_device_count first (see --mesh).
import jax
import jax.numpy as jnp
import numpy as np

POP, GENS, TOPK = 40, 10, 10
AREA = 150.0


def per_workload_scores(
    genome: np.ndarray, ws, area=AREA, objective: str = "ela"
) -> Dict[str, float]:
    """Score of ONE design on each single workload (one evaluation)."""
    from repro.core import space
    from repro.core.objectives import OBJECTIVE_WEIGHTS
    from repro.imc.cost import evaluate_designs

    d = space.decode(jnp.asarray(genome[None, :]))
    r = evaluate_designs(d, ws)
    e = np.asarray(r.energy_pj[0])  # per-workload columns are independent,
    l = np.asarray(r.latency_ns[0])  # so one full-set eval == W subset evals
    a = float(r.area_mm2[0])
    we, wl, wa = OBJECTIVE_WEIGHTS[objective]
    out = {}
    for i, name in enumerate(ws.names):
        feasible = bool(r.fits[0, i]) and bool(r.valid[0]) and a <= area
        s = float(e[i]) ** we * float(l[i]) ** wl * a ** wa
        out[name] = s if feasible else float("inf")
    return out


def run(seeds: int = 5, verbose: bool = True, mesh=None,
        backend: str = "jnp", fast: bool = False) -> dict:
    from repro.core.search import batched_search, joint_search_batched
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    W = ws.n
    largest = "vgg16"
    results = {"seeds": [], "pop": POP, "gens": GENS, "backend": backend,
               "fast": bool(fast)}
    if mesh is not None:
        from repro.launch.mesh import describe

        results["mesh"] = describe(mesh)

    # --fast: direct table seeding for both figure programs.  It draws
    # DIFFERENT (equally valid) initial populations, so the figure's
    # statistics stay comparable but not bit-identical.
    engine = None
    if fast:
        from repro.core.engine import SearchEngine

        engine = SearchEngine(mesh=mesh, max_slots=max(64, seeds * W),
                              direct_seed=True)

    t0 = time.time()
    joint_keys = jnp.stack([jax.random.PRNGKey(s) for s in range(seeds)])
    joints = joint_search_batched(
        joint_keys, ws, pop_size=POP, generations=GENS, top_k=TOPK, mesh=mesh,
        backend=backend, engine=engine,
    )
    t_joint = time.time() - t0

    # seeds x W single-workload GAs, seed-major, in one program
    t0 = time.time()
    sep_keys = jnp.concatenate(
        [jax.random.split(jax.random.PRNGKey(s + 100), W) for s in range(seeds)]
    )
    seps = batched_search(
        sep_keys,
        jnp.tile(ws.feats[:, None], (seeds, 1, 1, 1)),
        jnp.tile(ws.mask[:, None], (seeds, 1, 1)),
        names=[(n,) for n in ws.names] * seeds,
        pop_size=POP,
        generations=GENS,
        top_k=TOPK,
        mesh=mesh,
        backend=backend,
        engine=engine,
    )
    t_sep = time.time() - t0
    results["joint_wall_s_total"] = t_joint
    results["separate_wall_s_total"] = t_sep

    # cross-rescore every separate winner on the FULL set in one evaluation
    from repro.core.search import rescore_designs

    all_top = [r.top_genomes for r in seps]
    counts = [len(g) for g in all_top]
    if sum(counts):
        s_flat, _ = rescore_designs(np.concatenate([g for g in all_top if len(g)]), ws)
    offs = np.cumsum([0] + counts)

    for seed in range(seeds):
        joint = joints[seed]
        sep = {
            name: seps[seed * W + i] for i, name in enumerate(ws.names)
        }
        failed = {}
        for i, name in enumerate(ws.names):
            b = seed * W + i
            s_all = s_flat[offs[b]:offs[b + 1]] if counts[b] else np.zeros((0,))
            failed[name] = float(np.mean(~np.isfinite(s_all))) if counts[b] else 1.0

        # optimize-for-largest vs joint, per workload
        big = sep[largest]
        comparison = {}
        if len(big.top_genomes) and len(joint.top_genomes):
            s_big = per_workload_scores(big.top_genomes[0], ws)
            s_joint = per_workload_scores(joint.top_genomes[0], ws)
            for w in ws.names:
                if np.isfinite(s_big[w]) and np.isfinite(s_joint[w]):
                    comparison[w] = 1.0 - s_joint[w] / s_big[w]  # + = joint better
                else:
                    comparison[w] = None if np.isfinite(s_joint[w]) else float("nan")
        entry = {
            "seed": seed,
            "joint_top10": [float(s) for s in joint.top_scores],
            "separate_failed_frac": failed,
            "joint_vs_largest_improvement": comparison,
            "joint_wall_s": t_joint / seeds,
        }
        results["seeds"].append(entry)
        if verbose:
            jbest = f"{joint.top_scores[0]:.3g}" if len(joint.top_scores) else "fail"
            print(f"[fig2 seed {seed}] joint best {jbest} "
                  f"({t_joint/seeds:.1f}s amortized); failed%: "
                  f"{ {k: f'{v:.0%}' for k, v in failed.items()} }")
            if comparison:
                print(f"          joint-vs-vgg16-optimized improvement: "
                      f"{ {k: (f'{v:.0%}' if v is not None and np.isfinite(v) else 'fail') for k, v in comparison.items()} }")
    if verbose:
        n_designs = seeds * (1 + W) * POP * (GENS + 1)
        print(f"[fig2] total wall {t_joint + t_sep:.1f}s "
              f"({n_designs / (t_joint + t_sep):.0f} designs/s end-to-end)")
    return results


def main(argv=None) -> int:
    import argparse

    from benchmarks.run import exp_dir, prepare_search_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument(
        "--mesh", nargs="?", const="auto", default=None, metavar="SEARCHxPOP",
        help="shard both figure programs over a (search, population) mesh",
    )
    ap.add_argument(
        "--backend", default="jnp", choices=["jnp", "pallas", "table"],
        help="cost-model backend for both figure programs",
    )
    ap.add_argument(
        "--fast", action="store_true",
        help="direct table seeding for both "
             "programs (use with --backend table; different but equally "
             "valid seed pools, so statistics — not bits — match)",
    )
    args = ap.parse_args(argv)
    if args.fast and args.backend != "table":
        ap.error("--fast requires --backend table (direct seeding samples "
                 "the factorized demand tables)")

    mesh = prepare_search_mesh(args.mesh) if args.mesh else None
    out = run(seeds=args.seeds, mesh=mesh, backend=args.backend,
              fast=args.fast)

    with open(exp_dir() / "fig2_joint_vs_separate.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
