"""Tracked search-throughput benchmark — the repo's perf trajectory.

End-to-end DSE throughput of the batched one-jit search stack at the
paper's operating point (P=40, G=10, 4-CNN workload set):

  * multi-seed joint search (``joint_search_batched``): cold (first call,
    includes trace+compile) and warm (cached program) wall time,
  * all-seeds x all-workloads separate search in one program,
  * designs-evaluated/sec for both, vs the paper's ~36 s/design.

``benchmarks/run.py`` writes the result to
``experiments/search_throughput.json`` so future PRs can diff the
trajectory.  The paper's 4 h for the same P x G search is the 1x line.

``--mesh [SEARCHxPOP]`` re-runs the same workload on a 2-D (search,
population) device mesh (``launch.mesh.make_search_mesh``) and records the
sharded row under the ``"sharded"`` key of the same json — on a CPU host
it forces 8 fake XLA devices first, so the row proves the fleet layout
end-to-end even without real hardware.  ``--backend table`` re-runs
through the factorized grid-table cost model (``imc.tables``; eval
independent of workload depth) and records the row under ``"table"``.
See benchmarks/README.md.
"""
from __future__ import annotations

import sys
import time

# NOTE: importing jax alone does not initialize the XLA backend, but the
# repro modules build device arrays at import — keep them inside run() so
# ``main()`` can still inject xla_force_host_platform_device_count first.
import jax
import jax.numpy as jnp

PAPER_S_PER_DESIGN = 36.0
POP, GENS = 40, 10


def _block(results) -> None:
    # pipelined (transfer-thin) results carry ga=None — their top arrays
    # are host numpy already, so blocking on them is the right no-op
    jax.block_until_ready(
        [r.ga.scores if r.ga is not None else r.top_scores for r in results]
    )


def run(quick: bool = False, verbose: bool = True, mesh=None,
        backend: str = "jnp") -> dict:
    from repro.core.search import batched_search, joint_search_batched
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    # sharded rows use a seed count divisible by every 8-device search-axis
    # layout so the batch axis actually shards (ragged dims replicate)
    seeds = (4 if quick else 8) if mesh is not None else (2 if quick else 5)
    warm_reps = 2 if quick else 3  # warm = best-of-N (steady state, not noise)
    per_search = POP * (GENS + 1)
    out = {
        "pop": POP, "gens": GENS, "seeds": seeds, "backend": backend,
        "warm_reps": warm_reps,
        "paper_s_per_design": PAPER_S_PER_DESIGN,
    }
    if mesh is not None:
        from repro.launch.mesh import describe

        out["mesh"] = describe(mesh)
        out["devices"] = int(jax.device_count())

    def keys(base):
        return jnp.stack([jax.random.PRNGKey(base + s) for s in range(seeds)])

    t0 = time.time()
    _block(joint_search_batched(keys(0), ws, pop_size=POP, generations=GENS,
                                mesh=mesh, backend=backend))
    cold = time.time() - t0
    warm = float("inf")
    for rep in range(warm_reps):
        t0 = time.time()
        _block(joint_search_batched(keys(1000 * (rep + 1)), ws, pop_size=POP,
                                    generations=GENS, mesh=mesh,
                                    backend=backend))
        warm = min(warm, time.time() - t0)
    n = seeds * per_search
    out["joint"] = {
        "searches": seeds,
        "cold_s": cold,  # includes trace + XLA compile
        "warm_s": warm,  # cached program: the steady-state number
        "designs_per_s": n / warm,
        "speedup_vs_paper": (n / warm) * PAPER_S_PER_DESIGN,
    }
    if verbose:
        print(f"[search-thru] joint x{seeds}: cold {cold:.2f}s, warm {warm:.2f}s "
              f"-> {n/warm:.0f} designs/s ({n/warm*PAPER_S_PER_DESIGN:.0f}x paper)")

    W = ws.n
    sep_feats = jnp.tile(ws.feats[:, None], (seeds, 1, 1, 1))
    sep_mask = jnp.tile(ws.mask[:, None], (seeds, 1, 1))

    def sep_keys(base):
        return jnp.concatenate(
            [jax.random.split(jax.random.PRNGKey(base + s), W) for s in range(seeds)]
        )

    t0 = time.time()
    _block(batched_search(sep_keys(0), sep_feats, sep_mask,
                          pop_size=POP, generations=GENS, mesh=mesh,
                          backend=backend))
    cold = time.time() - t0
    warm = float("inf")
    for rep in range(warm_reps):
        t0 = time.time()
        _block(batched_search(sep_keys(1000 * (rep + 1)), sep_feats, sep_mask,
                              pop_size=POP, generations=GENS, mesh=mesh,
                              backend=backend))
        warm = min(warm, time.time() - t0)
    n = seeds * W * per_search
    out["separate"] = {
        "searches": seeds * W,
        "cold_s": cold,
        "warm_s": warm,
        "designs_per_s": n / warm,
        "speedup_vs_paper": (n / warm) * PAPER_S_PER_DESIGN,
    }
    if verbose:
        print(f"[search-thru] separate x{seeds*W}: cold {cold:.2f}s, warm {warm:.2f}s "
              f"-> {n/warm:.0f} designs/s ({n/warm*PAPER_S_PER_DESIGN:.0f}x paper)")
    return out


def run_fused(quick: bool = False, verbose: bool = True,
              densities=(1, 2, 3)) -> dict:
    """The fast-path row: direct table seeding on the separate-search
    config (B single-workload GAs, table backend) —
    the configuration the >=1M designs/s acceptance number is measured on
    — swept over grid densities to characterize table memory vs gather
    cost (``configure_grid``; density d inserts d-1 points per grid
    interval, so the joint design space grows ~d^9).

    The first density in ``densities`` (the baseline grid) provides the
    row's top-level ``designs_per_s``."""
    import numpy as np

    from repro.core import space
    from repro.core.engine import SearchEngine
    from repro.core.search import batched_search
    from repro.imc.tables import grid_table_shape, table_bytes
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    W = ws.n
    seeds = 10 if quick else 40
    B = seeds * W
    warm_reps = 2 if quick else 4
    per_search = POP * (GENS + 1)
    n = B * per_search

    keys = np.concatenate([
        np.asarray(jax.random.split(jax.random.PRNGKey(100 + s), W))
        for s in range(seeds)
    ])
    feats = np.tile(np.asarray(ws.feats)[:, None], (seeds, 1, 1, 1))
    mask = np.tile(np.asarray(ws.mask)[:, None], (seeds, 1, 1))
    names = [(w,) for w in PAPER_WORKLOADS] * seeds

    out = {
        "pop": POP, "gens": GENS, "searches": B, "backend": "table",
        "config": "separate", "direct_seed": True,
        "warm_reps": warm_reps, "paper_s_per_design": PAPER_S_PER_DESIGN,
        "densities": [],
    }
    base_density = space.GRID_DENSITY
    try:
        for d in densities:
            space.configure_grid(d)
            eng = SearchEngine(max_slots=B, direct_seed=True)

            def go():
                return batched_search(keys, feats, mask, names=names,
                                      pop_size=POP, generations=GENS,
                                      backend="table", engine=eng)

            t0 = time.time()
            _block(go())
            cold = time.time() - t0
            warm = float("inf")
            for _ in range(warm_reps):
                t0 = time.time()
                _block(go())
                warm = min(warm, time.time() - t0)
            cells = 1
            for f in space.FIELDS:
                cells *= len(space.SPACE[f])
            row = {
                "density": int(d),
                "space_cells": cells,
                "table_shape": grid_table_shape(),
                "table_kb_per_workload": table_bytes(ws.tables()) / W / 1024.0,
                "cold_s": cold,
                "warm_s": warm,
                "designs_per_s": n / warm,
                "speedup_vs_paper": (n / warm) * PAPER_S_PER_DESIGN,
            }
            out["densities"].append(row)
            if verbose:
                print(f"[search-thru] fused x{B} density={d} "
                      f"({cells:.3g} cells, "
                      f"{row['table_kb_per_workload']:.1f} KB/workload): "
                      f"cold {cold:.2f}s, warm {warm*1e3:.1f}ms -> "
                      f"{n/warm/1e6:.3f}M designs/s")
    finally:
        space.configure_grid(base_density)
    # the row's steady-state number: the baseline grid's warm throughput
    out.update({k: out["densities"][0][k]
                for k in ("cold_s", "warm_s", "designs_per_s",
                          "speedup_vs_paper")})
    return out


def run_pipelined(quick: bool = False, verbose: bool = True) -> dict:
    """The transfer-thin row: the SAME configuration as the ``fused`` row's
    baseline-grid entry (B = seeds x W separate searches, table backend,
    direct table seeding) executed through a
    ``pipelined=True`` engine — the GA program computes its top-k-unique
    epilogue on device and only (B, top_k, n) genomes, (B, top_k) scores
    and (B, G+1) convergence cross the wire instead of the full (B, G+1,
    P, n) history.

    Records warm designs/s plus host-transfer bytes per launch for BOTH
    the thin and the history-syncing engine (``transfer_reduction_x`` is
    their ratio)."""
    import numpy as np

    from repro.core.engine import SearchEngine
    from repro.core.search import batched_search
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    W = ws.n
    seeds = 10 if quick else 40
    B = seeds * W
    warm_reps = 2 if quick else 4
    per_search = POP * (GENS + 1)
    n = B * per_search

    keys = np.concatenate([
        np.asarray(jax.random.split(jax.random.PRNGKey(100 + s), W))
        for s in range(seeds)
    ])
    feats = np.tile(np.asarray(ws.feats)[:, None], (seeds, 1, 1, 1))
    mask = np.tile(np.asarray(ws.mask)[:, None], (seeds, 1, 1))
    names = [(w,) for w in PAPER_WORKLOADS] * seeds

    thin = SearchEngine(max_slots=B, direct_seed=True, pipelined=True)
    hist = SearchEngine(max_slots=B, direct_seed=True)

    def go(eng):
        return batched_search(keys, feats, mask, names=names,
                              pop_size=POP, generations=GENS,
                              backend="table", engine=eng)

    t0 = time.time()
    _block(go(thin))
    cold = time.time() - t0
    warm = float("inf")
    for _ in range(warm_reps):
        t0 = time.time()
        _block(go(thin))
        warm = min(warm, time.time() - t0)

    # transfer accounting: one dedicated warm run per engine (the history
    # engine's program is also warmed first so its number is steady-state)
    thin.reset_transfer_stats()
    _block(go(thin))
    thin_bpl = thin.transfer_bytes / max(1, thin.launches)
    _block(go(hist))
    hist.reset_transfer_stats()
    _block(go(hist))
    hist_bpl = hist.transfer_bytes / max(1, hist.launches)

    out = {
        "pop": POP, "gens": GENS, "searches": B, "backend": "table",
        "config": "separate", "direct_seed": True,
        "pipelined": True, "warm_reps": warm_reps,
        "paper_s_per_design": PAPER_S_PER_DESIGN,
        "cold_s": cold,
        "warm_s": warm,
        "designs_per_s": n / warm,
        "speedup_vs_paper": (n / warm) * PAPER_S_PER_DESIGN,
        "launches": int(thin.launches),
        "transfer_bytes_per_launch": thin_bpl,
        "history_transfer_bytes_per_launch": hist_bpl,
        "transfer_reduction_x": hist_bpl / max(1.0, thin_bpl),
    }
    if verbose:
        print(f"[search-thru] pipelined x{B}: cold {cold:.2f}s, "
              f"warm {warm*1e3:.1f}ms -> {n/warm/1e6:.3f}M designs/s; "
              f"{thin_bpl:.0f} B/launch vs {hist_bpl:.0f} B/launch history "
              f"({out['transfer_reduction_x']:.1f}x thinner)")
    return out


def run_pareto(quick: bool = False, verbose: bool = True,
               pareto_k: int = 10) -> dict:
    """The multi-objective row: NSGA-II Pareto-front search
    (``objective="pareto"``) on the fast-path configuration (B = seeds x W
    separate searches, table backend, direct seeding,
    transfer-thin pipelined engine).  Each search returns its ``pareto_k``
    best front members with per-member (E, L, A) vectors instead of one
    scalar optimum — this row tracks what the front search costs relative
    to the scalar ``pipelined`` row on the same B and operating point."""
    import numpy as np

    from repro.core.engine import SearchEngine
    from repro.core.search import batched_search
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    W = ws.n
    seeds = 10 if quick else 40
    B = seeds * W
    warm_reps = 2 if quick else 4
    per_search = POP * (GENS + 1)
    n = B * per_search

    keys = np.concatenate([
        np.asarray(jax.random.split(jax.random.PRNGKey(100 + s), W))
        for s in range(seeds)
    ])
    feats = np.tile(np.asarray(ws.feats)[:, None], (seeds, 1, 1, 1))
    mask = np.tile(np.asarray(ws.mask)[:, None], (seeds, 1, 1))
    names = [(w,) for w in PAPER_WORKLOADS] * seeds

    eng = SearchEngine(max_slots=B, direct_seed=True, pipelined=True)

    def go():
        return batched_search(keys, feats, mask, names=names,
                              pop_size=POP, generations=GENS,
                              backend="table", objective="pareto",
                              pareto_k=pareto_k, engine=eng)

    t0 = time.time()
    res = go()
    _block(res)
    cold = time.time() - t0
    warm = float("inf")
    for _ in range(warm_reps):
        t0 = time.time()
        res = go()
        _block(res)
        warm = min(warm, time.time() - t0)

    front_sizes = [len(r.top_scores) for r in res]
    out = {
        "pop": POP, "gens": GENS, "searches": B, "backend": "table",
        "config": "separate", "objective": "pareto",
        "pareto_k": int(pareto_k), "direct_seed": True,
        "pipelined": True, "warm_reps": warm_reps,
        "paper_s_per_design": PAPER_S_PER_DESIGN,
        "cold_s": cold,
        "warm_s": warm,
        "designs_per_s": n / warm,
        "speedup_vs_paper": (n / warm) * PAPER_S_PER_DESIGN,
        "mean_front_size": float(np.mean(front_sizes)),
        "min_front_size": int(min(front_sizes)),
    }
    if verbose:
        print(f"[search-thru] pareto x{B} (k={pareto_k}): cold {cold:.2f}s, "
              f"warm {warm*1e3:.1f}ms -> {n/warm/1e6:.3f}M designs/s; "
              f"front size mean {out['mean_front_size']:.1f} "
              f"min {out['min_front_size']}")
    return out


def main(argv=None) -> int:
    import argparse

    from benchmarks.run import prepare_search_mesh, write_search_throughput

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer seeds")
    ap.add_argument(
        "--mesh", nargs="?", const="auto", default=None, metavar="SEARCHxPOP",
        help="shard over a (search, population) mesh (e.g. 2x4; default: all "
             "devices on search) and record the row under 'sharded'",
    )
    ap.add_argument(
        "--backend", default="jnp", choices=["jnp", "pallas", "table"],
        help="cost-model backend; 'table' records its row under 'table' "
             "(the factorized-eval trajectory)",
    )
    ap.add_argument(
        "--fused", action="store_true",
        help="run the fast-path config (direct table seeding, "
             "separate-search B, table backend) over a grid-"
             "density sweep and record the row under 'fused'",
    )
    ap.add_argument(
        "--grid-density", default="1,2,3", metavar="D[,D...]",
        help="comma-separated grid densities for the --fused sweep "
             "(the first gives the row's top-level numbers)",
    )
    ap.add_argument(
        "--pipelined", action="store_true",
        help="run the fast-path config through a transfer-thin pipelined "
             "engine (on-device top-k epilogue) and record the row under "
             "'pipelined' (warm designs/s + host-transfer bytes/launch)",
    )
    ap.add_argument(
        "--pareto", action="store_true",
        help="run the fast-path config under objective='pareto' (NSGA-II "
             "front search, thin pipelined engine) and record the row "
             "under 'pareto' (warm designs/s + front-size stats)",
    )
    ap.add_argument("--pareto-k", type=int, default=10,
                    help="front members per search for --pareto")
    args = ap.parse_args(argv)

    if args.pareto:
        if args.mesh or args.backend != "jnp" or args.fused or args.pipelined:
            ap.error("--pareto is its own configuration; "
                     "drop --mesh/--backend/--fused/--pipelined")
        res = run_pareto(quick=args.quick, pareto_k=args.pareto_k)
        write_search_throughput(res, row="pareto")
        return 0

    if args.pipelined:
        if args.mesh or args.backend != "jnp" or args.fused:
            ap.error("--pipelined is its own configuration; "
                     "drop --mesh/--backend/--fused")
        res = run_pipelined(quick=args.quick)
        write_search_throughput(res, row="pipelined")
        return 0

    if args.fused:
        if args.mesh or args.backend != "jnp":
            ap.error("--fused is its own configuration; drop --mesh/--backend")
        densities = tuple(int(v) for v in args.grid_density.split(","))
        res = run_fused(quick=args.quick, densities=densities)
        write_search_throughput(res, row="fused")
        return 0

    # each json row tracks ONE configuration: top-level = dense jnp
    # unsharded, 'sharded' = dense jnp on the mesh, 'table' = table backend
    # unsharded — refuse combinations that would overwrite a row with
    # numbers from a different configuration
    if args.mesh and args.backend != "jnp":
        ap.error("--mesh records the dense-jnp 'sharded' row; "
                 "combine it with --backend jnp only")
    mesh = prepare_search_mesh(args.mesh) if args.mesh else None
    res = run(quick=args.quick, mesh=mesh, backend=args.backend)
    if args.backend == "pallas":
        print("[search-thru] pallas run not recorded (no tracked row; "
              "interpret-mode timing off-TPU is not meaningful)")
        return 0
    row = "sharded" if mesh is not None else (
        "table" if args.backend == "table" else None
    )
    write_search_throughput(res, row=row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
