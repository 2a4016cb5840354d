"""Paper driver: joint hardware-workload search CLI.

    python -m repro.launch.search --workloads vgg16,resnet18,alexnet,mobilenetv3 \
        --objective ela --area 150 --pop 40 --gens 10 --seeds 1

Joint (the paper's method) vs separate (per-workload baseline) searches,
cross-rescoring, and LM-workload search (beyond paper: the assigned
architectures exported as IMC workloads):

    python -m repro.launch.search --lm-workloads llama3.2-1b,mixtral-8x7b \
        --mode decode

``--search-mesh SxP`` lays the batched programs out over a 2-D
(search, population) device mesh (on CPU-only hosts export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first; real
multi-chip hosts need nothing).  Scores are unchanged — it only scales
how many searches run in parallel.  ``--backend table`` evaluates through
the factorized per-workload grid tables (``imc.tables``): throughput
independent of layer count, which is what makes deep ``--lm-workloads``
tables free at search time.

``--serve N`` runs the DSE service instead: N heterogeneous requests
(cycling workload subsets x objectives x seeds over the selected
workload set) are submitted to the continuous-batching queue
(``serve.dse.DSEService``) and drained slot-packed through the shared
search engine — the per-request best designs stream as each launch
lands, followed by a requests/s + latency-percentile summary:

    python -m repro.launch.search --serve 256 --backend table

``--serve-policy priority|edf`` schedules the queue by request priority
(0 = most urgent, wait-time aging) or earliest absolute deadline, and
``--serve-async`` drains through the threaded ``AsyncDSEService`` front
end (``submit`` returns futures; requests join the next launch without
blocking the current one):

    python -m repro.launch.search --serve 256 --backend table \
        --serve-policy priority --serve-async

Robustness knobs (anytime fault-tolerant DSE): ``--segment-gens K``
runs every search as segments of K generations — bit-identical to the
single launch, but a fault loses at most one segment — and
``--checkpoint-dir DIR`` persists segment boundaries so a killed run
resumes from the newest committed state.  Under ``--serve``,
``--retry-attempts``/``--retry-backoff`` arm the deterministic
retry-with-backoff lane (failed chunks re-plan each member in isolation,
quarantining persistent offenders) and ``--partial-results`` resolves
quarantined / past-deadline requests with their best-so-far anytime
result instead of dropping them:

    python -m repro.launch.search --serve 64 --backend table \
        --segment-gens 2 --retry-attempts 3 --partial-results

``--pipelined`` turns on transfer-thin pipelined execution: the GA
program computes its own top-k epilogue on device (only the per-request
top-k genomes/scores and the convergence curve cross the wire;
``result.ga`` is ``None``) and, under ``--serve``, the drain
double-buffers launches — dispatch plan i+1, then harvest plan i — so
host finalize overlaps device compute.  Results are bit-identical; the
summary prints the dispatch->harvest gap, device-idle estimate and
harvested bytes next to the cache hit rate.

``--result-cache DIR`` arms the fingerprint-keyed result cache
(``serve.cache.ResultCache``, disk tier under DIR): a request whose
``request_key`` was answered before — this process or any earlier one
over the same DIR — resolves at submit with zero GA launches, bit
identical to a fresh search.  ``--stream-progress`` prints each
request's improving best-so-far after every guarded GA segment (implies
segmented execution; 2-generation segments unless ``--segment-gens`` /
``--checkpoint-dir`` already chose a boundary):

    python -m repro.launch.search --serve 64 --backend table \
        --result-cache /tmp/dse-cache --stream-progress
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import space
from repro.core.search import (
    joint_search_batched,
    rescore_designs,
    seed_population,
    separate_search,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.lm import lm_workload
from repro.workloads.pack import WorkloadSet, pack_workloads


def build_workloads(args) -> WorkloadSet:
    named = []
    if args.workloads:
        for n in args.workloads.split(","):
            named.append((n, cnn_workload(n)))
    if args.lm_workloads:
        for n in args.lm_workloads.split(","):
            cfg = get_config(n)
            named.append((n, lm_workload(cfg, mode=args.mode, seq=args.seq)))
    if not named:
        named = [(n, cnn_workload(n)) for n in PAPER_WORKLOADS]
    return pack_workloads(named)


def _fmt(v, spec: str = ".2f") -> str:
    """Format a possibly-``None`` stats percentile (empty window)."""
    return "n/a" if v is None else f"{v:{spec}}"


def build_engine(args, mesh, result_cache=None):
    """A configured ``SearchEngine`` when any robustness knob is set
    (segmented execution, checkpoint/resume), else ``None`` (the drivers
    fall back to the shared default engine; under ``--serve`` the
    service then builds its own engine around ``result_cache``)."""
    if not (args.segment_gens or args.checkpoint_dir):
        return None
    from repro.core.engine import SearchEngine

    # checkpointing only happens at segment boundaries, so a checkpoint
    # dir without an explicit segment length gets 1-generation segments
    return SearchEngine(
        mesh=mesh,
        segment_gens=args.segment_gens or (1 if args.checkpoint_dir else None),
        segment_retries=args.segment_retries,
        checkpoint_dir=args.checkpoint_dir or None,
        result_cache=result_cache,
        pipelined=args.pipelined,
    )


def serve(args, ws: WorkloadSet, mesh) -> int:
    """``--serve N``: drain N mixed requests through the DSE service.
    ``--serve-policy`` picks the scheduling policy (mixed priorities /
    deadlines are cycled into the request mix so the policy has work to
    do); ``--serve-async`` drains through the threaded
    ``AsyncDSEService`` front end instead of the synchronous queue.
    ``--retry-attempts``/``--retry-backoff`` arm the retry-with-backoff
    lane and ``--partial-results`` the anytime graceful-degradation path
    (quarantined / past-deadline requests resolve with their best-so-far
    instead of nothing)."""
    from repro.serve.dse import (
        AsyncDSEService,
        DSEService,
        RetryPolicy,
        paper_request_mix,
    )

    cache = None
    if args.result_cache:
        from repro.serve.cache import ResultCache

        cache = ResultCache(disk_dir=args.result_cache)
        print(f"[serve] result cache armed ({len(cache.disk_keys())} "
              f"entries on disk under {args.result_cache})")
    if args.stream_progress and not (args.segment_gens or args.checkpoint_dir):
        # streaming needs segment boundaries to emit at; segmented
        # execution is bit-identical to single-shot, so defaulting one
        # in changes no result
        args.segment_gens = 2
        print("[serve] --stream-progress: defaulting --segment-gens 2")
    engine = build_engine(args, mesh, result_cache=cache)
    on_progress = None
    if args.stream_progress:
        def on_progress(rid, snap):
            best = (f"{snap.top_scores[0]:.4g}" if len(snap.top_scores)
                    else "infeasible")
            print(f"[serve] rid {rid} partial @gen {snap.generations}: "
                  f"best-so-far {best}")
    retry = None
    if args.retry_attempts > 1:
        retry = RetryPolicy(max_attempts=args.retry_attempts,
                            backoff_s=args.retry_backoff)
    svc_kw = dict(engine=engine, mesh=mesh, policy=args.serve_policy,
                  retry=retry, partial_results=args.partial_results,
                  result_cache=cache,
                  pipelined=args.pipelined or None)
    mix_kw = {}
    if args.serve_policy == "priority":
        mix_kw["priorities"] = [3, 0, 1, 2]
    elif args.serve_policy == "edf":
        mix_kw["deadlines_s"] = [5.0, 60.0, 30.0, None]
    reqs = paper_request_mix(
        ws, args.serve, backend=args.backend, pop_size=args.pop,
        generations=args.gens, area_constr=args.area, **mix_kw,
    )
    results = {}
    t0 = time.time()
    if args.serve_async:
        with AsyncDSEService(**svc_kw) as svc:
            futs = [svc.submit(r, on_progress=on_progress) for r in reqs]
            print(f"[serve] {args.serve} heterogeneous requests submitted "
                  f"async (policy={args.serve_policy}, "
                  f"backend={args.backend}, "
                  f"slots={svc.service.engine.max_slots})")
            for fut in futs:
                res = fut.result()
                results[fut.rid] = res
                best = (f"{res.top_scores[0]:.4g}" if len(res.top_scores)
                        else "infeasible")
                print(f"[serve] rid {fut.rid}: {res.objective} on "
                      f"{','.join(res.workload_names)} -> best={best}")
        stats = svc.stats
    else:
        svc = DSEService(**svc_kw)
        rids = [svc.submit(r, on_progress=on_progress) for r in reqs]
        print(f"[serve] {args.serve} heterogeneous requests queued "
              f"(policy={args.serve_policy}, backend={args.backend}, "
              f"slots={svc.engine.max_slots})")
        # cache hits resolved AT submit — they never reach the queue, so
        # the stream below won't yield them
        for rid in rids:
            res = svc.results.get(rid)
            if res is not None:
                results[rid] = res
                best = (f"{res.top_scores[0]:.4g}" if len(res.top_scores)
                        else "infeasible")
                print(f"[serve] rid {rid}: {res.objective} on "
                      f"{','.join(res.workload_names)} -> best={best} "
                      f"(cache hit)")
        for rid, res in svc.stream():
            results[rid] = res
            best = (f"{res.top_scores[0]:.4g}" if len(res.top_scores)
                    else "infeasible")
            print(f"[serve] rid {rid}: {res.objective} on "
                  f"{','.join(res.workload_names)} -> best={best}")
        stats = svc.stats
    dt = time.time() - t0
    n_evald = args.serve * args.pop * (args.gens + 1)
    print(f"[serve] drained {len(results)} requests in {dt:.1f}s "
          f"({len(results)/dt:.1f} req/s, {n_evald/dt:.0f} designs/s, "
          f"{stats.launches} launches, wait p50/p99 "
          f"{_fmt(stats.wait_p(50))}/{_fmt(stats.wait_p(99))}s, "
          f"latency p50/p99 {_fmt(stats.latency_p(50))}/"
          f"{_fmt(stats.latency_p(99))}s, "
          f"{stats.deadline_misses} deadline misses)")
    print(f"[serve] faults: {stats.failures} failures, {stats.retries} "
          f"retries, {stats.partials} partials, {stats.abandoned} abandoned")
    eng = svc.service.engine if args.serve_async else svc.engine
    print(f"[serve] overlap: pipelined={'on' if args.pipelined else 'off'}, "
          f"dispatch->harvest gap p50 "
          f"{_fmt(stats.dispatch_gap_p(50), '.4f')}s, device idle "
          f"{stats.device_idle_s:.3f}s, "
          f"{getattr(eng, 'transfer_bytes', 0)} bytes harvested over "
          f"{getattr(eng, 'launches', 0)} engine launches")
    if cache is not None:
        print(f"[serve] cache: {stats.cache_hits} submit hits / "
              f"{stats.cache_misses} misses this drain "
              f"(hit rate {stats.cache_hit_rate():.1%}); tiers: "
              f"{cache.stats.summary()}")
    if args.out:
        payload = [
            {
                "rid": rid,
                "objective": res.objective,
                "workloads": list(res.workload_names),
                "best": float(res.top_scores[0]) if len(res.top_scores) else None,
                "best_design": res.top_designs[0] if res.top_designs else None,
            }
            for rid, res in sorted(results.items())
        ]
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[serve] wrote {args.out}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", default="", help="CNN names, comma-sep")
    ap.add_argument("--lm-workloads", default="", help="assigned arch ids")
    ap.add_argument("--mode", default="decode", choices=["decode", "prefill"])
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument(
        "--objective", default="ela",
        help="scalar objective family (ela/edp/e/l) or 'pareto' for "
             "NSGA-II front search: the result holds the --pareto-k best "
             "non-dominated designs in crowded order with their per-member "
             "(E, L, A) objective vectors",
    )
    ap.add_argument(
        "--pareto-k", type=int, default=10, metavar="K",
        help="--objective pareto: how many front members to return "
             "(crowded order, decoded-cell-deduped)",
    )
    ap.add_argument(
        "--backend", default="jnp", choices=["jnp", "pallas", "table"],
        help="cost-model evaluation backend: dense jnp oracle, the Pallas "
             "TPU kernel, or precomputed per-workload grid tables "
             "(layer-depth-independent eval; see imc/tables.py)",
    )
    ap.add_argument("--area", type=float, default=150.0)
    ap.add_argument("--pop", type=int, default=40)
    ap.add_argument("--gens", type=int, default=10)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--separate", action="store_true", help="also run per-workload baselines")
    ap.add_argument(
        "--search-mesh", default=None, metavar="SxP",
        help="(search, population) mesh, e.g. 8x1 — shard the batched "
             "programs over the visible devices",
    )
    ap.add_argument(
        "--serve", type=int, default=0, metavar="N",
        help="run the continuous-batching DSE service on N heterogeneous "
             "requests (mixed workload subsets / objectives / seeds) "
             "instead of the one-off joint search",
    )
    ap.add_argument(
        "--serve-policy", default="fifo", choices=["fifo", "priority", "edf"],
        help="--serve scheduling policy; priority/edf cycle mixed "
             "priorities / deadlines into the request mix",
    )
    ap.add_argument(
        "--serve-async", action="store_true",
        help="drain --serve through the threaded AsyncDSEService front "
             "end (submit returns futures) instead of the sync queue",
    )
    ap.add_argument(
        "--pipelined", action="store_true",
        help="transfer-thin pipelined execution: on-device top-k epilogue "
             "(only (top_k, n) genomes + scores + the convergence curve "
             "cross the wire; result.ga is None) and, under --serve, a "
             "double-buffered dispatch/harvest drain that overlaps host "
             "finalize with device compute — bit-identical results",
    )
    ap.add_argument(
        "--segment-gens", type=int, default=0, metavar="K",
        help="run each search as ceil(gens/K) segments of K generations "
             "(bit-identical to single-shot) so faults lose at most one "
             "segment of work; 0 = single-shot",
    )
    ap.add_argument(
        "--segment-retries", type=int, default=1,
        help="per-segment retry budget from the last good GA state "
             "before the engine gives up with an EngineFault",
    )
    ap.add_argument(
        "--checkpoint-dir", default="", metavar="DIR",
        help="persist segment boundaries under DIR; a re-run of the same "
             "plan resumes from the latest checkpoint (implies segmented "
             "execution, 1-generation segments if --segment-gens unset)",
    )
    ap.add_argument(
        "--retry-attempts", type=int, default=0, metavar="N",
        help="--serve: total launch attempts per request before it is "
             "abandoned (failed chunks re-plan each member in isolation, "
             "quarantining persistent offenders); <2 disables the retry "
             "lane",
    )
    ap.add_argument(
        "--retry-backoff", type=float, default=0.5, metavar="S",
        help="--serve: base retry backoff in seconds (exponential, "
             "deterministically jittered per rid)",
    )
    ap.add_argument(
        "--partial-results", action="store_true",
        help="--serve: resolve quarantined / past-deadline requests with "
             "their best-so-far anytime result (partial=True) instead of "
             "dropping them",
    )
    ap.add_argument(
        "--result-cache", default="", metavar="DIR",
        help="--serve: arm the fingerprint-keyed result cache with a disk "
             "tier under DIR — a request answered before (this process or "
             "any earlier one over DIR) resolves at submit with zero GA "
             "launches, bit-identical to a fresh search",
    )
    ap.add_argument(
        "--stream-progress", action="store_true",
        help="--serve: print each request's improving best-so-far after "
             "every guarded GA segment (implies segmented execution; "
             "defaults --segment-gens 2 if no boundary was chosen)",
    )
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh = None
    if args.search_mesh:
        from repro.launch.mesh import describe, make_search_mesh

        s, p = (int(v) for v in args.search_mesh.lower().split("x"))
        mesh = make_search_mesh(s, p)
        print(f"[search] mesh: {describe(mesh)} ({jax.device_count()} devices)")

    ws = build_workloads(args)
    print(f"[search] workloads: {ws.names} (L_max={ws.feats.shape[1]})")

    if args.serve:
        return serve(args, ws, mesh)

    if args.seeds < 1:
        ap.error("--seeds must be >= 1")
    # all seeds' joint searches run as ONE vmapped XLA program
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(args.seeds)])
    engine = build_engine(args, mesh)
    t0 = time.time()
    ress = joint_search_batched(
        keys, ws,
        objective=args.objective, area_constr=args.area,
        pop_size=args.pop, generations=args.gens,
        pareto_k=args.pareto_k,
        mesh=mesh, backend=args.backend, engine=engine,
        pipelined=args.pipelined or None,
    )
    dt_all = time.time() - t0
    n_evald = args.seeds * args.pop * (args.gens + 1)
    print(f"[search] {args.seeds} seed(s) in {dt_all:.1f}s "
          f"({n_evald/dt_all:.0f} designs/s vs paper's ~0.03/s)")

    results = []
    for seed, res in enumerate(ress):
        dt = dt_all / args.seeds
        best = f"{res.top_scores[0]:.4g}" if len(res.top_scores) else "infeasible"
        print(f"[search] seed {seed}: best={best}")
        if res.top_designs:
            print(f"         best design: {res.top_designs[0]}")
        entry = {
            "seed": seed,
            "joint_best": float(res.top_scores[0]) if len(res.top_scores) else None,
            "joint_top10": [float(s) for s in res.top_scores],
            "best_design": res.top_designs[0] if res.top_designs else None,
            "convergence": [float(c) for c in res.convergence],
            "wall_s": dt,
        }
        if res.objective_vectors is not None:
            # pareto mode: the k front members' (E, L, A) trade-off triples
            entry["pareto_front"] = [
                {"E_pj": float(v[0]), "L_ns": float(v[1]), "A_mm2": float(v[2])}
                for v in res.objective_vectors
            ]
            for j, v in enumerate(res.objective_vectors):
                print(f"         front[{j}]: E={v[0]:.4g}pJ L={v[1]:.4g}ns "
                      f"A={v[2]:.4g}mm2")
        if args.separate:
            key2 = jax.random.PRNGKey(seed + 1000)
            sep = separate_search(
                key2, ws,
                objective=args.objective, area_constr=args.area,
                pop_size=args.pop, generations=args.gens,
                mesh=mesh, backend=args.backend, engine=engine,
                pipelined=args.pipelined or None,
            )
            cross = {}
            for name, r in sep.items():
                if len(r.top_genomes):
                    s_all, res_all = rescore_designs(
                        r.top_genomes, ws,
                        objective=args.objective, area_constr=args.area,
                    )
                    failed = float(np.mean(~np.isfinite(s_all)))
                else:
                    failed = 1.0
                cross[name] = {
                    "own_best": float(r.top_scores[0]) if len(r.top_scores) else None,
                    "failed_frac_on_all": failed,
                }
            entry["separate"] = cross
            print(f"         separate: {json.dumps(cross)}")
        results.append(entry)

    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[search] wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
