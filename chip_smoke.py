#!/usr/bin/env python3
"""Bring-up smoke of the DSE service on a TPU.

Run from the checkout root, one process, nothing else on the chip:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded search on a 4-chip host

One chip: two drains of 64 heterogeneous table-backend requests at
P=1024, G=100 over the four paper CNNs through a pipelined ``DSEService``
(cold, then warm with fresh seeds); the warm results rescored by the dense
``jnp`` oracle; one population scored by the compiled Pallas ``imc_eval``
kernel against the oracle; and one ``objective="pareto"`` request sized so
its front epilogue fits the chip.  ``--four-chips`` runs only a 64-seed
joint ``batched_search`` on a (search=4, data=1) mesh and the same search
on one device, and compares them.

Any exception or failed check exits non-zero before the last line, which
is the JSON ``{"ok": true, "device": {...}}`` record.  It exits non-zero
when JAX finds no TPU.  Times printed are single smoke readings, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import space  # noqa: E402
from repro.core.engine import SearchEngine, SearchRequest  # noqa: E402
from repro.core.ga import (  # noqa: E402
    PARETO_BUDGETS,
    PARETO_MASK_SHARE,
    pareto_mask_bytes,
    pick_pareto_budget,
)
from repro.core.objectives import make_objective  # noqa: E402
from repro.core.search import joint_search_batched, rescore_designs  # noqa: E402
from repro.imc.cost import evaluate_designs_arrays  # noqa: E402
from repro.imc.tech import TECH  # noqa: E402
from repro.kernels.imc_eval.ops import evaluate_designs_kernel_arrays  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import describe, make_search_mesh  # noqa: E402
from repro.serve.dse import DSEService, paper_request_mix  # noqa: E402
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload  # noqa: E402
from repro.workloads.pack import pack_workloads  # noqa: E402

SLOTS, POP, GENS = 64, 1024, 100  # one full service launch of a real sweep
RTOL = 1e-5  # tests/test_tables.py's table-vs-jnp tolerance

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Executables built since start, from ``jax.monitoring`` events:
    ``programs`` counts every backend compile request (a fresh XLA
    compile or a persistent-cache load), ``cache_hits`` the loads."""

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.programs += 1

    def _on_event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def mark(self):
        return self.programs, self.cache_hits

    def since(self, mark):
        return self.programs - mark[0], self.cache_hits - mark[1]


def paper_set():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def device_record() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# --------------------------------------------------------------- one chip
def drain(svc: DSEService, reqs, counter: CompileCounter, label: str,
          pop: int, gens: int):
    """Submit ``reqs``, stream them out, check every result; returns
    [(request, result)] in submit order."""
    mark = counter.mark()
    t0 = time.perf_counter()
    rids = svc.submit_all(reqs)
    out = dict(svc.stream())
    dt = time.perf_counter() - t0
    programs, hits = counter.since(mark)
    check(set(out) == set(rids),
          f"{label} drain returned {len(out)}/{len(rids)} results")
    pairs = [(r, out[rid]) for rid, r in zip(rids, reqs)]
    n_ok = sum(res.valid and not res.partial and len(res.top_scores) > 0
               and bool(np.isfinite(res.top_scores).all()) for _, res in pairs)
    st = svc.stats
    say(f"{label} drain: {n_ok}/{len(reqs)} valid results with finite "
        f"top_scores; failures={st.failures} partials={st.partials} "
        f"abandoned={st.abandoned}")
    evals = len(reqs) * pop * (gens + 1)
    say(f"{label} drain (smoke reading, not a benchmark): {dt:.3f} s wall, "
        f"{evals / dt:.0f} GA designs/s, {programs} programs built "
        f"({hits} from the persistent cache)")
    check(n_ok == len(reqs), f"{label} drain: only {n_ok}/{len(reqs)} valid")
    check_no_faults(svc, f"{label} drain")
    return pairs, programs


def check_no_faults(svc: DSEService, label: str) -> None:
    st = svc.stats
    check(st.failures == st.partials == st.abandoned == 0,
          f"{label}: service faults {st.summary()}")


def rel_diff(a, b) -> float:
    """Largest |a - b| / |b| over the entries where a and b differ."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    d = np.where(a == b, 0.0, np.abs(a - b) / np.maximum(np.abs(b), 1e-300))
    return float(d.max(initial=0.0))


def oracle_rescore(pairs) -> None:
    """Every warm result's top designs rescored by the dense jnp oracle
    on the request's own workload set, objective and area."""
    worst = 0.0
    for req, res in pairs:
        ref, r = rescore_designs(res.top_genomes, req.ws,
                                 objective=req.objective,
                                 area_constr=req.area_constr, tech=req.tech)
        bad = np.flatnonzero(~np.isfinite(ref))
        check(bad.size == 0,
              f"the oracle scores returned design {res.top_designs[bad[0]]} "
              f"infeasible ({req.objective} on {req.ws.names}): valid="
              f"{np.asarray(r.valid)[bad[0]]} fits={np.asarray(r.fits)[bad[0]]} "
              f"area={np.asarray(r.area_mm2)[bad[0]]}" if bad.size else "")
        worst = max(worst, rel_diff(res.top_scores, ref))
        np.testing.assert_allclose(res.top_scores, ref, rtol=RTOL)
    say(f"table vs jnp oracle: {len(pairs)} requests' top designs agree, "
        f"max rel diff {worst:.3e} (rtol {RTOL})")


def pallas_vs_oracle(ws, pairs, pop: int) -> None:
    """One population scored by the compiled imc_eval kernel
    (``interpret=False``) and by the dense jnp oracle: the warm results'
    top designs, so many are feasible, topped up with random genomes."""
    top = np.concatenate([res.top_genomes for _, res in pairs])[:pop]
    g = jnp.concatenate([
        jnp.asarray(top, jnp.float32),
        space.random_genomes(jax.random.PRNGKey(7), pop - len(top))])
    kern = jax.jit(lambda g, f, m: evaluate_designs_kernel_arrays(
        space.decode(g), f, m, TECH, interpret=False))
    compiled = kern.lower(g, ws.feats, ws.mask).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "the pallas backend's HLO has no tpu_custom_call")
    r_k = compiled(g, ws.feats, ws.mask)
    r_j = jax.jit(lambda g, f, m: evaluate_designs_arrays(
        space.decode(g), f, m, TECH))(g, ws.feats, ws.mask)
    worst = 0.0
    for field in ("energy_pj", "latency_ns", "area_mm2", "util"):
        a, b = np.asarray(getattr(r_k, field)), np.asarray(getattr(r_j, field))
        worst = max(worst, rel_diff(a, b))
        np.testing.assert_allclose(a, b, rtol=RTOL, err_msg=field)
    np.testing.assert_array_equal(np.asarray(r_k.fits), np.asarray(r_j.fits))
    obj = make_objective("ela")
    s_k, s_j = np.asarray(obj(r_k)), np.asarray(obj(r_j))
    fin = np.isfinite(s_j)
    np.testing.assert_array_equal(np.isfinite(s_k), fin)
    check(bool(fin.any()), "no feasible design in the pallas population")
    np.testing.assert_allclose(s_k[fin], s_j[fin], rtol=RTOL)
    say(f"pallas (compiled, tpu_custom_call in HLO) vs jnp oracle at P={pop}: "
        f"agree, max rel diff {worst:.3e}, {int(fin.sum())} feasible designs")


def pareto_phase(svc: DSEService, ws, device_bytes: int):
    cap = PARETO_MASK_SHARE * device_bytes
    for p, g in PARETO_BUDGETS:
        mb = pareto_mask_bytes(p, g)
        say(f"pareto budget P={p} G={g}: N=(G+1)P={(g + 1) * p}, dominance "
            f"masks {mb / 2**30:.2f} GiB vs {cap / 2**30:.2f} GiB "
            f"({PARETO_MASK_SHARE:.0%} of device memory) -> "
            f"{'fits' if mb <= cap else 'no'}")
    p, g = pick_pareto_budget(device_bytes)
    req = SearchRequest(ws=ws, objective="pareto", backend="table",
                        pop_size=p, generations=g, seed=4242)
    t0 = time.perf_counter()
    rid = svc.submit(req)
    out = dict(svc.stream())
    dt = time.perf_counter() - t0
    check(rid in out, "pareto request did not come back")
    check_no_faults(svc, "pareto")
    res = out[rid]
    vec = res.objective_vectors
    check(res.valid and vec is not None and len(vec) > 0,
          "pareto front is empty")
    check(bool(np.isfinite(vec).all()), "pareto front has non-finite (E, L, A)")
    say(f"pareto P={p} G={g}: front of {len(vec)} members, all (E, L, A) "
        f"finite; {dt:.3f} s wall incl. compile (smoke reading)")


def one_chip() -> None:
    counter = CompileCounter()
    ws = paper_set()
    svc = DSEService(pipelined=True, max_slots=SLOTS)
    cold, cold_programs = drain(
        svc, paper_request_mix(ws, SLOTS, backend="table", pop_size=POP,
                               generations=GENS),
        counter, "cold", POP, GENS)
    warm, warm_programs = drain(
        svc, paper_request_mix(ws, SLOTS, backend="table", pop_size=POP,
                               generations=GENS, seed0=SLOTS),
        counter, "warm", POP, GENS)
    n_valid = sum(res.valid for _, res in cold + warm)
    say(f"service: {n_valid}/{len(cold) + len(warm)} valid results over "
        f"both drains; {warm_programs} programs built in the warm drain")
    check(warm_programs == 0,
          f"the warm drain built {warm_programs} programs")
    oracle_rescore(warm)
    pallas_vs_oracle(ws, warm, POP)
    device_bytes = int(jax.devices()[0].memory_stats()["bytes_limit"])
    pareto_phase(svc, ws, device_bytes)
    say(f"compiles over the whole run: {counter.programs} programs built, "
        f"{counter.cache_hits} from the persistent cache")


# ------------------------------------------------------------ four chips
class _PlacementProbe(SearchEngine):
    """A pipelined engine that records how many devices each launch's
    on-device outputs span, before harvesting them."""

    def __init__(self, **kw):
        super().__init__(pipelined=True, **kw)
        self.output_devices = []

    def harvest(self, pending):
        self.output_devices.append(
            len(pending.thin.top_genomes.sharding.device_set))
        return super().harvest(pending)


def four_chips() -> None:
    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    ws = paper_set()
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(SLOTS)])
    kw = dict(backend="table", pop_size=POP, generations=GENS)
    mesh = make_search_mesh(4, 1)
    say(f"mesh {describe(mesh)}: {SLOTS} seeds, P={POP}, G={GENS}")

    runs = {}
    for label, m in (("sharded", mesh), ("one device", None)):
        eng = _PlacementProbe()
        t0 = time.perf_counter()
        res = joint_search_batched(keys, ws, mesh=m, engine=eng, **kw)
        dt = time.perf_counter() - t0
        runs[label] = (res, eng.output_devices)
        say(f"{label}: {dt:.3f} s wall incl. compile (smoke reading), "
            f"outputs on {eng.output_devices} devices per launch")
    (sh, sh_devs), (one, one_devs) = runs["sharded"], runs["one device"]
    check(sh_devs == [4], f"sharded outputs span {sh_devs} devices, not [4]")
    check(one_devs == [1], f"unsharded outputs span {one_devs} devices")
    check(all(r.valid for r in sh + one), "an invalid search result")
    same_top1 = sum(np.array_equal(a.top_genomes[0], b.top_genomes[0])
                    for a, b in zip(sh, one))
    bit_equal = sum(np.array_equal(a.top_scores, b.top_scores)
                    for a, b in zip(sh, one))
    worst = max(rel_diff(a.top_scores, b.top_scores) for a, b in zip(sh, one))
    say(f"sharded vs one device: top-1 genome equal in {same_top1}/{SLOTS}, "
        f"top_scores bit-equal in {bit_equal}/{SLOTS}, max rel diff "
        f"{worst:.3e}")
    check(same_top1 == SLOTS, "sharded top-1 genomes differ from one device")
    for a, b in zip(sh, one):
        np.testing.assert_allclose(a.top_scores, b.top_scores, rtol=RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded search on 4 chips and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    dev = device_record()
    say(f"devices: {jax.devices()}")
    say(f"platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX finds no TPU; nothing was run", file=sys.stderr)
        return 1
    say(f"compile cache: {enable_compile_cache()}")
    try:
        if args.four_chips:
            four_chips()
        else:
            one_chip()
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
