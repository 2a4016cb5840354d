"""Fingerprint-keyed request-level result cache for the DSE service.

Millions of users means massive request overlap, and the cheapest
throughput is not launching at all: ``WorkloadSet.fingerprint()`` already
content-keys table packing, and this module extends the same idea to the
full request — ``request_key`` is a sha256 over EVERYTHING that
determines a search's result bits

    (workload fingerprint, tech constants, objective / exponent weights,
     area constraint, backend, pop size, generations, top_k, pareto_k,
     the raw PRNG key bytes, and any explicit init population)

and deliberately over nothing else: ``priority`` and ``deadline_s`` are
scheduling metadata (they reorder launches, never change a result bit —
the same invariant ``SearchRequest.signature()`` pins for program
shapes), and ``SearchRequest.seed`` enters only through the PRNG key
bytes it derives, so ``seed=3`` and ``key=PRNGKey(3)`` are the SAME
cache entry while an explicit ``key=`` override is its own.

``ResultCache`` maps that key to a finalized ``SearchResult`` through
two tiers:

  * an in-memory LRU front (``capacity`` entries, thread-safe — the
    async service's worker and client threads share one instance), and
  * an optional on-disk tier under ``disk_dir/<request_key>`` reusing
    ``checkpoint.store``'s atomic write/commit-marker/scan machinery: a
    crash mid-write never corrupts an entry, a fresh process over the
    same directory serves bit-identical results, and memory evictions
    never touch disk (the disk tier is the larger, durable one).

Only FULL results are cached: ``partial=True`` snapshots (deadline
sweeps, quarantine, mid-search streams) are anytime views of an
unfinished search, never a request's answer.  ``valid=False`` full-budget
results (every design infeasible) ARE cached — re-searching cannot
un-infeasible them.  Thin full results (``ga=None`` — what the pipelined
engine and pareto requests produce) ARE cached too: they round-trip with
an empty-history marker, so ``pipelined=True`` + ``result_cache``
resolves a resubmitted drain with zero GA launches.

Wired in two places (see ``core.engine.SearchEngine(result_cache=)`` and
``serve.dse.DSEService(result_cache=)``): the engine persists per-request
entries as plans complete — keyed independently of chunk-mates, unlike
the checkpoint tier's ``plan_key`` — and the service resolves hits at
submit, so a repeated request costs zero GA launches.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
from collections import OrderedDict
from pathlib import Path
from typing import Dict, List, Optional, Union

import numpy as np

from repro.core import space
from repro.core.engine import SearchRequest, SearchResult
from repro.core.ga import GAResult

# fixed leaf layout of one serialized entry: jax.tree flattens dicts in
# sorted-key order, so "arrays" (8 leaves, fixed order) precede "meta".
# Thin (ga=None) and non-pareto entries keep the SAME leaf count with
# empty placeholder arrays — the layout never varies per entry, so
# ``checkpoint.store.restore`` always sees one template.
_ARRAY_FIELDS = 8
_TEMPLATE = {"arrays": [0] * _ARRAY_FIELDS, "meta": 0}
_EMPTY = np.zeros((0,), np.float32)


def request_key(req: SearchRequest) -> str:
    """Content key of one request's RESULT (not its program shape).

    Everything that can change a result bit is hashed; scheduling
    metadata (``priority``, ``deadline_s``) is excluded by design — see
    the module docstring.  ``objective`` is hashed even when
    ``obj_weights`` overrides it (conservative: a spurious miss is
    correct, a spurious hit never is).  Two process-level knobs also
    enter the key because they change result bits for identical request
    fields: ``imc.COST_MODEL_VERSION`` (a persisted disk tier must never
    serve entries computed under an older model's math) and
    ``space.grid_token()`` (the active grid density redefines what a
    genome decodes to)."""
    from repro.imc import COST_MODEL_VERSION

    h = hashlib.sha256()
    h.update(COST_MODEL_VERSION.encode())
    h.update(space.grid_token().encode())
    h.update(req.ws.fingerprint().encode())
    h.update(repr((
        req.objective, req.obj_weights, float(req.area_constr),
        req.backend, int(req.pop_size), int(req.generations),
        int(req.top_k), int(req.pareto_k), req.tech,
    )).encode())
    h.update(req.key_data().tobytes())
    if req.init_genomes is not None:
        init = np.ascontiguousarray(np.asarray(req.init_genomes, np.float32))
        h.update(repr(init.shape).encode())
        h.update(init.tobytes())
    return h.hexdigest()


def _encode(res: SearchResult) -> dict:
    """SearchResult -> a pytree of numpy leaves ``checkpoint.store`` can
    write (non-array fields ride as a JSON byte leaf).  Thin results
    (``ga is None`` — the pipelined engine's full answers) serialize
    empty placeholder leaves for the history fields and a ``thin`` meta
    flag, so the leaf layout stays fixed; ``objective_vectors`` (pareto
    fronts) rides the same way behind a ``vectors`` flag."""
    thin = res.ga is None
    vecs = res.objective_vectors
    meta = {
        "workload_names": list(res.workload_names),
        "objective": res.objective,
        "valid": bool(res.valid),
        "generations": int(res.generations),
        "thin": thin,
        "vectors": vecs is not None,
    }
    arrays = [
        _EMPTY if thin else np.asarray(res.ga.genomes),
        _EMPTY if thin else np.asarray(res.ga.scores),
        _EMPTY if thin else np.asarray(res.ga.best_genome),
        _EMPTY if thin else np.asarray(res.ga.best_score),
        np.asarray(res.top_scores), np.asarray(res.top_genomes),
        np.asarray(res.convergence),
        _EMPTY if vecs is None else np.asarray(vecs),
    ]
    blob = np.frombuffer(json.dumps(meta).encode(), np.uint8)
    return {"arrays": arrays, "meta": blob}


def _decode(tree: dict) -> SearchResult:
    meta = json.loads(bytes(np.asarray(tree["meta"]).tobytes()).decode())
    g, s, bg, bs, ts, tg, cv, ov = tree["arrays"]
    ga = (
        None if meta.get("thin")
        else GAResult(genomes=g, scores=s, best_genome=bg, best_score=bs)
    )
    # top_designs are a pure function of top_genomes — recomputed, not
    # serialized, so the dict form can never drift from the arrays
    designs: List[Dict[str, float]] = (
        space.design_dicts_from_indices(space.decode_indices_np(np.asarray(tg)))
        if np.asarray(tg).size else []
    )
    return SearchResult(
        workload_names=tuple(meta["workload_names"]),
        objective=meta["objective"],
        ga=ga,
        top_designs=designs,
        top_scores=np.asarray(ts),
        top_genomes=np.asarray(tg),
        convergence=np.asarray(cv),
        valid=bool(meta["valid"]),
        partial=False,
        generations=int(meta["generations"]),
        objective_vectors=np.asarray(ov) if meta.get("vectors") else None,
    )


@dataclasses.dataclass
class CacheStats:
    hits: int = 0          # memory-tier hits
    disk_hits: int = 0     # disk-tier hits (promoted into memory)
    misses: int = 0
    puts: int = 0
    evictions: int = 0     # memory-tier LRU evictions (disk untouched)

    def hit_rate(self) -> float:
        """Fraction of lookups served from EITHER tier (0.0 when no
        lookups yet — a cold cache reports 0, not NaN)."""
        served = self.hits + self.disk_hits
        total = served + self.misses
        return served / total if total else 0.0

    def summary(self) -> Dict[str, Union[int, float]]:
        out: Dict[str, Union[int, float]] = dataclasses.asdict(self)
        out["hit_rate"] = self.hit_rate()
        return out


class ResultCache:
    """Two-tier (LRU memory + optional disk) ``request_key`` -> finalized
    ``SearchResult`` store.  ``get``/``put`` take a ``SearchRequest`` (or
    a precomputed key string); a disk hit is promoted into the memory
    tier.  Thread-safe; disk writes are atomic (``checkpoint.store``)."""

    def __init__(self, capacity: int = 1024,
                 disk_dir: Optional[Union[str, Path]] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.disk_dir = None if disk_dir is None else Path(disk_dir)
        self._mem: "OrderedDict[str, SearchResult]" = OrderedDict()
        self._lock = threading.RLock()
        self.stats = CacheStats()

    # ------------------------------------------------------------------ keys
    @staticmethod
    def key(req: SearchRequest) -> str:
        return request_key(req)

    def _as_key(self, req_or_key: Union[SearchRequest, str]) -> str:
        return req_or_key if isinstance(req_or_key, str) else request_key(req_or_key)

    # ----------------------------------------------------------------- tiers
    def get(self, req_or_key: Union[SearchRequest, str]) -> Optional[SearchResult]:
        key = self._as_key(req_or_key)
        with self._lock:
            hit = self._mem.get(key)
            if hit is not None:
                self._mem.move_to_end(key)
                self.stats.hits += 1
                return hit
            res = self._disk_get(key)
            if res is not None:
                self.stats.disk_hits += 1
                self._mem_put(key, res)  # promote
                return res
            self.stats.misses += 1
            return None

    def put(self, req_or_key: Union[SearchRequest, str],
            res: SearchResult) -> bool:
        """Insert a FULL result; ``partial=True`` snapshots are refused
        (returns False) — an anytime snapshot must never shadow the
        request's real answer.  Thin full results (``ga is None``, the
        pipelined engine's complete answers) ARE cached: their top-k /
        convergence / vector fields are the whole deliverable, and the
        history was never materialized to begin with."""
        if res.partial:
            return False
        key = self._as_key(req_or_key)
        with self._lock:
            self.stats.puts += 1
            self._mem_put(key, res)
            self._disk_put(key, res)
        return True

    def _mem_put(self, key: str, res: SearchResult) -> None:
        self._mem[key] = res
        self._mem.move_to_end(key)
        while len(self._mem) > self.capacity:
            self._mem.popitem(last=False)
            self.stats.evictions += 1

    # ------------------------------------------------------------- disk tier
    def _disk_get(self, key: str) -> Optional[SearchResult]:
        if self.disk_dir is None:
            return None
        from repro.checkpoint import store

        d = self.disk_dir / key
        if store.latest_step(d) is None:
            return None
        tree, _ = store.restore(d, _TEMPLATE)
        return _decode(tree)

    def _disk_put(self, key: str, res: SearchResult) -> None:
        if self.disk_dir is None:
            return
        from repro.checkpoint import store

        d = self.disk_dir / key
        if store.latest_step(d) is not None:
            return  # content-keyed: an existing committed entry is this one
        store.save(d, 0, _encode(res))

    # ------------------------------------------------------------ inspection
    def __len__(self) -> int:
        with self._lock:
            return len(self._mem)

    def __contains__(self, req_or_key) -> bool:
        key = self._as_key(req_or_key)
        with self._lock:
            if key in self._mem:
                return True
        return self._disk_get(key) is not None if self.disk_dir else False

    def mem_keys(self) -> List[str]:
        """Memory-tier keys, LRU-first (next-to-evict first)."""
        with self._lock:
            return list(self._mem)

    def disk_keys(self) -> List[str]:
        """Committed disk-tier keys (``checkpoint.store.scan``)."""
        if self.disk_dir is None:
            return []
        from repro.checkpoint import store

        return store.scan(self.disk_dir)

    def clear(self, *, disk: bool = False) -> None:
        """Drop the memory tier; ``disk=True`` also removes every
        committed disk entry (explicit — eviction never implies it)."""
        with self._lock:
            self._mem.clear()
            if disk and self.disk_dir is not None:
                from repro.checkpoint import store

                for key in store.scan(self.disk_dir):
                    store.clear(self.disk_dir / key)
