"""Anytime fault-tolerant execution: segmented GA + engine.

The contract this module pins (ISSUE: robustness PR):

  * **Bit-parity** — N segment launches of k generations through
    ``run_ga_segment`` / ``run_ga_batched_segment`` reproduce a single
    ``run_ga`` of N*k generations bit-for-bit (same history, same best),
    for any split of the budget, odd populations, ragged final segments,
    batched element-wise, on every backend, and under the fake-8-device
    (search, population) mesh.
  * **Guarded retry** — a transient segment failure (exception or NaN
    scores) re-launches from the last good ``GAState`` and the recovered
    run is STILL bit-identical; exhausted retries raise ``EngineFault``
    carrying per-request anytime partial results.
  * **Kill/resume** — a run killed mid-drain (KeyboardInterrupt) leaves
    a committed on-disk checkpoint; a fresh engine re-executing the same
    plan resumes from it and finishes bit-identical to an uninterrupted
    run, then clears its own checkpoint directory.
  * **Finite-score guard** — a history with no finite score finalizes as
    ``valid=False`` instead of silently returning garbage designs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core import space
from repro.core.engine import (
    EngineFault,
    NonFiniteScoreError,
    SearchEngine,
    SearchRequest,
    _finalize,
    empty_partial_result,
    plan_batch,
    plan_key,
)
from repro.core.ga import (
    GAResult,
    GAState,
    init_ga_state,
    init_ga_state_batched,
    run_ga,
    run_ga_batched,
    run_ga_batched_segment,
    run_ga_segment,
)
from repro.serve.dse import DSEService
from repro.workloads.cnn import cnn_workload
from repro.workloads.pack import pack_workloads

POP, GENS = 8, 6


@pytest.fixture(scope="module")
def ws():
    return pack_workloads(
        [(n, cnn_workload(n)) for n in ("resnet18", "vgg16")]
    )


def _toy_eval(genomes):
    # cheap deterministic objective; module-level so the jit caches hit
    return jnp.sum((genomes - 0.3) ** 2, axis=-1)


def _init(seed, pop):
    return space.random_genomes(jax.random.PRNGKey(1000 + seed), pop)


def _chain(key, init, splits, total, *, pop):
    """init + segment launches over ``splits``; returns the accumulated
    (total+1, P, n)/(total+1, P) history exactly as the engine builds it."""
    st = init_ga_state(key, _toy_eval, init)
    hg = [np.asarray(st.genomes)[None]]
    hs = [np.asarray(st.scores)[None]]
    for k in splits:
        st, (g, s) = run_ga_segment(
            st, _toy_eval, generations=k, total_generations=total
        )
        hg.append(np.asarray(g))
        hs.append(np.asarray(s))
    return st, np.concatenate(hg), np.concatenate(hs)


# ------------------------------------------------------------ GA-level parity
@pytest.mark.parametrize("splits", [(6,), (3, 3), (2, 2, 2), (1, 5), (4, 2)],
                         ids=lambda s: "+".join(map(str, s)))
def test_ga_segments_bit_identical_to_single_shot(splits):
    key = jax.random.PRNGKey(7)
    init = _init(0, POP)
    full = run_ga(key, _toy_eval, pop_size=POP, generations=GENS,
                  init_genomes=init + 0)  # donated: pass a copy
    st, hg, hs = _chain(key, init, splits, GENS, pop=POP)
    np.testing.assert_array_equal(hg, np.asarray(full.genomes))
    np.testing.assert_array_equal(hs, np.asarray(full.scores))
    # the state's counter walked the whole budget; the history's argmin
    # (what _finalize consumes) equals the single-shot best
    assert int(np.asarray(st.gen)) == GENS
    b = int(np.argmin(hs.reshape(-1)))
    np.testing.assert_array_equal(
        hg.reshape(-1, hg.shape[-1])[b], np.asarray(full.best_genome)
    )
    assert hs.reshape(-1)[b] == float(full.best_score)


def test_ga_segments_odd_population():
    pop = 17  # odd P exercises the extra-pair/truncate path per segment
    key = jax.random.PRNGKey(3)
    init = _init(1, pop)
    full = run_ga(key, _toy_eval, pop_size=pop, generations=5,
                  init_genomes=init + 0)
    _, hg, hs = _chain(key, init, (2, 2, 1), 5, pop=pop)
    np.testing.assert_array_equal(hg, np.asarray(full.genomes))
    np.testing.assert_array_equal(hs, np.asarray(full.scores))


def test_ga_batched_segments_bit_identical():
    B = 3
    keys = jax.random.split(jax.random.PRNGKey(11), B)
    init = jnp.stack([_init(10 + b, POP) for b in range(B)])
    full = run_ga_batched(keys, _toy_eval, pop_size=POP, generations=GENS,
                          init_genomes=init + 0)
    st = init_ga_state_batched(keys, _toy_eval, init)
    hg = [np.asarray(st.genomes)[:, None]]
    hs = [np.asarray(st.scores)[:, None]]
    for k in (2, 3, 1):
        st, (g, s) = run_ga_batched_segment(
            st, _toy_eval, generations=k, total_generations=GENS
        )
        hg.append(np.asarray(g))
        hs.append(np.asarray(s))
    np.testing.assert_array_equal(np.concatenate(hg, axis=1),
                                  np.asarray(full.genomes))
    np.testing.assert_array_equal(np.concatenate(hs, axis=1),
                                  np.asarray(full.scores))
    # batched elements match the unbatched chain element-wise
    _, hg0, hs0 = _chain(keys[0], init[0], (2, 3, 1), GENS, pop=POP)
    np.testing.assert_array_equal(np.concatenate(hg, axis=1)[0], hg0)
    np.testing.assert_array_equal(np.concatenate(hs, axis=1)[0], hs0)


def test_ga_segment_does_not_donate_state():
    # a failed launch must be able to re-run from the same state
    st = init_ga_state(jax.random.PRNGKey(0), _toy_eval, _init(2, POP))
    before = np.asarray(st.genomes).copy()
    a = run_ga_segment(st, _toy_eval, generations=2, total_generations=4)
    b = run_ga_segment(st, _toy_eval, generations=2, total_generations=4)
    np.testing.assert_array_equal(np.asarray(a[1][1]), np.asarray(b[1][1]))
    np.testing.assert_array_equal(np.asarray(st.genomes), before)


# -------------------------------------------------------- engine-level parity
def _reqs(ws, n, backend, *, gens=GENS, seed0=0):
    subsets = [[0, 1], [0], [1]]
    return [
        SearchRequest(ws=ws.subset(subsets[i % 3]), seed=seed0 + i,
                      backend=backend, pop_size=POP, generations=gens)
        for i in range(n)
    ]


def _assert_result_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.ga.scores),
                                  np.asarray(b.ga.scores))
    np.testing.assert_array_equal(np.asarray(a.ga.genomes),
                                  np.asarray(b.ga.genomes))
    np.testing.assert_array_equal(a.top_scores, b.top_scores)
    np.testing.assert_array_equal(a.top_genomes, b.top_genomes)
    assert float(a.ga.best_score) == float(b.ga.best_score)
    assert a.valid == b.valid and a.generations == b.generations


@pytest.mark.parametrize("backend", ["table", "jnp", "pallas"])
def test_segmented_engine_matches_single_shot(ws, backend):
    n = 1 if backend == "pallas" else 3
    reqs = _reqs(ws, n, backend)
    ref = SearchEngine().run(reqs)
    out = SearchEngine(segment_gens=2).run(reqs)
    for a, b in zip(out, ref):
        _assert_result_equal(a, b)
        assert not a.partial and a.generations == GENS


def test_segmented_engine_ragged_final_segment(ws):
    reqs = _reqs(ws, 2, "table")  # 6 = 4 + ragged 2
    ref = SearchEngine().run(reqs)
    out = SearchEngine(segment_gens=4).run(reqs)
    for a, b in zip(out, ref):
        _assert_result_equal(a, b)


def test_segment_gens_at_or_above_budget_uses_single_shot(ws):
    # k >= G falls back to the original one-launch path (same results by
    # construction; pin that it doesn't take the segment path at all)
    eng = SearchEngine(segment_gens=GENS)
    reqs = _reqs(ws, 1, "table")
    ref = SearchEngine().run(reqs)
    _assert_result_equal(eng.run(reqs)[0], ref[0])


@pytest.mark.multidevice
def test_segmented_engine_sharded_parity(ws):
    from repro.launch.mesh import make_search_mesh

    reqs = _reqs(ws, 4, "table")
    ref = SearchEngine().run(reqs)
    eng = SearchEngine(mesh=make_search_mesh(2, 4), segment_gens=2)
    for a, b in zip(eng.run(reqs), ref):
        _assert_result_equal(a, b)


# ------------------------------------------------------------- guarded retry
def test_transient_segment_failure_retries_bit_identical(ws, monkeypatch):
    reqs = _reqs(ws, 2, "table")
    ref = SearchEngine(segment_gens=2).run(reqs)
    real = engine_mod.run_ga_batched_segment
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected transient launch failure")
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", flaky)
    out = SearchEngine(segment_gens=2, segment_retries=1).run(reqs)
    assert calls["n"] == 4  # 3 segments + 1 retried
    for a, b in zip(out, ref):
        _assert_result_equal(a, b)


def test_nan_segment_guard_retries_from_last_good_state(ws, monkeypatch):
    reqs = _reqs(ws, 2, "table")
    ref = SearchEngine(segment_gens=2).run(reqs)
    real = engine_mod.run_ga_batched_segment
    calls = {"n": 0}

    def poisoned_once(*a, **kw):
        calls["n"] += 1
        st, (hg, hs) = real(*a, **kw)
        if calls["n"] == 1:
            return st, (hg, jnp.full_like(hs, jnp.nan))
        return st, (hg, hs)

    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", poisoned_once)
    out = SearchEngine(segment_gens=2, segment_retries=1).run(reqs)
    for a, b in zip(out, ref):
        _assert_result_equal(a, b)


def test_exhausted_retries_raise_fault_with_partials(ws, monkeypatch):
    reqs = _reqs(ws, 2, "table")

    def always_fails(*a, **kw):
        raise RuntimeError("injected permanent failure")

    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", always_fails)
    eng = SearchEngine(segment_gens=2, segment_retries=1)
    with pytest.raises(EngineFault) as ei:
        eng.run(reqs)
    fault = ei.value
    assert fault.generations_done == 0
    assert fault.partials is not None and len(fault.partials) == len(reqs)
    for p, r in zip(fault.partials, reqs):
        # only the seed evaluation ran: an anytime result over generation 0
        assert p.partial and p.generations == 0
        assert p.workload_names == r.ws.names
        assert p.convergence.shape == (1,)
        # seeds can all be area-infeasible (+inf): valid iff a finite
        # score exists, and whatever made the top list is finite
        assert p.valid == bool(p.top_scores.size)
        assert np.isfinite(p.top_scores).all()


def test_nan_seed_evaluation_raises(ws, monkeypatch):
    def nan_seed_state(keys, eval_fn, init, ctx=None):
        st = init_ga_state_batched(keys, eval_fn, init, ctx=ctx)
        return GAState(genomes=st.genomes,
                       scores=jnp.full_like(st.scores, jnp.nan),
                       key=st.key, gen=st.gen)

    monkeypatch.setattr(engine_mod, "init_ga_state_batched", nan_seed_state)
    eng = SearchEngine(segment_gens=2)
    with pytest.raises(NonFiniteScoreError, match="seed"):
        eng.run(_reqs(ws, 1, "table"))


# --------------------------------------------------------------- kill/resume
def test_kill_resume_from_disk_bit_identical(ws, tmp_path, monkeypatch):
    """The acceptance drill: a drain killed after a checkpointed segment
    resumes from disk in a FRESH engine and produces the same final bests
    as an uninterrupted run — then clears its own checkpoint."""
    from repro.checkpoint import store

    reqs = _reqs(ws, 2, "table", seed0=50)
    ref = SearchEngine(segment_gens=2).run(reqs)
    ck_root = tmp_path / "ck"
    real = engine_mod.run_ga_batched_segment
    calls = {"n": 0}

    def killed_on_second(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise KeyboardInterrupt()  # SIGINT mid-drain
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", killed_on_second)
    eng = SearchEngine(segment_gens=2, checkpoint_dir=str(ck_root))
    with pytest.raises(KeyboardInterrupt):
        eng.run(reqs)
    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", real)

    # segment 1 committed its checkpoint before the kill
    ck = ck_root / plan_key(plan_batch(reqs, max_slots=eng.max_slots)[0])
    assert store.latest_step(ck) == 2

    out = SearchEngine(segment_gens=2, checkpoint_dir=str(ck_root)).run(reqs)
    for a, b in zip(out, ref):
        _assert_result_equal(a, b)
    assert store.latest_step(ck) is None  # completed plan cleared its state


def test_service_drain_kill_resume(ws, tmp_path, monkeypatch):
    """Same drill through the service front end: the sync service rolls
    the dispatched plan back on KeyboardInterrupt (queue intact), and a
    fresh service over a fresh engine resumes from the same directory."""
    reqs = _reqs(ws, 2, "table", seed0=80)
    ref_svc = DSEService(engine=SearchEngine(segment_gens=2))
    ref_rids = ref_svc.submit_all(reqs)
    ref_map = ref_svc.drain()
    ref_res = [ref_map[r] for r in ref_rids]
    ck_root = str(tmp_path / "svc_ck")
    real = engine_mod.run_ga_batched_segment
    calls = {"n": 0}

    def killed_on_second(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise KeyboardInterrupt()
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", killed_on_second)
    svc = DSEService(engine=SearchEngine(segment_gens=2,
                                         checkpoint_dir=ck_root))
    svc.submit_all(reqs)
    with pytest.raises(KeyboardInterrupt):
        svc.drain()
    assert svc.pending() == len(reqs)  # rolled back, still retryable
    monkeypatch.setattr(engine_mod, "run_ga_batched_segment", real)

    svc2 = DSEService(engine=SearchEngine(segment_gens=2,
                                          checkpoint_dir=ck_root))
    rids = svc2.submit_all(reqs)
    res = svc2.drain()
    for rid, b in zip(rids, ref_res):
        _assert_result_equal(res[rid], b)


def test_checkpoint_cadence_writes_only_at_interval(ws, tmp_path, monkeypatch):
    from repro.checkpoint import store

    saves = []
    real_save = store.save

    def counting_save(ck, step, tree, **kw):
        saves.append(step)
        return real_save(ck, step, tree, **kw)

    monkeypatch.setattr(store, "save", counting_save)
    eng = SearchEngine(segment_gens=1, checkpoint_every=2,
                       checkpoint_dir=str(tmp_path / "cad"))
    eng.run(_reqs(ws, 1, "table", gens=5, seed0=70))
    assert saves == [2, 4]  # every 2nd of 5 one-generation segments


# -------------------------------------------------------- finite-score guard
def test_finalize_flags_poisoned_history_invalid():
    P, n = 4, space.N_GENES
    g = np.random.default_rng(0).random((3, P, n)).astype(np.float32)
    for bad in (np.nan, np.inf):
        ga = GAResult(genomes=jnp.asarray(g),
                      scores=jnp.full((3, P), bad, jnp.float32),
                      best_genome=jnp.zeros((n,)),
                      best_score=jnp.float32(bad))
        res = _finalize(ga, ("w0",), "ela", 5)
        assert not res.valid
        assert res.top_scores.size == 0 and res.top_designs == []


def test_poisoned_eval_fn_yields_invalid_result():
    """Satellite regression: a GA run whose eval fn only ever returns
    non-finite scores must finalize as ``valid=False`` — never as a
    confident result over garbage designs."""
    def poisoned(genomes):
        return jnp.full((genomes.shape[0],), jnp.nan, jnp.float32)

    ga = run_ga(jax.random.PRNGKey(0), poisoned, pop_size=POP, generations=2,
                init_genomes=_init(3, POP))
    res = _finalize(ga, ("w0",), "ela", 5)
    assert not res.valid and res.top_scores.size == 0


def test_empty_partial_result_contract(ws):
    req = SearchRequest(ws=ws, seed=1, backend="table", pop_size=POP,
                        generations=GENS)
    res = empty_partial_result(req)
    assert res.partial and not res.valid and res.generations == 0
    assert res.ga is None and res.top_scores.size == 0
    assert res.workload_names == ws.names and res.objective == "ela"
    wreq = dataclasses.replace(req, obj_weights=(1.0, 2.0, 0.0))
    assert empty_partial_result(wreq).objective.startswith("weighted")


# ------------------------------------------- segment chain x survival ties
@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
def test_ga_segments_fused_parity(tied):
    """Segment chains equal the single shot bit-for-bit, also from a
    population of duplicated designs: their equal scores must be broken
    by candidate index the same way in every segment's combined-sort
    survival as in the single launch."""
    key = jax.random.PRNGKey(19)
    init = _init(4, POP)
    if tied:
        init = jnp.concatenate([init[: POP // 2]] * 2)  # each design twice
    full = run_ga(key, _toy_eval, pop_size=POP, generations=GENS,
                  init_genomes=init + 0)
    st = init_ga_state(key, _toy_eval, init)
    hg = [np.asarray(st.genomes)[None]]
    hs = [np.asarray(st.scores)[None]]
    for k in (2, 2, 2):
        st, (g, s) = run_ga_segment(st, _toy_eval, generations=k,
                                    total_generations=GENS)
        hg.append(np.asarray(g))
        hs.append(np.asarray(s))
    np.testing.assert_array_equal(np.concatenate(hg),
                                  np.asarray(full.genomes))
    np.testing.assert_array_equal(np.concatenate(hs),
                                  np.asarray(full.scores))


def test_segmented_engine_fused_cross_parity(ws):
    """Engine-level: segmented == single shot, including the
    mixed-subset slot packing and both finalize epilogues."""
    reqs = _reqs(ws, 3, "table", seed0=40)
    ref = SearchEngine().run(reqs)
    out = SearchEngine(segment_gens=2).run(reqs)
    for a, b in zip(out, ref):
        _assert_result_equal(a, b)


def test_segmented_engine_direct_seed_parity(ws):
    """direct_seed crossed with segmentation: the segmented direct-seed
    engine equals the single-shot direct-seed engine bit-for-bit."""
    reqs = _reqs(ws, 3, "table", seed0=60)
    ref = SearchEngine(direct_seed=True).run(reqs)
    out = SearchEngine(direct_seed=True, segment_gens=2).run(reqs)
    for a, b in zip(out, ref):
        _assert_result_equal(a, b)
