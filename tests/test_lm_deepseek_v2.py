"""DeepSeek-V2-Lite as IMC chiplet workloads (``workloads/lm.py``).

* The export equals a plain enumeration of the rows, written here from
  the published config's numbers and the MLA equations of
  arXiv:2405.04434 §2.1 without ``lm.py`` or ``ModelConfig``: at the
  published widths for the four stage shares of the benchmark's
  ``dsv2lite_ep8`` deployment, and for a tiny MLA config.
* Expert parallelism: the 8 shares' routed experts add up to the uncut
  layer's weights and work, with attention, router and shared experts
  (what every rank computes alike) counted once; the expected tokens
  over all experts are the step's tokens times ``topk``.
* The latent-cache row's bytes at decode and prefill.
* The service (table backend, tiny P/G, CPU) on those workloads: its
  answers agree with the dense ``jnp`` cost model, and each launch
  records the seeder's rounds without a blocking read more.
"""
import dataclasses
import time

import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.engine import SearchEngine, SearchRequest
from repro.core.search import rescore_designs
from repro.serve.dse import AsyncDSEService
from repro.utils import spans
from repro.workloads.lm import lm_workload
from repro.workloads.pack import pack_workloads

# DeepSeek-V2-Lite's config.json
PUBLISHED = dict(d=2048, heads=16, nope=128, rope=64, v=128, rank=512,
                 experts=64, topk=6, expert_w=1408, shared=2, dense_w=10944,
                 vocab=102400)
# a tiny config of the same block (one dense layer, then MoE layers)
TINY = dict(d=64, heads=4, nope=16, rope=8, v=16, rank=32, experts=8,
            topk=2, expert_w=24, shared=1, dense_w=96, vocab=256)


def _tiny_config():
    return dataclasses.replace(
        get_config("deepseek-v2-lite"), name="deepseek-v2-tiny", n_layers=6,
        d_model=64, n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=256,
        n_experts=8, topk=2, moe_d_ff=24, n_shared_experts=1,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16)


def _row(m, k, n, g=1):
    return (m, k, n, m * k * g, m * n * g, g)


def plain_rows(c, *, mode, layers, ep, batch=1, chunk=1, context,
               head_share):
    """One chip's rows for one step, from the numbers alone."""
    d, H, r = c["d"], c["heads"], c["rank"]
    M = batch if mode == "decode" else chunk
    rows = []
    for layer in range(layers[0], layers[1] + 1):
        rows.append(_row(M, d, H * (c["nope"] + c["rope"])))  # W^Q (no q lora)
        rows.append(_row(M, d, r + c["rope"]))  # W^DKV and W^KR
        if mode == "decode":  # W^UK absorbed into q, W^UV into the output
            rows.append(_row(M, c["nope"], r, H))
            rows.append(_row(M, r, c["v"], H))
        else:  # keys and values up-projected for prefix and chunk
            rows.append(_row(context + chunk, r, H * (c["nope"] + c["v"])))
        rows.append(_row(M, H * c["v"], d))  # W^O
        if layer == 0:  # first_k_dense_replace = 1
            w = c["dense_w"]
            rows += [_row(M, d, w), _row(M, d, w), _row(M, w, d)]
            continue
        rows.append(_row(M, d, c["experts"]))  # router
        w = c["shared"] * c["expert_w"]
        rows += [_row(M, d, w), _row(M, d, w), _row(M, w, d)]
        t = ep * M * c["topk"] / c["experts"]
        w = c["expert_w"]
        for _ in range(c["experts"] // ep):
            rows += [_row(t, d, w), _row(t, d, w), _row(t, w, d)]
    if head_share:
        rows.append(_row(M, d, int(c["vocab"] * head_share)))
    per_tok = (layers[1] - layers[0] + 1) * (r + c["rope"])
    read, written = ((batch * context, batch) if mode == "decode"
                     else (context, chunk))
    rows.append((0, 0, 0, read * per_tok, written * per_tok, 1))
    return rows


# the benchmark's four stage shares (benchmarks/chip/configs/dsv2lite_ep8)
STAGES = {
    "moe4.decode_16k": dict(mode="decode", layers=(1, 4), ep=8, batch=1,
                            context=16384, head_share=0.0),
    "moe4.decode_128k": dict(mode="decode", layers=(1, 4), ep=8, batch=1,
                             context=131072, head_share=0.0),
    "moe4.prefill_16k": dict(mode="prefill", layers=(1, 4), ep=8,
                             chunk=4096, context=12288, head_share=0.0),
    "dense0_head.decode_16k": dict(mode="decode", layers=(0, 0), ep=8,
                                   batch=1, context=16384, head_share=0.125),
}
TINY_STAGES = {
    "decode": dict(mode="decode", layers=(0, 3), ep=4, batch=3, context=40,
                   head_share=0.5),
    "prefill": dict(mode="prefill", layers=(2, 5), ep=2, chunk=16,
                    context=48, head_share=0.0),
}


@pytest.mark.parametrize("stage", list(STAGES) + [f"tiny.{k}" for k in TINY_STAGES])
def test_export_equals_plain_enumeration(stage):
    if stage.startswith("tiny."):
        cfg, c, kw = _tiny_config(), TINY, TINY_STAGES[stage[5:]]
    else:
        cfg, c, kw = get_config("deepseek-v2-lite"), PUBLISHED, STAGES[stage]
    got = np.asarray(lm_workload(cfg, **kw), np.float64)
    want = np.asarray(plain_rows(c, **kw), np.float64)
    np.testing.assert_array_equal(got, want)


def test_stage_shares_row_counts_and_weights():
    """133/133/129/10 rows; a 4-layer stage holds 4.02e8 8-bit weights."""
    cfg = get_config("deepseek-v2-lite")
    n = {k: lm_workload(cfg, **kw) for k, kw in STAGES.items()}
    assert [len(v) for v in n.values()] == [133, 133, 129, 10]
    w = {k: sum(r[1] * r[2] * r[5] for r in v) for k, v in n.items()}
    assert w["moe4.decode_16k"] == w["moe4.prefill_16k"] == 401_604_608


def _split(rows, n_layer_rows, n_front):
    """(expert rows, other rows) of a one-layer export without head or
    cache: the first ``n_front`` rows of the layer are what every rank
    holds alike."""
    assert len(rows) == n_layer_rows
    return np.asarray(rows[n_front:], np.float64), np.asarray(rows[:n_front],
                                                              np.float64)


@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("tiny", [False, True], ids=["published", "tiny"])
def test_expert_parallel_shares_add_up(mode, tiny):
    cfg = _tiny_config() if tiny else get_config("deepseek-v2-lite")
    ep = 4 if tiny else 8
    E, topk = cfg.n_experts, cfg.topk
    per = dict(mode=mode, layers=(1, 1), head_share=0.0)
    tok = dict(batch=2) if mode == "decode" else dict(chunk=32)
    # the uncut layer serves the EP group's tokens on one chip
    whole = dict(batch=2 * ep) if mode == "decode" else dict(chunk=32 * ep)
    n_front = 5 if mode == "decode" else 4  # MLA rows
    n_front += 1 + 3  # router, shared experts
    share = lm_workload(cfg, ep=ep, **per, **tok)
    uncut = lm_workload(cfg, ep=1, **per, **whole)
    ex_s, front_s = _split(share, n_front + 3 * E // ep, n_front)
    ex_u, front_u = _split(uncut, n_front + 3 * E, n_front)

    def weights(a):
        return float((a[:, 1] * a[:, 2] * a[:, 5]).sum())

    def work(a):
        return float((a[:, 0] * a[:, 1] * a[:, 2] * a[:, 5]).sum())

    # routed experts: ep shares hold and compute what the uncut layer does
    assert ep * weights(ex_s) == weights(ex_u)
    np.testing.assert_allclose(ep * work(ex_s), work(ex_u), rtol=1e-12)
    np.testing.assert_allclose(ep * ex_s[:, 3:5].sum(0), ex_u[:, 3:5].sum(0),
                               rtol=1e-12)
    # attention, router and shared experts: every rank holds them (counted
    # once) and runs its own tokens, 1/ep of the group's
    assert weights(front_s) == weights(front_u)
    np.testing.assert_allclose(ep * work(front_s), work(front_u))
    # expected tokens over all E experts = the step's tokens * topk
    T = ep * (tok["batch"] if mode == "decode" else tok["chunk"])
    m_expert = ex_s[::3, 0]
    assert m_expert.shape == (E // ep,)
    np.testing.assert_allclose(ep * m_expert.sum(), T * topk)


def test_decode_expert_tokens_are_fractional():
    """One token a rank, EP 8: each held expert gets 8 * 6 / 64 = 0.75 of a
    token, not a whole one, and its activations count that share."""
    rows = lm_workload(get_config("deepseek-v2-lite"), layers=(1, 1), ep=8,
                       head_share=0.0)
    gate = rows[9]
    assert gate == (0.75, 2048, 1408, 0.75 * 2048, 0.75 * 1408, 1)


@pytest.mark.parametrize("mode", ["decode", "prefill"])
def test_cache_row_bytes(mode):
    cfg = get_config("deepseek-v2-lite")
    kw = (dict(batch=3, context=1000) if mode == "decode"
          else dict(chunk=256, context=768))
    rows = lm_workload(cfg, mode=mode, layers=(2, 5), ep=8, head_share=0.0,
                       **kw)
    latent = 512 + 64  # kv_lora_rank + qk_rope_head_dim, 8-bit
    if mode == "decode":
        want = (0, 0, 0, 4 * 3 * 1000 * latent, 4 * 3 * latent, 1)
    else:
        want = (0, 0, 0, 4 * 768 * latent, 4 * 256 * latent, 1)
    assert rows[-1] == want
    # no context, no cache row; a GQA model caches K and V of its kv heads
    assert all(r[1] for r in lm_workload(cfg, mode=mode, layers=(2, 5), ep=8,
                                         head_share=0.0))
    llama = get_config("llama3.2-1b")
    row = lm_workload(llama, batch=2, context=10)[-1]
    per = llama.n_layers * 2 * llama.n_kv_heads * llama.head_dim_
    assert row == (0, 0, 0, 2 * 10 * per, 2 * per, 1)


def test_export_rejects_what_it_cannot_share():
    cfg = get_config("deepseek-v2-lite")
    with pytest.raises(ValueError):
        lm_workload(cfg, ep=7)
    with pytest.raises(ValueError):
        lm_workload(cfg, head_share=1 / 3)
    with pytest.raises(ValueError):
        lm_workload(cfg, layers=(3, 27))


def test_published_parameter_count():
    """15.7B parameters, as published; the model stack does not list it."""
    from repro.configs.base import list_configs

    cfg = get_config("deepseek-v2-lite")
    assert 15.6e9 < cfg.param_count() < 15.8e9
    assert "deepseek-v2-lite" not in list_configs()


# --------------------------------------------------------------- service
P, G = 16, 3


@pytest.fixture(scope="module")
def ws():
    cfg = get_config("deepseek-v2-lite")
    return pack_workloads([(k, lm_workload(cfg, **kw))
                           for k, kw in STAGES.items()])


def _requests(ws, backend):
    subsets = [[0, 1, 2, 3], [0], [2], [3], [1, 2]]
    return [SearchRequest(ws=ws.subset(subsets[i % len(subsets)]),
                          objective=("ela", "edp", "e", "l")[i % 4],
                          area_constr=300.0, seed=1000 + i, backend=backend,
                          pop_size=P, generations=G)
            for i in range(8)]


def test_service_table_backend_agrees_with_dense(ws):
    """The served path on the stage tables: every returned design's score
    equals the dense cost model's score of it, and the search matches the
    same requests run on the dense backend."""
    with AsyncDSEService(engine=SearchEngine(max_slots=8, pipelined=True),
                         pipelined=True) as svc:
        futs = [svc.submit(r) for r in _requests(ws, "table")]
        got = [f.result(timeout=600) for f in futs]
    ref = SearchEngine(max_slots=8).run(_requests(ws, "jnp"))
    assert any(len(r.top_scores) for r in got)
    for req, a, b in zip(_requests(ws, "jnp"), got, ref):
        if len(a.top_genomes):
            dense, _ = rescore_designs(
                np.asarray(a.top_genomes), req.ws, objective=req.objective,
                area_constr=req.area_constr)
            np.testing.assert_allclose(a.top_scores, dense, rtol=1e-5)
        np.testing.assert_allclose(a.top_scores, b.top_scores, rtol=1e-5)
        np.testing.assert_allclose(a.convergence, b.convergence, rtol=1e-5)


def test_seed_rounds_recorded_syncs_unchanged(ws):
    """The harvest records the slots the seeder filled and their rounds,
    read with the counts: a pipelined launch still makes 5 blocking reads
    (the seed check and the four thin fields).  The stage tables fit few
    designs, so a slot needs more than one round."""
    eng = SearchEngine(max_slots=8, pipelined=True)
    eng.run(_requests(ws, "table"))  # warm
    t0 = time.perf_counter()
    eng.run(_requests(ws, "table"))
    snap = spans.snapshot(t0)
    harv = [s for s in snap.spans if s.name == "dse.harvest"]
    assert len(harv) == 1
    assert harv[0].attrs["syncs"] == 5
    assert harv[0].attrs["seed_slots"] == 8
    per = spans.counters(snap)
    assert per["syncs"] == 5
    assert per["seed_rounds"] == harv[0].attrs["seed_rounds"] / 8 > 1.0
