"""The ``dsv2lite_ep8`` configuration and its cell ``dsv2lite.sweep``.

* The file holds what the program exports: each workload's table is
  ``lm_workload(get_config("deepseek-v2-lite"), **exports[name])``, the
  grid and the technology are the program's, and the catalog's config is
  kept but for the keys that ``reduced`` names.
* At the tiny size on the CPU the cell passes the output check against
  ``plain_ref`` and the bfloat16 control fails it; a traced run reports
  the seeder's rounds a seeded slot.
"""
import json

import numpy as np
import pytest

import harness
from conftest import BENCH, ROOT, make_tiny, run_tiny

NAME = "dsv2lite_ep8"
PUBLISHED = {"num_hidden_layers": 27, "n_routed_experts": 64,
             "vocab_size": 102400}


def _cfg():
    return json.loads((BENCH / "configs" / f"{NAME}.json").read_text())


def test_config_holds_the_programs_export():
    from repro.configs.base import get_config
    from repro.core import space
    from repro.imc.tech import TECH
    from repro.workloads.lm import lm_workload

    cfg = _cfg()
    model = get_config("deepseek-v2-lite")
    assert list(cfg["workloads"]) == list(cfg["exports"])
    for name, kw in cfg["exports"].items():
        kw = dict(kw, layers=tuple(kw["layers"]))
        assert [tuple(r) for r in cfg["workloads"][name]] == \
            [tuple(r) for r in lm_workload(model, **kw)], name
    assert [len(t) for t in cfg["workloads"].values()] == [133, 133, 129, 10]
    for f in space.FIELDS:
        np.testing.assert_array_equal(
            np.asarray(cfg["design_space"][f], np.float32), space.SPACE[f])
    assert cfg["tech"] == TECH._asdict()
    assert cfg["source"] == model.source


def test_config_keeps_the_catalog_but_its_cuts():
    cfg = _cfg()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(PUBLISHED)
    assert cfg["published"] == PUBLISHED
    # what the file keeps of the published model
    kept = {"hidden_size": 2048, "intermediate_size": 10944,
            "moe_intermediate_size": 1408, "num_attention_heads": 16,
            "num_experts_per_tok": 6, "n_shared_experts": 2,
            "first_k_dense_replace": 1, "kv_lora_rank": 512,
            "q_lora_rank": None, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128}
    assert {k: cfg[k] for k in kept} == kept
    # the cuts: 4 MoE layers and layer 0, 8 of 64 experts, 1/8 vocabulary
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 12800)
    ex = cfg["exports"]
    assert {tuple(e["layers"]) for e in ex.values()} == {(1, 4), (0, 0)}
    assert {e["ep"] for e in ex.values()} == {64 // cfg["n_routed_experts"]}
    assert ex["dense0_head.decode_16k"]["head_share"] * PUBLISHED[
        "vocab_size"] == cfg["vocab_size"]


@pytest.fixture
def tiny_ds(tmp_path) -> harness.Paths:
    paths = make_tiny(tmp_path)
    cfg = _cfg()
    cfg["service"]["max_slots"] = 9
    (paths.here / "configs" / f"{NAME}.json").write_text(json.dumps(cfg))
    return paths


def test_program_passes_and_control_fails(tiny_ds):
    cell = harness.Cell("dsv2lite.sweep", paths=tiny_ds, require_tpu=False,
                        compile_cache=False, log=lambda m: None)
    cell.setup(5)
    w = cell.measure(2 ** 32 + 11, 1.5)
    cell.close()
    prog = cell.check(w, 11)
    assert cell.correct(prog), prog
    assert prog["answers_checked"]["value"] >= 6
    assert prog["score_gap"]["value"] < 1e-6
    ctl = cell.check(w, 11, control="bfloat16")
    assert not cell.correct(ctl), ctl
    assert ctl["score_gap"]["value"] > 1e-4


def test_traced_run_reports_seed_rounds(tiny_ds):
    out = run_tiny(tiny_ds, "dsv2lite.sweep", trace=True)
    assert out["correct"], out["checks"]
    # the stage tables fit few designs: a slot draws more than one round
    assert out["metrics"]["engine.seed_rounds_per_slot.sweep"]["value"] > 1.0
