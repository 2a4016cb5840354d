"""System-level behaviour: cells lower end-to-end, HLO analysis parses,
roofline terms are sane, launchers run."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import collective_stats, op_census, shape_bytes
from repro.analysis.roofline import model_flops, roofline_terms
from repro.configs.base import SHAPES_BY_NAME, ShapeSpec, get_config, list_configs
from repro.launch.cells import all_cells, build_step, input_specs, skipped_cells
from repro.launch.mesh import make_test_mesh


# -------------------------------------------------------------- cells/ skips
def test_cell_enumeration_counts():
    cells = all_cells()
    skips = skipped_cells()
    # 10 archs x 4 shapes = 40; skips are the pure-full-attention long_500k
    assert len(cells) + len(skips) == 40
    assert len(skips) == 7
    long_runners = {c.cfg.name for c in cells if c.shape.name == "long_500k"}
    assert long_runners == {"mamba2-780m", "jamba-v0.1-52b", "mixtral-8x7b"}


def test_input_specs_cover_all_cells():
    for cell in all_cells():
        specs = input_specs(cell.cfg, cell.shape)
        assert specs, cell.name
        for name, s in specs.items():
            assert all(d > 0 for d in s.shape), (cell.name, name)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-780m", "mixtral-8x7b"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_cell_lowers_on_test_mesh(arch, kind):
    """Reduced configs of three families lower for all three step kinds on
    the single-device test mesh (same builder code as the 512-dev dry-run)."""
    cfg = get_config(arch).reduced()
    shape = ShapeSpec("t", 64, 2, kind)
    mesh = make_test_mesh(1, 1)
    bundle = build_step(cfg, shape, mesh)
    jitted = jax.jit(bundle.fn, donate_argnums=bundle.donate_argnums)
    lowered = jitted.lower(*bundle.args)
    assert lowered is not None


# ------------------------------------------------------------- HLO analysis
HLO_SAMPLE = """
HloModule test
ENTRY main {
  p0 = f32[128,256]{1,0} parameter(0)
  ag = f32[128,4096]{1,0} all-gather(p0), dimensions={1}
  ar = f32[128,256]{1,0} all-reduce(p0), to_apply=add
  rs = f32[8,256]{1,0} reduce-scatter(p0), dimensions={0}
  cp = f32[128,256]{1,0} collective-permute(p0), source_target_pairs={{0,1}}
  d = f32[128,128]{1,0} dot(p0, p0), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  ROOT t = (f32[128,4096]{1,0}) tuple(ag)
}
"""


def test_shape_bytes():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[2,4]") == 16
    assert shape_bytes("(f32[8], s32[2])") == 40
    assert shape_bytes("f32[]") == 4
    assert shape_bytes("pred[16]") == 16


def test_collective_stats_parses_all_kinds():
    st = collective_stats(HLO_SAMPLE)
    assert st.counts == {
        "all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
        "collective-permute": 1,
    }
    assert st.by_kind["all-gather"] == 128 * 4096 * 4
    assert st.by_kind["all-reduce"] == 128 * 256 * 4
    assert st.total_bytes == sum(st.by_kind.values())


def test_op_census():
    c = op_census(HLO_SAMPLE)
    assert c["dot"] == 1 and c["all-gather"] == 1


# ----------------------------------------------------------------- roofline
def test_roofline_bottleneck_selection():
    from repro.analysis.hlo import CollectiveStats

    rf = roofline_terms(
        cell="x", mesh_name="m", chips=256,
        hlo_flops=1e12, hlo_bytes=1e9,
        coll=CollectiveStats(total_bytes=10**12, by_kind={}, counts={}),
        model_flops_global=2.56e14,
    )
    assert rf.bottleneck == "collective"
    assert rf.t_collective == pytest.approx(1e12 / (2 * 50e9))
    assert rf.useful_ratio == pytest.approx(1.0)


def test_model_flops_train_is_6nd():
    cfg = get_config("llama3.2-1b")
    shape = SHAPES_BY_NAME["train_4k"]
    mf = model_flops(cfg, shape)
    n = cfg.param_count()
    tokens = shape.global_batch * shape.seq_len
    assert mf >= 6 * n * tokens  # plus attention term
    assert mf < 6 * n * tokens * 1.5


def test_model_flops_decode_much_smaller_than_prefill():
    cfg = get_config("yi-9b")
    f_pre = model_flops(cfg, SHAPES_BY_NAME["prefill_32k"])
    f_dec = model_flops(cfg, SHAPES_BY_NAME["decode_32k"])
    assert f_dec < f_pre / 10


# ----------------------------------------------------------------- launchers
@pytest.mark.slow
def test_train_launcher_end_to_end(tmp_path):
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "llama3.2-1b", "--d-model", "64", "--layers", "2",
        "--seq", "64", "--batch", "2", "--steps", "6",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "3", "--log-every", "2",
    ]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout


# ------------------------------------------------------------- compile cache
@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """The entry points' cache helper: ``JAX_COMPILATION_CACHE_DIR`` wins
    and nothing is set in code; otherwise the fixed ``<checkout>/.jax_cache``
    (no pid, temp name or time in it).  The config is restored before any
    compile, so the suite stays cache-free."""
    from repro.launch.compile_cache import CHECKOUT_ROOT, enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = enable_compile_cache()
            assert (CHECKOUT_ROOT / "chip_smoke.py").is_file()
            assert path == str(CHECKOUT_ROOT / ".jax_cache") == enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
