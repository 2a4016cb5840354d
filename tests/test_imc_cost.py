"""IMC cost model: physical-consistency checks + kernel parity.

(Property-based variants live in test_properties.py, guarded on
hypothesis being installed.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import space
from repro.core.engine import _valid_vt_mask
from repro.imc.cost import DesignArrays, area_mm2, design_valid, evaluate_designs
from repro.imc.tech import TECH
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.lm import lm_workload
from repro.workloads.pack import pack_workloads


@pytest.fixture(scope="module")
def ws():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def _design(**kw):
    base = dict(rows=128.0, cols=128.0, c_per_tile=8.0, t_per_router=8.0,
                g_per_chip=8.0, v_op=0.9, bits_cell=2.0, t_cycle_ns=2.0,
                glb_mb=1.0)
    base.update(kw)
    return DesignArrays(**{k: jnp.asarray([v], jnp.float32) for k, v in base.items()})


def test_energy_latency_area_positive(ws):
    g = space.random_genomes(jax.random.PRNGKey(0), 256)
    r = evaluate_designs(space.decode(g), ws)
    assert bool((r.energy_pj > 0).all())
    assert bool((r.latency_ns > 0).all())
    assert bool((r.area_mm2 > 0).all())


def test_more_capacity_never_hurts_fit(ws):
    for rows in (32.0, 128.0, 512.0):
        small = evaluate_designs(_design(rows=rows, c_per_tile=2.0), ws)
        big = evaluate_designs(_design(rows=rows, c_per_tile=32.0), ws)
        # strictly more crossbars on chip -> fits is monotone
        assert bool((big.fits | ~small.fits).all())


def test_area_monotone_in_everything():
    base = area_mm2(_design())
    for f, hi in [("rows", 512.0), ("cols", 512.0), ("c_per_tile", 32.0),
                  ("t_per_router", 16.0), ("g_per_chip", 64.0), ("glb_mb", 16.0)]:
        bigger = area_mm2(_design(**{f: hi}))
        assert float(bigger[0]) > float(base[0]), f


def test_voltage_frequency_coupling():
    # at 0.7 V the device cannot run at 0.5 ns; at 8 ns it can
    fast = evaluate_designs(_design(v_op=0.7, t_cycle_ns=0.5),
                            pack_workloads([("x", [(1, 8, 8, 8, 8, 1)])]))
    slow = evaluate_designs(_design(v_op=0.7, t_cycle_ns=8.0),
                            pack_workloads([("x", [(1, 8, 8, 8, 8, 1)])]))
    assert not bool(fast.valid[0])
    assert bool(slow.valid[0])


def test_nominal_operating_point_is_valid():
    """(V_nominal, 1 ns) lies exactly on t_min (tech.py normalizes the
    alpha-power law there): the dense model, its jitted form and the
    seeder's host mask all call it valid, whatever float32 rounding does
    to the tie, and the next voltage step down stays invalid."""
    nominal = _design(v_op=TECH.v_nominal, t_cycle_ns=1.0)
    below = _design(v_op=0.875, t_cycle_ns=1.0)
    for valid in (design_valid, jax.jit(design_valid)):
        assert bool(valid(nominal)[0])
        assert not bool(valid(below)[0])
    v_grid = np.asarray(space.SPACE["v_op"], np.float32)
    t_grid = np.asarray(space.SPACE["t_cycle_ns"], np.float32)
    mask = _valid_vt_mask(TECH)
    vi = int(np.argmin(np.abs(v_grid - TECH.v_nominal)))
    ti = int(np.argmin(np.abs(t_grid - 1.0)))
    assert mask[vi, ti] and not mask[vi - 1, ti]


def test_bits_per_cell_tradeoff(ws):
    """More bits/cell packs weights denser -> less crossbar demand."""
    lo = evaluate_designs(_design(bits_cell=1.0), ws)
    hi = evaluate_designs(_design(bits_cell=4.0), ws)
    assert bool((hi.util <= lo.util + 1e-6).all())


def test_glb_spill_increases_latency_energy(ws):
    small = evaluate_designs(_design(glb_mb=0.125), ws)
    big = evaluate_designs(_design(glb_mb=16.0), ws)
    # latency is unconditionally monotone (DRAM spill stalls)
    assert bool((small.latency_ns >= big.latency_ns - 1e-3).all())
    # energy: decouple leakage (bigger GLB -> more area -> more leak is a
    # REAL competing effect); with leakage off, spill energy dominates
    tech0 = TECH._replace(leak_mw_per_mm2=0.0)
    small0 = evaluate_designs(_design(glb_mb=0.125), ws, tech0)
    big0 = evaluate_designs(_design(glb_mb=16.0), ws, tech0)
    assert bool((small0.energy_pj >= big0.energy_pj - 1e-3).all())


def test_depthwise_maps_badly():
    """MobileNet's depthwise convs (groups=C) demand far more crossbars per
    MAC than dense convs — the known IMC pathology the paper's workload mix
    exercises."""
    dense = [(196, 1152, 128, 1, 1, 1)]  # 1 group
    dw = [(196, 9, 1, 1, 1, 128)]  # 128 groups, same-ish macs
    r_dense = evaluate_designs(_design(), pack_workloads([("d", dense)]))
    r_dw = evaluate_designs(_design(), pack_workloads([("w", dw)]))
    assert float(r_dw.util[0, 0]) > 0.1 * float(r_dense.util[0, 0])


# ----------------------------------------------------------------- LM export
@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x7b", "mamba2-780m",
                                  "whisper-medium", "jamba-v0.1-52b"])
def test_lm_workload_export(arch):
    from repro.configs.base import get_config

    cfg = get_config(arch)
    layers = lm_workload(cfg, mode="decode")
    assert len(layers) > 0
    arr = np.asarray(layers, np.float64)
    assert (arr[:, :3] >= 1).all()  # M, K, N positive
    # decode mode: single-token presentations everywhere
    assert arr[:, 0].max() <= max(1, cfg.topk or 1)


def test_lm_workload_prefill_scales_m():
    from repro.configs.base import get_config

    cfg = get_config("llama3.2-1b")
    d = np.asarray(lm_workload(cfg, mode="decode"), np.float64)
    p = np.asarray(lm_workload(cfg, mode="prefill", seq=128), np.float64)
    assert p[:, 0].max() == 128


# -------------------------------------------------------------- kernel parity
def test_imc_eval_kernel_parity(ws):
    from repro.kernels.imc_eval.ops import evaluate_designs_kernel

    g = space.random_genomes(jax.random.PRNGKey(0), 300)
    d = space.decode(g)
    ref = evaluate_designs(d, ws)
    for backend in ("jnp", "pallas"):
        r = evaluate_designs_kernel(d, ws, backend=backend)
        np.testing.assert_allclose(r.energy_pj, ref.energy_pj, rtol=2e-5)
        np.testing.assert_allclose(r.latency_ns, ref.latency_ns, rtol=2e-5)
        np.testing.assert_array_equal(np.asarray(r.fits), np.asarray(ref.fits))
        np.testing.assert_array_equal(np.asarray(r.valid), np.asarray(ref.valid))
