"""Compile rehearsals for a TPU v5e: the chip's compiler, no chip.

Each test lowers one program of the chip path for a *described* v5e
device (``jax.experimental.topologies``) and compiles it, so what the TPU
compiler refuses (block layouts, unsupported primitives, programs that do
not fit 16 GB of HBM) fails here instead of on the chip.  Nothing runs:
these say nothing about results or times.  The main-path shapes are the
ones ``chip_smoke.py`` drives: 64 table-backend slots of P=1024, G=100
over the four paper CNNs.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and the test workers must
all collect the same tests.  The persistent compilation cache is off
around the compiles, since an entry compiled for a described device
cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.core import space
from repro.core.distributed import shape_spec
from repro.core.engine import INDEXED, _ctx_eval, _seed_batched_jit
from repro.core.ga import (
    MUT_ETA,
    SBX_ETA,
    SBX_PROB,
    _run_ga_batched_thin_jit,
    _run_pareto_batched_jit,
    pareto_mask_bytes,
    pick_pareto_budget,
)
from repro.core.objectives import PARETO
from repro.imc.tech import TECH
from repro.kernels.imc_eval.kernel import imc_eval_pallas_multi
from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
from repro.workloads.pack import pack_workloads

SLOTS, POP, GENS, TOP_K = 64, 1024, 100, 10
V5E_HBM = 16 * 1024**3  # bytes of HBM on one v5e chip


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ws():
    return pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _table_ctx(ws, slots, sharding_of):
    """Shape stand-ins of a plan's stacked table ctx: WorkloadTables leaves
    with a leading slot axis (placed by ``sharding_of(shape)``)."""
    tables = ws.tables()
    return jax.tree_util.tree_map(
        lambda a: _sds((slots,) + a.shape, a.dtype, sharding_of((slots,) + a.shape)),
        tables,
    )


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < V5E_HBM, f"{total} bytes do not fit one v5e chip"
    return total


@pytest.mark.parametrize("pop", [40, POP])
def test_imc_eval_kernel_compiles(one_chip, ws, pop):
    """The Pallas cost-model kernel at the paper CNNs' padded L, W=4."""
    W, L = ws.feats.shape[:2]
    fn = jax.jit(lambda d, f, m: imc_eval_pallas_multi(d, f, m, interpret=False))
    compiled = fn.lower(
        _sds((pop, 9), jnp.float32, one_chip),
        _sds((W, L, 6), jnp.float32, one_chip),
        _sds((W, L), jnp.bool_, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_thin_table_ga_program_compiles(one_chip, ws):
    """The service's main program: the thin, table-backend batched
    GA with the indexed objective, one full 64-slot launch."""
    sh = lambda shape: one_chip  # noqa: E731
    compiled = _run_ga_batched_thin_jit.lower(
        _sds((SLOTS, 2), jnp.uint32, one_chip),
        _sds((SLOTS, POP, space.N_GENES), jnp.float32, one_chip),
        (_table_ctx(ws, SLOTS, sh), _sds((SLOTS,), jnp.int32, one_chip),
         _sds((SLOTS,), jnp.float32, one_chip)),
        eval_fn=_ctx_eval(INDEXED, 0.0, TECH, "table"), pop_size=POP,
        generations=GENS, sbx_prob=SBX_PROB, sbx_eta=SBX_ETA,
        mut_eta=MUT_ETA, top_k=TOP_K,
    ).compile()
    _fits_hbm(compiled)


def test_rejection_seeder_compiles(one_chip, ws):
    """The service's seeding program (64x oversampled rejection rounds
    against each slot's largest workload) at the same launch shape."""
    W, L = ws.feats.shape[:2]
    compiled = _seed_batched_jit.lower(
        _sds((SLOTS, 2), jnp.uint32, one_chip),
        _sds((SLOTS, W, L, 6), jnp.float32, one_chip),
        _sds((SLOTS, W, L), jnp.bool_, one_chip),
        pop_size=POP, oversample=64, max_rounds=8, tech=TECH,
    ).compile()
    _fits_hbm(compiled)


def test_pareto_program_compiles(one_chip, ws):
    """The NSGA-II program with its front epilogue at the largest (P, G)
    ``pick_pareto_budget`` admits on one v5e chip — the size the smoke's
    Pareto phase runs."""
    pop, gens = pick_pareto_budget(V5E_HBM)
    assert pareto_mask_bytes(pop, gens) <= V5E_HBM
    sh = lambda shape: one_chip  # noqa: E731
    compiled = _run_pareto_batched_jit.lower(
        _sds((1, 2), jnp.uint32, one_chip),
        _sds((1, pop, space.N_GENES), jnp.float32, one_chip),
        (_table_ctx(ws, 1, sh), _sds((1,), jnp.float32, one_chip)),
        eval_fn=_ctx_eval(PARETO, 0.0, TECH, "table"), pop_size=pop,
        generations=gens, sbx_prob=SBX_PROB, sbx_eta=SBX_ETA,
        mut_eta=MUT_ETA, top_k=TOP_K, history=False,
    ).compile()
    _fits_hbm(compiled)


def test_sharded_thin_program_compiles_for_four_chips(topo, ws):
    """The four-chip path of ``chip_smoke.py --four-chips``: the same
    64-slot program laid out over a (search=4, data=1) mesh of the v5e
    2x2 host, every input placed by ``core.distributed.shape_spec``."""
    devs = np.asarray(topo.devices[:4]).reshape(4, 1)
    mesh = Mesh(devs, ("search", "data"), axis_types=(AxisType.Auto,) * 2)

    def place(shape, pop_dim=None):
        return NamedSharding(mesh, shape_spec(mesh, shape, pop_dim=pop_dim))

    compiled = _run_ga_batched_thin_jit.lower(
        _sds((SLOTS, 2), jnp.uint32, place((SLOTS, 2))),
        _sds((SLOTS, POP, space.N_GENES), jnp.float32,
             place((SLOTS, POP, space.N_GENES), pop_dim=1)),
        (_table_ctx(ws, SLOTS, place),
         _sds((SLOTS,), jnp.int32, place((SLOTS,))),
         _sds((SLOTS,), jnp.float32, place((SLOTS,)))),
        eval_fn=_ctx_eval(INDEXED, 0.0, TECH, "table"), pop_size=POP,
        generations=GENS, sbx_prob=SBX_PROB, sbx_eta=SBX_ETA,
        mut_eta=MUT_ETA, top_k=TOP_K,
    ).compile()
    _fits_hbm(compiled)
    assert len(compiled.output_shardings[0].device_set) == 4
