"""Analytical IMC chip performance model — pure JAX, fully vectorized.

Evaluates a *population* of chip designs against a *set* of workloads in one
tensor program (CIMLoop/NeuroSim-class estimates, closed form):

    E (P, W) pJ,   L (P, W) ns,   A (P,) mm^2,   fits (P, W),   valid (P,)

Architecture (paper Fig. 1): chip = ``G_per_chip`` tile groups + global
buffer; each group has one shared router serving ``T_per_router`` tiles;
each tile has ``C_per_tile`` crossbars (rows x cols RRAM cells) with ADCs
(8-bit, 8:1 column mux), drivers and IO buffers.  Weight-stationary mapping:
every layer's weights are pinned; a design *fails* a workload when the
crossbar demand exceeds chip capacity (the paper's "failed designs").

Model structure (what scales with what):
  * crossbar demand:  ceil(K/rows) * ceil(N*cpw/cols) * groups   per layer,
    cpw = ceil(weight_bits / bits_cell)
  * compute latency:  M * input_bits * adc_share * T_cycle    (bit-serial
    inputs, ADC column mux serializes readout), layers sequential
  * comm latency:     activation bytes through G routers, flit_bytes/cycle
  * GLB:              per-layer working set beyond GLB spills to DRAM
  * V/f coupling:     T_cycle >= t_min(V_op) (alpha-power law) else invalid;
    cell read energy ~ V^2 * G_avg * T_cycle
  * energy:           cells + ADC + DAC + routers + buffers + DRAM spill
                      + leakage(Area) * latency
  * area:             full provisioned capacity (crossbars+ADCs+drivers)
                      + routers + tile buffers + GLB + 10% overhead

All `ceil`s are `jnp` ops — a GA generation (eval -> select -> SBX ->
mutate) is a single XLA program; the population axis shards over the mesh
``data`` axis for pod-scale DSE (see ``repro.core.distributed``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.imc.tech import TECH, TechParams
from repro.workloads.pack import WorkloadSet


class DesignArrays(NamedTuple):
    """Decoded designs, each field (P,) float32/int32."""

    rows: jnp.ndarray
    cols: jnp.ndarray
    c_per_tile: jnp.ndarray
    t_per_router: jnp.ndarray
    g_per_chip: jnp.ndarray
    v_op: jnp.ndarray
    bits_cell: jnp.ndarray
    t_cycle_ns: jnp.ndarray
    glb_mb: jnp.ndarray


class EvalResult(NamedTuple):
    energy_pj: jnp.ndarray  # (P, W)
    latency_ns: jnp.ndarray  # (P, W)
    area_mm2: jnp.ndarray  # (P,)
    fits: jnp.ndarray  # (P, W) bool — workload weights resident on chip
    valid: jnp.ndarray  # (P,) bool — design self-consistent (V/f)
    util: jnp.ndarray  # (P, W) crossbar-capacity utilization


def _ceil_div(a, b):
    return jnp.ceil(a / b)


# Relative slack of the V/f check.  The nominal point (V_nominal, 1 ns)
# lies exactly on t_min by construction of k, and float32 rounding of the
# alpha-power law lands it on either side depending on the device and on
# how XLA fuses the expression: on a TPU v5e the table and dense paths
# disagreed, and the dense path missed the tie by more than 1e-6.  Every
# other point of the grids up to density 4 clears t_min by more than
# 8e-4, so the slack only settles that tie, as valid, on every backend.
VF_RTOL = 1e-4


def design_valid(d: DesignArrays, tech: TechParams = TECH) -> jnp.ndarray:
    """V/f self-consistency (P,): alpha-power-law minimum cycle at V_op."""
    k = (tech.v_nominal - tech.v_th) ** tech.alpha_power / tech.v_nominal
    t_min = k * d.v_op / (d.v_op - tech.v_th) ** tech.alpha_power
    return d.t_cycle_ns >= t_min * (1.0 - VF_RTOL)


def area_mm2(d: DesignArrays, tech: TechParams = TECH) -> jnp.ndarray:
    """Provisioned chip area (independent of workload)."""
    n_tiles = d.g_per_chip * d.t_per_router
    n_xbars = n_tiles * d.c_per_tile
    xbar = (
        d.rows * d.cols * tech.cell_area_mm2
        + d.rows * tech.driver_area_mm2_per_row
        + (d.cols / tech.adc_share) * tech.adc_area_mm2
    )
    tile_buf = tech.tile_buf_kb / 1024.0 * tech.sram_area_mm2_per_mb
    a = (
        n_xbars * xbar
        + n_tiles * tile_buf
        + d.g_per_chip * tech.router_area_mm2
        + d.glb_mb * tech.sram_area_mm2_per_mb
    )
    return a * 1.10  # global wiring/pads overhead


def evaluate_designs(
    d: DesignArrays, ws: WorkloadSet, tech: TechParams = TECH
) -> EvalResult:
    """Vectorized evaluation: designs (P,) x workloads (W, L, 6)."""
    return evaluate_designs_arrays(d, ws.feats, ws.mask, tech)


def evaluate_designs_arrays(
    d: DesignArrays, feats: jnp.ndarray, mask: jnp.ndarray, tech: TechParams = TECH
) -> EvalResult:
    """Same as ``evaluate_designs`` but on raw (feats (W, L, 6), mask (W, L))
    tensors, so workload sets can be traced arguments — the batched search
    path (``core.search.batched_search``) vmaps over a leading batch axis of
    these and the jit cache is keyed only on shapes, not WorkloadSet objects."""
    M, K, N, A_in, A_out, G = [feats[..., i] for i in range(6)]
    maskf = mask.astype(jnp.float32)

    # broadcast designs to (P, 1, 1) against layers (1, W, L)
    def b(x):
        return x[:, None, None].astype(jnp.float32)

    rows, cols = b(d.rows), b(d.cols)
    v_op, bits = b(d.v_op), b(d.bits_cell)
    t_cyc = b(d.t_cycle_ns)
    glb_bytes = b(d.glb_mb) * (1 << 20)

    Ml, Kl, Nl, Gl = M[None], K[None], N[None], G[None]
    Ain, Aout = A_in[None], A_out[None]
    mk = maskf[None]

    cpw = _ceil_div(jnp.float32(tech.weight_bits), bits)
    xb_layer = _ceil_div(Kl, rows) * _ceil_div(Nl * cpw, cols) * Gl  # (P,W,L)
    demand = (xb_layer * mk).sum(-1)  # (P, W)
    capacity = (d.g_per_chip * d.t_per_router * d.c_per_tile).astype(jnp.float32)
    fits = demand <= capacity[:, None]
    util = demand / capacity[:, None]

    # ---------------- latency ------------------------------------------------
    phases = jnp.float32(tech.input_bits)
    cyc_per_vec = phases * tech.adc_share
    l_comp = (Ml * cyc_per_vec * t_cyc * mk).sum(-1)  # (P, W) ns

    bytes_layer = Ain + Aout  # 8-bit activations = 1 B each
    router_bw = b(d.g_per_chip) * tech.router_flit_bytes  # bytes / cycle
    l_comm = (bytes_layer / router_bw * t_cyc * mk).sum(-1)

    spill = jnp.maximum(bytes_layer - glb_bytes, 0.0)
    l_dram = (spill * mk).sum(-1) / tech.dram_bw_bytes_per_ns

    latency = l_comp + l_comm + l_dram  # (P, W)

    # ---------------- energy -------------------------------------------------
    e_cell = v_op**2 * tech.g_avg_s * t_cyc * 1e3  # pJ per cell per phase
    cells = Kl * (Nl * cpw) * Gl  # active cells per presentation
    e_analog = (Ml * phases * cells * e_cell * mk).sum(-1)

    n_col_splits = _ceil_div(Nl * cpw, cols)
    n_row_splits = _ceil_div(Kl, rows)
    convs = Ml * phases * (Nl * cpw) * Gl  # ADC conversions (per col result)
    e_adc = (convs * tech.adc_energy_pj * mk).sum(-1)
    drives = Ml * phases * Kl * n_col_splits * Gl
    e_dac = (drives * tech.dac_energy_pj * mk).sum(-1)

    e_route = (bytes_layer * tech.router_energy_pj_per_byte * mk).sum(-1)
    e_buf = (
        bytes_layer
        * (tech.tile_buf_energy_pj_per_byte + tech.glb_energy_pj_per_byte)
        * mk
    ).sum(-1)
    e_dram = (spill * tech.dram_energy_pj_per_byte * mk).sum(-1)

    area = area_mm2(d, tech)  # (P,)
    # 1 mW x 1 ns = 1e-3 W x 1e-9 s = 1e-12 J = 1 pJ -> direct product is pJ
    e_leak = tech.leak_mw_per_mm2 * area[:, None] * latency

    energy = e_analog + e_adc + e_dac + e_route + e_buf + e_dram + e_leak

    valid = design_valid(d, tech)

    return EvalResult(
        energy_pj=energy,
        latency_ns=latency,
        area_mm2=area,
        fits=fits,
        valid=valid,
        util=util,
    )


def evaluate_one(design: Dict[str, float], ws: WorkloadSet, tech: TechParams = TECH) -> EvalResult:
    d = DesignArrays(**{k: jnp.asarray([v], jnp.float32) for k, v in design.items()})
    return evaluate_designs(d, ws, tech)
