"""Pallas TPU kernel: IMC design-space population evaluation.

The paper's hot loop — evaluate a population of chip designs against a
whole SET of workloads' layer tables — as a VMEM-tiled 3-D grid in ONE
kernel launch:

  * designs live on the LANE axis (tile 128, the VPU vector width),
  * layers live on the SUBLANE axis (tile 8): the layer table is laid out
    (W, L, 6), so a (SUB, 6) feature block is 8 sublanes by the full
    6-wide feature axis — the shape Mosaic's (8, 128) block rule accepts,
  * workloads are a middle grid axis (W is small; each (p, w) cell owns
    one (1, 128) row of the (W, 1, P) accumulators),
  * grid = (P // 128, W, L // 8); the layer axis is the innermost
    ("arbitrary") grid dim so each (design-tile, workload)'s partial sums
    accumulate in-place in the output block across layer steps,
  * all tech constants are compile-time Python floats (baked into the
    kernel body; nothing but the design/layer tiles touches VMEM).

Layout choices (HW-codesign): every per-(design, layer) term is an
(8, 128) outer-product-style vector op — sublane-broadcast of the layer
feature column against the lane vector of design parameters.  This is the
TPU-native shape of the paper's evaluator: no MXU needed (no matmuls),
pure 8x128 VPU tiles, one pass over HBM for all W layer tables.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.imc.tech import TECH, TechParams

LANE = 128  # designs per tile (lane axis)
SUB = 8  # layers per tile (sublane axis)


def default_interpret() -> bool:
    """Interpret the kernel unless the default backend is a real TPU, so
    TPU runs get the Mosaic-compiled kernel with no flag and CPU/GPU hosts
    (this container, CI) keep working via the interpreter."""
    return jax.default_backend() != "tpu"


def _eval_kernel(
    feats_ref,  # (1, SUB, 6)   this workload's layer-features tile
    mask_ref,  # (1, SUB, 1)
    d_ref,  # (9, LANE)  design params tile (param-major)
    energy_ref,  # (1, 1, LANE)  accumulated outputs, one (w, p) row each
    latency_ref,  # (1, 1, LANE)
    demand_ref,  # (1, 1, LANE)
    *,
    tech: TechParams,
):
    li = pl.program_id(2)  # layer-tile index (innermost, sequential)

    d = d_ref[...]  # (9, LANE)
    rows, cols = d[0:1], d[1:2]  # (1, LANE)
    g_chip, v_op, bits = d[4:5], d[5:6], d[6:7]
    t_cyc, glb_mb = d[7:8], d[8:9]

    f = feats_ref[0]  # (SUB, 6)
    mkc = mask_ref[0]  # (SUB, 1)

    # (SUB, 1) feature columns x (1, LANE) design rows -> (SUB, LANE) tiles
    M, K, N, Ain, Aout, G = (f[:, i : i + 1] for i in range(6))

    phases = jnp.float32(tech.input_bits)
    cpw = jnp.ceil(jnp.float32(tech.weight_bits) / bits)  # (1, LANE)
    ncol = jnp.ceil(N * cpw / cols)  # (SUB, LANE)
    nrow = jnp.ceil(K / rows)
    xb = nrow * ncol * G
    demand = (xb * mkc).sum(axis=0, keepdims=True)  # (1, LANE)

    bytes_l = Ain + Aout
    l_comp = M * (phases * tech.adc_share) * t_cyc
    l_comm = bytes_l / (g_chip * tech.router_flit_bytes) * t_cyc
    spill = jnp.maximum(bytes_l - glb_mb * float(1 << 20), 0.0)
    l_dram = spill * (1.0 / tech.dram_bw_bytes_per_ns)
    latency = ((l_comp + l_comm + l_dram) * mkc).sum(axis=0, keepdims=True)

    e_cell = v_op * v_op * (tech.g_avg_s * 1e3) * t_cyc  # (1, LANE)
    e_analog = M * phases * (K * (N * cpw) * G) * e_cell
    e_adc = M * phases * (N * cpw) * G * tech.adc_energy_pj
    e_dac = M * phases * K * ncol * G * tech.dac_energy_pj
    e_route = bytes_l * tech.router_energy_pj_per_byte
    e_buf = bytes_l * (
        tech.tile_buf_energy_pj_per_byte + tech.glb_energy_pj_per_byte
    )
    e_dram = spill * tech.dram_energy_pj_per_byte
    energy = (
        (e_analog + e_adc + e_dac + e_route + e_buf + e_dram) * mkc
    ).sum(axis=0, keepdims=True)

    @pl.when(li == 0)
    def _init():
        energy_ref[0] = energy
        latency_ref[0] = latency
        demand_ref[0] = demand

    @pl.when(li > 0)
    def _acc():
        energy_ref[0] += energy
        latency_ref[0] += latency
        demand_ref[0] += demand


def imc_eval_pallas_multi(
    designs: jnp.ndarray,  # (P, 9)
    feats: jnp.ndarray,  # (W, L, 6)
    mask: jnp.ndarray,  # (W, L)
    *,
    tech: TechParams = TECH,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pad, tile and launch ONCE for the whole workload set.

    Returns (energy, latency, demand), each (W, P)."""
    if interpret is None:
        interpret = default_interpret()
    P = designs.shape[0]
    W, L = feats.shape[0], feats.shape[1]
    Pp = -(-P // LANE) * LANE
    Lp = -(-L // SUB) * SUB

    dT = jnp.zeros((9, Pp), jnp.float32)
    dT = dT.at[:, :P].set(designs.T.astype(jnp.float32))
    # padded designs keep zeros -> guard divisions: set rows/cols/bits/g to 1
    if Pp != P:
        ones = jnp.ones((9, Pp - P), jnp.float32)
        dT = dT.at[:, P:].set(ones)
    # zero-padded layers are masked out, so their features never count
    fp = jnp.pad(feats.astype(jnp.float32), ((0, 0), (0, Lp - L), (0, 0)))
    mk = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, Lp - L)))[..., None]

    grid = (Pp // LANE, W, Lp // SUB)
    out_shape = [jax.ShapeDtypeStruct((W, 1, Pp), jnp.float32)] * 3
    out_spec = pl.BlockSpec((1, 1, LANE), lambda p, w, l: (w, 0, p))
    energy, latency, demand = pl.pallas_call(
        functools.partial(_eval_kernel, tech=tech),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, SUB, 6), lambda p, w, l: (w, l, 0)),
            pl.BlockSpec((1, SUB, 1), lambda p, w, l: (w, l, 0)),
            pl.BlockSpec((9, LANE), lambda p, w, l: (0, p)),
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(fp, mk, dT)
    return energy[:, 0, :P], latency[:, 0, :P], demand[:, 0, :P]


def imc_eval_pallas(
    designs: jnp.ndarray,  # (P, 9)
    feats: jnp.ndarray,  # (L, 6)
    mask: jnp.ndarray,  # (L,)
    *,
    tech: TechParams = TECH,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-workload convenience wrapper.  Returns (P,) each."""
    e, l, x = imc_eval_pallas_multi(
        designs, feats[None], mask[None], tech=tech, interpret=interpret
    )
    return e[0], l[0], x[0]
