"""Pallas kernel: one WHOLE GA generation per launch (table backend).

The fused lax path (``core.ga._make_gen_step(fused=True)``) still round-
trips the (2P, n) offspring block and the survival keys through HBM
between the XLA ops of a generation.  This kernel keeps the entire
generation — tournament selection, SBX, polynomial mutation, the
factorized-table cost model with the indexed objective, and (mu+lambda)
survival — resident in VMEM and writes only the new population, its
scores, and the history row.

Bit-parity with the lax path is a design constraint, achieved by using
only exactly-representable re-expressions of the lax ops:

  * gathers become masked where-selects / one-hot contractions — exact
    because exactly one position is selected and ``0 * finite = 0``,
    ``0 + v = v``; score gathers use where-select (never multiply) so
    +inf infeasible scores survive untouched,
  * table lookups at ``decode_indices`` grid points become one-hot
    matmuls against the flattened tables (finite values -> exact),
  * the survival sort becomes a bitonic compare-exchange network over
    the same unique (total-order-int32, index) key pairs the lax sort
    uses; unique keys mean ANY correct sort produces the identical
    permutation.  Partner access ``i ^ j`` is a pure reshape + flip
    (TPU-expressible: no dynamic gathers anywhere in the network),
  * every cost-model line mirrors ``imc.tables.evaluate_designs_tables``
    / ``imc.cost.area_mm2`` / ``design_valid`` / the indexed objective
    op-for-op.

Tested in interpret mode against the lax generation step
(tests/test_fused_gen.py).  The TPU compiler refuses the kernel: Mosaic
has no lowering for the ``rev`` that ``jnp.flip`` emits in the partner
exchange, and with that exchange done by lane rolls it next refuses the
rank-3 reshapes of the one-hot selects.  tests/test_tpu_compile.py pins
the refusal.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import space
from repro.imc.cost import VF_RTOL
from repro.imc.tech import TECH, TechParams


def default_interpret() -> bool:
    """Interpret the kernel unless the default backend is a real TPU (same
    policy as ``kernels.imc_eval``): TPU hosts get the Mosaic kernel with
    no flag, CPU/GPU hosts (this container, CI) run the interpreter."""
    return jax.default_backend() != "tpu"


def _next_pow2(x: int) -> int:
    n = 1
    while n < x:
        n *= 2
    return n


def _sel_vals(idx, vec, size):
    """``vec[idx]`` as a masked where-select (no multiply: +inf survives)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], size), 1)
    eq = idx[:, None] == iota
    return jnp.where(eq, vec[None, :], 0.0).sum(axis=1)


def _sel_rows(idx, mat, size):
    """``mat[idx]`` (rows) as a masked where-select."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], size), 1)
    eq = idx[:, None] == iota
    return jnp.where(eq[:, :, None], mat[None, :, :], 0.0).sum(axis=1)


def _pow_recip_eta1(x, eta):
    if eta == 3.0:
        return jnp.sqrt(jnp.sqrt(x))
    return x ** (1.0 / (eta + 1.0))


def _pow_eta1(x, eta):
    if eta == 3.0:
        x2 = x * x
        return x2 * x2
    return x ** (eta + 1.0)


def _bitonic_sort(key, idx, val, N):
    """Ascending bitonic network on unique (key, idx) int32 pairs, carrying
    ``val``.  Partner ``i ^ j`` is computed by reshape + flip — no gathers;
    the stage masks come from a traced iota (pallas kernels cannot capture
    array constants)."""
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)[0]

    def xor_swap(x, j):
        return jnp.flip(x.reshape(N // (2 * j), 2, j), axis=1).reshape(N)

    k = 2
    while k <= N:
        up = (pos & k) == 0  # ascending block mask (static stage bit)
        j = k // 2
        while j >= 1:
            is_lo = (pos & j) == 0  # bit j clear: lower partner
            kp, ip, vp = xor_swap(key, j), xor_swap(idx, j), xor_swap(val, j)
            gt = (key > kp) | ((key == kp) & (idx > ip))
            # unique pairs: my-pair < partner-pair <=> ~gt
            take = jnp.where(is_lo == up, gt, ~gt)
            key = jnp.where(take, kp, key)
            idx = jnp.where(take, ip, idx)
            val = jnp.where(take, vp, val)
            j //= 2
        k *= 2
    return key, idx, val


def _gen_kernel(
    pop_ref,  # (P, n) current population
    scores_ref,  # (1, P)
    u_ref,  # (1, TOT) this generation's uniform block
    demand_ref,  # (W, R*C*Bc) flattened demand table
    dac_ref,  # (W, C*Bc)
    spill_ref,  # (W, Gn)
    sums_ref,  # (4, W) sum_m / sum_bytes / sum_mkng / sum_mng
    grids_ref,  # (n, Gmax) grid values, zero-padded per row
    kind_ref,  # (1, 1) int32 objective kind index
    area_ref,  # (1, 1) float32 area constraint
    new_pop_ref,  # (P, n) out
    new_scores_ref,  # (1, P) out
    children_ref,  # (P, n) out (history row)
    child_scores_ref,  # (1, P) out
    *,
    tech: TechParams,
    grid_sizes: Tuple[int, ...],
    pop_size: int,
    n_genes: int,
    sbx_prob: float,
    sbx_eta: float,
    mut_eta: float,
):
    P, n = pop_size, n_genes
    mut_prob = 1.0 / n
    n_pairs = (P + 1) // 2
    n_contest = 2 * n_pairs
    o_t = 2 * n_contest
    o_u = o_t + n_pairs * n
    o_p = o_u + n_pairs
    o_g = o_p + n_pairs * n
    o_mu = o_g + P * n
    o_md = o_mu + P * n

    pop = pop_ref[...]
    scores = scores_ref[0, :]
    u = u_ref[0, :]

    # ---- binary tournament (one-hot select, never a dynamic gather)
    ti = (u[:o_t] * P).astype(jnp.int32)
    ca, cb = ti[:n_contest], ti[n_contest:o_t]
    parents = jnp.where(_sel_vals(ca, scores, P) <= _sel_vals(cb, scores, P),
                        ca, cb)
    p1 = _sel_rows(parents[:n_pairs], pop, P)
    p2 = _sel_rows(parents[n_pairs:], pop, P)

    # ---- SBX
    ub = u[o_t:o_u].reshape(n_pairs, n)
    beta = jnp.where(
        ub <= 0.5,
        _pow_recip_eta1(2.0 * ub, sbx_eta),
        _pow_recip_eta1(1.0 / (2.0 * (1.0 - ub)), sbx_eta),
    )
    c1 = 0.5 * ((1 + beta) * p1 + (1 - beta) * p2)
    c2 = 0.5 * ((1 - beta) * p1 + (1 + beta) * p2)
    do_pair = u[o_u:o_p].reshape(n_pairs, 1) < sbx_prob
    do_gene = u[o_p:o_g].reshape(n_pairs, n) < 0.5
    use = do_pair & do_gene
    c1 = jnp.clip(jnp.where(use, c1, p1), 0.0, 1.0 - 1e-7)
    c2 = jnp.clip(jnp.where(use, c2, p2), 0.0, 1.0 - 1e-7)
    children = jnp.concatenate([c1, c2], axis=0)[:P]

    # ---- polynomial mutation
    um = u[o_g:o_mu].reshape(P, n)
    lo, hi = children, 1.0 - children
    d1 = _pow_recip_eta1(
        2 * um + (1 - 2 * um) * _pow_eta1(1 - lo, mut_eta), mut_eta) - 1
    d2 = 1 - _pow_recip_eta1(
        2 * (1 - um) + (2 * um - 1) * _pow_eta1(1 - hi, mut_eta), mut_eta)
    delta = jnp.where(um <= 0.5, d1, d2)
    do = u[o_mu:o_md].reshape(P, n) < mut_prob
    children = jnp.clip(
        jnp.where(do, children + delta, children), 0.0, 1.0 - 1e-7)

    # ---- decode + grid-value lookup (one-hot; grid constants are finite)
    i_rows = space.FIELDS.index("rows")
    i_cols = space.FIELDS.index("cols")
    i_bits = space.FIELDS.index("bits_cell")
    i_glb = space.FIELDS.index("glb_mb")
    idxs, vals = [], []
    for j, nj in enumerate(grid_sizes):
        ij = jnp.clip((children[:, j] * nj).astype(jnp.int32), 0, nj - 1)
        idxs.append(ij)
        vals.append(_sel_vals(ij, grids_ref[j, :nj], nj))
    d = dict(zip(space.FIELDS, vals))

    # ---- table gathers as one-hot matmuls against the flattened tables
    R, C = grid_sizes[i_rows], grid_sizes[i_cols]
    Bc, Gn = grid_sizes[i_bits], grid_sizes[i_glb]
    ri, ci, bi, gi = idxs[i_rows], idxs[i_cols], idxs[i_bits], idxs[i_glb]
    fi = (ri * C + ci) * Bc + bi  # row-major (R, C, Bc) flat index
    iota_rcb = jax.lax.broadcasted_iota(jnp.int32, (P, R * C * Bc), 1)
    oh_rcb = (fi[:, None] == iota_rcb).astype(jnp.float32)
    demand = oh_rcb @ demand_ref[...].T  # (P, W)
    fj = ci * Bc + bi
    iota_cb = jax.lax.broadcasted_iota(jnp.int32, (P, C * Bc), 1)
    oh_cb = (fj[:, None] == iota_cb).astype(jnp.float32)
    dac_t = oh_cb @ dac_ref[...].T  # (P, W)
    iota_g = jax.lax.broadcasted_iota(jnp.int32, (P, Gn), 1)
    oh_g = (gi[:, None] == iota_g).astype(jnp.float32)
    spill = oh_g @ spill_ref[...].T  # (P, W)

    sums = sums_ref[...]
    sum_m, sum_bytes = sums[0], sums[1]
    sum_mkng, sum_mng = sums[2], sums[3]

    # ---- cost model: op-for-op imc.tables.evaluate_designs_tables
    capacity = (d["g_per_chip"] * d["t_per_router"] * d["c_per_tile"]).astype(
        jnp.float32)
    fits = demand <= capacity[:, None]

    t_cyc = d["t_cycle_ns"][:, None]
    phases = jnp.float32(tech.input_bits)
    cpw = jnp.ceil(jnp.float32(tech.weight_bits) / d["bits_cell"])[:, None]

    l_comp = sum_m[None, :] * (phases * tech.adc_share) * t_cyc
    l_comm = (sum_bytes[None, :]
              / (d["g_per_chip"][:, None] * tech.router_flit_bytes) * t_cyc)
    l_dram = spill / tech.dram_bw_bytes_per_ns
    latency = l_comp + l_comm + l_dram

    e_cell = (d["v_op"] ** 2 * tech.g_avg_s * d["t_cycle_ns"] * 1e3)[:, None]
    e_analog = sum_mkng[None, :] * phases * cpw * e_cell
    e_adc = sum_mng[None, :] * phases * cpw * tech.adc_energy_pj
    e_dac = dac_t * phases * tech.dac_energy_pj
    e_route = sum_bytes[None, :] * tech.router_energy_pj_per_byte
    e_buf = sum_bytes[None, :] * (
        tech.tile_buf_energy_pj_per_byte + tech.glb_energy_pj_per_byte)
    e_dram = spill * tech.dram_energy_pj_per_byte

    # area_mm2, inlined
    n_tiles = d["g_per_chip"] * d["t_per_router"]
    n_xbars = n_tiles * d["c_per_tile"]
    xbar = (d["rows"] * d["cols"] * tech.cell_area_mm2
            + d["rows"] * tech.driver_area_mm2_per_row
            + (d["cols"] / tech.adc_share) * tech.adc_area_mm2)
    tile_buf = tech.tile_buf_kb / 1024.0 * tech.sram_area_mm2_per_mb
    area = (n_xbars * xbar + n_tiles * tile_buf
            + d["g_per_chip"] * tech.router_area_mm2
            + d["glb_mb"] * tech.sram_area_mm2_per_mb) * 1.10

    e_leak = tech.leak_mw_per_mm2 * area[:, None] * latency
    energy = e_analog + e_adc + e_dac + e_route + e_buf + e_dram + e_leak

    # design_valid, inlined
    kv = (tech.v_nominal - tech.v_th) ** tech.alpha_power / tech.v_nominal
    t_min = kv * d["v_op"] / (d["v_op"] - tech.v_th) ** tech.alpha_power
    valid = d["t_cycle_ns"] >= t_min * (1.0 - VF_RTOL)

    # ---- indexed objective (where-chain == trailing-axis stack + gather)
    e = energy.max(axis=-1)
    l = latency.max(axis=-1)
    kind = kind_ref[0, 0]
    s = jnp.where(kind == 0, e * l * area,
                  jnp.where(kind == 1, e * l, jnp.where(kind == 2, e, l)))
    feasible = fits.all(axis=-1) & valid & (area <= area_ref[0, 0])
    child_scores = jnp.where(feasible, s, jnp.float32(jnp.inf))

    # ---- (mu + lambda) survival: bitonic network on total-order keys
    allg = jnp.concatenate([pop, children], axis=0)
    alls = jnp.concatenate([scores, child_scores], axis=0)
    bits = jax.lax.bitcast_convert_type(alls.astype(jnp.float32), jnp.int32)
    okey = jnp.where(bits < 0, -(bits & jnp.int32(0x7FFFFFFF)), bits)
    N = _next_pow2(2 * P)
    iota2p = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)[0]
    key_pad, val_pad = okey, alls
    if N > 2 * P:  # no zero-width pad when 2P is already a power of two
        key_pad = jnp.concatenate(
            [okey, jnp.full((N - 2 * P,), jnp.int32(2**31 - 1))])
        val_pad = jnp.concatenate(
            [alls, jnp.zeros((N - 2 * P,), jnp.float32)])
    _, sidx, sval = _bitonic_sort(key_pad, iota2p, val_pad, N)
    new_pop_ref[...] = _sel_rows(sidx[:P], allg, 2 * P)
    new_scores_ref[0, :] = sval[:P]
    children_ref[...] = children
    child_scores_ref[0, :] = child_scores


def ga_gen_step_pallas(
    pop: jnp.ndarray,  # (P, n)
    scores: jnp.ndarray,  # (P,)
    u: jnp.ndarray,  # (TOT,) pre-drawn uniforms
    tables,  # imc.tables.WorkloadTables (W-leading leaves)
    kind: jnp.ndarray,  # () int32
    area_constr: jnp.ndarray,  # () float32
    *,
    tech: TechParams = TECH,
    sbx_prob: float,
    sbx_eta: float,
    mut_eta: float,
    interpret: Optional[bool] = None,
):
    """One generation in one kernel launch.  Returns
    ``(new_pop, new_scores, children, child_scores)`` bit-identical to the
    fused lax generation step fed the same uniform block."""
    if interpret is None:
        interpret = default_interpret()
    P, n = pop.shape
    W = tables.demand.shape[0]
    grids = [np.asarray(space.SPACE[f], np.float32) for f in space.FIELDS]
    grid_sizes = tuple(len(g) for g in grids)
    gmax = max(grid_sizes)
    grids_pad = np.zeros((n, gmax), np.float32)
    for j, g in enumerate(grids):
        grids_pad[j, : len(g)] = g
    demand2 = tables.demand.reshape(W, -1)
    dac2 = tables.dac.reshape(W, -1)
    sums = jnp.stack(
        [tables.sum_m, tables.sum_bytes, tables.sum_mkng, tables.sum_mng])
    kernel = functools.partial(
        _gen_kernel, tech=tech, grid_sizes=grid_sizes, pop_size=P, n_genes=n,
        sbx_prob=sbx_prob, sbx_eta=sbx_eta, mut_eta=mut_eta,
    )
    out_shape = [
        jax.ShapeDtypeStruct((P, n), jnp.float32),
        jax.ShapeDtypeStruct((1, P), jnp.float32),
        jax.ShapeDtypeStruct((P, n), jnp.float32),
        jax.ShapeDtypeStruct((1, P), jnp.float32),
    ]
    new_pop, new_scores, children, child_scores = pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret,
    )(
        pop.astype(jnp.float32),
        scores.astype(jnp.float32)[None, :],
        u.astype(jnp.float32)[None, :],
        demand2, dac2, tables.spill, sums,
        jnp.asarray(grids_pad),
        kind.astype(jnp.int32).reshape(1, 1),
        area_constr.astype(jnp.float32).reshape(1, 1),
    )
    return new_pop, new_scores[0], children, child_scores[0]
