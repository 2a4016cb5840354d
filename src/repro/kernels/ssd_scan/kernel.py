"""Pallas TPU kernel: Mamba-2 SSD chunked scan (single-group, per-head).

Tiling (HW-codesign): the SSD chunk algorithm maps onto the MXU as three
(Q x Q)/(Q x N)/(N x P) matmuls per chunk with a tiny sequential state
carry — exactly the structure TPUs like: big systolic contractions inside
a chunk, one (N, P) VMEM-resident state across chunks.

  * grid = (B*H, S/Q); the chunk axis is the innermost ("arbitrary") dim,
    the (N, P) state persists in VMEM scratch across chunk steps,
  * per chunk and head:   scores = C @ B^T          (Q x N @ N x Q -> MXU)
                          y_intra = (M * scores) @ (x * dt)
                          y_inter = exp(cum) * (C @ h)
                          h       = exp(total) * h + (B * w dt)^T @ x
    with M the causal intra-chunk decay matrix from cumulative log-decay,
  * B/C inputs are group-shared (G=1, mamba2/jamba): their BlockSpec maps
    (b*H + h) -> b — no repeat in HBM,
  * the final state is written once on the last chunk (decode handoff).

Q (chunk) = 128 rows, N (state) = lane-padded to 128; P (head dim) = 64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # (1, Q, P)  head inputs
    dt_ref,  # (1, Q, 1)  per-head step sizes (softplus'd)
    a_ref,  # (1, 1, 1)   per-head decay A (negative)
    b_ref,  # (1, Q, N)
    c_ref,  # (1, Q, N)
    y_ref,  # (1, Q, P)
    hout_ref,  # (1, N, P) final state (written at last chunk)
    h_ref,  # VMEM (N, P) carried state
    *,
    n_chunks: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)  # (Q, 1)
    A = a_ref[0, 0, 0].astype(jnp.float32)  # scalar
    Bm = b_ref[0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)  # (Q, N)

    a = dt * A  # (Q, 1) log-decay per step (<= 0)
    cum = jnp.cumsum(a, axis=0)  # (Q, 1) inclusive
    total = cum[-1:, :]  # (1, 1)

    # intra-chunk: M[i, j] = exp(cum_i - cum_j) for j <= i
    diff = cum - cum.T  # (Q, Q)
    Q = diff.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    M = jnp.exp(jnp.where(ii >= jj, diff, -jnp.inf))
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q) C_i . B_j
    xdt = x * dt  # (Q, P)
    y_intra = jax.lax.dot_general(
        M * scores, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, P)

    # inter-chunk: y_inter = exp(cum) * (C @ h_in)
    h_in = h_ref[...]
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        Cm, h_in, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(total) * h_in + (B * (w * dt))^T @ x
    w = jnp.exp(total - cum)  # (Q, 1)
    S_c = jax.lax.dot_general(
        Bm * (w * dt), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (N, P)
    h_ref[...] = jnp.exp(total) * h_in + S_c

    @pl.when(ci == n_chunks - 1)
    def _done():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssd_scan_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) post-softplus
    A: jnp.ndarray,  # (H,) negative decay
    Bm: jnp.ndarray,  # (B, S, N)  single group
    Cm: jnp.ndarray,  # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = True,
):
    """Returns (y (B,S,H,P), final state (B,H,N,P)).  G=1 layout."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    BH = B * H
    xh = x.transpose(0, 2, 1, 3).reshape(BH, S, P)
    dth = dt.transpose(0, 2, 1).reshape(BH, S, 1)
    ah = jnp.broadcast_to(A[None, :], (B, H)).reshape(BH, 1, 1)

    grid = (BH, nc)
    y, hfin = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, c, H=H: (i // H, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, c, H=H: (i // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, N, P), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xh, dth, ah, Bm, Cm)

    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    hfin = hfin.reshape(B, H, N, P)
    return y, hfin
