"""Pallas TPU kernel: blockwise online-softmax attention (GQA, causal, SWA).

Tiling (HW-codesign for the MXU + VMEM hierarchy):

  * grid = (B, H, Sq/TQ, Skv/TK); the KV axis is the innermost
    ("arbitrary") dim — the (m, l, acc) online-softmax state lives in VMEM
    scratch and persists across KV steps of one (b, h, q-tile),
  * q/k/v blocks are (TQ, D) / (TK, D) MXU-aligned tiles (TQ = TK = 128,
    D padded to a multiple of 128 by the wrapper),
  * GQA is pure indexing: the kv BlockSpec maps query head h to kv head
    h // (H // KV) — no repeat/copy of K/V in HBM or VMEM,
  * causal/window masking is computed from block-relative iotas; fully
    masked KV blocks still iterate (grid is static) but their contribution
    is exp(-inf) = 0.

The output block writes once, on the last KV step: out = acc / l.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(
    q_ref,  # (1, 1, TQ, D)
    k_ref,  # (1, 1, TK, D)
    v_ref,  # (1, 1, TK, D)
    o_ref,  # (1, 1, TQ, D)
    m_ref,  # VMEM (TQ, 128) running max
    l_ref,  # VMEM (TQ, 128) running sum-exp
    acc_ref,  # VMEM (TQ, D) weighted accumulator
    *,
    scale: float,
    causal: bool,
    window: int,
    n_kv: int,
    block_q: int,
    block_k: int,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (TQ, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (TK, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (TQ, TK)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    kv_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = jnp.ones_like(s, jnp.bool_)
    if causal:
        mask &= q_pos >= kv_pos
    if window > 0:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]  # (TQ, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)  # (TQ, TK)
    corr = jnp.exp(m_prev - m_new)  # (TQ, 1)
    l_new = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (TQ, D)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv - 1)
    def _done():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, H, Sq, D)
    k: jnp.ndarray,  # (B, KV, Skv, D)
    v: jnp.ndarray,  # (B, KV, Skv, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
    scale: float | None = None,
) -> jnp.ndarray:
    """Head-major layouts; wrapper in ops.py does transposes/padding."""
    B, H, Sq, D = q.shape
    _, KV, Skv, _ = k.shape
    assert H % KV == 0
    g = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    n_kv = Skv // block_k
    grid = (B, H, Sq // block_q, n_kv)

    kernel = functools.partial(
        _attn_kernel,
        scale=D ** -0.5 if scale is None else scale,
        causal=causal,
        window=window,
        n_kv=n_kv,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
