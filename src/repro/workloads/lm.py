"""Export LM architectures as IMC workloads (beyond-paper).

Every *weight* GEMM of a ``ModelConfig`` becomes an IMC layer descriptor
``(M, K, N, A_in, A_out, groups)`` counted as ``workloads/cnn.py`` counts
them (``A_in = M*K*groups``, ``A_out = M*N*groups``, 8-bit activations),
derived from the same config object that drives the JAX model, so the DSE
workload can never drift from the model's shapes.

What one export is: one IMC chip's share of a stated deployment, for one
serving step.

* ``mode="decode"``: a step decodes one token for each of the rank's
  ``batch`` sequences, so ``M = batch`` on attention, router, shared
  experts and dense MLPs.  ``mode="prefill"``: a step runs one
  ``chunk``-token piece of a prompt, ``M = chunk``.
* ``layers=(first, last)``: the pipeline stage, decoder layers ``first``
  to ``last`` inclusive (``None``: every layer, and an encoder-decoder's
  encoder too).
* ``ep``: the expert-parallel degree.  The tokens of the EP group in a
  step are ``T = ep * batch`` (decode) or ``ep * chunk`` (prefill); this
  chip holds ``n_experts / ep`` routed experts.  Routing is taken as
  uniform: each held expert gets the expected ``T * topk / n_experts``
  tokens, fractional and never rounded up, and its rows' ``M``, ``A_in``
  and ``A_out`` count those tokens only.  Skewed routing is not modelled.
  Shared experts (``n_shared_experts``, merged into one SwiGLU of width
  ``n_shared_experts * moe_d_ff`` as the reference code merges them),
  attention and the router take the rank's own tokens.
* ``head_share``: the slice of the vocabulary whose LM-head rows this
  chip holds (0: no head).
* ``context``: the KV cache.  ``None`` exports no cache row.  Otherwise
  one row per export, not per layer: the cache persists across steps and
  shares the chip's one GLB, so it is a descriptor with no weights,
  ``(0, 0, 0, read, written, 1)``, whose bytes the cost model charges as
  it charges activations (NoC transfer, buffer energy, DRAM spill past
  the GLB).  ``read``/``written`` are the bytes of every attention layer
  of the stage: at decode ``batch * context`` tokens read and ``batch``
  written, at prefill the ``context``-token prefix read and the chunk
  written.  A token costs ``kv_lora_rank + qk_rope_head_dim`` bytes a
  layer under MLA (the latent and the shared rope key), else
  ``2 * n_kv_heads * head_dim``.

Mapping choices:

* IMC crossbars hold *weights*; activation-activation products (attention
  scores, softmax, the score-times-value product, SSD state updates) run
  on the digital periphery and are not crossbar layers, as in the IMC
  literature.  Their MACs are not costed (ROADMAP 2.2).
* Multi-head latent attention (arXiv:2405.04434 §2.1) takes the two paths
  DeepSeek serves it by.  Decode is weight-absorbed: ``kv_b_proj`` splits
  into ``W_UK^T`` (each head's no-rope query, ``qk_nope_head_dim`` wide,
  into the latent) and ``W_UV`` (each head's latent output back to
  ``v_head_dim``), two grouped crossbar layers with ``groups = n_heads``
  that run on the rank's tokens; the cache is read in latent form.
  Prefill is not absorbed: ``kv_b_proj`` up-projects the latents of the
  prefix and the chunk, ``M = context + chunk``.  Queries come from
  ``q_proj``, or ``q_a``/``q_b`` where ``q_lora_rank`` is set.
* MoE: every held expert's weights are resident (capacity pressure, the
  IMC trade-off); only its routed tokens pass through it.
* The 4-tap causal depthwise conv of Mamba blocks stays on the periphery
  (one crossbar per channel for 4 weights each); MobileNet's wide
  depthwise convs are mapped, since they stress capacity by design.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from repro.configs.base import ModelConfig

Layer = Tuple[float, int, int, float, float, int]


def _gemm(m: float, k: int, n: int, groups: int = 1) -> Layer:
    return (m, k, n, m * k * groups, m * n * groups, groups)


def lm_workload(cfg: ModelConfig, *, mode: str = "decode",
                layers: Optional[Tuple[int, int]] = None, ep: int = 1,
                batch: int = 1, chunk: int = 1,
                context: Optional[int] = None,
                head_share: float = 1.0) -> List[Layer]:
    """One IMC chip's layer table for one serving step (module docstring)."""
    if mode not in ("decode", "prefill"):
        raise ValueError(f"mode must be decode or prefill, got {mode!r}")
    decode = mode == "decode"
    M = batch if decode else chunk
    first, last = (0, cfg.n_layers - 1) if layers is None else layers
    if not 0 <= first <= last < cfg.n_layers:
        raise ValueError(f"layers {layers} outside 0..{cfg.n_layers - 1}")
    if cfg.n_experts and cfg.n_experts % ep:
        raise ValueError(f"ep={ep} does not divide {cfg.n_experts} experts")
    head_n = cfg.vocab_size * head_share
    if head_n != int(head_n):
        raise ValueError(f"head_share {head_share} slices no whole rows "
                         f"of {cfg.vocab_size}")
    d, Dh = cfg.d_model, cfg.head_dim_
    H, KV = cfg.n_heads, cfg.n_kv_heads
    out: List[Layer] = []

    def attn_layers() -> List[Layer]:
        if not cfg.is_mla:
            return [
                _gemm(M, d, H * Dh),      # wq
                _gemm(M, d, KV * Dh),     # wk
                _gemm(M, d, KV * Dh),     # wv
                _gemm(M, H * Dh, d),      # wo
            ]
        r, nope, rope, v = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.v_head_dim)
        if cfg.q_lora_rank:
            q = [_gemm(M, d, cfg.q_lora_rank),                      # q_a_proj
                 _gemm(M, cfg.q_lora_rank, H * (nope + rope))]      # q_b_proj
        else:
            q = [_gemm(M, d, H * (nope + rope))]                    # q_proj
        q.append(_gemm(M, d, r + rope))                   # kv_a_proj_with_mqa
        if decode:  # weight-absorbed
            kv = [_gemm(M, nope, r, groups=H),            # W_UK^T
                  _gemm(M, r, v, groups=H)]               # W_UV
        else:
            kv = [_gemm((context or 0) + chunk, r, H * (nope + v))]  # kv_b_proj
        return q + kv + [_gemm(M, H * v, d)]              # o_proj

    def swiglu(m: float, width: int) -> List[Layer]:
        return [_gemm(m, d, width), _gemm(m, d, width), _gemm(m, width, d)]

    def moe_layers() -> List[Layer]:
        m_exp = ep * M * cfg.topk / cfg.n_experts  # expected routed tokens
        rows = [_gemm(M, d, cfg.n_experts)]  # router: scores every expert
        if cfg.n_shared_experts:
            rows += swiglu(M, cfg.n_shared_experts * cfg.moe_d_ff_)
        for _ in range(cfg.n_experts // ep):
            rows += swiglu(m_exp, cfg.moe_d_ff_)
        return rows

    def mamba_layers() -> List[Layer]:
        from repro.models.mamba import _dims

        d_inner, G, N, Hs, Pd, conv_ch, d_in_proj = _dims(cfg)
        return [
            _gemm(M, d, d_in_proj),  # in_proj
            _gemm(M, d_inner, d),    # out_proj
        ]

    per_layer = {
        "attn": attn_layers,
        "mamba": mamba_layers,
        "mlp": lambda: swiglu(M, cfg.d_ff),
        "moe": moe_layers,
        "none": lambda: [],
    }
    stage = cfg.layer_kinds()[first:last + 1]
    for mixer, ffn in stage:
        out += per_layer[mixer]()
        if cfg.is_encdec and mixer == "attn":
            out += attn_layers()  # cross-attention projections
        out += per_layer[ffn]()
    if cfg.is_encdec and layers is None:
        for _ in range(cfg.encoder_layers):
            out += attn_layers() + swiglu(M, cfg.d_ff)
    # LM head (embedding lookup is a table read, not a GEMM; the head is)
    if head_n:
        out.append(_gemm(M, d, int(head_n)))
    if context is not None:
        per_layer_tok = (cfg.kv_lora_rank + cfg.qk_rope_head_dim if cfg.is_mla
                         else 2 * KV * Dh)  # bytes a token caches, 8-bit
        per_tok = sum(mixer == "attn" for mixer, _ in stage) * per_layer_tok
        read, written = (batch * context, batch) if decode else (context, chunk)
        out.append((0, 0, 0, read * per_tok, written * per_tok, 1))
    return out
