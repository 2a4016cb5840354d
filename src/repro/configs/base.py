"""Model configuration system.

Every assigned architecture is expressed as a ``ModelConfig``.  The same config
object drives
  * the JAX model implementation (``repro.models``),
  * the IMC workload export (``repro.workloads.lm``), and
  * the dry-run / roofline launchers (``repro.launch``).

A config describes a *family* via a layer plan: a repeating period of
(mixer, ffn) sub-layer kinds.  Dense transformers have period 1 =
[("attn", "mlp")]; Jamba has period 8 with one attention layer and MoE on odd
layers; Mamba-2 is [("mamba", "none")] (the SSD block contains its own gating
MLP-equivalent), etc.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

MIXER_KINDS = ("attn", "mamba")
FFN_KINDS = ("mlp", "moe", "none")


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


# The four assigned shape cells (identical across LM archs).
TRAIN_4K = ShapeSpec("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524_288, 1, "decode")
ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # --- attention / embedding details -------------------------------------
    mlp_act: str = "silu"  # "silu" (SwiGLU) | "gelu" (GeGLU)
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rope_type: str = "rope"  # "rope" | "mrope" | "none"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    sliding_window: int = 0  # 0 = full attention
    logit_softcap: float = 0.0
    scale_embeds: bool = False  # gemma: multiply embeddings by sqrt(d_model)

    # --- MoE ----------------------------------------------------------------
    n_experts: int = 0
    topk: int = 0
    moe_every: int = 1  # MoE ffn on layers with (i % moe_every == moe_every-1)
    capacity_factor: float = 1.25
    moe_d_ff: int = 0  # expert hidden size; 0 -> d_ff
    n_shared_experts: int = 0  # always-on experts of width moe_d_ff per MoE layer
    first_k_dense: int = 0  # leading layers whose ffn is a dense MLP of width d_ff

    # --- multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1) -----
    kv_lora_rank: int = 0  # >0 -> MLA: keys and values through this latent
    q_lora_rank: int = 0  # 0 -> queries projected from the hidden state directly
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- SSM (Mamba-2 / SSD) -------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_groups: int = 1
    attn_every: int = 0  # hybrid: one attn layer per `attn_every` (jamba: 8);
    attn_offset: int = 4  # ... placed at this index within the period
    # 0 -> pure family default (all-attn for transformers, all-mamba for ssm)

    # --- encoder-decoder -----------------------------------------------------
    encoder_layers: int = 0  # >0 -> enc-dec (whisper)

    # --- VLM -----------------------------------------------------------------
    vision_tokens: int = 0  # stubbed patch-embedding prefix length (train/prefill)

    # --- source provenance ---------------------------------------------------
    source: str = ""

    # ------------------------------------------------------------------ derived
    @property
    def head_dim_(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def moe_d_ff_(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def model_stack(self) -> bool:
        """Whether the JAX model (``repro.models``) implements this config.
        Latent attention, shared experts and leading dense layers are read
        by the IMC exporter (``repro.workloads.lm``) alone."""
        return not (self.is_mla or self.n_shared_experts or self.first_k_dense)

    @property
    def supports_long_context(self) -> bool:
        """True if decode at 500k is sub-quadratic / bounded-memory.

        SSM state is O(1); hybrids attend in only 1/attn_every layers (and we
        seq-shard their cache); sliding-window attention has a bounded cache.
        Pure full-attention archs skip ``long_500k`` (recorded in DESIGN.md).
        """
        if self.family == "ssm":
            return True
        if self.family == "hybrid":
            return True
        if self.sliding_window > 0:
            return True
        return False

    def supported_shapes(self) -> List[ShapeSpec]:
        out = []
        for s in ALL_SHAPES:
            if s.name == "long_500k" and not self.supports_long_context:
                continue
            out.append(s)
        return out

    def shape_skips(self) -> List[Tuple[str, str]]:
        """(shape, reason) pairs for cells that are intentionally not run."""
        skips = []
        if not self.supports_long_context:
            skips.append(
                (
                    "long_500k",
                    "pure full-attention arch: O(S) KV cache at 524k infeasible; "
                    "needs sub-quadratic attention (see DESIGN.md §4)",
                )
            )
        return skips

    # ---------------------------------------------------------------- layer plan
    def layer_plan(self) -> List[Tuple[str, str]]:
        """The repeating (mixer, ffn) period; len divides n_layers."""
        if self.family == "ssm":
            return [("mamba", "none")]
        if self.family == "hybrid":
            assert self.attn_every > 0
            plan = []
            for i in range(self.attn_every):
                mixer = "attn" if i == self.attn_offset % self.attn_every else "mamba"
                ffn = (
                    "moe"
                    if (self.n_experts and i % self.moe_every == self.moe_every - 1)
                    else "mlp"
                )
                plan.append((mixer, ffn))
            return plan
        # dense / moe / encdec / vlm transformers
        if self.n_experts and self.moe_every == 1:
            return [("attn", "moe")]
        if self.n_experts:
            return [
                ("attn", "moe" if i % self.moe_every == self.moe_every - 1 else "mlp")
                for i in range(self.moe_every)
            ]
        return [("attn", "mlp")]

    def layer_kinds(self) -> List[Tuple[str, str]]:
        """(mixer, ffn) of every decoder layer, in order: the period
        repeated, the first ``first_k_dense`` ffns dense."""
        kinds = self.layer_plan() * self.n_blocks
        return [(m, "mlp" if i < self.first_k_dense and f == "moe" else f)
                for i, (m, f) in enumerate(kinds)]

    @property
    def period(self) -> int:
        return len(self.layer_plan())

    @property
    def n_blocks(self) -> int:
        assert self.n_layers % self.period == 0, (
            f"{self.name}: n_layers={self.n_layers} not divisible by "
            f"period={self.period}"
        )
        return self.n_layers // self.period

    # ---------------------------------------------------------------- param count
    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, hd = self.d_model, self.head_dim_
        n = 0
        n += self.vocab_size * d  # embed
        if not self.tie_embeddings:
            n += self.vocab_size * d  # lm head
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (
            self.n_heads * hd
        ) * d
        if self.qkv_bias:
            attn += (self.n_heads + 2 * self.n_kv_heads) * hd
        if self.is_mla:
            H, r = self.n_heads, self.kv_lora_rank
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            q = (d * self.q_lora_rank + self.q_lora_rank + self.q_lora_rank * H * qk
                 if self.q_lora_rank else d * H * qk)
            attn = (q + d * (r + self.qk_rope_head_dim) + r  # kv_a, its norm
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)  # kv_b
                    + H * self.v_head_dim * d)  # o_proj
        mlp = 3 * d * self.d_ff
        moe = ((self.n_experts + self.n_shared_experts) * 3 * d * self.moe_d_ff_
               + d * self.n_experts)
        di, ns = self.d_inner, self.ssm_state
        mamba = (
            d * (2 * di + 2 * self.ssm_groups * ns + self.ssm_heads)  # in_proj
            + self.ssm_conv * (di + 2 * self.ssm_groups * ns)  # conv
            + 3 * self.ssm_heads  # A, D, dt_bias
            + di * d  # out_proj
        )
        per_layer = {"attn": attn, "mamba": mamba, "mlp": mlp, "moe": moe, "none": 0}
        for mixer, ffn in self.layer_kinds():
            n += per_layer[mixer] + per_layer[ffn] + 2 * d
        if self.is_encdec:
            # encoder self-attn+mlp plus decoder cross-attn
            n += self.encoder_layers * (attn + mlp + 2 * d)
            n += self.n_layers * (attn + d)  # cross-attn per decoder layer
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if not self.n_experts:
            return self.param_count()
        full_moe = self.n_experts * 3 * self.d_model * self.moe_d_ff_
        act_moe = self.topk * 3 * self.d_model * self.moe_d_ff_
        n_moe_layers = sum(1 for _, f in self.layer_kinds() if f == "moe")
        return self.param_count() - n_moe_layers * (full_moe - act_moe)

    # ---------------------------------------------------------------- reduction
    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small = dict(
            n_layers=self.period * 2,
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            head_dim=16,
            d_ff=128,
            vocab_size=256,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            topk=min(self.topk, 2) if self.topk else 0,
            moe_d_ff=64 if self.n_experts else 0,
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
            encoder_layers=2 if self.encoder_layers else 0,
            vision_tokens=8 if self.vision_tokens else 0,
            name=self.name + "-smoke",
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)


_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> List[str]:
    """The registered configs the JAX model stack runs (``model_stack``);
    ``get_config`` also finds those only the IMC exporter reads."""
    if not _REGISTRY:
        _load_all()
    return sorted(n for n, c in _REGISTRY.items() if c.model_stack)


def _load_all() -> None:
    # importing the modules triggers register()
    from repro.configs import (  # noqa: F401
        yi_9b,
        gemma_7b,
        qwen2_72b,
        llama32_1b,
        mamba2_780m,
        qwen2_vl_2b,
        whisper_medium,
        jamba_52b,
        mixtral_8x7b,
        qwen3_moe_235b,
        deepseek_v2_lite,
    )
