"""DeepSeek-V2-Lite — MLA, 64 routed experts top-6 plus 2 shared, one
leading dense layer.  [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]

Exported as IMC workloads only (``repro.workloads.lm``): the JAX model
stack implements neither latent attention nor shared experts, so
``list_configs`` leaves it out and ``get_config`` finds it.
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,  # intermediate_size: the dense layer 0
    vocab_size=102400,
    n_experts=64,
    topk=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    first_k_dense=1,
    kv_lora_rank=512,
    q_lora_rank=0,  # q_lora_rank: null
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    norm_eps=1e-6,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
))
