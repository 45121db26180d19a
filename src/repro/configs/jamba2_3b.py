"""AI21-Jamba2-3B — the Mamba/attention hybrid that serves behind the
cache on one chip.

[https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json]
28L, d_model=2560, 20 query heads over one KV head (head_dim 128), no
positional encoding, a gated SiLU MLP of width 8192 in every layer
(``num_experts`` 1), Mamba-1 mixers (d_state 16, d_conv 4 with bias,
expand 2, dt_rank 160, no projection bias) with Jamba's RMSNorms on
dt, B and C, RMSNorm eps 1e-6, tied 65,536-word vocabulary.

``attn_layer_period`` 14 and ``attn_layer_offset`` 7 put attention at
layers 7 and 21 under HF Jamba's rule (layer i is attention iff
i % 14 == 7), so the period is 14 layers and the model is two of them.
3,029,337,472 parameters: bf16 weights (the checkpoint's dtype) take
6.06 GB, which fits one v5e chip beside the cache's stack; f32 would
not.
"""
from repro.configs.base import (
    ATTN, DENSE, MAMBA, LayerSpec, ModelConfig, SSMConfig, register,
)

ATTN_LAYER_PERIOD, ATTN_LAYER_OFFSET = 14, 7

CONFIG = register(ModelConfig(
    name="jamba2-3b",
    family="hybrid",
    source="https://huggingface.co/ai21labs/AI21-Jamba2-3B",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    mlp_type="swiglu",
    norm_type="rmsnorm",
    norm_eps=1e-6,
    use_rope=False,
    tie_embeddings=True,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, dt_rank=160,
                  inner_norms=True),
    period=tuple(LayerSpec(ATTN if i == ATTN_LAYER_OFFSET else MAMBA, DENSE)
                 for i in range(ATTN_LAYER_PERIOD)),
    max_seq_len=262_144,
    param_dtype="bfloat16",
))
