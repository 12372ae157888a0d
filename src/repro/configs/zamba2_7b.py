"""Zamba2-7B hybrid [arXiv:2411.15242; hf:Zyphra/Zamba2-7B-Instruct config.json].

81 Mamba-2 layers (112 SSD heads of 64, state 64, 2 B/C groups, conv 4,
chunk 256). At the 13 ``hybrid_layer_ids`` one of two shared blocks
(application i runs block i mod 2) reads concat(h, embedding), 7168 wide:
attention with 32 MHA heads of 224 (rotary over the whole head, scale
(224/2)^-0.5) back to 3584, then a gated-GELU MLP of 14336 whose gate and
up projections take the application's own rank-128 adapter; the result
goes through the application's 3584 x 3584 linear and is added to the
input of that layer's Mamba block (its residual is the input before the
addition). Embeddings are tied. For the long_500k dry-run cell the shared
attention uses a 4096-token sliding window (the published context).
"""
from repro.configs.base import ModelConfig, SSMConfig, register

CONFIG = register(ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    ssm=SSMConfig(state_dim=64, head_dim=64, num_heads=112, expand=2,
                  conv_width=4, chunk_size=256, ngroups=2),
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    rope_theta=10_000.0,
    mlp_type="geglu",
    norm_eps=1e-5,
    tie_embeddings=True,
    source="hf:Zyphra/Zamba2-7B-Instruct (config.json); arXiv:2411.15242",
))
