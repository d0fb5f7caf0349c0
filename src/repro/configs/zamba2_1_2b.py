"""zamba2-1.2b  [hybrid]

38L d_model=2048 vocab=32000, Mamba2 ssm_state=64 (64 heads of 64) plus
one shared attention+MLP block [arXiv:2411.15242; hf Zyphra/Zamba2-1.2B].

Every layer is a pre-norm Mamba2 layer.  At the hybrid layers 6, 12, ...,
36 the one shared block reads [hidden | embedding output] (4096 wide):
RMSNorm, 32 attention heads of 128 with RoPE at scale (128/2)^-0.5, the
output projection back to 2048, RMSNorm, a GeGLU MLP (exact GELU, 8192
wide) whose gate-up projection adds the site's own rank-128 LoRA adapter,
and the site's own 2048 x 2048 linear.  Its output is added to that
layer's Mamba input before the layer's norm, not to the residual.
"""
from repro.configs.base import ModelConfig, SSMConfig, register

CONFIG = register(
    ModelConfig(
        name="zamba2-1.2b",
        family="hybrid",
        n_layers=38,
        d_model=2048,
        n_heads=32,
        n_kv_heads=32,
        head_dim=128,
        d_ff=8192,
        vocab_size=32000,
        ssm=SSMConfig(state_dim=64, head_dim=64, expand=2,
                      conv_width=4, chunk_size=256),
        shared_attn_every=6,
        adapter_rank=128,
        tie_embeddings=True,
        act="gelu_erf",
        vocab_chunk=16000,
    ),
    reduced=ModelConfig(
        name="zamba2-1.2b",
        family="hybrid",
        n_layers=5,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        head_dim=32,
        d_ff=128,
        vocab_size=256,
        ssm=SSMConfig(state_dim=16, head_dim=16, expand=2,
                      conv_width=4, chunk_size=32),
        shared_attn_every=2,
        adapter_rank=8,
        tie_embeddings=True,
        act="gelu_erf",
    ),
)
