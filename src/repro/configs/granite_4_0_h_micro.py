"""granite-4.0-h-micro [mamba_attn] — 40L d_model=2048: 36 Mamba-2 mixers
(64 heads x 64, d_state 128, 1 group, conv 4 with bias, chunk 256) and 4
GQA attention layers (32/8 heads, head_dim 64, no position embedding,
softmax scale 1/64) in four periods of [5 M, A, 4 M]; a SwiGLU MLP of
8192 after every mixer; muP: embeddings x12, sublayer outputs x0.22,
logits /8; tied vocab 100352.
[huggingface.co/ibm-granite/granite-4.0-h-micro config.json]"""
from repro.models.config import ModelConfig

FULL = ModelConfig(
    name="granite-4.0-h-micro", family="mamba_attn",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192,
    vocab_size=100352, head_dim=64, rope_theta=0.0,
    mlp_type="swiglu", norm_type="rms", norm_eps=1e-5, tie_embeddings=True,
    ssm_state=128, ssm_head_dim=64, expand=2, d_conv=4, ssm_chunk=256,
    ssm_groups=1, conv_bias=True, layer_pattern="MMMMMAMMMM",
    attention_multiplier=0.015625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0,
)

SMOKE = FULL.with_(
    n_layers=20, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab_size=512, head_dim=16, ssm_state=16, ssm_head_dim=16,
    ssm_chunk=16, attention_multiplier=0.25, remat="none",
)
