"""hunyuanvideo-dit [vdm]: HunyuanVideo's MMDiT (arXiv:2412.03603; release
``tencent/HunyuanVideo``, config ``HYVideo-T/2-cfgdistill``): a 2-block
token refiner over 256 LLM text tokens of width 4096, 20 double-stream
and 40 single-stream blocks, d 3072, 24 heads of 128, MLP ratio 4
(GELU-tanh), q/k RMSNorm, 3D RoPE (16, 56, 56) with theta 256 on the
video tokens, a pooled CLIP-L vector of 768, embedded guidance,
patchify (1, 2, 2) over 16 latent channels (VAE stride 4x8x8)."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="hunyuanvideo-dit",
    family="vdm",
    num_layers=60,
    double_layers=20,
    single_layers=40,
    refiner_layers=2,
    d_model=3072,
    num_heads=24,
    num_kv_heads=24,
    d_ff=12288,
    vocab_size=0,
    head_dim=128,
    latent_channels=16,
    patch_sizes=(1, 2, 2),
    context_len=256,
    context_dim=4096,       # LLaVA-LLaMA-3-8B hidden states
    pooled_dim=768,         # CLIP-L pooled output
    rope_axes=(16, 56, 56),
    rope_theta=256.0,
    guidance_embed=True,
)
