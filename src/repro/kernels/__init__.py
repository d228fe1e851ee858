"""Pallas TPU kernels for the compute hot-spots LP exercises:

  dit_attention   — the DiT's self- and cross-attention on a TPU (bf16
                    MXU operands, f32 softmax state, no masks); the
                    largest device time of every denoise step.  It takes
                    q/k/v as the projections produce them: ragged tails
                    are read in place, not padded, and optional
                    rotate-half RoPE tables rotate q and k in the
                    ``dit_rope`` pass (bf16 in and out, q's 1/sqrt(D)
                    folded in).  Where Pallas is interpreted or a GSPMD
                    activation context is active, ``models/dit`` falls
                    back to ``dit.apply_rope`` and the chunked jnp scan
                    (``models/attention.attention_chunked``)
  flash_attention — the LM attention inner loop (MXU-tiled online
                    softmax, GQA, causal/SWA masks)
  latent_blend    — LP's position-aware reconstruction (Eqs. 15-17) in a
                    single fused pass
  guidance_update — CFG combine + scheduler step epilogue, fused
  mamba_ssd       — chunked SSD scan with VMEM-resident recurrent state
                    (the zamba2 hybrid's dominant traffic, §Perf A4)

Each ships with a pure-jnp oracle in ``ref.py`` and a jit'd wrapper in
``ops.py``; tests sweep shapes/dtypes in interpret mode (CPU container;
TPU v5e is the lowering target).
"""
from . import ops, ref  # noqa: F401
