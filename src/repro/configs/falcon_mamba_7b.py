"""falcon-mamba-7b [ssm]: 64L d_model=4096, attention-free, vocab=65024,
ssm_state=16 (mamba1 arch). [arXiv:2410.05355; huggingface.co/tiiuae/falcon-mamba-7b]

Pure Mamba-1: d_inner = 2*d_model = 8192, conv4, dt_rank = d_model/16 = 256,
one RMSNorm (eps 1e-5) before each mixer and no MLP; untied output head
(7.27B parameters). Falcon-Mamba's change to Mamba-1: dt, B and C are
normalised by a weightless RMSNorm (eps 1e-6) after x_proj. As published
(``residual_in_fp32``), the residual stream is carried in float32.
Attention-free => long_500k RUNS; the decode "cache" is (conv window, SSM
state), O(1) in sequence length.
"""
from repro.configs.base import MambaConfig, ModelConfig, register

CONFIG = register(ModelConfig(
    name="falcon_mamba_7b",
    family="ssm",
    num_layers=64,
    d_model=4096,
    num_heads=0,
    num_kv_heads=0,
    d_ff=0,
    vocab_size=65024,
    mamba=MambaConfig(d_inner=8192, d_state=16, d_conv=4, dt_rank=256,
                      mixer_rms_eps=1e-6, residual_in_fp32=True),
    tie_embeddings=False,
    norm_eps=1e-5,
    grad_accum=16,   # mamba backward temporaries are f32 [B,S,DI,N]-shaped
    logits_chunk=1024,
))
