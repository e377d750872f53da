"""internlm2-20b — dense GQA [arXiv:2403.17297].
48L d=6144 48H kv=8 ff=16384 v=92544."""
from repro.configs.base import ModelConfig

FULL = ModelConfig(
    arch_id="internlm2-20b", family="dense",
    d_model=6144, n_layers=48, n_heads=48, n_kv=8, d_ff=16384, vocab=92544,
    head_dim=128, act="swiglu", norm="rms", norm_eps=1e-5, rope_theta=1e6,
    tie_embeddings=False,
)

SMOKE = ModelConfig(
    dtype="float32",
    arch_id="internlm2-20b", family="dense",
    d_model=64, n_layers=2, n_heads=4, n_kv=2, d_ff=128, vocab=512,
    head_dim=16, act="swiglu", norm="rms", rope_theta=1e6,
    tie_embeddings=False, remat="none", loss_chunk=8,
)
