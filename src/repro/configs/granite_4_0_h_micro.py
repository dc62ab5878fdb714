"""granite-4.0-h-micro: 40 layers, 36 Mamba2 mixers and 4 GQA attention
layers (5, 15, 25, 35) without position embeddings, a SwiGLU MLP in
every layer, muP multipliers, tied embeddings.
[hf:ibm-granite/granite-4.0-h-micro, config.json, granitemoehybrid]
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro", family="interleaved",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab=100352, head_dim=64,
    ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_conv=4, ssm_ngroups=1,
    ssm_chunk=256,
    layer_types=tuple("attention" if i % 10 == 5 else "mamba"
                      for i in range(40)),
    pos_embed="none", norm_eps=1e-5, tie_embeddings=True,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8.0,
)
