"""granite-4.0-h-micro [hybrid] — 40L d_model=2048: a period of 10 layers,
5 Mamba-2 + 1 attention + 4 Mamba-2 (attention at 5, 15, 25, 35), every
mixer followed by a dense SwiGLU MLP of 8192.  Mamba-2: 64 heads of 64
(d_inner 4096, expand 2), d_state 128, one group, conv 4 with bias, chunk
256.  Attention: GQA 32/8 heads of 64, no positional embedding, scores
times attention_multiplier 1/64 (query_pre_attn_scalar 4096).  Granite's
multipliers: embeddings x12, residual x0.22, logits /8; RMSNorm eps 1e-5;
tied vocab=100352.
[https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json]"""

from repro.models.registry import register
from .base import ModelConfig


@register("granite-4.0-h-micro")
def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro",
        family="hybrid",
        n_layers=40,
        d_model=2048,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab=100352,
        pattern=((("mamba2", "mlp"),) * 5 + (("attn", "mlp"),)
                 + (("mamba2", "mlp"),) * 4),
        norm="rmsnorm",
        activation="silu",
        mlp_gated=True,
        use_rope=False,
        query_pre_attn_scalar=4096.0,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        norm_eps=1e-5,
        ssm_state=128,
        ssm_conv=4,
        ssm_expand=2,
        ssm_head_dim=64,
        ssm_chunk=256,
    )
