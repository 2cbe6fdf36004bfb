"""granite-4.0-h-small [hybrid]: 40L d=4096; 36 Mamba-2 mixers (128 heads
of 64, d_state 128, 1 group, conv 4, expand 2, chunk 256) and 4 GQA
attention layers without positions (32 query / 8 KV heads of 128) at
layers 5, 15, 25 and 35; in every layer 72 experts of width 768, top-10,
and a shared expert of width 1536; vocab 100,352, tied.
[hf:ibm-granite/granite-4.0-h-small config.json]

Granite's factors: embeddings x 12, each residual branch x 0.22, the
attention scores x 1/128 (``attention_multiplier``), logits / 16. The
catalog reads ``intermediate_size`` (768) as the width of one expert.
"""
from repro.config import ATTN, MAMBA, MambaConfig, ModelConfig, MoEConfig, register

ATTENTION_LAYERS = (5, 15, 25, 35)


@register("granite-4.0-h-small")
def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-small",
        family="hybrid",
        num_layers=40,
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        d_ff=0,
        vocab_size=100352,
        head_dim=128,
        tie_embeddings=True,
        norm_eps=1e-5,
        moe=MoEConfig(num_experts=72, top_k=10, d_ff_expert=768,
                      d_ff_shared=1536),
        mamba=MambaConfig(d_state=128, d_conv=4, expand=2, headdim=64,
                          chunk_size=256),
        layer_pattern=tuple(ATTN if i in ATTENTION_LAYERS else MAMBA
                            for i in range(40)),
        rope=False,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        attention_multiplier=0.0078125,
        logits_scaling=16.0,
        source="hf:ibm-granite/granite-4.0-h-small",
    )
