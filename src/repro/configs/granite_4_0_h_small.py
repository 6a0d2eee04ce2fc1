"""granite-4.0-h-small [hybrid] — Mamba-2 + NoPE GQA, 72-expert MoE with
a shared expert (IBM Granite 4.0-H Small, 32B-A9B).

40L d_model=4096 [hf:ibm-granite/granite-4.0-h-small config.json,
``model_type: granitemoehybrid``]. ``layer_types`` puts attention at 5, 15,
25, 35: a 10-layer period of 9 Mamba-2 layers and one attention layer at
index 5. Mamba-2: 128 heads of 64 (expand 2), d_state 128, 1 group, conv
4 with bias, chunk 256. Attention: 32 query heads and 8 KV heads of 128,
no positional encoding (``position_embedding_type: nope``), score scale
``attention_multiplier`` 0.0078125. Every layer: 72 experts of 768, top-10
(softmax over the top-k logits, i.e. renormalized top-k probabilities),
plus a SwiGLU shared expert of 1536. ``embedding_multiplier`` 12,
``residual_multiplier`` 0.22 on both branches, logits divided by
``logits_scaling`` 16; tied embeddings, vocab 100,352, rms eps 1e-5.
The Mamba-2 state is carried in float32 (``mamba2.STATE_DTYPE``): the
recurrence accumulates over every position.
"""

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    d_ff_expert=768,
    d_ff_shared=1536,
    n_experts=72,
    moe_top_k=10,
    moe_every=1,
    vocab_size=100352,
    rope_theta=None,
    attn_scale=0.0078125,
    hybrid_period=10,
    hybrid_attn_index=5,
    ssm_kind="mamba2",
    ssm_d_state=128,
    ssm_d_conv=4,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_n_groups=1,
    ssm_chunk=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_embeddings=True,
    norm_eps=1e-5,
    capacity_factor=1.5,
    remat="dots",
    source="hf:ibm-granite/granite-4.0-h-small config.json",
)
