"""DeepSeek-V3 — multi-head latent attention, 3 leading dense layers, then
256 routed experts of 2048 (top-8, sigmoid scores, 8 groups with the top
4 kept, one ungated shared expert) [hf:deepseek-ai/DeepSeek-V3
config.json].  The multi-token-prediction layer is not modelled: it
serves speculative decoding, not the next token's logits."""
from repro.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v3", arch_type="moe",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128,
    d_ff=18432, vocab=129280,
    lead_pattern=("mla_dense",) * 3, block_pattern=("mla",),
    rope_theta=10000.0,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    moe=MoEConfig(n_experts=256, top_k=8, d_ff_expert=2048,
                  n_shared_experts=1, d_ff_shared=2048, shared_gated=False,
                  scoring="sigmoid", n_group=8, topk_group=4,
                  routed_scaling=2.5),
    supports_long_context=False,
    long_context_note="163840 positions published; served here to 2048",
    source="hf:deepseek-ai/DeepSeek-V3",
))
