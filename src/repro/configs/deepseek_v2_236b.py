"""deepseek-v2-236b [moe] — MLA (kv_lora=512, YaRN rope), 2 shared + 160
routed experts top-6 with group-limited routing (8 groups, best 3 kept,
weights x16, not renormalised), first layer dense. [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2 config.json]"""
from ..models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b", arch_type="moe",
        n_layers=60, d_model=5120, n_heads=128, n_kv_heads=128,
        d_ff=12288, vocab=102400,
        norm="rmsnorm", act="silu", mlp_glu=True, rope_theta=10_000.0,
        mla=True, kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
        n_experts=160, top_k=6, n_shared_experts=2, d_ff_expert=1536,
        n_group=8, topk_group=3, norm_topk_prob=False,
        routed_scaling_factor=16.0,
        first_dense=1,
        source="arXiv:2405.04434",
    )
