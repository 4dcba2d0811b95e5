"""DeepSeek-V3 671B [arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3].
Multi-head latent attention on all 61 layers (128 heads, q latent 1536, kv
latent 512, nope/rope/v head widths 128/64/128); the first 3 layers have a
dense FFN of width 18432, the other 58 are MoE: 256 routed experts top-8
(sigmoid scores, 8 groups, top-4 groups, weights x2.5) + 1 shared, expert
width 2048.  d_model 7168, vocab 129280.  The multi-token-prediction
module is a training and self-drafting head and is left out; YaRN context
scaling changes no shape and is left out."""

from repro.models.common import BlockSpec, ModelConfig

_DENSE = BlockSpec(kind="mla", moe=False)
_MOE = BlockSpec(kind="mla", moe=True)


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-671b",
        vocab_size=129280,
        d_model=7168,
        layer_pattern=(_DENSE,) * 3 + (_MOE,) * 58,
        n_periods=1,
        n_heads=128,
        n_kv_heads=128,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        d_ff=18432,
        n_experts=256,
        top_k=8,
        n_shared_experts=1,
        d_ff_expert=2048,
        router_score="sigmoid",
        n_expert_groups=8,
        topk_groups=4,
        routed_scale=2.5,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-smoke",
        vocab_size=512,
        d_model=64,
        layer_pattern=(_DENSE, _MOE),
        n_periods=1,
        n_heads=4,
        n_kv_heads=4,
        q_lora_rank=32,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        d_ff=128,
        n_experts=16,
        top_k=4,
        n_shared_experts=1,
        d_ff_expert=32,
        router_score="sigmoid",
        n_expert_groups=4,
        topk_groups=2,
        routed_scale=2.5,
        remat=False,
    )
